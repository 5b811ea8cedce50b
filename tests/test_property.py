"""Hypothesis property tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="pip install -r requirements-dev.txt")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import (RING32, Parties, msb_extract, mul, reconstruct,
                        reconstruct_bits, share, truncate)
from repro.core.rss import RSS

SET = settings(max_examples=25, deadline=None)


@given(st.lists(st.integers(-2**30, 2**30 - 1), min_size=1, max_size=32),
       st.integers(0, 2**31))
@SET
def test_ring_share_roundtrip_exact(vals, seed):
    ring = RING32
    v = ring.encode_int(jnp.asarray(vals, jnp.int32))
    xs = share(v, jax.random.PRNGKey(seed), ring, encoded=True)
    assert np.array_equal(np.asarray(reconstruct(xs, decode=False)),
                          np.asarray(v))


@given(st.lists(st.floats(-30, 30, allow_nan=False), min_size=1,
                max_size=16), st.integers(0, 1000))
@SET
def test_fixed_point_roundtrip(vals, seed):
    ring = RING32
    x = jnp.asarray(vals, jnp.float32)
    xs = share(x, jax.random.PRNGKey(seed), ring)
    assert np.abs(np.asarray(reconstruct(xs))
                  - np.asarray(x)).max() <= 2.0 ** -ring.frac + 1e-6


@given(st.lists(st.floats(-28, 28, allow_nan=False), min_size=1,
                max_size=16), st.integers(0, 1000))
@SET
def test_truncate_error_bound(vals, seed):
    """Exact-trunc invariant: error ≤ 4 ulp, never the 2^{l-f} wrap."""
    ring = RING32
    parties = Parties.setup(jax.random.PRNGKey(seed + 1))
    x = jnp.asarray(vals, jnp.float32)
    xs = share(x, jax.random.PRNGKey(seed), ring)
    lifted = RSS(xs.shares << jnp.asarray(ring.frac, ring.dtype), ring)
    got = np.asarray(reconstruct(truncate(lifted, parties)))
    assert np.abs(got - np.asarray(x)).max() <= 5 * 2.0 ** -ring.frac


@given(st.lists(st.floats(-31, 31, allow_nan=False), min_size=1,
                max_size=32), st.integers(0, 1000))
@SET
def test_msb_matches_sign(vals, seed):
    ring = RING32
    parties = Parties.setup(jax.random.PRNGKey(seed + 1))
    x = jnp.asarray(vals, jnp.float32)
    m = msb_extract(share(x, jax.random.PRNGKey(seed), ring), parties)
    enc = np.asarray(ring.encode(x)).astype(np.uint32)
    want = (enc >> 31).astype(np.uint8)
    assert np.array_equal(np.asarray(reconstruct_bits(m)), want)


@given(st.lists(st.floats(-4, 4, allow_nan=False), min_size=2, max_size=12),
       st.integers(0, 500))
@SET
def test_mul_linearity(vals, seed):
    """(x+y)·z == x·z + y·z under the protocol (distributivity survives
    sharing, masking, reshare and truncation up to ulp error)."""
    ring = RING32
    parties = Parties.setup(jax.random.PRNGKey(seed + 1))
    n = len(vals) // 2
    if n == 0:
        return
    x = jnp.asarray(vals[:n], jnp.float32)
    y = jnp.asarray(vals[n:2 * n], jnp.float32)
    z = jnp.asarray(vals[:n][::-1], jnp.float32)
    kx, ky, kz = (jax.random.PRNGKey(seed + i) for i in range(3))
    xs, ys, zs = share(x, kx, ring), share(y, ky, ring), share(z, kz, ring)
    lhs = reconstruct(truncate(mul(xs + ys, zs, parties), parties))
    r1 = truncate(mul(xs, zs, parties), parties)
    r2 = truncate(mul(ys, zs, parties), parties)
    rhs = reconstruct(r1 + r2)
    assert np.abs(np.asarray(lhs) - np.asarray(rhs)).max() < 4e-3


@given(st.integers(0, 10**6))
@SET
def test_zero_share_invariant(seed):
    parties = Parties.setup(jax.random.PRNGKey(seed))
    a = parties.zero_shares((7,), RING32)
    assert np.array_equal(np.asarray(a.sum(0)),
                          np.zeros(7, RING32.np_dtype()))


# ---------------------------------------------------------------------------
# Attention-path substrate (DESIGN.md §16): fixed-point error vs plaintext
# stays bounded across random shapes, scales and ring widths
# ---------------------------------------------------------------------------
from contextlib import nullcontext  # noqa: E402

from repro.core import RING64  # noqa: E402
from repro.core.norm import secure_rmsnorm  # noqa: E402
from repro.core.softmax import (relu_attention_scores,  # noqa: E402
                                secure_softmax)

ring_widths = st.sampled_from([RING32, RING64])


def _ring_ctx(ring):
    """RING64 needs 64-bit lanes; scope x64 so the suite stays 32-bit."""
    return jax.enable_x64(True) if ring.bits == 64 else nullcontext()


def _bound_bits(ring):
    """MSB envelope |x_enc| < 2^bound_bits: the default 18 covers RING32's
    f=12 activations; RING64 at f=20 needs frac+6 for the same magnitude."""
    return 18 if ring.bits == 32 else ring.frac + 6


@given(st.integers(1, 3), st.integers(2, 8), st.floats(0.25, 4),
       st.integers(0, 10**6), ring_widths)
@SET
def test_secure_softmax_bounded(rows, last, scale, seed, ring):
    with _ring_ctx(ring):
        rng = np.random.default_rng(seed)
        x = (rng.uniform(-1, 1, (rows, last)) * scale).astype(np.float32)
        parties = Parties.setup(jax.random.PRNGKey(seed + 1))
        xs = share(jnp.asarray(x), jax.random.PRNGKey(seed), ring)
        got = np.asarray(reconstruct(
            secure_softmax(xs, parties, bound_bits=_bound_bits(ring))))
    e = np.exp(x - x.max(-1, keepdims=True))
    want = e / e.sum(-1, keepdims=True)
    assert np.abs(got - want).max() < 0.02, (x.shape, scale)
    assert np.abs(got.sum(-1) - 1).max() < 0.02  # rows stay normalised


@given(st.integers(1, 2), st.integers(1, 4), st.integers(2, 8),
       st.floats(0.25, 4), st.integers(0, 10**6), ring_widths)
@SET
def test_relu_attention_bounded(h, q, s, scale, seed, ring):
    with _ring_ctx(ring):
        rng = np.random.default_rng(seed)
        x = (rng.uniform(-1, 1, (h, q, s)) * scale).astype(np.float32)
        parties = Parties.setup(jax.random.PRNGKey(seed + 1))
        xs = share(jnp.asarray(x), jax.random.PRNGKey(seed), ring)
        got = np.asarray(reconstruct(relu_attention_scores(
            xs, s, parties, bound_bits=_bound_bits(ring))))
    want = np.maximum(x, 0) / s
    assert np.abs(got - want).max() < 8 * 2.0 ** -ring.frac, (x.shape, s)


@given(st.integers(1, 3), st.sampled_from([8, 16, 32]),
       st.floats(0.3, 2.0), st.integers(0, 10**6), ring_widths)
@SET
def test_secure_rmsnorm_bounded(n, d, scale, seed, ring):
    from hypothesis import assume
    rng = np.random.default_rng(seed)
    x = rng.normal(0, scale, (n, d)).astype(np.float32)
    ms = (x * x).mean(-1)
    # the Newton-rsqrt envelope RMSNorm operands land in by construction
    assume(0.05 < ms.min() and ms.max() < 8)
    g = rng.uniform(0.5, 1.5, (d,)).astype(np.float32)
    with _ring_ctx(ring):
        parties = Parties.setup(jax.random.PRNGKey(seed + 1))
        xs = share(jnp.asarray(x), jax.random.PRNGKey(seed), ring)
        gs = share(jnp.asarray(g), jax.random.PRNGKey(seed + 2), ring)
        got = np.asarray(reconstruct(secure_rmsnorm(xs, gs, parties)))
    want = x / np.sqrt(ms[:, None] + 1e-5) * g
    assert np.abs(got - want).max() < 0.02, (n, d, scale)


@given(st.lists(st.integers(-16, 16), min_size=1, max_size=24),
       st.integers(0, 10**6), ring_widths)
@SET
def test_msb_sign_at_truncation_boundary(ks, seed, ring):
    """Sign/MSB extraction is EXACT even a few ulp from zero — the regime
    truncation noise would flip a naive comparison."""
    with _ring_ctx(ring):
        x = jnp.asarray(np.asarray(ks, np.float64) * 2.0 ** -ring.frac,
                        jnp.float32)
        parties = Parties.setup(jax.random.PRNGKey(seed + 1))
        bits = np.asarray(reconstruct_bits(
            msb_extract(share(x, jax.random.PRNGKey(seed), ring), parties)))
        enc = np.asarray(ring.encode(x))
    want = (enc >> (ring.bits - 1)).astype(bits.dtype)
    assert np.array_equal(bits, want), (ks, bits, want)
