"""Fused RSS linear engine (ISSUE 2): one Pallas kernel for all three
parties, cached weight limbs, fused-round inference by default."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RING32, Parties, share
from repro.core import linear
from repro.core.linear import set_fused_rounds
from repro.core.secure_model import (compile_secure, secure_infer,
                                     secure_infer_cost)
from repro.kernels.bin_rss_matmul import (bin_grouped_matmul_parts,
                                          bin_grouped_matmul_ref,
                                          bin_rss_matmul, bin_rss_matmul_ref,
                                          public_grouped_limbs,
                                          public_weight_limbs)
from repro.kernels.limbs import count_decompositions
from repro.kernels.lowering import resolve_interpret
from repro.kernels.ops import rss_matmul_dot
from repro.kernels.rss_matmul import (precompute_weight_limbs, rss_matmul,
                                      rss_matmul_parts, rss_matmul_parts_ref)
from test_secure_model import _grid_input, _random_net_params


@pytest.mark.parametrize("m,k,n", [
    (128, 128, 128), (256, 128, 384), (64, 96, 32), (33, 17, 5), (1, 128, 1),
])
def test_rss_matmul_kernel_exact(m, k, n):
    """Kernel == reference == RSS identity, bit-exact mod 2^32."""
    key = jax.random.PRNGKey(m + 7 * k + 13 * n)
    xs = jax.random.bits(key, (3, m, k), jnp.uint32)
    ws = jax.random.bits(jax.random.fold_in(key, 1), (3, k, n), jnp.uint32)
    wl = precompute_weight_limbs(ws)
    got = np.asarray(rss_matmul_parts(xs, wl, min_dim=1))
    ref = np.asarray(rss_matmul_parts_ref(xs, wl))
    assert np.array_equal(got, ref)
    # Σ_i z_i == (Σ x_i)(Σ w_i) mod 2^32 — the Araki multiplication identity
    tot = (got[0] + got[1] + got[2]).astype(np.uint32)
    want = np.asarray(jax.lax.dot_general(
        xs.sum(0), ws.sum(0), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.uint32))
    assert np.array_equal(tot, want)


_BLOCKS = [(128, 128, 128), (256, 128, 128), (128, 256, 128),
           (128, 128, 256)]


@pytest.mark.parametrize(
    "family,bm,bn,bk",
    [("rss_matmul",) + b for b in _BLOCKS]
    + [("bin_rss_matmul",) + b for b in _BLOCKS]
    + [("bin_grouped_matmul", bm, None, None) for bm in (128, 256)])
def test_kernel_exact_at_block_shape(family, bm, bn, bk):
    """Each kernel gives the jnp reference's integers at block shapes
    other than its fixed one: one block or two along each axis of 256³
    operands (the grouped kernel: along M, 4 channels of 3×3 taps)."""
    key = jax.random.PRNGKey(bm + 3 * (bn or 0) + 7 * (bk or 0))
    kx, kw = jax.random.split(key)
    if family == "bin_grouped_matmul":
        xs = jax.random.bits(kx, (3, 4, 256, 9), jnp.uint32)
        w = public_grouped_limbs(jax.random.bits(kw, (4, 9, 1), jnp.uint32))
        got = bin_grouped_matmul_parts(xs, w, bm=bm)
        want = bin_grouped_matmul_ref(xs, w)
    else:
        xs = jax.random.bits(kx, (3, 256, 256), jnp.uint32)
        wbits = jax.random.bits(kw, (3, 256, 256), jnp.uint32)
        if family == "rss_matmul":
            w = precompute_weight_limbs(wbits)
            got = rss_matmul(xs, w, bm=bm, bn=bn, bk=bk)
            want = rss_matmul_parts_ref(xs, w)
        else:
            w = public_weight_limbs(wbits[0])
            got = bin_rss_matmul(xs, w, bm=bm, bn=bn, bk=bk)
            want = bin_rss_matmul_ref(xs, w)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_resolve_interpret_platform_default():
    """Interpret-vs-compiled defaults are platform-aware — compiled on
    TPU, interpret elsewhere; explicit wins always."""
    on_tpu = jax.default_backend() == "tpu"
    assert resolve_interpret(None) == (not on_tpu)
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


def test_shared_limb_decomposition_counts(parties):
    """Acceptance pin: the cached-limb kernel path decomposes ≤ 2 slabs per
    secure matmul online (1: the activation stack; x_{i+1} limbs are a roll)
    vs 12 for the naive per-dot ring_matmul route (6 dots × 2 operands).

    Counted at trace time (jax.eval_shape) with an unjitted per-dot impl —
    an inner jit cache would hide the naive path's repeated decompositions
    (which still all execute at runtime, once per dot)."""
    from repro.kernels.ring_matmul import ring_matmul_impl

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (128, 128))
    b = jax.random.normal(jax.random.fold_in(key, 1), (128, 128))
    xs = share(a, key, RING32)
    ws = share(b, jax.random.fold_in(key, 2), RING32)
    wl = precompute_weight_limbs(ws.shares)  # setup-time, not per-query

    with count_decompositions() as naive:
        jax.eval_shape(
            lambda x, w: linear.matmul(x, w, parties, dot=ring_matmul_impl),
            xs, ws)
    jax.clear_caches()  # the fused path's decomposition sits inside a jit
    with count_decompositions() as fused:
        jax.eval_shape(lambda x: linear.matmul(x, None, parties, w_limbs=wl),
                       xs)
    assert naive[0] == 12, naive[0]
    assert fused[0] <= 2, fused[0]

    # and the cached-weight setup itself is 2 decompositions (w, w-fused)
    with count_decompositions() as setup:
        jax.eval_shape(precompute_weight_limbs, ws.shares)
    assert setup[0] == 2, setup[0]


@pytest.mark.parametrize("net,shape", [
    ("MnistNet1", (28, 28, 1)),   # fc net
    ("MnistNet2", (28, 28, 1)),   # conv net
])
def test_kernel_secure_inference_bit_identical(net, shape):
    """use_kernel_dot=True must reconstruct BIT-identically to the reference
    _ring_dot path: both are exact mod-2^32, and the protocol randomness
    (PRF counters) advances identically.

    Batch 8 so every fc layer clears rss_matmul_parts' min_dim=8 and the
    Pallas kernel (not the small-shape fallback) actually runs."""
    params = _random_net_params(net)
    x = _grid_input((8,) + shape)

    def run(use_kernel):
        model = compile_secure(params, net, jax.random.PRNGKey(2), RING32,
                               use_kernel_dot=use_kernel)
        return np.asarray(secure_infer(
            model, share(x, jax.random.PRNGKey(4), RING32),
            Parties.setup(jax.random.PRNGKey(3))))

    ref, ker = run(False), run(True)
    assert np.array_equal(ref, ker)


def test_kernel_model_caches_weight_limbs():
    params = _random_net_params("MnistNet2")
    model = compile_secure(params, "MnistNet2", jax.random.PRNGKey(0), RING32,
                           use_kernel_dot=True)
    assert model.use_kernel
    lin_ops = [op for op in model.ops if op["op"] in ("conv", "fc")]
    assert lin_ops and all(op["wlimbs"][0] is not None for op in lin_ops)
    # fused operand cached too: wf == w_i + w_{i+1}
    wl = lin_ops[0]["wlimbs"][0]
    assert np.array_equal(np.asarray(wl.wf),
                          np.asarray(wl.ws + jnp.roll(wl.ws, -1, axis=0)))


@pytest.mark.parametrize("net", ["MnistNet1", "MnistNet3", "MnistNet4"])
def test_fused_rounds_ledger(net):
    """Acceptance pin: the fused default spends ≥ ~40% fewer online rounds
    than the paper-faithful structure, and never more bytes."""
    params = _random_net_params(net)
    model = compile_secure(params, net, jax.random.PRNGKey(0), RING32)
    led_fused = secure_infer_cost(model, (1, 28, 28, 1))
    try:
        set_fused_rounds(False)
        led_paper = secure_infer_cost(model, (1, 28, 28, 1))
    finally:
        set_fused_rounds(True)
    assert led_fused.rounds <= 0.6 * led_paper.rounds, \
        (led_fused.rounds, led_paper.rounds)
    assert led_fused.nbytes <= led_paper.nbytes


def test_fused_matches_paper_faithful_values():
    """Round fusion must not change computed values beyond trunc ulp noise."""
    net = "MnistNet3"
    params = _random_net_params(net)
    x = _grid_input((2, 28, 28, 1))

    def run():
        model = compile_secure(params, net, jax.random.PRNGKey(2), RING32)
        return np.asarray(secure_infer(
            model, share(x, jax.random.PRNGKey(4), RING32),
            Parties.setup(jax.random.PRNGKey(3))))

    fused = run()
    try:
        set_fused_rounds(False)
        paper = run()
    finally:
        set_fused_rounds(True)
    assert np.abs(fused - paper).max() < 0.05
