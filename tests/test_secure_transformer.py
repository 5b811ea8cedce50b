"""CBNN protocols on a transformer block + LM serving: correctness,
customization gap, prefill/decode bit-identity, mesh equivalence, and the
compile-once-per-bucket pin (DESIGN.md §4/§16)."""
import jax
import numpy as np
import pytest

from conftest import run_party_subprocess
from repro.core import RING32, Parties
from repro.core.comm import estimate_cost
from repro.core.rss import reconstruct, share
from repro.core.secure_transformer import (CompiledDecodeStep, init_kv_cache,
                                           plaintext_block,
                                           plaintext_lm_forward,
                                           scan_prefill, secure_block,
                                           secure_decode_step,
                                           secure_prefill, share_block_params,
                                           share_lm_params)


def _setup(seq=8, d=32, heads=2, d_ff=64):
    bp, plain = share_block_params(jax.random.PRNGKey(0), d, heads, d_ff)
    x = np.random.default_rng(1).normal(0, 0.5, (seq, d)).astype(np.float32)
    xs = share(x, jax.random.PRNGKey(2))
    return bp, plain, x, xs, heads


def test_customized_block_matches_plaintext():
    bp, plain, x, xs, heads = _setup()
    parties = Parties.setup(jax.random.PRNGKey(3))
    out = reconstruct(secure_block(xs, bp, parties, customized=True))
    want = plaintext_block(x, plain, heads, customized=True)
    assert np.abs(np.asarray(out) - want).max() < 0.05


def test_softmax_block_matches_plaintext():
    bp, plain, x, xs, heads = _setup()
    parties = Parties.setup(jax.random.PRNGKey(3))
    out = reconstruct(secure_block(xs, bp, parties, customized=False))
    want = plaintext_block(x, plain, heads, customized=False)
    assert np.abs(np.asarray(out) - want).max() < 0.12


def test_customization_reduces_rounds_and_bytes():
    """The paper's claim, on attention: MPC-friendly customization cuts
    both communication rounds and bytes."""
    bp, plain, x, xs, heads = _setup()
    led_c = estimate_cost(
        lambda s: secure_block(s, bp, Parties.setup(jax.random.PRNGKey(5)),
                               customized=True), xs)
    led_s = estimate_cost(
        lambda s: secure_block(s, bp, Parties.setup(jax.random.PRNGKey(5)),
                               customized=False), xs)
    assert led_c.rounds < led_s.rounds
    assert led_c.nbytes < led_s.nbytes


# ---------------------------------------------------------------------------
# LM serving (DESIGN.md §16): prefill/decode identity, oracle parity,
# compile-once-per-bucket.
#
# Compile-budget note: XLA-CPU compile time scales with the protocol-op
# count of the traced program (the Newton-rsqrt ladders dominate), so the
# jit-dependent pins here (scan-vs-loop identity, trace counting) run under
# the §16 static-norm customization — the properties they pin (fold_in
# randomness, share-local cache writes, jit caching) are norm-independent.
# The full RMSNorm decode path is exercised EAGERLY in the oracle-parity
# rollouts below, where nothing gets compiled whole.
# ---------------------------------------------------------------------------

VOCAB, D, HEADS, D_FF, BLOCKS = 16, 16, 2, 32, 1
BUCKET = 8


@pytest.fixture(scope="module")
def lm_small():
    lm, plain = share_lm_params(jax.random.PRNGKey(0), VOCAB, D, HEADS,
                                D_FF, BLOCKS, RING32)
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    tokens = np.random.default_rng(5).integers(0, VOCAB, BUCKET - 1) \
        .astype(np.int32)
    return lm, plain, keys, tokens


@pytest.fixture(scope="module")
def custom_step(lm_small):
    lm = lm_small[0]
    return CompiledDecodeStep(lm, customized=True, static_norm=True)


def _fresh_cache(lm):
    return init_kv_cache(lm.n_blocks, lm.n_heads, lm.head_dim, BUCKET,
                         RING32)


def test_prefill_then_decode_bit_identity(lm_small, custom_step):
    """A scanned prefill over the whole sequence and prefill-then-decode
    (prompt prefix, then one jitted step per remaining token) emit
    bit-identical logits at EVERY position and bit-identical caches: the
    traced step body is position-independent and draws its protocol
    randomness from fold_in(keys, pos)."""
    lm, plain, keys, tokens = lm_small
    full = jax.jit(
        lambda c, t: secure_prefill(lm, c, t, keys, static_norm=True))
    lg_full, cache_full = full(_fresh_cache(lm), tokens)
    lg_full = np.asarray(lg_full)

    split = 3
    pre = jax.jit(
        lambda c, t: scan_prefill(custom_step.raw, c, t, keys))
    lg_pre, cache = pre(_fresh_cache(lm), tokens[:split])
    got = [np.asarray(lg_pre)]
    for p in range(split, len(tokens)):
        lg, cache = custom_step(cache, jax.numpy.asarray(int(tokens[p])),
                                jax.numpy.asarray(p), keys)
        got.append(np.asarray(lg)[None])
    got = np.concatenate(got, axis=0)

    assert np.array_equal(got, lg_full), np.abs(got - lg_full).max()
    assert np.array_equal(np.asarray(cache.k), np.asarray(cache_full.k))
    assert np.array_equal(np.asarray(cache.v), np.asarray(cache_full.v))
    # and the whole scanned run tracks the fp32 oracle at every position
    oracle = plaintext_lm_forward(plain, tokens, HEADS, True, BUCKET,
                                  static_norm=True)
    assert np.abs(lg_full - oracle).max() < 0.06


@pytest.mark.parametrize("customized", [True, False],
                         ids=["custom", "softmax"])
def test_decode_rollout_matches_oracle(lm_small, customized):
    """Greedy multi-token rollout over the full default path (RMSNorm
    included), run EAGERLY: token-identical to the fp32 oracle at every
    position, logits inside the fixed-point envelope, both attention
    modes."""
    lm, plain, keys, tokens = lm_small
    prompt = tokens[:3]
    tol = 0.06 if customized else 0.15

    cache = _fresh_cache(lm)
    seq = list(map(int, prompt))
    for p in range(len(prompt)):
        lg, cache = secure_decode_step(lm, cache,
                                       jax.numpy.asarray(seq[p]),
                                       jax.numpy.asarray(p), keys,
                                       customized)
    lg = np.asarray(lg)
    for p in range(len(prompt), BUCKET):
        oracle = plaintext_lm_forward(plain, np.asarray(seq, np.int32),
                                      HEADS, customized, BUCKET)[-1]
        assert np.abs(lg - oracle).max() < tol, (p, np.abs(lg - oracle).max())
        nxt = int(np.argmax(lg))
        assert nxt == int(np.argmax(oracle)), (p, lg, oracle)
        if p == BUCKET - 1:
            break
        seq.append(nxt)
        lg, cache = secure_decode_step(lm, cache, jax.numpy.asarray(nxt),
                                       jax.numpy.asarray(p), keys,
                                       customized)
        lg = np.asarray(lg)


def test_decode_compiles_once_per_bucket(lm_small):
    """The serving invariant the bucket policy rests on: a CompiledDecodeStep
    traces exactly once per cache bucket length no matter how many
    (token, position) pairs stream through it."""
    lm, _plain, keys, tokens = lm_small
    step = CompiledDecodeStep(lm, customized=True, static_norm=True)
    cache = _fresh_cache(lm)
    for p in range(3):
        _lg, cache = step(cache, jax.numpy.asarray(int(tokens[p])),
                          jax.numpy.asarray(p), keys)
    assert step.traces == 1, step.traces

    wide = init_kv_cache(lm.n_blocks, lm.n_heads, lm.head_dim, 12, RING32)
    for p in range(2):
        _lg, wide = step(wide, jax.numpy.asarray(int(tokens[p])),
                         jax.numpy.asarray(p), keys)
    assert step.traces == 2, step.traces  # one NEW trace for the new bucket

    # replays at both bucket lengths reuse the compiled programs
    step(cache, jax.numpy.asarray(0), jax.numpy.asarray(3), keys)
    step(wide, jax.numpy.asarray(0), jax.numpy.asarray(2), keys)
    assert step.traces == 2, step.traces


# ---------------------------------------------------------------------------
# Mesh backend equivalence (subprocess: fake-device XLA flag must be set
# before jax initializes — same pattern as test_transport_mesh)
# ---------------------------------------------------------------------------

MESH_BLOCK_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import RING32, Parties, transport
from repro.core.rss import RSS, reconstruct, share
from repro.core.secure_transformer import secure_block, share_block_params

bp, plain = share_block_params(jax.random.PRNGKey(0), 32, 2, 64)
x = np.random.default_rng(1).normal(0, 0.5, (8, 32)).astype(np.float32)
xs = share(x, jax.random.PRNGKey(2))
keys = Parties.setup(jax.random.PRNGKey(3)).keys
leaves, treedef = jax.tree_util.tree_flatten(bp)
mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:3]), ("party",))
w = P("party")
roll = lambda a: jnp.roll(a, -1, axis=0)

# customized mode runs the full RMSNorm path (the CI's mesh x rmsnorm
# coverage); the softmax mode uses the static-norm customization to keep
# the second shard_map compile inside the subprocess timeout (XLA-CPU
# compile time scales with protocol-op count)
for customized, static_norm in ((True, False), (False, True)):
    loc = secure_block(xs, bp, Parties(keys), customized=customized,
                       static_norm=static_norm)
    loc = np.asarray(reconstruct(loc, decode=False))

    def inner(keys, xo, xn, own, nxt):
        t = transport.MeshTransport("party")
        with transport.use_transport(t):
            bpl = jax.tree_util.tree_unflatten(
                treedef, [t.ingest(o, n) for o, n in zip(own, nxt)])
            xr = RSS(t.ingest(xo, xn), RING32)
            out = secure_block(xr, bpl, Parties(keys),
                               customized=customized,
                               static_norm=static_norm)
            return out.shares

    sm = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(), w, w, (w,) * len(leaves), (w,) * len(leaves)),
        out_specs=w, check_vma=False)
    glob = np.asarray(jax.jit(sm)(
        keys, xs.shares, roll(xs.shares), tuple(leaves),
        tuple(roll(a) for a in leaves)))
    # global pair layout (6, S, d): rows [0,2,4] are the additive shares
    msh = glob[[0, 2, 4]].sum(0, dtype=np.uint32)
    assert np.array_equal(loc, msh), (customized,
                                      int(np.abs(loc ^ msh).max()))
    print("block OK", customized)
print("OK")
"""


MESH_DECODE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import RING32
from repro.core.secure_transformer import (CompiledDecodeStep, init_kv_cache,
                                           make_secure_lm_mesh,
                                           share_lm_params)

lm, plain = share_lm_params(jax.random.PRNGKey(0), 16, 16, 2, 32, 1, RING32)
keys = jax.random.split(jax.random.PRNGKey(11), 3)
tokens = np.random.default_rng(5).integers(0, 16, 4).astype(np.int32)
mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:3]), ("party",))

loc = CompiledDecodeStep(lm, customized=True, static_norm=True)
msh = CompiledDecodeStep(
    step_fn=make_secure_lm_mesh(lm, mesh, True, static_norm=True))
cl = init_kv_cache(1, 2, 8, 8, RING32, slots=3)
cm = init_kv_cache(1, 2, 8, 8, RING32, slots=6)

for p, t in enumerate(tokens):
    ll, cl = loc(cl, jnp.asarray(int(t)), jnp.asarray(p), keys)
    lg, cm = msh(cm, jnp.asarray(int(t)), jnp.asarray(p), keys)
    # revealed logits: token-identical means bit-identical floats here
    assert np.array_equal(np.asarray(ll), np.asarray(lg)), p
    # cache circulates in the global pair layout; rows [0,2,4] are the
    # additive slots of the local simulation
    assert np.array_equal(np.asarray(cl.k),
                          np.asarray(cm.k)[[0, 2, 4]]), p
    assert np.array_equal(np.asarray(cl.v),
                          np.asarray(cm.v)[[0, 2, 4]]), p
    print("step OK", p, int(np.argmax(np.asarray(ll))))
assert loc.traces == 1 and msh.traces == 1, (loc.traces, msh.traces)
print("OK")
"""


def test_mesh_block_equivalence(tmp_path):
    """secure_block under MeshTransport == LocalTransport bit-for-bit in
    both attention modes (encoded-domain comparison)."""
    run_party_subprocess(MESH_BLOCK_SCRIPT, tmp_path, "mesh_block.py")


def test_mesh_decode_token_identity(tmp_path):
    """The decode loop on the mesh backend reveals bit-identical logits to
    the local simulation at every step, the circulated pair-layout cache
    stays consistent with the 3-slot cache, and each backend compiles its
    step exactly once."""
    run_party_subprocess(MESH_DECODE_SCRIPT, tmp_path, "mesh_decode.py")
