"""Offline preprocessing plant (DESIGN.md §12), LocalTransport side:
MaterialSpec extraction, one-launch tape generation, tape-backed online
inference bit-identity, the online-only ledger/PRF pins, and the Parties
counter retrace regression.  (Mesh-side coverage:
tests/test_preprocessing_mesh.py.)"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import RING32, Parties, share
from repro.core import preprocessing as prep
from repro.core.rss import RSS
from repro.core.secure_model import (compile_secure, secure_infer,
                                     secure_infer_cost)
from repro.nn import bnn
from repro.nn.bnn import INPUT_SHAPES
from repro.roofline.analyze import prf_ops_in_hlo


def _model(net, **kw):
    params = bnn.init_bnn(jax.random.PRNGKey(0), net)
    return compile_secure(params, net, jax.random.PRNGKey(1), RING32, **kw)


def _inputs(net, batch, seed=1):
    shape = INPUT_SHAPES[net]
    x = (np.random.default_rng(seed).integers(0, 2, (batch,) + shape)
         .astype(np.float32) - 0.5)
    return share(x, jax.random.PRNGKey(4), RING32)


def test_retrace_counter_sequence():
    """Two jit traces of the same compiled model (triggered by different
    batch shapes, sharing ONE Parties object) must consume identical
    counter sequences — the retrace hazard `Parties.fresh` removes."""
    model = _model("MnistNet1")
    parties = Parties.setup(jax.random.PRNGKey(7))
    run = jax.jit(lambda xs: secure_infer(model, RSS(xs, RING32), parties))
    out2 = np.asarray(run(_inputs("MnistNet1", 2).shares))  # trace 1
    xs4 = _inputs("MnistNet1", 4)
    out4 = np.asarray(run(xs4.shares))                      # trace 2
    # ground truth: a fresh Parties with the same session key
    ref4 = np.asarray(secure_infer(model, xs4,
                                   Parties.setup(jax.random.PRNGKey(7))))
    assert out2.shape[0] == 2
    assert np.array_equal(out4, ref4)
    # the spec extractor sees the same deterministic sequence every trace
    shape = (4,) + INPUT_SHAPES["MnistNet1"]
    s1, s2 = prep.trace_material(model, shape), prep.trace_material(model,
                                                                    shape)
    assert [(i.kind, i.cnt, i.shape) for i in s1.items] \
        == [(i.kind, i.cnt, i.shape) for i in s2.items]
    assert len(s1.items) > 0


@pytest.mark.parametrize("net,kw", [
    ("MnistNet1", {}),                      # fc net, shared weights
    ("MnistNet1", {"weights": "public"}),   # fc net, public weights
    ("MnistNet3", {}),                      # conv net (Sign + maxpool)
    ("MnistNet3", {"weights": "public"}),
])
def test_tape_bit_identical_local(net, kw):
    """Tape playback == inline PRF inference, bit for bit, for every
    query slot (per-slot session keys)."""
    model = _model(net, **kw)
    batch = 2
    xs = _inputs(net, batch)
    spec = prep.trace_material(model, (batch,) + INPUT_SHAPES[net])
    keys0 = Parties.setup(jax.random.PRNGKey(7)).keys
    keys1 = Parties.setup(jax.random.PRNGKey(8)).keys
    tape = prep.generate_tape(spec, jnp.stack([keys0, keys1]))
    run = jax.jit(prep.make_tape_infer(model, spec))
    for q, keys in enumerate((keys0, keys1)):
        ref = np.asarray(secure_infer(model, xs, Parties(keys)))
        out = np.asarray(run(keys, xs.shares, tape.query_slice(q)))
        assert np.array_equal(ref, out), (net, kw, q)


@pytest.mark.parametrize("net,fused", [
    ("MnistNet1", False),   # paper rounds: pair, parts and replicated slabs
    ("MnistNet3", True),    # conv net: 21 stacked slabs
])
def test_query_slice_one_program_equals_eager_indexing(net, fused):
    """``query_slice(q)`` is eager per-slab indexing (``v[:, q]`` stacked,
    ``v[q]`` replicated) bit for bit, in dtype and shape, for every slab
    and slot, from one cached executable."""
    from repro.core.linear import set_fused_rounds
    model = _model(net)
    set_fused_rounds(fused)
    try:
        spec = prep.trace_material(model, (2,) + INPUT_SHAPES[net])
    finally:
        set_fused_rounds(True)
    layouts = {info.layout for info in spec.slabs.values()}
    if not fused:
        assert layouts == {prep.STACK_PAIR, prep.STACK_PARTS,
                           prep.REPLICATED}
    tape = prep.generate_tape(
        spec, prep.tape_session_keys(jax.random.PRNGKey(9), 3))
    for q in range(3):
        sl = tape.query_slice(q)
        assert set(sl) == set(spec.slabs)
        for k, v in tape.slabs.items():
            want = np.asarray(v[q] if spec.slabs[k].layout == prep.REPLICATED
                              else v[:, q])
            got = np.asarray(sl[k])
            assert got.dtype == want.dtype and got.shape == want.shape, k
            assert np.array_equal(got, want), (k, q)
    assert prep.make_tape_slicer(spec)._cache_size() == 1
    with pytest.raises(IndexError):
        tape.query_slice(3)


def test_online_ledger_matches_inline_online_rows():
    """The tape-backed program's ledger is exactly the inline ledger's
    online (non-``pre:``) rows — rounds, bytes, and per-tag."""
    model = _model("MnistNet1")
    shape = (2,) + INPUT_SHAPES["MnistNet1"]
    spec = prep.trace_material(model, shape)
    led_in = secure_infer_cost(model, shape)
    led_on = prep.online_cost(model, spec, shape)
    assert led_on.pre_rounds == 0 and led_on.pre_nbytes == 0
    assert (led_on.rounds, led_on.nbytes) == (led_in.rounds, led_in.nbytes)
    online_tags = {t: tuple(v) for t, v in led_in.by_tag.items()
                   if not t.startswith("pre:")}
    assert {t: tuple(v) for t, v in led_on.by_tag.items()} == online_tags
    assert led_in.pre_nbytes > 0   # the plant actually moved work offline


def test_online_hlo_prf_free():
    """Compiled tape-backed HLO contains zero PRF work; inline doesn't."""
    model = _model("MnistNet1")
    batch = 2
    xs = _inputs("MnistNet1", batch)
    spec = prep.trace_material(model, (batch,) + INPUT_SHAPES["MnistNet1"])
    keys = Parties.setup(jax.random.PRNGKey(7)).keys
    tape = prep.generate_tape(spec, keys[None])

    hlo_tape = jax.jit(prep.make_tape_infer(model, spec)).lower(
        keys, xs.shares, tape.query_slice(0)).compile().as_text()
    assert prf_ops_in_hlo(hlo_tape) == 0, "PRF work left in online program"

    def inline(keys, x_stack):
        return secure_infer(model, RSS(x_stack, RING32), Parties(keys))

    hlo_inline = jax.jit(inline).lower(keys, xs.shares).compile().as_text()
    assert prf_ops_in_hlo(hlo_inline) > 0, "PRF marker lost its teeth"

    # the jaxpr-level view agrees: no randomness primitives at all
    jaxpr = str(jax.make_jaxpr(prep.make_tape_infer(model, spec))(
        keys, xs.shares, tape.query_slice(0)))
    assert "random_bits" not in jaxpr and "threefry" not in jaxpr


def test_tape_desync_fails_loudly():
    """Consuming a tape against a different program must raise, not
    silently serve wrong material."""
    m1 = _model("MnistNet1")
    m3 = _model("MnistNet3")
    shape = (2,) + INPUT_SHAPES["MnistNet1"]
    spec = prep.trace_material(m1, shape)
    run = prep.make_tape_infer(m3, spec)   # wrong model for this spec
    keys = Parties.setup(jax.random.PRNGKey(7)).keys
    x = jax.ShapeDtypeStruct((3, 2) + INPUT_SHAPES["MnistNet3"],
                             RING32.dtype)
    with pytest.raises(RuntimeError, match="desync|exhausted"):
        jax.eval_shape(run, keys, x, spec.slab_structs())


def test_spec_slab_structs_match_generated():
    """The abstract slab views (used to trace the online program) agree
    with what the generator actually produces."""
    model = _model("MnistNet3")
    spec = prep.trace_material(model, (2,) + INPUT_SHAPES["MnistNet3"])
    keys = Parties.setup(jax.random.PRNGKey(7)).keys
    tape = prep.generate_tape(spec, keys[None])
    sl = tape.query_slice(0)
    structs = spec.slab_structs()
    assert set(sl) == set(structs)
    for k in sl:
        assert sl[k].shape == structs[k].shape, k
        assert sl[k].dtype == structs[k].dtype, k
    assert tape.nbytes > 0
