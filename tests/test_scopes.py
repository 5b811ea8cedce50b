"""The program's names for a profiler trace.

* Every ledger head of a served program is a ``jax.named_scope`` on its
  ops (``comm.scope``), with each protocol call's full tag nested inside
  its head, in the inline, tape and mesh online programs — the names by
  which a device trace is split into protocol layers.
* Every dense convolution's patch build is an ``im2col`` scope inside
  its layer tag.
* ``telemetry.span`` writes a ``cbnn.<name>`` annotation into a profiler
  trace with no tracer installed, and records nothing then.
* The tape pool's staging has its spans and counters.
"""
import re

import jax
import pytest

from repro.core import RING32, comm, telemetry
from repro.core.preprocessing import (MaterialTape, TapePool,
                                      make_tape_generator, make_tape_infer,
                                      online_cost, tape_session_keys,
                                      trace_material)
from repro.core.randomness import Parties
from repro.core.rss import RSS
from repro.core.secure_model import (compile_secure, secure_infer,
                                     secure_infer_cost)
from repro.nn.bnn import INPUT_SHAPES, init_bnn

from conftest import run_party_subprocess, with_array_arguments

TAG = re.compile(r"^((?:l|sign|relu|aff|mp)\d+)\.")


def _model(net):
    params = init_bnn(jax.random.PRNGKey(0), net)
    return compile_secure(params, net, jax.random.PRNGKey(1), RING32)


def _heads(led):
    return {t.split(":", 1)[-1].split(".", 1)[0] for t in led.by_tag}


def _scope_paths(lowered):
    """The name-stack paths of the lowered program's ops."""
    text = lowered.as_text(debug_info=True)
    return [p.split("/") for p in re.findall(r'loc\("([^"]+)"', text)]


def _check_scopes(paths, heads):
    scopes = {part for p in paths for part in p}
    assert heads <= scopes, sorted(heads - scopes)
    for p in paths:
        for i, part in enumerate(p):
            m = TAG.match(part)
            if m:   # a protocol call's full tag sits inside its head
                assert i > 0 and p[i - 1] == m.group(1), p


@pytest.mark.parametrize("net", ["MnistNet1", "MnistNet3-sep"])
def test_every_ledger_head_is_a_scope_inline(net):
    model = _model(net)
    shape = (2,) + INPUT_SHAPES[net]
    keys = Parties.setup(jax.random.PRNGKey(7)).keys
    x = jax.ShapeDtypeStruct((3,) + shape, RING32.dtype)
    lowered = jax.jit(lambda k, xs: secure_infer(
        model, RSS(xs, model.ring), Parties(k))).lower(keys, x)
    heads = _heads(secure_infer_cost(model, shape))
    assert "output" in heads and any(h.startswith("sign") for h in heads)
    _check_scopes(_scope_paths(lowered), heads)


@pytest.mark.parametrize("net", ["MnistNet1", "MnistNet3-sep"])
def test_every_ledger_head_is_a_scope_tape(net):
    model = _model(net)
    shape = (2,) + INPUT_SHAPES[net]
    spec = trace_material(model, shape)
    keys = Parties.setup(jax.random.PRNGKey(7)).keys
    x = jax.ShapeDtypeStruct((3,) + shape, RING32.dtype)
    lowered = jax.jit(make_tape_infer(model, spec)).lower(
        keys, x, spec.slab_structs())
    heads = _heads(online_cost(model, spec, shape))
    assert heads == _heads(secure_infer_cost(model, shape))
    _check_scopes(_scope_paths(lowered), heads)


@pytest.mark.parametrize("binary_linear", ["auto", "off"])
def test_depthwise_product_runs_under_taps(binary_linear):
    """Every depthwise half of a separable net computes its product inside
    a ``taps`` scope nested in its ledger tag (``l{i}.dwconv[.bin]``), and
    no kernel launch: the trace shows the direct product apart from the
    reshare."""
    net = "MnistNet3-sep"
    params = init_bnn(jax.random.PRNGKey(0), net)
    model = compile_secure(params, net, jax.random.PRNGKey(1), RING32,
                           use_kernel_dot=True, binary_linear=binary_linear)
    shape = (2,) + INPUT_SHAPES[net]
    keys = Parties.setup(jax.random.PRNGKey(7)).keys
    x = jax.ShapeDtypeStruct((3,) + shape, RING32.dtype)
    lowered = jax.jit(lambda k, xs: secure_infer(
        model, RSS(xs, model.ring), Parties(k))).lower(keys, x)
    paths = _scope_paths(lowered)
    dw = {t for t in secure_infer_cost(model, shape).by_tag
          if re.fullmatch(r"l\d+\.dwconv(\.bin)?", t)}
    assert len(dw) == sum(op["op"] == "sepconv" for op in model.ops)
    under = {p[i - 1] for p in paths for i, part in enumerate(p)
             if part == "taps" and i > 0}
    assert under == dw, (under, dw)
    assert "_grouped_shared_call" not in lowered.as_text()


def _bare_scope(tag):
    """``comm.scope`` that names nothing."""
    from contextlib import nullcontext
    return nullcontext(tag)


@pytest.mark.parametrize("net", ["CifarNet6", "CifarNet2"])
def test_patch_build_runs_under_im2col(net, monkeypatch):
    """Every dense convolution (CifarNet6's 3x3 layers, CifarNet2's
    pointwise halves) builds its patch matrix inside an ``im2col`` scope
    nested in its ledger tag (``l{i}.conv``, ``l{i}.conv.bin``,
    ``l{i}.pwconv``), so a trace shows the patch build apart from the
    product and the reshare; the scope leaves the ledger as it was."""
    model = _model(net)
    shape = (1,) + INPUT_SHAPES[net]
    led = secure_infer_cost(model, shape)
    arrays, run = with_array_arguments(model)
    keys = Parties.setup(jax.random.PRNGKey(7)).keys
    x = jax.ShapeDtypeStruct((3,) + shape, RING32.dtype)
    paths = _scope_paths(jax.jit(run).lower(keys, x, arrays))
    dense = {t for t in led.by_tag
             if re.fullmatch(r"l\d+\.(conv|pwconv)(\.bin)?", t)}
    assert len(dense) == sum(op["op"] in ("conv", "sepconv")
                             for op in model.ops)
    under = {p[i - 1] for p in paths for i, part in enumerate(p)
             if part == "im2col" and i > 0}
    assert under == dense, (under, dense)
    monkeypatch.setattr(comm, "scope", _bare_scope)
    assert dict(secure_infer_cost(model, shape).by_tag) == dict(led.by_tag)


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=3"
import re
import numpy as np
import jax
from repro.core import RING32, Parties
from repro.core.secure_model import (compile_secure, make_secure_infer_mesh,
                                     secure_infer_cost)
from repro.nn.bnn import INPUT_SHAPES, init_bnn

net = "MnistNet1"
model = compile_secure(init_bnn(jax.random.PRNGKey(0), net), net,
                       jax.random.PRNGKey(1), RING32)
shape = (2,) + INPUT_SHAPES[net]
mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:3]), ("party",))
fn = make_secure_infer_mesh(model, mesh)
keys = Parties.setup(jax.random.PRNGKey(7)).keys
x = jax.ShapeDtypeStruct((3,) + shape, RING32.dtype)
text = jax.jit(fn).lower(keys, x).as_text(debug_info=True)
scopes = {part for p in re.findall(r'loc\("([^"]+)"', text)
          for part in p.split("/")}
heads = {t.split(":", 1)[-1].split(".", 1)[0]
         for t in secure_infer_cost(model, shape).by_tag}
assert heads <= scopes, sorted(heads - scopes)
print("OK", sorted(heads))
"""


def test_every_ledger_head_is_a_scope_mesh(tmp_path):
    run_party_subprocess(MESH_SCRIPT, tmp_path, "mesh_scopes.py")


def test_span_is_a_profiler_annotation_without_a_tracer(tmp_path):
    from jax.profiler import ProfileData
    assert telemetry.tracer() is None
    reg = telemetry.MetricsRegistry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.collecting(reg):
            with telemetry.span("tape_refill[3]", cat="offline") as s:
                jax.block_until_ready(jax.numpy.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    assert not isinstance(s, telemetry.Span)
    # nothing lands in the registry: a span is not a metric
    assert reg.as_dict() == {"counters": {}, "gauges": {}, "histograms": {}}
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert telemetry.profiler_name("tape_refill[3]") == "cbnn.tape_refill"
    assert "cbnn.tape_refill" in names
    assert not any(n.startswith("cbnn.tape_refill[") for n in names)


def test_tape_staging_spans_and_counters():
    net = "MnistNet1"
    model = _model(net)
    spec = trace_material(model, (2,) + INPUT_SHAPES[net])
    gen = make_tape_generator(spec)
    t, reg = telemetry.Tracer(), telemetry.MetricsRegistry()
    with telemetry.tracing(t), telemetry.collecting(reg):
        pool = TapePool(gen, spec, 2, jax.random.PRNGKey(5), demand=3)
        for _ in range(3):
            pool.take()
    names = [s.name for s in t.spans]
    assert names.count("tape_take") == 3 and names.count("tape_slice") == 3
    assert {"tape_refill[0]", "tape_refill[1]"} <= set(names)
    # every slice lies inside its take
    takes = [s for s in t.spans if s.name == "tape_take"]
    for s in (s for s in t.spans if s.name == "tape_slice"):
        assert any(k.ts <= s.ts and s.ts + s.dur <= k.ts + k.dur
                   for k in takes)
    c = reg.as_dict()["counters"]
    assert c["tape_slices_total"] == 3
    # one sliced array per slab of the spec, every slice
    assert c["tape_slice_dispatches_total"] == 3 * len(spec.slabs)
    # a tape sliced outside the pool counts the same way
    tape = MaterialTape(gen(tape_session_keys(jax.random.PRNGKey(6), 2)),
                        spec, 2)
    with telemetry.collecting(reg):
        tape.query_slice(1)
    assert reg.as_dict()["counters"]["tape_slices_total"] == 4
