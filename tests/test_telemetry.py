"""Observability layer (DESIGN.md §17): tracer, metrics, attribution.

Pinned acceptance contracts of PR 10:

* emitted traces validate against the Chrome trace-event schema (and the
  validator actually rejects malformed events);
* the attribution report's per-layer measured wire bytes sum to the live
  ``CommLedger`` totals EXACTLY (classifier zoo including a separable
  net, with and without a verify-digest ledger row);
* telemetry disabled records nothing (a span is only a profiler
  annotation: no Span, no samples) and enabled telemetry never changes
  model outputs —
  bit-identical logits under both transports (the mesh case runs in a
  party subprocess with fake devices, like the other mesh tests).
"""
import json

import jax
import numpy as np
import pytest

from repro.core import RING32, comm, cost_model, telemetry
from repro.core.randomness import Parties
from repro.core.rss import share
from repro.core.secure_model import (compile_secure, secure_infer,
                                     secure_infer_cost)
from repro.nn.bnn import INPUT_SHAPES, init_bnn

from conftest import run_party_subprocess


def _model(net, **kw):
    params = init_bnn(jax.random.PRNGKey(0), net)
    return compile_secure(params, net, jax.random.PRNGKey(1), RING32, **kw)


# ---------------------------------------------------------------------------
# Disabled-mode cost contract
# ---------------------------------------------------------------------------

def test_disabled_mode_is_noop():
    assert telemetry.tracer() is None and telemetry.metrics() is None
    assert not telemetry.enabled()
    # module-level span is only a profiler annotation: no Span recorded
    a, b = telemetry.span("x"), telemetry.span("y", cat="compile")
    assert isinstance(a, jax.profiler.TraceAnnotation)
    assert isinstance(b, jax.profiler.TraceAnnotation)
    with a as s:
        assert not isinstance(s, telemetry.Span)
    # metric hooks are silent no-ops
    telemetry.inc("c")
    telemetry.gauge("g", 1.0)
    telemetry.observe("h", 0.5)
    telemetry.movement("complete", "local")


def test_tracing_none_is_noop():
    with telemetry.tracing(None) as t:
        assert t is None and telemetry.tracer() is None
    with telemetry.collecting(None) as r:
        assert r is None and telemetry.metrics() is None


def test_tracing_restores_on_exception():
    t = telemetry.Tracer()
    with pytest.raises(RuntimeError, match="escape"):
        with telemetry.tracing(t):
            assert telemetry.tracer() is t
            assert t.on_comm in comm._LISTENERS
            raise RuntimeError("escape")
    assert telemetry.tracer() is None
    assert t.on_comm not in comm._LISTENERS


# ---------------------------------------------------------------------------
# Tracer: spans, comm correlation, Chrome trace schema
# ---------------------------------------------------------------------------

def test_emitted_trace_is_schema_valid(tmp_path):
    t = telemetry.Tracer(parties=3)
    with telemetry.tracing(t):
        with telemetry.span("compile", cat="compile"):
            comm.record("l0.fc", 1, 128)
            comm.record("sign1.msb", 2, 64, preprocess=True)
        with telemetry.span("query[0]", cat="online", lane="parties"):
            with telemetry.span("inner", cat="online"):
                pass
        t.instant("abort", cat="verify", party=2)
    path = tmp_path / "trace.json"
    t.write(str(path))
    trace = json.loads(path.read_text())
    telemetry.validate_chrome_trace(trace)   # must not raise
    ev = trace["traceEvents"]
    names = {e["name"] for e in ev}
    assert {"process_name", "thread_name", "compile", "query[0]",
            "l0.fc", "pre:sign1.msb", "abort"} <= names
    # the compile span carries the correlated comm totals
    compile_ev = next(e for e in ev if e["name"] == "compile")
    assert compile_ev["args"]["rounds"] == 1
    assert compile_ev["args"]["wire_bytes"] == 128
    assert compile_ev["args"]["pre_rounds"] == 2
    assert compile_ev["args"]["pre_wire_bytes"] == 64
    assert compile_ev["args"]["comm_ops"] == 2


def test_party_lane_fanout():
    t = telemetry.Tracer(parties=3)
    with t.span("q", cat="online", lane="parties"):
        pass
    with t.span("host", cat="setup"):
        pass
    ev = t.chrome_trace()["traceEvents"]
    lanes = {e["args"]["name"]: e["tid"] for e in ev
             if e["name"] == "thread_name"}
    assert {"main", "party0", "party1", "party2"} <= set(lanes)
    q_tids = sorted(e["tid"] for e in ev if e["name"] == "q")
    # one complete event per party lane, same measured interval
    assert q_tids == sorted(lanes[f"party{p}"] for p in range(3))
    (host,) = [e for e in ev if e["name"] == "host"]
    assert host["tid"] == lanes["main"]


def test_comm_instants_attribute_to_innermost_open_span():
    t = telemetry.Tracer()
    with telemetry.tracing(t):
        with telemetry.span("outer", cat="online"):
            with telemetry.span("inner", cat="online"):
                comm.record("x", 1, 10)
    inner = next(s for s in t.spans if s.name == "inner")
    outer = next(s for s in t.spans if s.name == "outer")
    assert inner.args.get("wire_bytes") == 10
    assert "wire_bytes" not in outer.args


def test_phase_seconds_counts_nested_same_category_once():
    fake = iter([0.0,                     # tracer t0
                 1.0, 2.0, 3.0,          # outer open, inner open/close
                 4.0, 5.0, 6.0]).__next__   # sub open/close, outer close
    t = telemetry.Tracer(clock=fake)
    with t.span("outer", cat="online"):        # 1.0 .. 6.0
        with t.span("inner", cat="online"):    # 2.0 .. 3.0 (nested: skip)
            pass
        with t.span("sub", cat="verify"):      # 4.0 .. 5.0
            pass
    ph = t.phase_seconds()
    assert ph["online"] == pytest.approx(5.0)   # outer only, inner nested
    assert ph["verify"] == pytest.approx(1.0)   # different category counts


@pytest.mark.parametrize("mutate, err", [
    (lambda tr: tr.pop("traceEvents"), "traceEvents"),
    (lambda tr: tr["traceEvents"].append({"ph": "X", "name": "x",
                                          "pid": 0, "tid": 0, "ts": 1.0}),
     "dur"),
    (lambda tr: tr["traceEvents"].append({"ph": "Q", "name": "x",
                                          "pid": 0, "tid": 0, "ts": 0}),
     "phase"),
    (lambda tr: tr["traceEvents"].append({"ph": "i", "pid": 0, "tid": 0,
                                          "ts": 0}), "name"),
    (lambda tr: tr["traceEvents"].append({"ph": "i", "name": "x",
                                          "pid": "0", "tid": 0, "ts": 0}),
     "pid"),
    (lambda tr: tr["traceEvents"].append({"ph": "i", "name": "x", "pid": 0,
                                          "tid": 0, "ts": -5}), "ts"),
])
def test_validator_rejects_malformed(mutate, err):
    t = telemetry.Tracer()
    with t.span("ok"):
        pass
    trace = t.chrome_trace()
    mutate(trace)
    with pytest.raises(ValueError, match=err):
        telemetry.validate_chrome_trace(trace)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_metrics_counters_gauges_histograms():
    r = telemetry.MetricsRegistry()
    r.inc("comm_bytes_total", 100, tag="l0.fc")
    r.inc("comm_bytes_total", 50, tag="l0.fc")
    r.inc("comm_bytes_total", 7, tag="sign1.msb")
    r.gauge("pool_supply", 5)
    r.gauge("pool_supply", 3)             # gauges overwrite
    for v in range(1, 101):
        r.observe("query_latency_seconds", v / 100.0)
    d = r.as_dict()
    assert d["counters"]['comm_bytes_total{tag="l0.fc"}'] == 150
    assert d["gauges"]["pool_supply"] == 3
    h = d["histograms"]["query_latency_seconds"]
    assert h["count"] == 100 and h["min"] == 0.01 and h["max"] == 1.0
    assert h["p50"] == pytest.approx(0.505, abs=1e-9)
    assert h["p95"] == pytest.approx(0.9505, abs=1e-9)
    assert h["p99"] == pytest.approx(0.9901, abs=1e-9)


def test_prometheus_text_format():
    r = telemetry.MetricsRegistry()
    r.inc("comm_rounds_total", 6, tag="l0.fc", phase="online")
    r.observe("query_latency_seconds", 0.25)
    txt = r.prometheus()
    assert "# TYPE cbnn_comm_rounds_total counter" in txt
    # labels render sorted and quoted
    assert 'cbnn_comm_rounds_total{phase="online",tag="l0.fc"} 6.0' in txt
    assert "# TYPE cbnn_query_latency_seconds summary" in txt
    assert 'cbnn_query_latency_seconds{quantile="0.5"} 0.25' in txt
    assert "cbnn_query_latency_seconds_count 1" in txt
    assert txt.endswith("\n")


def test_metrics_write_files(tmp_path):
    r = telemetry.MetricsRegistry()
    r.inc("c", 1)
    r.write_json(str(tmp_path / "m.json"))
    r.write_prom(str(tmp_path / "m.prom"))
    assert json.loads((tmp_path / "m.json").read_text())["counters"]["c"] == 1
    assert "cbnn_c 1.0" in (tmp_path / "m.prom").read_text()


def test_record_ledger_scales_by_queries_and_labels_paths():
    model = _model("MnistNet1")
    led = secure_infer_cost(model, (2,) + INPUT_SHAPES["MnistNet1"])
    r = telemetry.MetricsRegistry()
    r.record_ledger(led, model, queries=3)
    d = r.as_dict()["counters"]
    total_b = sum(v for k, v in d.items()
                  if k.startswith("comm_bytes_total")
                  and 'phase="online"' in k)
    assert total_b == 3 * led.nbytes
    total_pre = sum(v for k, v in d.items()
                    if k.startswith("comm_bytes_total")
                    and 'phase="offline"' in k)
    assert total_pre == 3 * led.pre_nbytes
    # §11 path labels ride along on the layer tags
    assert any('path=' in k for k in d)


def test_movement_counters_fire_at_trace_time():
    model = _model("MnistNet1")
    reg = telemetry.MetricsRegistry()
    with telemetry.collecting(reg):
        secure_infer_cost(model, (1,) + INPUT_SHAPES["MnistNet1"])
    d = reg.as_dict()["counters"]
    assert d.get('transport_ops_total{backend="local",kind="complete"}', 0) \
        > 0
    assert d.get('transport_ops_total{backend="local",kind="open_rss"}', 0) \
        > 0


# ---------------------------------------------------------------------------
# Attribution: measured == ledger, exactly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("net", ["MnistNet1", "MnistNet3-sep"])
def test_attribution_measured_matches_ledger_exactly(net):
    model = _model(net)
    shape = (2,) + INPUT_SHAPES[net]
    led = secure_infer_cost(model, shape)
    pred = cost_model.model_cost(model, shape)
    heads = [e.name.split(" ", 1)[0] for e in pred.entries]
    layer_ms = {h: float(i + 1) for i, h in enumerate(heads)}
    rep = telemetry.attribution(pred, led, layer_ms=layer_ms, online_s=0.5)
    # per-row measured wire bytes sum to the live ledger totals EXACTLY
    assert sum(r.meas_bytes for r in rep.rows) == led.nbytes
    assert sum(r.meas_rounds for r in rep.rows) == led.rounds
    assert sum(r.pre_bytes for r in rep.rows) == led.pre_nbytes
    # every ledger tag is attributed to exactly one row
    attributed = [t for r in rep.rows for t in r.tags]
    assert sorted(attributed) == sorted(led.by_tag)
    # prediction agrees per-row (the §15 fidelity contract, row-resolved)
    assert rep.exact
    for r in rep.rows:
        assert (r.pred_rounds, r.pred_bytes) == (r.meas_rounds,
                                                 r.meas_bytes), r.name
    # the device-time column is the caller's per-head ms, row by row,
    # and nothing is spread over rows that were given none
    assert [r.device_ms for r in rep.rows] == [layer_ms[h] for h in heads]
    assert sum(r.device_ms for r in rep.rows) == pytest.approx(
        sum(layer_ms.values()))
    assert "total" in rep.render()


def test_attribution_ledger_only_rows_keep_totals_exact():
    model = _model("MnistNet1")
    shape = (1,) + INPUT_SHAPES["MnistNet1"]
    led = secure_infer_cost(model, shape)
    pred = cost_model.model_cost(model, shape)
    led.add("verify.digest", 1, 48)   # the §14 compare-view round
    rep = telemetry.attribution(pred, led)
    (vrow,) = [r for r in rep.rows if r.name == "verify"]
    assert not vrow.has_pred and vrow.meas_bytes == 48
    assert vrow.exact   # vacuous: nothing predicted to disagree with
    assert rep.exact
    assert sum(r.meas_bytes for r in rep.rows) == led.nbytes
    assert sum(r.meas_rounds for r in rep.rows) == led.rounds


def test_attribution_without_prediction_uses_byte_share():
    model = _model("MnistNet1")
    shape = (1,) + INPUT_SHAPES["MnistNet1"]
    led = secure_infer_cost(model, shape)
    rep = telemetry.attribution(None, led, online_s=1.0)
    assert all(not r.has_pred for r in rep.rows)
    assert sum(r.meas_bytes for r in rep.rows) == led.nbytes
    assert sum(r.share for r in rep.rows) == pytest.approx(1.0)
    # a wall time is a label, never split into per-layer times
    assert all(r.device_ms is None for r in rep.rows)
    assert rep.as_dict()["online_s"] == 1.0
    rows = rep.render().splitlines()[2:2 + len(rep.rows)]
    assert all(line.endswith(" -") for line in rows)
    assert rep.as_dict()["ledger_bytes"] == led.nbytes


# ---------------------------------------------------------------------------
# Bit-identity: telemetry never changes model outputs
# ---------------------------------------------------------------------------

def test_local_outputs_bit_identical_with_telemetry_on():
    model = _model("MnistNet1")
    shape = (2,) + INPUT_SHAPES["MnistNet1"]
    parties = Parties.setup(jax.random.PRNGKey(7))
    rng = np.random.default_rng(0)
    x = rng.integers(0, 2, shape).astype(np.float32) - 0.5
    xs = share(x, jax.random.PRNGKey(3), RING32)

    def run():
        from repro.core.rss import RSS
        return np.asarray(secure_infer(model, RSS(xs.shares, model.ring),
                                       Parties(parties.keys)))

    base = run()
    t, reg = telemetry.Tracer(), telemetry.MetricsRegistry()
    with telemetry.tracing(t), telemetry.collecting(reg):
        with telemetry.span("query[0]", cat="online"):
            instrumented = run()
    np.testing.assert_array_equal(base, instrumented)
    assert t.spans and t.spans[-1].args.get("wire_bytes", 0) > 0
    telemetry.validate_chrome_trace(t.chrome_trace())


def test_mesh_outputs_bit_identical_with_telemetry_on(tmp_path):
    run_party_subprocess("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
import numpy as np
from repro.core import RING32, telemetry
from repro.core.randomness import Parties
from repro.core.rss import share
from repro.core.secure_model import compile_secure, make_secure_infer_mesh
from repro.nn.bnn import INPUT_SHAPES, init_bnn

net = "MnistNet1"
params = init_bnn(jax.random.PRNGKey(0), net)
model = compile_secure(params, net, jax.random.PRNGKey(1), RING32)
parties = Parties.setup(jax.random.PRNGKey(7))
rng = np.random.default_rng(0)
x = rng.integers(0, 2, (2,) + INPUT_SHAPES[net]).astype(np.float32) - 0.5
xs = share(x, jax.random.PRNGKey(3), RING32)

mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:3]), ("party",))
fn = jax.jit(make_secure_infer_mesh(model, mesh))
base = np.asarray(fn(parties.keys, xs.shares)[0])

tracer = telemetry.Tracer(parties=3)
reg = telemetry.MetricsRegistry()
with telemetry.tracing(tracer), telemetry.collecting(reg):
    with telemetry.span("jit_warmup", cat="compile"):
        fn2 = jax.jit(make_secure_infer_mesh(model, mesh))
        instrumented = np.asarray(fn2(parties.keys, xs.shares)[0])
    with telemetry.span("query[0]", cat="online", lane="parties"):
        again = np.asarray(fn2(parties.keys, xs.shares)[0])

np.testing.assert_array_equal(base, instrumented)
np.testing.assert_array_equal(base, again)
trace = tracer.chrome_trace()
telemetry.validate_chrome_trace(trace)
lanes = {e["args"]["name"] for e in trace["traceEvents"]
         if e["name"] == "thread_name"}
assert {"party0", "party1", "party2"} <= lanes, lanes
q = [e for e in trace["traceEvents"] if e["name"] == "query[0]"]
assert len(q) == 3 and len({e["tid"] for e in q}) == 3, q
ops = reg.as_dict()["counters"]
assert ops.get('transport_ops_total{backend="mesh",kind="complete"}', 0) > 0
print("OK")
""", tmp_path, "telemetry_mesh.py")


def test_span_totals_from_trace_collapses_party_fanout():
    """roofline.analyze.span_totals_from_trace joins a tracer export to
    per-category totals, collapsing the party-lane fanout (3 tids share
    one logical span) so totals match wall time."""
    from repro.roofline.analyze import span_totals_from_trace

    clock = iter([0.0,            # tracer epoch
                  1.0, 3.0,       # compile span: 2.0 s
                  4.0, 4.5,       # query[0]:     0.5 s (fans out x3 tids)
                  5.0, 5.25]).__next__
    tr = telemetry.Tracer(parties=3, clock=clock)
    with tr.span("compile_secure", cat="compile"):
        pass
    with tr.span("query[0]", cat="online", lane="parties"):
        pass
    with tr.span("query[1]", cat="online", lane="parties"):
        pass
    trace = tr.chrome_trace()
    telemetry.validate_chrome_trace(trace)
    # 2 online spans x 3 party tids + 1 compile span = 7 "X" events...
    assert sum(e["ph"] == "X" for e in trace["traceEvents"]) == 7
    tot = span_totals_from_trace(trace)
    # ...but totals count each logical span once
    assert tot["by_cat"]["compile"] == {"us": 2.0e6, "count": 1}
    assert tot["by_cat"]["online"] == {"us": 0.75e6, "count": 2}
    assert tot["by_span"][("online", "query[0]")]["count"] == 1
    assert tot["total_us"] == pytest.approx(2.75e6)
