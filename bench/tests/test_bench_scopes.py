"""Tests of the scope reduction (``bench/scope_reduce.py``) and of the
metrics that read it.

The synthetic cases pin the arithmetic, laid out as the TPU trace is:
host spans ``bench.*`` from the harness and ``cbnn.*`` from the program,
device ops with the ledger head of their ``jax.named_scope``, kernels by
the jitted wrapper of their ``pallas_call``, and one module event per
program execution.  The recorded case is a trace of
``record_scoped_trace.py`` on a TPU v5e chip
(``data/chip_trace.xplane.pb``).
"""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import harness  # noqa: E402
import scope_reduce as sr  # noqa: E402
import trace_reduce as tr  # noqa: E402

CHIP_TRACE = HERE / "data" / "chip_trace.xplane.pb"
NEW_METRICS = ("protocol.linear_ms_per_query", "protocol.sign_ms_per_query",
               "protocol.maxpool_ms_per_query", "stage_idle_share")


def _synthetic():
    """Window [0, 200]: staging [10, 40] with a tape_take inside it, one
    online execution [44, 120] with ops of every class, then the host
    outside every span."""
    host = [(0, 200, "bench.window"), (10, 40, "bench.stage"),
            (12, 38, "cbnn.tape_take"), (40, 42, "bench.dispatch"),
            (42, 122, "bench.block")]
    ops = [  # start, end, instruction, kernel?, kernel name, ledger tag
        (20, 24, "slice.1", False, "slice.1", sr.NO_SCOPE),
        (44, 50, "fusion.1", False, "fusion.1", "l0.dwconv"),
        (50, 60, "_rss_matmul_call.3", True, "_rss_matmul_call", "l0.pwconv"),
        (58, 66, "fusion.2", False, "fusion.2", "l0"),       # overlaps
        (66, 80, "fusion.3", False, "fusion.3", "sign1.msb"),
        (80, 90, "fusion.4", False, "fusion.4", "mp2"),
        (90, 100, "fusion.5", False, "fusion.5", "sign3.msb"),
        (100, 104, "copy.1", False, "copy.1", "output"),
        (104, 106, "copy.2", False, "copy.2", sr.NO_SCOPE),
        (150, 160, "fusion.6", False, "fusion.6", "l4.trunc")]  # no module
    mods = [(19, 25, "jit_slice"), (44, 106, "jit_run")]
    order = sorted(range(len(ops)), key=lambda i: ops[i][:5])
    return {"host": host, "devices": {"/device:TPU:0": {
        "ops": [ops[i][:5] for i in order],
        "tags": [ops[i][5] for i in order], "modules": mods}}}


def _run(red, queries=1, online="jit_run"):
    return SimpleNamespace(trace=red, queries=queries,
                           programs={"online": online})


def test_ledger_tag_of_a_scope_path():
    assert sr.tag_of("jit(run)/jit(main)/sign4/sign4.msb/xor:") == "sign4.msb"
    assert sr.tag_of("jit(run)/l12/l12.dwconv.bin/jit(_grouped_shared_call)"
                     "/pallas_call:") == "l12.dwconv.bin"
    assert sr.tag_of("jit(run)/l3/add:") == "l3"
    assert sr.tag_of("jit(run)/output/jit(_roll_static)/slice:") == "output"
    assert sr.tag_of("jit(run)/reshape:") == sr.NO_SCOPE
    assert sr.tag_of("") == sr.NO_SCOPE
    assert [sr.head_of(t) for t in ("l12.dwconv.bin", "sign4", sr.NO_SCOPE)] \
        == ["l12", "sign4", sr.NO_SCOPE]
    assert [sr.class_of(h) for h in ("l3", "sign4", "relu5", "mp6", "aff7",
                                     "output", sr.NO_SCOPE)] == \
        ["linear", "sign", "sign", "maxpool", "affine", "output",
         sr.NO_SCOPE]


def _pb(*fields):
    """A protocol-buffer message from (field, value) pairs: an int is a
    varint, bytes or a str a length-delimited field."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for field, value in fields:
        if isinstance(value, int):
            out += varint(field << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(field << 3 | 2) + varint(len(value)) + value
    return out


def test_op_names_read_from_the_event_metadata(tmp_path):
    """The ``tf_op`` stat of an op's event metadata, as a string or as a
    reference to a stat metadata's name, on TPU device planes only."""
    stat_md = lambda i, name: (5, _pb((1, i), (2, _pb((1, i), (2, name)))))
    event_md = lambda i, name, *stats: (4, _pb(
        (1, i), (2, _pb((1, i), (2, name), *((5, st) for st in stats)))))
    tpu = _pb((1, 7), (2, "/device:TPU:0"), (3, b"\x08\x01"),
              stat_md(3, "hlo_op"), stat_md(4, "tf_op"),
              stat_md(9, "jit(run)/l2/l2.dwconv.bin/pad:"),
              event_md(1, "%pad.1 = ...", _pb((1, 3), (5, "pad.1")),
                       _pb((1, 4), (5, "jit(run)/sign5/sign5.msb/xor:"))),
              event_md(2, "%pad.2 = ...", _pb((1, 4), (7, 9))),
              event_md(5, "%copy.3 = ...", _pb((1, 3), (5, "copy.3"))))
    host = _pb((2, "/host:CPU"), stat_md(4, "tf_op"),
               event_md(1, "x", _pb((1, 4), (5, "jit(run)/l0:"))))
    core = _pb((2, "/device:TPU:0 Core"), stat_md(4, "tf_op"),
               event_md(1, "x", _pb((1, 4), (5, "jit(run)/l0:"))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, tpu), (1, host), (1, core)))
    assert sr.tf_ops(path) == {"/device:TPU:0": {
        "%pad.1 = ...": "jit(run)/sign5/sign5.msb/xor:",
        "%pad.2 = ...": "jit(run)/l2/l2.dwconv.bin/pad:"}}


def test_base_keys_are_unchanged():
    t = _synthetic()
    red, base = sr.reduce_trace(t), tr.reduce_trace(t)
    assert {k: red[k] for k in base} == base


def test_ops_grouped_by_scope_into_classes():
    red = sr.reduce_trace(_synthetic())
    # non-kernel ops of the online program only, each class a union
    assert red["class_ns"]["jit_run"] == {
        "linear": 6 + 8, "sign": 14 + 10, "maxpool": 10, "output": 4,
        sr.NO_SCOPE: 2}
    assert red["class_ns"]["jit_slice"] == {sr.NO_SCOPE: 4}
    # tags: non-kernel busy time by innermost scope
    assert red["tag_ns"]["jit_run"] == {
        "l0.dwconv": 6, "l0": 8, "sign1.msb": 14, "mp2": 10, "sign3.msb": 10,
        "output": 4, sr.NO_SCOPE: 2}
    # heads: busy time, kernels included
    assert red["head_ns"]["jit_run"]["l0"] == 66 - 44
    # the classes and the ops outside every scope add up to the program's
    # non-kernel busy time (protocol_ms_per_query) up to the overlap [58, 60]
    protocol = (red["module_ns"]["jit_run"]
                - red["module_kernel_ns"]["jit_run"])
    assert sum(red["class_ns"]["jit_run"].values()) == protocol + 2
    run = _run(red)
    values = harness.read_metrics(
        [{"name": n, "unit": "ms"} for n in NEW_METRICS[:3]], run)
    assert [values[n]["value"] for n in NEW_METRICS[:3]] == \
        [14 / 1e6, 24 / 1e6, 10 / 1e6]


def test_program_span_inside_bench_span_labels_the_gap():
    red = sr.reduce_trace(_synthetic())
    gaps = dict((label, ns) for label, ns in red["gaps"])
    # [0, 20] opens in the window, its middle (10) at the stage's start;
    # [24, 44] has its middle (34) inside cbnn.tape_take inside bench.stage
    assert gaps["cbnn.tape_take"] == 20
    host = _synthetic()["host"]
    assert tr.label_at(host, 11) == "bench.stage"
    assert tr.label_at(host, 13) == "cbnn.tape_take"
    assert tr.label_at(host, 39) == "bench.stage"


def test_idle_summed_by_label():
    red = sr.reduce_trace(_synthetic())
    # gaps: [0, 20] (middle 10: bench.stage), [24, 44] (34: tape_take),
    # [106, 150] (128: outside), [160, 200] (180: outside)
    assert red["idle_by_label"] == {"bench.stage": 20, "cbnn.tape_take": 20,
                                    "host: outside bench spans": 44 + 40}
    assert sum(red["idle_by_label"].values()) == \
        red["window_ns"] - red["busy_ns"]
    assert red["span_count"] == {"bench.stage": 1, "cbnn.tape_take": 1,
                                 "bench.dispatch": 1, "bench.block": 1}
    # the host is outside every span in [0, 10] and [122, 200]; the
    # device is idle in all of the first and in [122, 150], [160, 200]
    assert red["idle_outside_ns"] == 10 + 28 + 40
    assert red["first_op_ns"] == 20


def test_host_time_outside_spans_by_neighbours():
    red = sr.reduce_trace(_synthetic())
    assert red["host_outside_ns"] == {
        "(window start) > bench.stage": 10,
        "bench.block > (window end)": 200 - 122}


def test_stage_idle_share_arithmetic():
    red = sr.reduce_trace(_synthetic())
    # idle inside tape_take [12, 38]: [12, 20] and [24, 38]
    assert red["stage_idle_ns"] == 8 + 14
    value = harness.read_metrics([{"name": "stage_idle_share", "unit": "%"}],
                                 _run(red))["stage_idle_share"]["value"]
    assert value == pytest.approx(100.0 * 22 / 200)


def test_devices_are_averaged():
    t = _synthetic()
    dev = t["devices"]["/device:TPU:0"]
    t["devices"]["/device:TPU:1"] = {
        "ops": list(dev["ops"]),
        "tags": ["sign1" if t == "l0" else t for t in dev["tags"]],
        "modules": dev["modules"]}
    red = sr.reduce_trace(t)
    assert red["devices"] == 2
    # device 1 moves fusion.2 [58, 66] from l0 to sign1
    assert red["class_ns"]["jit_run"]["linear"] == (14 + 6) / 2
    assert red["class_ns"]["jit_run"]["sign"] == (24 + 32) / 2


def test_new_metrics_read_nothing_without_scopes():
    """The harness's own reduction has no scopes: the new readers return
    ``None`` there (a checkout without the scope reduction)."""
    red = tr.reduce_trace(_synthetic())
    assert harness.read_metrics(
        [{"name": n, "unit": "ms"} for n in NEW_METRICS], _run(red)) == {}
    assert harness.read_metrics(
        [{"name": n, "unit": "ms"} for n in NEW_METRICS], _run(None)) == {}


# -- the recorded case --------------------------------------------------------

@pytest.fixture(scope="module")
def chip():
    if not CHIP_TRACE.exists():
        pytest.fail(f"recorded trace {CHIP_TRACE} is missing")
    return sr.load(CHIP_TRACE)


def test_recorded_scopes_on_tpu_op_events(chip):
    (dev,) = [d for d in chip["devices"].values() if d["ops"]]
    tags = dict(zip((o[2] for o in dev["ops"]), dev["tags"]))
    kernels = {o[2] for o in dev["ops"] if o[3]}
    assert kernels == {"_rss_matmul_call.1"}
    assert tags["_rss_matmul_call.1"] == "l0.conv"
    assert {"l0.conv", "sign1.msb"} <= set(tags.values())
    red = sr.reduce_trace(chip)
    assert red["module_count"]["jit_online"] == 4
    assert red["head_ns"]["jit_online"]["l0"] > red["class_ns"][
        "jit_online"]["linear"]         # the kernel is inside l0
    online = red["class_ns"]["jit_online"]
    assert online["linear"] > 0 and online["sign"] > 0
    # the programs without scopes: the tape draws, slices and the xor
    assert set(red["class_ns"]["jit_generate"]) == {sr.NO_SCOPE}


def test_recorded_gap_is_labelled_by_the_program_span(chip):
    """Query 0 compiles its small staging programs inside
    ``cbnn.tape_take`` and query 1 sleeps 20 ms there: both gaps are the
    program span's, inside the harness's ``bench.stage``."""
    red = sr.reduce_trace(chip)
    assert red["gaps"][0][0] == "cbnn.tape_take"
    sleep = [ns for label, ns in red["gaps"] if label == "cbnn.tape_take"
             and 20e6 <= ns < 40e6]
    assert sleep
    assert red["idle_by_label"]["cbnn.tape_take"] >= red["gaps"][0][1] + 20e6
    assert red["stage_idle_ns"] >= red["idle_by_label"]["cbnn.tape_take"]
    assert red["span_count"]["cbnn.tape_take"] == 4
    assert red["idle_outside_ns"] < 1e6
