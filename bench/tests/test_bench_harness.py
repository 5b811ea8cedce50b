"""CPU tests of the benchmark harness (``bench/``).

They need no chip: a run is driven through ``harness.main`` with the look
for a TPU replaced, on MnistNet1 at batch 4 (``bench/tests/data``), so the
served path, the window, the reference and the comparison all run here.
"""
import argparse
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402
import work  # noqa: E402

TINY = "mnistnet1.b4-inline"


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _tiny_root(tmp_path):
    """A checkout-like root whose one cell is MnistNet1 at batch 4."""
    (tmp_path / "bench/configs").mkdir(parents=True)
    (tmp_path / "bench/traffic").mkdir(parents=True)
    shutil.copy(DATA / "mnistnet1.json", tmp_path / "bench/configs")
    shutil.copy(DATA / "b4-inline.json", tmp_path / "bench/traffic")
    b = _bench()
    b["configs"] = [{"name": "mnistnet1", "source": "test",
                     "file": "bench/configs/mnistnet1.json", "reduced": [],
                     "why": "test"}]
    b["workloads"] = [{"name": TINY, "config": "mnistnet1",
                       "traffic": "b4-inline", "chips": 1, "why": "test"}]
    for m in b["end_to_end"] + b["per_layer"]:
        m.pop("workloads", None)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    (tmp_path / "bench/peaks.json").write_text(json.dumps(
        {"source": "test", "devices": {"cpu": {
            "int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}}))
    return tmp_path


def _run_tiny(tmp_path, capsys, monkeypatch, seconds=0.5, trace=0):
    import jax
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    root = _tiny_root(tmp_path)
    args = argparse.Namespace(workload=TINY, seed=2 ** 31 + 11,
                              seconds=seconds, trace=trace)
    rc = harness.main(args, time.perf_counter(), root=root,
                      require=lambda chips: jax.devices())
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


# -- resolution by name -------------------------------------------------------

@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_every_workload_resolves_by_name(cell):
    spec = harness.resolve(cell)
    cfg, cell_entry = spec["config"], spec["cell"]
    assert cfg["name"] == cell_entry["config"]
    assert spec["traffic"]["batch"] > 0
    assert (BENCH / "families" / f"{cfg['family']}.py").is_file()
    assert (BENCH / "refs" / f"{cfg['reference']}.py").is_file()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    assert {"setup_s", "img_per_s"} <= {m["name"] for m in spec["end_to_end"]}


def test_metrics_name_their_cells_and_moves():
    b = _bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        for c in m.get("workloads", cells):
            assert c in cells
            assert c in e2e[m["moves"]].get("workloads", cells)


def test_peaks_by_device_kind():
    peak = harness.peaks_for("TPU v5 lite")
    assert peak["int8_ops_per_s"] == 393e12
    assert peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v9 imaginary")


# -- the result line -------------------------------------------------------

def test_last_line_keys(tmp_path, capsys, monkeypatch):
    rc, res = _run_tiny(tmp_path, capsys, monkeypatch)
    assert rc == 0
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) >= {"setup_s", "img_per_s"}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["checks"]["logit_gap_max"]["value"] \
        <= res["checks"]["logit_gap_max"]["limit"]


def test_breakdown_lists_ops_and_gaps():
    red = {"top_ops": [("fusion.1", 3e6), ("custom-call.2", 1e6)],
           "gaps": [("bench.block", 2e6)]}
    b = harness.breakdown(red)
    assert b == {"device_ops": [["fusion.1", 0.003], ["custom-call.2", 0.001]],
                 "idle_gaps": [["bench.block", 0.002]]}


# -- rates over every query of the window ----------------------------------

class _StallServing:
    """Queries of 10 ms with one stall of 200 ms at query 5."""

    def __init__(self):
        self.calls = 0

    def query(self, q):
        dt = 0.2 if q == 5 else 0.01
        time.sleep(dt)
        self.calls += 1
        return None, dt


def test_window_counts_every_query_and_all_its_time():
    d = _StallServing()
    answers, online, window_s, errors = harness.serve_window(d, 0.3)
    assert not errors and len(answers) == d.calls == len(online)
    assert 0.2 in online                      # the stall is in the sample
    assert window_s >= sum(online)            # and in the window's time
    run = SimpleNamespace(images=4 * len(answers), window_s=window_s,
                      online_s=online)
    rate = harness.load_module(BENCH / "metrics/img_per_s.py", "m1").read(run)
    assert rate == pytest.approx(4 * len(answers) / window_s)


def test_p95_is_the_tail_of_all_queries():
    p95 = harness.load_module(BENCH / "metrics/query_ms_p95.py", "m2")
    quick = [0.1] * 90
    run = SimpleNamespace(online_s=quick + [1.0] * 10)
    assert p95.read(run) == pytest.approx(1000.0)  # 10 stalls in 100
    run = SimpleNamespace(online_s=quick + [1.0] * 10, images=400,
                          window_s=19.0)
    img = harness.load_module(BENCH / "metrics/img_per_s.py", "m3")
    assert img.read(run) == pytest.approx(400 / 19.0)


# -- work counted from shapes ------------------------------------------------

def test_plaintext_ops_and_kernel_work_by_hand():
    cfg = json.loads((BENCH / "configs/cifarnet2.json").read_text())
    first = cfg["layers"][:1]             # sepconv 3 -> 16, 3x3, pad 1
    # depthwise 32*32*9*3 MACs + pointwise 32*32*3*16 MACs, 2 ops each
    assert work.plaintext_ops_per_image(first, (32, 32, 3)) \
        == 2 * (1024 * 9 * 3 + 1024 * 3 * 16)
    launches = work.kernel_launches(first, (32, 32, 3), 32)
    # the pointwise part has k = 3 < 8: only the grouped launch runs
    assert [(l["part"], l["m"], l["k"], l["n"], l["groups"])
            for l in launches] == [("depthwise", 32768, 9, 1, 3)]
    ops, nbytes = work.launch_work(launches[0], ring_bits=32)
    # 3 parties x 2 ring products x 10 int8 partial products x 2 m k n g
    assert ops == 3 * 2 * 10 * 2 * 32768 * 9 * 1 * 3
    # input, weights and output share stacks, 4 bytes an element
    assert nbytes == 3 * 4 * (32 * 32 * 32 * 3 + 9 * 3 + 32 * 32 * 32 * 3)
    peak = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    t, bound = work.least_seconds(ops, nbytes, peak)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def test_launch_counts_match_the_served_nets():
    for name, n in (("cifarnet2", 18), ("cifarnet6", 16)):
        cfg = json.loads((BENCH / f"configs/{name}.json").read_text())
        assert len(work.kernel_launches(cfg["layers"], (32, 32, 3), 32)) == n


def test_configuration_matches_the_program_net():
    from repro.nn import bnn
    for name in ("cifarnet2", "cifarnet6"):
        cfg = json.loads((BENCH / f"configs/{name}.json").read_text())
        d = harness.load_module(BENCH / "families/bnn_classifier.py", "drv")
        d.Serving(cfg, {"batch": 1, "offline": "inline"}).check_program()
        assert tuple(bnn.INPUT_SHAPES[cfg["net"]]) == tuple(cfg["input_shape"])


# -- no chip, no result -----------------------------------------------------

def test_run_without_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run_cell.py"), "--workload",
         "cifarnet2.b32-inline", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()


# -- the comparison fails what it must ---------------------------------------

def _break_runner(monkeypatch, fault):
    """Wrap the program's inline runner so its answers come out wrong."""
    import numpy as np
    from repro.launch import serve_secure
    real = serve_secure.make_runner

    def make_runner(*a, **kw):
        run, mesh = real(*a, **kw)

        def broken(keys, xs):
            out = np.array(run(keys, xs))
            return fault(out)
        return broken, mesh
    monkeypatch.setattr(serve_secure, "make_runner", make_runner)


def _alter_one(out):
    out[0, 3] += 0.25
    return out


def _drop_half(out):
    half = out.shape[0] // 2
    out[half:] = out[:half].mean(axis=0)
    return out


@pytest.mark.parametrize("fault", [_alter_one, _drop_half],
                         ids=["answer_altered", "half_batch_left_out"])
def test_broken_path_is_not_correct(tmp_path, capsys, monkeypatch, fault):
    _break_runner(monkeypatch, fault)
    rc, res = _run_tiny(tmp_path, capsys, monkeypatch, seconds=0.2)
    assert rc == 0
    assert res["correct"] is False and res["failed"] > 0
    gap = res["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("name", ["cifarnet2", "cifarnet6"])
def test_bfloat16_control_is_not_correct(name):
    """The reference held in bfloat16, put in the program's place, at the
    configuration's widths (batch 4 here, 32 on the chip)."""
    import jax
    import numpy as np
    cfg = json.loads((BENCH / f"configs/{name}.json").read_text())
    drv = harness.load_module(BENCH / "families/bnn_classifier.py", "drv2")
    ref = harness.load_module(BENCH / "refs/bnn_fp32.py", "ref2")
    shape = tuple(cfg["input_shape"])
    params = drv.make_params(cfg["layers"], shape, cfg["weights"])
    x = drv.make_images(jax.random.PRNGKey(3), 1, 4, shape)[0]
    f32 = np.asarray(ref.forward(params, x, cfg["layers"], "float32"))
    bf16 = np.asarray(ref.forward(params, x, cfg["layers"], "bfloat16"))
    gap = float(np.abs(bf16 - f32).max())
    assert gap > cfg["correct"]["logit_gap_max"], gap


@pytest.mark.parametrize("fun, module", [("jit(run)", "jit_run"),
                                         ("jit(full)", "jit_full"),
                                         ("run", "run")])
def test_program_names_match_the_trace_modules(fun, module):
    assert harness.module_name(fun) == module
