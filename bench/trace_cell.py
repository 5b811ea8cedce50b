"""Run one cell once with ``--trace 1``, its trace reduced by the
program's own names (``bench/scope_reduce.py``).

    python3 bench/trace_cell.py --workload <name> --seed <n> \
        [--seconds <s>] [--keep <dir>]

From the root of a checkout, on a machine with the chips the cell asks
for.  It is the harness's own run (``bench/harness.py``, as ``run_cell.py
--trace 1`` runs it) with two things added: the reduction of
``scope_reduce.py`` in place of ``trace_reduce.py`` (the same keys and
more), and, on the result line, the per-layer metrics that read what it
adds (:data:`METRICS`).  Standard error gets the idle time summed by
label, the host's time outside every named span, the program spans per
query, the device ms per ledger head, per ledger tag and per protocol
class with their sum against ``protocol_ms_per_query``, and the
attribution table (``repro.core.telemetry.attribution``) with that
device time.  ``--keep`` copies the raw trace there, as
``<workload>.xplane.pb``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import scope_reduce  # noqa: E402

METRICS = [{"name": "protocol.linear_ms_per_query", "unit": "ms"},
           {"name": "protocol.sign_ms_per_query", "unit": "ms"},
           {"name": "protocol.maxpool_ms_per_query", "unit": "ms"},
           {"name": "stage_idle_share", "unit": "%"}]
CLASSES = ("linear", "sign", "maxpool", "affine", "output",
           scope_reduce.NO_SCOPE)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--keep", default=None)
    return ap.parse_args(argv)


def report(run, ledgers, protocol_ms):
    """The scope reduction's findings on standard error."""
    t, log = run.trace, harness.log
    online = run.programs.get("online")
    idle = t["window_ns"] - t["busy_ns"]
    named = sum(v for k, v in t["idle_by_label"].items()
                if k.startswith(("bench.", scope_reduce.PROGRAM_PREFIX)))
    log("idle by label, ms in the window (share of idle): " + ", ".join(
        f"{k} {v / 1e6:.3f} ({100 * v / max(idle, 1):.1f} %)"
        for k, v in sorted(t["idle_by_label"].items(), key=lambda kv: -kv[1]))
        + f"; in named spans {100 * named / max(idle, 1):.2f} % of "
        f"{idle / 1e6:.3f} ms idle; idle while the host is outside every "
        f"named span {t['idle_outside_ns'] / 1e6:.3f} ms, inside "
        f"{scope_reduce.STAGE_SPAN} {t['stage_idle_ns'] / 1e6:.3f} ms; "
        f"first device op {t['first_op_ns'] / 1e6:.3f} ms into the window")
    q = max(run.queries, 1)
    log("host ms per query outside every named span, by the spans around "
        "it: " + ", ".join(f"{k} {v / q / 1e6:.3f}" for k, v in sorted(
            t["host_outside_ns"].items(), key=lambda kv: -kv[1])))
    log("host spans per query: " + ", ".join(
        f"{k} {v / q:.2f}" for k, v in sorted(t["span_count"].items())))
    heads = t["head_ns"].get(online, {})
    per_q = {h: v / q / 1e6 for h, v in heads.items()}
    log("device ms per query by ledger head (kernels included): " + ", ".join(
        f"{h} {v:.3f}" for h, v in sorted(per_q.items(),
                                          key=lambda kv: -kv[1])))
    tags = t["tag_ns"].get(online, {})
    log("non-kernel device ms per query by ledger tag: " + ", ".join(
        f"{k} {v / q / 1e6:.3f}" for k, v in sorted(tags.items(),
                                                    key=lambda kv: -kv[1])))
    classes = t["class_ns"].get(online, {})
    parts = {c: classes.get(c, 0) / q / 1e6 for c in CLASSES}
    total = sum(parts.values())
    log("non-kernel device ms per query by class: " + ", ".join(
        f"{c} {v:.3f}" for c, v in parts.items())
        + f"; sum {total:.3f} against protocol_ms_per_query "
        + (f"{protocol_ms:.3f} ({100 * (total / protocol_ms - 1):+.2f} %)"
           if protocol_ms else "(not read)"))
    if ledgers:
        from repro.core import telemetry
        rep = telemetry.attribution(ledgers["predicted"], ledgers["ledger"],
                                    layer_ms=per_q)
        log("attribution per query, device ms from the trace:\n"
            + rep.render())


def main(argv=None):
    args = parse(argv)
    load_module, read_metrics = harness.load_module, harness.read_metrics
    ledgers = {}

    def keeping_load(path):
        if args.keep:
            Path(args.keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(path, Path(args.keep) / f"{args.workload}.xplane.pb")
        return scope_reduce.load(path)

    def load(path, name):
        if Path(path).name == "trace_reduce.py":
            return SimpleNamespace(load=keeping_load,
                                   reduce_trace=scope_reduce.reduce_trace)
        mod = load_module(path, name)
        if name == "bench_family":
            release = mod.Serving.release

            def release_after_ledger(self):
                from repro.core import cost_model
                from repro.core.secure_model import secure_infer_cost
                shape = (self.batch,) + self.input_shape
                ledgers["ledger"] = secure_infer_cost(self.model, shape)
                ledgers["predicted"] = cost_model.model_cost(self.model,
                                                             shape)
                release(self)
            mod.Serving.release = release_after_ledger
        return mod

    def read(specs, run):
        out = read_metrics(specs, run)
        if run.trace and "class_ns" in run.trace:
            out.update(read_metrics(METRICS, run))
            protocol = out.get("protocol_ms_per_query", {}).get("value")
            report(run, ledgers, protocol)
        return out

    harness.load_module, harness.read_metrics = load, read
    return harness.main(SimpleNamespace(workload=args.workload,
                                        seed=args.seed, seconds=args.seconds,
                                        trace=1), T_START)


if __name__ == "__main__":
    sys.exit(main())
