"""The benchmark harness: one run of one cell.

``run_cell.py`` parses the command line and calls :func:`main`.  Everything
that belongs to one configuration, traffic mix or metric is data or a file
of its own, found by name:

* ``BENCHMARK.json`` (the checkout's root) names the cell's configuration,
  traffic and chips, and the metrics with the cells that report them;
* ``bench/configs/<config>.json``: the model, its layer list, its weights
  recipe, the layout of the parties, the family and reference to use, and
  the limits of the comparison that decides ``correct``;
* ``bench/traffic/<traffic>.json``: batch, offline material, warm-up,
  window loop and the traced window's length;
* ``bench/families/<family>.py``: how a family of models is served through
  the program's served path; ``bench/refs/<reference>.py``: its plain
  reference;
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``,
  which returns a number or ``None`` when the run has nothing to read.

A run: set-up (weights, build, compile or cache load, queries, warm-up),
then a closed loop of one querier for ``--seconds`` (with ``--trace 1``, a
shorter window under the profiler), then the program's state is freed,
the reference runs, and every answer of the window is compared with it.
The last line of standard output is one JSON object.
"""
import collections
import contextlib
import importlib.util
import json
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_MISS = "/jax/compilation_cache/cache_misses"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload, root=ROOT):
    """The cell, its configuration and traffic, and the metrics it reports,
    all found by name from ``BENCHMARK.json``."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    config = load_json(root / entry["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def peaks_for(kind, root=ROOT):
    table = load_json(root / "bench" / "peaks.json")
    if kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table["devices"][kind]


def require_chips(chips):
    """The devices of this run, or exit: no TPU, or fewer chips than the
    cell asks for, ends the run before anything is built."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"needs {chips} TPU chip(s); JAX finds {len(devs)} "
            f"{devs[0].platform} device(s)")
        raise SystemExit(2)
    return devs


def enable_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program however small, and never evicted (an eviction limit
    set in the environment is overridden)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Events:
    """JAX's own compile and cache events, counted from set-up on, and the
    compile seconds of each program by name."""

    def __init__(self):
        import jax
        self.counts = collections.Counter()
        self.compile_s = 0.0
        self.by_program = collections.Counter()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        self.counts[name] += 1

    def _duration(self, name, secs, fun_name=None, **_):
        if name in COMPILE_EVENTS:
            self.compile_s += secs
            if fun_name:
                self.by_program[fun_name] += secs

    @contextlib.contextmanager
    def heaviest(self, into, role):
        """Name the program that took longest to compile inside the block
        (the served program of that step) as ``into[role]``."""
        before = collections.Counter(self.by_program)
        yield
        grown = self.by_program - before
        if grown:
            into[role] = module_name(grown.most_common(1)[0][0])


def module_name(fun_name):
    """The name XLA gives the module of a jitted function: ``jit(run)``
    compiles to ``jit_run``."""
    m = re.fullmatch(r"jit\((.*)\)", fun_name)
    return f"jit_{m.group(1)}" if m else fun_name


@contextlib.contextmanager
def host_span(name):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def trace_options():
    """Device ops and host spans; no Python function tracing, which would
    slow the host inside the window."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def serve_window(serving, seconds, first_q=0):
    """Closed loop, one querier: queries back to back until ``seconds``
    have passed; the query in flight then completes and counts.  Returns
    (answers, online seconds per query, window seconds, errors)."""
    answers, online, errors = [], [], []
    q = first_q
    with host_span("bench.window"):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            try:
                out, dt = serving.query(q)
            except Exception as e:            # a failed query is counted
                errors.append(f"query {q}: {type(e).__name__}: {e}")
                break
            answers.append((q, out))
            online.append(dt)
            q += 1
        window_s = time.perf_counter() - t0
    return answers, online, window_s, errors


def compare(serving, answers, refs, limits):
    """Every answer of the window against the reference of its batch.
    Returns (checks, failed queries)."""
    import numpy as np
    gap_max, failed = 0.0, 0
    for q, out in answers:
        ref = refs[serving.batch_of(q)]
        if out.shape != ref.shape or not np.isfinite(out).all():
            failed += 1                       # no answer to measure
            continue
        gap = float(np.abs(out.astype(np.float64) - ref).max())
        gap_max = max(gap_max, gap)
        if gap > limits["logit_gap_max"]:
            failed += 1
    checks = {"logit_gap_max": {"value": gap_max,
                                "limit": limits["logit_gap_max"]},
              "answers_failed": {"value": failed, "limit": 0}}
    return checks, failed


def memory_peak(devs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return max(peaks) if peaks else 0


def read_metrics(specs, run):
    out = {}
    for m in specs:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name']}")
        v = reader.read(run)
        if v is None:
            log(f"metric {m['name']}: nothing to read in this run")
            continue
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def breakdown(red):
    if red is None:
        return None
    return {"device_ops": [[n, ns / 1e9] for n, ns in red["top_ops"]],
            "idle_gaps": [[n, ns / 1e9] for n, ns in red["gaps"]]}


def main(args, t_start, root=ROOT, require=None):
    spec = resolve(args.workload, root)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    enable_cache()
    devs = (require or require_chips)(int(cell["chips"]))
    peak = peaks_for(devs[0].device_kind, root)
    events = Events()
    family_mod = load_module(BENCH / "families" / f"{config['family']}.py",
                             "bench_family")
    ref_mod = load_module(BENCH / "refs" / f"{config['reference']}.py",
                          "bench_reference")

    # -- set-up ------------------------------------------------------------
    spans = {}

    @contextlib.contextmanager
    def timed(name):
        t = time.perf_counter()
        with host_span(name):
            yield
        spans[name] = spans.get(name, 0.0) + time.perf_counter() - t

    serving = family_mod.Serving(config, traffic, mark=timed)
    programs = {}
    serving.build()
    with events.heaviest(programs, "generator"):
        serving.load(args.seed)
    with timed("bench.warmup"):
        with events.heaviest(programs, "online"):
            serving.query(-1)
        for q in range(1, int(traffic["warmup_queries"])):
            serving.query(-1 - q)
    setup_s = time.perf_counter() - t_start
    setup_compile_s = events.compile_s
    misses_setup = events.counts[CACHE_MISS]
    log(f"set-up {setup_s:.3f} s: compile events {events.compile_s:.3f} s, "
        f"cache hits {events.counts[CACHE_HIT]}, misses {misses_setup}, "
        f"programs {programs}; "
        + ", ".join(f"{k} {v:.3f} s" for k, v in spans.items()))

    # -- window --------------------------------------------------------------
    red, trace_dir = None, None
    seconds = float(args.seconds)
    serving.mark = host_span
    if args.trace:
        seconds = min(seconds, float(traffic["trace_seconds"]))
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir, profiler_options=trace_options())
    answers, online, window_s, errors = serve_window(serving, seconds)
    if args.trace:
        jax.profiler.stop_trace()
    window_misses = events.counts[CACHE_MISS] - misses_setup
    if window_misses:
        log(f"WARNING: {window_misses} compile(s) inside the window")
    mem = memory_peak(devs)
    if args.trace:
        trace_mod = load_module(BENCH / "trace_reduce.py", "bench_trace")
        paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        red = trace_mod.reduce_trace(trace_mod.load(paths[-1])) \
            if paths else None
        shutil.rmtree(trace_dir, ignore_errors=True)
        if red is not None:
            ms = lambda d: {k: round(v / 1e6, 3) for k, v in d.items()}
            log(f"trace: {red['devices']} device(s), kernel ms by name "
                f"{ms(red['by_kernel'])}, program executions "
                f"{red['module_count']}, busy ms {ms(red['module_ns'])}")
    queries = len(answers)
    images = queries * int(traffic["batch"])

    # -- correctness ------------------------------------------------------
    serving.release()
    refs = serving.reference(ref_mod.forward)
    checks, failed = compare(serving, answers, refs, config["correct"])
    failed += len(errors)
    for e in errors:
        log(e)
    correct = failed == 0 and queries > 0

    run = SimpleNamespace(  # what a metric reader may read
        cell=cell, config=config, traffic=traffic, peak=peak,
        setup_s=setup_s, spans=spans, compile_s=setup_compile_s,
        queries=queries, images=images, window_s=window_s, online_s=online,
        trace=red, programs=programs,
        work=load_module(BENCH / "work.py", "bench_work"))
    metrics = read_metrics(spec["per_layer" if args.trace else "end_to_end"],
                           run)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    if red is not None:
        device["busy_s"] = red["busy_ns"] / 1e9
        device["window_s"] = red["window_ns"] / 1e9
    result = {"correct": correct, "attempted": queries + len(errors),
              "failed": failed, "metrics": metrics, "device": device}
    if red is not None:
        result["breakdown"] = breakdown(red)
    log(f"window {window_s:.3f} s, {queries} queries, {images} images, "
        f"peak {mem} B")
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0
