"""End-to-end secure-inference throughput: net × transport backend × batch
× deployment mode.

Rows land in BENCH_secure_e2e.json via

    PYTHONPATH=src python -m benchmarks.run --only secure \
        --json BENCH_secure_e2e.json

Each timing row runs the full CBNN protocol stack (compile-once cached-limb
models, fused rounds) through ``secure_infer``: the ``local`` backend is
the stacked single-program simulation, the ``mesh`` backend runs one party
per device over the size-3 party mesh axis (skipped with a stderr note
when fewer than 3 devices are visible — benchmarks/run.py raises the fake
host device count when the secure suite is requested).

Deployment-mode suffixes (DESIGN.md §11):

  (none)   binary-domain engine, shared weights (the default serving path)
  .arith   binarization-unaware ablation (binary_linear="off": post-Sign
           layers lifted to scale f and paying the full trunc opening)
  .wpub    public-weight deployment (weights="public": linear layers are
           local share algebra — zero wire bytes on post-Sign layers)

``secure.comm.<net>.<mode>.kb`` rows record the per-query ONLINE wire
kilobytes from the traced CommLedger in the us_per_call column, so the
bytes trajectory (arith > binary > public) is machine-readable in
BENCH_secure_e2e.json alongside the timings.

``secure.online.<net>.<backend>.b<batch>`` rows time the TAPE-BACKED
online phase (DESIGN.md §12): the model's MaterialSpec is traced once, a
MaterialTape is generated offline, and each query consumes a tape slice —
the compiled online program contains zero PRF work.  The ``.inline``
sibling times the SAME serving configuration (same net/batch/topology —
party-only mesh, jnp ring dots so the offline/online split is not
drowned by interpret-mode Pallas cost on CPU) drawing its randomness
inline; CI pins online-only strictly below that inline total on the mesh
backend.  The ``.amortized`` sibling folds the offline plant's per-query
generation cost back in.

``secure.verify.<net>.local.b<batch>.{off,opens,full}`` rows time the
integrity levels of DESIGN.md §14 on the same serving cell: CI pins
``opens`` within ~10% of the unverified ``off`` row, and this module
asserts all three produce bit-identical logits."""
from __future__ import annotations

import sys
import time

# (net, batch) cells; kept CI-sized — interpret-mode Pallas on CPU.
CELLS = [("MnistNet1", 8), ("MnistNet1", 32), ("MnistNet3", 4)]
# deployment-mode cells: (net, batch, mode, backends)
MODE_CELLS = [("MnistNet1", 8, "arith", ("local",)),
              ("MnistNet1", 8, "wpub", ("local", "mesh")),
              ("MnistNet3", 4, "wpub", ("local",))]
# offline-plant cells: (net, batch, backends) timed online-only vs a
# matched inline total, + amortized
ONLINE_CELLS = [("MnistNet1", 8, ("local", "mesh")),
                ("MnistNet3", 4, ("local", "mesh"))]
# verified-inference cells (DESIGN.md §14): off vs opens vs full on the
# local backend; CI pins opens within ~10% of off and bit-identity
VERIFY_CELLS = [("MnistNet3", 4)]
# observability cells (DESIGN.md §17): telemetry disabled vs full tracing
# on the same cell as the secure.<net>.local.b<batch> baseline; CI pins
# off within 5% of that untouched baseline and on within 15% of off
OBS_CELLS = [("MnistNet3", 4)]
COMM_NETS = ["MnistNet1", "MnistNet3"]
QUERIES = 3

# mode -> (weights, binary_linear) for serve_secure.build, so the bench
# measures exactly the model the serving launcher builds
_MODES = {"binary": ("shared", "auto"),
          "arith": ("shared", "off"),
          "wpub": ("public", "auto")}


def _compile(net: str, mode: str, use_kernel: bool = True):
    from repro.launch.serve_secure import build

    weights, binary_linear = _MODES[mode]
    return build(net, use_kernel, weights, binary_linear)


def _rows_for(net: str, batch: int, backend: str, mode: str = "binary"):
    import numpy as np
    import jax
    from repro.core import RING32, share
    from repro.core.randomness import Parties
    from repro.core.secure_model import secure_infer_cost
    from repro.launch.serve_secure import make_runner
    from repro.nn.bnn import INPUT_SHAPES

    shape = INPUT_SHAPES[net]
    model = _compile(net, mode)
    run, _ = make_runner(model, backend, batch)

    rng = np.random.default_rng(0)
    x = (rng.integers(0, 2, (batch,) + shape).astype(np.float32) - 0.5)
    xs = share(x, jax.random.PRNGKey(3), RING32)
    keys = Parties.setup(jax.random.PRNGKey(7)).keys

    np.asarray(run(keys, xs.shares))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(QUERIES):
        out = run(keys, xs.shares)
    np.asarray(out)
    us = (time.perf_counter() - t0) / QUERIES * 1e6

    led = secure_infer_cost(model, (batch,) + shape)
    ips = batch / (us / 1e6)
    suffix = "" if mode == "binary" else f".{mode}"
    return [(f"secure.{net}.{backend}.b{batch}{suffix}", us,
             f"{ips:.1f} img/s; {led.megabytes:.3f} MB/query; "
             f"{led.rounds} rounds")]


def _online_rows(net: str, batch: int, backends):
    """Tape-backed online latency vs a matched inline total (+ amortized
    incl. tape generation) per backend — the offline-plant rows."""
    import numpy as np
    import jax
    from repro.core import RING32, share
    from repro.core.preprocessing import (MaterialTape, make_tape_generator,
                                          tape_session_keys, trace_material)
    from repro.core.randomness import Parties
    from repro.core.rss import RSS
    from repro.core.secure_model import (make_secure_infer_mesh,
                                         secure_infer)
    from repro.launch.serve_secure import make_tape_runner
    from repro.nn.bnn import INPUT_SHAPES

    shape = INPUT_SHAPES[net]
    # jnp ring dots: the comparison isolates the offline/online split
    # rather than interpret-mode Pallas kernel cost (CPU CI)
    model = _compile(net, "binary", use_kernel=False)
    spec = trace_material(model, (batch,) + shape)
    gen = make_tape_generator(spec)
    depth = QUERIES

    rng = np.random.default_rng(0)
    x = (rng.integers(0, 2, (batch,) + shape).astype(np.float32) - 0.5)
    xs = share(x, jax.random.PRNGKey(3), RING32)
    keys = Parties.setup(jax.random.PRNGKey(7)).keys
    tape = MaterialTape(gen(tape_session_keys(jax.random.PRNGKey(11),
                                              depth)), spec, depth)
    jax.block_until_ready(tape.slabs)

    def timed(fn, n=QUERIES):
        jax.block_until_ready(fn(0))          # compile + warm
        best = float("inf")
        for q in range(n):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(q))
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    rows = []
    for backend in backends:
        # matched inline runner: same topology as the tape runner
        # (party-only mesh), drawing its randomness inline
        if backend == "local":
            jin = jax.jit(lambda k, xst: secure_infer(
                model, RSS(xst, model.ring), Parties(k)))
            run_inline = lambda q: jin(keys, xs.shares)
        else:
            mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:3]),
                                     ("party",))
            jin = jax.jit(make_secure_infer_mesh(model, mesh))
            run_inline = lambda q: jin(keys, xs.shares)
        run, prepare, _ = make_tape_runner(model, spec, backend)
        # dealer-side staging (slab pairing) happens ahead of the clock,
        # like serve_pool does per query
        staged = [prepare(xs.shares, tape.query_slice(q))
                  for q in range(depth)]
        jax.block_until_ready(staged)
        us_online = timed(lambda q: run(keys, staged[q]))
        us_inline = timed(run_inline)

        # amortized: regenerate the tape (the jitted plant is already
        # compiled) and serve the same queries from it
        t0 = time.perf_counter()
        tape2 = MaterialTape(gen(tape_session_keys(jax.random.PRNGKey(13),
                                                   depth)), spec, depth)
        out = None
        for q in range(QUERIES):
            out = run(keys, prepare(xs.shares, tape2.query_slice(q)))
        jax.block_until_ready(out)
        us_amort = (time.perf_counter() - t0) / QUERIES * 1e6

        ips = batch / (us_online / 1e6)
        rows.append((f"secure.online.{net}.{backend}.b{batch}", us_online,
                     f"{ips:.1f} img/s online-only; zero PRF in HLO; "
                     f"{us_inline / us_online:.2f}x vs inline"))
        rows.append((f"secure.online.{net}.{backend}.b{batch}.inline",
                     us_inline,
                     "matched inline total (same topology, jnp dots)"))
        rows.append((f"secure.online.{net}.{backend}.b{batch}.amortized",
                     us_amort,
                     f"incl. tape generation over depth-{depth} pool"))
    return rows


def _verify_rows(net: str, batch: int):
    """Verified-inference overhead (DESIGN.md §14): the same local serving
    cell at --verify off / opens / full.  The digest fold is a handful of
    uint32 multiply-reduces fused into the traced program plus one
    deferred compare-view exchange, so ``opens`` must stay within ~10% of
    the unverified row — CI pins that ratio from the JSON.  Verified and
    unverified outputs are asserted bit-identical here (the checks observe
    values, they never perturb them)."""
    import numpy as np
    import jax
    from repro.core import RING32, share
    from repro.core.randomness import Parties
    from repro.launch.serve_secure import make_runner
    from repro.nn.bnn import INPUT_SHAPES

    shape = INPUT_SHAPES[net]
    model = _compile(net, "binary")
    rng = np.random.default_rng(0)
    x = (rng.integers(0, 2, (batch,) + shape).astype(np.float32) - 0.5)
    xs = share(x, jax.random.PRNGKey(3), RING32)
    keys = Parties.setup(jax.random.PRNGKey(7)).keys

    rows, outs = [], {}
    for mode in ("off", "opens", "full"):
        run, _ = make_runner(model, "local", batch, verify=mode)
        outs[mode] = np.asarray(run(keys, xs.shares))  # compile + warm
        best = float("inf")
        for _ in range(QUERIES):
            t0 = time.perf_counter()
            np.asarray(run(keys, xs.shares))
            best = min(best, time.perf_counter() - t0)
        note = ("unverified baseline" if mode == "off" else
                f"{'opened values' if mode == 'opens' else 'opens + pair/send consistency'}"
                " cross-checked; one deferred digest round")
        rows.append((f"secure.verify.{net}.local.b{batch}.{mode}",
                     best * 1e6, note))
    assert np.array_equal(outs["off"], outs["opens"]) and \
        np.array_equal(outs["off"], outs["full"]), \
        "verified inference must be bit-identical to unverified"
    return rows


def _obs_rows(net: str, batch: int):
    """Telemetry overhead (DESIGN.md §17) on the SAME serving cell as the
    ``secure.<net>.local.b<batch>`` baseline row: ``off`` exercises the
    disabled-mode cost contract (every runtime hook is a module-level
    ``is None`` check), ``on`` runs full tracing + metrics — per-query
    spans, a latency histogram, and the comm-correlated trace export.
    Outputs are asserted bit-identical in both states, and the emitted
    trace must be Chrome-trace-schema valid."""
    import numpy as np
    import jax
    from repro.core import RING32, share, telemetry
    from repro.core.randomness import Parties
    from repro.launch.serve_secure import make_runner
    from repro.nn.bnn import INPUT_SHAPES

    shape = INPUT_SHAPES[net]
    model = _compile(net, "binary")
    run, _ = make_runner(model, "local", batch)
    rng = np.random.default_rng(0)
    x = (rng.integers(0, 2, (batch,) + shape).astype(np.float32) - 0.5)
    xs = share(x, jax.random.PRNGKey(3), RING32)
    keys = Parties.setup(jax.random.PRNGKey(7)).keys

    base = np.asarray(run(keys, xs.shares))   # compile + warm

    def best_of(fn):
        best = float("inf")
        for _ in range(QUERIES):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    us_off = best_of(lambda: run(keys, xs.shares))
    out_off = np.asarray(run(keys, xs.shares))

    tracer, reg = telemetry.Tracer(), telemetry.MetricsRegistry()
    with telemetry.tracing(tracer), telemetry.collecting(reg):
        with telemetry.span("jit_warmup", cat="compile"):
            out_on = np.asarray(run(keys, xs.shares))

        def one():
            with telemetry.span("query", cat="online", lane="parties"):
                tq = time.perf_counter()
                out = run(keys, xs.shares)
                jax.block_until_ready(out)
                telemetry.observe("query_latency_seconds",
                                  time.perf_counter() - tq)
            return out

        us_on = best_of(one)
    telemetry.validate_chrome_trace(tracer.chrome_trace())
    assert np.array_equal(base, out_off) and np.array_equal(base, out_on), \
        "telemetry must never change model outputs"
    return [(f"secure.obs.{net}.local.b{batch}.off", us_off,
             "telemetry disabled (module-level None checks only)"),
            (f"secure.obs.{net}.local.b{batch}.on", us_on,
             f"full tracing+metrics ({len(tracer.spans)} spans); "
             f"{us_on / us_off:.2f}x vs off")]


def _comm_rows(net: str):
    """Per-query online wire KB per deployment mode (batch 1) — the
    binary-domain byte trajectory, machine-readable in the JSON."""
    from repro.core.secure_model import secure_infer_cost
    from repro.nn.bnn import INPUT_SHAPES

    rows = []
    for mode in ("arith", "binary", "wpub"):
        # the ledger is trace-only (jax.eval_shape) and kernel-agnostic:
        # skip the limb-decomposition setup work
        model = _compile(net, mode, use_kernel=False)
        led = secure_infer_cost(model, (1,) + INPUT_SHAPES[net])
        rows.append((f"secure.comm.{net}.{mode}.kb", led.nbytes / 1e3,
                     f"{led.rounds} online rounds; "
                     f"{led.pre_nbytes/1e3:.1f} KB offline"))
    return rows


def secure_e2e():
    import jax

    rows = []
    backends = ["local"]
    if len(jax.devices()) >= 3:
        backends.append("mesh")
    else:
        print("secure: <3 devices, skipping mesh backend rows "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)",
              file=sys.stderr)
    for net, batch in CELLS:
        for backend in backends:
            rows.extend(_rows_for(net, batch, backend))
    for net, batch, mode, wanted in MODE_CELLS:
        for backend in wanted:
            if backend in backends:
                rows.extend(_rows_for(net, batch, backend, mode))
    for net, batch, wanted in ONLINE_CELLS:
        rows.extend(_online_rows(net, batch,
                                 [b for b in wanted if b in backends]))
    for net, batch in VERIFY_CELLS:
        rows.extend(_verify_rows(net, batch))
    for net, batch in OBS_CELLS:
        rows.extend(_obs_rows(net, batch))
    for net in COMM_NETS:
        rows.extend(_comm_rows(net))
    # secure LM serving rows (DESIGN.md §16): measured decode/prefill
    # tokens/sec + per-token comm, customized vs softmax
    from . import secure_lm
    rows.extend(secure_lm.lm_rows())
    return rows
