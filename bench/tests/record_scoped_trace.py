"""Record a small profiler trace on the chip with the program's own names,
to keep as the recorded case of ``test_bench_scopes.py``
(``data/chip_trace.xplane.pb``).

Run on one TPU chip from the repository root:

    python bench/tests/record_scoped_trace.py [--out bench/tests/data]

Two jitted programs stand in for a served cell, as in
``record_trace.py``: ``online`` runs one RSS matmul kernel
(``repro.kernels.rss_matmul``) inside the scopes ``l0``/``l0.conv`` and
elementwise ring ops inside ``sign1``/``sign1.msb``, opened with
``repro.core.comm.scope`` as the executor opens them; ``generate`` draws
random ring elements, as the tape plant does.  Host spans are the
harness's (``bench.window``, ``bench.stage``, ``bench.dispatch``,
``bench.block``) and, inside every ``bench.stage``, the program's
``telemetry.span("tape_take")`` (``cbnn.tape_take`` in the trace), which
holds a deliberate host sleep in one query so the trace has an idle gap
labelled by the program span.  The run prints every plane and line, and
the first events of each with all their stats.
"""
import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

QUERIES = 4
SLEEP_S = 0.02


def dump(path, n_events=4):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"PLANE {plane.name!r} stats={list(plane.stats)[:6]}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r} events={len(events)}")
            for e in events[:n_events]:
                print(f"    {e.name[:300]!r} len={len(e.name)} "
                      f"start={e.start_ns} dur={e.duration_ns} "
                      f"stats={[(k, str(v)[:200]) for k, v in e.stats]}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(ROOT / "bench/tests/data"))
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from repro.core import comm, telemetry
    from repro.kernels.rss_matmul import precompute_weight_limbs, rss_matmul
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_scoped_trace.py needs a TPU")

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    w = precompute_weight_limbs(
        jax.random.bits(k1, (3, 256, 128), jnp.uint32))
    x = jax.random.bits(k2, (3, 512, 256), jnp.uint32)

    @jax.jit
    def online(x):
        with comm.scope("l0"), comm.scope("l0.conv"):
            y = rss_matmul(x * jnp.uint32(3) + jnp.uint32(1), w)
        with comm.scope("sign1"), comm.scope("sign1.msb"):
            return (y >> 12) ^ jnp.roll(y, 1, axis=0)

    @jax.jit
    def generate(key):
        return jax.random.bits(key, (4, 3, 512, 256), jnp.uint32)

    jax.block_until_ready((online(x), generate(k3)))
    tmp = Path(tempfile.mkdtemp(prefix="record_trace_"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # as the harness traces
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for q in range(QUERIES):
            with jax.profiler.TraceAnnotation("bench.stage"):
                with telemetry.span(f"tape_take[{q}]"):
                    tape = generate(jax.random.fold_in(k3, q))
                    if q == 1:
                        time.sleep(SLEEP_S)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = online(x ^ tape[0])
            with jax.profiler.TraceAnnotation("bench.block"):
                jax.block_until_ready(out)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp / "**/*.xplane.pb"), recursive=True))[-1]
    dump(path)
    os.makedirs(args.out, exist_ok=True)
    dest = Path(args.out) / "chip_trace.xplane.pb"
    shutil.copy(path, dest)
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"trace": str(dest), "bytes": dest.stat().st_size,
                      "device_kind": jax.devices()[0].device_kind,
                      "queries": QUERIES, "sleep_s": SLEEP_S}))


if __name__ == "__main__":
    main()
