"""Readings that the limits of ``correct`` are set from, for one cell.

    python bench/calibrate.py --workload <name> --seeds 1,2,... \
        --seconds <s> [--control-seeds 1,2,3]

One process on the cell's chips builds and compiles the served program
once (the weights are fixed per configuration), then for each seed makes
that seed's queries, warms up and serves a window of ``--seconds`` exactly
as a benchmark run does, and keeps every answer.  Once the program's state
is freed, each seed's answers are compared with the float32 reference: the
lower reading is the largest ``logit_gap_max`` of these sound runs.  The
control is the same reference held in bfloat16 (``bench/refs``), put in
the program's place and compared the same way on the control seeds: the
upper reading is the smallest gap it gives.  The benchmark's own runs do
not run the control.

The last line of standard output is one JSON object with both readings.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    spec = harness.resolve(args.workload)
    config, traffic = spec["config"], spec["traffic"]
    sys.path.insert(0, str(harness.ROOT / "src"))
    import numpy as np
    harness.enable_cache()
    harness.require_chips(int(spec["cell"]["chips"]))
    drv = harness.load_module(
        harness.BENCH / "families" / f"{config['family']}.py", "cal_family")
    ref = harness.load_module(
        harness.BENCH / "refs" / f"{config['reference']}.py", "cal_ref")

    serving = drv.Serving(config, traffic, mark=harness.host_span)
    if args.seeds:
        serving.build()
    else:                            # the control alone needs no program
        serving.params = drv.make_params(config["layers"],
                                        tuple(config["input_shape"]),
                                        config["weights"])
    served = {}
    for seed in args.seeds:
        serving.load(seed)
        for q in range(int(traffic["warmup_queries"])):
            serving.query(-1 - q)
        answers, _, window_s, errors = harness.serve_window(
            serving, args.seconds)
        served[seed] = (answers, np.asarray(serving.images), errors)
        harness.log(f"seed {seed}: {len(answers)} queries in "
                    f"{window_s:.2f} s, errors {errors}")
    if args.seeds:
        serving.release()

    program = {}
    for seed, (answers, images, errors) in served.items():
        serving.images = images
        checks, _ = harness.compare(serving, answers,
                                    serving.reference(ref.forward),
                                    config["correct"])
        program[seed] = None if errors else checks["logit_gap_max"]["value"]
    control = {}
    for seed in args.control_seeds:
        serving.load_images(seed)
        f32 = serving.reference(ref.forward, "float32")
        low = serving.reference(ref.forward, "bfloat16")
        control[seed] = max(float(np.abs(a - b).max())
                            for a, b in zip(low, f32))
    sound = [v for v in program.values() if v is not None]
    out = {"workload": args.workload, "program": program,
           "control": control,
           "lower": max(sound) if sound else None,
           "upper": min(control.values()) if control else None,
           "limit": config["correct"]["logit_gap_max"]}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    t = time.perf_counter()
    main()
    harness.log(f"calibration took {time.perf_counter() - t:.1f} s")
