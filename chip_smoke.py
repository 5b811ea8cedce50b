"""Chip smoke: secure CifarNet2/6 serving and the secure LM decode, once, on a TPU.

Drives ``repro.launch.serve_secure``'s own entry points (``build`` /
``make_runner`` / ``make_tape_runner`` / ``serve_pool`` / ``main``) in one
process, at CifarNet2's and CifarNet6's published widths (32x32x3 input;
the paper's binarized Fitnet with separable convolutions, and its
binarized VGG16), with random weights made from a seed.  Default phases,
all on one chip:

  (a) fail unless JAX's default device is a TPU;
  (b) CifarNet2, shared weights, inline material, local backend: the
      compiled runner holds one ``tpu_custom_call`` per kernel launch the
      cost model lists (the dense ones: the depthwise halves are direct
      tap products), its logits are bit-identical to the jnp reference
      lowering (``--no-kernel``) and within the fixed-point bounds of
      ``bnn_forward``;
  (c) the same with public weights;
  (d) CifarNet2 shared under the tape pool (``--offline pool``),
      bit-identical to (b)'s runner fed the same tape's session keys;
  (e) ``--model lm --quick``: token-identical to the fp32 oracle with one
      decode trace per bucket;
  (f) CifarNet6, shared weights, inline material, local backend: as (b),
      with weights on a unit-scale grid and logits equal to ``bnn_forward``'s
      (its 13 dense 3x3 convolutions run im2col and the dense kernel).

``--mesh`` (a host with at least three chips) runs the party mesh and what
it is compared with, and nothing else: CifarNet2 shared with one party per
device against the local backend on device 0, and the LM ``--quick`` decode
on ``make_secure_lm_mesh`` against the local one.

Seconds printed on the way are smoke timings, not metrics.  Any failed
phase exits non-zero; the last line of standard output is one JSON object
naming the device.

    python chip_smoke.py [--mesh]
"""
import argparse
import json
import os
import sys
import tempfile
import time
from contextlib import contextmanager
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

NET = "CifarNet2"
DEEP_NET = "CifarNet6"
# serving batch: the compiled CifarNet2 program needs 2.8 GB of one v5e
# chip's 16 GB at batch 32 (shared weights, memory_analysis)
BATCH = 32
QUERIES = 2
# fixed-point error bounds against the fp32 forward
# (tests/test_secure_model.py::test_secure_cifarnet2_statistical)
MEDIAN_ERR, MAX_ERR = 0.3, 8.0
KERNEL_CALL = 'custom_call_target="tpu_custom_call"'


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


@contextmanager
def phase(name):
    log(f"phase {name} ...")
    t0 = time.perf_counter()
    yield
    log(f"phase {name} passed ({time.perf_counter() - t0:.1f} s wall, "
        "smoke timing)")


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def require_tpu():
    """Phase (a): the device JAX runs on, or exit when it is no TPU."""
    from repro.launch.runtime import device_info
    dev = device_info()
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print(f"[chip_smoke] FAIL: no TPU (JAX runs on {dev['platform']})",
              file=sys.stderr)
        raise SystemExit(1)
    return dev


def query(batch, seed=0, net=NET):
    """serve_secure's query: ±0.5 pixels, secret-shared, and party keys."""
    import jax
    import numpy as np
    from repro.core import RING32, share
    from repro.core.randomness import Parties
    from repro.nn.bnn import INPUT_SHAPES
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 2, (batch,) + INPUT_SHAPES[net]).astype(
        np.float32) - 0.5
    xs = share(x, jax.random.PRNGKey(seed + 3), RING32)
    return x, xs.shares, Parties.setup(jax.random.PRNGKey(seed + 7)).keys


def grid_params(seed=0, net=NET, unit=False):
    """Random weights on a 1/8 grid with identity BN, as in
    tests/test_secure_model.py: with ±0.5 pixels every pre-activation sits
    at least 1/256 from the Sign boundary, far outside the fixed-point
    noise, so the secure run and the fp32 forward make the same Sign
    decisions.  ``unit``: each weight is its initial value over the
    tensor's standard deviation, rounded and clipped to ±2, over 8 (the
    benchmark's recipe); the fan-in scaled grid rounds every weight of a
    wide layer to zero."""
    import jax
    import jax.numpy as jnp
    from repro.nn import bnn

    def quant(path, p):
        name = str(path[-1].key)
        if name.endswith("_var"):
            return jnp.full_like(p, 1.0 - 1e-5)   # rsqrt(var + eps) == 1
        if name.endswith(("_mu", "_beta")):
            return jnp.zeros_like(p)
        if name.endswith("_g"):
            return jnp.ones_like(p)
        if p.ndim > 1 and unit:
            return jnp.clip(jnp.round(p / jnp.std(p)), -2, 2) / 8
        if p.ndim > 1:
            return jnp.round(p * 0.5 * 8) / 8
        return jnp.round(p * 8) / 8 + 1.0 / 256

    return jax.tree_util.tree_map_with_path(
        quant, bnn.init_bnn(jax.random.PRNGKey(seed), net))


def plaintext_logits(params, x, net=NET):
    """fp32 forward of the weights the secure model shares."""
    import jax
    import numpy as np
    from repro.nn import bnn
    with jax.default_matmul_precision("highest"):
        out, _ = bnn.bnn_forward(params, x, net)
    return np.asarray(out, np.float32)


def timed_compile(run, *args):
    t0 = time.perf_counter()
    compiled = run.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def serve_queries(run, keys, xs, queries):
    import jax
    import numpy as np
    t0 = time.perf_counter()
    for _ in range(queries):
        out = jax.block_until_ready(run(keys, xs))
    return np.asarray(out), time.perf_counter() - t0


def bnn_inline(weights, batch, queries, net=NET):
    """Phases (b)/(c)/(f): kernel runner vs jnp lowering vs fp32 forward
    (CifarNet6: unit-grid weights, logits equal to the fp32 forward's).
    Returns the model, the kernel runner's compiled program and its
    inputs."""
    import numpy as np
    from repro.core import cost_model
    from repro.launch.serve_secure import build, make_runner
    from repro.nn.bnn import INPUT_SHAPES

    exact = net == DEEP_NET
    x, xs, keys = query(batch, net=net)
    params = grid_params(net=net, unit=exact)
    model = build(net, True, weights, params=params)
    reqs = cost_model.model_cost(
        model, (batch,) + INPUT_SHAPES[net]).kernel_requests()
    run, _ = make_runner(model, "local", batch)
    compiled, csec = timed_compile(run, keys, xs)
    text = compiled.as_text()
    n_calls = text.count(KERNEL_CALL)
    log(f"{weights}: {len(reqs)} kernel launches listed, {n_calls} "
        f"tpu_custom_call in the compiled runner; compile {csec:.1f} s")
    check(n_calls == len(reqs),
          f"{n_calls} tpu_custom_call != {len(reqs)} listed launches")
    check("_grouped_shared_call" not in text,
          "a depthwise half launched the grouped shared-weight kernel")
    out, wall = serve_queries(compiled, keys, xs, queries)
    log(f"{weights}: {queries} queries of batch {batch} in {wall:.3f} s")

    ref_run, _ = make_runner(build(net, False, weights, params=params),
                             "local", batch)
    ref_compiled, rsec = timed_compile(ref_run, keys, xs)
    ref = np.asarray(ref_compiled(keys, xs))
    log(f"{weights}: jnp-lowering runner compile {rsec:.1f} s")
    check(out.shape == (batch, 10) and np.isfinite(out).all(),
          f"logits shape {out.shape} or non-finite values")
    check(np.array_equal(out, ref),
          f"kernel logits differ from the jnp lowering in "
          f"{int((out != ref).sum())} of {out.size} places")

    err = np.abs(out - plaintext_logits(params, x, net))
    log(f"{net} {weights}: |secure - fp32| median {np.median(err):.3g} "
        f"max {err.max():.3g}")
    if exact:
        check(err.max() == 0, "secure logits differ from the fp32 forward")
    else:
        check(np.median(err) < MEDIAN_ERR and err.max() < MAX_ERR,
              "secure logits outside the fixed-point bounds")
    return model, compiled, keys, xs


def bnn_pool(model, inline_run, keys, xs, batch, queries, seed=0):
    """Phase (d): the tape pool's online runner, bit-identical to the
    inline runner fed the session keys of the tape slice it consumed."""
    import jax
    import numpy as np
    from repro.core.preprocessing import (make_tape_generator,
                                          tape_session_keys, trace_material)
    from repro.launch.serve_secure import make_tape_runner, serve_pool
    from repro.nn.bnn import INPUT_SHAPES

    spec = trace_material(model, (batch,) + INPUT_SHAPES[NET])
    gen = make_tape_generator(spec)
    run, prepare, _ = make_tape_runner(model, spec, "local")
    depth = queries + 1          # one buffer: warm-up slice + each query
    master = jax.random.PRNGKey(seed + 11)
    out, online_s, total_s, refills = serve_pool(
        run, prepare, gen, spec, keys, xs, queries, depth, master)
    out = np.asarray(out)
    log(f"pool: {queries} queries, online {online_s:.3f} s, total "
        f"{total_s:.3f} s with staging, {refills} refills")
    # the last query consumed slice `queries` of buffer 0
    slot_keys = tape_session_keys(jax.random.fold_in(master, 0),
                                  depth)[queries]
    ref = np.asarray(inline_run(slot_keys, xs))
    check(np.array_equal(out, ref),
          f"tape-pool logits differ from inline in "
          f"{int((out != ref).sum())} of {out.size} places")


def lm_quick(backend):
    """serve_secure --model lm --quick: the CLI checks token identity with
    the fp32 oracle and one trace per bucket; returns its stats."""
    from repro.launch import serve_secure
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "lm.json")
        serve_secure.main(["--model", "lm", "--quick", "--backend", backend,
                           "--json", path])
        with open(path) as f:
            stats = json.load(f)
    check(stats["traces"] == 1, f"decode traced {stats['traces']}x")
    check(stats["device"]["platform"] == "tpu", f"ran on {stats['device']}")
    log(f"lm {backend}: tokens {stats['tokens']}, traces {stats['traces']}")
    return stats


def mesh_bnn(batch):
    """CifarNet2 shared, one party per device, vs local on device 0."""
    import jax
    import numpy as np
    from repro.launch.serve_secure import build, make_runner

    model = build(NET, True, "shared", params=grid_params())
    _, xs, keys = query(batch)
    run, mesh = make_runner(model, "mesh", batch)
    party_devs = list(mesh.devices.flat)
    log(f"mesh axes {dict(zip(mesh.axis_names, mesh.devices.shape))} on "
        f"devices {[d.id for d in party_devs]}")
    check(len({d.id for d in party_devs}) == 3,
          f"parties share devices: {party_devs}")
    t0 = time.perf_counter()
    out = np.asarray(run(keys, xs))
    log(f"mesh: first query (with compile) {time.perf_counter() - t0:.1f} s")
    peaks = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()}
    log(f"peak bytes in use per device after the mesh run: {peaks}")
    check(all(peaks[d.id] > 0 for d in party_devs),
          "a party device holds no memory: the parties did not spread")
    out_m, wall = serve_queries(run, keys, xs, QUERIES)
    check(np.array_equal(out, out_m), "mesh logits changed between queries")
    log(f"mesh: {QUERIES} queries of batch {batch} in {wall:.3f} s")

    local, _ = make_runner(model, "local", batch)
    ref = np.asarray(local(keys, xs))
    check(np.array_equal(out, ref),
          f"mesh logits differ from local in {int((out != ref).sum())} "
          f"of {out.size} places")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="run only the party mesh (needs >= 3 chips)")
    args = ap.parse_args(argv)

    with phase("a (device)"):
        dev = require_tpu()
    from repro.launch.runtime import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    if args.mesh:
        with phase("mesh: CifarNet2 shared, party mesh vs local"):
            mesh_bnn(BATCH)
        with phase("mesh: lm --quick, party mesh vs local"):
            toks = [lm_quick(b)["tokens"] for b in ("mesh", "local")]
            check(toks[0] == toks[1], f"mesh tokens {toks[0]} != {toks[1]}")
    else:
        with phase("b (CifarNet2 shared, inline, local)"):
            model, inline_run, keys, xs = bnn_inline("shared", BATCH,
                                                     QUERIES)
        with phase("c (CifarNet2 public, inline, local)"):
            bnn_inline("public", BATCH, QUERIES)
        with phase("d (CifarNet2 shared, tape pool)"):
            bnn_pool(model, inline_run, keys, xs, BATCH, QUERIES)
        with phase("e (lm --quick)"):
            lm_quick("local")
        with phase("f (CifarNet6 shared, inline, local)"):
            bnn_inline("shared", BATCH, QUERIES, net=DEEP_NET)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
