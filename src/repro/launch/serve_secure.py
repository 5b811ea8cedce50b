"""Secure serving launcher: batched secure-BNN inference end to end.

The first end-to-end secure serving path (DESIGN.md §1/§2): the model owner
compiles once (``compile_secure`` — BN fusing + secret sharing + cached
weight limbs for the fused 3-party Pallas kernel), then every query batch
runs the full CBNN protocol stack under either transport backend:

  * ``--backend local`` — stacked single-program simulation
    (LocalTransport); communication is accounted, not performed.
  * ``--backend mesh``  — one party per device over a size-3 "party" mesh
    axis (MeshTransport): reshares are ppermutes, openings are all_gathers,
    and the query batch is sharded over the remaining devices as a §6
    "data" axis when the batch divides.

``--weights`` selects the deployment scenario (DESIGN.md §11, README
"Threat model & deployment scenarios"):

  * ``shared`` (default) — the model is secret-shared too; post-Sign
    layers run the bin-shared reshare-only path.
  * ``public`` — private input, public model: linear layers are local
    share algebra (zero wire bytes on post-Sign layers) and the kernel
    uses the adaptive public limb collapse.

``--offline`` selects the preprocessing phase (DESIGN.md §12):

  * ``inline`` (default) — correlated randomness (PRF zero shares, trunc
    pads, MSB material, OT masks) is drawn inside the online query.
  * ``pool`` — the offline plant: the model's MaterialSpec is traced
    once, a double-buffered pool of ``--pool-depth`` consumable
    MaterialTapes is generated ahead of traffic (one jitted launch per
    refill, dispatched while online batches run), and every query
    consumes a tape slice — the compiled online program contains ZERO
    PRF work, so online-only latency drops below the inline total.

``--verify`` selects the integrity level (DESIGN.md §14):

  * ``off`` (default) — semi-honest execution, no checks.
  * ``opens`` — every opened value is cross-checked across the redundant
    share views via one deferred compare-view digest exchange per
    inference; a mismatch aborts with the offending layer/op/round/party.
  * ``full`` — additionally checks reshare/send pair consistency, the
    ingested model shares, and every consumed tape slice's structure.

Reports throughput (online-only vs amortized-total under ``pool``) plus
the per-query CommLedger and its modeled LAN/WAN wall-clock, total and
online-only.

  PYTHONPATH=src python -m repro.launch.serve_secure --net MnistNet1 \
      --backend mesh --batch 32 --queries 4 --offline pool --pool-depth 8
"""
import argparse
import json
import os
import sys
import time

from repro.launch.runtime import device_info, enable_compile_cache


def build(net: str, use_kernel: bool, weights: str = "shared",
          binary_linear: str = "auto", deployment=None, params=None):
    """Compile ``params`` (default: ``init_bnn`` weights from seed 0) into
    the secure model."""
    import jax
    from repro.core import RING32
    from repro.core.secure_model import compile_secure
    from repro.nn import bnn

    if params is None:
        params = bnn.init_bnn(jax.random.PRNGKey(0), net)
    model = compile_secure(params, net, jax.random.PRNGKey(1), RING32,
                           use_kernel_dot=use_kernel, weights=weights,
                           binary_linear=binary_linear,
                           deployment=deployment)
    return model


def party_devices() -> list:
    """The devices a mesh backend lays its parties on, one party per
    device; exits when there are fewer than three."""
    import jax
    devs = jax.devices()
    if len(devs) < 3:
        raise SystemExit(
            f"mesh backend runs one party per device and needs 3 devices; "
            f"found {len(devs)} {devs[0].platform} device(s)")
    return devs


def make_runner(model, backend: str, batch: int, party_axis: str = "party",
                verify: str = "off"):
    """Compile-once runner fn(keys, x_stack) -> (B, classes) logits.

    ``verify`` selects the integrity level (DESIGN.md §14): ``"opens"``
    cross-checks every opened value across the redundant share views,
    ``"full"`` additionally checks reshare/send pair consistency.  The
    verified program returns a digest report alongside the logits; the
    wrapper checks it on the host and raises
    :class:`~repro.core.integrity.IntegrityError` (with the offending
    layer/op/round/party) before releasing an output."""
    import jax
    import numpy as np
    from repro.core import integrity
    from repro.core.rss import RSS
    from repro.core.secure_model import make_secure_infer_mesh, secure_infer
    from repro.core.randomness import Parties

    v = None if verify == "off" else integrity.Verifier(verify)
    if backend == "local":
        if v is None:
            def run(keys, x_stack):
                return secure_infer(model, RSS(x_stack, model.ring),
                                    Parties(keys))
            return jax.jit(run), None

        def raw(keys, x_stack):
            with integrity.verify_scope(v):
                out = secure_infer(model, RSS(x_stack, model.ring),
                                   Parties(keys))
                return out, v.traced_report()
        jitted = jax.jit(raw)

        def run(keys, x_stack):
            out, rep = jitted(keys, x_stack)
            v.check(rep)
            return out
        return run, None

    devices = party_devices()
    # the digest report layout is per-party: verified mesh runs party-only
    data = 1 if v is not None else \
        max(d for d in range(1, len(devices) // 3 + 1) if batch % d == 0)
    devs = np.asarray(devices[:3 * data])
    if data > 1:
        mesh = jax.sharding.Mesh(devs.reshape(3, data), (party_axis, "data"))
        fn = make_secure_infer_mesh(model, mesh, batch_axis="data")
    else:
        mesh = jax.sharding.Mesh(devs, (party_axis,))
        fn = make_secure_infer_mesh(model, mesh, verifier=v)
    jitted = jax.jit(fn)
    if v is None:
        return (lambda keys, x_stack: jitted(keys, x_stack)[0]), mesh

    def run(keys, x_stack):
        out, rep = jitted(keys, x_stack)
        v.check(rep)
        return out[0]
    return run, mesh


def make_tape_runner(model, spec, backend: str, party_axis: str = "party",
                     verify: str = "off"):
    """Compile-once ONLINE phase for a MaterialTape (DESIGN.md §12),
    returned as ``(run, prepare, mesh)``: ``prepare(x_stack, slabs)`` is
    the dealer-side staging (under ``mesh`` it builds the pre-paired slab
    copies — offline-phase work, outside the compiled online program and
    outside online timing) and ``run(keys, prepared) -> logits`` is the
    PRF-free online step.  ``verify`` as in :func:`make_runner`."""
    import jax
    import numpy as np
    from repro.core import integrity
    from repro.core.preprocessing import make_tape_infer
    from repro.core.secure_model import make_secure_infer_mesh

    v = None if verify == "off" else integrity.Verifier(verify)
    if backend == "local":
        base = make_tape_infer(model, spec)
        if v is None:
            jitted = jax.jit(base)
            return (lambda keys, prepared: jitted(keys, *prepared),
                    lambda x_stack, slabs: (x_stack, slabs), None)

        def raw(keys, x_stack, slabs):
            with integrity.verify_scope(v):
                out = base(keys, x_stack, slabs)
                return out, v.traced_report()
        jitted = jax.jit(raw)

        def run(keys, prepared):
            out, rep = jitted(keys, *prepared)
            v.check(rep)
            return out
        return run, (lambda x_stack, slabs: (x_stack, slabs)), None
    # tape material is traced at the global batch: party-only mesh
    mesh = jax.sharding.Mesh(np.asarray(party_devices()[:3]), (party_axis,))
    fn = make_secure_infer_mesh(model, mesh, tape_spec=spec, verifier=v)
    jitted = jax.jit(fn)
    if v is None:
        return (lambda keys, prepared: jitted(keys, prepared)[0],
                fn.prepare, mesh)

    def run(keys, prepared):
        out, rep = jitted(keys, prepared)
        v.check(rep)
        return out[0]
    return run, fn.prepare, mesh


def serve_pool(run, prepare, gen, spec, keys, xs_shares, queries: int,
               depth: int, master_key, verify: str = "off"):
    """Serve ``queries`` batches from a demand-gated :class:`TapePool`
    (double-buffered: the next refill is dispatched while online batches
    run).  Per query, the dealer-side ``prepare`` staging runs outside
    the online timer.  The pool generates exactly
    ``ceil((queries + 1) / depth)`` buffers — a trailing partial buffer
    costs only the refills it needs — and turns over-consumption into
    backpressure (block + warn) and then a typed
    :class:`~repro.core.integrity.PoolExhaustedError` instead of silent
    material reuse.  Returns (outputs, online_s, total_s, refills)."""
    import jax
    from repro.core import telemetry
    from repro.core.preprocessing import TapePool

    if queries < 1:
        raise ValueError(f"queries must be >= 1, got {queries}")
    # +1: the compile warm-up consumes one slice before the timed loop
    pool = TapePool(gen, spec, depth, master_key, demand=queries + 1,
                    verify=verify == "full")
    with telemetry.span("jit_warmup", cat="compile"):
        jax.block_until_ready(run(keys, prepare(xs_shares, pool.take())))

    out = None
    online_s = 0.0
    t0 = time.perf_counter()
    for qi in range(queries):
        prepared = prepare(xs_shares, pool.take())
        jax.block_until_ready(prepared)   # staging done before the clock
        t1 = time.perf_counter()
        with telemetry.span(f"query[{qi}]", cat="online", lane="parties"):
            out = run(keys, prepared)
            jax.block_until_ready(out)
        dq = time.perf_counter() - t1
        online_s += dq
        telemetry.observe("query_latency_seconds", dq)
    total_s = time.perf_counter() - t0
    return out, online_s, total_s, pool.refills


def serve_lm(args, ap):
    """Telemetry-wrapped entry for :func:`_serve_lm` (--model lm)."""
    from repro.core import telemetry
    tracer, reg = make_obs(args, parties=3 if args.backend == "mesh" else 0)
    with telemetry.tracing(tracer), telemetry.collecting(reg):
        return _serve_lm(args, ap, tracer, reg)


def _serve_lm(args, ap, tracer=None, reg=None):
    """Secure autoregressive LM serving (DESIGN.md §16): scanned secure
    prefill of the prompt, then a greedy decode loop whose step program is
    compiled ONCE per padded bucket length (the cache is bucket-shaped and
    the position is a traced argument, so every token reuses the program —
    the trace count is asserted).  Reports tokens/sec and the byte-exact
    comm-per-token next to the §16 closed-form prediction; ``--quick``
    additionally pins token parity against the fp32 oracle."""
    import jax
    import numpy as np
    from repro.core import RING32, comm, cost_model, telemetry
    from repro.core.secure_transformer import (
        CompiledDecodeStep, init_kv_cache, make_secure_lm_mesh,
        plaintext_lm_forward, scan_prefill, secure_decode_step,
        share_lm_params)

    if args.quick:
        # CI-smoke preset: 1 block with the static-norm customization, so
        # the two jits (prefill scan + decode step) compile in ~a minute
        # each on XLA CPU — compile time scales with protocol-op count and
        # the Newton-rsqrt ladders dominate it (DESIGN.md §16).  The full
        # RMSNorm path runs eagerly in tests/test_secure_transformer.py.
        d, heads, d_ff, blocks, vocab = 16, 2, 32, 1, 16
        prompt_len, gen = 3, 5
        buckets = [8]
        args.static_norm = True
    else:
        d, heads, d_ff = args.lm_d, args.lm_heads, args.lm_ffn
        blocks, vocab = args.lm_blocks, args.lm_vocab
        prompt_len, gen = args.prompt, args.gen
        buckets = sorted(int(b) for b in args.buckets.split(","))
    if d % heads:
        ap.error(f"--lm-d {d} must divide by --lm-heads {heads}")
    need = prompt_len + gen
    fitting = [b for b in buckets if b >= need]
    if not fitting:
        ap.error(f"no bucket in {buckets} fits prompt+gen = {need}; "
                 "grow --buckets or shrink --prompt/--gen")
    bucket = fitting[0]   # bucket policy: smallest padded length that fits
    customized = not args.softmax_attention
    static_norm = args.static_norm

    lm, plain = share_lm_params(jax.random.PRNGKey(args.seed + 1), vocab, d,
                                heads, d_ff, blocks, RING32)
    keys = jax.random.split(jax.random.PRNGKey(args.seed + 7), 3)
    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(0, vocab, prompt_len).astype(np.int32)

    # per-token comm: the live ledger of ONE decode step, cross-checked
    # byte-exact against the §16 closed form (same abort contract as the
    # BNN path — serving never runs on a drifted cost table)
    with telemetry.span("ledger_estimate", cat="setup", bucket=bucket):
        led = comm.estimate_cost(
            lambda c, t, p, k: secure_decode_step(lm, c, t, p, k, customized,
                                                  static_norm),
            init_kv_cache(blocks, heads, d // heads, bucket, RING32),
            jnp_scalar(0), jnp_scalar(0), keys)
    pred = cost_model.lm_step_cost(bucket, d, heads, d_ff, blocks, vocab,
                                   RING32.nbytes, customized=customized,
                                   static_norm=static_norm)
    pred_ok = (pred.rounds, pred.nbytes) == (led.rounds, led.nbytes)
    print(f"[serve_secure] lm cost model: predicted {pred.rounds} rounds / "
          f"{pred.nbytes:,} B/token vs measured {led.rounds} / "
          f"{led.nbytes:,} B -> {'exact' if pred_ok else 'MISMATCH'}")
    if not pred_ok:
        raise SystemExit("cost-model prediction diverged from the ledger")

    # one compiled step per padded bucket length
    slots = 3
    if args.backend == "mesh":
        mesh = jax.sharding.Mesh(np.asarray(party_devices()[:3]), ("party",))
        print(f"[serve_secure] mesh axes "
              f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
        mesh_step = make_secure_lm_mesh(lm, mesh, customized, static_norm)
        steps = {bucket: CompiledDecodeStep(step_fn=mesh_step,
                                            bucket=bucket)}
        slots = 6   # global pair layout circulates through shard_map
    else:
        steps = {bucket: CompiledDecodeStep(lm, customized, static_norm,
                                            bucket=bucket)}
    step = steps[bucket]
    prefill = jax.jit(lambda c, t: scan_prefill(step.raw, c, t, keys))

    def one_generation():
        cache = init_kv_cache(blocks, heads, d // heads, bucket, RING32,
                              slots=slots)
        with telemetry.span(f"prefill[{prompt_len}]", cat="online",
                            lane="parties"):
            lgs, cache = prefill(cache, prompt)
            lg = np.asarray(lgs)[-1]
        toks = []
        for p in range(prompt_len, prompt_len + gen):
            nxt = int(np.argmax(lg))   # public greedy selection
            toks.append(nxt)
            if p == prompt_len + gen - 1:
                break
            tq = time.perf_counter()
            lg, cache = step(cache, jnp_scalar(nxt), jnp_scalar(p), keys)
            lg = np.asarray(lg)
            telemetry.observe("token_latency_seconds",
                              time.perf_counter() - tq, bucket=str(bucket))
        return toks

    with telemetry.span("jit_warmup", cat="compile", bucket=bucket):
        toks = one_generation()         # compile warm-up
    t0 = time.time()
    for _ in range(args.queries):
        toks = one_generation()
    dt = time.time() - t0
    tps = args.queries * gen / dt
    assert step.traces == 1, (
        f"decode step retraced {step.traces}x for one bucket length")
    print(f"[serve_secure] lm backend={args.backend} "
          f"{'customized' if customized else 'softmax'}"
          f"{'+static-norm' if static_norm else ''} d={d} heads={heads} "
          f"blocks={blocks} vocab={vocab} bucket={bucket}: "
          f"{args.queries}x{gen} tokens in {dt:.2f}s = {tps:.2f} tok/s "
          f"(1 trace/bucket)")
    print(f"[serve_secure] per-token comm: {led.nbytes / 1e3:.1f} KB online "
          f"({led.rounds} rounds) + {led.pre_nbytes / 1e3:.1f} KB offline "
          f"({led.pre_rounds} rounds); modeled LAN "
          f"{led.time(comm.LAN) * 1e3:.1f} ms / WAN "
          f"{led.time(comm.WAN) * 1e3:.0f} ms per token")

    stats = {"model": "lm", "backend": args.backend,
             "customized": customized, "static_norm": static_norm,
             "d": d, "heads": heads,
             "blocks": blocks, "vocab": vocab, "bucket": bucket,
             "prompt": prompt_len, "gen": gen, "tok_per_s": tps,
             "comm_kb_per_token": led.nbytes / 1e3, "rounds_per_token":
             led.rounds, "predicted_rounds": pred.rounds,
             "traces": step.traces, "tokens": toks,
             "device": device_info()}

    emit_obs(args, tracer, reg, led, online_s=dt,
             queries=args.queries * gen, unit="token")

    if args.quick:
        # token-identical to the fp32 oracle's greedy rollout
        otoks, cur = [], list(prompt)
        for _ in range(gen):
            olg = plaintext_lm_forward(plain, np.asarray(cur, np.int32),
                                       heads, customized, bucket,
                                       static_norm)
            otoks.append(int(olg[-1].argmax()))
            cur.append(otoks[-1])
        if toks != otoks:
            raise SystemExit(f"secure decode diverged from oracle: "
                             f"{toks} vs {otoks}")
        print(f"[serve_secure] quick check OK: {gen} greedy tokens "
              f"token-identical to the fp32 oracle ({toks})")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(stats, f, indent=2)
        print(f"[serve_secure] wrote {args.json}")


def jnp_scalar(v):
    import jax.numpy as jnp
    return jnp.asarray(v, jnp.int32)


def make_obs(args, parties: int = 0):
    """``--trace``/``--metrics-*`` -> (Tracer | None, Registry | None).

    ``parties`` > 0 (the mesh backend) fans ``lane="parties"`` spans out
    into one trace lane per party (DESIGN.md §17)."""
    from repro.core import telemetry
    if not (args.trace or args.metrics_json or args.metrics_prom):
        return None, None
    return telemetry.Tracer(parties=parties), telemetry.MetricsRegistry()


def emit_obs(args, tracer, reg, led, predicted=None, model=None,
             online_s=None, queries=1, unit="query"):
    """Write the ``--trace``/``--metrics-*`` artifacts and print the
    predicted-vs-measured attribution table (DESIGN.md §17).  Measured
    rounds/bytes per row come straight from the live ledger and sum to
    its totals exactly.  Its device-time column needs a profiler trace
    reduced by layer head, which this entry point does not take: it
    shows ``-``, and ``online_s`` over ``queries`` units only labels
    the report."""
    from repro.core import telemetry
    if tracer is None and reg is None:
        return None
    if reg is not None:
        reg.record_ledger(led, model, queries=queries)
    per_q = online_s / queries if online_s and queries else None
    rep = telemetry.attribution(predicted, led, online_s=per_q,
                                deployment=args.deployment)
    print(f"[serve_secure] attribution per {unit} "
          f"(deployment={rep.deployment}, "
          f"{'prediction exact' if rep.exact else 'prediction DIVERGED'}):")
    print(rep.render())
    if tracer is not None:
        print("[serve_secure] phases: "
              + "  ".join(f"{k}={v * 1e3:.1f}ms" for k, v in
                          sorted(tracer.phase_seconds().items())))
        if args.trace:
            tracer.write(args.trace)
            print(f"[serve_secure] wrote trace {args.trace} "
                  f"({len(tracer.spans)} spans; open in Perfetto or "
                  "chrome://tracing)")
    if args.metrics_json:
        reg.write_json(args.metrics_json)
        print(f"[serve_secure] wrote metrics {args.metrics_json}")
    if args.metrics_prom:
        reg.write_prom(args.metrics_prom)
        print(f"[serve_secure] wrote metrics {args.metrics_prom}")
    return rep


def main(argv=None):
    # only the CLI mutates the env (importing this module must not); the
    # flag works only before jax initializes
    if "jax" not in sys.modules:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8")
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=("bnn", "lm"), default="bnn",
                    help="serve the BNN classifier zoo or the secure "
                         "autoregressive LM decode loop (DESIGN.md §16)")
    ap.add_argument("--net", default="MnistNet1")
    ap.add_argument("--backend", choices=("local", "mesh"), default="local")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--queries", type=int, default=4)
    ap.add_argument("--no-kernel", action="store_true",
                    help="skip the fused Pallas kernel (jnp ring dots)")
    ap.add_argument("--weights", choices=("shared", "public"),
                    default="shared",
                    help="deployment scenario: secret-shared model (full "
                         "CBNN guarantees) or public model (private input "
                         "only; linear layers cost zero wire bytes)")
    ap.add_argument("--binary-linear", choices=("auto", "generic", "off"),
                    default="auto",
                    help="post-Sign linear routing (DESIGN.md §11): the "
                         "binary-domain engine, the generic Alg-2 "
                         "reference, or the binarization-unaware ablation")
    ap.add_argument("--deployment", default=None, metavar="NAME",
                    help="deployment descriptor the protocol-path solver "
                         "optimizes for (DESIGN.md §15): lan, wan, or "
                         "local; default keeps the lexicographic "
                         "(bytes, rounds) assignment")
    ap.add_argument("--offline", choices=("inline", "pool"),
                    default="inline",
                    help="preprocessing phase (DESIGN.md §12): draw "
                         "correlated randomness inside the online query, "
                         "or serve from a double-buffered MaterialTape "
                         "pool generated ahead of traffic")
    ap.add_argument("--pool-depth", type=int, default=None, metavar="K",
                    help="queries of material per tape buffer (pool mode "
                         "only; default 8)")
    ap.add_argument("--verify", choices=("off", "opens", "full"),
                    default="off",
                    help="integrity level (DESIGN.md §14): cross-check "
                         "opened values across redundant share views "
                         "(opens), plus reshare/send pair consistency and "
                         "tape-slab structure (full); any deviation aborts "
                         "with the offending layer/op/round/party")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the query generator and sharing keys")
    ap.add_argument("--json", default="", metavar="PATH")
    obs = ap.add_argument_group("observability (DESIGN.md §17)")
    obs.add_argument("--trace", default="", metavar="PATH",
                     help="write a Chrome trace-event JSON of the run "
                          "(compile / offline / online / verify spans with "
                          "per-op comm annotations; open in Perfetto or "
                          "chrome://tracing)")
    obs.add_argument("--metrics-json", default="", metavar="PATH",
                     help="write the metrics registry (comm counters, "
                          "latency histograms with p50/p95/p99, pool "
                          "gauges) as JSON")
    obs.add_argument("--metrics-prom", default="", metavar="PATH",
                     help="write the same metrics in Prometheus text "
                          "exposition format")
    lm = ap.add_argument_group("lm serving (--model lm, DESIGN.md §16)")
    lm.add_argument("--lm-d", type=int, default=32, metavar="D",
                    help="model width")
    lm.add_argument("--lm-heads", type=int, default=2)
    lm.add_argument("--lm-ffn", type=int, default=64)
    lm.add_argument("--lm-blocks", type=int, default=2)
    lm.add_argument("--lm-vocab", type=int, default=32)
    lm.add_argument("--prompt", type=int, default=4, metavar="T",
                    help="prompt length (synthetic random tokens)")
    lm.add_argument("--gen", type=int, default=8, metavar="N",
                    help="tokens to generate greedily")
    lm.add_argument("--buckets", default="16,32", metavar="L1,L2",
                    help="padded decode lengths; the smallest bucket >= "
                         "prompt+gen is compiled (once)")
    lm.add_argument("--softmax-attention", action="store_true",
                    help="serve the un-customized comparison mode (full "
                         "secure softmax) instead of ReLU-attention")
    lm.add_argument("--static-norm", action="store_true",
                    help="CBNN norm customization: RMSNorm folded into the "
                         "adjacent linear at setup — zero online rounds "
                         "and much faster decode-jit compiles")
    lm.add_argument("--quick", action="store_true",
                    help="small static-norm preset + token-parity check "
                         "against the fp32 oracle (the CI smoke)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    dev = device_info()
    print(f"[serve_secure] device: {dev['platform']} {dev['kind']} "
          f"x{dev['count']}")
    if args.model == "lm":
        if args.quick and args.queries == 4:
            args.queries = 1
        return serve_lm(args, ap)
    for flag, dflt in (("quick", False), ("softmax_attention", False),
                       ("static_norm", False)):
        if getattr(args, flag) != dflt:
            ap.error(f"--{flag.replace('_', '-')} requires --model lm")
    return serve_bnn(args, ap)


def serve_bnn(args, ap):
    """Telemetry-wrapped entry for :func:`_serve_bnn` (--model bnn)."""
    from repro.core import telemetry
    tracer, reg = make_obs(args, parties=3 if args.backend == "mesh" else 0)
    with telemetry.tracing(tracer), telemetry.collecting(reg):
        return _serve_bnn(args, ap, tracer, reg)


def _serve_bnn(args, ap, tracer=None, reg=None):
    """Batched secure-BNN classifier serving: the pre-PR-10 main() body
    plus observability spans (DESIGN.md §17)."""
    import jax
    import numpy as np
    from repro.core import RING32, comm, cost_model, share, telemetry
    from repro.core.integrity import IntegrityError, verify_model_ingest
    from repro.core.randomness import Parties
    from repro.core.secure_model import secure_infer_cost
    from repro.nn.bnn import INPUT_SHAPES

    # argument validation with actionable errors (exit code 2, argparse
    # style) before any compilation work
    if args.net not in INPUT_SHAPES:
        ap.error(f"unknown --net {args.net!r}; available: "
                 + ", ".join(sorted(INPUT_SHAPES)))
    if args.deployment is not None \
            and args.deployment.lower() not in cost_model.DEPLOYMENTS:
        ap.error(f"unknown --deployment {args.deployment!r}; available: "
                 + ", ".join(sorted(cost_model.DEPLOYMENTS)))
    if args.batch < 1:
        ap.error(f"--batch must be >= 1, got {args.batch}")
    if args.queries < 1:
        ap.error(f"--queries must be >= 1, got {args.queries}")
    if args.weights == "public" and args.binary_linear == "generic":
        ap.error("--weights public has no generic Alg-2 route (public "
                 "layers are local share algebra); use --binary-linear "
                 "auto or off")
    if args.pool_depth is not None and args.offline != "pool":
        ap.error("--pool-depth only applies to --offline pool")
    if args.pool_depth is not None and args.pool_depth < 1:
        ap.error(f"--pool-depth must be >= 1, got {args.pool_depth}")
    pool_depth = args.pool_depth if args.pool_depth is not None else 8

    shape = INPUT_SHAPES[args.net]
    deployment = None
    if args.deployment is not None:
        deployment = cost_model.resolve_deployment(
            args.deployment).with_batch(args.batch)
    with telemetry.span("compile_secure", cat="compile", net=args.net,
                        batch=args.batch):
        model = build(args.net, not args.no_kernel, args.weights,
                      args.binary_linear, deployment=deployment)
    if deployment is not None:
        rep = model.predicted
        print(f"[serve_secure] path solver ({deployment.name}): "
              + ", ".join(f"{e.name}={e.path}" for e in rep.entries
                          if e.name.startswith("l")))
        print(f"[serve_secure] predicted online: {rep.rounds} rounds, "
              f"{rep.nbytes / 1e6:.3f} MB, "
              f"{rep.time(deployment) * 1e3:.1f} ms/query")
    if args.verify == "full":
        # structural RSS pair-consistency check on the ingested shares
        verify_model_ingest(model)
        print("[serve_secure] model ingest verified "
              f"({len(model.ops)} layers)")

    # the abstract trace fires every comm.record: under --trace this span
    # carries the whole per-query op stream as instant events
    with telemetry.span("ledger_estimate", cat="setup", net=args.net):
        led = secure_infer_cost(model, (args.batch,) + shape)
    # symbolic model vs live ledger: byte-exact by construction (§15) —
    # a mismatch means the cost table drifted from the protocol stack
    pred = cost_model.model_cost(model, (args.batch,) + shape)
    pred_ok = (pred.rounds, pred.nbytes) == (led.rounds, led.nbytes)
    print(f"[serve_secure] cost model: predicted {pred.rounds} rounds / "
          f"{pred.nbytes:,} B vs measured {led.rounds} / {led.nbytes:,} B "
          f"-> {'exact' if pred_ok else 'MISMATCH'}")
    if not pred_ok:
        raise SystemExit("cost-model prediction diverged from the ledger")
    parties = Parties.setup(jax.random.PRNGKey(args.seed + 7))

    rng = np.random.default_rng(args.seed)
    x = (rng.integers(0, 2, (args.batch,) + shape).astype(np.float32) - 0.5)
    xs = share(x, jax.random.PRNGKey(args.seed + 3), RING32)

    stats = {"net": args.net, "backend": args.backend, "batch": args.batch,
             "weights": args.weights, "offline": args.offline,
             "verify": args.verify, "deployment": args.deployment,
             "comm_mb_per_query": led.megabytes, "rounds": led.rounds,
             "predicted_rounds": pred.rounds,
             "predicted_bytes": pred.nbytes, "device": device_info()}

    try:
        if args.offline == "pool":
            from repro.core.preprocessing import (make_tape_generator,
                                                  trace_material)
            spec = trace_material(model, (args.batch,) + shape)
            print(f"[serve_secure] material spec: {spec.summary()}")
            gen = make_tape_generator(spec)
            run, prepare, mesh = make_tape_runner(model, spec, args.backend,
                                                  verify=args.verify)
            if mesh is not None:
                print(f"[serve_secure] mesh axes "
                      f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
            out, online_s, total_s, refills = serve_pool(
                run, prepare, gen, spec, parties.keys, xs.shares,
                args.queries, pool_depth,
                jax.random.PRNGKey(args.seed + 11), verify=args.verify)
            out = np.asarray(out)
            assert out.shape[0] == args.batch
            qps_on = args.queries / online_s
            qps_total = args.queries / total_s
            print(f"[serve_secure] {args.net} backend={args.backend} "
                  f"batch={args.batch} offline=pool depth={pool_depth} "
                  f"verify={args.verify}: "
                  f"{args.queries} queries, online-only {qps_on:.2f} q/s "
                  f"({qps_on * args.batch:.1f} img/s), amortized total "
                  f"{qps_total:.2f} q/s ({qps_total * args.batch:.1f} "
                  f"img/s, {refills} refills)")
            stats.update({"pool_depth": pool_depth,
                          "query_per_s_online": qps_on,
                          "img_per_s_online": qps_on * args.batch,
                          "query_per_s": qps_total,
                          "img_per_s": qps_total * args.batch})
            measured_online = online_s
        else:
            run, mesh = make_runner(model, args.backend, args.batch,
                                    verify=args.verify)
            if mesh is not None:
                print(f"[serve_secure] mesh axes "
                      f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
            with telemetry.span("jit_warmup", cat="compile"):
                out = np.asarray(run(parties.keys, xs.shares))
            assert out.shape[0] == args.batch
            t0 = time.time()
            for q in range(args.queries):
                if telemetry.enabled():
                    with telemetry.span(f"query[{q}]", cat="online",
                                        lane="parties"):
                        tq = time.perf_counter()
                        out = run(parties.keys, xs.shares)
                        jax.block_until_ready(out)
                        telemetry.observe("query_latency_seconds",
                                          time.perf_counter() - tq)
                else:
                    out = run(parties.keys, xs.shares)
            np.asarray(out)
            dt = time.time() - t0
            qps = args.queries / dt
            ips = qps * args.batch
            print(f"[serve_secure] {args.net} backend={args.backend} "
                  f"batch={args.batch} kernel={not args.no_kernel} "
                  f"weights={args.weights} verify={args.verify}: "
                  f"{args.queries} queries in {dt:.2f}s = {qps:.2f} q/s "
                  f"({ips:.1f} img/s)")
            stats.update({"img_per_s": ips, "query_per_s": qps})
            measured_online = dt
    except IntegrityError as e:
        # deviation detected: abort with diagnostics, never a wrong answer
        # — but still flush the trace/metrics so the abort is inspectable
        print(f"[serve_secure] ABORT: {e}", file=sys.stderr)
        emit_obs(args, tracer, reg, led, predicted=pred, model=model)
        raise SystemExit(3)

    # modeled network wall-clock: total (online + preprocessing) next to
    # the online-only phase the tape pool leaves on the wire
    print(f"[serve_secure] per-query comm: {led.megabytes:.3f} MB online "
          f"({led.rounds} rounds) + {led.pre_nbytes / 1e6:.3f} MB offline "
          f"({led.pre_rounds} rounds)")
    print(f"[serve_secure] modeled total   LAN "
          f"{led.time(comm.LAN, online_only=False)*1e3:.1f} ms / WAN "
          f"{led.time(comm.WAN, online_only=False)*1e3:.0f} ms")
    print(f"[serve_secure] modeled online  LAN "
          f"{led.time(comm.LAN, online_only=True)*1e3:.1f} ms / WAN "
          f"{led.time(comm.WAN, online_only=True)*1e3:.0f} ms")
    stats.update({
        "lan_ms_total": led.time(comm.LAN, online_only=False) * 1e3,
        "wan_ms_total": led.time(comm.WAN, online_only=False) * 1e3,
        "lan_ms_online": led.time(comm.LAN, online_only=True) * 1e3,
        "wan_ms_online": led.time(comm.WAN, online_only=True) * 1e3})
    emit_obs(args, tracer, reg, led, predicted=pred, model=model,
             online_s=measured_online, queries=args.queries)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(stats, f, indent=2)
        print(f"[serve_secure] wrote {args.json}")


if __name__ == "__main__":
    main()
