"""Kernel microbenchmarks + TPU-projected derivations.

CPU wall times here time the *oracle* ring path (the interpret-mode Pallas
kernel is a correctness vehicle, not a perf number); the derived column is
the TPU v5e projection from the limb-decomposition arithmetic:
general ring matmul = 10 int8 MXU passes, binary-weight = 4, binary×binary
= 1 (DESIGN.md §3).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.limbs import N_LIMBS, balanced_limbs
from repro.kernels.rss_matmul import precompute_weight_limbs

V5E_INT8_OPS = 394e12  # int8 MXU ops/s (2× bf16 peak)


def _limb_dot(al, bl):
    """Limb-arithmetic matmul (the kernel's math in pure jnp, for timing)."""
    acc = jnp.zeros((al.shape[1], bl.shape[2]), jnp.uint32)
    for p in range(N_LIMBS):
        for q in range(N_LIMBS - p):
            prod = jax.lax.dot_general(
                al[p], bl[q], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            acc = acc + (prod.astype(jnp.uint32) << (8 * (p + q)))
    return acc


def _rss_perdot(xs, ws):
    """OLD path: 6 separate limb dots, each re-decomposing both operands
    (12 decompositions per secure matmul)."""
    xn, wn = jnp.roll(xs, -1, axis=0), jnp.roll(ws, -1, axis=0)
    return jnp.stack([
        _limb_dot(balanced_limbs(xs[i]), balanced_limbs(ws[i] + wn[i]))
        + _limb_dot(balanced_limbs(xn[i]), balanced_limbs(ws[i]))
        for i in range(3)])


def _rss_fused(xs, wl, wfl):
    """NEW path: activation stack decomposed ONCE (x_{i+1} limbs are a
    party roll), weight limbs cached from setup (kernels/rss_matmul.py)."""
    xl = balanced_limbs(xs).transpose(1, 0, 2, 3)
    xnl = jnp.roll(xl, -1, axis=0)
    return jnp.stack([_limb_dot(xl[i], wfl[i]) + _limb_dot(xnl[i], wl[i])
                      for i in range(3)])


def _t(fn, *args, iters=5):
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(*args).block_until_ready()
    return (time.perf_counter() - t0) / iters


def kernels():
    rows = []
    key = jax.random.PRNGKey(0)
    m = k = n = 512
    a = jax.random.bits(key, (m, k), jnp.uint32)
    b = jax.random.bits(jax.random.fold_in(key, 1), (k, n), jnp.uint32)
    w8 = (jax.random.randint(key, (k, n), 0, 2) * 2 - 1).astype(jnp.int8)
    a8 = (jax.random.randint(key, (m, k), 0, 2) * 2 - 1).astype(jnp.int8)

    macs = 2 * m * k * n
    ring_ideal = 10 * macs / V5E_INT8_OPS  # 10 limb passes
    bin_ideal = 4 * macs / V5E_INT8_OPS
    bb_ideal = 1 * macs / V5E_INT8_OPS

    f = jax.jit(ref.ring_matmul_ref)
    rows.append(("kernel.ring_matmul.512", _t(f, a, b) * 1e6,
                 f"tpu_v5e_ideal_us={ring_ideal*1e6:.2f} limbs=10/16"))
    f2 = jax.jit(ref.binary_weight_matmul_ref)
    rows.append(("kernel.binary_weight.512", _t(f2, a, w8) * 1e6,
                 f"tpu_v5e_ideal_us={bin_ideal*1e6:.2f} limbs=4 "
                 f"speedup_vs_general=2.5x"))
    f3 = jax.jit(ref.binary_binary_matmul_ref)
    rows.append(("kernel.binary_binary.512", _t(f3, a8, w8) * 1e6,
                 f"tpu_v5e_ideal_us={bb_ideal*1e6:.2f} limbs=1 "
                 f"speedup_vs_general=10x"))

    # RSS secure-matmul engine: old per-dot limb decomposition (6 dots, 12
    # decompositions) vs the shared-limb fused path (1 online decomposition,
    # weight limbs cached at setup) — ISSUE 2 trajectory row.
    xs3 = jax.random.bits(jax.random.fold_in(key, 7), (3, m, k), jnp.uint32)
    ws3 = jax.random.bits(jax.random.fold_in(key, 8), (3, k, n), jnp.uint32)
    wlimbs = precompute_weight_limbs(ws3)
    wl = wlimbs.wl[:, :, :k, :n]
    wfl = wlimbs.wfl[:, :, :k, :n]
    fp = jax.jit(_rss_perdot)
    t_old = _t(fp, xs3, ws3) * 1e6
    ff = jax.jit(_rss_fused)
    t_new = _t(ff, xs3, wl, wfl) * 1e6
    rss_ideal = 2 * 10 * macs / V5E_INT8_OPS  # 2 limb matmuls/party stack
    rows.append(("kernel.rss_matmul.perdot.512", t_old,
                 "decomps=12/layer launches=6"))
    # CPU wall clock is dominated by the (identical) 60 int8 dots, so the
    # cpu ratio hovers near 1x; the structural win (12->1 decompositions,
    # 6->1 launches, fused operand cached) is the derived column's story.
    rows.append(("kernel.rss_matmul.fused.512", t_new,
                 f"tpu_v5e_ideal_us={rss_ideal*1e6:.2f} decomps=1/layer "
                 f"launches=1 cpu_ratio_vs_perdot="
                 f"{t_old/max(t_new,1e-9):.2f}x"))

    q = jax.random.normal(key, (1, 512, 8, 64), jnp.float32)
    kk = jax.random.normal(jax.random.fold_in(key, 2), (1, 512, 2, 64))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, 512, 2, 64))
    f4 = jax.jit(ref.flash_attention_ref)
    attn_flops = 4 * 512 * 512 * 8 * 64 / 2
    rows.append(("kernel.flash_attn_ref.512", _t(f4, q, kk, v) * 1e6,
                 f"tpu_v5e_ideal_us={attn_flops/197e12*1e6:.2f}"))

    # SSD chunked scan (mamba2 hot spot): the kernel itself, platform-default
    # lowering (interpret on CPU, compiled on TPU) — a real wall-clock, not
    # the placeholder this row used to fabricate.
    from repro.kernels.ssd import ssd_scan
    s, hh, hd, n, qc = 512, 4, 64, 32, 64
    xs = jax.random.normal(jax.random.fold_in(key, 9), (1, s, hh, hd),
                           jnp.float32) * 0.5
    bm = jax.random.normal(jax.random.fold_in(key, 10), (1, s, n)) * 0.5
    cm = jax.random.normal(jax.random.fold_in(key, 11), (1, s, n)) * 0.5
    da = -jax.random.uniform(jax.random.fold_in(key, 12), (1, s, hh)) * 0.5
    dt = jax.random.uniform(jax.random.fold_in(key, 13), (1, s, hh)) * 0.9 \
        + 0.1
    f5 = jax.jit(lambda *a: ssd_scan(*a, chunk=qc))
    ssd_flops = 2 * s * hh * (qc * n + qc * hd + 2 * hd * n)
    rows.append(("kernel.ssd_scan.512", _t(f5, xs, bm, cm, da, dt) * 1e6,
                 f"tpu_v5e_ideal_us={ssd_flops/197e12*1e6:.3f} "
                 f"chunk={qc} (intra-chunk MXU matrix form)"))
    return rows

