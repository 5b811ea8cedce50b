"""Pallas TPU kernel: causal flash attention (GQA) for the prefill path.

Streaming-softmax tiling: grid (batch, q_heads, Sq/bq); the kernel walks KV
blocks up to the causal frontier keeping running (max, sum, acc) in VMEM.
GQA is handled in the index map (kv head = q head // group) — K/V are never
materialized per-q-head.

VMEM budget per program instance (bq=bk=128, hd=128, f32 acc):
  q (128·hd·4) + k,v (128·hd·4 each) + acc (128·hd·4) ≈ 256 KB  « 16 MB.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int,
                  scale: float, seq_len: int):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, hd)

    m = jnp.full((bq,), NEG_INF, jnp.float32)
    l = jnp.zeros((bq,), jnp.float32)
    acc = jnp.zeros(q.shape, jnp.float32)

    q_pos = qi * bq + jax.lax.iota(jnp.int32, bq)

    def body(kv_i, carry):
        m_, l_, acc_ = carry
        k = k_ref[0, 0, pl.ds(kv_i * bk, bk), :].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0, pl.ds(kv_i * bk, bk), :].astype(jnp.float32)
        s = q @ k.T                                    # (bq, bk)
        kv_pos = kv_i * bk + jax.lax.iota(jnp.int32, bk)
        mask = q_pos[:, None] >= kv_pos[None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_ - m_new)
        l_new = l_ * alpha + p.sum(axis=-1)
        acc_new = acc_ * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    n_kv = (qi + 1) * bq // bk  # causal frontier: only blocks ≤ q block
    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m, l, acc))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = True) -> jax.Array:
    """q: (B,S,H,hd), k/v: (B,S,Hkv,hd) -> (B,S,H,hd). Causal, GQA-aware."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    group = h // hkv
    bq, bk = min(bq, s), min(bk, s)
    assert s % bq == 0 and s % bk == 0 and bq % bk == 0
    scale = 1.0 / math.sqrt(hd)

    qt = q.transpose(0, 2, 1, 3)   # (B,H,S,hd)
    kt = k.transpose(0, 2, 1, 3)   # (B,Hkv,S,hd)
    vt = v.transpose(0, 2, 1, 3)

    grid = (b, h, s // bq)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, scale=scale,
                          seq_len=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, s, hd),
                         lambda bi, hi, qi, g=group: (bi, hi // g, 0, 0)),
            pl.BlockSpec((1, 1, s, hd),
                         lambda bi, hi, qi, g=group: (bi, hi // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
