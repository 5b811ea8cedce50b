"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def ring_matmul_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """uint32 matmul mod 2^32 (XLA integer dot wraps natively)."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.uint32)


def binary_weight_matmul_ref(a: jax.Array, w: jax.Array) -> jax.Array:
    return jax.lax.dot_general(a, w.astype(jnp.uint32),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.uint32)


def binary_binary_matmul_ref(a: jax.Array, w: jax.Array) -> jax.Array:
    return jax.lax.dot_general(a.astype(jnp.int32), w.astype(jnp.int32),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def grouped_rss_matmul_ref(x_stack: jax.Array, ws: jax.Array, wf: jax.Array,
                           x_next_stack: jax.Array | None = None) -> jax.Array:
    """All parties' additive grouped (depthwise) products mod 2^32:
    z_i[c] = x_i[c]·wf_i[c] + x_{i+1}[c]·ws_i[c], the fused-operand Alg-2
    identity per channel, with wf_i = w_i + w_{i+1}.  x_stack: (S, C, M, K)
    uint32 shares; ws/wf: (S, C, K, N); ``x_next_stack`` defaults to the
    party-axis roll of x_stack.  Returns (S, C, M, N) uint32."""
    xn = (jnp.roll(x_stack, -1, axis=0) if x_next_stack is None
          else x_next_stack)

    def dot(a, b):
        # (C, M, K) @ (C, K, N) -> (C, M, N), channel as the batch dim
        return jax.lax.dot_general(
            a, b, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.uint32)

    return jnp.stack([dot(x_stack[i], wf[i]) + dot(xn[i], ws[i])
                      for i in range(x_stack.shape[0])])


def flash_attention_ref(q, k, v, causal: bool = True):
    """q: (B,S,H,hd), k/v: (B,S,Hkv,hd) — plain softmax attention (GQA)."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qf = q.astype(jnp.float32) / math.sqrt(hd)
    qg = qf.reshape(b, s, hkv, g, hd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None, None], scores, -1e9)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v.astype(jnp.float32))
    return out.reshape(b, s, h, hd).astype(q.dtype)
