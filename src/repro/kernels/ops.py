"""jit'd public wrappers for the Pallas kernels + padding/shape handling.

These are the entry points the rest of the framework uses; each dispatches
to the kernel (interpret-mode on CPU, compiled on TPU) and falls back to the
pure-jnp oracle for shapes below the tiling threshold.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .bin_rss_matmul import (PublicGroupedLimbs, PublicWeightLimbs,
                             bin_grouped_matmul_parts, bin_rss_matmul_parts)
from .binary_matmul import binary_binary_matmul, binary_weight_matmul
from .flash_attention import flash_attention
from .ring_matmul import ring_matmul
from .rss_matmul import WeightLimbs, precompute_weight_limbs, rss_matmul_parts

_MIN_TILE = 128


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def ring_matmul_op(a: jax.Array, b: jax.Array, *,
                   use_kernel: bool = True) -> jax.Array:
    """C = A @ B mod 2^32 for arbitrary (M,K)x(K,N); pads to 128 tiles."""
    if not use_kernel or min(a.shape + b.shape) < 8:
        return ref.ring_matmul_ref(a, b)
    a2, pm = _pad_to(a, _MIN_TILE, 0)
    a2, pk = _pad_to(a2, _MIN_TILE, 1)
    b2, _ = _pad_to(b, _MIN_TILE, 0)
    b2, pn = _pad_to(b2, _MIN_TILE, 1)
    out = ring_matmul(a2, b2)
    return out[:a.shape[0], :b.shape[1]]


def binary_weight_matmul_op(a: jax.Array, w: jax.Array, *,
                            use_kernel: bool = True) -> jax.Array:
    """A (uint32 ring) @ W (int8 ±1 / {0,1}) mod 2^32."""
    if not use_kernel or min(a.shape + w.shape) < 8:
        return ref.binary_weight_matmul_ref(a, w)
    a2, _ = _pad_to(a, _MIN_TILE, 0)
    a2, _ = _pad_to(a2, _MIN_TILE, 1)
    w2, _ = _pad_to(w, _MIN_TILE, 0)
    w2, _ = _pad_to(w2, _MIN_TILE, 1)
    out = binary_weight_matmul(a2, w2)
    return out[:a.shape[0], :w.shape[1]]


def binary_binary_matmul_op(a: jax.Array, w: jax.Array, *,
                            use_kernel: bool = True) -> jax.Array:
    if not use_kernel or min(a.shape + w.shape) < 8:
        return ref.binary_binary_matmul_ref(a, w)
    a2, _ = _pad_to(a, _MIN_TILE, 0)
    a2, _ = _pad_to(a2, _MIN_TILE, 1)
    w2, _ = _pad_to(w, _MIN_TILE, 0)
    w2, _ = _pad_to(w2, _MIN_TILE, 1)
    out = binary_binary_matmul(a2, w2)
    return out[:a.shape[0], :w.shape[1]]


def flash_attention_op(q, k, v, *, bq: int = 128, bk: int = 128):
    """Causal GQA flash attention; falls back to the oracle when seq is not
    tile-divisible (ragged prefill uses the reference path)."""
    s = q.shape[1]
    if s % bq or s % bk or bq % bk:
        return ref.flash_attention_ref(q, k, v, causal=True)
    return flash_attention(q, k, v, bq=bq, bk=bk)


def rss_matmul_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """Drop-in `dot` for core.linear.matmul — routes RSS linear layers
    through the limb-decomposed MXU kernel (folds leading batch dims).

    NOTE: this is the legacy per-dot path (6 kernel launches, 12 limb
    decompositions per secure matmul).  The fused path below does the whole
    3-party product in one launch with cached weight limbs."""
    lead = a.shape[:-1]
    a2 = a.reshape(-1, a.shape[-1])
    out = ring_matmul_op(a2, b)
    return out.reshape(lead + (b.shape[-1],))


def bin_rss_matmul_op(x_stack: jax.Array,
                      weights: PublicWeightLimbs) -> jax.Array:
    """Local share-stack product with a PUBLIC weight matrix (binary-domain
    engine, DESIGN.md §11): z_s = x_s @ W for every share slot the caller
    holds — no communication, no neighbour operand, and the public limb
    grid collapsed to ``weights.n_limbs`` (1 for binarized weights).

    x_stack: (S, ..., K) uint32 RSS stack (S = 3 stacked sim / 2 per-party
    pair); leading dims folded into M.  Returns (S, ..., N)."""
    s = x_stack.shape[0]
    lead = x_stack.shape[1:-1]
    x2 = x_stack.reshape(s, -1, x_stack.shape[-1])
    out = bin_rss_matmul_parts(x2, weights)
    return out.reshape((s,) + lead + (weights.n,))


def _fold_grouped(x: jax.Array):
    """(S, ..., K, C) patch stack -> (S, C, M, K) kernel layout."""
    s, k, c = x.shape[0], x.shape[-2], x.shape[-1]
    return x.reshape(s, -1, k, c).transpose(0, 3, 1, 2)


def _unfold_grouped(out: jax.Array, lead, n: int):
    """(S, C, M, N) kernel output -> (S, ..., C, N) channel-major layout
    (matches the per-channel einsum's `...cm` output ordering)."""
    s, c = out.shape[0], out.shape[1]
    return out.transpose(0, 2, 1, 3).reshape((s,) + lead + (c, n))


def bin_grouped_matmul_op(x_stack: jax.Array,
                          weights: PublicGroupedLimbs) -> jax.Array:
    """Local per-channel product with a PUBLIC depthwise kernel (bin-public
    path): z_s[c] = x_s[c] @ W[c] for every held slot — zero communication,
    adaptive public limb collapse.  x_stack: (S, ..., K, C) patch stack;
    returns (S, ..., C, N)."""
    lead = x_stack.shape[1:-2]
    out = bin_grouped_matmul_parts(_fold_grouped(x_stack), weights)
    return _unfold_grouped(out, lead, weights.n)


def rss_matmul_parts_op(x_stack: jax.Array, x_next_stack: jax.Array,
                        weights: WeightLimbs) -> jax.Array:
    """Full 3-party additive-product stack from one fused kernel launch.

    x_stack / x_next_stack: (S, ..., K) uint32 share stacks in additive
    alignment (S = 3 stacked sim / 1 per-party; leading dims folded into
    M); ``weights`` arrays are RSS-layout stacks that may carry the
    per-party pair — only the own slot feeds the kernel.
    Returns (S, ..., N) with z_i = x_i·(w_i+w_{i+1}) + x_{i+1}·w_i."""
    from ..core import transport
    t = transport.current()
    s = x_stack.shape[0]
    lead = x_stack.shape[1:-1]
    x2 = x_stack.reshape(s, -1, x_stack.shape[-1])
    if not t.carries_pair:
        # stacked sim: next == roll(own); the kernel derives the neighbour
        # limbs by rolling the shared limb tensor (no extra decomposition)
        w_own, xn2 = weights, None
    else:
        w_own = WeightLimbs(*(t.own_view(a) for a in weights))
        xn2 = x_next_stack.reshape(s, -1, x_next_stack.shape[-1])
    out = rss_matmul_parts(x2, w_own, x_next_stack=xn2)
    return out.reshape((s,) + lead + (weights.n,))
