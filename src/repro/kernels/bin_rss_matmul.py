"""Pallas TPU kernel: the binary-domain public-weight secure linear layer.

The binary-domain engine (DESIGN.md §11) compiles a linear layer whose
weights are *public* (deployment scenario: private input, public model)
into pure local share algebra: party P_i computes

    z_i = x_i @ W        (mod 2^32)

for every share slot it holds — including the replicated neighbour slot
x_{i+1} — so the full RSS pair is reproduced with ZERO communication (no
reshare, no truncation opening when the activations are post-Sign ±1 at
scale 0).

This kernel is the MXU path for that product.  The decisive difference
from the secret-weight kernel (`rss_matmul.py`): a *public* weight's ring
encoding is a bounded signed value, not a uniformly random share, so its
balanced-limb decomposition (`kernels/limbs.py`) needs only

    L = highest nonzero balanced limb   (adaptive, data-derived, 1..4)

instead of the 4 limbs a full-range share always needs.  Fixed-point
weights at f=12 land at L=2–3; weight-binarized layers (W ∈ {±1}, scale 0)
collapse to L=1.  With the activation-share stack at 4 limbs and limb
pairs p+q > 3 vanishing mod 2^32, the per-cell MXU work is

    dots(L) = Σ_{q<L} (4 − q)  =  4 / 7 / 9 / 10   for L = 1 / 2 / 3 / 4

versus 20 for the secret-weight fused kernel — the ~4–5× binary-domain
collapse (exactly 4 int8 dots per cell for a binarized public weight).

The grid is (slot, M/bm, N/bn, K/bk) like `rss_matmul`, but the weight
blocks are *shared across the slot axis* (index map ignores the slot
index): one copy of the public limbs feeds every party's dot.

Interpret-mode correct everywhere; TPU-shaped (128-aligned MXU tiles,
int8×int8→int32 accumulation whose wraparound *is* mod-2^32 arithmetic).
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .limbs import N_LIMBS, balanced_limbs
from .lowering import resolve_interpret

__all__ = ["PublicWeightLimbs", "public_weight_limbs", "bin_rss_matmul",
           "bin_rss_matmul_ref", "bin_rss_matmul_parts",
           "PublicGroupedLimbs", "public_grouped_limbs",
           "bin_grouped_matmul_parts"]

_TILE = 128
# Block shapes of every launch: one MXU tile on each axis of the dense
# kernel, one tile of M for the grouped one.  No shape has been tuned on
# the chip yet.
_BM = _BN = _BK = _TILE


class PublicWeightLimbs(typing.NamedTuple):
    """Cached limb decomposition of one PUBLIC (K, N) ring weight matrix.

    ``w`` keeps the raw uint32 encoding for the small-shape reference
    fallback; ``wl`` holds the minimal ``n_limbs`` balanced int8 limbs,
    tile-padded.  Computed once at model setup (`compile_secure`) from
    public data — the adaptive limb count leaks nothing.
    """

    w: jax.Array        # (K, N) uint32 — public ring encoding
    wl: jax.Array       # (L, Kp, Np) int8 — minimal balanced limbs
    n_limbs: int        # static L ∈ {1..4}

    @property
    def k(self) -> int:
        return self.w.shape[0]

    @property
    def n(self) -> int:
        return self.w.shape[1]


def _pad_axis(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def min_public_limbs(w_enc: np.ndarray | jax.Array) -> int:
    """Minimal balanced-limb count for a PUBLIC ring matrix.

    Derived from the actual decomposition: L is the index of the highest
    nonzero balanced limb, so dropping the trailing limbs is exact by
    construction (a magnitude formula is off at the digit boundaries —
    balanced digits top out at +127, e.g. 32767 → [−1, −128, 1, 0] needs
    3 limbs, not 2).  Bounded public encodings land at 1–3; a share
    (uniform mod 2^32) always needs all 4 — DESIGN.md §11, the
    public-weight limb collapse."""
    l4 = np.asarray(balanced_limbs(jnp.asarray(w_enc, jnp.uint32)))
    n = N_LIMBS
    while n > 1 and not np.any(l4[n - 1]):
        n -= 1
    return n


def public_weight_limbs(w_enc: jax.Array,
                        n_limbs: int | None = None) -> PublicWeightLimbs:
    """Decompose a public (K, N) uint32 weight matrix once, at model setup.

    ``n_limbs`` defaults to the minimal exact count (`min_public_limbs`);
    callers may force a larger L."""
    if n_limbs is None:
        n_limbs = min_public_limbs(w_enc)
    wp = _pad_axis(_pad_axis(jnp.asarray(w_enc, jnp.uint32), _TILE, 0),
                   _TILE, 1)
    wl = balanced_limbs(wp)[:n_limbs]
    return PublicWeightLimbs(w=jnp.asarray(w_enc, jnp.uint32), wl=wl,
                             n_limbs=n_limbs)


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------

def _make_bin_kernel(n_w_limbs: int):
    """Kernel body for a static public-weight limb count L.

    x_ref: (1, 4, bm, bk) int8 — limbs of share slot x_s
    w_ref: (L, bk, bn) int8    — public weight limbs (slot-invariant)
    o_ref: (1, bm, bn) uint32  — z_s = x_s @ W
    """

    def kernel(x_ref, w_ref, o_ref):
        kk = pl.program_id(3)

        @pl.when(kk == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        acc = jnp.zeros(o_ref.shape[1:], jnp.uint32)
        for q in range(n_w_limbs):
            for p in range(N_LIMBS - q):  # limbs with p+q > 3 vanish mod 2^32
                prod = jax.lax.dot_general(
                    x_ref[0, p], w_ref[q], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                acc = acc + (prod.astype(jnp.uint32) << (8 * (p + q)))
        o_ref[...] = o_ref[...] + acc[None]

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def _bin_rss_matmul_call(xl, wl, *, bm, bn, bk, interpret):
    """xl: (S,4,M,K) int8 share-stack limbs; wl: (L,K,N) int8 public limbs
    -> (S,M,N) uint32.  S covers every slot the caller holds: 3 in the
    stacked simulation, 2 (the replicated pair) in a MeshTransport
    per-party program — all slots are computable locally from public W."""
    s, _, m, k = xl.shape
    n_w_limbs, k2, n = wl.shape
    assert k2 == k, (xl.shape, wl.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"({m},{k})x({k},{n}) not divisible by ({bm},{bk},{bn})"

    grid = (s, m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _make_bin_kernel(n_w_limbs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, N_LIMBS, bm, bk),
                         lambda p, i, j, kk: (p, 0, i, kk)),
            # public weights: the slot axis does not appear — every party's
            # dot reads the same limb block
            pl.BlockSpec((n_w_limbs, bk, bn),
                         lambda p, i, j, kk: (0, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda p, i, j, kk: (p, i, j)),
        out_shape=jax.ShapeDtypeStruct((s, m, n), jnp.uint32),
        interpret=interpret,
    )(xl, wl)


def bin_rss_matmul(x_stack: jax.Array, weights: PublicWeightLimbs, *,
                   bm: int = _BM, bn: int = _BN, bk: int = _BK,
                   interpret: bool | None = None) -> jax.Array:
    """Every held share slot's local product with a public weight matrix.

    x_stack: (S, M, K) uint32 share stack (S = 3 stacked sim / 2 per-party
    pair).  Returns (S, M, N) uint32 with z_s = x_s @ W mod 2^32 — a valid
    RSS stack of x @ W with no communication.  Handles non-tile-aligned
    M/K/N by zero padding.  ``interpret=None`` resolves to the platform
    default (compiled on TPU, interpreter elsewhere)."""
    interpret = resolve_interpret(interpret)
    s, m, k = x_stack.shape
    assert k == weights.k, (x_stack.shape, weights.w.shape)
    xp = _pad_axis(_pad_axis(x_stack, _TILE, 1), _TILE, 2)
    xl = balanced_limbs(xp).transpose(1, 0, 2, 3)
    out = _bin_rss_matmul_call(xl, weights.wl, bm=bm, bn=bn, bk=bk,
                               interpret=interpret)
    return out[:, :m, :weights.n]


def bin_rss_matmul_ref(x_stack: jax.Array,
                       weights: PublicWeightLimbs) -> jax.Array:
    """Reference path (exact, same mod-2^32 integers as the kernel):
    per-slot uint32 dot_generals on the raw public encoding."""

    def dot(a):
        return jax.lax.dot_general(a, weights.w, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.uint32)

    return jnp.stack([dot(x_stack[i]) for i in range(x_stack.shape[0])])


def bin_rss_matmul_parts(x_stack: jax.Array, weights: PublicWeightLimbs, *,
                         min_dim: int = 8,
                         interpret: bool | None = None) -> jax.Array:
    """Kernel dispatch with the small-shape fallback used across kernels/:
    both paths are exact mod 2^32, so results are bit-identical."""
    _, m, k = x_stack.shape
    if min(m, k, weights.n) < min_dim:
        return bin_rss_matmul_ref(x_stack, weights)
    return bin_rss_matmul(x_stack, weights, interpret=interpret)


# ---------------------------------------------------------------------------
# Grouped (depthwise) public-weight variant (DESIGN.md §11/§13)
# ---------------------------------------------------------------------------
#
# A depthwise conv is a *grouped* matmul: channel c contracts its own
# (M, K=kh·kw) patch matrix against its own tiny (K, mult) kernel.  With
# PUBLIC weights every held slot's local product z_s[c] = x_s[c] @ W[c]
# runs on the limb-decomposed path below, with the same adaptive limb
# collapse as the dense public kernel (L = 1..4 from the bounded
# encoding).  Shared-weight depthwise products launch no kernel: they are
# a direct tap multiply-accumulate (`core.linear._grouped_conv_parts`).
#
# The grid is (slot, channel, M/bm): the channel axis replaces the dense
# kernels' N/bn axis, M carries the 128-tiling, and the tiny K/mult axes
# stay whole inside a block (K = kh·kw ≤ 25 — padding them to MXU tiles
# would waste >5× the FLOPs the grouping saves).  Interpret-mode correct
# everywhere, like every kernel in this package.


class PublicGroupedLimbs(typing.NamedTuple):
    """Cached limbs of a PUBLIC (C, K, N) grouped (depthwise) weight —
    the per-channel analogue of :class:`PublicWeightLimbs`, with the same
    adaptive limb collapse (bounded public encodings need 1–3 limbs)."""

    w: jax.Array        # (C, K, N) uint32 — public ring encoding
    wl: jax.Array       # (L, C, K, N) int8 — minimal balanced limbs
    n_limbs: int        # static L ∈ {1..4}

    @property
    def channels(self) -> int:
        return self.w.shape[0]

    @property
    def k(self) -> int:
        return self.w.shape[1]

    @property
    def n(self) -> int:
        return self.w.shape[2]


def public_grouped_limbs(w_enc: jax.Array,
                         n_limbs: int | None = None) -> PublicGroupedLimbs:
    """Decompose a public grouped weight once; minimal exact limb count."""
    if n_limbs is None:
        n_limbs = min_public_limbs(w_enc)
    wl = balanced_limbs(jnp.asarray(w_enc, jnp.uint32))[:n_limbs]
    return PublicGroupedLimbs(w=jnp.asarray(w_enc, jnp.uint32), wl=wl,
                              n_limbs=n_limbs)


def _make_grouped_public_kernel(n_w_limbs: int):
    """Grouped public-weight kernel body (adaptive L, like the dense
    bin kernel): x_ref (1, 4, 1, bm, K), w_ref (L, 1, K, N),
    o_ref (1, 1, bm, N)."""

    def kernel(x_ref, w_ref, o_ref):
        acc = jnp.zeros(o_ref.shape[2:], jnp.uint32)
        for q in range(n_w_limbs):
            for p in range(N_LIMBS - q):  # p+q > 3 vanishes mod 2^32
                prod = jax.lax.dot_general(
                    x_ref[0, p, 0], w_ref[q, 0], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                acc = acc + (prod.astype(jnp.uint32) << (8 * (p + q)))
        o_ref[...] = acc[None, None]

    return kernel


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def _grouped_public_call(xl, wl, *, bm, interpret):
    """xl: (S,4,C,Mp,K) int8; wl: (L,C,K,N) int8 -> (S,C,Mp,N) uint32."""
    s, _, c, m, k = xl.shape
    n_w_limbs, _, _, n = wl.shape
    bm = min(bm, m)
    assert m % bm == 0, (m, bm)
    grid = (s, c, m // bm)
    return pl.pallas_call(
        _make_grouped_public_kernel(n_w_limbs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, N_LIMBS, 1, bm, k),
                         lambda p, ch, i: (p, 0, ch, i, 0)),
            # public weights: the slot axis does not appear — every party's
            # dot reads the same per-channel limb block
            pl.BlockSpec((n_w_limbs, 1, k, n),
                         lambda p, ch, i: (0, ch, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bm, n), lambda p, ch, i: (p, ch, i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, c, m, n), jnp.uint32),
        interpret=interpret,
    )(xl, wl)


def bin_grouped_matmul_ref(x_stack: jax.Array,
                           weights: PublicGroupedLimbs) -> jax.Array:
    """Reference: per-slot per-channel uint32 batched dot on the raw
    public encoding."""

    def dot(a):
        return jax.lax.dot_general(
            a, weights.w, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.uint32)

    return jnp.stack([dot(x_stack[i]) for i in range(x_stack.shape[0])])


def bin_grouped_matmul_parts(x_stack: jax.Array, weights: PublicGroupedLimbs,
                             *, bm: int = _BM, min_dim: int = 8,
                             interpret: bool | None = None) -> jax.Array:
    """Every held slot's local grouped product with a public depthwise
    kernel: z_s[c] = x_s[c] @ W[c] mod 2^32 — zero communication, and the
    public limb collapse cuts the per-cell dots to Σ_{q<L}(4−q) like the
    dense bin kernel.  x_stack: (S, C, M, K) uint32; returns (S, C, M, N)."""
    s, c, m, k = x_stack.shape
    assert (c, k) == (weights.channels, weights.k), \
        (x_stack.shape, weights.w.shape)
    if m < min_dim:
        return bin_grouped_matmul_ref(x_stack, weights)
    xp = _pad_axis(x_stack, _TILE, 2)
    xl = balanced_limbs(xp).transpose(1, 0, 2, 3, 4)
    out = _grouped_public_call(xl, weights.wl, bm=bm,
                               interpret=resolve_interpret(interpret))
    return out[:, :, :m, :]
