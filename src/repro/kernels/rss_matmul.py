"""Pallas TPU kernel: the full 3-party RSS matmul in ONE pallas_call.

The secure linear layer (core/linear.py, fused-operand form) needs, per
party i, the additive product

    z_i = x_i @ (w_i + w_{i+1}) + x_{i+1} @ w_i        (mod 2^32)

Run naively through the scalar ``ring_matmul`` kernel this is 6 separate
dots, each re-decomposing both of its uint32 operands into int8 limbs — 12
decompositions per layer, and the three x_i slabs are decomposed twice each
(once as x_i, once as x_{i+1}).  This kernel instead takes the whole
(3, M, K) activation-share stack and the (3, K, N) weight-share stack as
*pre-decomposed* int8 limbs and emits the full (3, M, N) additive-product
stack from a single pallas_call:

  * limb decomposition happens once per share slab — the activation stack is
    decomposed in one call (x_{i+1} limbs are a party-axis roll of the same
    tensor, decomposition commutes with roll), and the weight stack plus the
    fused operand w_i + w_{i+1} are decomposed at model-setup time and
    cached across queries (core/secure_model.py);
  * the grid is (party, M/bm, N/bn, K/bk) with K innermost, so each output
    block stays resident in VMEM while its contraction accumulates;
  * inside a block the two matmuls of the fused-operand identity share the
    limb-product loop: 2 int8 MXU dots per surviving (p, q) limb pair, 20
    dots per (party, m, n, k) cell — vs 6 kernel launches × 10 dots with
    duplicated operand traffic for the per-dot path.

Both operands here are *shares* — uniform mod 2^32 — so every limb grid is
the full 4×4 with 10 surviving pairs (20 dots per cell across the two
fused-operand matmuls).  When the weights are public instead, the bounded
encoding collapses the weight limbs to 1–3 and the whole layer needs no
neighbour operand — that variant lives in `bin_rss_matmul.py` (the
binary-domain engine's bin-public path, DESIGN.md §11).

The caller views (own/next activation stacks, per-party weight slots) come
from the active transport backend (DESIGN.md §1): the stacked simulation
passes the full (3, ...) stacks, a MeshTransport per-party program passes
its replicated pair with S = 1 local slot.

Interpret-mode correct everywhere; TPU-shaped (128-aligned MXU tiles,
int8×int8→int32 accumulation whose wraparound *is* mod-2^32 arithmetic).
See DESIGN.md §3.
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .limbs import N_LIMBS, balanced_limbs
from .lowering import resolve_interpret

__all__ = ["WeightLimbs", "precompute_weight_limbs", "rss_matmul",
           "rss_matmul_parts", "rss_matmul_parts_ref"]

PARTIES = 3
_TILE = 128
# Block shape (bm, bn, bk) of every launch: one MXU tile on each axis.
# No shape has been tuned on the chip yet.
_BM = _BN = _BK = _TILE


class WeightLimbs(typing.NamedTuple):
    """Cached per-layer weight-share operands for the RSS kernel.

    ``ws``/``wf`` keep the raw uint32 stacks for the small-shape reference
    fallback; ``wl``/``wfl`` are their int8 limbs pre-padded to MXU tiles.
    All four are computed once at model setup (compile_secure) and reused
    for every query.
    """

    ws: jax.Array   # (3, K, N) uint32 — w_i
    wf: jax.Array   # (3, K, N) uint32 — fused operand w_i + w_{i+1}
    wl: jax.Array   # (3, 4, Kp, Np) int8 — limbs of ws, tile-padded
    wfl: jax.Array  # (3, 4, Kp, Np) int8 — limbs of wf, tile-padded

    @property
    def k(self) -> int:
        return self.ws.shape[1]

    @property
    def n(self) -> int:
        return self.ws.shape[2]


def _pad_axis(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _stack_limbs(stack: jax.Array) -> jax.Array:
    """(3, A, B) uint32 -> (3, 4, A, B) int8, ONE decomposition call."""
    return balanced_limbs(stack).transpose(1, 0, 2, 3)


def precompute_weight_limbs(w_shares: jax.Array) -> WeightLimbs:
    """Decompose a (3, K, N) weight-share stack once, at model setup.

    Limbs of the zero padding are zero, so padding before decomposition
    equals decomposing then padding — done here so queries never touch
    weight limbs again."""
    ws = w_shares
    wf = ws + jnp.roll(ws, -1, axis=0)
    wsp = _pad_axis(_pad_axis(ws, _TILE, 1), _TILE, 2)
    wfp = _pad_axis(_pad_axis(wf, _TILE, 1), _TILE, 2)
    return WeightLimbs(ws=ws, wf=wf, wl=_stack_limbs(wsp),
                       wfl=_stack_limbs(wfp))


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------

def _rss_matmul_kernel(x_ref, xn_ref, wf_ref, w_ref, o_ref):
    """One (party, m, n) output block, revisited across the K grid axis.

    x_ref  : (1, 4, bm, bk) int8 — limbs of x_p
    xn_ref : (1, 4, bm, bk) int8 — limbs of x_{p+1}
    wf_ref : (1, 4, bk, bn) int8 — limbs of (w_p + w_{p+1})
    w_ref  : (1, 4, bk, bn) int8 — limbs of w_p
    o_ref  : (1, bm, bn) uint32 — additive product z_p
    """
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc = jnp.zeros(o_ref.shape[1:], jnp.uint32)
    for p in range(N_LIMBS):
        for q in range(N_LIMBS - p):  # limbs with p+q > 3 vanish mod 2^32
            prod = jax.lax.dot_general(
                x_ref[0, p], wf_ref[0, q], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            prod += jax.lax.dot_general(
                xn_ref[0, p], w_ref[0, q], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            acc = acc + (prod.astype(jnp.uint32) << (8 * (p + q)))
    o_ref[...] = o_ref[...] + acc[None]


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def _rss_matmul_call(xl, xnl, wl, wfl, *, bm, bn, bk, interpret):
    """xl/xnl: (S,4,M,K) int8; wl/wfl: (S,4,K,N) int8 -> (S,M,N) uint32.

    S is the local party count: 3 in the stacked single-program simulation,
    1 inside a MeshTransport per-party program (each device runs its own
    slice of the same grid)."""
    s, _, m, k = xl.shape
    n = wl.shape[3]
    assert wl.shape[2] == k, (xl.shape, wl.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"({m},{k})x({k},{n}) not divisible by ({bm},{bk},{bn})"

    grid = (s, m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _rss_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, N_LIMBS, bm, bk),
                         lambda p, i, j, kk: (p, 0, i, kk)),
            pl.BlockSpec((1, N_LIMBS, bm, bk),
                         lambda p, i, j, kk: (p, 0, i, kk)),
            pl.BlockSpec((1, N_LIMBS, bk, bn),
                         lambda p, i, j, kk: (p, 0, kk, j)),
            pl.BlockSpec((1, N_LIMBS, bk, bn),
                         lambda p, i, j, kk: (p, 0, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda p, i, j, kk: (p, i, j)),
        out_shape=jax.ShapeDtypeStruct((s, m, n), jnp.uint32),
        interpret=interpret,
    )(xl, xnl, wfl, wl)


def rss_matmul(x_stack: jax.Array, weights: WeightLimbs, *,
               x_next_stack: jax.Array | None = None, bm: int = _BM,
               bn: int = _BN, bk: int = _BK,
               interpret: bool | None = None) -> jax.Array:
    """All parties' additive products in one kernel launch.

    x_stack: (S, M, K) uint32 activation-share stack (S = 3 stacked sim /
    1 per-party).  ``x_next_stack`` carries x_{i+1} explicitly when the
    caller holds the replicated pair (MeshTransport); when None it is the
    party-axis roll of x_stack (stacked simulation).
    Returns (S, M, N) uint32 with z_i = x_i·(w_i+w_{i+1}) + x_{i+1}·w_i.
    Handles non-tile-aligned M/K/N by zero padding (zero rows/cols
    contribute zero mod 2^32).  ``interpret=None`` resolves to the
    platform default (compiled on TPU, interpreter elsewhere)."""
    interpret = resolve_interpret(interpret)
    s, m, k = x_stack.shape
    assert k == weights.k, (x_stack.shape, weights.ws.shape)
    if x_next_stack is None:
        # x_{p+1} limbs: party-axis roll of the SAME limb tensor
        # (decomposition is elementwise, so it commutes with the roll —
        # no second decomposition)
        xp = _pad_axis(_pad_axis(x_stack, _TILE, 1), _TILE, 2)
        xl = _stack_limbs(xp)
        xnl = jnp.roll(xl, -1, axis=0)
    else:
        # pair layout: ONE decomposition of the concatenated (own, next)
        # slabs keeps the one-decomposition-per-slab property
        both = jnp.concatenate([x_stack, x_next_stack], axis=0)
        bl = _stack_limbs(_pad_axis(_pad_axis(both, _TILE, 1), _TILE, 2))
        xl, xnl = bl[:s], bl[s:]
    out = _rss_matmul_call(xl, xnl, weights.wl, weights.wfl, bm=bm, bn=bn,
                           bk=bk, interpret=interpret)
    return out[:, :m, :weights.n]


def rss_matmul_parts_ref(x_stack: jax.Array, weights: WeightLimbs,
                         x_next_stack: jax.Array | None = None) -> jax.Array:
    """Reference path (exact, same mod-2^32 integers as the kernel):
    per-party uint32 dot_generals on the cached fused operand."""
    xn = (jnp.roll(x_stack, -1, axis=0) if x_next_stack is None
          else x_next_stack)

    def dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.uint32)
    return jnp.stack([dot(x_stack[i], weights.wf[i]) + dot(xn[i], weights.ws[i])
                      for i in range(x_stack.shape[0])])


def rss_matmul_parts(x_stack: jax.Array, weights: WeightLimbs, *,
                     x_next_stack: jax.Array | None = None,
                     min_dim: int = 8,
                     interpret: bool | None = None) -> jax.Array:
    """Kernel dispatch with the small-shape fallback used across kernels/:
    both paths are exact mod 2^32, so results are bit-identical."""
    _, m, k = x_stack.shape
    if min(m, k, weights.n) < min_dim:
        return rss_matmul_parts_ref(x_stack, weights, x_next_stack)
    return rss_matmul(x_stack, weights, x_next_stack=x_next_stack,
                      interpret=interpret)
