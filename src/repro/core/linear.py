"""Linear-layer protocols over RSS (paper Algorithm 2) + truncation + reveal.

Multiplication identity (Araki et al.): with x = Σ x_i, y = Σ y_i,
    z_i = x_i·y_i + x_{i+1}·y_i + x_i·y_{i+1} + a_i,   Σ a_i = 0
gives Σ z_i = x·y.  P_i computes z_i purely from its view (x_i, x_{i+1}),
(y_i, y_{i+1}) and its zero-share a_i, then re-shares z_i to P_{i-1}
(1 round, one ring element each).

Beyond-paper optimization ("fused-operand", §Perf): per party
    z_i = x_i·(y_i + y_{i+1}) + x_{i+1}·y_i + a_i
— identical value, but for matmul/conv this is 2 ring matmuls per party
instead of 3 (33% of the MPC linear-layer FLOPs removed).

Binary-domain entry points (DESIGN.md §11): `bin_matmul` / `bin_conv2d`
consume post-Sign ±1 activations (scale 0) directly — the product already
sits at the activations' target scale, so no truncation opening rides the
layer and the whole cost is the reshare round (3 ring elements per output
slot, half the fused arithmetic path's 6).  With a :class:`PublicTensor`
weight (public-model deployment) the layer degenerates to local share
algebra: every party computes its full RSS pair z_s = x_s @ W itself —
zero rounds, zero wire bytes, and the public weight's bounded encoding
collapses the kernel limb grid (kernels/bin_rss_matmul.py).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from . import comm, transport
from .randomness import Parties
from .ring import RingSpec
from .rss import RSS

__all__ = ["reveal", "mul", "matmul", "conv2d", "truncate",
           "truncate_probabilistic", "linear_layer", "square",
           "set_matmul_mode", "set_fused_rounds", "fused_rounds",
           "mul_open", "matmul_truncate", "conv2d_truncate", "mul_truncate",
           "square_truncate", "PublicTensor", "bin_matmul", "bin_conv2d"]

# "opt2" = fused-operand (2 matmuls/party); "paper3" = Algorithm 2 verbatim.
_MATMUL_MODE = "opt2"
# Round-fused protocol variants (mul_open / matmul_truncate / local Sign
# conversion): beyond-paper, ON by default — every linear layer's trunc and
# every MSB multiply-open ride the layer's reshare round (2 rounds -> 1).
# set_fused_rounds(False) restores the paper-faithful round structure.
_FUSED_ROUNDS = True


def set_matmul_mode(mode: str):
    global _MATMUL_MODE
    assert mode in ("opt2", "paper3")
    _MATMUL_MODE = mode


def set_fused_rounds(on: bool):
    global _FUSED_ROUNDS
    _FUSED_ROUNDS = bool(on)


def fused_rounds() -> bool:
    return _FUSED_ROUNDS


# ---------------------------------------------------------------------------
# Reveal
# ---------------------------------------------------------------------------

def reveal(x: RSS, tag: str = "reveal", decode: bool = False):
    """Open x to all parties: P_i sends x_i to P_{i-1}; 1 round, 3 elements."""
    comm.record(tag, rounds=1, nbytes=3 * _numel(x) * x.ring.nbytes)
    total = transport.current().open_rss(x.shares)
    return x.ring.decode(total) if decode else total


# ---------------------------------------------------------------------------
# Multiplication (elementwise) and matmul
# ---------------------------------------------------------------------------

def _numel(x: RSS) -> int:
    n = 1
    for d in x.shape:
        n *= int(d)
    return n


def _reshare(z_parts, ring: RingSpec, parties: Parties, tag: str) -> RSS:
    """z_parts: additive-parts stack of shares z_i computed by each P_i.
    Adds the 3-of-3 zero mask and performs the reshare round
    (P_i -> P_{i-1}), after which P_i holds (z_i, z_{i+1}).  Under
    MeshTransport the round is a real ppermute (transport.complete)."""
    a = parties.zero_shares(z_parts.shape[1:], ring)
    z = z_parts + a
    n = 1
    for d in z.shape[1:]:
        n *= int(d)
    comm.record(tag, rounds=1, nbytes=3 * n * ring.nbytes)
    return RSS(transport.current().complete(z), ring)


def _align_party_axis(xs, ys):
    """Broadcast two share stacks, keeping axis 0 as the party axis."""
    nd = max(xs.ndim, ys.ndim)
    if xs.ndim < nd:
        xs = xs.reshape(xs.shape[:1] + (1,) * (nd - xs.ndim) + xs.shape[1:])
    if ys.ndim < nd:
        ys = ys.reshape(ys.shape[:1] + (1,) * (nd - ys.ndim) + ys.shape[1:])
    return xs, ys


def _mul_parts(xs, ys):
    """Elementwise additive product stack z_i, honoring the matmul mode."""
    t = transport.current()
    xo, yo = t.own_view(xs), t.own_view(ys)
    xn, yn = t.next_view(xs), t.next_view(ys)
    if _MATMUL_MODE == "opt2":
        return xo * (yo + yn) + xn * yo
    return xo * yo + xn * yo + xo * yn


def mul(x: RSS, y: RSS, parties: Parties, tag: str = "mul") -> RSS:
    """Elementwise secure multiplication. Output scale = sum of input scales
    (caller truncates when both operands are fixed-point)."""
    xs, ys = _align_party_axis(x.shares, y.shares)
    return _reshare(_mul_parts(xs, ys), x.ring, parties, tag)


def square(x: RSS, parties: Parties, tag: str = "square") -> RSS:
    """x^2 with one fewer local product: z_i = x_i^2 + 2·x_i·x_{i+1}."""
    return _reshare(_square_parts(x), x.ring, parties, tag)


def _square_parts(x: RSS):
    t = transport.current()
    xo, xn = t.own_view(x.shares), t.next_view(x.shares)
    return xo * xo + jnp.asarray(2, x.ring.dtype) * xo * xn


def _ring_dot(a, b, ring: RingSpec):
    """Integer matmul in the ring; wraps mod 2^l by construction."""
    return jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=ring.dtype)


def _matmul_parts(x: RSS, w: RSS | None, dot, w_limbs) -> jax.Array:
    """Additive product stack z_i (parts layout) — local compute, no comm.

    With ``w_limbs`` (a kernels.rss_matmul.WeightLimbs cached at model
    setup) the whole 3-party product runs in ONE fused Pallas launch:
    activations are limb-decomposed once per share slab, weight limbs
    (including the fused operand w_i + w_{i+1}) come precomputed."""
    t = transport.current()
    if w_limbs is not None:
        from ..kernels.ops import rss_matmul_parts_op
        return rss_matmul_parts_op(t.own_view(x.shares),
                                   t.next_view(x.shares), w_limbs)
    dot = dot or (lambda a, b: _ring_dot(a, b, x.ring))
    xo, wo = t.own_view(x.shares), t.own_view(w.shares)
    xn, wn = t.next_view(x.shares), t.next_view(w.shares)
    slots = xo.shape[0]
    if _MATMUL_MODE == "opt2":
        # z_i = x_i @ (w_i + w_{i+1}) + x_{i+1} @ w_i      (2 matmuls/party)
        return jnp.stack([dot(xo[i], wo[i] + wn[i]) + dot(xn[i], wo[i])
                          for i in range(slots)])
    # Algorithm 2 verbatim                                  (3 matmuls/party)
    return jnp.stack([dot(xo[i], wo[i]) + dot(xn[i], wo[i])
                      + dot(xo[i], wn[i]) for i in range(slots)])


def matmul(x: RSS, w: RSS | None, parties: Parties, tag: str = "matmul",
           dot=None, w_limbs=None) -> RSS:
    """Secure matmul  z = x @ w  (x: (..., K), w: (K, N)).

    ``dot`` may be swapped for the Pallas ring-matmul kernel
    (kernels/ops.py::ring_matmul) — same contract: uintL x uintL -> uintL
    mod 2^l.  ``w_limbs`` routes through the fused 3-party kernel with
    cached weight limbs instead (w may then be None).
    """
    z = _matmul_parts(x, w, dot, w_limbs)
    return _reshare(z, x.ring, parties, tag)


# ---------------------------------------------------------------------------
# Fused one-round variants (beyond-paper §Perf optimizations)
# ---------------------------------------------------------------------------

def mul_open(x: RSS, y: RSS, parties: Parties, tag: str = "mul_open"):
    """Multiply-and-reveal in ONE round (beyond-paper).

    When a product is immediately opened (MSB protocol step 9-10), the
    reshare round is wasted: each P_i broadcasts its additive z_i directly
    and everyone sums.  1 round / 6 elements vs mul(1r/3el)+reveal(1r/3el).
    """
    xs, ys = _align_party_axis(x.shares, y.shares)
    z = _mul_parts(xs, ys)
    z = z + parties.zero_shares(z.shape[1:], x.ring)
    n = 1
    for d in z.shape[1:]:
        n *= int(d)
    # each party broadcasts z_i to both peers: 6 messages, one round
    comm.record(tag, rounds=1, nbytes=6 * n * x.ring.nbytes)
    return transport.current().open_parts(z)


def matmul_truncate(x: RSS, w: RSS | None, parties: Parties,
                    tag: str = "matmul_tr", dot=None, w_limbs=None,
                    bias_parts=None) -> RSS:
    """Fused Alg-2 matmul + Π_trunc in ONE online round (beyond-paper).

    The reshare round already moves one ring element per output slot; the
    truncation's masked opening rides the same round: parties compute the
    additive product z_i, subtract their (offline) bounded mask share r_i,
    and broadcast  c_i = z_i − r_i + offset_i ; everyone opens c = z − r +
    2^{l−2} locally and finishes the shift exactly as in `truncate`.
    1 round / 6 elements vs matmul(1r/3el)+trunc(1r/3el) = 2 rounds.

    ``bias_parts`` (3, ..., N) additive shares (already lifted to the
    product's 2f scale) are folded in before the opening, so bias addition
    costs nothing.  ``w_limbs`` routes the product through the fused
    3-party Pallas kernel with cached weight limbs.
    """
    ring = x.ring
    z = _matmul_parts(x, w, dot, w_limbs)
    if bias_parts is not None:
        z = z + bias_parts
    return _open_shift(z, parties, ring, ring.frac, tag)


def _trunc_pair(shape, parties: Parties, ring: RingSpec, f: int):
    """Offline exact-trunc pair ([r], [r >> f]): additive shares
    r_i ~ U[0, 2^{l-3}) from the PRF, so shares of r >> f are the local
    shifts (no carries can wrap).  Shared by `truncate` and the fused ops —
    the correctness-critical constants live only here and _trunc_decode."""
    r = parties.rand_rss(shape, ring, max_bits=ring.bits - 1)
    return r, RSS(r.shares >> f, ring)


def _trunc_decode(c, ring: RingSpec, f: int):
    """Public part of the exact truncation: arithmetic-shift the opened
    c = x + 2^{l-2} − r and compensate the offset bias (+1: see DESIGN.md
    §10)."""
    c_shift = (ring.to_signed(c) >> f).astype(ring.dtype)
    return c_shift - jnp.asarray(1 << (ring.bits - 2 - f), ring.dtype) \
        + jnp.asarray(1, ring.dtype)


def _open_shift(z, parties: Parties, ring: RingSpec, f: int, tag: str) -> RSS:
    """Shared tail of the fused ops: mask additive parts with the bounded
    trunc pair, broadcast, open, arithmetic-shift.  One round, 6 elements."""
    t = transport.current()
    z = z + parties.zero_shares(z.shape[1:], ring)
    r, rp = _trunc_pair(z.shape[1:], parties, ring, f)
    offset = jnp.asarray(1 << (ring.bits - 2), ring.dtype)
    c_parts = z - t.own_view(r.shares)
    n = 1
    for d in z.shape[1:]:
        n *= int(d)
    comm.record(tag, rounds=1, nbytes=6 * n * ring.nbytes)
    c = t.open_parts(c_parts) + offset
    return rp.add_public(_trunc_decode(c, ring, f))


def mul_truncate(x: RSS, y: RSS, parties: Parties, frac: int | None = None,
                 tag: str = "mul_tr") -> RSS:
    """Fused elementwise multiply + truncate, one online round."""
    ring = x.ring
    xs, ys = _align_party_axis(x.shares, y.shares)
    z = _mul_parts(xs, ys)
    return _open_shift(z, parties, ring, ring.frac if frac is None else frac,
                       tag)


def square_truncate(x: RSS, parties: Parties, frac: int | None = None,
                    tag: str = "sq_tr") -> RSS:
    ring = x.ring
    z = _square_parts(x)
    return _open_shift(z, parties, ring, ring.frac if frac is None else frac,
                       tag)


# ---------------------------------------------------------------------------
# Convolution = im2col + ring matmul (TPU has no integer conv primitive;
# see DESIGN.md §3)
# ---------------------------------------------------------------------------

def _im2col(x, kh: int, kw: int, stride: int, padding: int):
    """x: (B, H, W, C) -> (B, Ho, Wo, kh*kw*C) patches."""
    if padding:
        x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    b, h, w, c = x.shape
    ho = (h - kh) // stride + 1
    wo = (w - kw) // stride + 1
    idx_h = jnp.arange(ho) * stride
    idx_w = jnp.arange(wo) * stride
    patches = []
    for i in range(kh):
        for j in range(kw):
            patches.append(jax.lax.dynamic_slice_in_dim(
                jax.lax.dynamic_slice_in_dim(x, i, h - kh + 1, axis=1),
                j, w - kw + 1, axis=2)[:, ::stride, ::stride, :])
    return jnp.concatenate(patches, axis=-1), ho, wo


def _grouped_conv_parts(x: RSS, w: RSS, stride: int, padding: int,
                        groups: int):
    """Additive per-channel (depthwise) product stack, fused-operand Alg 2
    as a direct kh·kw-tap ring multiply-accumulate:

        z_i = Σ_taps x_i[tap]·(w_i + w_{i+1})[tap] + x_{i+1}[tap]·w_i[tap]

    on strided views of the once-padded shares, each tap's per-channel
    weight broadcast along the channel axis (and over the depthwise
    multiplier m, for the output layout ``out[..., c*mult + m]``).  Exact
    mod 2^32.  The contraction is kh·kw deep with one output column per
    channel, a shape no MXU tiling suits, so it stays elementwise uint32
    work with no im2col copies.

    Returns the (S, B, Ho, Wo, Cout) parts stack — local compute, no comm;
    callers add bias parts and reshare."""
    kh, kw, cin_g, cout = (int(d) for d in w.shape)
    cin = int(x.shape[3])
    assert groups == cin and cin_g == 1 and cout % groups == 0
    mult = cout // groups
    t = transport.current()
    with jax.named_scope("taps"):
        xs = x.shares
        if padding:
            xs = jnp.pad(xs, ((0, 0), (0, 0), (padding, padding),
                              (padding, padding), (0, 0)))
        xo, xn = t.own_view(xs), t.next_view(xs)
        s, b, hp, wp, _ = (int(d) for d in xo.shape)
        ho = (hp - kh) // stride + 1
        wo = (wp - kw) // stride + 1
        # (S, kh, kw, C, mult): own weight share and the fused operand
        ws = t.own_view(w.shares).reshape(s, kh, kw, cin, mult)
        wf = ws + t.next_view(w.shares).reshape(s, kh, kw, cin, mult)

        def tap(a, i, j):
            v = jax.lax.slice(
                a, (0, 0, i, j, 0),
                (s, b, i + stride * (ho - 1) + 1, j + stride * (wo - 1) + 1,
                 cin), (1, 1, stride, stride, 1))
            return v[..., None]                  # (S, B, Ho, Wo, C, 1)

        def wtap(a, i, j):
            return a[:, i, j].reshape(s, 1, 1, 1, cin, mult)

        z = sum(tap(xo, i, j) * wtap(wf, i, j) + tap(xn, i, j) * wtap(ws, i, j)
                for i in range(kh) for j in range(kw))
        return z.reshape(s, b, ho, wo, cout)


def conv2d(x: RSS, w: RSS, parties: Parties, stride: int = 1,
           padding: int = 0, groups: int = 1, tag: str = "conv",
           w_limbs=None) -> RSS:
    """Secure 2-D convolution. x: (B,H,W,Cin), w: (kh,kw,Cin/groups,Cout).

    ``w_limbs`` holds the setup-time limb cache of the (kh·kw·Cin, Cout)
    weight matrix (a `kernels.rss_matmul.WeightLimbs`, groups == 1): the
    im2col patches run through the fused 3-party kernel.  The depthwise
    case (groups == Cin) is the direct tap product of
    `_grouped_conv_parts`, which needs no cache (``w_limbs`` is ignored
    there).  Depthwise costs one reshare round for the whole
    layer, same as dense."""
    kh, kw, cin_g, cout = (int(d) for d in w.shape)
    if groups == 1:
        cols, ho, wo = _im2col_rss(x, kh, kw, stride, padding)
        wmat = w.reshape(kh * kw * cin_g, cout)
        return matmul(cols, wmat, parties, tag=tag, w_limbs=w_limbs)
    z = _grouped_conv_parts(x, w, stride, padding, groups)
    return _reshare(z, x.ring, parties, tag=tag)


def _im2col_rss(x: RSS, kh, kw, stride, padding):
    """Patch matrix of every share slot, under an ``im2col`` scope nested
    in the caller's layer tag, so a trace shows the patch build apart
    from the product and the reshare.

    The patch matrix is materialized (``optimization_barrier``) before
    its consumer runs.  Left free, the TPU compiler fuses the taps'
    concatenation into the kernel operand's int8 limb decomposition, and
    for a 3-channel input (CifarNet6's first convolution, K = 27) that
    fusion reads the taps wrongly: on a v5e 5.6 M of its 50 M limbs came
    out wrong, while the patch matrix alone and the kernel alone were
    exact."""
    p = x.shares.shape[0]
    b, h, w, c = (int(d) for d in x.shape)
    with comm.scope("im2col"):
        cols, ho, wo = _im2col(x.shares.reshape(p * b, h, w, c),
                               kh, kw, stride, padding)
        cols = jax.lax.optimization_barrier(
            cols.reshape((p, b) + cols.shape[1:]))
    return RSS(cols, x.ring), ho, wo


def conv2d_truncate(x: RSS, w: RSS, parties: Parties, stride: int = 1,
                    padding: int = 0, tag: str = "conv_tr", w_limbs=None,
                    bias_parts=None) -> RSS:
    """Fused conv (groups=1) + bias + Π_trunc, one online round: im2col then
    `matmul_truncate`."""
    kh, kw, cin_g, cout = (int(d) for d in w.shape)
    cols, ho, wo = _im2col_rss(x, kh, kw, stride, padding)
    wmat = w.reshape(kh * kw * cin_g, cout)
    return matmul_truncate(cols, wmat, parties, tag=tag, w_limbs=w_limbs,
                           bias_parts=bias_parts)


# ---------------------------------------------------------------------------
# Binary-domain linear engine (DESIGN.md §11)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PublicTensor:
    """A *public* model tensor in ring encoding (public-weight deployment).

    Unlike an :class:`RSS`, there is no party axis: every party holds the
    same encoding, so linear algebra against shares is purely local.
    ``limbs`` optionally carries the setup-time
    :class:`kernels.bin_rss_matmul.PublicWeightLimbs` cache for the MXU
    path (the adaptive public limb collapse — DESIGN.md §11).
    """

    enc: jax.Array                 # ring-encoded public value
    limbs: object | None = None    # PublicWeightLimbs (matmul weights only)

    def tree_flatten(self):
        return (self.enc, self.limbs), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1])

    @property
    def shape(self):
        return self.enc.shape


def bin_matmul(x: RSS, w: RSS | PublicTensor, parties: Parties,
               tag: str = "bin_matmul", dot=None, w_limbs=None,
               bias_parts=None, bias_public=None) -> RSS:
    """Binary-domain secure matmul: x holds post-Sign ±1 activations at
    scale 0, so z = x @ w already sits at the weights' scale f — no
    truncation opening ever rides this layer (DESIGN.md §11).

    Shared weights (``w: RSS``): the additive products (fused-operand Alg 2,
    optionally the one-launch Pallas kernel via ``w_limbs``) plus the
    scale-f ``bias_parts`` go through ONE reshare round — 3 ring elements
    per output slot, vs the arithmetic path's 6 (`matmul_truncate`).

    Public weights (``w: PublicTensor``): every party computes its whole
    replicated pair z_s = x_s @ W locally (it holds both x_s slots), so the
    RSS invariant is rebuilt with ZERO rounds and ZERO bytes; the ledger
    records the 0-cost entry so the protocol table can show the layer.
    ``bias_public`` is the ring-encoded public bias, added via the slot-0
    mask (`RSS.add_public`).
    """
    if isinstance(w, PublicTensor):
        from ..kernels.ops import bin_rss_matmul_op
        assert bias_parts is None, \
            "public weights take bias_public (a public encoding), not " \
            "additive bias_parts"
        comm.record(tag, rounds=0, nbytes=0)
        wl = w.limbs if w_limbs is None else w_limbs
        if wl is not None:
            z = bin_rss_matmul_op(x.shares, wl)
        else:
            d = dot or (lambda a, b: _ring_dot(a, b, x.ring))
            z = jnp.stack([d(x.shares[i], w.enc)
                           for i in range(x.shares.shape[0])])
        out = RSS(z, x.ring)
        if bias_public is not None:
            out = out.add_public(bias_public)
        return out
    assert bias_public is None, \
        "shared weights take additive bias_parts, not a public encoding"
    z = _matmul_parts(x, w, dot, w_limbs)
    if bias_parts is not None:
        z = z + bias_parts
    return _reshare(z, x.ring, parties, tag)


def bin_conv2d(x: RSS, w: RSS | PublicTensor, parties: Parties,
               stride: int = 1, padding: int = 0, groups: int = 1,
               tag: str = "bin_conv", w_limbs=None, bias_parts=None,
               bias_public=None) -> RSS:
    """Binary-domain secure conv: im2col + `bin_matmul` (groups == 1) or the
    per-channel grouped contraction (groups == Cin, the depthwise half of a
    sepconv) — either way the post-Sign layer costs one reshare round
    (shared weights) or nothing at all (public weights).  Shared grouped
    convs are the direct tap product of `_grouped_conv_parts` (``w_limbs``
    is ignored there).  Public grouped convs run locally on
    every held slot, through the grouped public-limb kernel when
    ``w.limbs`` carries a `PublicGroupedLimbs` cache."""
    if isinstance(w, PublicTensor):
        assert bias_parts is None, \
            "public weights take bias_public (a public encoding), not " \
            "additive bias_parts"
        kh, kw, cin_g, cout = (int(d) for d in w.shape)
        if groups == 1:
            cols, ho, wo = _im2col_rss(x, kh, kw, stride, padding)
            wmat = PublicTensor(w.enc.reshape(kh * kw * cin_g, cout), w.limbs)
            return bin_matmul(cols, wmat, parties, tag=tag,
                              bias_public=bias_public)
        # depthwise: per-channel contraction against the public kernel,
        # on every slot at once — still zero communication
        b = int(x.shape[0])
        cin = int(x.shape[3])
        assert groups == cin and cin_g == 1 and cout % groups == 0
        mult = cout // groups
        cols, ho, wo = _im2col_rss(x, kh, kw, stride, padding)
        slots = cols.shares.shape[0]
        cols5 = cols.shares.reshape(slots, b, ho, wo, kh * kw, cin)
        comm.record(tag, rounds=0, nbytes=0)
        if w.limbs is not None:
            from ..kernels.ops import bin_grouped_matmul_op
            z = bin_grouped_matmul_op(cols5, w.limbs)
        else:
            wk = w.enc.reshape(kh * kw, cin, mult)
            z = jnp.einsum("sbhwkc,kcm->sbhwcm", cols5, wk,
                           preferred_element_type=x.ring.dtype)
        out = RSS(z.reshape(slots, b, ho, wo, cout), x.ring)
        if bias_public is not None:
            out = out.add_public(bias_public)
        return out
    assert bias_public is None, \
        "shared weights take additive bias_parts, not a public encoding"
    kh, kw, cin_g, cout = (int(d) for d in w.shape)
    if groups != 1:
        # bin-shared depthwise: the ±1·W product already sits at scale f,
        # so the whole grouped layer is the one reshare round — same parts
        # arithmetic (and PRF draw order) as conv2d's grouped branch, hence
        # bit-identical to the generic route
        z = _grouped_conv_parts(x, w, stride, padding, groups)
        if bias_parts is not None:
            z = z + bias_parts
        return _reshare(z, x.ring, parties, tag=tag)
    cols, ho, wo = _im2col_rss(x, kh, kw, stride, padding)
    wmat = w.reshape(kh * kw * cin_g, cout)
    return bin_matmul(cols, wmat, parties, tag=tag, w_limbs=w_limbs,
                      bias_parts=bias_parts)


# ---------------------------------------------------------------------------
# Truncation (ABY3 Π_trunc1-style; paper §3.3)
# ---------------------------------------------------------------------------

def truncate(x: RSS, parties: Parties, frac: int | None = None,
             tag: str = "trunc") -> RSS:
    """Divide by 2^f after a fixed-point multiply (paper §3.3 Π_trunc).

    Statistical-masking variant with *exact* (never catastrophic) arithmetic:

      offline:  each additive share r_i ~ U[0, 2^{l-3}) from the parties'
                PRF (purely local), so r = Σ r_i < 3·2^{l-3} < 2^{l-1} and
                shares of r >> f are the local shifts r_i >> f (no carries
                can wrap — shares are bounded by construction).
      online:   open c = (x + 2^{l-2}) − r  (1 round).  The positive offset
                keeps the opened value inside (−2^{l-1}, 2^{l-1}), so its
                signed interpretation is exact over the integers — the
                mod-2^l wrap of ABY3's full-range mask (error 2^{l−f} with
                probability ≈ |x|/2^l) can never occur.  Result =
                (c >>_a f) + [r >> f] − 2^{l-2-f} + 1 (bias compensation).

    Deterministic error ≤ 3 ulp; privacy is statistical in the gap between
    |x| and 2^{l-3} (the standard masking argument; DESIGN.md §10).
    Requires |x| < 2^{l-3} — callers keep fixed-point magnitudes bounded.
    """
    ring = x.ring
    f = ring.frac if frac is None else frac

    # ---- offline pair ([r], [r >> f]) — local, zero traffic --------------
    r, rp = _trunc_pair(x.shape, parties, ring, f)

    # ---- online ----------------------------------------------------------
    offset = jnp.asarray(1 << (ring.bits - 2), ring.dtype)
    c = reveal(x.add_public(offset) - r, tag=tag)
    return rp.add_public(_trunc_decode(c, ring, f))


def truncate_probabilistic(x: RSS, parties: Parties, frac: int | None = None,
                           tag: str = "trunc_prob") -> RSS:
    """ABY3 Π_trunc1 with a full-range mask — the paper's citation, kept as
    the reference baseline.  ±1 ulp usually, but fails catastrophically
    (error 2^{l-f}) with probability ≈ |x_fixed| / 2^l; see DESIGN.md §10."""
    ring = x.ring
    f = ring.frac if frac is None else frac
    shape = x.shape
    t = transport.current()
    r, r_plain = parties.rand_rss_open(shape, ring)
    r_shift = ring.to_signed(r_plain) >> f
    zero = parties.zero_shares(shape, ring)
    rp_parts = zero + (r_shift.astype(ring.dtype)
                       * t.party_mask_parts(0, len(shape), ring.dtype))
    # the preprocessing reshare that turns the additive [r >> f] into RSS
    comm.record(tag, rounds=1, nbytes=3 * _numel(x) * ring.nbytes,
                preprocess=True)
    rp = RSS(t.complete(rp_parts), ring)
    masked = reveal(x - r, tag=tag)
    public = (ring.to_signed(masked) >> f).astype(ring.dtype)
    return rp.add_public(public)


# ---------------------------------------------------------------------------
# Algorithm 2: complete linear layer (matmul/conv + bias + trunc)
# ---------------------------------------------------------------------------

def linear_layer(x: RSS, w: RSS | None, b: RSS | None, parties: Parties,
                 truncate_out: bool = True, tag: str = "linear",
                 dot=None, w_limbs=None) -> RSS:
    """z = x @ w + b, truncated back to scale 2^f.

    With fused rounds on (the default) the truncation's masked opening
    rides the matmul's reshare round — 1 online round instead of 2."""
    t = transport.current()
    if truncate_out and _FUSED_ROUNDS:
        bias_parts = None
        if b is not None:
            # product carries scale 2^{2f}; lift the (scale-f) bias to match
            bias_parts = (t.own_view(b.shares).reshape(
                (t.parts_slots,) + (1,) * (x.ndim - 1) + (-1,))
                << jnp.asarray(x.ring.frac, x.ring.dtype))
        return matmul_truncate(x, w, parties, tag=tag, dot=dot,
                               w_limbs=w_limbs, bias_parts=bias_parts)
    z = matmul(x, w, parties, tag=tag, dot=dot, w_limbs=w_limbs)
    if b is not None:
        bsh = b.shares.reshape((t.rss_slots,) + (1,) * (z.ndim - 1) + (-1,))
        if truncate_out:
            # product carries scale 2^{2f}; lift the (scale-f) bias to match
            bsh = bsh << jnp.asarray(z.ring.frac, z.ring.dtype)
        z = RSS(z.shares + bsh, z.ring)
    if truncate_out:
        z = truncate(z, parties, tag=tag + ".trunc")
    return z
