"""Secure inference executor: runs a trained (customized) BNN under the
CBNN protocol stack (paper §3.2–3.6).

Two phases, mirroring the deployment:

  setup (model owner, plaintext):  walk the layer spec, apply the adaptive
    fusing rules — BN→Sign folds into a shared threshold (eq. 8), BN→ReLU
    folds into the preceding linear's (W, b) (eqs. 10–11) — then secret-share
    the resulting weights (or keep them public, see below).

  infer (all parties):  data owner shares the input; every layer runs the
    *cheapest applicable* protocol.  The compiler assigns each linear layer
    a path from the binary-domain taxonomy (DESIGN.md §11):

      arith       fixed-point input × shared weights — Alg 2 + Π_trunc,
                  fused to one opening round (6 ring elements / output).
      bin-shared  post-Sign ±1 input (scale 0) × shared weights — the
                  product lands at scale f, so the layer is ONE reshare
                  round (3 elements / output), bias riding the parts
                  (`linear.bin_matmul` / `bin_conv2d`).
      bin-public  public weights (`compile_secure(..., weights="public")`,
                  the private-input / public-model deployment): every party
                  rebuilds its whole RSS pair locally — zero rounds, zero
                  wire bytes on post-Sign layers; non-binary inputs keep
                  only the truncation opening.

    Sign activations travel as ±1 *integers* (scale 0), so products after a
    Sign layer carry a single 2^f scale — the ring-32 fixed point stays
    inside the MSB-extraction bound.  ``binary_linear="generic"`` routes
    post-Sign layers through the generic Alg-2 machinery (bit-identity
    reference for the binary engine); ``binary_linear="off"`` is the
    binarization-unaware ablation (lift ±1 to scale f, pay the full
    arithmetic opening).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.bin_rss_matmul import public_grouped_limbs, public_weight_limbs
from ..kernels.rss_matmul import precompute_weight_limbs
from ..nn.bnn import ALL_NETS, INPUT_SHAPES, L
from . import comm, transport
from .activation import (relu_from_msb, relu_from_msb_arith, sign_from_msb,
                         sign_from_msb_arith)
from .linear import (PublicTensor, bin_conv2d, bin_matmul, conv2d,
                     conv2d_truncate, fused_rounds, linear_layer, matmul,
                     matmul_truncate, reveal, truncate)
from .msb import msb_extract, msb_extract_arith
from .norm import fuse_bn_linear, fuse_bn_sign_threshold
from .pooling import secure_maxpool, sign_maxpool_fused
from .randomness import Parties
from .ring import RingSpec, default_ring
from .rss import RSS, share

WEIGHT_MODES = ("shared", "public")
BINARY_LINEAR_MODES = ("auto", "generic", "off")


@dataclasses.dataclass
class SecureModel:
    ops: list
    ring: RingSpec
    net: str
    comm_per_query: comm.CommLedger | None = None
    use_kernel: bool = False
    weights: str = "shared"        # "shared" | "public"  (DESIGN.md §11)
    binary_linear: str = "auto"    # "auto" | "generic" | "off"
    deployment: str | None = None  # descriptor the path solver ran against
    predicted: Any = None          # cost_model.CostReport from compile time


def _fold_bn(spec, params, i):
    """Return (gamma', beta'-style fold targets) for bn layer i."""
    return (np.asarray(params[f"l{i}_g"]), np.asarray(params[f"l{i}_beta"]),
            np.asarray(params[f"l{i}_mu"]), np.asarray(params[f"l{i}_var"]))


def compile_secure(params: dict, net: str, key,
                   ring: RingSpec | None = None,
                   use_kernel_dot: bool = False,
                   weights: str = "shared",
                   binary_linear: str = "auto",
                   deployment=None) -> SecureModel:
    """Model-owner setup: fuse + share (or publish).  `params` are the
    trained plaintext parameters (bnn.py layout).

    ``use_kernel_dot=True`` additionally pre-decomposes every linear/conv
    weight-share stack (and its fused operand w_i + w_{i+1}) into cached
    int8 limbs, so `secure_infer` routes the layer through the single-launch
    3-party Pallas kernel — weight limbs are never recomputed per query.
    Depthwise (grouped) convs with shared weights need no cache: they run
    as a direct tap product of the shares (`linear._grouped_conv_parts`)
    on either setting.

    ``weights="public"`` keeps model parameters in the clear (the
    private-input / public-model deployment, DESIGN.md §11): linear layers
    become local share algebra (zero wire bytes on post-Sign layers) and
    the kernel cache uses the adaptive public limb collapse
    (`kernels.bin_rss_matmul.public_weight_limbs` — 1–3 limbs instead of a
    share's unconditional 4).  ``binary_linear`` selects the post-Sign
    routing: "auto" = the binary-domain engine, "generic" = the plain Alg-2
    machinery (bit-identity reference), "off" = binarization-unaware
    ablation (±1 lifted to scale f, full truncation opening paid).

    ``deployment`` (a `cost_model.DeploymentDescriptor` or registry name
    "local" / "lan" / "wan") switches the path assignment from the fixed
    preference order to the symbolic cost solver: each linear layer gets
    the path minimizing predicted time under that link/compute model, and
    the per-layer prediction rides on the op as ``op["cost"]`` (the whole
    report on ``model.predicted``)."""
    assert weights in WEIGHT_MODES, weights
    assert binary_linear in BINARY_LINEAR_MODES, binary_linear
    # "generic" is the bit-identity reference for the bin-SHARED engine;
    # public weights have no generic Alg-2 route, so reject the combination
    # instead of silently behaving like "auto"
    assert not (weights == "public" and binary_linear == "generic"), \
        'binary_linear="generic" is a shared-weights reference mode; ' \
        'use "auto" or "off" with weights="public"'
    ring = ring or default_ring()
    spec = ALL_NETS[net]
    public = weights == "public"
    ops: list[dict[str, Any]] = []
    i = 0
    kidx = 0

    def nk():
        nonlocal kidx
        kidx += 1
        return jax.random.fold_in(key, kidx)

    while i < len(spec):
        l = spec[i]
        if l.kind in ("conv", "sepconv", "fc"):
            if l.kind == "sepconv":
                w_parts = [np.asarray(params[f"l{i}_dw"]),
                           np.asarray(params[f"l{i}_pw"])]
            else:
                w_parts = [np.asarray(params[f"l{i}_w"])]
            b = np.asarray(params[f"l{i}_b"])
            # lookahead: bn (+ act) fusing
            nxt = spec[i + 1] if i + 1 < len(spec) else None
            nxt2 = spec[i + 2] if i + 2 < len(spec) else None
            sign_threshold = None
            if nxt is not None and nxt.kind == "bn":
                g, beta, mu, var = _fold_bn(spec, params, i + 1)
                gp = g / np.sqrt(var + 1e-5)
                if nxt2 is not None and nxt2.kind == "act" \
                        and nxt2.act == "sign" and np.all(gp > 0):
                    # eq. 8: threshold shift, applied inside the Sign layer
                    sign_threshold = fuse_bn_sign_threshold(g, beta, mu, var)
                    i += 1  # consume bn
                else:
                    # eqs. 10-11: fold into (W, b) (ReLU / plain / γ'≤0 case)
                    w_parts[-1], b = fuse_bn_linear(w_parts[-1], b, g, beta,
                                                    mu, var)
                    i += 1
            op = {"op": l.kind, "k": l.k, "stride": l.stride, "pad": l.pad}
            if public:
                op["pub_w"] = [_public_weight(w, l.kind, j, ring,
                                              use_kernel_dot)
                               for j, w in enumerate(w_parts)]
                op["pub_b"] = np.asarray(ring.encode(b))
                op["pub_thresh"] = (np.asarray(ring.encode(sign_threshold))
                                    if sign_threshold is not None else None)
            else:
                op["w"] = [share(w, nk(), ring) for w in w_parts]
                op["b"] = share(b, nk(), ring)
                op["sign_threshold"] = (share(sign_threshold, nk(), ring)
                                        if sign_threshold is not None
                                        else None)
                if use_kernel_dot:
                    op["wlimbs"] = [_weight_limbs_for(wr, l.kind, j)
                                    for j, wr in enumerate(op["w"])]
            ops.append(op)
        elif l.kind == "act":
            ops.append({"op": "sign" if l.act == "sign" else "relu"})
        elif l.kind == "bn":
            # un-fused BN (no preceding linear): affine via public-style op
            g, beta, mu, var = _fold_bn(spec, params, i)
            scale = g / np.sqrt(var + 1e-5)
            shift = beta - mu * scale
            if public:
                ops.append({"op": "affine",
                            "pub_scale": np.asarray(ring.encode(scale)),
                            "pub_shift": np.asarray(ring.encode(shift))})
            else:
                ops.append({"op": "affine", "scale": share(scale, nk(), ring),
                            "shift": share(shift, nk(), ring)})
        elif l.kind == "maxpool":
            ops.append({"op": "maxpool"})
        elif l.kind == "flatten":
            ops.append({"op": "flatten"})
        i += 1
    _annotate_binary_paths(ops, weights, binary_linear)
    from . import cost_model
    dep = cost_model.resolve_deployment(deployment)
    model = SecureModel(ops=ops, ring=ring, net=net,
                        use_kernel=use_kernel_dot, weights=weights,
                        binary_linear=binary_linear,
                        deployment=dep.name if dep else None)
    # the symbolic solver re-derives every op's path label (ties keep the
    # fixed preference order, so deployment=None reproduces the legacy
    # labels exactly) and stamps per-layer predicted costs
    model.predicted = cost_model.annotate_model(model, deployment=dep)
    return model


def _annotate_binary_paths(ops: list, weights: str = "shared",
                           binary_linear: str = "auto") -> None:
    """Static per-layer input-domain + path-taxonomy analysis (§11).

    Walks the compiled op list with the same transition rules the executor
    applies at runtime and stamps every linear op with ``binary_in``: True
    iff the layer spec guarantees its input is a Sign layer's ±1 integers
    at scale 0 (maxpool and flatten preserve the domain; linear / ReLU /
    affine leave it).  The executor dispatches paths off this flag, so the
    routing is decided at compile time, not traced state.

    Each linear op additionally gets ``path`` — the human-readable §11
    taxonomy label the compiler assigned ("arith" / "bin-shared" /
    "bin-public" / "bin-public+trunc"); sepconv ops get a
    ``(depthwise, pointwise)`` pair because the two halves can land on
    different paths (a post-Sign depthwise is reshare-only or free, while
    its pointwise always re-enters the fixed-point domain at 2f).
    Benchmarks and the DESIGN.md table generator read these labels instead
    of re-deriving the dispatch rules."""
    public = weights == "public"
    binary = False

    def label(binary_in: bool) -> str:
        # "off" lifts ±1 to scale f at runtime, so even a post-Sign layer
        # routes arith (the binarization-unaware ablation); ``binary_in``
        # itself stays domain-truth — the cost accounting selects post-Sign
        # layers by domain, not by the routing chosen for them
        routed = binary_in and binary_linear != "off"
        if public:
            return "bin-public" if routed else "bin-public+trunc"
        if routed and binary_linear == "auto":
            return "bin-shared"
        return "arith"

    for op in ops:
        kind = op["op"]
        if kind in ("conv", "sepconv", "fc"):
            op["binary_in"] = binary
            if kind == "sepconv":
                # pointwise input is the depthwise product at scale f —
                # never binary, so the pw half always pays the truncation
                op["path"] = (label(binary), label(False))
            else:
                op["path"] = label(binary)
            binary = False
        elif kind == "sign":
            binary = True
        elif kind in ("relu", "affine"):
            binary = False
        # maxpool / flatten: domain-preserving


def _public_weight(w: np.ndarray, kind: str, part_idx: int, ring: RingSpec,
                   use_kernel_dot: bool) -> PublicTensor:
    """Encode one public weight tensor; cache its adaptive public limbs for
    the matmul-able halves when the kernel path is requested."""
    enc = jnp.asarray(ring.encode(w))
    limbs = None
    if use_kernel_dot:
        if kind == "fc":
            limbs = public_weight_limbs(enc)
        elif kind == "conv" or (kind == "sepconv" and part_idx == 1):
            kh, kw, cin_g, cout = (int(d) for d in enc.shape)
            limbs = public_weight_limbs(enc.reshape(kh * kw * cin_g, cout))
        else:  # depthwise half: per-channel public grouped limbs
            kh, kw, cin_g, cout = (int(d) for d in enc.shape)
            assert cin_g == 1, "depthwise kernels are (kh, kw, 1, Cin)"
            limbs = public_grouped_limbs(
                enc.reshape(kh * kw, cout, 1).transpose(1, 0, 2))
    return PublicTensor(enc, limbs)


def _weight_limbs_for(w: RSS, kind: str, part_idx: int):
    """Setup-time limb cache for one weight-share stack: dense layers get
    `WeightLimbs` for the fused matmul kernel.  The depthwise half of a
    sepconv gets None: it runs as a direct tap product on the weight
    shares themselves (`linear._grouped_conv_parts`), with no kernel
    launch to feed."""
    if kind == "fc":
        return precompute_weight_limbs(w.shares)
    if kind == "conv" or (kind == "sepconv" and part_idx == 1):
        kh, kw, cin_g, cout = (int(d) for d in w.shape)
        return precompute_weight_limbs(
            w.shares.reshape(3, kh * kw * cin_g, cout))
    return None


# the tag of each linear kind's product (``pw``: a sepconv's pointwise half)
_LIN_TAG = {"fc": "fc", "conv": "conv", "pw": "pwconv"}


def _infer_linear_shared(h: RSS, op: dict, parties: Parties, idx: int,
                         ring: RingSpec, binary_in: bool,
                         binary_engine: bool) -> RSS:
    """One shared-weight linear layer, dispatched by input domain.

    ``binary_in`` + ``binary_engine``: the bin-shared path — product at
    scale f, bias riding the additive parts, ONE reshare round
    (`bin_matmul` / `bin_conv2d`, DESIGN.md §11).  Otherwise the arithmetic
    path: fused matmul+Π_trunc opening at scale 2f, or (``binary_in`` with
    the "generic" routing) the plain Alg-2 round without truncation —
    bit-identical to the bin-shared path, kept as its reference."""
    tp = transport.current()
    wlimbs = op.get("wlimbs") or [None] * len(op["w"])
    kind = op["op"]
    if kind == "sepconv":
        # separable: depthwise then pointwise (Alg 2 twice, Fig 3), the
        # depthwise half as a direct tap product of the shares.  A
        # post-Sign depthwise product is already at scale f — the binary
        # engine runs it as a first-class bin-shared layer (one reshare,
        # no truncation); otherwise the arith route pays the dwtrunc.
        cin = int(h.shape[-1])
        if binary_in and binary_engine:
            with comm.scope(f"l{idx}.dwconv.bin") as tag:
                h = bin_conv2d(h, op["w"][0], parties, stride=op["stride"],
                               padding=op["pad"], groups=cin, tag=tag,
                               w_limbs=wlimbs[0])
        else:
            with comm.scope(f"l{idx}.dwconv") as tag:
                h = conv2d(h, op["w"][0], parties, stride=op["stride"],
                           padding=op["pad"], groups=cin, tag=tag,
                           w_limbs=wlimbs[0])
            if not binary_in:
                with comm.scope(f"l{idx}.dwtrunc") as tag:
                    h = truncate(h, parties, tag=tag)
        at_2f = True
        lin, w_rss, wl = "pw", op["w"][1], wlimbs[1]
    else:
        at_2f = not binary_in
        lin, w_rss, wl = kind, op["w"][0], wlimbs[0]
    if not at_2f and binary_engine:
        # bin-shared engine: scale-f bias rides the additive parts through
        # the single reshare round — 3 ring elements per output slot
        bias = tp.own_view(op["b"].shares).reshape(
            (tp.parts_slots,) + (1,) * (h.ndim - 1) + (-1,))
        with comm.scope(f"l{idx}.{lin}.bin") as tag:
            if lin == "fc":
                return bin_matmul(h, w_rss, parties, tag=tag, w_limbs=wl,
                                  bias_parts=bias)
            return bin_conv2d(h, w_rss, parties, stride=op["stride"],
                              padding=op["pad"], tag=tag, w_limbs=wl,
                              bias_parts=bias)
    if at_2f and fused_rounds():
        # beyond-paper default: product + bias + Π_trunc in the one
        # reshare round (matmul_truncate / conv2d_truncate) — the
        # bias rides the additive parts, so only the own share
        bias = tp.own_view(op["b"].shares).reshape(
            (tp.parts_slots,) + (1,) * (h.ndim - 1) + (-1,))
        bias = bias * jnp.asarray(ring.scale, ring.dtype)
        with comm.scope(f"l{idx}.{_LIN_TAG[lin]}") as tag:
            if lin == "fc":
                return matmul_truncate(h, w_rss, parties, tag=tag,
                                       w_limbs=wl, bias_parts=bias)
            if lin == "conv":
                return conv2d_truncate(h, w_rss, parties,
                                       stride=op["stride"], padding=op["pad"],
                                       tag=tag, w_limbs=wl, bias_parts=bias)
            return conv2d_truncate(h, w_rss, parties, tag=tag, w_limbs=wl,
                                   bias_parts=bias)
    with comm.scope(f"l{idx}.{_LIN_TAG[lin]}") as tag:
        if lin == "fc":
            z = matmul(h, w_rss, parties, tag=tag, w_limbs=wl)
        elif lin == "conv":
            z = conv2d(h, w_rss, parties, stride=op["stride"],
                       padding=op["pad"], tag=tag, w_limbs=wl)
        else:
            z = conv2d(h, w_rss, parties, tag=tag, w_limbs=wl)
    # z is a full RSS here, so the bias is added share-wise
    bias = op["b"].shares.reshape(
        (z.shares.shape[0],) + (1,) * (z.ndim - 1) + (-1,))
    if at_2f:
        bias = bias * jnp.asarray(ring.scale, ring.dtype)
    z = RSS(z.shares + bias, ring)
    if at_2f:
        with comm.scope(f"l{idx}.trunc") as tag:
            z = truncate(z, parties, tag=tag)
    return z


def _infer_linear_public(h: RSS, op: dict, parties: Parties, idx: int,
                         ring: RingSpec, binary_in: bool) -> RSS:
    """One public-weight linear layer (bin-public path, DESIGN.md §11).

    Every product is local share algebra — the only protocol cost left is
    the truncation opening when the input still carries scale f (first
    layer, ReLU nets, the depthwise→pointwise seam); post-Sign layers cost
    zero rounds and zero bytes."""
    kind = op["op"]
    lift = jnp.asarray(ring.frac, ring.dtype)
    pub_b = jnp.asarray(op["pub_b"])
    if kind == "sepconv":
        cin = int(h.shape[-1])
        with comm.scope(f"l{idx}.dwconv.pub") as tag:
            h = bin_conv2d(h, op["pub_w"][0], parties, stride=op["stride"],
                           padding=op["pad"], groups=cin, tag=tag)
        if not binary_in:
            with comm.scope(f"l{idx}.dwtrunc") as tag:
                h = truncate(h, parties, tag=tag)
        # pointwise input carries scale f, so the product lands at 2f
        with comm.scope(f"l{idx}.pwconv.pub") as tag:
            h = bin_conv2d(h, op["pub_w"][1], parties, tag=tag,
                           bias_public=pub_b << lift)
        with comm.scope(f"l{idx}.trunc") as tag:
            return truncate(h, parties, tag=tag)
    w = op["pub_w"][0]
    bias = pub_b if binary_in else pub_b << lift
    with comm.scope(f"l{idx}.{kind}.pub") as tag:
        if kind == "fc":
            h = bin_matmul(h, w, parties, tag=tag, bias_public=bias)
        else:
            h = bin_conv2d(h, w, parties, stride=op["stride"],
                           padding=op["pad"], tag=tag, bias_public=bias)
    if not binary_in:
        with comm.scope(f"l{idx}.trunc") as tag:
            h = truncate(h, parties, tag=tag)
    return h


# ledger head of each executor op kind: the head of every tag the op
# records and the name of its jax.named_scope (``l3``, ``sign4``, ``mp5``)
_HEADS = {"conv": "l", "sepconv": "l", "fc": "l", "sign": "sign",
          "relu": "relu", "affine": "aff", "maxpool": "mp"}


def secure_infer(model: SecureModel, x_shares: RSS, parties: Parties,
                 reveal_output: bool = True):
    """Run one secure inference. x_shares: RSS of (B,H,W,C) or (B,D).

    Defaults to the fused one-round protocol variants (matmul_truncate for
    linear+trunc, multiply-open + local Alg-4 inside MSB extraction) —
    DESIGN.md §8; `set_fused_rounds(False)` restores the paper-faithful
    round structure.  Models compiled with use_kernel_dot=True route every
    non-depthwise linear through the fused 3-party Pallas kernel with the
    cached weight limbs.  Each linear layer runs the path the compiler
    assigned it (arith / bin-shared / bin-public — DESIGN.md §11)."""
    # every trace starts from the counter base, so jit retraces (and tape
    # playback, DESIGN.md §12) consume identical draw sequences — pinned by
    # tests/test_preprocessing.py::test_retrace_counter_sequence.  Corollary
    # (see Parties): one secure_infer per Parties per traced program —
    # derive per-inference Parties from separate session keys to compose.
    parties = parties.fresh()
    ring = model.ring
    h = x_shares
    prev_sign = False  # is the current activation ±1-integer valued?
    pending_sign_threshold = None

    for idx, op in enumerate(model.ops):
        kind = op["op"]
        if kind == "flatten":      # a reshape: no ledger head, no scope
            b = int(h.shape[0])
            h = h.reshape(b, int(np.prod(h.shape[1:])))
            continue
        # one named scope per ledger head, so device time joins the ledger
        with comm.scope(f"{_HEADS[kind]}{idx}") as head:
            if kind in ("conv", "sepconv", "fc"):
                # product scale: input(±1 int: 0 | fixed: f) + W(f) => f or 2f
                binary_in = op.get("binary_in", False)
                if model.binary_linear == "off" and binary_in:
                    # binarization-unaware ablation: lift ±1 to scale f and
                    # pay the full arithmetic opening
                    h = h.mul_public_int(jnp.asarray(ring.scale, ring.dtype))
                    binary_in = False
                if model.weights == "public":
                    h = _infer_linear_public(h, op, parties, idx, ring,
                                             binary_in)
                else:
                    # the compile-time solver may pin the engine choice per
                    # op (cost_model.annotate_model); absent that, the
                    # model-wide routing mode decides
                    h = _infer_linear_shared(
                        h, op, parties, idx, ring, binary_in,
                        binary_engine=op.get(
                            "engine", model.binary_linear == "auto"))
                prev_sign = False
                pending_sign_threshold = (op.get("sign_threshold")
                                          if model.weights == "shared"
                                          else op.get("pub_thresh"))
            elif kind == "sign":
                if pending_sign_threshold is not None:
                    t = pending_sign_threshold
                    if isinstance(t, RSS):
                        h = RSS(h.shares + t.shares.reshape(
                            (h.shares.shape[0],) + (1,) * (h.ndim - 1)
                            + (-1,)), ring)
                    else:  # public threshold (ring-encoded array)
                        h = h.add_public(t)
                    pending_sign_threshold = None
                if fused_rounds():
                    # 1 online round: multiply-open + local Alg-4
                    # (activation.py)
                    with comm.scope(f"{head}.msb") as tag:
                        _, msb_a = msb_extract_arith(h, parties, tag=tag)
                    bits = sign_from_msb_arith(msb_a)
                else:
                    with comm.scope(f"{head}.msb") as tag:
                        msb = msb_extract(h, parties, tag=tag)
                    bits = sign_from_msb(msb, parties, ring, tag=head)
                # keep {0,1} if maxpool follows (fused path); else lift to ±1
                nxt = (model.ops[idx + 1]["op"] if idx + 1 < len(model.ops)
                       else None)
                if nxt == "maxpool":
                    h = bits  # §3.6 fusion consumes the indicator bits
                else:
                    h = bits.mul_public_int(2).add_public(
                        jnp.asarray(-1, ring.signed_dtype).astype(ring.dtype))
                prev_sign = True
            elif kind == "relu":
                fused = fused_rounds()
                with comm.scope(f"{head}.msb") as tag:
                    msb = (msb_extract_arith(h, parties, tag=tag)[1] if fused
                           else msb_extract(h, parties, tag=tag))
                relu = relu_from_msb_arith if fused else relu_from_msb
                h = relu(h, msb, parties, tag=head)
                prev_sign = False
            elif kind == "affine":
                from .linear import mul, mul_truncate
                if model.weights == "public":
                    # public BN affine: local mult by the encoded scale (2f),
                    # truncate, public shift — no multiplication protocol
                    h = RSS(h.shares * jnp.asarray(op["pub_scale"]), ring)
                    with comm.scope(f"{head}.tr") as tag:
                        h = truncate(h, parties, tag=tag)
                    h = h.add_public(jnp.asarray(op["pub_shift"]))
                elif fused_rounds():
                    h = mul_truncate(h, op["scale"], parties, tag=head)
                    h = h + op["shift"]
                else:
                    h = mul(h, op["scale"], parties, tag=head)
                    with comm.scope(f"{head}.tr") as tag:
                        h = truncate(h, parties, tag=tag)
                    h = h + op["shift"]
                prev_sign = False
            elif kind == "maxpool":
                if prev_sign:
                    bits = sign_maxpool_fused(h, parties, tag=head)
                    h = bits.mul_public_int(2).add_public(
                        jnp.asarray(-1, ring.signed_dtype).astype(ring.dtype))
                    prev_sign = True
                else:
                    h = secure_maxpool(h, parties, tag=head)
    if reveal_output:
        with comm.scope("output") as tag:
            return reveal(h, tag=tag, decode=True)
    return h


def secure_infer_cost(model: SecureModel, input_shape,
                      parties_key=None) -> comm.CommLedger:
    """Trace-only communication ledger for one query batch."""
    parties = Parties.setup(jax.random.PRNGKey(7))
    x = jax.ShapeDtypeStruct((3,) + tuple(input_shape), model.ring.dtype)

    def run(xs):
        return secure_infer(model, RSS(xs, model.ring), parties)

    return comm.estimate_cost(run, x)


def post_sign_linear_cost(model: SecureModel,
                          led: comm.CommLedger) -> tuple[int, int]:
    """(online bytes, online rounds) summed over the linear layers the
    compiler marked ``binary_in`` — the post-Sign layers the binary-domain
    engine targets (DESIGN.md §11).  Shared by the acceptance pins
    (tests/test_bin_linear.py) and the DESIGN.md cost-table generator so
    the two can never drift."""
    idxs = {i for i, op in enumerate(model.ops)
            if op["op"] in ("conv", "sepconv", "fc")
            and op.get("binary_in", False)}
    nbytes = rounds = 0
    for tag, (r, b) in led.by_tag.items():
        if tag.startswith("pre:"):
            continue
        head = tag.split(".", 1)[0]
        if head.startswith("l") and head[1:].isdigit() \
                and int(head[1:]) in idxs:
            nbytes += b
            rounds += r
    return nbytes, rounds


# ---------------------------------------------------------------------------
# Mesh backend: one real per-party program over a size-3 "party" mesh axis
# ---------------------------------------------------------------------------

def _is_public_leaf(path) -> bool:
    """A model-ops leaf is public iff it sits under a ``pub_*`` dict key
    (public weights/bias/threshold/affine of the bin-public path): such
    tensors are replicated to every party, not party-sharded."""
    return any(isinstance(k, jax.tree_util.DictKey)
               and str(k.key).startswith("pub") for k in path)


def _split_arrays(tree):
    """Partition a pytree into its party-stacked jax-array leaves, its
    replicated PUBLIC array leaves (``pub_*`` entries — no party axis),
    and a rebuild closure for the remaining static structure."""
    leaves_p, treedef = jax.tree_util.tree_flatten_with_path(tree)
    kinds = []   # "shared" | "public" | None per leaf
    for path, leaf in leaves_p:
        if not isinstance(leaf, (jax.Array, np.ndarray)):
            kinds.append(None)
        else:
            kinds.append("public" if _is_public_leaf(path) else "shared")
    arrays = tuple(l for (_, l), k in zip(leaves_p, kinds) if k == "shared")
    pub_arrays = tuple(l for (_, l), k in zip(leaves_p, kinds)
                       if k == "public")

    def rebuild(new_arrays, new_pub):
        it, itp = iter(new_arrays), iter(new_pub)
        new_leaves = [next(it) if k == "shared"
                      else next(itp) if k == "public" else l
                      for (_, l), k in zip(leaves_p, kinds)]
        return jax.tree_util.tree_unflatten(treedef, new_leaves)

    return arrays, pub_arrays, rebuild


def make_secure_infer_mesh(model: SecureModel, mesh, *,
                           party_axis: str = "party",
                           batch_axis: str | None = None,
                           reveal_output: bool = True,
                           tape_spec=None,
                           verifier=None,
                           transport_wrap=None):
    """Build a jit-able mesh-backend runner for ``secure_infer``.

    Returns ``fn(keys, x_stack) -> (3, B, classes)`` where ``x_stack`` is
    the global (3, B, ...) share stack.  Inside, each device of the size-3
    ``party_axis`` runs ONE party's program under :class:`MeshTransport`:
    share stacks travel as the replicated pair ``[x_i, x_{i+1}]``, reshares
    are ``ppermute``, openings are ``all_gather`` (DESIGN.md §2).  The
    model's share/limb tensors enter pre-paired (the dealer hands each
    party both components of its pair, like input sharing — unmetered), so
    the only collectives in the compiled per-party HLO are the ones the
    CommLedger records.

    ``batch_axis`` optionally shards the query batch over a second mesh
    axis — the §6 data axis composing with the party axis.  On a
    party-only mesh the run is strictly bit-identical to LocalTransport
    (identical shapes ⇒ identical PRF streams); with a sharded batch the
    per-shard PRF draws differ from the full-batch sim, so the exact
    truncation's ±ulp noise may differ (values still agree to a few ulp;
    Sign decisions are unaffected outside ulp-sized margins).

    ``tape_spec`` (a :class:`~repro.core.preprocessing.MaterialSpec`)
    switches the runner to the tape-backed online phase (DESIGN.md §12):
    the returned ``fn(keys, x_stack, slabs)`` consumes one query's
    material slice instead of computing PRFs — party-stacked slabs enter
    pre-paired like the model shares (own + rolled, ``ingest``), parts
    slabs shard to their own row, key-replicated slabs stay whole.  The
    material is traced at the full query batch, so it composes with the
    party axis only (no ``batch_axis``).

    ``verifier`` (an :class:`~repro.core.integrity.Verifier`) switches the
    runner to verified inference: the traced program digests every
    opening/reshare/send view and ``fn`` returns ``(out, report)`` — run
    ``verifier.check(report)`` host-side before releasing ``out``
    (DESIGN.md §14).  ``transport_wrap`` wraps the per-party transport
    (e.g. :class:`~repro.core.integrity.FaultInjectingTransport` — the
    chaos harness)."""
    from jax.sharding import PartitionSpec as P

    from . import integrity

    assert mesh.shape[party_axis] == 3, \
        f"mesh axis {party_axis!r} must have size 3"
    assert tape_spec is None or batch_axis is None, \
        "tape playback is traced at the global batch — party-only mesh"
    # the verified runner returns (out, digest report); report vectors are
    # per party, so the digest layout composes with the party axis only
    assert verifier is None or batch_axis is None, \
        "verified mesh serving runs party-only (digest report layout)"
    arrays, pub_arrays, rebuild = _split_arrays(model.ops)
    for a in arrays:
        assert int(a.shape[0]) == 3, f"expected party-stacked array: {a.shape}"

    from .preprocessing import REPLICATED, STACK_PAIR, TapeParties
    x_spec = P(party_axis, batch_axis)
    w_spec = P(party_axis)
    n_arr = len(arrays)
    # public (pub_*) tensors are replicated: every party holds the clear
    # model, so their in_spec carries no party axis (bin-public path);
    # tape slab dicts take pytree-prefix specs (party-sharded stacks,
    # replicated key-derived masks)
    in_specs = (P(), x_spec, x_spec, (w_spec,) * n_arr, (w_spec,) * n_arr,
                (P(),) * len(pub_arrays), w_spec, w_spec, w_spec, P())
    out_specs = P(party_axis, batch_axis)
    if verifier is not None:
        # (out, digest report): each report leaf is this party's digest
        # vector, stacked to (3, n) across the party axis for the
        # host-side cross-party compare (integrity.Verifier.check)
        out_specs = (out_specs,
                     {k: P(party_axis) for k in integrity.REPORT_KEYS})
    cnt0 = 0

    def inner(keys, x_own, x_nxt, arrs_own, arrs_nxt, pub_arrs,
              tp_own, tp_nxt, tp_parts, tp_repl):
        t = transport.MeshTransport(party_axis)
        if transport_wrap is not None:
            t = transport_wrap(t)
        with transport.use_transport(t), integrity.verify_scope(verifier):
            if tape_spec is not None:
                slabs = {k: t.ingest(tp_own[k], tp_nxt[k]) for k in tp_own}
                slabs.update(tp_parts)
                slabs.update(tp_repl)
                prt = TapeParties(keys, slabs, tape_spec)
            else:
                prt = Parties(keys, cnt0)
            ops = rebuild([t.ingest(o, n) for o, n in zip(arrs_own,
                                                          arrs_nxt)],
                          pub_arrs)
            m = SecureModel(ops=ops, ring=model.ring, net=model.net,
                            use_kernel=model.use_kernel,
                            weights=model.weights,
                            binary_linear=model.binary_linear)
            x = RSS(t.ingest(x_own, x_nxt), model.ring)
            out = secure_infer(m, x, prt, reveal_output=reveal_output)
            if reveal_output:
                out = out[None]       # replicated opening, stacked per party
            else:
                out = t.own_view(out.shares)
            if verifier is None:
                return out
            rep = verifier.traced_report()
            return out, {k: v[None] for k, v in rep.items()}

    sm = jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)

    def roll(a):
        return jnp.roll(a, -1, axis=0)

    arrs_nxt = tuple(roll(a) for a in arrays)

    if tape_spec is None:
        def fn(keys, x_stack):
            return sm(keys, x_stack, roll(x_stack), arrays, arrs_nxt,
                      pub_arrays, {}, {}, {}, {})
        return fn

    layout = {k: v.layout for k, v in tape_spec.slabs.items()}

    def prepare(x_stack, slabs):
        """Dealer-side pairing for one query, OUTSIDE the online program:
        build the rolled (next-share) copies of the input stack and the
        pair-layout slabs eagerly so the compiled online HLO contains only
        the protocol's own collectives (the exact online-row cross-check
        of roofline.analyze.ledger_vs_wire)."""
        pair = {k: v for k, v in slabs.items() if layout[k] == STACK_PAIR}
        parts = {k: v for k, v in slabs.items()
                 if layout[k] not in (STACK_PAIR, REPLICATED)}
        repl = {k: v for k, v in slabs.items() if layout[k] == REPLICATED}
        return (x_stack, roll(x_stack), pair,
                {k: roll(v) for k, v in pair.items()}, parts, repl)

    def fn_tape(keys, prepared):
        x_own, x_nxt, pair, pair_nxt, parts, repl = prepared
        return sm(keys, x_own, x_nxt, arrays, arrs_nxt, pub_arrays,
                  pair, pair_nxt, parts, repl)

    fn_tape.prepare = prepare
    return fn_tape


def secure_infer_mesh(model: SecureModel, x_shares: RSS, parties: Parties,
                      mesh, *, party_axis: str = "party",
                      batch_axis: str | None = None,
                      reveal_output: bool = True, jit: bool = True):
    """Run one secure inference with each party as a real per-device
    program (MeshTransport backend).  Bit-identical to the LocalTransport
    path on a party-only mesh — tests/test_transport_mesh.py pins this
    (see make_secure_infer_mesh for the sharded-batch ulp caveat).

    Returns the revealed output of party 0 (all parties' openings are
    identical) or, with ``reveal_output=False``, the output RSS."""
    fn = make_secure_infer_mesh(model, mesh, party_axis=party_axis,
                                batch_axis=batch_axis,
                                reveal_output=reveal_output)
    if jit:
        fn = jax.jit(fn)
    out = fn(parties.keys, x_shares.shares)
    if reveal_output:
        return out[0]
    return RSS(out, model.ring)
