"""CBNN protocols applied to a transformer block + LM serving (DESIGN.md §4/§16).

The paper's customization recipe carried to the LM families: every linear is
Alg-2 RSS matmul (+Π_trunc), the attention softmax is replaced by the
MPC-friendly ReLU-attention (ReLU(s)/L — only Alg 3+5 + a public multiply),
FFN activation is secure ReLU, and RMSNorm uses the Newton-rsqrt substrate.
An un-customized mode with full secure softmax exists for comparison; the
benchmark (benchmarks/secure_lm.py) measures the comm/round gap — the same
experiment shape as paper Table 2's customized-vs-typical comparison.

Autoregressive serving (DESIGN.md §16): :class:`SecureKVCache` holds the
per-block K/V projections as RSS share stacks whose leading axis is the
active transport's slot layout — 3 additive slots under ``LocalTransport``,
the replicated pair ``[c_i, c_{i+1}]`` per party under ``MeshTransport`` —
so :func:`secure_decode_step` (one token through every block, cache rows
written in place) runs bit-identically under both backends.
:func:`secure_prefill` is a ``lax.scan`` of the *same* step body over the
prompt (mirroring launch/serve.py's jitted prefill ingest): per-position
PRF keys come from ``fold_in(keys, pos)`` inside the step, so the scanned
prefill and the per-token decode loop draw identical randomness at every
position — prefill-then-decode equals the full-sequence run bit-for-bit
(tests/test_secure_transformer.py pins this).

Generated tokens are public by functionality: each step reveals the logits
(the output the data owner receives), the argmax is public, and the next
embedding row is a local gather on the shared embedding table — zero rounds.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import comm, transport
from .linear import matmul, matmul_truncate, mul, reveal, truncate, \
    fused_rounds
from .activation import secure_relu
from .norm import secure_rmsnorm
from .randomness import Parties
from .ring import RingSpec, default_ring
from .rss import RSS, share
from .softmax import relu_attention_scores, secure_softmax


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SecureBlockParams:
    wq: RSS
    wk: RSS
    wv: RSS
    wo: RSS
    w_up: RSS
    w_down: RSS
    g1: RSS
    g2: RSS
    n_heads: int
    head_dim: int

    _FIELDS = ("wq", "wk", "wv", "wo", "w_up", "w_down", "g1", "g2")

    def tree_flatten(self):
        return (tuple(getattr(self, f) for f in self._FIELDS),
                (self.n_heads, self.head_dim))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, n_heads=aux[0], head_dim=aux[1])


def share_block_params(key, d: int, n_heads: int, d_ff: int,
                       ring: RingSpec | None = None,
                       numpy_params: dict | None = None) -> SecureBlockParams:
    """Model-owner setup: create (or take) plaintext weights and share them."""
    ring = ring or default_ring()
    hd = d // n_heads
    rng = np.random.default_rng(0)
    p = numpy_params or {
        "wq": rng.normal(0, 1 / math.sqrt(d), (d, d)).astype(np.float32),
        "wk": rng.normal(0, 1 / math.sqrt(d), (d, d)).astype(np.float32),
        "wv": rng.normal(0, 1 / math.sqrt(d), (d, d)).astype(np.float32),
        "wo": rng.normal(0, 1 / math.sqrt(d), (d, d)).astype(np.float32),
        "w_up": rng.normal(0, 1 / math.sqrt(d), (d, d_ff)).astype(np.float32),
        "w_down": rng.normal(0, 1 / math.sqrt(d_ff),
                             (d_ff, d)).astype(np.float32),
        "g1": np.ones((d,), np.float32),
        "g2": np.ones((d,), np.float32),
    }
    ks = jax.random.split(key, 8)
    shared_p = dict(p)
    # fold the 1/√hd attention scale into W_q at setup (model-owner side,
    # free) — a 3f-scaled product would overflow the 32-bit ring otherwise
    shared_p["wq"] = p["wq"] / math.sqrt(hd)
    sh = {k: share(v, kk, ring) for (k, v), kk in zip(shared_p.items(), ks)}
    return SecureBlockParams(n_heads=n_heads, head_dim=hd, **sh), p


def secure_block(x: RSS, bp: SecureBlockParams, parties: Parties,
                 customized: bool = True, static_norm: bool = False,
                 tag: str = "blk") -> RSS:
    """One decoder block under RSS. x: (S, d) one sequence (simulation scale).

    customized=True  -> ReLU-attention (paper's recipe; distillation recovers
                        accuracy — see distill/).
    customized=False -> full secure softmax (max/exp/reciprocal substrate).
    static_norm=True -> CBNN-style norm customization: RMSNorm is replaced at
                        training time by a *static* per-channel scale (the
                        model owner folds g·ĉ into the next linear's weights,
                        so the online cost is ZERO rounds); accuracy is
                        recovered by distillation, exactly the paper's recipe
                        for MPC-hostile ops.  §Perf iteration 3.
    """
    ring = x.ring
    s = int(x.shape[0])
    h, hd = bp.n_heads, bp.head_dim
    d = h * hd

    def lin(inp, w, t):
        if fused_rounds():  # beyond-paper: matmul+trunc in one round
            return matmul_truncate(inp, w, parties, tag=t)
        return truncate(matmul(inp, w, parties, tag=t), parties,
                        tag=t + ".tr")

    def norm(v, g, t):
        if static_norm:
            return v  # scale folded into the following linear at setup
        return secure_rmsnorm(v, g, parties, tag=t)

    hin = norm(x, bp.g1, tag + ".norm1")
    q = lin(hin, bp.wq, tag + ".wq")
    k = lin(hin, bp.wk, tag + ".wk")
    v = lin(hin, bp.wv, tag + ".wv")

    # per-head scores: (h, S, S); the 1/√hd scale is pre-folded into W_q
    qh = q.reshape(s, h, hd).transpose((1, 0, 2))   # (h, S, hd)
    kh = k.reshape(s, h, hd).transpose((1, 2, 0))   # (h, hd, S)
    scores = _bmm(qh, kh, parties, tag=tag + ".qk", fuse_trunc=True)

    # causal mask: public structure — parties zero the upper triangle locally
    mask = jnp.tril(jnp.ones((s, s), ring.dtype))
    if customized:
        probs = relu_attention_scores(scores, s, parties, tag=tag + ".reluattn")
        probs = RSS(probs.shares * mask[None, None], ring)
    else:
        neg = ring.encode(jnp.float32(-16.0))
        masked = RSS(scores.shares * mask[None, None], ring).add_public(
            jnp.where(mask == 0, neg, jnp.asarray(0, ring.dtype)).astype(ring.dtype))
        probs = secure_softmax(masked, parties, tag=tag + ".softmax")

    vh = v.reshape(s, h, hd).transpose((1, 0, 2))   # (h, S, hd)
    ctx = _bmm(probs, vh, parties, tag=tag + ".av", fuse_trunc=True)
    ctx = ctx.transpose((1, 0, 2)).reshape(s, d)
    attn_out = lin(ctx, bp.wo, tag + ".wo")
    x = x + attn_out

    hin2 = norm(x, bp.g2, tag + ".norm2")
    up = lin(hin2, bp.w_up, tag + ".up")
    act = secure_relu(up, parties, tag=tag + ".relu")
    down = lin(act, bp.w_down, tag + ".down")
    return x + down


def _bmm(a: RSS, b: RSS, parties: Parties, tag: str,
         fuse_trunc: bool = False) -> RSS:
    """Batched secure matmul over a leading head axis: (h,S,K)x(h,K,T);
    optionally with the one-round fused truncation."""
    from . import transport
    from .linear import _reshare, truncate as _trunc
    ring = a.ring
    t = transport.current()
    xs, ys = t.own_view(a.shares), t.own_view(b.shares)
    xn, yn = t.next_view(a.shares), t.next_view(b.shares)

    def dot(p, q):
        return jnp.einsum("hsk,hkt->hst", p, q,
                          preferred_element_type=ring.dtype)

    z = jnp.stack([dot(xs[i], ys[i] + yn[i]) + dot(xn[i], ys[i])
                   for i in range(xs.shape[0])])
    if not fuse_trunc:
        return _reshare(z, ring, parties, tag)
    if not fused_rounds():
        return _trunc(_reshare(z, ring, parties, tag), parties,
                      tag=tag + ".tr")
    # fused: broadcast masked additive parts, open, shift (1 round)
    z = z + parties.zero_shares(z.shape[1:], ring)
    r = parties.rand_rss(z.shape[1:], ring, max_bits=ring.bits - 1)
    rp = RSS(r.shares >> ring.frac, ring)
    offset = jnp.asarray(1 << (ring.bits - 2), ring.dtype)
    c_parts = z - t.own_view(r.shares)
    n = 1
    for dd in z.shape[1:]:
        n *= int(dd)
    comm.record(tag + ".fused", rounds=1, nbytes=6 * n * ring.nbytes)
    c = t.open_parts(c_parts) + offset
    c_shift = (ring.to_signed(c) >> ring.frac).astype(ring.dtype)
    public = c_shift - jnp.asarray(1 << (ring.bits - 2 - ring.frac),
                                   ring.dtype) + jnp.asarray(1, ring.dtype)
    return rp.add_public(public)


def plaintext_block(x, p, n_heads: int, customized: bool = True,
                    static_norm: bool = False):
    """fp32 oracle matching secure_block's computation graph."""
    s, d = x.shape
    hd = d // n_heads

    def rms(v, g):
        if static_norm:
            return v
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5) * g

    hin = rms(x, p["g1"])
    q = (hin @ p["wq"]).reshape(s, n_heads, hd).transpose(1, 0, 2)
    k = (hin @ p["wk"]).reshape(s, n_heads, hd).transpose(1, 0, 2)
    v = (hin @ p["wv"]).reshape(s, n_heads, hd).transpose(1, 0, 2)
    scores = q @ k.transpose(0, 2, 1) / math.sqrt(hd)
    mask = np.tril(np.ones((s, s)))
    if customized:
        probs = np.maximum(scores, 0) / s * mask[None]
    else:
        sm = np.where(mask[None] > 0, scores, -16.0)
        e = np.exp(sm - sm.max(-1, keepdims=True))
        probs = e / e.sum(-1, keepdims=True)
    ctx = (probs @ v).transpose(1, 0, 2).reshape(s, d)
    x = x + ctx @ p["wo"]
    hin2 = rms(x, p["g2"])
    ffn = np.maximum(hin2 @ p["w_up"], 0) @ p["w_down"]
    return x + ffn


# ---------------------------------------------------------------------------
# Autoregressive LM serving (DESIGN.md §16)
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SecureKVCache:
    """RSS-shared K/V cache for every block, laid out under the transport.

    ``k``/``v``: ``(slots, n_blocks, n_heads, bucket, head_dim)`` in the ring
    dtype.  ``slots`` follows the transport share layout: 3 additive slots
    for the local simulation; for the mesh the *global* array carries each
    party's replicated pair stacked — 6 rows ``[c0,c1, c1,c2, c2,c0]`` —
    which shards under ``P(party)`` back to exactly the ``(2, ...)`` pair
    each party holds.  Zero-initialised rows are exact ring zeros, so scores
    against unwritten positions are exactly 0 before masking.
    """

    k: jax.Array
    v: jax.Array

    def tree_flatten(self):
        return (self.k, self.v), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def bucket(self) -> int:
        return self.k.shape[3]


def init_kv_cache(n_blocks: int, n_heads: int, head_dim: int, bucket: int,
                  ring: RingSpec | None = None, slots: int = 3
                  ) -> SecureKVCache:
    """Fresh zero cache.  ``slots=3`` for LocalTransport; ``slots=6`` for the
    global pair layout circulated through ``make_secure_lm_mesh``."""
    ring = ring or default_ring()
    shape = (slots, n_blocks, n_heads, bucket, head_dim)
    return SecureKVCache(jnp.zeros(shape, ring.dtype),
                         jnp.zeros(shape, ring.dtype))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SecureLMParams:
    """A whole decoder LM under RSS: tied-free embedding, blocks, final norm,
    LM head.  All weight leaves are shares, so the object tree-flattens to
    exactly the arrays a mesh program must shard per party."""

    embed: RSS                              # (vocab, d)
    blocks: tuple                           # of SecureBlockParams
    gf: RSS                                 # (d,)
    w_out: RSS                              # (d, vocab)
    vocab: int = 0

    def tree_flatten(self):
        return (self.embed, self.blocks, self.gf, self.w_out), (self.vocab,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, vocab=aux[0])

    @property
    def n_heads(self) -> int:
        return self.blocks[0].n_heads

    @property
    def head_dim(self) -> int:
        return self.blocks[0].head_dim

    @property
    def n_blocks(self) -> int:
        return len(self.blocks)

    @property
    def d_model(self) -> int:
        return self.n_heads * self.head_dim


def share_lm_params(key, vocab: int, d: int, n_heads: int, d_ff: int,
                    n_blocks: int, ring: RingSpec | None = None):
    """Model-owner setup for the LM: deterministic plaintext weights (scaled
    so every intermediate stays inside the Newton/bound envelopes of the
    fixed-point substrate) plus their RSS sharing.  Returns
    ``(SecureLMParams, plain_dict)`` — the dict drives the fp32 oracle."""
    ring = ring or default_ring()
    rng = np.random.default_rng(7)
    blocks, plain_blocks = [], []
    keys = jax.random.split(key, n_blocks + 3)
    for i in range(n_blocks):
        p = {
            "wq": rng.normal(0, 1 / math.sqrt(d), (d, d)).astype(np.float32),
            "wk": rng.normal(0, 1 / math.sqrt(d), (d, d)).astype(np.float32),
            "wv": rng.normal(0, 1 / math.sqrt(d), (d, d)).astype(np.float32),
            "wo": rng.normal(0, 1 / math.sqrt(d), (d, d)).astype(np.float32),
            "w_up": rng.normal(0, 1 / math.sqrt(d),
                               (d, d_ff)).astype(np.float32),
            "w_down": rng.normal(0, 1 / math.sqrt(d_ff),
                                 (d_ff, d)).astype(np.float32),
            "g1": np.ones((d,), np.float32),
            "g2": np.ones((d,), np.float32),
        }
        bp, _ = share_block_params(keys[i], d, n_heads, d_ff, ring,
                                   numpy_params=p)
        blocks.append(bp)
        plain_blocks.append(p)
    embed = rng.normal(0, 0.5, (vocab, d)).astype(np.float32)
    gf = np.ones((d,), np.float32)
    w_out = rng.normal(0, 1 / math.sqrt(d), (d, vocab)).astype(np.float32)
    lm = SecureLMParams(
        embed=share(embed, keys[-3], ring),
        blocks=tuple(blocks),
        gf=share(gf, keys[-2], ring),
        w_out=share(w_out, keys[-1], ring),
        vocab=vocab)
    plain = {"embed": embed, "blocks": plain_blocks, "gf": gf,
             "w_out": w_out}
    return lm, plain


def _lin(inp: RSS, w: RSS, parties: Parties, t: str) -> RSS:
    if fused_rounds():
        return matmul_truncate(inp, w, parties, tag=t)
    return truncate(matmul(inp, w, parties, tag=t), parties, tag=t + ".tr")


def secure_decode_step(lm: SecureLMParams, cache: SecureKVCache, tok, pos,
                       keys, customized: bool = True,
                       static_norm: bool = False, tag: str = "lm"):
    """One token through every block; cache row ``pos`` written in place.

    ``tok``/``pos`` may be traced (the decode jit and the prefill scan share
    this body).  Per-position protocol randomness comes from
    ``fold_in(keys, pos)``: the traced program is position-independent, so
    the scanned prefill and the per-token decode loop consume identical PRF
    streams at every position — the basis of the prefill-vs-decode
    bit-identity pinned in tests.  The step reveals the logits (the
    functionality's public output); token selection is public.

    ``static_norm`` is :func:`secure_block`'s norm customization carried to
    the LM path: RMSNorm replaced at training time by a static per-channel
    scale the owner folds into the adjacent linear — zero online rounds and
    ~60% fewer protocol ops per step (the Newton-rsqrt ladders dominate the
    op count, which also dominates XLA-CPU compile time of the decode jit).
    """
    ring = lm.embed.ring
    fold = jax.vmap(jax.random.fold_in, in_axes=(0, None))
    parties = Parties(fold(keys, pos))
    h, hd = lm.n_heads, lm.head_dim
    d = h * hd
    bucket = cache.bucket
    pos = jnp.asarray(pos, jnp.int32)
    valid = (jnp.arange(bucket) <= pos)

    # token embedding: public index into the shared table — a local gather,
    # zero rounds, zero bytes
    x = RSS(jnp.take(lm.embed.shares, tok, axis=1)[:, None, :], ring)

    def norm(v, g, t):
        if static_norm:
            return v   # folded into the following linear at setup
        return secure_rmsnorm(v, g, parties, tag=t)

    ck, cv = cache.k, cache.v
    for i, bp in enumerate(lm.blocks):
        bt = f"{tag}.b{i}"
        hin = norm(x, bp.g1, bt + ".norm1")
        q = _lin(hin, bp.wq, parties, bt + ".wq")
        k = _lin(hin, bp.wk, parties, bt + ".wk")
        v = _lin(hin, bp.wv, parties, bt + ".wv")

        qh = q.reshape(1, h, hd).transpose((1, 0, 2))   # (h, 1, hd)
        kh = k.reshape(1, h, hd).transpose((1, 0, 2))
        vh = v.reshape(1, h, hd).transpose((1, 0, 2))

        # write row `pos` of this block's cache — pure share-local updates,
        # so the transport layout (3 additive slots / per-party pairs) is
        # preserved untouched
        ck = jax.lax.dynamic_update_slice(
            ck, kh.shares[:, None], (0, i, 0, pos, 0))
        cv = jax.lax.dynamic_update_slice(
            cv, vh.shares[:, None], (0, i, 0, pos, 0))
        K = RSS(ck[:, i], ring)                          # (h, bucket, hd)
        V = RSS(cv[:, i], ring)

        scores = _bmm(qh, K.transpose((0, 2, 1)), parties, tag=bt + ".qk",
                      fuse_trunc=True)                   # (h, 1, bucket)
        vmask = valid.astype(ring.dtype)
        if customized:
            probs = relu_attention_scores(scores, bucket, parties,
                                          tag=bt + ".reluattn")
            probs = RSS(probs.shares * vmask, ring)
        else:
            neg = ring.encode(jnp.float32(-16.0))
            masked = RSS(scores.shares * vmask, ring).add_public(
                jnp.where(valid, jnp.asarray(0, ring.dtype),
                          neg).astype(ring.dtype))
            probs = secure_softmax(masked, parties, tag=bt + ".softmax")

        ctx = _bmm(probs, V, parties, tag=bt + ".av", fuse_trunc=True)
        ctx = ctx.transpose((1, 0, 2)).reshape(1, d)
        x = x + _lin(ctx, bp.wo, parties, bt + ".wo")

        hin2 = norm(x, bp.g2, bt + ".norm2")
        up = _lin(hin2, bp.w_up, parties, bt + ".up")
        act = secure_relu(up, parties, tag=bt + ".relu")
        x = x + _lin(act, bp.w_down, parties, bt + ".down")

    xf = norm(x, lm.gf, tag + ".normf")
    logits = _lin(xf, lm.w_out, parties, tag + ".head")   # (1, vocab)
    out = reveal(logits, tag=tag + ".logits", decode=True)
    return out[0], SecureKVCache(ck, cv)


def scan_prefill(step, cache: SecureKVCache, tokens, keys):
    """Prefill by scanning a ``(cache, tok, pos, keys) -> (logits, cache)``
    step over the prompt — the launch/serve.py jitted-ingest pattern.  Works
    with the local step, a :class:`CompiledDecodeStep`'s traced body, or the
    shard_map'd mesh step.  Returns ``(logits (T, vocab), cache)``."""
    tokens = jnp.asarray(tokens, jnp.int32)

    def body(c, tp):
        t, p = tp
        lg, c2 = step(c, t, p, keys)
        return c2, lg

    cache, logits = jax.lax.scan(
        body, cache, (tokens, jnp.arange(tokens.shape[0], dtype=jnp.int32)))
    return logits, cache


def secure_prefill(lm: SecureLMParams, cache: SecureKVCache, tokens, keys,
                   customized: bool = True, static_norm: bool = False,
                   tag: str = "lm"):
    """Scanned secure prefill under the local transport: the scan body IS
    ``secure_decode_step``, so prefill-then-decode and a pure decode loop
    compute bit-identical logits and cache at every position."""

    def step(c, t, p, ks):
        return secure_decode_step(lm, c, t, p, ks, customized, static_norm,
                                  tag)

    return scan_prefill(step, cache, tokens, keys)


class CompiledDecodeStep:
    """One jitted decode step per padded bucket length, with a trace-time
    counter: serving keeps a dict keyed by bucket and asserts the program
    compiled exactly once per bucket (pinned in tests).

    A ``step_fn`` that carries a ``cache_sharding`` (the mesh step) gets
    every incoming cache placed there first, so a host-built initial cache
    and the step's own output enter the jit with one sharding and the
    program traces once."""

    def __init__(self, lm: SecureLMParams | None = None,
                 customized: bool = True, static_norm: bool = False,
                 tag: str = "lm", step_fn=None, bucket=None):
        self.traces = 0
        self.bucket = bucket   # padded bucket length (telemetry label)
        if step_fn is None:
            def step_fn(cache, tok, pos, keys):
                return secure_decode_step(lm, cache, tok, pos, keys,
                                          customized, static_norm, tag)

        def counted(cache, tok, pos, keys):
            self.traces += 1  # trace-time: counts compilations, not calls
            return step_fn(cache, tok, pos, keys)

        # .raw is the uncounted body — safe to embed in other programs
        # (the prefill scan) without charging this step's trace budget
        self.raw = step_fn
        self._jit = jax.jit(counted)
        self.cache_sharding = getattr(step_fn, "cache_sharding", None)

    def __call__(self, cache, tok, pos, keys):
        from . import telemetry
        if self.cache_sharding is not None:
            cache = jax.device_put(cache, self.cache_sharding)
        if not telemetry.enabled():   # disabled mode: no clock, no span
            return self._jit(cache, tok, pos, keys)
        # the traces counter distinguishes the compile call from steady-
        # state decode, so compile cost lands in its own span category
        before = self.traces
        b = self.bucket if self.bucket is not None else "?"
        with telemetry.span(f"decode_step[b{b}]", cat="online",
                            lane="parties") as s:
            out = self._jit(cache, tok, pos, keys)
        if self.traces > before and s is not None:
            s.name, s.cat = f"decode_compile[b{b}]", "compile"
        return out


def make_secure_lm_mesh(lm: SecureLMParams, mesh, customized: bool = True,
                        static_norm: bool = False,
                        party_axis: str = "party"):
    """Real per-party decode step over a size-3 mesh axis.

    The weight leaves enter pre-paired exactly like
    ``secure_model.make_secure_infer_mesh``; the cache circulates in the
    global pair layout ``(6, ...)`` (``out_specs=P(party)`` stacks each
    party's ``(2, ...)`` result, and the next call's ``in_specs=P(party)``
    splits the same rows back), so no re-pairing is needed between steps.
    Returns ``step(cache, tok, pos, keys) -> (logits, cache)``; its
    ``cache_sharding`` is the sharding of the cache it returns, where
    :class:`CompiledDecodeStep` places every incoming cache.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    assert mesh.shape[party_axis] == 3, mesh
    leaves, treedef = jax.tree_util.tree_flatten(lm)
    w_spec = P(party_axis)

    def inner(keys, tok, pos, own, nxt, ck, cv):
        t = transport.MeshTransport(party_axis)
        with transport.use_transport(t):
            lm_local = jax.tree_util.tree_unflatten(
                treedef, [t.ingest(o, n) for o, n in zip(own, nxt)])
            cache = SecureKVCache(ck, cv)
            logits, c2 = secure_decode_step(lm_local, cache, tok, pos, keys,
                                            customized, static_norm)
            return logits[None], c2.k, c2.v

    sm = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(), P(), P(), (w_spec,) * len(leaves),
                  (w_spec,) * len(leaves), w_spec, w_spec),
        out_specs=(w_spec, w_spec, w_spec),
        check_vma=False)

    def roll(a):
        return jnp.roll(a, -1, axis=0)

    own = tuple(leaves)
    nxt = tuple(roll(a) for a in leaves)

    def step(cache, tok, pos, keys):
        lg, ck, cv = sm(keys, jnp.asarray(tok, jnp.int32),
                        jnp.asarray(pos, jnp.int32), own, nxt,
                        cache.k, cache.v)
        return lg[0], SecureKVCache(ck, cv)

    step.cache_sharding = NamedSharding(mesh, w_spec)
    return step


def plaintext_lm_forward(plain: dict, tokens, n_heads: int,
                         customized: bool = True, bucket: int | None = None,
                         static_norm: bool = False):
    """fp32 LM oracle matching the secure decode's bucket-padded graph:
    K/V padded with zeros to ``bucket``, causal validity mask, ReLU-attention
    normalised by the static bucket length (or −16-masked softmax).  Returns
    logits ``(T, vocab)``."""
    tokens = np.asarray(tokens)
    emb = plain["embed"][tokens]                      # (T, d)
    T, d = emb.shape
    S = bucket or T
    hd = d // n_heads

    def rms(v, g):
        if static_norm:
            return v
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5) * g

    valid = np.arange(S)[None, :] <= np.arange(T)[:, None]   # (T, S)
    x = emb
    for p in plain["blocks"]:
        hin = rms(x, p["g1"])
        q = (hin @ p["wq"]).reshape(T, n_heads, hd).transpose(1, 0, 2)
        k = (hin @ p["wk"]).reshape(T, n_heads, hd).transpose(1, 0, 2)
        v = (hin @ p["wv"]).reshape(T, n_heads, hd).transpose(1, 0, 2)
        kp = np.zeros((n_heads, S, hd), np.float32)
        vp = np.zeros((n_heads, S, hd), np.float32)
        kp[:, :T], vp[:, :T] = k, v
        scores = q @ kp.transpose(0, 2, 1) / math.sqrt(hd)    # (h, T, S)
        if customized:
            probs = np.maximum(scores, 0) / S * valid[None]
        else:
            sm = np.where(valid[None], scores, -16.0)
            e = np.exp(sm - sm.max(-1, keepdims=True))
            probs = e / e.sum(-1, keepdims=True)
        ctx = (probs @ vp).transpose(1, 0, 2).reshape(T, d)
        x = x + ctx @ p["wo"]
        hin2 = rms(x, p["g2"])
        x = x + np.maximum(hin2 @ p["w_up"], 0) @ p["w_down"]
    return rms(x, plain["gf"]) @ plain["w_out"]


def block_comm_profile(seq: int = 16, d: int = 64, heads: int = 4,
                       d_ff: int = 128):
    """§Perf measurement helper: (variant -> ledger) across the protocol
    optimization ladder."""
    import jax as _jax
    from .comm import estimate_cost
    from .linear import set_fused_rounds, set_matmul_mode

    bp, _ = share_block_params(_jax.random.PRNGKey(0), d, heads, d_ff)
    x = np.zeros((seq, d), np.float32)
    xs = share(x, _jax.random.PRNGKey(1))
    out = {}
    variants = [
        ("paper_softmax", dict(customized=False), False, "paper3"),
        ("paper_softmax_opt2", dict(customized=False), False, "opt2"),
        ("customized", dict(customized=True), False, "opt2"),
        ("customized_fused", dict(customized=True), True, "opt2"),
        ("customized_fused_staticnorm",
         dict(customized=True, static_norm=True), True, "opt2"),
    ]
    for name, kw, fused, mode in variants:
        set_fused_rounds(fused)
        set_matmul_mode(mode)
        try:
            out[name] = estimate_cost(
                lambda s_: secure_block(
                    s_, bp, Parties.setup(_jax.random.PRNGKey(9)), **kw), xs)
        finally:
            set_fused_rounds(False)
            set_matmul_mode("opt2")
    return out
