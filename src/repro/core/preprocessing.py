"""Offline preprocessing plant (DESIGN.md §12): traced material specs,
consumable tapes, and an online-only serving phase.

CBNN's protocols run on input-independent correlated randomness — PRF zero
shares (`Parties.zero_shares`), bounded truncation pads (`rand_rss`),
random Sign bits plus their B2A conversion and the ρ mult
(`Parties.msb_material`), and OT masks (`Parties.ot_masks`).  The inline
runtime draws all of it *during* the online query; this module moves that
work ahead of traffic, the offline/online split PraxiMLP and FOBNN-style
3PC systems win their online latency with:

  1. :func:`trace_material` traces a ``compile_secure``'d model ONCE with a
     recording :class:`Parties` and extracts the per-query
     :class:`MaterialSpec` — the ordered list of (kind, counter, shape,
     ring, aux) of every correlated draw the protocol stack consumes.
     Draw order is deterministic because the trace-time freshness counter
     is (``Parties.fresh``) pinned to the same base on every trace.

  2. :func:`make_tape_generator` / :func:`generate_tape` produce a
     :class:`MaterialTape` for N queries in ONE jitted launch: per-kind
     slabs stacked as ``(3, N, n_slots, *shape)`` (party-stacked layouts)
     or ``(N, n_slots, *shape)`` (key-replicated values).  Generation runs
     the *same inline PRF/protocol code* the online path would have run
     (seeking the counter to each item's traced value), so tape playback
     is bit-identical to inline draws by construction.

  3. :class:`TapeParties` is the consumable: a drop-in ``Parties`` whose
     draw methods pop the next tape slice instead of computing PRFs.  The
     compiled online HLO then contains ZERO PRF work and zero offline
     sub-protocols — its party collectives are exactly the CommLedger's
     *online* rows (cross-checked by ``roofline.analyze.ledger_vs_wire``
     plus ``prf_ops_in_hlo``; pinned in tests).

Slab layouts mirror the transport layouts (core/transport.py): under
``LocalTransport`` a party-stacked slab is consumed whole; under
``MeshTransport`` the leading party axis is sharded so each device holds
its own row, and pair-layout kinds enter pre-paired (own + rolled copies,
``transport.ingest`` — the same dealer convention as model shares).
Key-replicated kinds (pairwise/private masks) are valid on the parties
that hold the deriving keys; the sim keeps them globally visible exactly
like the inline PRF draws they replace.
"""
from __future__ import annotations

import dataclasses
import time
import warnings

import jax
import jax.numpy as jnp

from . import comm, telemetry, transport
from .integrity import (MaterialDesyncError, PoolExhaustedError,
                        verify_tape_slice)
from .randomness import Parties
from .ring import RingSpec
from .rss import RSS, BinRSS, PARTIES

__all__ = ["MaterialItem", "MaterialSpec", "MaterialTape", "TapeParties",
           "TapePool", "trace_material", "make_tape_generator",
           "make_tape_slicer", "generate_tape", "tape_session_keys",
           "online_cost", "STACK_PAIR", "STACK_PARTS", "REPLICATED"]

# slab layout classes (how a party-sliced consumer reads the slab)
STACK_PAIR = "stack_pair"    # party-stacked; P_i consumes rows (i, i+1)
STACK_PARTS = "stack_parts"  # party-stacked; P_i consumes row i only
REPLICATED = "repl"          # derived from shared keys; held replicated

# kind -> list of (field suffix, layout, dtype kind) — "ring" resolves to
# the item's ring dtype, "bits" to uint8
_KIND_FIELDS = {
    "zero": (("", STACK_PARTS, "ring"),),
    "rss": (("", STACK_PAIR, "ring"),),
    "bits": (("", STACK_PAIR, "bits"),),
    "pair": (("", REPLICATED, "ring"),),
    "private": (("", REPLICATED, "ring"),),
    "ot_masks": (("", REPLICATED, "ring"),),   # leading axis 2: (m0, m1)
    "msb": ((".beta", STACK_PAIR, "bits"),
            (".beta_a", STACK_PAIR, "ring"),
            (".rho", STACK_PAIR, "ring")),
}


@dataclasses.dataclass(frozen=True)
class MaterialItem:
    """One correlated draw of the traced program, in consumption order."""

    kind: str          # key into _KIND_FIELDS
    cnt: int           # Parties counter value BEFORE the draw (seekable)
    shape: tuple       # tensor shape of the draw
    ring: RingSpec | None
    aux: tuple = ()    # (max_bits,) | (a, b) | (i,) | (kidx,) | (r_bits,)

    @property
    def group(self):
        return (self.kind, self.shape, self.ring, self.aux)


@dataclasses.dataclass(frozen=True)
class SlabInfo:
    layout: str        # STACK_PAIR | STACK_PARTS | REPLICATED
    shape: tuple       # per-query slab shape (party axis leading if stacked)
    dtype: object


class MaterialSpec:
    """Ordered draw list + its grouping into stacked per-kind slabs.

    ``items[i]`` is consumed i-th; ``index[i] = (slab base key, slot)``
    locates it inside the tape.  ``slabs`` maps every full slab key (base +
    field suffix) to its :class:`SlabInfo`.
    """

    def __init__(self, items: list[MaterialItem]):
        self.items = list(items)
        groups: dict = {}          # group -> (base key, next slot)
        self.index: list[tuple[str, int]] = []
        counts: dict[str, int] = {}
        base_of: dict = {}
        for it in self.items:
            g = it.group
            if g not in base_of:
                base_of[g] = f"g{len(base_of):02d}.{it.kind}"
                counts[base_of[g]] = 0
            base = base_of[g]
            self.index.append((base, counts[base]))
            counts[base] += 1
        self.slabs: dict[str, SlabInfo] = {}
        for g, base in base_of.items():
            kind, shape, ring, aux = g
            n = counts[base]
            for suffix, layout, dt in _KIND_FIELDS[kind]:
                dtype = jnp.uint8 if dt == "bits" else ring.dtype
                inner = (2,) + shape if kind == "ot_masks" else shape
                if layout == REPLICATED:
                    sshape = (n,) + inner
                else:
                    sshape = (PARTIES, n) + inner
                self.slabs[base + suffix] = SlabInfo(layout, sshape, dtype)

        self._gen = None   # cached jitted offline plant (make_tape_generator)
        self._slice = None  # cached jitted slot slicer (make_tape_slicer)

    def __len__(self):
        return len(self.items)

    def slab_structs(self) -> dict:
        """Per-query abstract slabs (for tracing the online program)."""
        return {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in self.slabs.items()}

    def summary(self) -> str:
        import math
        from collections import Counter
        kinds = Counter(it.kind for it in self.items)
        els = sum(math.prod(v.shape) for v in self.slabs.values())
        return (f"{len(self.items)} draws ({dict(kinds)}), "
                f"{len(self.slabs)} slabs, {els:,} ring elements/query")


# ---------------------------------------------------------------------------
# Spec extraction: trace once with a recording Parties
# ---------------------------------------------------------------------------

class _SpecParties(Parties):
    """Inline Parties that records every draw (kind, cnt, shape, aux)."""

    def __init__(self, keys):
        super().__init__(keys)
        self.items: list[MaterialItem] = []
        self._suspend = False   # True inside a composite (msb_material)

    def fresh(self):
        self._cnt = self._base
        return self

    def _rec(self, kind, shape, ring, aux=()):
        if not self._suspend:
            self.items.append(MaterialItem(
                kind, self._cnt, tuple(int(d) for d in shape), ring, aux))

    def zero_shares(self, shape, ring=None):
        from .ring import default_ring
        ring = ring or default_ring()
        self._rec("zero", shape, ring)
        return super().zero_shares(shape, ring)

    def rand_rss(self, shape, ring=None, max_bits=None):
        from .ring import default_ring
        ring = ring or default_ring()
        self._rec("rss", shape, ring, (max_bits,))
        return super().rand_rss(shape, ring, max_bits)

    def rand_bits(self, shape):
        from .ring import default_ring
        self._rec("bits", shape, default_ring())
        return super().rand_bits(shape)

    def common_pair(self, a, b, shape, ring=None):
        from .ring import default_ring
        ring = ring or default_ring()
        self._rec("pair", shape, ring, (a, b))
        return super().common_pair(a, b, shape, ring)

    def private_to(self, i, shape, ring=None):
        from .ring import default_ring
        ring = ring or default_ring()
        self._rec("private", shape, ring, (i,))
        return super().private_to(i, shape, ring)

    def ot_masks(self, kidx, shape, ring=None):
        from .ring import default_ring
        ring = ring or default_ring()
        self._rec("ot_masks", shape, ring, (kidx,))
        return super().ot_masks(kidx, shape, ring)

    def msb_material(self, shape, ring, r_bits, tag="msb"):
        self._rec("msb", shape, ring, (r_bits,))
        self._suspend = True
        try:
            return super().msb_material(shape, ring, r_bits, tag)
        finally:
            self._suspend = False

    def rand_rss_open(self, shape, ring=None):
        raise NotImplementedError(
            "rand_rss_open (truncate_probabilistic baseline) is inline-only "
            "— the tape mode covers the serving protocol stack")


def trace_material(model, input_shape) -> MaterialSpec:
    """Trace one secure inference of ``model`` (batch included in
    ``input_shape``) abstractly and return its per-query MaterialSpec.
    Pure ``jax.eval_shape`` under ``LocalTransport`` — nothing executes."""
    from .secure_model import secure_infer
    rec = _SpecParties(jax.random.split(jax.random.PRNGKey(0), PARTIES))
    x = jax.ShapeDtypeStruct((PARTIES,) + tuple(input_shape),
                             model.ring.dtype)

    def run(xs):
        return secure_infer(model, RSS(xs, model.ring), rec)

    with transport.use_transport(transport.LocalTransport()):
        jax.eval_shape(run, x)
    return MaterialSpec(rec.items)


# ---------------------------------------------------------------------------
# Offline generation: the jitted material plant
# ---------------------------------------------------------------------------

def _draw_inline(p: Parties, item: MaterialItem) -> dict:
    """Run the inline draw of one item (counter already seeked), returning
    {field suffix -> raw slab row}.  Exactly the code the online path would
    have run, so tape == inline bit for bit."""
    if item.kind == "zero":
        return {"": p.zero_shares(item.shape, item.ring)}
    if item.kind == "rss":
        return {"": p.rand_rss(item.shape, item.ring,
                               max_bits=item.aux[0]).shares}
    if item.kind == "bits":
        return {"": p.rand_bits(item.shape).shares}
    if item.kind == "pair":
        return {"": p.common_pair(item.aux[0], item.aux[1], item.shape,
                                  item.ring)}
    if item.kind == "private":
        return {"": p.private_to(item.aux[0], item.shape, item.ring)}
    if item.kind == "ot_masks":
        m0, m1 = p.ot_masks(item.aux[0], item.shape, item.ring)
        return {"": jnp.stack([m0, m1])}
    if item.kind == "msb":
        beta, beta_a, rho = p.msb_material(item.shape, item.ring,
                                           item.aux[0], tag="tape")
        return {".beta": beta.shares, ".beta_a": beta_a.shares,
                ".rho": rho.shares}
    raise ValueError(f"unknown material kind {item.kind!r}")


def make_tape_generator(spec: MaterialSpec):
    """Jitted offline plant: ``gen(keys_stack) -> slabs`` for
    ``keys_stack`` of shape (N, 3) party keys — N queries' material in one
    launch (vmapped over queries; the whole offline phase is one XLA
    program).  Generation always runs the stacked LocalTransport layout;
    mesh consumers shard the leading party axis (see
    ``secure_model.make_secure_infer_mesh``).  The jitted plant is cached
    on the spec, so repeated calls (each pool refill, `generate_tape`)
    dispatch the compiled program instead of retracing it."""
    if spec._gen is not None:
        return spec._gen

    def one(keys):
        p = Parties(keys)
        with transport.use_transport(transport.LocalTransport()):
            vals: dict[str, list] = {}
            for it, (base, _slot) in zip(spec.items, spec.index):
                p._cnt = it.cnt    # seek to the traced counter value
                for suffix, arr in _draw_inline(p, it).items():
                    vals.setdefault(base + suffix, []).append(arr)
            return {k: jnp.stack(v, axis=0) for k, v in vals.items()}

    def full(keys_stack):
        out = jax.vmap(one)(keys_stack)
        # stacked kinds: (N, n, 3, *s) -> (3, N, n, *s); repl: (N, n, *s)
        return {k: (jnp.moveaxis(v, 2, 0)
                    if spec.slabs[k].layout != REPLICATED else v)
                for k, v in out.items()}

    spec._gen = jax.jit(full)
    return spec._gen


def make_tape_slicer(spec: MaterialSpec):
    """``slice(slabs, q) -> slabs at query slot q``: every slab of a tape
    cut at a traced int32 slot in ONE jitted program (axis 1 of stacked
    layouts, axis 0 of replicated ones), so staging a query is one
    dispatch, and one executable serves every slot and buffer of a pool.
    Cached on the spec, like :func:`make_tape_generator`."""
    if spec._slice is not None:
        return spec._slice

    def cut(slabs, q):
        return {k: jax.lax.dynamic_index_in_dim(
                    v, q, axis=0 if spec.slabs[k].layout == REPLICATED
                    else 1, keepdims=False)
                for k, v in slabs.items()}

    spec._slice = jax.jit(cut)
    return spec._slice


def tape_session_keys(session_key, n_queries: int):
    """(N, 3) fresh per-query party-key stacks from one session key."""
    return jax.vmap(lambda k: jax.random.split(k, PARTIES))(
        jax.random.split(session_key, n_queries))


@dataclasses.dataclass
class MaterialTape:
    """N queries' worth of correlated randomness, ready to consume."""

    slabs: dict
    spec: MaterialSpec
    n_queries: int

    def query_slice(self, q: int) -> dict:
        """The per-query slab dict of slot ``q`` (device slicing, async:
        one program cuts every slab, :func:`make_tape_slicer`, and
        ``tape_slice_dispatches_total`` counts it)."""
        if not 0 <= q < self.n_queries:
            raise IndexError(f"tape slot {q} outside a tape of "
                             f"{self.n_queries} queries")
        with telemetry.span("tape_slice", cat="offline"):
            sl = make_tape_slicer(self.spec)(self.slabs, q)
        telemetry.inc("tape_slices_total")
        telemetry.inc("tape_slice_dispatches_total")
        return sl

    @property
    def nbytes(self) -> int:
        return sum(int(v.size) * v.dtype.itemsize
                   for v in self.slabs.values())


def generate_tape(spec: MaterialSpec, keys_stack) -> MaterialTape:
    """One-launch tape for ``keys_stack`` (N, 3) per-query party keys."""
    slabs = make_tape_generator(spec)(keys_stack)
    return MaterialTape(slabs, spec, int(keys_stack.shape[0]))


# ---------------------------------------------------------------------------
# The consumable: tape-backed Parties
# ---------------------------------------------------------------------------

class TapeParties(Parties):
    """Drop-in ``Parties`` that consumes one query's tape slice in spec
    order instead of computing PRFs — the online phase of the plant.

    ``slabs`` must already be in the *active transport's* layout: whole
    party stacks under ``LocalTransport``; per-device rows (pair-ingested
    for STACK_PAIR kinds) under ``MeshTransport``.  Every draw validates
    (kind, shape, aux) against the spec, so a program drift since
    ``trace_material`` fails loudly instead of consuming wrong material.
    """

    def __init__(self, keys, slabs: dict, spec: MaterialSpec):
        super().__init__(keys)
        self.slabs = slabs
        self.spec = spec
        self._pos = 0

    def fresh(self):
        self._pos = 0
        self._cnt = self._base
        return self

    def _take(self, kind, shape, aux, ring):
        if self._pos >= len(self.spec.items):
            raise MaterialDesyncError(
                f"material tape exhausted: online program drew more than "
                f"the {len(self.spec.items)} traced items (kind={kind})")
        it = self.spec.items[self._pos]
        base, slot = self.spec.index[self._pos]
        shape = tuple(int(d) for d in shape)
        if (it.kind, it.shape, it.aux, it.ring) != (kind, shape, aux, ring):
            raise MaterialDesyncError(
                f"material tape desync at draw {self._pos} (kind={it.kind!r} "
                f"cnt={it.cnt}): traced "
                f"{(it.kind, it.shape, it.aux, it.ring)}, online asked "
                f"{(kind, shape, aux, ring)} — retrace the MaterialSpec")
        self._validate_slabs(it, base)
        self._pos += 1
        return base, slot

    def _validate_slabs(self, it: MaterialItem, base: str):
        """Trace-time structural check of the slabs this draw will read:
        right dtype (the item's ring), right trailing tensor shape, and
        the party-axis layout the *active transport* consumes (whole
        stacks under LocalTransport, per-device rows under
        MeshTransport).  A tampered / truncated / re-ringed slab fails
        loudly here instead of silently corrupting the protocol."""
        t = transport.current()
        lead = {STACK_PAIR: t.rss_slots, STACK_PARTS: t.parts_slots,
                REPLICATED: 0}
        for suffix, layout, dt in _KIND_FIELDS[it.kind]:
            arr = self.slabs.get(base + suffix)
            dtype = jnp.uint8 if dt == "bits" else it.ring.dtype
            inner = (2,) + it.shape if it.kind == "ot_masks" else it.shape
            n_lead = lead[layout]
            # (slots?, n_slots, *inner): one slab axis per traced slot
            want_ndim = (1 if n_lead == 0 else 2) + len(inner)
            ok = (arr is not None and arr.dtype == dtype
                  and arr.ndim == want_ndim
                  and (not inner
                       or tuple(int(d) for d in arr.shape[-len(inner):])
                       == inner)
                  and (n_lead == 0 or int(arr.shape[0]) == n_lead))
            if not ok:
                got = (None if arr is None
                       else f"{tuple(arr.shape)} {arr.dtype}")
                raise MaterialDesyncError(
                    f"material tape desync at draw {self._pos}: slab "
                    f"{base + suffix!r} for kind={it.kind!r} cnt={it.cnt} "
                    f"is {got}, expected party lead {n_lead or 'none'} + "
                    f"tail {inner} {dtype} under the "
                    f"{type(t).__name__} layout")

    # -- draw points ------------------------------------------------------
    def zero_shares(self, shape, ring=None):
        from .ring import default_ring
        base, slot = self._take("zero", shape, (), ring or default_ring())
        return self.slabs[base][:, slot]

    def rand_rss(self, shape, ring=None, max_bits=None):
        from .ring import default_ring
        ring = ring or default_ring()
        base, slot = self._take("rss", shape, (max_bits,), ring)
        return RSS(self.slabs[base][:, slot], ring)

    def rand_bits(self, shape):
        from .ring import default_ring
        base, slot = self._take("bits", shape, (), default_ring())
        return BinRSS(self.slabs[base][:, slot])

    def common_pair(self, a, b, shape, ring=None):
        from .ring import default_ring
        base, slot = self._take("pair", shape, (a, b),
                                ring or default_ring())
        return self.slabs[base][slot]

    def private_to(self, i, shape, ring=None):
        from .ring import default_ring
        base, slot = self._take("private", shape, (i,),
                                ring or default_ring())
        return self.slabs[base][slot]

    def ot_masks(self, kidx, shape, ring=None):
        from .ring import default_ring
        base, slot = self._take("ot_masks", shape, (kidx,),
                                ring or default_ring())
        m = self.slabs[base][slot]
        return m[0], m[1]

    def msb_material(self, shape, ring, r_bits, tag="msb"):
        base, slot = self._take("msb", shape, (r_bits,), ring)
        return (BinRSS(self.slabs[base + ".beta"][:, slot]),
                RSS(self.slabs[base + ".beta_a"][:, slot], ring),
                RSS(self.slabs[base + ".rho"][:, slot], ring))

    def rand_rss_open(self, shape, ring=None):
        raise NotImplementedError(
            "rand_rss_open (truncate_probabilistic baseline) is inline-only")


# ---------------------------------------------------------------------------
# The pool: bounded, accounted, backpressured tape supply
# ---------------------------------------------------------------------------

# pause between readiness polls of a staged slice (seconds)
_POLL_PAUSE_S = 5e-5


def _poll_ready(slabs: dict):
    """Wait until ``slabs`` are on the device by polling with short
    pauses.  The online program is dispatched right after; a host thread
    that slept through the refill and the slice in one long
    ``block_until_ready`` dispatches and collects it measurably slower
    (PERF.md §6), and a tight poll slows it more."""
    for leaf in slabs.values():   # one program's outputs: ready together
        while not leaf.is_ready():
            time.sleep(_POLL_PAUSE_S)


class TapePool:
    """Double-buffered supply of per-query tape slices with explicit
    accounting (DESIGN.md §14).

    Refill dispatch runs ahead of consumption (JAX async dispatch
    overlaps the offline plant with online batches, like PR 4's
    ``serve_pool`` loop), but unlike the old loop every buffer is
    *demand-gated*: with ``demand`` total slices declared up front, the
    pool never generates a buffer no query will consume — a trailing
    partial buffer costs exactly the refills it needs (the old loop
    silently generated and discarded one full extra buffer whenever
    ``queries`` was not a multiple of the depth, polluting amortized
    throughput).

    Underrun is explicit instead of a desync: when consumption overtakes
    the prefetched supply the pool blocks on a synchronous refill and
    warns (backpressure — the online phase is waiting on offline work);
    when the budget (``demand`` or ``max_buffers``) is spent it raises
    :class:`~repro.core.integrity.PoolExhaustedError` rather than
    replaying consumed correlated randomness.

    ``verify=True`` structurally checks every slice against the traced
    spec before handing it out (:func:`integrity.verify_tape_slice` —
    host metadata only, the ``--verify full`` serving mode)."""

    def __init__(self, gen, spec: MaterialSpec, depth: int, master_key,
                 demand: int | None = None, max_buffers: int | None = None,
                 verify: bool = False, prefetch: bool = True):
        if depth < 1:
            raise ValueError(f"pool depth must be >= 1, got {depth}")
        self.gen = gen
        self.spec = spec
        self.depth = depth
        self.master_key = master_key
        self.demand = demand
        self.max_buffers = max_buffers
        self.verify = verify
        self.prefetch = prefetch   # dispatch the next buffer ahead of need
        self.taken = 0
        self.generated = 0   # buffers dispatched so far
        self.refills = 0     # buffers beyond the initial one
        self._bufs: list = []    # FIFO of [MaterialTape, next slot]
        self._warned_dry = False
        self._prefetch()
        if prefetch:
            self._prefetch()

    def _want_more(self) -> bool:
        if self.max_buffers is not None and self.generated >= self.max_buffers:
            return False
        if self.demand is not None \
                and self.generated * self.depth >= self.demand:
            return False
        return True

    def _prefetch(self):
        if not self._want_more():
            return
        with telemetry.span(f"tape_refill[{self.generated}]", cat="offline",
                            depth=self.depth):
            keys = tape_session_keys(
                jax.random.fold_in(self.master_key, self.generated),
                self.depth)
            self._bufs.append([MaterialTape(self.gen(keys), self.spec,
                                            self.depth), 0])
        self.generated += 1
        if self.generated > 1:
            self.refills += 1
            telemetry.inc("pool_refills_total")

    @property
    def supply(self) -> int:
        """Slices generated and not yet consumed."""
        return self.generated * self.depth - self.taken

    def take(self) -> dict:
        """The next per-query slab slice, on the device when it returns,
        dispatching the next refill as a buffer drains (all of it the
        ``tape_take`` span).  Warns on
        backpressure, raises :class:`PoolExhaustedError` when the budget
        is spent."""
        with telemetry.span("tape_take", cat="offline"):
            if self._bufs and self._bufs[0][1] >= self.depth:
                self._bufs.pop(0)       # drained: swap + prefetch the next
                if self.prefetch:
                    self._prefetch()
            if not self._bufs:
                if not self._want_more():
                    raise PoolExhaustedError(
                        f"material pool exhausted after {self.taken} "
                        f"slices: offline budget spent ({self.generated} "
                        f"buffers x "
                        f"depth {self.depth}"
                        + (f", demand {self.demand}" if self.demand else "")
                        + ") — raise --pool-depth or the buffer budget")
                # backpressure: budget remains but no buffer is ready — the
                # online phase blocks on a synchronous refill
                warnings.warn(
                    "tape pool underrun: online phase blocked on a "
                    "synchronous refill (offline plant is falling behind)",
                    RuntimeWarning, stacklevel=2)
                telemetry.inc("pool_backpressure_total")
                self._prefetch()
            if self.demand is not None and not self._warned_dry \
                    and self.demand - self.taken > self.supply \
                    and not self._want_more():
                self._warned_dry = True
                warnings.warn(
                    f"tape pool nearly exhausted: {self.supply} slices left "
                    f"for {self.demand - self.taken} demanded — later "
                    f"queries will abort with PoolExhaustedError",
                    RuntimeWarning, stacklevel=2)
            tape, slot = self._bufs[0]
            self._bufs[0][1] += 1
            self.taken += 1
            if telemetry.enabled():
                telemetry.gauge("pool_supply", self.supply)
            sl = tape.query_slice(slot)
            if self.verify:
                verify_tape_slice(self.spec, sl)
            _poll_ready(sl)
            return sl


# ---------------------------------------------------------------------------
# Online-phase helpers
# ---------------------------------------------------------------------------

def make_tape_infer(model, spec: MaterialSpec, reveal_output: bool = True):
    """The LocalTransport online runner:
    ``run(keys, x_stack, slabs) -> logits`` consuming one tape slice.
    Jit it once; its compiled HLO contains zero PRF work."""
    from .secure_model import secure_infer

    def run(keys, x_stack, slabs):
        tp = TapeParties(keys, slabs, spec)
        return secure_infer(model, RSS(x_stack, model.ring), tp,
                            reveal_output=reveal_output)

    return run


def online_cost(model, spec: MaterialSpec, input_shape) -> comm.CommLedger:
    """Trace-only ledger of the tape-backed ONLINE program.  Its rows are
    exactly the inline ledger's online (non-``pre:``) rows — the offline
    sub-protocols live on the tape (cross-checked in tests against
    ``secure_infer_cost`` and the compiled mesh HLO's wire bytes)."""
    run = make_tape_infer(model, spec)
    keys = jax.random.split(jax.random.PRNGKey(0), PARTIES)
    x = jax.ShapeDtypeStruct((PARTIES,) + tuple(input_shape),
                             model.ring.dtype)
    with comm.track() as led:
        jax.eval_shape(run, keys, x, spec.slab_structs())
    return led
