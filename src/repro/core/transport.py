"""Party transport layer: every inter-party data movement in one place.

The protocol modules (linear / msb / activation / pooling / softmax / norm /
secure_model) never touch the party axis directly any more — they ask the
active :class:`Transport` for the handful of primitives a 3-party RSS
deployment actually has:

  * ``next_view``    — the neighbour share x_{i+1} a party holds by the RSS
                       replication invariant (P_i holds the pair (x_i, x_{i+1})),
  * ``complete``     — the reshare move: additive parts z_i become a full RSS
                       pair (P_i sends z_i to P_{i-1}),
  * ``open_parts`` / ``open_rss`` — openings (broadcast additive parts /
                       reveal a shared value),
  * ``send``         — a point-to-point message between two named parties,
  * ``slot_view``    — read an absolute share slot (valid only on the two
                       parties that hold it),
  * ``prf_*``        — PRF-correlated randomness laid out per party.

Two backends implement the interface:

``LocalTransport`` (default)
    The original single-program simulation: shares stacked on a leading axis
    of size 3, neighbour access is ``jnp.roll``, opens are stack sums.
    Bit-identical to the pre-transport code; communication is *accounted*
    (core/comm.py), never performed.

``MeshTransport``
    A real per-party program: the code runs inside ``shard_map`` over a
    size-3 ``"party"`` mesh axis, each device holding one party's slice.
    Share stacks are carried as the replicated *pair* (local leading axis 2:
    ``[x_i, x_{i+1}]``), so neighbour access is local — exactly the RSS
    holding set.  ``complete`` is a ``jax.lax.ppermute`` (the reshare
    message), opens are ``all_gather`` + local sum, ``send`` is a
    single-pair ppermute.  Every ledger entry recorded by the protocols now
    corresponds to a real collective in the compiled per-party HLO, and the
    bytes agree (tests/test_transport_mesh.py cross-checks them via
    roofline.analyze).

Layouts (leading axis = party):

  =============  ===============  =====================================
  layout         LocalTransport   MeshTransport (per-device)
  =============  ===============  =====================================
  RSS stack      (3, *s) x_i      (2, *s)  [x_i, x_{i+1}]
  additive parts (3, *s) z_i      (1, *s)  [z_i]
  plain value    (*s) global      (*s) valid on the parties that know it
  =============  ===============  =====================================

The ``prf_*`` primitives lay PRF-correlated randomness out per party for
the *inline* drawing mode.  The offline preprocessing plant
(core/preprocessing.py, DESIGN.md §12) precomputes the same material into
MaterialTape slabs that mirror these layouts slab-for-slab — RSS-layout
slabs enter a mesh program pre-paired via :meth:`ingest` exactly like
model shares, parts-layout slabs shard to their own row — so a
tape-backed online program touches the transport only through its data
movement primitives and compiles with zero PRF work.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from . import integrity
from . import telemetry

__all__ = ["Transport", "LocalTransport", "MeshTransport", "current",
           "use_transport", "PARTIES"]

PARTIES = 3


class LocalTransport:
    """Stacked-axis single-program simulation (the historical semantics)."""

    name = "local"
    # shares are globally stacked: the neighbour slot is a roll, not a
    # carried pair (MeshTransport sets True — callers that can exploit a
    # pre-carried pair key on this, not on concrete types)
    carries_pair = False

    # -- layout ----------------------------------------------------------
    @property
    def rss_slots(self) -> int:
        return PARTIES

    @property
    def parts_slots(self) -> int:
        return PARTIES

    def ingest(self, own, nxt):
        """Form an RSS stack from pre-paired global inputs (nxt unused:
        the local stack already carries every party's share)."""
        return own

    # -- views -----------------------------------------------------------
    def own_view(self, stack):
        """RSS stack -> additive alignment of each party's first share."""
        return stack

    def next_view(self, stack):
        """x_{i+1} aligned with x_i — the second half of P_i's pair."""
        return jnp.roll(stack, -1, axis=0)

    def slot_view(self, stack, i: int):
        """Absolute share slot i (plain).  Globally visible in simulation;
        under the mesh it is valid only on the two parties holding it."""
        return stack[i]

    # -- movement --------------------------------------------------------
    def complete(self, parts):
        """Additive parts -> RSS stack.  The reshare data movement: P_i
        sends z_i to P_{i-1}.  The stacked sim already holds every slot."""
        telemetry.movement("complete", self.name)
        v = integrity.active()
        if v is not None:
            own = [integrity.fold_digest(parts[i]) for i in range(PARTIES)]
            v.observe_pair(jnp.stack(own),
                           jnp.stack([own[(i + 1) % PARTIES]
                                      for i in range(PARTIES)]))
        return parts

    def send(self, x, frm: int, to: int):
        """Point-to-point message; globally visible in simulation."""
        telemetry.movement("send", self.name)
        v = integrity.active()
        if v is not None:
            row = jnp.stack([integrity.fold_digest(x)] * PARTIES)
            v.observe_send(row, row, frm, to)
        return x

    def merge_recv(self, primary, received, holder: int):
        """Combine a sender-side value with its received copy (they are the
        same array in simulation)."""
        return primary

    # -- openings --------------------------------------------------------
    def open_parts(self, parts):
        """All parties learn sum of additive parts (each P_i broadcasts)."""
        telemetry.movement("open_parts", self.name)
        o = parts[0] + parts[1] + parts[2]
        v = integrity.active()
        if v is not None:
            v.observe_open(jnp.stack([integrity.fold_digest(o)] * PARTIES))
        return o

    def open_rss(self, stack):
        """Reveal a shared value: P_i sends x_i to P_{i-1} (each party is
        missing exactly one share thanks to the pair invariant)."""
        telemetry.movement("open_rss", self.name)
        o = stack[0] + stack[1] + stack[2]
        v = integrity.active()
        if v is not None:
            v.observe_open(jnp.stack([integrity.fold_digest(o)] * PARTIES))
        return o

    # -- party-indexed construction --------------------------------------
    def build_rss(self, vals: Sequence):
        """RSS stack from per-slot plain values (vals[i] must be valid on
        both holders of slot i)."""
        return jnp.stack(list(vals))

    def build_parts(self, vals: Sequence):
        """Additive-parts stack from per-slot plain values (vals[i] valid
        on P_i)."""
        return jnp.stack(list(vals))

    def party_mask_rss(self, i: int, ndim: int, dtype):
        """{0,1} mask selecting share slot i of an RSS stack."""
        m = jnp.zeros((PARTIES,) + (1,) * ndim, dtype)
        return m.at[i].set(jnp.asarray(1, dtype))

    def party_mask_parts(self, i: int, ndim: int, dtype):
        m = jnp.zeros((PARTIES,) + (1,) * ndim, dtype)
        return m.at[i].set(jnp.asarray(1, dtype))

    # -- PRF layout ------------------------------------------------------
    def prf_rss(self, keys, draw: Callable):
        """RSS stack of PRF draws: slot i = draw(keys[i]) (2-of-3: P_i can
        derive both halves of its pair from the keys it holds)."""
        return jnp.stack([draw(keys[i]) for i in range(PARTIES)])

    def prf_parts_pair(self, keys, draw: Callable):
        """(F(k_i), F(k_{i+1})) in additive alignment — both PRF-local."""
        f = jnp.stack([draw(keys[i]) for i in range(PARTIES)])
        return f, jnp.roll(f, -1, axis=0)


class MeshTransport:
    """Per-party program over a size-3 mesh axis (inside shard_map).

    Only valid while tracing inside a ``shard_map`` whose mesh carries the
    ``axis`` axis with size 3.  All cross-party movement is explicit:
    ``ppermute`` for reshares/sends, ``all_gather`` for openings — the
    compiled per-party HLO contains exactly the collectives the CommLedger
    records (see DESIGN.md §2).
    """

    name = "mesh"
    carries_pair = True

    def __init__(self, axis: str = "party"):
        self.axis = axis

    # -- helpers ---------------------------------------------------------
    def _pid(self):
        return jax.lax.axis_index(self.axis)

    def _by_pid(self, vals: Sequence):
        pid = self._pid()
        out = vals[PARTIES - 1]
        for i in range(PARTIES - 2, -1, -1):
            out = jnp.where(pid == i, vals[i], out)
        return out

    def _recv_from_next(self, x):
        """result on party i = x from party i+1 (P_{i+1} sends to P_i)."""
        perm = [((i + 1) % PARTIES, i) for i in range(PARTIES)]
        return jax.lax.ppermute(x, self.axis, perm)

    # -- layout ----------------------------------------------------------
    @property
    def rss_slots(self) -> int:
        return 2

    @property
    def parts_slots(self) -> int:
        return 1

    def ingest(self, own, nxt):
        return jnp.concatenate([own, nxt], axis=0)

    # -- views -----------------------------------------------------------
    def own_view(self, stack):
        return stack[0:1]

    def next_view(self, stack):
        return stack[1:2]

    def slot_view(self, stack, i: int):
        # valid where pid == i (own) or pid == i-1 (the neighbour copy)
        return jnp.where(self._pid() == i, stack[0], stack[1])

    # -- movement --------------------------------------------------------
    def complete(self, parts):
        telemetry.movement("complete", self.name)
        recv = self._recv_from_next(parts)
        v = integrity.active()
        if v is not None:
            v.observe_pair(integrity.fold_digest(parts[0]),
                           integrity.fold_digest(recv[0]))
        return jnp.concatenate([parts, recv], axis=0)

    def send(self, x, frm: int, to: int):
        telemetry.movement("send", self.name)
        r = jax.lax.ppermute(x, self.axis, [(frm, to)])
        v = integrity.active()
        if v is not None:
            v.observe_send(integrity.fold_digest(x),
                           integrity.fold_digest(r), frm, to)
        return r

    def merge_recv(self, primary, received, holder: int):
        return jnp.where(self._pid() == holder, received, primary)

    # -- openings --------------------------------------------------------
    def open_parts(self, parts):
        telemetry.movement("open_parts", self.name)
        g = jax.lax.all_gather(parts[0], self.axis, axis=0)
        o = g[0] + g[1] + g[2]
        v = integrity.active()
        if v is not None:
            v.observe_open(integrity.fold_digest(o))
        return o

    def open_rss(self, stack):
        # P_i holds (x_i, x_{i+1}); the missing x_{i+2} is the neighbour's
        # second component — one ppermute, exactly the ledger's 3 messages.
        telemetry.movement("open_rss", self.name)
        third = self._recv_from_next(stack[1])
        o = stack[0] + stack[1] + third
        v = integrity.active()
        if v is not None:
            v.observe_open(integrity.fold_digest(o))
        return o

    # -- party-indexed construction --------------------------------------
    def build_rss(self, vals: Sequence):
        own = self._by_pid(vals)
        nxt = self._by_pid([vals[(i + 1) % PARTIES] for i in range(PARTIES)])
        return jnp.stack([own, nxt])

    def build_parts(self, vals: Sequence):
        return self._by_pid(vals)[None]

    def party_mask_rss(self, i: int, ndim: int, dtype):
        pid = self._pid()
        own = (pid == i)
        nxt = (pid == (i - 1) % PARTIES)
        return jnp.stack([own, nxt]).astype(dtype).reshape((2,) + (1,) * ndim)

    def party_mask_parts(self, i: int, ndim: int, dtype):
        return (self._pid() == i).astype(dtype).reshape((1,) + (1,) * ndim)

    # -- PRF layout ------------------------------------------------------
    def _key(self, keys, idx):
        return jnp.take(keys, idx % PARTIES, axis=0)

    def prf_rss(self, keys, draw: Callable):
        pid = self._pid()
        return jnp.stack([draw(self._key(keys, pid)),
                          draw(self._key(keys, pid + 1))])

    def prf_parts_pair(self, keys, draw: Callable):
        pid = self._pid()
        return (draw(self._key(keys, pid))[None],
                draw(self._key(keys, pid + 1))[None])


Transport = LocalTransport | MeshTransport

_STACK: list = []
_DEFAULT = LocalTransport()


def current() -> Transport:
    """The active transport (LocalTransport unless overridden)."""
    return _STACK[-1] if _STACK else _DEFAULT


@contextlib.contextmanager
def use_transport(t: Transport):
    """Route all protocol party traffic through ``t`` inside the context."""
    _STACK.append(t)
    try:
        yield t
    finally:
        _STACK.pop()
