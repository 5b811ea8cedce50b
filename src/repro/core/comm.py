"""Communication accounting for the simulated 3-party deployment.

All three CBNN parties run inside one SPMD program, but every protocol records
the messages it *would* send (who -> whom, how many ring elements, how many
sequential rounds).  Costs depend only on traced shapes, so recording happens
at trace time; :func:`estimate_cost` runs ``jax.eval_shape`` under a tracker to
obtain the exact ledger without executing anything.

Wall-time is then modeled with the paper's network settings:
  LAN: 0.2 ms latency, 625 MBps   |   WAN: 80 ms latency, 40 MBps
"""
from __future__ import annotations

import contextlib
import dataclasses
from collections import defaultdict
from typing import Callable

import jax

__all__ = [
    "NetworkModel", "LAN", "WAN", "CommLedger", "track", "record",
    "estimate_cost", "round_barrier", "add_listener", "remove_listener",
    "listening", "scope",
]


@dataclasses.dataclass(frozen=True)
class NetworkModel:
    name: str
    latency_s: float
    bandwidth_Bps: float

    def time(self, rounds: int, nbytes: int) -> float:
        return rounds * self.latency_s + nbytes / self.bandwidth_Bps


# Paper §4: LAN 0.2ms / 625 MBps ; WAN 80ms / 40 MBps.
LAN = NetworkModel("LAN", 0.2e-3, 625e6)
WAN = NetworkModel("WAN", 80e-3, 40e6)


@dataclasses.dataclass
class CommLedger:
    """Accumulated protocol communication."""

    rounds: int = 0
    nbytes: int = 0
    by_tag: dict = dataclasses.field(default_factory=lambda: defaultdict(lambda: [0, 0]))
    # Offline/preprocessing phase (input independent) tracked separately.
    pre_rounds: int = 0
    pre_nbytes: int = 0

    def add(self, tag: str, rounds: int, nbytes: int, preprocess: bool = False):
        if preprocess:
            self.pre_rounds += rounds
            self.pre_nbytes += nbytes
            tag = "pre:" + tag
        else:
            self.rounds += rounds
            self.nbytes += nbytes
        ent = self.by_tag[tag]
        ent[0] += rounds
        ent[1] += nbytes

    # -- reporting ------------------------------------------------------
    def time(self, net: NetworkModel, online_only: bool = True) -> float:
        r, b = (self.rounds, self.nbytes)
        if not online_only:
            r, b = r + self.pre_rounds, b + self.pre_nbytes
        return net.time(r, b)

    @property
    def megabytes(self) -> float:
        return self.nbytes / 1e6

    def summary(self) -> str:
        """Per-tag breakdown, hottest online tags first: sorted by bytes
        descending with a percent-of-online-total column (offline
        ``pre:`` tags follow, sorted the same way against the offline
        total)."""
        lines = [f"total  rounds={self.rounds:4d}  bytes={self.nbytes:,} "
                 f"({self.megabytes:.4f} MB)  [pre: r={self.pre_rounds} "
                 f"b={self.pre_nbytes:,}]"]
        online = [(t, rb) for t, rb in self.by_tag.items()
                  if not t.startswith("pre:")]
        offline = [(t, rb) for t, rb in self.by_tag.items()
                   if t.startswith("pre:")]
        for group, total in ((online, self.nbytes), (offline, self.pre_nbytes)):
            for tag, (r, b) in sorted(group, key=lambda kv: (-kv[1][1], kv[0])):
                pct = 100.0 * b / total if total else 0.0
                lines.append(f"  {tag:28s} rounds={r:4d}  bytes={b:,}"
                             f"  ({pct:5.1f}%)")
        return "\n".join(lines)


_STACK: list[CommLedger] = []
_PREPROCESS_DEPTH = 0
# trace-time observers of every record() call, ledger or not — the
# integrity verifier (core/integrity.py) uses this to attribute each
# movement op's digest to the protocol tag + round index that moved it
_LISTENERS: list[Callable] = []


def add_listener(fn: Callable) -> None:
    """Register ``fn(tag, rounds, nbytes, preprocess)`` to observe every
    :func:`record` call (fires even with no tracking ledger active)."""
    _LISTENERS.append(fn)


def remove_listener(fn: Callable) -> None:
    _LISTENERS.remove(fn)


@contextlib.contextmanager
def listening(fn: Callable):
    """Register ``fn`` as a :func:`record` listener for the enclosed
    block, guaranteeing removal on exit (even if the block raises)."""
    add_listener(fn)
    try:
        yield fn
    finally:
        remove_listener(fn)


@contextlib.contextmanager
def preprocessing():
    """All comm recorded inside is input-independent offline traffic."""
    global _PREPROCESS_DEPTH
    _PREPROCESS_DEPTH += 1
    try:
        yield
    finally:
        _PREPROCESS_DEPTH -= 1


@contextlib.contextmanager
def track():
    """Context manager collecting protocol comm into a fresh ledger."""
    led = CommLedger()
    _STACK.append(led)
    try:
        yield led
    finally:
        _STACK.pop()


def record(tag: str, rounds: int, nbytes: int, preprocess: bool = False):
    """Called by protocols at trace time. Ledger add is a no-op when no
    tracker is active; listeners always fire.

    A raising listener cannot corrupt the accounting: every listener
    still runs and the ledger add still happens, after which the first
    listener exception propagates (the verifier relies on its own
    raises surfacing; the ledger must stay byte-exact regardless)."""
    preprocess = preprocess or _PREPROCESS_DEPTH > 0
    err = None
    for fn in list(_LISTENERS):
        try:
            fn(tag, rounds, nbytes, preprocess)
        except BaseException as e:  # noqa: BLE001 — deferred, re-raised below
            if err is None:
                err = e
    if _STACK:  # top-only: round_barrier propagates to its parent on exit
        _STACK[-1].add(tag, rounds, nbytes, preprocess=preprocess)
    if err is not None:
        raise err


@contextlib.contextmanager
def round_barrier(tag: str, rounds: int):
    """Group independent protocol invocations into `rounds` network rounds.

    Inside the context, byte costs accumulate normally but the nested calls'
    round counts are replaced by the stated barrier count (models protocols
    executed in parallel over a batch/layer, e.g. the two independent OTs of
    the Secure ReLU protocol).
    """
    outer = _STACK[-1] if _STACK else None
    with track() as inner:
        yield
    if outer is not None:
        outer.add(tag, rounds, inner.nbytes)
        if inner.pre_nbytes or inner.pre_rounds:
            outer.add(tag, inner.pre_rounds, inner.pre_nbytes, preprocess=True)


@contextlib.contextmanager
def scope(tag: str):
    """``jax.named_scope(tag)``, yielding ``tag``: every device op traced
    inside carries ``tag`` in its HLO ``op_name``, so a profiler trace
    joins the ledger under the same names.  The executor opens one per
    ledger head (``l3``, ``sign4``, ``output``) and one per protocol call
    inside it, whose tag is written once::

        with comm.scope(f"sign{idx}.msb") as tag:
            msb = msb_extract(h, parties, tag=tag)

    Scopes change only HLO metadata, never the computation."""
    with jax.named_scope(tag):
        yield tag


def estimate_cost(fn: Callable, *args, **kwargs) -> CommLedger:
    """Trace ``fn`` abstractly and return its communication ledger."""
    with track() as led:
        jax.eval_shape(fn, *args, **kwargs)
    return led
