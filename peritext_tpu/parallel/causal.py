"""Host-side causal scheduling.

The device kernel applies a *linear*, padded op stream per document; it must
never see a change whose dependencies haven't been applied.  This module
linearizes an arbitrary set of changes into a deterministic admissible order
(and, for streaming, into causal waves).  Determinism matters only for
reproducibility — any admissible order converges, because op application is
commutative across causally-concurrent changes (that's the CRDT's job).

This replaces the reference's catch-and-requeue delivery loop
(test/merge.ts:4-23) with an explicit topological schedule: O(n log n) instead
of retry-until-fixpoint, and it yields the padded batches the TPU wants.
"""

from __future__ import annotations

import heapq
import logging
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from .. import native
from ..core.errors import PeritextError
from ..core.types import Change, Clock
from ..obs import GLOBAL_COUNTERS

#: Below this many changes the Python scheduler wins (array setup overhead).
_NATIVE_THRESHOLD = 64

_log = logging.getLogger(__name__)
#: whether the fall-back after a failed native build has been logged
_warned = False


def _admissible(change: Change, clock: Clock) -> bool:
    if change.seq != clock.get(change.actor, 0) + 1:
        return False
    return all(clock.get(actor, 0) >= dep for actor, dep in (change.deps or {}).items())


def causal_schedule(
    changes: Iterable[Change], base_clock: Optional[Clock] = None
) -> Tuple[List[Change], List[Change]]:
    """Schedule as many changes as causally possible.

    Returns ``(ordered, stuck)``: ``ordered`` is a deterministic admissible
    order (smallest (actor, seq) among ready first); ``stuck`` are changes
    whose dependencies are absent from the set (e.g. lost in transit) —
    callers under faulty delivery leave them for the next anti-entropy round.

    Large sets route through the native C++ scheduler (peritext_tpu/native)
    when it is available; both implementations produce identical output.
    Each schedule counts under ``causal.schedules.native`` or
    ``causal.schedules.python`` in ``GLOBAL_COUNTERS``, by the path that ran.
    """
    changes = list(changes)
    if len(changes) >= _NATIVE_THRESHOLD:
        result = _native_schedule(changes, base_clock)
        if result is not None:
            GLOBAL_COUNTERS.add("causal.schedules.native")
            return result
    GLOBAL_COUNTERS.add("causal.schedules.python")
    clock: Clock = dict(base_clock or {})
    pending: Dict[Tuple[str, int], Change] = {}
    for ch in changes:
        key = (ch.actor, ch.seq)
        if key in pending:
            continue  # duplicate delivery
        if ch.seq <= clock.get(ch.actor, 0):
            continue  # already incorporated
        pending[key] = ch

    # Reverse index: blocker (actor, seq) -> keys waiting on it.  A change
    # waits on its per-actor predecessor and on each unsatisfied dep; since
    # seqs apply in order, clock[a] reaches d exactly when (a, d) is applied.
    waiters: Dict[Tuple[str, int], List[Tuple[str, int]]] = {}
    for key, ch in pending.items():
        if ch.seq > 1 and clock.get(ch.actor, 0) < ch.seq - 1:
            waiters.setdefault((ch.actor, ch.seq - 1), []).append(key)
        for actor, dep in (ch.deps or {}).items():
            if clock.get(actor, 0) < dep and actor != ch.actor:
                waiters.setdefault((actor, dep), []).append(key)

    ready: List[Tuple[str, int]] = [k for k, c in pending.items() if _admissible(c, clock)]
    heapq.heapify(ready)
    out: List[Change] = []

    while ready:
        key = heapq.heappop(ready)
        ch = pending.pop(key, None)
        if ch is None:
            continue  # woken more than once
        out.append(ch)
        clock[ch.actor] = ch.seq
        for waiter in waiters.pop(key, ()):
            cand = pending.get(waiter)
            if cand is not None and _admissible(cand, clock):
                heapq.heappush(ready, waiter)

    stuck = [pending[k] for k in sorted(pending.keys())]
    return out, stuck


def native_loaded() -> bool:
    """Whether the native core is loaded.  The first time a failed build
    leaves it unloaded, the compiler's error is logged once: schedules and
    the batch encode (ops/encode.py) then run in Python."""
    global _warned
    if native.available():
        return True
    error = native.build_error()
    if error is not None and not _warned:
        _warned = True
        _log.warning("native build failed, causal schedules run in "
                     "Python: %s", error)
    return False


def _native_schedule(
    changes: List[Change], base_clock: Optional[Clock]
) -> Optional[Tuple[List[Change], List[Change]]]:
    """Array form of the schedule for the C++ core (peritext_tpu/native).
    Actor indices are assigned in sorted-string order so the native heap's
    integer ordering reproduces the Python tie-break exactly."""
    if not native_loaded():
        return None
    actors = sorted(
        {ch.actor for ch in changes} | set(base_clock or {})
    )
    index = {a: i for i, a in enumerate(actors)}
    n = len(changes)
    actor_arr = np.fromiter((index[ch.actor] for ch in changes), np.int32, n)
    seq_arr = np.fromiter((ch.seq for ch in changes), np.int32, n)
    dep_off = np.zeros(n + 1, np.int32)
    dep_actor: List[int] = []
    dep_seq: List[int] = []
    for i, ch in enumerate(changes):
        for a, s in (ch.deps or {}).items():
            if a in index:
                dep_actor.append(index[a])
                dep_seq.append(s)
            elif s > 0:
                # dep on an actor absent from clock and set: never satisfiable
                # in this call; encode as an impossible self-dep
                dep_actor.append(index[ch.actor])
                dep_seq.append(np.iinfo(np.int32).max)
        dep_off[i + 1] = len(dep_actor)
    clock_arr = np.zeros(len(actors), np.int32)
    for a, s in (base_clock or {}).items():
        clock_arr[index[a]] = s

    order = native.causal_schedule_indices(
        actor_arr,
        seq_arr,
        dep_off,
        np.asarray(dep_actor, np.int32),
        np.asarray(dep_seq, np.int32),
        len(actors),
        clock_arr,
    )
    if order is None:
        return None
    ordered = [changes[i] for i in order]
    if len(ordered) == len(changes):
        return ordered, []  # nothing dropped: skip the stuck reconstruction
    scheduled = set(int(i) for i in order)
    clock0: Clock = dict(base_clock or {})
    pending: Dict[Tuple[str, int], int] = {}
    for i, ch in enumerate(changes):
        key = (ch.actor, ch.seq)
        if key in pending or ch.seq <= clock0.get(ch.actor, 0):
            continue
        pending[key] = i
    stuck = [
        changes[i] for k, i in sorted(pending.items()) if i not in scheduled
    ]
    return ordered, stuck


def causal_sort(
    changes: Iterable[Change], base_clock: Optional[Clock] = None
) -> List[Change]:
    """Order changes so every change's deps precede it; raises if the set has
    a causal gap relative to ``base_clock`` (strict variant of
    :func:`causal_schedule`)."""
    ordered, stuck = causal_schedule(changes, base_clock)
    if stuck:
        missing = sorted((c.actor, c.seq) for c in stuck)[:5]
        raise PeritextError(f"Causal gap: cannot schedule changes {missing}")
    return ordered


def causal_waves(
    changes: Iterable[Change], base_clock: Optional[Clock] = None
) -> List[List[Change]]:
    """Group changes into waves: wave k contains changes admissible once waves
    < k are applied.  Within a wave all changes are causally concurrent (up to
    per-actor seq chains), which is the unit a streaming pipeline can overlap."""
    clock: Clock = dict(base_clock or {})
    seen: set = set()
    remaining: List[Change] = []
    for ch in changes:
        key = (ch.actor, ch.seq)
        if key in seen or ch.seq <= clock.get(ch.actor, 0):
            continue  # duplicate or already incorporated
        seen.add(key)
        remaining.append(ch)
    waves: List[List[Change]] = []
    while remaining:
        wave = [ch for ch in remaining if _admissible(ch, clock)]
        if not wave:
            raise PeritextError("Causal gap: no admissible changes remain")
        wave.sort(key=lambda c: (c.actor, c.seq))
        for ch in wave:
            clock[ch.actor] = ch.seq
        applied = {(c.actor, c.seq) for c in wave}
        remaining = [c for c in remaining if (c.actor, c.seq) not in applied]
        waves.append(wave)
    return waves
