"""The benchmark's plain reference, importing nothing of the program, so a
later program PR cannot move the yardstick.

``spans_of`` converges one change history by the definition of Peritext's
result rather than by its algorithm: every character inserted by any change
takes its place in RGA order (each insert right after the element it names,
past concurrent inserts with greater op ids), deletes tombstone it, and a
visible character carries each mark op whose anchors enclose it in that
order; conflicting mark ops resolve by op id (``spans.ops_to_marks``).  The
harness tests pin it to the program's scalar document replay on many
seeded histories.  ``Doc`` (a copy of the program's scalar document)
replays a history change by change, for the parked serve drivers' patch
streams.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional

from .doc import Doc  # noqa: F401  (the serve drivers' replay)
from .opids import HEAD
from .spans import add_characters_to_spans, ops_to_marks
from .types import AFTER, BEFORE, Change


def causal_order(logs: Dict[str, List[Change]],
                 base: Optional[Dict[str, int]] = None) -> List[Change]:
    """Every change of ``logs`` (each actor's log in seq order, starting
    after ``base``) with each one after its dependencies: repeatedly take,
    actor by actor in sorted order, the next change whose deps the clock
    already covers."""
    clock: Dict[str, int] = dict(base or {})
    cursor = {actor: 0 for actor in logs}
    out: List[Change] = []
    total = sum(len(log) for log in logs.values())
    while len(out) < total:
        progressed = False
        for actor in sorted(logs):
            log = logs[actor]
            while cursor[actor] < len(log):
                ch = log[cursor[actor]]
                if any(clock.get(a, 0) < s for a, s in ch.deps.items() if a != actor):
                    break
                out.append(ch)
                clock[actor] = ch.seq
                cursor[actor] += 1
                progressed = True
        if not progressed:
            raise ValueError("causal gap in a generated history")
    return out


def spans_of(logs: Dict[str, List[Change]]) -> list:
    """The formatted spans every replica of ``logs`` converges to."""
    order: list = []
    values: Dict = {}
    deleted: set = set()
    marks: list = []
    for change in causal_order(logs):
        for op in change.ops:
            if op.action == "set" and op.insert:
                pos = 0 if op.elem_id is HEAD else order.index(op.elem_id) + 1
                while pos < len(order) and op.opid < order[pos]:
                    pos += 1
                order.insert(pos, op.opid)
                values[op.opid] = op.value
            elif op.action == "del":
                deleted.add(op.elem_id)
            elif op.action in ("addMark", "removeMark"):
                marks.append(op)

    # boundary coordinates: element i sits at 2i + 1, between its "before"
    # gap 2i and its "after" gap 2i + 2; a mark covers what lies between
    # its start and end gaps
    index = {elem: i for i, elem in enumerate(order)}

    def gap(boundary) -> float:
        if boundary.kind == BEFORE:
            return 2 * index[boundary.elem]
        if boundary.kind == AFTER:
            return 2 * index[boundary.elem] + 2
        return float("inf") if boundary.kind == "endOfText" else -1

    shown = [elem for elem in order if elem not in deleted]
    where = [2 * index[elem] + 1 for elem in shown]
    covering: List[list] = [[] for _ in shown]
    for op in marks:
        lo = bisect.bisect_left(where, gap(op.start))
        hi = bisect.bisect_left(where, gap(op.end))
        for k in range(lo, hi):
            covering[k].append(op)

    spans: list = []
    run: List[str] = []
    run_marks: Dict = {}
    for elem, ops in zip(shown, covering):
        m = ops_to_marks(ops)
        if m != run_marks:
            add_characters_to_spans(run, run_marks, spans)
            run, run_marks = [], m
        run.append(values[elem])
    add_characters_to_spans(run, run_marks, spans)
    return spans
