"""Seeded single-author editing histories shaped like crdt-benchmarks B4.

B4 replays Kleppmann's automerge-perf trace: one author typing a paper from
an empty text, one single-character edit per change, 182,315 inserts and
77,463 deletes.  The trace itself is not in the repository; its published
counts fix the shape, and the positions of the edits come from an assumed
cursor model (the traffic file's ``trace`` parameters):

- edits come in runs at a cursor: a typing run inserts ``typing_run``
  characters (uniform) at the cursor, a backspace run deletes
  ``backspace_run`` characters (uniform) before it, never more than the
  cursor has before it;
- a run is a typing run with probability ``p_typing``;
- before each run the cursor jumps, with probability ``p_jump``, to a
  uniformly drawn visible position, and otherwise stays;
- quotas end the history at exactly ``inserts`` inserts and ``deletes``
  deletes.

One author needs no RGA replica: an insert names the visible character
before the cursor (or the list head), and a new id is the largest yet, so
it lands right there.  The visible ids are kept as a gap buffer, the ids
before the cursor in order and those after it reversed, so typing and
backspacing cost O(1) and a jump costs one slice.
"""

from __future__ import annotations

import random
from dataclasses import dataclass

from ..reference.opids import HEAD, ROOT
from ..reference.types import Change, Operation
from .fuzz import History

ACTOR = "doc1"


@dataclass(frozen=True)
class Trace:
    """A traffic file's ``trace``: the history's totals and its cursor model."""
    inserts: int
    deletes: int
    typing_run: tuple
    backspace_run: tuple
    p_typing: float
    p_jump: float
    alphabet: str

    @classmethod
    def of(cls, params: dict) -> "Trace":
        return cls(inserts=params["inserts"], deletes=params["deletes"],
                   typing_run=tuple(params["typing_run"]),
                   backspace_run=tuple(params["backspace_run"]),
                   p_typing=params["p_typing"], p_jump=params["p_jump"],
                   alphabet=params["alphabet"])


def _move_cursor(left: list, right: list, to: int) -> None:
    """Move the gap to visible position ``to``: ``left`` holds the ids before
    it in order, ``right`` the ids after it in reverse order."""
    if to < len(left):
        moved = left[to:]
        del left[to:]
        moved.reverse()
        right.extend(moved)
    elif to > len(left):
        k = to - len(left)
        moved = right[len(right) - k:]
        del right[len(right) - k:]
        moved.reverse()
        left.extend(moved)


def history(seed: int, trace: Trace) -> History:
    """One author's history: a change creating the text, then one change per
    single-character edit, ``trace.inserts`` inserts and ``trace.deletes``
    deletes in all."""
    rng = random.Random(seed)
    text = (1, ACTOR)
    log = [Change(actor=ACTOR, seq=1, deps={}, start_op=1,
                  ops=[Operation(action="makeList", obj=ROOT, opid=text, key="text")])]
    ctr = 1
    left: list = []
    right: list = []
    ins_left, del_left = trace.inserts, trace.deletes

    def edit(op: Operation) -> None:
        seq = len(log) + 1
        log.append(Change(actor=ACTOR, seq=seq, deps={ACTOR: seq - 1},
                          start_op=op.opid[0], ops=[op]))

    while ins_left or del_left:
        visible = len(left) + len(right)
        if rng.random() < trace.p_jump:
            _move_cursor(left, right, rng.randint(0, visible))
        typing = rng.random() < trace.p_typing
        if not ins_left:
            typing = False
        elif not del_left or not left:
            typing = True
        if typing:
            for _ in range(min(rng.randint(*trace.typing_run), ins_left)):
                ctr += 1
                ref = left[-1] if left else HEAD
                op = Operation(action="set", obj=text, opid=(ctr, ACTOR), elem_id=ref,
                               insert=True, value=rng.choice(trace.alphabet))
                edit(op)
                left.append(op.opid)
                ins_left -= 1
        else:
            if not left:
                # nothing left to insert and the cursor at the start: put it
                # after a visible character, so the backspace has one to take
                _move_cursor(left, right, rng.randint(1, visible))
            for _ in range(min(rng.randint(*trace.backspace_run), len(left), del_left)):
                ctr += 1
                edit(Operation(action="del", obj=text, opid=(ctr, ACTOR),
                               elem_id=left.pop()))
                del_left -= 1
    return {ACTOR: log}
