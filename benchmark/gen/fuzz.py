"""Seeded Peritext edit histories, from a traffic file's ``mix`` parameters.

Reference: raboof/peritext ``test/fuzz.ts`` (3 replicas; insert / remove /
addMark / removeMark over strong, em, link and comment; random pairwise
syncs), with the program's documented fixes: removeMark really removes,
deletes stay in bounds, seeding is deterministic.  For the same seed and the
fuzz mix it makes, change for change, the histories of the program's
``testing/fuzz.generate_workload``; the harness tests pin that.

Each replica keeps only what choosing and writing its next edit needs: the
elements in RGA order with their tombstones, the visible ones, and the
elements that carry a span-end ``after`` anchor (a local insert steps past
such a tombstone, as the reference's ``change`` does).  No replica resolves
marks, so a history costs time linear in its ops times its length, not the
reference document's span walk per mark op.
"""

from __future__ import annotations

import random
import string
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ..reference import causal_order
from ..reference.opids import HEAD, ROOT
from ..reference.schema import MARK_SPEC
from ..reference.types import AFTER, BEFORE, END_OF_TEXT, Boundary, Change, Operation

MARK_TYPES = ("strong", "em", "link", "comment")
EXAMPLE_URLS = tuple(f"{c}.com" for c in string.ascii_uppercase)

History = Dict[str, List[Change]]


@dataclass(frozen=True)
class Mix:
    """A traffic file's ``mix``: what one edit is drawn from."""
    kinds: tuple = ("insert", "remove", "addMark", "removeMark")
    insert_chars: tuple = (1, 3)
    alphabet: str = string.ascii_lowercase + "0123456789"
    initial_text: str = "ABCDE"
    replicas: int = 3
    sync: str = "pairwise"   # after each edit, or "never": all concurrent

    @classmethod
    def of(cls, params: dict) -> "Mix":
        return cls(kinds=tuple(params["kinds"]), insert_chars=tuple(params["insert_chars"]),
                   alphabet=params["alphabet"], initial_text=params["initial_text"],
                   replicas=params["replicas"], sync=params.get("sync", "pairwise"))


class Replica:
    """One editing replica: its clock and the text's element order."""

    def __init__(self, actor: str) -> None:
        self.actor = actor
        self.seq = 0
        self.max_op = 0
        self.clock: Dict[str, int] = {}
        self.text = None                 # the text list's object id
        self.order: list = []            # element ids, RGA order, tombstones kept
        self.alive = bytearray()         # 1 where order[i] is visible
        self.visible: list = []          # visible element ids in order
        self.after_anchored: set = set()  # elements that end a span "after" them

    # -- local edits (reference Doc.change) --------------------------------

    def begin(self) -> Change:
        deps = dict(self.clock)
        self.seq += 1
        self.clock[self.actor] = self.seq
        return Change(actor=self.actor, seq=self.seq, deps=deps,
                      start_op=self.max_op + 1, ops=[])

    def emit(self, change: Change, op: Operation) -> Operation:
        self.max_op += 1
        op.opid = (self.max_op, self.actor)
        self.apply_op(op)
        change.ops.append(op)
        return op

    def insert(self, change: Change, index: int, values) -> None:
        ref = HEAD if index == 0 else self.insert_ref(index - 1)
        for value in values:
            ref = self.emit(change, Operation(action="set", obj=self.text, opid=(0, ""),
                                              elem_id=ref, insert=True, value=value)).opid

    def insert_ref(self, index: int):
        """The element a new character goes after: the ``index``-th visible
        one, or the last tombstone behind it that carries an ``after``
        anchor (reference ``_get_list_element_id(look_after_tombstones)``)."""
        elem = self.visible[index]
        pos = self.order.index(elem) + 1
        stop = self.alive.find(1, pos)
        stop = len(self.order) if stop < 0 else stop
        for p in range(stop - 1, pos - 1, -1):
            if self.order[p] in self.after_anchored:
                return self.order[p]
        return elem

    def delete(self, change: Change, index: int, count: int) -> None:
        for elem in self.visible[index:index + count]:
            self.emit(change, Operation(action="del", obj=self.text, opid=(0, ""),
                                        elem_id=elem))

    def mark(self, change: Change, action: str, mark_type: str, start: int, end: int,
             attrs: Optional[dict]) -> None:
        first = Boundary(BEFORE, self.visible[start])
        if MARK_SPEC[mark_type].inclusive:
            last = (Boundary(BEFORE, self.visible[end]) if end < len(self.visible)
                    else Boundary(END_OF_TEXT))
        else:
            last = Boundary(AFTER, self.visible[end - 1])
        self.emit(change, Operation(action=action, obj=self.text, opid=(0, ""),
                                    start=first, end=last, mark_type=mark_type,
                                    attrs=dict(attrs) if attrs is not None else None))

    # -- applying any op (reference Doc._apply_op, list part) --------------

    def apply_change(self, change: Change) -> None:
        for op in change.ops:
            self.apply_op(op)
        self.clock[change.actor] = change.seq
        self.max_op = max(self.max_op, change.start_op + len(change.ops) - 1)

    def apply_op(self, op: Operation) -> None:
        if op.action == "makeList":
            self.text = op.opid
        elif op.action == "set":
            pos = 0 if op.elem_id is HEAD else self.order.index(op.elem_id) + 1
            while pos < len(self.order) and op.opid < self.order[pos]:
                pos += 1  # concurrent inserts land in descending op-id order
            self.visible.insert(self.alive.count(1, 0, pos), op.opid)
            self.order.insert(pos, op.opid)
            self.alive.insert(pos, 1)
        elif op.action == "del":
            pos = self.order.index(op.elem_id)
            if self.alive[pos]:
                del self.visible[self.alive.count(1, 0, pos)]
                self.alive[pos] = 0
        elif op.end.kind == AFTER:
            self.after_anchored.add(op.end.elem)


@dataclass
class FuzzState:
    replicas: List[Replica]
    logs: Dict[str, List[Change]]
    rng: random.Random
    mix: Mix
    comment_history: List[str] = field(default_factory=list)
    ops_generated: int = 0


def make_fuzz_state(seed: int, mix: Mix) -> FuzzState:
    replicas = [Replica(f"doc{i + 1}") for i in range(mix.replicas)]
    first = replicas[0]
    initial = first.begin()
    first.emit(initial, Operation(action="makeList", obj=ROOT, opid=(0, ""), key="text"))
    first.insert(initial, 0, list(mix.initial_text))
    for rep in replicas[1:]:
        rep.apply_change(initial)
    return FuzzState(replicas=replicas, logs={first.actor: [initial]},
                     rng=random.Random(seed), mix=mix)


def _mark_attrs(state: FuzzState, kind: str, mark_type: str):
    """The mark's attrs, or False where a removeMark has no comment to name."""
    rng = state.rng
    if mark_type == "link":
        return {"url": rng.choice(EXAMPLE_URLS)} if kind == "addMark" else None
    if mark_type == "comment":
        if kind == "addMark":
            cid = f"comment-{rng.randrange(1 << 16):04x}"
            state.comment_history.append(cid)
            return {"id": cid}
        if not state.comment_history:
            return False
        return {"id": rng.choice(state.comment_history)}
    return None


def random_edit(state: FuzzState, rep: Replica) -> Optional[Change]:
    """One edit of the mix on ``rep`` (reference ``random_input_op`` and
    ``Doc.change``): insert, remove, addMark or removeMark."""
    rng, mix = state.rng, state.mix
    length = len(rep.visible)
    kind = rng.choice(mix.kinds)
    if kind == "insert" or length == 0:
        index = rng.randint(0, length)
        count = rng.randint(*mix.insert_chars)
        values = [rng.choice(mix.alphabet) for _ in range(count)]
        change = rep.begin()
        rep.insert(change, index, values)
        return change
    if kind == "remove":
        index = rng.randrange(length)
        count = rng.randint(1, length - index)
        change = rep.begin()
        rep.delete(change, index, count)
        return change
    start = rng.randrange(length)
    end = rng.randint(start + 1, length)
    mark_type = rng.choice(MARK_TYPES)
    attrs = _mark_attrs(state, kind, mark_type)
    if attrs is False:
        return None
    change = rep.begin()
    rep.mark(change, kind, mark_type, start, end, attrs)
    return change


def missing_changes(logs: Dict[str, List[Change]], source: Dict[str, int],
                    target: Dict[str, int]) -> List[Change]:
    out: List[Change] = []
    for actor, seq in source.items():
        have = target.get(actor, 0)
        if have < seq:
            out.extend(logs.get(actor, [])[have:seq])
    return out


def fuzz_step(state: FuzzState) -> None:
    """One iteration: a random edit on a random replica, then (unless the
    mix's replicas never sync) a random pairwise sync delivered in shuffled
    order."""
    rng = state.rng
    rep = state.replicas[rng.randrange(len(state.replicas))]
    change = random_edit(state, rep)
    if change is not None:
        state.logs.setdefault(change.actor, []).append(change)
        state.ops_generated += len(change.ops)
    if state.mix.sync == "never":
        return
    left = rng.randrange(len(state.replicas))
    right = rng.randrange(len(state.replicas))
    if left == right:
        return
    for src, dst in ((left, right), (right, left)):
        dst_rep = state.replicas[dst]
        missing = missing_changes(state.logs, state.replicas[src].clock, dst_rep.clock)
        rng.shuffle(missing)  # delivery order must not matter
        for ch in causal_order_from(missing, dst_rep.clock):
            dst_rep.apply_change(ch)


def causal_order_from(changes: List[Change], clock: Dict[str, int]) -> List[Change]:
    logs: Dict[str, List[Change]] = {}
    for ch in sorted(changes, key=lambda c: (c.actor, c.seq)):
        logs.setdefault(ch.actor, []).append(ch)
    return causal_order(logs, base=clock)


def history(seed: int, ops: int, mix: Mix = Mix()) -> History:
    """One document's edit history of at least ``ops`` ops: actor -> log."""
    state = make_fuzz_state(seed, mix)
    while state.ops_generated < ops:
        fuzz_step(state)
    return {actor: list(log) for actor, log in state.logs.items()}
