"""A plain reference for text histories of inserts and deletes only, in
O(n log n): the RGA order as the pre-order walk of the insert tree.

Each inserted character is a child of the element it was inserted after
(the list head for an insert at the start), and siblings are ordered by
descending op id.  An insert's id is greater than that of every element
its author had seen, so all of an element's descendants carry greater ids
than it, and the skip-right rule of ``spans_of`` (a new element passes
every element with a greater id to the right of its reference) places each
element exactly where this walk does.  Deleted elements stay in the tree
as tombstones and are left out of the text.

The walk keeps its own stack: a typing run is a chain, each character the
child of the one before it, so the tree is as deep as the longest run of
typing without a jump.  ``spans_of`` walks the element list for every op
and takes minutes on a book-length history; this takes seconds.
"""

from __future__ import annotations

from typing import Dict, List

from . import causal_order
from .opids import HEAD
from .spans import add_characters_to_spans
from .types import Change


def spans_of_text(logs: Dict[str, List[Change]]) -> list:
    """The formatted spans of ``logs``, as ``spans_of`` gives them, for a
    history of one text list with inserts and deletes; raises ValueError on
    any other op."""
    children: Dict = {}
    values: Dict = {}
    deleted: set = set()
    text = None
    for change in causal_order(logs):
        for op in change.ops:
            if op.action == "makeList" and text is None:
                text = op.opid
            elif op.action == "set" and op.insert and op.obj == text:
                children.setdefault(op.elem_id, []).append(op.opid)
                values[op.opid] = op.value
            elif op.action == "del" and op.elem_id is not None and op.obj == text:
                deleted.add(op.elem_id)
            else:
                raise ValueError(f"rga_tree takes inserts and deletes of one text "
                                 f"only, not {op.action!r} (op {op.opid})")

    chars: List[str] = []
    stack = [HEAD]
    while stack:
        elem = stack.pop()
        if elem is not HEAD and elem not in deleted:
            chars.append(values[elem])
        # the greatest id is walked first, so it goes on the stack last
        stack.extend(sorted(children.get(elem, ())))
    spans: list = []
    add_characters_to_spans(chars, {}, spans)
    return spans
