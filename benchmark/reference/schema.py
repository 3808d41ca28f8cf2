"""The mark schema the reference reads (copy of ``peritext_tpu/schema.py``'s
CRDT half: ``inclusive`` and ``allow_multiple`` per mark type, and the
attribute keys a mark must carry)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Tuple


@dataclass(frozen=True)
class MarkSchema:
    #: Does the span end grow to include text inserted at its right edge?
    inclusive: bool
    #: Multiple concurrent values coexist (set semantics) vs last-writer-wins.
    allow_multiple: bool
    #: Names of data attributes carried by the mark ("url", "id", ...).
    attr_keys: Tuple[str, ...] = field(default=())


MARK_SPEC: Dict[str, MarkSchema] = {
    "strong": MarkSchema(inclusive=True, allow_multiple=False),
    "em": MarkSchema(inclusive=True, allow_multiple=False),
    "comment": MarkSchema(inclusive=False, allow_multiple=True, attr_keys=("id",)),
    "link": MarkSchema(inclusive=False, allow_multiple=False, attr_keys=("url",)),
}


def is_mark_type(s: str) -> bool:
    return s in MARK_SPEC
