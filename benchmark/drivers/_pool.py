"""The seeded pool of distinct edit histories that every cell draws its
documents from (doc ``i`` gets history ``i mod distinct``), and their
conversion into the program's input types."""

from __future__ import annotations

from typing import Dict, List

from benchmark.gen.fuzz import History, Mix, history
from benchmark.reference import causal_order


def history_seed(seed: int, j: int) -> int:
    """History ``j`` of run ``seed``: distinct for every (seed, j)."""
    return seed * 4096 + j


def make_pool(seed: int, distinct: int, ops: int, mix: dict) -> List[History]:
    """``distinct`` histories of ``ops`` ops each, drawn from the traffic
    file's ``mix`` parameters."""
    edits = Mix.of(mix)
    return [history(history_seed(seed, j), ops, edits) for j in range(distinct)]


def op_count(h: History) -> int:
    """CRDT ops in a history, as the reference counts them: one per
    inserted character, deleted character, mark op or object op."""
    return sum(len(ch.ops) for log in h.values() for ch in log)


def to_program(h: History) -> Dict[str, list]:
    """The history as the program takes it: ``peritext_tpu`` ``Change``
    objects, made from the reference's wire JSON."""
    from peritext_tpu.core.types import Change

    return {actor: [Change.from_json(ch.to_json()) for ch in log]
            for actor, log in h.items()}


def frames_of(h: History, changes_per_frame: int) -> List[bytes]:
    """The history as a client sends it: causally ordered changes, a fixed
    number per wire frame (``parallel/codec.encode_frame``)."""
    from peritext_tpu.core.types import Change
    from peritext_tpu.parallel.codec import encode_frame

    ordered = [Change.from_json(ch.to_json()) for ch in causal_order(h)]
    return [encode_frame(ordered[i:i + changes_per_frame])
            for i in range(0, len(ordered), changes_per_frame)]


def frame_ops(h: History, changes_per_frame: int) -> List[int]:
    ordered = causal_order(h)
    return [sum(len(ch.ops) for ch in ordered[i:i + changes_per_frame])
            for i in range(0, len(ordered), changes_per_frame)]


def frame_inserts(h: History, changes_per_frame: int) -> List[int]:
    """Inserted characters per frame: what grows a document's slots."""
    ordered = causal_order(h)
    return [sum(1 for ch in ordered[i:i + changes_per_frame] for op in ch.ops
                if op.insert)
            for i in range(0, len(ordered), changes_per_frame)]
