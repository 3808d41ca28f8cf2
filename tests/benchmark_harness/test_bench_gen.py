"""The benchmark's generator makes the program's fuzz histories change for
change (``testing/fuzz.py``), its reference converges each to the spans the
program's scalar document replay reaches (``core/doc.py``), and the
reference's causal order respects every dependency."""

import json
from pathlib import Path

import pytest

from benchmark.drivers import batch_merge
from benchmark.gen.fuzz import Mix, history
from benchmark.reference import causal_order, spans_of

ROOT = Path(__file__).resolve().parents[2]
FUZZ = Mix.of(json.loads((ROOT / "benchmark/traffic/fuzz.json").read_text())["mix"])


def _as_json(h):
    return {a: [c.to_json() for c in log] for a, log in h.items()}


@pytest.mark.parametrize("seed,ops", [(0, 96), (7, 96), (2**31 + 5, 96), (3, 256),
                                      (2**32 + 9, 256), (11, 640)])
def test_fuzz_history_matches_program(seed, ops):
    from peritext_tpu.testing.fuzz import generate_workload

    ours = history(seed, ops, FUZZ)
    theirs = generate_workload(seed, 1, ops)[0]
    assert _as_json(ours) == _as_json(theirs)


@pytest.mark.parametrize("seeds", [range(0, 40), range(5000, 5040), range(2**31, 2**31 + 40)])
def test_reference_matches_scalar_replay(seeds):
    """On short histories (most marks overlap most of the text) the reference
    reaches the spans of the program's change-by-change replay."""
    from peritext_tpu.api.batch import oracle_merge

    hs = [history(s, 128, FUZZ) for s in seeds]
    theirs = oracle_merge([{a: [_program_change(c) for c in log] for a, log in h.items()}
                           for h in hs])
    assert [spans_of(h) for h in hs] == theirs


@pytest.mark.parametrize("seed", [1, 2**31 + 77])
def test_reference_matches_scalar_replay_long(seed):
    from peritext_tpu.api.batch import oracle_merge

    h = history(seed, 1500, FUZZ)
    assert spans_of(h) == oracle_merge([{a: [_program_change(c) for c in log]
                                         for a, log in h.items()}])[0]


def _program_change(change):
    from peritext_tpu.core.types import Change

    return Change.from_json(change.to_json())


def test_full_size_history_is_quick_and_sized():
    """A 6000-op history (the configuration's length) in well under a
    second, with the capacities' worth of elements and mark ops."""
    import time

    t0 = time.perf_counter()
    h = history(2**31 + 1, 6000, FUZZ)
    assert time.perf_counter() - t0 < 5
    ops = [op for log in h.values() for c in log for op in c.ops]
    assert len(ops) >= 6000
    assert 1500 < sum(op.insert for op in ops) < 2300
    assert 1200 < sum(op.action in ("addMark", "removeMark") for op in ops) < 2048


def test_stale_control_differs():
    h = history(5, 256, FUZZ)
    assert batch_merge.stale_spans(h) != spans_of(h)


def test_causal_order_respects_deps():
    h = history(5, 96, FUZZ)
    seen = {}
    for ch in causal_order(h):
        for actor, seq in ch.deps.items():
            assert seen.get(actor, 0) >= seq or actor == ch.actor
        assert seen.get(ch.actor, 0) == ch.seq - 1
        seen[ch.actor] = ch.seq


TEXT = Mix.of(json.loads((ROOT / "benchmark/traffic/concurrent_inserts.json").read_text())["mix"])


@pytest.mark.parametrize("seed", [0, 2**31 + 3])
def test_concurrent_inserts_match_scalar_replay(seed):
    """crdt-benchmarks B2's shape: two replicas that never see each other's
    inserts, and the reference interleaves them as the scalar replay does."""
    from peritext_tpu.api.batch import oracle_merge

    h = history(seed, 400, TEXT)
    assert set(h) == {"doc1", "doc2"}
    # doc2 saw doc1's first change (the empty text) and nothing after it
    assert all(c.deps.get("doc2", 0) == 0 for c in h["doc1"])
    assert all(c.deps.get("doc1", 0) == 1 for c in h["doc2"])
    ours = spans_of(h)
    assert sum(len(s["text"]) for s in ours) == 400
    assert ours == oracle_merge([{a: [_program_change(c) for c in log]
                                  for a, log in h.items()}])[0]
