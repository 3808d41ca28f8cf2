"""Long single-author documents through ``DocBatch(layout="ragged")``: the
merge equals the insert-tree reference on B4-shaped histories (typing and
backspace runs at a cursor) of unequal lengths, each many pages long, and
the merge reports its encode read-back and apply plan as spans and its
pages and loop steps as counters."""

import numpy as np
import pytest

from benchmark.drivers._pool import to_program
from benchmark.gen.editing_trace import Trace, history
from benchmark.reference.rga_tree import spans_of_text
from peritext_tpu.api import DocBatch
from peritext_tpu.obs import metrics
from peritext_tpu.obs.spans import Tracer

PAGE = 128
CURSOR = {"typing_run": [1, 24], "backspace_run": [1, 8], "p_typing": 0.46,
          "p_jump": 0.1, "alphabet": "abcdefghijklmnopqrstuvwxyz ,."}


def b4_history(seed, ops):
    ins = round(ops * 182315 / 259778)
    return history(seed, Trace.of(dict(CURSOR, inserts=ins, deletes=ops - ins)))


@pytest.fixture(scope="module")
def longdocs():
    hs = [b4_history(seed, ops) for seed, ops in ((1, 3000), (2, 4500), (3, 6000))]
    return hs, [to_program(h) for h in hs]


@pytest.fixture
def counters(monkeypatch):
    fresh = metrics.Counters()
    monkeypatch.setattr(metrics, "GLOBAL_COUNTERS", fresh)
    import peritext_tpu.api.batch as batch_mod

    monkeypatch.setattr(batch_mod, "GLOBAL_COUNTERS", fresh)
    return fresh


def test_ragged_merge_equals_the_tree_reference(longdocs, counters):
    hs, docs = longdocs
    tracer = Tracer()
    seen = []
    tracer.add_sink(seen.append)
    batch = DocBatch(layout="ragged", page_size=PAGE, slot_capacity=36 * PAGE,
                     op_capacity=36 * PAGE, mark_capacity=8, comment_capacity=8,
                     tracer=tracer)
    report = batch.merge(docs)
    assert report.fallback_docs == []
    assert report.spans == [spans_of_text(h) for h in hs]
    pages = batch.last_store.alloc
    assert all(len(pages.pages_of(d)) > 8 for d in range(len(docs)))

    # one read-back under encode and one plan under apply, per merge
    by_name = {}
    for sp in seen:
        by_name.setdefault(sp.name, []).append(sp)
    parents = {sp.span_id: sp.name for sp in seen}
    for child, parent in (("batch.encode.rows", "batch.encode"),
                          ("batch.apply.plan", "batch.apply")):
        assert len(by_name[child]) == 1
        assert parents[by_name[child][0].parent_id] == parent

    # the counters add the planned pages and the device loops' true bounds
    ins = [sum(op.insert for ch in log for op in ch.ops) for h in hs for log in h.values()]
    dels = [sum(op.action == "del" for ch in log for op in ch.ops)
            for h in hs for log in h.values()]
    planned = sum(-(-n // PAGE) for n in ins)
    assert counters.get("merge.ragged_pages") == planned
    assert counters.get("merge.ragged_loop_steps") == max(ins) + max(dels)
    batch.merge(docs)
    assert counters.get("merge.ragged_pages") == 2 * planned
    assert counters.get("merge.ragged_calls") == 2


def test_padded_merge_emits_no_ragged_spans(longdocs):
    _, docs = longdocs
    tracer = Tracer()
    seen = []
    tracer.add_sink(seen.append)
    DocBatch(slot_capacity=36 * PAGE, mark_capacity=8, comment_capacity=8,
             tracer=tracer).merge(docs[:1])
    names = {sp.name for sp in seen}
    assert "batch.encode" in names
    assert not names & {"batch.encode.rows", "batch.apply.plan"}


def test_delete_masks_equal_pairwise():
    """The ragged apply's sorted delete masks against the padded path's
    pairwise compares, on targets with repeats, absent ids and dead zeros."""
    import jax.numpy as jnp

    from peritext_tpu.ops.ragged import _delete_masks

    rng = np.random.default_rng(5)
    elems = rng.permutation(np.arange(1, 400))[:300].astype(np.int32)
    elems = np.concatenate([elems, np.zeros(20, np.int32)])
    tombs = np.concatenate([rng.choice(elems[:300], 30, replace=False),
                            np.zeros(10, np.int32)]).astype(np.int32)
    targets = rng.integers(0, 420, 200).astype(np.int32)
    exists, skip = _delete_masks(jnp.asarray(elems), jnp.asarray(tombs),
                                 jnp.asarray(targets))
    live = targets != 0
    want_exists = (elems[:, None] == targets[None, :]).any(axis=0)
    earlier = np.array([(targets[:j] == targets[j]).any() for j in range(len(targets))])
    want_skip = (tombs[:, None] == targets[None, :]).any(axis=0) | earlier
    assert np.array_equal(np.asarray(exists)[live], want_exists[live])
    assert np.array_equal(np.asarray(skip)[live], want_skip[live])
