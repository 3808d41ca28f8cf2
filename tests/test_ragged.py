"""Ragged paged apply (ops/ragged.py): byte-equality against the padded
oracle, at every tier.

The ragged layout's contract is the paged layout's, sharpened: IDENTICAL
final docs, patches, digests, spans, roots and cursors to the padded
backend on every workload family — while dispatching exactly ONE compiled
apply shape for the whole pool (the recompile sentinel pins the
one-executable half; this file pins the bytes).  Both implementations are
exercised: the lax pool walk (the CPU production path) and the Pallas
kernel under ``interpret=True`` (the TPU path's semantics, minus Mosaic).
"""

import random

import numpy as np
import pytest

import jax.numpy as jnp

from peritext_tpu.api.batch import DocBatch
from peritext_tpu.ops.encode import encode_doc_streams, pad_doc_streams
from peritext_tpu.ops.kernel import apply_batch_jit, encoded_arrays_of
from peritext_tpu.ops.packed import empty_docs
from peritext_tpu.ops.ragged import (
    apply_batch_ragged_jit,
    plan_arrays,
    stream_counts,
)
from peritext_tpu.parallel.codec import encode_frame
from peritext_tpu.parallel.streaming import StreamingMerge
from peritext_tpu.store.paged import PagedDocStore, group_stream_arrays
from peritext_tpu.store.ragged import ragged_plan
from peritext_tpu.testing.fuzz import (
    generate_markheavy_workload,
    generate_workload,
)

ACTORS = ("doc1", "doc2", "doc3")

IMPLS = ("lax", "pallas_interpret")


# ---------------------------------------------------------------------------
# kernel differential: apply_batch_ragged vs the padded apply, field by field
# ---------------------------------------------------------------------------


def _ragged_vs_padded(workloads, slot_capacity, mark_capacity, page_size, impl):
    """Apply one batch both ways; assert every PackedDocs field byte-equal."""
    per_doc, fallback, actor_tables, attr_tables, map_tables = (
        encode_doc_streams(workloads)
    )
    enc = pad_doc_streams(
        per_doc, fallback, actor_tables, attr_tables, map_tables
    )
    d = enc.ins_ref.shape[0]
    ins_counts, del_counts = stream_counts(enc)

    ref = apply_batch_jit(
        empty_docs(d, slot_capacity, mark_capacity), encoded_arrays_of(enc)
    )

    store = PagedDocStore(
        d, slot_capacity, mark_capacity, page_size=page_size
    )
    rows = np.arange(d, dtype=np.int64)
    store.ensure_rows(rows, np.asarray(ins_counts, np.int64))
    plan = ragged_plan(store)
    store.pool_elem, store.pool_char, store.aux = apply_batch_ragged_jit(
        store.pool_elem, store.pool_char, store.aux,
        *plan_arrays(plan),
        group_stream_arrays(enc, None, d),
        jnp.asarray(ins_counts), jnp.asarray(del_counts),
        ragged_impl=impl,
    )
    got = store.materialize_rows(rows, bucket_pages=store.max_doc_pages)
    for f in ref._fields:
        a = np.asarray(getattr(ref, f))
        b = np.asarray(getattr(got, f))
        if f in ("elem_id", "char"):
            b = b[:, : a.shape[1]]
        assert np.array_equal(a, b), f"ragged/{impl} diverges on {f}"
    # the null page is never owned, so no dispatch may dirty it
    assert np.all(np.asarray(store.pool_elem[0]) == 0)
    assert np.all(np.asarray(store.pool_char[0]) == 0)


@pytest.mark.parametrize("impl", IMPLS)
def test_ragged_apply_uniform(impl):
    _ragged_vs_padded(
        generate_workload(3, num_docs=6, ops_per_doc=40), 512, 128, 64, impl
    )


@pytest.mark.parametrize("impl", IMPLS)
def test_ragged_apply_markheavy(impl):
    _ragged_vs_padded(
        generate_markheavy_workload(5, num_docs=4, ops_per_doc=50),
        512, 128, 64, impl,
    )


@pytest.mark.parametrize("impl", IMPLS)
def test_ragged_apply_longdoc_mix(impl):
    # the motivating shape: a book-scale doc among tweets — the paged
    # engine would split these across a bucket ladder; ragged runs ONE
    # program whose per-doc trip counts absorb the skew
    w = generate_workload(11, num_docs=5, ops_per_doc=12)
    w += generate_workload(12, num_docs=1, ops_per_doc=300)
    _ragged_vs_padded(w, 512, 128, 64, impl)


@pytest.mark.parametrize("impl", IMPLS)
def test_ragged_apply_overflow(impl):
    # docs larger than the slot capacity: the overflow flag must trip at
    # the SAME op as the padded path (cap = page_count * P == S)
    _ragged_vs_padded(
        generate_workload(7, num_docs=3, ops_per_doc=90), 64, 64, 32, impl
    )


@pytest.mark.parametrize("seed", range(4))
def test_ragged_apply_fuzz(seed):
    w = generate_workload(seed * 101 + 17, num_docs=4, ops_per_doc=30 + seed * 25)
    _ragged_vs_padded(w, 512, 128, 64, "lax")


# ---------------------------------------------------------------------------
# batch API: DocBatch(layout="ragged") vs the padded oracle
# ---------------------------------------------------------------------------


def test_docbatch_ragged_matches_padded():
    wl = generate_workload(seed=3, num_docs=6, ops_per_doc=40)
    wl += generate_workload(seed=13, num_docs=2, ops_per_doc=150)
    wl += generate_markheavy_workload(seed=7, num_docs=2, ops_per_doc=30)
    rp = DocBatch(layout="padded").merge(wl)
    rb = DocBatch(layout="ragged")
    rr = rb.merge(wl)
    assert rr.spans == rp.spans
    assert rr.roots == rp.roots
    assert rr.fallback_docs == rp.fallback_docs
    assert rr.device_ops == rp.device_ops
    # no bucket pad anywhere: occupancy is definitionally perfect
    assert rr.stats.padding_efficiency == 1.0
    assert rr.stats.extras["layout_ragged"] == 1.0
    assert rb.last_store is not None


def test_docbatch_ragged_cursors_match_padded():
    from peritext_tpu.api.batch import _oracle_doc

    wl = generate_workload(seed=29, num_docs=4, ops_per_doc=35)
    cursors = []
    for w in wl:
        doc = _oracle_doc(w)
        lids = [o for o, m in doc._metadata.items() if isinstance(m, list)]
        row = []
        if lids and doc._metadata[lids[0]]:
            meta = doc._metadata[lids[0]]
            for el in (meta[0].elem_id, meta[len(meta) // 2].elem_id):
                row.append({"objectId": lids[0], "elemId": el})
        cursors.append(row)
    rp = DocBatch(layout="padded").merge(wl, cursors=cursors)
    rr = DocBatch(layout="ragged").merge(wl, cursors=cursors)
    assert rr.cursor_positions == rp.cursor_positions


def test_docbatch_ragged_overflow_fallback_parity():
    big = generate_workload(seed=21, num_docs=4, ops_per_doc=90)
    rp = DocBatch(layout="padded", slot_capacity=64, mark_capacity=16).merge(big)
    rr = DocBatch(
        layout="ragged", slot_capacity=64, mark_capacity=16, page_size=32
    ).merge(big)
    assert rr.spans == rp.spans
    assert rr.fallback_docs == rp.fallback_docs


def test_docbatch_ragged_validation():
    with pytest.raises(ValueError):
        DocBatch(layout="bogus")
    with pytest.raises(ValueError):
        DocBatch(layout="ragged", slot_capacity=100)  # not page-aligned
    import jax

    mesh_like = object.__new__(jax.sharding.Mesh) if hasattr(
        jax.sharding, "Mesh"
    ) else object()
    with pytest.raises(ValueError):
        DocBatch(layout="ragged", mesh=mesh_like)


# ---------------------------------------------------------------------------
# streaming: RaggedStreamingMerge vs the padded session
# ---------------------------------------------------------------------------


def _arrival(workloads, rounds=3, seed=1):
    rng = random.Random(seed)
    out = []
    for w in workloads:
        chs = [ch for log in w.values() for ch in log]
        rng.shuffle(chs)
        size = -(-len(chs) // rounds)
        out.append(
            [
                encode_frame(
                    sorted(chs[i : i + size], key=lambda c: (c.actor, c.seq))
                )
                for i in range(0, len(chs), size)
            ]
        )
    return out


def _build(arrival, layout, num_docs, rounds=3, fused=True, **kw):
    s = StreamingMerge(
        num_docs=num_docs, actors=ACTORS, slot_capacity=256,
        mark_capacity=64, tomb_capacity=64, layout=layout, **kw
    )
    s.fused_pipeline = fused
    for r in range(rounds):
        s.ingest_frames(
            (d, b[r]) for d, b in enumerate(arrival) if r < len(b)
        )
        s.drain()
    return s


def test_streaming_ragged_factory_and_validation():
    s = StreamingMerge(
        num_docs=2, actors=ACTORS, slot_capacity=256, mark_capacity=16,
        tomb_capacity=16, layout="ragged",
    )
    assert type(s).__name__ == "RaggedStreamingMerge"
    assert s.layout == "ragged"
    assert s.health()["layout"] == "ragged"
    with pytest.raises(ValueError):
        StreamingMerge(
            num_docs=2, actors=ACTORS, slot_capacity=100, mark_capacity=16,
            tomb_capacity=16, layout="ragged",
        )


def test_streaming_ragged_matches_padded():
    wl = generate_workload(seed=5, num_docs=8, ops_per_doc=70)
    arr = _arrival(wl)
    sp = _build(arr, "padded", 8)
    sr = _build(arr, "ragged", 8)
    assert sr.read_all() == sp.read_all()
    assert sr.read_patches_all() == sp.read_patches_all()
    assert sr.digest() == sp.digest()
    assert sr.digest(full=False) == sp.digest(full=False)
    assert sr.digest(refresh=True) == sp.digest(refresh=True)
    assert sr.frontier() == sp.frontier()
    assert sr.overflow_count() == sp.overflow_count()


def test_streaming_ragged_serial_drain_matches():
    wl = generate_workload(seed=5, num_docs=8, ops_per_doc=70)
    arr = _arrival(wl)
    sp = _build(arr, "padded", 8)
    sr = _build(arr, "ragged", 8, fused=False)
    assert sr.digest() == sp.digest()
    assert sr.read_all() == sp.read_all()


def test_streaming_ragged_mixed_sizes_match():
    # tweet fleet + essay docs over uneven rounds: the exact mix the
    # bucket ladder fragments; digests must stay bit-equal regardless
    wl = generate_workload(seed=9, num_docs=6, ops_per_doc=12)
    wl += generate_workload(seed=11, num_docs=2, ops_per_doc=160)
    arr = _arrival(wl, rounds=4, seed=2)
    mp = _build(arr, "padded", 8, rounds=4)
    mr = _build(arr, "ragged", 8, rounds=4)
    assert mr.digest() == mp.digest()
    assert mr.read_all() == mp.read_all()


def test_streaming_ragged_overflow_parity():
    wl = generate_workload(seed=17, num_docs=3, ops_per_doc=80)
    arr = _arrival(wl, rounds=1)

    def tiny(layout):
        s = StreamingMerge(
            num_docs=3, actors=ACTORS, slot_capacity=64, mark_capacity=16,
            tomb_capacity=16, layout=layout,
            **({} if layout == "padded" else {"page_size": 64}),
        )
        s.ingest_frames((d, arr[d][0]) for d in range(3))
        s.drain()
        return s

    tp, tr = tiny("padded"), tiny("ragged")
    assert tr.overflow_count() == tp.overflow_count()
    assert tr.digest() == tp.digest()
    assert tr.read_all() == tp.read_all()
