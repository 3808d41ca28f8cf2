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
    """Apply one batch both ways; assert every PackedDocs field byte-equal,
    and return the ragged side's store."""
    per_doc, fallback, actor_tables, attr_tables, map_tables = (
        encode_doc_streams(workloads)
    )
    enc = pad_doc_streams(
        per_doc, fallback, actor_tables, attr_tables, map_tables
    )
    d = enc.ins_ref.shape[0]
    ins_counts = stream_counts(enc)

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
        jnp.asarray(ins_counts),
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
    return store


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


def test_ragged_kernel_streams_in_chunks(monkeypatch):
    # a stream longer than one SMEM chunk is read a chunk per grid step,
    # with each doc's count, slot count and overflow carried across them
    import jax

    from peritext_tpu.ops import ragged_pallas

    monkeypatch.setattr(ragged_pallas, "STREAM_CHUNK", 16)
    jax.clear_caches()  # the chunk is read when the kernel traces
    try:
        w = generate_workload(21, num_docs=3, ops_per_doc=14)
        w += generate_workload(22, num_docs=2, ops_per_doc=160)
        _ragged_vs_padded(w, 512, 128, 64, "pallas_interpret")
        _ragged_vs_padded(generate_workload(23, num_docs=2, ops_per_doc=90),
                          64, 64, 32, "pallas_interpret")
    finally:
        jax.clear_caches()


# ---------------------------------------------------------------------------
# the kernel's pending run: a typing run is spliced once, at its end
# ---------------------------------------------------------------------------


def _one_author(refs, seed=0):
    """One author's history of single-insert changes: insert ``j`` follows
    insert ``refs[j]`` (-1: the list head)."""
    from benchmark.drivers._pool import to_program
    from benchmark.reference.opids import HEAD, ROOT
    from benchmark.reference.types import Change, Operation

    rng = random.Random(seed)
    text = (1, "doc1")
    log = [Change(actor="doc1", seq=1, deps={}, start_op=1,
                  ops=[Operation(action="makeList", obj=ROOT, opid=text, key="text")])]
    ids = []
    for r in refs:
        ids.append((len(ids) + 2, "doc1"))
        op = Operation(action="set", obj=text, opid=ids[-1],
                       elem_id=HEAD if r < 0 else ids[r], insert=True,
                       value=rng.choice("abcdefgh"))
        log.append(Change(actor="doc1", seq=len(log) + 1,
                          deps={"doc1": len(log)}, start_op=ids[-1][0], ops=[op]))
    return to_program({"doc1": log})


def _two_runs_at_one_ref(n):
    """doc1 types "ab"; then doc1 and doc2 each type a run of ``n`` after
    "a" concurrently, at equal counters, so doc2's ids are the greater."""
    from benchmark.drivers._pool import to_program
    from benchmark.reference.opids import HEAD, ROOT
    from benchmark.reference.types import Change, Operation

    text = (1, "doc1")
    logs = {"doc1": [Change(actor="doc1", seq=1, deps={}, start_op=1, ops=[
        Operation(action="makeList", obj=ROOT, opid=text, key="text")])],
        "doc2": []}

    def typed(actor, ctr, ref, deps):
        log = logs[actor]
        op = Operation(action="set", obj=text, opid=(ctr, actor), elem_id=ref,
                       insert=True, value=actor[-1])
        log.append(Change(actor=actor, seq=len(log) + 1, deps=deps,
                          start_op=ctr, ops=[op]))
        return op.opid

    a = typed("doc1", 2, HEAD, {"doc1": 1})
    typed("doc1", 3, a, {"doc1": 2})
    for actor in ("doc1", "doc2"):
        ref = a
        for j in range(n):
            deps = {actor: len(logs[actor])} if logs[actor] else {"doc1": 3}
            ref = typed(actor, 4 + j, ref, deps)
    return to_program(logs)


def _b4(seed, ops):
    from benchmark.drivers._pool import to_program
    from benchmark.gen.editing_trace import Trace, history

    cursor = {"typing_run": [1, 24], "backspace_run": [1, 8], "p_typing": 0.46,
              "p_jump": 0.1, "alphabet": "abcdefghijklmnopqrstuvwxyz ,."}
    ins = round(ops * 182315 / 259778)
    return to_program(history(seed, Trace.of(dict(
        cursor, inserts=ins, deletes=ops - ins))))


#: name -> (workloads, slot capacity, page size, STREAM_CHUNK or None)
PENDING_RUN_CASES = {
    # a few hundred B4-shaped edits: runs, backspaces and jumps
    "b4": lambda: ([_b4(5, 420), _b4(6, 300)], 512, 64, None),
    # runs that straddle 16-entry stream chunks
    "b4_chunked": lambda: ([_b4(7, 420), _b4(8, 150)], 512, 64, 16),
    # one run of 300: the pending buffer fills at 128 and flushes
    "long_run": lambda: ([_one_author([-1] + list(range(299)))], 512, 128, None),
    # the same on a page wider than the buffer
    "long_run_wide_page": lambda: (
        [_one_author([-1] + list(range(299)))], 512, 256, None),
    # the run cap falls to a narrow page's row: 32
    "long_run_narrow_page": lambda: (
        [_one_author([-1] + list(range(99)))], 512, 32, None),
    # ten chars, then a run at the head: the slots run out 54 entries into
    # a pending run
    "overflow_mid_run": lambda: (
        [_one_author([-1] + list(range(9)) + [-1] + list(range(10, 259)))],
        192, 64, None),
    # inserts whose reference is a pending entry other than the last
    "ref_inside_run": lambda: (
        [_one_author([-1, 0, 1, 2, 3, 1, 5, 6, 7, 2, 9, 10, 0, 12, 4, 14])],
        512, 64, None),
    # two actors' concurrent runs after one char: a greater id sits right
    # of the lower-id run's reference
    "two_actors": lambda: ([_two_runs_at_one_ref(40)], 512, 64, None),
}


@pytest.mark.parametrize("case", sorted(PENDING_RUN_CASES))
def test_ragged_kernel_pending_runs(case, monkeypatch):
    """The Pallas kernel against the lax pool walk (pool planes, slot
    counts and overflow flags bit for bit) and the padded apply (every
    field), on histories whose typing runs the kernel splices once."""
    import jax

    from peritext_tpu.ops import ragged_pallas

    workloads, slots, page, chunk = PENDING_RUN_CASES[case]()
    if chunk is not None:
        monkeypatch.setattr(ragged_pallas, "STREAM_CHUNK", chunk)
    jax.clear_caches()  # the chunk is read when the kernel traces
    try:
        walk = _ragged_vs_padded(workloads, slots, 16, page, "lax")
        kern = _ragged_vs_padded(workloads, slots, 16, page, "pallas_interpret")
    finally:
        jax.clear_caches()
    assert np.array_equal(np.asarray(kern.pool_elem), np.asarray(walk.pool_elem))
    assert np.array_equal(np.asarray(kern.pool_char), np.asarray(walk.pool_char))
    for field in ("num_slots", "overflow"):
        assert np.array_equal(np.asarray(kern.aux_field(field)),
                              np.asarray(walk.aux_field(field)))
    assert np.asarray(kern.aux_field("overflow")).any() == (case == "overflow_mid_run")


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


@pytest.mark.parametrize("layout", ["paged", "nonsense"])
def test_docbatch_rejects_unknown_layout(layout):
    # "paged" is a StreamingMerge layout, not a DocBatch one
    with pytest.raises(ValueError):
        DocBatch(layout=layout)


def _batch_parity(wl, cursors=None, **kw):
    """Merge ``wl`` under both layouts, assert every result equal, and
    return the (padded, ragged) reports."""
    rp = DocBatch(layout="padded", **kw).merge(wl, cursors=cursors)
    rr = DocBatch(layout="ragged", **kw).merge(wl, cursors=cursors)
    assert rr.spans == rp.spans
    assert rr.roots == rp.roots
    assert rr.fallback_docs == rp.fallback_docs
    assert rr.device_ops == rp.device_ops
    assert rr.cursor_positions == rp.cursor_positions
    return rp, rr


@pytest.mark.parametrize("seed", [3, 11, 42])
def test_docbatch_ragged_matches_padded_on_fuzz_seeds(seed):
    wl = generate_workload(seed=seed, num_docs=8, ops_per_doc=60)
    _batch_parity(wl, cursors=[[] for _ in wl],
                  slot_capacity=256, mark_capacity=64)


#: capacities that route docs of seed 7's workload to the oracle: the
#: configured caps act as fallback thresholds identically under both
#: layouts, and a slot overflow falls back after the device apply
CAPACITY_FALLBACKS = {
    "marks": dict(slot_capacity=256, mark_capacity=8, page_size=64),
    "slots": dict(slot_capacity=32, mark_capacity=64, page_size=32),
    "ops": dict(slot_capacity=256, mark_capacity=64, op_capacity=32,
                page_size=64),
}


@pytest.mark.parametrize("caps", sorted(CAPACITY_FALLBACKS))
def test_docbatch_ragged_matches_padded_under_capacity_fallbacks(caps):
    wl = generate_workload(seed=7, num_docs=6, ops_per_doc=70)
    rp, _ = _batch_parity(wl, **CAPACITY_FALLBACKS[caps])
    assert rp.fallback_docs


def test_docbatch_ragged_cursor_parity():
    from peritext_tpu.api.batch import _oracle_doc

    wl = generate_workload(seed=2, num_docs=4, ops_per_doc=50)
    curs = []
    for w in wl:
        doc = _oracle_doc(w)
        lids = [oid for oid, m in doc._metadata.items() if isinstance(m, list)]
        row = []
        if lids and doc._metadata[lids[0]]:
            el = doc._metadata[lids[0]][0].elem_id
            row = [{"objectId": lids[0], "elemId": el}]
        curs.append(row)
    _batch_parity(wl, cursors=curs)


def test_docbatch_ragged_matches_padded_on_recorded_traces():
    from peritext_tpu.testing.traces import available_traces, load_trace_queues

    traces = available_traces()
    if not traces:
        pytest.skip("no recorded reference traces in this image")
    wl = [load_trace_queues(t) for t in traces[:4]]
    _batch_parity(wl, slot_capacity=1024, mark_capacity=256)


def test_docbatch_ragged_occupancy_beats_padded_on_longtail():
    """One essay among tweets: padded pays the essay's stream widths for
    every tweet; ragged dispatches the real ops alone."""
    wl = generate_workload(seed=5, num_docs=12, ops_per_doc=8)
    wl += generate_workload(seed=501, num_docs=1, ops_per_doc=300)
    rp, rr = _batch_parity(wl, slot_capacity=512, mark_capacity=128)
    assert rr.stats.padding_efficiency == 1.0 > rp.stats.padding_efficiency

    def wasted(r):
        real = r.stats.device_ops + r.stats.fallback_ops
        eff = r.stats.padding_efficiency
        return real / eff - real if eff else 0.0

    assert wasted(rr) == 0.0 < wasted(rp)


# ---------------------------------------------------------------------------
# DocBatch.merge's shared tail, under both layouts
# ---------------------------------------------------------------------------

LAYOUTS = ("padded", "ragged")
#: the per-layout step that places docs on the device and applies them
APPLY_STEP = {"padded": "apply_encoded", "ragged": "_apply_ragged"}


@pytest.mark.parametrize("layout", LAYOUTS)
def test_docbatch_guarded_merge_degrades_to_oracle(layout, monkeypatch):
    from peritext_tpu.api.batch import _oracle_doc, oracle_merge
    from peritext_tpu.obs import GLOBAL_COUNTERS

    wl = generate_workload(seed=41, num_docs=3, ops_per_doc=20)

    def boom(enc):
        raise RuntimeError("injected device failure")

    batch = DocBatch(layout=layout, guard=True)
    monkeypatch.setattr(batch, APPLY_STEP[layout], boom)
    before = GLOBAL_COUNTERS.get("merge.guarded_fallbacks")
    report = batch.merge(wl)
    assert GLOBAL_COUNTERS.get("merge.guarded_fallbacks") == before + 1
    assert report.spans == oracle_merge(wl)
    assert report.roots == [_oracle_doc(w).root for w in wl]
    assert report.fallback_docs == [0, 1, 2]
    assert report.device_ops == 0
    assert report.stats.device_docs == 0
    assert report.stats.extras["guarded_fallback"] == 1.0
    assert "injected device failure" in report.stats.extras["guarded_error"]
    # unguarded, the device failure propagates
    strict = DocBatch(layout=layout)
    monkeypatch.setattr(strict, APPLY_STEP[layout], boom)
    with pytest.raises(RuntimeError):
        strict.merge(wl)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_docbatch_accounting_agrees_across_layouts(layout):
    from peritext_tpu.obs import GLOBAL_COUNTERS

    wl = generate_workload(seed=7, num_docs=6, ops_per_doc=70)
    kw = CAPACITY_FALLBACKS["ops"]
    other = "ragged" if layout == "padded" else "padded"
    ref = DocBatch(layout=other, **kw).merge(wl)
    names = ("merge.calls", "merge.ragged_calls", "merge.device_ops",
             "merge.fallback_docs")
    before = {n: GLOBAL_COUNTERS.get(n) for n in names}
    report = DocBatch(layout=layout, **kw).merge(wl)
    moved = {n: GLOBAL_COUNTERS.get(n) - before[n] for n in names}

    s = report.stats
    assert report.fallback_docs  # the op cap sends a doc to the oracle
    assert s.fallback_docs == len(report.fallback_docs)
    assert s.device_docs == len(wl) - s.fallback_docs
    assert s.device_ops == report.device_ops == sum(
        len(ch.ops) for d, w in enumerate(wl) if d not in report.fallback_docs
        for log in w.values() for ch in log
    )
    fields = ("device_ops", "fallback_ops", "fallback_docs", "device_docs")
    assert ({f: getattr(s, f) for f in fields}
            == {f: getattr(ref.stats, f) for f in fields})
    assert moved == {
        "merge.calls": 1,
        "merge.ragged_calls": int(layout == "ragged"),
        "merge.device_ops": report.device_ops,
        "merge.fallback_docs": len(report.fallback_docs),
    }


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
