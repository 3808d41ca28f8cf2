"""Paged document storage (peritext_tpu/store/): the byte-equality oracle
and the subsystem invariants.

The paged layout's correctness contract is blunt: for every fuzz seed,
the paged streaming backend must produce IDENTICAL final docs,
patches and store digests to the padded backend — the padded path stays
resident as the oracle.  On top of that: allocator determinism (page
tables are replicated state), typed pool exhaustion, checkpoint round-trip
of a paged session, a recompile-sentinel replay proving paged dispatch
mints no per-round compiles, and the page-pool telemetry surfaces.
"""

import random
import tempfile

import numpy as np
import pytest

from peritext_tpu.parallel.codec import encode_frame
from peritext_tpu.parallel.streaming import StreamingMerge
from peritext_tpu.store import PageAllocator, PagedDocStore, PoolExhausted
from peritext_tpu.testing.fuzz import generate_workload

ACTORS = ("doc1", "doc2", "doc3")


# ---------------------------------------------------------------------------
# allocator: deterministic, typed exhaustion, compact/evacuate/reseat
# ---------------------------------------------------------------------------


def test_allocator_is_deterministic_lowest_first():
    a = PageAllocator(10)
    assert a.ensure(0, 3) == [1, 2, 3]
    assert a.ensure(1, 2) == [4, 5]
    assert a.ensure(0, 3) == []  # already satisfied: no-op
    a.free_doc(0)
    # freed pages come back lowest-id-first, ahead of never-used ones
    assert a.ensure(2, 4) == [1, 2, 3, 6]
    # two allocators fed the same request sequence agree exactly
    b = PageAllocator(10)
    for doc, n in ((0, 3), (1, 2)):
        b.ensure(doc, n)
    b.free_doc(0)
    assert b.ensure(2, 4) == [1, 2, 3, 6]
    assert a.pages_of(2) == b.pages_of(2)


def test_allocator_exhaustion_is_typed_and_atomic():
    a = PageAllocator(6)
    a.ensure(0, 3)
    with pytest.raises(PoolExhausted) as exc:
        a.ensure(1, 5)
    assert exc.value.requested == 5
    assert exc.value.free == 2
    assert exc.value.total == 6
    assert a.pages_of(1) == []  # failed ensure assigned nothing
    a.grow(12)
    assert a.ensure(1, 5) == [4, 5, 6, 7, 8]


def test_allocator_compact_plan_packs_sorted():
    a = PageAllocator(12)
    a.ensure(3, 2)
    a.ensure(1, 2)
    a.free_doc(3)
    a.ensure(5, 1)
    plan = a.compact_plan()
    a.apply_compact(plan)
    # docs walk in sorted row order: doc 1 first, then doc 5
    assert a.pages_of(1) == [1, 2]
    assert a.pages_of(5) == [3]
    assert a.free_pages == 12 - 1 - 3


def test_store_compact_and_evacuate_preserve_content():
    s = PagedDocStore(4, slot_capacity=256, mark_capacity=8,
                      tomb_capacity=8, page_size=64, initial_pages=16)
    s.ensure_rows([0, 1, 2], [100, 30, 64])
    s.pool_elem = s.pool_elem.at[s.alloc.pages_of(1)[0], 0].set(42)
    before = np.asarray(s.materialize_rows([1], 1).elem_id)
    s.evacuate_row(0)
    moved = s.compact()
    assert moved > 0
    after = np.asarray(s.materialize_rows([1], 1).elem_id)
    assert (before == after).all()
    # freed pages and the null page read as zeros
    assert int(np.asarray(s.pool_elem[0]).sum()) == 0
    free_page = s.alloc._free[0]
    assert int(np.asarray(s.pool_elem[free_page]).sum()) == 0


def test_store_pool_grows_and_caps():
    s = PagedDocStore(2, slot_capacity=512, mark_capacity=8,
                      page_size=64, initial_pages=4, max_pool_pages=8)
    s.ensure_rows([0], [300])  # 5 pages: forces one doubling
    assert s.growths == 1
    assert s.pool_elem.shape[0] == 8
    with pytest.raises(PoolExhausted):
        s.ensure_rows([1], [512])  # 8 more pages would exceed the cap
    assert s.pool_stats()["growths"] == 1


def test_store_default_tomb_capacity_matches_padded_layout():
    """An omitted tomb_capacity must default to the slot capacity (the
    padded layout's empty_docs default), not to the width-1 aux proto."""
    s = PagedDocStore(2, slot_capacity=256, mark_capacity=64, page_size=64)
    assert s.aux_capacities["tomb_capacity"] == 256


def test_store_rejects_unaligned_slot_capacity():
    with pytest.raises(ValueError):
        PagedDocStore(2, slot_capacity=100, mark_capacity=8, page_size=64)


# ---------------------------------------------------------------------------
# streaming: paged vs padded byte equality, blocks, digests, checkpoints
# ---------------------------------------------------------------------------


def _arrival(workloads, rounds=3, seed=1):
    rng = random.Random(seed)
    out = []
    for w in workloads:
        chs = [ch for log in w.values() for ch in log]
        rng.shuffle(chs)
        size = -(-len(chs) // rounds)
        out.append([
            encode_frame(sorted(chs[i:i + size], key=lambda c: (c.actor, c.seq)))
            for i in range(0, len(chs), size)
        ])
    return out


def _build(arrival, layout, num_docs, rounds=3, read_chunk=8192, **kw):
    s = StreamingMerge(
        num_docs=num_docs, actors=ACTORS, slot_capacity=256,
        mark_capacity=64, tomb_capacity=64, read_chunk=read_chunk,
        layout=layout, **kw,
    )
    for r in range(rounds):
        s.ingest_frames(
            (d, b[r]) for d, b in enumerate(arrival) if r < len(b)
        )
        s.drain()
    return s


def test_streaming_paged_factory_and_validation():
    s = StreamingMerge(num_docs=2, actors=ACTORS, layout="paged")
    assert type(s).__name__ == "PagedStreamingMerge"
    assert s.layout == "paged"
    assert StreamingMerge(num_docs=2, actors=ACTORS).layout == "padded"
    with pytest.raises(ValueError):
        StreamingMerge(num_docs=2, actors=ACTORS, layout="paged",
                       static_rounds=True)
    with pytest.raises(ValueError):
        StreamingMerge(num_docs=2, actors=ACTORS, layout="bogus")
    with pytest.raises(ValueError):
        StreamingMerge(num_docs=2, actors=ACTORS, layout="paged",
                       slot_capacity=100)


@pytest.mark.parametrize("seed", [5, 23])
def test_streaming_paged_matches_padded(seed):
    wl = generate_workload(seed=seed, num_docs=8, ops_per_doc=70)
    arr = _arrival(wl)
    sp = _build(arr, "padded", 8)
    sq = _build(arr, "paged", 8)
    assert sp.read_all() == sq.read_all()
    assert sp.read_patches_all() == sq.read_patches_all()
    assert sp.digest() == sq.digest()
    assert sp.digest(full=False) == sq.digest(full=False)
    assert sp.digest(refresh=True) == sq.digest(refresh=True)
    assert sp.frontier() == sq.frontier()
    assert sp.overflow_count() == sq.overflow_count()


def test_streaming_paged_block_chunked_reads_match():
    """read_chunk smaller than the batch: the paged backend materializes
    per block at page-bucketed widths — reads and digests must still be
    bit-equal to the padded session."""
    wl = generate_workload(seed=9, num_docs=10, ops_per_doc=50)
    arr = _arrival(wl, rounds=2)
    sp = _build(arr, "padded", 10, rounds=2, read_chunk=4)
    sq = _build(arr, "paged", 10, rounds=2, read_chunk=4)
    assert sp.read_all() == sq.read_all()
    assert sp.digest() == sq.digest()
    assert [sp.read(d) for d in range(10)] == [sq.read(d) for d in range(10)]
    assert [sp.read_root(d) for d in range(10)] == [sq.read_root(d) for d in range(10)]


def test_streaming_paged_async_digest_and_fallback_parity():
    wl = generate_workload(seed=13, num_docs=6, ops_per_doc=50)
    arr = _arrival(wl, rounds=2)
    sp = _build(arr, "padded", 6, rounds=2)
    sq = _build(arr, "paged", 6, rounds=2)
    assert sp.digest_async().wait() == sq.digest_async().wait()
    # corrupt-frame quarantine + forced demotion behave identically
    bad = arr[2][0][:12] + b"\xffgarbage"
    sp.ingest_frame(2, bad, on_corrupt="quarantine")
    sq.ingest_frame(2, bad, on_corrupt="quarantine")
    assert sorted(sp.quarantined()) == sorted(sq.quarantined())
    sp.force_fallback(4)
    sq.force_fallback(4)
    assert sp.digest() == sq.digest()
    assert sp.read(4) == sq.read(4)
    assert sp.health()["fallback_docs"] == sq.health()["fallback_docs"]


def test_streaming_paged_overflow_routes_to_replay_like_padded():
    wl = generate_workload(seed=17, num_docs=3, ops_per_doc=80)
    arr = _arrival(wl, rounds=1)

    def tiny(layout):
        kw = {} if layout == "padded" else {"page_size": 64}
        s = StreamingMerge(num_docs=3, actors=ACTORS, slot_capacity=64,
                           mark_capacity=16, tomb_capacity=16, layout=layout,
                           **kw)
        s.ingest_frames((d, arr[d][0]) for d in range(3))
        s.drain()
        return s

    tp, tq = tiny("padded"), tiny("paged")
    assert tp.overflow_count() == tq.overflow_count()
    assert tp.digest() == tq.digest()
    assert tp.read_all() == tq.read_all()


def test_streaming_paged_pool_exhaustion_is_typed():
    wl = generate_workload(seed=19, num_docs=4, ops_per_doc=60)
    arr = _arrival(wl, rounds=1)
    s = StreamingMerge(num_docs=4, actors=ACTORS, slot_capacity=256,
                       mark_capacity=64, layout="paged",
                       pool_pages=2, max_pool_pages=3)
    s.ingest_frames((d, arr[d][0]) for d in range(4))
    with pytest.raises(PoolExhausted):
        s.drain()


def test_streaming_paged_reshard_pages_and_digest_invariance():
    wl = generate_workload(seed=21, num_docs=9, ops_per_doc=40)
    arr = _arrival(wl, rounds=1)
    sq = _build(arr, "paged", 9, rounds=1, read_chunk=3)
    before = sq.digest()
    spans_before = sq.read_all()
    out = sq.reshard()
    assert "page_load" in out
    assert sum(out["page_load"]) == int(sq.store.page_loads().sum())
    assert sq.digest() == before
    assert sq.read_all() == spans_before
    # ingest keeps working after the permutation
    sq.ingest_frames([(0, arr[0][0])])  # duplicate frames are idempotent
    sq.drain()
    assert sq.digest() == before


def test_paged_checkpoint_round_trip():
    from peritext_tpu import checkpoint as ckpt

    wl = generate_workload(seed=25, num_docs=5, ops_per_doc=40)
    arr = _arrival(wl, rounds=2)
    sq = _build(arr, "paged", 5, rounds=2)
    with tempfile.TemporaryDirectory() as td:
        meta = ckpt.save_session(sq, td)
        assert meta["config"]["layout"] == "paged"
        assert meta["config"]["page_size"] == sq.page_size
        restored = ckpt.restore_session(td)
        assert type(restored).__name__ == "PagedStreamingMerge"
        assert restored.digest() == sq.digest()
        assert restored.read_all() == sq.read_all()


def test_paged_replay_mints_no_per_round_compiles(recompile_sentinel):
    """Shape discipline: a fresh paged session replaying a known workload
    reuses every compiled program (apply groups, materialization, fused
    digest) — zero XLA compiles after the warmup session."""
    wl = generate_workload(seed=31, num_docs=6, ops_per_doc=50)
    arr = _arrival(wl, rounds=2)

    def run():
        s = _build(arr, "paged", 6, rounds=2)
        s.digest()
        return s.read_all()

    first = run()  # warmup: compiles everything
    recompile_sentinel.mark()
    second = run()
    assert second == first
    assert recompile_sentinel.since_mark() == {}, (
        f"paged replay recompiled: {recompile_sentinel.since_mark()}"
    )


# ---------------------------------------------------------------------------
# telemetry: page-pool gauges, devprof section, mux snapshot, router loads
# ---------------------------------------------------------------------------


def test_devprof_page_pool_section_and_gauges():
    from peritext_tpu.obs import DeviceProfiler, prometheus_text

    prof = DeviceProfiler()
    assert prof.snapshot()["page_pool"] is None  # padded-only: no section
    wl = generate_workload(seed=33, num_docs=5, ops_per_doc=40)
    arr = _arrival(wl, rounds=1)
    with prof:
        import peritext_tpu.obs.devprof as devprof_mod
        old = devprof_mod.GLOBAL_DEVPROF
        devprof_mod.GLOBAL_DEVPROF = prof
        try:
            # module-level GLOBAL_DEVPROF references were imported by value
            # in the session module via ..obs; drive the store's stats in
            # directly instead of monkeypatching every site
            s = _build(arr, "paged", 5, rounds=1)
            prof.observe_page_pool(s.store.pool_stats())
        finally:
            devprof_mod.GLOBAL_DEVPROF = old
    snap = prof.snapshot()
    pp = snap["page_pool"]
    assert pp is not None
    for key in ("page_size", "pool_pages", "pages_in_use", "pool_utilization",
                "internal_frag_slots", "internal_frag_ratio",
                "frag_by_decile", "peak_utilization"):
        assert key in pp, key
    text = prometheus_text(devprof=prof)
    assert "peritext_page_pool_pages" in text
    assert "peritext_page_pool_utilization" in text
    assert 'peritext_page_frag_ratio{decile="d0"}' in text
    # health_snapshot composition carries the section through devprof
    from peritext_tpu.obs import health_snapshot

    snap = health_snapshot(devprof=prof)
    assert snap["devprof"]["page_pool"]["pool_pages"] == pp["pool_pages"]


def test_streaming_paged_health_and_occupancy_accounting():
    wl = generate_workload(seed=35, num_docs=6, ops_per_doc=40)
    arr = _arrival(wl, rounds=2)
    from peritext_tpu.obs import GLOBAL_DEVPROF

    GLOBAL_DEVPROF.reset()
    with GLOBAL_DEVPROF:
        sq = _build(arr, "paged", 6, rounds=2)
    h = sq.health()
    assert h["layout"] == "paged"
    assert h["page_pool"]["pages_in_use"] > 0
    assert sq.last_round_stats.extras["layout_paged"] == 1.0
    assert 0.0 < sq.last_round_stats.padding_efficiency <= 1.0
    snap = GLOBAL_DEVPROF.snapshot()
    assert snap["page_pool"] is not None
    assert any(
        o["origin"].startswith("streaming.paged")
        for o in snap["occupancy"].values()
    )
    assert any(site.startswith("apply_batch_paged") for site in snap["sites"])


def test_mux_snapshot_reports_layout_and_pool():
    from peritext_tpu.serve import SessionMux

    sq = StreamingMerge(num_docs=4, actors=ACTORS, slot_capacity=256,
                        mark_capacity=64, layout="paged")
    mux = SessionMux(sq, host="t")
    sid, verdict = mux.open_session("client0")
    assert verdict.admitted
    wl = generate_workload(seed=37, num_docs=1, ops_per_doc=30)
    frame = encode_frame(sorted(
        [ch for log in wl[0].values() for ch in log],
        key=lambda c: (c.actor, c.seq),
    ))
    mux.submit(sid, frame)
    mux.flush()
    snap = mux.snapshot()
    assert snap["layout"] == "paged"
    assert snap["page_pool"]["pages_in_use"] >= 1
    # the mux serves byte-identical patches off a paged session
    sp = StreamingMerge(num_docs=4, actors=ACTORS, slot_capacity=256,
                        mark_capacity=64)
    sp.ingest_frame(0, frame)
    sp.drain()
    assert sq.read(0) == sp.read(0)


def test_router_page_load_dimension():
    from peritext_tpu.parallel.router import FleetRouter

    r = FleetRouter()
    r.add_host("a", capacity=8)
    r.add_host("b", capacity=8)
    # paged fleet: hosts report pages; the loaded host loses placement
    r.observe("a", page_load=100)
    r.observe("b", page_load=10)
    assert r.place("doc-1", size=2) == "b"
    assert r.host("b").page_load == 12  # estimate drifts in pages
    assert r.host("b").to_json()["page_load"] == 12
    # a fresh paged host with an EMPTY pool stays in the page dimension
    r.add_host("c", capacity=8)
    r.observe("c", page_load=0, slot_load=999)
    assert r.host("c").paged and r.host("c").device_load() == 0
    assert r.place("doc-2", size=1) == "c"
    # a doc placed BEFORE the paged latch must not wipe the page estimate
    # on eviction: its slot-unit size was never added to page_load
    r3 = FleetRouter()
    r3.add_host("a", capacity=8)
    r3.add_host("b", capacity=8)
    r3.place("pre-latch", size=512)  # slot units, host assumed padded
    host = r3.host_of("pre-latch")
    r3.observe(host, page_load=40)
    r3.evacuate(host)
    other = "b" if host == "a" else "a"
    assert r3.host(host).page_load == 40  # untouched by the slot-unit doc
    assert r3.host_of("pre-latch") == other
    # slot-unit host: page_load stays 0 and slot placement is unchanged
    r2 = FleetRouter()
    r2.add_host("a", capacity=8)
    r2.observe("a", slot_load=5)
    assert r2.host("a").device_load() == 5


# ---------------------------------------------------------------------------
# graftlint: store/ is merge scope; the corpus case must keep failing
# ---------------------------------------------------------------------------


_REPO_ROOT = __import__("pathlib").Path(__file__).resolve().parents[1]


def test_graftlint_store_is_merge_scope_and_corpus_fires():
    from peritext_tpu.analysis.engine import scan_paths

    findings = scan_paths(
        [_REPO_ROOT / "tests/graftlint_corpus/bad/store/allocator_walk.py"],
        root=_REPO_ROOT,
    )
    ids = {f.rule for f in findings}
    assert "PTL001" in ids, "unsorted free-set walk must fire PTL001"
    assert "PTL006" in ids, "wall-clock allocation stamp must fire PTL006"


def test_graftlint_store_package_scans_clean():
    from peritext_tpu.analysis.engine import scan_paths

    findings = scan_paths(
        [_REPO_ROOT / "peritext_tpu" / "store"], root=_REPO_ROOT
    )
    assert findings == [], [str(f) for f in findings]
