"""Fused device-resident round pipeline (ISSUE 9): byte-equality of the
fused vs per-round dispatch disciplines across BOTH storage layouts, the
zero-recompile steady-state contract for the fused programs, donation
semantics, the drain-end digest prefetch, and the staging lane itself.

The equivalence oracle is the compat switch ``fused_pipeline=False``: it
restores the pre-fusion per-round dispatch (one compact apply per round,
per-round device_put staging, unpipelined drain) — fusion is the same apply
sequence staged and traced together, so every observable (spans, incremental
patches, full-state digests, round counts) must be indistinguishable."""

import random

import numpy as np
import pytest

import jax

from peritext_tpu.parallel.codec import encode_frame
from peritext_tpu.parallel.staging import FrameStager
from peritext_tpu.parallel.streaming import StreamingMerge
from peritext_tpu.testing.fuzz import generate_workload

ACTORS = ("doc1", "doc2", "doc3")


def _session(layout="padded", static_rounds=False, num_docs=6, fused=True,
             caps=(8, 8, 8, 8)):
    # one shared config across this module ON PURPOSE: the width buckets
    # collapse to a single signature (caps == the bucket floor), so every
    # test reuses the same compiled fused programs — the module stays
    # seconds, not minutes, and the zero-recompile test still proves the
    # steady state (its assertion is on the WARM run only)
    # small resident shapes: per-variant XLA compile time scales with the
    # program (slot window x mark table), and this module's cost is almost
    # entirely first-compiles of the per-seed (K, lens) signatures
    s = StreamingMerge(
        num_docs=num_docs,
        actors=ACTORS,
        slot_capacity=64,
        mark_capacity=48,
        tomb_capacity=48,
        round_insert_capacity=caps[0],
        round_delete_capacity=caps[1],
        round_mark_capacity=caps[2],
        round_map_capacity=caps[3],
        static_rounds=static_rounds,
        layout=layout,
        # a page must divide the tiny slot capacity
        **({} if layout == "padded" else {"page_size": 64}),
    )
    s.fused_pipeline = fused
    # narrow fuse window: drains split into SEVERAL staged batches (more
    # pipeline coverage per op) while the chained program bodies stay
    # small — the XLA compile bill is per (K, lens) signature and K <= 2
    # keeps the variant set tiny
    s.FUSE_MAX_ROUNDS = 2
    return s


def _feed(s, workloads, rng, chunks=3, per_round_steps=False,
          prefetch=False):
    """Ingest each doc's log as ``chunks`` wire frames with interleaved
    drains — fused sessions drain pipelined, oracle sessions step per
    round."""
    s.prefetch_digest = prefetch
    plans = []
    for w in workloads:
        ch = [c for a in sorted(w) for c in w[a]]
        rng.shuffle(ch)
        size = -(-len(ch) // chunks)
        plans.append([ch[i:i + size] for i in range(0, len(ch), size)])
    for r in range(chunks):
        s.ingest_frames(
            (d, encode_frame(sorted(p[r], key=lambda c: (c.actor, c.seq))))
            for d, p in enumerate(plans) if r < len(p)
        )
        if per_round_steps:
            while s.step() > 0:
                pass
        else:
            s.drain()
    return s


@pytest.mark.parametrize("layout", ["padded", "paged"])
@pytest.mark.parametrize("seed", [
    11,
    # extra fuzz seeds ride the slow tier (each seed's arrival shapes mint
    # their own XLA variants — ~10 s/seed of pure compile); the CI
    # fused-smoke job sweeps two more seeds on every push, and the bench
    # row asserts equality on three per run
    pytest.param(203, marks=pytest.mark.slow),
    pytest.param(47, marks=pytest.mark.slow),
])
def test_fused_equals_per_round_across_layouts(layout, seed):
    """Fuzz-seed byte-equality of the fused pipeline vs the per-round
    dispatch oracle, padded AND paged: digests (full state), spans,
    incremental patch streams, committed round counts."""
    workloads = generate_workload(seed=seed, num_docs=6, ops_per_doc=40)
    fused = _feed(_session(layout), workloads, random.Random(seed),
                  prefetch=True)
    oracle = _feed(_session(layout, fused=False), workloads,
                   random.Random(seed), per_round_steps=True)
    assert fused.rounds == oracle.rounds
    assert fused.digest() == oracle.digest()
    assert fused.read_all() == oracle.read_all()
    assert fused.read_patches_all() == oracle.read_patches_all()
    assert fused.rounds > 1  # low caps force real multi-round fusion


def test_static_rounds_fused_parity():
    """The serving shape discipline rides the fused pipeline through the
    STACKED fixed-width program: byte equality with the per-round static
    path, and the committed apply keeps the session's configured widths
    (the one-shape contract)."""
    workloads = generate_workload(seed=7, num_docs=6, ops_per_doc=40)
    fused = _feed(_session(static_rounds=True, caps=(24, 12, 12, 8)),
                  workloads, random.Random(7))
    oracle = _feed(_session(static_rounds=True, caps=(24, 12, 12, 8),
                            fused=False),
                   workloads, random.Random(7), per_round_steps=True)
    assert fused.digest() == oracle.digest()
    assert fused.read_all() == oracle.read_all()
    assert fused.rounds == oracle.rounds


def test_fused_pipeline_zero_recompiles_on_repeat_workload(recompile_sentinel):
    """The fused pipeline adds ZERO compiles on a repeat workload: a fresh
    session serving the same arrival shapes again dispatches only
    already-compiled fused programs (staged multi-round apply, fused
    resolve+digest prefetch included)."""

    def fresh():
        return _session()

    workloads = generate_workload(seed=31, num_docs=6, ops_per_doc=36)
    cold = _feed(fresh(), workloads, random.Random(3), prefetch=True)
    cold_spans = cold.read_all()
    cold_digest = cold.digest()

    recompile_sentinel.mark()
    warm = _feed(fresh(), workloads, random.Random(3), prefetch=True)
    warm_digest = warm.digest()
    recompile_sentinel.assert_steady_state("fused pipeline repeat workload")
    assert warm.read_all() == cold_spans
    assert warm_digest == cold_digest


def test_prefetch_digest_matches_plain_digest():
    """The drain-end fused resolve+digest pre-dispatch is an overlap
    optimization, not a semantics change: digest() after a prefetching
    drain equals a non-prefetching twin bit-for-bit, including after
    further ingest+drain cycles."""
    workloads = generate_workload(seed=91, num_docs=6, ops_per_doc=32)
    a = _feed(_session(), workloads, random.Random(1), prefetch=True)
    b = _feed(_session(), workloads, random.Random(1), prefetch=False)
    assert a.digest() == b.digest()
    assert a.digest(refresh=True) == b.digest()


def test_drain_end_digest_chains_into_final_staged_batch():
    """Round-14 rung: with the prefetch armed, a multi-round drain's FINAL
    staged batch carries the resolve+digest in ITS OWN program — no
    separate prefetch dispatch.  Pinned three ways: the chained counter
    moves, the per-round block cache is already seeded when drain()
    returns (so digest() is a pure cache hit), and the digest stays
    byte-equal to the unchained per-round oracle."""
    from peritext_tpu.obs import GLOBAL_COUNTERS

    workloads = generate_workload(seed=55, num_docs=6, ops_per_doc=36)
    before = GLOBAL_COUNTERS.get("streaming.digest_chained")
    fused = _feed(_session(), workloads, random.Random(9), prefetch=True)
    assert GLOBAL_COUNTERS.get("streaming.digest_chained") > before
    # the final batch's dispatch seeded the resolution cache at the
    # current round stamp: the block program need not run again
    stamp, cache = fused._resolved_cache
    assert stamp == fused.rounds and 0 in cache
    entry = cache[0]
    digest = fused.digest()
    # digest() consumed the SEEDED entry (same object — no re-dispatch)
    assert fused._resolved_cache[1][0] is entry
    oracle = _feed(_session(fused=False), workloads, random.Random(9),
                   per_round_steps=True)
    assert digest == oracle.digest()
    assert fused.read_all() == oracle.read_all()


def test_drain_end_digest_chains_on_stacked_serving_form():
    """The static-rounds serving discipline chains too (the stacked
    fixed-width program grows a digest tail), with the same byte
    equality."""
    from peritext_tpu.obs import GLOBAL_COUNTERS

    workloads = generate_workload(seed=23, num_docs=6, ops_per_doc=36)
    before = GLOBAL_COUNTERS.get("streaming.digest_chained")
    fused = _feed(_session(static_rounds=True, caps=(24, 12, 12, 8)),
                  workloads, random.Random(4), prefetch=True)
    assert GLOBAL_COUNTERS.get("streaming.digest_chained") > before
    oracle = _feed(_session(static_rounds=True, caps=(24, 12, 12, 8),
                            fused=False),
                   workloads, random.Random(4), per_round_steps=True)
    assert fused.digest() == oracle.digest()
    assert fused.read_all() == oracle.read_all()


def test_staged_rounds_donation_consumes_input_state():
    """Donation semantics of the fused apply program: with donate=True the
    input state buffer is consumed (further reads raise), and the result is
    bit-identical to the undonated twin."""
    from peritext_tpu.ops.encode import MAP_STREAM_COLS, MARK_COLS
    from peritext_tpu.ops.kernel import apply_batch_staged_rounds_jit
    from peritext_tpu.ops.packed import empty_docs

    d = 4
    counts_all = np.zeros((1, 4, d), np.int32)
    counts_all[0, 0] = 2
    ins = [np.zeros(8, np.int32) for _ in range(3)]
    # two head inserts per doc: ref=0, ascending op ids, char payloads
    ops = np.arange(1, 2 * d + 1, dtype=np.int32)
    ins[1][: 2 * d] = ops
    ins[2][: 2 * d] = 65 + (ops % 26)
    dev = jax.device_put((
        counts_all, tuple(ins), np.zeros(8, np.int32),
        {c: np.zeros(8, np.int32) for c in MARK_COLS},
        {c: np.zeros(8, np.int32) for c in MAP_STREAM_COLS},
    ))
    statics = dict(widths_seq=((8, 8, 8, 8),), loop_slots_seq=(8,),
                   ins_lens=(8,), del_lens=(8,), mark_lens=(8,),
                   map_lens=(8,))

    plain_in = jax.device_put(empty_docs(d, 16, 8, tomb_capacity=8))
    plain = apply_batch_staged_rounds_jit(plain_in, *dev, donate=False,
                                          **statics)
    donated_in = jax.device_put(empty_docs(d, 16, 8, tomb_capacity=8))
    donated = apply_batch_staged_rounds_jit(donated_in, *dev, donate=True,
                                            **statics)
    for a, b in zip(plain, donated):
        assert (np.asarray(a) == np.asarray(b)).all()
    with pytest.raises(RuntimeError):
        np.asarray(donated_in.elem_id)  # the donated buffer is dead


def test_cpu_resolves_to_undonated_dispatch():
    """On a CPU backend the fused programs must NOT donate: a donated
    dispatch blocks on the donated input's pending producer there,
    serializing the host/device overlap the pipeline exists for."""
    from peritext_tpu.ops.kernel import resolve_state_donation

    assert resolve_state_donation(platform="cpu") is False
    assert resolve_state_donation(platform="tpu") is True


# ---------------------------------------------------------------------------
# the staging lane itself
# ---------------------------------------------------------------------------


class TestFrameStager:
    def test_fifo_results(self):
        st = FrameStager()
        try:
            handles = [st.submit(lambda i=i: i * i) for i in range(8)]
            assert [h.wait() for h in handles] == [i * i for i in range(8)]
            assert st.stats()["staged"] == 8
        finally:
            st.close()

    def test_error_propagates_to_waiter(self):
        st = FrameStager()
        try:
            def boom():
                raise ValueError("staging failed")

            ok = st.submit(lambda: 1)
            bad = st.submit(boom)
            after = st.submit(lambda: 2)
            assert ok.wait() == 1
            with pytest.raises(ValueError, match="staging failed"):
                bad.wait()
            # one failed job must not kill the lane
            assert after.wait() == 2
            assert st.stats()["errors"] == 1
        finally:
            st.close()

    def test_close_is_idempotent_and_rejects_new_jobs(self):
        st = FrameStager()
        h = st.submit(lambda: 42)
        assert h.wait() == 42
        st.close()
        st.close()
        with pytest.raises(RuntimeError):
            st.submit(lambda: 0)

    def test_depth_validation(self):
        with pytest.raises(ValueError):
            FrameStager(depth=0)

    def test_session_respawns_closed_stager(self):
        s = _session(num_docs=2)
        lane = s._ensure_stager()
        lane.close()
        assert s._ensure_stager() is not lane

    def test_idle_retired_worker_respawns_on_submit(self, monkeypatch):
        # the worker self-reaps after IDLE_TIMEOUT_SECONDS; a later submit
        # must respawn it and resolve — submit publishes the job BEFORE
        # the worker check, so the retire/submit race can never strand a
        # staged job on a worker-less lane
        import time

        from peritext_tpu.parallel import staging

        monkeypatch.setattr(staging, "IDLE_TIMEOUT_SECONDS", 0.05)
        lane = FrameStager()
        assert lane.submit(lambda: 1).wait() == 1
        deadline = time.time() + 5.0
        while lane._thread is not None and time.time() < deadline:
            time.sleep(0.01)
        assert lane._thread is None  # worker retired while idle
        assert lane.submit(lambda: 2).wait() == 2
        assert lane.stats()["staged"] == 2


class TestDrainDeadlineScaling:
    """The guarded fused drain's watchdog budget scales with the backlog:
    deadline_ceiling per staged batch, batches estimated from the deepest
    per-doc pending queue — a deep healthy drain is not a hung device."""

    def _frames(self, seed=31, num_docs=4, ops_per_doc=24):
        workloads = generate_workload(seed=seed, num_docs=num_docs,
                                      ops_per_doc=ops_per_doc)
        out = []
        for d, w in enumerate(workloads):
            ch = sorted((c for a in sorted(w) for c in w[a]),
                        key=lambda c: (c.actor, c.seq))
            out.append((d, encode_frame(ch)))
        return out

    def test_pending_rounds_estimate_tracks_deepest_queue(self):
        s = _session(num_docs=4)
        assert s.pending_rounds_estimate() == 0
        s.ingest_frames(self._frames())
        assert s.pending_rounds_estimate() >= 1
        s.drain()
        assert s.pending_rounds_estimate() == 0

    def test_guarded_drain_budget_scales_with_backlog(self, tmp_path):
        from peritext_tpu.parallel.supervisor import GuardedSession

        guarded = GuardedSession(lambda: _session(num_docs=4), tmp_path,
                                 deadline=30.0)
        # empty backlog: exactly one ceiling
        assert guarded._drain_deadline(1000) == guarded.deadline_ceiling
        for d, frame in self._frames():
            guarded.ingest_frame(d, frame)
        est = guarded.session.pending_rounds_estimate()
        assert est > guarded.session.FUSE_MAX_ROUNDS  # deep enough to scale
        batches = -(-min(est, 1000) // guarded.session.FUSE_MAX_ROUNDS)
        assert guarded._drain_deadline(1000) == pytest.approx(
            guarded.deadline_ceiling * batches)
        # max_rounds clamps the budget back to one batch
        assert guarded._drain_deadline(1) == guarded.deadline_ceiling
        assert guarded.drain() > 0  # and the scaled drain commits cleanly
