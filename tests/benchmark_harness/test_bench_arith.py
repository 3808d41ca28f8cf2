"""The benchmark's own arithmetic: percentiles and rates over a window that
holds a stall, the open-loop schedule, and the roofline's byte count."""

import pytest

from benchmark import schedule, stats


def test_percentile_nearest_rank():
    values = list(range(1, 101))
    assert stats.percentile(values, 50) == 50
    assert stats.percentile(values, 95) == 95
    assert stats.percentile(values, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_tail_counts_a_stall():
    """A 2 s stall in a window of 100 frames due every 10 ms: every frame
    due during the stall waits for its end, and the tail shows it."""
    due = [i * 0.01 for i in range(100)]
    stall_end = 0.8 + 2.0
    visible = [max(d + 0.005, stall_end) if 0.8 <= d < stall_end else d + 0.005
               for d in due]
    lat = [(v - d) * 1e3 for d, v in zip(due, visible)]
    assert stats.percentile(lat, 50) == pytest.approx(5.0)
    assert stats.percentile(lat, 95) > 1000


def test_rate_over_whole_window():
    assert stats.rate(300, 3.0) == 100
    with pytest.raises(ValueError):
        stats.rate(1, 0)


def test_schedule_is_due_time_round_robin():
    sched = schedule.round_robin([5, 5, 5], rate=10, seconds=1.2)
    assert len(sched) == 12
    assert [a.due for a in sched[:3]] == [0.0, 0.1, 0.2]
    assert [a.session for a in sched[:4]] == [0, 1, 2, 0]
    assert [a.frame for a in sched if a.session == 0] == [0, 1, 2, 3]


def test_schedule_never_offers_a_frame_twice():
    sched = schedule.round_robin([9, 8, 8, 8], rate=100, seconds=0.33)
    pairs = [(a.session, a.frame) for a in sched]
    assert len(pairs) == len(set(pairs)) == 33


def test_schedule_that_cannot_cover_the_window_fails():
    with pytest.raises(schedule.ScheduleError):
        schedule.round_robin([3, 3, 2], rate=10, seconds=0.9)
    assert len(schedule.round_robin([3, 3, 2], rate=10, seconds=0.8)) == 8


def test_lateness_is_from_due_time():
    assert schedule.lateness([0.0, 0.1, 0.2], [0.0, 0.15, 0.19]) == pytest.approx(
        [0.0, 0.05, 0.0])


def test_apply_bytes_from_shapes():
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parents[2] / "benchmark/metrics/batch.apply_roofline.py"
    spec = importlib.util.spec_from_file_location("roofline_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # one doc, 384 slots, 256 marks, 384-wide insert and delete streams, 32 registers
    state = 4 * (2 * 384 + 384 + 8 * 256 + 5 * 32 + 4) + 1
    streams = 4 * (3 * 384 + 384 + 8 * 256 + 0 + 2)
    assert mod.apply_bytes(1, 384, 256, 384, 384, 32, 0) == 2 * state + streams
    assert mod.apply_bytes(10000, 384, 256, 384, 384, 32, 0) == 10000 * (2 * state + streams)


def test_roofline_reads_share_of_hbm_time():
    import importlib.util
    from pathlib import Path
    from types import SimpleNamespace

    from benchmark.trace import Event, Trace

    path = Path(__file__).resolve().parents[2] / "benchmark/metrics/batch.apply_roofline.py"
    spec = importlib.util.spec_from_file_location("roofline_under_test2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    per_merge = mod.apply_bytes(100, 384, 256, 384, 384, 32, 0)
    least = per_merge / 819e9
    tr = Trace(modules={"/device:TPU:0": [Event("jit_apply_batch", 1.0, 1.0 + 4 * least)]})
    r = SimpleNamespace(trace=tr, lo=0.0, hi=10.0, peaks={"hbm_bytes_per_s": 819e9},
                        window={"merges": 1, "docs": 100, "program": {
                            "slot_capacity": 384, "mark_capacity": 256, "op_capacity": 384}})
    assert mod.read(r) == pytest.approx(25.0)


def test_lead_in_and_window_share_no_frame():
    """The open loop's lead-in takes the first arrivals, the window the
    rest, re-timed from its own start; no frame is offered twice and each
    session's history ends where its first offered frame begins."""
    from types import SimpleNamespace

    from benchmark.drivers import open_loop

    params = {"rate_per_s": 10, "lead_in_s": 1.0}
    run = SimpleNamespace(seconds=2.0, param=params.__getitem__)
    state = {"sessions": 4, "pool": [0, 1], "frames": [[b""] * 9, [b""] * 8]}
    open_loop.plan(run, state)
    assert len(state["lead_in"]) == 10 and len(state["schedule"]) == 20
    assert state["schedule"][0].due == 0.0 and state["lead_in"][-1].due < 1.0
    pairs = [(a.session, a.frame) for a in state["lead_in"] + state["schedule"]]
    assert len(pairs) == len(set(pairs)) == 30
    assert state["offset"] == [9 - 8, 8 - 8, 9 - 7, 8 - 7]
