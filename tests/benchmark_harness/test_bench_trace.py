"""The reduction from a profiler trace to device time: busy union, idle
share, per-program device time and named idle gaps, on synthetic events
and on a small trace recorded on a TPU v5e (``tpu_batch.xplane.pb``: two
DocBatch merges of 32 docs inside ``bench.merge`` spans)."""

from pathlib import Path

import pytest

from benchmark import trace
from benchmark.trace import Event, Trace

RECORDED = Path(__file__).with_name("tpu_batch.xplane.pb")


def _synthetic():
    dev = "/device:TPU:0"
    return Trace(
        modules={dev: [Event("jit_apply_batch(1)", 1.0, 2.0),
                       Event("jit_resolve(2)", 1.5, 3.0),   # overlaps the apply
                       Event("jit_apply_batch(1)", 6.0, 7.0)]},
        ops={dev: [Event("fusion.1", 1.0, 1.8), Event("fusion.2", 6.0, 7.0)]},
        host=[Event("bench.window", 0.0, 10.0), Event("bench.merge", 0.5, 7.0),
              Event("bench.decode", 3.0, 5.5)])


def test_union_merges_overlaps():
    assert trace.union([Event("a", 1, 2), Event("b", 1.5, 3), Event("c", 4, 5)]) == [
        (1, 3), (4, 5)]


def test_busy_and_idle_share():
    tr = _synthetic()
    lo, hi = tr.window()
    assert (lo, hi) == (0.0, 10.0)
    assert trace.mean_busy_seconds(tr, lo, hi) == pytest.approx(3.0)
    assert trace.idle_share(tr, lo, hi) == pytest.approx(0.7)
    # a window that cuts an event counts only its part inside
    assert trace.busy_seconds(tr.modules["/device:TPU:0"], 2.5, 6.5) == pytest.approx(1.0)


def test_program_seconds_by_name():
    tr = _synthetic()
    assert trace.program_seconds(tr, ("jit_apply_batch",), 0, 10) == pytest.approx(2.0)
    assert trace.program_seconds(tr, ("jit_resolve",), 0, 10) == pytest.approx(1.5)
    assert trace.program_seconds(tr, ("no_such_program",), 0, 10) is None


def test_top_ops_and_named_gaps():
    tr = _synthetic()
    assert trace.top_ops(tr, 0, 10) == [["jit_apply_batch/fusion.2", 1.0],
                                        ["jit_apply_batch/fusion.1", pytest.approx(0.8)]]
    gaps = trace.idle_gaps(tr, 0, 10, tr.host)
    assert gaps[0] == ["bench.decode", pytest.approx(3.0)]  # 3 .. 6: decode overlaps most
    assert gaps[1] == ["host.other", pytest.approx(3.0)]    # 7 .. 10: no span
    assert gaps[2] == ["bench.merge", pytest.approx(1.0)]   # 0 .. 1


def test_no_device_is_an_error():
    with pytest.raises(trace.TraceError):
        trace.mean_busy_seconds(Trace(host=[Event("bench.window", 0, 1)]), 0, 1)
    with pytest.raises(trace.TraceError):
        Trace().window()


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_tpu_trace():
    tr = trace.load(str(RECORDED))
    assert list(tr.modules) == ["/device:TPU:0"]
    lo, hi = tr.window()
    assert [e.name for e in tr.host if e.name == "bench.merge"] == ["bench.merge"] * 2
    busy = trace.mean_busy_seconds(tr, lo, hi)
    assert 0 < busy < hi - lo
    apply_s = trace.program_seconds(tr, ("jit_apply_batch",), lo, hi)
    resolve_s = trace.program_seconds(tr, ("jit_resolve",), lo, hi)
    assert apply_s and resolve_s and apply_s + resolve_s <= busy + 1e-9
    # every device program of the merges falls inside its bench.merge span
    merges = [e for e in tr.host if e.name == "bench.merge"]
    for e in tr.modules["/device:TPU:0"]:
        if lo <= e.start < hi:
            assert any(m.start <= e.start and e.end <= m.end + 1e-3 for m in merges)
    top = trace.top_ops(tr, lo, hi)
    assert top and all(name.split("/")[0] in ("jit_apply_batch", "jit_resolve")
                       for name, _ in top[:3])
