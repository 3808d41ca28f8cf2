"""The readers of the host-encode stage spans, the collector spans and the
scheduler-path counters, on synthetic readings; and the device program
names that the device-trace readers match."""

import pytest

from benchmark.metrics import _programs
from benchmark.run import Readings, metric_reader

SPAN_READERS = {
    "batch.encode.sort_s": "batch.encode.sort",
    "batch.encode.split_s": "batch.encode.split",
    "batch.encode.pad_s": "batch.encode.pad",
    "host.gc_s": "host.gc",
}


def readings(spans, merges=2):
    return Readings(spans=spans, trace=None, lo=10.0, hi=20.0,
                    window={"merges": merges}, peaks={}, config={}, traffic={})


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_reader_sums_window_spans_per_merge(metric):
    span = SPAN_READERS[metric]
    spans = [
        (span, 11.0, 11.5),            # in the window
        (span, 15.0, 15.25),           # in the window
        (span, 25.0, 26.0),            # after it
        ("batch.encode", 11.0, 16.0),  # another span
    ]
    assert metric_reader(metric)(readings(spans, merges=2)) == pytest.approx(0.375)


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_reader_without_its_spans_reads_nothing(metric):
    spans = [("batch.encode", 11.0, 16.0), (SPAN_READERS[metric], 1.0, 2.0)]
    assert metric_reader(metric)(readings(spans)) is None


@pytest.fixture
def counters(monkeypatch):
    from peritext_tpu.obs import metrics

    fresh = metrics.Counters()
    monkeypatch.setattr(metrics, "GLOBAL_COUNTERS", fresh)
    return fresh


def test_native_sort_share(counters):
    counters.add("causal.schedules.native", 3)
    counters.add("causal.schedules.python")
    read = metric_reader("batch.encode.native_sort_pct")
    assert read(readings([])) == pytest.approx(75.0)


def test_native_sort_share_without_counters_reads_nothing(counters):
    assert metric_reader("batch.encode.native_sort_pct")(readings([])) is None


def _tiny_state():
    from peritext_tpu.api import DocBatch
    from peritext_tpu.ops.kernel import encoded_arrays_of
    from peritext_tpu.ops.packed import empty_docs
    from peritext_tpu.testing.fuzz import generate_workload

    batch = DocBatch(slot_capacity=64, mark_capacity=32, comment_capacity=8)
    enc = batch.encode(generate_workload(seed=3, num_docs=2, ops_per_doc=10))
    arrays = encoded_arrays_of(enc)
    state = empty_docs(enc.num_docs, 64, 32, tomb_capacity=arrays[3].shape[1],
                       map_capacity=batch.map_capacity)
    return state, arrays


def _lower_apply():
    from peritext_tpu.ops import kernel

    state, arrays = _tiny_state()
    impl = kernel.resolve_insert_impl(state.elem_id)
    return kernel._apply_batch_jit.lower(state, arrays, insert_impl=impl,
                                         insert_loop_slots=None)


def _lower_resolve():
    from peritext_tpu.ops.resolve import resolve_jit

    state, _ = _tiny_state()
    return resolve_jit.lower(state, 8)


@pytest.mark.parametrize("lower, names, module", [
    (_lower_apply, _programs.APPLY, "jit_apply_batch"),
    (_lower_resolve, _programs.RESOLVE, "jit_resolve"),
], ids=["apply", "resolve"])
def test_device_program_names_the_readers_match(lower, names, module):
    text = lower().as_text()
    assert f"module @{module} " in text
    assert module in names
