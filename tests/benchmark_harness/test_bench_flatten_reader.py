"""The reader of the flatten-path counters, on synthetic readings."""

import pytest

from benchmark.run import Readings, metric_reader


def readings():
    return Readings(spans=[], trace=None, lo=10.0, hi=20.0,
                    window={"merges": 2}, peaks={}, config={}, traffic={})


@pytest.fixture
def counters(monkeypatch):
    from peritext_tpu.obs import metrics

    fresh = metrics.Counters()
    monkeypatch.setattr(metrics, "GLOBAL_COUNTERS", fresh)
    return fresh


def test_native_flatten_share(counters):
    counters.add("encode.flatten.native", 255)
    counters.add("encode.flatten.python", 1)
    read = metric_reader("batch.encode.native_flatten_pct")
    assert read(readings()) == pytest.approx(100.0 * 255 / 256)


def test_native_flatten_share_without_counters_reads_nothing(counters):
    # a program without the native flatten counts neither
    assert metric_reader("batch.encode.native_flatten_pct")(readings()) is None
