"""The reader of the ragged insert kernel's typing-run counters, on
synthetic readings."""

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


def test_run_insert_share(counters):
    counters.add("merge.ragged_inserts", 729260)
    counters.add("merge.ragged_run_inserts", 695020)
    read = metric_reader("batch.apply.run_insert_pct")
    assert read(readings()) == pytest.approx(100.0 * 695020 / 729260)


def test_run_insert_share_without_counters_reads_nothing(counters):
    # a padded merge, or a program without the counters, counts neither
    assert metric_reader("batch.apply.run_insert_pct")(readings()) is None
