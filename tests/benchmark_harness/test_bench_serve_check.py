"""The parked serve cells' comparison with the reference: a sound run is
correct, the control and every fault the timed path can have are not.

The serve cells are out of ``BENCHMARK.json`` until their configuration
has a public source for its sizes (PERF.md, Open questions); their drivers
stay, run here from a spec of their own."""

import functools

import pytest

from bench_harness_helpers import rehearse as _rehearse  # noqa: F401  (fixture)

CELLS = ["serve_fuzz_r80", "serve_catchup"]
PARKED = {
    "configs": [{"name": "peritext_serve", "file": "benchmark/configs/peritext_serve.json"}],
    "workloads": [
        {"name": "serve_fuzz_r80", "config": "peritext_serve", "traffic": "fuzz_open_r80",
         "chips": 1},
        {"name": "serve_catchup", "config": "peritext_serve", "traffic": "fuzz_catchup",
         "chips": 1},
    ],
    "end_to_end": [
        {"name": "visibility_p50_ms", "unit": "ms", "workloads": ["serve_fuzz_r80"]},
        {"name": "visibility_p95_ms", "unit": "ms", "workloads": ["serve_fuzz_r80"]},
        {"name": "catchup_ops_per_s", "unit": "ops/s", "workloads": ["serve_catchup"]},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [],
}


@pytest.fixture
def rehearse(_rehearse):  # noqa: F811
    return functools.partial(_rehearse, spec=PARKED)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(rehearse, cell):  # noqa: F811
    line = rehearse(cell)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_comes_out_incorrect(rehearse, cell):  # noqa: F811
    line = rehearse(cell, control="stale")
    assert line["correct"] is False
    assert line["checks"]["docs_wrong"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_drain_that_leaves_state_unchanged(rehearse, monkeypatch, cell):  # noqa: F811
    from peritext_tpu.parallel.streaming import StreamingMerge

    monkeypatch.setattr(StreamingMerge, "drain", lambda self, max_rounds=1000: 0)
    line = rehearse(cell)
    assert line["correct"] is False


def test_half_the_batch_left_out(rehearse, monkeypatch):  # noqa: F811
    from peritext_tpu.serve import SessionMux

    real = SessionMux._ingest_batch

    def half(self, batch):
        return real(self, batch[: len(batch) // 2])

    monkeypatch.setattr(SessionMux, "_ingest_batch", half)
    line = rehearse("serve_fuzz_r80")
    assert line["correct"] is False


def test_patch_altered_where_produced(rehearse, monkeypatch):  # noqa: F811
    from peritext_tpu.serve import SessionMux

    real = SessionMux.patches

    def altered(self, sid):
        out = real(self, sid)
        return out[:-1]

    monkeypatch.setattr(SessionMux, "patches", altered)
    line = rehearse("serve_fuzz_r80")
    assert line["correct"] is False
    assert line["checks"]["reads_wrong"]["value"] > 0


def test_read_altered_where_produced(rehearse, monkeypatch):  # noqa: F811
    from peritext_tpu.serve import SessionMux

    real = SessionMux.read

    def altered(self, sid):
        out = real(self, sid)
        return out + [{"marks": {}, "text": "x"}]

    monkeypatch.setattr(SessionMux, "read", altered)
    line = rehearse("serve_catchup")
    assert line["correct"] is False
