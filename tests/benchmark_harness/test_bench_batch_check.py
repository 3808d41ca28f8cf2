"""The batch cells' comparison with the reference: a sound run is correct,
the control and every fault the timed path can have are not."""

import pytest

from bench_harness_helpers import rehearse  # noqa: F401  (fixture)

CELLS = ["batch_fuzz", "batch_text"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(rehearse, cell):  # noqa: F811
    line = rehearse(cell)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"merge_ops_per_s", "setup_s"}
    assert list(line)[-1] == "checks"
    assert "device" not in line and line["rehearsal"] is True


@pytest.mark.parametrize("cell", CELLS)
def test_control_comes_out_incorrect(rehearse, cell):  # noqa: F811
    line = rehearse(cell, control="stale")
    assert line["correct"] is False
    assert line["checks"]["docs_wrong"]["value"] > 0


def test_answer_altered_where_produced(rehearse, monkeypatch):  # noqa: F811
    from peritext_tpu.api import batch

    real = batch.decode_block_spans

    def altered(*args, **kwargs):
        out = real(*args, **kwargs)
        for spans in out:
            if spans:
                spans[0] = dict(spans[0], text=spans[0]["text"][::-1] + "x")
                break
        return out

    monkeypatch.setattr(batch, "decode_block_spans", altered)
    line = rehearse("batch_fuzz")
    assert line["correct"] is False
    assert line["checks"]["docs_wrong"]["value"] > 0


def test_half_the_batch_left_out(rehearse, monkeypatch):  # noqa: F811
    from peritext_tpu.api import DocBatch

    real = DocBatch.merge

    def half(self, workloads, cursors=None):
        n = len(workloads) // 2
        report = real(self, workloads[:n], cursors)
        report.spans = report.spans + [[] for _ in workloads[n:]]
        return report

    monkeypatch.setattr(DocBatch, "merge", half)
    line = rehearse("batch_fuzz")
    assert line["correct"] is False


def test_apply_that_returns_its_state_unchanged(rehearse, monkeypatch):  # noqa: F811
    from peritext_tpu.api import DocBatch
    from peritext_tpu.ops.packed import empty_docs

    def unchanged(self, encoded):
        return empty_docs(encoded.num_docs, self.slot_capacity, self.mark_capacity,
                          tomb_capacity=encoded.del_target.shape[1],
                          map_capacity=self.map_capacity)

    monkeypatch.setattr(DocBatch, "apply_encoded", unchanged)
    line = rehearse("batch_fuzz")
    assert line["correct"] is False
