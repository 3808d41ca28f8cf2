"""Spans inside host encode (sort, split, pad), the causal scheduler's
path counters and native-build span, the garbage-collection spans, and
program spans on the profiler's clock."""

import gc
import glob
import hashlib
import logging
import os
import subprocess

import numpy as np
import pytest

from peritext_tpu import native
from peritext_tpu.api import DocBatch
from peritext_tpu.obs import GLOBAL_COUNTERS, GLOBAL_TRACER, Tracer, current_span
from peritext_tpu.ops.encode import MAP_STREAM_COLS, MARK_COLS, encode_workloads
from peritext_tpu.parallel import causal
from peritext_tpu.testing.fuzz import generate_workload
from peritext_tpu.testing.generate import generate_docs

STAGES = ["batch.encode.sort", "batch.encode.split", "batch.encode.pad"]


def _digest(enc) -> str:
    """Every array, table and fallback doc of an EncodedBatch, hashed."""
    h = hashlib.sha256()
    arrays = (enc.ins_ref, enc.ins_op, enc.ins_char, enc.del_target,
              *(enc.marks[c] for c in MARK_COLS), enc.mark_count,
              *(enc.map_ops[c] for c in MAP_STREAM_COLS), enc.map_count,
              enc.num_ops)
    for a in arrays:
        h.update(repr(a.shape).encode())
        h.update(np.ascontiguousarray(a, np.int32).tobytes())
    for tables in (enc.actor_tables, enc.attr_tables, enc.map_tables):
        h.update(repr([[t.lookup(i) for i in range(len(t))]
                       for t in tables]).encode())
    h.update(repr(enc.fallback_docs).encode())
    return h.hexdigest()


def _float_value_doc():
    """A doc the device path cannot express: a float map value."""
    docs, _, initial = generate_docs("ab", 1)
    c, _ = docs[0].change([{"path": [], "action": "set", "key": "r", "value": 0.5}])
    return {"doc1": [initial, c]}


def _mixed_workloads():
    w = generate_workload(seed=99, num_docs=4, ops_per_doc=50)
    return [w[0], _float_value_doc(), *w[1:]]


#: name -> (workloads, capacities, recorded digest, recorded fallback docs);
#: the digests were recorded from the per-doc Python encode loop
ENCODE_CASES = {
    "seed7": (
        lambda: generate_workload(seed=7, num_docs=12, ops_per_doc=60), {},
        "635bffa4f72357f3e1c10161b6ce40f1449934b43bed2431ab4f59b58ca4f89b", []),
    "seed1234_capped": (
        lambda: generate_workload(seed=1234, num_docs=8, ops_per_doc=80),
        {"mark_capacity": 24, "insert_capacity": 32},
        "38fcc5e1f88b64fe152db5933d089364f4a0cc40aa8efc077c102dc84160038c",
        [0, 6, 7]),
    "seed99_mixed": (
        _mixed_workloads, {"delete_capacity": 17},
        "c16552620586e446489b41d17ebbca52ea013732ff651a4d7d95f8af7d4ae518",
        [1, 2]),
}


@pytest.mark.parametrize("case", sorted(ENCODE_CASES))
def test_stage_major_encode_matches_recorded(case):
    make, caps, digest, fallback = ENCODE_CASES[case]
    enc = encode_workloads(make(), **caps)
    assert enc.fallback_docs == fallback
    assert _digest(enc) == digest


class _Sink:
    def __init__(self):
        self.spans = []

    def __call__(self, span):
        self.spans.append(span)

    def named(self, name):
        return [s for s in self.spans if s.name == name]


@pytest.fixture
def sink():
    s = _Sink()
    GLOBAL_TRACER.add_sink(s)
    try:
        yield s
    finally:
        GLOBAL_TRACER.remove_sink(s)


needs_native = pytest.mark.skipif(not native.available(), reason="needs the native core")
LAYOUTS = ["padded", "ragged"]
#: the encode path as a test parameter: the native one under the plain id,
#: the per-doc Python one (a failed native build) under "<id>-python"
PATHS = [pytest.param("native", id="native", marks=needs_native),
         pytest.param("python", id="python")]

#: the encode's stage spans (names, doc args) for 3 docs, by path and
#: layout.  With the native core: a flatten (split) per doc, the allocation
#: (pad), one schedule and scatter (sort) and the tables (pad), and the
#: ragged layout reads each doc's rows back (rows) and pads the batch after
#: that.  In Python: a sort then a split per doc, then one pad.
_NATIVE_PADDED = ([STAGES[1]] * 3 + [STAGES[2], STAGES[0], STAGES[2]],
                  [0, 1, 2, None, None, None])
_NATIVE_RAGGED = (_NATIVE_PADDED[0] + ["batch.encode.rows", STAGES[2]],
                  _NATIVE_PADDED[1] + [None, None])
_PYTHON = (STAGES[:2] * 3 + STAGES[2:], [0, 0, 1, 1, 2, 2, None])
STAGE_SPANS = {
    "native": {"padded": _NATIVE_PADDED, "ragged": _NATIVE_RAGGED},
    "python": dict.fromkeys(LAYOUTS, _PYTHON),
}


def _use_path(path, monkeypatch):
    if path == "python":
        monkeypatch.setattr(native, "available", lambda: False)


def _merge_stages(layout):
    sink = _Sink()
    tracer = Tracer(host="encode-stages")
    tracer.add_sink(sink)
    workloads = generate_workload(seed=7, num_docs=3, ops_per_doc=30)
    DocBatch(slot_capacity=128, mark_capacity=64, layout=layout,
             page_size=32, jit=False, tracer=tracer).merge(workloads)
    (encode,) = sink.named("batch.encode")
    stages = [s for s in sink.spans if s.name.startswith("batch.encode.")]
    assert all(s.parent_id == encode.span_id for s in stages)
    assert sum(s.duration for s in stages) <= encode.duration
    return [s.name for s in stages], [s.args.get("doc") for s in stages]


@pytest.mark.parametrize("layout,path", [
    *(pytest.param(layout, "native", id=layout, marks=needs_native) for layout in LAYOUTS),
    *(pytest.param(layout, "python", id=f"{layout}-python") for layout in LAYOUTS),
])
def test_merge_opens_encode_stage_spans_under_encode(layout, path, monkeypatch):
    _use_path(path, monkeypatch)
    assert _merge_stages(layout) == STAGE_SPANS[path][layout]


@pytest.mark.parametrize("path", PATHS)
def test_stage_spans_carry_counts(path, monkeypatch):
    _use_path(path, monkeypatch)
    sink = _Sink()
    tracer = Tracer(host="encode-counts")
    tracer.add_sink(sink)
    workloads = generate_workload(seed=7, num_docs=3, ops_per_doc=30)
    encode_workloads(workloads, tracer=tracer)
    sorts, splits = sink.named(STAGES[0]), sink.named(STAGES[1])
    changes = [sum(map(len, w.values())) for w in workloads]
    ops = [sum(len(ch.ops) for log in w.values() for ch in log) for w in workloads]
    if path == "native":
        # one schedule and scatter for the batch, a flatten per doc
        assert [s.args for s in sorts] == [{"docs": 3, "changes": sum(changes)}]
        assert [s.args for s in splits] == [
            {"doc": d, "changes": changes[d], "ops": ops[d], "rows": True}
            for d in range(3)]
    else:
        # a causal sort and a stream split per doc
        assert [s.args for s in sorts] == [
            {"doc": d, "changes": changes[d]} for d in range(3)]
        assert [s.args for s in splits] == [{"doc": d, "ops": ops[d]} for d in range(3)]


@needs_native
def test_batch_encode_counts_a_native_schedule_per_doc(monkeypatch):
    workloads = generate_workload(seed=7, num_docs=3, ops_per_doc=30)
    before = (GLOBAL_COUNTERS.get("causal.schedules.native"),
              GLOBAL_COUNTERS.get("causal.schedules.python"))
    encode_workloads(workloads)
    assert (GLOBAL_COUNTERS.get("causal.schedules.native"),
            GLOBAL_COUNTERS.get("causal.schedules.python")) == (before[0] + 3, before[1])
    monkeypatch.setattr(native, "available", lambda: False)
    encode_workloads(workloads)  # 3 docs of under 64 changes each
    assert GLOBAL_COUNTERS.get("causal.schedules.python") == before[1] + 3


def test_gc_collection_is_a_host_gc_span(sink):
    gc.collect()
    full = [s for s in sink.named("host.gc") if s.args["generation"] == 2]
    assert len(full) == 1
    assert full[0].args["collected"] >= 0
    assert full[0].duration >= 0


def test_gc_hook_records_nothing_while_tracer_inactive(monkeypatch):
    assert not GLOBAL_TRACER.active()
    recorded = []
    monkeypatch.setattr(GLOBAL_TRACER, "record",
                        lambda *a, **k: recorded.append(a))
    gc.collect()
    assert recorded == []


def test_record_does_not_touch_the_span_stack():
    sink = _Sink()
    tracer = Tracer(host="record")
    tracer.add_sink(sink)
    with tracer.span("outer") as outer:
        tracer.record("finished", 0.0, 0.25, k=1)
        assert current_span() is outer
    (done,) = sink.named("finished")
    assert done.parent_id == outer.span_id
    assert done.duration == 0.25 and done.args == {"k": 1}


def _sortable_changes():
    return [ch for w in generate_workload(seed=7, num_docs=1, ops_per_doc=200)
            for log in w.values() for ch in log]


def test_python_scheduler_is_counted(monkeypatch):
    changes = _sortable_changes()
    assert len(changes) >= causal._NATIVE_THRESHOLD
    monkeypatch.setattr(native, "available", lambda: False)
    before = GLOBAL_COUNTERS.get("causal.schedules.python")
    causal.causal_sort(changes)
    assert GLOBAL_COUNTERS.get("causal.schedules.python") == before + 1


@needs_native
def test_native_scheduler_is_counted():
    before = GLOBAL_COUNTERS.get("causal.schedules.native")
    causal.causal_sort(_sortable_changes())
    assert GLOBAL_COUNTERS.get("causal.schedules.native") == before + 1


def test_failed_native_build_is_a_span_and_one_warning(monkeypatch, tmp_path,
                                                       caplog, sink):
    def fail(cmd, **kwargs):
        raise subprocess.CalledProcessError(1, cmd, stderr=b"no g++ here")

    monkeypatch.setattr(native, "_BUILD_DIR", tmp_path)
    monkeypatch.setattr(native.subprocess, "run", fail)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_build_error", None)
    monkeypatch.setattr(causal, "_warned", False)
    monkeypatch.delenv("PERITEXT_TPU_NO_NATIVE", raising=False)
    changes = _sortable_changes()
    with caplog.at_level(logging.WARNING, logger=causal.__name__):
        causal.causal_sort(changes)
        causal.causal_sort(changes)
    (build,) = sink.named("native.build")
    assert "no g++ here" in build.args["error"]
    warnings = [r for r in caplog.records if r.name == causal.__name__]
    assert len(warnings) == 1
    assert "no g++ here" in warnings[0].getMessage()


@needs_native
def test_failed_native_build_encodes_in_python_alike(monkeypatch, tmp_path,
                                                       caplog):
    make, caps, digest, fallback = ENCODE_CASES["seed99_mixed"]
    native_digest = _digest(encode_workloads(make(), **caps))

    def fail(cmd, **kwargs):
        raise subprocess.CalledProcessError(1, cmd, stderr=b"no g++ here")

    monkeypatch.setattr(native, "_BUILD_DIR", tmp_path)
    monkeypatch.setattr(native.subprocess, "run", fail)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_build_error", None)
    monkeypatch.setattr(causal, "_warned", False)
    monkeypatch.delenv("PERITEXT_TPU_NO_NATIVE", raising=False)
    with caplog.at_level(logging.WARNING, logger=causal.__name__):
        digests = [_digest(encode_workloads(make(), **caps)) for _ in range(2)]
    assert digests == [native_digest, native_digest] == [digest, digest]
    warnings = [r for r in caplog.records if r.name == causal.__name__]
    assert len(warnings) == 1
    assert "no g++ here" in warnings[0].getMessage()


def test_program_spans_land_on_the_profiler_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData

    workloads = generate_workload(seed=7, num_docs=2, ops_per_doc=20)
    jax.profiler.start_trace(str(tmp_path))
    try:
        DocBatch(slot_capacity=128, mark_capacity=64, jit=False).merge(workloads)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"batch.merge", "batch.encode", *STAGES} <= names
