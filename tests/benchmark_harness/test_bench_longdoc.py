"""The book-length cell's parts: the insert-tree reference against the
plain reference, the B4-shaped generator's totals and validity, the cell's
comparison (a sound rehearsal is correct, its control is not) and the
readers of the metrics it adds."""

import random

import pytest

from bench_harness_helpers import rehearse  # noqa: F401  (fixture)
from benchmark.gen import fuzz
from benchmark.gen.editing_trace import ACTOR, Trace, history
from benchmark.reference import spans_of
from benchmark.reference.opids import HEAD, ROOT
from benchmark.reference.rga_tree import spans_of_text
from benchmark.reference.types import BEFORE, END_OF_TEXT, Boundary, Change, Operation
from benchmark.run import Readings, load_json, metric_reader, ROOT as REPO

B4 = {"inserts": 182315, "deletes": 77463}


def b4_trace(ops: int) -> Trace:
    """The cell's cursor model with B4's insert:delete ratio at ``ops``."""
    params = load_json(REPO / "benchmark/traffic/editing_trace_b4.json")["trace"]
    ins = round(ops * B4["inserts"] / (B4["inserts"] + B4["deletes"]))
    return Trace.of(dict(params, inserts=ins, deletes=ops - ins))


def edits(h):
    return [op for ch in h[ACTOR][1:] for op in ch.ops]


# -- the tree reference -----------------------------------------------------

@pytest.mark.parametrize("seed,ops", [(1, 500), (2, 1200), (3, 2000), (4, 3000),
                                      (2**31 + 7, 2600)])
def test_tree_reference_equals_spans_of_on_b4_histories(seed, ops):
    h = history(seed, b4_trace(ops))
    assert spans_of_text(h) == spans_of(h)


def _fuzz_mix(name):
    params = load_json(REPO / f"benchmark/traffic/{name}.json")["mix"]
    if name == "fuzz":
        params = dict(params, kinds=["insert", "remove"])
    return fuzz.Mix.of(params)


@pytest.mark.parametrize("mix", ["concurrent_inserts", "fuzz"])
@pytest.mark.parametrize("seed", [5, 6, 7])
def test_tree_reference_equals_spans_of_with_many_writers(mix, seed):
    h = fuzz.history(seed, 600, _fuzz_mix(mix))
    assert len(h) > 1
    assert spans_of_text(h) == spans_of(h)


def test_tree_reference_raises_on_a_mark_op():
    h = history(3, b4_trace(200))
    log = h[ACTOR]
    first = edits(h)[0].opid
    seq = len(log) + 1
    mark = Operation(action="addMark", obj=(1, ACTOR), opid=(seq, ACTOR),
                     start=Boundary(BEFORE, first), end=Boundary(END_OF_TEXT),
                     mark_type="strong")
    log.append(Change(actor=ACTOR, seq=seq, deps={ACTOR: seq - 1}, start_op=seq,
                      ops=[mark]))
    with pytest.raises(ValueError, match="addMark"):
        spans_of_text(h)


def test_typing_chain_of_200k_chars_walks_without_recursion():
    n = 200_000
    text = (1, ACTOR)
    ops = [Operation(action="makeList", obj=ROOT, opid=text, key="text")]
    ref = HEAD
    for k in range(2, n + 2):
        ops.append(Operation(action="set", obj=text, opid=(k, ACTOR), elem_id=ref,
                             insert=True, value="ab"[k % 2]))
        ref = (k, ACTOR)
    h = {ACTOR: [Change(actor=ACTOR, seq=1, deps={}, start_op=1, ops=ops)]}
    spans = spans_of_text(h)
    assert spans == [{"text": "ab" * (n // 2), "marks": {}}]


# -- the generator ----------------------------------------------------------

@pytest.mark.parametrize("ops", [2598, 9000, 26000])
def test_generator_hits_exact_totals(ops):
    trace = b4_trace(ops)
    h = history(99, trace)
    assert list(h) == [ACTOR]
    log = h[ACTOR]
    assert log[0].ops[0].action == "makeList" and len(log[0].ops) == 1
    assert all(len(ch.ops) == 1 for ch in log)
    assert [ch.seq for ch in log] == list(range(1, len(log) + 1))
    ins = sum(op.insert for op in edits(h))
    dels = sum(op.action == "del" for op in edits(h))
    assert (ins, dels) == (trace.inserts, trace.deletes)
    assert abs(dels / ins - B4["deletes"] / B4["inserts"]) < 0.01
    text = spans_of_text(h)
    assert len(text[0]["text"]) == ins - dels


def test_generator_deletes_only_visible_chars_before_the_cursor():
    """Replays the history on a visible list: an insert lands right after
    its reference, and every delete takes a visible character, the one
    before the cursor (where the last edit left it) unless a jump came
    between."""
    h = history(1234, b4_trace(5000))
    visible = []
    cursor = 0
    backspaces_in_place = 0
    for op in edits(h):
        if op.insert:
            at = 0 if op.elem_id is HEAD else visible.index(op.elem_id) + 1
            visible.insert(at, op.opid)
            cursor = at + 1
        else:
            assert op.elem_id in visible  # never from an empty text, never twice
            at = visible.index(op.elem_id)
            backspaces_in_place += at == cursor - 1
            del visible[at]
            cursor = at
    dels = sum(op.action == "del" for op in edits(h))
    # jumps come before one run in ten; runs of deletes average 4.5 chars
    assert backspaces_in_place > 0.8 * dels


def test_generator_is_deterministic_per_seed():
    trace = b4_trace(1500)
    a, b, c = history(7, trace), history(7, trace), history(8, trace)
    dump = lambda h: [ch.to_json() for ch in h[ACTOR]]  # noqa: E731
    assert dump(a) == dump(b)
    assert dump(a) != dump(c)


def test_generator_typing_and_backspace_run_lengths():
    trace = b4_trace(20000)
    runs, last, length = [], None, 0
    for op in edits(history(random.Random(3).randrange(2**40), trace)):
        if op.insert != last and last is not None:
            runs.append((last, length))
            length = 0
        last, length = op.insert, length + 1
    typing = [n for kind, n in runs if kind]
    backspace = [n for kind, n in runs if not kind]
    # a run is followed by one of its kind with p(typing) or 1 - p(typing),
    # and the two read as one: typing 12.5 / 0.54, backspace 4.5 / 0.46
    assert 20 < sum(typing) / len(typing) < 26
    assert 7 < sum(backspace) / len(backspace) < 11


# -- the cell's comparison --------------------------------------------------

def test_rehearsal_is_correct(rehearse):  # noqa: F811
    line = rehearse("batch_longdoc", seed=2**33 + 5)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"merge_ops_per_s", "setup_s"}
    assert line["checks"]["docs_fallback"]["value"] == 0


def test_stale_control_is_incorrect(rehearse):  # noqa: F811
    line = rehearse("batch_longdoc", control="stale")
    assert line["correct"] is False
    docs = line["attempted"]
    assert line["checks"]["docs_wrong"]["value"] == docs


# -- the readers of the metrics the cell adds -------------------------------

def _readings(spans=(), trace=None, merges=2):
    config = load_json(REPO / "benchmark/configs/peritext_longdoc.json")
    return Readings(spans=list(spans), trace=trace, lo=10.0, hi=20.0,
                    window={"merges": merges, "docs": 4, "program": config["program"]},
                    peaks={"hbm_bytes_per_s": 819e9}, config=config, traffic={})


@pytest.mark.parametrize("metric,span", [("batch.encode.rows_s", "batch.encode.rows"),
                                         ("batch.apply.plan_s", "batch.apply.plan")])
def test_span_readers(metric, span):
    read = metric_reader(metric)
    spans = [(span, 11.0, 11.5), (span, 15.0, 15.25), (span, 25.0, 26.0),
             ("batch.encode", 11.0, 16.0)]
    assert read(_readings(spans)) == pytest.approx(0.375)
    assert read(_readings([("batch.encode", 11.0, 16.0)])) is None


def _trace(*modules):
    from benchmark.trace import Event, Trace

    return Trace(modules={"/device:TPU:0": [Event(n, a, b) for n, a, b in modules]},
                 host=[Event("bench.window", 10.0, 20.0)])


def test_ragged_apply_readers_take_only_the_ragged_program():
    tr = _trace(("jit_apply_batch_ragged(77)", 11.0, 13.0),
                ("jit_apply_batch(12)", 13.0, 14.0),
                ("jit_resolve(3)", 14.0, 14.5),
                ("jit_apply_batch_ragged(77)", 15.0, 16.0))
    r = _readings(trace=tr, merges=2)
    assert metric_reader("batch.ragged_apply_device_ms")(r) == pytest.approx(1500.0)
    share = metric_reader("batch.ragged_apply_roofline")(r)
    least = 2 * 4 * 4 * (5 * B4["inserts"] + 2 * B4["deletes"]) / 819e9
    assert share == pytest.approx(100.0 * least / 3.0)
    assert 0 < share < 100


def test_ragged_apply_readers_without_the_program_read_nothing():
    r = _readings(trace=_trace(("jit_apply_batch(12)", 11.0, 12.0)))
    assert metric_reader("batch.ragged_apply_device_ms")(r) is None
    assert metric_reader("batch.ragged_apply_roofline")(r) is None


def test_ragged_apply_jit_name_is_the_readers():
    """The name the trace prints for the ragged apply is the jit's module
    name; lowering a tiny apply pins it."""
    import jax.numpy as jnp
    import numpy as np

    from peritext_tpu.ops.encode import encode_doc_streams, pad_doc_streams
    from peritext_tpu.ops.ragged import _apply_batch_ragged_jit, plan_arrays, stream_counts
    from peritext_tpu.store.paged import PagedDocStore, group_stream_arrays
    from peritext_tpu.store.ragged import ragged_plan
    from peritext_tpu.testing.fuzz import generate_workload

    enc = pad_doc_streams(*encode_doc_streams(generate_workload(3, num_docs=2,
                                                                ops_per_doc=10)))
    ins = stream_counts(enc)
    store = PagedDocStore(2, 256, 16, page_size=128)
    store.ensure_rows(np.arange(2), np.asarray(ins, np.int64))
    lowered = _apply_batch_ragged_jit.lower(
        store.pool_elem, store.pool_char, store.aux, *plan_arrays(ragged_plan(store)),
        group_stream_arrays(enc, None, 2), jnp.asarray(ins),
        ragged_impl="lax")
    assert "module @jit_apply_batch_ragged " in lowered.as_text()
    for name in ("batch.ragged_apply_device_ms", "batch.ragged_apply_roofline"):
        assert metric_reader(name).__globals__["RAGGED_APPLY"] == ("jit_apply_batch_ragged",)
