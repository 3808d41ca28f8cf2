"""The columnar batch encode (one flatten per doc, one native schedule and
scatter for the batch) against the per-doc Python encode: every array,
table and fallback doc equal, byte for byte; and the native flatten
against the Python one, column for column."""

import random

import pytest

from benchmark.drivers._pool import to_program
from benchmark.gen.editing_trace import Trace, history
from benchmark.run import ROOT as BENCH_ROOT
from benchmark.run import load_json, metric_reader
from peritext_tpu import native
from peritext_tpu.core.errors import PeritextError
from peritext_tpu.core.opids import HEAD, ROOT
from peritext_tpu.core.types import AFTER, BEFORE, Boundary, Change, Operation
from peritext_tpu.obs import GLOBAL_COUNTERS, Tracer, metrics
from peritext_tpu.ops import encode
from peritext_tpu.ops.encode import (
    MAP_STREAM_COLS,
    MARK_COLS,
    encode_doc_streams,
    encode_workloads,
)
from peritext_tpu.ops.packed import MAX_ACTORS, MAX_CTR
from peritext_tpu.parallel import causal
from peritext_tpu.testing.fuzz import generate_markheavy_workload, generate_workload
from peritext_tpu.testing.generate import generate_docs

pytestmark = pytest.mark.skipif(not native.available(), reason="needs the native core")


def _tables(tables):
    return [[t.lookup(i) for i in range(len(t))] for t in tables]


def _assert_same_batch(got, want):
    arrays = lambda e: {  # noqa: E731
        "ins_ref": e.ins_ref, "ins_op": e.ins_op, "ins_char": e.ins_char,
        "del_target": e.del_target, "mark_count": e.mark_count,
        "map_count": e.map_count, "num_ops": e.num_ops,
        **{c: e.marks[c] for c in MARK_COLS},
        **{c: e.map_ops[c] for c in MAP_STREAM_COLS},
    }
    for name, want_a in arrays(want).items():
        got_a = arrays(got)[name]
        assert got_a.dtype == want_a.dtype and got_a.shape == want_a.shape, name
        assert got_a.flags["C_CONTIGUOUS"], name
        assert got_a.tobytes() == want_a.tobytes(), name
    assert got.fallback_docs == want.fallback_docs
    for tables in ("actor_tables", "attr_tables", "map_tables"):
        assert _tables(getattr(got, tables)) == _tables(getattr(want, tables)), tables


def _python_encode(monkeypatch, fn, *args, **kwargs):
    with monkeypatch.context() as m:
        m.setattr(encode, "native_loaded", lambda: False)
        return fn(*args, **kwargs)


def _change(actor, seq, deps, ops):
    return Change(actor=actor, seq=seq, deps=deps, start_op=ops[0].opid[0], ops=ops)


def _map_heavy():
    """Two replicas writing maps, nested maps and every register value kind
    concurrently, with text edits between."""
    docs, _, initial = generate_docs("hello", 2)
    a, b = docs
    log = {"doc1": [initial], "doc2": []}
    for doc, ops in (
        (a, [{"path": [], "action": "makeMap", "key": "meta"},
             {"path": ["meta"], "action": "set", "key": "title", "value": "hi"},
             {"path": ["meta"], "action": "set", "key": "n", "value": 7}]),
        (b, [{"path": [], "action": "set", "key": "flag", "value": True},
             {"path": [], "action": "set", "key": "none", "value": None},
             {"path": [], "action": "set", "key": "title", "value": "root"}]),
        (a, [{"path": ["meta"], "action": "makeMap", "key": "sub"},
             {"path": ["meta", "sub"], "action": "set", "key": "x", "value": "hi"},
             {"path": ["meta"], "action": "del", "key": "n"},
             {"path": ["text"], "action": "insert", "index": 2, "values": ["Z"]}]),
        (b, [{"path": [], "action": "set", "key": "flag", "value": False},
             {"path": [], "action": "del", "key": "none"},
             {"path": ["text"], "action": "delete", "index": 0, "count": 1}]),
    ):
        change, _ = doc.change(ops)
        log[change.actor].append(change)
    return log


def _second_list():
    """A second list object: encode_doc falls back."""
    docs, _, initial = generate_docs("ab", 1)
    c, _ = docs[0].change([
        {"path": [], "action": "makeList", "key": "other"},
        {"path": ["other"], "action": "insert", "index": 0, "values": ["x"]},
    ])
    return {"doc1": [initial, c]}


def _float_value():
    docs, _, initial = generate_docs("ab", 1)
    c, _ = docs[0].change([{"path": [], "action": "set", "key": "r", "value": 0.5}])
    return {"doc1": [initial, c]}


def _many_actors():
    """More actors than packed ids can hold: encode_doc falls back."""
    _, _, initial = generate_docs("ab", 1)
    log = {"doc1": [initial]}
    for i in range(MAX_ACTORS + 1):
        actor = f"w{i:04d}"
        log[actor] = [_change(actor, 1, {"doc1": 1}, [
            Operation("set", ROOT, (10, actor), key="k", value=i)])]
    return log


def _counter_overflow():
    """An op id counter past MAX_CTR: encode_doc falls back."""
    _, _, initial = generate_docs("ab", 1)
    text = initial.ops[0].opid
    big = MAX_CTR + 1
    c = _change("doc1", 2, {"doc1": 1}, [
        Operation("set", text, (big, "doc1"), elem_id=HEAD, insert=True, value="x")])
    return {"doc1": [initial, c]}


def _foreign_op_actor():
    """An op id from an actor that sent no change of its own."""
    docs, _, initial = generate_docs("ab", 1)
    text = initial.ops[0].opid
    c = _change("doc1", 2, {"doc1": 1}, [
        Operation("set", text, (9, "ghost"), elem_id=HEAD, insert=True, value="x")])
    return {"doc1": [initial, c]}


def _text_change(*ops):
    """The "ab" text's origin change and one more change of doc1 holding
    ``ops``, each given as a function of the text's id (op counters from 4)."""
    _, _, initial = generate_docs("ab", 1)
    text = initial.ops[0].opid
    ops = [make(text, (4 + i, "doc1")) for i, make in enumerate(ops)]
    return {"doc1": [initial, _change("doc1", 2, {"doc1": 1}, ops)]}


def _non_bmp():
    """Inserts of a code point beyond the BMP and one beyond Latin-1."""
    return _text_change(
        lambda t, o: Operation("set", t, o, elem_id=HEAD, insert=True, value="\U0001F600"),
        lambda t, o: Operation("set", t, o, elem_id=(4, "doc1"), insert=True, value="\u0101"))


def _mark(mark_type, attrs, end=(3, "doc1")):
    return lambda t, o: Operation("addMark", t, o, start=Boundary(BEFORE, (2, "doc1")),
                                  end=Boundary(AFTER, end), mark_type=mark_type,
                                  attrs=attrs)


def _empty_url():
    """A link whose url is the empty string: a value, not an absent attr."""
    return _text_change(_mark("link", {"url": ""}), _mark("strong", {}))


def _url_and_id():
    """Mark attrs with both keys: the url is the attr."""
    return _text_change(_mark("link", {"url": "https://a.example", "id": "c1"}),
                        _mark("comment", {"id": "c1"}), _mark("comment", {"id": "c1"}))


def _unknown_dep_actor():
    """A dep on an actor that sent no change (at seq 0, so the doc still
    schedules): the row flatten leaves the doc to encode_doc."""
    log = _text_change(
        lambda t, o: Operation("set", t, o, elem_id=HEAD, insert=True, value="x"))
    log["doc1"][1].deps["ghost"] = 0
    return log


def _counter_at_max():
    """An op id counter of MAX_CTR exactly: it packs."""
    _, _, initial = generate_docs("ab", 1)
    text = initial.ops[0].opid
    c = _change("doc1", 2, {"doc1": 1}, [
        Operation("set", text, (MAX_CTR, "doc1"), elem_id=HEAD, insert=True, value="x")])
    return {"doc1": [initial, c]}


def _counter_over_max_in_mark():
    """A mark boundary on an element whose counter is just over MAX_CTR."""
    return _text_change(_mark("em", None, end=(MAX_CTR + 1, "doc1")))


def _actors(n):
    """A doc edited by ``n`` actors: doc1 and n - 1 root-map writers."""
    _, _, initial = generate_docs("ab", 1)
    log = {"doc1": [initial]}
    for i in range(n - 1):
        actor = f"w{i:04d}"
        log[actor] = [_change(actor, 1, {"doc1": 1}, [
            Operation("set", ROOT, (10, actor), key="k", value=i)])]
    return log


def _b4(seed):
    """A B4-shaped one-author history at the book-length cell's rehearse size."""
    rehearse = load_json(BENCH_ROOT / "benchmark/configs/peritext_longdoc.json")["rehearse"]
    params = load_json(BENCH_ROOT / "benchmark/traffic/editing_trace_b4.json")["trace"]
    trace = Trace.of(dict(params, inserts=rehearse["inserts"], deletes=rehearse["deletes"]))
    return to_program(history(seed, trace))


def _duplicated(w):
    """Every doc's first log delivered twice more, out of order."""
    out = []
    for doc in w:
        first = next(iter(doc.values()))
        out.append({**doc, "again": list(reversed(first)), "more": first[:2]})
    return out


#: name -> (workloads, capacities)
CASES = {
    "fuzz_seed3": (lambda: generate_workload(seed=3, num_docs=5, ops_per_doc=80), {}),
    "fuzz_seed11": (lambda: generate_workload(seed=11, num_docs=4, ops_per_doc=150), {}),
    "fuzz_seed2024": (lambda: generate_workload(seed=2024, num_docs=6, ops_per_doc=60),
                      {"map_capacity": 8}),
    "markheavy": (lambda: generate_markheavy_workload(seed=5, num_docs=4, ops_per_doc=120),
                  {}),
    "map_heavy": (lambda: [_map_heavy(), *generate_workload(seed=4, num_docs=2, ops_per_doc=40)],
                  {}),
    "fallbacks": (lambda: [_second_list(), *generate_workload(seed=8, num_docs=2, ops_per_doc=40),
                           _float_value(), _counter_overflow(), _foreign_op_actor()], {}),
    "many_actors": (lambda: [_many_actors(), *generate_workload(seed=9, num_docs=1,
                                                                 ops_per_doc=40)], {}),
    "duplicates": (lambda: _duplicated(generate_workload(seed=12, num_docs=3,
                                                          ops_per_doc=60)), {}),
    "capacity": (lambda: generate_workload(seed=1234, num_docs=8, ops_per_doc=80),
                 {"mark_capacity": 24, "insert_capacity": 32, "delete_capacity": 20}),
    "capacity_maps": (lambda: [_map_heavy(), _map_heavy()], {"map_capacity": 8}),
    "empty": (lambda: [{}, {"doc1": []}, *generate_workload(seed=2, num_docs=1,
                                                          ops_per_doc=20)], {}),
    "no_docs": (lambda: [], {"mark_capacity": 16}),
    "non_bmp": (lambda: [_non_bmp(), *generate_workload(seed=13, num_docs=1,
                                                       ops_per_doc=30)], {}),
    "mark_attrs": (lambda: [_empty_url(), _url_and_id()], {}),
    "unknown_dep": (lambda: [_unknown_dep_actor(), *generate_workload(seed=14, num_docs=1,
                                                                     ops_per_doc=30)], {}),
    "counters": (lambda: [_counter_at_max(), _counter_over_max_in_mark()], {}),
    "actors_1023_1024": (lambda: [_actors(MAX_ACTORS), _actors(MAX_ACTORS + 1)], {}),
    "b4": (lambda: [_b4(5), _b4(2**31 + 9)], {}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_columnar_encode_matches_python(case, monkeypatch):
    make, caps = CASES[case]
    got = encode_workloads(make(), **caps)
    want = _python_encode(monkeypatch, encode_workloads, make(), **caps)
    _assert_same_batch(got, want)


@pytest.mark.parametrize("case", ["fuzz_seed3", "markheavy", "map_heavy", "fallbacks",
                                  "duplicates", "empty", "non_bmp", "unknown_dep", "b4"])
def test_columnar_doc_streams_match_python(case, monkeypatch):
    make, _ = CASES[case]
    got = encode_doc_streams(make())
    want = _python_encode(monkeypatch, encode_doc_streams, make())
    for g, w in zip(got[0], want[0]):
        assert (g.ins, g.dels, g.marks, g.maps) == (w.ins, w.dels, w.marks, w.maps)
    assert len(got[0]) == len(want[0])
    assert got[1] == want[1]
    for g, w in zip(got[2:], want[2:]):
        assert _tables(g) == _tables(w)


def test_expected_fallbacks_are_the_python_ones():
    enc = encode_workloads(CASES["fallbacks"][0]())
    assert enc.fallback_docs == [0, 3, 4]  # second list, float value, counter
    assert encode_workloads(CASES["many_actors"][0]()).fallback_docs == [0]
    assert encode_workloads(CASES["counters"][0]()).fallback_docs == [1]
    assert encode_workloads(CASES["actors_1023_1024"][0]()).fallback_docs == [1]


_FLAT_FIELDS = ("heads", "deps", "ops", "doc_ch_off", "doc_int_off", "doc_n_actors",
                "expressed", "bounds", "attr_strs", "key_strs", "doc_attr_off",
                "doc_key_off", "actors")


@pytest.mark.parametrize("case", sorted(CASES))
def test_native_flatten_matches_python(case):
    """Doc by doc into one batch (so the string ids start past the docs
    before): the native walk gives the Python flatten's columns, byte for
    byte, and declines exactly the docs where the Python flatten raises."""
    walk = native.flatten_walker()
    assert walk is not None
    workloads = CASES[case][0]()
    flat_native, flat_python = encode._Flat(), encode._Flat()
    for queues in workloads:
        *counts, rows, walked = encode._flatten_doc(queues, flat_native, walk)
        want = encode._flatten_doc(queues, flat_python, None)
        assert want[3] is False
        assert [*counts, rows] == list(want[:3])
        assert walked == rows  # declined exactly where _flatten_rows raises
    for name in _FLAT_FIELDS:
        got, want = getattr(flat_native, name), getattr(flat_python, name)
        if name in ("heads", "deps", "ops"):
            got, want = got.tobytes(), want.tobytes()
        assert got == want, name
    assert [list(map(id, c)) for c in flat_native.changes] == [
        list(map(id, c)) for c in flat_python.changes]


def test_native_flatten_declines_what_it_does_not_read():
    """Types the walk does not read exactly are left to the Python flatten,
    which takes them: a bool seq, a list op id, a str subclass value, a
    tuple of changes for a log."""
    walk = native.flatten_walker()

    class Char(str):
        pass

    for edit in (lambda log: setattr(log["doc1"][-1], "seq", True),
                 lambda log: setattr(log["doc1"][-1].ops[0], "opid",
                                     list(log["doc1"][-1].ops[0].opid)),
                 lambda log: setattr(log["doc1"][-1].ops[0], "value", Char("x")),
                 lambda log: log.update(doc1=tuple(log["doc1"]))):
        log = _text_change(
            lambda t, o: Operation("set", t, o, elem_id=HEAD, insert=True, value="x"))
        edit(log)
        assert walk(log, encode._WALK_CONSTS, 0, 0) is None
        flat = encode._Flat()
        assert encode._flatten_doc(log, flat, walk)[2:] == (True, False)


def test_native_flatten_reads_every_op_on_every_encode(monkeypatch):
    """Nothing of a doc survives an encode: an op changed in place between
    two encodes changes the second's rows, and each encode walks every doc."""
    fresh = metrics.Counters()
    monkeypatch.setattr(encode, "GLOBAL_COUNTERS", fresh)
    w = generate_workload(seed=31, num_docs=3, ops_per_doc=60)
    first = encode_workloads(w)
    assert fresh.get("encode.flatten.native") == 3
    op = next(op for ch in w[1]["doc1"][1:] for op in ch.ops if op.insert)
    op.value = "Q" if op.value != "Q" else "R"
    second = encode_workloads(w)
    assert fresh.get("encode.flatten.native") == 6
    assert fresh.get("encode.flatten.python") == 0
    assert (first.ins_char[1] != second.ins_char[1]).sum() >= 1
    assert (first.ins_char[[0, 2]] == second.ins_char[[0, 2]]).all()
    _assert_same_batch(second, _python_encode(monkeypatch, encode_workloads, w))


def test_without_the_walker_every_doc_flattens_in_python(monkeypatch):
    fresh = metrics.Counters()
    monkeypatch.setattr(metrics, "GLOBAL_COUNTERS", fresh)
    monkeypatch.setattr(encode, "GLOBAL_COUNTERS", fresh)
    monkeypatch.setattr(native, "flatten_walker", lambda: None)
    make, caps = CASES["fallbacks"]
    got = encode_workloads(make(), **caps)
    _assert_same_batch(got, _python_encode(monkeypatch, encode_workloads, make(), **caps))
    assert fresh.get("encode.flatten.python") == got.num_docs
    assert fresh.get("encode.flatten.native") == 0
    assert metric_reader("batch.encode.native_flatten_pct")(None) == 0.0


@pytest.mark.parametrize("drop", ["first", "middle", "unexpressed"])
def test_causal_gap_raises_on_both_paths(drop, monkeypatch):
    w = generate_workload(seed=21, num_docs=3, ops_per_doc=60)
    if drop == "unexpressed":  # a gap in a doc the row flatten cannot take
        w[1] = _foreign_op_actor()
        log = w[1]["doc1"]
    else:
        log = w[1]["doc2"]
    del log[0 if drop != "middle" else len(log) // 2]
    with pytest.raises(PeritextError) as native_gap:
        encode_workloads(w)
    with pytest.raises(PeritextError) as python_gap:
        _python_encode(monkeypatch, encode_workloads, w)
    assert str(native_gap.value) == str(python_gap.value)


def test_typical_docs_take_the_row_flatten_and_count_as_native():
    sink = []
    tracer = Tracer(host="columnar")
    tracer.add_sink(sink.append)
    w = generate_workload(seed=7, num_docs=4, ops_per_doc=60)
    before = GLOBAL_COUNTERS.get("causal.schedules.native")
    encode_workloads(w, tracer=tracer)
    assert GLOBAL_COUNTERS.get("causal.schedules.native") == before + 4
    splits = [s for s in sink if s.name == "batch.encode.split"]
    assert [s.args["rows"] for s in splits] == [True] * 4


def _random_changes(rng, n_actors, n):
    """Changes with random deps, shuffled, some duplicated, some missing."""
    actors = [f"a{i}" for i in range(n_actors)]
    clock = {}
    out = []
    for _ in range(n):
        a = rng.choice(actors)
        seq = clock.get(a, 0) + 1
        deps = {b: s for b, s in clock.items() if b != a and rng.random() < 0.7}
        if rng.random() < 0.3:
            deps[a] = seq - 1
        clock[a] = seq
        out.append(Change(actor=a, seq=seq, deps=deps, start_op=1))
    out += rng.sample(out, n // 12)  # duplicate deliveries
    rng.shuffle(out)
    if rng.random() < 0.5:
        out.remove(rng.choice(out))  # perhaps a gap
    return out


#: (seed, actors, changes): small sets, and large ones with many actors
SCHEDULES = [(seed, 4, 120) for seed in range(6)] + [(seed, 48, 6000) for seed in (6, 7, 8)]


@pytest.mark.parametrize("seed,n_actors,n", SCHEDULES)
def test_native_scheduler_matches_python(seed, n_actors, n, monkeypatch):
    rng = random.Random(seed)
    changes = _random_changes(rng, n_actors, n)
    # odd seeds start from a clock that covers part of some actors' changes
    base = ({f"a{i}": rng.randint(0, 3 * n // n_actors // 4) for i in range(0, n_actors, 3)}
            if seed % 2 else None)
    got = causal._native_schedule(changes, base)
    monkeypatch.setattr(native, "available", lambda: False)
    want = causal.causal_schedule(changes, base)
    key = lambda chs: [(c.actor, c.seq, id(c)) for c in chs]  # noqa: E731
    assert key(got[0]) == key(want[0])
    assert key(got[1]) == key(want[1])
