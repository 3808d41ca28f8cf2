"""Batch convergence through ``DocBatch.merge``: change logs in, formatted
spans out, whole merges back to back.

Each merge converges ``docs`` documents; merge ``k`` gives document ``i``
the history ``(i + k * rotate) mod distinct`` of the seeded pool, so every
merge does the same work on differently placed documents.  The rate is all
the ops of the whole merges run, over the time to the end of the last
one; a merge is never counted in part.

Control ``stale`` (for the comparison's test): the reference with the last
change of every actor left out, a merge that misses what arrived last.
"""

from __future__ import annotations

import time

from benchmark import stats
from benchmark.drivers import _pool
from benchmark.reference import causal_order, spans_of
from benchmark.run import Check, Window


def setup(run):
    sz = run.sizes()
    with run.spans.span("bench.generate"):
        pool = _pool.make_pool(run.seed, sz["distinct_histories"], sz["ops_per_doc"],
                               run.param("mix"))
    program_pool = [_pool.to_program(h) for h in pool]

    from peritext_tpu.api import DocBatch

    batch = DocBatch(**run.config["program"])
    state = {"pool": pool, "program_pool": program_pool, "batch": batch,
             "docs": sz["docs"], "rotate": run.param("rotate")}
    state["ops_per_merge"] = sum(_pool.op_count(pool[i % len(pool)])
                                 for i in range(sz["docs"]))
    # one whole merge: it compiles (or loads) every program, and leaves the
    # host heap as every later merge finds it (a warm-up of one-change
    # documents compiled the same programs, but the window's merges then
    # spread 4-9% from run to run, my chip run, PR 22)
    with run.spans.span("bench.warmup"):
        batch.merge(workloads(state, 0))
    return state


def placement(state, k: int, i: int) -> int:
    return (i + k * state["rotate"]) % len(state["pool"])


def workloads(state, k: int):
    pp = state["program_pool"]
    return [pp[placement(state, k, i)] for i in range(state["docs"])]


def window(run, state) -> Window:
    batch = state["batch"]
    merges = []
    ends = []
    t0 = time.perf_counter()
    k = 0
    while time.perf_counter() - t0 < run.seconds:
        k += 1
        docs = workloads(state, k)
        with run.spans.span("bench.merge"):
            report = batch.merge(docs)
        merges.append((k, report.spans, report.fallback_docs))
        ends.append(time.perf_counter() - t0)
    elapsed = ends[-1]
    ops = state["ops_per_merge"] * len(merges)
    state["merges"] = merges
    run.log(f"{len(merges)} merges of {state['docs']} docs, {ops} ops in "
            f"{elapsed:.3f} s; merges ended at {[round(t, 3) for t in ends[:12]]} s")
    return Window(metrics={"merge_ops_per_s": stats.rate(ops, elapsed)},
                  attempted=len(merges) * state["docs"], failed=0,
                  readings={"merges": len(merges), "docs": state["docs"],
                            "program": run.config["program"]})


def stale_spans(h) -> list:
    """Control: the history without each actor's last change, and without
    whatever depended on one left out."""
    clock: dict = {}
    kept: dict = {}
    for ch in causal_order(h):
        dropped = ch.seq == h[ch.actor][-1].seq and len(h[ch.actor]) > 1
        if dropped or clock.get(ch.actor, 0) != ch.seq - 1 or any(
                clock.get(a, 0) < s for a, s in ch.deps.items() if a != ch.actor):
            continue
        kept.setdefault(ch.actor, []).append(ch)
        clock[ch.actor] = ch.seq
    return spans_of(kept)


def verify(run, state, win: Window):
    merges = state.pop("merges")
    state.pop("batch")  # the program's device state goes before the reference runs
    with run.spans.span("bench.reference"):
        expected = [spans_of(h) for h in state["pool"]]
    if run.control == "stale":
        stale = [stale_spans(h) for h in state["pool"]]
        merges = [(k, [stale[placement(state, k, i)] for i in range(state["docs"])], [])
                  for k, _, _ in merges]
    elif run.control is not None:
        raise ValueError(f"batch_merge has no control {run.control!r}")
    wrong = sum(spans[i] != expected[placement(state, k, i)]
                for k, spans, _ in merges for i in range(state["docs"]))
    fallback = sum(len(fb) for _, _, fb in merges)
    win.failed = wrong + fallback
    return [Check("docs_wrong", wrong, 0), Check("docs_fallback", fallback, 0)]
