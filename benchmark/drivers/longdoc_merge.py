"""Batch convergence of book-length single-author documents through
``DocBatch.merge``: the window of ``batch_merge``, on histories from
``gen/editing_trace.py``, checked against the insert-tree reference
(``reference/rga_tree.py``), which takes seconds where ``spans_of`` takes
minutes at this length.  The log names the program's ragged-merge counters
over the window, so a run shows that every merge took the ragged layout.

Control ``stale`` (for the comparison's test): the reference with each
history's last change left out.
"""

from __future__ import annotations

from benchmark.drivers import _pool
from benchmark.drivers.batch_merge import placement, window  # noqa: F401  (the window)
from benchmark.gen.editing_trace import Trace, history
from benchmark.reference.rga_tree import spans_of_text
from benchmark.run import Check, Window

COUNTERS = ("merge.ragged_calls", "merge.ragged_pages", "merge.ragged_loop_steps")


def counters() -> dict:
    from peritext_tpu.obs import metrics

    return {name: metrics.GLOBAL_COUNTERS.get(name) for name in COUNTERS}


def setup(run):
    sz = run.sizes()
    trace = Trace.of(dict(run.param("trace"), inserts=sz["inserts"],
                          deletes=sz["deletes"]))
    with run.spans.span("bench.generate"):
        pool = [history(_pool.history_seed(run.seed, j), trace)
                for j in range(sz["distinct_histories"])]
    program_pool = [_pool.to_program(h) for h in pool]

    from peritext_tpu.api import DocBatch

    batch = DocBatch(**run.config["program"])
    state = {"pool": pool, "program_pool": program_pool, "batch": batch,
             "docs": sz["docs"], "rotate": run.param("rotate")}
    state["ops_per_merge"] = sum(_pool.op_count(pool[i % len(pool)])
                                 for i in range(sz["docs"]))
    # one whole merge compiles (or loads) every program the window runs
    with run.spans.span("bench.warmup"):
        batch.merge([program_pool[placement(state, 0, i)] for i in range(sz["docs"])])
    state["counters"] = counters()
    return state


def verify(run, state, win: Window):
    merges = state.pop("merges")
    seen = {k: v - state["counters"][k] for k, v in counters().items()}
    run.log(f"{len(merges)} window merges; " + ", ".join(
        f"{k} {v:.0f}" for k, v in seen.items()))
    state.pop("batch")  # the program's device state goes before the reference runs
    with run.spans.span("bench.reference"):
        if run.control == "stale":
            expected = [spans_of_text({a: log[:-1] for a, log in h.items()})
                        for h in state["pool"]]
        elif run.control is None:
            expected = [spans_of_text(h) for h in state["pool"]]
        else:
            raise ValueError(f"longdoc_merge has no control {run.control!r}")
    wrong = sum(spans[i] != expected[placement(state, k, i)]
                for k, spans, _ in merges for i in range(state["docs"]))
    fallback = sum(len(fb) for _, _, fb in merges)
    win.failed = wrong + fallback
    return [Check("docs_wrong", wrong, 0), Check("docs_fallback", fallback, 0)]
