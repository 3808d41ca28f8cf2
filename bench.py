#!/usr/bin/env python
"""peritext-tpu benchmark: batched CRDT op application throughput.

Measures the north-star metric (BASELINE.md): CRDT ops applied/sec/chip for
converging a batch of concurrently-edited documents, vs the single-thread
scalar baseline.

Baseline caveat: BASELINE.json config 1 calls for the reference TypeScript
micromerge on one CPU core, but this image has no node runtime.  Two
stand-ins are measured every run: the C++ single-core scalar apply
(``native.pt_scalar_apply`` — a HARDER bar than interpreted TS; this is
what ``vs_baseline`` divides by) and the framework's own pure-Python scalar
oracle (continuity with round-1 records, reported as
``python_oracle_ops_per_sec``).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "ops/s", "vs_baseline": N, ...extras}

The default entry point is an ORCHESTRATOR that imports no jax: each
measurement runs in a worker subprocess (hidden ``--_worker`` flag) under
a timeout, and the orchestrator always prints the JSON line.  A run needs
the chip: a worker that finds no TPU fails, and the run exits nonzero with
a ``"failed": true`` record.  ``--platform cpu`` is the explicit CPU
rehearsal; its rows say ``cpu``.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

# Worker timeouts (seconds; env-overridable so the driver or tests can
# tighten them).
WORKER_TIMEOUT = float(os.environ.get("PT_BENCH_TIMEOUT", "2700"))
# Ladder mode (the no-args default): per-row worker timeout and a global
# deadline after which remaining rows are recorded as skipped — one slow or
# wedged row must never cost the round its entire evidence record.
ROW_TIMEOUT = float(os.environ.get("PT_BENCH_ROW_TIMEOUT", "900"))
LADDER_DEADLINE = float(os.environ.get("PT_BENCH_LADDER_DEADLINE", "3600"))
# The driver keeps only a short tail of stdout, which a full ladder record
# (~5 KB) outgrows.  The ladder therefore prints a COMPACT summary as the
# LAST line — hard-budgeted below — and writes the full rows to a sidecar
# file.
FINAL_LINE_BUDGET = int(os.environ.get("PT_BENCH_FINAL_LINE_BUDGET", "1536"))
SIDECAR = os.environ.get(
    "PT_BENCH_SIDECAR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_self.json"),
)


def _baseline_changes(num_ops: int = 4000, seed: int = 7):
    """Causally-ordered fuzz change log shared by both scalar baselines."""
    from peritext_tpu.parallel.causal import causal_sort
    from peritext_tpu.testing.fuzz import make_fuzz_state, fuzz_step

    state = make_fuzz_state(seed, num_replicas=3)
    while state.ops_generated < num_ops:
        fuzz_step(state, check=False)
    changes = causal_sort(
        [ch for actor in state.store.actors() for ch in state.store.log(actor)]
    )
    return changes, sum(len(ch.ops) for ch in changes)


def measure_scalar_baseline(num_ops: int = 4000, seed: int = 7) -> float:
    """Single-thread ops/sec: replay fuzz-generated change logs through the
    scalar oracle's apply_change path (pure Python)."""
    from peritext_tpu.core.doc import Doc

    changes, total_ops = _baseline_changes(num_ops, seed)
    doc = Doc("baseline")
    t0 = time.perf_counter()
    for ch in changes:
        doc.apply_change(ch)
    elapsed = time.perf_counter() - t0
    return total_ops / elapsed


def measure_native_baseline(num_docs: int = 16, ops_per_doc: int = 256, seed: int = 7):
    """Single-CORE ops/sec through the C++ scalar apply (pt_scalar_apply) —
    the defensible stand-in for the reference's single-thread TS baseline
    (no node runtime in this image; an optimized native single core is a
    strictly harder bar than interpreted TS, which pays for JS objects,
    per-mark gap-set maintenance and patch emission this baseline skips).
    Callers pass the device benchmark's ops_per_doc so per-op scan lengths
    match the workload being compared against.  Every doc's applied text is
    validated against the Python oracle before timing.  Returns None if the
    native core is unavailable."""
    from peritext_tpu import native
    from peritext_tpu.testing.baseline import (
        check_scalar_apply_matches_oracle,
        workload_op_matrices,
    )
    from peritext_tpu.testing.fuzz import generate_workload

    if not native.available():
        return None
    workloads = generate_workload(seed, num_docs=num_docs, ops_per_doc=ops_per_doc)
    matrices, total_ops = workload_op_matrices(workloads)
    check_scalar_apply_matches_oracle(workloads, matrices)

    # a single sweep is fast; amortize wrapper overhead over repetitions
    reps = 20
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            for m in matrices:
                native.scalar_apply(m)
        dt = (time.perf_counter() - t0) / reps
        best = dt if best is None or dt < best else best
    return total_ops / best


def _baselines_for(ops_per_doc: int, seed: int):
    """(python_oracle, native_cpp) baselines — reused from the ladder's
    baselines row via PT_BENCH_BASELINES when the shapes match, else
    measured in-process (the scalar baselines cost ~30 s each, too much to
    re-pay in every ladder row)."""
    blob = os.environ.get("PT_BENCH_BASELINES")
    if blob:
        try:
            b = json.loads(blob)
        except json.JSONDecodeError:
            b = None
        if b and b.get("scalar_python_ops_per_sec"):
            python = b["scalar_python_ops_per_sec"]
            if b.get("native_ops_per_doc") == ops_per_doc and \
                    b.get("native_cpp_ops_per_sec"):
                return python, b["native_cpp_ops_per_sec"]
            return python, measure_native_baseline(ops_per_doc=ops_per_doc, seed=seed)
    return (
        measure_scalar_baseline(),
        measure_native_baseline(ops_per_doc=ops_per_doc, seed=seed),
    )


def run(args) -> dict:
    import jax

    from peritext_tpu.ops.kernel import apply_batch_jit
    from peritext_tpu.ops.packed import empty_docs
    from peritext_tpu.ops.resolve import resolve_jit
    from peritext_tpu.testing.synth import synth_streams, synth_total_ops

    d, k, s, m = args.docs, args.ops_per_doc, args.slots, args.marks
    if args.layout == "ragged":
        # the ragged store pages the element planes: round the shared slot
        # capacity to a page multiple so both layouts overflow at the same
        # op (cap = page_count * P must be able to equal S exactly)
        from peritext_tpu.store import DEFAULT_PAGE_SIZE

        s = -(-s // DEFAULT_PAGE_SIZE) * DEFAULT_PAGE_SIZE
    # op mix matching the fuzz distribution: ~70% inserts, 15% deletes, 15% marks
    ki = int(k * 0.7)
    kd = int(k * 0.15)
    km = k - ki - kd

    gen_start = time.perf_counter()
    streams = synth_streams(
        d, inserts_per_doc=ki, deletes_per_doc=kd, marks_per_doc=km, seed=args.seed
    )
    total_ops = synth_total_ops(streams)
    gen_time = time.perf_counter() - gen_start

    state0 = empty_docs(d, s, max(m, km), tomb_capacity=max(kd, 8))
    ops_dev = jax.device_put(streams)

    # Docs start empty here, so the insert loop can be statically bounded to
    # the insert-stream width (pallas_insert loop_slots contract).
    def apply_jit(st, ops):
        return apply_batch_jit(st, ops, insert_loop_slots=ki)

    # sync on a small output: the host transfer waits for the apply
    def sync(r):
        return np.asarray(r.num_slots)

    compile_start = time.perf_counter()
    result = apply_jit(state0, ops_dev)
    sync(result)
    compile_time = time.perf_counter() - compile_start

    if args.layout == "ragged":
        # the batch_8k_ragged row (ISSUE 12): same streams, same protocol,
        # but the apply runs ragged over a page pool — the padded result
        # just computed is its byte-equality oracle
        return _batch_ragged_tail(
            args, ops_dev, state0, apply_jit, sync, result, total_ops,
            gen_time, compile_time, d=d, s=s, mark_cap=max(m, km),
            tomb_cap=max(kd, 8),
        )

    # single_call_seconds DEFINITION: wall time of ONE whole-batch apply
    # dispatch through to a host sync on a small output — apply compute
    # plus the fixed dispatch+sync cost, while apply_seconds (back-to-back
    # enqueue, one sync) amortizes that fixed cost away.
    t0 = time.perf_counter()
    sync(apply_jit(state0, ops_dev))
    single_call = time.perf_counter() - t0

    # Steady-state throughput (the headline): enqueue iters applies
    # back-to-back — the device executes queued programs serially — and
    # sync once, amortizing dispatch latency exactly as a streaming
    # deployment does.
    from peritext_tpu.observability import profile_trace

    times = []
    with profile_trace(args.profile, enabled=args.profile is not None):
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(args.iters):
                result = apply_jit(state0, ops_dev)
            sync(result)
            times.append(time.perf_counter() - t0)
    best = min(times) / args.iters

    overflow = int(np.asarray(result.overflow).sum())
    device_ops_per_sec = total_ops / best

    # resolution (read path) timing, reported as extra context; sync on a
    # small field (visible is (D,S) and would measure the host transfer).
    resolved = resolve_jit(result, 32)
    np.asarray(resolved.overflow)
    t0 = time.perf_counter()
    for _ in range(args.iters):
        resolved = resolve_jit(result, 32)
    np.asarray(resolved.overflow)
    resolve_time = (time.perf_counter() - t0) / args.iters

    baseline, native_baseline = _baselines_for(args.ops_per_doc, args.seed or 7)
    honest = native_baseline or baseline

    return {
        "metric": "crdt_ops_per_sec_per_chip",
        "value": round(device_ops_per_sec, 1),
        "unit": "ops/s",
        "vs_baseline": round(device_ops_per_sec / honest, 2),
        "baseline_ops_per_sec": round(honest, 1),
        "baseline_impl": "cpp-single-core-scalar-apply (native.pt_scalar_apply; "
                         "no node runtime in image for the TS reference)",
        "python_oracle_ops_per_sec": round(baseline, 1),
        "vs_python_oracle": round(device_ops_per_sec / baseline, 2),
        "docs": d,
        "ops_per_doc": k,
        "slot_capacity": s,
        "apply_seconds": round(best, 4),
        "single_call_seconds": round(single_call, 4),
        "resolve_seconds": round(resolve_time, 4),
        "compile_seconds": round(compile_time, 1),
        "workload_gen_seconds": round(gen_time, 1),
        "overflow_docs": overflow,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


def _batch_ragged_tail(args, ops_dev, state0, apply_jit, sync, oracle,
                       total_ops, gen_time, padded_compile_s, *, d, s,
                       mark_cap, tomb_cap) -> dict:
    """layout=ragged variant of the batch row (ISSUE 12): the SAME synth
    streams apply through ops/ragged.py directly against a page pool — one
    compiled program for the whole batch, per-doc op/page counts as data —
    with the padded apply just computed as the byte-equality oracle, then
    the identical steady-state enqueue/sync protocol.  ``vs_baseline`` is
    measured in-row against the padded apply under the same protocol (one
    pass of ``--iters``), so the row gates the ragged/padded ratio, not
    two machines' clocks."""
    import jax
    import jax.numpy as jnp

    from peritext_tpu.ops.kernel import PAGED_AUX_FIELDS
    from peritext_tpu.ops.ragged import apply_batch_ragged_jit, plan_arrays
    from peritext_tpu.store import DEFAULT_PAGE_SIZE
    from peritext_tpu.store.paged import PagedDocStore
    from peritext_tpu.store.ragged import ragged_plan

    ins_counts = np.count_nonzero(np.asarray(ops_dev[1]), axis=1)
    del_counts = np.count_nonzero(np.asarray(ops_dev[3]), axis=1)
    max_pages = max(1, s // DEFAULT_PAGE_SIZE)
    need = np.minimum(
        -(-np.maximum(ins_counts, 1) // DEFAULT_PAGE_SIZE), max_pages
    )
    # pre-sized pool: growth mid-run would change the pool shape (an honest
    # recompile); sizing is the deployer's lever, shape stability the row's
    store = PagedDocStore(
        d, s, mark_cap, tomb_capacity=tomb_cap,
        initial_pages=1 + int(need.sum()),
    )
    rows = np.arange(d, dtype=np.int64)
    store.ensure_rows(rows, ins_counts)
    planes = plan_arrays(ragged_plan(store))
    ic_dev = jnp.asarray(ins_counts, jnp.int32)
    dc_dev = jnp.asarray(del_counts, jnp.int32)
    pool0 = (store.pool_elem, store.pool_char, store.aux)

    def apply_ragged():
        # nodonate: every dispatch re-applies the round to the SAME empty
        # pool, exactly as the padded loop re-applies to state0
        return apply_batch_ragged_jit(
            *pool0, *planes, ops_dev, ic_dev, dc_dev, donate=False,
        )

    ns_i = PAGED_AUX_FIELDS.index("num_slots")

    def sync_ragged(out):
        return np.asarray(out[2][ns_i])

    t0 = time.perf_counter()
    out = apply_ragged()
    sync_ragged(out)
    ragged_compile = time.perf_counter() - t0

    # byte equality, field by field: materialize the pool back to the
    # padded (D, S) view (widths match — S is a page multiple here, so
    # max_doc_pages * P == S) and compare against the padded oracle
    store.pool_elem, store.pool_char, store.aux = out
    got = store.materialize_rows(rows, bucket_pages=store.max_doc_pages)
    for f in oracle._fields:
        a = np.asarray(getattr(oracle, f))
        b = np.asarray(getattr(got, f))
        if f in ("elem_id", "char"):
            b = b[:, : a.shape[1]]
        assert np.array_equal(a, b), f"ragged apply diverged on {f}"
    overflow = int(np.asarray(got.overflow).sum())

    t0 = time.perf_counter()
    sync_ragged(apply_ragged())
    single_call = time.perf_counter() - t0

    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = apply_ragged()
        sync_ragged(out)
        times.append(time.perf_counter() - t0)
    best = min(times) / args.iters
    value = total_ops / best

    # the in-row padded baseline: one pass of the same protocol (the full
    # 3-pass padded measurement is the batch_8k row's job, not this one's)
    t0 = time.perf_counter()
    for _ in range(args.iters):
        res = apply_jit(state0, ops_dev)
    sync(res)
    padded_best = (time.perf_counter() - t0) / args.iters

    pool = store.pool_stats()
    return {
        "metric": "ragged_crdt_ops_per_sec_per_chip",
        "value": round(value, 1),
        "unit": "ops/s",
        "vs_baseline": round(padded_best / best, 2),
        "baseline_impl": "same synth batch through the padded (D, S) apply "
                         "(one pass of the same enqueue/sync protocol)",
        "baseline_ops_per_sec": round(total_ops / padded_best, 1),
        "byte_equal": True,
        "docs": d,
        "ops_per_doc": args.ops_per_doc,
        "slot_capacity": s,
        "apply_seconds": round(best, 4),
        "single_call_seconds": round(single_call, 4),
        "padded_apply_seconds": round(padded_best, 4),
        "compile_seconds": round(ragged_compile, 1),
        "padded_compile_seconds": round(padded_compile_s, 1),
        "overflow_docs": overflow,
        "page_pool": pool,
        "workload_gen_seconds": round(gen_time, 1),
        "platform": jax.devices()[0].platform,
    }


def build_arrival(workloads, rounds: int, seed, as_frames: bool = True,
                  arrival_model: str = "shuffle", wire: str = "v2"):
    """Per-doc round batches of a streaming session's arrival, split into
    ``rounds`` batches and — for the wire path — encoded per-sender
    sequential (senders flush their queues in order, changeQueue semantics;
    also what the wire codec's delta context expects).

    ``arrival_model``: "shuffle" (the r1-r3 bench shape: full random
    shuffle, i.e. per-sender REORDERING — a stress the real transport never
    produces, kept for record continuity and scheduling stress) or "fifo"
    (per-sender FIFO with random cross-sender interleave — what TCP + the
    reference's changeQueue actually deliver, src/changeQueue.ts:16-28).
    ``wire``: "v2" self-contained frames, or "v4" session-scoped frames
    (one WireSession per doc link: persistent string dictionary + deflate,
    codec.WireSession).

    SHARED by the end-to-end (run_streaming) and engine-limit (run_engine)
    rows: the engine row's whole value is being the same workload minus
    host cost, so the two must never drift apart.
    Returns (arrival, wire_bytes)."""
    import random

    from peritext_tpu.parallel.codec import WireSession, encode_frame

    rng = random.Random(seed)
    arrival = []
    wire_bytes = 0
    for w in workloads:
        if arrival_model == "fifo":
            logs = {a: list(l) for a, l in w.items()}
            actors = sorted(logs)
            changes = []
            while True:
                live = [a for a in actors if logs[a]]
                if not live:
                    break
                changes.append(logs[rng.choice(live)].pop(0))
        else:
            changes = [ch for log in w.values() for ch in log]
            rng.shuffle(changes)
        size = -(-len(changes) // rounds)
        batches = [changes[i : i + size] for i in range(0, len(changes), size)]
        if as_frames:
            enc = WireSession(compress=True).encode_frame if wire == "v4" \
                else encode_frame
            batches = [
                enc(sorted(b, key=lambda c: (c.actor, c.seq)))
                for b in batches
            ]
            wire_bytes += sum(len(b) for b in batches)
        arrival.append(batches)
    return arrival, wire_bytes


def run_streaming(args) -> dict:
    """BASELINE config 5: multi-round streaming merge on carried device state.

    Arrival batches are pre-encoded as binary wire frames (what a host
    actually receives over DCN, parallel/codec.py); ingestion takes the
    frame-native fast path (C++ parse + vectorized schedule/split,
    ops/frames.py) unless --object-ingest forces the Python object path."""
    import jax

    from peritext_tpu.obs import GLOBAL_HISTOGRAMS, GLOBAL_TRACER
    from peritext_tpu.parallel.streaming import StreamingMerge
    from peritext_tpu.testing.fuzz import generate_workload

    if args.trace_out:
        # pipeline spans for the measured sessions -> Perfetto JSON; render
        # a per-stage table with `python -m peritext_tpu.obs <trace>`
        GLOBAL_TRACER.enabled = True

    d, rounds = args.docs, args.rounds
    gen_start = time.perf_counter()
    workloads = generate_workload(seed=args.seed, num_docs=d, ops_per_doc=args.ops_per_doc)
    gen_time = time.perf_counter() - gen_start

    arrival, wire_bytes = build_arrival(
        workloads, rounds, args.seed, as_frames=not args.object_ingest
    )

    def session():
        return StreamingMerge(
            num_docs=d,
            actors=("doc1", "doc2", "doc3"),
            slot_capacity=args.slots,
            mark_capacity=args.marks,
            tomb_capacity=args.slots,
            round_insert_capacity=256,
            round_delete_capacity=128,
            round_mark_capacity=128,
        )

    def feed_round(s, r):
        if args.object_ingest:
            for doc, batches in enumerate(arrival):
                if r < len(batches):
                    s.ingest(doc, batches[r])
        else:
            # the bulk DCN receive path: one native parse call per round
            s.ingest_frames(
                (doc, batches[r])
                for doc, batches in enumerate(arrival)
                if r < len(batches)
            )

    def run_session():
        stages = {"ingest": 0.0, "schedule_apply": 0.0, "digest": 0.0}
        t_all = time.perf_counter()
        s = session()
        for r in range(rounds):
            t0 = time.perf_counter()
            feed_round(s, r)
            t1 = time.perf_counter()
            s.drain()
            t2 = time.perf_counter()
            stages["ingest"] += t1 - t0
            stages["schedule_apply"] += t2 - t1
        t0 = time.perf_counter()
        digest = s.digest()  # sync point: absorbs all queued device work
        stages["digest"] += time.perf_counter() - t0
        # host-parse share of the ingest stage (the C++ wire parse; the
        # rest of "ingest" is Python queue/bookkeeping) — VERDICT r4 task 3
        stages["host_parse"] = s.host_parse_seconds
        return time.perf_counter() - t_all, digest, stages, s

    # warmup compile
    _, digest0, _, s = run_session()
    fallbacks = sum(1 for sess in s.docs if sess.fallback)

    # host-clock timings are noisy: best of 3 timed sessions
    elapsed, stages = None, None
    for _ in range(3):
        t, digest, st, _ = run_session()
        assert digest == digest0
        if elapsed is None or t < elapsed:
            elapsed, stages = t, st

    total_ops = sum(
        len(ch.ops) for w in workloads for log in w.values() for ch in log
    )
    baseline, native_baseline = _baselines_for(args.ops_per_doc, args.seed or 7)
    honest = native_baseline or baseline
    value = total_ops / elapsed
    if args.trace_out:
        GLOBAL_TRACER.write_chrome_trace(args.trace_out)
    return {
        # rolling percentiles of the committed-round wall (schedule+apply
        # dispatch) across the whole measurement, the deadline-autotune view
        "round_latency": GLOBAL_HISTOGRAMS.get(
            "streaming.round_seconds"
        ).snapshot(),
        "metric": "streaming_crdt_ops_per_sec_per_chip",
        "value": round(value, 1),
        "unit": "ops/s",
        "vs_baseline": round(value / honest, 2),
        "baseline_ops_per_sec": round(honest, 1),
        "baseline_impl": "cpp-single-core-scalar-apply",
        "python_oracle_ops_per_sec": round(baseline, 1),
        "docs": d,
        "rounds": rounds,
        "ops_per_doc": args.ops_per_doc,
        "ingest": "objects" if args.object_ingest else "frames",
        "wire_bytes_per_op": round(wire_bytes / total_ops, 2) if wire_bytes else None,
        "fallback_docs": fallbacks,
        "workload_gen_seconds": round(gen_time, 1),
        "wall_seconds": round(elapsed, 3),
        "stage_seconds": {k: round(v, 3) for k, v in stages.items()},
        "platform": jax.devices()[0].platform,
    }


def run_streaming_fused(args) -> dict:
    """Fused device-resident round pipeline vs per-round dispatch (ISSUE 9).

    The SAME generated workload runs through two arms on identical session
    configs: (a) the FUSED pipeline — pipelined drain committing staged
    multi-round programs (one concatenated tensor set + one dispatch per
    batch, state donated where the platform profits, flatten+upload on the
    double-buffered staging lane) with the drain-end fused resolve+digest
    pre-dispatch; (b) the pre-fusion PER-ROUND dispatch discipline
    (``fused_pipeline=False`` compat switch: one compact apply dispatch per
    round, per-round staging, unpipelined).  Byte equality of spans,
    incremental patches and full-state digests is asserted IN-ROW on every
    seed measured (the fuzz-seed oracle); the row's value is the fused
    arm's throughput.  Round caps sit below the streaming row's so each
    drain carries a genuinely multi-round queue — the scenario the fused
    dispatch exists for."""
    import jax

    from peritext_tpu.parallel.streaming import StreamingMerge
    from peritext_tpu.testing.fuzz import generate_workload

    d, rounds = args.docs, args.rounds
    gen_start = time.perf_counter()
    workloads = generate_workload(seed=args.seed, num_docs=d,
                                  ops_per_doc=args.ops_per_doc)
    gen_time = time.perf_counter() - gen_start
    arrival, _ = build_arrival(workloads, rounds, args.seed)
    total_ops = sum(
        len(ch.ops) for w in workloads for log in w.values() for ch in log
    )

    def session(fused: bool, prefetch: bool):
        s = StreamingMerge(
            num_docs=d,
            actors=("doc1", "doc2", "doc3"),
            slot_capacity=args.slots,
            mark_capacity=args.marks,
            tomb_capacity=args.slots,
            round_insert_capacity=48,
            round_delete_capacity=24,
            round_mark_capacity=24,
            round_map_capacity=12,
        )
        s.fused_pipeline = fused
        # the drain-end digest pre-dispatch pays off when reads/digests
        # follow EVERY drain (the serving pump — measured by the serve
        # row); this row digests once at the end, so the measured arm runs
        # prefetch off while the equality arms keep it on (its semantic
        # parity is part of the in-row oracle)
        s.prefetch_digest = fused and prefetch
        return s

    def run_arm(fused: bool, this_arrival=None, prefetch: bool = True):
        batches = this_arrival if this_arrival is not None else arrival
        s = session(fused, prefetch)
        stages = {"ingest": 0.0, "drain": 0.0, "digest": 0.0}
        t_all = time.perf_counter()
        for r in range(len(max(batches, key=len))):
            t0 = time.perf_counter()
            s.ingest_frames(
                (doc, b[r]) for doc, b in enumerate(batches) if r < len(b)
            )
            t1 = time.perf_counter()
            if fused:
                s.drain()
            else:
                while s.step() > 0:  # the per-round dispatch discipline
                    pass
            stages["ingest"] += t1 - t0
            stages["drain"] += time.perf_counter() - t1
        t0 = time.perf_counter()
        digest = s.digest()
        stages["digest"] += time.perf_counter() - t0
        return time.perf_counter() - t_all, digest, stages, s

    # warmup (compiles) + the measured seed's byte-equality assertion:
    # spans, incremental patches, digests — fused vs per-round
    _, dg_f, _, s_f = run_arm(True)
    _, dg_p, _, s_p = run_arm(False)
    assert dg_f == dg_p, f"fused digest {dg_f:#x} != per-round {dg_p:#x}"
    assert s_f.rounds == s_p.rounds
    assert s_f.read_all() == s_p.read_all()
    assert s_f.read_patches_all() == s_p.read_patches_all()
    fused_rounds = s_f.rounds

    # extra fuzz seeds: the equivalence must hold beyond the measured seed
    equality_seeds = [args.seed]
    for extra in (args.seed + 1, args.seed + 2):
        wl = generate_workload(seed=extra, num_docs=min(d, 16),
                               ops_per_doc=min(args.ops_per_doc, 64))
        arr, _ = build_arrival(wl, max(2, rounds // 2), extra)
        _, dg_a, _, sa = run_arm(True, arr)
        _, dg_b, _, sb = run_arm(False, arr)
        assert dg_a == dg_b, f"seed {extra}: fused/per-round digests differ"
        assert sa.read_all() == sb.read_all()
        equality_seeds.append(extra)

    def best_of(fused: bool):
        # the row's stager counters come from the BEST MEASURED run, so
        # the overlap accounting describes the execution whose wall the
        # row reports (not the prefetch-on warmup/equality arm)
        best, best_stages, best_stager, dg0 = None, None, None, None
        for _ in range(3):
            t, dg, st, sess = run_arm(fused, prefetch=False)
            if dg0 is None:
                dg0 = dg
            assert dg == dg0
            if best is None or t < best:
                best, best_stages = t, st
                best_stager = (sess._stager.stats()
                               if sess._stager is not None else None)
        return best, best_stages, best_stager

    fused_wall, fused_stages, stager_stats = best_of(True)
    per_round_wall, _, _ = best_of(False)

    baseline, native_baseline = _baselines_for(args.ops_per_doc, args.seed or 7)
    honest = native_baseline or baseline
    value = total_ops / fused_wall
    per_round_value = total_ops / per_round_wall
    return {
        "metric": "streaming_fused_crdt_ops_per_sec_per_chip",
        "value": round(value, 1),
        "unit": "ops/s",
        "vs_baseline": round(value / honest, 2),
        "baseline_ops_per_sec": round(honest, 1),
        "baseline_impl": "cpp-single-core-scalar-apply",
        "per_round_ops_per_sec": round(per_round_value, 1),
        "speedup_vs_per_round": round(value / per_round_value, 2),
        "byte_equal_seeds": equality_seeds,
        "docs": d,
        "rounds": rounds,
        "device_rounds": fused_rounds,
        "ops_per_doc": args.ops_per_doc,
        "workload_gen_seconds": round(gen_time, 1),
        "wall_seconds": round(fused_wall, 3),
        "per_round_wall_seconds": round(per_round_wall, 3),
        "stage_seconds": {k: round(v, 3) for k, v in fused_stages.items()},
        "stager": stager_stats,
        "platform": jax.devices()[0].platform,
    }


def _run_bounded(argv, timeout, env=None):
    """Run argv in its own session under a hard timeout; SIGKILL the whole
    process group on expiry (a plain terminate can leave child threads
    holding the pipe open).  Returns (rc, stdout, stderr); rc is None on
    timeout."""
    proc = subprocess.Popen(
        argv,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        start_new_session=True,
        env=env,
    )
    try:
        out, err = proc.communicate(timeout=timeout)
        return proc.returncode, out, err
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        out, err = proc.communicate()
        return None, out, err


def _select_platform(platform):
    """Worker start-up: pin ``platform`` when it is given (the explicit CPU
    rehearsal); otherwise the run needs the chip and fails without one.
    Turns on the persistent compile cache either way."""
    import jax

    from peritext_tpu.utils.platform import enable_compile_cache

    if platform:
        jax.config.update("jax_platforms", platform)
    found = jax.devices()[0].platform
    if platform is None and found != "tpu":
        raise SystemExit(
            f"bench: no TPU found (default backend is {found}); pass "
            f"--platform cpu to rehearse on the CPU"
        )
    enable_compile_cache()


def _parse_json_tail(out):
    """Last stdout line that parses as a JSON object (jax warnings precede it)."""
    for line in reversed(out.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def _worker_argv(extra):
    return [sys.executable, os.path.abspath(__file__), "--_worker", *extra]


def _append_ledger(path, rows, config, platform, devprof=None):
    """Append one perf-ledger record (obs/ledger.py) built from bench rows.

    Device fingerprinting here must NOT import jax — the orchestrator
    process never initializes a backend (it would hold the chip its
    workers need) — so the key is the measured rows' platform + host
    cores."""
    from peritext_tpu.obs import ledger as _ledger

    device = {"platform": platform, "kind": platform, "cpus": os.cpu_count()}
    record = _ledger.ledger_record(
        rows, config=config, devprof=devprof, device=device,
    )
    try:
        _ledger.append_record(path, record)
    except OSError as exc:  # an unwritable ledger must not cost the record
        print(f"bench: perf-ledger append failed: {exc}", file=sys.stderr)
        return
    print(f"bench: appended perf-ledger record ({len(rows)} row(s)) -> {path}",
          file=sys.stderr)


def orchestrate(args, passthrough) -> int:
    """Run one worker under a timeout and always print one JSON line.

    Exit 0 when the worker recorded a measurement; otherwise exit 1 with a
    structured ``"failed": true`` line carrying the error tail.  No retry,
    no other platform: without ``--platform`` the worker needs the chip."""
    worker_args = list(passthrough)
    if args.platform:
        worker_args += ["--platform", args.platform]
    rc, out, err = _run_bounded(_worker_argv(worker_args), WORKER_TIMEOUT)
    result = _parse_json_tail(out)
    if rc == 0 and result is not None:
        print(json.dumps(result))
        if args.ledger:
            row = dict(result)
            devprof = row.pop("devprof", None)
            row.setdefault("row", args.mode)
            _append_ledger(
                args.ledger, [row],
                config=args.mode + ("-smoke" if args.smoke else ""),
                platform=row.get("platform"),
                devprof={row["row"]: devprof} if devprof else None,
            )
        return 0
    status = "timed out" if rc is None else f"rc={rc}"
    tail = (err or out).strip()[-1500:]
    print(f"bench: worker {status}: {tail}", file=sys.stderr)
    metric_of_mode = {
        "streaming": "streaming_crdt_ops_per_sec_per_chip",
        "engine": "engine_limit_streaming_ops_per_sec_per_chip",
        "batch": "crdt_ops_per_sec_per_chip",
        "serve": "serve_sustained_docs_per_sec",
        "serve-fused": "serve_multitenant_dispatch_amortization",
        "mesh": "mesh_sustained_ops_per_sec",
        "storm": "reconnect_storm_drain_ops_per_sec",
        "longdoc": "longdoc_ragged_ops_per_sec",
        "markheavy": "markheavy_ops_per_sec",
        "fleet-serve": "fleet_serve_applied_frames_per_sec",
    }
    print(json.dumps({
        "metric": metric_of_mode.get(args.mode, "crdt_ops_per_sec_per_chip"),
        "value": None,
        "unit": "ops/s",
        "vs_baseline": None,
        "failed": True,
        "error": f"worker {status}: {tail}",
    }))
    return 1


def run_engine(args) -> dict:
    """Engine-limit streaming measurement (round-3 VERDICT item 3; round-5
    steady-state redefinition, VERDICT r4 task 2).

    The end-to-end streaming row is bounded by the host link (parse +
    transfer + dispatch latency); this mode measures the ENGINE itself: a
    real streaming session runs once with round capture enabled, recording
    every round's device-ready op streams, then the replay times pure
    device work — K chained apply programs plus the fused full-state digest
    — with zero host parse/schedule/transfer per round.

    Two numbers, mirroring the batch row's apply_seconds vs
    single_call_seconds split: the HEADLINE is steady-state throughput
    (several replay passes enqueued back-to-back, one sync — what a
    continuously-fed engine sustains, the per-measurement dispatch+sync
    cost amortized away), and ``engine_pass_seconds`` is the single-pass
    latency including that fixed cost (what one isolated
    ingest->converge->digest costs)."""
    import jax

    import jax.numpy as jnp

    from peritext_tpu.ops.kernel import apply_batch_compact_rounds_jit
    from peritext_tpu.ops.packed import empty_docs
    from peritext_tpu.parallel.streaming import (
        StreamingMerge, _resolve_block_digest_jit,
    )
    from peritext_tpu.testing.fuzz import generate_workload

    d, rounds = args.docs, args.rounds
    workloads = generate_workload(seed=args.seed, num_docs=d, ops_per_doc=args.ops_per_doc)
    arrival, _ = build_arrival(workloads, rounds, args.seed)

    def session(capture=None):
        s = StreamingMerge(
            num_docs=d,
            actors=("doc1", "doc2", "doc3"),
            slot_capacity=args.slots,
            mark_capacity=args.marks,
            tomb_capacity=args.slots,
            round_insert_capacity=256,
            round_delete_capacity=128,
            round_mark_capacity=128,
        )
        s._capture_rounds = capture
        t0 = time.perf_counter()
        for r in range(rounds):
            s.ingest_frames(
                (doc, batches[r]) for doc, batches in enumerate(arrival)
                if r < len(batches)
            )
            s.drain()
        digest = s.digest()
        return s, digest, time.perf_counter() - t0

    captured: list = []
    s, expected_digest, _ = session(captured)  # warmup run (compiles) + capture
    _, digest2, end_to_end = session()  # warm end-to-end reference
    assert digest2 == expected_digest, "end-to-end sessions disagree"
    assert not any(sess.fallback for sess in s.docs), \
        "fallback docs would skew the engine row (raise capacities)"
    # overflowed docs are hashed HOST-side by digest() but masked in the
    # device-only replay sum — they would break the digest cross-check below
    assert s.overflow_count() == 0, \
        f"{s.overflow_count()} docs overflowed device capacities (raise --slots/--marks)"

    # replay: pre-stage everything device-side, then chain the rounds.
    # The captured rounds are _padded_docs-shaped (meshless sessions pad to
    # a read-block multiple), so the replay state must match.
    state0 = empty_docs(s._padded_docs, args.slots, args.marks,
                        tomb_capacity=args.slots)
    state0 = jax.device_put(state0)
    staged = [
        ((tuple(jax.device_put(np.asarray(c)) for c in counts),
          ins, dels, marks, maps), widths, loop_slots)
        for (counts, ins, dels, marks, maps), widths, loop_slots in captured
    ]
    tables = s._digest_tables(0, s._padded_docs)
    row_mask = jnp.ones(s._padded_docs, bool)

    def engine_pass_async():
        """Dispatch one full replay (rounds fused in FUSE_MAX_ROUNDS
        chunks, exactly as the live drain() fuses a deep queue, plus the
        fused resolve/digest); returns the device per-doc hash vector
        WITHOUT syncing."""
        fmax = StreamingMerge.FUSE_MAX_ROUNDS
        st = state0
        for lo in range(0, len(staged), fmax):
            part = staged[lo:lo + fmax]
            st = apply_batch_compact_rounds_jit(
                st, [r[0] for r in part],
                widths_seq=[r[1] for r in part],
                loop_slots_seq=[r[2] for r in part],
            )
        _, per_doc = _resolve_block_digest_jit(
            st, s.comment_capacity, row_mask, *tables
        )
        return per_doc

    def digest_of(per_doc):
        # the sync point (per-doc hash vector; block sum = digest)
        return int(np.asarray(per_doc).sum(dtype=np.uint32))

    warm = digest_of(engine_pass_async())  # warmup + correctness
    assert warm == expected_digest, \
        f"engine replay digest {warm:#x} != live session {expected_digest:#x}"
    # single-pass latency: dispatch -> converged digest on host, incl. the
    # fixed per-measurement dispatch+sync cost
    lat_times = []
    for _ in range(3):
        t0 = time.perf_counter()
        digest = digest_of(engine_pass_async())
        lat_times.append(time.perf_counter() - t0)
    assert digest == expected_digest, "engine replay digest drifted across passes"
    latency = min(lat_times)

    # steady-state: enqueue several independent replay passes back-to-back
    # (the device executes queued programs serially) and sync ONLY the
    # last pass inside the clock — it completes after all queued
    # predecessors, so the timed region holds one sync, not one per
    # pass; every pass's digest is verified after the clock stops
    passes = max(2, int(args.iters) // 2)
    t0 = time.perf_counter()
    per_docs = [engine_pass_async() for _ in range(passes)]
    last_digest = digest_of(per_docs[-1])
    steady = (time.perf_counter() - t0) / passes
    digests = [digest_of(p) for p in per_docs[:-1]] + [last_digest]
    assert all(g == expected_digest for g in digests), \
        "steady-state engine pass diverged"

    total_ops = sum(
        len(ch.ops) for w in workloads for log in w.values() for ch in log
    )
    value = total_ops / steady
    return {
        "metric": "engine_limit_streaming_ops_per_sec_per_chip",
        "value": round(value, 1),
        "unit": "ops/s",
        "vs_baseline": round(value / (total_ops / end_to_end), 2),
        "baseline_impl": "same session end-to-end (host parse + transfer + dispatch)",
        "end_to_end_ops_per_sec": round(total_ops / end_to_end, 1),
        "single_pass_ops_per_sec": round(total_ops / latency, 1),
        "docs": d,
        "rounds": len(staged),
        "ops_per_doc": args.ops_per_doc,
        "steady_passes": passes,
        "engine_wall_seconds": round(steady, 3),
        "engine_pass_seconds": round(latency, 3),
        "end_to_end_wall_seconds": round(end_to_end, 3),
        "platform": jax.devices()[0].platform,
    }


def run_baselines(args) -> dict:
    """Scalar baselines row (BASELINE config 1): the pure-Python oracle and
    the C++ single-core apply, measured once per ladder and shared with the
    other rows via PT_BENCH_BASELINES."""
    python = measure_scalar_baseline()
    native = measure_native_baseline(ops_per_doc=256, seed=7)
    return {
        "metric": "baseline_ops_per_sec",
        "value": round(native or python, 1),
        "unit": "ops/s",
        "vs_baseline": 1.0,
        "baseline_impl": "cpp-single-core-scalar-apply" if native
                         else "python-scalar-oracle",
        "scalar_python_ops_per_sec": round(python, 1),
        "native_cpp_ops_per_sec": round(native, 1) if native else None,
        "native_ops_per_doc": 256,
        "platform": "cpu",
    }


def run_wire(args) -> dict:
    """Wire-efficiency row: bytes/op of the binary frame codec on the three
    shapes the round-3 analysis tracks (VERDICT r3 weak #4) — interactive
    typing, a causal fuzz session, and the streaming bench's arrival frames
    — each against the reference's JSON-per-change wire
    (src/micromerge.ts:563-564) as the compression baseline.  Each shape is
    measured self-contained (v2) and through a session-scoped WireSession
    (v4: persistent string dictionary + deflate, VERDICT r3 task 3).
    Host-only: no device work, so the row is platform-independent."""
    from peritext_tpu.core.doc import Doc
    from peritext_tpu.parallel.causal import causal_sort
    from peritext_tpu.parallel.codec import WireSession, decode_frame, encode_frame
    from peritext_tpu.testing.fuzz import generate_workload

    def json_bytes(chs):
        return sum(len(json.dumps(c.to_json()).encode()) for c in chs)

    def session_bytes(frame_batches):
        """Total v4 bytes: one WireSession per link, frames in order."""
        enc = WireSession(compress=True)
        dec = WireSession(compress=True)
        total = 0
        for chs in frame_batches:
            f = enc.encode_frame(chs)
            assert dec.decode_frame(f) == chs
            total += len(f)
        return total

    shapes = {}

    # typing shape: 20 multi-char inserts (the reference's chained-op path)
    d = Doc("alice")
    chs = [d.change([{"path": [], "action": "makeList", "key": "text"}])[0]]
    text = "The quick brown fox jumps over the lazy dog. " * 20
    pos = 0
    for i in range(20):
        seg = text[i * 45:(i + 1) * 45]
        chs.append(d.change([{"path": ["text"], "action": "insert",
                              "index": pos, "values": list(seg)}])[0])
        pos += len(seg)
    f = encode_frame(chs)
    assert decode_frame(f) == chs
    n = sum(len(c.ops) for c in chs)
    shapes["typing"] = {
        "bytes_per_op": round(len(f) / n, 2),
        "session_bytes_per_op": round(session_bytes([chs]) / n, 2),
        "json_bytes_per_op": round(json_bytes(chs) / n, 2),
        "ops": n,
    }

    # fuzz-session shape: causally-ordered 3-replica session logs
    tot_b = tot_o = tot_j = tot_s = 0
    for wl in generate_workload(seed=21, num_docs=3, ops_per_doc=140):
        sess = causal_sort([ch for log in wl.values() for ch in log])
        f = encode_frame(sess)
        assert decode_frame(f) == sess
        tot_b += len(f)
        tot_s += session_bytes([sess])
        tot_j += json_bytes(sess)
        tot_o += sum(len(c.ops) for c in sess)
    shapes["fuzz_session"] = {
        "bytes_per_op": round(tot_b / tot_o, 2),
        "session_bytes_per_op": round(tot_s / tot_o, 2),
        "json_bytes_per_op": round(tot_j / tot_o, 2),
        "ops": tot_o,
    }

    # streaming-bench shape: the arrival frames the streaming row pays, in
    # both arrival models (shuffle = r1-r3 record continuity; fifo = what
    # TCP + changeQueue actually deliver) and both wire generations
    docs = args.docs
    workloads = generate_workload(seed=args.seed, num_docs=docs, ops_per_doc=192)
    total_ops = sum(len(c.ops) for w in workloads for log in w.values() for c in log)
    sample_json = sum(
        json_bytes([c for log in w.values() for c in log]) for w in workloads[:32]
    )
    sample_ops = sum(
        len(c.ops) for w in workloads[:32] for log in w.values() for c in log
    )
    variants = {}
    for model in ("shuffle", "fifo"):
        for wire in ("v2", "v4"):
            _, wb = build_arrival(workloads, rounds=4, seed=args.seed,
                                  arrival_model=model, wire=wire)
            variants[f"{model}_{wire}"] = round(wb / total_ops, 2)
    # host-link model: a DCN link between two hosts muxes EVERY doc's frames
    # through one WireSession (per-doc sessions above are the conservative
    # bound — real deployments share the link dictionary + deflate window)
    from peritext_tpu.parallel.codec import WireSession as _WS

    batches, _ = build_arrival(workloads, rounds=4, seed=args.seed,
                               as_frames=False, arrival_model="fifo")
    enc, dec = _WS(compress=True), _WS(compress=True)
    link_bytes = 0
    for r in range(4):
        for doc_batches in batches:
            if r < len(doc_batches):
                b = sorted(doc_batches[r], key=lambda c: (c.actor, c.seq))
                f = enc.encode_frame(b)
                assert dec.decode_frame(f) == b
                link_bytes += len(f)
    variants["fifo_v4_host_link"] = round(link_bytes / total_ops, 2)
    # per-doc links with the protocol preset dictionary (codec.WireSession
    # preset=True): a fresh link's deflate window is primed so first frames
    # back-reference the dictionary the way a warm link references its own
    # window — the per-doc-link answer to the <=6 target (VERDICT r4 task 8)
    preset_bytes = 0
    for doc_batches in batches:
        enc = _WS(compress=True, preset=True)
        dec = _WS(compress=True, preset=True)
        for b in doc_batches:
            b = sorted(b, key=lambda c: (c.actor, c.seq))
            f = enc.encode_frame(b)
            assert dec.decode_frame(f) == b
            preset_bytes += len(f)
    variants["fifo_v4_preset"] = round(preset_bytes / total_ops, 2)
    shapes["bench_frames"] = {
        "bytes_per_op": variants["shuffle_v2"],   # r1-r3 continuity number
        "variants_bytes_per_op": variants,
        "session_bytes_per_op": variants["fifo_v4_host_link"],  # real transport
        "json_bytes_per_op": round(sample_json / sample_ops, 2),
        "ops": total_ops,
        "docs": docs,
    }

    headline = shapes["bench_frames"]["session_bytes_per_op"]
    return {
        "metric": "wire_bytes_per_op",
        "value": headline,
        "unit": "B/op",
        # vs the JSON wire: how many times smaller the binary frames are
        "vs_baseline": round(shapes["bench_frames"]["json_bytes_per_op"] / headline, 2),
        "baseline_impl": "json-encoded changes (reference wire, src/micromerge.ts:563)",
        "shapes": shapes,
        "platform": "host",
    }


def run_fleet_heal(args) -> dict:
    """Fleet-heal row (ISSUE 4): time-to-convergence and ops drained per
    second after a simulated partition heal.  Drives the chaos fleet
    harness (``testing/chaos.run_fleet_chaos``): an N-host ReplicaServer
    fleet diverges under an asymmetric partition, then the gossip
    scheduler's most-behind-first rounds drain it; the row reports how fast
    the anti-entropy layer re-converges the fleet.  Host-only (TCP +
    codec + store work, no device), so the row is platform-independent."""
    from peritext_tpu.testing.chaos import run_fleet_chaos

    hosts = 3 if args.smoke else 4
    reports = []
    for i in range(max(1, min(args.iters, 3))):
        reports.append(run_fleet_chaos(args.seed + i, hosts=hosts,
                                       metrics=False))
    best = max(reports, key=lambda r: r.ops_drained / max(r.heal_seconds, 1e-9))
    rate = best.ops_drained / max(best.heal_seconds, 1e-9)
    return {
        "metric": "fleet_heal_ops_per_sec",
        "value": round(rate, 1),
        "unit": "ops/s",
        "baseline_impl": "asymmetric-partition heal over localhost TCP gates",
        "hosts": hosts,
        "episodes": len(reports),
        "time_to_convergence_s": round(best.heal_seconds, 4),
        "heal_rounds": best.heal_rounds,
        "ops_drained": best.ops_drained,
        "partition_lag_ops": sum(best.expected_lag.values()),
        "converged": all(r.converged for r in reports),
        "platform": "host",
    }


def run_serve(args) -> dict:
    """Serving-tier row (ISSUE 7): sustained OPEN-LOOP traffic ladder.

    Drives a :class:`~peritext_tpu.serve.SessionMux` (admission control +
    autotuned round window over a streaming session) with an open-loop
    arrival schedule — arrival times fixed by the offered rate, never by
    service completions — sweeping the rate upward until the p99
    apply-latency SLO breaks or verdicts stop being clean.  The headline is
    docs/s at the SLO (each arrival is one session's frame), the breakdown
    rung is recorded too, and the typed-verdict accounting plus the
    autotuned window land in the row for the serve exporters' story."""
    import jax

    from peritext_tpu.parallel.codec import encode_frame
    from peritext_tpu.parallel.streaming import StreamingMerge
    from peritext_tpu.serve import (
        AdmissionController, SessionMux, sustained_ladder,
    )
    from peritext_tpu.testing.fuzz import generate_workload

    d = args.docs
    slo_s = args.serve_slo_ms / 1e3
    workloads = generate_workload(seed=args.seed + 11, num_docs=d,
                                  ops_per_doc=args.ops_per_doc)
    frame_plans = []
    for w in workloads:
        changes = [ch for log in w.values() for ch in log]
        frame_plans.append([
            encode_frame(changes[i:i + 6])
            for i in range(0, len(changes), 6)
        ])

    def serve_session():
        # static_rounds: the serving-tier shape discipline — one padded
        # apply shape for the session's lifetime, so an arrival pattern
        # can never mint an XLA compile inside a client's p99
        opd = args.ops_per_doc
        return StreamingMerge(
            num_docs=d, actors=("doc1", "doc2", "doc3"),
            slot_capacity=max(256, 4 * opd), mark_capacity=max(64, opd),
            tomb_capacity=max(128, opd),
            round_insert_capacity=128, round_delete_capacity=64,
            round_mark_capacity=64,
            static_rounds=True,
        )

    def mux_factory():
        mux = SessionMux(
            serve_session(),
            admission=AdmissionController(
                max_depth=max(256, 4 * d), session_quota=None,
            ),
            host="bench",
        )
        frames = {}
        for doc in range(d):
            sid, verdict = mux.open_session(f"client{doc}")
            assert verdict.admitted
            frames[sid] = frame_plans[doc]
        return mux, frames

    # warmup: compile the apply/digest programs OUTSIDE the measured rungs.
    # Trickle rounds pick ADAPTIVE power-of-two round widths (streaming's
    # shape discipline), so each distinct batch size class can mint a new
    # XLA variant — walk the batch-size ladder once so no rung pays a
    # compile inside its latency percentile.
    mux, frames = mux_factory()
    sids = sorted(frames)
    cursor = {sid: 0 for sid in sids}
    batch_size = 1
    while batch_size <= 2 * d:
        for i in range(batch_size):
            sid = sids[i % len(sids)]
            plan = frames[sid]
            mux.submit(sid, plan[cursor[sid] % len(plan)])
            cursor[sid] += 1
        mux.flush()
        batch_size *= 2

    base = 25.0 if args.smoke else 50.0
    rates = [base * (2 ** i) for i in range(11 if args.smoke else 12)]
    duration = 0.5 if args.smoke else 1.5
    rungs, best = sustained_ladder(
        mux_factory, rates, slo_p99_s=slo_s, duration_s=duration,
        warmup=2,
    )
    broke = next((r for r in rungs if not r.sustained), None)
    if best is not None and broke is not None:
        # refine between the last sustained and the breaking rung: the x2
        # sweep quantizes the headline to a factor of two, which is wider
        # than the perf ledger's wall-clock band — one midpoint rung
        # tightens resolution to x1.5
        mid_rungs, mid_best = sustained_ladder(
            mux_factory, [best.rate_per_s * 1.5], slo_p99_s=slo_s,
            duration_s=duration, warmup=1,
        )
        rungs.extend(mid_rungs)
        if mid_best is not None:
            best = mid_best
    value = best.rate_per_s if best is not None else 0.0

    # traced pass: one extra sustained-rate rung on a fresh mux with the
    # latency plane armed — OUTSIDE the measured ladder, so arming cost
    # can never touch the headline.  read_every marks visibility, so the
    # row's decomposition carries the full admit→visibility story, and
    # the sum-consistency oracle is asserted IN-ROW.
    from peritext_tpu.obs.latency import LatencyPlane
    from peritext_tpu.obs.timeseries import TimeSeriesPlane
    from peritext_tpu.serve import build_arrivals, run_open_loop

    tmux, tframes = mux_factory()
    tmux.latency_plane = LatencyPlane().enable()
    # the history plane rides the same traced rung: one retained frame
    # per settled batch, so the row carries the trend view's raw feed
    hist = TimeSeriesPlane(sample_every=1, min_frames=4)
    tmux.history_plane = hist.enable()
    trace_rate = max(base, value / 2.0) if value else base
    traced = run_open_loop(
        tmux, build_arrivals(tframes, trace_rate, duration),
        deadline_s=max(duration * 4, duration + 2.0), read_every=4,
    )
    lat = traced.latency
    assert lat is not None and lat["records"] > 0, (
        "armed latency plane sampled no drain batches in the traced rung"
    )
    assert lat["sum_consistent"], f"latency decomposition inconsistent: {lat}"
    assert all(v >= 0 for v in lat["stages_ms"].values()), (
        f"negative stage duration: {lat['stages_ms']}"
    )
    assert hist.frames_sampled > 0, (
        "armed history plane retained no frames in the traced rung"
    )

    return {
        "metric": "serve_sustained_docs_per_sec",
        "value": round(value, 1),
        "unit": "docs/s",
        "vs_baseline": None,
        "baseline_impl": "open-loop arrival ladder vs p99 apply-latency SLO",
        "slo_p99_ms": args.serve_slo_ms,
        "docs": d,
        "ops_per_doc": args.ops_per_doc,
        "sessions": d,
        "rung_duration_s": duration,
        "sustained_rung": best.to_json() if best is not None else None,
        "breaking_rung": broke.to_json() if broke is not None else None,
        # every offered rate sustained: the true ceiling is above the sweep
        "ladder_exhausted": broke is None,
        "latency": lat,
        "history": {
            "frames_sampled": hist.frames_sampled,
            "frames_retained": sum(hist.snapshot()["tier_frames"]),
            "rounds": hist.rounds,
            "anomalies_total": hist.anomalies_total,
        },
        "traced_rate_per_s": round(trace_rate, 1),
        "rungs": [r.to_json() for r in rungs],
        "window": (best.result.window_seconds if best is not None else None),
        "platform": jax.devices()[0].platform,
    }


def run_serve_fused(args) -> dict:
    """Multi-tenant fused-dispatch row (ISSUE 13): N small tenants served
    through ONE :class:`~peritext_tpu.serve.FusedMuxGroup` lane vs N
    standalone per-session muxes, same frames, same windows.

    The fused arm commits each batching window as one staged device
    program per touched lane (the plan tier's
    :class:`~peritext_tpu.plan.fusion.FusionGroup` assigns disjoint
    doc-row ranges; sparse windows ride the multi-tenant offset-plane
    staged form); the per-session arm drains every tenant separately —
    the dispatch-floor bill this row exists to show.  Byte equality of
    every tenant's patch stream against its standalone twin is asserted
    IN-ROW (the CRDT correctness oracle), and both arms' p99 apply
    latencies ride along.  Headline = device programs per window saved:
    per-session dispatches / fused dispatches."""
    import jax

    from peritext_tpu.obs import GLOBAL_COUNTERS
    from peritext_tpu.parallel.codec import encode_frame
    from peritext_tpu.parallel.streaming import StreamingMerge
    from peritext_tpu.plan.fusion import TenantSpec
    from peritext_tpu.serve import (
        FusedMuxGroup, SessionMux, default_lane_factory,
    )
    from peritext_tpu.testing.fuzz import generate_workload

    tenants_n = args.docs  # one small tenant per doc slot
    opd = args.ops_per_doc
    actors = ("doc1", "doc2", "doc3")
    windows = 6
    workloads = generate_workload(seed=args.seed + 13, num_docs=tenants_n,
                                  ops_per_doc=opd)
    names = [f"tenant{i:03d}" for i in range(tenants_n)]
    frame_plans = {}
    for name, w in zip(names, workloads):
        changes = sorted((ch for log in w.values() for ch in log),
                         key=lambda c: (c.actor, c.seq))
        frame_plans[name] = [
            encode_frame(changes[i::windows]) for i in range(windows)
        ]
    # window plan: alternating full and sparse activity — the sparse
    # windows exercise the multi-tenant offset-plane staged form (only
    # the active tenants' doc blocks ship), the full ones the shared
    # full-lane staging.  Every tenant's frames stay in causal order.
    active_of = []
    cursor = {n: 0 for n in names}
    for w in range(windows):
        if w % 2 == 0:
            active_of.append(list(names))
        else:
            active_of.append(names[(w // 2) % 4::4])
    plan = []  # (window, tenant, frame)
    for w, active in enumerate(active_of):
        step = []
        for n in active:
            if cursor[n] < windows:
                step.append((n, frame_plans[n][cursor[n]]))
                cursor[n] += 1
        plan.append(step)
    # leftover frames drain in a final full window
    tail = [(n, frame_plans[n][c])
            for n in names for c in range(cursor[n], windows)]
    if tail:
        plan.append(tail)

    session_kw = dict(
        slot_capacity=max(256, 4 * opd), mark_capacity=max(64, opd),
        tomb_capacity=max(128, opd),
        round_insert_capacity=128, round_delete_capacity=64,
        round_mark_capacity=64,
    )

    def build_group():
        group = FusedMuxGroup(
            [TenantSpec(tenant=n, docs=1) for n in names],
            default_lane_factory(actors, **session_kw),
            host="bench-fused",
        )
        sids = {}
        for n in names:
            sid, verdict = group.open_session(n, "client")
            assert verdict.admitted
            sids[n] = sid
            group.muxes[n].latency_sink = []
        return group, sids

    def build_solo():
        muxes, sids = {}, {}
        for n in names:
            mux = SessionMux(
                StreamingMerge(num_docs=1, actors=actors,
                               static_rounds=True, **session_kw),
                host="bench-solo",
            )
            sid, verdict = mux.open_session("client")
            assert verdict.admitted
            muxes[n], sids[n] = mux, sid
            mux.latency_sink = []
        return muxes, sids

    def drive_group(group, sids):
        d0 = GLOBAL_COUNTERS.get("streaming.fused_dispatches")
        t0 = time.perf_counter()
        for step in plan:
            for n, frame in step:
                verdict = group.submit(n, sids[n], frame)
                assert verdict.admitted, verdict
            group.flush()
        wall = time.perf_counter() - t0
        return (int(GLOBAL_COUNTERS.get("streaming.fused_dispatches") - d0),
                wall)

    def drive_solo(muxes, sids):
        d0 = GLOBAL_COUNTERS.get("streaming.fused_dispatches")
        t0 = time.perf_counter()
        for step in plan:
            touched = []
            for n, frame in step:
                verdict = muxes[n].submit(sids[n], frame)
                assert verdict.admitted, verdict
                touched.append(n)
            for n in dict.fromkeys(touched):
                muxes[n].flush()
        wall = time.perf_counter() - t0
        return (int(GLOBAL_COUNTERS.get("streaming.fused_dispatches") - d0),
                wall)

    def p99_ms(sinks):
        lats = sorted(x for sink in sinks for x in sink)
        if not lats:
            return None
        return round(lats[min(len(lats) - 1, int(0.99 * len(lats)))] * 1e3, 3)

    # warmup: walk both arms once on throwaway instances so every staged
    # variant (full-lane, offset-plane, per-session) compiles OUTSIDE the
    # measured pass — steady-state serving never pays an XLA compile
    drive_group(*build_group())
    drive_solo(*build_solo())

    group, gsids = build_group()
    # arm ONE shared plane across every fused lane: the row's per-stage
    # decomposition spans the whole tenant fleet, and the patch-equality
    # reads below double as the visibility watermark
    from peritext_tpu.obs.latency import LatencyPlane
    from peritext_tpu.obs.timeseries import TimeSeriesPlane

    plane = LatencyPlane().enable()
    # ...and ONE history plane: pump() feeds it an occupancy row per lane
    # per committed window — the raw material `propose(history=...)`
    # weights the cost model by (the closed planner loop)
    hist = TimeSeriesPlane(sample_every=1, min_frames=4)
    group.history = hist.enable()
    for n in names:
        group.muxes[n].latency_plane = plane
    fused_dispatches, fused_wall = drive_group(group, gsids)
    muxes, ssids = build_solo()
    solo_dispatches, solo_wall = drive_solo(muxes, ssids)

    # the correctness oracle: every tenant's patch stream byte-equal to
    # its standalone twin's
    for n in names:
        fused_patches = group.patches(n, gsids[n])
        solo_patches = muxes[n].patches(ssids[n])
        assert fused_patches == solo_patches, (
            f"fused/unfused patch divergence for {n}"
        )
    fusion = group.fusion_snapshot()
    amortization = (solo_dispatches / fused_dispatches
                    if fused_dispatches else 0.0)
    lat = plane.decomposition()
    assert lat["records"] > 0, (
        "armed latency plane sampled no fused drain batches"
    )
    assert lat["sum_consistent"], f"latency decomposition inconsistent: {lat}"
    assert all(v >= 0 for v in lat["stages_ms"].values()), (
        f"negative stage duration: {lat['stages_ms']}"
    )
    occ_rows = hist.occupancy_rows()
    assert occ_rows, (
        "armed history plane recorded no fused occupancy rows"
    )
    return {
        "metric": "serve_multitenant_dispatch_amortization",
        "value": round(amortization, 2),
        "unit": "x",
        "vs_baseline": round(solo_wall / fused_wall, 2) if fused_wall else None,
        "baseline_impl": "one standalone SessionMux drain per tenant",
        "tenants": tenants_n,
        "ops_per_doc": opd,
        "windows": len(plan),
        "fused_dispatches": fused_dispatches,
        "per_session_dispatches": solo_dispatches,
        "fused_wall_s": round(fused_wall, 4),
        "per_session_wall_s": round(solo_wall, 4),
        "fused_p99_apply_ms": p99_ms(
            [group.muxes[n].latency_sink for n in names]
        ),
        "per_session_p99_apply_ms": p99_ms(
            [muxes[n].latency_sink for n in names]
        ),
        "byte_equal": True,
        "latency": lat,
        "history_occupancy_rows": len(occ_rows),
        "history_occupancy": hist.snapshot()["occupancy"]["distribution"],
        "docs_per_dispatch": fusion["docs_per_dispatch"],
        "window_occupancy": fusion["window_occupancy"],
        "platform": jax.devices()[0].platform,
    }


def run_storm(args) -> dict:
    """Reconnect-storm row (ISSUE 7 / ROADMAP scenario item): a peer back
    from a long offline window drains a giant backlog through one gossip
    exchange WHILE the serving tier carries open-loop traffic.  Reports the
    backlog drain rate; the serving tier's p99 during the storm and the
    typed-verdict accounting ride along.  The same episode runs as a chaos
    schedule (testing/chaos.run_reconnect_storm asserts the oracles)."""
    import jax

    from peritext_tpu.testing.chaos import run_reconnect_storm

    backlog = 500 if args.smoke else 4000
    report = run_reconnect_storm(
        args.seed + 3, backlog_ops=backlog, num_docs=args.docs,
        ops_per_doc=args.ops_per_doc,
        serve_rate_per_s=100.0 if args.smoke else 250.0,
        storm_duration_s=0.5 if args.smoke else 1.5,
    )
    return {
        "metric": "reconnect_storm_drain_ops_per_sec",
        "value": report.drain_ops_per_sec,
        "unit": "ops/s",
        "vs_baseline": None,
        "baseline_impl": "gossip backlog drain concurrent with open-loop serving",
        "backlog_ops": report.backlog_ops,
        "drain_seconds": report.drain_seconds,
        "serve_offered": report.offered,
        "serve_admitted": report.admitted,
        "serve_shed": report.shed,
        "serve_delayed": report.delayed,
        "serve_p99_apply_ms": report.p99_apply_ms,
        "serve_rounds": report.served_rounds,
        "queue_peak": report.queue_peak,
        "converged": report.converged,
        "platform": jax.devices()[0].platform,
    }


def run_longdoc(args) -> dict:
    """Long-tail workload family (ISSUE 8): one giant essay among a fleet
    of tweets — the distribution the padded (doc x op) layout is worst at,
    because every tweet pays the essay's stream width and slot bucket.

    The SAME workload merges through the padded DocBatch (the byte-equality
    oracle), the paged DocBatch (store/: page pool + per-doc page tables,
    size-bucketed groups) and the ragged DocBatch (ops/ragged.py: one
    program over the pool, per-doc counts as data — ISSUE 12); the row
    asserts byte equality, then reports every layout's wall clock and
    padded-op waste.  Headline = ragged throughput; ``vs_baseline`` =
    ragged/paged speedup (the bucket ladder this layout kills);
    ``vs_padded`` and the waste ratio (absolute padded ops burned,
    padded / paged; ragged burns ZERO) ride along.  ``--docs`` sizes the
    tweet fleet, ``--ops-per-doc`` the essay."""
    import jax

    from peritext_tpu.api.batch import DocBatch
    from peritext_tpu.testing.fuzz import generate_workload

    d_small, big_ops, small_ops = args.docs, args.ops_per_doc, 8
    gen_start = time.perf_counter()
    workloads = generate_workload(seed=args.seed + 1, num_docs=d_small,
                                  ops_per_doc=small_ops)
    workloads += generate_workload(seed=args.seed + 90_001, num_docs=1,
                                   ops_per_doc=big_ops)
    gen_time = time.perf_counter() - gen_start
    total_ops = sum(
        len(ch.ops) for w in workloads for log in w.values() for ch in log
    )

    # slot capacity: power of two covering the essay (both layouts share
    # it — the padded layout must pay it for EVERY doc, which is the row's
    # whole point; paged pays it only in the essay's page table).  Rounded
    # to a page multiple so an odd --slots can't pass the padded half and
    # then crash the paged half's alignment check.
    from peritext_tpu.store import DEFAULT_PAGE_SIZE

    slots = args.slots or 256
    while slots < big_ops:
        slots *= 2
    slots = -(-slots // DEFAULT_PAGE_SIZE) * DEFAULT_PAGE_SIZE
    marks = args.marks or max(64, big_ops // 4)

    def measure(layout):
        batch = DocBatch(slot_capacity=slots, mark_capacity=marks,
                         layout=layout)
        report = batch.merge(workloads)  # warmup (compiles)
        t_best = None
        for _ in range(2):
            t0 = time.perf_counter()
            report = batch.merge(workloads)
            dt = time.perf_counter() - t0
            t_best = dt if t_best is None or dt < t_best else t_best
        return batch, report, t_best

    padded_batch, padded, wall_padded = measure("padded")
    paged_batch, paged, wall_paged = measure("paged")
    ragged_batch, ragged, wall_ragged = measure("ragged")
    for name, rep in (("paged", paged), ("ragged", ragged)):
        assert padded.spans == rep.spans, f"{name} layout diverged from padded"
        assert padded.roots == rep.roots, f"{name} roots diverged from padded"
        assert padded.fallback_docs == rep.fallback_docs

    # padded-op waste: absolute padded stream ops burned per layout (the
    # devprof occupancy quantity, derivable here from padding_efficiency)
    def wasted(report):
        eff = report.stats.padding_efficiency
        real = report.stats.device_ops + report.stats.fallback_ops
        capacity = real / eff if eff else 0.0
        return capacity - real, capacity

    waste_padded, cap_padded = wasted(padded)
    waste_paged, cap_paged = wasted(paged)
    waste_ragged, cap_ragged = wasted(ragged)
    pool_paged = paged_batch.last_store.pool_stats()
    pool = ragged_batch.last_store.pool_stats()
    value = total_ops / wall_ragged
    return {
        "metric": "longdoc_ragged_ops_per_sec",
        "value": round(value, 1),
        "unit": "ops/s",
        "vs_baseline": round(wall_paged / wall_ragged, 2),
        "baseline_impl": "same long-tail workload through the paged "
                         "(pow-2 bucketed) layout",
        "vs_padded": round(wall_padded / wall_ragged, 2),
        "docs": d_small + 1,
        "small_doc_ops": small_ops,
        "big_doc_ops": big_ops,
        "total_ops": total_ops,
        "slot_capacity": slots,
        "byte_equal": True,
        "padded_ops_per_sec": round(total_ops / wall_padded, 1),
        "paged_ops_per_sec": round(total_ops / wall_paged, 1),
        "wall_padded_s": round(wall_padded, 3),
        "wall_paged_s": round(wall_paged, 3),
        "wall_ragged_s": round(wall_ragged, 3),
        "stream_capacity_padded": round(cap_padded),
        "stream_capacity_paged": round(cap_paged),
        "stream_capacity_ragged": round(cap_ragged),
        "padded_ops_wasted": round(waste_padded),
        "paged_ops_wasted": round(waste_paged),
        "ragged_ops_wasted": round(waste_ragged),
        "waste_ratio": round(waste_padded / waste_paged, 2) if waste_paged else None,
        "state_slots_padded": (d_small + 1) * slots,
        "state_slots_paged": pool_paged["pages_in_use"] * pool_paged["page_size"],
        "state_slots_ragged": pool["pages_in_use"] * pool["page_size"],
        "page_pool": pool,
        "workload_gen_seconds": round(gen_time, 1),
        "platform": jax.devices()[0].platform,
    }


def run_markheavy(args) -> dict:
    """Mark-heavy editorial-pass row (ISSUE 10 / ROADMAP scenario
    diversity): the span-overlap-explosion workload family — mostly long
    overlapping addMark/removeMark spans over a thin insert substrate —
    streamed through a session with the byte-equality oracle ATTACHED
    (device spans must equal the scalar oracle's, in-row).  Reports
    streaming throughput on the mark-heavy mix plus the mark/op ratio; the
    same family runs as a chaos schedule
    (testing/chaos.run_markheavy_chaos)."""
    import jax

    from peritext_tpu.api.batch import _oracle_doc
    from peritext_tpu.parallel.codec import encode_frame
    from peritext_tpu.testing.fuzz import (
        _campaign_session, generate_markheavy_workload,
    )

    d, opd = args.docs, args.ops_per_doc
    gen_start = time.perf_counter()
    workloads = generate_markheavy_workload(
        seed=args.seed + 17, num_docs=d, ops_per_doc=opd,
    )
    gen_time = time.perf_counter() - gen_start
    total_ops = 0
    mark_ops = 0
    for w in workloads:
        for log in w.values():
            for ch in log:
                for op in ch.ops:
                    total_ops += 1
                    if op.action in ("addMark", "removeMark"):
                        mark_ops += 1
    plans = []
    for w in workloads:
        changes = [ch for log in sorted(w) for ch in w[log]]
        plans.append([
            encode_frame(changes[i:i + 8])
            for i in range(0, len(changes), 8)
        ])

    def feed():
        session = _campaign_session(d, opd)
        for doc, frames in enumerate(plans):
            for f in frames:
                session.ingest_frame(doc, f)
        while session.drain() > 0:
            pass
        session.digest()
        return session

    feed()  # warmup (compiles)
    t_best = None
    for _ in range(2):
        t0 = time.perf_counter()
        session = feed()
        dt = time.perf_counter() - t0
        t_best = dt if t_best is None or dt < t_best else t_best

    # the byte-equality oracle, in-row: spans vs the scalar reference
    oracle = [_oracle_doc(w).get_text_with_formatting(["text"])
              for w in workloads]
    got = session.read_all()
    for doc in range(d):
        assert got[doc] == oracle[doc], (
            f"markheavy doc {doc}: device spans diverge from the scalar "
            "oracle"
        )
    value = total_ops / t_best
    return {
        "metric": "markheavy_ops_per_sec",
        "value": round(value, 1),
        "unit": "ops/s",
        "vs_baseline": None,
        "baseline_impl": "scalar-oracle byte equality asserted in-row",
        "docs": d,
        "ops_per_doc": opd,
        "total_ops": total_ops,
        "mark_ops": mark_ops,
        "mark_fraction": round(mark_ops / max(1, total_ops), 3),
        "byte_equal": True,
        "wall_seconds": round(t_best, 3),
        "fallback_docs": sum(1 for s in session.docs if s.fallback),
        "workload_gen_seconds": round(gen_time, 1),
        "platform": jax.devices()[0].platform,
    }


def run_fleet_serve(args) -> dict:
    """Fleet-serve row (ISSUE 10 tentpole evidence): the host-kill failover
    episode as a measurement — a ≥3-host FleetFrontend carries round-robin
    traffic, one serving host is killed mid-traffic, the lease detects it,
    failover re-homes the docs from checkpoint + journal, and client
    retries drain.  All of run_host_kill_failover's oracles (typed
    verdicts only, acked-op survival, post-heal fleet-wide byte equality)
    are ASSERTED in-row; the reported value is fleet frames applied per
    second over the whole episode, with the detection/failover evidence
    riding along."""
    import jax

    from peritext_tpu.testing.chaos import run_host_kill_failover

    report = run_host_kill_failover(
        args.seed + 29,
        hosts=3,
        num_docs=args.docs,
        ops_per_doc=args.ops_per_doc,
        transport=not args.smoke,
    )
    value = report.applied_frames / max(report.traffic_seconds, 1e-9)
    return {
        "metric": "fleet_serve_applied_frames_per_sec",
        "value": round(value, 1),
        "unit": "frames/s",
        "vs_baseline": None,
        "baseline_impl": "host-kill failover episode, all oracles asserted",
        "hosts": report.hosts,
        "docs": report.num_docs,
        "ops_per_doc": args.ops_per_doc,
        "victim": report.victim,
        "victim_docs": report.victim_docs,
        "detection_rounds": report.detection_rounds,
        "failover_docs": report.failover_docs,
        "offered": report.offered,
        "admitted": report.admitted,
        "delayed": report.delayed,
        "shed": report.shed,
        "acked_survived": report.acked_survived,
        "converged": report.converged,
        "transport": "tcp" if not args.smoke else "in-process",
        "episode_seconds": round(report.traffic_seconds, 3),
        "platform": jax.devices()[0].platform,
    }


def run_sweep(args) -> dict:
    """Full-corpus sweep row (BASELINE config 5b, VERDICT r3 task 5): build
    an N-doc converged session on carried device state (the scale demo's
    shape: one 3-replica session streamed to every doc as wire frames over 2
    arrival rounds), then MEASURE the full read_all / read_patches_all
    sweeps and the full-state digest — the numbers round 3 projected from
    2,048-doc memoization measurements instead of timing."""
    import jax

    from peritext_tpu.api.batch import _oracle_doc
    from peritext_tpu.parallel.codec import encode_frame
    from peritext_tpu.parallel.streaming import StreamingMerge
    from peritext_tpu.testing.fuzz import generate_workload

    d = args.docs
    w = generate_workload(seed=args.seed, num_docs=1, ops_per_doc=args.ops_per_doc)[0]
    changes = [ch for log in w.values() for ch in log]
    half = len(changes) // 2
    frames = [encode_frame(changes[:half]), encode_frame(changes[half:])]
    expected = _oracle_doc(w).get_text_with_formatting(["text"])
    total_ops = sum(len(c.ops) for c in changes) * d

    sess = StreamingMerge(
        num_docs=d, actors=("doc1", "doc2", "doc3"),
        slot_capacity=512, mark_capacity=160, tomb_capacity=192,
        round_insert_capacity=192, round_delete_capacity=96,
        round_mark_capacity=96,
        layout=args.layout,
    )
    t0 = time.perf_counter()
    for frame in frames:
        sess.ingest_frames((doc, frame) for doc in range(d))
        sess.drain()
    build_seconds = time.perf_counter() - t0

    for doc in (0, d // 2, d - 1):
        assert sess.read(doc) == expected, f"doc {doc} diverged"
    assert not any(s.fallback for s in sess.docs), "docs demoted to scalar replay"
    assert sess.overflow_count() == 0

    t0 = time.perf_counter()
    digest = sess.digest()
    digest_seconds = time.perf_counter() - t0

    t0 = time.perf_counter()
    all_spans = sess.read_all()
    read_seconds = time.perf_counter() - t0
    assert all(s == expected for s in all_spans), "full-sweep read diverged"
    t0 = time.perf_counter()
    n_patches = sum(len(p) for p in sess.read_patches_all())
    patches_seconds = time.perf_counter() - t0

    sweep = read_seconds + patches_seconds
    return {
        "metric": "full_sweep_docs_per_sec",
        "value": round(d / sweep, 1),
        "unit": "docs/s",
        "vs_baseline": None,
        "layout": args.layout,
        "docs": d,
        "ops_per_doc_session": sum(len(c.ops) for c in changes),
        "total_ops": total_ops,
        "build_seconds": round(build_seconds, 1),
        "build_ops_per_sec": round(total_ops / build_seconds, 1),
        "digest": f"{digest:#010x}",
        "digest_seconds": round(digest_seconds, 2),
        "read_all_seconds": round(read_seconds, 2),
        "read_patches_all_seconds": round(patches_seconds, 2),
        "sweep_seconds": round(sweep, 2),
        "n_patches": n_patches,
        "platform": jax.devices()[0].platform,
    }


def run_mesh(args) -> dict:
    """Mesh-sharded host row (ISSUE 14): the doc-axis ``shard_map`` fused
    drain swept over shard counts, byte equality vs the single-device
    fused path asserted in-row.

    Each rung builds a fresh paged-layout session over a 1/2/4/8-device
    mesh (virtual CPU devices on a single-chip host — the flag must land
    before the backend initializes, hence the env fixup below), replays
    the same fuzz workload through the fused drain, asserts digest +
    ``read_all`` equality against the meshless fused reference, and times
    steady-state replay sessions (the warmup session pays the rung's
    compiles; the jit + mesh_fn caches carry them across sessions).  A
    drain batch is ONE staged program for the whole mesh, so the rung's
    fused-dispatch count rides along with ``speedup_vs_1shard``."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from peritext_tpu.obs import GLOBAL_COUNTERS
    from peritext_tpu.parallel.streaming import StreamingMerge
    from peritext_tpu.testing.fuzz import generate_workload

    d = args.docs
    opd = args.ops_per_doc
    workloads = generate_workload(seed=args.seed + 19, num_docs=d,
                                  ops_per_doc=opd)
    changes = [[ch for log in w.values() for ch in log] for w in workloads]
    total_ops = sum(len(c.ops) for log in changes for c in log)

    def replay(mesh):
        sess = StreamingMerge(
            num_docs=d, actors=("doc1", "doc2", "doc3"),
            layout="paged", mesh=mesh,
            slot_capacity=max(256, 4 * opd), mark_capacity=max(128, opd),
            tomb_capacity=max(128, opd),
        )
        for doc, log in enumerate(changes):
            sess.ingest(doc, log)
        sess.drain()
        return sess

    ref = replay(None)
    ref_digest = ref.digest()
    ref_spans = ref.read_all()

    devices = jax.devices()
    shard_counts = [n for n in (1, 2, 4, 8)
                    if n <= len(devices) and d % n == 0]
    iters = max(2, args.iters // 2)
    rungs = []
    base_ops_per_sec = None
    for n in shard_counts:
        mesh = Mesh(np.asarray(devices[:n]), ("docs",))
        # warmup replay: pays the rung's compiles AND is the oracle check
        sess = replay(mesh)
        assert sess.digest() == ref_digest, f"{n}-shard digest diverged"
        assert sess.read_all() == ref_spans, f"{n}-shard read_all diverged"
        d0 = GLOBAL_COUNTERS.get("streaming.fused_dispatches")
        t0 = time.perf_counter()
        for _ in range(iters):
            sess = replay(mesh)
        elapsed = max(time.perf_counter() - t0, 1e-9)
        ops_per_sec = total_ops * iters / elapsed
        if base_ops_per_sec is None:
            base_ops_per_sec = ops_per_sec
        stats = sess._mesh_stats()
        rungs.append({
            "shards": n,
            "ops_per_sec": round(ops_per_sec, 1),
            "seconds": round(elapsed, 3),
            "sessions": iters,
            "fused_dispatches": int(
                GLOBAL_COUNTERS.get("streaming.fused_dispatches") - d0
            ),
            "speedup_vs_1shard": round(ops_per_sec / base_ops_per_sec, 3),
            "imbalance_ratio": stats.get("imbalance_ratio"),
            "ici_page_moves": stats.get("ici_page_moves"),
            "equality": "byte-identical",
        })
    widest = rungs[-1]
    return {
        "metric": "mesh_sustained_ops_per_sec",
        "value": widest["ops_per_sec"],
        "unit": "ops/s",
        "vs_baseline": None,
        "baseline_impl": "single-device fused drain, byte equality in-row",
        "layout": "paged",
        "docs": d,
        "ops_per_doc": opd,
        "shards": widest["shards"],
        "speedup_vs_1shard": widest["speedup_vs_1shard"],
        "rungs": rungs,
        "platform": jax.devices()[0].platform,
    }


def ladder_rows(platform):
    """The evidence-ladder row specs: (name, BASELINE config tag, worker
    args, platform, timeout).  Ordered so the highest-value rows land first
    if the global deadline cuts the run short.  ``platform`` None means the
    default backend, which the worker requires to be the chip."""
    t = ROW_TIMEOUT
    return [
        ("baselines",    "1",  ["--mode", "baselines"], "cpu", t),
        ("batch_8k",     "4",  ["--mode", "batch"], platform, t),
        # the ragged twin (ISSUE 12): same synth batch, one program over
        # the page pool, padded byte-equality asserted in-row
        ("batch_8k_ragged", "4r", ["--mode", "batch", "--layout", "ragged"],
         platform, t),
        ("streaming",    "5",  ["--mode", "streaming"], platform, t),
        ("streaming_fused", "5f", ["--mode", "streaming-fused"], platform, t),
        ("wire",         "-",  ["--mode", "wire"], "cpu", t),
        ("fleet_heal",   "-",  ["--mode", "fleet"], "cpu", t),
        ("engine",       "5e", ["--mode", "engine"], platform, t),
        ("batch_1k",     "3",  ["--mode", "batch", "--docs", "1024"], platform, t),
        ("batch_128_cpu", "2", ["--mode", "batch", "--docs", "128"], "cpu", t),
        ("serve_sustained", "-", ["--mode", "serve"], platform, t),
        # the multi-tenant fused-dispatch row (ISSUE 13): N small tenants
        # on one lane vs per-session drains, byte equality asserted in-row
        ("serve_multitenant", "-", ["--mode", "serve-fused"], platform, t),
        # the mesh-sharded host row (ISSUE 14): shard_map fused drain over
        # 1/2/4/8 virtual devices, single-device byte equality in-row
        ("serve_mesh_sustained", "-", ["--mode", "mesh"], "cpu", t),
        ("reconnect_storm", "-", ["--mode", "storm"], platform, t),
        ("batch_longdoc", "4b", ["--mode", "longdoc"], platform, t),
        ("markheavy",    "-",  ["--mode", "markheavy"], platform, t),
        ("fleet_serve",  "-",  ["--mode", "fleet-serve"], "cpu", t),
        ("sweep_100k",   "5b", ["--mode", "sweep"], platform, max(t, 1800.0)),
        # the paged-vs-padded sweep comparison: same 100K-doc corpus, paged
        # resident storage — gate history is per row name, so regressions
        # in EITHER layout's sweep show up independently
        ("sweep_paged",  "5b", ["--mode", "sweep", "--layout", "paged"],
         platform, max(t, 1800.0)),
    ]


def orchestrate_ladder(args) -> int:
    """The no-args default: run EVERY evidence row as its own bounded
    worker and print one JSON line whose ``rows`` array carries the whole
    ladder.  A row failure/timeout records a structured entry, and any
    failed row makes the record ``failed`` with a nonzero exit; the
    headline fields mirror the best batch row so the driver contract (one
    line, metric/value/vs_baseline) is unchanged."""
    t_start = time.perf_counter()
    extras = {}
    if getattr(args, "profile", None) or getattr(args, "object_ingest", False):
        print("bench: --profile/--object-ingest are not supported by the "
              "ladder and will be ignored (use --mode batch/streaming)",
              file=sys.stderr)
    platform = args.platform

    only = os.environ.get("PT_BENCH_LADDER_ROWS")
    specs = ladder_rows(platform)
    if only:
        wanted = {w.strip() for w in only.split(",")}
        specs = [s for s in specs if s[0] in wanted]

    rows = []
    baselines_blob = None
    for name, config, rargs, plat, timeout in specs:
        left = LADDER_DEADLINE - (time.perf_counter() - t_start)
        if left < 30:
            rows.append({"row": name, "config": config, "skipped": "ladder deadline"})
            continue
        worker_args = list(rargs)
        if args.smoke:
            worker_args.append("--smoke")
        if args.devprof:
            worker_args.append("--devprof")
        if args.iters != 10:  # explicit --mode ladder may shape the workers
            worker_args += ["--iters", str(args.iters)]
        if args.seed:
            worker_args += ["--seed", str(args.seed)]
        if plat:
            worker_args += ["--platform", plat]
        env = dict(os.environ)
        if baselines_blob:
            env["PT_BENCH_BASELINES"] = baselines_blob
        rc, out, err = _run_bounded(
            _worker_argv(worker_args), min(timeout, left), env=env
        )
        result = _parse_json_tail(out)
        if rc == 0 and result is not None:
            result["row"] = name
            result["config"] = config
            rows.append(result)
            if name == "baselines":
                baselines_blob = json.dumps(result)
            continue
        status = "timed out" if rc is None else f"rc={rc}"
        tail = (err or out).strip()[-800:]
        print(f"bench: ladder row {name} on {plat} {status}: {tail}",
              file=sys.stderr)
        rows.append({"row": name, "config": config,
                     "failed": True, "error": f"{status}: {tail}"})

    extras["ladder_seconds"] = round(time.perf_counter() - t_start, 1)
    headline = None
    for want in ("batch_8k", "batch_1k", "batch_128_cpu", "streaming"):
        headline = next(
            (r for r in rows if r.get("row") == want and not r.get("failed")
             and not r.get("skipped")), None)
        if headline:
            break
    # a row subset (PT_BENCH_LADDER_ROWS) may not include a batch/streaming
    # row at all: all-green rows are still a success; any failed or skipped
    # row fails the record
    all_ok = bool(rows) and all(
        not r.get("failed") and not r.get("skipped") for r in rows
    )
    record = {
        "metric": headline.get("metric") if headline else "crdt_ops_per_sec_per_chip",
        "value": headline.get("value") if headline else None,
        "unit": "ops/s",
        "vs_baseline": headline.get("vs_baseline") if headline else None,
        "headline_row": headline.get("row") if headline else None,
        **({} if all_ok else {"failed": True}),
        "rows": rows,
        **extras,
    }
    # Full record: sidecar file (the judge's evidence) + an early stdout line
    # (so a human log still carries everything).  The LAST line is the
    # compact summary the driver parses — budget enforced by compact_record
    # and pinned by tests/test_bench_harness.py.
    try:
        with open(SIDECAR, "w") as fh:
            json.dump(record, fh, indent=1)
        record["sidecar"] = os.path.basename(SIDECAR)
    except OSError as exc:  # unwritable sidecar dir must not cost the line
        print(f"bench: sidecar write failed: {exc}", file=sys.stderr)
    if args.ledger:
        # perf-ledger emission: ladder rows (devprof snapshots lifted out of
        # the rows and keyed per row) appended as ONE record for the
        # regression gate (python -m peritext_tpu.obs perf --gate)
        devprof_map = {}
        ledger_rows = []
        for r in rows:
            r = dict(r)
            snap = r.pop("devprof", None)
            if snap is not None:
                devprof_map[r.get("row")] = snap
            ledger_rows.append(r)
        _append_ledger(
            args.ledger, ledger_rows,
            config="ladder" + ("-smoke" if args.smoke else ""),
            platform=platform, devprof=devprof_map or None,
        )
    print(json.dumps(record))
    print(json.dumps(compact_record(record)))
    return 0 if all_ok else 1


def compact_record(record, budget=None):
    """Shrink a full ladder record to the driver-parsed summary: headline
    fields plus per-row ``{row, value, unit, platform, config, vs_baseline}``
    (and failure markers), guaranteed to serialize within ``budget`` bytes.
    Degrades by dropping optional per-row fields, then
    trailing rows, never the headline."""
    budget = FINAL_LINE_BUDGET if budget is None else budget
    head = {k: record.get(k) for k in
            ("metric", "value", "unit", "vs_baseline", "headline_row")}
    if record.get("failed"):
        head["failed"] = True
    for k in ("sidecar", "ladder_seconds"):
        if k in record:
            head[k] = record[k]

    def row_of(r, keys):
        out = {"row": r.get("row")}
        for k in keys:
            if r.get(k) is not None:
                out[k] = r[k]
        if r.get("failed"):
            out["failed"] = True
        if r.get("skipped"):
            out["skipped"] = True
        return out

    tiers = (("value", "unit", "platform", "config", "vs_baseline"),
             ("value", "unit", "platform"),
             ("value",))
    # degrade fields first (all rows kept), truncate rows only when even
    # the slimmest field tier overflows
    for keys in tiers:
        out = dict(head, rows=[row_of(r, keys) for r in record.get("rows", [])])
        if len(json.dumps(out)) <= budget:
            return out
    while out["rows"]:
        out["rows"] = out["rows"][:-1]
        out["rows_truncated"] = True
        if len(json.dumps(out)) <= budget:
            return out
    head["rows"] = []
    return head


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true", help="small fast config")
    parser.add_argument(
        "--mode",
        choices=("batch", "streaming", "streaming-fused", "engine", "wire",
                 "sweep", "baselines", "fleet", "serve", "serve-fused",
                 "mesh", "storm", "longdoc", "markheavy", "fleet-serve",
                 "ladder"),
        default=None,
        help="batch = one-shot converge (configs 2-4); streaming = config 5 "
             "end-to-end; engine = device-only streaming replay (the engine "
             "limit, decoupled from host parse/link); wire = codec bytes/op; "
             "sweep = config-5b full-corpus read sweep; baselines = scalar "
             "baselines only; fleet = partition-heal time-to-convergence "
             "(ISSUE 4); serve = sustained open-loop serving ladder (docs/s "
             "at a p99 apply-latency SLO, ISSUE 7); serve-fused = N small "
             "tenants fused onto one device lane vs per-session dispatch "
             "(dispatch amortization + byte equality, ISSUE 13); "
             "mesh = doc-axis-sharded shard_map fused drain swept over "
             "shard counts (single-device byte equality in-row, ISSUE 14); "
             "storm = reconnect-storm "
             "backlog drain under serving load; longdoc = long-tail "
             "paged-vs-padded comparison (one essay among a tweet fleet, "
             "ISSUE 8); markheavy = mark-heavy editorial pass (span-overlap "
             "explosion, scalar-oracle byte equality in-row, ISSUE 10); "
             "fleet-serve = host-kill failover episode as a measurement "
             "(ISSUE 10); ladder = every row as "
             "bounded sub-workers (the default when invoked with no mode "
             "and no --smoke)",
    )
    parser.add_argument("--rounds", type=int, default=4, help="streaming arrival rounds")
    parser.add_argument(
        "--object-ingest", action="store_true",
        help="streaming: force the Python object ingest path (default: wire frames)",
    )
    parser.add_argument("--docs", type=int, default=None)
    parser.add_argument("--ops-per-doc", type=int, default=None)
    parser.add_argument("--slots", type=int, default=None)
    parser.add_argument("--marks", type=int, default=None)
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--platform", default=None, help="force a jax platform (e.g. cpu)"
    )
    parser.add_argument(
        "--layout", choices=("padded", "paged", "ragged"), default="padded",
        help="resident-state storage layout for the sweep row and (ragged "
             "only) the batch row's one-program-over-the-pool variant; the "
             "longdoc row always measures all three layouts itself",
    )
    parser.add_argument(
        "--profile", default=None, metavar="DIR",
        help="capture a jax.profiler trace of the steady-state loop into DIR",
    )
    parser.add_argument(
        "--trace-out", default=None, metavar="PATH", dest="trace_out",
        help="write the streaming pipeline spans as Perfetto/Chrome "
             "trace-event JSON to PATH (streaming mode)",
    )
    parser.add_argument(
        "--serve-slo-ms", type=float, default=250.0, dest="serve_slo_ms",
        metavar="MS",
        help="serve mode: the p99 apply-latency SLO the open-loop ladder "
             "sweeps against (default 250 ms)",
    )
    parser.add_argument(
        "--devprof", action="store_true",
        help="enable device-cost profiling (obs/devprof.py: XLA cost/memory "
             "introspection + bucket occupancy) for the measured rows; the "
             "snapshot lands in the row JSON and the perf ledger",
    )
    parser.add_argument(
        "--ledger", default=None, metavar="PATH",
        help="append the run's rows (+ devprof snapshots) to the JSONL perf "
             "ledger at PATH; gate with `python -m peritext_tpu.obs perf`",
    )
    parser.add_argument(
        "--_worker", action="store_true", dest="worker", help=argparse.SUPPRESS
    )
    args = parser.parse_args()

    if args.trace_out and args.mode not in ("streaming",):
        # only the streaming runner consumes it; anything else would both
        # skip the default ladder AND silently write no trace
        parser.error("--trace-out requires --mode streaming")
    layout_modes = {"paged": ("sweep",), "ragged": ("sweep", "batch")}
    if args.layout != "padded" and args.mode not in layout_modes[args.layout]:
        # only these runners consume it (longdoc measures every layout
        # itself); anything else would silently measure the padded layout
        parser.error(
            f"--layout {args.layout} requires --mode "
            + "/".join(layout_modes[args.layout])
        )

    explicit_sizing = (
        any(v is not None for v in (args.docs, args.ops_per_doc, args.slots,
                                    args.marks, args.profile, args.trace_out))
        or args.iters != 10 or args.seed != 0 or args.rounds != 4
        or args.object_ingest
    )
    if not args.worker:
        if args.mode is None and not args.smoke and not explicit_sizing:
            # the driver's plain `python bench.py`: the full evidence ladder
            # (explicit sizing flags mean a hand-run single measurement —
            # ladder_rows would silently drop them, so classic batch instead)
            sys.exit(orchestrate_ladder(args))
        args.mode = args.mode or "batch"
        # argv minus the program name IS the passthrough (worker re-parses it);
        # --platform is re-added per attempt by the orchestrator.
        argv = sys.argv[1:]
        passthrough = [a for i, a in enumerate(argv)
                       if a != "--platform"
                       and not a.startswith("--platform=")
                       and not (i > 0 and argv[i - 1] == "--platform")]
        if args.mode == "ladder":  # --smoke ladder: shrunk rows, same shape
            sys.exit(orchestrate_ladder(args))
        sys.exit(orchestrate(args, passthrough))

    args.mode = args.mode or "batch"
    if args.mode == "sweep":
        defaults = (2000, 220, 0, 0) if args.smoke else (100_000, 220, 0, 0)
        args.seed = args.seed or 200
    elif args.mode in ("wire", "fleet"):
        defaults = (64, 192, 0, 0) if args.smoke else (512, 192, 0, 0)
    elif args.mode == "serve":
        defaults = (16, 48, 0, 0) if args.smoke else (64, 96, 0, 0)
    elif args.mode == "serve-fused":
        # --docs = the tenant count (one doc slot per small tenant)
        defaults = (16, 48, 0, 0) if args.smoke else (32, 96, 0, 0)
    elif args.mode == "mesh":
        # docs stay divisible by every swept shard count (1/2/4/8)
        defaults = (16, 48, 0, 0) if args.smoke else (64, 96, 0, 0)
    elif args.mode == "storm":
        defaults = (4, 30, 0, 0) if args.smoke else (8, 64, 0, 0)
    elif args.mode == "longdoc":
        # --docs = the tweet fleet, --ops-per-doc = the essay
        defaults = (64, 512, 0, 0) if args.smoke else (1024, 8192, 0, 0)
    elif args.mode == "markheavy":
        defaults = (16, 64, 0, 0) if args.smoke else (256, 192, 0, 0)
    elif args.mode == "fleet-serve":
        defaults = (4, 16, 0, 0) if args.smoke else (8, 48, 0, 0)
    elif args.mode in ("streaming", "streaming-fused", "engine"):
        defaults = (64, 96, 256, 64) if args.smoke else (2048, 192, 384, 96)
    else:
        defaults = (64, 128, 192, 64) if args.smoke else (8192, 256, 384, 96)
    args.docs = args.docs or defaults[0]
    args.ops_per_doc = args.ops_per_doc or defaults[1]
    args.slots = args.slots or defaults[2]
    args.marks = args.marks or defaults[3]

    runners = {"streaming": run_streaming,
               "streaming-fused": run_streaming_fused,
               "engine": run_engine, "batch": run,
               "wire": run_wire, "sweep": run_sweep, "baselines": run_baselines,
               "fleet": run_fleet_heal, "serve": run_serve,
               "serve-fused": run_serve_fused, "mesh": run_mesh,
               "storm": run_storm,
               "longdoc": run_longdoc, "markheavy": run_markheavy,
               "fleet-serve": run_fleet_serve}
    if args.mode == "mesh":
        # the mesh row sweeps virtual CPU devices: the count must be in
        # XLA_FLAGS before the backend initializes
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    _select_platform(args.platform)
    if args.devprof:
        # arm the process profiler before any jit dispatches; cost capture
        # on — the worker is a bounded measurement run, and the AOT
        # captures happen once per compiled shape
        from peritext_tpu.obs import GLOBAL_DEVPROF

        GLOBAL_DEVPROF.enable(capture_costs=True)
    result = runners[args.mode](args)
    if args.devprof:
        result["devprof"] = GLOBAL_DEVPROF.snapshot()
    print(json.dumps(result))


if __name__ == "__main__":
    sys.exit(main())
