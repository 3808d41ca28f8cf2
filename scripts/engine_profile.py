"""Engine-limit stage attribution, consolidated (VERDICT r4 task 2 + r5).

One script, two granularities over the SAME captured-round replay that
bench.py --mode engine times:

* default (coarse): the apply chain and the digest program separately
  (each behind its own sync), sweepable over round depth x docs to locate
  the fixed-cost knee (``--sweep``).
* ``--fine``: per-sync decomposition (each stage ends in a small host
  fetch) — bare sync round trip,
  each staged round-apply individually, the chained applies, and the
  digest program — so an engine pass decomposes into launch/compute/sync
  terms instead of guesses.

Both modes run under the device profiler (obs/devprof.py), so ad-hoc
profiling emits the SAME snapshot schema the perf ledger stores:
``--devprof-out PATH`` writes the shape-bucket/occupancy/memory snapshot
as JSON, and ``--ledger PATH`` appends a full ledger record (throughput
row + devprof snapshot) for `python -m peritext_tpu.obs perf`.

Run on the chip:  python scripts/engine_profile.py [--fine|--sweep]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import numpy as np


def stage_replay(docs, rounds, opd, slots, marks, round_caps=(256, 128, 128)):
    """Build a captured-round replay: run a real streaming session with
    round capture on, pre-stage every round's device-ready inputs, and
    return everything the timing loops need."""
    import jax
    import jax.numpy as jnp

    from bench import build_arrival
    from peritext_tpu.ops.packed import empty_docs
    from peritext_tpu.parallel.streaming import StreamingMerge
    from peritext_tpu.testing.fuzz import generate_workload

    workloads = generate_workload(seed=0, num_docs=docs, ops_per_doc=opd)
    arrival, _ = build_arrival(workloads, rounds, 0)
    captured = []
    ki, kd, km = round_caps
    s = StreamingMerge(
        num_docs=docs, actors=("doc1", "doc2", "doc3"),
        slot_capacity=slots, mark_capacity=marks, tomb_capacity=slots,
        round_insert_capacity=ki, round_delete_capacity=kd,
        round_mark_capacity=km,
    )
    s._capture_rounds = captured
    for r in range(rounds):
        s.ingest_frames((doc, batches[r]) for doc, batches in enumerate(arrival)
                        if r < len(batches))
        s.drain()
    expected = s.digest()
    assert s.overflow_count() == 0

    state0 = jax.device_put(
        empty_docs(s._padded_docs, slots, marks, tomb_capacity=slots))
    staged = [
        ((tuple(jax.device_put(np.asarray(c)) for c in counts),
          ins, dels, mk, mp), widths, loop_slots)
        for (counts, ins, dels, mk, mp), widths, loop_slots in captured
    ]
    tables = s._digest_tables(0, s._padded_docs)
    row_mask = jnp.ones(s._padded_docs, bool)
    total_ops = sum(len(ch.ops) for w in workloads for log in w.values()
                    for ch in log)
    return s, staged, state0, tables, row_mask, expected, total_ops


def _apply_chain(staged, state0):
    from peritext_tpu.ops.kernel import apply_batch_compact_jit

    st = state0
    for (c, i, dl, mk, mp), w, ls in staged:
        st = apply_batch_compact_jit(st, c, i, dl, mk, mp, widths=w,
                                     insert_loop_slots=ls)
    return st


def _digest_of(s, st, tables, row_mask):
    from peritext_tpu.obs import GLOBAL_DEVPROF, note_jit_dispatch
    from peritext_tpu.parallel.streaming import _resolve_block_digest_jit

    args = (st, s.comment_capacity, row_mask, *tables)
    if GLOBAL_DEVPROF.enabled:
        note_jit_dispatch("_resolve_block_digest_jit",
                          _resolve_block_digest_jit, args)
    _, per_doc = _resolve_block_digest_jit(*args)
    return int(np.asarray(per_doc).sum(dtype=np.uint32))


def measure(docs, rounds, opd, slots=384, marks=96, passes=3,
            profile_dir=None):
    """Coarse attribution: apply chain vs digest, each behind its own sync."""
    import jax

    s, staged, state0, tables, row_mask, expected, total_ops = stage_replay(
        docs, rounds, opd, slots, marks)

    # warm
    st = _apply_chain(staged, state0)
    assert _digest_of(s, st, tables, row_mask) == expected

    apply_t, digest_t, total_t = [], [], []
    for _ in range(passes):
        t0 = time.perf_counter()
        st = _apply_chain(staged, state0)
        jax.block_until_ready(st.char)
        t1 = time.perf_counter()
        dg = _digest_of(s, st, tables, row_mask)
        t2 = time.perf_counter()
        apply_t.append(t1 - t0)
        digest_t.append(t2 - t1)
        # combined single-sync (the bench row's definition)
        t0 = time.perf_counter()
        dg = _digest_of(s, _apply_chain(staged, state0), tables, row_mask)
        total_t.append(time.perf_counter() - t0)
    assert dg == expected

    if profile_dir:
        import jax.profiler
        with jax.profiler.trace(profile_dir):
            _digest_of(s, _apply_chain(staged, state0), tables, row_mask)

    n_staged = len(staged)
    return dict(docs=docs, rounds=rounds, staged_rounds=n_staged,
                ops=total_ops,
                apply_s=round(min(apply_t), 4),
                apply_per_round_ms=round(1e3 * min(apply_t) / n_staged, 2),
                digest_s=round(min(digest_t), 4),
                total_s=round(min(total_t), 4),
                ops_per_sec=round(total_ops / min(total_t), 1))


def measure_fine(docs, rounds, opd, slots=384, marks=96):
    """Fine attribution with HONEST syncs: bare RTT, per-round applies,
    chained applies, digest (the old engine_profile2)."""
    import jax
    import jax.numpy as jnp

    from peritext_tpu.ops.kernel import apply_batch_compact_jit

    s, staged, state0, tables, row_mask, expected, total_ops = stage_replay(
        docs, rounds, opd, slots, marks)
    print("round widths:", [(w, ls) for _, w, ls in staged])

    def sync(st):
        return np.asarray(st.num_slots if hasattr(st, "num_slots") else st)

    # warm every executable
    st = _apply_chain(staged, state0)
    sync(st)
    assert _digest_of(s, st, tables, row_mask) == expected

    # bare sync RTT on an already-materialized tiny array
    tiny = jax.jit(lambda x: x + 1)(jnp.zeros(8, jnp.int32))
    np.asarray(tiny)
    rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(tiny)
        rtts.append(time.perf_counter() - t0)
    print(f"bare fetch of ready tiny array: {min(rtts)*1e3:.1f} ms")
    rtts = []
    for _ in range(5):
        y = jax.jit(lambda x: x + 1)(tiny)
        t0 = time.perf_counter()
        np.asarray(y)
        rtts.append(time.perf_counter() - t0)
    print(f"dispatch+fetch tiny:            {min(rtts)*1e3:.1f} ms")

    # each staged round individually, honest sync
    for k, ((c, i, dl, mk, mp), w, ls) in enumerate(staged):
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = apply_batch_compact_jit(state0, c, i, dl, mk, mp, widths=w,
                                          insert_loop_slots=ls)
            sync(out)
            ts.append(time.perf_counter() - t0)
        print(f"round {k} apply (dispatch+sync): {min(ts)*1e3:7.1f} ms  "
              f"widths={w}")

    # chained applies, single sync
    chain_ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        st = _apply_chain(staged, state0)
        sync(st)
        chain_ts.append(time.perf_counter() - t0)
    print(f"chained {len(staged)} applies + sync:   {min(chain_ts)*1e3:7.1f} ms")

    # digest alone on the converged state
    digest_ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        dg = _digest_of(s, st, tables, row_mask)
        digest_ts.append(time.perf_counter() - t0)
    assert dg == expected
    print(f"digest (dispatch+sync):         {min(digest_ts)*1e3:7.1f} ms")
    # the pass total is apply chain + digest — reporting the digest loop
    # alone would overstate engine throughput several-fold in the ledger
    total = min(chain_ts) + min(digest_ts)
    return dict(docs=docs, rounds=rounds, staged_rounds=len(staged),
                ops=total_ops, mode="fine",
                apply_s=round(min(chain_ts), 4),
                digest_s=round(min(digest_ts), 4),
                total_s=round(total, 4),
                ops_per_sec=round(total_ops / max(total, 1e-9), 1))


def measure_fused_pipeline(docs, rounds, opd, slots=384, marks=96):
    """Fused-pipeline decomposition (ISSUE 9 satellite): how much of the
    host's parse/schedule wall the pipelined drain actually HIDES behind
    device compute.

    Three honest measurements over the same live workload:

    * ``pipelined_s`` — the fused discipline end-to-end (pipelined drain:
      staged batches, async dispatch, staging lane);
    * ``serialized_s`` — the identical session forced lock-step: a device
      sync after every drain, so host work and device math strictly
      alternate (the no-overlap upper bound);
    * ``host_parse_s`` — the session's own wire-parse wall
      (``host_parse_seconds``).

    ``overlap_hidden_s = serialized_s - pipelined_s`` is the wall the
    pipeline removed; ``parse_overlap_ratio = clamp(hidden / host_parse,
    0, 1)`` expresses it against the parse stage the ISSUE attributes the
    streaming gap to — the remaining-gap attribution the fused row's
    throughput alone cannot give."""
    import time as _time

    from bench import build_arrival
    from peritext_tpu.parallel.streaming import StreamingMerge
    from peritext_tpu.testing.fuzz import generate_workload

    workloads = generate_workload(seed=0, num_docs=docs, ops_per_doc=opd)
    arrival, _ = build_arrival(workloads, rounds, 0)
    total_ops = sum(len(ch.ops) for w in workloads for log in w.values()
                    for ch in log)

    def run(serialize: bool):
        s = StreamingMerge(
            num_docs=docs, actors=("doc1", "doc2", "doc3"),
            slot_capacity=slots, mark_capacity=marks, tomb_capacity=slots,
            round_insert_capacity=64, round_delete_capacity=32,
            round_mark_capacity=32, round_map_capacity=16,
        )
        t0 = _time.perf_counter()
        for r in range(rounds):
            s.ingest_frames(
                (doc, b[r]) for doc, b in enumerate(arrival) if r < len(b))
            s.drain()
            if serialize:
                s.sync_device()
        digest = s.digest()
        return _time.perf_counter() - t0, digest, s

    run(False)  # warm compiles
    run(True)
    pipe, dg_a, s_pipe = min(
        (run(False) for _ in range(3)), key=lambda x: x[0])
    serial, dg_b, _ = min((run(True) for _ in range(3)), key=lambda x: x[0])
    assert dg_a == dg_b, "overlap must not change the digest"
    hidden = max(0.0, serial - pipe)
    parse = max(s_pipe.host_parse_seconds, 1e-9)
    row = dict(
        docs=docs, rounds=rounds, staged_rounds=s_pipe.rounds,
        ops=total_ops, mode="fused",
        pipelined_s=round(pipe, 4),
        serialized_s=round(serial, 4),
        host_parse_s=round(s_pipe.host_parse_seconds, 4),
        overlap_hidden_s=round(hidden, 4),
        parse_overlap_ratio=round(min(1.0, hidden / parse), 3),
        ops_per_sec=round(total_ops / pipe, 1),
    )
    print(f"fused pipeline: pipelined {pipe*1e3:7.1f} ms  "
          f"serialized {serial*1e3:7.1f} ms  "
          f"parse {s_pipe.host_parse_seconds*1e3:6.1f} ms  "
          f"hidden {hidden*1e3:6.1f} ms  "
          f"overlap_ratio {row['parse_overlap_ratio']}")
    return row


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--fine", action="store_true",
                        help="honest-sync launch/compute/sync decomposition "
                        "(the old engine_profile2.py)")
    parser.add_argument("--sweep", action="store_true",
                        help="sweep round depth x docs (coarse mode only)")
    parser.add_argument("--profile", action="store_true",
                        help="capture a jax.profiler trace to /tmp/engine_trace")
    parser.add_argument("--docs", type=int, default=2048)
    parser.add_argument("--rounds", type=int, default=4)
    parser.add_argument("--ops-per-doc", type=int, default=192)
    parser.add_argument("--slots", type=int, default=384)
    parser.add_argument("--marks", type=int, default=96)
    parser.add_argument("--devprof-out", default=None, metavar="PATH",
                        help="write the devprof snapshot (shape buckets, "
                        "occupancy, memory watermarks) as JSON to PATH — the "
                        "same schema the perf ledger stores")
    parser.add_argument("--ledger", default=None, metavar="PATH",
                        help="append a perf-ledger record (throughput row + "
                        "devprof snapshot) to PATH")
    args = parser.parse_args(argv)

    from peritext_tpu.obs import GLOBAL_DEVPROF

    GLOBAL_DEVPROF.enable(capture_costs=True)

    if args.fine:
        results = [measure_fine(args.docs, args.rounds, args.ops_per_doc,
                                args.slots, args.marks),
                   measure_fused_pipeline(args.docs, args.rounds,
                                          args.ops_per_doc, args.slots,
                                          args.marks)]
    else:
        shapes = [(args.docs, args.rounds, args.ops_per_doc)]
        if args.sweep:
            shapes = [
                (2048, 4, 192),   # the bench shape
                (2048, 1, 192),   # one big round: all ops in a single apply
                (2048, 2, 192),
                (2048, 8, 192),
                (2048, 16, 192),
                (512, 4, 192),
                (8192, 4, 192),
            ]
        results = []
        for docs, rounds, opd in shapes:
            r = measure(docs, rounds, opd, args.slots, args.marks,
                        profile_dir="/tmp/engine_trace" if args.profile else None)
            print(r)
            results.append(r)

    if args.devprof_out:
        with open(args.devprof_out, "w") as fh:
            json.dump(GLOBAL_DEVPROF.snapshot(), fh, indent=1)
        print(f"devprof snapshot -> {args.devprof_out}")
    if args.ledger:
        from peritext_tpu.obs import ledger as _ledger

        # fine mode measures a two-sync pass (chain + digest separately),
        # coarse mode a single-sync pass — distinct row identities so the
        # two never pollute each other's rolling reference
        rows = [
            dict(row=({"fine": "engine_profile_fine",
                       "fused": "fused_pipeline"}.get(r.get("mode"),
                                                      "engine_profile"))
                 + f"[{r['docs']}x{r['staged_rounds']}]",
                 metric="engine_profile_ops_per_sec", value=r["ops_per_sec"],
                 unit="ops/s", docs=r["docs"], rounds=r["rounds"])
            for r in results
        ]
        _ledger.append_record(args.ledger, _ledger.ledger_record(
            rows, config="engine_profile",
            devprof=GLOBAL_DEVPROF.snapshot(),
        ))
        print(f"perf-ledger record -> {args.ledger}")


if __name__ == "__main__":
    main()
