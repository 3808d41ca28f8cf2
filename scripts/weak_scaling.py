#!/usr/bin/env python
"""Multi-chip weak-scaling evidence on the virtual device mesh.

This runs without chips: chip_smoke.py --chips 4 is the four-chip run.
This is the CPU artifact, per SURVEY §4's "multi-node
without a cluster" recipe: N virtual CPU devices
(``--xla_force_host_platform_device_count``), a ``jax.sharding.Mesh`` over
the doc axis, and the SAME merge programs the TPU path runs.

For each mesh size 1/2/4/8 it measures, at FIXED docs-per-device (weak
scaling):

* batch merge wall time + per-device throughput (DocBatch over the mesh),
* streaming merge wall time + per-device throughput (StreamingMerge rounds),
* the convergence digest of a FIXED 16-doc probe workload, which must be
  IDENTICAL across every mesh size (re-sharding must never change content).

Emits one JSON line per mesh size plus a final summary line; the BASELINE.md
weak-scaling table is generated from this output.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--docs-per-device", type=int, default=64)
    parser.add_argument("--ops-per-doc", type=int, default=96)
    parser.add_argument("--seed", type=int, default=11)
    parser.add_argument("--sizes", type=int, nargs="+", default=[1, 2, 4, 8])
    args = parser.parse_args()

    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from peritext_tpu.utils.platform import pin_cpu_platform

    devices = pin_cpu_platform(max(args.sizes))

    import numpy as np
    import jax
    from jax.sharding import Mesh

    from peritext_tpu.api.batch import DocBatch
    from peritext_tpu.parallel.codec import encode_frame
    from peritext_tpu.parallel.streaming import StreamingMerge
    from peritext_tpu.testing.fuzz import generate_workload

    probe = generate_workload(args.seed ^ 0xD16, num_docs=16, ops_per_doc=48)
    digests = {}

    for n in args.sizes:
        mesh = Mesh(np.asarray(devices[:n]), ("docs",))
        docs = args.docs_per_device * n
        workloads = generate_workload(args.seed, num_docs=docs,
                                      ops_per_doc=args.ops_per_doc)
        total_ops = sum(
            len(ch.ops) for w in workloads for log in w.values() for ch in log
        )

        # ---- batch merge over the mesh ----
        batch = DocBatch(slot_capacity=4 * args.ops_per_doc,
                         mark_capacity=2 * args.ops_per_doc,
                         comment_capacity=32, mesh=mesh)
        batch.merge(workloads)  # warm: compiles are per (docs, caps) shape
        t0 = time.perf_counter()
        report = batch.merge(workloads)
        batch_s = time.perf_counter() - t0
        assert not report.fallback_docs, report.fallback_docs

        # ---- streaming merge over the mesh ----
        def mk():
            return StreamingMerge(
                num_docs=docs, actors=("doc1", "doc2", "doc3"), mesh=mesh,
                slot_capacity=4 * args.ops_per_doc,
                mark_capacity=2 * args.ops_per_doc,
                tomb_capacity=2 * args.ops_per_doc,
                round_insert_capacity=128, round_delete_capacity=64,
                round_mark_capacity=64,
            )

        frames = [
            encode_frame([ch for log in w.values() for ch in log])
            for w in workloads
        ]
        s = mk()  # warm
        s.ingest_frames(list(enumerate(frames)))
        s.drain()
        s.digest()
        t0 = time.perf_counter()
        s = mk()
        s.ingest_frames(list(enumerate(frames)))
        t_ingest = time.perf_counter() - t0
        s.drain()
        # drain() only ENQUEUES the jitted apply programs (step() documents
        # the async dispatch); without an explicit sync the apply compute
        # would be mis-attributed to the digest stage below
        np.asarray(s.state.num_slots)
        t_drain = time.perf_counter() - t0 - t_ingest
        s.digest()
        t_digest = time.perf_counter() - t0 - t_ingest - t_drain
        stream_s = t_ingest + t_drain + t_digest

        # ---- sharding-overhead probe: SAME total work on every mesh size —
        # with docs fixed, any slowdown vs mesh=1 is genuine sharding/
        # collective overhead, while the weak-scaling totals above also
        # absorb shared-CPU contention (all virtual devices share one chip)
        fixed_docs = args.docs_per_device
        fixed_w = generate_workload(args.seed ^ 0xF1, num_docs=fixed_docs,
                                    ops_per_doc=args.ops_per_doc)
        fixed_frames = [
            encode_frame([ch for log in w.values() for ch in log])
            for w in fixed_w
        ]
        fixed_ops = sum(
            len(ch.ops) for w in fixed_w for log in w.values() for ch in log
        )

        def fixed_run():
            fs = StreamingMerge(
                num_docs=fixed_docs, actors=("doc1", "doc2", "doc3"), mesh=mesh,
                slot_capacity=4 * args.ops_per_doc,
                mark_capacity=2 * args.ops_per_doc,
                tomb_capacity=2 * args.ops_per_doc,
                round_insert_capacity=128, round_delete_capacity=64,
                round_mark_capacity=64,
            )
            fs.ingest_frames(list(enumerate(fixed_frames)))
            fs.drain()
            fs.digest()

        fixed_run()  # warm
        t0 = time.perf_counter()
        fixed_run()
        fixed_s = time.perf_counter() - t0

        # ---- touched-round digest (r3 VERDICT task 2 "done" signal) ----
        # a converged session absorbs a FIXED 16-doc round (the held-back
        # second half of those docs' real histories, so causality holds);
        # the incremental digest must re-resolve only the touched span, so
        # this stage must NOT grow with the session's total docs (idle
        # rounds are cheaper still: all carried).  Mesh sessions hold one
        # whole-batch block, so their touched span is docs/devices — flat
        # per device under weak scaling.
        warm_round, held = {}, {}
        first_frames = []
        for i, w in enumerate(workloads):
            ch = [c for log in w.values() for c in log]
            if i < 16:
                first_frames.append(encode_frame(ch[: len(ch) // 3]))
                warm_round[i] = encode_frame(ch[len(ch) // 3: 2 * len(ch) // 3])
                held[i] = encode_frame(ch[2 * len(ch) // 3:])
            else:
                first_frames.append(encode_frame(ch))
        ts = mk()
        ts.ingest_frames(list(enumerate(first_frames)))
        ts.drain()
        ts.digest()  # warm the carried row plane
        ts.ingest_frames(list(warm_round.items()))
        ts.drain()
        ts.digest()  # warm the touched-rows sub-batch program (compiles)
        ts.ingest_frames(list(held.items()))
        ts.drain()
        np.asarray(ts.state.num_slots)  # attribute apply to its own stage
        t0 = time.perf_counter()
        ts.digest()
        touched_digest_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        ts.digest()
        idle_digest_s = time.perf_counter() - t0

        # shard-count sanity: the doc axis really spans all n devices
        n_shards = len(s.state.elem_id.sharding.device_set)
        assert n_shards == n, f"expected {n} shards, got {n_shards}"

        # ---- skewed arrival + reshard (SURVEY §5.8(c)) ----
        # first-seen placement pins heavy docs wherever they arrived; the
        # reshard all-to-all must restore per-shard load balance with the
        # digest bit-unchanged
        skew_stats = None
        if n > 1:
            sk_docs = args.docs_per_device * n
            heavy = generate_workload(args.seed ^ 0x5E, num_docs=sk_docs // 4,
                                      ops_per_doc=args.ops_per_doc * 3)
            light = generate_workload(args.seed ^ 0x5F, num_docs=sk_docs - len(heavy),
                                      ops_per_doc=max(8, args.ops_per_doc // 4))
            sk_w = heavy + light  # heavy docs all land in the first shard(s)
            sk = StreamingMerge(
                num_docs=sk_docs, actors=("doc1", "doc2", "doc3"), mesh=mesh,
                slot_capacity=12 * args.ops_per_doc,
                mark_capacity=6 * args.ops_per_doc,
                tomb_capacity=6 * args.ops_per_doc,
                round_insert_capacity=256, round_delete_capacity=128,
                round_mark_capacity=128,
            )
            sk.ingest_frames(
                (d, encode_frame([ch for log in w.values() for ch in log]))
                for d, w in enumerate(sk_w)
            )
            sk.drain()
            d_before = sk.digest()

            def shard_loads(sess):
                slots = np.asarray(sess.state.num_slots)
                per = sess._padded_docs // n
                return [int(slots[i * per:(i + 1) * per].sum()) for i in range(n)]

            loads_before = shard_loads(sk)
            t0 = time.perf_counter()
            moved = sk.reshard()
            np.asarray(sk.state.num_slots)  # sync the gather
            reshard_s = time.perf_counter() - t0
            loads_after = shard_loads(sk)
            assert sk.digest() == d_before, "reshard changed the digest"
            skew_stats = {
                "docs": sk_docs,
                "moved_docs": moved["moved"],
                "reshard_seconds": round(reshard_s, 3),
                "shard_load_before": loads_before,
                "shard_load_after": loads_after,
                "imbalance_before": round(max(loads_before) / max(1, min(loads_before)), 2),
                "imbalance_after": round(max(loads_after) / max(1, min(loads_after)), 2),
            }

        # ---- fixed-probe digest: content must be mesh-size invariant ----
        ps = StreamingMerge(
            num_docs=16, actors=("doc1", "doc2", "doc3"), mesh=mesh,
            slot_capacity=256, mark_capacity=128, tomb_capacity=128,
        )
        for d, w in enumerate(probe):
            ps.ingest(d, [ch for log in w.values() for ch in log])
        ps.drain()
        digests[n] = ps.digest()

        print(json.dumps({
            "mesh_devices": n,
            "docs": docs,
            "total_ops": total_ops,
            "batch_seconds": round(batch_s, 3),
            "batch_ops_per_sec_total": round(total_ops / batch_s, 1),
            "batch_ops_per_sec_per_device": round(total_ops / batch_s / n, 1),
            "streaming_seconds": round(stream_s, 3),
            "streaming_ops_per_sec_total": round(total_ops / stream_s, 1),
            "streaming_ops_per_sec_per_device": round(total_ops / stream_s / n, 1),
            "streaming_stage_seconds": {
                "ingest_host": round(t_ingest, 3),
                "schedule_apply": round(t_drain, 3),
                "digest": round(t_digest, 3),
            },
            "fixed_work_seconds": round(fixed_s, 3),
            "fixed_work_ops_per_sec": round(fixed_ops / fixed_s, 1),
            "touched_round_digest_seconds": round(touched_digest_s, 3),
            "idle_round_digest_seconds": round(idle_digest_s, 4),
            "skewed_arrival_reshard": skew_stats,
            "probe_digest": digests[n],
        }))

    assert len(set(digests.values())) == 1, f"digest mismatch across meshes: {digests}"
    print(json.dumps({
        "summary": "weak-scaling",
        "sizes": args.sizes,
        "digest_equal_across_mesh_sizes": True,
        "probe_digest": digests[args.sizes[0]],
    }))


if __name__ == "__main__":
    main()
