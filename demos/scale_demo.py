#!/usr/bin/env python
"""Pod-scale streaming demo: converge N docs on carried device state.

One collaborative editing session (3 replicas, fuzz-generated) is streamed
to N independent documents as binary wire frames over two arrival rounds —
the config-5 shape of BASELINE.md.  Ingest takes the frame-native fast path
(C++ parse + one-call round scheduling); reads and the convergence digest
resolve the doc axis in memory-bounded blocks, so N scales to 100K docs on
a single chip (BASELINE.md row 5b: 22.6M ops converged on-device in ~2 minutes wall (see BASELINE.md row 5b for the recorded numbers),
zero fallbacks or overflows).

Run: python demos/scale_demo.py [--docs N]   (default 2000; try 100000 on TPU)
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--docs", type=int, default=2000)
    parser.add_argument("--ops-per-doc", type=int, default=220)
    parser.add_argument("--seed", type=int, default=200)
    args = parser.parse_args()

    from peritext_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
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
    print(f"{d} docs x {sum(len(c.ops) for c in changes)} ops "
          f"({total_ops / 1e6:.1f}M total), 2 arrival rounds of wire frames\n")

    sess = StreamingMerge(
        num_docs=d, actors=("doc1", "doc2", "doc3"),
        slot_capacity=512, mark_capacity=160, tomb_capacity=192,
        round_insert_capacity=192, round_delete_capacity=96,
        round_mark_capacity=96,
    )
    t_all = time.perf_counter()
    pending = None
    for r, frame in enumerate(frames):
        if pending is not None:
            # fetch LAST round's digest BEFORE this round's ingest mutates
            # any change history (digest_async's documented precondition for
            # sessions that could hold fallback/overflow docs); the fetch is
            # scalar + overflow only, and the device computed it behind the
            # queue while round r-1's host work finished
            pending.wait()
        t0 = time.perf_counter()
        sess.ingest_frames((doc, frame) for doc in range(d))
        t_ing = time.perf_counter() - t0
        t0 = time.perf_counter()
        sess.drain()
        t_drain = time.perf_counter() - t0
        t0 = time.perf_counter()
        pending = sess.digest_async()  # per-round convergence sync point
        t_sched = time.perf_counter() - t0
        print(f"round {r}: ingest {t_ing:.1f}s, device rounds {t_drain:.1f}s, "
              f"digest scheduled in {t_sched * 1000:.0f}ms (async)")
    wall = time.perf_counter() - t_all

    t0 = time.perf_counter()
    digest = pending.wait()
    t_digest = time.perf_counter() - t0
    assert digest == sess.digest(), "async digest != sync digest"
    for doc in (0, d // 2, d - 1):
        assert sess.read(doc) == expected, f"doc {doc} diverged"
    assert not any(s.fallback for s in sess.docs), "docs demoted to scalar replay"
    # overflowed docs silently read via scalar replay and are masked from the
    # digest — the demo's claim is DEVICE convergence, so none may overflow
    assert sess.overflow_count() == 0, (
        f"{sess.overflow_count()} docs overflowed device capacities"
    )

    # full-sweep reads: every doc's spans and incremental patches in one
    # vectorized pass per block (decode_block_spans / block_char_states)
    t0 = time.perf_counter()
    all_spans = sess.read_all()
    t_read = time.perf_counter() - t0
    assert all(s == expected for s in all_spans), "full-sweep read diverged"
    t0 = time.perf_counter()
    n_patches = sum(len(p) for p in sess.read_patches_all())
    t_patches = time.perf_counter() - t0

    print(f"\nconverged ON DEVICE: digest {digest:#010x} "
          f"(final wait {t_digest:.2f}s; per-round sync is the async schedule above)")
    print(f"{total_ops / 1e6:.1f}M ops in {wall:.1f}s "
          f"({total_ops / wall / 1e3:.0f}K ops/s end-to-end incl. host ingest)")
    print(f"full span sweep {t_read:.1f}s, full patch sweep {t_patches:.1f}s "
          f"({n_patches} patches) across {d} docs")
    print("ALL docs verified against the scalar oracle; 0 fallbacks")


if __name__ == "__main__":
    main()
