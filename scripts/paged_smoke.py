#!/usr/bin/env python
"""paged-storage smoke: the store/ subsystem's CI contract (and
``make paged-smoke``).

Runs a small long-tail streaming session through BOTH layouts on CPU and
asserts the paged subsystem's two promises:

* **byte equality** — a paged streaming session fed the same frames as a
  padded one produces identical spans, patches and full-state digests;
* **observable** — the ``peritext_page_*`` gauges render in the
  Prometheus exposition, ``/devprof.json``'s snapshot carries the
  ``page_pool`` section, and ``health_snapshot`` composes it.

Artifacts (``paged-report.json``, a devprof snapshot, the Prometheus
exposition) are written for upload.  Exit nonzero on any violation — a
paged-storage regression fails CI like a correctness one.
"""

import argparse
import json
import os
import random
import sys
from pathlib import Path

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seed", type=int, default=8)
    parser.add_argument("--out", default="paged-artifacts",
                        help="artifact directory")
    args = parser.parse_args()

    from peritext_tpu.obs import GLOBAL_DEVPROF, health_snapshot, prometheus_text
    from peritext_tpu.parallel.codec import encode_frame
    from peritext_tpu.parallel.streaming import StreamingMerge
    from peritext_tpu.testing.fuzz import generate_workload

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    report = {"seed": args.seed}

    # a fleet of short docs: the first 12 of a 24-doc fuzz workload
    workloads = generate_workload(seed=args.seed, num_docs=24,
                                  ops_per_doc=8)[:12]

    # -- streaming byte equality under the page pool --------------------------
    rng = random.Random(args.seed)
    arrival = []
    for w in workloads:
        chs = [ch for log in w.values() for ch in log]
        rng.shuffle(chs)
        half = max(1, len(chs) // 2)
        arrival.append([
            encode_frame(sorted(chs[:half], key=lambda c: (c.actor, c.seq))),
            encode_frame(sorted(chs[half:], key=lambda c: (c.actor, c.seq))),
        ])

    def build(layout):
        s = StreamingMerge(
            num_docs=len(arrival), actors=("doc1", "doc2", "doc3"),
            slot_capacity=512, mark_capacity=128, tomb_capacity=128,
            layout=layout,
        )
        for r in range(2):
            s.ingest_frames((d, b[r]) for d, b in enumerate(arrival))
            s.drain()
        return s

    GLOBAL_DEVPROF.reset()
    sp = build("padded")
    with GLOBAL_DEVPROF:
        sq = build("paged")
        dq = sq.digest()
    dp = sp.digest()
    assert dp == dq, f"digest diverged: padded {dp:#x} paged {dq:#x}"
    assert sp.read_all() == sq.read_all(), "streaming spans diverged"
    assert sp.read_patches_all() == sq.read_patches_all(), "patches diverged"
    report["streaming"] = {
        "docs": len(arrival),
        "digest": f"{dq:#010x}",
        "rounds": sq.rounds,
        "page_pool": sq.store.pool_stats(),
        "byte_equal": True,
    }
    print(f"paged-smoke: streaming equal (digest {dq:#010x}, "
          f"{sq.store.pool_stats()['pages_in_use']} pages in use)")

    # -- telemetry surfaces ---------------------------------------------------
    snap = GLOBAL_DEVPROF.snapshot()
    assert snap["page_pool"] is not None, "devprof page_pool section missing"
    assert any(
        o["origin"] == "streaming.paged" for o in snap["occupancy"].values()
    ), "paged occupancy rows missing"
    text = prometheus_text(devprof=GLOBAL_DEVPROF, session=sq)
    for gauge in ("peritext_page_pool_pages", "peritext_page_pages_in_use",
                  "peritext_page_pool_utilization",
                  "peritext_page_internal_frag_ratio"):
        assert gauge in text, f"gauge {gauge} missing from exposition"
    health = health_snapshot(session=sq, devprof=GLOBAL_DEVPROF)
    assert health["session"]["page_pool"]["pages_in_use"] > 0
    assert health["devprof"]["page_pool"] is not None
    report["telemetry"] = {
        "gauges": True,
        "devprof_page_pool": snap["page_pool"],
    }
    print("paged-smoke: peritext_page_* gauges + /devprof.json section OK")

    (out / "paged-report.json").write_text(json.dumps(report, indent=2))
    (out / "devprof-snapshot.json").write_text(json.dumps(snap, indent=2))
    (out / "metrics.prom").write_text(text)
    print(f"paged-smoke: PASS (artifacts in {out})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
