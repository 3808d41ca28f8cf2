#!/usr/bin/env python
"""ragged-layout smoke: the ops/ragged.py CI contract (and
``make ragged-smoke``).

Runs the long-tail shape through ``layout="ragged"`` on CPU and asserts
the ragged subsystem's three promises:

* **byte equality, kernel-first** — the Pallas kernel under
  ``interpret=True`` (the TPU path's semantics, minus Mosaic) and the lax
  pool walk both reproduce the padded apply field by field, and the
  ragged ``DocBatch`` merge / streaming session match the padded oracle
  end to end (spans, roots, patches, digest);
* **the buckets are gone** — the merge reports
  ``padding_efficiency == 1.0`` (trip counts are data: zero padded-op
  waste, where even the paged layout burns its pow-2 page buckets);
* **observable** — the ``peritext_ragged_*`` gauges render in the
  Prometheus exposition and ``devprof.snapshot()`` carries the
  ``ragged`` section (docs/pages walked, padded-slot waste 0).

Artifacts (``ragged-report.json``, a devprof snapshot, the Prometheus
exposition) are written for upload.  Exit nonzero on any violation — a
ragged regression fails CI like a correctness one.
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
    parser.add_argument("--out", default="ragged-artifacts",
                        help="artifact directory")
    args = parser.parse_args()

    import numpy as np

    import jax.numpy as jnp

    from peritext_tpu.api.batch import DocBatch
    from peritext_tpu.obs import GLOBAL_DEVPROF, prometheus_text
    from peritext_tpu.ops.encode import encode_doc_streams, pad_doc_streams
    from peritext_tpu.ops.kernel import apply_batch_jit, encoded_arrays_of
    from peritext_tpu.ops.packed import empty_docs
    from peritext_tpu.ops.ragged import (
        apply_batch_ragged_jit,
        plan_arrays,
        stream_counts,
    )
    from peritext_tpu.parallel.codec import encode_frame
    from peritext_tpu.parallel.streaming import StreamingMerge
    from peritext_tpu.store.paged import PagedDocStore, group_stream_arrays
    from peritext_tpu.store.ragged import ragged_plan
    from peritext_tpu.testing.fuzz import generate_workload

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    report = {"seed": args.seed}

    # long-tail workload: a tweet fleet plus one essay
    tweets = generate_workload(seed=args.seed, num_docs=24, ops_per_doc=8)
    essay = generate_workload(seed=args.seed + 90_001, num_docs=1,
                              ops_per_doc=300)
    workloads = tweets + essay

    # -- kernel differential, BOTH impls (interpret = the TPU semantics) -----
    per_doc, fallback, actor_tables, attr_tables, map_tables = (
        encode_doc_streams(workloads)
    )
    enc = pad_doc_streams(
        per_doc, fallback, actor_tables, attr_tables, map_tables
    )
    d = enc.ins_ref.shape[0]
    ins_counts = stream_counts(enc)
    oracle = apply_batch_jit(
        empty_docs(d, 512, 128), encoded_arrays_of(enc)
    )
    for impl in ("lax", "pallas_interpret"):
        store = PagedDocStore(d, 512, 128)
        rows = np.arange(d, dtype=np.int64)
        store.ensure_rows(rows, np.asarray(ins_counts, np.int64))
        store.pool_elem, store.pool_char, store.aux = apply_batch_ragged_jit(
            store.pool_elem, store.pool_char, store.aux,
            *plan_arrays(ragged_plan(store)),
            group_stream_arrays(enc, None, d),
            jnp.asarray(ins_counts),
            ragged_impl=impl,
        )
        got = store.materialize_rows(rows, bucket_pages=store.max_doc_pages)
        for f in oracle._fields:
            a = np.asarray(getattr(oracle, f))
            b = np.asarray(getattr(got, f))
            if f in ("elem_id", "char"):
                b = b[:, : a.shape[1]]
            assert np.array_equal(a, b), f"ragged/{impl} diverges on {f}"
    report["kernel"] = {"docs": d, "impls": ["lax", "pallas_interpret"],
                        "byte_equal": True}
    print(f"ragged-smoke: kernel equal on {d} docs (lax + pallas interpret)")

    # -- batch byte equality + zero waste ------------------------------------
    GLOBAL_DEVPROF.reset()
    padded = DocBatch(slot_capacity=512, mark_capacity=128).merge(workloads)
    with GLOBAL_DEVPROF:
        ragged_batch = DocBatch(slot_capacity=512, mark_capacity=128,
                                layout="ragged")
        ragged = ragged_batch.merge(workloads)
    assert padded.spans == ragged.spans, "ragged batch diverged from padded"
    assert padded.roots == ragged.roots, "ragged roots diverged from padded"
    assert padded.fallback_docs == ragged.fallback_docs
    assert ragged.stats.padding_efficiency == 1.0, (
        "ragged layout reported padded-op waste; trip counts must be data"
    )
    report["batch"] = {
        "docs": len(workloads),
        "padding_efficiency_padded": padded.stats.padding_efficiency,
        "padding_efficiency_ragged": ragged.stats.padding_efficiency,
        "page_pool": ragged_batch.last_store.pool_stats(),
        "byte_equal": True,
    }
    print(f"ragged-smoke: batch equal; stream efficiency "
          f"{padded.stats.padding_efficiency:.3f} -> "
          f"{ragged.stats.padding_efficiency:.3f}")

    # -- streaming byte equality through the ragged drain ---------------------
    rng = random.Random(args.seed)
    arrival = []
    for w in workloads[:12]:
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

    sp = build("padded")
    with GLOBAL_DEVPROF:
        sq = build("ragged")
        dq = sq.digest()
    dp = sp.digest()
    assert dp == dq, f"digest diverged: padded {dp:#x} ragged {dq:#x}"
    assert sp.read_all() == sq.read_all(), "streaming spans diverged"
    assert sp.read_patches_all() == sq.read_patches_all(), "patches diverged"
    report["streaming"] = {
        "docs": len(arrival),
        "digest": f"{dq:#010x}",
        "rounds": sq.rounds,
        "page_pool": sq.store.pool_stats(),
        "byte_equal": True,
    }
    print(f"ragged-smoke: streaming equal (digest {dq:#010x}, "
          f"{sq.store.pool_stats()['pages_in_use']} pages in use)")

    # -- telemetry surfaces ---------------------------------------------------
    snap = GLOBAL_DEVPROF.snapshot()
    rg = snap["ragged"]
    assert rg is not None, "devprof ragged section missing"
    assert rg["padded_slot_waste"] == 0, "ragged padded-slot waste must be 0"
    assert rg["docs_walked"] > 0 and rg["pages_walked"] > 0
    text = prometheus_text(devprof=GLOBAL_DEVPROF, session=sq)
    for gauge in ("peritext_ragged_dispatches", "peritext_ragged_docs_walked",
                  "peritext_ragged_pages_walked",
                  "peritext_ragged_padded_slot_waste"):
        assert gauge in text, f"gauge {gauge} missing from exposition"
    report["telemetry"] = {"gauges": True, "devprof_ragged": rg}
    print("ragged-smoke: peritext_ragged_* gauges + devprof section OK")

    (out / "ragged-report.json").write_text(json.dumps(report, indent=2))
    (out / "devprof-snapshot.json").write_text(json.dumps(snap, indent=2))
    (out / "metrics.prom").write_text(text)
    print(f"ragged-smoke: PASS (artifacts in {out})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
