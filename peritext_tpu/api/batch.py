"""DocBatch: the batched TPU merge backend.

The user-facing entry for the framework's north-star workload: given change
logs for D collaborative documents (each a dict actor -> [Change], exactly
what the replication layer accumulates), converge all of them at once on
device and return each document's formatted spans.

Pipeline: host causal sort + interning + stream splitting (ops/encode.py) ->
device batched apply (ops/kernel.py) -> device span resolution
(ops/resolve.py) -> host decode (ops/decode.py).  Documents the device path
cannot express (non-text objects, too many actors) or that overflow their
static capacities fall back to the scalar oracle (core/doc.py) transparently;
``MergeReport.fallback_docs`` says which.

Semantically equivalent to constructing a fresh ``core.Doc`` per workload and
replaying all changes — the differential tests assert exactly that equality.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..core.doc import Doc
from ..core.types import Change, FormatSpan
from ..obs import (
    GLOBAL_COUNTERS,
    GLOBAL_DEVPROF,
    GLOBAL_HISTOGRAMS,
    GLOBAL_TRACER,
    MergeStats,
    occupancy_key,
)
from ..ops.decode import decode_block_spans, decode_doc_root
from ..ops.encode import (
    _EMPTY_STREAMS,
    EncodedBatch,
    encode_doc_streams,
    encode_workloads,
    pad_doc_streams,
)
from ..ops.kernel import apply_batch, apply_batch_jit, encoded_arrays_of
from ..ops.packed import PackedDocs, empty_docs
from ..ops.resolve import resolve, resolve_jit
from ..parallel.causal import causal_sort

Workload = Dict[str, List[Change]]


@dataclass
class MergeReport:
    """Outcome of a batched merge."""

    spans: List[List[FormatSpan]]
    #: doc indices resolved by the scalar oracle instead of the device
    fallback_docs: List[int] = field(default_factory=list)
    #: ops applied on device (excludes fallback docs)
    device_ops: int = 0
    #: per-merge observability (stage timings, padding efficiency)
    stats: MergeStats = field(default_factory=MergeStats)
    #: resolved cursor indices (aligned with merge()'s ``cursors`` argument);
    #: -1 = cursor's element does not exist in the converged document
    cursor_positions: Optional[List[List[int]]] = None
    #: per-doc materialized root map (nested maps + text list), equal to the
    #: scalar oracle's ``Doc.root`` — device docs decode their LWW register
    #: table (ops/decode.decode_doc_root), fallback docs replay
    roots: Optional[List[dict]] = None


class DocBatch:
    """Batched document merge engine.

    Capacities are static (XLA compiles one program per shape bucket):
    ``slot_capacity`` bounds elements-including-tombstones per doc,
    ``mark_capacity`` bounds mark ops per doc, ``comment_capacity`` bounds
    distinct interned attrs per doc, ``op_capacity`` bounds the insert and
    delete streams per merge call (None = sized to the batch).

    ``layout`` decides only how docs sit on the device: ``"padded"`` (the
    default) applies one (D, S) batch with every doc at the batch's stream
    widths; ``"ragged"`` applies against store/'s page pool in one program
    over each doc's true op and page counts.  Both give identical results.
    """

    def __init__(
        self,
        slot_capacity: int = 256,
        mark_capacity: int = 64,
        comment_capacity: int = 32,
        op_capacity: Optional[int] = None,
        map_capacity: int = 32,
        jit: bool = True,
        mesh=None,
        guard: bool = False,
        tracer=None,
        layout: str = "padded",
        page_size: Optional[int] = None,
    ) -> None:
        #: storage layout: "padded" (one (D, S) batch, every doc at the
        #: widest bucket — the byte-equality oracle) or "ragged" (store/
        #: page pool + per-doc page tables, ONE apply over every doc's true
        #: op and page counts — one compiled program; see ops/ragged.py)
        if layout not in ("padded", "ragged"):
            raise ValueError(f"unknown layout: {layout!r}")
        if layout == "ragged" and mesh is not None:
            raise ValueError(
                f"layout={layout!r} does not support a mesh yet"
            )
        self.layout = layout
        if page_size is None:
            from ..store import DEFAULT_PAGE_SIZE

            page_size = DEFAULT_PAGE_SIZE
        self.page_size = int(page_size)
        if layout == "ragged" and slot_capacity % self.page_size:
            raise ValueError(
                f"slot_capacity {slot_capacity} must be a multiple of "
                f"page_size {self.page_size} under layout={layout!r}"
            )
        #: pipeline-span producer (obs/spans.py): merge() opens a
        #: ``batch.merge`` span with encode/apply/resolve/decode children,
        #: whose durations also feed MergeStats — one clock, two surfaces
        self.tracer = tracer if tracer is not None else GLOBAL_TRACER
        self.slot_capacity = slot_capacity
        self.mark_capacity = mark_capacity
        self.comment_capacity = comment_capacity
        self.op_capacity = op_capacity
        self.map_capacity = map_capacity
        #: fault-domain guard: a device-stage failure (XLA compile/runtime
        #: error, device OOM) degrades the whole merge to the scalar oracle
        #: — slower but byte-identical — instead of raising.  Off by default
        #: so development surfaces device bugs loudly; the supervisor layer
        #: turns it on for production serving.
        self.guard = guard
        #: optional jax.sharding.Mesh; when set, the doc axis of every tensor
        #: is sharded across it (pure data parallelism; XLA adds collectives
        #: only for cross-doc reductions like the convergence digest).
        self.mesh = mesh
        # Reuse the module-level jitted wrappers: JAX's compilation cache is
        # keyed per-wrapper, so per-instance jax.jit would recompile the same
        # kernel for every DocBatch.
        self._apply = apply_batch_jit if jit else apply_batch
        self._resolve = resolve_jit if jit else resolve
        #: the page store of the most recent ragged merge (telemetry/tests)
        self.last_store = None

    # -- device pipeline ---------------------------------------------------

    def encode(self, workloads: Sequence[Workload]) -> EncodedBatch:
        return encode_workloads(
            list(workloads),
            insert_capacity=self.op_capacity,
            delete_capacity=self.op_capacity,
            mark_capacity=self.mark_capacity,
            tracer=self.tracer,
        )

    def apply_encoded(self, encoded: EncodedBatch) -> PackedDocs:
        """Run the batched two-phase apply on an encoded batch."""
        arrays = encoded_arrays_of(encoded)
        num_docs = encoded.num_docs
        if self.mesh is not None:
            from ..parallel.mesh import pad_doc_axis, shard_docs
            import jax

            arrays = jax.tree_util.tree_map(
                lambda x: pad_doc_axis(np.asarray(x), self.mesh.size), arrays
            )
            arrays = shard_docs(arrays, self.mesh)
            num_docs = arrays[0].shape[0]
        state = empty_docs(
            num_docs,
            self.slot_capacity,
            self.mark_capacity,
            tomb_capacity=arrays[3].shape[1],  # delete-stream width
            map_capacity=self.map_capacity,
        )
        if self.mesh is not None:
            from ..parallel.mesh import shard_docs

            state = shard_docs(state, self.mesh)
        return self._apply(state, arrays)

    def merge(
        self,
        workloads: Sequence[Workload],
        cursors: Optional[Sequence[Sequence[dict]]] = None,
    ) -> MergeReport:
        """Converge every workload; returns per-doc formatted spans.

        ``cursors`` optionally gives, per document, stable cursors
        (``{"objectId", "elemId"}``, the reference's ``Cursor`` shape,
        src/micromerge.ts:859-870) to resolve against the converged state;
        resolved visible indices land in ``MergeReport.cursor_positions``
        (-1 when the cursor's element is absent).  Device docs resolve on
        device (ops/resolve.resolve_cursors); fallback docs via the oracle.
        """
        with self.tracer.span("batch.merge", docs=len(workloads)) as sp:
            report = self._merge(workloads, cursors)
        GLOBAL_HISTOGRAMS.observe("merge.seconds", sp.duration)
        return report

    def _merge(
        self,
        workloads: Sequence[Workload],
        cursors: Optional[Sequence[Sequence[dict]]],
    ) -> MergeReport:
        """merge() behind its pipeline span: each stage runs under a child
        span whose duration doubles as the MergeStats stage wall-clock.  The
        layout picks only the encode and how docs are placed on the device
        (``apply_encoded`` or ``_apply_ragged``); resolve, fallback, cursors,
        decode and accounting are one path for both."""
        ragged = self.layout == "ragged"
        d_total = len(workloads)
        stats = MergeStats(docs=d_total)
        with self.tracer.span("batch.encode") as sp:
            enc = self._encode_ragged(workloads) if ragged else self.encode(workloads)
        stats.encode_seconds = sp.duration

        try:
            with self.tracer.span("batch.apply") as sp:
                if ragged:
                    store, plan = self._apply_ragged(enc)
                else:
                    state = self.apply_encoded(enc)
                    np.asarray(state.num_slots)  # host sync: time apply honestly
            stats.apply_seconds = sp.duration

            with self.tracer.span("batch.resolve") as sp:
                if ragged:
                    # one materialize at the batch's true max page count —
                    # the only dense block the ragged merge builds, sized by
                    # the data, not a bucket; row i IS doc i
                    g_max = max(1, int(np.max(plan.page_count)))
                    state = store.materialize_rows(
                        np.arange(d_total, dtype=np.int64), g_max
                    )
                resolved_dev = self._resolve(state, self.comment_capacity)
                # One whole-array transfer per field, up front: decoding per
                # doc on the raw (possibly mesh-sharded) arrays would do 5
                # device gathers per document.
                resolved = type(resolved_dev)(
                    *(np.asarray(x) for x in resolved_dev)
                )
            stats.resolve_seconds = sp.duration
        except Exception as exc:  # graftlint: boundary(guarded merge: ANY device-path failure degrades to the scalar oracle; re-raised when unguarded)
            if not self.guard:
                raise
            return self._degraded_merge(workloads, cursors, stats, exc)

        # both encodes put their capacity fallbacks in enc.fallback_docs;
        # overflow counts on real rows only (a mesh pads the doc axis)
        fallback = set(enc.fallback_docs) | {
            int(d) for d in np.nonzero(resolved.overflow)[0] if d < d_total
        }

        # Fallback docs may be replayed for both cursors and spans; build each
        # oracle doc at most once per merge.
        oracle_docs: Dict[int, Doc] = {}

        def oracle_doc_for(d: int) -> Doc:
            if d not in oracle_docs:
                oracle_docs[d] = _oracle_doc(workloads[d])
            return oracle_docs[d]

        cursor_positions: Optional[List[List[int]]] = None
        if cursors is not None:
            cursor_positions = self._resolve_cursor_batch(
                state, resolved_dev.visible, enc, cursors, fallback, oracle_doc_for
            )

        with self.tracer.span("batch.decode") as sp:
            # register table transfer (small: 5 x (D, R) int32)
            regs = SimpleNamespace(
                r_obj=np.asarray(state.r_obj), r_key=np.asarray(state.r_key),
                r_op=np.asarray(state.r_op), r_kind=np.asarray(state.r_kind),
                r_val=np.asarray(state.r_val), num_regs=np.asarray(state.num_regs),
            )
            # one vectorized span decode for the whole batch (Python touches
            # only mark-run segments); fallback docs replay through the oracle
            device_mask = np.zeros(resolved.visible.shape[0], bool)
            for d in range(d_total):
                device_mask[d] = d not in fallback
            block_spans = decode_block_spans(
                resolved,
                lambda d: enc.attr_tables[d],
                lambda d: enc.attr_tables[d],
                doc_mask=device_mask,
            )
            spans: List[List[FormatSpan]] = []
            roots: List[dict] = []
            device_ops = 0
            fallback_ops = 0
            for d in range(d_total):
                if d in fallback:
                    doc = oracle_doc_for(d)
                    spans.append(doc.get_text_with_formatting(["text"]))
                    roots.append(doc.root)
                    fallback_ops += int(enc.num_ops[d])
                else:
                    spans.append(block_spans[d])
                    roots.append(
                        decode_doc_root(regs, resolved, d, enc.map_tables[d])
                    )
                    device_ops += int(enc.num_ops[d])
        stats.decode_seconds = sp.duration

        widths = (
            enc.ins_op.shape[1],
            enc.del_target.shape[1],
            next(iter(enc.marks.values())).shape[1],
            next(iter(enc.map_ops.values())).shape[1],
        )
        real_ops = int(enc.num_ops.sum())
        # what the dispatched program paid: every doc at the padded widths,
        # or, ragged, the real ops alone (the apply walks true counts)
        capacity = max(real_ops, 1) if ragged else enc.num_docs * sum(widths)
        stats.device_ops = device_ops
        stats.fallback_ops = fallback_ops
        stats.fallback_docs = len(fallback)
        stats.device_docs = d_total - len(fallback)
        stats.padding_efficiency = real_ops / capacity if capacity else 0.0
        if ragged:
            pool = store.pool_stats()
            stats.extras["layout_ragged"] = 1.0
            stats.extras["page_pool_utilization"] = pool["pool_utilization"]
            stats.extras["page_internal_frag_ratio"] = pool["internal_frag_ratio"]
        if GLOBAL_DEVPROF.enabled:
            # one-shot batch merges land in the same bucket-occupancy table
            # as streaming rounds, keyed by their stream widths
            GLOBAL_DEVPROF.observe_round(
                occupancy_key(enc.num_docs, *widths), real_ops, capacity,
                origin="batch.merge.ragged" if ragged else "batch.merge",
            )
            if ragged:
                GLOBAL_DEVPROF.observe_ragged(
                    docs_walked=plan.docs_walked,
                    pages_walked=plan.pages_walked,
                    real_ops=real_ops,
                )
                GLOBAL_DEVPROF.observe_page_pool(pool)
            GLOBAL_DEVPROF.sample_memory()
        GLOBAL_COUNTERS.add("merge.calls")
        if ragged:
            GLOBAL_COUNTERS.add("merge.ragged_calls")
        GLOBAL_COUNTERS.add("merge.device_ops", device_ops)
        GLOBAL_COUNTERS.add("merge.fallback_docs", len(fallback))
        return MergeReport(
            spans=spans,
            fallback_docs=sorted(fallback),
            device_ops=device_ops,
            stats=stats,
            cursor_positions=cursor_positions,
            roots=roots,
        )

    # -- ragged layout (ops/ragged.py over store/) ----------------------------

    def _encode_ragged(self, workloads: Sequence[Workload]) -> EncodedBatch:
        """The ragged encode: every doc's streams at their own length, the
        configured capacities applied as per-doc fallback thresholds (so the
        same docs fall back as under padded), then one pad to the batch's
        own true maxima."""
        per_doc, fallback, actor_tables, attr_tables, map_tables = (
            encode_doc_streams(workloads, self.tracer)
        )
        with self.tracer.span("batch.encode.pad"):
            fallback = set(fallback)
            for d, s in enumerate(per_doc):
                over = len(s.marks) > self.mark_capacity
                if self.op_capacity is not None:
                    over = over or len(s.ins) > self.op_capacity \
                        or len(s.dels) > self.op_capacity
                if over:
                    fallback.add(d)
            return pad_doc_streams(
                [_EMPTY_STREAMS if d in fallback else s
                 for d, s in enumerate(per_doc)],
                sorted(fallback),
                actor_tables,
                attr_tables,
                map_tables=map_tables,
            )

    def _apply_ragged(self, enc: EncodedBatch):
        """The ragged placement and apply: a page pool pre-sized to the
        batch's true page demand, and ONE ``ops/ragged.apply_batch_ragged``
        dispatch that walks every doc's true op count against its true
        pages — no power-of-two buckets, so the merge compiles one apply
        executable whatever the doc-size mix.  Returns ``(store, plan)``
        after a host sync."""
        import jax.numpy as jnp

        from ..ops.ragged import apply_batch_ragged_jit, plan_arrays
        from ..store.paged import PagedDocStore, group_stream_arrays
        from ..store.ragged import ragged_plan

        d_total = enc.num_docs
        with self.tracer.span("batch.apply.plan"):
            ins_op = np.asarray(enc.ins_op)
            ins_counts = (ins_op != 0).sum(axis=1)
            # inserts that continue a typing run: their reference is the
            # op just before them in the doc's stream (the insert kernel
            # splices such a run once, at its end)
            run_inserts = int(np.count_nonzero(
                (ins_op[:, 1:] != 0) & (ins_op[:, :-1] != 0)
                & (np.asarray(enc.ins_ref)[:, 1:] == ins_op[:, :-1])))
            del_counts = (np.asarray(enc.del_target) != 0).sum(axis=1)
            max_pages = max(1, self.slot_capacity // self.page_size)
            page_need = np.minimum(
                -(-np.maximum(ins_counts, 1) // self.page_size), max_pages
            )
            store = PagedDocStore(
                d_total,
                slot_capacity=self.slot_capacity,
                mark_capacity=self.mark_capacity,
                tomb_capacity=enc.del_target.shape[1],
                map_capacity=self.map_capacity,
                page_size=self.page_size,
                # page 0 is the null page; true demand, no bucket round
                initial_pages=1 + int(page_need.sum()),
            )
            self.last_store = store
            store.ensure_rows(np.arange(d_total, dtype=np.int64), ins_counts)
            plan = ragged_plan(store)
            planes = plan_arrays(plan)
            streams = group_stream_arrays(enc, None, d_total)
            ins_dev = jnp.asarray(ins_counts, jnp.int32)
        # the device loops run to the batch's true maxima
        GLOBAL_COUNTERS.add("merge.ragged_pages", plan.pages_walked)
        GLOBAL_COUNTERS.add(
            "merge.ragged_loop_steps",
            int(ins_counts.max(initial=0)) + int(del_counts.max(initial=0)),
        )
        GLOBAL_COUNTERS.add("merge.ragged_inserts", int(ins_counts.sum()))
        GLOBAL_COUNTERS.add("merge.ragged_run_inserts", run_inserts)
        store.pool_elem, store.pool_char, store.aux = apply_batch_ragged_jit(
            store.pool_elem, store.pool_char, store.aux,
            *planes, streams, ins_dev,
        )
        np.asarray(store.aux_field("num_slots"))  # host sync: time apply honestly
        return store, plan

    def _degraded_merge(
        self, workloads, cursors, stats: MergeStats, exc: Exception
    ) -> MergeReport:
        """Guarded-merge degradation: the whole batch replays through the
        scalar oracle (byte-identical spans/roots/cursors, no device).  The
        failure is preserved as evidence in counters and ``stats.extras``."""
        from ..ops.resolve import oracle_cursor_positions

        GLOBAL_COUNTERS.add("merge.guarded_fallbacks")
        spans: List[List[FormatSpan]] = []
        roots: List[dict] = []
        positions: Optional[List[List[int]]] = [] if cursors is not None else None
        fallback_ops = 0
        with self.tracer.span("batch.degraded-replay", docs=len(workloads)) as sp:
            for d, workload in enumerate(workloads):
                doc = _oracle_doc(workload)
                spans.append(doc.get_text_with_formatting(["text"]))
                roots.append(doc.root)
                fallback_ops += sum(
                    len(ch.ops) for log in workload.values() for ch in log
                )
                if positions is not None:
                    positions.append(oracle_cursor_positions(doc, cursors[d]))
        stats.decode_seconds = sp.duration
        stats.fallback_docs = len(workloads)
        stats.device_docs = 0
        stats.fallback_ops = fallback_ops
        stats.extras["guarded_fallback"] = 1.0
        stats.extras["guarded_error"] = repr(exc)
        return MergeReport(
            spans=spans,
            fallback_docs=list(range(len(workloads))),
            device_ops=0,
            stats=stats,
            cursor_positions=positions,
            roots=roots,
        )

    def _resolve_cursor_batch(
        self, state, visible_dev, encoded, cursors, fallback, oracle_doc_for
    ) -> List[List[int]]:
        """Pack per-doc cursor element ids with each doc's actor table and
        resolve them on device in one batched call; fallback docs replay
        through the oracle (shared helpers in ops/resolve.py)."""
        from ..ops.resolve import (
            oracle_cursor_positions,
            pack_cursor_rows,
            resolve_cursors_jit,
        )

        num_docs = state.elem_id.shape[0]
        cursor_map = {
            d: doc_cursors
            for d, doc_cursors in enumerate(cursors)
            if d not in fallback
        }
        cursor_elem = pack_cursor_rows(
            cursor_map, num_docs, lambda d: encoded.actor_tables[d]
        )
        positions = np.asarray(
            resolve_cursors_jit(state, visible_dev, cursor_elem)
        )
        out: List[List[int]] = []
        for d, doc_cursors in enumerate(cursors):
            if d in fallback:
                out.append(oracle_cursor_positions(oracle_doc_for(d), doc_cursors))
            else:
                out.append([int(p) for p in positions[d, : len(doc_cursors)]])
        return out


def _oracle_doc(workload: Workload) -> Doc:
    doc = Doc("batch-fallback")
    for change in causal_sort([ch for log in workload.values() for ch in log]):
        doc.apply_change(change)
    return doc


def _oracle_spans(workload: Workload) -> List[FormatSpan]:
    return _oracle_doc(workload).get_text_with_formatting(["text"])


def oracle_merge(workloads: Sequence[Workload]) -> List[List[FormatSpan]]:
    """Scalar reference path for the same inputs (differential-test anchor)."""
    return [_oracle_spans(w) for w in workloads]
