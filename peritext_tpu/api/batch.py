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
from ..ops.decode import decode_block_spans
from ..ops.encode import EncodedBatch, encode_workloads
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
        #: widest bucket — the byte-equality oracle), "paged" (store/
        #: page pool + per-doc page tables; docs group by size bucket so
        #: stream padding AND element-plane memory scale with real ops),
        #: or "ragged" (same pool, but ONE apply over every doc's true op
        #: and page counts — no bucket ladder, one compiled program; see
        #: ops/ragged.py).
        if layout not in ("padded", "paged", "ragged"):
            raise ValueError(f"unknown layout: {layout!r}")
        if layout in ("paged", "ragged") and mesh is not None:
            raise ValueError(
                f"layout={layout!r} does not support a mesh yet"
            )
        self.layout = layout
        if page_size is None:
            from ..store import DEFAULT_PAGE_SIZE

            page_size = DEFAULT_PAGE_SIZE
        self.page_size = int(page_size)
        if layout in ("paged", "ragged") and slot_capacity % self.page_size:
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
        #: the page store of the most recent paged merge (telemetry/tests)
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
            if self.layout == "paged":
                report = self._merge_paged(workloads, cursors)
            elif self.layout == "ragged":
                report = self._merge_ragged(workloads, cursors)
            else:
                report = self._merge(workloads, cursors)
        GLOBAL_HISTOGRAMS.observe("merge.seconds", sp.duration)
        return report

    def _merge(
        self,
        workloads: Sequence[Workload],
        cursors: Optional[Sequence[Sequence[dict]]],
    ) -> MergeReport:
        """merge() behind its pipeline span: each stage runs under a child
        span whose duration doubles as the MergeStats stage wall-clock."""
        stats = MergeStats(docs=len(workloads))
        with self.tracer.span("batch.encode") as sp:
            encoded = self.encode(workloads)
        stats.encode_seconds = sp.duration

        try:
            with self.tracer.span("batch.apply") as sp:
                state = self.apply_encoded(encoded)
                np.asarray(state.num_slots)  # host sync: time apply honestly
            stats.apply_seconds = sp.duration

            with self.tracer.span("batch.resolve") as sp:
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

        overflow = np.asarray(resolved.overflow)
        fallback = set(encoded.fallback_docs) | {
            int(d) for d in np.nonzero(overflow)[0] if d < len(workloads)
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
                state, resolved_dev.visible, encoded, cursors, fallback, oracle_doc_for
            )

        with self.tracer.span("batch.decode") as sp:
            from ..ops.decode import decode_doc_root
            from types import SimpleNamespace

            # register table transfer (small: 5 x (D, R) int32)
            regs = SimpleNamespace(
                r_obj=np.asarray(state.r_obj), r_key=np.asarray(state.r_key),
                r_op=np.asarray(state.r_op), r_kind=np.asarray(state.r_kind),
                r_val=np.asarray(state.r_val), num_regs=np.asarray(state.num_regs),
            )
            # one vectorized span decode for the whole batch (Python touches
            # only mark-run segments); fallback docs replay through the oracle
            device_mask = np.zeros(resolved.visible.shape[0], bool)
            for d in range(len(workloads)):
                device_mask[d] = d not in fallback
            block_spans = decode_block_spans(
                resolved,
                lambda d: encoded.attr_tables[d],
                lambda d: encoded.attr_tables[d],
                doc_mask=device_mask,
            )
            spans: List[List[FormatSpan]] = []
            roots: List[dict] = []
            device_ops = 0
            fallback_ops = 0
            for d, workload in enumerate(workloads):
                if d in fallback:
                    doc = oracle_doc_for(d)
                    spans.append(doc.get_text_with_formatting(["text"]))
                    roots.append(doc.root)
                    fallback_ops += int(encoded.num_ops[d])
                else:
                    spans.append(block_spans[d])
                    roots.append(
                        decode_doc_root(regs, resolved, d, encoded.map_tables[d])
                    )
                    device_ops += int(encoded.num_ops[d])
        stats.decode_seconds = sp.duration

        stream_capacity = encoded.num_docs * (
            encoded.ins_op.shape[1]
            + encoded.del_target.shape[1]
            + next(iter(encoded.marks.values())).shape[1]
            + next(iter(encoded.map_ops.values())).shape[1]
        )
        stats.device_ops = device_ops
        stats.fallback_ops = fallback_ops
        stats.fallback_docs = len(fallback)
        stats.device_docs = len(workloads) - len(fallback)
        stats.padding_efficiency = (
            float(encoded.num_ops.sum()) / stream_capacity if stream_capacity else 0.0
        )
        if GLOBAL_DEVPROF.enabled:
            # one-shot batch merges land in the same bucket-occupancy table
            # as streaming rounds, keyed by their padded stream widths
            GLOBAL_DEVPROF.observe_round(
                occupancy_key(
                    encoded.num_docs,
                    encoded.ins_op.shape[1],
                    encoded.del_target.shape[1],
                    next(iter(encoded.marks.values())).shape[1],
                    next(iter(encoded.map_ops.values())).shape[1],
                ),
                int(encoded.num_ops.sum()), stream_capacity,
                origin="batch.merge",
            )
            GLOBAL_DEVPROF.sample_memory()
        GLOBAL_COUNTERS.add("merge.calls")
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

    # -- paged layout (store/) ----------------------------------------------

    def _merge_paged(
        self,
        workloads: Sequence[Workload],
        cursors: Optional[Sequence[Sequence[dict]]],
    ) -> MergeReport:
        """merge() under ``layout="paged"`` (store/paged.py): docs group
        into power-of-two page-count buckets; each bucket encodes, applies
        and resolves at ITS OWN widths through the page pool's gather-based
        apply (ops/kernel.apply_batch_paged), so stream padding and
        element-plane memory scale with real ops instead of every doc
        paying the widest doc's bucket.  The padded path is the
        byte-equality oracle — the differential tests pin spans / roots /
        cursors equality across both layouts on every fuzz seed."""
        from types import SimpleNamespace

        from ..ops.decode import decode_block_spans, decode_doc_root
        from ..ops.encode import _EMPTY_STREAMS, encode_doc_streams, pad_doc_streams
        from ..store.paged import (
            PagedDocStore,
            _pow2,
            group_stream_arrays,
        )

        stats = MergeStats(docs=len(workloads))
        d_total = len(workloads)
        with self.tracer.span("batch.encode") as sp:
            per_doc, fb_encode, actor_tables, attr_tables, map_tables = (
                encode_doc_streams(workloads, self.tracer)
            )
            with self.tracer.span("batch.encode.pad"):
                fb_set = set(fb_encode)
                # capacity fallback happens HERE, not in pad_doc_streams: group
                # streams size to the subgroup max (that is the point of the
                # layout), so the configured capacities act as per-doc fallback
                # thresholds exactly as they do on the padded path — same docs
                # fall back under both layouts
                empty = _EMPTY_STREAMS
                for d in range(d_total):
                    s = per_doc[d]
                    over = len(s.marks) > self.mark_capacity
                    if self.op_capacity is not None:
                        over = over or len(s.ins) > self.op_capacity \
                            or len(s.dels) > self.op_capacity
                    if over:
                        fb_set.add(d)
                # two-component size bucketing: page need (inserts drive slots —
                # the delete/mark/register tables stay dense aux rows) AND a
                # power-of-two total-op bucket.  The second component matters
                # below one page: without it every sub-page tweet pads its
                # streams to the widest tweet's op count, which is most of the
                # long-tail waste the paged layout exists to kill.  Fallback
                # docs carry no streams and ride the smallest bucket as no-ops.
                max_pages = max(1, self.slot_capacity // self.page_size)
                buckets: Dict[tuple, List[int]] = {}
                for d in range(d_total):
                    s = empty if d in fb_set else per_doc[d]
                    ops = len(s.ins) + len(s.dels) + len(s.marks) + len(s.maps)
                    g = min(
                        _pow2(-(-max(1, len(s.ins)) // self.page_size)), max_pages
                    )
                    buckets.setdefault((g, _pow2(max(8, ops))), []).append(d)
                groups = [(g, np.asarray(buckets[(g, sb)], np.int64))
                          for g, sb in sorted(buckets)]
                encs = []
                for g, docs in groups:
                    local_fb = [i for i, d in enumerate(docs) if int(d) in fb_set]
                    enc_g = pad_doc_streams(
                        [empty if int(d) in fb_set else per_doc[int(d)]
                         for d in docs],
                        local_fb,
                        [actor_tables[int(d)] for d in docs],
                        [attr_tables[int(d)] for d in docs],
                        map_tables=[map_tables[int(d)] for d in docs],
                    )
                    encs.append((g, docs, enc_g))
        stats.encode_seconds = sp.duration

        try:
            with self.tracer.span("batch.apply") as sp:
                tomb_cap = max(
                    (enc.del_target.shape[1] for _, _, enc in encs), default=8
                )
                store = PagedDocStore(
                    d_total,
                    slot_capacity=self.slot_capacity,
                    mark_capacity=self.mark_capacity,
                    tomb_capacity=tomb_cap,
                    map_capacity=self.map_capacity,
                    page_size=self.page_size,
                )
                self.last_store = store
                stream_capacity = 0
                real_ops = 0
                for g, docs, enc in encs:
                    ins_counts = (np.asarray(enc.ins_op) != 0).sum(axis=1)
                    store.ensure_rows(docs, ins_counts)
                    b = _pow2(len(docs))
                    store.apply_rows(
                        docs, g, group_stream_arrays(enc, None, b),
                        pad_rows_to=b,
                    )
                    widths = (
                        enc.ins_op.shape[1], enc.del_target.shape[1],
                        next(iter(enc.marks.values())).shape[1],
                        next(iter(enc.map_ops.values())).shape[1],
                    )
                    # capacity is what the DISPATCHED program paid: b padded
                    # rows, not the real group size — the streaming paged
                    # path and the occupancy table must agree on this
                    group_cap = b * sum(widths)
                    stream_capacity += group_cap
                    real_ops += int(enc.num_ops.sum())
                    if GLOBAL_DEVPROF.enabled:
                        GLOBAL_DEVPROF.observe_round(
                            occupancy_key(b, *widths),
                            int(enc.num_ops.sum()), group_cap,
                            origin="batch.merge.paged",
                        )
                # host sync: time apply honestly (mirror of _merge)
                np.asarray(store.aux_field("num_slots"))
            stats.apply_seconds = sp.duration

            with self.tracer.span("batch.resolve") as sp:
                resolved_groups = []
                for g, docs, enc in encs:
                    # same power-of-two row bucket as the apply: gather,
                    # resolve and cursor programs compile once per
                    # (rows-bucket, pages-bucket, widths), never per exact
                    # group size; padding rows are masked downstream
                    b = _pow2(len(docs))
                    state_g = store.materialize_rows(docs, g, pad_rows_to=b)
                    res_dev = self._resolve(state_g, self.comment_capacity)
                    res_np = type(res_dev)(*(np.asarray(x) for x in res_dev))
                    resolved_groups.append((docs, enc, state_g, res_dev, res_np))
            stats.resolve_seconds = sp.duration
        except Exception as exc:  # graftlint: boundary(guarded merge: ANY device-path failure degrades to the scalar oracle; re-raised when unguarded)
            if not self.guard:
                raise
            return self._degraded_merge(workloads, cursors, stats, exc)

        fallback = set(fb_encode)
        for docs, enc, _, _, res_np in resolved_groups:
            fallback.update(int(docs[i]) for i in enc.fallback_docs)
            # only the REAL rows: padding rows clamp-gather a neighbor's aux
            # and may carry its overflow flag
            fallback.update(
                int(docs[int(i)])
                for i in np.nonzero(res_np.overflow[: len(docs)])[0]
            )

        oracle_docs: Dict[int, Doc] = {}

        def oracle_doc_for(d: int) -> Doc:
            if d not in oracle_docs:
                oracle_docs[d] = _oracle_doc(workloads[d])
            return oracle_docs[d]

        cursor_positions: Optional[List[List[int]]] = None
        if cursors is not None:
            from ..ops.resolve import (
                oracle_cursor_positions,
                pack_cursor_rows,
                resolve_cursors_jit,
            )

            cursor_positions = [[] for _ in range(d_total)]
            for docs, enc, state_g, res_dev, _ in resolved_groups:
                local_map = {
                    i: list(cursors[int(d)])
                    for i, d in enumerate(docs)
                    if int(d) not in fallback
                }
                if not any(local_map.values()):
                    continue
                cursor_elem = pack_cursor_rows(
                    local_map, int(state_g.elem_id.shape[0]),
                    lambda i: enc.actor_tables[i],
                )
                positions = np.asarray(
                    resolve_cursors_jit(state_g, res_dev.visible, cursor_elem)
                )
                for i, d in enumerate(docs):
                    if int(d) not in fallback:
                        cursor_positions[int(d)] = [
                            int(p) for p in positions[i, : len(cursors[int(d)])]
                        ]
            for d in sorted(fallback):
                cursor_positions[d] = oracle_cursor_positions(
                    oracle_doc_for(d), cursors[d]
                )

        with self.tracer.span("batch.decode") as sp:
            spans: List[Optional[List[FormatSpan]]] = [None] * d_total
            roots: List[Optional[dict]] = [None] * d_total
            device_ops = 0
            fallback_ops = 0
            for docs, enc, state_g, _, res_np in resolved_groups:
                mask = np.zeros(res_np.visible.shape[0], bool)
                mask[: len(docs)] = [int(d) not in fallback for d in docs]
                block_spans = decode_block_spans(
                    res_np,
                    lambda i: enc.attr_tables[i],
                    lambda i: enc.attr_tables[i],
                    doc_mask=mask,
                )
                regs = SimpleNamespace(
                    r_obj=np.asarray(state_g.r_obj),
                    r_key=np.asarray(state_g.r_key),
                    r_op=np.asarray(state_g.r_op),
                    r_kind=np.asarray(state_g.r_kind),
                    r_val=np.asarray(state_g.r_val),
                    num_regs=np.asarray(state_g.num_regs),
                )
                for i, d in enumerate(docs):
                    d = int(d)
                    if d in fallback:
                        doc = oracle_doc_for(d)
                        spans[d] = doc.get_text_with_formatting(["text"])
                        roots[d] = doc.root
                        fallback_ops += int(enc.num_ops[i])
                    else:
                        spans[d] = block_spans[i]
                        roots[d] = decode_doc_root(
                            regs, res_np, i, enc.map_tables[i]
                        )
                        device_ops += int(enc.num_ops[i])
        stats.decode_seconds = sp.duration

        stats.device_ops = device_ops
        stats.fallback_ops = fallback_ops
        stats.fallback_docs = len(fallback)
        stats.device_docs = d_total - len(fallback)
        stats.padding_efficiency = (
            real_ops / stream_capacity if stream_capacity else 0.0
        )
        pool = store.pool_stats()
        stats.extras["layout_paged"] = 1.0
        stats.extras["page_pool_utilization"] = pool["pool_utilization"]
        stats.extras["page_internal_frag_ratio"] = pool["internal_frag_ratio"]
        if GLOBAL_DEVPROF.enabled:
            GLOBAL_DEVPROF.observe_page_pool(pool)
            GLOBAL_DEVPROF.sample_memory()
        GLOBAL_COUNTERS.add("merge.calls")
        GLOBAL_COUNTERS.add("merge.paged_calls")
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

    def _merge_ragged(
        self,
        workloads: Sequence[Workload],
        cursors: Optional[Sequence[Sequence[dict]]],
    ) -> MergeReport:
        """merge() under ``layout="ragged"``: the whole batch is ONE group.
        Streams pad once to the batch's own true maxima, the page pool is
        pre-sized to the batch's true page demand, and a single
        ``ops/ragged.apply_batch_ragged`` dispatch walks every doc's true
        op count against its true pages — no power-of-two buckets anywhere,
        so the whole merge compiles exactly one apply executable regardless
        of the doc-size mix.  The padded path stays the byte-equality
        oracle, exactly as for "paged"."""
        import jax.numpy as jnp

        from ..ops.encode import _EMPTY_STREAMS, encode_doc_streams, pad_doc_streams
        from ..ops.ragged import apply_batch_ragged_jit, plan_arrays
        from ..store.paged import PagedDocStore, group_stream_arrays
        from ..store.ragged import ragged_plan

        stats = MergeStats(docs=len(workloads))
        d_total = len(workloads)
        with self.tracer.span("batch.encode") as sp:
            per_doc, fb_encode, actor_tables, attr_tables, map_tables = (
                encode_doc_streams(workloads, self.tracer)
            )
            with self.tracer.span("batch.encode.pad"):
                fb_set = set(fb_encode)
                # per-doc capacity fallback thresholds: identical to the paged
                # path so the same docs fall back under every layout
                for d in range(d_total):
                    s = per_doc[d]
                    over = len(s.marks) > self.mark_capacity
                    if self.op_capacity is not None:
                        over = over or len(s.ins) > self.op_capacity \
                            or len(s.dels) > self.op_capacity
                    if over:
                        fb_set.add(d)
                enc = pad_doc_streams(
                    [_EMPTY_STREAMS if d in fb_set else per_doc[d]
                     for d in range(d_total)],
                    sorted(fb_set),
                    actor_tables,
                    attr_tables,
                    map_tables=map_tables,
                )
        stats.encode_seconds = sp.duration

        try:
            with self.tracer.span("batch.apply") as sp:
                with self.tracer.span("batch.apply.plan"):
                    ins_counts = (np.asarray(enc.ins_op) != 0).sum(axis=1)
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
                    rows = np.arange(d_total, dtype=np.int64)
                    store.ensure_rows(rows, ins_counts)
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
                store.pool_elem, store.pool_char, store.aux = (
                    apply_batch_ragged_jit(
                        store.pool_elem, store.pool_char, store.aux,
                        *planes, streams, ins_dev,
                    )
                )
                real_ops = int(enc.num_ops.sum())
                widths = (
                    enc.ins_op.shape[1], enc.del_target.shape[1],
                    next(iter(enc.marks.values())).shape[1],
                    next(iter(enc.map_ops.values())).shape[1],
                )
                if GLOBAL_DEVPROF.enabled:
                    # ragged pays real ops only: capacity IS the real work
                    GLOBAL_DEVPROF.observe_round(
                        occupancy_key(d_total, *widths),
                        real_ops, max(real_ops, 1),
                        origin="batch.merge.ragged",
                    )
                    GLOBAL_DEVPROF.observe_ragged(
                        docs_walked=plan.docs_walked,
                        pages_walked=plan.pages_walked,
                        real_ops=real_ops,
                    )
                # host sync: time apply honestly (mirror of _merge)
                np.asarray(store.aux_field("num_slots"))
            stats.apply_seconds = sp.duration

            with self.tracer.span("batch.resolve") as sp:
                # one materialize at the batch's true max page count — the
                # only place the ragged merge builds a dense block, and it
                # is sized by the data, not a bucket
                g_max = max(1, int(np.max(np.asarray(plan.page_count))))
                state = store.materialize_rows(rows, g_max)
                resolved_dev = self._resolve(state, self.comment_capacity)
                resolved = type(resolved_dev)(
                    *(np.asarray(x) for x in resolved_dev)
                )
            stats.resolve_seconds = sp.duration
        except Exception as exc:  # graftlint: boundary(guarded merge: ANY device-path failure degrades to the scalar oracle; re-raised when unguarded)
            if not self.guard:
                raise
            return self._degraded_merge(workloads, cursors, stats, exc)

        overflow = np.asarray(resolved.overflow)
        fallback = fb_set | set(enc.fallback_docs) | {
            int(d) for d in np.nonzero(overflow)[0] if d < d_total
        }

        oracle_docs: Dict[int, Doc] = {}

        def oracle_doc_for(d: int) -> Doc:
            if d not in oracle_docs:
                oracle_docs[d] = _oracle_doc(workloads[d])
            return oracle_docs[d]

        # row i IS doc i (one group, no bucket permutation), so the padded
        # path's batch cursor resolver applies verbatim
        cursor_positions: Optional[List[List[int]]] = None
        if cursors is not None:
            cursor_positions = self._resolve_cursor_batch(
                state, resolved_dev.visible, enc, cursors, fallback,
                oracle_doc_for,
            )

        with self.tracer.span("batch.decode") as sp:
            from types import SimpleNamespace

            from ..ops.decode import decode_doc_root

            device_mask = np.zeros(resolved.visible.shape[0], bool)
            for d in range(d_total):
                device_mask[d] = d not in fallback
            block_spans = decode_block_spans(
                resolved,
                lambda d: enc.attr_tables[d],
                lambda d: enc.attr_tables[d],
                doc_mask=device_mask,
            )
            regs = SimpleNamespace(
                r_obj=np.asarray(state.r_obj), r_key=np.asarray(state.r_key),
                r_op=np.asarray(state.r_op), r_kind=np.asarray(state.r_kind),
                r_val=np.asarray(state.r_val),
                num_regs=np.asarray(state.num_regs),
            )
            spans: List[List[FormatSpan]] = []
            roots: List[dict] = []
            device_ops = 0
            fallback_ops = 0
            for d, workload in enumerate(workloads):
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

        stats.device_ops = device_ops
        stats.fallback_ops = fallback_ops
        stats.fallback_docs = len(fallback)
        stats.device_docs = d_total - len(fallback)
        # no pow-2 row bucket, no padded stream slots dispatched: the apply
        # walks true counts, so the occupancy ratio is 1.0 by construction
        stats.padding_efficiency = 1.0 if real_ops else 0.0
        pool = store.pool_stats()
        stats.extras["layout_ragged"] = 1.0
        stats.extras["page_pool_utilization"] = pool["pool_utilization"]
        stats.extras["page_internal_frag_ratio"] = pool["internal_frag_ratio"]
        if GLOBAL_DEVPROF.enabled:
            GLOBAL_DEVPROF.observe_page_pool(pool)
            GLOBAL_DEVPROF.sample_memory()
        GLOBAL_COUNTERS.add("merge.calls")
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
