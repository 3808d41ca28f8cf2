"""PagedDocStore: the device-resident page pool + per-doc page tables.

Layout (module doc of :mod:`peritext_tpu.store`): the ELEMENT planes —
``elem_id`` / ``char``, the tensors whose padded ``(D, S)`` form carries
essentially all of the padded layout's waste — live as fixed-size pages in
a global ``(N_pages, P)`` pool, addressed per doc through a page table
(page ``k`` of a doc backs slots ``[k*P, (k+1)*P)``).  The small per-doc
aux tables (tombstones, mark rows, LWW registers, scalars) stay dense
``(D, ·)`` device rows: a 500K-op essay needs ~8K element pages but the
same 128-row tombstone table as a tweet, so paging them would buy nothing
and cost a second indirection.

Invariants the rest of the subsystem leans on:

* **Page 0 is the null page and every free page is all-zero.**  Gathers
  through padding page-table entries read zeros; a page handed out by the
  allocator reads as empty slots (elem_id 0) exactly like a fresh padded
  row.  Frees and compaction re-zero, the apply program re-zeroes page 0
  after its scatter.
* **Allocation is deterministic** (:class:`~.alloc.PageAllocator`): page
  tables are a pure function of the admission sequence.
* **Group widths are power-of-two page counts** capped at the doc slot
  capacity, so the apply/materialize programs compile once per
  (rows-bucket, pages-bucket, stream widths) triple — the paged analog of
  the padded path's width buckets, pinned by the recompile-sentinel test.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax.numpy as jnp

from ..ops.kernel import (
    PAGED_AUX_FIELDS,
    apply_batch_paged_jit,
    gather_paged_state_jit,
)
from ..ops.packed import PackedDocs, empty_docs
from ..utils.shapes import next_pow2
from .alloc import PageAllocator, PoolExhausted

#: Default op-page width: one TPU lane row (128 int32).  The ragged Pallas
#: kernel DMAs whole pages out of the HBM pool, whose rows are tiled 128
#: lanes wide, so a page narrower than a lane row cannot be sliced out on
#: the chip (ops/ragged_pallas.py); any width divides the work on the CPU.
DEFAULT_PAGE_SIZE = 128


def _pow2(n: int) -> int:
    """Smallest power of two >= n (floor 1 — page counts, not stream
    widths).  Delegates to the one canonical spelling
    (:func:`peritext_tpu.utils.shapes.next_pow2`); kept under its
    historical name because the session/batch layers import it from here."""
    return next_pow2(n, floor=1)


def plan_page_groups(
    rows: Sequence[int], pages_of_row, max_doc_pages: int
) -> List[Tuple[int, np.ndarray]]:
    """Bucket ``rows`` by power-of-two page count (capped at
    ``max_doc_pages``): returns ``[(bucket_pages, rows_array), ...]`` in
    ascending bucket order, rows sorted within each bucket — the dispatch
    plan every paged apply/materialize shares, deterministic by
    construction."""
    buckets: Dict[int, List[int]] = {}
    for row in rows:
        g = min(_pow2(max(1, int(pages_of_row(row)))), max_doc_pages)
        buckets.setdefault(g, []).append(int(row))
    return [
        (g, np.asarray(sorted(buckets[g]), np.int64))
        for g in sorted(buckets)
    ]


def group_stream_arrays(enc, rows, b: int):
    """One paged group's device stream tensors (the apply_batch 8-tuple):
    rows sliced out of any EncodedBatch-shaped staging object (the batch
    EncodedBatch and streaming's _RoundBuffers share the field names) and
    zero-padded to the power-of-two row bucket ``b`` — padding rows are
    all-zero no-ops.  ``rows=None`` takes every row (a group-encoded
    batch).  The ONE shared helper: the batch and streaming paged paths
    must never drift on the stream tuple's field order."""
    def take(a):
        a = np.asarray(a)
        src = a if rows is None else a[rows]
        out = np.zeros((b,) + a.shape[1:], a.dtype)
        out[: src.shape[0]] = src
        return jnp.asarray(out)

    return (
        take(enc.ins_ref), take(enc.ins_op), take(enc.ins_char),
        take(enc.del_target),
        {c: take(enc.marks[c]) for c in sorted(enc.marks)},
        take(enc.mark_count),
        {c: take(enc.map_ops[c]) for c in sorted(enc.map_ops)},
        take(enc.map_count),
    )


class PagedDocStore:
    """Page pool + page tables + dense aux rows for ``num_docs`` doc rows."""

    def __init__(
        self,
        num_docs: int,
        slot_capacity: int,
        mark_capacity: int,
        tomb_capacity: Optional[int] = None,
        map_capacity: int = 32,
        page_size: int = DEFAULT_PAGE_SIZE,
        initial_pages: Optional[int] = None,
        max_pool_pages: Optional[int] = None,
    ) -> None:
        if slot_capacity % page_size:
            raise ValueError(
                f"slot_capacity {slot_capacity} must be a multiple of the "
                f"page size {page_size} (digest pad-term parity needs W <= S)"
            )
        self.num_docs = int(num_docs)
        self.page_size = int(page_size)
        self.slot_capacity = int(slot_capacity)
        self.max_doc_pages = slot_capacity // page_size
        # hard ceiling: every doc fully grown, plus the null page — beyond
        # it ensure_rows raises the typed PoolExhausted instead of growing
        self.max_pool_pages = int(
            max_pool_pages
            if max_pool_pages is not None
            else 1 + self.num_docs * self.max_doc_pages
        )
        start = initial_pages or min(
            self.max_pool_pages, _pow2(1 + max(self.num_docs, 8))
        )
        start = max(2, min(int(start), self.max_pool_pages))
        self.alloc = PageAllocator(start)
        self.pool_elem = jnp.zeros((start, page_size), jnp.int32)
        self.pool_char = jnp.zeros((start, page_size), jnp.int32)
        # aux rows share empty_docs' field construction (schema single
        # source): build at elem width 1 and keep everything but elem/char.
        # tomb default mirrors the padded layout's (empty_docs defaults an
        # omitted tomb table to the SLOT width — which here is the proto's
        # width-1 element axis, so the default must be made explicit or a
        # second delete would overflow every doc)
        proto = empty_docs(
            num_docs, 1, mark_capacity,
            tomb_capacity=(
                tomb_capacity if tomb_capacity is not None else slot_capacity
            ),
            map_capacity=map_capacity,
        )
        self.aux = tuple(getattr(proto, f) for f in PAGED_AUX_FIELDS)
        self._num_pages = np.zeros(num_docs, np.int32)
        #: host-side upper bound on per-row used slots (the session/batch
        #: layer's cumulative admitted inserts) — drives allocation AND the
        #: internal-fragmentation telemetry
        self._used_hint = np.zeros(num_docs, np.int64)
        #: pool growths so far (each one is a fresh device allocation and a
        #: new program shape — telemetry wants to see them)
        self.growths = 0
        #: bumped whenever any page table (or the pool size) changes —
        #: ragged callers key their plan caches on (alloc_epoch, pool size)
        #: so stale owner/pos_base planes can never reach a dispatch
        self.alloc_epoch = 0

    # -- sizing --------------------------------------------------------------

    @property
    def aux_capacities(self) -> Dict[str, int]:
        aux = dict(zip(PAGED_AUX_FIELDS, self.aux))
        return {
            "tomb_capacity": int(aux["tomb_id"].shape[1]),
            "mark_capacity": int(aux["m_action"].shape[1]),
            "map_capacity": int(aux["r_obj"].shape[1]),
        }

    def num_pages(self, row: int) -> int:
        return int(self._num_pages[row])

    def aux_field(self, name: str):
        """One dense aux plane by PackedDocs field name (e.g. "num_slots")."""
        return self.aux[PAGED_AUX_FIELDS.index(name)]

    def pages_needed(self, used_slots: int) -> int:
        used = min(int(used_slots), self.slot_capacity)
        return max(1, -(-used // self.page_size))

    def width_for_rows(self, rows: Sequence[int]) -> int:
        """Power-of-two page bucket covering every row's allocation (>= 1,
        capped at the doc slot capacity)."""
        top = int(self._num_pages[np.asarray(rows, np.int64)].max()) if len(rows) else 1
        return min(_pow2(max(1, top)), self.max_doc_pages)

    # -- allocation ----------------------------------------------------------

    def ensure_rows(self, rows: Sequence[int], used_slots: Sequence[int]) -> None:
        """Grow each row's page table to cover ``used_slots`` (its cumulative
        admitted inserts), growing the device pool (doubling, up to
        ``max_pool_pages``) when the free list runs dry.  Deterministic:
        rows walk in sorted order; raises :class:`PoolExhausted` past the
        ceiling."""
        order = np.argsort(np.asarray(rows, np.int64), kind="stable")
        rows_arr = np.asarray(rows, np.int64)[order]
        used_arr = np.asarray(used_slots, np.int64)[order]
        for row, used in zip(rows_arr, used_arr):
            row = int(row)
            need = self.pages_needed(int(used))
            delta = need - self.alloc.num_pages(row)
            if delta > 0 and delta > self.alloc.free_pages:
                self._grow_pool(self.alloc.pages_in_use + self.alloc.reserved + delta)
            self.alloc.ensure(row, need)
            if delta > 0:
                self.alloc_epoch += 1
            self._num_pages[row] = self.alloc.num_pages(row)
            self._used_hint[row] = max(self._used_hint[row], int(used))

    def _grow_pool(self, min_total: int) -> None:
        target = _pow2(max(min_total, 2 * self.alloc.total_pages))
        target = min(target, self.max_pool_pages)
        if target < min_total:
            raise PoolExhausted(
                min_total - self.alloc.total_pages,
                self.alloc.free_pages, self.alloc.total_pages,
            )
        added = self.alloc.grow(target)
        if added:
            pad = jnp.zeros((added, self.page_size), jnp.int32)
            self.pool_elem = jnp.concatenate([self.pool_elem, pad], axis=0)
            self.pool_char = jnp.concatenate([self.pool_char, pad], axis=0)
            self.growths += 1
            self.alloc_epoch += 1

    def page_rows(self, rows: Sequence[int], bucket_pages: int,
                  pad_rows_to: Optional[int] = None) -> np.ndarray:
        """(B, bucket_pages) int32 page-table slab for ``rows`` — padding
        entries (beyond a doc's allocation, and whole padding rows) point
        at the null page 0."""
        b = pad_rows_to if pad_rows_to is not None else len(rows)
        table = np.zeros((b, bucket_pages), np.int32)
        for i, row in enumerate(rows):
            pages = self.alloc.pages_of(int(row))
            table[i, : len(pages)] = pages
        return table

    # -- device plumbing -----------------------------------------------------

    def materialize_rows(
        self, rows: Sequence[int], bucket_pages: Optional[int] = None,
        pad_rows_to: Optional[int] = None,
    ) -> PackedDocs:
        """Dense PackedDocs view of ``rows`` gathered from the pool at
        ``bucket_pages * page_size`` slots (default: the rows' own bucket).
        Padding rows (up to ``pad_rows_to``) gather null pages and clamp
        into the aux tables; callers mask them."""
        g = bucket_pages or self.width_for_rows(rows)
        b = pad_rows_to if pad_rows_to is not None else len(rows)
        row_idx = np.full(b, self.num_docs, np.int64)
        row_idx[: len(rows)] = np.asarray(rows, np.int64)
        table = self.page_rows(rows, g, pad_rows_to=b)
        return gather_paged_state_jit(
            self.pool_elem, self.pool_char, self.aux,
            jnp.asarray(row_idx), jnp.asarray(table),
        )

    def apply_rows(
        self, rows: Sequence[int], bucket_pages: int, encoded_arrays,
        pad_rows_to: Optional[int] = None,
        insert_impl: str = "auto",
        insert_loop_slots: Optional[int] = None,
    ) -> None:
        """Dispatch one gather-apply-scatter group (ops/kernel.
        apply_batch_paged) and adopt the updated pool/aux arrays.  The
        stream tensors in ``encoded_arrays`` carry the (possibly padded)
        group row axis; padding rows must be all-zero no-ops."""
        b = pad_rows_to if pad_rows_to is not None else len(rows)
        row_idx = np.full(b, self.num_docs, np.int64)
        row_idx[: len(rows)] = np.asarray(rows, np.int64)
        table = self.page_rows(rows, bucket_pages, pad_rows_to=b)
        self.pool_elem, self.pool_char, self.aux = apply_batch_paged_jit(
            self.pool_elem, self.pool_char, self.aux,
            jnp.asarray(row_idx), jnp.asarray(table), encoded_arrays,
            insert_impl=insert_impl, insert_loop_slots=insert_loop_slots,
        )

    def group_plan(self, rows: Sequence[int], bucket_pages: int,
                   pad_rows_to: Optional[int] = None):
        """One group's host-side plan pair for the fused group chain
        (kernel.apply_batch_paged_groups): the padded row-index vector and
        a SNAPSHOT of the group's page-table slab — taken at plan time so
        a later round's ``ensure_rows`` growth can never leak into an
        already-planned group."""
        b = pad_rows_to if pad_rows_to is not None else len(rows)
        row_idx = np.full(b, self.num_docs, np.int64)
        row_idx[: len(rows)] = np.asarray(rows, np.int64)
        return row_idx, self.page_rows(rows, bucket_pages, pad_rows_to=b)

    # -- lifecycle: evacuate / compact / permute -----------------------------

    def evacuate_row(self, row: int) -> int:
        """Release one row's pages back to the (zeroed) free list and clear
        its aux row — the doc's state has moved elsewhere (host move, or
        demotion with history replay).  Returns the page count released."""
        pages = self.alloc.evacuate(int(row))
        if pages:
            # scalar-broadcast scatter: a len(pages)-shaped zeros tensor
            # would mint one XLA shape per distinct page count (PTL004)
            idx = jnp.asarray(np.asarray(pages, np.int32))
            self.pool_elem = self.pool_elem.at[idx].set(0)
            self.pool_char = self.pool_char.at[idx].set(0)
        r = int(row)
        self.aux = tuple(
            a.at[r].set(jnp.zeros((), a.dtype)) for a in self.aux
        )
        if pages:
            self.alloc_epoch += 1
        self._num_pages[r] = 0
        self._used_hint[r] = 0
        return len(pages)

    def compact(self) -> int:
        """Pack every held page into the lowest pool ids (one device gather;
        free tail reads from the null page, so it comes back zeroed).
        Returns the number of pages that moved.  Page tables stay
        deterministic: the plan walks docs in sorted row order."""
        mapping = self.alloc.compact_plan()
        moved = sum(1 for old, new in sorted(mapping.items()) if old != new)
        if moved:
            src = np.zeros(self.alloc.total_pages, np.int32)  # default: null
            for old, new in sorted(mapping.items()):
                src[new] = old
            idx = jnp.asarray(src)
            self.pool_elem = jnp.take(self.pool_elem, idx, axis=0)
            self.pool_char = jnp.take(self.pool_char, idx, axis=0)
        self.alloc.apply_compact(mapping)
        if moved:
            self.alloc_epoch += 1
        self._num_pages[:] = 0
        for doc in self.alloc.docs():
            self._num_pages[doc] = self.alloc.num_pages(doc)
        return moved

    def permute_rows(self, src: np.ndarray) -> None:
        """Re-home doc rows: new row ``r`` takes old row ``src[r]`` (a full
        permutation — reshard()'s contract).  Pages do NOT move; page
        TABLES do, plus the dense aux rows (one device gather)."""
        src = np.asarray(src, np.int64)
        old_pages = {r: self.alloc.pages_of(r) for r in self.alloc.docs()}
        self.alloc.reseat({
            int(r): old_pages[int(src[r])]
            for r in range(len(src))
            if int(src[r]) in old_pages
        })
        idx = jnp.asarray(src)
        self.aux = tuple(jnp.take(a, idx, axis=0) for a in self.aux)
        self._num_pages = self._num_pages[src]
        self._used_hint = self._used_hint[src]
        self.alloc_epoch += 1

    # -- telemetry -----------------------------------------------------------

    def page_loads(self) -> np.ndarray:
        """(num_docs,) pages held per row — the paged load dimension
        reshard()/FleetRouter balance on."""
        return self._num_pages.copy()

    def pool_stats(self) -> Dict:
        """The ``peritext_page_*`` snapshot: pool occupancy, internal
        fragmentation (allocated-but-unused slots) overall and per
        doc-size decile — the paged layout's waste is fragmentation inside
        the last page of each doc, and this table is how a mis-chosen page
        size shows up."""
        total = self.alloc.total_pages - self.alloc.reserved
        in_use = self.alloc.pages_in_use
        live = np.nonzero(self._num_pages > 0)[0]
        alloc_slots = self._num_pages[live].astype(np.int64) * self.page_size
        used_slots = np.minimum(self._used_hint[live], alloc_slots)
        frag = alloc_slots - used_slots
        deciles = {}
        if len(live):
            order = np.argsort(alloc_slots, kind="stable")
            chunks = np.array_split(order, 10)
            for i, chunk in enumerate(chunks):
                if not len(chunk):
                    deciles[f"d{i}"] = 0.0
                    continue
                a = int(alloc_slots[chunk].sum())
                f = int(frag[chunk].sum())
                deciles[f"d{i}"] = round(f / a, 4) if a else 0.0
        return {
            "page_size": self.page_size,
            "pool_pages": total,
            "pages_in_use": in_use,
            "pages_free": total - in_use,
            "pool_utilization": round(in_use / total, 4) if total else 0.0,
            "growths": self.growths,
            "docs_resident": int(len(live)),
            "allocated_slots": int(alloc_slots.sum()),
            "used_slots": int(used_slots.sum()),
            "internal_frag_slots": int(frag.sum()),
            "internal_frag_ratio": (
                round(int(frag.sum()) / int(alloc_slots.sum()), 4)
                if len(live) and int(alloc_slots.sum()) else 0.0
            ),
            "frag_by_decile": deciles,
        }
