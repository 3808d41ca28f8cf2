"""PagedStreamingMerge: StreamingMerge over the page pool.

Selected via ``StreamingMerge(layout="paged")``.  The host half of every
round (causal admission, frame scheduling, staging buffers) is shared with
the padded engine verbatim; what changes is WHERE device state lives and
WHAT each round dispatches:

* **Commit** — instead of applying all D docs at the session slot capacity,
  the round's touched rows group into power-of-two page-count buckets and
  each group dispatches one gather→apply→scatter program
  (ops/kernel.apply_batch_paged) at its own width, so per-round device work
  is ``sum(touched docs x their bucket)`` — one 500K-op essay among 100K
  tweets costs its own pages, not everyone's.
* **Reads/digests** — blocks materialize on demand from the pool at the
  block's page-bucketed width (cached per round like the padded block
  cache).  The per-doc full-state hash includes a pad-slot term
  (mesh.per_doc_text_digest hashes ``slot_capacity - n_visible`` pad
  slots), so every paged digest program adds the missing
  ``(S - W) * avalanche(PAD_SEED)`` per live doc — digests are BIT-EQUAL
  to a padded session's, which is what lets mixed-layout fleets compare
  frontiers and the byte-equality oracle pin the layouts against each
  other.
* **reshard()** — balances PAGES (the resource the pool actually spends):
  page tables and aux rows permute, pages never move.  The return gains a
  ``page_load`` dimension for the FleetRouter.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..obs import (
    GLOBAL_COUNTERS,
    GLOBAL_DEVPROF,
    GLOBAL_HISTOGRAMS,
    MergeStats,
    SIZE_BUCKETS,
    note_jit_dispatch,
    occupancy_key,
)
from ..ops.packed import PackedDocs
from ..ops.resolve import resolve
from ..parallel import mesh as _mesh
from ..parallel.streaming import (
    StreamingMerge,
    _BlockResolution,
    _doc_char_slots,
    _per_doc_full_digest,
    _replay_doc,
    _width_bucket,
)
from .paged import (
    DEFAULT_PAGE_SIZE,
    PagedDocStore,
    _pow2,
    group_stream_arrays,
    plan_page_groups,
)


def _pad_unit() -> int:
    """Host value of one pad slot's digest contribution —
    avalanche(PAD_SEED), the same constant mesh.per_doc_text_digest folds
    per non-visible slot (and doc_digest_host multiplies by the pad
    count)."""
    x = (_mesh._PAD_SEED * _mesh._KF) & 0xFFFFFFFF
    return x ^ (x >> 15)


_PAD_UNIT = _pad_unit()


@partial(jax.jit, static_argnums=1)
def _resolve_block_digest_paged_jit(
    state: PackedDocs, comment_capacity: int, row_mask, pad_slots,
    sess_attr, sess_key, comment_hash, row_map, obj_attr, obj_key,
):
    """The paged twin of streaming._resolve_block_digest_jit: resolution at
    the block's materialized width W plus the per-doc full-state hash, with
    the ``pad_slots = S - W`` pad-term correction folded in so the hash
    equals what the padded layout computes at width S."""
    resolved = resolve(state, comment_capacity, with_comments=True)
    per_doc = _per_doc_full_digest(
        state, resolved, row_mask,
        sess_attr, sess_key, comment_hash, row_map, obj_attr, obj_key,
    )
    mask = row_mask & ~resolved.overflow
    per_doc = jnp.where(
        mask, per_doc + pad_slots * jnp.uint32(_PAD_UNIT), jnp.uint32(0)
    )
    return resolved, per_doc


@partial(jax.jit, static_argnums=1)
def _rows_digest_paged_jit(
    sub: PackedDocs, comment_capacity: int, row_mask, pad_slots,
    sess_attr, sess_key, comment_hash, row_map, obj_attr, obj_key,
):
    """Paged twin of streaming._rows_digest_jit (gathered dirty-row
    sub-batch), pad-term corrected."""
    resolved = resolve(sub, comment_capacity, with_comments=True)
    per_doc = _per_doc_full_digest(
        sub, resolved, row_mask,
        sess_attr, sess_key, comment_hash, row_map, obj_attr, obj_key,
    )
    mask = row_mask & ~resolved.overflow
    per_doc = jnp.where(
        mask, per_doc + pad_slots * jnp.uint32(_PAD_UNIT), jnp.uint32(0)
    )
    return per_doc, resolved.overflow


@partial(jax.jit, static_argnums=1)
def _resolve_digest_paged_jit(
    state: PackedDocs, comment_capacity: int, row_mask, pad_slots
):
    """Paged twin of streaming._resolve_digest_jit (TEXT-ONLY digest),
    pad-term corrected per contributing doc."""
    resolved = resolve(state, comment_capacity, with_comments=False)
    mask = row_mask & ~resolved.overflow
    per_doc = _mesh.per_doc_text_digest(resolved.char, resolved.visible)
    per_doc = jnp.where(
        mask, per_doc + pad_slots * jnp.uint32(_PAD_UNIT), jnp.uint32(0)
    )
    return jnp.sum(per_doc, dtype=jnp.uint32), resolved.overflow


class PagedStreamingMerge(StreamingMerge):
    """StreamingMerge whose resident element planes live in a page pool
    (module doc).  Under ``mesh=`` the pool shards per shard
    (store/sharded.ShardedPagedDocStore) and the fused commit runs the
    whole drain batch's group chain as ONE ``shard_map`` program with
    per-shard plan planes as data (round 19); ``static_rounds`` (the
    serving tier's one-shape discipline) stays on the padded layout."""

    _layout = "paged"

    def __init__(self, num_docs, actors, *args,
                 layout: str = "paged",
                 page_size: int = DEFAULT_PAGE_SIZE,
                 pool_pages: Optional[int] = None,
                 max_pool_pages: Optional[int] = None,
                 **kwargs) -> None:
        if layout != "paged":
            raise ValueError(f"PagedStreamingMerge is layout='paged', got {layout!r}")
        if kwargs.get("static_rounds"):
            raise ValueError(
                "layout='paged' is incompatible with static_rounds: the "
                "serving shape discipline is exactly the padded one-shape "
                "apply; use the padded layout for static-round serving"
            )
        self.page_size = int(page_size)
        super().__init__(num_docs, actors, *args, layout="paged", **kwargs)
        if self._slot_capacity % self.page_size:
            raise ValueError(
                f"slot_capacity {self._slot_capacity} must be a multiple of "
                f"page_size {self.page_size} under layout='paged'"
            )
        if self.mesh is not None:
            from .sharded import ShardedPagedDocStore

            self._store = ShardedPagedDocStore(
                self._padded_docs, self.mesh,
                slot_capacity=self._slot_capacity,
                mark_capacity=self._mark_capacity,
                tomb_capacity=self._tomb_capacity,
                map_capacity=self._map_capacity,
                page_size=self.page_size,
                initial_pages=pool_pages,
                max_pool_pages=max_pool_pages,
            )
        else:
            self._store = PagedDocStore(
                self._padded_docs,
                slot_capacity=self._slot_capacity,
                mark_capacity=self._mark_capacity,
                tomb_capacity=self._tomb_capacity,
                map_capacity=self._map_capacity,
                page_size=self.page_size,
                initial_pages=pool_pages,
                max_pool_pages=max_pool_pages,
            )
        #: per-(round, epoch) materialized-block cache (<= 2 blocks, the
        #: paged analog of the padded path's _apply_blocks reuse)
        self._mat_cache: tuple = ((-1, -1), {})
        #: per-round-buffer dispatched stream capacity (feeds the stats
        #: override: padded capacity is what the GROUPS paid, not D x K)
        self._commit_caps: Dict[int, int] = {}

    # -- store access --------------------------------------------------------

    @property
    def store(self) -> PagedDocStore:
        return self._store

    @property
    def config(self) -> Dict[str, int]:
        cfg = dict(StreamingMerge.config.fget(self))
        cfg["page_size"] = self.page_size
        return cfg

    def sync_device(self) -> None:
        np.asarray(self._store.aux_field("num_slots"))

    def health(self) -> Dict:
        h = super().health()
        h["layout"] = "paged"
        h["page_pool"] = self._store.pool_stats()
        return h

    # -- the paged device half of a round ------------------------------------

    def _commit_rounds(self, batch) -> None:
        """Dispatch scheduled rounds through the page pool as ONE donated
        fused program: per round, the touched rows (and only them) group by
        page bucket; every (round, group) gather-apply-scatter chains
        inside the program in causal order, with the pool operands donated
        so XLA updates pages in place instead of copying the whole pool per
        group (the fused round pipeline's paged form).  Page growth
        (``ensure_rows``) stays a per-round HOST decision made in prep, and
        each group's page-table slab snapshots at plan time, so grouping
        and gather widths are byte-identical to the per-round discipline."""
        if not self.fused_pipeline:
            self._commit_rounds_serial(batch)
            return
        statics = self._prep_fused_batch(batch)
        inputs = self._stage_fused_batch(batch, statics)
        self._dispatch_fused_batch(batch, statics, inputs)

    def _commit_rounds_serial(self, batch) -> None:
        """Pre-fusion discipline (``fused_pipeline=False``): one
        gather-apply-scatter dispatch per (round, group), each paying its
        own whole-pool copy — the bench fused row's comparison arm and the
        equivalence tests' oracle side."""
        for enc, widths in batch:
            self._cum_ins += enc.ins_count
            rows = np.nonzero(enc.num_ops)[0]
            if len(rows):
                self._store.ensure_rows(rows, self._cum_ins[rows])
                groups = plan_page_groups(
                    rows, self._store.num_pages, self._store.max_doc_pages
                )
                cap_total = 0
                for g, g_rows in groups:
                    b = _pow2(len(g_rows))
                    self._store.apply_rows(
                        g_rows, g, group_stream_arrays(enc, g_rows, b),
                        pad_rows_to=b,
                    )
                    cap = b * sum(widths)
                    cap_total += cap
                    if GLOBAL_DEVPROF.enabled:
                        GLOBAL_DEVPROF.observe_round(
                            occupancy_key(b, *widths),
                            int(enc.num_ops[g_rows].sum()), cap,
                            origin="streaming.paged",
                        )
                self._commit_caps[id(enc)] = cap_total
                self._digest_row_valid[rows] = False
            self.rounds += 1
            GLOBAL_COUNTERS.add("streaming.rounds")
        if GLOBAL_DEVPROF.enabled:
            GLOBAL_DEVPROF.observe_page_pool(self._store.pool_stats())

    def _prep_fused_batch(self, batch):
        """Main-thread prep: advance cum-inserts, grow/allocate pages per
        round, plan that round's page groups and SNAPSHOT their page-table
        slabs (``PagedDocStore.group_plan``) — everything that reads or
        mutates allocator state happens here, in round order."""
        if self.mesh is not None:
            return self._prep_mesh_fused_batch(batch)
        plans = []
        for enc, widths in batch:
            self._cum_ins += enc.ins_count
            rows = np.nonzero(enc.num_ops)[0]
            if not len(rows):
                plans.append((widths, []))
                continue
            self._store.ensure_rows(rows, self._cum_ins[rows])
            groups = plan_page_groups(
                rows, self._store.num_pages, self._store.max_doc_pages
            )
            plan = []
            for g, g_rows in groups:
                b = _pow2(len(g_rows))
                row_idx, table = self._store.group_plan(g_rows, g,
                                                        pad_rows_to=b)
                plan.append((g_rows, b, row_idx, table))
            plans.append((widths, plan))
        return ("paged", tuple(plans))

    def _stage_fused_batch(self, batch, statics):
        """Worker-safe staging: slice each group's stream tensors out of
        its round's staging buffers and upload the whole (round, group)
        input sequence with one ``jax.device_put``."""
        if statics[0] == "mesh_paged":
            return self._stage_mesh_fused_batch(batch, statics)
        _, plans = statics
        group_inputs = []
        for (enc, _), (widths, plan) in zip(batch, plans):
            for g_rows, b, row_idx, table in plan:
                group_inputs.append(
                    (row_idx, table, group_stream_arrays(enc, g_rows, b))
                )
        return jax.device_put(tuple(group_inputs))

    def _dispatch_fused_batch(self, batch, statics, inputs,
                              chain_digest: bool = False) -> bool:
        """Dispatch the donated group chain + per-round bookkeeping and
        the fused-site occupancy telemetry.  ``chain_digest`` is accepted
        for drain-loop compatibility but never chains here (returns
        False): a paged digest twin of the group-chain program is an open
        rung — the drain keeps the separate prefetch dispatch instead."""
        if statics[0] == "mesh_paged":
            return self._dispatch_mesh_fused_batch(batch, statics, inputs)
        from ..ops.kernel import apply_batch_paged_groups_jit

        from ..ops.kernel import (
            apply_batch_paged_jit,
            resolve_state_donation,
        )

        _, plans = statics
        store = self._store
        if len(inputs) == 1 and not resolve_state_donation(store.pool_elem):
            # single-group commit on a non-donating platform: the legacy
            # per-group program IS the dispatch (shared compile with the
            # pre-fusion path — group chaining buys nothing at length 1)
            row_idx, table, enc_arrays = inputs[0]
            store.pool_elem, store.pool_char, store.aux = (
                apply_batch_paged_jit(
                    store.pool_elem, store.pool_char, store.aux,
                    row_idx, table, enc_arrays,
                )
            )
        elif inputs:
            store.pool_elem, store.pool_char, store.aux = (
                apply_batch_paged_groups_jit(
                    store.pool_elem, store.pool_char, store.aux, inputs,
                    loop_slots_seq=(None,) * len(inputs),
                )
            )
        for (enc, _), (widths, plan) in zip(batch, plans):
            cap_total = 0
            rows = np.nonzero(enc.num_ops)[0]
            for g_rows, b, _, _ in plan:
                cap = b * sum(widths)
                cap_total += cap
                if GLOBAL_DEVPROF.enabled:
                    GLOBAL_DEVPROF.observe_round(
                        occupancy_key(b, *widths),
                        int(enc.num_ops[g_rows].sum()), cap,
                        origin="streaming.paged.fused",
                    )
            self._commit_caps[id(enc)] = cap_total
            if len(rows):
                self._digest_row_valid[rows] = False
            self.rounds += 1
            GLOBAL_COUNTERS.add("streaming.rounds")
        if GLOBAL_DEVPROF.enabled:
            GLOBAL_DEVPROF.observe_page_pool(self._store.pool_stats())
        return False

    # -- mesh-sharded fused commit (round 19) --------------------------------

    def _prep_mesh_fused_batch(self, batch):
        """The meshless prep's round walk, but groups are planned PER SHARD
        with LOCAL row ids (pad = rows_per_shard, the locally-OOB drop
        sentinel) and LOCAL page tables built straight off the per-shard
        allocators — never by translating global page ids, so pad entries
        are each shard's OWN null page.  The bucket ladder unifies across
        shards: one (round, bucket) group spans the whole mesh at the
        max-shard row bucket; shards short of rows ride as all-pad no-op
        lanes (zero streams + null tables are free by the same argument as
        padding rows)."""
        store = self._store
        n = store.n_shards
        rps = store.rows_per_shard
        plans = []
        for enc, widths in batch:
            self._cum_ins += enc.ins_count
            rows = np.nonzero(enc.num_ops)[0]
            if not len(rows):
                plans.append((widths, []))
                continue
            store.ensure_rows(rows, self._cum_ins[rows])
            buckets: Dict[int, Dict[int, list]] = {}
            for row in rows:
                row = int(row)
                g = min(_pow2(max(1, store.num_pages(row))),
                        store.max_doc_pages)
                buckets.setdefault(g, {}).setdefault(row // rps, []).append(row)
            plan = []
            for g in sorted(buckets):
                by_shard = buckets[g]
                b = _pow2(max(len(v) for v in by_shard.values()))
                shard_rows = [sorted(by_shard.get(s, ())) for s in range(n)]
                row_idx = np.full((n, b), rps, np.int64)
                table = np.zeros((n, b, g), np.int32)
                for s in range(n):
                    alloc = store.alloc.shards[s]
                    for i, r in enumerate(shard_rows[s]):
                        row_idx[s, i] = r - s * rps
                        pages = alloc.pages_of(r)
                        table[s, i, : len(pages)] = pages
                plan.append((shard_rows, g, b, row_idx, table))
            plans.append((widths, plan))
        return ("mesh_paged", tuple(plans))

    def _stage_mesh_fused_batch(self, batch, statics):
        """Every (round, group) input grows a leading ``(n_shards,)`` axis
        — shard ``s``'s local row ids, local page-table slab and stream
        slice — and the whole chain ships with ONE sharded device_put, so
        each shard receives exactly its own planes and the dispatch below
        needs no in-program resharding."""
        from ..parallel.mesh_fused import shard_leading

        _, plans = statics
        n = self._store.n_shards

        def stack(a, shard_rows, b):
            a = np.asarray(a)
            out = np.zeros((n, b) + a.shape[1:], a.dtype)
            for s in range(n):
                rows = shard_rows[s]
                if len(rows):
                    out[s, : len(rows)] = a[rows]
            return out

        group_inputs = []
        for (enc, _), (widths, plan) in zip(batch, plans):
            for shard_rows, g, b, row_idx, table in plan:
                streams = (
                    stack(enc.ins_ref, shard_rows, b),
                    stack(enc.ins_op, shard_rows, b),
                    stack(enc.ins_char, shard_rows, b),
                    stack(enc.del_target, shard_rows, b),
                    {c: stack(enc.marks[c], shard_rows, b)
                     for c in sorted(enc.marks)},
                    stack(enc.mark_count, shard_rows, b),
                    {c: stack(enc.map_ops[c], shard_rows, b)
                     for c in sorted(enc.map_ops)},
                    stack(enc.map_count, shard_rows, b),
                )
                group_inputs.append((row_idx, table, streams))
        return shard_leading(tuple(group_inputs), self.mesh)

    def _mesh_paged_fn(self):
        """The drain batch's whole (round, group) chain as ONE compiled
        ``shard_map`` program: each shard runs
        ops/kernel.apply_batch_paged_groups over its local pool block with
        its own plan planes (sliced off the staged leading shard axis).
        The jit retraces per chain structure exactly like the meshless
        bucket ladder — one executable per (group shapes, widths) chain,
        shared across the mesh and cached per mesh fingerprint."""
        from ..ops.kernel import (
            apply_batch_paged_groups,
            resolve_insert_impl,
            resolve_state_donation,
        )
        from ..parallel.mesh_fused import mesh_fn

        mesh = self.mesh
        impl = resolve_insert_impl(self._store.pool_elem)
        donate = resolve_state_donation(self._store.pool_elem)

        def build():
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            def body(pool_elem, pool_char, aux, group_inputs):
                local = jax.tree_util.tree_map(lambda x: x[0], group_inputs)
                return apply_batch_paged_groups(
                    pool_elem, pool_char, aux, local,
                    loop_slots_seq=(None,) * len(local),
                    insert_impl=impl,
                )

            # check_vma=False: pallas_call results carry no varying-axes
            # type; every operand and result is doc-sharded anyway
            wrapped = shard_map(
                body, mesh=mesh,
                in_specs=(P(_mesh.DOC_AXIS),) * 4,
                out_specs=(P(_mesh.DOC_AXIS),) * 3,
                check_vma=False,
            )
            return jax.jit(
                wrapped, donate_argnums=(0, 1, 2) if donate else ())

        return mesh_fn(mesh, ("paged_groups", impl, donate), build)

    def _dispatch_mesh_fused_batch(self, batch, statics, inputs) -> bool:
        """One program for the whole mesh drain batch + the same per-round
        bookkeeping as the meshless dispatch.  Returns False (the paged
        digest twin stays an open rung under the mesh too — the drain
        keeps the separate prefetch dispatch)."""
        _, plans = statics
        store = self._store
        if inputs:
            fn = self._mesh_paged_fn()
            if GLOBAL_DEVPROF.enabled:
                note_jit_dispatch(
                    "apply_batch_paged_groups.mesh", fn,
                    (store.pool_elem, store.pool_char, store.aux, inputs),
                )
            store.pool_elem, store.pool_char, store.aux = fn(
                store.pool_elem, store.pool_char, store.aux, inputs
            )
            GLOBAL_COUNTERS.add("streaming.fused_dispatches")
        for (enc, _), (widths, plan) in zip(batch, plans):
            cap_total = 0
            rows = np.nonzero(enc.num_ops)[0]
            for shard_rows, g, b, _, _ in plan:
                cap = b * store.n_shards * sum(widths)
                cap_total += cap
                if GLOBAL_DEVPROF.enabled:
                    g_rows = [r for sr in shard_rows for r in sr]
                    GLOBAL_DEVPROF.observe_round(
                        occupancy_key(b * store.n_shards, *widths),
                        int(enc.num_ops[g_rows].sum()), cap,
                        origin="streaming.paged.fused",
                    )
            self._commit_caps[id(enc)] = cap_total
            if len(rows):
                self._digest_row_valid[rows] = False
            self.rounds += 1
            GLOBAL_COUNTERS.add("streaming.rounds")
        if GLOBAL_DEVPROF.enabled:
            GLOBAL_DEVPROF.observe_page_pool(store.pool_stats())
            GLOBAL_DEVPROF.observe_mesh(self._mesh_stats())
        return False

    def _mesh_stats(self) -> Dict:
        """Real per-shard pool occupancy (the padded base reports the
        cum-insert proxy) plus the ICI page-move counter."""
        return dict(self._store.shard_stats())

    def _emit_round_stats(self, batch, scheduled: int,
                          schedule_s: float, apply_s: float,
                          origin: str = "streaming.paged") -> None:
        """Padded capacity under the paged layout is what the dispatched
        GROUPS paid (rows-bucket x widths per bucket), recorded at commit
        time — the base accounting's D x widths would charge the whole
        session for every trickle round."""
        touched: set = set()
        real = 0
        capacity = 0
        for enc, _ in batch:
            touched.update(int(r) for r in np.nonzero(enc.num_ops)[0])
            real += int(enc.num_ops.sum())
            capacity += self._commit_caps.pop(id(enc), 0)
        if GLOBAL_DEVPROF.enabled:
            GLOBAL_DEVPROF.sample_memory()
        stats = MergeStats(
            docs=len(touched),
            device_docs=len(touched),
            device_ops=real,
            encode_seconds=schedule_s,
            apply_seconds=apply_s,
            padding_efficiency=real / capacity if capacity else 0.0,
            extras={"rounds": len(batch), "scheduled_changes": scheduled,
                    "layout_paged": 1.0},
        )
        self.last_round_stats = stats
        self._pad_real_ops += real
        self._pad_capacity += capacity
        GLOBAL_HISTOGRAMS.observe("streaming.round_seconds", schedule_s + apply_s)
        GLOBAL_HISTOGRAMS.observe(
            "streaming.round_scheduled_changes", scheduled, buckets=SIZE_BUCKETS
        )

    # -- reads: block materialization ----------------------------------------

    def _state_block(self, block_index: int) -> PackedDocs:
        """Materialize one read block from the pool at the block's
        page-bucketed width (cached per (round, epoch), <= 2 resident)."""
        stamp = (self.rounds, self._placement_epoch)
        key_stamp, cache = self._mat_cache
        if key_stamp != stamp:
            cache = {}
            self._mat_cache = (stamp, cache)
        hit = cache.get(block_index)
        if hit is not None:
            return hit
        lo, hi = self._block_bounds(block_index)
        rows = np.arange(lo, hi)
        state = self._store.materialize_rows(
            rows, self._store.width_for_rows(rows)
        )
        if len(cache) >= 2:
            cache.pop(next(iter(cache)))
        cache[block_index] = state
        return state

    def _resolution(self, block_index: int) -> _BlockResolution:
        """Base _resolution with the paged fused program: resolution at the
        block's width plus the pad-corrected per-doc hash vector."""
        stamp, cache = self._resolved_cache
        if stamp != self.rounds:
            cache = {}
            self._resolved_cache = (self.rounds, cache)
        if block_index in cache:
            entry = cache.pop(block_index)  # re-insert: LRU, not FIFO
            cache[block_index] = entry
            return entry
        lo, hi = self._block_bounds(block_index)
        on_device = self._block_fallback_mask(block_index)
        with self.tracer.span("streaming.resolve", block=block_index):
            state = self._state_block(block_index)
            pad_slots = self._slot_capacity - int(state.elem_id.shape[1])
            dispatch_args = (
                state, self.comment_capacity,
                jnp.asarray(on_device), jnp.uint32(pad_slots),
                *self._digest_tables(lo, hi),
            )
            if GLOBAL_DEVPROF.enabled:
                note_jit_dispatch(
                    "_resolve_block_digest_paged_jit",
                    _resolve_block_digest_paged_jit, dispatch_args,
                )
            resolved, digest_dev = _resolve_block_digest_paged_jit(*dispatch_args)
        entry = _BlockResolution(resolved, digest_dev, on_device)
        if len(cache) >= 2:
            cache.pop(next(iter(cache)))
        cache[block_index] = entry
        return entry

    def _dispatch_compact(self, block_index: int):
        """Base _dispatch_compact with the visible-prefix width capped at
        the block's MATERIALIZED width: the session-wide width prior may
        come from a wider block, and an over-wide take_along_axis would
        silently truncate the packed buffer's layout math."""
        from ..parallel.streaming import _compact_packed_jit

        entry = self._resolution(block_index)
        width = self._compact_width_for(block_index, entry)
        width = min(width, int(entry.device.char.shape[1]))
        buf = _compact_packed_jit(
            entry.device, self._state_block(block_index).elem_id, width
        )
        return buf, width

    # -- digests -------------------------------------------------------------

    def _schedule_rows_digest(self, rest: np.ndarray):
        k = _width_bucket(len(rest))
        rows_idx = np.zeros(k, np.int32)
        rows_idx[: len(rest)] = rest
        mask = np.zeros(k, bool)
        mask[: len(rest)] = True
        g = self._store.width_for_rows(rest)
        sub = self._store.materialize_rows(rest, g, pad_rows_to=k)
        pad_slots = self._slot_capacity - g * self.page_size
        dispatch_args = (
            sub, self.comment_capacity, jnp.asarray(mask),
            jnp.uint32(pad_slots),
            *self._digest_tables_rows(rows_idx, len(rest)),
        )
        if GLOBAL_DEVPROF.enabled:
            note_jit_dispatch(
                "_rows_digest_paged_jit", _rows_digest_paged_jit, dispatch_args,
            )
        return _rows_digest_paged_jit(*dispatch_args)

    def _digest(self, full: bool, refresh: bool) -> int:
        if full:
            # the carried-plane path: _resolution/_schedule_rows_digest above
            # already fold the pad correction into every hash they produce
            return super()._digest(True, refresh)
        from ..parallel.mesh import doc_digest_host

        if refresh:
            self._digest_row_valid[:] = False
            self._resolved_cache = (-1, {})
        replay_docs = [i for i, s in enumerate(self.docs) if s.fallback]
        on_device_all = self._on_device_mask()
        total = 0
        n_blocks = -(-self._padded_docs // self._read_chunk)
        for bi in range(n_blocks):
            lo, hi = self._block_bounds(bi)
            state = self._state_block(bi)
            pad_slots = self._slot_capacity - int(state.elem_id.shape[1])
            digest, overflow = _resolve_digest_paged_jit(
                state, self.comment_capacity,
                jnp.asarray(on_device_all[lo:hi]), jnp.uint32(pad_slots),
            )
            total = (total + int(digest)) & 0xFFFFFFFF
            ov = np.asarray(overflow)
            replay_docs.extend(
                int(self._doc_at[int(r) + lo])
                for r in np.nonzero(ov & on_device_all[lo:hi])[0]
                if int(self._doc_at[int(r) + lo]) >= 0
            )
        s_cap = self._slot_capacity
        for i in replay_docs:
            doc = _replay_doc(self._replay_changes(self.docs[i]))
            cps, slots = _doc_char_slots(doc)
            total = (total + doc_digest_host(cps, slots, s_cap)) & 0xFFFFFFFF
        return total

    # -- placement: pages are the load dimension -----------------------------

    def _reshard_sizes(self) -> np.ndarray:
        """Balance PAGES: the pool spends pages, so a shard's load is the
        pages its docs hold (a host-bound doc's replay cost still balances
        through the host_bound dimension exactly as in the base)."""
        return self._store.page_loads()[self._row_of[: self.num_docs]]

    def _permute_rows(self, src: np.ndarray) -> None:
        self._store.permute_rows(src)

    def reshard(self, assignment=None) -> dict:
        out = super().reshard(assignment)
        n_shards = max(len(out["shard_load"]), 1)
        rows_per_shard = max(self._padded_docs // n_shards, 1)
        page_load = [0] * n_shards
        pages = self._store.page_loads()
        for d in range(self.num_docs):
            row = int(self._row_of[d])
            page_load[min(row // rows_per_shard, n_shards - 1)] += int(pages[row])
        out["page_load"] = page_load
        return out


class RaggedStreamingMerge(PagedStreamingMerge):
    """StreamingMerge over the page pool with the RAGGED apply: every round
    is ONE ``ops/ragged.apply_batch_ragged`` dispatch straight against pool
    pages — no page-count buckets, no row-bucket pad, no gather/scatter,
    and therefore exactly one compiled apply executable per session
    regardless of the doc-size mix (tests/test_recompile_sentinel.py pins
    a tweet-fleet + essay + book drain to one program where the paged
    engine compiles a bucket ladder).

    Storage, reads, digests, compaction and resharding are inherited from
    :class:`PagedStreamingMerge` unchanged — the pool IS the paged pool,
    so materialized blocks and the pad-term-corrected digests stay
    bit-equal to both other layouts.  What changes is only the commit
    half: the round's streams dispatch over ALL ``D`` doc rows (a static
    batch axis; untouched rows carry all-zero streams, which the traced
    per-doc loop bounds make genuinely free, not just masked), with the
    plan planes (store/ragged.ragged_plan) cached per
    ``(alloc_epoch, pool size)`` so steady-state rounds re-upload
    nothing."""

    _layout = "ragged"

    def __init__(self, num_docs, actors, *args,
                 layout: str = "ragged", **kwargs) -> None:
        if layout != "ragged":
            raise ValueError(
                f"RaggedStreamingMerge is layout='ragged', got {layout!r}"
            )
        super().__init__(num_docs, actors, *args, layout="paged", **kwargs)
        #: (alloc_epoch, pool_pages) -> (RaggedPlan, device plane tuple)
        self._ragged_cache: tuple = ((-1, -1), None)
        #: mesh twin: (alloc_epoch, pages_per_shard) -> ((docs_walked,
        #: pages_walked), stacked per-shard device planes)
        self._mesh_ragged_cache: tuple = ((-1, -1), None)

    def health(self) -> Dict:
        h = super().health()
        h["layout"] = "ragged"
        return h

    def _round_widths(self, pool, obj_streams, ki, kd, km, kp):
        """Keep round stream widths FIXED at the session caps (the
        block-chunked/static_rounds discipline): the ragged apply's trip
        counts are data, so padded stream slots cost transfer bytes but
        zero compute — while a shrunk width is a brand-new apply shape.
        One width set x one pool shape = the ONE executable the recompile
        sentinel pins."""
        return ki, kd, km, kp

    # -- the ragged device half of a round -----------------------------------

    def _ragged_planes(self):
        """The whole-session ragged plan, rebuilt only when the allocator
        state it snapshots actually changed (ensure growth, evacuation,
        compaction, permutation, pool growth — anything that bumps
        ``PagedDocStore.alloc_epoch``)."""
        from ..ops.ragged import plan_arrays
        from .ragged import ragged_plan

        store = self._store
        key = (store.alloc_epoch, int(store.pool_elem.shape[0]))
        cached_key, cached = self._ragged_cache
        if cached_key != key:
            plan = ragged_plan(store)
            cached = (plan, plan_arrays(plan))
            self._ragged_cache = (key, cached)
        return cached

    def _commit_round_ragged(self, enc, widths) -> None:
        """One round = one ragged dispatch over the whole pool."""
        from ..ops.ragged import apply_batch_ragged_jit

        store = self._store
        d = self._padded_docs
        rows = np.nonzero(enc.num_ops)[0]
        real = int(enc.num_ops.sum())
        if len(rows):
            store.ensure_rows(rows, self._cum_ins[rows])
        plan, planes = self._ragged_planes()
        row_idx, owner, pos_base, prev_page, page_count, page_table = planes
        store.pool_elem, store.pool_char, store.aux = apply_batch_ragged_jit(
            store.pool_elem, store.pool_char, store.aux,
            row_idx, owner, pos_base, prev_page, page_count, page_table,
            group_stream_arrays(enc, None, d),
            jnp.asarray(enc.ins_count, jnp.int32),
            jnp.asarray(enc.del_count, jnp.int32),
        )
        # ragged pays real ops only: no bucket pad rows, no padded slots —
        # capacity IS the real work, so padding_efficiency reads 1.0
        self._commit_caps[id(enc)] = real
        if GLOBAL_DEVPROF.enabled:
            GLOBAL_DEVPROF.observe_round(
                occupancy_key(d, *widths), real, max(real, 1),
                origin="streaming.ragged",
            )
            GLOBAL_DEVPROF.observe_ragged(
                docs_walked=plan.docs_walked,
                pages_walked=plan.pages_walked,
                real_ops=real,
            )
        if len(rows):
            self._digest_row_valid[rows] = False
        self.rounds += 1
        GLOBAL_COUNTERS.add("streaming.rounds")

    def _commit_rounds(self, batch) -> None:
        """Per-round ragged dispatches (a Python loop, ONE executable): a
        rounds-chained fused program would mint one shape per drain depth,
        which is exactly the ladder this layout exists to kill.  The fused
        staged-drain hooks below reuse this same discipline, so serving
        drains and direct commits share the single compiled apply."""
        for enc, widths in batch:
            self._cum_ins += enc.ins_count
            self._commit_round_ragged(enc, widths)
        if GLOBAL_DEVPROF.enabled:
            GLOBAL_DEVPROF.observe_page_pool(self._store.pool_stats())

    def _commit_rounds_serial(self, batch) -> None:
        self._commit_rounds(batch)

    # -- fused staged-drain hooks (serve/mux.py drains) ----------------------
    #
    # The drain loop stages rounds through the prep/stage/dispatch trio so
    # host staging overlaps device work.  The ragged prep is allocation
    # only (the plan planes are cached device-side), the stage uploads each
    # round's stream tensors, and the dispatch is the same per-round
    # program as a direct commit — shapes never depend on the drain depth.

    def _prep_fused_batch(self, batch):
        for enc, _ in batch:
            self._cum_ins += enc.ins_count
            rows = np.nonzero(enc.num_ops)[0]
            if len(rows):
                self._store.ensure_rows(rows, self._cum_ins[rows])
        if self.mesh is not None:
            return ("mesh_ragged", len(batch))
        return ("ragged", len(batch))

    def _stage_fused_batch(self, batch, statics):
        d = self._padded_docs
        inputs = tuple(
            (
                group_stream_arrays(enc, None, d),
                jnp.asarray(enc.ins_count, jnp.int32),
                jnp.asarray(enc.del_count, jnp.int32),
            )
            for enc, _ in batch
        )
        if statics[0] == "mesh_ragged":
            from ..parallel.mesh_fused import shard_leading

            return shard_leading(inputs, self.mesh)
        return jax.device_put(inputs)

    def _mesh_ragged_planes(self):
        """Per-shard ragged plans — LOCAL row ids over each shard's local
        pool block, built straight off the per-shard allocators (the
        owner sentinel is ``rows_per_shard``, the prev-page sentinel each
        shard's OWN null page 0) — stacked on a leading shard axis and
        cached device-side keyed by (alloc_epoch, per-shard pool size):
        the meshless ``_ragged_planes`` discipline, one plane set per
        shard, re-uploaded only when the allocator state changes."""
        from ..parallel.mesh_fused import shard_leading

        store = self._store
        key = (store.alloc_epoch, store.pages_per_shard)
        cached_key, cached = self._mesh_ragged_cache
        if cached_key != key:
            n, rps = store.n_shards, store.rows_per_shard
            ps = store.pages_per_shard
            p = store.page_size
            row_idx = np.tile(np.arange(rps, dtype=np.int64), (n, 1))
            owner = np.full((n, ps), rps, np.int32)
            pos_base = np.zeros((n, ps), np.int32)
            prev_page = np.zeros((n, ps), np.int32)
            page_count = np.zeros((n, rps), np.int32)
            page_table = np.zeros((n, rps, store.max_doc_pages), np.int32)
            pages_walked = 0
            for s in range(n):
                alloc = store.alloc.shards[s]
                for doc in alloc.docs():
                    row = doc - s * rps
                    pages = alloc.pages_of(doc)
                    page_count[s, row] = len(pages)
                    pages_walked += len(pages)
                    for k, pg in enumerate(pages):
                        owner[s, pg] = row
                        pos_base[s, pg] = k * p
                        prev_page[s, pg] = pages[k - 1] if k else 0
                        page_table[s, row, k] = pg
            planes = shard_leading(
                (row_idx, owner, pos_base, prev_page, page_count,
                 page_table),
                self.mesh,
            )
            cached = ((self._padded_docs, pages_walked), planes)
            self._mesh_ragged_cache = (key, cached)
        return cached

    def _mesh_ragged_fn(self):
        """The ONE mesh ragged apply executable: per-round ``shard_map``
        dispatch whose body walks each shard's local pool with its own
        plan planes.  Like the meshless ragged engine, rounds dispatch one
        at a time against the same compiled program — chaining a drain's
        rounds into one program would mint one XLA shape per drain depth,
        the ladder this layout exists to kill."""
        from ..ops.kernel import resolve_ragged_impl, resolve_state_donation
        from ..ops.ragged import apply_batch_ragged
        from ..parallel.mesh_fused import mesh_fn

        mesh = self.mesh
        impl = resolve_ragged_impl(self._store.pool_elem)
        donate = resolve_state_donation(self._store.pool_elem)

        def build():
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            def body(pool_elem, pool_char, aux, planes, earrays,
                     ins_counts, del_counts):
                (row_idx, owner, pos_base, prev_page, page_count,
                 page_table) = jax.tree_util.tree_map(
                    lambda x: x[0], planes)
                return apply_batch_ragged(
                    pool_elem, pool_char, aux, row_idx, owner, pos_base,
                    prev_page, page_count, page_table, earrays,
                    ins_counts, del_counts, ragged_impl=impl,
                )

            # check_vma=False: pallas_call results carry no varying-axes
            # type; every operand and result is doc-sharded anyway
            wrapped = shard_map(
                body, mesh=mesh,
                in_specs=(P(_mesh.DOC_AXIS),) * 7,
                out_specs=(P(_mesh.DOC_AXIS),) * 3,
                check_vma=False,
            )
            return jax.jit(
                wrapped, donate_argnums=(0, 1, 2) if donate else ())

        return mesh_fn(mesh, ("ragged_apply", impl, donate), build)

    def _dispatch_mesh_fused_batch(self, batch, statics, inputs) -> bool:
        store = self._store
        (docs_walked, pages_walked), planes = self._mesh_ragged_planes()
        fn = self._mesh_ragged_fn()
        GLOBAL_COUNTERS.add("streaming.fused_dispatches")
        for (enc, widths), (earrays, ins_counts, del_counts) in zip(
            batch, inputs
        ):
            rows = np.nonzero(enc.num_ops)[0]
            real = int(enc.num_ops.sum())
            if GLOBAL_DEVPROF.enabled:
                note_jit_dispatch(
                    "apply_batch_ragged.mesh", fn,
                    (store.pool_elem, store.pool_char, store.aux, planes,
                     earrays, ins_counts, del_counts),
                )
            store.pool_elem, store.pool_char, store.aux = fn(
                store.pool_elem, store.pool_char, store.aux, planes,
                earrays, ins_counts, del_counts,
            )
            self._commit_caps[id(enc)] = real
            if GLOBAL_DEVPROF.enabled:
                GLOBAL_DEVPROF.observe_round(
                    occupancy_key(self._padded_docs, *widths), real,
                    max(real, 1), origin="streaming.ragged",
                )
                GLOBAL_DEVPROF.observe_ragged(
                    docs_walked=docs_walked, pages_walked=pages_walked,
                    real_ops=real,
                )
            if len(rows):
                self._digest_row_valid[rows] = False
            self.rounds += 1
            GLOBAL_COUNTERS.add("streaming.rounds")
        if GLOBAL_DEVPROF.enabled:
            GLOBAL_DEVPROF.observe_page_pool(store.pool_stats())
            GLOBAL_DEVPROF.observe_mesh(self._mesh_stats())
        return False

    def _dispatch_fused_batch(self, batch, statics, inputs,
                              chain_digest: bool = False) -> bool:
        if statics[0] == "mesh_ragged":
            return self._dispatch_mesh_fused_batch(batch, statics, inputs)
        from ..ops.ragged import apply_batch_ragged_jit

        store = self._store
        plan, planes = self._ragged_planes()
        row_idx, owner, pos_base, prev_page, page_count, page_table = planes
        for (enc, widths), (earrays, ins_counts, del_counts) in zip(
            batch, inputs
        ):
            rows = np.nonzero(enc.num_ops)[0]
            real = int(enc.num_ops.sum())
            store.pool_elem, store.pool_char, store.aux = (
                apply_batch_ragged_jit(
                    store.pool_elem, store.pool_char, store.aux,
                    row_idx, owner, pos_base, prev_page, page_count,
                    page_table, earrays, ins_counts, del_counts,
                )
            )
            self._commit_caps[id(enc)] = real
            if GLOBAL_DEVPROF.enabled:
                GLOBAL_DEVPROF.observe_round(
                    occupancy_key(self._padded_docs, *widths), real,
                    max(real, 1), origin="streaming.ragged",
                )
                GLOBAL_DEVPROF.observe_ragged(
                    docs_walked=plan.docs_walked,
                    pages_walked=plan.pages_walked,
                    real_ops=real,
                )
            if len(rows):
                self._digest_row_valid[rows] = False
            self.rounds += 1
            GLOBAL_COUNTERS.add("streaming.rounds")
        if GLOBAL_DEVPROF.enabled:
            GLOBAL_DEVPROF.observe_page_pool(store.pool_stats())
        return False

    def _emit_round_stats(self, batch, scheduled: int,
                          schedule_s: float, apply_s: float,
                          origin: str = "streaming.ragged") -> None:
        touched: set = set()
        real = 0
        capacity = 0
        for enc, _ in batch:
            touched.update(int(r) for r in np.nonzero(enc.num_ops)[0])
            real += int(enc.num_ops.sum())
            capacity += self._commit_caps.pop(id(enc), 0)
        if GLOBAL_DEVPROF.enabled:
            GLOBAL_DEVPROF.sample_memory()
        stats = MergeStats(
            docs=len(touched),
            device_docs=len(touched),
            device_ops=real,
            encode_seconds=schedule_s,
            apply_seconds=apply_s,
            padding_efficiency=real / capacity if capacity else 0.0,
            extras={"rounds": len(batch), "scheduled_changes": scheduled,
                    "layout_ragged": 1.0},
        )
        self.last_round_stats = stats
        self._pad_real_ops += real
        self._pad_capacity += capacity
        GLOBAL_HISTOGRAMS.observe("streaming.round_seconds", schedule_s + apply_s)
        GLOBAL_HISTOGRAMS.observe(
            "streaming.round_scheduled_changes", scheduled, buckets=SIZE_BUCKETS
        )
