"""Batched CRDT op-application kernel (two-phase, split-stream).

Phase structure per document (vmap over the doc axis, which is the sharded
axis under a mesh):

1. **Inserts** — the only sequential phase: a ``lax.fori_loop`` whose carry
   is exactly two (S,) arrays (packed element ids + characters) plus two
   scalars.  Each step realizes the reference's RGA insert-after-reference
   with its convergence skip (src/micromerge.ts:1187-1245): the O(n)
   pointer-chasing scans become O(S) lane-parallel compare/select, and the
   list splice becomes a masked shift.  Keeping the carry to 2 arrays is the
   point — the loop is HBM-bandwidth bound.
2. **Deletes** — tombstones are idempotent flag-sets that commute with each
   other and do not affect insert placement (the RGA skip compares only
   element ids), so the whole delete stream applies as ONE vectorized
   any-match over (S x KD) (reference applyListUpdate, :1250-1277; the
   visible-array splice is unnecessary — visibility is recomputed on read).
3. **Marks** — already encoded in mark-table layout host-side; appended with
   one masked scatter (span semantics live in ops/resolve.py).

A reference element that cannot be found, or a capacity overflow, sets the
doc's ``overflow`` flag; the API layer falls back to the scalar oracle for
flagged docs.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

# Devprof bucket plumbing at the kernel boundary (obs/devprof.py): each jit
# wrapper below derives the dispatch's shape-bucket key from the ACTUAL
# argument arrays plus the static kwargs — exactly the granularity of jax's
# compile cache, so the per-site distinct-shape count cross-checks the
# RecompileSentinel.  Guarded on ``GLOBAL_DEVPROF.enabled``: the disabled
# path costs one attribute check per dispatch.  Merge-scope modules import
# telemetry from ..obs only (the PR-3 facade invariant).
from ..obs import GLOBAL_DEVPROF, note_jit_dispatch as _note_dispatch
from .encode import EncodedBatch, MARK_COLS
from .packed import PackedDocs


def _insert_loop(elem_id, char, n0, overflow0, ins_ref, ins_op, ins_char):
    """Sequential RGA insert phase for one document."""
    s_cap = elem_id.shape[0]
    pos = jnp.arange(s_cap, dtype=jnp.int32)

    def body(k, carry):
        elem, chars, n, ov = carry
        ref, op = ins_ref[k], ins_op[k]
        live = op != 0
        is_head = ref == 0
        match = (elem == ref) & (pos < n)
        found = is_head | jnp.any(match)
        p = jnp.where(is_head, jnp.int32(-1), jnp.argmax(match).astype(jnp.int32))

        # Convergence skip: first position right of the reference whose
        # element id is NOT greater than the inserting op's id.  Packed ids
        # make this a single integer compare.
        candidate = (pos > p) & (pos < n) & (elem < op)
        q = jnp.min(jnp.where(candidate, pos, n))

        ok = live & found & (n < s_cap)
        rolled_elem = jnp.roll(elem, 1)
        rolled_char = jnp.roll(chars, 1)
        new_elem = jnp.where(pos < q, elem, jnp.where(pos == q, op, rolled_elem))
        new_char = jnp.where(pos < q, chars, jnp.where(pos == q, ins_char[k], rolled_char))
        return (
            jnp.where(ok, new_elem, elem),
            jnp.where(ok, new_char, chars),
            jnp.where(ok, n + 1, n),
            ov | (live & ~found) | (live & (n >= s_cap)),
        )

    return lax.fori_loop(0, ins_op.shape[0], body, (elem_id, char, n0, overflow0))


def _append_rows(table, count, rows, rows_count):
    """Masked scatter appending ``rows`` (dict or single array) into append-only
    ``table`` at [count, count + rows_count); out-of-range writes drop.

    Keep the SCATTER formulation: round 5 tried a gather+select over the
    capacity axis (each table slot takes rows[j - count] when in range) on
    the theory that the vmapped scatter lowered badly, and a same-process
    A/B (scripts/append_ab.py) measured the gather 2.6x SLOWER on the
    batch_8k shape (35.7 -> 95.3 ms/apply) — the batched dynamic gather is
    what lowers badly on TPU, the batch-dim scatter is fine."""
    single = not isinstance(table, dict)
    tables = {"_": table} if single else table
    new_rows = {"_": rows} if single else rows
    cap = next(iter(tables.values())).shape[0]
    km = next(iter(new_rows.values())).shape[0]
    src = jnp.arange(km, dtype=jnp.int32)
    dst = count + src
    valid = src < rows_count
    dst = jnp.where(valid, dst, cap)
    out = {
        col: tables[col].at[dst].set(new_rows[col], mode="drop") for col in tables
    }
    overflow = count + rows_count > cap
    new_count = jnp.minimum(count + rows_count, cap)
    if single:
        return out["_"], new_count, overflow
    return out, new_count, overflow


def _apply_doc(state: PackedDocs, ins_ref, ins_op, ins_char, del_target, mark_rows, mark_count):
    elem, char, n, ov = _insert_loop(
        state.elem_id, state.char, state.num_slots, state.overflow,
        ins_ref, ins_op, ins_char,
    )
    return _post_insert_doc(
        state._replace(elem_id=elem, char=char, num_slots=n, overflow=ov),
        del_target, mark_rows, mark_count,
    )


def _post_insert_doc(state: PackedDocs, del_target, mark_rows, mark_count,
                     exists=None, skip=None):
    """Phases 2+3 (deletes, marks) for one doc, after the insert phase.

    ``exists`` optionally carries a precomputed (KD,) target-exists mask so
    callers whose element planes do NOT live in ``state`` (the ragged pool
    walk, ops/ragged.py) can reuse these phases on a dummy-elem state; with
    it given, ``state.elem_id`` is never read.  ``skip`` likewise carries a
    precomputed (KD,) mask of the targets already tombstoned or deleted by
    an earlier entry of the stream (ops/ragged.py sorts for both)."""
    elem, n, ov = state.elem_id, state.num_slots, state.overflow

    # Deletes: validate targets exist, then append to the tombstone table
    # (dedup against rows already there keeps re-delivery idempotent).
    live = del_target != 0
    if exists is None:
        exists = jnp.any(elem[:, None] == del_target[None, :], axis=0)  # (KD,)
    # Idempotence: skip targets already tombstoned in the carried-over table
    # AND duplicates within this stream (concurrent deletes of one char).
    if skip is None:
        kd = del_target.shape[0]
        dup_earlier = jnp.any(
            (del_target[None, :] == del_target[:, None])
            & (jnp.arange(kd)[:, None] < jnp.arange(kd)[None, :]),
            axis=0,
        )
        skip = jnp.any(state.tomb_id[:, None] == del_target[None, :], axis=0) | dup_earlier
    already = skip & live
    del_err = jnp.any(live & ~exists)
    keep = live & exists & ~already
    # compact kept targets to a dense prefix so the append is contiguous
    order = jnp.argsort(~keep, stable=True)  # kept rows first
    dense = jnp.where(keep[order], del_target[order], 0)
    tomb_id, num_tombs, tomb_ov = _append_rows(
        state.tomb_id, state.num_tombs, dense, jnp.sum(keep).astype(jnp.int32)
    )

    marks_in = {col: getattr(state, col) for col in MARK_COLS}
    marks_out, num_marks, mark_ov = _append_rows(
        marks_in, state.num_marks, mark_rows, mark_count
    )
    return state._replace(
        tomb_id=tomb_id,
        num_tombs=num_tombs,
        num_marks=num_marks,
        overflow=ov | del_err | tomb_ov | mark_ov,
        **marks_out,
    )




def _apply_map_doc(state: PackedDocs, p_obj, p_key, p_op, p_kind, p_val, count):
    """Phase 4: LWW upsert of map registers for one doc.

    The scalar semantics is core/doc.py ``_apply_op``'s map branch (reference
    src/micromerge.ts:1151-1175): per (object, key), the op with the largest
    id wins; ``del`` wins like any write (kind VK_DELETED).  Sequential over
    the round's map stream because an unseen key must append exactly one
    register row even when written twice in a round; winner choice itself is
    an order-independent max, so any causally-valid schedule converges."""
    cap = state.r_obj.shape[0]
    kp = p_op.shape[0]

    def body(i, carry):
        r_obj, r_key, r_op, r_kind, r_val, n, ov = carry
        live = (i < count) & (p_op[i] != 0)
        match = (r_op != 0) & (r_obj == p_obj[i]) & (r_key == p_key[i])
        exists = jnp.any(match)
        pos = jnp.where(exists, jnp.argmax(match), n).astype(jnp.int32)
        full = ~exists & (n >= cap)
        pos = jnp.minimum(pos, cap - 1)
        win = live & ~full & (p_op[i] > r_op[pos])
        r_obj = r_obj.at[pos].set(jnp.where(win, p_obj[i], r_obj[pos]))
        r_key = r_key.at[pos].set(jnp.where(win, p_key[i], r_key[pos]))
        r_op = r_op.at[pos].set(jnp.where(win, p_op[i], r_op[pos]))
        r_kind = r_kind.at[pos].set(jnp.where(win, p_kind[i], r_kind[pos]))
        r_val = r_val.at[pos].set(jnp.where(win, p_val[i], r_val[pos]))
        n = n + (live & ~exists & ~full).astype(jnp.int32)
        ov = ov | (live & full)
        return (r_obj, r_key, r_op, r_kind, r_val, n, ov)

    r_obj, r_key, r_op, r_kind, r_val, n, ov = lax.fori_loop(
        0, kp, body,
        (state.r_obj, state.r_key, state.r_op, state.r_kind, state.r_val,
         state.num_regs, state.overflow),
    )
    return state._replace(
        r_obj=r_obj, r_key=r_key, r_op=r_op, r_kind=r_kind, r_val=r_val,
        num_regs=n, overflow=ov,
    )


def apply_batch(
    state: PackedDocs,
    encoded_arrays,
    *,
    insert_impl: str = "auto",
    insert_loop_slots: int | None = None,
) -> PackedDocs:
    """Batched apply: vmap of the phase pipeline over the doc axis.

    ``encoded_arrays`` is the tuple
    (ins_ref, ins_op, ins_char, del_target, marks_dict, mark_count[,
    maps_dict, map_count]) with leading doc axes, as produced by
    :func:`encoded_arrays_of`; the 6-tuple form (no map stream) is accepted
    for callers without map ops.

    ``insert_impl`` selects the sequential-phase implementation:
    ``"auto"`` (pallas on TPU, lax elsewhere), ``"lax"``, ``"pallas"``, or
    ``"pallas_interpret"`` (CPU-debuggable pallas, for differential tests).
    ``insert_loop_slots`` optionally bounds the slot window the insert loop
    touches (see pallas_insert.insert_batch_pallas); ignored on the lax path.
    """
    if len(encoded_arrays) == 6:
        ins_ref, ins_op, ins_char, del_target, marks, mark_count = encoded_arrays
        maps, map_count = None, None
    else:
        (ins_ref, ins_op, ins_char, del_target, marks, mark_count,
         maps, map_count) = encoded_arrays
    impl = insert_impl
    if impl == "auto":
        impl = resolve_insert_impl(state.elem_id)
    if impl == "pallas":
        # Long-doc shapes whose resident state cannot fit VMEM take the lax
        # path (streams state through HBM; slower but unbounded).
        from .pallas_insert import effective_loop_slots, pallas_vmem_ok

        s_loop = effective_loop_slots(state.elem_id.shape[1], insert_loop_slots)
        if not pallas_vmem_ok(s_loop):
            impl = "lax"
    if impl in ("pallas", "pallas_interpret"):
        from .pallas_insert import insert_batch_pallas

        elem, char, n, ov = insert_batch_pallas(
            state.elem_id, state.char, state.num_slots, state.overflow,
            ins_ref, ins_op, ins_char,
            interpret=(impl == "pallas_interpret"),
            loop_slots=insert_loop_slots,
        )
        state = state._replace(elem_id=elem, char=char, num_slots=n, overflow=ov)
        state = jax.vmap(_post_insert_doc)(state, del_target, marks, mark_count)
    elif impl == "lax":
        state = jax.vmap(_apply_doc)(
            state, ins_ref, ins_op, ins_char, del_target, marks, mark_count
        )
    else:
        raise ValueError(f"unknown insert_impl: {insert_impl!r}")
    if maps is not None:
        state = jax.vmap(_apply_map_doc)(
            state, maps["p_obj"], maps["p_key"], maps["p_op"],
            maps["p_kind"], maps["p_val"], map_count,
        )
    return state


# -- paged storage (store/): gather-based apply through a page table --------
#
# The paged layout (store/paged.py) keeps the element planes in a global
# (N_pages, P) pool with per-doc page tables instead of a padded (D, S)
# batch.  The apply path gathers ONLY the dispatched docs' pages into a
# dense (B, G*P) group — G the group's power-of-two page-count bucket — runs
# the exact same phase pipeline (apply_batch; byte-identical math), and
# scatters the element pages + aux rows back.  Page 0 is the reserved NULL
# page: page-table padding slots gather zeros from it, their scatters all
# land on it, and the program re-zeroes it last so padding can never leak
# state between docs.  Per-round device work therefore scales with
# sum(touched docs x their own bucket width), not docs x widest-doc width.

#: PackedDocs fields that stay dense per-doc rows under the paged layout
#: (tombstones/marks/registers/scalars are small; the element planes are
#: where the padded waste lives)
PAGED_AUX_FIELDS = tuple(
    f for f in PackedDocs._fields if f not in ("elem_id", "char")
)


def paged_state_of(pool_elem, pool_char, aux, row_idx, page_rows) -> PackedDocs:
    """Dense (B, G*P) PackedDocs view of ``row_idx``'s docs, gathered from
    the page pool through ``page_rows`` (B, G) and the dense aux rows.
    Out-of-range padding in ``row_idx`` clamps (jit gather semantics) to a
    real row whose streams are all-zero no-ops at apply time."""
    b, g = page_rows.shape
    p = pool_elem.shape[1]
    elem = pool_elem[page_rows].reshape(b, g * p)
    char = pool_char[page_rows].reshape(b, g * p)
    sub = {f: a[row_idx] for f, a in zip(PAGED_AUX_FIELDS, aux)}
    return PackedDocs(elem_id=elem, char=char, **sub)


_gather_paged_jit = jax.jit(paged_state_of)


def gather_paged_state_jit(pool_elem, pool_char, aux, row_idx, page_rows) -> PackedDocs:
    """jit-compiled :func:`paged_state_of` — the materialization program the
    paged read/digest paths dispatch (one program per (B, G) bucket)."""
    args = (pool_elem, pool_char, aux, row_idx, page_rows)
    if GLOBAL_DEVPROF.enabled:
        _note_dispatch("gather_paged_state", _gather_paged_jit, args)
    return _gather_paged_jit(*args)


def apply_batch_paged(
    pool_elem,
    pool_char,
    aux,  # tuple of dense (D, ...) arrays in PAGED_AUX_FIELDS order
    row_idx,  # (B,) int32 doc rows (padding >= D: gathers clamp, scatters drop)
    page_rows,  # (B, G) int32 page ids (padding entries = 0, the null page)
    encoded_arrays,  # the apply_batch stream tuple with (B, ...) doc axes
    *,
    insert_impl: str = "auto",
    insert_loop_slots: int | None = None,
):
    """Gather-through-page-table apply: the paged twin of
    :func:`apply_batch`.  Returns ``(pool_elem, pool_char, aux)`` updated.

    The math is exactly :func:`apply_batch` on the gathered dense view, so
    a paged backend is byte-identical to the padded one by construction —
    the layouts differ only in where the slots live between rounds."""
    state = paged_state_of(pool_elem, pool_char, aux, row_idx, page_rows)
    state = apply_batch(
        state, encoded_arrays,
        insert_impl=insert_impl, insert_loop_slots=insert_loop_slots,
    )
    b, g = page_rows.shape
    p = pool_elem.shape[1]
    flat = page_rows.reshape(-1)
    pool_elem = pool_elem.at[flat].set(state.elem_id.reshape(b * g, p))
    pool_char = pool_char.at[flat].set(state.char.reshape(b * g, p))
    # padding page-table entries all scattered onto the null page; restore it
    pool_elem = pool_elem.at[0].set(0)
    pool_char = pool_char.at[0].set(0)
    aux = tuple(
        a.at[row_idx].set(getattr(state, f))
        for f, a in zip(PAGED_AUX_FIELDS, aux)
    )
    return pool_elem, pool_char, aux


_apply_batch_paged_jit = jax.jit(
    apply_batch_paged, static_argnames=("insert_impl", "insert_loop_slots")
)


def apply_batch_paged_jit(pool_elem, pool_char, aux, row_idx, page_rows,
                          encoded_arrays, *, insert_impl: str = "auto",
                          insert_loop_slots: int | None = None):
    """jit-compiled :func:`apply_batch_paged` (``"auto"`` resolved at the
    boundary from the pool arrays' placement, as in :func:`apply_batch_jit`)."""
    if insert_impl == "auto":
        insert_impl = resolve_insert_impl(pool_elem)
    if GLOBAL_DEVPROF.enabled:
        _note_dispatch(
            "apply_batch_paged", _apply_batch_paged_jit,
            (pool_elem, pool_char, aux, row_idx, page_rows, encoded_arrays),
            dict(insert_impl=insert_impl, insert_loop_slots=insert_loop_slots),
        )
    return _apply_batch_paged_jit(
        pool_elem, pool_char, aux, row_idx, page_rows, encoded_arrays,
        insert_impl=insert_impl, insert_loop_slots=insert_loop_slots,
    )


def apply_batch_paged_groups(
    pool_elem,
    pool_char,
    aux,
    group_inputs,  # tuple of per-group (row_idx, page_rows, encoded_arrays)
    *,
    loop_slots_seq,  # static tuple of per-group insert_loop_slots
    insert_impl: str = "auto",
):
    """One round's page-bucket groups chained inside ONE program — the
    paged half of the fused round pipeline.  Each per-group dispatch of
    :func:`apply_batch_paged` reads and functionally rewrites the WHOLE
    pool (the ``.at[].set`` scatter allocates a fresh pool copy per group
    without donation), so a round touching several buckets paid one pool
    copy per bucket; chained + donated (the jit wrapper donates all three
    pool operands), XLA updates the pool in place across every group."""
    if len(group_inputs) != len(loop_slots_seq):
        raise ValueError("paged groups: inputs/loop_slots length mismatch")
    for (row_idx, page_rows, encoded_arrays), loop_slots in zip(
            group_inputs, loop_slots_seq):
        pool_elem, pool_char, aux = apply_batch_paged(
            pool_elem, pool_char, aux, row_idx, page_rows, encoded_arrays,
            insert_impl=insert_impl, insert_loop_slots=loop_slots,
        )
    return pool_elem, pool_char, aux


_apply_paged_groups_jit = jax.jit(
    apply_batch_paged_groups,
    static_argnames=("loop_slots_seq", "insert_impl"),
    donate_argnums=(0, 1, 2),
)
_apply_paged_groups_jit_nodonate = jax.jit(
    apply_batch_paged_groups,
    static_argnames=("loop_slots_seq", "insert_impl"),
)


def apply_batch_paged_groups_jit(pool_elem, pool_char, aux, group_inputs, *,
                                 loop_slots_seq, insert_impl: str = "auto",
                                 donate: bool | None = None):
    """jit-compiled :func:`apply_batch_paged_groups`; the pool operands
    (``pool_elem``/``pool_char``/``aux``) are donated per
    :func:`resolve_state_donation` (or the explicit ``donate``) — rebind
    to the returned triple either way."""
    if insert_impl == "auto":
        insert_impl = resolve_insert_impl(pool_elem)
    if donate is None:
        donate = resolve_state_donation(pool_elem)
    fn = (_apply_paged_groups_jit if donate
          else _apply_paged_groups_jit_nodonate)
    statics = dict(loop_slots_seq=tuple(loop_slots_seq),
                   insert_impl=insert_impl)
    if GLOBAL_DEVPROF.enabled:
        _note_dispatch(
            "apply_batch_paged_groups", fn,
            (pool_elem, pool_char, aux, tuple(group_inputs)), statics,
        )
    return fn(
        pool_elem, pool_char, aux, tuple(group_inputs), **statics,
    )


def _pad_from_flat(flat, counts, width: int):
    """(N,) flat per-doc-concatenated values + (D,) counts -> (D, width)
    zero-padded rows, reconstructed on device with ONE gather (host->device
    transfer is proportional to real ops, not padded capacity)."""
    counts = counts.astype(jnp.int32)
    if flat.shape[0] == 0:  # a round with zero ops of this kind
        return jnp.zeros((counts.shape[0], width), jnp.int32)
    offsets = jnp.cumsum(counts) - counts
    idx = offsets[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    mask = jnp.arange(width, dtype=jnp.int32)[None, :] < counts[:, None]
    safe = jnp.clip(idx, 0, int(flat.shape[0]) - 1)
    return jnp.where(mask, flat[safe], 0)


def apply_batch_compact(
    state: PackedDocs,
    stream_counts,  # (n_ins, n_del, n_mark, n_map) each (D,) int32
    ins_flat,  # (ref, op, char) each (N_i,) int32
    del_flat,  # (N_d,) int32
    mark_flat,  # dict col -> (N_m,) int32 in MARK_COLS order
    map_flat=None,  # dict col -> (N_p,) int32, packed.MAP_STREAM_COLS (optional)
    *,
    widths,  # static (ki, kd, km[, kp]) padded stream widths
    insert_impl: str = "auto",
    insert_loop_slots: int | None = None,
) -> PackedDocs:
    """apply_batch over compactly-transferred streams.

    The padded (D, K) layout the kernel consumes is rebuilt on device from
    flat arrays; with a slow host link (the padded rows are mostly zeros)
    this cuts per-round transfer several-fold.  Flat arrays may carry
    power-of-two padding at the END (zero rows beyond sum(counts) are never
    gathered into a live slot)."""
    n_ins, n_del, n_mark = stream_counts[0], stream_counts[1], stream_counts[2]
    ki, kd, km = widths[0], widths[1], widths[2]
    ins_ref = _pad_from_flat(ins_flat[0], n_ins, ki)
    ins_op = _pad_from_flat(ins_flat[1], n_ins, ki)
    ins_char = _pad_from_flat(ins_flat[2], n_ins, ki)
    del_target = _pad_from_flat(del_flat, n_del, kd)
    marks = {col: _pad_from_flat(mark_flat[col], n_mark, km) for col in mark_flat}
    arrays = (ins_ref, ins_op, ins_char, del_target, marks,
              n_mark.astype(jnp.int32))
    if map_flat is not None:
        n_map = stream_counts[3]
        kp = widths[3]
        maps = {col: _pad_from_flat(map_flat[col], n_map, kp) for col in map_flat}
        arrays = arrays + (maps, n_map.astype(jnp.int32))
    return apply_batch(
        state,
        arrays,
        insert_impl=insert_impl,
        insert_loop_slots=insert_loop_slots,
    )


_apply_batch_compact_jit = jax.jit(
    apply_batch_compact,
    static_argnames=("widths", "insert_impl", "insert_loop_slots"),
)


def apply_batch_compact_jit(state, stream_counts, ins_flat, del_flat, mark_flat,
                            map_flat=None, *, widths, insert_impl: str = "auto",
                            insert_loop_slots: int | None = None) -> PackedDocs:
    """jit-compiled :func:`apply_batch_compact` (``"auto"`` resolved at the
    boundary, as in :func:`apply_batch_jit`)."""
    if insert_impl == "auto":
        insert_impl = resolve_insert_impl(state.elem_id)
    if GLOBAL_DEVPROF.enabled:
        _note_dispatch(
            "apply_batch_compact", _apply_batch_compact_jit,
            (state, stream_counts, ins_flat, del_flat, mark_flat, map_flat),
            dict(widths=widths, insert_impl=insert_impl,
                 insert_loop_slots=insert_loop_slots),
        )
    return _apply_batch_compact_jit(
        state, stream_counts, ins_flat, del_flat, mark_flat, map_flat,
        widths=widths, insert_impl=insert_impl,
        insert_loop_slots=insert_loop_slots,
    )


def apply_batch_compact_rounds(
    state: PackedDocs,
    rounds,  # tuple of per-round (stream_counts, ins_flat, del_flat, mark_flat, map_flat)
    *,
    widths_seq,  # static tuple of per-round widths tuples
    loop_slots_seq,  # static tuple of per-round insert_loop_slots
    insert_impl: str = "auto",
) -> PackedDocs:
    """K causally-ordered rounds chained inside ONE program.

    Every dispatch of the 21-leaf state program pays a fixed floor
    regardless of its compute (scripts/apply_phase_cost.py --floor; not
    measured on a directly attached chip), so a drain with several pending
    rounds pays K floors when each round dispatches alone.  Chaining the
    rounds in one jit keeps per-round causal semantics bit-identical (the
    same apply_batch_compact sequence, just traced together) and pays the
    floor once.  Compile cache is keyed by the static
    (widths_seq, loop_slots_seq); the scheduler's pow-2 width bucketing
    keeps the variant count small."""
    if not (len(rounds) == len(widths_seq) == len(loop_slots_seq)):
        raise ValueError(
            f"rounds/widths_seq/loop_slots_seq length mismatch: "
            f"{len(rounds)}/{len(widths_seq)}/{len(loop_slots_seq)}"
        )
    for r, widths, loop_slots in zip(rounds, widths_seq, loop_slots_seq):
        counts, ins_flat, del_flat, mark_flat, map_flat = r
        state = apply_batch_compact(
            state, counts, ins_flat, del_flat, mark_flat, map_flat,
            widths=widths, insert_impl=insert_impl,
            insert_loop_slots=loop_slots,
        )
    return state


_apply_rounds_jit = jax.jit(
    apply_batch_compact_rounds,
    static_argnames=("widths_seq", "loop_slots_seq", "insert_impl"),
)


def apply_batch_staged_rounds(
    state: PackedDocs,
    counts_all,  # (K, 4, D) int32: per-round (ins, del, mark, map) counts
    ins_all,  # (ref, op, char) each (sum ins_lens,) int32
    del_all,  # (sum del_lens,) int32
    mark_all,  # dict col -> (sum mark_lens,) int32
    map_all,  # dict col -> (sum map_lens,) int32
    *,
    widths_seq,  # static tuple of per-round (ki, kd, km, kp)
    loop_slots_seq,  # static tuple of per-round insert_loop_slots
    ins_lens,  # static tuple: per-round pow-2 bucket of each flat stream —
    del_lens,  # the in-program slice boundaries (static starts, so XLA
    mark_lens,  # lowers them to free constant-offset slices)
    map_lens,
    insert_impl: str = "auto",
) -> PackedDocs:
    """K causally-ordered rounds from ONE staged tensor set (the fused
    device-resident round pipeline's apply half).

    Functionally :func:`apply_batch_compact_rounds`, but the host ships one
    concatenated tensor per stream kind for the WHOLE batch instead of ~20
    arrays per round: the per-round flat streams (each pow-2 padded to its
    static entry in ``*_lens``) concatenate along their only axis, and the
    per-doc count vectors stack into one (K, 4, D) tensor — so a deep drain
    pays one host->device staging transfer set and one dispatch no matter
    how many rounds it fused.  The jit wrapper donates ``state``: XLA
    updates the 21-leaf resident state in place instead of allocating (and
    copying) a fresh copy per commit."""
    if not (len(widths_seq) == len(loop_slots_seq) == counts_all.shape[0]
            == len(ins_lens) == len(del_lens) == len(mark_lens)
            == len(map_lens)):
        raise ValueError("staged rounds: per-round static/tensor length mismatch")
    io = do = mo = po = 0
    for r in range(len(widths_seq)):
        counts = tuple(counts_all[r, j] for j in range(4))
        li, ld, lm, lp = ins_lens[r], del_lens[r], mark_lens[r], map_lens[r]
        ins = tuple(a[io:io + li] for a in ins_all)
        dels = del_all[do:do + ld]
        marks = {c: a[mo:mo + lm] for c, a in mark_all.items()}
        maps = {c: a[po:po + lp] for c, a in map_all.items()}
        state = apply_batch_compact(
            state, counts, ins, dels, marks, maps,
            widths=widths_seq[r], insert_impl=insert_impl,
            insert_loop_slots=loop_slots_seq[r],
        )
        io, do, mo, po = io + li, do + ld, mo + lm, po + lp
    return state


def resolve_state_donation(*arrays, platform: str | None = None) -> bool:
    """Whether the fused-pipeline programs should DONATE their resident
    state operands, resolved from where the data lives (the
    :func:`resolve_insert_impl` sniffing discipline).

    On TPU donation is the point of the fused pipeline: XLA aliases the
    21-leaf state (or the page pool) in place instead of allocating and
    copying a fresh resident copy per commit, and dispatch stays async.
    On XLA CPU a donated dispatch BLOCKS until the donated input's pending
    producer has finished (measured ~40x the async dispatch wall: 4.3 ms
    vs 0.11 ms per commit on the smoke shape), which would serialize the
    exact host/device overlap the pipeline exists to create — so CPU runs
    the undonated twin of the same program."""
    if platform is None:
        for a in arrays:
            sharding = getattr(a, "sharding", None)
            device_set = getattr(sharding, "device_set", None)
            if device_set:
                platform = next(iter(device_set)).platform
                break
    if platform is None:
        platform = jax.default_backend()
    return platform == "tpu"


_STAGED_ROUNDS_STATICS = ("widths_seq", "loop_slots_seq", "ins_lens",
                          "del_lens", "mark_lens", "map_lens", "insert_impl")
_apply_staged_rounds_jit = jax.jit(
    apply_batch_staged_rounds,
    static_argnames=_STAGED_ROUNDS_STATICS,
    donate_argnums=0,
)
_apply_staged_rounds_jit_nodonate = jax.jit(
    apply_batch_staged_rounds,
    static_argnames=_STAGED_ROUNDS_STATICS,
)


def apply_batch_staged_rounds_jit(state, counts_all, ins_all, del_all,
                                  mark_all, map_all, *, widths_seq,
                                  loop_slots_seq, ins_lens, del_lens,
                                  mark_lens, map_lens,
                                  insert_impl: str = "auto",
                                  donate: bool | None = None) -> PackedDocs:
    """jit-compiled :func:`apply_batch_staged_rounds`.  With ``donate``
    (default: :func:`resolve_state_donation`) the caller's input state
    buffer is consumed in place (reads of the old reference raise) —
    rebind to the returned state either way.  ``"auto"`` resolves at the
    boundary, as in :func:`apply_batch_jit`."""
    if insert_impl == "auto":
        insert_impl = resolve_insert_impl(state.elem_id)
    if donate is None:
        donate = resolve_state_donation(state.elem_id)
    fn = _apply_staged_rounds_jit if donate else _apply_staged_rounds_jit_nodonate
    statics = dict(widths_seq=tuple(widths_seq),
                   loop_slots_seq=tuple(loop_slots_seq),
                   ins_lens=tuple(ins_lens), del_lens=tuple(del_lens),
                   mark_lens=tuple(mark_lens), map_lens=tuple(map_lens),
                   insert_impl=insert_impl)
    if GLOBAL_DEVPROF.enabled:
        _note_dispatch(
            "apply_batch_staged_rounds", fn,
            (state, counts_all, ins_all, del_all, mark_all, map_all), statics,
        )
    return fn(
        state, counts_all, ins_all, del_all, mark_all, map_all, **statics,
    )


def apply_batch_stacked_rounds(
    state: PackedDocs,
    stacked,  # the apply_batch 8-tuple with a leading round axis R
    *,
    loop_slots_seq,  # static tuple of per-round insert_loop_slots
    insert_impl: str = "auto",
) -> PackedDocs:
    """K rounds of the PADDED (D, K) apply chained in one donated program —
    the fused pipeline's static-rounds form (serve/ shape discipline: every
    round at the session's fixed widths, so the only variant axes are the
    fused depth R and the log2 slot-window ladder)."""
    (ins_ref, ins_op, ins_char, del_t, marks, mark_count, maps,
     map_count) = stacked
    for r in range(len(loop_slots_seq)):
        arrays = (
            ins_ref[r], ins_op[r], ins_char[r], del_t[r],
            {c: a[r] for c, a in marks.items()}, mark_count[r],
            {c: a[r] for c, a in maps.items()}, map_count[r],
        )
        state = apply_batch(
            state, arrays, insert_impl=insert_impl,
            insert_loop_slots=loop_slots_seq[r],
        )
    return state


_apply_stacked_rounds_jit = jax.jit(
    apply_batch_stacked_rounds,
    static_argnames=("loop_slots_seq", "insert_impl"),
    donate_argnums=0,
)
_apply_stacked_rounds_jit_nodonate = jax.jit(
    apply_batch_stacked_rounds,
    static_argnames=("loop_slots_seq", "insert_impl"),
)


def apply_batch_stacked_rounds_jit(state, stacked, *, loop_slots_seq,
                                   insert_impl: str = "auto",
                                   donate: bool | None = None) -> PackedDocs:
    """jit-compiled :func:`apply_batch_stacked_rounds`; ``state`` donated
    per :func:`resolve_state_donation` (or the explicit ``donate``)."""
    if insert_impl == "auto":
        insert_impl = resolve_insert_impl(state.elem_id)
    if donate is None:
        donate = resolve_state_donation(state.elem_id)
    fn = (_apply_stacked_rounds_jit if donate
          else _apply_stacked_rounds_jit_nodonate)
    statics = dict(loop_slots_seq=tuple(loop_slots_seq),
                   insert_impl=insert_impl)
    if GLOBAL_DEVPROF.enabled:
        _note_dispatch(
            "apply_batch_stacked_rounds", fn, (state, stacked), statics,
        )
    return fn(state, stacked, **statics)


def _scatter_tenant_blocks(blocks, row_base, docs: int):
    """Per-tenant row blocks -> one (docs, ...) staging plane, in-program.

    ``blocks`` is (T, Dt, ...) — tenant t's Dt doc rows of one staging
    plane — and ``row_base`` is a (T,) int32 DATA plane: tenant t's rows
    land at ``row_base[t] + arange(Dt)``.  Scatter-ADD into zeros, not
    dynamic-update-slice, on purpose: all-zero rows are no-op rows to the
    apply phases, so a zero PAD block (T is pow-2 bucketed to keep one
    compile shape while the active-tenant subset varies as data) adds
    nothing wherever its row_base points, and overlapping pad targets
    stay harmless.  Tenant blocks themselves never alias — the fusion
    plan hands every tenant a disjoint doc-row range."""
    t, dt = blocks.shape[0], blocks.shape[1]
    rows = (row_base[:, None]
            + jnp.arange(dt, dtype=jnp.int32)[None, :]).reshape(-1)
    flat = blocks.reshape((t * dt,) + blocks.shape[2:])
    out = jnp.zeros((docs,) + blocks.shape[2:], blocks.dtype)
    return out.at[rows].add(flat)


def apply_batch_stacked_rounds_multi(
    state: PackedDocs,
    stacked,  # the apply_batch 8-tuple, leaves shaped (R, T, Dt, ...)
    row_base,  # (T,) int32 data plane: per-tenant doc-row offsets
    *,
    docs: int,  # static: the session's padded doc axis
    loop_slots_seq,  # static tuple of per-round insert_loop_slots
    insert_impl: str = "auto",
) -> PackedDocs:
    """The multi-tenant doc-row-offset form of
    :func:`apply_batch_stacked_rounds` (cross-tenant fusion, plan/).

    A fusion window usually touches a SUBSET of a lane's tenants; staging
    the lane's full (D, K) planes would ship mostly zeros.  This entry
    point ships only the active tenants' row blocks — (R, T, Dt, ...) per
    staging plane — plus ``row_base``, and rebuilds the full-width planes
    in-program via :func:`_scatter_tenant_blocks` before chaining the
    same per-round padded apply the stacked form runs.  ``row_base`` is
    DATA, so which tenants are active never recompiles; only the (T, Dt)
    block shape is static, and T pow-2 bucketing keeps that a ladder."""
    (ins_ref, ins_op, ins_char, del_t, marks, mark_count, maps,
     map_count) = stacked
    for r in range(len(loop_slots_seq)):
        def sc(plane, _r=r):
            return _scatter_tenant_blocks(plane[_r], row_base, docs)

        arrays = (
            sc(ins_ref), sc(ins_op), sc(ins_char), sc(del_t),
            {c: sc(a) for c, a in marks.items()}, sc(mark_count),
            {c: sc(a) for c, a in maps.items()}, sc(map_count),
        )
        state = apply_batch(
            state, arrays, insert_impl=insert_impl,
            insert_loop_slots=loop_slots_seq[r],
        )
    return state


_STACKED_MULTI_STATICS = ("docs", "loop_slots_seq", "insert_impl")
_apply_stacked_multi_jit = jax.jit(
    apply_batch_stacked_rounds_multi,
    static_argnames=_STACKED_MULTI_STATICS,
    donate_argnums=0,
)
_apply_stacked_multi_jit_nodonate = jax.jit(
    apply_batch_stacked_rounds_multi,
    static_argnames=_STACKED_MULTI_STATICS,
)


def apply_batch_stacked_rounds_multi_jit(
        state, stacked, row_base, *, loop_slots_seq,
        insert_impl: str = "auto", donate: bool | None = None) -> PackedDocs:
    """jit-compiled :func:`apply_batch_stacked_rounds_multi`; ``state``
    donated per :func:`resolve_state_donation` (or the explicit
    ``donate``)."""
    if insert_impl == "auto":
        insert_impl = resolve_insert_impl(state.elem_id)
    if donate is None:
        donate = resolve_state_donation(state.elem_id)
    fn = (_apply_stacked_multi_jit if donate
          else _apply_stacked_multi_jit_nodonate)
    statics = dict(docs=int(state.elem_id.shape[0]),
                   loop_slots_seq=tuple(loop_slots_seq),
                   insert_impl=insert_impl)
    if GLOBAL_DEVPROF.enabled:
        _note_dispatch(
            "apply_batch_stacked_rounds_multi", fn,
            (state, stacked, row_base), statics,
        )
    return fn(state, stacked, row_base, **statics)


def apply_batch_compact_rounds_jit(state, rounds, *, widths_seq,
                                   loop_slots_seq,
                                   insert_impl: str = "auto") -> PackedDocs:
    """jit-compiled :func:`apply_batch_compact_rounds` (``"auto"`` resolved
    at the boundary, as in :func:`apply_batch_jit`)."""
    if insert_impl == "auto":
        insert_impl = resolve_insert_impl(state.elem_id)
    rounds = tuple(rounds)
    statics = dict(widths_seq=tuple(widths_seq),
                   loop_slots_seq=tuple(loop_slots_seq),
                   insert_impl=insert_impl)
    if GLOBAL_DEVPROF.enabled:
        _note_dispatch(
            "apply_batch_compact_rounds", _apply_rounds_jit,
            (state, rounds), statics,
        )
    return _apply_rounds_jit(state, rounds, **statics)


def encoded_arrays_of(encoded: EncodedBatch):
    """The device-array tuple for apply_batch from a host EncodedBatch.

    Emits the 8-tuple (with the map-register stream) when the source carries
    one — both EncodedBatch and the streaming round buffers do; sources
    without a ``map_ops`` attribute yield the 6-tuple form apply_batch
    equally accepts."""
    base = (
        jnp.asarray(encoded.ins_ref),
        jnp.asarray(encoded.ins_op),
        jnp.asarray(encoded.ins_char),
        jnp.asarray(encoded.del_target),
        {col: jnp.asarray(arr) for col, arr in sorted(encoded.marks.items())},
        jnp.asarray(encoded.mark_count),
    )
    map_ops = getattr(encoded, "map_ops", None)
    if map_ops is None:
        return base
    return base + (
        {col: jnp.asarray(arr) for col, arr in sorted(map_ops.items())},
        jnp.asarray(encoded.map_count),
    )


def resolve_insert_impl(*arrays, platform: str | None = None) -> str:
    """Pick the insert-phase implementation for where the data actually lives.

    ``jax.default_backend()`` alone is wrong on machines where a TPU plugin is
    the default platform but the computation targets a CPU mesh (the driver's
    multi-chip dry run uses ``--xla_force_host_platform_device_count`` virtual
    CPU devices while a real TPU stays registered): Pallas TPU kernels cannot
    lower for CPU.  So prefer the platform of the concrete input arrays'
    shardings; tracers carry no devices, so under an outer jit fall back to
    the default backend — callers jitting over a non-default mesh must pass
    ``insert_impl`` explicitly.
    """
    if platform is None:
        for a in arrays:
            sharding = getattr(a, "sharding", None)
            device_set = getattr(sharding, "device_set", None)
            if device_set:
                platform = next(iter(device_set)).platform
                break
    if platform is None:
        platform = jax.default_backend()
    return "pallas" if platform == "tpu" else "lax"


def resolve_ragged_impl(*arrays, platform: str | None = None) -> str:
    """Pick the ragged pool-walk implementation (ops/ragged.py) for where
    the pool actually lives — the :func:`resolve_insert_impl` sniffing
    discipline, with the same pallas-iff-TPU outcome: ``"pallas"`` walks
    pages with the ragged Pallas grid, ``"lax"`` is the dense pool-walk
    fallback every CPU path (tier-1, interpret smokes) runs."""
    if platform is None:
        for a in arrays:
            sharding = getattr(a, "sharding", None)
            device_set = getattr(sharding, "device_set", None)
            if device_set:
                platform = next(iter(device_set)).platform
                break
    if platform is None:
        platform = jax.default_backend()
    return "pallas" if platform == "tpu" else "lax"


_apply_batch_jit = jax.jit(
    apply_batch, static_argnames=("insert_impl", "insert_loop_slots")
)


def doc_axis_mesh(x):
    """``(mesh, axis)`` when ``x`` is sharded on its leading (doc) axis over
    a mesh of more than one device, else None."""
    sharding = getattr(x, "sharding", None)
    if not isinstance(sharding, jax.sharding.NamedSharding):
        return None
    spec = sharding.spec
    if sharding.mesh.size == 1 or not spec or spec[0] is None:
        return None
    return sharding.mesh, spec[0]


def doc_sharded_apply(mesh, axis, *, insert_impl: str,
                      insert_loop_slots: int | None = None):
    """The apply for state sharded on ``mesh``'s doc ``axis``, called as
    ``fn(state, encoded_arrays)``: the whole per-doc apply under
    ``shard_map``.  A Mosaic kernel cannot be partitioned automatically,
    and the apply never reduces across docs, so each device applies its
    own rows."""
    from ..parallel.mesh_fused import mesh_fn

    def build():
        body = functools.partial(
            apply_batch, insert_impl=insert_impl,
            insert_loop_slots=insert_loop_slots,
        )
        spec = jax.sharding.PartitionSpec(axis)
        # check_vma=False: pallas_call results carry no varying-axes type;
        # every operand and result is doc-sharded anyway
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
            check_vma=False,
        ))

    return mesh_fn(
        mesh, ("apply_batch", axis, insert_impl, insert_loop_slots), build
    )


def apply_batch_jit(
    state: PackedDocs,
    encoded_arrays,
    *,
    insert_impl: str = "auto",
    insert_loop_slots: int | None = None,
) -> PackedDocs:
    """jit-compiled :func:`apply_batch`, resolving ``"auto"`` and the mesh
    at the jit boundary where input shardings are still observable."""
    if insert_impl == "auto":
        insert_impl = resolve_insert_impl(state.elem_id)
    statics = dict(insert_impl=insert_impl, insert_loop_slots=insert_loop_slots)
    placed = doc_axis_mesh(state.elem_id)
    if placed is None:
        fn, kwargs = _apply_batch_jit, statics
    else:
        fn, kwargs = doc_sharded_apply(*placed, **statics), {}
    if GLOBAL_DEVPROF.enabled:
        _note_dispatch("apply_batch", fn, (state, encoded_arrays), kwargs)
    return fn(state, encoded_arrays, **kwargs)
