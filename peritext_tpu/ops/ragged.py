"""Ragged paged apply: one compiled program over the whole page pool.

Every other device path buckets — the padded apply pads all docs to the
slot capacity, the paged apply groups docs by power-of-two page count and
pads each group's row axis, and both pay a log2 compile ladder plus padded
FLOPs for the privilege.  This module is the Ragged Paged Attention answer
(PAPERS.md): the causal-insert round runs DIRECTLY against the ``(N, P)``
page pool, consuming the per-doc page tables ragged — true op counts and
true page counts arrive as *data* (plan planes + traced loop bounds), so
the compiled shape depends only on the pool size and the round's stream
staging widths.  A mixed drain of tweets, essays and book-scale docs is
ONE executable (tests/test_recompile_sentinel.py pins it), and padded
slots cost zero loop trips.

Two implementations behind ``resolve_ragged_impl`` (ops/kernel.py):

* ``"lax"`` — the pool-walk fallback every CPU path runs (tier-1, smoke
  ladders).  Per insert step it operates on the whole ``(N, P)`` pool at
  once: per-doc reductions become segment reductions over the ``owner``
  plane (``.at[owner].min/max``), and the RGA splice's roll becomes a
  lane shift whose lane-0 value comes through ``prev_page``.  One
  ``lax.fori_loop`` with a TRACED bound = the round's max true insert
  count.
* ``"pallas"`` / ``"pallas_interpret"`` — the TPU kernel: grid over docs
  with the page table scalar-prefetched, each doc's true pages gathered
  once into a VMEM window, its true ops applied, pages written back
  (``input_output_aliases`` keeps the pool in place).  The per-doc window
  ``(max_doc_pages, P)`` is deliberately the unit the v5e-8 mesh roadmap
  item will shard.

Byte-equality with the padded oracle holds phase by phase: the insert math
is kernel._insert_loop with positions relabeled through ``pos_base``
(element ids are unique, so the segment min over matches IS the padded
argmax), the delete/mark/register phases ARE kernel._post_insert_doc /
_apply_map_doc vmapped over the dense aux rows, with the target-exists
and already-deleted masks precomputed by sorting each doc's pages and
tombstones (O(n log n), where the padded path's pairwise compares would
cost 1.2e10 pairs for a book-length doc).  tests/test_ragged.py pins the
equality across every workload family.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import GLOBAL_DEVPROF, note_jit_dispatch as _note_dispatch
from .kernel import (
    PAGED_AUX_FIELDS,
    _apply_map_doc,
    _post_insert_doc,
    resolve_ragged_impl,
    resolve_state_donation,
)
from .packed import PackedDocs

#: sentinel "no position" for the segment-min reductions (any real slot
#: position is far below it; int32 max would overflow the +1 in minimum)
_INF = 2**30

_NUM_SLOTS = PAGED_AUX_FIELDS.index("num_slots")
_OVERFLOW = PAGED_AUX_FIELDS.index("overflow")


def _pad_row(x):
    """Append one all-zero row — the inert segment every unowned pool page
    (owner == num_rows) reduces into and gathers from."""
    return jnp.concatenate(
        [x, jnp.zeros((1,) + x.shape[1:], x.dtype)], axis=0
    )


def _ragged_insert_lax(pool_elem, pool_char, owner, pos_base, prev_page,
                       n0, ov0, cap, ins_ref, ins_op, ins_char, k_ins):
    """Pool-walk insert phase: kernel._insert_loop over the whole pool.

    All per-doc operands carry one trailing inert row (index B = the owner
    sentinel); ``cap`` is each doc's TRUE allocated slot coverage
    (page_count * P) — by the ensure_rows discipline it covers every
    admitted insert up to the slot capacity, so the overflow point is the
    padded oracle's."""
    p = pool_elem.shape[1]
    bp1 = n0.shape[0]
    lane = jnp.arange(p, dtype=jnp.int32)
    pos = pos_base[:, None] + lane[None, :]  # (N, P) global slot positions

    def body(k, carry):
        elem, chars, n, ov = carry
        ref = lax.dynamic_index_in_dim(ins_ref, k, axis=1, keepdims=False)
        op = lax.dynamic_index_in_dim(ins_op, k, axis=1, keepdims=False)
        ch = lax.dynamic_index_in_dim(ins_char, k, axis=1, keepdims=False)
        live = op != 0
        is_head = ref == 0
        n_pg = n[owner]  # (N,) owner doc's current count, per page
        # reference match: ids are unique, so the segment MIN over matching
        # positions is exactly the padded path's argmax(match)
        match = (elem == ref[owner][:, None]) & (pos < n_pg[:, None])
        page_min = jnp.min(jnp.where(match, pos, _INF), axis=1)
        pmin = jnp.full((bp1,), _INF, jnp.int32).at[owner].min(page_min)
        found = is_head | (pmin < _INF)
        pref = jnp.where(is_head, jnp.int32(-1), pmin)
        ok = live & found & (n < cap)
        # convergence skip: first position right of the reference whose
        # element id is NOT greater than the inserting op's id
        candidate = (
            (pos > pref[owner][:, None])
            & (pos < n_pg[:, None])
            & (elem < op[owner][:, None])
        )
        page_q = jnp.min(jnp.where(candidate, pos, _INF), axis=1)
        q = jnp.minimum(
            jnp.full((bp1,), _INF, jnp.int32).at[owner].min(page_q), n
        )
        q_pg = q[owner][:, None]
        # the splice's roll-by-one, in page space: lane 0 takes the LAST
        # lane of the doc's previous page (first pages read the null page's
        # zero, which the select below never keeps: q >= 0 always)
        shifted_elem = jnp.concatenate(
            [elem[prev_page, p - 1][:, None], elem[:, :-1]], axis=1
        )
        shifted_char = jnp.concatenate(
            [chars[prev_page, p - 1][:, None], chars[:, :-1]], axis=1
        )
        new_elem = jnp.where(
            pos < q_pg, elem,
            jnp.where(pos == q_pg, op[owner][:, None], shifted_elem),
        )
        new_char = jnp.where(
            pos < q_pg, chars,
            jnp.where(pos == q_pg, ch[owner][:, None], shifted_char),
        )
        apply_pg = ok[owner][:, None]
        return (
            jnp.where(apply_pg, new_elem, elem),
            jnp.where(apply_pg, new_char, chars),
            jnp.where(ok, n + 1, n),
            ov | (live & ~found) | (live & (n >= cap)),
        )

    return lax.fori_loop(0, k_ins, body, (pool_elem, pool_char, n0, ov0))


def _member(sorted_values, x):
    """Whether each of ``x`` occurs in ``sorted_values`` (both 1-D)."""
    at = jnp.searchsorted(sorted_values, x)
    return sorted_values[jnp.minimum(at, sorted_values.shape[0] - 1)] == x


def _delete_masks(doc_elems, tomb_id, del_target):
    """One doc's delete-phase masks, by sorting instead of kernel.
    _post_insert_doc's pairwise compares (which cost KD x S and KD x KD a
    doc: 1.2e10 pairs for a book-length doc's 77K deletes):

    * ``exists`` (KD,) — the target is among the doc's elements,
    * ``skip`` (KD,) — the target is already tombstoned, or an earlier
      entry of the stream deletes it too.

    Dead (zero) targets come out arbitrary; the caller's ``live`` mask
    gates them, as it gates the pairwise forms."""
    exists = _member(jnp.sort(doc_elems), del_target)
    order = jnp.argsort(del_target, stable=True)  # equal targets keep stream order
    ranked = del_target[order]
    repeat = jnp.concatenate([jnp.zeros((1,), bool), ranked[1:] == ranked[:-1]])
    dup_earlier = jnp.zeros(del_target.shape, bool).at[order].set(repeat)
    return exists, _member(jnp.sort(tomb_id), del_target) | dup_earlier


def apply_batch_ragged(
    pool_elem,
    pool_char,
    aux,  # tuple of dense (D, ...) arrays in PAGED_AUX_FIELDS order
    row_idx,  # (B,) batch doc rows (every row real — no padding axis)
    owner,  # (N,) batch-local owner per pool page (B = unowned)
    pos_base,  # (N,) first slot position of each page within its doc
    prev_page,  # (N,) preceding page of the same doc (0 = null page)
    page_count,  # (B,) TRUE allocated pages per row
    page_table,  # (B, max_doc_pages) pool page per doc-page (pallas plane)
    encoded_arrays,  # the apply_batch stream tuple with (B, ...) doc axes
    ins_counts,  # (B,) int32 TRUE per-doc insert counts (data, not shape)
    *,
    ragged_impl: str = "auto",
):
    """The ragged twin of kernel.apply_batch_paged: apply one round's
    streams directly against pool pages, no gather/scatter, no buckets.
    Returns ``(pool_elem, pool_char, aux)`` updated.

    The compiled shape is (pool, streams, plan planes) only — per-doc op
    and page counts are data (the lax walk trips its fori loops on the
    batch maxima as TRACED bounds; the pallas grid cells trip on each
    doc's own count), so every round of a session (and every doc mix
    within a round) reuses ONE executable."""
    if len(encoded_arrays) == 6:
        ins_ref, ins_op, ins_char, del_target, marks, mark_count = encoded_arrays
        maps, map_count = None, None
    else:
        (ins_ref, ins_op, ins_char, del_target, marks, mark_count,
         maps, map_count) = encoded_arrays
    impl = ragged_impl
    if impl == "auto":
        # backend-default sniff only: under the jit wrappers "auto" was
        # already resolved against the REAL pool array at the boundary
        # (apply_batch_ragged_jit); in here the pool is a tracer whose
        # sharding is unobservable, so the array adds nothing
        impl = resolve_ragged_impl()

    p = pool_elem.shape[1]
    ins_counts = jnp.asarray(ins_counts, jnp.int32)
    n0 = aux[_NUM_SLOTS][row_idx]
    ov0 = aux[_OVERFLOW][row_idx]
    cap = page_count.astype(jnp.int32) * jnp.int32(p)

    if impl in ("pallas", "pallas_interpret"):
        from .ragged_pallas import ragged_vmem_ok

        if not ragged_vmem_ok(page_table.shape[1], p):
            impl = "lax"
    if impl in ("pallas", "pallas_interpret"):
        from .ragged_pallas import ragged_insert_pallas

        pool_elem, pool_char, n1, ov1 = ragged_insert_pallas(
            pool_elem, pool_char, page_table, page_count, ins_counts,
            n0, ov0, cap, ins_ref, ins_op, ins_char,
            interpret=(impl == "pallas_interpret"),
        )
    elif impl == "lax":
        k_ins = jnp.max(ins_counts, initial=0)
        pool_elem, pool_char, n_pad, ov_pad = _ragged_insert_lax(
            pool_elem, pool_char, owner, pos_base, prev_page,
            _pad_row(n0), _pad_row(ov0), _pad_row(cap),
            _pad_row(ins_ref), _pad_row(ins_op), _pad_row(ins_char), k_ins,
        )
        n1, ov1 = n_pad[:-1], ov_pad[:-1]
    else:
        raise ValueError(f"unknown ragged_impl: {ragged_impl!r}")

    # phases 2-4 run on the dense aux rows exactly as the padded path does
    # (they never touch the element planes: the one elem read — the delete
    # target-exists test — is made here against each doc's pool pages)
    sub = {f: a[row_idx] for f, a in zip(PAGED_AUX_FIELDS, aux)}
    b = ins_ref.shape[0]
    doc_elems = pool_elem[page_table].reshape(b, -1)  # null page 0 pads
    exists, skip = jax.vmap(_delete_masks)(doc_elems, sub["tomb_id"], del_target)
    dummy = jnp.zeros((b, 1), jnp.int32)
    state = PackedDocs(elem_id=dummy, char=dummy, **sub)
    state = state._replace(num_slots=n1, overflow=ov1)
    state = jax.vmap(
        lambda s, d, m, mc, ex, sk: _post_insert_doc(s, d, m, mc, exists=ex, skip=sk)
    )(state, del_target, marks, mark_count, exists, skip)
    if maps is not None:
        state = jax.vmap(_apply_map_doc)(
            state, maps["p_obj"], maps["p_key"], maps["p_op"],
            maps["p_kind"], maps["p_val"], map_count,
        )
    aux = tuple(
        a.at[row_idx].set(getattr(state, f))
        for f, a in zip(PAGED_AUX_FIELDS, aux)
    )
    return pool_elem, pool_char, aux


_apply_batch_ragged_jit = jax.jit(
    apply_batch_ragged, static_argnames=("ragged_impl",),
    donate_argnums=(0, 1, 2),
)
_apply_batch_ragged_jit_nodonate = jax.jit(
    apply_batch_ragged, static_argnames=("ragged_impl",),
)


def apply_batch_ragged_jit(pool_elem, pool_char, aux, row_idx, owner,
                           pos_base, prev_page, page_count, page_table,
                           encoded_arrays, ins_counts, *,
                           ragged_impl: str = "auto",
                           donate: bool | None = None):
    """jit-compiled :func:`apply_batch_ragged`; the pool operands are
    donated per kernel.resolve_state_donation (or the explicit ``donate``)
    — rebind to the returned triple either way.  ``"auto"`` resolves at
    the boundary from the pool arrays' placement."""
    if ragged_impl == "auto":
        ragged_impl = resolve_ragged_impl(pool_elem)
    if donate is None:
        donate = resolve_state_donation(pool_elem)
    fn = _apply_batch_ragged_jit if donate else _apply_batch_ragged_jit_nodonate
    args = (pool_elem, pool_char, aux, row_idx, owner, pos_base, prev_page,
            page_count, page_table, encoded_arrays, ins_counts)
    if GLOBAL_DEVPROF.enabled:
        _note_dispatch("apply_batch_ragged", fn, args,
                       dict(ragged_impl=ragged_impl))
    return fn(*args, ragged_impl=ragged_impl)


def plan_arrays(plan):
    """Device operands of a store/ragged.RaggedPlan — the static-per-epoch
    plane set a session uploads once per allocation epoch, not per round."""
    return (
        jnp.asarray(plan.row_idx),
        jnp.asarray(plan.owner),
        jnp.asarray(plan.pos_base),
        jnp.asarray(plan.prev_page),
        jnp.asarray(plan.page_count),
        jnp.asarray(plan.page_table),
    )


def stream_counts(enc, rows=None):
    """Host-side int32 TRUE per-doc insert counts of one round's staging
    buffers (restricted to ``rows`` when given): the loop trip counts the
    ragged program runs — the quantity that makes padded stream slots free.

    Streaming round buffers carry the counts directly; EncodedBatch does
    not, so fall back to counting live stream entries (a live insert has a
    nonzero op id)."""
    import numpy as np

    ins = getattr(enc, "ins_count", None)
    if ins is not None:
        ins = np.asarray(ins, np.int32)
    else:
        ins = np.count_nonzero(np.asarray(enc.ins_op), axis=1).astype(np.int32)
    return ins if rows is None else ins[rows]
