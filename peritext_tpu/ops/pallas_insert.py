"""Pallas TPU kernel for the sequential RGA insert phase.

This is the hot loop of the whole framework (kernel.py phase 1, reference
``applyListInsert`` src/micromerge.ts:1187-1245).  The plain-XLA formulation
(`kernel._insert_loop` under vmap) carries the full ``(D, S)`` element-id and
character tensors through HBM on every one of the K insert steps; at the
BASELINE config-4 scale that is ~K x 4 x D x S bytes of traffic and the loop
is purely bandwidth bound.

The Pallas kernel instead blocks the doc axis onto the grid and keeps each
block's entire document state resident in VMEM across the WHOLE K-step loop:
HBM traffic drops from O(K * D * S) to O(D * (S + K)) — read the state and
the op streams once, write the state once.

Layout: everything is transposed so **documents ride the 128-wide lane
axis** and slots/ops ride sublanes.  That makes the per-step stream access a
dynamic *sublane* slice (cheap on TPU; dynamic lane indexing would force a
relayout every iteration), reductions over slots are sublane reductions, and
the RGA splice is a sublane rotate.  ``argmax`` is avoided (unsupported for
int32 in mosaic): the reference-element position comes from a masked integer
max, which is exact because element ids are unique so at most one slot
matches.

Semantics are identical to ``kernel._insert_loop`` (the CPU/differential
path); tests assert equality between the two in interpreter mode and
``kernel.apply_batch`` selects this kernel automatically on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _insert_body(ins_ref, ins_op, ins_char, pos, s_cap):
    """The per-insert step shared by the single-chunk and chunked kernels.

    Mask algebra exploits two invariants to keep per-step VPU work minimal:
    real element ids are never 0, and empty slots hold id 0.  So the
    reference match needs no ``pos < n`` guard (a non-HEAD ref can't match a
    padding slot), and the convergence skip needs none either — the first
    padding slot (id 0 < any op id) acts as a natural sentinel at exactly
    ``pos == n``, which is the append position.  The no-op case folds into
    the splice select by forcing the insert position to S (never matched by
    ``pos``), so the carry needs no final where.
    """

    def body(k, carry):
        elem, chars, n, ov = carry  # (S,L) (S,L) (1,L) (1,L)
        ref = ins_ref[pl.ds(k, 1), :]  # (1,L)
        op = ins_op[pl.ds(k, 1), :]
        ch = ins_char[pl.ds(k, 1), :]
        live = op != 0
        is_head = ref == 0

        # Locate the reference element.  Ids are unique, so the masked max
        # IS the match position; no match (or HEAD) yields -1.
        p = jnp.max(jnp.where(elem == ref, pos, -1), axis=0, keepdims=True)
        found = is_head | (p >= 0)
        p = jnp.where(is_head, jnp.int32(-1), p)
        ok = live & found & (n < s_cap)

        # Convergence skip (reference :1201-1208): first position right of
        # the reference whose element id is NOT greater than the new op id.
        q = jnp.min(
            jnp.where((pos > p) & (elem < op), pos, s_cap), axis=0, keepdims=True
        )
        q = jnp.where(ok, q, s_cap)  # no-op => splice position out of range

        lt, eq = pos < q, pos == q
        new_elem = jnp.where(lt, elem, jnp.where(eq, op, jnp.roll(elem, 1, axis=0)))
        new_char = jnp.where(lt, chars, jnp.where(eq, ch, jnp.roll(chars, 1, axis=0)))
        return (
            new_elem,
            new_char,
            n + ok.astype(jnp.int32),
            ov | ((live & ~found) | (live & (n >= s_cap))).astype(jnp.int32),
        )

    return body


def _insert_kernel(ins_ref, ins_op, ins_char, elem_in, char_in, n_in, ov_in,
                   elem_out, char_out, n_out, ov_out):
    """One grid cell: ALL K inserts for an (S, L) block of documents (the
    fast path when the whole op stream fits VMEM next to the state)."""
    s_cap, lanes = elem_in.shape
    pos = lax.broadcasted_iota(jnp.int32, (s_cap, lanes), 0)
    body = _insert_body(ins_ref, ins_op, ins_char, pos, s_cap)
    init = (elem_in[:], char_in[:], n_in[:], ov_in[:])
    elem, chars, n, ov = lax.fori_loop(0, ins_ref.shape[0], body, init)
    elem_out[:] = elem
    char_out[:] = chars
    n_out[:] = n
    ov_out[:] = ov


def _insert_kernel_chunked(ins_ref, ins_op, ins_char, elem_in, char_in, n_in,
                           ov_in, elem_out, char_out, n_out, ov_out):
    """One grid cell: one op-stream CHUNK of inserts for an (S, L) block of
    documents.  The grid is (doc blocks, stream chunks) with the stream axis
    sequential ("arbitrary"): the state OUTPUT blocks are indexed by doc
    only, so Pallas keeps them resident in VMEM across all chunk steps —
    chunk 0 seeds them from the inputs, later chunks continue in place.
    Chunking bounds VMEM by the chunk width instead of the whole K stream
    (BASELINE config-4 long docs overflow VMEM otherwise)."""
    s_cap, lanes = elem_in.shape
    pos = lax.broadcasted_iota(jnp.int32, (s_cap, lanes), 0)

    @pl.when(pl.program_id(1) == 0)
    def _seed():
        elem_out[:] = elem_in[:]
        char_out[:] = char_in[:]
        n_out[:] = n_in[:]
        ov_out[:] = ov_in[:]

    body = _insert_body(ins_ref, ins_op, ins_char, pos, s_cap)
    init = (elem_out[:], char_out[:], n_out[:], ov_out[:])
    elem, chars, n, ov = lax.fori_loop(0, ins_ref.shape[0], body, init)
    elem_out[:] = elem
    char_out[:] = chars
    n_out[:] = n
    ov_out[:] = ov


@functools.partial(jax.jit, static_argnames=("interpret", "loop_slots"))
def insert_batch_pallas(elem_id, char, num_slots, overflow,
                        ins_ref, ins_op, ins_char, *, interpret: bool = False,
                        loop_slots: int | None = None):
    """Pallas-accelerated equivalent of ``vmap(kernel._insert_loop)``.

    Args mirror the lax path: (D,S) elem_id/char, (D,) num_slots, (D,) bool
    overflow, (D,K) insert streams.  Returns the same tuple of updated
    arrays.  The doc axis is padded up to a multiple of 128 lanes (padded
    docs carry op id 0 == not live, so they are untouched no-ops).

    ``loop_slots``: static upper bound on ``max(num_slots) + live inserts``
    known by the caller (e.g. K for a batch built from empty docs).  The
    K-step loop then runs on only the first ``loop_slots`` slot rows — the
    splice can never move an element across that boundary when the bound
    holds — roughly halving VPU work for fresh batches.  If the bound is
    violated the kernel flags ``overflow`` (the API's scalar-fallback path),
    so a bad bound degrades performance, never correctness.
    """
    d, s_cap = elem_id.shape
    k = ins_ref.shape[1]
    if k == 0:  # mark/delete-only batch: the insert phase is a no-op
        return elem_id, char, num_slots, overflow
    s_loop = effective_loop_slots(s_cap, loop_slots)
    kc = _stream_chunk(s_loop, k)
    kp = -(-k // kc) * kc  # stream padded to whole chunks (op id 0 = no-op)
    dp = -(-d // LANES) * LANES
    pad = dp - d
    chunked = kp != kc  # stream larger than one VMEM-resident chunk

    def t(x, extra_rows=0):  # (D, W) -> (W + extra, Dp)
        return jnp.pad(x.T.astype(jnp.int32), ((0, extra_rows), (0, pad)))

    if chunked:
        grid = (dp // LANES, kp // kc)
        index = lambda i, j: (0, i)  # noqa: E731
        stream_index = lambda i, j: (j, i)  # noqa: E731
        kernel = _insert_kernel_chunked
        params = dict(
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT,
            )
        )
    else:
        grid = (dp // LANES,)
        index = lambda i: (0, i)  # noqa: E731
        stream_index = index
        kernel = _insert_kernel
        params = dict(
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)
        )

    state_col = lambda width: pl.BlockSpec(  # noqa: E731
        (width, LANES), index, memory_space=pltpu.VMEM
    )
    stream_col = pl.BlockSpec((kc, LANES), stream_index, memory_space=pltpu.VMEM)

    elem, chars, n, ov = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            stream_col, stream_col, stream_col,
            state_col(s_loop), state_col(s_loop), state_col(1), state_col(1),
        ],
        out_specs=[state_col(s_loop), state_col(s_loop), state_col(1), state_col(1)],
        out_shape=[
            jax.ShapeDtypeStruct((s_loop, dp), jnp.int32),
            jax.ShapeDtypeStruct((s_loop, dp), jnp.int32),
            jax.ShapeDtypeStruct((1, dp), jnp.int32),
            jax.ShapeDtypeStruct((1, dp), jnp.int32),
        ],
        interpret=interpret,
        **params,
    )(
        t(ins_ref, kp - k), t(ins_op, kp - k), t(ins_char, kp - k),
        t(elem_id[:, :s_loop]), t(char[:, :s_loop]),
        t(num_slots.reshape(d, 1)), t(overflow.reshape(d, 1)),
    )

    elem_new, char_new = elem[:, :d].T, chars[:, :d].T
    if s_loop < s_cap:
        # Slots past the loop window are untouched by construction.
        elem_new = jnp.concatenate([elem_new, elem_id[:, s_loop:]], axis=1)
        char_new = jnp.concatenate([char_new, char[:, s_loop:]], axis=1)
    return elem_new, char_new, n[0, :d], ov[0, :d] != 0


#: VMEM ceiling requested from the compiler (v5e has 128M per core; the
#: default scoped limit is only 16M) and the occupancy budget this module
#: plans against.  The budget leaves a wide margin under the ceiling.
_VMEM_LIMIT = 100 * 1024 * 1024
_VMEM_BUDGET = 72 * 1024 * 1024


def effective_loop_slots(s_cap: int, loop_slots: int | None) -> int:
    """The slot-window height the kernel will actually use."""
    if loop_slots is None:
        return s_cap
    return max(8, min(-(-loop_slots // 8) * 8, s_cap))


def _state_bytes(s_loop: int) -> int:
    """Resident bytes attributable to the (elem, char) state at one grid
    cell, counted conservatively at 6 copies of the 2-array state (pipeline
    double-buffered inputs, revisited outputs, fori_loop carry, and the
    chunk-0 seed copy — observed occupancy on v5e is ~6x)."""
    return 6 * (2 * s_loop * LANES * 4)


def _stream_bytes(kc: int) -> int:
    """Resident bytes for the 3 op-stream blocks (double-buffered inputs)."""
    return 2 * (3 * kc * LANES * 4)


def _stream_chunk(s_loop: int, k: int) -> int:
    """Op-stream chunk width: the whole stream when it fits the VMEM budget
    next to the resident state blocks (the fast single-chunk kernel, no
    padding); otherwise the largest multiple-of-8 chunk that fits."""
    room = max(_VMEM_BUDGET - _state_bytes(s_loop), 0)
    kc = room // (2 * 3 * LANES * 4)
    if kc >= k:
        return k
    return max(8, (kc // 8) * 8)


def pallas_vmem_ok(s_loop: int) -> bool:
    """Whether the kernel's resident state for this slot window fits VMEM at
    all (the op-stream width never matters: chunking bounds it to whatever
    room remains, down to the minimum chunk of 8).  When False, callers
    should use the lax path, which streams state through HBM and has no
    such limit."""
    return _state_bytes(s_loop) + _stream_bytes(8) <= _VMEM_BUDGET
