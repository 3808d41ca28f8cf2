"""Ragged Pallas insert kernel: grid over docs, page table scalar-prefetched.

The padded kernel (ops/pallas_insert.py) blocks a dense ``(D, S)`` state
onto the grid — every doc pays the widest doc's slot axis.  This kernel is
its ragged twin over the page pool: the grid is one cell per BATCH DOC, the
doc's page table rides in scalar-prefetch memory (so the gather targets are
known before the cell body runs), and each cell

1. DMA-gathers the doc's TRUE pages from the pool (HBM refs) into a
   ``(max_doc_pages, P)`` VMEM scratch window,
2. runs the doc's TRUE insert count through the RGA insert loop on that
   window — the same masked-reduction formulation as pallas_insert
   (argmax is unsupported by Mosaic; min/max over ``where`` masks), with
   the padded path's roll-by-one spelled as a lane shift whose lane-0
   values come from the previous page row,
3. DMA-scatters the pages back.

A step touches only the ``(8, P)`` blocks its work needs, never the whole
window: the reference is looked for first in the block of the previous
step's insert (a typing run's next reference) and only then in every
block below the doc's count, the convergence skip walks forward from the
reference until it finds a position, and the splice rewrites the blocks
from the insert position to the count.  A book-length doc (1,425 pages)
would otherwise pay its whole window three times per insert.  The insert
streams ride SMEM a ``STREAM_CHUNK`` at a time, one chunk per grid step
along a second grid axis, with the window and the doc's count carried
across them: a whole book-length stream does not fit SMEM.

The splice is paid once per typing run, not once per char.  The kernel
holds a pending run: its window position, its length ``k`` and its
entries, one lane each in a small VMEM scratch.  An insert whose
reference is the run's last entry lands right after it (the position
after the run's first entry holds a smaller id, or is the end, and a
reference's id is below its op's), so it only appends a lane.  Any other
insert first flushes the run, shifting the tail right by ``k`` lanes in
one pass, then looks up its reference and opens a new run.  The doc's
count includes the pending entries, so the overflow point is unchanged;
a run is flushed at the end of every stream chunk.

``input_output_aliases`` pins the pool in place (indices count flattened
leaves INCLUDING the scalar-prefetch operands — the megablox convention).
Unowned pool pages are untouched by construction: no page table points at
them.  The per-doc ``(max_doc_pages, P)`` window is deliberately the unit
the v5e-8 mesh roadmap item shards.

Loop bounds (pages gathered, inserts applied) come from the prefetched
scalar planes, so one compiled program serves every doc mix — the whole
point of the ragged layout (see ops/ragged.py; the recompile sentinel
pins it).

CPU runs this kernel under ``interpret=True`` only (differential tests);
the production CPU path is the lax pool walk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_insert import LANES

#: reuse the padded kernel's "no position" sentinel discipline; far above
#: any slot position, far below int32 max so +1 arithmetic stays safe
#: (a plain int: Pallas kernels may not close over device constants)
_INF = 2**30

#: VMEM ceiling / working budget, matching ops/pallas_insert.py
_VMEM_LIMIT = 100 * 1024 * 1024
_VMEM_BUDGET = 72 * 1024 * 1024

#: window rows per block: one (8, 128) int32 tile, the unit every insert
#: step loads, compares and stores
_ROWS = 8

#: insert-stream entries per grid step: the streams ride SMEM (1 MiB on a
#: v5e) a chunk at a time, double-buffered, three columns; a whole
#: book-length stream (182K entries) would not fit
STREAM_CHUNK = 4096


def window_rows(gmax: int) -> int:
    """Rows of the per-doc VMEM window: ``gmax`` rounded up to a block."""
    return -(-gmax // _ROWS) * _ROWS


def ragged_vmem_ok(gmax: int, page_size: int) -> bool:
    """Whether one grid cell's residents (two window scratches) fit the
    VMEM working budget; the streams ride SMEM."""
    return 2 * window_rows(gmax) * page_size * 4 <= _VMEM_BUDGET


def _ragged_insert_kernel(
    # scalar prefetch
    page_table_ref,  # (B * Gmax,) pool page per (doc, doc-page) — 0 = null
    page_count_ref,  # (B,) true page count per doc
    ins_count_ref,   # (B,) true insert count per doc
    n_ref,           # (B,) slot count before the round
    ov_ref,          # (B,) overflow flag before the round (int32)
    cap_ref,         # (B,) allocated slot coverage
    # inputs
    pool_elem_hbm,   # (N, P) HBM — aliased with out
    pool_char_hbm,   # (N, P) HBM — aliased with out
    ins_ref_ref,     # (1, C) SMEM chunk of (B, 1, KI)
    ins_op_ref,      # (1, C) SMEM chunk of (B, 1, KI)
    ins_char_ref,    # (1, C) SMEM chunk of (B, 1, KI)
    # outputs
    out_elem_hbm,    # (N, P) HBM — IS pool_elem_hbm (aliased)
    out_char_hbm,    # (N, P) HBM — IS pool_char_hbm (aliased)
    n_out_ref,       # (1, 1) VMEM block of (B, 1, 1)
    ov_out_ref,      # (1, 1) VMEM block of (B, 1, 1)
    # scratch
    elem_scr,        # VMEM (R, P), R = Gmax rounded up to _ROWS
    char_scr,        # VMEM (R, P)
    pend_op_scr,     # VMEM (_ROWS, P): the pending run's op ids, lane j
    pend_char_scr,   # VMEM (_ROWS, P): its chars (every row alike)
    carry_scr,       # SMEM (3,): n, ov, hint between stream chunks
    dma_sem,
    *,
    gmax: int,
):
    i = pl.program_id(0)
    c = pl.program_id(1)
    g = page_count_ref[i]
    rows, p = elem_scr.shape
    chunk = ins_op_ref.shape[1]
    bsz = _ROWS * p
    run_cap = min(LANES, p)  # a flush shifts the tail by at most one row

    def _copy_pages(pairs, to_window):
        """DMA each of the doc's true pages between the pool and its
        window row, for each (pool, window) pair."""
        def one(j, _):
            pg = page_table_ref[i * gmax + j]
            for hbm, scr in pairs:
                page, row = hbm.at[pl.ds(pg, 1), :], scr.at[pl.ds(j, 1), :]
                cp = pltpu.make_async_copy(
                    *((page, row) if to_window else (row, page)), dma_sem)
                cp.start()
                cp.wait()
            return 0

        lax.fori_loop(0, g, one, 0)

    @pl.when(c == 0)
    def _gather():
        # window rows past the doc's pages must read as zero: they would
        # carry the previous doc's pages otherwise
        elem_scr[...] = jnp.zeros((rows, p), jnp.int32)
        char_scr[...] = jnp.zeros((rows, p), jnp.int32)
        _copy_pages(((pool_elem_hbm, elem_scr), (pool_char_hbm, char_scr)), True)
        carry_scr[0] = n_ref[i]
        carry_scr[1] = ov_ref[i]
        carry_scr[2] = jnp.int32(0)

    cap = cap_ref[i]
    lane = lax.broadcasted_iota(jnp.int32, (_ROWS, p), 1)
    row = lax.broadcasted_iota(jnp.int32, (_ROWS, p), 0)
    offset = row * p + lane  # position within a block

    def load(scr, b):
        return scr[pl.ds(pl.multiple_of(b * _ROWS, _ROWS), _ROWS), :]

    def flush(n, hint, q0, k):
        """Write the pending run into the window: positions q0.. shift right
        by k, and q0 .. q0+k-1 take the run's entries.  ``n`` counts the
        run, so blocks q0 // bsz .. (n-1) // bsz change; they are rewritten
        from the last down, so each reads its predecessor's last k lanes
        before that block is rewritten.  Returns the hint: the run's last
        entry."""

        @pl.when(k > 0)
        def _():
            lo = q0 // bsz
            hi = (n - 1) // bsz + 1
            kr = lax.rem(k, p)  # a run of a whole row rolls its lanes by 0
            runs = [(scr, pltpu.roll(pend[...], lax.rem(q0, p), 1))
                    for scr, pend in ((elem_scr, pend_op_scr),
                                      (char_scr, pend_char_scr))]

            def block(t, _):
                b = hi - 1 - t
                pos = b * bsz + offset
                for scr, run in runs:
                    cur = load(scr, b)
                    prev = jnp.where(b > 0, load(scr, jnp.maximum(b - 1, 0)), 0)
                    rolled = pltpu.roll(cur, kr, 1)
                    # lanes < k take the row above's last k lanes; row 0
                    # those of the previous block's last row
                    carry_in = jnp.where(
                        row == 0,
                        jnp.roll(pltpu.roll(prev, kr, 1), 1, axis=0),
                        jnp.roll(rolled, 1, axis=0))
                    shifted = jnp.where(lane < k, carry_in, rolled)
                    scr[pl.ds(pl.multiple_of(b * _ROWS, _ROWS), _ROWS), :] = jnp.where(
                        pos < q0, cur, jnp.where(pos < q0 + k, run, shifted))
                return 0

            lax.fori_loop(0, hi - lo, block, 0)

        return jnp.where(k > 0, q0 + k - 1, hint)

    def _body(t, carry):
        n, ov, hint, q0, k, last = carry
        ref = ins_ref_ref[0, t]
        op = ins_op_ref[0, t]
        ch = ins_char_ref[0, t]
        live = op != 0

        def extend():
            # a typing run's next char: its reference is the run's last
            # entry, and the first position right of that holds a smaller
            # id than the run's first op (the skip stopped there) or is the
            # end, so the op lands right after its reference.  The id
            # compare keeps that exact on any input: a reference's id is
            # below its op's (counter-major ids, ops/packed.py).
            for pend, val in ((pend_op_scr, op), (pend_char_scr, ch)):
                pend[...] = jnp.where(lane == k, val, pend[...])
            return n + 1, ov, hint, q0, k + 1, op

        def place():
            hint_f = flush(n, hint, q0, k)
            is_head = ref == 0
            used = (n + bsz - 1) // bsz  # blocks holding positions < n

            def match(b):
                pos = b * bsz + offset
                return jnp.where((load(elem_scr, b) == ref) & (pos < n), pos, _INF)

            # the reference is most often near the previous insert: look in
            # that block first, then in every used one (ids are unique, so
            # the masked min IS the padded argmax)
            near = jnp.min(match(jnp.minimum(hint_f // bsz, jnp.maximum(used - 1, 0))))

            def scan():
                acc = lax.fori_loop(
                    0, used, lambda b, a: jnp.minimum(a, match(b)),
                    jnp.full((_ROWS, p), _INF, jnp.int32),
                )
                return jnp.min(acc)

            pmin = lax.cond(is_head | (near < _INF), lambda: near, scan)
            found = is_head | (pmin < _INF)
            pref = jnp.where(is_head, jnp.int32(-1), pmin)
            ok = live & found & (n < cap)

            # convergence skip: first position right of the reference whose
            # element id is NOT greater than the inserting op's id, else n
            def skip_more(st):
                b, q = st
                return (q == _INF) & (b < used)

            def skip_block(st):
                b, _ = st
                pos = b * bsz + offset
                cand = (pos > pref) & (pos < n) & (load(elem_scr, b) < op)
                return b + 1, jnp.min(jnp.where(cand, pos, _INF))

            first = jnp.where(ok, (pref + 1) // bsz, used)
            _, qmin = lax.while_loop(skip_more, skip_block, (first, jnp.int32(_INF)))
            q = jnp.minimum(qmin, n)

            # the op opens a new run at q; its splice waits for the run's end
            pend_op_scr[...] = jnp.full((_ROWS, p), op, jnp.int32)
            pend_char_scr[...] = jnp.full((_ROWS, p), ch, jnp.int32)
            return (
                jnp.where(ok, n + 1, n),
                ov | ((live & ~found) | (live & (n >= cap))).astype(jnp.int32),
                jnp.where(ok, q, hint_f),
                q,
                ok.astype(jnp.int32),
                op,
            )

        fast = live & (k > 0) & (ref == last) & (op > last) & (n < cap) & (k < run_cap)
        return lax.cond(fast, extend, place)

    todo = jnp.clip(ins_count_ref[i] - c * chunk, 0, chunk)
    zero = jnp.int32(0)
    n1, ov1, hint1, q0, k, _ = lax.fori_loop(
        0, todo, _body,
        (carry_scr[0], carry_scr[1], carry_scr[2], zero, zero, zero))
    # a run never outlives its chunk: the window holds every entry between
    # grid steps, and before the scatter
    hint1 = flush(n1, hint1, q0, k)
    carry_scr[0] = n1
    carry_scr[1] = ov1
    carry_scr[2] = hint1
    n_out_ref[...] = jnp.full((1, 1), n1, jnp.int32)
    ov_out_ref[...] = jnp.full((1, 1), ov1, jnp.int32)

    @pl.when(c == pl.num_programs(1) - 1)
    def _scatter():
        _copy_pages(((out_elem_hbm, elem_scr), (out_char_hbm, char_scr)), False)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ragged_insert_pallas(
    pool_elem, pool_char, page_table, page_count, ins_counts,
    n0, ov0, cap, ins_ref, ins_op, ins_char, *, interpret: bool = False,
):
    """Ragged insert phase over the pool (module doc).  ``n0``/``ov0``/
    ``cap``/streams carry plain (B,)/(B, KI) batch axes — no inert row; the
    kernel never reduces across docs.  Returns ``(pool_elem, pool_char,
    n, ov)`` with ``ov`` as bool."""
    b, ki = ins_op.shape
    n, p = pool_elem.shape
    gmax = page_table.shape[1]
    if p % LANES and not interpret:
        raise ValueError(
            f"page size {p} is not a multiple of {LANES}: the chip cannot "
            f"DMA a page out of the lane-tiled pool"
        )
    # a stream longer than one chunk pads to whole chunks (entries past a
    # doc's count are never read)
    chunk = ki if ki <= STREAM_CHUNK else STREAM_CHUNK
    chunks = -(-ki // chunk)
    pad = chunks * chunk - ki

    def stream_operand(x):
        return jnp.pad(x, ((0, 0), (0, pad)))[:, None, :]

    # Mosaic requires a block's last two dims to be (8, 128)-aligned or
    # equal to the array's, so a per-doc block of a (B, w) operand is
    # illegal: the streams ride as (B, 1, KI) in SMEM (read one scalar per
    # insert step), a chunk per grid step, the per-doc outputs as
    # (B, 1, 1), and the per-doc scalars ride scalar prefetch with the page
    # table — flattened, since SMEM pads a 2-D array's last dim to 128 words.
    stream = pl.BlockSpec(
        (None, 1, chunk), lambda i, c, *_: (i, 0, c), memory_space=pltpu.SMEM
    )
    scalar_out = pl.BlockSpec(
        (None, 1, 1), lambda i, c, *_: (i, 0, 0), memory_space=pltpu.VMEM
    )
    pool = pl.BlockSpec(memory_space=pltpu.HBM)
    rows = window_rows(gmax)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(b, chunks),
        in_specs=[pool, pool, stream, stream, stream],
        out_specs=[pool, pool, scalar_out, scalar_out],
        scratch_shapes=[
            pltpu.VMEM((rows, p), jnp.int32),
            pltpu.VMEM((rows, p), jnp.int32),
            pltpu.VMEM((_ROWS, p), jnp.int32),
            pltpu.VMEM((_ROWS, p), jnp.int32),
            pltpu.SMEM((3,), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    out_elem, out_char, n1, ov1 = pl.pallas_call(
        functools.partial(_ragged_insert_kernel, gmax=gmax),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, p), pool_elem.dtype),
            jax.ShapeDtypeStruct((n, p), pool_char.dtype),
            jax.ShapeDtypeStruct((b, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, 1), jnp.int32),
        ],
        # flattened-leaf indices, scalar-prefetch operands included
        # (six prefetch planes, then pool_elem=6, pool_char=7)
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(
        page_table.reshape(-1), page_count, ins_counts,
        n0.astype(jnp.int32), ov0.astype(jnp.int32), cap.astype(jnp.int32),
        pool_elem, pool_char,
        stream_operand(ins_ref), stream_operand(ins_op), stream_operand(ins_char),
    )
    return out_elem, out_char, n1[:, 0, 0], ov1[:, 0, 0] != 0
