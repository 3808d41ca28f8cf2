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


def ragged_vmem_ok(gmax: int, page_size: int, k_ins: int) -> bool:
    """Whether one grid cell's residents (two (gmax, P) scratch windows +
    the doc's stream block) fit the VMEM working budget."""
    scratch = 2 * gmax * page_size * 4
    stream = 3 * k_ins * 4
    return scratch + stream <= _VMEM_BUDGET


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
    ins_ref_ref,     # (1, KI) SMEM block of (B, 1, KI)
    ins_op_ref,      # (1, KI) SMEM block of (B, 1, KI)
    ins_char_ref,    # (1, KI) SMEM block of (B, 1, KI)
    # outputs
    out_elem_hbm,    # (N, P) HBM — IS pool_elem_hbm (aliased)
    out_char_hbm,    # (N, P) HBM — IS pool_char_hbm (aliased)
    n_out_ref,       # (1, 1) VMEM block of (B, 1, 1)
    ov_out_ref,      # (1, 1) VMEM block of (B, 1, 1)
    # scratch
    elem_scr,        # VMEM (Gmax, P)
    char_scr,        # VMEM (Gmax, P)
    dma_sem,
):
    i = pl.program_id(0)
    g = page_count_ref[i]
    gmax, p = elem_scr.shape

    # beyond-allocation window rows must read as zero (they carry stale
    # VMEM between grid cells otherwise; the insert math relies on unused
    # slots being zero only up to the doc's own cap, but the exists-free
    # design below never writes them back, so zeroing is purely defensive)
    elem_scr[...] = jnp.zeros((gmax, p), jnp.int32)
    char_scr[...] = jnp.zeros((gmax, p), jnp.int32)

    def _gather(j, _):
        pg = page_table_ref[i * gmax + j]
        cp = pltpu.make_async_copy(
            pool_elem_hbm.at[pl.ds(pg, 1), :], elem_scr.at[pl.ds(j, 1), :],
            dma_sem,
        )
        cp.start()
        cp.wait()
        cp = pltpu.make_async_copy(
            pool_char_hbm.at[pl.ds(pg, 1), :], char_scr.at[pl.ds(j, 1), :],
            dma_sem,
        )
        cp.start()
        cp.wait()
        return 0

    lax.fori_loop(0, g, _gather, 0)

    n0 = n_ref[i]
    ov0 = ov_ref[i]
    cap = cap_ref[i]
    lane = lax.broadcasted_iota(jnp.int32, (gmax, p), 1)
    grow = lax.broadcasted_iota(jnp.int32, (gmax, p), 0)
    pos = grow * jnp.int32(p) + lane

    def _body(k, carry):
        n, ov = carry
        ref = ins_ref_ref[0, k]
        op = ins_op_ref[0, k]
        ch = ins_char_ref[0, k]
        live = op != 0
        is_head = ref == 0
        elem = elem_scr[...]
        chars = char_scr[...]
        # first matching position via masked min (ids unique, so the min
        # of matches IS the padded argmax); Mosaic has no argmax
        match = (elem == ref) & (pos < n)
        pmin = jnp.min(jnp.where(match, pos, _INF))
        found = is_head | (pmin < _INF)
        pref = jnp.where(is_head, jnp.int32(-1), pmin)
        ok = live & found & (n < cap)
        candidate = (pos > pref) & (pos < n) & (elem < op)
        q = jnp.minimum(jnp.min(jnp.where(candidate, pos, _INF)), n)
        # fold rejected steps into a no-op: q beyond every window position
        q = jnp.where(ok, q, jnp.int32(gmax * p))
        # the splice's roll-by-one across the 2D window: lane 0 of each
        # page row takes the LAST lane of the previous page row
        rolled_e = jnp.roll(elem, 1, axis=1)
        rolled_c = jnp.roll(chars, 1, axis=1)
        prev_last_e = jnp.roll(elem[:, p - 1 : p], 1, axis=0)
        prev_last_c = jnp.roll(chars[:, p - 1 : p], 1, axis=0)
        shifted_e = jnp.where(lane == 0, prev_last_e, rolled_e)
        shifted_c = jnp.where(lane == 0, prev_last_c, rolled_c)
        elem_scr[...] = jnp.where(
            pos < q, elem, jnp.where(pos == q, op, shifted_e)
        )
        char_scr[...] = jnp.where(
            pos < q, chars, jnp.where(pos == q, ch, shifted_c)
        )
        return (
            jnp.where(ok, n + 1, n),
            ov | ((live & ~found) | (live & (n >= cap))).astype(jnp.int32),
        )

    n1, ov1 = lax.fori_loop(0, ins_count_ref[i], _body, (n0, ov0))
    n_out_ref[...] = jnp.full((1, 1), n1, jnp.int32)
    ov_out_ref[...] = jnp.full((1, 1), ov1, jnp.int32)

    def _scatter(j, _):
        pg = page_table_ref[i * gmax + j]
        cp = pltpu.make_async_copy(
            elem_scr.at[pl.ds(j, 1), :], out_elem_hbm.at[pl.ds(pg, 1), :],
            dma_sem,
        )
        cp.start()
        cp.wait()
        cp = pltpu.make_async_copy(
            char_scr.at[pl.ds(j, 1), :], out_char_hbm.at[pl.ds(pg, 1), :],
            dma_sem,
        )
        cp.start()
        cp.wait()
        return 0

    lax.fori_loop(0, g, _scatter, 0)


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
    # Mosaic requires a block's last two dims to be (8, 128)-aligned or
    # equal to the array's, so a per-doc block of a (B, w) operand is
    # illegal: the streams ride as (B, 1, KI) in SMEM (read one scalar per
    # insert step), the per-doc outputs as (B, 1, 1), and the per-doc
    # scalars ride scalar prefetch with the page table — flattened, since
    # SMEM pads a 2-D array's last dim to 128 words.
    stream = pl.BlockSpec(
        (None, 1, ki), lambda i, *_: (i, 0, 0), memory_space=pltpu.SMEM
    )
    scalar_out = pl.BlockSpec(
        (None, 1, 1), lambda i, *_: (i, 0, 0), memory_space=pltpu.VMEM
    )
    pool = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(b,),
        in_specs=[pool, pool, stream, stream, stream],
        out_specs=[pool, pool, scalar_out, scalar_out],
        scratch_shapes=[
            pltpu.VMEM((gmax, p), jnp.int32),
            pltpu.VMEM((gmax, p), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    out_elem, out_char, n1, ov1 = pl.pallas_call(
        _ragged_insert_kernel,
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
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(
        page_table.reshape(-1), page_count, ins_counts,
        n0.astype(jnp.int32), ov0.astype(jnp.int32), cap.astype(jnp.int32),
        pool_elem, pool_char,
        ins_ref[:, None, :], ins_op[:, None, :], ins_char[:, None, :],
    )
    return out_elem, out_char, n1[:, 0, 0], ov1[:, 0, 0] != 0
