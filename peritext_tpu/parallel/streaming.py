"""Streaming pod-scale merge (BASELINE config 5).

The batch path (`api.batch.DocBatch`) converges a *closed* set of change logs
in one shot.  This session engine converges an *open* stream: changes for up
to ``num_docs`` documents arrive over time (``ingest``), and each ``step``
applies everything admissible as one incremental device round on top of the
carried-over packed state — the device never replays history.

TPU-shaped design decisions:

* **Static shapes** — one compiled program for the whole session: per-round
  op streams are padded to fixed ``round_*_capacity`` widths; a doc whose
  round overflows a width simply defers the excess to the next round (the
  host-side pending queue is the elastic buffer, the device sees a constant
  shape).
* **Doc-axis sharding** — with a ``Mesh``, every (D, ...) tensor is sharded
  over the doc axis; documents are independent so steps need no cross-shard
  communication.  Cross-shard collectives appear exactly where SURVEY §5.8
  predicts: the global convergence digest / frontier reductions
  (:meth:`digest`), which XLA lowers to an all-reduce over the mesh.
* **Async overlap** — ``step`` only *dispatches* device work (JAX async
  dispatch): the next round's host-side causal scheduling and encoding
  overlaps the current round's device apply.  Reads (:meth:`read`,
  :meth:`digest`) are the synchronization points.
* **Event-sourced durability** — the session retains per-doc change logs, so
  any doc can fall back to scalar replay (undeclared actor, non-text ops,
  capacity overflow) and a session can checkpoint/restore through
  ``peritext_tpu.checkpoint``.

The reference has no analog (its replication is per-replica in-memory
callbacks); this is the TPU-native replacement for "a server holding many
collaborative documents", per BASELINE.json config 5.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, Iterable, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .. import native
from ..core.doc import Doc
from ..core.errors import DecodeError
from ..core.types import Change, Clock, FormatSpan
from ..obs import (
    GLOBAL_COUNTERS,
    GLOBAL_DEVPROF,
    GLOBAL_HISTOGRAMS,
    GLOBAL_TRACER,
    MergeStats,
    SIZE_BUCKETS,
    TraceContext,
    note_jit_dispatch,
    occupancy_key,
)
from ..ops.decode import decode_doc_spans
from ..ops.encode import DocEncoder, _DocStreams
from ..ops.encode import MAP_STREAM_COLS, MARK_COLS
from ..ops.frames import (
    FRAME_CORRUPT,
    FRAME_DEMOTE,
    FRAME_OK,
    KIND_MARK,
    FrameIngestError,
    ParsedChanges,
    parse_frames_bulk,
    schedule_split,
)
from ..schema import MARK_INDEX
from ..ops.kernel import (
    apply_batch_jit,
    apply_batch_staged_rounds,
    apply_batch_staged_rounds_jit,
    apply_batch_stacked_rounds,
    apply_batch_stacked_rounds_jit,
    apply_batch_stacked_rounds_multi_jit,
    encoded_arrays_of,
    resolve_insert_impl,
    resolve_state_donation,
)
from ..ops.packed import PackedDocs, empty_docs
from ..ops.resolve import resolve, resolve_jit
from ..utils.interning import Interner, OrderedActorTable
from ..utils.shapes import next_pow2
from .causal import causal_schedule
from .codec import decode_frame, encode_frame, strip_trace_context
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import DOC_AXIS, convergence_digest, shard_docs

@partial(jax.jit, static_argnums=1)
def _resolve_digest_jit(state: PackedDocs, comment_capacity: int, row_mask):
    """Fused span resolution + TEXT-ONLY convergence digest in ONE program:
    resolution runs with the comment planes compiled away (this digest never
    reads them — resolve.py ``with_comments``), and only the scalar digest
    plus the overflow vector ever reach the host."""
    resolved = resolve(state, comment_capacity, with_comments=False)
    mask = row_mask & ~resolved.overflow
    # masked docs contribute ZERO (not the pad constant): their host-side
    # replay hash is summed in instead (digest())
    return (
        convergence_digest(resolved.char, resolved.visible, doc_mask=mask),
        resolved.overflow,
    )


def _per_doc_full_digest(state, resolved, row_mask,
                         sess_attr, sess_key, comment_hash, row_map,
                         obj_attr, obj_key):
    """(D,) uint32 per-doc FULL-STATE hashes — visible text, resolved
    formatting (LWW winner bits, link url, comment-id sets) and the
    map-register table.  The reference's convergence oracles compare full
    formatted text (test/fuzz.ts:245-278), and cross-replica map state is
    part of the document too.  Interned identities enter only through the
    session content-hash tables (``sess_attr``/``sess_key``, flat (A,)/(K,)
    uint32, broadcast to rows HERE — shipping a pre-broadcast (D, A) table
    through the device link was the entire digest-stage cost) plus
    the sparse object-path overrides (``row_map``/``obj_attr``/``obj_key``),
    so digests are comparable across sessions with different intern orders.
    Masked or overflowed rows contribute ZERO (their host-side replay hash
    is summed in instead)."""
    from ..ops.packed import VK_DELETED, VK_STR
    from ..ops.resolve import COMMENT_TYPE, LINK_TYPE
    from .mesh import per_doc_format_digest, per_doc_register_digest, per_doc_text_digest

    d = row_map.shape[0]
    if obj_attr.shape[0]:  # static: compiled only when object docs exist
        safe = jnp.clip(row_map, 0, obj_attr.shape[0] - 1)
        is_obj = (row_map >= 0)[:, None]
        attr_hash = jnp.where(is_obj, obj_attr[safe], sess_attr[None, :])
        key_hash = jnp.where(is_obj, obj_key[safe], sess_key[None, :])
    else:
        attr_hash = jnp.broadcast_to(sess_attr[None, :], (d, sess_attr.shape[0]))
        key_hash = jnp.broadcast_to(sess_key[None, :], (d, sess_key.shape[0]))

    mask = row_mask & ~resolved.overflow
    per_doc = per_doc_text_digest(resolved.char, resolved.visible)
    per_doc = per_doc + per_doc_format_digest(
        resolved.visible, resolved.lww_active, resolved.link_attr,
        resolved.comment_bits, attr_hash, comment_hash,
        COMMENT_TYPE, LINK_TYPE,
    )
    per_doc = per_doc + per_doc_register_digest(
        state.r_obj, state.r_key, state.r_op, state.r_kind, state.r_val,
        key_hash, VK_DELETED, VK_STR,
    )
    return jnp.where(mask, per_doc, jnp.uint32(0))


@partial(jax.jit, static_argnums=1)
def _resolve_block_digest_jit(
    state: PackedDocs, comment_capacity: int, row_mask,
    sess_attr, sess_key, comment_hash, row_map, obj_attr, obj_key,
):
    """ONE program per block and round: span resolution (what every read
    path needs) PLUS the (D,) per-doc full-state hash vector (see
    :func:`_per_doc_full_digest`).  Returning both from one program means
    digest() and the read paths share the per-round resolution work (the
    block cache), and a digest-only sync point fetches just the per-doc
    vector + overflow — not the (D, S) planes.  The vector (not a scalar)
    comes back so the carried per-ROW digest plane can absorb it: later
    rounds re-hash only the rows they touch."""
    resolved = resolve(state, comment_capacity, with_comments=True)
    per_doc = _per_doc_full_digest(
        state, resolved, row_mask,
        sess_attr, sess_key, comment_hash, row_map, obj_attr, obj_key,
    )
    return resolved, per_doc


@jax.jit
def _concat_state_jit(*blocks: PackedDocs) -> PackedDocs:
    """Reassemble block-chunked apply outputs along the doc axis (one fused
    device program; compiled once per block count)."""
    return PackedDocs(*(jnp.concatenate(xs, axis=0) for xs in zip(*blocks)))


@partial(jax.jit, static_argnums=1)
def _split_blocks_jit(state: tuple, bounds: tuple):
    return tuple(
        tuple(x[lo:hi] for x in state) for lo, hi in bounds
    )


def _split_blocks(state: PackedDocs, bounds: tuple):
    """Slice session state into per-block states as ONE device program.

    The obvious `x[lo:hi]` per leaf per block dispatches n_blocks x 21
    separate slice programs, each paying the per-dispatch floor (through
    the remote-chip link of round 5, ~4.5 s of block-list construction at
    16K docs and ~27 s at 100K).  One jitted
    program (static bounds: compiled once per session shape) returns every
    block in a single dispatch."""
    return [PackedDocs(*b) for b in _split_blocks_jit(tuple(state), bounds)]




def _gather_rows(state: PackedDocs, rows_idx, mesh) -> PackedDocs:
    """K-row gather along the doc axis for the touched-rows digest.

    Meshless: one jitted fancy-index gather.  Mesh: an explicit shard_map —
    each device selects the rows its shard owns (zeros elsewhere) and a
    psum merges them — because the SPMD partitioner lowers a dynamic gather
    from a doc-sharded operand to an ALL-GATHER of the full operand, which
    made a 16-doc round's digest scale with total session docs.  Traffic
    here is K x row-bytes per device, independent of D (the analytic bound
    lives in DESIGN.md §10; tests/test_sharding.py pins the lowered HLO:
    psum all-reduces on (K, ...) shapes only, no all-gather of the (D, ...)
    operand)."""
    return PackedDocs(*gather_rows_fn(mesh)(tuple(state), rows_idx))


def gather_rows_fn(mesh):
    """The jitted K-row gather for ``mesh`` (cached through
    :func:`~.mesh_fused.mesh_fn` — bounded, keyed by mesh VALUE rather than
    the live object, so repeated test meshes share one compiled entry
    instead of accumulating stale ones).  Exposed as a function so the
    HLO-inspection test can ``.lower()`` exactly the program
    :func:`_gather_rows` dispatches."""
    from .mesh_fused import mesh_fn

    def build():
        if mesh is None:
            return jax.jit(lambda st, idx: tuple(x[idx] for x in st))
        from jax import shard_map

        from .mesh import DOC_AXIS

        def per_shard(local, idx):
            d_local = local[0].shape[0]
            start = jax.lax.axis_index(DOC_AXIS) * d_local
            rel = idx - start
            inb = (rel >= 0) & (rel < d_local)
            safe = jnp.clip(rel, 0, d_local - 1)
            out = []
            for x in local:
                g = x[safe]
                m = inb.reshape((-1,) + (1,) * (g.ndim - 1))
                if g.dtype == jnp.bool_:
                    g = jax.lax.psum(
                        jnp.where(m, g.astype(jnp.int32), 0), DOC_AXIS
                    ).astype(jnp.bool_)
                else:
                    g = jax.lax.psum(jnp.where(m, g, 0), DOC_AXIS)
                out.append(g)
            return tuple(out)

        return jax.jit(shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(DOC_AXIS), P()), out_specs=P(),
        ))

    return mesh_fn(mesh, "gather_rows", build)


@partial(jax.jit, static_argnums=1)
def _rows_digest_jit(
    sub: PackedDocs, comment_capacity: int, row_mask,
    sess_attr, sess_key, comment_hash, row_map, obj_attr, obj_key,
):
    """Per-doc full-state hashes for a GATHERED row subset (see
    :func:`_gather_rows`): resolve and hash only the (power-of-two
    bucketed) rows a round touched, so the per-round digest cost scales
    with touched docs on every platform and mesh — the block program
    re-resolves docs/block (the whole batch, under a mesh) even for a
    one-doc round.  Padding rows (``row_mask`` False) hash to zero."""
    resolved = resolve(sub, comment_capacity, with_comments=True)
    per_doc = _per_doc_full_digest(
        sub, resolved, row_mask,
        sess_attr, sess_key, comment_hash, row_map, obj_attr, obj_key,
    )
    return per_doc, resolved.overflow


# -- drain-end digest chaining (fused pipeline, round 14) --------------------
#
# The FINAL staged batch of a pipelined drain chains the resolve+digest
# block program INTO its own donated program: the drain-end digest prefetch
# used to pre-dispatch _resolve_block_digest_jit as a SEPARATE program right
# after the final apply — one more dispatch than strictly needed per drain.
# The chained twins below return (state, resolved, per_doc) from ONE
# program; the dispatch seeds the per-round block cache with the result, so
# digest() and the read paths find the round's resolution exactly as if the
# separate prefetch had run (byte equality pinned in tests/test_fused.py).
# Only the genuinely fused multi-round forms chain ("flat" staged tensors
# and the static-rounds "stacked" form): the single-round "compact1"/
# "static1" fallbacks exist precisely to SHARE compiled programs with the
# per-round discipline, and welding a digest into them would mint the
# variant back.


def _staged_rounds_digest(
    state, counts_all, ins_all, del_all, mark_all, map_all,
    row_mask, sess_attr, sess_key, comment_hash, row_map, obj_attr, obj_key,
    *, widths_seq, loop_slots_seq, ins_lens, del_lens, mark_lens, map_lens,
    insert_impl, comment_capacity,
):
    state = apply_batch_staged_rounds(
        state, counts_all, ins_all, del_all, mark_all, map_all,
        widths_seq=widths_seq, loop_slots_seq=loop_slots_seq,
        ins_lens=ins_lens, del_lens=del_lens, mark_lens=mark_lens,
        map_lens=map_lens, insert_impl=insert_impl,
    )
    resolved = resolve(state, comment_capacity, with_comments=True)
    per_doc = _per_doc_full_digest(
        state, resolved, row_mask,
        sess_attr, sess_key, comment_hash, row_map, obj_attr, obj_key,
    )
    return state, resolved, per_doc


_STAGED_DIGEST_STATICS = (
    "widths_seq", "loop_slots_seq", "ins_lens", "del_lens", "mark_lens",
    "map_lens", "insert_impl", "comment_capacity",
)
_staged_rounds_digest_jit = jax.jit(
    _staged_rounds_digest, static_argnames=_STAGED_DIGEST_STATICS,
    donate_argnums=0,
)
_staged_rounds_digest_jit_nodonate = jax.jit(
    _staged_rounds_digest, static_argnames=_STAGED_DIGEST_STATICS,
)


def _stacked_rounds_digest(
    state, stacked,
    row_mask, sess_attr, sess_key, comment_hash, row_map, obj_attr, obj_key,
    *, loop_slots_seq, insert_impl, comment_capacity,
):
    state = apply_batch_stacked_rounds(
        state, stacked, loop_slots_seq=loop_slots_seq,
        insert_impl=insert_impl,
    )
    resolved = resolve(state, comment_capacity, with_comments=True)
    per_doc = _per_doc_full_digest(
        state, resolved, row_mask,
        sess_attr, sess_key, comment_hash, row_map, obj_attr, obj_key,
    )
    return state, resolved, per_doc


_STACKED_DIGEST_STATICS = ("loop_slots_seq", "insert_impl",
                           "comment_capacity")
_stacked_rounds_digest_jit = jax.jit(
    _stacked_rounds_digest, static_argnames=_STACKED_DIGEST_STATICS,
    donate_argnums=0,
)
_stacked_rounds_digest_jit_nodonate = jax.jit(
    _stacked_rounds_digest, static_argnames=_STACKED_DIGEST_STATICS,
)


@partial(jax.jit, static_argnums=2)
def _compact_packed_jit(resolved, elem_id, width: int):
    """Gather a resolved block's planes to a visible-prefix layout of static
    ``width`` columns (visible chars keep their slot order) and concatenate
    EVERYTHING into one (D, 2 + 4*width + words*width) int32 buffer:
    ``[n_vis | overflow | char | elem | link | lww | comment words]`` per
    row.  One buffer = ONE device->host transfer per block; a sweep pays
    per-transfer latency more than bytes (seven separate small fetches
    cost ~0.9 s/block against ~0.15 s for this one through the remote-chip
    link of round 4).
    The LWW type planes pack to one bitmask column group per char."""
    # bitmask column group: a 9th LWW mark type would silently vanish from
    # every sweep read — fail the trace instead (trace-time, free at run)
    assert resolved.lww_active.shape[1] <= 8, "lww bitmask plane is uint8"
    order = jnp.argsort(~resolved.visible, axis=1, stable=True)[:, :width]
    take = lambda x: jnp.take_along_axis(x, order, axis=1)  # noqa: E731
    n_vis = jnp.sum(resolved.visible, axis=1).astype(jnp.int32)
    lww_bits = jnp.zeros(resolved.char.shape, jnp.int32)
    for t in range(resolved.lww_active.shape[1]):
        lww_bits = lww_bits | (
            resolved.lww_active[:, t, :].astype(jnp.int32) << t
        )
    words = resolved.comment_bits.shape[1]
    parts = [
        n_vis[:, None],
        resolved.overflow.astype(jnp.int32)[:, None],
        take(resolved.char).astype(jnp.int32),
        take(elem_id).astype(jnp.int32),
        take(resolved.link_attr).astype(jnp.int32),
        take(lww_bits),
    ] + [
        jax.lax.bitcast_convert_type(
            take(resolved.comment_bits[:, w, :]), jnp.int32
        )
        for w in range(words)
    ]
    return jnp.concatenate(parts, axis=1)


def _unpack_compact(buf: np.ndarray, width: int, words: int):
    """Host-side CompactBlock view over one packed sweep buffer."""
    from ..ops.decode import CompactBlock

    w = width
    char = buf[:, 2:2 + w]
    elem = buf[:, 2 + w:2 + 2 * w]
    link = buf[:, 2 + 2 * w:2 + 3 * w]
    lww = buf[:, 2 + 3 * w:2 + 4 * w].astype(np.uint8)
    comment = (
        buf[:, 2 + 4 * w:].view(np.uint32).reshape(buf.shape[0], words, w)
        if words
        else np.zeros((buf.shape[0], 0, w), np.uint32)
    )
    return CompactBlock(
        buf[:, 0], char, elem, link, lww, comment, buf[:, 1].astype(bool)
    )


@jax.jit
def _max_visible_jit(visible):
    return jnp.max(jnp.sum(visible, axis=1))


class _BlockResolution:
    """Per-(round, block) resolution artifacts: the device-side resolved
    planes, the fused per-doc full-state hash vector, and a LAZY numpy
    conversion.  Digest-only sync points fetch the hash vector + overflow
    (D uint32 + D bools); only actual span/patch reads pay the (D, S) plane
    transfer — through a narrow device link that asymmetry is the
    difference between a ~ms and a ~second sync."""

    __slots__ = ("device", "digest_dev", "on_device", "_np", "_overflow",
                 "_digest_vec")

    def __init__(self, device, digest_dev, on_device):
        self.device = device
        self.digest_dev = digest_dev  # (D,) per-doc hash vector, device
        self.on_device = on_device  # fallback mask the digest was fused with
        self._np = None
        self._overflow = None
        self._digest_vec = None

    @property
    def digest_per_doc(self) -> np.ndarray:
        if self._digest_vec is None:
            self._digest_vec = np.asarray(self.digest_dev)
        return self._digest_vec

    @property
    def overflow(self) -> np.ndarray:
        if self._overflow is None:
            self._overflow = np.asarray(self.device.overflow)
        return self._overflow

    def to_np(self):
        if self._np is None:
            self._np = type(self.device)(*(np.asarray(x) for x in self.device))
            self._overflow = self._np.overflow
        return self._np


def _width_bucket(n: int) -> int:
    """Power-of-two table width so growing interners reuse compiled digests
    (canonical spelling: utils/shapes.next_pow2, floor 8)."""
    return next_pow2(n, floor=8)


#: byte budget for the per-(round, epoch) CompactBlock cache — 100K docs of
#: compacted planes is ~250 MB, comfortably inside it; sessions beyond the
#: budget degrade to one transfer per sweep instead of one per round
_COMPACT_CACHE_BYTES = int(
    os.environ.get("PT_COMPACT_CACHE_BYTES", 512 * 1024 * 1024)
)


#: quarantine reasons — the fault-domain vocabulary.  ``decode``: a wire
#: frame failed codec decode/validation (the doc's log has a gap until
#: anti-entropy re-ships it; device state is untouched).  ``capacity`` /
#: ``schedule`` / ``encode``: the doc left the device path for scalar
#: replay (degraded but correct).  ``device-round``: the supervisor rolled
#: a failed guarded round back and demoted the doc's pending work to
#: scalar replay.
REASON_DECODE = "decode"
REASON_CAPACITY = "capacity"
REASON_SCHEDULE = "schedule"
REASON_ENCODE = "encode"
REASON_DEVICE_ROUND = "device-round"


@dataclass
class QuarantineRecord:
    """Why one doc is quarantined (typed reason + free-form detail), and at
    which session round the quarantine was imposed."""

    reason: str
    detail: str = ""
    round: int = 0
    #: a clean delivery for the doc has arrived since the corrupt one — the
    #: first half of the ``decode`` re-admission condition (the second half
    #: is the doc draining with no stuck work; see _sweep_decode_quarantine)
    clean_delivery: bool = False


@dataclass
class _DocSession:
    encoder: Optional[DocEncoder] = None
    clock: Clock = field(default_factory=dict)
    pending: List[Change] = field(default_factory=list)
    log: List[Change] = field(default_factory=list)
    fallback: bool = False
    # frame-native mode (ops/frames.py): raw wire frames are the event source;
    # pending parsed ops live in the session-level pool (one flat array chunk
    # per bulk arrival, never per-doc Python objects), applied clocks live in
    # the session-level clock matrix, attr interning is session-level too.
    frame_mode: bool = False
    frames: List[bytes] = field(default_factory=list)
    text_obj: int = 0


class _RoundBuffers:
    """One round's padded device-stream staging arrays (host side).

    Fresh zeros each round: np.zeros is a calloc, so untouched rows cost no
    page writes; only rows with scheduled work are filled (object docs by the
    per-doc encoder, frame docs by the one-call native scheduler).  Duck-typed
    to what kernel.encoded_arrays_of consumes."""

    __slots__ = ("ins_ref", "ins_op", "ins_char", "del_target", "marks",
                 "map_ops", "ins_count", "del_count", "mark_count",
                 "map_count", "num_ops")

    def __init__(self, d: int, ki: int, kd: int, km: int, kp: int) -> None:
        self.ins_ref = np.zeros((d, ki), np.int32)
        self.ins_op = np.zeros((d, ki), np.int32)
        self.ins_char = np.zeros((d, ki), np.int32)
        self.del_target = np.zeros((d, kd), np.int32)
        self.marks = {col: np.zeros((d, km), np.int32) for col in MARK_COLS}
        self.map_ops = {col: np.zeros((d, kp), np.int32) for col in MAP_STREAM_COLS}
        self.ins_count = np.zeros(d, np.int32)
        self.del_count = np.zeros(d, np.int32)
        self.mark_count = np.zeros(d, np.int32)
        self.map_count = np.zeros(d, np.int32)
        self.num_ops = np.zeros(d, np.int32)


class StreamingMerge:
    """Incremental multi-round merge of up to ``num_docs`` documents.

    ``actors`` declares the replica set whose changes may arrive (needed up
    front: packed op-ID order requires a complete ordered actor table; an
    undeclared actor demotes that doc to scalar-replay fallback).

    ``layout`` selects the resident-state storage: ``"padded"`` (this
    class: one (D, S) element batch, every doc at the slot capacity),
    ``"paged"`` (store/session.PagedStreamingMerge: a global op-page pool
    + per-doc page tables, gathered per round at each doc's own size
    bucket), or ``"ragged"`` (store/session.RaggedStreamingMerge: the same
    pool applied IN PLACE by ops/ragged — no buckets, one compiled apply
    for any doc mix).  The constructor is the factory — ``StreamingMerge(
    ..., layout="paged")`` builds the matching subclass; the padded layout
    remains the byte-equality oracle.
    """

    #: storage layout of this class (the paged subclass overrides)
    _layout = "padded"

    def __new__(cls, *args, **kwargs):
        layout = kwargs.get("layout", "padded")
        if layout not in ("padded", "paged", "ragged"):
            raise ValueError(f"unknown layout: {layout!r}")
        if cls is StreamingMerge and layout == "paged":
            from ..store.session import PagedStreamingMerge

            return super().__new__(PagedStreamingMerge)
        if cls is StreamingMerge and layout == "ragged":
            from ..store.session import RaggedStreamingMerge

            return super().__new__(RaggedStreamingMerge)
        return super().__new__(cls)

    def __init__(
        self,
        num_docs: int,
        actors: Sequence[str],
        slot_capacity: int = 256,
        mark_capacity: int = 128,
        tomb_capacity: int = 128,
        round_insert_capacity: int = 64,
        round_delete_capacity: int = 32,
        round_mark_capacity: int = 32,
        round_map_capacity: int = 16,
        comment_capacity: int = 32,
        map_capacity: int = 32,
        read_chunk: int = 8192,
        mesh=None,
        tracer=None,
        static_rounds: bool = False,
        layout: str = "padded",
    ) -> None:
        self.num_docs = num_docs
        self.actors = list(actors)
        self.mesh = mesh
        # static capacities as plain attributes: the paged layout has no
        # (D, S) self.state to read shapes off, so every capacity consumer
        # (compact width caps, digest pad terms, config) uses these
        self._slot_capacity = int(slot_capacity)
        self._mark_capacity = int(mark_capacity)
        self._tomb_capacity = int(tomb_capacity)
        self._map_capacity = int(map_capacity)
        #: serving-tier shape discipline (serve/ SessionMux): commit every
        #: round through the PADDED (D, K) apply at the configured widths —
        #: one XLA apply shape for the session's whole lifetime (plus the
        #: log2 slot-window ladder) instead of the adaptive width / flat
        #: stream-bucket / fused-depth variant space.  Trickle rounds pay
        #: padded staging they don't fill, but a latency-SLO tier would
        #: rather waste bucket occupancy than eat a multi-second XLA
        #: compile inside a client's p99.  Meshless sessions only; sized
        #: for serving hosts (thousands of docs), not 100K-doc analytics
        #: sessions (whose block-chunked flat path exists for exactly the
        #: opposite trade).
        self.static_rounds = bool(static_rounds)
        #: pipeline-span producer (obs/spans.py).  Spans always measure, so
        #: per-round MergeStats work even with tracing off; they are only
        #: retained when the tracer is enabled or has sinks (e.g. the
        #: supervisor's flight recorder).
        self.tracer = tracer if tracer is not None else GLOBAL_TRACER
        #: optional FlightRecorder: quarantines land as fault records (and
        #: trigger its auto-dump) — the supervisor attaches one
        self.recorder = None
        #: MergeStats of the most recent committed round batch
        self.last_round_stats: Optional[MergeStats] = None
        #: per-drain span-duration accumulator for the serve tier's
        #: latency plane: reset by drain(), filled by _emit_round_stats
        #: with the drain's schedule/apply span sums.  Durations only —
        #: this module never reads a wall clock (PTL006 merge scope);
        #: the serve mux pairs these with ITS watermarks to split the
        #: drain wall into dispatch vs commit stages.
        self.last_drain_marks: Optional[Dict[str, float]] = None
        # cumulative padded-stream accounting behind health()'s
        # padding-efficiency readout
        self._pad_real_ops = 0
        self._pad_capacity = 0
        self.round_caps = (round_insert_capacity, round_delete_capacity,
                           round_mark_capacity, round_map_capacity)
        self.comment_capacity = comment_capacity
        # Sharding needs equal shards: pad the DEVICE doc axis up to a mesh
        # multiple; padded rows are permanently empty docs (all-zero streams
        # are no-ops) and are invisible in the public API (num_docs, reads).
        # Meshless sessions larger than a read block pad to a BLOCK multiple
        # instead, so every block-chunked program (apply, resolve, compact)
        # compiles exactly one doc shape — a ragged tail block would mint a
        # second XLA shape for each.
        if mesh is not None:
            self._padded_docs = -(-num_docs // mesh.size) * mesh.size
        elif num_docs > read_chunk:
            self._padded_docs = -(-num_docs // read_chunk) * read_chunk
        else:
            self._padded_docs = num_docs
        # reads resolve the doc axis in blocks of this size (see the
        # block-cached resolution section); meshed state is never sliced
        self._read_chunk_requested = read_chunk
        self._read_chunk = (
            self._padded_docs if mesh is not None else max(1, min(read_chunk, max(num_docs, 1)))
        )
        self.docs = [_DocSession() for _ in range(num_docs)]
        #: fault-domain registry: doc -> QuarantineRecord.  Quarantine is
        #: health METADATA — it never changes read routing by itself (a
        #: demoted doc is additionally in ``fallback``); a ``decode``
        #: quarantine auto-lifts once a later clean delivery arrives for the
        #: doc AND its pending work drains (anti-entropy repair; see
        #: _sweep_decode_quarantine).
        self._quarantine: Dict[int, QuarantineRecord] = {}
        self.rounds = 0
        #: cumulative wall seconds in the native wire parse (bench stage)
        self.host_parse_seconds = 0.0
        self._patch_base: Dict[int, list] = {}
        # per-round cache of numpy-resolved doc blocks: (rounds, {bi: resolved})
        self._resolved_cache = (-1, {})
        # Incremental convergence digest (VERDICT r3 task 2): per-ROW
        # full-state hashes CARRIED across rounds in host planes.  A round
        # invalidates only the rows it applied ops to; digest() re-hashes
        # heavily-dirty blocks through the fused block program (shared with
        # the read paths) and pools the remaining dirty rows into ONE
        # gathered sub-batch program, so the per-round digest cost scales
        # with TOUCHED docs on every platform and mesh (a block — the whole
        # batch, under a mesh — is never re-resolved for a one-doc round).
        # Fallback masking happens at SUM time from current flags, so
        # demotions never stale the carried hashes; per-doc hashes are
        # invariant under interner growth (tables are gathered by ids
        # present in the doc's own rows).  digest(refresh=True) is the
        # full-recompute verification path.
        self._digest_plane = np.zeros(self._padded_docs, np.uint32)
        self._digest_ov = np.zeros(self._padded_docs, bool)
        self._digest_row_valid = np.zeros(self._padded_docs, bool)
        # Physical placement indirection (SURVEY §5.8(c) re-sharding):
        # logical doc d lives in device row _row_of[d]; _doc_at is the
        # inverse (-1 = empty/pad row).  Identity until reshard() moves
        # rows; every device-facing site maps through it, every host
        # structure stays logical-doc-indexed.
        self._row_of = np.arange(num_docs, dtype=np.int64)
        self._doc_at = np.full(self._padded_docs, -1, np.int64)
        self._doc_at[:num_docs] = np.arange(num_docs)
        #: bumped by reshard(): in-flight async digests must neither write
        #: their pre-reshard scalars back into the carry nor map their
        #: schedule-time rows through the new placement
        self._placement_epoch = 0
        #: per-(lo, hi) device-resident digest hash tables, keyed by an
        #: interner/placement fingerprint (see _digest_tables)
        self._digest_tables_cache: Dict = {}
        #: fetched CompactBlocks for the current (round, epoch) — lets
        #: read_all + read_patches_all share one device transfer per block
        #: (bounded by _COMPACT_CACHE_BYTES; beyond it each sweep re-fetches)
        self._compact_cache: tuple = ((-1, -1), {}, 0)
        #: per-block visible-prefix widths (-1 = session-wide prior); see
        #: _compact_width_for
        self._compact_width: Dict[int, int] = {}
        #: last chunked-apply output blocks (next round's inputs); None
        #: whenever self.state was rebuilt outside _apply_compact
        self._apply_blocks: Optional[list] = None
        self._actor_table = OrderedActorTable(self.actors)
        # frame-native session state (bulk path, ops/frames.parse_frames_bulk):
        # parsed-but-unscheduled changes pool as (doc_of_change, ParsedChanges)
        # chunks; per-doc applied frontiers as one (D, A) clock matrix; attr
        # interning shared across frame docs (ids are per-session, append-only).
        self._pool: List = []
        self._frame_mode = np.zeros(num_docs, bool)
        self._clock_mat = np.zeros((num_docs, len(self._actor_table)), np.int32)
        self._frame_attrs = Interner()
        # map keys + string values share one session interner (read_root)
        self._map_keys = Interner()
        # comment-mark ids must be PER-DOC dense (they index the capacity-C
        # comment planes); link urls etc. stay in the session table
        self._doc_comment_ids: Dict[int, Interner] = {}
        # object-path docs with pending changes (so step() never scans all D)
        self._object_pending: set = set()
        # when a list, _apply_compact records each round's device-ready
        # inputs (engine-limit bench replay; see bench.py run_engine)
        self._capture_rounds: Optional[list] = None
        #: lazy double-buffered staging lane (parallel/staging.FrameStager):
        #: the pipelined drain flattens + uploads batch k's fused inputs on
        #: its worker while batch k+1 schedules here
        self._stager = None
        #: fused-pipeline digest accumulation: when True, a pipelined drain
        #: ends by PRE-DISPATCHING the fused resolve+digest block program
        #: (async, with an async host copy of the per-doc hash vector), so
        #: the next digest()/read is one readback of already-computed
        #: device results instead of a dispatch+compute sync.  Off by
        #: default: a drain whose caller neither digests nor reads before
        #: the next commit would pay a wasted resolve per drain.  The bench
        #: fused row and the serving mux (reads follow every pump) turn it
        #: on.
        self.prefetch_digest = False
        #: compat switch: False restores the pre-fusion per-round dispatch
        #: discipline (one compact apply dispatch per round, per-round
        #: device_put staging, unpipelined drain) — the bench fused row's
        #: comparison arm and the equivalence tests' oracle side
        self.fused_pipeline = True
        #: cross-tenant fusion window extents (plan/fusion.FusionGroup
        #: window_rows): ``(row_bases, block_docs)`` set by the serve
        #: tier's FusedMuxGroup around a drain whose window touched a
        #: SUBSET of the lane's tenants.  When set, static-round commits
        #: stage only the active tenants' row blocks and rebuild the full
        #: (D, K) planes in-program (kernel.apply_batch_stacked_rounds
        #: _multi: the offsets ride as DATA, so the active subset never
        #: recompiles).  None = whole-lane staging, the stacked form.
        self.fusion_rows = None
        # Per-ROW cumulative admitted inserts: a host-side upper bound on
        # device slot occupancy (slots only grow, one per admitted insert;
        # device-side convergence dedup can only make the true count
        # smaller).  max() of it bounds the pallas insert loop's slot
        # window (kernel insert_loop_slots) so early/steady rounds scan
        # the occupied prefix, not the whole slot capacity.  Maintained at
        # every admission site; reshard() permutes it with the rows.
        self._cum_ins = np.zeros(self._padded_docs, np.int64)
        if self._layout == "padded":
            state = empty_docs(self._padded_docs, slot_capacity, mark_capacity,
                               tomb_capacity, map_capacity=map_capacity)
            self.state: PackedDocs = shard_docs(state, mesh) if mesh is not None else state
        else:
            # paged layout: the element planes live in the page pool the
            # subclass builds after this init; there is no (D, S) batch
            self.state = None

    # -- ingestion ---------------------------------------------------------

    def ingest(self, doc_index: int, changes: Iterable[Change]) -> None:
        """Queue newly-arrived changes for one document (any order, dups ok)."""
        sess = self.docs[doc_index]
        changes = list(changes)
        if not changes:
            return  # a zero-change frame would only grow durable history
        if sess.frame_mode:
            # the doc's pending state lives as parsed arrays; route object
            # arrivals through the same (cheap) frame parse
            self.ingest_frame(doc_index, encode_frame(changes))
            return
        sess.pending.extend(changes)
        self._object_pending.add(doc_index)

    def ingest_frame(self, doc_index: int, data: bytes,
                     on_corrupt: str = "raise") -> None:
        """Queue one binary change frame (the wire format a peer host ships,
        parallel/codec.py) for one document.  Raises :class:`DecodeError`
        (a ValueError) on corrupt frames (nothing is queued) unless
        ``on_corrupt="quarantine"``.  This is the single-frame convenience
        form of :meth:`ingest_frames` — a host draining a DCN receive queue
        should hand the whole batch over at once."""
        self.ingest_frames([(doc_index, data)], on_corrupt=on_corrupt)

    def ingest_frames(self, items: Iterable, on_corrupt: str = "raise") -> None:
        """Bulk-queue binary change frames, many docs per call — the native
        fast path at pod scale: ONE C++ call parses every frame (header,
        string tables, varint payload, packed identifiers) straight into flat
        arrays; no per-frame Python, no ``Change`` objects unless a doc
        leaves the fast path.

        ``items`` is an iterable of ``(doc_index, frame_bytes)``.  Frames are
        processed in order; corrupt frames contribute nothing, quarantine
        their doc (typed reason ``decode``), and — per-doc fault isolation —
        never block the other docs' frames, which are all queued first.

        ``on_corrupt`` picks the failure surface: ``"raise"`` (default, the
        pre-supervisor contract) raises one :class:`DecodeError` naming the
        affected docs after everything parseable has been queued;
        ``"quarantine"`` absorbs the fault entirely — the quarantine registry
        plus counters are the only signal, and the quarantine lifts
        automatically once a later clean delivery for the doc arrives and
        its pending work drains (anti-entropy repair)."""
        if on_corrupt not in ("raise", "quarantine"):
            raise ValueError(f"unknown on_corrupt mode: {on_corrupt!r}")
        items = list(items)
        # Traced (v5) / checked (v6) transport frames normalize to the
        # self-contained v2 storage form here — durable history and the
        # native parser only ever see v1/v2 — and the wire-carried context
        # links this host's ingest span into the SENDING host's trace.  A
        # v6 frame whose CRC fails passes through UNCHANGED (identity) and
        # is rejected as corrupt by the per-doc decode below, preserving
        # per-doc fault isolation.
        ctx: Optional[TraceContext] = None
        for j, (d, data) in enumerate(items):
            c, plain = strip_trace_context(data)
            if plain is not data:
                items[j] = (d, plain)
            if c is not None and ctx is None:
                ctx = TraceContext(*c)
        with self.tracer.span("streaming.ingest", ctx=ctx, frames=len(items)):
            self._ingest_items(items, on_corrupt)

    def _ingest_items(self, items: List, on_corrupt: str) -> None:
        fast: List = []
        corrupt: List[int] = []
        use_native = native.available()
        for doc_index, data in items:
            sess = self.docs[doc_index]
            object_bound = sess.fallback or sess.encoder is not None or bool(
                sess.pending or sess.log
            )
            if (not sess.frame_mode and object_bound) or not use_native:
                try:
                    self.ingest(doc_index, decode_frame(data))
                except ValueError:
                    corrupt.append(doc_index)
            else:
                fast.append((doc_index, data))
        if fast:
            corrupt.extend(self._ingest_frames_native(fast))
        bad = set(corrupt)
        # anti-entropy repair, first half: note which decode-quarantined docs
        # saw a clean delivery; re-admission happens once the doc also drains
        # with no stuck work (_sweep_decode_quarantine)
        for d in {int(d) for d, _ in items} - bad:
            rec = self._quarantine.get(int(d))
            if rec is not None and rec.reason == REASON_DECODE:
                rec.clean_delivery = True
        if bad:
            GLOBAL_COUNTERS.add("streaming.corrupt_frames", len(corrupt))
            for d in sorted(bad):  # deterministic quarantine-registry order
                self.quarantine_doc(
                    int(d), REASON_DECODE, "corrupt wire frame discarded"
                )
            if on_corrupt == "raise":
                raise DecodeError(
                    f"corrupt frame(s) for doc(s) {sorted(bad)}"
                )

    def _ingest_frames_native(self, items: List) -> List[int]:
        """Bulk-parse eligible frames; returns doc indices of corrupt frames."""
        doc_ids = np.asarray([d for d, _ in items], np.int64)
        frames = [data for _, data in items]
        frame_off = np.concatenate(
            [[0], np.cumsum([len(f) for f in frames], dtype=np.int64)]
        ).astype(np.int64)
        text_objs: Dict[int, int] = {}
        for d in doc_ids:
            d = int(d)
            sess = self.docs[d]
            if not sess.frame_mode:
                sess.frame_mode = True
                self._frame_mode[d] = True
            text_objs.setdefault(d, sess.text_obj)

        t0 = time.perf_counter()
        out = parse_frames_bulk(
            b"".join(frames), frame_off, self._actor_table,
            self._frame_attrs, doc_ids, text_objs,
            keys=self._map_keys,
        )
        # host-parse share of ingest, surfaced by the bench streaming row
        # (VERDICT r4 task 3): the C++ wire parse + its Python finishing
        self.host_parse_seconds += time.perf_counter() - t0
        if out is None:  # pragma: no cover - native.available() checked
            corrupt = []
            for (d, data) in items:
                try:
                    self.ingest(int(d), decode_frame(data))
                except ValueError:
                    corrupt.append(int(d))
            return corrupt
        parsed, f_ch_off, status = out

        # Re-map comment-mark attr ids from the session table to PER-DOC
        # dense ids: comment ids index capacity-C resolution planes, and a
        # session-wide numbering would overflow every doc's capacity once
        # the session has seen more than C distinct ids anywhere.
        ops = parsed.ops
        sel = np.nonzero(
            (ops[:, 0] == KIND_MARK)
            & (ops[:, 4] == MARK_INDEX["comment"])
            & (ops[:, 9] > 0)
        )[0]
        if len(sel):
            ch_idx = np.searchsorted(parsed.ops_off, sel, side="right") - 1
            f_idx = np.searchsorted(f_ch_off, ch_idx, side="right") - 1
            # Intern only rows of frames that passed every corrupt/demote
            # check: rows of discarded frames never reach the device, and
            # interning their ids would let an adversarial peer exhaust the
            # doc's dense comment-id space (capacity C) with corrupt frames,
            # permanently routing its reads to scalar replay (advisor r2).
            ok = status[f_idx] == FRAME_OK
            sel, ch_idx, f_idx = sel[ok], ch_idx[ok], f_idx[ok]
        if len(sel):
            docs_of_rows = doc_ids[f_idx].astype(np.int64)
            keycode = (docs_of_rows << 32) | ops[sel, 9].astype(np.int64)
            uniq, inv = np.unique(keycode, return_inverse=True)
            local_ids = np.empty(len(uniq), np.int32)
            for j, kc in enumerate(uniq):
                doc, gid = int(kc >> 32), int(kc & 0xFFFFFFFF)
                table = self._doc_comment_ids.setdefault(doc, Interner())
                local_ids[j] = table.intern(self._frame_attrs.lookup(gid))
            ops[sel, 9] = local_ids[inv]

        # Per-frame bookkeeping in arrival order: a demotion mid-call routes
        # the same doc's later frames to the object path (its pooled changes
        # are dropped at gather time; the frame history replay covers them).
        corrupt: List[int] = []
        keep_frame = np.zeros(len(items), bool)
        for f, (d, data) in enumerate(items):
            d = int(d)
            sess = self.docs[d]
            if not sess.frame_mode:  # demoted earlier in this call
                try:
                    self.ingest(d, decode_frame(data))
                except ValueError:
                    corrupt.append(d)
                continue
            if status[f] == FRAME_CORRUPT:
                corrupt.append(d)
            elif status[f] == FRAME_DEMOTE:
                try:
                    extra = decode_frame(data)
                except ValueError:
                    # natively parseable but not object-decodable: corrupt
                    # semantics — contribute nothing, keep the doc's state
                    corrupt.append(d)
                    continue
                self._demote_frame_doc(
                    d, extra=extra, reason=REASON_SCHEDULE,
                    detail="frame parseable but not device-expressible",
                )
            else:
                sess.frames.append(data)
                sess.text_obj = text_objs[d]
                keep_frame[f] = True

        if keep_frame.all() and parsed.num_changes:
            self._pool.append(
                (np.repeat(doc_ids, np.diff(f_ch_off).astype(np.int64)), parsed)
            )
        elif parsed.num_changes:
            doc_of = np.repeat(doc_ids, np.diff(f_ch_off).astype(np.int64))
            sel = np.nonzero(np.repeat(keep_frame, np.diff(f_ch_off)))[0]
            if len(sel):
                self._pool.append((doc_of[sel], parsed.select(sel)))
        return corrupt

    # -- fault-domain quarantine -------------------------------------------

    def quarantine_doc(self, doc_index: int, reason: str,
                       detail: str = "") -> None:
        """Quarantine one doc with a typed reason.  Idempotent per doc with
        one escalation rule: a demotion-class reason OVERWRITES a ``decode``
        record (the doc's routing really changed — a later clean frame must
        not lift the record while the doc sits on the scalar path), while a
        repeated fault never re-labels an existing same-class record."""
        rec = self._quarantine.get(doc_index)
        if rec is None:
            self._quarantine[doc_index] = QuarantineRecord(
                reason=reason, detail=detail, round=self.rounds
            )
            GLOBAL_COUNTERS.add("streaming.quarantined_docs")
            if self.recorder is not None:
                # flight recorder: the quarantine becomes a post-mortem —
                # fault() auto-dumps the recent span/event ring as JSONL
                self.recorder.fault(
                    "quarantine", doc=doc_index, quarantine_reason=reason,
                    detail=detail, round=self.rounds,
                )
        elif rec.reason == REASON_DECODE and reason != REASON_DECODE:
            self._quarantine[doc_index] = QuarantineRecord(
                reason=reason, detail=detail, round=self.rounds
            )
        elif rec.reason == REASON_DECODE and reason == REASON_DECODE:
            # a fresh corrupt frame invalidates any earlier repair evidence
            rec.clean_delivery = False

    def readmit(self, doc_index: int) -> bool:
        """Lift a doc's quarantine (any reason); returns whether a record
        was present.  Demotion-class reasons leave the doc on the scalar
        path — re-admission clears the health flag, not the routing."""
        if self._quarantine.pop(doc_index, None) is not None:
            GLOBAL_COUNTERS.add("streaming.readmitted_docs")
            return True
        return False

    def _sweep_decode_quarantine(self) -> None:
        """Auto re-admission, second half: a ``decode``-quarantined doc
        lifts once a clean delivery has arrived AND the doc has no pending
        work left (a causal gap the corrupt frame tore keeps its dependents
        pending, so a stuck doc stays quarantined until anti-entropy really
        re-ships the missing changes).  Only ``decode`` records lift —
        demotion-class records describe device-path state that a new frame
        does not repair.  Note the limit: a gap with no local dependents is
        locally undetectable (the wire format has no checksum — see ROADMAP
        "Wire-frame checksum"); the frontier diff of the next anti-entropy
        round is what closes that window."""
        candidates = [
            d for d, r in sorted(self._quarantine.items())  # readmit in doc order
            if r.reason == REASON_DECODE and r.clean_delivery
        ]
        if not candidates:
            return
        pending = self.pending_docs()
        for d in candidates:
            if d not in pending:
                self.readmit(d)

    def quarantined(self) -> Dict[int, QuarantineRecord]:
        """Snapshot of the quarantine registry (doc -> record); sweeps any
        ``decode`` record whose re-admission condition is now met, so the
        snapshot never reports a repaired doc as sick."""
        self._sweep_decode_quarantine()
        return dict(self._quarantine)

    def pending_docs(self) -> set:
        """Docs with undelivered (pending or pooled) changes."""
        out = {d for d, s in enumerate(self.docs) if s.pending}
        for doc_of, _ in self._pool:
            out.update(int(x) for x in np.unique(doc_of))
        return out

    def force_fallback(self, doc_index: int,
                       reason: str = REASON_DEVICE_ROUND,
                       detail: str = "") -> None:
        """Demote one doc to scalar replay (degraded but correct) and
        quarantine it with ``reason`` — the supervisor's containment move
        after a failed guarded device round.  Frame docs replay their frame
        history; object docs fold pending work into the replay log."""
        sess = self.docs[doc_index]
        if sess.frame_mode:
            self._demote_frame_doc(doc_index, reason=reason, detail=detail)
            return
        if not sess.fallback:
            sess.fallback = True
            GLOBAL_COUNTERS.add("streaming.fallback_docs")
        sess.log.extend(sess.pending)
        sess.pending = []
        self._object_pending.discard(doc_index)
        self.quarantine_doc(doc_index, reason, detail)

    def health(self) -> Dict:
        """One structured snapshot of the session's fault-domain state —
        what a fleet health endpoint would export per session.  Includes
        the padding-efficiency readout of the LAST committed round batch
        and the session-cumulative ratio (real ops / padded stream
        capacity), so a fleet scrape can spot a session whose round widths
        are mis-sized for its workload."""
        last = self.last_round_stats
        return {
            "rounds": self.rounds,
            "num_docs": self.num_docs,
            "pending_changes": self.pending_count(),
            "fallback_docs": sum(1 for s in self.docs if s.fallback),
            "frame_docs": int(self._frame_mode.sum()),
            "round_padding_efficiency": (
                round(last.padding_efficiency, 4) if last is not None else None
            ),
            "padding_efficiency_cum": (
                round(self._pad_real_ops / self._pad_capacity, 4)
                if self._pad_capacity else None
            ),
            "quarantined": {
                d: {"reason": r.reason, "detail": r.detail, "round": r.round}
                for d, r in sorted(self.quarantined().items())
            },
        }

    def _demote_frame_doc(self, doc_index: int, extra: List[Change] = (),
                          reason: str = REASON_CAPACITY,
                          detail: str = "") -> None:
        """Leave the fast path: the doc becomes a scalar-replay fallback fed
        by its decoded frame history (its device rows may already hold applied
        ops, so only the oracle path is still correct for it).  The doc is
        quarantined with ``reason`` so health snapshots can attribute the
        demotion."""
        sess = self.docs[doc_index]
        changes = [ch for f in sess.frames for ch in decode_frame(f)]
        changes.extend(extra)
        sess.log.extend(changes)
        # fold the applied frontier into the object-path clock so frontier()
        # stays truthful across the demotion
        row = self._clock_mat[doc_index]
        for idx in np.nonzero(row)[0]:
            actor = self._actor_table.lookup(int(idx))
            sess.clock[actor] = max(sess.clock.get(actor, 0), int(row[idx]))
        self._clock_mat[doc_index] = 0
        sess.frame_mode = False
        self._frame_mode[doc_index] = False
        sess.frames = []
        sess.text_obj = 0
        sess.fallback = True
        GLOBAL_COUNTERS.add("streaming.fallback_docs")
        self.quarantine_doc(doc_index, reason, detail)

    # -- the incremental device round --------------------------------------

    def step(self) -> int:
        """Apply every admissible pending change in one device round.

        Returns the number of changes scheduled this round.  Device work is
        dispatched asynchronously; the caller may immediately ingest and
        schedule the next round while the TPU runs this one.
        """
        with self.tracer.span("streaming.round") as rsp:
            with self.tracer.span("streaming.schedule") as ssp:
                enc, widths, scheduled = self._schedule_round()
            if scheduled:
                with self.tracer.span("streaming.apply", rounds=1) as asp:
                    self._commit_rounds([(enc, widths)])
                self._emit_round_stats(
                    [(enc, widths)], scheduled, ssp.duration, asp.duration
                )
            rsp.args["scheduled"] = scheduled
        self._sweep_decode_quarantine()
        return scheduled

    def _emit_round_stats(self, batch, scheduled: int,
                          schedule_s: float, apply_s: float,
                          origin: str = "streaming.round") -> None:
        """Per-commit MergeStats + histograms: the streaming path's analog
        of ``DocBatch.merge``'s report — the slowest bench row is no longer
        the least instrumented.  ``apply_seconds`` is host DISPATCH wall
        (device work is async; reads are the sync points), which is exactly
        the quantity the per-dispatch-floor analysis needs.  ``origin``
        labels the devprof occupancy rows ("streaming.fused" for pipelined
        drain commits), so the observability stack attributes per-fused-
        round cost to the fused sites."""
        touched: set = set()
        real = 0
        capacity = 0
        for enc, widths in batch:
            round_real = int(enc.num_ops.sum())
            round_cap = self._padded_docs * sum(widths)
            touched.update(int(r) for r in np.nonzero(enc.num_ops)[0])
            real += round_real
            capacity += round_cap
            if GLOBAL_DEVPROF.enabled:
                # per-bucket occupancy (devprof): the round's real ops vs
                # its padded (doc x width) capacity, keyed by the width set
                # — the per-bucket generalization of padding_efficiency
                GLOBAL_DEVPROF.observe_round(
                    occupancy_key(self._padded_docs, *widths),
                    round_real, round_cap, origin=origin,
                )
        if GLOBAL_DEVPROF.enabled:
            # round-boundary device-memory watermark (one sample per
            # committed batch, not per fused round — bounded overhead)
            GLOBAL_DEVPROF.sample_memory()
        stats = MergeStats(
            docs=len(touched),
            device_docs=len(touched),
            device_ops=real,
            encode_seconds=schedule_s,
            apply_seconds=apply_s,
            padding_efficiency=real / capacity if capacity else 0.0,
            extras={"rounds": len(batch), "scheduled_changes": scheduled},
        )
        self.last_round_stats = stats
        if self.last_drain_marks is not None:
            # span-derived stage durations for the serve tier's latency
            # plane (clock-free: spans always measure)
            self.last_drain_marks["schedule_seconds"] += schedule_s
            self.last_drain_marks["apply_seconds"] += apply_s
            self.last_drain_marks["rounds"] += len(batch)
        self._pad_real_ops += real
        self._pad_capacity += capacity
        GLOBAL_HISTOGRAMS.observe("streaming.round_seconds", schedule_s + apply_s)
        GLOBAL_HISTOGRAMS.observe(
            "streaming.round_scheduled_changes", scheduled, buckets=SIZE_BUCKETS
        )

    def _schedule_round(self):
        """The HOST half of a round: causal admission into staging buffers
        (object-path encode + the C++ frame scheduler), width selection —
        no device dispatch.  Returns ``(enc, widths, scheduled)``;
        ``drain`` schedules several rounds back-to-back and commits them as
        one fused program (the scheduling state is host-only clocks, so
        admission never needs the previous round's device result)."""
        ki, kd, km, kp = self.round_caps
        scheduled = 0

        # ---- object-path docs (editor-style sessions): per-doc encode ----
        obj_streams: Dict[int, _DocStreams] = {}
        for i in sorted(self._object_pending):
            sess = self.docs[i]
            if sess.fallback:
                sess.log.extend(sess.pending)
                sess.pending = []
                self._object_pending.discard(i)
                continue
            if sess.encoder is None:
                sess.encoder = DocEncoder(self.actors)
            ordered, stuck = causal_schedule(sess.pending, sess.clock)
            # budget the round to the static stream widths: admit a prefix
            # whose stream usage fits; the rest waits (shapes stay constant,
            # docs just take extra rounds)
            admitted, deferred = self._budget(ordered, ki, kd, km, kp)
            if not admitted and ordered and self._never_fits(ordered[0], ki, kd, km, kp):
                # a single change larger than a round width can never be
                # admitted: demote instead of wedging the doc (and every
                # change behind it) forever — the frame path's batched
                # scheduler does the same via its demote status
                sess.fallback = True
                GLOBAL_COUNTERS.add("streaming.fallback_docs")
                self.quarantine_doc(
                    i, REASON_CAPACITY, "change exceeds round stream widths"
                )
            streams, ok = sess.encoder.encode_increment(admitted)
            if not ok:
                sess.fallback = True
                GLOBAL_COUNTERS.add("streaming.fallback_docs")
                self.quarantine_doc(
                    i, REASON_ENCODE, "change not device-expressible"
                )
            else:
                for ch in admitted:
                    sess.clock[ch.actor] = ch.seq
                scheduled += len(admitted)
                if streams.ins or streams.dels or streams.marks or streams.maps:
                    obj_streams[i] = streams
            sess.log.extend(admitted)
            sess.pending = deferred + stuck
            if sess.fallback:
                # keep full history for scalar replay; nothing on device
                sess.log.extend(sess.pending)
                sess.pending = []
            if not sess.pending:
                self._object_pending.discard(i)

        pool = self._gather_pool()
        if scheduled == 0 and pool is None:
            return None, None, 0

        # Adaptive round widths: the (D, K) staging buffers are a real cost
        # (host->device transfer every round), so trickle rounds shrink them.
        # One shared power-of-two shift keeps the apply-program variant count
        # logarithmic; any doc with large pending work keeps the full widths.
        # Block-chunked sessions keep the widths FIXED instead: the flat
        # streams already transfer only real ops, and at 100K-doc scale each
        # extra (width-set x stream-bucket) shape is a multi-second XLA
        # compile of the apply program — one shape amortizes across every
        # block and round.  static_rounds sessions (the serving tier) keep
        # them fixed too: their whole point is ONE apply shape.
        if self._padded_docs <= self._read_chunk and not self.static_rounds:
            ki, kd, km, kp = self._round_widths(pool, obj_streams, ki, kd, km, kp)

        enc = _RoundBuffers(self._padded_docs, ki, kd, km, kp)
        for i, streams in obj_streams.items():
            r = int(self._row_of[i])  # device staging rows are PHYSICAL
            if streams.ins:
                arr = np.asarray(streams.ins, np.int32)
                enc.ins_ref[r, : len(arr)] = arr[:, 0]
                enc.ins_op[r, : len(arr)] = arr[:, 1]
                enc.ins_char[r, : len(arr)] = arr[:, 2]
            if streams.dels:
                enc.del_target[r, : len(streams.dels)] = streams.dels
            if streams.marks:
                arr = np.asarray(streams.marks, np.int32)
                for c, col in enumerate(MARK_COLS):
                    enc.marks[col][r, : len(arr)] = arr[:, c]
                enc.mark_count[r] = len(arr)
            if streams.maps:
                arr = np.asarray(streams.maps, np.int32)
                for c, col in enumerate(MAP_STREAM_COLS):
                    enc.map_ops[col][r, : len(arr)] = arr[:, c]
                enc.map_count[r] = len(arr)
            enc.ins_count[r] = len(streams.ins)
            enc.del_count[r] = len(streams.dels)
            enc.num_ops[r] = (
                len(streams.ins) + len(streams.dels)
                + len(streams.marks) + len(streams.maps)
            )

        # Frame-native pass: ONE C++ call schedules + splits every frame-mode
        # doc's pooled parsed changes into its padded row (the per-doc Python
        # version is the no-native fallback).
        if pool is not None:
            scheduled += self._step_frame_docs(pool, enc, (ki, kd, km, kp))

        if scheduled == 0:
            return None, None, 0
        GLOBAL_COUNTERS.add("streaming.scheduled_changes", scheduled)
        return enc, (ki, kd, km, kp), scheduled

    #: max rounds chained into one fused dispatch by drain(); bounds both
    #: the compile-cache variant space and the staged host memory
    FUSE_MAX_ROUNDS = 8

    def _fused_eligible(self) -> bool:
        """Whether commits route through the fused device-resident round
        pipeline: single-block (the donated state program covers the whole
        doc axis — mesh sessions always qualify, their block IS the padded
        batch, and their batch commits as ONE shard_map'd staged program
        over the mesh) and not an engine-capture session (capture records
        per-ROUND device inputs, the replay contract
        bench.run_engine/engine_profile consume)."""
        return (
            self.fused_pipeline
            and self._capture_rounds is None
            and self._padded_docs <= self._read_chunk
        )

    def _commit_rounds(self, batch) -> None:
        """The DEVICE half: dispatch scheduled rounds ``[(enc, widths),
        ...]`` — for fused-eligible sessions as ONE donated device program
        per batch (kernel.apply_batch_staged_rounds: round state updates in
        place, the whole batch ships as one staged tensor set; static-round
        serving sessions chain through the stacked fixed-width twin so the
        one-shape discipline holds) — plus the per-round digest/round
        bookkeeping.  Mesh, block-chunked and engine-capture sessions
        commit per round (their dispatch paths are shape-disciplined
        differently; see kernel.apply_batch_compact_rounds for the replay
        fuse)."""
        if self._fused_eligible():
            statics = self._prep_fused_batch(batch)
            inputs = self._stage_fused_batch(batch, statics)
            self._dispatch_fused_batch(batch, statics, inputs)
            return
        for enc, widths in batch:
            self._cum_ins += enc.ins_count
            if self.mesh is not None:
                # sharded path: padded (D, K) rows partition over the mesh
                arrays = encoded_arrays_of(enc)
                arrays = shard_docs(arrays, self.mesh)
                self.state = apply_batch_jit(self.state, arrays)
            elif self.static_rounds:
                # serving-tier static path: the padded (D, K) staging at
                # the session's fixed widths — one apply shape forever
                # (the slot-window bound stays pow-2 bucketed, a log2(S)
                # ladder); see the __init__ note for the trade
                s_cap = int(self.state.elem_id.shape[1])
                bound = _width_bucket(int(self._cum_ins.max()))
                self._apply_blocks = None
                self.state = apply_batch_jit(
                    self.state, encoded_arrays_of(enc),
                    insert_loop_slots=bound if bound < s_cap else None,
                )
            else:
                # single-device path: flat streams proportional to real
                # ops, padded layout rebuilt on device (_pad_from_flat)
                self.state = self._apply_compact(enc, widths)
            # incremental digest bookkeeping: only the rows this round
            # wrote need their carried per-row hash recomputed
            self._digest_row_valid[np.nonzero(enc.num_ops)[0]] = False
            self.rounds += 1
            GLOBAL_COUNTERS.add("streaming.rounds")

    @staticmethod
    def _flatten_round(enc: _RoundBuffers, widths, lo: int, hi: int):
        """Doc-major flat streams + counts for rows [lo, hi) of a round."""
        ki, kd, km, kp = widths
        ic, dc = enc.ins_count[lo:hi], enc.del_count[lo:hi]
        mc, pc = enc.mark_count[lo:hi], enc.map_count[lo:hi]
        mi = np.arange(ki, dtype=np.int32)[None, :] < ic[:, None]
        md = np.arange(kd, dtype=np.int32)[None, :] < dc[:, None]
        mm = np.arange(km, dtype=np.int32)[None, :] < mc[:, None]
        mp = np.arange(kp, dtype=np.int32)[None, :] < pc[:, None]
        return (
            (ic, dc, mc, pc),
            (enc.ins_ref[lo:hi][mi], enc.ins_op[lo:hi][mi], enc.ins_char[lo:hi][mi]),
            enc.del_target[lo:hi][md],
            {col: enc.marks[col][lo:hi][mm] for col in MARK_COLS},
            {col: enc.map_ops[col][lo:hi][mp] for col in MAP_STREAM_COLS},
        )

    @staticmethod
    def _pad_put(v: np.ndarray, cap: Optional[int] = None):
        """Pow-of-two pad + ASYNC h2d: the copy streams while the host
        parses/schedules the next block (a jit call would otherwise block
        on each input)."""
        if cap is None:
            cap = _width_bucket(len(v))
        out = np.zeros(cap, np.int32)
        out[: len(v)] = v
        return jax.device_put(out)

    def _device_round_inputs(self, enc: _RoundBuffers, widths):
        """Whole-batch device inputs for one scheduled round: flatten, pow-2
        pad + async h2d, slot-window bound from the (already-updated)
        cumulative-insert plane, and the engine-bench capture hook — the
        ONE place the fused and per-round dispatch paths share, so the
        capture tuple and padding can never desync between them.
        Returns ``(round_inputs, loop_slots)``."""
        d = enc.ins_count.shape[0]
        s_cap = int(self.state.elem_id.shape[1])
        bound = _width_bucket(int(self._cum_ins.max()))
        loop_slots = bound if bound < s_cap else None
        counts, ins, dels, marks, maps = self._flatten_round(enc, widths, 0, d)
        round_inputs = (
            tuple(jax.device_put(np.ascontiguousarray(c)) for c in counts),
            tuple(self._pad_put(v) for v in ins),
            self._pad_put(dels),
            {c: self._pad_put(v) for c, v in marks.items()},
            {c: self._pad_put(v) for c, v in maps.items()},
        )
        if self._capture_rounds is not None:
            # engine-limit benchmarking (bench.py --mode engine): record the
            # round's device-ready inputs so a replay can time the pure
            # device engine with zero host parse/schedule/transfer
            self._capture_rounds.append((round_inputs, widths, loop_slots))
        return round_inputs, loop_slots

    # -- the fused device-resident round pipeline ---------------------------
    #
    # A committed batch is one donated device program: the per-round flat
    # streams concatenate into ONE staged tensor per stream kind (static
    # per-round slice boundaries), the 21-leaf resident state is donated so
    # XLA updates it in place, and under drain() the flatten+upload of
    # batch k runs on the staging lane's worker while batch k+1 schedules
    # on this thread and batch k-1 computes behind the async dispatch
    # queue.  Split into prep (main thread: mutates _cum_ins, derives the
    # static signature) / stage (worker-safe: pure reads of the batch's own
    # staging buffers + jax.device_put) / dispatch (main thread: the
    # donated jit call + round bookkeeping) so the pipelined drain can
    # overlap them.

    def _prep_fused_batch(self, batch):
        """Main-thread half of staging: advance the cumulative-insert
        plane, derive each round's slot-window bound and the fused
        program's static signature.  Returns the statics tuple handed to
        ``_stage_fused_batch``/``_dispatch_fused_batch`` (tagged with the
        program form: flat staged tensors, or the stacked fixed-width form
        for static-round serving sessions)."""
        from ..ops.kernel import resolve_state_donation

        s_cap = self._slot_capacity
        loop_seq = []
        for enc, _ in batch:
            self._cum_ins += enc.ins_count
            bound = _width_bucket(int(self._cum_ins.max()))
            loop_seq.append(bound if bound < s_cap else None)
        if self.mesh is not None:
            # mesh-sharded fused form: per-round (D, K) staging planes
            # stack on a leading round axis — zero-padded to the batch-max
            # width per stream kind first (zero op ids are no-op slots, so
            # rounds of different widths share one stacked shape) — and
            # the stacked program runs under shard_map on the doc axis:
            # the whole batch commits as ONE dispatch for the whole mesh.
            # fusion_rows is ignored here (full-lane staging): the
            # offset-plane subset form would need per-shard row bases, and
            # the mesh trades that staging saving for the single dispatch.
            ki = max(enc.ins_ref.shape[1] for enc, _ in batch)
            kd = max(enc.del_target.shape[1] for enc, _ in batch)
            km = max(enc.marks[MARK_COLS[0]].shape[1] for enc, _ in batch)
            kp = max(
                enc.map_ops[MAP_STREAM_COLS[0]].shape[1] for enc, _ in batch
            )
            return ("mesh_stacked", tuple(loop_seq), (ki, kd, km, kp))
        if self.static_rounds:
            if self.fusion_rows is not None:
                # cross-tenant fusion window: only the active tenants'
                # row blocks ship; T is pow-2 bucketed (zero pad blocks
                # are no-op rows wherever their row_base points) so the
                # static shape is a (T_bucket, block_docs) ladder while
                # the subset itself rides as data
                bases, block = self.fusion_rows
                return ("stacked_multi", tuple(loop_seq), tuple(bases),
                        int(block), _width_bucket(len(bases)))
            if (len(batch) == 1
                    and not resolve_state_donation(self.state.elem_id)):
                # single-round serving commit, non-donating platform: the
                # legacy one-shape padded apply IS the program (shared
                # compile with the pre-fusion static path)
                return ("static1", loop_seq[0])
            return ("stacked", tuple(loop_seq))
        if len(batch) == 1 and not resolve_state_donation(self.state.elem_id):
            # single-round commit on a non-donating platform: stage and
            # dispatch through the SAME compact apply program the
            # per-round discipline (and the capture/oracle paths) use —
            # K=1 chaining buys nothing without donation, and sharing the
            # compiled program keeps the suite-wide variant count where
            # the pre-fusion path left it.  Donating platforms route K=1
            # through the staged program so state still updates in place.
            return ("compact1", loop_seq[0], batch[0][1])
        widths_seq = tuple(widths for _, widths in batch)
        # SHARED per-kind stream buckets across the batch (the block-chunk
        # idiom): every round pads to the batch's max bucket, so the
        # compile signature carries ONE length per stream kind instead of
        # a per-round combination — the variant space stays (K x 4 bucket
        # scalars), not their product
        k = len(batch)
        ib = _width_bucket(max(int(enc.ins_count.sum()) for enc, _ in batch))
        db = _width_bucket(max(int(enc.del_count.sum()) for enc, _ in batch))
        mb = _width_bucket(max(int(enc.mark_count.sum()) for enc, _ in batch))
        pb = _width_bucket(max(int(enc.map_count.sum()) for enc, _ in batch))
        return ("flat", tuple(loop_seq), widths_seq,
                (ib,) * k, (db,) * k, (mb,) * k, (pb,) * k)

    def _stage_fused_batch(self, batch, statics):
        """Worker-safe half: flatten the batch into its single staged
        tensor set and upload everything with ONE ``jax.device_put`` of the
        whole pytree.  Touches only the batch's own staging buffers (never
        session state), so the pipelined drain may run it on the staging
        lane while this thread schedules the next batch."""
        d = self._padded_docs
        k = len(batch)
        if statics[0] == "compact1":
            # the shared-program single-round form: flat streams pow-2
            # padded exactly as _device_round_inputs stages them
            _, _, widths = statics
            enc = batch[0][0]
            counts, ins, dels, marks, maps = self._flatten_round(
                enc, widths, 0, d)

            def pad(v):
                out = np.zeros(_width_bucket(len(v)), np.int32)
                out[: len(v)] = v
                return out

            return jax.device_put((
                tuple(np.ascontiguousarray(c) for c in counts),
                tuple(pad(v) for v in ins),
                pad(dels),
                {c: pad(v) for c, v in marks.items()},
                {c: pad(v) for c, v in maps.items()},
            ))
        if statics[0] == "static1":
            enc = batch[0][0]
            return jax.device_put((
                enc.ins_ref, enc.ins_op, enc.ins_char, enc.del_target,
                {c: enc.marks[c] for c in MARK_COLS}, enc.mark_count,
                {c: enc.map_ops[c] for c in MAP_STREAM_COLS}, enc.map_count,
            ))
        if statics[0] == "stacked_multi":
            # cross-tenant fusion form: per-round, slice the ACTIVE
            # tenants' row blocks out of the (D, K) staging planes —
            # (T_bucket, block, K) per plane, zero pad blocks beyond the
            # active count — and stack along the round axis; the row_base
            # data plane ships alongside
            _, _, bases, block, t_pad = statics

            def blocks(plane):
                out = np.zeros((t_pad, block) + plane.shape[1:], plane.dtype)
                for t, b in enumerate(bases):
                    out[t] = plane[b:b + block]
                return out

            def round_tree(enc):
                return (
                    blocks(enc.ins_ref), blocks(enc.ins_op),
                    blocks(enc.ins_char), blocks(enc.del_target),
                    {c: blocks(enc.marks[c]) for c in MARK_COLS},
                    blocks(enc.mark_count),
                    {c: blocks(enc.map_ops[c]) for c in MAP_STREAM_COLS},
                    blocks(enc.map_count),
                )

            per_round = [round_tree(enc) for enc, _ in batch]
            stacked = jax.tree_util.tree_map(
                lambda *leaves: np.stack(leaves), *per_round,
            )
            row_base = np.zeros(t_pad, np.int32)
            row_base[: len(bases)] = bases
            return jax.device_put((stacked, row_base))
        if statics[0] == "mesh_stacked":
            # mesh-sharded form: per-round planes zero-pad to the batch-max
            # width per kind, stack on the round axis, and ship with ONE
            # sharded device_put — round axis replicated, doc axis
            # partitioned over the mesh (every shard receives only its own
            # rows of every round)
            ki, kd, km, kp = statics[2]

            def pad_to(a, w):
                if a.shape[1] == w:
                    return a
                out = np.zeros((a.shape[0], w) + a.shape[2:], a.dtype)
                out[:, : a.shape[1]] = a
                return out

            tree = (
                np.stack([pad_to(enc.ins_ref, ki) for enc, _ in batch]),
                np.stack([pad_to(enc.ins_op, ki) for enc, _ in batch]),
                np.stack([pad_to(enc.ins_char, ki) for enc, _ in batch]),
                np.stack([pad_to(enc.del_target, kd) for enc, _ in batch]),
                {col: np.stack([pad_to(enc.marks[col], km)
                                for enc, _ in batch]) for col in MARK_COLS},
                np.stack([enc.mark_count for enc, _ in batch]),
                {col: np.stack([pad_to(enc.map_ops[col], kp)
                                for enc, _ in batch])
                 for col in MAP_STREAM_COLS},
                np.stack([enc.map_count for enc, _ in batch]),
            )
            return jax.device_put(
                tree, NamedSharding(self.mesh, P(None, DOC_AXIS))
            )
        if statics[0] == "stacked":
            # static-round serving form: the padded (D, K) staging rows at
            # the session's fixed widths, stacked along a leading round axis
            ins_ref = np.stack([enc.ins_ref for enc, _ in batch])
            ins_op = np.stack([enc.ins_op for enc, _ in batch])
            ins_char = np.stack([enc.ins_char for enc, _ in batch])
            del_t = np.stack([enc.del_target for enc, _ in batch])
            marks = {
                col: np.stack([enc.marks[col] for enc, _ in batch])
                for col in MARK_COLS
            }
            mark_count = np.stack([enc.mark_count for enc, _ in batch])
            maps = {
                col: np.stack([enc.map_ops[col] for enc, _ in batch])
                for col in MAP_STREAM_COLS
            }
            map_count = np.stack([enc.map_count for enc, _ in batch])
            return jax.device_put(
                (ins_ref, ins_op, ins_char, del_t, marks, mark_count,
                 maps, map_count)
            )
        _, _, widths_seq, ins_lens, del_lens, mark_lens, map_lens = statics
        counts_all = np.zeros((k, 4, d), np.int32)
        ins_all = [np.zeros(sum(ins_lens), np.int32) for _ in range(3)]
        del_all = np.zeros(sum(del_lens), np.int32)
        mark_all = {col: np.zeros(sum(mark_lens), np.int32)
                    for col in MARK_COLS}
        map_all = {col: np.zeros(sum(map_lens), np.int32)
                   for col in MAP_STREAM_COLS}
        io = do = mo = po = 0
        for r, (enc, widths) in enumerate(batch):
            counts, ins, dels, marks, maps = self._flatten_round(
                enc, widths, 0, d)
            for j in range(4):
                counts_all[r, j] = counts[j]
            for a, v in zip(ins_all, ins):
                a[io:io + len(v)] = v
            del_all[do:do + len(dels)] = dels
            for col in MARK_COLS:
                mark_all[col][mo:mo + len(marks[col])] = marks[col]
            for col in MAP_STREAM_COLS:
                map_all[col][po:po + len(maps[col])] = maps[col]
            io += ins_lens[r]
            do += del_lens[r]
            mo += mark_lens[r]
            po += map_lens[r]
        return jax.device_put(
            (counts_all, tuple(ins_all), del_all, mark_all, map_all)
        )

    def _dispatch_fused_batch(self, batch, statics, inputs,
                              chain_digest: bool = False) -> bool:
        """Dispatch half: ONE donated program applies the whole batch (the
        old state buffer is consumed in place), then the per-round digest
        and round bookkeeping.  With ``chain_digest`` (the drain's FINAL
        batch, digest prefetch armed) the staged multi-round forms chain
        the resolve+digest block program INTO the same dispatch and seed
        the block cache with its result — returns True when that happened
        (the drain then skips the separate prefetch dispatch)."""
        self._apply_blocks = None
        # one staged device program is about to launch (the digest-chained
        # arm is still ONE program) — the serve tier's fusion accounting
        # and the multi-tenant bench row measure deltas of this counter
        GLOBAL_COUNTERS.add("streaming.fused_dispatches")
        if statics[0] in ("mesh_stacked", "mesh_paged", "mesh_ragged") \
                and GLOBAL_DEVPROF.enabled:
            GLOBAL_DEVPROF.observe_mesh(self._mesh_stats())
        if chain_digest and statics[0] in ("stacked", "flat", "mesh_stacked"):
            self._dispatch_fused_batch_digest(batch, statics, inputs)
            return True
        if statics[0] == "compact1":
            from ..ops.kernel import apply_batch_compact_jit

            _, loop_slots, widths = statics
            counts, ins, dels, marks, maps = inputs
            self.state = apply_batch_compact_jit(
                self.state, counts, ins, dels, marks, maps,
                widths=widths, insert_loop_slots=loop_slots,
            )
        elif statics[0] == "static1":
            self.state = apply_batch_jit(
                self.state, inputs, insert_loop_slots=statics[1],
            )
        elif statics[0] == "mesh_stacked":
            fn = self._mesh_stacked_fn(statics[1])
            if GLOBAL_DEVPROF.enabled:
                note_jit_dispatch(
                    "apply_batch_stacked_rounds.mesh", fn,
                    (self.state, inputs),
                )
            self.state = fn(self.state, inputs)
        elif statics[0] == "stacked":
            loop_seq = statics[1]
            self.state = apply_batch_stacked_rounds_jit(
                self.state, inputs, loop_slots_seq=loop_seq,
            )
        elif statics[0] == "stacked_multi":
            stacked, row_base = inputs
            self.state = apply_batch_stacked_rounds_multi_jit(
                self.state, stacked, row_base, loop_slots_seq=statics[1],
            )
        else:
            _, loop_seq, widths_seq, ins_lens, del_lens, mark_lens, \
                map_lens = statics
            counts_all, ins_all, del_all, mark_all, map_all = inputs
            self.state = apply_batch_staged_rounds_jit(
                self.state, counts_all, ins_all, del_all, mark_all, map_all,
                widths_seq=widths_seq, loop_slots_seq=loop_seq,
                ins_lens=ins_lens, del_lens=del_lens,
                mark_lens=mark_lens, map_lens=map_lens,
            )
        for enc, _ in batch:
            self._digest_row_valid[np.nonzero(enc.num_ops)[0]] = False
            self.rounds += 1
            GLOBAL_COUNTERS.add("streaming.rounds")
        return False

    def _dispatch_fused_batch_digest(self, batch, statics, inputs) -> None:
        """The chain_digest arm of :meth:`_dispatch_fused_batch`: apply the
        final staged batch AND the fused resolve+digest of the (single)
        block in one program, then seed the per-round block cache with the
        returned resolution — the drain-end digest costs zero extra
        dispatches, and digest()/read paths behave exactly as after the
        separate prefetch (same cache entry, same mask semantics)."""
        on_device = self._block_fallback_mask(0)
        digest_args = (jnp.asarray(on_device),
                       *self._digest_tables(0, self._padded_docs))
        insert_impl = resolve_insert_impl(self.state.elem_id)
        donate = resolve_state_donation(self.state.elem_id)
        if statics[0] == "mesh_stacked":
            # the shard_map twin: apply + resolve + per-doc digest in the
            # SAME sharded program — the per-shard digest vectors come back
            # doc-sharded and the host combine (digest()) sums them exactly
            # as it does meshless
            fn = self._mesh_stacked_digest_fn(statics[1])
            args = (self.state, inputs, *digest_args)
            kw = {}
        elif statics[0] == "stacked":
            fn = (_stacked_rounds_digest_jit if donate
                  else _stacked_rounds_digest_jit_nodonate)
            args = (self.state, inputs, *digest_args)
            kw = dict(loop_slots_seq=statics[1], insert_impl=insert_impl,
                      comment_capacity=self.comment_capacity)
        else:  # "flat"
            _, loop_seq, widths_seq, ins_lens, del_lens, mark_lens, \
                map_lens = statics
            counts_all, ins_all, del_all, mark_all, map_all = inputs
            fn = (_staged_rounds_digest_jit if donate
                  else _staged_rounds_digest_jit_nodonate)
            args = (self.state, counts_all, ins_all, del_all, mark_all,
                    map_all, *digest_args)
            kw = dict(widths_seq=widths_seq, loop_slots_seq=loop_seq,
                      ins_lens=ins_lens, del_lens=del_lens,
                      mark_lens=mark_lens, map_lens=map_lens,
                      insert_impl=insert_impl,
                      comment_capacity=self.comment_capacity)
        if GLOBAL_DEVPROF.enabled:
            note_jit_dispatch(
                "_fused_rounds_digest" if statics[0] == "flat"
                else "_stacked_rounds_digest", fn, args, kw,
            )
        self.state, resolved, digest_dev = fn(*args, **kw)
        for enc, _ in batch:
            self._digest_row_valid[np.nonzero(enc.num_ops)[0]] = False
            self.rounds += 1
            GLOBAL_COUNTERS.add("streaming.rounds")
        entry = _BlockResolution(resolved, digest_dev, on_device)
        self._resolved_cache = (self.rounds, {0: entry})
        self._start_digest_readback(entry)
        GLOBAL_COUNTERS.add("streaming.digest_chained")

    # -- the mesh-sharded fused programs (round 19) --------------------------
    #
    # The stacked fixed-shape form runs under shard_map on the doc axis:
    # every shard applies its own rows of every round from the one staged
    # tensor set, so a drain batch is ONE dispatch for the whole mesh.
    # Programs cache through mesh_fused.mesh_fn (bounded, mesh-VALUE keyed)
    # and close over statics only — plan planes and stream staging ride as
    # data, so repeat mesh drains compile nothing (sentinel-pinned).

    def _mesh_stacked_fn(self, loop_seq):
        from jax import shard_map

        from .mesh_fused import mesh_fn

        mesh = self.mesh
        insert_impl = resolve_insert_impl(self.state.elem_id)
        donate = resolve_state_donation(self.state.elem_id)

        def build():
            def body(state, stacked):
                return apply_batch_stacked_rounds(
                    state, stacked, loop_slots_seq=loop_seq,
                    insert_impl=insert_impl,
                )

            # check_vma=False: pallas_call results carry no varying-axes
            # type; every operand and result is doc-sharded anyway
            sm = shard_map(
                body, mesh=mesh,
                in_specs=(P(DOC_AXIS), P(None, DOC_AXIS)),
                out_specs=P(DOC_AXIS), check_vma=False,
            )
            return jax.jit(sm, donate_argnums=(0,) if donate else ())

        return mesh_fn(
            mesh, ("stacked_apply", loop_seq, insert_impl, donate), build
        )

    def _mesh_stacked_digest_fn(self, loop_seq):
        from jax import shard_map

        from .mesh_fused import mesh_fn

        mesh = self.mesh
        insert_impl = resolve_insert_impl(self.state.elem_id)
        donate = resolve_state_donation(self.state.elem_id)
        cc = self.comment_capacity

        def build():
            def body(state, stacked, row_mask, sess_attr, sess_key,
                     comment_hash, row_map, obj_attr, obj_key):
                # row_map values are GLOBAL override indices into the
                # replicated obj_attr/obj_key tables, so the per-shard body
                # reads them unchanged
                return _stacked_rounds_digest(
                    state, stacked, row_mask, sess_attr, sess_key,
                    comment_hash, row_map, obj_attr, obj_key,
                    loop_slots_seq=loop_seq, insert_impl=insert_impl,
                    comment_capacity=cc,
                )

            sm = shard_map(
                body, mesh=mesh,
                in_specs=(P(DOC_AXIS), P(None, DOC_AXIS), P(DOC_AXIS),
                          P(), P(), P(DOC_AXIS), P(DOC_AXIS), P(), P()),
                out_specs=(P(DOC_AXIS), P(DOC_AXIS), P(DOC_AXIS)),
                check_vma=False,
            )
            return jax.jit(sm, donate_argnums=(0,) if donate else ())

        return mesh_fn(
            mesh, ("stacked_digest", loop_seq, insert_impl, donate, cc),
            build,
        )

    def _mesh_stats(self) -> Dict:
        """Per-shard load snapshot behind the ``peritext_mesh_*`` gauges:
        shard count, per-shard cumulative admitted inserts (the padded
        layout's live-slot proxy) and the max/mean imbalance ratio.  The
        paged subclass overrides with real per-shard pool occupancy."""
        n = self.mesh.size
        rows = self._padded_docs // n
        per = np.asarray(self._cum_ins).reshape(n, rows).sum(axis=1)
        mean = float(per.mean())
        return {
            "shards": n,
            "rows_per_shard": rows,
            "shard_load": [int(x) for x in per],
            "shard_utilization": [
                round(float(x) / (rows * self._slot_capacity), 4)
                for x in per
            ],
            "imbalance_ratio": (
                round(float(per.max()) / mean, 4) if mean > 0 else 1.0
            ),
            "ici_page_moves": 0,
        }

    def _apply_compact(self, enc: _RoundBuffers, widths) -> PackedDocs:
        """Dispatch one round via kernel.apply_batch_compact_jit: the host
        link carries flat op streams (power-of-two padded) plus per-doc
        counts instead of the mostly-zero (D, K) staging rows.

        Sessions larger than a read block apply BLOCK-CHUNKED: the round's
        rows slice into read_chunk-doc blocks whose flat streams share one
        pow-of-two bucket per stream kind, so XLA compiles ONE block-shaped
        program reused across blocks and rounds — at 100K docs the
        whole-batch shape cost ~22 s of XLA compile PER ROUND (stream
        totals land in a different bucket each round) plus hundreds of MB
        of monolithic transfer; block shapes compile once in seconds, and
        per-block transfers overlap the next block's host flatten.  The
        per-block states concatenate back on device (one fused program)."""
        from ..ops.kernel import apply_batch_compact_jit

        d = enc.ins_count.shape[0]
        chunk = self._read_chunk
        if self._capture_rounds is not None or d <= chunk:
            round_inputs, loop_slots = self._device_round_inputs(enc, widths)
            # whole-batch apply rebuilds state outside the chunked path —
            # any carried blocks describe the PREVIOUS state
            self._apply_blocks = None
            return apply_batch_compact_jit(self.state, *round_inputs,
                                           widths=widths,
                                           insert_loop_slots=loop_slots)
        # Slot-window bound for the pallas insert loop: pow-2 bucketed so a
        # growing session mints at most log2(S) program shapes; None once
        # the bound reaches the slot capacity (full window).
        s_cap = int(self.state.elem_id.shape[1])
        bound = _width_bucket(int(self._cum_ins.max()))
        loop_slots = bound if bound < s_cap else None

        n_blocks = -(-d // chunk)
        touched = [
            bi for bi in range(n_blocks)
            if enc.num_ops[slice(*self._block_bounds(bi))].any()
        ]
        if not touched:
            return self.state
        flats = {
            bi: self._flatten_round(enc, widths, *self._block_bounds(bi))
            for bi in touched
        }
        # shared stream buckets: every touched block compiles to ONE shape
        b_ins = _width_bucket(max(len(f[1][0]) for f in flats.values()))
        b_del = _width_bucket(max(len(f[2]) for f in flats.values()))
        b_mark = _width_bucket(max(
            len(next(iter(f[3].values()))) for f in flats.values()
        ))
        b_map = _width_bucket(max(
            len(next(iter(f[4].values()))) for f in flats.values()
        ))
        # block inputs come from the PREVIOUS round's outputs — steady-state
        # rounds never slice the concatenated state (device slicing is a
        # compile per (leaf shape, start), and a traced-start dynamic slice
        # of the 22-leaf state compiled in ~28 s at 100K docs).  Keeping the
        # block list alongside the concatenated state costs a second device
        # copy of session state (~1 GB at 100K docs) — the price of never
        # re-slicing; untouched blocks pass through by reference.
        blocks_in = self._apply_blocks
        if blocks_in is None:
            blocks_in = _split_blocks(
                self.state,
                tuple(self._block_bounds(b) for b in range(n_blocks)),
            )
        new_blocks = list(blocks_in)
        for bi in touched:
            counts, ins, dels, marks, maps = flats[bi]
            new_blocks[bi] = apply_batch_compact_jit(
                blocks_in[bi],
                counts,
                tuple(self._pad_put(v, b_ins) for v in ins),
                self._pad_put(dels, b_del),
                {c: self._pad_put(v, b_mark) for c, v in marks.items()},
                {c: self._pad_put(v, b_map) for c, v in maps.items()},
                widths=widths,
                insert_loop_slots=loop_slots,
            )
        self._apply_blocks = new_blocks
        return _concat_state_jit(*new_blocks)

    #: fraction of frame-pool docs whose whole pending need must fit the
    #: round width; the skewed tail above it defers to later (cheap,
    #: mostly-idle) rounds instead of inflating every doc's padded width —
    #: the apply program's insert phase costs D x width x slot-window, so
    #: one heavy doc at width 256 made 2,048 docs pay 4-5x the p98 width
    #: (the measured 47x engine-vs-batch gap of VERDICT r4 task 2)
    ROUND_WIDTH_QUANTILE = 0.98

    def _round_widths(self, pool, obj_streams, ki: int, kd: int, km: int, kp: int):
        """Shrink this round's stream widths by a shared power-of-two shift.

        Object-path docs were already admitted at the full caps, so their
        exact usage is a hard floor.  Frame-pool docs defer un-admitted
        changes to the next round anyway (the C++ scheduler budgets a
        causal prefix per doc), so their widths follow the
        ROUND_WIDTH_QUANTILE of per-doc need — bounded below by the largest
        single change in the pool, which guarantees every doc still admits
        at least one change per round (no livelock, no demotion: the
        scheduler's never-fits check sees the same floor).

        Each stream kind buckets INDEPENDENTLY (round 5): the insert width
        drives the expensive sequential phase (cost ~ ki x slot window per
        doc), and under shuffle arrival the delete/mark backlogs grow
        faster than the insert need (targets must exist first), so the old
        shared power-of-two shift let a deep delete queue inflate the
        insert width 2-4x.  Worst-case program-variant count stays small:
        pow-2 buckets per kind, and consecutive rounds have similar
        needs."""
        need_i = max((len(s.ins) for s in obj_streams.values()), default=0)
        need_d = max((len(s.dels) for s in obj_streams.values()), default=0)
        need_m = max((len(s.marks) for s in obj_streams.values()), default=0)
        need_p = max((len(s.maps) for s in obj_streams.values()), default=0)
        if pool is not None:
            doc_of, parsed = pool
            starts = np.nonzero(
                np.concatenate([[True], doc_of[1:] != doc_of[:-1]])
            )[0]
            q = self.ROUND_WIDTH_QUANTILE
            wants = []
            for cap, cnt in ((ki, parsed.cnt_ins), (kd, parsed.cnt_del),
                             (km, parsed.cnt_mark), (kp, parsed.cnt_map)):
                per_doc = np.minimum(np.add.reduceat(cnt, starts), cap)
                floor = int(cnt.max()) if len(cnt) else 0  # largest single change
                want = max(floor,
                           int(np.quantile(per_doc, q)) if len(per_doc) else 0)
                wants.append(min(cap, want))
            need_i = max(need_i, wants[0])
            need_d = max(need_d, wants[1])
            need_m = max(need_m, wants[2])
            need_p = max(need_p, wants[3])
        return (
            min(ki, _width_bucket(max(need_i, 8))),
            min(kd, _width_bucket(max(need_d, 8))),
            min(km, _width_bucket(max(need_m, 8))),
            min(kp, _width_bucket(max(need_p, 8))),
        )

    def _gather_pool(self):
        """Merge pooled parsed-change chunks into one doc-grouped batch:
        ``(doc_of_change, ParsedChanges)`` sorted by doc, demoted docs'
        entries dropped (their frame-history replay covers those changes)."""
        if not self._pool:
            return None
        chunks = self._pool
        self._pool = []
        doc_of = (
            chunks[0][0] if len(chunks) == 1
            else np.concatenate([d for d, _ in chunks])
        )
        parsed = ParsedChanges.concat_many([p for _, p in chunks])
        keep = self._frame_mode[doc_of]
        if not keep.all():
            idx = np.nonzero(keep)[0]
            if not len(idx):
                return None
            doc_of, parsed = doc_of[idx], parsed.select(idx)
        if np.any(doc_of[:-1] > doc_of[1:]):
            order = np.argsort(doc_of, kind="stable")
            doc_of, parsed = doc_of[order], parsed.select(order)
        return doc_of, parsed

    def _step_frame_docs(self, pool, enc, caps) -> int:
        """Round-schedule every frame-mode doc's pooled changes into its
        padded row; deferred changes go back to the pool as one chunk."""
        doc_of, parsed = pool
        if not native.available():
            return self._step_frame_docs_python(pool, enc, caps)

        frame_docs = np.unique(doc_of)
        frame_rows = self._row_of[frame_docs]  # device staging is physical
        ch_off = np.concatenate(
            [np.searchsorted(doc_of, frame_docs), [len(doc_of)]]
        ).astype(np.int32)
        # gather the scheduled docs' clock rows; scatter back after the call
        clock = np.ascontiguousarray(self._clock_mat[frame_docs], np.int32)
        text_obj = np.asarray(
            [self.docs[int(i)].text_obj for i in frame_docs], np.int32
        )
        batch = native.schedule_split_batch(
            len(self._actor_table),
            ch_off,
            frame_rows.astype(np.int32),
            text_obj,
            (parsed.ch_actor, parsed.ch_seq, parsed.dep_off,
             parsed.dep_actor, parsed.dep_seq, parsed.ops_off, parsed.ops),
            clock,
            caps,
            (enc.ins_ref, enc.ins_op, enc.ins_char),
            enc.del_target,
            enc.marks,
            enc.map_ops,
        )
        if batch is None:  # pragma: no cover - available() checked above
            return self._step_frame_docs_python(pool, enc, caps)

        _, n_ins, n_del, n_mark, n_map, n_admitted, admitted, status = batch
        self._clock_mat[frame_docs] = clock
        enc.mark_count[frame_rows] = n_mark
        enc.map_count[frame_rows] = n_map
        enc.num_ops[frame_rows] = n_ins + n_del + n_mark + n_map
        scheduled = int(n_admitted.sum())

        enc.ins_count[frame_rows] = n_ins
        enc.del_count[frame_rows] = n_del

        demoted_docs = frame_docs[status != 0] if status.any() else None
        if demoted_docs is not None:
            for i in demoted_docs:  # rare: demote (rows zeroed natively)
                i = int(i)
                r = int(self._row_of[i])
                enc.ins_count[r] = 0
                enc.del_count[r] = 0
                enc.mark_count[r] = 0
                enc.map_count[r] = 0
                enc.num_ops[r] = 0
                # folds + zeroes the doc's clock row
                self._demote_frame_doc(
                    i, reason=REASON_SCHEDULE,
                    detail="batched scheduler demoted the doc's round",
                )

        defer = admitted == 0
        if demoted_docs is not None:
            defer &= ~np.isin(doc_of, demoted_docs)
        if defer.any():
            idx = np.nonzero(defer)[0]
            self._pool.append((doc_of[idx], parsed.select(idx)))
        return scheduled

    def _step_frame_docs_python(self, pool, enc, caps) -> int:
        """Per-doc Python fallback (no native library)."""
        doc_of, parsed = pool
        ki, kd, km, kp = caps
        scheduled = 0
        frame_docs = np.unique(doc_of)
        bounds = np.concatenate(
            [np.searchsorted(doc_of, frame_docs), [len(doc_of)]]
        )
        for j, i in enumerate(frame_docs):
            i = int(i)
            r = int(self._row_of[i])  # device staging rows are PHYSICAL
            sess = self.docs[i]
            doc_parsed = parsed.select(
                np.arange(bounds[j], bounds[j + 1], dtype=np.int64)
            )
            try:
                nch, (ni, nd, nm, np_), deferred = schedule_split(
                    doc_parsed,
                    self._clock_mat[i],  # row view: advanced in place
                    sess.text_obj,
                    (ki, kd, km, kp),
                    (enc.ins_ref[r], enc.ins_op[r], enc.ins_char[r]),
                    enc.del_target[r],
                    {col: enc.marks[col][r] for col in sorted(enc.marks)},
                    {col: enc.map_ops[col][r] for col in sorted(enc.map_ops)},
                    len(self._actor_table),
                )
            except FrameIngestError:
                for col in sorted(enc.marks):  # discard any partial row writes
                    enc.marks[col][r] = 0
                for col in sorted(enc.map_ops):
                    enc.map_ops[col][r] = 0
                enc.ins_ref[r] = 0
                enc.ins_op[r] = 0
                enc.ins_char[r] = 0
                enc.del_target[r] = 0
                self._demote_frame_doc(
                    i, reason=REASON_SCHEDULE,
                    detail="scalar scheduler rejected the doc's round",
                )
                continue
            if deferred.num_changes:
                self._pool.append(
                    (np.full(deferred.num_changes, i, np.int64), deferred)
                )
            enc.ins_count[r] = ni
            enc.del_count[r] = nd
            enc.mark_count[r] = nm
            enc.map_count[r] = np_
            enc.num_ops[r] = ni + nd + nm + np_
            scheduled += nch
        return scheduled

    def drain(self, max_rounds: int = 1_000) -> int:
        """Drain all admissible pending work; returns rounds run.

        Scheduling is host-only (causal clocks), so drain schedules every
        pending round FIRST and commits them as fused device programs (up
        to FUSE_MAX_ROUNDS per dispatch) — a deep queue pays the
        ~11 ms/dispatch platform floor once instead of once per round.

        Fused-eligible sessions (meshless, single-block) run the PIPELINED
        form: batch k's flatten + host→device upload happens on the
        double-buffered staging lane while batch k+1 schedules on this
        thread and batch k-1's donated program computes behind the async
        dispatch queue — the host parse/transfer wall hides behind device
        math instead of serializing with it.  With
        :attr:`prefetch_digest`, the drain ends by pre-dispatching the
        fused resolve+digest block program so the caller's next digest or
        sweep read is one readback.  Byte equality with the per-round
        ``step`` discipline is pinned by test on every path."""
        # fresh per-drain accumulator: after the drain returns, the serve
        # tier reads this drain's schedule/apply span sums (stage durations
        # for the latency plane — durations, never clocks, in merge scope)
        self.last_drain_marks = {
            "schedule_seconds": 0.0, "apply_seconds": 0.0, "rounds": 0,
        }
        if not self._fused_eligible():
            return self._drain_serial(max_rounds)
        rounds = 0
        committed = False
        chained = False
        pending = None  # (handle, batch, statics, scheduled, schedule_span)
        while True:
            batch, scheduled_total, ssp = self._schedule_batch(
                rounds, max_rounds
            )
            if pending is not None:
                # an empty schedule means the staged batch in flight is the
                # drain's FINAL one: with the prefetch armed, its dispatch
                # chains the resolve+digest into the same program (the
                # staged forms), saving the separate prefetch dispatch
                chained = self._commit_pending(
                    pending,
                    chain_digest=self.prefetch_digest and not batch,
                )
                committed = True
                pending = None
            if not batch:
                break
            statics = self._prep_fused_batch(batch)
            handle = self._ensure_stager().submit(
                self._stage_fused_batch, batch, statics
            )
            pending = (handle, batch, statics, scheduled_total, ssp)
            rounds += len(batch)
        if committed and self.prefetch_digest and not chained:
            # single-round compat forms (compact1/static1) and the paged
            # subclass keep the separate pre-dispatch
            self._prefetch_digest()
        self._sweep_decode_quarantine()
        return rounds

    def _commit_pending(self, pending, chain_digest: bool = False) -> bool:
        """Land one staged batch: wait its staging handle (a staging fault
        surfaces HERE, inside whatever guard wraps the drain) and dispatch
        the donated program.  ``chain_digest`` marks the drain's final
        batch with the digest prefetch armed; returns whether the dispatch
        actually chained the resolve+digest in."""
        handle, batch, statics, scheduled, ssp = pending
        with self.tracer.span("streaming.apply", rounds=len(batch)) as asp:
            inputs = handle.wait()
            chained = bool(self._dispatch_fused_batch(
                batch, statics, inputs, chain_digest=chain_digest,
            ))
        self._emit_round_stats(
            batch, scheduled, ssp.duration, asp.duration,
            origin="streaming.fused",
        )
        return chained

    def _ensure_stager(self):
        """The session's staging lane (lazy; respawned if closed).  The
        lane's jobs run under a ``staging.stage`` span so the stage wall
        is measured on the worker thread (timing telemetry stays the
        caller's, per the staging module's contract)."""
        from .staging import FrameStager

        if self._stager is None or self._stager._closed:
            self._stager = FrameStager()
            self._stager.span_factory = (
                lambda: self.tracer.span("staging.stage")
            )
        return self._stager

    def _prefetch_digest(self) -> None:
        """Fused-pipeline digest accumulation: dispatch the fused
        resolve+digest program for the (single) block NOW — async, with an
        async device→host copy of the per-doc hash vector — so digest()
        (and, via the shared block cache, the sweep reads) find the round's
        resolution already computed: one readback per committed drain
        instead of a dispatch+compute sync at the read point."""
        self._start_digest_readback(self._digest_resolution(0))

    @staticmethod
    def _start_digest_readback(entry) -> None:
        """Start the async device→host copy of a resolved block's digest
        planes — the ONE spelling shared by the drain-end prefetch and the
        heavy-block sweep's lookahead (no-op on platforms without async
        copy)."""
        for a in (entry.digest_dev, entry.device.overflow):
            try:
                a.copy_to_host_async()
            except AttributeError:  # platform without async copy
                pass

    def _schedule_batch(self, rounds: int, max_rounds: int):
        """Schedule the next fused batch (host-only causal admission): up
        to ``FUSE_MAX_ROUNDS`` rounds within the drain's ``max_rounds``
        bound.  ONE spelling of the batching policy — both the pipelined
        and serial drain disciplines call it, so the fused_pipeline=False
        equality oracle can never diverge on scheduling."""
        batch = []
        scheduled_total = 0
        with self.tracer.span("streaming.schedule") as ssp:
            while (len(batch) < self.FUSE_MAX_ROUNDS
                   and rounds + len(batch) < max_rounds):
                enc, widths, scheduled = self._schedule_round()
                if not scheduled:
                    break
                batch.append((enc, widths))
                scheduled_total += scheduled
        return batch, scheduled_total, ssp

    def _drain_serial(self, max_rounds: int) -> int:
        """Unpipelined drain for mesh / block-chunked / engine-capture
        sessions: schedule-then-commit per batch through the session's
        per-round dispatch discipline."""
        rounds = 0
        while rounds < max_rounds:
            batch, scheduled_total, ssp = self._schedule_batch(
                rounds, max_rounds
            )
            if not batch:
                break
            with self.tracer.span("streaming.apply", rounds=len(batch)) as asp:
                self._commit_rounds(batch)
            self._emit_round_stats(
                batch, scheduled_total, ssp.duration, asp.duration
            )
            rounds += len(batch)
        self._sweep_decode_quarantine()
        return rounds

    @staticmethod
    def _op_counts(change: Change) -> tuple:
        """(inserts, deletes, marks, map-register ops) — the round-width cost
        model shared by admission budgeting and the never-fits check."""
        ci = cd = cm = cp = 0
        for op in change.ops:
            if op.action == "set" and op.insert:
                ci += 1
            elif op.action == "del" and op.elem_id is not None:
                cd += 1
            elif op.action in ("addMark", "removeMark"):
                cm += 1
            else:  # map set/del/makeMap/makeList -> one register row
                cp += 1
        return ci, cd, cm, cp

    @classmethod
    def _never_fits(cls, change: Change, ki: int, kd: int, km: int, kp: int) -> bool:
        ci, cd, cm, cp = cls._op_counts(change)
        return ci > ki or cd > kd or cm > km or cp > kp

    @classmethod
    def _budget(cls, ordered: List[Change], ki: int, kd: int, km: int, kp: int):
        """Admit the longest causal prefix whose op streams fit the static
        round widths."""
        ins = dels = marks = maps = 0
        admitted: List[Change] = []
        for idx, ch in enumerate(ordered):
            ci, cd, cm, cp = cls._op_counts(ch)
            if ins + ci > ki or dels + cd > kd or marks + cm > km or maps + cp > kp:
                return admitted, ordered[idx:]
            ins, dels, marks, maps = ins + ci, dels + cd, marks + cm, maps + cp
            admitted.append(ch)
        return admitted, []

    # -- reads (synchronization points) ------------------------------------

    @staticmethod
    def _replay_changes(sess: _DocSession) -> List[Change]:
        """A doc's full change history for scalar replay: decoded wire frames
        in frame mode, the object log otherwise."""
        if sess.frame_mode:
            return [ch for f in sess.frames for ch in decode_frame(f)]
        return sess.log + sess.pending

    def _attr_tables(self, sess: _DocSession, doc_index: int):
        """(link/general attr table, per-doc comment-id table) for decode."""
        if sess.frame_mode:
            return self._frame_attrs, self._doc_comment_ids.get(doc_index)
        attrs = sess.encoder.attrs if sess.encoder else None
        return attrs, attrs  # object path interns per doc already

    # -- block-cached resolution ------------------------------------------
    #
    # Reads resolve the doc axis in fixed-size BLOCKS: at 100K docs a full-
    # batch span resolution materializes multi-GB comment planes and OOMs
    # HBM, while any single read only needs its own block.  Blocks are
    # cached per round (the hot pattern: many per-doc reads between steps)
    # with at most two blocks resident.  Mesh sessions use one whole-batch
    # block: state is sharded across devices there, and slicing would
    # gather across shards.

    def _block_bounds(self, block_index: int):
        lo = block_index * self._read_chunk
        return lo, min(lo + self._read_chunk, self._padded_docs)

    def _state_block(self, block_index: int) -> PackedDocs:
        lo, hi = self._block_bounds(block_index)
        if lo == 0 and hi == self._padded_docs:
            return self.state
        if self._apply_blocks is None:
            # one dispatch splits EVERY block (and the list is kept: it is
            # exactly the "blocks match state" invariant _apply_compact
            # maintains), instead of 21 per-leaf slice programs per block
            n_blocks = -(-self._padded_docs // self._read_chunk)
            self._apply_blocks = _split_blocks(
                self.state,
                tuple(self._block_bounds(b) for b in range(n_blocks)),
            )
        return self._apply_blocks[block_index]

    def _block_fallback_mask(self, block_index: int) -> np.ndarray:
        """(block,) bool: rows currently served by the device (a real doc's
        row, and that doc not fallback)."""
        lo, hi = self._block_bounds(block_index)
        on_device = np.zeros(hi - lo, bool)
        for local, d in enumerate(self._doc_at[lo:hi]):
            if d >= 0:
                on_device[local] = not self.docs[d].fallback
        return on_device

    def _resolution(self, block_index: int) -> _BlockResolution:
        """Per-round cached resolution + fused digest of one doc block (ONE
        device program for both — digest() and the read paths share it).

        Cache hits are O(1): the fused digest's doc mask is validated only
        by the digest consumers (:meth:`digest` / :meth:`digest_async`, via
        ``fresh_mask=True``) — the read paths route each doc on its CURRENT
        ``fallback`` flag before consulting the cache, so a stale mask can
        only ever affect the digest scalar, never a read."""
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
            dispatch_args = (
                self._state_block(block_index), self.comment_capacity,
                jnp.asarray(on_device), *self._digest_tables(lo, hi),
            )
            if GLOBAL_DEVPROF.enabled:
                note_jit_dispatch(
                    "_resolve_block_digest_jit", _resolve_block_digest_jit,
                    dispatch_args,
                )
            resolved, digest_dev = _resolve_block_digest_jit(*dispatch_args)
        entry = _BlockResolution(resolved, digest_dev, on_device)
        if len(cache) >= 2:  # bound host/device memory at large scale
            cache.pop(next(iter(cache)))  # least-recently-used
        cache[block_index] = entry
        return entry

    def _digest_resolution(self, block_index: int) -> _BlockResolution:
        """_resolution plus doc-mask freshness: a fallback transition without
        a round bump (demotion at read time, or a test flipping the flag)
        invalidates the fused digest's mask — recompute the block then."""
        entry = self._resolution(block_index)
        current = self._block_fallback_mask(block_index)
        if not np.array_equal(entry.on_device, current):
            stamp, cache = self._resolved_cache
            cache.pop(block_index, None)
            entry = self._resolution(block_index)
        return entry

    def _resolved_block(self, block_index: int):
        """Numpy-converted span resolution of one doc block (read paths)."""
        return self._resolution(block_index).to_np()

    def _resolved_doc(self, doc_index: int):
        """(resolved block, index of the doc within it)."""
        row = int(self._row_of[doc_index])
        bi = row // self._read_chunk
        return self._resolved_block(bi), row - bi * self._read_chunk

    def read(self, doc_index: int) -> List[FormatSpan]:
        sess = self.docs[doc_index]
        if sess.fallback:
            return _replay_spans(self._replay_changes(sess))
        resolved, local = self._resolved_doc(doc_index)
        if bool(resolved.overflow[local]):
            return _replay_spans(self._replay_changes(sess))
        attrs, comments = self._attr_tables(sess, doc_index)
        return decode_doc_spans(resolved, local, attrs, comments)

    def read_patches(self, doc_index: int) -> List:
        """Incremental reference-shaped patches since this doc's previous
        ``read_patches`` call (the first call builds the doc from empty) —
        config 5's "async patch scatter": device state is diffed host-side
        between reads (ops/patches.py), keyed on stable element identities,
        so editors receive the same patch vocabulary the scalar path emits
        (insert/delete/addMark/removeMark, testing/accumulate.py model)."""
        from ..ops.patches import diff_patches

        chars = self._doc_chars(doc_index)
        base = self._patch_base.get(doc_index, [])
        patches = diff_patches(base, chars)
        self._patch_base[doc_index] = chars
        return patches

    def _doc_chars(self, doc_index: int):
        from ..ops.patches import doc_chars_device, doc_chars_scalar

        sess = self.docs[doc_index]
        if sess.fallback:
            return doc_chars_scalar(_replay_doc(self._replay_changes(sess)))
        resolved, local = self._resolved_doc(doc_index)
        if bool(resolved.overflow[local]):
            return doc_chars_scalar(_replay_doc(self._replay_changes(sess)))
        attrs, comments = self._attr_tables(sess, doc_index)
        # the doc's element row comes from the same BLOCK the resolution
        # used (layout-independent: the paged backend materializes blocks
        # at their page-bucketed width, and the elem row must align with
        # the resolved planes' slot axis)
        bi = int(self._row_of[doc_index]) // self._read_chunk
        return doc_chars_device(
            resolved,
            local,
            attrs,
            np.asarray(self._state_block(bi).elem_id[local]),
            self._actor_table,
            comments,
        )

    def resolve_cursors(self, doc_index: int, cursors) -> List[int]:
        """Resolve stable cursors (reference ``Cursor`` dicts, src/
        micromerge.ts:859-870) for one doc; see resolve_cursors_batch."""
        return self.resolve_cursors_batch({doc_index: list(cursors)})[doc_index]

    def resolve_cursors_batch(self, cursor_map) -> Dict[int, List[int]]:
        """Resolve cursors for many docs in ONE batched device call
        (ops/resolve.resolve_cursors; width bucketed so varying counts reuse
        one compiled program).  ``cursor_map``: {doc_index: [Cursor, ...]}.
        Fallback and overflowed docs resolve via scalar replay.  Returns
        visible indices per doc, -1 for absent elements."""
        from ..ops.resolve import (
            oracle_cursor_positions,
            pack_cursor_rows,
            resolve_cursors_jit,
        )

        # Route on the per-block RESOLVED overflow (apply-time overflow plus
        # resolve-time mark/comment errors) so cursor fallback matches
        # read()/read_all() exactly; blocks are cached per round.
        device_map, replay_docs = {}, []
        for d, cursors in cursor_map.items():
            if self.docs[d].fallback:
                replay_docs.append(d)
                continue
            row = int(self._row_of[d])
            bi = row // self._read_chunk
            # overflow routing needs only the (D,) vector, not the planes
            if bool(self._resolution(bi).overflow[row - bi * self._read_chunk]):
                replay_docs.append(d)
            else:
                device_map[d] = cursors

        out: Dict[int, List[int]] = {}
        by_block: Dict[int, Dict[int, list]] = {}
        for d, cursors in device_map.items():
            by_block.setdefault(int(self._row_of[d]) // self._read_chunk, {})[d] = cursors
        for bi, block_map in by_block.items():
            lo, hi = self._block_bounds(bi)
            local_map = {
                int(self._row_of[d]) - lo: c for d, c in block_map.items()
            }
            cursor_elem = pack_cursor_rows(
                local_map, hi - lo, lambda d: self._actor_table
            )
            visible_dev = self._resolution(bi).device.visible  # stays on device
            positions = np.asarray(
                resolve_cursors_jit(
                    self._state_block(bi), visible_dev, cursor_elem
                )
            )
            for d, cursors in block_map.items():
                row = int(self._row_of[d])
                out[d] = [int(p) for p in positions[row - lo, : len(cursors)]]
        for d in replay_docs:
            doc = _replay_doc(self._replay_changes(self.docs[d]))
            out[d] = oracle_cursor_positions(doc, cursor_map[d])
        return out

    def read_root(self, doc_index: int) -> dict:
        """Materialize one doc's root map (nested maps + the text character
        list) — the streaming twin of MergeReport.roots: device docs decode
        their LWW register table (ops/decode.decode_doc_root; both ingest
        paths emit a VK_TEXT register for the makeList, so text placement
        resolves through register LWW), fallback docs replay through the
        oracle."""
        from ..ops.decode import decode_doc_root

        sess = self.docs[doc_index]
        if sess.fallback:
            return _replay_doc(self._replay_changes(sess)).root
        resolved, local = self._resolved_doc(doc_index)
        if bool(resolved.overflow[local]):
            return _replay_doc(self._replay_changes(sess)).root
        lo = (doc_index // self._read_chunk) * self._read_chunk
        block_state = self._state_block(doc_index // self._read_chunk)
        keys = (
            self._map_keys if sess.frame_mode
            else (sess.encoder.keys if sess.encoder else self._map_keys)
        )
        # both ingest paths emit a VK_TEXT register for the makeList, so the
        # text placement resolves through register LWW like any other key
        return decode_doc_root(block_state, resolved, doc_index - lo, keys)

    def _block_tables(self, lo: int):
        """(attr_of, comment_of) accessors for block-local ROW indices."""
        def attr_of(local: int):
            d = int(self._doc_at[lo + local])
            return self._attr_tables(self.docs[d], d)[0]

        def comment_of(local: int):
            d = int(self._doc_at[lo + local])
            table = self._attr_tables(self.docs[d], d)[1]
            return table if table is not None else Interner()

        return attr_of, comment_of

    def _block_device_mask(self, resolved, lo: int, hi: int) -> np.ndarray:
        """Rows of a block served from device state (not fallback/overflow)."""
        return self._block_fallback_mask(
            lo // self._read_chunk
        ) & ~np.asarray(resolved.overflow)[: hi - lo]

    def _compact_cached(self, block_index: int):
        """CompactBlock cache lookup for the current (round, epoch)."""
        stamp = (self.rounds, self._placement_epoch)
        if self._compact_cache[0] != stamp:
            self._compact_cache = (stamp, {}, 0)
        return self._compact_cache[1].get(block_index)

    def _compact_store(self, block_index: int, c):
        stamp, cache, nbytes = self._compact_cache
        if nbytes + c.nbytes <= _COMPACT_CACHE_BYTES:
            cache[block_index] = c
            self._compact_cache = (stamp, cache, nbytes + c.nbytes)

    def _compact_width_for(self, block_index: int, entry) -> int:
        """Visible-prefix width for a block's packed transfer.  The first
        block of a session pays one device round-trip for its max visible
        count; later blocks start from the session-wide prior (docs are
        statistically alike across blocks) and the post-transfer validation
        in _finish_compact widens on the rare miss — steady-state sweeps
        make ZERO width round-trips."""
        width = self._compact_width.get(block_index) or self._compact_width.get(-1)
        if width is None:
            width = min(
                _width_bucket(int(_max_visible_jit(entry.device.visible))),
                self._slot_capacity,
            )
            self._compact_width[-1] = width
        self._compact_width[block_index] = width
        return width

    def _dispatch_compact(self, block_index: int):
        """Dispatch (async) one block's packed visible-prefix transfer;
        returns ``(device_buf, width)`` for :meth:`_finish_compact`."""
        entry = self._resolution(block_index)
        width = self._compact_width_for(block_index, entry)
        buf = _compact_packed_jit(
            entry.device, self._state_block(block_index).elem_id, width
        )
        return buf, width

    def _finish_compact(self, block_index: int, buf, width: int):
        """Fetch + unpack a dispatched packed buffer, re-fetching wider if
        any live row's visible count outgrew the cached width (truncation
        would otherwise drop characters silently)."""
        words = (buf.shape[1] - 2 - 4 * width) // max(width, 1)
        c = _unpack_compact(np.asarray(buf), width, words)
        live = ~c.overflow & self._block_fallback_mask(block_index)
        if live.any():
            need = int(c.n_vis[live].max())
            if need > width:
                wide = min(_width_bucket(need), self._slot_capacity)
                entry = self._resolution(block_index)
                # never wider than the block's resolved planes: the paged
                # backend materializes blocks below slot capacity, and an
                # over-wide take would silently truncate the packed layout
                wide = min(wide, int(entry.device.char.shape[1]))
                self._compact_width[block_index] = wide
                self._compact_width[-1] = max(self._compact_width.get(-1) or 0, wide)
                buf = _compact_packed_jit(
                    entry.device,
                    self._state_block(block_index).elem_id, wide,
                )
                c = _unpack_compact(np.asarray(buf), wide, words)
        return c

    def _compact_block(self, block_index: int):
        """Fetched visible-prefix planes of one block (ops/decode.
        CompactBlock): the resolution's (D, S) planes gathered device-side
        to bucketed visible-prefix width and transferred as ONE packed
        buffer — the sweep paths decode from this instead of the full
        planes (~5x fewer bytes, one RPC), and a (round, epoch)-scoped
        byte-bounded cache lets a spans sweep and a patches sweep share
        the transfer."""
        hit = self._compact_cached(block_index)
        if hit is not None:
            return hit
        buf, width = self._dispatch_compact(block_index)
        c = self._finish_compact(block_index, buf, width)
        self._compact_store(block_index, c)
        return c

    def _sweep_compact(self, blocks=None, lookahead: int = 1):
        """Iterate ``(block_index, CompactBlock)`` over the session's live
        (non-pad-only) blocks — or an explicit list — with the next block's
        device work dispatched (and its packed buffer copying to host
        asynchronously) while the caller decodes the current one: the
        sweep's device/link time hides behind its Python decode time."""
        if blocks is None:
            blocks = [
                bi for bi in range(-(-self._padded_docs // self._read_chunk))
                if (self._doc_at[slice(*self._block_bounds(bi))] >= 0).any()
            ]
        blocks = list(blocks)
        inflight: Dict[int, tuple] = {}
        nxt = 0
        for j, bi in enumerate(blocks):
            while nxt < len(blocks) and nxt <= j + lookahead:
                b = blocks[nxt]
                if self._compact_cached(b) is None and b not in inflight:
                    buf, width = self._dispatch_compact(b)
                    try:
                        buf.copy_to_host_async()
                    except AttributeError:  # platform without async copy
                        pass
                    inflight[b] = (buf, width)
                nxt += 1
            hit = self._compact_cached(bi)
            if hit is None:
                buf, width = inflight.pop(bi)
                hit = self._finish_compact(bi, buf, width)
                self._compact_store(bi, hit)
            else:
                inflight.pop(bi, None)
            yield bi, hit

    def read_all(self) -> List[List[FormatSpan]]:
        """Span sweep over every doc: device docs decode in ONE vectorized
        pass per block (ops/decode.decode_block_spans_compact — Python
        touches only mark-run segments, the device link only visible-prefix
        planes), fallback/overflow docs replay."""
        with self.tracer.span("streaming.decode", docs=self.num_docs):
            return self._read_all()

    def _read_all(self) -> List[List[FormatSpan]]:
        from ..ops.decode import decode_block_spans_compact

        out: List[Optional[List[FormatSpan]]] = [None] * self.num_docs
        for bi, compact in self._sweep_compact():
            lo, hi = self._block_bounds(bi)
            docs_here = self._doc_at[lo:hi]
            mask = self._block_device_mask(compact, lo, hi)
            attr_of, comment_of = self._block_tables(lo)
            spans = decode_block_spans_compact(
                compact, attr_of, comment_of, doc_mask=mask
            )
            for local, d in enumerate(docs_here):
                if d < 0:
                    continue
                if mask[local]:
                    out[d] = spans[local]
                else:
                    out[d] = _replay_spans(self._replay_changes(self.docs[d]))
        return out

    def read_patches_all(self) -> List[List]:
        """Batched incremental-patch sweep: one vectorized char-state
        extraction per block (ops/decode.block_char_states_compact), then
        the per-doc identity diff — config 5's async patch scatter for a
        whole-session sweep (the per-doc ``read_patches`` stays for point
        reads).  Shares the per-block compact transfer with read_all via
        the (round, epoch) cache."""
        with self.tracer.span("streaming.patch-scatter", docs=self.num_docs):
            return self._read_patches_all()

    def _read_patches_all(self) -> List[List]:
        from ..ops.decode import block_char_states_compact
        from ..ops.patches import diff_patches, doc_chars_scalar

        out: List[List] = [None] * self.num_docs
        for bi, compact in self._sweep_compact():
            lo, hi = self._block_bounds(bi)
            docs_here = self._doc_at[lo:hi]
            mask = self._block_device_mask(compact, lo, hi)
            attr_of, comment_of = self._block_tables(lo)
            chars_block = block_char_states_compact(
                compact, self._actor_table, attr_of, comment_of, doc_mask=mask
            )
            for local, d in enumerate(docs_here):
                if d < 0:
                    continue
                if mask[local]:
                    chars = chars_block[local]
                else:
                    chars = doc_chars_scalar(
                        _replay_doc(self._replay_changes(self.docs[d]))
                    )
                base = self._patch_base.get(d, [])
                out[d] = diff_patches(base, chars)
                self._patch_base[d] = chars
        return out

    # -- cross-shard reductions (the ICI/DCN collectives) ------------------

    def reshard(self, assignment: Optional[Sequence[int]] = None) -> dict:
        """Load-balance doc placement across shards (SURVEY §5.8(c)).

        Streaming sessions place docs at first sight and never move them
        (``rebalance`` is placement-time only), so skewed arrival leaves hot
        shards bounding round latency.  This moves packed doc rows between
        shards as ONE gather over the doc axis — under a mesh XLA lowers
        the cross-shard row movement to collective permutes over ICI (the
        all-to-all) — while every logical doc id, clock, interner, pending
        queue and fallback flag stays put: placement is an internal detail
        behind ``_row_of``/``_doc_at``, so reads, ingest and digests are
        unchanged (digest is a doc-sum — permutation-invariant by
        construction; tests assert it bit-equal across a reshard).

        ``assignment`` maps each logical doc to a target shard (len
        ``num_docs``); default balances per-shard LIVE SLOT load greedily
        (largest doc first onto the least-loaded shard with a free row),
        with quarantine-aware placement: quarantined/fallback docs are
        HOST-BOUND (scalar replay runs on the shard's host CPU, not its
        chip), so the default assignment additionally spreads their load —
        a host-bound doc goes to the shard carrying the least host-bound
        load first, slot load second, while device docs weigh slot load
        first — keeping a burst of scalar-replay docs from crowding one
        shard's host.  Shards are ``mesh.size`` for mesh sessions, else the
        read-block count (balancing per-block read/digest latency).
        Returns ``{"moved": n, "shard_load": [...],
        "host_bound_load": [...]}``."""
        n_blocks = -(-self._padded_docs // self._read_chunk)
        n_shards = self.mesh.size if self.mesh is not None else n_blocks
        if n_shards <= 1 or self.num_docs == 0:
            return {"moved": 0, "shard_load": [0] * max(n_shards, 1),
                    "host_bound_load": [0] * max(n_shards, 1)}
        if self._padded_docs % n_shards:
            raise ValueError("padded doc axis must divide the shard count")
        rows_per_shard = self._padded_docs // n_shards
        sizes = self._reshard_sizes()
        host_bound = {
            d for d in range(self.num_docs)
            if self.docs[d].fallback or d in self._quarantine
        }
        if assignment is None:
            # host-bound docs place FIRST (they are the scarce dimension:
            # row capacity must not strand the last of them on a crowded
            # host), then device docs, each group largest-first
            order = sorted(
                range(self.num_docs),
                key=lambda d: (d not in host_bound, -int(sizes[d])),
            )
            load = [0] * n_shards
            hb_load = [0] * n_shards
            free = [rows_per_shard] * n_shards
            assignment = [0] * self.num_docs
            for d in order:
                # host-bound (quarantined/fallback scalar-replay) docs cost
                # the shard's HOST, not its chip: balance that dimension
                # first for them, second for device docs, so neither the
                # chips nor one host's CPU becomes the round bound
                key = (
                    (lambda s: (hb_load[s], load[s])) if d in host_bound
                    else (lambda s: (load[s], hb_load[s]))
                )
                s = min((s for s in range(n_shards) if free[s] > 0), key=key)
                assignment[d] = s
                load[s] += int(sizes[d])
                if d in host_bound:
                    hb_load[s] += int(sizes[d])
                free[s] -= 1
        else:
            assignment = [int(s) for s in assignment]
            if len(assignment) != self.num_docs:
                raise ValueError("assignment must cover every doc")
            for s, count in zip(*np.unique(assignment, return_counts=True)):
                if not 0 <= s < n_shards:
                    raise ValueError(f"shard {s} out of range")
                if count > rows_per_shard:
                    raise ValueError(f"shard {s} over capacity: {count} docs")

        next_row = [s * rows_per_shard for s in range(n_shards)]
        new_row = np.empty(self.num_docs, np.int64)
        for d, s in enumerate(assignment):
            new_row[d] = next_row[s]
            next_row[s] += 1
        moved = int((new_row != self._row_of).sum())
        if moved == 0:
            pass
        else:
            # permutation: new physical row r carries old row src[r]; rows
            # not holding a doc recycle the old empty rows (zeros), so src
            # is a full permutation and pad rows stay no-op
            src = np.full(self._padded_docs, -1, np.int64)
            src[new_row] = self._row_of
            spare = iter(sorted(
                set(range(self._padded_docs)) - set(int(r) for r in self._row_of)
            ))
            for r in range(self._padded_docs):
                if src[r] < 0:
                    src[r] = next(spare)
            self._permute_rows(src)
            self._cum_ins = self._cum_ins[src]  # occupancy bound rides the rows
            self._row_of = new_row
            self._doc_at = np.full(self._padded_docs, -1, np.int64)
            self._doc_at[new_row] = np.arange(self.num_docs)
            # placement changed: every physically-keyed cache is stale, and
            # in-flight async digests must not write back (epoch guard)
            self._resolved_cache = (-1, {})
            self._digest_row_valid[:] = False
            self._apply_blocks = None
            self._placement_epoch += 1
        shard_load = [0] * n_shards
        host_bound_load = [0] * n_shards
        for d, s in enumerate(assignment):
            shard_load[s] += int(sizes[d])
            if d in host_bound:
                host_bound_load[s] += int(sizes[d])
        return {"moved": moved, "shard_load": shard_load,
                "host_bound_load": host_bound_load}

    def _reshard_sizes(self) -> np.ndarray:
        """(num_docs,) per-doc load for reshard's balancing — live device
        slots under the padded layout; the paged subclass balances PAGES
        (the resource its pool actually spends)."""
        return np.asarray(self.state.num_slots)[self._row_of[: self.num_docs]]

    def _permute_rows(self, src: np.ndarray) -> None:
        """Move physical doc rows per ``src`` (new row r takes old row
        src[r]) — one gather over the padded layout's doc axis; the paged
        subclass permutes page TABLES and aux rows instead."""
        idx = jnp.asarray(src)
        state = PackedDocs(*(jnp.take(x, idx, axis=0) for x in self.state))
        self.state = shard_docs(state, self.mesh) if self.mesh is not None else state

    def _digest_tables_rows(self, rows: np.ndarray, n_real: int):
        """Digest hash tables for a GATHERED row subset (the sub-batch
        program) — same shapes/semantics as :meth:`_digest_tables` but
        row-indexed by position in ``rows``; small, so uncached.  Only the
        first ``n_real`` positions are real (the rest is power-of-two
        padding that repeats row 0 — its table entries must stay zero, and
        building them would also let the padding shadow the REAL row 0);
        everything here is O(n_real), not O(session)."""
        k = len(rows)
        sess_attr = self._frame_attrs.content_hashes()
        sess_keys = self._map_keys.content_hashes()
        enc = {}
        for i in range(n_real):
            d = int(self._doc_at[rows[i]])
            if d >= 0 and not self.docs[d].frame_mode and \
                    self.docs[d].encoder is not None:
                enc[i] = self.docs[d].encoder
        a_w = _width_bucket(max(
            [len(sess_attr)] + [len(e.attrs.content_hashes()) for e in enc.values()]
        ))
        k_w = _width_bucket(max(
            [len(sess_keys)] + [len(e.keys.content_hashes()) for e in enc.values()]
        ))
        c_w = self.comment_capacity
        sess_attr_t = np.zeros(a_w, np.uint32)
        sess_attr_t[: len(sess_attr)] = sess_attr
        sess_key_t = np.zeros(k_w, np.uint32)
        sess_key_t[: len(sess_keys)] = sess_keys
        row_map = np.full(k, -1, np.int32)
        obj_attr = np.zeros((_width_bucket(len(enc)) if enc else 0, a_w), np.uint32)
        obj_key = np.zeros((obj_attr.shape[0], k_w), np.uint32)
        comment_hash = np.zeros((k, c_w), np.uint32)
        # sorted: override-row assignment order must be a function of the
        # row set (it feeds row_map and the digest tables), never of dict
        # insertion history
        for j, (i, e) in enumerate(sorted(enc.items())):
            ah = e.attrs.content_hashes()
            kh = e.keys.content_hashes()
            row_map[i] = j
            obj_attr[j, : len(ah)] = ah
            obj_key[j, : len(kh)] = kh
            comment_hash[i, : min(c_w, len(ah))] = ah[:min(c_w, len(ah))]
        for i in range(n_real):
            d = int(self._doc_at[rows[i]])
            table = self._doc_comment_ids.get(d) if d >= 0 else None
            if table is not None and self.docs[d].frame_mode:
                ch = table.content_hashes()
                comment_hash[i, : min(c_w, len(ch))] = ch[:min(c_w, len(ch))]
        return (jnp.asarray(sess_attr_t), jnp.asarray(sess_key_t),
                jnp.asarray(comment_hash), jnp.asarray(row_map),
                jnp.asarray(obj_attr), jnp.asarray(obj_key))

    def _on_device_mask(self) -> np.ndarray:
        """(padded,) bool: rows currently backed by device state (their doc
        not fallback); placement goes through ``_row_of``."""
        on_dev = np.zeros(self._padded_docs, bool)
        for d, s in enumerate(self.docs):
            if not s.fallback:
                on_dev[self._row_of[d]] = True
        return on_dev

    def _schedule_rows_digest(self, rest: np.ndarray):
        """Dispatch the gathered sub-batch hash program for dirty rows
        ``rest`` (shared by digest() and digest_async()); returns the
        device refs ``(per_doc_dev, ov_dev)`` — callers slice the first
        ``len(rest)`` entries after fetching."""
        k = _width_bucket(len(rest))
        rows_idx = np.zeros(k, np.int32)
        rows_idx[: len(rest)] = rest
        mask = np.zeros(k, bool)
        mask[: len(rest)] = True
        sub = _gather_rows(self.state, jnp.asarray(rows_idx), self.mesh)
        dispatch_args = (
            sub, self.comment_capacity, jnp.asarray(mask),
            *self._digest_tables_rows(rows_idx, len(rest)),
        )
        if GLOBAL_DEVPROF.enabled:
            note_jit_dispatch(
                "_rows_digest_jit", _rows_digest_jit, dispatch_args,
            )
        return _rows_digest_jit(*dispatch_args)

    def _refresh_digest_rows(self):
        """Bring the carried per-row hash plane current for every on-device
        real-doc row, re-hashing only invalid rows: heavily-dirty blocks go
        through the fused block program (lookahead-pipelined, shared with
        the read paths); the remaining dirty rows pool into ONE gathered
        sub-batch program regardless of how many blocks they span."""
        on_dev = self._on_device_mask()
        need = ~self._digest_row_valid & on_dev & (self._doc_at >= 0)
        if not need.any():
            return on_dev
        n_blocks = -(-self._padded_docs // self._read_chunk)
        heavy = []
        for bi in range(n_blocks):
            lo, hi = self._block_bounds(bi)
            if int(need[lo:hi].sum()) > (hi - lo) // 4:
                heavy.append(bi)
        # heavy blocks: fused resolve+hash, lookahead-1 pipelined
        pending: Dict[int, object] = {}
        nxt = 0
        for j, bi in enumerate(heavy):
            while nxt < len(heavy) and nxt <= j + 1:
                entry = self._digest_resolution(heavy[nxt])
                self._start_digest_readback(entry)
                pending[heavy[nxt]] = entry
                nxt += 1
            entry = pending.pop(bi)
            lo, hi = self._block_bounds(bi)
            self._digest_plane[lo:hi] = entry.digest_per_doc
            self._digest_ov[lo:hi] = entry.overflow
            self._digest_row_valid[lo:hi] = on_dev[lo:hi] & (self._doc_at[lo:hi] >= 0)
            need[lo:hi] = False
        # the long tail: one gathered sub-batch program
        rest = np.nonzero(need)[0]
        if len(rest):
            per_doc_dev, ov_dev = self._schedule_rows_digest(rest)
            self._digest_plane[rest] = np.asarray(per_doc_dev)[: len(rest)]
            self._digest_ov[rest] = np.asarray(ov_dev)[: len(rest)]
            self._digest_row_valid[rest] = True
        return on_dev

    def digest(self, full: bool = True, refresh: bool = False) -> int:
        """Global convergence digest: with a mesh, XLA lowers the cross-doc
        reduction to an all-reduce over ICI.  Two sessions that converged
        hold equal digests.

        ``full=True`` (default) digests the COMPLETE document state — visible
        text, resolved formatting (LWW winner bits, link urls, comment-id
        sets) and map registers — matching the scope of the reference's
        convergence oracles (test/fuzz.ts:245-278 compare formatted text, not
        characters).  Interned identities are folded as content hashes, so
        two sessions that interned attrs/keys/values in different orders
        still agree.  ``full=False`` is the cheaper text-only digest (the
        comment planes compile away entirely — resolve.py ``with_comments``).

        Device-resident docs hash on device; fallback and overflowed docs —
        the ones the read paths route to scalar replay — are masked out of
        the device sum and hashed HOST-SIDE with the bit-identical per-doc
        formula (mesh.doc_digest_host and the format/register mirrors), so
        two converged peers agree even when their demotion histories differ.
        (The equivalence needs the replayed doc to fit the device capacities;
        a doc too large for any device row hashes consistently between
        fallback peers only.)

        The digest is a doc-sum of per-doc hashes carried in a host-side
        per-row plane; a call re-hashes only rows invalidated since the
        last one (see :meth:`_refresh_digest_rows`), then sums the plane
        mod 2^32 — identical to a whole-batch recompute while keeping the
        per-round cost proportional to touched docs.  ``refresh=True`` is
        the verification path: every row re-hashes from current device
        state, ignoring (and rebuilding) the carried plane."""
        with self.tracer.span("streaming.digest", full=full, refresh=refresh):
            return self._digest(full, refresh)

    def _digest(self, full: bool, refresh: bool) -> int:
        from .mesh import doc_digest_host

        if refresh:
            self._digest_row_valid[:] = False
            self._resolved_cache = (-1, {})

        replay_docs = [i for i, s in enumerate(self.docs) if s.fallback]
        if full:
            on_device_all = self._refresh_digest_rows()
            ok = (self._digest_row_valid & on_device_all & ~self._digest_ov
                  & (self._doc_at >= 0))
            total = int(self._digest_plane[ok].sum(dtype=np.uint32))
            replay_docs.extend(
                int(self._doc_at[r])
                for r in np.nonzero(self._digest_ov & on_device_all
                                    & (self._doc_at >= 0))[0]
            )
        else:
            on_device_all = self._on_device_mask()
            total = 0
            n_blocks = -(-self._padded_docs // self._read_chunk)
            for bi in range(n_blocks):
                lo, hi = self._block_bounds(bi)
                digest, overflow = _resolve_digest_jit(
                    self._state_block(bi), self.comment_capacity,
                    jnp.asarray(on_device_all[lo:hi]),
                )
                digest, ov = int(digest), np.asarray(overflow)
                total = (total + digest) & 0xFFFFFFFF
                replay_docs.extend(
                    int(self._doc_at[int(r) + lo])
                    for r in np.nonzero(ov & on_device_all[lo:hi])[0]
                    if int(self._doc_at[int(r) + lo]) >= 0
                )
        s_cap = self._slot_capacity
        for i in replay_docs:
            doc = _replay_doc(self._replay_changes(self.docs[i]))
            cps, slots = _doc_char_slots(doc)
            part = doc_digest_host(cps, slots, s_cap)
            if full:
                part = (part + _doc_full_extras_host(doc, slots, self._actor_table)) & 0xFFFFFFFF
            total = (total + part) & 0xFFFFFFFF
        return total

    def doc_digest(self, doc_index: int) -> int:
        """ONE doc's full-state convergence hash — exactly the per-doc term
        :meth:`digest` sums (device rows read the carried per-row hash
        plane; fallback/overflowed docs hash host-side with the
        bit-identical formula), so ``sum(doc_digest(i)) mod 2^32 ==
        digest()`` on an all-real-doc session (pinned by test).

        Interned identities fold as content hashes, so two SESSIONS that
        interned attrs/keys in different orders still agree per doc — this
        is the fleet tier's migration-cutover oracle: a doc shipped to a
        new host must hash byte-equal there before the old slot is
        released."""
        from .mesh import doc_digest_host

        sess = self.docs[doc_index]
        if not sess.fallback:
            on_device_all = self._refresh_digest_rows()
            row = int(self._row_of[doc_index])
            if on_device_all[row] and not self._digest_ov[row]:
                return int(self._digest_plane[row])
        doc = _replay_doc(self._replay_changes(sess))
        cps, slots = _doc_char_slots(doc)
        part = doc_digest_host(cps, slots, self._slot_capacity)
        return (part + _doc_full_extras_host(
            doc, slots, self._actor_table
        )) & 0xFFFFFFFF

    def digest_async(self) -> "_PendingDigest":
        """Schedule the full-state convergence digest WITHOUT synchronizing:
        the fused resolve+digest programs are enqueued (device work proceeds
        behind the queue) and the returned handle's ``wait()`` fetches only
        the per-block scalars + overflow vectors.  A per-round sync point
        then costs one enqueue (~ms) instead of a blocking device
        round-trip, and the digest overlaps the next round's host-side
        ingest parsing (VERDICT r2 weak #7).

        Semantics: the device hashes snapshot the state AT SCHEDULING time
        (the per-round block cache / carried row plane).  Docs that were
        already fallback — or that the overflow vectors route to scalar
        replay — are hashed at ``wait()`` time from their CURRENT change
        history, so call ``wait()`` before further ingestion whenever such
        docs exist (sessions with zero fallbacks/overflows may wait at any
        time)."""
        on_dev = self._on_device_mask()
        need = ~self._digest_row_valid & on_dev & (self._doc_at >= 0)
        parts = []
        n_blocks = -(-self._padded_docs // self._read_chunk)
        for bi in range(n_blocks):
            lo, hi = self._block_bounds(bi)
            if int(need[lo:hi].sum()) > (hi - lo) // 4:
                entry = self._digest_resolution(bi)
                # keep ONLY the hash-vector + overflow device refs — not
                # the _BlockResolution itself, whose resolved (D, S) planes
                # would otherwise stay pinned on device across the handle's
                # lifetime, defeating the block-cache memory bound
                parts.append(("block", lo, hi, entry.digest_dev,
                              entry.device.overflow))
                need[lo:hi] = False
        rest = np.nonzero(need)[0]
        if len(rest):
            per_doc, ov = self._schedule_rows_digest(rest)
            parts.append(("rows", rest, per_doc, ov))
        snapshot = (
            self._digest_plane.copy(), self._digest_ov.copy(),
            self._digest_row_valid.copy(), on_dev, self._doc_at.copy(),
            [i for i, s in enumerate(self.docs) if s.fallback],
        )
        return _PendingDigest(self, parts, snapshot, self.rounds,
                              self._placement_epoch)

    def _digest_tables(self, lo: int, hi: int):
        """Compact content-hash tables for the full digest: interned-id ->
        FNV-1a hash for link/mark attrs, per-doc dense comment ids, and map
        keys/string-values.

        Frame-mode docs all share the SESSION tables, so those ship as flat
        ``(A,)`` / ``(K,)`` arrays and are broadcast to rows on DEVICE — a
        host-side ``(D, A)`` materialization was ~A*4 bytes per doc per
        digest through the device link (128 MB/call at 2K docs x 16K attrs,
        the whole streaming digest stage cost in round 4).  Only
        object-path docs carry genuinely per-doc id spaces: their encoder
        tables ride in a sparse ``(n_obj, A)`` override matrix addressed by
        ``row_map`` (-1 = session tables).  Per-doc comment-id tables stay
        dense — (D, comment_capacity) is small.  Fallback rows are masked
        out device-side so their contents are irrelevant."""
        d_block = hi - lo
        sess_attr = self._frame_attrs.content_hashes()
        sess_keys = self._map_keys.content_hashes()
        # rows hold docs through the placement indirection: table row r-lo
        # describes the doc at physical row r (identity until reshard)
        enc = {
            row: self.docs[d].encoder
            for row in range(lo, hi)
            if (d := int(self._doc_at[row])) >= 0
            and not self.docs[d].frame_mode and self.docs[d].encoder is not None
        }
        # interner/placement fingerprint: tables only change when an interner
        # grows, object-doc membership shifts, or docs move rows — reuse the
        # device-resident copies otherwise (repeat transfers, and under a
        # mesh the replicated device_put, are the cost being avoided here)
        key = (
            len(sess_attr), len(sess_keys), self._placement_epoch,
            tuple((row, len(e.attrs.content_hashes()), len(e.keys.content_hashes()))
                  for row, e in sorted(enc.items())),
            tuple(sorted(
                (d, len(t)) for d, t in self._doc_comment_ids.items()
                if lo <= int(self._row_of[d]) < hi and self.docs[d].frame_mode
            )),
        )
        cached = self._digest_tables_cache.get((lo, hi))
        if cached is not None and cached[0] == key:
            return cached[1]
        a_w = _width_bucket(max(
            [len(sess_attr)] + [len(e.attrs.content_hashes()) for e in enc.values()]
        ))
        k_w = _width_bucket(max(
            [len(sess_keys)] + [len(e.keys.content_hashes()) for e in enc.values()]
        ))
        c_w = self.comment_capacity
        # override row count is bucketed like the widths: each new object-path
        # doc must not mint a fresh (n_obj, ·) shape -> XLA recompile
        n_obj_w = _width_bucket(len(enc)) if enc else 0
        sess_attr_t = np.zeros(a_w, np.uint32)
        sess_attr_t[: len(sess_attr)] = sess_attr
        sess_key_t = np.zeros(k_w, np.uint32)
        sess_key_t[: len(sess_keys)] = sess_keys
        row_map = np.full(d_block, -1, np.int32)
        obj_attr = np.zeros((n_obj_w, a_w), np.uint32)
        obj_key = np.zeros((n_obj_w, k_w), np.uint32)
        comment_hash = np.zeros((d_block, c_w), np.uint32)
        # sorted for the same reason as the cache key above: the override
        # matrix row order (and therefore row_map) must depend only on
        # which rows hold object docs
        for i, (row, e) in enumerate(sorted(enc.items())):
            ah = e.attrs.content_hashes()
            kh = e.keys.content_hashes()
            row_map[row - lo] = i
            obj_attr[i, : len(ah)] = ah
            obj_key[i, : len(kh)] = kh
            # object-path comment marks index the same per-doc attr interner
            comment_hash[row - lo, : min(c_w, len(ah))] = ah[:min(c_w, len(ah))]
        for d, table in sorted(self._doc_comment_ids.items()):
            row = int(self._row_of[d])
            if lo <= row < hi and self.docs[d].frame_mode:
                ch = table.content_hashes()
                comment_hash[row - lo, : min(c_w, len(ch))] = ch[:min(c_w, len(ch))]
        comment_hash_d = jnp.asarray(comment_hash)
        row_map_d = jnp.asarray(row_map)
        sess_attr_d = jnp.asarray(sess_attr_t)
        sess_key_d = jnp.asarray(sess_key_t)
        obj_attr_d = jnp.asarray(obj_attr)
        obj_key_d = jnp.asarray(obj_key)
        if self.mesh is not None:
            comment_hash_d, row_map_d = shard_docs(
                (comment_hash_d, row_map_d), self.mesh
            )
            repl = NamedSharding(self.mesh, P())  # session/override tables
            sess_attr_d, sess_key_d, obj_attr_d, obj_key_d = (
                jax.device_put(x, repl)
                for x in (sess_attr_d, sess_key_d, obj_attr_d, obj_key_d)
            )
        tables = (sess_attr_d, sess_key_d, comment_hash_d, row_map_d,
                  obj_attr_d, obj_key_d)
        self._digest_tables_cache[(lo, hi)] = (key, tables)
        return tables

    # -- checkpoint support (peritext_tpu.checkpoint.save_session) ----------

    def doc_history_frames(self, doc_index: int) -> List[bytes]:
        """The doc's full ingested history as wire frames — the durable,
        event-sourced form (re-ingesting them reconstructs the doc exactly;
        duplicate-tolerant, so crash-replay overlap is safe).  Frame-mode
        docs return their raw frames; object/fallback docs re-encode their
        log (lossless: the codec JSON-spills anything exotic)."""
        sess = self.docs[doc_index]
        if sess.frame_mode:
            return list(sess.frames)
        changes = self._replay_changes(sess)
        return [encode_frame(changes)] if changes else []

    @property
    def config(self) -> Dict[str, int]:
        """Constructor-shape configuration (for checkpoint restore)."""
        return {
            "num_docs": self.num_docs,
            "slot_capacity": self._slot_capacity,
            "mark_capacity": self._mark_capacity,
            "tomb_capacity": self._tomb_capacity,
            "round_insert_capacity": self.round_caps[0],
            "round_delete_capacity": self.round_caps[1],
            "round_mark_capacity": self.round_caps[2],
            "round_map_capacity": self.round_caps[3],
            "comment_capacity": self.comment_capacity,
            "map_capacity": self._map_capacity,
            # the REQUESTED value: a mesh session's effective block is its
            # whole padded batch, but a meshless restore must block reads
            "read_chunk": self._read_chunk_requested,
            # the storage layout rides in the config so checkpoint restore
            # (and serve snapshots) rebuild the same backend
            "layout": self._layout,
        }

    def frontier(self) -> Clock:
        """Merged vector-clock frontier across all docs (host-side metadata)."""
        merged: Clock = {}
        if self._clock_mat.size:
            col_max = self._clock_mat.max(axis=0)  # frame docs, vectorized
            for idx in np.nonzero(col_max)[0]:
                merged[self._actor_table.lookup(int(idx))] = int(col_max[idx])
        for sess in self.docs:
            for actor, seq in sorted(sess.clock.items()):
                merged[actor] = max(merged.get(actor, 0), seq)
        # sorted at the END so the frontier's key order (which reaches wire
        # frames via json) is a function of the actor set alone — the
        # clock-matrix loop above inserts in actor-table interning (arrival)
        # order, which is replica-local
        return dict(sorted(merged.items()))

    def overflow_count(self) -> int:
        """Docs the device read path cannot serve: apply-time capacity
        overflow OR resolve-time errors (mark anchor not found, comment attr
        beyond capacity) — exactly the docs read() routes to scalar replay
        and digest() masks.  A nonzero count on a converged session means
        capacities should be raised for the workload (correctness is
        preserved via replay either way)."""
        n_blocks = -(-self._padded_docs // self._read_chunk)
        return sum(
            int(self._resolution(bi).overflow.sum()) for bi in range(n_blocks)
        )

    def pending_count(self) -> int:
        pooled = sum(int(self._frame_mode[d].sum()) for d, _ in self._pool)
        return pooled + sum(len(s.pending) for s in self.docs)

    def pending_rounds_estimate(self) -> int:
        """Upper-bound estimate of the device rounds a full ``drain()``
        needs: the deepest per-doc pending queue.  Docs drain in parallel
        and causal admission feeds each doc at least one change per round
        it participates in, so the deepest queue bounds the round count —
        the supervisor scales its fused-drain watchdog budget by this so a
        legitimately deep backlog is not mistaken for a hung device."""
        if not self.num_docs:
            return 0
        per_doc = np.zeros(self.num_docs, np.int64)
        for doc_of, _ in self._pool:
            live = np.asarray(doc_of)[self._frame_mode[doc_of]]
            if live.size:
                per_doc += np.bincount(live, minlength=self.num_docs)
        for d, sess in enumerate(self.docs):
            per_doc[d] += len(sess.pending)
        return int(per_doc.max())

    @property
    def layout(self) -> str:
        """Resident-state storage layout ("padded", "paged" or "ragged")."""
        return self._layout

    def sync_device(self) -> None:
        """Block until all dispatched device work has completed (a cheap
        host fetch of one per-doc scalar plane) — the layout-independent
        sync point the supervisor's guarded rounds use."""
        np.asarray(self.state.num_slots)


def _doc_char_slots(doc: Doc):
    """(visible codepoints, their slot positions in full element order incl.
    tombstones) for a scalar replica's text list — the inputs the device
    digest formula needs (mesh.doc_digest_host).

    The text list is located by OBJECT, not by the literal ``["text"]``
    path: encode_doc/the frame parser accept a makeList under any key, and
    the device path adopts whichever list the doc created — so a fallback
    doc whose list key isn't "text" must still hash the same list a
    device-resident peer adopted (advisor r2: the path-keyed lookup hashed
    such docs as empty, silently breaking digest parity across demotion
    sets).  With several lists (device peers demote such docs, but both
    sides of the comparison must stay deterministic) the earliest-created
    one — minimum (ctr, actor) opid, the same total order compareOpIds
    defines — is hashed."""
    list_id = _doc_text_list_id(doc)
    if list_id is None:
        return [], []
    meta = doc._metadata[list_id]
    text = doc._objects[list_id]
    cps, slots, vis = [], [], 0
    for i, el in enumerate(meta):
        if not el.deleted:
            cps.append(ord(text[vis]))
            slots.append(i)
            vis += 1
    return cps, slots


class _PendingDigest:
    """Deferred digest handle from :meth:`StreamingMerge.digest_async`.

    Holds references to the scheduled per-doc hash VECTORS and overflow
    vectors only (safe across cache eviction — never the resolved planes)
    plus a scheduling-time snapshot of the carried row plane and masks;
    ``wait`` merges the fetched vectors into the snapshot, folds host-side
    replay hashes exactly as ``digest()`` does, writes the fresh hashes
    back into the live plane when no round/reshard intervened, then
    releases the device refs."""

    __slots__ = ("_session", "_parts", "_snapshot", "_value", "_stamp",
                 "_epoch")

    def __init__(self, session: "StreamingMerge", parts, snapshot,
                 stamp: int, epoch: int) -> None:
        self._session = session
        self._parts = parts
        self._snapshot = snapshot
        self._value: Optional[int] = None
        self._stamp = stamp  # session round at scheduling time
        self._epoch = epoch  # placement epoch at scheduling time

    def wait(self) -> int:
        if self._value is not None:
            return self._value
        s = self._session
        plane, ovp, valid, on_dev, doc_at, fallback_docs = self._snapshot
        writeback = (s.rounds == self._stamp
                     and s._placement_epoch == self._epoch)
        for part in self._parts:
            if part[0] == "block":
                _, lo, hi, vec_dev, ov_dev = part
                vec, ov = np.asarray(vec_dev), np.asarray(ov_dev)
                rows = np.arange(lo, hi)
            else:
                _, rows, vec_dev, ov_dev = part
                vec = np.asarray(vec_dev)[: len(rows)]
                ov = np.asarray(ov_dev)[: len(rows)]
            plane[rows], ovp[rows] = vec, ov
            valid[rows] = on_dev[rows] & (doc_at[rows] >= 0)
            if writeback:
                # a round/reshard in between makes these hashes describe
                # rows that no longer hold the same content — never write
                # back then (the snapshot math above still answers for
                # scheduling time)
                s._digest_plane[rows] = vec
                s._digest_ov[rows] = ov
                s._digest_row_valid[rows] = valid[rows]
        ok = valid & on_dev & ~ovp & (doc_at >= 0)
        total = int(plane[ok].sum(dtype=np.uint32))
        replay_docs = list(fallback_docs)
        replay_docs.extend(
            int(doc_at[r]) for r in np.nonzero(ovp & on_dev & (doc_at >= 0))[0]
        )
        from .mesh import doc_digest_host

        s_cap = s._slot_capacity
        for i in replay_docs:
            doc = _replay_doc(s._replay_changes(s.docs[i]))
            cps, slots = _doc_char_slots(doc)
            part = doc_digest_host(cps, slots, s_cap)
            part = (part + _doc_full_extras_host(doc, slots, s._actor_table)) & 0xFFFFFFFF
            total = (total + part) & 0xFFFFFFFF
        self._value = total
        self._parts = ()  # release the device refs once folded
        self._snapshot = None
        return total


def _doc_text_list_id(doc: Doc):
    """The doc's text list object id, or None (see _doc_char_slots)."""
    list_ids = [
        oid for oid, meta in doc._metadata.items()
        if isinstance(meta, list) and oid in doc._objects
    ]
    if not list_ids:
        return None
    return min(list_ids)  # OpId tuples order exactly as compareOpIds


def _doc_path_of_object(doc: Doc, target) -> Optional[list]:
    """Key path from the root map to ``target`` (BFS over map children)."""
    from ..core.doc import MapMeta
    from ..core.opids import ROOT

    queue = [(ROOT, [])]
    seen = set()
    while queue:
        oid, path = queue.pop(0)
        if oid in seen:
            continue
        seen.add(oid)
        meta = doc._metadata.get(oid)
        if not isinstance(meta, MapMeta):
            continue
        for key, child in sorted(meta.children.items()):  # deterministic BFS path
            if child == target:
                return path + [key]
            queue.append((child, path + [key]))
    return None


def _doc_full_extras_host(doc: Doc, slot_positions, actor_table) -> int:
    """Formatting + map-register digest contribution of ONE scalar-replay
    doc, bit-identical to the device sums in _resolve_full_digest_jit (the
    mirrors live in mesh.format_digest_host / register_digest_host).
    ``slot_positions`` are the visible characters' element-order slots from
    :func:`_doc_char_slots`."""
    import json as _json

    from ..core.doc import MapMeta
    from ..core.opids import ROOT
    from ..ops.packed import (
        MAX_CTR,
        OBJ_ROOT,
        VK_FALSE,
        VK_INT,
        VK_NULL,
        VK_OBJ,
        VK_STR,
        VK_TEXT,
        VK_TRUE,
        pack_id,
    )
    from ..ops.resolve import COMMENT_TYPE
    from ..schema import ALL_MARKS
    from ..utils.interning import content_hash32
    from .mesh import format_digest_host, register_digest_host

    # -- formatting: expand spans to per-visible-char mark maps -------------
    marks_per_char: list = []
    list_id = _doc_text_list_id(doc)
    if list_id is not None and slot_positions:
        path = _doc_path_of_object(doc, list_id)
        if path is not None:
            for span in doc.get_text_with_formatting(path):
                marks_per_char.extend([span["marks"]] * len(span["text"]))
    if len(marks_per_char) != len(slot_positions):
        # degenerate doc (unreachable list) — formatting contributes nothing,
        # deterministically on every peer applying the same rule
        marks_per_char = [{}] * len(slot_positions)
    total = format_digest_host(
        slot_positions, marks_per_char, ALL_MARKS, COMMENT_TYPE
    )

    # -- map registers: LWW winner per (object, key), live keys only --------
    def packed_u32(opid) -> int:
        ctr, actor = opid
        idx = actor_table.get(actor)
        if idx is None or ctr > MAX_CTR:
            # undeclared actor / over-wide counter: no device peer can hold
            # this doc; a deterministic stand-in keeps fallback peers equal
            return content_hash32(f"{ctr}@{actor}")
        return pack_id(ctr, idx) & 0xFFFFFFFF

    rows = []
    for oid, meta in doc._metadata.items():
        if not isinstance(meta, MapMeta):
            continue
        obj_u32 = (OBJ_ROOT & 0xFFFFFFFF) if oid is ROOT else packed_u32(oid)
        obj = doc._objects.get(oid, {})
        for key, value in obj.items():
            if isinstance(value, bool):
                kind, val = (VK_TRUE, 0) if value else (VK_FALSE, 0)
            elif isinstance(value, int):
                kind, val = VK_INT, value & 0xFFFFFFFF
            elif isinstance(value, str):
                kind, val = VK_STR, content_hash32(value)
            elif value is None:
                kind, val = VK_NULL, 0
            elif isinstance(value, dict):
                kind, val = VK_OBJ, packed_u32(meta.children[key])
            elif isinstance(value, list):
                kind, val = VK_TEXT, packed_u32(meta.children[key])
            else:
                # device-inexpressible value (float/containers): the doc is
                # in fallback on every peer; hash a canonical JSON form
                kind = 255
                val = content_hash32(_json.dumps(value, sort_keys=True))
            rows.append((obj_u32, content_hash32(key), kind, val))
    return (total + register_digest_host(rows)) & 0xFFFFFFFF


def _replay_doc(changes: List[Change]) -> Doc:
    doc = Doc("streaming-fallback")
    ordered, stuck = causal_schedule(changes)
    for ch in ordered:
        doc.apply_change(ch)
    return doc


def _replay_spans(changes: List[Change]) -> List[FormatSpan]:
    return _replay_doc(changes).get_text_with_formatting(["text"])


def rebalance(workload_sizes: Sequence[int], num_shards: int) -> List[List[int]]:
    """Greedy load-balance: assign doc indices to shards equalizing total op
    counts (host-side placement; docs are independent so no device
    all-to-all is needed — placement happens before transfer)."""
    order = sorted(range(len(workload_sizes)), key=lambda i: -workload_sizes[i])
    shards: List[List[int]] = [[] for _ in range(num_shards)]
    loads = [0] * num_shards
    for i in order:
        target = loads.index(min(loads))
        shards[target].append(i)
        loads[target] += workload_sizes[i]
    return shards
