"""Host-side encoding: change logs -> padded split-stream device tensors.

The irregular, string-y work that is wrong for the TPU happens here: causal
sorting (parallel/causal.py), actor/attr interning (utils/interning.py),
boundary-anchor flattening, and padding/bucketing.

Ops are split into three streams per document, exploiting the commutation
structure of the packed representation (ops/packed.py):

* **inserts** — the only truly sequential stream (each insert's position
  depends on prior inserts); consumed by the per-doc fori_loop.
* **deletes** — idempotent tombstone sets; they commute with each other and
  with inserts' *placement* (the RGA skip compares only element ids,
  reference src/micromerge.ts:1201-1208), so they apply as one vectorized
  pass after all inserts.
* **marks** — grow-only table rows; they are encoded host-side directly in
  mark-table layout and appended with one vectorized scatter.

All identifiers are packed int32s (packed.pack_id).  Documents whose logs the
device path cannot express (non-text objects, too many actors/ops) are routed
to the scalar-oracle fallback (``EncodedBatch.fallback_docs``).
"""

from __future__ import annotations

from array import array
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import native
from ..core.opids import HEAD, ROOT
from ..core.types import AFTER, BEFORE, END_OF_TEXT, START_OF_TEXT, Boundary, Change
from ..obs import GLOBAL_COUNTERS, GLOBAL_TRACER
from ..parallel.causal import causal_sort, native_loaded
from ..schema import MARK_INDEX
from ..utils.interning import Interner, OrderedActorTable
from .packed import (
    ACTOR_BITS,
    BK_AFTER,
    BK_BEFORE,
    BK_END_OF_TEXT,
    BK_START_OF_TEXT,
    MA_ADD,
    MA_REMOVE,
    MAX_ACTORS,
    MAX_CTR,
    OBJ_ROOT,
    VK_DELETED,
    VK_FALSE,
    VK_INT,
    VK_NULL,
    VK_OBJ,
    VK_STR,
    VK_TEXT,
    VK_TRUE,
    pack_id,
)

_BK = {
    BEFORE: BK_BEFORE,
    AFTER: BK_AFTER,
    START_OF_TEXT: BK_START_OF_TEXT,
    END_OF_TEXT: BK_END_OF_TEXT,
}

#: Columns of a host-side mark row, in PackedDocs mark-table order.
MARK_COLS = (
    "m_action",
    "m_type",
    "m_start_kind",
    "m_start_elem",
    "m_end_kind",
    "m_end_elem",
    "m_op",
    "m_attr",
)

# canonical map-register column order lives in packed.py (device & host
# share one definition); re-exported here for stream-filling callers
from .packed import MAP_STREAM_COLS  # noqa: E402  (grouped with MARK_COLS)


@dataclass
class EncodedBatch:
    """Padded split-stream batch plus intern tables for decoding outputs."""

    # insert stream (D, KI)
    ins_ref: np.ndarray  # packed predecessor elem (0 = HEAD)
    ins_op: np.ndarray  # packed op id (0 = pad)
    ins_char: np.ndarray  # int32 codepoint
    # delete stream (D, KD); packed target elem (0 = pad)
    del_target: np.ndarray
    # mark stream (D, KM) per MARK_COLS
    marks: Dict[str, np.ndarray]
    mark_count: np.ndarray  # int32 (D,)
    # map-register stream (D, KP) per MAP_STREAM_COLS
    map_ops: Dict[str, np.ndarray]
    map_count: np.ndarray  # int32 (D,)
    num_ops: np.ndarray  # int32 (D,) total encoded ops (stats)
    actor_tables: List[OrderedActorTable]
    attr_tables: List[Interner]
    #: per-doc interner for map keys and string values
    map_tables: List[Interner]
    #: doc indices the device path cannot express; resolved by the oracle
    fallback_docs: List[int] = field(default_factory=list)

    @property
    def num_docs(self) -> int:
        return self.ins_op.shape[0]


class _DocStreams:
    def __init__(self) -> None:
        self.ins: List[Tuple[int, int, int]] = []  # (ref, op, char)
        self.dels: List[int] = []
        self.marks: List[Tuple[int, ...]] = []  # MARK_COLS order
        self.maps: List[Tuple[int, int, int, int, int]] = []  # MAP_STREAM_COLS


def _pack_opid(opid, actors: OrderedActorTable) -> int:
    ctr, actor = opid
    if ctr > MAX_CTR:
        raise OverflowError(f"op counter {ctr} exceeds packed capacity")
    return pack_id(ctr, actors.intern(actor))


def _pack_boundary(b: Boundary, actors: OrderedActorTable) -> Tuple[int, int]:
    if b.elem is not None:
        return _BK[b.kind], _pack_opid(b.elem, actors)
    return _BK[b.kind], 0


def _encode_value(value, keys: Interner):
    """Map-set value -> (VK_*, payload), or None when inexpressible on
    device (nested containers, floats, out-of-range ints -> oracle)."""
    if isinstance(value, bool):
        return (VK_TRUE if value else VK_FALSE), 0
    if value is None:
        return VK_NULL, 0
    if isinstance(value, str):
        return VK_STR, keys.intern(value)
    if isinstance(value, int) and -(2**31) <= value < 2**31:
        return VK_INT, value
    return None


def encode_doc(
    changes: Sequence[Change],
    actors: OrderedActorTable,
    attrs: Interner,
    keys: Interner,
    text_obj=None,
    map_objs: Optional[set] = None,
    text_key: Optional[str] = None,
):
    """Split one document's causally-sorted changes into four streams
    (text inserts / deletes / marks, plus map-register writes).
    Returns (_DocStreams, ok, text_obj, text_key); ok=False -> host fallback.
    ``text_obj`` (the op id of the document's text list), ``map_objs`` (the
    packed ids of known map objects, mutated in place) and ``text_key`` carry
    across incremental rounds for streaming sessions."""
    streams = _DocStreams()
    if map_objs is None:
        map_objs = set()

    for change in changes:
        for op in change.ops:
            if text_obj is not None and op.obj == text_obj:
                if op.action == "set" and op.insert:
                    ref = 0 if op.elem_id is HEAD else _pack_opid(op.elem_id, actors)
                    streams.ins.append((ref, _pack_opid(op.opid, actors), ord(op.value)))
                elif op.action == "del":
                    streams.dels.append(_pack_opid(op.elem_id, actors))
                elif op.action in ("addMark", "removeMark"):
                    sk, se = _pack_boundary(op.start, actors)
                    ek, ee = _pack_boundary(op.end, actors)
                    attr = 0
                    if op.attrs:
                        # key-presence, not truthiness: empty url/id is a value
                        if "url" in op.attrs:
                            attr = attrs.intern(op.attrs["url"])
                        elif "id" in op.attrs:
                            attr = attrs.intern(op.attrs["id"])
                    streams.marks.append(
                        (
                            MA_ADD if op.action == "addMark" else MA_REMOVE,
                            MARK_INDEX[op.mark_type],
                            sk,
                            se,
                            ek,
                            ee,
                            _pack_opid(op.opid, actors),
                            attr,
                        )
                    )
                else:
                    return streams, False, text_obj, text_key
                continue

            # Map-object ops (reference src/micromerge.ts:1151-1175): the
            # containing object must be the root or a known child map.
            if op.obj is ROOT:
                pobj = OBJ_ROOT
            else:
                pobj = _pack_opid(op.obj, actors)
                if pobj not in map_objs:
                    return streams, False, text_obj, text_key
            if op.key is None:
                return streams, False, text_obj, text_key
            popid = _pack_opid(op.opid, actors)
            pkey = keys.intern(op.key)
            if op.action == "makeList":
                # exactly one list (the text sequence) is device-expressible
                if text_obj is not None:
                    return streams, False, text_obj, text_key
                text_obj = op.opid
                text_key = op.key
                streams.maps.append((pobj, pkey, popid, VK_TEXT, popid))
            elif op.action == "makeMap":
                map_objs.add(popid)
                streams.maps.append((pobj, pkey, popid, VK_OBJ, popid))
            elif op.action == "set" and not op.insert:
                encoded = _encode_value(op.value, keys)
                if encoded is None:
                    return streams, False, text_obj, text_key
                streams.maps.append((pobj, pkey, popid, *encoded))
            elif op.action == "del":
                streams.maps.append((pobj, pkey, popid, VK_DELETED, 0))
            else:
                return streams, False, text_obj, text_key
    return streams, True, text_obj, text_key


class DocEncoder:
    """Persistent per-document encoder for incremental (streaming) rounds.

    The actor table must be declared up front: packed int32 op-ID comparison
    equals (counter, actor-string) order only when actor indices follow string
    order, and a table that grows mid-session could violate that
    (utils/interning.OrderedActorTable).  A change from an undeclared actor
    marks the encoder failed; the streaming layer then falls back to scalar
    replay for that document.
    """

    def __init__(self, actor_names) -> None:
        self.actors = OrderedActorTable(actor_names)
        self.attrs = Interner()
        self.keys = Interner()
        self.text_obj = None
        self.text_key: Optional[str] = None
        self.map_objs: set = set()
        self.ok = len(self.actors) - 1 <= MAX_ACTORS

    def encode_increment(self, ordered_changes: Sequence[Change]):
        """Encode one round's causally-ordered new changes.  Returns
        (_DocStreams, ok); once not ok, the encoder stays failed."""
        if not self.ok:
            return _DocStreams(), False
        try:
            streams, ok, self.text_obj, self.text_key = encode_doc(
                ordered_changes, self.actors, self.attrs, self.keys,
                self.text_obj, self.map_objs, self.text_key,
            )
        except (OverflowError, KeyError):  # ctr overflow / undeclared actor
            ok = False
            streams = _DocStreams()
        self.ok = ok
        return streams, ok


def _round8(n: int) -> int:
    return max(8, -(-n // 8) * 8)


#: shared all-empty stream set: the stand-in for capacity-fallback docs in
#: the ragged batch encode — their real streams must not inflate the
#: batch's widths, and their rows stay all-zero no-ops
_EMPTY_STREAMS = _DocStreams()

#: the device columns of each stream, in stream order: insert, delete,
#: mark, map (a _DocStreams row holds one value per column)
_STREAMS = (("ins_ref", "ins_op", "ins_char"), ("del_target",), MARK_COLS,
            MAP_STREAM_COLS)


def _split_doc(ordered: Sequence[Change], changes: Sequence[Change]):
    """One doc's stream split in Python, on its causally ``ordered``
    changes: ``(streams, ok, actors, attrs, keys)``, the streams empty when
    not ok.  ``changes`` is everything delivered, duplicates included: its
    actors make the actor table."""
    actors = OrderedActorTable(
        {ch.actor for ch in changes} | {op.opid[1] for ch in changes for op in ch.ops}
    )
    attrs = Interner()
    keys = Interner()
    # len(actors) includes the reserved index-0 None slot, so the largest
    # assigned actor index is len(actors) - 1, which must fit ACTOR_BITS.
    ok = len(actors) - 1 <= MAX_ACTORS
    streams = _DocStreams()
    if ok:
        try:
            streams, ok, _, _ = encode_doc(ordered, actors, attrs, keys)
        except OverflowError:
            ok = False
    if not ok:
        streams = _DocStreams()
    return streams, ok, actors, attrs, keys


def _stream_rows(streams: _DocStreams):
    return streams.ins, streams.dels, streams.marks, streams.maps


def _encode_doc_streams_python(workloads, tracer):
    """The per-doc Python encode, used when the native core is not loaded:
    each doc runs under a ``batch.encode.sort`` span (gather and causal
    sort) and a ``batch.encode.split`` span (tables and stream split).  The
    loop stays doc by doc: sorting every doc before splitting any reads
    each doc's changes cold again, 2.5-3.5% slower on a TPU v5e host."""
    per_doc: List[_DocStreams] = []
    actor_tables: List[OrderedActorTable] = []
    attr_tables: List[Interner] = []
    map_tables: List[Interner] = []
    fallback: List[int] = []
    for doc_index, queues in enumerate(workloads):
        with tracer.span("batch.encode.sort", doc=doc_index) as sp:
            all_changes = [ch for log in queues.values() for ch in log]
            ordered = causal_sort(all_changes)
            sp.args["changes"] = len(all_changes)
        with tracer.span("batch.encode.split", doc=doc_index) as sp:
            streams, ok, actors, attrs, keys = _split_doc(ordered, all_changes)
            if not ok:
                fallback.append(doc_index)
            sp.args["ops"] = sum(map(len, _stream_rows(streams)))
        per_doc.append(streams)
        actor_tables.append(actors)
        attr_tables.append(attrs)
        map_tables.append(keys)
    return per_doc, fallback, actor_tables, attr_tables, map_tables


# -- columnar encode (the native path) ---------------------------------------
#
# Each doc's changes are flattened once, in delivery order, into the int
# columns of native.cpp's pt_encode_batch: a header per change (actor index,
# seq, deps) and a row per op in the pt_parse_changes column layout, its
# trailing zero columns dropped, ids packed inline.  The flatten is
# flatten.cpp's walk of the objects where the walker is built and takes the
# doc, else _flatten_rows; the two give the same columns.  One native call then
# schedules every doc (the causal sort's exact order) and walks its ops
# through encode_doc's rules straight into the stream arrays.  Strings (mark
# attrs, map keys and values) travel as ids into per-batch lists and are
# interned in scheduled order by that walk, so every table equals
# encode_doc's.  A doc whose walk stops where encode_doc falls back is split
# again in Python on the native order; a doc the flatten cannot express (an
# actor or value encode_doc would reject or treat specially) enters the
# native call empty and is causally sorted and split in Python after it.
# Either way encode_doc decides it.

#: pt_encode_batch's per-doc status other than 0 (encoded): split in
#: Python, causal gap, over a capacity
_REDO, _GAP, _OVER = 1, 2, 3


class _Unexpressed(Exception):
    """An op the flatten leaves to encode_doc (inexpressible map value,
    non-string key or attr)."""


#: what stops a doc's flatten: the doc is then split in Python
_UNEXPRESSED = (_Unexpressed, AttributeError, KeyError, OverflowError,
                TypeError, ValueError)


class _Flat:
    """A batch's changes as int columns, filled doc by doc by
    :func:`_flatten_doc`: per change a header (actor index, seq, dep count,
    op count), per dep an (actor index, seq) pair, per op a row."""

    def __init__(self) -> None:
        self.heads = array("i")
        self.deps = array("i")
        self.ops = array("i")
        self.doc_ch_off = [0]
        self.doc_int_off: List[int] = []
        self.doc_n_actors: List[int] = []
        #: per doc: whether it entered as rows (else it is split in Python)
        self.expressed: List[bool] = []
        #: per doc: the most rows its insert, delete, mark and map streams
        #: can take
        self.bounds: List[Tuple[int, int, int, int]] = []
        self.attr_strs: List[str] = []
        self.key_strs: List[str] = []
        self.doc_attr_off = [0]
        self.doc_key_off = [0]
        #: per doc: its changes in delivery order, and its sorted actors
        self.changes: List[List[Change]] = []
        self.actors: List[List[str]] = []

    def columns(self):
        """pt_encode_batch's input columns."""
        heads = np.frombuffer(self.heads, np.int32).reshape(-1, 4)
        deps = np.frombuffer(self.deps, np.int32).reshape(-1, 2)

        def offsets(counts):
            return np.concatenate([[0], np.cumsum(counts, dtype=np.int32)]).astype(np.int32)

        return (
            np.asarray(self.doc_ch_off, np.int32),
            np.asarray(self.doc_int_off, np.int32),
            np.asarray(self.doc_n_actors, np.int32),
            heads[:, 0], heads[:, 1],
            offsets(heads[:, 2]), deps[:, 0], deps[:, 1],
            offsets(heads[:, 3]), np.frombuffer(self.ops, np.int32),
            np.asarray(self.doc_attr_off, np.int32),
            np.asarray(self.doc_key_off, np.int32),
        )

    def add_doc(self, changes, actors, expressed, bounds, heads, deps, rows=b"",
                attr_strs=(), key_strs=()) -> None:
        """``heads``, ``deps`` and ``rows``: int32 columns as bytes, in the
        machine's byte order."""
        self.doc_int_off.append(len(self.ops))
        self.heads.frombytes(heads)
        self.deps.frombytes(deps)
        self.ops.frombytes(rows)
        self.attr_strs += attr_strs
        self.key_strs += key_strs
        self.changes.append(changes)
        self.actors.append(actors)
        self.doc_n_actors.append(len(actors) + 1)
        self.expressed.append(expressed)
        self.bounds.append(bounds)
        self.doc_ch_off.append(len(self.heads) // 4)
        self.doc_attr_off.append(len(self.attr_strs))
        self.doc_key_off.append(len(self.key_strs))


def _string_id(s: str, ids: Dict[str, int], strs: List[str]) -> int:
    g = ids.get(s)
    if g is None:
        g = ids[s] = len(strs)
        strs.append(s)
    return g


def _map_row(op, pobj: int, popid: int, string_id) -> tuple:
    """The row of an op on a map object: kind 7 for a makeList, else kind 6
    with its register value kind and payload (c0-c5); ``string_id`` gives
    a key or string value its batch-wide id."""
    key = op.key
    if type(key) is not str:
        raise _Unexpressed(op.action)
    k = string_id(key)
    action = op.action
    if action == "makeList":
        return (7, pobj, popid, k)
    if action == "makeMap":
        return (6, pobj, popid, k, VK_OBJ, popid)
    if action == "del" and op.elem_id is None:
        return (6, pobj, popid, k, VK_DELETED, 0)
    if action != "set":
        raise _Unexpressed(action)
    value = op.value  # _encode_value's cases
    if value is True or value is False:
        return (6, pobj, popid, k, VK_TRUE if value else VK_FALSE, 0)
    if value is None:
        return (6, pobj, popid, k, VK_NULL, 0)
    if type(value) is str:
        return (6, pobj, popid, k, VK_STR, string_id(value) + 1)
    if type(value) is int and -(2**31) <= value < 2**31:
        return (6, pobj, popid, k, VK_INT, value)
    raise _Unexpressed("map value")


def _flatten_rows(changes: List[Change], flat: _Flat) -> int:
    """Append a doc's headers, deps and op rows to ``flat`` in one pass
    over its changes; returns its op count.  Raises one of
    ``_UNEXPRESSED`` (and leaves ``flat`` as it was) where the doc needs
    encode_doc: an op id, element or dep of an actor that sent no change, a
    value the device cannot hold, more actors than ids can pack.  The
    native flatten (``native/src/flatten.cpp``) is its twin, column for
    column; this runs where that one is not built or declines the doc."""
    actors = sorted({ch.actor for ch in changes})
    if len(actors) > MAX_ACTORS:
        raise _Unexpressed("actors")
    index = dict(zip(actors, range(1, len(actors) + 1)))
    attr_base, key_base = len(flat.attr_strs), len(flat.key_strs)
    attr_ids: Dict[str, int] = {}
    key_ids: Dict[str, int] = {}
    attr_strs: List[str] = []
    key_strs: List[str] = []

    def key_id(s: str) -> int:
        return key_base + _string_id(s, key_ids, key_strs)

    heads: List[int] = []
    deps: List[int] = []
    rows: List[int] = []
    put_head, put_dep, put = heads.extend, deps.extend, rows.extend
    n_ins = n_del = n_mark = n_ops = 0
    bits, head, root, bk, mark_index = ACTOR_BITS, HEAD, ROOT, _BK, MARK_INDEX
    for ch in changes:
        ch_deps = ch.deps
        ops = ch.ops
        put_head((index[ch.actor], ch.seq, len(ch_deps), len(ops)))
        for a, s in ch_deps.items():
            put_dep((index[a], s))
        n_ops += len(ops)
        for op in ops:
            action = op.action
            obj = op.obj
            ctr, actor = op.opid
            popid = (ctr << bits) | index[actor]
            pobj = -1 if obj is root else (obj[0] << bits) | index[obj[1]]
            if action == "set" and op.insert:
                e = op.elem_id
                ref = 0 if e is head else (e[0] << bits) | index[e[1]]
                put((0, pobj, popid, ref, ord(op.value)))
                n_ins += 1
            elif action == "del" and op.key is None:
                e = op.elem_id
                put((1, pobj, popid, (e[0] << bits) | index[e[1]]))
                n_del += 1
            elif action == "addMark" or action == "removeMark":
                start, end = op.start, op.end
                se, ee = start.elem, end.elem
                attr = 0
                attrs = op.attrs
                # key-presence, not truthiness: empty url/id is a value
                if attrs and ("url" in attrs or "id" in attrs):
                    value = attrs["url"] if "url" in attrs else attrs["id"]
                    if type(value) is not str:
                        raise _Unexpressed("mark attr")
                    attr = attr_base + _string_id(value, attr_ids, attr_strs) + 1
                put((
                    2, pobj, popid,
                    MA_ADD if action == "addMark" else MA_REMOVE,
                    mark_index[op.mark_type],
                    bk[start.kind], 0 if se is None else (se[0] << bits) | index[se[1]],
                    bk[end.kind], 0 if ee is None else (ee[0] << bits) | index[ee[1]],
                    attr,
                ))
                n_mark += 1
            else:
                put(_map_row(op, pobj, popid, key_id))
    # array() raises OverflowError for a counter over MAX_CTR
    heads, deps, rows = (array("i", c).tobytes() for c in (heads, deps, rows))
    flat.add_doc(changes, actors, True,
                 (n_ins, n_del, n_mark, n_ops - n_ins - n_del - n_mark),
                 heads, deps, rows, attr_strs, key_strs)
    return n_ops


#: the constants flatten.cpp's walk compares against, in its order
_WALK_CONSTS = (ROOT, HEAD, MARK_INDEX, _BK, ACTOR_BITS, MAX_ACTORS, MA_ADD, MA_REMOVE,
                VK_DELETED, VK_STR, VK_INT, VK_TRUE, VK_FALSE, VK_NULL, VK_OBJ)


def _flatten_doc(queues: Dict[str, List[Change]], flat: _Flat,
                 walk=None) -> Tuple[int, int, bool, bool]:
    """Append one doc to ``flat``, in delivery order (no sort).  ``walk``
    is the native flatten (``native.flatten_walker()``), or None.  Returns
    the doc's change and op counts, whether its ops became rows, and
    whether the native walk made them."""
    if walk is not None:
        walked = walk(queues, _WALK_CONSTS, len(flat.attr_strs), len(flat.key_strs))
        if walked is not None:
            changes, actors, bounds, *columns = walked
            flat.add_doc(changes, actors, True, bounds, *columns)
            return len(changes), sum(bounds), True, True
    changes = [ch for log in queues.values() for ch in log]
    try:
        return len(changes), _flatten_rows(changes, flat), True, False
    except _UNEXPRESSED:
        # it enters the native call empty, each stream with room for every
        # op (an op makes at most one row), and is split in Python after it
        n_ops = sum(len(ch.ops) for ch in changes)
        flat.add_doc(changes, [], False, (n_ops,) * 4, b"", b"")
        return len(changes), n_ops, False, False


def _alloc_streams(sizes) -> Dict[str, np.ndarray]:
    """Zeroed flat buffers for every stream column, ``sizes`` rows each."""
    return {col: np.zeros(int(n), np.int32)
            for cols, n in zip(_STREAMS, sizes) for col in cols}


def _write_streams(columns: Dict[str, np.ndarray], offsets, streams: _DocStreams) -> None:
    """Write one doc's stream rows into flat buffers at ``offsets``."""
    for cols, off, rows in zip(_STREAMS, offsets, _stream_rows(streams)):
        if rows:
            arr = np.asarray(rows, np.int32).reshape(len(rows), len(cols))
            for c, col in enumerate(cols):
                columns[col][off:off + len(rows)] = arr[:, c]


def _encode_columnar(workloads, tracer, widths, finish):
    """Flatten, schedule and scatter ``workloads`` (module comment above).

    ``widths``: the padded layout's (insert, delete, mark, map) row
    capacities, each None for the batch's own maximum; None itself gives
    each doc just the rows it may need (the per-doc streams of
    :func:`encode_doc_streams`).  Returns ``finish(columns, row_off,
    counts, fallback, actor_tables, attr_tables, map_tables)``, called
    inside the last ``batch.encode.pad`` span: flat stream buffers, each
    doc's first row per stream, and the rows its streams took (zero where
    encode fell back)."""
    flat = _Flat()
    walk = native.flatten_walker()
    walked = 0
    for doc_index, queues in enumerate(workloads):
        with tracer.span("batch.encode.split", doc=doc_index) as sp:
            sp.args["changes"], sp.args["ops"], sp.args["rows"], by_walk = (
                _flatten_doc(queues, flat, walk))
        walked += by_walk
    d = len(workloads)
    GLOBAL_COUNTERS.add("encode.flatten.native", walked)
    GLOBAL_COUNTERS.add("encode.flatten.python", d - walked)
    with tracer.span("batch.encode.pad"):
        bounds = np.asarray(flat.bounds, np.int64).reshape(d, 4)
        if widths is None:
            row_cap = bounds
            row_off = np.cumsum(bounds, axis=0) - bounds
            sizes = bounds.sum(axis=0)
        else:
            width = np.asarray([cap or _round8(int(bounds[:, s].max(initial=0)))
                                for s, cap in enumerate(widths)], np.int64)
            row_cap = np.broadcast_to(width, (d, 4))
            row_off = np.arange(d, dtype=np.int64)[:, None] * width
            sizes = d * width
        columns = _alloc_streams(sizes)
    with tracer.span("batch.encode.sort", docs=d, changes=flat.doc_ch_off[-1]):
        result = native.encode_batch(
            flat.columns(), row_off, row_cap,
            [columns[col] for cols in _STREAMS for col in cols])
    GLOBAL_COUNTERS.add("causal.schedules.native", sum(flat.expressed))

    with tracer.span("batch.encode.pad"):
        counts, order, n_sched, attr_order, n_attrs, key_order, n_keys, status = result
        n_sched, n_attrs, n_keys = n_sched.tolist(), n_attrs.tolist(), n_keys.tolist()
        fallback: List[int] = []
        actor_tables: List[OrderedActorTable] = []
        attr_tables: List[Interner] = []
        map_tables: List[Interner] = []
        for doc, st in enumerate(status.tolist()):
            changes = flat.changes[doc]
            if st == _GAP:
                causal_sort(changes)  # raises the gap as the Python path does
                raise RuntimeError(f"native schedule of doc {doc} stopped short")
            if st == _REDO or not flat.expressed[doc]:
                with tracer.span("batch.encode.split", doc=doc):
                    if flat.expressed[doc]:
                        lo = flat.doc_ch_off[doc]
                        ordered = [changes[i] for i in order[lo:lo + n_sched[doc]].tolist()]
                    else:
                        ordered = causal_sort(changes)
                    streams, ok, actors, attrs, keys = _split_doc(ordered, changes)
                if not ok:
                    fallback.append(doc)
                else:
                    counts[doc] = [len(rows) for rows in _stream_rows(streams)]
                    if (counts[doc] > row_cap[doc]).any():
                        fallback.append(doc)
                    else:
                        _write_streams(columns, row_off[doc].tolist(), streams)
            else:
                a0, k0 = flat.doc_attr_off[doc], flat.doc_key_off[doc]
                actors = OrderedActorTable(flat.actors[doc])
                attrs = Interner([flat.attr_strs[g] for g in
                                  attr_order[a0:a0 + n_attrs[doc]].tolist()])
                keys = Interner([flat.key_strs[g] for g in
                                 key_order[k0:k0 + n_keys[doc]].tolist()])
                if st == _OVER:
                    fallback.append(doc)
            actor_tables.append(actors)
            attr_tables.append(attrs)
            map_tables.append(keys)
        return finish(columns, row_off, counts, fallback,
                      actor_tables, attr_tables, map_tables)


def _streams_at(columns: Dict[str, np.ndarray], offsets, counts) -> _DocStreams:
    """One doc's streams read back from flat buffers."""
    def cols(s):
        o, n = offsets[s], counts[s]
        return [columns[c][o:o + n].tolist() for c in _STREAMS[s]]

    streams = _DocStreams()
    streams.ins = list(zip(*cols(0)))
    streams.dels = cols(1)[0]
    streams.marks = list(zip(*cols(2)))
    streams.maps = list(zip(*cols(3)))
    return streams


def encode_doc_streams(
    workloads: Sequence[Dict[str, List[Change]]],
    tracer=None,
):
    """The per-doc half of :func:`encode_workloads`: causal sort + intern +
    stream split for every doc, WITHOUT padding into a shared (D, K) shape.
    Returns ``(per_doc, fallback, actor_tables, attr_tables, map_tables)``.

    With the native core loaded this is the columnar encode (spans as in
    :func:`encode_workloads`), each doc given just the rows it may need and
    its streams read back as rows under one ``batch.encode.rows`` span;
    without it, the per-doc Python loop.

    Exposed separately so the ragged layout (api/batch.py
    ``layout="ragged"``) can apply its per-doc capacity thresholds BEFORE
    padding, then pad the batch to its own true maxima via
    :func:`pad_doc_streams`."""
    tracer = tracer if tracer is not None else GLOBAL_TRACER
    if not native_loaded():
        return _encode_doc_streams_python(workloads, tracer)

    columns, row_off, counts, fallback, *tables = _encode_columnar(
        workloads, tracer, None, lambda *parts: parts)
    fb = set(fallback)
    with tracer.span("batch.encode.rows", docs=len(workloads)):
        per_doc = [_DocStreams() if d in fb
                   else _streams_at(columns, row_off[d].tolist(), counts[d].tolist())
                   for d in range(len(workloads))]
    return (per_doc, fallback, *tables)


def encode_workloads(
    workloads: Sequence[Dict[str, List[Change]]],
    insert_capacity: Optional[int] = None,
    delete_capacity: Optional[int] = None,
    mark_capacity: Optional[int] = None,
    map_capacity: Optional[int] = None,
    tracer=None,
) -> EncodedBatch:
    """Encode a batch of per-doc change-log sets (dict actor -> [Change])
    into padded streams.  A capacity left None (or 0) is the batch's own
    widest stream, rounded up to 8; a doc over a capacity falls back.

    With the native core loaded, under spans of ``tracer``: a
    ``batch.encode.split`` per doc around its flatten, a
    ``batch.encode.pad`` around the array allocation, one
    ``batch.encode.sort`` around the native schedule and scatter of the
    whole batch, and a ``batch.encode.pad`` around the tables (and any doc
    split again in Python, under its own ``batch.encode.split``).  Without
    it, the per-doc Python loop, then padding under ``batch.encode.pad``."""
    tracer = tracer if tracer is not None else GLOBAL_TRACER
    caps = (insert_capacity, delete_capacity, mark_capacity, map_capacity)
    if not native_loaded():
        per_doc, fallback, actor_tables, attr_tables, map_tables = (
            _encode_doc_streams_python(workloads, tracer)
        )
        with tracer.span("batch.encode.pad"):
            return pad_doc_streams(
                per_doc, fallback, actor_tables, attr_tables, map_tables,
                *caps,
            )
    d = len(workloads)

    def pad(columns, row_off, counts, fallback, *tables):
        grids = {}
        for s, cols in enumerate(_STREAMS):
            # an open capacity is the widest stream of the docs that
            # encoded, capacity fallbacks included, as pad_doc_streams has it
            width = caps[s] or _round8(int(counts[:, s].max(initial=0)))
            for col in cols:
                grid = columns[col].reshape(d, -1) if d else np.zeros((0, width), np.int32)
                grids[col] = (grid if grid.shape[1] == width
                              else np.ascontiguousarray(grid[:, :width]))
        counts[fallback] = 0
        return _encoded_batch(grids, counts, fallback, *tables)

    return _encode_columnar(workloads, tracer, caps, pad)


def pad_doc_streams(
    per_doc: Sequence[_DocStreams],
    fallback: List[int],
    actor_tables: List[OrderedActorTable],
    attr_tables: List[Interner],
    map_tables: Optional[List[Interner]] = None,
    insert_capacity: Optional[int] = None,
    delete_capacity: Optional[int] = None,
    mark_capacity: Optional[int] = None,
    map_capacity: Optional[int] = None,
) -> EncodedBatch:
    """Pad per-doc split streams into dense (D, K) arrays.  Docs exceeding a
    fixed capacity are appended to ``fallback`` (shape buckets are static so
    XLA compiles once per bucket)."""
    d = len(per_doc)
    counts = np.asarray([[len(rows) for rows in _stream_rows(s)] for s in per_doc],
                        np.int32).reshape(d, 4)
    caps = (insert_capacity, delete_capacity, mark_capacity, map_capacity)
    width = [cap or _round8(int(counts[:, s].max(initial=0))) for s, cap in enumerate(caps)]
    columns = _alloc_streams([d * w for w in width])
    for i, streams in enumerate(per_doc):
        if i in fallback:
            continue
        if any(n > w for n, w in zip(counts[i].tolist(), width)):
            fallback.append(i)  # over this shape bucket: oracle fallback
            continue
        _write_streams(columns, [i * w for w in width], streams)
    counts[fallback] = 0
    grids = {col: columns[col].reshape(d, w)
             for cols, w in zip(_STREAMS, width) for col in cols}
    return _encoded_batch(
        grids, counts, fallback, actor_tables, attr_tables,
        map_tables if map_tables is not None else [Interner() for _ in range(d)],
    )


def _encoded_batch(grids, counts, fallback, actor_tables, attr_tables,
                   map_tables) -> EncodedBatch:
    """An EncodedBatch of (D, K) stream ``grids`` by column name; ``counts``
    (D, 4) are each doc's stream rows, zero for fallback docs."""
    return EncodedBatch(
        ins_ref=grids["ins_ref"],
        ins_op=grids["ins_op"],
        ins_char=grids["ins_char"],
        del_target=grids["del_target"],
        marks={col: grids[col] for col in MARK_COLS},
        mark_count=counts[:, 2].astype(np.int32),
        map_ops={col: grids[col] for col in MAP_STREAM_COLS},
        map_count=counts[:, 3].astype(np.int32),
        num_ops=counts.sum(axis=1).astype(np.int32),
        actor_tables=actor_tables,
        attr_tables=attr_tables,
        map_tables=map_tables,
        fallback_docs=sorted(fallback),
    )
