"""Mesh-fused dispatch plumbing: value-keyed program cache + ICI page mover.

The mesh-sharded fused commit path (round 19) runs the staged K-round
programs under ``shard_map`` on the doc axis.  Two pieces of machinery are
shared by every arm (padded stacked, paged group chain, ragged per-round,
the K-row digest gather) and live here:

* :func:`mesh_fn` — a bounded, VALUE-keyed cache of mesh-specialized
  compiled callables.  ``jax.Mesh`` objects hash by identity, so a cache
  keyed by the live mesh (the pre-round-19 ``_GATHER_ROWS_CACHE``) grew one
  stale compiled entry per test-suite mesh and could never share programs
  between two meshes over the same devices.  :func:`mesh_fingerprint` keys
  by (axis names, device grid shape, device ids) instead — the exact value
  identity under which a compiled program is reusable.
* :func:`page_mover_fn` — the collective reshard protocol: pages move
  between per-shard pools over ICI via ``ppermute`` (one program, a static
  ring-offset loop), never through host round-trips.  The caller
  (store/sharded.ShardedPagedDocStore.permute_rows) owns the allocate-first
  discipline that makes the in-place scatter sound: destination local ids
  are drawn from the complement of (pages staying + pages leaving) per
  shard, so a shard's incoming pages can never land on a slot whose payload
  has not yet been gathered.

Programs built THROUGH :func:`mesh_fn` close over static shapes only; all
per-round variation (plan planes, stream staging, page tables) rides as
data — the recompile-sentinel pin for repeat mesh drains depends on it.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from .mesh import DOC_AXIS

#: Cache bound for :func:`mesh_fn`.  One mesh serving session needs several
#: live programs at once (stacked apply + digest chain + a paged group
#: ladder + the row gather); 64 keeps every program of a handful of
#: concurrent meshes resident — so the steady-state zero-compile pin holds
#: — while still bounding a test suite that builds hundreds of throwaway
#: meshes.
MESH_FN_CACHE_BOUND = 64

_MESH_FN_CACHE: "OrderedDict[tuple, Callable]" = OrderedDict()


def mesh_fingerprint(mesh) -> Tuple:
    """Value identity of a mesh: (axis names, device grid shape, device
    ids).  Two ``Mesh`` objects agreeing on all three compile to identical
    programs, so cache entries key on this — never on the live object."""
    if mesh is None:
        return ("meshless",)
    return (
        tuple(mesh.axis_names),
        tuple(mesh.devices.shape),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


def mesh_fn(mesh, key, build: Callable[[], Callable]) -> Callable:
    """The compiled callable for ``(mesh, key)``, building it via
    ``build()`` on first use.  ``key`` must carry every static the built
    program closes over (widths, bucket ladders, impl names) — the cache
    returns an existing entry on key equality alone."""
    cache_key = (mesh_fingerprint(mesh), key)
    fn = _MESH_FN_CACHE.get(cache_key)
    if fn is None:
        fn = build()
        _MESH_FN_CACHE[cache_key] = fn
        while len(_MESH_FN_CACHE) > MESH_FN_CACHE_BOUND:
            _MESH_FN_CACHE.popitem(last=False)
    else:
        _MESH_FN_CACHE.move_to_end(cache_key)
    return fn


def mesh_fn_cache_size() -> int:
    """Current entry count (the bound test reads it)."""
    return len(_MESH_FN_CACHE)


def shard_leading(tree, mesh):
    """Device-put a host pytree with every leaf's LEADING axis sharded over
    the doc axis — the per-shard plan-plane staging idiom: host stacks
    per-shard planes on a fresh ``(n_shards, ...)`` axis, this ships each
    shard its own slice."""
    return jax.device_put(
        tree, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(DOC_AXIS))
    )


def page_mover_fn(mesh, m_pages: int, m_zero: int) -> Callable:
    """The ICI page-move program for ``mesh``: one ``shard_map`` dispatch
    moves up to ``m_pages`` pool pages between every ordered shard pair
    (a static ring-offset loop of ``ppermute``) and re-zeroes up to
    ``m_zero`` vacated source pages per shard — the free-page all-zero
    invariant survives the move.

    Operands (global shapes; ``n`` = mesh size, ``Ps`` = per-shard pool
    pages, ``P`` = page width):

    * ``pool_elem`` / ``pool_char`` — ``(n * Ps, P)``, page axis sharded.
    * ``send_idx`` — ``(n, n - 1, m_pages)`` int32: shard ``s`` row ``d-1``
      holds the LOCAL page ids it sends at ring offset ``d`` (to shard
      ``(s + d) % n``); pad = 0, the per-shard null page, which gathers
      zeros.
    * ``recv_idx`` — ``(n, n - 1, m_pages)`` int32: shard ``s`` row ``d-1``
      holds the LOCAL destination ids for pages arriving at offset ``d``
      (from shard ``(s - d) % n``); pad = ``Ps`` (out of bounds — the
      scatter drops it).
    * ``zero_idx`` — ``(n, m_zero)`` int32: each shard's vacated source
      ids to re-zero after the scatters; pad = ``Ps`` (dropped).

    Returns the updated ``(pool_elem, pool_char)``.  Cache through
    :func:`mesh_fn` with key ``("page_mover", m_pages, m_zero)``."""
    from jax import shard_map

    from jax.sharding import PartitionSpec as P

    n = mesh.size

    def body(pool_elem, pool_char, send_idx, recv_idx, zero_idx):
        send_idx = send_idx[0]
        recv_idx = recv_idx[0]
        zero_idx = zero_idx[0]
        # gather every outgoing payload BEFORE any scatter lands: with the
        # caller's src/dst disjointness this makes the in-place move sound
        payload_e = pool_elem[send_idx]  # (n-1, m_pages, P)
        payload_c = pool_char[send_idx]
        for d in range(1, n):
            perm = [(i, (i + d) % n) for i in range(n)]
            pe = jax.lax.ppermute(payload_e[d - 1], DOC_AXIS, perm)
            pc = jax.lax.ppermute(payload_c[d - 1], DOC_AXIS, perm)
            idx = recv_idx[d - 1]
            pool_elem = pool_elem.at[idx].set(pe, mode="drop")
            pool_char = pool_char.at[idx].set(pc, mode="drop")
        zeros = jnp.zeros(
            (zero_idx.shape[0], pool_elem.shape[1]), pool_elem.dtype
        )
        pool_elem = pool_elem.at[zero_idx].set(zeros, mode="drop")
        pool_char = pool_char.at[zero_idx].set(zeros, mode="drop")
        return pool_elem, pool_char

    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(DOC_AXIS), P(DOC_AXIS), P(DOC_AXIS), P(DOC_AXIS),
                  P(DOC_AXIS)),
        out_specs=(P(DOC_AXIS), P(DOC_AXIS)),
    ))
