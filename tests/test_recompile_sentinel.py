"""Runtime recompile sentinel: the compile-shape discipline, enforced.

The streaming engine's whole throughput story rests on "one compiled
program per session" — every per-round tensor is padded to a static width,
so after warmup NO round may trigger XLA compilation (the hazard the width
buckets in parallel/streaming.py exist to prevent, and the runtime half of
graftlint's PTL004).  These tests pin that invariant with a live counter
instead of a comment.
"""

import random

import jax
import jax.numpy as jnp

from peritext_tpu.observability import health_snapshot
from peritext_tpu.parallel.streaming import StreamingMerge
from peritext_tpu.testing.fuzz import generate_workload

ACTORS = ("doc1", "doc2", "doc3")


def _arrival_rounds(workloads, rounds, rng):
    """Split each doc's change logs into ``rounds`` shuffled arrival
    batches (the steady-state shape: new changes every round, same static
    widths)."""
    arrival = []
    for workload in workloads:
        changes = [ch for log in workload.values() for ch in log]
        rng.shuffle(changes)
        size = -(-len(changes) // rounds)
        arrival.append(
            [changes[i : i + size] for i in range(0, len(changes), size)]
        )
    return arrival


def _run_schedule(session, arrival, rounds):
    for r in range(rounds):
        for d, batches in enumerate(arrival):
            if r < len(batches):
                session.ingest(d, batches[r])
        session.drain()
        session.digest()
    return session.read_all()


def test_sentinel_counts_per_site_compiles(recompile_sentinel):
    """The sentinel sees a fresh jit compile exactly once per signature."""
    recompile_sentinel.mark()

    @jax.jit
    def _sentinel_probe(x):
        return x * 2 + 1

    _sentinel_probe(jnp.ones(3))
    first = sum(recompile_sentinel.since_mark().values())
    assert first >= 1  # fresh function, fresh signature: compiled
    recompile_sentinel.mark()
    _sentinel_probe(jnp.ones(3))  # same signature: cache hit, no compile
    assert recompile_sentinel.since_mark() == {}
    recompile_sentinel.mark()
    _sentinel_probe(jnp.ones(7))  # new shape: recompiles, and we see it
    assert sum(recompile_sentinel.since_mark().values()) >= 1


def test_health_snapshot_exports_recompile_counters(recompile_sentinel):
    """Tier-1 smoke: compile counts surface through health_snapshot both as
    jit.* counters and as the per-site dict."""

    @jax.jit
    def _snapshot_probe(x):
        return x + 1

    _snapshot_probe(jnp.ones(2))
    snap = health_snapshot(sentinel=recompile_sentinel)
    assert snap["recompiles"]["total"] >= 1
    assert any(site for site in snap["recompiles"]["sites"])
    assert snap["counters"].get("jit.compiles_total", 0) >= 1
    assert any(k.startswith("jit.compiles.") for k in snap["counters"])


def test_steady_state_streaming_rounds_zero_recompiles(recompile_sentinel):
    """The fleet steady-state contract: once a workload shape has been seen,
    serving it again — a fresh session, same config, same arrival shapes —
    dispatches only already-compiled programs.  ZERO compiles.

    (Within a single cold session the width buckets intentionally mint a
    small logarithmic variant set as docs grow — that is the compile-cache
    design, not a hazard.  The hazard PTL004 and this sentinel guard is
    unbounded variant minting: any per-doc shape that escapes the padded
    tables makes the replay below recompile, and this test fail.)"""

    def fresh_session():
        return StreamingMerge(
            num_docs=4,
            actors=ACTORS,
            round_insert_capacity=32,
            round_delete_capacity=16,
            round_mark_capacity=16,
        )

    workloads = generate_workload(seed=21, num_docs=4, ops_per_doc=60)
    arrival = _arrival_rounds(workloads, rounds=6, rng=random.Random(5))
    # cold run: compiles every program variant this schedule needs
    cold = _run_schedule(fresh_session(), arrival, rounds=6)

    recompile_sentinel.mark()
    warm = _run_schedule(fresh_session(), arrival, rounds=6)
    recompile_sentinel.assert_steady_state("steady-state streaming rounds")
    assert warm == cold  # replay converges byte-equal, and compiled nothing


def test_mixed_size_drain_one_ragged_executable(recompile_sentinel):
    """The ragged layout's headline, pinned live: a tweet fleet + an essay
    + a book-scale doc drain through ONE compiled ragged apply — per-doc op
    and page counts are data, so the size mix cannot mint shapes.  The
    paged engine on the IDENTICAL schedule splits the same mix across its
    power-of-two bucket ladder and compiles several apply variants; that
    contrast is the point, so it is asserted too."""
    tweets = generate_workload(seed=31, num_docs=6, ops_per_doc=10)
    essay = generate_workload(seed=32, num_docs=1, ops_per_doc=120)
    book = generate_workload(seed=33, num_docs=1, ops_per_doc=400)
    workloads = tweets + essay + book
    rounds = 5
    arrival = _arrival_rounds(workloads, rounds=rounds, rng=random.Random(7))

    def session(layout):
        return StreamingMerge(
            num_docs=8,
            actors=ACTORS,
            slot_capacity=512,
            mark_capacity=64,
            tomb_capacity=64,
            round_insert_capacity=128,
            round_delete_capacity=32,
            round_mark_capacity=32,
            layout=layout,
            # pre-sized pool: growth mid-drain would change the pool
            # shape, which recompiles HONESTLY — sizing is the deployer's
            # lever, shape stability is the layout's
            pool_pages=64,
        )

    recompile_sentinel.mark()
    ragged_reads = _run_schedule(session("ragged"), arrival, rounds)
    ragged_compiles = recompile_sentinel.since_mark().get(
        "apply_batch_ragged", 0
    )
    assert ragged_compiles == 1, (
        f"mixed-size drain minted {ragged_compiles} ragged apply "
        "executables; the whole layout exists to make this 1"
    )

    recompile_sentinel.mark()
    paged_reads = _run_schedule(session("paged"), arrival, rounds)
    paged_compiles = sum(
        n for site, n in recompile_sentinel.since_mark().items()
        if "apply_batch_paged" in site
    )
    assert paged_compiles > 1  # the bucket ladder, observed
    assert ragged_reads == paged_reads  # same bytes, fewer programs


# ---------------------------------------------------------------------------
# log-record parsing regression (ISSUE 3 satellite): the sentinel must
# tolerate prefixed and multi-line jax log_compiles records
# ---------------------------------------------------------------------------

import logging

from peritext_tpu.obs.sentinel import _COMPILE_MSG_RE

#: VERBATIM record messages captured from the installed jax (0.9.0, CPU
#: backend, jax_log_compiles=True) — see the emitting sites in
#: jax._src.interpreters.pxla / jax._src.dispatch.  If a jax upgrade
#: changes these shapes, re-capture and extend; the sentinel must keep
#: counting exactly the "Compiling jit(<site>)" records.
VERBATIM_JAX = [
    ("Finished tracing + transforming convert_element_type for pjit "
     "in 0.008886576 sec", None),
    ("Compiling jit(convert_element_type) with global shapes and types "
     "(ShapedArray(float32[]),). Argument mapping: (UnspecifiedValue,).",
     "convert_element_type"),
    ("Finished jaxpr to MLIR module conversion jit(convert_element_type) "
     "in 0.066748619 sec", None),
    ("Finished XLA compilation of jit(convert_element_type) "
     "in 0.008085012 sec", None),
    ("Compiling jit(f) with global shapes and types "
     "(ShapedArray(float32[3]),). Argument mapping: (UnspecifiedValue,).",
     "f"),
    ("Finished tracing + transforming multiply for pjit in 0.001347542 sec",
     None),
]

#: shapes the regex must ALSO tolerate: a formatter-prefixed record and a
#: multi-line record with "Finished tracing" noise batched ahead of the
#: Compiling line (both observed from handlers downstream of other logging
#: layers)
HOSTILE_SHAPES = [
    ("WARNING:2026-10-15 03:05:19,712:jax._src.interpreters.pxla:1943: "
     "Compiling jit(f) with global shapes and types (ShapedArray(float32[3]),).",
     "f"),
    ("Finished tracing + transforming f for pjit in 0.003565311 sec\n"
     "Compiling jit(f) with global shapes and types (ShapedArray(float32[3]),). "
     "Argument mapping: (UnspecifiedValue,).", "f"),
    # prose containing "compilation"/"Recompiling" must NOT count
    ("Finished XLA compilation of jit(f) in 0.081711054 sec", None),
    ("Recompiling is not what this says", None),
]


def test_compile_regex_on_verbatim_and_hostile_records():
    for message, site in VERBATIM_JAX + HOSTILE_SHAPES:
        m = _COMPILE_MSG_RE.search(message)
        if site is None:
            assert m is None, f"false positive on: {message!r}"
        else:
            assert m is not None and m.group(1) == site, message


def test_sentinel_counts_prefixed_and_multiline_records():
    """End-to-end through logging.Handler.emit with hostile record shapes:
    the per-site counts must land exactly once per Compiling record."""
    from peritext_tpu.observability import Counters, RecompileSentinel

    counters = Counters()
    sentinel = RecompileSentinel(counters=counters)
    for message, _ in VERBATIM_JAX + HOSTILE_SHAPES:
        record = logging.LogRecord(
            "jax._src.interpreters.pxla", logging.WARNING, __file__, 1,
            message, None, None,
        )
        sentinel.emit(record)
    expected_sites = [s for _, s in VERBATIM_JAX + HOSTILE_SHAPES if s]
    assert sentinel.total == len(expected_sites)
    assert sentinel.counts == {"convert_element_type": 1, "f": 3}
    assert counters.get("jit.compiles_total") == len(expected_sites)
    assert counters.get("jit.compiles.f") == 3
