#!/usr/bin/env python
"""Chip smoke: the merge path on a TPU, through its user entry points, at
the repo's full-size shapes.  Proves the system starts on the chip; it
measures nothing (its seconds are smoke timings, not a benchmark).

One process, no children.  Phases, each checked for correctness:

* batch  — ``apply_batch_jit`` on the ``batch_8k`` synth shape (8192 docs x
  256 ops: 70% insert, 15% delete, 15% mark; 384 slots, 96 marks): the
  Pallas insert is taken (``tpu_custom_call`` in the compiled program) and
  its state equals the lax insert's bit for bit on the same chip; then
  ``DocBatch.merge`` of 8192 seeded fuzz docs (256 ops each, 256 distinct
  docs tiled 32x) whose spans must all equal the scalar oracle.
* serve  — ``SessionMux`` over the fused ``StreamingMerge`` at the
  streaming shape (2048 docs x 192 ops, 4 arrival rounds of wire frames):
  patches and reads of sampled sessions equal the oracle, and a second
  identical session gives the same digest.
* ragged — one drain of the same frames through ``layout="ragged"``,
  byte-equal to a padded session.

``--chips 4`` runs only the mesh phase: ``DocBatch(mesh=...)`` and the
fused ``StreamingMerge(mesh=...)`` over ``make_mesh(4)`` against their
single-device runs, with the state checked to span all four devices.

A lax insert traced where Pallas is expected fails the phase, as does a
native host runtime that did not build.  Without a TPU the script exits
nonzero before any phase.  The last stdout line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.

``--rehearse`` runs every phase at a tiny size on whatever backend there
is (the CPU rehearsal); it never prints the ok line.
"""

import argparse
import functools
import json
import random
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

ACTORS = ("doc1", "doc2", "doc3")


def log(msg):
    print(f"chip_smoke: {msg}", flush=True)


def sizes(rehearse: bool) -> dict:
    """Full size (bench.py's batch_8k and streaming rows) or the tiny
    CPU rehearsal.  The oracle docs of the batch phase are tiled up to the
    batch width: fuzz generation costs ~40 ms per doc on the host."""
    if rehearse:
        return dict(batch_docs=64, batch_ops=128, batch_slots=256,
                    batch_marks=64, distinct=16, stream_docs=32,
                    stream_ops=96, stream_slots=384, stream_marks=96)
    return dict(batch_docs=8192, batch_ops=256, batch_slots=384,
                batch_marks=96, distinct=256, stream_docs=2048,
                stream_ops=192, stream_slots=384, stream_marks=96)


class CompileClock:
    """Sums jax's backend-compile durations (a persistent-cache hit is
    counted as the time it took to load) and cache hits while active."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return (self.seconds, self.compiles, self.cache_hits)

    def since(self, mark):
        return {"compile_s": self.seconds - mark[0],
                "compiles": self.compiles - mark[1],
                "cache_hits": self.cache_hits - mark[2]}


class LaxGuard:
    """Fails any trace of a lax insert (padded or ragged) while armed: on
    the chip every insert in the smoke's phases must be a Pallas kernel."""

    def __init__(self, armed: bool):
        from peritext_tpu.ops import kernel, ragged

        self.armed = armed
        self._patched = [(kernel, "_insert_loop"),
                         (ragged, "_ragged_insert_lax")]
        self._orig = [getattr(m, n) for m, n in self._patched]

    def __enter__(self):
        if self.armed:
            for (module, name), orig in zip(self._patched, self._orig):
                setattr(module, name, self._refuse(name))
        return self

    def __exit__(self, *exc):
        for (module, name), orig in zip(self._patched, self._orig):
            setattr(module, name, orig)

    @staticmethod
    def _refuse(name):
        def refuse(*args, **kwargs):
            raise SmokeFailure(f"{name}: the lax insert was traced where "
                               f"the Pallas kernel was expected")
        return refuse


class SmokeFailure(Exception):
    """A phase's result is wrong, or it took a path it must not take."""


def check(ok, msg):
    # a raise, not an assert: `python -O` must not turn the checks off
    if not ok:
        raise SmokeFailure(msg)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def block(tree):
    import jax

    return jax.block_until_ready(tree)


def check_state_equal(a, b, what):
    for field in a._fields:
        x, y = np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
        check(np.array_equal(x, y), f"{what}: field {field} differs")


def batch_workloads(sz, seed):
    """The batch phase's fuzz docs: ``distinct`` seeded docs tiled up to
    the batch width, with the oracle spans of each distinct doc."""
    from peritext_tpu.api.batch import oracle_merge
    from peritext_tpu.testing.fuzz import generate_workload

    distinct = generate_workload(seed=seed, num_docs=sz["distinct"],
                                 ops_per_doc=sz["batch_ops"])
    oracle = oracle_merge(distinct)
    reps = sz["batch_docs"] // sz["distinct"]
    return distinct * reps, oracle * reps


def merge_capacities(sz):
    """DocBatch capacities for the fuzz docs: the batch slots, and a mark
    table above the 105 marks the busiest 256-op fuzz doc carries (at 96,
    six of the 256 docs fell back to the oracle on the chip)."""
    return dict(slot_capacity=sz["batch_slots"], mark_capacity=128)


# -- phases -----------------------------------------------------------------


def phase_batch(sz, seed, on_chip, clock):
    import jax

    from peritext_tpu.api import DocBatch
    from peritext_tpu.ops.kernel import (
        apply_batch, apply_batch_jit, resolve_insert_impl,
    )
    from peritext_tpu.ops.packed import empty_docs
    from peritext_tpu.testing.synth import synth_streams

    d, k = sz["batch_docs"], sz["batch_ops"]
    ki, kd = int(k * 0.7), int(k * 0.15)
    km = k - ki - kd
    streams = jax.device_put(synth_streams(
        d, inserts_per_doc=ki, deletes_per_doc=kd, marks_per_doc=km,
        seed=seed,
    ))
    state0 = empty_docs(d, sz["batch_slots"], max(sz["batch_marks"], km),
                        tomb_capacity=max(kd, 8))
    impl = resolve_insert_impl(state0.elem_id)
    if on_chip:
        check(impl == "pallas", f"insert impl resolved to {impl!r} on TPU")

    mark = clock.mark()
    lowered = jax.jit(functools.partial(
        apply_batch, insert_impl=impl, insert_loop_slots=ki,
    )).lower(state0, streams)
    text = lowered.compile().as_text()
    if on_chip:
        check("tpu_custom_call" in text, "no Pallas kernel in the program")
    out, first = timed(lambda: block(
        apply_batch_jit(state0, streams, insert_loop_slots=ki)))
    _, warm = timed(lambda: block(
        apply_batch_jit(state0, streams, insert_loop_slots=ki)))
    stats = dict(clock.since(mark), first_s=first, warm_s=warm)
    check(not np.asarray(out.overflow).any(), "synth batch overflowed")

    workloads, oracle = batch_workloads(sz, seed)
    batch = DocBatch(**merge_capacities(sz))
    mark = clock.mark()
    report, merge_first = timed(lambda: batch.merge(workloads))
    _, merge_warm = timed(lambda: batch.merge(workloads))
    stats.update(merge_compile_s=clock.since(mark)["compile_s"],
                 merge_first_s=merge_first, merge_warm_s=merge_warm)
    check(report.fallback_docs == [],
          f"{len(report.fallback_docs)} docs fell back to the scalar oracle")
    sample = random.Random(seed).sample(range(d), min(d, 256))
    for i in sample:
        check(report.spans[i] == oracle[i], f"DocBatch doc {i} != oracle")
    check(report.spans == oracle, "DocBatch spans != oracle")
    return dict(stats, docs=d, ops_per_doc=k, slots=sz["batch_slots"],
                oracle_docs=d, impl=impl), (state0, streams, out, ki)


def batch_lax_reference(ctx):
    """The batch state through the lax insert, outside the lax guard."""
    from peritext_tpu.ops.kernel import apply_batch_jit

    state0, streams, out, ki = ctx
    ref = block(apply_batch_jit(state0, streams, insert_impl="lax",
                                insert_loop_slots=ki))
    check_state_equal(out, ref, "pallas vs lax batch state")


def stream_workload(sz, seed):
    from bench import build_arrival
    from peritext_tpu.api.batch import oracle_merge
    from peritext_tpu.testing.fuzz import generate_workload

    workloads = generate_workload(seed=seed, num_docs=sz["stream_docs"],
                                  ops_per_doc=sz["stream_ops"])
    arrival, _ = build_arrival(workloads, 4, seed)
    picks = random.Random(seed).sample(range(len(workloads)),
                                       min(16, len(workloads)))
    oracle = dict(zip(picks, oracle_merge([workloads[i] for i in picks])))
    return arrival, oracle


def streaming_session(sz, **kw):
    from peritext_tpu.parallel.streaming import StreamingMerge

    return StreamingMerge(
        num_docs=sz["stream_docs"], actors=ACTORS,
        slot_capacity=sz["stream_slots"], mark_capacity=sz["stream_marks"],
        tomb_capacity=sz["stream_slots"], round_insert_capacity=256,
        round_delete_capacity=128, round_mark_capacity=128, **kw,
    )


def serve_once(sz, arrival, oracle):
    """One mux session as bench.py's serve row drives it: open a session
    per doc, submit each arrival round's frames, pump; read back."""
    from peritext_tpu.serve import AdmissionController, SessionMux
    from peritext_tpu.testing.accumulate import accumulate_patches

    d = sz["stream_docs"]
    mux = SessionMux(
        streaming_session(sz, static_rounds=True),
        admission=AdmissionController(max_depth=max(256, 4 * d),
                                      session_quota=None),
        host="chip_smoke",
    )
    sids = []
    for doc in range(d):
        sid, verdict = mux.open_session(f"client{doc}")
        check(verdict.admitted, verdict)
        sids.append(sid)
    for r in range(4):
        for doc, sid in enumerate(sids):
            if r < len(arrival[doc]):
                verdict = mux.submit(sid, arrival[doc][r])
                check(verdict.admitted, verdict)
        mux.flush()
    for doc, spans in oracle.items():
        got = accumulate_patches(mux.patches(sids[doc]))
        check(got == spans, f"serve doc {doc}: patches != oracle")
        check(mux.read(sids[doc]) == spans,
              f"serve doc {doc}: read != oracle")
    check(not mux.session.overflow_count(), "serve session overflowed")
    return mux.session.digest()


def phase_serve(sz, stream, clock):
    arrival, oracle = stream
    mark = clock.mark()
    digest, first = timed(lambda: serve_once(sz, arrival, oracle))
    compile_stats = clock.since(mark)
    again, warm = timed(lambda: serve_once(sz, arrival, oracle))
    check(again == digest, "serve digest differs across identical sessions")
    return dict(compile_stats, first_s=first, warm_s=warm,
                docs=sz["stream_docs"], ops_per_doc=sz["stream_ops"],
                rounds=4, oracle_docs=len(oracle), digest=digest)


def drain_once(sz, arrival, **kw):
    s = streaming_session(sz, **kw)
    for r in range(4):
        s.ingest_frames((doc, batches[r]) for doc, batches in
                        enumerate(arrival) if r < len(batches))
        s.drain()
    return s


def phase_ragged(sz, stream, clock):
    arrival, oracle = stream
    padded = drain_once(sz, arrival)
    mark = clock.mark()
    ragged, first = timed(lambda: drain_once(sz, arrival, layout="ragged"))
    compile_stats = clock.since(mark)
    _, warm = timed(lambda: drain_once(sz, arrival, layout="ragged"))
    check(ragged.digest() == padded.digest(), "ragged digest != padded")
    check(ragged.read_all() == padded.read_all(), "ragged spans != padded")
    for doc, spans in oracle.items():
        check(ragged.read(doc) == spans, f"ragged doc {doc} != oracle")
    return dict(compile_stats, first_s=first, warm_s=warm,
                docs=sz["stream_docs"], page_size=ragged.page_size)


def spans_devices(x):
    return {s.device for s in x.addressable_shards}


def phase_mesh(sz, seed, stream, clock, n):
    from peritext_tpu.api import DocBatch
    from peritext_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(n)
    workloads, oracle = batch_workloads(sz, seed)
    kw = merge_capacities(sz)
    single = DocBatch(**kw).merge(workloads)
    meshed = DocBatch(mesh=mesh, **kw)
    mark = clock.mark()
    report, first = timed(lambda: meshed.merge(workloads))
    batch_compile = clock.since(mark)
    _, warm = timed(lambda: meshed.merge(workloads))
    check(report.fallback_docs == [] == single.fallback_docs,
          "mesh DocBatch docs fell back to the scalar oracle")
    check(report.spans == single.spans == oracle, "mesh DocBatch != single")
    state = meshed.apply_encoded(meshed.encode(workloads))
    check(len(spans_devices(state.elem_id)) == n, "batch state not sharded")

    arrival, picks = stream
    ref = drain_once(sz, arrival)
    mark = clock.mark()
    sess, s_first = timed(lambda: drain_once(sz, arrival, mesh=mesh))
    stream_compile = clock.since(mark)
    _, s_warm = timed(lambda: drain_once(sz, arrival, mesh=mesh))
    check(len(spans_devices(sess.state.elem_id)) == n, "stream not sharded")
    check(sess.digest() == ref.digest(), "mesh streaming digest != single")
    check(sess.read_all() == ref.read_all(), "mesh streaming != single")
    for doc, spans in picks.items():
        check(sess.read(doc) == spans, f"mesh stream doc {doc} != oracle")
    return dict(batch=dict(batch_compile, first_s=first, warm_s=warm,
                           docs=sz["batch_docs"]),
                streaming=dict(stream_compile, first_s=s_first,
                               warm_s=s_warm, docs=sz["stream_docs"]),
                devices=n)


# -- driver -----------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs only the mesh phase on four chips")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny shapes on any backend; never prints ok")
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        log(f"no TPU: jax's default backend is {dev.platform}")
        return 1
    if len(devices) < args.chips:
        log(f"--chips {args.chips} but jax sees {len(devices)} device(s)")
        return 1
    on_chip = dev.platform == "tpu"

    from peritext_tpu import native
    from peritext_tpu.utils.platform import enable_compile_cache

    cache = enable_compile_cache()
    log(f"device {dev.platform} / {dev.device_kind} x {len(devices)}; "
        f"compile cache {cache}; native {native.available()}")
    check(native.available(), "the native host runtime did not build")

    sz = sizes(args.rehearse)
    clock = CompileClock()
    results = {}
    t_all = time.perf_counter()
    stream, gen_s = timed(lambda: stream_workload(sz, args.seed))
    log(f"streaming workload generated in {gen_s:.1f} s")
    with LaxGuard(armed=on_chip):
        if args.chips == 4:
            results["mesh"] = phase_mesh(sz, args.seed, stream, clock, 4)
        else:
            results["batch"], ctx = phase_batch(sz, args.seed, on_chip, clock)
            log(f"batch: {json.dumps(results['batch'])}")
            results["serve"] = phase_serve(sz, stream, clock)
            log(f"serve: {json.dumps(results['serve'])}")
            results["ragged"] = phase_ragged(sz, stream, clock)
            log(f"ragged: {json.dumps(results['ragged'])}")
    if args.chips == 1:
        batch_lax_reference(ctx)
        log("batch: pallas state == lax state, bit for bit")
    else:
        log(f"mesh: {json.dumps(results['mesh'])}")
    log(f"all phases passed in {time.perf_counter() - t_all:.1f} s "
        f"(total compile {clock.seconds:.1f} s over {clock.compiles} "
        f"programs, {clock.cache_hits} cache hits)")
    if not on_chip:
        log("rehearsal passed (not a chip run)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
