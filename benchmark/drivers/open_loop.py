"""Open-loop serving: frames arrive on a fixed schedule whatever the server
does, and every session reads its patches after each pump that settled one
of its frames.

The sessions are live: set-up applies every session's history up to the
frames the lead-in and the window will send, each client reads its
document once, and a lead-in of ``lead_in_s`` seconds at the cell's rate
brings the server to its steady state; so the window's frames land on
documents of their full size and every read in it is incremental.  Arrival
``i`` is due at ``i / rate`` and belongs to session ``i mod S``, which
sends the last frames of its history in order; no frame is offered twice
(``benchmark/schedule.py``).  A frame's time to
visibility runs from when it was due to the return of its session's first
``mux.patches`` read after the pump that applied it.  Percentiles are over
every frame of the window; a frame that is shed, or not visible by the end,
counts as failed.

Control ``stale`` (for the comparison's test): the reference one frame
behind at every read, a read path that serves the state before the last
pump.
"""

from __future__ import annotations

import gc
import random
import time

from benchmark import stats
from benchmark.drivers import _serve
from benchmark.run import Check, Window
from benchmark.schedule import lateness, round_robin


def setup(run):
    state = _serve.setup_pool(run)
    plan(run, state)
    with run.spans.span("bench.warmup"):
        warm_up(run, state)
    rng = random.Random(run.seed)
    sessions = state["sessions"]
    sample = set(rng.sample(range(sessions), min(sessions, run.param("sample_sessions"))))
    sample.add(0)  # session 0 receives the most frames of the window
    state["sample"] = sample
    start(run, state)
    return state


def plan(run, state) -> None:
    """The lead-in's and the window's arrivals, one round-robin schedule at
    the cell's rate over every session's last frames; the frames before
    them are the sessions' history."""
    counts = [len(_serve.session_frames(state, i)) for i in range(state["sessions"])]
    rate, lead = run.param("rate_per_s"), run.param("lead_in_s")
    arrivals = round_robin(counts, rate, lead + run.seconds)
    sent = [0] * len(counts)
    for a in arrivals:
        sent[a.session] += 1
    state["offset"] = [c - n for c, n in zip(counts, sent)]
    state["arrivals"] = arrivals
    n_lead = int(rate * lead)
    state["lead_in"] = arrivals[:n_lead]
    state["schedule"] = [a._replace(due=a.due - lead) for a in arrivals[n_lead:]]


def start(run, state) -> None:
    """A preloaded mux, driven through the lead-in at the cell's rate so
    that the window opens on a server in its steady state.  The preload's
    bulk flushes are the benchmark's bookkeeping, not traffic: the mux's
    round-window tuner is replaced by a fresh one (``SessionMux(tuner=)``)
    before the lead-in, which would otherwise carry their walls into the
    window (its rolling p99 over 64 rounds, quantized to the histogram's
    buckets, read 0.1 s or 0.25 s by whether a preload flush took more than
    0.1 s: p50 335-395 ms or 515-560 ms, my chip run, PR 22).  Then every
    object set-up made (the pool of histories, their frames, the warm-up's
    leftovers, the preloaded mux) is frozen out of the cycle collector: a
    full collection over those millions of objects inside a pump stalls it
    past 0.1 s, and the tuner then holds a 0.25 s window for its next 64
    rounds, the rest of the run (one run in six at 200/s without the
    freeze, one in 21 with it, my chip runs, PR 22)."""
    from peritext_tpu.serve.mux import BatchWindowTuner

    with run.spans.span("bench.preload"):
        mux, sids, first = preload(run, state)
    mux.tuner = BatchWindowTuner()
    gc.collect()
    gc.freeze()
    state["mux"], state["sids"] = mux, sids
    # (frames applied so far, patches) per read of the sampled sessions
    state["reads"] = {s: [(state["offset"][s], first[s])] for s in state["sample"]}
    state["settled"] = list(state["offset"])
    with run.spans.span("bench.lead_in"):
        _, visible, shed, _, _ = drive(run, state, state["lead_in"])
    if shed or None in visible:
        raise RuntimeError(f"lead-in: {shed} frames shed, "
                           f"{visible.count(None) - shed} never visible")


def frame_at(state, session: int, k: int) -> bytes:
    """The ``k``-th frame the lead-in and the window send to ``session``."""
    return _serve.session_frames(state, session)[state["offset"][session] + k]


def preload(run, state):
    """A fresh mux whose sessions hold their histories up to the window's
    frames, each read once by its client."""
    mux, sids = _serve.build_mux(run, state["sessions"])
    offset = state["offset"]
    for k in range(max(offset)):
        for i, sid in enumerate(sids):
            if k < offset[i]:
                verdict = mux.submit(sid, _serve.session_frames(state, i)[k])
                if not verdict.admitted:
                    raise RuntimeError(f"preload frame refused: {verdict}")
        mux.flush()
    return mux, sids, [mux.patches(sid) for sid in sids]


def slot_steps(n: int) -> tuple:
    """Where a document's slot count crosses a power of two or a multiple of
    32: the static-rounds apply keys its slot window on the first (the
    program's power-of-two bucket of the busiest document's inserts); the
    second is room in case that bucketing changes."""
    return (max(8, 1 << max(0, n - 1).bit_length()), n // 32)


def warm_up(run, state) -> None:
    """Compile every program the window will run: preload a throwaway mux,
    then replay the window's own schedule on it without waiting for due
    times, draining whenever a session would get a second frame into one
    round (the window never does that) or before the busiest document's
    slots cross a step, so that every step the window's drains can meet
    gets a drain here.  Each drain is read as the window reads."""
    mux, sids, _ = preload(run, state)
    inserts = [state["frame_inserts"][_serve.history_of(state, i)] for i in range(len(sids))]
    cum = [sum(ins[:state["offset"][i]]) for i, ins in enumerate(inserts)]
    top = max(cum)
    step = slot_steps(top)
    pending = set()

    def drain():
        mux.flush()
        for s in sorted(pending):
            mux.patches(sids[s])
        pending.clear()

    for a in state["arrivals"]:
        cum[a.session] += inserts[a.session][state["offset"][a.session] + a.frame]
        top = max(top, cum[a.session])
        if pending and (a.session in pending or slot_steps(top) != step):
            drain()
        step = slot_steps(top)
        mux.submit(sids[a.session], frame_at(state, a.session, a.frame))
        pending.add(a.session)
    if pending:
        drain()
    del mux
    gc.collect()  # the throwaway session's device state goes before the real one's


def drive(run, state, sched):
    """Offer ``sched`` open loop to the live mux: each frame is submitted
    once it is due, a pump runs whenever the mux's round window has
    expired, and every session reads its patches after each pump that
    settled one of its frames.  Returns, in seconds from the start, when
    each frame was submitted and when it became visible (None: never), the
    frames shed, the pumps that applied frames, and the end."""
    mux, sids = state["mux"], state["sids"]
    reads, settled = state["reads"], state["settled"]
    n = len(sched)
    sent = [0.0] * n
    visible = [None] * n
    shed = pumps = 0
    pending = []                      # arrivals admitted since the last pump
    limit = (sched[-1].due if sched else 0.0) + run.param("drain_limit_s")
    clock = time.perf_counter
    t0 = clock()
    i = 0
    while True:
        now = clock() - t0
        while i < n and sched[i].due <= now:
            a = sched[i]
            verdict = mux.submit(sids[a.session], frame_at(state, a.session, a.frame))
            sent[i] = clock() - t0
            if verdict.admitted:
                pending.append(i)
            else:
                shed += 1
            i += 1
        if pending and mux.window_expired():
            with run.spans.span("bench.pump"):
                applied = mux.pump()
            if applied:
                pumps += 1
                batch, pending = pending, []
                by_session = {}
                for j in batch:
                    by_session.setdefault(sched[j].session, []).append(j)
                for s, js in by_session.items():
                    with run.spans.span("bench.read"):
                        patches = mux.patches(sids[s])
                    t = clock() - t0
                    for j in js:
                        visible[j] = t
                    settled[s] += len(js)
                    if s in reads:
                        reads[s].append((settled[s], patches))
        now = clock() - t0
        if i >= n and not pending:
            break
        if now > limit:
            break
        wait = mux.window_seconds() / 4
        if i < n:
            wait = min(wait, sched[i].due - now)
        if wait > 0:
            time.sleep(wait)
    return sent, visible, shed, pumps, clock() - t0


def window(run, state) -> Window:
    sched = state["schedule"]
    plane = None
    if run.trace:
        from peritext_tpu.obs.latency import LatencyPlane

        plane = state["mux"].latency_plane = LatencyPlane().enable()
    sent, visible, shed, pumps, end = drive(run, state, sched)
    n = len(sched)
    lat = [(v - a.due) * 1e3 for a, v in zip(sched, visible) if v is not None]
    lag = [x * 1e3 for x in lateness([a.due for a in sched], sent)]
    lost = sum(v is None for v in visible) - shed
    state["lost"] = lost
    state["latencies"] = lat
    ops = sum(state["frame_ops"][_serve.history_of(state, a.session)]
              [state["offset"][a.session] + a.frame]
              for a, v in zip(sched, visible) if v is not None)
    readings = {"pumps": pumps,
                "gen_lag_p95_ms": stats.percentile(lag, 95),
                "applied_ops_per_s": stats.rate(ops, end)}
    if plane is not None and plane.hists["window"].count:
        h = plane.hists["window"]
        readings["latency_window_ms"] = 1e3 * h.sum / h.count
    run.log(f"{n} frames ({ops} ops) at {run.param('rate_per_s')}/s over "
            f"{len(state['sids'])} sessions after a {run.param('lead_in_s')} s lead-in; "
            f"{end:.3f} s to the last read; {pumps} pumps, round window "
            f"{state['mux'].window_seconds():.3f} s at the end; shed {shed}, "
            f"never visible {lost}; generator lag p95 {readings['gen_lag_p95_ms']:.3f} ms")
    if not lat:
        raise RuntimeError("no frame became visible in the window")
    return Window(metrics={"visibility_p50_ms": stats.percentile(lat, 50),
                           "visibility_p95_ms": stats.percentile(lat, 95),
                           "applied_ops_per_s": readings["applied_ops_per_s"]},
                  attempted=n, failed=n - len(lat), readings=readings)


def verify(run, state, win: Window):
    from benchmark.reference.accumulate import accumulate_patches

    gc.unfreeze()
    mux = state.pop("mux")
    final = mux.session.read_all()
    off_device = mux.session.overflow_count() + sum(1 for d in mux.session.docs if d.fallback)
    del mux
    gc.collect()  # the program's device state goes before the reference runs
    per = state["per_frame"]
    with run.spans.span("bench.reference"):
        prefixes = [_serve.prefix_spans(h, per) for h in state["pool"]]

    if run.control not in (None, "stale"):
        raise ValueError(f"open_loop has no control {run.control!r}")

    def want(s, k):
        return prefixes[_serve.history_of(state, s)][k]

    def answer(s, k, observed):
        """The program's answer, or the control's in its place."""
        return want(s, max(0, k - 1)) if run.control == "stale" else observed

    def replayed(patches):
        try:
            return accumulate_patches(patches)
        except (IndexError, KeyError, ValueError):
            return None  # a stream the accumulator cannot replay is wrong

    reads_wrong = reads_checked = 0
    for s, reads in state["reads"].items():
        acc = []
        for settled, patches in reads:
            acc.extend(patches)
            reads_wrong += answer(s, settled, replayed(acc)) != want(s, settled)
            reads_checked += 1
    docs_wrong = sum(answer(s, state["settled"][s], spans) != want(s, state["settled"][s])
                     for s, spans in enumerate(final))
    run.log(f"compared {reads_checked} reads of {len(state['reads'])} sampled "
            f"sessions and the final state of {len(final)} docs")
    return [Check("reads_wrong", reads_wrong, 0), Check("docs_wrong", docs_wrong, 0),
            Check("frames_lost", state["lost"], 0), Check("docs_fallback", off_device, 0)]
