"""A reconnect storm: every session's client replays its whole offline
backlog through ``SessionMux``, closed loop, one frame in flight per
client, and reads its document once at the end.

Each episode starts from empty documents with warm programs: building the
session and reopening every client session is inside the window, as it is
for a restarted server.  A round closes as soon as every client with
backlog has its next frame in, so no round waits for arrivals and the
admission window is bypassed.  The window runs whole episodes back to back; the
rate is all the ops of the whole episodes over the time to the end of the
last one.

Control ``stale`` (for the comparison's test): the reference without each
client's last frame, a catch-up that stops one frame short.
"""

from __future__ import annotations

import time

from benchmark import stats
from benchmark.drivers import _serve
from benchmark.run import Check, Window


def setup(run):
    state = _serve.setup_pool(run)
    state["ops_per_episode"] = sum(
        sum(state["frame_ops"][_serve.history_of(state, i)])
        for i in range(state["sessions"]))
    with run.spans.span("bench.warmup"):
        episode(run, state)
    return state


def episode(run, state):
    mux, sids = _serve.build_mux(run, state["sessions"])
    _serve.closed_loop_episode(run, state, mux, sids)
    final = []
    for sid in sids:
        with run.spans.span("bench.read"):
            final.append(mux.read(sid))
    overflow = mux.session.overflow_count()
    fallback = sum(1 for d in mux.session.docs if d.fallback)
    return final, overflow + fallback


def window(run, state) -> Window:
    episodes = []
    ends = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < run.seconds:
        with run.spans.span("bench.episode"):
            episodes.append(episode(run, state))
        ends.append(time.perf_counter() - t0)
    elapsed = ends[-1]
    ops = state["ops_per_episode"] * len(episodes)
    state["episodes"] = episodes
    run.log(f"{len(episodes)} episodes of {state['sessions']} sessions, {ops} ops "
            f"in {elapsed:.3f} s; episodes ended at {[round(t, 3) for t in ends[:12]]} s")
    return Window(metrics={"catchup_ops_per_s": stats.rate(ops, elapsed)},
                  attempted=len(episodes) * state["sessions"], failed=0,
                  readings={"episodes": len(episodes)})


def verify(run, state, win: Window):
    with run.spans.span("bench.reference"):
        prefixes = [_serve.prefix_spans(h, state["per_frame"]) for h in state["pool"]]
    if run.control not in (None, "stale"):
        raise ValueError(f"catchup has no control {run.control!r}")
    skip = 2 if run.control == "stale" else 1
    wrong = 0
    left_device = 0
    for final, off_device in state.pop("episodes"):
        left_device += off_device
        for i, spans in enumerate(final):
            p = prefixes[_serve.history_of(state, i)]
            got = p[len(p) - skip] if run.control == "stale" else spans
            wrong += got != p[-1]
    win.failed = wrong
    return [Check("docs_wrong", wrong, 0), Check("docs_fallback", left_device, 0)]
