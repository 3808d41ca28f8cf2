"""What the serve drivers share: the pool of histories as wire frames, the
mux as the configuration builds it, a closed-loop episode, and the
reference's spans after each frame."""

from __future__ import annotations

from typing import List

from benchmark.drivers import _pool
from benchmark.reference import Doc, causal_order


def setup_pool(run) -> dict:
    sz = run.sizes()
    with run.spans.span("bench.generate"):
        pool = _pool.make_pool(run.seed, sz["distinct_histories"], sz["ops_per_doc"],
                               run.param("mix"))
    per = sz["changes_per_frame"]
    frames = [_pool.frames_of(h, per) for h in pool]
    ops = [_pool.frame_ops(h, per) for h in pool]
    inserts = [_pool.frame_inserts(h, per) for h in pool]
    return {"pool": pool, "frames": frames, "frame_ops": ops, "frame_inserts": inserts,
            "sessions": sz["docs"], "per_frame": per}


def history_of(state, session: int) -> int:
    return session % len(state["pool"])


def session_frames(state, session: int) -> List[bytes]:
    return state["frames"][history_of(state, session)]


def build_mux(run, sessions: int):
    """A fresh SessionMux over a fresh static-rounds StreamingMerge, one
    client session per document."""
    from peritext_tpu.parallel.streaming import StreamingMerge
    from peritext_tpu.serve import AdmissionController, SessionMux

    cfg = run.config
    session = StreamingMerge(num_docs=sessions, actors=tuple(cfg["actors"]),
                             static_rounds=True, **cfg["program"])
    mux = SessionMux(session, admission=AdmissionController(
        max_depth=cfg["admission"]["depth_per_session"] * sessions,
        session_quota=None), host="bench")
    sids = []
    for i in range(sessions):
        sid, verdict = mux.open_session(f"client{i}")
        if not verdict.admitted:
            raise RuntimeError(f"session {i} refused: {verdict}")
        sids.append(sid)
    return mux, sids


def closed_loop_episode(run, state, mux, sids) -> int:
    """Every client sends its next frame as soon as its last one is
    applied, one frame in flight per client; returns the frames applied.
    The round closes as soon as every client with backlog has its frame in
    (``mux.flush()``): nothing else can arrive, so no round waits for the
    admission window."""
    cursor = [0] * len(sids)
    applied = 0
    while True:
        live = []
        for i, sid in enumerate(sids):
            frames = session_frames(state, i)
            if cursor[i] < len(frames):
                verdict = mux.submit(sid, frames[cursor[i]])
                if not verdict.admitted:
                    raise RuntimeError(f"catch-up frame refused: {verdict}")
                cursor[i] += 1
                live.append(i)
        if not live:
            return applied
        with run.spans.span("bench.pump"):
            applied += mux.flush()


def prefix_spans(h, per_frame: int) -> list:
    """The reference's spans after each whole frame of the history:
    ``out[k]`` after the first ``k`` frames."""
    doc = Doc("_reference")
    ordered = causal_order(h)
    out = [[]]
    for k in range(0, len(ordered), per_frame):
        for ch in ordered[k:k + per_frame]:
            doc.apply_change(ch)
        out.append(doc.get_text_with_formatting(["text"]))
    return out
