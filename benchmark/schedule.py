"""The open-loop schedule (``serve/traffic.build_arrivals``'s idea, with
its faults repaired):

* each arrival is timed from when it was due, not from when the generator
  got round to submitting it, so a stall in the generator shows in the
  latency of every frame it delays;
* no frame is ever offered twice: a session that runs out of fresh frames
  makes the schedule fail instead of cycling, because a cycled frame is a
  duplicate the CRDT absorbs without work.
"""

from __future__ import annotations

from typing import List, NamedTuple, Sequence


class Arrival(NamedTuple):
    due: float      # seconds after the window opens
    session: int    # index into the sessions
    frame: int      # index into that session's frames, in order


class ScheduleError(ValueError):
    """The sessions hold too few fresh frames to cover the window."""


def round_robin(frames_per_session: Sequence[int], rate: float,
                seconds: float) -> List[Arrival]:
    """Arrival ``i`` is due at ``i / rate`` and goes to session
    ``i mod S``, which sends its frames in order.  Deterministic."""
    if rate <= 0 or seconds <= 0:
        raise ValueError(f"need a positive rate and window, got {rate}, {seconds}")
    n = int(rate * seconds)
    s = len(frames_per_session)
    if s == 0:
        raise ScheduleError("no sessions")
    need = [n // s + (1 if i < n % s else 0) for i in range(s)]
    short = [i for i in range(s) if frames_per_session[i] < need[i]]
    if short:
        i = short[0]
        raise ScheduleError(
            f"{n} arrivals over {s} sessions need {need[i]} fresh frames "
            f"of session {i}, which holds {frames_per_session[i]} "
            f"({len(short)} sessions short)")
    return [Arrival(i / rate, i % s, i // s) for i in range(n)]


def lateness(due: Sequence[float], sent: Sequence[float]) -> List[float]:
    """How late the generator submitted each arrival, in seconds."""
    return [max(0.0, t - d) for d, t in zip(due, sent)]
