"""Device milliseconds per episode in the fused round programs that commit
the causal rounds (``ops/kernel.py`` stacked/staged rounds), from the
trace."""

from benchmark.metrics._programs import ROUNDS


def read(r):
    from benchmark.trace import program_seconds

    s = program_seconds(r.trace, ROUNDS, r.lo, r.hi)
    return None if s is None else s * 1e3 / r.window["episodes"]
