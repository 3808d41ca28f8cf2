"""Device milliseconds per merge in span resolution (``ops/resolve.py``),
from the trace."""

from benchmark.metrics._programs import RESOLVE


def read(r):
    from benchmark.trace import program_seconds

    s = program_seconds(r.trace, RESOLVE, r.lo, r.hi)
    return None if s is None else s * 1e3 / r.window["merges"]
