"""Device milliseconds per merge in the batched apply program
(``ops/kernel.py`` with the Pallas insert), from the trace."""

from benchmark.metrics._programs import APPLY


def read(r):
    from benchmark.trace import program_seconds

    s = program_seconds(r.trace, APPLY, r.lo, r.hi)
    return None if s is None else s * 1e3 / r.window["merges"]
