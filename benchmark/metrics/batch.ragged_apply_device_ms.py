"""Device milliseconds per merge in the ragged apply program
(``ops/ragged.py`` ``apply_batch_ragged``), from the trace."""

#: the jit name the trace prints for the ragged apply; ``_programs.APPLY``'s
#: ``jit_apply_batch`` is a prefix of it, so that tuple is not used here
RAGGED_APPLY = ("jit_apply_batch_ragged",)


def read(r):
    from benchmark.trace import program_seconds

    s = program_seconds(r.trace, RAGGED_APPLY, r.lo, r.hi)
    return None if s is None else s * 1e3 / r.window["merges"]
