"""Mean milliseconds of one session's read after a pump
(``SessionMux.patches``: resolve, digest, patch diff): the benchmark's
``bench.read`` span."""


def read(r):
    spans = r.span_seconds("bench.read")
    return 1e3 * sum(spans) / len(spans) if spans else None
