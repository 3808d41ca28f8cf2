"""Mean milliseconds of a pump that drains (``SessionMux.pump``: ingest,
causal rounds, staging, dispatch): the benchmark's ``bench.pump`` span."""


def read(r):
    spans = r.span_seconds("bench.pump")
    return 1e3 * sum(spans) / len(spans) if spans else None
