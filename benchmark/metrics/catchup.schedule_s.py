"""Host seconds per episode in causal round scheduling: the program's
``streaming.schedule`` span (``parallel/streaming.py``)."""


def read(r):
    spans = r.span_seconds("streaming.schedule")
    return sum(spans) / r.window["episodes"] if spans else None
