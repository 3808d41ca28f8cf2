"""Host seconds per episode in wire parse and ingest: the program's
``streaming.ingest`` span (``ops/frames.py``, ``parallel/codec.py``,
``native/``)."""


def read(r):
    spans = r.span_seconds("streaming.ingest")
    return sum(spans) / r.window["episodes"] if spans else None
