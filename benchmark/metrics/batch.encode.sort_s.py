"""Host seconds per merge in the program's ``batch.encode.sort`` spans,
one per doc (``ops/encode.py``, inside ``batch.encode``): gathering the
doc's changes and sorting them causally (``parallel/causal.py``)."""


def read(r):
    spans = r.span_seconds("batch.encode.sort")
    return sum(spans) / r.window["merges"] if spans else None
