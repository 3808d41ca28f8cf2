"""Host seconds per merge in the program's ``batch.encode.split`` spans,
one per doc (``ops/encode.py``, inside ``batch.encode``): building the
doc's actor, attr and key tables and splitting its ops into the insert,
delete, mark and map streams."""


def read(r):
    spans = r.span_seconds("batch.encode.split")
    return sum(spans) / r.window["merges"] if spans else None
