"""Host seconds per merge in the program's ``batch.encode.pad`` span
(``ops/encode.py``, inside ``batch.encode``): padding every doc's streams
into the device arrays."""


def read(r):
    spans = r.span_seconds("batch.encode.pad")
    return sum(spans) / r.window["merges"] if spans else None
