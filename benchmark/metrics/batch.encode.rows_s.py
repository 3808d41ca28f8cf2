"""Host seconds per merge in the program's ``batch.encode.rows`` span
(``ops/encode.py`` ``encode_doc_streams``, inside ``batch.encode``): the
read-back of the native call's columns into per-doc stream rows, which only
the paged and ragged layouts do."""


def read(r):
    spans = r.span_seconds("batch.encode.rows")
    return sum(spans) / r.window["merges"] if spans else None
