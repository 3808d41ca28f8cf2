"""Host encode seconds per merge: the program's ``batch.encode`` span
(``api/batch.py`` around ``ops/encode.py``)."""


def read(r):
    spans = r.span_seconds("batch.encode")
    return sum(spans) / r.window["merges"] if spans else None
