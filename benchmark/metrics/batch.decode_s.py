"""Host decode seconds per merge: the program's ``batch.decode`` span
(``api/batch.py`` around ``ops/decode.py``)."""


def read(r):
    spans = r.span_seconds("batch.decode")
    return sum(spans) / r.window["merges"] if spans else None
