"""Host seconds per merge in the program's ``batch.apply.plan`` span
(``api/batch.py`` ragged merge, inside ``batch.apply``): page demand, the
page pool's construction, the ragged plan and the stream operands, up to
the device call."""


def read(r):
    spans = r.span_seconds("batch.apply.plan")
    return sum(spans) / r.window["merges"] if spans else None
