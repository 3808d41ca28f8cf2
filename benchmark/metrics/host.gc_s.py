"""Host seconds per merge spent in garbage collection: every ``host.gc``
span (``obs/spans.py``'s collector hook, one per collection of any
generation) in the traced window.  They overlap the program's other
spans."""


def read(r):
    spans = r.span_seconds("host.gc")
    return sum(spans) / r.window["merges"] if spans else None
