"""Mean admit -> window-close milliseconds of each drained batch's first
frame: the latency plane's watermarks (``obs/latency.py``, armed in the
traced run only) in ``serve/mux.py``."""


def read(r):
    return r.window.get("latency_window_ms")
