"""Share of the run's docs whose columns the native flatten walked, from
the program's ``encode.flatten.native`` and ``encode.flatten.python``
counters (``ops/encode.py``: one per doc of each columnar encode; a doc the
walker declines counts as python), read in process.  The warm-up merge
flattens as many docs as every window merge, so counting it in leaves the
share as it is."""


def read(r):
    from peritext_tpu.obs import metrics

    native = metrics.GLOBAL_COUNTERS.get("encode.flatten.native")
    python = metrics.GLOBAL_COUNTERS.get("encode.flatten.python")
    total = native + python
    return 100.0 * native / total if total else None
