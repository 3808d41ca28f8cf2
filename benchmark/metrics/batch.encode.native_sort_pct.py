"""Share of the run's causal schedules that the native scheduler ran,
from the program's ``causal.schedules.native`` and
``causal.schedules.python`` counters (``parallel/causal.py``), read in
process.  The warm-up merge sorts as many docs as every window merge, so
counting it in leaves the share as it is."""


def read(r):
    from peritext_tpu.obs import metrics

    native = metrics.GLOBAL_COUNTERS.get("causal.schedules.native")
    python = metrics.GLOBAL_COUNTERS.get("causal.schedules.python")
    total = native + python
    return 100.0 * native / total if total else None
