"""Share of the ragged merges' inserts that continue a typing run, from the
program's ``merge.ragged_inserts`` and ``merge.ragged_run_inserts``
counters (``api/batch.py``, added under ``batch.apply.plan`` once a ragged
merge: its live inserts, and those whose reference is the insert just
before them in the doc's stream), read in process.  The ragged insert
kernel splices such a run once, at its end, so this is the share its
pending run takes.  The warm-up merge has the window's histories, so
counting it in leaves the share as it is."""


def read(r):
    from peritext_tpu.obs import metrics

    inserts = metrics.GLOBAL_COUNTERS.get("merge.ragged_inserts")
    runs = metrics.GLOBAL_COUNTERS.get("merge.ragged_run_inserts")
    return 100.0 * runs / inserts if inserts else None
