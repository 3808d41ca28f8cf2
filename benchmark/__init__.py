"""The chip benchmark: cells from ``BENCHMARK.json``, run one process at a
time by ``python3 -m benchmark.run``.  Everything a cell needs is found by
name: ``configs/<config>.json``, ``traffic/<traffic>.json`` (which names its
driver), ``drivers/<driver>.py`` and ``metrics/<metric>.py``."""
