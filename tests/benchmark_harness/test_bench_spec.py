"""Every cell of BENCHMARK.json resolves to its files by name, and the file
keeps to the benchmark contract's shape rules."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p


def test_names_and_units():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in SPEC[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    metric_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_free_text_fields():
    texts = [e["why"] for e in SPEC["configs"] + SPEC["workloads"]]
    texts += [e["source"] for e in SPEC["configs"]]
    texts += [m["layer"] for m in SPEC["per_layer"]] + list(SPEC["command"])
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)


def test_bounds():
    names = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in names
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    from benchmark import run

    parts = run.resolve_cell(SPEC, cell)
    for fn in ("setup", "window", "verify"):
        assert callable(getattr(parts["driver"], fn))
    e2e = run.end_to_end_of(SPEC, cell)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    layer = run.per_layer_of(SPEC, cell)
    assert layer, f"{cell} reports no per-layer metric"
    for m in layer:
        assert callable(run.metric_reader(m["name"]))
        assert m["moves"] in {x["name"] for x in e2e}


@pytest.mark.parametrize("conf", [c["name"] for c in SPEC["configs"]])
def test_config_file(conf):
    entry = next(c for c in SPEC["configs"] if c["name"] == conf)
    assert entry["file"].startswith("benchmark/")
    data = json.loads((ROOT / entry["file"]).read_text())
    assert set(entry["reduced"]) == set(data["reduced"])
    for key in entry["reduced"]:
        assert key in data["sizes"]
        assert not key.endswith(("_dim", "_rank"))
    assert data["guarantees"] and data["assumed"]
    assert any(w["config"] == conf for w in SPEC["workloads"])


def test_check_budget():
    """A full check of 24 cells fits its 43200 seconds at this run length."""
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_peaks_known_kinds():
    from benchmark import run

    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.peaks_for("TPU v9 imaginary")
