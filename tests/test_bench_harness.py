"""The bench orchestrator's contract (bench.py): one parseable JSON line
on every run, and no CPU fallback.  Without ``--platform`` a run needs
the chip: on a host with none (this image — conftest pins JAX_PLATFORMS=cpu)
it exits nonzero with a ``"failed": true`` record.  ``--platform cpu`` is
the explicit rehearsal.
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")


def _run_bench(extra_args=(), env_extra=(), timeout=600):
    env = dict(os.environ)
    # conftest pins JAX_PLATFORMS=cpu: the workers find no chip
    env.update(dict(env_extra))
    return subprocess.run(
        [sys.executable, BENCH, "--smoke", "--iters", "2", *extra_args],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
    )


def _json_line(stdout):
    lines = [ln for ln in stdout.splitlines() if ln.strip().startswith("{")]
    assert lines, f"no JSON line in stdout: {stdout!r}"
    return json.loads(lines[-1])


@pytest.mark.slow
def test_explicit_cpu_platform_still_one_json_line():
    """--platform cpu is the explicit rehearsal: one JSON line, rows on cpu."""
    proc = _run_bench(["--platform", "cpu"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = _json_line(proc.stdout)
    assert result["metric"] == "crdt_ops_per_sec_per_chip"
    assert result["value"] > 0
    assert result["platform"] == "cpu"


def _assert_failed_line(proc, metric):
    assert proc.returncode != 0, proc.stdout[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip().startswith("{")]
    assert len(lines) == 1, proc.stdout
    result = json.loads(lines[0])
    assert result["failed"] is True
    assert result["metric"] == metric
    assert result["value"] is None
    assert "no TPU found" in result["error"]


@pytest.mark.slow
def test_no_chip_batch_run_fails_nonzero():
    """No chip and no --platform: the batch run fails, one failed line."""
    proc = _run_bench()
    _assert_failed_line(proc, "crdt_ops_per_sec_per_chip")


@pytest.mark.slow
def test_no_chip_streaming_run_fails_fast():
    """The worker finds no chip at start-up: no probe, no retry, no CPU
    rerun — the failure comes back within seconds of the jax import."""
    import time

    t0 = time.monotonic()
    proc = _run_bench(["--mode", "streaming"])
    _assert_failed_line(proc, "streaming_crdt_ops_per_sec_per_chip")
    assert time.monotonic() - t0 < 120


@pytest.mark.slow
def test_engine_mode_reports_engine_and_end_to_end():
    """--mode engine replays captured device-ready rounds (digest-verified
    against the real session) and reports both the engine-limit rate and the
    end-to-end reference it is decoupled from."""
    proc = _run_bench(["--mode", "engine", "--platform", "cpu"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = _json_line(proc.stdout)
    assert result["metric"] == "engine_limit_streaming_ops_per_sec_per_chip"
    assert result["value"] > 0 and result["end_to_end_ops_per_sec"] > 0
    # the replay syncs once; it can never be slower than end-to-end by much
    assert result["vs_baseline"] > 0.8


@pytest.mark.slow
def test_ladder_smoke_emits_rows():
    """--mode ladder runs every selected row as its own bounded worker and
    prints ONE JSON line with a rows array (VERDICT r3 task 1).  The
    headline fields mirror the best batch row so the driver contract is
    unchanged."""
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        sidecar = os.path.join(td, "BENCH_self.json")
        proc = _run_bench(
            ["--mode", "ladder", "--platform", "cpu"],
            env_extra={"PT_BENCH_LADDER_ROWS": "baselines,batch_8k,wire",
                       "PT_BENCH_SIDECAR": sidecar}.items(),
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        # the LAST stdout line is the driver-parsed compact summary: within
        # the hard byte budget no matter what (VERDICT r4 task 1)
        last = proc.stdout.strip().splitlines()[-1]
        assert len(last) <= 1536, f"final line {len(last)} B over budget"
        result = json.loads(last)
        assert result["metric"] == "crdt_ops_per_sec_per_chip"
        assert result["value"] > 0
        assert result["headline_row"] == "batch_8k"
        assert result["sidecar"] == "BENCH_self.json"
        crows = {r["row"]: r for r in result["rows"]}
        assert set(crows) == {"baselines", "batch_8k", "wire"}
        assert crows["batch_8k"]["platform"] == "cpu"
        assert crows["batch_8k"]["value"] > 0
        # the FULL rows live in the sidecar (and in an earlier stdout line)
        full = json.load(open(sidecar))
        rows = {r["row"]: r for r in full["rows"]}
        assert rows["baselines"]["scalar_python_ops_per_sec"] > 0
        assert rows["wire"]["shapes"]["typing"]["bytes_per_op"] < 4
        # the batch row REUSED the baselines row's python-oracle measurement
        # (shape-independent; the native one re-measures when ops/doc differ)
        assert rows["batch_8k"]["python_oracle_ops_per_sec"] == \
            rows["baselines"]["scalar_python_ops_per_sec"]
        # the earlier stdout line carries the same full record
        full_line = json.loads(
            [ln for ln in proc.stdout.splitlines()
             if ln.strip().startswith("{")][-2])
        assert full_line["rows"] == full["rows"]


@pytest.mark.slow
def test_ladder_without_chip_fails_nonzero():
    """The ladder never moves a chip row to the CPU: without a chip those
    rows fail and the record fails; rows pinned to the CPU still run."""
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        env = {
            "PT_BENCH_LADDER_ROWS": "wire,batch_8k",
            "PT_BENCH_SIDECAR": os.path.join(td, "BENCH_self.json"),
        }
        proc = subprocess.run(
            [sys.executable, BENCH, "--mode", "ladder", "--iters", "2",
             "--smoke"],
            capture_output=True, text=True,
            env={**os.environ, **env}, timeout=600,
        )
        assert proc.returncode == 1, proc.stderr[-2000:]
        last = proc.stdout.strip().splitlines()[-1]
        assert len(last) <= 1536
        result = json.loads(last)
        assert result["failed"] is True
        rows = {r["row"]: r for r in result["rows"]}
        assert set(rows) == {"wire", "batch_8k"}
        assert rows["batch_8k"].get("failed") is True
        assert not rows["wire"].get("failed")


def test_compact_record_fits_budget_on_full_ladder_shape():
    """A full ladder record (every ladder row with its per-row extras,
    ~5 KB) compacts to within the driver's tail budget with every row
    retained."""
    import bench

    rows = []
    for i, (name, config, *_rest) in enumerate(bench.ladder_rows(None)):
        rows.append({
            "row": name, "config": config, "metric": f"{name}_ops_per_sec",
            "value": 1000.0 + i, "unit": "ops/s", "vs_baseline": 1.5,
            "platform": "tpu", "docs": 8192, "ops_per_doc": 256,
            "apply_seconds": 0.0123, "compile_seconds": 4.2,
            "stage_seconds": {"ingest": 0.1, "schedule_apply": 0.2},
        })
    full = {"metric": rows[1]["metric"], "value": rows[1]["value"],
            "unit": "ops/s", "vs_baseline": 1.5, "headline_row": "batch_8k",
            "rows": rows, "ladder_seconds": 1234.5}
    assert len(json.dumps(full)) > 1536
    compact = bench.compact_record(full)
    blob = json.dumps(compact)
    assert len(blob) <= 1536, f"{len(blob)} B over budget"
    assert compact["value"] == full["value"]
    assert [r["row"] for r in compact["rows"]] == \
        [r["row"] for r in full["rows"]]
    assert all("value" in r for r in compact["rows"]
               if not r.get("failed") and not r.get("skipped"))


def test_compact_record_degrades_but_never_overflows():
    """Pathological rows (huge error strings, many rows) still compact to
    within the budget — by dropping optional fields, then trailing rows."""
    import bench

    record = {
        "metric": "m", "value": 1.0, "unit": "ops/s", "vs_baseline": 2.0,
        "headline_row": "r0", "failed": True,
        "rows": [{"row": f"r{i}", "value": float(i), "unit": "ops/s",
                  "platform": "tpu", "config": str(i), "vs_baseline": 1.0,
                  "error": "y" * 2000}
                 for i in range(40)],
    }
    compact = bench.compact_record(record)
    assert len(json.dumps(compact)) <= 1536
    assert compact["value"] == 1.0
    assert compact["failed"] is True
    # tiny budget: rows degrade away entirely but the headline survives
    tiny = bench.compact_record(record, budget=200)
    assert len(json.dumps(tiny)) <= 200
    assert tiny["value"] == 1.0


def test_worker_requires_chip_unless_platform_given(monkeypatch):
    """The worker's start-up check: on a host without a TPU the default
    backend is refused, while an explicit ``cpu`` is the rehearsal."""
    import bench
    from peritext_tpu.utils import platform

    # the test process must keep counting real compiles: no cache
    monkeypatch.setattr(platform, "enable_compile_cache", lambda: None)

    with pytest.raises(SystemExit, match="no TPU found"):
        bench._select_platform(None)
    bench._select_platform("cpu")


def test_parse_json_tail_skips_warnings():
    import bench

    out = "WARNING: jax warning text\nnot json {\n" + json.dumps(
        {"metric": "m", "value": 1}
    )
    assert bench._parse_json_tail(out) == {"metric": "m", "value": 1}
    assert bench._parse_json_tail("no json here") is None


def test_worker_crash_yields_structured_failure_line():
    """If even the CPU worker dies, the orchestrator still prints a JSON
    line carrying the error tail (rc 1 is then honest)."""
    import bench

    class _Args:
        platform = "cpu"
        smoke = True
        docs = None
        ops_per_doc = None
        mode = "batch"

    real = bench._run_bounded
    calls = []

    def fake_run_bounded(argv, timeout):
        calls.append(argv)
        return 1, "", "boom: synthetic worker crash"

    bench._run_bounded = fake_run_bounded
    try:
        import io
        from contextlib import redirect_stdout

        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = bench.orchestrate(_Args(), ["--smoke"])
    finally:
        bench._run_bounded = real
    assert rc == 1
    result = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert result["failed"] is True
    assert "synthetic worker crash" in result["error"]
    assert result["value"] is None
    assert len(calls) >= 1
