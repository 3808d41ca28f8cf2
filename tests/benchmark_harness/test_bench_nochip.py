"""Without a TPU the benchmark prints no result and exits nonzero, with
``"failed": true`` on standard error; and it fails in a directory that
holds only BENCHMARK.json and the benchmark's own paths."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "batch_fuzz",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _result_lines(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return [x for x in out if isinstance(x, dict) and "correct" in x]


def test_no_tpu_fails_without_a_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout)
    err = [json.loads(x) for x in proc.stderr.splitlines() if x.startswith("{")]
    assert err and err[-1]["failed"] is True and "no TPU" in err[-1]["error"]


def test_benchmark_alone_fails(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, "--rehearse")
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout)
