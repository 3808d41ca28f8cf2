"""Shared by the harness's tests: run a cell in-process at its rehearse
sizes on the CPU, past the harness's look for a chip, without touching the
persistent compilation cache."""

import json

import pytest


@pytest.fixture
def rehearse(monkeypatch):
    from benchmark import run

    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")

    def go(cell, seed=11, seconds=2.0, control=None, spec=None):
        line = run.execute(cell, seed, seconds, False, rehearse=True, control=control,
                           spec=spec)
        json.dumps(line)  # the line must serialize
        return line

    return go
