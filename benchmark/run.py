"""One run of one benchmark cell, in its own process.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration in
``benchmark/configs/<config>.json``, its traffic in
``benchmark/traffic/<traffic>.json``, the driver that traffic names in
``benchmark/drivers/<driver>.py`` and each per-layer metric's reader in
``benchmark/metrics/<metric>.py``.  The driver sets up (inputs and
weights-free program state from ``--seed``, warm-up of every shape the
window uses), runs the measured window, and after it compares what the
timed path produced with the plain reference (``benchmark/reference``).

The last line of standard output is the result:
``{"correct", "attempted", "failed", "metrics", "device", ["breakdown"], "checks"}``.
Without a TPU, or with fewer chips than the cell asks for, the run prints
no result, reports ``{"failed": true, ...}`` on standard error and exits 2.
``--rehearse`` runs the cell at the configuration's tiny ``rehearse`` sizes
on any backend (the CPU rehearsal); its line carries ``"rehearsal": true``
and no ``device``, so it can never be read as a cell.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # noqa: E402  (set-up is timed from here)

import argparse
import contextlib
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, NamedTuple, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Check(NamedTuple):
    """One number compared with the reference: correct iff value <= limit."""
    name: str
    value: float
    limit: float


@dataclass
class Window:
    """What a driver's measured window hands back."""
    metrics: Dict[str, float]           # end-to-end metrics by name
    attempted: int
    failed: int
    readings: Dict = field(default_factory=dict)  # for the per-layer readers


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def resolve_cell(spec: dict, cell: str) -> dict:
    """The cell's entry, its configuration and traffic files, its driver."""
    entry = find(spec["workloads"], cell, "workload")
    conf_entry = find(spec["configs"], entry["config"], "config")
    config = load_json(ROOT / conf_entry["file"])
    traffic = load_json(HERE / "traffic" / f"{entry['traffic']}.json")
    driver = importlib.import_module(f"benchmark.drivers.{traffic['driver']}")
    return {"entry": entry, "config": config, "traffic": traffic, "driver": driver}


def end_to_end_of(spec: dict, cell: str) -> List[dict]:
    return [m for m in spec["end_to_end"] if cell in m.get("workloads", [cell])]


def per_layer_of(spec: dict, cell: str) -> List[dict]:
    moved = {m["name"] for m in end_to_end_of(spec, cell)}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in moved)]


def metric_reader(name: str) -> Callable:
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"benchmark.metrics.{name}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read


class Spans:
    """The benchmark's own host spans around its calls into each layer, on
    the host clock (``time.perf_counter``); in a traced run each is also a
    ``jax.profiler.TraceAnnotation``, so the trace can name what the host
    did in each device-idle gap.  Program spans (``obs/spans.py``) arrive
    through :meth:`program_sink`."""

    def __init__(self, traced: bool) -> None:
        self.traced = traced
        self.events: List[tuple] = []   # (name, start, end) in perf_counter s

    @contextlib.contextmanager
    def span(self, name: str):
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        else:
            ann = contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.events.append((name, t0, time.perf_counter()))

    def program_sink(self, span) -> None:
        end = time.perf_counter()
        self.events.append((span.name, end - span.duration, end))


@dataclass
class Run:
    """Everything a driver is given."""
    cell: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    control: Optional[str]
    spans: Spans

    def sizes(self) -> dict:
        """The configuration's sizes, or its tiny ``rehearse`` sizes."""
        sizes = dict(self.config["sizes"])
        if self.rehearse:
            sizes.update(self.config.get("rehearse", {}))
        return sizes

    def param(self, key: str):
        """A traffic parameter, with its ``rehearse`` override."""
        if self.rehearse and key in self.traffic.get("rehearse", {}):
            return self.traffic["rehearse"][key]
        return self.traffic[key]

    def log(self, msg: str) -> None:
        print(f"bench[{self.cell}]: {msg}", file=sys.stderr, flush=True)


@dataclass
class Readings:
    """What a per-layer metric's reader reads: the host spans, the traced
    window on the trace's clock and the device trace, the driver's own
    readings, and the chip's peaks."""
    spans: List[tuple]            # (name, start, end) on the trace's clock
    trace: object                 # benchmark.trace.Trace
    lo: float
    hi: float
    window: Dict
    peaks: Dict
    config: dict
    traffic: dict

    def span_seconds(self, name: str) -> List[float]:
        """Durations of the spans named ``name`` inside the traced window."""
        return [b - a for n, a, b in self.spans
                if n == name and self.lo <= (a + b) / 2 <= self.hi]


def peaks_for(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in benchmark/peaks.json")
    return table["devices"][kind]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path: the one
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache/`` in the
    checkout (the path the program's entry points use too)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def look_for_chip(chips: int):
    """The devices, if JAX's default backend is a TPU with enough chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: jax's default backend is {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, jax sees {len(devices)}")
    return devices


def profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # a Python tracer would slow the host path it measures
    opts.host_tracer_level = 2
    return opts


def execute(cell: str, seed: int, seconds: float, trace: bool,
            rehearse: bool = False, control: Optional[str] = None,
            devices=None, spec: Optional[dict] = None) -> dict:
    """Set up, measure, compare; return the result line as a dict."""
    import jax

    from benchmark import trace as tracemod
    from benchmark.compile_clock import CompileClock

    spec = spec if spec is not None else load_spec()
    parts = resolve_cell(spec, cell)
    driver = parts["driver"]
    devices = devices if devices is not None else jax.devices()
    dev = devices[0]
    cache = enable_compile_cache()
    clock = CompileClock()
    spans = Spans(traced=trace)
    run = Run(cell, parts["config"], parts["traffic"], seed, seconds, trace,
              rehearse, control, spans)
    run.log(f"device {dev.platform} / {dev.device_kind} x {len(devices)}; "
            f"compile cache {cache}; seed {seed}")

    state = driver.setup(run)
    gc.collect()  # every window starts from the same collected heap
    setup_compile = clock.mark()
    trace_dir = None
    sink_added = False
    if trace:
        from peritext_tpu.obs import GLOBAL_TRACER

        GLOBAL_TRACER.add_sink(spans.program_sink)
        sink_added = True
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir, profiler_options=profiler_options())
    try:
        t_window = time.perf_counter()
        with spans.span("bench.window"):
            window = driver.window(run, state)
        t_end = time.perf_counter()
    finally:
        if trace:
            jax.profiler.stop_trace()
        if sink_added:
            GLOBAL_TRACER.remove_sink(spans.program_sink)
    setup_s = t_window - T_START
    in_window = clock.since(setup_compile)
    run.log(f"set-up {setup_s:.3f} s (compile {setup_compile[0]:.3f} s over "
            f"{setup_compile[1]} programs, {setup_compile[2]} cache hits); window "
            f"{t_end - t_window:.3f} s with {in_window['compiles']} compiles")
    if in_window["compiles"]:
        # the warm-up missed a shape the window uses: its time is not the
        # window's work, so the run has no result
        raise RuntimeError(f"{in_window['compiles']} programs compiled inside the "
                           f"measured window ({in_window['compile_s']:.3f} s)")
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in devices[:parts["entry"]["chips"]])

    readings = None
    if trace:
        try:
            tr = tracemod.load(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = tr.window()
        offset = lo - t_window  # perf_counter seconds -> trace seconds
        host = [(n, a + offset, b + offset) for n, a, b in spans.events]
        readings = Readings(host, tr, lo, hi, window.readings,
                            peaks_for(dev.device_kind) if not rehearse else {},
                            run.config, run.traffic)
        busy = tracemod.mean_busy_seconds(tr, lo, hi)
        if busy <= 0:
            raise tracemod.TraceError("no device operation ran in the traced window")

    checks = driver.verify(run, state, window)
    correct = all(c.value <= c.limit for c in checks)

    metrics: Dict[str, dict] = {}
    if not trace:
        values = dict(window.metrics, setup_s=setup_s)
        for m in end_to_end_of(spec, cell):
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        for m in per_layer_of(spec, cell):
            value = metric_reader(m["name"])(readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    line = {"correct": correct, "attempted": window.attempted,
            "failed": window.failed, "metrics": metrics}
    if rehearse:
        line["rehearsal"] = True
    else:
        line["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                          "count": len(devices), "memory_peak_bytes": memory_peak}
    if trace:
        host_events = [tracemod.Event(n, a, b) for n, a, b in readings.spans]
        line.setdefault("device", {}).update(busy_s=busy, window_s=hi - lo)
        line["breakdown"] = {
            "device_ops": tracemod.top_ops(readings.trace, lo, hi),
            "idle_gaps": tracemod.idle_gaps(readings.trace, lo, hi, host_events),
        }
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    for c in checks:
        run.log(f"check {c.name} {c.value} limit {c.limit}")
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny sizes on any backend; never a cell's result")
    parser.add_argument("--control", default=None,
                        help="put the driver's named control in the program's "
                             "place for the comparison (must come out incorrect)")
    args = parser.parse_args(argv)

    spec = load_spec()
    entry = find(spec["workloads"], args.workload, "workload")
    devices = None
    if not args.rehearse:
        try:
            devices = look_for_chip(entry["chips"])
        except NoChip as exc:
            print(json.dumps({"failed": True, "error": str(exc)}), file=sys.stderr)
            return 2
    line = execute(args.workload, args.seed, args.seconds, bool(args.trace),
                   rehearse=args.rehearse, control=args.control,
                   devices=devices, spec=spec)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
