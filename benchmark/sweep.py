"""Find the knee of an open-loop serve cell once, by a sweep on the chip:
the highest offered rate the program sustains without a growing backlog.

    python3 -m benchmark.sweep --workload serve_fuzz_r80 --seed <n> \\
        --seconds <s> --rates 500,1000,2000 [--sessions <S>]

One process: the cell's set-up once (warmed at the highest rate), then
for each rate a freshly preloaded mux, its lead-in and its window, as
``benchmark.run`` runs them.  A rate is sustained when no frame is shed or lost and the second
half of the window's frames become visible no later, at the median, than
1.5 times the first half's (a backlog that grows through the window shows
there).  Prints one JSON line per rate; the cell's traffic file then takes
0.8 of the highest sustained rate as a number.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from benchmark import run as benchrun
from benchmark import stats
from benchmark.drivers import open_loop


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--sessions", type=int, default=None)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    rates = sorted(float(r) for r in args.rates.split(","))

    spec = benchrun.load_spec()
    parts = benchrun.resolve_cell(spec, args.workload)
    if not args.rehearse:
        benchrun.look_for_chip(parts["entry"]["chips"])
    benchrun.enable_compile_cache()
    config = json.loads(json.dumps(parts["config"]))
    if args.sessions:
        config["sizes"]["docs"] = args.sessions
        config.setdefault("rehearse", {})["docs"] = args.sessions
    traffic = dict(parts["traffic"], rate_per_s=rates[-1])
    traffic.pop("rehearse", None)
    run = benchrun.Run(args.workload, config, traffic, args.seed, args.seconds,
                       False, args.rehearse, None, benchrun.Spans(False))
    t0 = time.perf_counter()
    state = open_loop.setup(run)
    print(f"sweep: set-up {time.perf_counter() - t0:.1f} s for "
          f"{state['sessions']} sessions", file=sys.stderr, flush=True)
    for rate in rates:
        gc.unfreeze()  # the last rate's mux goes
        del state["mux"]
        gc.collect()
        run.traffic = dict(traffic, rate_per_s=rate)
        run.spans = benchrun.Spans(False)
        open_loop.plan(run, state)
        open_loop.start(run, state)
        win = open_loop.window(run, state)
        lat = state["latencies"]
        half = len(lat) // 2
        first, second = stats.percentile(lat[:half], 50), stats.percentile(lat[half:], 50)
        row = {"rate_per_s": rate, "sessions": state["sessions"],
               "p50_ms": win.metrics["visibility_p50_ms"],
               "p95_ms": win.metrics["visibility_p95_ms"],
               "first_half_p50_ms": first, "second_half_p50_ms": second,
               "failed": win.failed, "lost": state["lost"],
               "gen_lag_p95_ms": win.readings["gen_lag_p95_ms"],
               "applied_ops_per_s": win.readings["applied_ops_per_s"],
               "pumps": win.readings["pumps"],
               "window_s_at_end": state["mux"].window_seconds(),
               "sustained": win.failed == 0 and second <= 1.5 * first}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
