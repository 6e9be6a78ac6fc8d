"""Find a cell's knee: the highest offered rate at which the queue does
not grow over the window.

    python bench/sweep.py --workload gat-flickr.zipf --seed 11 \
        --seconds 10 --rates 100,200,300,400 --repeats 2

One process, one set-up (the cell's configuration, weights and warm-up),
then ``--repeats`` open-loop windows per rate, rates in the order given,
each window on targets of its own (the mix's seed moved on, so that no
window replays targets an earlier one left in the caches), with the cell's target law and the rate
replaced. A window's queue grows when requests due in its last quarter
wait clearly longer than those due in its first quarter; a rate is over
the knee when any of its windows grows. The count completed inside the
window is printed but does not decide: requests due in its last moments
cannot finish by its close however fast the server is. Prints one JSON
line per window; the readings go into PERF.md and the chosen rate into
the cell's traffic file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# growth: last-quarter median wait over first-quarter median wait,
# beyond which the window is over the knee
GROWTH_RATIO = 2.0
GROWTH_FLOOR_MS = 20.0


def grows(due: np.ndarray, lat: np.ndarray, seconds: float) -> bool:
    first = lat[due < 0.25 * seconds]
    last = lat[due >= 0.75 * seconds]
    if not len(first) or not len(last):
        return False
    a, b = np.median(first) * 1e3, np.median(last) * 1e3
    return bool(b > max(GROWTH_RATIO * a, a + GROWTH_FLOOR_MS))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, targets/s")
    ap.add_argument("--repeats", type=int, default=2,
                    help="windows per rate")
    args = ap.parse_args(argv)
    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from bench import graphgen, harness

    cell = harness.load_cell(ROOT, args.workload)
    harness.enable_compile_cache(ROOT)
    try:
        harness.chip(int(cell.workload["chips"]),
                     os.path.join(cell.bench_dir, "peaks.json"))
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    counter = harness.CompileCounter()
    graph = graphgen.make_graph(cell.config["graph"])
    dep = harness.deploy(cell, graph, args.seed)
    try:
        harness.warm_up(cell, dep, args.seed)
        grown, k = 0, 0
        for rate in (float(r) for r in args.rates.split(",")):
            if grown >= 2:          # two rates over the knee: stop
                break
            over = False
            for rep in range(args.repeats):
                k += 1
                row = window(cell, dep, graph, counter, args, rate, k)
                row["repeat"] = rep
                over = over or row["grows"]
                print(json.dumps(row), flush=True)
            grown = grown + 1 if over else 0
    finally:
        dep.server.stop()
        dep.engine.close()
    return 0


def window_mix(mix: dict, k: int) -> dict:
    """The mix of the ``k``-th window: its targets drawn anew."""
    return dict(mix, seed=int(mix.get("seed", 0)) + k)


def window(cell, dep, graph, counter, args, rate: float, k: int) -> dict:
    """One window at ``rate`` on the ``k``-th schedule after the seed."""
    from bench import harness, loadgen
    mix = window_mix(cell.mix, k)
    sched = loadgen.window_schedule(
        mix, args.seed + k, args.seconds, graph.degrees, rate=rate,
        traffic_dir=os.path.join(cell.bench_dir, "traffic"))
    gen, before, after, compiles, (t0, end) = harness.run_window(
        dep, sched, args.seconds, counter)
    ok = np.array([r is not None and r.error is None and r.t_done > 0
                   for r in gen.requests])
    t_done = np.array([r.t_done if r is not None else 0.0
                       for r in gen.requests])
    lat = loadgen.latencies(gen.due_abs, t_done, ok)
    done_in = int((ok & (t_done <= end)).sum())
    return {"workload": args.workload, "rate": rate,
            "offered": len(sched.due), "completed_in_window": done_in,
            "targets_per_s": done_in / args.seconds,
            "p50_ms": 1e3 * loadgen.percentile(lat, 50),
            "p99_ms": 1e3 * loadgen.percentile(lat, 99),
            "gen_lag_p99_ms": 1e3 * loadgen.percentile(
                gen.t_submit - gen.due_abs, 99),
            "batches": after["lane_batches"] - before["lane_batches"],
            "nbr_hits": after["cache_hits"] - before["cache_hits"],
            "nbr_misses": after["cache_misses"] - before["cache_misses"],
            "compiles": compiles,
            "grows": grows(sched.due, lat, args.seconds)}


if __name__ == "__main__":
    raise SystemExit(main())
