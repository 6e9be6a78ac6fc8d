"""One open-loop window of a benchmark cell, read through the scheduler's
hand-off ledger, optionally with the profiler on.

    python scripts/ledger_window.py --workload gcn-flickr.zipf \
        --seed 7 --seconds 25 --profile 1 --out results/ledger

Builds the cell's deployment with the benchmark's own harness (same
graph, weights, warm-up and traffic as ``bench/run.py``), offers the
window, and writes one JSON file to ``--out`` with:

* the ledger per batch (``queue.*``, ``device.*``) and the stage service
  times, their sum, and the lane's mean batch latency over the same
  window (``ServerStats.batch_hist``): the closure of the ledger;
* the window's p50 latency from due time, as the benchmark computes it;
* the cost of one station annotation with the profiler off;
* with ``--profile 1``: the device's idle seconds in the window by the
  innermost ``repro.*`` span open on each host thread at the middle of
  each idle gap, the share of the device's busy time that falls inside
  the dispatcher's ``repro.device``/``repro.drain`` spans (the program's
  spans on the device trace's clock), and the metadata of one kernel op
  (whether it carries the ACK step's named scope).

Needs a TPU (as ``bench/run.py`` does).
"""
from __future__ import annotations

import argparse
import bisect
import contextlib
import glob
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

WINDOW = "ledger.window"
PREFIX = "repro."


def union(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def innermost(spans, starts, t):
    """Name of the shortest span of one thread open at ``t`` (spans of
    one thread nest, so walk back from the last start before ``t``)."""
    i = bisect.bisect_right(starts, t) - 1
    best = None
    for j in range(i, max(-1, i - 64), -1):
        name, s, d = spans[j]
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "none"


def read_profile(path):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window, ops, threads, sample = None, [], {}, None
    device_done = False
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and not device_done:
            device_done = True
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    ops.append((int(e.start_ns),
                                int(e.start_ns + e.duration_ns)))
                    if sample is None and "fused_gnn_layer" in e.name:
                        sample = {"name": e.name.split(" = ")[0],
                                  "stats": {k: str(v)[:300]
                                            for k, v in e.stats}}
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                spans = []
                for e in line.events:
                    if e.name == WINDOW:
                        window = (int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                    elif e.name.startswith(PREFIX):
                        spans.append((e.name[len(PREFIX):],
                                      int(e.start_ns), int(e.duration_ns)))
                if spans:
                    threads[f"{plane.name}:{k}:{line.name}"] = sorted(
                        spans, key=lambda x: x[1])
    return window, ops, threads, sample


def idle_by_thread(window, ops, threads):
    lo, hi = window
    busy = union([(max(s, lo), min(e, hi)) for s, e in ops
                  if min(e, hi) > max(s, lo)])
    busy_ns = sum(e - s for s, e in busy)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    out = {}
    for tid, spans in threads.items():
        starts = [s for _, s, _ in spans]
        names = sorted({n for n, _, _ in spans})
        by = {}
        for s, e in gaps:
            lab = innermost(spans, starts, (s + e) // 2)
            by[lab] = by.get(lab, 0.0) + (e - s) * 1e-9
        out[tid] = {"stations": names,
                    "idle_s": dict(sorted(by.items(),
                                          key=lambda kv: -kv[1]))}
    # the device's busy time inside the dispatcher's device/drain spans
    disp = [sp for sp in threads.values()
            if any(n == "drain" for n, _, _ in sp)]
    inside = 0
    if disp:
        cover = union([(s, s + d) for n, s, d in disp[0]
                       if n in ("device", "drain")])
        for s, e in busy:
            for cs, ce in cover:
                inside += max(0, min(e, ce) - max(s, cs))
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_ns * 1e-9,
            "idle_s": (hi - lo - busy_ns) * 1e-9,
            "busy_in_device_or_drain": inside / busy_ns if busy_ns else None,
            "threads": out}


def annotation_cost_us(n=100000):
    from jax.profiler import TraceAnnotation
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(n):
            with TraceAnnotation("repro.select", seq=i, queued_us=1.0):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    return 1e6 * best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "results",
                                                  "ledger"))
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from bench import graphgen, harness, loadgen
    from repro.compile_cache import enable_compile_cache

    cell = harness.load_cell(ROOT, args.workload)
    enable_compile_cache(ROOT)
    devices, _ = harness.chip(int(cell.workload["chips"]),
                              os.path.join(cell.bench_dir, "peaks.json"))
    counter = harness.CompileCounter()
    graph = graphgen.make_graph(cell.config["graph"])
    dep = harness.deploy(cell, graph, args.seed)
    trace_dir = None
    try:
        harness.warm_up(cell, dep, args.seed)
        schedule = loadgen.window_schedule(
            cell.mix, args.seed, args.seconds, graph.degrees,
            traffic_dir=os.path.join(cell.bench_dir, "traffic"))
        if args.profile:
            trace_dir = tempfile.mkdtemp(prefix="ledger_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        lane = dep.server.model_stats(dep.lane)
        h0 = (lane.batch_hist.count, lane.batch_hist.mean)
        marked = (lambda: jax.profiler.TraceAnnotation(WINDOW)) \
            if args.profile else (lambda: contextlib.nullcontext())
        gen, before, after, compiles, _ = harness.run_window(
            dep, schedule, args.seconds, counter, marked)
        h1 = (lane.batch_hist.count, lane.batch_hist.mean)
        if args.profile:
            jax.profiler.stop_trace()
    finally:
        dep.server.stop()
        dep.engine.close()
    ok = np.array([r is not None and r.error is None and r.t_done > 0
                   for r in gen.requests], bool)
    t_done = np.array([r.t_done if r is not None else 0.0
                       for r in gen.requests])
    lat = loadgen.latencies(gen.due_abs, t_done, ok)
    n = after["batches"] - before["batches"]
    keys = sorted(set(after["stage_times"]) | set(before["stage_times"]))
    per_batch = {k: 1e3 * (after["stage_times"].get(k, 0.0)
                           - before["stage_times"].get(k, 0.0)) / n
                 for k in keys}
    lane_n = h1[0] - h0[0]
    lane_mean_ms = 1e3 * (h1[0] * h1[1] - h0[0] * h0[1]) / lane_n
    path_ms = sum(v for k, v in per_batch.items() if k != "queue.lane")
    waits_ms = sum(v for k, v in per_batch.items()
                   if k.startswith("queue.") and k != "queue.lane")
    requests = after["lane_requests"] - before["lane_requests"]
    out = {"workload": args.workload, "seed": args.seed,
           "profile": args.profile, "device": devices[0].device_kind,
           "batches": n, "lane_batches": lane_n, "requests": requests,
           "compiles_in_window": compiles,
           "latency_p50_ms": 1e3 * loadgen.percentile(lat, 50),
           "latency_p99_ms": 1e3 * loadgen.percentile(lat, 99),
           "failed": int((~ok).sum()),
           "ms_per_batch": per_batch,
           "lane_wait_ms_per_request": 1e3 * (
               after["stage_times"].get("queue.lane", 0.0)
               - before["stage_times"].get("queue.lane", 0.0)) / requests,
           "ledger_path_ms_per_batch": path_ms,
           "waits_ms_per_batch": waits_ms,
           "lane_batch_mean_ms": lane_mean_ms,
           "closure": path_ms / lane_mean_ms,
           "annotation_cost_us": annotation_cost_us()}
    if trace_dir is not None:
        try:
            found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                              recursive=True)
            window, ops, threads, sample = read_profile(found[0])
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        out["profile_trace"] = idle_by_thread(window, ops, threads)
        out["kernel_op_sample"] = sample
    os.makedirs(args.out, exist_ok=True)
    name = f"{args.workload}-{args.seed}-p{args.profile}.json"
    with open(os.path.join(args.out, name), "w") as f:
        json.dump(out, f, indent=1)
    brief = {k: out[k] for k in ("workload", "profile", "latency_p50_ms",
                                 "ledger_path_ms_per_batch",
                                 "lane_batch_mean_ms", "closure",
                                 "waits_ms_per_batch")}
    print(json.dumps(brief), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
