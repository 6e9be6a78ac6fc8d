"""The readings a cell's check limit is set from, on the chip.

    python bench/control.py --workload gat-flickr.zipf --seconds 5 \
        --seeds 101,102,103,...

For each seed, in one process (the graph is built once): the cell's own
set-up, warm-up and a short open-loop window at its own rate, then the
check's sample of served answers against the plain reference. It prints
one JSON line per seed with

  emb_gap, emb_rms_gap
        the program against the reference (lower readings): the worst
        sampled row's relative gap, and the sample's relative RMS gap
  control_emb_gap, control_emb_rms_gap
        the control against the reference: the reference itself,
        computed in bfloat16, in the program's place (upper readings)
  *_highest
        the same against the reference at full float32 matmul precision
        (information)

The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also read the control")
    args = ap.parse_args(argv)
    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from bench import graphgen, harness, loadgen

    cell = harness.load_cell(ROOT, args.workload)
    harness.enable_compile_cache(ROOT)
    try:
        harness.chip(int(cell.workload["chips"]),
                     os.path.join(cell.bench_dir, "peaks.json"))
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    counter = harness.CompileCounter()
    graph = graphgen.make_graph(cell.config["graph"])
    tdir = os.path.join(cell.bench_dir, "traffic")
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        dep = harness.deploy(cell, graph, seed)
        params = dep.params
        try:
            harness.warm_up(cell, dep, seed)
            sched = loadgen.window_schedule(cell.mix, seed, args.seconds,
                                            graph.degrees, traffic_dir=tdir)
            gen, *_ = harness.run_window(dep, sched, args.seconds, counter)
        finally:
            dep.server.stop()
            dep.engine.close()
        del dep
        gc.collect()
        checks, info = harness.check_sample(cell, graph, params, gen, seed)
        row = {"workload": args.workload, "seed": seed,
               **{k: c["value"] for k, c in checks.items()}, **info}
        if i < args.control_seeds:
            reqs = harness.sample_requests(gen, seed,
                                           int(cell.config["check"]
                                               ["sample"]))
            targets = [r.target for r in reqs if r is not None]
            uniq = sorted(set(targets))
            rows = [uniq.index(t) for t in targets]
            refs = harness.reference_rows(
                cell, graph, params, uniq,
                {**harness.REFERENCE, **harness.CONTROL})
            control = refs["control_bf16"][rows]
            for suffix, ref in (("", "ref"), ("_highest", "ref_highest")):
                for k, v in harness.gaps(control, refs[ref][rows]).items():
                    row[f"control_{k}{suffix}"] = v
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
