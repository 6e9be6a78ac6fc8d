"""Benchmark harness entry point: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # quick pass
  PYTHONPATH=src python -m benchmarks.run --full     # paper-size sweeps
  PYTHONPATH=src python -m benchmarks.run --only fig8_latency

Roofline/dry-run numbers live in launch/dryrun.py + launch/roofline.py
(they need the 512-device env var and are run as their own processes).
"""
from __future__ import annotations

import argparse
import time
import traceback

from benchmarks import (bench_eq1_loadbalance, bench_fig3_breakdown,
                        bench_fig8_latency, bench_fig10_batch,
                        bench_kernels, bench_obs, bench_pipeline,
                        bench_precompute, bench_program, bench_rpc,
                        bench_serve_multimodel, bench_shard,
                        bench_store, bench_table5_load, bench_table6_ini)
from benchmarks.common import enable_cache

SUITES = {
    "fig8_latency": bench_fig8_latency.run,
    "fig10_batch": bench_fig10_batch.run,
    "fig3_breakdown": bench_fig3_breakdown.run,
    "table5_load": bench_table5_load.run,
    "table6_ini": bench_table6_ini.run,
    "eq1_loadbalance": bench_eq1_loadbalance.run,
    "kernels": bench_kernels.run,
    "serve_multimodel": bench_serve_multimodel.run_suite,
    "store": bench_store.run_suite,
    "program": bench_program.run_suite,
    "shard": bench_shard.run_suite,
    "pipeline": bench_pipeline.run_suite,
    "rpc": bench_rpc.run_suite,
    "obs": bench_obs.run_suite,
    "precompute": bench_precompute.run_suite,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    enable_cache()
    names = [args.only] if args.only else list(SUITES)
    failed = []
    for name in names:
        print(f"\n=== {name} {'(full)' if args.full else '(quick)'} ===",
              flush=True)
        t0 = time.time()
        try:
            SUITES[name](quick=not args.full)
            print(f"[{name}] done in {time.time()-t0:.1f}s", flush=True)
        except Exception:   # noqa: BLE001 — report all suites
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"\nFAILED suites: {failed}")
        return 1
    print("\nall benchmark suites passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
