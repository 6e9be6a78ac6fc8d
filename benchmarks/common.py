"""Shared benchmark utilities: timing, result persistence, dataset prep.

Result persistence is ONE writer: ``record_trajectory(name, payload)``
appends a timestamped record to the tracked append-only trajectory
``results/BENCH_<name>.json`` (a JSON list, one entry per run). The old
dual scheme — a per-run snapshot under ``results/bench/`` PLUS the
trajectory — left a stray untracked tree in every checkout; the
trajectory's newest entry IS the latest snapshot, so the snapshot dir is
gone. Pass ``regress={...}`` with lower-is-better scalars to gate the
run against its own history via ``python -m repro.obs.regress``.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, Optional

import numpy as np

RESULTS_DIR = os.environ.get("REPRO_BENCH_DIR", "results")

# container-scale dataset knobs (full-scale graphs exceed 1-core CPU time
# budgets; degree structure and feature dims are preserved)
QUICK_SCALE = {"flickr": 0.02, "ogbn-arxiv": 0.01, "reddit": 0.004}
FULL_SCALE = {"flickr": 0.2, "ogbn-arxiv": 0.1, "reddit": 0.02}


def enable_cache() -> str:
    """Persistent compile cache for a benchmark entry point, kept where
    ``repro.compile_cache`` says (call before the first compile)."""
    from repro.compile_cache import enable_compile_cache
    return enable_compile_cache(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(fn: Callable, *, warmup: int = 1, iters: int = 3) -> Dict:
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    a = np.array(ts)
    return {"mean_s": float(a.mean()), "min_s": float(a.min()),
            "std_s": float(a.std()), "iters": iters}


def trajectory_path(name: str) -> str:
    """The tracked trajectory artifact for one suite, governed by
    REPRO_BENCH_DIR (default results/): results/BENCH_<name>.json."""
    return os.path.join(RESULTS_DIR, f"BENCH_{name}.json")


def record_trajectory(name: str, payload: dict,
                      regress: Optional[dict] = None) -> str:
    """Append one timestamped run record to the suite's trajectory (the
    ONE benchmark writer; created on first use, unreadable/corrupt files
    restart the list). ``regress`` carries this run's lower-is-better
    gate scalars for ``python -m repro.obs.regress``."""
    record = dict(payload, timestamp=time.strftime("%Y-%m-%dT%H:%M:%S"))
    if regress:
        record["regress"] = {k: float(v) for k, v in regress.items()}
    path = trajectory_path(name)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    runs = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                runs = json.load(f)
            if not isinstance(runs, list):
                runs = [runs]
        except (json.JSONDecodeError, OSError):
            runs = []
    runs.append(record)
    with open(path, "w") as f:
        json.dump(runs, f, indent=1, default=float)
    print(f"trajectory appended to {path}")
    return path


def print_table(rows, cols):
    widths = [max(len(str(r.get(c, ""))) for r in rows + [{c: c}])
              for c in cols]
    line = " | ".join(c.ljust(w) for c, w in zip(cols, widths))
    print(line)
    print("-+-".join("-" * w for w in widths))
    for r in rows:
        print(" | ".join(str(r.get(c, "")).ljust(w)
                         for c, w in zip(cols, widths)))
