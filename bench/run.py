"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload gat-flickr.zipf --seed 7 --seconds 20 \
        --trace 0

Loads the cell's configuration and traffic mix (named in BENCHMARK.json),
builds the graph and the server, warms up, offers the mix open-loop for
``--seconds``, checks a sample of the answers against the plain reference,
and prints one JSON object as the last line of standard output: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics from a run with the profiler on from set-up until the
window has drained.

Needs a TPU listed in bench/peaks.json (and as many chips as the cell
asks for); without one it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    try:
        import repro  # noqa: F401  the system under test
        from bench import harness
    except ImportError as e:
        print(f"bench: cannot import the program or the harness: {e}",
              file=sys.stderr)
        return 2
    try:
        result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                             bool(args.trace))
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
