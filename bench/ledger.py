"""Readers of the scheduler's hand-off ledger: the namespaced keys
(``queue.*``, ``device.*``) that the program folds into
``SchedulerStats.stage_times`` beside the stage service times, and that
``harness.counters`` copies with them. A program without the ledger has
no such key; the readers then return None, and the result line leaves the
metric out."""
from __future__ import annotations

from typing import Optional


def _has(run, key: str) -> bool:
    return key in run.after.get("stage_times", {})


def ms_per_batch(run, key: str) -> Optional[float]:
    """Milliseconds under ``key`` per scheduler batch in the window."""
    if not _has(run, key):
        return None
    return run.stage_ms_per_batch(key)


def ms_per_request(run, key: str) -> Optional[float]:
    """Milliseconds under ``key`` per answered request in the window."""
    n = run.delta("lane_requests")
    if not _has(run, key) or not n:
        return None
    t = run.after["stage_times"][key] \
        - run.before.get("stage_times", {}).get(key, 0.0)
    return 1e3 * t / n
