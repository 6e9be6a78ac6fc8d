"""Open-loop load: one general generator over traffic-mix data files.

A traffic mix is ``bench/traffic/<mix>.json``::

    {"targets": {"kind": "zipf", "a": 1.1},   # sampler bench/traffic/zipf.py
     "rate_per_s": 320,                        # offered load, targets/s
     "warmup_requests": 6000}                  # set-up traffic, same law

One request is one target vertex id. Arrivals are Poisson at the mix's
rate, with the count fixed: a window of ``seconds`` holds exactly
``round(rate * seconds)`` requests. Every seed gets the same work in
another order: the targets and the exponential gaps between arrivals are
drawn once from the mix's own ``seed`` (0 unless the file gives one), and
the run's seed shuffles both; the same holds for the warm-up's targets.
Each request's latency runs from its due time, not from when the
generator got to it; the generator's own lateness is reported beside it.
"""
from __future__ import annotations

import importlib.util
import json
import os
import threading
import time
from dataclasses import dataclass
from typing import Callable, List, Optional

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")

# independent random streams drawn from one run seed
STREAM_WINDOW, STREAM_WARMUP, STREAM_SAMPLE, STREAM_WEIGHTS = range(4)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one purpose of one run; any whole-number seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                         stream]))


def load_mix(name: str, traffic_dir: str = TRAFFIC_DIR) -> dict:
    with open(os.path.join(traffic_dir, f"{name}.json")) as f:
        return json.load(f)


def sampler(kind: str, traffic_dir: str = TRAFFIC_DIR) -> Callable:
    """The ``sample(rng, degrees, n, **params)`` of
    ``bench/traffic/<kind>.py``."""
    path = os.path.join(traffic_dir, f"{kind}.py")
    spec = importlib.util.spec_from_file_location(f"bench_traffic_{kind}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.sample


def draw_targets(mix: dict, rng: np.random.Generator, degrees: np.ndarray,
                 n: int, traffic_dir: str = TRAFFIC_DIR) -> np.ndarray:
    params = dict(mix["targets"])
    kind = params.pop("kind")
    return np.asarray(sampler(kind, traffic_dir)(rng, degrees, n, **params),
                      np.int64)


@dataclass
class Schedule:
    due: np.ndarray         # [n] seconds after the window opens, sorted
    targets: np.ndarray     # [n] vertex ids


def window_schedule(mix: dict, seed: int, seconds: float,
                    degrees: np.ndarray, rate: Optional[float] = None,
                    traffic_dir: str = TRAFFIC_DIR) -> Schedule:
    rate = float(mix["rate_per_s"] if rate is None else rate)
    n = int(round(rate * seconds))
    fixed = rng_for(int(mix.get("seed", 0)), STREAM_WINDOW)
    targets = draw_targets(mix, fixed, degrees, n, traffic_dir)
    gaps = fixed.exponential(1.0, size=n + 1)
    gaps *= seconds / gaps.sum()        # n arrivals, then the window's end
    order = rng_for(seed, STREAM_WINDOW)
    due = np.cumsum(order.permutation(gaps[:n]))
    return Schedule(due, order.permutation(targets))


def warmup_targets(mix: dict, seed: int, degrees: np.ndarray,
                   traffic_dir: str = TRAFFIC_DIR) -> np.ndarray:
    fixed = rng_for(int(mix.get("seed", 0)), STREAM_WARMUP)
    targets = draw_targets(mix, fixed, degrees,
                           int(mix["warmup_requests"]), traffic_dir)
    return rng_for(seed, STREAM_WARMUP).permutation(targets)


class OpenLoop:
    """Submits ``schedule`` through ``submit(target) -> request`` on its
    own thread, each request at its due time (``t0`` + due, on the
    ``time.perf_counter`` clock). Requests that fall due together go out
    together; none waits for an earlier one to complete."""

    def __init__(self, schedule: Schedule, submit: Callable):
        self.schedule = schedule
        self.submit = submit
        n = len(schedule.due)
        self.requests: List = [None] * n
        self.t_submit = np.zeros(n)
        self.t0 = 0.0
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._loop, name="openloop",
                                        daemon=True)

    def start(self, t0: float) -> None:
        self.t0 = t0
        self._thread.start()

    def join(self, timeout: float) -> None:
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise TimeoutError("load generator did not finish")
        if self.error is not None:
            raise RuntimeError("load generator failed") from self.error

    @property
    def due_abs(self) -> np.ndarray:
        return self.t0 + self.schedule.due

    def _loop(self) -> None:
        due = self.due_abs
        targets = self.schedule.targets
        try:
            for i in range(len(due)):
                wait = due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                self.t_submit[i] = time.perf_counter()
                self.requests[i] = self.submit(int(targets[i]))
        except BaseException as e:       # noqa: BLE001 — re-raised in join
            self.error = e


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile (no interpolation), so a failed request
    counted as an infinite latency stays infinite instead of turning the
    result into nan."""
    return float(np.percentile(values, q, method="higher"))


def latencies(due_abs: np.ndarray, t_done: np.ndarray,
              ok: np.ndarray) -> np.ndarray:
    """Seconds from each request's due time to its completion; a request
    that failed or never completed counts as infinitely late."""
    lat = np.asarray(t_done, np.float64) - np.asarray(due_abs, np.float64)
    return np.where(np.asarray(ok, bool), lat, np.inf)
