"""One run of one benchmark cell: set-up, warm-up, an open-loop window,
the check against the plain reference, and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name in ``BENCHMARK.json``:

  bench/configs/<config>.json     sizes, serving settings, check limits
  bench/models/<kind>.py          weights, the plain reference model and
                                  its counts (``model_flops``,
                                  ``FUSED_USES``)
  bench/kernels/<kernel>.py       one Pallas kernel's count per call
  bench/traffic/<mix>.json        rate, target law and warm-up size;
  bench/traffic/<law>.py          the target sampler the mix names
  bench/metrics/<metric>.py       one per-layer metric reader; its
                                  ``MODEL_NEEDS`` names what it reads
                                  from the model module
  bench/peaks.json                chip peaks by ``device_kind``

The configuration's ``model`` and ``serving`` groups reach the program
key by key: each key is the field of that name of ``GNNConfig`` and
``ServingConfig``, converted to the field's type.

From the program this file takes the system under test: the graph
constructor, ``GNNServer`` with its engine, and the engine's counters.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
import typing
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import loadgen, reference
from repro.compile_cache import enable_compile_cache

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")
DRAIN_S = 60.0              # how long past the window an answer may come


class NoChip(RuntimeError):
    """The run needs an accelerator that is not here."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# files found by name


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names."""
    name: str
    workload: dict
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str
    _module: object = dataclasses.field(default=None, init=False,
                                        repr=False, compare=False)

    @property
    def model(self) -> dict:
        return self.config["model"]

    def model_module_path(self) -> str:
        return os.path.join(self.bench_dir, "models",
                            f"{self.model['kind']}.py")

    def model_module(self):
        """``bench/models/<kind>.py``, loaded once."""
        if self._module is None:
            self._module = load_module(self.model_module_path(),
                                       f"bench_model_{self.model['kind']}")
        return self._module

    def metric_readers(self) -> Dict[str, object]:
        """This cell's per-layer readers, each checked against its entry
        in ``BENCHMARK.json``."""
        out = {}
        for m in self.per_layer:
            mod = load_module(os.path.join(self.bench_dir, "metrics",
                                           f"{m['name']}.py"),
                              f"bench_metric_{m['name']}")
            for key in ("layer", "unit", "source", "moves", "better"):
                if getattr(mod, key.upper()) != m[key]:
                    raise ValueError(
                        f"metric {m['name']}: {key} is "
                        f"{getattr(mod, key.upper())!r} in its reader but "
                        f"{m[key]!r} in BENCHMARK.json")
            out[m["name"]] = mod
        return out


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str) -> Cell:
    bench_dir = os.path.join(root, "bench")
    bm = load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bm['workloads']]}")
    cfg_entry = next(c for c in bm["configs"] if c["name"] == entry["config"])
    config = load_json(os.path.join(root, cfg_entry["file"]))
    mix = loadgen.load_mix(entry["traffic"],
                           os.path.join(bench_dir, "traffic"))
    cell = Cell(name=name, workload=entry, config=config, mix=mix,
                end_to_end=[m for m in bm["end_to_end"]
                            if applies(m, name)],
                per_layer=[m for m in bm["per_layer"] if applies(m, name)],
                bench_dir=bench_dir)
    module = cell.model_module()
    for metric, reader in cell.metric_readers().items():
        for need in getattr(reader, "MODEL_NEEDS", ()):
            if not hasattr(module, need):
                raise ValueError(
                    f"{cell.model_module_path()} has no {need}, which "
                    f"metric {metric} of {name} needs")
    program_configs(cell)
    return cell


# ---------------------------------------------------------------------------
# the chip


def chip(chips: int, peaks_path: str):
    """(devices, peaks) of the accelerator this run needs; raises
    ``NoChip`` rather than fall back to the CPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from e
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"JAX's first device is {dev.platform!r}, not a TPU")
    peaks = load_json(peaks_path)
    if dev.device_kind not in peaks:
        raise NoChip(f"{dev.device_kind!r} is not in {peaks_path}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices[:chips], peaks[dev.device_kind]


class CompileCounter:
    """Counts compilations (and programs read back from the persistent
    cache) through JAX's monitoring events, as they happen."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    def _timed(self, event, duration, **_):
        if event == COMPILE_EVENTS[0]:
            self.count += 1

    def _event(self, event, **_):
        if event == COMPILE_EVENTS[1]:
            self.count += 1


# ---------------------------------------------------------------------------
# the deployment under test


@dataclass
class Deployment:
    server: object
    engine: object
    graph: object
    params: dict            # the benchmark's own weights (unpadded)
    lane: str


def make_weights(cell: Cell, seed: int):
    """The model's weights, made on the device in one jitted call from the
    run's seed."""
    import jax
    key_seed = int(loadgen.rng_for(seed, loadgen.STREAM_WEIGHTS)
                   .integers(0, 2 ** 31 - 1))
    init = cell.model_module().init
    model = cell.model
    return jax.jit(lambda k: init(k, model))(jax.random.PRNGKey(key_seed))


def from_config(cls, values: dict, where: str):
    """``cls(**values)``, each value converted to its field's type: int,
    float, str and bool by that type, a nested dataclass (``store`` to
    ``StorePolicy``) key by key, a tuple from a list, ``None`` where the
    field is optional. A key ``cls`` has no field for is refused."""
    hints = typing.get_type_hints(cls)
    names = {f.name for f in dataclasses.fields(cls)}
    for key in values:
        if key not in names:
            raise ValueError(f"configuration key {where}.{key}: "
                             f"{cls.__name__} has no field {key!r}")
    return cls(**{k: _as_type(hints[k], v, f"{where}.{k}")
                  for k, v in values.items()})


def _as_type(hint, value, where: str):
    if dataclasses.is_dataclass(hint):
        return from_config(hint, value, where)
    args = [a for a in typing.get_args(hint) if a is not type(None)]
    if typing.get_origin(hint) is typing.Union:
        if value is None:
            return None
        return _as_type(args[0], value, where) if len(args) == 1 else value
    if typing.get_origin(hint) is tuple:
        return tuple(value)
    if hint in (int, float, str, bool):
        return hint(value)
    return value


def program_configs(cell: Cell):
    """The program's ``GNNConfig`` and ``ServingConfig`` from the
    configuration's ``model`` and ``serving`` groups."""
    from repro.core.config import ServingConfig
    from repro.gnn.model import GNNConfig
    return (from_config(GNNConfig, cell.model, "model"),
            from_config(ServingConfig, cell.config["serving"], "serving"))


def deploy(cell: Cell, graph, seed: int) -> Deployment:
    """The cell's server with one lane, on the seed's weights."""
    from repro.serve.gnn_server import GNNServer

    gcfg, sconf = program_configs(cell)
    params = make_weights(cell, seed)
    server = GNNServer(max_wait_s=sconf.max_wait_s)
    lane = cell.config["name"]
    server.register(lane, graph=graph, cfg=gcfg, params=params,
                    config=sconf)
    return Deployment(server, server.engine_for(lane), graph, params, lane)


def counters(dep: Deployment) -> dict:
    """The program's own counters, read between batches."""
    st = dep.engine.scheduler.stats
    lane = dep.server.model_stats(dep.lane)
    return {"batches": st.n_batches, "stage_times": dict(st.stage_times),
            "bytes_shipped": st.bytes_shipped, "cache_hits": st.cache_hits,
            "cache_misses": st.cache_misses, "build_hits": st.build_hits,
            "build_misses": st.build_misses,
            "lane_batches": lane.n_batches, "lane_requests": lane.hist.count}


def warm_up(cell: Cell, dep: Deployment, seed: int) -> int:
    """Start the server and answer the mix's warm-up traffic, sent at
    once: it compiles the program (or reads it from the cache) and fills
    the caches as the cell's own traffic would. Returns its size."""
    targets = loadgen.warmup_targets(cell.mix, seed, dep.graph.degrees,
                                     os.path.join(cell.bench_dir, "traffic"))
    dep.server.start()
    reqs = [dep.server.submit(int(t), model=dep.lane) for t in targets]
    dep.server.drain(reqs, timeout=600)
    return len(targets)


# ---------------------------------------------------------------------------
# one run


@dataclass
class RunRecord:
    """What the per-layer readers read (``bench/metrics/*.py``)."""
    cell: Cell
    peaks: dict
    seconds: float
    lat: np.ndarray                  # s from due time, inf if failed
    lag: np.ndarray                  # s the generator submitted late
    before: dict                     # counters() at the window's open
    after: dict                      # counters() once it has drained
    compiles: int                    # in the window
    trace: Optional[object] = None   # tracing.Summary of the traced span

    def delta(self, key: str) -> float:
        return self.after[key] - self.before[key]

    def stage_ms_per_batch(self, stage: str) -> Optional[float]:
        n = self.delta("batches")
        if not n:
            return None
        t = self.after["stage_times"].get(stage, 0.0) \
            - self.before["stage_times"].get(stage, 0.0)
        return 1e3 * t / n


def run_window(dep: Deployment, schedule: loadgen.Schedule, seconds: float,
               compile_counter: CompileCounter, marked=None):
    """Offer ``schedule`` open-loop for ``seconds`` and wait for the
    answers (up to ``DRAIN_S`` past the close); returns the load
    generator, the counters before and after, the compiles inside the
    window, and the window's (open, close) times. ``marked()``, if given,
    is a context manager held from the window's open to its close (the
    profiler's mark of the window)."""
    before = counters(dep)
    c0 = compile_counter.count
    gen = loadgen.OpenLoop(schedule,
                           lambda t: dep.server.submit(t, model=dep.lane))
    t0 = time.perf_counter() + 0.05
    gen.start(t0)
    end = t0 + seconds
    sleep_until(t0)
    with (marked() if marked is not None else contextlib.nullcontext()):
        sleep_until(end)
    compiles = compile_counter.count - c0
    gen.join(timeout=DRAIN_S)
    pending = [r for r in gen.requests if r is not None]
    deadline = end + DRAIN_S
    i = 0
    while i < len(pending) and time.perf_counter() < deadline:
        if pending[i].t_done or pending[i].error is not None:
            i += 1
        else:
            time.sleep(0.01)
    after = counters(dep)
    return gen, before, after, compiles, (t0, end)


def sleep_until(t: float) -> None:
    while time.perf_counter() < t:
        time.sleep(min(0.05, max(0.0, t - time.perf_counter())))


class GcPauses:
    """Python's garbage-collector pauses, by generation, as they happen
    (``gc.callbacks``): a whole-process stall shows here if the collector
    causes it."""

    def __init__(self):
        self.pauses: List[tuple] = []       # (start, seconds, generation)
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((self._t, time.perf_counter() - self._t,
                                info["generation"]))

    def close(self) -> None:
        if self._cb in gc.callbacks:
            gc.callbacks.remove(self._cb)

    def summary(self, lo: float, hi: float) -> str:
        inside = [p for p in self.pauses if lo <= p[0] <= hi]
        parts = []
        for g in range(3):
            d = [p[1] for p in inside if p[2] == g]
            parts.append(f"gen{g} {len(d)} max_ms "
                         f"{1e3 * max(d, default=0.0):.3f} sum_ms "
                         f"{1e3 * sum(d):.3f}")
        return "; ".join(parts)


# the plain reference as the configuration states it, at full float32
# matmul precision (information), and the control: the same reference in
# the next precision below the configuration's, bfloat16
REFERENCE = {"ref": (None, None), "ref_highest": (None, "highest")}
CONTROL = {"control_bf16": ("bfloat16", None)}


def reference_rows(cell: Cell, graph, params, targets,
                   variants=None) -> Dict[str, np.ndarray]:
    """The plain reference's embeddings of ``targets``, one row each, for
    each of ``variants`` (default ``REFERENCE``)."""
    arrays = {"indptr": graph.indptr, "indices": graph.indices,
              "features": graph.features}
    batch = int(cell.config["serving"]["batch_size"])
    return reference.embeddings(cell.model_module().forward, params, arrays,
                                targets, cell.model, batch,
                                REFERENCE if variants is None else variants)


def sample_requests(gen: loadgen.OpenLoop, seed: int, k: int) -> list:
    """``k`` of the window's requests, drawn from the seed."""
    n = len(gen.requests)
    idx = np.sort(loadgen.rng_for(seed, loadgen.STREAM_SAMPLE)
                  .choice(n, size=min(k, n), replace=False))
    return [gen.requests[i] for i in idx]


def check_sample(cell: Cell, graph, params, gen: loadgen.OpenLoop,
                 seed: int, served_override: Optional[Callable] = None):
    """The served answers of a sample of the window's requests, drawn from
    the seed, beside the plain reference's. Returns (checks, info):
    ``checks`` maps each compared number to its value and limit."""
    chk = cell.config["check"]
    reqs = sample_requests(gen, seed, int(chk["sample"]))
    answered = [r for r in reqs if r is not None and r.embedding is not None]
    targets = [r.target for r in answered]
    served = np.stack([r.embedding for r in answered]) if answered \
        else np.zeros((0, int(cell.model["f_hidden"])), np.float32)
    if served_override is not None:
        served = served_override(targets, served)
    info = {"sampled": len(reqs), "distinct_targets": len(set(targets))}
    stats = {"emb_gap": float("inf"), "emb_rms_gap": float("inf")}
    if targets:
        uniq = sorted(set(targets))
        rows = [uniq.index(t) for t in targets]
        refs = reference_rows(cell, graph, params, uniq)
        stats = gaps(served, refs["ref"][rows])
        for k, v in gaps(served, refs["ref_highest"][rows]).items():
            info[f"{k}_highest"] = v
    checks = {name: {"value": stats[name], "limit": float(limit)}
              for name, limit in chk["limits"].items()}
    checks["unanswered"] = {"value": len(reqs) - len(answered), "limit": 0}
    info.update({k: v for k, v in stats.items() if k not in checks})
    return checks, info


def gaps(got: np.ndarray, want: np.ndarray) -> Dict[str, float]:
    """The numbers a check may compare: the worst row's relative gap and
    the whole sample's relative root-mean-square gap."""
    return {"emb_gap": float(reference.relative_gap(got, want).max()),
            "emb_rms_gap": reference.relative_rms_gap(got, want)}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        *, need_chip: bool = True, chaos: Optional[Callable] = None,
        served_override: Optional[Callable] = None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``need_chip=False`` skips the look for a TPU (tests on the CPU);
    ``chaos(dep)`` may break the deployment under test before the window
    and ``served_override(targets, served)`` may replace the served
    answers before the check (tests of the check itself)."""
    t_start = time.perf_counter()
    cell = load_cell(root, workload)
    import jax
    enable_compile_cache(root)
    chips = int(cell.workload["chips"])
    peaks_path = os.path.join(cell.bench_dir, "peaks.json")
    if need_chip:
        devices, peaks = chip(chips, peaks_path)
    else:
        devices = jax.devices()[:chips]
        peaks = next(iter(load_json(peaks_path).values()))
    compile_counter = CompileCounter()
    gc_pauses = GcPauses()
    from bench import graphgen
    graph = graphgen.make_graph(cell.config["graph"])
    dep = deploy(cell, graph, seed)
    t_built = time.perf_counter()
    tracer = None
    try:
        n_warm = warm_up(cell, dep, seed)
        if chaos is not None:
            chaos(dep)
        schedule = loadgen.window_schedule(
            cell.mix, seed, seconds, graph.degrees,
            traffic_dir=os.path.join(cell.bench_dir, "traffic"))
        if trace:
            from bench import tracing
            tracer = tracing.WindowTracer(dep, cell)
            tracer.start()
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s:.2f}s (build {t_built - t_start:.2f}s, "
            f"warm-up {n_warm} requests); window {seconds}s, "
            f"{len(schedule.due)} requests")
        gen, before, after, compiles, (t0, end) = run_window(
            dep, schedule, seconds, compile_counter,
            tracer.window if tracer is not None else None)
        if tracer is not None:
            tracer.stop()
        peak_bytes = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devices)
        reqs = gen.requests
        ok = np.array([r is not None and r.error is None and r.t_done > 0
                       and r.embedding is not None
                       and r.embedding.shape == (int(cell.model["f_hidden"]),)
                       and bool(np.isfinite(r.embedding).all())
                       for r in reqs], bool)
        t_done = np.array([r.t_done if r is not None else 0.0
                           for r in reqs])
        lat = loadgen.latencies(gen.due_abs, t_done, ok)
        lag = gen.t_submit - gen.due_abs
        failed = int((~ok).sum())
        in_window = int((ok & (t_done <= end)).sum())
        record = RunRecord(cell=cell, peaks=peaks, seconds=seconds, lat=lat,
                           lag=lag, before=before, after=after,
                           compiles=compiles)
        if tracer is not None:
            record.trace = tracer.summary()
    finally:
        if tracer is not None:
            tracer.stop()
        gc_pauses.close()
        dep.server.stop()
        dep.engine.close()
    graph, params = dep.graph, dep.params
    del dep
    gc.collect()
    checks, info = check_sample(cell, graph, params, gen, seed,
                                served_override)
    result = {"correct": bool(passed(checks) and failed == 0),
              "attempted": int(len(reqs)), "failed": failed}
    if trace:
        result["metrics"] = layer_metrics(cell, record)
    else:
        result["metrics"] = {
            "latency_p50_ms": {"value": 1e3 * loadgen.percentile(lat, 50),
                               "unit": "ms"},
            "latency_p99_ms": {"value": 1e3 * loadgen.percentile(lat, 99),
                               "unit": "ms"},
            "targets_per_s": {"value": in_window / seconds,
                              "unit": "targets/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        result["metrics"] = {k: v for k, v in result["metrics"].items()
                             if any(m["name"] == k for m in cell.end_to_end)}
    dev = devices[0]
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devices),
                        "memory_peak_bytes": peak_bytes}
    if trace and record.trace is not None:
        result["device"]["busy_s"] = record.trace.busy_s
        result["device"]["window_s"] = record.trace.window_s
        result["breakdown"] = record.trace.breakdown()
    for k, v in info.items():
        log(f"info {k} {v}")
    log(f"info compiles_in_window {compiles} in_window {in_window} "
        f"p50_ms {1e3 * loadgen.percentile(lat, 50):.3f} "
        f"p99_ms {1e3 * loadgen.percentile(lat, 99):.3f} gen_lag_p99_ms "
        f"{1e3 * loadgen.percentile(lag, 99):.3f}")
    log(f"info gc_pauses_in_window {gc_pauses.summary(t0, end)}")
    slowest = np.argsort(lat)[-5:][::-1]
    log("info slowest_ms_at_s " + " ".join(
        f"{1e3 * lat[i]:.1f}@{schedule.due[i]:.2f}" for i in slowest))
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def layer_metrics(cell: Cell, record: RunRecord) -> dict:
    out = {}
    for name, mod in cell.metric_readers().items():
        value = mod.read(record)
        if value is not None:
            out[name] = {"value": float(value), "unit": mod.UNIT}
    return out


__all__ = ["run", "load_cell", "Cell", "RunRecord", "NoChip"]
