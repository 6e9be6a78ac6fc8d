"""The traced run: host spans on the profiler's clock, and the reduction
from the profiler's trace to device metrics.

Spans come from the benchmark's own wrappers around the calls into each
layer (nothing in the program changes): every engine stage object's
``run`` (named by its ``.name``), the scheduler's device function, and the
lane's ``submit_chunk``. Each is a ``jax.profiler.TraceAnnotation`` named
``bench.<layer>``.

The reduction works on a small normalized form of the trace::

    {"window": [start_ns, end_ns],
     "device_ops": [[name, start_ns, duration_ns], ...],   # per op run
     "modules":    [[name, start_ns, duration_ns], ...],   # per program run
     "host_spans": [[name, start_ns, duration_ns], ...]}   # bench.* spans

so that it can be checked on a recorded trace (``bench/testdata``).
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from bench import flops

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# the Pallas kernels, by the name their custom calls carry in the trace:
# one count file each in bench/kernels
KERNELS = flops.kernel_names()
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


# ---------------------------------------------------------------------------
# reading the trace


def extract(xplane_path: str) -> dict:
    """The normalized form of one ``.xplane.pb`` (first TPU device only)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    out = {"window": None, "device_ops": [], "modules": [],
           "host_spans": []}
    device_done = False
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name) and not device_done:
            device_done = True
            for line in plane.lines:
                key = {OPS_LINE: "device_ops",
                       MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    ev = [short_name(e.name), int(e.start_ns),
                          int(e.duration_ns)]
                    if key == "device_ops" and kernel_of(ev[0]):
                        ev.append(arrays_of(e.name))
                    out[key].append(ev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        span = [e.name, int(e.start_ns), int(e.duration_ns)]
                        if e.name == WINDOW_SPAN:
                            out["window"] = [span[1], span[1] + span[2]]
                        else:
                            out["host_spans"].append(span)
    return out


def short_name(name: str) -> str:
    """An XLA op's instruction name ("%fused_gnn_layer.4 = f32[...]
    custom-call(...)" -> "fused_gnn_layer.4"), a module's name without
    its fingerprint ("jit__take(1780...)" -> "jit__take")."""
    return name.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]


ARRAY = re.compile(r"\b(f32|bf16|f16|s32|u32|s8|u8|pred)\[([\d,]*)\]"
                   r"\{([^}]*)\}")
ITEM_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
              "u8": 1, "pred": 1}


def arrays_of(op_text: str) -> list:
    """[output, operand, ...] of an XLA op's text, each as [shape, item
    bytes, memory space]; space 1 (``S(1)`` in the layout) is on-chip."""
    head = op_text.split("custom_call_target", 1)[0]
    out = []
    for dtype, dims, layout in ARRAY.findall(head):
        space = re.search(r"S\((\d+)\)", layout)
        out.append([[int(d) for d in dims.split(",") if d],
                    ITEM_BYTES[dtype], int(space.group(1)) if space else 0])
    return out


def _clip(ev: Sequence, lo: int, hi: int) -> Optional[Tuple[int, int]]:
    s, e = max(ev[1], lo), min(ev[1] + ev[2], hi)
    return (s, e) if e > s else None


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Disjoint sorted cover of ``intervals``."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def kernel_of(op_name: str) -> Optional[str]:
    for k in KERNELS:
        if k in op_name:
            return k
    return None


@dataclass
class Summary:
    """Device metrics of one traced window."""
    window_s: float
    busy_s: float
    op_seconds: Dict[str, float]              # by op name
    kernel_seconds: Dict[str, float]          # by Pallas kernel, in runs
    kernel_calls: Dict[str, list]             # each call's arrays, in runs
    program_runs: int                         # served program executions
    program_seconds: float
    idle_by_span: Dict[str, float]            # idle time by open host span
    model_flops: float = 0.0                  # of real targets, traced span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def innermost(spans: List[Sequence], t: int) -> str:
    """The name of the shortest host span open at ``t`` (what the host was
    doing then), or "no span"."""
    best = None
    for name, s, d in spans:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name[len(SPAN_PREFIX):], d)
    return best[0] if best is not None else "no span"


def reduce(tr: dict) -> Summary:
    """Busy union, idle share and time by op within the traced window;
    runs of the served program (the module whose runs hold the Pallas
    kernels) that lie wholly inside it, with the kernel calls they made;
    and each idle gap labelled by the host span open in its middle."""
    lo, hi = tr["window"]
    clipped = [(ev, c) for ev in tr["device_ops"]
               if (c := _clip(ev, lo, hi)) is not None]
    busy = union([c for _, c in clipped])
    busy_ns = sum(e - s for s, e in busy)
    op_s: Dict[str, float] = {}
    for ev, (s, e) in clipped:
        op_s[ev[0]] = op_s.get(ev[0], 0.0) + (e - s) * 1e-9
    kernel_evs = sorted((ev[1], ev[2], k, ev[3] if len(ev) > 3 else None)
                        for ev in tr["device_ops"]
                        if (k := kernel_of(ev[0])) is not None)
    starts = [k[0] for k in kernel_evs]

    def kernels_in(s: int, d: int):
        i = bisect.bisect_left(starts, s)
        j = bisect.bisect_left(starts, s + d)
        return kernel_evs[i:j]

    program = {ev[0] for ev in tr["modules"] if kernels_in(ev[1], ev[2])}
    runs, prog_ns = 0, 0
    k_s: Dict[str, float] = {}
    k_n: Dict[str, list] = {}
    for name, s, d in tr["modules"]:
        if name in program and s >= lo and s + d <= hi:
            runs += 1
            prog_ns += d
            for _, kd, k, arrays in kernels_in(s, d):
                k_s[k] = k_s.get(k, 0.0) + kd * 1e-9
                k_n.setdefault(k, []).append(arrays)
    idle: Dict[str, float] = {}
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    spans = tr["host_spans"]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            label = innermost(spans, (s + e) // 2)
            idle[label] = idle.get(label, 0.0) + (e - s) * 1e-9
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9,
                   op_seconds=op_s, kernel_seconds=k_s, kernel_calls=k_n,
                   program_runs=runs, program_seconds=prog_ns * 1e-9,
                   idle_by_span=idle)


def kernel_roofline(summary: Summary, kernel: str, model,
                    peaks: dict) -> Optional[float]:
    """Share (%) of the least time the chip could take for ``kernel``'s
    calls in the served program's whole runs inside the traced window
    (each call counted at its own traced shapes, ``flops.kernel_call``,
    for the cell's model module ``model``), over those calls' measured
    device time."""
    t = summary.kernel_seconds.get(kernel, 0.0)
    calls = summary.kernel_calls.get(kernel, [])
    if t <= 0 or not calls:
        return None
    bound = sum(flops.bound_seconds(*flops.kernel_call(kernel, a, model),
                                    peaks)[0] for a in calls)
    return 100.0 * bound / t


# ---------------------------------------------------------------------------
# taking the trace


class WindowTracer:
    """Host spans around the calls into each layer, the profiler on from
    set-up until the window has drained (so that starting and stopping it,
    which stall the whole process, fall outside the window), the window
    marked by a span of its own, and what the reduction needs from the
    host side: each device call's real targets and their model work, by
    the ``model_flops`` of the cell's model module (none where it has
    none; ``harness.load_cell`` refuses a cell whose metrics need it)."""

    def __init__(self, dep, cell):
        import jax
        self.jax = jax
        self.cell = cell
        self.model = cell.model
        self._count = getattr(cell.model_module(), "model_flops", None)
        self._real: Dict[int, int] = {}
        self._calls: List[Tuple[float, float]] = []   # (t, model flops)
        self._lock = threading.Lock()
        self._span = None
        self._dir = None
        self._on = False
        self._wrap(dep)

    # -- host spans ----------------------------------------------------------
    def _annotated(self, name: str, fn):
        ann = self.jax.profiler.TraceAnnotation

        def call(*a, **k):
            with ann(SPAN_PREFIX + name):
                return fn(*a, **k)
        return call

    def _wrap(self, dep) -> None:
        eng = dep.engine
        for stage in eng.stages:
            stage.run = self._annotated(stage.name, stage.run)
        inner_submit = self._annotated("submit_chunk", eng.submit_chunk)

        def submit_chunk(targets, on_done=None):
            ticket = inner_submit(targets, on_done=on_done)
            with self._lock:
                self._real[id(ticket.item)] = len(targets)
            return ticket
        eng.submit_chunk = submit_chunk
        inner_device = self._annotated("device", eng.scheduler.device_fn)
        model, count = self.model, self._count

        def device(plan):
            with self._lock:
                real = self._real.pop(id(plan.targets), len(plan.targets))
            work = 0.0 if count is None else sum(
                count(model, r.n_vertices, r.n_edges)
                for r in (plan.rows or [])[:real])
            with self._lock:
                self._calls.append((time.perf_counter(), work))
            return inner_device(plan)
        eng.scheduler.device_fn = device

    # -- the profiler --------------------------------------------------------
    def start(self) -> None:
        """Turn the profiler on (part of set-up)."""
        jax = self.jax
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._on = True

    @contextlib.contextmanager
    def window(self):
        """Mark the measured window on the profiler's clock."""
        with self.jax.profiler.TraceAnnotation(WINDOW_SPAN):
            a = time.perf_counter()
            try:
                yield
            finally:
                self._span = (a, time.perf_counter())

    def stop(self) -> None:
        """Turn the profiler off, once the window has drained."""
        if self._on:
            self._on = False
            self.jax.profiler.stop_trace()

    def summary(self) -> Optional[Summary]:
        if self._dir is None or self._span is None:
            return None
        try:
            found = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not found:
                return None
            tr = extract(found[0])
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        if tr["window"] is None:
            return None
        s = reduce(tr)
        a, b = self._span
        with self._lock:
            inside = [w for t, w in self._calls if a <= t <= b]
        s.model_flops = float(sum(inside))
        return s


__all__ = ["extract", "reduce", "union", "Summary", "WindowTracer",
           "kernel_roofline"]
