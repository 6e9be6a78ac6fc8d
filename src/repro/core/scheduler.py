"""Task scheduling on the host/accelerator boundary (paper §4.4, Fig. 7).

The paper overlaps, per PE: (a) CPU-side INI + subgraph build, (b) PCIe
transfer into on-chip buffers (triple-buffered), (c) accelerator compute.
Here (a) runs on host threads ``depth`` batches ahead (the triple buffer),
(b) is ``jax.device_put`` async H2D, and (c) is the jitted engine program —
JAX's async dispatch naturally pipelines (b)/(c) while the host side
pipelines (a).

The host side is either ONE opaque ``host_fn`` (the back-compat one-stage
spelling, run on a ``depth``-worker pool) or a sequence of named STAGES
(``core.batchplan.PlanStage``): each stage gets its own worker station and
batches flow through them in order, so stage i of batch k overlaps stage
i+1 of batch k-1 — a slow Select (PPR miss) on one batch no longer stalls
the Build/Pack of the batches behind it, and every stage's wall time is
visible in ``SchedulerStats.stage_times`` (a software Fig. 3 breakdown).

``PipelineScheduler`` is a *persistent streaming* pipeline: construct it
once per deployment, then ``submit()`` micro-batches as they arrive (a
long-lived server) or ``run()`` a list of them (offline inference). Both
entry points share the same stage workers, dispatcher thread, and
cumulative ``SchedulerStats`` — nothing is rebuilt per call, which is the
paper's "single accelerator configuration, no reconfiguration between
batches" property at the software layer.

``SchedulerStats`` reports the paper's §5.4 quantities: t_initialization
(first-batch host latency, the un-hideable prologue), per-stage sums, and
the achieved overlap fraction.

Every ticket also keeps a hand-off ledger: ``StreamTicket.mark`` stamps
``time.perf_counter()`` at each hand-off (submit, admission, each stage's
start and end, the dispatcher's pick-up, the program's launch, the drain,
``block_until_ready``, the end of the completion callbacks), so the
ledger's intervals and the stage service times add up exactly to the
ticket's time from submit to completion. Completed tickets fold the
ledger into ``SchedulerStats.stage_times`` under namespaced keys
(``queue.*`` for waits, ``device.*`` for the device station's own
intervals) beside the stage service times; ``service_times`` and
``wait_times`` split the two. Each station also runs under a
``jax.profiler.TraceAnnotation`` named ``repro.<station>`` (with the
batch's ``seq`` and the wait before it), so a profile shows the stations
on the device trace's clock.
"""
from __future__ import annotations

import queue
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
from jax.profiler import TraceAnnotation

from repro.core.report_schema import scheduler_summary

# per-batch raw-timing window: the newest RECENT_TIMES host/device times
# are kept verbatim (recent forensics); older ones roll off, so stats
# memory is O(1) in batch count (cumulative totals stay exact)
RECENT_TIMES = 512
# profiler annotations of the stations (``repro.select``, ``repro.device``)
ANNOTATION_PREFIX = "repro."


@dataclass
class SchedulerStats:
    t_wall: float = 0.0
    t_host_total: float = 0.0        # sum of per-batch host prep times
    t_device_total: float = 0.0      # sum of per-batch device times
    t_initialization: float = 0.0    # host prep of the FIRST batch
    n_batches: int = 0
    host_times: "deque" = field(
        default_factory=lambda: deque(maxlen=RECENT_TIMES))
    device_times: "deque" = field(
        default_factory=lambda: deque(maxlen=RECENT_TIMES))
    # per-stage host wall time totals (staged pipelines only; the
    # one-stage host_fn spelling accumulates under "host") — the paper's
    # Fig. 3 breakdown of the host budget — and, under namespaced keys
    # ("queue.<stage>", "device.ready", ...), the completed tickets'
    # hand-off ledgers (see ``service_times`` / ``wait_times``)
    stage_times: Dict[str, float] = field(default_factory=dict)
    # host->device transfer accounting (the paper's t_load, Eq. 2): what
    # actually crossed the link vs. what the dense baseline would ship,
    # plus the store's neighborhood-cache outcome — fed by the host side
    # via ``PipelineScheduler.note_host_metrics``. ``bytes_packed`` is
    # what Pack allocated for the batches' structure arrays and payload.
    bytes_shipped: int = 0
    bytes_dense: int = 0
    bytes_packed: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    # Build-stage subgraph-row cache outcome (staged pipelines only)
    build_hits: int = 0
    build_misses: int = 0
    last_dedup_ratio: Optional[float] = None
    # induced-subgraph density seen by the host pipeline (sum of per-
    # batch mean edges/subgraph; divide by n_density for the mean) —
    # what per-batch adaptive dispatch keys its FLOP fallback on
    batch_edges_total: float = 0.0
    n_density: int = 0
    # sharded feature store only: cumulative host->device bytes PER SHARD
    # (empty for unsharded deployments)
    shard_bytes: List[int] = field(default_factory=list)
    # multi-host transport only (distributed.rpc): per-stage remote call
    # accounting — wall is what the device host observed end-to-end,
    # remote is the graph host's reported handler time, wire is local
    # encode/decode; the gap between them is the link
    rpc_calls: int = 0
    rpc_bytes_out: int = 0
    rpc_bytes_in: int = 0
    rpc_retries: int = 0
    rpc_timeouts: int = 0
    rpc_errors: int = 0
    t_rpc_wall: float = 0.0
    t_rpc_remote: float = 0.0
    t_rpc_wire: float = 0.0

    @property
    def overlap_fraction(self) -> float:
        """How much of the smaller stage was hidden under the larger one.
        1.0 = perfect pipelining, 0.0 = fully serial."""
        lo = min(self.t_host_total, self.t_device_total)
        serial = self.t_host_total + self.t_device_total
        if lo <= 0 or serial <= self.t_wall:
            return 0.0 if serial <= self.t_wall else 1.0
        return min(1.0, (serial - self.t_wall) / lo)

    @property
    def service_times(self) -> Dict[str, float]:
        """Stage service times: the plain stage names of ``stage_times``."""
        return {k: v for k, v in list(self.stage_times.items())
                if "." not in k}

    @property
    def wait_times(self) -> Dict[str, float]:
        """The hand-off ledger: ``queue.admit`` (blocked on the in-flight
        bound), ``queue.<stage>`` (handed to a stage until its station
        starts), ``queue.dispatch`` (last stage done until the dispatcher
        picks the batch up), ``device.launch`` (the device function's
        call), ``queue.drain`` (launched until the drain starts),
        ``device.ready`` (``block_until_ready``), ``device.reply``
        (completion bookkeeping and callbacks), and ``queue.lane`` (per
        answered request, from the server's lane)."""
        return {k: v for k, v in list(self.stage_times.items())
                if "." in k}

    @property
    def cache_hit_rate(self) -> float:
        total = self.cache_hits + self.cache_misses
        return self.cache_hits / total if total else 0.0

    @property
    def build_hit_rate(self) -> float:
        """Subgraph-row cache hit rate (Build stage skipped on a hit)."""
        total = self.build_hits + self.build_misses
        return self.build_hits / total if total else 0.0

    @property
    def batch_edges(self) -> float:
        """Mean measured edges per induced subgraph across all batches
        (0.0 until the first Build stage reports density)."""
        return self.batch_edges_total / self.n_density \
            if self.n_density else 0.0

    @property
    def transfer_ratio(self) -> float:
        """Bytes actually shipped / dense-baseline bytes (< 1 = savings)."""
        return self.bytes_shipped / self.bytes_dense if self.bytes_dense \
            else 1.0

    @property
    def shard_balance(self) -> float:
        """max/mean of per-shard shipped bytes (1.0 = perfectly even;
        1.0 also when the deployment is unsharded)."""
        if not self.shard_bytes:
            return 1.0
        mean = sum(self.shard_bytes) / len(self.shard_bytes)
        return max(self.shard_bytes) / mean if mean > 0 else 1.0

    def summary(self) -> dict:
        """Nested ``latency.* / stages.* / store.* / shards.* / rpc.*``
        summary under the ONE versioned key schema every reporting
        surface shares (core.report_schema, SCHEMA_VERSION)."""
        return scheduler_summary(self)

    def record(self, t_host: float, t_device: float):
        if self.n_batches == 0:
            self.t_initialization = t_host
        self.host_times.append(t_host)
        self.device_times.append(t_device)
        self.t_host_total += t_host
        self.t_device_total += t_device
        self.n_batches += 1

    def merge_stage_times(self, stage_times: Dict[str, float]):
        for k, v in stage_times.items():
            self.stage_times[k] = self.stage_times.get(k, 0.0) + v


class StreamTicket:
    """Handle for one in-flight micro-batch: resolves to the device output.

    ``t_host``/``t_device`` carry the per-stage timings once done
    (``stage_times`` the named host-stage split); ``on_done(ticket)`` (if
    given) fires on the dispatcher thread — keep it light (recording
    latencies, handing results to waiters).

    ``ledger`` holds the intervals between hand-offs (the keys of
    ``SchedulerStats.wait_times``); ``t_call`` is the submit call and
    ``t_done`` the end of the completion callbacks, and ``ledger`` plus
    ``stage_times`` add up to ``t_done - t_call``.
    """

    __slots__ = ("item", "seq", "on_done", "t_call", "t_host", "t_device",
                 "stage_times", "output", "error", "trace", "_event",
                 "_host_future", "t_mark", "t_done", "ledger")

    def __init__(self, item: Any, seq: int,
                 on_done: Optional[Callable] = None,
                 t_call: Optional[float] = None):
        self.item = item
        self.seq = seq
        self.on_done = on_done
        self.t_call = time.perf_counter() if t_call is None else t_call
        self.t_mark = self.t_call        # the newest hand-off mark
        self.t_done = 0.0
        self.ledger: Dict[str, float] = {}
        self.t_host = 0.0
        self.t_device = 0.0
        self.stage_times: Dict[str, float] = {}
        self.output: Any = None
        self.error: Optional[BaseException] = None
        self.trace = None            # obs.TraceContext when sampled
        self._event = threading.Event()
        self._host_future = None

    def mark(self, key: str, book: Optional[Dict[str, float]] = None
             ) -> float:
        """Stamp a hand-off: the time since the previous mark is added
        under ``key`` to ``book`` (default the ledger; a stage's service
        time goes to ``stage_times``). Returns that interval."""
        t = time.perf_counter()
        dt = t - self.t_mark
        book = self.ledger if book is None else book
        book[key] = book.get(key, 0.0) + dt
        self.t_mark = t
        return dt

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError(f"batch {self.seq} not done in {timeout}s")
        if self.error is not None:
            raise self.error
        return self.output


_SHUTDOWN = object()


class PipelineScheduler:
    """Persistent double/triple-buffered host->device streaming pipeline.

    host            -> either ``host_fn(item) -> host batch`` (one-stage
                      back-compat spelling, run on a ``depth``-worker
                      pool) or a sequence of ``PlanStage`` objects, each
                      run on its own worker station so consecutive
                      batches pipeline through the stages
    device_fn(batch)-> device array(s); device work is async-dispatched
    depth           -> how many batches the host runs ahead (2 = double
                      buffering, 3 = the paper's triple buffering); in
                      staged mode the stage stations bound it instead
    max_inflight    -> bound on submitted-but-incomplete batches;
                      ``submit()`` blocks past it (backpressure), default
                      2 * depth.
    on_batch        -> optional ``on_batch(ticket)`` completion hook,
                      fired on the dispatcher thread after stats are
                      recorded (the engine's auto-repin trigger point);
                      exceptions are swallowed.
    tracer          -> optional ``obs.Tracer``; sampled tickets get a
                      TraceContext and every stage/device step runs
                      under a span. None (default) = tracing off —
                      each hot-path site pays one ``is None`` test.
    telemetry       -> optional ``obs.Telemetry`` hub; every completed
                      batch feeds its end-to-end latency + per-stage
                      wall split into the windowed metrics (same
                      zero-cost-when-off contract as tracer).

    Lifecycle: lazily started on first submit/run; ``close()`` drains and
    tears down threads (stage objects themselves are owned — and closed —
    by their engine). ``self.stats`` accumulates over the scheduler's
    whole lifetime; ``run()`` additionally returns call-local stats.
    """

    def __init__(self, host: Union[Callable, Sequence],
                 device_fn: Callable, depth: int = 3,
                 max_inflight: Optional[int] = None,
                 on_batch: Optional[Callable] = None,
                 tracer=None, telemetry=None):
        if callable(host):
            self.host_fn, self.stages = host, None
        else:
            self.host_fn, self.stages = None, list(host)
            if not self.stages:
                raise ValueError("empty stage sequence")
        self.device_fn = device_fn
        self.tracer = tracer
        self.telemetry = telemetry
        self.depth = max(1, depth)
        self.max_inflight = max_inflight or 2 * self.depth
        self.on_batch = on_batch
        self.stats = SchedulerStats()
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._order_q: "queue.Queue" = queue.Queue()
        self._slots = threading.BoundedSemaphore(self.max_inflight)
        self._inflight = 0
        self._active_since: Optional[float] = None
        self._seq = 0
        self._pool: Optional[ThreadPoolExecutor] = None
        self._stage_pools: Optional[List[ThreadPoolExecutor]] = None
        self._dispatcher: Optional[threading.Thread] = None
        self._closed = False

    # -- lifecycle -----------------------------------------------------------
    @property
    def started(self) -> bool:
        return self._dispatcher is not None

    @property
    def stage_names(self) -> List[str]:
        return [st.name for st in self.stages] if self.stages else ["host"]

    def start(self) -> "PipelineScheduler":
        with self._lock:
            if self._closed:
                raise RuntimeError("scheduler is closed")
            if self._dispatcher is not None:
                return self
            if self.stages is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=self.depth, thread_name_prefix="sched-host")
            else:
                # one worker station per stage: batches flow through in
                # submission order, consecutive batches occupy adjacent
                # stages (the paper's Fig. 7 pipelining, host-side)
                self._stage_pools = [
                    ThreadPoolExecutor(
                        max_workers=max(1, getattr(st, "workers", 1)),
                        thread_name_prefix=f"sched-{st.name}")
                    for st in self.stages]
            self._dispatcher = threading.Thread(
                target=self._dispatch_loop, name="sched-dispatch",
                daemon=True)
            self._dispatcher.start()
        return self

    def close(self):
        if self._dispatcher is None or self._closed:
            self._closed = True
            return
        self.flush()
        self._closed = True
        self._order_q.put(_SHUTDOWN)
        self._dispatcher.join(timeout=10)
        if self._pool is not None:
            self._pool.shutdown(wait=True)
        for p in self._stage_pools or ():
            p.shutdown(wait=True)
        # a submit() that raced past the closed-check may have enqueued
        # after _SHUTDOWN; fail its ticket rather than hang its waiter
        while True:
            try:
                t = self._order_q.get_nowait()
            except queue.Empty:
                break
            if t is not _SHUTDOWN:
                t.error = RuntimeError("scheduler closed before dispatch")
                self._complete(t)

    # -- host execution ------------------------------------------------------
    def _traced(self, name: str, ticket: StreamTicket, queued: float,
                fn, *args):
        """Run one station's work for ``ticket``: always under the
        profiler annotation ``repro.<name>`` (about a microsecond while no
        profile is taken), and under a Tracer span when the batch is
        sampled. Both carry the batch's ``seq`` and ``queued_us``, the
        ledger's wait before the station."""
        queued_us = round(queued * 1e6, 1)
        with TraceAnnotation(ANNOTATION_PREFIX + name, seq=ticket.seq,
                             queued_us=queued_us):
            tr = self.tracer
            if tr is None or ticket.trace is None:
                return fn(*args)
            with tr.span(name, ctx=ticket.trace, seq=ticket.seq,
                         queued_us=queued_us):
                return fn(*args)

    def _timed_host(self, ticket: StreamTicket):
        queued = ticket.mark("queue.host")
        hb = self._traced("host", ticket, queued, self.host_fn, ticket.item)
        return hb, ticket.mark("host", ticket.stage_times)

    def _host_serial(self, item, stage_times: Optional[Dict] = None):
        """Run the full host side inline (run()'s no-overlap path)."""
        if self.stages is None:
            t0 = time.perf_counter()
            v = self.host_fn(item)
            if stage_times is not None:
                stage_times["host"] = stage_times.get("host", 0.0) \
                    + time.perf_counter() - t0
            return v
        v = item
        for st in self.stages:
            t0 = time.perf_counter()
            v = st.run(v)
            if stage_times is not None:
                stage_times[st.name] = stage_times.get(st.name, 0.0) \
                    + time.perf_counter() - t0
        return v

    def _stage_step(self, ticket: StreamTicket, i: int, value):
        st = self.stages[i]
        queued = ticket.mark("queue." + st.name)
        try:
            out = self._traced(st.name, ticket, queued, st.run, value)
        except BaseException as e:             # noqa: BLE001
            ticket.mark(st.name, ticket.stage_times)
            ticket._host_future.set_exception(e)
            return
        ticket.mark(st.name, ticket.stage_times)
        if i + 1 < len(self.stages):
            try:
                self._stage_pools[i + 1].submit(self._stage_step, ticket,
                                                i + 1, out)
            except RuntimeError:               # racing close()
                ticket._host_future.set_exception(
                    RuntimeError("scheduler closed mid-pipeline"))
        else:
            ticket._host_future.set_result(
                (out, sum(ticket.stage_times.values())))

    def _submit_host(self, ticket: StreamTicket):
        if self.stages is None:
            ticket._host_future = self._pool.submit(self._timed_host,
                                                    ticket)
        else:
            ticket._host_future = Future()
            self._stage_pools[0].submit(self._stage_step, ticket, 0,
                                        ticket.item)

    # -- streaming interface -------------------------------------------------
    def submit(self, item, on_done: Optional[Callable] = None,
               t_call: Optional[float] = None) -> StreamTicket:
        """Enqueue one micro-batch; blocks when max_inflight is reached.
        ``t_call`` (default: now) is where the ticket's ledger starts."""
        if t_call is None:
            t_call = time.perf_counter()
        self.start()
        self._slots.acquire()
        if self._closed:             # close() ran while we were blocked
            self._slots.release()
            raise RuntimeError("scheduler is closed")
        with self._lock:
            t = StreamTicket(item, self._seq, on_done, t_call=t_call)
            self._seq += 1
            if self._inflight == 0:
                self._active_since = time.perf_counter()
            self._inflight += 1
        t.mark("queue.admit")
        if self.tracer is not None:
            t.trace = self.tracer.maybe_trace(seq=t.seq)
        try:
            self._submit_host(t)
            self._order_q.put(t)
        except RuntimeError as e:    # pool shut down by a racing close()
            with self._idle:
                self._inflight -= 1
                if self._inflight == 0:
                    self._active_since = None
                self._idle.notify_all()
            self._slots.release()
            raise RuntimeError("scheduler is closed") from e
        return t

    def note_host_metrics(self, *, bytes_shipped: int = 0,
                          bytes_dense: int = 0, bytes_packed: int = 0,
                          cache_hits: int = 0,
                          cache_misses: int = 0, build_hits: int = 0,
                          build_misses: int = 0,
                          dedup_ratio: Optional[float] = None,
                          shard_bytes: Optional[Sequence[int]] = None,
                          batch_edges: Optional[float] = None):
        """Accumulate transfer/cache counters for one prepared batch.

        Called by the host side itself (it alone knows what it shipped and
        what the dense baseline would have been); safe from the stage
        worker threads and from run()'s serial path alike. ``shard_bytes``
        (one entry per feature-store shard) accumulates elementwise."""
        with self._lock:
            s = self.stats
            s.bytes_shipped += int(bytes_shipped)
            s.bytes_dense += int(bytes_dense)
            s.bytes_packed += int(bytes_packed)
            s.cache_hits += int(cache_hits)
            s.cache_misses += int(cache_misses)
            s.build_hits += int(build_hits)
            s.build_misses += int(build_misses)
            if dedup_ratio is not None:
                s.last_dedup_ratio = float(dedup_ratio)
            if batch_edges is not None:
                s.batch_edges_total += float(batch_edges)
                s.n_density += 1
            if shard_bytes is not None:
                if len(s.shard_bytes) < len(shard_bytes):
                    s.shard_bytes += [0] * (len(shard_bytes)
                                            - len(s.shard_bytes))
                for i, b in enumerate(shard_bytes):
                    s.shard_bytes[i] += int(b)

    def note_waits(self, *, lane: float = 0.0):
        """Accumulate waits measured outside the scheduler: ``lane`` is
        the summed time a batch's answered requests spent in the server's
        lane before the batch was submitted (``queue.lane``)."""
        with self._lock:
            st = self.stats.stage_times
            st["queue.lane"] = st.get("queue.lane", 0.0) + float(lane)

    def note_rpc_metrics(self, *, calls: int = 0, bytes_out: int = 0,
                         bytes_in: int = 0, retries: int = 0,
                         timeouts: int = 0, errors: int = 0,
                         wall: float = 0.0, remote: float = 0.0,
                         wire: float = 0.0):
        """Accumulate one remote stage call's transport accounting
        (distributed.rpc.RemoteSelectBuildStage) — safe from concurrent
        stage workers, surfaced under ``rpc.*`` in summary()/report()."""
        with self._lock:
            s = self.stats
            s.rpc_calls += int(calls)
            s.rpc_bytes_out += int(bytes_out)
            s.rpc_bytes_in += int(bytes_in)
            s.rpc_retries += int(retries)
            s.rpc_timeouts += int(timeouts)
            s.rpc_errors += int(errors)
            s.t_rpc_wall += float(wall)
            s.t_rpc_remote += float(remote)
            s.t_rpc_wire += float(wire)

    def flush(self, timeout: Optional[float] = None):
        """Block until every submitted batch has completed."""
        with self._idle:
            if not self._idle.wait_for(lambda: self._inflight == 0,
                                       timeout=timeout):
                raise TimeoutError("scheduler flush timed out")

    def _complete(self, ticket: StreamTicket):
        with self._lock:             # same lock as run()'s serial recorder
            self.stats.record(ticket.t_host, ticket.t_device)
            self.stats.merge_stage_times(ticket.stage_times)
        self._traced("reply", ticket, 0.0, self._reply, ticket)
        ticket.mark("device.reply")
        ticket.t_done = ticket.t_mark
        # in-flight accounting last, so flush() implies callbacks finished
        # and the ledger is folded in
        with self._idle:
            if ticket.error is None:
                self.stats.merge_stage_times(ticket.ledger)
            self._inflight -= 1
            if self._inflight == 0 and self._active_since is not None:
                self.stats.t_wall += time.perf_counter() - self._active_since
                self._active_since = None
            self._idle.notify_all()
        self._slots.release()

    def _reply(self, ticket: StreamTicket):
        """Completion bookkeeping and callbacks of one batch."""
        if ticket.trace is not None:
            # close the batch's span tree before waiters wake, so a
            # result() immediately followed by export sees the full tree
            self.tracer.finish_ticket(
                ticket.trace, error=ticket.error is not None,
                t_host=round(ticket.t_host, 6),
                t_device=round(ticket.t_device, 6))
        if self.telemetry is not None:
            self.telemetry.observe_batch(
                time.perf_counter() - ticket.t_call,
                ticket.stage_times, error=ticket.error is not None)
        ticket._event.set()          # resolve BEFORE on_done: callbacks may
        if ticket.on_done is not None:           # call ticket.result()
            try:
                ticket.on_done(ticket)
            except Exception:        # callback errors must not kill pipeline
                pass
        if self.on_batch is not None:
            try:                     # completion hook (e.g. auto-repin) —
                self.on_batch(ticket)            # never kills the pipeline
            except Exception:
                pass

    def _dispatch_loop(self):
        pending: Optional[StreamTicket] = None
        while True:
            try:
                # only poll while a batch is pending drain; otherwise block
                # (an idle pipeline must not busy-wake — engines keep their
                # scheduler for life and many may be idle at once)
                if pending is None:
                    t = self._order_q.get()
                else:
                    t = self._order_q.get(timeout=0.05)
            except queue.Empty:
                self._drain(pending)
                pending = None
                continue
            if t is _SHUTDOWN:
                if pending is not None:
                    self._drain(pending)
                break
            try:
                hb, t.t_host = t._host_future.result()
                queued = t.mark("queue.dispatch")
                # "device" span = dispatch of the jitted program (async);
                # the sync wait shows up as the "drain" span in _drain
                t.output = self._traced("device", t, queued,
                                        self.device_fn, hb)
                t.t_device = t.mark("device.launch")
            except BaseException as e:             # noqa: BLE001
                t.error = e
            if pending is not None:                # drain batch i-1 while
                self._drain(pending)               # batch i computes
                pending = None
            if t.error is not None:
                self._complete(t)
            elif self._order_q.empty():
                # nothing behind us: finish now for lowest tail latency
                self._drain(t)
            else:
                pending = t

    def _drain(self, ticket: StreamTicket):
        queued = ticket.mark("queue.drain")
        try:
            self._traced("drain", ticket, queued, jax.block_until_ready,
                         ticket.output)
        except BaseException as e:                 # noqa: BLE001
            ticket.error = e
        # the batch's device time is its own launch and ready wait, not
        # the drain of the batch before it
        ticket.t_device += ticket.mark("device.ready")
        self._complete(ticket)

    # -- batch interface (offline inference) ---------------------------------
    def run(self, items: Sequence, overlap: bool = True):
        """Run a list of micro-batches; returns (outputs, call stats).

        overlap=False executes fully serially on the caller thread (the
        paper's no-pipelining baseline); both paths accumulate into the
        cumulative ``self.stats``.
        """
        call = SchedulerStats(n_batches=len(items))
        with self._lock:       # store-metric baseline for call-local delta
            base = (self.stats.bytes_shipped, self.stats.bytes_dense,
                    self.stats.cache_hits, self.stats.cache_misses,
                    self.stats.build_hits, self.stats.build_misses,
                    self.stats.batch_edges_total, self.stats.n_density,
                    self.stats.bytes_packed)
        t0 = time.perf_counter()
        if not overlap or self.depth == 1:
            outs = []
            for it in items:
                st_times: Dict[str, float] = {}
                th = time.perf_counter()
                hb = self._host_serial(it, st_times)
                th = time.perf_counter() - th
                td = time.perf_counter()
                out = self.device_fn(hb)
                jax.block_until_ready(out)
                td = time.perf_counter() - td
                call.host_times.append(th)
                call.device_times.append(td)
                call.merge_stage_times(st_times)
                with self._lock:
                    self.stats.record(th, td)
                    self.stats.merge_stage_times(st_times)
                    self.stats.t_wall += th + td
                if self.telemetry is not None:
                    self.telemetry.observe_batch(th + td, st_times)
                if self.on_batch is not None:
                    try:             # completion hook fires on the serial
                        self.on_batch(None)      # path too (no ticket)
                    except Exception:
                        pass
                outs.append(out)
        else:
            tickets = [self.submit(it) for it in items]
            outs = [t.result() for t in tickets]
            call.host_times = [t.t_host for t in tickets]
            call.device_times = [t.t_device for t in tickets]
            for t in tickets:
                call.merge_stage_times(t.stage_times)
        call.t_wall = time.perf_counter() - t0
        call.t_host_total = sum(call.host_times)
        call.t_device_total = sum(call.device_times)
        call.t_initialization = call.host_times[0] if call.host_times \
            else 0.0
        with self._lock:
            # this call's share of the note_host_metrics counters (exact
            # when run() has the scheduler to itself; concurrent submit()
            # traffic from other threads folds into the same window)
            call.bytes_shipped = self.stats.bytes_shipped - base[0]
            call.bytes_dense = self.stats.bytes_dense - base[1]
            call.cache_hits = self.stats.cache_hits - base[2]
            call.cache_misses = self.stats.cache_misses - base[3]
            call.build_hits = self.stats.build_hits - base[4]
            call.build_misses = self.stats.build_misses - base[5]
            call.batch_edges_total = self.stats.batch_edges_total - base[6]
            call.n_density = self.stats.n_density - base[7]
            call.bytes_packed = self.stats.bytes_packed - base[8]
            call.last_dedup_ratio = self.stats.last_dedup_ratio
        return outs, call
