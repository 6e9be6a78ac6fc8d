"""The one versioned key schema behind every reporting surface.

Three surfaces grew three ad-hoc flat dicts — ``SchedulerStats.summary``,
``engine.store_report`` and ``GNNServer.report`` — and the RPC counters
would have made a fourth. This module pins ONE nested namespace that all
of them emit, stamped with ``SCHEMA_VERSION`` so downstream dashboards
can detect drift:

  latency.*   wall-clock: t_wall / t_host / t_device / t_init (paper
              Eq. 2 terms) and, per served model, the request
              percentiles p50/p90/p99/mean/batch_mean/n
  stages.*    host BatchPlan pipeline: per-stage wall totals ("times",
              the software Fig. 3 breakdown), the hand-off ledger's
              waits and device intervals ("waits"), achieved overlap
              fraction, batch count, Build-stage row-cache hit rate
  store.*     transfer + cache accounting (paper t_load / t_pre):
              bytes_shipped / bytes_dense / bytes_packed / transfer_ratio /
              cache_hit_rate / dedup_ratio, plus the engine's store
              subsystem state (policy / features / nbr_cache /
              subgraph_cache / auto_repins)
  shards.*    sharded feature store only: per-shard link bytes +
              max/mean balance
  rpc.*       multi-host transport only: calls / bytes_out / bytes_in /
              retries / timeouts / errors and the wall vs remote vs
              wire time split of the remote stage
  trace.*     observability (ServingConfig(trace=...)): tracing config +
              span/ticket counters, per-span-name latency histograms,
              the flight recorder's slowest-batch summary, per-endpoint
              clock-sync estimates, and the per-op calibration table
  precompute.* offline embedding tier (ServingConfig(precompute=...)):
              residency / freshness / generation, tier hit + demotion +
              promotion counters, refresh backlog and chunk counts, and
              the tier's resident bytes
  telemetry.* live telemetry plane (ServingConfig(telemetry=...)):
              windowed metrics snapshot (counters / gauges / histogram
              quantiles over the sliding window), SLO burn-rate rows,
              watchdog state, and the structured event ring summary
  dispatch.*  per-batch adaptive dispatch (ServingConfig(dispatch=...)):
              policy identity + decision/source counters + warmup
              schedule state, the compiled-variant cache's bounded
              size / hit / eviction counters, the resolved Pallas
              block overrides, and the calibration table's cell count

Section builders take a ``SchedulerStats``-shaped object (duck-typed to
avoid an import cycle with core.scheduler) and return plain dicts;
absent subsystems return None and the section is omitted, never
half-filled.

Version history:
  1  initial five-section namespace (latency/stages/store/shards/rpc)
  2  observability: new optional ``trace`` section (emitted only on
     traced deployments), and ``latency.hist`` — the serialized
     log-bucketed request-latency histogram (obs.hist.LogHistogram
     .to_dict()) whose p50/p90/p99 now come from fixed-memory buckets
     instead of unbounded raw lists. Existing keys are unchanged, so
     v1 consumers keep working; the bump flags the additive keys.
  3  hybrid precompute serving: new optional ``precompute`` section
     (emitted only on deployments with an embedding tier). Existing
     keys unchanged — additive, like the v2 bump.
  4  live telemetry plane: new optional ``telemetry`` section (emitted
     only on deployments with ServingConfig(telemetry=...)) carrying
     the windowed metrics snapshot, SLO burn rates, watchdog summary,
     and event ring. Existing keys unchanged — additive again.
  5  per-batch adaptive dispatch: new optional ``dispatch`` section
     (emitted only on deployments with ServingConfig(dispatch=...)),
     and ``stages.batch_edges`` — the mean measured induced-subgraph
     edge count the Build stage reported (0.0 on pre-dispatch
     deployments and tier-only batches). Additive, like v2-v4.
  6  ``dispatch.exploration_errors``: the count of failed calibration,
     warmup-exploration and block-autotune passes (they used to be
     dropped). Additive.
  7  ``stages.waits``: the scheduler's hand-off ledger (``queue.*`` waits
     and ``device.*`` intervals, ``SchedulerStats.wait_times``); ``times``
     stays service time only. Additive.
  8  ``store.bytes_packed``: the bytes the Pack stage allocated for the
     batches' structure arrays and payload, next to ``bytes_shipped``.
     Additive.
"""
from __future__ import annotations

from typing import Optional

SCHEMA_VERSION = 8

# documented key map (stable contract; bump SCHEMA_VERSION on change)
SCHEMA = {
    "latency": ("t_wall", "t_host", "t_device", "t_init",
                "p50", "p90", "p99", "mean", "batch_mean", "n", "hist"),
    "stages": ("times", "waits", "overlap", "batches", "build_hit_rate",
               "batch_edges"),
    "store": ("bytes_shipped", "bytes_dense", "bytes_packed",
              "transfer_ratio", "cache_hit_rate", "dedup_ratio", "policy",
              "features", "nbr_cache", "subgraph_cache", "auto_repins",
              "graph_hosts"),
    "shards": ("bytes", "balance"),
    "rpc": ("calls", "bytes_out", "bytes_in", "retries", "timeouts",
            "errors", "wall_s", "remote_s", "wire_s"),
    "trace": ("enabled", "sample_every", "ring_capacity", "flight_k",
              "calibrate_every", "tickets_traced", "spans",
              "spans_dropped", "remote_spans", "host", "hists",
              "flight", "clock_sync", "calibration"),
    "precompute": ("enabled", "resident", "fresh", "hits", "misses",
                   "hit_rate", "demotions", "promotions",
                   "refresh_chunks", "refresh_backlog",
                   "refresh_errors", "tier_bytes", "generation",
                   "builds"),
    "telemetry": ("enabled", "host", "window_s", "windows", "series",
                  "counters", "gauges", "hists", "slo", "watchdog",
                  "evaluations", "events"),
    "dispatch": ("enabled", "policy", "impl", "mux_sites", "decisions",
                 "sources", "warmup", "variants", "blocks",
                 "table_cells", "table_passes", "artifact",
                 "exploration_errors"),
}


def stages_section(stats) -> dict:
    return {"times": {k: round(v, 6)
                      for k, v in stats.service_times.items()},
            "waits": {k: round(v, 6)
                      for k, v in stats.wait_times.items()},
            "overlap": round(stats.overlap_fraction, 3),
            "batches": stats.n_batches,
            "build_hit_rate": round(stats.build_hit_rate, 4),
            "batch_edges": round(stats.batch_edges, 2)}


def store_section(stats) -> dict:
    """The scheduler-side transfer counters of ``store.*`` (the engine
    merges its store-subsystem state into the same namespace)."""
    return {"bytes_shipped": stats.bytes_shipped,
            "bytes_dense": stats.bytes_dense,
            "bytes_packed": stats.bytes_packed,
            "transfer_ratio": round(stats.transfer_ratio, 4),
            "cache_hit_rate": round(stats.cache_hit_rate, 4),
            "dedup_ratio": stats.last_dedup_ratio}


def shards_section(stats) -> Optional[dict]:
    if not stats.shard_bytes:
        return None
    return {"bytes": list(stats.shard_bytes),
            "balance": round(stats.shard_balance, 4)}


def rpc_section(stats) -> Optional[dict]:
    if not stats.rpc_calls:
        return None
    return {"calls": stats.rpc_calls,
            "bytes_out": stats.rpc_bytes_out,
            "bytes_in": stats.rpc_bytes_in,
            "retries": stats.rpc_retries,
            "timeouts": stats.rpc_timeouts,
            "errors": stats.rpc_errors,
            "wall_s": round(stats.t_rpc_wall, 6),
            "remote_s": round(stats.t_rpc_remote, 6),
            "wire_s": round(stats.t_rpc_wire, 6)}


def trace_section(tracer, calibration=None) -> Optional[dict]:
    """The ``trace.*`` section of a traced deployment (None when tracing
    is off — the section is omitted, keeping v1 consumers byte-stable)."""
    if tracer is None:
        return None
    d = tracer.report()
    if calibration is not None and len(calibration):
        d["calibration"] = calibration.to_dict()
    return d


def precompute_section(manager) -> dict:
    """The ``precompute.*`` section of a tiered deployment;
    ``{"enabled": False}`` when the deployment has no embedding tier."""
    if manager is None:
        return {"enabled": False}
    return manager.report()


def telemetry_section(telemetry) -> Optional[dict]:
    """The ``telemetry.*`` section of a metered deployment (None when
    telemetry is off — the section is omitted, like ``trace``)."""
    if telemetry is None:
        return None
    return telemetry.report()


def dispatch_section(engine) -> Optional[dict]:
    """The ``dispatch.*`` section of an adaptively-dispatched deployment
    (None when ServingConfig(dispatch=...) is unset — omitted, like
    ``trace``). Duck-typed on the engine's ``dispatch_report``."""
    rep = getattr(engine, "dispatch_report", None)
    if rep is None:
        return None
    return rep()


def scheduler_summary(stats) -> dict:
    """The full nested summary a ``SchedulerStats`` emits."""
    d = {"schema_version": SCHEMA_VERSION,
         "latency": {"t_wall": stats.t_wall,
                     "t_host": stats.t_host_total,
                     "t_device": stats.t_device_total,
                     "t_init": stats.t_initialization},
         "stages": stages_section(stats),
         "store": store_section(stats)}
    shards = shards_section(stats)
    if shards is not None:
        d["shards"] = shards
    rpc = rpc_section(stats)
    if rpc is not None:
        d["rpc"] = rpc
    return d


__all__ = ["SCHEMA_VERSION", "SCHEMA", "scheduler_summary",
           "stages_section", "store_section", "shards_section",
           "rpc_section", "trace_section", "precompute_section",
           "telemetry_section", "dispatch_section"]
