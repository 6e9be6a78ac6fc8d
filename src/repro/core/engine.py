"""Decoupled mini-batch GNN inference engine (paper Algorithm 2 + 3).

Host side: a staged **BatchPlan pipeline** (core.batchplan) — Select (PPR
neighborhoods via the nbr cache), Build (induced-subgraph rows via the
subgraph-row cache), Pack (store payload + transfer accounting) — each a
named stage the scheduler pipelines across consecutive batches. Device
side: one jitted AckProgram per (model, N, C) — the model's registered
lowering (core.program) executed through the ACK kernels with a PER-OP
dense/scatter-gather mux (XLA or Pallas implementation) and the Readout.
The fixed shapes are the decoupling dividend: ONE compiled program serves
every batch — the paper's "single accelerator, no reconfiguration"
property.

``DecoupledEngine.infer`` overlaps host preparation of batch i+1 with
device execution of batch i via core.scheduler (paper Fig. 7). The engine
owns ONE persistent ``PipelineScheduler`` for its whole lifetime — batch
and streaming calls share its stage workers, dispatcher, and cumulative
stats, so serving never pays per-call pipeline construction.
"""
from __future__ import annotations

import functools
import threading
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, replace
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batchplan import (BatchPlan, BuildStage, PackStage,
                                  SelectStage)
from repro.core.config import ServingConfig
from repro.core.program import (ProgramDecision, execute,
                                input_width_params, lower,
                                required_adjacency, specialize)
from repro.core.scheduler import (PipelineScheduler, SchedulerStats,
                                  StreamTicket)
from repro.core.subgraph import SubgraphBatch, default_edge_pad
from repro.gnn.model import GNNConfig, init_gnn
from repro.graphs.csr import CSRGraph
from repro.store import NeighborhoodCache, StorePolicy, build_feature_source
from repro.store.feature_store import pad_feature_dim
from repro.store.nbr_cache import SubgraphRowCache


# the sg-mode structure arrays: the edge list and its carried extras
EDGE_KEYS = ("edge_src", "edge_dst", "edge_w", "self_w", "edge_w_mean")


def _pad128(f: int) -> int:
    return f + (-f) % 128


def _with_edge_extras(sb: SubgraphBatch) -> SubgraphBatch:
    """An externally constructed batch without the Build stage's carried
    ``self_w``/``edge_w_mean``: recover them from the dense adjacency."""
    n = sb.n
    self_w = sb.adj[:, np.arange(n), np.arange(n)]
    indeg = np.einsum("cij->ci", (sb.adj_mean > 0).astype(np.float32))
    dst_deg = np.take_along_axis(np.maximum(indeg, 1.0),
                                 sb.edge_dst.astype(np.int64), axis=1)
    ew_mean = np.where(sb.edge_w != 0, 1.0 / dst_deg, 0.0)
    return replace(sb, self_w=self_w.astype(np.float32),
                   edge_w_mean=ew_mean.astype(np.float32))


@dataclass
class InferenceResult:
    embeddings: np.ndarray           # [num_targets, f]
    stats: Optional[SchedulerStats]
    decision: ProgramDecision        # per-op mode decisions + summary


class DecoupledEngine:
    """One engine instance = one (graph, model, batch-size) deployment."""

    def __init__(self, graph: CSRGraph, cfg: GNNConfig, params=None,
                 config: Optional[ServingConfig] = None, **legacy):
        """``config=ServingConfig(...)`` is the constructor surface; the
        legacy per-kwarg spellings (batch_size=, impl=, store=, ...) are
        routed through ``ServingConfig.from_kwargs`` and deprecated."""
        if legacy:
            config = ServingConfig.from_kwargs(base=config, **legacy)
        elif config is None:
            config = ServingConfig()
        self.config = config
        self.graph, self.cfg = graph, cfg
        # observability (off by default, zero-cost when off: every site
        # downstream guards on ``tracer is None``)
        if config.trace is not None:
            from repro.obs.calib import CalibrationTable
            from repro.obs.trace import Tracer
            self.tracer = Tracer(config.trace)
            self._calib = CalibrationTable()
        else:
            self.tracer = None
            self._calib = None
        self._calib_count = 0
        # failed calibration / exploration passes: serving continues on
        # the jitted program, but every failure is counted (dispatch
        # report + repro_exploration_errors_total) and warned, never
        # swallowed
        self.exploration_errors = 0
        # live telemetry plane (same contract: off by default, every
        # hot-path site guards on ``telemetry is None``)
        if config.telemetry is not None:
            from repro.obs.metrics import Telemetry
            self.telemetry = Telemetry(config.telemetry, host="client")
        else:
            self.telemetry = None
        self.batch_size = config.batch_size
        self.num_threads = config.num_threads
        self.impl = config.impl
        mode = config.mode
        store = config.store
        self.store_policy = store
        self.dedup_features = store.features == "packed"
        self.last_dedup_ratio = None
        n = cfg.receptive_field
        self.e_pad = config.e_pad or default_edge_pad(graph, n)
        avg_edges = min(self.e_pad, n * float(graph.degrees.mean()))
        # graph-global degree estimate, re-seeded by the FIRST measured
        # batch density from the Build stage (run_device) — the measured
        # number is what per-batch dispatch and reports key on
        self.avg_edges_prior = avg_edges
        self._density_seeded = False
        # compile the model through the lowering registry, then set each
        # op's mode mux from ITS kernel's FLOP model (mode="auto") or the
        # caller's force — a single program may mix sg aggregation with
        # dense (systolic) transforms
        self.program, self.decision = specialize(
            lower(cfg), n=n, avg_edges=avg_edges, f_in=cfg.f_in,
            f_hidden=cfg.f_hidden,
            force=None if mode == "auto" else mode)
        self.mode = self.decision.mode
        self.needs_edges = any(d.mode == "sg" for d in self.decision)
        # ship only the adjacency arrays the specialized program reads
        # (an all-sg aggregation path ships none — just the edge list)
        self.adj_keys = required_adjacency(self.program)
        # per-batch adaptive dispatch (core.dispatch): only meaningful
        # with mode="auto" — a forced mode pins the mux, so the policy
        # never runs there (counters still label those batches "forced")
        dconf = config.dispatch
        self.dispatch = None
        self._variants = None
        self._disp_counters: Dict = {}
        self._forced_dispatch = 0
        self._last_blocks: Dict[str, int] = {}
        self._static_assignment = {d.site: d.mode
                                   for d in self.decision if d.mux}
        if dconf is not None and mode == "auto":
            from repro.core.dispatch import DispatchPolicy, VariantCache
            from repro.obs.calib import CalibrationTable
            table = self._calib if self._calib is not None \
                else CalibrationTable()
            if dconf.artifact is not None:
                from repro.ckpt.checkpoint import committed_steps
                from repro.obs.calib import load_calibration
                if committed_steps(dconf.artifact):
                    # a committed table dispatches MEASURED from the
                    # first batch (warmup is skipped — its cells are
                    # already populated); stale stamps raise here
                    table = load_calibration(dconf.artifact, graph=graph,
                                             cfg=cfg, impl=self.impl)
            self._calib = table
            self.dispatch = DispatchPolicy(
                self.program, self.impl, table, n=n, f_in=cfg.f_in,
                f_hidden=cfg.f_hidden,
                warmup_passes=dconf.warmup_passes, seed=dconf.seed,
                autotune_blocks=dconf.autotune_blocks)
            self._variants = VariantCache(dconf.variant_capacity)
            # adaptive payload union: ANY per-batch mode vector must
            # find its arrays in the device batch, so ship the
            # conservative (unspecialized) adjacency set + the edge
            # list. Extra unused keys do not change jit outputs.
            self.adj_keys = required_adjacency(lower(cfg))
            self.needs_edges = True
        if params is None:
            params = init_gnn(cfg, jax.random.PRNGKey(config.seed))
        self.params = params
        self.f_pad = _pad128(cfg.f_in) if self.impl == "pallas" \
            else cfg.f_in
        if self.f_pad != cfg.f_in:
            # MXU alignment: zero-pad layer0 input-rows to match the padded
            # feature columns (padded features are zero, so this is exact).
            # WHICH weights are f_in-sized is read off the lowered program
            # (registry contract: custom kinds need no engine edits)
            pad = self.f_pad - cfg.f_in
            l0 = dict(params["layer0"])
            for k in input_width_params(self.program):
                l0[k] = jnp.pad(l0[k], ((0, pad), (0, 0)))
            self.params = dict(params, layer0=l0)
        self._infer = jax.jit(functools.partial(self._forward))
        self._fsource = build_feature_source(graph, store, self.f_pad)
        if config.remote:
            # multi-host deployment: Select/Build run on graph hosts
            # behind the transport (distributed.rpc); the nbr/row caches
            # live WITH the graph over there, Pack + device execution
            # stay here where the feature store and compiled program are
            from repro.distributed.rpc import (RemoteSelectBuildStage,
                                               build_host_pool)
            self.nbr_cache = None
            self.sg_cache = None
            self._host_pool = build_host_pool(config, graph=graph)
            self.stages = [RemoteSelectBuildStage(
                self, self._host_pool,
                workers=config.rpc_concurrency), PackStage(self)]
            if self.tracer is not None:
                # ping-based clock-offset estimate per graph host, so
                # their spans stitch onto this process's timeline
                from repro.distributed.rpc import estimate_clock_offsets
                self.tracer.clock_sync = estimate_clock_offsets(
                    self._host_pool)
        else:
            self._host_pool = None
            self.nbr_cache = self._build_nbr_cache(store)
            # Build-stage subgraph-row cache ("auto": rows are cached
            # whenever neighborhoods are — hot traffic that re-selects
            # also re-builds). Unlike node lists, one entry is ~2N^2
            # floats + the edge arrays, so the default capacity is
            # BYTE-bounded (subgraph_budget_bytes), not inherited from
            # nbr_capacity alone.
            if store.cache_subgraph_rows:
                cap = store.subgraph_capacity
                if cap is None:
                    entry = 2 * n * n * 4 + 2 * n * 4 + 4 * self.e_pad * 4
                    cap = max(1, min(store.nbr_capacity,
                                     store.subgraph_budget_bytes // entry))
                self.sg_cache = SubgraphRowCache(cap)
            else:
                self.sg_cache = None
            # the host side as an explicit staged pipeline (Select ->
            # Build -> Pack, see core.batchplan); prepare() runs the same
            # stages serially, so the staged path is the monolithic one
            # by construction
            self.stages = [SelectStage(self), BuildStage(self),
                           PackStage(self)]
        # offline precompute tier (hybrid serving): build or load the
        # layer-major embedding table and prepend the TierStage router —
        # tier-fresh targets skip Select/Build/Pack entirely, stale/cold
        # targets ride the online pipeline above. Note ``params`` (the
        # local) is the UNPADDED parameter tree — offline propagation
        # runs on unpadded features
        pconf = config.precompute
        if pconf is not None and (pconf.models is None
                                  or cfg.kind in pconf.models):
            from repro.precompute.manager import (PrecomputeManager,
                                                  TierStage)
            self.precompute = PrecomputeManager(self, pconf, params)
            self.stages = [TierStage(self)] + self.stages
        else:
            self.precompute = None
        # auto-repin trigger state (StorePolicy.repin_every / _hit_floor)
        self._repin_auto = bool(store.repin_every or store.repin_hit_floor)
        self._repin_lock = threading.Lock()
        self._repin_batches = 0
        self._repin_base = (0, 0)       # (lookups, resident) at last repin
        # floor-trigger backoff: when the hit rate stays below the floor
        # even after a repin (working set > budget), checks space out
        # exponentially instead of rebuilding the table every batch
        self._floor_batches = 0
        self._floor_wait = 1
        # repins execute on their own single worker — NEVER on the
        # scheduler's dispatcher thread, where a table rebuild would
        # stall completion of every in-flight batch
        self._repin_pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="repin") \
            if self._repin_auto else None
        self.auto_repins = 0
        # one pipeline per deployment (paper: one accelerator config, no
        # per-batch reconfiguration); lazily started on first use
        self.scheduler = PipelineScheduler(
            self.stages, self.run_device, depth=config.depth,
            max_inflight=config.max_inflight,
            on_batch=self._on_batch_done if self._repin_auto else None,
            tracer=self.tracer, telemetry=self.telemetry)
        if self.telemetry is not None:
            self._register_metrics()
        # graph-update streaming: CSRGraph.apply_edge_updates notifies us
        # so cached neighborhoods / resident rows never serve stale state
        if hasattr(graph, "register_listener"):
            graph.register_listener(self.invalidate)

    def _build_nbr_cache(self, policy: StorePolicy
                         ) -> Optional[NeighborhoodCache]:
        if policy.nbr_cache == "none":
            return None
        pinned = None
        if policy.nbr_cache == "pinned":
            pinned = policy.pinned_targets
            if pinned is None:
                # default hot set: top-degree targets (hub-heavy traffic
                # hits them most under Zipf skew)
                k = min(self.graph.num_vertices,
                        policy.pinned_count or
                        max(1, policy.nbr_capacity // 4))
                pinned = np.argpartition(self.graph.degrees, -k)[-k:]
        return NeighborhoodCache(policy.nbr_capacity, pinned_targets=pinned)

    def _register_metrics(self):
        """Join the existing subsystem counters to the telemetry plane
        as collect-time callbacks: the hot path increments nothing
        twice — the registry samples each source at scrape/report time,
        so metered serving stays bitwise-identical to unmetered."""
        reg = self.telemetry.registry
        stats = self.scheduler.stats
        src = self._fsource
        if self.nbr_cache is not None:
            c = self.nbr_cache
            reg.counter_fn("repro_nbr_cache_hits_total",
                           lambda: c.hits, help="neighborhood cache hits")
            reg.counter_fn("repro_nbr_cache_misses_total",
                           lambda: c.misses,
                           help="neighborhood cache misses")
            reg.counter_fn("repro_nbr_cache_evictions_total",
                           lambda: c.evictions,
                           help="neighborhood cache evictions")
        if self.sg_cache is not None:
            rc = self.sg_cache
            reg.counter_fn("repro_row_cache_hits_total",
                           lambda: rc.hits,
                           help="subgraph-row cache hits")
            reg.counter_fn("repro_row_cache_misses_total",
                           lambda: rc.misses,
                           help="subgraph-row cache misses")
        if hasattr(src, "lookups"):
            reg.counter_fn("repro_store_lookups_total",
                           lambda: src.lookups,
                           help="feature rows resolved")
            reg.counter_fn("repro_store_resident_lookups_total",
                           lambda: src.resident_lookups,
                           help="feature rows served device-resident")
        reg.counter_fn("repro_exploration_errors_total",
                       lambda: self.exploration_errors,
                       help="failed calibration/exploration/autotune "
                            "passes")
        reg.counter_fn("repro_store_bytes_shipped_total",
                       lambda: stats.bytes_shipped,
                       help="host->device bytes actually shipped")
        reg.counter_fn("repro_store_bytes_dense_total",
                       lambda: stats.bytes_dense,
                       help="dense-baseline host->device bytes")
        if self._repin_auto:
            reg.counter_fn("repro_auto_repins_total",
                           lambda: self.auto_repins,
                           help="automatic residency rebalances")
        if self.dispatch is not None:
            pol, vc = self.dispatch, self._variants
            reg.counter_fn("repro_dispatch_decisions_total",
                           lambda: pol.decisions,
                           help="per-batch dispatch decisions taken")
            reg.counter_fn("repro_variant_cache_hits_total",
                           lambda: vc.hits,
                           help="compiled-variant cache hits")
            reg.counter_fn("repro_variant_cache_misses_total",
                           lambda: vc.misses,
                           help="compiled-variant cache misses (builds)")
            reg.counter_fn("repro_variant_cache_evictions_total",
                           lambda: vc.evictions,
                           help="compiled variants evicted (LRU bound)")
            reg.gauge_fn("repro_variant_cache_size", lambda: len(vc),
                         help="live compiled variants (<= capacity)")
        if self.precompute is not None:
            tier, mgr = self.precompute.tier, self.precompute
            reg.counter_fn("repro_tier_hits_total", lambda: tier.hits,
                           help="embedding-tier fresh hits")
            reg.counter_fn("repro_tier_misses_total",
                           lambda: tier.misses,
                           help="embedding-tier misses (online path)")
            reg.counter_fn("repro_tier_demotions_total",
                           lambda: tier.demotions,
                           help="tier rows demoted by invalidation")
            reg.counter_fn("repro_tier_promotions_total",
                           lambda: tier.promotions,
                           help="tier rows re-promoted by refresh")
            reg.counter_fn("repro_refresh_chunks_total",
                           lambda: mgr.refresh_chunks,
                           help="background refresh chunks completed")
            reg.counter_fn("repro_refresh_errors_total",
                           lambda: mgr.refresh_errors,
                           help="background refresh chunk failures")
            reg.gauge_fn("repro_refresh_backlog",
                         lambda: len(mgr._backlog),
                         help="vertices awaiting tier refresh")
        if self._host_pool is not None:
            reg.counter_fn("repro_rpc_calls_total",
                           lambda: stats.rpc_calls,
                           help="remote stage calls")
            reg.counter_fn("repro_rpc_retries_total",
                           lambda: stats.rpc_retries,
                           help="remote stage call retries")
            reg.counter_fn("repro_rpc_timeouts_total",
                           lambda: stats.rpc_timeouts,
                           help="remote stage call timeouts")
            reg.counter_fn("repro_rpc_errors_total",
                           lambda: stats.rpc_errors,
                           help="remote stage call errors")
            reg.counter_fn("repro_rpc_bytes_out_total",
                           lambda: stats.rpc_bytes_out,
                           help="bytes sent to graph hosts")
            reg.counter_fn("repro_rpc_bytes_in_total",
                           lambda: stats.rpc_bytes_in,
                           help="bytes received from graph hosts")
            quarantines = self.telemetry.counter(
                "repro_host_quarantines_total",
                help="graph-host quarantine episodes")
            events = self.telemetry.events

            def _on_quarantine(endpoint: str):
                quarantines.inc()
                events.emit("host_quarantine", severity="warn",
                            message=f"graph host {endpoint} quarantined",
                            endpoint=endpoint)

            self._host_pool.on_quarantine = _on_quarantine

    # -- device program ----------------------------------------------------
    def _forward(self, params, batch: Dict[str, jax.Array]):
        emb, _ = execute(self.program, params, batch, impl=self.impl)
        return emb

    # -- host side ----------------------------------------------------------
    def _pad_feature_dim(self, feats):
        """Engine-facing entry to the single padding implementation
        (store.feature_store.pad_feature_dim) bound to this engine's
        f_pad — prepare/device_batch/run_device all route through it."""
        return pad_feature_dim(feats, self.f_pad)

    def _node_lists(self, targets):
        """PPR neighborhoods for a batch, via the neighborhood cache when
        the policy has one — the Select stage's back-compat spelling.
        Returns (node_lists, hits, misses) counted over the batch's
        UNIQUE targets."""
        plan = self.stages[0].run(BatchPlan(targets=np.asarray(targets)))
        return plan.node_lists, plan.nbr_hits, plan.nbr_misses

    def plan(self, targets) -> BatchPlan:
        """Run the host pipeline's stages back-to-back on the caller
        thread and return the full BatchPlan artifact (the staged
        decomposition of the old monolithic prepare()).

        Note: for resident/sharded stores the packed payload PINS the
        store's current residency generation until it is consumed by
        ``run_device`` (that is what keeps in-flight batches coherent
        across ``repin()``) — feed ``plan.device`` to ``run_device`` or
        avoid repinning while holding abandoned plans."""
        plan = BatchPlan(targets=np.asarray(targets))
        for stage in self.stages:
            plan = stage.run(plan)
        return plan

    def prepare(self, targets) -> Dict[str, np.ndarray]:
        """Monolithic host prep (all stages serially): the one-call
        spelling of the staged pipeline, bitwise-identical to it."""
        return self.plan(targets).device

    @property
    def structure_keys(self) -> Tuple[str, ...]:
        """The structure arrays the compiled program reads: the keys
        ``device_batch`` returns besides ``feats``, and the only
        ``SubgraphRows`` fields the Pack stage stacks (``adj_keys`` and
        ``needs_edges`` already cover every mode vector a dispatching
        engine may pick)."""
        return ("mask",) + tuple(self.adj_keys) + \
            (EDGE_KEYS if self.needs_edges else ())

    def device_batch(self, sb: SubgraphBatch,
                     include_feats: bool = True) -> Dict[str, np.ndarray]:
        if self.needs_edges and (sb.self_w is None
                                 or sb.edge_w_mean is None):
            sb = _with_edge_extras(sb)
        d = {k: getattr(sb, k) for k in self.structure_keys}
        if include_feats:
            d["feats"] = self._pad_feature_dim(sb.feats)
        return d

    def run_device(self, device_batch) -> jax.Array:
        plan = device_batch if isinstance(device_batch, BatchPlan) \
            else None                             # staged pipeline output
        if plan is not None:
            if plan.tier_done:
                # all-fresh fast path: the tier row gather IS the
                # answer — no device program runs for this batch
                return plan.tier_rows
            device_batch = plan.device
        db = dict(device_batch)
        src = self._fsource
        tr = self.tracer
        if all(k in db for k in src.payload_keys):
            payload = {k: db.pop(k) for k in src.payload_keys}
            if tr is None:
                feats = src.device_feats(payload)
            else:
                # child of the scheduler's "device" span (thread-local
                # parent); no-ops when this batch is untraced. It times
                # the host's asynchronous dispatch of the gather, not
                # the gather on the device
                with tr.span("store.gather", cat="store",
                             store=src.name):
                    feats = src.device_feats(payload)
        else:       # externally built dense batch (e.g. device_batch())
            feats = db["feats"]
        db["feats"] = self._pad_feature_dim(feats)
        if tr is not None and tr.config.calibrate_every \
                and tr.current() is not None:
            # sampled instrumented eager per-op pass (obs.calib): its
            # outputs are DISCARDED — the jitted result below is what
            # gets served, so outputs stay bitwise-identical
            self._calib_count += 1
            if self._calib_count % tr.config.calibrate_every == 0:
                from repro.obs.calib import run_instrumented
                try:
                    with tr.span("calibrate", cat="calib"):
                        run_instrumented(self.program, self.params, db,
                                         self.impl, self._calib)
                except Exception as e:
                    self._exploration_failed("calibration", e)
        if plan is not None and not self._density_seeded \
                and plan.n_edges is not None:
            # first measured batch density replaces the degree-based
            # construction-time estimate as the engine's prior
            self._density_seeded = True
            self.avg_edges_prior = min(float(plan.n_edges),
                                       float(self.e_pad))
        if self.dispatch is not None and plan is not None \
                and plan.n_edges is not None:
            out = self._dispatch_infer(plan, db)
        else:
            if self.config.dispatch is not None \
                    and self.dispatch is None:
                # forced mode with dispatch telemetry requested: the
                # policy never runs, but the mode counters still tell
                # the operator WHAT served and WHY ("forced")
                self._forced_dispatch += 1
                self._count_dispatch(self._static_assignment,
                                     {s: "forced"
                                      for s in self._static_assignment})
            out = self._infer(self.params, db)
        if plan is not None and plan.online_index is not None:
            # mixed batch: the online program ran on the stale targets
            # only (padded) — rejoin with the tier rows on the original
            # slot order. Stays a lazy jax expression: dispatch remains
            # async, the scheduler's device station is not stalled.
            out = jnp.where(jnp.asarray(plan.tier_fresh)[:, None],
                            jnp.asarray(plan.tier_rows),
                            out[jnp.asarray(plan.online_index)])
        return out

    # -- per-batch adaptive dispatch ----------------------------------------
    def _exploration_failed(self, what: str, err: Exception) -> None:
        """A discarded-output pass (calibration, warmup exploration,
        block autotune) raised. Serving is unaffected — the jitted
        program still answers the batch — but the failure is counted and
        warned: a tuner that fails in silence never tunes."""
        # run_device is the only caller: one device thread per engine
        self.exploration_errors += 1
        warnings.warn(f"{what} pass failed on {self.cfg.display}: "
                      f"{type(err).__name__}: {err}", RuntimeWarning,
                      stacklevel=2)

    def _count_dispatch(self, assignment: Dict[str, str],
                        sources: Dict[str, str]) -> None:
        """Per-mux-op dispatch counters:
        ``repro_dispatch_total{op,mode,source}``. Counter handles are
        cached per label set so the hot path pays one dict probe."""
        if self.telemetry is None:
            return
        for site, m in assignment.items():
            key = (site, m, sources[site])
            c = self._disp_counters.get(key)
            if c is None:
                c = self._disp_counters[key] = self.telemetry.counter(
                    "repro_dispatch_total",
                    help="mux-op dispatch outcomes per batch",
                    op=site, mode=m, source=sources[site])
            c.inc()

    def _build_variant(self, assignment, blocks):
        """Jit one compiled variant: the engine's program re-specialized
        to this mode vector (+ Pallas block overrides). The op stream
        never changes — only the per-site dense/sg mux — so every
        variant serves from the same fixed shapes."""
        from repro.core.program import respecialize
        prog = respecialize(self.program, dict(assignment))
        blk = dict(blocks) or None

        def fwd(params, batch):
            emb, _ = execute(prog, params, batch, impl=self.impl,
                             blocks=blk)
            return emb

        return jax.jit(fwd)

    def _dispatch_infer(self, plan: BatchPlan, db) -> jax.Array:
        """The adaptive device step: consult the policy with THIS
        batch's measured density, run the warmup/autotune exploration
        pass when scheduled (outputs discarded), then serve through the
        bounded variant cache."""
        from repro.core.dispatch import variant_key
        from repro.core.program import respecialize
        from repro.obs.calib import (run_block_autotune, run_instrumented,
                                     size_bucket)
        pol = self.dispatch
        bucket = size_bucket(db)
        avg_e = min(float(plan.n_edges), float(self.e_pad))
        dec = pol.decide(avg_e, bucket)
        if dec.blocks:
            self._last_blocks = dict(dec.blocks)
        if dec.warm_mode is not None:
            # instrumented exploration pass in the scheduled forced mode
            # — its outputs are DISCARDED (serving stays on
            # dec.assignment below), so warmup batches remain bitwise-
            # identical to an engine with dispatch off
            try:
                warm = {s: dec.warm_mode for s in pol.sites}
                run_instrumented(respecialize(self.program, warm),
                                 self.params, db, self.impl, pol.table)
                if pol.autotune_blocks and self.impl == "pallas":
                    run_block_autotune(self.program, self.params, db,
                                       pol.table)
            except Exception as e:
                self._exploration_failed("exploration", e)
        self._count_dispatch(dec.assignment, dec.site_sources)
        tr = self.tracer
        if tr is not None and tr.current() is not None:
            tr.annotate(dispatch_source=dec.source,
                        dispatch_bucket=dec.bucket,
                        dispatch_modes=",".join(
                            f"{s}={m}" for s, m
                            in sorted(dec.assignment.items())),
                        batch_avg_edges=round(dec.avg_edges, 1))
        fn = self._variants.get(
            variant_key(dec.assignment, dec.blocks),
            lambda: self._build_variant(dec.assignment, dec.blocks))
        return fn(self.params, db)

    def programs(self) -> Dict[str, object]:
        """The jitted device programs this deployment serves from: the
        static program, or with dispatch on, every live variant (keyed
        by its mode vector and block overrides). Each takes
        ``(self.params, device_batch)``."""
        if self._variants is None:
            return {"static": self._infer}
        return {repr(k): fn for k, fn in self._variants.items()}

    def dispatch_report(self) -> Optional[dict]:
        """Adaptive-dispatch state (the ``dispatch.*`` schema section):
        decision/source counters, warmup schedule, variant-cache bounds
        and hit/evict counters, resolved block overrides. None when the
        deployment was built without ``ServingConfig(dispatch=...)`` —
        the section is omitted, like ``trace``."""
        dconf = self.config.dispatch
        if dconf is None:
            return None
        if self.dispatch is None:    # forced mode: policy inert
            return {"enabled": True, "policy": "forced",
                    "impl": self.impl,
                    "mux_sites": sorted(self._static_assignment),
                    "decisions": self._forced_dispatch,
                    "sources": {"forced": self._forced_dispatch},
                    "artifact": dconf.artifact,
                    "exploration_errors": self.exploration_errors}
        d = self.dispatch.report()
        d.update(enabled=True, variants=self._variants.stats(),
                 blocks=dict(self._last_blocks),
                 artifact=dconf.artifact,
                 exploration_errors=self.exploration_errors)
        return d

    def save_calibration(self, path: Optional[str] = None) -> str:
        """Persist the live calibration table (per-op p50 cells + block
        autotune cells) as a committed artifact at ``path`` (default:
        ``DispatchConfig.artifact``); a later engine with the same
        graph/model/impl loads it and dispatches measured from the
        first batch."""
        from repro.obs.calib import save_calibration
        dconf = self.config.dispatch
        path = path or (dconf.artifact if dconf is not None else None)
        if path is None:
            raise ValueError(
                "no artifact path: pass save_calibration(path=...) or "
                "set DispatchConfig(artifact=...)")
        if self._calib is None:
            raise ValueError(
                "no calibration table on this engine; enable "
                "ServingConfig(dispatch=...) or trace calibration")
        return save_calibration(path, self._calib, graph=self.graph,
                                cfg=self.cfg, impl=self.impl)

    # -- end-to-end ----------------------------------------------------------
    def pad_targets(self, targets: np.ndarray) -> np.ndarray:
        """Pad a tail chunk to the engine's fixed batch size C by repeating
        the last target (fixed shapes keep the one compiled program)."""
        C = self.batch_size
        targets = np.asarray(targets)
        if len(targets) == C:
            return targets
        if len(targets) > C or len(targets) == 0:
            raise ValueError(f"chunk size {len(targets)} vs C={C}")
        return np.concatenate(
            [targets, np.repeat(targets[-1:], C - len(targets))])

    def submit_chunk(self, targets, on_done=None) -> StreamTicket:
        """Streaming entry: enqueue ONE micro-batch (≤ C targets, tail is
        padded) on the persistent pipeline; returns a StreamTicket whose
        result is the [C, f] embedding block. The ticket's hand-off
        ledger starts at this call."""
        t_call = time.perf_counter()
        return self.scheduler.submit(self.pad_targets(np.asarray(targets)),
                                     on_done=on_done, t_call=t_call)

    def infer(self, targets, overlap: bool = True) -> InferenceResult:
        """Mini-batch inference for arbitrary #targets (chunks of C)."""
        targets = np.asarray(targets)
        C = self.batch_size
        chunks = [self.pad_targets(targets[i:i + C])
                  for i in range(0, len(targets), C)]
        outs, stats = self.scheduler.run(chunks, overlap=overlap)
        emb = np.concatenate([np.asarray(o) for o in outs], axis=0)
        return InferenceResult(embeddings=emb[:len(targets)], stats=stats,
                               decision=self.decision)

    # -- store hooks ---------------------------------------------------------
    def invalidate(self, vertices) -> int:
        """Graph-update hook, every cache level: drop every cached
        neighborhood AND every cached subgraph row whose push FRONTIER
        contains any of ``vertices`` (exact — the miss path caches each
        push's full touched set, see FrontierCache.invalidate), and
        re-upload those vertices' device-resident feature rows from
        ``graph.features`` (so feature mutations take effect without an
        engine rebuild). Returns the number of NEIGHBORHOOD entries
        dropped (row-cache drops are visible in store_report())."""
        if hasattr(self._fsource, "refresh_features"):
            self._fsource.refresh_features(vertices)
        if self.precompute is not None:
            # demote the dependency ball in the embedding tier (those
            # vertices fall back to the online path until refreshed)
            self.precompute.on_invalidate(vertices)
        if self._host_pool is not None:
            # multi-host: the caches live on the graph hosts — broadcast
            # the drop (best-effort; a dead host holds no live state)
            from repro.store.nbr_cache import as_vertex_ids
            results = self._host_pool.broadcast(
                "invalidate", {"vertices": as_vertex_ids(vertices)})
            return sum(r["dropped"] for r in results if r is not None)
        if self.sg_cache is not None:
            self.sg_cache.invalidate(vertices)
        if self.nbr_cache is None:
            return 0
        return self.nbr_cache.invalidate(vertices)

    def _on_batch_done(self, ticket=None):
        """Pipeline completion hook: evaluate the policy's automatic
        repin triggers and hand the rebalance to the engine's single
        repin worker — the completion path itself stays light (the
        scheduler's contract), and in-flight batches keep their residency
        snapshot (the payload carries its generation), so a repin landing
        mid-stream never corrupts them.

        The hit-floor trigger backs off exponentially while the rate
        stays below the floor (a working set larger than the budget can
        NEVER satisfy it — without backoff every batch would pay a full
        table rebuild) and re-arms as soon as a check passes."""
        pol = self.store_policy
        src = self._fsource
        with self._repin_lock:
            self._repin_batches += 1
            self._floor_batches += 1
            due = bool(pol.repin_every
                       and self._repin_batches >= pol.repin_every)
            if not due and pol.repin_hit_floor \
                    and self._floor_batches >= self._floor_wait:
                lk = getattr(src, "lookups", 0) - self._repin_base[0]
                res = getattr(src, "resident_lookups", 0) \
                    - self._repin_base[1]
                self._floor_batches = 0
                if lk > 0 and (res / lk) < pol.repin_hit_floor:
                    due = True
                    self._floor_wait = min(64, self._floor_wait * 2)
                else:
                    self._floor_wait = 1
            if not due:
                return
            self._repin_batches = 0
            self._repin_base = (getattr(src, "lookups", 0),
                                getattr(src, "resident_lookups", 0))
            self.auto_repins += 1
        self._repin_pool.submit(self._auto_repin_job)

    def _auto_repin_job(self):
        try:
            self.repin()
        except Exception:            # a failed rebalance must not kill
            pass                     # the worker (serving is unaffected)

    def drain_repins(self, timeout: Optional[float] = 60.0):
        """Block until every triggered auto-repin has executed (tests /
        orderly shutdown; serving never needs this)."""
        if self._repin_pool is not None:
            self._repin_pool.submit(lambda: None).result(timeout)

    def repin(self, **kwargs) -> dict:
        """Online residency rebalance (resident + sharded stores):
        re-derive the device-resident set from the PPR mass observed
        since start — hot cold-rows promote, dead resident rows demote
        (and, sharded, skewed shards even out). In-flight batches keep
        their residency snapshot (the payload carries its generation), so
        serving never pauses."""
        if not hasattr(self._fsource, "repin"):
            raise ValueError(
                f"store strategy {self._fsource.name!r} has no repin(); "
                "use StorePolicy(features='resident' | 'sharded', ...)")
        return self._fsource.repin(**kwargs)

    def store_report(self) -> dict:
        """Cache/transfer state of this deployment's store subsystem."""
        pol = self.store_policy.describe()
        if self.nbr_cache is not None:
            # resolve the policy's "auto" pin set to what is actually
            # evict-exempt in this deployment
            pol["pinned_count"] = self.nbr_cache.num_pinned_targets
        r = {"policy": pol, "features": self._fsource.report()}
        if self.nbr_cache is not None:
            r["nbr_cache"] = self.nbr_cache.stats()
        if self.sg_cache is not None:
            r["subgraph_cache"] = self.sg_cache.stats()
        if self._repin_auto:
            r["auto_repins"] = self.auto_repins
        if self._host_pool is not None:
            # multi-host: per-host health + the graph hosts' own cache
            # stats (best-effort — a down host reports health only)
            health = self._host_pool.report()
            remote = self._host_pool.broadcast("report", None)
            for h, rep in zip(health, remote):
                if rep is not None:
                    h["report"] = rep
            r["graph_hosts"] = health
        return r

    def trace_report(self) -> dict:
        """Observability state of this deployment: tracing counters,
        per-span-name latency histograms, flight-recorder summary,
        clock-sync estimates, and the per-op calibration table (the
        ``trace.*`` schema section). ``{"enabled": False}`` when the
        deployment was built without ``ServingConfig(trace=...)``."""
        if self.tracer is None:
            return {"enabled": False}
        from repro.core.report_schema import trace_section
        return trace_section(self.tracer, self._calib)

    def export_trace(self, path: str) -> dict:
        """Write this deployment's finished spans (export ring + flight
        recorder trees) as a Perfetto-loadable chrome trace."""
        if self.tracer is None:
            raise ValueError(
                "tracing is off; construct the engine with "
                "ServingConfig(trace=TraceConfig(...)) to record spans")
        from repro.obs.export import write_chrome_trace
        return write_chrome_trace(path, self.tracer.export_spans(),
                                  metadata={"config":
                                            self.config.describe()})

    def telemetry_report(self) -> dict:
        """Live telemetry state of this deployment (the ``telemetry.*``
        schema section): windowed metric snapshot, SLO burn-rate rows,
        watchdog state, and the event ring. ``{"enabled": False}`` when
        the deployment was built without ``ServingConfig(telemetry=...)``.
        """
        if self.telemetry is None:
            return {"enabled": False}
        from repro.core.report_schema import telemetry_section
        return telemetry_section(self.telemetry)

    def metrics_wire(self, cluster: bool = True) -> dict:
        """This deployment's metrics in wire form. With ``cluster=True``
        on a multi-host deployment, every graph host's registry is
        scraped over the ``metrics`` RPC (best-effort broadcast) and
        merged losslessly into one cluster view — per-host histograms
        fold bucket-by-bucket, so the merged count is exactly the sum of
        the per-host counts."""
        if self.telemetry is None:
            raise ValueError(
                "telemetry is off; construct the engine with "
                "ServingConfig(telemetry=TelemetryConfig(...))")
        local = self.telemetry.to_wire()
        if not cluster or self._host_pool is None:
            return local
        from repro.obs.metrics import merge_wire
        remote = self._host_pool.broadcast("metrics", None)
        return merge_wire([local] + [r for r in remote if r])

    def metrics_text(self, cluster: bool = True) -> str:
        """Prometheus text exposition of ``metrics_wire()`` (what an
        HTTP ``/metrics`` endpoint serves for this deployment)."""
        from repro.obs.promexp import render_wire
        return render_wire(self.metrics_wire(cluster=cluster))

    def precompute_report(self) -> dict:
        """Embedding-tier state of this deployment (the ``precompute.*``
        schema section): residency, freshness, hit/demotion counters and
        refresh backlog. ``{"enabled": False}`` when the deployment was
        built without ``ServingConfig(precompute=...)`` (or this model
        kind is excluded from ``PrecomputeConfig.models``)."""
        from repro.core.report_schema import precompute_section
        return precompute_section(self.precompute)

    def close(self):
        dconf = self.config.dispatch
        if self.dispatch is not None and dconf.save_on_close \
                and dconf.artifact:
            try:                     # best-effort: a failed save must
                self.save_calibration()   # not block shutdown
            except Exception as e:
                warnings.warn(f"calibration save failed: {e}",
                              RuntimeWarning, stacklevel=2)
        if hasattr(self.graph, "unregister_listener"):
            self.graph.unregister_listener(self.invalidate)
        if self.precompute is not None:
            self.precompute.close()
        self.scheduler.close()
        if self.telemetry is not None:
            self.telemetry.close()
        if self._repin_pool is not None:
            self._repin_pool.shutdown(wait=True)
        for stage in self.stages:
            stage.close()
        if self._host_pool is not None:
            self._host_pool.close()

    def __enter__(self) -> "DecoupledEngine":
        return self

    def __exit__(self, *exc):
        self.close()
