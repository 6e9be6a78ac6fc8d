"""Sharded device feature store: the resident table partitioned across N
logical shards with cross-shard gather and online PPR-mass rebalancing.

``DeviceFeatureStore`` (store/feature_store.py) keeps inference index-only
while the feature matrix fits ONE device's HBM budget; past that, every
cold row re-pays the paper's t_load as a per-batch miss block. HP-GNN and
GraphAGILE scale past one accelerator by partitioning vertex data across
memory banks/devices — this module is that step for the TPU substrate:

  * the resident table is split into ``num_shards`` shard tables, each
    placed on its own jax device when the host has that many (simulated
    shards — all tables on the default device — otherwise), each under
    its OWN ``budget_bytes``;
  * placement is ``hash`` (vertex id mod shards — uniform, no stats
    needed) or ``range`` (degree-rank bands — shard 0 holds the hottest
    band, matching HP-GNN's degree-ordered partitioning);
  * a batch ships, per shard, the int32 shard-local slot list of the
    unique rows it needs there; each shard gathers its rows LOCALLY and
    the blocks are concatenated + reordered on the target shard via one
    [C, N] int32 reorder map. Rows resident on no shard fall back to a
    host miss partition exactly like the single-device store (shipped at
    f_in — the link never carries pad zeros);
  * every lookup accumulates rank-weighted PPR mass per row (node lists
    arrive PPR-rank-ordered, so 1/(1+rank) is the online estimate of the
    paper's PPR score); ``repin()`` rebuilds the residency from that
    observed mass — promoting hot cold-rows, demoting dead resident
    rows, and rebalancing skewed shards — without restarting the engine.

Placements are immutable snapshots keyed by a generation counter that
rides inside the batch payload, so a ``repin()`` landing between a
batch's host prep and its device gather cannot mismap slots.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import shard_devices
from repro.graphs.csr import CSRGraph
from repro.store.nbr_cache import as_vertex_ids
from repro.store.policy import PLACEMENT_MODES as PLACEMENTS

BudgetSpec = Union[None, int, Sequence[int]]


@dataclass(frozen=True)
class ShardPlacement:
    """One immutable residency snapshot: which shard (if any) holds each
    vertex row, at which shard-local slot, and the shard tables built
    from that assignment. ``gen`` keys the snapshot in the payload."""
    gen: int
    shard_of: np.ndarray          # [V] int32, -1 = host partition
    slot_of: np.ndarray           # [V] int32 shard-local slot, -1 = host
    tables: Tuple[jax.Array, ...]  # per shard [R_s, f_pad] device-resident

    @property
    def resident_per_shard(self) -> Tuple[int, ...]:
        return tuple(int(t.shape[0]) for t in self.tables)

    @property
    def num_resident(self) -> int:
        return sum(self.resident_per_shard)


def _normalize_budgets(budget: BudgetSpec, num_shards: int,
                       total_rows: int, row_bytes: int) -> List[int]:
    """Per-shard row capacities. ``None`` = the whole matrix split evenly
    (full residency across the union of shards); an int applies to every
    shard; a sequence gives per-shard budgets (uneven shards)."""
    if budget is None:
        base = total_rows // num_shards
        extra = total_rows - base * num_shards
        return [base + (1 if s < extra else 0) for s in range(num_shards)]
    if isinstance(budget, (int, np.integer)):
        budgets = [int(budget)] * num_shards
    else:
        budgets = [int(b) for b in budget]
        if len(budgets) != num_shards:
            raise ValueError(f"{len(budgets)} shard budgets for "
                             f"{num_shards} shards")
    return [max(0, b // row_bytes) for b in budgets]


class ShardedFeatureStore:
    """Feature rows partitioned across shard-resident tables; batches ship
    per-shard slot lists + one reorder map (+ the host-fallback miss
    block). Implements the engine's feature-source interface."""

    name = "sharded"
    needs_host_feats = False

    def __init__(self, graph: CSRGraph, f_pad: int, *,
                 num_shards: int = 2, placement: str = "hash",
                 budget_bytes: BudgetSpec = None,
                 hot_scores: Optional[np.ndarray] = None):
        if num_shards < 1:
            raise ValueError("num_shards must be >= 1")
        if placement not in PLACEMENTS:
            raise ValueError(f"placement={placement!r}, expected one of "
                             f"{PLACEMENTS}")
        self.graph, self.f_pad = graph, f_pad
        self.num_shards = num_shards
        self.placement = placement
        v = graph.num_vertices
        self.row_bytes = f_pad * 4
        self.capacities = _normalize_budgets(budget_bytes, num_shards, v,
                                             self.row_bytes)
        score = np.asarray(graph.degrees if hot_scores is None
                           else hot_scores, np.float64)
        if len(score) != v:
            raise ValueError("hot_scores must have one entry per vertex")
        self.devices = shard_devices(num_shards)
        self.target_device = self.devices[0]
        self.simulated = len(set(self.devices)) < num_shards
        self._lock = threading.Lock()
        # online hotness: rank-weighted appearance mass per row (the
        # ROADMAP's PPR-mass feedback — node lists are PPR-rank-ordered)
        self._mass = np.zeros(v, np.float64)
        self._pad_row = jax.device_put(
            jnp.zeros((1, f_pad), jnp.float32), self.target_device)
        self._placements: Dict[int, ShardPlacement] = {}
        # generation refcounts: host_payload takes a reference on its
        # snapshot, device_feats releases it — a placement is retired
        # only when it is no longer current AND no in-flight batch still
        # points at it, so arbitrarily many repin() calls can land while
        # batches sit in the pipeline
        self._gen_refs: Dict[int, int] = {}
        self._gen = 0
        self._install(self._initial_assignment(score))
        # cumulative counters (under _lock)
        self.lookups = 0
        self.resident_lookups = 0
        self.miss_rows_shipped = 0
        self.cross_shard_rows = 0     # rows gathered off the target shard
        self.shard_lookups = np.zeros(num_shards, np.int64)
        self.repins = 0

    # payload keys are an instance attribute: they enumerate the shards
    @property
    def payload_keys(self) -> Tuple[str, ...]:
        return tuple(f"shard{s}_slots" for s in range(self.num_shards)) \
            + ("reorder", "miss_feats", "shard_gen")

    # -- placement construction ---------------------------------------------
    def _initial_assignment(self, score: np.ndarray) -> np.ndarray:
        """[V] int32 shard assignment (-1 = host) from the static policy.

        hash:  home shard = v mod num_shards; within a home bucket the
               top rows by ``score`` stay under that shard's capacity.
        range: vertices in descending-score order are cut into contiguous
               bands, one per shard, band s sized to capacity_s (shard 0
               holds the hottest band).
        """
        v = self.graph.num_vertices
        assign = np.full(v, -1, np.int32)
        if self.placement == "hash":
            home = (np.arange(v) % self.num_shards).astype(np.int32)
            for s in range(self.num_shards):
                mine = np.flatnonzero(home == s)
                k = min(len(mine), self.capacities[s])
                if k:
                    top = mine[np.argpartition(score[mine], -k)[-k:]]
                    assign[top] = s
        else:                                     # degree-range bands
            order = np.argsort(-score, kind="stable")
            lo = 0
            for s in range(self.num_shards):
                hi = min(v, lo + self.capacities[s])
                assign[order[lo:hi]] = s
                lo = hi
        return assign

    def _install(self, assign: np.ndarray) -> ShardPlacement:
        """Build shard tables + slot maps for ``assign`` and make it the
        current placement (new generation)."""
        v = self.graph.num_vertices
        slot_of = np.full(v, -1, np.int32)
        tables = []
        for s in range(self.num_shards):
            ids = np.flatnonzero(assign == s)
            slot_of[ids] = np.arange(len(ids), dtype=np.int32)
            rows = np.zeros((len(ids), self.f_pad), np.float32)
            if len(ids):
                rows[:, :self.graph.feature_dim] = self.graph.features[ids]
            tables.append(jax.device_put(rows, self.devices[s]))
        with self._lock:
            self._gen += 1
            pl = ShardPlacement(self._gen, assign.astype(np.int32),
                                slot_of, tuple(tables))
            self._placements[pl.gen] = pl
            self._current = pl
            # retire snapshots nothing references anymore
            for g in [g for g in self._placements
                      if g != pl.gen and not self._gen_refs.get(g)]:
                del self._placements[g]
        return pl

    # -- feature-source interface -------------------------------------------
    def host_payload(self, node_lists, n, feats=None):
        with self._lock:                       # one snapshot per batch,
            pl = self._current                 # pinned until the gather
            self._gen_refs[pl.gen] = self._gen_refs.get(pl.gen, 0) + 1
        c = len(node_lists)
        ids = np.full((c, n), -1, np.int64)
        for i, nl in enumerate(node_lists):
            k = min(len(nl), n)
            ids[i, :k] = nl[:k]
        valid = ids >= 0
        flat = ids[valid]
        shard = pl.shard_of[flat]
        slot = pl.slot_of[flat]
        # reorder map into [pad_row | shard blocks ... | miss block]
        pos = np.zeros(len(flat), np.int64)
        payload: Dict[str, np.ndarray] = {}
        offset = 1                             # row 0 = zero pad row
        per_shard = np.zeros(self.num_shards, np.int64)
        for s in range(self.num_shards):
            sel = shard == s
            uniq, inv = np.unique(slot[sel], return_inverse=True)
            payload[f"shard{s}_slots"] = uniq.astype(np.int32)
            pos[sel] = offset + inv
            offset += len(uniq)
            per_shard[s] = int(sel.sum())
        miss_sel = shard < 0
        miss_ids, miss_inv = np.unique(flat[miss_sel], return_inverse=True)
        pos[miss_sel] = offset + miss_inv
        # host-fallback miss block ships at f_in: the shard tables carry
        # the MXU pad columns, the link must not (see PackedFeatureShipper)
        payload["miss_feats"] = self.graph.features[miss_ids] if \
            len(miss_ids) else np.zeros((0, self.graph.feature_dim),
                                        np.float32)
        reorder = np.zeros((c, n), np.int32)
        reorder[valid] = pos
        payload["reorder"] = reorder
        payload["shard_gen"] = np.asarray(pl.gen, np.int32)
        # rank-weighted PPR-mass accumulation: node lists are ordered by
        # descending PPR score, so 1/(1+rank) tracks each row's share.
        # The O(C*N) reduction runs OUTSIDE the lock (unique rows +
        # bincount); only the O(unique) merge holds it, so concurrent
        # prepare threads don't serialize on the scatter-add
        w = (1.0 / (1.0 + np.arange(n, dtype=np.float64)))[None, :]
        uids, uinv = np.unique(flat, return_inverse=True)
        contrib = np.bincount(uinv,
                              weights=np.broadcast_to(w, ids.shape)[valid])
        with self._lock:
            self._mass[uids] += contrib
            self.lookups += int(valid.sum())
            self.resident_lookups += int(valid.sum() - miss_sel.sum())
            self.miss_rows_shipped += int(len(miss_ids))
            self.shard_lookups += per_shard
            self.cross_shard_rows += int(sum(
                len(payload[f"shard{s}_slots"])
                for s in range(1, self.num_shards)))
        return payload, None

    def device_feats(self, payload):
        gen = int(payload["shard_gen"])
        with self._lock:
            pl = self._placements[gen]
        try:
            blocks = [self._pad_row]
            for s in range(self.num_shards):
                slots = payload[f"shard{s}_slots"]
                if slots.shape[0] == 0:
                    continue
                # shard-local gather: slot list crosses to shard s (int32
                # — index-only), the gathered rows cross back to the
                # target. On simulated shards (same device) both hops are
                # skipped — no device_put round-trips per batch
                sl = jnp.asarray(slots)
                if self.devices[s] is not self.target_device:
                    sl = jax.device_put(sl, self.devices[s])
                blk = jnp.take(pl.tables[s], sl, axis=0)
                if self.devices[s] is not self.target_device:
                    blk = jax.device_put(blk, self.target_device)
                blocks.append(blk)
            miss = payload["miss_feats"]
            if miss.shape[0]:
                # default device == devices[0] == the target shard, so
                # the padded miss block lands there without an explicit
                # transfer
                m = jnp.asarray(miss)
                pad = self.f_pad - m.shape[-1]
                if pad:
                    m = jnp.pad(m, ((0, 0), (0, pad)))
                blocks.append(m)
            gathered = jnp.concatenate(blocks, axis=0) \
                if len(blocks) > 1 else self._pad_row
            return jnp.take(gathered, jnp.asarray(payload["reorder"]),
                            axis=0)
        finally:
            with self._lock:
                r = self._gen_refs.get(gen, 0)
                if r > 1:
                    self._gen_refs[gen] = r - 1
                else:
                    self._gen_refs.pop(gen, None)
                    if gen != self._current.gen:
                        self._placements.pop(gen, None)

    # -- per-batch shard metrics (pure function of one payload) --------------
    def shard_metrics_for(self, payload) -> List[int]:
        """Host->device bytes this payload ships to each shard: the shard's
        slot list, plus (on the target shard) the reorder map and the miss
        block. Pure — safe from concurrent prepare threads."""
        out = [int(payload[f"shard{s}_slots"].nbytes)
               for s in range(self.num_shards)]
        out[0] += int(payload["reorder"].nbytes) \
            + int(payload["miss_feats"].nbytes)
        return out

    # -- online rebalancing ---------------------------------------------------
    def repin(self, decay: float = 0.0) -> dict:
        """Re-derive residency from the accumulated PPR mass: the globally
        hottest rows (by observed mass, degree as tiebreak for never-seen
        rows) fill the shard capacities. Rows keep their current shard
        when it still has room (minimizing table churn); the rest go to
        the least-loaded shard. Returns a movement/balance report;
        ``decay`` scales the retained mass afterwards (0 keeps it all)."""
        with self._lock:
            mass = self._mass.copy()
            old = self._current
        # degree epsilon-tiebreak: rows never observed rank by degree
        deg = self.graph.degrees.astype(np.float64)
        key = mass + 1e-12 * deg
        total_cap = sum(self.capacities)
        v = self.graph.num_vertices
        k = min(v, total_cap)
        hot = np.argsort(-key, kind="stable")[:k] if k else \
            np.empty(0, np.int64)
        assign = np.full(v, -1, np.int32)
        free = np.array(self.capacities, np.int64)
        # pass 1: sticky — hot rows stay on their current shard
        cur = old.shard_of[hot]
        for s in range(self.num_shards):
            keep = hot[(cur == s)][:self.capacities[s]]
            assign[keep] = s
            free[s] -= len(keep)
        # pass 2: promote the remaining hot rows across the free slots,
        # vectorized stride-scheduling fill (equivalent to repeatedly
        # picking the least-loaded shard, without the per-row Python
        # loop): shard s's k-th free slot sits at fractional position
        # (k + 1) / free_s, and filling slots in that order interleaves
        # shards proportionally to their free capacity
        pending = hot[assign[hot] < 0]
        slot_shard = np.repeat(np.arange(self.num_shards), np.maximum(
            free, 0))
        slot_pos = np.concatenate(
            [(np.arange(f) + 1.0) / f for f in free if f > 0]) \
            if (free > 0).any() else np.empty(0)
        order = np.argsort(slot_pos, kind="stable")
        take = min(len(pending), len(slot_shard))
        assign[pending[:take]] = slot_shard[order[:take]]
        promoted = int(((old.shard_of < 0) & (assign >= 0)).sum())
        demoted = int(((old.shard_of >= 0) & (assign < 0)).sum())
        moved = int(((old.shard_of >= 0) & (assign >= 0)
                     & (old.shard_of != assign)).sum())
        bal_before = self._balance(old, mass)
        pl = self._install(assign)
        bal_after = self._balance(pl, mass)
        with self._lock:
            self.repins += 1
            if decay:
                self._mass *= (1.0 - decay)
        return {"promoted": promoted, "demoted": demoted, "moved": moved,
                "resident_per_shard": pl.resident_per_shard,
                "mass_balance_before": bal_before,
                "mass_balance_after": bal_after}

    def _balance(self, pl: ShardPlacement, mass: np.ndarray) -> float:
        """max/mean of per-shard resident mass (1.0 = perfectly even)."""
        per = np.zeros(self.num_shards)
        res = pl.shard_of >= 0
        np.add.at(per, pl.shard_of[res], mass[res])
        mean = per.mean()
        return round(float(per.max() / mean), 4) if mean > 0 else 1.0

    # -- graph-update hook ----------------------------------------------------
    def refresh_features(self, vertices) -> int:
        """Re-upload the shard-resident rows of ``vertices`` from the
        (updated) host feature matrix. Host-partition rows need nothing —
        they ship fresh on every miss. Returns rows re-uploaded."""
        ids = as_vertex_ids(vertices)
        with self._lock:
            pl = self._current
            refreshed = 0
            tables = list(pl.tables)
            for s in range(self.num_shards):
                mine = ids[pl.shard_of[ids] == s]
                if not len(mine):
                    continue
                rows = np.zeros((len(mine), self.f_pad), np.float32)
                rows[:, :self.graph.feature_dim] = self.graph.features[mine]
                tables[s] = tables[s].at[
                    jnp.asarray(pl.slot_of[mine])].set(jnp.asarray(rows))
                refreshed += len(mine)
            if refreshed:
                new = ShardPlacement(pl.gen, pl.shard_of, pl.slot_of,
                                     tuple(tables))
                self._placements[pl.gen] = new
                self._current = new
        return refreshed

    # -- introspection --------------------------------------------------------
    @property
    def num_resident(self) -> int:
        return self._current.num_resident

    @property
    def resident_fraction(self) -> float:
        return self.num_resident / max(1, self.graph.num_vertices)

    @property
    def device_bytes(self) -> int:
        return sum(int(t.nbytes) for t in self._current.tables)

    def report(self) -> dict:
        with self._lock:
            pl = self._current
            lk, res, miss = (self.lookups, self.resident_lookups,
                             self.miss_rows_shipped)
            cross, repins = self.cross_shard_rows, self.repins
            per_lookups = self.shard_lookups.tolist()
            mass = self._mass.copy()
        per_rows = pl.resident_per_shard
        return {"strategy": self.name,
                "num_shards": self.num_shards,
                "placement": self.placement,
                "simulated": self.simulated,
                # where each shard table actually lives (device ids)
                "shard_devices": [int(next(iter(t.devices())).id)
                                  for t in pl.tables],
                "resident_rows": sum(per_rows),
                "resident_fraction": round(self.resident_fraction, 4),
                "device_bytes": sum(int(t.nbytes) for t in pl.tables),
                "shard_rows": list(per_rows),
                "shard_bytes": [int(t.nbytes) for t in pl.tables],
                "shard_lookups": per_lookups,
                "shard_hit_share": [round(x / lk, 4) for x in per_lookups]
                if lk else [0.0] * self.num_shards,
                "mass_balance": self._balance(pl, mass),
                "lookups": lk,
                "resident_hit_rate": round(res / lk, 4) if lk else 0.0,
                "miss_rows_shipped": miss,
                "cross_shard_rows": cross,
                "repins": repins}
