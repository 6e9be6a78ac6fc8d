"""Bring-up check: the multi-model ACK server end to end on one TPU v5e.

    python chip_smoke.py              # one chip: five lanes, full Flickr
    python chip_smoke.py --chips 4    # four chips: sharded feature store only

One chip. One ``GNNServer`` under one shared DSE plan serves five lanes
over synthetic Flickr at full scale (89,250 vertices, f_in 500), at the
paper's widths (f_hidden 256, L=3, N=128, C=64), every lane on the Pallas
kernels (``impl="pallas"``) with a resident feature store and an LRU
neighborhood cache:

    gcn, sage, gat (4 heads)   mode="auto" (dense at this density)
    gcn_sg                     mode="sg": runs the scatter-gather kernel
    gcn_dispatch               per-batch dispatch with block autotuning

Each lane's first batch is checked against a float32 reference (same
params, same BatchPlan, ``impl="xla"`` at matmul precision "highest"),
a few hundred Zipf requests go through the server, every served program
must contain the Pallas kernel (``tpu_custom_call``), and no exploration
pass may fail.

Four chips. The gcn lane's engine with a feature store sharded over four
chips (range placement, each shard's budget 30% of the padded matrix) must
serve bitwise the same embeddings as the resident store on one chip.

Runs in this one process and starts no other. Without a TPU it exits
non-zero and prints no result. The last line of a passing run is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

N, C, L, F_HIDDEN = 128, 64, 3, 256
# Tolerance of the served program against the float32 reference, as
# max|served - ref| / max|ref| over the batch's embeddings. On the TPU,
# float32 matmuls at the default precision round their operands to
# bfloat16 (8-bit mantissa, relative error <= 2^-9 each); the program
# chains about two such products per layer over three layers, so a
# relative error of a few 2^-8 ~ 1e-2 at the output scale is expected,
# and 3e-2 leaves margin. A wrong layout, mask or gather is an O(1) error.
REL_TOL = 3e-2
# what a compiled program holds where a Pallas kernel runs on the TPU
KERNEL_MARK = "tpu_custom_call"


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


# ---------------------------------------------------------------------------
# set-up


def require_tpu():
    """The first device, if it is the TPU v5e that ``TPUSpec`` plans
    for; otherwise exit non-zero, with no fallback."""
    import jax

    from repro.core.dse import TPUSpec
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"no TPU found: JAX could not start a backend ({e})")
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's first device is {dev.platform!r}")
    try:
        TPUSpec().check_device(dev)
    except RuntimeError as e:
        fail(str(e))
    return devices


class CompileClock:
    """Sums XLA backend compile seconds (JAX's own monitoring event), so a
    phase's compile cost can be read as a difference."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def lane_configs():
    from repro.core.config import ServingConfig
    from repro.core.dispatch import DispatchConfig
    from repro.gnn.model import GNNConfig
    from repro.store import StorePolicy

    def cfg(kind):
        return GNNConfig(kind=kind, n_layers=L, receptive_field=N, f_in=500,
                         f_hidden=F_HIDDEN, n_heads=4)

    base = ServingConfig(batch_size=C, impl="pallas", mode="auto",
                         store=StorePolicy(features="resident",
                                           nbr_cache="lru"))
    sg = ServingConfig(batch_size=C, impl="pallas", mode="sg",
                       store=base.store)
    disp = ServingConfig(batch_size=C, impl="pallas", mode="auto",
                         store=base.store,
                         dispatch=DispatchConfig(warmup_passes=1,
                                                 autotune_blocks=True))
    return {"gcn": (cfg("gcn"), base), "sage": (cfg("sage"), base),
            "gat": (cfg("gat"), base), "gcn_sg": (cfg("gcn"), sg),
            "gcn_dispatch": (cfg("gcn"), disp)}


def reference(eng, plan):
    """The float32 reference for one BatchPlan: the same params and the
    same subgraphs, features gathered on the host instead of from the
    device store, through the XLA ops at matmul precision "highest"."""
    import jax
    import numpy as np

    from repro.core.program import execute
    from repro.core.subgraph import assemble_batch
    sb = assemble_batch(eng.graph, plan.targets, plan.node_lists, plan.rows,
                        eng.cfg.receptive_field, eng.e_pad, build_feats=True)
    db = eng.device_batch(sb)
    with jax.default_matmul_precision("highest"):
        emb, _ = execute(eng.program, eng.params, db, impl="xla")
    return np.asarray(emb), db


def rel_err(got, want) -> float:
    import numpy as np
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# ---------------------------------------------------------------------------
# one chip: the five-lane server


def run_one_chip(scale: float = 1.0, requests: int = 400,
                 seed: int = 0) -> None:
    import numpy as np

    from repro.graphs.synthetic import get_graph, zipf_traffic
    from repro.serve.gnn_server import GNNServer

    clock = CompileClock()
    t0 = time.perf_counter()
    g = get_graph("flickr", scale, seed=seed)
    log(f"graph: flickr scale={scale} V={g.num_vertices} "
        f"E={g.num_edges} f_in={g.feature_dim} "
        f"({time.perf_counter() - t0:.1f}s)")

    lanes = lane_configs()
    server = GNNServer(max_wait_s=0.02)
    for name, (cfg, sconf) in lanes.items():
        server.register(name, graph=g, cfg=cfg, config=sconf)
    p = server.plan
    log(f"plan: one DSE plan for {server.models}: block_f={p.block_f} "
        f"c_core={p.c_core} vmem_used={p.vmem_used}")

    rng = np.random.default_rng(seed)
    fixed = rng.choice(g.num_vertices, C, replace=False)
    refs = {}
    try:
        # warm-up + correctness: each lane's first batch is the fixed one
        for name in lanes:
            eng = server.engine_for(name)
            c0 = clock.seconds
            t0 = time.perf_counter()
            plan = eng.plan(fixed)
            served = np.asarray(eng.run_device(plan))
            wall = time.perf_counter() - t0
            compile_s = clock.seconds - c0
            want, db = reference(eng, plan)
            refs[name] = (want, db)
            if served.shape != (C, F_HIDDEN) or \
                    not np.isfinite(served).all():
                fail(f"{name}: served {served.shape}, finite="
                     f"{np.isfinite(served).all()}")
            err = rel_err(served, want)
            log(f"lane {name}: mode={eng.mode} compile_s={compile_s:.2f} "
                f"first_batch_s={wall:.2f} "
                f"rel_err={err:.3e} (tol {REL_TOL:g})")
            if not err <= REL_TOL:
                fail(f"{name}: rel_err {err:.3e} > {REL_TOL:g}")

        # traffic: Zipf targets across the lanes, plus the fixed batch
        # on every lane so server answers meet the reference too
        server.start()
        names = list(lanes)
        targets = zipf_traffic(g, requests, seed=seed + 1)
        which = rng.integers(0, len(names), requests)
        c0, n0 = clock.seconds, clock.count
        t0 = time.perf_counter()
        reqs = [server.submit(int(t), model=names[k])
                for t, k in zip(targets, which)]
        fixed_reqs = {name: [server.submit(int(t), model=name)
                             for t in fixed] for name in names}
        every = reqs + [r for rs in fixed_reqs.values() for r in rs]
        server.drain(every, timeout=900)
        wall = time.perf_counter() - t0
        server.stop()
        errors = [r for r in every if r.error is not None
                  or r.embedding is None
                  or not np.isfinite(r.embedding).all()]
        if errors:
            fail(f"{len(errors)} requests failed: {errors[0].error!r}")
        log(f"traffic: {len(every)} requests answered, 0 errors, "
            f"{wall:.1f}s wall, {clock.count - n0} compiles "
            f"({clock.seconds - c0:.1f}s) during traffic")

        rep = server.report()
        n_explore = 0
        for name in names:
            eng = server.engine_for(name)
            lat = rep["models"][name]["latency"]
            got = np.stack([r.embedding for r in fixed_reqs[name]])
            err = rel_err(got, refs[name][0])
            if not err <= REL_TOL:
                fail(f"{name}: served-through-server rel_err {err:.3e}")
            # every program this lane served from holds the Pallas kernel
            progs = eng.programs()
            for key, fn in progs.items():
                text = fn.lower(eng.params, refs[name][1]).compile() \
                    .as_text()
                if KERNEL_MARK not in text:
                    fail(f"{name}: program {key} has no {KERNEL_MARK}")
            n_explore += eng.exploration_errors
            log(f"lane {name}: requests={lat['n']} "
                f"server_rel_err={err:.3e} programs={len(progs)} "
                f"tpu_custom_call=all p50_ms(info)={lat['p50'] * 1e3:.1f}")
        d = server.engine_for("gcn_dispatch").dispatch_report()
        log(f"dispatch: sources={d['sources']} blocks={d['blocks']} "
            f"variants={d['variants']} table_cells={d['table_cells']}")
        log(f"exploration_errors: {n_explore}")
        if n_explore:
            fail(f"{n_explore} exploration passes failed (warnings above)")
    finally:
        server.stop()
        for name in lanes:
            server.engine_for(name).close()


# ---------------------------------------------------------------------------
# four chips: the sharded feature store


def run_four_chips(scale: float = 1.0, requests: int = 256,
                   seed: int = 0) -> None:
    import jax
    import numpy as np

    from repro.core.config import ServingConfig
    from repro.core.engine import DecoupledEngine
    from repro.gnn.model import init_gnn
    from repro.graphs.synthetic import get_graph, zipf_traffic
    from repro.store import StorePolicy

    if len(jax.devices()) < 4:
        fail(f"--chips 4 needs four devices, found {len(jax.devices())}")
    g = get_graph("flickr", scale, seed=seed)
    cfg, base = lane_configs()["gcn"]
    params = init_gnn(cfg, jax.random.PRNGKey(base.seed))
    f_pad = 512                                  # 500 padded to 128 lanes
    budget = int(0.3 * g.num_vertices * f_pad * 4)
    sharded = ServingConfig(
        batch_size=C, impl="pallas", mode="auto",
        store=StorePolicy(features="sharded", num_shards=4,
                          placement="range", shard_budget_bytes=budget,
                          nbr_cache="lru"))
    targets = zipf_traffic(g, requests, seed=seed + 1)
    out = {}
    for name, sconf in (("resident", base), ("sharded", sharded)):
        with DecoupledEngine(g, cfg, params=params, config=sconf) as eng:
            assert eng.f_pad == f_pad
            t0 = time.perf_counter()
            out[name] = eng.infer(targets, overlap=False).embeddings
            feats = eng.store_report()["features"]
            shown = ("strategy", "simulated", "shard_devices",
                     "shard_rows", "resident_fraction")
            log(f"{name}: {len(targets)} targets in "
                f"{time.perf_counter() - t0:.1f}s, store "
                f"{ {k: feats[k] for k in shown if k in feats} }")
            if name == "sharded":
                devs = feats["shard_devices"]
                if feats["simulated"] or len(set(devs)) != 4:
                    fail(f"shards not on four devices: {feats}")
    if not np.isfinite(out["resident"]).all():
        fail("resident embeddings are not finite")
    same = np.array_equal(out["sharded"], out["resident"])
    log(f"sharded(4 chips) == resident(1 chip) bitwise: {same}")
    if not same:
        fail("sharded embeddings differ from resident: max abs diff "
             f"{np.abs(out['sharded'] - out['resident']).max():.3e}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-store phase on four "
                         "chips")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, SRC)
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache(ROOT)
    devices = require_tpu()
    dev = devices[0]
    log(f"device_kind: {dev.device_kind} platform={dev.platform} "
        f"count={len(devices)}")
    log(f"compile cache: {cache}")
    if args.chips == 4:
        run_four_chips()
    else:
        run_one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
