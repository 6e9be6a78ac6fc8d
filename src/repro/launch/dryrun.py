import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape) cell on the single-pod 16x16 mesh and the
2x16x16 multi-pod mesh; record memory_analysis, cost_analysis and the HLO
roofline terms per cell as JSON.

The device-count override above MUST precede any jax import (jax locks the
backend device count at first init), which is why this file sets it in its
first two lines and why nothing else in the package sets it globally.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # everything
  ... --arch deepseek-7b --shape train_4k --mesh single        # one cell
  ... --gnn                                                    # GNN cells
  ... --out results/dryrun --skip-existing                     # resumable
"""

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402

from repro.configs.base import SHAPES, optimized, shape_cells  # noqa: E402
from repro.configs.registry import ARCHS, get_config        # noqa: E402
from repro.gnn.model import GNNConfig                       # noqa: E402
from repro.launch.cells import build_cell, build_gnn_cell   # noqa: E402
from repro.launch.hlo_analysis import analyze               # noqa: E402
from repro.launch.mesh import make_production_mesh          # noqa: E402

GNN_CELLS = [GNNConfig(kind=k, n_layers=L, receptive_field=N, f_in=512)
             for (k, L, N) in
             [("gcn", 3, 128), ("sage", 5, 128), ("gat", 3, 128),
              ("sage", 16, 256), ("gcn", 8, 64)]]


def run_cell(fn, args, in_sh, out_sh, mesh, n_devices: int,
             donate=()) -> dict:
    t0 = time.time()
    with mesh:
        jf = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=donate)
        lowered = jf.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo = analyze(compiled.as_text(), n_devices=n_devices)
    return {
        "ok": True,
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_est": ma.argument_size_in_bytes
            + ma.output_size_in_bytes + ma.temp_size_in_bytes,
        },
        "cost_analysis": {k: ca[k] for k in ("flops",)
                          if k in ca},
        "hlo": hlo.to_json(),
    }


def cell_name(arch: str, shape: str, mesh_kind: str) -> str:
    return f"{arch}__{shape}__{mesh_kind}".replace("/", "_")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch name | all (LM archs)")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--gnn", action="store_true",
                    help="also run the GNN serve cells")
    ap.add_argument("--gnn-only", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", default="base", choices=["base", "opt"],
                    help="opt = beyond-paper optimizations "
                         "(chunked attention, gather MoE, cache CP)")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": (make_production_mesh(), 256),
              "multi": (make_production_mesh(multi_pod=True), 512)}
    if args.mesh != "both":
        meshes = {args.mesh: meshes[args.mesh]}

    cells = []
    if not args.gnn_only:
        archs = list(ARCHS) if args.arch == "all" else [args.arch]
        for arch in archs:
            cfg = get_config(arch)
            if args.variant == "opt":
                cfg = optimized(cfg)
            shapes = (shape_cells(cfg) if args.shape == "all"
                      else [SHAPES[args.shape]])
            for shp in shapes:
                cells.append(("lm", arch, cfg, shp))
    if args.gnn or args.gnn_only:
        for g in GNN_CELLS:
            cells.append(("gnn", g.display, g, None))

    failures = []
    for mesh_kind, (mesh, ndev) in meshes.items():
        for kind, arch, cfg, shp in cells:
            sname = shp.name if shp else "serve"
            if args.variant != "base":
                sname += "." + args.variant
            name = cell_name(arch, sname, mesh_kind)
            path = os.path.join(args.out, name + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[skip] {name}")
                continue
            print(f"[cell] {name} ...", flush=True)
            try:
                if kind == "lm":
                    fn, a, i_sh, o_sh, don = build_cell(cfg, shp, mesh)
                else:
                    fn, a, i_sh, o_sh, don = build_gnn_cell(
                        cfg, mesh, variant=args.variant)
                rec = run_cell(fn, a, i_sh, o_sh, mesh, ndev, don)
            except Exception as e:   # noqa: BLE001 — survey must continue
                rec = {"ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                failures.append(name)
            rec.update(arch=arch, shape=sname, mesh=mesh_kind,
                       n_devices=ndev)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            if rec["ok"]:
                mm = rec["memory"]
                print(f"  ok: compile {rec['t_compile_s']}s, "
                      f"args {mm['argument_bytes']/2**30:.2f} GiB, "
                      f"temp {mm['temp_bytes']/2**30:.2f} GiB, "
                      f"flops {rec['hlo']['flops']:.3e}", flush=True)
            else:
                print(f"  FAIL: {rec['error']}", flush=True)
    print(f"\ndone. {len(failures)} failures: {failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
