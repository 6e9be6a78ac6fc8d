"""Kernel-level benchmark: ACK kernels vs their pure-jnp oracles
(correctness residual) + the modeled TPU-v5e roofline occupancy per kernel
configuration from the DSE cost model (modeled, not measured: no time is
taken here).

The kernels go through ``repro.kernels.ops``, so they compile with Mosaic
on a TPU and run interpreted on the CPU. The oracles run at float32
matmul precision ("highest"), so the residual measures the kernel alone.
The gate is relative (``rel_err`` = max abs error over max abs oracle):
on a TPU the kernels' float32 dots run at the default precision, whose
bfloat16 operand passes leave a relative error near 2^-8; 1e-2 holds that
and still catches a wrong layout, mask or index, which is an O(1) error."""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import enable_cache, print_table, record_trajectory
from repro.core.dse import TPUSpec
from repro.kernels import ops, ref

REL_TOL = 1e-2


def _residual(got, want) -> dict:
    err = float(jnp.abs(got - want).max())
    rel = err / max(float(jnp.abs(want).max()), 1e-30)
    return {"max_err": f"{err:.1e}", "rel_err": f"{rel:.1e}"}


def _roofline(flops, hbm_bytes, spec=TPUSpec()):
    t_c = flops / spec.peak_flops
    t_m = hbm_bytes / spec.hbm_bw
    return {"t_compute_us": round(t_c * 1e6, 3),
            "t_memory_us": round(t_m * 1e6, 3),
            "bound": "compute" if t_c >= t_m else "memory",
            "intensity": round(flops / hbm_bytes, 1)}


def run(quick: bool = True):
    """quick=True is the CI smoke mode: one small config per kernel, used
    as a correctness regression canary (rel_err vs the jnp oracle)."""
    rows = []
    key = jax.random.PRNGKey(0)
    fused_cfgs = [(8, 64, 512, 256)] if quick else \
        [(8, 64, 512, 256), (8, 128, 512, 256), (8, 256, 512, 256)]
    for (c, n, f_in, f_out) in fused_cfgs:
        ks = jax.random.split(key, 3)
        h = jax.random.normal(ks[0], (c, n, f_in), jnp.float32)
        adj = (jax.random.uniform(ks[1], (c, n, n)) < 0.2).astype(
            jnp.float32)
        w = jax.random.normal(ks[2], (f_in, f_out)) * 0.1
        got = ops.fused_gnn_layer(adj, h, w, None, None, None)
        with jax.default_matmul_precision("highest"):
            want = ref.fused_gnn_layer_ref(adj, h, w, None, None, None)
        flops = c * (2 * n * f_in * f_out + 2 * n * n * f_out)
        hbm = 4 * c * (n * f_in + n * n + n * f_out) + 4 * f_in * f_out
        rows.append({"kernel": "fused_gnn", "cfg": f"C{c} N{n} f{f_in}",
                     **_residual(got, want), **_roofline(flops, hbm)})
    # scatter-gather
    c, n, f, e = (4, 64, 128, 512) if quick else (8, 128, 256, 2048)
    ks = jax.random.split(key, 4)
    src = jax.random.randint(ks[0], (c, e), 0, n).astype(jnp.int32)
    dst = jax.random.randint(ks[1], (c, e), 0, n).astype(jnp.int32)
    wts = jax.random.normal(ks[2], (c, e))
    h = jax.random.normal(ks[3], (c, n, f))
    got = ops.scatter_gather_aggregate(src, dst, wts, h)
    with jax.default_matmul_precision("highest"):
        want = ref.scatter_gather_aggregate_ref(src, dst, wts, h)
    flops = c * 4 * e * n * f            # one-hot routing matmuls
    hbm = 4 * c * (n * f * 2 + 3 * e)
    rows.append({"kernel": "scatter_gather", "cfg": f"C{c} N{n} E{e}",
                 **_residual(got, want), **_roofline(flops, hbm)})
    # gat attention
    c, n, f, heads = (4, 64, 128, 4) if quick else (8, 128, 256, 4)
    z = jax.random.normal(ks[0], (c, n, f))
    ss = jax.random.normal(ks[1], (c, n, heads))
    sd = jax.random.normal(ks[2], (c, n, heads))
    struct = (jax.random.uniform(ks[3], (c, n, n)) < 0.3).astype(
        jnp.float32) + jnp.eye(n)[None]
    got = ops.gat_attention(z, ss, sd, struct, n_heads=heads)
    with jax.default_matmul_precision("highest"):
        want = ref.gat_attention_ref(z, ss, sd, struct, n_heads=heads)
    flops = c * (2 * n * n * f + 8 * n * n * heads)
    hbm = 4 * c * (2 * n * f + n * n)
    rows.append({"kernel": "gat_attention", "cfg": f"C{c} N{n} h{heads}",
                 **_residual(got, want), **_roofline(flops, hbm)})
    print_table(rows, ["kernel", "cfg", "max_err", "rel_err",
                       "t_compute_us", "t_memory_us", "bound", "intensity"])
    payload = {"rows": rows}
    # regress gate scalars: one residual per kernel (lower is better) so
    # a numerics regression in ANY kernel trips python -m repro.obs.regress
    regress: dict = {}
    for r in rows:           # worst residual per kernel (several cfgs in
        k = f"max_err_{r['kernel']}"          # the non-quick sweep)
        regress[k] = max(regress.get(k, 0.0), float(r["max_err"]))
    regress["max_err_worst"] = float(np.max(list(regress.values())))
    record_trajectory("kernels", payload, regress=regress)
    # np.max propagates NaN (python max() would drop a non-leading NaN)
    worst = float(np.max([float(r["rel_err"]) for r in rows]))
    if not (worst <= REL_TOL):
        raise RuntimeError(f"kernel residual regression: rel_err={worst}")
    return payload


if __name__ == "__main__":
    enable_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small configs only (CI regression canary)")
    run(quick=ap.parse_args().smoke)
