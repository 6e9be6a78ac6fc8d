"""Fig. 1/3 reproduction: the Coupled model's exponential receptive-field /
communication growth and low C2C ratio vs the Decoupled model's fixed cost.

Measures per L: average L-hop receptive-field size (full and fanout-
sampled), host->device bytes, compute FLOPs, and the resulting C2C ratio —
the quantities the paper uses to justify decoupling (§2.2, §3.2).
"""
from __future__ import annotations


from benchmarks.common import (QUICK_SCALE, enable_cache, print_table,
                               record_trajectory)
from repro.core.coupled import receptive_field_size
from repro.core.subgraph import build_batch
from repro.graphs.synthetic import get_graph

F_HIDDEN = 256


def run(quick: bool = True):
    g = get_graph("flickr", scale=QUICK_SCALE["flickr"])
    f_in = g.feature_dim
    targets = list(range(16 if quick else 64))
    rows = []
    fanouts = [25, 10, 10, 10]
    for L in ([1, 2, 3] if quick else [1, 2, 3, 4]):
        n_full = receptive_field_size(g, targets, L)
        n_samp = receptive_field_size(g, targets, L, fanouts[:L])
        bytes_coupled = 4.0 * n_samp * f_in
        flops_coupled = 2.0 * n_samp * f_in * F_HIDDEN
        rows.append({
            "model": "coupled", "L": L,
            "receptive_field": round(n_samp, 1),
            "rf_unsampled": round(n_full, 1),
            "h2d_KB": round(bytes_coupled / 1024, 1),
            "c2c_flops_per_byte": round(flops_coupled / bytes_coupled, 1),
        })
    # decoupled: fixed N regardless of L
    for L in ([3, 8] if quick else [3, 5, 8, 16]):
        N = 128
        sb = build_batch(g, targets[:8], N, num_threads=4)
        nbytes = sb.nbytes("dense") / len(targets[:8])
        flops = (2.0 * N * f_in * F_HIDDEN
                 + (L - 1) * 2.0 * N * F_HIDDEN * F_HIDDEN
                 + L * 2.0 * N * N * F_HIDDEN)
        rows.append({
            "model": "decoupled", "L": L, "receptive_field": N,
            "rf_unsampled": N,
            "h2d_KB": round(nbytes / 1024, 1),
            "c2c_flops_per_byte": round(flops / nbytes, 1),
        })
    print_table(rows, ["model", "L", "receptive_field", "h2d_KB",
                       "c2c_flops_per_byte"])
    # paper claims: coupled rf grows superlinearly; decoupled C2C grows
    # linearly with L while bytes stay constant
    cp = [r for r in rows if r["model"] == "coupled"]
    dc = [r for r in rows if r["model"] == "decoupled"]
    claims = {
        "coupled_rf_explodes": cp[-1]["receptive_field"]
        > 4 * cp[0]["receptive_field"],
        "decoupled_bytes_constant": len({r["h2d_KB"] for r in dc}) == 1,
        "decoupled_c2c_grows_with_L": dc[-1]["c2c_flops_per_byte"]
        > 1.5 * dc[0]["c2c_flops_per_byte"],
    }
    print(claims)
    payload = {"rows": rows, "claims": claims}
    record_trajectory("fig3_breakdown", payload)
    return payload


if __name__ == "__main__":
    enable_cache()
    run(quick=False)
