"""Operation and byte counts, against hand counts at a small size."""
import pytest

import bench_tiny_root  # noqa: F401
from bench import flops

GCN = {"kind": "gcn", "n_layers": 2, "receptive_field": 4, "f_in": 3,
       "f_hidden": 8, "n_heads": 2}
GAT = dict(GCN, kind="gat")


def test_gcn_model_work_counts_real_vertices_and_edges():
    # layer 0: transform 2*4*3*8 = 192, aggregate 2*(5+4)*8 = 144
    # layer 1: transform 2*4*8*8 = 512, aggregate 144
    assert flops.model_flops(GCN, 4, 5) == 192 + 144 + 512 + 144


def test_gat_model_work_adds_scores_and_softmax():
    # per layer on top of GCN's: scores 2*2*4*8 = 128, softmax 8*(5+4)*2
    extra = 128 + 8 * 9 * 2
    assert flops.model_flops(GAT, 4, 5) == 192 + 144 + 512 + 144 + 2 * extra


def test_model_work_refuses_an_unknown_kind():
    with pytest.raises(ValueError):
        flops.model_flops(dict(GCN, kind="sage"), 4, 5)


def test_fused_layer_call_counts():
    ops, moved = flops.fused_gnn_layer(2, 4, 3, 8, aggregate=True)
    assert ops == 2 * (2 * 4 * 3 * 8 + 3 * 4 * 8) + 2 * 2 * 4 * 4 * 8
    # h, W, b, mask, out, adj in float32
    assert moved == 4 * (2 * 4 * 3 + 3 * 8 + 8 + 2 * 4 + 2 * 4 * 8
                         + 2 * 4 * 4)
    ops2, moved2 = flops.fused_gnn_layer(2, 4, 3, 8, aggregate=False)
    assert ops - ops2 == 2 * 2 * 4 * 4 * 8 and moved - moved2 == 4 * 32


def test_gat_attention_call_counts():
    ops, moved = flops.gat_attention(2, 4, 8, 2)
    assert ops == 2 * (2 * 4 * 4 * 8 + 8 * 4 * 4 * 2)
    assert moved == 4 * (2 * 4 * 8 * 2 + 2 * 2 * 4 * 2 + 2 * 4 * 4)


def _arr(shape, space=0):
    return [list(shape), 4, space]


def test_kernel_call_counts_only_hbm_bytes_of_what_the_call_uses():
    # output, adj, h, w_neigh, w_self, b, mask of a [2,4,3] -> 8 call
    arrays = [_arr((2, 4, 8), 1), _arr((2, 4, 4)), _arr((2, 4, 3)),
              _arr((3, 8)), _arr((3, 8)), _arr((1, 8), 1), _arr((2, 1, 4))]
    ops, moved = flops.kernel_call("fused_gnn_layer", arrays, GCN)
    assert ops == flops.fused_gnn_layer(2, 4, 3, 8, aggregate=True)[0]
    # on-chip output and b moved nothing; w_self is unused by GCN
    assert moved == 4 * (2 * 4 * 4 + 2 * 4 * 3 + 3 * 8 + 2 * 4)
    ops, moved = flops.kernel_call("fused_gnn_layer", arrays, GAT)
    assert ops == flops.fused_gnn_layer(2, 4, 3, 8, aggregate=False)[0]
    assert moved == 4 * (2 * 4 * 3 + 3 * 8 + 2 * 4)   # h, w_self, mask
    gat = [_arr((2, 4, 8)), _arr((2, 4, 8)), _arr((2, 4, 2)),
           _arr((2, 4, 2)), _arr((2, 4, 4), 1)]
    ops, moved = flops.kernel_call("gat_attention", gat, GAT)
    assert ops == flops.gat_attention(2, 4, 8, 2)[0]
    assert moved == 4 * (2 * 4 * 8 * 2 + 2 * 2 * 4 * 2)


def test_bound_names_the_limiting_resource():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.bound_seconds(1000.0, 50.0, peaks) == (10.0, "compute")
    assert flops.bound_seconds(100.0, 50.0, peaks) == (5.0, "memory")
