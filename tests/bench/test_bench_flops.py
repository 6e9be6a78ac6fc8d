"""Operation and byte counts, against hand counts at a small size, and
pinned at the full configurations' shapes."""
import json
import os

import pytest

from bench_tiny_root import REPO, make_root
from bench import flops, harness, tracing

GCN = {"kind": "gcn", "n_layers": 2, "receptive_field": 4, "f_in": 3,
       "f_hidden": 8, "n_heads": 2}
GAT = dict(GCN, kind="gat")


def model_module(kind):
    return harness.load_module(os.path.join(REPO, "bench", "models",
                                            f"{kind}.py"), f"test_{kind}")


def full_model(kind):
    with open(os.path.join(REPO, "bench", "configs",
                           f"{kind}-flickr.json")) as f:
        return json.load(f)["model"]


def test_gcn_model_work_counts_real_vertices_and_edges():
    # layer 0: transform 2*4*3*8 = 192, aggregate 2*(5+4)*8 = 144
    # layer 1: transform 2*4*8*8 = 512, aggregate 144
    assert model_module("gcn").model_flops(GCN, 4, 5) == \
        192 + 144 + 512 + 144


def test_gat_model_work_adds_scores_and_softmax():
    # per layer on top of GCN's: scores 2*2*4*8 = 128, softmax 8*(5+4)*2
    extra = 128 + 8 * 9 * 2
    assert model_module("gat").model_flops(GAT, 4, 5) == \
        192 + 144 + 512 + 144 + 2 * extra


@pytest.mark.parametrize("need,metric", [
    ("model_flops", "ack_step_mfu"),
    ("FUSED_USES", "fused_gnn_layer_roofline"),
    ("FUSED_USES", "kernels_roofline")])
def test_model_work_refuses_an_unknown_kind(tmp_path, need, metric):
    """A cell whose model module lacks a count that one of its metrics
    needs is refused by ``load_cell``, before any server starts."""
    root = make_root(tmp_path, kinds=("gcn",))
    path = os.path.join(root, "bench", "models", "gcn.py")
    with open(path) as f:
        src = f.read()
    with open(path, "w") as f:
        f.write(src + f"\n\ndel {need}\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["per_layer"] = [m for m in bm["per_layer"] if m["name"] == metric]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    with pytest.raises(ValueError, match=f"models/gcn.py has no {need}, "
                                         f"which metric {metric}"):
        harness.load_cell(root, "gcn-tiny.zipf")


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


def _arr(shape, space=0, item=4):
    return [list(shape), item, space]


def test_kernel_call_counts_only_hbm_bytes_of_what_the_call_uses():
    # output, adj, h, w_neigh, w_self, b, mask of a [2,4,3] -> 8 call
    arrays = [_arr((2, 4, 8), 1), _arr((2, 4, 4)), _arr((2, 4, 3)),
              _arr((3, 8)), _arr((3, 8)), _arr((1, 8), 1), _arr((2, 1, 4))]
    gcn, gat = model_module("gcn"), model_module("gat")
    ops, moved = flops.kernel_call("fused_gnn_layer", arrays, gcn)
    assert ops == flops.fused_gnn_layer(2, 4, 3, 8, aggregate=True)[0]
    # on-chip output and b moved nothing; w_self is unused by GCN
    assert moved == 4 * (2 * 4 * 4 + 2 * 4 * 3 + 3 * 8 + 2 * 4)
    ops, moved = flops.kernel_call("fused_gnn_layer", arrays, gat)
    assert ops == flops.fused_gnn_layer(2, 4, 3, 8, aggregate=False)[0]
    assert moved == 4 * (2 * 4 * 3 + 3 * 8 + 2 * 4)   # h, w_self, mask
    att = [_arr((2, 4, 8)), _arr((2, 4, 8)), _arr((2, 4, 2)),
           _arr((2, 4, 2)), _arr((2, 4, 4), 1)]
    ops, moved = flops.kernel_call("gat_attention", att, gat)
    assert ops == flops.gat_attention(2, 4, 8, 2)[0]
    assert moved == 4 * (2 * 4 * 8 * 2 + 2 * 2 * 4 * 2)


def test_scatter_gather_call_counts():
    # C=2 targets, E padded to 256 (one block of EB=256), N=4, F=8:
    # out [2,4,8] f32; src, dst s32 and w f32 as [2,1,256]; h [2,4,8]
    arrays = [_arr((2, 4, 8)), _arr((2, 1, 256)), _arr((2, 1, 256)),
              _arr((2, 1, 256)), _arr((2, 4, 8))]
    ops, moved = flops.kernel_call("scatter_gather_aggregate", arrays,
                                   model_module("gcn"))
    # two one-hot matmuls, 2*256*4*8 each, and 256*8 weight products, x2
    assert ops == 2 * (2 * 2 * 256 * 4 * 8 + 256 * 8) == 69632
    assert moved == 4 * (2 * 4 * 8 + 3 * 2 * 256 + 2 * 4 * 8) == 6656
    # h kept on chip moves nothing
    arrays[4] = _arr((2, 4, 8), 1)
    assert flops.kernel_call("scatter_gather_aggregate", arrays,
                             model_module("gcn"))[1] == 6656 - 256


def test_an_unknown_kernel_has_no_count():
    with pytest.raises(ValueError, match="no count for kernel"):
        flops.kernel_call("no_such_kernel", [_arr((1,))], None)


def test_kernels_are_the_count_files():
    names = sorted(f[:-3] for f in os.listdir(os.path.join(
        REPO, "bench", "kernels")) if f.endswith(".py"))
    assert tracing.KERNELS == tuple(names)
    # today's three kernels are counted; a new kernel joins by a count
    # file of its own
    assert {"fused_gnn_layer", "gat_attention",
            "scatter_gather_aggregate"} <= set(tracing.KERNELS)
    for kernel in tracing.KERNELS:
        assert callable(flops.kernel_count(kernel)), kernel


def test_bound_names_the_limiting_resource():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.bound_seconds(1000.0, 50.0, peaks) == (10.0, "compute")
    assert flops.bound_seconds(100.0, 50.0, peaks) == (5.0, "memory")


# Pinned: the counts at the full configurations' widths, as the counts
# read before they moved into the model and kernel files.
FIELDS = [(128, 1412), (57, 230), (1, 0)]
MODEL_PINS = {"gcn": [68687872.0, 29975040.0, 519680.0],
              "gat": [69228928.0, 30177696.0, 522848.0]}
FUSED_CALLS = [
    [_arr((64, 128, 256), 1), _arr((64, 128, 128)), _arr((64, 128, 512)),
     _arr((512, 256)), _arr((512, 256), 1), _arr((1, 256)),
     _arr((64, 1, 128))],
    [_arr((64, 128, 256), 1), _arr((64, 128, 128), 1),
     _arr((64, 128, 256), 1), _arr((256, 256), 1), _arr((256, 256), 1),
     _arr((1, 256), 1), _arr((64, 1, 128), 1)],
    [_arr((64, 128, 256)), _arr((64, 128, 128)), _arr((64, 128, 256)),
     _arr((256, 256), 0, 2), _arr((256, 256), 0, 2), _arr((1, 256), 1),
     _arr((64, 1, 128))]]
FUSED_PINS = {"gcn": [(2690646016.0, 21529600.0), (1616904192.0, 0.0),
                      (1616904192.0, 21135360.0)],
              "gat": [(2153775104.0, 16811008.0), (1080033280.0, 0.0),
                      (1080033280.0, 16941056.0)]}
ATTENTION_CALLS = [
    [_arr((64, 128, 256)), _arr((64, 128, 256)), _arr((64, 128, 4)),
     _arr((64, 128, 4)), _arr((64, 128, 128))],
    [_arr((64, 128, 256), 1), _arr((64, 128, 256), 1), _arr((64, 128, 4)),
     _arr((64, 128, 4), 1), _arr((64, 128, 128))],
    [_arr((8, 16, 32)), _arr((8, 16, 32), 0, 2), _arr((8, 16, 2)),
     _arr((8, 16, 2)), _arr((8, 16, 16))]]
ATTENTION_PINS = [(570425344.0, 21233664.0), (570425344.0, 4325376.0),
                  (163840.0, 34816.0)]


@pytest.mark.parametrize("kind", ["gcn", "gat"])
@pytest.mark.parametrize("shape", range(3))
def test_counts_are_pinned_at_full_widths(kind, shape):
    model, module = full_model(kind), model_module(kind)
    assert module.model_flops(model, *FIELDS[shape]) == \
        MODEL_PINS[kind][shape]
    assert flops.kernel_call("fused_gnn_layer", FUSED_CALLS[shape],
                             module) == FUSED_PINS[kind][shape]
    if kind == "gat":
        assert flops.kernel_call("gat_attention", ATTENTION_CALLS[shape],
                                 module) == ATTENTION_PINS[shape]
