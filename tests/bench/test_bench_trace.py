"""The reduction from a profiler trace to device metrics, on hand-made
traces and on a small trace recorded on a TPU v5e (bench/testdata)."""
import glob
import json
import os

import numpy as np
import pytest

from bench_tiny_root import REPO
from bench import flops, harness, tracing

# a fused GCN layer call's arrays, [shape, item bytes, memory space]:
# output, adj, h, w_neigh, w_self, b, mask; w_self and the output on-chip
FUSED = [[[2, 4, 8], 4, 1], [[2, 4, 4], 4, 0], [[2, 4, 3], 4, 0],
         [[3, 8], 4, 0], [[3, 8], 4, 1], [[1, 8], 4, 0], [[2, 1, 4], 4, 0]]
GAT = [[[2, 4, 8], 4, 0], [[2, 4, 8], 4, 0], [[2, 4, 2], 4, 0],
       [[2, 4, 2], 4, 0], [[2, 4, 4], 4, 0]]
HAND = {
    "window": [1000, 2000],
    "device_ops": [
        ["fusion.1", 900, 200],                   # clipped to [1000, 1100]
        ["fused_gnn_layer.3", 1050, 100, FUSED],  # overlaps: union 1000-1150
        ["gat_attention", 1400, 100, GAT],
        ["copy.2", 1950, 100],                    # clipped to [1950, 2000]
    ],
    "modules": [["jit__forward(7)", 1040, 500],
                ["jit_take(2)", 1600, 50]],
    "host_spans": [["bench.select", 1100, 400],   # open across 1150-1400
                   ["bench.device", 1500, 100],
                   ["bench.build", 1550, 300]],   # innermost at 1775
}


def swept_busy(tr):
    """Busy ns in the window by an endpoint sweep (coverage counting),
    independent of the reduction's interval merge."""
    lo, hi = tr["window"]
    points = []
    for _, s, d, *_ in tr["device_ops"]:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    busy, depth, last = 0, 0, None
    for t, step in sorted(points):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_busy_union_idle_share_and_labels_on_a_hand_trace():
    s = tracing.reduce(HAND)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(swept_busy(HAND) * 1e-9)
    assert s.busy_s == pytest.approx((150 + 100 + 50) * 1e-9)
    assert s.idle_share == pytest.approx(0.7)
    assert s.kernel_seconds == {"fused_gnn_layer": pytest.approx(100e-9),
                                "gat_attention": pytest.approx(100e-9)}
    assert s.kernel_calls == {"fused_gnn_layer": [FUSED],
                              "gat_attention": [GAT]}
    assert s.program_runs == 1
    assert s.program_seconds == pytest.approx(500e-9)
    # gaps: 1150-1400 (select open at 1275), 1500-1950 (at 1725 only
    # build is open)
    assert s.idle_by_span == {"select": pytest.approx(250e-9),
                              "build": pytest.approx(450e-9)}
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s)
    b = s.breakdown()
    assert b["device_ops"][0][0] in ("fusion.1", "fused_gnn_layer.3",
                                     "gat_attention")
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_union_merges_touching_and_nested_intervals():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (3, 4), (6, 6)]) == [
        (0, 4), (5, 7)]


def model_module(kind):
    return harness.load_module(os.path.join(REPO, "bench", "models",
                                            f"{kind}.py"), f"test_{kind}")


def test_kernel_roofline_is_bound_time_over_measured_time():
    s = tracing.reduce(HAND)
    model = model_module("gcn")
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    ops, moved = flops.kernel_call("fused_gnn_layer", FUSED, model)
    want = max(ops / 1e12, moved / 1e9) / 100e-9
    share = tracing.kernel_roofline(s, "fused_gnn_layer", model, peaks)
    assert share == pytest.approx(100.0 * want)
    assert tracing.kernel_roofline(s, "scatter_gather_aggregate", model,
                                   peaks) is None


def test_kernel_arrays_are_read_from_the_op_text():
    text = ("%fused_gnn_layer.4 = f32[64,128,256]{2,1,0:T(8,128)S(1)} "
            "custom-call(f32[64,128,128]{2,1,0:T(8,128)} %batch__adj__.1, "
            "bf16[512,256]{1,0:T(8,128)(2,1)} %w), "
            'custom_call_target="tpu_custom_call", operand_layout_'
            "constraints={f32[64,128,128]{2,1,0}, bf16[512,256]{1,0}}")
    assert tracing.short_name(text) == "fused_gnn_layer.4"
    assert tracing.arrays_of(text) == [[[64, 128, 256], 4, 1],
                                       [[64, 128, 128], 4, 0],
                                       [[512, 256], 2, 0]]


# a scatter-gather call: out [2,4,8]; src, dst, w [2,1,256]; h [2,4,8]
SG = [[[2, 4, 8], 4, 0], [[2, 1, 256], 4, 0], [[2, 1, 256], 4, 0],
      [[2, 1, 256], 4, 0], [[2, 4, 8], 4, 0]]


def test_kernels_roofline_reads_a_trace_that_holds_scatter_gather():
    tr = dict(HAND, device_ops=HAND["device_ops"]
              + [["scatter_gather_aggregate.2", 1100, 40, SG]])
    s = tracing.reduce(tr)
    assert s.kernel_calls["scatter_gather_aggregate"] == [SG]
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    model = model_module("gcn")
    ops, moved = flops.kernel_call("scatter_gather_aggregate", SG, model)
    assert tracing.kernel_roofline(s, "scatter_gather_aggregate", model,
                                   peaks) == pytest.approx(
        100.0 * max(ops / 1e12, moved / 1e9) / 40e-9)
    cell = type("Cell", (), {"model_module": lambda self: model})()
    run = type("Run", (), {"trace": s, "cell": cell, "peaks": peaks})()
    reader = harness.load_module(os.path.join(
        REPO, "bench", "metrics", "kernels_roofline.py"), "test_kr")
    shares = {k: tracing.kernel_roofline(s, k, model, peaks)
              for k in s.kernel_seconds}
    want = sum(shares[k] * s.kernel_seconds[k] for k in shares) \
        / sum(s.kernel_seconds.values())
    assert reader.read(run) == pytest.approx(want)


RECORDED = sorted(glob.glob(os.path.join(REPO, "bench", "testdata",
                                         "*.trace.json")))


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_reduction_of_a_recorded_chip_trace(path):
    with open(path) as f:
        rec = json.load(f)
    tr, want = rec["trace"], rec["expect"]
    s = tracing.reduce(tr)
    assert s.busy_s == pytest.approx(swept_busy(tr) * 1e-9, rel=1e-9)
    assert 0 < s.busy_s <= s.window_s
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s)
    assert s.busy_s == pytest.approx(want["busy_s"])
    assert s.program_runs == want["program_runs"]
    assert s.kernel_calls == want["kernel_calls"]
    for k, v in want["kernel_seconds"].items():
        assert s.kernel_seconds[k] == pytest.approx(v)
    assert sum(s.kernel_seconds.values()) <= s.busy_s + 1e-12


def test_recorded_chip_trace_reads_the_pinned_roofline():
    """``fused_gnn_layer_roofline`` of the recorded GCN trace, as it read
    before the counts moved into the model and kernel files (the ledger's
    42.8 for the cell)."""
    with open(os.path.join(REPO, "bench", "testdata",
                           "gcn-flickr.zipf.trace.json")) as f:
        s = tracing.reduce(json.load(f)["trace"])
    with open(os.path.join(REPO, "bench", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    assert tracing.kernel_roofline(s, "fused_gnn_layer", model_module("gcn"),
                                   peaks) == 42.81726095920053
