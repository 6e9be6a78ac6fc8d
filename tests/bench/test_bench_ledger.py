"""The readers of the scheduler's hand-off ledger, on hand-built run
records: each reads its key per batch (or per answered request), and a
program without the ledger (no such key) leaves the metric out."""
import numpy as np
import pytest

from bench_tiny_root import REPO
from bench import harness

# metric -> (ledger key, per request?)
LEDGER = {
    "lane_wait_ms_per_request": ("queue.lane", True),
    "admit_wait_ms_per_batch": ("queue.admit", False),
    "select_queue_ms_per_batch": ("queue.select", False),
    "build_queue_ms_per_batch": ("queue.build", False),
    "pack_queue_ms_per_batch": ("queue.pack", False),
    "dispatch_queue_ms_per_batch": ("queue.dispatch", False),
    "launch_ms_per_batch": ("device.launch", False),
    "drain_queue_ms_per_batch": ("queue.drain", False),
    "ready_wait_ms_per_batch": ("device.ready", False),
    "reply_ms_per_batch": ("device.reply", False),
}
SERVICE = {"select": 2.0, "build": 3.0, "pack": 0.5}


def counters(batches, requests, stage_times):
    return {"batches": batches, "stage_times": dict(stage_times),
            "bytes_shipped": 0, "cache_hits": 0, "cache_misses": 0,
            "build_hits": 0, "build_misses": 0, "lane_batches": batches,
            "lane_requests": requests}


def record(cell, after_times, before_times=None):
    return harness.RunRecord(
        cell=harness.load_cell(REPO, cell), peaks={}, seconds=25.0,
        lat=np.zeros(3), lag=np.zeros(3),
        before=counters(10, 30, before_times or {}),
        after=counters(110, 330, after_times), compiles=0)


@pytest.mark.parametrize("metric", sorted(LEDGER))
def test_each_reader_reads_its_key_over_the_window(metric):
    key, per_request = LEDGER[metric]
    before = dict(SERVICE, **{k: 1.0 for k, _ in LEDGER.values()})
    after = {k: v + 4.0 for k, v in before.items()}
    after[key] = before[key] + 6.0          # 6 s over the window
    rec = record("gcn-flickr.zipf", after, before)
    out = harness.layer_metrics(rec.cell, rec)
    want = 6e3 / (300 if per_request else 100)
    assert out[metric] == {"value": pytest.approx(want), "unit": "ms"}


@pytest.mark.parametrize("cell", ["gcn-flickr.zipf", "gat-flickr.zipf"])
def test_a_program_without_the_ledger_leaves_the_metrics_out(cell):
    rec = record(cell, {k: v + 1.0 for k, v in SERVICE.items()}, SERVICE)
    out = harness.layer_metrics(rec.cell, rec)
    assert not set(LEDGER) & set(out)
    assert out["select_ms_per_batch"]["value"] == pytest.approx(10.0)


def test_every_ledger_metric_is_in_both_cells():
    for cell in ("gcn-flickr.zipf", "gat-flickr.zipf"):
        names = {m["name"] for m in harness.load_cell(REPO, cell).per_layer}
        assert set(LEDGER) <= names

