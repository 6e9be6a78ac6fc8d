"""Faults planted under the timed path, each of which the check must
catch: an answer altered where it is produced, a wrong neighborhood out
of Select, a wrong feature row out of the store gather."""
import numpy as np
import pytest

from bench_tiny_root import make_root
from bench import harness


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"), kinds=("gcn",))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)


def altered_answer(dep):
    """One element of each batch's output is off by one."""
    inner = dep.engine.scheduler.device_fn

    def device(plan):
        return inner(plan).at[0, 0].add(1.0)
    dep.engine.scheduler.device_fn = device


def wrong_neighborhood(dep):
    """Select hands on each field with its last vertex replaced."""
    select = dep.engine.stages[0]
    inner = select.run
    v = dep.graph.num_vertices

    def run(plan):
        plan = inner(plan)
        plan.node_lists = [np.append(nl[:-1], (nl[-1] + 1) % v)
                           for nl in plan.node_lists]
        return plan
    select.run = run


def wrong_feature_row(dep):
    """The resident store's gather comes back scaled by 1%."""
    src = dep.engine._fsource
    inner = src.device_feats

    def device_feats(payload):
        return inner(payload) * 1.01
    src.device_feats = device_feats


@pytest.mark.parametrize("fault", [altered_answer, wrong_neighborhood,
                                   wrong_feature_row])
def test_fault_under_the_timed_path_is_not_correct(root, fault):
    r = harness.run(root, "gcn-tiny.zipf", 12345, 1.5, False,
                    need_chip=False, chaos=fault)
    assert r["correct"] is False
    assert all(c["value"] > c["limit"] for name, c in r["checks"].items()
               if name != "unanswered")
