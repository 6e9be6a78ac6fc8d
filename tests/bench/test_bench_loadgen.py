"""The benchmark's open-loop generator: schedule, lateness, percentiles."""
import time

import numpy as np
import pytest

import bench_tiny_root  # noqa: F401  puts the checkout on sys.path
from bench import loadgen

DEG = np.array([3, 50, 7, 1, 20, 9, 2, 11], np.int64)
ZIPF = {"targets": {"kind": "zipf", "a": 1.1}, "rate_per_s": 400,
        "warmup_requests": 100}


@pytest.mark.parametrize("rate,seconds", [(400, 10.0), (37.5, 20.0),
                                          (1000, 1.5)])
def test_schedule_offers_exactly_rate_times_window(rate, seconds):
    s = loadgen.window_schedule(ZIPF, 5, seconds, DEG, rate=rate)
    assert len(s.due) == len(s.targets) == round(rate * seconds)
    assert np.all(np.diff(s.due) >= 0)
    assert s.due.min() >= 0 and s.due.max() < seconds
    # Poisson conditioned on its count: arrivals spread evenly
    quarters = np.histogram(s.due, bins=4, range=(0, seconds))[0]
    assert np.all(np.abs(quarters - len(s.due) / 4)
                  < 5 * np.sqrt(len(s.due) / 4) + 1)


def test_every_seed_offers_the_same_work_in_another_order():
    a = loadgen.window_schedule(ZIPF, 1, 5.0, DEG)
    b = loadgen.window_schedule(ZIPF, 2, 5.0, DEG)
    assert not np.array_equal(a.targets, b.targets)
    np.testing.assert_array_equal(np.sort(a.targets), np.sort(b.targets))
    np.testing.assert_allclose(np.sort(np.diff(a.due, prepend=0.0)),
                               np.sort(np.diff(b.due, prepend=0.0)),
                               rtol=0, atol=1e-9)
    w1, w2 = (loadgen.warmup_targets(ZIPF, s, DEG) for s in (1, 2))
    np.testing.assert_array_equal(np.sort(w1), np.sort(w2))
    other = loadgen.window_schedule(dict(ZIPF, seed=7), 1, 5.0, DEG)
    assert not np.array_equal(np.sort(other.targets), np.sort(a.targets))


def test_schedule_is_a_function_of_the_seed():
    big = 2 ** 31 + 12345                      # beyond 32 signed bits
    a = loadgen.window_schedule(ZIPF, big, 5.0, DEG)
    b = loadgen.window_schedule(ZIPF, big, 5.0, DEG)
    c = loadgen.window_schedule(ZIPF, big + 1, 5.0, DEG)
    np.testing.assert_array_equal(a.due, b.due)
    np.testing.assert_array_equal(a.targets, b.targets)
    assert not np.array_equal(a.due, c.due)
    w1 = loadgen.warmup_targets(ZIPF, big, DEG)
    np.testing.assert_array_equal(w1, loadgen.warmup_targets(ZIPF, big, DEG))
    assert len(w1) == 100


def test_zipf_targets_follow_degree_rank():
    s = loadgen.window_schedule(ZIPF, 3, 50.0, DEG)
    counts = np.bincount(s.targets, minlength=len(DEG))
    assert counts.argmax() == DEG.argmax()
    assert counts[DEG.argmin()] < counts[DEG.argmax()]
    u = loadgen.window_schedule({"targets": {"kind": "uniform"},
                                 "rate_per_s": 400}, 3, 50.0, DEG)
    assert set(np.unique(u.targets)) == set(range(len(DEG)))


def test_latency_runs_from_due_time_and_failures_are_infinite():
    due = np.array([10.0, 10.5, 11.0, 11.5])
    done = np.array([10.2, 10.6, 0.0, 12.5])
    ok = np.array([True, True, False, True])
    lat = loadgen.latencies(due, done, ok)
    np.testing.assert_allclose(lat[[0, 1, 3]], [0.2, 0.1, 1.0])
    assert np.isinf(lat[2])


@pytest.mark.parametrize("values,q,want", [
    ([5.0, 1.0, 3.0, 2.0, 4.0], 50, 3.0),
    (list(range(1, 101)), 99, 100.0),      # rank ceil(0.99 * 99) = 99
    (list(range(1, 201)), 99, 199.0),
    ([1.0, 2.0, np.inf, 4.0], 50, 4.0),          # nearest rank: no nan
    ([1.0, 2.0, 3.0, np.inf], 99, np.inf),
])
def test_percentiles_are_nearest_rank(values, q, want):
    assert loadgen.percentile(np.array(values), q) == want


def test_open_loop_submits_on_schedule_without_waiting_for_answers():
    sched = loadgen.Schedule(due=np.array([0.0, 0.05, 0.05, 0.12]),
                             targets=np.array([4, 5, 6, 7]))
    seen = []

    def submit(t):                              # a server that never answers
        seen.append((t, time.perf_counter()))
        return object()

    gen = loadgen.OpenLoop(sched, submit)
    t0 = time.perf_counter() + 0.02
    gen.start(t0)
    gen.join(timeout=5)
    assert [t for t, _ in seen] == [4, 5, 6, 7]
    lag = gen.t_submit - gen.due_abs
    assert np.all(lag >= 0) and np.all(lag < 0.05)
    assert all(r is not None for r in gen.requests)


@pytest.mark.parametrize("late_ms,grows", [(0.0, False), (5.0, False),
                                           (80.0, True)])
def test_knee_is_judged_on_the_wait_trend_alone(late_ms, grows):
    """A window whose last requests cannot finish by its close does not
    grow; one whose waits climb through it does."""
    from bench import sweep
    due = np.linspace(0.0, 10.0, 400, endpoint=False)
    lat = 0.02 + 1e-3 * late_ms * due / 10.0
    lat[-4:] = np.inf if not grows else lat[-4:]      # unfinished at close
    assert sweep.grows(due, lat, 10.0) is grows


def test_sweep_windows_offer_targets_of_their_own():
    """Each window of a knee sweep draws new targets: a replay of an
    earlier window's would find them in the caches."""
    from bench import sweep
    deg = np.arange(1, 50001, dtype=np.int64)
    uni = {"targets": {"kind": "uniform"}, "rate_per_s": 100}
    a, b = (loadgen.window_schedule(sweep.window_mix(uni, k), 7, 5.0, deg)
            for k in (1, 2))
    run = loadgen.window_schedule(uni, 7, 5.0, deg)
    assert len(set(a.targets) & set(b.targets)) < 0.1 * len(a.targets)
    assert len(set(a.targets) & set(run.targets)) < 0.1 * len(a.targets)


def test_collector_pauses_are_counted_inside_the_window():
    import gc
    from bench import harness
    pauses = harness.GcPauses()
    try:
        lo = time.perf_counter()
        gc.collect()
        hi = time.perf_counter()
        gc.collect()
    finally:
        pauses.close()
    text = pauses.summary(lo, hi)
    assert text.startswith("gen0 0 ") and "gen2 1 " in text
    assert pauses._cb not in gc.callbacks
