"""The traffic generator and the arithmetic of the end-to-end metrics."""

import statistics
import threading
import time

import numpy as np
import pytest

from bench import harness as H
from bench import stats, traffic


def test_open_schedule_is_a_function_of_the_seed():
    mix = traffic.Mix(loop="open", rate_per_s=40.0)
    a = traffic.open_schedule(mix, 2**33 + 7, 10.0)
    assert a == traffic.open_schedule(mix, 2**33 + 7, 10.0)
    b = traffic.open_schedule(mix, 12, 10.0)
    assert a != b
    # every seed offers the same jobs with the same gaps, in another order
    assert len(a) == len(b) == 400
    gaps = lambda d: sorted(np.round(np.diff(d + [10.0]), 9))  # noqa: E731
    assert gaps(a) == gaps(b)
    assert a[0] == 0.0 and max(a) < 10.0
    assert sum(np.diff(a + [10.0])) == pytest.approx(10.0)


def test_job_seeds_are_a_function_of_the_run_seed():
    assert traffic.job_seeds(2**40 + 3, 5) == traffic.job_seeds(2**40 + 3, 5)
    assert traffic.job_seeds(1, 5) != traffic.job_seeds(2, 5)
    assert all(0 < s < 2**31 for s in traffic.job_seeds(9, 1000))


def test_mix_rejects_what_it_cannot_generate():
    with pytest.raises(ValueError):
        traffic.Mix(loop="open", rate_per_s=0)
    with pytest.raises(ValueError):
        traffic.Mix(loop="closed", clients=0)
    with pytest.raises(ValueError):
        traffic.Mix(loop="open", rate_per_s=3, arrivals="bursty")


def test_quantile_matches_numpy_linear():
    xs = list(np.random.default_rng(0).exponential(size=257))
    for q in (0.0, 0.5, 0.95, 1.0):
        assert stats.quantile(xs, q) == pytest.approx(np.quantile(xs, q))
    assert stats.quantile([3.0], 0.95) == 3.0


def test_rate_is_all_work_over_the_whole_window():
    jobs = [H.JobRecord(seed=i, due=float(i), submitted=float(i),
                        done=float(i) + 0.5, summary={}) for i in range(10)]
    jobs.append(H.JobRecord(seed=10, due=9.8, submitted=9.8, done=10.7,
                            summary={}))
    jobs.append(H.JobRecord(seed=11, due=9.9, submitted=9.9,
                            error="lost"))
    out = H.end_to_end(jobs, (0.0, 10.0), job_evals=100, setup_s=3.0)
    # ten jobs ended inside the 10 s window; the one that ended after it
    # counts in the latencies but not in the work
    assert out["evals_per_s"] == 100.0
    lat = [0.5] * 10 + [0.9]
    assert out["job_p50_s"] == pytest.approx(np.quantile(lat, 0.5))
    assert out["job_p95_s"] == pytest.approx(np.quantile(lat, 0.95))
    assert out["setup_s"] == 3.0


class SlowService:
    """Ends each job a fixed time after the previous one ended, one at a
    time, whenever it was sent: a queue that a stall holds up."""

    def __init__(self, service_s):
        self.service_s = service_s
        self.lock = threading.Lock()
        self.free_at = 0.0

    def submit(self, seed):
        with self.lock:
            start = max(time.monotonic(), self.free_at)
            self.free_at = start + self.service_s
            return self.free_at

    def wait(self, ends_at, timeout=None):
        time.sleep(max(0.0, ends_at - time.monotonic()))
        return {"best_y": np.float32(0)}


def test_open_loop_times_each_job_from_when_it_was_due():
    mix = traffic.Mix(loop="open", rate_per_s=20.0)
    seeds = iter(traffic.job_seeds(5, 100))
    t0 = time.monotonic() + 0.05
    jobs, waiter = H.open_loop(SlowService(0.1), mix, seeds, 5, t0, 1.0,
                               H.annotator(None, False))
    waiter.join(30)
    assert not waiter.is_alive()
    due = [j.due - t0 for j in jobs]
    assert due == pytest.approx(traffic.open_schedule(mix, 5, 1.0))
    assert all(j.done is not None for j in jobs)
    # offered at 20/s, served at 10/s: the queue grows, and the latency
    # counted from when each job was due grows with it
    lat = [j.latency for j in jobs]
    assert lat[-1] > 0.8
    assert all(j.latency >= 0.1 - 1e-3 for j in jobs)
    assert statistics.mean(lat[-5:]) > statistics.mean(lat[:5]) + 0.5
