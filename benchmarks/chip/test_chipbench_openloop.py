"""The copied open-loop generator (no chip, no program)."""
import gc
import time

import numpy as np

import openloop


def test_every_seed_gets_the_same_gaps_in_its_own_order():
    a = openloop.fixed_poisson_schedule(500, 100.0,
                                        np.random.default_rng(1))
    b = openloop.fixed_poisson_schedule(500, 100.0,
                                        np.random.default_rng(2 ** 33))
    ga, gb = np.diff(a, prepend=0.0), np.diff(b, prepend=0.0)
    np.testing.assert_allclose(np.sort(ga), np.sort(gb))
    assert not np.allclose(ga, gb)
    assert abs(a[-1] - 5.0) < 0.05       # 500 requests at 100/s


class _Ticket:
    def __init__(self):
        self.t_done = None


class _Service:
    """Completes every request on the pump after it was submitted."""

    def __init__(self):
        self.open = []
        self.pumps = 0

    def submit(self, q):
        t = _Ticket()
        self.open.append(t)
        return t

    def pump(self):
        self.pumps += 1
        for t in self.open:
            t.t_done = time.perf_counter()
        self.open = []

    def drain(self):
        self.pump()


def test_latency_counts_from_the_scheduled_arrival():
    sched = np.array([0.01, 0.02, 0.03, 0.2])
    svc = _Service()
    run = openloop.run_open_loop(svc, [None] * 4, sched)
    assert np.all(np.isfinite(run.latencies_ms))
    assert np.all(run.latencies_ms >= 0)
    assert run.wall_s >= 0.2
    assert np.all(run.late_ms >= 0)
    assert svc.pumps >= 4


class _SleepingService(_Service):
    """Sleeps in its fourth pump: the host waits."""

    def pump(self):
        if self.pumps == 3:
            time.sleep(0.2)
        super().pump()


class _CollectingService(_Service):
    """Collects garbage in its fourth pump: the host computes."""

    def pump(self):
        if self.pumps == 3:
            gc.collect()
        super().pump()


SCHED = np.array([0.0, 0.01, 0.02, 0.03])


def test_a_stall_that_waited_shows_little_cpu_time():
    st = openloop.run_open_loop(_SleepingService(), [None] * 4, SCHED).stall
    assert st["stall_phase"] == "bench.pump"
    assert st["stall_ms"] >= 200.0
    assert st["stall_cpu_ms"] < 0.5 * st["stall_ms"]


def test_a_collection_shows_as_gc_time():
    st = openloop.run_open_loop(_CollectingService(), [None] * 4,
                                SCHED).stall
    assert st["gc_ms"] > 0.0 and st["gc_max_ms"] <= st["gc_ms"]
    assert st["stall_phase"] == "bench.pump"
    assert st["stall_ms"] >= st["gc_max_ms"]
