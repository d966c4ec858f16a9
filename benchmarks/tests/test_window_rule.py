"""The window rule and the boundary seam, on a fake clock."""

import numpy as np

from benchmarks.harness import core, observe


def test_window_closes_at_first_boundary_at_or_after_seconds():
    rule = core.WindowRule(51.0)
    rule.open(100.0, 7)
    assert not rule.offer(120.0, 8)
    assert not rule.offer(150.9, 9)
    assert rule.is_open
    assert rule.offer(151.0, 10)            # at --seconds exactly
    assert not rule.is_open
    assert rule.length == 51.0 and rule.counted == 3
    assert not rule.offer(200.0, 12)        # closed stays closed
    assert rule.counted == 3


def test_window_may_run_one_boundary_longer():
    rule = core.WindowRule(51.0)
    rule.open(0.0, 0)
    assert not rule.offer(50.0, 2)
    assert rule.offer(86.0, 3)              # an iteration of 36 s straddles
    assert rule.length == 86.0 and rule.counted == 3


def test_early_end_closes_where_it_ended():
    rule = core.WindowRule(51.0)
    rule.open(10.0, 40)
    rule.close(30.0, 260)                   # a certified gap
    assert rule.length == 20.0 and rule.counted == 220


class FakeOpt:
    def __init__(self):
        self._iter = 0
        self.W = np.zeros((2, 3))
        self.xbars = np.ones((2, 3))
        self.local_x = np.ones((2, 5))
        self.pri_res = np.array([0.0, 1e-4])
        self.dua_res = np.array([0.0, 1e-5])

    def step(self, n):
        self._iter += n
        self.W = self.W + n
        self.local_x = self.local_x + n


def test_watch_counts_boundaries_and_keeps_every_single_step():
    now = [0.0]
    watch = observe.HubWatch(clock=lambda: now[0])
    assert watch.boundary() is False        # before Iter0: nothing to read
    opt = FakeOpt()
    watch.on_iter0(opt)
    assert watch.rescued0.tolist() == [True, False]
    seen = []
    watch.on_boundary = lambda w, t, it: seen.append((t, it)) or it >= 18
    now[0] = 1.0
    opt.step(1)                             # a legacy iteration
    assert watch.boundary() is False
    assert [st["iteration"] for st in watch.steps] == [1]
    assert watch.steps[0]["W_prev"].max() == 0
    assert watch.steps[0]["W"].max() == 1
    now[0] = 2.0
    opt.step(15)                            # a megastep window
    assert watch.boundary() is False
    assert len(watch.steps) == 1            # not a single step
    now[0] = 3.0
    assert watch.boundary() is False        # a linger poll: same iteration
    now[0] = 4.0
    opt.step(1)
    assert watch.boundary() is False
    assert [st["iteration"] for st in watch.steps] == [1, 17]
    now[0] = 4.0
    opt.step(1)
    assert watch.boundary() is True
    assert seen == [(1.0, 1), (2.0, 16), (3.0, 16), (4.0, 17), (4.0, 18)]
    assert watch.t_first_at(16) == 2.0 and watch.t_first_at(17) == 4.0
    assert watch.t_first_at(99) is None


def test_request_seeds_are_distinct_and_repeat():
    from benchmarks.drivers import serve_closed

    a = [serve_closed.request_seed(2**31 + 12345, k) for k in range(50)]
    b = [serve_closed.request_seed(2**31 + 12345, k) for k in range(50)]
    assert a == b and len(set(a)) == 50
    assert a != [serve_closed.request_seed(2**31 + 12346, k)
                 for k in range(50)]
    assert core.data_seed(2**31 + 99) == 2**31 + 99
    assert 0 <= core.data_seed(2**33) < 4_000_000_000

