"""The traffic generator's accounting, on a fake clock: the closed loop's
counts, latencies and window, the horizon loop's sample, and the seeded
scenario plan."""

import pytest

from cellbench import traffic


class FakeService:
    """Serves in batches of ``batch`` every ``period`` seconds of a fake
    clock; ``fail`` holds request indices that end in an error."""

    def __init__(self, clock, period=0.1, batch=8, fail=()):
        self.clock, self.period, self.batch = clock, period, batch
        self.fail = set(fail)
        self.queue, self.done, self.next_id = [], {}, 0

    def now(self):
        return self.clock["t"]

    def submit(self, req):
        t = self.next_id
        self.next_id += 1
        self.queue.append((t, req))
        return t

    def _serve_one_batch(self):
        self.clock["t"] += self.period
        for t, req in self.queue[:self.batch]:
            self.done[t] = req
        del self.queue[:self.batch]

    def wait(self, t):
        while t not in self.done:
            self._serve_one_batch()
        return self._result(t)

    def poll(self, t):
        return self._result(t) if t in self.done else None

    def _result(self, t):
        req = self.done.pop(t)
        if req in self.fail:
            raise RuntimeError(f"scenario {req} failed")
        return ("result", req)


def _run(seconds=1.0, clients=16, **kw):
    clock = {"t": 0.0}
    svc = FakeService(clock, **kw)
    res = traffic.closed_loop(svc.submit, svc.wait, svc.poll,
                              lambda i: i, clients=clients,
                              seconds=seconds, keep=lambda i: i % 5 == 0,
                              clock=svc.now)
    return res, svc


def test_closed_loop_counts_the_window():
    res, svc = _run(seconds=1.05)
    # batches of 8 every 0.1 s: 8 completions at each of 0.1 .. 1.0
    assert len(res.latencies) == 80
    # two batches in flight: each scenario waits its own and one other
    assert set(round(x, 9) for x in res.latencies) <= {0.1, 0.2}
    assert res.failed == 0 and res.errors == []
    # no scenario is sent after the window; the 16 in flight drain
    assert res.drained == 16 and svc.queue == []
    assert res.attempted == 80 + 16
    assert res.window_s == 1.05
    assert sorted(res.kept) == [i for i in range(80) if i % 5 == 0]
    assert all(res.kept[i] == (i, ("result", i)) for i in res.kept)


def test_closed_loop_keeps_clients_in_flight():
    res, svc = _run(seconds=0.55, clients=3, batch=2)
    # 2 of the 3 served every 0.1 s, so each client waits one period or
    # two; no more than 3 ever outstanding
    assert len(res.latencies) == 10
    assert max(res.latencies) <= 0.2 + 1e-9
    assert svc.next_id == res.attempted


def test_closed_loop_counts_failures_and_goes_on():
    res, _ = _run(fail={3, 4})
    assert res.failed == 2 and len(res.errors) == 2
    assert "scenario 3 failed" in res.errors[0]
    assert res.attempted == len(res.latencies) + 2 + res.drained


def test_p95_of_all_values():
    assert traffic.p95(list(range(1, 101))) == pytest.approx(95.05)


def test_horizon_samples_the_drawn_call():
    clock = {"t": 0.0}

    def call(state):
        clock["t"] += 0.25
        return state + 1, {"report": state}

    res = traffic.horizon(call, 0, 2.0, sample_at=0.5,
                          clock=lambda: clock["t"])
    assert res.calls == 8 and res.elapsed_s == pytest.approx(2.0)
    assert res.state == 8
    # the first call starting at or after 1.0 s is the fifth (input 4)
    assert res.sample == (4, 5, {"report": 4})


def test_horizon_makes_at_least_one_call():
    res = traffic.horizon(lambda s: (s, None), 7, 0.0, sample_at=0.0)
    assert res.calls == 1 and res.sample == (7, 7, None)


MIX = {"rates": [0.05, 0.07, 0.09], "pool": 5, "sample_every": 4}


def test_scenario_plan_is_the_seeds_and_the_same_work():
    req_a, keep_a = traffic.scenario_plan(MIX, 2**33 + 1)
    req_b, keep_b = traffic.scenario_plan(MIX, 2**33 + 1)
    req_c, _ = traffic.scenario_plan(MIX, 1)
    a = [req_a(i) for i in range(400)]
    assert a == [req_b(i) for i in range(400)]
    assert a != [req_c(i) for i in range(400)]
    assert {i for i, _ in a} == set(range(5))
    assert {r for _, r in a} == set(MIX["rates"])
    kept = sum(keep_a(i) for i in range(4000))
    assert 800 < kept < 1200
    assert [keep_a(i) for i in range(50)] == [keep_b(i) for i in range(50)]
