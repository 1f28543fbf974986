"""Window arithmetic: rates over the whole window, tails over every
request due in it, served or not."""

import numpy as np
import pytest

from bench.cell import end_to_end
from bench.lib import check
from bench.lib.replay import Record, Replay, Run, Step, percentile
from bench.lib.traffic import Request

E2E = [{"name": n, "unit": "u"} for n in
       ("output_tok_s", "prompt_tok_s", "ttft_p95_ms", "itl_p95_ms",
        "setup_s")]


def rec(i, due, plen, times, done=True):
    r = Record(Request(i, due, np.zeros(plen, np.int32), len(times) or 1))
    r.times = list(times)
    r.tokens = [0] * len(times)
    r.done = done
    return r


def values(run):
    return {k: v["value"] for k, v in end_to_end(run, E2E, 7.0).items()}


def test_rates_use_the_whole_window():
    # 4 tokens in a 10 s window, 2 of them in its first second: the rate
    # is over all 10 s, not the stretch with tokens in it.
    run = Run(10.0, [rec(0, 0.0, 100, [0.5, 0.9]),
                     rec(1, 0.0, 300, [9.5, 9.9]),
                     rec(2, 0.0, 50, [-1.0, 10.5])], [])
    v = values(run)
    assert v["output_tok_s"] == pytest.approx(4 / 10)
    # Prompt tokens count where the prefill finished in the window.
    assert v["prompt_tok_s"] == pytest.approx(400 / 10)
    assert v["setup_s"] == 7.0


def test_tails_include_requests_not_yet_served():
    served = [rec(i, float(i), 10, [i + 0.1, i + 0.2]) for i in range(19)]
    late = rec(19, 5.0, 10, [], done=False)
    run = Run(20.0, served + [late], [], drain_end=65.0)
    v = values(run)
    ttfts = [100.0] * 19 + [(65.0 - 5.0) * 1e3]
    assert v["ttft_p95_ms"] == pytest.approx(percentile(ttfts, 95))
    assert v["ttft_p95_ms"] > 100.0 * 1.5


def test_itl_gaps_inside_the_window_only():
    run = Run(10.0, [rec(0, 0.0, 10, [-0.5, 0.5, 1.0, 9.0, 11.0])], [])
    v = values(run)
    # Gaps (0.5, 1.0) and (1.0, 9.0): the one from set-up and the one
    # past the close are out.
    assert v["itl_p95_ms"] == pytest.approx(percentile([500.0, 8000.0], 95))


def test_percentile_matches_numpy():
    xs = list(np.random.default_rng(0).exponential(size=101))
    for q in (50, 95, 99):
        assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert percentile([], 95) is None


def test_metrics_not_in_the_cell_stay_out():
    run = Run(10.0, [rec(0, 0.0, 10, [1.0, 2.0])], [Step(0, 0, 1, [0])])
    got = end_to_end(run, [{"name": "output_tok_s", "unit": "tokens/s"}],
                     1.0)
    assert set(got) == {"output_tok_s"}


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class Engine:
    """Admits every request at once and emits one token a step for each;
    a step takes ``dt`` seconds of the fake clock."""

    def __init__(self, clock, dt=0.25):
        self.clock, self.dt, self.live, self.new = clock, dt, {}, []

    def submit(self, prompt, max_new, on_token):
        rid = len(self.live) + len(self.new) + 1000
        self.new.append((rid, [max_new, on_token]))
        return rid

    def step(self):
        self.clock.t += self.dt
        admitted = [rid for rid, _ in self.new]
        self.live.update(self.new)
        self.new = []
        decoded = []
        for rid, st in list(self.live.items()):
            if st[0] > 0:
                st[0] -= 1
                decoded.append(rid)
                st[1](rid, 1, st[0] == 0)
        return {"admitted": admitted, "decoded": decoded}


def test_lead_is_served_before_the_window_opens(monkeypatch):
    clock = Clock()
    monkeypatch.setattr("time.sleep", lambda s: setattr(clock, "t",
                                                        clock.t + s))
    opened = []
    reqs = [Record(Request(i, due, np.zeros(4, np.int32), 8))
            for i, due in enumerate([-3.0, -1.0, 0.5, 2.0])]
    rp = Replay(Engine(clock), clock=clock)
    run = rp.window(reqs, 4.0, on_open=lambda: opened.append(clock.t))
    # The window opens once the lead (3 s of arrivals) has been served.
    assert opened and run.opened == opened[0] >= 100.0 + 3.0
    lead, inside = reqs[:2], reqs[2:]
    assert all(r.submit_t < 0 for r in lead)
    assert all(r.submit_t >= r.req.due_s >= 0 for r in inside)
    # The lead's requests hold tokens on both sides of the opening; only
    # those in the window count toward its rate.
    assert min(reqs[0].times) < 0 < max(reqs[1].times)
    assert all(st.t0 >= 0 for st in run.steps)
    v = values(run)
    in_window = sum(1 for r in reqs for t in r.times if 0 <= t <= 4.0)
    assert v["output_tok_s"] == pytest.approx(in_window / 4.0)


def test_check_samples_requests_the_window_served():
    lead_only = rec(0, -40.0, 10, [-39.0, -30.0])
    across = rec(1, -20.0, 10, [-19.0, 2.0])
    inside = [rec(i, 1.0, 10 + i, [1.5, 2.5]) for i in range(2, 6)]
    run = Run(10.0, [lead_only, across] + inside, [])
    got = check.sample(run, 48, 2**31 + 5)
    assert lead_only not in got
    assert sorted(r.req.index for r in got) == [1, 2, 3, 4, 5]
    # The longest always, then a seeded draw of the rest.
    assert got[0] is inside[-1]
    assert len(check.sample(run, 3, 7)) == 3
    assert check.sample(run, 3, 7) == check.sample(run, 3, 7)
