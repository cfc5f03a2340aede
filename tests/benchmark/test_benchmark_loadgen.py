"""The load generator: the schedule is a pure function of the seed, every
seed offers the same sizes and arrivals in another order, the closed and
open loops against a stand-in for the driver, and the reduction of what
clients saw: latency from the due time, lateness, refusals as failures,
and the two rates."""

import pytest

from benchmark.harness import loadgen
from cellkit import CLOSED, OPEN, FakeDriver, run_load


def _plan(s, n):
    return [(s[i].prompt_len, s[i].max_new, s[i].due_s) for i in range(n)]


@pytest.mark.parametrize("traffic", [CLOSED, OPEN], ids=["closed", "open"])
def test_schedule_is_a_pure_function_of_the_seed(traffic):
    n = 30
    a = loadgen.Schedule(traffic, 2 ** 31 + 5, 1.0, 1000)
    b = loadgen.Schedule(traffic, 2 ** 31 + 5, 1.0, 1000)
    c = loadgen.Schedule(traffic, 6, 1.0, 1000)
    assert _plan(a, n) == _plan(b, n)
    assert [a.prompt_tokens(i) for i in range(5)] == \
        [b.prompt_tokens(i) for i in range(5)]
    assert _plan(a, n) != _plan(c, n)
    assert a.prompt_tokens(0) != c.prompt_tokens(0)
    assert len(a.prompt_tokens(3)) == a[3].prompt_len
    assert all(loadgen.FIRST_TOKEN_ID <= t < 1000
               for t in a.prompt_tokens(3))


def test_every_seed_offers_the_same_sizes_in_another_order():
    pool = CLOSED["pool"]
    a = loadgen.Schedule(CLOSED, 1, 1.0, 1000)
    c = loadgen.Schedule(CLOSED, 2, 1.0, 1000)
    sizes = lambda s: sorted((s[i].prompt_len, s[i].max_new)   # noqa: E731
                             for i in range(pool))
    assert sizes(a) == sizes(c)
    assert all(8 <= p <= 200 and 4 <= m <= 12 for p, m in sizes(a))
    # ... and the open loop the same set of gaps between arrivals.
    oa = loadgen.Schedule(dict(OPEN, ramp_s=0.0), 1, 1.0, 1000)
    oc = loadgen.Schedule(dict(OPEN, ramp_s=0.0), 2, 1.0, 1000)
    assert len(oa) > 10 and abs(len(oa) - len(oc)) < len(oa)
    assert oa[0].due_s != oc[0].due_s


def test_open_schedule_starts_in_the_ramp_and_ends_with_the_window():
    s = loadgen.Schedule(OPEN, 3, 1.0, 1000)
    due = [s[i].due_s for i in range(len(s))]
    assert due == sorted(due)
    assert -0.2 <= due[0] < 0.3 and due[-1] < 1.0
    with pytest.raises(IndexError):
        s[len(s)]
    with pytest.raises(TypeError):
        len(loadgen.Schedule(CLOSED, 3, 1.0, 1000))


def test_bursty_arrivals_keep_the_mean_rate():
    import numpy as np

    rng = np.random.default_rng(0)
    gaps = loadgen.draw_gaps({"process": "gamma", "rate_per_s": 5.0,
                              "cv": 3.0}, 20000, rng)
    assert gaps.mean() == pytest.approx(0.2, rel=0.1)
    assert gaps.std() / gaps.mean() == pytest.approx(3.0, rel=0.15)


def test_closed_loop_never_exceeds_its_callers_and_keeps_them_busy():
    driver = FakeDriver()
    load, wm = run_load(CLOSED, driver)
    assert driver.peak == CLOSED["callers"] == load.max_in_flight
    assert wm["attempted"] > 20 and wm["failed"] == 0
    assert all(len(r.tokens) == r.planned.max_new for r in wm["finished"])
    assert wm["tokens"] > 0 and wm["gaps_ms"]
    # whatever was in flight at the close was cut off, not failed
    assert sum(r.abandoned for r in load.records) <= CLOSED["callers"]


# -- the reduction of what clients saw to the window's numbers --------------


def test_open_loop_times_from_the_due_time_and_reports_lateness():
    load, wm = run_load(OPEN, FakeDriver(), drain=2.0)
    assert wm["attempted"] == sum(
        1 for r in load.records
        if load.t_open <= r.due_at < load.t_open + 0.6)
    assert wm["failed"] == 0 and len(wm["ttft_ms"]) == wm["attempted"]
    assert len(wm["late_ms"]) == wm["attempted"]
    assert all(x >= 0 for x in wm["late_ms"])
    for r in wm["finished"]:
        assert r.first_token_at - r.due_at >= r.sent_at - r.due_at
    # the ramp's requests load the system but are not measured
    assert any(r.due_at < load.t_open for r in load.records)


def test_a_refused_request_is_a_failed_one():
    load, wm = run_load(OPEN, FakeDriver(period=0.05, limit=2), drain=2.0)
    refused = [r for r in load.records if r.status == "refused"]
    assert refused and "AdmissionFull" in refused[0].error
    assert wm["failed"] >= sum(
        1 for r in refused
        if load.t_open <= r.due_at < load.t_open + 0.6) > 0
    assert len(wm["ttft_ms"]) == wm["attempted"] - wm["failed"]


def _four_lanes():
    # 4 lanes commit 8 tokens each every 0.5 s, stamps within 2 ms.
    return [0.1 + 0.5 * k + 0.0005 * lane
            for k in range(8) for lane in range(4) for _ in range(8)]


def test_the_windows_rate_is_every_token_over_the_whole_window():
    class Rec:
        status, abandoned, ended_at = "ok", False, 3.7
        tokens = [0] * 64
        sent_at = 0.0

        class planned:
            max_new = 64

        def __init__(self, times):
            self._times = times
            self.first_token_at = times[0]

        def token_times(self):
            return self._times

    times = _four_lanes()
    recs = [Rec(times[i::4]) for i in range(4)]
    wm = loadgen.window_metrics(recs, 0.55, 2.65, "closed")
    # commits at 0.6, 1.1, .. 3.1 fall inside [0.55, 3.2): 6 x 32 tokens
    assert wm["tokens"] == 6 * 32
    # ... over all 2.65 s: a stall at an edge would show
    assert wm["tokens"] / 2.65 == pytest.approx(72.45, abs=0.01)
    assert wm["committed"][0] == pytest.approx(64.0)


def test_the_committed_rate_is_taken_between_commits():
    times = _four_lanes()
    clusters = loadgen.commit_clusters(times)
    assert len(clusters) == 8 and all(c[2] == 32 for c in clusters)
    # window [0.55, 3.2): commits at 0.6 .. 3.1 lie inside (6 of them)
    rate, tokens, span, commits = loadgen.committed_rate(times, 0.55, 3.2)
    assert commits == 6 and tokens == 5 * 32
    assert span == pytest.approx(2.5, abs=1e-9)
    assert rate == pytest.approx(64.0)
    # moving the edges inside the same commit period changes nothing
    assert loadgen.committed_rate(times, 0.3, 3.55)[0] == pytest.approx(64.0)
    assert loadgen.committed_rate(times, 0.55, 0.9) is None
