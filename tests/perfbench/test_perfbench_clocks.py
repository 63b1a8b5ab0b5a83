"""Percentiles, spread, step grouping, and the due-time arithmetic of
the traffic generator, on hand-made schedules."""

import numpy as np
import pytest

from perfbench.harness import arrivals, clocks
from perfbench.runners import serve


def test_percentile_interpolates_between_closest_ranks():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert clocks.percentile(xs, 0) == 10.0
    assert clocks.percentile(xs, 50) == 30.0
    assert clocks.percentile(xs, 100) == 50.0
    assert clocks.percentile(xs, 95) == pytest.approx(48.0)
    rs = np.random.RandomState(0).rand(101)
    for q in (5, 50, 95, 99):
        assert clocks.percentile(rs, q) == pytest.approx(
            np.percentile(rs, q))
    with pytest.raises(ValueError):
        clocks.percentile([], 50)


def test_spread_is_the_quartile_distance_over_the_median():
    # statistics.quantiles(n=4) of 1..6 (exclusive method): 1.75, 3.5, 5.25
    assert clocks.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert clocks.spread([100, 100, 100, 100, 100, 100]) == 0.0


def test_grouped_step_seconds_spans_a_quarter_second():
    stamps = [0.05 * i for i in range(41)]          # 50 ms steps
    assert clocks.grouped_step_seconds(stamps) == pytest.approx(0.05)
    slow = [0.4 * i for i in range(6)]              # longer than a span
    assert clocks.grouped_step_seconds(slow) == pytest.approx(0.4)
    with pytest.raises(ValueError):
        clocks.grouped_step_seconds([1.0])


def test_poisson_arrivals_start_at_zero_and_keep_their_rate():
    t = arrivals.poisson(50.0, 20000, np.random.default_rng(3))
    assert t[0] == 0.0 and np.all(np.diff(t) >= 0)
    assert 20000 / t[-1] == pytest.approx(50.0, rel=0.03)


def test_flash_crowd_compresses_the_burst_window():
    rng = np.random.default_rng(4)
    t = arrivals.flash_crowd(10.0, 4000, rng, at=50.0, factor=4.0,
                             duration=10.0)
    inside = np.sum((t >= 50.0) & (t < 60.0))
    before = np.sum(t < 50.0)
    assert inside / 10.0 == pytest.approx(40.0, rel=0.15)
    assert before / 50.0 == pytest.approx(10.0, rel=0.15)


def test_diurnal_arrivals_are_monotone_and_keep_the_mean_rate():
    t = arrivals.diurnal(20.0, 6000, np.random.default_rng(5), period=30.0,
                         depth=0.5)
    assert np.all(np.diff(t) >= 0)
    assert 6000 / t[-1] == pytest.approx(20.0, rel=0.05)


def test_lengths_are_clipped_whole_numbers():
    spec = {"dist": "lognormal", "median": 384, "sigma": 0.8, "min": 64,
            "max": 1536}
    x = arrivals.lengths(spec, 5000, np.random.default_rng(6))
    assert x.min() >= 64 and x.max() <= 1536 and x.dtype == np.int64
    assert np.median(x) == pytest.approx(384, rel=0.1)
    assert np.all(arrivals.lengths({"dist": "fixed", "value": 7}, 3,
                                   None) == 7)


TRAFFIC = {
    "arrivals": {"process": "poisson", "rate_per_s": 5.0},
    "prompt_len": {"dist": "lognormal", "median": 384, "sigma": 0.8,
                   "min": 64, "max": 1536},
    "output_len": {"dist": "lognormal", "median": 96, "sigma": 0.7,
                   "min": 16, "max": 384},
    "schedule_seed": 23,
}


def test_every_run_offers_the_same_requests_at_the_same_times():
    a = serve.schedule(TRAFFIC, 20.0)
    assert len(a[0]) == len(a[1]) == len(a[2]) == 100
    assert a[0][0] == 0.0 and np.all(np.diff(a[0]) >= 0)
    # the same n requests span every window exactly
    assert a[0][-1] == pytest.approx(20.0 * 99 / 100)
    again = serve.schedule(TRAFFIC, 20.0)
    for x, y in zip(a, again):
        assert np.array_equal(x, y)
    other = serve.schedule(dict(TRAFFIC, schedule_seed=24), 20.0)
    assert not np.array_equal(a[1], other[1])


@pytest.mark.parametrize("rate, n", [(2.5, 50), (5.0, 100), (10.0, 200)])
def test_a_swept_rate_offers_rate_times_seconds_requests(rate, n):
    due, prompts, outputs = serve.schedule(TRAFFIC, 20.0, rate=rate)
    assert len(due) == len(prompts) == len(outputs) == n
    assert due[0] == 0.0 and due[-1] == pytest.approx(20.0 * (n - 1) / n)


def test_the_seed_gives_what_is_inside_the_requests_only():
    sz = {"vocab_size": 32000}
    _, prompts, outputs = serve.schedule(TRAFFIC, 4.0)
    a = serve.make_requests(sz, prompts, outputs, 1)
    b = serve.make_requests(sz, prompts, outputs, 2**31 + 12345)
    assert [r.prompt.size for r in a] == [r.prompt.size for r in b]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    again = serve.make_requests(sz, prompts, outputs, 1)
    assert all(np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, again))


class _Req:
    def __init__(self, n, state="completed"):
        self.tokens = list(range(n))
        self.max_new_tokens = n
        self.state = state


def test_latencies_are_taken_from_due_times_on_a_hand_made_trial():
    due = np.array([0.0, 1.0, 2.0, 9.0])
    reqs = [_Req(3), _Req(2), _Req(2), _Req(2, state="queued")]
    reqs[3].tokens = []
    trial = serve.Trial(4)
    trial.submitted[:] = [0.01, 1.5, 2.0, 9.2]     # the second was late
    trial.slotted[:] = [0.1, 1.6, 2.3, np.nan]
    trial.token_times = [[0.5, 0.6, 0.8], [2.0, 2.2], [9.5, 11.0], []]
    trial.done_at[:] = [0.8, 2.2, 11.0, np.nan]
    trial.queue_depth = [(1.0, 0), (4.0, 0), (6.0, 2), (9.0, 4)]
    s = serve.summarize(trial, reqs, due, seconds=10.0)
    assert s["attempted"] == 4 and s["failed"] == 1
    assert s["completed_share"] == 0.75
    assert sorted(s["ttft_ms"]) == pytest.approx([500.0, 1000.0, 7500.0])
    assert sorted(s["itl_ms"]) == pytest.approx([100, 200, 200, 1500])
    # every token stamped inside the window counts, whoever finished
    assert s["serve_tokens_per_s"] == pytest.approx(6 / 10.0)
    assert sorted(s["late_ms"]) == pytest.approx([0, 10, 200, 500])
    assert sorted(s["queue_wait_ms"]) == pytest.approx([100, 300, 600])
    assert s["queue_depth_halves"] == (0.0, 3.0)
