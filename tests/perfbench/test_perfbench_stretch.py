"""The traced stretch of a serving run is the unit: it always closes
(``runners/serve.py:open_loop`` leaves no hook unfired by any way out),
its two hooks are built once for both serve runners (``trace_hooks``),
and what a reader divides by its device time is counted inside it
(``program_trace.CounterWindow``)."""

import inspect
import types

import numpy as np
import pytest

from perfbench.harness import clocks, loader, program_trace as pt
from perfbench.harness import trace as tr
from perfbench.runners import serve, serve_gap_share

from conftest import REPO, counter_window

STEP_S = 0.01


def near(want):
    return pytest.approx(want, abs=1e-4)


class Request:
    def __init__(self, n_tokens):
        self.prompt = np.zeros(5, np.int32)
        self.max_new_tokens, self.tokens = n_tokens, []
        self.slot, self.done, self.state = None, False, "queued"


class Engine:
    """Every step takes ``STEP_S`` of the clock and gives each submitted
    request one token; a ``stuck`` engine gives none, ever."""

    def __init__(self, clock, stuck=False):
        self.clock, self.stuck, self.held = clock, stuck, []
        self.scheduler = types.SimpleNamespace(queue_depth=0)

    def submit(self, r):
        r.slot = len(self.held)
        self.held.append(r)

    def step(self):
        self.clock.t += STEP_S
        for r in self.held:
            if not r.done and not self.stuck:
                r.tokens.append(7)
                if len(r.tokens) == r.max_new_tokens:
                    r.done, r.slot, r.state = True, None, "completed"


class Clock:
    """The host's clock and its sleep, by hand: time passes in engine
    steps, sleeps and hooks alone."""

    def __init__(self):
        self.t = 100.0

    def sleep(self, s):
        # a sleep takes time, as a real one does, also where rounding
        # leaves the loop a billionth short of a due time
        self.t += max(s, 1e-6)


@pytest.fixture
def loop(monkeypatch):
    """``open_loop`` over three requests due at 0.0, 0.05 and 0.15 s for
    2, 3 and 4 tokens, on a clock by hand: the schedule drains at 0.19."""
    clock = Clock()
    monkeypatch.setattr(clocks, "now", lambda: clock.t)
    monkeypatch.setattr(serve, "time", clock)

    def call(hooks=None, seconds=1.0, drain_s=0.5, stuck=False):
        server = types.SimpleNamespace(engine=Engine(clock, stuck),
                                       spans=clocks.Spans())
        requests = [Request(n) for n in (2, 3, 4)]
        trial = serve.open_loop(server, requests, [0.0, 0.05, 0.15],
                                seconds, drain_s, hooks)
        return trial, requests

    call.clock = clock
    return call


def hook(loop, log, name, lasts=0.0):
    def fn():
        log.append(name)
        loop.clock.t += lasts    # the profiler stalls the loop
    return fn


def fields(trial):
    return {k: (v.tolist() if isinstance(v, np.ndarray) else v)
            for k, v in vars(trial).items() if k != "fired"}


def test_the_loop_without_hooks_returns_what_it_returned(loop):
    trial, requests = loop()
    assert trial.fired == [] and trial.rejected == 0
    assert all(r.done for r in requests)
    assert trial.submitted.tolist() == near([0.0, 0.05, 0.15])
    # a token a step of 10 ms from the step after the request's arrival;
    # the loop sleeps from 0.02 to 0.05 and from 0.08 to 0.15
    assert trial.token_times[0] == near([0.01, 0.02])
    assert trial.token_times[1] == near([0.06, 0.07, 0.08])
    assert trial.token_times[2] == near([0.16, 0.17, 0.18, 0.19])
    assert trial.done_at.tolist() == near([0.02, 0.08, 0.19])
    assert trial.slotted.tolist() == near([0.01, 0.06, 0.16])
    assert len(trial.step_spans) == 9
    assert [n for _, n in trial.live_tokens] == [
        6, 0, 6, 7, 0, 6, 7, 8, 0]


@pytest.mark.parametrize("t_on", [0.06, 0.5])
def test_a_schedule_that_drains_first_still_fires_both_hooks(loop, t_on):
    """The stop hook's time (1.0 s) never comes: the schedule drains at
    0.19 s.  Both hooks have run when the loop returns, in order, once
    each, and the clock stood still while they did: but for ``fired``
    the trial is the one a loop without hooks observes."""
    log = []
    trial, requests = loop([(1.0, hook(loop, log, "stop", 2.0)),
                            (t_on, hook(loop, log, "start", 3.0))])
    assert log == ["start", "stop"]
    assert all(r.done for r in requests)
    started = 0.06 if t_on == 0.06 else 0.19
    assert trial.fired == near([started, 0.19])
    assert loop.clock.t == near(100.0 + 0.19 + 5.0)
    bare, _ = loop()
    assert fields(trial) == near(fields(bare))


def test_the_clock_stands_still_while_a_hook_runs(loop):
    """A hook of 5 s at 0.1 s, in the sleep before the request due at
    0.15 s: it is submitted at 0.15 s of the window's clock, not 5.1."""
    log = []
    trial, _ = loop([(0.03, hook(loop, log, "a", 5.0)),
                     (0.17, hook(loop, log, "b", 7.0))])
    assert log == ["a", "b"]
    assert trial.fired == near([0.05, 0.17])
    assert trial.submitted.tolist() == near([0.0, 0.05, 0.15])
    assert trial.done_at[2] == near(0.19)
    assert loop.clock.t == near(100.0 + 0.19 + 12.0)


def test_the_drain_limit_leaves_no_hook_behind_either(loop):
    """An engine that never finishes: the loop gives up past ``seconds +
    drain_s`` = 0.5 s, and the hook whose time had not come runs then."""
    log = []
    trial, requests = loop([(0.1, hook(loop, log, "a")),
                            (0.3, hook(loop, log, "b")),
                            (9.0, hook(loop, log, "c", 1.0))],
                           seconds=0.3, drain_s=0.2, stuck=True)
    assert log == ["a", "b", "c"]
    assert not any(r.done for r in requests)
    assert trial.fired[:2] == near([0.1, 0.3])
    assert 0.5 < trial.fired[2] <= 0.5 + 2 * STEP_S


def test_the_stretch_of_live_tokens_ends_where_the_trace_closed(loop):
    log = []
    trial, _ = loop([(0.06, hook(loop, log, "start")),
                     (1.0, hook(loop, log, "stop"))])
    # the samples stamped from 0.06 (the hook's time) to the drain
    assert serve.mean_live_tokens(trial) == near(
        np.mean([6, 7, 0, 6, 7, 8, 0]))
    bare, _ = loop()
    assert serve.mean_live_tokens(bare) == near(
        np.mean([6, 0, 6, 7, 0, 6, 7, 8, 0]))


# ------------------------------------------------------------------ #
# the hooks, once
# ------------------------------------------------------------------ #
def test_the_two_serve_runners_build_their_hooks_in_one_place():
    assert serve_gap_share.trace_hooks is serve.trace_hooks
    assert "start_trace" in inspect.getsource(serve.trace_hooks)
    assert "start_trace" not in inspect.getsource(serve.run)
    assert "start_trace" not in inspect.getsource(serve_gap_share)
    for run in (serve.run, serve_gap_share.run):
        assert "trace_hooks(trace_dir, seconds)" in inspect.getsource(run)


@pytest.mark.parametrize("seconds, t_on", [(40.0, 36.0), (1.0, 0.5),
                                           (6.0, 3.0)])
def test_the_stretch_is_the_windows_last_four_seconds(seconds, t_on):
    hooks, counters = serve.trace_hooks("unused", seconds)
    assert [t for t, _ in hooks] == [t_on, seconds]
    assert counters.opened is None and counters.closed is None


def test_the_hooks_trace_a_stretch_and_snapshot_the_counters(tmp_path):
    """The real hooks on the CPU: the profile is written, the window's
    span is in it, and the counter grew by what was counted between the
    two hooks."""
    from bluefog_tpu.observe import get_registry

    name = "bf_test_stretch_hooks_total"
    counter = get_registry().counter(name, kind="a")
    counter.inc(5)
    hooks, counters = serve.trace_hooks(str(tmp_path), 8.0)
    hooks[0][1]()
    counter.inc(3)
    hooks[1][1]()
    counter.inc(11)
    assert counters.delta(name, kind="a") == 3.0
    assert pt.counter_value(name, kind="a") == 19.0
    trace = tr.load(tr.find_xplane(str(tmp_path)))
    assert "pb.trace_window" in {n for n, _, _ in trace.spans}


# ------------------------------------------------------------------ #
# a counter gets a window
# ------------------------------------------------------------------ #
def test_snapshots_at_the_two_edges_give_the_difference():
    from bluefog_tpu.observe import get_registry

    name = "bf_test_counter_window_total"
    reg = get_registry()
    reg.counter(name).inc(3)
    reg.counter(name, stage="a").inc(10)
    window = pt.CounterWindow()
    assert window.delta(name) is None            # no stretch was traced
    window.open()
    assert window.delta(name) is None            # nor one that is open
    reg.counter(name).inc(4)
    reg.counter(name, stage="b").inc(2)          # first published inside
    window.close()
    reg.counter(name).inc(100)                   # after the stretch
    assert window.delta(name) == 4.0
    assert window.delta(name, stage="a") == 0.0
    assert window.delta(name, stage="b") == 2.0
    assert window.delta(name, stage="c") is None     # never published
    assert window.delta("bf_test_no_such_total") is None
    assert pt.counter_value(name) == 107.0       # the process's stays
    ctx = {"counter_window": window}
    assert pt.counter_delta(ctx, name) == 4.0
    assert pt.counter_delta({}, name) is None
    assert pt.counter_delta({"counter_window": None}, name) is None
    assert pt.registry_metric(name, stage="c") is None   # asked, not made


def test_a_ratio_over_the_stretch_and_over_the_process(monkeypatch):
    process = {"bf_a_total": 300.0, "bf_steps_total": 100.0}
    monkeypatch.setattr(pt, "counter_value",
                        lambda name, **labels: process.get(name))
    ctx = {"counter_window": counter_window(
        {"bf_a_total": 20.0, "bf_steps_total": 10.0})}
    assert pt.stretch_and_process(ctx, "bf_steps_total", "bf_a_total") \
        == (2.0, 3.0)
    assert pt.stretch_and_process({}, "bf_steps_total", "bf_a_total") \
        == (None, 3.0)
    # a stretch with no step, a counter the program lacks
    still = {"counter_window": counter_window(
        {"bf_a_total": 0.0, "bf_steps_total": 0.0})}
    assert pt.stretch_and_process(still, "bf_steps_total", "bf_a_total") \
        == (None, 3.0)
    assert pt.stretch_and_process(ctx, "bf_steps_total", "bf_b_total") \
        == (None, None)


@pytest.mark.parametrize("traced", [True, False])
def test_the_batch_of_a_decode_step_is_the_stretchs_where_one_was_traced(
        monkeypatch, traced):
    reader = loader.load_cell("falcon-h1-34b-serve-chat-bursts",
                              REPO).layer_metric("decode_slots_per_step")
    process = {"bf_serving_decode_slots_total": 19.9 * 2000,
               "bf_serving_decode_steps_total": 2000.0}
    monkeypatch.setattr(pt, "counter_value",
                        lambda name, **labels: process.get(name))
    ctx = {"counter_window": counter_window(
        {"bf_serving_decode_slots_total": 12.6 * 300,
         "bf_serving_decode_steps_total": 300.0}) if traced else None}
    assert reader.reduce(None, None, ctx) is None        # off the chip
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    assert reader.reduce(None, None, ctx) == pytest.approx(
        12.6 if traced else 19.9)
    assert pt.slots_line(ctx) == (
        f"{'12.6' if traced else 'no'} decoding slots a step in the "
        "traced stretch, 19.9 over the process")
