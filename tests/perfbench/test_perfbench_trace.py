"""The trace reduction on a trace built by hand, whose busy, idle, gap
and exposed-collective figures are known by construction, and on a
small trace recorded on the chip."""

import os

import pytest

from perfbench.harness import trace as tr

MS = 1e6  # nanoseconds


def hand_trace():
    """Two chips, a 100 ms window.  Chip 0: compute 10-30 and 50-70, a
    collective pair in flight 25-55 (start 25-26, done 45-55), so 20 ms
    of the exchange (30-50) run with no compute; idle 0-10 and 70-100.
    Chip 1: one op 0-100 (never idle) and a synchronous all-reduce
    inside it."""
    ops0 = [("fusion.1", 10 * MS, 30 * MS),
            ("collective-permute-start.3", 25 * MS, 26 * MS),
            ("collective-permute-done.3", 45 * MS, 55 * MS),
            ("fusion.2", 50 * MS, 70 * MS)]
    ops1 = [("fusion.1", 0 * MS, 100 * MS),
            ("%conditional.1 = () conditional(...)", 35 * MS, 65 * MS),
            ("all-reduce.7", 40 * MS, 60 * MS)]
    spans = [("pb.trace_window", 0.0, 100 * MS),
             ("pb.step_dispatch", 0.0, 8 * MS),
             ("pb.step_wait", 8 * MS, 100 * MS),
             ("pb.inner", 72 * MS, 90 * MS)]
    return tr.Trace(
        [tr.DeviceTrace(0, sorted(ops0, key=lambda t: t[1]),
                        [("jit_train_step(1)", 10 * MS, 70 * MS)]),
         tr.DeviceTrace(1, ops1, [("jit_train_step(1)", 0, 100 * MS)])],
        spans)


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.total([(0, 3), (5, 7)]) == 5
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 6)]) == [
        (0, 2), (3, 5), (6, 10)]
    assert tr.subtract([(0, 10), (20, 30)], [(5, 25)]) == [
        (0, 5), (25, 30)]
    assert tr.gaps([(2, 4)], 0, 10) == [(0, 2), (4, 10)]


def test_busy_idle_and_window_of_the_hand_trace():
    t = hand_trace()
    assert t.window == (0.0, 100 * MS)
    busy, window = tr.busy_and_window_s(t)
    # chip 0: 10-30, 45-70 (done op counts as an op), start inside -> 45
    # busy; chip 1: 100
    assert window == pytest.approx(0.100)
    assert busy == pytest.approx((0.045 + 0.100) / 2)
    assert tr.idle_pct(t) == pytest.approx(100 * (1 - 0.0725 / 0.1))


def test_exposed_collective_time_of_the_hand_trace():
    in_flight, exposed = tr.exchange_seconds(hand_trace())
    # chip 0: in flight 25-55 = 30 ms, compute covers 25-30 and 50-55:
    # 20 ms exposed; chip 1: 20 ms in flight, all under fusion.1 (the
    # conditional that holds the all-reduce is no compute of its own)
    assert in_flight == pytest.approx((0.030 + 0.020) / 2)
    assert exposed == pytest.approx((0.020 + 0.0) / 2)


def test_idle_gaps_go_to_the_innermost_covering_span():
    sums = tr.idle_gaps_by_span(hand_trace())
    # chip 0's gaps: 0-10 (dispatch covers 8 of it), 30-45 (wait),
    # 70-100 (wait covers all 30, inner only 18: most cover wins)
    assert sums == pytest.approx({"pb.step_dispatch": 0.010,
                                  "pb.step_wait": 0.045})


def test_ops_by_name_and_module_calls():
    t = hand_trace()
    ops = tr.op_seconds(t)
    assert ops["fusion.1"] == pytest.approx((0.020 + 0.100) / 2)
    assert tr.op_seconds(t, r"^all-reduce") == pytest.approx(
        {"all-reduce.7": 0.010})
    assert tr.module_calls(t, "train_step") == pytest.approx([0.060])
    b = tr.breakdown(t)
    assert b["device_ops"][0][0] == "fusion.1"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_trace_without_a_window_span_uses_the_ops_extent():
    t = hand_trace()
    t.spans = []
    assert t.window == (0.0, 100 * MS)
    with pytest.raises(ValueError):
        tr.Trace([tr.DeviceTrace(0, [], [])], []).window


XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 } }
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(7)" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "pb.trace_window" } }
  event_metadata { key: 2 value { id: 2 name: "other" } }
}
"""


def test_planes_and_lines_of_a_profile_are_read_by_name():
    from jax.profiler import ProfileData

    t = tr.from_profile_data(ProfileData.from_text_proto(XSPACE))
    assert [d.index for d in t.devices] == [0]
    assert [o[0] for o in t.devices[0].ops] == ["fusion.1", "copy.2"]
    assert t.devices[0].modules[0][0] == "jit_step(7)"
    assert [s[0] for s in t.spans] == ["pb.trace_window"]
    busy, window = tr.busy_and_window_s(t)
    assert busy == pytest.approx(3e-6) and window == pytest.approx(5e-6)


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "tiny_train_v5e.xplane.pb.gz")


def test_the_trace_recorded_on_the_chip_reduces_to_known_figures(tmp_path):
    """A few steps of a tiny decoder on one TPU v5e (my chip run, PR 23;
    ``data/record_tiny_trace.py``).  The figures were read off the trace
    by hand (``harness/trace.describe``)."""
    import gzip

    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(RECORDED) as src:
        path.write_bytes(src.read())
    t = tr.load(str(path))
    assert len(t.devices) == 1 and len(t.devices[0].ops) == 10848
    assert t.window == (45646531.0, 67261051.0)     # pb.trace_window
    busy, window = tr.busy_and_window_s(t)
    assert window == pytest.approx(0.02161452)
    assert busy == pytest.approx(0.005623894, rel=1e-6)
    assert tr.idle_pct(t) == pytest.approx(73.98, abs=0.01)
    steps = tr.module_calls(t)                      # jit_wrapped(...)
    assert len(steps) >= 20 and all(2e-4 < d < 3e-4 for d in steps)
    assert tr.module_calls(t, "no_such_program") == []
    # the Pallas kernels: flash forward, dq and dkv backward, 2 layers
    flash = tr.op_seconds(t, tr.PALLAS_KERNEL)
    assert sorted(flash) == [f"attention.{i}" for i in (10, 11, 6, 7, 8, 9)]
    assert all(v > 0 for v in flash.values())
    assert tr.exchange_seconds(t) == (0.0, 0.0)     # one chip
    gaps = tr.idle_gaps_by_span(t)
    assert sum(gaps.values()) == pytest.approx(window - busy, rel=1e-6)
    assert set(gaps) <= {"pb.step_dispatch", "pb.step_wait", "(no span)"}
    b = tr.breakdown(t)
    assert all(" = " not in name for name, _ in b["device_ops"])


FOUR_CHIPS = os.path.join(os.path.dirname(__file__), "data",
                          "atc_4chip_v5e.xplane.pb.gz")


def test_the_four_chip_trace_of_the_atc_cell_reduces_to_known_figures(
        tmp_path):
    """The traced stretch of one run of ``mistral7b-train-atc-4chip``
    (my chip run, PR 23: ``python3 perfbench/run.py --workload
    mistral7b-train-atc-4chip --seed 2147494222 --seconds 10 --trace 1``
    on four TPU v5e; the file is the run's ``*.xplane.pb``, gzipped).
    Every ``collective-permute`` of a step lies inside ``conditional.1``
    (the ``lax.switch`` over the schedule's rounds), so no more can be
    in flight than that container lasts."""
    import gzip

    path = tmp_path / "atc.xplane.pb"
    with gzip.open(FOUR_CHIPS) as src:
        path.write_bytes(src.read())
    t = tr.load(str(path))
    assert [d.index for d in t.devices] == [0, 1, 2, 3]
    steps = tr.module_calls(t)
    assert len(steps) == 14
    assert all(d == pytest.approx(0.3309, abs=5e-4) for d in steps)
    by_chip = tr.exchange_seconds_by_chip(t)
    for in_flight, exposed in by_chip:
        assert 1e3 * in_flight / 14 == pytest.approx(63.2, abs=0.1)
        assert 1e3 * exposed / 14 == pytest.approx(52.15, abs=0.1)
    in_flight, exposed = tr.exchange_seconds(t)
    assert in_flight == pytest.approx(sum(a for a, _ in by_chip) / 4)
    container = tr.op_seconds(t, r"^%?conditional\.1 ")["conditional.1"]
    assert 1e3 * container / 14 == pytest.approx(65.5, abs=0.1)
    assert in_flight < container
    assert tr.idle_pct(t) == pytest.approx(0.162, abs=0.005)
