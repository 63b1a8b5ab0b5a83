"""Each runner end to end at a tiny size on CPU devices, through the
command's own ``main``: the last line's keys, a traced run, and a run
that finds no TPU (test_perfbench_checks.py breaks the timed path).
The harness's chip-only names are replaced as tests/test_chip_smoke.py
replaces the bring-up script's."""

import json
import os

import pytest

from perfbench import run as pbrun
from perfbench.harness import trace as tr

from conftest import (ATC_ONE_PEER, TINY_DECODER, TINY_RESNET, TINY_TRAFFIC,
                      add_cell)

SEED = 2**31 + 4242   # more than 32 signed bits hold


@pytest.fixture
def main(bench_copy, monkeypatch):
    """``run.main`` on the temporary copy, the program's cache helper
    kept from re-pointing this session's JAX."""
    from bluefog_tpu import config

    monkeypatch.setattr(config, "configure_compilation_cache",
                        lambda: "/cache")

    def call(workload, trace=0, seconds=0.5):
        return pbrun.main(["--workload", workload, "--seed", str(SEED),
                           "--seconds", str(seconds), "--trace",
                           str(trace)], root=bench_copy)

    call.root = bench_copy
    return call


def last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def check_record(rec, metrics, count=1):
    assert set(rec) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert rec["correct"] is True and rec["failed"] == 0
    assert rec["attempted"] > 0
    assert set(rec["metrics"]) == set(metrics)
    for m in rec["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert rec["device"]["platform"] == "cpu"
    assert rec["device"]["count"] == count
    assert set(rec["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    # each number compared beside its limit, last on the line
    assert list(rec)[-1] == "checked" and rec["checked"]
    for pair in rec["checked"].values():
        assert set(pair) == {"value", "limit"}
        assert 0 <= pair["value"] <= pair["limit"]


CELLS = {
    "tiny-train": (TINY_DECODER, 1),
    "tiny-train-atc": (TINY_DECODER, 4),
    "tiny-images": (TINY_RESNET, 1),
}


@pytest.mark.parametrize("mix", sorted(CELLS))
def test_a_training_cell_prints_the_contract_line(main, on_cpu, capsys,
                                                  mix):
    config, chips = CELLS[mix]
    add_cell(main.root, "cell", config, mix, TINY_TRAFFIC[mix], chips)
    assert main("cell") == 0
    rec = last_line(capsys)
    check_record(rec, {"setup_s", "train_rate_per_chip"}, chips)
    assert rec["metrics"]["train_rate_per_chip"]["unit"] == "items/s/chip"


# the cells PERF.md lists for later PRs, each as files and entries only:
# (keywords of build_train_step, the reference's exchange file or None
# for one the benchmark has, the name of that exchange)
GRAPH = {"call": "bluefog_tpu.topology:uniform_topology_spec", "args": [
    {"call": "bluefog_tpu.topology:ExponentialTwoGraph",
     "args": ["$ranks"]}]}
LATER_CELLS = {
    "allreduce": (
        {"comm_mode": "gradient_allreduce"}, "allreduce_gradients",
        "import numpy as np\nMIXES = 'gradients'\n"
        "def matrices(n):\n    return [np.full((n, n), 1.0 / n)]\n"),
    "exp2-static": (
        {"comm_mode": "atc", "topology": GRAPH}, "exp2_static",
        "import numpy as np\nMIXES = 'parameters'\n"
        "def matrices(n):\n    w = np.eye(n)\n    k = 1\n"
        "    while k < n:\n        w += np.roll(np.eye(n), k, axis=0)\n"
        "        k *= 2\n    return [w / w.sum(1, keepdims=True)]\n"),
    "bucketed": (
        dict(ATC_ONE_PEER, overlap="bucketed", overlap_buckets=2),
        "one_peer_exp2", None),
}


@pytest.mark.parametrize("which", sorted(LATER_CELLS))
def test_a_listed_later_cell_arrives_as_files_and_entries_only(
        main, on_cpu, capsys, which):
    step, exchange, source = LATER_CELLS[which]
    before = {}
    for base, _, files in os.walk(os.path.join(main.root, "perfbench")):
        for f in files:
            with open(os.path.join(base, f), "rb") as fh:
                before[os.path.join(base, f)] = fh.read()
    if source:
        with open(os.path.join(main.root, "perfbench", "exchanges",
                               f"{exchange}.py"), "x") as fh:
            fh.write(source)
    traffic = dict(TINY_TRAFFIC["tiny-train-atc"], step=step,
                   exchange=exchange)
    add_cell(main.root, "cell", TINY_DECODER, f"tiny-{which}", traffic, 4)
    assert main("cell") == 0
    out = capsys.readouterr().out
    rec = json.loads(out.strip().splitlines()[-1])
    check_record(rec, {"setup_s", "train_rate_per_chip"}, 4)
    assert "mix_abs_gap" in out and "<-- over" not in out
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, f"{path} was edited"


def test_the_serving_cell_prints_the_contract_line(main, on_cpu, capsys,
                                                   monkeypatch):
    add_cell(main.root, "cell", TINY_DECODER, "tiny-serve",
             TINY_TRAFFIC["tiny-serve"])
    assert main("cell", seconds=1.0) == 0
    out, err = capsys.readouterr()
    rec = json.loads(out.strip().splitlines()[-1])
    check_record(rec, {"setup_s", "serve_tokens_per_s", "ttft_p95_ms",
                       "itl_p95_ms"})
    assert rec["attempted"] == 20
    assert "check: logit_gap" in out and "(limit" in out
    assert set(rec["checked"]) == {"logit_gap"}
    assert err.strip().splitlines()[-1].startswith(
        "perfbench check: logit_gap = ")


def synthetic_trace(module: str):
    ms = 1e6
    ops = [("fusion.1", 1 * ms, 4 * ms), ("_flash_fwd_kernel", 4 * ms, 5 * ms),
           ("fusion.2", 6 * ms, 9 * ms)]
    return tr.Trace([tr.DeviceTrace(0, ops, [(module, 1 * ms, 9 * ms)])],
                    [("pb.trace_window", 0.0, 10 * ms),
                     ("pb.step_wait", 0.0, 10 * ms)])


def test_a_traced_training_run_reports_per_layer_metrics_only(
        main, on_cpu, capsys, monkeypatch):
    add_cell(main.root, "cell", TINY_DECODER, "tiny-train",
             TINY_TRAFFIC["tiny-train"])
    monkeypatch.setattr(tr, "find_xplane", lambda d: d)
    monkeypatch.setattr(tr, "load",
                        lambda p: synthetic_trace("jit_train_step(3)"))
    assert main("cell", trace=1) == 0
    rec = last_line(capsys)
    assert "setup_s" not in rec["metrics"]
    assert "train_rate_per_chip" not in rec["metrics"]
    assert set(rec["metrics"]) == {"compile_s", "step_ms.train",
                                   "device_idle_pct.train"}
    assert rec["metrics"]["device_idle_pct.train"]["value"] == \
        pytest.approx(30.0)
    assert rec["device"]["busy_s"] == pytest.approx(0.007)
    assert rec["device"]["window_s"] == pytest.approx(0.010)
    assert rec["breakdown"]["device_ops"][0][0] in ("fusion.1", "fusion.2")
    assert rec["breakdown"]["idle_gaps"] == [
        ["pb.step_wait", pytest.approx(0.003)]]


def test_a_traced_serving_run_reports_per_layer_metrics_only(
        main, on_cpu, capsys, monkeypatch):
    add_cell(main.root, "cell", TINY_DECODER, "tiny-serve",
             TINY_TRAFFIC["tiny-serve"])
    monkeypatch.setattr(tr, "find_xplane", lambda d: d)
    monkeypatch.setattr(tr, "load", lambda p: synthetic_trace(
        "jit__decode_step_prog(5)"))
    assert main("cell", trace=1, seconds=1.0) == 0
    rec = last_line(capsys)
    assert set(rec["metrics"]) == {
        "compile_s", "engine_step_ms", "queue_wait_p95_ms",
        "decode_step_device_ms", "device_idle_pct.serve",
        "loadgen_late_p95_ms"}
    assert rec["metrics"]["decode_step_device_ms"]["value"] == \
        pytest.approx(8.0)


def test_a_run_that_finds_no_tpu_exits_nonzero_and_prints_no_result(
        main, capsys):
    """As the sandbox runs it: JAX finds only the CPU."""
    with pytest.raises(SystemExit) as exc:
        main("mistral7b-train-1chip")
    assert exc.value.code not in (0, None)
    assert "needs a tpu device" in str(exc.value.code)
    out = capsys.readouterr().out
    assert '"metrics"' not in out and '"correct"' not in out


def test_another_count_of_chips_than_the_cell_asks_for_exits_nonzero(
        main, monkeypatch, capsys):
    from perfbench.harness import device

    monkeypatch.setattr(device, "PLATFORM", "cpu")
    with pytest.raises(SystemExit) as exc:
        main("mistral7b-train-1chip")   # asks for 1, the session has 8
    assert "needs 1 chip(s), JAX found 8" in str(exc.value.code)
    assert '"metrics"' not in capsys.readouterr().out


def test_an_unknown_workload_exits_nonzero(main, capsys):
    with pytest.raises(SystemExit) as exc:
        main("no-such-cell")
    assert "no workload" in str(exc.value.code)
