"""The loader: every name in BENCHMARK.json resolves to a file, a bad
name or unit is refused, and a configuration, a traffic mix and a
per-layer metric are each added as new files plus entries."""

import json
import os

import pytest

from perfbench.harness import loader

from conftest import REPO, TINY_DECODER, TINY_TRAFFIC, add_cell


def test_every_name_in_benchmark_json_resolves_to_a_file():
    bench = loader.load_benchmark(REPO)
    assert bench["command"] == ["python3", "perfbench/run.py"]
    for w in bench["workloads"]:
        cell = loader.load_cell(w["name"], REPO)
        assert cell.chips == w["chips"]
        assert callable(cell.runner().run)
        assert callable(cell.family().make_params)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
        for m in cell.per_layer:
            assert callable(cell.layer_metric(m["name"]).reduce)
            assert os.path.isfile(os.path.join(
                REPO, "perfbench", "layer_metrics", m["name"] + ".py"))


def test_benchmark_json_keeps_to_the_contract():
    bench = loader.load_benchmark(REPO)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200
    for c in bench["configs"]:
        cfg = loader.read_json(os.path.join(REPO, c["file"]))
        assert cfg["reduced"] == c["reduced"]
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536


@pytest.mark.parametrize("bad", ["has space", "a,b", "a/b", "", "x" * 65,
                                 "-lead", "tokens per s", "µs"])
def test_a_name_outside_the_allowed_set_is_refused(bad):
    with pytest.raises(loader.BenchmarkError):
        loader.check_name(bad, "test")


@pytest.mark.parametrize("bad", ["tokens per second", "", "µs",
                                 "x" * 17, "a,b"])
def test_a_unit_outside_the_allowed_set_is_refused(bad):
    with pytest.raises(loader.BenchmarkError):
        loader.check_unit(bad, "test")


@pytest.mark.parametrize("good", ["tokens/s", "%", "items/s/chip", "ms"])
def test_the_units_in_use_are_allowed(good):
    assert loader.check_unit(good, "test") == good


def _edit(root, fn):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    fn(bench)
    with open(path, "w") as fh:
        json.dump(bench, fh)


def test_a_bad_metric_name_in_the_file_is_refused(bench_copy):
    _edit(bench_copy, lambda b: b["per_layer"][0].update(name="bad name"))
    with pytest.raises(loader.BenchmarkError):
        loader.load_benchmark(bench_copy)


def test_a_missing_traffic_file_fails_loudly(bench_copy):
    _edit(bench_copy,
          lambda b: b["workloads"][0].update(traffic="no-such-mix"))
    with pytest.raises(loader.BenchmarkError, match="missing file"):
        loader.load_cell(loader.load_benchmark(bench_copy)["workloads"][0][
            "name"], bench_copy)


def test_a_missing_layer_metric_file_fails_loudly(bench_copy):
    _edit(bench_copy, lambda b: b["per_layer"].append(
        {"name": "no_such_metric", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "x", "moves": "setup_s"}))
    with pytest.raises(loader.BenchmarkError, match="no_such_metric"):
        loader.load_cell("mistral7b-train-1chip", bench_copy)


def test_an_unknown_workload_is_refused():
    with pytest.raises(loader.BenchmarkError, match="no workload"):
        loader.load_cell("no-such-cell", REPO)


def test_new_files_and_entries_add_a_cell_and_a_metric(bench_copy):
    """A configuration, a traffic mix and a per-layer metric arrive as
    new files and new entries; no file of the copy is edited."""
    before = {}
    for base, _, files in os.walk(os.path.join(bench_copy, "perfbench")):
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                before[path] = fh.read()
    add_cell(bench_copy, "throwaway", TINY_DECODER, "tiny-train",
             TINY_TRAFFIC["tiny-train"])
    with open(os.path.join(bench_copy, "perfbench", "layer_metrics",
                           "steps_counted.py"), "x") as fh:
        fh.write("def reduce(trace, spans, ctx):\n"
                 "    return float(len(ctx.get('stamps', []))) or None\n")
    _edit(bench_copy, lambda b: b["per_layer"].append(
        {"name": "steps_counted", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "train step builder",
         "moves": "train_rate_per_chip", "workloads": ["throwaway"]}))
    cell = loader.load_cell("throwaway", bench_copy)
    assert cell.config["hidden_size"] == 64
    assert "steps_counted" in [m["name"] for m in cell.per_layer]
    value = cell.layer_metric("steps_counted").reduce(
        None, None, {"stamps": [0.1, 0.2, 0.3]})
    assert value == 3.0
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, f"{path} was edited"
