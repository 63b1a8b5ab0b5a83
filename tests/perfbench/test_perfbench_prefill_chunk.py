"""``prefill_chunk_device_ms`` and ``moe_tile_fill_pct`` (PR 29): the
entries of both; the first reader on a trace built by hand (whole
executions of the prefill-chunk program only, the median; its table by
scope on the chip), on a stretch with no chunk and on a training cell;
the second on counters set by hand, on a program that does not count,
off the chip, and on the counters of a tiny engine whose chunk is longer
than a tile."""

import re

import jax
import numpy as np
import pytest

from bluefog_tpu.models import afmoe
from bluefog_tpu.observe import MetricsRegistry
from bluefog_tpu.serving import Request, ServingEngine
from perfbench.harness import loader, trace as tr
from perfbench.harness import program_trace as pt

from conftest import REPO

CELL = "trinity-large-serve-mixed-len"
SERVE_CELLS = ["mistral7b-serve-steady", CELL]
MS = 1e6


def _reader(name):
    return loader.load_cell(CELL, REPO).layer_metric(name)


@pytest.mark.parametrize("name, layer, better, source, cells", [
    ("prefill_chunk_device_ms", "serving engine", "lower", "device_trace",
     SERVE_CELLS),
    ("moe_tile_fill_pct", "model", "higher", "program_counter", [CELL]),
])
def test_the_entries_of_the_two_metrics(name, layer, better, source, cells):
    bench = loader.load_benchmark(REPO)
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    assert entry["layer"] == layer and entry["moves"] == "itl_p95_ms"
    assert entry["better"] == better and entry["source"] == source
    assert entry["workloads"] == cells
    for cell in cells:
        assert name in {m["name"] for m in
                        loader.load_cell(cell, REPO).per_layer}
    # appended: nothing that was there moved
    assert [m["name"] for m in bench["per_layer"]][-2:] == [
        "prefill_chunk_device_ms", "moe_tile_fill_pct"]


def hand_trace():
    """Three whole executions of the prefill-chunk program (30, 22 and
    24 ms), one the window cuts, and decode steps between them."""
    modules = [("jit__prefill_chunk_prog(3)", 5 * MS, 35 * MS),
               ("jit__decode_step_prog(7)", 35 * MS, 44 * MS),
               ("jit__prefill_chunk_prog(3)", 50 * MS, 72 * MS),
               ("jit__decode_step_prog(7)", 72 * MS, 80 * MS),
               ("jit__prefill_chunk_prog(3)", 100 * MS, 124 * MS),
               ("jit__prefill_chunk_prog(3)", 190 * MS, 215 * MS)]
    ops = [("%fusion.1 = f32[] fusion()", 5 * MS, 11 * MS),     # experts
           ("%while.2 = () while()", 5 * MS, 20 * MS),          # container
           ("%fusion.3 = f32[] fusion()", 20 * MS, 35 * MS),    # full
           ("%fusion.1 = f32[] fusion()", 36 * MS, 40 * MS),    # decode
           ("%fusion.1 = f32[] fusion()", 50 * MS, 53 * MS),
           ("%fusion.3 = f32[] fusion()", 53 * MS, 71 * MS),
           ("%fusion.4 = f32[] fusion()", 71 * MS, 72 * MS),    # no scope
           ("%fusion.3 = f32[] fusion()", 100 * MS, 124 * MS),
           ("%fusion.1 = f32[] fusion()", 190 * MS, 199 * MS)]
    tf_ops = {0: {
        "%fusion.1 = f32[] fusion()":
            "jit(_prefill_chunk_prog)/Afmoe/layer_1/moe/bf.moe.experts/"
            "while/body/dot_general",
        "%fusion.3 = f32[] fusion()":
            "jit(_prefill_chunk_prog)/Afmoe/layer_4/attention/"
            "bf.attn.full/reduce_max",
        "%fusion.4 = f32[] fusion()": "jit(_prefill_chunk_prog)/norm/mul"}}
    trace = tr.Trace([tr.DeviceTrace(0, ops, modules)],
                     [("pb.trace_window", 0.0, 200 * MS)])
    return trace, tf_ops


def test_the_median_of_whole_chunk_executions(capsys):
    trace, _ = hand_trace()
    reader = _reader("prefill_chunk_device_ms")
    assert reader.executions(trace) == [
        (5 * MS, 35 * MS), (50 * MS, 72 * MS), (100 * MS, 124 * MS)]
    assert reader.reduce(trace, None, {"serve": {}}) == pytest.approx(24.0)
    assert "by scope" not in capsys.readouterr().out    # off the chip


def test_a_stretch_with_no_chunk_and_a_training_cell_read_nothing():
    trace, _ = hand_trace()
    reader = _reader("prefill_chunk_device_ms")
    assert reader.reduce(trace, None, {"train": {}}) is None
    decode_only = tr.Trace(
        [tr.DeviceTrace(0, [], [("jit__decode_step_prog(7)", 10 * MS,
                                 19 * MS)])],
        [("pb.trace_window", 0.0, 100 * MS)])
    assert reader.reduce(decode_only, None, {"serve": {}}) is None
    no_device = tr.Trace([], [("pb.trace_window", 0.0, 100 * MS)])
    assert reader.reduce(no_device, None, {"serve": {}}) is None


@pytest.mark.parametrize("scoped", [True, False])
def test_the_chunk_programs_table_by_scope(monkeypatch, capsys, scoped):
    trace, tf_ops = hand_trace()

    class Run:
        pass

    Run.tf_ops = tf_ops if scoped else {0: {}}
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    monkeypatch.setattr(pt, "for_run", lambda f: Run())
    reader = _reader("prefill_chunk_device_ms")
    assert reader.reduce(trace, None, {"serve": {}}) == pytest.approx(24.0)
    out = capsys.readouterr().out
    if not scoped:      # the parent of the PR that wrote the scopes
        assert "[prefill_chunk]" not in out
        return
    rows = {m.group(1): float(m.group(2)) for m in re.finditer(
        r"^\[prefill_chunk\]   (\S+|\(no scope\)) +([0-9.]+)  ", out,
        re.MULTILINE)}
    # over the three whole executions; the loop itself is left out, the
    # decode step's and the cut execution's operations too
    assert rows == {"bf.moe.experts": pytest.approx(3.0),
                    "bf.attn.full": pytest.approx(19.0),
                    "(no scope)": pytest.approx(1 / 3, abs=1e-3)}


def test_the_fill_of_counters_set_by_hand(monkeypatch):
    counters = {"bf_moe_expert_rows_total": 4096.0,
                "bf_moe_expert_assignments_total": 256.0}
    monkeypatch.setattr(pt, "counter_value",
                        lambda name, **labels: counters.get(name))
    read = _reader("moe_tile_fill_pct").reduce
    assert read(None, None, {}) is None             # off the chip
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    assert read(None, None, {}) == pytest.approx(6.25)
    counters["bf_moe_expert_rows_total"] = 0.0      # nothing computed yet
    assert read(None, None, {}) is None
    counters.clear()                 # a program that does not count
    assert read(None, None, {}) is None


def test_the_fill_of_a_tiny_engines_own_counters(monkeypatch):
    from bluefog_tpu import observe

    family = loader.load_module(REPO, "families", "afmoe_decoder")
    sz = {
        "hidden_size": 32, "intermediate_size": 64,
        "moe_intermediate_size": 16, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 16, "num_hidden_layers": 2,
        "num_dense_layers": 1,
        "layer_types": ["sliding_attention", "full_attention"],
        "sliding_window": 8, "vocab_size": 64, "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0, "num_experts": 4, "router_outputs": 8,
        "experts_held_from": 2, "num_experts_per_tok": 2,
        "num_shared_experts": 1, "route_norm": True, "route_scale": 1.0,
        "mup_enabled": True, "initializer_range": 0.2,
        "router_bias_std": 0.1, "compute_dtype": "float32",
        "param_dtype": "float32"}
    params = jax.jit(lambda k: family.make_params(sz, k, "float32")[0])(
        jax.random.PRNGKey(1))
    reg = MetricsRegistry()
    monkeypatch.setattr(observe, "get_registry", lambda: reg)
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    chunk = 2 * afmoe.EXPERT_TILE
    eng = ServingEngine({"params": params}, family.model_config(sz),
                        capacity=2, max_len=2 * chunk, prefill_chunk=chunk,
                        registry=reg)
    rng = np.random.default_rng(2)
    for n in (chunk + 1, 5):
        eng.submit(Request(rng.integers(0, 64, n), 3))
    eng.run()
    fill = _reader("moe_tile_fill_pct").reduce(None, None, {})
    value = lambda name: reg.counter(name, "").value
    rows = value("bf_moe_expert_rows_total")
    assigned = value("bf_moe_expert_assignments_total")
    assert 0 < assigned < rows
    assert fill == pytest.approx(100.0 * assigned / rows)
    # one expert layer; the full chunk's part is whole tiles that hold
    # its assignments: at most one part-full tile an expert
    assert rows >= afmoe.EXPERT_TILE
