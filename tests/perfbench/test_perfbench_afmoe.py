"""The afmoe configuration in the benchmark: its configuration, traffic
and cell load; the family's tree feeds program and reference; a tiny
cell of the family runs through its runner (``serve_gap_share``: the
serve runner's loop, a check that also limits the share of served
tokens that differ) and the check's control fails; the new readers on a trace built by hand; the bytes of
``moe_decode_step_roofline`` against a count by hand."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import clocks, decode_scopes, loader, trace as tr
from perfbench.harness import program_trace as pt

from conftest import (REPO, add_cell, counter_window, labelled,
                      readers_on_the_chip)

CELL = "trinity-large-serve-mixed-len"
MS = 1e6

TINY_AFMOE = {
    "name": "tiny-afmoe", "source": "test", "family": "afmoe_decoder",
    "item": "token", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 8,
    "num_dense_layers": 2,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"] * 2,
    "sliding_window": 8, "vocab_size": 1024, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "num_experts": 16, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "route_norm": True, "route_scale": 2.448,
    "mup_enabled": True, "initializer_range": 0.2, "router_bias_std": 0.1,
    "reduced": [],
    "cuts": {"serve": {
        "num_hidden_layers": 5, "layers_kept": [0, 4, 5, 6, 7],
        "num_dense_layers": 1, "num_experts": 4, "router_outputs": 16,
        "experts_held_from": 4, "vocab_size": 128,
        "compute_dtype": "float32", "param_dtype": "float32"}},
}
TINY_MIX = {
    "runner": "serve_gap_share", "cut": "serve",
    "engine": {"capacity": 3, "max_len": 64, "prefill_chunk": 4,
               "decode_attn": "auto", "max_queue": 64},
    "arrivals": {"process": "poisson", "rate_per_s": 20.0},
    "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.9,
                   "min": 2, "max": 44},
    "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                   "min": 2, "max": 16},
    "schedule_seed": 5, "drain_s": 60.0, "check_requests": 3,
    "limits": {"logit_gap": {"limit": 1e-3,
                             "why": "float32 against float32"},
               "gap_share": {"limit": 0.1, "why": "the same"}}}


# ------------------------------------------------------------------ #
# the files
# ------------------------------------------------------------------ #
def test_the_new_cell_loads_with_its_files_and_metrics():
    cell = loader.load_cell(CELL, REPO)
    assert cell.chips == 1 and cell.config["family"] == "afmoe_decoder"
    assert cell.traffic["runner"] == "serve_gap_share"
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"}
    names = {m["name"] for m in cell.per_layer}
    assert names >= {"attn_scope_ms.window", "attn_scope_ms.full",
                     "moe_experts_device_ms", "moe_decode_step_roofline",
                     "moe_held_share_pct", "kv_reserved_mib_per_slot",
                     "compile_s", "decode_step_device_ms"}
    assert "decode_step_roofline" not in names
    assert callable(cell.reference().moe_decode_step_bytes)


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_configuration_keeps_the_published_widths():
    cell = loader.load_cell(CELL, REPO)
    config = cell.config
    if os.path.exists(CATALOG):     # the guide's row, where it is at hand
        with open(CATALOG) as fh:
            rows = [json.loads(line) for line in fh if line.strip()]
        published = next(r["config"] for r in rows
                         if r["name"] == "Trinity-Large-Preview")
        for key, value in published.items():
            assert config[key] == value, key
    sz = cell.family().sizes(config, "serve")
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "sliding_window", "route_scale"):
        assert sz[key] == config[key], key
    assert sz["router_outputs"] == config["num_experts"] == 256
    assert (sz["num_experts"], sz["vocab_size"]) == (32, 200192 // 8)
    assert [config["layer_types"][i] for i in sz["layers_kept"]] == \
        ["sliding_attention"] * 4 + ["full_attention"]
    assert set(config["reduced"]) == {"num_hidden_layers", "num_dense_layers",
                                      "num_experts", "vocab_size"}
    # the cell's own arithmetic: 8.64 GB of bf16 weights, 136 MiB a slot
    ref = cell.reference()
    params = jax.eval_shape(lambda: cell.family().make_params(
        sz, jax.random.PRNGKey(0), jnp.bfloat16)[0])
    nbytes = sum(leaf.size * leaf.dtype.itemsize
                 for leaf in jax.tree.leaves(params))
    assert 8.6e9 < nbytes < 8.7e9
    engine = cell.traffic["engine"]
    slot = ref.cache_bytes_per_position(sz) * (
        engine["max_len"] + 4 * (sz["sliding_window"]
                                 + engine["prefill_chunk"]))
    assert slot == 136 * 2 ** 20


def test_the_traffic_is_the_issues_and_fits_the_engine():
    cell = loader.load_cell(CELL, REPO)
    runner, mix = cell.runner(), cell.traffic
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 1.4, "min": 64, "max": 12288}
    assert mix["output_len"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.7, "min": 16, "max": 512}
    assert set(mix["limits"]) == {"logit_gap", "gap_share"}
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= mix["engine"]["max_len"]
    assert mix["engine"]["max_len"] % mix["engine"]["prefill_chunk"] == 0
    _, prompts, outputs = runner.schedule(mix, 40.0)
    assert (prompts > cell.config["sliding_window"]).mean() > 0.1
    # answers longer than serve.py's 384 rows are in the mix, and read
    from perfbench.runners import serve

    assert outputs.max() > serve.CHECK_ROWS


# ------------------------------------------------------------------ #
# a tiny cell of the family through the runner and its check
# ------------------------------------------------------------------ #
def test_a_tiny_cell_of_the_family_is_served_and_correct(bench_copy, on_cpu,
                                                         capsys):
    add_cell(bench_copy, "cell", TINY_AFMOE, "tiny-afmoe-serve", TINY_MIX)
    cell = loader.load_cell("cell", bench_copy)
    result = cell.runner().run(cell, 2147494999, 1.0, False,
                               jax.devices()[:1], clocks.Spans(),
                               clocks.now(), "/unused")
    assert result["failed"] == 0 and result["correct"] is True
    out = capsys.readouterr().out
    assert "check: logit_gap" in out
    # the check's longest request crossed the window and the ring's wrap
    assert result["attempted"] >= 10


def test_the_control_of_the_tiny_cell_is_not_correct(bench_copy, on_cpu):
    add_cell(bench_copy, "cell", TINY_AFMOE, "tiny-afmoe-serve", TINY_MIX)
    cell = loader.load_cell("cell", bench_copy)
    runner, family = cell.runner(), cell.family()
    sz = family.sizes(cell.config, "serve")
    params = jax.jit(lambda k: family.make_params(sz, k, jnp.float32)[0])(
        jax.random.PRNGKey(3))
    engine = family.serving_engine(sz, cell.traffic, params)
    requests = runner.make_requests(sz, [30, 9], [8, 8], 7)
    for r in requests:
        engine.submit(r)
    engine.run()
    limits = cell.traffic["limits"]
    sound, ok = runner.compare(runner.readings(runner.position_gaps(
        cell, sz, params, requests, [0, 1])), limits)
    assert ok and sound["logit_gap"][0] < 1e-3
    control, ok = runner.compare(runner.readings(runner.position_gaps(
        cell, sz, params, requests, [0, 1], control=True)), limits)
    assert not ok
    # fp8 differs at many positions, not at one: the share alone fails it
    assert control["gap_share"][0] > limits["gap_share"]["limit"]


def test_the_checks_numbers_from_gaps_by_hand():
    runner = loader.load_module(REPO, "runners", "serve_gap_share")
    limits = {"logit_gap": {"limit": 1.0}, "gap_share": {"limit": 0.25}}
    few = runner.readings(np.array([0.0, 0.0, 0.0, 0.9, 0.0, 0.0]))
    assert few == {"logit_gap": 0.9, "gap_share": pytest.approx(1 / 6)}
    assert runner.compare(few, limits) == (
        {"logit_gap": (0.9, 1.0), "gap_share": (few["gap_share"], 0.25)},
        True)
    # the same widest gap at half the positions: the maximum cannot tell
    many = runner.readings(np.array([0.3, 0.0, 0.9, 0.0, 0.2, 0.0]))
    assert many["logit_gap"] == 0.9 and not runner.compare(many, limits)[1]
    assert not runner.compare(runner.readings(np.array([1.2, 0, 0, 0, 0])),
                              limits)[1]
    # nothing served, nothing passes
    assert not runner.compare(runner.readings(np.zeros(0)), limits)[1]


# ------------------------------------------------------------------ #
# the readers
# ------------------------------------------------------------------ #
def hand_trace():
    """Two whole executions of the decode program (10-30 and 50-70) and
    one the window cuts (95-105); a prefill chunk between them whose
    operations carry the same scopes and must not count."""
    ops = [("%fusion.1 = f32[] fusion()", 10 * MS, 14 * MS),   # window
           ("%fusion.2 = f32[] fusion()", 14 * MS, 17 * MS),   # full
           ("%while.3 = () while()", 10 * MS, 30 * MS),        # container
           ("%fusion.4 = f32[] fusion()", 17 * MS, 27 * MS),   # experts
           ("%fusion.5 = f32[] fusion()", 27 * MS, 29 * MS),   # no scope
           ("%fusion.1 = f32[] fusion()", 35 * MS, 45 * MS),   # prefill
           ("%fusion.1 = f32[] fusion()", 50 * MS, 56 * MS),
           ("%fusion.2 = f32[] fusion()", 56 * MS, 57 * MS),
           ("%fusion.4 = f32[] fusion()", 57 * MS, 69 * MS),
           ("%fusion.1 = f32[] fusion()", 95 * MS, 99 * MS)]
    modules = [("jit__decode_step_prog(7)", 10 * MS, 30 * MS),
               ("jit__prefill_chunk_prog(3)", 35 * MS, 45 * MS),
               ("jit__decode_step_prog(7)", 50 * MS, 70 * MS),
               ("jit__decode_step_prog(7)", 95 * MS, 105 * MS)]
    tf_ops = {0: {
        "%fusion.1 = f32[] fusion()":
            "jit(_decode_step_prog)/while/body/vmap(Afmoe)/layer_1/"
            "attention/bf.attn.window/dot_general",
        "%fusion.2 = f32[] fusion()":
            "jit(_decode_step_prog)/while/body/vmap(Afmoe)/layer_4/"
            "attention/bf.attn.full/reduce_max",
        "%fusion.4 = f32[] fusion()":
            "jit(_decode_step_prog)/while/body/vmap(Afmoe)/layer_2/moe/"
            "bf.moe.experts/dot_general",
        "%fusion.5 = f32[] fusion()":
            "jit(_decode_step_prog)/while/body/vmap(Afmoe)/norm/mul"}}
    trace = tr.Trace([tr.DeviceTrace(0, ops, modules)],
                     [("pb.trace_window", 0.0, 100 * MS)])
    return trace, tf_ops


def test_scopes_are_read_over_whole_decode_executions_only():
    trace, tf_ops = hand_trace()
    runs = decode_scopes.decode_executions(trace)
    assert runs == [(10 * MS, 30 * MS), (50 * MS, 70 * MS)]
    scopes = decode_scopes.by_scope(trace, tf_ops, runs)
    ms = {k: sum(v.values()) / MS for k, v in scopes.items()}
    assert ms == {"bf.attn.window": 4 + 6, "bf.attn.full": 3 + 1,
                  "bf.moe.experts": 10 + 12, None: 2}


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(f)/vmap(Afmoe)/layer_1/attention/bf.attn.window/dot", "bf.attn.window"),
    ("jit(f)/layer_2/moe/bf.moe.router/bf.moe.experts/x", "bf.moe.experts"),
    ("jit(f)/bf.forward_backward/jvp(Llama)/layer_1/dot", None),
    (None, None),
])
def test_the_innermost_dotted_scope_names_an_operation(tf_op, scope):
    assert decode_scopes.scope_of(tf_op) == scope


def test_the_readers_read_nothing_off_the_chip_or_without_scopes(
        monkeypatch):
    trace, _ = hand_trace()
    cell = loader.load_cell(CELL, REPO)
    ctx = {"serve": {}, "traffic": cell.traffic, "peaks": None,
           "reference": cell.reference(), "sizes": {}}
    for name in ("attn_scope_ms.window", "attn_scope_ms.full",
                 "moe_experts_device_ms", "moe_decode_step_roofline",
                 "moe_held_share_pct", "kv_reserved_mib_per_slot"):
        assert cell.layer_metric(name).reduce(trace, None, ctx) is None
    # on the chip, with a trace of a program that writes no such scope
    monkeypatch.setattr(pt, "on_chip", lambda: True)

    class Run:
        tf_ops = {0: {}}
        kept = {}

        def keep(self, key, make):
            return make()

    monkeypatch.setattr(pt, "for_run", lambda f: Run())
    assert cell.layer_metric("attn_scope_ms.window").reduce(
        trace, None, ctx) is None


def test_the_scope_metrics_of_a_run_with_scopes(monkeypatch, capsys):
    trace, tf_ops = hand_trace()
    cell = loader.load_cell(CELL, REPO)
    monkeypatch.setattr(pt, "on_chip", lambda: True)

    class Run:
        kept = {}

        def keep(self, key, make):
            if key not in self.kept:
                self.kept[key] = make()
            return self.kept[key]

    Run.tf_ops = tf_ops
    run = Run()
    monkeypatch.setattr(pt, "for_run", lambda f: run)
    read = lambda name: cell.layer_metric(name).reduce(trace, None, {})
    assert read("attn_scope_ms.window") == pytest.approx(5.0)
    assert read("attn_scope_ms.full") == pytest.approx(2.0)
    assert read("moe_experts_device_ms") == pytest.approx(11.0)
    assert capsys.readouterr().out.count("by scope") == 1


def test_the_counter_metrics(monkeypatch):
    cell = loader.load_cell(CELL, REPO)
    counters = {("bf_moe_assignments_total", "true"): 30.0,
                ("bf_moe_assignments_total", "false"): 210.0}
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    monkeypatch.setattr(pt, "counter_value", lambda name, **labels:
                        counters.get((name, labels.get("held"))))
    assert cell.layer_metric("moe_held_share_pct").reduce(
        None, None, {}) == pytest.approx(12.5)
    counters.clear()
    assert cell.layer_metric("moe_held_share_pct").reduce(
        None, None, {}) is None

    class Gauge:
        def __init__(self, value):
            self.value = value

    gauges = {"window": Gauge(24 * 72 * 2 ** 20),
              "full": Gauge(24 * 64 * 2 ** 20)}
    monkeypatch.setattr(pt, "registry_metric", lambda name, **labels:
                        gauges.get(labels["kind"]))
    ctx = {"serve": {}, "traffic": cell.traffic}
    assert cell.layer_metric("kv_reserved_mib_per_slot").reduce(
        None, None, ctx) == pytest.approx(136.0)
    gauges.clear()      # a program that sets no such gauge
    assert cell.layer_metric("kv_reserved_mib_per_slot").reduce(
        None, None, ctx) is None


ATTENDED = "bf_serving_attended_positions_total"


def step_counts(steps, hit, window, full, slots):
    """Counters of ``steps`` decode steps over 4 expert layers: ``hit``
    held experts a layer a step, ``window`` + ``full`` attended
    positions and ``slots`` decoding slots a step."""
    return {"bf_serving_decode_steps_total": steps,
            "bf_serving_decode_slots_total": slots * steps,
            "bf_moe_layer_steps_total": 4.0 * steps,
            "bf_moe_experts_hit_total": hit * 4 * steps,
            labelled(ATTENDED, kind="window"): window * steps,
            labelled(ATTENDED, kind="full"): full * steps}


def test_the_decode_steps_roofline_counts_in_the_traced_stretch(
        monkeypatch, capsys):
    """The stretch's steps hit 9.5 experts a layer and attend 100,000
    positions where the process's mean is 19 and 200,000.  The reader
    divides the stretch's calls, so it takes the stretch's counts; the
    process's would read past 100% and raise on a program that is
    right."""
    trace, tf_ops = hand_trace()
    cell = loader.load_cell(CELL, REPO)
    sz = cell.family().sizes(cell.config, "serve")
    ref = cell.reference()
    reader = cell.layer_metric("moe_decode_step_roofline")
    stretch = step_counts(50.0, 9.5, 60_000.0, 40_000.0, 3.0)
    process = step_counts(900.0, 19.0, 120_000.0, 80_000.0, 6.0)
    readers_on_the_chip(monkeypatch, tf_ops, process)
    ctx = {"serve": {}, "traffic": cell.traffic, "sizes": sz,
           "reference": ref, "peaks": {"hbm_bytes_per_s": 819e9},
           "counter_window": counter_window(stretch)}
    # the two whole calls of the hand-made trace take 20 ms each
    nbytes = ref.moe_decode_step_bytes(sz, 9.5, 100_000.0)
    assert reader.reduce(trace, None, ctx) == pytest.approx(
        100 * nbytes / 819e9 / 20e-3)
    out = capsys.readouterr().out
    assert "3.0 decoding slots a step in the traced stretch, 6.0 over " \
        "the process" in out
    assert "9.50 held experts hit a layer a step and 100000 attended " \
        "positions a step there (19.00 and 200000 over the process)" in out
    # calls at which the stretch's bytes are 70% of the peak: the
    # process's counts pass 100%
    seconds = nbytes / 819e9 / 0.7
    monkeypatch.setattr(reader.tr, "module_calls",
                        lambda trace, pattern: [seconds] * 3)
    assert reader.reduce(trace, None, ctx) == pytest.approx(70.0)
    from perfbench.harness.peaks import share_pct
    with pytest.raises(ValueError, match="cannot be right"):
        share_pct(ref.moe_decode_step_bytes(sz, 19.0, 200_000.0) / 819e9,
                  seconds, "the process's counts")
    # a program that counts no expert, and a run that traced no stretch
    bare = {k: v for k, v in stretch.items()
            if not str(k).startswith("bf_moe")}
    assert reader.reduce(trace, None, dict(
        ctx, counter_window=counter_window(bare))) is None
    assert reader.reduce(trace, None,
                         dict(ctx, counter_window=None)) is None


# ------------------------------------------------------------------ #
# the bytes of a decode step
# ------------------------------------------------------------------ #
def test_decode_step_bytes_against_a_count_by_hand():
    cell = loader.load_cell(CELL, REPO)
    sz = cell.family().sizes(cell.config, "serve")
    ref = cell.reference()
    attention = 2 * 3072 * 6144 + 6144 * 3072 + 2 * 3072 * 1024
    assert attention == 62_914_560
    expert = 3 * 3072 * 3072
    by_hand = (5 * attention + 3 * 3072 * 12288
               + 4 * (3072 * 256 + expert + 9.5 * expert)
               + 3072 * 25024)
    assert ref.decode_weight_params(sz, 9.5) == by_hand
    assert ref.cache_bytes_per_position(sz) == 4096
    assert ref.moe_decode_step_bytes(sz, 9.5, 100_000) == \
        2 * by_hand + 100_000 * 4096
    # every held expert hit, every row of every slot attended: what the
    # program reads if it reads everything once: 8.64 GB of weights less
    # the embedding's 0.15 (a lookup), and 3.22 GB of attended cache
    full = ref.moe_decode_step_bytes(
        sz, 32, 24 * (16384 + 4 * 4096))
    assert 8.45e9 + 3.2e9 < full < 8.55e9 + 3.25e9
