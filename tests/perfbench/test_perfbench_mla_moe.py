"""The latent-attention configuration in the benchmark, and the saturated
chat cell beside it: both cells load with their files and metrics; the
configuration keeps the published widths; the traffic is the issue's and
its check reads a request past position 8192; the family's tree feeds
program and reference; a tiny cell of the family runs through its runner
and the check's control fails; the new readers on a trace built by hand;
the bytes of ``moe_decode_step_roofline`` against a count by hand; the
saturated traffic file is the steady one but for its rate and ``what``."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import chunk_scopes, clocks, decode_scopes, loader
from perfbench.harness import program_trace as pt, trace as tr

from conftest import REPO, add_cell

CELL = "mistral-small4-serve-long-prompt"
SATURATED, STEADY = "mistral7b-serve-saturated", "mistral7b-serve-steady"
MS = 1e6
NEW_READERS = ("attn_scope_ms.latent", "chunk_attn_ms.latent",
               "latent_cache_bytes_per_token")

TINY_MLA = {
    "name": "tiny-mla-moe", "source": "test", "family": "mla_moe_decoder",
    "item": "token", "hidden_size": 64, "intermediate_size": 999,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 12, "num_hidden_layers": 6,
    "first_k_dense_replace": 0, "vocab_size": 1024, "rms_norm_eps": 1e-6,
    "rope_interleave": True,
    "rope_parameters": {
        "beta_fast": 4, "beta_slow": 0.25, "factor": 8,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"},
    "n_routed_experts": 16, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "initializer_range": 0.2, "reduced": [],
    "cuts": {"serve": {
        "num_hidden_layers": 3, "n_routed_experts": 4, "router_outputs": 16,
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
    assert cell.chips == 1 and cell.config["family"] == "mla_moe_decoder"
    assert cell.traffic["runner"] == "serve_gap_share"
    assert len(cell.why) <= 200
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"}
    names = {m["name"] for m in cell.per_layer}
    trinity = {m["name"] for m in loader.load_cell(
        "trinity-large-serve-mixed-len", REPO).per_layer}
    assert names == (trinity - {"attn_scope_ms.window", "attn_scope_ms.full"}
                     ) | set(NEW_READERS)
    assert callable(cell.reference().moe_decode_step_bytes)


def test_the_saturated_cell_is_the_steady_one_at_a_higher_rate():
    cell, steady = (loader.load_cell(n, REPO) for n in (SATURATED, STEADY))
    assert cell.chips == 1 and cell.config_name == steady.config_name
    assert len(cell.why) <= 200
    mix, base = dict(cell.traffic), dict(steady.traffic)
    assert mix.pop("what") != base.pop("what")
    rate, knee_share = mix.pop("arrivals"), base.pop("arrivals")
    assert mix == base
    assert rate["process"] == knee_share["process"] == "poisson"
    assert rate["rate_per_s"] > knee_share["rate_per_s"] / 0.8 * 1.1
    # judged on completed tokens a second alone; the tails are printed
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "serve_tokens_per_s"}
    moved = {m["moves"] for m in cell.per_layer}
    assert moved == {"setup_s", "serve_tokens_per_s"}
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in steady.per_layer if m["moves"] in moved}


@pytest.mark.parametrize("name, cells", [
    ("prefill_chunk_device_ms", [STEADY, "trinity-large-serve-mixed-len"]),
    ("moe_tile_fill_pct", ["trinity-large-serve-mixed-len"]),
])
def test_an_append_moves_nothing_of_the_entries_before_it(name, cells):
    """What ``test_perfbench_prefill_chunk.py`` pins, in the form that an
    append keeps: that test wants its two entries to be the list's last
    with the cells they came with, so it is expected to fail from the
    first append on (``tests/conftest.py``) and is not this PR's to
    edit."""
    bench = loader.load_benchmark(REPO)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("prefill_chunk_device_ms")
    assert names[at:] == ["prefill_chunk_device_ms", "moe_tile_fill_pct",
                          *NEW_READERS]
    entry = bench["per_layer"][names.index(name)]
    assert entry["workloads"] == cells + [CELL]


def test_every_text_of_the_benchmark_file_fits_its_limit():
    bench = loader.load_benchmark(REPO)
    texts = [(e["name"], key, e[key])
             for e in bench["configs"] + bench["workloads"]
             for key in ("why", "source") if key in e]
    texts += [(m["name"], "layer", m["layer"]) for m in bench["per_layer"]]
    for name, key, text in texts:
        assert 1 <= len(text) <= 200 and text.isprintable(), (name, key)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_configuration_keeps_the_published_widths():
    cell = loader.load_cell(CELL, REPO)
    config = cell.config
    if os.path.exists(CATALOG):     # the guide's row, where it is at hand
        with open(CATALOG) as fh:
            rows = [json.loads(line) for line in fh if line.strip()]
        published = next(r["config"] for r in rows
                         if r["name"] == "Mistral-Small-4-119B-2603")
        for key, value in published.items():
            assert config[key] == value, key
    sz = cell.family().sizes(config, "serve")
    for key in ("hidden_size", "moe_intermediate_size", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "num_attention_heads", "num_experts_per_tok",
                "n_shared_experts", "rope_parameters", "rms_norm_eps"):
        assert sz[key] == config[key], key
    assert sz["router_outputs"] == config["n_routed_experts"] == 128
    assert (sz["num_hidden_layers"], sz["n_routed_experts"],
            sz["vocab_size"]) == (5, 32, 131072 // 4)
    bench = loader.load_benchmark(REPO)
    entry = next(c for c in bench["configs"]
                 if c["name"] == cell.config_name)
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert len(entry["source"]) <= 200
    # the cell's own arithmetic: 9.13 GB of bf16 weights, 50 MiB a slot
    ref = cell.reference()
    params = jax.eval_shape(lambda: cell.family().make_params(
        sz, jax.random.PRNGKey(0), jnp.bfloat16)[0])
    nbytes = sum(leaf.size * leaf.dtype.itemsize
                 for leaf in jax.tree.leaves(params))
    assert 9.1e9 < nbytes < 9.16e9
    engine = cell.traffic["engine"]
    slot = ref.cache_bytes_per_position(sz) * engine["max_len"] \
        * sz["num_hidden_layers"]
    assert slot == 50 * 2 ** 20
    # the program's own pool says the same, from shapes alone
    cfg = cell.family().model_config(sz).serving_layout(engine["max_len"])
    leaves = jax.eval_shape(lambda: cfg.init_cache(1, engine["max_len"]))
    assert sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(leaves)
               if leaf.ndim == 3) == slot


def test_the_traffic_is_the_issues_and_its_check_passes_position_8192():
    cell = loader.load_cell(CELL, REPO)
    runner, mix = cell.runner(), cell.traffic
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 3072,
                                 "sigma": 1.0, "min": 256, "max": 15360}
    assert mix["output_len"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.7, "min": 16, "max": 512}
    assert mix["engine"] == {"capacity": 32, "max_len": 16384,
                             "prefill_chunk": 512, "decode_attn": "auto",
                             "max_queue": 256}
    assert (mix["drain_s"], mix["check_requests"]) == (30.0, 3)
    assert set(mix["limits"]) == {"logit_gap", "gap_share"}
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= mix["engine"]["max_len"]
    _, prompts, outputs = runner.schedule(mix, 40.0)
    original = cell.config["rope_parameters"][
        "original_max_position_embeddings"]
    assert 0.08 < (prompts > original).mean() < 0.3
    # the check reads the longest finished request: even if only the
    # shorter half of the window's requests finished, one past 8192 is
    # among them
    assert np.sort(prompts + outputs)[len(prompts) // 2:].min() < original \
        < np.sort(prompts)[-4]


# ------------------------------------------------------------------ #
# a tiny cell of the family through the runner and its check
# ------------------------------------------------------------------ #
def test_a_tiny_cell_of_the_family_is_served_and_correct(bench_copy, on_cpu,
                                                         capsys):
    add_cell(bench_copy, "cell", TINY_MLA, "tiny-mla-serve", TINY_MIX)
    cell = loader.load_cell("cell", bench_copy)
    result = cell.runner().run(cell, 2147494999, 1.0, False,
                               jax.devices()[:1], clocks.Spans(),
                               clocks.now(), "/unused")
    assert result["failed"] == 0 and result["correct"] is True
    assert "check: logit_gap" in capsys.readouterr().out
    assert result["attempted"] >= 10


def test_the_control_of_the_tiny_cell_is_not_correct(bench_copy, on_cpu):
    add_cell(bench_copy, "cell", TINY_MLA, "tiny-mla-serve", TINY_MIX)
    cell = loader.load_cell("cell", bench_copy)
    runner, family = cell.runner(), cell.family()
    sz = family.sizes(cell.config, "serve")
    params = jax.jit(lambda k: family.make_params(sz, k, jnp.float32)[0])(
        jax.random.PRNGKey(3))
    # the family's one tree feeds the program and the reference
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
    assert control["gap_share"][0] > limits["gap_share"]["limit"]


# ------------------------------------------------------------------ #
# the readers
# ------------------------------------------------------------------ #
def hand_trace():
    """Two whole executions of the decode program (10-30, 50-70), one
    the window cuts (95-105), and two prefill chunks between them
    (32-48, 72-92) whose operations carry the same scopes."""
    ops = [("%fusion.1 = f32[] fusion()", 10 * MS, 14 * MS),   # latent
           ("%fusion.2 = f32[] fusion()", 14 * MS, 17 * MS),   # absorb
           ("%fusion.4 = f32[] fusion()", 17 * MS, 27 * MS),   # experts
           ("%fusion.5 = f32[] fusion()", 27 * MS, 29 * MS),   # no scope
           ("%while.3 = () while()", 32 * MS, 48 * MS),        # container
           ("%fusion.1 = f32[] fusion()", 32 * MS, 34 * MS),
           ("%fusion.2 = f32[] fusion()", 34 * MS, 44 * MS),
           ("%fusion.4 = f32[] fusion()", 44 * MS, 47 * MS),
           ("%fusion.1 = f32[] fusion()", 50 * MS, 56 * MS),
           ("%fusion.2 = f32[] fusion()", 56 * MS, 57 * MS),
           ("%fusion.4 = f32[] fusion()", 57 * MS, 69 * MS),
           ("%fusion.1 = f32[] fusion()", 72 * MS, 76 * MS),
           ("%fusion.2 = f32[] fusion()", 76 * MS, 90 * MS),
           ("%fusion.1 = f32[] fusion()", 95 * MS, 99 * MS)]
    modules = [("jit__decode_step_prog(7)", 10 * MS, 30 * MS),
               ("jit__prefill_chunk_prog(3)", 32 * MS, 48 * MS),
               ("jit__decode_step_prog(7)", 50 * MS, 70 * MS),
               ("jit__prefill_chunk_prog(3)", 72 * MS, 92 * MS),
               ("jit__decode_step_prog(7)", 95 * MS, 105 * MS)]
    tf_ops = {0: {
        "%fusion.1 = f32[] fusion()":
            "jit(f)/vmap(MlaMoe)/layer_1/attention/bf.attn.latent/wq_a/"
            "dot_general",
        "%fusion.2 = f32[] fusion()":
            "jit(f)/vmap(MlaMoe)/layer_1/attention/bf.attn.latent/"
            "bf.attn.latent_absorb/dot_general",
        "%fusion.4 = f32[] fusion()":
            "jit(f)/vmap(MlaMoe)/layer_2/moe/bf.moe.experts/dot_general",
        "%fusion.5 = f32[] fusion()": "jit(f)/vmap(MlaMoe)/norm/mul"}}
    trace = tr.Trace([tr.DeviceTrace(0, ops, modules)],
                     [("pb.trace_window", 0.0, 100 * MS)])
    return trace, tf_ops


def test_a_nested_scope_is_the_innermost_and_still_under_its_parent():
    nested = "jit(f)/layer_0/attention/bf.attn.latent/" \
        "bf.attn.latent_expand/dot_general"
    assert decode_scopes.scope_of(nested) == "bf.attn.latent_expand"
    found = ({"bf.attn.latent": 1.0, "bf.attn.latent_absorb": 2.0,
              "bf.attn.latent_expand": 0.5, "bf.moe.experts": 7.0}, 3)
    assert chunk_scopes.scopes_ms(found, "bf.attn.latent") == 3.5
    assert chunk_scopes.scopes_ms(found, "bf.attn.window") is None
    assert chunk_scopes.scopes_ms(None, "bf.attn.latent") is None


def test_chunk_executions_are_the_whole_ones_of_the_chunk_program():
    trace, tf_ops = hand_trace()
    runs = chunk_scopes.chunk_executions(trace)
    assert runs == [(32 * MS, 48 * MS), (72 * MS, 92 * MS)]
    scopes = decode_scopes.by_scope(trace, tf_ops, runs)
    assert {k: sum(v.values()) / MS for k, v in scopes.items()} == {
        "bf.attn.latent": 2 + 4, "bf.attn.latent_absorb": 10 + 14,
        "bf.moe.experts": 3}


def test_the_readers_read_nothing_off_the_chip_or_without_scopes(
        monkeypatch):
    trace, _ = hand_trace()
    cell = loader.load_cell(CELL, REPO)
    sz = cell.family().sizes(cell.config, "serve")
    ctx = {"serve": {}, "traffic": cell.traffic, "peaks": None,
           "reference": cell.reference(), "sizes": sz}
    for name in NEW_READERS:
        assert cell.layer_metric(name).reduce(trace, None, ctx) is None
    # on the chip, with a trace of a program that writes no such scope
    # and a registry that holds no such gauge (the parent's)
    monkeypatch.setattr(pt, "on_chip", lambda: True)

    class Run:
        tf_ops = {0: {}}

        def keep(self, key, make):
            return make()

    monkeypatch.setattr(pt, "for_run", lambda f: Run())
    monkeypatch.setattr(pt, "registry_metric", lambda name, **labels: None)
    for name in NEW_READERS:
        assert cell.layer_metric(name).reduce(trace, None, ctx) is None
    # a model whose cache is no latent has no such metric
    assert cell.layer_metric("latent_cache_bytes_per_token").reduce(
        trace, None, dict(ctx, sizes={"num_hidden_layers": 5})) is None


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
    counters = {"bf_serving_latent_expanded_positions_total": 5 * 40960.0,
                "bf_serving_prefill_chunks_total": 10.0}
    monkeypatch.setattr(pt, "counter_value",
                        lambda name, **labels: counters.get(name))
    sz = cell.family().sizes(cell.config, "serve")
    read = lambda name: cell.layer_metric(name).reduce(
        trace, None, {"sizes": sz})
    # a decode step: (4 + 3 + 6 + 1) / 2; a chunk: (2 + 10 + 4 + 14) / 2
    assert read("attn_scope_ms.latent") == pytest.approx(7.0)
    assert read("chunk_attn_ms.latent") == pytest.approx(15.0)
    assert read("moe_experts_device_ms") == pytest.approx(11.0)
    out = capsys.readouterr().out
    assert "bf.attn.latent_absorb 12.000" in out
    assert "4096 cached positions a layer a chunk rebuilt" in out


def test_the_cache_bytes_a_token(monkeypatch):
    cell = loader.load_cell(CELL, REPO)
    sz = cell.family().sizes(cell.config, "serve")
    monkeypatch.setattr(pt, "on_chip", lambda: True)

    class Gauge:
        value = 32 * 50 * 2 ** 20

    monkeypatch.setattr(pt, "registry_metric", lambda name, **labels:
                        Gauge() if labels == {"kind": "full"} else None)
    ctx = {"serve": {}, "traffic": cell.traffic, "sizes": sz,
           "reference": cell.reference()}
    assert cell.layer_metric("latent_cache_bytes_per_token").reduce(
        None, None, ctx) == pytest.approx(640.0)
    assert cell.layer_metric("kv_reserved_mib_per_slot").reduce(
        None, None, ctx) == pytest.approx(50.0)
    # a pool that held expanded keys and values would read 16 KiB
    Gauge.value = 32 * 16384 * 5 * 2 * 32 * (128 + 128)
    assert cell.layer_metric("latent_cache_bytes_per_token").reduce(
        None, None, ctx) == pytest.approx(16384.0)


# ------------------------------------------------------------------ #
# the bytes of a decode step
# ------------------------------------------------------------------ #
def test_decode_step_bytes_against_a_count_by_hand():
    cell = loader.load_cell(CELL, REPO)
    sz = cell.family().sizes(cell.config, "serve")
    ref = cell.reference()
    attention = 4096 * 1024 + 1024 * 4096 + 4096 * 320 + 256 * 6144 \
        + 4096 * 4096
    assert attention == 28_049_408
    expert = 3 * 4096 * 2048
    assert expert == 25_165_824
    by_hand = 5 * (attention + 4096 * 128 + expert + 9.5 * expert) \
        + 4096 * 32768
    assert ref.decode_weight_params(sz, 9.5) == by_hand
    assert ref.cache_bytes_per_position(sz) == 640
    assert ref.moe_decode_step_bytes(sz, 9.5, 100_000) == \
        2 * by_hand + 100_000 * 640
    # every held expert hit, every row of every slot attended: what the
    # program reads if it reads everything once: 9.13 GB of weights less
    # the embedding's 0.27 (a lookup), and 1.68 GB of latent
    full = ref.moe_decode_step_bytes(sz, 32, 32 * 16384 * 5)
    assert 8.8e9 + 1.65e9 < full < 8.9e9 + 1.7e9
