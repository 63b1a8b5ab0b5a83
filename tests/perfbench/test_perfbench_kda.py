"""The configuration of two layer kinds in the benchmark: it keeps the
catalog row's numbers key by key; the cut's arithmetic is the trees';
the cell loads with its files and metrics; the traffic is the issue's;
an append moved nothing that was there; a tiny cell of the family goes
through the command and is ``correct``, and is not with the delta term,
the decay or the convolution left out of the PROGRAM, nor with the
reference computed in a lower precision; the byte functions against a
count by hand; the new readers on a trace built by hand, and nothing off
the chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import loader
from perfbench.harness import program_trace as pt, trace as tr

from conftest import (REPO, add_cell, counter_window,
                      readers_on_the_chip)

CELL, XING = "ling3-flash-serve-doc-reasoning", "xing4-serve-long-answer"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MS = 1e6
NEW_READERS = ("attn_scope_ms.kda", "chunk_attn_ms.kda",
               "kda_state_roofline", "kda_chunk_roofline",
               "state_mib_per_slot")
# readers of the four-stream cell that find nothing to read here (no
# residual streams), and one that divides by the configuration's depth
NOT_HERE = {"hc_scope_ms.decode", "hc_scope_ms.chunk", "hc_mix_roofline",
            "hc_mixed_tokens_per_step", "latent_cache_bytes_per_token"}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size"]

TINY_KDA = {
    "name": "tiny-kda-mla-moe", "source": "test",
    "family": "kda_mla_moe_decoder", "item": "token", "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32, "num_attention_heads": 4,
    "q_lora_rank": None, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 8, "head_dim": 8, "rotary_dim": 8,
    "num_hidden_layers": 12, "layer_group_size": 3,
    "first_k_dense_replace": 2, "vocab_size": 128, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "max_position_embeddings": 4096,
    "num_experts": 32, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "norm_topk_prob": True,
    "moe_router_enable_expert_bias": True, "routed_scaling_factor": 2.5,
    "score_function": "sigmoid", "short_conv_kernel_size": 4,
    "kda_lower_bound": -5, "kda_safe_gate": True, "linear_silu": True,
    "group_norm_size": 1,
    "gated_attention_proj_granularity_type": "head_wise",
    "use_nGPT": False, "value_norm": False, "up_proj_norm": False,
    "scale_router_input": False, "use_kda_lora": False,
    "initializer_range": 0.2, "router_bias_std": 0.01, "kda_conv_std": 0.5,
    "kda_a_log_max": 1.386, "kda_dt_bias_std": 1.0, "kda_beta_std": 0.4,
    "reduced": [],
    "cuts": {"serve": {
        "num_hidden_layers": 4, "published_layers": [0, 2, 3, 4],
        "first_k_dense_replace": 1, "num_experts": 16,
        "router_outputs": 32, "experts_held_from": 0,
        "compute_dtype": "float32", "param_dtype": "float32"}},
}
TINY_MIX = {
    "runner": "serve_gap_share", "cut": "serve",
    "engine": {"capacity": 3, "max_len": 64, "prefill_chunk": 4,
               "decode_attn": "auto", "max_queue": 64},
    "arrivals": {"process": "poisson", "rate_per_s": 20.0},
    "prompt_len": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                   "min": 2, "max": 32},
    "output_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "min": 4, "max": 24},
    "schedule_seed": 5, "drain_s": 60.0, "check_requests": 3,
    "limits": {"logit_gap": {"limit": 1e-3,
                             "why": "float32 against float32"},
               "gap_share": {"limit": 0.1, "why": "the same"}}}


# ------------------------------------------------------------------ #
# the files
# ------------------------------------------------------------------ #
def test_the_configuration_keeps_the_catalog_rows_numbers_key_by_key():
    cell = loader.load_cell(CELL, REPO)
    config = cell.config
    if os.path.exists(CATALOG):     # the guide's row, where it is at hand
        with open(CATALOG) as fh:
            rows = [json.loads(line) for line in fh if line.strip()]
        row = next(r for r in rows if r["name"] == "Ling-3.0-flash-VL")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert config[key] == value, key
    sz = cell.family().sizes(config, "serve")
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "head_dim", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "num_experts_per_tok", "n_group", "topk_group",
                "short_conv_kernel_size", "kda_lower_bound",
                "layer_group_size", "routed_scaling_factor", "rope_theta"):
        assert sz[key] == config[key], key
    assert (sz["hidden_size"], sz["head_dim"], sz["moe_intermediate_size"],
            sz["num_experts_per_tok"], sz["q_lora_rank"]) \
        == (2560, 128, 768, 8, None)
    # the cut: depth, the dense layers' count, the share, the slice
    assert {k: config[k] for k in REDUCED} == config["published_counts"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "vocab_size": 157184}
    assert [sz[k] for k in REDUCED] == [7, 1, 128, 39296]
    assert (sz["router_outputs"], sz["experts_held_from"]) == (512, 0)
    assert sz["published_layers"] == [0, 2, 3, 4, 5, 6, 7]
    assert cell.family().layer_types(sz) == cell.reference().layer_types(sz) \
        == ("kda", "kda", "kda", "kda", "latent", "kda", "kda")
    # the floors: a whole period after the dense layer, 8 experts, 1/8
    assert sz["vocab_size"] * 4 == config["vocab_size"]
    bench = loader.load_benchmark(REPO)
    entry = next(c for c in bench["configs"]
                 if c["name"] == cell.config_name)
    assert config["reduced"] == entry["reduced"] == REDUCED
    assert entry["source"].startswith(config["source"])
    assert "layers 0, 2-7 of 42; 128 of 512 experts; 1/4 of the " \
        "vocabulary" in entry["source"] and len(entry["source"]) <= 200
    for key in ("layer_kinds", "qk_norm", "kda_lora", "safe_gate",
                "output_norm_and_gate", "head_gate", "untied_head",
                "kda_draw", "unused_keys", "left_out", "score_function",
                "rotation", "initialisation"):
        assert key in config["assumed"], key
        assert "PLACEHOLDER" not in config["assumed"][key]
    assert "FOUR chips share each layer" in config["deployment"]
    assert "PLACEHOLDER" not in config["cuts"]["serve"]["why"]


def test_the_cuts_arithmetic_is_the_trees():
    """5,232M parameters in 10.48 GB, 30.4 MiB a slot (12.4 of state
    whatever the length, 18 of latent rows), from shapes alone."""
    cell = loader.load_cell(CELL, REPO)
    family, ref = cell.family(), cell.reference()
    sz = family.sizes(cell.config, "serve")
    params = jax.eval_shape(lambda: family.make_params(
        sz, jax.random.PRNGKey(0), jnp.bfloat16)[0])
    count = lambda t: sum(leaf.size for leaf in jax.tree.leaves(t))
    nbytes = sum(leaf.size * leaf.dtype.itemsize
                 for leaf in jax.tree.leaves(params))
    assert count(params) == 5_231_790_016 and nbytes == 10_480_033_536
    assert count(params["layer_0"]["attention"]) == 63_049_888
    assert count(params["layer_4"]["attention"]) == 31_965_696
    assert "feed_forward" in params["layer_0"] \
        and "moe" not in params["layer_0"]
    moe = params["layer_1"]["moe"]
    assert moe["w1"].shape == (128, 2560, 768)
    assert moe["router"].shape == (2560, 512)
    assert moe["router"].dtype == moe["router_bias"].dtype == jnp.float32
    kda = params["layer_1"]["attention"]
    assert kda["conv_q"].shape == (4, 4096) and kda["A_log"].shape == (32,)
    assert all(kda[k].dtype == jnp.float32
               for k in ("conv_q", "A_log", "dt_bias"))
    engine = cell.traffic["engine"]
    cfg = family.model_config(sz).serving_layout(engine["max_len"])
    assert (cfg.state_layers, cfg.latent_layers, cfg.latent_width,
            cfg.held, cfg.n_group, cfg.topk_group) \
        == (6, 1, 576, (0, 128), 8, 4)
    leaves = jax.tree_util.tree_flatten_with_path(jax.eval_shape(
        lambda: cfg.init_cache(1, engine["max_len"])))[0]
    size = lambda prefix: sum(
        leaf.size * leaf.dtype.itemsize for path, leaf in leaves
        if path[-1].key.startswith(prefix))
    assert size("state_") == ref.kda_state_bytes_per_slot(sz) \
        == 6 * (2 * 2 ** 20 + 72 * 2 ** 10)
    assert size("cached_latent") == 18 * 2 ** 20 \
        == ref.cache_bytes_per_position(sz) * engine["max_len"]
    assert ref.kda_state_bytes_per_layer(sz) == 2 * 2 ** 20


def test_the_cell_loads_with_its_files_and_metrics():
    cell = loader.load_cell(CELL, REPO)
    assert cell.chips == 1
    assert cell.config["family"] == "kda_mla_moe_decoder"
    assert cell.traffic["runner"] == "serve_gap_share"
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"}
    names = {m["name"] for m in cell.per_layer}
    xing = {m["name"] for m in loader.load_cell(XING, REPO).per_layer}
    assert names == (xing - NOT_HERE) | set(NEW_READERS)
    for name in names:
        assert callable(cell.layer_metric(name).reduce), name
    ref = cell.reference()
    for function in ("logits", "moe_decode_step_bytes", "kda_step_bytes",
                     "kda_chunk_flops", "kda_chunk_bytes",
                     "kda_state_bytes_per_slot", "cache_bytes_per_position"):
        assert callable(getattr(ref, function)), function
    bench = loader.load_benchmark(REPO)
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"] in NEW_READERS}
    assert all(m["workloads"] == [CELL] for m in new.values())
    assert {n: (m["moves"], m["source"], m["layer"])
            for n, m in new.items()} == {
        "attn_scope_ms.kda": ("itl_p95_ms", "device_trace", "model"),
        "chunk_attn_ms.kda": ("ttft_p95_ms", "device_trace", "model"),
        "kda_state_roofline": ("itl_p95_ms", "device_trace", "model"),
        "kda_chunk_roofline": ("ttft_p95_ms", "device_trace", "model"),
        "state_mib_per_slot": ("serve_tokens_per_s", "program_counter",
                               "serving engine")}
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 2 == len(bench["workloads"]) // 4


def test_the_traffic_is_the_issues():
    cell = loader.load_cell(CELL, REPO)
    runner, mix = cell.runner(), cell.traffic
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 1.2, "min": 128, "max": 14336}
    assert mix["output_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.6, "min": 64, "max": 1536}
    assert mix["engine"] == {"capacity": 64, "max_len": 16384,
                             "prefill_chunk": 512, "decode_attn": "auto",
                             "max_queue": 256}
    assert (mix["drain_s"], mix["check_requests"]) == (30.0, 3)
    assert mix["arrivals"]["process"] == "poisson"
    assert set(mix["limits"]) == {"logit_gap", "gap_share"}
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= mix["engine"]["max_len"]
    # a long document now and then in a queue of ordinary questions
    _, prompts, outputs = runner.schedule(mix, 40.0, rate=4.0)
    assert np.median(prompts) < 1400 and prompts.max() > 8192
    assert 0.05 < (prompts > 4096).mean() < 0.2
    assert 500 < outputs.mean() < 700
    for text in [mix["what"]] + [v["why"] for v in mix["limits"].values()]:
        assert "PLACEHOLDER" not in text


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_the_append_of_this_cell_moved_nothing_that_was_there(kind):
    """Against the benchmark as the commit before this PR left it: the
    names in order, and every ``workloads`` list a prefix of what it is
    now, grown by this cell alone."""
    before = {
        "configs": ["mistral-7b-v0.1", "resnet50", "trinity-large-preview",
                    "mistral-small-4-119b-2603", "xing4.0-29b-a4b"],
        "workloads": ["mistral7b-train-1chip", "resnet50-train-1chip",
                      "mistral7b-serve-steady", "mistral7b-train-atc-4chip",
                      "trinity-large-serve-mixed-len",
                      "mistral-small4-serve-long-prompt",
                      "mistral7b-serve-saturated", XING,
                      "mistral7b-train-allreduce-4chip"],
        "end_to_end": ["setup_s", "train_rate_per_chip",
                       "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"],
    }
    bench = loader.load_benchmark(REPO)
    names = [e["name"] for e in bench[kind]]
    old = set(before["workloads"])
    if kind in ("per_layer", "end_to_end"):
        entries = bench[kind]
        if kind == "per_layer":
            assert names[-5:] == list(NEW_READERS)
            assert len(names) == len(set(names)) == 65
            entries = entries[:-5]
        else:
            assert names == before[kind]
        for m in entries:
            cells = m.get("workloads", [])
            kept = [c for c in cells if c in old]
            assert cells[:len(kept)] == kept, m["name"]
            assert cells[len(kept):] in ([], [CELL]), m["name"]
            # where the four-stream cell reads, this one reads, but for
            # what only that cell has
            if XING in cells and m["name"] not in NOT_HERE:
                assert cells[-1] == CELL, m["name"]
        return
    added = {"configs": ["ling-3.0-flash-vl"], "workloads": [CELL]}[kind]
    assert names == before[kind] + added


def test_the_family_refuses_a_program_without_the_recurrent_mixer(
        monkeypatch):
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec", lambda name, *a: None
        if name == "bluefog_tpu.models.kda" else real(name, *a))
    monkeypatch.setattr(loader, "_MODULES", {})
    with pytest.raises(ImportError, match="models.kda"):
        loader.load_module(REPO, "families", "kda_mla_moe_decoder")


# ------------------------------------------------------------------ #
# a tiny cell of the family through the command and its check
# ------------------------------------------------------------------ #
def test_a_tiny_cell_goes_through_the_command_and_is_correct(
        bench_copy, on_cpu, monkeypatch, capsys):
    from bluefog_tpu import config
    from perfbench import run as pbrun

    monkeypatch.setattr(config, "configure_compilation_cache",
                        lambda: "/cache")
    add_cell(bench_copy, "cell", TINY_KDA, "tiny-kda-serve", TINY_MIX)
    # ``add_cell`` knows the ``serve`` runner by name and takes any other
    # for a training cell: list the copy's cell where this family's own
    # cell, which this runner serves too, is listed (the four-stream cell
    # stood here until PR 48 took ``ttft_p95_ms`` from its end-to-end list)
    path = os.path.join(bench_copy, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None:
            cells[:] = [c for c in cells if c != "cell"] \
                + (["cell"] if CELL in cells else [])
    with open(path, "w") as fh:
        json.dump(bench, fh)
    rc = pbrun.main(["--workload", "cell", "--seed", str(2 ** 31 + 38),
                     "--seconds", "1.0", "--trace", "0"], root=bench_copy)
    out = capsys.readouterr().out
    rec = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and rec["correct"] is True and rec["failed"] == 0
    assert rec["attempted"] >= 10 and "check: logit_gap" in out
    assert set(rec["metrics"]) >= {"setup_s", "serve_tokens_per_s",
                                   "ttft_p95_ms", "itl_p95_ms"}


def _served(cell, params, lengths=(30, 9), budgets=(12, 12)):
    family, runner = cell.family(), cell.runner()
    sz = family.sizes(cell.config, "serve")
    engine = family.serving_engine(sz, cell.traffic, params)
    requests = runner.make_requests(sz, list(lengths), list(budgets), 7)
    for r in requests:
        engine.submit(r)
    engine.run()
    assert all(r.state == "completed" for r in requests)
    return sz, requests


def _tiny(bench_copy, seed=3):
    add_cell(bench_copy, "cell", TINY_KDA, "tiny-kda-serve", TINY_MIX)
    cell = loader.load_cell("cell", bench_copy)
    family = cell.family()
    sz = family.sizes(cell.config, "serve")
    params = jax.jit(lambda k: family.make_params(sz, k, jnp.float32)[0])(
        jax.random.PRNGKey(seed))
    return cell, params


def test_the_reference_in_a_lower_precision_is_not_correct(bench_copy,
                                                           on_cpu):
    cell, params = _tiny(bench_copy)
    runner, limits = cell.runner(), cell.traffic["limits"]
    sz, requests = _served(cell, params)
    sound, ok = runner.compare(runner.readings(runner.position_gaps(
        cell, sz, params, requests, [0, 1])), limits)
    assert ok and sound["logit_gap"][0] < 1e-3
    control, ok = runner.compare(runner.readings(runner.position_gaps(
        cell, sz, params, requests, [0, 1], control=True)), limits)
    assert not ok
    assert control["gap_share"][0] > limits["gap_share"]["limit"]


@pytest.mark.parametrize("left_out", ["delta term", "decay", "convolution"])
def test_a_program_with_a_part_left_out_is_not_correct(
        bench_copy, on_cpu, monkeypatch, left_out):
    """The PROGRAM's recurrent mixer without the delta rule's correction
    (``S = alpha S + beta k v^T``), without the decay, or without the
    convolution's earlier taps, served through the engine: the runner's
    own check, at the tiny cell's limits, says not correct."""
    from bluefog_tpu.models import kda

    cell, params = _tiny(bench_copy)
    runner, limits = cell.runner(), cell.traffic["limits"]
    step, chunked, conv = kda.delta_step, kda.delta_chunked, kda.causal_conv
    if left_out == "delta term":
        def plain(q, k, v, g, beta, state):
            new = jnp.exp(g)[..., None] * state \
                + (beta[..., None] * k)[..., None] * v[..., None, :]
            return jnp.einsum("bhkv,bhk->bhv", new, q), new

        def plain_chunk(q, k, v, g, beta, state):
            def turn(s, x):
                o, s = plain(*x, s)
                return s, o
            xs = [jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)]
            state, o = jax.lax.scan(turn, state, xs)
            return jnp.moveaxis(o, 0, 1), state
        monkeypatch.setattr(kda, "delta_step", plain)
        monkeypatch.setattr(kda, "delta_chunked", plain_chunk)
    elif left_out == "decay":
        monkeypatch.setattr(kda, "delta_step", lambda q, k, v, g, *a:
                            step(q, k, v, jnp.zeros_like(g), *a))
        monkeypatch.setattr(kda, "delta_chunked", lambda q, k, v, g, *a:
                            chunked(q, k, v, jnp.zeros_like(g), *a))
    else:
        def last_tap(x, history, filters, n_live):
            y, kept = conv(x, history, filters, n_live)
            return x.astype(jnp.float32) * filters[-1], kept
        monkeypatch.setattr(kda, "causal_conv", last_tap)
    # programs traced before the patch must not answer for it
    jax.clear_caches()
    try:
        sz, requests = _served(cell, params)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    numbers, ok = runner.compare(runner.readings(runner.position_gaps(
        cell, sz, params, requests, [0, 1])), limits)
    assert not ok, numbers
    assert numbers["gap_share"][0] > limits["gap_share"]["limit"]


# ------------------------------------------------------------------ #
# the bytes and the operations
# ------------------------------------------------------------------ #
def test_the_state_and_weight_bytes_against_a_count_by_hand():
    cell = loader.load_cell(CELL, REPO)
    sz = cell.family().sizes(cell.config, "serve")
    ref = cell.reference()
    state = 32 * 128 * 128 * 4
    assert ref.kda_layers(sz) == 6
    # 28 slots decode: 168 slot-layers, read once and written once
    assert ref.kda_step_bytes(sz, 168.0) == 2 * 168 * state == 704_643_072
    # a chunk of 512 in one layer: 32 heads x (10 x 16 x 128 + 6 x 128^2)
    assert ref.kda_chunk_flops(sz, 512) == 512 * 32 * (20_480 + 98_304)
    assert ref.kda_chunk_bytes(sz, 512) == 512 * 5 * 4096 * 2 + 2 * state
    kda = 6 * 2560 * 4096 + 2560 * 32
    latent = 2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32 \
        + 4096 * 2560
    assert (kda, latent) == (62_996_480, 31_965_184)
    expert, dense = 3 * 2560 * 768, 3 * 2560 * 6144
    held = 6 * kda + latent + dense + 6 * (1 + 45.0) * expert \
        + 2560 * 39296
    by_hand = 2 * held + 4 * 6 * 2561 * 512
    assert ref.decode_step_weight_bytes(sz, 45.0) == by_hand
    assert ref.moe_decode_step_bytes(sz, 45.0, 50_000) \
        == by_hand + 50_000 * 1152
    # every held expert hit: the weights less the embedding's 0.2 GB
    assert 10.2e9 < ref.decode_step_weight_bytes(sz, 128) < 10.3e9


# ------------------------------------------------------------------ #
# the readers
# ------------------------------------------------------------------ #
def hand_trace():
    """Two whole executions of the decode program (10-30, 50-70) and two
    prefill chunks (32-48, 72-92), their operations under the recurrent
    mixer's three scopes, the latent scope and none."""
    ops = [("%fusion.1 = f32[] fusion()", 10 * MS, 12 * MS),   # kda
           ("%fusion.2 = f32[] fusion()", 12 * MS, 15 * MS),   # kda_state
           ("%fusion.3 = f32[] fusion()", 15 * MS, 16 * MS),   # kda_conv
           ("%fusion.4 = f32[] fusion()", 16 * MS, 27 * MS),   # latent
           ("%fusion.1 = f32[] fusion()", 32 * MS, 34 * MS),
           ("%fusion.2 = f32[] fusion()", 34 * MS, 42 * MS),
           ("%fusion.5 = f32[] fusion()", 42 * MS, 47 * MS),   # no scope
           ("%fusion.1 = f32[] fusion()", 50 * MS, 52 * MS),
           ("%fusion.2 = f32[] fusion()", 52 * MS, 57 * MS),
           ("%fusion.2 = f32[] fusion()", 72 * MS, 84 * MS),
           ("%fusion.3 = f32[] fusion()", 84 * MS, 86 * MS)]
    modules = [("jit__decode_step_prog(7)", 10 * MS, 30 * MS),
               ("jit__prefill_chunk_prog(3)", 32 * MS, 48 * MS),
               ("jit__decode_step_prog(7)", 50 * MS, 70 * MS),
               ("jit__prefill_chunk_prog(3)", 72 * MS, 92 * MS)]
    at = "jit(f)/vmap(MlaMoe)/layer_1/attention/bf.attn.kda/"
    tf_ops = {0: {
        "%fusion.1 = f32[] fusion()": at + "wq/dot_general",
        "%fusion.2 = f32[] fusion()": at + "bf.attn.kda_state/mul",
        "%fusion.3 = f32[] fusion()": at + "bf.attn.kda_conv/add",
        "%fusion.4 = f32[] fusion()":
            "jit(f)/vmap(MlaMoe)/layer_4/attention/bf.attn.latent/dot",
        "%fusion.5 = f32[] fusion()": "jit(f)/vmap(MlaMoe)/norm/mul"}}
    trace = tr.Trace([tr.DeviceTrace(0, ops, modules)],
                     [("pb.trace_window", 0.0, 100 * MS)])
    return trace, tf_ops


def _ctx(cell):
    sz = cell.family().sizes(cell.config, "serve")
    return {"serve": {}, "traffic": cell.traffic, "peaks": None,
            "reference": cell.reference(), "sizes": sz}


def test_the_new_readers_read_nothing_off_the_chip_or_on_the_parent(
        monkeypatch):
    trace, _ = hand_trace()
    cell = loader.load_cell(CELL, REPO)
    ctx = _ctx(cell)
    for name in NEW_READERS:
        assert cell.layer_metric(name).reduce(trace, None, ctx) is None
    # on the chip, with a trace of a program that writes no such scope
    # and a registry that sets and counts none of it (the parent's)
    monkeypatch.setattr(pt, "on_chip", lambda: True)

    class Run:
        tf_ops = {0: {}}

        def keep(self, key, make):
            return make()

    monkeypatch.setattr(pt, "for_run", lambda f: Run())
    monkeypatch.setattr(pt, "registry_metric", lambda name, **labels: None)
    monkeypatch.setattr(pt, "counter_value", lambda name, **labels: None)
    ctx["peaks"] = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    for name in NEW_READERS:
        assert cell.layer_metric(name).reduce(trace, None, ctx) is None
    # and a reference that states no such bytes (another family's)
    other = dict(ctx, reference=loader.load_cell(XING, REPO).reference())
    for name in ("kda_state_roofline", "kda_chunk_roofline"):
        assert cell.layer_metric(name).reduce(trace, None, other) is None


def test_the_new_readers_on_a_run_with_scopes_and_counters(monkeypatch,
                                                           capsys):
    trace, tf_ops = hand_trace()
    cell = loader.load_cell(CELL, REPO)
    # 24 decoding slots x 7 recurrent layers a step, in the stretch as
    # over the process
    counters = {"bf_serving_state_steps_total": 168.0 * 50,
                "bf_serving_decode_steps_total": 50.0,
                "bf_serving_decode_slots_total": 24.0 * 50}
    readers_on_the_chip(monkeypatch, tf_ops, counters, 13_025_280)
    ctx = dict(_ctx(cell), peaks={"hbm_bytes_per_s": 819e9,
                                  "bf16_flops_per_s": 197e12},
               counter_window=counter_window(counters))
    read = lambda name: cell.layer_metric(name).reduce(trace, None, ctx)
    # a decode step: (2 + 3 + 1 + 2 + 5) / 2; a chunk: (2 + 8 + 12 + 2) / 2
    assert read("attn_scope_ms.kda") == pytest.approx(6.5)
    assert read("chunk_attn_ms.kda") == pytest.approx(12.0)
    assert read("attn_scope_ms.latent") == pytest.approx(5.5)
    # 704.6 MB a step at 819 GB/s over the (3 + 5) / 2 ms under kda_state
    assert read("kda_state_roofline") == pytest.approx(
        100 * 704_643_072 / 819e9 / 4e-3)
    # six layers' bytes (HBM bound) over the (8 + 12) / 2 ms of a chunk
    nbytes = 6 * (512 * 5 * 4096 * 2 + 2 * 2 ** 21)
    assert read("kda_chunk_roofline") == pytest.approx(
        100 * nbytes / 819e9 / 10e-3)
    assert read("state_mib_per_slot") == pytest.approx(12.421875)
    out = capsys.readouterr().out
    assert "bf.attn.kda_state 4.000" in out and "hbm bound" in out
    assert "the reference states 13025280" in out
    assert "24.0 decoding slots a step in the traced stretch, 24.0 over " \
        "the process" in out
    # a time under the scope too short for the bytes is refused, not capped
    ctx["counter_window"] = counter_window(
        dict(counters, bf_serving_state_steps_total=168.0 * 50 * 100))
    with pytest.raises(ValueError, match="cannot be right"):
        read("kda_state_roofline")
    # and a run that traced no stretch has nothing to divide
    assert cell.layer_metric("kda_state_roofline").reduce(
        trace, None, dict(ctx, counter_window=None)) is None


def test_the_state_roofline_counts_in_the_traced_stretch(monkeypatch,
                                                         capsys):
    """The stretch decodes 12 slots a step where the process's mean is
    24.  The reader divides the stretch's device time, so it takes the
    stretch's slots; the process's would read twice the share, past
    100%, and raise on a program that is right."""
    trace, tf_ops = hand_trace()
    cell = loader.load_cell(CELL, REPO)
    process = {"bf_serving_state_steps_total": 168.0 * 900,
               "bf_serving_decode_steps_total": 900.0,
               "bf_serving_decode_slots_total": 24.0 * 900}
    stretch = {"bf_serving_state_steps_total": 84.0 * 50,
               "bf_serving_decode_steps_total": 50.0,
               "bf_serving_decode_slots_total": 12.0 * 50}
    readers_on_the_chip(monkeypatch, tf_ops, process)
    ref = cell.reference()
    ctx = dict(_ctx(cell), peaks={"hbm_bytes_per_s": 819e9},
               counter_window=counter_window(stretch))
    # the step's device time at which 84 slot-layers are 70% of the peak
    ms = ref.kda_step_bytes(ctx["sizes"], 84.0) / 819e9 / 0.7 * 1e3
    reader = cell.layer_metric("kda_state_roofline")
    monkeypatch.setattr(reader.decode_scopes, "scope_ms",
                        lambda f, t, scope: ms)
    assert reader.reduce(trace, None, ctx) == pytest.approx(70.0)
    assert "12.0 decoding slots a step in the traced stretch, 24.0 over " \
        "the process" in capsys.readouterr().out
    from perfbench.harness.peaks import share_pct
    with pytest.raises(ValueError, match="140.00% of the peak"):
        share_pct(ref.kda_step_bytes(ctx["sizes"], 168.0) / 819e9,
                  1e-3 * ms, "the process's occupancy")
