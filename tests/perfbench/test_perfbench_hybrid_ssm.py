"""The hybrid state-space configuration in the benchmark: it keeps the
catalog row's numbers key by key and cuts depth alone; the cut's
arithmetic is the tree's; the cell loads with its files and metrics; the
traffic is the issue's; an append moved nothing that was there; a tiny
cell of the family goes through the command and is ``correct``, and is
not with the reference computed in a lower precision, nor with the
state-space mixer or the key scale left out of the PROGRAM;
the byte functions against a count by hand; the new readers on a trace
built by hand, and nothing off the chip."""

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

CELL, STEADY = "falcon-h1-34b-serve-chat-bursts", "mistral7b-serve-steady"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MS = 1e6
NEW_READERS = ("attn_scope_ms.ssd", "chunk_attn_ms.ssd",
               "ssd_state_roofline", "ssd_chunk_roofline")
MIB = 2 ** 20

TINY_HYBRID = {
    "name": "tiny-hybrid-ssm", "source": "test",
    "family": "ssm_gqa_parallel_decoder", "item": "token",
    "hidden_size": 48, "intermediate_size": 96, "num_attention_heads": 5,
    "num_key_value_heads": 1, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 128, "rms_norm_eps": 1e-5, "rope_theta": 100000000000,
    "max_position_embeddings": 256, "mamba_n_heads": 4, "mamba_d_head": 8,
    "mamba_d_ssm": 32, "mamba_d_state": 16, "mamba_n_groups": 2,
    "mamba_d_conv": 4, "mamba_chunk_size": 8, "mamba_use_mlp": True,
    "mamba_rms_norm": True, "mamba_norm_before_gate": False,
    "attn_layer_indices": None,
    # 0.02 x sqrt(5120 / 48): the mixers' share of the residual at the
    # published width (tests/test_hybrid_ssm.py)
    "initializer_range": 0.2, "conv_std": 0.5,
    "embedding_multiplier": 5.656854249492381,
    "lm_head_multiplier": 0.0078125, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375,
    "key_multiplier": 0.011048543456039804, "ssm_in_multiplier": 0.25,
    "ssm_out_multiplier": 0.08838834764831845,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "reduced": [],
    "cuts": {"serve": {"compute_dtype": "float32",
                       "param_dtype": "float32"}},
}
TINY_MIX = {
    "runner": "serve", "cut": "serve",
    "engine": {"capacity": 3, "max_len": 64, "prefill_chunk": 8,
               "decode_attn": "auto", "max_queue": 64},
    "arrivals": {"process": "flash_crowd", "rate_per_s": 20.0, "at": 0.2,
                 "factor": 2.5, "duration": 0.2},
    "prompt_len": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                   "min": 2, "max": 32},
    "output_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "min": 4, "max": 24},
    "schedule_seed": 5, "drain_s": 60.0, "check_requests": 3,
    "limits": {"logit_gap": {"limit": 1e-3,
                             "why": "float32 against float32"}}}


# ------------------------------------------------------------------ #
# the files
# ------------------------------------------------------------------ #
def test_the_configuration_keeps_the_catalog_rows_numbers_and_cuts_depth():
    cell = loader.load_cell(CELL, REPO)
    config = cell.config
    if os.path.exists(CATALOG):     # the guide's row, where it is at hand
        with open(CATALOG) as fh:
            rows = [json.loads(line) for line in fh if line.strip()]
        row = next(r for r in rows if r["name"] == "Falcon-H1-34B-Instruct")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert config[key] == value, key
    sz = cell.family().sizes(config, "serve")
    # every published width unchanged; depth alone is cut
    changed = {k for k in config if k not in ("cuts", "assumed", "reduced")
               and sz[k] != config[k]}
    assert changed == {"num_hidden_layers"}
    assert (config["num_hidden_layers"], sz["num_hidden_layers"]) == (72, 6)
    assert (sz["hidden_size"], sz["num_attention_heads"],
            sz["num_key_value_heads"], sz["head_dim"],
            sz["intermediate_size"], sz["mamba_n_heads"], sz["mamba_d_head"],
            sz["mamba_d_state"], sz["mamba_n_groups"], sz["mamba_d_conv"],
            sz["mamba_chunk_size"], sz["vocab_size"]) \
        == (5120, 20, 4, 128, 21504, 32, 128, 256, 2, 4, 128, 261120)
    assert (sz["compute_dtype"], sz["param_dtype"]) \
        == ("bfloat16", "bfloat16")
    bench = loader.load_benchmark(REPO)
    entry = next(c for c in bench["configs"]
                 if c["name"] == cell.config_name)
    assert config["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"]
    for key in ("multipliers", "in_proj_segments", "convolution",
                "mamba_use_mlp", "gated_norm", "heads_of_a_group",
                "rotation", "mamba_expand", "unused_keys", "state_dtype",
                "initialisation"):
        assert key in config["assumed"], key
    assert "66 layers left out lie on further chips" in config["deployment"]
    assert config["published_counts"]["parameters"] \
        == 72 * 430_120_032 + 2 * 261120 * 5120 + 5120
    cfg = cell.family().model_config(sz)
    b = cfg.block
    assert (b.n_layers, b.n_heads, b.n_kv_heads, b.head_dim, b.ffn_dim,
            b.rope_theta, b.norm_eps, b.dim) \
        == (6, 20, 4, 128, 21504, 1e11, 1e-5, 5120)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk, cfg.ssm_inner, cfg.conv_channels) \
        == (32, 128, 256, 2, 4, 128, 4096, 5120)
    assert (cfg.key_multiplier, cfg.ssm_multipliers, cfg.mlp_multipliers) \
        == (config["key_multiplier"], tuple(config["ssm_multipliers"]),
            tuple(config["mlp_multipliers"]))
    with pytest.raises(ValueError, match="published block"):
        cell.family().model_config(dict(sz, mamba_use_mlp=False))


def test_the_cuts_arithmetic_is_the_trees():
    """5,254,594,112 parameters, 10.51 GB in bf16; a slot of 24.2 MiB of
    state and 24.0 MiB of reserved keys and values; 64 slots 3.01 GiB:
    from shapes alone."""
    cell = loader.load_cell(CELL, REPO)
    family, ref = cell.family(), cell.reference()
    sz = family.sizes(cell.config, "serve")
    params = jax.eval_shape(lambda: family.make_params(
        sz, jax.random.PRNGKey(0), jnp.bfloat16)[0])
    count = lambda t: sum(leaf.size for leaf in jax.tree.leaves(t))
    assert count(params) == ref.total_params(sz) == 5_254_594_112
    layer = params["layer_0"]
    assert count(layer) == 430_120_032
    assert count(layer["attention"]) == 31_457_280
    assert count(layer["mamba"]) == 68_351_072
    assert sum(count(layer[w]) for w in ("w1", "w2", "w3")) == 330_301_440
    assert count(params["tok_embeddings"]) + count(params["output"]) \
        == 2_673_868_800
    assert layer["mamba"]["in_proj"]["kernel"].shape == (5120, 9248)
    assert layer["attention"]["wq"]["kernel"].shape == (5120, 2560)
    nbytes = sum(leaf.size * leaf.dtype.itemsize
                 for leaf in jax.tree.leaves(params))
    assert 10.50e9 < nbytes < 10.52e9
    # the mixer's own small parameters stay float32
    assert {k: v.dtype for k, v in layer["mamba"].items()
            if k not in ("in_proj", "out_proj")} == {
        k: jnp.float32 for k in ("A_log", "D", "dt_bias", "conv_kernel",
                                 "conv_bias", "norm")}
    engine = cell.traffic["engine"]
    cfg = family.model_config(sz, max_seq_len=engine["max_len"]) \
        .serving_layout(engine["max_len"], chunk=engine["prefill_chunk"])
    cache = jax.eval_shape(lambda: cfg.init_cache(1, engine["max_len"]))
    leaf = cache["layer_0"]
    assert leaf["mamba"]["state_ssm"].shape == (1, 32, 128, 256)
    assert leaf["mamba"]["state_ssm"].dtype == jnp.float32
    assert leaf["mamba"]["state_conv"].shape == (1, 3, 5120)
    assert leaf["attention"]["cached_key"].shape == (1, 4, 2048, 128)
    size = lambda a: a.size * a.dtype.itemsize
    state = sum(size(c[k]) for c in (cache[f"layer_{i}"]["mamba"]
                                     for i in range(6)) for k in c)
    kv = sum(size(cache[f"layer_{i}"]["attention"][k]) for i in range(6)
             for k in ("cached_key", "cached_value"))
    assert state == ref.state_bytes_per_slot(sz) == 6 * (4 * MIB + 30720)
    assert kv == engine["max_len"] * ref.cache_bytes_per_token(sz) \
        == 24 * MIB
    assert round(state / MIB, 1) == 24.2
    assert 3.0 < engine["capacity"] * (state + kv) / 2 ** 30 < 3.02
    assert cfg.cache_kinds() == {"full": (6, None)}
    assert cfg.state_layers == 6
    assert cfg.state_streamed_steps(10, 64) == 64 * 6


def test_the_cell_loads_with_its_files_and_metrics():
    cell = loader.load_cell(CELL, REPO)
    assert cell.chips == 1
    assert cell.config["family"] == "ssm_gqa_parallel_decoder"
    assert cell.traffic["runner"] == "serve"
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"}
    names = {m["name"] for m in cell.per_layer}
    steady = {m["name"] for m in loader.load_cell(STEADY, REPO).per_layer}
    assert names == steady | set(NEW_READERS) \
        | {"kv_reserved_mib_per_slot", "state_mib_per_slot"}
    for name in names:
        assert callable(cell.layer_metric(name).reduce), name
    ref = cell.reference()
    for function in ("logits", "total_params", "decode_step_bytes",
                     "ssd_step_bytes", "ssd_chunk_flops", "ssd_chunk_bytes",
                     "state_bytes_per_slot", "mm_highest", "mm_control"):
        assert callable(getattr(ref, function)), function
    with open(ref.__file__) as fh:
        assert "bluefog_tpu" not in fh.read().split('"""', 2)[2]
    bench = loader.load_benchmark(REPO)
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"] in NEW_READERS}
    assert all(m["workloads"] == [CELL] for m in new.values())
    assert {n: (m["moves"], m["source"], m["layer"], m["unit"])
            for n, m in new.items()} == {
        "attn_scope_ms.ssd": ("itl_p95_ms", "device_trace", "model", "ms"),
        "chunk_attn_ms.ssd": ("ttft_p95_ms", "device_trace", "model",
                              "ms"),
        "ssd_state_roofline": ("itl_p95_ms", "device_trace", "model", "%"),
        "ssd_chunk_roofline": ("ttft_p95_ms", "device_trace", "model",
                               "%")}
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 2 and len(bench["workloads"]) >= 12
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "flash crowd" in entry["why"]


def test_the_traffic_is_the_issues():
    cell = loader.load_cell(CELL, REPO)
    runner, mix = cell.runner(), cell.traffic
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.8, "min": 64, "max": 1536}
    assert mix["output_len"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.7, "min": 16, "max": 384}
    assert mix["engine"] == {"capacity": 64, "max_len": 2048,
                             "prefill_chunk": 256, "decode_attn": "auto",
                             "max_queue": 512}
    assert (mix["drain_s"], mix["check_requests"], mix["cut"]) \
        == (30.0, 6, "serve")
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] == 1920 \
        <= mix["engine"]["max_len"]
    assert mix["output_len"]["max"] <= runner.CHECK_ROWS
    arrivals = mix["arrivals"]
    assert arrivals["process"] == "flash_crowd"
    assert arrivals["factor"] in (2.5, 2.0)
    # the runner stretches the schedule to span its window exactly, so
    # the file holds the process BEFORE the stretch: what the window
    # offers is 0.6 K outside a burst of factor x 0.6 K for 5 s from 10 s on
    from perfbench.harness import arrivals as arr

    window, knee = 40.0, mix["knee_per_s"]
    due, prompts, outputs = runner.schedule(mix, window)
    n = len(due)
    assert n == round(arrivals["rate_per_s"] * window) <= 512
    nominal = window / (window - (arrivals["factor"] - 1)
                        * arrivals["duration"])
    assert arrivals["rate_per_s"] == pytest.approx(0.6 * knee * nominal,
                                                   rel=1e-3)
    assert arrivals["at"] * nominal == pytest.approx(10.0, abs=0.01)
    assert arrivals["duration"] * nominal == pytest.approx(5.0, abs=0.01)
    raw, _ = arr.fixed_schedule(mix["schedule_seed"], n, arrivals, {})
    stretch = due[-1] / raw[-1]         # of this schedule's own draw
    lo = arrivals["at"] * stretch
    hi = lo + arrivals["duration"] * stretch
    assert abs(lo - 10.0) < 0.5 and abs(hi - lo - 5.0) < 0.25
    inside = int(((due >= lo) & (due < hi)).sum())
    burst = 0.6 * arrivals["factor"] * knee
    assert 0.93 * burst < inside / (hi - lo) < 1.07 * burst
    assert 0.55 * knee < (n - inside) / (window - (hi - lo)) < 0.65 * knee
    assert 330 < np.median(prompts) < 440 and 100 < np.median(outputs) < 150
    assert prompts.max() <= 1536 and outputs.max() <= 384
    assert mix["schedule_seed"] not in (5, 23, 40)      # one of its own
    for text in (mix["what"], mix["who"], mix["limits"]["logit_gap"]["why"]):
        assert "PLACEHOLDER" not in text
    assert "sweep" in mix["what"] and "consumer chat assistant" in mix["who"]


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_the_append_of_this_cell_moved_nothing_that_was_there(kind):
    """Against the benchmark as the commit before this PR left it, as a
    PREFIX (a later append keeps it): the names in order, and every
    ``workloads`` list that was there a prefix of what it is now, grown
    by this cell where the dense serve cell reads and on the two gauges
    of the pool's bytes, and by nothing else."""
    before = {
        "configs": ["mistral-7b-v0.1", "resnet50", "trinity-large-preview",
                    "mistral-small-4-119b-2603", "xing4.0-29b-a4b",
                    "ling-3.0-flash-vl", "ouro-2.6b"],
        "workloads": ["mistral7b-train-1chip", "resnet50-train-1chip",
                      STEADY, "mistral7b-train-atc-4chip",
                      "trinity-large-serve-mixed-len",
                      "mistral-small4-serve-long-prompt",
                      "mistral7b-serve-saturated", "xing4-serve-long-answer",
                      "mistral7b-train-allreduce-4chip",
                      "ling3-flash-serve-doc-reasoning",
                      "ouro-2.6b-serve-short-answer"],
        "end_to_end": ["setup_s", "train_rate_per_chip",
                       "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"],
    }
    bench = loader.load_benchmark(REPO)
    names = [e["name"] for e in bench[kind]]
    old = set(before["workloads"])
    if kind in ("configs", "workloads"):
        n = len(before[kind])
        assert names[:n] == before[kind]
        assert names[n] == {"configs": "falcon-h1-34b-instruct",
                            "workloads": CELL}[kind]
        return
    entries = bench[kind]
    if kind == "per_layer":
        assert names[69:73] == list(NEW_READERS)
        assert names[65:69] == ["loop_scope_ms.decode", "loop_scope_ms.chunk",
                                "loop_cache_streamed_pct",
                                "loop_layer_tokens_per_step"]
        assert len(names) == len(set(names))
        entries = entries[:69]
    else:
        assert names == before[kind]
    for m in entries:
        cells = m.get("workloads", [])
        kept = [c for c in cells if c in old]
        assert cells[:len(kept)] == kept, m["name"]
        reads = STEADY in cells or m["name"] in ("kv_reserved_mib_per_slot",
                                                 "state_mib_per_slot")
        assert (CELL in cells) == reads, m["name"]
        if reads:
            assert cells[len(kept)] == CELL, m["name"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        assert len(fh.read()) < 64 * 1024


def test_the_family_refuses_a_program_without_the_mixer(monkeypatch):
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec", lambda name, *a: None
        if name == "bluefog_tpu.models.hybrid_ssm" else real(name, *a))
    monkeypatch.setattr(loader, "_MODULES", {})
    with pytest.raises(ImportError, match="models.hybrid_ssm"):
        loader.load_module(REPO, "families", "ssm_gqa_parallel_decoder")


# ------------------------------------------------------------------ #
# a tiny cell of the family through the command and its check
# ------------------------------------------------------------------ #
def test_a_tiny_cell_goes_through_the_command_and_is_correct(
        bench_copy, on_cpu, monkeypatch, capsys):
    from bluefog_tpu import config
    from perfbench import run as pbrun

    monkeypatch.setattr(config, "configure_compilation_cache",
                        lambda: "/cache")
    add_cell(bench_copy, "cell", TINY_HYBRID, "tiny-hybrid-serve", TINY_MIX)
    rc = pbrun.main(["--workload", "cell", "--seed", str(2 ** 31 + 45),
                     "--seconds", "1.0", "--trace", "0"], root=bench_copy)
    out = capsys.readouterr().out
    rec = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and rec["correct"] is True and rec["failed"] == 0
    assert rec["attempted"] >= 10 and "check: logit_gap" in out
    assert set(rec["metrics"]) >= {"setup_s", "serve_tokens_per_s",
                                   "ttft_p95_ms", "itl_p95_ms"}


def _tiny(bench_copy, seed=3):
    add_cell(bench_copy, "cell", TINY_HYBRID, "tiny-hybrid-serve", TINY_MIX)
    cell = loader.load_cell("cell", bench_copy)
    family = cell.family()
    sz = family.sizes(cell.config, "serve")
    params = jax.jit(lambda k: family.make_params(sz, k, jnp.float32)[0])(
        jax.random.PRNGKey(seed))
    return cell, sz, params


def _served(cell, sz, params, lengths=(30, 9, 17), budgets=(12, 12, 8)):
    family, runner = cell.family(), cell.runner()
    engine = family.serving_engine(sz, cell.traffic, params)
    requests = runner.make_requests(sz, list(lengths), list(budgets), 7)
    for r in requests:
        engine.submit(r)
    engine.run()
    assert all(r.state == "completed" for r in requests)
    return requests


def test_the_reference_in_a_lower_precision_is_not_correct(bench_copy,
                                                           on_cpu):
    cell, sz, params = _tiny(bench_copy)
    runner = cell.runner()
    limit = cell.traffic["limits"]["logit_gap"]["limit"]
    requests = _served(cell, sz, params)
    sound, read = runner.logit_gaps(cell, sz, params, requests, [0, 1, 2])
    assert sound <= limit and read == 32
    control, _ = runner.logit_gaps(cell, sz, params, requests, [0, 1, 2],
                                   control=True)
    assert control > 10 * limit


@pytest.mark.parametrize("fault", ["no state-space mixer",
                                   "no key_multiplier"])
def test_a_program_with_a_part_left_out_is_not_correct(
        bench_copy, on_cpu, monkeypatch, fault):
    """The PROGRAM with the state-space mixer's output left out of the
    residual, or with the keys unscaled: served through the engine, the
    runner's own check at the tiny cell's limit says not correct.  (The
    check reads how far a SERVED token lies below the reference's best:
    a part that moves the logits by a fiftieth of their deviation and
    flips no greedy token, as the decay does at this size, is held by
    ``tests/test_hybrid_ssm.py``'s logits, not here.)"""
    from bluefog_tpu.models import hybrid_ssm as hs

    cell, sz, params = _tiny(bench_copy)
    runner = cell.runner()
    limit = cell.traffic["limits"]["logit_gap"]["limit"]
    if fault == "no state-space mixer":
        real = hs.SsdMixer.__call__
        monkeypatch.setattr(
            hs.SsdMixer, "__call__",
            lambda self, x, live=None, start=None:
            0.0 * real(self, x, live, start))
    else:
        real = cell.family().model_config
        monkeypatch.setattr(
            cell.family(), "model_config", lambda sz, **kw:
            real(dict(sz, key_multiplier=1.0), **kw))
    # programs traced before the patch must not answer for it
    jax.clear_caches()
    try:
        requests = _served(cell, sz, params)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    gap, _ = runner.logit_gaps(cell, sz, params, requests, [0, 1, 2])
    assert gap > 10 * limit, gap


# ------------------------------------------------------------------ #
# the bytes
# ------------------------------------------------------------------ #
def test_the_state_and_weight_bytes_against_a_count_by_hand():
    cell = loader.load_cell(CELL, REPO)
    sz = cell.family().sizes(cell.config, "serve")
    ref = cell.reference()
    layer = 2 * 5120 * 2560 + 2 * 5120 * 512 + 5120 * 9248 + 4096 * 5120 \
        + 3 * 5120 * 21504
    assert ref.layer_matmul_params(sz) == layer == 430_080_000
    weights = 2 * (6 * layer + 5120 * 261120)
    assert weights == 7_834_828_800
    assert ref.ssd_state_bytes_per_layer(sz) == 4 * MIB
    # a decoding slot's state of a layer read once and written once
    assert ref.ssd_step_bytes(sz, 1) == 8 * MIB
    assert ref.ssd_step_bytes(sz, 6 * 64) == 3_221_225_472
    # a caller that knows only the live positions gets ONE slot's state
    assert ref.decode_step_bytes(sz, 0) == weights + 6 * 8 * MIB
    assert ref.decode_step_bytes(sz, 30000.0) \
        == weights + 6 * 8 * MIB + 30000 * 12288
    assert ref.decode_step_bytes(sz, 30000.0, decoding_slots=64) \
        == weights + 3_221_225_472 + 30000 * 12288
    # 9.6 ms of weights, 3.9 ms of 64 slots' state at 819 GB/s
    assert 9.5e-3 < weights / 819e9 < 9.6e-3
    assert 3.9e-3 < 3_221_225_472 / 819e9 < 4.0e-3
    # a chunk of 256 in a layer: C B^T a group, the masked product and
    # the two products with the state a head
    flops = 256 * (2 * 128 * 256 * 2 + 2 * 128 * 128 * 32
                   + 4 * 128 * 256 * 32)
    assert ref.ssd_chunk_flops(sz, 256) == flops == 1_375_731_712
    assert ref.ssd_chunk_bytes(sz, 256) \
        == 256 * (2 * 4096 + 1024 + 32) * 2 + 8 * MIB
    assert ref.ssd_layers(sz) == 6


# ------------------------------------------------------------------ #
# the readers
# ------------------------------------------------------------------ #
def hand_trace():
    """Two whole executions of the decode program (10-30, 50-70) and two
    prefill chunks (32-48, 72-92), their operations under the state-space
    mixer (its projections, its step, its scan), the attention and
    outside both."""
    ops = [("%fusion.1 = f32[] fusion()", 10 * MS, 12 * MS),   # in_proj
           ("%fusion.2 = f32[] fusion()", 12 * MS, 15 * MS),   # the step
           ("%fusion.4 = f32[] fusion()", 15 * MS, 20 * MS),   # attention
           ("%fusion.5 = f32[] fusion()", 20 * MS, 27 * MS),   # head
           ("%fusion.1 = f32[] fusion()", 32 * MS, 34 * MS),
           ("%fusion.3 = f32[] fusion()", 34 * MS, 42 * MS),   # the scan
           ("%fusion.4 = f32[] fusion()", 42 * MS, 46 * MS),
           ("%fusion.1 = f32[] fusion()", 50 * MS, 51 * MS),
           ("%fusion.2 = f32[] fusion()", 51 * MS, 56 * MS),
           ("%fusion.4 = f32[] fusion()", 56 * MS, 58 * MS),
           ("%fusion.1 = f32[] fusion()", 72 * MS, 74 * MS),
           ("%fusion.3 = f32[] fusion()", 74 * MS, 86 * MS)]
    modules = [("jit__decode_step_prog(7)", 10 * MS, 30 * MS),
               ("jit__prefill_chunk_prog(3)", 32 * MS, 48 * MS),
               ("jit__decode_step_prog(7)", 50 * MS, 70 * MS),
               ("jit__prefill_chunk_prog(3)", 72 * MS, 92 * MS)]
    layer = "jit(f)/vmap(HybridSsm)/layer_0/"
    tf_ops = {0: {
        "%fusion.1 = f32[] fusion()":
            layer + "mamba/bf.attn.ssd/in_proj/dot_general",
        "%fusion.2 = f32[] fusion()":
            layer + "mamba/bf.attn.ssd/bf.attn.ssd_state/mul",
        "%fusion.3 = f32[] fusion()":
            layer + "mamba/bf.attn.ssd/bf.attn.ssd_chunk/while/body/dot",
        "%fusion.4 = f32[] fusion()":
            layer + "attention/_decode_attend/decode_attn",
        "%fusion.5 = f32[] fusion()": "jit(f)/vmap(_Head)/dot_general"}}
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
    # and a registry that counts no state step (the parent's)
    monkeypatch.setattr(pt, "on_chip", lambda: True)

    class Run:
        tf_ops = {0: {}}

        def keep(self, key, make):
            return make()

    monkeypatch.setattr(pt, "for_run", lambda f: Run())
    monkeypatch.setattr(pt, "registry_metric", lambda name, **labels: None)
    monkeypatch.setattr(pt, "counter_value", lambda name, **labels: {
        "bf_serving_decode_steps_total": 50.0}.get(name))
    ctx["peaks"] = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    for name in NEW_READERS:
        assert cell.layer_metric(name).reduce(trace, None, ctx) is None
    # and with another family's reference, which states no such bytes
    other = loader.load_cell(STEADY, REPO).reference()
    for name in ("ssd_state_roofline", "ssd_chunk_roofline"):
        assert cell.layer_metric(name).reduce(
            trace, None, dict(ctx, reference=other)) is None


def test_the_new_readers_on_a_run_with_scopes_and_counters(monkeypatch,
                                                           capsys):
    trace, tf_ops = hand_trace()
    cell = loader.load_cell(CELL, REPO)
    # 40 decoding slots x 6 layers a step, in the stretch as over the
    # process
    counters = {"bf_serving_state_steps_total": 240.0 * 50,
                "bf_serving_decode_steps_total": 50.0,
                "bf_serving_decode_slots_total": 40.0 * 50}
    readers_on_the_chip(monkeypatch, tf_ops, counters, 25_350_144)
    ctx = dict(_ctx(cell), peaks={"hbm_bytes_per_s": 819e9,
                                  "bf16_flops_per_s": 197e12},
               counter_window=counter_window(counters))
    read = lambda name: cell.layer_metric(name).reduce(trace, None, ctx)
    # a decode step: (2 + 3 + 1 + 5) / 2; a chunk: (2 + 8 + 2 + 12) / 2
    assert read("attn_scope_ms.ssd") == pytest.approx(5.5)
    assert read("chunk_attn_ms.ssd") == pytest.approx(12.0)
    # 240 x 8 MiB a step at 819 GB/s over the (3 + 5) / 2 ms of the step
    assert read("ssd_state_roofline") == pytest.approx(
        100 * 240 * 8 * MIB / 819e9 / 4e-3)
    # six layers' bytes (HBM bound) over the (8 + 12) / 2 ms of the scan
    nbytes = 6 * (256 * 9248 * 2 + 8 * MIB)
    assert 6 * 1_375_731_712 / 197e12 < nbytes / 819e9
    assert read("ssd_chunk_roofline") == pytest.approx(
        100 * nbytes / 819e9 / 10e-3)
    assert read("state_mib_per_slot") == pytest.approx(24.17578125)
    out = capsys.readouterr().out
    assert "bf.attn.ssd_state 4.000" in out and "hbm bound" in out
    assert "bf.attn.ssd_chunk 10.000" in out
    assert "40.0 decoding slots a step in the traced stretch, 40.0 over " \
        "the process" in out
    # a time under the scope too short for the bytes is refused, not capped
    ctx["counter_window"] = counter_window(
        dict(counters, bf_serving_state_steps_total=240.0 * 50 * 100))
    with pytest.raises(ValueError, match="cannot be right"):
        read("ssd_state_roofline")
    # and a run that traced no stretch has nothing to divide
    assert cell.layer_metric("ssd_state_roofline").reduce(
        trace, None, dict(ctx, counter_window=None)) is None


def test_the_state_roofline_counts_in_the_traced_stretch(monkeypatch,
                                                         capsys):
    """A flash crowd's lull: the stretch decodes 20 slots a step where
    the process's mean is 40.  The reader divides the stretch's device
    time, so it takes the stretch's slots; the process's would read
    twice the share, past 100%, and raise on a program that is right."""
    trace, tf_ops = hand_trace()
    cell = loader.load_cell(CELL, REPO)
    process = {"bf_serving_state_steps_total": 240.0 * 900,
               "bf_serving_decode_steps_total": 900.0,
               "bf_serving_decode_slots_total": 40.0 * 900}
    stretch = {"bf_serving_state_steps_total": 120.0 * 50,
               "bf_serving_decode_steps_total": 50.0,
               "bf_serving_decode_slots_total": 20.0 * 50}
    readers_on_the_chip(monkeypatch, tf_ops, process)
    # the step's device time at which 120 slot-layers are 70% of the peak
    ms = 120 * 8 * MIB / 819e9 / 0.7 * 1e3
    monkeypatch.setattr(
        cell.layer_metric("ssd_state_roofline").decode_scopes, "scope_ms",
        lambda f, t, scope: ms)
    ctx = dict(_ctx(cell), peaks={"hbm_bytes_per_s": 819e9},
               counter_window=counter_window(stretch))
    assert cell.layer_metric("ssd_state_roofline").reduce(
        trace, None, ctx) == pytest.approx(70.0)
    assert "20.0 decoding slots a step in the traced stretch, 40.0 over " \
        "the process" in capsys.readouterr().out
    from perfbench.harness.peaks import share_pct
    with pytest.raises(ValueError, match="140.00% of the peak"):
        share_pct(cell.reference().ssd_step_bytes(ctx["sizes"], 240.0)
                  / 819e9, 1e-3 * ms, "the process's occupancy")
