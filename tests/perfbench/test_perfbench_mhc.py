"""The four-stream configuration in the benchmark, and the all-reduce
training cell beside it: the configuration keeps the catalog row's widths
key by key; both cells load with their files and metrics; the traffic is
the issue's; texts fit their limits; an append moved nothing that was
there; a tiny cell of the family is served and ``correct`` and its fp8
control is not; the byte functions against a count by hand; the new
readers on a trace built by hand, and nothing off the chip."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from perfbench.harness import clocks, loader
from perfbench.harness import program_trace as pt, trace as tr

from conftest import REPO, TINY_DECODER, TINY_TRAFFIC, add_cell

CELL, ALLREDUCE = "xing4-serve-long-answer", "mistral7b-train-allreduce-4chip"
LATENT, ATC = "mistral-small4-serve-long-prompt", "mistral7b-train-atc-4chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MS = 1e6
NEW_READERS = ("hc_scope_ms.decode", "hc_scope_ms.chunk", "hc_mix_roofline",
               "hc_mixed_tokens_per_step")
# PR 48's answer to the check: this cell's first-token tail repeats too
# loosely for any bound the contract allows, so the cell reports it per
# layer, and the readers that moved ``ttft_p95_ms`` read here under a
# second name that moves a metric the cell does report
NO_TTFT_BOUND = ".no_ttft_bound"
TWINNED = {"queue_wait_p95_ms": "serve_tokens_per_s",
           "loadgen_late_p95_ms": "serve_tokens_per_s",
           "queue_wait_prog_p95_ms": "serve_tokens_per_s",
           "engine_phase_ms.admit": "itl_p95_ms",
           "prefill_pad_pct": "itl_p95_ms",
           "chunk_attn_ms.latent": "itl_p95_ms"}
PUBLISHED_WIDTHS = {
    "hidden_size": 3584, "intermediate_size": 9216,
    "moe_intermediate_size": 1024, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "n_routed_experts": 64, "n_shared_experts": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "routed_scaling_factor": 2,
    "scoring_func": "sigmoid", "vocab_size": 131072, "rms_norm_eps": 1e-6}

TINY_MHC = {
    "name": "tiny-mhc-mla-moe", "source": "test",
    "family": "mhc_mla_moe_decoder", "item": "token", "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 12,
    "num_hidden_layers": 6, "first_k_dense_replace": 2, "vocab_size": 128,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "moe_layer_freq": 1,
    "rope_scaling": {"beta_fast": 4, "beta_slow": 0.25, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "n_routed_experts": 16, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "initializer_range": 0.2,
    "router_bias_std": 0.01, "hc_phi_std": 0.05, "hc_alpha": 1.0,
    "reduced": [],
    "cuts": {"serve": {
        "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "n_routed_experts": 16, "router_outputs": 16,
        "experts_held_from": 0, "compute_dtype": "float32",
        "param_dtype": "float32"}},
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
def test_the_configuration_keeps_the_catalog_rows_widths_key_by_key():
    cell = loader.load_cell(CELL, REPO)
    config = cell.config
    if os.path.exists(CATALOG):     # the guide's row, where it is at hand
        with open(CATALOG) as fh:
            rows = [json.loads(line) for line in fh if line.strip()]
        row = next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert config[key] == value, key
    sz = cell.family().sizes(config, "serve")
    for key, value in PUBLISHED_WIDTHS.items():
        assert sz[key] == config[key] == value, key
    assert sz["rope_scaling"] == config["rope_scaling"]
    # the cut: depth and the dense layers' count, nothing else
    assert (config["num_hidden_layers"], config["first_k_dense_replace"]) \
        == (40, 2)
    assert (sz["num_hidden_layers"], sz["first_k_dense_replace"]) == (5, 1)
    assert sz["router_outputs"] == sz["n_routed_experts"] == 64
    assert sz["experts_held_from"] == 0
    bench = loader.load_benchmark(REPO)
    entry = next(c for c in bench["configs"]
                 if c["name"] == cell.config_name)
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace"]
    assert entry["source"].startswith(config["source"])
    assert "layers 0, 2-5 of 40; all 64 experts" in entry["source"]
    for key in ("sinkhorn_order", "clamp", "xh", "entry_and_exit",
                "mixing_draw", "multi_token_prediction", "score_function"):
        assert key in config["assumed"], key
    assert "one chip a layer group" in config["deployment"]


def test_the_cuts_arithmetic_is_the_trees():
    """8.11 GB of weights, 22.5 MiB a slot, 1,152 bytes a token a
    layer, from shapes alone."""
    cell = loader.load_cell(CELL, REPO)
    family, ref = cell.family(), cell.reference()
    sz = family.sizes(cell.config, "serve")
    params = jax.eval_shape(lambda: family.make_params(
        sz, jax.random.PRNGKey(0), jnp.bfloat16)[0])
    nbytes = sum(leaf.size * leaf.dtype.itemsize
                 for leaf in jax.tree.leaves(params))
    assert 8.09e9 < nbytes < 8.13e9
    mixing = params["layer_3"]["ffn_hc"]
    assert mixing["phi"].shape == (4 * 3584, 24)
    assert all(leaf.dtype == jnp.float32
               for leaf in jax.tree.leaves(mixing))
    assert "feed_forward" in params["layer_0"] \
        and "moe" not in params["layer_0"]
    assert params["layer_1"]["moe"]["w1"].shape == (64, 3584, 1024)
    assert params["layer_1"]["moe"]["router"].dtype == jnp.float32
    engine = cell.traffic["engine"]
    assert ref.cache_bytes_per_position(sz) == 1152
    slot = 1152 * engine["max_len"] * sz["num_hidden_layers"]
    assert slot == 22.5 * 2 ** 20
    cfg = family.model_config(sz).serving_layout(engine["max_len"])
    assert (cfg.hc_mult, cfg.n_dense_layers, cfg.score_func,
            cfg.latent_width, cfg.held) == (4, 1, "sigmoid", 576, (0, 64))
    leaves = jax.eval_shape(lambda: cfg.init_cache(1, engine["max_len"]))
    assert sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(leaves)
               if leaf.ndim == 3) == slot


def test_the_serve_cell_loads_with_its_files_and_metrics():
    cell = loader.load_cell(CELL, REPO)
    assert cell.chips == 1
    assert cell.config["family"] == "mhc_mla_moe_decoder"
    assert cell.traffic["runner"] == "serve_gap_share"
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "serve_tokens_per_s", "itl_p95_ms"}
    names = {m["name"] for m in cell.per_layer}
    latent = {m["name"] for m in loader.load_cell(LATENT, REPO).per_layer}
    assert names == (latent - set(TWINNED)) | set(NEW_READERS) \
        | {n + NO_TTFT_BOUND for n in TWINNED} | {"first_token_p95_ms"}
    for name in names:
        assert callable(cell.layer_metric(name).reduce), name
    ref = cell.reference()
    for function in ("logits", "moe_decode_step_bytes", "hc_chunk_bytes",
                     "hc_mix_bytes_per_token", "cache_bytes_per_position"):
        assert callable(getattr(ref, function)), function
    bench = loader.load_benchmark(REPO)
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["layer"] == "model"
    moves = {m["name"]: m["moves"] for m in bench["per_layer"]}
    assert moves["hc_scope_ms.decode"] == moves[
        "hc_mixed_tokens_per_step"] == "itl_p95_ms"
    # a chunk's device time is a gap of every slot that decodes beside it
    # (``prefill_chunk_device_ms`` moves the same); ``ttft_p95_ms`` is no
    # end-to-end metric of this cell since PR 48
    assert moves["hc_scope_ms.chunk"] == moves["hc_mix_roofline"] \
        == "itl_p95_ms"


def test_the_cell_reports_its_first_token_tail_per_layer_and_holds_no_bound():
    """Every per-layer metric of the cell moves a metric the cell reports
    end to end; the first-token tail is one of them under a name of its
    own; the six readers that move ``ttft_p95_ms`` elsewhere are listed
    here under their second names and there without this cell."""
    bench = loader.load_benchmark(REPO)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL not in e2e["ttft_p95_ms"]["workloads"]
    assert len(e2e["ttft_p95_ms"]["workloads"]) == 6
    assert e2e["ttft_p95_ms"]["bound"] == 0.1
    for name in ("serve_tokens_per_s", "itl_p95_ms"):
        assert CELL in e2e[name]["workloads"]
    cell = loader.load_cell(CELL, REPO)
    reported = {m["name"] for m in cell.end_to_end}
    assert all(m["moves"] in reported for m in cell.per_layer)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    tail = per_layer["first_token_p95_ms"]
    assert (tail["workloads"], tail["unit"], tail["better"], tail["source"],
            tail["layer"], tail["moves"]) == (
        [CELL], "ms", "lower", "host_clock", "serving engine",
        "serve_tokens_per_s")
    for name, moves in TWINNED.items():
        first, second = per_layer[name], per_layer[name + NO_TTFT_BOUND]
        assert first["moves"] == "ttft_p95_ms" and CELL not in first[
            "workloads"]
        assert (second["workloads"], second["moves"]) == ([CELL], moves)
        assert {k: second[k] for k in ("unit", "better", "source", "layer")} \
            == {k: first[k] for k in ("unit", "better", "source", "layer")}


@pytest.mark.parametrize("name", sorted(TWINNED))
def test_a_second_name_reads_with_the_first_names_reader(name):
    first = loader.load_module(REPO, "layer_metrics", name)
    second = loader.load_module(REPO, "layer_metrics", name + NO_TTFT_BOUND)
    assert second.reduce is first.reduce
    assert name in second.__doc__ and "first_token_p95_ms" in second.__doc__


def test_the_first_token_reader_is_the_end_to_end_percentile():
    reader = loader.load_module(REPO, "layer_metrics", "first_token_p95_ms")
    ttft = [float(x) for x in range(100, 225)]       # 125 requests
    assert reader.reduce(None, None, {"serve": {"ttft_ms": ttft}}) \
        == clocks.percentile(ttft, 95) == pytest.approx(217.8)
    assert reader.reduce(None, None, {"serve": {"ttft_ms": []}}) is None
    assert reader.reduce(None, None, {}) is None     # a training cell


def test_the_serve_traffic_is_the_issues():
    cell = loader.load_cell(CELL, REPO)
    runner, mix = cell.runner(), cell.traffic
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.8, "min": 32, "max": 2048}
    assert mix["output_len"] == {"dist": "lognormal", "median": 448,
                                 "sigma": 0.6, "min": 64, "max": 1024}
    assert mix["engine"] == {"capacity": 64, "max_len": 4096,
                             "prefill_chunk": 256, "decode_attn": "auto",
                             "max_queue": 256}
    assert (mix["drain_s"], mix["check_requests"]) == (30.0, 3)
    assert mix["arrivals"]["process"] == "poisson"
    assert set(mix["limits"]) == {"logit_gap", "gap_share"}
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= mix["engine"]["max_len"]
    # decode-heavy: more answer tokens than prompt tokens (means about
    # 520 against 350; every other serve cell's prompts are many times
    # its answers)
    _, prompts, outputs = runner.schedule(mix, 40.0)
    assert len(prompts) >= 80
    assert 1.1 < outputs.sum() / prompts.sum() < 2.6
    assert 450 < outputs.mean() < 600
    for key in ("what",):
        assert "PLACEHOLDER" not in mix[key]
    for limit in mix["limits"].values():
        assert "PLACEHOLDER" not in limit["why"]


def test_the_allreduce_cell_is_the_atc_cell_but_for_its_exchange():
    cell, atc = (loader.load_cell(n, REPO) for n in (ALLREDUCE, ATC))
    assert cell.chips == atc.chips == 4
    assert cell.config_name == atc.config_name == "mistral-7b-v0.1"
    mix, base = dict(cell.traffic), dict(atc.traffic)
    assert mix.pop("what") != base.pop("what")
    assert mix.pop("step") == {"comm_mode": "gradient_allreduce"}
    assert base.pop("step")["comm_mode"] == "atc"
    assert (mix.pop("exchange"), base.pop("exchange")) \
        == ("allreduce_gradients", "one_peer_exp2")
    assert set(mix.pop("limits")) == set(base.pop("limits")) == {
        "loss_rel_gap", "grad_norm_gap", "update_norm_gap", "mix_abs_gap"}
    assert mix == base
    assert mix["expect_kernel"] is True
    exchange = loader.load_module(REPO, "exchanges", "allreduce_gradients")
    assert exchange.MIXES == "gradients"
    (w,) = exchange.matrices(4)
    assert w.shape == (4, 4) and (w == 0.25).all()
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "train_rate_per_chip"}
    # every metric of the atc cell but the two that look for permutes
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in atc.per_layer} - {"exchange_ms",
                                             "exchange_exposed_ms"}
    four = [w for w in loader.load_benchmark(REPO)["workloads"]
            if w["chips"] == 4]
    assert len(four) == 2 == len(loader.load_benchmark(REPO)[
        "workloads"]) // 4


@pytest.mark.parametrize("step, correct", [
    ({"comm_mode": "gradient_allreduce"}, True),
    ({"comm_mode": "none"}, False)])
def test_a_tiny_allreduce_cell_is_held_to_its_all_reduce(
        bench_copy, on_cpu, monkeypatch, capsys, step, correct):
    """Four ranks at a tiny size through the command, with the
    repository's own ``exchanges/allreduce_gradients.py``: ``correct``;
    and a step that leaves the all-reduce out (every rank steps by its
    own gradient) is not: the reference, which averages the gradients,
    tells it apart."""
    from bluefog_tpu import config
    from perfbench import run as pbrun

    monkeypatch.setattr(config, "configure_compilation_cache",
                        lambda: "/cache")
    traffic = dict(TINY_TRAFFIC["tiny-train-atc"], step=step,
                   exchange="allreduce_gradients")
    add_cell(bench_copy, "cell", TINY_DECODER, "tiny-allreduce", traffic, 4)
    rc = pbrun.main(["--workload", "cell", "--seed", str(2 ** 31 + 77),
                     "--seconds", "0.5", "--trace", "0"], root=bench_copy)
    out = capsys.readouterr().out
    rec = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and rec["correct"] is correct
    assert rec["device"]["count"] == 4
    assert ("<-- over" in out) is not correct


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_an_append_moved_nothing_that_was_there(kind):
    """Against the benchmark as the commit before this PR left it (the
    names in order, and every ``workloads`` list as a prefix)."""
    before = {
        "configs": ["mistral-7b-v0.1", "resnet50", "trinity-large-preview",
                    "mistral-small-4-119b-2603"],
        "workloads": ["mistral7b-train-1chip", "resnet50-train-1chip",
                      "mistral7b-serve-steady", "mistral7b-train-atc-4chip",
                      "trinity-large-serve-mixed-len", LATENT,
                      "mistral7b-serve-saturated"],
        "end_to_end": ["setup_s", "train_rate_per_chip",
                       "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"],
    }
    bench = loader.load_benchmark(REPO)
    names = [e["name"] for e in bench[kind]]
    if kind == "per_layer":
        assert names[-4:] == list(NEW_READERS)
        assert len(names) == len(set(names)) == 55
        old = set(before["workloads"])
        for m in bench[kind][:-4]:
            cells = m["workloads"]
            kept = [c for c in cells if c in old]
            assert cells[:len(kept)] == kept, m["name"]
            assert set(cells[len(kept):]) <= {CELL, ALLREDUCE}, m["name"]
        return
    assert names[:len(before[kind])] == before[kind]
    added = {"configs": ["xing4.0-29b-a4b"], "workloads": [CELL, ALLREDUCE],
             "end_to_end": []}[kind]
    assert names[len(before[kind]):] == added
    if kind == "end_to_end":
        old = set(before["workloads"])
        for m in bench[kind]:
            cells = m.get("workloads", [])
            kept = [c for c in cells if c in old]
            assert cells[:len(kept)] == kept, m["name"]


def test_no_entry_keeps_a_placeholder_or_a_key_the_contract_lacks():
    """(That every text fits its 200 characters and the file its 64 KiB
    is ``test_perfbench_mla_moe.py``'s, over the whole file.)"""
    bench = loader.load_benchmark(REPO)
    for entry in bench["configs"] + bench["workloads"]:
        assert "PLACEHOLDER" not in entry["why"], entry["name"]
        assert len(entry["why"]) <= 200, entry["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "layer", "moves", "workloads"}, m["name"]


def test_the_family_refuses_a_program_without_the_residual_path(
        monkeypatch):
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec", lambda name, *a: None
        if name == "bluefog_tpu.models.hyper_connections"
        else real(name, *a))
    monkeypatch.setattr(loader, "_MODULES", {})
    with pytest.raises(ImportError, match="hyper_connections"):
        loader.load_module(REPO, "families", "mhc_mla_moe_decoder")


# ------------------------------------------------------------------ #
# a tiny cell of the family through the runner and its check
# ------------------------------------------------------------------ #
def test_a_tiny_cell_of_the_family_is_served_and_correct(bench_copy, on_cpu,
                                                         capsys):
    add_cell(bench_copy, "cell", TINY_MHC, "tiny-mhc-serve", TINY_MIX)
    cell = loader.load_cell("cell", bench_copy)
    result = cell.runner().run(cell, 2147494999, 1.0, False,
                               jax.devices()[:1], clocks.Spans(),
                               clocks.now(), "/unused")
    assert result["failed"] == 0 and result["correct"] is True
    assert "check: logit_gap" in capsys.readouterr().out
    assert result["attempted"] >= 10


@pytest.mark.parametrize("trace", [0, 1])
def test_a_tiny_cell_listed_as_this_cell_holds_no_first_token_bound(
        bench_copy, on_cpu, monkeypatch, capsys, trace):
    """A tiny cell listed exactly where this cell is listed, through the
    command: untraced, its line holds no ``ttft_p95_ms`` (and the command
    does not miss it); traced, the first-token tail and the two host-clock
    waits come under their per-layer names and not under the first ones."""
    from bluefog_tpu import config
    from perfbench import run as pbrun

    monkeypatch.setattr(config, "configure_compilation_cache",
                        lambda: "/cache")
    # the dense tiny model: what is held here is the listing, not the
    # family, and its programs compile in seconds
    add_cell(bench_copy, "cell", TINY_DECODER, "tiny-serve",
             TINY_TRAFFIC["tiny-serve"])
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
    if trace:
        ops = [("fusion.1", 1 * MS, 4 * MS), ("fusion.2", 6 * MS, 9 * MS)]
        monkeypatch.setattr(tr, "find_xplane", lambda d: d)
        monkeypatch.setattr(tr, "load", lambda p: tr.Trace(
            [tr.DeviceTrace(0, ops, [("jit__decode_step_prog(5)", 1 * MS,
                                      9 * MS)])],
            [("pb.trace_window", 0.0, 10 * MS)]))
    rc = pbrun.main(["--workload", "cell", "--seed", str(2 ** 31 + 48),
                     "--seconds", "1.0", "--trace", str(trace)],
                    root=bench_copy)
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and rec["correct"] is True and rec["failed"] == 0
    names = set(rec["metrics"])
    if not trace:
        assert names == {"setup_s", "serve_tokens_per_s", "itl_p95_ms"}
        return
    assert names >= {"first_token_p95_ms",
                     "queue_wait_p95_ms" + NO_TTFT_BOUND,
                     "loadgen_late_p95_ms" + NO_TTFT_BOUND}
    assert not names & (set(TWINNED) | {"ttft_p95_ms"})
    assert rec["metrics"]["first_token_p95_ms"]["value"] >= rec["metrics"][
        "queue_wait_p95_ms" + NO_TTFT_BOUND]["value"] > 0


def test_the_control_of_the_tiny_cell_is_not_correct(bench_copy, on_cpu):
    add_cell(bench_copy, "cell", TINY_MHC, "tiny-mhc-serve", TINY_MIX)
    cell = loader.load_cell("cell", bench_copy)
    runner, family = cell.runner(), cell.family()
    sz = family.sizes(cell.config, "serve")
    params = jax.jit(lambda k: family.make_params(sz, k, jnp.float32)[0])(
        jax.random.PRNGKey(3))
    # the family's one tree feeds the program and the reference
    engine = family.serving_engine(sz, cell.traffic, params)
    assert engine.cfg.mixed_sublayers == 6
    requests = runner.make_requests(sz, [30, 9], [12, 12], 7)
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
# the bytes
# ------------------------------------------------------------------ #
def test_the_mixings_bytes_against_a_count_by_hand():
    cell = loader.load_cell(CELL, REPO)
    sz = cell.family().sizes(cell.config, "serve")
    ref = cell.reference()
    # hc_pre reads 4 streams; hc_post reads 4 and y and writes 4: 13 x
    # 3584 bf16 values a token a sublayer
    assert ref.hc_mix_bytes_per_token(sz) == 93_184
    # a chunk of 256: nine sublayers whole (the last layer's feed-forward
    # is dead code, its router is not) and the tenth's weighted sum alone
    by_hand = 256 * (9 * 93_184 + 4 * 3584 * 2)
    assert by_hand == 222_035_968
    assert ref.hc_chunk_bytes(sz, 1, 256) == by_hand
    assert ref.hc_chunk_bytes(sz, 7, 256) == 7 * by_hand
    # under what a form that mixed all ten sublayers whole would move
    assert by_hand < 256 * 10 * 93_184
    assert ref.mixing_params(sz) == 14336 * 24 + 3 + 4 + 4 + 16


def test_decode_step_bytes_against_a_count_by_hand():
    cell = loader.load_cell(CELL, REPO)
    sz = cell.family().sizes(cell.config, "serve")
    ref = cell.reference()
    attention = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 \
        + 512 * 32 * 256 + 32 * 128 * 3584
    assert attention == 28_409_856
    expert, dense = 3 * 3584 * 1024, 3 * 3584 * 9216
    held = 5 * attention + dense + 4 * (1 + 37.5) * expert + 3584 * 131072
    float32 = 4 * 3584 * 64 + 10 * (14336 * 24 + 27)
    by_hand = 2 * held + 4 * float32
    assert ref.decode_step_weight_bytes(sz, 37.5) == by_hand
    assert ref.moe_decode_step_bytes(sz, 37.5, 100_000) \
        == by_hand + 100_000 * 1152
    # every expert hit and every reserved row attended: the weights less
    # the embedding's 0.94 GB (a lookup) and the pool's 1.51 GB
    full = ref.moe_decode_step_bytes(sz, 64, 64 * 4096 * 5)
    assert 7.1e9 + 1.5e9 < full < 7.2e9 + 1.52e9


# ------------------------------------------------------------------ #
# the readers
# ------------------------------------------------------------------ #
def hand_trace():
    """Two whole executions of the decode program (10-30, 50-70) and two
    prefill chunks (32-48, 72-92), their operations under the mixing's
    two scopes, an attention scope and none."""
    ops = [("%fusion.1 = f32[] fusion()", 10 * MS, 12 * MS),   # hc.pre
           ("%fusion.2 = f32[] fusion()", 12 * MS, 13 * MS),   # hc.post
           ("%fusion.3 = f32[] fusion()", 13 * MS, 27 * MS),   # attention
           ("%fusion.1 = f32[] fusion()", 32 * MS, 38 * MS),
           ("%fusion.2 = f32[] fusion()", 38 * MS, 42 * MS),
           ("%fusion.4 = f32[] fusion()", 42 * MS, 47 * MS),   # no scope
           ("%fusion.1 = f32[] fusion()", 50 * MS, 54 * MS),
           ("%fusion.2 = f32[] fusion()", 54 * MS, 57 * MS),
           ("%fusion.1 = f32[] fusion()", 72 * MS, 80 * MS),
           ("%fusion.2 = f32[] fusion()", 80 * MS, 82 * MS)]
    modules = [("jit__decode_step_prog(7)", 10 * MS, 30 * MS),
               ("jit__prefill_chunk_prog(3)", 32 * MS, 48 * MS),
               ("jit__decode_step_prog(7)", 50 * MS, 70 * MS),
               ("jit__prefill_chunk_prog(3)", 72 * MS, 92 * MS)]
    tf_ops = {0: {
        "%fusion.1 = f32[] fusion()":
            "jit(f)/vmap(MlaMoe)/layer_1/bf.hc.pre/div",
        "%fusion.2 = f32[] fusion()":
            "jit(f)/vmap(MlaMoe)/layer_1/bf.hc.post/add",
        "%fusion.3 = f32[] fusion()":
            "jit(f)/vmap(MlaMoe)/layer_1/attention/bf.attn.latent/dot",
        "%fusion.4 = f32[] fusion()": "jit(f)/vmap(MlaMoe)/norm/mul"}}
    trace = tr.Trace([tr.DeviceTrace(0, ops, modules)],
                     [("pb.trace_window", 0.0, 100 * MS)])
    return trace, tf_ops


def test_the_new_readers_read_nothing_off_the_chip_or_without_scopes(
        monkeypatch):
    trace, _ = hand_trace()
    cell = loader.load_cell(CELL, REPO)
    sz = cell.family().sizes(cell.config, "serve")
    ctx = {"serve": {}, "traffic": cell.traffic, "peaks": None,
           "reference": cell.reference(), "sizes": sz}
    for name in NEW_READERS:
        assert cell.layer_metric(name).reduce(trace, None, ctx) is None
    # on the chip, with a trace of a program that writes no such scope
    # and a registry that counts no such tokens (the parent's)
    monkeypatch.setattr(pt, "on_chip", lambda: True)

    class Run:
        tf_ops = {0: {}}

        def keep(self, key, make):
            return make()

    monkeypatch.setattr(pt, "for_run", lambda f: Run())
    monkeypatch.setattr(pt, "registry_metric", lambda name, **labels: None)
    monkeypatch.setattr(pt, "counter_value", lambda name, **labels: None)
    ctx["peaks"] = {"hbm_bytes_per_s": 819e9}
    for name in NEW_READERS:
        assert cell.layer_metric(name).reduce(trace, None, ctx) is None
    # and a reference that states no such bytes (another family's)
    other = dict(ctx, reference=loader.load_cell(LATENT, REPO).reference())
    assert cell.layer_metric("hc_mix_roofline").reduce(
        trace, None, other) is None


def test_the_new_readers_on_a_run_with_scopes_and_counters(monkeypatch,
                                                           capsys):
    trace, tf_ops = hand_trace()
    cell = loader.load_cell(CELL, REPO)
    sz = cell.family().sizes(cell.config, "serve")
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
    counters = {"bf_hc_mixed_tokens_total": 10 * 420.0 * 50,
                "bf_serving_steps_total": 50.0}
    monkeypatch.setattr(pt, "counter_value",
                        lambda name, **labels: counters.get(name))

    class Gauge:
        value = 4

    monkeypatch.setattr(pt, "registry_metric", lambda name, **labels:
                        Gauge() if name == "bf_hc_streams" else None)
    ctx = {"sizes": sz, "traffic": cell.traffic, "serve": {},
           "reference": cell.reference(),
           "peaks": {"hbm_bytes_per_s": 819e9}}
    read = lambda name: cell.layer_metric(name).reduce(trace, None, ctx)
    # a decode step: (2 + 1 + 4 + 3) / 2; a chunk: (6 + 4 + 8 + 2) / 2
    assert read("hc_scope_ms.decode") == pytest.approx(5.0)
    assert read("hc_scope_ms.chunk") == pytest.approx(10.0)
    assert read("hc_mixed_tokens_per_step") == pytest.approx(4200.0)
    # 222.0 MB a chunk at 819 GB/s is 0.2711 ms of the 10 under the scopes
    assert read("hc_mix_roofline") == pytest.approx(
        100 * 222_035_968 / 819e9 / 10e-3)
    out = capsys.readouterr().out
    assert "bf.hc.post 3.000" in out and "bf.hc.pre 7.000" in out
    assert "bf_hc_streams 4" in out
