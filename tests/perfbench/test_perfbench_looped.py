"""The looped configuration in the benchmark: it keeps the catalog row's
numbers key by key and cuts nothing; the cut's arithmetic is the tree's;
the cell loads with its files and metrics; the traffic is the issue's;
an append moved nothing that was there; a tiny cell of the family goes
through the command and is ``correct``, and is not with the reference
computed in a lower precision, nor with the final norm left out of the
PROGRAM's loop, nor with one cache shared by all its passes; the byte
function against a count by hand; the new readers on a trace built by
hand, and nothing off the chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import loader
from perfbench.harness import program_trace as pt, trace as tr

from conftest import REPO, add_cell

CELL, STEADY = "ouro-2.6b-serve-short-answer", "mistral7b-serve-steady"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MS = 1e6
NEW_READERS = ("loop_scope_ms.decode", "loop_scope_ms.chunk",
               "loop_cache_streamed_pct", "loop_layer_tokens_per_step")
# the one reader of the dense serve cell that this cell leaves: it
# divides by the configuration's layers and knows no passes
NOT_HERE = {"decode_cache_streamed_pct"}

TINY_LOOPED = {
    "name": "tiny-looped", "source": "test",
    "family": "looped_dense_decoder", "item": "token", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "num_hidden_layers": 3,
    "vocab_size": 128, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "max_position_embeddings": 4096, "total_ut_steps": 4,
    "early_exit_threshold": 1, "initializer_range": 0.2, "reduced": [],
    "cuts": {"serve": {"compute_dtype": "float32",
                       "param_dtype": "float32"}},
}
TINY_MIX = {
    "runner": "serve", "cut": "serve",
    "engine": {"capacity": 3, "max_len": 64, "prefill_chunk": 8,
               "decode_attn": "auto", "max_queue": 64},
    "arrivals": {"process": "poisson", "rate_per_s": 20.0},
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
def test_the_configuration_keeps_the_catalog_rows_numbers_and_cuts_nothing():
    cell = loader.load_cell(CELL, REPO)
    config = cell.config
    if os.path.exists(CATALOG):     # the guide's row, where it is at hand
        with open(CATALOG) as fh:
            rows = [json.loads(line) for line in fh if line.strip()]
        row = next(r for r in rows if r["name"] == "Ouro-2.6B")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert config[key] == value, key
    sz = cell.family().sizes(config, "serve")
    # nothing is cut: the run's sizes ARE the published ones
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "num_hidden_layers",
                "vocab_size", "total_ut_steps", "early_exit_threshold",
                "rope_theta", "rms_norm_eps"):
        assert sz[key] == config[key], key
    assert (sz["hidden_size"], sz["num_hidden_layers"], sz["head_dim"],
            sz["num_attention_heads"], sz["num_key_value_heads"],
            sz["intermediate_size"], sz["vocab_size"], sz["total_ut_steps"],
            sz["early_exit_threshold"], sz["rope_theta"]) \
        == (2048, 48, 128, 16, 16, 5632, 49152, 4, 1, 1000000)
    assert set(config["cuts"]["serve"]) \
        == {"compute_dtype", "param_dtype", "why"}
    assert "NOTHING IS CUT" in config["cuts"]["serve"]["why"]
    bench = loader.load_benchmark(REPO)
    entry = next(c for c in bench["configs"]
                 if c["name"] == cell.config_name)
    assert config["reduced"] == entry["reduced"] == []
    assert entry["source"] == config["source"]
    for key in ("exit_gate", "final_norm", "rotation", "early_exit",
                "shared_cache", "initialisation", "unused_keys"):
        assert key in config["assumed"], key
    assert "one replica on one chip, the WHOLE model" in config["deployment"]
    cfg = cell.family().model_config(sz)
    assert (cfg.loop_steps, cfg.sandwich_norms, cfg.rope_halves,
            cfg.block.n_layers, cfg.block.n_kv_heads, cfg.block.head_dim,
            cfg.block.ffn_dim, cfg.block.rope_theta, cfg.block.norm_eps) \
        == (4, True, True, 48, 16, 128, 5632, 1e6, 1e-6)
    with pytest.raises(ValueError, match="exit threshold of 1"):
        cell.family().model_config(dict(sz, early_exit_threshold=0.8))


def test_the_cuts_arithmetic_is_the_trees():
    """2,667,974,657 parameters in 4.97 GiB of bf16; 1,572,864 bytes of
    cache a token, 9.0 GiB in 8 slots of 768: from shapes alone."""
    cell = loader.load_cell(CELL, REPO)
    family, ref = cell.family(), cell.reference()
    sz = family.sizes(cell.config, "serve")
    params = jax.eval_shape(lambda: family.make_params(
        sz, jax.random.PRNGKey(0), jnp.bfloat16)[0])
    count = lambda t: sum(leaf.size for leaf in jax.tree.leaves(t))
    assert count(params) == ref.total_params(sz) == 2_667_974_657
    layers = params["layers"]["block"]
    assert count(layers) == 48 * 51_388_416
    assert count(params["tok_embeddings"]) == count(params["output"]) \
        == 100_663_296
    assert count(params["norm"]) + count(params["exit_gate"]) == 4_097
    assert layers["attention"]["wk"]["kernel"].shape == (48, 2048, 2048)
    assert layers["feed_forward"]["w2"]["kernel"].shape == (48, 5632, 2048)
    assert {k for k in layers if k.endswith("norm")} == {
        "attention_norm", "attention_post_norm", "ffn_norm",
        "ffn_post_norm"}
    nbytes = 2 * count(params)
    assert 4.96 < nbytes / 2 ** 30 < 4.98
    # the program's own tree is the family's
    from bluefog_tpu.models.looped import init_params

    engine = cell.traffic["engine"]
    cfg = family.model_config(sz).serving_layout(engine["max_len"])
    drawn = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda a: a.shape, drawn) \
        == jax.tree.map(lambda a: a.shape, params)
    cache = jax.eval_shape(lambda: cfg.init_cache(1, engine["max_len"]))
    assert cache["cached_key"].shape == (1, 192, 16, 768, 128)
    kv = sum(cache[k].size * cache[k].dtype.itemsize
             for k in ("cached_key", "cached_value"))
    assert kv == ref.cache_bytes_per_token(sz) * engine["max_len"] \
        == 1_572_864 * 768
    assert engine["capacity"] * kv == 9 * 2 ** 30
    assert cfg.cache_kinds() == {"full": (192, None)}


def test_the_cell_loads_with_its_files_and_metrics():
    cell = loader.load_cell(CELL, REPO)
    assert cell.chips == 1
    assert cell.config["family"] == "looped_dense_decoder"
    assert cell.traffic["runner"] == "serve"
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"}
    names = {m["name"] for m in cell.per_layer}
    steady = {m["name"] for m in loader.load_cell(STEADY, REPO).per_layer}
    assert names == (steady - NOT_HERE) | set(NEW_READERS) \
        | {"kv_reserved_mib_per_slot"}
    for name in names:
        assert callable(cell.layer_metric(name).reduce), name
    ref = cell.reference()
    for function in ("logits", "exit_pdf", "total_params",
                     "decode_step_bytes", "cache_bytes_per_token",
                     "mm_highest", "mm_control"):
        assert callable(getattr(ref, function)), function
    bench = loader.load_benchmark(REPO)
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"] in NEW_READERS}
    assert all(m["workloads"] == [CELL] for m in new.values())
    assert {n: (m["moves"], m["source"], m["layer"], m["unit"])
            for n, m in new.items()} == {
        "loop_scope_ms.decode": ("itl_p95_ms", "device_trace", "model",
                                 "ms"),
        "loop_scope_ms.chunk": ("ttft_p95_ms", "device_trace", "model",
                                "ms"),
        "loop_cache_streamed_pct": ("itl_p95_ms", "program_counter",
                                    "decode", "%"),
        "loop_layer_tokens_per_step": ("serve_tokens_per_s",
                                       "program_counter", "model", "count")}
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 2 == len(bench["workloads"]) // 4
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "nothing cut" in entry["why"]


def test_the_traffic_is_the_issues():
    cell = loader.load_cell(CELL, REPO)
    runner, mix = cell.runner(), cell.traffic
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 160,
                                 "sigma": 0.7, "min": 32, "max": 512}
    assert mix["output_len"] == {"dist": "lognormal", "median": 64,
                                 "sigma": 0.6, "min": 16, "max": 256}
    assert mix["engine"] == {"capacity": 8, "max_len": 768,
                             "prefill_chunk": 256, "decode_attn": "auto",
                             "max_queue": 256}
    assert (mix["drain_s"], mix["check_requests"], mix["cut"]) \
        == (30.0, 6, "serve")
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= mix["engine"]["max_len"]
    assert mix["output_len"]["max"] <= runner.CHECK_ROWS
    assert mix["schedule_seed"] not in (5, 23)      # one of its own
    rate = mix["arrivals"]["rate_per_s"]
    _, prompts, outputs = runner.schedule(mix, 40.0)
    assert len(prompts) == round(rate * 40)
    assert 120 < np.median(prompts) < 200 and 50 < np.median(outputs) < 80
    assert prompts.max() <= 512 and outputs.max() <= 256
    assert prompts.mean() > 2 * outputs.mean()
    for text in (mix["what"], mix["who"], mix["limits"]["logit_gap"]["why"]):
        assert "PLACEHOLDER" not in text
    assert "fits one 16 GB accelerator" in mix["who"]


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_the_append_of_this_cell_moved_nothing_that_was_there(kind):
    """Against the benchmark as the commit before this PR left it, as a
    PREFIX (a later append keeps it): the names in order, and every
    ``workloads`` list that was there a prefix of what it is now, grown
    by this cell where the dense serve cell reads and by nothing else."""
    before = {
        "configs": ["mistral-7b-v0.1", "resnet50", "trinity-large-preview",
                    "mistral-small-4-119b-2603", "xing4.0-29b-a4b",
                    "ling-3.0-flash-vl"],
        "workloads": ["mistral7b-train-1chip", "resnet50-train-1chip",
                      STEADY, "mistral7b-train-atc-4chip",
                      "trinity-large-serve-mixed-len",
                      "mistral-small4-serve-long-prompt",
                      "mistral7b-serve-saturated", "xing4-serve-long-answer",
                      "mistral7b-train-allreduce-4chip",
                      "ling3-flash-serve-doc-reasoning"],
        "end_to_end": ["setup_s", "train_rate_per_chip",
                       "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"],
    }
    bench = loader.load_benchmark(REPO)
    names = [e["name"] for e in bench[kind]]
    old = set(before["workloads"])
    if kind in ("configs", "workloads"):
        n = len(before[kind])
        assert names[:n] == before[kind]
        assert names[n] == {"configs": "ouro-2.6b", "workloads": CELL}[kind]
        return
    entries = bench[kind]
    if kind == "per_layer":
        assert names[65:69] == list(NEW_READERS)
        assert names[60:65] == ["attn_scope_ms.kda", "chunk_attn_ms.kda",
                                "kda_state_roofline", "kda_chunk_roofline",
                                "state_mib_per_slot"]
        assert len(names) == len(set(names))
        entries = entries[:65]
    else:
        assert names == before[kind]
    for m in entries:
        cells = m.get("workloads", [])
        kept = [c for c in cells if c in old]
        assert cells[:len(kept)] == kept, m["name"]
        reads = (STEADY in cells and m["name"] not in NOT_HERE) \
            or m["name"] == "kv_reserved_mib_per_slot"
        assert (CELL in cells) == reads, m["name"]
        if reads:
            assert cells[len(kept)] == CELL, m["name"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        assert len(fh.read()) < 64 * 1024


def test_the_family_refuses_a_program_without_the_looped_stack(monkeypatch):
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec", lambda name, *a: None
        if name == "bluefog_tpu.models.looped" else real(name, *a))
    monkeypatch.setattr(loader, "_MODULES", {})
    with pytest.raises(ImportError, match="models.looped"):
        loader.load_module(REPO, "families", "looped_dense_decoder")


# ------------------------------------------------------------------ #
# a tiny cell of the family through the command and its check
# ------------------------------------------------------------------ #
def test_a_tiny_cell_goes_through_the_command_and_is_correct(
        bench_copy, on_cpu, monkeypatch, capsys):
    from bluefog_tpu import config
    from perfbench import run as pbrun

    monkeypatch.setattr(config, "configure_compilation_cache",
                        lambda: "/cache")
    add_cell(bench_copy, "cell", TINY_LOOPED, "tiny-looped-serve", TINY_MIX)
    rc = pbrun.main(["--workload", "cell", "--seed", str(2 ** 31 + 40),
                     "--seconds", "1.0", "--trace", "0"], root=bench_copy)
    out = capsys.readouterr().out
    rec = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and rec["correct"] is True and rec["failed"] == 0
    assert rec["attempted"] >= 10 and "check: logit_gap" in out
    assert set(rec["metrics"]) >= {"setup_s", "serve_tokens_per_s",
                                   "ttft_p95_ms", "itl_p95_ms"}


def _tiny(bench_copy, seed=3):
    add_cell(bench_copy, "cell", TINY_LOOPED, "tiny-looped-serve", TINY_MIX)
    cell = loader.load_cell("cell", bench_copy)
    family = cell.family()
    sz = family.sizes(cell.config, "serve")
    params = jax.jit(lambda k: family.make_params(sz, k, jnp.float32)[0])(
        jax.random.PRNGKey(seed))
    # norm scales off 1, so that a norm left out shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))
    params["norm"]["scale"] = params["norm"]["scale"] + 0.3 \
        * jax.random.normal(next(keys), params["norm"]["scale"].shape)
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
    assert control > 100 * limit


@pytest.mark.parametrize("fault", ["no final norm inside the loop",
                                   "one cache for all passes"])
def test_a_program_with_a_part_of_the_loop_wrong_is_not_correct(
        bench_copy, on_cpu, monkeypatch, fault):
    """The PROGRAM with the final norm applied once, after the last
    pass, and not after every pass; or with every pass reading and
    writing the FIRST pass's cache leaves: served through the engine,
    the runner's own check at the tiny cell's limit says not correct."""
    from bluefog_tpu.models import looped

    cell, sz, params = _tiny(bench_copy)
    runner = cell.runner()
    limit = cell.traffic["limits"]["logit_gap"]["limit"]
    if fault == "one cache for all passes":
        real = looped._cached_attend
        monkeypatch.setattr(
            looped, "_cached_attend",
            lambda b, q, k, v, kv, leaf, *rest: real(
                b, q, k, v, kv, leaf % sz["num_hidden_layers"], *rest))
    else:
        norm, head = looped.RMSNorm, looped._head

        class Unnormed:                   # the loop's N_f: left out
            def apply(self, variables, x):
                return x

        class LateNorm:                   # applied once, before the head
            def __init__(self, b):
                self.b = b

            def apply(self, variables, x):
                x = norm(self.b.norm_eps).apply({"params": params["norm"]},
                                                x)
                return head(self.b).apply(variables, x)

        monkeypatch.setattr(
            looped, "RMSNorm", lambda eps, name=None: norm(eps, name=name)
            if name else Unnormed())
        monkeypatch.setattr(looped, "_head", LateNorm)
    # programs traced before the patch must not answer for it
    jax.clear_caches()
    try:
        requests = _served(cell, sz, params)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    gap, _ = runner.logit_gaps(cell, sz, params, requests, [0, 1, 2])
    assert gap > 100 * limit, gap


# ------------------------------------------------------------------ #
# the bytes
# ------------------------------------------------------------------ #
def test_the_decode_steps_bytes_against_a_count_by_hand():
    cell = loader.load_cell(CELL, REPO)
    sz = cell.family().sizes(cell.config, "serve")
    ref = cell.reference()
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert ref.layer_matmul_params(sz) == layer == 51_380_224
    weights = 2 * (4 * 48 * layer + 2048 * 49152)
    assert weights == 19_931_332_608
    assert ref.decode_step_bytes(sz, 0) == weights
    assert ref.decode_step_bytes(sz, 2400.0) \
        == weights + 2400 * 1_572_864 == 23_706_206_208
    # the passes multiply the layers' bytes and not the head's
    once = ref.decode_step_bytes(dict(sz, total_ut_steps=1), 0)
    assert once == 2 * (48 * layer + 2048 * 49152)
    # 24.3 ms of weights and 4.6 ms of live cache at 819 GB/s
    assert 24.2e-3 < weights / 819e9 < 24.4e-3
    assert 4.5e-3 < 2400 * 1_572_864 / 819e9 < 4.7e-3


# ------------------------------------------------------------------ #
# the readers
# ------------------------------------------------------------------ #
def hand_trace():
    """Two whole executions of the decode program (10-30, 50-70) and two
    prefill chunks (32-48, 72-92), their operations under the loop's
    attention, the rest of the loop, and outside it."""
    ops = [("%fusion.1 = f32[] fusion()", 10 * MS, 12 * MS),   # embed
           ("%fusion.2 = f32[] fusion()", 12 * MS, 19 * MS),   # loop
           ("%fusion.3 = f32[] fusion()", 19 * MS, 22 * MS),   # loop.attn
           ("%fusion.4 = f32[] fusion()", 22 * MS, 27 * MS),   # head
           ("%fusion.2 = f32[] fusion()", 32 * MS, 40 * MS),
           ("%fusion.3 = f32[] fusion()", 40 * MS, 46 * MS),
           ("%fusion.1 = f32[] fusion()", 46 * MS, 47 * MS),
           ("%fusion.2 = f32[] fusion()", 50 * MS, 59 * MS),
           ("%fusion.3 = f32[] fusion()", 59 * MS, 64 * MS),
           ("%fusion.2 = f32[] fusion()", 72 * MS, 84 * MS),
           ("%fusion.3 = f32[] fusion()", 84 * MS, 86 * MS)]
    modules = [("jit__decode_step_prog(7)", 10 * MS, 30 * MS),
               ("jit__prefill_chunk_prog(3)", 32 * MS, 48 * MS),
               ("jit__decode_step_prog(7)", 50 * MS, 70 * MS),
               ("jit__prefill_chunk_prog(3)", 72 * MS, 92 * MS)]
    loop = "jit(f)/vmap(bf.loop)/while/body/closed_call/while/body/"
    tf_ops = {0: {
        "%fusion.1 = f32[] fusion()": "jit(f)/vmap(Embed)/take",
        "%fusion.2 = f32[] fusion()":
            loop + "LoopBlock/feed_forward/w1/dot_general",
        "%fusion.3 = f32[] fusion()":
            loop + "LoopBlock/attention/bf.loop.attn/decode_attn",
        "%fusion.4 = f32[] fusion()": "jit(f)/vmap(Dense)/dot_general"}}
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
    monkeypatch.setattr(pt, "counter_value", lambda name, **labels: {
        "bf_serving_decode_steps_total": 50.0,
        "bf_serving_steps_total": 60.0,
        "bf_serving_streamed_positions_total": 1e6}.get(name))
    for name in NEW_READERS:
        assert cell.layer_metric(name).reduce(trace, None, ctx) is None


def test_the_new_readers_on_a_run_with_scopes_and_counters(monkeypatch,
                                                           capsys):
    from perfbench.harness import loop_scopes

    assert [loop_scopes.part_of(t) for t in (
        "a/bf.loop/while/b", "a/vmap(bf.loop)/c/bf.loop.attn/d",
        "a/bf.loops/b", "a/b", None)] \
        == ["rest", "attn", "outside", "outside", "outside"]
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
    counters = {"bf_serving_loop_layer_tokens_total": 192.0 * 5000,
                "bf_serving_steps_total": 100.0,
                "bf_serving_decode_steps_total": 50.0,
                "bf_serving_streamed_positions_total":
                    50.0 * 8 * 384 * 192}
    monkeypatch.setattr(pt, "counter_value",
                        lambda name, **labels: counters.get(name))

    class Gauge:
        def __init__(self, value):
            self.value = value

    gauges = {"bf_serving_loop_steps": Gauge(4),
              "bf_serving_exit_pass_mean": Gauge(2.5)}
    monkeypatch.setattr(pt, "registry_metric",
                        lambda name, **labels: gauges.get(name))
    ctx = _ctx(cell)
    read = lambda name: cell.layer_metric(name).reduce(trace, None, ctx)
    # a decode step: (7 + 3 + 9 + 5) / 2 under the loop; a chunk:
    # (8 + 6 + 12 + 2) / 2, its one operation outside left out
    assert read("loop_scope_ms.decode") == pytest.approx(12.0)
    assert read("loop_scope_ms.chunk") == pytest.approx(14.0)
    # half of every reserved row: 8 slots x 768 x 4 passes x 48 layers
    assert read("loop_cache_streamed_pct") == pytest.approx(50.0)
    assert read("loop_layer_tokens_per_step") == pytest.approx(9600.0)
    out = capsys.readouterr().out
    assert "bf.loop.attn 4.000" in out and "outside it 3.500" in out
    assert "bf_serving_exit_pass_mean 2.5" in out and "4 passes" in out
