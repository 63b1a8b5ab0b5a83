"""Fixtures of the benchmark's own tests: a temporary copy of the
benchmark (``BENCHMARK.json`` + ``perfbench/``) that gains throw-away
tiny configurations, traffic mixes and cells as NEW files and entries,
with no file of the copy edited, and the harness's chip-only names
replaced so that it runs on CPU devices."""

import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_DECODER = {
    "name": "tiny-decoder", "source": "test", "family": "dense_gqa_decoder",
    "item": "token", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 256, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "max_position_embeddings": 256,
    "initializer_range": 0.02, "reduced": [],
    "cuts": {
        "train": {"compute_dtype": "float32", "param_dtype": "float32",
                  "attn_impl": "xla", "remat": False},
        "train-bf16": {"compute_dtype": "bfloat16",
                       "param_dtype": "float32", "attn_impl": "xla",
                       "remat": False},
        "serve": {"compute_dtype": "float32", "param_dtype": "float32"},
        "serve-bf16": {"compute_dtype": "bfloat16",
                       "param_dtype": "bfloat16"},
    },
}

TINY_RESNET = {
    "name": "tiny-resnet", "source": "test", "family": "resnet",
    "item": "image", "stage_sizes": [1, 1], "num_filters": 8,
    "expansion": 4, "num_classes": 10, "image_size": 32,
    "bn_momentum": 0.9, "bn_epsilon": 1e-5, "compute_dtype": "float32",
    "param_dtype": "float32", "pallas_conv1x1": False, "reduced": [],
}

ADAMW = {"name": "adamw", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.95,
         "eps": 1e-8, "weight_decay": 0.1}
ATC_ONE_PEER = {"comm_mode": "atc", "schedule": {
    "call": "bluefog_tpu.topology:one_peer_dynamic_schedule",
    "args": ["$ranks"]}}
TIGHT = {k: {"limit": 1e-4, "why": "float32 against float32"}
         for k in ("loss_rel_gap", "grad_norm_gap", "update_norm_gap")}

TINY_TRAFFIC = {
    "tiny-train": {
        "runner": "train", "cut": "train", "step": {"comm_mode": "none"},
        "exchange": "none", "batch_per_chip": 2, "seq_len": 32,
        "optimizer": ADAMW, "limits": TIGHT},
    "tiny-train-bf16": {
        "runner": "train", "cut": "train-bf16", "step": {"comm_mode": "none"},
        "exchange": "none", "batch_per_chip": 2, "seq_len": 32,
        "optimizer": ADAMW, "limits": TIGHT},
    "tiny-train-atc": {
        "runner": "train", "cut": "train", "step": ATC_ONE_PEER,
        "exchange": "one_peer_exp2",
        "batch_per_chip": 2, "seq_len": 32, "optimizer": ADAMW,
        "limits": dict(TIGHT, mix_abs_gap={
            "limit": 1e-6, "why": "float32 roundings of weights near 1"})},
    "tiny-serve": {
        "runner": "serve", "cut": "serve",
        "engine": {"capacity": 4, "max_len": 64, "prefill_chunk": 8,
                   "decode_attn": "auto", "max_queue": 64},
        "arrivals": {"process": "poisson", "rate_per_s": 20.0},
        "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                       "min": 4, "max": 40},
        "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                       "min": 2, "max": 16},
        "schedule_seed": 5, "drain_s": 30.0, "check_requests": 4,
        "limits": {"logit_gap": {"limit": 1e-3,
                                 "why": "float32 against float32"}}},
    "tiny-images": {
        "runner": "train", "step": {"comm_mode": "none"}, "exchange": "none",
        "batch_per_chip": 4,
        "optimizer": {"name": "sgd", "learning_rate": 0.1,
                      "momentum": 0.9},
        "limits": TIGHT},
}


def add_cell(root, name, config, traffic_name, traffic, chips=1):
    """Add one cell to the copy at ``root``: new files, new entries."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    cfg_file = f"perfbench/configs/{config['name']}.json"
    if not any(c["name"] == config["name"] for c in bench["configs"]):
        with open(os.path.join(root, cfg_file), "x") as fh:
            json.dump(config, fh)
        bench["configs"].append({
            "name": config["name"], "source": "test", "file": cfg_file,
            "reduced": [], "why": "test"})
    traffic_file = os.path.join(root, "perfbench", "traffic",
                                f"{traffic_name}.json")
    if not os.path.exists(traffic_file):
        with open(traffic_file, "x") as fh:
            json.dump(traffic, fh)
    bench["workloads"].append({
        "name": name, "config": config["name"], "traffic": traffic_name,
        "chips": chips, "why": "test"})
    kind = "serve" if traffic["runner"] == "serve" else "train"
    for m in bench["end_to_end"] + bench["per_layer"]:
        # a tiny serve cell reports ``ttft_p95_ms`` end to end, so it
        # reads under the first names, not those of a cell that holds no
        # bound on its first-token tail
        if m["name"].endswith(".no_ttft_bound") \
                or m["name"] == "first_token_p95_ms":
            continue
        if "workloads" in m and any(
                kind in w for w in m["workloads"]) and (
                    "4chip" not in "".join(m["workloads"])
                    or len(m["workloads"]) > 1):
            m["workloads"].append(name)
    with open(path, "w") as fh:
        json.dump(bench, fh)
    return name


def labelled(name: str, **labels):
    """The key of ``name{labels}`` in ``counter_window``'s and
    ``readers_on_the_chip``'s dictionaries (a bare name: no label)."""
    return name, tuple(sorted((k, str(v)) for k, v in labels.items()))


def _keyed(counts: dict) -> dict:
    return {k if isinstance(k, tuple) else labelled(k): v
            for k, v in counts.items()}


def counter_window(grew: dict, before: float = 1000.0):
    """A ``program_trace.CounterWindow`` whose counters stood at
    ``before`` when the stretch opened and grew by ``grew`` in it."""
    from perfbench.harness import program_trace as pt

    grew = _keyed(grew)
    return pt.CounterWindow(dict.fromkeys(grew, before),
                            {k: before + v for k, v in grew.items()})


class ReaderRun:
    """What ``program_trace.for_run`` hands a reader, of a trace built
    by hand."""

    def __init__(self, tf_ops):
        self.tf_ops, self.kept = tf_ops, {}

    def keep(self, key, make):
        if key not in self.kept:
            self.kept[key] = make()
        return self.kept[key]


def readers_on_the_chip(monkeypatch, tf_ops, process: dict,
                        state_bytes_per_slot=None):
    """The readers' look for the chip answered yes, a hand-made trace's
    run behind ``for_run``, ``process`` as the registry's counters and,
    where given, the gauge of a slot's state."""
    from perfbench.harness import program_trace as pt

    class Gauge:
        value = state_bytes_per_slot

    run, process = ReaderRun(tf_ops), _keyed(process)
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    monkeypatch.setattr(pt, "for_run", lambda f: run)
    monkeypatch.setattr(
        pt, "counter_value",
        lambda name, **labels: process.get(labelled(name, **labels)))
    monkeypatch.setattr(
        pt, "registry_metric", lambda name, **labels: Gauge()
        if name == "bf_serving_state_bytes_per_slot"
        and state_bytes_per_slot else None)


@pytest.fixture
def bench_copy(tmp_path):
    """A temporary copy of ``BENCHMARK.json`` and ``perfbench/``."""
    root = str(tmp_path / "copy")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


@pytest.fixture
def on_cpu(monkeypatch):
    """The harness steered onto CPU devices: its look for a chip is
    skipped (the first ``chips`` CPU devices stand in) and the rest of
    a run is driven as it is."""
    import jax

    from perfbench.harness import device

    monkeypatch.setattr(device, "PLATFORM", "cpu")
    monkeypatch.setattr(device, "require_chips",
                        lambda n: jax.devices()[:n])
    return device
