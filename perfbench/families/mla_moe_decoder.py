"""The latent-attention decoder with softmax-routed experts beside a
shared one (``model_type: mistral4``), built as
``bluefog_tpu.models.mla_moe.MlaMoe(MlaMoeConfig(...))`` and served
through the program's normal ``ServingEngine``.

The benchmark makes the weights itself, from the seed, as a tree in the
layout the program's model takes (flax names); the same tree is handed
to the plain reference as data.  Every matrix is normal(0,
``initializer_range``), norm scales are 1; the router has no bias.
"""

from __future__ import annotations

import importlib.util

import jax
import jax.numpy as jnp

ITEM = "token"

# a program without the model cannot run this family: say so when the
# cell is loaded, before any weight is drawn or program compiled
if importlib.util.find_spec("bluefog_tpu.models.mla_moe") is None:
    raise ImportError(
        "the program has no bluefog_tpu.models.mla_moe: the "
        "mla_moe_decoder family needs the model that PR 30 added")


def sizes(config: dict, cut: str) -> dict:
    """The configuration's published sizes with the cut's overrides."""
    out = {k: v for k, v in config.items()
           if k not in ("cuts", "assumed", "reduced")}
    out.update(config["cuts"][cut])
    return out


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


# ------------------------------------------------------------------ #
# weights from the seed
# ------------------------------------------------------------------ #
def _leaves(sz: dict):
    """(path, shape, kind) of every parameter leaf, in a fixed order."""
    d, v, h = sz["hidden_size"], sz["vocab_size"], sz["num_attention_heads"]
    rq, dc = sz["q_lora_rank"], sz["kv_lora_rank"]
    dn, dr, dv = (sz["qk_nope_head_dim"], sz["qk_rope_head_dim"],
                  sz["v_head_dim"])
    f = sz["moe_intermediate_size"]
    held, outputs = sz["n_routed_experts"], sz["router_outputs"]
    out = [(("tok_embeddings", "embedding"), (v, d), "matrix")]
    for i in range(sz["num_hidden_layers"]):
        layer = f"layer_{i}"
        att, moe = (layer, "attention"), (layer, "moe")
        out += [
            (att + ("wq_a", "kernel"), (d, rq), "matrix"),
            (att + ("q_norm", "scale"), (rq,), "scale"),
            (att + ("wq_b", "kernel"), (rq, h * (dn + dr)), "matrix"),
            (att + ("wkv_a", "kernel"), (d, dc + dr), "matrix"),
            (att + ("kv_norm", "scale"), (dc,), "scale"),
            (att + ("wkv_b",), (dc, h, dn + dv), "matrix"),
            (att + ("wo", "kernel"), (h * dv, d), "matrix"),
            ((layer, "attention_norm", "scale"), (d,), "scale"),
            ((layer, "ffn_norm", "scale"), (d,), "scale"),
            (moe + ("router",), (d, outputs), "router"),
            (moe + ("w1",), (held, d, f), "matrix"),
            (moe + ("w3",), (held, d, f), "matrix"),
            (moe + ("w2",), (held, f, d), "matrix"),
            (moe + ("shared", "w1", "kernel"), (d, f), "matrix"),
            (moe + ("shared", "w3", "kernel"), (d, f), "matrix"),
            (moe + ("shared", "w2", "kernel"), (f, d), "matrix"),
        ]
    out += [(("norm", "scale"), (d,), "scale"),
            (("output",), (d, v), "matrix")]
    return out


def _put(tree: dict, path, leaf) -> None:
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = leaf


def make_params(sz: dict, key, dtype, only=None):
    """The parameter tree, every leaf from ``fold_in(key, its index)``
    (``only``: a predicate on the path).  Traceable: call it inside one
    jit.  The router's matrix stays float32 whatever ``dtype`` is: the
    program routes in float32.  Returns ``(params, None)``."""
    std = sz["initializer_range"]
    tree = {}
    for i, (path, shape, kind) in enumerate(_leaves(sz)):
        if only is not None and not only(path):
            continue
        if kind == "scale":
            leaf = jnp.ones(shape, jnp.float32)
        else:
            leaf = std * jax.random.normal(jax.random.fold_in(key, i),
                                           shape, jnp.float32)
            if kind == "matrix":
                leaf = leaf.astype(dtype)
        _put(tree, path, leaf)
    return tree, None


# ------------------------------------------------------------------ #
# the system under test
# ------------------------------------------------------------------ #
def model_config(sz: dict, **overrides):
    from bluefog_tpu.models.mla_moe import MlaMoeConfig

    rope = sz["rope_parameters"]
    # the program's model has these built in, not as options
    if not (sz["norm_topk_prob"] and sz["n_shared_experts"] == 1
            and sz["first_k_dense_replace"] == 0 and sz["n_group"] == 1
            and rope["rope_type"] == "yarn" and sz["rope_interleave"]):
        raise ValueError(
            "the program's latent-attention model normalises the chosen "
            "scores, has one shared expert and no dense layer, routes "
            "without groups and rotates interleaved pairs at YaRN's "
            "frequencies; the configuration asks for something else")
    base = dict(
        vocab_size=sz["vocab_size"], dim=sz["hidden_size"],
        n_layers=sz["num_hidden_layers"], n_heads=sz["num_attention_heads"],
        q_lora_rank=sz["q_lora_rank"], kv_lora_rank=sz["kv_lora_rank"],
        qk_nope_head_dim=sz["qk_nope_head_dim"],
        qk_rope_head_dim=sz["qk_rope_head_dim"], v_head_dim=sz["v_head_dim"],
        expert_hidden_dim=sz["moe_intermediate_size"],
        n_experts=sz["router_outputs"], top_k=sz["num_experts_per_tok"],
        route_scale=float(sz["routed_scaling_factor"]),
        experts_held=(sz.get("experts_held_from", 0), sz["n_routed_experts"]),
        rope_theta=float(rope["rope_theta"]),
        rope_factor=float(rope["factor"]),
        rope_original_max=rope["original_max_position_embeddings"],
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        query_scale_beta=float(rope["llama_4_scaling_beta"]),
        norm_eps=sz["rms_norm_eps"],
        initializer_range=sz["initializer_range"],
        dtype=dtype_of(sz["compute_dtype"]))
    base.update(overrides)
    return MlaMoeConfig(**base)


def serving_engine(sz: dict, traffic: dict, params):
    """The ``ServingEngine`` of the traffic file's ``engine`` section
    over ``params`` (held in the cut's ``param_dtype``)."""
    from bluefog_tpu.serving import ServingEngine

    cfg = model_config(sz, max_seq_len=traffic["engine"]["max_len"])
    return ServingEngine({"params": params}, cfg, **traffic["engine"])
