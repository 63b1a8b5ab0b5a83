"""The ``afmoe`` decoder (window and full attention mixed, gated heads,
sandwich norms, sigmoid-routed experts beside a shared one), built as
``bluefog_tpu.models.afmoe.Afmoe(AfmoeConfig(...))`` and served through
the program's normal ``ServingEngine``.

The benchmark makes the weights itself, from the seed, as a tree in the
layout the program's model takes (flax names); the same tree is handed
to the plain reference as data.  Every matrix is normal(0,
``initializer_range``), norm scales are 1, and the router's bias is a
seeded draw of ``router_bias_std`` (the configuration file's
``assumed``): large enough to change some of the choices, so that a
bias added into the weights as well would fail the output check.
"""

from __future__ import annotations

import importlib.util

import jax
import jax.numpy as jnp

ITEM = "token"

# a program without the model cannot run this family: say so when the
# cell is loaded, before any weight is drawn or program compiled
if importlib.util.find_spec("bluefog_tpu.models.afmoe") is None:
    raise ImportError(
        "the program has no bluefog_tpu.models.afmoe: the afmoe_decoder "
        "family needs the model that PR 26 added")


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
    d, v, hd = sz["hidden_size"], sz["vocab_size"], sz["head_dim"]
    nq, nkv = sz["num_attention_heads"], sz["num_key_value_heads"]
    f_dense, f_exp = sz["intermediate_size"], sz["moe_intermediate_size"]
    held, outputs = sz["num_experts"], sz["router_outputs"]
    out = [(("tok_embeddings", "embedding"), (v, d), "matrix")]
    for i in range(sz["num_hidden_layers"]):
        layer = f"layer_{i}"
        att = (layer, "attention")
        out += [
            (att + ("wq", "kernel"), (d, nq * hd), "matrix"),
            (att + ("wk", "kernel"), (d, nkv * hd), "matrix"),
            (att + ("wv", "kernel"), (d, nkv * hd), "matrix"),
            (att + ("wg", "kernel"), (d, nq * hd), "matrix"),
            (att + ("wo", "kernel"), (nq * hd, d), "matrix"),
            (att + ("q_norm", "scale"), (hd,), "scale"),
            (att + ("k_norm", "scale"), (hd,), "scale"),
        ]
        out += [((layer, name, "scale"), (d,), "scale") for name in
                ("attention_norm", "attention_post_norm", "ffn_norm",
                 "ffn_post_norm")]
        if i < sz["num_dense_layers"]:
            ff, width = (layer, "feed_forward"), f_dense
        else:
            ff = (layer, "moe", "shared")
            width = f_exp
            moe = (layer, "moe")
            out += [
                (moe + ("router",), (d, outputs), "router"),
                (moe + ("router_bias",), (outputs,), "bias"),
                (moe + ("w1",), (held, d, f_exp), "matrix"),
                (moe + ("w3",), (held, d, f_exp), "matrix"),
                (moe + ("w2",), (held, f_exp, d), "matrix"),
            ]
        out += [(ff + ("w1", "kernel"), (d, width), "matrix"),
                (ff + ("w3", "kernel"), (d, width), "matrix"),
                (ff + ("w2", "kernel"), (width, d), "matrix")]
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
    jit.  The router's matrix and bias stay float32 whatever ``dtype``
    is: the program routes in float32.  Returns ``(params, None)``."""
    std = sz["initializer_range"]
    tree = {}
    for i, (path, shape, kind) in enumerate(_leaves(sz)):
        if only is not None and not only(path):
            continue
        if kind == "scale":
            leaf = jnp.ones(shape, jnp.float32)
        else:
            scale = sz["router_bias_std"] if kind == "bias" else std
            leaf = scale * jax.random.normal(jax.random.fold_in(key, i),
                                             shape, jnp.float32)
            if kind == "matrix":
                leaf = leaf.astype(dtype)
        _put(tree, path, leaf)
    return tree, None


# ------------------------------------------------------------------ #
# the system under test
# ------------------------------------------------------------------ #
def model_config(sz: dict, **overrides):
    from bluefog_tpu.models.afmoe import AfmoeConfig

    # the program's model has these three built in, not as options
    if not (sz["route_norm"] and sz["mup_enabled"]
            and sz["num_shared_experts"] == 1):
        raise ValueError(
            "the program's afmoe model normalises the chosen scores, "
            "scales the embedding by sqrt(hidden_size) and has one shared "
            "expert; the configuration asks for route_norm="
            f"{sz['route_norm']}, mup_enabled={sz['mup_enabled']}, "
            f"num_shared_experts={sz['num_shared_experts']}")
    kept = sz.get("layers_kept") or range(sz["num_hidden_layers"])
    base = dict(
        vocab_size=sz["vocab_size"], dim=sz["hidden_size"],
        n_heads=sz["num_attention_heads"],
        n_kv_heads=sz["num_key_value_heads"], head_dim=sz["head_dim"],
        layer_types=tuple(sz["layer_types"][i] for i in kept),
        window=sz["sliding_window"], n_dense_layers=sz["num_dense_layers"],
        dense_hidden_dim=sz["intermediate_size"],
        expert_hidden_dim=sz["moe_intermediate_size"],
        n_experts=sz["router_outputs"], top_k=sz["num_experts_per_tok"],
        route_scale=float(sz["route_scale"]),
        experts_held=(sz.get("experts_held_from", 0), sz["num_experts"]),
        rope_theta=float(sz["rope_theta"]), norm_eps=sz["rms_norm_eps"],
        initializer_range=sz["initializer_range"],
        dtype=dtype_of(sz["compute_dtype"]))
    base.update(overrides)
    if len(base["layer_types"]) != sz["num_hidden_layers"]:
        raise ValueError("layers_kept and num_hidden_layers disagree")
    return AfmoeConfig(**base)


def serving_engine(sz: dict, traffic: dict, params):
    """The ``ServingEngine`` of the traffic file's ``engine`` section
    over ``params`` (held in the cut's ``param_dtype``)."""
    from bluefog_tpu.serving import ServingEngine

    cfg = model_config(sz, max_seq_len=traffic["engine"]["max_len"])
    return ServingEngine({"params": params}, cfg, **traffic["engine"])
