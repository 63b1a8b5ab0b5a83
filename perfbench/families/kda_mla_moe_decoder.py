"""The decoder whose layers are of two kinds, Kimi Delta Attention (a
recurrent mixer with a matrix of state a head) five to one latent
attention, with a leading dense layer and sigmoid-routed experts chosen
inside the best groups beside a shared one (Ling-3.0-flash-VL's language
model), built as ``bluefog_tpu.models.mla_moe.MlaMoe(MlaMoeConfig(
layer_types=..., q_lora_rank=None, head_gate=True, n_group=8, ...))``
and served through the program's normal ``ServingEngine``.

The benchmark makes the weights itself, from the seed, as a tree in the
layout the program's model takes (flax names); the same tree is handed
to the plain reference as data.  Every matrix is normal(0,
``initializer_range``), norm scales are 1, the router's bias a seeded
draw of ``router_bias_std``, and the recurrent mixer's own parameters
the configuration file's ``assumed`` draw (``kda_draw``): the filters of
the convolution normal(0, ``kda_conv_std``), ``A_log`` uniform on (0,
``kda_a_log_max``), ``dt_bias`` normal(0, ``kda_dt_bias_std``) and
``W_beta`` normal(0, ``kda_beta_std``): wide enough that the decay
spreads over (e^-5, 1) and beta over (0, 1), so that a decay, a delta
term or a convolution left out fails the output check.
"""

from __future__ import annotations

import importlib.util

import jax
import jax.numpy as jnp

from perfbench.families.mla_moe_decoder import (  # noqa: F401
    ITEM, _put, dtype_of, sizes)

# a program without the recurrent mixer cannot run this family: say so
# when the cell is loaded, before any weight is drawn or program compiled
if importlib.util.find_spec("bluefog_tpu.models.kda") is None:
    raise ImportError(
        "the program has no bluefog_tpu.models.kda: the kda_mla_moe_decoder "
        "family needs the recurrent mixer and the state leaf that PR 38 "
        "added")


def layer_types(sz: dict) -> tuple:
    """The mixer of each layer kept, from the PUBLISHED index of the
    layer: ``(i + 1) % layer_group_size == 0`` is latent attention,
    every other layer Kimi Delta Attention."""
    kept = sz.get("published_layers", range(sz["num_hidden_layers"]))
    return tuple("latent" if (i + 1) % sz["layer_group_size"] == 0
                 else "kda" for i in kept)


# ------------------------------------------------------------------ #
# weights from the seed
# ------------------------------------------------------------------ #
def _leaves(sz: dict):
    """(path, shape, kind) of every parameter leaf, in a fixed order."""
    d, v, h = sz["hidden_size"], sz["vocab_size"], sz["num_attention_heads"]
    dc, dn, dr, dv = (sz["kv_lora_rank"], sz["qk_nope_head_dim"],
                      sz["qk_rope_head_dim"], sz["v_head_dim"])
    dk, taps = sz["head_dim"], sz["short_conv_kernel_size"]
    f_dense, f = sz["intermediate_size"], sz["moe_intermediate_size"]
    held, outputs = sz["num_experts"], sz["router_outputs"]
    out = [(("tok_embeddings", "embedding"), (v, d), "matrix")]
    for i, kind in enumerate(layer_types(sz)):
        layer = f"layer_{i}"
        att, moe = (layer, "attention"), (layer, "moe")
        if kind == "kda":
            out += [(att + (name, "kernel"), (d, h * dk), "matrix")
                    for name in ("wq", "wk", "wv")]
            out += [(att + (f"conv_{c}",), (taps, h * dk), "conv")
                    for c in "qkv"]
            out += [
                (att + ("A_log",), (h,), "a_log"),
                (att + ("dt_bias",), (h * dk,), "dt_bias"),
                (att + ("wf", "kernel"), (d, h * dk), "matrix"),
                (att + ("wbeta", "kernel"), (d, h), "beta"),
                (att + ("o_norm", "scale"), (dk,), "scale"),
                (att + ("wg", "kernel"), (d, h * dk), "matrix"),
                (att + ("wo", "kernel"), (h * dk, d), "matrix"),
            ]
        else:
            out += [
                (att + ("wq", "kernel"), (d, h * (dn + dr)), "matrix"),
                (att + ("wkv_a", "kernel"), (d, dc + dr), "matrix"),
                (att + ("kv_norm", "scale"), (dc,), "scale"),
                (att + ("wkv_b",), (dc, h, dn + dv), "matrix"),
                (att + ("wgate", "kernel"), (d, h), "matrix"),
                (att + ("wo", "kernel"), (h * dv, d), "matrix"),
            ]
        out += [((layer, "attention_norm", "scale"), (d,), "scale"),
                ((layer, "ffn_norm", "scale"), (d,), "scale")]
        if i < sz["first_k_dense_replace"]:
            ff, width = (layer, "feed_forward"), f_dense
        else:
            ff, width = moe + ("shared",), f
            out += [(moe + ("router",), (d, outputs), "router"),
                    (moe + ("router_bias",), (outputs,), "bias"),
                    (moe + ("w1",), (held, d, f), "matrix"),
                    (moe + ("w3",), (held, d, f), "matrix"),
                    (moe + ("w2",), (held, f, d), "matrix")]
        out += [(ff + ("w1", "kernel"), (d, width), "matrix"),
                (ff + ("w3", "kernel"), (d, width), "matrix"),
                (ff + ("w2", "kernel"), (width, d), "matrix")]
    out += [(("norm", "scale"), (d,), "scale"),
            (("output",), (d, v), "matrix")]
    return out


def make_params(sz: dict, key, dtype, only=None):
    """The parameter tree, every leaf from ``fold_in(key, its index)``
    (``only``: a predicate on the path).  Traceable: call it inside one
    jit.  The router's matrix and bias, the filters, ``A_log`` and
    ``dt_bias`` stay float32 whatever ``dtype`` is.  Returns ``(params,
    None)``."""
    stds = {"matrix": sz["initializer_range"],
            "router": sz["initializer_range"],
            "bias": sz["router_bias_std"], "conv": sz["kda_conv_std"],
            "dt_bias": sz["kda_dt_bias_std"], "beta": sz["kda_beta_std"]}
    tree = {}
    for i, (path, shape, kind) in enumerate(_leaves(sz)):
        if only is not None and not only(path):
            continue
        k = jax.random.fold_in(key, i)
        if kind == "scale":
            leaf = jnp.ones(shape, jnp.float32)
        elif kind == "a_log":
            leaf = jax.random.uniform(k, shape, jnp.float32, 0.0,
                                      sz["kda_a_log_max"])
        else:
            leaf = stds[kind] * jax.random.normal(k, shape, jnp.float32)
            if kind in ("matrix", "beta"):
                leaf = leaf.astype(dtype)
        _put(tree, path, leaf)
    return tree, None


# ------------------------------------------------------------------ #
# the system under test
# ------------------------------------------------------------------ #
def model_config(sz: dict, **overrides):
    from bluefog_tpu.models.mla_moe import MlaMoeConfig

    # the program's model has these built in, not as options
    if not (sz["norm_topk_prob"] and sz["moe_router_enable_expert_bias"]
            and sz["q_lora_rank"] is None and sz["kda_safe_gate"]
            and sz["linear_silu"] and sz["group_norm_size"] == 1
            and sz["gated_attention_proj_granularity_type"] == "head_wise"
            and not (sz["use_nGPT"] or sz["value_norm"] or sz["up_proj_norm"]
                     or sz["scale_router_input"] or sz["use_kda_lora"])
            and sz["moe_shared_expert_intermediate_size"]
            == sz["moe_intermediate_size"]
            and sz["head_dim"] == sz["qk_nope_head_dim"] == sz["v_head_dim"]
            and sz["rotary_dim"] == sz["qk_rope_head_dim"]):
        raise ValueError(
            "the program's model of two layer kinds normalises the chosen "
            "scores, selects with a bias, takes the latent layer's query "
            "straight from the input, gates its heads, bounds the "
            "recurrent layer's decay from below and has one shared expert "
            "of the routed width; the configuration asks for something "
            "else")
    base = dict(
        vocab_size=sz["vocab_size"], dim=sz["hidden_size"],
        n_layers=sz["num_hidden_layers"], n_heads=sz["num_attention_heads"],
        layer_types=layer_types(sz), q_lora_rank=None, head_gate=True,
        kv_lora_rank=sz["kv_lora_rank"],
        qk_nope_head_dim=sz["qk_nope_head_dim"],
        qk_rope_head_dim=sz["qk_rope_head_dim"], v_head_dim=sz["v_head_dim"],
        kda_head_dim=sz["head_dim"],
        kda_conv_kernel=sz["short_conv_kernel_size"],
        kda_lower_bound=float(sz["kda_lower_bound"]),
        expert_hidden_dim=sz["moe_intermediate_size"],
        n_experts=sz["router_outputs"], top_k=sz["num_experts_per_tok"],
        n_group=sz["n_group"], topk_group=sz["topk_group"],
        route_scale=float(sz["routed_scaling_factor"]),
        score_func=sz["score_function"],
        experts_held=(sz.get("experts_held_from", 0), sz["num_experts"]),
        n_dense_layers=sz["first_k_dense_replace"],
        dense_hidden_dim=sz["intermediate_size"],
        rope_theta=float(sz["rope_theta"]), rope_factor=1.0,
        rope_original_max=sz["max_position_embeddings"],
        query_scale_beta=0.0, norm_eps=sz["rms_norm_eps"],
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
