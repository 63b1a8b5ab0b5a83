"""The latent-attention decoder whose residual path is four streams mixed
a token at a time by manifold-constrained hyper-connections, with a
leading dense layer and sigmoid-routed experts beside a shared one
(``model_type: xing4_0``), built as
``bluefog_tpu.models.mla_moe.MlaMoe(MlaMoeConfig(hc_mult=4, ...))`` and
served through the program's normal ``ServingEngine``.

The benchmark makes the weights itself, from the seed, as a tree in the
layout the program's model takes (flax names); the same tree is handed
to the plain reference as data.  Every matrix is normal(0,
``initializer_range``), norm scales are 1, the router's bias a seeded
draw of ``router_bias_std``, and the mixing parameters the
configuration file's ``assumed`` draw: ``phi`` normal(0, ``hc_phi_std``),
the three ``alpha`` ``hc_alpha``, the mixing biases 0: large enough that
the coefficients differ from token to token by far more than the output
check's tolerance, so that a mixing left out would fail it.
"""

from __future__ import annotations

import importlib.util

import jax
import jax.numpy as jnp

from perfbench.families.mla_moe_decoder import (  # noqa: F401
    ITEM, _put, dtype_of, sizes)

# a program without the residual path cannot run this family: say so
# when the cell is loaded, before any weight is drawn or program compiled
if importlib.util.find_spec("bluefog_tpu.models.hyper_connections") is None:
    raise ImportError(
        "the program has no bluefog_tpu.models.hyper_connections: the "
        "mhc_mla_moe_decoder family needs the residual path that PR 32 "
        "added")


# ------------------------------------------------------------------ #
# weights from the seed
# ------------------------------------------------------------------ #
def _leaves(sz: dict):
    """(path, shape, kind) of every parameter leaf, in a fixed order."""
    d, v, h = sz["hidden_size"], sz["vocab_size"], sz["num_attention_heads"]
    rq, dc = sz["q_lora_rank"], sz["kv_lora_rank"]
    dn, dr, dv = (sz["qk_nope_head_dim"], sz["qk_rope_head_dim"],
                  sz["v_head_dim"])
    f_dense, f = sz["intermediate_size"], sz["moe_intermediate_size"]
    held, outputs, n = (sz["n_routed_experts"], sz["router_outputs"],
                        sz["hc_mult"])
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
        ]
        for mix in ((layer, "attention_hc"), (layer, "ffn_hc")):
            out += [(mix + ("phi",), (n * d, 2 * n + n * n), "phi"),
                    (mix + ("alpha",), (3,), "alpha"),
                    (mix + ("b_pre",), (n,), "zero"),
                    (mix + ("b_post",), (n,), "zero"),
                    (mix + ("b_res",), (n, n), "zero")]
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
    jit.  The router's matrix and bias and the mixing parameters stay
    float32 whatever ``dtype`` is: the program routes and mixes in
    float32.  Returns ``(params, None)``."""
    stds = {"matrix": sz["initializer_range"],
            "router": sz["initializer_range"],
            "bias": sz["router_bias_std"], "phi": sz["hc_phi_std"]}
    consts = {"scale": 1.0, "alpha": sz["hc_alpha"], "zero": 0.0}
    tree = {}
    for i, (path, shape, kind) in enumerate(_leaves(sz)):
        if only is not None and not only(path):
            continue
        if kind in consts:
            leaf = jnp.full(shape, consts[kind], jnp.float32)
        else:
            leaf = stds[kind] * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            if kind == "matrix":
                leaf = leaf.astype(dtype)
        _put(tree, path, leaf)
    return tree, None


# ------------------------------------------------------------------ #
# the system under test
# ------------------------------------------------------------------ #
def model_config(sz: dict, **overrides):
    from bluefog_tpu.models.mla_moe import MlaMoeConfig

    rope = sz["rope_scaling"]
    # the program's model has these built in, not as options
    if not (sz["norm_topk_prob"] and sz["n_shared_experts"] == 1
            and sz["n_group"] == 1 and sz["topk_group"] == 1
            and sz["moe_layer_freq"] == 1 and rope["type"] == "yarn"):
        raise ValueError(
            "the program's latent-attention model normalises the chosen "
            "scores, has one shared expert, an expert layer in every "
            "layer after the dense ones, routes without groups and "
            "rotates at YaRN's frequencies; the configuration asks for "
            "something else")
    base = dict(
        vocab_size=sz["vocab_size"], dim=sz["hidden_size"],
        n_layers=sz["num_hidden_layers"], n_heads=sz["num_attention_heads"],
        q_lora_rank=sz["q_lora_rank"], kv_lora_rank=sz["kv_lora_rank"],
        qk_nope_head_dim=sz["qk_nope_head_dim"],
        qk_rope_head_dim=sz["qk_rope_head_dim"], v_head_dim=sz["v_head_dim"],
        expert_hidden_dim=sz["moe_intermediate_size"],
        n_experts=sz["router_outputs"], top_k=sz["num_experts_per_tok"],
        route_scale=float(sz["routed_scaling_factor"]),
        score_func=sz["scoring_func"],
        experts_held=(sz.get("experts_held_from", 0), sz["n_routed_experts"]),
        n_dense_layers=sz["first_k_dense_replace"],
        dense_hidden_dim=sz["intermediate_size"],
        hc_mult=sz["hc_mult"], hc_sinkhorn_iters=sz["hc_sinkhorn_iters"],
        hc_eps=sz["hc_eps"],
        hc_res_clamp=(float(sz["mhc_h_res_clamp_min"]),
                      float(sz["mhc_h_res_clamp_max"])),
        rope_theta=float(sz["rope_theta"]),
        rope_factor=float(rope["factor"]),
        rope_original_max=rope["original_max_position_embeddings"],
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        query_scale_beta=0.0,
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
