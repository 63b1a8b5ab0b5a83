"""A dense decoder whose every layer holds a Mamba-2 state-space mixer
and grouped-query attention side by side (Falcon-H1-34B-Instruct), built
as ``bluefog_tpu.models.hybrid_ssm.HybridSsmConfig`` and served through
the program's normal ``ServingEngine``.

The benchmark makes the weights itself, from the seed, as a tree in the
layout the program's model takes (flax names); the same tree is handed
to the plain reference as data.  Matrices are normal(0,
``initializer_range``), norm scales 1; the state-space mixer's own
parameters follow Mamba-2's published initialisation and stay float32
whatever the cut's ``param_dtype``: ``D`` 1, ``A_log`` the log of
uniform(1, 16) a head, ``dt_bias`` the inverse softplus of a step drawn
log-uniform in (0.001, 0.1), the convolution's filters normal(0,
``conv_std``) and its bias normal(0, ``initializer_range``).
"""

from __future__ import annotations

import importlib.util
import math

import jax
import jax.numpy as jnp

from perfbench.families.dense_gqa_decoder import (  # noqa: F401
    ITEM, _put, dtype_of, sizes)

# a program without the state-space mixer cannot run this family: say so
# when the cell is loaded, before any weight is drawn or program compiled
if importlib.util.find_spec("bluefog_tpu.models.hybrid_ssm") is None:
    raise ImportError(
        "the program has no bluefog_tpu.models.hybrid_ssm: the "
        "ssm_gqa_parallel_decoder family needs the layer that holds a "
        "state-space mixer beside its attention, which PR 45 added")


# ------------------------------------------------------------------ #
# weights from the seed
# ------------------------------------------------------------------ #
def _leaves(sz: dict):
    """(path, shape, kind) of every parameter leaf, in a fixed order."""
    d, f, v = sz["hidden_size"], sz["intermediate_size"], sz["vocab_size"]
    hd = sz["head_dim"]
    nq, nkv = sz["num_attention_heads"], sz["num_key_value_heads"]
    h, taps = sz["mamba_n_heads"], sz["mamba_d_conv"]
    inner = h * sz["mamba_d_head"]
    if inner != sz["mamba_d_ssm"]:
        raise ValueError(f"mamba_n_heads x mamba_d_head = {inner} is not "
                         f"mamba_d_ssm {sz['mamba_d_ssm']}")
    conv = inner + 2 * sz["mamba_n_groups"] * sz["mamba_d_state"]
    out = [(("tok_embeddings", "embedding"), (v, d), "matrix")]
    for i in range(sz["num_hidden_layers"]):
        layer = f"layer_{i}"
        out += [
            ((layer, "attention", "wq", "kernel"), (d, nq * hd), "matrix"),
            ((layer, "attention", "wk", "kernel"), (d, nkv * hd), "matrix"),
            ((layer, "attention", "wv", "kernel"), (d, nkv * hd), "matrix"),
            ((layer, "attention", "wo", "kernel"), (nq * hd, d), "matrix"),
            ((layer, "attention_norm", "scale"), (d,), "scale"),
            ((layer, "mamba", "in_proj", "kernel"),
             (d, inner + conv + h), "matrix"),
            ((layer, "mamba", "out_proj", "kernel"), (inner, d), "matrix"),
            ((layer, "mamba", "conv_kernel"), (taps, conv), "filters"),
            ((layer, "mamba", "conv_bias"), (conv,), "bias"),
            ((layer, "mamba", "A_log"), (h,), "a_log"),
            ((layer, "mamba", "D"), (h,), "one"),
            ((layer, "mamba", "dt_bias"), (h,), "dt_bias"),
            ((layer, "mamba", "norm"), (inner,), "one"),
            ((layer, "w1", "kernel"), (d, f), "matrix"),
            ((layer, "w2", "kernel"), (f, d), "matrix"),
            ((layer, "w3", "kernel"), (d, f), "matrix"),
            ((layer, "ffn_norm", "scale"), (d,), "scale"),
        ]
    out += [(("norm", "scale"), (d,), "scale"),
            (("output", "kernel"), (d, v), "matrix")]
    return out


def make_params(sz: dict, key, dtype, only=None):
    """The parameter tree, every leaf from ``fold_in(key, its index)``
    (``only``: a predicate on the path).  Traceable: call it inside one
    jit.  Returns ``(params, aux)`` with ``aux`` None.  Matrices and the
    norms' scales are held in ``dtype``; the state-space mixer's own
    small parameters in float32."""
    std = sz["initializer_range"]
    f32 = jnp.float32
    tree = {}
    for i, (path, shape, kind) in enumerate(_leaves(sz)):
        if only is not None and not only(path):
            continue
        k = jax.random.fold_in(key, i)
        if kind == "matrix":
            leaf = (std * jax.random.normal(k, shape, f32)).astype(dtype)
        elif kind == "scale":
            leaf = jnp.ones(shape, dtype)
        elif kind == "one":
            leaf = jnp.ones(shape, f32)
        elif kind == "filters":
            leaf = sz["conv_std"] * jax.random.normal(k, shape, f32)
        elif kind == "bias":
            leaf = std * jax.random.normal(k, shape, f32)
        elif kind == "a_log":
            leaf = jnp.log(jax.random.uniform(k, shape, f32, 1.0, 16.0))
        else:
            # dt_bias: softplus(dt_bias) is log-uniform in (1e-3, 1e-1)
            step = jnp.exp(jax.random.uniform(
                k, shape, f32, math.log(1e-3), math.log(1e-1)))
            leaf = step + jnp.log(-jnp.expm1(-step))
        _put(tree, path, leaf)
    return tree, None


# ------------------------------------------------------------------ #
# the system under test
# ------------------------------------------------------------------ #
def model_config(sz: dict, **overrides):
    from bluefog_tpu.models.hybrid_ssm import HybridSsmConfig, StatedHeads

    if not sz["mamba_use_mlp"] or not sz["mamba_rms_norm"] \
            or sz["mamba_norm_before_gate"] or sz["attn_layer_indices"]:
        raise ValueError(
            "the program serves the published block: a SwiGLU in every "
            "layer, the gated norm after the gate, attention in every layer")
    base = dict(
        vocab_size=sz["vocab_size"], dim=sz["hidden_size"],
        n_layers=sz["num_hidden_layers"], n_heads=sz["num_attention_heads"],
        n_kv_heads=sz["num_key_value_heads"], head_size=sz["head_dim"],
        hidden_dim=sz["intermediate_size"],
        max_seq_len=sz["max_position_embeddings"],
        rope_theta=float(sz["rope_theta"]), norm_eps=sz["rms_norm_eps"],
        dtype=dtype_of(sz["compute_dtype"]))
    base.update(overrides)
    return HybridSsmConfig(
        StatedHeads(**base),
        ssm_heads=sz["mamba_n_heads"], ssm_head_dim=sz["mamba_d_head"],
        ssm_state=sz["mamba_d_state"], ssm_groups=sz["mamba_n_groups"],
        ssm_conv=sz["mamba_d_conv"], ssm_chunk=sz["mamba_chunk_size"],
        embedding_multiplier=sz["embedding_multiplier"],
        lm_head_multiplier=sz["lm_head_multiplier"],
        attention_in_multiplier=float(sz["attention_in_multiplier"]),
        attention_out_multiplier=sz["attention_out_multiplier"],
        key_multiplier=sz["key_multiplier"],
        ssm_in_multiplier=sz["ssm_in_multiplier"],
        ssm_out_multiplier=sz["ssm_out_multiplier"],
        ssm_multipliers=tuple(sz["ssm_multipliers"]),
        mlp_multipliers=tuple(sz["mlp_multipliers"]),
        initializer_range=sz["initializer_range"])


def serving_engine(sz: dict, traffic: dict, params):
    """The ``ServingEngine`` of the traffic file's ``engine`` section
    over ``params`` (held in the cut's ``param_dtype``)."""
    from bluefog_tpu.serving import ServingEngine

    cfg = model_config(sz, max_seq_len=traffic["engine"]["max_len"])
    return ServingEngine({"params": params}, cfg, **traffic["engine"])
