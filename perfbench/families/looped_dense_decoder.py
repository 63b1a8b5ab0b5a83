"""A dense decoder whose layers run several times over shared weights
(Ouro-2.6B), built as ``bluefog_tpu.models.looped.LoopedConfig(block=
LlamaConfig(...), loop_steps=total_ut_steps)`` and served through the
program's normal ``ServingEngine``.

The benchmark makes the weights itself, from the seed, as a tree in the
layout the program's model takes (flax names, the layers stacked under
``layers/block``); the same tree is handed to the plain reference as
data.  Every matrix is normal(0, ``initializer_range``), norm scales
are 1, the exit gate's bias 0.
"""

from __future__ import annotations

import importlib.util

import jax
import jax.numpy as jnp

from perfbench.families.dense_gqa_decoder import (  # noqa: F401
    ITEM, _put, dtype_of, sizes)

# a program without the looped stack cannot run this family: say so when
# the cell is loaded, before any weight is drawn or program compiled
if importlib.util.find_spec("bluefog_tpu.models.looped") is None:
    raise ImportError(
        "the program has no bluefog_tpu.models.looped: the "
        "looped_dense_decoder family needs the stack that runs several "
        "times over shared weights, which PR 40 added")


# ------------------------------------------------------------------ #
# weights from the seed
# ------------------------------------------------------------------ #
def _leaves(sz: dict):
    """(path, shape, kind) of every parameter leaf, in a fixed order;
    a layer's leaves carry the leading ``[num_hidden_layers]`` axis."""
    d, f, v = sz["hidden_size"], sz["intermediate_size"], sz["vocab_size"]
    hd, n = sz["head_dim"], sz["num_hidden_layers"]
    nq, nkv = sz["num_attention_heads"], sz["num_key_value_heads"]
    block = ("layers", "block")
    out = [(("tok_embeddings", "embedding"), (v, d), "matrix")]
    out += [
        (block + ("attention", "wq", "kernel"), (n, d, nq * hd), "matrix"),
        (block + ("attention", "wk", "kernel"), (n, d, nkv * hd), "matrix"),
        (block + ("attention", "wv", "kernel"), (n, d, nkv * hd), "matrix"),
        (block + ("attention", "wo", "kernel"), (n, nq * hd, d), "matrix"),
        (block + ("feed_forward", "w1", "kernel"), (n, d, f), "matrix"),
        (block + ("feed_forward", "w2", "kernel"), (n, f, d), "matrix"),
        (block + ("feed_forward", "w3", "kernel"), (n, d, f), "matrix"),
    ]
    out += [(block + (name, "scale"), (n, d), "scale")
            for name in ("attention_norm", "attention_post_norm",
                         "ffn_norm", "ffn_post_norm")]
    out += [(("norm", "scale"), (d,), "scale"),
            (("output", "kernel"), (d, v), "matrix"),
            (("exit_gate", "kernel"), (d, 1), "matrix"),
            (("exit_gate", "bias"), (1,), "zero")]
    return out


def make_params(sz: dict, key, dtype, only=None):
    """The parameter tree, every leaf from ``fold_in(key, its index)``
    (``only``: a predicate on the path).  Traceable: call it inside one
    jit.  Returns ``(params, aux)`` with ``aux`` None."""
    std = sz["initializer_range"]
    tree = {}
    for i, (path, shape, kind) in enumerate(_leaves(sz)):
        if only is not None and not only(path):
            continue
        if kind == "scale":
            leaf = jnp.ones(shape, jnp.float32)
        elif kind == "zero":
            leaf = jnp.zeros(shape, jnp.float32)
        else:
            leaf = std * jax.random.normal(jax.random.fold_in(key, i),
                                           shape, jnp.float32)
        _put(tree, path, leaf.astype(dtype))
    return tree, None


# ------------------------------------------------------------------ #
# the system under test
# ------------------------------------------------------------------ #
def model_config(sz: dict, **overrides):
    from bluefog_tpu import models
    from bluefog_tpu.models.looped import LoopedConfig

    base = dict(
        vocab_size=sz["vocab_size"], dim=sz["hidden_size"],
        n_layers=sz["num_hidden_layers"], n_heads=sz["num_attention_heads"],
        n_kv_heads=sz["num_key_value_heads"],
        hidden_dim=sz["intermediate_size"],
        max_seq_len=sz["max_position_embeddings"],
        rope_theta=float(sz["rope_theta"]), norm_eps=sz["rms_norm_eps"],
        dtype=dtype_of(sz["compute_dtype"]))
    base.update(overrides)
    block = models.LlamaConfig(**base)
    if block.head_dim != sz["head_dim"]:
        raise ValueError(f"head_dim {block.head_dim} != {sz['head_dim']}")
    if sz["early_exit_threshold"] != 1:
        raise ValueError(
            "the program serves the exit threshold of 1 (every token runs "
            f"every pass), not {sz['early_exit_threshold']}")
    return LoopedConfig(block, loop_steps=sz["total_ut_steps"],
                        initializer_range=sz["initializer_range"])


def serving_engine(sz: dict, traffic: dict, params):
    """The ``ServingEngine`` of the traffic file's ``engine`` section
    over ``params`` (held in the cut's ``param_dtype``)."""
    from bluefog_tpu.serving import ServingEngine

    cfg = model_config(sz, max_seq_len=traffic["engine"]["max_len"])
    return ServingEngine({"params": params}, cfg, **traffic["engine"])
