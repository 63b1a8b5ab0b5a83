"""Pre-norm decoder with grouped-query attention, RoPE and SwiGLU,
built as ``bluefog_tpu.models.Llama(LlamaConfig(...))`` through the
program's normal entry points.

The benchmark makes the weights itself, from the seed, as a tree in
the layout the program's model takes (flax names); the same tree is
handed to the plain reference as data.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ITEM = "token"


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
    d, f, v = sz["hidden_size"], sz["intermediate_size"], sz["vocab_size"]
    hd = sz["head_dim"]
    nq, nkv = sz["num_attention_heads"], sz["num_key_value_heads"]
    out = [(("tok_embeddings", "embedding"), (v, d), "matrix")]
    for i in range(sz["num_hidden_layers"]):
        layer = f"layer_{i}"
        out += [
            ((layer, "attention", "wq", "kernel"), (d, nq * hd), "matrix"),
            ((layer, "attention", "wk", "kernel"), (d, nkv * hd), "matrix"),
            ((layer, "attention", "wv", "kernel"), (d, nkv * hd), "matrix"),
            ((layer, "attention", "wo", "kernel"), (nq * hd, d), "matrix"),
            ((layer, "attention_norm", "scale"), (d,), "scale"),
            ((layer, "feed_forward", "w1", "kernel"), (d, f), "matrix"),
            ((layer, "feed_forward", "w2", "kernel"), (f, d), "matrix"),
            ((layer, "feed_forward", "w3", "kernel"), (d, f), "matrix"),
            ((layer, "ffn_norm", "scale"), (d,), "scale"),
        ]
    out += [(("norm", "scale"), (d,), "scale"),
            (("output", "kernel"), (d, v), "matrix")]
    return out


def _put(tree: dict, path, leaf) -> None:
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = leaf


def make_params(sz: dict, key, dtype, only=None):
    """The parameter tree, every leaf from ``fold_in(key, its index)``
    so that one layer can be made again alone (``only``: a predicate on
    the path).  Traceable: call it inside one jit.  Returns
    ``(params, aux)`` with ``aux`` None (no mutable model state)."""
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
        _put(tree, path, leaf.astype(dtype))
    return tree, None


def make_batch(sz: dict, traffic: dict, key, n_ranks: int):
    """Uniform token ids, every row different: ``[ranks, batch, seq+1]``
    int32; inputs are ``[..., :-1]`` and targets ``[..., 1:]``."""
    b, t = traffic["batch_per_chip"], traffic["seq_len"]
    return jax.random.randint(key, (n_ranks, b, t + 1), 0, sz["vocab_size"],
                              jnp.int32)


def items_per_rank_step(sz: dict, traffic: dict) -> int:
    return traffic["batch_per_chip"] * traffic["seq_len"]


# ------------------------------------------------------------------ #
# the system under test
# ------------------------------------------------------------------ #
def llama_config(sz: dict, **overrides):
    from bluefog_tpu import models

    base = dict(
        vocab_size=sz["vocab_size"], dim=sz["hidden_size"],
        n_layers=sz["num_hidden_layers"], n_heads=sz["num_attention_heads"],
        n_kv_heads=sz["num_key_value_heads"],
        hidden_dim=sz["intermediate_size"],
        max_seq_len=sz["max_position_embeddings"],
        rope_theta=float(sz["rope_theta"]), norm_eps=sz["rms_norm_eps"],
        dtype=dtype_of(sz["compute_dtype"]))
    base.update(overrides)
    cfg = models.LlamaConfig(**base)
    if cfg.head_dim != sz["head_dim"]:
        raise ValueError(f"head_dim {cfg.head_dim} != {sz['head_dim']}")
    return cfg


def train_loss(sz: dict, traffic: dict):
    """``(loss_fn, has_aux)`` for ``build_train_step``: next-token
    cross-entropy of the model on one rank's ``[batch, seq+1]`` ids."""
    import optax

    from bluefog_tpu import models

    cfg = llama_config(sz, attn_impl=sz["attn_impl"], remat=sz["remat"],
                       max_seq_len=traffic["seq_len"])
    model = models.Llama(cfg)

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch[:, :-1])
        return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
            logits, batch[:, 1:]))

    return loss_fn, False


def serving_engine(sz: dict, traffic: dict, params):
    """The ``ServingEngine`` of the traffic file's ``engine`` section
    over ``params`` (held in the cut's ``param_dtype``)."""
    from bluefog_tpu.serving import ServingEngine

    cfg = llama_config(sz, max_seq_len=traffic["engine"]["max_len"])
    return ServingEngine({"params": params}, cfg, **traffic["engine"])
