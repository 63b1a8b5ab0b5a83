"""ResNet with bottleneck blocks, built as
``bluefog_tpu.models.ResNet(stage_sizes=..., block_cls=BottleneckBlock)``
and trained through ``build_train_step``'s ``has_aux`` branch (batch
statistics are the mutable state).

The benchmark makes the weights itself, from the seed, under the names
the program's flax module gives its leaves; the same tree goes to the
plain reference as data.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ITEM = "image"


def sizes(config: dict, cut=None) -> dict:
    return {k: v for k, v in config.items()
            if k not in ("assumed", "reduced")}


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def blocks(sz: dict):
    """(block name, input channels, bottleneck width, stride) of every
    bottleneck, in order."""
    out, cin, idx = [], sz["num_filters"], 0
    for i, count in enumerate(sz["stage_sizes"]):
        width = sz["num_filters"] * 2 ** i
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            out.append((f"BottleneckBlock_{idx}", cin, width, stride))
            cin = width * sz["expansion"]
            idx += 1
    return out


def _leaves(sz: dict):
    """(collection, path, shape, kind) of every leaf, in a fixed order.
    Kinds: conv, scale, zero_scale (a bottleneck's last batch norm),
    bias, dense, dense_bias, mean, var."""
    out = []

    def norm(prefix, c, last=False):
        out.extend([
            ("params", prefix + ("scale",), (c,),
             "zero_scale" if last else "scale"),
            ("params", prefix + ("bias",), (c,), "bias"),
            ("batch_stats", prefix + ("mean",), (c,), "mean"),
            ("batch_stats", prefix + ("var",), (c,), "var")])

    f = sz["num_filters"]
    out.append(("params", ("conv_init", "kernel"), (7, 7, 3, f), "conv"))
    norm(("bn_init",), f)
    for name, cin, width, stride in blocks(sz):
        cout = width * sz["expansion"]
        out.append(("params", (name, "Conv_0", "kernel"),
                    (1, 1, cin, width), "conv"))
        norm((name, "BatchNorm_0"), width)
        out.append(("params", (name, "Conv_1", "kernel"),
                    (3, 3, width, width), "conv"))
        norm((name, "BatchNorm_1"), width)
        out.append(("params", (name, "Conv_2", "kernel"),
                    (1, 1, width, cout), "conv"))
        norm((name, "BatchNorm_2"), cout, last=True)
        if stride != 1 or cin != cout:
            out.append(("params", (name, "conv_proj", "kernel"),
                        (1, 1, cin, cout), "conv"))
            norm((name, "norm_proj"), cout)
    c = sz["num_filters"] * 2 ** (len(sz["stage_sizes"]) - 1) \
        * sz["expansion"]
    out.append(("params", ("Dense_0", "kernel"), (c, sz["num_classes"]),
                "dense"))
    out.append(("params", ("Dense_0", "bias"), (sz["num_classes"],),
                "dense_bias"))
    return out


def _put(tree: dict, path, leaf) -> None:
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = leaf


def make_params(sz: dict, key, dtype):
    """``(params, batch_stats)``: convolutions normal(0, sqrt(2 /
    fan_out)); batch-norm scale 1 and bias 0, but the scale of each
    bottleneck's last batch norm 0, so that a block starts as the
    identity (arXiv:1706.02677 section 5.1; the program's own model does
    the same); running mean 0 and variance 1; head
    uniform(+-1/sqrt(fan_in)).  Traceable."""
    trees = {"params": {}, "batch_stats": {}}
    for i, (coll, path, shape, kind) in enumerate(_leaves(sz)):
        k = jax.random.fold_in(key, i)
        if kind == "conv":
            fan_out = shape[0] * shape[1] * shape[3]
            leaf = math.sqrt(2.0 / fan_out) * jax.random.normal(
                k, shape, jnp.float32)
        elif kind in ("dense", "dense_bias"):
            bound = 1.0 / math.sqrt(_leaves(sz)[-2][2][0])
            leaf = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        elif kind in ("scale", "var"):
            leaf = jnp.ones(shape, jnp.float32)
        else:
            leaf = jnp.zeros(shape, jnp.float32)
        _put(trees[coll], path, leaf.astype(dtype))
    return trees["params"], trees["batch_stats"]


def make_batch(sz: dict, traffic: dict, key, n_ranks: int):
    """``(images [ranks, batch, S, S, 3]`` normal, in the compute
    dtype, ``labels [ranks, batch])``, every row different."""
    b, s = traffic["batch_per_chip"], sz["image_size"]
    k1, k2 = jax.random.split(key)
    images = jax.random.normal(k1, (n_ranks, b, s, s, 3), jnp.float32)
    labels = jax.random.randint(k2, (n_ranks, b), 0, sz["num_classes"],
                                jnp.int32)
    return images.astype(dtype_of(sz["compute_dtype"])), labels


def items_per_rank_step(sz: dict, traffic: dict) -> int:
    return traffic["batch_per_chip"]


def train_loss(sz: dict, traffic: dict):
    """``(loss_fn, has_aux)``: softmax cross-entropy with train-mode
    batch norm; the new batch statistics are the step's ``aux``."""
    import optax

    from bluefog_tpu.models.resnet import BottleneckBlock, ResNet

    model = ResNet(stage_sizes=tuple(sz["stage_sizes"]),
                   block_cls=BottleneckBlock,
                   num_classes=sz["num_classes"],
                   num_filters=sz["num_filters"],
                   dtype=dtype_of(sz["compute_dtype"]),
                   pallas_conv1x1=sz["pallas_conv1x1"])

    def loss_fn(params, aux, batch):
        images, labels = batch
        logits, updates = model.apply(
            {"params": params, "batch_stats": aux}, images, train=True,
            mutable=["batch_stats"])
        loss = jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
            logits, labels))
        return loss, updates["batch_stats"]

    return loss_fn, True
