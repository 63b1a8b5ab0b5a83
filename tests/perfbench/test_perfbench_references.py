"""Each plain reference against the system at a tiny size in float32,
and the same comparison failing when the system runs in bfloat16
against the float32-tight tolerance."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import loader
from perfbench.runners.train import key_from_seed

from conftest import REPO, TINY_DECODER, TINY_RESNET

TIGHT = 2e-5   # float32 against float32, relative to the logits' spread


def modules(family):
    return (loader.load_module(REPO, "families", family),
            loader.load_module(REPO, "references", family))


def decoder_gap(compute_dtype):
    from bluefog_tpu import models

    fam, ref = modules("dense_gqa_decoder")
    sz = fam.sizes(TINY_DECODER, "train")
    sz["compute_dtype"] = compute_dtype
    params, _ = fam.make_params(sz, key_from_seed(2**31 + 5), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (48,), 0,
                                sz["vocab_size"])
    cfg = fam.llama_config(sz, max_seq_len=64)
    got = models.Llama(cfg).apply({"params": params}, tokens[None])[0]
    want = ref.logits(params, tokens, sz)
    return float(jnp.max(jnp.abs(got - want)) / jnp.std(want))


def test_decoder_reference_agrees_with_the_system_in_float32():
    assert decoder_gap("float32") < TIGHT


def test_decoder_in_bfloat16_fails_the_float32_tolerance():
    assert decoder_gap("bfloat16") > 10 * TIGHT


def test_decoder_reference_rows_select_positions():
    fam, ref = modules("dense_gqa_decoder")
    sz = fam.sizes(TINY_DECODER, "serve")
    params, _ = fam.make_params(sz, key_from_seed(3), jnp.float32)
    tokens = jnp.arange(32) % sz["vocab_size"]
    full = ref.logits(params, tokens, sz)
    rows = jnp.array([0, 7, 31])
    assert np.allclose(ref.logits(params, tokens, sz, rows=rows),
                       full[rows], atol=1e-6)
    # causal: a later token does not move an earlier position
    other = tokens.at[20].set(5)
    assert np.allclose(ref.logits(params, other, sz)[:20], full[:20],
                       atol=1e-6)


def test_decoder_weights_are_a_function_of_the_seed_alone():
    fam, _ = modules("dense_gqa_decoder")
    sz = fam.sizes(TINY_DECODER, "train")
    a, _ = fam.make_params(sz, key_from_seed(2**32 + 9), jnp.float32)
    b, _ = fam.make_params(sz, key_from_seed(2**32 + 9), jnp.float32)
    c, _ = fam.make_params(sz, key_from_seed(2**32 + 10), jnp.float32)
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not np.array_equal(la[0], lc[0])
    one, _ = fam.make_params(sz, key_from_seed(2**32 + 9), jnp.float32,
                             only=lambda path: path[0] == "layer_1")
    assert list(one) == ["layer_1"]
    assert np.array_equal(one["layer_1"]["feed_forward"]["w2"]["kernel"],
                          a["layer_1"]["feed_forward"]["w2"]["kernel"])
    # the tree is the one the program's own init makes
    from bluefog_tpu import models

    own = jax.eval_shape(lambda: models.Llama(fam.llama_config(sz)).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert jax.tree.structure(own) == jax.tree.structure(a)
    assert all(x.shape == y.shape for x, y in zip(
        jax.tree.leaves(own), la))


def resnet_gap(compute_dtype):
    fam, ref = modules("resnet")
    sz = dict(fam.sizes(TINY_RESNET), compute_dtype=compute_dtype)
    key = key_from_seed(11)
    params, stats = fam.make_params(sz, key, jnp.float32)
    batch = jax.tree.map(lambda x: x[0], fam.make_batch(
        sz, {"batch_per_chip": 4}, jax.random.fold_in(key, 1), 1))
    loss_fn, has_aux = fam.train_loss(sz, {})
    assert has_aux
    (got, got_stats), got_g = jax.value_and_grad(loss_fn, has_aux=True)(
        params, stats, batch)
    (want, want_stats), want_g = jax.value_and_grad(
        ref.loss, has_aux=True)(params, stats, batch, sz)
    assert jax.tree.structure(got_stats) == jax.tree.structure(want_stats)
    norm = lambda t: float(jnp.sqrt(sum(  # noqa: E731
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree.leaves(t))))
    diff = jax.tree.map(lambda a, b: a - b, got_g, want_g)
    stats_gap = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree.leaves(got_stats), jax.tree.leaves(want_stats)))
    return (abs(float(got - want)) / float(want), norm(diff) / norm(want_g),
            stats_gap)


def test_resnet_reference_agrees_with_the_system_in_float32():
    loss_gap, grad_gap, stats_gap = resnet_gap("float32")
    assert loss_gap < TIGHT and grad_gap < 10 * TIGHT
    assert stats_gap < 1e-5


def test_resnet_in_bfloat16_fails_the_float32_tolerance():
    _, grad_gap, _ = resnet_gap("bfloat16")
    assert grad_gap > 100 * TIGHT


def test_resnet_tree_is_the_one_the_program_makes():
    from bluefog_tpu.models.resnet import BottleneckBlock, ResNet

    fam, _ = modules("resnet")
    sz = fam.sizes(TINY_RESNET)
    params, stats = fam.make_params(sz, key_from_seed(1), jnp.float32)
    own = jax.eval_shape(lambda: ResNet(
        stage_sizes=tuple(sz["stage_sizes"]), block_cls=BottleneckBlock,
        num_classes=sz["num_classes"], num_filters=sz["num_filters"]).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3))))
    for mine, theirs in ((params, own["params"]),
                         (stats, own["batch_stats"])):
        assert jax.tree.structure(mine) == jax.tree.structure(theirs)
        assert all(a.shape == b.shape for a, b in zip(
            jax.tree.leaves(mine), jax.tree.leaves(theirs)))


@pytest.mark.parametrize("family", ["dense_gqa_decoder", "resnet"])
def test_a_reference_imports_nothing_of_the_program(family):
    import ast
    import os

    path = os.path.join(REPO, "perfbench", "references", family + ".py")
    with open(path) as fh:
        tree = ast.parse(fh.read())
    names = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names.append(node.module or "")
    assert not [n for n in names if n.split(".")[0] in (
        "bluefog_tpu", "flax", "optax")], names
    with open(os.path.join(REPO, "perfbench", "harness",
                           "reference_train.py")) as fh:
        assert "bluefog_tpu" not in fh.read().replace(
            "imported from the program", "")
