"""The latent-attention model with softmax-routed experts against the
benchmark's plain reference (which only ever EXPANDS keys and values), at
a small size on the CPU: the full forward pass, the served path through
``ServingEngine`` (chunked prefill across the query scale's step and the
YaRN ramp, a padded last chunk, slots reused, long beside short; key
blocks of two sizes), absorbed against expanded for one layer,
the shares of an expert layer, the softmax router, the pool's one latent
leaf, and the pins of the frequencies and the query's scale.  The model
with four residual streams is ``tests/test_mla_moe_mhc.py``'s, the one
with two kinds of layer ``tests/test_mla_moe_kda.py``'s: a worker takes a
file whole."""

import dataclasses
import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_model
from bluefog_tpu.models import experts, mla_moe
from bluefog_tpu.serving import Request, ServingEngine, SlotPool
from bluefog_tpu.serving.prefix_cache import PrefixCache
from perfbench.harness import loader

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = loader.load_module(REPO, "references", "mla_moe_decoder")
FAMILY = loader.load_module(REPO, "families", "mla_moe_decoder")

pytestmark = pytest.mark.serving

# the original context, small: prompts of a few dozen tokens cross the
# step of the query's scale (positions 16, 32, 48: three steps) and every
# pair of the ramp (low 0, high 2 of 4 pairs) turns at its own rate
ORIGINAL = 16
# float32 program against float32-highest reference, both on the CPU:
# what is left is the order of the sums (absorbed against expanded, the
# running softmax over key blocks, the loop over the experts hit).  A
# routing flip would show as ~1e-1; a missing norm, the m^2, the query's
# scale or a wrong ramp as 1e-2 to 1.
TOL = 2e-4

SZ = {
    "hidden_size": 64, "intermediate_size": 999, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 12,
    "num_hidden_layers": 3, "first_k_dense_replace": 0, "vocab_size": 128,
    "rms_norm_eps": 1e-6, "rope_interleave": True,
    "rope_parameters": {
        "beta_fast": 4, "beta_slow": 0.25, "factor": 8,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": ORIGINAL, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"},
    "n_routed_experts": 16, "router_outputs": 16, "experts_held_from": 0,
    "num_experts_per_tok": 4, "n_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "initializer_range": 0.2, "compute_dtype": "float32",
    "param_dtype": "float32",
}
# key blocks a chunk's attention walks (max_len 72 is 9 of 8 or 3 of 24)
KEY_BLOCKS = (8, 24)


def _params(sz=SZ, seed=0, dtype=jnp.float32):
    return served_model.params(FAMILY, sz, seed, dtype)


def _reference(params, tokens, sz=SZ):
    return served_model.reference(REF, sz, params, tokens)


@functools.cache
def _program(cfg):
    model = mla_moe.MlaMoe(cfg)
    return jax.jit(lambda p, t: model.apply({"params": p}, t)[0])


def _forward(cfg, params, tokens):
    """The training layout's logits of one sequence: one program a config."""
    return np.asarray(_program(cfg)(params, tokens[None]))


# ------------------------------------------------------------------ #
# (a) the full forward pass
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("held", [(0, 16), (4, 8)])
def test_full_forward_matches_the_reference(held):
    sz = dict(SZ, experts_held_from=held[0], n_routed_experts=held[1])
    params = _params(sz)
    tokens = np.random.default_rng(1).integers(0, sz["vocab_size"], 56)
    # seven key blocks of 8: the running softmax
    got = _forward(FAMILY.model_config(sz, key_block=8), params, tokens)
    want = _reference(params, tokens, sz)
    assert got.shape == (56, sz["vocab_size"])
    assert served_model.gap(got, want) < TOL
    if held == (0, 16):
        assert served_model.padding_moves(REF, sz, params, tokens) \
            < 0.25 * TOL


def test_every_part_of_the_mathematics_is_seen_by_the_tolerance():
    """Leave one thing out of the REFERENCE and the program no longer
    agrees: the tolerance sees the query's scale, the m^2, the ramp and
    each inner norm."""
    params = _params()
    tokens = np.random.default_rng(2).integers(0, SZ["vocab_size"], 56)
    got = _forward(FAMILY.model_config(SZ), params, tokens)
    rope = SZ["rope_parameters"]
    twos = lambda x: jax.tree.map(lambda s: 2.0 * s, x)
    spoiled = {
        "query scale": (dict(SZ, rope_parameters=dict(
            rope, llama_4_scaling_beta=0.0)), params),
        "m^2": (dict(SZ, rope_parameters=dict(rope, mscale_all_dim=0)),
                params),
        "ramp": (dict(SZ, rope_parameters=dict(rope, factor=1.0001)),
                 params),
    }
    for name in ("q_norm", "kv_norm"):
        # a scale of 2 on one side only stands for a norm left out
        other = jax.tree.map(lambda x: x, params)
        for i in range(SZ["num_hidden_layers"]):
            att = other[f"layer_{i}"]["attention"]
            att[name] = twos(att[name])
        spoiled[name] = (SZ, other)
    for name, (sz, p) in spoiled.items():
        want = _reference(p, tokens, sz)
        assert np.abs(got - want).max() > 50 * TOL * want.std(), name


# ------------------------------------------------------------------ #
# (b) through the engine
# ------------------------------------------------------------------ #
def _serve(params, prompts, budgets, key_block=8, **engine):
    """Two slots of 72 rows, chunks of 4 (``served_model.serve``)."""
    return served_model.serve(FAMILY.model_config(SZ, key_block=key_block),
                              params, prompts, budgets, **engine)


@pytest.mark.parametrize("key_block", KEY_BLOCKS)
@pytest.mark.parametrize("chunk, lengths, budgets", [
    # chunks of 6 straddle positions 16, 32 and 48 (the scale's steps) and
    # the key blocks' edges; 29 = 4 chunks and a padded fifth
    (6, (30, 6), (12, 12)),
    # a chunk as wide as a key block, a long prompt beside a short one,
    # decoding side by side across position 48
    (8, (45, 3), (12, 20)),
    # four requests through two slots: a slot reused after a longer one
    (4, (27, 9, 33, 5), (6, 9, 4, 12)),
    # a one-token prompt (no prefill at all) beside a long one
    (2, (1, 41), (20, 5)),
])
def test_served_tokens_match_the_reference(chunk, lengths, budgets,
                                           key_block):
    params = _params()
    rng = np.random.default_rng(sum(lengths))
    prompts = [rng.integers(0, SZ["vocab_size"], n) for n in lengths]
    _, reqs = _serve(params, prompts, budgets, key_block,
                     prefill_chunk=chunk)
    for r in reqs:
        served_model.assert_served_is_the_references_greedy(REF, SZ, params,
                                                            r, TOL)


def test_no_recompile_inside_the_window():
    eng, _ = _serve(_params(), [np.arange(5)], [3])
    served_model.assert_other_lengths_compile_nothing(eng)


def test_the_positions_a_chunk_rebuilds_are_counted():
    from bluefog_tpu.observe.registry import MetricsRegistry

    params = _params()
    prompt = np.random.default_rng(4).integers(0, 128, 30)
    reg = MetricsRegistry()
    eng, _ = _serve(params, [prompt], [3], prefill_chunk=6, registry=reg)
    # 29 prompt tokens in chunks of 6 at 0, 6, .., 24; a chunk ending at
    # position e walks e // 8 + 1 key blocks of 8, in 3 layers
    blocks = sum((start + 6 - 1) // 8 + 1 for start in range(0, 30, 6))
    assert reg.counter("bf_serving_latent_expanded_positions_total",
                       "").value == 3 * 8 * blocks
    assert eng.cfg.rebuilt_positions(24, 6) == 3 * 8 * 4
    # a single-token step absorbs: nothing rebuilt
    assert eng.cfg.rebuilt_positions(24, 1) == 0


def test_quantised_serving_refuses_loudly():
    cfg = FAMILY.model_config(SZ)
    for kw in ({"kv_quant": "int8"}, {"weight_quant": "int8"}):
        with pytest.raises(NotImplementedError):
            cfg.serving_layout(64, **kw)
    with pytest.raises(ValueError):
        cfg.serving_layout(64, decode_attn="mosaic")
    assert cfg.serving_layout(64, decode_attn="auto").decode


@pytest.mark.parametrize("backend,max_len,rope,resolved", [
    ("cpu", 2048, 8, "xla"), ("tpu", 2048, 8, "pallas"),
    ("tpu", 16384, 8, "pallas"), ("tpu", 1031, 8, "xla"),
    ("tpu", 896, 8, "xla"), ("tpu", 2048, 112, "xla")])
def test_auto_is_decided_from_the_platform_the_cache_length_and_the_width(
        monkeypatch, backend, max_len, rope, resolved):
    """As ``generate.decode_config`` decides for the dense model: the
    kernel on a real TPU where it reads the leaf as the chip lays it
    out, the einsums otherwise: off the chip; a cache that has no block
    of whole lanes (1031 is a prime, 896 splits into two of 448); a
    latent of whole lanes (16 + 112), which lies row-major.  Asked for
    by name the kernel is taken."""
    cfg = FAMILY.model_config(SZ, qk_rope_head_dim=rope)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert cfg.decode_attn == "xla" and not cfg.decode
    assert cfg.serving_layout(max_len, decode_attn="auto").decode_attn \
        == resolved
    assert cfg.serving_layout(max_len).decode_attn == "xla"
    assert cfg.serving_layout(max_len, decode_attn="pallas").decode_attn \
        == "pallas"


@pytest.mark.parametrize("positions", [
    [5, -1, 9], [0], [71], [-1, -1], [8, 9, 17, 18, 70], [-1, 40, -1]])
def test_streamed_positions_under_the_kernel_are_the_blocks_the_plan_names(
        monkeypatch, positions):
    """Blocks of 9 rows at ``max_len`` 72 (the tests' small block): the
    host's count a layer is the distinct blocks the index map names."""
    from bluefog_tpu.parallel import pallas_decode

    monkeypatch.setattr(pallas_decode, "_LATENT_BLOCKS", (9, 9))
    block = pallas_decode.latent_block(72)
    assert block == 9
    cfg = FAMILY.model_config(SZ).serving_layout(72, decode_attn="pallas")
    live = jnp.asarray([p >= 0 for p in positions])
    plan = np.asarray(pallas_decode._stream_plan(
        jnp.clip(jnp.asarray(positions, jnp.int32), 0, 71), live, block))
    named = {tuple(int(x) for x in pallas_decode._named_block(b, sj, plan))
             for b in range(len(positions)) for sj in range(72 // block)}
    layers = SZ["num_hidden_layers"]
    assert cfg.streamed_positions(positions) \
        == (("full", layers * len(named) * block),)
    # the einsums read every reserved row of every slot, whatever is live
    assert dataclasses.replace(cfg, decode_attn="xla").streamed_positions(
        positions) == (("full", layers * len(positions) * 72),)


# ------------------------------------------------------------------ #
# (c) absorbed equals expanded, one layer
# ------------------------------------------------------------------ #
def _one_layer(dtype, seed=0):
    """The attention sublayer of layer 0 for the LAST of 40 positions
    behind a cache that holds the 39 before it: by a single-token step
    (absorbed) and as the second token of a two-token call (walked in
    blocks, expanded)."""
    cfg = dataclasses.replace(
        FAMILY.model_config(SZ, dtype=dtype, key_block=16), n_layers=1)
    params = _params(seed=seed, dtype=dtype)["layer_0"]["attention"]
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, 40, 64), dtype)
    layer = mla_moe.LatentAttention(cfg.serving_layout(64))
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0),
                                          x[:, :1]))["cache"])
    call = jax.jit(lambda c, x: layer.apply({"params": params, "cache": c},
                                            x, mutable=["cache"]))
    cache = call(cache, x[:, :39])[1]["cache"]
    back = dict(cache, cache_index=cache["cache_index"] - 1)
    return [np.asarray(out, np.float32)[0, -1]
            for out, _ in (call(cache, x[:, 39:]), call(back, x[:, 38:]))]


def test_absorbed_equals_expanded_for_one_layer():
    step, expand = _one_layer(jnp.float32)
    size = np.abs(expand).max()
    # float32: the same sums in another order
    assert np.abs(step - expand).max() < 1e-5 * size
    # bfloat16: each form rounds its operands once more than the other
    # somewhere (absorbed: q W_uk and the weighted latent; expanded: the
    # rebuilt keys and values), 2^-8 relative each, over sums of 16 to
    # 40 terms that mostly cancel: 2% of the largest output bounds it
    # with room (a wrong scale or a lost column would read 10% or more)
    step, expand = _one_layer(jnp.bfloat16)
    assert np.abs(step - expand).max() < 2e-2 * size


# ------------------------------------------------------------------ #
# (d) the shares add up to the uncut layer
# ------------------------------------------------------------------ #
def _shares(ref, family, sz, moe, held):
    """An expert layer over 24 seeded tokens: the reference's uncut
    layer, the program's shared expert, and the routed part of every
    share of ``held`` experts (the shared expert taken off it)."""
    m = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64), jnp.float32)
    want = ref.swiglu(m[0], moe["shared"], ref.mm_highest) \
        + ref.routed_part(m[0], moe, sz, ref.mm_highest)
    cfg = family.model_config(sz)
    shared = experts.SwiGLU(cfg, sz["moe_intermediate_size"]).apply(
        {"params": moe["shared"]}, m)[0]
    parts = []
    for first in range(0, sz["router_outputs"], held):
        share = dict(moe, **{k: moe[k][first:first + held]
                             for k in ("w1", "w3", "w2")})
        layer = experts.ExpertLayer(dataclasses.replace(
            cfg, experts_held=(first, held)))
        parts.append(np.asarray(layer.apply({"params": share}, m)[0]
                                - shared, np.float64))
    return m, np.asarray(want), np.asarray(shared, np.float64), parts


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    _, want, shared, parts = _shares(REF, FAMILY, SZ,
                                     _params()["layer_1"]["moe"], 4)
    assert np.abs(shared + sum(parts) - want).max() < TOL * want.std()


# ------------------------------------------------------------------ #
# (e) the router
# ------------------------------------------------------------------ #
def test_the_router_is_a_float32_softmax_with_no_bias():
    cfg = FAMILY.model_config(SZ)
    params = _params()["layer_0"]["moe"]
    assert "router_bias" not in params
    m = jax.random.normal(jax.random.PRNGKey(7), (1, 9, 64), jnp.float32)
    # the layer's own router, read through the experts it reports
    dcfg = cfg.serving_layout(16)
    layer = experts.ExpertLayer(dcfg)
    cache = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), m))
    assert "router_bias" not in cache["params"]
    g = jax.nn.softmax(jnp.dot(m[0], params["router"],
                               precision=jax.lax.Precision.HIGHEST), -1)
    chosen, weights = experts.route(g, None, 4, 1.0)
    want_c, want_w = REF.route(m[0], params, SZ, REF.mm_highest)
    assert np.array_equal(np.asarray(chosen), np.asarray(want_c))
    np.testing.assert_allclose(np.asarray(weights), np.asarray(want_w),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    # equal to a softmax over the four chosen logits
    logits = jnp.dot(m[0], params["router"],
                     precision=jax.lax.Precision.HIGHEST)
    four = jax.nn.softmax(jnp.take_along_axis(logits, chosen, -1), -1)
    np.testing.assert_allclose(np.asarray(weights), np.asarray(four),
                               rtol=1e-5)
    _, mut = layer.apply({"params": params,
                          "cache": jax.tree.map(
                              lambda s: jnp.zeros(s.shape, s.dtype),
                              cache["cache"])}, m, mutable=["cache"])
    assert np.array_equal(np.sort(np.asarray(mut["cache"]["stat_experts"])),
                          np.sort(np.asarray(chosen[-1:])))
    # routing is float32 at the highest precision whatever the model's
    # dtype: the router's dot in a bfloat16 model names both
    jaxpr = jax.make_jaxpr(lambda p, x: experts.ExpertLayer(
        dataclasses.replace(cfg, dtype=jnp.bfloat16)).apply(
            {"params": p}, x))(params, m.astype(jnp.bfloat16))
    router_dot = next(e for e in jaxpr.jaxpr.eqns
                      if e.primitive.name == "dot_general"
                      and e.outvars[0].aval.shape == (9, 16))
    assert router_dot.outvars[0].aval.dtype == jnp.float32
    assert all(v.aval.dtype == jnp.float32 for v in router_dot.invars)
    assert "HIGHEST" in str(router_dot.params["precision"])


# ------------------------------------------------------------------ #
# (f) the cache
# ------------------------------------------------------------------ #
def test_no_cache_leaf_is_wider_than_the_latent():
    from bluefog_tpu.serving import protocol

    cfg = FAMILY.model_config(SZ)
    pool = SlotPool(cfg, 3, 64, chunk=4)
    width = SZ["kv_lora_rank"] + SZ["qk_rope_head_dim"]
    assert cfg.latent_width == width
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(pool.cache)[0]:
        kind = protocol.leaf_kind(path)
        assert kind in (protocol.FULL, protocol.INDEX, protocol.STAT)
        if kind == protocol.FULL:
            # [capacity, 1, max_len, values a position]
            assert leaf.shape[-2] == 64 and leaf.shape[-1] <= width
            assert leaf.size == 3 * 64 * leaf.shape[-1]
            total += leaf.size * leaf.dtype.itemsize
    assert pool.cache_bytes() == {"full": total}
    per_position = total / (3 * 64 * SZ["num_hidden_layers"])
    assert per_position == REF.cache_bytes_per_position(SZ)
    # at the published widths: 640 bytes, where expanded is 16 KiB
    published = loader.read_json(os.path.join(
        REPO, "perfbench", "configs", "mistral-small-4-119b-2603.json"))
    sz = FAMILY.sizes(published, "serve")
    assert REF.cache_bytes_per_position(sz) == 640
    assert cfg.cache_kinds() == {"full": (3, None)}
    assert cfg.serving_layout(64).streamed_positions([5, -1, 9]) \
        == (("full", 3 * 3 * 64),)


def test_a_prefix_cache_restores_latent_chunks_exactly():
    params = _params()
    rng = np.random.default_rng(11)
    head = rng.integers(0, 128, 16)
    prompts = [np.concatenate([head, rng.integers(0, 128, n)])
               for n in (9, 5)]
    outs = []
    for prefix in (False, True):
        eng = ServingEngine({"params": params}, FAMILY.model_config(SZ),
                            capacity=1, max_len=64, prefill_chunk=4,
                            prefix_cache=PrefixCache(4, 1 << 20)
                            if prefix else False)
        reqs = [eng.submit(Request(p, 6)) for p in prompts]
        eng.run()
        outs.append([r.output().tolist() for r in reqs])
        if prefix:
            assert eng.metrics.n_prefix_chunks_restored == 4
    assert outs[0] == outs[1]


# ------------------------------------------------------------------ #
# (g) the frequencies and the query's scale, by hand
# ------------------------------------------------------------------ #
def test_yarn_frequencies_and_query_scale_are_pinned():
    # the published rope_parameters: 64 rotated columns, theta 10000,
    # factor 128 over an original context of 8192, beta 32 and 1
    d = lambda r: 64 * math.log(8192 / (2 * math.pi * r)) \
        / (2 * math.log(10000))
    assert (math.floor(d(32)), math.ceil(d(1))) == (12, 25)
    assert abs(d(32) - 12.8805) < 1e-3 and abs(d(1) - 24.9217) < 1e-3
    freqs = mla_moe.yarn_frequencies(64, 10000.0, 128.0, 8192, 32.0, 1.0)
    assert freqs.shape == (32,) and freqs.dtype == np.float32
    plain = lambda i: 10000.0 ** (-2 * i / 64)
    # the first pair turns as it did; the last 128 times slower; pair 18
    # sits 6/13 up the ramp
    ramp = (18 - 12) / (25 - 12)
    want = {0: 1.0, 31: plain(31) / 128,
            18: plain(18) * (1 - ramp) + plain(18) / 128 * ramp}
    for i, w in want.items():
        assert abs(freqs[i] - w) < 2e-6 * w, (i, freqs[i], w)
    assert abs(want[18] - 0.00304827) < 1e-7
    np.testing.assert_allclose(freqs[:13], [plain(i) for i in range(13)],
                               rtol=2e-6)
    np.testing.assert_allclose(freqs[25:],
                               [plain(i) / 128 for i in range(25, 32)],
                               rtol=2e-6)
    ref = np.asarray(REF.yarn_freqs(
        dict(rope_theta=10000, factor=128, beta_fast=32, beta_slow=1,
             original_max_position_embeddings=8192), 64))
    np.testing.assert_allclose(freqs, ref, rtol=2e-6)
    scale = np.asarray(mla_moe.query_scale(
        jnp.asarray([0, 8191, 8192, 16383]), 0.1, 8192))
    np.testing.assert_allclose(
        scale, [1.0, 1.0, 1 + 0.1 * math.log(2), 1 + 0.1 * math.log(2)],
        rtol=1e-6)
    assert abs(scale[2] - 1.0693147) < 1e-6
    # the softmax's scale at the published sizes: 128^-1/2 * 1.4852^2
    m = 0.1 * math.log(128) + 1
    assert abs(m - 1.48520) < 1e-5
    published = loader.read_json(os.path.join(
        REPO, "perfbench", "configs", "mistral-small-4-119b-2603.json"))
    cfg = FAMILY.model_config(FAMILY.sizes(published, "serve"))
    assert abs(cfg.softmax_scale - m * m / math.sqrt(128)) < 1e-9
    assert abs(REF.softmax_scale(FAMILY.sizes(published, "serve"))
               - cfg.softmax_scale) < 1e-9
