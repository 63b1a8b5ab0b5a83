"""``models/mla_moe.py`` with four residual streams
(``models/hyper_connections.py``), a leading dense layer and sigmoid
routing (model_type xing4_0, PR 32) against the benchmark's plain
reference at a small size on the CPU, and the decode step through the
kernel that reads the live blocks (both latent models).  A file of its
own, as ``tests/test_mla_moe_kda.py`` is: a worker takes a file whole.

The toy mixes in ``TURNS`` = 4 Sinkhorn turns on both sides, not the
published 20: ``hyper_connections._turns`` unrolls every turn over 16
arrays, and XLA's CPU compile of six such sublayers takes 74 s at 20 turns
and 4 s at 4.  Four is the smallest count whose control stands well clear
(the turns alternate rows and columns): a program two turns short of 4
misses the reference by 2,355 ``TOL`` and one short by 2,299, where at 3
turns one short misses by 102 and at 5 by 10 (the control asks for 50).
The published 20 are held by ``tests/test_hyper_connections.py`` (one
sublayer), by one layer of this model below, and by the tiny cell under
``tests/perfbench/``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_model
from bluefog_tpu.models import experts, mla_moe
from perfbench.harness import loader
from test_mla_moe import (FAMILY, ORIGINAL, REPO, SZ, TOL, _forward, _params,
                          _serve)

pytestmark = pytest.mark.serving

HC_REF = loader.load_module(REPO, "references", "mhc_mla_moe_decoder")
HC_FAMILY = loader.load_module(REPO, "families", "mhc_mla_moe_decoder")
TURNS = 4
# one dense and two expert layers; phi at 0.05 gives xh phi a deviation of
# 0.05 sqrt(256) = 0.8: the coefficients differ from token to token
HC_SZ = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 12,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "vocab_size": 128,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "moe_layer_freq": 1,
    "rope_scaling": {"beta_fast": 4, "beta_slow": 0.25, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": ORIGINAL,
                     "type": "yarn"},
    "n_routed_experts": 16, "router_outputs": 16, "experts_held_from": 0,
    "num_experts_per_tok": 4, "n_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2,
    "scoring_func": "sigmoid", "hc_mult": 4, "hc_sinkhorn_iters": TURNS,
    "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "initializer_range": 0.2, "router_bias_std": 0.01, "hc_phi_std": 0.05,
    "hc_alpha": 1.0, "compute_dtype": "float32", "param_dtype": "float32",
}


def _hc_params(seed=0):
    return served_model.params(HC_FAMILY, HC_SZ, seed)


def _hc_reference(params, tokens, sz=HC_SZ):
    return served_model.reference(HC_REF, sz, params, tokens)


def _hc_gap(cfg, params, tokens):
    """The widest gap between the logits through the cache (chunks of 6
    up to position 36, then steps) and the reference's full forward
    pass, in deviations of the reference's logits."""
    got = served_model.chunks_then_steps(cfg, params, tokens, 6, 36)
    assert got.shape == (tokens.size, HC_SZ["vocab_size"])
    return served_model.gap(got, _hc_reference(params, tokens))


def test_four_streams_through_the_cache_match_the_reference(monkeypatch):
    """Prefill in chunks, then decode through the cache, against the
    reference's full forward pass: logits, not tokens.  The same
    comparison FAILS with the mixing left out (H_res = I, H_pre = 1/n,
    H_post = 1) and with 2 Sinkhorn turns in place of 4."""
    params = _hc_params()
    tokens = np.random.default_rng(8).integers(0, HC_SZ["vocab_size"], 52)
    cfg = HC_FAMILY.model_config(HC_SZ, key_block=8)
    assert (cfg.hc_mult, cfg.n_dense_layers, cfg.score_func) \
        == (4, 1, "sigmoid")
    assert _hc_gap(cfg, params, tokens) < TOL
    # two turns of Sinkhorn in the program, four in the reference
    two = dataclasses.replace(cfg, hc_sinkhorn_iters=TURNS - 2)
    assert _hc_gap(two, params, tokens) > 50 * TOL
    # no mixing at all: a plain average in, the identity back
    hc = mla_moe.hc

    def unmixed(x, p, *, n, **kw):
        y_in = sum(hc._stream(x, i, n) for i in range(n)) / n
        eye = jnp.broadcast_to(jnp.eye(n).reshape(-1),
                               x.shape[:-1] + (n * n,))
        return y_in, jnp.ones(x.shape[:-1] + (n,)), eye

    monkeypatch.setattr(hc, "hc_pre", unmixed)
    assert _hc_gap(cfg, params, tokens) > 50 * TOL


def test_every_part_of_the_four_stream_model_is_seen_by_the_tolerance():
    """Spoil one thing in the REFERENCE's sizes or weights and the
    program no longer agrees: the dense layer, the sigmoid, the bias
    that selects, the route scale, the clamp."""
    params = _hc_params(1)
    tokens = np.random.default_rng(9).integers(0, HC_SZ["vocab_size"], 40)
    program = lambda p: _forward(HC_FAMILY.model_config(HC_SZ), p, tokens)
    got = program(params)
    want = _hc_reference(params, tokens)
    assert np.abs(got - want).max() < TOL * want.std()
    assert served_model.padding_moves(HC_REF, HC_SZ, params, tokens) \
        < 0.25 * TOL
    biased = jax.tree.map(lambda x: x, params)
    for i in (1, 2):
        moe = biased[f"layer_{i}"]["moe"]
        moe["router_bias"] = 30.0 * moe["router_bias"]
    hot = jax.tree.map(lambda x: x, params)
    for i in range(3):
        for name in ("attention_hc", "ffn_hc"):
            mix = hot[f"layer_{i}"][name]
            mix["alpha"] = mix["alpha"].at[2].set(40.0)
    spoiled = {
        "route scale": (dict(HC_SZ, routed_scaling_factor=1), params),
        "bias": (HC_SZ, biased),
        "clamp": (dict(HC_SZ, mhc_h_res_clamp_max=3), hot),
        "eps": (dict(HC_SZ, hc_eps=1e-2), params),
    }
    for name, (sz, p) in spoiled.items():
        other = _hc_reference(p, tokens, sz)
        assert np.abs(got - other).max() > 50 * TOL * other.std(), name
    # and a program told the same (the clamp, the bias) agrees again
    for p in (hot, biased):
        again = program(p)
        want = _hc_reference(p, tokens)
        assert np.abs(again - want).max() < TOL * want.std()


def test_one_layer_at_the_published_twenty_turns_is_the_references():
    """The published 20 turns inside a whole model, the smallest that has
    them (one dense layer: two mixed sublayers, 35-40 s of compile on a
    CPU): the full forward pass against the reference at 20 turns, and
    told apart from the reference at the toy's 4 (1.9 ``TOL`` against
    0.02 at 20: Sinkhorn has converged by 10 turns, so the late ones move
    a logit by little, and what is asked is that the tolerance sees them)."""
    sz = dict(HC_SZ, num_hidden_layers=1, hc_sinkhorn_iters=20)
    params = served_model.params(HC_FAMILY, sz, 3)
    tokens = np.random.default_rng(11).integers(0, sz["vocab_size"], 24)
    cfg = HC_FAMILY.model_config(sz)
    assert (cfg.hc_sinkhorn_iters, cfg.mixed_sublayers) == (20, 2)
    got = _forward(cfg, params, tokens)
    want = _hc_reference(params, tokens, sz)
    assert served_model.gap(got, want) < TOL
    four = _hc_reference(params, tokens, dict(sz, hc_sinkhorn_iters=TURNS))
    assert served_model.gap(got, four) > TOL


def test_one_stream_and_no_dense_layer_is_the_jaxpr_of_before():
    """``hc_mult=1, n_dense_layers=0`` (the defaults) trace to the
    program the module built before it knew streams: the parent's
    ``Block`` and ``MlaMoe``, transcribed here, give the same jaxpr for
    the training layout, a chunk and a single-token step."""
    import flax.linen as nn

    from bluefog_tpu.models.llama import RMSNorm

    class Block(nn.Module):
        cfg: mla_moe.MlaMoeConfig

        @nn.compact
        def __call__(self, x, live=None):
            cfg = self.cfg
            norm = lambda name: RMSNorm(cfg.norm_eps, name=name)
            x = x + mla_moe.LatentAttention(cfg, name="attention")(
                norm("attention_norm")(x))
            return x + experts.ExpertLayer(cfg, name="moe")(
                norm("ffn_norm")(x), live)

    class Before(nn.Module):
        cfg: mla_moe.MlaMoeConfig

        @nn.compact
        def __call__(self, tokens, all_logits=False, live=None):
            cfg = self.cfg
            x = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype,
                         param_dtype=jnp.float32, name="tok_embeddings",
                         embedding_init=nn.initializers.normal(
                             cfg.initializer_range))(tokens)
            for i in range(cfg.n_layers):
                x = Block(cfg, name=f"layer_{i}")(x, live)
            x = RMSNorm(cfg.norm_eps, name="norm")(x)
            if cfg.decode and not all_logits:
                x = x[:, -1:]
            w_out = self.param("output", nn.initializers.normal(
                cfg.initializer_range), (cfg.dim, cfg.vocab_size),
                jnp.float32)
            return jnp.einsum("btd,dv->btv", x, w_out.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)

    params = _params()
    cfg = FAMILY.model_config(SZ, dtype=jnp.bfloat16, key_block=8)
    assert (cfg.hc_mult, cfg.n_dense_layers, cfg.score_func) \
        == (1, 0, "softmax")
    tokens = jnp.zeros((1, 16), jnp.int32)
    text = lambda model: str(jax.make_jaxpr(
        lambda p, t: model.apply({"params": p}, t))(params, tokens))
    assert text(mla_moe.MlaMoe(cfg)) == text(Before(cfg))
    served = cfg.serving_layout(72)
    cache = served.init_cache(1, 72)
    for width in (8, 1):
        step = lambda model: str(jax.make_jaxpr(
            lambda p, c, t: model.apply(
                {"params": p, "cache": c}, t, mutable=["cache"]))(
                    params, cache, tokens[:, :width]))
        assert step(mla_moe.MlaMoe(served)) == step(Before(served))


def test_score_func_is_a_field_of_the_config():
    cfg = mla_moe.MlaMoeConfig(score_func="sigmoid")
    assert cfg.score_func == "sigmoid"
    assert "score_func" in {f.name for f in dataclasses.fields(cfg)}
    assert mla_moe.MlaMoeConfig().score_func == "softmax"
    with pytest.raises(ValueError):
        mla_moe.MlaMoeConfig(hc_mult=0)
    with pytest.raises(ValueError):
        mla_moe.MlaMoeConfig(n_layers=2, n_dense_layers=3)
    # the sigmoid path of the shared expert layer is reached: a bias
    layer = experts.ExpertLayer(cfg)
    shapes = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 3, cfg.dim), cfg.dtype)))
    assert shapes["params"]["router_bias"].shape == (cfg.n_experts,)


def test_the_engine_serves_four_streams_with_a_slot_freed_and_reused():
    """Four requests through two slots; the served tokens are the
    reference's greedy ones; the pool holds latent leaves and no stream;
    the two counters read what the host can count by hand."""
    from bluefog_tpu.observe.registry import MetricsRegistry
    from bluefog_tpu.serving import protocol

    params = _hc_params(2)
    rng = np.random.default_rng(10)
    lengths, budgets = (27, 9, 33, 5), (6, 9, 4, 12)
    reg = MetricsRegistry()
    eng, reqs = served_model.serve(
        HC_FAMILY.model_config(HC_SZ, key_block=8), params,
        [rng.integers(0, 128, n) for n in lengths], budgets, registry=reg)
    for r in reqs:
        served_model.assert_served_is_the_references_greedy(
            HC_REF, HC_SZ, params, r, TOL)
    # nothing in the pool knows a stream: the other latent model's leaves
    cfg = eng.cfg
    assert cfg.cache_kinds() == {"full": (3, None)}
    assert cfg.latent_width == 24
    assert cfg.streamed_positions([3, -1]) == (("full", 3 * 2 * 72),)
    assert cfg.rebuilt_positions(8, 4) == 3 * 16
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            eng.pool.cache)[0]:
        if protocol.leaf_kind(path) == protocol.FULL:
            assert leaf.shape == (2, 1, 72, 24)
    # every prompt token but the last is prefilled, every served token
    # but a request's first comes from a decode step; six sublayers
    mixed = sum(n - 1 for n in lengths) + sum(budgets)
    assert (cfg.mixed_sublayers, cfg.residual_streams) == (6, 4)
    assert reg.counter("bf_hc_mixed_tokens_total", "").value == 6 * mixed
    assert reg.gauge("bf_hc_streams", "").value == 4
    # a plain residual counts neither
    plain = MetricsRegistry()
    _serve(_params(), [rng.integers(0, 128, 6)], [2], registry=plain)
    assert not any(name.startswith("bf_hc_")
                   for name, *_ in plain.collect())


# ------------------------------------------------------------------ #
# the decode step through the kernel that reads the live blocks (PR 33)
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("streams", [1, 4])
def test_the_kernel_engine_serves_what_the_einsum_engine_serves(
        streams, monkeypatch):
    """Four requests through two slots (a slot freed and reused, one
    still prefilling while the other decodes), eight blocks of nine rows
    a slot: the same tokens from both lowerings of the single-token
    step, and the counter of streamed positions reads every reserved
    row under the einsums and the plan's blocks under the kernel."""
    from bluefog_tpu.observe.registry import MetricsRegistry
    from bluefog_tpu.parallel import pallas_decode

    monkeypatch.setattr(pallas_decode, "_LATENT_BLOCKS", (9, 9))
    family, sz, params = (FAMILY, SZ, _params()) if streams == 1 \
        else (HC_FAMILY, HC_SZ, _hc_params(2))
    lengths, budgets = (27, 9, 33, 5), (6, 9, 4, 12)
    asked = []

    def recorded(positions, s_len, **kw):
        asked.append(streamed_positions(positions, s_len, **kw))
        return asked[-1]

    streamed_positions = pallas_decode.streamed_positions
    monkeypatch.setattr(pallas_decode, "streamed_positions", recorded)

    def serve(decode_attn):
        del asked[:]
        rng = np.random.default_rng(10)
        reg = MetricsRegistry()
        eng, reqs = served_model.serve(
            family.model_config(sz, key_block=8), params,
            [rng.integers(0, 128, n) for n in lengths], budgets,
            decode_attn=decode_attn, registry=reg)
        assert eng.cfg.decode_attn == decode_attn
        assert eng.cfg.residual_streams == streams
        value = lambda name, **labels: reg.counter(name, "", **labels).value
        return ([list(r.tokens) for r in reqs],
                value("bf_serving_decode_steps_total"),
                value("bf_serving_streamed_positions_total", kind="full"),
                list(asked))

    want, steps, every, _ = serve("xla")
    got, steps_k, live, counts = serve("pallas")
    assert got == want and steps_k == steps == len(counts)
    layers = sz["num_hidden_layers"]
    assert every == steps * layers * 2 * 72
    assert live == layers * sum(counts)
    # a slot's rows are fetched in blocks of 9 up to its position, never
    # the 72 reserved: under half of what the einsums read
    assert all(n % 9 == 0 and 9 <= n <= 2 * 72 for n in counts)
    assert live < every / 2
