"""``models/mla_moe.py`` with layers of two kinds, a recurrent mixer
(``models/kda.py``) five to one latent attention and experts chosen
inside groups (PR 38), against the benchmark's plain reference at a small
size on the CPU: logits through chunks and single steps, each part of
the mathematics left out of the reference, the four shares of an expert
layer under the group limit, ``route`` without groups, the new config
fields, and the engine with slots freed and reused.  A file of its own:
a worker takes a file whole."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_model
from bluefog_tpu.models import experts, mla_moe
from perfbench.harness import loader
from test_mla_moe import FAMILY, REPO, SZ, TOL, _forward, _shares
from test_mla_moe_mhc import HC_FAMILY, HC_SZ

pytestmark = pytest.mark.serving

KDA_REF = loader.load_module(REPO, "references", "kda_mla_moe_decoder")
KDA_FAMILY = loader.load_module(REPO, "families", "kda_mla_moe_decoder")
# published layers 0-4 at a period of 3: kda, kda, LATENT, kda, kda; one
# dense layer; 32 router outputs in 4 groups of 8, 2 groups kept, 4 a
# token.  The draws of the family: the decay spreads over (e^-5, 1)
KDA_SZ = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32, "num_attention_heads": 4,
    "q_lora_rank": None, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 8, "head_dim": 8, "rotary_dim": 8,
    "num_hidden_layers": 5, "published_layers": [0, 1, 2, 3, 4],
    "layer_group_size": 3, "first_k_dense_replace": 1, "vocab_size": 128,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "max_position_embeddings": 4096, "num_experts": 32,
    "router_outputs": 32, "experts_held_from": 0, "num_experts_per_tok": 4,
    "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
    "moe_router_enable_expert_bias": True, "routed_scaling_factor": 2.5,
    "score_function": "sigmoid", "short_conv_kernel_size": 4,
    "kda_lower_bound": -5, "kda_safe_gate": True, "linear_silu": True,
    "group_norm_size": 1,
    "gated_attention_proj_granularity_type": "head_wise",
    "use_nGPT": False, "value_norm": False, "up_proj_norm": False,
    "scale_router_input": False, "use_kda_lora": False,
    "initializer_range": 0.2, "router_bias_std": 0.01, "kda_conv_std": 0.5,
    "kda_a_log_max": 1.386, "kda_dt_bias_std": 1.0, "kda_beta_std": 0.4,
    "compute_dtype": "float32", "param_dtype": "float32",
}


def _kda_params(sz=KDA_SZ, seed=0):
    return served_model.params(KDA_FAMILY, sz, seed)


def _kda_reference(params, tokens):
    return served_model.reference(KDA_REF, KDA_SZ, params, tokens)


@functools.cache
def _through_the_cache(seed, n):
    """``n`` seeded tokens and the program's logits of them through the
    cache (chunks of 6 up to position 36: blocks of the recurrence cut
    short and a chunk boundary inside the convolution's reach; then
    single steps), made once for every reference they are held to."""
    tokens = np.random.default_rng(seed).integers(0, KDA_SZ["vocab_size"], n)
    cfg = KDA_FAMILY.model_config(KDA_SZ, key_block=8)
    return tokens, served_model.chunks_then_steps(cfg, _kda_params(), tokens,
                                                  6, 36)


def test_two_layer_kinds_through_the_cache_match_the_reference():
    """Prefill in chunks, then decode through the state and the latent
    cache, against the reference's token-by-token full forward pass:
    LOGITS at every position.  Float32 on both sides: what is left is
    the order of the sums (the chunked form's triangular solve against
    the token loop, absorbed against expanded)."""
    params = _kda_params()
    tokens, got = _through_the_cache(8, 52)
    cfg = KDA_FAMILY.model_config(KDA_SZ, key_block=8)
    assert cfg.layer_types == ("kda", "kda", "latent", "kda", "kda")
    assert (cfg.state_layers, cfg.latent_layers, cfg.n_dense_layers,
            cfg.n_group, cfg.topk_group, cfg.head_gate, cfg.q_lora_rank) \
        == (4, 1, 1, 4, 2, True, None)
    want = _kda_reference(params, tokens)
    assert served_model.gap(got, want) < TOL
    # the training layout: one call of all 52 tokens, four blocks of 16
    assert served_model.gap(_forward(cfg, params, tokens), want) < TOL
    assert served_model.padding_moves(KDA_REF, KDA_SZ, params, tokens) \
        < 0.25 * TOL
    # a block of the chunked form stays inside float32 at this bound
    from bluefog_tpu.models import kda

    assert kda.KDA_BLOCK * -KDA_SZ["kda_lower_bound"] < 88


@pytest.mark.parametrize("part", ["delta term", "decay", "convolution",
                                  "head gate", "group limit", "q k norm",
                                  "output norm"])
def test_every_part_of_the_two_kind_model_is_seen_by_the_tolerance(
        part, monkeypatch):
    """Leave one thing out of the REFERENCE and the program no longer
    agrees: the tolerance sees the delta rule's correction, the decay,
    the convolution, the latent layer's gate a head, the choice inside
    groups, and both norms of the recurrent mixer."""
    params = _kda_params()
    tokens, got = _through_the_cache(9, 40)
    sz, p = KDA_SZ, params
    if part == "delta term":
        # S_t = Diag(alpha) S + beta k v^T: no (I - beta k k^T)
        def plain(q, k, v, g, beta, steps, mm):
            def turn(i, carry):
                s, out = carry
                s = jnp.exp(g[i])[:, :, None] * s + beta[i][:, None, None] \
                    * k[i][:, :, None] * v[i][:, None, :]
                return s, out.at[i].set(mm("hkv,hk->hv", s, q[i]))
            h, d = q.shape[1:]
            return jax.lax.fori_loop(
                0, steps, turn, (jnp.zeros((h, d, d), jnp.float32),
                                 jnp.zeros(v.shape, jnp.float32)))[1]
        monkeypatch.setattr(KDA_REF, "delta_rule", plain)
    elif part == "decay":
        real = KDA_REF.delta_rule
        monkeypatch.setattr(KDA_REF, "delta_rule", lambda q, k, v, g, *a:
                            real(q, k, v, jnp.zeros_like(g), *a))
    elif part == "convolution":
        monkeypatch.setattr(KDA_REF, "short_conv",
                            lambda x, filters: x * filters[-1])
    elif part == "group limit":
        sz = dict(KDA_SZ, n_group=1, topk_group=1)
    elif part == "q k norm":
        monkeypatch.setattr(KDA_REF, "L2_EPS", 1.0)
    else:
        p = jax.tree.map(lambda x: x, params)
        att, leaf = {"head gate": ("layer_2", "wgate"),
                     "output norm": ("layer_0", "o_norm")}[part]
        node = p[att]["attention"][leaf]
        key = "kernel" if "kernel" in node else "scale"
        # a gate of one half everywhere; a norm's scale of two
        node[key] = jnp.zeros_like(node[key]) if key == "kernel" \
            else 2.0 * node[key]
    # traced here, under the patch (``served_model.reference`` keeps its
    # trace)
    spoiled = jax.jit(lambda p, t: KDA_REF.logits(p, t, sz))
    assert served_model.gap(got, np.asarray(spoiled(p, jnp.asarray(tokens)))) \
        > 50 * TOL, part


def test_the_four_shares_add_up_under_the_group_limit():
    """With the choice inside groups: the routed parts of the shares
    (0, 8), (8, 8), (16, 8), (24, 8), a group each, plus the shared
    expert once, are the uncut layer of the reference."""
    moe = _kda_params()["layer_1"]["moe"]
    m, want, shared, parts = _shares(KDA_REF, KDA_FAMILY, KDA_SZ, moe, 8)
    assert len(parts) == 4
    assert np.abs(shared + sum(parts) - want).max() < TOL * want.std()
    # a token's experts lie in 2 of the 4 groups: every token leaves at
    # least two shares' routed parts at exactly nothing
    idle = sum((np.abs(part).max(-1) == 0).astype(int) for part in parts)
    assert idle.min() >= 2 and (idle == 2).any()
    # and the reference's own choice keeps to its groups
    chosen, _ = KDA_REF.route(np.asarray(m[0]), moe, KDA_SZ,
                              KDA_REF.mm_highest)
    groups = np.asarray(chosen) // 8
    assert all(len(set(row)) <= 2 for row in groups)


@pytest.mark.parametrize("score_func", experts.SCORE_FUNCS)
def test_route_without_groups_is_the_route_of_before(score_func):
    """``n_group`` 1 (the default, and every accepted model) is the
    function as it stood, bit for bit, for both score functions."""
    def before(scores, bias, top_k, route_scale):
        _, chosen = jax.lax.top_k(
            scores if bias is None else scores + bias, top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
        return chosen, picked * route_scale

    logits = jax.random.normal(jax.random.PRNGKey(1), (40, 32))
    if score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        bias = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (32,))
    else:
        scores, bias = jax.nn.softmax(logits, -1), None
    for got, want in zip(experts.route(scores, bias, 4, 2.5),
                         before(scores, bias, 4, 2.5)):
        np.testing.assert_array_equal(got, want)
    text = lambda f: str(jax.make_jaxpr(lambda s: f(s, bias, 4, 2.5))(scores))
    assert text(experts.route) == text(before)
    # with groups the choice moves: some token's best four span more
    # than two groups, and the limit keeps them to two
    chosen, weights = experts.route(scores, bias, 4, 2.5, 4, 2)
    free, _ = experts.route(scores, bias, 4, 2.5)
    assert (np.asarray(free) // 8 != np.asarray(chosen) // 8).any()
    assert all(len(set(row)) <= 2 for row in np.asarray(chosen) // 8)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)


def test_the_new_fields_are_checked_and_default_to_the_models_of_before():
    cfg = mla_moe.MlaMoeConfig()
    assert (cfg.layer_types, cfg.n_group, cfg.topk_group, cfg.head_gate,
            cfg.q_lora_rank) == (None, 1, 1, False, 32)
    assert (cfg.latent_layers, cfg.state_layers) == (2, 0)
    with pytest.raises(ValueError, match="layer_types"):
        mla_moe.MlaMoeConfig(n_layers=2, layer_types=("kda",))
    with pytest.raises(ValueError, match="layer_types"):
        mla_moe.MlaMoeConfig(n_layers=1, layer_types=("window",))
    with pytest.raises(ValueError, match="n_group"):
        mla_moe.MlaMoeConfig(n_experts=16, n_group=3)
    with pytest.raises(ValueError, match="topk_group"):
        mla_moe.MlaMoeConfig(n_experts=16, n_group=8, topk_group=1, top_k=4)
    # no state leaf, no new parameter, for the two models of before
    for family, sz in ((FAMILY, SZ), (HC_FAMILY, HC_SZ)):
        served = family.model_config(sz).serving_layout(72)
        leaves = jax.tree_util.tree_flatten_with_path(
            jax.eval_shape(lambda: served.init_cache(1, 72)))[0]
        names = {path[-1].key for path, _ in leaves}
        assert names == {"cache_index", "cached_latent", "stat_experts",
                         "stat_expert_rows"}
        assert served.state_layers == 0


def test_the_engine_serves_two_layer_kinds_with_slots_freed_and_reused():
    """Five requests through two slots (one a single token: its first
    call is a decode step at index 0 in a slot another request left);
    the served tokens are the reference's greedy ones; the pool holds a
    state and a convolution leaf a recurrent layer and one latent
    leaf."""
    from bluefog_tpu.serving import protocol

    params = _kda_params(seed=2)
    rng = np.random.default_rng(10)
    lengths, budgets = (27, 9, 33, 5, 1), (6, 9, 4, 12, 5)
    eng, reqs = served_model.serve(
        KDA_FAMILY.model_config(KDA_SZ, key_block=8), params,
        [rng.integers(0, 128, n) for n in lengths], budgets)
    for r in reqs:
        served_model.assert_served_is_the_references_greedy(
            KDA_REF, KDA_SZ, params, r, TOL)
    kinds = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            eng.pool.cache)[0]:
        kinds.setdefault(protocol.leaf_kind(path), []).append(leaf.shape)
    assert kinds[protocol.STATE] == [(2, 1, 3, 96), (2, 1, 4, 8, 8)] * 4
    assert kinds[protocol.FULL] == [(2, 1, 72, 24)]
    assert eng.cfg.cache_kinds() == {"full": (1, None)}
    assert eng.cfg.streamed_positions([3, -1]) == (("full", 2 * 72),)
    assert eng.cfg.rebuilt_positions(8, 4) == 16


def test_the_engine_serves_the_kernels_tokens_and_counts_what_it_reads():
    """The two-kind model at the kernel's width (a state of 128 x 128 a
    head) through three slots, five requests, with the single-token
    steps as kernels (``decode_attn="pallas"``, interpreted: the
    recurrent layers follow the layout's resolved field) and as XLA's:
    the same tokens, though a reused slot's leaf holds the state its
    last request left (the index-0 rule rides in the kernel's plan, and
    a slot that does not decode is not written).  The slots whose state
    a decode program READS are the decoding ones under the kernel and
    every one of the pool under XLA."""
    from bluefog_tpu.observe import MetricsRegistry

    sz = dict(KDA_SZ, head_dim=128, qk_nope_head_dim=128, v_head_dim=128,
              num_hidden_layers=3, published_layers=[0, 1, 2])
    params = _kda_params(sz, seed=3)
    rng = np.random.default_rng(12)
    lengths, budgets = (9, 5, 13, 1, 6), (5, 8, 3, 6, 4)
    prompts = [rng.integers(0, 128, n) for n in lengths]
    served, read = {}, {}
    for attn in ("xla", "pallas"):
        reg = MetricsRegistry()
        eng, reqs = served_model.serve(
            KDA_FAMILY.model_config(sz, key_block=8), params, prompts,
            budgets, capacity=3, max_len=24, decode_attn=attn, registry=reg)
        assert eng.cfg.decode_attn == attn and eng.cfg.state_layers == 2
        served[attn] = [list(r.tokens) for r in reqs]
        count = lambda name: reg.counter(name, "").value
        assert count("bf_serving_state_steps_total") == 2 * sum(budgets)
        read[attn] = (count("bf_serving_state_streamed_steps_total"),
                      count("bf_serving_decode_steps_total"))
    assert served["pallas"] == served["xla"]
    assert read["pallas"][0] == 2 * sum(budgets)
    assert read["xla"][0] == 2 * 3 * read["xla"][1]
