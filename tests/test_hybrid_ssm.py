"""``models/hybrid_ssm.py``: a layer with a state-space mixer beside its
attention.  The recurrence's two forms and a float64 token loop are one
recurrence; the model's full forward pass, and chunks then steps through
the cache, are the benchmark reference's full forward pass; each part of
the mathematics left out of the REFERENCE fails the same tolerance; a
token that is not live leaves both state leaves bit for bit, and a call
at index 0 starts from nothing; ``ServingEngine`` serves it through the
protocol alone, and a prefix cache and the speculative step refuse it.

The tiny widths keep the published ratios (five query heads a key/value
head, two groups of state-space heads, a state twice a head's channels)
and the published multipliers; matrices are drawn at ``initializer_range``
0.2, which at a hidden size of 48 gives the mixers the share of the
residual that 0.02 gives them at 5,120 (at 0.02 the state-space mixer is
a 500th of the residual here and rounds away)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_model
from bluefog_tpu.models import hybrid_ssm as hs
from bluefog_tpu.serving import ServingEngine, SpeculativeConfig
from perfbench.harness import loader

FAMILY = loader.load_module(loader.ROOT, "families",
                            "ssm_gqa_parallel_decoder")
REF = loader.load_module(loader.ROOT, "references",
                         "ssm_gqa_parallel_decoder")
SZ = {
    "hidden_size": 48, "intermediate_size": 96, "num_attention_heads": 5,
    "num_key_value_heads": 1, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 128, "rms_norm_eps": 1e-5, "rope_theta": 100000000000,
    "max_position_embeddings": 256, "mamba_n_heads": 4, "mamba_d_head": 8,
    "mamba_d_ssm": 32, "mamba_d_state": 16, "mamba_n_groups": 2,
    "mamba_d_conv": 4, "mamba_chunk_size": 8, "mamba_use_mlp": True,
    "mamba_rms_norm": True, "mamba_norm_before_gate": False,
    "attn_layer_indices": None, "initializer_range": 0.2, "conv_std": 0.5,
    "compute_dtype": "float32", "param_dtype": "float32",
    "embedding_multiplier": 5.656854249492381,
    "lm_head_multiplier": 0.0078125, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375,
    "key_multiplier": 0.011048543456039804, "ssm_in_multiplier": 0.25,
    "ssm_out_multiplier": 0.08838834764831845,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
}
CFG = FAMILY.model_config(SZ)
# float32 on both sides, the sums in another order: the widest reading
# over this file's sequences is 4e-7 of the logits' deviation; the
# faintest part left out (the decay) reads 2e-2
TOL = 1e-5


def weights(seed=0):
    return served_model.params(FAMILY, SZ, seed)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 128, n).astype(np.int32)


# ------------------------------------------------------------------ #
# the recurrence alone
# ------------------------------------------------------------------ #
def token_loop(x, dt, a, bmat, cmat, skip, state):
    """The recurrence as written, float64, one sequence: x [T, H, P],
    dt [T, H], a [H], bmat and cmat [T, G, N], skip [H], state [H, P,
    N]."""
    s = np.array(state, np.float64)
    h, g = x.shape[1], bmat.shape[1]
    out = []
    for t in range(x.shape[0]):
        y = np.zeros(x.shape[1:])
        for i in range(h):
            grp = i // (h // g)
            s[i] = np.exp(dt[t, i] * a[i]) * s[i] \
                + dt[t, i] * np.outer(x[t, i], bmat[t, grp])
            y[i] = s[i] @ cmat[t, grp] + skip[i] * x[t, i]
        out.append(y)
    return np.stack(out), s


def draw(seed, t, h=4, p=3, n=5, g=2, live=None):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(t, h, p))
    dt = rng.uniform(0.001, 2.0, size=(t, h))
    if live is not None:
        dt = np.where(live[:, None], dt, 0.0)
    a = -rng.uniform(1.0, 16.0, size=(h,))
    bmat, cmat = rng.normal(size=(2, t, g, n))
    return x, dt, a, bmat, cmat, rng.normal(size=(h,)), \
        rng.normal(size=(h, p, n))


def f32(*xs):
    return [jnp.asarray(v, jnp.float32) for v in xs]


@pytest.mark.parametrize("t", [1, 2, 7, 8, 9, 17, 24])
def test_chunked_steps_and_the_token_loop_agree(t):
    """Several lengths at blocks of 8, with a block boundary inside from
    9 on and a last block part full at 9 and 17.  The tolerance is
    float32's own: every product is HIGHEST and the state float32."""
    x, dt, a, bmat, cmat, skip, state = draw(t, t)
    want_y, want_s = token_loop(x, dt, a, bmat, cmat, skip, state)
    xj, dtj, bj, cj, sj = (v[None] for v in f32(x, dt, bmat, cmat, state))
    aj, kj = f32(a, skip)
    y, s = jax.jit(hs.ssd_chunked, static_argnums=7)(
        xj, dtj, aj, bj, cj, kj, sj, 8)
    np.testing.assert_allclose(y[0], want_y, atol=2e-5)
    np.testing.assert_allclose(s[0], want_s, atol=2e-5)
    s1, outs, step = sj, [], jax.jit(hs.ssd_step)
    for i in range(t):
        y1, s1 = step(xj[:, i], dtj[:, i], aj, bj[:, i], cj[:, i], kj, s1)
        outs.append(y1[0])
    np.testing.assert_allclose(np.stack(outs), want_y, atol=2e-5)
    np.testing.assert_allclose(s1[0], want_s, atol=2e-5)


def test_tokens_that_are_not_live_leave_the_state_bit_for_bit():
    """``dt`` 0 is alpha 1 and no input: a padded tail of a chunk, and a
    single step of a slot that sits out, return the state they were
    given (the step) or the state the live tokens left (the chunk)."""
    live = np.arange(12) < 7
    x, dt, a, bmat, cmat, skip, state = draw(3, 12, live=live)
    xj, dtj, bj, cj, sj = (v[None] for v in f32(x, dt, bmat, cmat, state))
    aj, kj = f32(a, skip)
    chunked = jax.jit(hs.ssd_chunked, static_argnums=7)
    _, padded = chunked(xj, dtj, aj, bj, cj, kj, sj, 8)
    _, exact = chunked(xj[:, :7], dtj[:, :7], aj, bj[:, :7], cj[:, :7],
                       kj, sj, 8)
    np.testing.assert_array_equal(padded, exact)
    _, sat_out = jax.jit(hs.ssd_step)(xj[:, 9], dtj[:, 9], aj, bj[:, 9],
                                      cj[:, 9], kj, sj)
    np.testing.assert_array_equal(sat_out, sj)


# ------------------------------------------------------------------ #
# the model against the benchmark's reference
# ------------------------------------------------------------------ #
def test_the_full_forward_pass_is_the_references():
    params, toks = weights(), tokens(37)
    got = np.asarray(jax.jit(hs.HybridSsm(CFG).apply)(
        {"params": params}, toks[None]))[0]
    want = served_model.reference(REF, SZ, params, toks)
    assert served_model.gap(got, want) < TOL
    assert served_model.padding_moves(REF, SZ, params, toks) < TOL
    # the tree's leaves are the family's, name for name
    made = jax.eval_shape(lambda: hs.HybridSsm(CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"])
    assert jax.tree.map(lambda s: s.shape, made) \
        == jax.tree.map(lambda s: s.shape, params)


@pytest.mark.parametrize("chunk,prefill", [(8, 24), (5, 20), (12, 24),
                                           (16, 32)])
def test_chunks_then_steps_through_the_cache_are_the_full_forward_pass(
        chunk, prefill):
    """Prefill in chunks that do (8, 16) and do not (5, 12) divide into
    the scan's blocks of 8, then one token a call: the logits at every
    position are the reference's full forward pass."""
    params, toks = weights(1), tokens(41, 1)
    got = served_model.chunks_then_steps(CFG, params, toks, chunk, prefill)
    want = served_model.reference(REF, SZ, params, toks)
    assert served_model.gap(got, want) < TOL


def _reference_without(part, monkeypatch):
    """``(params, sizes)`` for the reference with one part left out."""
    params, sz = weights(), dict(SZ)
    mamba = lambda name, leaf: [params[f"layer_{i}"]["mamba"].__setitem__(
        name, leaf) for i in range(SZ["num_hidden_layers"])]
    if part == "decay":                    # alpha 1: nothing is forgotten
        mamba("A_log", jnp.full((4,), -40.0))
    elif part == "D":
        mamba("D", jnp.zeros((4,)))
    elif part == "gate":
        monkeypatch.setattr(
            REF, "gated_norm", lambda y, z, *rest, gated=REF.gated_norm:
            gated(y, jnp.full_like(z, 1.278), *rest))   # silu(1.278) = 1
    elif part == "convolution":            # the last tap alone, no bias
        mamba("conv_kernel", jnp.zeros((4, 96)).at[3].set(1.0))
        mamba("conv_bias", jnp.zeros((96,)))
    elif part == "convolution bias":
        mamba("conv_bias", jnp.zeros((96,)))
    elif part == "key_multiplier":
        sz["key_multiplier"] = 1.0
    elif part == "attention mixer":
        sz["attention_out_multiplier"] = 0.0
    elif part == "state-space mixer":
        sz["ssm_out_multiplier"] = 0.0
    return params, sz


@pytest.mark.parametrize("part", [
    "decay", "D", "gate", "convolution", "convolution bias",
    "key_multiplier", "attention mixer", "state-space mixer"])
def test_a_part_left_out_of_the_reference_fails_the_tolerance(
        part, monkeypatch):
    """The program as it is against the reference WITHOUT one part: the
    distance is at least a thousand tolerances (the faintest, the decay,
    reads 2e-2 of the logits' deviation)."""
    toks = tokens(37)
    got = np.asarray(jax.jit(hs.HybridSsm(CFG).apply)(
        {"params": weights()}, toks[None]))[0]
    params, sz = _reference_without(part, monkeypatch)
    want = np.asarray(REF.logits(params, jnp.asarray(toks), sz))
    assert served_model.gap(got, want) > 1000 * TOL, part


# ------------------------------------------------------------------ #
# the state leaves' rule (serving/protocol.py)
# ------------------------------------------------------------------ #
def _cached(chunk=8):
    cfg = CFG.serving_layout(served_model.PADDED, chunk=chunk)
    call = jax.jit(lambda p, c, t, live: cfg.apply_cached(
        p, c, t, all_logits=True, live=live))
    return cfg, call


def _states(cache):
    return [cache[f"layer_{i}"]["mamba"][leaf]
            for i in range(SZ["num_hidden_layers"])
            for leaf in ("state_ssm", "state_conv")]


def test_a_padded_tail_and_a_slot_that_sits_out_leave_both_state_leaves():
    cfg, call = _cached()
    params, toks = weights(), tokens(20)
    cache = cfg.init_cache(1, served_model.PADDED)
    assert {k: sorted(v) for k, v in cache["layer_0"].items()} == {
        "attention": ["cache_index", "cached_key", "cached_value"],
        "mamba": ["state_conv", "state_ssm"]}
    assert cache["layer_0"]["mamba"]["state_ssm"].shape == (1, 4, 8, 16)
    assert cache["layer_0"]["mamba"]["state_ssm"].dtype == jnp.float32
    assert cache["layer_0"]["mamba"]["state_conv"].shape == (1, 3, 96)
    _, cache = call(params, cache, toks[None, :8], jnp.ones((1, 8), bool))
    # a chunk of 8 of which 5 are tokens: whatever the tail holds, both
    # leaves come out the same bit for bit, and they are what a call of
    # those 5 leaves (another program: to float32 rounding)
    live = (jnp.arange(8) < 5)[None]
    _, padded = call(params, cache, toks[None, 8:16], live)
    other = np.concatenate([toks[8:13], tokens(3, 9)])
    assert not np.array_equal(other, toks[8:16])
    _, again = call(params, cache, other[None], live)
    _, exact = call(params, cache, toks[None, 8:13], jnp.ones((1, 5), bool))
    for a, b, c in zip(_states(padded), _states(again), _states(exact)):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-9)
    # a single step whose token is not live: both leaves as they were
    _, sat_out = call(params, exact, toks[None, 13:14],
                      jnp.zeros((1, 1), bool))
    for a, b in zip(_states(sat_out), _states(exact)):
        np.testing.assert_array_equal(a, b)
    _, stepped = call(params, exact, toks[None, 13:14],
                      jnp.ones((1, 1), bool))
    assert all(np.abs(np.asarray(a) - np.asarray(b)).max() > 0
               for a, b in zip(_states(stepped), _states(exact)))


def test_a_call_at_index_0_starts_from_nothing_whatever_the_leaves_hold():
    cfg, call = _cached()
    params, toks = weights(), tokens(9)
    clean = cfg.init_cache(1, served_model.PADDED)
    dirty = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf if path[-1].key == "cache_index"
        else jnp.full_like(leaf, 3.0), clean)
    for width in (8, 1):        # the chunked form and the step
        live = jnp.ones((1, width), bool)
        want, _ = call(params, clean, toks[None, :width], live)
        got, left = call(params, dirty, toks[None, :width], live)
        np.testing.assert_array_equal(got, want)
    assert float(np.abs(_states(left)[0]).max()) < 3.0


# ------------------------------------------------------------------ #
# through the engine
# ------------------------------------------------------------------ #
def test_the_engine_serves_staggered_requests_through_the_protocol_alone():
    """Five requests through three slots (a freed slot is admitted again
    and starts from zero state though its leaves hold the last
    request's), prompts that are and are not whole chunks, one of a
    single token: every served token is the reference's greedy one, the
    state counters count the model's layers, and the pool's gauges hold
    both kinds of memory."""
    from bluefog_tpu.observe import MetricsRegistry

    params, rng = weights(2), np.random.default_rng(4)
    lengths, budgets = (9, 16, 13, 1, 30), (6, 9, 4, 7, 5)
    prompts = [rng.integers(0, 128, n) for n in lengths]
    reg = MetricsRegistry()
    eng, reqs = served_model.serve(CFG, params, prompts, budgets,
                                   capacity=3, prefill_chunk=8,
                                   registry=reg)
    assert isinstance(eng.cfg, hs.HybridSsmConfig) and eng.cfg.block.decode
    for r in reqs:
        served_model.assert_served_is_the_references_greedy(
            REF, SZ, params, r, 1e-4)
    served_model.assert_other_lengths_compile_nothing(eng)
    count = lambda name, **kw: reg.counter(name, "", **kw).value
    layers = SZ["num_hidden_layers"]
    assert eng.cfg.state_layers == layers
    assert eng.cfg.cache_kinds() == {"full": (layers, None)}
    steps = count("bf_serving_decode_steps_total")
    # the three lengths after the five requests decode too
    assert count("bf_serving_state_steps_total") \
        == layers * (sum(budgets) + 18)
    assert count("bf_serving_state_chunk_tokens_total") \
        == layers * (sum(n - 1 for n in lengths) + 39 + 1 + 16)
    # the step is mapped over the pool: every slot's state, every step
    assert count("bf_serving_state_streamed_steps_total") \
        == layers * 3 * steps
    gauge = lambda name, **kw: reg.gauge(name, "", **kw).value
    state = layers * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert gauge("bf_serving_state_bytes_per_slot") == state
    assert gauge("bf_serving_cache_bytes", kind="state") == 3 * state
    assert gauge("bf_serving_cache_bytes", kind="full") \
        == 3 * layers * 2 * served_model.PADDED * 16 * 4


def test_a_prefix_cache_and_the_speculative_step_refuse_a_state_leaf():
    params = {"params": weights()}
    shape = dict(capacity=2, max_len=served_model.PADDED, prefill_chunk=8)
    with pytest.raises(ValueError, match="recurrent state"):
        ServingEngine(params, CFG, prefix_cache=True, **shape)
    with pytest.raises(ValueError, match="recurrent state"):
        ServingEngine(params, CFG, speculative=SpeculativeConfig(
            variables=params, cfg=CFG, lookahead=2), **shape)


def test_the_config_refuses_what_the_block_does_not_serve():
    with pytest.raises(NotImplementedError, match="full-precision"):
        CFG.serving_layout(64, kv_quant="int8")
    with pytest.raises(ValueError, match="ssm_groups"):
        FAMILY.model_config(dict(SZ, mamba_n_heads=3, mamba_d_ssm=24))
    with pytest.raises(ValueError, match="dense"):
        FAMILY.model_config(SZ, scan_layers=True)
    assert (CFG.block.head_dim, CFG.block.dim // CFG.block.n_heads) \
        == (16, 9)
    assert CFG.key_multiplier == SZ["key_multiplier"]
    assert CFG.state_streamed_steps(1, 7) == 7 * CFG.n_layers
