"""The looped stack (``models/looped.py``: the layers run several times
over shared weights, a cache for every pass) at tiny widths on the CPU,
against the plain reference of ``perfbench/references/
looped_dense_decoder.py`` on seeded weights.

Tolerances, on logits whose standard deviation is about 1.8 at these
widths (``initializer_range`` 0.2 and norm scales off 1, so that
attention, the gate and every norm matter):

* float32 program against the float32 reference: the largest difference
  of a logit under ``F32_TOL`` = 2e-4.  Both compute the same mathematics
  in another order (a cache's masked rows against a causal mask, a
  running softmax in the kernel); measured 5e-5 at most over three
  seeds.  The same program in bfloat16 differs by 0.7 and more, so a
  lower precision in a float32 case fails it (asserted).
* bfloat16 program (weights and activations bfloat16, float32 norms,
  softmax and logits) against the float32 reference: the root mean
  square of the difference under ``BF16_TOL`` = 0.25 of the logits'
  standard deviation.  Twelve layer applications with a norm after every
  sublayer read 0.06-0.10 over three seeds; one pass left out reads
  0.58-0.74: the limit holds the loop's structure, not its rounding.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_model
from bluefog_tpu.models import looped
from bluefog_tpu.models.llama import Llama, LlamaConfig
from bluefog_tpu.models.looped import LoopedConfig, init_params
from bluefog_tpu.observe import registry as obs_registry
from bluefog_tpu.serving import Request, ServingEngine, SpeculativeConfig
from bluefog_tpu.serving.kv_pool import SlotPool, pack_stats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL, BF16_TOL = 2e-4, 0.25
LAYERS, STEPS, MAX_LEN = 3, 4, 64
SZ = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
      "num_key_value_heads": 4, "head_dim": 16, "num_hidden_layers": LAYERS,
      "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
      "total_ut_steps": STEPS, "early_exit_threshold": 1,
      "compute_dtype": "float32", "param_dtype": "float32"}


@pytest.fixture(scope="module")
def ref():
    from perfbench.harness import loader

    return loader.load_module(REPO, "references", "looped_dense_decoder")


def config(dtype=jnp.float32, steps=STEPS, **extra) -> LoopedConfig:
    block = LlamaConfig(vocab_size=256, dim=64, n_layers=LAYERS, n_heads=4,
                        n_kv_heads=4, hidden_dim=128, max_seq_len=MAX_LEN,
                        rope_theta=1e6, norm_eps=1e-6, dtype=dtype)
    return LoopedConfig(block, loop_steps=steps, initializer_range=0.2,
                        **extra)


def weights(cfg, seed=0):
    """Seeded weights with norm scales and a gate bias that are no
    identities, so that a norm or the bias left out shows."""
    params = init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    wobble = lambda leaf: leaf + 0.3 * jax.random.normal(
        next(keys), leaf.shape, leaf.dtype)
    params["layers"]["block"] = {
        name: jax.tree.map(wobble, sub) if name.endswith("norm") else sub
        for name, sub in params["layers"]["block"].items()}
    params["norm"] = jax.tree.map(wobble, params["norm"])
    params["exit_gate"]["bias"] = jnp.asarray([0.4], jnp.float32)
    return params


def tokens(n, seed=3, batch=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, n), 0, 256,
                              jnp.int32)


def reference_logits(ref, params, toks, sz=SZ):
    return np.stack([served_model.reference(ref, sz, params, row)
                     for row in toks])


def differs(got, want, dtype) -> float:
    """The reading a tolerance is held against: the largest difference
    (float32), or the root mean square over the logits' standard
    deviation (bfloat16)."""
    d = np.asarray(got, np.float64) - want
    if dtype == jnp.float32:
        return float(np.abs(d).max())
    return float(np.sqrt((d ** 2).mean()) / want.std())


# ------------------------------------------------------------------ #
# the whole forward, and the cache, against the reference
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)],
                         ids=["float32", "bfloat16"])
def test_the_full_forward_is_the_references(ref, dtype, tol):
    cfg = config(dtype)
    params = weights(cfg)
    toks = tokens(24, batch=2)
    want = reference_logits(ref, params, toks)
    held = jax.tree.map(lambda a: a.astype(dtype), params)
    got, _ = cfg.apply(held, toks)
    err = differs(got, want, dtype)
    assert err < tol, err
    assert 1.0 < want.std() < 3.0      # what the tolerances are read against
    if dtype == jnp.float32:
        # padded: twelve applications carry a sum's order further than three
        assert served_model.padding_moves(ref, SZ, params, toks[0]) \
            * want.std() < 0.5 * F32_TOL
    if dtype == jnp.bfloat16:
        # tight enough: bfloat16 would fail the float32 case
        assert differs(got, want, jnp.float32) > 100 * F32_TOL
        # and one pass left out fails the bfloat16 one
        short, _ = config(steps=STEPS - 1).apply(params, toks)
        assert differs(short, want, dtype) > 2 * BF16_TOL


@pytest.mark.parametrize("dtype,tol,decode_attn", [
    (jnp.float32, F32_TOL, "xla"), (jnp.float32, F32_TOL, "pallas"),
    (jnp.bfloat16, BF16_TOL, "pallas")],
    ids=["float32-xla", "float32-pallas", "bfloat16-pallas"])
def test_prefill_in_chunks_then_decode_through_the_cache(ref, dtype, tol,
                                                         decode_attn):
    """Two chunks (the second part full), then single-token steps, every
    call's logits against the reference's full forward."""
    cfg = config(dtype).serving_layout(MAX_LEN, decode_attn=decode_attn)
    params = weights(cfg)
    toks = tokens(23, batch=2)
    want = reference_logits(ref, params, toks)
    held = jax.tree.map(lambda a: a.astype(dtype), params)
    cache = cfg.init_cache(2, MAX_LEN)
    call = jax.jit(cfg.apply_cached, static_argnames="all_logits")
    got = []
    for lo, hi in ((0, 8), (8, 13)):
        lg, cache = call(held, cache, toks[:, lo:hi], all_logits=True)
        got.append(lg)
    for i in range(13, 23):
        lg, cache = call(held, cache, toks[:, i:i + 1])
        got.append(lg)
    err = differs(np.concatenate(got, 1), want, dtype)
    assert err < tol, err
    assert int(cache["cache_index"]) == 23


def test_the_exit_distribution_is_the_references(ref):
    cfg = config()
    params = weights(cfg)
    toks = tokens(20)
    want = np.asarray(ref.exit_pdf(params, toks[0], SZ))
    _, pdf = cfg.apply(params, toks)
    assert np.abs(np.asarray(pdf[0]) - want).max() < 1e-5
    assert np.allclose(want.sum(-1), 1.0, atol=1e-6)
    # no pass is a certainty at this draw
    assert want.min() > 0 and want.max() < 1
    # the cache's stat leaf: the last token's, after a chunk and a step
    dcfg = cfg.serving_layout(MAX_LEN)
    cache = dcfg.init_cache(1, MAX_LEN)
    _, cache = dcfg.apply_cached(params, cache, toks[:, :19])
    assert np.abs(np.asarray(cache["stat_exit_pdf"][0]) - want[18]).max() \
        < 1e-5
    _, cache = dcfg.apply_cached(params, cache, toks[:, 19:])
    assert np.abs(np.asarray(cache["stat_exit_pdf"][0]) - want[19]).max() \
        < 1e-5


# ------------------------------------------------------------------ #
# a pass reads its own keys alone
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("form", ["xla", "pallas", "chunk", "in_pool"])
def test_a_pass_reads_no_key_that_another_pass_wrote(form):
    """``_cached_attend`` on leaf ``j`` of the stacked pair: noise on
    every OTHER leaf (the other passes' and the other layers') moves
    nothing; noise on leaf ``j`` does."""
    block = config().serving_layout(
        MAX_LEN, decode_attn="pallas" if form == "pallas" else "xla").block
    n = STEPS * LAYERS
    t = 1 if form in ("xla", "pallas") else 5
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    shape = (2, n, 4, MAX_LEN, 16)
    slot = None
    if form == "in_pool":
        shape, slot = (3,) + shape, jnp.int32(1)
    kv = tuple(jax.random.normal(k, shape, jnp.float32) for k in keys[:2])
    q, k, v = (jax.random.normal(kk, (2, t, 4, 16), jnp.float32)
               for kk in keys[2:5])
    idx, leaf = jnp.int32(17), jnp.int32(1 * LAYERS + 2)   # pass 2, layer 3
    run = lambda kv: looped._cached_attend(block, q, k, v, kv, leaf, idx,
                                           None, slot)[0]
    base = run(kv)
    mine = (jnp.arange(n) == leaf)[:, None, None, None]
    noise = jax.random.normal(keys[5], shape, jnp.float32)
    others = tuple(jnp.where(mine, c, c + noise) for c in kv)
    assert np.array_equal(np.asarray(run(others)), np.asarray(base))
    own = tuple(jnp.where(mine, c + noise, c) for c in kv)
    assert np.abs(np.asarray(run(own)) - np.asarray(base)).max() > 1e-2
    if form == "in_pool":
        elsewhere = (jnp.arange(3) == 1)[:, None, None, None, None, None]
        moved = tuple(jnp.where(elsewhere, c, c + noise) for c in kv)
        assert np.array_equal(np.asarray(run(moved)), np.asarray(base))


def test_the_stacked_kernel_reads_its_leaf_where_it_lies():
    """``decode_attention(leaf=)`` over a stack against the kernel over
    the slice, rows at their own positions, one dead, under the
    engine's ``vmap`` too."""
    from bluefog_tpu.parallel.pallas_decode import decode_attention

    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    k_all, v_all = (jax.random.normal(kk, (3, 5, 4, 32, 16), jnp.float32)
                    for kk in keys[:2])
    q = jax.random.normal(keys[2], (3, 1, 8, 16), jnp.float32)
    idx = jnp.asarray([3, 30, 11], jnp.int32)
    live = jnp.asarray([True, False, True])
    for leaf in (0, 3):
        want = decode_attention(q, k_all[:, leaf], v_all[:, leaf], idx,
                                live=live, block_s=8)
        got = decode_attention(q, k_all, v_all, idx, live=live,
                               leaf=jnp.int32(leaf), block_s=8)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        mapped = jax.vmap(lambda qq, kk, vv, ii, ll: decode_attention(
            qq[None], kk[None], vv[None], ii, live=ll, leaf=leaf,
            block_s=8)[0])(q, k_all, v_all, idx, live)
        assert np.allclose(np.asarray(mapped), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("live", [(True, True, True), (False, True, False),
                                  (True, False, True)],
                         ids=["all", "first-dead", "middle-dead"])
def test_the_writing_step_is_the_write_then_the_step(dtype, live):
    """``decode_attention(fresh=)`` writes the step's rows and attends
    over them in one call: the same output and the same stacks as the
    rows written first (``dynamic_update_slice``) and the kernel after;
    a row that does not decode writes nothing and no other row, leaf or
    position moves."""
    from bluefog_tpu.parallel.pallas_decode import decode_attention

    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    k_all, v_all = (jax.random.normal(kk, (3, 5, 4, 64, 16)).astype(dtype)
                    for kk in keys[:2])
    q = jax.random.normal(keys[2], (3, 1, 8, 16)).astype(dtype)
    k_new, v_new = (jax.random.normal(kk, (3, 4, 16)).astype(dtype)
                    for kk in keys[3:])
    idx = jnp.asarray([3, 37, 16], jnp.int32)     # tiles 0, 2 and 1
    live = jnp.asarray(live)
    leaf = jnp.int32(3)

    def written(c, new):
        rows = jnp.arange(64)[None, None, :, None] == idx[:, None, None, None]
        at = (live[:, None, None, None] & rows)[:, None] \
            & (jnp.arange(5) == leaf)[None, :, None, None, None]
        return jnp.where(at, new[:, None, :, None, :], c)

    want_k, want_v = written(k_all, k_new), written(v_all, v_new)
    want = decode_attention(q, want_k, want_v, idx, live=live, leaf=leaf,
                            block_s=32)
    got, got_k, got_v = decode_attention(q, k_all, v_all, idx, live=live,
                                         leaf=leaf, fresh=(k_new, v_new),
                                         block_s=32)
    assert np.array_equal(np.asarray(got_k), np.asarray(want_k))
    assert np.array_equal(np.asarray(got_v), np.asarray(want_v))
    assert np.allclose(np.asarray(got, np.float32),
                       np.asarray(want, np.float32), atol=1e-6)
    # under the engine's map over slots, each slot a batch of one
    mapped = jax.vmap(lambda qq, kk, vv, ii, ll, kn, vn: decode_attention(
        qq[None], kk[None], vv[None], ii, live=ll, leaf=leaf,
        fresh=(kn[None], vn[None]), block_s=32))(
            q, k_all, v_all, idx, live, k_new, v_new)
    assert np.array_equal(np.asarray(mapped[1][:, 0]), np.asarray(want_k))
    assert np.array_equal(np.asarray(mapped[2][:, 0]), np.asarray(want_v))
    assert np.allclose(np.asarray(mapped[0][:, 0], np.float32),
                       np.asarray(want, np.float32), atol=1e-6)


# ------------------------------------------------------------------ #
# one pass without the extra norms is the dense model
# ------------------------------------------------------------------ #
def test_one_pass_without_the_extra_norms_is_llama_leaf_for_leaf():
    cfg = config(steps=1, sandwich_norms=False, rope_halves=False)
    llama = dataclasses.replace(cfg.block, scan_layers=True)
    toks = tokens(21, batch=2)
    variables = Llama(llama).init(jax.random.PRNGKey(0), toks)
    params = dict(variables["params"])
    mine = dict(params, exit_gate=init_params(
        cfg, jax.random.PRNGKey(1))["exit_gate"])
    # the parameter tree is Llama(scan_layers=True)'s, and the gate
    drawn = init_params(cfg, jax.random.PRNGKey(1))
    assert jax.tree.structure(drawn) == jax.tree.structure(mine)
    assert jax.tree.map(jnp.shape, drawn) == jax.tree.map(jnp.shape, mine)
    want = Llama(llama).apply({"params": params}, toks)
    got, pdf = cfg.apply(mine, toks)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert np.array_equal(np.asarray(pdf), np.ones((2, 21, 1)))
    # and through the cache: a chunk, then a step; K/V leaf for leaf
    dcfg, dllama = cfg.serving_layout(MAX_LEN), llama.serving_layout(MAX_LEN)
    cache, lcache = dcfg.init_cache(2, MAX_LEN), dllama.init_cache(2, MAX_LEN)
    for lo, hi in ((0, 20), (20, 21)):
        lg, cache = dcfg.apply_cached(mine, cache, toks[:, lo:hi])
        lw, lcache = dllama.apply_cached(params, lcache, toks[:, lo:hi])
        assert np.abs(np.asarray(lg) - np.asarray(lw)).max() < 1e-5
    theirs = lcache["layers"]["block"]["attention"]
    for name in ("cached_key", "cached_value"):
        # Llama's scan stacks [layers, B, ...]; the loop [B, leaves, ...]
        assert np.abs(np.asarray(cache[name])
                      - np.swapaxes(np.asarray(theirs[name]), 0, 1)).max() \
            < 1e-5
    assert int(cache["cache_index"]) == 21 \
        == int(theirs["cache_index"][0])


def test_rotation_by_halves_is_an_argument_whose_default_is_interleaved():
    from bluefog_tpu.models.llama import rotary_embed

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 8), jnp.float32)
    pos = jnp.arange(6) + 3
    # the two layouts are one rotation under a permutation of the head
    perm = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    halves = rotary_embed(x[..., perm], pos, 1e4, halves=True)
    assert np.allclose(np.asarray(halves),
                       np.asarray(rotary_embed(x, pos, 1e4))[..., perm],
                       atol=1e-6)
    # the default's program does not move: one trace with and without
    plain = jax.make_jaxpr(lambda a: rotary_embed(a, pos, 1e4))(x)
    explicit = jax.make_jaxpr(
        lambda a: rotary_embed(a, pos, 1e4, None, False))(x)
    assert str(plain) == str(explicit)


# ------------------------------------------------------------------ #
# the loop is rolled
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("tokens_a_call", [1, 8], ids=["decode", "chunk"])
def test_a_lowering_holds_one_copy_of_the_block_whatever_the_passes(
        tokens_a_call):
    def dots(steps):
        cfg = config(steps=steps).serving_layout(MAX_LEN)
        params = jax.eval_shape(lambda: init_params(
            cfg, jax.random.PRNGKey(0)))
        cache = jax.eval_shape(lambda: cfg.init_cache(1, MAX_LEN))
        text = jax.jit(cfg.apply_cached).lower(
            params, cache, jax.ShapeDtypeStruct((1, tokens_a_call),
                                                jnp.int32)).as_text()
        return text.count("dot_general")

    # 7 projections, 2 attention products, the gate and the head
    assert dots(1) == dots(4) == 11


# ------------------------------------------------------------------ #
# through the engine
# ------------------------------------------------------------------ #
def served(cfg, params, lengths, registry=None, **engine):
    options = dict(capacity=3, max_len=MAX_LEN, prefill_chunk=8)
    options.update(engine)
    eng = ServingEngine({"params": params}, cfg, registry=registry,
                        **options)
    rng = np.random.default_rng(7)
    requests = [Request(rng.integers(0, 256, n, np.int32), m)
                for n, m in lengths]
    seen = {}                 # the slot each request was given
    for r in requests:
        eng.submit(r)
    for _ in range(10_000):
        busy = eng.step()
        for r in requests:
            if r.slot is not None:
                seen.setdefault(r.rid, r.slot)
        if not busy:
            break
    return eng, requests, seen


LENGTHS = [(20, 6), (5, 9), (1, 4), (30, 5), (9, 7), (17, 3)]


@pytest.mark.parametrize("decode_attn", ["xla", "pallas"])
def test_the_engine_serves_the_references_tokens_with_a_freed_slot_reused(
        ref, decode_attn):
    """Three slots, six requests that come and go: every served token is
    the one the reference's logits put first (to ``F32_TOL``: a served
    token's reference logit lies no further under the reference's
    best)."""
    cfg = config()
    params = weights(cfg)
    reg = obs_registry.MetricsRegistry()
    eng, requests, seen = served(cfg, params, LENGTHS, registry=reg,
                                 decode_attn=decode_attn)
    assert all(r.state == "completed" for r in requests)
    slots = [seen[r.rid] for r in requests]
    assert len(set(slots)) == 3 < len(slots)      # a freed slot was reused
    for r in requests:
        served_model.assert_served_is_the_references_greedy(
            ref, SZ, params, r, F32_TOL, in_deviations=False)
    # what the engine counts of the loop
    count = lambda name, **labels: reg.counter(name, "", **labels).value
    assert reg.gauge("bf_serving_loop_steps", "").value == STEPS
    live = count("bf_serving_prefill_tokens_total") \
        + count("bf_serving_decode_slots_total")
    assert count("bf_serving_loop_layer_tokens_total") \
        == live * STEPS * LAYERS
    kinds = eng.cfg.cache_kinds()
    assert kinds == {"full": (STEPS * LAYERS, None)}
    fused = decode_attn == "pallas"
    assert eng.cfg.decode_attn == decode_attn
    steps = count("bf_serving_decode_steps_total")
    streamed = count("bf_serving_streamed_positions_total", kind="full")
    if fused:
        assert 0 < streamed < steps * 3 * MAX_LEN * STEPS * LAYERS
    else:
        assert streamed == steps * 3 * MAX_LEN * STEPS * LAYERS
    assert reg.gauge("bf_serving_cache_bytes", "", kind="full").value \
        == 3 * MAX_LEN * STEPS * LAYERS * 2 * 4 * 16 * 4
    mean = reg.gauge("bf_serving_exit_pass_mean", "").value
    assert 1.0 < mean < STEPS


def test_the_exit_pass_mean_is_the_references(ref):
    """One request alone: the gauge is the mean, over its decoded
    tokens, of ``sum_t t p_t`` at the position each was decoded from."""
    cfg = config()
    params = weights(cfg)
    reg = obs_registry.MetricsRegistry()
    _, (r,), _ = served(cfg, params, [(11, 6)], registry=reg)
    pdf = np.asarray(ref.exit_pdf(params, jnp.asarray(r.output()[:-1]),
                                  SZ))[r.prompt.size - 1:]
    want = (pdf * np.arange(1, STEPS + 1)).sum(-1).mean()
    assert reg.gauge("bf_serving_exit_pass_mean", "").value \
        == pytest.approx(want, abs=1e-5)


def test_float_stat_leaves_travel_as_their_bits():
    cfg = config().serving_layout(MAX_LEN)
    pool = SlotPool(cfg, 3, MAX_LEN)
    pdf = jax.random.uniform(jax.random.PRNGKey(0), (3, 1, STEPS),
                             jnp.float32)
    pool.cache = dict(pool.cache, stat_exit_pdf=pdf)
    rows = np.asarray(pack_stats(pool.cache))
    assert rows.shape == (STEPS, 3) == (pool.stat_rows, 3)
    back = pool.unpack_stats(rows)["stat_exit_pdf"][0]
    assert back.dtype == np.float32
    assert np.array_equal(back, np.asarray(pdf))


def test_the_chunk_program_writes_the_slot_where_it_lies():
    """``apply_in_pool`` on slot 1 of a pool against ``apply_cached`` on
    the slot's own tree: the same logits, the same rows, the other
    slots untouched."""
    cfg = config().serving_layout(MAX_LEN)
    params = weights(cfg)
    pool = jax.tree.map(
        lambda leaf: jax.random.normal(jax.random.PRNGKey(1), (3,)
                                       + leaf.shape).astype(leaf.dtype),
        cfg.init_cache(1, MAX_LEN))
    pool["cache_index"] = jnp.asarray([4, 9, 0], jnp.int32)
    toks = tokens(8)
    lg, new = cfg.apply_in_pool(params, pool, jnp.int32(1), toks)
    slot = jax.tree.map(lambda leaf: leaf[1], pool)
    want_lg, want = cfg.apply_cached(params, slot, toks)
    assert np.allclose(np.asarray(lg), np.asarray(want_lg), atol=1e-5)
    for name, leaf in new.items():
        assert np.allclose(np.asarray(leaf[1]), np.asarray(want[name]),
                           atol=1e-6), name
        for other in (0, 2):
            assert np.array_equal(np.asarray(leaf[other]),
                                  np.asarray(pool[name][other])), name


def test_prefix_reuse_restores_the_rows_of_every_pass(ref):
    """The prefix cache's two programs find the stacked leaves'
    position axis by its scaling: a prompt served twice comes out the
    same, the second time from restored chunks."""
    cfg = config()
    params = weights(cfg)
    reg = obs_registry.MetricsRegistry()
    eng = ServingEngine({"params": params}, cfg, capacity=2,
                        max_len=MAX_LEN, prefill_chunk=8, registry=reg,
                        prefix_cache=True, prefix_cache_bytes=1 << 26)
    prompt = np.random.default_rng(1).integers(0, 256, 27, np.int32)
    runs = []
    for _ in range(2):
        r = Request(prompt.copy(), 6)
        eng.submit(r)
        eng.run()
        runs.append(list(r.tokens))
    assert runs[0] == runs[1]
    assert reg.counter("bf_serving_prefix_chunks_restored_total",
                       "").value == 3
    want = served_model.reference(ref, SZ, params, np.concatenate(
        [prompt, runs[1]])[:-1])[prompt.size - 1:]
    assert (want.argmax(-1) == np.asarray(runs[1])).all()


def test_the_speculative_step_verifies_over_the_loops_leaves():
    """A one-pass draft under the four-pass target: the target's verify
    window is a call of several tokens over its stacked leaves, rolled
    back by the index; greedy output is the plain engine's."""
    cfg = config()
    params = weights(cfg)
    draft = config(steps=1)
    spec = SpeculativeConfig({"params": weights(draft, seed=4)}, draft,
                             lookahead=3)
    _, plain, _ = served(cfg, params, LENGTHS[:3])
    _, fast, _ = served(cfg, params, LENGTHS[:3], speculative=spec)
    assert [r.tokens for r in fast] == [r.tokens for r in plain]


def test_the_layout_refuses_what_the_stacked_leaf_cannot_hold():
    with pytest.raises(NotImplementedError, match="full-precision"):
        config().serving_layout(MAX_LEN, kv_quant="int8")
    with pytest.raises(ValueError, match="loop_steps"):
        config(steps=0)
