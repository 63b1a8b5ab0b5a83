"""The afmoe model (window and full attention mixed, gated heads,
sandwich norms, an expert layer told which experts it holds) against
the benchmark's plain reference, at a small size on the CPU: the full
forward pass, the served path through ``ServingEngine`` (chunked
prefill across the window's edge and the ring's wrap, slots reused,
long beside short), the shares of an expert layer, the router, and the
pool's two kinds of cache."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_model
from bluefog_tpu.models import afmoe
from bluefog_tpu.serving import Request, ServingEngine, SlotPool
from bluefog_tpu.serving.prefix_cache import PrefixCache
from perfbench.harness import loader

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = loader.load_module(REPO, "references", "afmoe_decoder")
FAMILY = loader.load_module(REPO, "families", "afmoe_decoder")

pytestmark = pytest.mark.serving

WINDOW = 8
# float32 program against float32-highest reference, both on the CPU:
# what is left is the order of the sums (the program's loop over the
# experts hit, its softmax over a ring).  A routing flip would show as
# ~1e-1, a missing norm or gate as ~1.
TOL = 2e-4

SZ = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"],
    "sliding_window": WINDOW, "vocab_size": 128, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "num_experts": 16, "router_outputs": 16,
    "experts_held_from": 0, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "route_norm": True, "route_scale": 2.448,
    "mup_enabled": True, "initializer_range": 0.2, "router_bias_std": 0.1,
    "compute_dtype": "float32", "param_dtype": "float32",
}


CHUNK, MAX_LEN = 4, 64            # the ring: WINDOW + CHUNK = 12 rows


def _params(sz=SZ, seed=0):
    return served_model.params(FAMILY, sz, seed)


def _reference(params, tokens, sz=SZ):
    return served_model.reference(REF, sz, params, tokens)


# ------------------------------------------------------------------ #
# (a) the full forward pass
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("held", [(0, 16), (4, 8)])
def test_full_forward_matches_the_reference(held):
    sz = dict(SZ, experts_held_from=held[0], num_experts=held[1])
    params = _params(sz)
    tokens = np.random.default_rng(1).integers(0, sz["vocab_size"], 40)
    model = afmoe.Afmoe(FAMILY.model_config(sz))
    got = jax.jit(lambda p, t: model.apply({"params": p}, t)[0])(
        params, tokens[None])
    want = _reference(params, tokens, sz)
    assert got.shape == want.shape == (40, sz["vocab_size"])
    assert np.abs(np.asarray(got) - want).max() < TOL * want.std()
    if held == (0, 16):
        assert served_model.padding_moves(REF, sz, params, tokens) \
            < 0.25 * TOL


# ------------------------------------------------------------------ #
# (b) through the engine
# ------------------------------------------------------------------ #
def _serve(params, prompts, budgets, **engine):
    return served_model.serve(FAMILY.model_config(SZ), params, prompts,
                              budgets, **{"max_len": MAX_LEN, **engine})


@pytest.mark.parametrize("chunk, lengths, budgets", [
    # a chunk that straddles the window's edge; prompts several windows
    # long, so the ring (8 + 4 rows) wraps more than once
    (4, (30, 6), (12, 12)),
    # the chunk as wide as the window, a long prompt beside a short one
    (8, (45, 3), (10, 16)),
    # four requests through two slots: slots reused after a wrapped ring
    (4, (27, 9, 33, 5), (6, 9, 4, 12)),
    # a one-token prompt (no prefill at all) beside a long one
    (2, (1, 41), (20, 5)),
])
def test_served_tokens_match_the_reference(chunk, lengths, budgets):
    params = _params()
    rng = np.random.default_rng(sum(lengths))
    prompts = [rng.integers(0, SZ["vocab_size"], n) for n in lengths]
    _, reqs = _serve(params, prompts, budgets, prefill_chunk=chunk)
    for r in reqs:
        served_model.assert_served_is_the_references_greedy(REF, SZ, params,
                                                            r, TOL)


def test_no_recompile_inside_the_window_or_across_the_wrap():
    eng, _ = _serve(_params(), [np.arange(5)], [3])
    served_model.assert_other_lengths_compile_nothing(eng)


# ------------------------------------------------------------------ #
# (b') a chunk's attention, block of key rows by block
# ------------------------------------------------------------------ #
def _chunk_layer(kind, dtype):
    """One attention layer in the serving layout, its parameters, and a
    cache whose every row holds something (a slot that was used before):
    rows past the index are garbage no query may see."""
    cfg = FAMILY.model_config(dict(SZ, compute_dtype=dtype)) \
        .serving_layout(MAX_LEN, chunk=CHUNK)
    layer = afmoe.Attention(cfg, kind)
    x = jnp.zeros((1, CHUNK, cfg.dim), cfg.dtype)
    variables = layer.init(jax.random.PRNGKey(0), x)
    return layer, variables["params"], variables["cache"]


def _used(cache, batch, idx, seed):
    rng = np.random.default_rng(seed)
    return {name: jnp.asarray(idx, jnp.int32) if name == "cache_index"
            else jnp.asarray(rng.standard_normal((batch,) + leaf.shape[1:]),
                             leaf.dtype)
            for name, leaf in cache.items()}


def _attend_a_chunk(layer, params, cache, x, monkeypatch, plain):
    """The layer's output for one cached call of several tokens: by
    ``blocked_attend`` in blocks of 4 key rows, or (``plain``) by
    ``attend`` over every row, the parent's lowering."""
    with monkeypatch.context() as m:
        m.setattr(afmoe, "KEY_BLOCK", 4)
        if plain:
            m.setattr(afmoe, "blocked_attend",
                      lambda *args: afmoe.attend(*args[:-1]))
        out, mut = layer.apply({"params": params, "cache": cache}, x,
                               mutable=["cache"])
    return np.asarray(out, np.float32), mut["cache"]


def _tolerance(dtype, want):
    """Both forms hold scores, weights and sums in float32 and differ in
    the order of a softmax's sum over at most 64 keys; the output is
    rounded to ``dtype`` once, so two roundings of nearly equal numbers
    may lie an ulp apart."""
    eps = max(2 * float(jnp.finfo(dtype).eps),
              64 * float(jnp.finfo(jnp.float32).eps))
    return eps * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind, idx, batch", [
    (afmoe.FULL, 0, 1),           # a first chunk
    (afmoe.SLIDING, 0, 1),
    # the chunk before it was padded: the index is on no block's edge
    # and the rows above it hold the padded tail's keys
    (afmoe.FULL, 6, 1),
    (afmoe.SLIDING, 6, 1),        # a ring before the wrap
    (afmoe.FULL, MAX_LEN - CHUNK, 1),   # ends on the leaf's last row
    (afmoe.SLIDING, 10, 1),       # across the wrap
    # (idx + t) % ring == 0: rows 0-3 hold the positions the call's last
    # query no longer sees, so its first block is wholly masked
    (afmoe.SLIDING, 8, 1),
    (afmoe.SLIDING, 20, 1),
    (afmoe.FULL, 17, 3),          # more than one sequence
    (afmoe.SLIDING, 29, 3),
])
def test_a_chunk_attends_block_by_block_as_over_every_row(
        kind, idx, batch, dtype, monkeypatch):
    layer, params, cache = _chunk_layer(kind, dtype)
    cache = _used(cache, batch, idx, seed=idx)
    x = jnp.asarray(np.random.default_rng(idx + 1).standard_normal(
        (batch, CHUNK, layer.cfg.dim)), layer.cfg.dtype)
    want, cache_want = _attend_a_chunk(layer, params, cache, x,
                                       monkeypatch, plain=True)
    got, cache_got = _attend_a_chunk(layer, params, cache, x, monkeypatch,
                                     plain=False)
    assert np.isfinite(want).all() and np.abs(want).max() > 1e-3
    assert np.abs(got - want).max() <= _tolerance(layer.cfg.dtype, want)
    for name in cache_want:       # the write is the same write
        np.testing.assert_array_equal(np.asarray(cache_got[name]),
                                      np.asarray(cache_want[name]))


@pytest.mark.parametrize("kind", [afmoe.FULL, afmoe.SLIDING])
def test_slots_under_vmap_each_walk_to_their_own_bound(kind, monkeypatch):
    """The speculative step maps a call of several tokens over slots:
    the bound is then one a slot."""
    layer, params, cache = _chunk_layer(kind, "float32")
    starts = (2, 9, 31)
    caches = [_used(cache, 1, idx, seed=idx) for idx in starts]
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *caches)
    xs = jnp.asarray(np.random.default_rng(5).standard_normal(
        (len(starts), 1, CHUNK, layer.cfg.dim)), jnp.float32)
    with monkeypatch.context() as m:
        m.setattr(afmoe, "KEY_BLOCK", 4)
        got = jax.vmap(lambda c, x: layer.apply(
            {"params": params, "cache": c}, x, mutable=["cache"])[0])(
                stacked, xs)
    for i, c in enumerate(caches):
        want, _ = _attend_a_chunk(layer, params, c, xs[i], monkeypatch,
                                  plain=True)
        assert np.abs(np.asarray(got[i]) - want).max() \
            <= _tolerance(jnp.float32, want)


@pytest.mark.parametrize("kind, idx, live_rows", [
    (afmoe.FULL, 9, 16),          # 13 positions written: 4 blocks of 4
    (afmoe.SLIDING, 2, 8),        # 6 written, the ring not yet wrapped
])
def test_a_chunk_reads_no_row_past_the_last_block_written(
        kind, idx, live_rows, monkeypatch):
    """Keys AND values of every row past the bound are NaN: the answer
    is the clean cache's, so those blocks were never read (``attend``
    multiplies them by a weight of 0 and answers NaN)."""
    layer, params, cache = _chunk_layer(kind, "float32")
    clean = _used(cache, 1, idx, seed=7)
    size = WINDOW + CHUNK if kind == afmoe.SLIDING else MAX_LEN
    dead = (jnp.arange(size) >= live_rows)[None, None, :, None]
    poisoned = {name: leaf if name == "cache_index"
                else jnp.where(dead, jnp.nan, leaf)
                for name, leaf in clean.items()}
    x = jnp.asarray(np.random.default_rng(8).standard_normal(
        (1, CHUNK, layer.cfg.dim)), jnp.float32)
    want, _ = _attend_a_chunk(layer, params, clean, x, monkeypatch,
                              plain=True)
    got, _ = _attend_a_chunk(layer, params, poisoned, x, monkeypatch,
                             plain=False)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= _tolerance(jnp.float32, want)
    parent, _ = _attend_a_chunk(layer, params, poisoned, x, monkeypatch,
                                plain=True)
    assert np.isnan(parent).any()


def test_the_block_rule_on_the_host_is_the_programs():
    cfg = FAMILY.model_config(SZ).serving_layout(16384, chunk=512)
    cfg = dataclasses.replace(cfg, window=4096)
    assert (afmoe.key_block(16384), afmoe.key_block(cfg.ring_len)) \
        == (512, 512)
    assert afmoe.key_block(12) == 12 and afmoe.key_block(1030) == 206
    for written in (1, 512, 513, 4608, 4609, 16384):
        traced = jax.jit(lambda w: (afmoe.live_blocks(w, 16384),
                                    afmoe.live_blocks(w, cfg.ring_len)))(
            jnp.int32(written))
        by_hand = (-(-written // 512), min(-(-written // 512), 9))
        assert tuple(int(n) for n in traced) == by_hand == (
            afmoe.live_blocks(written, 16384),
            afmoe.live_blocks(written, cfg.ring_len))
    # 4 window layers and 1 full: a chunk at 1,024 reads 3 blocks of each
    assert cfg.chunk_streamed_positions(1024, 512) == (
        ("window", 4 * 3 * 512), ("full", 3 * 512))
    assert cfg.chunk_streamed_positions(8192, 512) == (
        ("window", 4 * 4608), ("full", 17 * 512))
    # a single-token step reads every row, as ``streamed_positions`` says
    assert cfg.chunk_streamed_positions(1024, 1) \
        == cfg.streamed_positions((1024,))


# ------------------------------------------------------------------ #
# (c) the shares add up to the uncut layer
# ------------------------------------------------------------------ #
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    sz = dict(SZ)
    params = _params()
    moe = params["layer_1"]["moe"]
    m = jax.random.normal(jax.random.PRNGKey(5), (24, sz["hidden_size"]))
    whole = REF.swiglu(m, moe["shared"], REF.mm_highest) \
        + REF.routed_part(m, moe, sz, REF.mm_highest)
    cfg = FAMILY.model_config(sz)
    held_of = lambda part: dict(moe, **{k: moe[k][part] for k in
                                        ("w1", "w2", "w3")})
    shared = afmoe.SwiGLU(cfg, sz["moe_intermediate_size"]).apply(
        {"params": moe["shared"]}, m)
    total = shared      # what every share computes alike, counted once
    for share in range(4):
        part = slice(4 * share, 4 * share + 4)
        layer = afmoe.ExpertLayer(dataclasses.replace(
            cfg, experts_held=(4 * share, 4)))
        routed = layer.apply({"params": held_of(part)}, m[None])[0] - shared
        total = total + routed
        # and the program's share is the reference's share
        ref_part = REF.routed_part(
            m, held_of(part), dict(sz, experts_held_from=4 * share,
                                   num_experts=4), REF.mm_highest)
        assert np.abs(np.asarray(routed - ref_part)).max() < 1e-5
    assert np.abs(np.asarray(total - whole)).max() < 1e-5 * float(
        np.abs(whole).max() + 1)


def test_an_expert_no_token_chose_is_never_read_even_under_vmap():
    """The decode step maps the model over slots; the expert loop takes
    the slots' tokens together and runs over the experts they hit.  An
    expert nobody chose holds NaNs here: one multiplication by its zero
    weight would show."""
    rng = np.random.default_rng(4)
    held, d, f, slots = 6, 16, 8, 5
    w1, w3 = (jnp.asarray(rng.normal(size=(held, d, f)), jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rng.normal(size=(held, f, d)), jnp.float32)
    combine = np.zeros((slots, 1, held), np.float32)
    for s, e in enumerate([0, 2, 2, 5, 0]):
        combine[s, 0, e] = 0.5 + 0.1 * s
    m = jnp.asarray(rng.normal(size=(slots, 1, d)), jnp.float32)
    dead = np.array([1, 3, 4])
    w1, w3, w2 = (w.at[dead].set(jnp.nan) for w in (w1, w3, w2))
    got = jax.jit(jax.vmap(
        lambda x, c: afmoe.held_experts(x, c, w1, w3, w2, 1)))(
            m, jnp.asarray(combine))
    assert got.shape == (slots, 1, d) and bool(jnp.isfinite(got).all())
    for s, e in enumerate([0, 2, 2, 5, 0]):
        x = np.asarray(m[s, 0], np.float64)
        gate, up = x @ np.asarray(w1[e]), x @ np.asarray(w3[e])
        want = (gate / (1 + np.exp(-gate)) * up * combine[s, 0, e]) \
            @ np.asarray(w2[e])
        np.testing.assert_allclose(np.asarray(got[s, 0]), want, rtol=2e-4,
                                   atol=2e-4)


@pytest.mark.parametrize("call", ["decode", "prefill"])
def test_padding_chooses_no_expert_and_none_is_read_for_it(call):
    """A slot that does not decode, and a chunk's padded tail, still go
    through the model; ``live`` keeps their tokens out of the expert
    loop.  Every held expert that no LIVE token chose holds NaNs here:
    reading one for a padding token would show in the live rows too
    (the loop applies an expert to all of the call's tokens)."""
    cfg = FAMILY.model_config(SZ).serving_layout(64, chunk=4)
    params = _params()
    one = cfg.init_cache(1, 64)

    def last_choice(tokens):
        """The experts the last of ``tokens`` chose, a layer."""
        _, cache = jax.jit(cfg.apply_cached)(params, one,
                                             jnp.asarray(tokens)[None])
        return {name: set(np.asarray(
            layer["moe"]["stat_experts"]).ravel().tolist())
            for name, layer in cache.items() if "moe" in layer}

    if call == "decode":    # three slots, as the engine maps them
        toks, live = jnp.asarray([5, 9, 77]), [True, False, False]
        pool = jax.tree.map(lambda leaf: jnp.stack([leaf] * 3), one)

        def run(p, mask):
            return jax.vmap(lambda c, t, a: cfg.apply_cached(
                p, c, t[None, None],
                live=a[None, None] if mask else None)[0])(
                    pool, toks, jnp.asarray(live))[0]

        read = [last_choice([5])]
    else:                   # a chunk of four, two of them the prompt's
        chunk = jnp.asarray([[5, 9, 77, 3]])
        live = jnp.asarray([[True, True, False, False]])

        def run(p, mask):
            return cfg.apply_cached(p, one, chunk, all_logits=True,
                                    live=live if mask else None)[0][0, :2]

        read = [last_choice([5]), last_choice([5, 9])]
    poisoned = dict(params)
    for name in read[0]:
        keep = sorted(set().union(*(r[name] for r in read)))
        dead = np.setdiff1d(np.arange(SZ["num_experts"]), keep)
        assert dead.size
        poisoned[name] = dict(params[name], moe=dict(
            params[name]["moe"], **{k: params[name]["moe"][k].at[dead].set(
                jnp.nan) for k in ("w1", "w2", "w3")}))
    run = jax.jit(run, static_argnums=1)    # one program, as the engine's
    clean, dirty = run(params, True), run(poisoned, True)
    assert bool(jnp.isfinite(dirty).all())
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))
    # and the test can tell: unmasked, the padding's experts are read
    assert not bool(jnp.isfinite(run(poisoned, False)).all())


# ------------------------------------------------------------------ #
# (c2) the expert loop runs over tiles of the rows that chose an expert
# ------------------------------------------------------------------ #
TILE = afmoe.EXPERT_TILE


def _routing(case, rng):
    """``(combine [..., N, held], slots, top_k)``: the held weights of a
    call's rows, as the expert layer makes them; ``slots`` where the
    engine maps the call over them; the most experts a row chose.
    Nobody's choice is expert 1."""
    held = 8
    weight = lambda *shape: rng.uniform(0.2, 1.5, shape)

    def rows(n, top_k=2, of=3 * held):
        # each row chooses top_k of ``of`` experts; the first ``held``
        # are held
        c = np.zeros((n, held), np.float32)
        for r in range(n):
            for e in rng.choice(of, top_k, replace=False):
                if e < held and e != 1:
                    c[r, e] = weight()
        return c

    if case == "one_row":
        c = rows(1)
        c[0, 3] = 0.7       # at least one held assignment
        return c, None, 2
    if case == "slots_24_vmap":
        return rows(24)[:, None], 24, 2
    if case == "chunk_512_padded_tail":
        c = rows(512, top_k=4, of=2 * held)
        c[389:] = 0.0       # the chunk's tail is padding
        return c, None, 4
    if case == "skewed_512":
        # every row chose expert 2 (four tiles of it: nothing is
        # dropped), a third of them expert 5 as well (more than one
        # tile, the last one part full), nobody any other
        c = np.zeros((512, held), np.float32)
        c[:, 2] = weight(512)
        c[::3, 5] = weight(171)
        return c, None, 2
    if case == "sparse_512":
        # the newest cell's shape of the problem: every held expert but
        # the dead one is hit by 1-3 of 512 rows, so every tile is part
        # full and its tail is the next expert's to overwrite; the hit
        # rows are few, so that some hold several experts (4 at most)
        c = np.zeros((512, 16), np.float32)
        pool = rng.choice(512, 12, replace=False)
        for e in set(range(16)) - {1}:
            free = pool[(c[pool] != 0).sum(1) < 4]
            c[rng.choice(free, rng.integers(1, 4), replace=False), e] = \
                weight()
        return c, None, 4
    if case == "tile_edges_512":
        # a run that ends on a tile's edge and one that ends one past
        # it, each followed by a hit expert
        c = np.zeros((512, held), np.float32)
        c[rng.choice(512, TILE, replace=False), 0] = weight(TILE)
        c[rng.choice(512, TILE + 1, replace=False), 2] = weight(TILE + 1)
        c[rng.choice(512, 7, replace=False), 3] = weight(7)
        return c, None, 3
    assert case == "full_512_last_tile_part_full"
    # every row chose one expert, so the packed order is as long as its
    # bound, and the last hit expert's part-full tile is the last turn:
    # its tail lies past the last assignment
    c = np.zeros((512, held), np.float32)
    of = np.repeat([0, 2, 5, 7], [200, 130, 100, 82])
    c[np.arange(512), rng.permutation(of)] = weight(512)
    return c, None, 1


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["one_row", "slots_24_vmap",
                                  "chunk_512_padded_tail", "skewed_512",
                                  "sparse_512", "tile_edges_512",
                                  "full_512_last_tile_part_full"])
def test_the_tiled_sum_is_the_dense_sum_and_takes_a_turn_a_tile(case, dtype):
    """``held_experts`` against ``sum_e combine[:, e] * expert_e(m)``
    in float64, over the same rounded operands; an expert nobody chose
    holds NaNs; the loop's turns, read from its count of the rows it
    computed (``experts_cost``), are ``sum_e ceil(count_e / TILE)``."""
    rng = np.random.default_rng(len(case))
    d, f = 32, 16
    dtype = jnp.dtype(dtype)
    combine, slots, top_k = _routing(case, rng)
    held = combine.shape[-1]
    flat = combine.reshape(-1, held)
    n = flat.shape[0]
    m = jnp.asarray(rng.normal(size=combine.shape[:-1] + (d,)), dtype)
    w1, w3 = (jnp.asarray(rng.normal(size=(held, d, f)) / np.sqrt(d),
                          jnp.float32) for _ in range(2))
    w2 = jnp.asarray(rng.normal(size=(held, f, d)) / np.sqrt(f), jnp.float32)
    count = (flat != 0).sum(0)
    dead = np.flatnonzero(count == 0)
    assert dead.size
    poisoned = [w.at[dead].set(jnp.nan) for w in (w1, w3, w2)]
    call = lambda x, c: (afmoe.held_experts(x, c, *poisoned, top_k),
                         afmoe.experts_cost(c))
    got, stat = jax.jit(jax.vmap(call) if slots else call)(
        m, jnp.asarray(combine))
    assert got.dtype == jnp.float32 and got.shape == m.shape

    x = np.asarray(m.astype(jnp.float32), np.float64).reshape(n, d)
    rounded = lambda w: np.asarray(w.astype(dtype).astype(jnp.float32),
                                   np.float64)
    want = np.zeros((n, d))
    for e in np.flatnonzero(count):
        gate, up = x @ rounded(w1[e]), x @ rounded(w3[e])
        want += (gate / (1 + np.exp(-gate)) * up * flat[:, e:e + 1]) \
            @ rounded(w2[e])
    # float32: the order of the sums; bfloat16: the activation is
    # rounded once before the last product
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert np.abs(np.asarray(got, np.float64).reshape(n, d) - want).max() \
        < tol * np.abs(want).max()
    assert not np.asarray(got).reshape(n, d)[(flat == 0).all(1)].any()

    # the slots' counts add up to the joint call's
    rows, assigned = np.asarray(stat).reshape(-1, 2).sum(0)
    assert assigned == (flat != 0).sum()
    if n <= TILE:       # a hit expert's tile is the call
        assert rows == n * (count > 0).sum()
    else:
        assert rows == TILE * np.ceil(count / TILE).sum()
        if case == "skewed_512":
            assert rows == TILE * (4 + 2)


def _equations(jaxpr, in_loop=False):
    """``(equation, whether a loop holds it)`` of a jaxpr and of every
    jaxpr its equations hold."""
    from bluefog_tpu.analysis.jaxpr_check import _sub_jaxprs

    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        for sub in _sub_jaxprs(eqn):
            yield from _equations(
                getattr(sub, "jaxpr", sub),
                in_loop or eqn.primitive.name == "while")


def _held_experts_equations(rows, vmapped):
    held, d, f = 16, 32, 16     # every width under a tile's rows
    weights = [jnp.zeros(shape) for shape in
               ((held, d, f), (held, d, f), (held, f, d))]
    call = lambda m, c: afmoe.held_experts(m, c, *weights, 4)
    shape = (rows, 1) if vmapped else (rows,)
    jaxpr = jax.make_jaxpr(jax.vmap(call) if vmapped else call)(
        jnp.zeros(shape + (d,)), jnp.zeros(shape + (held,)))
    return list(_equations(jaxpr.jaxpr))


def test_a_turn_of_the_tiled_loop_moves_a_tile_and_nothing_of_the_call():
    """What a prefill chunk's expert loop is about, held where a CPU can
    hold it: inside the loop's body nothing as tall as the call (512
    rows) or as long as the packed order is computed on; such an array
    is only ever cut (a tile in) or written into (a tile out)."""
    moves = {"dynamic_slice", "dynamic_update_slice", "gather"}
    body = [eqn for eqn, in_loop in _held_experts_equations(512, False)
            if in_loop]
    assert sum(eqn.primitive.name == "dot_general" for eqn in body) == 3
    assert any(eqn.primitive.name == "dynamic_update_slice" for eqn in body)
    for eqn in body:
        if eqn.primitive.name in moves:
            continue
        for var in list(eqn.invars) + list(eqn.outvars):
            assert max(var.aval.shape, default=0) <= TILE, (
                f"{eqn.primitive.name} on {var.aval.shape} inside a turn")


def test_the_decode_steps_loop_packs_nothing():
    """The mirror: a call that fits one tile (24 slots under ``vmap``)
    keeps its loop over the hit experts and has no sort, no gather and
    no packed buffer."""
    names = {eqn.primitive.name
             for eqn, _ in _held_experts_equations(24, True)}
    assert "while" in names and "dot_general" in names
    assert not names & {"sort", "gather", "pad", "dynamic_update_slice"}


# ------------------------------------------------------------------ #
# (d) the router
# ------------------------------------------------------------------ #
def test_the_bias_selects_and_does_not_weigh():
    scores = jnp.asarray([[0.9, 0.8, 0.7, 0.1, 0.2, 0.3]], jnp.float32)
    bias = jnp.asarray([0.0, 0.0, -1.0, 1.0, 0.0, 0.0], jnp.float32)
    chosen, weights = afmoe.route(scores, bias, 3, 2.0)
    # expert 3 gets in on its bias, expert 2 is pushed out by its own
    assert sorted(np.asarray(chosen)[0].tolist()) == [0, 1, 3]
    picked = {int(e): float(w) for e, w in zip(np.asarray(chosen)[0],
                                               np.asarray(weights)[0])}
    total = 0.9 + 0.8 + 0.1
    for e, s in ((0, 0.9), (1, 0.8), (3, 0.1)):
        assert picked[e] == pytest.approx(2.0 * s / total, rel=1e-6)
    assert weights.dtype == jnp.float32


def test_the_router_stays_float32_in_a_bfloat16_model():
    cfg = dataclasses.replace(FAMILY.model_config(SZ), dtype=jnp.bfloat16)
    params = _params()
    jaxpr = jax.make_jaxpr(lambda p, x: afmoe.ExpertLayer(cfg).apply(
        {"params": p}, x))(params["layer_1"]["moe"],
                           jnp.zeros((1, 3, 64), jnp.bfloat16))
    sigmoids = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "logistic"]
    assert sigmoids and all(e.outvars[0].aval.dtype == jnp.float32
                            and e.outvars[0].aval.shape[-1] == 16
                            for e in sigmoids[:1])


def test_the_seeded_bias_changes_some_choices():
    params = _params()
    moe = params["layer_2"]["moe"]
    m = jax.random.normal(jax.random.PRNGKey(9), (256, 64))
    with_bias, _ = REF.route(m, moe, SZ, REF.mm_highest)
    without, _ = REF.route(m, dict(moe, router_bias=jnp.zeros(16)), SZ,
                           REF.mm_highest)
    changed = np.mean(np.sort(np.asarray(with_bias), -1)
                      != np.sort(np.asarray(without), -1))
    assert 0.01 < changed < 0.9


# ------------------------------------------------------------------ #
# (e) the pool's two kinds of cache
# ------------------------------------------------------------------ #
def test_the_pool_reports_window_and_full_bytes_of_its_leaves():
    cfg = FAMILY.model_config(SZ)
    pool = SlotPool(cfg, capacity=3, max_len=64, chunk=4)
    per_position = 2 * 2 * 16 * 4          # k and v, 2 heads of 16, f32
    assert pool.cache_bytes() == {
        "window": 3 * 4 * (WINDOW + 4) * per_position,
        "full": 3 * 1 * 64 * per_position}
    by_hand = {"window": 0, "full": 0}
    for path, leaf in jax.tree_util.tree_flatten_with_path(pool.cache)[0]:
        name = path[-1].key
        if name.startswith("window_"):
            assert leaf.shape == (3, 1, 2, WINDOW + 4, 16)
            by_hand["window"] += leaf.nbytes
        elif name.startswith("cached_"):
            assert leaf.shape == (3, 1, 2, 64, 16)
            by_hand["full"] += leaf.nbytes
    assert by_hand == pool.cache_bytes()
    # an expert layer's last choices and what its expert loop has cost
    from bluefog_tpu.serving.kv_pool import pack_stats

    assert pool.stat_rows == 4 * (4 + 2)
    packed = np.arange(pool.stat_rows * 3, dtype=np.int32).reshape(-1, 3)
    assert pack_stats(pool.cache).shape == packed.shape
    stats = pool.unpack_stats(packed)
    assert pool.has_stats and {k: [a.shape for a in v]
                               for k, v in stats.items()} \
        == {"stat_experts": [(3, 1, 4)] * 4,
            "stat_expert_rows": [(3, 2)] * 4}
    # a row a number a slot holds, leaf by leaf as the tree flattens
    # them: a layer's rows, then its choices
    np.testing.assert_array_equal(stats["stat_expert_rows"][0],
                                  packed[:2].T)
    np.testing.assert_array_equal(stats["stat_experts"][0],
                                  packed[2:6].T.reshape(3, 1, 4))
    # and pack_stats lays the device's leaves out the same way
    cache = jax.tree_util.tree_map_with_path(
        lambda path, leaf: (jnp.arange(leaf.size, dtype=leaf.dtype)
                            .reshape(leaf.shape)
                            if path[-1].key.startswith("stat_") else leaf),
        pool.cache)
    back = pool.unpack_stats(np.asarray(pack_stats(cache)))
    want = [leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(cache)[0]
            if path[-1].key == "stat_experts"]
    for got, leaf in zip(back["stat_experts"], want):
        np.testing.assert_array_equal(got, leaf)
    assert cfg.cache_kinds() == {"window": (4, WINDOW), "full": (1, None)}


def test_the_counters_of_a_served_run():
    from bluefog_tpu.observe.registry import MetricsRegistry

    reg = MetricsRegistry()
    params = _params()
    rng = np.random.default_rng(2)
    sz = dict(SZ, experts_held_from=4, num_experts=8)
    part = lambda t: {k: (v[4:12] if k in ("w1", "w2", "w3") else v)
                      for k, v in t.items()}
    params = {k: (dict(v, moe=part(v["moe"])) if "moe" in v else v)
              for k, v in params.items()}
    eng, reqs = served_model.serve(
        FAMILY.model_config(sz), params,
        [rng.integers(0, 128, n) for n in (20, 3)], [5, 5], max_len=MAX_LEN,
        registry=reg)
    value = lambda name, **labels: reg.counter(name, "", **labels).value
    steps = value("bf_serving_decode_steps_total")
    slots = value("bf_serving_decode_slots_total")
    held = value("bf_moe_assignments_total", held="true")
    absent = value("bf_moe_assignments_total", held="false")
    assert held + absent == slots * 4 * 4      # 4 expert layers, top 4
    assert 0 < held < held + absent
    assert value("bf_moe_layer_steps_total") == steps * 4
    assert 0 < value("bf_moe_experts_hit_total") <= held
    # 5 decode steps a request: the query at position n - 1 + i sees
    # n + i positions in the full layer, at most WINDOW in each of 4
    full = sum(n + i for n in (20, 3) for i in range(5))
    window = 4 * sum(min(n + i, WINDOW) for n in (20, 3) for i in range(5))
    assert value("bf_serving_attended_positions_total", kind="full") == full
    assert value("bf_serving_attended_positions_total",
                 kind="window") == window
    assert reg.gauge("bf_serving_cache_bytes", "", kind="window").value \
        == eng.pool.cache_bytes()["window"]


def test_the_counters_of_a_run_ahead_are_the_synchronous_count():
    """A model with ``stat_*`` leaves, served one program ahead: the
    leaves of program k are read (from the program's own packed output)
    after program k+1 took the pool by donation, and every counter
    (the expert choices and rows, the attended and streamed positions,
    the decode slots) ends where it ends when every program is read
    before the next is dispatched."""
    from bluefog_tpu.observe.registry import MetricsRegistry

    params = {"params": _params()}

    def counters(sync):
        reg = MetricsRegistry()
        rng = np.random.default_rng(8)
        # a slot each: which program a request joins then hangs on the
        # prefill before it alone, and both orders dispatch the same
        # programs (a request that waits for a slot is admitted when
        # the host READS the retirement, a program later run ahead)
        eng = ServingEngine(params, FAMILY.model_config(SZ), capacity=3,
                            max_len=64, prefill_chunk=4, registry=reg)
        reqs = [Request(rng.integers(0, 128, n), new)
                for n, new in ((20, 5), (3, 7), (9, 4))]
        for r in reqs[:2]:
            eng.submit(r)
        k = 0
        while eng.busy:
            if k == 3:
                eng.submit(reqs[2])
            eng.step()
            if sync:
                eng.collect()
            k += 1
        assert all(r.state == "completed" for r in reqs)
        ahead = eng.metrics.n_decode_ahead
        assert (ahead == 0) if sync else (ahead > 0)
        out = {}
        for name, rows in reg.snapshot().items():
            if name.endswith("_total") and name not in (
                    "bf_serving_decode_ahead_total",
                    "bf_serving_steps_total"):
                for row in rows:
                    out[name, tuple(sorted(row["labels"].items()))] = \
                        row["value"]
        return [list(r.tokens) for r in reqs], out

    (sync_tokens, want), (tokens, got) = counters(True), counters(False)
    assert tokens == sync_tokens
    assert {name for name, _ in want} >= {
        "bf_moe_assignments_total", "bf_moe_experts_hit_total",
        "bf_moe_expert_rows_total", "bf_moe_expert_assignments_total",
        "bf_serving_attended_positions_total",
        "bf_serving_streamed_positions_total"}
    assert got == want


def test_the_rows_a_chunk_reads_are_counted_by_kind(monkeypatch):
    from bluefog_tpu.observe.registry import MetricsRegistry

    monkeypatch.setattr(afmoe, "KEY_BLOCK", 4)
    name = "bf_serving_chunk_streamed_positions_total"
    reg = MetricsRegistry()
    rng = np.random.default_rng(6)
    # 60 rows: a leaf no other test compiles a program for
    eng, reqs = _serve(_params(), [rng.integers(0, 128, n) for n in (30, 3)],
                       [3, 3], max_len=60, registry=reg)
    for r in reqs:
        served_model.assert_served_is_the_references_greedy(
            REF, SZ, eng._params, r, TOL)
    # chunks of 4 cover prompt[:-1]: they start at 0, 4, ...; a chunk
    # that ends at e reads ceil(e / 4) blocks of 4 rows, a ring (12
    # rows) at most its 3; 1 full layer, 4 window layers
    ends = [start + 4 for n in (30, 3) for start in range(0, n - 1, 4)]
    assert reg.counter("bf_serving_prefill_chunks_total", "").value \
        == len(ends) == 9
    assert reg.counter(name, "", kind="full").value \
        == sum(4 * (e // 4) for e in ends)
    assert reg.counter(name, "", kind="window").value \
        == 4 * sum(4 * min(e // 4, 3) for e in ends)
    # a model that declares nothing counts nothing
    reg = MetricsRegistry()
    cfg, variables = served_model.tiny_llama()
    eng = ServingEngine(variables, cfg, capacity=2, max_len=32,
                        prefill_chunk=4, registry=reg)
    eng.submit(Request(rng.integers(0, 128, 11), 2))
    eng.run()
    assert reg.counter("bf_serving_prefill_chunks_total", "").value == 3
    assert name not in reg.snapshot()


def test_the_expert_rows_counters_of_a_served_run():
    """A chunk longer than a tile goes through the tiled loop, a decode
    step (two slots) through the one-tile case; with every expert held
    each live token makes ``top_k`` assignments a layer, so the
    assignments are known exactly, and the rows are whole tiles (or
    whole calls) that hold them."""
    from bluefog_tpu.observe.registry import MetricsRegistry

    reg = MetricsRegistry()
    rng = np.random.default_rng(6)
    chunk = TILE + 64
    lengths, new = (chunk + 41, 3), 4
    _serve(_params(), [rng.integers(0, 128, n) for n in lengths], [new, new],
           max_len=2 * chunk, prefill_chunk=chunk, registry=reg)
    value = lambda name, **labels: reg.counter(name, "", **labels).value
    rows = value("bf_moe_expert_rows_total")
    assigned = value("bf_moe_expert_assignments_total")
    # the last prompt token and the answer's tokens but the last decode
    slot_steps = value("bf_serving_decode_slots_total")
    assert slot_steps == len(lengths) * new
    live = sum(n - 1 for n in lengths) + slot_steps
    assert assigned == 4 * SZ["num_experts_per_tok"] * live
    assert assigned == 4 * 4 * sum(n - 1 for n in lengths) \
        + value("bf_moe_assignments_total", held="true")
    # three chunk calls, each of at most 4 x chunk / TILE full tiles
    # and one part-full tile an expert, a layer; a decode step applies
    # at most 16 experts to its 2 rows
    steps = value("bf_serving_decode_steps_total")
    assert assigned < rows <= 4 * (
        3 * TILE * (4 * chunk // TILE + 16) + steps * 16 * 2)


def test_expert_rows_are_counted_by_what_the_totals_grew():
    from bluefog_tpu.observe.registry import MetricsRegistry
    from bluefog_tpu.serving.metrics import ServingMetrics

    reg = MetricsRegistry()
    metrics = ServingMetrics(registry=reg)
    value = lambda name: reg.counter(name, "").value
    read = lambda: (value("bf_moe_expert_rows_total"),
                    value("bf_moe_expert_assignments_total"))
    layer = lambda *slots: np.asarray(slots, np.int32)
    metrics.on_expert_rows([layer((256, 20), (0, 0)),
                            layer((128, 9), (3, 1))])
    assert read() == (387, 30)
    metrics.on_expert_rows([layer((256, 20), (128, 7)),
                            layer((384, 30), (3, 1))])
    assert read() == (387 + 128 + 256, 30 + 7 + 21)
    # a slot freed and zeroed, then written again: it counts from zero
    metrics.on_expert_rows([layer((128, 5), (128, 7)),
                            layer((384, 30), (3, 1))])
    assert read() == (771 + 128, 58 + 5)
    metrics.on_expert_rows([])
    assert read() == (899, 63)


def test_with_no_registry_the_step_fetches_no_stat_leaf(monkeypatch):
    """Observe off: the decode step reads its tokens and nothing else
    off the device, and counts no lengths."""
    monkeypatch.setenv("BLUEFOG_OBSERVE", "0")
    eng = ServingEngine({"params": _params()}, FAMILY.model_config(SZ),
                        capacity=2, max_len=64, prefill_chunk=4)
    assert eng.pool.has_stats and not eng.metrics.publishing

    def boom(*_):
        raise AssertionError("counted with nobody to count for")

    monkeypatch.setattr(eng.pool, "unpack_stats", boom)
    monkeypatch.setattr("bluefog_tpu.serving.protocol.attended_positions",
                        boom)
    req = eng.submit(Request(np.arange(9), 4))
    eng.run()
    assert req.state == "completed" and len(req.tokens) == 4


def test_a_prefix_cache_refuses_a_ring_leaf_at_construction():
    with pytest.raises(ValueError, match="ring"):
        ServingEngine({"params": _params()}, FAMILY.model_config(SZ),
                      capacity=2, max_len=64, prefill_chunk=4,
                      prefix_cache=PrefixCache(4, 1 << 20))
