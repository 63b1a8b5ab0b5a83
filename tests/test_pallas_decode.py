"""Fused Pallas decode-attention kernel (parallel/pallas_decode.py):
exactness against the XLA cached-attention lowerings, and end-to-end
token parity through llama_generate.

The reference has no decode path at all (generation is a new capability,
docs/parity.md); the exactness bar here is the repo's own XLA decode
step.  CPU runs use interpret mode (selected automatically)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu import models
from bluefog_tpu.models.llama import (_amax_quantize, _cached_attention)
from bluefog_tpu.models import llama_generate
from bluefog_tpu.parallel import pallas_decode
from bluefog_tpu.parallel.pallas_decode import (decode_attention,
                                                decode_attention_int8,
                                                streamed_positions)
from bluefog_tpu.serving import Request, ServingEngine


def _rand_cache(b, n_kv, s, d, seed=0):
    rng = np.random.RandomState(seed)
    k = jnp.asarray(rng.randn(b, n_kv, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, n_kv, s, d), jnp.float32)
    return k, v


@pytest.mark.parametrize("idx", [0, 5, 127])
@pytest.mark.parametrize("rep", [1, 4])
def test_decode_attention_matches_xla(idx, rep):
    b, n_kv, s, d = 2, 3, 128, 16
    n_q = n_kv * rep
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, 1, n_q, d), jnp.float32)
    k, v = _rand_cache(b, n_kv, s, d)
    # zero the unwritten tail like a real cache (the kernel must mask it)
    mask = (np.arange(s) <= idx)[None, None, :, None]
    k = k * mask
    v = v * mask
    ref = _cached_attention(q, k, v, jnp.int32(idx))
    out = decode_attention(q, k, v, jnp.int32(idx))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_decode_attention_int8_matches_dequant_reference():
    """The int8 kernel == dequantize-the-cache + float attention (its
    scales commute exactly; probabilities are never re-quantized)."""
    b, n_kv, rep, s, d = 2, 2, 4, 256, 32
    idx = 200
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(b, 1, n_kv * rep, d), jnp.float32)
    k, v = _rand_cache(b, n_kv, s, d, seed=3)
    mask = (np.arange(s) <= idx)[None, None, :, None]
    k = k * mask
    v = v * mask
    kq, ks = _amax_quantize(k)
    vq, vs = _amax_quantize(v)
    ks, vs = ks[..., 0], vs[..., 0]
    k_deq = kq.astype(jnp.float32) * ks[..., None]
    v_deq = vq.astype(jnp.float32) * vs[..., None]
    ref = _cached_attention(q, k_deq, v_deq, jnp.int32(idx))
    out = decode_attention_int8(q, kq, ks, vq, vs, jnp.int32(idx))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_vmap_over_rows_folds_into_the_kernel_batch(quantized):
    """The serving engine maps the decode step over its cache slots,
    every slot at its OWN position: the kernel's vmap rule folds the
    mapped axis into its batch grid axis (one launch, per-row
    positions) and must equal a Python loop of single-slot calls."""
    slots, n_kv, rep, s, d = 3, 2, 2, 64, 16
    positions = jnp.asarray([0, 17, 63], jnp.int32)
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(slots, 1, 1, n_kv * rep, d), jnp.float32)
    k, v = _rand_cache(slots, n_kv, s, d, seed=5)
    cache = (k[:, None], v[:, None])          # [slots, B=1, KV, S, D]
    if quantized:
        (kq, ks), (vq, vs) = _amax_quantize(cache[0]), _amax_quantize(
            cache[1])
        operands = (kq, ks[..., 0], vq, vs[..., 0])
        kernel = decode_attention_int8
    else:
        operands, kernel = cache, decode_attention

    def one(q, idx, *ops):
        return kernel(q, *ops, idx)

    mapped = jax.jit(jax.vmap(one))(q, positions, *operands)
    jaxpr = str(jax.make_jaxpr(jax.vmap(one))(q, positions, *operands))
    assert jaxpr.count("pallas_call") == 1
    for i in range(slots):
        alone = one(q[i], positions[i], *[op[i] for op in operands])
        np.testing.assert_array_equal(np.asarray(mapped[i]),
                                      np.asarray(alone))


def test_decode_attention_blocked_softmax_is_stable():
    """Online softmax across S blocks == one-shot softmax (block_s
    smaller than S exercises the flash recurrence)."""
    b, n_kv, rep, s, d = 1, 2, 2, 512, 16
    idx = 511
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(b, 1, n_kv * rep, d) * 4.0, jnp.float32)
    k, v = _rand_cache(b, n_kv, s, d, seed=5)
    ref = _cached_attention(q, k, v, jnp.int32(idx))
    out = decode_attention(q, k, v, jnp.int32(idx), block_s=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("kv_quant,weight_quant,max_len", [
    ("none", "none", None), ("int8", "int8", None), ("none", "none", 32)],
    ids=["none-none", "int8-int8", "none-none-writes"])
def test_generate_pallas_decode_token_parity(kv_quant, weight_quant,
                                             max_len):
    """llama_generate with decode_attn='pallas' emits the same tokens as
    the XLA path: for the full-precision cache both compute identical
    f32 attention; for kv int8 + weight-only int8 the XLA path dequants
    the cache into float attention — the exact math the kernel fuses.
    At 32 positions (two 16-row tiles) the full-precision step writes
    its rows inside the kernel; at the default 19 XLA writes them."""
    assert pallas_decode.writable(32) and not pallas_decode.writable(19)
    cfg = models.LlamaConfig.tiny(dtype=jnp.float32)
    model = models.Llama(cfg)
    rng = np.random.RandomState(6)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 7)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 4), jnp.int32))
    if weight_quant != "none":
        from bluefog_tpu.models import quantize_llama_params
        variables = jax.jit(quantize_llama_params)(variables)
    kw = dict(kv_quant=kv_quant, weight_quant=weight_quant, max_len=max_len)
    # pin the reference to the XLA lowering: the default decode_attn=
    # "auto" resolves to pallas for short full-precision caches, which
    # would make this parity check compare pallas against itself
    ref = llama_generate(variables, cfg, prompt, 12, decode_attn="xla",
                         **kw)
    out = llama_generate(variables, cfg, prompt, 12, decode_attn="pallas",
                         **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_decode_attn_validation():
    with pytest.raises(ValueError):
        models.LlamaConfig.tiny(decode_attn="pallas")  # decode-only knob
    with pytest.raises(ValueError):
        models.LlamaConfig.tiny(decode=True, decode_attn="mosaic")


# ------------------------------------------------------------------ #
# the stream is bounded by each row's position (PR 27)
# ------------------------------------------------------------------ #
S, BLOCK = 64, 16
EDGES = [0, BLOCK - 1, BLOCK, S - 1]


def _operands(quantized, k, v, past):
    """``(kernel, clean cache operands, the same with NaN wherever
    ``past [B, S]`` says)``: NaN goes into the float scales of a
    quantized cache (int8 holds none).  A block that is fetched and only
    masked turns the output NaN through ``0 * NaN``."""
    spoil = lambda x, m: jnp.where(m, jnp.nan, x)
    if quantized:
        (kq, ks), (vq, vs) = _amax_quantize(k), _amax_quantize(v)
        ks, vs = ks[..., 0], vs[..., 0]
        return (decode_attention_int8, (kq, ks, vq, vs),
                (kq, spoil(ks, past[:, None]), vq, spoil(vs, past[:, None])))
    return (decode_attention, (k, v), (spoil(k, past[:, None, :, None]),
                                       spoil(v, past[:, None, :, None])))


def _past(positions, live=None):
    """[B, S]: the positions in blocks wholly past each row's own (all
    of a row that does not decode)."""
    past = (np.arange(S)[None] // BLOCK
            > np.asarray(positions)[:, None] // BLOCK)
    return past if live is None else past | ~np.asarray(live)[:, None]


def _kernel_and_reference(quantized, q, k, v, positions, poison=False):
    """The kernel's output over rows at ``positions`` (its cache
    poisoned past each row's position if asked) and the XLA lowering's
    over the clean cache."""
    positions = jnp.asarray(positions, jnp.int32)
    kernel, clean, dirty = _operands(quantized, k, v, _past(positions))
    if quantized:
        kq, ks, vq, vs = clean
        k = kq.astype(jnp.float32) * ks[..., None]
        v = vq.astype(jnp.float32) * vs[..., None]
    ref = jax.vmap(lambda q, k, v, i: _cached_attention(
        q[None], k[None], v[None], i)[0])(q, k, v, positions)
    out = kernel(q, *(dirty if poison else clean), positions, block_s=BLOCK)
    return np.asarray(out), np.asarray(ref)


def _rows(b, n_kv=2, rep=2, d=16, seed=7):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, 1, n_kv * rep, d), jnp.float32)
    return (q,) + _rand_cache(b, n_kv, S, d, seed=seed + 1)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("idx", EDGES)
def test_parity_at_the_block_edges(idx, quantized):
    out, ref = _kernel_and_reference(quantized, *_rows(2), [idx, idx])
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("idx", EDGES[:-1])
def test_blocks_past_the_position_are_not_read(idx, quantized):
    """Not merely masked: a NaN in any of them would reach the output
    through ``0 * NaN`` in the value product (the kernel before PR 27
    fetched every block and fails this)."""
    out, ref = _kernel_and_reference(quantized, *_rows(2), [idx, idx],
                                     poison=True)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_rows_at_different_positions_stream_their_own_blocks(quantized):
    """The engine's map over slots: one row at 0, one at a block edge,
    one that does not decode, one at the end, folded into one launch,
    each bounded by its own position."""
    positions = jnp.asarray([0, BLOCK, 40, S - 1], jnp.int32)
    live = jnp.asarray([True, True, False, True])
    q, k, v = _rows(4)
    kernel, clean, dirty = _operands(quantized, k, v,
                                     _past(positions, live))

    def one(q, idx, live, *ops):     # a slot of the pool: batch 1
        return kernel(q[None], *[op[None] for op in ops], idx,
                      live=live[None], block_s=BLOCK)[0]

    mapped = jax.jit(jax.vmap(one))(q, positions, live, *dirty)
    assert np.isfinite(np.asarray(mapped)).all()
    for i in range(4):
        alone = one(q[i], positions[i], live[i], *[op[i] for op in clean])
        np.testing.assert_allclose(np.asarray(mapped[i]), np.asarray(alone),
                                   atol=1e-6, rtol=1e-6)
    assert not np.asarray(mapped[2]).any()   # the dead row: zeros


@pytest.mark.parametrize("positions", [
    [0], [BLOCK - 1, BLOCK], [S - 1], [0, 5, 17, 40, 63], [S + 9],
    [-1, 20, -1, -1, 63, -1], [40, -1, -1, 3], [-1, -1], [-1]])
def test_streamed_positions_counts_the_blocks_the_index_map_names(
        positions):
    """``-1``: a row that does not decode.  The pipeline fetches a block
    when a grid step names another than the step before it did."""
    live = jnp.asarray([p >= 0 for p in positions])
    idx = jnp.clip(jnp.asarray(positions, jnp.int32), 0, S - 1)
    plan = np.asarray(pallas_decode._stream_plan(idx, live, BLOCK))
    named = [tuple(int(x) for x in pallas_decode._named_block(b, sj, plan))
             for b in range(len(positions)) for sj in range(S // BLOCK)]
    fetched = 1 + sum(a != b for a, b in zip(named, named[1:]))
    assert len(set(named)) == fetched       # no block is fetched twice
    assert streamed_positions(positions, S, block_s=BLOCK) \
        == fetched * BLOCK
    # the XLA lowering reads every row whole
    assert streamed_positions(positions, S, fused=False) \
        == len(positions) * S


def test_the_block_follows_the_cache_length():
    """No keyword chooses it: the largest divisor of the length up to
    the measured block; a length with no block of 8 rows is refused."""
    assert streamed_positions([0], 2048) == pallas_decode._BLOCK_S == 512
    assert streamed_positions([0], 48) == 48
    assert pallas_decode.tileable(2048) and pallas_decode.tileable(5)
    assert not pallas_decode.tileable(1031)        # a prime
    q, k, v = _rows(1)
    with pytest.raises(ValueError, match="no block divisor"):
        decode_attention(jnp.zeros((1, 1, 4, 16)),
                         jnp.zeros((1, 2, 1031, 16)),
                         jnp.zeros((1, 2, 1031, 16)), jnp.int32(3))


# ------------------------------------------------------------------ #
# the step that writes its own rows (PR 41): the stacked form's kernel
# (tests/test_looped.py) over a plain pair, a stack of one leaf
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("live", [
    [True, True, True, True], [True, False, True, False],
    [False, True, False, True], [False, False, False, False]],
    ids=["all", "even", "odd", "none"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_dense_writing_step_is_the_write_then_the_step(dtype, live):
    """``decode_attention(fresh=)`` over a plain ``[B, KV, S, D]`` pair:
    the output and the caches of the rows written first and the kernel
    after.  Rows at a block's last and first position (blocks of 32),
    at a 16-row tile's last and first; a row that does not decode
    writes nothing (its cache bit for bit, its output zeros); and the
    same under the engine's map over rows, each at its own position."""
    keys = jax.random.split(jax.random.PRNGKey(41), 5)
    k_all, v_all = (jax.random.normal(kk, (4, 2, S, 16)).astype(dtype)
                    for kk in keys[:2])
    q = jax.random.normal(keys[2], (4, 1, 4, 16)).astype(dtype)
    k_new, v_new = (jax.random.normal(kk, (4, 2, 16)).astype(dtype)
                    for kk in keys[3:])
    idx = jnp.asarray([31, 32, 15, 48], jnp.int32)
    live = jnp.asarray(live)

    def written(c, new):
        at = (jnp.arange(S)[None] == idx[:, None]) & live[:, None]
        return jnp.where(at[:, None, :, None], new[:, :, None, :], c)

    want_k, want_v = written(k_all, k_new), written(v_all, v_new)
    want = decode_attention(q, want_k, want_v, idx, live=live, block_s=32)
    assert np.all(np.asarray(want, np.float32)[~np.asarray(live)] == 0)

    def same(got, rows=lambda x: x):
        out, got_k, got_v = got
        assert out.dtype == q.dtype and got_k.dtype == got_v.dtype == dtype
        assert np.array_equal(np.asarray(rows(got_k)), np.asarray(want_k))
        assert np.array_equal(np.asarray(rows(got_v)), np.asarray(want_v))
        assert np.allclose(np.asarray(rows(out), np.float32),
                           np.asarray(want, np.float32), atol=1e-6)

    same(decode_attention(q, k_all, v_all, idx, live=live,
                          fresh=(k_new, v_new), block_s=32))
    step = jax.vmap(lambda qq, kk, vv, ii, ll, kn, vn: decode_attention(
        qq[None], kk[None], vv[None], ii, live=ll,
        fresh=(kn[None], vn[None]), block_s=32))
    operands = (q, k_all, v_all, idx, live, k_new, v_new)
    assert str(jax.make_jaxpr(step)(*operands)).count("pallas_call") == 1
    same(step(*operands), rows=lambda x: x[:, 0])


@pytest.mark.parametrize("s_len,block_s,leaf", [
    (24, None, None), (S, 8, None), (592, None, None), (24, None, 0)],
    ids=["one-block-of-24", "blocks-of-8", "blocks-of-296", "stacked"])
def test_fresh_rows_where_the_kernel_cannot_write_them_raise(
        s_len, block_s, leaf):
    """A block that is no whole number of 16-row tiles cannot hold the
    tile a writing call puts back: ``fresh=`` raises there, it is never
    dropped (``writable`` says so beforehand, which is what
    ``models/llama.py`` asks)."""
    # by its default blocks; a caller's ``block_s`` is checked at the call
    assert pallas_decode.writable(s_len) == (block_s is not None)
    assert pallas_decode.tileable(s_len)     # the reading kernel serves it
    q = jnp.zeros((2, 1, 4, 16))
    cache = jnp.zeros((2, 2, s_len, 16))
    if leaf is not None:
        cache = cache[:, None]
    new = jnp.zeros((2, 2, 16))
    assert decode_attention(q, cache, cache, jnp.int32(3), leaf=leaf,
                            block_s=block_s).shape == q.shape
    with pytest.raises(ValueError, match="cannot write"):
        decode_attention(q, cache, cache, jnp.int32(3), leaf=leaf,
                         fresh=(new, new), block_s=block_s)


# ------------------------------------------------------------------ #
# through the serving engine
# ------------------------------------------------------------------ #
ENGINE = dict(capacity=5, max_len=1024, prefill_chunk=32)


def _serve(decode_attn, monkeypatch):
    """Four greedy requests through a five-slot engine (two blocks of
    512 positions a slot): two cross the first block's edge while they
    decode, one stays inside it, one arrives late and prefills (seven
    chunks) while the others decode, and the fifth slot stays free.
    Returns the tokens, the registry and every ``(positions, count)``
    the kernel's own function was asked."""
    from bluefog_tpu.observe import MetricsRegistry

    asked = []

    def recorded(positions, s_len, **kw):
        asked.append((list(positions),
                      streamed_positions(positions, s_len, **kw)))
        return asked[-1][1]

    monkeypatch.setattr(pallas_decode, "streamed_positions", recorded)
    cfg = models.LlamaConfig.tiny(dtype=jnp.float32, max_seq_len=1024)
    variables = models.Llama(cfg).init(jax.random.PRNGKey(1),
                                       jnp.zeros((2, 4), jnp.int32))
    reg = MetricsRegistry()
    eng = ServingEngine(variables, cfg, decode_attn=decode_attn,
                        registry=reg, **ENGINE)
    assert eng.cfg.decode_attn == decode_attn
    rs = np.random.RandomState(11)
    reqs = [Request(rs.randint(0, 256, (n,)).astype(np.int32), new)
            for n, new in ((506, 12), (5, 40), (511, 10), (200, 4))]
    for r in reqs[:3]:
        eng.submit(r)
    for _ in range(36):      # 16 + 1 + 16 chunk steps, then decoding
        eng.step()
    eng.submit(reqs[3])
    eng.run()
    assert all(r.state == "completed" for r in reqs)
    return [list(r.tokens) for r in reqs], reg, asked


def test_the_engine_serves_the_same_tokens_and_counts_what_it_streams(
        monkeypatch):
    value = lambda reg, name, **labels: reg.counter(name, "",
                                                    **labels).value
    cap, max_len = ENGINE["capacity"], ENGINE["max_len"]
    ref, reg_x, asked_x = _serve("xla", monkeypatch)
    out, reg_p, asked_p = _serve("pallas", monkeypatch)
    assert out == ref
    steps = value(reg_x, "bf_serving_decode_steps_total")
    assert steps == value(reg_p, "bf_serving_decode_steps_total") \
        == len(asked_p)
    layers = 2
    assert value(reg_x, "bf_serving_streamed_positions_total",
                 kind="full") == steps * cap * max_len * layers
    assert value(reg_p, "bf_serving_streamed_positions_total",
                 kind="full") == layers * sum(n for _, n in asked_p)
    # the same lengths on both sides; every slot of the pool is a row
    assert [p for p, _ in asked_p] == [p for p, _ in asked_x]
    assert all(len(p) == cap for p, _ in asked_p)
    block = pallas_decode._fit_block(max_len, pallas_decode._BLOCK_S)
    # a slot that does not decode (free, or still prefilling) is no
    # row of the stream: the most ever asked is the four requests'
    assert all(1 <= sum(x >= 0 for x in p) <= 4 and -1 in p
               for p, _ in asked_p)
    assert max(n for _, n in asked_p) <= 5 * block
    assert sum(n for _, n in asked_p) < steps * cap * max_len / 3


def _serve_reusing_slots(decode_attn):
    """Five greedy requests through a TWO-slot engine of 64 positions
    (one block, four 16-row tiles): one decodes across three tile
    edges; the other slot finishes a short answer, stays free for five
    steps (an inactive row at a frozen index beside a live one), is
    taken by a prompt of three chunks (inactive again while it
    prefills), and both slots are reused by what queued behind them."""
    cfg = models.LlamaConfig.tiny(dtype=jnp.float32, max_seq_len=64)
    variables = models.Llama(cfg).init(jax.random.PRNGKey(2),
                                       jnp.zeros((2, 4), jnp.int32))
    eng = ServingEngine(variables, cfg, decode_attn=decode_attn, capacity=2,
                        max_len=64, prefill_chunk=16)
    assert eng.cfg.decode_attn == decode_attn
    rs = np.random.RandomState(41)
    reqs = [Request(rs.randint(0, 256, (n,)).astype(np.int32), new)
            for n, new in ((12, 40), (5, 4), (37, 10), (17, 12), (3, 20))]
    eng.submit(reqs[0])
    eng.submit(reqs[1])
    while reqs[1].state != "completed":
        eng.step()
    for _ in range(5):
        eng.step()
    assert reqs[0].state != "completed"
    for r in reqs[2:]:
        eng.submit(r)
    eng.run()
    assert all(r.state == "completed" for r in reqs)
    return [list(r.tokens) for r in reqs]


def test_the_engine_serves_the_same_tokens_from_slots_it_reuses():
    """The step that writes inside the kernel leaves the pool the write
    through XLA left, as far as anything reads it: a row that is not
    live writes nothing where XLA wrote behind the causal mask."""
    assert pallas_decode.writable(64)
    assert _serve_reusing_slots("pallas") == _serve_reusing_slots("xla")


@pytest.mark.parametrize("kv_quant,max_len,resolved", [
    ("none", 2048, "pallas"), ("none", 1024, "pallas"),
    ("int8", 2048, "xla"), ("none", 1031, "xla")])
def test_auto_is_decided_from_platform_cache_dtype_and_tiling(
        monkeypatch, kv_quant, max_len, resolved):
    from bluefog_tpu.models import generate

    cfg = models.LlamaConfig.tiny()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert generate.decode_config(cfg, max_len, kv_quant=kv_quant,
                                  decode_attn="auto").decode_attn == resolved
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert generate.decode_config(cfg, max_len, kv_quant=kv_quant,
                                  decode_attn="auto").decode_attn == "xla"


# ------------------------------------------------------------------ #
# the latent variant (PR 33): one cached row for every head, scores
# over its dc + dr columns, values its first dc
# ------------------------------------------------------------------ #
def _latent_rows(b, dc, dr, dtype, heads=4, dn=8, seed=21):
    """Queries, a cache and up-projections whose value half is the
    identity, so that ``mla_moe.absorbed_step`` returns the weighted
    latent itself: the kernel's output, up to ``W_uv``."""
    rng = np.random.RandomState(seed)
    q_n = jnp.asarray(rng.randn(b, 1, heads, dn) * 0.3, dtype)
    q_r = jnp.asarray(rng.randn(b, 1, heads, dr) * 0.3, dtype)
    latent = jnp.asarray(rng.randn(b, S, dc + dr), dtype)
    w_uk = jnp.asarray(rng.randn(dc, heads, dn) * 0.3, dtype)
    w_uv = jnp.broadcast_to(jnp.eye(dc, dtype=dtype)[:, None],
                            (dc, heads, dc))
    w_ukv = jnp.concatenate([w_uk, w_uv], axis=-1)
    qa = jnp.einsum("bthn,chn->bthc", q_n, w_uk,
                    preferred_element_type=jnp.float32)
    qcat = jnp.concatenate([qa.astype(dtype), q_r], axis=-1)
    return q_n, q_r, qcat, latent, w_ukv, dn


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("how", ["rows", "scalar", "vmap"])
@pytest.mark.parametrize("dc,dr", [(32, 16), (256, 64), (512, 64)])
def test_latent_kernel_is_the_absorbed_step_up_to_the_value_projection(
        dc, dr, how, dtype):
    """Rows at 0, a block's last row, a block's first, one that does not
    decode and the cache's end; one position for every row; and the
    engine's map over slots, each at its own position.  The cache is NaN
    wherever the plan names no block (past a row's own, and all of a row
    that does not decode), so a block fetched and only masked shows."""
    from bluefog_tpu.models.mla_moe import absorbed_step
    from bluefog_tpu.parallel.pallas_decode import latent_decode_attention

    if how == "scalar":
        positions, live = [BLOCK] * 3, None
    else:
        positions = [0, BLOCK - 1, BLOCK, 40, S - 1]
        live = jnp.asarray([True, True, True, False, True])
    b = len(positions)
    positions = jnp.asarray(positions, jnp.int32)
    q_n, q_r, qcat, latent, w_ukv, dn = _latent_rows(b, dc, dr, dtype)
    want = jax.vmap(lambda qn, qr, c, p: absorbed_step(
        qn[None], qr[None], c[None], p[None], w_ukv, dc, dn)[0])(
            q_n, q_r, latent, positions)
    dirty = jnp.where(_past(positions, live)[..., None], jnp.nan, latent)
    if how == "rows":
        got = latent_decode_attention(qcat, dirty, positions, dc=dc,
                                      live=live, block_s=BLOCK)
        # the row that does not decode names the block the row before
        # it ended on, none of its own
        plan = np.asarray(pallas_decode._stream_plan(positions, live, BLOCK))
        assert plan[pallas_decode._SRC, 3] == 2 and plan[
            pallas_decode._FIRST, 3] == plan[pallas_decode._LAST, 3] == 1
    elif how == "scalar":
        got = latent_decode_attention(qcat, dirty, positions[0], dc=dc,
                                      block_s=BLOCK)
    else:
        def one(q, c, idx, alive):       # a slot of the pool: batch 1
            return latent_decode_attention(q[None], c[None], idx, dc=dc,
                                           live=alive[None],
                                           block_s=BLOCK)[0]

        mapped = jax.vmap(one)
        assert str(jax.make_jaxpr(mapped)(qcat, dirty, positions, live)
                   ).count("pallas_call") == 1
        got = jax.jit(mapped)(qcat, dirty, positions, live)
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == np.float32 and np.isfinite(got).all()
    alive = np.ones(b, bool) if live is None else np.asarray(live)
    # bfloat16: the einsums round the probabilities to the cache's
    # dtype before the value contraction, the kernel keeps them float32
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[alive], want[alive], atol=tol, rtol=tol)
    assert not got[~alive].any()         # a dead row: zeros


def test_the_latent_block_follows_the_cache_length():
    """No keyword chooses it in the model: a function of the length
    alone, shared by the kernel and the host's count: a quarter of the
    cache between the dense kernel's block and four of them."""
    from bluefog_tpu.parallel.pallas_decode import latent_block

    assert [latent_block(s) for s in (16384, 4096, 2048, 1024, 72)] \
        == [2048, 1024, 512, 512, 72]
    assert latent_block(4 * 1031) == 1031      # the largest divisor under
    with pytest.raises(ValueError, match="no block divisor"):
        pallas_decode.latent_decode_attention(
            jnp.zeros((1, 1, 4, 24)), jnp.zeros((1, 1031, 24)),
            jnp.int32(3), dc=16)
