"""The main path's Pallas kernels, compiled by the TPU compiler for a
DESCRIBED v5e:2x2 topology (no chip attached) at the "1b" Llama widths
chip_smoke.py runs: 32 query / 8 KV heads of 64, batch 4 x 2048 for
training, 8 slots x 1024 (bf16) and 2048 (int8) cache positions for
decode.  Interpret mode cannot see what these catch — a block shape or a
memory space Mosaic refuses — and each costs a second or two and no chip
time.  Nothing executes: a pass says the kernel compiles, not that it is
right (tests/test_pallas_*.py hold the numerics, chip_smoke.py the chip).

The whole file skips where the topology cannot be described (no libtpu).
The suite's conftest turns x64 on; the chip runs without it and the
splash library kernel refuses it, so every test here scopes it off.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bluefog_tpu import models
from bluefog_tpu.models import generate
from bluefog_tpu.parallel import pallas_decode
from bluefog_tpu.parallel.pallas_attention import flash_attention
from bluefog_tpu.parallel.splash import splash_attention
from bluefog_tpu.serving import engine
from bluefog_tpu.serving.kv_pool import SlotPool

CFG = models.LlamaConfig.llama_1b(dtype=jnp.bfloat16)
BATCH, SEQ, SLOTS = 4, 2048, 8


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu, or it cannot describe a chip
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_x64():
    with jax.enable_x64(False):
        yield


def _compiled_text(fn, *shapes, chip, **static):
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        shapes)
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile().as_text()


def _qkv(heads=CFG.n_heads):
    q = jax.ShapeDtypeStruct((BATCH, SEQ, heads, CFG.head_dim),
                             jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((BATCH, SEQ, CFG.n_kv_heads, CFG.head_dim),
                              jnp.bfloat16)
    return q, kv, kv


def _loss_grads(attend):
    def loss(q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32) ** 2)

    return jax.grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
def test_flash_kernel_compiles_for_v5e(chip, backward):
    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    text = _compiled_text(_loss_grads(attend) if backward else attend,
                          *_qkv(), chip=chip)
    assert "tpu_custom_call" in text


def test_splash_backward_compiles_for_v5e(chip):
    def attend(q, k, v):
        return splash_attention(q, k, v, causal=True, interpret=False)

    assert "tpu_custom_call" in _compiled_text(_loss_grads(attend),
                                               *_qkv(), chip=chip)


# the last row is mistral7b-serve-steady's pool (BENCHMARK.json): 32
# slots x 2048 positions, 32 query / 8 KV heads of 128
@pytest.mark.parametrize("kv_quant,slots,positions,heads,head_dim", [
    ("none", SLOTS, 1024, CFG.n_heads, CFG.head_dim),
    ("int8", SLOTS, 2048, CFG.n_heads, CFG.head_dim),
    ("none", 32, 2048, 32, 128),
    ("int8", 32, 2048, 32, 128)])
def test_decode_kernel_compiles_for_v5e(chip, kv_quant, slots, positions,
                                        heads, head_dim):
    q = jax.ShapeDtypeStruct((slots, 1, heads, head_dim), jnp.bfloat16)
    idx = jax.ShapeDtypeStruct((slots,), jnp.int32)
    shape = (slots, CFG.n_kv_heads, positions, head_dim)
    if kv_quant == "int8":
        kv = jax.ShapeDtypeStruct(shape, jnp.int8)
        scale = jax.ShapeDtypeStruct(shape[:-1], jnp.float32)
        text = _compiled_text(
            lambda q, k, ks, v, vs, i: pallas_decode.decode_attention_int8(
                q, k, ks, v, vs, i, interpret=False),
            q, kv, scale, kv, scale, idx, chip=chip)
    else:
        kv = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        text = _compiled_text(
            lambda q, k, v, i: pallas_decode.decode_attention(
                q, k, v, i, interpret=False),
            q, kv, kv, idx, chip=chip)
    assert "tpu_custom_call" in text


# the pools of mistral-small4-serve-long-prompt and of
# xing4-serve-long-answer (BENCHMARK.json), 32 heads: latent rows of
# 256 + 64 and of 512 + 64 values
@pytest.mark.parametrize("slots,positions,dc,dr", [
    (32, 16384, 256, 64), (64, 4096, 512, 64)])
def test_latent_decode_kernel_compiles_for_v5e_with_no_copy_of_the_pool(
        chip, slots, positions, dc, dr):
    """A width that is no multiple of 128 lanes: the TPU lays the leaf
    position-minor, and the kernel reads it as it lies.  A kernel that
    asked for the leaf row-major compiled too, behind a transposed copy
    of the whole leaf (0.38 and 0.31 GiB of temporaries a call)."""
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=chip)
    compiled = jax.jit(
        lambda q, c, i, alive: pallas_decode.latent_decode_attention(
            q, c, i, dc=dc, live=alive, interpret=False)).lower(
        sds((slots, 1, 32, dc + dr), jnp.bfloat16),
        sds((slots, positions, dc + dr), jnp.bfloat16),
        sds((slots,), jnp.int32), sds((slots,), bool)).compile()
    assert pallas_decode.latent_tileable(positions, dc + dr)
    assert "tpu_custom_call" in compiled.as_text()
    leaf = slots * positions * (dc + dr) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < leaf // 16


def test_engine_decode_step_holds_the_kernel_on_v5e(chip, monkeypatch):
    """The serving engine's resident decode step maps the model over
    its slots, each at its own position: the program the TPU lowering
    refused before the kernel learned to fold a mapped axis.  Two
    layers (depth repeats the same kernel call); the model asks the
    backend whether to interpret, which is ``cpu`` here, so the test
    answers for it."""
    monkeypatch.setattr(pallas_decode, "_auto_interpret",
                        lambda interpret: False)
    cfg = generate.decode_config(
        models.LlamaConfig.llama_1b(dtype=jnp.bfloat16, n_layers=2), 1024,
        decode_attn="pallas")
    variables = jax.eval_shape(
        lambda: models.Llama(cfg).init(jax.random.PRNGKey(0),
                                       jnp.zeros((1, 1), jnp.int32)))
    pool = jax.eval_shape(lambda: SlotPool(cfg, SLOTS, 1024).cache)

    def slots(dtype, *tail):
        return jax.ShapeDtypeStruct((SLOTS,) + tail, dtype)

    text = _compiled_text(
        engine._decode_step_prog.__wrapped__, variables["params"], pool,
        slots(jnp.int32), slots(bool), slots(jnp.uint32, 2),
        slots(jnp.int32), slots(jnp.float32), chip=chip, cfg=cfg,
        horizon=1)
    assert "tpu_custom_call" in text


def test_the_mistral_decode_step_writes_its_rows_inside_the_kernel_on_v5e(
        chip, monkeypatch):
    """The resident decode step of mistral7b-serve-steady and
    -saturated (BENCHMARK.json) at the serving cut's widths and pool,
    two layers of the sixteen: 32 slots x 2,048 positions, 8 KV heads of
    128 under 32 query heads, bf16.  The step's key and value rows go
    into the cache inside the kernel that reads it: XLA's write under
    the engine's map over slots was a scatter, lowered as a loop over
    the slots for K and one for V a layer (32 loops, 4.4 of 14.8 ms a
    step; PERF.md section 6, PR 41).  The program holds no scatter, no
    loop, and no copy of a pool leaf on the way into the kernel or out
    of it (the plain pair enters as a stack of one leaf: a bitcast),
    and the pool is donated through."""
    import re

    monkeypatch.setattr(pallas_decode, "_auto_interpret",
                        lambda interpret: False)
    slots_n, max_len, layers = 32, 2048, 2
    cfg = generate.decode_config(models.LlamaConfig(
        vocab_size=32000, dim=4096, n_layers=layers, n_heads=32,
        n_kv_heads=8, hidden_dim=14336, rope_theta=1e4, norm_eps=1e-5,
        dtype=jnp.bfloat16), max_len, decode_attn="pallas")
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        tree)
    params = on_chip(jax.eval_shape(lambda: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16),
        models.Llama(cfg).init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 1), jnp.int32))["params"])))
    pool = on_chip(jax.eval_shape(
        lambda: SlotPool(cfg, slots_n, max_len).cache))
    sds = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=chip)
    compiled = engine._decode_step_prog.lower(
        params, pool, sds(jnp.int32, slots_n), sds(bool, slots_n),
        sds(jnp.uint32, slots_n, 2), sds(jnp.int32, slots_n),
        sds(jnp.float32, slots_n), cfg=cfg, horizon=1).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == layers
    assert "scatter" not in text
    assert not re.search(r"\bwhile\(", text)
    leaf = r"bf16\[32,(1,)?8,2048,128\]"
    assert re.search(leaf, text)
    assert not re.search(leaf + r"\S* copy\(", text)
    leaf_bytes = slots_n * 8 * max_len * 128 * 2
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * layers * leaf_bytes


def _latent_decode_step(cfg, slots_n, max_len, chip):
    """``engine._decode_step_prog`` of a ``models/mla_moe.py`` config
    over a pool of ``slots_n`` x ``max_len``, compiled from shapes."""
    from bluefog_tpu.models.mla_moe import MlaMoe

    variables = jax.eval_shape(
        lambda: MlaMoe(cfg).init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 1), jnp.int32)))
    pool = jax.eval_shape(lambda: SlotPool(cfg, slots_n, max_len).cache)

    def slots(dtype, *tail):
        return jax.ShapeDtypeStruct((slots_n,) + tail, dtype, sharding=chip)

    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        (variables["params"], pool))
    return engine._decode_step_prog.lower(
        *args, slots(jnp.int32), slots(bool), slots(jnp.uint32, 2),
        slots(jnp.int32), slots(jnp.float32), cfg=cfg, horizon=1).compile()


def test_engine_decode_step_reads_the_latent_pool_in_place_on_v5e(
        chip, monkeypatch):
    """The latent model's resident decode step at Mistral-Small-4's
    attention widths and pool (32 slots x 16,384 positions of 320), one
    layer of few experts: the cache write and the kernel both take the
    leaf as the program's argument lies, so the program's temporaries
    stay far under one leaf (a row-major kernel made 0.39 GiB of them a
    layer: a transposed copy in, another out)."""
    from bluefog_tpu.models.mla_moe import MlaMoeConfig

    monkeypatch.setattr(pallas_decode, "_auto_interpret",
                        lambda interpret: False)
    slots_n, max_len = 32, 16384
    cfg = MlaMoeConfig(
        vocab_size=1024, dim=1024, n_layers=1, n_heads=32, q_lora_rank=256,
        kv_lora_rank=256, qk_nope_head_dim=64, qk_rope_head_dim=64,
        v_head_dim=128, expert_hidden_dim=256, n_experts=8, top_k=2,
        rope_factor=128.0).serving_layout(max_len, decode_attn="pallas")
    compiled = _latent_decode_step(cfg, slots_n, max_len, chip)
    assert "tpu_custom_call" in compiled.as_text()
    leaf = slots_n * max_len * cfg.latent_width * 2
    assert compiled.memory_analysis().temp_size_in_bytes < leaf // 4


def test_engine_decode_step_advances_the_live_states_in_place_on_v5e(
        chip, monkeypatch):
    """The two-kind model's resident decode step at Ling3's recurrent
    widths (ling3-flash-serve-doc-reasoning, BENCHMARK.json: 64 slots,
    32 heads of a 128 x 128 float32 state, 2 MiB a slot a layer), one
    recurrent layer and one latent, few experts.  The step of the state
    is ONE kernel over the leaf as the program's argument lies: the leaf
    is aliased from argument to result and the program's temporaries
    stay far under one leaf of 128 MiB (``delta_step`` under the
    engine's map over slots walked it three times: a select for the
    index-0 rule, a reduction and a ``multiply_add_fusion`` as tall as
    the leaf).  A model with no recurrent layer never loads the kernel's
    module."""
    import re
    import sys

    from bluefog_tpu import parallel
    from bluefog_tpu.models.mla_moe import MlaMoeConfig

    monkeypatch.setattr(pallas_decode, "_auto_interpret",
                        lambda interpret: False)
    slots_n, max_len, heads, head_dim = 64, 4096, 32, 128
    widths = dict(
        vocab_size=1024, dim=1024, n_layers=2, n_heads=heads,
        q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, expert_hidden_dim=256,
        n_experts=8, top_k=2, head_gate=True, kda_head_dim=head_dim)
    decode_step = lambda cfg: _latent_decode_step(
        cfg.serving_layout(max_len, decode_attn="pallas"), slots_n, max_len,
        chip)

    # as in a process that never loaded it
    monkeypatch.delitem(sys.modules, "bluefog_tpu.parallel.pallas_kda",
                        raising=False)
    monkeypatch.delattr(parallel, "pallas_kda", raising=False)
    decode_step(MlaMoeConfig(**widths))
    assert "bluefog_tpu.parallel.pallas_kda" not in sys.modules

    compiled = decode_step(MlaMoeConfig(**widths,
                                        layer_types=("kda", "latent")))
    text = compiled.as_text()
    # the recurrent layer's kernel and the latent layer's
    assert text.count("tpu_custom_call") == 2
    leaf = slots_n * heads * head_dim * head_dim * 4
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < leaf // 4
    assert memory.alias_size_in_bytes >= leaf
    state = rf"f32\[{slots_n},(?:1,)?{heads},{head_dim},{head_dim}\]"
    assert re.search(state, text)
    # nothing but the kernel makes an array as tall as the leaf
    made = re.findall(state + r"\S* (\w[\w-]*)\(", text)
    assert set(made) <= {"parameter", "custom-call", "bitcast",
                         "get-tuple-element"}, made


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_the_looped_models_programs_hold_one_cache_on_v5e(
        chip, monkeypatch, program):
    """Ouro-2.6B's two resident programs at the sizes of
    ouro-2.6b-serve-short-answer (BENCHMARK.json): 48 layers x 4 passes
    rolled, 8 slots x 768 positions, a pool of 9.0 GiB in two stacked
    leaves beside 4.97 GiB of bf16 weights.  The loops carry the pool
    and write it in place; the decode step's kernel finds its (pass,
    layer) inside the stack and the chunk writes its slot where it lies,
    so what the compiler adds to the arguments stays under 64 MiB (the
    forms that do not: a transposed copy of wq, wk and wv of every
    layer, 1.125 GiB; a chunk that cuts its slot out of the pool, 1.125
    GiB; a key or value leaf laid out anew for the chunk's einsums, 4.5
    GiB and no fit)."""
    from bluefog_tpu.models.looped import LoopedConfig, init_params

    monkeypatch.setattr(pallas_decode, "_auto_interpret",
                        lambda interpret: False)
    slots_n, max_len, chunk = 8, 768, 256
    cfg = LoopedConfig(models.LlamaConfig(
        vocab_size=49152, dim=2048, n_layers=48, n_heads=16, n_kv_heads=16,
        hidden_dim=5632, rope_theta=1e6, norm_eps=1e-6,
        dtype=jnp.bfloat16)).serving_layout(max_len, chunk=chunk,
                                            decode_attn="pallas")
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        tree)
    params = on_chip(jax.eval_shape(lambda: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16),
        init_params(cfg, jax.random.PRNGKey(0)))))
    pool = on_chip(jax.eval_shape(
        lambda: SlotPool(cfg, slots_n, max_len, chunk=chunk).cache))
    sds = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=chip)
    if program == "decode_step":
        lowered = engine._decode_step_prog.lower(
            params, pool, sds(jnp.int32, slots_n), sds(bool, slots_n),
            sds(jnp.uint32, slots_n, 2), sds(jnp.int32, slots_n),
            sds(jnp.float32, slots_n), cfg=cfg, horizon=1)
    else:
        lowered = engine._prefill_chunk_prog.lower(
            params, pool, sds(jnp.int32), sds(jnp.int32, 1, chunk),
            sds(jnp.int32), cfg=cfg)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 9 * 2 ** 30      # the pool, once
    assert memory.temp_size_in_bytes < 64 * 2 ** 20
    need = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    assert need < 14.1 * 2 ** 30
    assert ("tpu_custom_call" in compiled.as_text()) \
        == (program == "decode_step")
    # one copy of the block: the seven projections and the gate; the
    # head where its logits are read (the step), the attention's two
    # products where no kernel stands for them (the chunk)
    assert lowered.as_text().count("dot_general") \
        == (9 if program == "decode_step" else 10)


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_the_hybrid_state_space_models_programs_fit_a_v5e(
        chip, monkeypatch, program):
    """Falcon-H1-34B-Instruct's two resident programs at the sizes of
    falcon-h1-34b-serve-chat-bursts (BENCHMARK.json): six layers at the
    published widths, 64 slots x (24.2 MiB of recurrent state + 2,048
    positions of keys and values), a pool of 3.01 GiB beside 9.79 GiB of
    bf16 weights.  The compiler's own count stays under 14 GiB of the
    chip's 15.75 (a seventh layer is 0.80 GiB more and does not), the
    pool is updated in place, and the decode step's attention is the
    fused kernel at FIVE query heads a key/value head, one call a
    layer."""
    from perfbench.harness import loader

    monkeypatch.setattr(pallas_decode, "_auto_interpret",
                        lambda interpret: False)
    cell = loader.load_cell("falcon-h1-34b-serve-chat-bursts")
    family = cell.family()
    sz = family.sizes(cell.config, cell.traffic["cut"])
    shape = cell.traffic["engine"]
    slots_n, max_len, chunk = (shape["capacity"], shape["max_len"],
                               shape["prefill_chunk"])
    cfg = family.model_config(sz, max_seq_len=max_len).serving_layout(
        max_len, chunk=chunk, decode_attn="pallas")
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        tree)
    params = on_chip(jax.eval_shape(lambda: family.make_params(
        sz, jax.random.PRNGKey(0), jnp.bfloat16)[0]))
    pool = on_chip(jax.eval_shape(
        lambda: SlotPool(cfg, slots_n, max_len, chunk=chunk).cache))
    sds = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=chip)
    if program == "decode_step":
        lowered = engine._decode_step_prog.lower(
            params, pool, sds(jnp.int32, slots_n), sds(bool, slots_n),
            sds(jnp.uint32, slots_n, 2), sds(jnp.int32, slots_n),
            sds(jnp.float32, slots_n), sds(bool, slots_n),
            sds(jnp.int32, 1, slots_n), cfg=cfg, horizon=1)
    else:
        lowered = engine._prefill_chunk_prog.lower(
            params, pool, sds(jnp.int32), sds(jnp.int32, 1, chunk),
            sds(jnp.int32), cfg=cfg)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 3 * 2 ** 30      # the pool, once
    assert memory.temp_size_in_bytes < 512 * 2 ** 20
    need = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    assert need < 14.0 * 2 ** 30
    assert compiled.as_text().count("tpu_custom_call") \
        == (6 if program == "decode_step" else 0)


# the chunk of ling3-flash-serve-doc-reasoning and of
# mistral-small4-serve-long-prompt (BENCHMARK.json): rows, width, an
# expert's width, experts held, experts a token
@pytest.mark.parametrize("n,d,f,held,top_k", [
    (512, 2560, 768, 128, 8), (512, 4096, 2048, 32, 4)])
def test_a_turn_of_the_tiled_expert_loop_is_three_products_on_v5e(
        chip, n, d, f, held, top_k):
    """A turn's operations run one after another on the chip, so each
    one that is not a product with the expert's matrices adds to the
    turn in full (3.2 us of 27 for a tile's result copied into the
    buffer, PERF.md section 6, PR 39).  The last product writes its
    tile in place because the tile lands on whole (8, 128) tiles of the
    buffer: the loop's body holds the three products, no copy of a tile
    and nothing as tall as the call."""
    import re

    from bluefog_tpu.models import experts

    sds = jax.ShapeDtypeStruct
    text = _compiled_text(
        lambda m, c, w1, w3, w2: experts._experts_hit(m, c, w1, w3, w2,
                                                      top_k),
        sds((n, d), jnp.bfloat16), sds((n, held), jnp.float32),
        sds((held, d, f), jnp.bfloat16), sds((held, d, f), jnp.bfloat16),
        sds((held, f, d), jnp.bfloat16), chip=chip)
    computations = re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \([^\n]*\) -> )", text)
    bodies = [c for c in computations if "/while/body/" in c
              and not c.startswith(("%fused", "%region"))]
    assert len(bodies) == 1
    ops = re.findall(r"^\s*(?:ROOT )?%[\w.\-]+ = (\w+\[\d*)\S* ([\w\-]+)\(",
                     bodies[0], flags=re.M)
    kinds = [kind for _, kind in ops]
    assert kinds.count("fusion") == 4     # the turn's expert; the products
    assert not {"dynamic-update-slice", "copy", "gather",
                "scatter"} & set(kinds), kinds
    assert not [shape for shape, _ in ops if shape.endswith(f"[{n}")]
