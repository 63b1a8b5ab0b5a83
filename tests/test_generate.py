"""Autoregressive generation with K/V caching (inference capability —
the reference framework is training-only).

Contract: the cached incremental decode is a pure optimization — greedy
generation must match the no-cache rollout (re-running the full forward
on the growing sequence and taking argmax) token for token, in both
layer layouts.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import served_model
from bluefog_tpu import models
from bluefog_tpu.models import llama_generate

B, T_PROMPT, NEW = 2, 7, 9


def _setup(scan_layers):
    cfg, variables = served_model.tiny_llama(scan_layers=scan_layers)
    model = models.Llama(cfg)
    prompt = np.random.RandomState(0).randint(
        0, 256, (B, T_PROMPT)).astype(np.int32)
    return cfg, model, variables, prompt


def _rollout_greedy(model, variables, prompt, n_new):
    """Reference: no cache, full forward over the growing sequence
    (causal, so zeros behind it change no row before them: one program
    at the last length, not one an operation and a length)."""
    forward = jax.jit(model.apply)
    t = prompt.shape[1]
    seq = np.zeros((prompt.shape[0], t + n_new), np.int32)
    seq[:, :t] = prompt
    for at in range(t, t + n_new):
        logits = forward(variables, jnp.asarray(seq))
        seq[:, at] = np.asarray(jnp.argmax(logits[:, at - 1], axis=-1))
    return seq


@pytest.mark.parametrize("scan_layers", [False, True])
def test_greedy_generate_matches_no_cache_rollout(scan_layers):
    cfg, model, variables, prompt = _setup(scan_layers)
    got = np.asarray(llama_generate(variables, cfg, jnp.asarray(prompt),
                                    NEW))
    want = _rollout_greedy(model, variables, prompt, NEW)
    np.testing.assert_array_equal(got, want)


def test_generate_single_token():
    cfg, model, variables, prompt = _setup(False)
    got = np.asarray(llama_generate(variables, cfg, jnp.asarray(prompt), 1))
    want = _rollout_greedy(model, variables, prompt, 1)
    np.testing.assert_array_equal(got, want)


def test_temperature_sampling_deterministic_given_rng():
    cfg, _, variables, prompt = _setup(False)
    a = np.asarray(llama_generate(
        variables, cfg, jnp.asarray(prompt), NEW, temperature=1.0,
        rng=jax.random.PRNGKey(7)))
    b = np.asarray(llama_generate(
        variables, cfg, jnp.asarray(prompt), NEW, temperature=1.0,
        rng=jax.random.PRNGKey(7)))
    np.testing.assert_array_equal(a, b)
    assert a.shape == (B, T_PROMPT + NEW)
    assert np.all((a >= 0) & (a < 256))


def test_generate_validates_inputs():
    cfg, _, variables, prompt = _setup(False)
    with pytest.raises(ValueError, match="max_len"):
        llama_generate(variables, cfg, jnp.asarray(prompt), NEW,
                       max_len=T_PROMPT)
    with pytest.raises(ValueError, match="rng"):
        llama_generate(variables, cfg, jnp.asarray(prompt), NEW,
                       temperature=0.7)
    # MoE decode is supported (dropless routing — tests/test_moe_decode);
    # only the non-causal expert_choice router still refuses
    moe = models.LlamaConfig.tiny(dtype=jnp.float32, n_experts=4,
                                  moe_router="expert_choice",
                                  allow_noncausal_router=True)
    with pytest.raises(NotImplementedError, match="expert_choice"):
        llama_generate(variables, moe, jnp.asarray(prompt), NEW)
    with pytest.raises(ValueError, match="max_new_tokens"):
        llama_generate(variables, cfg, jnp.asarray(prompt), 0)


def test_temperature_change_does_not_recompile():
    """temperature is a traced operand: sweeping it shares ONE compiled
    generation program (only greedy <-> sampling switches compile)."""
    from bluefog_tpu.models.generate import _generate_impl

    cfg, _, variables, prompt = _setup(False)
    before = _generate_impl._cache_size()
    a = llama_generate(variables, cfg, jnp.asarray(prompt), 3,
                       temperature=0.7, rng=jax.random.PRNGKey(0))
    mid = _generate_impl._cache_size()
    b = llama_generate(variables, cfg, jnp.asarray(prompt), 3,
                       temperature=1.3, rng=jax.random.PRNGKey(0))
    after = _generate_impl._cache_size()
    assert mid == before + 1
    assert after == mid  # second temperature hit the same compilation
    assert np.asarray(a).shape == np.asarray(b).shape


def test_generate_clears_model_parallel_axes():
    """A TP-trained config decodes with replicated params — the mesh-axis
    knobs are training-time layouts, cleared internally (they would
    otherwise hit unbound-axis psums outside shard_map)."""
    cfg = models.LlamaConfig.tiny(dtype=jnp.float32, tp_axis="tp",
                                  tp_size=2)
    plain, model, variables, prompt = _setup(False)
    got = np.asarray(llama_generate(variables, cfg, jnp.asarray(prompt), 4))
    want = _rollout_greedy(model, variables, prompt, 4)
    np.testing.assert_array_equal(got, want)


def test_tp_sharded_decode_matches_no_cache_rollout():
    """Round-2 verdict item 8: K/V-cached generation under tp=2 (sharded
    heads, per-shard caches, psum-merged logits) == the replicated
    no-cache rollout, token for token.  This is the decode layout that
    serves HF-imported checkpoints too big for one chip."""
    from jax.sharding import Mesh

    cfg = models.LlamaConfig.tiny(dtype=jnp.float32, tp_axis="tp",
                                  tp_size=2)
    plain, model, variables, prompt = _setup(False)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    got = np.asarray(llama_generate(variables, cfg, jnp.asarray(prompt),
                                    NEW, mesh=mesh))
    want = _rollout_greedy(model, variables, prompt, NEW)
    np.testing.assert_array_equal(got, want)


def test_tp_sharded_decode_sampling_agrees_across_shards():
    """Temperature sampling under tp: every shard draws from the SAME
    replicated logits with the SAME rng — one consistent token stream."""
    from jax.sharding import Mesh

    cfg = models.LlamaConfig.tiny(dtype=jnp.float32, tp_axis="tp",
                                  tp_size=2)
    plain, model, variables, prompt = _setup(False)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    rng = jax.random.PRNGKey(7)
    a = np.asarray(llama_generate(variables, cfg, jnp.asarray(prompt), 5,
                                  temperature=0.8, rng=rng, mesh=mesh))
    b = np.asarray(llama_generate(variables, plain, jnp.asarray(prompt), 5,
                                  temperature=0.8, rng=rng))
    np.testing.assert_array_equal(a, b)


def test_eos_unseen_matches_unstopped_path():
    """eos_id parity: an eos that never fires leaves the output
    bit-identical to the unstopped path (the done mask is pure
    plumbing until it triggers)."""
    cfg, _, variables, prompt = _setup(False)
    plain = np.asarray(llama_generate(variables, cfg, jnp.asarray(prompt),
                                      NEW))
    unseen = [t for t in range(256)
              if t not in plain[:, T_PROMPT:]][0]
    stopped = np.asarray(llama_generate(variables, cfg,
                                        jnp.asarray(prompt), NEW,
                                        eos_id=unseen))
    np.testing.assert_array_equal(stopped, plain)


def test_eos_freezes_finished_rows():
    """Once a row emits eos_id, every later position in that row is
    eos_id padding; other rows keep generating their unstopped stream."""
    cfg, _, variables, prompt = _setup(False)
    plain = np.asarray(llama_generate(variables, cfg, jnp.asarray(prompt),
                                      NEW))
    # force row 0 to stop in mid-stream: at its first generated token,
    # from the third on, that no earlier generated position of the row
    # holds (the row would stop at the earlier one)
    row = plain[0, T_PROMPT:]
    stop = next(j for j in range(2, NEW - 1) if row[j] not in row[:j])
    eos = int(row[stop])
    assert eos not in row[:stop]
    got = np.asarray(llama_generate(variables, cfg, jnp.asarray(prompt),
                                    NEW, eos_id=eos))
    np.testing.assert_array_equal(got[0, :T_PROMPT + stop + 1],
                                  plain[0, :T_PROMPT + stop + 1])
    assert np.all(got[0, T_PROMPT + stop + 1:] == eos)
    for r in range(1, prompt.shape[0]):
        if eos not in plain[r, T_PROMPT:]:
            np.testing.assert_array_equal(got[r], plain[r])


def test_generate_from_hf_import():
    """HF-imported weights decode directly."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from bluefog_tpu.interop import (llama_config_from_hf,
                                     llama_params_from_hf)

    hf_cfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=128, max_position_embeddings=256,
        rope_theta=500000.0, rms_norm_eps=1e-5, attention_bias=False,
        mlp_bias=False, tie_word_embeddings=False)
    torch.manual_seed(0)
    hf = transformers.LlamaForCausalLM(hf_cfg).float().eval()
    cfg = llama_config_from_hf(hf_cfg, dtype=jnp.float32)
    variables = llama_params_from_hf(hf, cfg)
    prompt = np.random.RandomState(3).randint(
        0, 256, (1, 5)).astype(np.int32)
    ours = np.asarray(llama_generate(variables, cfg, jnp.asarray(prompt), 6))
    want = _rollout_greedy(models.Llama(cfg), variables, prompt, 6)
    np.testing.assert_array_equal(ours, want)
