"""Continuous-batching inference engine.

One-shot :func:`~bluefog_tpu.models.generate.llama_generate` is a
benchmark artifact: fixed batch, fixed prompt length, everyone finishes
together.  A server sees none of that — requests arrive whenever,
prompts differ, budgets differ — and a bandwidth-bound TPU decode loop
that waits for batch formation or pads dead rows is idle silicon.  This
engine keeps ONE resident jitted program busy across an arbitrary
arrival pattern:

* every request owns a **slot** of the fixed-capacity K/V pool
  (:class:`~bluefog_tpu.serving.kv_pool.SlotPool`);
* each host-loop :meth:`~ServingEngine.step` admits queued requests and
  runs up to ``prefill_budget`` **chunked-prefill** calls (fixed chunk
  shape — a long prompt spreads over several steps instead of stalling
  running decodes), then advances EVERY active slot ``decode_horizon``
  tokens in a single vmapped program with a per-slot active mask and
  per-slot cache index;
* that program is dispatched ONE STEP AHEAD of the host's knowledge of
  the tokens: a step dispatches the next decode program (its input
  tokens are the previous program's output, still on the device) and
  only then reads the tokens of the program the step before
  dispatched, so the host's work of a step runs beside the device's
  (:meth:`~ServingEngine.step` says what a caller may rely on);
* slots retire on EOS / token budget / deadline / cancellation and are
  zeroed for reuse.

The resident program set is FIXED AT BUILD TIME and its shapes depend
only on ``(capacity, max_len, prefill_chunk, decode_horizon)`` — never
on the arrival pattern: no recompiles across requests.  A plain engine
residents a prefill-chunk and a decode-step program (plus the slot
housekeeping scatter); a :class:`SpeculativeConfig` swaps the decode
step for a draft/verify pair — the draft model proposes ``lookahead``
tokens through the same single-token step, ONE multi-token target
forward scores the whole window (``apply_cached(..., all_logits=True)``),
and acceptance is rejection sampling (token-exact greedy at temperature
0).  Either way
the count is fixed before the first request arrives, and
:meth:`ServingEngine.profile` enumerates whatever is resident.

Two optional subsystems ride the same fixed programs: a
:class:`~bluefog_tpu.serving.prefix_cache.PrefixCache` admits requests
that share a prompt prefix by COPYING cached K/V chunks into the slot
instead of re-running prefill (chain-hashed whole chunks — bit-exact vs
cold prefill), and ``registry=`` isolates the engine's metrics for
multi-replica fleets (:mod:`bluefog_tpu.serving.fleet`).

Numerics are the one-shot path's numerics: both are the same cached
``model.apply`` (``cfg.apply_cached`` here, :func:`prefill_cache` /
:func:`decode_token_step` there), so a GREEDY
request served through the engine reproduces its one-shot
``llama_generate(prompt[None], n, max_len=pool_max_len)`` output token
for token (tests/test_serving.py).  Temperature sampling is
deterministic per request (the rng folds the request seed with the
token index) but uses a different rng chain than the one-shot scan, so
sampled streams are engine-reproducible, not one-shot-identical.
Chunked prefill stays exact because
attention is causal: a padded chunk's real rows never attend to the pad
tail, and the corrected per-slot cache index masks the tail until real
tokens overwrite it.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from functools import lru_cache, partial
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bluefog_tpu.serving import protocol
from bluefog_tpu.serving.kv_pool import SlotPool, pack_stats
from bluefog_tpu.serving.metrics import ServingMetrics
from bluefog_tpu.serving.scheduler import FifoScheduler, RequestRejected

__all__ = ["ServingEngine", "Request", "RequestRejected",
           "SpeculativeConfig", "EXPIRED", "FAILOVER"]

_rid_counter = itertools.count()

# terminal / live request states
QUEUED, PREFILL, DECODE = "queued", "prefill", "decode"
COMPLETED, CANCELLED, REJECTED = "completed", "cancelled", "rejected"
# EXPIRED: terminal — deadline passed while the request was stranded on
# a dead/draining replica (the queue-shedding path stays CANCELLED).
# FAILOVER: transitional retire outcome — the slot is released here but
# the request immediately resets to QUEUED for resubmission elsewhere,
# so ``done`` stays False.
EXPIRED, FAILOVER = "expired", "failover"


@dataclasses.dataclass(eq=False)  # identity semantics: the scheduler
# removes by object (a generated __eq__ would compare prompt arrays)
class Request:
    """One generation request.

    ``deadline`` is in absolute engine-clock seconds (the engine's
    injected ``clock``, ``time.monotonic`` by default): a request that
    has not RETIRED by its deadline is cancelled — queued ones are shed
    without ever touching the device.  ``temperature``/``seed`` drive
    per-request sampling (greedy at 0.0); sampling is deterministic
    given the seed and independent of what the request is co-batched
    with (the rng folds in the per-request token index, not the engine
    step)."""
    prompt: np.ndarray
    max_new_tokens: int
    eos_id: Optional[int] = None
    temperature: float = 0.0
    seed: int = 0
    deadline: Optional[float] = None
    rid: int = dataclasses.field(default_factory=lambda: next(_rid_counter))

    # engine-owned state
    state: str = dataclasses.field(default=QUEUED, init=False)
    tokens: List[int] = dataclasses.field(default_factory=list, init=False)
    slot: Optional[int] = dataclasses.field(default=None, init=False)
    _prefill_pos: int = dataclasses.field(default=0, init=False)
    _cancel: bool = dataclasses.field(default=False, init=False)
    _prefix_keys: Optional[List[str]] = dataclasses.field(default=None,
                                                          init=False)

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens ({self.max_new_tokens}) must be >= 1")

    @property
    def done(self) -> bool:
        return self.state in (COMPLETED, CANCELLED, REJECTED, EXPIRED)

    def output(self) -> np.ndarray:
        """prompt ‖ generated tokens (no padding — streaming semantics:
        exactly what was emitted, EOS included when it fired)."""
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int32)])

    def reset_for_resume(self) -> "Request":
        """Return the request to the submittable QUEUED state while
        KEEPING its emitted tokens — the failover/drain primitive.  The
        next engine re-prefills ``prompt ‖ tokens`` (cached chunks by
        chain hash, cold tail otherwise) and its decode continues the
        rng fold chain at ``len(tokens)``, so the resumed stream is
        bit-equal to an unfaulted run."""
        self.state = QUEUED
        self.slot = None
        self._prefill_pos = 0
        self._cancel = False
        self._prefix_keys = None
        return self


@lru_cache(maxsize=4096)
def _rng_key(seed: int) -> np.ndarray:
    """A request's sampling key as host words, made once a seed and not
    once a slot a decode step: ``jax.random.PRNGKey`` is two tiny device
    programs and the read back waits for both, 2 ms a slot on a TPU and
    a time that differs from one process to the next (the traced
    ``decode_inputs`` phase showed it).  Callers copy, never write."""
    return np.asarray(jax.random.PRNGKey(seed))


def _sample(logits, key, temp):
    """Per-row sampling: greedy argmax at temp 0.0 (bit-identical to the
    one-shot path), categorical otherwise.  Both branches are computed
    and selected by ``where`` so temperature stays a traced operand."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled = jax.random.categorical(
        key, logits / jnp.maximum(temp, 1e-6), axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0.0, sampled, greedy)


def _corrected_index(new_cache, old_cache, valid_len):
    """Rewrite every ``cache_index`` leaf to ``old + valid_len``: the
    model advanced the index by the full (padded) chunk length; the
    request only wrote ``valid_len`` real tokens.  The pad tail's K/V
    stays in the cache but above the index, where the causal mask hides
    it until real tokens overwrite it — exactness needs only the index.
    (A ``state_*`` leaf has no such tail: the model itself left it at
    the last LIVE token, ``serving/protocol.py``.)"""
    def fix(path, new, old):
        if protocol.leaf_kind(path) == protocol.INDEX:
            return old + jnp.asarray(valid_len, old.dtype)
        return new

    return jax.tree_util.tree_map_with_path(fix, new_cache, old_cache)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _prefill_chunk_prog(params, pool, slot, chunk, valid_len, cfg):
    """Write one fixed-shape prompt chunk into ``slot``'s cache.  Only
    the K/V side effect matters: the engine prefills ``prompt[:-1]``
    through chunks (their logits are never sampled — in decode layout
    the model only materializes the FINAL position's logits, which for a
    padded chunk is a pad row) and routes the last prompt token through
    the regular decode step, whose output IS the first generated token.
    Shapes depend on ``(cfg, chunk_len)`` alone.  ``cfg`` is the
    model's serving layout (``serving/protocol.py``)."""
    # the chunk's padded tail is no token: no expert is read for it
    live = jnp.arange(chunk.shape[-1])[None] < valid_len
    in_pool = getattr(cfg, "apply_in_pool", None)
    if in_pool is not None:
        # the model writes its rows where they lie in the pool: no
        # slot's tree is cut out and put back (serving/protocol.py)
        _, new_pool = in_pool(params, pool, slot, chunk, live=live)

        def fix(path, new, old):
            if protocol.leaf_kind(path) == protocol.INDEX:
                return old.at[slot].add(valid_len.astype(old.dtype))
            return new

        return jax.tree_util.tree_map_with_path(fix, new_pool, pool)
    cache = jax.tree.map(
        lambda leaf: lax.dynamic_index_in_dim(leaf, slot, 0,
                                              keepdims=False), pool)
    _, new_cache = cfg.apply_cached(params, cache, chunk, live=live)
    new_cache = _corrected_index(new_cache, cache, valid_len)
    return jax.tree.map(
        lambda p, c: lax.dynamic_update_index_in_dim(p, c, slot, 0),
        pool, new_cache)


@partial(jax.jit, static_argnames=("cfg", "horizon"), donate_argnums=(1,))
def _decode_step_prog(params, pool, toks, active, keys, counts, temps,
                      fresh=None, prev=None, *, cfg, horizon: int):
    """Advance EVERY slot ``horizon`` decode tokens (vmapped
    single-token steps inside one ``lax.scan`` — each slot carries its
    own cache index, so rotary/mask positions are per-request) and
    freeze inactive slots' caches via the mask.  Inactive slots still
    compute — that is the fixed-shape price that buys zero recompiles —
    but their state is bit-frozen.

    ``horizon`` amortizes the host loop (dispatch + token fetch) over
    several tokens; each token is the SAME per-slot step (and the rng
    folds in the per-request token index), so the emitted stream is
    identical for every horizon — the host truncates a retiring slot's
    surplus tail, and the slot's zero-on-free makes its overrun cache
    writes unobservable.

    The engine dispatches this program before the host has read the
    tokens of the one before it, so a slot's input token comes from
    either side: ``prev`` is the previous program's output, still on
    the device, whose last token row feeds every slot that goes on;
    ``fresh [n_slots]`` marks the slots that take the host's ``toks``
    instead (a slot that joins; every slot when the host knows all
    tokens).  The merge is inside the program: no launch of its own,
    and one executable whatever the mix.  (Without the two operands
    every slot takes ``toks``: the form a caller that lowers the
    program by hand uses.)

    Returns ``(pool, out [horizon + n, n_slots])``: ``horizon`` rows of
    tokens, then the pool's ``stat_*`` leaves as this program left them
    (``kv_pool.pack_stats``: ``n`` rows, none for a model that declares
    no such leaf) in an array that is no leaf of the pool, so the host
    can read them after the next program took the pool by donation.
    """
    def keep_index(path, new, old):
        # Freezing an inactive slot needs only its cache_index: the
        # step's K/V write lands AT the frozen index, where the causal
        # mask hides it until something real overwrites it — the next
        # prefill chunk (mid-admission slots), the next real decode
        # write, or the zero-on-free (free slots).  Masking just the
        # index leaves skips two whole-pool copies per token.  A step
        # that writes its rows inside the decode kernel writes NOTHING
        # for a slot that is not ``live``: the same cache to every
        # reader, since nothing read the hidden row.  (A
        # recurrent layer's state_* leaf comes back as it went in: the
        # slot's token was not ``live``, and the model froze it.)
        if protocol.leaf_kind(path) != protocol.INDEX:
            return new
        m = active.reshape(active.shape + (1,) * (new.ndim - 1))
        return jnp.where(m, new, old)

    def hstep(carry, j):
        pool, toks = carry

        def one(cache, tok, act, key, count, temp):
            logits, cache = cfg.apply_cached(params, cache,
                                             tok[None, None],
                                             live=act[None, None])
            nxt = _sample(logits[0, -1],
                          jax.random.fold_in(key, count + j), temp)
            return cache, nxt

        new_pool, nxt = jax.vmap(one)(pool, toks, active, keys, counts,
                                      temps)
        nxt = jnp.where(active, nxt, toks)
        return (jax.tree_util.tree_map_with_path(keep_index, new_pool,
                                                 pool), nxt), nxt

    device_side = prev
    if device_side is not None:
        toks = jnp.where(fresh, toks, prev[horizon - 1])
    (pool, _), hist = lax.scan(hstep, (pool, toks),
                               jnp.arange(horizon, dtype=jnp.int32))
    return pool, jnp.concatenate([hist, pack_stats(pool)])


@dataclasses.dataclass(frozen=True)
class SpeculativeConfig:
    """Draft model spec for speculative decoding.

    ``variables``/``cfg`` are the DRAFT model (same vocabulary as the
    target; typically much smaller).  Each engine step the draft
    proposes ``lookahead`` tokens through the resident single-token
    step, the target scores the whole window in ONE multi-token forward
    (``cfg.apply_cached(..., all_logits=True)``), and standard
    rejection sampling accepts a prefix of the proposals plus one
    correction/bonus token — so every step emits between 1 and
    ``lookahead + 1`` tokens with the TARGET model's distribution
    (bit-exact greedy argmax at temperature 0; provably unbiased
    sampling otherwise).  The engine reserves ``lookahead`` cache
    positions of headroom per slot (checked at submit)."""

    variables: dict
    cfg: object   # the draft model's config (serving/protocol.py)
    lookahead: int = 4
    weight_quant: str = "none"


@partial(jax.jit, static_argnames=("cfg_t", "cfg_d", "k"),
         donate_argnums=(2, 3))
def _spec_step_prog(params_t, params_d, pool_t, pool_d, toks, active,
                    keys, counts, temps, cfg_t, cfg_d, k: int):
    """One speculative decode step for EVERY slot: draft ``k`` proposals
    (a ``k+1``-step single-token scan — the extra step writes the last
    proposal's K/V so the draft cache index stays position-aligned
    whatever gets accepted), verify the window in one multi-token target
    forward, accept by rejection sampling, and emit ``n_acc + 1`` tokens
    per slot (accepted prefix + correction/bonus).

    Exactness at temperature 0: the accepted tokens ARE the target's
    greedy argmaxes (acceptance literally compares them), and the
    correction token is the argmax after the accepted prefix — the
    emitted stream is bitwise the non-speculative greedy stream, relying
    only on the row-wise bit-stability of the multi-token forward that
    chunked prefill already depends on.  At temperature > 0 the
    accept-with-``min(1, p/q)`` + residual-resample scheme emits tokens
    distributed exactly as target sampling (Leviathan et al.) — streams
    are deterministic per request (salted ``fold_in`` chains off the
    request seed and token count) but follow a different rng chain than
    the non-speculative step.

    Cache discipline: both pools' writes advance ``k + 1`` positions;
    the per-slot index is corrected to ``old + n_emit`` (0 for inactive
    slots), so rejected drafts sit ABOVE the index where the causal
    mask hides them until real tokens overwrite — the same invariant
    padded prefill chunks use.  Returns
    ``(pool_t, pool_d, emitted [cap, k+1], n_emit [cap])``."""
    def one(cache_t, cache_d, tok, act, key, count, temp):
        old_t, old_d = cache_t, cache_d
        tmp = jnp.maximum(temp, 1e-6)

        def dstep(carry, i):
            cache_d, cur = carry
            last, cache_d = cfg_d.apply_cached(params_d, cache_d,
                                               cur[None, None],
                                               live=act[None, None])
            lg = last[0, -1]
            nxt = _sample(lg, jax.random.fold_in(
                jax.random.fold_in(key, 1), count + i), temp)
            return (cache_d, nxt), (cur, nxt, lg)

        (cache_d, _), (window, props, dlg) = lax.scan(
            dstep, (cache_d, tok), jnp.arange(k + 1, dtype=jnp.int32))
        # window = [cur, d_1..d_k] (the tokens whose K/V lands in the
        # cache); props = [d_1..d_{k+1}] (the k+1-th proposal is only
        # drafted so d_k's K/V gets written — it is never considered);
        # dlg[i] is the draft distribution that proposed props[i]
        vlogits, cache_t = cfg_t.apply_cached(
            params_t, cache_t, window[None], all_logits=True,
            live=jnp.broadcast_to(act, window[None].shape))
        vlogits = vlogits[0]                          # [k+1, V]
        tgt = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)

        # greedy acceptance: leading run where draft == target argmax
        hit = (props[:k] == tgt[:k]).astype(jnp.int32)
        acc_greedy = jnp.cumprod(hit).sum()
        # rejection sampling: accept d_i with prob min(1, p_i/q_i)
        p = jax.nn.softmax(vlogits / tmp, axis=-1)    # [k+1, V]
        q = jax.nn.softmax(dlg / tmp, axis=-1)        # [k+1, V]
        idx = jnp.arange(k)
        ratio = (p[idx, props[:k]]
                 / jnp.maximum(q[idx, props[:k]], 1e-30))
        u = jax.vmap(lambda i: jax.random.uniform(jax.random.fold_in(
            jax.random.fold_in(key, 2), count + i)))(idx)
        ok = (u < jnp.minimum(ratio, 1.0)).astype(jnp.int32)
        acc_sample = jnp.cumprod(ok).sum()
        n_acc = jnp.where(temp > 0.0, acc_sample, acc_greedy)

        # correction token after the accepted prefix: residual resample
        # max(0, p - q) on a rejection, plain target sample on the
        # all-accepted bonus (no draft proposed there, q := 0)
        p_row = p[n_acc]
        q_row = jnp.where(n_acc < k, q[n_acc], 0.0)
        resid = jnp.maximum(p_row - q_row, 0.0)
        rsum = resid.sum()
        resid = jnp.where(rsum > 1e-30, resid / jnp.maximum(rsum, 1e-30),
                          p_row)
        corr_sample = jax.random.categorical(
            jax.random.fold_in(jax.random.fold_in(key, 3), count + n_acc),
            jnp.log(jnp.maximum(resid, 1e-38))).astype(jnp.int32)
        corr = jnp.where(temp > 0.0, corr_sample, tgt[n_acc])

        n_emit = jnp.where(act, n_acc + 1, 0)
        emitted = jnp.where(jnp.arange(k + 1) < n_acc, props, corr)
        cache_t = _corrected_index(cache_t, old_t, n_emit)
        cache_d = _corrected_index(cache_d, old_d, n_emit)
        return cache_t, cache_d, emitted, n_emit

    return jax.vmap(one)(pool_t, pool_d, toks, active, keys, counts,
                         temps)


@dataclasses.dataclass(eq=False)
class _Flight:
    """A decode program the device holds and the host has not read:
    the requests it advances by slot, its small output (token rows,
    then ``stat_*`` rows; still on the device), its ``launch=`` number,
    whether another program was in flight when it was dispatched, and
    the positions it attended and streamed at the lengths it ran at
    (``on_decode_step``'s; empty where no registry counts)."""
    decoding: Dict[int, Request]
    out: jax.Array
    launch: int
    ahead: bool
    attended: tuple = ()
    streamed: tuple = ()


class ServingEngine:
    """Continuous-batching serving loop over a :class:`SlotPool`.

    Args:
      variables: ``{"params": ...}`` (full-precision, or the
        ``quantize_llama_params`` tree with ``weight_quant`` set — same
        contract as ``llama_generate``).
      cfg: model config, a :class:`~bluefog_tpu.serving.protocol.
        ServedModel` (training layout fine; normalized through its
        ``serving_layout``).
      capacity: resident request slots (= decode batch).
      max_len: per-slot cache length; every request needs
        ``len(prompt) + max_new_tokens <= max_len`` (checked at submit).
      prefill_chunk: fixed prompt-chunk length; must divide ``max_len``
        (chunk windows then never cross the cache end — an overrunning
        ``dynamic_update_slice`` start would CLAMP, silently corrupting
        near-``max_len`` prompts).  Smaller chunks bound how long
        running decodes stall behind one admission; larger chunks
        finish prefill in fewer steps.
      decode_horizon: tokens every active slot advances per host
        iteration (one inner ``lax.scan``).  1 = lowest TTFT and
        per-token scheduling; larger values amortize host dispatch over
        the horizon (throughput mode — retirements, admissions, and
        deadline checks happen at horizon boundaries).  The emitted
        streams are identical for every horizon.
      prefill_budget: max prefill CHUNKS one step may run (admissions
        continue until the budget or the pool is exhausted).  1
        (default) bounds per-step admission work to one chunk — the
        lowest decode jitter; raise it alongside ``decode_horizon`` so
        admission keeps the pool full in throughput mode.
      max_queue: backpressure bound — submits beyond it raise
        :class:`RequestRejected` with the queue depth attached.
      clock: injectable monotonic clock (tests drive virtual time; the
        Poisson bench uses the default ``time.monotonic``).
      decode_attn: attention lowering of the resident single-token
        step: "xla" (default) reads every reserved position of every
        slot behind a mask; "pallas" fetches the cache blocks at or
        before each slot's position (``parallel/pallas_decode.py``; the
        map over slots folds into the kernel's batch axis); "auto"
        takes the kernel where ``generate.decode_config`` finds a TPU,
        a full-precision cache and a length it can tile.
      registry: explicit metrics registry for this engine's
        :class:`ServingMetrics` (default: the global observe registry).
        A multi-replica fleet gives each replica its own so the router
        can read per-replica occupancy/queue/TTFT signals
        (:mod:`bluefog_tpu.serving.fleet`).
      zero_on_free: passed to :class:`SlotPool` (default: the
        ``BLUEFOG_KV_ZERO_ON_FREE`` env knob, off).
      prefix_cache: ``True`` builds a
        :class:`~bluefog_tpu.serving.prefix_cache.PrefixCache` sized by
        ``prefix_cache_bytes`` (default ``BLUEFOG_PREFIX_CACHE_MB``);
        or pass an instance to share/inspect it.  Admission then
        restores any chain-hash-matched prompt chunks by device copy
        and prefills only the novel tail — bit-exact vs cold prefill.
      speculative: a :class:`SpeculativeConfig` — swaps the resident
        decode step for the draft/verify program pair.  Requires
        ``decode_horizon=1`` (a speculative step already advances up to
        ``lookahead+1`` tokens) and reserves ``lookahead`` cache
        positions of headroom per request (checked at submit).
    """

    def __init__(self, variables, cfg, *, capacity: int,
                 max_len: int, prefill_chunk: int = 32,
                 decode_horizon: int = 1, prefill_budget: int = 1,
                 kv_quant: str = "none", weight_quant: str = "none",
                 max_queue: int = 64,
                 clock: Optional[Callable[[], float]] = None,
                 decode_attn: str = "xla", registry=None,
                 zero_on_free: Optional[bool] = None,
                 prefix_cache=False,
                 prefix_cache_bytes: Optional[int] = None,
                 speculative: Optional[SpeculativeConfig] = None):
        from bluefog_tpu.models.quant import is_quantized_params

        if (weight_quant != "none") != is_quantized_params(variables):
            raise ValueError(
                "weight_quant='int8'/'w8a8' requires params converted by "
                "quantize_llama_params (and full-precision params require "
                "weight_quant='none'); got a mismatched tree")
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk ({prefill_chunk}) must be "
                             ">= 1")
        if max_len % prefill_chunk != 0:
            # chunk writes land at multiples of prefill_chunk, so this
            # guarantees no chunk's fixed-size window crosses max_len —
            # XLA CLAMPS an out-of-range dynamic_update_slice start,
            # which would silently overwrite earlier K/V positions for
            # near-max_len prompts instead of erroring
            raise ValueError(
                f"prefill_chunk ({prefill_chunk}) must divide max_len "
                f"({max_len}) so no chunk window crosses the cache end")
        if decode_horizon < 1:
            raise ValueError(f"decode_horizon ({decode_horizon}) must be "
                             ">= 1")
        if prefill_budget < 1:
            raise ValueError(f"prefill_budget ({prefill_budget}) must be "
                             ">= 1")
        if speculative is not None:
            if decode_horizon != 1:
                raise ValueError(
                    "speculative decoding requires decode_horizon=1 (a "
                    "speculative step already advances up to lookahead+1 "
                    f"tokens); got decode_horizon={decode_horizon}")
            if speculative.lookahead < 1:
                raise ValueError(
                    f"lookahead ({speculative.lookahead}) must be >= 1")
            if speculative.cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab ({speculative.cfg.vocab_size}) != "
                    f"target vocab ({cfg.vocab_size}) — speculative "
                    "decoding needs one tokenizer")
            if ((speculative.weight_quant != "none")
                    != is_quantized_params(speculative.variables)):
                raise ValueError(
                    "SpeculativeConfig.weight_quant does not match the "
                    "draft param tree (quantize_llama_params contract)")
        # the most tokens one cached call writes: what a window
        # layer's ring needs beyond its window
        call = prefill_chunk if speculative is None else max(
            prefill_chunk, speculative.lookahead + 1)
        self.cfg = cfg.serving_layout(max_len, chunk=call,
                                      kv_quant=kv_quant,
                                      weight_quant=weight_quant,
                                      decode_attn=decode_attn)
        from bluefog_tpu.serving.prefix_cache import PrefixCache

        prefix = None
        # NB: isinstance first — an EMPTY PrefixCache is falsy (__len__)
        if isinstance(prefix_cache, PrefixCache) or prefix_cache:
            prefix = (prefix_cache if isinstance(prefix_cache, PrefixCache)
                      else PrefixCache(prefill_chunk, prefix_cache_bytes))
            if prefix.chunk != prefill_chunk:
                raise ValueError(
                    f"prefix cache chunk ({prefix.chunk}) != prefill_chunk"
                    f" ({prefill_chunk}) — hashes must match the chunk "
                    "grid prefill writes")
        self.pool = SlotPool(cfg, capacity, max_len, kv_quant=kv_quant,
                             zero_on_free=zero_on_free, prefix=prefix,
                             chunk=call)
        self._kinds = self.cfg.cache_kinds()
        # optional in the protocol: what a chunk rebuilds before it attends
        self._rebuilt = getattr(self.cfg, "rebuilt_positions", None)
        # and what it reads to attend, by kind of leaf
        self._chunk_streamed = getattr(self.cfg, "chunk_streamed_positions",
                                       None)
        # and around how many sublayers a token's residual streams are mixed
        self._mixed_sublayers = getattr(self.cfg, "mixed_sublayers", 0)
        # and how many of its layers keep a recurrent state
        self._state_layers = getattr(self.cfg, "state_layers", 0)
        # and the slots x layers whose state a single-token step reads
        self._state_streamed = getattr(self.cfg, "state_streamed_steps",
                                       None)
        # and how many layer applications a token makes, where it makes
        # several passes through its layers
        self._loop_steps = getattr(self.cfg, "loop_steps", 1)
        self._loop_layers = (self._loop_steps * self.cfg.n_layers
                             if self._loop_steps > 1 else 0)
        self._spec = speculative
        self._draft_pool: Optional[SlotPool] = None
        self._draft_params = None
        self.draft_cfg = None
        if speculative is not None:
            from bluefog_tpu.serving.prefix_cache import PrefixCache

            dprefix = (PrefixCache(prefill_chunk,
                                   prefix_cache_bytes)
                       if prefix is not None else None)
            self.draft_cfg = speculative.cfg.serving_layout(
                max_len, chunk=call, kv_quant=kv_quant,
                weight_quant=speculative.weight_quant,
                decode_attn=decode_attn)
            # the draft pool mirrors the target pool's alloc/free order,
            # so slot i means the same request in both trees
            self._draft_pool = SlotPool(speculative.cfg, capacity,
                                        max_len, kv_quant=kv_quant,
                                        zero_on_free=zero_on_free,
                                        prefix=dprefix, chunk=call)
            self._draft_params = speculative.variables["params"]
            for pool in (self.pool, self._draft_pool):
                if pool.state_leaves:
                    raise ValueError(
                        f"cache leaf {pool.state_leaves[0]} is a recurrent "
                        "state: the speculative step rolls a slot's index "
                        "back over the drafts it rejects, and a state leaf "
                        "does not roll back with it")
        self.scheduler = FifoScheduler(max_queue=max_queue)
        self.metrics = ServingMetrics(registry=registry)
        self.metrics.on_pool(self.pool.cache_bytes(), capacity)
        if getattr(self.cfg, "n_group", 1) > 1:
            self.metrics.on_expert_groups(self.cfg.n_group,
                                          self.cfg.topk_group)
        if self._mixed_sublayers:
            self.metrics.on_residual_streams(self.cfg.residual_streams)
        if self._loop_layers:
            self.metrics.on_loop_steps(self._loop_steps)
        self.prefill_chunk = prefill_chunk
        self.decode_horizon = decode_horizon
        self.prefill_budget = prefill_budget
        self.clock = clock or time.monotonic
        self._params = variables["params"]
        self._running: Dict[int, Request] = {}   # slot -> request
        self._admitting: Optional[Request] = None  # mid-prefill request
        self._draining = False     # drain(): admission permanently off
        # device programs dispatched so far (``launch=`` of the spans
        # that dispatch one: the k-th span is the k-th execution)
        self._launches = 0
        # the plain decode program the device holds and the host has
        # not read (None: the host knows every token), and the output
        # of the newest one, the next one's ``prev`` operand
        self._flight: Optional[_Flight] = None
        self._newest_out = jnp.zeros(
            (decode_horizon + self.pool.stat_rows, capacity), jnp.int32)
        self._step_spans: list = []  # the current step's phase spans
        self._drain_flushed = 0    # KV chunks flushed to the prefix
        # cache on behalf of migrating/completing drain residents
        self._resident = self._build_resident()

    # -- submission ---------------------------------------------------- #
    def submit(self, request: Request) -> Request:
        """Enqueue a request.  Raises :class:`RequestRejected` under
        backpressure (queue at ``max_queue``) and ``ValueError`` when the
        request cannot fit a slot at all."""
        total = request.prompt.size + request.max_new_tokens
        if self._spec is not None:
            # a speculative step may write lookahead draft positions
            # past the final emitted token; reserving that headroom at
            # admission keeps every window inside the slot (an
            # overrunning dynamic_update_slice start would CLAMP and
            # silently overwrite real K/V)
            total += self._spec.lookahead
        if total > self.pool.max_len:
            # refusal paths agree: a request the engine will never run
            # is terminal (done == True) AND counted in n_rejected,
            # whichever way it was refused — caller loops polling
            # req.done must not wait on a phantom, and a dashboard
            # must see every refusal
            request.state = REJECTED
            self.metrics.on_reject(request.rid, self.clock())
            raise ValueError(
                f"request needs {total} cache positions but slots hold "
                f"{self.pool.max_len} (prompt {request.prompt.size} + "
                f"max_new_tokens {request.max_new_tokens}"
                + (f" + speculative headroom {self._spec.lookahead}"
                   if self._spec is not None else "") + ")")
        now = self.clock()
        if self._draining:
            request.state = REJECTED
            self.metrics.on_reject(request.rid, now)
            raise RequestRejected("engine draining",
                                  queue_depth=self.scheduler.queue_depth,
                                  max_queue=self.scheduler.max_queue)
        try:
            self.scheduler.submit(request)
        except RequestRejected:
            request.state = REJECTED
            self.metrics.on_reject(request.rid, now)
            raise
        # a request one replica refused may be accepted by the next in
        # the router's walk — acceptance supersedes the earlier REJECTED
        request.state = QUEUED
        self.metrics.on_submit(request.rid, now)
        return request

    def cancel(self, request: Request) -> bool:
        """Cancel a queued or running request (idempotent; False once the
        request already retired)."""
        if request.done:
            return False
        if self.scheduler.cancel(request):
            request.state = CANCELLED
            self.metrics.on_retire(request.rid, self.clock(), CANCELLED)
            return True
        request._cancel = True  # picked up at the next step boundary
        return True

    # -- the serving loop --------------------------------------------- #
    def step(self) -> bool:
        """One engine iteration: shed/cancel, admit + one prefill chunk,
        dispatch the next decode program over all active slots, then
        read the tokens of the decode program the call before
        dispatched.  Returns True while there is live work (queued,
        prefilling, decoding, or a decode program in flight).

        A plain engine runs ONE DECODE PROGRAM AHEAD of what the host
        knows: the program dispatched here takes the tokens of the one
        in flight off the device, so the host's work of a step (this
        method, and the caller's between two calls) runs beside the
        device's and the step lasts the longer of the two.  A call
        therefore returns with a program in flight, and the tokens it
        emitted are the program's before: ``req.tokens`` is complete
        once ``step`` returns False, or after :meth:`collect`.  What
        only the tokens can say (an EOS) the host learns one program
        late: the slot has run once more, that token is dropped
        (``bf_serving_decode_overrun_slots_total``) and the cache write
        sits above an index that the slot's release resets.  A
        speculative engine dispatches and reads within the call: how
        far a slot advanced is itself an output of its program.

        The whole iteration is one ``step`` span on the ``engine`` track
        with its phases inside — ``admit``, ``prefill_chunk``,
        ``decode_inputs``, ``decode_dispatch``, ``token_fetch``,
        ``emit`` (``bf.engine.*`` in a profiler trace): per step, never
        per token or per slot."""
        now = self.clock()
        spans = self._step_spans
        spans.clear()
        with self.metrics.span("step") as step_span:
            decoding = self._step_phases(now)
        # the step's wall time is the span's own two stamps (real time:
        # the injected clock may be virtual) — feeds the fleet
        # step-time view; so are its phases' seconds
        phases: Dict[str, float] = {}
        for sp in spans:
            phases[sp.name] = phases.get(sp.name, 0.0) + sp.seconds
        self.metrics.on_step(self.pool.occupancy(),
                             self.scheduler.queue_depth,
                             step_span.seconds, now=now, phases=phases,
                             decoding=decoding)
        return self.busy

    @property
    def busy(self) -> bool:
        """Whether there is live work: a request queued, prefilling or
        decoding, or a decode program in flight."""
        return bool(self._running or self._admitting
                    or self.scheduler.queue_depth
                    or self._flight is not None)

    def collect(self) -> None:
        """Read the decode program in flight, if any: emit its tokens
        and retire what it finished.  Afterwards the host knows every
        token the device has computed (``req.tokens`` of a live request
        is as long as its cache).  :meth:`step` does this for the
        program before the one it dispatches; :meth:`drain`, failover
        and a caller that reads ``req.tokens`` between steps do it for
        the last one."""
        flight, self._flight = self._flight, None
        if flight is not None:
            self._collect(flight)

    def _span(self, name: str, **args):
        """A phase of the current step: ``metrics.span``, kept so that
        the step's end can read the phase's seconds off its stamps."""
        sp = self.metrics.span(name, **args)
        self._step_spans.append(sp)
        return sp

    def _step_phases(self, now: float) -> int:
        """The step's phases; returns the slots it decoded."""
        span = self._span
        # 1-4. shedding, cancellations, admission + chunked prefill,
        #      bounded by the per-step chunk budget (prefill work is
        #      what stalls running decodes, so IT is what gets budgeted
        #      — not admissions).  With the default budget of one the
        #      admit span runs once; a larger budget admits again after
        #      a prefill that finished inside the step.
        with span("admit") as admit_span:
            self._shed(now)
            admit_span.set(admitted=self._admit(now))
        chunks = 0
        while self._admitting is not None and chunks < self.prefill_budget:
            self._prefill_one_chunk(self._admitting)
            chunks += 1
            if self._admitting is None and chunks < self.prefill_budget:
                with span("admit") as admit_span:
                    admit_span.set(admitted=self._admit(now))
        # 5. one decode program over every active slot
        if self._spec is not None:
            decoding = {s: r for s, r in self._running.items()
                        if r.state == DECODE}
            if decoding:
                self._spec_decode_step(decoding)
            return len(decoding)
        return self._decode_step()

    def _shed(self, now: float) -> None:
        # 1. deadline shedding in the queue (zero device cost)
        for req in self.scheduler.expire(now):
            req.state = CANCELLED
            self.metrics.on_retire(req.rid, now, CANCELLED)
        # 2. running cancellations (explicit or deadline) — including a
        #    request still mid-prefill, whose slot must come back too
        live = list(self._running.values())
        if self._admitting is not None:
            live.append(self._admitting)
        for req in live:
            if req._cancel or (req.deadline is not None
                               and now >= req.deadline):
                self._retire(req, CANCELLED, now)

    def _admit(self, now: float) -> int:
        """Give slots to queued requests until one needs a prefill (it
        becomes ``_admitting``) or none can be admitted.  Returns the
        number admitted."""
        admitted = 0
        while self._admitting is None:
            if self._draining:
                break  # drain(): the current prefill finishes, but
                # nothing new leaves the queue
            if self.pool.n_free == 0:
                break
            req = self.scheduler.admit(now)
            if req is None:
                break
            req.slot = self.pool.alloc()
            if self._draft_pool is not None:
                dslot = self._draft_pool.alloc()
                assert dslot == req.slot, (dslot, req.slot)
            self.metrics.on_admit(req.rid, now)
            admitted += 1
            # a failed-over request resumes with emitted tokens: its
            # prefill region is (prompt ‖ tokens)[:-1] — the same
            # chunk grid the original prefill stashed, so the replay
            # restores cached chunks and computes only the tail
            n_ctx = req.prompt.size + len(req.tokens)
            if n_ctx > 1:
                self._restore_prefix(req)  # no-op without the cache
                if req._prefill_pos < n_ctx - 1:
                    req.state = PREFILL
                    self._admitting = req
                    break
                # the whole prefill region came out of the prefix cache
                # — straight to decode, zero prefill compute spent
            # (a single-token prompt has nothing to prefill either: the
            # decode step consumes the whole prompt directly)
            req.state = DECODE
            self._running[req.slot] = req
        return admitted

    def run(self, max_steps: int = 100_000) -> None:
        """Drive :meth:`step` until idle (drain the queue and every
        slot); ``max_steps`` guards against a caller submitting faster
        than the loop drains."""
        for _ in range(max_steps):
            if not self.step():
                return
        raise RuntimeError(f"engine still busy after {max_steps} steps")

    def drain(self, handoff: Optional[Callable[[Request], object]] = None,
              max_steps: int = 100_000) -> Dict[str, int]:
        """Retire this replica cleanly — the elastic-serving primitive.

        Admission stops permanently (subsequent :meth:`submit` raises
        :class:`RequestRejected`; the admission loop stops popping the
        queue).  Then:

        * with a ``handoff`` callable (e.g. ``router.submit``): every
          resident request flushes its written K/V chunks to the shared
          prefix cache, retires here with outcome ``failover``, resets
          to QUEUED **keeping its emitted tokens**, and is handed off —
          the target replica re-prefills ``prompt ‖ tokens`` (restored
          chunks + novel tail) and continues bit-exactly.  Queued
          requests hand off as-is.
        * without one: queued requests are REJECTED (backpressure — the
          caller resubmits elsewhere), residents run to completion in
          place, flushing their chunks as they retire.

        Host-side control flow only: no new programs, no recompiles.
        Returns a summary dict (``handed_off`` / ``completed`` /
        ``rejected_queue`` / ``cancelled_queue`` / ``flushed_chunks``).
        """
        self._draining = True
        if handoff is not None:
            # the residents leave with every token the device computed
            # for them (and _flush_resident with the chunks they wrote)
            self.collect()
        now = self.clock()
        summary = {"handed_off": 0, "completed": 0, "rejected_queue": 0,
                   "cancelled_queue": 0, "flushed_chunks": 0}
        # queue: deadline-expired requests shed exactly as step() would
        for req in self.scheduler.expire(now):
            req.state = CANCELLED
            self.metrics.on_retire(req.rid, now, CANCELLED)
            summary["cancelled_queue"] += 1
        queued = self.scheduler.drain()
        if handoff is None:
            for req in queued:
                req.state = REJECTED
                self.metrics.on_reject(req.rid, now)
                self.metrics.on_retire(req.rid, now, REJECTED)
                summary["rejected_queue"] += 1
            residents = {r.rid: r for r in self._running.values()}
            if self._admitting is not None:
                residents[self._admitting.rid] = self._admitting
            for _ in range(max_steps):
                if not self.step():
                    break
            else:
                raise RuntimeError(
                    f"drain still busy after {max_steps} steps")
            summary["completed"] = sum(
                1 for r in residents.values() if r.state == COMPLETED)
        else:
            residents = sorted(self._running.values(),
                               key=lambda r: r.slot)
            if self._admitting is not None:
                residents = sorted(residents + [self._admitting],
                                   key=lambda r: r.slot)
            for req in residents + queued:
                # _retire flushes the written chunks (self._draining is
                # set) and releases the slot; reset_for_resume returns
                # the request to QUEUED with its tokens intact
                self._retire(req, FAILOVER, now)
                self.metrics.on_failover(req.rid, now)
                req.reset_for_resume()
                handoff(req)
                summary["handed_off"] += 1
        summary["flushed_chunks"] = self._drain_flushed
        from bluefog_tpu.observe.blackbox import record_decision

        record_decision(
            "serving", "drain", step=-1,
            telemetry={k: int(v) for k, v in sorted(summary.items())},
            winner="handoff" if handoff is not None else "complete")
        return summary

    def _build_resident(self) -> Dict[str, tuple]:
        """The engine's resident data-plane executables, fixed at build
        time: ``{name: (jitted_fn, example_args_thunk, static_kwargs)}``.
        A plain engine residents the prefill chunk + decode step; a
        speculative engine swaps the decode step for the draft-prefill /
        draft+verify pair.  :meth:`profile` (and any future
        introspection) enumerates THIS dict instead of hardcoding the
        program list, so new programs are profiled without another
        special case.  (The slot-housekeeping scatters — zero /
        index-reset on free — are deliberately not listed: they are
        O(slot) bookkeeping, not the serving data plane.)"""
        cap = self.pool.capacity

        def slot_args():
            return (jnp.zeros((cap,), jnp.int32), jnp.zeros((cap,), bool),
                    jnp.zeros((cap, 2), jnp.uint32),
                    jnp.zeros((cap,), jnp.int32),
                    jnp.zeros((cap,), jnp.float32))

        resident: Dict[str, tuple] = {
            "prefill_chunk": (
                _prefill_chunk_prog,
                lambda: (self._params, self.pool.cache, jnp.int32(0),
                         jnp.zeros((1, self.prefill_chunk), jnp.int32),
                         jnp.int32(0)),
                {"cfg": self.cfg}),
        }
        if self._spec is None:
            resident["decode_step"] = (
                _decode_step_prog,
                lambda: (self._params, self.pool.cache, *slot_args(),
                         jnp.zeros((cap,), bool), self._newest_out),
                {"cfg": self.cfg, "horizon": self.decode_horizon})
        else:
            resident["draft_prefill_chunk"] = (
                _prefill_chunk_prog,
                lambda: (self._draft_params, self._draft_pool.cache,
                         jnp.int32(0),
                         jnp.zeros((1, self.prefill_chunk), jnp.int32),
                         jnp.int32(0)),
                {"cfg": self.draft_cfg})
            resident["spec_step"] = (
                _spec_step_prog,
                lambda: (self._params, self._draft_params,
                         self.pool.cache, self._draft_pool.cache,
                         *slot_args()),
                {"cfg_t": self.cfg, "cfg_d": self.draft_cfg,
                 "k": self._spec.lookahead})
        return resident

    def profile(self, **kw) -> Dict[str, "object"]:
        """HLO-attributed :class:`~bluefog_tpu.observe.StepProfile` of
        EVERY resident device program — enumerated generically from the
        build-time registry (``prefill_chunk`` + ``decode_step`` for a
        plain engine; ``prefill_chunk`` + ``draft_prefill_chunk`` +
        ``spec_step`` for a speculative one), via
        :func:`bluefog_tpu.observe.profile_step`.  AOT — compiles
        (hitting the jit cache when the engine already ran) but executes
        nothing, so it is safe on a live engine.  Keyword args
        (``step_seconds``, chip figures, ...) pass through; the serving
        bench emits these instead of hand-rolled cost dicts."""
        from bluefog_tpu.observe import profile_step

        return {name: profile_step(fn, *args(),
                                   name=f"serving.{name}", **static, **kw)
                for name, (fn, args, static) in self._resident.items()}

    # -- internals ----------------------------------------------------- #
    @staticmethod
    def _context(req: Request) -> np.ndarray:
        """The request's full prefill context: the prompt, plus any
        tokens already emitted on a previous replica (failover resume).
        The decode step then consumes context[-1] and continues the
        per-request rng fold chain at ``len(tokens)`` — bit-equal to
        never having moved."""
        if not req.tokens:
            return req.prompt
        return np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)])

    def _restore_prefix(self, req: Request) -> int:
        """Admission-time prefix reuse: chain-hash the prompt's full
        chunks and device-copy the longest cached run into the slot
        (both pools, lockstep, under speculation — target and draft K/V
        are different tensors for the same tokens, so the usable prefix
        is the MINIMUM of the two matches).  Advances ``_prefill_pos``
        past the restored region; restores do not consume prefill
        budget (they replace the model forward, not ride next to it)."""
        if self.pool.prefix is None:
            return 0
        keys = self.pool.prefix.chunk_keys(self._context(req))
        req._prefix_keys = keys
        if not keys:
            return 0
        matched = self.pool.prefix.match(keys)
        if self._draft_pool is not None:
            matched = min(matched,
                          self._draft_pool.prefix.match(keys))
        if matched:
            self.pool.restore_prefix(req.slot, keys, matched)
            if self._draft_pool is not None:
                self._draft_pool.restore_prefix(req.slot, keys, matched)
            req._prefill_pos = matched * self.prefill_chunk
            self.metrics.on_prefix_restore(
                req.rid, matched, matched * self.prefill_chunk)
        return matched

    def _prefill_one_chunk(self, req: Request) -> None:
        # chunks cover prompt[:-1] — the K/V everyone after needs; the
        # final prompt token goes through the decode step below, whose
        # logits yield the request's first generated token (the exact
        # split the one-shot path computes inside one big call)
        c = self.prefill_chunk
        pos = req._prefill_pos
        with self._span("prefill_chunk", rid=req.rid, slot=req.slot,
                        start=int(pos),
                        launch=self._launches) as chunk_span:
            self._launches += 1 if self._draft_pool is None else 2
            ctx = self._context(req)
            n_prefill = ctx.size - 1
            valid = min(c, n_prefill - pos)
            chunk_span.set(tokens=int(valid))
            chunk = np.zeros((1, c), np.int32)
            chunk[0, :valid] = ctx[pos:pos + valid]
            chunk = jnp.asarray(chunk)
            self.pool.cache = _prefill_chunk_prog(
                self._params, self.pool.cache, jnp.int32(req.slot),
                chunk, jnp.int32(valid), cfg=self.cfg)
            if self._draft_pool is not None:
                # the draft model needs the SAME context in its own
                # cache; its chunk rides the target's budget slot (one
                # admission unit of work, two trees)
                self._draft_pool.cache = _prefill_chunk_prog(
                    self._draft_params, self._draft_pool.cache,
                    jnp.int32(req.slot), chunk, jnp.int32(valid),
                    cfg=self.draft_cfg)
            self.metrics.on_prefill_chunk(
                int(valid), self._rebuilt(pos, c) if self._rebuilt else 0,
                self._chunk_streamed(pos, c) if self._chunk_streamed else (),
                mixed=int(valid) * self._mixed_sublayers,
                state=int(valid) * self._state_layers,
                looped=int(valid) * self._loop_layers)
            if (valid == c and req._prefix_keys
                    and pos // c < len(req._prefix_keys)):
                # a FULL cold chunk just landed on the chunk grid —
                # stash its K/V while it provably matches the chain hash
                key = req._prefix_keys[pos // c]
                self.pool.stash_chunk(req.slot, key, pos)
                if self._draft_pool is not None:
                    self._draft_pool.stash_chunk(req.slot, key, pos)
        req._prefill_pos = pos + valid
        if req._prefill_pos < n_prefill:
            return  # more chunks to go; decodes keep running meanwhile
        self._admitting = None
        self._running[req.slot] = req
        req.state = DECODE

    def _decode_inputs(self, decoding: Dict[int, Request],
                       lengths: Optional[Dict[int, int]] = None) -> tuple:
        """The decode programs' per-slot operands, on the device:
        (tokens, active, rng keys, token counts, temperatures).
        ``lengths`` (the plain program): each slot's token count WITH
        the tokens of the program in flight, which the host does not
        hold yet.  A slot that is further than ``req.tokens`` says has
        its input token on the device; a sixth operand marks every
        OTHER slot as taking the host's."""
        cap = self.pool.capacity
        toks = np.zeros((cap,), np.int32)
        active = np.zeros((cap,), bool)
        keys = np.zeros((cap, 2), np.uint32)
        counts = np.zeros((cap,), np.int32)
        temps = np.zeros((cap,), np.float32)
        fresh = np.zeros((cap,), bool)
        for slot, req in decoding.items():
            active[slot] = True
            keys[slot] = _rng_key(req.seed)
            known = len(req.tokens)
            counts[slot] = known if lengths is None else lengths[slot]
            temps[slot] = req.temperature
            if counts[slot] == known:
                # first step after prefill consumes the LAST prompt
                # token (writing its K/V and sampling the first
                # generated token); afterwards the request's own stream
                # feeds back
                toks[slot] = req.tokens[-1] if known else req.prompt[-1]
                fresh[slot] = True
        operands = (toks, active, keys, counts, temps)
        if lengths is not None:
            operands += (fresh,)
        return tuple(jnp.asarray(a) for a in operands)

    def _emit(self, decoding: Dict[int, Request], tokens_of) -> int:
        """The per-token loop of a decode step: append each slot's run
        (``tokens_of(slot)``), publish, finish and retire.  A run stops
        at a retirement: surplus horizon or accepted tokens for a
        retired slot are discarded (its cache index is reset on free, so
        their cache writes are unobservable).  So is the whole run of a
        slot whose request left it while the program was in flight (an
        EOS in the program before, a cancel, a shed): an overrun.
        Returns the tokens emitted."""
        with self._span("emit") as emit_span:
            now = self.clock()
            emitted = overrun = 0
            for slot, req in decoding.items():
                if self._running.get(slot) is not req:
                    overrun += 1
                    continue
                for token in tokens_of(slot):
                    first = not req.tokens
                    req.tokens.append(int(token))
                    emitted += 1
                    if first:
                        self.metrics.on_first_token(req.rid, now)
                    else:
                        self.metrics.on_token(req.rid, now)
                    if self._maybe_finish(req):
                        break
            self.metrics.on_tokens(emitted, overrun)
            emit_span.set(tokens=emitted)
        return emitted

    def _fetch(self, fetch_span, first, tree):
        """``jax.device_get(tree)``, the step's one host sync, inside
        the open ``token_fetch`` span.  Where a tracer takes the span
        it is split in two: ``device_wait``, the wait for ``first`` (the
        step's token array), and ``host_copy``, the transfer and
        conversion of every leaf.  The copies are queued before the
        host blocks, as ``device_get`` queues them, so the split adds
        no round trip of its own."""
        if not fetch_span.recording:
            return jax.device_get(tree)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        for leaf in leaves:
            leaf.copy_to_host_async()
        with self._span("device_wait"):
            first.block_until_ready()
        with self._span("host_copy") as copy_span:
            # (leaf by leaf, and the bytes off the host's arrays: a
            # tree_map and eleven ``nbytes`` of device arrays cost more
            # than the two spans)
            host = [np.asarray(leaf) for leaf in leaves]
            copy_span.set(leaves=len(host),
                          bytes=sum(a.nbytes for a in host))
        return treedef.unflatten(host)

    def _decode_step(self) -> int:
        """The plain decode path, one program ahead: dispatch the next
        program, THEN read the one in flight.  Returns the slots of the
        program it read."""
        flight = self._flight
        self._flight = self._launch(flight)
        if flight is None:
            return 0
        self._collect(flight)
        return len(flight.decoding)

    def _launch(self, flight: Optional[_Flight]) -> Optional[_Flight]:
        """Dispatch a decode program over every slot that goes on, from
        what the host knows without the tokens of ``flight`` (the
        program the device still holds, or None): a request goes on
        unless ``flight`` takes it to ``max_new_tokens``, which the host
        can count.  None when no slot goes on."""
        ahead = flight.decoding if flight is not None else {}
        horizon = self.decode_horizon
        decoding, lengths = {}, {}
        for slot, req in self._running.items():
            n = len(req.tokens) + (horizon if ahead.get(slot) is req else 0)
            if req.state == DECODE and n < req.max_new_tokens:
                decoding[slot], lengths[slot] = req, n
        if not decoding:
            return None
        span = self._span
        with span("decode_inputs", slots=len(decoding)):
            operands = self._decode_inputs(decoding, lengths)
        launch = self._launches
        with span("decode_dispatch", launch=launch):
            self._launches += 1
            self.pool.cache, out = _decode_step_prog(
                self._params, self.pool.cache, *operands,
                self._newest_out, cfg=self.cfg, horizon=horizon)
            self._newest_out = out
        launched = _Flight(decoding, out, launch, ahead=flight is not None)
        if self.metrics.publishing:
            # the query of a slot sits on its last token and sees every
            # position up to itself
            positions = [-1] * self.pool.capacity
            for slot, req in decoding.items():
                positions[slot] = req.prompt.size + lengths[slot] - 1
            launched.attended = protocol.attended_positions(
                self._kinds, [p + 1 for p in positions if p >= 0])
            launched.streamed = self.cfg.streamed_positions(positions)
        return launched

    def _collect(self, flight: _Flight) -> None:
        """Wait for ``flight``'s tokens, count it, emit."""
        decoding = flight.decoding
        with self._span("token_fetch", launch=flight.launch) as fetch_span:
            # [horizon + stat rows, cap] — the per-step host sync:
            # tokens stream; a model's stat_* leaves come below them
            out = self._fetch(fetch_span, flight.out, flight.out)
        hist = out[:self.decode_horizon]
        if self.pool.has_stats and self.metrics.publishing:
            stats = self.pool.unpack_stats(out[self.decode_horizon:])
            if "stat_experts" in stats:
                self.metrics.on_expert_choices(
                    stats["stat_experts"], sorted(decoding), self.cfg.held)
            self.metrics.on_expert_rows(stats.get("stat_expert_rows", ()))
            self.metrics.on_exit_pdf(stats.get("stat_exit_pdf", ()),
                                     sorted(decoding))
        self._emit(decoding, lambda slot: hist[:, slot])
        self.metrics.on_decode_step(
            len(decoding), flight.attended, flight.streamed,
            mixed=len(decoding) * self._mixed_sublayers,
            ahead=flight.ahead, state=len(decoding) * self._state_layers,
            state_streamed=(self._state_streamed(len(decoding),
                                                 self.pool.capacity)
                            if self._state_streamed else 0),
            looped=len(decoding) * self._loop_layers)

    def _spec_decode_step(self, decoding: Dict[int, Request]) -> None:
        """The speculative twin of :meth:`_decode_step`: one resident
        draft/verify program advances every active slot by 1 to
        ``lookahead+1`` tokens.  The host appends each slot's emitted
        run with the same EOS/budget truncation the plain path
        applies."""
        span = self._span
        with span("decode_inputs", slots=len(decoding)):
            operands = self._decode_inputs(decoding)
        with span("decode_dispatch", launch=self._launches):
            self._launches += 1
            (self.pool.cache, self._draft_pool.cache, hist,
             n_emit) = _spec_step_prog(
                self._params, self._draft_params, self.pool.cache,
                self._draft_pool.cache, *operands, cfg_t=self.cfg,
                cfg_d=self.draft_cfg, k=self._spec.lookahead)
        with span("token_fetch") as fetch_span:
            # [cap, lookahead+1] and [cap]
            hist, n_emit = self._fetch(fetch_span, hist, (hist, n_emit))
        emitted = self._emit(
            decoding, lambda slot: hist[slot, :int(n_emit[slot])])
        self.metrics.on_decode_step(len(decoding))
        self.metrics.on_spec_step(len(decoding), emitted)

    def _maybe_finish(self, req: Request) -> bool:
        hit_eos = (req.eos_id is not None
                   and req.tokens[-1] == req.eos_id)
        if hit_eos or len(req.tokens) >= req.max_new_tokens:
            self._retire(req, COMPLETED, self.clock())
            return True
        return False

    def _flush_resident(self, req: Request) -> int:
        """Flush a resident request's WRITTEN full K/V chunks into the
        shared prefix cache — the drain migration path: a request
        completing or handing off mid-drain leaves its context behind so
        the replica inheriting the conversation restores instead of
        recomputing.  Only positions actually written are eligible: a
        PREFILL resident has written ``_prefill_pos``; a DECODE one has
        written ``context − 1`` positions (the final token's K/V lands
        with its NEXT decode step, which will not run here)."""
        if self.pool.prefix is None or req.slot is None:
            return 0
        c = self.prefill_chunk
        ctx = self._context(req)
        keys = self.pool.prefix.chunk_keys(ctx)
        written = (req._prefill_pos if req.state == PREFILL
                   else ctx.size - 1)
        flushed = 0
        for i in range(min(len(keys), written // c)):
            if keys[i] not in self.pool.prefix:
                self.pool.stash_chunk(req.slot, keys[i], i * c)
                flushed += 1
            if (self._draft_pool is not None
                    and keys[i] not in self._draft_pool.prefix):
                self._draft_pool.stash_chunk(req.slot, keys[i], i * c)
        return flushed

    def _retire(self, req: Request, outcome: str, now: float) -> None:
        if req is self._admitting:
            self._admitting = None
        if self._draining and outcome in (COMPLETED, FAILOVER):
            self._drain_flushed += self._flush_resident(req)
        if req.slot is not None:
            self._running.pop(req.slot, None)
            self.pool.free(req.slot)
            if self._draft_pool is not None:
                self._draft_pool.free(req.slot)
            req.slot = None
        req.state = outcome
        self.metrics.on_retire(req.rid, now, outcome)
