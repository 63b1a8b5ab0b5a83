"""Fleet serving (ISSUE 9): prefix/KV reuse, speculative decoding, and
the gossip-fed multi-replica router.

Contracts under test:

* **Prefix cache exactness** — for ANY mix of shared-prefix prompts
  (random prefix lengths, chunk-misaligned boundaries, int8 K/V, slot
  reuse between the insert and the restore), a prefix-cached engine's
  outputs are bit-identical to the one-shot path.  A restored chunk is
  the same bytes the prefill wrote, so reuse must be invisible.
* **Router determinism + backpressure** — routing is a pure function
  of the replicas' gauges (same state -> same decision), spreads load
  away from busy replicas, and surfaces whole-fleet saturation as
  :class:`FleetSaturated` carrying every replica's queue depth.
* **Speculative decoding** — the draft/verify resident pair is
  token-exact with the plain engine at temperature 0 (self-draft AND an
  independently-initialized draft), and the resident-program set is
  fixed at build time.
* **Zero-on-free** — both free modes (index-reset default, full zero
  via ``BLUEFOG_KV_ZERO_ON_FREE``/``zero_on_free=``) keep slot reuse
  exact; only the default retains bytes a prefix cache can reuse.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bluefog_tpu import models
from bluefog_tpu.observe.registry import MetricsRegistry
from bluefog_tpu.serving import (FleetRouter, FleetSaturated, PrefixCache,
                                 Request, RequestRejected,
                                 SlotPool, SpeculativeConfig,
                                 collect_serving_signals)
from served_model import (one_shot as _one_shot, tiny_engine as _engine,
                          tiny_llama as _setup)

pytestmark = pytest.mark.fleet_serving

MAX_LEN = 48


# --------------------------------------------------------------------- #
# prefix cache: hashing + store semantics
# --------------------------------------------------------------------- #
def test_chunk_keys_are_chained():
    """Keys commit to the WHOLE prefix: equal prefixes share keys, a
    single differing token kills every key from its chunk on, and only
    full chunks of prompt[:-1] are keyed (the last token rides decode)."""
    pc = PrefixCache(chunk=4, capacity_bytes=1 << 20)
    a = np.arange(13, dtype=np.int32)            # 12 prefill tokens
    assert len(pc.chunk_keys(a)) == 3
    assert len(pc.chunk_keys(a[:12])) == 2       # 11 prefill -> 2 full
    assert len(pc.chunk_keys(a[:4])) == 0        # 3 prefill tokens
    b = a.copy()
    b[5] = 99                                    # differ inside chunk 1
    ka, kb = pc.chunk_keys(a), pc.chunk_keys(b)
    assert ka[0] == kb[0]
    assert ka[1] != kb[1] and ka[2] != kb[2]     # chain severed
    # same tokens, different chunk size -> different key space
    assert PrefixCache(chunk=8).chunk_keys(a)[0] != ka[0]


def test_prefix_cache_lru_bound():
    """Insertion respects the byte budget: least-recently-USED entries
    evict first, an over-budget chunk is refused outright, and match()
    walks the chain (a miss at chunk i forecloses chunk i+1)."""
    leaf = np.zeros(100, np.float32)             # 400 bytes/entry
    pc = PrefixCache(chunk=4, capacity_bytes=1000)
    pc.insert("k0", [leaf])
    pc.insert("k1", [leaf])
    assert pc.match(["k0", "k1", "k2"]) == 2     # touches k0 then k1
    pc.insert("k2", [leaf])                      # evicts the LRU...
    assert len(pc) == 2 and pc.nbytes == 800
    assert pc.match(["k0"]) == 0                 # ...which was k0
    pc.insert("huge", [np.zeros(1001, np.uint8)])
    assert len(pc) == 2                          # refused, not thrashed
    assert pc.match(["k0", "k1"]) == 0           # chain: dead at k0
    s = pc.stats()
    assert s["evictions"] == 1 and s["hit_rate"] < 1.0


def test_seq_axes_structural_detection():
    """The per-leaf sequence axis comes from shape-evaluating the cache
    at two lengths — index leaves (no scaling axis) come back None, and
    both K/V layouts resolve without a registry."""
    from bluefog_tpu.serving.prefix_cache import seq_axes

    cfg, _ = _setup()
    for kv_quant in ("none", "int8"):
        axes = seq_axes(cfg, 16, kv_quant)
        assert None in axes                      # cache_index leaves
        assert any(a is not None for a in axes)  # K/V leaves


# --------------------------------------------------------------------- #
# prefix cache: the admission-exactness property
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_prefix_admission_bitwise_exact_property(kv_quant):
    """The acceptance property: random shared-prefix prompt families —
    prefix lengths off the chunk grid, novel tails, slot reuse and
    capacity-1 recycling between insert and restore — every output is
    bit-identical to COLD prefill (a cacheless engine running the same
    compiled programs; engine==one-shot is test_serving's anchor)."""
    cfg, variables = _setup()
    params = variables
    kw = {}
    if kv_quant == "int8":
        from bluefog_tpu.models.quant import quantize_llama_params

        params = quantize_llama_params(variables)
        kw = dict(kv_quant="int8", weight_quant="int8")
    rs = np.random.RandomState(42)
    eng = _engine(params, cfg, capacity=1, prefix_cache=True, max_queue=64,
                  registry=MetricsRegistry(), **kw)
    cold = _engine(params, cfg, capacity=1, prefix_cache=False, max_queue=64,
                   registry=MetricsRegistry(), **kw)
    prompts = []
    for _ in range(3):
        # a family: one prefix, several continuations of random length
        prefix = rs.randint(0, 256,
                            (rs.randint(3, 20),)).astype(np.int32)
        prompts.append(prefix)
        for _ in range(2):
            tail = rs.randint(0, 256,
                              (rs.randint(1, 8),)).astype(np.int32)
            prompts.append(np.concatenate([prefix, tail]))
    order = rs.permutation(len(prompts))
    reqs = {}
    for i in order:
        reqs[i] = eng.submit(Request(prompts[i], 5))
        eng.run()  # capacity 1: each admission reuses THE slot
    for i, r in reqs.items():
        ref = cold.submit(Request(prompts[i], 5))
        cold.run()
        np.testing.assert_array_equal(r.output(), ref.output())
    # the families actually exercised the cache
    assert eng.metrics.summary()["prefix_chunks_restored"] > 0
    assert eng.pool.prefix.stats()["hits"] > 0
    assert cold.metrics.summary()["prefix_chunks_restored"] == 0


def test_prefix_restore_skips_prefill_work():
    """A warm admission computes only its novel tail: the engine's
    prefill-chunk counter advances by the tail chunks alone, and the
    restored token count lands in the summary."""
    cfg, variables = _setup()
    eng = _engine(variables, cfg, capacity=1, prefix_cache=True,
                  registry=MetricsRegistry())
    rs = np.random.RandomState(7)
    prefix = rs.randint(0, 256, (16,)).astype(np.int32)
    a = np.concatenate([prefix, rs.randint(0, 256, (2,)).astype(np.int32)])
    b = np.concatenate([prefix, rs.randint(0, 256, (2,)).astype(np.int32)])
    eng.submit(Request(a, 4))
    eng.run()
    cold_chunks = eng.metrics.summary()["prefill_chunks"]
    eng.submit(Request(b, 4))
    eng.run()
    m = eng.metrics.summary()
    # b's 17 prefill tokens = 4 cached chunks restored + 1 tail chunk
    assert m["prefix_chunks_restored"] == 4
    assert m["prefix_tokens_restored"] == 16
    assert m["prefill_chunks"] == cold_chunks + 1
    assert 0 < m["prefix_hit_rate"] < 1


def test_prefix_chunk_must_match_engine_chunk():
    cfg, variables = _setup()
    with pytest.raises(ValueError, match="chunk"):
        _engine(variables, cfg, capacity=1, prefix_cache=PrefixCache(chunk=8))


# --------------------------------------------------------------------- #
# zero-on-free: both modes exact, retention only in the default
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("zero_on_free", [False, True])
def test_slot_reuse_exact_both_free_modes(zero_on_free):
    """Index-reset (default) and full-zero free both keep slot reuse
    bit-exact — the zero mode buys nothing for correctness."""
    cfg, variables = _setup()
    # lengths/budget shared with the speculative tests so the one-shot
    # reference programs compile once for the whole file
    prompts = [p.astype(np.int32) for p in
               (np.arange(5) + 3, np.arange(9) * 2 + 1)]
    eng = _engine(variables, cfg, capacity=1, zero_on_free=zero_on_free)
    assert eng.pool.zero_on_free is zero_on_free
    for p in prompts:
        r = eng.submit(Request(p, 6))
        eng.run()
        np.testing.assert_array_equal(
            r.output(), _one_shot(variables, cfg, p, 6))


def test_free_modes_differ_only_in_retention():
    """After free: the default leaves K/V bytes in place (what the
    prefix cache feeds on) and only resets ``cache_index``; zero-on-free
    wipes the whole slot.  Env var ``BLUEFOG_KV_ZERO_ON_FREE`` selects
    the mode when the ctor argument is left None."""
    cfg, variables = _setup()

    def run_one(zero):
        eng = _engine(variables, cfg, capacity=1, zero_on_free=zero)
        eng.submit(Request(np.arange(9, dtype=np.int32), 4))
        eng.run()
        total = 0.0
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                eng.pool.cache)[0]:
            if getattr(path[-1], "key", None) == "cache_index":
                assert not np.asarray(leaf).any()  # always reset
            else:
                total += float(np.abs(np.asarray(
                    leaf, np.float32)).sum())
        return total

    assert run_one(zero=False) > 0.0   # bytes retained
    assert run_one(zero=True) == 0.0   # slot wiped
    import os

    from bluefog_tpu import config as bfconfig

    old = os.environ.get("BLUEFOG_KV_ZERO_ON_FREE")
    try:
        os.environ["BLUEFOG_KV_ZERO_ON_FREE"] = "1"
        assert bfconfig.kv_zero_on_free() is True
        assert SlotPool(cfg, capacity=1, max_len=16).zero_on_free
        os.environ["BLUEFOG_KV_ZERO_ON_FREE"] = "0"
        assert not SlotPool(cfg, capacity=1, max_len=16).zero_on_free
    finally:
        if old is None:
            os.environ.pop("BLUEFOG_KV_ZERO_ON_FREE", None)
        else:
            os.environ["BLUEFOG_KV_ZERO_ON_FREE"] = old


# --------------------------------------------------------------------- #
# speculative decoding
# --------------------------------------------------------------------- #
def _spec_engine(variables, cfg, draft_vars, draft_cfg=None, **kw):
    spec = SpeculativeConfig(variables=draft_vars,
                             cfg=draft_cfg or cfg, lookahead=3)
    return _engine(variables, cfg, speculative=spec,
                   registry=MetricsRegistry(), **kw)


def test_speculative_self_draft_exact_and_fast():
    """Target-as-its-own-draft at temp 0: every window verifies, so
    each step emits lookahead+1 tokens AND the stream is bit-exact with
    the plain engine / one-shot path."""
    cfg, variables = _setup()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 256, (n,)).astype(np.int32)
               for n in (5, 9, 3)]
    eng = _spec_engine(variables, cfg, variables)
    reqs = [eng.submit(Request(p, 6)) for p in prompts]
    eng.run()
    for r, p in zip(reqs, prompts):
        np.testing.assert_array_equal(
            r.output(), _one_shot(variables, cfg, p, 6))
    m = eng.metrics.summary()
    assert m["accepted_per_step"] > 1.0
    assert m["spec_steps"] > 0


def test_speculative_independent_draft_exact():
    """An independently-initialized draft disagrees with the target
    almost everywhere — the rejection path dominates — and the output
    is STILL bit-exact at temp 0 (speculation changes cost, never
    content)."""
    cfg, variables = _setup()
    draft = models.Llama(cfg).init(jax.random.PRNGKey(7),
                                   jnp.zeros((2, 4), jnp.int32))
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, 256, (n,)).astype(np.int32)
               for n in (9, 3)]
    eng = _spec_engine(variables, cfg, draft)
    reqs = [eng.submit(Request(p, 6)) for p in prompts]
    eng.run()
    for r, p in zip(reqs, prompts):
        np.testing.assert_array_equal(
            r.output(), _one_shot(variables, cfg, p, 6))


def test_speculative_sampled_path_completes():
    """temperature > 0 goes through rejection sampling + residual
    resample; streams complete within budget (distribution equality is
    the algorithm's guarantee; bit-equality is only promised at 0)."""
    cfg, variables = _setup()
    draft = models.Llama(cfg).init(jax.random.PRNGKey(7),
                                   jnp.zeros((2, 4), jnp.int32))
    rs = np.random.RandomState(2)
    prompts = [rs.randint(0, 256, (5,)).astype(np.int32)
               for _ in range(2)]
    eng = _spec_engine(variables, cfg, draft)
    reqs = [eng.submit(Request(p, 6, temperature=0.8, seed=3 + i))
            for i, p in enumerate(prompts)]
    eng.run()
    for r, p in zip(reqs, prompts):
        assert r.state == "completed"
        assert r.output().size == p.size + 6
        assert (r.output() >= 0).all()


def test_speculative_headroom_reservation():
    """submit() reserves lookahead positions past the budget: a prompt
    that fits the plain engine is refused by the speculative one when
    the draft window could overrun the slot (dynamic_update_slice would
    CLAMP and corrupt K/V silently)."""
    cfg, variables = _setup()
    prompt = np.arange(MAX_LEN - 8, dtype=np.int32)
    plain = _engine(variables, cfg, capacity=1)
    plain.submit(Request(prompt, 8))  # exactly fits
    eng = _spec_engine(variables, cfg, variables)
    with pytest.raises(ValueError, match="headroom"):
        eng.submit(Request(prompt, 8))


def test_resident_program_set_fixed_at_build():
    """The resident registry is a build-time constant: 2 programs
    plain, 3 speculative, unchanged by serving load, and profile()
    enumerates exactly that set."""
    cfg, variables = _setup()
    plain = _engine(variables, cfg)
    eng = _spec_engine(variables, cfg, variables)
    assert sorted(plain._resident) == ["decode_step", "prefill_chunk"]
    assert sorted(eng._resident) == ["draft_prefill_chunk",
                                     "prefill_chunk", "spec_step"]
    rs = np.random.RandomState(3)
    for e in (plain, eng):
        before = set(e._resident)
        for n in (3, 6):
            e.submit(Request(rs.randint(0, 256, (n,)).astype(np.int32),
                             4))
        e.run()
        assert set(e._resident) == before
    # generic profile() enumeration over the draft/verify pair (the
    # plain 2-program enumeration is test_observe's profile test)
    profs = eng.profile(publish=False)
    assert set(profs) == {"draft_prefill_chunk", "prefill_chunk",
                          "spec_step"}
    assert all(p.flops > 0 for p in profs.values())


def test_speculative_no_recompiles_across_arrivals():
    """One compiled speculative step serves every arrival pattern —
    same zero-recompile contract the plain decode step carries."""
    from bluefog_tpu.serving.engine import _spec_step_prog

    cfg, variables = _setup()
    eng = _spec_engine(variables, cfg, variables)
    rs = np.random.RandomState(4)
    eng.submit(Request(rs.randint(0, 256, (5,)).astype(np.int32), 4))
    eng.run()
    n0 = _spec_step_prog._cache_size()
    for n, b in ((3, 6), (9, 3), (1, 5)):
        eng.submit(Request(rs.randint(0, 256, (n,)).astype(np.int32), b))
        eng.step()
    eng.run()
    assert _spec_step_prog._cache_size() == n0


# --------------------------------------------------------------------- #
# fleet router
# --------------------------------------------------------------------- #
def _fleet(variables, cfg, n, capacity=2, max_queue=2, **kw):
    regs = [MetricsRegistry() for _ in range(n)]
    engines = [_engine(variables, cfg, capacity=capacity, max_queue=max_queue,
                       registry=r)
               for r in regs]
    return engines, regs, FleetRouter(engines, registries=regs, **kw)


def test_collect_serving_signals():
    cfg, variables = _setup()
    reg = MetricsRegistry()
    eng = _engine(variables, cfg, registry=reg)
    sig = collect_serving_signals(reg)
    assert sig == {"occupancy": 0.0, "queue_depth": 0.0, "ttft_p50": 0.0,
                   "last_step_ts": -1.0}  # -1: never stepped (the
    # staleness guard exempts cold replicas)
    eng.submit(Request(np.arange(5, dtype=np.int32), 3))
    eng.run()
    sig = collect_serving_signals(reg)
    assert sig["ttft_p50"] >= 0.0  # histogram scraped without error
    assert sig["last_step_ts"] >= 0.0  # heartbeat advanced by stepping


def test_router_is_deterministic_and_prefers_idle():
    """Same replica state -> identical snapshot, scores, and order; a
    loaded replica ranks behind an idle one; per-rank converged views
    agree (push-sum exactness over the serving gauges)."""
    cfg, variables = _setup()
    engines, regs, router = _fleet(variables, cfg, 3)
    rs = np.random.RandomState(5)
    engines[0].submit(Request(rs.randint(0, 256, (5,)).astype(np.int32),
                              6))
    engines[0].step()
    s1, s2 = router.poll(), router.poll()
    assert s1.order == s2.order
    np.testing.assert_allclose(s1.scores, s2.scores, rtol=0, atol=0)
    np.testing.assert_array_equal(s1.signals, s2.signals)
    assert s1.order[-1] == 0            # the busy replica ranks last
    assert s1.rounds > 0 and s1.spread <= 1e-10
    # another rank's router sees the same fleet (decentralized: no
    # rank is special)
    other = FleetRouter(engines, registries=regs, rank=2)
    np.testing.assert_allclose(other.poll().signals, s1.signals,
                               rtol=1e-9, atol=1e-12)
    # single replica bypasses gossip
    engines1, _, router1 = _fleet(variables, cfg, 1)
    snap = router1.poll()
    assert snap.rounds == 0 and snap.order == (0,)


def test_router_spreads_and_saturates():
    """Requests spread across replicas; when every queue is full the
    router raises FleetSaturated with all per-replica depths (a
    RequestRejected subclass — client backoff code keeps working)."""
    cfg, variables = _setup()
    engines, regs, router = _fleet(variables, cfg, 2, capacity=1,
                                   max_queue=1)
    rs = np.random.RandomState(6)

    def req():
        return Request(rs.randint(0, 256, (4,)).astype(np.int32), 3)

    picks = [router.submit(req())[0] for _ in range(2)]
    assert sorted(picks) == [0, 1]      # second submit avoids the first
    for e in engines:
        e.step()                        # queued -> slots (queues empty)
    for _ in range(2):                  # re-fill both 1-deep queues
        router.submit(req())
    with pytest.raises(FleetSaturated) as ei:
        router.submit(req())
    assert isinstance(ei.value, RequestRejected)
    assert ei.value.queue_depths == [1, 1]
    assert router.summary()["n_saturated"] == 1
    for e in engines:
        e.run()                         # fleet drains fine afterwards
    assert all(e.pool.n_active == 0 for e in engines)


def test_router_dead_replica_excised():
    """A dead replica's signals drop out of the gossip and its score is
    +inf: it is never routed to — same excision semantics as the
    training-side dead-rank handling."""
    cfg, variables = _setup()
    engines, regs, router = _fleet(variables, cfg, 2)
    snap = router.poll(dead_mask=[False, True])
    assert snap.order[0] == 0
    assert not np.isfinite(snap.scores[1])
    idx, _ = router.submit(Request(np.arange(4, dtype=np.int32), 3),
                           snapshot=snap)
    assert idx == 0
    engines[0].run()


def test_router_publish_lands_fleet_gauges():
    cfg, variables = _setup()
    pub = MetricsRegistry()
    engines, regs, router = _fleet(variables, cfg, 2, registry=pub)
    router.submit(Request(np.arange(5, dtype=np.int32), 3))
    for e in engines:
        e.run()
    router.publish()
    names = {n for n, *_ in pub.collect()}
    assert "bf_fleet_serving_occupancy" in names
    assert "bf_fleet_serving_queue_depth" in names
    assert "bf_fleet_serving_best_replica" in names


# --------------------------------------------------------------------- #
# the bench artifact (slow: subprocess + wall-clock measurement)
# --------------------------------------------------------------------- #
@pytest.mark.slow
def test_fleet_serving_bench_smoke(tmp_path):
    """benchmarks/fleet_serving.py end to end at a tiny scale: all
    machine-checked claims hold and the record carries every section."""
    import os
    import subprocess
    import sys

    out = str(tmp_path / "fleet.json")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmarks",
                                      "fleet_serving.py"),
         "--num-requests", "8", "--capacity", "2", "--max-len", "48",
         "--prompt-len", "3", "8", "--new-tokens", "3", "6",
         "--prefix-pairs", "2", "--prefix-len", "24",
         "--prefill-chunk", "4", "--lookahead", "2",
         "--dim", "64", "--layers", "2",
         "--out", out, "--compare", ""],
        capture_output=True, text=True, timeout=600, env=env, cwd=repo)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.load(open(out))
    assert all(rec["machine_checked"].values()), rec["machine_checked"]
    assert rec["fleet_two"]["fleet_speedup"] > 1.0
    assert (rec["prefix"]["warm_admit_ttft_p50"]
            < rec["prefix"]["cold_admit_ttft_p50"])
    assert rec["speculative"]["accepted_per_step"] > 1.0
    assert rec["resident"]["plain_count"] == 2
    assert rec["resident"]["speculative_count"] == 3
