"""What the tests of a served model share, each compiled once a worker:
seeded weights, the benchmark's plain reference as ONE jitted program at
one padded length, a run through ``ServingEngine``, the teacher-forced
check of what it served, logits through the cache in chunks and steps,
and the tiny dense model of the engine's own tests.
(``tests/reference_step.py`` does the same for the training step.)

Nearly all of a tiny model's test is compile time: a reference called op
by op compiles every primitive again for every sequence length, and an
engine at another shape compiles its three programs again.  So a model's
tests start from here, keep to one engine shape a file where the test's
point is not the shape, and ask a toy for a published loop count only in
the one case that is about it (``ROADMAP.md`` Design 9)."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from bluefog_tpu import models
from bluefog_tpu.serving import Request, ServingEngine
from bluefog_tpu.serving.engine import _decode_step_prog, _prefill_chunk_prog

# the one length a reference is compiled at, and the engines' ``max_len``
PADDED = 72


def _cached(make):
    """``make(module, sizes, *hashable)`` once a distinct call, the sizes
    (a dict) told apart by their JSON."""
    once = functools.cache(lambda module, text, *rest: make(
        module, json.loads(text), *rest))
    return lambda module, sz, *rest: once(
        module, json.dumps(sz, sort_keys=True), *rest)


_drawn = _cached(lambda family, sz, seed, dtype: jax.jit(
    lambda k: family.make_params(sz, k, dtype)[0])(jax.random.PRNGKey(seed)))
_compiled = _cached(lambda ref, sz: jax.jit(
    lambda p, t: ref.logits(p, t, sz)))


def params(family, sz, seed=0, dtype=jnp.float32):
    """The family's seeded weights: drawn once a ``(sizes, seed)``, and a
    tree of its own a call, so that a test may write into it."""
    return jax.tree.map(lambda x: x, _drawn(family, sz, seed,
                                            jnp.dtype(dtype)))


def reference(ref, sz, weights, tokens, length=PADDED):
    """``ref.logits`` of one sequence, ``[len(tokens), vocab]``.  The
    references are causal full forward passes, so zeros behind the
    sequence change no row before them: one program a ``(reference,
    sizes)`` at ``length`` tokens serves every shorter sequence.  A
    reference a test has monkeypatched does not come through here (the
    trace is kept)."""
    tokens = np.asarray(tokens)
    padded = np.zeros(max(length, tokens.size), np.int32)
    padded[:tokens.size] = tokens
    return np.asarray(_compiled(ref, sz)(
        weights, jnp.asarray(padded)))[:tokens.size]


def padding_moves(ref, sz, weights, tokens) -> float:
    """How far the padded, jitted reference's rows lie from the plain
    call's at the sequence's own length, in deviations of its logits
    (another shape sums the float32 products in another order).  As shares
    of a tolerance of 2e-4, three sequences a model: 0.01-0.05 for three to
    five attention layers, 0.04-0.18 with four recurrent ones, 0.1-0.4 for
    the looped stack's twelve applications; the program's own distance
    reads the same against either call (0.02-0.2)."""
    plain = np.asarray(ref.logits(weights, jnp.asarray(tokens), sz))
    return gap(reference(ref, sz, weights, tokens), plain)


def gap(got, want) -> float:
    """The widest difference of two logit arrays, in deviations of ``want``."""
    assert got.shape == want.shape
    return float(np.abs(got - want).max() / want.std())


def serve(cfg, weights, prompts, budgets, **engine):
    """The requests through an engine of the file's one shape, to their
    end: ``(engine, requests)``."""
    eng = ServingEngine({"params": weights}, cfg, **{
        "capacity": 2, "max_len": PADDED, "prefill_chunk": 4, **engine})
    reqs = [eng.submit(Request(p, n)) for p, n in zip(prompts, budgets)]
    eng.run()
    assert all(r.state == "completed" for r in reqs)
    return eng, reqs


def assert_served_is_the_references_greedy(ref, sz, weights, req, tol,
                                           in_deviations=True):
    """Teacher-forced: at every served position the reference's best
    token is the served one (or ties with it inside ``tol``, read in
    deviations of the reference's logits over the sequence)."""
    want = reference(ref, sz, weights, req.output()[:-1])
    p, g = req.prompt.size, len(req.tokens)
    rows = want[p - 1:p - 1 + g]
    best = rows.max(-1) - rows[np.arange(g), np.asarray(req.tokens)]
    assert best.max() < tol * (want.std() if in_deviations else 1), (
        p, g, best.max())


def assert_other_lengths_compile_nothing(eng):
    """An engine that has served a request serves prompts of other
    lengths with the executables it has."""
    sizes = lambda: (_prefill_chunk_prog._cache_size(),
                     _decode_step_prog._cache_size())
    before, rng = sizes(), np.random.default_rng(3)
    reqs = [eng.submit(Request(rng.integers(0, 128, n), 6))
            for n in (40, 2, 17)]
    eng.run()
    assert all(r.state == "completed" for r in reqs)
    assert sizes() == before


def chunks_then_steps(cfg, weights, tokens, chunk, prefill, max_len=PADDED):
    """Logits ``[len(tokens), vocab]`` of ``tokens`` through the cache:
    ``prefill`` of them in chunks of ``chunk``, the rest one token a
    call (two programs, as the engine has; traced anew every call, so a
    module a test has monkeypatched is seen)."""
    cfg = cfg.serving_layout(max_len, chunk=chunk)
    cache = cfg.init_cache(1, max_len)
    call = jax.jit(lambda p, c, t: cfg.apply_cached(p, c, t,
                                                    all_logits=True))
    out, at = [], 0
    while at < tokens.size:
        width = chunk if at < prefill else 1
        logits, cache = call(weights, cache,
                             jnp.asarray(tokens[None, at:at + width]))
        out.append(np.asarray(logits[0]))
        at += width
    return np.concatenate(out)


# the engine's own tests: the tiny dense model, its one-shot generation
@functools.cache
def tiny_llama(**cfg_overrides):
    """``(cfg, variables)`` of ``LlamaConfig.tiny`` in float32, its
    weights drawn by one program and once an override (no test writes
    into them)."""
    cfg = models.LlamaConfig.tiny(dtype=jnp.float32, **cfg_overrides)
    return cfg, jax.jit(models.Llama(cfg).init)(
        jax.random.PRNGKey(1), jnp.zeros((2, 4), jnp.int32))


def tiny_engine(variables, cfg, capacity=2, prefill_chunk=4, **kw):
    """The engine tests' one shape, unless a test's point is another."""
    return ServingEngine(variables, cfg, capacity=capacity, max_len=48,
                         prefill_chunk=prefill_chunk, **kw)


def one_shot(variables, cfg, prompt, n, max_len=48, **kw):
    """What an engine's stream is held to: ``llama_generate`` of the
    request alone, at the pool's ``max_len``."""
    return np.asarray(models.llama_generate(
        variables, cfg, jnp.asarray(prompt[None]), n, max_len=max_len,
        **kw))[0]


class VirtualClock:
    """Deterministic engine clock: tests advance time explicitly, so
    deadline behavior and latency percentiles are reproducible."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
