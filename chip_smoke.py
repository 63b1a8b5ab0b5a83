"""Bring-up proof: the trainer, the server and the neighbor exchange on
the attached TPU, through the entry points a user calls, at the width of
the repo's "1b" Llama (``LlamaConfig.llama_1b``), with weights made from
a seed.

  python chip_smoke.py             one chip: device, train, serve
  python chip_smoke.py --chips 4   four chips: device, exchange (only)

Every check that fails ends the run with a non-zero exit code at once.
The last line of a run that passed is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, with the
device as JAX reports it.  Times printed on the way are information for
the reader of a bring-up log, taken on the named device; they are not
benchmark metrics.

One process drives every chip it uses; nothing here starts another.
"""

import argparse
import dataclasses
import importlib.metadata
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu import models, native
from bluefog_tpu.config import configure_compilation_cache
from bluefog_tpu.models.generate import llama_generate
from bluefog_tpu.optim import functional as F
from bluefog_tpu.serving import Request, ServingEngine
from bluefog_tpu.topology import one_peer_dynamic_schedule

# -------------------------------------------------------------------- #
# What only a chip can supply.  tests/test_chip_smoke.py runs the phases
# on CPU devices, where Pallas kernels interpret and the allocator keeps
# no statistics, by replacing exactly these three names; the script
# itself has no other platform and no size option.
# -------------------------------------------------------------------- #
PLATFORM = "tpu"


def on_chip_path(ok: bool, what: str) -> None:
    """A fact that holds only when the run took the chip's own path and
    not a stand-in for it: a Pallas kernel compiled INTO the program
    (interpret mode leaves no custom call), ``"auto"`` resolving to the
    kernel."""
    check(ok, what)


def memory_stat(device, key: str) -> int:
    return device.memory_stats()[key]


def check(ok: bool, what: str) -> None:
    if not ok:
        sys.exit(f"chip_smoke: FAILED: {what}")


def say(phase: str, text: str) -> None:
    print(f"[{phase}] {text}", flush=True)


def device_line(devices) -> str:
    d = devices[0]
    return f"{d.platform} {d.device_kind} x{len(devices)}"


def result_line(devices) -> str:
    d = devices[0]
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}})


# -------------------------------------------------------------------- #
# device
# -------------------------------------------------------------------- #
def check_device(count: int, cache_dir: str):
    """The attached devices, or exit: the platform must be PLATFORM and
    the process must see exactly ``count`` chips."""
    devices = jax.devices()
    found = devices[0].platform
    if found != PLATFORM:
        sys.exit(f"chip_smoke: needs a {PLATFORM} device, JAX found "
                 f"platform {found!r}")
    if len(devices) != count:
        sys.exit(f"chip_smoke: needs {count} chip(s), JAX found "
                 f"{len(devices)}")
    say("device", f"{device_line(devices)}; jax {jax.__version__}, jaxlib "
        f"{importlib.metadata.version('jaxlib')}, libtpu "
        f"{importlib.metadata.version('libtpu')}")
    say("device", f"compile cache: {cache_dir}")
    say("device", f"native library: {native.status()}")
    return devices


# -------------------------------------------------------------------- #
# shared pieces
# -------------------------------------------------------------------- #
def lm_loss(cfg):
    """Next-token cross-entropy of ``Llama(cfg)`` on ``(inputs,
    targets)`` — examples/llama_benchmark.py's loss."""
    model = models.Llama(cfg)

    def loss_fn(params, batch):
        inputs, targets = batch
        logits = model.apply(params, inputs)
        return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
            logits, targets))

    return loss_fn


def init_train_state(cfg, opt, mesh, seed: int):
    """Parameters and optimizer state, rank-major and born sharded over
    ``mesh`` (no device ever stages the whole stack), as
    examples/llama_benchmark.py initializes them."""
    init_model = models.Llama(dataclasses.replace(cfg, attn_impl="xla"))

    def init_state():
        base = init_model.init(jax.random.PRNGKey(seed),
                               jnp.zeros((1, 8), jnp.int32))
        return {"params": base, "opt": opt.init(base)}

    state = F.rank_major_init(init_state, mesh)
    return state["params"], state["opt"]


def token_batch(cfg, mesh, batch: int, seq: int, seed: int):
    n = mesh.shape["bf"]
    raw = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (n, batch, seq + 1)).astype(np.int32)
    sharding = NamedSharding(mesh, P("bf"))
    return (jax.device_put(raw[:, :, :-1], sharding),
            jax.device_put(raw[:, :, 1:], sharding))


def compile_step(step_fn, params, opt_state, batch):
    """AOT-compile the step's own program (the jitted call then reuses
    it): a line of seconds — tracing and lowering apart from the
    compiler, which is the part the persistent cache saves — the
    optimized module text, and the compiler's per-device byte count."""
    t0 = time.perf_counter()
    lowered = step_fn.lower(params, opt_state, batch, np.int32(0))
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    return (f"trace+lower {t1 - t0:.1f} s, compile {t2 - t1:.1f} s",
            compiled.as_text(), need)


def run_steps(step_fn, params, opt_state, batch, warmup: int, steps: int):
    """``warmup`` then ``steps`` calls, the timed ones ended by
    ``block_until_ready``.  Returns (params, opt_state, per-step losses
    as numpy [steps_total, n_ranks], first-call s, seconds per timed
    step)."""
    losses = []
    t_first = time.perf_counter()
    for i in range(warmup):
        params, opt_state, loss = step_fn(params, opt_state, batch,
                                          np.int32(i))
        losses.append(loss)
        if i == 0:
            jax.block_until_ready(loss)
            t_first = time.perf_counter() - t_first
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for i in range(warmup, warmup + steps):
        params, opt_state, loss = step_fn(params, opt_state, batch,
                                          np.int32(i))
        losses.append(loss)
    jax.block_until_ready((params, opt_state, loss))
    per_step = (time.perf_counter() - t0) / steps
    return (params, opt_state, np.stack([np.asarray(x) for x in losses]),
            t_first, per_step)


# -------------------------------------------------------------------- #
# train (one chip)
# -------------------------------------------------------------------- #
def train_phase(cfg, devices, *, batch: int, seq: int, warmup: int = 2,
                steps: int = 3, loss_rtol: float = 1e-2, seed: int = 0):
    """``build_train_step(comm_mode="none")`` on a one-device mesh with
    the flash kernel: finite, moving losses; the kernel in the compiled
    program; step-0 loss equal to the ``attn_impl="xla"`` loss of the
    same parameters and batch within ``loss_rtol`` (relative; both run
    bf16, the kernel accumulates in f32)."""
    dev = device_line(devices[:1])
    mesh = Mesh(np.array(devices[:1]), ("bf",))
    cfg = dataclasses.replace(cfg, attn_impl="flash")
    opt = optax.sgd(1e-3, momentum=0.9)
    step_fn = F.build_train_step(lm_loss(cfg), opt, mesh, comm_mode="none")
    params, opt_state = init_train_state(cfg, opt, mesh, seed)
    tokens = token_batch(cfg, mesh, batch, seq, seed)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    say("train", f"dim {cfg.dim}, {cfg.n_layers} layers, {cfg.n_heads}/"
        f"{cfg.n_kv_heads} heads, hidden {cfg.ffn_dim}, vocab "
        f"{cfg.vocab_size}, {n_params / 1e6:.0f}M parameters, batch "
        f"{batch} x {seq}, remat={cfg.remat}, attn_impl={cfg.attn_impl}")

    # the reference loss first: the step donates its state
    xla_loss = lm_loss(dataclasses.replace(cfg, attn_impl="xla"))

    def rank0(tree):
        return jax.tree.map(lambda x: x[0], tree)

    ref = float(jax.jit(lambda p, b: xla_loss(rank0(p), rank0(b)))(
        params, tokens))

    timing, text, need = compile_step(step_fn, params, opt_state, tokens)
    on_chip_path("tpu_custom_call" in text,
                 "the compiled train step holds no tpu_custom_call: the "
                 "flash kernel was replaced")
    del text
    say("train", f"{timing} on {dev}; the step needs "
        f"{need / 2**30:.2f} GiB of "
        f"{memory_stat(devices[0], 'bytes_limit') / 2**30:.2f} GiB")

    params, opt_state, losses, first_s, step_s = run_steps(
        step_fn, params, opt_state, tokens, warmup, steps)
    losses = losses[:, 0]
    check(bool(np.all(np.isfinite(losses))), f"train losses {losses}")
    check(len(set(losses.tolist())) > 1,
          f"train loss did not move over {len(losses)} updates: {losses}")
    rel = abs(float(losses[0]) - ref) / abs(ref)
    check(rel <= loss_rtol,
          f"step-0 loss {losses[0]} (flash) vs {ref} (xla): relative "
          f"difference {rel:.2e} > {loss_rtol}")
    say("train", f"losses {[round(float(x), 4) for x in losses]}; step-0 "
        f"flash {float(losses[0]):.5f} vs xla {ref:.5f} (rel {rel:.1e}, "
        f"tolerance {loss_rtol})")
    say("train", f"first call {first_s:.2f} s, then {step_s * 1e3:.1f} "
        f"ms/step = {batch * seq / step_s:.0f} tokens/s on {dev} "
        f"(information, not a benchmark)")
    say("train", f"peak_bytes_in_use "
        f"{memory_stat(devices[0], 'peak_bytes_in_use') / 2**30:.2f} GiB")


# -------------------------------------------------------------------- #
# serve (one chip)
# -------------------------------------------------------------------- #
def serve_phase(cfg, devices, *, capacity: int, max_len: int,
                prefill_chunk: int, n_requests: int, prompt_len: tuple,
                new_tokens: tuple, seed: int = 0):
    """A ``ServingEngine`` with ``decode_attn="auto"`` answering
    ``n_requests`` through submit/step: every request completes with the
    token count asked for, no resident program recompiles after warm-up,
    and the first request's greedy tokens are identical to
    ``llama_generate``'s on the same weights, prompt, dtype and
    ``decode_attn`` (in bf16 on the chip too, as the CPU suite holds it
    in float32)."""
    dev = device_line(devices[:1])
    model = models.Llama(cfg)
    variables = jax.jit(model.init)(jax.random.PRNGKey(seed + 1),
                                    jnp.zeros((1, 8), jnp.int32))
    engine = ServingEngine(variables, cfg, capacity=capacity,
                           max_len=max_len, prefill_chunk=prefill_chunk,
                           decode_attn="auto", max_queue=n_requests)
    resolved = engine.cfg.decode_attn
    say("serve", f"capacity {capacity}, max_len {max_len}, prefill_chunk "
        f"{prefill_chunk}, dtype {jnp.dtype(cfg.dtype).name}; "
        f"decode_attn 'auto' resolved to {resolved!r}")
    on_chip_path(resolved == "pallas",
                 f"decode_attn 'auto' resolved to {resolved!r}, not the "
                 "Pallas decode kernel")
    fn, example_args, static = engine._resident["decode_step"]
    text = fn.lower(*example_args(), **static).compile().as_text()
    on_chip_path("tpu_custom_call" in text,
                 "the resident decode step holds no tpu_custom_call")
    del text

    rs = np.random.RandomState(seed)

    def make_request(n_prompt, n_new):
        prompt = rs.randint(0, cfg.vocab_size, (n_prompt,))
        return Request(prompt.astype(np.int32), n_new)

    # warm-up: one request through prefill, decode and retirement
    # compiles everything the engine will ever run
    t0 = time.perf_counter()
    engine.submit(make_request(prompt_len[0], 2))
    engine.run()
    warm_s = time.perf_counter() - t0
    programs = {name: fn for name, (fn, _, _) in engine._resident.items()}
    sizes = {k: p._cache_size() for k, p in programs.items()}

    requests = [
        make_request(int(rs.randint(prompt_len[0], prompt_len[1] + 1)),
                     int(rs.randint(new_tokens[0], new_tokens[1] + 1)))
        for _ in range(n_requests)]
    t0 = time.perf_counter()
    for r in requests:
        engine.submit(r)
    ttft = {}
    busy = True
    while busy:
        busy = engine.step()
        now = time.perf_counter() - t0
        for r in requests:
            if r.tokens and r.rid not in ttft:
                ttft[r.rid] = now
    total_s = time.perf_counter() - t0
    for r in requests:
        check(r.state == "completed" and len(r.tokens) == r.max_new_tokens,
              f"request {r.rid}: state {r.state}, {len(r.tokens)} of "
              f"{r.max_new_tokens} tokens")
    after = {k: p._cache_size() for k, p in programs.items()}
    check(after == sizes, f"resident programs recompiled after warm-up: "
          f"{sizes} -> {after}")
    n_tokens = sum(len(r.tokens) for r in requests)
    say("serve", f"{n_requests} requests completed, {n_tokens} tokens, "
        f"prompts {min(r.prompt.size for r in requests)}-"
        f"{max(r.prompt.size for r in requests)}; jit cache sizes {after} "
        "unchanged after warm-up")

    first = requests[0]
    t1 = time.perf_counter()
    ref = np.asarray(llama_generate(
        variables, cfg, jnp.asarray(first.prompt)[None],
        first.max_new_tokens, max_len=max_len, decode_attn="auto"))[0]
    gen_s = time.perf_counter() - t1
    got = first.output()
    check(np.array_equal(got, ref),
          f"request 0 differs from llama_generate at position "
          f"{int(np.argmin(got == ref)) - first.prompt.size} of its "
          f"{first.max_new_tokens} new tokens: {got[first.prompt.size:]} "
          f"vs {ref[first.prompt.size:]}")
    say("serve", f"request 0 ({first.prompt.size} prompt + "
        f"{first.max_new_tokens} new tokens): identical to llama_generate")
    say("serve", f"warm-up request (compiles) {warm_s:.1f} s, "
        f"llama_generate (compiles) {gen_s:.1f} s; time to first token "
        f"median "
        f"{np.median(list(ttft.values())) * 1e3:.0f} ms, max "
        f"{max(ttft.values()) * 1e3:.0f} ms; {n_tokens / total_s:.0f} "
        f"tokens/s over {total_s:.2f} s on {dev} (information, not a "
        "benchmark)")
    say("serve", f"peak_bytes_in_use "
        f"{memory_stat(devices[0], 'peak_bytes_in_use') / 2**30:.2f} GiB")


# -------------------------------------------------------------------- #
# exchange (four chips)
# -------------------------------------------------------------------- #
def mixing_matrix(rnd) -> np.ndarray:
    """Row-stochastic W of one schedule round, from its edge list alone:
    ``W[dst, src]`` is what ``dst`` applies to ``src``'s value."""
    w = np.diag(np.asarray(rnd.self_weight_values, np.float64))
    for (src, dst), val in zip(rnd.edges, rnd.edge_weight_values):
        w[dst, src] += val
    return w


def exchange_phase(cfg, devices, *, batch: int, seq: int,
                   parity_layers: int, full_layers: int, steps: int = 3,
                   mix_atol: float = 1e-5, seed: int = 0):
    """The neighbor exchange across ``devices``: sharded placement, one
    ``atc`` one-peer step against a NumPy mixing of the ``none`` step's
    per-rank results, the README's one-peer sweep to the exact mean, and
    ``steps`` full-depth steps each of ``atc`` and of the
    ``gradient_allreduce`` baseline."""
    n = len(devices)
    dev = device_line(devices)
    mesh = Mesh(np.array(devices), ("bf",))
    cfg = dataclasses.replace(cfg, attn_impl="flash")
    opt = optax.sgd(1e-3, momentum=0.9)
    schedule = one_peer_dynamic_schedule(n)

    # ---- parity leg ------------------------------------------------- #
    small = dataclasses.replace(cfg, n_layers=parity_layers)
    loss_fn = lm_loss(small)
    tokens = token_batch(small, mesh, batch, seq, seed)
    offsets = jnp.arange(n, dtype=jnp.float32) * 1e-3

    def diverged_state():
        params, opt_state = init_train_state(small, opt, mesh, seed)
        leaves, treedef = jax.tree.flatten(params)
        # per-rank DIFFERENT parameters: rank r's first leaf is shifted
        # by r * 1e-3, so a wrong peer or weight shows in the mix
        leaves[0] = leaves[0] + offsets.reshape(
            (n,) + (1,) * (leaves[0].ndim - 1))
        return jax.tree.unflatten(treedef, leaves), opt_state

    results = {}
    for mode, kwargs in (("none", {}), ("atc", {"schedule": schedule})):
        step_fn = F.build_train_step(loss_fn, opt, mesh, comm_mode=mode,
                                     **kwargs)
        params, opt_state = diverged_state()
        params, opt_state, loss = step_fn(params, opt_state, tokens,
                                          np.int32(0))
        results[mode] = (jax.tree.map(np.asarray, params),
                         np.asarray(loss))
        del params, opt_state, step_fn
    w0 = mixing_matrix(schedule[0])
    worst = 0.0
    flat_none = jax.tree.leaves_with_path(results["none"][0])
    flat_atc = jax.tree.leaves(results["atc"][0])
    for (path, local), mixed in zip(flat_none, flat_atc):
        want = np.tensordot(w0, local.astype(np.float64), axes=1)
        err = float(np.abs(mixed - want).max())
        worst = max(worst, err)
        check(err <= mix_atol,
              f"atc step leaf {jax.tree_util.keystr(path)}: max |atc - "
              f"W0 @ none| = {err:.2e} > {mix_atol}")
    spread = float(np.ptp(np.asarray(
        jax.tree.leaves(results["none"][0])[0], np.float64), axis=0).max())
    check(spread > 100 * mix_atol, f"ranks did not diverge ({spread})")
    check(np.allclose(results["none"][1], results["atc"][1], rtol=1e-3),
          f"losses differ: {results['none'][1]} vs {results['atc'][1]}")
    say("exchange", f"parity at {parity_layers} layers: atc one-peer "
        f"step == W0 @ (none step) on every leaf, max abs error "
        f"{worst:.1e} (tolerance {mix_atol}, rank spread {spread:.1e})")
    del results, tokens

    # ---- the README's first example, dynamic one-peer ---------------- #
    bf.init(devices=devices)
    try:
        x = bf.from_rank_values(lambda r: np.full((4,), float(r)))
        shift = 1
        while shift < n:
            x = bf.neighbor_allreduce(
                x, self_weight=0.5,
                src_weights=[{(r - shift) % n: 0.5} for r in range(n)],
                dst_weights=[[(r + shift) % n] for r in range(n)])
            shift *= 2
        x = np.asarray(x)
    finally:
        bf.shutdown()
    check(np.array_equal(x, np.full((n, 4), (n - 1) / 2.0, x.dtype)),
          f"one-peer sweep did not reach the exact mean: {x[:, 0]}")
    say("exchange", f"bf.neighbor_allreduce one-peer sweep over shifts "
        f"{[1 << i for i in range(n.bit_length() - 1)]}: exact mean "
        f"{x[0, 0]} on every rank")

    # ---- full-depth leg ---------------------------------------------- #
    full = dataclasses.replace(cfg, n_layers=full_layers)
    loss_fn = lm_loss(full)
    tokens = token_batch(full, mesh, batch, seq, seed)
    say("exchange", f"full depth: {full_layers} layers, remat="
        f"{full.remat}, batch {batch} x {seq} per chip")
    for mode, kwargs, collective in (
            ("atc", {"schedule": schedule}, "collective-permute"),
            ("gradient_allreduce", {}, "all-reduce")):
        step_fn = F.build_train_step(loss_fn, opt, mesh, comm_mode=mode,
                                     **kwargs)
        params, opt_state = init_train_state(full, opt, mesh, seed)
        if mode == "atc":
            jax.block_until_ready(params)
            for leaf in jax.tree.leaves(params):
                homes = {s.device for s in leaf.addressable_shards}
                check(homes == set(devices)
                      and all(s.data.shape[0] == 1
                              for s in leaf.addressable_shards),
                      f"a parameter leaf of shape {leaf.shape} is not "
                      f"one rank per device: {leaf.sharding}")
            in_use = [memory_stat(d, "bytes_in_use") for d in devices]
            say("exchange", "bytes_in_use per chip after init: "
                f"{[round(b / 2**30, 2) for b in in_use]} GiB")
            check(max(in_use) <= 1.25 * min(in_use),
                  f"state is not spread evenly over the chips: {in_use}")
        timing, text, need = compile_step(step_fn, params, opt_state,
                                             tokens)
        check(collective in text,
              f"the compiled {mode} step holds no {collective}")
        on_chip_path("tpu_custom_call" in text,
                     f"the compiled {mode} step holds no tpu_custom_call")
        del text
        params, opt_state, losses, first_s, step_s = run_steps(
            step_fn, params, opt_state, tokens, 1, steps - 1)
        check(bool(np.all(np.isfinite(losses))), f"{mode} losses {losses}")
        say("exchange", f"{mode}: {collective} in the program, "
            f"{timing}, {need / 2**30:.2f} GiB per chip, mean "
            f"losses {[round(float(x), 4) for x in losses.mean(axis=1)]}, "
            f"first call {first_s:.2f} s, then {step_s * 1e3:.1f} ms/step "
            f"= {n * batch * seq / step_s:.0f} tokens/s on {dev} "
            "(information, not a benchmark)")
        del params, opt_state, step_fn
    say("exchange", "peak_bytes_in_use per chip "
        f"{[round(memory_stat(d, 'peak_bytes_in_use') / 2**30, 2) for d in devices]}"
        " GiB")


# -------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the neighbor-exchange phase across four "
                    "chips and no other phase")
    args = ap.parse_args(argv)
    cache_dir = configure_compilation_cache()
    devices = check_device(args.chips, cache_dir)
    # the repo's "1b" Llama in bf16 compute.  Batch 4 x 2048 without
    # remat takes 14.80 GiB of a chip's 15.75 by the compiler's count,
    # and 14.85 with the exchange around it: full depth fits both ways
    cfg = models.LlamaConfig.llama_1b(dtype=jnp.bfloat16, remat=False)
    if args.chips == 4:
        exchange_phase(cfg, devices, batch=4, seq=2048, parity_layers=2,
                       full_layers=16)
    else:
        # a phase's arrays die with its frame: the next starts empty
        train_phase(cfg, devices, batch=4, seq=2048)
        serve_phase(cfg, devices, capacity=8, max_len=1024,
                    prefill_chunk=128, n_requests=12,
                    prompt_len=(64, 512), new_tokens=(16, 64))
    print(result_line(devices))
    return 0


if __name__ == "__main__":
    sys.exit(main())
