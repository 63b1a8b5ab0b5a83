"""Runner of a training job: ``build_train_step`` over one rank a chip,
driven for a window of whole steps.

The traffic file gives ``cut`` (where the configuration has cuts),
``step`` (the keywords ``build_train_step`` is called with, see
``resolve``), ``exchange`` and ``optimizer`` (whose names are files
under ``exchanges/`` and ``optimizers/``: what the reference mixes and
updates by), the per-chip batch and the limits of the output check.
The family gives the weights, the batch, the program's loss and the
reference's loss.  No name of a mode, graph or optimizer is known here:
a later cell brings its own as data.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perfbench.harness import clocks, device as dev, reference_train
from perfbench.harness.peaks import peaks_for

CHECK_STEPS = 2      # steps the reference follows, all before the window
#                      (two, not three: float32 at HIGHEST is ~10 s a step)
TRACE_SECONDS = 4.0  # the traced stretch, after the timed window


def say(text: str) -> None:
    print(f"[train] {text}", flush=True)


def key_from_seed(seed: int):
    """A PRNG key from any whole number, above 2**32 too."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def resolve(value, env: dict):
    """A traffic file's value made into what the program takes:
    ``"$name"`` becomes ``env[name]``, and ``{"call":
    "module:function", "args": [...], "kwargs": {...}}`` becomes what
    the call returns, innermost first.  So a schedule or a graph is
    named in the file by the program's own public function."""
    if isinstance(value, str) and value.startswith("$"):
        return env[value[1:]]
    if isinstance(value, list):
        return [resolve(v, env) for v in value]
    if isinstance(value, dict):
        if "call" not in value:
            return {k: resolve(v, env) for k, v in value.items()}
        module, _, name = value["call"].partition(":")
        fn = getattr(importlib.import_module(module), name)
        return fn(*resolve(value.get("args", []), env),
                  **resolve(value.get("kwargs", {}), env))
    return value


def program_optimizer(opt: dict):
    """``optax.<name>`` called with the file's other keys."""
    import optax

    spec = dict(opt)
    return getattr(optax, spec.pop("name"))(**spec)


def build_step(cell, mesh):
    """The program's step for the cell over ``mesh``, through the
    program's own entry.  Returns ``(step, optimizer, has_aux)``."""
    from bluefog_tpu.optim import functional as F

    traffic = cell.traffic
    opt = program_optimizer(traffic["optimizer"])
    loss_fn, has_aux = cell.family().train_loss(cut_sizes(cell), traffic)
    step = F.build_train_step(
        loss_fn, opt, mesh, has_aux=has_aux,
        **resolve(traffic["step"], {"ranks": mesh.devices.size}))
    return step, opt, has_aux


def reference_rules(cell):
    """The reference's optimizer and exchange, by the traffic file's
    names."""
    traffic = cell.traffic
    return (cell.module("optimizers", traffic["optimizer"]["name"]),
            cell.module("exchanges", traffic["exchange"]))


def cut_sizes(cell):
    family = cell.family()
    return family.sizes(cell.config, cell.traffic.get("cut"))


class Job:
    """The compiled step with its state: the ONE object that set-up
    builds, checks and hands to the window."""

    def __init__(self, cell, seed: int, devices, spans):
        family, traffic = cell.family(), cell.traffic
        self.sz = sz = cut_sizes(cell)
        self.n = n = len(devices)
        self.spans = spans
        self.mesh = Mesh(np.array(devices), ("bf",))
        rank = NamedSharding(self.mesh, P("bf"))
        self.opt_spec = traffic["optimizer"]
        self.rule, self.exchange = reference_rules(cell)
        self.step_fn, opt, self.has_aux = build_step(cell, self.mesh)
        self.make_state = lambda key: family.make_params(sz, key,
                                                         jnp.float32)

        def init(key):
            # F.rank_major_init's own arithmetic (one traced init, every
            # leaf broadcast to the ranks, outputs born rank-sharded),
            # with the key an ARGUMENT: closed over, it would be a
            # constant of the program and every new seed a new compile
            params, aux = self.make_state(key)
            tree = {"params": params, "aux": aux, "opt": opt.init(params)}
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)

        self._batch = jax.jit(
            lambda key: family.make_batch(sz, traffic, key, n),
            out_shardings=rank)
        self._init = jax.jit(init, out_shardings=rank)
        self.items_per_step_per_chip = family.items_per_rank_step(
            sz, traffic)
        with spans.span("pb.compile.init"):
            self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """The seeded state and batch, as a new job has them (the
        readings of many seeds go through one job: the key is an
        argument, so nothing compiles again)."""
        key = key_from_seed(seed)
        self.k_params = jax.random.fold_in(key, 0)
        self.state = None    # free the old state first
        self.batch = self._batch(jax.random.fold_in(key, 1))
        state = self._init(self.k_params)
        jax.block_until_ready(state)
        self.state = (state["params"], state["aux"], state["opt"])
        self.calls = 0

    def args(self):
        params, aux, opt_state = self.state
        step = np.int32(self.calls)
        if self.has_aux:
            return (params, aux, opt_state, self.batch, step)
        return (params, opt_state, self.batch, step)

    def compile(self):
        """Ahead-of-time compile of the step's own program, for the
        compiler's byte count and the module text; the jitted call then
        finds the executable in the persistent cache."""
        with self.spans.span("pb.compile.step"):
            compiled = self.step_fn.lower(*self.args()).compile()
        return compiled

    def call(self):
        """One step through the program's own entry.  Returns the
        per-rank loss (not waited for)."""
        out = self.step_fn(*self.args())
        self.calls += 1
        if self.has_aux:
            params, aux, opt_state, loss = out
        else:
            (params, opt_state, loss), aux = out, None
        self.state = (params, aux, opt_state)
        return loss

    def cache_size(self) -> int:
        return self.step_fn.jitted._cache_size()


# ------------------------------------------------------------------ #
# the output check
# ------------------------------------------------------------------ #
MIX_ELEMENTS = 4096   # of every leaf, as many leading elements are
#                       compared one by one with the exchange's mix


def moment_trees(opt_state, params):
    """The sub-trees of an optax state that have the parameters' own
    structure, in order: Adam's ``mu`` and ``nu``, SGD's momentum
    trace."""
    want = jax.tree.structure(params)
    found = [x for x in jax.tree.leaves(
        opt_state, is_leaf=lambda x: jax.tree.structure(x) == want)
        if jax.tree.structure(x) == want]
    if not found:
        raise ValueError("no first moment in the optimizer state")
    return found


def program_readings(job: Job, n_steps: int = CHECK_STEPS):
    """Drive the job's own step ``n_steps`` times from its seeded state
    and read what the reference reads, and beside it how far each
    step's parameters lie from the exchange's mix of the ranks' own
    updates (``reference_train.mix_gap``, on the leading
    ``MIX_ELEMENTS`` of every leaf)."""
    scale = job.rule.first_gradient_scale(job.opt_spec)
    norms = jax.jit(reference_train.leaf_norms)
    rounds = reference_train.rounds_of(job.exchange, job.n)
    if job.exchange.MIXES != "parameters":
        rounds = [np.eye(job.n)]   # the moments hold what was mixed

    @jax.jit
    def update_norms(p, key):
        p0, _ = job.make_state(key)
        return reference_train.leaf_norms(
            jax.tree.map(lambda a, b: a - b[None], p, p0))

    @jax.jit
    def leading(tree):
        return jax.tree.map(
            lambda x: x.reshape(x.shape[0], -1)[:, :MIX_ELEMENTS], tree)

    def fetch(tree):
        return jax.tree.leaves(jax.device_get(leading(tree)))

    losses, grad_norms, mixes = [], None, []
    before = fetch(job.state[0])
    for i in range(n_steps):
        with job.spans.span("pb.compile.first_call" if i == 0
                            else "pb.check_step"):
            losses.append(np.asarray(job.call()))
        moments = moment_trees(job.state[2], job.state[0])
        if i == 0:
            grad_norms = [np.asarray(x) * scale
                          for x in jax.tree.leaves(norms(moments[0]))]
        after, m = fetch(job.state[0]), fetch(moments[0])
        # (a rule with one moment takes no notice of the second)
        v = fetch(moments[1]) if len(moments) > 1 else m
        mixes.append((rounds[i % len(rounds)], before, after, m, v, i + 1))
        before = after
    upd = [np.asarray(x) for x in jax.tree.leaves(
        update_norms(job.state[0], job.k_params))]
    return {"losses": np.stack(losses), "grad_norms": grad_norms,
            "update_norms": upd, "mixes": mixes,
            "mix_abs_gap": max(reference_train.mix_gap(
                job.opt_spec, job.rule, *mix) for mix in mixes)}


def reference_readings(cell, seed: int, devices, control: bool = False,
                       n_steps: int = CHECK_STEPS):
    """What the plain reference reads on the same seeded weights and
    batch; ``control`` computes it in the precision below the
    configuration's."""
    family, ref, traffic = cell.family(), cell.reference(), cell.traffic
    sz = cut_sizes(cell)
    n = len(devices)
    key = key_from_seed(seed)
    k_params, k_batch = jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)
    mesh = Mesh(np.array(devices), ("r",))
    batch = jax.jit(lambda key: family.make_batch(sz, traffic, key, n),
                    out_shardings=NamedSharding(mesh, P("r")))(k_batch)
    mm = ref.mm_control if control else ref.mm_highest

    def loss_fn(params, aux, rank_batch):
        return ref.loss(params, aux, rank_batch, sz, mm)

    return reference_train.follow(
        loss_fn, lambda key: family.make_params(sz, key, jnp.float32),
        k_params, batch, traffic["optimizer"], *reference_rules(cell),
        n_steps, devices)


def compare(got: dict, want: dict, limits: dict):
    """The numbers compared, each beside its limit: ``{name: (value,
    limit)}`` and whether all hold.  Every cell is held to the three
    numbers against the reference; ``mix_abs_gap`` (which needs no
    reference, and which the reference in the program's place does not
    give) is held where the traffic file sets it a limit."""
    steps = min(len(got["losses"]), len(want["losses"]))
    numbers = {
        "loss_rel_gap": float(np.max(
            np.abs(got["losses"][:steps] - want["losses"][:steps])
            / np.abs(want["losses"][:steps]))),
        "grad_norm_gap": reference_train.worst_leaf_gap(
            got["grad_norms"], want["grad_norms"]),
        "update_norm_gap": reference_train.worst_leaf_gap(
            got["update_norms"], want["update_norms"]),
    }
    if "mix_abs_gap" in got and "mix_abs_gap" in limits:
        numbers["mix_abs_gap"] = got["mix_abs_gap"]
    out = {k: (v, limits[k]["limit"]) for k, v in numbers.items()}
    ok = all(np.isfinite(v) and v <= lim for v, lim in out.values())
    return out, ok


def say_numbers(numbers: dict, who: str) -> None:
    for name, (value, limit) in numbers.items():
        say(f"check {who}: {name} = {value:.6g} (limit {limit:.6g})"
            f"{'' if value <= limit else '  <-- over'}")


# ------------------------------------------------------------------ #
# the window
# ------------------------------------------------------------------ #
def drive(job: Job, seconds: float):
    """Whole steps for ``seconds``: the host runs one step ahead of the
    device, waits for the step before, and stops dispatching once the
    time is up; the stretch ends when the last step's outputs are
    ready.  Returns (steps, elapsed seconds, completion stamps)."""
    spans = job.spans
    jax.block_until_ready(job.state)
    stamps, pending, steps = [], None, 0
    t0 = clocks.now()
    while True:
        with spans.span("pb.step_dispatch"):
            loss = job.call()
        steps += 1
        if pending is not None:
            with spans.span("pb.step_wait"):
                jax.block_until_ready(pending)
            stamps.append(clocks.now())
            if stamps[-1] - t0 >= seconds:
                break
        pending = loss
    with spans.span("pb.step_wait"):
        jax.block_until_ready((job.state, loss))
    return steps, clocks.now() - t0, stamps


def traced_stretch(job: Job, seconds: float, log_dir: str):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        with job.spans.span("pb.trace_window"):
            drive(job, seconds)
    finally:
        jax.profiler.stop_trace()


def run(cell, seed: int, seconds: float, trace: bool, devices, spans,
        t_start: float, trace_dir: str):
    """One run of the cell.  Returns the harness's result record."""
    limits = cell.traffic["limits"]
    t0 = clocks.now()
    want = reference_readings(cell, seed, devices)
    reference_s = clocks.now() - t0
    say(f"reference followed {CHECK_STEPS} steps in {reference_s:.1f} s "
        "(not counted in setup_s)")

    job = Job(cell, seed, devices, spans)
    compiled = job.compile()
    need = dev.program_bytes(compiled)
    text = compiled.as_text()
    kernel_ok = True
    if cell.traffic.get("expect_kernel") and dev.PLATFORM == "tpu":
        kernel_ok = "tpu_custom_call" in text
        say(f"tpu_custom_call in the compiled step: {kernel_ok}")
    n_collectives = text.count(" collective-permute")
    del text, compiled
    say(f"the step program needs {need / 2**30:.2f} GiB a chip by the "
        f"compiler's count; {job.n} rank(s), "
        f"{job.items_per_step_per_chip} {cell.config['item']}s a step a "
        f"chip; collective-permute ops in the module: {n_collectives}")

    got = program_readings(job)
    numbers, ok = compare(got, want, limits)
    say_numbers(numbers, "program vs reference")
    if "mix_abs_gap" not in numbers:
        say(f"mix_abs_gap = {got['mix_abs_gap']:.6g} (no limit in this "
            "cell: not compared)")
    say(f"losses program {got['losses'][:, 0].tolist()} reference "
        f"{want['losses'][:, 0].tolist()}")
    cache0 = job.cache_size()
    say("set-up spans: " + ", ".join(
        f"{name[3:]} {e - s:.1f} s" for name, s, e in spans.records
        if name.startswith("pb.compile.")))

    setup_s = (clocks.now() - t_start) - reference_s
    steps, elapsed, stamps = drive(job, seconds)
    rate = steps * job.items_per_step_per_chip / elapsed
    say(f"window: {steps} steps in {elapsed:.3f} s"
        + (f"; longest wait between two steps' ends "
           f"{1e3 * float(np.max(np.diff(stamps))):.1f} ms"
           if len(stamps) > 1 else ""))
    if trace:
        traced_stretch(job, min(TRACE_SECONDS, seconds), trace_dir)
    compiled_in_window = job.cache_size() != cache0
    if compiled_in_window:
        say(f"the step's jit cache grew in the window: {cache0} -> "
            f"{job.cache_size()}")
    final = np.asarray(job.call())
    finite = bool(np.all(np.isfinite(final)))
    say(f"loss after the window {final.tolist()}")

    peaks = peaks_for(devices[0].device_kind) \
        if dev.PLATFORM == "tpu" else None
    ref = cell.reference()
    return {
        "correct": bool(ok and kernel_ok and finite
                        and not compiled_in_window),
        "attempted": steps, "failed": 0, "checked": numbers,
        "end_to_end": {"setup_s": setup_s, "train_rate_per_chip": rate},
        "program_bytes": need,
        "ctx": {
            "peaks": peaks, "sizes": job.sz, "traffic": cell.traffic,
            "chips": job.n, "stamps": stamps, "rate_per_chip": rate,
            "items_per_step_per_chip": job.items_per_step_per_chip,
            "flops_per_item": ref.train_flops_per_item(job.sz,
                                                       cell.traffic),
            "reference": ref,
        },
    }
