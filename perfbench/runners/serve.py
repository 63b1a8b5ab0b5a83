"""Runner of a serving cell: a ``ServingEngine`` under an open loop.

One thread submits every request whose due time has come, then calls
``engine.step()``, and sleeps to the next due time only when the engine
has nothing to do.  The traffic file fixes the engine's shape, the
arrival process and rate, and the length distributions; every run
offers the same requests at the same times (drawn from the traffic
file's ``schedule_seed``, scaled so that they span the window exactly),
and the run's seed gives the token ids and the weights.  Latencies are
taken by the benchmark from due times and from what it observes on the
engine's public request objects between steps.
"""

from __future__ import annotations

import contextlib
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.harness import arrivals, clocks, device as dev, program_trace
from perfbench.harness.peaks import peaks_for
from perfbench.runners.train import key_from_seed

TRACE_SECONDS = 4.0
CHECK_ROWS = 384          # the longest answer any mix may ask for


def say(text: str) -> None:
    print(f"[serve] {text}", flush=True)


# ------------------------------------------------------------------ #
# traffic
# ------------------------------------------------------------------ #
def schedule(traffic: dict, seconds: float, rate=None):
    """Due times (s, from the window's start) and lengths of the
    ``round(rate * seconds)`` requests of one window."""
    spec = dict(traffic["arrivals"])
    if rate is not None:
        spec["rate_per_s"] = rate
    n = max(2, int(round(spec["rate_per_s"] * seconds)))
    times, fields = arrivals.fixed_schedule(
        traffic["schedule_seed"], n, spec,
        {"prompt": traffic["prompt_len"], "output": traffic["output_len"]})
    # the same n requests span every window exactly
    times = times * (seconds * (n - 1) / n / times[-1])
    return times, fields["prompt"], fields["output"]


def make_requests(sz: dict, prompts, outputs, seed: int):
    from bluefog_tpu.serving import Request

    rng = np.random.default_rng([int(seed), 7])
    return [Request(rng.integers(0, sz["vocab_size"], int(p), np.int32),
                    int(o)) for p, o in zip(prompts, outputs)]


# ------------------------------------------------------------------ #
# the system
# ------------------------------------------------------------------ #
class Server:
    """The engine with its weights, warmed: the ONE object that set-up
    builds and the window drives."""

    def __init__(self, cell, seed: int, spans):
        family, traffic = cell.family(), cell.traffic
        self.sz = sz = family.sizes(cell.config, traffic["cut"])
        self.spans = spans
        self.key = jax.random.fold_in(key_from_seed(seed), 0)
        dtype = family.dtype_of(sz["param_dtype"])
        with spans.span("pb.compile.init"):
            # the key is an argument: closed over, it would be a
            # constant of the program and every new seed a new compile
            self.params = jax.jit(
                lambda key: family.make_params(sz, key, dtype)[0])(self.key)
            jax.block_until_ready(self.params)
        with spans.span("pb.compile.engine"):
            self.engine = family.serving_engine(sz, traffic, self.params)
            # one request through admission, two prefill chunks, decode
            # and retirement compiles everything the engine will run
            chunk = traffic["engine"]["prefill_chunk"]
            warm = make_requests(sz, [chunk + 8], [3], seed)[0]
            self.engine.submit(warm)
            self.engine.run()
        if not warm.done or len(warm.tokens) != 3:
            raise RuntimeError("the warm-up request did not complete")
        self.programs = {name: fn for name, (fn, _, _)
                         in self.engine._resident.items()}

    def cache_sizes(self) -> dict:
        return {k: fn._cache_size() for k, fn in self.programs.items()}

    def free(self) -> None:
        """Drop the engine (its cache slab) before the reference runs;
        the weights stay: they are the benchmark's own data."""
        self.engine = None
        self.programs = {}


class Trial:
    """What one window observed, per request."""

    def __init__(self, n: int):
        nan = np.full((n,), np.nan)
        self.submitted, self.slotted = nan.copy(), nan.copy()
        self.done_at = nan.copy()
        self.token_times = [[] for _ in range(n)]
        self.rejected = 0
        self.fired = []           # the window's time at which each hook ran
        self.live_tokens = []     # (time, cache positions in use)
        self.queue_depth = []     # (time, requests waiting)
        self.step_spans = []      # (start, end) of each engine.step()


@contextlib.contextmanager
def old_objects_frozen():
    """What set-up made is old and stays: kept out of the collector's
    scans, so that a full collection inside the window walks the
    window's own garbage and not every object JAX and Flax loaded."""
    gc.collect()
    gc.freeze()
    try:
        yield
    finally:
        gc.unfreeze()


def drive(server: Server, requests, due, seconds: float, drain_s: float,
          on_time=None):
    """One window of the open loop (``open_loop``), set-up's objects
    frozen out of the garbage collector's scans."""
    with old_objects_frozen():
        return open_loop(server, requests, due, seconds, drain_s, on_time)


def open_loop(server: Server, requests, due, seconds: float,
              drain_s: float, on_time=None):
    """The open loop.  ``due`` in seconds from now; stops when every
    request is done, or ``seconds + drain_s`` have passed.  ``on_time``:
    ``[(t, fn)]`` called once when the window's clock passes ``t``; the
    window's clock stands still while ``fn`` runs (starting or stopping
    the profiler stalls the loop for seconds: arrivals pause with it, so
    no backlog is made that the traffic does not hold).  A hook whose
    time has not come when the loop ends runs then, in order: a schedule
    that drains before ``seconds`` closes its trace at the drain, and
    the engine is not kept idling for a tail that no span would own."""
    engine, spans = server.engine, server.spans
    n = len(requests)
    trial = Trial(n)
    seen = [0] * n            # tokens already stamped
    live = {}                 # index -> request, submitted and not done
    hooks = sorted(on_time or [], key=lambda h: h[0])
    nxt = 0
    start = clocks.now()

    class Clock:
        paused = 0.0

        def now(self):
            return clocks.now() - start - self.paused

    clock = Clock()

    def fire(every: bool = False):
        """Run the queued hooks whose time has come (``every``: all that
        are left), the window's clock paused."""
        while hooks and (every or clock.now() >= hooks[0][0]):
            trial.fired.append(clock.now())
            began = clocks.now()
            hooks.pop(0)[1]()
            clock.paused += clocks.now() - began

    while True:
        fire()
        now = clock.now()
        if now > seconds + drain_s:
            break
        with spans.span("pb.submit"):
            while nxt < n and due[nxt] <= now:
                try:
                    engine.submit(requests[nxt])
                    live[nxt] = requests[nxt]
                except Exception as e:  # RequestRejected, or no fit
                    trial.rejected += 1
                    say(f"request {nxt} refused: {e}")
                trial.submitted[nxt] = clock.now()
                nxt += 1
        if live:
            s = clock.now()
            with spans.span("pb.engine_step"):
                engine.step()
            t = clock.now()
            trial.step_spans.append((s, t))
            with spans.span("pb.observe"):
                in_use = 0
                for i in list(live):
                    r = live[i]
                    if np.isnan(trial.slotted[i]) and (
                            r.slot is not None or r.tokens):
                        trial.slotted[i] = t
                    fresh = len(r.tokens) - seen[i]
                    if fresh:
                        trial.token_times[i].extend([t] * fresh)
                        seen[i] += fresh
                    if r.slot is not None:
                        in_use += r.prompt.size + len(r.tokens)
                    if r.done:
                        trial.done_at[i] = t
                        del live[i]
                trial.live_tokens.append((t, in_use))
                trial.queue_depth.append(
                    (t, engine.scheduler.queue_depth))
        elif nxt >= n:
            break
        else:
            with spans.span("pb.idle_wait"):
                time.sleep(max(0.0, due[nxt] - clock.now()))
    if hooks:
        say(f"the loop ended at {clock.now():.3f} s of the window with "
            f"{len(hooks)} hook(s) not yet due: run now")
    fire(every=True)
    return trial


def summarize(trial: Trial, requests, due, seconds: float) -> dict:
    """The window's numbers.  Latencies are over every request due in
    the window (all of them), the token rate over every token stamped
    inside it: all the work and all the time of the window."""
    n = len(requests)
    ok = np.array([r.state == "completed"
                   and len(r.tokens) == r.max_new_tokens
                   for r in requests])
    first = np.array([t[0] if t else np.nan for t in trial.token_times])
    sel = ~np.isnan(first)
    ttft = (first - due)[sel]
    gaps = np.concatenate(
        [np.diff(t) for t in trial.token_times if len(t) > 1]
        or [np.zeros(0)])
    tokens = sum(int(np.sum(np.asarray(t) <= seconds))
                 for t in trial.token_times)
    qwait = (trial.slotted - due)[~np.isnan(trial.slotted)]
    late = (trial.submitted - due)[~np.isnan(trial.submitted)]
    depth = np.array(trial.queue_depth or [(0.0, 0)])
    half = depth[:, 0] < seconds / 2
    return {
        "attempted": n, "failed": int(n - ok.sum()),
        "completed_share": float(ok.mean()),
        "serve_tokens_per_s": tokens / seconds,
        "ttft_ms": 1e3 * ttft, "itl_ms": 1e3 * gaps,
        "queue_wait_ms": 1e3 * qwait, "late_ms": 1e3 * late,
        "queue_depth_halves": (
            float(depth[half, 1].mean()) if half.any() else 0.0,
            float(depth[~half & (depth[:, 0] <= seconds), 1].mean())
            if (~half & (depth[:, 0] <= seconds)).any() else 0.0),
    }


# ------------------------------------------------------------------ #
# the output check
# ------------------------------------------------------------------ #
def reference_program(cell, sz: dict):
    """``f(params, tokens [max_len], rows [CHECK_ROWS], mm) -> logits
    [CHECK_ROWS, vocab]`` of the plain reference, jitted per ``mm``."""
    ref = cell.reference()

    def f(params, tokens, rows, control=False):
        mm = ref.mm_control if control else ref.mm_highest
        return ref.logits(params, tokens, sz, mm, rows=rows)

    return jax.jit(f, static_argnames=("control",))


def logit_gaps(cell, sz: dict, params, requests, sample,
               control: bool = False):
    """For each sampled finished request, run the reference once over
    its prompt and served tokens and read, at every served position,
    how far the served token's reference logit lies below the
    reference's best, in units of that position's logit standard
    deviation.  With ``control``, the token read is the one the lower
    precision puts first at that position instead of the served one.
    Returns the widest gap and the number of positions read."""
    program = reference_program(cell, sz)
    max_len = cell.traffic["engine"]["max_len"]
    widest, read = 0.0, 0
    for i in sample:
        r = requests[i]
        served = np.asarray(r.tokens, np.int32)
        g, p = served.size, r.prompt.size
        seq = np.zeros((max_len,), np.int32)
        seq[:p + g - 1] = np.concatenate([r.prompt, served])[:-1]
        rows = np.full((CHECK_ROWS,), p - 1, np.int32)
        rows[:g] = p - 1 + np.arange(g)
        want = np.asarray(program(params, jnp.asarray(seq),
                                  jnp.asarray(rows)))[:g]
        if control:
            low = np.asarray(program(params, jnp.asarray(seq),
                                     jnp.asarray(rows), control=True))[:g]
            tokens = low.argmax(-1)
        else:
            tokens = served
        gap = (want.max(-1) - want[np.arange(g), tokens]) / want.std(-1)
        widest = max(widest, float(gap.max()))
        read += g
    return widest, read


def check_sample(requests, seed: int, k: int):
    """``k`` finished requests drawn from the seed, the longest among
    them."""
    done = [i for i, r in enumerate(requests)
            if r.state == "completed" and r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda i: requests[i].prompt.size
                  + len(requests[i].tokens))
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng([int(seed), 11])
    picked = rng.permutation(rest)[:max(0, k - 1)].tolist()
    return [longest] + picked


# ------------------------------------------------------------------ #
# the traced stretch
# ------------------------------------------------------------------ #
def trace_hooks(trace_dir: str, seconds: float):
    """``(hooks, counters)`` of a traced window: ``open_loop``'s hooks
    that start the profiler ``TRACE_SECONDS`` (half the window, where it
    is shorter) before ``seconds`` and stop it at ``seconds``, or when
    the loop ends; and the ``CounterWindow`` that holds the program's
    counters as they stood at those two edges, for the readers that
    divide a count by the stretch's device time."""
    t_on = max(0.0, seconds - min(TRACE_SECONDS, seconds / 2))
    counters = program_trace.CounterWindow()
    window = []

    def start():
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        # made once the profiler runs: an annotation made before it
        # records nothing
        window.append(jax.profiler.TraceAnnotation("pb.trace_window"))
        window[0].__enter__()
        counters.open()

    def stop():
        counters.close()
        window[0].__exit__(None, None, None)
        jax.profiler.stop_trace()

    return [(t_on, start), (seconds, stop)], counters


def mean_live_tokens(trial: Trial):
    """Mean cache positions in use after the engine steps of the traced
    stretch, from when ``trace_hooks``' first hook ran to when its
    second did (of the whole trial where no hook ran), or None."""
    lo, hi = (trial.fired[0], trial.fired[-1]) if trial.fired \
        else (-np.inf, np.inf)
    live = [n for t, n in trial.live_tokens if lo <= t <= hi]
    return float(np.mean(live)) if live else None


# ------------------------------------------------------------------ #
def run(cell, seed: int, seconds: float, trace: bool, devices, spans,
        t_start: float, trace_dir: str):
    traffic = cell.traffic
    server = Server(cell, seed, spans)
    sz = server.sz
    due, prompts, outputs = schedule(traffic, seconds)
    requests = make_requests(sz, prompts, outputs, seed + 1)
    say(f"{len(requests)} requests over {seconds:.0f} s at "
        f"{len(requests) / seconds:.3f}/s; prompts {prompts.min()}-"
        f"{prompts.max()} (median {int(np.median(prompts))}), outputs "
        f"{outputs.min()}-{outputs.max()} (median "
        f"{int(np.median(outputs))}); decode_attn resolved to "
        f"{server.engine.cfg.decode_attn!r}")
    sizes0 = server.cache_sizes()
    say("set-up spans: " + ", ".join(
        f"{name[3:]} {e - s:.1f} s" for name, s, e in spans.records
        if name.startswith("pb.compile.")))
    hooks, counters = trace_hooks(trace_dir, seconds) if trace \
        else ([], None)
    setup_s = clocks.now() - t_start
    trial = drive(server, requests, due, seconds, traffic["drain_s"],
                  hooks)
    grew = server.cache_sizes() != sizes0
    if grew:
        say(f"a resident program compiled in the window: {sizes0} -> "
            f"{server.cache_sizes()}")
    stats = summarize(trial, requests, due, seconds)
    say(f"completed {stats['completed_share']:.3f} of "
        f"{stats['attempted']}, refused {trial.rejected}; ttft samples "
        f"{stats['ttft_ms'].size}, gap samples {stats['itl_ms'].size}; "
        f"mean queue depth first half {stats['queue_depth_halves'][0]:.2f}"
        f", second half {stats['queue_depth_halves'][1]:.2f}; ttft p50 "
        f"{clocks.percentile(stats['ttft_ms'], 50):.1f} ms")
    steps_ms = [1e3 * (e - s) for s, e in trial.step_spans if e <= seconds]
    if steps_ms:
        # the host loop sets the pace: a slow host shows in the median, a
        # stall of the machine in the longest step
        say(f"engine.step() on the host clock: median "
            f"{clocks.percentile(steps_ms, 50):.2f} ms, p95 "
            f"{clocks.percentile(steps_ms, 95):.2f} ms, longest "
            f"{max(steps_ms):.1f} ms over {len(steps_ms)} steps in the "
            "window")
    peak = max(dev.memory_stat(d, "peak_bytes_in_use") for d in devices)
    server.free()

    t0 = clocks.now()
    sample = check_sample(requests, seed, traffic["check_requests"])
    limit = traffic["limits"]["logit_gap"]["limit"]
    if sample:
        widest, read = logit_gaps(cell, sz, server.params, requests, sample)
    else:
        widest, read = float("inf"), 0
    say(f"check: logit_gap = {widest:.6g} (limit {limit:.6g}) over {read} "
        f"served tokens of {len(sample)} requests, reference "
        f"{clocks.now() - t0:.1f} s (not counted in setup_s)")

    e2e = {"setup_s": setup_s,
           "serve_tokens_per_s": stats["serve_tokens_per_s"]}
    if stats["ttft_ms"].size:
        e2e["ttft_p95_ms"] = clocks.percentile(stats["ttft_ms"], 95)
    if stats["itl_ms"].size:
        e2e["itl_p95_ms"] = clocks.percentile(stats["itl_ms"], 95)
    return {
        "correct": bool(widest <= limit and stats["failed"] == 0
                        and not grew),
        "attempted": stats["attempted"], "failed": stats["failed"],
        "checked": {"logit_gap": (widest, limit)},
        "end_to_end": e2e, "program_bytes": peak,
        "ctx": {
            "peaks": peaks_for(devices[0].device_kind)
            if dev.PLATFORM == "tpu" else None,
            "sizes": sz, "traffic": traffic, "chips": 1,
            "reference": cell.reference(), "serve": stats,
            "engine_steps": [(s, e) for s, e in trial.step_spans
                             if e <= seconds],
            "live_tokens_mean": mean_live_tokens(trial),
            "counter_window": counters,
        },
    }
