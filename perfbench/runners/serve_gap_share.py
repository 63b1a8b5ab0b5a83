"""Runner of a serving cell whose output check also limits HOW MANY
served tokens differ from the reference's, and reads answers as long
as the mix's longest.

The open loop, the schedule, the requests, the window's numbers and the
choice of the checked requests are ``runners/serve.py``'s, imported and
unchanged; this file differs from it in the check alone, and ``run`` is
that file's ``run`` around it (PERF.md section 7 asks the next
``benchmark`` issue to fold the two into one).

Why: ``serve.logit_gaps`` returns the WIDEST gap over the served
positions.  A model that routes tokens to experts differs from its
float32 reference at a few positions by a whole expert (a near-tie
among the router's scores falls the other way) and at the others by
rounding; the fp8 control differs at every position by about as much
as one flipped expert.  The maximum over positions cannot tell few such
positions from many; their SHARE can.  The limits of the traffic file::

    "limits": {"logit_gap": {"limit": ...},   the widest gap, as serve.py
               "gap_share": {"limit": ...}}   the share of served positions
                                              whose gap is not zero: the
                                              served token is not the
                                              reference's first

``correct`` needs both (and no failed request, no compile in the
window).  And ``serve.CHECK_ROWS`` = 384 rows are read of a checked
answer, so that no mix may ask for more; here the rows are the mix's
``output_len.max``.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from perfbench.harness import clocks, device as dev
from perfbench.harness.peaks import peaks_for
from perfbench.runners.serve import (TRACE_SECONDS, Server,  # noqa: F401
                                     check_sample, drive, key_from_seed,
                                     make_requests, mean_live_tokens,
                                     reference_program, say, schedule,
                                     summarize, trace_hooks)


# ------------------------------------------------------------------ #
# the output check
# ------------------------------------------------------------------ #
def position_gaps(cell, sz: dict, params, requests, sample,
                  control: bool = False):
    """For each sampled finished request, run the reference once over
    its prompt and served tokens and read, at every served position,
    how far the served token's reference logit lies below the
    reference's best, in units of that position's logit standard
    deviation (``serve.logit_gaps``'s quantity).  With ``control``, the
    token read is the one the lower precision puts first at that
    position instead of the served one.  Returns the gaps of all read
    positions, one array."""
    program = reference_program(cell, sz)
    max_len = cell.traffic["engine"]["max_len"]
    n_rows = cell.traffic["output_len"]["max"]
    gaps = [np.zeros(0)]
    for i in sample:
        r = requests[i]
        served = np.asarray(r.tokens, np.int32)
        g, p = served.size, r.prompt.size
        seq = np.zeros((max_len,), np.int32)
        seq[:p + g - 1] = np.concatenate([r.prompt, served])[:-1]
        rows = np.full((n_rows,), p - 1, np.int32)
        rows[:g] = p - 1 + np.arange(g)
        want = np.asarray(program(params, jnp.asarray(seq),
                                  jnp.asarray(rows)))[:g]
        if control:
            low = np.asarray(program(params, jnp.asarray(seq),
                                     jnp.asarray(rows), control=True))[:g]
            tokens = low.argmax(-1)
        else:
            tokens = served
        gaps.append((want.max(-1) - want[np.arange(g), tokens])
                    / want.std(-1))
    return np.concatenate(gaps)


def readings(gaps) -> dict:
    """The numbers the limits hold, from the gaps of the read positions
    (none read: nothing was served, and nothing passes)."""
    if not gaps.size:
        return {"logit_gap": float("inf"), "gap_share": 1.0}
    return {"logit_gap": float(gaps.max()),
            "gap_share": float((gaps > 0).mean())}


def compare(numbers: dict, limits: dict):
    """``({name: (reading, limit)}, every reading inside its limit)``."""
    pairs = {k: (numbers[k], limits[k]["limit"]) for k in numbers}
    return pairs, all(v <= lim for v, lim in pairs.values())


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
        f"{int(np.median(outputs))})")
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
        say(f"engine.step() on the host clock: median "
            f"{clocks.percentile(steps_ms, 50):.2f} ms, p95 "
            f"{clocks.percentile(steps_ms, 95):.2f} ms, longest "
            f"{max(steps_ms):.1f} ms over {len(steps_ms)} steps in the "
            "window")
    peak = max(dev.memory_stat(d, "peak_bytes_in_use") for d in devices)
    server.free()

    t0 = clocks.now()
    sample = check_sample(requests, seed, traffic["check_requests"])
    gaps = position_gaps(cell, sz, server.params, requests, sample)
    numbers, inside = compare(readings(gaps), traffic["limits"])
    say("check: " + ", ".join(
        f"{k} = {v:.6g} (limit {lim:.6g})" for k, (v, lim)
        in numbers.items()) + f" over {gaps.size} served tokens of "
        f"{len(sample)} requests, reference {clocks.now() - t0:.1f} s "
        "(not counted in setup_s)")

    e2e = {"setup_s": setup_s,
           "serve_tokens_per_s": stats["serve_tokens_per_s"]}
    if stats["ttft_ms"].size:
        e2e["ttft_p95_ms"] = clocks.percentile(stats["ttft_ms"], 95)
    if stats["itl_ms"].size:
        e2e["itl_p95_ms"] = clocks.percentile(stats["itl_ms"], 95)
    return {
        "correct": bool(inside and stats["failed"] == 0 and not grew),
        "attempted": stats["attempted"], "failed": stats["failed"],
        "checked": numbers,
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
