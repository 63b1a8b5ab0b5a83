"""Per-conv roofline profile of ResNet-50's forward/backward on one chip.

Times every distinct (shape, stride) conv in the batch-128 ResNet-50 step
three ways — forward, input-gradient (dgrad), weight-gradient (wgrad) —
using ``jax.linear_transpose`` so each backward op is measured in
isolation.  The op under test is iterated inside ONE jitted ``lax.scan``
(a tiny output-dependent perturbation chains iterations and defeats CSE),
because a host dispatch per call would swamp sub-millisecond convs.

Output: a table sorted by total backward wall-clock weighted by how many
times the conv appears in the model, pinpointing where the 33%-MFU
backward wall actually is (round-2 verdict item 1).

Run on the chip: PYTHONPATH=. python benchmarks/profile_resnet_convs.py \
    [--iters 24]
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bluefog_tpu.benchutil import chip_peak_flops, device_fetch, fetch_overhead

B = 128

# (name, count, H, W, Cin, Cout, K, stride) — batch-128 ResNet-50 with the
# space-to-depth stem; counts are appearances per train step.
CONVS = [
    ("stem4x4", 1, 112, 112, 12, 64, 4, 1),
    # layer1 @56 (3 blocks; first block input is 64ch from maxpool)
    ("l1.1x1a_first", 1, 56, 56, 64, 64, 1, 1),
    ("l1.1x1a", 2, 56, 56, 256, 64, 1, 1),
    ("l1.3x3", 3, 56, 56, 64, 64, 3, 1),
    ("l1.1x1b", 3, 56, 56, 64, 256, 1, 1),
    ("l1.proj", 1, 56, 56, 64, 256, 1, 1),
    # layer2: 56->28 (4 blocks)
    ("l2.1x1a_first", 1, 56, 56, 256, 128, 1, 1),
    ("l2.3x3_s2", 1, 56, 56, 128, 128, 3, 2),
    ("l2.proj_s2", 1, 56, 56, 256, 512, 1, 2),
    ("l2.1x1a", 3, 28, 28, 512, 128, 1, 1),
    ("l2.3x3", 3, 28, 28, 128, 128, 3, 1),
    ("l2.1x1b", 4, 28, 28, 128, 512, 1, 1),
    # layer3: 28->14 (6 blocks)
    ("l3.1x1a_first", 1, 28, 28, 512, 256, 1, 1),
    ("l3.3x3_s2", 1, 28, 28, 256, 256, 3, 2),
    ("l3.proj_s2", 1, 28, 28, 512, 1024, 1, 2),
    ("l3.1x1a", 5, 14, 14, 1024, 256, 1, 1),
    ("l3.3x3", 5, 14, 14, 256, 256, 3, 1),
    ("l3.1x1b", 6, 14, 14, 256, 1024, 1, 1),
    # layer4: 14->7 (3 blocks)
    ("l4.1x1a_first", 1, 14, 14, 1024, 512, 1, 1),
    ("l4.3x3_s2", 1, 14, 14, 512, 512, 3, 2),
    ("l4.proj_s2", 1, 14, 14, 1024, 2048, 1, 2),
    ("l4.1x1a", 2, 7, 7, 2048, 512, 1, 1),
    ("l4.3x3", 2, 7, 7, 512, 512, 3, 1),
    ("l4.1x1b", 3, 7, 7, 512, 2048, 1, 1),
]


def conv_fn(k, stride):
    pad = "SAME" if k > 1 else "VALID"
    if k == 4:  # space-to-depth stem padding
        pad = [(2, 1), (2, 1)]

    def f(x, w):
        return lax.conv_general_dilated(
            x, w, window_strides=(stride, stride), padding=pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return f


def chained(op, iters):
    """jit(op iterated `iters` times): each iteration's input is nudged by
    a bounded output-dependent epsilon — sequential dependence, no CSE,
    ONE host dispatch for the whole chain."""

    def many(a0):
        def body(a, _):
            out = op(a)
            s = jnp.tanh(jnp.sum(out.astype(jnp.float32))) * 1e-20
            return a + s.astype(a.dtype), None

        a, _ = lax.scan(body, a0, None, length=iters)
        return jnp.sum(a.astype(jnp.float32))

    return jax.jit(many)


def time_chain(fn, a0, iters, repeats=3):
    """Per-iteration seconds by DIFFERENCING: enqueue k chain calls
    before one fetch, for k=1 and k=5; the fetch and dispatch overheads
    cancel in (T5 - T1) / 4."""
    device_fetch(fn(a0))  # compile

    def run(k, reps):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = None
            for _ in range(k):
                out = fn(a0)
            device_fetch(out)
            best = min(best, time.perf_counter() - t0)
        return best

    for reps in (repeats, 2 * repeats):
        t1, t5 = run(1, reps), run(5, reps)
        if t5 > t1:
            return (t5 - t1) / (4 * iters)
    # host jitter swamped the signal twice: report NaN, never a
    # garbage near-zero that would corrupt the ranking downstream
    return float("nan")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    args = ap.parse_args()

    peak = chip_peak_flops()
    rng = np.random.RandomState(0)
    rtt = fetch_overhead()
    print(f"fetch rtt ~{rtt*1e3:.1f} ms", file=sys.stderr)
    rows = []
    for (name, count, h, w, cin, cout, k, stride) in CONVS:
        x = jnp.asarray(rng.randn(B, h, w, cin), jnp.bfloat16)
        wt = jnp.asarray(rng.randn(k, k, cin, cout) * 0.1, jnp.bfloat16)
        f = conv_fn(k, stride)
        y = jax.eval_shape(f, x, wt)
        dy = jnp.asarray(rng.randn(*y.shape) * 0.1, jnp.bfloat16)
        oh, ow = y.shape[1], y.shape[2]
        flops = 2.0 * B * oh * ow * k * k * cin * cout

        t_f = time_chain(chained(lambda a: f(a, wt), args.iters), x,
                         args.iters)
        t_d = time_chain(chained(
            lambda a: jax.linear_transpose(lambda xx: f(xx, wt), x)(a)[0],
            args.iters), dy, args.iters)
        t_w = time_chain(chained(
            lambda a: jax.linear_transpose(lambda ww: f(x, ww), wt)(a)[0],
            args.iters), dy, args.iters)
        row = dict(
            name=name, count=count, k=k, stride=stride,
            shape=f"{h}x{w}x{cin}->{cout}", gflops=flops / 1e9,
            fwd_us=t_f * 1e6, dgrad_us=t_d * 1e6, wgrad_us=t_w * 1e6,
            fwd_mfu=flops / t_f / peak, dgrad_mfu=flops / t_d / peak,
            wgrad_mfu=flops / t_w / peak,
            bwd_total_us=count * (t_d + t_w) * 1e6)
        rows.append(row)
        print(f"[{name}] fwd {row['fwd_us']:.0f}us/{row['fwd_mfu']:.0%} "
              f"dgrad {row['dgrad_us']:.0f}us/{row['dgrad_mfu']:.0%} "
              f"wgrad {row['wgrad_us']:.0f}us/{row['wgrad_mfu']:.0%}",
              file=sys.stderr)

    # NaN rows (jitter-swamped measurements) sort LAST, not arbitrarily
    rows.sort(key=lambda r: -r["bwd_total_us"]
              if r["bwd_total_us"] == r["bwd_total_us"] else float("inf"))
    hdr = (f"{'conv':<16}{'xN':>3} {'shape':<20}{'GF':>6} "
           f"{'fwd us':>8}{'mfu':>5} {'dgrad':>8}{'mfu':>5} "
           f"{'wgrad':>8}{'mfu':>5} {'bwd tot us':>11}")
    print(hdr)
    tot_f = tot_d = tot_w = 0.0
    for r in rows:
        print(f"{r['name']:<16}{r['count']:>3} {r['shape']:<20}"
              f"{r['gflops']:>6.1f} {r['fwd_us']:>8.0f}{r['fwd_mfu']:>5.0%} "
              f"{r['dgrad_us']:>8.0f}{r['dgrad_mfu']:>5.0%} "
              f"{r['wgrad_us']:>8.0f}{r['wgrad_mfu']:>5.0%} "
              f"{r['bwd_total_us']:>11.0f}")
        tot_f += r["count"] * r["fwd_us"]
        tot_d += r["count"] * r["dgrad_us"]
        tot_w += r["count"] * r["wgrad_us"]
    print(f"\ntotals: fwd {tot_f/1e3:.2f} ms  dgrad {tot_d/1e3:.2f} ms  "
          f"wgrad {tot_w/1e3:.2f} ms")
    with open("benchmarks/resnet_conv_profile.json", "w") as fh:
        json.dump(rows, fh, indent=1)


if __name__ == "__main__":
    main()
