"""Headline benchmark: ResNet-50 training throughput (images/sec/chip).

Mirrors the reference's benchmark protocol (reference
examples/pytorch_benchmark.py: synthetic ImageNet-shaped data, batch 64,
timed steady-state steps).  The reference's published number is 4310.6
img/sec TOTAL on 16 V100s with neighbor_allreduce (docs/performance.rst:15-23)
= 269.4 img/sec/GPU, which is the ``vs_baseline`` denominator here.

Runs the same fully-jitted decentralized train-step code path used
multi-chip (bluefog_tpu.optim.functional) on however many chips are
attached (driver: one v5e chip), with train-mode batch norm, bf16 compute.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

``--compare PREV.json`` turns the run into a regression gate: headline
throughput/MFU fields are compared against a prior record (a raw line
or a wrapper holding it under ``"parsed"``) with a per-metric relative
tolerance (``--tolerance``, default 5%); a regression prints the delta
table and exits nonzero.  ``--out`` additionally writes the fresh
record to a file, so the next run has something to gate against —
SKIPPED when the gate fails, so a regressed run can never overwrite
the baseline it was gated against.  A plain ``python bench.py``
compares with nothing: a baseline is a record taken on the same
installation, and the caller names it.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REFERENCE_IMG_PER_SEC_PER_CHIP = 4310.6 / 16  # docs/performance.rst:15-23
# 128/chip keeps the MXU saturated on v5e (measured: 64 -> 1737 img/s,
# 128 -> 2522, 256 -> 2464); the reference benchmarks at 64/GPU but
# per-chip throughput is the comparable metric.
BATCH_PER_CHIP = 128
WARMUP_STEPS = 5
TIMED_STEPS = 10
TIMED_WINDOWS = 3  # report the median window


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--compare", metavar="PREV.json", default=None,
                    help="gate this run against a prior bench record; "
                         "exits 1 on regression beyond --tolerance "
                         "(default: no comparison)")
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="per-metric relative regression tolerance")
    ap.add_argument("--out", default=None,
                    help="also write the fresh record to this JSON file")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bluefog_tpu import models
    from bluefog_tpu.benchutil import (chip_peak_flops, compiled_step_flops,
                                       mfu)
    from bluefog_tpu.config import configure_compilation_cache
    from bluefog_tpu.optim import functional as F
    from bluefog_tpu.topology import ExponentialTwoGraph, uniform_topology_spec

    configure_compilation_cache()
    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.array(devices), ("bf",))

    # bf16 compute, f32 params; BLUEFOG_BENCH_PALLAS_CONV1X1=1 routes the
    # bottleneck 1x1s through the fused Pallas backward for A/B runs
    model = models.ResNet50(
        num_classes=1000,
        pallas_conv1x1=os.environ.get(
            "BLUEFOG_BENCH_PALLAS_CONV1X1", "0") == "1")

    def loss_fn(params, aux, batch):
        images, labels = batch
        logits, updates = model.apply(
            {"params": params, "batch_stats": aux}, images, train=True,
            mutable=["batch_stats"])
        loss = jnp.mean(
            optax.softmax_cross_entropy_with_integer_labels(logits, labels))
        return loss, updates["batch_stats"]

    if n > 1:
        topo = dict(topology=uniform_topology_spec(ExponentialTwoGraph(n)))
        comm_mode = "atc"
    else:
        topo = dict()
        comm_mode = "none"
    opt = optax.sgd(0.1, momentum=0.9)
    step_fn = F.build_train_step(
        loss_fn, opt, mesh, comm_mode=comm_mode, has_aux=True, **topo)

    rng = jax.random.PRNGKey(0)
    sample = jnp.ones((BATCH_PER_CHIP, 224, 224, 3), jnp.bfloat16)
    variables = model.init(rng, sample)
    params = F.rank_major(variables["params"], mesh)
    aux = F.rank_major(variables["batch_stats"], mesh)
    opt_state = F.rank_major(opt.init(variables["params"]), mesh)

    images = np.random.RandomState(0).randn(
        n, BATCH_PER_CHIP, 224, 224, 3).astype(np.float32)
    labels = np.random.RandomState(1).randint(
        0, 1000, size=(n, BATCH_PER_CHIP)).astype(np.int32)
    sharding = NamedSharding(mesh, P("bf"))
    batch = (jax.device_put(jnp.asarray(images, jnp.bfloat16), sharding),
             jax.device_put(labels, sharding))

    for i in range(WARMUP_STEPS):
        params, aux, opt_state, loss = step_fn(params, aux, opt_state, batch,
                                               jnp.int32(i))
    jax.block_until_ready(loss)

    rates = []
    step = WARMUP_STEPS
    for _ in range(TIMED_WINDOWS):
        t0 = time.perf_counter()
        for _ in range(TIMED_STEPS):
            params, aux, opt_state, loss = step_fn(
                params, aux, opt_state, batch, jnp.int32(step))
            step += 1
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        rates.append(n * BATCH_PER_CHIP * TIMED_STEPS / dt)

    total_img_per_sec = float(np.median(rates))
    per_chip = total_img_per_sec / n

    # Roofline accounting: per-device FLOPs of the compiled step from
    # XLA's own cost analysis (includes remat recompute — what the chip
    # actually executes) over the published bf16 peak.
    flops_per_step = compiled_step_flops(
        step_fn, params, aux, opt_state, batch, jnp.int32(0))
    step_seconds = BATCH_PER_CHIP * n / total_img_per_sec
    achieved_mfu = mfu(flops_per_step, step_seconds)
    record = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "img/s/chip",
        "vs_baseline": round(per_chip / REFERENCE_IMG_PER_SEC_PER_CHIP, 3),
        "mfu": round(achieved_mfu, 4),
        "flops_per_step_per_device": flops_per_step,
        "peak_tflops_per_chip": chip_peak_flops() / 1e12,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": n},
    }
    print(json.dumps(record))
    # gate BEFORE writing --out: with the rolling-baseline usage
    # (--compare BASE.json --out BASE.json) a regressed run must not
    # overwrite the good baseline and ratchet the regression through
    if args.compare:
        from bluefog_tpu.benchutil import bench_regression_gate

        if not bench_regression_gate(record, args.compare,
                                     tolerance=args.tolerance):
            if args.out:
                print(f"[bench-gate] regression: NOT writing {args.out}")
            return 1
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(record, fh, indent=1)
            fh.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
