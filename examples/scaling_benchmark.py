"""Scaling-efficiency harness — the BASELINE north-star measurement.

Measures train-step throughput at world size 1 and at full world size on
the same hardware, and reports::

    efficiency = (throughput_n / n) / throughput_1

for each distributed optimizer (one-peer dynamic exp2, static exp2 ATC,
horovod-style gradient allreduce).  The reference's claim is >95% for
neighbor_allreduce vs ~66% for ring-allreduce at 128 GPUs (reference
README.rst:26-34); on a TPU pod slice this script is that comparison.

On a single chip (or the CPU mesh) the harness still runs end-to-end —
use it there as a smoke test; efficiency numbers only mean something with
real multi-chip ICI underneath.
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu import models
from bluefog_tpu.benchutil import device_fetch, fetch_overhead
from bluefog_tpu.config import configure_compilation_cache
from bluefog_tpu.optim import functional as F
from bluefog_tpu.topology import (
    ExponentialTwoGraph,
    one_peer_dynamic_schedule,
    uniform_topology_spec,
)

KNOWN_OPTIMIZERS = ("dynamic", "neighbor_allreduce", "horovod", "local")

parser = argparse.ArgumentParser()
parser.add_argument("--model", default="resnet50",
                    choices=["mlp", "resnet18", "resnet50"])
parser.add_argument("--batch-size", type=int, default=128)
parser.add_argument("--image-size", type=int, default=224)
parser.add_argument("--optimizers", default="dynamic,neighbor_allreduce,horovod")
parser.add_argument("--num-warmup", type=int, default=3)
parser.add_argument("--num-steps", type=int, default=10)
args = parser.parse_args()


def build(n_devices, dist_optimizer):
    devices = jax.devices()[:n_devices]
    mesh = Mesh(np.array(devices), ("bf",))
    if args.model == "mlp":
        model = models.MLP(features=(256, 128, 10))
        sample = jnp.ones((args.batch_size, 28, 28, 1), jnp.float32)

        def loss_fn(params, aux, batch):
            x, y = batch
            logits = model.apply(params, x)
            return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
                logits, y)), aux

        images = np.random.RandomState(0).randn(
            n_devices, args.batch_size, 28, 28, 1).astype(np.float32)
        n_classes = 10
    else:
        ctor = models.ResNet18 if args.model == "resnet18" else models.ResNet50
        model = ctor(num_classes=1000)
        sample = jnp.ones(
            (args.batch_size, args.image_size, args.image_size, 3),
            jnp.bfloat16)

        def loss_fn(params, aux, batch):
            x, y = batch
            logits, updates = model.apply(
                {"params": params, "batch_stats": aux}, x, train=True,
                mutable=["batch_stats"])
            return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
                logits, y)), updates["batch_stats"]

        images = np.random.RandomState(0).randn(
            n_devices, args.batch_size, args.image_size, args.image_size,
            3).astype(np.float32)
        n_classes = 1000

    if dist_optimizer not in KNOWN_OPTIMIZERS:
        raise SystemExit(f"unknown optimizer {dist_optimizer!r}; "
                         f"choose from {KNOWN_OPTIMIZERS}")
    topo_kwargs, comm_mode = {}, "none"
    if n_devices > 1:
        if dist_optimizer == "dynamic":
            topo_kwargs = dict(schedule=one_peer_dynamic_schedule(n_devices))
            comm_mode = "atc"
        elif dist_optimizer == "neighbor_allreduce":
            topo_kwargs = dict(topology=uniform_topology_spec(
                ExponentialTwoGraph(n_devices)))
            comm_mode = "atc"
        elif dist_optimizer == "horovod":
            comm_mode = "gradient_allreduce"

    opt = optax.sgd(0.1, momentum=0.9)
    step_fn = F.build_train_step(loss_fn, opt, mesh, comm_mode=comm_mode,
                                 has_aux=True, **topo_kwargs)

    variables = model.init(jax.random.PRNGKey(0), sample)
    if args.model == "mlp":
        params_tree, aux_tree = variables, {}
    else:
        params_tree, aux_tree = variables["params"], variables["batch_stats"]
    params = F.rank_major(params_tree, mesh)
    aux = F.rank_major(aux_tree, mesh)
    opt_state = F.rank_major(opt.init(params_tree), mesh)
    sharding = NamedSharding(mesh, P("bf"))
    dtype = jnp.float32 if args.model == "mlp" else jnp.bfloat16
    batch = (jax.device_put(jnp.asarray(images, dtype), sharding),
             jax.device_put(np.random.randint(
                 0, n_classes, (n_devices, args.batch_size)).astype(np.int32),
                 sharding))
    return step_fn, params, aux, opt_state, batch


def throughput(n_devices, dist_optimizer):
    step_fn, params, aux, opt_state, batch = build(n_devices, dist_optimizer)
    step = 0
    for _ in range(max(args.num_warmup, 1)):  # >=1: compile outside timing
        params, aux, opt_state, loss = step_fn(params, aux, opt_state, batch,
                                               jnp.int32(step))
        step += 1
    device_fetch(loss)
    rtt = fetch_overhead()
    t0 = time.perf_counter()
    for _ in range(args.num_steps):
        params, aux, opt_state, loss = step_fn(params, aux, opt_state, batch,
                                               jnp.int32(step))
        step += 1
    device_fetch(loss)
    dt = max(time.perf_counter() - t0 - rtt, 1e-9)
    return n_devices * args.batch_size * args.num_steps / dt


def main():
    configure_compilation_cache()
    n = len(jax.devices())
    base = throughput(1, "local")
    print(f"single-device baseline: {base:.1f} img/s")
    results = {}
    for name in args.optimizers.split(","):
        if n == 1:
            results[name] = {"img_per_sec": base, "efficiency": 1.0}
            continue
        tput = throughput(n, name)
        eff = (tput / n) / base
        results[name] = {"img_per_sec": round(tput, 1),
                         "efficiency": round(eff, 4)}
        print(f"{name}: {tput:.1f} img/s total on {n} devices, "
              f"efficiency {eff:.1%}")
    print(json.dumps({"model": args.model, "chips": n,
                      "baseline_img_per_sec": round(base, 1),
                      "results": results}))


if __name__ == "__main__":
    main()
