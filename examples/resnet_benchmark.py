"""Synthetic-data ResNet throughput benchmark with selectable distributed
optimizer and per-step dynamic topology.

TPU twin of reference examples/pytorch_benchmark.py (+ the dynamic-topology
update pattern of examples/pytorch_resnet.py:333-372).  Uses the fully-
jitted train step (bluefog_tpu.optim.functional): the dynamic one-peer
exponential-2 schedule is compiled once (one program a round) and the
round's program is picked by step index — the per-iteration
"dynamic_topology_update" becomes a choice among executables, not a retrace.

  --dist-optimizer neighbor_allreduce : ATC over the static exp2 graph
  --dist-optimizer dynamic            : one-peer exp2 schedule (BlueFog's
                                        headline O(1)-per-step mode)
  --dist-optimizer horovod            : global gradient allreduce baseline
  --dist-optimizer local              : no communication (upper bound)
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

parser = argparse.ArgumentParser()
parser.add_argument("--model", default="resnet50",
                    choices=["resnet18", "resnet34", "resnet50", "resnet101"])
parser.add_argument("--batch-size", type=int, default=64)
parser.add_argument("--image-size", type=int, default=224)
parser.add_argument("--dist-optimizer", default="neighbor_allreduce",
                    choices=["neighbor_allreduce", "dynamic", "horovod",
                             "local"])
parser.add_argument("--num-warmup-batches", type=int, default=5)
parser.add_argument("--num-batches-per-iter", type=int, default=10)
parser.add_argument("--num-iters", type=int, default=3)
parser.add_argument("--fp32", action="store_true")
args = parser.parse_args()


def main():
    configure_compilation_cache()
    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.array(devices), ("bf",))
    dtype = jnp.float32 if args.fp32 else jnp.bfloat16
    model = {
        "resnet18": models.ResNet18, "resnet34": models.ResNet34,
        "resnet50": models.ResNet50, "resnet101": models.ResNet101,
    }[args.model](num_classes=1000, dtype=dtype)

    def loss_fn(params, aux, batch):
        images, labels = batch
        logits, updates = model.apply(
            {"params": params, "batch_stats": aux}, images, train=True,
            mutable=["batch_stats"])
        loss = jnp.mean(
            optax.softmax_cross_entropy_with_integer_labels(logits, labels))
        return loss, updates["batch_stats"]

    topo_kwargs, comm_mode = {}, "none"
    if n > 1:
        if args.dist_optimizer == "neighbor_allreduce":
            topo_kwargs = dict(
                topology=uniform_topology_spec(ExponentialTwoGraph(n)))
            comm_mode = "atc"
        elif args.dist_optimizer == "dynamic":
            topo_kwargs = dict(schedule=one_peer_dynamic_schedule(n))
            comm_mode = "atc"
        elif args.dist_optimizer == "horovod":
            comm_mode = "gradient_allreduce"

    opt = optax.sgd(0.1, momentum=0.9)
    step_fn = F.build_train_step(loss_fn, opt, mesh, comm_mode=comm_mode,
                                 has_aux=True, **topo_kwargs)

    sample = jnp.ones((args.batch_size, args.image_size, args.image_size, 3),
                      dtype)
    variables = model.init(jax.random.PRNGKey(0), sample)
    params = F.rank_major(variables["params"], mesh)
    aux = F.rank_major(variables["batch_stats"], mesh)
    opt_state = F.rank_major(opt.init(variables["params"]), mesh)

    rng = np.random.RandomState(0)
    sharding = NamedSharding(mesh, P("bf"))
    batch = (
        jax.device_put(jnp.asarray(rng.randn(
            n, args.batch_size, args.image_size, args.image_size, 3), dtype),
            sharding),
        jax.device_put(rng.randint(0, 1000, (n, args.batch_size)).astype(
            np.int32), sharding),
    )

    step = 0
    for _ in range(max(args.num_warmup_batches, 1)):
        params, aux, opt_state, loss = step_fn(params, aux, opt_state, batch,
                                               jnp.int32(step))
        step += 1
    device_fetch(loss)
    rtt = fetch_overhead()

    img_secs = []
    for it in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            params, aux, opt_state, loss = step_fn(
                params, aux, opt_state, batch, jnp.int32(step))
            step += 1
        device_fetch(loss)
        dt = max(time.perf_counter() - t0 - rtt, 1e-9)
        ips = n * args.batch_size * args.num_batches_per_iter / dt
        img_secs.append(ips)
        print(f"Iter #{it}: {ips:.1f} img/sec total ({n} chips)")

    mean, std = np.mean(img_secs), np.std(img_secs)
    print(f"Total img/sec on {n} chip(s): {mean:.1f} +- {std:.1f}")
    print(json.dumps({"model": args.model, "optimizer": args.dist_optimizer,
                      "img_per_sec": round(float(mean), 1), "chips": n}))


if __name__ == "__main__":
    main()
