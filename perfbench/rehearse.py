"""Compile every program of a cell at its real size for a DESCRIBED
TPU v5e (``jax.experimental.topologies``; no chip attached, nothing
runs) and print the compiler's byte count for each: the program's own
step or resident programs, and the plain reference's.  What the
compiler refuses here costs no chip time.

  JAX_PLATFORMS=cpu python3 perfbench/rehearse.py [--workload <name>] [--batch N] [--layers N]

The program's Pallas helpers ask ``jax.default_backend()`` whether to
interpret, which is ``cpu`` here; this script answers for them and
requires ``tpu_custom_call`` in a module that should hold a kernel.  A
compile that passes is not a chip run and is never reported as one.
Never run two of these at once (libtpu's lock file).
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

GIB = 2.0 ** 30


def steer_kernels() -> None:
    from bluefog_tpu.parallel import (pallas_attention, pallas_conv,
                                      pallas_decode)

    for mod in (pallas_attention, pallas_conv, pallas_decode):
        mod._auto_interpret = lambda interpret: False


def report(what: str, compiled, want_kernel=None) -> None:
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    text = compiled.as_text()
    kernel = "tpu_custom_call" in text
    print(f"{what}: needs {need / GIB:.2f} GiB a chip (arguments "
          f"{mem.argument_size_in_bytes / GIB:.2f}, temporaries "
          f"{mem.temp_size_in_bytes / GIB:.2f}, aliased "
          f"{mem.alias_size_in_bytes / GIB:.2f}); tpu_custom_call: "
          f"{kernel}; collective-permute: "
          f"{text.count(' collective-permute')}", flush=True)
    if want_kernel and not kernel:
        sys.exit(f"{what}: no tpu_custom_call in the module")


def with_sharding(shapes, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes)


def rehearse_train(cell, devices) -> None:
    from perfbench.harness import reference_train
    from perfbench.runners import train as runner

    family, ref, traffic = cell.family(), cell.reference(), cell.traffic
    sz = runner.cut_sizes(cell)
    n = cell.chips
    mesh = Mesh(np.array(devices[:n]), ("bf",))
    rank = NamedSharding(mesh, P("bf"))
    key = runner.key_from_seed(0)
    step_fn, opt, has_aux = runner.build_step(cell, mesh)

    def stack(tree):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)

    def init():
        params, aux = family.make_params(sz, key, jnp.float32)
        return stack({"params": params, "aux": aux,
                      "opt": opt.init(params)})

    state = with_sharding(jax.eval_shape(init), rank)
    batch = with_sharding(jax.eval_shape(
        lambda: family.make_batch(sz, traffic, key, n)), rank)
    args = ((state["params"], state["aux"], state["opt"], batch)
            if has_aux else (state["params"], state["opt"], batch))
    compiled = step_fn.lower(*args, np.int32(0)).compile()
    report(f"{cell.name} program step", compiled,
           want_kernel=traffic.get("expect_kernel"))

    def ref_loss(params, aux, rank_batch):
        return ref.loss(params, aux, rank_batch, sz, ref.mm_highest)

    init_r, step_r, _, _, rank_r = reference_train.build(
        ref_loss, lambda k: family.make_params(sz, k, jnp.float32),
        traffic["optimizer"], *runner.reference_rules(cell), n, devices)
    state_r = with_sharding(jax.eval_shape(init_r, key), rank_r)
    batch_r = with_sharding(batch, rank_r)
    w = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=rank_r)
    t = jax.ShapeDtypeStruct((), jnp.float32)
    report(f"{cell.name} reference step",
           step_r.lower(*state_r, batch_r, t, w).compile())


def rehearse_serve(cell, devices) -> None:
    from bluefog_tpu.models import generate
    from bluefog_tpu.serving import engine
    from bluefog_tpu.serving.kv_pool import SlotPool
    from perfbench.runners import serve as runner

    family, traffic = cell.family(), cell.traffic
    sz = family.sizes(cell.config, traffic["cut"])
    eng = traffic["engine"]
    one = jax.sharding.SingleDeviceSharding(devices[0])
    cfg = generate.decode_config(
        family.llama_config(sz, max_seq_len=eng["max_len"]), eng["max_len"],
        decode_attn=eng.get("decode_attn", "xla"))
    print(f"decode_attn resolved to {cfg.decode_attn!r}")
    dtype = family.dtype_of(sz["param_dtype"])
    params = with_sharding(jax.eval_shape(
        lambda: family.make_params(sz, runner.key_from_seed(0), dtype)[0]),
        one)
    cap, chunk = eng["capacity"], eng["prefill_chunk"]
    pool = with_sharding(jax.eval_shape(
        lambda: SlotPool(cfg, cap, eng["max_len"]).cache), one)

    def s(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    report(f"{cell.name} decode_step",
           engine._decode_step_prog.lower(
               params, pool, s(jnp.int32, cap), s(bool, cap),
               s(jnp.uint32, cap, 2), s(jnp.int32, cap),
               s(jnp.float32, cap), cfg=cfg,
               horizon=eng.get("decode_horizon", 1)).compile())
    report(f"{cell.name} prefill_chunk",
           engine._prefill_chunk_prog.lower(
               params, pool, s(jnp.int32), s(jnp.int32, 1, chunk),
               s(jnp.int32), cfg=cfg).compile())
    report(f"{cell.name} reference forward",
           runner.reference_program(cell, sz).lower(
               params, s(jnp.int32, eng["max_len"]),
               s(jnp.int32, runner.CHECK_ROWS)).compile())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--batch", type=int, help="override batch_per_chip")
    ap.add_argument("--layers", type=int,
                    help="override the cut's num_hidden_layers")
    args = ap.parse_args(argv)
    from jax.experimental import topologies

    from perfbench.harness import loader

    steer_kernels()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = loader.load_benchmark()
    names = args.workload or [w["name"] for w in bench["workloads"]]
    for name in names:
        cell = loader.load_cell(name)
        if args.batch:
            cell.traffic["batch_per_chip"] = args.batch
        if args.layers:
            cell.config["cuts"][cell.traffic["cut"]][
                "num_hidden_layers"] = args.layers
        kind = cell.traffic["runner"]
        {"train": rehearse_train, "serve": rehearse_serve}[kind](
            cell, topo.devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
