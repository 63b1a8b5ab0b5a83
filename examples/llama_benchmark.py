"""Llama decentralized-SGD throughput benchmark (tokens/sec).

The BASELINE.json stress config: "Llama-3-8B decentralized SGD with
neighbor_allreduce — stress ICI at LLM scale".  Runs the fully-jitted
decentralized train step on a Llama model, synthetic tokens, bf16 compute,
optional sequence parallelism (ring attention) and Pallas flash attention.

  --model tiny|200m|1b|8b   (8b needs a pod slice; 200m fits one v5e chip)
  --dist-optimizer neighbor_allreduce|dynamic|horovod|local
  --sp N                    sequence-parallel ways (ring attention)
  --tp N / --ep N / --pp N  tensor- / expert- / pipeline-parallel ways
                            (mesh becomes dp x tp|ep x pp x sp)
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
from bluefog_tpu.benchutil import (chip_peak_flops, compiled_step_flops,
                                   device_fetch, fetch_overhead, mfu)
from bluefog_tpu.config import configure_compilation_cache
from bluefog_tpu.optim import functional as F
from bluefog_tpu.topology import (
    ExponentialTwoGraph,
    one_peer_dynamic_schedule,
    uniform_topology_spec,
)

parser = argparse.ArgumentParser()
parser.add_argument("--model", default="200m",
                    choices=["tiny", "200m", "1b", "8b"])
parser.add_argument("--batch-size", type=int, default=4)
parser.add_argument("--seq-len", type=int, default=2048)
parser.add_argument("--dist-optimizer", default="neighbor_allreduce",
                    choices=["neighbor_allreduce", "dynamic", "horovod",
                             "local"])
parser.add_argument("--sp", type=int, default=1,
                    help="sequence-parallel ways")
parser.add_argument("--sp-mode", default="ring",
                    choices=["ring", "ulysses"],
                    help="sequence-parallel flavor: ring attention "
                    "(K/V rotate over ICI) or ulysses (two all-to-alls, "
                    "heads sharded during attention)")
parser.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel ways (Megatron column->row)")
parser.add_argument("--experts", type=int, default=0,
                    help="mixture-of-experts FFN with this many experts")
parser.add_argument("--ep", type=int, default=1,
                    help="expert-parallel ways (needs --experts)")
parser.add_argument("--moe-aux-weight", type=float, default=0.01,
                    help="Switch load-balance aux loss weight (MoE only)")
parser.add_argument("--moe-router", default="topk",
                    choices=["topk", "expert_choice"],
                    help="token-choice top-k (causal) or expert-choice "
                    "(dropless, perfectly balanced; non-causal)")
parser.add_argument("--pp", type=int, default=1,
                    help="pipeline-parallel stages (GPipe over a pp mesh "
                    "axis; forces --scan-layers)")
parser.add_argument("--pp-loops", type=int, default=1,
                    help="circular-pipeline interleave factor (each stage "
                    "holds this many round-robin layer chunks; bubble "
                    "shrinks by the same factor)")
parser.add_argument("--microbatches", type=int, default=0,
                    help="pipeline microbatches (default 2*pp; the "
                    "circular schedule requires at least pp)")
parser.add_argument("--attn-impl", default="xla",
                    choices=["xla", "flash", "splash"])
parser.add_argument("--attn-block-size", type=int, default=0,
                    help="flash/blockwise attention tile size "
                    "(0 = config default)")
parser.add_argument("--attn-block-k", type=int, default=0,
                    help="flash K/V tile size alone (0 = config "
                    "default; --attn-block-size sets both)")
parser.add_argument("--scan-layers", action="store_true",
                    help="nn.scan the decoder stack (O(1) compile in depth)")
parser.add_argument("--bf16-logits", action="store_true",
                    help="run the logits head matmul in bf16 "
                    "(logits_dot_in_fp32=False); ~2x faster head")
parser.add_argument("--no-remat", action="store_true",
                    help="disable rematerialization (when HBM allows, "
                    "saves the recompute FLOPs)")
parser.add_argument("--xent-chunks", type=int, default=0,
                    help="compute the head + cross-entropy in this many "
                    "sequence chunks (models.chunked_xent: the full "
                    "[B,S,V] logits never materialize; 0 = monolithic)")
parser.add_argument("--remat-policy", default="none",
                    choices=["none", "dots", "everything"])
parser.add_argument("--layers", type=int, default=0,
                    help="override the model's layer count (e.g. to give "
                    "--model tiny enough layers for --pp x --pp-loops)")
parser.add_argument("--num-warmup", type=int, default=3)
parser.add_argument("--num-steps", type=int, default=10)
args = parser.parse_args()


def make_config():
    base = dict(remat=not args.no_remat,
                scan_layers=args.scan_layers or args.pp > 1,
                remat_policy=args.remat_policy,
                logits_dot_in_fp32=not args.bf16_logits)
    if args.tp > 1:
        base.update(tp_axis="tp", tp_size=args.tp)
    if args.experts:
        # expert choice is perfectly balanced by construction — a Switch
        # aux term would only perturb the objective
        aux = (0.0 if args.moe_router == "expert_choice"
               else args.moe_aux_weight)
        base.update(n_experts=args.experts, moe_aux_weight=aux,
                    moe_router=args.moe_router)
        if args.moe_router == "expert_choice":
            # benchmark-only acknowledgement: EC routing is non-causal,
            # so the trained logits are not autoregressively reproducible
            print("WARNING: --moe-router expert_choice is non-causal on "
                  "this decoder stack (throughput/ablation use only)")
            base.update(allow_noncausal_router=True)
        if args.ep > 1:
            base.update(ep_axis="ep", ep_size=args.ep)
    if args.sp > 1:
        base.update(attn_mode=args.sp_mode, sp_axis="sp",
                    attn_impl=args.attn_impl)
    elif args.attn_impl != "xla":
        base.update(attn_impl=args.attn_impl)
    if args.attn_block_size:
        base.update(attn_block_size=args.attn_block_size,
                    attn_flash_block_size=args.attn_block_size,
                    attn_flash_block_k=args.attn_block_size)
    if args.attn_block_k:
        base.update(attn_flash_block_k=args.attn_block_k)
    if args.model == "tiny":
        return models.LlamaConfig.tiny(**base)
    if args.model == "200m":
        return models.LlamaConfig(
            vocab_size=32000, dim=1024, n_layers=12, n_heads=16,
            n_kv_heads=4, hidden_dim=2816, max_seq_len=8192, **base)
    if args.model == "1b":
        return models.LlamaConfig.llama_1b(**base)
    return models.LlamaConfig.llama3_8b(**base)


def main():
    configure_compilation_cache()
    devices = jax.devices()
    n_total = len(devices)
    n_sp, n_tp, n_ep, n_pp = args.sp, args.tp, args.ep, args.pp
    assert n_tp == 1 or n_ep == 1, "tp and ep do not compose yet"
    assert n_ep == 1 or args.experts > 0, \
        "--ep > 1 without --experts would replicate the dense model " \
        "across the ep axis (wasted devices); add --experts N"
    n_model = n_tp * n_ep
    assert n_total % (n_sp * n_model * n_pp) == 0, \
        (n_total, n_sp, n_tp, n_ep, n_pp)
    assert args.seq_len % n_sp == 0, (args.seq_len, n_sp)
    n_dp = n_total // (n_sp * n_model * n_pp)
    assert args.microbatches == 0 or n_pp > 1, \
        "--microbatches only applies with --pp > 1"
    n_micro = args.microbatches or (2 * n_pp if n_pp > 1 else 1)
    assert args.batch_size % n_micro == 0, (args.batch_size, n_micro)
    model_axis = "ep" if n_ep > 1 else "tp"
    assert args.pp_loops == 1 or n_pp > 1, \
        "--pp-loops > 1 only applies with --pp > 1"
    assert args.sp_mode == "ring" or n_sp > 1, \
        "--sp-mode only applies with --sp > 1"
    mesh = Mesh(np.array(devices).reshape(n_dp, n_model, n_pp, n_sp),
                ("bf", model_axis, "pp", "sp"))
    cfg = make_config()
    if args.layers:
        import dataclasses

        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    assert cfg.n_layers % (n_pp * args.pp_loops) == 0, \
        (cfg.n_layers, n_pp, args.pp_loops)
    model = models.Llama(cfg)
    t_local = args.seq_len // n_sp

    if n_pp > 1:
        from bluefog_tpu.models.llama import llama_pp_loss_fn

        loss_fn = llama_pp_loss_fn(cfg, pp_axis="pp", n_stages=n_pp,
                                   n_micro=n_micro,
                                   n_loops=args.pp_loops)
    elif args.xent_chunks > 0:
        assert n_sp == 1 and not args.experts, \
            "--xent-chunks: plain dp/tp configs only"
        loss_fn = models.llama_chunked_xent_loss_fn(
            cfg, n_chunks=args.xent_chunks)
    else:
        want_aux = cfg.n_experts > 0 and cfg.moe_aux_weight > 0.0

        def loss_fn(params, batch):
            inp, tgt = batch
            offset = jax.lax.axis_index("sp") * t_local if n_sp > 1 else 0
            aux = 0.0
            if want_aux:
                logits, mut = model.apply(params, inp, pos_offset=offset,
                                          mutable=["intermediates"])
                aux = sum(jnp.sum(v) for v in
                          jax.tree.leaves(mut["intermediates"]))
            else:
                logits = model.apply(params, inp, pos_offset=offset)
            ce = jnp.mean(
                optax.softmax_cross_entropy_with_integer_labels(logits, tgt))
            return ce + cfg.moe_aux_weight * aux

    topo_kwargs, comm_mode = {}, "none"
    if n_dp > 1:
        if args.dist_optimizer == "neighbor_allreduce":
            topo_kwargs = dict(
                topology=uniform_topology_spec(ExponentialTwoGraph(n_dp)))
            comm_mode = "atc"
        elif args.dist_optimizer == "dynamic":
            topo_kwargs = dict(schedule=one_peer_dynamic_schedule(n_dp))
            comm_mode = "atc"
        elif args.dist_optimizer == "horovod":
            comm_mode = "gradient_allreduce"

    opt = optax.sgd(1e-3, momentum=0.9)
    batch_specs = P("bf", None, "sp") if n_sp > 1 else P("bf")
    # ONE unsharded config override serves both the spec derivation here
    # and the sharded init below
    init_model = models.Llama(
        models.LlamaConfig(**{**cfg.__dict__, "attn_mode": "full",
                              "attn_impl": "xla", "sp_axis": None,
                              "tp_axis": None, "tp_size": 1,
                              "ep_axis": None, "ep_size": 1}))
    if n_model > 1 or n_pp > 1:
        from bluefog_tpu.models.llama import llama_param_specs

        shapes = jax.eval_shape(
            lambda: init_model.init(jax.random.PRNGKey(0),
                                    jnp.zeros((1, 8), jnp.int32)))
        param_specs = llama_param_specs(
            shapes, tp_axis="tp" if n_tp > 1 else None,
            ep_axis="ep" if n_ep > 1 else None,
            pp_axis="pp" if n_pp > 1 else None)
        opt_state_specs = F.optax_state_specs(opt, shapes, param_specs)
    else:
        param_specs = opt_state_specs = None
    step_fn = F.build_train_step(
        loss_fn, opt, mesh, comm_mode=comm_mode,
        sp_axis="sp" if n_sp > 1 else None,
        pp_axis="pp" if n_pp > 1 else None, batch_specs=batch_specs,
        param_specs=param_specs, opt_state_specs=opt_state_specs,
        **topo_kwargs)

    rng = np.random.RandomState(0)
    raw = rng.randint(0, cfg.vocab_size,
                      (n_dp, args.batch_size, args.seq_len + 1)).astype(np.int32)
    sharding = NamedSharding(mesh, batch_specs)
    batch = (jax.device_put(raw[:, :, :-1], sharding),
             jax.device_put(raw[:, :, 1:], sharding))

    # sharded init: params materialize already rank-major over the mesh —
    # no single-device staging of the full model (matters at 1b/8b scale)
    init_tokens = jnp.zeros((args.batch_size, min(8, args.seq_len)), jnp.int32)

    def init_state():
        base = init_model.init(jax.random.PRNGKey(0), init_tokens)
        if args.pp_loops > 1:
            from bluefog_tpu.models.llama import llama_circular_layout

            base = llama_circular_layout(base, n_pp, args.pp_loops)
        return {"params": base, "opt": opt.init(base)}

    state_specs = None
    if param_specs is not None:
        state_specs = {"params": param_specs, "opt": opt_state_specs}
    state = F.rank_major_init(init_state, mesh, specs=state_specs)
    params, opt_state = state["params"], state["opt"]
    n_params = sum(x.size for x in jax.tree.leaves(params)) // max(
        mesh.shape["bf"], 1)

    step = 0
    for _ in range(max(args.num_warmup, 1)):  # >=1: compile outside timing
        params, opt_state, loss = step_fn(params, opt_state, batch,
                                          jnp.int32(step))
        step += 1
    device_fetch(loss)
    rtt = fetch_overhead()

    t0 = time.perf_counter()
    for _ in range(args.num_steps):
        params, opt_state, loss = step_fn(params, opt_state, batch,
                                          jnp.int32(step))
        step += 1
    final_loss = float(device_fetch(loss).mean())
    dt = max(time.perf_counter() - t0 - rtt, 1e-9)
    tokens = n_dp * args.batch_size * args.seq_len * args.num_steps
    tokens_per_sec = tokens / dt

    # Roofline accounting:
    #  * mfu     — model-FLOPs utilization from the standard analytic count
    #              (6*N per token for the dense stack + 6*L*T*d for causal
    #              attention, fwd+bwd; PaLM-appendix style).  The primary
    #              number: independent of remat/compiler choices.
    #  * mfu_hw  — XLA cost-analysis FLOPs of the compiled step (counts
    #              remat recompute).  CAVEAT: the HLO cost model counts a
    #              scanned loop body ONCE, so with --scan-layers it
    #              understates by ~n_layers; reported only when not
    #              scanning.
    step_seconds = dt / args.num_steps
    peak = chip_peak_flops()
    step_tokens = n_dp * args.batch_size * args.seq_len
    # 6*N per token over MATMUL params (the input embedding table is a
    # gather, not a matmul — excluded; the output head is a real matmul —
    # included in n_params) + causal attention 6*L*T*d.  For MoE, each
    # token executes only ~top_k of the n_experts expert FFNs, so count
    # the ACTIVATED expert params (standard MoE accounting; capacity
    # drops make this a slight overcount, i.e. MFU is conservative).
    matmul_params = n_params - cfg.vocab_size * cfg.dim
    if cfg.n_experts:
        expert_params = (cfg.n_layers * cfg.n_experts * 3
                         * cfg.dim * cfg.ffn_dim)
        matmul_params -= expert_params * (1 - cfg.moe_top_k / cfg.n_experts)
    model_flops_per_step = (6.0 * matmul_params * step_tokens
                            + 6.0 * cfg.n_layers * args.seq_len * cfg.dim
                            * step_tokens)
    result = {
        "model": args.model, "params": n_params,
        "optimizer": args.dist_optimizer,
        "mesh": f"{n_dp}dp x {n_tp}tp x {n_ep}ep x {n_pp}pp x {n_sp}sp",
        "attn": cfg.attn_mode + "/" + cfg.attn_impl,
        "remat": cfg.remat, "scan_layers": cfg.scan_layers,
        "tokens_per_sec": round(tokens_per_sec, 1),
        "mfu": round(model_flops_per_step / n_total / step_seconds / peak, 4)
        if peak else 0.0,
        "peak_tflops_per_chip": peak / 1e12,
        "loss": round(final_loss, 4), "chips": n_total,
    }
    if not cfg.scan_layers:
        hw_flops = compiled_step_flops(
            step_fn, params, opt_state, batch, jnp.int32(0))
        result["mfu_hw"] = round(
            mfu(hw_flops, step_seconds, peak_per_chip=peak), 4)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
