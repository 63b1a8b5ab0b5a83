"""Llama-3-8B overlap audit: the DEFENDED overlap fraction for the
composed pod projection, from the real train-step program.

Round 5 left the 8B north-star MFU as a 26.9%-46.4% SPREAD hanging on an
unverified comment ("XLA overlaps the ppermutes with compute").  This
script replaces the comment with an accounting pass over the compiled
program itself:

1. AOT-compile the REAL bucketed decentralized train step at the shipped
   8B pod layout's per-group shape (tp8 + seq-shard + vocab-parallel,
   dp ring over 2 virtual ranks — per-device payloads and compute are
   IDENTICAL to the dp16 pod, only the ring is shorter) on the
   16-virtual-device CPU mesh, the same AOT method as
   ``llama_8b_structural.py``.  ``build_train_step(overlap="bucketed")``
   is what ships for the pod config.
2. Run ``benchutil.overlap_accounting`` over the scheduled module: for
   every dp ``collective-permute`` and every tp ``all-gather`` /
   ``reduce-scatter``, measure the compute available to hide it, and
   count its payload overlappable when that compute outlasts the
   payload's transfer time at v5e link rate (pod-schedule congestion
   charged on dp).  On this CPU lowering the collectives are
   synchronous, so the measure is the DATAFLOW basis: compute that is
   neither ancestor nor descendant of the collective — exactly the set
   the latency-hiding scheduler may place in flight (``basis`` records
   this; on a pod with ``benchutil.latency_hiding_xla_flags()`` the same
   accounting upgrades to the scheduled start->done windows).
3. Merge the fractions into the measured-components JSON
   (``llama_8b_measured.json``, written by llama_8b_measured.py on the
   chip) and re-base the composed projection:

       t_step = t_chip + (1 - f_tp) * t_tp + (1 - f_dp) * t_dp

   — ONE defended MFU number instead of the no-overlap/full-overlap
   spread.

Run (CPU by design, no TPU needed):

  PYTHONPATH=. python benchmarks/llama_8b_overlap.py \
      [--buckets 8] [--out benchmarks/llama_8b_measured.json]
"""

import argparse
import json
import os
import sys
import time

if "jax" not in sys.modules:  # script entry: pin the AOT audit env
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=16")
    os.environ["JAX_PLATFORMS"] = "cpu"  # AOT audit by design

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu import benchutil, models
from bluefog_tpu.context import _uniform_topology_spec
from bluefog_tpu.models import vocab_parallel_xent
from bluefog_tpu.models.llama import llama_param_specs
from bluefog_tpu.optim import functional as F
from bluefog_tpu.topology.graphs import RingGraph

DP, TP = 2, 8   # dp2 x the pod's 8-chip tp group (dp16 pays the same
                # per-device bytes/compute; only the ring is longer)
B, T = 2, 4096
V5E_LINK_GBPS = 200.0
POD_DP_CONGESTION = 16 / 7  # default_pod_schedule mean (r05 projection)


def lower_bucketed_step(buckets: int, comm_mode: str = "atc",
                        compress: str = "int8"):
    """AOT-lower the shipped 8B pod train step with the overlap engine
    on; returns (scheduled_hlo_text, seconds_spent)."""
    build, a_args = _pod_step_setup()
    step = build(comm_mode=comm_mode, compress=compress,
                 overlap="bucketed", overlap_buckets=buckets)
    t0 = time.perf_counter()
    compiled = step.lower(*a_args, jnp.int32(0)).compile()
    return compiled.as_text(), time.perf_counter() - t0


def _pod_step_setup(dp: int = DP, tp: int = TP, topo_kwargs=None):
    """The ONE 8B pod layout both audits measure: returns
    ``(build(**train_step_kwargs) -> step, (a_params, a_opt, a_batch))``
    so the records in the same JSON are guaranteed to describe the same
    model/mesh/spec configuration.  ``dp``/``tp``
    reshape the same 16 virtual devices (the hierarchical audit needs a
    dp ring long enough to decompose into machines); ``topo_kwargs``
    overrides the default dp ring topology (e.g. a MACHINE-level
    schedule plus ``hierarchical=``)."""
    cfg = models.LlamaConfig.llama3_8b(
        dtype=jnp.bfloat16, scan_layers=True, remat=True,
        remat_policy="everything", max_seq_len=8192,
        rope_scaling_kind="llama3", tp_axis="tp", tp_size=tp,
        vocab_parallel=True, tp_seq_shard=True)
    plain = models.LlamaConfig.llama3_8b(
        dtype=jnp.bfloat16, scan_layers=True, remat=True,
        remat_policy="everything", max_seq_len=8192,
        rope_scaling_kind="llama3")
    abstract = jax.eval_shape(lambda: models.Llama(plain).init(
        jax.random.PRNGKey(0), jnp.zeros((B, 8), jnp.int32)))

    opt = optax.sgd(1e-2, momentum=0.9)
    pspecs = llama_param_specs(abstract, tp_axis="tp", ep_axis=None,
                               vocab_axis="tp")
    ospecs = F.optax_state_specs(opt, abstract, pspecs)
    mesh = Mesh(np.array(jax.devices()[:dp * tp]).reshape(dp, tp),
                ("bf", "tp"))
    model = models.Llama(cfg)

    def loss_fn(params, batch):
        inp, tgt = batch
        logits = model.apply(params, inp)
        return vocab_parallel_xent(logits, tgt, "tp")

    topo_kwargs = topo_kwargs or dict(
        topology=_uniform_topology_spec(RingGraph(dp)))

    def build(**kwargs):
        return F.build_train_step(
            loss_fn, opt, mesh, batch_specs=P("bf"), param_specs=pspecs,
            opt_state_specs=ospecs, **topo_kwargs, **kwargs)

    def absharded(tree, specs):
        return jax.tree.map(
            lambda l, s: jax.ShapeDtypeStruct(
                (dp,) + l.shape, l.dtype,
                sharding=NamedSharding(mesh, s)),
            tree, specs)

    a_params = absharded(abstract, pspecs)
    a_opt = absharded(jax.eval_shape(opt.init, abstract), ospecs)
    bsh = NamedSharding(mesh, P("bf"))
    a_batch = tuple(jax.ShapeDtypeStruct((dp, B, T), jnp.int32,
                                         sharding=bsh) for _ in range(2))
    build.mesh = mesh  # the compressed audit shards MixState over it
    return build, (a_params, a_opt, a_batch)


HIER_DP, HIER_TP = 4, 4   # same 16 devices, dp ring long enough to split
HIER_M, HIER_L = 2, 2     # ... into 2 machines x 2 chips across DCN


def hierarchical_audit(buckets: int, comm_mode: str = "atc") -> dict:
    """The ISSUE-11 claim, machine-checked at the real 8B step: the
    two-level exchange (exact ICI allreduce inside the machine,
    decentralized mixing of machine means across DCN) cuts measured
    DCN bytes/step vs the flat exchange at the same guard+health+int8
    bucketed config.

    Same-16-device reshape to dp4 x tp4 (dp2 cannot decompose into
    machines); flat leg = exp2(4) static dp graph, hierarchical leg =
    the 2-machine one-peer schedule at L=2.  DCN bytes are the
    ``collective-permute`` payloads of the compiled module — the only
    inter-machine wire in either build (tp all-gather/reduce-scatter
    and the hierarchical ICI reduce stay inside the machine) — via
    ``stepprof.profile_step``, which also defends the tp overlap
    fraction and holds the cost-model bytes/step to the flat leg's."""
    from bluefog_tpu.observe import stepprof
    from bluefog_tpu.optim.functional import GuardConfig, HealthConfig
    from bluefog_tpu.topology.dynamic import one_peer_dynamic_schedule
    from bluefog_tpu.topology.graphs import ExponentialTwoGraph

    t0 = time.perf_counter()
    link = V5E_LINK_GBPS * 1e9 / 8

    def leg(topo_kwargs, name):
        build, a_args = _pod_step_setup(dp=HIER_DP, tp=HIER_TP,
                                        topo_kwargs=topo_kwargs)
        step = build(comm_mode=comm_mode, compress="int8",
                     overlap="bucketed", overlap_buckets=buckets,
                     guard=GuardConfig(), health=HealthConfig())
        prof = stepprof.profile_step(
            step, *a_args, jnp.int32(0), step.default_comm_weights,
            name=name, publish=False, peak_flops=197e12,
            hbm_bytes_per_s=819e9, link_bytes_per_s=link,
            kinds=("all-gather", "reduce-scatter"))
        return step, prof

    _, pf = leg(dict(topology=_uniform_topology_spec(
        ExponentialTwoGraph(HIER_DP))), "hier_audit_flat")
    step_h, ph = leg(dict(schedule=one_peer_dynamic_schedule(HIER_M),
                          hierarchical=HIER_L), "hier_audit_two_level")
    assert step_h.hierarchical_local_size == HIER_L

    def dcn(p):
        return p.collective_bytes.get("collective-permute",
                                      {"count": 0, "bytes": 0})

    def summarize(p):
        return {
            "dcn_permute_count": dcn(p)["count"],
            "dcn_bytes_per_step": dcn(p)["bytes"],
            "ici_all_reduce_bytes": p.collective_bytes.get(
                "all-reduce", {"bytes": 0})["bytes"],
            "cost_bytes_accessed": p.cost_bytes_accessed,
            "tp_overlap_fraction": round(p.overlap["fraction"], 4),
        }

    sf, sh = summarize(pf), summarize(ph)
    return {
        "method": "stepprof.profile_step of the guard+health+int8 "
                  f"bucketed (K={buckets}, {comm_mode}) 8B step at "
                  "dp4 x tp4 on the 16-virtual-device CPU mesh: flat "
                  "exp2(4) dp graph vs the hierarchical two-level "
                  "exchange (2 machines x L=2, one-peer machine "
                  "schedule).  dcn_bytes_per_step = collective-permute "
                  "payloads (the only inter-machine wire either build "
                  "emits); the hierarchical ICI leg is the grouped "
                  "all-reduce, billed separately.",
        "config": {"dp": HIER_DP, "tp": HIER_TP, "machines": HIER_M,
                   "local_size": HIER_L, "buckets": buckets,
                   "comm_mode": comm_mode, "guard": True,
                   "health": True, "compress": "int8"},
        "compile_s": round(time.perf_counter() - t0, 1),
        "flat": sf,
        "hierarchical": sh,
        "dcn_bytes_per_step": sh["dcn_bytes_per_step"],
        "tp_overlap_fraction": sh["tp_overlap_fraction"],
        "claims": {
            "dcn_bytes_cut":
                sh["dcn_bytes_per_step"] < sf["dcn_bytes_per_step"],
            "dcn_bytes_ratio": round(
                sh["dcn_bytes_per_step"]
                / max(sf["dcn_bytes_per_step"], 1), 4),
            "tp_overlap_defended":
                sh["tp_overlap_fraction"] > 0.41,
            # the exact local mean is extra in-machine work; the cost
            # model must show it bounded, not a hidden 2x — the DCN
            # win may not be bought with a memory-traffic blowup
            "cost_model_overhead_ratio": round(
                sh["cost_bytes_accessed"]
                / max(sf["cost_bytes_accessed"], 1.0), 4),
            "cost_model_overhead_bounded":
                sh["cost_bytes_accessed"]
                <= 1.05 * sf["cost_bytes_accessed"],
        },
    }


MIX_RATIO = 0.25          # MixCompressConfig's shipped default


def compressed_audit(buckets: int, comm_mode: str = "atc",
                     baseline_dcn: float = 0.0) -> dict:
    """The r17 claim, machine-checked at the real 8B step: top-k(0.25)
    error-feedback mixing composed with the int8 wire cuts measured
    DCN bytes/step to <= 0.5x the r14 int8-only hierarchical record,
    while the collective contract stays byte-exact (every lowered
    permute payload is one of the per-bucket ``mix_wire_bytes`` sizes
    predicted from the layout alone) and a live compress-ratio swap
    changes pure data (identical avals/shardings, so the jit cache hit
    is structural — tests/test_epilogue.py runs the live zero-recompile
    check on the small mesh).

    Same dp4 x tp4 / 2-machine x L=2 layout and guard+health bucketed
    config as the hierarchical audit, so ``baseline_dcn`` (that leg's
    int8-only measurement) is apples-to-apples."""
    from bluefog_tpu import benchutil as B_
    from bluefog_tpu.optim.functional import (GuardConfig, HealthConfig,
                                              MixCompressConfig,
                                              MixState)
    from bluefog_tpu.topology.dynamic import one_peer_dynamic_schedule

    t0 = time.perf_counter()
    build, (a_params, a_opt, a_batch) = _pod_step_setup(
        dp=HIER_DP, tp=HIER_TP,
        topo_kwargs=dict(schedule=one_peer_dynamic_schedule(HIER_M),
                         hierarchical=HIER_L))
    step = build(comm_mode=comm_mode,
                 compress=MixCompressConfig(ratio=MIX_RATIO,
                                            values="int8"),
                 overlap="bucketed", overlap_buckets=buckets,
                 guard=GuardConfig(), health=HealthConfig())
    # MixState avals take the step's own specs — under tp the EF rows
    # shard per DEVICE (P("bf", "tp")), not per rank (P("bf") would
    # hand each tp slice the full-rank row, 4x its bucket shards)
    sp = step.mix_state_specs
    sds = lambda l, s: jax.ShapeDtypeStruct(
        l.shape, l.dtype, sharding=NamedSharding(build.mesh, s))
    t = jax.eval_shape(step.init_mix_state, a_params)
    a_mix = MixState(
        ratio=sds(t.ratio, sp.ratio),
        err=tuple(sds(e, sp.err) for e in t.err),
        ref=tuple(sds(r, sp.ref) for r in t.ref),
        mirror=tuple(sds(m, sp.mirror) for m in t.mirror))
    a_state = (a_opt, a_mix)
    compiled = step.lower(a_params, a_state, a_batch, jnp.int32(0),
                          step.default_comm_weights).compile()
    hlo = compiled.as_text()
    dcn = B_.hlo_collective_bytes(hlo).get(
        "collective-permute", {"count": 0, "bytes": 0})

    # the contract: every permute payload is one of the per-bucket
    # wire sizes predicted from shapes alone, and the totals match
    layout = step.mix_wire_layout(a_params)
    rounds = len(one_peer_dynamic_schedule(HIER_M))
    predicted = {
        "permutes_per_period": len(layout) * rounds,
        "bytes_per_period": float(
            sum(r["wire_bytes"] for r in layout) * rounds),
    }
    payloads = sorted({r["wire_bytes"] for r in layout})
    contract = B_.verify_collective_contract(hlo, predicted, payloads)

    # a ratio swap is pure data: identical avals in, identical out
    swapped = jax.eval_shape(
        lambda s: step.set_mix_ratio(s, MIX_RATIO / 2), a_state)
    avals_unchanged = (jax.tree.structure(swapped)
                       == jax.tree.structure(a_state)) and all(
        a.shape == b.shape and a.dtype == b.dtype
        for a, b in zip(jax.tree.leaves(swapped),
                        jax.tree.leaves(a_state)))

    return {
        "method": "AOT-compiled guard+health bucketed "
                  f"(K={buckets}, {comm_mode}) 8B step at the "
                  "hierarchical dp4 x tp4 / 2-machine x L=2 layout "
                  "with compress=MixCompressConfig(ratio=0.25, "
                  "values='int8'): DCN bytes = collective-permute "
                  "payloads of the compiled module; the contract "
                  "holds every lowered permute to the per-bucket "
                  "mix_wire_bytes prediction (values int8 + packed "
                  "keep-mask + scale per bucket).",
        "config": {"dp": HIER_DP, "tp": HIER_TP, "machines": HIER_M,
                   "local_size": HIER_L, "buckets": buckets,
                   "comm_mode": comm_mode, "guard": True,
                   "health": True, "mix_ratio": MIX_RATIO,
                   "mix_values": "int8"},
        "compile_s": round(time.perf_counter() - t0, 1),
        "wire_layout": list(layout),
        "dcn_permute_count": dcn["count"],
        "dcn_bytes_per_step": dcn["bytes"],
        "claims": {
            "predicted_collectives_byte_exact": contract == [],
            "contract_problems": contract,
            "dcn_bytes_vs_int8_only": round(
                dcn["bytes"] / max(baseline_dcn, 1.0), 4),
            "dcn_bytes_halved":
                bool(baseline_dcn)
                and dcn["bytes"] <= 0.5 * baseline_dcn,
            "ratio_swap_avals_unchanged": bool(avals_unchanged),
        },
    }


def audit(buckets: int, comm_mode: str = "atc") -> dict:
    hlo, secs = lower_bucketed_step(buckets, comm_mode)
    link = V5E_LINK_GBPS * 1e9 / 8
    peak = 197e12          # v5e dense bf16 peak
    hbm = 819e9            # v5e HBM bytes/s
    dp = benchutil.overlap_accounting(
        hlo, peak_flops_per_s=peak, link_bytes_per_s=link,
        hbm_bytes_per_s=hbm, congestion=POD_DP_CONGESTION,
        kinds=("collective-permute",))
    tp = benchutil.overlap_accounting(
        hlo, peak_flops_per_s=peak, link_bytes_per_s=link,
        hbm_bytes_per_s=hbm, congestion=1.0,
        kinds=("all-gather", "reduce-scatter"))

    def summarize(acc):
        return {
            "basis": acc["basis"],
            "count": sum(r["count"] for r in acc["per_kind"].values()),
            "bytes_total": acc["bytes_total"],
            "bytes_overlappable": acc["bytes_overlappable"],
            "fraction": round(acc["fraction"], 4),
        }

    return {
        "method": "AOT-compiled bucketed train step (overlap='bucketed', "
                  f"K={buckets}, {comm_mode}, int8 wire) at the "
                  "tp8_seqshard 8B layout on the 16-virtual-device CPU "
                  "mesh; benchutil.overlap_accounting over the scheduled "
                  "module at v5e figures (197 TFLOP/s peak, 819 GB/s "
                  "HBM, 25 GB/s/link, dp congestion 16/7). basis="
                  "'dataflow' = compute neither ancestor nor descendant "
                  "of the collective, the latency-hiding scheduler's "
                  "admissible set; re-run on a pod with "
                  "benchutil.latency_hiding_xla_flags() for the "
                  "'scheduled' (start->done window) basis.",
        "buckets": buckets,
        "comm_mode": comm_mode,
        "compile_s": round(secs, 1),
        "xla_flags_for_pods": list(benchutil.LATENCY_HIDING_XLA_FLAGS),
        "dp_neighbor_exchange": summarize(dp),
        "tp_allgather_reducescatter": summarize(tp),
    }


def rebase_projection(result: dict) -> None:
    """Re-base the composed 8B projection on the defended fractions —
    one MFU number (docs/performance.md 'Overlap engine')."""
    train = result.get("train")
    overlap = result.get("overlap")
    if not train or not overlap:
        return
    comp = train["composition"]
    ici = train["ici_analytic"]
    t_chip = comp["t_chip_s"]
    t_tp = ici["tp_allgather_reducescatter_s_per_step"]
    t_dp = ici["dp_neighbor_exchange_int8_s"]
    comp["formula"] = (
        "t_chip = 32*(fwd+fwd_bwd) + embed + min(head, head_chunked) + "
        "opt; t_step = t_chip + (1-f_tp)*t_tp + (1-f_dp)*t_dp with f_* "
        "the defended overlap fractions (overlap record)")
    f_dp = overlap["dp_neighbor_exchange"]["fraction"]
    f_tp = overlap["tp_allgather_reducescatter"]["fraction"]
    t_step = t_chip + (1 - f_tp) * t_tp + (1 - f_dp) * t_dp
    flops = train["projected"]["flops_per_step_per_dp_rank"]
    peak = train["projected"]["chip_peak_flops"]
    train["composition"]["t_step_defended_s"] = round(t_step, 4)
    train["projected"] = {
        "flops_per_step_per_dp_rank": flops,
        "chip_peak_flops": peak,
        "overlap_fraction_dp": f_dp,
        "overlap_fraction_tp": f_tp,
        "overlap_basis": overlap["dp_neighbor_exchange"]["basis"],
        "mfu_defended": round(flops / TP / t_step / peak, 4),
        "tokens_per_sec_v5e128_dp16": round(16 * B * T / t_step, 1),
        "note": "t_step = t_chip + (1-f_tp)*t_tp + (1-f_dp)*t_dp with "
                "f_* the overlappable-bytes fractions above — replaces "
                "the r05 no-overlap/full-overlap spread with one "
                "defended number",
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--buckets", type=int, default=8)
    ap.add_argument("--comm-mode", default="atc",
                    choices=["atc", "cta"])
    ap.add_argument("--out",
                    default="benchmarks/llama_8b_measured.json")
    ap.add_argument("--skip-hierarchical", action="store_true",
                    help="skip the flat-vs-two-level DCN byte "
                         "accounting (2 extra AOT compiles)")
    ap.add_argument("--skip-compressed", action="store_true",
                    help="skip the EF top-k compressed-mixing DCN "
                         "audit (1 extra AOT compile)")
    args = ap.parse_args()

    result = {}
    if os.path.exists(args.out):
        with open(args.out) as fh:
            result = json.load(fh)
    result["overlap"] = audit(args.buckets, args.comm_mode)
    if not args.skip_hierarchical:
        result["hierarchical"] = hierarchical_audit(args.buckets,
                                                    args.comm_mode)
    if not args.skip_compressed:
        base = result.get("hierarchical", {}).get(
            "dcn_bytes_per_step", 0.0)
        result["compressed"] = compressed_audit(
            args.buckets, args.comm_mode, baseline_dcn=base)
    rebase_projection(result)
    with open(args.out, "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result["overlap"], indent=1))
    if "hierarchical" in result:
        print(json.dumps(result["hierarchical"]["claims"], indent=1))
    if "compressed" in result:
        print(json.dumps(result["compressed"]["claims"], indent=1))
    if "train" in result:
        print(json.dumps(result["train"]["projected"], indent=1))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
