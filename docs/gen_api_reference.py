"""Generate the API reference (markdown) by introspection.

The reference ships a 16-file Sphinx tree with autodoc pages
(reference docs/torch_api.rst, tensorflow_api.rst, topo_api.rst,
bluefog_ops.rst, ...).  This environment has no sphinx/pdoc, so this is
a self-contained autodoc: it imports every public module, walks its
public surface (``__all__`` when declared, else public names defined in
the module), and emits one markdown page per module with signatures +
docstrings, plus an index.  Deterministic output — rerunning on an
unchanged tree is a no-op, so CI can assert freshness.

Run (CI-runnable):  PYTHONPATH=. python docs/gen_api_reference.py
Output:             docs/api/*.md
"""

import dataclasses
import importlib
import inspect
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

OUT_DIR = os.environ.get(
    "BLUEFOG_API_REF_OUT",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "api"))

# module -> one-line description for the index
MODULES = [
    ("bluefog_tpu", "top-level package: init/size/rank + the full op API"),
    ("bluefog_tpu.api", "the flat op API (collectives, windows, timeline)"),
    ("bluefog_tpu.topology", "graph generators, weights, dynamic iterators"),
    ("bluefog_tpu.topology.graphs",
     "static graph generators (exp2, ring, mesh, star) + weights"),
    ("bluefog_tpu.topology.dynamic",
     "dynamic one-peer schedules: world-level rounds + iterators"),
    ("bluefog_tpu.topology.spec",
     "device-ready Topology/DynamicTopology shift-class specs"),
    ("bluefog_tpu.topology.torus", "physical ICI torus routing/congestion"),
    ("bluefog_tpu.topology.compiler",
     "topology compiler: pod cost model + schedule synthesis"),
    ("bluefog_tpu.topology.control",
     "closed-loop control plane: detect, re-plan, hot-swap"),
    ("bluefog_tpu.optim", "distributed optimizer wrappers (eager API)"),
    ("bluefog_tpu.optim.functional",
     "jitted whole-pytree train steps (SPMD API)"),
    ("bluefog_tpu.resilience",
     "resilience: fault injection, detection, healing, guarded rollback"),
    ("bluefog_tpu.resilience.faults",
     "deterministic fault-injection plans (the chaos harness)"),
    ("bluefog_tpu.resilience.detector",
     "failure detection: numeric health + liveness heartbeats"),
    ("bluefog_tpu.resilience.healing",
     "topology healing: dead-rank weight re-planning"),
    ("bluefog_tpu.resilience.runner",
     "run_resilient: the skip/heal/rollback control loop"),
    ("bluefog_tpu.elastic",
     "elastic membership: ranks that join, not just die"),
    ("bluefog_tpu.elastic.membership",
     "membership lifecycle + grow_weights (heal's exact inverse)"),
    ("bluefog_tpu.elastic.bootstrap",
     "joiner bootstrap: annealed pull weights + disagreement gate"),
    ("bluefog_tpu.models", "model zoo: Llama, ResNet, ViT, MNIST nets"),
    ("bluefog_tpu.models.llama", "Llama config/stack, TP/EP/vocab-parallel"),
    ("bluefog_tpu.models.generate", "K/V-cached autoregressive decode"),
    ("bluefog_tpu.models.quant", "int8 weight quantization for decode"),
    ("bluefog_tpu.models.afmoe",
     "decoder of mixed window/full attention, gated heads and an expert "
     "layer told which experts it holds"),
    ("bluefog_tpu.models.experts",
     "the expert layer of both expert models: float32 routing (sigmoid "
     "with a bias, or softmax), a shared expert, the held experts in tiles"),
    ("bluefog_tpu.models.mla_moe",
     "decoder of latent attention (a 640- or 1,152-byte-a-token cache, "
     "absorbed decode), YaRN rotation and routed experts; a plain "
     "residual or several mixed streams, leading dense layers"),
    ("bluefog_tpu.models.hyper_connections",
     "a residual of several streams mixed a token at a time "
     "(manifold-constrained hyper-connections): hc_pre, hc_post"),
    ("bluefog_tpu.models.kda",
     "Kimi Delta Attention: a recurrent mixer with a matrix of state a "
     "head; a single-token step and a chunked form"),
    ("bluefog_tpu.models.looped",
     "a dense stack that runs several times over shared weights, a "
     "cache for every pass: one rolled program over stacked leaves"),
    ("bluefog_tpu.models.hybrid_ssm",
     "a Mamba-2 state-space mixer and grouped-query attention side by "
     "side in every layer: a single-token step and a block form of the "
     "scan, a recurrent state beside the keys and values"),
    ("bluefog_tpu.serving.protocol",
     "what the serving layer needs of a model (config methods, cache "
     "leaf kinds)"),
    ("bluefog_tpu.serving.engine",
     "continuous-batching serving engine (slot-pooled K/V decode)"),
    ("bluefog_tpu.serving.kv_pool", "fixed-capacity K/V cache slot pool"),
    ("bluefog_tpu.serving.prefix_cache",
     "chunk-hashed prefix/KV reuse (host-side LRU of prompt-chunk K/V)"),
    ("bluefog_tpu.serving.fleet",
     "gossip-fed multi-replica request router (no central balancer)"),
    ("bluefog_tpu.serving.scheduler",
     "FIFO admission, deadlines, backpressure"),
    ("bluefog_tpu.serving.metrics",
     "serving metrics (TTFT, tokens/s) + request timeline spans"),
    ("bluefog_tpu.serving.resilience",
     "serving chaos: replica faults, token-exact failover, seeded "
     "backoff"),
    ("bluefog_tpu.observe",
     "unified observability: metrics, spans, step profiles, exporters"),
    ("bluefog_tpu.observe.registry",
     "metrics registry: counters, gauges, windowed histograms"),
    ("bluefog_tpu.observe.tracer",
     "span tracer: nested spans, instants, per-thread tracks"),
    ("bluefog_tpu.observe.stepprof",
     "HLO-attributed step profiler (profile_step / StepProfile)"),
    ("bluefog_tpu.observe.compiles",
     "compile accounting: one jax.monitoring listener -> counters + instants"),
    ("bluefog_tpu.observe.export",
     "exporters: Prometheus text, JSONL events, Chrome trace, snapshot"),
    ("bluefog_tpu.observe.fleet",
     "fleet telemetry: push-sum metric gossip, edge traffic, stragglers"),
    ("bluefog_tpu.observe.blackbox",
     "decision flight recorder: causal audit ring, replay, explain CLI"),
    ("bluefog_tpu.parallel.collectives",
     "XLA collective data plane (mesh ops)"),
    ("bluefog_tpu.parallel.ring_attention", "ring/blockwise attention (SP)"),
    ("bluefog_tpu.parallel.ulysses", "all-to-all sequence parallelism"),
    ("bluefog_tpu.parallel.pipeline", "GPipe + circular pipeline schedules"),
    ("bluefog_tpu.parallel.pallas_attention", "Pallas flash attention"),
    ("bluefog_tpu.parallel.pallas_decode",
     "Pallas fused decode-attention step"),
    ("bluefog_tpu.parallel.pallas_kda",
     "Pallas single-token step of the delta rule over the live rows"),
    ("bluefog_tpu.windows", "one-sided window ops (win_put/get/update)"),
    ("bluefog_tpu.compressor", "gradient compression (TopK/RandomK/int8)"),
    ("bluefog_tpu.checkpoint", "orbax checkpoint/resume wrappers"),
    ("bluefog_tpu.data", "DataLoader + DistributedSampler (C++ prefetch)"),
    ("bluefog_tpu.timeline", "Chrome-trace timeline"),
    ("bluefog_tpu.interop.torch_adapter", "torch tensor interop"),
    ("bluefog_tpu.interop.tf_adapter", "TensorFlow bridge (eager + graph)"),
    ("bluefog_tpu.interop.hf_llama", "HuggingFace Llama checkpoint import"),
    ("bluefog_tpu.run.run", "bfrun launcher (local + multi-host)"),
    ("bluefog_tpu.utility", "broadcast/allreduce convenience helpers"),
    ("bluefog_tpu.config", "environment-variable configuration"),
    ("bluefog_tpu.sim",
     "discrete-event fleet simulator: real control plane, virtual time"),
    ("bluefog_tpu.sim.clock",
     "virtual clock: monotonic simulated seconds, no wall reads"),
    ("bluefog_tpu.sim.engine",
     "event heap + streaming event log (byte-stable digests)"),
    ("bluefog_tpu.sim.cost",
     "calibrated cost model: virtual seconds per unit of real work"),
    ("bluefog_tpu.sim.wire",
     "per-step virtual transport billing the telemetry registry"),
    ("bluefog_tpu.sim.traces",
     "request traces + membership churn schedules (seeded)"),
    ("bluefog_tpu.sim.serving",
     "simulated replicas + lockstep fleet around the real router"),
    ("bluefog_tpu.sim.training",
     "simulated training fleet driving the real control plane"),
    ("bluefog_tpu.moe",
     "MoE expert parallelism: compiled a2a dispatch + expert sharding"),
    ("bluefog_tpu.moe.dispatch",
     "all-to-all dispatch plans, route tables, capacity healing"),
    ("bluefog_tpu.moe.layer",
     "top-k routed MoE layer + the expert-sharded loss"),
    ("bluefog_tpu.analysis",
     "static contract checker (bfcheck): findings + baseline"),
    ("bluefog_tpu.analysis.lint",
     "AST lint: env reads, host syncs, traced-if, weight bypass"),
    ("bluefog_tpu.analysis.jaxpr_check",
     "jaxpr/HLO sweep: weights-as-data, divergent cond, collectives"),
]


def _public_names(mod):
    if hasattr(mod, "__all__"):
        return list(mod.__all__)
    names = []
    for name, obj in vars(mod).items():
        if name.startswith("_"):
            continue
        owner = getattr(obj, "__module__", None)
        if inspect.ismodule(obj):
            continue
        if owner is not None and owner != mod.__name__:
            continue
        names.append(name)
    return names


def _strip_addresses(s: str) -> str:
    """Drop runtime memory addresses (e.g. flax's module sentinel
    defaults) so regeneration on an unchanged tree is byte-identical."""
    return re.sub(r" at 0x[0-9a-f]+", "", s)


def _signature(obj) -> str:
    try:
        return _strip_addresses(str(inspect.signature(obj)))
    except (ValueError, TypeError):
        return "(...)"


def _doc(obj) -> str:
    doc = inspect.getdoc(obj)
    return _strip_addresses(doc.strip()) if doc else ""


def _render_function(name, fn, depth="###"):
    out = [f"{depth} `{name}{_signature(fn)}`", ""]
    doc = _doc(fn)
    if doc:
        out += [doc, ""]
    return out


def _render_class(name, cls):
    out = [f"### class `{name}`", ""]
    doc = _doc(cls)
    if doc:
        out += [doc, ""]
    if dataclasses.is_dataclass(cls):
        out += ["**Fields:**", ""]
        for f in dataclasses.fields(cls):
            if f.name in ("parent", "name"):  # flax Module plumbing
                continue
            default = ""
            if f.default is not dataclasses.MISSING:
                # strip runtime memory addresses (sentinel objects) so
                # regeneration on an unchanged tree is byte-identical
                rep = re.sub(r" at 0x[0-9a-f]+", "", repr(f.default))
                default = f" = `{rep}`"
            elif f.default_factory is not dataclasses.MISSING:
                default = " (factory)"
            out.append(f"- `{f.name}`{default}")
        out.append("")
    for mname, meth in sorted(vars(cls).items()):
        if mname.startswith("_") or not callable(meth):
            continue
        fn = meth.__func__ if isinstance(meth, (classmethod,
                                                staticmethod)) else meth
        if not (inspect.isfunction(fn) or inspect.ismethod(fn)):
            continue
        out += _render_function(f"{name}.{mname}", fn, depth="####")
    return out


def render_module(modname: str) -> str:
    mod = importlib.import_module(modname)
    lines = [f"# `{modname}`", ""]
    doc = _doc(mod)
    if doc:
        lines += [doc, ""]
    names = _public_names(mod)
    consts, funcs, classes = [], [], []
    for name in names:
        obj = getattr(mod, name, None)
        if obj is None:
            continue
        if inspect.isclass(obj):
            classes.append((name, obj))
        elif callable(obj):
            funcs.append((name, obj))
        else:
            consts.append((name, obj))
    if funcs:
        lines += ["## Functions", ""]
        for name, fn in funcs:
            lines += _render_function(name, fn)
    if classes:
        lines += ["## Classes", ""]
        for name, cls in classes:
            lines += _render_class(name, cls)
    if consts:
        lines += ["## Constants", ""]
        for name, val in consts:
            rep = re.sub(r" at 0x[0-9a-f]+", "", repr(val))
            if len(rep) > 120:
                rep = rep[:117] + "..."
            lines += [f"- `{name}` = `{rep}`"]
        lines.append("")
    return "\n".join(lines).rstrip() + "\n"


def main() -> int:
    os.makedirs(OUT_DIR, exist_ok=True)
    index = ["# bluefog_tpu API reference", "",
             "Generated by `python docs/gen_api_reference.py` "
             "(introspection autodoc — no sphinx in this environment).",
             ""]
    for modname, desc in MODULES:
        page = modname.replace(".", "_") + ".md"
        content = render_module(modname)
        with open(os.path.join(OUT_DIR, page), "w") as f:
            f.write(content)
        index.append(f"- [`{modname}`]({page}) — {desc}")
        print(f"wrote docs/api/{page}")
    index.append("")
    with open(os.path.join(OUT_DIR, "index.md"), "w") as f:
        f.write("\n".join(index))
    print(f"wrote docs/api/index.md ({len(MODULES)} modules)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
