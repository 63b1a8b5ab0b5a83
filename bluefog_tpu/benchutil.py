"""Shared benchmark timing helpers.

JAX dispatch is asynchronous, so timed work must end in a sync:
``jax.block_until_ready`` on an output, or — what ``device_fetch`` does —
materializing on the host a value that depends on the computation.  The
fetch carries one host<->device copy, which ``fetch_overhead`` measures
with a FRESH value each probe (``x + 1``): re-fetching the same
jax.Array hits its cached host copy and measures ~0.
"""

from __future__ import annotations

import re
import time

import jax
import numpy as np

__all__ = ["device_fetch", "fetch_overhead", "timed",
           "chain_time", "fwd_bwd_time", "poisson_arrivals",
           "chip_peak_flops", "chip_hbm_bandwidth", "compiled_step_flops",
           "mfu", "hlo_collective_bytes", "hlo_op_breakdown",
           "scheduled_collective_windows", "overlap_accounting",
           "LATENCY_HIDING_XLA_FLAGS", "latency_hiding_xla_flags",
           "bench_headline", "bench_compare", "bench_regression_gate"]

# Dense bf16 peak FLOP/s per chip, from published TPU specs.  Keyed by
# substrings of jax's ``device_kind``; override with BLUEFOG_CHIP_PEAK_TFLOPS
# when the kind is unlisted (e.g. a new generation).
_PEAK_BF16_TFLOPS = (
    ("v6e", 918.0),      # Trillium
    ("v6", 918.0),
    ("v5p", 459.0),
    ("v5e", 197.0),
    ("v5 lite", 197.0),  # v5e's device_kind spelling in some releases
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)


def _chip_figure(table, what: str, device) -> float:
    """Look ``device`` (default: the first attached one) up in a
    published-spec table keyed by ``device_kind`` substrings.  A TPU
    whose kind is unlisted RAISES — a silent 0.0 there turns a missing
    table row into ``"mfu": 0.0`` with exit code 0; any other platform
    (the CPU test meshes) has no published peak and reads 0.0."""
    if device is None:
        device = jax.devices()[0]
    kind = device.device_kind.lower()
    for key, value in table:
        if key in kind:
            return value
    if device.platform == "tpu":
        raise ValueError(
            f"no published {what} for TPU device_kind "
            f"{device.device_kind!r}: add it to the table in "
            "bluefog_tpu/benchutil.py")
    return 0.0


def chip_peak_flops(device=None) -> float:
    """Peak dense bf16 FLOP/s of one chip; 0.0 off-TPU (CPU test
    meshes), an error for a TPU kind the table does not list.
    Override: BLUEFOG_CHIP_PEAK_TFLOPS=<float>."""
    from bluefog_tpu import config as bfconfig

    override = bfconfig.chip_peak_tflops_override()
    if override:
        return override * 1e12
    return _chip_figure(_PEAK_BF16_TFLOPS, "bf16 peak", device) * 1e12


# HBM bandwidth per chip (bytes/s), published specs; same keying and
# override pattern as the FLOPs table (BLUEFOG_CHIP_HBM_GBPS).
_HBM_GBPS = (
    ("v6e", 1638.0),
    ("v6", 1638.0),
    ("v5p", 2765.0),
    ("v5e", 819.0),
    ("v5 lite", 819.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
)


def chip_hbm_bandwidth(device=None) -> float:
    """HBM bandwidth of one chip in bytes/s; 0.0 off-TPU, an error for
    an unlisted TPU kind.  Override: BLUEFOG_CHIP_HBM_GBPS=<float>."""
    from bluefog_tpu import config as bfconfig

    override = bfconfig.chip_hbm_gbps_override()
    if override:
        return override * 1e9
    return _chip_figure(_HBM_GBPS, "HBM bandwidth", device) * 1e9


def compiled_step_flops(jitted, *args) -> float:
    """Per-device FLOPs of one execution of ``jitted(*args)`` from XLA's
    own cost analysis of the optimized module — the hardware-honest count
    (rematerialized FLOPs included, which is what the chip executes).
    A failed compile or a cost analysis without a FLOP count raises."""
    return float(jitted.lower(*args).compile().cost_analysis()["flops"])


def mfu(flops_per_step: float, step_seconds: float,
        peak_per_chip: float = None) -> float:
    """Model FLOPs utilization: achieved FLOP/s over peak FLOP/s.
    ``flops_per_step`` is PER DEVICE (as ``compiled_step_flops`` reports);
    returns 0.0 when the peak is unknown."""
    if peak_per_chip is None:
        peak_per_chip = chip_peak_flops()
    if not peak_per_chip or step_seconds <= 0:
        return 0.0
    return flops_per_step / step_seconds / peak_per_chip


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

# one HLO collective instruction: `%name = TYPE op-name(%operand, ...)` —
# optimized HLO prints operands as bare names, so the payload shape is the
# RESULT type to the left of the op name (tuple types for fused/async ops)
_COLLECTIVE_RE = re.compile(
    r"=\s*(?P<types>[^=]*?)\s*\b(?P<op>collective-permute|all-reduce|"
    r"all-gather|reduce-scatter|all-to-all)(?P<suffix>-start|-done)?\(")
_SHAPE_RE = re.compile(r"\b(pred|[sub]8|[sufb]\d+|bf16)\[([0-9,]*)\]")


def hlo_collective_bytes(hlo_text: str) -> dict:
    """Per-collective-kind payload bytes of one execution of an optimized
    HLO module: ``{kind: {"count": n_instructions, "bytes": sum}}``.

    Bytes come from each collective's result type — the PER-DEVICE shard
    payload (tuple results summed; async ``-start`` skipped and counted
    at the matching ``-done`` so pairs are not double-counted).  For
    all-gather the result is the gathered buffer, an upper bound within
    (n-1)/n of the wire bytes.  Collectives inside ``conditional``
    branches (a caller's own ``lax.switch``; ``build_train_step``
    compiles a dynamic schedule one program a round and emits none) are
    all present in the module text but only one branch executes per
    step — callers divide by the branch count for per-step figures."""
    out: dict = {}
    # tuple types are printed with /*index=N*/ comments whose '=' would
    # truncate the types capture — strip them first
    hlo_text = re.sub(r"/\*.*?\*/", "", hlo_text)
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        if m.group("suffix") == "-start":
            continue
        kind = m.group("op")
        rec = out.setdefault(kind, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += _shape_bytes(m.group("types"))
    return out


# ---------------------------------------------------------------------------
# Overlap accounting over the scheduled HLO module.
#
# The overlap engine (build_train_step(overlap="bucketed")) structures the
# program so the latency-hiding scheduler CAN overlap the decentralized
# exchange with compute; this section is the "prove it" half.  Two measures,
# one threshold:
#
# * overlap_available — schedule-INVARIANT: for each collective, the flops
#   of instructions that are neither its dataflow ancestors nor descendants
#   (compute that may legally execute while the transfer is in flight).
#   Computable from any lowering, including the CPU AOT audit modules
#   (benchmarks/llama_8b_structural.py style) where collectives lower
#   synchronously.
# * overlap_scheduled — what the scheduler DID: flops of instructions the
#   schedule placed inside each async ``-start``/``-done`` window.  Only
#   nonzero on async lowerings (TPU with the latency-hiding scheduler).
#
# A collective's payload counts as OVERLAPPABLE when the measured flops
# cover the payload's transfer time: flops/peak >= bytes*congestion/link.
# ---------------------------------------------------------------------------

# Flags that let the TPU latency-hiding scheduler overlap collectives with
# compute — set them identically for benchmarks and prod so measured overlap
# fractions transfer (append to XLA_FLAGS before jax initializes; see
# docs/performance.md).
LATENCY_HIDING_XLA_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_collective_permute=true",
    "--xla_enable_async_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    # scheduler memory budget: HBM headroom the scheduler may spend
    # keeping transfers in flight instead of minimizing live ranges
    "--xla_tpu_scheduler_percent_shared_memory_limit=90",
)


def latency_hiding_xla_flags(extra: tuple = ()) -> str:
    """Merge ``LATENCY_HIDING_XLA_FLAGS`` (+ any extras) into the
    XLA_FLAGS environment string and return it; flags already present in
    the environment win (so a deployment can pin its own scheduler
    budget).  Call BEFORE the first jax import/initialization."""
    import os

    current = os.environ.get("XLA_FLAGS", "")
    have = {f.split("=")[0] for f in current.split() if f}
    add = [f for f in tuple(LATENCY_HIDING_XLA_FLAGS) + tuple(extra)
           if f.split("=")[0] not in have]
    merged = " ".join(filter(None, [current] + add))
    os.environ["XLA_FLAGS"] = merged
    return merged


_COLLECTIVE_OPS = ("collective-permute", "all-reduce", "all-gather",
                   "reduce-scatter", "all-to-all")
# `%name = <types> op(args...)[, attrs]` with optional ROOT; types may be a
# tuple `(f32[..], ...)`.  args are cut at the matching close-paren by hand.
_HLO_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<types>\([^)]*\)|[^\s]+)\s+(?P<op>[\w\-]+)\((?P<rest>.*)$")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
# computation header: `[ENTRY] %name (params...) -> type {` — the param
# list may contain nested parens (tuple-typed args of conditional
# branches / while bodies), so the name is captured up to the first "("
# and the rest of the line is only checked for the "-> ... {" tail
_COMP_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")

# ops that move/alias bytes or carry no arithmetic — zero flops
_ZERO_FLOP_OPS = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "copy", "copy-start", "copy-done", "broadcast", "reshape", "transpose",
    "convert", "iota", "slice", "dynamic-slice", "dynamic-update-slice",
    "concatenate", "pad", "after-all", "partition-id", "replica-id",
    "custom-call", "send", "recv", "send-done", "recv-done",
    "opt-barrier", "optimization-barrier", "domain", "gather", "scatter",
))


def _shape_elems(type_text: str) -> int:
    """Total elements across every shape in an HLO type string."""
    total = 0
    for sm in _SHAPE_RE.finditer(type_text):
        n = 1
        for d in sm.group(2).split(","):
            if d:
                n *= int(d)
        total += n
    return max(total, 0)


def _shape_bytes(type_text: str) -> int:
    nbytes = 0
    for sm in _SHAPE_RE.finditer(type_text):
        dt, dims = sm.group(1), sm.group(2)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * _DTYPE_BYTES.get(dt, 4)
    return nbytes


def _dot_flops(types: str, rest: str) -> float:
    """2 * result_elems * contracted_size from the printed dot line:
    result type on the left, lhs operand type + lhs_contracting_dims on
    the right."""
    result_elems = _shape_elems(types)
    lhs_m = _SHAPE_RE.search(rest)
    cm = _CONTRACT_RE.search(rest)
    if not lhs_m or not cm:
        return 2.0 * result_elems  # malformed print; floor estimate
    lhs_dims = [int(d) for d in lhs_m.group(2).split(",") if d]
    contracted = 1
    for idx in (int(i) for i in cm.group(1).split(",") if i):
        if idx < len(lhs_dims):
            contracted *= lhs_dims[idx]
    return 2.0 * result_elems * contracted


def _parse_computations(hlo_text: str):
    """{computation_name: [instruction dicts in scheduled order]}.

    Each instruction: name, op, types, rest (text after the open paren),
    operands (referenced %names), line index within the computation."""
    hlo_text = re.sub(r"/\*.*?\*/", "", hlo_text)
    comps: dict = {}
    cur_name, cur_list = None, None
    for line in hlo_text.splitlines():
        if cur_name is None:
            st = line.strip()
            if st.endswith("{") and "->" in st and "=" not in st:
                m = _COMP_HEADER_RE.match(st)
                if m:
                    cur_name, cur_list = m.group(1), []
            continue
        if line.strip().startswith("}"):
            comps[cur_name] = cur_list
            cur_name, cur_list = None, None
            continue
        im = _HLO_INSTR_RE.match(line)
        if im:
            cur_list.append({
                "name": im.group("name"),
                "op": im.group("op"),
                "types": im.group("types"),
                "rest": im.group("rest"),
                "operands": _OPERAND_RE.findall(im.group("rest")),
                "idx": len(cur_list),
            })
    return comps


def _instr_flops(instr: dict, comps: dict, _memo: dict) -> float:
    """Estimated flops of one instruction: dots get the exact
    2*M*N*K; fusions add their called computation's dots to an
    elementwise sweep of the fusion result; reductions read their
    operand; other arithmetic ops count one flop per result element;
    pure data movement counts zero.  This intentionally mirrors what
    XLA's own cost analysis charges for the ops that matter here
    (collective-window compute is dominated by dots and elementwise
    fusions)."""
    op = instr["op"]
    if op in _ZERO_FLOP_OPS or any(op.startswith(c) for c in
                                   _COLLECTIVE_OPS):
        return 0.0
    if op == "dot":
        return _dot_flops(instr["types"], instr["rest"])
    if op == "fusion":
        cm = _CALLS_RE.search(instr["rest"])
        inner = 0.0
        if cm and cm.group(1) in comps:
            key = cm.group(1)
            if key not in _memo:
                _memo[key] = 0.0  # cycle guard
                _memo[key] = sum(
                    _instr_flops(i, comps, _memo)
                    for i in comps[key] if i["op"] != "fusion")
            inner = _memo[key]
        return inner + _shape_elems(instr["types"])
    if op in ("reduce", "reduce-window"):
        # a reduction reads every operand element once
        return float(_shape_elems(instr["rest"]))
    if op in ("while", "conditional", "call", "sort", "scatter"):
        return 0.0  # accounted inside their own computations
    return float(_shape_elems(instr["types"]))


def _instr_bytes_accessed(instr: dict) -> int:
    """Estimated HBM bytes an instruction touches: result + operand
    shapes from the printed line (elementwise compute is
    bandwidth-bound; its capacity to hide a transfer is bytes/HBM_bw,
    not flops/peak)."""
    return _shape_bytes(instr["types"]) + _shape_bytes(instr["rest"])


def scheduled_collective_windows(hlo_text: str) -> list:
    """One record per collective instruction of a (scheduled) HLO module:

    ``{kind, computation, bytes, async, window_flops,
    window_bytes_accessed, independent_flops,
    independent_bytes_accessed}``

    * ``window_flops`` — flops the SCHEDULE placed between the
      collective's ``-start`` and ``-done`` (async lowerings; 0 when the
      op lowered synchronously): compute that provably executes during
      the transfer.
    * ``independent_flops`` — flops of instructions in the same
      computation that are neither dataflow ancestors nor descendants of
      the collective: compute a latency-hiding scheduler MAY place in
      flight, measurable even from sync lowerings (CPU AOT audits).

    Bytes come from the result payload (the ``-done``/sync result), per
    device, same convention as :func:`hlo_collective_bytes`.
    """
    comps = _parse_computations(hlo_text)
    memo: dict = {}
    out = []
    for cname, instrs in comps.items():
        by_name = {i["name"]: i for i in instrs}
        flops = [_instr_flops(i, comps, memo) for i in instrs]
        # users map for ancestor/descendant walks
        users: dict = {i["name"]: [] for i in instrs}
        for i in instrs:
            for o in i["operands"]:
                if o in users:
                    users[o].append(i["name"])

        def _closure(start_name, direction):
            seen, stack = set(), [start_name]
            while stack:
                n = stack.pop()
                if n in seen or n not in by_name:
                    continue
                seen.add(n)
                nxt = (by_name[n]["operands"] if direction == "up"
                       else users.get(n, ()))
                stack.extend(nxt)
            return seen

        touched = [_instr_bytes_accessed(i) if f or i["op"] == "fusion"
                   else 0 for i, f in zip(instrs, flops)]
        for i in instrs:
            op = i["op"]
            kind = next((c for c in _COLLECTIVE_OPS
                         if op == c or op == c + "-start"), None)
            if kind is None:
                continue
            is_async = op.endswith("-start")
            done_idx = None
            if is_async:
                for j in instrs[i["idx"] + 1:]:
                    if (j["op"] == kind + "-done"
                            and i["name"] in j["operands"]):
                        done_idx = j["idx"]
                        break
            window = wbytes = 0.0
            if done_idx is not None:
                rng = range(i["idx"] + 1, done_idx)
                window = sum(flops[k] for k in rng)
                wbytes = sum(touched[k] for k in rng)
            blocked = _closure(i["name"], "up") | _closure(i["name"],
                                                           "down")
            independent = sum(
                f for j, f in zip(instrs, flops)
                if j["name"] not in blocked)
            ibytes = sum(
                t for j, t in zip(instrs, touched)
                if j["name"] not in blocked)
            payload = i["types"]
            if done_idx is not None:
                payload = instrs[done_idx]["types"]
            elif is_async:
                # unmatched start (done in another computation print):
                # charge the operand payload
                payload = i["rest"]
            out.append({
                "kind": kind,
                "computation": cname,
                "bytes": _shape_bytes(payload),
                "async": bool(is_async),
                "window_flops": float(window),
                "window_bytes_accessed": float(wbytes),
                "independent_flops": float(independent),
                "independent_bytes_accessed": float(ibytes),
            })
    return out


def _count_hlo_collectives(hlo_text: str, kind: str) -> int:
    """Instruction count of one collective ``kind`` in optimized HLO —
    sync spelling plus async ``-start`` (the start counted alone so an
    async pair is one op), the counting rule the HLO-guarantee tests
    always used."""
    return len(re.findall(re.escape(kind) + r"(?:-start)?\(", hlo_text))


def _expected_replica_groups(n_groups: int, group_size: int) -> str:
    """The ``replica_groups`` attribute text of a grouped all-reduce over
    contiguous rank blocks — machine g owns ranks
    ``[g*L, (g+1)*L)``, exactly how the hierarchical exchange groups."""
    groups = ",".join(
        "{" + ",".join(str(g * group_size + i) for i in range(group_size))
        + "}" for g in range(n_groups))
    return "replica_groups={" + groups + "}"


def verify_collective_contract(compiled, predicted, payload_bytes,
                               *, round_index=None) -> list:
    """Hold a lowered program to its declared collective sketch.

    ``compiled`` is optimized HLO text or anything with ``.as_text()``
    (a jit ``Compiled``); ``predicted`` is a
    ``CompiledTopology.predicted_collectives(payload_bytes)`` /
    ``CompiledHierarchicalTopology`` dict.  With ``round_index=None``
    the module is a program holding the full period and is checked
    against the per-period totals; with ``round_index=i`` it is round
    *i* lowered alone and is checked against ``per_round[i]``.

    Returns a list of human-readable mismatch strings — empty means the
    contract holds.  This is the supported promotion of the
    predicted-vs-lowered comparison the HLO-guarantee tests pioneered
    (tests/test_hlo_guarantees.py is now a thin wrapper, and
    ``bluefog_tpu.analysis`` runs the same check statically): permute
    count, per-permute payload bytes, total bytes, and — for
    hierarchical predictions — the grouped all-reduce count and its
    ``replica_groups`` machine decomposition.

    ``payload_bytes`` is one admissible per-permute payload or a
    collection of them: compressed mixing moves a DIFFERENT (but still
    statically known) wire size per bucket, so a multi-bucket program
    legitimately lowers heterogeneous permutes.  Every lowered payload
    must be a member of the collection, and the per-period TOTAL must
    still match exactly, so an unexpected payload cannot hide inside an
    admissible multiset.
    """
    hlo = compiled.as_text() if hasattr(compiled, "as_text") else compiled
    problems = []

    per_round = predicted.get("per_round", [])
    # internal consistency of the prediction itself: the per-period
    # totals must be the per-round sum, or the dict was tampered/stale
    if per_round:
        tot_p = sum(r["permutes"] for r in per_round)
        if tot_p != predicted["permutes_per_period"]:
            problems.append(
                f"prediction inconsistent: per_round permutes sum {tot_p}"
                f" != permutes_per_period "
                f"{predicted['permutes_per_period']}")
        tot_b = float(sum(r["permutes"] * r["bytes_per_permute"]
                          for r in per_round))
        if tot_b != predicted["bytes_per_period"]:
            problems.append(
                f"prediction inconsistent: per_round bytes sum {tot_b}"
                f" != bytes_per_period {predicted['bytes_per_period']}")

    wins = [w for w in scheduled_collective_windows(hlo)
            if w["kind"] == "collective-permute"]
    if round_index is None:
        want_p = predicted["permutes_per_period"]
        want_bytes = predicted["bytes_per_period"]
        want_r = predicted.get("all_reduces_per_period")
    else:
        rp = per_round[round_index]
        want_p = rp["permutes"]
        want_bytes = rp["permutes"] * rp["bytes_per_permute"]
        want_r = rp.get("all_reduces")
        payload_bytes = rp.get("bytes_per_permute", payload_bytes)

    where = ("program" if round_index is None
             else f"round {round_index}")
    if len(wins) != want_p:
        problems.append(
            f"{where}: {len(wins)} collective-permutes lowered, "
            f"predicted {want_p}")
    admissible = (set(int(p) for p in payload_bytes)
                  if isinstance(payload_bytes, (set, frozenset, list,
                                                tuple))
                  else {int(payload_bytes)})
    bad = [w["bytes"] for w in wins if w["bytes"] not in admissible]
    if bad:
        problems.append(
            f"{where}: permute payloads {bad} not in predicted "
            f"{sorted(admissible)} bytes")
    got_bytes = sum(w["bytes"] for w in wins)
    if got_bytes != want_bytes:
        problems.append(
            f"{where}: {got_bytes} permute bytes lowered, predicted "
            f"{want_bytes}")
    if want_r is not None:
        got_r = _count_hlo_collectives(hlo, "all-reduce")
        if got_r != want_r:
            problems.append(
                f"{where}: {got_r} all-reduces lowered, predicted "
                f"{want_r}")
        groups = predicted.get("all_reduce_groups")
        size = predicted.get("all_reduce_group_size")
        if got_r and groups and size and size > 1:
            expect = _expected_replica_groups(groups, size)
            if expect not in hlo:
                problems.append(
                    f"{where}: grouped all-reduce missing machine "
                    f"decomposition {expect}")
    return problems


def hlo_op_breakdown(hlo_text: str) -> dict:
    """Per-op-kind accounting of an HLO module: ``{op: {"count",
    "flops"}}``, flops from the same estimator the overlap windows use
    (dots exact 2*M*N*K, fusions their called computation + an
    elementwise sweep, data movement zero).  Computations reached only
    through ``fusion(... calls=...)`` are charged at the fusion site,
    not double-counted as free-standing computations.  Loop bodies are
    counted once (a scan executes its body T times — scale by trip
    count when attributing a multi-token program).  This is the
    "per-op accounting" view the round-5 VERDICT asked for on the
    large-batch decode path; the supported entry point is
    ``bluefog_tpu.observe.profile_step`` (which records it as
    ``StepProfile.op_breakdown``)."""
    comps = _parse_computations(hlo_text)
    fusion_called = set()
    for instrs in comps.values():
        for i in instrs:
            if i["op"] == "fusion":
                m = _CALLS_RE.search(i["rest"])
                if m:
                    fusion_called.add(m.group(1))
    memo: dict = {}
    out: dict = {}
    for cname, instrs in comps.items():
        if cname in fusion_called:
            continue
        for i in instrs:
            rec = out.setdefault(i["op"], {"count": 0, "flops": 0.0})
            rec["count"] += 1
            rec["flops"] += _instr_flops(i, comps, memo)
    return out


def overlap_accounting(hlo_text: str,
                       peak_flops_per_s: float,
                       link_bytes_per_s: float,
                       hbm_bytes_per_s: float = 0.0,
                       congestion: float = 1.0,
                       kinds: tuple = ("collective-permute",)) -> dict:
    """Overlappable-bytes accounting for the collectives of ``kinds``.

    A collective's payload is overlappable when the compute available to
    hide it runs at least as long as the transfer::

        max(flops / peak, bytes_accessed / hbm_bw)
            >= payload_bytes * congestion / link_bytes_per_s

    (the bandwidth term matters because the natural hiding material at
    LLM scale — the optimizer's elementwise parameter sweeps — is
    HBM-bound: its wall time is bytes/819GB/s on v5e, far more than its
    flop count suggests; pass ``hbm_bytes_per_s=0`` to score on flops
    alone).

    The measure is chosen PER COLLECTIVE: an async-lowered one is
    scored on its start->done window (the scheduler DID overlap), a
    sync-lowered one on its dataflow-independent compute (the scheduler
    CAN overlap; schedule-invariant, so measurable from the CPU AOT
    audit modules too).  ``basis`` summarizes the module:
    ``"scheduled"`` (all async), ``"dataflow"`` (all sync), or
    ``"mixed"``.  Returns per-kind and total bytes, overlappable bytes,
    and the byte-weighted fraction.
    """
    if peak_flops_per_s <= 0 or link_bytes_per_s <= 0:
        raise ValueError("peak_flops_per_s and link_bytes_per_s must be "
                         "positive (pass the target chip's figures when "
                         "auditing from a CPU host)")
    windows = [w for w in scheduled_collective_windows(hlo_text)
               if w["kind"] in kinds]
    n_async = sum(1 for w in windows if w["async"])
    basis = ("scheduled" if n_async == len(windows) and windows else
             "dataflow" if n_async == 0 else "mixed")
    per_kind: dict = {}
    for w in windows:
        rec = per_kind.setdefault(
            w["kind"], {"count": 0, "bytes": 0, "bytes_overlappable": 0})
        rec["count"] += 1
        rec["bytes"] += w["bytes"]
        # basis PER WINDOW: an async-lowered collective is judged on
        # what the scheduler actually placed in its start->done window;
        # a sync-lowered one (even in the same module) on its
        # dataflow-independent headroom
        if w["async"]:
            flops, touched = w["window_flops"], w["window_bytes_accessed"]
        else:
            flops, touched = (w["independent_flops"],
                              w["independent_bytes_accessed"])
        hide_s = flops / peak_flops_per_s
        if hbm_bytes_per_s > 0:
            hide_s = max(hide_s, touched / hbm_bytes_per_s)
        transfer_s = w["bytes"] * congestion / link_bytes_per_s
        if hide_s >= transfer_s and w["bytes"] > 0:
            rec["bytes_overlappable"] += w["bytes"]
    total = sum(r["bytes"] for r in per_kind.values())
    good = sum(r["bytes_overlappable"] for r in per_kind.values())
    return {
        "basis": basis,
        "per_kind": per_kind,
        "bytes_total": int(total),
        "bytes_overlappable": int(good),
        "fraction": (good / total) if total else 0.0,
        "windows": windows,
    }


def poisson_arrivals(rate: float, n: int, seed: int = 0) -> np.ndarray:
    """Arrival times (seconds, ascending, starting at 0.0) of ``n``
    Poisson arrivals at ``rate`` requests/s: the cumulative sum of
    seeded exponential inter-arrival gaps.  Pure function of
    ``(rate, n, seed)`` — no wall clock anywhere — so the serving bench
    and the serving tests replay the SAME trace
    (benchmarks/serving_bench.py, tests/test_serving.py)."""
    if rate <= 0:
        raise ValueError(f"rate ({rate}) must be positive")
    if n < 1:
        return np.zeros((0,), np.float64)
    gaps = np.random.RandomState(seed).exponential(1.0 / rate, size=n)
    gaps[0] = 0.0
    return np.cumsum(gaps)


def _unit_poisson_targets(n: int, seed: int) -> np.ndarray:
    """Unit-rate Poisson cumulative targets — the shared substrate of
    the non-homogeneous generators below (inversion method: arrival
    *i* lands where the cumulative rate function crosses target *i*).
    Same convention as :func:`poisson_arrivals`: first arrival at 0."""
    gaps = np.random.RandomState(seed).exponential(1.0, size=n)
    gaps[0] = 0.0
    return np.cumsum(gaps)


def diurnal_arrivals(rate: float, n: int, seed: int = 0, *,
                     period: float = 60.0, depth: float = 0.5,
                     phase: float = 0.0) -> np.ndarray:
    """Arrival times of ``n`` requests from a sinusoidally modulated
    Poisson process — the diurnal load shape: instantaneous rate
    ``rate * (1 + depth * sin(2*pi*t/period + phase))`` requests/s.
    Exact inversion of the cumulative rate function (vectorized
    bisection), so counts over any window match its integral in
    expectation and the trace is a pure function of the arguments —
    no thinning, no wall clock, no resampling loop.  ``0 <= depth < 1``
    keeps the rate strictly positive."""
    if rate <= 0:
        raise ValueError(f"rate ({rate}) must be positive")
    if not 0.0 <= depth < 1.0:
        raise ValueError(f"depth ({depth}) must be in [0, 1)")
    if period <= 0:
        raise ValueError(f"period ({period}) must be positive")
    if n < 1:
        return np.zeros((0,), np.float64)
    targets = _unit_poisson_targets(n, seed)
    w = 2.0 * np.pi / period
    amp = rate * depth / w

    def cum_rate(t):
        return rate * t + amp * (np.cos(phase) - np.cos(w * t + phase))

    # cum_rate(t) >= rate*t - 2*amp, so t <= (target + 2*amp)/rate
    lo = np.zeros(n, np.float64)
    hi = (targets + 2.0 * amp) / rate + 1.0
    for _ in range(64):  # bisection to ~1 ulp of the window width
        mid = 0.5 * (lo + hi)
        below = cum_rate(mid) < targets
        lo = np.where(below, mid, lo)
        hi = np.where(below, hi, mid)
    out = 0.5 * (lo + hi)
    out[0] = 0.0
    return out


def flash_crowd_arrivals(rate: float, n: int, seed: int = 0, *,
                         at: float = 0.0, factor: float = 4.0,
                         duration: float = 1.0) -> np.ndarray:
    """Arrival times of ``n`` requests from a Poisson process at
    ``rate`` requests/s with one flash crowd: inside ``[at, at +
    duration)`` the rate jumps to ``rate * factor``.  The cumulative
    rate function is piecewise linear, so the inversion is closed-form
    and exact; outside the burst the trace statistics match
    :func:`poisson_arrivals` at the same base rate.  Deterministic in
    ``(rate, n, seed, at, factor, duration)``."""
    if rate <= 0:
        raise ValueError(f"rate ({rate}) must be positive")
    if factor <= 0:
        raise ValueError(f"factor ({factor}) must be positive")
    if duration < 0 or at < 0:
        raise ValueError(f"burst window (at={at}, duration={duration}) "
                         f"must be non-negative")
    if n < 1:
        return np.zeros((0,), np.float64)
    targets = _unit_poisson_targets(n, seed)
    c1 = rate * at                           # cum rate at burst start
    c2 = c1 + rate * factor * duration       # cum rate at burst end
    out = np.where(
        targets < c1, targets / rate,
        np.where(targets < c2,
                 at + (targets - c1) / (rate * factor),
                 at + duration + (targets - c2) / rate))
    return out.astype(np.float64)


def device_fetch(a) -> np.ndarray:
    """Synchronize by materializing ``a`` on the host."""
    return np.asarray(jax.device_get(a))


def fetch_overhead(repeats: int = 3) -> float:
    """Median wall time of dispatching + fetching a fresh trivial
    computation — the per-sync overhead to subtract from timed loops."""
    x = jax.device_put(np.zeros(1, np.float32))
    y = x + 1.0
    device_fetch(y)  # compile outside timing
    times = []
    for i in range(repeats):
        t0 = time.perf_counter()
        device_fetch(x + float(i + 2))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def timed(run_steps, sync_value_fn, overhead: float = None) -> float:
    """Run ``run_steps()`` (which enqueues work), sync via
    ``sync_value_fn()`` (returning a computation-dependent array), and
    return wall seconds with the fetch overhead subtracted."""
    if overhead is None:
        overhead = fetch_overhead()
    t0 = time.perf_counter()
    run_steps()
    device_fetch(sync_value_fn())
    return max(time.perf_counter() - t0 - overhead, 1e-9)


def chain_time(f, params, x0, n=20, reps=3):
    """Per-iteration seconds of ``x <- barrier(f(params, x)*eps + x0)``
    iterated INSIDE one jitted fori_loop, so a sub-millisecond op is
    timed back to back on the device and not once per host dispatch;
    the data dependence through ``x`` keeps iterations from
    overlapping.  ``params`` ride as jit ARGUMENTS (closed-over weights
    would be baked into the module as constants).  Promoted from
    benchmarks/llama_roofline.py (round 5), whose per-layer sums
    composed to that round's measured 1B train step.
    """
    import jax.numpy as jnp

    @jax.jit
    def chained(p, x):
        def body(i, x):
            y = f(p, x)
            if y.shape != x0.shape:
                # consume EVERY element (a slice would let XLA narrow
                # the producing dot to the sliced columns — observed as
                # a 116% "MFU" on the vocab head)
                y = jnp.mean(y.astype(jnp.float32), axis=-1,
                             keepdims=True)
                y = jnp.broadcast_to(y, x0.shape[:-1] + (1,))
            y = (y.astype(jnp.float32) * 1e-30).astype(x0.dtype)
            return jax.lax.optimization_barrier(x0 + y)
        return jax.lax.fori_loop(0, n, body, x)

    device_fetch(chained(params, x0)[..., :1])
    ov = fetch_overhead()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        device_fetch(chained(params, x0)[..., :1])
        times.append((time.perf_counter() - t0 - ov) / n)
    return float(np.median(times))


def fwd_bwd_time(f, params, x0, n=20, reps=3):
    """fwd+bwd seconds of y = f(params, x) with grads wrt both, chained
    through dx inside one jitted fori_loop (see chain_time).

    Signature is ``(f, params, x0)`` — the SAME argument order as
    ``chain_time`` (round-5 advice: the two public timers previously
    disagreed, silently swapping operands at call sites)."""
    import jax.numpy as jnp

    def loss(p, x):
        return jnp.sum(f(p, x).astype(jnp.float32) ** 2)

    grad = jax.grad(loss, argnums=(0, 1))

    @jax.jit
    def chained(p, x):
        def body(i, x):
            dp, dx = grad(p, x)
            # consume EVERY gradient: an unused dp would let XLA DCE
            # the dW matmuls and report a 2N-FLOP backward as 4N
            dp_sum = sum(jnp.sum(leaf.astype(jnp.float32)) * 1e-30
                         for leaf in jax.tree.leaves(dp))
            return jax.lax.optimization_barrier(
                (dx.astype(jnp.float32) * 1e-30 + dp_sum
                 ).astype(x0.dtype) + x0)
        return jax.lax.fori_loop(0, n, body, x)

    device_fetch(chained(params, x0)[..., :1])
    ov = fetch_overhead()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        device_fetch(chained(params, x0)[..., :1])
        times.append((time.perf_counter() - t0 - ov) / n)
    return float(np.median(times))


# --------------------------------------------------------------------- #
# bench regression gate (ISSUE 5 satellite): compare a fresh run's
# headline numbers against a prior BENCH_*.json — per-metric tolerance,
# one-line delta table, nonzero exit on regression.  The BENCH
# trajectory was previously unaggregated; this makes each run a gate.
# --------------------------------------------------------------------- #
# headline fields worth gating, with their GOOD direction
_HEADLINE_HIGHER = ("value", "mfu", "tokens_per_sec", "useful_tokens",
                    "speedup_tokens_per_sec", "vs_baseline",
                    "compiled_advantage", "hit_rate",
                    "accepted_per_step", "fleet_speedup",
                    "throughput_recovery", "tp_overlap_fraction",
                    "cost_to_consensus_advantage", "decisions_replayed")
_HEADLINE_LOWER = ("ttft_p50", "ttft_p99", "latency_p50", "latency_p99",
                   "makespan_s", "p99", "p50", "cost_to_consensus",
                   "post_rejoin_floor", "dcn_bytes_per_step",
                   "lost_requests", "step_time_ratio",
                   "consensus_floor", "mean_drift", "detect_to_swap_s",
                   "cost_to_dispatch", "mismatches")


def bench_headline(record: dict) -> dict:
    """Extract the gateable headline metrics of a bench JSON record as
    ``{name: float}``.  Understands the three shapes this repo emits:
    the raw ``bench.py`` line (``{"metric", "value", "mfu", ...}``),
    the driver's ``BENCH_*.json`` wrapper (same dict under
    ``"parsed"``), and section records like ``serving_bench``'s
    (headline fields under ``"continuous"``)."""
    if isinstance(record.get("parsed"), dict):
        record = record["parsed"]
    keys = set(_HEADLINE_HIGHER) | set(_HEADLINE_LOWER)
    out: dict = {}

    def grab(d: dict, prefix: str) -> None:
        for k, v in d.items():
            if (k in keys and isinstance(v, (int, float))
                    and not isinstance(v, bool)):
                out[prefix + k] = float(v)

    grab(record, "")
    for section in ("continuous", "static", "chaos", "straggler",
                    "rejoin", "pod_4x8", "pod_8x16", "fleet_one",
                    "fleet_two", "prefix", "speculative",
                    "hierarchical", "fault_free", "chaos_serving",
                    "drain", "adaptation", "congested", "shrink",
                    "rollback", "compressed", "sim_training",
                    "sim_serving", "moe", "measured", "replay"):
        if isinstance(record.get(section), dict):
            grab(record[section], section + ".")
    return out


def _direction(name: str) -> int:
    """+1 = higher is better, -1 = lower is better (latency tails)."""
    base = name.rsplit(".", 1)[-1]
    return -1 if base in _HEADLINE_LOWER else +1


def bench_compare(current: dict, previous: dict, tolerance: float = 0.05,
                  tolerances: dict = None) -> tuple:
    """Compare two bench records' shared headline metrics.

    Returns ``(ok, rows)``: ``rows`` is one dict per shared metric
    (``name, prev, cur, delta_frac, tol, regressed``); ``ok`` is False
    iff any metric moved more than its tolerance in the BAD direction
    (improvements never fail the gate).  ``tolerances`` overrides the
    per-metric relative tolerance by headline name."""
    cur_h = bench_headline(current)
    prev_h = bench_headline(previous)
    rows = []
    ok = True
    for name in sorted(set(cur_h) & set(prev_h)):
        prev, cur = prev_h[name], cur_h[name]
        tol = float((tolerances or {}).get(name, tolerance))
        denom = max(abs(prev), 1e-12)
        delta = (cur - prev) / denom
        regressed = (-delta if _direction(name) > 0 else delta) > tol
        ok = ok and not regressed
        rows.append(dict(name=name, prev=prev, cur=cur,
                         delta_frac=delta, tol=tol, regressed=regressed))
    return ok, rows


def _record_round(path: str, record: dict) -> str:
    """The baseline record's round, for gate attribution: the ``_r<N>``
    filename convention first (``fleet_sim_r20.json`` -> ``r20``), then
    an explicit ``round`` field, else ``r?``."""
    m = re.search(r"_r(\d+)", path.rsplit("/", 1)[-1])
    if m:
        return "r" + m.group(1)
    rec = record.get("parsed") if isinstance(record.get("parsed"),
                                             dict) else record
    rnd = rec.get("round") if isinstance(rec, dict) else None
    return f"r{rnd}" if rnd is not None else "r?"


def _record_sections(record: dict) -> str:
    """Comma-joined section names (dict-valued keys) of a bench record —
    what a no-shared-metrics mismatch message lists for each side."""
    if isinstance(record.get("parsed"), dict):
        record = record["parsed"]
    secs = sorted(k for k, v in record.items() if isinstance(v, dict))
    return ",".join(secs) if secs else "-"


def bench_regression_gate(current: dict, prev_path: str,
                          tolerance: float = 0.05,
                          tolerances: dict = None) -> bool:
    """Gate ``current`` against the record stored at ``prev_path``:
    prints the one-line delta table (naming the baseline file and its
    record round, so a failing gate says exactly which artifact it
    compared against) and returns False on regression (callers
    ``sys.exit(1)``)."""
    import json as _json

    with open(prev_path) as fh:
        previous = _json.load(fh)
    rnd = _record_round(prev_path, previous)
    ok, rows = bench_compare(current, previous, tolerance, tolerances)
    if not rows:
        print(f"[bench-gate] no shared headline metrics with {prev_path} "
              f"({rnd}): current sections [{_record_sections(current)}] "
              f"vs baseline sections [{_record_sections(previous)}]")
        return True
    cells = []
    for r in rows:
        mark = "REGRESSED" if r["regressed"] else "ok"
        cells.append(f"{r['name']} {r['prev']:.4g}->{r['cur']:.4g} "
                     f"({r['delta_frac']:+.1%} {mark})")
    print(f"[bench-gate] vs {prev_path} ({rnd}): " + " | ".join(cells))
    return ok
