"""Environment-variable configuration.

The reference configures everything through ``BLUEFOG_*`` env vars
(reference: docs/env_variable.rst; operations.cc:42-47).  We honor the same
names where they still mean something on TPU, and document the ones that are
obsolete by construction (fusion/cycle/negotiation are XLA's job now).
"""

from __future__ import annotations

import os

__all__ = [
    "log_level",
    "log_hide_time",
    "log_format",
    "observe",
    "observe_raw",
    "blackbox_enabled",
    "blackbox_capacity",
    "blackbox_dump_dir",
    "timeline_path",
    "timeline_flush_every",
    "timeline_queue_capacity",
    "timeline_native",
    "straggler_z_threshold",
    "skip_negotiate_default",
    "ops_on_cpu",
    "stall_warning_time",
    "op_timeout",
    "fusion_threshold",
    "hier_local_size",
    "mix_compress",
    "mix_compress_ratio",
    "moe_capacity_factor",
    "kv_zero_on_free",
    "prefix_cache_mb",
    "replica_stale_s",
    "router_retries",
    "router_retry_base_s",
    "router_cooldown_s",
    "elastic_bootstrap_rounds",
    "elastic_quarantine_threshold",
    "topology_replan_window",
    "topology_replan_patience",
    "topology_replan_degrade_ratio",
    "topology_replan_margin",
    "topology_replan_cooldown",
    "topology_replan_probation",
    "coordinator",
    "num_processes",
    "process_id",
    "engine_token",
    "state_dir",
    "chip_peak_tflops_override",
    "chip_hbm_gbps_override",
    "environ_passthrough",
    "configure_host_platform",
    "configure_compilation_cache",
]


def _env(name: str, default: str = "") -> str:
    return os.environ.get(name, default)


def log_level() -> str:
    """BLUEFOG_LOG_LEVEL: trace|debug|info|warn|error|fatal (reference
    logging.h:75, docs/env_variable.rst:9-16)."""
    return _env("BLUEFOG_LOG_LEVEL", "warn").lower()


def log_hide_time() -> bool:
    """BLUEFOG_LOG_HIDE_TIME (reference logging.h:76)."""
    return _env("BLUEFOG_LOG_HIDE_TIME", "0") in ("1", "true", "True")


def log_format() -> str:
    """BLUEFOG_LOG_FORMAT: ``text`` (default, human-readable) or
    ``json`` — one JSON object per line with rank/timestamp/level, the
    shape log aggregators ingest without a parse rule."""
    return _env("BLUEFOG_LOG_FORMAT", "text").lower()


def observe() -> bool:
    """BLUEFOG_OBSERVE (default on): whether the built-in publishers
    write into the observability registry/tracer
    (:mod:`bluefog_tpu.observe`).  ``0`` opts out."""
    return observe_raw()


def observe_raw() -> bool:
    """The raw BLUEFOG_OBSERVE read.
    :func:`bluefog_tpu.observe.registry.enabled` is the public gate the
    publishers call; it delegates here so the env access itself lives in
    this module (the ``env-read-outside-config`` lint contract)."""
    return _env("BLUEFOG_OBSERVE", "1") not in ("0", "false", "False")


def blackbox_enabled() -> bool:
    """BLUEFOG_BLACKBOX (default on): whether the control planes record
    into the process-global decision flight recorder
    (:mod:`bluefog_tpu.observe.blackbox`).  ``0`` opts out; compiled
    programs and step outputs are bit-identical either way — the
    recorder is host-side only, like BLUEFOG_OBSERVE."""
    return _env("BLUEFOG_BLACKBOX", "1") not in ("0", "false", "False")


def blackbox_capacity() -> int:
    """BLUEFOG_BLACKBOX_CAPACITY (default 4096): bound of the decision
    flight recorder's event ring.  At capacity the oldest event is
    evicted and counted (``bf_blackbox_dropped_events``) — O(1) memory
    however long the run; the streaming chain digest is unaffected by
    eviction."""
    try:
        return max(1, int(_env("BLUEFOG_BLACKBOX_CAPACITY", "4096")))
    except ValueError:
        return 4096


def blackbox_dump_dir() -> str:
    """BLUEFOG_BLACKBOX_DUMP: directory the recorder dumps its ring
    into (one JSONL file per anomaly kind) when an anomaly — rollback,
    ``rank_join_failed``, lost request, bench-gate failure — is
    recorded.  Empty (the default) disables the file dump; the
    Chrome-trace instant and the drop/decision counters publish either
    way."""
    return _env("BLUEFOG_BLACKBOX_DUMP", "")


def timeline_path() -> str:
    """BLUEFOG_TIMELINE: path prefix for per-process Chrome-trace files
    (reference operations.cc:464-473)."""
    return _env("BLUEFOG_TIMELINE", "")


def timeline_flush_every() -> int:
    """BLUEFOG_TIMELINE_FLUSH_EVERY (default 1024): every this many
    events drained by the Python timeline writer, the accumulated drop
    count flushes to the ``bf_timeline_dropped_events`` gauge — a
    long-running saturated run is visible before shutdown, not only at
    ``close()``."""
    try:
        return max(1, int(_env("BLUEFOG_TIMELINE_FLUSH_EVERY", "1024")))
    except ValueError:
        return 1024


def timeline_queue_capacity() -> int:
    """BLUEFOG_TIMELINE_QUEUE_CAPACITY (default 65536): bound of the
    Python timeline writer's event queue — roughly the native ring's
    depth.  A full queue drops the event and counts it (the bounded
    contract both backends share); override for stress tests."""
    try:
        return max(1, int(_env("BLUEFOG_TIMELINE_QUEUE_CAPACITY",
                               "65536")))
    except ValueError:
        return 65536


def timeline_native() -> bool:
    """BLUEFOG_TIMELINE_NATIVE (default on): prefer the C++ lock-free
    ring writer when the native extension built; ``0`` forces the
    Python queue backend."""
    return _env("BLUEFOG_TIMELINE_NATIVE", "1") != "0"


def straggler_z_threshold() -> float:
    """BLUEFOG_STRAGGLER_Z (default 4.0): robust step-time z-score above
    which the fleet telemetry layer's
    :class:`~bluefog_tpu.observe.fleet.StragglerDetector` counts a rank
    as slow (flagged after ``patience`` consecutive observations)."""
    try:
        return float(_env("BLUEFOG_STRAGGLER_Z", "4.0"))
    except ValueError:
        return 4.0


def hier_local_size():
    """BLUEFOG_HIER_LOCAL_SIZE (default unset): default intra-machine
    group width of the HIERARCHICAL neighbor exchange — when set (>= 1),
    :func:`bluefog_tpu.optim.functional.build_train_step` builds the
    two-level combine (exact ICI allreduce inside each machine of this
    many ranks, decentralized mixing of machine means across DCN) for
    cta/atc steps that did not pass ``hierarchical=`` /
    ``hierarchical_local_size=`` explicitly; the ``topology=`` /
    ``schedule=`` specs must then be MACHINE-level.  Unset/0 keeps the
    flat rank-level exchange.  Explicit builder arguments always win
    over this env default."""
    raw = _env("BLUEFOG_HIER_LOCAL_SIZE", "")
    try:
        v = int(raw)
    except ValueError:
        return None
    return v if v >= 1 else None


def mix_compress():
    """BLUEFOG_MIX_COMPRESS (default unset): default WIRE COMPRESSION
    mode of :func:`bluefog_tpu.optim.functional.build_train_step` for
    cta/atc steps that did not pass ``compress=`` explicitly —
    ``int8``, ``int8_sr``, ``bf16``, or ``topk`` (error-feedback
    compressed mixing; pair with :func:`mix_compress_ratio`).  Unset or
    unrecognized keeps the full-precision wire.  Explicit builder
    arguments always win over this env default."""
    raw = _env("BLUEFOG_MIX_COMPRESS", "").strip().lower()
    return raw if raw in ("int8", "int8_sr", "bf16", "topk") else None


def mix_compress_ratio():
    """BLUEFOG_MIX_COMPRESS_RATIO (default unset -> builder default):
    kept fraction of each bucket's elements for the error-feedback
    compressed mixing wire (``BLUEFOG_MIX_COMPRESS=topk`` or
    ``compress="topk"``), in (0, 1].  Values >= 1.0 mean "keep
    everything" and build the uncompressed exchange; out-of-range or
    unparsable values are ignored (``None``)."""
    raw = _env("BLUEFOG_MIX_COMPRESS_RATIO", "")
    try:
        v = float(raw)
    except ValueError:
        return None
    return v if v > 0 else None


def moe_capacity_factor() -> float:
    """BLUEFOG_MOE_CAPACITY_FACTOR (default 1.25): default expert
    capacity factor of :func:`bluefog_tpu.moe.layer.default_capacity`
    — each destination rank accepts ``ceil(factor * tokens / n)``
    tokens per source shard; batch-order overflow beyond it is dropped
    onto the residual path (the keep mask is traced data).  Explicit
    ``capacity=`` arguments always win over this env default."""
    try:
        v = float(_env("BLUEFOG_MOE_CAPACITY_FACTOR", "1.25"))
    except ValueError:
        return 1.25
    return v if v > 0 else 1.25


def kv_zero_on_free() -> bool:
    """BLUEFOG_KV_ZERO_ON_FREE (default OFF): whether
    :meth:`bluefog_tpu.serving.SlotPool.free` zeroes the freed slot's
    whole K/V cache.  The default resets only the slot's ``cache_index``
    leaves — correctness needs nothing more (everything above the index
    is invisible behind the causal mask and gets overwritten as the next
    request writes its own positions), and the full-slot zero is a
    whole-slot HBM write per retirement that also destroys K/V the
    prefix cache could have served.  ``1`` restores the old
    zero-everything behavior (a debugging aid: a zeroed pool makes
    "reuse leaves no trace" literal instead of masked)."""
    return _env("BLUEFOG_KV_ZERO_ON_FREE", "0") in ("1", "true", "True")


def prefix_cache_mb() -> int:
    """BLUEFOG_PREFIX_CACHE_MB (default 64): host-side byte budget of the
    serving prefix cache (:mod:`bluefog_tpu.serving.prefix_cache`), in
    MiB.  Evicted K/V chunks are retained up to this bound (LRU) so
    requests sharing a prompt prefix admit by copying cached chunks
    instead of re-running prefill.  0 disables retention."""
    try:
        return int(_env("BLUEFOG_PREFIX_CACHE_MB", "64"))
    except ValueError:
        return 64


def replica_stale_s() -> float:
    """BLUEFOG_REPLICA_STALE_S (seconds, default 0 = disabled): serving
    fleet staleness guard.  A replica that has not published a step
    heartbeat (``bf_serving_last_step_ts``) within this window is marked
    *suspect* by :class:`bluefog_tpu.serving.FleetRouter` — its gossip
    row is masked out and its score pinned to +inf, exactly like the
    explicit dead-mask path — until it steps again.  Replicas that have
    never stepped are exempt (cold replicas must stay routable)."""
    try:
        return float(_env("BLUEFOG_REPLICA_STALE_S", "0"))
    except ValueError:
        return 0.0


def router_retries() -> int:
    """BLUEFOG_ROUTER_RETRIES (default 0): extra full-fleet walks
    :meth:`FleetRouter.submit` makes after the first walk exhausts every
    live replica, separated by seeded exponential backoff
    (:func:`bluefog_tpu.serving.resilience.backoff_sleep`).  0 keeps the
    historical single-walk behavior: one pass, then ``FleetSaturated``."""
    try:
        return max(0, int(_env("BLUEFOG_ROUTER_RETRIES", "0")))
    except ValueError:
        return 0


def router_retry_base_s() -> float:
    """BLUEFOG_ROUTER_RETRY_BASE_S (seconds, default 0.05): base delay of
    the router's seeded exponential backoff between submit retry walks
    (attempt k sleeps ~ base * 2**k, jittered deterministically from the
    router seed and request id)."""
    try:
        return float(_env("BLUEFOG_ROUTER_RETRY_BASE_S", "0.05"))
    except ValueError:
        return 0.05


def router_cooldown_s() -> float:
    """BLUEFOG_ROUTER_COOLDOWN_S (seconds, default 0 = disabled): after a
    replica rejects repeated submits, the router demotes it to the back
    of the candidate walk for this long.  Cooldown only re-orders the
    walk — a cooling replica is still tried last, so cooldown can never
    manufacture a ``FleetSaturated`` on its own."""
    try:
        return float(_env("BLUEFOG_ROUTER_COOLDOWN_S", "0"))
    except ValueError:
        return 0.0


def elastic_bootstrap_rounds() -> int:
    """BLUEFOG_ELASTIC_BOOTSTRAP_ROUNDS (default 8): quarantined mixing
    rounds a joining rank's self-weight anneals over (0 -> its pristine
    weight) while bootstrapping by pulled neighbor averaging
    (:mod:`bluefog_tpu.elastic.bootstrap`).  More rounds = gentler
    re-entry; the first round is always a pure pull regardless."""
    try:
        return max(1, int(_env("BLUEFOG_ELASTIC_BOOTSTRAP_ROUNDS", "8")))
    except ValueError:
        return 8


def elastic_quarantine_threshold() -> float:
    """BLUEFOG_ELASTIC_QUARANTINE_THRESHOLD (default 1.0): max
    normalized bootstrap disagreement (joiner's L2 distance from the
    live mean, in units of the live ranks' own max deviation — see
    :func:`bluefog_tpu.elastic.bootstrap.disagreement`) for promotion
    to LIVE.  <= 1.0 means the joiner sits inside the live consensus
    cloud.  Until it clears, live receivers keep zero weight on the
    joiner — a half-synced value never leaks into the fleet."""
    try:
        return float(_env("BLUEFOG_ELASTIC_QUARANTINE_THRESHOLD", "1.0"))
    except ValueError:
        return 1.0


def topology_replan_window() -> int:
    """BLUEFOG_TOPOLOGY_REPLAN_WINDOW (steps, default 8): how often the
    topology control plane (:class:`bluefog_tpu.topology.control.
    TopologyControlPlane`) takes a telemetry window — per-edge
    byte/second DELTAS, straggler z snapshot, live-set — and re-scores
    the incumbent schedule against it.  Larger windows smooth noise;
    smaller ones react faster."""
    try:
        return max(1, int(_env("BLUEFOG_TOPOLOGY_REPLAN_WINDOW", "8")))
    except ValueError:
        return 8


def topology_replan_patience() -> int:
    """BLUEFOG_TOPOLOGY_REPLAN_PATIENCE (windows, default 2): consecutive
    DEGRADED telemetry windows before the control plane triggers a
    background re-synthesis — the debounce half of the hysteresis pair
    (one noisy window never re-plans).  A live-set transition (death,
    promotion) bypasses patience: membership is structural, not
    noise."""
    try:
        return max(1, int(_env("BLUEFOG_TOPOLOGY_REPLAN_PATIENCE", "2")))
    except ValueError:
        return 2


def topology_replan_degrade_ratio() -> float:
    """BLUEFOG_TOPOLOGY_REPLAN_DEGRADE (default 1.3): a telemetry window
    counts as degraded when some active edge's measured
    seconds-per-activation (normalized by its nominal link cost)
    exceeds the fleet-wide median by this factor — a RELATIVE test, so
    uniform load (every link equally busy) never trips it and the units
    of the seconds counters cancel out."""
    try:
        return float(_env("BLUEFOG_TOPOLOGY_REPLAN_DEGRADE", "1.3"))
    except ValueError:
        return 1.3


def topology_replan_margin() -> float:
    """BLUEFOG_TOPOLOGY_REPLAN_MARGIN (default 0.05): fractional
    cost-to-consensus improvement a synthesized candidate must show
    over the RE-SCORED incumbent to be accepted for a hot swap — the
    anti-flap half of the hysteresis pair (a candidate that merely
    ties the incumbent is noise, and swapping on noise would oscillate
    between near-equal plans)."""
    try:
        return float(_env("BLUEFOG_TOPOLOGY_REPLAN_MARGIN", "0.05"))
    except ValueError:
        return 0.05


def topology_replan_cooldown() -> int:
    """BLUEFOG_TOPOLOGY_REPLAN_COOLDOWN (steps, default 16): minimum
    steps between topology swaps (and after a rollback, before the
    next trigger may fire).  Bounds the worst-case swap rate no matter
    how noisy telemetry gets."""
    try:
        return max(0, int(_env("BLUEFOG_TOPOLOGY_REPLAN_COOLDOWN", "16")))
    except ValueError:
        return 16


def topology_replan_probation() -> int:
    """BLUEFOG_TOPOLOGY_REPLAN_PROBATION (steps, default 8): how long a
    freshly swapped-in schedule is on probation — the control plane
    watches the consensus-distance health signal and rolls back to the
    incumbent if it worsens past the pre-swap baseline; after this
    many clean steps the candidate is committed as the new
    incumbent."""
    try:
        return max(1, int(_env("BLUEFOG_TOPOLOGY_REPLAN_PROBATION", "8")))
    except ValueError:
        return 8


def fusion_threshold() -> int:
    """BLUEFOG_FUSION_THRESHOLD: max bytes of per-rank payload packed into
    one flat fusion buffer by the eager optimizers' communication
    (reference operations.cc:42-44 default 8 MB + tensor_queue.h:75-124).
    0 disables fusion (one collective per parameter leaf)."""
    return int(_env("BLUEFOG_FUSION_THRESHOLD", str(8 * 1024 * 1024)))


def skip_negotiate_default() -> bool:
    """BLUEFOG_SKIP_NEGOTIATE_STAGE — negotiation does not exist on TPU;
    the flag is kept so scripts that set it keep working
    (reference operations.cc:1149-1183)."""
    return _env("BLUEFOG_SKIP_NEGOTIATE_STAGE", "0") in ("1", "true", "True")


def stall_warning_time() -> float:
    """BLUEFOG_STALL_WARNING_TIME (seconds, default 60; <=0 disables) — how
    long a blocking wait may run before the stall watchdog logs a warning
    (reference STALL_WARNING_TIME operations.cc:47, watchdog :388-433)."""
    try:
        return float(_env("BLUEFOG_STALL_WARNING_TIME", "60"))
    except ValueError:
        return 60.0


def op_timeout() -> float:
    """BLUEFOG_OP_TIMEOUT (seconds, default 0; <=0 disables) — hard ceiling
    on any blocking wait (synchronize/barrier/win_wait/win_fence).  Where
    the stall watchdog only *warns* (BLUEFOG_STALL_WARNING_TIME), this
    RAISES ``BluefogError`` naming the stalled op and the stale processes
    from the heartbeat beacons, so a wedged collective fails fast instead
    of hanging the job forever."""
    try:
        return float(_env("BLUEFOG_OP_TIMEOUT", "0"))
    except ValueError:
        return 0.0


def ops_on_cpu() -> bool:
    """BLUEFOG_OPS_ON_CPU — run collectives on the host CPU backend instead
    of the accelerator (reference torch/mpi_ops.cc:48-50)."""
    return _env("BLUEFOG_OPS_ON_CPU", "0") in ("1", "true", "True")


# ------------------------------------------------------------------ #
# launcher / process-identity contract (the BLUEFOG_TPU_* vars bfrun
# exports into every child — bluefog_tpu/run/run.py _child_env)
# ------------------------------------------------------------------ #
def coordinator() -> str:
    """BLUEFOG_TPU_COORDINATOR: ``host:port`` of the jax.distributed
    coordinator; empty when not launched by bfrun (single process)."""
    return _env("BLUEFOG_TPU_COORDINATOR", "")


def num_processes() -> int:
    """BLUEFOG_TPU_NUM_PROCESSES (default 1): job size bfrun exported."""
    try:
        return int(_env("BLUEFOG_TPU_NUM_PROCESSES", "1"))
    except ValueError:
        return 1


def process_id():
    """BLUEFOG_TPU_PROCESS_ID as an int, or ``None`` when unset (or
    unparsable) — callers that REQUIRE an id under a coordinator
    (api._maybe_init_distributed) treat None as the error it is; the
    log formatter falls back to rank 0."""
    raw = _env("BLUEFOG_TPU_PROCESS_ID", "")
    try:
        return int(raw)
    except ValueError:
        return None


def engine_token() -> str:
    """BLUEFOG_TPU_ENGINE_TOKEN: shared secret the interactive-run
    engine processes require on every control connection
    (bluefog_tpu/run/engines.py); empty disables nothing — an empty
    token still HMACs, it is just guessable."""
    return _env("BLUEFOG_TPU_ENGINE_TOKEN", "")


def state_dir() -> str:
    """BLUEFOG_TPU_STATE_DIR (default ``~/.bluefog_tpu``), expanded:
    where ``ibfrun`` keeps its per-profile engine state files."""
    return os.path.expanduser(_env("BLUEFOG_TPU_STATE_DIR",
                                   "~/.bluefog_tpu"))


def chip_peak_tflops_override():
    """BLUEFOG_CHIP_PEAK_TFLOPS: per-chip peak bf16 TFLOP/s override for
    :func:`bluefog_tpu.benchutil.chip_peak_flops` (auditing a TPU target
    from a CPU host).  ``None``/0 when unset or empty."""
    raw = _env("BLUEFOG_CHIP_PEAK_TFLOPS", "")
    return float(raw) if raw else None


def chip_hbm_gbps_override():
    """BLUEFOG_CHIP_HBM_GBPS: per-chip HBM GB/s override for
    :func:`bluefog_tpu.benchutil.chip_hbm_bandwidth`; same convention as
    :func:`chip_peak_tflops_override`."""
    raw = _env("BLUEFOG_CHIP_HBM_GBPS", "")
    return float(raw) if raw else None


def environ_passthrough(base=None) -> dict:
    """Snapshot of the process environment (or ``base`` when given) for
    the launchers' pass-through forwarding — bfrun/ibfrun filter this
    by ``PASS_PREFIXES`` when building child/remote environments.  The
    one sanctioned whole-environment read outside this module's named
    accessors, kept here so the env-access surface stays auditable."""
    return dict(os.environ if base is None else base)


def configure_host_platform(devices: int = 8) -> None:
    """Force the JAX CPU backend with ``devices`` virtual devices —
    the same environment tests/conftest.py pins — by setting
    ``JAX_PLATFORMS=cpu`` and merging
    ``--xla_force_host_platform_device_count`` into ``XLA_FLAGS``.
    Must run BEFORE the first jax import; used by ``bfcheck`` so the
    static sweep can build 8-rank programs anywhere.  Values already
    present in the environment win."""
    env = os.environ
    env.setdefault("JAX_PLATFORMS", "cpu")
    flags = env.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={devices}"
        ).strip()


def configure_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already
    uses it and nothing is changed; otherwise the cache lives at
    ``<checkout>/.jax_cache``, derived from this package's own path — a
    FIXED location, because the directory is part of what a later
    process must agree on to hit.  Call before the first compile."""
    path = _env("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax

        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
