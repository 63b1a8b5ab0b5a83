"""Unified observability: metrics registry, span tracer, step profiler.

The one telemetry layer every subsystem reports into (the reference
treats its C++ ``Timeline`` as first-class infrastructure; this
subsystem extends that stance to metrics and per-op cost attribution):

* :mod:`~bluefog_tpu.observe.registry` — process-local counters,
  gauges, and windowed histograms with labeled families; cheap enough
  for per-step use, host-side only (enabling it never touches a
  compiled program — asserted via jit cache sizes and bit-identical
  step outputs in tests/test_observe.py);
* :mod:`~bluefog_tpu.observe.tracer` — nested spans / instant events /
  per-thread tracks; the serving engine, resilience runner, eager op
  API, and ``build_train_step`` wrappers publish here, and
  ``timeline.py`` is a thin Chrome-trace exporter over it;
* :mod:`~bluefog_tpu.observe.compiles` — one ``jax.monitoring``
  listener, registered here: ``bf_compile_seconds_total{stage=}``,
  ``bf_compiles_total``, ``bf_compile_cache_misses_total`` and a
  ``compile.<fun_name>`` instant a backend compile;
* :mod:`~bluefog_tpu.observe.stepprof` — ``profile_step`` returns a
  :class:`StepProfile` (FLOPs, per-collective bytes, overlap windows,
  MFU) from XLA's own view of the compiled module;
* :mod:`~bluefog_tpu.observe.export` — Prometheus text / JSONL event
  log / Chrome trace, plus the one-call ``bf.observe.snapshot()``;
* :mod:`~bluefog_tpu.observe.fleet` — decentralized CROSS-RANK
  aggregation: push-sum gossip of registry metrics over the training
  topology (``FleetAggregator``), per-edge traffic accounting
  (``bf_edge_bytes_total{src,dst}``), and the gossip-fed
  ``StragglerDetector``.

Opt out with ``BLUEFOG_OBSERVE=0`` (publication stops; explicitly-held
registries/tracers keep working).  See docs/observability.md.
"""

from bluefog_tpu.observe.registry import (Counter, Gauge, Histogram,
                                          MetricsRegistry, enabled,
                                          get_registry, percentile)
from bluefog_tpu.observe.tracer import Tracer, get_tracer, publish_tracer
from bluefog_tpu.observe import compiles as _compiles
from bluefog_tpu.observe.stepprof import (StepProfile, hlo_op_breakdown,
                                          profile_step,
                                          verify_collective_contract)
from bluefog_tpu.observe.export import (chrome_trace, jsonl_events,
                                        prometheus_text, snapshot)
from bluefog_tpu.observe.fleet import (FleetAggregate, FleetAggregator,
                                       StragglerDetector, collect_local,
                                       edge_list, push_sum_matrix,
                                       record_edge_traffic)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "enabled",
    "get_registry", "percentile",
    "Tracer", "get_tracer", "publish_tracer",
    "StepProfile", "profile_step", "hlo_op_breakdown",
    "verify_collective_contract",
    "prometheus_text", "jsonl_events", "chrome_trace", "snapshot",
    "FleetAggregate", "FleetAggregator", "StragglerDetector",
    "collect_local", "edge_list", "push_sum_matrix",
    "record_edge_traffic",
    "BlackBox", "DecisionEvent", "explain", "get_blackbox",
    "record_decision",
]

_compiles.install()

# The decision flight recorder resolves lazily: its module reaches
# into bluefog_tpu.sim for the canonical byte-stable formatting, and
# the sim package in turn imports the control planes that record into
# it — binding it here eagerly would cycle the package imports.
_BLACKBOX_EXPORTS = ("BlackBox", "DecisionEvent", "explain",
                     "get_blackbox", "record_decision")


def __getattr__(name):
    if name in _BLACKBOX_EXPORTS:
        from bluefog_tpu.observe import blackbox as _blackbox
        return getattr(_blackbox, name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
