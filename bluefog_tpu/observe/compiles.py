"""Compile accounting: what JAX itself reports of every compilation.

One listener on ``jax.monitoring`` (registered once, when
:mod:`bluefog_tpu.observe` is imported) turns JAX's own events into

* ``bf_compile_seconds_total{stage=trace|lower|backend}`` — seconds of
  Python tracing to a jaxpr, of lowering the jaxpr to an MLIR module,
  and inside the backend (the compiler itself, or the read of the
  persistent cache that stands in for it);
* ``bf_compiles_total`` — backend compiles (cache reads included: each
  is one executable made ready);
* ``bf_compile_cache_misses_total`` — executables the persistent
  compilation cache did not hold and now stores;
* one tracer instant ``compile.<fun_name>`` on the ``compile`` track a
  backend compile: a recompile inside a serving window or a training
  run thereby has a name and a time in the trace.

The counters are the one account of the seconds; they take every
stage's seconds when JAX reports them.  A ``jit`` called while another
is being traced (most of ``jax.numpy``) reports its own tracing inside
the outer function's: JAX announces the start of every trace too, so
the listener keeps the nesting depth of the thread and counts the
outermost trace only.

Publication follows ``BLUEFOG_OBSERVE`` like every built-in producer.
"""

from __future__ import annotations

import threading

from bluefog_tpu.observe.registry import enabled, get_registry
from bluefog_tpu.observe.tracer import get_tracer

__all__ = ["install", "backend_compiles", "COMPILE_TRACK"]

COMPILE_TRACK = "compile"

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_STAGES = {
    _TRACE: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

_tracing = threading.local()   # .depth: traces open on this thread
_backend_compiles = 0          # what bf_compiles_total counts, as an int
_installed = False
_install_lock = threading.Lock()


def _on_scalar(event: str, value, **kwargs) -> None:
    # JAX records a trace's start time as a scalar when it begins
    if event == _TRACE:
        _tracing.depth = getattr(_tracing, "depth", 0) + 1


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    stage = _STAGES.get(event)
    if stage is None:
        return
    if event == _TRACE:
        _tracing.depth = depth = max(getattr(_tracing, "depth", 1) - 1, 0)
        if depth:
            return      # inside another trace, whose seconds hold these
    if stage == "backend":
        global _backend_compiles
        _backend_compiles += 1
    if not enabled():
        return
    reg = get_registry()
    reg.counter("bf_compile_seconds_total",
                "seconds JAX reports per compile stage",
                stage=stage).inc(max(float(duration_secs), 0.0))
    if stage != "backend":
        return
    reg.counter("bf_compiles_total",
                "backend compiles (persistent-cache reads included)").inc()
    get_tracer().instant(f"compile.{kwargs.get('fun_name', '')}",
                         COMPILE_TRACK)


def backend_compiles() -> int:
    """What ``bf_compiles_total`` counts, as a plain integer (and counted
    whether or not anything publishes): what a producer that wants
    "compiles since the step before" reads a step without a registry
    lookup."""
    return _backend_compiles


def _on_event(event: str, **kwargs) -> None:
    if event == _CACHE_MISS and enabled():
        get_registry().counter(
            "bf_compile_cache_misses_total",
            "executables the persistent compilation cache did not "
            "hold").inc()


def install() -> None:
    """Register the listener with ``jax.monitoring``.  Idempotent."""
    global _installed
    with _install_lock:
        if _installed:
            return
        from jax import monitoring

        monitoring.register_scalar_listener(_on_scalar)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _installed = True
