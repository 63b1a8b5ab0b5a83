"""Finding the chips a cell asks for, and reading their memory."""

from __future__ import annotations

import sys

# What only a chip supplies.  tests/perfbench replace these names to run
# the harness on CPU devices; the command itself has no other platform.
PLATFORM = "tpu"


def require_chips(count: int):
    """The attached devices, or exit non-zero with no result printed:
    platform ``PLATFORM``, exactly ``count`` devices, a kind that the
    peaks table knows."""
    import jax

    from perfbench.harness.peaks import UnknownDevice, peaks_for

    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"perfbench: JAX found no device: {e}")
    if devices[0].platform != PLATFORM:
        sys.exit(f"perfbench: needs a {PLATFORM} device, JAX found "
                 f"platform {devices[0].platform!r}")
    if len(devices) != count:
        sys.exit(f"perfbench: the cell needs {count} chip(s), JAX found "
                 f"{len(devices)}")
    try:
        peaks_for(devices[0].device_kind)
    except UnknownDevice as e:
        sys.exit(f"perfbench: {e}")
    return devices


def configure_compile_cache() -> str:
    """The program's own persistent compile cache (where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``),
    holding every program, the small ones too, so that only a
    checkout's first run compiles.  Returns the directory."""
    import jax

    from bluefog_tpu.config import configure_compilation_cache

    cache_dir = configure_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def memory_stat(device, key: str) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get(key, 0))


def device_record(devices, program_bytes: int = 0) -> dict:
    """``device`` of the result line.  ``memory_peak_bytes`` is the
    fullest chip's ``peak_bytes_in_use``, or the compiler's count for
    the largest resident program (arguments + outputs - aliases +
    temporaries) where that is larger: on this runtime the allocator's
    statistic leaves out a program's temporary space (PERF.md)."""
    d = devices[0]
    stat = max(memory_stat(dev, "peak_bytes_in_use") for dev in devices)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(max(stat, program_bytes))}


def program_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return int(mem.argument_size_in_bytes + mem.output_size_in_bytes
               - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
