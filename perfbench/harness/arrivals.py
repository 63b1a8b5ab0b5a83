"""Seeded arrival times and length draws: the one general generator
that every serving traffic file is read by.  The arrival functions are
copies of ``bluefog_tpu.benchutil``'s (PERF.md lists the originals for a
later PR to delete), on ``numpy.random.default_rng`` so that a seed
above 2**32 is taken."""

from __future__ import annotations

import numpy as np


def _unit_targets(n: int, rng) -> np.ndarray:
    gaps = rng.exponential(1.0, size=n)
    gaps[0] = 0.0
    return np.cumsum(gaps)


def poisson(rate: float, n: int, rng) -> np.ndarray:
    """``n`` ascending arrival times at ``rate`` a second, first at 0."""
    if rate <= 0:
        raise ValueError(f"rate ({rate}) must be positive")
    return _unit_targets(n, rng) / rate


def flash_crowd(rate: float, n: int, rng, *, at: float, factor: float,
                duration: float) -> np.ndarray:
    """Poisson at ``rate`` with the rate times ``factor`` inside
    ``[at, at + duration)``: closed-form inversion of the piecewise
    linear cumulative rate."""
    if rate <= 0 or factor <= 0 or duration < 0 or at < 0:
        raise ValueError("rate, factor > 0 and at, duration >= 0")
    targets = _unit_targets(n, rng)
    c1 = rate * at
    c2 = c1 + rate * factor * duration
    return np.where(
        targets < c1, targets / rate,
        np.where(targets < c2, at + (targets - c1) / (rate * factor),
                 at + duration + (targets - c2) / rate))


def diurnal(rate: float, n: int, rng, *, period: float, depth: float,
            phase: float = 0.0) -> np.ndarray:
    """Sinusoidally modulated Poisson, rate ``rate * (1 + depth *
    sin(2 pi t / period + phase))``, by bisection on the cumulative
    rate (monotone since ``depth < 1``)."""
    if not 0 <= depth < 1:
        raise ValueError("depth in [0, 1)")
    targets = _unit_targets(n, rng)
    w = 2 * np.pi / period

    def cum(t):
        return rate * (t - depth / w * (np.cos(w * t + phase)
                                        - np.cos(phase)))

    lo = np.zeros_like(targets)
    hi = targets / (rate * (1 - depth)) + period
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        below = cum(mid) < targets
        lo = np.where(below, mid, lo)
        hi = np.where(below, hi, mid)
    return 0.5 * (lo + hi)


ARRIVALS = {"poisson": poisson, "flash_crowd": flash_crowd,
            "diurnal": diurnal}


def arrival_times(spec: dict, n: int, rng) -> np.ndarray:
    """``spec``: ``{"process": name, "rate_per_s": r, ...parameters}``."""
    spec = dict(spec)
    fn = ARRIVALS[spec.pop("process")]
    return fn(spec.pop("rate_per_s"), n, rng, **spec)


def lengths(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` whole lengths from ``{"dist": "lognormal", "median": m,
    "sigma": s, "min": a, "max": b}`` (clipped) or ``{"dist": "fixed",
    "value": v}``."""
    if spec["dist"] == "fixed":
        return np.full((n,), int(spec["value"]), np.int64)
    if spec["dist"] == "lognormal":
        raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
        return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(
            np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def fixed_schedule(base_seed: int, n: int, arrivals: dict, fields: dict):
    """Arrival times (first at 0) and each field's lengths of ``n``
    requests, drawn once from ``base_seed`` (the traffic file's): every
    run of a cell offers the same requests at the same times, and the
    run's own seed gives only what is inside them.  (In another order a
    seed changes what queues behind what, which is the work: PERF.md.)"""
    base = np.random.default_rng(base_seed)
    times = arrival_times(arrivals, n, base)
    return times, {k: lengths(v, n, base) for k, v in fields.items()}
