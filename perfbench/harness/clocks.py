"""Host clock, percentiles, spread, and the benchmark's own spans."""

from __future__ import annotations

import contextlib
import math
import statistics
import time


def now() -> float:
    return time.perf_counter()


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks, as numpy's default does."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)`` — the driver's
    measure of run-to-run spread."""
    q1, _, q3 = statistics.quantiles([float(v) for v in values], n=4)
    return (q3 - q1) / statistics.median(values)


def grouped_step_seconds(stamps, least_span: float = 0.25) -> float:
    """Median seconds per step from completion stamps, each sample a
    span of ``k`` consecutive steps long enough (``least_span``) for the
    host clock's half-millisecond to be small in it."""
    if len(stamps) < 2:
        raise ValueError("need two completion stamps or more")
    mean = (stamps[-1] - stamps[0]) / (len(stamps) - 1)
    k = max(1, min(len(stamps) - 1, math.ceil(least_span / mean)))
    samples = [(stamps[i + k] - stamps[i]) / k
               for i in range(0, len(stamps) - k, k)]
    return median(samples)


class Spans:
    """The benchmark's own spans: name, start, end on the host clock,
    kept in memory, and written into the profiler's trace as a
    ``TraceAnnotation`` of the same name so that an idle gap on the
    device can be given to what the host was doing."""

    def __init__(self):
        self.records = []        # (name, start_s, end_s)

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = now()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.records.append((name, t0, now()))
