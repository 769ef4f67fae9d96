"""Arithmetic every metric of the benchmark goes through. Pure Python: the
load generator's child process imports this file and must never import jax
or numpy's heavier relatives."""
import math


def percentile(values, p):
    """The p-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default rule), of a non-empty sequence."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = (len(xs) - 1) * p / 100.0
    lo, hi = int(math.floor(k)), int(math.ceil(k))
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values):
    return percentile(values, 50.0)


def samples_beyond(n, p):
    """How many of n samples lie strictly beyond the p-th percentile."""
    return int(math.floor(n * (100.0 - p) / 100.0 + 1e-9))


def highest_percentile(n, candidates=(99.0, 95.0, 90.0, 75.0), beyond=10):
    """The highest of ``candidates`` that leaves at least ``beyond`` of n
    samples beyond it (the choosing-metrics rule), or None."""
    for p in candidates:
        if samples_beyond(n, p) >= beyond:
            return p
    return None


def window_rates(fence_times, units_per_window):
    """Rates of consecutive sub-windows: ``fence_times`` are the host-clock
    instants at which consecutive fences returned; each pair closes one
    sub-window that did ``units_per_window`` units of work."""
    return [units_per_window / (b - a)
            for a, b in zip(fence_times, fence_times[1:]) if b > a]


def median_of_windows(fence_times, units_per_window):
    """The benchmark's training rate: the MEDIAN sub-window, never the
    fastest (bench.py's "fastest epoch window wins" is the statistic this
    replaces)."""
    rates = window_rates(fence_times, units_per_window)
    if not rates:
        raise ValueError("no closed sub-window")
    return median(rates)


def lateness(due, sent):
    """How late the generator ran, per request, in seconds (never negative:
    a request is not sent before it is due)."""
    return [max(0.0, s - d) for d, s in zip(due, sent)]


def hist_delta_percentile(before, after, p):
    """Percentile p of the observations a cumulative-bucket histogram
    snapshot gained between two readings (``{"buckets": {le: cum}}``, the
    telemetry registry's snapshot form), by linear interpolation inside
    the covering bucket. None when nothing was observed in between."""
    def cum(snap):
        out = []
        for le, c in (snap.get("buckets") or {}).items():
            out.append((math.inf if le == "+Inf" else float(le), c))
        return sorted(out)

    a, b = dict(cum(before)), cum(after)
    gained = [(le, c - a.get(le, 0)) for le, c in b]
    total = gained[-1][1] if gained else 0
    if total <= 0:
        return None
    target = total * p / 100.0
    lo, prev = 0.0, 0
    for le, c in gained:
        if c >= target:
            if math.isinf(le):
                return after.get("max", lo)
            frac = (target - prev) / (c - prev) if c > prev else 0.0
            return lo + frac * (le - lo)
        lo, prev = le, c
    return after.get("max", lo)
