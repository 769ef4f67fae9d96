"""The one general traffic generator. A mix is a data file under
``benchmark/traffic/``; this module turns (mix, seed, seconds) into the list
of requests a run sends. Pure Python on purpose: the load generator's child
process imports it and must start in milliseconds and never import jax.

A serve mix has:

  driver        "serve"
  loop          "open" (requests are due on a schedule) or "closed"
                (``clients`` callers, each sending its next request when
                its reply arrives)
  rate_per_s    open loop: the fixed offered rate of Poisson arrivals, a
                number (never searched for inside a run)
  clients       closed loop: the number of callers
  request_rate_cap  closed loop: requests generated per second of window
                (an upper bound on what the callers can consume)
  prompt_len / output_len   a length distribution:
                {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
                {"dist": "uniform", "min": a, "max": b}
  max_total     prompt + output never exceeds it (the output is shortened)

Work is FIXED per seed, not only in expectation: the number of arrivals is
``round(rate x seconds)`` (a Poisson process conditioned on its count is
that many independent uniform instants), and lengths are the distribution's
own quantiles at (i + 1/2)/n, shuffled by the seed, so every run of a cell
carries the same multiset of lengths in another order with other tokens.
Another arrival process, length distribution or sharing of prefixes comes
with the benchmark PR that adds the cell needing it.
"""
import math
import random
from statistics import NormalDist

STRATUM = 64    # lengths are stratified in blocks of this many requests


def _quantile(spec, u):
    kind = spec["dist"]
    if kind == "uniform":
        lo, hi = int(spec["min"]), int(spec["max"])
        return min(hi, lo + int(u * (hi - lo + 1)))
    if kind == "lognormal":
        x = spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(u))
        return int(min(spec["max"], max(spec["min"], round(x))))
    raise ValueError("unknown length distribution %r" % (kind,))


def lengths(spec, n, rng):
    """n lengths: per block of STRATUM (the last one may be shorter), the
    distribution's quantiles at the block's mid-points, shuffled. The
    multiset depends on n alone, never on the seed."""
    out = []
    while len(out) < n:
        size = min(STRATUM, n - len(out))
        block = [_quantile(spec, (i + 0.5) / size) for i in range(size)]
        rng.shuffle(block)
        out.extend(block)
    return out


def arrival_times(mix, seconds, rng):
    """Due times in [0, seconds) of an open-loop mix, ascending: a Poisson
    process conditioned on its count."""
    n = int(round(mix["rate_per_s"] * seconds))
    return sorted(rng.random() * seconds for _ in range(n))


def planned(mix, seconds):
    """How many requests a closed loop's plan holds: what the callers can
    consume at ``request_rate_cap`` a second, and one more a caller."""
    return int(math.ceil(mix["request_rate_cap"] * seconds)) + mix["clients"]


def plan(mix, seed, seconds, vocab):
    """The run's requests, in sending order:
    ``[{"i", "due" (open loop; seconds after the window opens), "tokens",
    "max_new_tokens"}]``. The same (mix, seed, seconds, vocab) gives the
    same list in any process."""
    rng = random.Random(int(seed) * 1000003 + 17)
    if mix["loop"] == "open":
        due = arrival_times(mix, seconds, rng)
        n = len(due)
    elif mix["loop"] == "closed":
        n = planned(mix, seconds)
        due = [None] * n
    else:
        raise ValueError("loop must be open or closed, not %r" % mix["loop"])
    p_len = lengths(mix["prompt_len"], n, rng)
    o_len = lengths(mix["output_len"], n, rng)
    max_total = int(mix["max_total"])
    out = []
    for i in range(n):
        p = min(p_len[i], max_total - 1)
        o = max(1, min(o_len[i], max_total - p))
        out.append({"i": i, "due": due[i],
                    "tokens": rng.choices(range(vocab), k=p),
                    "max_new_tokens": o})
    return out
