"""Median time per output token after the first, from the replies' own
clocks: (latency_s - ttft_s) / (n - 1)."""
from benchmark import stats


def read(obs):
    if obs["kind"] != "serve":
        return None
    gaps = [(r["latency_s"] - r["ttft_s"]) / (r["n_out"] - 1)
            for r in obs["replies"] if r["n_out"] > 1]
    return 1e3 * stats.median(gaps) if gaps else None
