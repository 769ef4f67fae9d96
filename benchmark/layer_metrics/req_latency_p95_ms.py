"""95th percentile of the client's whole-request latency (due to reply
complete). An end-to-end quantity kept among the per-layer metrics because
thirty seconds of Poisson arrivals move it by 7-8% from seed to seed (eight
chip runs, PR 22), more than a bound of 10% admits (PERF.md section 6)."""
from benchmark import stats


def read(obs):
    if obs["kind"] != "serve" or not obs["replies"]:
        return None
    return 1e3 * stats.percentile(
        [r["done"] - r["due"] for r in obs["replies"]], 95)
