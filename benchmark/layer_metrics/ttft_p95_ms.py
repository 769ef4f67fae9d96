"""95th percentile of the replies' own ``ttft_s``: the program's clock, from
arrival at the engine to the first token, hence a layer's metric."""
from benchmark import stats


def read(obs):
    if obs["kind"] != "serve" or not obs["replies"]:
        return None
    return 1e3 * stats.percentile([r["ttft_s"] for r in obs["replies"]], 95)
