"""Keys a full layer's walk read per live lane and decode step: the
traffic's own mix on the ledger, so that a change of the walks is told from
a change of what was walked."""
from benchmark.layer_metrics import _hybrid


def read(obs):
    d = _hybrid.delta(obs, "full")
    return None if d is None else d["ctx_tokens"] / d["lane_steps"]
