"""Share of device busy time inside the paged kernel's walks of the full
pool (``paged_full_walk``: a "gqa" model's "full" layers, decode)."""
from benchmark.layer_metrics import _hybrid


def read(obs):
    return _hybrid.time_share(obs, "full")
