"""Share of device busy time inside the flash forward of a "gqa" model's
prefill (``flash_gqa_fwd``: every layer's call, window and full)."""
from benchmark.layer_metrics import _hybrid


def read(obs):
    return _hybrid.time_share(obs, "flash")
