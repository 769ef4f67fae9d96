"""The steps' ``serving.retire`` sections, milliseconds a decode chunk."""
from benchmark.layer_metrics import _loop


def read(obs):
    return _loop.mean(obs, "retire_s", 1e3, _loop.has_chunk)
