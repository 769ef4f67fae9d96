"""Mean wait for the engine's step lock, a step: the driver thread behind
the callers' ``submit()``."""
from benchmark.layer_metrics import _loop


def read(obs):
    return _loop.mean(obs, "lock_s", 1e3)
