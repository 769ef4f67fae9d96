"""Prompts a step's group of prefills held
(``stats()["prefill"]["prompts_per_group"]`` over the window)."""
from benchmark.layer_metrics import _loop


def read(obs):
    return _loop.mean(obs, "prefills", over=_loop.has_group)
