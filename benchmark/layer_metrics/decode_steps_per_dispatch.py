"""Decode steps a dispatch of the decode program ran on the device: how
often the chunk engages (``stats()["decode"]["steps_per_dispatch"]`` over
the window)."""
from benchmark.layer_metrics import _loop


def read(obs):
    return _loop.mean(obs, "chunk_steps", over=_loop.has_chunk)
