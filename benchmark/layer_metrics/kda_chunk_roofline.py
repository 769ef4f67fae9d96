"""The chunkwise prefill's share of its roofline: what the window's prefilled
rows need through the linear layers priced as the recurrence
(``_linear.chunk_cost``; from the loop records' ``prefill_tokens`` and
``prefills``) against the time the ``kda_chunk`` kernels took."""
from benchmark.layer_metrics import _linear


def read(obs):
    return _linear.roofline(obs, "chunk")
