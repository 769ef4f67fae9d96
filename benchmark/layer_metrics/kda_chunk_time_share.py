"""Share of device busy time inside the prefill programs' ``kda_chunk``
kernels (``ops/kda.py``: a prompt's chunkwise pass through a linear layer),
by the name their custom call carries."""
from benchmark.layer_metrics import _linear


def read(obs):
    return _linear.time_share(obs, "chunk")
