"""Share of device busy time inside the decode programs' ``kda_step``
kernels (``ops/kda.py``: the streams' matrix states rewritten in their
slots), by the name their custom call carries."""
from benchmark.layer_metrics import _linear


def read(obs):
    return _linear.time_share(obs, "step")
