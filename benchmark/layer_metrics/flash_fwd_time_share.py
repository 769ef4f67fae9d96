"""Share of device busy time inside the flash-attention forward kernel
(``ops/attention.py`` ``_pallas_forward``), by the name today's trace gives
its custom call."""
from benchmark.layer_metrics import _kernels


def read(obs):
    return _kernels.time_share(obs, "flash")
