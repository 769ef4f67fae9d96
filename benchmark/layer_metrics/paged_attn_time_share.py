"""Share of device busy time inside the paged-attention decode kernel
(``ops/attention.py`` ``_paged_pallas``), by the name today's trace gives
its custom call."""
from benchmark.layer_metrics import _kernels


def read(obs):
    return _kernels.time_share(obs, "paged")
