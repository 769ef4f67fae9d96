"""Share of device busy time inside the latent-attention decode kernel
(``ops/attention.py`` ``latent_paged``) of the decode programs."""
from benchmark.layer_metrics import _latent


def read(obs):
    s = _latent.kernel_seconds(obs)
    return None if s is None else 100.0 * s / obs["trace"]["busy_s"]
