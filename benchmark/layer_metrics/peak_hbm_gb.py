"""Peak bytes on the fullest chip after the window, in GB: the run's
``memory_peak_bytes`` (``harness.memory_peak_bytes``: the runtime's
``peak_bytes_in_use`` plus ``peak_bytes_reserved``)."""


def read(obs):
    peak = obs.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
