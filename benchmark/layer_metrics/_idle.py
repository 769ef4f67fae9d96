"""The one idle-share reader: 1 - union of device-op intervals over the
traced window. ``device_idle_share``, ``decode_idle_share`` and
``prefill_idle_share`` are three names of ``mean_idle``, one per end-to-end
metric it moves, because a metric names one ``moves``."""


def mean_idle(obs):
    tr = obs.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def worst_idle(obs):
    tr = obs.get("trace")
    if not tr:
        return None
    return 100.0 * max(d["idle_share"] for d in tr["per_device"].values())
