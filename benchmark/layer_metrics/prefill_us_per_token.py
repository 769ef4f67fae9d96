"""Device microseconds a prompt token costs: the prefill programs' seconds
per second of traced stretch over the prompt tokens the engine prefilled
per second of window (``stats()["latent"]["prefill_tokens"]`` at the
window's two ends: replays after a preemption count, as they cost). None
where the program does not count prefilled tokens."""
from benchmark.layer_metrics import prefill_busy_share


def read(obs):
    s, lat = prefill_busy_share.seconds(obs), obs.get("latent")
    if s is None or not lat or not lat.get("before") or not lat.get("after"):
        return None
    tokens = lat["after"]["prefill_tokens"] - lat["before"]["prefill_tokens"]
    if tokens <= 0:
        return None
    return 1e6 * (s / obs["trace"]["window_s"]) / (tokens / obs["window_s"])
