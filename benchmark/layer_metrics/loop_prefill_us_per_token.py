"""Device microseconds a prompt token costs: the prefill programs' seconds
per second of traced stretch over the tokens the engine prefilled per second
of window, from the loop's records (``LoopRecord.prefill_tokens``, which
every model's engine books: replays after a preemption count, as they cost).
``prefill_us_per_token`` is the same quantity from the latent counters, for
the one model that has them. None without a trace, for a program whose
records lack the field (the parent of the PR that added it) and where no
prompt was prefilled in the window."""
from benchmark.layer_metrics import _loop, prefill_busy_share


def read(obs):
    s, recs = prefill_busy_share.seconds(obs), _loop.records(obs)
    if s is None or not recs or not hasattr(recs[0], "prefill_tokens"):
        return None
    tokens = _loop.total(recs, "prefill_tokens")
    if tokens <= 0:
        return None
    return 1e6 * (s / obs["trace"]["window_s"]) / (tokens / _loop.seconds(obs))
