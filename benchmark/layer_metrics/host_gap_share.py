"""The host's part of the device's idle time, percent of the window: the
two gaps of every step — fetch's return to next dispatch's return, during
which nothing is in flight — summed. On the host's clock; what
``decode_idle_share`` reads beyond it is the runtime's (the fetch's tail,
the launch)."""
from benchmark.layer_metrics import _loop


def read(obs):
    recs = _loop.records(obs)
    if recs is None:
        return None
    gaps = _loop.total(recs, "gap_chunk_s") + _loop.total(recs, "gap_group_s")
    return 100.0 * gaps / _loop.seconds(obs)
