"""How late the load generator sent: send time minus due time, 95th
percentile. A guard on the measurement; moves nothing."""
from benchmark import stats


def read(obs):
    if obs["kind"] != "serve" or not obs["late_s"]:
        return None
    return 1e3 * stats.percentile(obs["late_s"], 95)
