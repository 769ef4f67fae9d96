"""95th percentile of the phase clock's ``queue_wait`` over the requests
that finished in the window, interpolated inside the registry histogram's
buckets (coarse: the bounds go 0.1, 0.25, 0.5, 1, 2.5 s)."""
from benchmark import stats


def read(obs):
    if obs["kind"] != "serve":
        return None
    v = stats.hist_delta_percentile(obs["before"]["queue_wait"],
                                    obs["after"]["queue_wait"], 95)
    return None if v is None else 1e3 * v
