"""Share of the window the fit loop spent waiting on the iterator: the
program's ``fit.data_wait_seconds`` (host clock), summed over the window."""


def read(obs):
    if obs["kind"] != "fit":
        return None
    return 100.0 * obs["fit"]["data_wait_s"] / obs["window_s"]
