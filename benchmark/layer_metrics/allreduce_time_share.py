"""Collective ops' summed duration over the traced window, mean over the
chips."""


def read(obs):
    tr = obs.get("trace")
    if not tr or obs["chips"] < 2:
        return None
    per = [c["total_s"] for c in tr["collectives"].values()]
    return 100.0 * sum(per) / len(per) / tr["window_s"]
