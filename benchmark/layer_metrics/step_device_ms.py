"""Device busy time per step: the union of device-op intervals in the traced
stretch (mean over the chips), over the steps that ran in it."""


def read(obs):
    tr = obs.get("trace")
    if obs["kind"] != "fit" or not tr or not obs["fit"]["traced_steps"]:
        return None
    return 1e3 * tr["busy_s"] / obs["fit"]["traced_steps"]
