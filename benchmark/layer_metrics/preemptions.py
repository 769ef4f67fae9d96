"""``scheduler.preempt_count`` across the window."""


def read(obs):
    if obs["kind"] != "serve":
        return None
    return obs["after"]["preemptions"] - obs["before"]["preemptions"]
