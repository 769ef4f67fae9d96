"""Mean host gap after a step's group of prefills: from the group's last
fetch's return to the same step's decode dispatch's return."""
from benchmark.layer_metrics import _loop


def read(obs):
    return _loop.mean(obs, "gap_group_s", 1e3)
