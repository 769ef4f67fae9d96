"""How much of a closed loop's plan the callers sent, in percent: requests
sent over requests planned (``traffic.planned``). A guard on the
measurement; moves nothing. At 100 the callers fell silent before the window
closed and ``serve.judge`` calls the run not correct: README.md says how a
mix's ``request_rate_cap`` is sized so that no engine gets there."""
from benchmark import traffic


def read(obs):
    if obs["kind"] != "serve":
        return None
    mix, sent = obs["mix"], len(obs["late_s"])
    if mix["loop"] != "closed" or not sent:
        return None
    return 100.0 * sent / traffic.planned(mix, obs["window_s"])
