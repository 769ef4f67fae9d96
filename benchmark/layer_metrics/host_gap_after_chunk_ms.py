"""Mean host gap after a decode chunk: from the chunk's fetch's return to
the next step's first dispatch call's return (retire, the step lock,
schedule, build, dispatch), waits on an empty queue left out."""
from benchmark.layer_metrics import _loop


def read(obs):
    return _loop.mean(obs, "gap_chunk_s", 1e3)
