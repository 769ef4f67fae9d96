"""The share of the rows the prefill programs computed that was padding:
``100 x (1 - prefill_tokens / prefill_rows)`` over the window's records
(``LoopRecord.prefill_rows``: each prompt's rung of the engine's prefill
ladder, beside the tokens it held; replays after a preemption count, as
they cost). What the ladder's spacing decides, and by arithmetic what the
cell's prompt lengths give on it: ``loop_prefill_us_per_token`` is what a
row costs the device. None for a driver that serves nothing, for a program
whose records lack the field (the parent of the PR that added it) and where
no prompt was prefilled in the window."""
from benchmark.layer_metrics import _loop


def read(obs):
    recs = _loop.records(obs)
    if not recs or not hasattr(recs[0], "prefill_rows"):
        return None
    rows = _loop.total(recs, "prefill_rows")
    if rows <= 0:
        return None
    return 100.0 * (1.0 - _loop.total(recs, "prefill_tokens") / rows)
