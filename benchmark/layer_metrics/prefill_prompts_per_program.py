"""Prompts a prefill PROGRAM held: ``prefills / prefill_programs`` over the
window's records (``LoopRecord.prefill_programs``: the packs a step's group
of prompts was cut into, each ONE dispatch of its rung's program with its
prompts end to end along the rows; ``stats()["prefill"]
["prompts_per_program"]`` over the window). 1.0 where every prompt runs in a
program of its own (a model with state slots; a step that admits one
prompt); what ``prefill_padded_share`` and the microseconds a prompt token
follow, since a program reads its weights once whatever its rows hold. None
for a driver that serves nothing, for a program whose records lack the
field (the parent of the PR that added it) and where no prompt was
prefilled in the window."""
from benchmark.layer_metrics import _loop


def read(obs):
    recs = _loop.records(obs)
    if not recs or not hasattr(recs[0], "prefill_programs"):
        return None
    programs = _loop.total(recs, "prefill_programs")
    if programs <= 0:
        return None
    return _loop.total(recs, "prefills") / programs
