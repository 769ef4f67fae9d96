"""The part of ``serving.retire`` that books the chunk's counters
(``_note_paged`` / ``_note_hybrid`` / ``_note_latent`` / ``_note_moe``, the
``serving.decode_batch`` observe, the throughput gauge), milliseconds a
decode chunk: what the instrumentation costs inside the gap."""
from benchmark.layer_metrics import _loop


def read(obs):
    return _loop.mean(obs, "retire_counters_s", 1e3, _loop.has_chunk)
