"""Device microseconds the paged decode kernel takes for one live block of
one layer's walk: the kernel's seconds per second of traced stretch
(``_kernels.kernel_seconds``) over the block walks per second of window.

A walk is the unit ``_note_paged`` counts, ``ceil(context / block size)`` a
live lane and decode step, and the loop's records carry the window's
(``live_blocks``). The kernel runs once a layer that attends, so a model
of one block walks ``num_layers`` times that; a model with ``layer_kinds``
books a window layer's walk (from the block of ``context - window``) and a
full-pool reader's apart (``window_live_blocks``, ``full_live_blocks``),
and walks each as often as it has "swa" layers and "full" or "cross" ones.
Not a share of anything: it can read no impossible value. None without a
trace, without the kernel in it, or without the records."""
from benchmark.layer_metrics import _kernels, _loop


def walks(recs, model):
    """Block walks of the paged kernel the window's decode steps made."""
    kinds = model.get("layer_kinds")
    if not kinds:
        return model["num_layers"] * _loop.total(recs, "live_blocks")
    return (kinds.count("swa") * _loop.total(recs, "window_live_blocks")
            + sum(k in ("full", "cross") for k in kinds)
            * _loop.total(recs, "full_live_blocks"))


def read(obs):
    s, recs = _kernels.kernel_seconds(obs, "paged"), _loop.records(obs)
    if s is None or recs is None:
        return None
    n = walks(recs, obs["config"]["model"])
    if n <= 0:
        return None
    return 1e6 * (s / obs["trace"]["window_s"]) / (n / _loop.seconds(obs))
