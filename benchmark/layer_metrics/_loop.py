"""Shared by the engine-loop readers: the loop's own record of the window.

``ServingEngine`` leaves one record a non-empty step (``serving/obs.py``
``LoopRecord``; docs/observability.md has its table: each section's seconds
from the span that times it, what the decode chunk walked, and the two host
gaps — from a blocking fetch's return to the next dispatch call's return,
on the host's clock alone) in a ring that outlives the engine, and
``loop_records(since, until)`` windows the rings of this process. The
window is the driver's: ``obs["before"]["t"]`` to ``obs["after"]["t"]``,
the instants it read the counters at. Whatever the record carries, a new
reader beside these can window: ``drivers/serve.py``'s ``snapshot()`` stays
as it is.

Nothing here reads the trace: a gap's two ends are one clock's, so no
reader of these swings with the offset between a trace's host and device
clocks, as ``_gaps.py``'s do.
"""


def records(obs):
    """The window's records, oldest first; None for a driver that serves
    nothing, a program without the accessor (the parent of the PR that
    added it) and a window that holds none."""
    if obs.get("kind") != "serve":
        return None
    try:
        from mxnet_tpu.serving.obs import loop_records
    except ImportError:
        return None
    return loop_records(obs["before"]["t"], obs["after"]["t"]) or None


def seconds(obs):
    """The window the records were taken over."""
    return obs["after"]["t"] - obs["before"]["t"]


def total(recs, field):
    """Sum of a field over the records; a gap a step does not have is
    None there and adds nothing."""
    return sum(getattr(r, field) or 0 for r in recs)


def mean(obs, field, scale=1.0, over=None):
    """``scale`` x the field's sum over the records that ``over`` picks
    (default: those where the field is not None), per such record. None
    where no record is picked."""
    recs = records(obs)
    if recs is None:
        return None
    n = sum(1 for r in recs if (over(r) if over else
                                getattr(r, field) is not None))
    return scale * total(recs, field) / n if n else None


def has_chunk(r):
    return r.chunk_steps > 0


def has_group(r):
    return r.prefills > 0
