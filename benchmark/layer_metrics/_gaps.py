"""Shared by the idle-attribution readers: whose fault the device's idle
time is, by the names the program gives its own host code.

``obs["trace"]["idle_gaps"]`` is ``trace_reduce.idle_gaps``: every gap
between device ops, labelled with the innermost host event at its middle,
summed by label. ``telemetry.span`` opens a profiler annotation, so the
serving engine's sections (``serving.schedule``, ``serving.decode.build``,
... ; docs/observability.md has the table) are such events. Four classes:

* ``host``     labels starting ``serving.`` other than ``serving.loop.idle``:
               the driver thread was inside the engine's own code;
* ``no_work``  ``serving.loop.idle``: the queue was empty, idle that no
               change to the loop can take away;
* ``unnamed``  ``trace_reduce.UNTRACED``: no host event at all, so the
               spans no longer cover the loop;
* ``runtime``  the rest: transfers, ``np.asarray``, PJRT.

``idle_gaps`` keeps its ten largest labels, so a class can miss a sliver
that fell below the cut: the four need not add up to the idle share.
"""
from benchmark.trace_reduce import UNTRACED

SPAN_PREFIX = "serving."
NO_WORK = "serving.loop.idle"


def classify(label):
    if label == UNTRACED:
        return "unnamed"
    if label == NO_WORK:
        return "no_work"
    return "host" if label.startswith(SPAN_PREFIX) else "runtime"


def share(obs, kind):
    """Percent of the traced window the device was idle under ``kind``.
    None without a trace, and for the two classes that only the program's
    spans can fill when the trace holds none of them (a program from
    before the spans: nothing to read, not a zero)."""
    tr = obs.get("trace")
    if not tr:
        return None
    gaps = tr["idle_gaps"]
    if kind in ("host", "no_work") and not any(
            label.startswith(SPAN_PREFIX) for label, _s in gaps):
        return None
    seconds = sum(s for label, s in gaps if classify(label) == kind)
    return 100.0 * seconds / tr["window_s"]
