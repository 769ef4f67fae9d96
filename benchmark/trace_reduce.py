"""From a profiler trace to numbers. The idea is tools/kernel_ab.py's and
tools/conv_bench.py's (device time per op from a ``jax.profiler`` trace);
this copy reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and
lives with the benchmark so that every PR computes the same number the same
way.

Two steps, kept apart so that the arithmetic is testable without jax:

* :func:`load_xplane` turns the file into plain data —
  ``{"devices": {plane: [(name, start_ns, dur_ns), ...]},
     "host": {thread: [(name, start_ns, dur_ns), ...]}}``;
* everything else works on that.

Device planes are ``/device:TPU:<n>``. Their ``XLA Ops`` line holds one
event per executed HLO op, named by the whole instruction and nested where
an op (a ``while``) contains others; ``XLA Modules`` holds one event per
program run (``jit__decode(<id>)``); ``Async XLA Ops`` holds the copies that
overlap compute and is not read. Host threads are lines of ``/host:CPU``.
Busy time is the UNION of the op intervals; an op's own time is its duration
minus its children's.
"""
import bisect
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# an idle gap during which no thread of the host was inside a profiler
# event: with the Python tracer off that is the host running Python code
UNTRACED = "host in Python code (no profiler event)"
# what a collective is called in the trace's op names
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def newest_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


def short_name(text):
    """The trace prints an op as its whole HLO instruction; keep the
    instruction's name and the (first) shape it produces:
    ``%copy.11 = f32[24,513,16,16,64]{...} copy(...)`` ->
    ``copy.11 f32[24,513,16,16,64]``."""
    if " = " not in text:
        return text[:120]
    head, rest = text.split(" = ", 1)
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return head.lstrip("%") + (" " + shape.group(1) if shape else "")


def module_name(text):
    """``jit__decode(1234567)`` -> ``jit__decode``."""
    return re.sub(r"\(\d+\)$", "", text)


def attribute_modules(ops, modules):
    """Prefix each op with the program that was running when it started
    (the device plane's ``XLA Modules`` line): op numbers repeat from one
    program to the next, and a Pallas custom call is told from another only
    by the program it sits in."""
    modules = sorted(modules, key=lambda m: m[1])
    out, i = [], 0
    for name, start, dur in sorted(ops, key=lambda e: e[1]):
        while i + 1 < len(modules) and modules[i + 1][1] <= start:
            i += 1
        if modules and modules[i][1] <= start < modules[i][1] + modules[i][2]:
            name = module_name(modules[i][0]) + "/" + name
        out.append((name, start, dur))
    return out


def load_xplane(path, host_lines=8, rehearsal=False):
    """Plain data from an ``.xplane.pb`` (needs jax, nothing else). In a
    ``rehearsal`` on the CPU there is no device plane: the CPU client's
    executor threads stand in for one, so that the reduction runs end to
    end (its numbers are then no device's, and a rehearsal prints none)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(short_name(e.name), float(e.start_ns),
                            float(e.duration_ns)) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(e.name, float(e.start_ns),
                                float(e.duration_ns)) for e in line.events]
            if ops:
                devices[plane.name] = attribute_modules(ops, modules)
        elif plane.name.startswith("/host:CPU"):
            lines = [(line.name, [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events if e.duration_ns > 0])
                for line in plane.lines]
            # the busiest threads say what the host was in
            lines.sort(key=lambda nl: -len(nl[1]))
            host.update(lines[:host_lines])
    if rehearsal and not devices:
        devices["rehearsal:cpu"] = [
            e for name, evs in host.items() if "XLAPjRtCpuClient" in name
            or "XLAEigen" in name for e in evs]
    return {"devices": devices, "host": host}


def union_intervals(events):
    """Merged, ascending [start, end) intervals covered by the events."""
    out = []
    for start, end in sorted((s, s + d) for _n, s, d in events if d > 0):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def busy_ns(events, window=None):
    """Nanoseconds in which at least one event ran, inside ``window``
    (start, end) if given."""
    total = 0.0
    for a, b in union_intervals(events):
        if window is not None:
            a, b = max(a, window[0]), min(b, window[1])
        if b > a:
            total += b - a
    return total


def self_times(events):
    """name -> nanoseconds of the op's OWN time: duration minus the time of
    the events nested inside it (a ``while`` around its body, a module
    envelope around its ops), so that sums do not count a nanosecond
    twice."""
    out = {}
    stack = []   # (name, end, child_ns, dur)

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _end, child, dur = stack.pop()
            out[name] = out.get(name, 0.0) + max(0.0, dur - child)
            if stack:
                top = stack[-1]
                stack[-1] = (top[0], top[1], top[2] + dur, top[3])

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        if dur <= 0:
            continue
        close(start)
        # an event that outlasts the one on top is its sibling, not its
        # child (two lines' worth of events, or clock rounding)
        while stack and start + dur > stack[-1][1]:
            close(stack[-1][1])
        stack.append((name, start + dur, 0.0, dur))
    close(float("inf"))
    return out


def is_collective(name):
    return any(c in name for c in COLLECTIVES)


def exposed_ns(events, pick=is_collective):
    """(total, exposed) nanoseconds of the picked ops (collectives): their
    summed duration, and the part during which no OTHER op ran on that
    device. Container events that merely enclose a picked op (a module
    envelope) would hide every gap, so only leaf events count as "other"."""
    picked = [e for e in events if pick(e[0])]
    others = union_intervals([e for e in leaf_events(events)
                              if not pick(e[0])])
    total = sum(d for _n, _s, d in picked)
    ends = [b for _a, b in others]        # ascending: others are disjoint
    exposed = 0.0
    for a, b in union_intervals(picked):
        covered = 0.0
        i = bisect.bisect_right(ends, a)  # the first other that ends after a
        while i < len(others) and others[i][0] < b:
            covered += min(b, others[i][1]) - max(a, others[i][0])
            i += 1
        exposed += (b - a) - covered
    return total, exposed


def leaf_events(events):
    """Events that contain no other event (events of one line nest
    properly, so an event has a child exactly when the next one in
    (start, longest-first) order starts before it ends)."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, start, dur) in enumerate(ordered):
        if i + 1 < len(ordered) and ordered[i + 1][1] < start + dur:
            continue
        out.append((name, start, dur))
    return out


def trace_window(devices):
    """(start, end) of the traced activity: first op start to last op end
    over all device planes."""
    starts = [s for evs in devices.values() for _n, s, _d in evs]
    ends = [s + d for evs in devices.values() for _n, s, d in evs]
    if not starts:
        raise RuntimeError("no device op in the trace: nothing ran on the "
                           "chip inside the traced window")
    return min(starts), max(ends)


def innermost_at(events, instants):
    """For each instant (ascending), the innermost event of one properly
    nested host line that covers it, as (name, dur) or None."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack, i = [], [], 0
    for t in instants:
        while i < len(ordered) and ordered[i][1] <= t:
            name, start, dur = ordered[i]
            while stack and stack[-1][1] <= start:
                stack.pop()
            stack.append((name, start + dur, dur))
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        out.append((stack[-1][0], stack[-1][2]) if stack else None)
    return out


def idle_gaps(events, host, top=10, window=None):
    """The gaps between device ops, each labelled with the host event that
    was innermost at the gap's middle (the shortest such event over the
    host's threads: a thread parked in a long wait says less than the one
    that was working). The profiler's host plane is all there is on that
    clock: the program has no spans of its own yet. Returns
    ``[(label, seconds), ...]`` summed by label, longest first."""
    gaps = []
    prev_end = window[0] if window else None
    for a, b in union_intervals(events):
        if prev_end is not None and a > prev_end:
            gaps.append((prev_end, a))
        prev_end = b if prev_end is None else max(prev_end, b)
    if window and prev_end is not None and window[1] > prev_end:
        gaps.append((prev_end, window[1]))
    mids = [(a + b) / 2.0 for a, b in gaps]
    labels = [None] * len(gaps)
    for _thread, evs in sorted(host.items()):
        for k, hit in enumerate(innermost_at(evs, mids)):
            if hit is not None and (labels[k] is None
                                    or hit[1] < labels[k][1]):
                labels[k] = hit
    summed = {}
    for (a, b), hit in zip(gaps, labels):
        name = hit[0] if hit else UNTRACED
        summed[name] = summed.get(name, 0.0) + (b - a)
    ranked = sorted(summed.items(), key=lambda kv: -kv[1])[:top]
    return [(name, ns / 1e9) for name, ns in ranked]


def summarize(data, top=10):
    """Everything the per-layer readers and the last line need:

    window_s, busy_s (mean over devices), per_device busy/idle,
    op_seconds (name -> own seconds, summed over devices, mean per device),
    device_ops / idle_gaps (the ``breakdown``), collective totals."""
    devices = data["devices"]
    w0, w1 = trace_window(devices)
    window_s = (w1 - w0) / 1e9
    per_device, ops, coll = {}, {}, {}
    for plane, events in sorted(devices.items()):
        busy = busy_ns(events, (w0, w1)) / 1e9
        per_device[plane] = {"busy_s": busy,
                             "idle_share": 1.0 - busy / window_s}
        for name, ns in self_times(events).items():
            ops[name] = ops.get(name, 0.0) + ns / 1e9
        total, exposed = exposed_ns(events)
        coll[plane] = {"total_s": total / 1e9, "exposed_s": exposed / 1e9}
    n = len(devices)
    ops = {k: v / n for k, v in ops.items()}
    first = sorted(devices)[0]
    return {
        "window_s": window_s,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / n,
        "per_device": per_device,
        "op_seconds": ops,
        "collectives": coll,
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": idle_gaps(devices[first], data["host"], top=top,
                               window=(w0, w1)),
    }
