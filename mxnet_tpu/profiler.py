"""Profiler (reference: python/mxnet/profiler.py:10-38 + src/engine/profiler.h —
per-op records dumped as chrome://tracing JSON).

TPU design: per-op wall timing is meaningless under whole-graph XLA fusion, so
this profiler has two tiers:
* device tier — delegates to jax.profiler (XLA's own tracing: HLO-level timeline
  viewable in TensorBoard/Perfetto), started/stopped by the same
  profiler_set_state API the reference exposes;
* python tier — records imperative-op dispatch + executor step spans into a
  chrome-tracing JSON file, matching the reference's dump format
  (profiler.h EmitEvent :107).
"""
from __future__ import annotations

import contextlib
import json
import os
import threading

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile",
           "emit_span", "is_running"]

# module-level so lock analysis (and the runtime witness) can name it;
# a dict slot is invisible to both
_lock = threading.Lock()

# race-ok: mutation happens under _lock; the hot-path reads ("running",
# "mode") are single-slot bool/str samples — a stale sample drops or keeps
# one span, and emit_span re-checks under the lock before appending
_state = {
    "mode": "symbolic",
    "filename": "profile.json",
    "running": False,
    "events": [],
    "jax_trace_dir": None,
}


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """(reference: profiler.py profiler_set_config; modes 'symbolic'|'all')"""
    with _lock:
        _state["mode"] = mode
        _state["filename"] = filename


def profiler_set_state(state="stop"):
    """'run' | 'stop' (reference: profiler.py profiler_set_state).

    State transitions and the event-buffer swap run under ``_lock``:
    a span completing on a worker thread while another thread restarts the
    profiler must land in exactly one of the old/new buffers, never corrupt
    the list mid-swap (the jax trace start/stop rides along under the same
    lock — it is rare and must not interleave with a concurrent toggle).
    """
    with _lock:
        if state == "run" and not _state["running"]:
            _state["running"] = True
            _state["events"] = []
            from .base import env_str

            trace_dir = env_str("MXNET_PROFILER_TRACE_DIR")
            if trace_dir:
                import jax

                jax.profiler.start_trace(trace_dir)
                _state["jax_trace_dir"] = trace_dir
        elif state == "stop" and _state["running"]:
            _state["running"] = False
            if _state["jax_trace_dir"]:
                import jax

                jax.profiler.stop_trace()
                _state["jax_trace_dir"] = None
        else:
            return


def is_running():
    """Whether the python-tier profiler is collecting spans."""
    return _state["running"]


_reserved = None  # race-ok: idempotent lazy cache of a constant — racing initializers store the same int


def _reserved_tid():
    """compileobs.COMPILE_TRACE_TID, cached (lazy import breaks the cycle)."""
    global _reserved
    if _reserved is None:
        from .compileobs import COMPILE_TRACE_TID
        _reserved = COMPILE_TRACE_TID
    return _reserved


def emit_span(name, category, wall_t0, dur_s, args=None, tid=None):
    """Append one complete span to the chrome-trace buffer if the profiler
    runs — the hook `telemetry.span` uses, so runtime-phase spans (the fit
    loop's `fit.step`, any user-opened span) land in the same timeline as
    the op/executor spans this module records itself. ``args`` (a
    JSON-able dict) becomes the trace event's ``args`` — the fit loop
    stamps epoch/nbatch so tools/trace_merge.py can match the same BSP
    step across worker lanes. ``tid`` pins the span to a synthetic lane
    instead of the emitting thread (compileobs routes every compile span
    onto one dedicated ``compile`` row this way)."""
    if not _state["running"]:
        return
    if tid is None:
        tid = threading.get_ident() % (1 << 16)
        if tid == _reserved_tid():
            # a thread whose hashed ident lands on the dedicated compile
            # lane would interleave unserialized spans with compileobs'
            # (overlaps the span-nesting checker rejects) and get its real
            # work labeled "compile" — shift it off the reserved row
            tid += 1
    ev = {
        "name": name,
        "cat": category,
        "ph": "X",
        "ts": wall_t0 * 1e6,
        "dur": dur_s * 1e6,
        "pid": os.getpid(),
        "tid": int(tid),
    }
    if args:
        ev["args"] = dict(args)
    with _lock:
        if not _state["running"]:
            return
        _state["events"].append(ev)


# what `record_span` hands out while it records nothing (reusable, shared)
_OFF = contextlib.nullcontext()


def record_span(name, category="operator"):
    """Context manager recording one span while the profiler runs; a shared
    no-op when stopped so the imperative hot path pays ~nothing. Mode
    "symbolic" records only executor spans (the reference's kOnlySymbolic);
    "all" adds per-op imperative spans (kAllOperator, profiler.h:63-66).
    These fire per operator, not per step: they feed the chrome trace only
    and open no `jax.profiler` annotation and no histogram
    (`telemetry.span` is the per-step kind)."""
    if not _state["running"]:
        return _OFF
    if _state["mode"] == "symbolic" and category == "operator":
        return _OFF
    from .telemetry import _Span

    return _Span(name, category, hist=False)


def dump_profile():
    """Write accumulated spans as chrome://tracing JSON
    (reference: MXDumpProfile → Profiler::DumpProfile, profiler.h:88).
    The event list is snapshotted under the lock so a span completing on a
    worker thread during the dump cannot mutate the list mid-serialization.

    Events are sorted by (tid, ts) — spans are appended at COMPLETION, so a
    long outer span lands after the short inner spans it encloses, and the
    raw append order would violate the per-tid start-time monotonicity the
    trace-schema regression test (and some viewers) expect. A distributed
    process also emits a ``process_name`` metadata row naming its rank, so
    ``tools/trace_merge.py`` can assign the file to a lane without
    guessing from pids."""
    with _lock:
        events = sorted(_state["events"],
                        key=lambda e: (e.get("tid", 0), e.get("ts", 0)))
        filename = _state["filename"]
    from . import telemetry

    rank = telemetry.get_rank()
    if rank is not None:
        events.insert(0, {
            "name": "process_name", "cat": "__metadata", "ph": "M",
            "pid": os.getpid(), "tid": 0,
            "args": {"name": "rank %d" % rank, "rank": rank},
        })
    # name the dedicated compile lane when any compile span landed on it
    compile_tid = _reserved_tid()
    if any(e.get("tid") == compile_tid for e in events):
        events.insert(0, {
            "name": "thread_name", "cat": "__metadata", "ph": "M",
            "pid": os.getpid(), "tid": compile_tid,
            "args": {"name": "compile"},
        })
    with open(filename, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)


# autostart + at-exit dump (reference: MXNET_PROFILER_AUTOSTART env,
# docs/how_to/env_var.md:73; profiler dump at exit, src/initialize.cc:39-48)
def _maybe_autostart():
    import atexit

    from .base import env_flag, env_str

    if env_flag("MXNET_PROFILER_AUTOSTART"):
        # default filename is pid-suffixed: launched clusters (tools/launch.py)
        # propagate the env to every process, and a shared name would leave
        # only the last exiter's trace
        profiler_set_config(
            mode="all",
            filename=env_str("MXNET_PROFILER_FILENAME",
                             "profile.%d.json" % os.getpid()))
        profiler_set_state("run")

        def _dump_at_exit():
            profiler_set_state("stop")
            dump_profile()

        atexit.register(_dump_at_exit)


_maybe_autostart()
