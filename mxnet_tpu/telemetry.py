"""Unified runtime telemetry: metrics registry, span tracing, training events.

The reference framework's observability was scattered — the profiler covered
op spans (src/engine/profiler.h), Speedometer printed one throughput number
(python/mxnet/callback.py:103), and everything else was free-text logging.
This module gives the runtime ONE process-wide, thread-safe registry of

* **counters**   — monotonically increasing event counts (engine push errors,
  KVStore retries, injected faults, server-side update failures);
* **gauges**     — last-value instruments (engine queue depth, dead PS nodes,
  instantaneous imgs/sec);
* **histograms** — bounded-bucket latency distributions with p50/p95/p99
  (step time, data wait, KV push/pull RTT, batch fetch);

plus **named spans** (context managers that feed the existing chrome-trace
profiler AND observe their duration as a histogram) and **structured events**
(epoch markers etc. as JSON-lines records).

Exposition:

* ``dump()``             — JSON-serializable snapshot of every instrument;
* ``prometheus_text()``  — Prometheus text exposition format (metric names
  are sanitized and prefixed ``mxnet_``);
* a background flusher   — ``MXNET_TELEMETRY_FILE`` names a JSON-lines sink;
  a daemon thread appends a snapshot record every
  ``MXNET_TELEMETRY_INTERVAL_S`` seconds (default 60) and a final one at
  exit; structured events are appended to the same file as they happen.

Overhead contract (the disabled-by-default fast path): metric OBJECTS are
always live — an ``inc()`` on a disabled registry still counts, so rare-path
counters (errors, retries, faults) never lose events — but every TIMING
instrumentation site in the runtime guards on :func:`enabled` before touching
the clock, so with telemetry off a hot path pays one module-global load and a
branch, no ``time`` calls, no dict lookups, no lock traffic. ``span()``
always opens a ``jax.profiler`` annotation — idle (a flag check in C++)
unless a ``jax.profiler`` session records — and reads the clock only while
telemetry or the MXNet-API profiler is active.

Enable with ``MXNET_TELEMETRY=1``, by setting ``MXNET_TELEMETRY_FILE``, or
programmatically via :func:`enable`.
"""
from __future__ import annotations

import bisect
import json
import math
import threading
import time
from collections import deque

# jax's profiler module starts no backend; the package imports jax anyway
from jax.profiler import (StepTraceAnnotation as _StepTraceAnnotation,
                          TraceAnnotation as _TraceAnnotation)

from . import profiler as _profiler
from .base import env_float as _env_float, env_str as _env_str

__all__ = [
    "Counter", "Gauge", "Histogram",
    "counter", "gauge", "histogram", "span", "event",
    "enable", "disable", "enabled",
    "dump", "prometheus_text", "reset", "state_summary", "totals",
    "flush", "start_flusher", "stop_flusher", "register_collector",
    "set_rank", "get_rank",
    "pipeline_stage", "PIPELINE_STAGES", "METRIC_HELP",
]

# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------

# Latency buckets in seconds: sub-millisecond host dispatch up through the
# tens-of-seconds XLA-compile / dead-node-probe tail. Bounded: 16 buckets +
# overflow, so a histogram's memory never grows with observation count.
DEFAULT_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 10.0, 30.0,
)


class Counter:
    """Monotonic event count. ``inc`` is atomic under its own lock, so N
    concurrent writers lose nothing (asserted in tests_tpu/test_telemetry)."""

    __slots__ = ("name", "labels", "_lock", "_value")

    def __init__(self, name, labels=()):
        self.name = name
        self.labels = labels
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, n=1):
        if n < 0:
            raise ValueError("counter can only increase (got %r)" % (n,))
        with self._lock:
            self._value += n

    @property
    def value(self):
        with self._lock:
            return self._value

    def snapshot(self):
        return self.value


class Gauge:
    """Last-value instrument (queue depth, dead nodes, imgs/sec)."""

    __slots__ = ("name", "labels", "_lock", "_value")

    def __init__(self, name, labels=()):
        self.name = name
        self.labels = labels
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, v):
        with self._lock:
            self._value = float(v)

    def inc(self, n=1):
        with self._lock:
            self._value += n

    def dec(self, n=1):
        with self._lock:
            self._value -= n

    @property
    def value(self):
        with self._lock:
            return self._value

    def snapshot(self):
        return self.value


class Histogram:
    """Bounded-bucket distribution with quantile estimates.

    Observations land in fixed buckets (cumulative counts in snapshots, the
    Prometheus convention); p50/p95/p99 are estimated by linear interpolation
    inside the covering bucket, clamped to the observed min/max — exact
    enough for latency triage, O(len(buckets)) memory forever.
    """

    __slots__ = ("name", "labels", "_lock", "_bounds", "_counts",
                 "_count", "_sum", "_min", "_max")

    def __init__(self, name, buckets=None, labels=()):
        self.name = name
        self.labels = labels
        self._lock = threading.Lock()
        self._bounds = tuple(sorted(buckets or DEFAULT_BUCKETS))
        if not self._bounds:
            raise ValueError("histogram needs at least one bucket bound")
        self._counts = [0] * (len(self._bounds) + 1)  # last = overflow (+Inf)
        self._count = 0
        self._sum = 0.0
        self._min = math.inf
        self._max = -math.inf

    def observe(self, v):
        v = float(v)
        idx = bisect.bisect_left(self._bounds, v)
        with self._lock:
            self._counts[idx] += 1
            self._count += 1
            self._sum += v
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v

    def time(self):
        """Context manager observing the block's wall duration."""
        return _Timer(self)

    @property
    def count(self):
        with self._lock:
            return self._count

    @property
    def sum(self):
        with self._lock:
            return self._sum

    def percentile(self, p):
        """Estimated value at percentile ``p`` (0-100), or None when empty."""
        with self._lock:
            return self._percentile_locked(p)

    def _percentile_locked(self, p):
        if self._count == 0:
            return None
        target = self._count * min(max(p, 0.0), 100.0) / 100.0
        cum = 0
        lo = 0.0
        for i, hi in enumerate(self._bounds):
            prev = cum
            cum += self._counts[i]
            if cum >= target:
                frac = ((target - prev) / self._counts[i]) if self._counts[i] else 0.0
                est = lo + frac * (hi - lo)
                return min(max(est, self._min), self._max)
            lo = hi
        return self._max  # landed in the overflow bucket

    def snapshot(self):
        with self._lock:
            if self._count == 0:
                return {"count": 0, "sum": 0.0}
            cum, cum_counts = 0, []
            for c in self._counts[:-1]:
                cum += c
                cum_counts.append(cum)
            return {
                "count": self._count,
                "sum": self._sum,
                "min": self._min,
                "max": self._max,
                "p50": self._percentile_locked(50),
                "p95": self._percentile_locked(95),
                "p99": self._percentile_locked(99),
                "buckets": {  # cumulative, le-keyed (Prometheus convention)
                    **{("%g" % b): c for b, c in zip(self._bounds, cum_counts)},
                    "+Inf": self._count,
                },
            }


class _Timer:
    __slots__ = ("_hist", "_t0")

    def __init__(self, hist):
        self._hist = hist

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self._hist.observe(time.perf_counter() - self._t0)
        return False


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_lock = threading.RLock()
_metrics = {}  # rendered key -> instrument
_name_types = {}  # bare name -> instrument class (Prometheus: one type/name)
_events = deque(maxlen=1024)
_enabled = False  # race-ok: config-time bool rebind; a reader that samples the old value emits (or skips) one event, never corrupts state
_flusher = None  # guarded-by: _lock — (thread, stop_event, path, interval)
_file_lock = threading.Lock()  # serializes sink appends (flusher vs events)
_rank = None  # race-ok: set once at launch/kvstore init (int-or-None rebind); this process's worker rank, None = unset
_collectors = []  # guarded-by: _lock — read-time refresh hooks (compileobs memory gauges)


def register_collector(fn):
    """Register a nullary hook run at the top of every registry READ
    (``dump`` / ``prometheus_text`` / ``state_summary``) to refresh
    derived gauges — e.g. compileobs re-reads device memory stats so a
    scrape always sees current bytes-in-use, without any per-step cost.
    Collectors must be cheap and must never raise (failures are logged and
    swallowed; a broken collector cannot take down a scrape)."""
    with _lock:
        if fn not in _collectors:
            _collectors.append(fn)


def _run_collectors():
    with _lock:
        hooks = list(_collectors)
    for fn in hooks:
        try:
            fn()
        except Exception:
            import logging

            logging.getLogger(__name__).warning(
                "telemetry collector %r failed", fn, exc_info=True)


def set_rank(rank):
    """Tag this process with its worker rank (distributed runs): every
    structured event and snapshot record from now on carries a ``rank``
    field, so merged JSON-lines streams from multiple workers stay
    distinguishable and ``tools/trace_merge.py`` can assign each file to
    its lane. Set automatically by the dist KVStore and by the launcher's
    DMLC env at import; pass ``None`` to clear (test isolation)."""
    global _rank
    _rank = None if rank is None else int(rank)


def get_rank():
    """The rank set via :func:`set_rank`, or None outside distributed runs."""
    return _rank


def _key(name, labels):
    if not labels:
        return name
    return "%s{%s}" % (name, ",".join("%s=%s" % kv for kv in labels))


def _get(cls, name, labels_dict, **ctor_kw):
    labels = tuple(sorted((str(k), str(v)) for k, v in labels_dict.items()))
    key = _key(name, labels)
    with _lock:
        # one instrument KIND per bare name, across all label sets — the
        # Prometheus data-model rule; enforcing it at registration turns a
        # mixed-type name into an immediate error at the misuse site
        # instead of a crashing scrape endpoint later
        have = _name_types.setdefault(name, cls)
        if have is not cls:
            raise TypeError("metric name %r already registered as %s"
                            % (name, have.__name__))
        m = _metrics.get(key)
        if m is None:
            m = cls(name, labels=labels, **ctor_kw)
            _metrics[key] = m
        return m


def counter(name, **labels):
    """Get-or-create the counter ``name`` (labels are kwargs)."""
    return _get(Counter, name, labels)


def gauge(name, **labels):
    """Get-or-create the gauge ``name``."""
    return _get(Gauge, name, labels)


def histogram(name, buckets=None, **labels):
    """Get-or-create the histogram ``name`` (bounded buckets, seconds)."""
    return _get(Histogram, name, labels, buckets=buckets)


# Input-pipeline stage attribution (docs/perf.md §pipeline, docs/
# observability.md): every stage of the rec-file path records its wall into
# ONE histogram name keyed by a `stage` label, so a dashboard reads the
# whole ladder with one query (tests_tpu/test_pipeline_feed.py does).
# Canonical stages:
#   decode    per-record JPEG decode + augment (ImageRecordIter workers)
#   assemble  per-batch host buffer fill (ImageRecordIter batcher)
#   upload    per-batch host->device transfer + on-device wire decode
#             (DeviceFeedIter transfer thread)
#   feed_wait per-batch consumer wait on the device feed queue
#   decode_native / augment_native / assemble_native
#             the same splits inside the native C++ stage
#             (ImageRecordIter(backend='native'), src/pipe.cc — observed
#             per batch as thread-summed deltas)
PIPELINE_STAGES = ("decode", "assemble", "upload", "feed_wait",
                   "decode_native", "augment_native", "assemble_native")


def pipeline_stage(stage):
    """The ``pipeline.stage_seconds{stage=...}`` histogram for one stage."""
    return histogram("pipeline.stage_seconds", stage=stage)


def enable():
    """Turn on timing capture, spans, and structured events."""
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def enabled():
    """Whether timing instrumentation sites should record. Rare-path
    counters (errors, retries, faults) count regardless — see module doc."""
    return _enabled


def reset():
    """Drop every instrument and buffered event (test isolation)."""
    with _lock:
        _metrics.clear()
        _name_types.clear()
        _events.clear()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class _Span:
    """The one span class. ``ann`` is the profiler annotation it holds open
    (None for `profiler.record_span`'s per-operator spans, which get none);
    the clock is read only while telemetry or the MXNet-API profiler is on,
    and the wall clock only for the profiler, whose chrome trace alone wants
    it. ``seconds`` is the span's duration once it has closed (None before,
    and None for a span that read no clock): whoever opened the span can
    book what the histogram got without a second pair of clock reads, as
    the serving engine's record of a step does."""

    __slots__ = ("name", "category", "args", "seconds", "_ann", "_hist",
                 "_t0", "_wall0")

    def __init__(self, name, category, args=None, ann=None, hist=True):
        self.name = name
        self.category = category
        self.args = args
        self.seconds = None
        self._ann = ann
        self._hist = hist

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        profiling = _profiler.is_running()
        if _enabled or profiling:
            self._wall0 = time.time() if profiling else None
            self._t0 = time.perf_counter()
        else:
            self._t0 = None
        return self

    def set(self, **args):
        """Add arguments known only inside the span (a count of what it
        did) before it closes."""
        if self._ann is not None:
            self._ann.set_metadata(**args)
        # thread-confined: a span is made, entered and left by one thread
        self.args = dict(self.args or (), **args)

    def __exit__(self, *exc):
        if self._t0 is not None:
            dur = self.seconds = time.perf_counter() - self._t0
            if _enabled and self._hist:
                histogram(self.name).observe(dur)
            if self._wall0 is not None:   # the profiler ran at the entry
                _profiler.emit_span(self.name, self.category, self._wall0,
                                    dur, self.args)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


def span(name, category="telemetry", step=None, **args):
    """Context manager around one named span of host code.

    The span always holds a ``jax.profiler.TraceAnnotation(name, **args)``
    open (a ``StepTraceAnnotation`` with ``step_num=step`` when ``step`` is
    given), so a ``jax.profiler`` session started by anybody shows it by
    name on the calling thread's line of the trace's host plane, on the
    device trace's clock. While no session records, the annotation is a
    flag check in C++ (about 0.5 us), and a span with everything off costs
    about 1 us in all: that and two flag reads, no ``time`` call, no lock.

    While telemetry is enabled the duration ALSO lands in histogram
    ``name``; while the MXNet-API profiler runs
    (``profiler_set_state('run')``) the span is ALSO appended to the
    chrome-trace event buffer, so `dump_profile()` timelines show runtime
    phases next to op/executor spans.

    Extra keyword ``args`` become the annotation's and the chrome-trace
    event's arguments — the fit loop stamps ``epoch``/``nbatch`` on
    ``fit.step`` so ``tools/trace_merge.py`` can match the same BSP step
    across worker lanes. They do not label the histogram (per-step label
    sets would grow without bound), and they must be host values: a span
    never touches a device array.
    """
    if step is None:
        ann = _TraceAnnotation(name, **args)
    else:
        ann = _StepTraceAnnotation(name, step_num=step, **args)
    return _Span(name, category, args or None, ann)


# ---------------------------------------------------------------------------
# structured events (JSON lines)
# ---------------------------------------------------------------------------


def event(name, **fields):
    """Record a structured training event (epoch markers, resume points).

    Buffered in memory (bounded deque, visible via ``dump()['events']``) and
    appended immediately as one JSON line to ``MXNET_TELEMETRY_FILE`` when a
    file sink is active. No-op while telemetry is disabled.
    """
    if not _enabled:
        return None
    rec = {"ts": time.time(), "type": "event", "event": name}
    if _rank is not None:
        rec["rank"] = _rank  # fields may override (e.g. registry-side
        # worker_lost events name the LOST worker's rank, not the host's)
    rec.update(fields)
    with _lock:
        _events.append(rec)
        sink = (_flusher[2] if _flusher
                else _expand_sink_path(_env_str("MXNET_TELEMETRY_FILE")))
    if sink:
        _append_line(sink, rec)
    return rec


def events(name=None):
    """Buffered events, optionally filtered by event name (newest last)."""
    with _lock:
        recs = list(_events)
    if name is not None:
        recs = [r for r in recs if r.get("event") == name]
    return recs


# ---------------------------------------------------------------------------
# exposition
# ---------------------------------------------------------------------------


def dump(include_events=True):
    """JSON-serializable snapshot of the whole registry."""
    _run_collectors()
    with _lock:
        items = sorted(_metrics.items())
        evs = list(_events) if include_events else None
    out = {
        "ts": time.time(),
        "enabled": _enabled,
        "counters": {},
        "gauges": {},
        "histograms": {},
    }
    kind = {Counter: "counters", Gauge: "gauges", Histogram: "histograms"}
    for key, m in items:
        out[kind[type(m)]][key] = m.snapshot()
    if evs is not None:
        out["events"] = evs
    return out


def state_summary(prefixes=()):
    """Compact ``{metric_key: value}`` snapshot of the registry, filtered to
    metric names starting with any of ``prefixes`` (all when empty).

    Counters/gauges render their value; histograms render ``count`` and
    ``p99``. This is the one-line runtime state the guard's stall watchdog
    dumps (docs/fault_tolerance.md §health-guard): queue depths and stage
    latencies point at WHICH stage wedged without shipping the full
    ``dump()`` blob into a log line.
    """
    _run_collectors()
    with _lock:
        items = sorted(_metrics.items())
    out = {}
    for key, m in items:
        if prefixes and not any(m.name.startswith(p) for p in prefixes):
            continue
        if isinstance(m, Histogram):
            snap = m.snapshot()
            out[key] = {"count": snap["count"], "p99": snap.get("p99")}
        else:
            out[key] = m.snapshot()
    return out


def totals(name):
    """Aggregate every instrument sharing bare metric ``name`` across its
    label sets: histograms return ``(count, sum)``; counters and gauges
    return ``(n_instruments, value_sum)``. ``(0, 0.0)`` when nothing is
    registered under the name. This is the cheap cross-label rollup the
    cluster-stats snapshot builder uses (e.g. ``kvstore.push_latency_seconds``
    is labeled per key — the per-step split wants the whole sync wall)."""
    with _lock:
        ms = [m for m in _metrics.values() if m.name == name]
    count, total = 0, 0.0
    for m in ms:
        if isinstance(m, Histogram):
            with m._lock:
                count += m._count
                total += m._sum
        else:
            count += 1
            total += m.value
    return count, total


# ---------------------------------------------------------------------------
# metric-description catalog
# ---------------------------------------------------------------------------
# One row per metric NAME the runtime registers (docs/observability.md keeps
# the operator-facing table; tests_tpu/test_telemetry.py asserts every name
# registered anywhere in mxnet_tpu/ appears both HERE and in the docs, so
# neither can drift from the code). Prometheus exposition emits each entry
# as a ``# HELP`` line.
METRIC_HELP = {
    "fit.step_time_seconds": "full fit-loop batch wall time",
    "fit.compute_seconds":
        "forward_backward+update dispatch time (XLA executes async)",
    "fit.data_wait_seconds": "time blocked on the data iterator",
    "fit.guard_seconds": "health-guard sentinel checks per step",
    "fit.batches": "fit-loop batches completed",
    "fit.samples": "fit-loop samples trained (net of batch padding)",
    "fit.epochs": "fit-loop epochs completed",
    "fit.imgs_per_sec": "instantaneous per-batch throughput",
    "fit.step": "fit.step span durations (chrome-trace timeline twin)",
    "fit.data_wait": "fit.data_wait span durations (the iterator fetch)",
    "eval.step_time_seconds":
        "score/predict per-batch wall time by path label",
    "eval.data_wait_seconds":
        "score/predict time blocked on the data iterator by path",
    "eval.compute_seconds":
        "score/predict forward+output dispatch time by path",
    "eval.batches": "score/predict batches completed by path",
    "eval.samples": "score/predict samples evaluated by path",
    "eval.imgs_per_sec": "instantaneous score/predict throughput by path",
    "compile.count": "XLA programs compiled per logical program (always-on)",
    "compile.seconds":
        "compile wall per program: trace+XLA compile+first dispatch "
        "(always-on)",
    "compile.run_seconds":
        "cumulative post-compile dispatch seconds per program "
        "(refreshed at read time)",
    "compile.recompile":
        "recompiles per program attributed by cause: batch/seq_len/axisN/"
        "dtype/rank/structure/placement (always-on)",
    "compile.cache_hits":
        "compiles served warm by the persistent compile cache per program "
        "(AOT artifact or jax disk cache underneath; always-on)",
    "compile.cache_misses":
        "genuinely cold XLA compiles per program while the persistent "
        "cache is enabled (always-on)",
    "compile.cache_errors":
        "persistent-cache faults: corrupt/stale artifacts, serialization "
        "refusals, IO failures — each falls back to a cold compile "
        "(always-on)",
    "compile.cache_evictions":
        "cache entries evicted to fit MXNET_COMPILE_CACHE_MAX_MB "
        "(always-on)",
    "graphpass.pass_seconds":
        "per-pass graph-optimization wall at bind time, labeled pass",
    "graphpass.nodes_eliminated":
        "graph nodes removed per pass (fold_constants/CSE; always-on)",
    "graphpass.nodes_fused":
        "pointwise nodes annotated into fusion groups (always-on)",
    "graphpass.shapes_bucketed":
        "declared batch dims padded by the opt-in bucket_shapes pass "
        "(always-on)",
    "graphpass.errors":
        "graph passes that raised and were skipped, labeled pass "
        "(always-on; the bind continues on the unoptimized graph)",
    "graphpass.fallbacks":
        "pipelines discarded for breaking the arg/aux/output binding "
        "surface (always-on; the unoptimized graph is used)",
    "device.bytes_in_use":
        "live device bytes per device (backend stats, NDArray-registry "
        "fallback)",
    "device.peak_bytes":
        "peak device bytes per device (backends exposing memory_stats)",
    "device.oom_events":
        "RESOURCE_EXHAUSTED failures caught at the executor boundary, by "
        "program (always-on; each dumps OOM forensics)",
    "speedometer.samples_per_sec": "last Speedometer window sample",
    "io.batch_fetch_seconds": "per-iterator batch fetch latency",
    "io.bad_records": "corrupt records quarantined by source",
    "io.native_decode_fallback":
        "native decode stage fallbacks to the Python pipeline by reason "
        "(always-on)",
    "pipeline.stage_seconds": "input-pipeline stage wall by stage label",
    "pipeline.feed_depth": "batches parked device-resident in the feed queue",
    "engine.pushes": "host-side ops pushed to the engine",
    "engine.push_latency_seconds": "pushed-fn execution time",
    "engine.queue_depth": "engine ops accepted but not yet started",
    "engine.push_errors": "pushed-fn exceptions (always-on)",
    "engine.sanitizer.undeclared_mutation":
        "sanitizer: pushed fn wrote an undeclared var (always-on)",
    "engine.sanitizer.const_write":
        "sanitizer: pushed fn wrote a declared-const var (always-on)",
    "engine.sanitizer.use_after_free":
        "sanitizer: pushed fn touched a deleted var (always-on)",
    "engine.sanitizer.undeclared_read":
        "sanitizer: pushed fn read an undeclared var (always-on)",
    "kvstore.push_latency_seconds":
        "per-key push latency incl. retries/backoff",
    "kvstore.pull_latency_seconds": "per-key pull latency",
    "kvstore.sync_wait_seconds":
        "per-step blocking wait harvesting the bucketed push/pull",
    "kv.overlap_seconds":
        "RPC wall hidden behind compute by gradient bucketing (always-on)",
    "kv.bucket_pushes":
        "gradient buckets whose pushes were issued (always-on)",
    "kv.buckets": "gradient buckets in the current step plan (always-on)",
    "kv.barrier":
        "worker wall blocked in the PS barrier rendezvous (span histogram)",
    "kvstore.rpc_failures": "failed RPC attempts by op (always-on)",
    "kvstore.retries": "RPC retry attempts by op (always-on)",
    "kvstore.backoff_ms": "cumulative scheduled RPC backoff (always-on)",
    "kvstore.dead_nodes":
        "servers the last liveness probe found unreachable (always-on)",
    "kv.membership.epoch": "current membership epoch (always-on)",
    "kv.membership.rejected":
        "requests rejected for a stale membership epoch (always-on)",
    "kv.membership.reconfigures":
        "registry-side membership epoch bumps (always-on)",
    "kv.membership.heartbeat_failures":
        "worker heartbeats the registry missed the deadline on (always-on)",
    "kv.replication.forwards":
        "primary->backup value/slot forwards issued (always-on)",
    "kv.replication.acks":
        "backup-acknowledged replication forwards (always-on)",
    "kv.replication.errors":
        "replication forwards that failed or timed out (always-on)",
    "kv.replication.lag_rounds":
        "replication rounds the slowest backup trails the primary by "
        "(always-on)",
    "kv.replication.failovers":
        "backup promotions after a server loss — registry-side plus "
        "standby registry activations (always-on)",
    "kv.server_ckpt.writes":
        "server optimizer-slot checkpoints written (always-on)",
    "kv.server_ckpt.restores":
        "server optimizer-slot checkpoints restored on recovery "
        "(always-on)",
    "kv.server_ckpt.bytes":
        "cumulative server optimizer-slot checkpoint bytes (always-on)",
    "kv.server_ckpt.errors":
        "failed or corrupt server checkpoint writes/restores — a corrupt "
        "restore cold-starts, never crashes (always-on)",
    "kv.stats_unreachable":
        "stats/trace polls skipped or failed per dead server — the poll "
        "pays one deadline per penalty window, not per poll (always-on)",
    "kvstore.server_loss_reports":
        "dead servers this worker reported to the registry (always-on)",
    "kv.registry.failover_probes":
        "registry traffic redirected to a standby registry host "
        "(always-on)",
    "kv.straggler.rank":
        "rank the straggler detector last named (-1 = none) (always-on)",
    "kv.cluster.publish_failures":
        "failed cluster-stats snapshot publishes (always-on)",
    "kvstore_server.updates_applied":
        "server-side optimizer updates applied (always-on)",
    "kvstore_server.update_failures":
        "server-side optimizer failures (always-on)",
    "guard.bad_steps": "health-guard bad steps by reason (always-on)",
    "guard.rollbacks": "guard snapshot restores (always-on)",
    "guard.stalls": "stall-watchdog firings (always-on)",
    "guard.checkpoint_errors":
        "failed guard mid-epoch checkpoint writes (always-on)",
    "fault.injections": "fired fault-injection rules by point (always-on)",
    "serving.kv_blocks_total": "usable KV pool blocks (pool size minus the "
                               "reserved trash block)",
    "serving.kv_blocks_used": "KV pool blocks currently allocated to "
                              "requests",
    "serving.kv_blocks_free": "KV pool blocks on the free list",
    "serving.kv_heads_per_row":
        "heads side by side in one page row of the KV pool (1 = the plain "
        "(heads, head_dim) row)",
    "serving.kv_blocks_frag_slots":
        "internal fragmentation: allocated-but-unused tail-block token "
        "slots across running requests",
    "serving.kv_blocks_allocs": "KV pool blocks handed out (cumulative)",
    "serving.kv_blocks_frees": "KV pool blocks returned (cumulative)",
    "serving.kv_blocks_alloc_failures":
        "KV pool allocations refused for exhaustion (each triggers "
        "preemption or request failure) (always-on)",
    "serving.queue_depth": "requests waiting for admission",
    "serving.active_requests": "requests admitted and holding KV blocks",
    "serving.requests_admitted": "requests admitted into prefill",
    "serving.requests_completed": "requests finished successfully",
    "serving.requests_failed":
        "requests failed (pool too small / engine error) (always-on)",
    "serving.preemptions":
        "recompute-style evictions under KV-block exhaustion (always-on)",
    "serving.step":
        "serving engine step wall, lock wait included (span histogram)",
    "serving.step.lock": "engine step's wait for the step lock (span)",
    "serving.loop.idle": "driver thread's waits on an empty queue (span)",
    "serving.schedule": "engine step's scheduling section (span)",
    "serving.prefill.build": "prefill host-input build (span)",
    "serving.prefill.dispatch":
        "prefill program call; a group's dispatches precede its fetches "
        "(span)",
    "serving.prefill.fetch":
        "prefill first-token blocking fetch, after the group's last "
        "dispatch (span)",
    "serving.prefill.group":
        "prompts in a step's group of prefills (dispatched back to back, "
        "fetched after the last dispatch); one observation a step that "
        "admitted any",
    "serving.prefill.groups": "groups of prefills run",
    "serving.prefill.pack":
        "prompts in one prefill program: a group's prompts are laid end to "
        "end into as few programs of the ladder as hold them; one "
        "observation a program",
    "serving.prefill.syncs_saved":
        "prefill fetches that exposed no host gap of their own: a group's "
        "prompts less one, summed",
    "serving.admit.stopped_by":
        "admission passes that found a request waiting, by what ended them "
        "(label reason: lanes, pool, slots, cap, preempted, queue); cap "
        "counts only where a prefills_per_step was given",
    "serving.decode.build": "decode step host-input build (span)",
    "serving.decode.dispatch": "decode program call (span)",
    "serving.decode.fetch": "decode next-token blocking fetch (span)",
    "serving.retire":
        "token bookkeeping and retirement after a prefill or a step (span)",
    "serving.retire.deferred":
        "the bookkeeping a step set aside (the chunk's counters, the "
        "tokens' telemetry, the throughput window, the step's record and "
        "event), carried out under the next step's dispatch (hidden=1) or "
        "by a read, an idle wait or the loop's return (span)",
    "serving.retire.finish":
        "the finished sweep inside a step's serving.retire: "
        "scheduler.finish and _retire a finished request (span)",
    "serving.prefill_seconds":
        "per-request prefill wall, own dispatch to the end of its fetch "
        "(in a group: with what was left of the prompts queued before it)",
    "serving.prefill_tokens": "prompt+replay tokens prefilled",
    "serving.prefill_rows":
        "rows the prefill programs computed for them: each prompt's rung "
        "of the prefill ladder (the rest of a rung is padding)",
    "serving.decode_batch":
        "live streams per fused decode step (one observation an inner "
        "step of a decode chunk, dead lanes not counted)",
    "serving.decode.dispatches":
        "dispatches of the decode program (a chunk of decode steps in a "
        "loop on the device, one blocking fetch each)",
    "serving.decode.inner_steps":
        "decode steps those dispatches ran on the device; / dispatches = "
        "how often the chunk engages",
    "serving.generated_tokens": "tokens generated across all streams",
    "serving.paged.live_blocks":
        "KV blocks the paged kernel walked: ceil(context / block size) a "
        "live stream, summed over decode and verify passes",
    "serving.paged.table_slots":
        "block-table slots those streams held (live streams x table "
        "width a pass): live_blocks / this = how full the tables ran",
    "serving.paged.mxu_share":
        "of the paged block walks counted since start, the share on "
        "head-major pages (a block is two MXU matmuls there, elementwise "
        "work on token-major pages); set when stats() is read",
    "serving.moe.pairs":
        "token-expert pairs the routed FFN computed (live tokens x "
        "experts per token x layers; nothing is dropped)",
    "serving.moe.routed_pairs":
        "every choice the router made for a live token, on an expert this "
        "engine holds or not (= experts per token x layer_tokens, exactly; "
        "pairs is the part computed here)",
    "serving.moe.layer_steps":
        "expert layers run: layers x programs (prefills and decode steps)",
    "serving.moe.layer_tokens":
        "live tokens the engine sent through an expert layer, summed over "
        "layer-steps (counted on the host: pairs = experts per token x "
        "this, exactly)",
    "serving.moe.experts_touched":
        "experts that received at least one live token, summed over "
        "layer-steps",
    "serving.moe.load_max_over_mean":
        "busiest expert's tokens over the mean expert's, averaged over "
        "the layers of the last step",
    "serving.looped.passes":
        "passes through a looped stack (loop_steps > 1) the step programs "
        "ran: loop_steps a prefill, an inner decode step, a verify pass",
    "serving.latent.ctx_tokens":
        "cached tokens the latent decode kernel read: the live lanes' "
        "context lengths summed over decode steps (x 'mla' layers = rows)",
    "serving.latent.lane_steps":
        "live lanes of a model with 'mla' layers summed over decode steps",
    "serving.latent.live_blocks":
        "latent-pool blocks the decode kernel walked, summed over steps",
    "serving.latent.prefill_tokens":
        "prompt+replay tokens whose latents a prefill cached",
    "serving.ssm.stream_steps":
        "decode state updates: live streams a decode step of a model with "
        "state-space layers (x its 'mamba' layers = slot updates)",
    "serving.ssm.prefill_tokens":
        "prompt+replay tokens scanned by the state-space layers' prefill",
    "serving.linear.state_updates":
        "matrix states the decode steps rewrote: live lanes x 'kda' layers, "
        "summed over decode steps (each is one kda_step slot update)",
    "serving.linear.prefill_tokens":
        "prompt+replay rows through the 'kda' layers' chunkwise prefill "
        "(tokens x 'kda' layers)",
    "serving.linear.prefill_chunks":
        "chunks of ops.kda.CHUNK rows the chunkwise prefill took "
        "(ceil(tokens / CHUNK) x 'kda' layers a prompt)",
    "serving.window.blocks_freed":
        "window-pool blocks returned during decode because they fell "
        "wholly behind a stream's sliding window",
    "serving.state_slots_used":
        "state slots (conv tail + float32 state a 'mamba' or 'kda' layer) "
        "held by running streams",
    "serving.ttft_seconds": "request time-to-first-token "
        "(bare = process-wide; engine label = per-engine)",
    "serving.request_latency_seconds": "request end-to-end latency "
        "(bare = process-wide; engine label = per-engine)",
    "serving.tokens_per_sec":
        "generated tokens/sec over a sliding 10s window",
    "serving.phase_seconds":
        "per-request wall by phase{engine,phase}: queue_wait / prefill / "
        "decode / replay / compile_stall sum to end-to-end "
        "(serving/obs.py)",
    "serving.tpot_seconds":
        "per-request time-per-output-token{engine} (decode-phase "
        "requests, >= 2 tokens)",
    "serving.slo_good":
        "requests meeting the SLO target{engine,phase}: phase=ttft vs "
        "MXNET_SERVING_SLO_TTFT_MS, phase=tpot vs "
        "MXNET_SERVING_SLO_TPOT_MS (always-on)",
    "serving.slo_total":
        "requests judged against the SLO target{engine,phase} (always-on)",
    "serving.goodput":
        "fraction of the last 32 finished requests meeting every "
        "applicable SLO target{engine}",
    "serving.prefix_lookups":
        "admissions probed against the prefix index "
        "(MXNET_SERVING_PREFIX_CACHE)",
    "serving.prefix_hits": "admissions that mapped >= 1 cached prefix block",
    "serving.prefix_hit_blocks":
        "KV blocks mapped from the prefix index instead of re-prefilled "
        "(cumulative)",
    "serving.prefix_shared_blocks":
        "allocated KV blocks currently shared by >= 2 streams",
    "serving.prefix_kv_bytes_saved":
        "KV bytes deduplicated right now: sum over shared blocks of "
        "(refcount-1) x block bytes",
    "serving.prefix_cow_copies":
        "copy-on-write block copies (a write slot backed by a shared "
        "block got a private copy)",
    "serving.spec_proposed_tokens":
        "draft tokens proposed (spec_k per stream per speculative step, "
        "MXNET_SERVING_SPEC_K)",
    "serving.spec_accepted_tokens":
        "draft proposals the target's verify pass accepted (emitted "
        "tokens stay bit-identical to target-only decoding)",
    "serving.spec_draft_seconds":
        "draft-model wall per speculative decode step (stall-free; the "
        "decode phase's draft sub-share)",
    "serving.spec_verify_seconds":
        "target multi-query verify wall per speculative decode step "
        "(stall-free)",
    "serving.shed":
        "submits rejected by load shedding (queue at MXNET_SERVING_MAX_"
        "QUEUE, engine draining, or supervisor mid-restart) — the 503 + "
        "Retry-After path (always-on)",
    "serving.timeouts":
        "requests swept to TIMED_OUT at their deadline (timeout_s / "
        "MXNET_SERVING_DEFAULT_TIMEOUT_MS); KV blocks freed at the sweep "
        "(always-on)",
    "serving.cancelled":
        "requests swept to CANCELLED after the consumer walked away "
        "(dropped connection / engine.cancel) (always-on)",
    "serving.restarts":
        "supervised engine restarts: abort -> salvage -> backoff -> "
        "rebuild warm -> replay survivors (resilience.EngineSupervisor) "
        "(always-on)",
    "serving.drains":
        "graceful drains begun (SIGTERM / POST /drain / start_drain): "
        "admission closed, inflight work finishing (always-on)",
    "lock.held_seconds":
        "hold time per witness-declared lock (MXNET_LOCK_WITNESS; "
        "always-on while the witness is enabled)",
    "lock.contention":
        "witnessed acquisitions that found the lock already taken "
        "(always-on while the witness is enabled)",
    "lock.order_violations":
        "classified lock-order violations the runtime witness observed: "
        "order inversions + edges absent from the static lock graph "
        "(always-on while the witness is enabled; strict mode also "
        "raises)",
}


def _prom_name(name):
    import re

    name = re.sub(r"[^a-zA-Z0-9_:]", "_", name)
    if not re.match(r"[a-zA-Z_:]", name):
        name = "_" + name
    return "mxnet_" + name


def _prom_labels(labels, extra=()):
    pairs = tuple(labels) + tuple(extra)
    if not pairs:
        return ""
    body = ",".join('%s="%s"' % (k, str(v).replace("\\", "\\\\").replace('"', '\\"'))
                    for k, v in pairs)
    return "{%s}" % body


def _prom_num(v):
    if v == math.inf:
        return "+Inf"
    if v == -math.inf:
        return "-Inf"
    return repr(float(v)) if isinstance(v, float) else str(v)


def prometheus_text():
    """The registry in Prometheus text exposition format (v0.0.4).

    Metric names are sanitized (``.`` -> ``_``) and prefixed ``mxnet_``;
    histograms expose the standard ``_bucket``/``_sum``/``_count`` triplet
    with cumulative ``le`` buckets. Serve this from any HTTP handler to make
    a training job scrapeable (docs/observability.md has a ready example).
    """
    _run_collectors()
    with _lock:
        items = sorted(_metrics.items())
    by_name = {}
    for _, m in items:
        by_name.setdefault(m.name, []).append(m)
    lines = []
    for name in sorted(by_name):
        group = by_name[name]
        pname = _prom_name(name)
        help_text = METRIC_HELP.get(name)
        if help_text:
            lines.append("# HELP %s %s" % (
                pname, help_text.replace("\\", "\\\\").replace("\n", "\\n")))
        if isinstance(group[0], Counter):
            lines.append("# TYPE %s counter" % pname)
            for m in group:
                lines.append("%s%s %s" % (pname, _prom_labels(m.labels),
                                          _prom_num(m.value)))
        elif isinstance(group[0], Gauge):
            lines.append("# TYPE %s gauge" % pname)
            for m in group:
                lines.append("%s%s %s" % (pname, _prom_labels(m.labels),
                                          _prom_num(m.value)))
        else:
            lines.append("# TYPE %s histogram" % pname)
            for m in group:
                # ONE snapshot (one lock acquisition) feeds every line: a
                # second read of the live counts could see observations that
                # arrived after it, printing finite buckets above le="+Inf"
                # — a non-monotone histogram scrapers reject
                snap = m.snapshot()
                buckets = snap.get("buckets")
                if buckets is None:  # empty histogram: all-zero buckets
                    buckets = {"%g" % b: 0 for b in m._bounds}
                    buckets["+Inf"] = 0
                for le, cum in buckets.items():
                    lines.append("%s_bucket%s %d" % (
                        pname, _prom_labels(m.labels, (("le", le),)), cum))
                lines.append("%s_sum%s %s" % (pname, _prom_labels(m.labels),
                                              _prom_num(snap["sum"])))
                lines.append("%s_count%s %d" % (pname, _prom_labels(m.labels),
                                                snap["count"]))
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# background flusher
# ---------------------------------------------------------------------------


def _expand_sink_path(path):
    """Expand ``{pid}`` / ``{rank}`` placeholders in a sink path. Every
    process of a launched cluster inherits the same ``MXNET_TELEMETRY_FILE``
    and appends are only serialized within one process — a literally shared
    file would tear multi-chunk snapshot appends across processes. ``{rank}``
    resolves to the worker rank (server processes get ``s<id>``; processes
    outside a launch fall back to the pid so two of them never collide)."""
    if not path or "{" not in path:
        return path
    import os

    rank = _rank
    if rank is None:
        if os.environ.get("DMLC_ROLE") == "server":
            rank = "s%s" % os.environ.get("DMLC_SERVER_ID", "0")
        else:
            rank = os.environ.get("DMLC_WORKER_ID", str(os.getpid()))
    return (path.replace("{pid}", str(os.getpid()))
            .replace("{rank}", str(rank)))


def _append_line(path, rec):
    # one writer at a time: a multi-chunk snapshot append racing an event
    # append would interleave buffered chunks and tear the JSON lines
    # (O_APPEND only makes single syscalls atomic). This serializes writers
    # within the process; across processes use one file per process, like
    # the profiler's pid-suffixed default.
    try:
        with _file_lock, open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError:
        import logging

        logging.getLogger(__name__).warning(
            "telemetry: cannot append to %s", path, exc_info=True)


def flush(path=None):
    """Append one snapshot record to the JSON-lines sink now."""
    if not path:
        with _lock:
            path = _flusher[2] if _flusher else None
        path = path or _expand_sink_path(_env_str("MXNET_TELEMETRY_FILE"))
    if not path:
        return
    rec = dump(include_events=False)
    rec["type"] = "snapshot"
    if _rank is not None:
        rec["rank"] = _rank
    _append_line(path, rec)


def start_flusher(path=None, interval_s=None):
    """Start the periodic snapshot flusher (idempotent).

    Defaults come from ``MXNET_TELEMETRY_FILE`` / ``MXNET_TELEMETRY_INTERVAL_S``
    (interval default 60s, floored at 0.05s). Also enables telemetry — a
    flushing-but-disabled registry would record empty snapshots forever.
    """
    global _flusher
    path = _expand_sink_path(path or _env_str("MXNET_TELEMETRY_FILE"))
    if not path:
        raise ValueError("no telemetry file: pass path= or set "
                         "MXNET_TELEMETRY_FILE")
    if interval_s is None:
        interval_s = _env_float("MXNET_TELEMETRY_INTERVAL_S", 60.0)
    interval_s = max(float(interval_s), 0.05)
    with _lock:
        if _flusher is not None:
            return
        enable()
        stop = threading.Event()

        def loop():
            while not stop.wait(interval_s):
                flush(path)

        t = threading.Thread(target=loop, name="mxnet-telemetry-flusher",
                             daemon=True)
        _flusher = (t, stop, path, interval_s)
        t.start()


def stop_flusher(final_flush=True):
    """Stop the periodic flusher (writing one last snapshot by default)."""
    global _flusher
    with _lock:
        if _flusher is None:
            return
        t, stop, path, _ = _flusher
        _flusher = None
    stop.set()
    t.join(timeout=5)
    if final_flush:
        flush(path)


def _maybe_autostart():
    import atexit
    import os

    from .base import env_flag

    # worker identity from the launcher env (tools/launch.py DMLC contract):
    # set BEFORE the flusher starts so {rank} sink expansion and every
    # event/snapshot record see it
    if os.environ.get("DMLC_ROLE", "worker") == "worker" and \
            os.environ.get("DMLC_WORKER_ID"):
        set_rank(os.environ["DMLC_WORKER_ID"])
    if _env_str("MXNET_TELEMETRY_FILE"):
        start_flusher()
        atexit.register(stop_flusher)
    elif env_flag("MXNET_TELEMETRY"):
        enable()


_maybe_autostart()
