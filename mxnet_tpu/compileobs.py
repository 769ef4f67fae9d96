"""Compile & device-memory observability: the program registry.

The runtime compiles ~a dozen logical XLA programs per training job — the
fused SPMD step, the executor's forward / fwd+bwd pair, the on-device wire
decode (``_image_wire_normalize``), the guard sentinel, the fused optimizer
update, deferred metric counts, export artifacts — and before this module
each called ``jax.jit`` independently with no shared accounting. Nobody
could say how many programs a fit compiled, which call site retraced on a
shape change, or where compile wall time went; ROADMAP #3's compile cache
has nothing to be judged against.

Every jit site now routes through :func:`jit` (the ``untracked-jit`` fwlint
rule keeps it that way) and the registry records, per logical program:

* a stable **graph digest** (``symbol_digest`` for graph programs, the
  op+attrs key for imperative kernels);
* the **input signature** (per-leaf shape/dtype) of every compilation, so a
  recompile is *attributed*: the ``compile.recompile`` event names the axis
  that changed (batch, seq_len, axis-k), the dtype flip, or the structural
  change, and the call site that paid for it;
* **compile wall seconds** (always-on ``compile.count`` /
  ``compile.seconds{program}`` metrics + a ``compile`` lane span on the
  chrome-trace timeline) vs **cumulative run seconds** — the
  compile-vs-steady-state split ``tools/compile_report.py`` renders offline;
* the program's **input footprint** (``arg_bytes``) and, where the backend
  exposes live stats, the device **peak watermark** observed right after the
  compile landed.

Detection is zero-copy on the hot path: a call is classified as a compile
when the underlying jit cache GREW during it (``_cache_size`` delta — jax's
own executable cache is the source of truth, so our view can never drift
from what XLA actually compiled); signatures are only computed on compile
events, never per step.

Device-memory accounting rides along: per-device live/peak byte gauges
(``jax Device.memory_stats`` where the backend exposes it, the NDArray
allocation registry as the fallback on backends that don't), and an OOM
forensics hook — :func:`oom_guard` wraps the executor boundary, catches
``RESOURCE_EXHAUSTED``, and dumps the top live allocations plus the program
table before re-raising, so the post-mortem names WHAT held the memory and
WHICH programs were resident. ``fault.py`` point ``oom`` injects the
failure for tests (``MXNET_FAULT_SPEC="oom:"``).

Always on: the accounting is a handful of counters and one cache-size read
per dispatch — the cost class of the fit loop's existing per-batch checks —
and a compile event is so expensive (seconds) that its bookkeeping is free.
"""
from __future__ import annotations

import hashlib
import logging
import threading
import time

from . import telemetry
from .base import MXNetError, env_int as _env_int

__all__ = [
    "jit", "raw_jit", "record_compile", "oom_guard", "symbol_digest",
    "program_table", "summary", "last_recompile", "reset",
    "device_memory_stats", "live_ndarray_report", "update_memory_gauges",
]

_log = logging.getLogger(__name__)

_lock = threading.RLock()
# race-ok: the one unlocked read is _rec()'s identity probe — a stale
# record is detected and re-resolved under _lock on the next line
_programs = {}  # program name -> _ProgramRecord
_recompiles = []  # chronological recompile attributions (bounded)
_MAX_RECOMPILE_LOG = 256

# chrome-trace lane for compile spans: a fixed synthetic tid so every
# compile lands on ONE dedicated row of the timeline instead of scattering
# across the worker threads that happened to trigger them
COMPILE_TRACE_TID = 59999

_lane_lock = threading.Lock()
_lane_last_end = 0.0

# sentinel: the AOT lane declined this call — take the normal jit path
_AOT_FELL_BACK = object()


def _emit_compile_span(name, wall0, dur, args):
    """One span on the compile lane. Placement is serialized: two threads
    compiling concurrently would partially overlap on the shared tid, which
    the trace-schema checker (trace_merge.validate_trace span nesting)
    rightly rejects — the later span is shifted to start after the earlier
    one ends (duration preserved, so total compile wall stays truthful)."""
    global _lane_last_end

    from . import profiler

    with _lane_lock:
        start = max(wall0, _lane_last_end)
        _lane_last_end = start + dur
    profiler.emit_span(name, "compile", start, dur, args=args,
                       tid=COMPILE_TRACE_TID)


class _ProgramRecord:
    """Registry row for one logical program (all wrappers sharing a name)."""

    __slots__ = ("name", "site", "digest", "compile_count", "compile_seconds",
                 "run_count", "run_seconds", "recompile_count", "arg_bytes",
                 "peak_bytes", "first_compile_ts", "last_compile_ts",
                 "signatures", "lock")

    def __init__(self, name, site, digest):
        self.name = name
        self.site = site
        self.digest = digest
        self.compile_count = 0
        self.compile_seconds = 0.0
        self.run_count = 0
        self.run_seconds = 0.0
        self.recompile_count = 0
        self.arg_bytes = 0
        self.peak_bytes = None  # backend peak right after last compile
        self.first_compile_ts = None
        self.last_compile_ts = None
        # graph_key -> last compiled signature (cross-wrapper recompile
        # attribution: a rebind/reshape builds a NEW wrapper for the SAME
        # logical graph, and its first compile must still diff against what
        # that graph compiled at before)
        self.signatures = {}
        self.lock = threading.Lock()

    def as_dict(self):
        with self.lock:
            return {
                "program": self.name,
                "site": self.site,
                "digest": self.digest,
                "compile_count": self.compile_count,
                "compile_seconds": round(self.compile_seconds, 6),
                "run_count": self.run_count,
                "run_seconds": round(self.run_seconds, 6),
                "recompile_count": self.recompile_count,
                "arg_bytes": self.arg_bytes,
                "peak_bytes": self.peak_bytes,
                "first_compile_ts": self.first_compile_ts,
                "last_compile_ts": self.last_compile_ts,
            }


def _record(name, site=None, digest=None):
    with _lock:
        rec = _programs.get(name)
        if rec is None:
            rec = _ProgramRecord(name, site or "", digest or "")
            _programs[name] = rec
        else:
            if site and not rec.site:
                rec.site = site
            if digest and not rec.digest:
                rec.digest = digest
        return rec


def reset():
    """Drop every program record (test isolation). The telemetry-side
    counters live in the telemetry registry and reset with it."""
    with _lock:
        _programs.clear()
        del _recompiles[:]


# ---------------------------------------------------------------------------
# graph digests & input signatures
# ---------------------------------------------------------------------------


def symbol_digest(symbol):
    """Stable digest of a Symbol's computation graph: the topo-ordered op
    sequence with attrs AND the full edge wiring (which node output feeds
    which input slot), independent of bind shapes and of node identity/
    names. Two Executors bound over the same graph share it, so a
    reshape/rebind's first compile is correctly attributed as a RECOMPILE
    of that graph rather than a fresh program — and, run after the
    graphpass canonicalize pass, digest-equal means structurally-equal:
    the property the persistent compile cache keys on.

    Variables hash by ROLE AND SLOT (``a<i>`` = i-th argument, ``x<j>`` =
    j-th aux state, in this symbol's own ordering), never by name: names
    are cosmetic, but WHICH slot feeds which input is semantics —
    ``(a+b)-a`` and ``(a+p)-p`` are different positional functions and
    must never share a digest (a shared persistent-cache key would serve
    one of them the other's executable)."""
    from .symbol import _topo_order

    order = _topo_order(symbol._entries)
    idx = {id(n): i for i, n in enumerate(order)}
    _, aux_vars = symbol._arg_aux_split()
    h = hashlib.sha1()
    n_arg = n_aux = 0
    for node in order:
        if node.is_variable:
            if id(node) in aux_vars:
                h.update(("var:x%d|" % n_aux).encode())
                n_aux += 1
            else:
                h.update(("var:a%d|" % n_arg).encode())
                n_arg += 1
            continue
        h.update(node.op.encode())
        for k, v in sorted(node.attrs.items()):
            h.update(("|%s=%s" % (k, v)).encode())
        for inp, k in node.inputs:
            h.update(("|@%d.%d" % (idx[id(inp)], k)).encode())
        h.update(b";")
    h.update(("out:" + ",".join(
        "%d.%d" % (idx[id(n)], k) for n, k in symbol._entries)).encode())
    return h.hexdigest()[:16]


def _leaf_desc(leaf):
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None:
        return ("py:%s" % type(leaf).__name__, (), "")
    return ("", tuple(int(d) for d in shape), str(dtype))


def _signature(args):
    """Per-leaf (kind, shape, dtype) tuple of a call's inputs, with jax
    keypath names so a diff can say WHICH argument changed. Computed only on
    compile events — never on the steady-state dispatch path."""
    import jax

    leaves_kp, _ = jax.tree_util.tree_flatten_with_path(args)
    sig = []
    for kp, leaf in leaves_kp:
        kind, shape, dtype = _leaf_desc(leaf)
        sig.append((jax.tree_util.keystr(kp), kind, shape, dtype))
    return tuple(sig)


def _axis_name(axis, rank):
    if axis == 0:
        return "batch"
    # "seq_len" only for token-shaped inputs (B,T) / (B,T,D): axis 1 of a
    # rank-4 image tensor is channels or height, not sequence length
    if axis == 1 and rank in (2, 3):
        return "seq_len"
    return "axis%d" % axis


def diff_signatures(old, new):
    """Attribute what changed between two compiled signatures of the same
    program: ``(cause, detail)`` where cause is one of ``batch`` /
    ``seq_len`` / ``axis<k>`` / ``dtype`` / ``rank`` / ``structure`` /
    ``placement`` (same shapes — the device/sharding moved, which our
    shape-level signature cannot see)."""
    if old == new:
        return "placement", {"note": "identical shapes: device/sharding or "
                                     "static-config change"}
    if len(old) != len(new) or \
            [e[0] for e in old] != [e[0] for e in new]:
        return "structure", {"old_leaves": len(old), "new_leaves": len(new)}
    changed = []
    for (name, okind, oshape, odt), (_, nkind, nshape, ndt) in zip(old, new):
        if okind != nkind or oshape != nshape or odt != ndt:
            changed.append((name, oshape, odt, nshape, ndt))
    if not changed:
        return "placement", {}
    name, oshape, odt, nshape, ndt = changed[0]
    detail = {"arg": name, "old_shape": list(oshape),
              "new_shape": list(nshape), "n_changed": len(changed)}
    if odt != ndt:
        detail["old_dtype"], detail["new_dtype"] = odt, ndt
        if oshape == nshape:
            return "dtype", detail
    if len(oshape) != len(nshape):
        return "rank", detail
    axes = [i for i, (a, b) in enumerate(zip(oshape, nshape)) if a != b]
    if not axes:
        return "dtype", detail
    detail["axis"] = axes[0]
    return _axis_name(axes[0], len(oshape)), detail


def _arg_nbytes(sig):
    import numpy as np

    total = 0
    for _, kind, shape, dtype in sig:
        if kind or not dtype:
            continue
        try:
            n = int(np.dtype(dtype).itemsize)
        except TypeError:
            continue
        for d in shape:
            n *= int(d)
        total += n
    return total


# ---------------------------------------------------------------------------
# the observed jit wrapper
# ---------------------------------------------------------------------------


class ObservedJit:
    """``jax.jit`` with compile accounting and an optional persistent-cache
    fast lane.

    Dispatch is jax's own (placement, retracing, donation — untouched); this
    wrapper only watches the executable-cache size across each call. Growth
    means THIS call traced+compiled: the call's wall time is recorded as
    compile seconds (trace + XLA compile + the first dispatch), a span lands
    on the chrome-trace compile lane, and — when the program's graph was
    compiled before — the old/new input signatures are diffed into a
    ``compile.recompile`` attribution. When the persistent compile cache is
    enabled (``mxnet_tpu/compile_cache.py``), every compile event is also
    classified cold-vs-disk-hit (``compile.cache_misses`` vs
    ``compile.cache_hits``) via the cache's marker index.

    ``aot=True`` marks a **single-signature** site (each executor instance,
    each serving shape bucket): with the cache enabled, the first dispatch
    resolves the call's key and either loads the serialized executable from
    disk (no trace, no compile) or AOT-compiles via ``lower().compile()``
    and serializes it for the next process; every later call dispatches the
    executable directly. A call whose signature drifts raises inside the
    executable's argument check and falls back to normal jit dispatch —
    never wrong numerics, at worst the seed's compile behavior.
    """

    __slots__ = ("_jitted", "_record", "_graph_key", "_cache_seen",
                 "_own_sigs", "_acct_lock", "_aot_mode", "_aot_state",
                 "_aot_exe", "_aot_drifts", "_cache_identity")

    def __init__(self, fn, program, site=None, graph_key=None, digest=None,
                 aot=False, cache_key=None, **jit_kwargs):
        import jax

        self._jitted = jax.jit(fn, **jit_kwargs)  # fwlint: disable=untracked-jit — the registry wrapper itself
        if digest is None:
            digest = (graph_key if isinstance(graph_key, str) else None)
        self._record = _record(program, site=site, digest=digest)
        # graph identity for cross-wrapper recompile attribution; None means
        # wrapper-scoped (per-instance programs like the fused updater whose
        # per-device call groups legitimately hold several signatures)
        self._graph_key = graph_key if graph_key is not None else id(self)
        # disk-cache identity: must be stable ACROSS processes (a bare
        # graph_key qualifies when the caller passed one — process-local
        # id(self) defaults never do). None → no hit/miss classification
        # and no AOT lane for this wrapper (jax's persistent cache still
        # serves it transparently underneath).
        if cache_key is not None:
            self._cache_identity = cache_key
        elif graph_key is not None:
            self._cache_identity = graph_key
        else:
            self._cache_identity = None
        self._aot_mode = bool(aot)
        self._aot_state = "init"  # init -> on|off (decided at first call)
        self._aot_exe = None
        self._aot_drifts = 0
        self._cache_seen = self._cache_size()
        self._own_sigs = None  # fallback signature cache when _cache_size
        # is unavailable (counts first compiles per signature, like jit)
        # serializes the classify-and-resync step only (dispatch itself is
        # unlocked): shared wrappers (op._JIT_CACHE kernels) are dispatched
        # from engine/pipeline threads concurrently, and without this both
        # the compiler and a blocked waiter would observe the cache delta
        # and double-count the compile
        self._acct_lock = threading.Lock()

    # -- introspection pass-throughs -----------------------------------
    def _cache_size(self):
        try:
            return self._jitted._cache_size()
        except (AttributeError, TypeError):
            return None

    def lower(self, *args, **kwargs):
        """AOT lowering pass-through (``Executor.memory_analysis``)."""
        return self._jitted.lower(*args, **kwargs)

    def _rec(self):
        """The live registry record — re-registered if :func:`reset` ran
        since this wrapper was built (long-lived wrappers like the
        imperative-op cache survive registry resets)."""
        rec = self._record
        if _programs.get(rec.name) is not rec:
            rec = _record(rec.name, rec.site, rec.digest)
            # race-ok: reference rebind to an equivalent record; racing
            # threads re-register the same (name, site, digest) idempotently
            self._record = rec
        return rec

    @property
    def program(self):
        return self._rec().name

    def compile_totals(self):
        """This wrapper's program-record compile tallies
        ``(compile_count, compile_seconds)`` — a cheap two-field read
        under the record lock. The serving engine samples it around each
        bucket dispatch to attribute compile-stall wall to the requests
        blocked behind a cold bucket (serving/obs.py). The record is
        shared per PROGRAM name, so concurrent compiles of sibling
        buckets land in the same tallies — callers diffing around a
        dispatch own the only driver thread in every shipped engine."""
        rec = self._rec()
        with rec.lock:
            return rec.compile_count, rec.compile_seconds

    @property
    def compiled(self):
        """Whether a call of this wrapper has compiled or loaded its
        program: a resident executable (the AOT lane) or an entry in jax's
        own cache. The serving engine lays prompts into a prefill rung of
        several only where this holds: a pack compiles nothing."""
        return (self._aot_exe is not None or bool(self._cache_size())
                or bool(self._own_sigs))

    @property
    def __wrapped__(self):
        return self._jitted

    # -- dispatch -------------------------------------------------------
    def __call__(self, *args, **kwargs):
        exe = self._aot_exe
        if exe is not None:
            out = self._aot_dispatch(exe, args, kwargs)
            if out is not _AOT_FELL_BACK:
                return out
        elif self._aot_state == "init":
            from . import compile_cache as _cc

            self._aot_state = (
                "on" if (self._aot_mode and self._cache_identity is not None
                         and _cc.aot_enabled())
                else "off")
        if self._aot_state == "on" and self._aot_exe is None:
            out = self._aot_first_call(args, kwargs)
            if out is not _AOT_FELL_BACK:
                return out
        t0 = time.perf_counter()
        try:
            out = self._jitted(*args, **kwargs)
        except Exception as exc:
            # resync so a successful trace+compile behind a failed dispatch
            # is not charged to the NEXT (cached, cheap) call
            self._resync_cache()
            if is_oom_error(exc):
                dump_oom_report(self._rec().name, exc)
            raise
        # keyword leaves ride the signature as one trailing dict group
        return self._account(args + (kwargs,) if kwargs else args, out, t0)

    # -- the AOT persistent-cache lane ----------------------------------
    def _aot_dispatch(self, exe, args, kwargs):
        """Steady-state dispatch through the resident executable. A
        signature drift (rebound shapes) raises inside the executable's
        argument check — fall back to jit dispatch; after two drifts the
        lane shuts off for good (an alternating-shape site belongs on
        jax's multi-signature cache, not here)."""
        t0 = time.perf_counter()
        try:
            out = exe(*args, **kwargs)
        except Exception as exc:
            if is_oom_error(exc):
                dump_oom_report(self._rec().name, exc)
                raise
            with self._acct_lock:
                self._aot_exe = None
                self._aot_drifts += 1
                if self._aot_drifts >= 2:
                    self._aot_state = "off"
            _log.warning(
                "compile cache: program %r AOT executable rejected a "
                "dispatch (%s: %s) — falling back to jit dispatch",
                self._rec().name, type(exc).__name__, str(exc)[:200])
            return _AOT_FELL_BACK
        dt = time.perf_counter() - t0
        rec = self._rec()
        with rec.lock:
            rec.run_count += 1
            rec.run_seconds += dt
        return out

    def _aot_first_call(self, args, kwargs):
        """Resolve this site's cache key from the first call's signature,
        then load-or-compile the executable. Any cache-layer failure falls
        back to plain jit dispatch (``compile.cache_errors`` counts it) —
        the lane is an optimization, never a correctness dependency."""
        from . import compile_cache as _cc

        rec = self._rec()
        t0 = time.perf_counter()
        wall0 = time.time()
        sig_args = args + (kwargs,) if kwargs else args
        try:
            sig = _signature(sig_args)
            key = _cc.make_key(rec.name, self._cache_identity, sig)
        except Exception:
            telemetry.counter("compile.cache_errors").inc()
            _log.warning("compile cache: could not key program %r — AOT "
                         "lane off", rec.name, exc_info=True)
            with self._acct_lock:
                self._aot_state = "off"
            return _AOT_FELL_BACK
        exe = _cc.load_executable(key, rec.name)
        if exe is not None:
            try:
                out = exe(*args, **kwargs)
            except Exception as exc:
                if is_oom_error(exc):
                    dump_oom_report(rec.name, exc)
                    raise
                # loads but won't run here (e.g. topology drift the
                # fingerprint missed): treat as corrupt, compile cold
                telemetry.counter("compile.cache_errors").inc()
                _log.warning(
                    "compile cache: loaded AOT executable for %r failed "
                    "to dispatch (%s) — compiling cold", rec.name,
                    type(exc).__name__)
                exe = None
        if exe is None:
            try:
                compiled = self._jitted.lower(*args, **kwargs).compile()
                out = compiled(*args, **kwargs)
            except Exception as exc:
                if is_oom_error(exc):
                    dump_oom_report(rec.name, exc)
                    raise
                # AOT compilation path unsupported here: shut the lane off
                # and let the normal jit dispatch (re)do the work
                telemetry.counter("compile.cache_errors").inc()
                _log.warning("compile cache: AOT lower/compile failed for "
                             "%r — falling back to jit dispatch", rec.name,
                             exc_info=True)
                with self._acct_lock:
                    self._aot_state = "off"
                return _AOT_FELL_BACK
            _cc.save_executable(key, compiled, rec.name)
            exe = compiled
        with self._acct_lock:
            self._aot_exe = exe
        # the whole resolve wall (deserialize on a hit, trace+XLA cold) is
        # a compile event; classification below splits hit from miss
        self._note_compile(sig_args, time.perf_counter() - t0, wall0,
                           sig=sig, cache_key=key)
        return out

    def _resync_cache(self):
        n = self._cache_size()
        if n is not None:
            with self._acct_lock:
                self._cache_seen = n

    def _account(self, args, out, t0):
        dt = time.perf_counter() - t0
        compiled = False
        with self._acct_lock:
            n = self._cache_size()
            if n is not None:
                # growth = this call (or one it blocked on) compiled; a
                # SHRINK (jax.clear_caches()/eviction) is not a compile —
                # resync either way so the next delta is measured from here
                if n > self._cache_seen:
                    compiled = True
                self._cache_seen = n
            else:  # degraded mode: track signatures ourselves
                if self._own_sigs is None:
                    self._own_sigs = set()
                sig = _signature(args)
                if sig not in self._own_sigs:
                    self._own_sigs.add(sig)
                    compiled = True
        if compiled:
            self._note_compile(args, dt, time.time() - dt)
        else:
            rec = self._rec()
            with rec.lock:
                rec.run_count += 1
                rec.run_seconds += dt
        return out

    def _note_compile(self, args, dt, wall0, sig=None, cache_key=None):
        rec = self._rec()
        if sig is None:
            try:
                sig = _signature(args)
            except Exception:  # never let accounting break dispatch
                sig = ()
        nbytes = _arg_nbytes(sig)
        prev = None
        with rec.lock:
            rec.compile_count += 1
            rec.compile_seconds += dt
            rec.arg_bytes = max(rec.arg_bytes, nbytes)
            now = wall0 + dt
            if rec.first_compile_ts is None:
                rec.first_compile_ts = now
            rec.last_compile_ts = now
            prev = rec.signatures.get(self._graph_key)
            rec.signatures[self._graph_key] = sig
        # persistent-cache classification: was this "compile" wall a cold
        # XLA compile or a disk hit underneath? (compile.cache_hits vs
        # compile.cache_misses — what tools/compile_report.py's warm-vs-
        # cold comparison and the "zero cold compiles" gate read)
        cached = None
        cls = None
        if self._cache_identity is not None:
            from . import compile_cache as _cc

            if _cc.enabled():
                try:
                    if cache_key is None:
                        cache_key = _cc.make_key(rec.name,
                                                 self._cache_identity, sig)
                    cls = _cc.classify_compile(rec.name, cache_key, dt)
                except Exception:
                    telemetry.counter("compile.cache_errors").inc()
                if cls is not None:
                    cached = (cls == "hit")
        # always-on metrics + the chrome-trace compile lane
        telemetry.counter("compile.count", program=rec.name).inc()
        telemetry.histogram("compile.seconds", program=rec.name).observe(dt)
        _emit_compile_span("compile[%s]" % rec.name, wall0, dt,
                           {"program": rec.name, "site": rec.site})
        ev = {"program": rec.name, "site": rec.site,
              "seconds": round(dt, 6), "count": rec.compile_count,
              "arg_bytes": nbytes}
        if cached is not None:
            ev["cached"] = cached
        telemetry.event("compile", **ev)
        peak = _backend_peak_bytes()
        if peak is not None:
            with rec.lock:
                rec.peak_bytes = peak
        if prev is not None:
            # ANY compile after the graph's first is a recompile — including
            # prev == sig, where the shapes are identical and what moved is
            # the placement (device/sharding), the one axis a shape-level
            # signature cannot see (diff_signatures labels it `placement`)
            self._note_recompile(prev, sig, dt)

    def _note_recompile(self, prev, sig, dt):
        rec = self._rec()
        cause, detail = diff_signatures(prev, sig)
        with rec.lock:
            rec.recompile_count += 1
        telemetry.counter("compile.recompile", program=rec.name,
                          cause=cause).inc()
        entry = {"ts": time.time(), "program": rec.name, "site": rec.site,
                 "cause": cause, "seconds": round(dt, 6)}
        entry.update(detail)
        with _lock:
            _recompiles.append(entry)
            if len(_recompiles) > _MAX_RECOMPILE_LOG:
                del _recompiles[:len(_recompiles) - _MAX_RECOMPILE_LOG]
        telemetry.event("compile.recompile", **entry)
        # imperative op kernels retrace at every new shape by design —
        # routine, so keep them off the warning stream; a STEP program
        # recompiling is the thing this module exists to make loud
        _log.log(
            logging.DEBUG if rec.name.startswith("op.")
            else logging.WARNING,
            "compile: program %r recompiled (%s%s) at %s — %.2fs",
            rec.name, cause,
            ", arg %s %s->%s" % (detail.get("arg"), detail.get("old_shape"),
                                 detail.get("new_shape"))
            if detail.get("arg") else "",
            rec.site or "<unknown site>", dt)


def jit(fn, program, site=None, graph_key=None, aot=False, cache_key=None,
        **jit_kwargs):
    """The registry's ``jax.jit``: every runtime jit site routes through
    here (enforced by the ``untracked-jit`` fwlint rule).

    ``program`` names the logical program (low-cardinality — it labels the
    always-on ``compile.*`` metrics); ``site`` is the defining call site for
    attribution messages; ``graph_key`` (hashable) identifies the traced
    GRAPH across wrapper rebuilds — pass :func:`symbol_digest` output for
    symbol-derived programs so rebind/reshape compiles diff against the
    graph's previous signature. ``aot=True`` opts a single-signature site
    into the persistent cache's AOT executable lane; ``cache_key``
    overrides the cross-process disk-cache identity when ``graph_key``
    carries process-local parts (e.g. a per-engine nonce) — it must encode
    EVERYTHING that shapes the traced program beyond the input signature.
    Remaining kwargs go to ``jax.jit``.
    """
    return ObservedJit(fn, program, site=site, graph_key=graph_key,
                       aot=aot, cache_key=cache_key, **jit_kwargs)


def raw_jit(fn, program, site=None, **jit_kwargs):
    """A bare ``jax.jit`` object, registered but unwatched — for
    export/AOT-style consumers (``jax.export.export``) that need the
    PjitFunction itself and never dispatch through it. Pair with
    :func:`record_compile` around the export/lower call so the compile wall
    still lands in the registry."""
    import jax

    _record(program, site=site)
    return jax.jit(fn, **jit_kwargs)  # fwlint: disable=untracked-jit — the registry wrapper itself


class record_compile:
    """Context manager charging a block's wall time to ``program`` as a
    compile (export lowering, AOT warmup): counts/seconds/span, no
    signature tracking."""

    def __init__(self, program, site=None):
        self._rec = _record(program, site=site)

    def __enter__(self):
        self._wall0 = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self._t0
        rec = self._rec
        if exc_type is None:
            with rec.lock:
                rec.compile_count += 1
                rec.compile_seconds += dt
                now = self._wall0 + dt
                if rec.first_compile_ts is None:
                    rec.first_compile_ts = now
                rec.last_compile_ts = now
            telemetry.counter("compile.count", program=rec.name).inc()
            telemetry.histogram("compile.seconds",
                                program=rec.name).observe(dt)
            _emit_compile_span("compile[%s]" % rec.name, self._wall0, dt,
                               {"program": rec.name, "site": rec.site})
            telemetry.event("compile", program=rec.name, site=rec.site,
                            seconds=round(dt, 6), count=rec.compile_count)
        return False


# ---------------------------------------------------------------------------
# registry views
# ---------------------------------------------------------------------------


def program_table():
    """Every program's registry row (list of dicts, most compile-expensive
    first) — what the OOM dump, cluster snapshots, and
    ``tools/compile_report.py`` render."""
    with _lock:
        recs = list(_programs.values())
    rows = [r.as_dict() for r in recs]
    rows.sort(key=lambda r: -r["compile_seconds"])
    return rows


def recompile_log():
    """Chronological recompile attributions (bounded to the last 256 —
    ``_MAX_RECOMPILE_LOG``)."""
    with _lock:
        return list(_recompiles)


def summary(include_recompiles=True):
    """Compact compile summary: program count, total compile count/seconds,
    total run seconds, and recompile attributions — embedded in
    cluster-stats snapshots. ``include_recompiles=False``
    skips copying the bounded recompile log (periodic publishers that only
    want the counts pair it with :func:`last_recompile`)."""
    rows = program_table()
    out = {
        "programs": len(rows),
        "compile_count": sum(r["compile_count"] for r in rows),
        "compile_seconds": round(
            sum(r["compile_seconds"] for r in rows), 6),
        "run_seconds": round(sum(r["run_seconds"] for r in rows), 6),
        "recompile_count": sum(r["recompile_count"] for r in rows),
    }
    from . import compile_cache as _cc

    if _cc.enabled():
        out["cache_hits"] = telemetry.totals("compile.cache_hits")[1]
        out["cache_misses"] = telemetry.totals("compile.cache_misses")[1]
        out["cache_errors"] = telemetry.totals("compile.cache_errors")[1]
    if include_recompiles:
        out["recompiles"] = recompile_log()
    return out


def last_recompile():
    """The most recent recompile attribution, or None — the cheap read the
    per-interval cluster-stats publisher wants (no full-log copy)."""
    with _lock:
        return dict(_recompiles[-1]) if _recompiles else None


# ---------------------------------------------------------------------------
# device-memory accounting
# ---------------------------------------------------------------------------


def _backend_peak_bytes():
    """Max ``peak_bytes_in_use`` across local devices, or None when the
    backend exposes no stats (CPU)."""
    stats = _jax_memory_stats()
    peaks = [s.get("peak_bytes_in_use") for s in stats.values()
             if s.get("peak_bytes_in_use") is not None]
    return max(peaks) if peaks else None


def _jax_memory_stats():
    """{device_str: raw Device.memory_stats dict} for devices that expose
    one (TPU/GPU backends; CPU returns none). Never INITIALIZES jax: this
    runs inside every telemetry read (dump/scrape/stall dump), and a
    host-only process — a PS server with a telemetry sink — must not pay
    backend init (or grab a process-exclusive TPU) for a scrape."""
    # nothing of ours can be on a device before a program compiled or an
    # NDArray was made, and both are this package's own records
    from . import ndarray as nd

    with _lock:
        compiled = any(r.compile_count for r in _programs.values())
    if not compiled and not nd.live_arrays():
        return {}
    out = {}
    try:
        import jax

        for d in jax.local_devices():
            try:
                st = d.memory_stats()
            except Exception:
                st = None
            if st:
                out[str(d)] = dict(st)
    except Exception:  # fwlint: disable=swallowed-exception — stats probe: no backend / no devices means "no stats", the fallback accounting takes over
        pass
    return out


def live_ndarray_report(top=None):
    """The NDArray allocation registry's view of live device memory:
    ``{"by_device": {ctx: {"bytes": n, "arrays": k}}, "top": [...]}`` with
    the ``top`` largest live buffers (shape/dtype/context/bytes). Views are
    skipped — their base carries the buffer. This is the accounting
    fallback where the backend exposes no memory stats, and the "top live
    allocations" section of the OOM dump."""
    from . import ndarray as nd

    if top is None:
        top = _env_int("MXNET_OOM_DUMP_TOP", 10)
    by_dev = {}
    entries = []
    for arr in nd.live_arrays():
        try:
            nbytes = int(arr.data.nbytes)
            ctx = str(arr.context)
            shape = tuple(arr.shape)
            dtype = str(arr.dtype)
        except Exception:  # fwlint: disable=swallowed-exception — a buffer deleted/donated mid-walk has no bytes to report; skipping it is the report
            continue
        slot = by_dev.setdefault(ctx, {"bytes": 0, "arrays": 0})
        slot["bytes"] += nbytes
        slot["arrays"] += 1
        entries.append((nbytes, shape, dtype, ctx))
    entries.sort(key=lambda e: -e[0])
    return {
        "by_device": by_dev,
        "top": [{"bytes": n, "shape": list(s), "dtype": d, "context": c}
                for n, s, d, c in entries[:max(int(top), 0)]],
    }


def device_memory_stats():
    """Per-device live/peak bytes: ``{device: {"bytes_in_use", "peak_bytes",
    "source"}}`` — jax backend stats where available, NDArray-allocation
    accounting (live bytes only) as the fallback."""
    stats = _jax_memory_stats()
    if stats:
        return {
            dev: {"bytes_in_use": s.get("bytes_in_use"),
                  "peak_bytes": s.get("peak_bytes_in_use"),
                  "source": "jax"}
            for dev, s in stats.items()
        }
    rep = live_ndarray_report(top=0)
    return {
        dev: {"bytes_in_use": slot["bytes"], "peak_bytes": None,
              "source": "ndarray"}
        for dev, slot in rep["by_device"].items()
    }


def update_memory_gauges():
    """Refresh the ``device.bytes_in_use`` / ``device.peak_bytes`` gauges
    from the current accounting. Registered as a telemetry collector, so
    every ``dump()`` / Prometheus scrape / guard stall dump reads fresh
    values; cheap enough for on-demand use too."""
    for dev, s in device_memory_stats().items():
        if s["bytes_in_use"] is not None:
            telemetry.gauge("device.bytes_in_use", device=dev).set(
                s["bytes_in_use"])
        if s["peak_bytes"] is not None:
            telemetry.gauge("device.peak_bytes", device=dev).set(
                s["peak_bytes"])
    # cumulative run seconds per program, refreshed registry-side (the hot
    # path only bumps the plain record fields; gauges render at read time)
    for row in program_table():
        telemetry.gauge("compile.run_seconds",
                        program=row["program"]).set(row["run_seconds"])


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                "OOM ")


def is_oom_error(exc):
    """Whether ``exc`` is a device out-of-memory failure (XLA surfaces these
    as RESOURCE_EXHAUSTED ``XlaRuntimeError``s; the fault injector's
    synthetic OOM carries the same marker)."""
    msg = str(exc)
    return any(m in msg for m in _OOM_MARKERS)


def dump_oom_report(program, exc, logger=None):
    """The OOM post-mortem, logged BEFORE the error propagates: per-device
    memory stats, the top live NDArray allocations, and the program table —
    what was resident and who compiled it. Counted always-on
    (``device.oom_events``) and mirrored as a structured ``oom`` event."""
    logger = logger or _log
    if getattr(exc, "_mxt_oom_dumped", False):
        return  # already dumped at an inner boundary (ObservedJit catch)
    try:
        exc._mxt_oom_dumped = True
    except AttributeError:
        pass  # slotted/frozen exception: worst case is a duplicate dump
    telemetry.counter("device.oom_events", program=program).inc()
    try:
        mem = device_memory_stats()
        live = live_ndarray_report()
        table = program_table()
        logger.error(
            "OOM at program %r: %s\n"
            "device memory: %s\n"
            "top live allocations: %s\n"
            "program table (by compile seconds): %s",
            program, exc, mem, live["top"],
            [{k: r[k] for k in ("program", "compile_count",
                                "compile_seconds", "run_seconds",
                                "arg_bytes")} for r in table])
        telemetry.event("oom", program=program, error=str(exc)[:500],
                        device_memory=mem, top_allocations=live["top"],
                        programs=[{k: r[k] for k in
                                   ("program", "compile_count", "arg_bytes")}
                                  for r in table])
    except Exception:
        logger.exception("OOM forensics dump itself failed (the original "
                         "RESOURCE_EXHAUSTED error still propagates)")


class oom_guard:
    """Executor-boundary guard: runs the block, and if it dies of
    RESOURCE_EXHAUSTED, dumps the forensics report before re-raising.
    Also hosts the ``oom`` fault-injection point (``MXNET_FAULT_SPEC=
    "oom:"``) so the dump path is testable without a real device OOM."""

    __slots__ = ("_program",)

    def __init__(self, program):
        self._program = program

    def __enter__(self):
        from . import fault

        if fault.hit("oom") is not None:
            exc = MXNetError(
                "RESOURCE_EXHAUSTED: injected device out-of-memory "
                "(fault.py point 'oom') at program %r" % self._program)
            # the injected failure takes the same forensics path a real
            # RESOURCE_EXHAUSTED from the block would: dump, then raise
            dump_oom_report(self._program, exc)
            raise exc
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc is not None and is_oom_error(exc):
            dump_oom_report(self._program, exc)
        return False


telemetry.register_collector(update_memory_gauges)
