"""Persistent cross-process compile cache (docs/compiler.md §cache).

Every process start — a serving replica, a BucketingModule bucket, an
elastic worker relaunched by ``tools/launch.py --elastic`` — used to pay
the full XLA compile wall because no compilation state survived the
process. This module makes compiled programs durable, keyed by the
identity compileobs already computes: ``(post-pass graph digest, input
signature, platform fingerprint)``.

Two layers, both rooted at the one directory :func:`resolve_dir` decides
(``JAX_COMPILATION_CACHE_DIR``, else ``MXNET_COMPILE_CACHE_DIR``, else —
for the entry points only — ``.compile_cache`` inside the checkout):

* **AOT artifacts** (``<dir>/aot/<key>``): serialized XLA executables via
  ``jax.experimental.serialize_executable`` — loaded by single-signature
  jit sites (the executor's fwd / fwd+bwd pair, every serving shape
  bucket) on their first dispatch, skipping trace AND compile entirely.
  Where jax doesn't expose executable serialization the layer degrades to
  the transparent one below (``compile.cache_errors`` counts the refusal,
  dispatch is untouched).
* **jax's own persistent compilation cache**, underneath everything else
  (``<dir>/jax``; where ``JAX_COMPILATION_CACHE_DIR`` names the directory
  jax reads it itself and this module sets none): multi-signature and
  imperative-op
  programs re-trace on a warm start but the XLA compile — the dominant
  cost — is a disk hit. The marker index (``<dir>/meta/<key>``) is how
  compileobs tells a warm disk hit from a cold compile:
  ``compile.cache_hits{program}`` vs ``compile.cache_misses{program}``.

Invalidation is by construction: the key includes the platform
fingerprint (jax/jaxlib version, backend, device kind, local device
count) and the post-pass graph digest, so a toolchain upgrade or a graph
edit simply misses. A corrupted or torn artifact deserializes to a cold
compile (``compile.cache_errors``, always-on) and is overwritten. Size is
bounded by ``MXNET_COMPILE_CACHE_MAX_MB`` (oldest-first eviction at
enable time, ``compile.cache_evictions``).

The whole module is inert until :func:`enable` runs — importing it (or
mxnet_tpu) with the env unset configures nothing and costs nothing.
"""
from __future__ import annotations

import hashlib
import logging
import os
import pickle
import threading
import time
import zlib

from . import telemetry
from .base import env_bool as _env_bool
from .base import env_int as _env_int
from .base import env_str as _env_str

__all__ = [
    "enable", "disable", "enabled", "aot_enabled", "cache_dir",
    "resolve_dir", "maybe_enable_from_env", "fingerprint", "make_key",
    "classify_compile", "save_executable", "load_executable",
    "prune", "stats", "ENV_DIR", "ENV_JAX_DIR", "DEFAULT_DIR",
]

_log = logging.getLogger(__name__)

ENV_DIR = "MXNET_COMPILE_CACHE_DIR"
ENV_JAX_DIR = "JAX_COMPILATION_CACHE_DIR"
# the entry points' default: a fixed path (the path is part of jax's cache
# key, so a directory that moves never hits), git-ignored, in the checkout
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".compile_cache")
_CACHE_FORMAT = 2  # bump to invalidate every existing entry

_lock = threading.Lock()
# race-ok: writes serialize under _lock; fast-path reads sample single
# dict slots (atomic under the GIL) and tolerate one stale configure()
_state = {"dir": None, "aot": False, "wired": False}
_fingerprint_cache = [None]


def resolve_dir(explicit=None, entry_point=False):
    """The one place the cache directory is decided.
    ``JAX_COMPILATION_CACHE_DIR`` wins over everything: jax keeps its own
    files there and ``aot/`` and ``meta/`` go beside them. Else an
    ``explicit`` argument (a ``--cache-dir`` flag), else
    ``MXNET_COMPILE_CACHE_DIR``, else :data:`DEFAULT_DIR` for an entry
    point (``chip_smoke.py``, ``benchmark/run.py``, ``tools/serve.py``,
    the children of ``tools/launch.py --elastic``) and None — cache off —
    for a library import."""
    return (_env_str(ENV_JAX_DIR) or explicit or _env_str(ENV_DIR)
            or (DEFAULT_DIR if entry_point else None))


def maybe_enable_from_env():
    """Enable the cache when the environment names a directory (called
    once at package import, before any jit site exists — jax's
    persistent-cache config must land before the first compile)."""
    return enable()


def enable(directory=None, aot=None, max_mb=None, wire_jax=True,
           entry_point=False):
    """Turn the cache on at ``resolve_dir(directory, entry_point)`` (created
    if absent; False when that names none). ``aot`` defaults from
    ``MXNET_COMPILE_CACHE_AOT`` (on), ``max_mb`` from
    ``MXNET_COMPILE_CACHE_MAX_MB`` (2048). ``wire_jax=False`` skips the
    jax persistent-cache config (unit tests exercising the artifact store
    without touching process-global jax state)."""
    directory = resolve_dir(directory, entry_point)
    if not directory:
        return False
    directory = os.path.abspath(directory)
    if aot is None:
        aot = _env_bool("MXNET_COMPILE_CACHE_AOT", True)
    if max_mb is None:
        max_mb = _env_int("MXNET_COMPILE_CACHE_MAX_MB", 2048)
    try:
        os.makedirs(os.path.join(directory, "aot"), exist_ok=True)
        os.makedirs(os.path.join(directory, "meta"), exist_ok=True)
    except OSError:
        telemetry.counter("compile.cache_errors").inc()
        _log.warning("compile cache: cannot create %s — cache disabled",
                     directory)
        return False
    with _lock:
        _state["dir"] = directory
        _state["aot"] = bool(aot)
    if max_mb and max_mb > 0:
        prune(max_mb)
    if wire_jax:
        _wire_jax_cache(directory)
    return True


def _wire_jax_cache(directory):
    """Put jax's own persistent compilation cache underneath ours, with
    the thresholds opened up (every program is cacheable — a 50ms
    executor program recompiled by 100 elastic relaunches is the same
    wall as one big one, and a marker must never call a program jax
    declined to store a hit). Where ``JAX_COMPILATION_CACHE_DIR`` chose
    the directory jax has read it already and no directory is set here."""
    import jax

    if not _env_str(ENV_JAX_DIR):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(directory, "jax"))
        _state["wired"] = True
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def disable():
    """Forget the cache (test isolation). jax's persistent-cache directory
    is reset too when this process set it."""
    with _lock:
        was_wired = _state["wired"]
        _state.update(dir=None, aot=False, wired=False)
    if was_wired:
        import jax

        jax.config.update("jax_compilation_cache_dir", None)


def enabled():
    return _state["dir"] is not None


def aot_enabled():
    return _state["dir"] is not None and _state["aot"]


def cache_dir():
    return _state["dir"]


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------


def _lowering_fingerprint():
    """Content hash of the framework code that shapes what a traced
    program COMPUTES for a given graph digest: the op lowerings, the
    executor/graphpass trace machinery, the serving model, the fused
    step. An upgrade that fixes an op's numerics without touching its
    name/attrs (so the graph digest is unchanged) must still miss —
    a long-lived cache dir outliving the install is the default for
    elastic jobs. One-time cost per process (~1MB read), only paid when
    the cache is enabled."""
    pkg = os.path.dirname(os.path.abspath(__file__))
    h = hashlib.sha1()
    files = []
    for rel in ("ops", "graphpass", "serving", "parallel"):
        d = os.path.join(pkg, rel)
        try:
            files.extend(os.path.join(d, f) for f in sorted(os.listdir(d))
                         if f.endswith(".py"))
        except OSError:  # fwlint: disable=swallowed-exception — a trimmed install without the optional subpackage simply contributes nothing to the hash
            continue
    files.extend(os.path.join(pkg, f) for f in ("executor.py", "placed.py"))
    for path in files:
        try:
            with open(path, "rb") as f:
                h.update(os.path.basename(path).encode())
                h.update(f.read())
        except OSError:  # fwlint: disable=swallowed-exception — a file vanishing mid-walk (reinstall race) yields a different hash, i.e. a safe miss
            continue
    return h.hexdigest()[:16]


def fingerprint():
    """The platform fingerprint baked into every key: an artifact compiled
    by a different jax/jaxlib, backend, device kind, device count,
    framework version, or op-lowering code must never load. Computed once
    per process (touches the backend — only called on compile/load
    events, never on the dispatch fast path)."""
    fp = _fingerprint_cache[0]
    if fp is None:
        import jax

        try:
            import jaxlib

            jaxlib_ver = getattr(jaxlib, "__version__", "?")
        except Exception:  # fwlint: disable=swallowed-exception — jaxlib is distributed without __version__ in some builds; the jax version still pins the toolchain
            jaxlib_ver = "?"
        try:
            devs = jax.local_devices()
            kind = devs[0].device_kind if devs else "none"
            ndev = len(devs)
        except Exception:
            kind, ndev = "none", 0
            telemetry.counter("compile.cache_errors").inc()
        try:
            from mxnet_tpu import __version__ as fw_ver
        except Exception:  # fwlint: disable=swallowed-exception — mid-package-import (__version__ not bound yet): the lowering hash still pins the code
            fw_ver = "?"
        fp = ("v%d|jax=%s|jaxlib=%s|backend=%s|device=%s|n=%d"
              "|mxt=%s|lowering=%s" % (
                  _CACHE_FORMAT, jax.__version__, jaxlib_ver,
                  jax.default_backend(), kind, ndev,
                  fw_ver, _lowering_fingerprint()))
        _fingerprint_cache[0] = fp
    return fp


def make_key(program, graph_digest, signature):
    """Stable cache key: sha1 over (fingerprint, program, graph digest,
    input signature). ``signature`` is compileobs's per-leaf
    (keypath, kind, shape, dtype) tuple; ``graph_digest`` any stable
    hashable describing the traced graph + static config (the executor
    passes its post-pass symbol digest plus compute-dtype/grad config,
    serving its model/bucket config)."""
    h = hashlib.sha1()
    h.update(fingerprint().encode())
    h.update(("|%s|%r|%r" % (program, graph_digest, signature)).encode())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# the marker index: cold-vs-warm classification for Layer-A programs
# ---------------------------------------------------------------------------


def classify_compile(program, key, seconds=None):
    """Called by compileobs when a compile event lands: ``"hit"`` when this
    key was compiled by a previous process (jax's persistent cache served
    the executable from disk underneath the event — the wall was
    trace + deserialize, not XLA), ``"miss"`` on a genuinely cold compile
    (the marker is written so the NEXT process classifies warm). Counted
    always-on: ``compile.cache_hits`` / ``compile.cache_misses``."""
    d = _state["dir"]
    if d is None:
        return None
    marker = os.path.join(d, "meta", key)
    try:
        if os.path.exists(marker):
            telemetry.counter("compile.cache_hits", program=program).inc()
            return "hit"
        tmp = marker + ".tmp.%d" % os.getpid()
        with open(tmp, "w") as f:
            f.write("%s %.3f %s\n" % (program, seconds or 0.0,
                                      time.strftime("%Y-%m-%dT%H:%M:%S")))
        os.replace(tmp, marker)
    except OSError:
        telemetry.counter("compile.cache_errors").inc()
    telemetry.counter("compile.cache_misses", program=program).inc()
    return "miss"


# ---------------------------------------------------------------------------
# the AOT artifact store
# ---------------------------------------------------------------------------


def _aot_path(key):
    return os.path.join(_state["dir"], "aot", key)


def save_executable(key, compiled, program="?"):
    """Serialize an AOT-compiled executable (``jit(f).lower().compile()``)
    under ``key``. Returns True on success; serialization being
    unsupported on this backend is an error-counted no-op, never a
    failure of the dispatch that triggered it."""
    if _state["dir"] is None:
        return False
    try:
        from jax.experimental import serialize_executable as _se

        payload, in_tree, out_tree = _se.serialize(compiled)
        # the devices it runs on ride along: loaded without them it would
        # span EVERY local device and refuse a one-device program's
        # arguments on a multi-device host
        device_ids = [d.id for d in
                      compiled.runtime_executable().local_devices()]
        # level 1: a TPU executable of a 12-layer serving bucket is
        # 23-99 MB raw and a quarter of that deflated, in under a second
        blob = zlib.compress(
            pickle.dumps((payload, in_tree, out_tree, device_ids),
                         protocol=pickle.HIGHEST_PROTOCOL), 1)
        path = _aot_path(key)
        tmp = path + ".tmp.%d" % os.getpid()
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
        return True
    except Exception:
        telemetry.counter("compile.cache_errors").inc()
        _log.warning("compile cache: AOT serialization failed for "
                     "program %r (falling back to the transparent layer)",
                     program, exc_info=True)
        return False


def load_executable(key, program="?"):
    """Deserialize the artifact stored under ``key`` into a callable
    executable, or None (absent — routine miss; corrupt/stale — counted
    ``compile.cache_errors``, the bad file is removed so the follow-up
    cold compile overwrites it)."""
    if _state["dir"] is None:
        return None
    path = _aot_path(key)
    if not os.path.exists(path):
        return None
    try:
        with open(path, "rb") as f:
            blob = f.read()
        from jax.experimental import serialize_executable as _se

        import jax

        payload, in_tree, out_tree, device_ids = pickle.loads(
            zlib.decompress(blob))
        by_id = {d.id: d for d in jax.local_devices()}
        return _se.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=[by_id[i] for i in device_ids])
    except Exception:
        telemetry.counter("compile.cache_errors").inc()
        _log.warning("compile cache: corrupt/stale AOT artifact for "
                     "program %r (key %s) — removed, compiling cold",
                     program, key[:12])
        try:
            os.unlink(path)
        except OSError:  # fwlint: disable=swallowed-exception — another process may have unlinked the same corrupt artifact first
            pass
        return None


# ---------------------------------------------------------------------------
# size bound + stats
# ---------------------------------------------------------------------------


def prune(max_mb):
    """Evict oldest-mtime files until the cache fits ``max_mb`` (the AOT
    store and jax's own cache files under the same root). Counted
    ``compile.cache_evictions``.

    The marker index is NOT a payload store and gets special handling:
    markers are ~60-byte classification records whose eviction would
    corrupt the hit/miss split (a missing marker reads as a cold
    compile), so they are only reaped last — and evicting an AOT
    artifact removes its paired marker, keeping key presence aligned
    with the executable it classifies."""
    d = _state["dir"]
    if d is None or not max_mb:
        return 0
    meta_dir = os.path.join(d, "meta")
    aot_dir = os.path.join(d, "aot")
    payloads, markers = [], []
    total = 0
    for root, _dirs, files in os.walk(d):
        for name in files:
            p = os.path.join(root, name)
            try:
                st = os.stat(p)
            except OSError:  # fwlint: disable=swallowed-exception — concurrent eviction/teardown: a vanished file needs no pruning
                continue
            (markers if root == meta_dir else payloads).append(
                (st.st_mtime, st.st_size, p))
            total += st.st_size
    budget = int(max_mb) * (1 << 20)
    evicted = 0
    if total > budget:
        payloads.sort()
        markers.sort()
        for _mtime, size, p in payloads + markers:
            if total <= budget:
                break
            try:
                os.unlink(p)
                total -= size
                evicted += 1
            except OSError:  # fwlint: disable=swallowed-exception — racing evictors: the other process freed the bytes for us
                continue
            if os.path.dirname(p) == aot_dir:
                try:
                    os.unlink(os.path.join(meta_dir, os.path.basename(p)))
                except OSError:  # fwlint: disable=swallowed-exception — no paired marker (Layer-A-only key) or a racing evictor took it
                    pass
    if evicted:
        telemetry.counter("compile.cache_evictions").inc(evicted)
        _log.info("compile cache: evicted %d entries to fit %d MB",
                  evicted, max_mb)
    return evicted


def stats():
    """One snapshot for bench records and ``/stats`` endpoints: state,
    artifact counts/bytes, and the process's hit/miss/error totals."""
    d = _state["dir"]
    out = {"enabled": d is not None, "dir": d,
           "aot": aot_enabled(),
           "hits": telemetry.totals("compile.cache_hits")[1],
           "misses": telemetry.totals("compile.cache_misses")[1],
           "errors": telemetry.totals("compile.cache_errors")[1]}
    if d is not None:
        n = nbytes = 0
        try:
            for name in os.listdir(os.path.join(d, "aot")):
                p = os.path.join(d, "aot", name)
                try:
                    nbytes += os.path.getsize(p)
                    n += 1
                except OSError:  # fwlint: disable=swallowed-exception — entry evicted mid-listing: the snapshot just counts what remains
                    continue
        except OSError:
            telemetry.counter("compile.cache_errors").inc()
        out["aot_artifacts"] = n
        out["aot_bytes"] = nbytes
    return out
