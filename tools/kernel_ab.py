"""Kernel A/B harness: measure device time of a jitted fn via the profiler trace.

Wall-clock timing of a single kernel mostly measures dispatch, so both
helpers read per-kernel durations from a jax.profiler device trace
(TensorCore "XLA Ops" track).

Two patterns, with very different trust levels:

- `device_time_us(fn, args)` — N independent back-to-back calls of jit(fn).
  Good for COMPUTE-bound kernels. UNDER-REPORTS memory time: the runtime
  overlaps the next call's HBM prefetch with the current call's compute, so
  a memory-bound kernel's reads of constant inputs largely vanish from its
  measured duration.
- `device_time_us_chained(body_fn, args)` — iterations chained through a
  lax.fori_loop inside ONE executable; every HBM read stays on the clock.
  Use this for anything memory-bound (and perturb an operand with the loop
  index to defeat loop-invariant hoisting).
"""
import collections
import glob
import gzip
import json
import os
import shutil
import tempfile

import jax
import numpy as np


def _trace_events(outdir):
    paths = sorted(glob.glob(os.path.join(
        outdir, "plugins", "profile", "*", "*.trace.json.gz")))
    if not paths:
        raise RuntimeError("no trace under %s" % outdir)
    with gzip.open(paths[-1], "rt") as f:
        return json.load(f)["traceEvents"]


def device_kernel_us(events, track="XLA Ops"):
    pid_names = {}
    tid_names = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pid_names[ev["pid"]] = ev["args"].get("name", "")
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            tid_names[(ev["pid"], ev["tid"])] = ev["args"].get("name", "")
    dev = {p for p, n in pid_names.items() if "TPU" in n}
    totals = collections.Counter()
    for ev in events:
        if ev.get("ph") != "X" or ev.get("pid") not in dev:
            continue
        if tid_names.get((ev["pid"], ev["tid"]), "") != track:
            continue
        totals[ev["name"]] += ev.get("dur", 0.0)
    return totals


def is_envelope(name):
    """True for trace events that span other kernels (the jit module event,
    the Framework op, the while op wrapping a fori_loop) — counting them
    alongside their children double-counts device time."""
    return (name.startswith("jit_") or name.startswith("Framework")
            or name.startswith("while"))


def device_time_us(fn, args, iters=20, warmup=3, drop=None):
    """Total device kernel time per call of jit(fn)(*args), in microseconds.

    Returns (us_per_call, {kernel_name: us_per_call}). `drop` is an optional
    predicate on kernel names to exclude (e.g. input-convert kernels that a
    real pipeline would amortize).
    """
    from mxnet_tpu import compileobs

    jf = compileobs.jit(fn, "bench.kernel_ab",
                        site="tools/kernel_ab.py:device_time_us")
    out = jf(*args)
    for _ in range(warmup):
        out = jf(*args)
    jax.tree_util.tree_map(
        lambda x: np.asarray(x).ravel()[:1], out)  # fence
    tmp = tempfile.mkdtemp(prefix="kab_")
    try:
        with jax.profiler.trace(tmp):
            for _ in range(iters):
                out = jf(*args)
            jax.tree_util.tree_map(lambda x: np.asarray(x).ravel()[:1], out)
        totals = device_kernel_us(_trace_events(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    per = {}
    tot = 0.0
    for name, us in totals.items():
        if is_envelope(name):
            continue
        if drop and drop(name):
            continue
        per[name] = us / iters
        tot += us / iters
    return tot, dict(sorted(per.items(), key=lambda kv: -kv[1]))


def device_time_us_chained(body_fn, args, iters=30):
    """HONEST timing for memory-bound kernels: run `body_fn` inside a
    lax.fori_loop within ONE jit call and read per-kernel times from the
    device trace of that single call.

    `device_time_us` above calls the jitted fn back-to-back with constant
    inputs; the TPU runtime overlaps the next call's HBM prefetch with the
    current call's compute, so memory time is under-reported (measured: a
    dot whose operand reads alone need ~175us at peak bandwidth shows 46us).
    Chaining iterations inside one executable keeps every HBM read on the
    clock. `body_fn(i, *args)` must return something the loop can feed back
    as a data dependency; args[-1] is used as the carry.

        def body(i, x, g):            # perturb an operand with i to defeat
            return bwd(x, g * (1 + 1e-6 * i))   # loop-invariant hoisting
        us, kernels = device_time_us_chained(body, (x, g))
    """
    import jax.numpy as jnp
    from jax import lax

    def looped(*a):
        def body(i, carry):
            return body_fn(i, *a[:-1], carry)
        return lax.fori_loop(0, iters, body, a[-1])

    from mxnet_tpu import compileobs

    jf = compileobs.jit(looped, "bench.kernel_ab_loop",
                        site="tools/kernel_ab.py:device_time_us_looped")
    out = jf(*args)
    np.asarray(out).ravel()[0]
    tmp = tempfile.mkdtemp(prefix="kab_")
    try:
        with jax.profiler.trace(tmp):
            out = jf(*args)
            np.asarray(out).ravel()[0]
        totals = device_kernel_us(_trace_events(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    per = {n: us / iters for n, us in totals.items() if not is_envelope(n)}
    return sum(per.values()), dict(sorted(per.items(), key=lambda kv: -kv[1]))
