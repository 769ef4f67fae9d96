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


# ---------------------------------------------------------------------------
# python3 tools/kernel_ab.py --latent: the latent decode kernel against its
# XLA path over the block-size ladder, and the flash forward at two widths
# ---------------------------------------------------------------------------
def latent_case(bs, ctx, streams=128, heads=128, rank=512, rope=64,
                max_len=3072, seed=0):
    """One layer's latent pages for ``streams`` streams of ``ctx`` cached
    tokens in blocks of ``bs`` (distinct blocks, block 0 trash), and the
    absorbed queries: ``(qc, qr, c_pages, r_pages, tables, context_lens)``
    in bfloat16."""
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    per = -(-ctx // bs)
    n = streams * per + 1
    ks = jax.random.split(key, 4)
    bf = jnp.bfloat16
    c_pages = jax.random.normal(ks[0], (n, 1, bs, rank), bf)
    r_pages = jnp.pad(jax.random.normal(ks[1], (n, 1, bs, rope), bf),
                      ((0, 0), (0, 0), (0, 0), (0, 128 - rope)))
    qc = jax.random.normal(ks[2], (streams, heads, rank), bf)
    qr = jnp.pad(jax.random.normal(ks[3], (streams, heads, rope), bf),
                 ((0, 0), (0, 0), (0, 128 - rope)))
    tables = np.zeros((streams, max_len // bs), np.int32)
    tables[:, :per] = 1 + np.arange(streams * per).reshape(streams, per)
    return (qc, qr, c_pages, r_pages, jnp.asarray(tables),
            jnp.full((streams,), ctx, jnp.int32))


def latent_ladder(block_sizes=(64, 128, 256), contexts=(512, 2048),
                  iters=10, xla=True, fetch_rows=(None,)):
    """``latent_paged``'s kernel and its XLA path, chained (memory-bound):
    one JSON-able row a rung with microseconds a call, the call's bytes
    (``ctx`` rows of 1,280 B a stream) over the HBM peak's time, and the
    two paths' largest difference."""
    import functools

    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as A

    rows = []
    for bs in block_sizes:
        for ctx in contexts:
            qc, qr, cp, rp, bt, cl = latent_case(bs, ctx)

            def body(path, i, qr, cp, rp, bt, cl, qc):
                return path(qc * (1 + 1e-3 * i).astype(qc.dtype), qr, cp, rp,
                            bt, cl, sm_scale=0.135)

            row = {"block_size": bs, "ctx": ctx, "streams": qc.shape[0]}
            def at_rows(r):
                """The kernel traced with ``r`` rows a fetch (the module's
                constant, for as long as the call traces)."""
                def path(*a, **kw):
                    was, A._LATENT_FETCH_ROWS = A._LATENT_FETCH_ROWS, r
                    try:
                        return A._latent_pallas(*a, **kw)
                    finally:
                        A._LATENT_FETCH_ROWS = was
                return path

            paths = [("kernel", A._latent_pallas) if r is None
                     else ("kernel_%d" % r, at_rows(r)) for r in fetch_rows]
            if xla:
                paths.append(("xla", A.latent_paged_reference))
            for name, path in paths:
                us, _per = device_time_us_chained(
                    functools.partial(body, path), (qr, cp, rp, bt, cl, qc),
                    iters=iters)
                row[name + "_us"] = round(us, 1)
            nbytes = qc.shape[0] * ctx * 1280
            row["hbm_peak_us"] = round(nbytes / 819e9 * 1e6, 1)
            row["kernel_share_of_peak"] = round(
                row["hbm_peak_us"] / row[paths[0][0] + "_us"], 3)
            got = A._latent_pallas(qc, qr, cp, rp, bt, cl, sm_scale=0.135)
            want = A.latent_paged_reference(qc, qr, cp, rp, bt, cl,
                                            sm_scale=0.135)
            row["max_diff"] = float(jnp.max(jnp.abs(
                got.astype(jnp.float32) - want.astype(jnp.float32))))
            rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# python3 tools/kernel_ab.py --paged-lanes: the paged kernel on Phi-4's
# head-major pages, a K/V row's four query heads as four query lanes
# ---------------------------------------------------------------------------
def paged_lanes_case(ctx, streams=64, lanes=4, rows=10, bs=64, w=128, seed=0):
    """One layer's head-major pages ``(N, rows, bs, w)`` in bfloat16 for
    ``streams`` streams of ``ctx`` cached tokens (distinct blocks, block 0
    trash) and ``lanes`` query lanes a stream that share the context:
    ``(q, k_pages, v_pages, tables, context_lens)``."""
    import jax.numpy as jnp

    per = -(-ctx // bs)
    n = streams * per + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    bf = jnp.bfloat16
    kp, vp = (jax.random.normal(k, (n, rows, bs, w), bf) for k in ks[:2])
    q = jax.random.normal(ks[2], (streams, lanes, rows, w), bf)
    tables = 1 + np.arange(streams * per, dtype=np.int32).reshape(streams, per)
    return (q, kp, vp, jnp.asarray(tables),
            jnp.full((streams, lanes), ctx, jnp.int32))


def paged_lanes_ladder(contexts=(512, 1536, 2560), windows=(512, None),
                       iters=10):
    """The paged kernel at Phi-4's decode shapes, chained (memory-bound):
    one JSON-able row a rung with the kernel's microseconds a call and a
    live block, the block's bytes (K and V) over the HBM peak's time beside
    it, and the largest difference from the gather reference."""
    import functools

    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as A

    rows = []
    for ctx in contexts:
        q, kp, vp, bt, cl = paged_lanes_case(ctx)
        bs = kp.shape[2]
        block_bytes = 2 * kp[0].size * kp.dtype.itemsize
        for window in windows:
            kw = dict(sm_scale=0.125, window=window, head_major=True)

            def body(i, kp, vp, bt, cl, q):
                return A._paged_pallas_multi(
                    q * (1 + 1e-3 * i).astype(q.dtype), kp, vp, bt, cl, **kw)

            call_us, per = device_time_us_chained(body, (kp, vp, bt, cl, q),
                                                  iters=iters)
            kernel_us = max(per.values())    # the custom call, by far
            first = 0 if window is None else max(ctx - window, 0) // bs
            live = q.shape[0] * (-(-ctx // bs) - first)
            bound = block_bytes / 819e9 * 1e6
            got = A._paged_pallas_multi(q, kp, vp, bt, cl, **kw)
            want = A.paged_attention_multi_reference(q, kp, vp, bt, cl, **kw)
            rows.append({
                "ctx": ctx, "window": window, "streams": q.shape[0],
                "lanes": q.shape[1], "live_blocks": live,
                "block_bytes": block_bytes,
                "call_us": round(call_us, 1),
                "kernel_us": round(kernel_us, 1),
                "us_per_live_block": round(kernel_us / live, 4),
                "hbm_peak_us_per_block": round(bound, 4),
                "share_of_peak": round(bound / (kernel_us / live), 3),
                "max_diff": float(jnp.max(jnp.abs(
                    got.astype(jnp.float32) - want.astype(jnp.float32))))})
    return rows


def flash_two_widths(seq=2048, heads=128, dk=192, dv=128, iters=5):
    """The flash forward at latent attention's expanded heads (keys ``dk``,
    values ``dv``): microseconds a call, kernel and XLA scan."""
    import functools

    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as A

    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    bf = jnp.bfloat16
    q, k = (jax.random.normal(kk, (1, heads, seq, dk), bf) for kk in ks[:2])
    v = jax.random.normal(ks[2], (1, heads, seq, dv), bf)
    out = {"seq": seq, "heads": heads, "dk": dk, "dv": dv}
    for name, fn in (
            ("kernel", functools.partial(A._pallas_forward, causal=True,
                                         sm_scale=0.135)),
            ("xla", functools.partial(A._scan_forward, causal=True,
                                      sm_scale=0.135, block_k=256))):
        us, _per = device_time_us(lambda q, k, v, fn=fn: fn(q, k, v)[0],
                                  (q, k, v), iters=iters)
        out[name + "_us"] = round(us, 1)
    out["causal_flops"] = 2 * heads * seq * seq * (dk + dv) // 2
    return out


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--latent", action="store_true",
                    help="latent_paged against its XLA path, the ladder")
    ap.add_argument("--paged-lanes", action="store_true",
                    help="the paged kernel on Phi-4's head-major pages, four "
                         "query lanes a stream, against the bytes bound")
    ap.add_argument("--flash", action="store_true",
                    help="the flash forward at keys 192 / values 128")
    ap.add_argument("--no-xla", action="store_true")
    ap.add_argument("--block-sizes", default="64,128,256")
    ap.add_argument("--contexts", default="512,2048")
    ap.add_argument("--fetch-rows", default=None,
                    help="comma-separated rows a fetch to time the kernel "
                         "at (default: the kernel's own)")
    args = ap.parse_args(argv)
    if args.latent:
        ints = lambda t: tuple(int(v) for v in t.split(","))  # noqa: E731
        for row in latent_ladder(
                ints(args.block_sizes), ints(args.contexts),
                xla=not args.no_xla,
                fetch_rows=ints(args.fetch_rows) if args.fetch_rows
                else (None,)):
            print(json.dumps(dict(row, kab="latent_paged")), flush=True)
    if args.paged_lanes:
        for row in paged_lanes_ladder():
            print(json.dumps(dict(row, kab="paged_lanes")), flush=True)
    if args.flash:
        print(json.dumps(dict(flash_two_widths(), kab="flash_forward")),
              flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
