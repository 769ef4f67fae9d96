"""What every driver shares: the compile cache, device memory, and the
profiler window of a traced run. ``ctx`` is the run's context as ``run.py``
builds it: cell, config, config_mod, mix, mix_path, seed, seconds, trace,
devices, chips, platform, peak, out_dir, t_start, say."""
import os
import threading
import time


def enable_compile_cache(ctx):
    """The cache goes where the program's ``compile_cache.resolve_dir``
    says: ``JAX_COMPILATION_CACHE_DIR`` if set, else ``.compile_cache`` in
    the checkout — a fixed path, since the path is part of jax's key. The
    harness owns its process, so it lifts jax's own LRU bound
    (``JAX_COMPILATION_CACHE_MAX_SIZE``; the chip tool's machine sets
    192 MiB, which evicted the train step in PR 21) for this process:
    a cell's programs must all still be there for its next run."""
    import jax

    from mxnet_tpu import compile_cache

    jax.config.update("jax_compilation_cache_max_size", -1)
    if not compile_cache.enable(entry_point=True):
        raise RuntimeError("the compile cache could not be enabled at %s"
                           % compile_cache.resolve_dir(entry_point=True))
    ctx.say("compile_cache", dir=compile_cache.cache_dir(),
            **cache_bytes())


def cache_bytes():
    """Bytes on disk under the cache directory: the program's AOT
    artifacts (``aot/``), its markers (``meta/``), and jax's own entries
    (everything else, wherever ``resolve_dir`` put them)."""
    from mxnet_tpu import compile_cache

    root = compile_cache.cache_dir()
    out = {"bytes_aot": 0, "bytes_meta": 0, "bytes_jax": 0}
    if not root:
        return out
    for d, _dirs, files in os.walk(root):
        top = os.path.relpath(d, root).split(os.sep)[0]
        key = "bytes_" + (top if top in ("aot", "meta") else "jax")
        out[key] += sum(os.path.getsize(os.path.join(d, f)) for f in files)
    return out


def compile_totals():
    """(count, seconds) of every compile the program has recorded."""
    from mxnet_tpu import compileobs

    s = compileobs.summary(include_recompiles=False)
    return s["compile_count"], s["compile_seconds"]


def memory_peak_bytes(devices):
    """Peak bytes on the fullest chip, as the runtime reports them:
    ``peak_bytes_in_use`` (live arrays) plus ``peak_bytes_reserved``. On
    the v5e the two regions are disjoint and held at once: ``bytes_limit -
    bytes_in_use - bytes_reserved`` is the largest free block to within
    2.3% on one chip (8-10% on four), and ``bytes_reserved`` equalled its
    peak at the end of all 31 runs (my chip runs, PR 22). The two peaks
    need not coincide, so the sum is an upper estimate: the arrays had
    shrunk from their peak by 0.1-2.5% of the sum at the end of the
    one-chip runs and by 21-23% in the four-chip cell, whose arrays peak
    during set-up. Arrays and reservation as they stood at the end of a
    run (6.5 to 7.9 GB in the four cells) are a floor under it. What the
    reservation holds is the runtime's business: it is the step's planned
    temporaries to 1% in the ResNet cells (5.57 against 5.61 GB) and less
    than the plan in the serving cells (3.31 against 4.94 GB). 0 where the
    backend keeps no statistic, as the CPU does."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks)


class TraceWindow:
    """The profiler around a steady stretch of a traced run. The Python
    tracer stays off: it slows host code severalfold and the idle share it
    would show is not the program's."""

    def __init__(self, ctx):
        self.dir = os.path.join(ctx.out_dir, "trace")
        self.rehearsal = ctx.platform != "tpu"
        self.active = False
        self.t_start = self.t_stop = None

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active = True
        self.t_start = time.time()

    def stop(self):
        import jax

        self.t_stop = time.time()
        jax.profiler.stop_trace()
        self.active = False

    def summary(self, sample_s=0.25):
        """Reduce the trace, keep a small sample of it as plain data beside
        the run's other output (``trace_sample.json``: the first
        ``sample_s`` seconds; ``ops.json``: every op's own time), and drop
        the raw files, which are large."""
        import json
        import shutil

        from benchmark import trace_reduce

        path = trace_reduce.newest_xplane(self.dir)
        data = trace_reduce.load_xplane(path, rehearsal=self.rehearsal)
        out = trace_reduce.summarize(data)
        w0 = trace_reduce.trace_window(data["devices"])[0]
        cut = w0 + sample_s * 1e9
        sample = {kind: {k: [e for e in evs if e[1] < cut]
                         for k, evs in data[kind].items()}
                  for kind in ("devices", "host")}
        base = os.path.dirname(self.dir)
        with open(os.path.join(base, "trace_sample.json"), "w") as f:
            json.dump(sample, f)
        with open(os.path.join(base, "ops.json"), "w") as f:
            json.dump(sorted(out["op_seconds"].items(),
                             key=lambda kv: -kv[1]), f, indent=0)
        shutil.rmtree(self.dir, ignore_errors=True)
        return out

    def in_thread(self, t0, start_after_s, seconds):
        """Trace [t0 + start_after_s, + seconds) from a helper thread, for
        drivers whose window runs on other threads. Join the returned
        thread before reading the summary."""
        def body():
            time.sleep(max(0.0, t0 + start_after_s - time.time()))
            self.start()
            time.sleep(seconds)
            self.stop()

        th = threading.Thread(target=body, name="bench-trace", daemon=True)
        th.start()
        return th
