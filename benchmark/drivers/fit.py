"""Driver of kind ``fit``: ``Module.fit`` over one device-resident batch.

The unchanged user contract — ``Module(context=[mx.tpu(i)...],
compute_dtype=...)``, ``fit(kvstore=..., optimizer=...)`` with an
``eval_metric`` — on the fused path. One ``fit`` epoch: ``warmup_steps``
steps first (they compile, and count as set-up), then the window, which the
iterator ends at the first sub-window boundary past ``--seconds``.

The window is cut into sub-windows of ``steps_per_window`` steps. Each is
closed by a FENCE: a host fetch of the last step's per-example losses, which
cannot return before every chip has finished that step (bench.py's fence,
on a kilobyte instead of the whole output). ``train_img_per_s`` is the
MEDIAN sub-window, never the fastest.

A mix of this kind has: ``warmup_steps``, ``steps_per_window``,
``trace_start_s``, ``trace_seconds``.
"""
import math
import time

import numpy as np

from benchmark import flops as flops_mod
from benchmark import harness, stats


class ResidentIter:
    """One DEVICE-resident synthetic batch, reused every step (a copy of
    bench.py's ``_ResidentIter``, the reference's own methodology:
    benchmark_score.py keeps its batch on the GPU). The batch is made on
    the device from the seed. The iterator never ends by itself: the
    driver's callback sets ``stop`` at a sub-window boundary."""

    def __init__(self, shapes, num_classes, ctx, device, seed):
        import jax
        import jax.numpy as jnp

        from mxnet_tpu import io as mx_io

        self._ctx = ctx
        key = jax.random.PRNGKey(int(seed))
        with jax.default_device(device):
            kd, kl = jax.random.split(key)
            data = jax.random.uniform(kd, shapes["data"], jnp.float32)
            label = jax.random.randint(
                kl, shapes["softmax_label"], 0, num_classes).astype(
                    jnp.float32)
        self.provide_data = [mx_io.DataDesc("data", shapes["data"])]
        self.provide_label = [mx_io.DataDesc("softmax_label",
                                             shapes["softmax_label"])]
        self.batch_size = shapes["data"][0]
        self.stop = False
        self.place(data, label)

    def place(self, data, label):
        from mxnet_tpu import io as mx_io
        from mxnet_tpu import ndarray as nd

        self.data, self.label = data, label
        self._batch = mx_io.DataBatch(
            data=[nd.NDArray(data, ctx=self._ctx)],
            label=[nd.NDArray(label, ctx=self._ctx)], pad=0, index=None)

    def shard(self, sharding):
        """Lay the batch out as the fused step wants it, once: a batch that
        sits on the first chip would be scattered to the others on every
        step, which no job's input does."""
        import jax

        self.place(jax.device_put(self.data, sharding),
                   jax.device_put(self.label, sharding))

    def __iter__(self):
        return self

    def reset(self):
        pass

    def __next__(self):
        if self.stop:
            raise StopIteration
        return self._batch

    next = __next__


def run(ctx):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    cfg, mix, say = ctx.config, ctx.mix, ctx.say
    chips = ctx.chips
    # where set-up goes, as seconds since the process started
    setup_at = {"driver": time.time() - ctx.t_start}
    batch = int(cfg["batch_per_chip"]) * chips
    warm, per_window = int(mix["warmup_steps"]), int(mix["steps_per_window"])
    if ctx.trace:
        telemetry.enable()   # fit.data_wait_seconds; off in a --trace 0 run

    contexts = [mx.tpu(i) for i in range(chips)]
    net = ctx.config_mod.build_symbol(cfg)
    shapes = ctx.config_mod.input_shapes(cfg, batch)
    classes = ctx.config_mod.num_classes(cfg)
    mod = mx.mod.Module(net, context=contexts,
                        compute_dtype=np.dtype(cfg["compute_dtype"])
                        if cfg["compute_dtype"] != "float32" else None)
    it = ResidentIter(shapes, classes, contexts[0], ctx.devices[0], ctx.seed)
    mx.random.seed(ctx.seed)

    @jax.jit
    def example_losses(probs, labels):
        picked = jnp.take_along_axis(
            probs.astype(jnp.float32),
            labels.astype(jnp.int32)[:, None], axis=1)[:, 0]
        return -jnp.log(jnp.maximum(picked, 1e-30))

    state = dict(step=0, losses=[], fences=[], t_open=None, compiles0=None,
                 wait0=None, tracer=harness.TraceWindow(ctx) if ctx.trace
                 else None, traced=None, trace_from=None)

    def data_wait():
        return telemetry.totals("fit.data_wait_seconds")[1]

    def on_batch(param):
        state["step"] += 1
        n = state["step"]
        loss = example_losses(mod.get_outputs()[0].data, it.label)
        if n == 1:
            setup_at["first_step"] = time.time() - ctx.t_start
            state["first_loss"] = loss
            # the step has compiled; from here the batch lies as the step
            # shards it
            it.shard(mod._fused.trainer.batch_sharding)
        if n <= warm:
            if n == warm:
                np.asarray(loss)            # fence: set-up ends here
                state["t_open"] = time.time()
                state["fences"].append(state["t_open"])
                state["compiles0"] = harness.compile_totals()
                state["wait0"] = data_wait()
            return
        state["losses"].append(loss)
        k = n - warm
        if k % per_window:
            return
        np.asarray(loss)                    # fence: closes a sub-window
        now = time.time()
        state["fences"].append(now)
        tr = state["tracer"]
        if tr is not None:
            since = now - state["t_open"]
            if tr.active and now - tr.t_start >= mix["trace_seconds"]:
                tr.stop()
                state["traced"] = k - state["trace_from"]
            elif (not tr.active and state["traced"] is None
                  and since >= mix["trace_start_s"]):
                state["trace_from"] = k
                tr.start()
        if now - state["t_open"] >= ctx.seconds:
            it.stop = True

    opt = dict(cfg["optimizer_params"], rescale_grad=1.0 / batch)
    setup_at["fit_called"] = time.time() - ctx.t_start
    mod.fit(it, num_epoch=1, kvstore=cfg["kvstore"],
            optimizer=cfg["optimizer"], optimizer_params=opt,
            initializer=mx.init.Xavier(**cfg["initializer"]),
            eval_metric=mx.metric.Accuracy(),
            batch_end_callback=[on_batch])
    tr = state["tracer"]
    if tr is not None and tr.active:      # the window closed under it
        tr.stop()
        state["traced"] = len(state["losses"]) - state["trace_from"]

    # ---- outside the window: checks -----------------------------------
    compiles1 = harness.compile_totals()
    wait1 = data_wait()
    fences = state["fences"]
    window_s = fences[-1] - fences[0]
    steps = len(state["losses"])
    losses = [float(np.asarray(v, np.float64).mean())
              for v in state["losses"]]
    problems = []

    def check(cond, what):
        if not cond:
            problems.append(what)

    fused = mod._fused
    check(fused is not None, "Module.fit left the fused path")
    for name in ("graphpass.fallbacks", "graphpass.errors"):
        check(telemetry.totals(name)[1] == 0, "%s > 0" % name)
    check(compiles1[0] == state["compiles0"][0],
          "compiled inside the window: %s -> %s"
          % (state["compiles0"], compiles1))
    check(all(math.isfinite(v) for v in losses), "a loss is not finite")
    first = float(np.asarray(state["first_loss"], np.float64).mean())
    chance = math.log(classes)
    rtol = cfg.get("first_loss_rtol", 0.1)
    check(abs(first - chance) <= rtol * chance,
          "the first loss %.4f is not within %d%% of ln(%d) = %.4f"
          % (first, 100 * rtol, classes, chance))
    check(len(losses) >= 20, "only %d steps in the window" % len(losses))
    if losses:
        check(np.mean(losses[-10:]) < np.mean(losses[:10]),
              "the loss did not fall: first ten %.4f, last ten %.4f"
              % (np.mean(losses[:10]), np.mean(losses[-10:])))
    facts = dict(steps=steps, batch=batch, window_s=window_s,
                 loss_step1=first,
                 loss_first=losses[0] if losses else None,
                 loss_last=losses[-1] if losses else None,
                 loss_chance=chance)
    if fused is not None:
        params = fused.state.params
        out = mod.get_outputs()[0].data
        for arr in [out] + list(params.values()):
            check({d.platform for d in arr.devices()} == {ctx.platform},
                  "an array is on %s, not on %s"
                  % (arr.devices(), ctx.platform))
        if chips > 1:
            facts.update(check_across_chips(fused, chips, check))
    say("fit", problems=problems, **facts)

    img_per_s = stats.median_of_windows(fences, per_window * batch)
    e2e = {"train_img_per_s": img_per_s,
           "setup_s": state["t_open"] - ctx.t_start}
    obs = None
    if ctx.trace:
        macs = flops_mod.symbol_macs(net, **shapes) / batch
        obs = {
            "kind": "fit", "chips": chips, "peak": ctx.peak,
            "window_s": window_s, "platform": ctx.platform,
            "fit": {"img_per_s": img_per_s, "steps": steps, "batch": batch,
                    "data_wait_s": wait1 - state["wait0"],
                    "flops_per_img": flops_mod.train_flops(macs),
                    "traced_steps": state["traced"]},
            "compile": {"setup_compile_s": state["compiles0"][1],
                        "compiles_in_window":
                            compiles1[0] - state["compiles0"][0]},
            "trace": tr.summary() if state["traced"] else None,
        }
    say("window", sub_windows=len(fences) - 1,
        rates=stats.window_rates(fences, per_window * batch),
        setup_compile_s=state["compiles0"][1],
        setup_at_s=dict(setup_at, window=e2e["setup_s"]))
    return {"correct": not problems, "attempted": steps, "failed": 0,
            "e2e": e2e, "obs": obs}


def check_across_chips(fused, chips, check):
    """On several chips: the batch and the parameters span them all, the
    step's compiled text holds an all-reduce, and every parameter's
    replicas are bit-identical after the window."""
    import jax

    st, tr = fused.state, fused.trainer
    spans = {"batch": tr.batch_sharding.device_set,
             "params": next(iter(st.params.values())).sharding.device_set}
    for name, devs in spans.items():
        check(len(devs) == chips, "%s spans %d device(s)" % (name, len(devs)))
    # the step the fit just ran, lowered again from its own arguments (a
    # hit in the compile cache)
    inputs = {n: jax.ShapeDtypeStruct(s, np.float32,
                                      sharding=tr.batch_sharding)
              for n, s in fused._data_shapes + fused._label_shapes}
    hlo = tr._build_step().lower(
        st.params, st.auxs, st.states, inputs, tr._rng_cache,
        np.float32(0.05), np.int32(1)).compile().as_text()
    check("all-reduce" in hlo, "no all-reduce in the %d-chip step" % chips)
    differing = 0
    for arr in st.params.values():
        shards = [np.asarray(s.data) for s in arr.addressable_shards]
        if any(not np.array_equal(shards[0], s) for s in shards[1:]):
            differing += 1
    check(differing == 0,
          "%d parameters differ between their replicas" % differing)
    return dict(all_reduce_ops=hlo.count("all-reduce("),
                replicas_differing=differing)
