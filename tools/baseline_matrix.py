#!/usr/bin/env python
"""Measured evidence for the BASELINE.json config matrix (VERDICT round-3
item 4): SSD training throughput + overfit mAP, DCGAN training stability +
throughput, LSTM-LM perplexity-to-floor + fused-path scaling table.

Run on the TPU (each config also runs on CPU for CI smoke):

    python tools/baseline_matrix.py ssd|dcgan|lstm|all [--quick]

Emits one JSON line per measurement (the bench.py convention) and a
markdown block to paste into docs/perf.md. Reference counterparts:
example/ssd/train.py + evaluate.py, example/gan/dcgan.py,
example/rnn/lstm_bucketing.py.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import mxnet_tpu as mx  # noqa: E402


def _ctx():
    return mx.tpu() if mx.context.num_tpus() else mx.cpu()


def emit(metric, value, unit, extra=None):
    rec = {"metric": metric, "value": round(float(value), 3), "unit": unit}
    if extra:
        rec.update(extra)
    print(json.dumps(rec), flush=True)
    return rec


# ---------------------------------------------------------------- SSD ----
def synth_det_data(n, num_classes, seed=0, size=300):
    """Images with 1-3 axis-aligned colored rectangles; labels are the
    boxes. Classes are color-coded so the task is genuinely learnable."""
    rng = np.random.RandomState(seed)
    X = np.zeros((n, 3, size, size), np.float32)
    Y = -np.ones((n, 8, 5), np.float32)
    for i in range(n):
        X[i] += rng.rand(3, 1, 1) * 0.1  # background tint
        for j in range(rng.randint(1, 4)):
            cls = rng.randint(0, num_classes)
            x0, y0 = rng.rand(2) * 0.55 + 0.05
            w, h = 0.15 + rng.rand(2) * 0.25
            x1, y1 = min(x0 + w, 0.98), min(y0 + h, 0.98)
            px0, py0, px1, py1 = (np.array([x0, y0, x1, y1]) * size).astype(int)
            # class encoded in channel intensity pattern
            X[i, cls % 3, py0:py1, px0:px1] = 0.5 + 0.5 * ((cls // 3) % 2)
            X[i, (cls + 1) % 3, py0:py1, px0:px1] = 0.25
            Y[i, j] = [cls, x0, y0, x1, y1]
    return X, Y



def run_ssd(quick=False):
    from mxnet_tpu.models import ssd

    num_classes = 4
    batch = 8 if quick else 32
    n = 4 * batch
    epochs = 2 if quick else 30
    ctx = _ctx()
    X, Y = synth_det_data(n, num_classes)
    it = mx.io.NDArrayIter({"data": X}, {"label": Y}, batch,
                           label_name="label")

    net = ssd.get_symbol_train(num_classes=num_classes)
    mod = mx.mod.Module(net, label_names=["label"], context=ctx)

    # throughput: time post-warmup epochs of fit
    times = []

    def batch_cb(param):
        times.append(time.perf_counter())

    sys.path.insert(0, os.path.join(ROOT, "examples"))
    from train_ssd import MultiBoxMetric

    t0 = time.perf_counter()
    mod.fit(it, num_epoch=epochs, optimizer="sgd",
            optimizer_params={"learning_rate": 0.002, "momentum": 0.9,
                              "wd": 5e-4},
            initializer=mx.init.Xavier(), eval_metric=MultiBoxMetric(),
            batch_end_callback=[batch_cb], force_init=True)
    # drop the first epoch (compile) from the rate
    per_epoch = len(times) // epochs
    steady = times[per_epoch:]
    if len(steady) >= 2:
        rate = batch * (len(steady) - 1) / (steady[-1] - steady[0])
    else:
        rate = batch * len(times) / (time.perf_counter() - t0)
    emit("ssd300_train_imgs_per_sec", rate, "img/s",
         {"batch": batch, "device": str(ctx)})

    # mAP through MultiBoxDetection on the training set (overfit check),
    # scored by the framework metric (mx.metric.MApMetric, 11-point VOC07)
    det_net = ssd.get_symbol(num_classes=num_classes)
    det = mx.mod.Module(det_net, label_names=None, context=ctx)
    det.bind(data_shapes=[("data", (batch, 3, 300, 300))],
             for_training=False)
    arg, aux = mod.get_params()
    det.set_params(arg, aux, allow_missing=True)
    metric = mx.metric.MApMetric(ovp_thresh=0.5, voc07=True,
                                 score_thresh=0.1)
    it.reset()
    for b in it:
        det.forward(b, is_train=False)
        metric.update(b.label, det.get_outputs())
    mean_ap = metric.get()[1]
    emit("ssd300_overfit_mAP@0.5", mean_ap, "mAP",
         {"classes": num_classes, "epochs": epochs})
    return rate, mean_ap


def run_ssd_overfit(steps=3000, batch=16, n=32, lr=5e-4, log_every=200,
                    seed=0):
    """Device-resident SSD overfit: the optimization-budget leg the host-fed
    run (34 MB/batch upload per step) does not reach. Batches are staged on
    device ONCE and reused, the fused fit path runs one program per step with
    no per-step host traffic, and losses are fetched only every ``log_every``
    steps. Also emits the compute-bound training rate."""
    from mxnet_tpu.models import ssd

    num_classes = 4
    ctx = _ctx()
    X, Y = synth_det_data(n, num_classes, seed=seed)
    net = ssd.get_symbol_train(num_classes=num_classes)
    mod = mx.mod.Module(net, label_names=["label"], context=ctx)
    mod.bind(data_shapes=[("data", (batch, 3, 300, 300))],
             label_shapes=[("label", (batch, Y.shape[1], 5))])
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(kvstore="device", optimizer="adam",
                       optimizer_params={"learning_rate": lr})

    batches = [
        mx.io.DataBatch(
            data=[mx.nd.array(X[i:i + batch], ctx=ctx)],
            label=[mx.nd.array(Y[i:i + batch], ctx=ctx)])
        for i in range(0, n, batch)
    ]

    sys.path.insert(0, os.path.join(ROOT, "examples"))
    from train_ssd import MultiBoxMetric

    metric = MultiBoxMetric()
    t_start = time.perf_counter()
    steps_timed0 = 0
    trajectory = []
    for step in range(steps):
        b = batches[step % len(batches)]
        mod.forward(b, is_train=True)
        mod.backward()
        mod.update()
        if step == len(batches):  # compiles done after the first pass;
            # a small output fetch drains the async queue so the timed
            # window starts clean (bench.py methodology)
            metric.reset()
            mod.update_metric(metric, b.label)
            t_start = time.perf_counter()
            steps_timed0 = step
        if (step + 1) % log_every == 0 or step == steps - 1:
            metric.reset()
            mod.update_metric(metric, b.label)  # the only host fetch
            names, vals = metric.get()
            trajectory.append((step + 1, round(vals[0], 4), round(vals[1], 4)))
    dt = time.perf_counter() - t_start
    rate = batch * (steps - steps_timed0) / dt
    emit("ssd300_train_imgs_per_sec_resident", rate, "img/s",
         {"batch": batch, "device": str(ctx),
          "loss_trajectory_[step,ce,smoothl1]": trajectory[-6:],
          "note": "device-resident batches; the compute-bound rate"})
    arg, aux = mod.get_params()

    # mAP on the overfit set through MultiBoxDetection + MApMetric
    def score(ectx, data, labels):
        det_net = ssd.get_symbol(num_classes=num_classes)
        det = mx.mod.Module(det_net, label_names=None, context=ectx)
        det.bind(data_shapes=[("data", (batch, 3, 300, 300))],
                 for_training=False)
        det.set_params(arg, aux, allow_missing=True)
        metric = mx.metric.MApMetric(ovp_thresh=0.5, voc07=True,
                                     score_thresh=0.1)
        for i in range(0, n, batch):
            db = mx.io.DataBatch(
                data=[mx.nd.array(data[i:i + batch], ctx=ectx)],
                label=[mx.nd.array(labels[i:i + batch], ctx=ectx)])
            det.forward(db, is_train=False)
            metric.update(db.label, det.get_outputs())
        return metric.get()[1]

    mean_ap = score(ctx, X, Y)
    emit("ssd300_overfit_mAP@0.5_resident", mean_ap, "mAP",
         {"classes": num_classes, "steps": steps, "images": n, "lr": lr,
          "eval_device": str(ctx)})
    return rate, mean_ap, trajectory


# -------------------------------------------------------------- DCGAN ----
def run_dcgan(quick=False):
    from mxnet_tpu.models import make_discriminator, make_generator

    batch = 16 if quick else 64
    z_dim = 100
    steps = 10 if quick else 200
    ctx = _ctx()
    gen = make_generator(ngf=32, nc=1)
    dis = make_discriminator(ndf=32)

    gen_mod = mx.mod.Module(gen, data_names=("rand",), label_names=None,
                            context=ctx)
    gen_mod.bind(data_shapes=[("rand", (batch, z_dim, 1, 1))],
                 inputs_need_grad=True)
    gen_mod.init_params(initializer=mx.init.Normal(0.02))
    gen_mod.init_optimizer(optimizer="adam",
                           optimizer_params={"learning_rate": 2e-4,
                                             "beta1": 0.5})
    dis_mod = mx.mod.Module(dis, data_names=("data",),
                            label_names=("label",), context=ctx)
    dis_mod.bind(data_shapes=[("data", (batch, 1, 64, 64))],
                 label_shapes=[("label", (batch,))], inputs_need_grad=True)
    dis_mod.init_params(initializer=mx.init.Normal(0.02))
    dis_mod.init_optimizer(optimizer="adam",
                           optimizer_params={"learning_rate": 2e-4,
                                             "beta1": 0.5})

    # "real" data: blobs with structure (offline MNIST stand-in).
    # Precomputed pool so host-side datagen does not pollute the
    # device-throughput measurement (the reference feeds a decoded rec file)
    rng = np.random.RandomState(0)
    yy, xx = np.mgrid[:64, :64]
    pool = []  # staged on device ONCE: no per-step host->device upload
    for _ in range(8):
        x = np.zeros((batch, 1, 64, 64), np.float32)
        for i in range(batch):
            cx, cy = rng.randint(16, 48, 2)
            r = rng.randint(6, 16)
            x[i, 0] = (((xx - cx) ** 2 + (yy - cy) ** 2) < r * r) * 1.0
        pool.append(mx.nd.array(x * 2 - 1, ctx=ctx))

    def real_batch():
        return pool[rng.randint(len(pool))]

    def ce_dev(prob, positive):
        # discriminator head is LogisticRegressionOutput: (batch, 1) sigmoid.
        # Computed on device, fetched in one pass after the run — the loop
        # itself stays free of host syncs.
        p = prob.reshape((-1,))
        if not positive:
            p = 1.0 - p
        return mx.nd.mean(-mx.nd.log(mx.nd.maximum(p, 1e-8)))

    # loss readout every 10th step, FETCHED immediately: a sparse host
    # sync is both the loss curve and the pacing
    loss_every = 10
    d_losses, g_losses = [], []
    t_start = None
    ones = mx.nd.ones((batch,), ctx=ctx)
    zeros = mx.nd.zeros((batch,), ctx=ctx)
    for step in range(steps):
        if step == 2:
            mx.nd.waitall()
            t_start = time.perf_counter()  # after compiles
        z = mx.nd.array(rng.randn(batch, z_dim, 1, 1), ctx=ctx)
        gen_mod.forward(mx.io.DataBatch(data=[z], label=[]), is_train=True)
        fake = gen_mod.get_outputs()[0]
        real = real_batch()

        # D on real
        dis_mod.forward(mx.io.DataBatch(data=[real], label=[ones]),
                        is_train=True)
        want_loss = step % loss_every == 0 or step == steps - 1
        d_real = ce_dev(dis_mod.get_outputs()[0], True) if want_loss else None
        dis_mod.backward()
        grads_real = [[g.copy() if g is not None else None for g in gl]
                      for gl in dis_mod._exec_group.grad_arrays]
        # D on fake
        dis_mod.forward(mx.io.DataBatch(data=[fake], label=[zeros]),
                        is_train=True)
        d_fake = ce_dev(dis_mod.get_outputs()[0], False) if want_loss else None
        dis_mod.backward()
        for gl, rl in zip(dis_mod._exec_group.grad_arrays, grads_real):
            for g, r in zip(gl, rl):
                if g is not None:
                    g += r
        dis_mod.update()
        if want_loss:
            d_losses.append(float((0.5 * (d_real + d_fake)).asnumpy()))

        # G step: D(fake) toward "real"
        dis_mod.forward(mx.io.DataBatch(data=[fake], label=[ones]),
                        is_train=True)
        if want_loss:
            g_losses.append(
                float(ce_dev(dis_mod.get_outputs()[0], True).asnumpy()))
        dis_mod.backward()
        gen_mod.backward([dis_mod.get_input_grads()[0]])
        gen_mod.update()
    mx.nd.waitall()  # the timed window covers completed device work
    dt = time.perf_counter() - t_start
    rate = batch * (steps - 2) / dt
    emit("dcgan_train_imgs_per_sec", rate, "img/s",
         {"batch": batch, "device": str(_ctx())})
    third = max(len(d_losses) // 3, 1)
    emit("dcgan_d_loss_final_third", float(np.mean(d_losses[-third:])),
         "ce", {"first_third": round(float(np.mean(d_losses[:third])), 3)})
    emit("dcgan_g_loss_final_third", float(np.mean(g_losses[-third:])),
         "ce", {"first_third": round(float(np.mean(g_losses[:third])), 3)})
    # stability: no NaNs, D not collapsed to 0 (G dead) or ln2-forever
    assert np.isfinite(d_losses).all() and np.isfinite(g_losses).all()
    return rate, d_losses, g_losses


def run_dcgan_fused(quick=False, steps=None, loss_every=10):
    """The fused opt-in (VERDICT round-4 item 7): the WHOLE adversarial
    iteration — G forward, D grads on fake+real, D update, G grads through
    the UPDATED D, G update — as ONE jitted program over device-resident
    params/optimizer state (donated buffers) and a device-resident real
    pool. Per-step semantics mirror the host-orchestrated loop exactly
    (same grad sums, same aux chaining order real -> fake -> G-step, Adam
    per update); z is derived in-graph from the step counter. The host
    does one dispatch per step and fetches losses every `loss_every`."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.executor import build_graph_fn
    from mxnet_tpu.models import make_discriminator, make_generator
    from mxnet_tpu.parallel import fused_opt

    batch = 16 if quick else 64
    z_dim = 100
    if steps is None:
        steps = 10 if quick else 200
    if steps < 4:
        raise ValueError("steps must be >= 4 (timing starts after 2 "
                         "warmup/compile steps)")
    lr = 2e-4
    gen = make_generator(ngf=32, nc=1)
    dis = make_discriminator(ndf=32)
    g_fn, g_args, g_auxn = build_graph_fn(gen)
    d_fn, d_args, d_auxn = build_graph_fn(dis)
    g_pnames = [n for n in g_args if n != "rand"]
    d_pnames = [n for n in d_args if n not in ("data", "label")]

    # identical initialization to the host-orchestrated run: let the
    # Modules init (no forward -> no compile), then lift the arrays
    ctx = _ctx()
    gen_mod = mx.mod.Module(gen, data_names=("rand",), label_names=None,
                            context=ctx)
    gen_mod.bind(data_shapes=[("rand", (batch, z_dim, 1, 1))])
    gen_mod.init_params(initializer=mx.init.Normal(0.02))
    dis_mod = mx.mod.Module(dis, data_names=("data",),
                            label_names=("label",), context=ctx)
    dis_mod.bind(data_shapes=[("data", (batch, 1, 64, 64))],
                 label_shapes=[("label", (batch,))])
    dis_mod.init_params(initializer=mx.init.Normal(0.02))
    gp = {k: v.asnumpy() for k, v in gen_mod.get_params()[0].items()}
    ga = {k: v.asnumpy() for k, v in gen_mod.get_params()[1].items()}
    dp = {k: v.asnumpy() for k, v in dis_mod.get_params()[0].items()}
    da = {k: v.asnumpy() for k, v in dis_mod.get_params()[1].items()}

    opt = mx.optimizer.create("adam", learning_rate=lr, beta1=0.5)
    rule = fused_opt.make_rule(opt)
    gs = {n: rule.init_state(gp[n].shape, np.float32) for n in g_pnames}
    ds = {n: rule.init_state(dp[n].shape, np.float32) for n in d_pnames}

    def g_forward(gp_, ga_, z):
        args = [z if n == "rand" else gp_[n] for n in g_args]
        outs, new_aux = g_fn(args, [ga_[n] for n in g_auxn], None, True)
        return outs[0], dict(zip(g_auxn, new_aux))

    def d_forward(dp_, da_, x, label):
        args = [x if n == "data" else label if n == "label" else dp_[n]
                for n in d_args]
        outs, new_aux = d_fn(args, [da_[n] for n in d_auxn], None, True)
        p = outs[0].reshape(-1)
        ce = -jnp.mean(label * jnp.log(jnp.maximum(p, 1e-8)) +
                       (1 - label) * jnp.log(jnp.maximum(1 - p, 1e-8)))
        return ce, dict(zip(d_auxn, new_aux))

    def step(gp_, gs_, ga_, dp_, ds_, da_, real, t):
        key = jax.random.fold_in(jax.random.PRNGKey(7), t)
        z = jax.random.normal(key, (batch, z_dim, 1, 1), jnp.float32)
        ones = jnp.ones((batch,), jnp.float32)
        zeros = jnp.zeros((batch,), jnp.float32)
        fake, ga1 = g_forward(gp_, ga_, z)
        fake_sg = jax.lax.stop_gradient(fake)

        def d_loss_fn(p):
            ce_r, da1 = d_forward(p, da_, real, ones)
            ce_f, da2 = d_forward(p, da1, fake_sg, zeros)
            return ce_r + ce_f, (ce_r, ce_f, da2)

        (_, (ce_r, ce_f, da2)), d_grads = jax.value_and_grad(
            d_loss_fn, has_aux=True)(dp_)
        dp1, ds1 = {}, {}
        for n in d_pnames:
            dp1[n], ds1[n] = rule.apply(dp_[n], d_grads[n], ds_[n],
                                        lr, 0.0, t)

        def g_loss_fn(p):
            fake2, _ = g_forward(p, ga_, z)  # same value; aux from 1st call
            ce, da3 = d_forward(dp1, da2, fake2, ones)
            return ce, da3

        (g_ce, da3), g_grads = jax.value_and_grad(
            g_loss_fn, has_aux=True)(gp_)
        gp1, gs1 = {}, {}
        for n in g_pnames:
            gp1[n], gs1[n] = rule.apply(gp_[n], g_grads[n], gs_[n],
                                        lr, 0.0, t)
        return gp1, gs1, ga1, dp1, ds1, da3, 0.5 * (ce_r + ce_f), g_ce

    from mxnet_tpu import compileobs

    step_jit = compileobs.jit(
        step, "bench.dcgan_fused",
        site="tools/baseline_matrix.py:dcgan_fused",
        donate_argnums=(0, 1, 2, 3, 4, 5))

    # the same device-resident real pool the host-orchestrated run builds
    rng = np.random.RandomState(0)
    yy, xx = np.mgrid[:64, :64]
    pool = []
    for _ in range(8):
        x = np.zeros((batch, 1, 64, 64), np.float32)
        for i in range(batch):
            cx, cy = rng.randint(16, 48, 2)
            r = rng.randint(6, 16)
            x[i, 0] = (((xx - cx) ** 2 + (yy - cy) ** 2) < r * r) * 1.0
        pool.append(jax.device_put(x * 2 - 1))

    d_losses, g_losses = [], []
    carry = (gp, gs, ga, dp, ds, da)
    # measurement-hygiene contract (docs/perf.md): the timed span after the
    # 2 warmup/compile steps splits into 5 synced windows, so every run
    # reports a median-of-5 with its min-max band (the 5 boundary syncs are
    # noise at 200 steps: dispatch is async, the sync drains ~1 step)
    n_windows = min(5, max(steps - 2, 1))
    bounds = [2 + ((steps - 2) * k) // n_windows for k in range(n_windows + 1)]
    marks = []
    for i in range(steps):
        if i in bounds:
            jax.block_until_ready(carry)
            marks.append(time.perf_counter())
        out = step_jit(*carry, pool[rng.randint(len(pool))],
                       np.int32(i + 1))
        carry = out[:6]
        if i % loss_every == 0 or i == steps - 1:
            d_losses.append(float(out[6]))
            g_losses.append(float(out[7]))
    jax.block_until_ready(carry)
    marks.append(time.perf_counter())
    window_rates = [
        batch * (b1 - b0) / (t1 - t0)
        for b0, b1, t0, t1 in zip(bounds, bounds[1:], marks, marks[1:])
        if t1 > t0 and b1 > b0
    ]
    rate = float(np.median(window_rates))
    emit("dcgan_fused_train_imgs_per_sec", rate, "img/s",
         {"batch": batch, "device": str(_ctx()), "loss_every": loss_every,
          "band_lo": round(min(window_rates), 1),
          "band_hi": round(max(window_rates), 1),
          "windows": len(window_rates)})
    third = max(len(d_losses) // 3, 1)
    emit("dcgan_fused_d_loss_final_third",
         float(np.mean(d_losses[-third:])), "ce",
         {"first_third": round(float(np.mean(d_losses[:third])), 3)})
    emit("dcgan_fused_g_loss_final_third",
         float(np.mean(g_losses[-third:])), "ce",
         {"first_third": round(float(np.mean(g_losses[:third])), 3)})
    assert np.isfinite(d_losses).all() and np.isfinite(g_losses).all()
    return rate, d_losses, g_losses


# ------------------------------------------------------------ LSTM-LM ----
def run_lstm(quick=False, batch=32, buckets=(8, 16, 24, 32), epochs=None,
             max_sentences=None):
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    from lstm_bucketing import stdlib_corpus

    if max_sentences is None:
        max_sentences = 1000 if quick else 4000
    sent, vocab = stdlib_corpus(vocab_size=5000,
                                max_sentences=max_sentences)
    it = mx.rnn.BucketSentenceIter(sent, batch, buckets=list(buckets))
    num_hidden, num_embed = 128, 128
    cell = mx.rnn.SequentialRNNCell()
    for i in range(2):
        cell.add(mx.rnn.LSTMCell(num_hidden=num_hidden,
                                 prefix="lstm_l%d_" % i))

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        embed = mx.sym.Embedding(data, input_dim=len(vocab),
                                 output_dim=num_embed, name="embed")
        outputs, _ = cell.unroll(seq_len, inputs=embed, merge_outputs=True)
        pred = mx.sym.Reshape(outputs, shape=(-1, num_hidden))
        pred = mx.sym.FullyConnected(pred, num_hidden=len(vocab),
                                     name="pred")
        label = mx.sym.Reshape(label, shape=(-1,))
        pred = mx.sym.SoftmaxOutput(pred, label=label, name="softmax")
        return pred, ("data",), ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen,
                                 default_bucket_key=it.default_bucket_key,
                                 context=_ctx())
    if epochs is None:
        epochs = 2 if quick else 10

    # everything through fit: the BucketingModule fused path trains every
    # bucket as one compiled program; the callback records the running
    # train perplexity and per-batch wall times (tokens/sec)
    records = []  # (epoch, ppl, t, tokens_in_batch)

    def cb(param):
        records.append((param.epoch, param.eval_metric.get()[1],
                        time.perf_counter()))

    mod.fit(it, num_epoch=epochs, optimizer="adam",
            optimizer_params={"learning_rate": 1e-3},
            initializer=mx.init.Xavier(),
            eval_metric=mx.metric.Perplexity(ignore_label=0),
            batch_end_callback=[cb], force_init=True)

    ppl_per_epoch = []
    for e in range(epochs):
        eps = [r for r in records if r[0] == e]
        if eps:
            ppl_per_epoch.append(float(eps[-1][1]))
    # steady-state PADDED tokens/sec from epochs > 0 (epoch 0 pays the
    # per-bucket compiles). Padded tokens per epoch counted from one host
    # pass over the iterator (what the device actually processes; raw
    # corpus length would both miss padding and count sentences the
    # bucketing drops)
    it.reset()
    epoch_tokens = sum(int(b.data[0].shape[0]) * int(b.data[0].shape[1])
                       for b in it)
    n_batches = len([r for r in records if r[0] == 0])
    avg_tokens = epoch_tokens / max(n_batches, 1)
    tok_rates = []
    for e in range(1, epochs):
        ts = [r[2] for r in records if r[0] == e]
        if len(ts) >= 2:
            tok_rates.append(avg_tokens * (len(ts) - 1) / (ts[-1] - ts[0]))
    emit("lstm_lm_perplexity_floor", ppl_per_epoch[-1], "ppl",
         {"epoch1": round(ppl_per_epoch[0], 1),
          "trajectory": [round(p, 1) for p in ppl_per_epoch]})
    if tok_rates:
        emit("lstm_lm_tokens_per_sec", float(np.median(tok_rates)), "tok/s",
             {"batch": batch, "buckets": list(buckets)})
    return ppl_per_epoch, tok_rates


def run_lstm_scaling(quick=False, repeats=5):
    """Fused-path win-threshold characterization: tokens/sec vs batch size
    and bucket count, so the fused path's win threshold is characterized
    rather than asserted. Every row is the MEDIAN OF `repeats` runs with the
    min/max band emitted alongside — host round-trip variance dominates
    small batches, so a single-shot number is not publishable."""
    rows = []
    combos = [(32, (16, 32)), (128, (16, 32)), (512, (16, 32)),
              (128, (8, 16, 24, 32))]
    if quick:
        combos = combos[:2]
        repeats = min(repeats, 2)
    for batch, buckets in combos:
        # the corpus must pack >=2 steady batches per bucket at this batch
        # size or the rate is unmeasurable (the round-4 512-row gap)
        per_run = []
        for _ in range(repeats):
            _, rates = run_lstm(quick=True, batch=batch, buckets=buckets,
                                epochs=2,
                                max_sentences=max(1000, batch * 12))
            per_run.append(float(np.median(rates)) if rates
                           else float("nan"))
        med = float(np.median(per_run))
        rows.append((batch, len(buckets), med))
        emit("lstm_scaling_tokens_per_sec", med, "tok/s",
             {"batch": batch, "n_buckets": len(buckets),
              "median_of": repeats,
              "min": round(float(np.min(per_run)), 1),
              "max": round(float(np.max(per_run)), 1)})
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("config", choices=["ssd", "ssd_overfit", "dcgan",
                                       "dcgan_fused", "lstm",
                                       "lstm_scaling", "all"])
    ap.add_argument("--quick", action="store_true",
                    help="tiny sizes for CI smoke")
    ap.add_argument("--steps", type=int, default=3000,
                    help="ssd_overfit optimization steps")
    ap.add_argument("--lr", type=float, default=5e-4,
                    help="ssd_overfit learning rate")
    a = ap.parse_args()
    if a.config in ("ssd", "all"):
        run_ssd(a.quick)
    if a.config == "ssd_overfit":
        if a.quick:
            run_ssd_overfit(steps=30, batch=4, n=8, log_every=10)
        else:
            run_ssd_overfit(steps=a.steps, lr=a.lr)
    if a.config in ("dcgan", "all"):
        run_dcgan(a.quick)
    if a.config in ("dcgan_fused", "all"):
        run_dcgan_fused(a.quick)
    if a.config in ("lstm", "all"):
        run_lstm(a.quick)
    if a.config in ("lstm_scaling", "all"):
        run_lstm_scaling(a.quick)
