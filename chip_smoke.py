#!/usr/bin/env python3
"""Chip smoke: the two main paths, once, on the TPU, through their entry points.

    python3 chip_smoke.py [--seed N]        # one chip: train, then serve
    python3 chip_smoke.py --multichip       # four chips: data-parallel fit only

The device is checked FIRST: without a TPU the script fails within seconds.
Every phase prints one JSON line (``{"smoke": <phase>, ...}``); these are
smoke output — pass/fail evidence and set-up cost — never a rate or a
benchmark number. Nothing is caught to let a run carry on: a failed check
raises, the exit code is non-zero, and the LAST line of stdout is

    {"ok": false, "device": {"platform": ..., "kind": ..., "count": N}}

(``"ok": true`` only when every phase passed). Everything runs in this one
process — a chip belongs to one process — with the HTTP server on a thread.

* train: ResNet-50 through the unchanged user contract —
  ``Module(context=mx.tpu(0), compute_dtype=bfloat16)``,
  ``fit(kvstore="device", optimizer="sgd")``, one device-resident batch of
  32 repeated — on the fused path, with a falling finite loss.
* serve: ``ServingEngine`` at GPT-2-small widths, started the way
  ``tools/serve.py --warmup`` starts it (supervisor + HTTP handler), eight
  concurrent requests; two of them re-scored token by token against a
  cache-free fp32 forward; then the same requests on a ``spec_k=4`` engine,
  whose streams must equal the plain engine's.
* --multichip: ``fit`` over ``[mx.tpu(i) for i in range(4)]`` against the
  same global batch and seed on ``mx.tpu(0)`` alone.

Weights and data come from ``--seed``. The compile cache goes where
``mxnet_tpu.compile_cache.resolve_dir`` says (``JAX_COMPILATION_CACHE_DIR``,
else ``MXNET_COMPILE_CACHE_DIR``, else ``.compile_cache`` in the checkout); a
cache error fails the run.
"""
import argparse
import functools
import gc
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np

RESNET50 = dict(num_layers=50, num_classes=1000, image_shape="3,224,224")
# GPT-2 small, as published: 12 layers x 768 wide, 12 heads of 64, 1024
# positions, 50257-token vocabulary
GPT2_SMALL = dict(vocab=50257, num_layers=12, model_dim=768, num_heads=12,
                  ffn_dim=3072, max_len=1024, block_size=16)
# short prompts prefill through the XLA scan (buckets 16..64), long ones
# through the Pallas kernel (buckets 128..1024)
PROMPT_LENS = (5, 19, 47, 100, 130, 260, 420, 700)
MAX_NEW = 32
RESCORED = (0, 7)       # one scan-prefilled request, one Pallas-prefilled
SPEC_K = 4
POOL_LADDER = (2049, 1025, 513, 257)
LOGIT_RTOL = 1e-3       # "same token" band for near-tied random-weight logits
# bf16 steps from the same weights on one chip and on four differ by the
# order of reductions only; over ten steps the losses drifted 0.5% apart on
# the v5e (PR 21), so 2% is a fault, not rounding
MULTICHIP_LOSS_RTOL = 0.02


def emit(phase, **fields):
    print(json.dumps(dict(smoke=phase, **fields)), flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def cache_delta(before):
    from mxnet_tpu import compile_cache

    now = compile_cache.stats()
    return {k: now[k] - before[k] for k in ("hits", "misses", "errors")}


def compile_seconds():
    from mxnet_tpu import compileobs

    return compileobs.summary(include_recompiles=False)["compile_seconds"]


# --------------------------------------------------------------- train ----
class _ResidentIter:
    """One DEVICE-resident synthetic batch, reused every step for
    ``epoch_batches`` steps an epoch — the reference's own methodology
    (benchmark_score.py keeps its synthetic batch on the GPU). Input IO is
    not under test: a per-step host->device upload of the 19MB batch would
    measure the host link, not the framework."""

    def __init__(self, batch, data_shape, num_classes, epoch_batches, ctx=None,
                 seed=0):
        from mxnet_tpu import io as mx_io
        from mxnet_tpu import ndarray as nd

        rng = np.random.RandomState(seed)
        self._data = [nd.array(
            rng.rand(batch, *data_shape).astype(np.float32), ctx=ctx)]
        self._label = [nd.array(
            rng.randint(0, num_classes, (batch,)).astype(np.float32), ctx=ctx)]
        self.provide_data = [mx_io.DataDesc("data", (batch,) + data_shape)]
        self.provide_label = [mx_io.DataDesc("softmax_label", (batch,))]
        self.batch_size = batch
        self._epoch_batches = epoch_batches
        self._i = 0
        self._batch = mx_io.DataBatch(
            data=self._data, label=self._label, pad=0, index=None)

    def __iter__(self):
        return self

    def reset(self):
        self._i = 0

    def __next__(self):
        if self._i >= self._epoch_batches:
            raise StopIteration
        self._i += 1
        return self._batch

    next = __next__


def fit_resnet(contexts, batch, steps, seed, platform, model=RESNET50,
               seed_after_bind=False):
    """``steps`` of Module.fit over one resident batch; returns the module,
    the per-step losses, and the compile count after each step.

    Every executor takes a key from the global chain as ``bind`` makes it,
    so the weights ``fit`` then draws depend on the number of contexts.
    ``seed_after_bind`` binds first and seeds afterwards: two runs over
    different context lists then start from the same weights (``fit`` finds
    the module bound and says so)."""
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import compileobs, models

    net = models.resnet(**model)
    dshape = tuple(int(x) for x in model["image_shape"].split(","))
    mod = mx.mod.Module(net, context=contexts,
                        compute_dtype=np.dtype(jnp.bfloat16))
    it = _ResidentIter(batch, dshape, model["num_classes"],
                       epoch_batches=steps, ctx=contexts[0], seed=seed)
    labels = it._label[0].asnumpy().astype(np.int64)
    losses, compiles = [], []
    if seed_after_bind:
        mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mx.random.seed(seed)

    def on_batch(param):
        probs = mod.get_outputs()[0].asnumpy().astype(np.float64)
        picked = probs[np.arange(batch), labels]
        losses.append(float(-np.log(np.maximum(picked, 1e-30)).mean()))
        compiles.append(
            compileobs.summary(include_recompiles=False)["compile_count"])

    mod.fit(it, num_epoch=1, kvstore="device", optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                              "rescale_grad": 1.0 / batch},
            initializer=mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2),
            eval_metric=mx.metric.Accuracy(),
            batch_end_callback=[on_batch])
    check(mod._fused is not None, "Module.fit left the fused path")
    check(len(losses) == steps, "fit ran %d of %d steps" % (len(losses), steps))
    check(all(np.isfinite(losses)), "non-finite loss: %s" % losses)
    out = mod.get_outputs()[0].data
    params = mod._fused.state.params
    for arr in [out] + list(params.values()):
        check({d.platform for d in arr.devices()} == {platform},
              "array on %s, expected %s" % (arr.devices(), platform))
    return mod, losses, compiles


def train_phase(seed, platform="tpu", batch=32, steps=20, model=RESNET50):
    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache, telemetry

    cache0, t0, c0 = compile_cache.stats(), time.time(), compile_seconds()
    mod, losses, compiles = fit_resnet([mx.tpu(0)], batch, steps, seed,
                                       platform, model)
    check(losses[-1] < losses[0],
          "loss did not fall: %.4f -> %.4f" % (losses[0], losses[-1]))
    check(compiles[-1] == compiles[0],
          "compiled after the first step: %s" % compiles)
    for name in ("graphpass.fallbacks", "graphpass.errors"):
        check(telemetry.totals(name)[1] == 0, "%s > 0" % name)
    emit("train", fused=True, steps=steps, batch=batch,
         loss_first=losses[0], loss_last=losses[-1],
         param_devices=sorted(
             str(d) for d in next(iter(mod._fused.state.params.values()))
             .devices()),
         compile_seconds=round(compile_seconds() - c0, 3),
         wall_seconds=round(time.time() - t0, 3),
         cache=cache_delta(cache0))


# --------------------------------------------------------------- serve ----
def size_pool(model, max_batch, budget_bytes):
    """The largest pool of POOL_LADDER whose decode program (compiled here
    for its memory analysis) plus the pool of a self-drafting spec engine
    fits ``budget_bytes`` on the device."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serving import model as lm

    cfg = lm.ModelConfig(model["vocab"], model["num_layers"],
                         model["model_dim"], model["num_heads"],
                         model["ffn_dim"], model["max_len"])
    bs = model["block_size"]
    # the engine's own page format: sized against (H, D) rows the ladder
    # would plan for programs that copy the whole pool (PERF.md, PR 25)
    pages_of = cfg.cache_specs().full

    spec = jax.ShapeDtypeStruct
    params = {k: spec(v, jnp.float32)
              for k, v in lm.param_shapes(cfg).items()}
    ints = spec((max_batch,), jnp.int32)
    tables = spec((max_batch, cfg.max_len // bs), jnp.int32)
    tried = []
    for n in POOL_LADDER:
        pages = spec(pages_of.shape(n, bs)[0], jnp.float32)
        ma = jax.jit(functools.partial(lm.decode, cfg=cfg),
                     donate_argnums=(5, 6)).lower(
            params, ints, ints, tables, ints, pages, pages
        ).compile().memory_analysis()
        pool = 2 * int(np.prod(pages.shape)) * 4
        need = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes + pool)
        tried.append(dict(num_blocks=n, pool_bytes=pool,
                          decode_temp_bytes=ma.temp_size_in_bytes,
                          decode_argument_bytes=ma.argument_size_in_bytes,
                          need_bytes=need))
        if need <= budget_bytes:
            emit("serve.pool", budget_bytes=budget_bytes, chosen=n,
                 tried=tried)
            return n
    raise AssertionError("no pool of %s fits %d bytes: %s"
                         % (POOL_LADDER, budget_bytes, tried))


def reference_logits(params, tokens, n_last, num_layers, num_heads):
    """Cache-free full forward in fp32 — plain jax.numpy plus
    ``attention_reference`` — over one whole sequence. Returns the logits
    of the last ``n_last`` positions, (n_last, vocab)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import attention_reference

    hi = jax.lax.Precision.HIGHEST
    seq = tokens.shape[0]

    def norm(x, name):
        mean = x.mean(-1, keepdims=True)
        var = jnp.square(x - mean).mean(-1, keepdims=True)
        return ((x - mean) / jnp.sqrt(var + 1e-5)
                * params[name + "_gamma"][0] + params[name + "_beta"][0])

    def heads(t):   # (S, M) -> (1, H, S, hd)
        return t.reshape(seq, num_heads, -1).transpose(1, 0, 2)[None]

    x = params["embed_weight"][tokens] + params["pos_embed_weight"][0, :seq]
    for i in range(num_layers):
        p = "layer%d" % i
        qkv = jnp.dot(norm(x, p + "_ln1"), params[p + "_attn_in_weight"].T,
                      precision=hi)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        att = attention_reference(heads(q), heads(k), heads(v), causal=True)
        att = att[0].transpose(1, 0, 2).reshape(seq, -1)
        x = x + jnp.dot(att, params[p + "_attn_out_weight"].T, precision=hi)
        f = jnp.dot(norm(x, p + "_ln2"), params[p + "_ffn1_weight"].T,
                    precision=hi) + params[p + "_ffn1_bias"]
        x = x + jnp.dot(jnp.maximum(f, 0), params[p + "_ffn2_weight"].T,
                        precision=hi) + params[p + "_ffn2_bias"]
    x = norm(x, "final_ln")[seq - n_last:]
    return (jnp.dot(x, params["lm_head_weight"].T, precision=hi)
            + params["lm_head_bias"])


def serve_requests(model, prompts, max_new, num_blocks, max_batch, seed,
                   spec_k):
    """Start the server as tools/serve.py does (supervised engine, --warmup,
    HTTP handler on a thread), send every prompt at once, stop it. Returns
    (streams, engine params, facts for the phase line)."""
    from tools import serve

    args = serve.parse_args(
        ["--port", "0", "--warmup", "--seed", str(seed),
         "--num-blocks", str(num_blocks), "--max-batch", str(max_batch)]
        + [a for k in ("vocab", "num_layers", "model_dim", "num_heads",
                       "ffn_dim", "max_len", "block_size")
           for a in ("--" + k.replace("_", "-"), str(model[k]))])
    # serve.py's engines read speculation from the environment
    os.environ["MXNET_SERVING_SPEC_K"] = str(spec_k)
    t0 = time.time()
    sup = serve.build_supervisor(args)
    warmup_s = time.time() - t0
    stop = threading.Event()
    driver = threading.Thread(target=sup.run_loop, args=(stop,),
                              name="serving-engine-driver", daemon=True)
    driver.start()
    httpd = serve.make_server(sup, args.host, args.port, driver=driver)
    server = threading.Thread(target=httpd.serve_forever,
                              name="serving-http", daemon=True)
    server.start()
    url = "http://%s:%d/generate" % (args.host, httpd.server_address[1])
    replies = [None] * len(prompts)
    # loopback only: never through a proxy the environment may name
    opener = urllib.request.build_opener(urllib.request.ProxyHandler({}))

    def client(i):
        body = json.dumps({"tokens": prompts[i],
                           "max_new_tokens": max_new}).encode()
        with opener.open(urllib.request.Request(url, body),
                         timeout=600) as r:
            replies[i] = json.loads(r.read())

    clients = [threading.Thread(target=client, args=(i,))
               for i in range(len(prompts))]
    try:
        for c in clients:
            c.start()
        for c in clients:
            c.join(timeout=900)
        stats = sup.stats()
        params = sup.engine.params
    finally:
        stop.set()
        httpd.shutdown()
        httpd.server_close()
        driver.join(timeout=60)
        server.join(timeout=60)
    check(not driver.is_alive() and not server.is_alive(),
          "a server thread did not stop")
    # a 200 reply IS state FINISHED (serve.py answers every other terminal
    # state with 5xx, which urlopen raises on and leaves the slot None)
    check(all(r is not None and len(r["tokens"]) == max_new
              for r in replies),
          "not every request finished with %d tokens: %s"
          % (max_new, replies))
    check(stats["supervisor"]["restarts"] == 0,
          "supervisor restarted the engine: %s" % stats["supervisor"])
    facts = dict(finished=len(replies), warmup_seconds=round(warmup_s, 3),
                 restarts=stats["supervisor"]["restarts"],
                 steps=stats["steps"], preemptions=stats.get("preemptions"),
                 compiles=stats.get("compiles"))
    if spec_k:
        facts["spec"] = stats.get("spec")
    return [r["tokens"] for r in replies], params, facts


def serve_phase(seed, platform="tpu", model=GPT2_SMALL,
                prompt_lens=PROMPT_LENS, max_new=MAX_NEW, max_batch=32,
                budget_bytes=None):
    import jax

    from mxnet_tpu import compile_cache

    dev = jax.devices()[0]
    if budget_bytes is None:
        budget_bytes = int(0.8 * dev.memory_stats()["bytes_limit"])
    cache0, c0 = compile_cache.stats(), compile_seconds()
    num_blocks = size_pool(model, max_batch, budget_bytes)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, model["vocab"], n).tolist()
               for n in prompt_lens]

    plain, params, facts = serve_requests(
        model, prompts, max_new, num_blocks, max_batch, seed, spec_k=0)
    check({d.platform for d in params["embed_weight"].devices()}
          == {platform}, "serving weights not on %s" % platform)

    ref = jax.jit(functools.partial(
        reference_logits, num_layers=model["num_layers"],
        num_heads=model["num_heads"]), static_argnames=("n_last",))

    def ref_after(i, stream, n_last):
        """fp32 logits of the last n_last positions of request i's prompt
        plus ``stream``."""
        toks = np.asarray(prompts[i] + list(stream), np.int32)
        return np.asarray(ref(params, toks, n_last=n_last), np.float64)

    def same(logits, a, b):
        return abs(logits[a] - logits[b]) <= LOGIT_RTOL * abs(logits.max())

    rescored = []
    for i in RESCORED:
        # position len(prompt)-1+j of prompt+generated[:-1] scores token j
        logits = ref_after(i, plain[i][:-1], max_new)
        off = [j for j, t in enumerate(plain[i])
               if not same(logits[j], t, int(logits[j].argmax()))]
        check(not off, "request %d: tokens %s are not the fp32 reference's"
              % (i, off))
        rescored.append(dict(
            request=i, prompt_len=prompt_lens[i],
            argmax_matches=sum(int(logits[j].argmax()) == t
                               for j, t in enumerate(plain[i]))))
    emit("serve.plain", num_blocks=num_blocks, prompt_lens=list(prompt_lens),
         max_new=max_new, rescored=rescored,
         compile_seconds=round(compile_seconds() - c0, 3),
         cache=cache_delta(cache0), **facts)

    cache1, c1 = compile_cache.stats(), compile_seconds()
    del params
    gc.collect()    # the first engine's pool leaves the chip first
    spec, params, facts = serve_requests(
        model, prompts, max_new, num_blocks, max_batch, seed, spec_k=SPEC_K)
    near_ties = []
    for i, (a, b) in enumerate(zip(plain, spec)):
        if a == b:
            continue
        j = next(k for k in range(max_new) if a[k] != b[k])
        logits = ref_after(i, a[:j], 1)[0]
        check(same(logits, a[j], b[j]),
              "request %d: spec_k=%d stream leaves the plain one at token "
              "%d (%d vs %d, fp32 logits %r vs %r)"
              % (i, SPEC_K, j, a[j], b[j], logits[a[j]], logits[b[j]]))
        near_ties.append(dict(request=i, token=j, plain=a[j], spec=b[j],
                              logits=[logits[a[j]], logits[b[j]]]))
    emit("serve.spec", spec_k=SPEC_K,
         identical=len(plain) - len(near_ties), near_ties=near_ties,
         compile_seconds=round(compile_seconds() - c1, 3),
         cache=cache_delta(cache1), **facts)


# ----------------------------------------------------------- multichip ----
def multichip_phase(seed, platform="tpu", chips=4, batch=128, steps=10,
                    model=RESNET50):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache

    check(len(jax.devices()) == chips,
          "--multichip needs %d devices, jax sees %d"
          % (chips, len(jax.devices())))
    cache0, c0 = compile_cache.stats(), compile_seconds()
    mod, many, _ = fit_resnet([mx.tpu(i) for i in range(chips)], batch,
                              steps, seed, platform, model,
                              seed_after_bind=True)
    fused = mod._fused
    shardings = {
        "batch": fused.trainer.batch_sharding.device_set,
        "params": next(iter(fused.state.params.values())).sharding.device_set,
    }
    for name, devs in shardings.items():
        check(len(devs) == chips, "%s spans %d device(s): %s"
              % (name, len(devs), devs))
    # the step the fit just ran, lowered again from its own arguments
    st, tr = fused.state, fused.trainer
    inputs = {n: jax.ShapeDtypeStruct(s, np.float32,
                                      sharding=tr.batch_sharding)
              for n, s in fused._data_shapes + fused._label_shapes}
    hlo = tr._build_step().lower(
        st.params, st.auxs, st.states, inputs, tr._rng_cache,
        np.float32(0.05), np.int32(1)).compile().as_text()
    check("all-reduce" in hlo, "no all-reduce in the %d-chip step" % chips)
    del mod, fused, st, tr
    gc.collect()
    _, one, _ = fit_resnet([mx.tpu(0)], batch, steps, seed, platform, model,
                           seed_after_bind=True)
    gaps = [abs(a - b) / abs(b) for a, b in zip(many, one)]
    check(max(gaps) <= MULTICHIP_LOSS_RTOL,
          "losses on %d chips and on one differ by %.4f > %.4f: %s vs %s"
          % (chips, max(gaps), MULTICHIP_LOSS_RTOL, many, one))
    emit("multichip", chips=chips, batch=batch, steps=steps,
         sharding_devices={k: sorted(str(d) for d in v)
                           for k, v in shardings.items()},
         all_reduce_ops=hlo.count("all-reduce("),
         losses_many=many, losses_one=one, max_rel_gap=max(gaps),
         rtol=MULTICHIP_LOSS_RTOL,
         compile_seconds=round(compile_seconds() - c0, 3),
         cache=cache_delta(cache0))


# ---------------------------------------------------------------- main ----
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip data-parallel fit and the "
                         "one-chip fit it is compared with")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    ok = False
    try:
        check(device["platform"] == "tpu",
              "chip_smoke needs a TPU; jax.devices() = %s" % devices)
        from mxnet_tpu import _native, compile_cache, compileobs

        compile_cache.enable(entry_point=True)
        _native.get_lib()
        emit("start", device=device, seed=args.seed,
             native_runtime=_native.status(),
             compile_cache=compile_cache.stats())
        if args.multichip:
            multichip_phase(args.seed)
        else:
            train_phase(args.seed)
            serve_phase(args.seed)
        cache = compile_cache.stats()
        emit("end", compile=compileobs.summary(include_recompiles=False),
             last_recompile=compileobs.last_recompile(),
             compile_cache=cache)
        check(cache["errors"] == 0, "compile cache errors: %s" % cache)
        ok = True
    finally:
        print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
