"""Driver of kind ``serve_arch``: ``drivers/serve.py``'s run for a served
architecture that the six GPT-2 keys of a configuration file cannot
describe. Two differences, nothing else:

* the ``ServingConfig`` comes from the configuration's own module,
  ``config_mod.serving_config(config)`` (``serve`` builds it from six fixed
  keys);
* the engine is warmed through the program's public
  ``ServingEngine.warmup(prefill_buckets=...)`` (``serve`` reaches into the
  engine's private step functions).

Everything else — server threads, load generator, priming request, the
client's numbers, the two re-scored requests — is ``drivers/serve.py``'s own
code, imported, and ``obs["kind"]`` stays ``"serve"``, so every reader of a
serving cell works unchanged. ``obs["before"]`` / ``obs["after"]`` are what
``serve.snapshot`` returns; ``obs["moe"]`` adds the engine's expert counters
(``stats()["moe"]``: pairs, layer_steps, layer_tokens, experts_touched and
the per-layer per-expert totals) read at the same two instants, which is why
the window is a copy of ``serve.window`` with that one read added.

``correct`` holds the run to two things more than ``serve.judge`` does.
"Nothing dropped", exactly: the pairs the programs counted in the window
are ``model.experts_per_tok`` of the configuration file times the tokens
the engine sent through the layers (:func:`nothing_dropped`); a server that
routes to fewer experts or caps an expert's load fails it by arithmetic.
And the served logits themselves, in set-up, once the engine is warm:
``ServingEngine.prefill_logits`` on prefixes of a seeded text against the
reference's rows (the configuration module's ``make_probe`` and its
``PROBE_RTOL``) — a dense comparison, where the band on served tokens
sees a wrong layer only at the rare positions whose two best tokens lie
close. Both references run over the weights this driver drew, not the
engine's copy of them.

A third served architecture adds a configuration module with
``serving_config`` (beside ``init_params`` and ``make_reference``) and a mix
whose ``"driver"`` is ``serve_arch`` — no new driver.
"""
import json
import os
import subprocess
import sys
import threading
import time

from benchmark import harness
from benchmark.drivers.serve import (HERE, judge, prime, reachable_buckets,
                                     snapshot)


def moe_counters(sup):
    """The expert counters of the engine's ``stats()``, or None for an
    engine that has none (a program from before them)."""
    return sup.engine.stats().get("moe")


def nothing_dropped(cfg, moe):
    """None if the window's ``pairs`` are exactly the configuration's
    experts per token times the live tokens the engine sent through the
    expert layers (and some were), else what is wrong."""
    k = cfg["model"].get("experts_per_tok", 0)
    if not k:
        return None
    if not moe["before"] or not moe["after"]:
        return "the engine reports no expert counters"
    pairs, tokens = (moe["after"][f] - moe["before"][f]
                     for f in ("pairs", "layer_tokens"))
    if tokens <= 0 or pairs != k * tokens:
        return ("%d token-expert pairs for %d tokens through the layers: "
                "not %d a token" % (pairs, tokens, k))
    return None


def run(ctx):
    import jax

    from mxnet_tpu.serving import EngineSupervisor, ServingEngine
    from tools import serve

    cfg, mix, say = ctx.config, ctx.mix, ctx.say
    scfg = ctx.config_mod.serving_config(cfg)
    t = time.time()
    params = ctx.config_mod.init_params(cfg, ctx.seed)
    jax.block_until_ready(params)
    t_weights = time.time() - t
    buckets = reachable_buckets(mix, scfg.prefill_buckets())

    def factory():
        eng = ServingEngine(scfg, arg_params=params, seed=ctx.seed)
        eng.warmup(prefill_buckets=buckets)
        jax.block_until_ready(eng.pool.k_pages)    # wait for the last
        return eng

    t = time.time()
    sup = EngineSupervisor(factory)
    t_warm = time.time() - t
    eng = sup.engine
    pool_tokens = eng.pool.num_usable * eng.pool.block_size
    say("engine", prefill_buckets=buckets,
        decode_buckets=scfg.decode_buckets(),
        pool_blocks=eng.pool.num_usable, pool_tokens=pool_tokens,
        pool_bytes=eng.pool.nbytes(), weights_s=round(t_weights, 3),
        warmup_s=round(t_warm, 3),
        compile_s=harness.compile_totals()[1])

    t = time.time()
    reference = ctx.config_mod.make_reference(cfg)

    def score(_served, prompt, generated):
        # over the weights this driver drew, not the engine's copy: what
        # the engine does to its weights (a narrower type, say) is then
        # part of what is compared. They are the same device arrays while
        # the engine keeps them as given, so this costs no memory
        return reference(params, prompt, generated)

    score(None, [1, 2, 3], [4, 5])             # the reference's own compile
    # served logits against the reference's, densely, while the engine is
    # warm and idle: what a band on served tokens is too coarse to see
    logits = ctx.config_mod.make_probe(cfg)(params, eng.prefill_logits,
                                            ctx.seed)
    logits["band"] = ctx.config_mod.PROBE_RTOL
    say("reference", compile_s=round(time.time() - t, 3), logits=logits)

    stop = threading.Event()
    driver = threading.Thread(target=sup.run_loop, args=(stop,),
                              name="serving-engine-driver", daemon=True)
    driver.start()
    httpd = serve.make_server(sup, "127.0.0.1", 0, driver=driver)
    server = threading.Thread(target=httpd.serve_forever,
                              name="serving-http", daemon=True)
    server.start()
    port = httpd.server_address[1]
    tracer = harness.TraceWindow(ctx) if ctx.trace else None
    result = None
    try:
        prime(port, min(mix["max_total"] - 2, 8))
        seen = window(ctx, sup, port, tracer)
        moe = seen.pop("moe")
        result = judge(ctx, sup, score, pool_tokens, tracer, **seen)
        say("moe", **{k: {f: v for f, v in c.items()
                          if f != "tokens_per_expert"}
                      for k, c in moe.items() if c})
        problems = [nothing_dropped(cfg, moe)]
        if not logits["quartile"] <= logits["band"]:
            problems.append(
                "served logits are %.4f from the fp32 reference's at the "
                "first quartile of %d rows: over %g" % (
                    logits["quartile"], logits["rows"], logits["band"]))
        for problem in filter(None, problems):
            result["correct"] = False
            say("correct", problem=problem)
        if result["obs"] is not None:
            result["obs"]["moe"] = moe
    finally:
        stop.set()
        httpd.shutdown()
        httpd.server_close()
        driver.join(timeout=60)
        server.join(timeout=60)
    if driver.is_alive() or server.is_alive():
        result["correct"] = False
        say("threads", problem="a server thread did not stop")
    return result


def window(ctx, sup, port, tracer):
    """``serve.window`` with the expert counters read beside each
    snapshot: start the child, hand it t0, read the engine's counters as
    the window opens and closes, collect the child's report."""
    mix = ctx.mix
    cmd = [sys.executable, os.path.join(HERE, "loadgen.py"),
           "--mix", ctx.mix_path, "--seed", str(ctx.seed),
           "--seconds", str(ctx.seconds),
           "--vocab", str(ctx.config["model"]["vocab"]), "--port", str(port)]
    env = {k: v for k, v in os.environ.items()
           if not k.lower().endswith("_proxy")}
    child = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                             stdout=subprocess.PIPE, env=env, text=True)
    try:
        if child.stdout.readline().strip() != "READY":
            raise RuntimeError("the load generator did not start")
        before, moe_before = snapshot(sup), moe_counters(sup)
        t0 = time.time() + 0.25
        child.stdin.write("%r\n" % t0)
        child.stdin.flush()
        trace_thread = None
        if tracer is not None:
            trace_thread = tracer.in_thread(
                t0, mix["trace_start_s"], mix["trace_seconds"])
        time.sleep(max(0.0, t0 + ctx.seconds - time.time()))
        after, moe_after = snapshot(sup), moe_counters(sup)
        out, _ = child.communicate(
            timeout=float(mix.get("drain_s", 60.0)) + 30.0)
        if trace_thread is not None:
            trace_thread.join(timeout=120)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if child.returncode != 0:
        raise RuntimeError("the load generator exited %d" % child.returncode)
    return dict(report=json.loads(out.strip().splitlines()[-1]),
                before=before, after=after, final=snapshot(sup), t0=t0,
                moe={"before": moe_before, "after": moe_after})
