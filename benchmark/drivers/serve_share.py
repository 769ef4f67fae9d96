"""Driver of kind ``serve_share``: ``drivers/serve_arch.py``'s run for an
engine that holds ONE RANK'S SHARE of an expert-parallel model
(``model.experts_held`` of ``model.num_experts``) and caches latents.
Three differences, nothing else:

* "nothing dropped" is the share's own arithmetic (:func:`share_adds_up`).
  ``serve_arch`` holds ``pairs == experts_per_tok x layer_tokens``, which a
  share fails by design: this chip computes the pairs that fall on the
  experts it holds. What must hold exactly is that the ROUTER chose
  ``experts_per_tok`` experts for every live token of every expert layer
  (``routed_pairs``), that the pairs computed here are the held experts'
  loads summed, and that they are some and no more than were routed;
* ``obs["latent"]`` beside ``obs["moe"]``: the engine's
  ``stats()["latent"]`` (cached tokens the decode kernel read, lane-steps,
  blocks) at the window's two ends, for the latent kernel's roofline;
* the dense probe is given the ENGINE (the configuration module's
  ``make_probe``: prefilled rows, rows decoded side by side in as many lanes
  as the engine has, and the same again in the held pass, where this rank's
  experts carry the answer); three bands of the module's hold it (the
  larger first quartile, the median of all rows, the held pass's larger
  first quartile); the two re-scored requests' largest distances from the
  reference's choice are printed beside the band that judged them.

Everything else is ``drivers/serve.py``'s and ``serve_arch``'s own code,
imported; ``obs["kind"]`` stays ``"serve"``.
"""
import threading
import time
from unittest import mock

from benchmark import harness
from benchmark.drivers import serve_arch
from benchmark.drivers.serve import judge, prime, reachable_buckets


def counters(sup):
    """``(moe, latent)`` of the engine's ``stats()``; None for what a
    program does not have."""
    stats = sup.engine.stats()
    return stats.get("moe"), stats.get("latent")


def share_adds_up(cfg, moe):
    """None if the window's counters are a share's, exactly, else what is
    wrong."""
    if not moe["before"] or not moe["after"]:
        return "the engine reports no expert counters"
    if "routed_pairs" not in moe["after"]:
        return "the engine does not count the router's choices"
    k = cfg["model"]["experts_per_tok"]
    routed, pairs, tokens = (moe["after"][f] - moe["before"][f]
                             for f in ("routed_pairs", "pairs",
                                       "layer_tokens"))
    held = sum(sum(a) - sum(b) for a, b in zip(
        moe["after"]["tokens_per_expert"], moe["before"]["tokens_per_expert"]))
    if tokens <= 0 or routed != k * tokens:
        return ("the router made %d choices for %d tokens through the "
                "expert layers: not %d a token" % (routed, tokens, k))
    if pairs != held:
        return ("%d pairs computed, but the held experts' loads sum to %d"
                % (pairs, held))
    if not 0 < pairs <= routed:
        return "%d pairs computed here of %d routed" % (pairs, routed)
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

    gaps = []

    def score(_served, prompt, generated):
        # over the weights this driver drew, not the engine's copy; the
        # re-scored requests' largest distances go to the log beside the
        # band that judged them
        if len(generated) > 2:
            g = reference.gaps(params, prompt, generated)
            gaps.append({"prompt_len": len(prompt), "tokens": len(generated),
                         "worst": float(g.max()),
                         "second": float(sorted(g)[-2])})
        return reference(params, prompt, generated)

    score(None, [1, 2, 3], [4, 5])             # the reference's own compile
    logits = ctx.config_mod.make_probe(cfg)(params, eng, ctx.seed)
    bands = (("quartile", logits, ctx.config_mod.PROBE_RTOL,
              "the first quartile of the worse half"),
             ("median", logits, ctx.config_mod.PROBE_MEDIAN_RTOL,
              "the median"),
             ("quartile", logits["held"], ctx.config_mod.PROBE_HELD_RTOL,
              "the first quartile of the worse half, in the held pass,"))
    logits.update(band=bands[0][2], median_band=bands[1][2])
    logits["held"]["band"] = bands[2][2]
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
        moe, latent = seen.pop("moe"), seen.pop("latent")
        result = judge(ctx, sup, score, pool_tokens, tracer, **seen)
        say("moe", **{k: {f: v for f, v in c.items()
                          if f != "tokens_per_expert"}
                      for k, c in moe.items() if c})
        say("latent", **{k: c for k, c in latent.items() if c})
        say("rescored_gaps", band=ctx.config_mod.LOGIT_RTOL, requests=gaps)
        problems = [share_adds_up(cfg, moe)] + [
            "served logits are %.4f from the fp32 reference's at %s of %d "
            "rows (prefilled %.4f, decoded %.4f): over %g" % (
                read[what], where, read["rows"], read["prefill_quartile"],
                read["decode_quartile"], band)
            for what, read, band, where in bands if not read[what] <= band]
        for problem in filter(None, problems):
            result["correct"] = False
            say("correct", problem=problem)
        if result["obs"] is not None:
            result["obs"].update(moe=moe, latent=latent)
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
    """``serve_arch.window``, whose one read of the engine's counters at
    each end of the window is :func:`counters` here."""
    with mock.patch.object(serve_arch, "moe_counters", counters):
        seen = serve_arch.window(ctx, sup, port, tracer)
    ends = seen.pop("moe")
    for i, name in enumerate(("moe", "latent")):
        seen[name] = {end: both[i] for end, both in ends.items()}
    return seen
