"""Driver of kind ``serve``: ``ServingEngine`` behind ``tools/serve.py``'s
HTTP handler and ``EngineSupervisor``, on threads of this one process (a
chip belongs to one process), loaded over loopback HTTP by a child process
that never imports jax (``loadgen.py``).

Set-up: weights on the device from the seed, the engine with its pool, a
warm-up of the prefill buckets this mix's lengths can reach (a preempted
request replays prompt + output so far) and of every decode bucket, the
reference's one program, the server's threads, one priming request. The
window opens at the instant ``t0`` the harness hands the child.

End-to-end numbers are the CLIENT's: output tokens of the replies it received
inside the window; latency from the instant a request was due to the
instant its reply was complete. In an open loop every request due in the window is a
latency sample, also one answered while the queue drains after the window
(to leave those out would cut the tail off); one still unanswered when the
drain's bound is reached counts as failed. Requests a closed loop has in
flight when the window closes count as neither completed nor failed.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

from benchmark import harness, stats, traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reachable_buckets(mix, buckets):
    """The prefill buckets the mix's lengths can reach."""
    def bucket_for(n):
        return next(b for b in buckets if n <= b)

    lo = mix["prompt_len"]["min"]
    hi = min(mix["max_total"],
             mix["prompt_len"]["max"] + mix["output_len"]["max"])
    return [b for b in buckets if bucket_for(lo) <= b <= bucket_for(hi)]


def warm_engine(eng, prefill_buckets):
    """``ServingEngine.warmup`` for a subset of the prefill buckets: one
    throwaway dispatch per shape through all-trash block tables. (The
    program's own warm-up compiles all thirteen buckets; a subset needs a
    change to the program, PERF.md section 7.)"""
    cfg = eng.config
    with eng._lock:
        for S in prefill_buckets:
            toks = np.zeros((1, S), np.int32)
            table = np.zeros(S // cfg.block_size, np.int32)
            _t, _l, kp, vp = eng._prefill_fn(
                eng.params, toks, np.int32(1), table,
                eng.pool.k_pages, eng.pool.v_pages)
            eng.pool.k_pages, eng.pool.v_pages = kp, vp
        for B in cfg.decode_buckets():
            ints = np.zeros(B, np.int32)
            tables = np.zeros((B, eng._nb_max), np.int32)
            _t, _l, kp, vp = eng._decode_fn(
                eng.params, ints, ints, tables, np.ones(B, np.int32),
                eng.pool.k_pages, eng.pool.v_pages)
            eng.pool.k_pages, eng.pool.v_pages = kp, vp
        np.asarray(eng.pool.k_pages[0, 0, 0, 0, 0])   # wait for the last


def snapshot(sup):
    """What the per-layer readers compare across the window."""
    from mxnet_tpu import telemetry

    eng = sup.engine
    stats_ = sup.stats()
    eid = eng.engine_id
    return {
        "t": time.time(),
        "steps": stats_["steps"],
        "preemptions": stats_["preemptions"],
        "restarts": stats_["supervisor"]["restarts"],
        "phases": {ph: v["total_s"] for ph, v in stats_["phases"].items()},
        "queue_wait": telemetry.histogram(
            "serving.phase_seconds", engine=eid,
            phase="queue_wait").snapshot(),
        "decode_batch": telemetry.totals("serving.decode_batch"),
        "compile": harness.compile_totals(),
        "resilience": stats_["resilience"],
    }


def run(ctx):
    import jax

    from mxnet_tpu.serving import (EngineSupervisor, ServingConfig,
                                   ServingEngine)
    from tools import serve

    cfg, mix, say = ctx.config, ctx.mix, ctx.say
    m, e = cfg["model"], cfg["engine"]
    scfg = ServingConfig(
        vocab_size=m["vocab"], num_layers=m["num_layers"],
        model_dim=m["model_dim"], num_heads=m["num_heads"],
        ffn_dim=m["ffn_dim"], max_len=m["max_len"],
        block_size=e["block_size"], num_blocks=e["num_blocks"],
        max_batch=e["max_batch"], spec_k=e["spec_k"],
        kv_dtype=np.dtype(e["kv_dtype"]), prefix_cache=e["prefix_cache"],
        prefills_per_step=e["prefills_per_step"])
    t = time.time()
    params = ctx.config_mod.init_params(cfg, ctx.seed)
    jax.block_until_ready(params)
    t_weights = time.time() - t
    buckets = reachable_buckets(mix, scfg.prefill_buckets())

    def factory():
        eng = ServingEngine(scfg, arg_params=params, seed=ctx.seed)
        warm_engine(eng, buckets)
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
    score = ctx.config_mod.make_reference(cfg)
    score(eng.params, [1, 2, 3], [4, 5])       # the reference's own compile
    say("reference", compile_s=round(time.time() - t, 3))

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
        result = judge(ctx, sup, score, pool_tokens, tracer,
                       **window(ctx, sup, port, tracer))
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
    """One measured window: start the child, hand it t0, read the engine's
    counters as the window opens and closes, collect the child's report."""
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
        before = snapshot(sup)
        t0 = time.time() + 0.25
        child.stdin.write("%r\n" % t0)
        child.stdin.flush()
        trace_thread = None
        if tracer is not None:
            trace_thread = tracer.in_thread(
                t0, mix["trace_start_s"], mix["trace_seconds"])
        time.sleep(max(0.0, t0 + ctx.seconds - time.time()))
        after = snapshot(sup)
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
                before=before, after=after, final=snapshot(sup), t0=t0)


def prime(port, n_tokens):
    """One small request before the window: the first connection, the
    handler's imports."""
    opener = urllib.request.build_opener(urllib.request.ProxyHandler({}))
    body = json.dumps({"tokens": list(range(1, n_tokens + 1)),
                       "max_new_tokens": 2}).encode()
    url = "http://127.0.0.1:%d/generate" % port
    with opener.open(urllib.request.Request(url, body), timeout=300) as r:
        json.loads(r.read())


def judge(ctx, sup, score, pool_tokens, tracer, report, before, after,
          final, t0):
    cfg, mix, say = ctx.config, ctx.mix, ctx.say
    vocab, seconds = cfg["model"]["vocab"], ctx.seconds
    planned = traffic.plan(mix, ctx.seed, seconds, vocab)
    records = {r["i"]: r for r in report["requests"]}
    problems = []

    def check(cond, what):
        if not cond:
            problems.append(what)

    check(report["late_start_s"] == 0.0,
          "the load generator started %.3f s late" % report["late_start_s"])
    check(final["restarts"] == 0, "the supervisor restarted the engine")
    check(after["compile"][0] == before["compile"][0],
          "compiled inside the window: %s -> %s"
          % (before["compile"], after["compile"]))

    open_loop = mix["loop"] == "open"
    check(open_loop or len(records) < report["planned"],
          "the callers used up the plan's %d requests before the window "
          "closed: raise request_rate_cap" % report["planned"])
    # every planned request of an open loop is due; a closed loop attempts
    # what its callers sent
    attempted = len(planned) if open_loop else len(records)
    ok = [r for r in records.values() if r["status"] == "ok"]
    in_window = [r for r in ok if r["done"] <= seconds]
    failed = sum(r["status"] != "ok" for r in records.values())
    if open_loop:
        failed += sum(1 for p in planned if p["i"] not in records)
    bad_len = [r["i"] for r in ok if r["n_out"] != r["asked"]]
    bad_tok = [r["i"] for r in ok
               if any(not 0 <= t < vocab for t in r["tokens"])]
    check(not bad_len, "replies with another number of tokens than asked "
          "for: requests %s" % bad_len[:8])
    check(not bad_tok, "tokens outside the vocabulary in requests %s"
          % bad_tok[:8])
    check(len(ok) > 0, "no request was answered")

    # ---- two requests of the window against the plain reference --------
    rescored = []
    for want in mix.get("rescore", [{}, {}]):
        pick = next((r for r in sorted(ok, key=lambda r: r["i"])
                     if want.get("min_prompt", 0)
                     <= len(planned[r["i"]]["tokens"])
                     <= want.get("max_prompt", 1 << 30)
                     and r["i"] not in [x["request"] for x in rescored]),
                    None)
        if pick is None:
            check(False, "no answered request with a prompt in %s to "
                  "re-score" % (want,))
            continue
        prompt = planned[pick["i"]]["tokens"]
        off, matches = score(sup.engine.params, prompt, pick["tokens"])
        check(not off, "request %d (prompt %d): tokens %s are not the fp32 "
              "reference's" % (pick["i"], len(prompt), off[:8]))
        rescored.append(dict(request=pick["i"], prompt_len=len(prompt),
                             tokens=len(pick["tokens"]),
                             argmax_matches=matches, outside_band=len(off)))

    # ---- the client's numbers -------------------------------------------
    late = stats.lateness([r["due"] for r in records.values()],
                          [r["sent"] for r in records.values()])
    e2e = {"setup_s": t0 - ctx.t_start}
    tokens_in = sum(r["n_out"] for r in in_window)
    e2e["serve_out_tok_per_s"] = tokens_in / seconds
    lat_ms = [1e3 * (r["done"] - r["due"]) for r in ok]
    if lat_ms:
        e2e["req_latency_p50_ms"] = stats.percentile(lat_ms, 50)
    say("client", attempted=attempted, answered=len(ok),
        answered_in_window=len(in_window), failed=failed,
        tokens_in_replies_inside=tokens_in,
        latency_samples=len(lat_ms),
        samples_beyond_p95=stats.samples_beyond(len(lat_ms), 95),
        highest_percentile_with_ten_beyond=stats.highest_percentile(
            len(lat_ms)),
        latency_ms={p: stats.percentile(lat_ms, p)
                    for p in (50, 90, 95, 99)} if lat_ms else None,
        gen_late_ms={p: 1e3 * stats.percentile(late, p)
                     for p in (50, 95, 100)} if late else None,
        hung_threads=report["hung_threads"],
        drained_after_window=len(ok) - len(in_window),
        rescored=rescored, problems=problems,
        engine_steps=after["steps"] - before["steps"],
        preemptions=after["preemptions"] - before["preemptions"],
        resilience=final["resilience"])
    obs = None
    if ctx.trace:
        obs = {
            "kind": "serve", "chips": ctx.chips, "peak": ctx.peak,
            "window_s": seconds, "platform": ctx.platform,
            "config": cfg, "mix": mix,
            "replies": [r for r in ok if r["done"] <= seconds]
            if not open_loop else ok,
            "late_s": late, "before": before, "after": after,
            "pool_tokens": pool_tokens,
            "compile": {"setup_compile_s": before["compile"][1],
                        "compiles_in_window":
                            after["compile"][0] - before["compile"][0]},
            "trace": tracer.summary() if tracer.t_stop else None,
        }
    return {"correct": not problems, "attempted": attempted,
            "failed": failed, "e2e": e2e, "obs": obs}
