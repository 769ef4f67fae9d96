#!/usr/bin/env python3
"""Wrong servers for a latent-attention, shared-expert configuration (and,
through ``--cell``, for a stack that runs several times, for window and
full layers of plain grouped-query attention over two pools, for gated
delta-rule linear layers beside gated attention, and for the prefill
program that takes several prompts end to end): plant ONE fault in
the served program (or its weights), run the configuration's own dense
probe over it, print what ``correct`` would compare.

    python3 tools/wrong_servers.py --config benchmark/configs/dots-vlm1-ep16-bf16.json \\
        --faults sound,float8_experts,zeroed_expert,no_shared --seed 7 [--gaps]

Faults of the WEIGHTS share one process, one engine and one set of programs
(the weights are altered in place after the reference has read them, and
drawn anew afterwards); a fault of the PROGRAM is planted by patching a
function of ``mxnet_tpu`` before the engine traces it, so give each its own
process, which then runs WITHOUT the compile cache (the engine's AOT lane
keys on the configuration: with the cache on, six patched servers read the
sound server's numbers to the last digit; PERF.md section 6, PRs 31 and 33).
Each result is one JSON line: the fault, the probe's numbers against the
configuration module's three probe bands, and with ``--gaps`` the largest
"same token" gap of one generated request against ``LOGIT_RTOL``.
``tests/test_dotsvlm1_serving.py`` plants the same faults at a tiny size.

With ``--cell`` the fault is planted under the HARNESS instead, and
``benchmark/run.py`` runs the cell over it, once a seed of ``--seeds``: the
comparison that reads ``correct`` false is then the driver's own, and the
run's last line says so. A fault of the program runs with the engine's AOT lane
off (it keys on the configuration); jax's own cache keys on a program's text,
so a wrong program compiles and is found again by the next seed.
``--probe-seeds`` are read afterwards by the probe alone, in the same
process over the same planted programs (a run costs a window and its drain):

    python3 tools/wrong_servers.py --cell dotsvlm1-chat-closed256 \\
        --faults latent_not_written --seeds 11 --probe-seeds 12,13
"""
import argparse
import contextlib
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

WEIGHTS = ("sound", "float8_all", "float8_experts", "zeroed_expert",
           "no_shared",
           # of gated delta-rule layers (solaropen2-reason-closed192): the
           # decay left out (alpha = 1), the output gate left out (gate = 1)
           "no_decay", "no_linear_gate")
PROGRAM = ("no_group_limit", "no_renorm", "no_scale", "no_mscale",
           "unrotated_key", "latent_before_norm", "latent_not_written",
           # of a stack that runs several times (ouro-chat-closed32)
           "float8_pages", "three_passes", "shared_cache", "no_post_norm",
           # of plain grouped-query attention in window and full layers
           # over two pools (mimov25-mixed-closed128)
           "no_sink", "window_64", "bases_swapped", "rope_all_lanes",
           "freed_block_read", "window_kv_not_written",
           # of gated delta-rule layers beside gated attention
           # (solaropen2-reason-closed192)
           "beta_not_doubled", "qk_not_normalised", "conv_tail_not_carried",
           "chunk_state_not_handed", "state_not_written", "no_gqa_gate",
           "bf16_state",
           # of a packed prefill (every cell whose model packs): the floor
           # dropped, so a prompt sees the prompts laid before it
           "pack_sees_neighbours")


def load_config(path):
    cfg = json.load(open(path))
    here = os.path.dirname(os.path.abspath(path))
    mod_path = os.path.join(here, cfg.get(
        "module", os.path.basename(path)[:-len(".json")] + ".py"))
    spec = importlib.util.spec_from_file_location("served_config", mod_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return cfg, mod


_MADE = {}


def _made(cfg, mod, what):
    """The configuration's probe or scorer, made (and compiled) once a
    process: a change of ``init`` draws other weights for the same
    programs."""
    key = (what, json.dumps({k: cfg[k] for k in ("model", "reference")},
                            sort_keys=True))
    if key not in _MADE:
        _MADE[key] = getattr(mod, what)(cfg)
    return _MADE[key]


def alter(name, *holders):
    """Plant a fault of the weights in ``holders`` (dicts that hold the
    same arrays), IN PLACE, one array at a time (a second copy of the held
    experts does not fit beside the first at the published widths)."""
    import jax.numpy as jnp

    def change(key, fn):
        new = fn(holders[0][key])
        for d in holders:
            d[key] = new

    for key in list(holders[0]):
        if (name == "float8_experts" and "_experts_" in key) or (
                name == "float8_all" and key.endswith("_weight")):
            # the nearest precision below the one the configuration states
            change(key, lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype))
        if name == "zeroed_expert" and key.endswith("_experts_down_weight"):
            change(key, lambda w: w.at[w.shape[0] // 2].set(0))  # one held
        if name == "no_shared" and key.endswith("_shared_down_weight"):
            change(key, jnp.zeros_like)
        if name == "no_decay" and key.endswith("_kda_a_log"):
            # g = -exp(A_log) softplus(.) = 0: every alpha is 1
            change(key, lambda a: jnp.full_like(a, -1e4))
        if name == "no_linear_gate" and key.endswith("_kda_g_bias"):
            change(key, lambda b: jnp.full_like(b, 1e4))    # sigmoid = 1


@contextlib.contextmanager
def planted(name, model):
    """A fault of the program, for as long as the engine traces and runs.
    ``model``: the configuration file's ``model`` object."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import moe
    from mxnet_tpu.serving import model as M
    from mxnet_tpu.serving.engine import ServingEngine

    undo = []

    def patch(owner, attr, new):
        undo.append((owner, attr, getattr(owner, attr)))
        setattr(owner, attr, new)

    class Other:
        """The configuration but for some fields."""

        def __init__(self, cfg, **fields):
            self.__dict__.update(fields, _cfg=cfg)

        def __getattr__(self, key):
            return getattr(self._cfg, key)

    sound_route = moe.route
    if name == "no_group_limit":        # plain top-k of s + b
        patch(moe, "route", lambda x, router, k, **kw: sound_route(
            x, router, k, **dict(kw, n_group=1, topk_group=1)))
    elif name in ("no_renorm", "no_scale"):
        def route(x, router, k, **kw):
            w, e = sound_route(x, router, k, **kw)
            if name == "no_scale":
                return w / kw["scale"], e
            s = jax.nn.sigmoid(jnp.dot(
                x, router.T, preferred_element_type=jnp.float32))
            return jnp.take_along_axis(s, e, axis=1) * kw["scale"], e
        patch(moe, "route", route)
    elif name == "no_mscale":
        patch(M, "mla_sm_scale", lambda cfg: float(
            cfg.head_dim + cfg.rope_dim) ** -0.5)
    elif name == "unrotated_key":       # the one-head call is the key's
        sound = M._rotate
        patch(M, "_rotate", lambda x4, pos, inv: x4 if x4.shape[2] == 1
              else sound(x4, pos, inv))
    elif name == "latent_before_norm":  # in the decode program only
        sound = M._rms_norm

        def rms(x, gamma, eps=M._NORM_EPS):
            if x.ndim == 3 and x.shape[1] == 1 \
                    and x.shape[-1] == model["kv_rank"]:
                return x
            return sound(x, gamma, eps)
        patch(M, "_rms_norm", rms)
    elif name == "latent_not_written":  # every decode step's latent is lost
        sound = ServingEngine._dispatch_decode

        def dispatch(self, *a, **kw):
            kept = jnp.copy(self.pool.k_pages)   # the argument is donated
            out = sound(self, *a, **kw)
            self.pool.k_pages = kept
            return out
        patch(ServingEngine, "_dispatch_decode", dispatch)
    elif name == "float8_pages":
        # every K and V rounded to float8 on its way to the pages (and to
        # prefill's attention, which reads what it caches)
        sound = M._mix_attn

        def to8(t):
            # float8_e4m3's four exponent and three mantissa bits, by an op
            # the compiler may not take for excess precision and drop (a
            # pair of converts it does: PERF.md section 6, PR 40)
            return jax.lax.reduce_precision(t, exponent_bits=4,
                                            mantissa_bits=3)

        def mix(h, params, p, i, cfg, prec, positions, attend, state):
            return sound(h, params, p, i, cfg, prec, positions,
                         lambda i, q, k, v, st, **kw: attend(
                             i, q, to8(k), to8(v), st, **kw), state)
        patch(M, "_mix_attn", mix)
    elif name in ("three_passes", "shared_cache", "no_post_norm"):
        sound = M._layers

        def layers(x, params, cfg, prec, positions, valid, attend, state,
                   recur=None):
            if name == "three_passes":      # the last pass left out
                cfg = Other(cfg, loop_steps=cfg.loop_steps - 1)
            elif name == "no_post_norm":
                cfg = Other(cfg, post_norm=False)
            else:           # every pass reads and writes pass 0's cache
                served = attend

                def attend(i, q, k, v, st, r):  # noqa: F811
                    return served(i, q, k, v, st, r * 0)
            return sound(x, params, cfg, prec, positions, valid, attend,
                         state, recur)
        patch(M, "_layers", layers)
    elif name == "no_sink":         # the window layers' softmax without it
        patch(M, "_sink", lambda params, i, cfg: None)
    elif name == "window_64":       # the programs' window, not the pools'
        for fn, at in (("_prefill_hybrid", 6), ("_paged_step_hybrid", 7)):
            def short(*a, _sound=getattr(M, fn), _at=at):
                return _sound(*a[:_at], Other(a[_at], window=64),
                              *a[_at + 1:])
            patch(M, fn, short)
    elif name == "bases_swapped":   # a full layer's base in a window layer
        sound = M.ModelConfig.rope_theta_of
        patch(M.ModelConfig, "rope_theta_of", lambda self, kind: sound(
            self, "full" if kind == "swa" else "swa"))
    elif name == "rope_all_lanes":  # the whole head turned, not its 64
        sound = M._rope_part
        patch(M, "_rope_part", lambda t, pos, theta, dr: sound(
            t, pos, theta, 0))
    elif name == "freed_block_read":
        # a window layer's walk starts one block early and its lanes read
        # what lies there: a block the stream has given back
        sound = M.paged_attention_multi

        def early(*a, **kw):
            if kw.get("window") is not None:
                kw["window"] += a[1].shape[-2]          # one block of keys
            return sound(*a, **kw)
        patch(M, "paged_attention_multi", early)
    elif name == "window_kv_not_written":
        # every decode step's K/V of the window layers is lost
        sound = ServingEngine._dispatch_decode

        def dispatch(self, *a, **kw):
            pool = self.window_pool     # the arguments are donated
            kept = jnp.copy(pool.k_pages), jnp.copy(pool.v_pages)
            out = sound(self, *a, **kw)
            pool.k_pages, pool.v_pages = kept
            return out
        patch(ServingEngine, "_dispatch_decode", dispatch)
    elif name in ("beta_not_doubled", "no_gqa_gate"):
        fn, field = (("_mix_kda", "kda_neg_eigval")
                     if name == "beta_not_doubled"
                     else ("_mix_gqa", "attn_gate"))
        at = 4 if fn == "_mix_kda" else 5       # where cfg rides

        def without(*a, _sound=getattr(M, fn), **kw):
            return _sound(*a[:at], Other(a[at], **{field: False}),
                          *a[at + 1:], **kw)
        patch(M, fn, without)
    elif name == "qk_not_normalised":   # q keeps its scale, neither its norm
        def heads(xc, cfg):
            hh, dk = cfg.kda_heads, cfg.kda_head_dim
            q, k, v = jnp.split(xc, [hh * dk, 2 * hh * dk], axis=-1)
            return ((q * float(dk) ** -0.5).astype(xc.dtype).reshape(
                -1, hh, dk), k.reshape(-1, hh, dk),
                v.reshape(-1, hh, dk))
        patch(M, "_kda_heads", heads)
    elif name == "chunk_state_not_handed":
        # every chunk of a prompt starts from an empty state: the slot ends
        # with the last live chunk's own
        from mxnet_tpu.ops.kda import CHUNK
        sound = M.kda_chunk

        def alone(q, k, v, g, beta, length, state, slot, layer):
            outs = []
            for at in range(0, q.shape[0], CHUNK):
                live = jnp.clip(length - at, 0, CHUNK)
                o, state = sound(
                    *(t[at:at + CHUNK] for t in (q, k, v, g, beta)), live,
                    state, jnp.where(live > 0, slot, 0), layer)
                outs.append(o)
            return jnp.concatenate(outs), state
        patch(M, "kda_chunk", alone)
    elif name == "bf16_state":
        # the state keeps bfloat16's eight bits after every decode update
        # and after a prompt (reduce_precision: a pair of converts is
        # dropped as excess precision, PERF.md section 6, PR 40)
        def rounded(state, layer, slots):
            return state.at[layer, slots].set(jax.lax.reduce_precision(
                state[layer, slots], exponent_bits=8, mantissa_bits=7))

        step, chunk = M.kda_step, M.kda_chunk

        def kda_step(q, k, v, g, beta, state, slots, layer):
            o, state = step(q, k, v, g, beta, state, slots, layer)
            return o, rounded(state, layer, slots)

        def kda_chunk(q, k, v, g, beta, length, state, slot, layer):
            o, state = chunk(q, k, v, g, beta, length, state, slot, layer)
            return o, rounded(state, layer, slot)
        patch(M, "kda_step", kda_step)
        patch(M, "kda_chunk", kda_chunk)
    elif name in ("conv_tail_not_carried", "state_not_written"):
        # the programs are sound; the engine loses what one of them wrote:
        # a prompt's conv tail (decode starts from what the slot held), or
        # every decode step's states
        lost, fn = (("conv", "_dispatch_prefill")
                    if name == "conv_tail_not_carried"
                    else ("ssm", "_dispatch_decode"))

        def dispatch(self, *a, _sound=getattr(ServingEngine, fn), **kw):
            kept = jnp.copy(getattr(self.state, lost))  # donated below
            out = _sound(self, *a, **kw)
            setattr(self.state, lost, kept)
            return out
        patch(ServingEngine, fn, dispatch)
    elif name == "pack_sees_neighbours":
        # a pack's rows keep their positions and their prompts' lengths;
        # the attention loses the floor, and is causal over the whole pack
        sound = M._pack_rows

        def rows(length, S, bs):
            positions, table_pos, _first_key, *rest = sound(length, S, bs)
            return (positions, table_pos, None) + tuple(rest)
        patch(M, "_pack_rows", rows)
    elif name not in WEIGHTS:
        raise ValueError("no fault %r (weights: %s; program: %s)"
                         % (name, WEIGHTS, PROGRAM))
    try:
        yield
    finally:
        for owner, attr, was in reversed(undo):
            setattr(owner, attr, was)


def refused_by(mod, seen):
    """Which of the configuration module's probe bands refuse a probe's
    numbers (``drivers/serve_share.py`` holds a run to the same three)."""
    limits = (("quartile", seen["quartile"], mod.PROBE_RTOL),
              ("median", seen["median"], mod.PROBE_MEDIAN_RTOL),
              ("held_quartile", seen["held"]["quartile"],
               mod.PROBE_HELD_RTOL))
    return [what for what, got, band in limits if not got <= band]


def reading(cfg, mod, params, name, seed, gaps=False, eng=None,
            consume=False):
    """The probe's numbers of an engine (``eng``, or one built here) with
    fault ``name`` planted. The reference runs over the SOUND weights: a
    fault of the weights is planted after the reference's rows are
    computed, before the probe's first question to the engine. ``consume``:
    ``params`` (drawn from ``seed``) may be altered in place and is drawn
    anew afterwards; otherwise a copy of the dict is altered and
    ``params`` keeps the sound arrays alive."""
    import gc

    import numpy as np

    from mxnet_tpu.serving import ServingEngine

    work = params if consume else dict(params)
    with planted(name, cfg["model"]):
        if eng is None:
            eng = ServingEngine(mod.serving_config(cfg), arg_params=work,
                                seed=seed)

        def plant():
            eng.params = work
            alter(name, work)

        seen = _made(cfg, mod, "make_probe")(work, eng, seed,
                                             before_serving=plant)
        refused = refused_by(mod, seen)
        out = {"fault": name, "seed": seed, "band": mod.PROBE_RTOL,
               "median_band": mod.PROBE_MEDIAN_RTOL,
               "held_band": mod.PROBE_HELD_RTOL, "refused_by": refused,
               "correct_by_probe": not refused}
        out.update(seen)
        tokens = None
        if gaps:
            rng = np.random.RandomState(seed % 2 ** 32)
            prompt = [int(t) for t in rng.randint(
                0, cfg["model"]["vocab"], 100)]
            n = cfg["reference"]["gen_max"]
            tokens = eng.generate([prompt], n)[0]
    if consume and name in WEIGHTS and name != "sound":
        eng.params = None
        work.clear()
        gc.collect()
        work.update(mod.init_params(cfg, seed))
        eng.params = work
    if tokens is not None:
        g = _made(cfg, mod, "make_reference").gaps(params, prompt, tokens)
        out.update(gap_worst=float(g.max()), gap_median=float(np.median(g)),
                   gap_top=[round(float(v), 4) for v in sorted(g)[-5:]],
                   gap_over_band=int((g > mod.LOGIT_RTOL).sum()),
                   gap_band=mod.LOGIT_RTOL, gap_tokens=len(tokens))
    return out


def through_run(name, cell, seeds, seconds, rehearsal=False,
                probe_seeds=()):
    """``benchmark/run.py`` over the cell with fault ``name`` planted, once
    a seed of ``seeds``; then, in the same process and over the same
    planted programs, the probe alone for each of ``probe_seeds`` (a run
    costs a window and its drain, a probe does not). ``rehearsal``: the
    cell of ``benchmark/rehearsal/``, on any platform."""
    import gc
    import io

    from benchmark import run

    manifest = run.load_json(
        os.path.join(ROOT, "benchmark", "rehearsal", cell + ".json")
        if rehearsal else os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in manifest["configs"]
                 if c["name"] == run.find_cell(manifest, cell)["config"])
    cfg_path = os.path.join(ROOT, entry["file"])
    model = run.load_json(cfg_path)["model"]
    if name in PROGRAM:
        # the engine's AOT lane keys on the CONFIGURATION and would hand
        # back a sound server's programs; jax's own cache keys on the
        # program's text, so a wrong program compiles and a sound one loads
        os.environ["MXNET_COMPILE_CACHE_AOT"] = "0"
    load = run.load_module

    def load_planted(path, modname):
        """The configuration's module, its probe planting the fault of the
        weights once the reference has read them."""
        mod = load(path, modname)
        make = mod.make_probe

        def make_probe(cfg):
            probe = make(cfg)
            return lambda params, eng, seed: probe(
                params, eng, seed,
                before_serving=lambda: alter(name, params, eng.params))
        mod.make_probe = make_probe
        return mod

    if name in WEIGHTS and name != "sound":
        run.load_module = load_planted
    try:
        with planted(name, model):
            for seed in seeds:
                out = io.StringIO()
                with contextlib.redirect_stdout(_Tee(sys.stdout, out)):
                    run.main(["--workload", cell, "--seed", str(seed),
                              "--seconds", str(seconds), "--trace", "0"]
                             + ["--rehearsal"] * rehearsal)
                last = json.loads(out.getvalue().strip().splitlines()[-1])
                print(json.dumps({"fault": name, "seed": seed, "cell": cell,
                                  "through": "benchmark/run.py",
                                  "correct": last["correct"]}), flush=True)
                gc.collect()        # an engine is a cycle: free its pool
    finally:
        run.load_module = load
    if probe_seeds:
        probes(name, cfg_path, probe_seeds)


def probes(names, cfg_path, seeds, gaps=False, gains=None):
    """The probe alone: one engine and one set of programs for every fault
    of the weights in ``names`` and every seed (a fault of the program:
    ``names`` is that one); a JSON line a reading."""
    import jax

    from mxnet_tpu.serving import ServingEngine

    names = [names] if isinstance(names, str) else names
    cfg, mod = load_config(cfg_path)
    gains = gains or [cfg.get("init", {}).get("expert_gain", 1.0)]
    eng = None
    for gain, seed in ((g, s) for g in gains for s in seeds):
        cfg["init"] = dict(cfg.get("init", {}), expert_gain=gain)
        params = mod.init_params(cfg, seed)
        jax.block_until_ready(params)
        if eng is None:
            with planted(names[0], cfg["model"]):
                eng = ServingEngine(mod.serving_config(cfg),
                                    arg_params=params, seed=seed)
        eng.params = params
        for name in names:
            out = reading(cfg, mod, params, name, seed, gaps, eng=eng,
                          consume=True)
            print(json.dumps(dict(out, expert_gain=gain)), flush=True)
        eng.params = None
        params.clear()


class _Tee:
    def __init__(self, *streams):
        self.streams = streams

    def write(self, text):
        for f in self.streams:
            f.write(text)

    def flush(self):
        for f in self.streams:
            f.flush()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config")
    ap.add_argument("--cell", help="run the benchmark's cell of this name "
                                   "over the fault, through benchmark/run.py")
    ap.add_argument("--faults", default="sound")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seeds", default=None,
                    help="comma-separated; each is read in turn")
    ap.add_argument("--probe-seeds", default=None,
                    help="with --cell: seeds read by the probe alone, after "
                         "the runs, over the same planted programs")
    ap.add_argument("--seconds", type=float, default=3.0,
                    help="the window of a --cell run")
    ap.add_argument("--rehearsal", action="store_true",
                    help="with --cell: the cell of benchmark/rehearsal/")
    ap.add_argument("--gaps", action="store_true")
    ap.add_argument("--expert-gains", default=None,
                    help="comma-separated init.expert_gain values to read "
                         "the faults of the weights at (a study of the draw)")
    args = ap.parse_args(argv)
    names = args.faults.split(",")
    seeds = [args.seed] if args.seeds is None \
        else [int(x) for x in args.seeds.split(",") if x]
    probe_seeds = [int(x) for x in (args.probe_seeds or "").split(",") if x]
    if bool(args.cell) == bool(args.config):
        raise SystemExit("give --config (the probe alone) or --cell (the "
                         "harness's run)")
    if set(names) & set(PROGRAM) or args.cell:
        if len(names) > 1:
            raise SystemExit("a fault of the program, and a run of the "
                             "cell, needs a process of its own")
    if args.cell:
        through_run(names[0], args.cell, seeds, args.seconds,
                    args.rehearsal, probe_seeds)
        return 0
    if set(names) & set(PROGRAM):
        # and no compile cache: the engine's AOT lane keys on the
        # CONFIGURATION and would hand back a sound server's programs (the
        # chip's machine names a cache directory in the environment)
        for var in ("JAX_COMPILATION_CACHE_DIR", "MXNET_COMPILE_CACHE_DIR"):
            os.environ.pop(var, None)
        from mxnet_tpu import compile_cache

        compile_cache.disable()
    probes(names, args.config, seeds, args.gaps,
           [float(g) for g in args.expert_gains.split(",")]
           if args.expert_gains else None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
