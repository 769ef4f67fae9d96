"""ServingEngine — the standing inference engine's Python API.

One engine owns: the weights (a trained checkpoint's ``arg_params`` or
deterministic ``random_params``), one :class:`~.kv_cache.KVBlockPool`, one
:class:`~.scheduler.Scheduler`, and exactly TWO compileobs-tracked XLA
programs — ``serving.prefill`` and ``serving.decode`` — each compiled once
per padded shape bucket (prompt-length buckets for prefill, batch-size
buckets for decode) and replayed forever after: ``compileobs`` showing a
flat compile count after bucket warmup is the engine's no-recompile
acceptance gate.

Each :meth:`step` runs the scheduler's plan: admitted prompts prefill into
the shared block pool (one call per request at its length bucket), then
every decoding stream advances up to ``DECODE_CHUNK`` tokens through ONE
dispatch of the fused paged decode program at the batch bucket, which
loops over the decode step on the device. The ONLY device->host sync per
dispatch is the tiny array of next tokens — that read IS the product
(tokens leave for clients); everything else stays device-resident, pool
pages donated call to call. The host is synchronous at that fetch: no
program is in flight while it retires, frees, schedules and admits.

Thread model: ``submit()`` is safe from any thread (HTTP handlers);
``step()``/``run_loop()`` must run on one driver thread. Per-request
latency metrics (TTFT, end-to-end, tokens/sec) flow through the telemetry
registry — ``serving.*`` in docs/observability.md — and render live in
``tools/serve.py``'s stat columns.
"""
import itertools
import threading
import time
from collections import deque

import numpy as np

from .. import compile_cache, compileobs, fault, telemetry
from ..analysis import witness
from ..base import env_bool, env_int, env_str
from ..ops.kda import CHUNK as _KDA_CHUNK
from . import model as _model
from .kv_cache import KVBlockPool
from .obs import ServingObs, open_record
from .resilience import ServingOverloadError, retry_after_s
from .scheduler import (CANCELLED, DECODING, FAILED, FINISHED, TIMED_OUT,
                        WAITING, Request, Scheduler)

_SITE = "serving/engine.py"
_PACK_ALIGN = _model.PACK_ALIGN
_CAT = "serving"   # the chrome-trace category of the engine's spans


def _no_clock():
    """The clock of a step that keeps no record (``ServingEngine._clock``)."""
    return 0.0


_engine_ids = itertools.count()

#: what a model with layer_kinds carries beside the full pool's pages, in
#: the step programs' argument order: the window pool's K and V pages, the
#: conv tails, the SSM states
_AUX = ("wk", "wv", "conv", "ssm")

#: decode steps one dispatch may run on the device (``model.decode_chunk``):
#: the host's gap between two dispatches — the blocking token fetch, jax's
#: dispatch, the loop's own bookkeeping — is paid once a chunk. Like a
#: kernel's block size: one value for every model, read on the chip at 2,
#: 4 and 8 in the three closed-loop cells (PERF.md section 6, PR 32: 8 read
#: best in all three, by less each doubling); a freed lane idles at most
#: DECODE_CHUNK - 1 steps and an arrival waits at most that many more
DECODE_CHUNK = 8

#: rows from which the prefill ladder takes a half step between two
#: doublings (``ServingConfig.prefill_buckets``). One value for every
#: model: below it a program more costs a short set-up more than its padded
#: rows cost the device (PERF.md section 6, PRs 45 and 46: half steps from
#: 512 cost OLMoE's 20 s set-up two programs and its gate)
HALF_STEP_FROM = 1024


class ServingConfig(_model.ModelConfig):
    """Model shape + engine knobs. Engine knobs default from the
    ``MXNET_SERVING_*`` environment (docs/env_var.md); ``**arch`` are
    :class:`~.model.ModelConfig`'s structural fields (norm, pos,
    rope_theta, qk_norm, head_dim, num_experts, experts_per_tok, bias,
    layer_kinds and what goes with it), passed through — they feed
    ``key()`` and so every graph and compile cache key.

    A model with "swa" layers has a second pool for them, sized for
    ``max_batch`` streams of ``window + block_size`` tokens and the trash
    block; one with "mamba" or "kda" layers ``max_batch`` state slots and
    the trash slot (``_build_caches``). ``num_blocks`` stays the full-length
    pool's."""

    __slots__ = ("block_size", "num_blocks", "max_batch",
                 "prefills_per_step", "kv_dtype", "prefix_cache",
                 "spec_k", "draft", "max_queue", "default_timeout_ms")

    def __init__(self, vocab_size=32000, num_layers=4, model_dim=256,
                 num_heads=4, ffn_dim=1024, max_len=128,
                 block_size=None, num_blocks=None, max_batch=None,
                 prefills_per_step=None, kv_dtype=np.float32,
                 prefix_cache=None, spec_k=None, draft=None,
                 max_queue=None, default_timeout_ms=None, **arch):
        super().__init__(vocab_size, num_layers, model_dim, num_heads,
                         ffn_dim, max_len, **arch)
        self.block_size = int(block_size if block_size is not None
                              else env_int("MXNET_SERVING_BLOCK_SIZE", 16))
        self.num_blocks = int(num_blocks if num_blocks is not None
                              else env_int("MXNET_SERVING_NUM_BLOCKS", 257))
        self.max_batch = int(max_batch if max_batch is not None
                             else env_int("MXNET_SERVING_MAX_BATCH", 32))
        # None (nothing given, nothing in the environment): a step admits
        # every waiting prompt that the lanes, the pool and the state
        # slots hold; an integer caps a step's group, for a caller who
        # would bound the stall of the streams that decode
        if prefills_per_step is None:
            prefills_per_step = env_int("MXNET_SERVING_PREFILLS_PER_STEP")
        self.prefills_per_step = (None if prefills_per_step is None
                                  else int(prefills_per_step))
        self.kv_dtype = np.dtype(kv_dtype)
        # prefix sharing (docs/serving.md §prefix-sharing): content-hash
        # full prefill blocks so same-prefix admissions map cached blocks
        # (refcounted, copy-on-write) instead of re-caching them
        self.prefix_cache = bool(
            prefix_cache if prefix_cache is not None
            else not (self.stateful or self.latent)
            and env_bool("MXNET_SERVING_PREFIX_CACHE", True))
        # speculative decoding (docs/serving.md §speculative-decoding):
        # spec_k > 0 turns it on — a draft LM proposes spec_k tokens per
        # step, the target scores all spec_k+1 window positions in one
        # multi-query verify pass, greedy acceptance keeps the emitted
        # stream bit-identical to target-only decoding
        self.spec_k = int(spec_k if spec_k is not None
                          else env_int("MXNET_SERVING_SPEC_K", 0))
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0 (0 disables speculative "
                             "decoding)")
        self.draft = str(draft if draft is not None
                         else env_str("MXNET_SERVING_DRAFT", "self"))
        # resilience knobs (docs/serving.md §resilience): a bounded
        # admission queue sheds load at submit instead of letting the
        # WAITING deque grow without limit, and a default deadline bounds
        # how long any request may live without the client asking
        self.max_queue = int(max_queue if max_queue is not None
                             else env_int("MXNET_SERVING_MAX_QUEUE", 0))
        if self.max_queue < 0:
            raise ValueError("max_queue must be >= 0 (0 = unbounded)")
        self.default_timeout_ms = int(
            default_timeout_ms if default_timeout_ms is not None
            else env_int("MXNET_SERVING_DEFAULT_TIMEOUT_MS", 0))
        if self.default_timeout_ms < 0:
            raise ValueError("default_timeout_ms must be >= 0 (0 = no "
                             "default deadline)")
        if self.max_len % self.block_size:
            raise ValueError(
                "max_len (%d) must be a multiple of block_size (%d): "
                "prefill buckets and the decode block table are sized in "
                "whole blocks" % (self.max_len, self.block_size))
        if self.linear and (self.prefix_cache or self.spec_k):
            raise ValueError(
                "%s needs what a model with 'kda' layers does not have yet: "
                "%s" % (("prefix_cache", "a snapshot of every linear layer's "
                         "matrix state (heads x keys x values, float32) and "
                         "conv tail at a block boundary, to start a stream "
                         "from a shared prefix: the state after a prefix is "
                         "no function of any block's K/V")
                        if self.prefix_cache
                        else ("spec_k > 0", "a verify pass over state: "
                              "several tokens a stream through the delta "
                              "rule with the state kept at each, and the "
                              "state put back when a speculated window is "
                              "rejected")))
        if self.gqa and (self.prefix_cache or self.spec_k):
            # before the two refusals of a stateful model: they would say
            # less, and a model of "full" layers alone passes them
            raise ValueError(
                "%s needs what plain grouped-query attention's step program "
                "(one query lane a stream) does not have yet: %s"
                % (("prefix_cache", "an extend step for a prompt that "
                    "starts from shared blocks; besides, a shared prefix's "
                    "window blocks are freed as its first reader's window "
                    "slides, so a second stream cannot start from them")
                   if self.prefix_cache
                   else ("spec_k > 0", "a verify pass against two pools "
                         "(several query lanes a stream over the window "
                         "pool, and the freed blocks back when a speculated "
                         "window is rejected)")))
        if self.stateful and self.prefix_cache:
            raise ValueError(
                "prefix_cache needs what a model with state or window "
                "layers does not have yet: the recurrent state (and the "
                "window's blocks) saved at block boundaries, to start a "
                "stream from a shared prefix")
        if self.latent and (self.prefix_cache or self.spec_k):
            raise ValueError(
                "%s needs what a model with 'mla' layers does not have yet: "
                "an extend step over cached latents (several query lanes a "
                "stream against the latent pool, for %s)"
                % (("prefix_cache", "a prompt that starts from shared "
                    "blocks") if self.prefix_cache
                   else ("spec_k > 0", "the verify pass")))
        if self.stateful and self.spec_k:
            raise ValueError(
                "spec_k > 0 needs what a model with state or window layers "
                "does not have yet: a roll-back of the state and of the "
                "window's freed blocks when a speculated window is "
                "rejected")

    @classmethod
    def from_json(cls, obj):
        """From the ``model`` and ``engine`` objects of a configuration
        file (``benchmark/configs/*.json``, ``tools/serve.py
        --model-config``): ``model`` holds the model fields under their
        own names (``vocab`` for ``vocab_size``), ``engine`` the engine
        knobs; a null or absent knob takes its default."""
        model = dict(obj["model"])
        model["vocab_size"] = model.pop("vocab")
        engine = {k: v for k, v in obj["engine"].items() if v is not None}
        if "kv_dtype" in engine:
            import jax.numpy as jnp

            engine["kv_dtype"] = jnp.dtype(engine["kv_dtype"])
        return cls(**model, **engine)

    def decode_buckets(self):
        """Padded decode batch sizes: powers of two up to max_batch."""
        out = []
        b = 1
        while b < self.max_batch:
            out.append(b)
            b *= 2
        out.append(self.max_batch)
        return out

    def prefill_buckets(self):
        """Padded prompt lengths: block_size doublings up to max_len, and
        from ``HALF_STEP_FROM`` rows up the half step between two
        doublings (1,024, 1,536, 2,048, 3,072, ...). A short program is
        bound by its weights' bytes and a padded row costs nothing; a
        long one pays for every row, and a prompt one token past a
        doubling would run half a program of padding."""
        out = []
        s = self.block_size
        while s < self.max_len:
            out.append(s)
            # (a half step is whole blocks from the second doubling on)
            if (s >= max(HALF_STEP_FROM, 2 * self.block_size)
                    and s + s // 2 < self.max_len):
                out.append(s + s // 2)
            s *= 2
        out.append(self.max_len)
        return out


def _pack_fetch(tokens, logits, k_pages, v_pages, load=None):
    """A step program's FOUR results, whatever the block: a config with
    experts appends the step's per-layer ``tokens_per_expert`` (L, E) to
    the next-token vector, so that the load comes back in the one fetch
    the step blocks on anyway (:func:`_unpack_fetch` splits it)."""
    if load is None:
        return tokens, logits, k_pages, v_pages
    import jax.numpy as jnp

    return (jnp.concatenate([tokens.reshape(-1), load.reshape(-1)]),
            logits, k_pages, v_pages)


def _unpack_fetch(fetched, shape, cfg, steps=()):
    """``(next tokens of shape, load (L, E) or None)`` from the fetched
    vector of :func:`_pack_fetch`; a decode chunk's load is one (L, E) a
    step: ``steps = (chunk,)``."""
    n = int(np.prod(shape))
    load = (fetched[n:].reshape(steps + (cfg.expert_layers,
                                         cfg.num_experts))
            if cfg.num_experts else None)
    return fetched[:n].reshape(shape), load


def _moe_args(load, cfg):
    """The fetch spans' arguments for a step with experts: what THIS
    engine computed (the experts it holds)."""
    if load is None:
        return {}
    first, count = cfg.experts_here
    load = load[..., first:first + count]
    return {"pairs": int(load.sum()),
            "experts_touched": int((load > 0).sum())}


def _max_over_mean(load):
    """Busiest expert's tokens over the mean expert's, per layer, averaged
    over the layers of ``load`` (L, E); 0.0 for an empty step."""
    if not load.size:
        return 0.0
    mean = load.mean(axis=1)
    live = mean > 0
    if not live.any():
        return 0.0
    return float((load.max(axis=1)[live] / mean[live]).mean())


def _bucket_for(n, buckets):
    for b in buckets:
        if n <= b:
            return b
    raise ValueError("no bucket holds %d (buckets %s)" % (n, buckets))


def cut_packs(need, buckets, warm, most):
    """Prompts of ``need`` rows each (whole ``PACK_ALIGN`` tiles), in plan
    order, cut into runs of neighbours, a run a prefill program:
    ``[(first, end, rung), ...]``, the cut that computes the fewest ROWS —
    the sum of the runs' rungs: a row costs the device the same in any
    program, so the rows a pack does not compute are what it saves — and
    among equals the fewest programs. A prompt alone runs the smallest of
    ``buckets`` that holds it; a run of several, at most ``most`` prompts,
    the smallest of ``warm`` (ascending: the rungs whose program is
    compiled), and none where no such rung holds it. One pass, each prompt
    trying the at most ``most - 1`` before it. (Closing a pack greedily
    when the next prompt would pass the top rung computes 6% more rows on
    dots.vlm1's lengths: ``tests/test_prefill_pack.py``.)"""
    # least[i]: (rows, programs, where the last run starts, its rung) of
    # the best cut of the first i prompts
    least = [(0, 0, 0, 0)]
    for i in range(1, len(need) + 1):
        rows, cuts = 0, []
        for j in range(i - 1, max(i - most, 0) - 1, -1):
            rows += need[j]
            rung = (_bucket_for(rows, buckets) if j == i - 1
                    else next((b for b in warm if rows <= b), None))
            if rung is None:
                break
            cuts.append((least[j][0] + rung, least[j][1] + 1, j, rung))
        least.append(min(cuts))
    runs, i = [], len(need)
    while i:
        _rows, _programs, j, rung = least[i]
        runs.append((j, i, rung))
        i = j
    return runs[::-1]


class ServingEngine:
    """Continuous-batching inference over the Transformer-LM zoo model."""

    def __init__(self, config, arg_params=None, seed=0, device=None,
                 enable_telemetry=True):
        if enable_telemetry:
            telemetry.enable()
        self.config = cfg = config
        # read once: the decode programs' results, the window pool and the
        # scheduler's headroom all follow it for the engine's life
        self._chunk = chunk = DECODE_CHUNK
        if arg_params is None:
            arg_params = _model.random_params(cfg, seed=seed)
        self.params = _model.as_device_params(arg_params, cfg, device=device)
        (self.pool, self.window_pool, self.state,
         self.streams) = self._build_caches(cfg, device)
        # a step writes up to a decode chunk's slots a stream, or the
        # spec_k+1 of a draft+verify window: headroom covers either
        self._spec = cfg.spec_k > 0
        self.spec_k = cfg.spec_k
        self.scheduler = Scheduler(self.pool, max_batch=cfg.max_batch,
                                   prefills_per_step=cfg.prefills_per_step,
                                   lookahead=max(cfg.spec_k + 1, chunk),
                                   max_positions=cfg.max_len,
                                   streams=self.streams)
        self._nb_max = cfg.max_len // cfg.block_size
        self._lock = threading.RLock()
        # separate statement: lockgraph keys the lock to the ctor line
        # above; the witness proxy is identity-transparent when off
        self._lock = witness.declare(
            "mxnet_tpu.serving.engine.ServingEngine._lock", self._lock)
        self._work = threading.Condition(self._lock)
        # retired requests awaiting pop_finished(), BOUNDED: a driver
        # that consumes done_events instead (serve.py) would otherwise
        # leak one Request per call served for the life of the server.
        # A polling driver draining every step never hits the cap — a
        # step retires at most max_batch streams plus a handful of
        # admission failures; only a mass abort can shed the oldest
        # entries, and those waiters were already woken via done_event.
        self._finished = deque(maxlen=max(256, 8 * cfg.max_batch))
        self._aborted = None
        self._draining = False
        # supervisor contract (resilience.EngineSupervisor): when set,
        # abort() parks still-salvageable inflight requests in _salvaged
        # (blocks dropped, tokens-so-far kept as a replay prompt) instead
        # of failing them, so a fresh engine can resubmit() them and —
        # greedy decode — finish them bit-identical to an unfaulted run
        self.salvage_on_abort = False
        self._salvaged = []
        self._steps = 0
        # per-engine tallies: the registry counters with the same names
        # are process-global and would attribute a previous engine's
        # traffic to this one in stats()
        self._n_completed = 0
        self._n_failed = 0
        self._n_timed_out = 0
        self._n_cancelled = 0
        self._n_shed = 0
        self._token_window = []   # (timestamp, tokens) a booking, for tokens/sec
        # routed experts: per-layer, per-expert tokens since the engine
        # started (the step's own (L, E) count rides in the token fetch)
        self._moe_load = np.zeros((cfg.expert_layers, cfg.experts_here[1]),
                                  np.int64)
        self._moe_routed = 0    # every choice the router made, here or not
        # plain grouped-query attention: the keys one layer's walk of each
        # pool read, summed over decode steps and live lanes
        self._hybrid = {"full_ctx_tokens": 0, "window_ctx_tokens": 0,
                        "lane_steps": 0}
        self._full_readers = len(cfg.layers_of("full", "cross", "mla")) \
            if cfg.hybrid else 0
        # latent attention: what the decode kernel read
        self._latent = {"ctx_tokens": 0, "lane_steps": 0, "live_blocks": 0,
                        "prefill_tokens": 0}
        # linear attention: the states the decode steps rewrote, the rows
        # and chunks the prefill kernel took, a "kda" layer each
        self._linear = {"state_updates": 0, "prefill_tokens": 0,
                        "prefill_chunks": 0}
        self._linear_layers = len(cfg.layers_of("kda"))
        self._moe_layer_steps = 0
        self._moe_layer_tokens = 0
        self._moe_touched = 0
        # what the paged kernel walked (decode and verify passes): blocks
        # that hold a live stream's context, of the table slots it holds
        self._paged_live_blocks = 0
        self._paged_table_slots = 0
        # a looped stack: passes run, over the program steps that ran them
        # (a prefill, an inner decode step, a verify pass)
        self._looped_passes = 0
        self._looped_steps = 0
        # how often the chunk engages: device steps over dispatches
        self._decode_dispatches = 0
        self._decode_inner_steps = 0
        # how often a step's prefills form a group: prompts over groups;
        # and how often a group's prompts share a program: over programs
        self._prefill_prompts = 0
        self._prefill_groups = 0
        self._prefill_programs = 0
        # the loop's record of the step under way (obs.LoopRecord's fields;
        # None outside a step and while telemetry is off) and what its two
        # gaps are measured from: the host's clock at the last blocking
        # fetch's return, the seconds under serving.loop.idle since, and
        # which gap the next dispatch closes ("chunk", "group" or None)
        self._rec = None
        self._fetch_end = None
        self._idle_s = 0.0
        self._gap_after = None
        # the last step's bookkeeping that no dispatch needs (_set_aside),
        # until the next step carries it out under its own dispatch or a
        # reader flushes it (_flush); whoever holds the step lock owns it
        self._pending = None
        self._t_started = time.time()
        self._tokens_total = 0
        # per-engine identity: labels this engine's histograms/counters in
        # the process-global registry (stats() reads ONLY its own label)
        # and salts the graph keys below
        self.engine_id = next(_engine_ids)
        self.obs = ServingObs(self.engine_id)

        # donation frees the pool's previous pages the moment the step
        # consumes them — without it every step would briefly double the
        # pool's device footprint (CPU backends ignore donation; harmless)
        import jax

        donate = {} if jax.default_backend() == "cpu" else \
            {"donate_argnums": (4, 5)}
        # the engine nonce is part of the graph identity: a second engine
        # in the same process (even one with an IDENTICAL config) holds
        # fresh function objects, so its bucket warmup compiles again —
        # under a shared graph key that warmup would diff against the
        # first engine's signatures and misreport as compile.recompile
        # (cause=placement; cause=dtype when only kv_dtype differs)
        gkey = ("serving", self.engine_id) + cfg.key() + (
            cfg.block_size, cfg.num_blocks, str(cfg.kv_dtype))

        # fresh function objects per bucket (factories, not one shared
        # closure): jax's tracing cache is keyed on the wrapped function,
        # so bucket wrappers sharing one function would share one cache
        # and each wrapper's cache-size delta would misfire on the
        # others' compiles
        def _mk_prefill():
            def _prefill(params, tokens, length, block_table,
                         k_pages, v_pages):
                return _pack_fetch(*_model.prefill(
                    params, tokens, length, block_table, k_pages, v_pages,
                    cfg))

            if not cfg.hybrid:
                return _prefill

            # the same name: the trace's ops are `jit__prefill/...` for
            # every model, which is how the benchmark's readers find them
            def _prefill(params, tokens, length, block_table,  # noqa: F811
                         k_pages, v_pages, wtable, slot, *arrays):
                tok, logits, kp, vp, out, *load = _model.prefill(
                    params, tokens, length, block_table, k_pages, v_pages,
                    cfg, dict(zip(_AUX, arrays), wtable=wtable, slot=slot))
                return _pack_fetch(tok, logits, kp, vp, *load) + tuple(
                    out[k] for k in _AUX)
            return _prefill

        # the name `_decode` stays whatever runs inside: the trace's ops
        # are `jit__decode/...`, and an op of the loop's body keeps its own
        def _mk_decode():
            def _decode(params, tokens, positions, block_tables,
                        context_lens, steps_left, eos, n, k_pages, v_pages,
                        *hybrid):
                aux = None
                if cfg.hybrid:
                    wtables, slots, *arrays = hybrid
                    aux = dict(zip(_AUX, arrays), wtables=wtables,
                               slots=slots)
                tok, logits, kp, vp, *rest = _model.decode_chunk(
                    params, tokens, positions, block_tables, context_lens,
                    steps_left, eos, n, k_pages, v_pages, cfg, chunk, aux)
                if cfg.hybrid:
                    return _pack_fetch(tok, logits, kp, vp, *rest[1:]) \
                        + tuple(rest[0][k] for k in _AUX)
                return _pack_fetch(tok, logits, kp, vp, *rest)
            return _decode

        # the draft's one-token step and the verify pass keep the plain
        # step's arguments (`_run_spec_decode` has its own loop)
        step_donate = {"donate_argnums": (5, 6)} if donate else {}
        decode_donate = {"donate_argnums": (8, 9)} if donate else {}
        if donate and cfg.hybrid:   # the window pages and the state slots too
            donate = {"donate_argnums": (4, 5, 8, 9, 10, 11)}
            decode_donate = {"donate_argnums": (8, 9, 12, 13, 14, 15)}
        # one wrapper per shape bucket: buckets are DESIGNED to each
        # compile once, so a bucket's first compile must not diff against
        # another bucket's signature under a shared graph key — that would
        # report routine warmup as compile.recompile (the counter
        # operators alarm on) with a WARNING per bucket. Per-bucket keys
        # reserve the recompile stream for a bucket compiling AGAIN.
        #
        # cache_key drops the per-engine NONCE from the graph key: the
        # persistent compile cache must hit across processes (and across
        # engines of identical config), so its identity is pure content —
        # model shape + pool geometry + bucket. aot=True: each bucket is a
        # single-signature site, the serialized-executable fast lane — a
        # warm replica's warmup() loads every bucket from disk instead of
        # compiling it (tools/serve.py --warmup; benchmark/run.py's set-up).
        ckey_base = cfg.key() + (cfg.block_size, cfg.num_blocks,
                                 str(cfg.kv_dtype))
        if cfg.hybrid:
            # the window pool and the state slots are sized from them
            ckey_base += (cfg.max_batch, chunk)
        self._prefill_jits = {
            S: compileobs.jit(_mk_prefill(), "serving.prefill", site=_SITE,
                              graph_key=gkey + ("prefill", S), aot=True,
                              cache_key=("serving.prefill",) + ckey_base
                              + (S,), **donate)
            for S in cfg.prefill_buckets()}
        self._decode_jits = {
            B: compileobs.jit(_mk_decode(), "serving.decode", site=_SITE,
                              graph_key=gkey + ("decode", B, chunk),
                              aot=True,
                              cache_key=("serving.decode",) + ckey_base
                              + (B, chunk), **decode_donate)
            for B in cfg.decode_buckets()}

        # ---- speculative decoding: draft model + verify pass ----------
        # two more compileobs program families riding the same nonce-free
        # persistent-cache pattern: `serving.draft` (the proposal model's
        # prefill + one-token decode over its own pages) and
        # `serving.verify` (the target scoring all spec_k+1 window
        # positions in ONE multi-query paged-attention pass). Fixed k per
        # engine: the verify window is a static shape, so compile counts
        # stay flat after bucket warmup — no per-k recompiles.
        self._draft_params = None
        self._draft_kp = self._draft_vp = None
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_draft_s = 0.0
        self._spec_verify_s = 0.0
        if self._spec:
            dcfg = _model.draft_config(cfg, cfg.draft)
            self.draft_config = dcfg
            if dcfg.key() == cfg.key():
                # self-drafting: the draft IS the target (shared device
                # params) — proposals match the verify pass and
                # acceptance sits near 1.0 (the test harness's mode)
                self._draft_params = self.params
            else:
                self._draft_params = _model.as_device_params(
                    _model.random_params(dcfg, seed=seed), dcfg,
                    device=device)
            import jax.numpy as jnp

            dshape, _ = dcfg.cache_specs().full.shape(cfg.num_blocks,
                                                      cfg.block_size)
            dk = jnp.zeros(dshape, cfg.kv_dtype)
            dv = jnp.zeros(dshape, cfg.kv_dtype)
            if device is not None:
                dk = jax.device_put(dk, device)
                dv = jax.device_put(dv, device)
            self._draft_kp, self._draft_vp = dk, dv

            def _mk_draft_prefill():
                def _dprefill(params, tokens, length, block_table,
                              k_pages, v_pages):
                    return _pack_fetch(*_model.prefill(
                        params, tokens, length, block_table, k_pages,
                        v_pages, dcfg))
                return _dprefill

            def _mk_draft_decode():
                def _ddecode(params, tokens, positions, block_tables,
                             context_lens, k_pages, v_pages):
                    return _pack_fetch(*_model.decode(
                        params, tokens, positions, block_tables,
                        context_lens, k_pages, v_pages, dcfg))
                return _ddecode

            def _mk_verify():
                def _verify(params, tokens, positions, block_tables,
                            context_lens, k_pages, v_pages):
                    return _pack_fetch(*_model.extend(  # fwlint: disable=trace-impure — module-level verify-step function, not a container mutation
                        params, tokens, positions, block_tables,
                        context_lens, k_pages, v_pages, cfg))
                return _verify

            dkey_base = dcfg.key() + (cfg.block_size, cfg.num_blocks,
                                      str(cfg.kv_dtype))
            self._draft_prefill_jits = {
                S: compileobs.jit(_mk_draft_prefill(), "serving.draft",
                                  site=_SITE,
                                  graph_key=gkey + ("draft.prefill", S),
                                  aot=True,
                                  cache_key=("serving.draft.prefill",)
                                  + dkey_base + (S,), **donate)
                for S in cfg.prefill_buckets()}
            self._draft_decode_jits = {
                B: compileobs.jit(_mk_draft_decode(), "serving.draft",
                                  site=_SITE,
                                  graph_key=gkey + ("draft.decode", B),
                                  aot=True,
                                  cache_key=("serving.draft.decode",)
                                  + dkey_base + (B,), **step_donate)
                for B in cfg.decode_buckets()}
            self._verify_jits = {
                B: compileobs.jit(_mk_verify(), "serving.verify",
                                  site=_SITE,
                                  graph_key=gkey + ("verify", B, cfg.spec_k),
                                  aot=True,
                                  cache_key=("serving.verify",) + ckey_base
                                  + (B, cfg.spec_k), **step_donate)
                for B in cfg.decode_buckets()}
            self._draft_prefill_fn = \
                lambda params, toks, L, table, kp, vp: \
                self._draft_prefill_jits[toks.shape[1]](*self._as_pack(
                    dcfg, params, toks, L, table, kp, vp))
            self._draft_decode_fn = \
                lambda params, toks, poss, tables, ctx, kp, vp: \
                self._draft_decode_jits[toks.shape[0]](
                    params, toks, poss, tables, ctx, kp, vp)
            self._verify_fn = \
                lambda params, toks, poss, tables, ctx, kp, vp: \
                self._verify_jits[toks.shape[0]](
                    params, toks, poss, tables, ctx, kp, vp)

    # ------------------------------------------------------------------ API
    def submit(self, prompt, max_new_tokens, eos_id=None, request_id=None,
               timeout_s=None):
        """Enqueue a request; returns the :class:`Request` (its
        ``done_event`` is set when it finishes — block on it from serving
        threads, or drive :meth:`step` yourself). ``request_id`` is the
        wire identity threaded through every lifecycle event and trace
        lane (auto-assigned from the rid when omitted). ``timeout_s``
        sets the request's deadline (default from
        ``MXNET_SERVING_DEFAULT_TIMEOUT_MS``; None/0 = none): once it
        expires the request is swept to TIMED_OUT and its KV blocks
        return to the pool. Raises :class:`ServingOverloadError` (with a
        ``retry_after_s`` hint) when the engine is draining or the
        admission queue is at ``cfg.max_queue`` — shed, not enqueued."""
        if timeout_s is None and self.config.default_timeout_ms > 0:
            timeout_s = self.config.default_timeout_ms / 1000.0
        req = Request(prompt, max_new_tokens, eos_id=eos_id,
                      request_id=request_id, timeout_s=timeout_s)
        total = len(req.prompt) + req.max_new_tokens
        if total > self.config.max_len:
            raise ValueError(
                "request needs %d total positions > max_len %d (the "
                "position-embedding table bounds every stream)"
                % (total, self.config.max_len))
        if self.pool.blocks_for(total) > self.pool.num_usable:
            raise ValueError(
                "request needs %d KV blocks > pool capacity %d"
                % (self.pool.blocks_for(total), self.pool.num_usable))
        req.done_event = threading.Event()
        with self._work:
            # checked under the lock: an abort() racing an unlocked check
            # could drain the queues first, leaving this request enqueued
            # behind a dead driver with a done_event nobody will ever set
            if self._aborted is not None:
                raise RuntimeError(self._aborted)
            if self._draining:
                telemetry.counter("serving.shed").inc()
                self._n_shed += 1
                raise ServingOverloadError(
                    "engine is draining (admission closed)",
                    reason="draining",
                    retry_after_s=retry_after_s(self))
            if (self.config.max_queue
                    and len(self.scheduler.waiting) >= self.config.max_queue):
                telemetry.counter("serving.shed").inc()
                self._n_shed += 1
                raise ServingOverloadError(
                    "admission queue full (%d waiting >= max_queue %d)"
                    % (len(self.scheduler.waiting), self.config.max_queue),
                    reason="queue_full",
                    retry_after_s=retry_after_s(self))
            self.obs.request_submitted(req)
            self.scheduler.add(req)
            self._work.notify_all()
        return req

    def cancel(self, req):
        """Mark ``req`` for cancellation (safe from any thread — serve.py
        calls it when the client connection drops). The next step's sweep
        moves it to CANCELLED and frees its KV blocks; a WAITING request
        is dropped without ever being admitted. No-op once terminal."""
        with self._work:
            if not req.finished():
                req.cancelled = True
                self._work.notify_all()

    def cancel_all(self):
        """Cancel every non-terminal request (the drain deadline passed:
        stragglers are cut loose rather than holding the process open).
        Returns the number marked."""
        with self._work:
            n = 0
            for req in (list(self.scheduler.running)
                        + list(self.scheduler.waiting)):
                if not req.finished():
                    req.cancelled = True
                    n += 1
            if n:
                self._work.notify_all()
            return n

    def start_drain(self):
        """Close admission: new submits are shed with
        ``reason="draining"`` while inflight work keeps stepping to
        completion. ``has_work()`` going False signals the drain is done
        (serve.py's drain sequence; idempotent)."""
        with self._work:
            if not self._draining:
                self._draining = True
                telemetry.counter("serving.drains").inc()
                telemetry.event("serving.drain", engine=self.engine_id,
                                waiting=len(self.scheduler.waiting),
                                active=len(self.scheduler.running))
                self._work.notify_all()
            self._flush()

    @property
    def draining(self):
        # under the lock: handler threads poll this against the driver's
        # locked writes — an unlocked read observes the flag torn against
        # the drain bookkeeping it summarizes (fwlint unguarded-shared-write)
        with self._lock:
            return self._draining

    @property
    def aborted(self):
        """The abort cause message, or None while the engine is live."""
        with self._lock:
            return self._aborted

    def has_work(self):
        with self._lock:
            return self.scheduler.has_work()

    def step(self):
        """One engine iteration: schedule, prefill admissions, fused decode,
        retire finished requests. Returns the requests that finished.
        Whoever steps by hand reads the step whole when this returns:
        what it set aside for later (:meth:`_set_aside`) is carried out
        first; :meth:`run_loop` leaves it to the next step's dispatch.

        A failure escaping the step (device error, XLA crash) aborts the
        engine before re-raising — the pool pages may have been donated
        into the failed dispatch and cannot be trusted, so EVERY driver
        (run_loop, :meth:`generate`, step-polling loops) gets the
        same contract: pending requests fail loudly, waiters wake, later
        submits refuse."""
        return self._guarded_step(defer=False)

    def _guarded_step(self, defer):
        try:
            # the step span opens BEFORE the lock: a driver queued behind
            # submit() shows as serving.step.lock on the trace, not as a gap
            # fwlint: disable=unguarded-shared-write — _steps is read for a label on the trace; only the stepping thread writes it
            with telemetry.span("serving.step", _CAT, step=self._steps):
                return self._step(defer)
        except Exception as exc:
            self.abort(exc)
            raise

    def _step(self, defer):
        """The step's sections, each under its span (docs/observability.md
        has the table): lock, schedule, prefills, schedule again after a
        prefill, decode, retire. While telemetry is on, a non-empty step
        also leaves ONE record of itself (``obs.LoopRecord``: each
        section's seconds from its span, the two host gaps) in the
        engine's ring and on its ``serving.step_timeline`` event — once
        what the step set aside has been carried out: during the next
        step with ``defer``, before this returns without."""
        with telemetry.span("serving.step.lock", _CAT) as lock:
            self._lock.acquire()
        try:
            # opened with the lock held: a window over the records' ``ts``
            # and two reads of stats() (under this lock) cut the steps at
            # the same places
            rec = self._rec = open_record() if telemetry.enabled() else None
            if rec is None:
                self._fetch_end = None   # no gap across a stretch unrecorded
            self._gap_after = "chunk"
            self._book("lock_s", lock)
            with self._schedule_span() as sched:
                # chaos: injected per-step latency (trips deadlines/SLOs
                # without faking clocks) — docs/fault_tolerance.md
                # fwlint: disable=lock-order — the injected delay models a slow device dispatch, which blocks under the step lock by design
                fault.hit("slow_step")
                # deadline/cancellation sweep BEFORE scheduling: expired
                # or abandoned requests release their KV blocks this step
                # instead of decoding on for a consumer that is gone, and
                # _drain_failed below routes them out the public channels
                self.scheduler.sweep()
                plan = self.scheduler.schedule()
                for req in plan.preempted:
                    self.obs.request_preempted(req)
                for req in plan.prefills:
                    self.obs.request_admitted(req)
                failed = self._drain_failed()
                if plan.empty():
                    return self._step_end(failed, defer)
                n_preempted = len(plan.preempted)
                decodes = () if plan.prefills else self._decodable()
            self._book("schedule_s", sched)
            if plan.prefills:
                # fwlint: disable=lock-order — fault.hit("dispatch_error") in the callee can inject a delay; real dispatch blocks under the step lock identically
                self._run_prefills(plan.prefills)
                with self._schedule_span() as sched:
                    # a prompt that exactly filled its blocks writes its
                    # first decode token at a fresh block boundary — back
                    # that slot with a real block NOW or the write lands in
                    # trash and the position's K/V is silently lost
                    late = self.scheduler.ensure_decode_headroom()
                    for req in late:
                        self.obs.request_preempted(req)
                    n_preempted += len(late)
                    failed += self._drain_failed()
                    decodes = self._decodable()
                self._book("schedule_s", sched)
            if decodes and self._spec:
                # fwlint: disable=lock-order — injected dispatch fault may stall; matches real device-dispatch blocking under the step lock
                fetched = self._run_spec_decode(decodes)
            elif decodes:
                # fwlint: disable=lock-order — injected dispatch fault may stall; matches real device-dispatch blocking under the step lock
                fetched = self._run_decode(decodes)
            # the device idles from the chunk's fetch to the next step's
            # first dispatch: this section holds what the scheduler, the
            # next build or a waiting caller reads, and sets the rest aside
            with telemetry.span("serving.retire", _CAT) as retire:
                booking = None
                if decodes:
                    note = (self._note_spec_decode if self._spec
                            else self._note_decode)
                    booking = note(decodes, fetched, retire)
                # one stretch, so a span of its own: the trace and the
                # idle gaps' labels name it too
                with telemetry.span("serving.retire.finish", _CAT) as finish:
                    finished = [r for r in list(self.scheduler.running)
                                if r.finished()]
                    for req in finished:
                        self.scheduler.finish(req)
                        self._retire(req)
                self._book("retire_finish_s", finish)
                self._steps += 1
                n_finished = len(finished) + len(failed)
                retire.set(finished=n_finished)
                clock = self._clock()
                t0 = clock()
                # the record's counts and the event's occupancy, queue and
                # pool are READ here, as the step leaves them; the record
                # is closed and the event made when the item is carried out
                self._set_aside(booking, None if rec is None else (
                    dict(step=self._steps, prefills=len(plan.prefills),
                         lanes=len(decodes), finished=n_finished),
                    dict(occupancy=len(decodes),
                         admitted=len(plan.prefills), preempted=n_preempted,
                         queue=len(self.scheduler.waiting),
                         running=len(self.scheduler.running),
                         kv_used=self.pool.used(),
                         kv_free=self.pool.available(),
                         kv_frag_slots=self.scheduler.frag_slots(),
                         stopped_by=self.scheduler.last_stop)))
                if rec is not None:
                    rec["retire_counters_s"] += clock() - t0
            self._book("retire_s", retire)
            return self._step_end(finished + failed, defer)
        finally:
            self._rec = None
            self._lock.release()

    def _step_end(self, out, defer):
        """A step's return, its lock still held: without ``defer`` the
        step's own item is carried out first, on no step's record."""
        self._rec = None
        if not defer:
            self._flush()
        return out

    # ---- what a step sets aside ---------------------------------------
    def _set_aside(self, booking, closing):
        """Leave for later what no dispatch needs of the step that ends:
        ``booking``, the deferred half of the chunk's bookkeeping (a
        method and its arguments, from :meth:`_note_decode` or
        :meth:`_note_spec_decode`; None for a step without a chunk), the
        throughput window, and ``closing`` the step's record (its counts
        and the ``serving.step_timeline`` event's readings; None while
        telemetry is off). ONE item: one still waiting (no dispatch
        followed it: a step whose prompts all ended at their first token)
        is carried out first."""
        self._flush()
        self._pending = (self._rec, booking, closing)

    def _flush(self, hidden=False):
        """Carry out the item :meth:`_set_aside` left, if any. ``hidden``:
        the caller has just returned from the step's last dispatch call
        before a blocking fetch, so the device works meanwhile (step N's
        item under step N+1's chunk) and none of this is in a host gap.
        Every other caller — ``stats()``, the public ``step()``, a wait on
        an empty queue, ``run_loop``'s return, ``abort``, a drain's start,
        the programs run outside a step — holds the step lock and flushes
        before it reads or runs anything: no reader sees a chunk unbooked
        or half booked. The seconds go to the record of the step under
        way, if one is (``deferred_s``); ``deferred_hidden`` to the item's
        own."""
        item, self._pending = self._pending, None
        if item is None:
            return
        rec, booking, closing = item
        with telemetry.span("serving.retire.deferred", _CAT,
                            hidden=int(hidden)) as deferred:
            noted = {}
            if booking is not None:
                book, args = booking
                noted = book(*args)
                deferred.set(**noted)
            self._refresh_throughput()
            if rec is not None:
                counts, timeline = closing
                rec["chunk_steps"] = noted.pop("steps", 0)
                rec["passes"] += noted.pop("passes", 0)
                rec.update(noted, deferred_hidden=int(hidden), **counts)
                self.obs.step_timeline(rec, **timeline)
        self._book("deferred_s", deferred)

    # ---- the step's record (obs.LoopRecord) ---------------------------
    def _book(self, field, span):
        """Add a closed span's seconds to the step's record."""
        rec = self._rec
        if rec is not None and span.seconds is not None:
            rec[field] += span.seconds

    def _clock(self):
        """``time.perf_counter`` while the step keeps a record, else a
        clock that reads nothing: telemetry off costs no clock read."""
        return time.perf_counter if self._rec is not None else _no_clock

    def _fetched(self):
        """A blocking fetch has just returned: nothing is in flight, and
        the device idles from here until the next dispatch call returns.
        Stale the day a fetch is made late (obs.LoopRecord)."""
        if self._rec is not None:
            self._fetch_end = time.perf_counter()
            self._idle_s = 0.0

    def _dispatched(self, span):
        """A dispatch call has just returned, inside its open ``span``. If
        it closes one of the step's two host gaps (the step's first
        dispatch: the gap since the step before's last fetch, less the
        loop's idle waits; the chunk's dispatch after a group of prefills:
        the gap since the group's last fetch), book the gap and say on the
        span where the host saw it end."""
        rec, after = self._rec, self._gap_after
        if rec is None or after is None:
            return
        self._gap_after = None
        if self._fetch_end is None:
            return      # nothing was fetched before: an engine's first step
        gap = time.perf_counter() - self._fetch_end - self._idle_s
        rec["gap_%s_s" % after] = gap
        span.set(gap_us=int(gap * 1e6), after=after)

    def _schedule_span(self):
        return telemetry.span("serving.schedule", _CAT,
                              waiting=len(self.scheduler.waiting),
                              running=len(self.scheduler.running))

    def _decodable(self):
        """The streams this step decodes, each write slot backed by a
        private block (part of the schedule section)."""
        decodes = self.scheduler.decodable()
        if decodes:
            # copy-on-write safety net: a write slot backed by a
            # SHARED block gets a private bit-exact copy first
            # (structurally unreachable — prefix matches cover
            # only full replay blocks, writes land past them —
            # but the pool invariant must hold unconditionally)
            self._cow_guard(decodes)
        return decodes

    def run_loop(self, stop_event=None, idle_wait_s=0.05):
        """Drive :meth:`step` until ``stop_event`` is set, sleeping on the
        submit condition while idle (the serve.py driver thread).

        A step failure must not strand clients blocked on their
        ``done_event`` forever behind a silently dead driver: ``step()``
        itself aborts the engine — every queued + running request is
        FAILED with the error and woken, later submits refuse — and the
        re-raise propagates here so the driver thread's death is
        observable (``Thread.is_alive()`` backs serve.py's
        ``/healthz``).

        What a step set aside (:meth:`_set_aside`) is carried out under
        the next step's dispatch; before a wait on an empty queue and
        before this returns, here."""
        try:
            while stop_event is None or not stop_event.is_set():
                # one more take of the lock a step than the step's own, and
                # it stays: the lock is not fair, and a caller blocked in
                # submit() gets its second chance a step here. Asking under
                # the step's own lock read +15 ms on the open loop's
                # queue_wait_p95_ms, three seeds of three (PERF.md, PR 39)
                with self._work:
                    if not self.scheduler.has_work():
                        # no step follows: the last one's item is carried
                        # out on the idle device
                        self._flush()
                        # idle steps never run, so decay the sliding
                        # tokens/sec window here or it freezes at its last
                        # loaded value on a quiet server
                        self._refresh_throughput()
                        # an empty queue, by name: device idle under this
                        # span is idle that no change to the loop can take
                        # away
                        with telemetry.span("serving.loop.idle",
                                            _CAT) as idle:
                            self._work.wait(timeout=idle_wait_s)
                        # no fault of the loop's: out of the step's
                        # gap_chunk_s
                        self._idle_s += idle.seconds or 0.0
                        if not self.scheduler.has_work():
                            continue
                self._guarded_step(defer=True)
        finally:
            with self._lock:
                self._flush()

    def abort(self, exc):
        """Fail every queued and running request (the driver died mid-
        step, or the caller is shutting down hard). After an abort the
        engine refuses new submits — the pool pages may have been donated
        into the failed dispatch and cannot be trusted.

        Under a supervisor (``salvage_on_abort`` set), non-terminal
        requests are PARKED instead of failed: blocks dropped (the pool
        dies with the engine), tokens-so-far kept, done_event left unset
        — :meth:`pop_salvaged` hands them to the supervisor, which
        :meth:`resubmit`-s them into a fresh engine where the replay
        prefill (recompute-preemption style) rebuilds their cache and
        greedy decode finishes them bit-identical to an unfaulted run."""
        msg = "serving engine aborted: %r" % (exc,)
        with self._lock:
            # the chunks whose tokens were delivered are the chunks booked
            self._flush()
            self._aborted = msg
            self._drain_failed()   # scheduler failures the step never saw
            reqs = list(self.scheduler.running) + list(self.scheduler.waiting)
            self.scheduler.running.clear()
            self.scheduler.waiting.clear()
            if self.salvage_on_abort:
                now = time.time()
                for req in reqs:
                    if req.finished():
                        continue
                    was_running = req.state != WAITING
                    req.blocks = []   # pool accounting is moot post-abort
                    req.wblocks, req.slot = [], None
                    req.shared_blocks = 0
                    req.context_len = 0
                    req.state = WAITING
                    if was_running:
                        # the restart wall is replay overhead, same clock
                        # as recompute preemption — the 5-phase sum still
                        # partitions the request's end-to-end wall
                        req.preemptions += 1
                        req.preempted_t = now
                        telemetry.counter("serving.preemptions").inc()
                        self.obs.request_preempted(req)
                    self._salvaged.append(req)
                return
            for req in reqs:
                req.blocks = []   # pool accounting is moot post-abort
                req.wblocks, req.slot = [], None
                req.state = FAILED
                req.error = msg
                req.finish_t = time.time()
                telemetry.counter("serving.requests_failed").inc()
                self.obs.request_finished(req, failed=True)
                if req.done_event is not None:
                    req.done_event.set()
            self._finished.extend(reqs)
            self._n_failed += len(reqs)

    def pop_salvaged(self):
        """Drain the requests :meth:`abort` parked for the supervisor
        (empty unless ``salvage_on_abort`` was set before the abort)."""
        with self._lock:
            out, self._salvaged = self._salvaged, []
            return out

    def resubmit(self, req):
        """Re-admit a request salvaged from a dead engine: it keeps its
        identity, done_event, trace clock, and generated-so-far tokens —
        ``replay_tokens()`` re-prefills prompt + emitted tokens exactly
        like a recompute preemption, so greedy decode continues the
        stream bit-identically. The supervisor calls this on the FRESH
        engine for every survivor, in original submit order."""
        with self._work:
            if self._aborted is not None:
                raise RuntimeError(self._aborted)
            telemetry.event("serving.request", request_id=req.request_id,
                            engine=self.engine_id, state="resubmitted",
                            generated=len(req.generated),
                            preemptions=req.preemptions)
            self.scheduler.add(req)
            self._work.notify_all()
        return req

    def warmup(self, prefill_buckets=None):
        """Compile every prefill length bucket and decode batch bucket in
        one pass (one throwaway dispatch each, all-trash block tables, no
        requests involved) so the first real traffic pays zero compile
        wall and the steady-state compile count is flat from step one.
        ``prefill_buckets`` warms only those prompt-length buckets (a
        deployment whose lengths cannot reach the others); the decode
        buckets are always all warmed. The dispatches are asynchronous:
        the pool's pages are ready when the last program has run."""
        cfg = self.config
        if prefill_buckets is None:
            prefill_buckets = cfg.prefill_buckets()
        unknown = sorted(set(prefill_buckets) - set(cfg.prefill_buckets()))
        if unknown:
            raise ValueError("no prefill bucket %s (buckets %s)"
                             % (unknown, cfg.prefill_buckets()))
        with self._lock:
            self._flush()
            for S in prefill_buckets:
                toks = np.zeros((1, S), np.int32)
                table = np.zeros(S // cfg.block_size, np.int32)
                self._dispatch_prefill(toks, [(0, 1)], table)
            for B in cfg.decode_buckets():
                toks = np.zeros(B, np.int32)
                poss = np.zeros(B, np.int32)
                tables = np.zeros((B, self._nb_max), np.int32)
                ctx = np.ones(B, np.int32)
                self._dispatch_decode(toks, poss, tables, ctx)
            if self._spec:
                # spec adds three program families — warm them too or the
                # first spec step pays draft + verify compile wall at once
                for S in prefill_buckets:
                    toks = np.zeros((1, S), np.int32)
                    table = np.zeros(S // cfg.block_size, np.int32)
                    _t, _l, dkp, dvp = self._draft_prefill_fn(
                        self._draft_params, toks, np.int32(1), table,
                        self._draft_kp, self._draft_vp)
                    self._draft_kp, self._draft_vp = dkp, dvp
                T = self.spec_k + 1
                for B in cfg.decode_buckets():
                    toks = np.zeros(B, np.int32)
                    poss = np.zeros(B, np.int32)
                    tables = np.zeros((B, self._nb_max), np.int32)
                    ctx = np.ones(B, np.int32)
                    _t, _l, dkp, dvp = self._draft_decode_fn(
                        self._draft_params, toks, poss, tables, ctx,
                        self._draft_kp, self._draft_vp)
                    self._draft_kp, self._draft_vp = dkp, dvp
                    toks2 = np.zeros((B, T), np.int32)
                    poss2 = np.zeros((B, T), np.int32)
                    ctx2 = np.ones((B, T), np.int32)
                    _t, _l, kp, vp = self._verify_fn(
                        self.params, toks2, poss2, tables, ctx2,
                        self.pool.k_pages, self.pool.v_pages)
                    self.pool.k_pages, self.pool.v_pages = kp, vp

    def prefill_logits(self, tokens, decode_from=None):
        """The model's next-token logits ``(V,)`` float32 after ``tokens``,
        from the prefill program of their length bucket: the served
        arithmetic (types, kernels, experts) on a text of the caller's
        choice — to score a fixed text, or to hold served logits against a
        reference. No request is involved and nothing is cached or booked:
        the program's K/V writes go to the trash block through an all-zero
        table, as :meth:`warmup`'s do.

        With ``decode_from = n`` the first ``n`` tokens go through prefill
        into a scratch stream and the rest one by one through the decode
        program of batch 1 with the given tokens forced
        (:meth:`decode_logits` of one text); the result is the last step's
        logits: prefill-then-decode through every kind of per-stream state,
        against a reference's full forward."""
        cfg = self.config
        n = len(tokens)
        if not 1 <= n <= cfg.max_len:
            raise ValueError("%d tokens: need 1..max_len (%d)"
                             % (n, cfg.max_len))
        if decode_from is not None:
            return self.decode_logits([tokens], [decode_from])[0]
        S = _bucket_for(n, cfg.prefill_buckets())
        toks = np.zeros((1, S), np.int32)
        toks[0, :n] = tokens
        table = np.zeros(S // cfg.block_size, np.int32)
        with self._lock:
            self._flush()
            _t, logits = self._dispatch_prefill(toks, [(0, n)], table)
        return np.asarray(logits, np.float32)[0]  # fwlint: disable=device-escape — the logits are what the caller asked for

    def decode_logits(self, texts, starts):
        """Each text's next-token logits ``(len(texts), V)`` float32, its
        first ``starts[i]`` tokens through prefill into a scratch stream of
        its own (blocks and, for a model that has them, a state slot and
        window blocks, booked for the call and returned after it; the
        prefixes laid end to end in the programs a step's packs run) and the
        rest forced one step at a time through the decode program — every
        text TOGETHER, a lane a text, at the batch bucket of ``len(texts)``:
        ragged contexts side by side as a serving step has them, a lane
        dead (``steps_left`` 0, a padded row's contract) once its text has
        ended. Nothing is booked."""
        cfg = self.config
        if not 1 <= len(texts) <= cfg.max_batch:
            raise ValueError("%d texts: need 1..max_batch (%d)"
                             % (len(texts), cfg.max_batch))
        starts = [int(n) for n in starts]
        for text, n in zip(texts, starts):
            if not 1 <= n < len(text) <= cfg.max_len:
                raise ValueError(
                    "decode_from must be in 1..%d, and at most max_len (%d) "
                    "tokens" % (len(text) - 1, cfg.max_len))
        B = _bucket_for(len(texts), cfg.decode_buckets())
        lanes = [Request(text[:n], 1) for text, n in zip(texts, starts)]
        out = np.zeros((len(texts), cfg.vocab_size), np.float32)
        with self._lock:
            self._flush()
            try:
                for req, text, n in zip(lanes, texts, starts):
                    req.blocks = self.pool.alloc(
                        self.pool.blocks_for(len(text)))
                    if self.streams is not None:
                        self.streams.admit(req, n)
                # the texts' prefixes through the programs a step's packs
                # run, cut as a step cuts them: what reads these logits
                # holds a PACK to its reference
                for pack, S in self._cut_packs(lanes):
                    toks, spans, table, wtable = self._lay_pack(pack, S)
                    self._dispatch_prefill(toks, spans, table, wtable,
                                           pack[0][0].slot or 0)
                for step in range(max(len(t) - n
                                      for t, n in zip(texts, starts))):
                    # fresh arrays a step: a dispatch may still read the last
                    toks, poss, left, slots = (np.zeros(B, np.int32)
                                               for _ in range(4))
                    ctx = np.ones(B, np.int32)
                    tables, wtables = (np.zeros((B, self._nb_max), np.int32)
                                       for _ in range(2))
                    for i, (req, text, n) in enumerate(
                            zip(lanes, texts, starts)):
                        pos = n + step
                        if pos >= len(text):
                            continue
                        if self.streams is not None:
                            self.streams.ensure(req, pos)
                        toks[i], poss[i], ctx[i], left[i] = (
                            text[pos], pos, pos + 1, 1)
                        tables[i] = self._table_row(req.blocks, self._nb_max)
                        wtables[i] = self._table_row(req.wblocks,
                                                     self._nb_max)
                        slots[i] = req.slot or 0
                    _t, logits = self._dispatch_decode(
                        toks, poss, tables, ctx, wtables, slots, left=left)
                    ended = [i for i, (text, n) in enumerate(
                        zip(texts, starts)) if n + step == len(text) - 1]
                    if ended:
                        out[ended] = np.asarray(logits, np.float32)[ended]  # fwlint: disable=device-escape — the logits are what the caller asked for
            finally:
                for req in lanes:
                    if req.blocks:
                        self.pool.free(req.blocks)
                    if self.streams is not None:
                        self.streams.release(req)
        return out

    def generate(self, prompts, max_new_tokens, eos_id=None, timeout_s=None):
        """Convenience batch API: submit every prompt, drive steps until
        all finish, return each request's generated tokens (in input
        order). Raises if any request failed.

        ``timeout_s`` bounds each request (threaded to :meth:`submit`):
        the per-step sweep moves expired requests to TIMED_OUT, so the
        drive loop terminates instead of decoding past a blown deadline.
        An abort — this loop's own step raising, or another thread
        killing the engine — surfaces as a RuntimeError carrying the
        classified cause rather than a silent spin."""
        if isinstance(max_new_tokens, int):
            max_new_tokens = [max_new_tokens] * len(prompts)
        reqs = [self.submit(p, n, eos_id=eos_id, timeout_s=timeout_s)
                for p, n in zip(prompts, max_new_tokens)]
        while any(not r.finished() for r in reqs):
            # an external abort() cleared the scheduler queues but this
            # loop's snapshot still holds the requests — re-stepping a
            # dead engine forever would spin without ever finishing them
            msg = self.aborted   # locked read: abort() publishes under it
            if msg is not None:
                raise RuntimeError(msg)
            self.step()
        bad = [r for r in reqs if r.state != FINISHED]
        if bad:
            raise RuntimeError("requests failed: %s"
                               % [(r.rid, r.state, r.error) for r in bad])
        return [list(r.generated) for r in reqs]

    def pop_finished(self):
        """Drain every request retired since the last call — FINISHED and
        FAILED both (check ``req.state``/``req.error``); a polling driver
        must never lose a request to a silent scheduler-side failure.
        The backlog is bounded (``max(256, 8 * max_batch)``) so drivers
        that consume ``done_event`` instead of polling don't accumulate
        one retired Request per call served — drain at least once per
        step to observe every retiree."""
        with self._lock:
            out = list(self._finished)
            self._finished.clear()
            return out

    def _drain_failed(self):
        """Requests the scheduler terminated — FAILED, and since the
        resilience layer also TIMED_OUT/CANCELLED — surface through the
        same channels as successes: appended to the ``pop_finished()``
        queue and returned from :meth:`step`. ``_terminate`` already
        stamped ``finish_t``, bumped the per-state counter and woke the
        ``done_event``; obs reads the terminal state off the request."""
        failed = self.scheduler.pop_failed()
        for req in failed:
            self.obs.request_finished(req)
            if req.state == TIMED_OUT:
                self._n_timed_out += 1
            elif req.state == CANCELLED:
                self._n_cancelled += 1
            else:
                self._n_failed += 1
        self._finished.extend(failed)
        return failed

    # ------------------------------------------------------------ internals
    @staticmethod
    def _table_row(blocks, width):
        # the admission grant includes the first decode slot's headroom
        # block, so a boundary-length replay holds one block more than its
        # prefill bucket's table width — clip; prefill never reads it
        row = np.zeros(width, np.int32)
        n = min(len(blocks), width)
        row[:n] = blocks[:n]
        return row

    def _build_caches(self, cfg, device):
        """``(pool, window_pool, state, streams)``, each of the page spec
        the configuration names (``cache_specs``). Every model has the
        full-length pool; a one-block model has nothing else (``None``). A
        model with ``layer_kinds`` — one cache layer a "full" layer: the
        "cross" layers read it and have none of their own — has beside it
        the window pool of its "swa" layers, the state slots of its
        "mamba" or "kda" layers, and the manager that books a stream's share of
        the two."""
        from .kv_cache import StateSlots, StreamState

        full, window = cfg.cache_specs()
        pool = KVBlockPool(
            full, cfg.num_blocks, cfg.block_size, dtype=cfg.kv_dtype,
            device=device, prefix_cache=cfg.prefix_cache and not cfg.hybrid)
        if not cfg.hybrid:
            return pool, None, None, None
        n_win = len(cfg.layers_of("swa"))
        n_ssm = len(cfg.layers_of("mamba", "kda"))
        # max_batch streams of window + one block of tokens and the slots
        # a decode chunk writes beyond its first, max_batch slots, and the
        # trash of each: running <= max_batch, so neither runs short. A
        # kind the model lacks keeps a two-block (two-slot) stand-in, so
        # that the step programs have one signature
        per_stream = -(-(cfg.window + cfg.block_size + self._chunk - 1)
                       // cfg.block_size)
        window_pool = KVBlockPool(
            window, cfg.max_batch * per_stream + 1 if n_win else 2,
            cfg.block_size, dtype=cfg.kv_dtype, device=device,
            prefix_cache=False, gauges=False)
        state = StateSlots(
            max(n_ssm, 1), cfg.max_batch + 1 if n_ssm else 2,
            *cfg.slot_shapes(),
            conv_dtype=self.params["embed_weight"].dtype, device=device)
        streams = StreamState(window_pool if n_win else None,
                              state if n_ssm else None, cfg.window)
        pool.extra_nbytes = window_pool.nbytes() + state.nbytes()
        return pool, window_pool, state, streams

    def _dispatch_prefill(self, toks, spans, table, wtable=None, slot=0):
        """Run the prefill program of ``toks``' bucket over the caches and
        keep what it hands back; ``(next tokens, logits)``, both on the
        device, a row a prompt the program takes (``model.pack_width``).
        ``spans``: the prompts' ``(first row, length)`` in ``toks``, one
        ``(0, n)`` for a prompt alone. ``wtable`` / ``slot``: a hybrid
        model's window-pool table and state slot (default: all trash)."""
        length = _model.pack_of(self.config, toks.shape[1], spans)
        if self.streams is None:
            tok, logits, kp, vp = self._prefill_fn(
                self.params, toks, length, table,
                self.pool.k_pages, self.pool.v_pages)
        else:
            if wtable is None:
                wtable = np.zeros_like(table)
            tok, logits, kp, vp, *aux = self._prefill_fn(
                self.params, toks, length, table,
                self.pool.k_pages, self.pool.v_pages, wtable,
                np.int32(slot), *self._aux())
            self._keep_aux(aux)
        self.pool.k_pages, self.pool.v_pages = kp, vp
        return tok, logits

    def _prefill_fn(self, params, toks, length, table, kp, vp, *aux):
        """The prefill program of ``toks``' rung (bucket dispatch: call
        sites pad to an exact bucket shape, so the padded dim indexes the
        wrapper table directly). ``length`` and ``table`` (and a hybrid
        model's window table, the first of ``aux``): what
        ``model.pack_of`` makes and ``model.pack_blocks`` entries, or a
        prompt's bare length and the blocks of its rung, which are made a
        pack of one here — the one prompt from row 0 that every rung's
        program takes, and the very executable a step's pack runs, loads
        or compiles."""
        return self._prefill_jits[toks.shape[1]](*self._as_pack(
            self.config, params, toks, length, table, kp, vp, *aux))

    def _as_pack(self, cfg, params, toks, length, table, *rest):
        """A prefill program's arguments with a bare length and a table of
        the rung's own blocks made the pack of one (:meth:`_prefill_fn`);
        a table as wide as ``cfg``'s program takes (a draft that takes one
        prompt is handed the target's, wider by empty entries)."""
        S = toks.shape[1]
        if np.ndim(length) == 0:
            length = _model.pack_of(cfg, S, [(0, int(length))])
        width = _model.pack_blocks(cfg, S, self.config.block_size)

        def whole(t):
            return np.concatenate(
                [t, np.zeros(max(width - len(t), 0), np.int32)])[:width]

        if cfg.hybrid:
            kp, vp, wtable, *rest = rest
            rest = [kp, vp, whole(wtable)] + rest
        return (params, toks, length, whole(table)) + tuple(rest)

    def _decode_fn(self, params, toks, poss, tables, ctx, kp, vp, *aux,
                   left=None, eos=None, n=1):
        """The decode program of ``toks``' batch bucket: ``n`` steps of the
        chunk, lane i for ``left[i]`` of them at most and until ``eos[i]``.
        The defaults are ONE step of every lane, so that the plain
        step's seven arguments (a warm-up's call) run, load or compile
        the very executable a chunk runs. Four results (the chunk's rows
        of tokens — with the experts' loads behind them —, logits, pages),
        and a model with ``layer_kinds``' four arrays."""
        B = toks.shape[0]
        if left is None:
            left = np.ones(B, np.int32)
        if eos is None:
            eos = np.full(B, -1, np.int32)
        # fwlint: disable=recompile-hazard — B is toks' bucket (the call sites pad to one) and n a traced np scalar: data, one executable a bucket
        return self._decode_jits[B](params, toks, poss, tables, ctx, left,
                                    eos, np.int32(n), kp, vp, *aux)

    def _dispatch_decode(self, toks, poss, tables, ctx, wtables=None,
                         slots=None, **chunk):
        """The same for the decode program of ``toks``' batch bucket;
        ``chunk``: :meth:`_decode_fn`'s ``left`` / ``eos`` / ``n``."""
        if self.streams is None:
            tok, logits, kp, vp = self._decode_fn(
                self.params, toks, poss, tables, ctx,
                self.pool.k_pages, self.pool.v_pages, **chunk)
        else:
            if wtables is None:
                wtables = np.zeros_like(tables)
            if slots is None:
                slots = np.zeros(len(toks), np.int32)
            tok, logits, kp, vp, *aux = self._decode_fn(
                self.params, toks, poss, tables, ctx,
                self.pool.k_pages, self.pool.v_pages, wtables, slots,
                *self._aux(), **chunk)
            self._keep_aux(aux)
        self.pool.k_pages, self.pool.v_pages = kp, vp
        return tok, logits

    def _aux(self):
        return (self.window_pool.k_pages, self.window_pool.v_pages,
                self.state.conv, self.state.ssm)

    def _keep_aux(self, aux):
        (self.window_pool.k_pages, self.window_pool.v_pages,
         self.state.conv, self.state.ssm) = aux

    def _run_prefills(self, reqs):
        """Run the step's admitted prompts as ONE GROUP of PACKS: the
        prompts, in plan order, are laid end to end into as few prefill
        programs as hold them (:meth:`_cut_packs`), and every pack's
        program is dispatched before the first blocking fetch. Pack i+1's
        program queues behind pack i's on the device (the pages and a
        hybrid model's caches chain from one call into the next, device to
        device), the host builds pack i+1's arrays while the device runs
        pack i, and books pack i's requests while it runs pack i+1: a
        group exposes one host gap, after its last program. Every first
        token is on the host when this returns: nothing is in flight when
        the step schedules its decode. A group of one prompt is a dispatch
        and its fetch, as a prompt alone always was. Where no chunk follows
        the group, its last dispatch is the step's last before a blocking
        fetch: the step before's item (:meth:`_flush`) runs under it."""
        packs = self._cut_packs(reqs)
        grouped = len(packs) > 1
        flights = [self._start_prefill(pack, S, grouped)
                   for pack, S in packs]
        # no chunk follows where every prompt ends at its first token and
        # nothing else decodes (an EOS there cannot be foreseen: _set_aside
        # then finds the item still waiting)
        if not (any(r.pending_token is not None or r.max_new_tokens > 1
                    for r in reqs)
                or any(r.state == DECODING for r in self.scheduler.running)):
            self._flush(hidden=True)
        for flight in flights:
            self._finish_prefill(*flight)
        # the chunk's dispatch closes the gap the group's last fetch opened
        self._gap_after = "group"
        self._prefill_prompts += len(reqs)
        self._prefill_groups += 1
        self._prefill_programs += len(packs)
        telemetry.histogram("serving.prefill.group").observe(len(reqs))
        telemetry.counter("serving.prefill.groups").inc()
        telemetry.counter("serving.prefill.syncs_saved").inc(len(reqs) - 1)
        for pack, _S in packs:
            telemetry.histogram("serving.prefill.pack").observe(len(pack))

    def _cut_packs(self, reqs):
        """The step's prompts, in plan order, as ``(pack, rung)``: a pack
        the ``(request, the tokens it prefills)`` of neighbours that share
        the program of ``rung`` rows, cut to compute the fewest rows
        (:func:`cut_packs`). A pack of several holds at most the prompts its
        program takes — one, for a model with state slots
        (``model.pack_width``) — and runs only in a rung whose program is
        compiled (``warmup(prefill_buckets=)`` or an earlier step's call: a
        deployment warms the rungs its lengths reach); a prompt alone runs,
        and compiles, its own rung as it always did: a pack compiles
        nothing."""
        cfg = self.config
        jits = [self._prefill_jits] + (
            [self._draft_prefill_jits] if self._spec else [])
        warm = [S for S in cfg.prefill_buckets()
                if all(j[S].compiled for j in jits)]
        replays = [req.replay_tokens() for req in reqs]
        # (a draft that takes one prompt a program holds the target to one)
        models = [cfg] + ([self.draft_config] if self._spec else [])
        runs = cut_packs(
            [-(-len(t) // _PACK_ALIGN) * _PACK_ALIGN for t in replays],
            cfg.prefill_buckets(), warm,
            min(_model.pack_width(m, warm[-1]) for m in models)
            if warm else 1)
        return [(list(zip(reqs[j:i], replays[j:i])), rung)
                for j, i, rung in runs]

    def _lay_pack(self, pack, S):
        """A pack's arrays: its prompts' tokens end to end in the rung of
        ``S`` rows, each from a multiple of ``PACK_ALIGN``; ``(tokens
        (1, S), spans [(first row, length), ...], write table, window
        table)``. In the tables a prompt's blocks stand behind those of
        the prompts before it, whole blocks each (``model.pack_blocks``
        entries); the entries behind the last stay 0: their writes go to
        the trash block."""
        cfg = self.config
        bs = cfg.block_size
        toks = np.zeros((1, S), np.int32)
        write_table, wtable = (
            np.zeros(_model.pack_blocks(cfg, S, bs), np.int32)
            for _ in range(2))
        spans, row, block = [], 0, 0
        for req, replay in pack:
            L = len(replay)
            nb = -(-L // bs)
            at = slice(block, block + nb)
            toks[0, row:row + L] = replay
            write_table[at] = self._table_row(req.blocks, nb)
            wtable[at] = self._table_row(req.wblocks, nb)
            # prefix sharing: blocks mapped from the index already hold
            # this prefix's K/V — route their WRITE entries to the trash
            # block so the scatter cannot touch a shared block
            # (copy-on-write contract; the logits are untouched, the
            # table only steers the scatter)
            if req.shared_blocks:
                write_table[at][:req.shared_blocks] = 0
            spans.append((row, L))
            row += -(-L // _PACK_ALIGN) * _PACK_ALIGN
            block += nb
        return toks, spans, write_table, wtable

    def _start_prefill(self, pack, S, grouped):
        """Build (:meth:`_lay_pack`) and dispatch ONE program, the rung of
        ``S`` rows, for ``pack``'s prompts; nothing of it is waited for.
        Returns what
        :meth:`_finish_prefill` takes.
        ``grouped``: other packs' programs run before the fetch, so the
        first tokens' copy to the host is asked for now, to start when the
        program ends and not when the host gets round to it."""
        args = {"request_id": pack[0][0].request_id,
                "prompt_len": sum(len(replay) for _req, replay in pack),
                "bucket": S, "prompts": len(pack)}
        with telemetry.span("serving.prefill.build", _CAT, **args) as build:
            toks, spans, write_table, wtable = self._lay_pack(pack, S)
            # compile-tally delta around the dispatch CALL (jax compiles
            # inside it): a bump means THIS call sat behind a cold prefill
            # bucket — that wall is the compile_stall of the requests in
            # it, not honest prefill time, and no part of it is the device
            # time of the prompts queued before it
            jits = [self._prefill_jits[S]] + (
                [self._draft_prefill_jits[S]] if self._spec else [])
            s0 = sum(j.compile_totals()[1] for j in jits)
            # chaos: injected dispatch failure — escapes step(), which
            # aborts the engine (the supervisor's restart trigger in the
            # chaos e2e)
            fault.hit("dispatch_error")
        self._book("prefill_build_s", build)
        t0 = time.time()
        with telemetry.span("serving.prefill.dispatch", _CAT,
                            **args) as dispatch:
            tok, _logits = self._dispatch_prefill(
                toks, spans, write_table, wtable, pack[0][0].slot or 0)
            self._dispatched(dispatch)
            if self._spec:
                # the draft caches the same replays through the same write
                # table into its OWN pages (its K/V never mixes with the
                # target's); shared blocks were draft-cached by the
                # prefix's original prefill, same as the target pages
                _dt, _dl, dkp, dvp = self._draft_prefill_fn(
                    self._draft_params, toks, _model.pack_of(
                        self.draft_config, S, spans), write_table,
                    self._draft_kp, self._draft_vp)
                self._draft_kp, self._draft_vp = dkp, dvp
            if grouped:
                tok.copy_to_host_async()
        self._book("prefill_dispatch_s", dispatch)
        stall = min(sum(j.compile_totals()[1] for j in jits) - s0,
                    time.time() - t0)
        return pack, args, tok, t0, stall

    def _finish_prefill(self, pack, args, tok, t0, stall):
        """Fetch a started pack's first tokens (the blocking sync: it
        returns when THIS pack's program has ended, whatever is queued
        behind it) and book its requests, each as a prompt alone was."""
        cfg = self.config
        with telemetry.span("serving.prefill.fetch", _CAT, **args) as fetch:
            # the per-step token egress: serving's output IS this transfer
            tok = np.asarray(tok)  # fwlint: disable=device-escape — token egress to the client is the product, one scalar per prompt (+ the experts' load in the same array)
            self._fetched()
            toks, load = _unpack_fetch(
                tok, (_model.pack_width(cfg, args["bucket"]),), cfg)
            fetch.set(**_moe_args(load, self.config))
        self._book("prefill_fetch_s", fetch)
        wall = time.time() - t0
        # the program's own: the experts' load counts the pack's valid
        # rows, the rung's rows were computed once; every prompt of it
        # took every pass of a looped stack
        self._note_moe(load, args["prompt_len"])
        self._note_passes(len(pack))
        telemetry.counter("serving.prefill_rows").inc(args["bucket"])
        if self._rec is not None:
            self._rec["prefill_rows"] += args["bucket"]
            self._rec["prefill_programs"] += 1
        for (req, replay), tok in zip(pack, toks):
            self._book_prefill(req, replay, int(tok), wall, stall)

    def _book_prefill(self, req, replay, tok, wall, stall):
        """A request's share of a finished pack: its first token ``tok``,
        the wall it waited, the compile stall it sat behind."""
        cfg = self.config
        L = len(replay)
        with telemetry.span("serving.retire", _CAT,
                            request_id=req.request_id) as retire:
            if cfg.latent:
                self._latent["prefill_tokens"] += L
                telemetry.counter("serving.latent.prefill_tokens").inc(L)
            telemetry.histogram("serving.prefill_seconds").observe(wall)
            telemetry.counter("serving.prefill_tokens").inc(L)
            if self._rec is not None:
                self._rec["prefill_tokens"] += L
            if cfg.linear:
                for name, n in (
                        ("prefill_tokens", L * self._linear_layers),
                        ("prefill_chunks",
                         -(-L // _KDA_CHUNK) * self._linear_layers)):
                    self._linear[name] += n
                    telemetry.counter("serving.linear." + name).inc(n)
            elif self.streams is not None and self.streams.slots is not None:
                telemetry.counter("serving.ssm.prefill_tokens").inc(L)
            # register this prefix's full blocks for later admissions
            # (first writer wins; the blocks it itself mapped shared are
            # already in)
            self.pool.prefix_insert(replay, req.blocks)
            was_replay = req.pending_token is not None
            req.context_len = L
            req.state = DECODING
            if not was_replay:
                # fresh prompt: the prefill's greedy token is the first
                # output
                self._note_token(req, tok)
            # else: preemption replay — the pending token was already
            # produced (greedy replay recomputes the same cache;
            # tok == pending_token)
            self.obs.prefill_done(req, stall, was_replay)
        self._book("prefill_retire_s", retire)

    def _run_decode(self, reqs):
        """Build, dispatch and fetch one CHUNK of fused decode steps: up
        to ``DECODE_CHUNK`` steps of the device's loop in one dispatch and
        ONE blocking fetch, after which (and not before) the host retires,
        frees and schedules. A lane runs for its own ``steps_left``; the
        chunk is as long as its longest lane's, not cut to the first that
        finishes (a stream ends every few steps of a full batch, and the
        chunk would never form). Returns what :meth:`_note_decode` takes."""
        cfg = self.config
        B = _bucket_for(len(reqs), cfg.decode_buckets())
        args = {"batch": len(reqs), "bucket": B}
        with telemetry.span("serving.decode.build", _CAT, **args) as build:
            toks = np.zeros(B, np.int32)
            poss = np.zeros(B, np.int32)
            ctx = np.ones(B, np.int32)
            left = np.zeros(B, np.int32)
            eos = np.full(B, -1, np.int32)
            tables = np.zeros((B, self._nb_max), np.int32)
            wtables = slots = None
            if self.streams is not None:
                wtables = np.zeros((B, self._nb_max), np.int32)
                slots = np.zeros(B, np.int32)
            for i, req in enumerate(reqs):
                toks[i] = req.pending_token
                poss[i] = req.context_len
                ctx[i] = req.context_len + 1
                left[i] = req.steps_left(cfg.max_len)
                if req.eos_id is not None:
                    eos[i] = req.eos_id
                tables[i] = self._table_row(req.blocks, self._nb_max)
                if self.streams is not None:
                    wtables[i] = self._table_row(req.wblocks, self._nb_max)
                    slots[i] = req.slot or 0
            n = int(min(self._chunk, left.max()))
            # the chunk's first step's context lengths on the trace (what
            # its steps walked is booked after the fetch, on serving.retire)
            args.update(steps=n, ctx_tokens=int(ctx.sum()),
                        ctx_max=int(ctx.max()))
            build.set(**args)
            # compile-tally delta: a cold decode batch bucket stalls EVERY
            # stream in the batch for the compile wall (serving/obs.py)
            jit = self._decode_jits[B]
            c0, s0 = jit.compile_totals()
            fault.hit("dispatch_error")
        self._book("decode_build_s", build)
        t0 = time.time()
        with telemetry.span("serving.decode.dispatch", _CAT,
                            **args) as dispatch:
            nxt, _logits = self._dispatch_decode(
                toks, poss, tables, ctx, wtables, slots, left=left, eos=eos,
                n=n)
            self._dispatched(dispatch)
        self._book("decode_dispatch_s", dispatch)
        # the device has the chunk to run: the step before's bookkeeping
        # goes here, ahead of the fetch the host would only wait in
        self._flush(hidden=True)
        with telemetry.span("serving.decode.fetch", _CAT, **args) as fetch:
            # the chunk's single device->host sync: its rows of next
            # tokens (with the experts' load of each step behind them,
            # where there are experts). Nothing is in flight after it
            fetched = np.asarray(nxt)  # fwlint: disable=device-escape — token egress to clients is the product, chunk x B int32s per dispatch
            self._fetched()
            nxt, load = _unpack_fetch(fetched, (self._chunk, B), cfg,
                                      (self._chunk,))
            fetch.set(**_moe_args(load, self.config))
        self._book("decode_fetch_s", fetch)
        wall = time.time() - t0
        c1, s1 = jit.compile_totals()
        if c1 > c0:
            self.obs.decode_stall(reqs, min(s1 - s0, wall))
        return nxt, load, left, n

    def _note_decode(self, reqs, fetched, retire):
        """The gap's half of a fetched chunk: each lane takes its tokens,
        in order, up to its death (its ``steps_left`` used up, its EOS or
        its length), which is what the scheduler, the next build and a
        waiting caller read. What the counters need of the chunk falls out
        of that: each lane's context length at the chunk's start and the
        tokens it took. Returns :meth:`_book_decode` and its arguments,
        for :meth:`_set_aside`; the record's two parts of this section are
        timed here (three clock reads a chunk)."""
        nxt, load, left, n = fetched
        clock = self._clock()
        t_in = clock()
        ctx0 = np.array([req.context_len for req in reqs])  # fwlint: disable=device-escape — host integers, nothing of the device's
        alive = np.zeros_like(ctx0)
        rows = nxt[:n, :len(reqs)].T.tolist()
        t0 = clock()
        for i, req in enumerate(reqs):
            if req.state == DECODING:
                alive[i] = took = self._deliver(req, rows[i][:left[i]])
                req.context_len += took
        stamp = time.time()
        self._decode_dispatches += 1
        self._decode_inner_steps += n
        retire.set(steps=n, lane_steps=int(alive.sum()))
        if self._rec is not None:
            self._rec["retire_counters_s"] += t0 - t_in
            self._rec["retire_tokens_s"] += clock() - t0
        return self._book_decode, (ctx0, alive, n, load, stamp)

    def _book_decode(self, ctx0, alive, n, load, stamp):
        """The deferred half: book the chunk inner step by inner step in
        order, each as ONE decode step. Step ``j``'s LIVE lanes are those
        that took more than ``j`` tokens, at context ``ctx0 + j + 1``: in
        ``serving.decode_batch``, the blocks and states they walked, the
        experts' load of that step against those lanes. Returns the
        chunk's sums, for the ``serving.retire.deferred`` span and the
        step's record."""
        cfg = self.config
        noted = {"steps": n, "lane_steps": 0, "live_blocks": 0}
        for j in range(n):
            live = alive > j
            lanes = int(live.sum())
            if not lanes:
                break       # every lane met its EOS: the rest ran dead
            ctx = ctx0[live] + (j + 1)
            blocks = self._note_paged(ctx)
            noted["lane_steps"] += lanes
            noted["live_blocks"] += blocks
            if self.streams is not None:
                for k, v in self._note_hybrid(ctx, blocks).items():
                    noted[k] = noted.get(k, 0) + v
            if cfg.latent:
                self._note_latent(ctx, blocks)
            if load is not None:
                self._note_moe(load[j], int((ctx <= cfg.max_len).sum()))
            telemetry.histogram("serving.decode_batch").observe(lanes)
        # the device ran the chunk's n steps whether a lane lived or not
        noted.update(self._note_passes(n, rec=False))
        self._book_tokens(int(alive.sum()), stamp)
        telemetry.counter("serving.decode.dispatches").inc()
        telemetry.counter("serving.decode.inner_steps").inc(n)
        return noted

    def _cow_guard(self, reqs):
        """Give every write slot this step will touch a PRIVATE block.

        Structurally unreachable with the current admission flow — a
        prefix match covers only FULL blocks of the replay (n <= L//bs)
        and every decode/spec write lands at a position >= L, i.e. in a
        later, privately-allocated block — but the pool's copy-on-write
        contract must hold unconditionally (a future scheduler change
        must fail a unit test, not corrupt a neighbour's cache)."""
        bs = self.config.block_size
        k = self.spec_k if self._spec else self._chunk - 1
        for req in reqs:
            first = req.context_len // bs
            last = min(req.context_len + k, self.config.max_len - 1) // bs
            for idx in range(first, min(last, len(req.blocks) - 1) + 1):
                b = req.blocks[idx]
                if self.pool.refcount(b) > 1:
                    nb = self.pool.cow(b)
                    if nb != b:
                        if self._draft_kp is not None:
                            # draft pages share the block table, so the
                            # draft copy rides the same COW decision
                            self._draft_kp = self.pool.copy_block(
                                self._draft_kp, b, nb)
                            self._draft_vp = self.pool.copy_block(
                                self._draft_vp, b, nb)
                        req.blocks[idx] = nb

    def _run_spec_decode(self, reqs):
        """Speculative decode: the draft proposes ``spec_k`` greedy
        tokens (one-token steps over its OWN pages, same block tables),
        then the target scores all ``spec_k+1`` window positions in ONE
        multi-query paged-attention pass and greedy acceptance emits the
        TARGET's tokens — the output stream is bit-identical to
        target-only decoding no matter what the draft proposed.

        The draft runs k+1 inner steps: steps 0..k-1 yield proposals,
        the last is cache-fill only — with all k proposals accepted the
        next step starts at position ctx+k+1, and the draft's attention
        there needs its K/V at ctx+k (which no proposal step wrote)."""
        cfg = self.config
        k = self.spec_k
        B = _bucket_for(len(reqs), cfg.decode_buckets())
        n = len(reqs)
        nb = self._nb_max
        base_ctx = [r.context_len for r in reqs]
        # the decode spans' names and arguments, told apart by spec/phase;
        # ctx_* are the window's first lane's (a verify pass reads k more)
        # live_blocks is the verify pass's: its kernel walks to the window's
        # last lane (the draft's own pool is not booked)
        window_ctx = np.minimum(np.add(base_ctx, k + 1), cfg.max_len)
        args = {"spec": 1, "batch": n, "bucket": B,
                "ctx_tokens": sum(base_ctx) + B, "ctx_max": max(base_ctx) + 1,
                "live_blocks": self._blocks(window_ctx)}
        with telemetry.span("serving.decode.build", _CAT, phase="draft",
                            **args) as build:
            tables = np.zeros((B, nb), np.int32)
            for i, req in enumerate(reqs):
                tables[i] = self._table_row(req.blocks, nb)
            proposals = [[] for _ in range(n)]
            cur = np.zeros(B, np.int32)
            for i, req in enumerate(reqs):
                cur[i] = req.pending_token
            djit = self._draft_decode_jits[B]
            c0, s0 = djit.compile_totals()
            fault.hit("dispatch_error")
        self._book("decode_build_s", build)
        t0 = time.time()
        # one span over the k+1 inner steps: each proposal's fetch steers
        # the next dispatch, so the two cannot be told apart per step (the
        # step's record books them all as dispatch; the gap before them
        # ends at the first call's return)
        with telemetry.span("serving.decode.dispatch", _CAT, phase="draft",
                            **args) as dispatch:
            for j in range(k + 1):
                toks = cur.copy()
                poss = np.zeros(B, np.int32)
                ctx = np.ones(B, np.int32)
                for i in range(n):
                    poss[i] = base_ctx[i] + j
                    ctx[i] = base_ctx[i] + j + 1
                dnxt, _dl, dkp, dvp = self._draft_decode_fn(
                    self._draft_params, toks, poss, tables, ctx,
                    self._draft_kp, self._draft_vp)
                self._draft_kp, self._draft_vp = dkp, dvp
                self._dispatched(dispatch)     # the first call's, if any
                if j < k:
                    # the proposal steers the NEXT inner step's input
                    # token — an unavoidable per-draft-step sync, B int32s
                    dnxt = np.asarray(dnxt)[:B]  # fwlint: disable=device-escape — draft proposals feed the next inner draft step, B int32s per step (a draft with experts has its load behind them: not booked)
                    for i in range(n):
                        proposals[i].append(int(dnxt[i]))
                        cur[i] = dnxt[i]
        self._book("decode_dispatch_s", dispatch)
        draft_wall = time.time() - t0
        c1, s1 = djit.compile_totals()
        draft_stall = min(s1 - s0, draft_wall) if c1 > c0 else 0.0
        # verify: the target scores position ctx+j for j in 0..k in one
        # extend() pass — lane j consumes [pending, d_1..d_k][j] and its
        # greedy argmax is the token the stream emits if lane j is reached
        T = k + 1
        with telemetry.span("serving.decode.build", _CAT, phase="verify",
                            **args) as build:
            toks2 = np.zeros((B, T), np.int32)
            poss2 = np.zeros((B, T), np.int32)
            ctx2 = np.ones((B, T), np.int32)
            for i, req in enumerate(reqs):
                toks2[i, 0] = req.pending_token
                for j in range(k):
                    toks2[i, j + 1] = proposals[i][j]
                for j in range(T):
                    poss2[i, j] = base_ctx[i] + j
                    ctx2[i, j] = base_ctx[i] + j + 1
            vjit = self._verify_jits[B]
            c0, s0 = vjit.compile_totals()
        self._book("decode_build_s", build)
        t0 = time.time()
        with telemetry.span("serving.decode.dispatch", _CAT, phase="verify",
                            **args) as dispatch:
            nxt2, _logits, kp, vp = self._verify_fn(
                self.params, toks2, poss2, tables, ctx2,
                self.pool.k_pages, self.pool.v_pages)
            self.pool.k_pages, self.pool.v_pages = kp, vp
        self._book("decode_dispatch_s", dispatch)
        # the window's last dispatch: its fetch is the one the host waits in
        self._flush(hidden=True)
        with telemetry.span("serving.decode.fetch", _CAT, phase="verify",
                            **args) as fetch:
            nxt2 = np.asarray(nxt2)  # fwlint: disable=device-escape — token egress to clients is the product, B×(k+1) int32s per step
            self._fetched()
            nxt2, load = _unpack_fetch(nxt2, (B, T), cfg)
            fetch.set(**_moe_args(load, self.config))
        self._book("decode_fetch_s", fetch)
        verify_wall = time.time() - t0
        c1, s1 = vjit.compile_totals()
        verify_stall = min(s1 - s0, verify_wall) if c1 > c0 else 0.0
        if draft_stall or verify_stall:
            self.obs.decode_stall(reqs, draft_stall + verify_stall)
        self._spec_draft_s += draft_wall
        self._spec_verify_s += verify_wall
        return (nxt2, proposals, draft_wall - draft_stall,
                verify_wall - verify_stall, load, base_ctx, window_ctx)

    def _note_spec_decode(self, reqs, fetched, retire):
        """Greedy acceptance — emit the TARGET's token at every reached
        lane. Lane j+1 is reached only if the draft's proposal d_{j+1}
        MATCHED the target's lane-j output (the window's K/V past a
        mismatch encodes the draft's wrong token, so stop there; the
        stale writes are overwritten by the next step's lane 0). The
        gap's half, as :meth:`_note_decode` is of a chunk: returns
        :meth:`_book_window` and its arguments, and times the record's
        same two parts."""
        nxt2, proposals, draft_s, verify_s, load, base_ctx, window_ctx = \
            fetched
        k = self.spec_k
        clock = self._clock()
        t_in = clock()
        proposed = accepted = emitted = 0
        for i, req in enumerate(reqs):
            proposed += k
            for j in range(k + 1):
                tok = int(nxt2[i, j])
                if tok < 0:
                    break   # overflow-poisoned lane (past max_len)
                req.context_len += 1
                emitted += self._deliver(req, (tok,))
                if req.state != DECODING or j >= k \
                        or proposals[i][j] != tok:
                    break
                accepted += 1
        stamp = time.time()
        t_out = clock()
        self._spec_proposed += proposed
        self._spec_accepted += accepted
        # the draft/verify split is on the phase clock of a request that
        # finishes in this very section
        self.obs.spec_step(reqs, draft_s, verify_s, proposed, accepted)
        retire.set(steps=1, lane_steps=len(reqs))
        if self._rec is not None:
            self._rec["retire_tokens_s"] += t_out - t_in
            self._rec["retire_counters_s"] += clock() - t_out
        return self._book_window, (base_ctx, window_ctx, load, emitted,
                                   stamp)

    def _book_window(self, base_ctx, window_ctx, load, emitted, stamp):
        """The deferred half of a draft-and-verify window, booked as one
        step of a chunk: the blocks the verify pass walked (to the
        window's last lane; the draft's own pool is not booked) and the
        experts' load against the positions it scored."""
        k, max_len = self.spec_k, self.config.max_len
        blocks = self._note_paged(window_ctx)
        self._note_moe(load, sum(min(k + 1, max(0, max_len - ctx))
                                 for ctx in base_ctx))
        telemetry.histogram("serving.decode_batch").observe(len(base_ctx))
        self._book_tokens(emitted, stamp)
        return dict(self._note_passes(1, rec=False), steps=1,
                    lane_steps=len(base_ctx), live_blocks=blocks)

    def _blocks(self, ctx):
        """The blocks that hold contexts of ``ctx`` tokens, summed."""
        return int((-(-ctx // self.config.block_size)).sum())

    def _note_passes(self, steps, rec=True):
        """Book ``steps`` program steps of a looped stack (a prompt's
        prefill, an inner decode step, a verify pass): ``loop_steps``
        passes each.
        Returns ``{"passes": n}`` for the step's span and record, {} for
        a stack that runs once; ``rec``: add them to the record of the
        step under way (the chunk's are added when its item is carried
        out)."""
        r = self.config.loop_steps
        if r == 1:
            return {}
        self._looped_steps += steps
        self._looped_passes += r * steps
        telemetry.counter("serving.looped.passes").inc(r * steps)
        if rec and self._rec is not None:
            self._rec["passes"] += r * steps
        return {"passes": r * steps}

    def _note_paged(self, ctx):
        """Book one decode or verify step of the paged kernel from the
        live streams' context lengths: the blocks it walks
        (``ceil(ctx / block_size)`` a stream, once a PASS of a looped
        stack: times ``num_layers`` it is what the kernel walked) and the
        table slots those streams hold (``nb_max`` each and pass; what the
        kernel's grid walked before PR 27). Returns the blocks, for the
        step's spans."""
        live = self._blocks(ctx) * self.config.loop_steps
        slots = len(ctx) * self._nb_max * self.config.loop_steps
        self._paged_live_blocks += live
        self._paged_table_slots += slots
        telemetry.counter("serving.paged.live_blocks").inc(live)
        telemetry.counter("serving.paged.table_slots").inc(slots)
        return live

    def _note_hybrid(self, ctx, full_live):
        """Book one decode pass of a model with ``layer_kinds`` from the
        live streams' context lengths: a state update a stream and "mamba"
        layer, and the blocks a window layer's walk and a full-pool
        reader's walk take; for plain grouped-query attention also the
        keys those walks read (``stats()["hybrid"]``). Returns what the
        spans and the step's record take."""
        cfg = self.config
        if cfg.linear:
            n = len(ctx) * self._linear_layers
            self._linear["state_updates"] += n
            telemetry.counter("serving.linear.state_updates").inc(n)
        elif self.streams.slots is not None:
            telemetry.counter("serving.ssm.stream_steps").inc(len(ctx))
        first = np.maximum(ctx - cfg.window, 0) // cfg.block_size
        window_live = int((-(-ctx // cfg.block_size) - first).sum()) \
            if self.streams.pool is not None else 0
        noted = {"window_live_blocks": window_live,
                 "full_live_blocks": full_live}
        if cfg.gqa:     # the keys ONE layer's walk of each pool read
            keys = {"full_ctx_tokens": int(ctx.sum()) if self._full_readers
                    else 0,
                    "window_ctx_tokens":
                        int(np.minimum(ctx, cfg.window).sum())
                        if self.streams.pool is not None else 0}
            noted.update(keys)
            for name, n in dict(keys, lane_steps=len(ctx)).items():
                self._hybrid[name] += n
                telemetry.counter("serving.hybrid." + name).inc(n)
        return noted

    def _note_latent(self, ctx, live_blocks):
        """Book one decode step of the latent kernel, a call a "mla"
        layer: the cached tokens the live lanes read (their context
        lengths, this step's own token included), the lanes, the blocks."""
        for name, n in (("ctx_tokens", int(ctx.sum())),
                        ("lane_steps", len(ctx)),
                        ("live_blocks", live_blocks)):
            self._latent[name] += n
            telemetry.counter("serving.latent." + name).inc(n)

    def _note_moe(self, load, tokens):
        """Book one program's per-layer ``tokens_per_expert`` (L, E), the
        router's count over all E experts: every choice it made
        (``routed_pairs``, so that ``routed_pairs == experts_per_tok x
        layer_tokens`` checks the program: the ``tokens`` live lanes the
        engine sent through each layer are counted here, on the host), and
        of the experts THIS engine holds the pairs computed, the
        layer-steps, the experts with at least one token, and how uneven
        the last step was. None (no experts): nothing."""
        if load is None:
            return
        routed = int(load.sum())
        self._moe_routed += routed
        telemetry.counter("serving.moe.routed_pairs").inc(routed)
        first, count = self.config.experts_here
        load = load[:, first:first + count]
        self._moe_load += load
        self._moe_layer_steps += load.shape[0]
        self._moe_layer_tokens += tokens * load.shape[0]
        touched = int((load > 0).sum())
        self._moe_touched += touched
        telemetry.counter("serving.moe.pairs").inc(int(load.sum()))
        telemetry.counter("serving.moe.layer_steps").inc(load.shape[0])
        telemetry.counter("serving.moe.layer_tokens").inc(
            tokens * load.shape[0])
        telemetry.counter("serving.moe.experts_touched").inc(touched)
        telemetry.gauge("serving.moe.load_max_over_mean").set(
            _max_over_mean(load))

    def _note_token(self, req, tok):
        """A prompt's first token, delivered and booked at once (a group's
        are booked under the device time of the prompts behind them)."""
        self._deliver(req, (tok,))
        self._book_tokens(1, req.first_token_t)

    def _deliver(self, req, toks):
        """Hand ``req`` its next tokens, in order, up to the one that ends
        it (its length, its EOS); the number it took. This is what the
        scheduler, the next build and the caller read of a token; its
        telemetry is :meth:`_book_tokens`'. A stream's first token reads
        the clock itself: the phase clock needs the instant."""
        if req.first_token_t is None:
            req.first_token_t = now = time.time()
            telemetry.histogram("serving.ttft_seconds").observe(
                now - req.arrival_t)
        generated, took = req.generated, 0
        for tok in toks:
            generated.append(tok)
            took += 1
            req.pending_token = tok
            if (len(generated) >= req.max_new_tokens
                    or (req.eos_id is not None and tok == req.eos_id)):
                req.state = FINISHED
                req.pending_token = None
                break
        self._tokens_total += took
        return took

    def _book_tokens(self, n, stamp):
        """``n`` tokens delivered at ``stamp`` (wall clock), for the
        counter and the sliding tokens/sec window."""
        if n:
            self._token_window.append((stamp, n))
            telemetry.counter("serving.generated_tokens").inc(n)

    def _retire(self, req):
        req.finish_t = time.time()
        # unlabeled aggregate kept alongside the engine-labeled observe in
        # obs.request_finished: process-wide dashboards and pre-existing
        # tests read the bare name
        telemetry.histogram("serving.request_latency_seconds").observe(
            req.finish_t - req.arrival_t)
        telemetry.counter("serving.requests_completed").inc()
        self.obs.request_finished(req)
        self._n_completed += 1
        self._finished.append(req)
        if req.done_event is not None:
            req.done_event.set()

    def _refresh_throughput(self, window_s=10.0):
        now = time.time()
        cut = now - window_s
        w = self._token_window = [e for e in self._token_window
                                  if e[0] >= cut]
        span = now - max(cut, self._t_started)
        telemetry.gauge("serving.tokens_per_sec").set(
            sum(n for _t, n in w) / span if span > 0 else 0.0)

    # ------------------------------------------------------------ stats
    def _state_stats(self):
        cfg, st = self.config, self.streams
        return {
            "slots": st.slots.num_usable if st.slots else 0,
            "slots_used": st.slots.used() if st.slots else 0,
            "slot_bytes": st.slots.slot_nbytes() if st.slots else 0,
            "state_bytes": st.slots.nbytes() if st.slots else 0,
            "window": cfg.window,
            "window_blocks_total": st.pool.num_usable if st.pool else 0,
            "window_blocks_used": st.pool.used() if st.pool else 0,
            "window_pool_bytes": st.pool.nbytes() if st.pool else 0,
            # the most window blocks any one stream held: never above
            # ceil((window + block_size + DECODE_CHUNK - 1) / block_size)
            "window_blocks_a_stream": st.max_blocks_held,
            "window_blocks_freed": st.blocks_freed,
            # model layers that read the full-length pool's K/V
            "full_pool_readers": self._full_readers,
            "full_pool_layers": self.pool.spec.layers,
        }

    def _mxu_share(self):
        """Of the block walks counted since start — a walk a live lane,
        step and layer that attends: the full pool's
        (``_paged_live_blocks``) and a window pool's (the loop records'
        ``window_live_blocks``) — the share on head-major pages, whose
        block is two matmuls on the MXU (``ops/attention.py``: the paged
        kernel's head-major form, ``latent_paged``); a token-major block
        is elementwise work. From what is already counted: nothing is
        booked a step for it."""
        cfg, st = self.config, self.streams
        readers = (len(cfg.layers_of("full", "cross", "mla")) if cfg.hybrid
                   else cfg.num_layers)
        walks = [(readers * self._paged_live_blocks, self.pool)]
        if st is not None and st.pool is not None:
            walks.append((len(cfg.layers_of("swa"))
                          * self.obs.loop_snapshot()["sums"][
                              "window_live_blocks"], st.pool))
        total = sum(n for n, _pool in walks)
        share = (sum(n for n, pool in walks if pool.spec.head_major) / total
                 if total else 0.0)
        telemetry.gauge("serving.paged.mxu_share").set(share)
        return share

    def stats(self):
        """One dashboard snapshot (serve.py columns, /stats endpoint).

        Everything here is THIS engine's: counts are per-engine tallies
        and the latency/TTFT percentiles read the ``engine=<id>``-labeled
        registry histograms, so two engines sharing a process never mix
        numbers (the bare-name histograms still aggregate process-wide
        for dashboards)."""
        with self._lock:
            self._flush()       # every chunk delivered is a chunk booked
            self._refresh_throughput()   # a stale window must read as 0
            eid = str(self.engine_id)
            lat = telemetry.histogram("serving.request_latency_seconds",
                                      engine=eid)
            ttft = telemetry.histogram("serving.ttft_seconds", engine=eid)
            prog = {p["program"]: p for p in compileobs.program_table()
                    if p["program"].startswith("serving.")}
            return {
                "engine": self.engine_id,
                "steps": self._steps,
                "waiting": len(self.scheduler.waiting),
                "active": len(self.scheduler.running),
                "kv_blocks_total": self.pool.num_usable,
                "kv_blocks_used": self.pool.used(),
                "kv_blocks_frag_slots": self.scheduler.frag_slots(),
                "kv_pool_bytes": self.pool.nbytes(),
                # 1 = the plain (H, D) page row; at head_dim 64 that row
                # is half a lane tile and every program copies the pool
                "kv_heads_per_row": self.pool.spec.heads_per_row,
                "kv_page_shape": list(self.pool.spec.k_rows),
                # a block is (G, bs, W): rows that do not fill their tiles
                "kv_head_major": self.pool.spec.head_major,
                "tokens_total": self._tokens_total,
                "tokens_per_sec":
                    telemetry.gauge("serving.tokens_per_sec").value,
                "latency_p50_s": lat.percentile(50),
                "latency_p99_s": lat.percentile(99),
                "ttft_p50_s": ttft.percentile(50),
                "ttft_p99_s": ttft.percentile(99),
                "preemptions": self.scheduler.preempt_count,
                "completed": self._n_completed,
                "failed": self._n_failed,
                "resilience": {
                    "draining": self._draining,
                    "aborted": self._aborted,
                    "max_queue": self.config.max_queue,
                    "default_timeout_ms": self.config.default_timeout_ms,
                    "shed": self._n_shed,
                    "timed_out": self._n_timed_out,
                    "cancelled": self._n_cancelled,
                },
                "prefix": self.pool.prefix_stats(),
                "spec": {
                    "enabled": self._spec,
                    "k": self.spec_k,
                    "draft": self.config.draft if self._spec else None,
                    "proposed_tokens": self._spec_proposed,
                    "accepted_tokens": self._spec_accepted,
                    "acceptance_rate":
                        (self._spec_accepted / self._spec_proposed)
                        if self._spec_proposed else 0.0,
                    "draft_seconds": round(self._spec_draft_s, 6),
                    "verify_seconds": round(self._spec_verify_s, 6),
                },
                "paged": {
                    "live_blocks": self._paged_live_blocks,
                    "table_slots": self._paged_table_slots,
                    "live_share":
                        (self._paged_live_blocks / self._paged_table_slots)
                        if self._paged_table_slots else 0.0,
                    "mxu_share": self._mxu_share(),
                },
                "decode": {
                    "dispatches": self._decode_dispatches,
                    "inner_steps": self._decode_inner_steps,
                    "steps_per_dispatch":
                        (self._decode_inner_steps / self._decode_dispatches)
                        if self._decode_dispatches else 0.0,
                },
                "prefill": {
                    "prompts": self._prefill_prompts,
                    "groups": self._prefill_groups,
                    "prompts_per_group":
                        (self._prefill_prompts / self._prefill_groups)
                        if self._prefill_groups else 0.0,
                    "programs": self._prefill_programs,
                    "prompts_per_program":
                        (self._prefill_prompts / self._prefill_programs)
                        if self._prefill_programs else 0.0,
                    # blocking fetches that no longer expose a host gap:
                    # every prompt of a group but its last
                    "syncs_saved":
                        self._prefill_prompts - self._prefill_groups,
                    # admission passes that found a request waiting, by
                    # what ended them (scheduler.STOP_REASONS)
                    "stopped_by": dict(self.scheduler.stopped_by),
                },
                # only for a stack that runs several times
                **({"looped": {
                    "passes": self._looped_passes,
                    "steps": self._looped_steps,
                    "passes_per_step":
                        (self._looped_passes / self._looped_steps)
                        if self._looped_steps else 0.0,
                    "cache_layers": self.pool.spec.cache_layers,
                }} if self.config.loop_steps > 1 else {}),
                # only for a model with window or state layers
                **({"state": self._state_stats()}
                   if self.streams is not None else {}),
                # only for plain grouped-query attention's two kinds
                **({"hybrid": dict(
                    self._hybrid,
                    full_blocks_used=self.pool.used(),
                    window_blocks_used=self.streams.pool.used()
                    if self.streams.pool is not None else 0)}
                   if self.config.gqa else {}),
                # only for a model with "mla" layers
                **({"latent": dict(self._latent)}
                   if self.config.latent else {}),
                # only for a model with "kda" layers
                **({"linear": dict(
                    self._linear, layers=self._linear_layers,
                    slots_used=self.streams.slots.used(),
                    slot_bytes=self.streams.slots.slot_nbytes())}
                   if self.config.linear else {}),
                # only for a model with experts
                **({"moe": {
                    "num_experts": self.config.num_experts,
                    "experts_per_tok": self.config.experts_per_tok,
                    # this engine's share of them, (first, count)
                    "experts_held": list(self.config.experts_here),
                    "routed_pairs": self._moe_routed,
                    "pairs": int(self._moe_load.sum()),
                    "layer_steps": self._moe_layer_steps,
                    "layer_tokens": self._moe_layer_tokens,
                    "experts_touched": self._moe_touched,
                    "load_max_over_mean": _max_over_mean(self._moe_load),
                    "tokens_per_expert": self._moe_load.tolist(),
                }} if self.config.num_experts else {}),
                "slo": self.obs.slo_snapshot(),
                "phases": self.obs.phase_snapshot(),
                # the steps on record (obs.LoopRecord): sums, a step's
                # mean milliseconds a section, the two host gaps
                "loop": self.obs.loop_snapshot(),
                "compiles": {n: {"count": p["compile_count"],
                                 "seconds": round(p["compile_seconds"], 3),
                                 "runs": p["run_count"]}
                             for n, p in prog.items()},
                "compile_cache": compile_cache.stats(),
            }
