"""SPMD fused training — the TPU-native data-parallel fast path.

The reference's data parallelism is: per-GPU executors + gradient gather to an
owner device + updater + broadcast (module/executor_group.py + kvstore comm.h).
On TPU the idiomatic equivalent is ONE program: jit the whole
forward+backward+update over a ``Mesh`` with the batch sharded on the ``dp``
axis and params replicated; XLA's SPMD partitioner inserts the gradient
all-reduce (psum over ICI) automatically and fuses it with the optimizer
update. Per-step host work drops to a single dispatch — no push/pull, no
per-device python loop.

Used by Module's fused path, the benchmark driver, and dryrun_multichip.
Tensor-parallel sharding: pass ``param_rules`` mapping parameter-name regex →
PartitionSpec to shard weights over a 'tp' axis (e.g. the FC head of ResNet or
attention/FFN blocks); everything unmatched stays replicated.
"""
from __future__ import annotations

import re

import numpy as np

from .. import compileobs as _compileobs
from .. import graphpass as _graphpass
from ..executor import build_graph_fn
from ..ops.registry import get_op
from . import fused_opt

__all__ = ["SPMDTrainer"]


class SPMDTrainer:
    def __init__(self, symbol, mesh, data_shapes, optimizer="sgd", optimizer_params=None,
                 label_shapes=None, dtype=np.float32, param_rules=None, batch_axis="dp",
                 donate=True, compute_dtype=None, input_dtype=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .. import optimizer as opt_mod

        self.symbol = symbol
        self.mesh = mesh
        self.batch_axis = batch_axis
        # graph-pass pipeline (docs/compiler.md) ahead of the fused-step
        # trace, same as the classic executor: the trainer's public
        # arg/aux order stays the ORIGINAL symbol's (checkpoints, shape
        # maps) and the optimized graph binds those slots by name
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self._opt_symbol = _graphpass.optimize(symbol)
        self._graph_fn, _, _ = build_graph_fn(
            self._opt_symbol, arg_names=self.arg_names,
            aux_names=self.aux_names)
        self.data_names = [n for n, _ in data_shapes]
        self.label_names = [n for n, _ in (label_shapes or [])]
        self.param_names = [
            n for n in self.arg_names if n not in self.data_names + self.label_names
        ]
        shapes = dict(data_shapes)
        shapes.update(dict(label_shapes or []))
        arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**shapes)
        self.arg_shapes = dict(zip(self.arg_names, arg_shapes))
        self.aux_shapes = dict(zip(self.aux_names, aux_shapes))
        self.out_shapes = out_shapes
        # optimizer: string (created with name-keyed mults so lr_mult/wd_mult
        # and __lr_mult__/__wd_mult__ symbol attrs resolve like the serial
        # path) or a ready Optimizer instance. The fused rule raises on
        # unsupported optimizers — never silently trains with different math.
        if isinstance(optimizer, str):
            optimizer = opt_mod.create(
                optimizer, sym=symbol,
                param_idx2name={n: n for n in self.param_names},
                **dict(optimizer_params or {}),
            )
        elif isinstance(optimizer, opt_mod.Optimizer):
            if optimizer_params:
                raise ValueError(
                    "optimizer_params cannot be combined with a ready "
                    "Optimizer instance; configure the instance directly"
                )
        else:
            raise TypeError("optimizer must be a name or an Optimizer instance")
        self.optimizer = optimizer
        self.rule = fused_opt.make_rule(optimizer)
        self.dtype = dtype
        # mixed precision: master params stay `dtype` (fp32); the graph runs in
        # `compute_dtype` (bf16 on TPU — MXU-native) with fp32 accumulation via
        # each op's preferred_element_type; grads flow back through the cast so
        # updates are fp32. The TPU-native form of the reference's fp16 story.
        self.compute_dtype = np.dtype(compute_dtype) if compute_dtype is not None else None
        if input_dtype is not None and self.compute_dtype is None and np.dtype(input_dtype) != np.dtype(dtype):
            self.compute_dtype = np.dtype(input_dtype)
        # same cast policy as Executor (executor.py:142-146): fp32 inputs run
        # in compute_dtype except labels/index-like inputs (class ids above
        # 256 are not exactly representable in bf16)
        from ..executor import _index_like_inputs

        self._cast_exempt = frozenset(self.label_names) | _index_like_inputs(symbol)
        self._param_rules = [(re.compile(k), v) for k, v in (param_rules or {}).items()]
        self._loss_flags = self._detect_loss_outputs()
        from ..symbol import _topo_order

        self._stochastic = any(
            not node.is_variable and getattr(get_op(node.op), "stochastic", False)
            for node in _topo_order(symbol._entries)
        )
        self._rng_cache = None

        # shardings
        self._P = P
        self.repl = NamedSharding(mesh, P())
        self.batch_sharding = NamedSharding(mesh, P(batch_axis))
        self.param_shardings = {
            n: NamedSharding(mesh, self._spec_for(n)) for n in self.param_names
        }
        self._step_fn = None
        self._donate = donate
        # graph identity for compile attribution (compileobs): every
        # trainer over this symbol shares it, so a bucket/rebind compile is
        # diffed against the graph's previous signature. Post-pass: the
        # canonical digest is also the fused step's persistent-cache
        # classification key (Layer A — the AOT lane stays off for the
        # sharded step; jax's disk cache serves it transparently)
        self._graph_digest = _compileobs.symbol_digest(self._opt_symbol)

    def _spec_for(self, name):
        for prog, spec in self._param_rules:
            if prog.match(name):
                return self._P(*spec) if isinstance(spec, (tuple, list)) else spec
        return self._P()

    def _detect_loss_outputs(self):
        flags = []
        for node, _ in self.symbol._entries:
            flags.append(
                False if node.is_variable else getattr(get_op(node.op), "is_loss", False)
            )
        return flags

    # ------------------------------------------------------------------
    def init_params(self, initializer):
        """Initialize replicated/sharded param dict + aux dict."""
        import jax

        from .. import ndarray as nd

        params = {}
        for n in self.param_names:
            host = nd.zeros(self.arg_shapes[n])
            initializer(n, host)
            params[n] = jax.device_put(
                host.asnumpy().astype(self.dtype), self.param_shardings[n]
            )
        auxs = {}
        for n in self.aux_names:
            host = nd.zeros(self.aux_shapes[n])
            initializer(n, host)
            auxs[n] = jax.device_put(host.asnumpy().astype(np.float32), self.repl)
        states = self.init_opt_state()
        return params, auxs, states

    def init_opt_state(self):
        """Fresh optimizer state: dict name -> tuple of slot arrays, each slot
        sharded like its parameter (so e.g. tp-sharded weights get tp-sharded
        momenta and the update stays fully local)."""
        import jax

        return {
            n: tuple(
                jax.device_put(s, self.param_shardings[n])
                for s in self.rule.init_state(self.arg_shapes[n], self.dtype)
            )
            for n in self.param_names
        }

    def _make_grads(self, params, auxs, inputs, rng):
        """Traced fwd+bwd core shared by _build_step and _build_grad_step:
        (grads in param dtype, new_aux dict, outputs). Handles compute-dtype
        casting, the MXNET_BACKWARD_DO_MIRROR rematerialization knob, and
        loss-flag cotangent seeding in ONE place so the single-program and
        hybrid-dist paths can never diverge."""
        import jax
        import jax.numpy as jnp

        from ..base import env_flag

        arg_order = self.arg_names
        aux_order = self.aux_names
        data_set = set(self.data_names + self.label_names)
        graph_fn = self._graph_fn
        compute_dtype = self.compute_dtype
        cast_exempt = self._cast_exempt

        aux_list = [auxs[n] for n in aux_order]
        if compute_dtype is not None:
            inputs = {
                n: v.astype(compute_dtype)
                if n not in cast_exempt and v.dtype == np.float32 else v
                for n, v in inputs.items()
            }

        def f(p):
            if compute_dtype is not None:
                p = {n: v.astype(compute_dtype) for n, v in p.items()}
            outs, new_aux = graph_fn(
                [p[n] if n not in data_set else inputs[n] for n in arg_order],
                aux_list, rng, True)
            return outs, [a.astype(np.float32) for a in new_aux]

        if env_flag("MXNET_BACKWARD_DO_MIRROR"):
            # activation recompute (same knob as the Executor path):
            # rematerialize instead of storing residuals — trades FLOPs for
            # HBM, which can WIN on a bandwidth-bound step
            f = jax.checkpoint(f)

        outs, vjp_fn, new_aux = jax.vjp(f, params, has_aux=True)
        seeds = [
            jnp.full(o.shape, 1.0 if fl else 0.0, o.dtype)
            for o, fl in zip(outs, self._loss_flags)
        ]
        grads = vjp_fn(list(seeds))[0]
        grads = {n: g.astype(params[n].dtype) for n, g in grads.items()}
        return grads, dict(zip(aux_order, new_aux)), outs

    def _build_step(self):
        import jax

        if self._step_fn is not None:
            return self._step_fn
        rule = self.rule
        base_wd = self.optimizer.wd
        lr_mult, wd_mult = fused_opt.mults_for(self.optimizer, self.param_names)

        def step(params, auxs, states, inputs, rng, lr, t):
            grads, new_auxs, outs = self._make_grads(params, auxs, inputs, rng)
            new_params = {}
            new_states = {}
            for n in params:
                # lr_mult/wd_mult are python floats: they constant-fold into
                # the trace; lr/t stay dynamic so schedulers never retrace
                new_params[n], new_states[n] = rule.apply(
                    params[n], grads[n], states[n],
                    lr * lr_mult[n], base_wd * wd_mult[n], t
                )
            return new_params, new_auxs, new_states, outs

        # params, auxs (BN stats), and optimizer slots all move every step —
        # donate all three so XLA reuses their buffers in place
        donate = (0, 1, 2) if self._donate else ()
        self._step_fn = _compileobs.jit(
            step, "fused.step",
            site="mxnet_tpu/parallel/spmd.py:SPMDTrainer._build_step",
            graph_key=self._graph_digest, donate_argnums=donate)
        return self._step_fn

    def step(self, params, auxs, states, inputs_np, rng=None):
        """One fused train step. inputs_np: dict name->np array (global batch).
        Returns (params, auxs, states, outputs)."""
        import jax

        from .. import random as _random

        if rng is None:
            # deterministic graphs get one cached device-resident key: no
            # per-step host RNG work or upload
            if self._stochastic:
                rng = _random.next_key()
            else:
                if self._rng_cache is None:
                    self._rng_cache = _random.next_key()
                rng = self._rng_cache
        inputs = {
            n: v if getattr(v, "sharding", None) == self.batch_sharding
            else jax.device_put(v, self.batch_sharding)
            for n, v in inputs_np.items()
        }
        lr, t = fused_opt.host_step_values(self.optimizer, self.param_names)
        return self._build_step()(
            params, auxs, states, inputs, rng, np.float32(lr), np.int32(t)
        )

    # ---- hybrid distributed (gradient / apply split) ---------------------
    # The dist_sync fused mode (SURVEY §7 stage 6): the worker runs
    # forward+backward+local-mesh allreduce as ONE program producing global
    # gradients, the parameter-server boundary happens on the host
    # (push/pull, BSP preserved), and — when the optimizer runs worker-side —
    # a second fused program applies the pulled gradients.
    def _build_grad_step(self):
        import jax

        if getattr(self, "_grad_fn", None) is not None:
            return self._grad_fn

        def gstep(params, auxs, inputs, rng):
            return self._make_grads(params, auxs, inputs, rng)

        # auxs move every step; params do NOT (apply comes later) — donate
        # only the aux argument (and only when donation is enabled at all)
        self._grad_fn = _compileobs.jit(
            gstep, "fused.grad_step",
            site="mxnet_tpu/parallel/spmd.py:SPMDTrainer._build_grad_step",
            graph_key=self._graph_digest,
            donate_argnums=(1,) if self._donate else ())
        return self._grad_fn

    def grad_step(self, params, auxs, inputs_np, rng=None):
        """fwd+bwd only: (global grads, new auxs, outputs)."""
        import jax

        from .. import random as _random

        if rng is None:
            if self._stochastic:
                rng = _random.next_key()
            else:
                if self._rng_cache is None:
                    self._rng_cache = _random.next_key()
                rng = self._rng_cache
        inputs = {
            n: v if getattr(v, "sharding", None) == self.batch_sharding
            else jax.device_put(v, self.batch_sharding)
            for n, v in inputs_np.items()
        }
        return self._build_grad_step()(params, auxs, inputs, rng)

    def _build_apply_step(self):
        import jax

        if getattr(self, "_apply_fn", None) is not None:
            return self._apply_fn
        rule = self.rule
        base_wd = self.optimizer.wd
        lr_mult, wd_mult = fused_opt.mults_for(self.optimizer, self.param_names)

        def apply(params, states, grads, lr, t):
            new_p, new_s = {}, {}
            for n in params:
                new_p[n], new_s[n] = rule.apply(
                    params[n], grads[n], states[n],
                    lr * lr_mult[n], base_wd * wd_mult[n], t)
            return new_p, new_s

        self._apply_fn = _compileobs.jit(
            apply, "fused.apply_grads",
            site="mxnet_tpu/parallel/spmd.py:SPMDTrainer._build_apply_step",
            graph_key=self._graph_digest,
            donate_argnums=(0, 1) if self._donate else ())
        return self._apply_fn

    def apply_grads(self, params, states, grads):
        """Optimizer update with externally supplied (e.g. PS-aggregated)
        gradients. Advances the schedule exactly like step()."""
        lr, t = fused_opt.host_step_values(self.optimizer, self.param_names)
        return self._build_apply_step()(
            params, states, grads, np.float32(lr), np.int32(t))

    def eval_step_fn(self):
        """Jitted inference fn(params, auxs, inputs) -> outputs."""
        import jax

        arg_order = self.arg_names
        aux_order = self.aux_names
        data_set = set(self.data_names + self.label_names)
        graph_fn = self._graph_fn
        compute_dtype = self.compute_dtype
        cast_exempt = self._cast_exempt

        def fwd(params, auxs, inputs):
            if compute_dtype is not None:
                params = {n: v.astype(compute_dtype) for n, v in params.items()}
                inputs = {
                    n: v.astype(compute_dtype)
                    if n not in cast_exempt and v.dtype == np.float32 else v
                    for n, v in inputs.items()
                }
            args = [params[n] if n not in data_set else inputs.get(n) for n in arg_order]
            aux_list = [auxs[n] for n in aux_order]
            outs, _ = graph_fn(args, aux_list, None, False)
            return outs

        return _compileobs.jit(
            fwd, "fused.eval",
            site="mxnet_tpu/parallel/spmd.py:SPMDTrainer.eval_step_fn",
            graph_key=self._graph_digest)
