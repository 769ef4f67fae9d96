"""Executor — binds a Symbol to arrays and compiles it.

Reference: src/executor/graph_executor.cc (GraphExecutor::Init :336 — gradient
pass, shape/type inference, memory planning, cached engine ops) and
python/mxnet/executor.py (the user wrapper: forward :95, backward :143).

TPU design — the reference's entire bind pipeline becomes "trace + jit":

* InitFullGraph's nnvm::pass::Gradient (:233) → ``jax.vjp`` over the traced
  forward. Hand-written Backward ops, DeclareBackwardDependency, mirror-path
  recompute (`MXNET_BACKWARD_DO_MIRROR`) all collapse into XLA autodiff +
  rematerialization.
* PlanMemory/DetectInplaceAddTo (:445-447) → XLA buffer assignment. ``kAddTo``
  gradient accumulation (grad_req='add') is done functionally: grads are added
  to the existing grad buffers after the vjp.
* InitCachedOps/InitOpSegs (bulk segments ≤15 nodes, :681) → one jit for the
  whole graph; XLA fuses better than any manual segmenting.
* Training forward is *deferred*: ``forward(is_train=True)`` records inputs and
  ``backward()`` runs one fused forward+backward executable — so a fit step
  costs exactly one device program (the reference pays two graph walks).
  Reading ``outputs`` before ``backward()`` materializes the forward alone.

BatchNorm-style aux states are threaded functionally (auxs in → new auxs out)
and written back after each training step, preserving FMutateInputs semantics.
"""
from __future__ import annotations

import os

import numpy as np

from . import compileobs as _compileobs
from . import graphpass as _graphpass
from . import profiler as _profiler
from . import random as _random
from .base import MXNetError
from .ops.registry import OpContext, get_op
from .symbol import _topo_order

__all__ = ["Executor"]


def build_graph_fn(symbol, node_callback=None, arg_names=None,
                   aux_names=None):
    """Build ``fn(arg_list, aux_list, rng, is_train) -> (outputs, new_auxs)``
    plus the metadata needed to bind arrays (arg names, aux names).

    This is the trace target: pure, shape-stable, jit-friendly. Stochastic ops
    get per-node keys folded from the step key so two dropout layers never share
    a mask.

    ``arg_names`` / ``aux_names`` — when given, variables bind to the slots
    of those lists BY NAME instead of by this symbol's own topo order. This
    is how the executor runs a graphpass-optimized graph against arrays
    bound in the ORIGINAL symbol's order: canonicalization may reorder the
    topo walk (and folding may orphan a variable entirely — its slot is
    simply never read), but the caller's binding contract stays fixed.

    ``node_callback(name, value)`` — when given, invoked with every
    non-variable node's visible outputs as they are computed (names
    ``<node>_output``/``<node>_output<k>``, the reference's per-node monitor
    contract, graph_executor.cc:761-781). Only meaningful when the function
    runs EAGERLY (un-jitted): under a jit trace the callback would observe
    tracers. Used by Executor's monitored forward.
    """
    import jax

    order = _topo_order(symbol._entries)
    arg_vars, aux_vars = symbol._arg_aux_split()
    if arg_names is None:
        arg_names = symbol.list_arguments()
    if aux_names is None:
        aux_names = symbol.list_auxiliary_states()
    arg_slot = {n: i for i, n in enumerate(arg_names)}
    aux_slot = {n: i for i, n in enumerate(aux_names)}
    arg_index = {}
    aux_index = {}
    for node in order:
        if node.is_variable:
            if id(node) in aux_vars:
                aux_index[id(node)] = aux_slot[node.name]
            else:
                arg_index[id(node)] = arg_slot[node.name]

    def graph_fn(arg_list, aux_list, rng, is_train):
        vals = {}
        new_aux = list(aux_list)
        stoch_i = 0
        for node in order:
            if node.is_variable:
                if id(node) in aux_index:
                    vals[id(node)] = [aux_list[aux_index[id(node)]]]
                else:
                    vals[id(node)] = [arg_list[arg_index[id(node)]]]
                continue
            op = get_op(node.op)
            n_args = len(op.arg_names(node.attrs))
            ins = [vals[id(n)][k] for n, k in node.inputs]
            args, auxs = ins[:n_args], ins[n_args:]
            key = None
            if op.stochastic and rng is not None:
                key = jax.random.fold_in(rng, stoch_i)
                stoch_i += 1
            octx = OpContext(is_train=is_train, rng=key)
            outs, updated_aux = op.forward(octx, node.attrs, args, auxs)
            vals[id(node)] = list(outs)
            if node_callback is not None:
                n_vis = op.num_visible_outputs(node.attrs)
                for k in range(n_vis):
                    suffix = "_output" if n_vis == 1 else "_output%d" % k
                    node_callback(node.name + suffix, outs[k])
            # record aux writebacks (aux inputs are always variables)
            for (inp, _), new in zip(node.inputs[n_args:], updated_aux):
                if id(inp) in aux_index:
                    new_aux[aux_index[id(inp)]] = new
        outputs = [vals[id(n)][k] for n, k in symbol._entries]
        return outputs, new_aux

    return graph_fn, arg_names, aux_names


# ops whose listed inputs carry integer ids; bf16 holds integers exactly only
# up to 256, so casting these under compute_dtype silently merges ids — they
# are auto-exempted from the mixed-precision downcast
_INDEX_ARG_POSITIONS = {
    "Embedding": (0,),
    "take": (1,),
    "batch_take": (1,),
    "one_hot": (0,),
    "gather_nd": (1,),
    "scatter_nd": (1,),
    "pick": (1,),
    "choose_element_0index": (1,),
    "fill_element_0index": (1,),
}


def _index_like_inputs(symbol):
    """Names of Variable inputs that feed an index argument of any op."""
    from .symbol import _topo_order

    exempt = set()
    for node in _topo_order(symbol._entries):
        if node.is_variable:
            continue
        for pos in _INDEX_ARG_POSITIONS.get(node.op, ()):
            if pos < len(node.inputs):
                inp, _ = node.inputs[pos]
                if inp.is_variable:
                    exempt.add(inp.name)
    return exempt


class Executor:
    """A bound, compiled computation graph."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None, shared_exec=None,
                 compute_dtype=None, cast_exempt=()):
        from . import ndarray as nd

        self._symbol = symbol
        self._ctx = ctx
        self._group2ctx = group2ctx
        self.monitor_callback = None
        self._monitor_active = None
        # mixed precision (the TPU-native form of the reference's fp16 symbols,
        # e.g. resnet_fp16.py's per-weight Casts): float32 args are cast to
        # compute_dtype inside the jitted graph — master copies stay fp32, and
        # backward() casts grads back, so optimizer updates run fp32.
        # cast_exempt names (labels, index-like inputs) keep their dtype.
        self._compute_dtype = np.dtype(compute_dtype) if compute_dtype else None
        self._cast_exempt = frozenset(cast_exempt) | _index_like_inputs(symbol)

        # ---- graph-pass pipeline (docs/compiler.md): canonicalize / fold /
        # CSE / fusion-group the Symbol graph before lowering. The optimized
        # graph is what gets traced; binding stays keyed to the ORIGINAL
        # symbol's arg/aux order (name-keyed slots in build_graph_fn).
        # The multi-device group2ctx path keeps the unoptimized graph — the
        # segment cutter consumes the original node structure.
        multi_dev = False
        if group2ctx:
            devs = {c.jax_device for c in group2ctx.values()}
            devs.add(ctx.jax_device if not isinstance(ctx, (list, tuple))
                     else ctx[0].jax_device)
            multi_dev = len(devs) > 1
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self._opt_symbol = symbol if multi_dev else _graphpass.optimize(symbol)
        self._graph_fn, _, _ = build_graph_fn(
            self._opt_symbol, arg_names=self._arg_names,
            aux_names=self._aux_names)
        # graph identity for compile attribution: shared by every executor
        # bound over this graph, so a reshape/rebind's compile is diffed
        # against the graph's previous signature (compileobs recompile
        # events name the changed axis instead of looking like new programs).
        # Post-pass: canonicalization makes the digest construction-order
        # independent — the stable half of the persistent compile-cache key.
        self._graph_digest = _compileobs.symbol_digest(self._opt_symbol)
        # the ORIGINAL graph's digest rides the disk-cache key too: the
        # traced function binds arrays in the ORIGINAL symbol's slot order,
        # so two sources whose optimized forms coincide but whose original
        # slot wiring differs must never share an executable (equal
        # original digests imply equal pass output AND equal binding)
        self._orig_digest = (self._graph_digest
                             if self._opt_symbol is symbol
                             else _compileobs.symbol_digest(symbol))

        # ---- normalize arg arrays (reference: CheckArguments in Bind) ----
        if isinstance(args, dict):
            try:
                self.arg_arrays = [args[n] for n in self._arg_names]
            except KeyError as e:
                raise MXNetError("key %s missing in args" % e) from e
        else:
            self.arg_arrays = list(args)
        if len(self.arg_arrays) != len(self._arg_names):
            raise MXNetError(
                "Expect %d args, got %d" % (len(self._arg_names), len(self.arg_arrays))
            )
        if isinstance(aux_states, dict):
            self.aux_arrays = [aux_states[n] for n in self._aux_names]
        else:
            self.aux_arrays = list(aux_states) if aux_states else []
        if len(self.aux_arrays) != len(self._aux_names):
            raise MXNetError(
                "Expect %d aux states, got %d" % (len(self._aux_names), len(self.aux_arrays))
            )
        # grad arrays + grad_req per arg
        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in self._arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(self._arg_names, grad_req))
        elif isinstance(grad_req, dict):
            self._grad_req = {n: grad_req.get(n, "null") for n in self._arg_names}
        else:
            raise MXNetError("invalid grad_req")
        if args_grad is None:
            self.grad_arrays = [None] * len(self._arg_names)
        elif isinstance(args_grad, dict):
            self.grad_arrays = [args_grad.get(n) for n in self._arg_names]
        else:
            self.grad_arrays = list(args_grad)
            self.grad_arrays += [None] * (len(self._arg_names) - len(self.grad_arrays))
        for n in self._arg_names:
            if self._grad_req.get(n, "null") != "null" and self.grad_arrays[self._arg_names.index(n)] is None:
                self._grad_req[n] = "null"

        self._diff_idx = [
            i for i, n in enumerate(self._arg_names) if self._grad_req[n] != "null"
        ]
        self._rng_base = _random.next_key()
        self._step = 0
        self._outputs_cache = None
        self._pending = None  # (args_data, auxs_data, rng) recorded by train forward
        self._jit_fwd = {}
        self._jit_fwd_bwd = None
        self._is_loss_output = self._detect_loss_outputs()
        self._graph_fn_monitored = None  # built lazily on first monitored forward

        # ---- real group2ctx placement (reference: AssignContext +
        # PlaceDevice + _CrossDeviceCopy, graph_executor.cc:245-334): when
        # the ctx groups map onto >=2 distinct devices, the graph is cut
        # into per-device segments, each params set genuinely lives on its
        # group's device, and boundary values move over explicit transfers
        # (ICI between chips). See mxnet_tpu/placed.py.
        self._placed = None
        if group2ctx:
            if multi_dev:
                from .placed import PlacedGraph

                base_ctx = ctx[0] if isinstance(ctx, (list, tuple)) else ctx
                cd = self._compute_dtype

                def cast_one(name, a):
                    if (cd is not None and name not in self._cast_exempt
                            and a.dtype == np.float32):
                        return a.astype(cd)
                    return a

                self._placed = PlacedGraph(
                    symbol, group2ctx, base_ctx,
                    self._arg_names, self._aux_names, cast_one)
                self._place_arrays()

    # ------------------------------------------------------------------
    def _place_arrays(self):
        """Move each bound array onto its ctx group's device — the user-visible
        face of model parallelism: ``ex.arg_dict['fc2_weight'].context`` is the
        group's context, and the buffer is committed there."""
        import jax

        for i, name in enumerate(self._arg_names):
            tgt = self._placed.arg_ctx.get(name)
            if tgt is None:
                continue
            for arr in (self.arg_arrays[i], self.grad_arrays[i]):
                if arr is None:
                    continue
                arr._set_data(jax.device_put(arr.data, tgt.jax_device))
                arr._ctx = tgt
        for j, name in enumerate(self._aux_names):
            tgt = self._placed.aux_ctx.get(name)
            if tgt is not None:
                self.aux_arrays[j]._set_data(
                    jax.device_put(self.aux_arrays[j].data, tgt.jax_device))
                self.aux_arrays[j]._ctx = tgt

    def _detect_loss_outputs(self):
        flags = []
        for node, _ in self._symbol._entries:
            if node.is_variable:
                flags.append(False)
            else:
                flags.append(getattr(get_op(node.op), "is_loss", False))
        return flags

    @property
    def _arg_data(self):
        return [a.data for a in self.arg_arrays]

    @property
    def _aux_data(self):
        return [a.data for a in self.aux_arrays]

    def _next_rng(self):
        import jax

        self._step += 1
        return jax.random.fold_in(self._rng_base, self._step)

    # ---- forward ------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        """Run forward (reference: executor.py:95 → GraphExecutor::Forward).

        kwargs update input arrays in place (data=..., label=...).
        In training mode execution is deferred so ``backward()`` can run one
        fused fwd+bwd program; reading ``outputs`` forces materialization.
        """
        from . import ndarray as nd

        if kwargs:
            name_to_idx = {n: i for i, n in enumerate(self._arg_names)}
            for k, v in kwargs.items():
                if k not in name_to_idx:
                    raise MXNetError("Unknown input %s" % k)
                dst = self.arg_arrays[name_to_idx[k]]
                if isinstance(v, nd.NDArray):
                    dst._set_data(v.data.astype(dst.dtype))
                else:
                    dst[:] = v
        rng = self._next_rng()
        monitored = self.monitor_callback is not None and (
            self._monitor_active is None or self._monitor_active()
        )
        if is_train:
            self._pending = (self._arg_data, self._aux_data, rng)
            self._outputs_cache = None
            if monitored:
                # reference-parity monitor mode: an extra eager node-by-node
                # pass fires the callback on EVERY node output
                # (graph_executor.cc:761-781). Debug path: per-op dispatches,
                # no whole-graph fusion — and the deferred fused fwd+bwd
                # below still runs for backward()
                self._outputs_cache = self._run_forward_monitored(True, rng)
        else:
            self._pending = None
            if monitored:
                self._outputs_cache = self._run_forward_monitored(False, rng)
            else:
                self._outputs_cache = self._run_forward(False, rng)
        return self.outputs

    def _cast_compute(self, arg_list):
        """Inside-jit downcast of float32 args to the compute dtype."""
        if self._compute_dtype is None:
            return arg_list
        cd = self._compute_dtype
        return [
            a.astype(cd)
            if (name not in self._cast_exempt and a.dtype == np.float32)
            else a
            for name, a in zip(self._arg_names, arg_list)
        ]

    def _get_jit_fwd(self, is_train):
        fn = self._jit_fwd.get(is_train)
        if fn is None:
            if self._placed is not None:
                # segmented multi-device execution (each segment is its own
                # single-device jit; transfers happen between them)
                fn = lambda args, auxs, rng, _t=is_train: (  # noqa: E731
                    self._placed.forward(args, auxs, rng, _t))
            else:
                def run(args, auxs, rng):
                    outs, new_aux = self._graph_fn(self._cast_compute(args), auxs, rng, is_train)
                    # aux states (BN moving stats) keep their master dtype
                    new_aux = [na.astype(a.dtype) for na, a in zip(new_aux, auxs)]
                    return outs, new_aux

                fn = _compileobs.jit(
                    run,
                    "executor.fwd_train" if is_train else "executor.fwd_eval",
                    site="mxnet_tpu/executor.py:Executor._get_jit_fwd",
                    graph_key=self._graph_digest, aot=True,
                    cache_key=self._cache_key("fwd", bool(is_train)))
            self._jit_fwd[is_train] = fn
        return fn

    def _cache_key(self, kind, *extra):
        """Cross-process disk-cache identity for this executor's programs:
        the post-pass graph digest plus every static knob that shapes the
        traced function beyond the input signature (compute dtype, cast
        exemptions, which args differentiate, the mirror-recompute flag).
        One missing knob here would serve a WRONG executable warm — when
        in doubt, widen the key (a spurious miss costs one compile)."""
        from .base import env_flag

        return ("executor", kind, self._graph_digest, self._orig_digest,
                str(self._compute_dtype),
                tuple(sorted(self._cast_exempt)),
                tuple(self._diff_idx),
                bool(env_flag("MXNET_BACKWARD_DO_MIRROR"))) + extra

    def _profile_name(self, kind):
        return "executor_%s[%s]" % (kind, getattr(self._symbol, "name", None) or "graph")

    def _run_forward(self, is_train, rng):
        with _profiler.record_span(self._profile_name("forward"), "executor"), \
                _compileobs.oom_guard("executor.fwd"):
            outs, new_aux = self._get_jit_fwd(is_train)(self._arg_data, self._aux_data, rng)
        if is_train:
            for arr, new in zip(self.aux_arrays, new_aux):
                arr._set_data(new)
        return outs

    def _run_forward_monitored(self, is_train, rng):
        """Eager node-by-node forward that feeds the monitor callback each
        node's outputs (reference ExecuteMonCallback semantics)."""
        from . import ndarray as nd

        if self._placed is not None:
            raise MXNetError(
                "Monitor is not supported on a multi-device group2ctx "
                "executor: the eager per-node pass cannot mix buffers "
                "committed to different devices. Remove the monitor or "
                "bind without group2ctx."
            )

        if self._graph_fn_monitored is None:
            def emit(name, value):
                cb = self.monitor_callback
                if cb is not None:
                    cb(name, nd.NDArray(value, ctx=self._ctx))

            self._graph_fn_monitored = build_graph_fn(
                self._symbol, node_callback=emit
            )[0]
        with _profiler.record_span(self._profile_name("forward_monitored"),
                                   "executor"):
            outs, new_aux = self._graph_fn_monitored(
                self._cast_compute(self._arg_data), self._aux_data, rng, is_train
            )
        if is_train:
            for arr, new, old in zip(self.aux_arrays, new_aux, self._aux_data):
                arr._set_data(new.astype(old.dtype))
        return outs

    @property
    def outputs(self):
        """Output NDArrays (materializes a deferred training forward)."""
        from . import ndarray as nd

        if self._outputs_cache is None:
            if self._pending is not None:
                args, auxs, rng = self._pending
                outs, new_aux = self._get_jit_fwd(True)(args, auxs, rng)
                for arr, new in zip(self.aux_arrays, new_aux):
                    arr._set_data(new)
                self._outputs_cache = outs
            else:
                raise MXNetError("call forward() first")
        return [nd.NDArray(o, ctx=self._ctx) for o in self._outputs_cache]

    # ---- backward -----------------------------------------------------
    def _build_fwd_bwd(self):
        import jax

        if self._jit_fwd_bwd is not None:
            return self._jit_fwd_bwd
        diff_idx = list(self._diff_idx)
        if self._placed is not None:
            def placed_run(args, auxs, out_grads, rng):
                outs, all_grads, new_aux = self._placed.fwd_bwd(
                    args, auxs, out_grads, rng)
                return outs, [all_grads[i] for i in diff_idx], new_aux

            self._jit_fwd_bwd = placed_run
            return placed_run
        # activation recompute (reference: MXNET_BACKWARD_DO_MIRROR,
        # graph_executor.cc:213-226 — rebuild cheap activations in backward
        # instead of keeping them): jax.checkpoint over the whole forward is
        # the TPU analog; XLA rematerializes instead of storing residuals.
        from .base import env_flag

        do_mirror = env_flag("MXNET_BACKWARD_DO_MIRROR")

        def run(args, auxs, out_grads, rng):
            def f(diff_args):
                full = list(args)
                for i, a in zip(diff_idx, diff_args):
                    full[i] = a
                outs, new_aux = self._graph_fn(self._cast_compute(full), auxs, rng, True)
                new_aux = [na.astype(a.dtype) for na, a in zip(new_aux, auxs)]
                return outs, new_aux

            if do_mirror:
                f = jax.checkpoint(f)

            diff_args = [args[i] for i in diff_idx]
            outs, vjp_fn, new_aux = jax.vjp(f, diff_args, has_aux=True)
            grads = vjp_fn(list(out_grads))[0]
            return outs, grads, new_aux

        self._jit_fwd_bwd = _compileobs.jit(
            run, "executor.fwd_bwd",
            site="mxnet_tpu/executor.py:Executor._build_fwd_bwd",
            graph_key=self._graph_digest, aot=True,
            cache_key=self._cache_key("fwd_bwd"))
        return self._jit_fwd_bwd

    def memory_analysis(self):
        """XLA's compile-time memory analysis of the fused fwd+bwd program
        (temp/argument/output bytes). The observability hook behind
        examples/memcost.py — not every backend exposes device live-stats,
        but the compiler's plan is exact for a static graph."""
        import jax

        if self._placed is not None:
            raise MXNetError(
                "memory_analysis is per-program; a multi-device group2ctx "
                "executor runs one program per device segment. Bind without "
                "group2ctx to analyze the fused single-device program."
            )
        # abstract out-grads and a fixed key: lowering only needs shapes, and
        # consuming the training rng stream here would shift later steps'
        # randomness (an observability call must not perturb training)
        ogs = [jax.ShapeDtypeStruct(tuple(sd.shape), sd.dtype)
               for sd in self._eval_out_shapes(self._arg_data, self._aux_data)]
        rng = self._rng_base  # fixed key, not _next_rng(): don't advance _step
        lowered = self._build_fwd_bwd().lower(self._arg_data, self._aux_data, ogs, rng)
        return lowered.compile().memory_analysis()

    def backward(self, out_grads=None):
        """Backward pass (reference: executor.py:143 → GraphExecutor::Backward).

        Without ``out_grads``, loss-op outputs are seeded with ones and other
        outputs with zeros — matching the reference, where only ops with
        declared gradients (SoftmaxOutput etc.) contribute and heads have no
        incoming gradient.
        """
        import jax.numpy as jnp

        from . import ndarray as nd

        if self._pending is None:
            # inference-mode backward: rerun with the last rng
            rng = self._next_rng()
            self._pending = (self._arg_data, self._aux_data, rng)
        args, auxs, rng = self._pending
        # build head gradients
        out_shapes = [tuple(o.shape) for o in self._eval_out_shapes(args, auxs)]
        if out_grads is None:
            ogs = []
            for shape_dtype, is_loss in zip(self._eval_out_shapes(args, auxs), self._is_loss_output):
                fill = 1.0 if is_loss else 0.0
                ogs.append(jnp.full(tuple(shape_dtype.shape), fill, shape_dtype.dtype))
        else:
            if isinstance(out_grads, nd.NDArray):
                out_grads = [out_grads]
            ogs = [g.data if isinstance(g, nd.NDArray) else jnp.asarray(g) for g in out_grads]
            # under compute_dtype the graph outputs (and so vjp cotangents) are
            # bf16; cast user-supplied fp32 head grads to match
            ogs = [g.astype(sd.dtype) for g, sd in
                   zip(ogs, self._eval_out_shapes(args, auxs))]
        with _profiler.record_span(self._profile_name("fwd_bwd"), "executor"), \
                _compileobs.oom_guard("executor.fwd_bwd"):
            outs, grads, new_aux = self._build_fwd_bwd()(args, auxs, ogs, rng)
        self._outputs_cache = outs
        self._pending = None
        for arr, new in zip(self.aux_arrays, new_aux):
            arr._set_data(new)
        for i, g in zip(self._diff_idx, grads):
            name = self._arg_names[i]
            req = self._grad_req[name]
            dst = self.grad_arrays[i]
            if req == "write":
                dst._set_data(g.astype(dst.dtype))
            elif req == "add":
                dst._set_data((dst.data + g).astype(dst.dtype))

    _out_shape_cache = None

    def _eval_out_shapes(self, args, auxs):
        import jax

        if self._out_shape_cache is None:
            # evaluate through the same compute-dtype cast the real jit uses so
            # dtypes (e.g. bf16 outputs) match the vjp's expectations
            outs, _ = jax.eval_shape(
                lambda a, x: self._graph_fn(self._cast_compute(a), x, None, False),
                args, auxs,
            )
            self._out_shape_cache = outs
        return self._out_shape_cache

    # ---- dicts ---------------------------------------------------------
    @property
    def arg_dict(self):
        return dict(zip(self._arg_names, self.arg_arrays))

    @property
    def grad_dict(self):
        return dict(zip(self._arg_names, self.grad_arrays))

    @property
    def aux_dict(self):
        return dict(zip(self._aux_names, self.aux_arrays))

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def copy_params_from(self, arg_params, aux_params=None, allow_extra_params=False):
        """(reference: executor.py copy_params_from)"""
        for name, array in arg_params.items():
            if name in self.arg_dict:
                array.copyto(self.arg_dict[name])
            elif not allow_extra_params:
                raise ValueError("Find name %s that is not in the arguments" % name)
        if aux_params:
            for name, array in aux_params.items():
                if name in self.aux_dict:
                    array.copyto(self.aux_dict[name])
                elif not allow_extra_params:
                    raise ValueError("Find name %s that is not in the auxiliary states" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor sharing this one's parameter arrays but bound
        at new data shapes (reference: executor.py:360; the shape-keyed compile
        cache replaces the reference's shared memory pool — XLA compiles one
        executable per shape signature, reusing donated buffers)."""
        from . import ndarray as nd

        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        if arg_shapes is None:
            raise MXNetError("Insufficient argument shapes provided.")
        new_args = []
        new_grads = []
        for i, (name, shape) in enumerate(zip(self._arg_names, arg_shapes)):
            cur = self.arg_arrays[i]
            if shape == cur.shape:
                new_args.append(cur)
                new_grads.append(self.grad_arrays[i])
            else:
                new_args.append(nd.zeros(shape, ctx=self._ctx, dtype=cur.dtype))
                new_grads.append(
                    nd.zeros(shape, ctx=self._ctx, dtype=cur.dtype)
                    if self.grad_arrays[i] is not None
                    else None
                )
        new_aux = []
        for i, (name, shape) in enumerate(zip(self._aux_names, aux_shapes)):
            cur = self.aux_arrays[i]
            new_aux.append(cur if shape == cur.shape else nd.zeros(shape, ctx=self._ctx, dtype=cur.dtype))
        return Executor(
            self._symbol, self._ctx, new_args, new_grads,
            [self._grad_req[n] for n in self._arg_names], new_aux,
            group2ctx=self._group2ctx,
            compute_dtype=self._compute_dtype, cast_exempt=self._cast_exempt,
        )

    def set_monitor_callback(self, callback, is_active=None):
        """Install a per-NODE monitor (reference: MXExecutorSetMonitorCallback
        → GraphExecutor::ExecuteMonCallback, graph_executor.cc:761-781).

        While installed AND active, forward runs an extra eager node-by-node
        pass that feeds every node output to ``callback`` — reference
        semantics at debug-mode cost (per-op dispatch, no whole-graph
        fusion). ``is_active`` (optional nullary predicate) lets the caller
        skip that pass on batches it will not record (Monitor's interval)."""
        self.monitor_callback = callback
        self._monitor_active = is_active

    def debug_str(self):
        return self._symbol.debug_str()
