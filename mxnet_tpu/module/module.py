"""Module — the main training API (reference: python/mxnet/module/module.py:21 —
bind :323, init_params, init_optimizer :432-510 with the kvstore/update_on_kvstore
decision and rescale_grad = 1/batch (or 1/(batch·workers) for dist_sync,
module.py:461-463), update :561-581, save/load_checkpoint :134)."""
from __future__ import annotations

import logging
import warnings

from .. import context as ctx_mod
from .. import io as io_mod
from .. import ndarray as nd
from .. import optimizer as opt
from ..base import MXNetError
from ..io import DataDesc
from ..model import (
    _create_kvstore, _initialize_kvstore, _update_params, _update_params_on_kvstore,
    load_checkpoint,
)
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup

__all__ = ["Module"]


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None, state_names=None, compute_dtype=None):
        super().__init__(logger=logger)
        if context is None:
            context = [ctx_mod.current_context()]
        if isinstance(context, ctx_mod.Context):
            context = [context]
        self._context = context
        if work_load_list is None:
            work_load_list = [1] * len(self._context)
        assert len(work_load_list) == len(self._context)
        self._work_load_list = work_load_list

        self._symbol = symbol
        # mixed precision: run the graph in this dtype with fp32 master params
        # (the TPU-native form of the reference's *_fp16 symbols)
        self._compute_dtype = compute_dtype
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []

        arg_names = symbol.list_arguments()
        input_names = data_names + label_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = list(fixed_param_names) if fixed_param_names else []
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = list(state_names) if state_names else []
        self._output_names = symbol.list_outputs()

        _check_input_names(symbol, data_names, "data", True)
        _check_input_names(symbol, label_names, "label", False)
        _check_input_names(symbol, self._state_names, "state", True)
        _check_input_names(symbol, self._fixed_param_names, "fixed_param", True)

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False

        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._grad_req = None
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        self._fused = None  # SPMD fast path (fused_path.py), set by init_optimizer
        self._monitor_installed = False

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Create from checkpoint (reference: module.py load)."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """(reference: module.py:134) — symbol.json + params + optional .states
        All files are written crash-safely (utils/atomic_file.py)."""
        from .. import fault

        self._symbol.save("%s-symbol.json" % prefix)
        fault.hit("checkpoint_between_files")
        param_name = "%s-%04d.params" % (prefix, epoch)
        self.save_params(param_name)
        # retire any stale mid-epoch .resume sidecar for this epoch number:
        # it described an older write of this params file (model.py
        # save_resume_state re-binds one for guard mid-epoch checkpoints)
        from ..model import clear_resume_state

        clear_resume_state(prefix, epoch)
        logging.info('Saved checkpoint to "%s"', param_name)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)
            logging.info('Saved optimizer state to "%s"', state_name)

    # ---- properties ------------------------------------------------------
    def _reset_bind(self):
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None

    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return self._exec_group.get_output_shapes()

    # ---- params ----------------------------------------------------------
    def get_params(self):
        """(reference: module.py get_params)"""
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        """(reference: module.py init_params)"""
        from .. import initializer as init_mod

        if self.params_initialized and not force_init:
            warnings.warn(
                "Parameters already initialized and force_init=False. "
                "init_params call ignored.", stacklevel=2,
            )
            return
        assert self.binded, "call bind before initializing the parameters"
        if initializer is None and not (arg_params and aux_params):
            initializer = init_mod.Uniform(0.01)

        def _impl(name, arr, cache):
            if cache is not None:
                if name in cache:
                    cache_arr = cache[name]
                    if cache_arr is not arr:
                        cache_arr.copyto(arr)
                else:
                    if not allow_missing:
                        raise RuntimeError("%s is not presented" % name)
                    if initializer is not None:
                        initializer(name, arr)
            else:
                initializer(name, arr)

        attrs = self._symbol.attr_dict()
        for name, arr in sorted(self._exec_group.execs[0].arg_dict.items()):
            if name not in self._param_names:
                continue
            desc = _init_desc(name, attrs)
            _impl(desc, arr, arg_params)
        for name, arr in sorted(self._exec_group.execs[0].aux_dict.items()):
            desc = _init_desc(name, attrs)
            _impl(desc, arr, aux_params)
        # mirror initialized exec0 params to module-level dicts + other devices
        self._arg_params = {
            name: nd.zeros(arr.shape, dtype=arr.dtype)
            for name, arr in self._exec_group.execs[0].arg_dict.items()
            if name in self._param_names
        }
        self._aux_params = {
            name: nd.zeros(arr.shape, dtype=arr.dtype)
            for name, arr in self._exec_group.execs[0].aux_dict.items()
        }
        for name in self._arg_params:
            # checkpoint-boundary sync by design, not a per-batch path
            self._arg_params[name][:] = self._exec_group.execs[0].arg_dict[name].asnumpy()  # fwlint: disable=device-escape
        for name in self._aux_params:
            self._aux_params[name][:] = self._exec_group.execs[0].aux_dict[name].asnumpy()  # fwlint: disable=device-escape
        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params)
        if self._fused is not None:
            self._fused.invalidate()

    def set_params(self, arg_params, aux_params, allow_missing=False, force_init=True):
        """(reference: module.py set_params)"""
        if (
            arg_params is self._arg_params and aux_params is self._aux_params
            and self._fused is not None and not self._fused.device_dirty
            and not self._params_dirty
        ):
            # fit's epoch-end self-sync (get_params -> set_params): host,
            # executor group, and fused device state are already coherent —
            # skip the full re-init/invalidate round-trip (it would download
            # and re-upload every param and optimizer slot for nothing)
            return
        if not allow_missing:
            self.init_params(
                initializer=None, arg_params=arg_params, aux_params=aux_params,
                allow_missing=allow_missing, force_init=force_init,
            )
            return
        if self.params_initialized and not force_init:
            warnings.warn(
                "Parameters already initialized and force_init=False. "
                "set_params call ignored.", stacklevel=2,
            )
            return
        self._exec_group.set_params(arg_params, aux_params)
        self._params_dirty = True
        self.params_initialized = True
        if self._fused is not None:
            self._fused.invalidate()

    # ---- bind ------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """(reference: module.py:323)"""
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True
        self._grad_req = grad_req
        if not for_training:
            assert not inputs_need_grad

        self._data_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x) for x in data_shapes]
        if label_shapes is not None and len(label_shapes):
            self._label_shapes = [
                x if isinstance(x, DataDesc) else DataDesc(*x) for x in label_shapes
            ]
        else:
            self._label_shapes = None

        if shared_module is not None:
            assert isinstance(shared_module, Module) and shared_module.binded and shared_module.params_initialized
            shared_group = shared_module._exec_group
        else:
            shared_group = None
        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list, self._data_shapes,
            self._label_shapes, self._param_names, for_training, inputs_need_grad,
            shared_group, logger=self.logger, fixed_param_names=self._fixed_param_names,
            grad_req=grad_req, state_names=self._state_names,
            compute_dtype=self._compute_dtype,
        )
        self._total_exec_bytes = 0
        if shared_module is not None:
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)
        else:
            assert self._arg_params is None and self._aux_params is None
        if shared_module is not None and shared_module.optimizer_initialized:
            self.borrow_optimizer(shared_module)

    def reshape(self, data_shapes, label_shapes=None):
        """(reference: module.py reshape)"""
        assert self.binded
        self._data_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x) for x in data_shapes]
        if label_shapes is not None:
            self._label_shapes = [
                x if isinstance(x, DataDesc) else DataDesc(*x) for x in label_shapes
            ]
        else:
            self._label_shapes = None
        self._exec_group.reshape(self._data_shapes, self._label_shapes)

    # ---- optimizer -------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),), force_init=False):
        """(reference: module.py:432-510)"""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        kvstore_arg = kvstore  # the user's string/instance, pre-resolution
        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params
        )
        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            idx2name = {}
            if update_on_kvstore:
                idx2name.update(enumerate(self._exec_group.param_names))
            else:
                for k in range(len(self._context)):
                    idx2name.update(
                        {
                            i * len(self._context) + k: n
                            for i, n in enumerate(self._exec_group.param_names)
                        }
                    )
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol, param_idx2name=idx2name, **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                warnings.warn(
                    "Optimizer created manually outside Module but rescale_grad "
                    + "is not normalized to 1.0/batch_size/num_workers (%s vs. %s). "
                    % (optimizer.rescale_grad, rescale_grad)
                    + "Is this intended?", stacklevel=2,
                )

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None
        self._fused_kvstore_arg = kvstore_arg  # for borrow_optimizer sharing
        self._fused = self._build_fused_path(kvstore_arg)
        if kvstore:
            # copy initialized local parameters to kvstore
            _initialize_kvstore(
                kvstore=kvstore, param_arrays=self._exec_group.param_arrays,
                arg_params=self._arg_params, param_names=self._param_names,
                update_on_kvstore=update_on_kvstore,
            )
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)
        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def _fused_veto(self, kvstore_arg):
        """Why this configuration is NOT expressible as ONE SPMD program —
        None when it is.

        ``kvstore='device'`` (the reference's reduce-on-device mode,
        kvstore.py:10-19) opts into in-graph allreduce on any platform; on TPU
        contexts the default local kvstores fuse too — that IS the TPU-native
        execution model. Everything stateful/introspective (monitors, input
        grads, custom grad_req, per-device workloads, distributed PS) keeps
        the executor-group path."""
        from ..base import env_flag
        from ..kvstore import KVStore

        if env_flag("MXNET_MODULE_NO_FUSED"):
            return "MXNET_MODULE_NO_FUSED=1 (explicit opt-out)"
        if isinstance(kvstore_arg, KVStore):
            # a ready store participates by its type string (the reference's
            # common/fit.py passes instances); dist stores are filtered below
            kvstore_arg = kvstore_arg.type
        if not isinstance(kvstore_arg, str) and kvstore_arg is not None:
            return "non-string kvstore object"
        if self._grad_req != "write":
            return "grad_req=%r (fused step supports 'write' only)" % (
                self._grad_req,)
        if self.inputs_need_grad:
            return "inputs_need_grad=True"
        if self._state_names:
            return "state_names are bound"
        if self._fixed_param_names:
            return "fixed_param_names are bound"
        if self._monitor_installed:
            return "a Monitor is installed (per-node hooks need the " \
                   "executor path)"
        if len(set(self._work_load_list)) > 1:
            return "non-uniform work_load_list"
        from .fused_path import batch_axes_standard

        if not batch_axes_standard(self._data_shapes or []) or (
                self._label_shapes
                and not batch_axes_standard(self._label_shapes)):
            return "a data/label layout has a non-leading batch axis"
        # the fused step seeds gradient cotangents into loss OUTPUT entries
        # only (executor.py's loss-flag seeding); a symbol without a loss
        # output (e.g. a SequentialModule feature stage trained via
        # out_grads) would silently train on zero gradients
        from ..ops.registry import get_op

        has_loss_output = any(
            not node.is_variable and getattr(get_op(node.op), "is_loss", False)
            for node, _ in self._symbol._entries
        )
        if not has_loss_output:
            return "symbol has no loss output (trained via out_grads)"
        devtypes = {c.device_type for c in self._context}
        if len(devtypes) != 1:
            return "mixed device types in context list"
        # contexts must land on DISTINCT jax devices (Context.jax_device wraps
        # host device ids modulo the device count, e.g. cpu(3) on a 1-CPU
        # process): a mesh with duplicates is not a valid SPMD target. A
        # context that names no device at all raises — no path can run it.
        jax_devs = [c.jax_device for c in self._context]
        if len(set(jax_devs)) != len(jax_devs):
            return "contexts resolve to duplicate devices (no SPMD mesh)"
        devtype = devtypes.pop()
        if kvstore_arg is not None and "dist" in kvstore_arg:
            # hybrid mode (fused_path._step_dist): fused local compute, PS at
            # the host boundary. 'device' in the type is the explicit opt-in
            # (the reference's dist_sync_device: reduce-on-device + PS);
            # plain dist types fuse on TPU contexts where fused IS the
            # native execution model.
            if "device" in kvstore_arg or devtype == "tpu":
                return None
            return "distributed kvstore %r on non-TPU contexts (pass " \
                   "kvstore='dist_sync_device' to opt into the hybrid " \
                   "fused step)" % (kvstore_arg,)
        if kvstore_arg in ("device", "local_allreduce_device"):
            return None
        if devtype == "tpu" and kvstore_arg in (None, "local"):
            return None
        return "kvstore=%r on non-TPU contexts (pass kvstore='device' to " \
               "opt in)" % (kvstore_arg,)

    def _fused_eligible(self, kvstore_arg):
        return self._fused_veto(kvstore_arg) is None

    def _build_fused_path(self, kvstore_arg, share_state=None):
        veto = self._fused_veto(kvstore_arg)
        if veto is not None:
            # demotions must be LOUD when the user plausibly expected the
            # fast path: TPU contexts, or an explicit kvstore='device'.
            # (cpu+local classic is the expected default — stay quiet.)
            from ..kvstore import KVStore

            kv_str = (kvstore_arg.type if isinstance(kvstore_arg, KVStore)
                      else kvstore_arg)
            wanted_fast = (
                (isinstance(kv_str, str)
                 and (kv_str in ("device", "local_allreduce_device")
                      or "dist" in kv_str))
                or any(c.device_type == "tpu" for c in self._context))
            if wanted_fast and "MXNET_MODULE_NO_FUSED" not in veto:
                self.logger.warning(
                    "Module.fit is NOT using the fused SPMD fast path: %s. "
                    "Training runs on the executor-group path (roughly an "
                    "order of magnitude slower on TPU). Set "
                    "MXNET_MODULE_NO_FUSED=1 to silence this warning if "
                    "the classic path is intended.", veto)
            return None
        try:
            from .fused_path import FusedFitPath

            return FusedFitPath(self, share_state=share_state)
        except ValueError as e:  # unsupported optimizer for the fused rules
            self.logger.info(
                "fused SPMD path unavailable (%s); using the executor-group path", e
            )
            return None

    def borrow_optimizer(self, shared_module):
        """(reference: module.py borrow_optimizer — bucketing modules share one
        optimizer/updater).

        When the lender trains on the fused SPMD path, the borrower gets its
        own shape-specialized fused path SHARING the lender's device state
        (fp32 masters, aux, optimizer state) — so every bucket of a
        BucketingModule runs the one-program-per-step fast path and bucket
        switches stay on-device."""
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self._fused_kvstore_arg = getattr(
            shared_module, "_fused_kvstore_arg", None)
        if shared_module._fused is not None:
            self._fused = self._build_fused_path(
                self._fused_kvstore_arg,
                share_state=shared_module._fused.state)
        self.optimizer_initialized = True

    # ---- compute ---------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        # uint8-wire batches (io.WireSpec) decode HERE, before the fused
        # path's shape check: the decoded fp32 NCHW arrays are what the
        # bound shapes describe. No-op for ordinary batches; target device
        # policy in io.wire_decode_ctx.
        data_batch = io_mod.apply_wire(
            data_batch, ctx=io_mod.wire_decode_ctx(self._context))
        if self._fused is not None:
            train = self.for_training if is_train is None else is_train
            if train and self._fused.accepts(data_batch):
                # fused fit path: stage only — update() runs the whole
                # fwd+bwd+update as one SPMD program
                self._fused.stage(data_batch)
                return
            # classic-path consumer (eval, odd-shaped batch): make the
            # executor group observe the fused updates, and drop any staged
            # batch/outputs so nothing stale is observed downstream
            self._fused.sync_to_module()
            self._fused.drop_batch()
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        if self._fused is not None and self._fused.pending:
            if out_grads is None:
                return  # gradient computation is fused into update()
            # explicit cotangents can't be seeded into the fused one-program
            # step: replay the staged batch through the executor group and
            # continue on the classic path (update() then sees no pending
            # fused batch and updates classically)
            batch = self._fused.staged_batch
            self._fused.sync_to_module()
            self._fused.drop_batch()
            self._exec_group.forward(batch, True)
        self._exec_group.backward(out_grads=out_grads)

    def update(self):
        """(reference: module.py:561-581)"""
        assert self.binded and self.params_initialized and self.optimizer_initialized
        self._params_dirty = True
        if self._fused is not None and self._fused.pending:
            self._fused.step()
            return
        handover = (self._fused is not None
                    and (self._fused.state.states is not None
                         or self._fused.state.host_states is not None))
        if handover:
            # a classic fallback update mid-fused-training (odd-shaped batch,
            # backward(out_grads)): seed the Updater with the fused optimizer
            # state so this step keeps its momentum/Adam moments and the
            # right bias-correction t, instead of silently updating from a
            # fresh state (the install_monitor handover, both directions)
            if self._updater is not None:
                opt = self._optimizer
                opt.begin_num_update = opt.num_update
                opt._index_update_count = {}
                self._updater.set_states(self._fused.get_states_bytes())
            elif self._kvstore is not None:
                self.logger.warning(
                    "classic fallback update with a kvstore-updating config: "
                    "this step's optimizer state starts fresh on the kvstore"
                )
        if self._update_on_kvstore:
            _update_params_on_kvstore(
                self._exec_group.param_arrays, self._exec_group.grad_arrays, self._kvstore
            )
        else:
            _update_params(
                self._exec_group.param_arrays, self._exec_group.grad_arrays,
                updater=self._updater, num_device=len(self._context), kvstore=self._kvstore,
            )
        if self._fused is not None:
            # a classic update ran: device-resident fused params are now
            # stale — drop them...
            replacing = handover and self._updater is not None
            self._fused.invalidate(stage_states=not replacing)
            if replacing:
                # ...and carry the classic step's state delta back so fused
                # training resumes from the updated moments, not the staged
                # pre-fallback ones
                self._fused.set_states_bytes(self._updater.get_states())

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        if self._fused is not None and self._fused.has_outputs:
            return self._fused.get_outputs()
        return self._exec_group.get_outputs(merge_multi_context=merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        return self._exec_group.get_input_grads(merge_multi_context=merge_multi_context)

    def update_metric(self, eval_metric, labels):
        if self._fused is not None and self._fused.has_outputs:
            self._fused.update_metric(eval_metric, labels)
            return
        self._exec_group.update_metric(eval_metric, labels)

    def _sync_params_from_devices(self):
        """(reference: module.py _sync_params_from_devices)"""
        if self._fused is not None and self._fused.device_dirty:
            self._fused.sync_to_module()  # also resets device_dirty
        else:
            self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    def save_optimizer_states(self, fname):
        """(reference: module.py save_optimizer_states) — crash-safe + CRC,
        like every other checkpoint file (utils/atomic_file.py)."""
        from ..utils.atomic_file import atomic_write

        assert self.optimizer_initialized
        if self._fused is not None:
            with atomic_write(fname) as fout:
                fout.write(self._fused.get_states_bytes())
        elif self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with atomic_write(fname) as fout:
                fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        """(reference: module.py load_optimizer_states).

        Restored states are validated against the BOUND parameter shapes
        before they are accepted: a ``.states`` file written by a different
        model (the symbol was edited between runs) used to load fine and
        then die deep inside the first optimizer update — now it raises a
        clear ``MXNetError`` here, which ``fit(auto_resume=...)`` catches
        and degrades to a warm start (params restored, fresh optimizer
        state) instead of dying."""
        from ..utils.atomic_file import read_verified

        assert self.optimizer_initialized
        if self._fused is not None:
            self._fused.set_states_bytes(read_verified(fname))
        elif self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            self._updater.set_states(read_verified(fname))
            self._updater.check_state_shapes(
                self._expected_state_shapes(), source=fname)

    def _expected_state_shapes(self):
        """``{flat_index: weight_shape}`` in the classic Updater's index
        layout (``param_idx * num_device + dev_idx``, model.py
        ``_update_params``) — what restored optimizer states must match."""
        shapes = {}
        num_device = len(self._context)
        for i, per_dev in enumerate(self._exec_group.param_arrays):
            for k, w in enumerate(per_dev):
                shapes[i * num_device + k] = tuple(w.shape)
        return shapes

    def install_monitor(self, mon):
        assert self.binded
        self._monitor_installed = True
        if self._fused is not None:
            # monitors need per-executor visibility: leave the fused path,
            # handing params AND optimizer state to the classic machinery so
            # momentum/Adam moments and the lr schedule continue seamlessly
            self._fused.sync_to_module()
            if self.optimizer_initialized:
                states = self._fused.get_states_bytes()
                opt = self._optimizer
                # fused counts are name-keyed; classic uses int indices.
                # Re-base so fresh indices resume the schedule where it left.
                opt.begin_num_update = opt.num_update
                opt._index_update_count = {}
                if self._updater is not None:
                    self._updater.set_states(states)
                elif self._kvstore is not None:
                    self.logger.warning(
                        "install_monitor mid-training with a kvstore-updating "
                        "config: optimizer state restarts fresh on the kvstore"
                    )
            self._fused = None
        self._exec_group.install_monitor(mon)


def _init_desc(name, attrs):
    from ..initializer import InitDesc

    return InitDesc(name, attrs.get(name, {}))
