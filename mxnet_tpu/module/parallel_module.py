"""ParallelLMModule — the Module-protocol face of the sp/pp/ep LM trainers.

Round-2 review: the parallel LM trainers (parallel/lm.py) were real but lived
"in a parallel universe" — their own param dicts, their own step loops,
nothing a Module user could `fit()`. This module closes that gap: ONE
user-facing path trains the same decoder-only transformer dense / sequence-
parallel / pipeline-parallel / expert-parallel, through the unchanged
``BaseModule.fit`` loop (bind → init_params → init_optimizer → forward/
update/update_metric → checkpoint callbacks), with parity across modes
asserted in tests/test_parallel_lm.py.

The reference has no counterpart (SURVEY §2.5: sp/pp/ep are new design work
for the TPU build); the Module protocol it implements is the reference's
(python/mxnet/module/base_module.py:79).

Usage::

    mod = mx.mod.ParallelLMModule(
        vocab_size=1000, num_layers=4, model_dim=128, num_heads=4,
        ffn_dim=256, seq_len=64, mode="sp", num_devices=8)
    mod.fit(train_iter, num_epoch=3, optimizer="adam",
            eval_metric=mx.metric.Perplexity(ignore_label=None))

Data contract (same as models/transformer_lm.py's symbol): batches carry
``data`` (B, T) token ids and ``softmax_label`` (B, T) next-token targets;
``get_outputs()`` returns softmax probabilities shaped (B*T, V), so
Perplexity/Accuracy metrics and score() behave exactly like the symbol
module's SoftmaxOutput head.

Parameters are one name-keyed family shared by every mode (lm.py
init_lm_params); checkpointing goes through the standard ``save_params`` /
``load_params`` NDArray-dict format, so a dense-trained file warm-starts an
sp/pp run and vice versa (ep adds per-expert FFN leaves — only the FFN
weights differ in shape).
"""
from __future__ import annotations

import logging

import numpy as np

from ..base import MXNetError
from .base_module import BaseModule

__all__ = ["ParallelLMModule"]


def _as_array(arr):
    """Param-dict value -> an array usable in the jax step WITHOUT a host
    round trip: an NDArray hands over its device buffer (no sync), numpy
    passes through, and only exotic list/tuple inputs pay a construction."""
    if hasattr(arr, "data") and hasattr(arr, "context"):
        return arr.data
    if isinstance(arr, np.ndarray):
        return arr
    # fwlint: disable=device-escape — host list/tuple input: construction, not a device sync
    return np.asarray(arr)


class ParallelLMModule(BaseModule):
    def __init__(self, vocab_size, num_layers, model_dim, num_heads, ffn_dim,
                 seq_len, mode="dense", mesh=None, num_devices=None,
                 num_experts=0, microbatches=None, capacity_factor=2.0,
                 seed=0, logger=logging):
        super().__init__(logger=logger)
        if mode not in ("dense", "sp", "pp", "ep"):
            raise MXNetError("ParallelLMModule: unknown mode %r" % (mode,))
        if mode == "ep" and not num_experts:
            raise MXNetError("mode='ep' needs num_experts > 0")
        self.mode = mode
        self._cfg = dict(vocab_size=vocab_size, num_layers=num_layers,
                         model_dim=model_dim, num_heads=num_heads,
                         ffn_dim=ffn_dim, seq_len=seq_len)
        self._num_experts = num_experts
        self._microbatches = microbatches
        self._capacity_factor = capacity_factor
        self._seed = seed
        self._mesh = mesh
        self._num_devices = num_devices
        self._trainer = None
        self._params = None      # name -> device/host array
        self._opt_state = None
        self._staged = None      # (tokens, labels) numpy staged by forward
        self._outs = None        # cached eval logits for get_outputs
        self._last_loss = None
        self._symbol = None      # no symbol graph: trainers are pure-jax

    # ---- mesh ------------------------------------------------------------
    def _ensure_mesh(self):
        if self.mode == "dense" or self._mesh is not None:
            return self._mesh
        from ..parallel import build_mesh

        import jax

        n = self._num_devices or len(jax.devices())
        self._mesh = build_mesh({self.mode: n})
        return self._mesh

    def _placed(self, a):
        """A device-resident param value the mode's step accepts: dense
        keeps the array as-is (single-device jit), mesh modes replicate
        onto the trainer mesh — a value committed to ONE device would
        collide with the shard_map device set (the ``_tokens_labels``
        placement rule, applied to params)."""
        if self.mode == "dense":
            return a
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(
            a, NamedSharding(self._ensure_mesh(), PartitionSpec()))

    # ---- Module protocol -------------------------------------------------
    @property
    def data_names(self):
        return ["data"]

    @property
    def label_names(self):
        return ["softmax_label"]

    @property
    def output_names(self):
        return ["softmax_output"]

    @property
    def data_shapes(self):
        return self._data_shapes

    @property
    def label_shapes(self):
        return self._label_shapes

    @property
    def output_shapes(self):
        (b, t) = self._data_shapes[0].shape
        return [("softmax_output", (b * t, self._cfg["vocab_size"]))]

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        from ..io import DataDesc

        if self.binded and not force_rebind:
            self.logger.warning("Already binded, ignoring bind()")
            return
        if inputs_need_grad or grad_req != "write":
            raise MXNetError(
                "ParallelLMModule supports grad_req='write' without input "
                "grads (the step is one fused program)")
        self._data_shapes = [d if isinstance(d, DataDesc) else DataDesc(*d)
                             for d in data_shapes]
        self._label_shapes = [d if isinstance(d, DataDesc) else DataDesc(*d)
                              for d in (label_shapes or [])]
        shape = tuple(self._data_shapes[0].shape)
        if len(shape) != 2 or shape[1] != self._cfg["seq_len"]:
            raise MXNetError(
                "data must be (batch, seq_len=%d), got %s"
                % (self._cfg["seq_len"], (shape,)))
        if self.mode == "pp":
            self._ensure_mesh()
            S = self._mesh.shape["pp"]
            m = self._microbatches or S
            if shape[0] % m:
                raise MXNetError(
                    "batch %d must divide into %d pipeline microbatches"
                    % (shape[0], m))
            self._microbatches = m
        self.for_training = for_training
        self.binded = True

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        assert self.binded
        if self.params_initialized and not force_init:
            return
        from ..parallel.lm import init_lm_params

        cfg = dict(self._cfg)
        if self.mode == "ep":
            cfg["num_experts"] = self._num_experts
        params = init_lm_params(self._seed, **cfg)
        if initializer is not None:
            from .. import ndarray as nd

            for name, arr in params.items():
                host = nd.array(arr)
                initializer(name, host)
                # keep the initialized value device-resident (astype is a
                # device op, _placed replicates mesh modes): the old
                # asnumpy().astype() pulled every freshly-initialized param
                # to the host only for the first step to re-upload it
                params[name] = self._placed(host.data.astype(arr.dtype))
        if arg_params:
            for name, arr in arg_params.items():
                if name in params:
                    a = _as_array(arr)
                    if tuple(a.shape) != tuple(params[name].shape):
                        raise MXNetError(
                            "shape mismatch loading %s: %s vs %s"
                            % (name, tuple(a.shape),
                               tuple(params[name].shape)))
                    params[name] = self._placed(
                        a.astype(params[name].dtype))
                elif not allow_missing:
                    raise MXNetError("unknown parameter %s" % name)
        self._params = params
        self.params_initialized = True

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring.")
            return
        from ..parallel import lm as lm_mod

        opt_params = dict(optimizer_params)
        cfg = dict(self._cfg)
        kwargs = dict(optimizer=optimizer, optimizer_params=opt_params)
        mesh = self._ensure_mesh()
        if self.mode == "dense":
            self._trainer = lm_mod.DenseLMTrainer(**cfg, **kwargs)
        elif self.mode == "sp":
            self._trainer = lm_mod.SPLMTrainer(mesh, **cfg, **kwargs)
        elif self.mode == "pp":
            self._trainer = lm_mod.PPLMTrainer(mesh, **cfg, **kwargs)
        else:
            self._trainer = lm_mod.MoELMTrainer(
                mesh, num_experts=self._num_experts,
                capacity_factor=self._capacity_factor, **cfg, **kwargs)
        self._opt_state = self._trainer.init_opt_state(self._params)
        self.optimizer_initialized = True

    def _forward_trainer(self):
        """Trainer for inference: created on demand so ``bind + load_params +
        score/predict`` works without ``init_optimizer`` (the classic
        Module's inference contract). The throwaway default optimizer only
        parameterizes the (unused) update rule."""
        if self._trainer is None:
            self.init_optimizer()
            self.optimizer_initialized = False  # inference-only: no claim
        return self._trainer

    # ---- step ------------------------------------------------------------
    def _tokens_labels(self, data_batch):
        def as_i32(x):
            if hasattr(x, "data") and hasattr(x, "context"):
                # NDArray: cast on device — the old asnumpy() pulled every
                # token batch to the host just to re-upload it into the step
                x = x.data.astype(np.int32)
            else:
                # fwlint: disable=device-escape — host list/ndarray input: a construction, not a device sync
                x = np.asarray(x, np.int32)
            if self.mode == "dense":
                return x
            # mesh trainers: replicate onto the trainer mesh — a batch
            # committed to one device would collide with the shard_map
            # device set (GSPMD reshards it to the step's layout in-graph)
            import jax
            from jax.sharding import NamedSharding, PartitionSpec

            return jax.device_put(
                x, NamedSharding(self._ensure_mesh(), PartitionSpec()))

        tok = as_i32(data_batch.data[0])
        labels = data_batch.label[0] if data_batch.label else None
        if labels is not None:
            labels = as_i32(labels)
        if self.mode == "pp":
            m = self._microbatches
            b, t = tok.shape
            tok = tok.reshape(m, b // m, t)
            if labels is not None:
                labels = labels.reshape(m, b // m, t)
        return tok, labels

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        train = self.for_training if is_train is None else is_train
        tok, labels = self._tokens_labels(data_batch)
        self._outs = None
        if train and labels is not None:
            self._staged = (tok, labels)
        else:
            self._staged = (tok, None)

    def backward(self, out_grads=None):
        if out_grads is not None:
            raise MXNetError(
                "ParallelLMModule fuses backward into update(); explicit "
                "out_grads are not supported")

    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)

    def update(self):
        assert self.optimizer_initialized
        assert self._staged is not None and self._staged[1] is not None, \
            "call forward(train) with labels before update()"
        tok, labels = self._staged
        self._params, self._opt_state, loss = self._trainer.step(
            self._params, self._opt_state, tok, labels)
        self._last_loss = loss
        # keep the tokens: update_metric after update() evaluates them
        # lazily (see get_outputs)
        self._metric_tokens = tok
        self._staged = None

    @property
    def loss(self):
        """Last step's scalar training loss (mean next-token NLL)."""
        return None if self._last_loss is None else float(self._last_loss)

    def get_outputs(self, merge_multi_context=True):
        """Softmax probabilities (B*T, V) for the current batch.

        Semantics note vs the classic Module: after ``update()`` the step's
        pre-update logits are NOT materialized (they would be O(B·T·V) extra
        output per fused step) — metric outputs are computed lazily with the
        post-update parameters. Loss-curve metrics (Perplexity/Accuracy in a
        fit loop) see a half-step-fresher model; ``.loss`` carries the exact
        in-step training loss."""
        from .. import ndarray as nd
        import jax

        if self._outs is None:
            import jax.numpy as jnp

            tok = (self._staged[0] if self._staged is not None
                   else getattr(self, "_metric_tokens", None))
            assert tok is not None, "call forward first"
            logits = self._forward_trainer().forward(self._params, tok)
            # softmax + reshape stay ON DEVICE: the only host transfer is
            # the consumer's eventual asnumpy (metric update), one pull of
            # the (B*T, V) probs instead of logits-pull + host softmax
            probs = jax.nn.softmax(jnp.asarray(logits, jnp.float32), axis=-1)
            V = self._cfg["vocab_size"]
            self._outs = probs.reshape(-1, V)
        return [nd.NDArray(self._outs)]

    def update_metric(self, eval_metric, labels):
        eval_metric.update(list(labels), self.get_outputs())

    def get_params(self):
        assert self.params_initialized
        from .. import ndarray as nd

        args = {n: nd.array(a) for n, a in self._params.items()}
        return args, {}

    def set_params(self, arg_params, aux_params=None, allow_missing=False,
                   force_init=True):
        if not self.params_initialized:
            self.init_params(arg_params=arg_params, aux_params=aux_params,
                             allow_missing=allow_missing)
            return
        for name, arr in (arg_params or {}).items():
            if name in self._params:
                # NDArray sources stay on device (.data + device-side cast);
                # _params values expose .dtype directly on either backing
                a = _as_array(arr)
                self._params[name] = self._placed(
                    a.astype(self._params[name].dtype))
            elif not allow_missing:
                raise MXNetError("unknown parameter %s" % name)

    def get_input_grads(self, merge_multi_context=True):
        raise MXNetError("ParallelLMModule does not expose input gradients")

    def install_monitor(self, mon):
        raise MXNetError(
            "Monitor is not supported on the fused parallel LM step; train "
            "a dense symbol Module (models/transformer_lm.get_symbol) to "
            "inspect per-node outputs")
