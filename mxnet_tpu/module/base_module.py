"""BaseModule ABC with the fit loop (reference:
python/mxnet/module/base_module.py:79 — fit :375-533: bind → init_params →
init_optimizer → epoch loop of forward_backward/update/update_metric with
checkpoint callbacks; score/predict/forward_backward helpers).

The loop is a faithful behavioral port — including the epoch-end aux-state
averaging across devices via get_params/set_params (base_module.py:514-516),
which matters for BatchNorm statistics parity under data parallelism.
"""
from __future__ import annotations

import logging
import time

import numpy as np

from .. import io
from .. import metric as metric_mod
from .. import ndarray as nd
from .. import telemetry
from ..base import MXNetError
from ..model import BatchEndParam

__all__ = ["BaseModule"]


def _check_input_names(symbol, names, typename, throw):
    """(reference: base_module.py _check_input_names)"""
    args = symbol.list_arguments()
    for name in names:
        if name in args:
            continue
        candidates = [arg for arg in args if not arg.endswith("_weight")
                      and not arg.endswith("_bias") and not arg.endswith("_gamma")
                      and not arg.endswith("_beta")]
        msg = (
            "\033[91mYou created Module with Module(..., %s_names=%s) but "
            "input with name '%s' is not found in symbol.list_arguments(). "
            "Did you mean one of:\n\t%s\033[0m"
            % (typename, str(names), name, "\n\t".join(candidates))
        )
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


class _EvalStepMeter:
    """Step-split telemetry for the eval/serving loops — the same data-wait
    vs compute attribution ``fit`` records, labeled by path
    (``eval.*{path=score|predict}``), so a slow evaluation can be blamed on
    the iterator or the model instead of guessed at. Instrument handles are
    resolved once; with telemetry disabled every call is one flag check."""

    __slots__ = ("_path", "_inst")

    def __init__(self, path):
        self._path = path
        self._inst = None

    def start(self):
        return time.perf_counter() if telemetry.enabled() else 0.0

    def step(self, t0, t_data, data_batch, source_iter):
        """Record one eval step: ``t0``..``t_data`` waited on the iterator,
        ``t_data``..now computed (dispatch + metric/output handling)."""
        if not telemetry.enabled():
            return
        if self._inst is None:
            p = self._path
            self._inst = (
                telemetry.histogram("eval.data_wait_seconds", path=p),
                telemetry.histogram("eval.compute_seconds", path=p),
                telemetry.histogram("eval.step_time_seconds", path=p),
                telemetry.counter("eval.batches", path=p),
                telemetry.counter("eval.samples", path=p),
                telemetry.gauge("eval.imgs_per_sec", path=p),
            )
        h_wait, h_comp, h_step, c_batch, c_samp, g_ips = self._inst
        now = time.perf_counter()
        step_s = now - t0
        h_wait.observe(t_data - t0)
        h_comp.observe(now - t_data)
        h_step.observe(step_s)
        c_batch.inc()
        n = _batch_samples(data_batch, source_iter)
        if n:
            c_samp.inc(n)
            if step_s > 0:
                g_ips.set(n / step_s)


class BaseModule:
    """The base class of a module (reference: base_module.py:79)."""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # ---- high-level ------------------------------------------------------
    def forward_backward(self, data_batch):
        """(reference: base_module.py forward_backward)"""
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None, batch_end_callback=None,
              score_end_callback=None, reset=True, epoch=0):
        """Evaluate (reference: base_module.py score)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        meter = _EvalStepMeter("score")
        data_iter = iter(eval_data)
        nbatch = 0
        while True:
            if num_batch is not None and nbatch == num_batch:
                break
            t0 = meter.start()
            try:
                eval_batch = next(data_iter)
            except StopIteration:
                break
            t_data = meter.start()
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            meter.step(t0, t_data, eval_batch, eval_data)
            if batch_end_callback is not None:
                batch_end_params = BatchEndParam(
                    epoch=epoch, nbatch=nbatch, eval_metric=eval_metric, locals=locals()
                )
                for callback in _as_list(batch_end_callback):
                    callback(batch_end_params)
            actual_num_batch += 1
            nbatch += 1
        if score_end_callback:
            params = BatchEndParam(
                epoch=epoch, nbatch=actual_num_batch, eval_metric=eval_metric, locals=locals()
            )
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """(reference: base_module.py iter_predict)"""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0 : out.shape[0] - pad] for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True, reset=True,
                always_output_list=False):
        """(reference: base_module.py predict)"""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        from .. import context as ctx_mod

        output_list = []
        meter = _EvalStepMeter("predict")
        data_iter = iter(eval_data)
        nbatch = 0
        while True:
            if num_batch is not None and nbatch == num_batch:
                break
            t0 = meter.start()
            try:
                eval_batch = next(data_iter)
            except StopIteration:
                break
            t_data = meter.start()
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            # one bounded host materialization per batch, pinned to the cpu
            # context: predictions must reach the host anyway, and keeping
            # every batch device-resident until the end would grow HBM
            # residency with dataset size — while the old default-context
            # nd.array() wrap re-STAGED each batch on the accelerator
            outputs = [nd.array(out[0 : out.shape[0] - pad].asnumpy(),  # fwlint: disable=device-escape — result materialization (bounded, cpu-pinned): predict outputs leave the device here by design
                                ctx=ctx_mod.cpu())
                       for out in self.get_outputs()]
            output_list.append(outputs)
            meter.step(t0, t_data, eval_batch, eval_data)
            nbatch += 1
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs, (
                    "Cannot merge batches, as num of outputs is not the same "
                    + "in mini-batches. Maybe bucketing is used?"
                )
            output_list2 = [
                nd.array(np.concatenate([out[i].asnumpy() for out in output_list]))  # fwlint: disable=device-escape — merging host-resident batch results, no device sync
                for i in range(num_outputs)
            ]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None, monitor=None,
            auto_resume=None, guard=None):
        """Train (reference: base_module.py:375-533).

        ``auto_resume`` is a checkpoint prefix (the one passed to
        ``callback.do_checkpoint``/``save_checkpoint``): when set, fit picks
        the newest *intact* epoch under that prefix — corrupt or torn files
        from a crash mid-save are CRC-detected and skipped — loads its
        params, and fast-forwards ``begin_epoch``, so a killed-and-relaunched
        training job continues instead of restarting. When the checkpoint
        carries a ``.resume`` sidecar (written by the health guard's
        mid-epoch checkpoints), the data iterator, numpy RNG, and optimizer
        schedule are ALSO restored and training lands on the exact next
        batch; checkpoints without one (every pre-guard file) resume at the
        epoch boundary as before. With no loadable checkpoint it trains
        from scratch.

        ``guard`` enables the training health guard
        (docs/fault_tolerance.md §health-guard): ``None`` defers to
        ``MXNET_GUARD_POLICY``/``MXNET_GUARD_STALL_S`` (off when unset — the
        zero-overhead default), or pass a policy name
        (``'skip'``/``'rollback'``/``'abort'``), a ``guard_mod.GuardPolicy``,
        or a ready ``TrainingGuard``. An active guard classifies each step's
        loss/grad health, skips or rolls back bad updates per its ladder,
        and its stall watchdog turns a hung step into a ``StallError``."""
        from .. import guard as guard_mod
        from .. import initializer as init_mod

        assert num_epoch is not None, "please specify number of epochs"
        if initializer is None:
            initializer = init_mod.Uniform(0.01)
        resume_epoch = None
        resume_state = None
        if auto_resume is not None:
            from ..model import load_latest_valid_checkpoint, load_resume_state

            ckpt = load_latest_valid_checkpoint(auto_resume)
            if ckpt is not None:
                _, arg_params, aux_params, resume_epoch = ckpt
                # checkpoint filenames carry the number of COMPLETED epochs
                # (callback._every saves iter_no+1), so resuming at index
                # resume_epoch repeats nothing and skips nothing
                begin_epoch = max(begin_epoch, resume_epoch)
                # mid-epoch sidecar (guard checkpoints): nbatch/iterator/RNG
                # position within epoch `resume_epoch`; None for plain
                # epoch-boundary checkpoints or any validation failure.
                # Only meaningful when training actually restarts at that
                # epoch — a caller-raised begin_epoch must not fast-forward
                # a LATER epoch by the sidecar's batch count.
                if begin_epoch == resume_epoch:
                    resume_state = load_resume_state(auto_resume,
                                                     resume_epoch)
                self.logger.info(
                    "auto-resume: restored '%s' epoch %d, continuing at "
                    "epoch %d%s", auto_resume, resume_epoch, begin_epoch,
                    " batch %d (exact mid-epoch resume)"
                    % resume_state["nbatch"] if resume_state else "")
        guard_obj = guard_mod.resolve(guard, checkpoint_prefix=auto_resume,
                                      logger=self.logger)
        # elastic membership (docs/distributed.md §elasticity): resolve the
        # kvstore + register with the PS membership registry BEFORE the
        # first PS traffic, and make sure a rollback-capable guard exists —
        # survivors recover from a lost worker by rolling back to its last
        # snapshot instead of dying
        from .. import elastic as elastic_mod
        from .. import fault as fault_mod
        from ..kvstore import KVMembershipError

        elastic_session = None
        if elastic_mod.enabled():
            kvstore, elastic_session = elastic_mod.prepare(
                kvstore, logger=self.logger)
            if elastic_session is not None and guard_obj is None:
                guard_obj = guard_mod.resolve(
                    "rollback", checkpoint_prefix=auto_resume,
                    logger=self.logger)
        import os as _os

        _fault_rank = int(_os.environ.get("DMLC_WORKER_ID", 0) or 0)
        _fit_completed = False
        # cluster observability (docs/observability.md §cluster): resolved
        # after init_optimizer — a PS-backed dist store gets per-batch
        # (rank, step_id) stamping + the cluster-stats publisher; on
        # single-process stores both stay None and the step path pays one
        # None-check per batch, nothing more
        _kv_obj = None
        _kv_set_step = None
        _kv_started_cluster = False
        # opt-in double-buffered async device feed (docs/env_var.md
        # MXNET_FEED_DEPTH): a dedicated transfer thread keeps the next
        # batch(es) device-resident so the loop's data wait is a queue pop.
        # Wrapping before bind lets the first uploads overlap the compile.
        _inner_iter = train_data
        train_data = io.maybe_device_feed(
            train_data, getattr(self, "_context", None))
        _owned_feed = train_data if train_data is not _inner_iter else None
        try:
            self.bind(
                data_shapes=train_data.provide_data, label_shapes=train_data.provide_label,
                for_training=True, force_rebind=force_rebind,
            )
            if monitor is not None:
                self.install_monitor(monitor)
            self.init_params(
                initializer=initializer, arg_params=arg_params, aux_params=aux_params,
                allow_missing=allow_missing,
                # a restored checkpoint must actually land: on an
                # already-initialized module (in-process retry loop calling fit
                # again) the default force_init=False would silently keep the
                # stale in-memory weights while begin_epoch was fast-forwarded
                force_init=force_init or resume_epoch is not None,
            )
            self.init_optimizer(kvstore=kvstore, optimizer=optimizer, optimizer_params=optimizer_params)
            _kv_obj = getattr(self, "_kvstore", None)
            _kv_set_step = getattr(_kv_obj, "set_step", None)
            if getattr(_kv_obj, "start_cluster_stats", None) is not None \
                    and getattr(_kv_obj, "_cluster", None) is None:
                # fit owns the publisher only when it started it — a
                # user-started one (idempotent start) outlives this fit
                _kv_started_cluster = (
                    _kv_obj.start_cluster_stats() is not None)
            if resume_epoch is not None:
                # checkpoints written with save_optimizer_states=True also carry
                # momentum/Adam state — restore it so the resumed run tracks the
                # uninterrupted one; params-only checkpoints (do_checkpoint)
                # resume with fresh optimizer state, as a warm start
                import os

                # try the writer's %04d name first, then the unpadded form —
                # load_latest_valid_checkpoint deliberately accepts hand-saved/
                # renamed 'prefix-N.params', whose sibling is 'prefix-N.states'
                states = next(
                    (s for s in ("%s-%04d.states" % (auto_resume, resume_epoch),
                                 "%s-%d.states" % (auto_resume, resume_epoch))
                     if os.path.exists(s)), None)
                if states is not None and hasattr(self, "load_optimizer_states"):
                    try:
                        self.load_optimizer_states(states)
                        self.logger.info(
                            "auto-resume: restored optimizer states from %s", states)
                    except Exception as exc:  # noqa: BLE001 — corrupt states must
                        # not kill the resume; params are already verified
                        self.logger.warning(
                            "auto-resume: ignoring unloadable optimizer states "
                            "%s: %s", states, exc)
            if resume_state is not None:
                # exact mid-epoch resume: put the numpy RNG and the
                # optimizer's schedule position (num_update, per-index t)
                # back where the sidecar captured them — the .states file
                # restored above carries the moments but not these counts
                from ..model import decode_rng

                rng = decode_rng(resume_state.get("numpy_rng"))
                if rng is not None:
                    np.random.set_state(rng)
                guard_mod._restore_optimizer_counts(
                    self, resume_state.get("optimizer_counts"))
            if elastic_session is not None and elastic_session.joining:
                # relaunched worker: rendezvous with the survivors — adopt
                # the current membership epoch + shard, pull the server's
                # params, and enter the loop at the published restart point
                join_res = elastic_session.join(self, train_data)
                if join_res is None:
                    self.logger.info(
                        "elastic: training already complete — nothing to do")
                    _fit_completed = True
                    return
                begin_epoch, resume_state = join_res
            if validation_metric is None:
                validation_metric = eval_metric
            if not isinstance(eval_metric, metric_mod.EvalMetric):
                eval_metric = metric_mod.create(eval_metric)

            ################################################################################
            # training loop (reference: base_module.py:475-533)
            #
            # Telemetry (docs/observability.md): while telemetry is enabled every
            # batch records its wall time split into data-wait (blocking on the
            # iterator) vs compute (forward_backward+update dispatch — on TPU
            # this is DISPATCH time; XLA executes async, so sustained throughput
            # comes from fit.step_time, not fit.compute), plus imgs/sec and
            # per-epoch structured events. Disabled: one enabled() check/batch.
            ################################################################################
            fit_instruments = None  # stable handles, resolved once when enabled:
            # re-resolving through the registry every batch would take the
            # global lock and re-render keys 6x per step for nothing
            if guard_obj is not None:
                guard_obj.start()
            # with a guard: remember the iterator position as of each
            # fetched batch (the resume contract, io.DataIter.state_dict) so
            # snapshots/checkpoints taken after step n restore to batch n+1
            # even though the loop prefetches n+1 before step n finishes
            _state_fn = getattr(train_data, "state_dict", None)
            track_state = guard_obj is not None and _state_fn is not None
            for epoch in range(begin_epoch, num_epoch):
                tic = time.time()
                telemetry.event("epoch_start", epoch=epoch)
                eval_metric.reset()
                start_nbatch = 0
                if resume_state is not None and epoch == begin_epoch:
                    start_nbatch = self._resume_fast_forward(
                        train_data, resume_state)
                    resume_state = None  # consumed: later epochs start fresh
                if guard_obj is not None:
                    guard_obj.epoch_start(self, train_data, epoch,
                                          start_nbatch)
                while True:  # restarted when the guard rolls back mid-epoch
                    rolled_back = False
                    nbatch = start_nbatch
                    data_iter = iter(train_data)
                    end_of_batch = False
                    tel = telemetry.enabled()
                    t0 = time.perf_counter() if tel else 0.0
                    try:
                        next_data_batch = next(data_iter)
                    except StopIteration:
                        # a mid-epoch resume can land exactly on the epoch's
                        # end: nothing left to train here
                        break
                    next_state = _state_fn() if track_state else None
                    if tel:
                        telemetry.histogram("fit.data_wait_seconds").observe(
                            time.perf_counter() - t0)
                    while not end_of_batch:
                        data_batch = next_data_batch
                        cur_state = next_state  # position as of THIS batch
                        if _kv_set_step is not None:
                            # one step id across the cluster — BSP ranks run
                            # the same (epoch, nbatch) sequence, so every PS
                            # RPC this step issues is attributable to it
                            _kv_set_step((epoch << 32) | nbatch)
                        # `kill_worker` injection point (fault.py): the
                        # machine-loss seam the elastic kill→reconfigure→
                        # rejoin cycle is tested through
                        fault_mod.kill_worker(_fault_rank)
                        if guard_obj is not None:
                            guard_obj.check_stall()
                        tel = telemetry.enabled()
                        if tel and fit_instruments is None:
                            fit_instruments = (
                                telemetry.histogram("fit.compute_seconds"),
                                telemetry.histogram("fit.data_wait_seconds"),
                                telemetry.histogram("fit.step_time_seconds"),
                                telemetry.counter("fit.batches"),
                                telemetry.counter("fit.samples"),
                                telemetry.gauge("fit.imgs_per_sec"),
                                telemetry.histogram("fit.guard_seconds"),
                            )
                        t_step = time.perf_counter() if tel else 0.0
                        if monitor is not None:
                            monitor.tic()
                        # span, not gated on `tel`: it is the step's annotation on
                        # a jax.profiler trace, and with the MXNet profiler running
                        # but telemetry off it must still land on the chrome trace
                        bad_reason = None
                        bad_applied = False
                        membership_changed = False
                        # epoch/nbatch args let trace_merge match the same
                        # BSP step across worker lanes in the merged trace
                        with telemetry.span("fit.step", "fit", step=nbatch,
                                            epoch=epoch, nbatch=nbatch):
                            try:
                                self.forward_backward(data_batch)
                                if guard_obj is not None:
                                    # sentinel BEFORE update: a bad
                                    # classic-path step is discarded with
                                    # the params untouched
                                    t_guard = (time.perf_counter() if tel
                                               else 0.0)
                                    bad_reason = guard_obj.step_check(self)
                                    if tel:
                                        fit_instruments[6].observe(
                                            time.perf_counter() - t_guard)
                                if bad_reason is None:
                                    self.update()
                                    if guard_obj is not None:
                                        # fused path: fwd+bwd+update ran as
                                        # one program — outputs observable
                                        # only now, with the update already
                                        # applied
                                        t_guard = (time.perf_counter() if tel
                                                   else 0.0)
                                        bad_reason = guard_obj.post_check(
                                            self)
                                        if tel:
                                            fit_instruments[6].observe(
                                                time.perf_counter() - t_guard)
                                        bad_applied = bad_reason is not None
                            except KVMembershipError:
                                # the cluster reconfigured under this step
                                # (a worker was lost or joined); without an
                                # elastic session this stays what it was —
                                # fatal
                                if elastic_session is None:
                                    raise
                                membership_changed = True
                        t_compute = time.perf_counter() if tel else 0.0
                        if membership_changed:
                            # staggered failures: if ANOTHER membership
                            # change lands while this one is being
                            # recovered (the coordinator's re-seed or the
                            # post-adopt traffic gets rejected), restart
                            # recovery against the newest epoch instead of
                            # dying mid-reconfiguration
                            for _attempt in range(5):
                                try:
                                    r_epoch, r_nbatch, iter_restored = \
                                        elastic_session.reconfigure(
                                            self, train_data, guard_obj)
                                    break
                                except KVMembershipError:
                                    self.logger.warning(
                                        "elastic: membership changed again "
                                        "during reconfiguration (attempt "
                                        "%d/5) — restarting recovery",
                                        _attempt + 1)
                            else:
                                raise MXNetError(
                                    "elastic: membership kept changing "
                                    "through 5 reconfiguration attempts — "
                                    "giving up (the cluster is flapping)")
                            if r_epoch != epoch:
                                self.logger.warning(
                                    "elastic: snapshot epoch %d != current "
                                    "epoch %d — resuming within the current "
                                    "epoch at its batch position", r_epoch,
                                    epoch)
                            eval_metric.reset()
                            start_nbatch = (r_nbatch if iter_restored
                                            else nbatch + 1)
                            rolled_back = True
                            break
                        if bad_reason is not None:
                            action = guard_obj.bad_step(bad_reason, epoch,
                                                        nbatch,
                                                        applied=bad_applied)
                            if action == "abort":
                                raise guard_obj.abort_error(bad_reason, epoch,
                                                            nbatch)
                            if action == "rollback":
                                _, r_nbatch, iter_restored = \
                                    guard_obj.rollback(self, train_data)
                                # metric counts from the undone span are
                                # wrong either way; restart it clean
                                eval_metric.reset()
                                start_nbatch = (r_nbatch if iter_restored
                                                else nbatch + 1)
                                rolled_back = True
                                break
                            # action == "skip": fall through — the bad
                            # gradients are dropped (no update ran), the
                            # batch still advances
                        try:
                            # pre-fetch next batch to overlap host IO with device work
                            with telemetry.span("fit.data_wait", "fit"):
                                next_data_batch = next(data_iter)
                            next_state = _state_fn() if track_state else None
                            self.prepare(next_data_batch)
                        except StopIteration:
                            end_of_batch = True
                        t_data = time.perf_counter() if tel else 0.0
                        if bad_reason is None:
                            self.update_metric(eval_metric, data_batch.label)
                            if guard_obj is not None:
                                guard_obj.good_step(self, train_data, epoch,
                                                    nbatch, cur_state)
                        if tel:
                            h_comp, h_wait, h_step, c_batch, c_samp, g_ips = \
                                fit_instruments[:6]
                            now = time.perf_counter()
                            step_s = now - t_step
                            h_comp.observe(t_compute - t_step)
                            h_wait.observe(t_data - t_compute)
                            h_step.observe(step_s)
                            n = _batch_samples(data_batch, train_data)
                            c_batch.inc()
                            if n:
                                c_samp.inc(n)
                                if step_s > 0:
                                    g_ips.set(n / step_s)
                        if monitor is not None:
                            monitor.toc_print()
                        if batch_end_callback is not None:
                            batch_end_params = BatchEndParam(
                                epoch=epoch, nbatch=nbatch, eval_metric=eval_metric, locals=locals()
                            )
                            for callback in _as_list(batch_end_callback):
                                callback(batch_end_params)
                        nbatch += 1
                    if not rolled_back:
                        break
                if guard_obj is not None:
                    # epoch-boundary work (validation score, checkpoint
                    # callbacks, iterator reset) is not a stall however long
                    # it takes; the first step of the next epoch re-arms
                    guard_obj.suspend_watchdog()
                # one epoch of training is finished
                for name, val in eval_metric.get_name_value():
                    self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
                toc = time.time()
                self.logger.info("Epoch[%d] Time cost=%.3f", epoch, (toc - tic))
                telemetry.counter("fit.epochs").inc()
                telemetry.event(
                    "epoch_end", epoch=epoch, seconds=round(toc - tic, 6),
                    nbatch=nbatch,
                    metrics={name: val
                             for name, val in eval_metric.get_name_value()})
                # sync aux params across devices (reference: base_module.py:514-516)
                arg_params_, aux_params_ = self.get_params()
                self.set_params(arg_params_, aux_params_)
                if epoch_end_callback is not None:
                    for callback in _as_list(epoch_end_callback):
                        callback(epoch, self.symbol, arg_params_, aux_params_)
                # ----------------------------------------
                # evaluation on validation set
                if eval_data:
                    res = self.score(
                        eval_data, validation_metric,
                        score_end_callback=eval_end_callback,
                        batch_end_callback=eval_batch_end_callback, epoch=epoch,
                    )
                    for name, val in res:
                        self.logger.info("Epoch[%d] Validation-%s=%f", epoch, name, val)
                # end of 1 epoch, reset the data-iter for another epoch. An
                # owned feed skips the FINAL reset: it would only respawn the
                # transfer thread to decode+upload batches the close() in the
                # finally immediately discards.
                if _owned_feed is None or epoch < num_epoch - 1:
                    train_data.reset()
            _fit_completed = True
        except KeyboardInterrupt:
            # the stall watchdog interrupts a wedged step via SIGINT (the
            # only signal that reaches a main thread blocked in a queue pop
            # or device sync); translate it back into the classified error.
            # A real Ctrl-C (watchdog never fired) re-raises untouched.
            if guard_obj is not None and guard_obj.stall_fired:
                raise guard_obj.stall_error() from None
            raise
        finally:
            if _kv_started_cluster:
                # fit started the publisher; a finished (or crashed) fit
                # must not leave a daemon thread polling the PS tier
                _kv_obj.stop_cluster_stats()
            if elastic_session is not None:
                # graceful end-of-training deregisters from the registry;
                # a FAILED fit only stops heartbeating — the registry's
                # lapse detection reconfigures the survivors, and the
                # launcher's relaunch rejoins this rank
                elastic_session.close(done=_fit_completed)
            if guard_obj is not None:
                guard_obj.close()
            if _owned_feed is not None:
                # fit created the feed wrapper: stop its transfer thread on
                # EVERY exit path (a crashed fit must not leave a thread
                # pulling the caller's iterator — a retrying fit() would
                # wrap a second feed over the same iterator and split its
                # batches between the two), and leave the caller's
                # iterator freshly reset.
                _owned_feed.close()
                _inner_iter.reset()

    def _resume_fast_forward(self, train_data, resume_state):
        """Position ``train_data`` at the mid-epoch batch a ``.resume``
        sidecar recorded; returns the nbatch to continue from.

        Prefers the iterator's exact ``load_state`` seek; an iterator
        without one is drained batch-by-batch to the same position (slower,
        same data alignment). Either way the post-resume batch stream is
        identical to the uninterrupted run's."""
        nbatch = int(resume_state.get("nbatch") or 0)
        state = resume_state.get("iter_state")
        if state is not None and \
                getattr(train_data, "load_state", None) is not None:
            try:
                train_data.load_state(state)
                self.logger.info(
                    "auto-resume: iterator repositioned to batch %d "
                    "(exact mid-epoch resume)", nbatch)
                return nbatch
            except Exception as exc:  # noqa: BLE001 — seek failure degrades
                # to the drain fallback below, never kills the resume
                self.logger.warning(
                    "auto-resume: iterator load_state failed (%s); "
                    "draining %d batches instead", exc, nbatch)
        it = iter(train_data)
        for done in range(nbatch):
            try:
                next(it)
            except StopIteration:
                self.logger.warning(
                    "auto-resume: iterator exhausted after %d of %d "
                    "skipped batches — epoch sizes changed?", done, nbatch)
                break
        return nbatch

    # ---- symbol ----------------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    def prepare(self, data_batch):
        """Prepare for processing a data batch (no-op by default)."""

    # ---- abstract interface ---------------------------------------------
    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False, force_init=True):
        self.init_params(
            initializer=None, arg_params=arg_params, aux_params=aux_params,
            allow_missing=allow_missing, force_init=force_init,
        )

    def save_params(self, fname):
        """(reference: base_module.py save_params)"""
        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
        nd.save(fname, save_dict)

    def load_params(self, fname):
        """(reference: base_module.py load_params)"""
        save_dict = nd.load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        assert not merge_multi_context
        return []

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized
        assert not states and not value

    def install_monitor(self, mon):
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),), force_init=False):
        raise NotImplementedError()

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()


def _as_list(obj):
    if isinstance(obj, (list, tuple)):
        return obj
    return [obj]


def _batch_samples(data_batch, train_data):
    """Samples in this batch, for throughput metrics: leading dim of the
    first data array, net of padding; iterator batch_size as the fallback."""
    try:
        n = int(data_batch.data[0].shape[0])
    except (AttributeError, IndexError, TypeError):
        n = int(getattr(train_data, "batch_size", 0) or 0)
    pad = getattr(data_batch, "pad", None)
    if pad:
        n = max(n - int(pad), 0)
    return n
