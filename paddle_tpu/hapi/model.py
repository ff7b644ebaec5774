"""hapi high-level Model API.

Reference: python/paddle/hapi/model.py — ``Model`` (``:1054``) wrapping a
Layer with prepare/fit/evaluate/predict/save/load, driven by the callbacks
in callbacks.py; distributed data parallel handled inside
(prepare_distributed_context, model.py:225).

TPU-native: the dygraph path runs the eager tape; under a hybrid topology
the network is wrapped in paddle_tpu.DataParallel so inputs shard over the
dp mesh axis and GSPMD emits the gradient reductions.
"""
from __future__ import annotations

import os
import pickle
import warnings
from typing import List, Optional, Sequence, Union

import numpy as np

from ..core.tensor import Tensor
from ..io.dataloader import DataLoader
from ..io.dataset import Dataset
from ..metric import Metric
from ..nn.layer import Layer
from .callbacks import config_callbacks


def _to_list(x):
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _np(x):
    return np.asarray(x._data) if isinstance(x, Tensor) else np.asarray(x)


class Model:
    """hapi/model.py Model:1054 analog."""

    def __init__(self, network: Layer, inputs=None, labels=None):
        self.network = network
        self._inputs = _to_list(inputs)
        self._labels = _to_list(labels)
        self._optimizer = None
        self._loss = None
        self._metrics: List[Metric] = []
        self._prepared = False
        self.stop_training = False
        self._step_guard = None
        self._ckpt_include_optimizer = True
        self._jit = False
        self._train_step = None
        self._fused_n_in = None
        self._pending_eager_grads = False
        self._resume_replay = False

    # -- setup ---------------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None, jit=False, plan=None):
        """``jit=True`` compiles forward + backward + optimizer update into
        ONE fused XLA executable (``paddle_tpu.jit.TrainStep``) with the
        param/master/opt-state buffers DONATED by default — XLA updates
        them in place, halving steady-state update HBM. The DF006 alias
        audit is consulted first; any finding downgrades to non-donating.
        ``train_batch`` falls back to the eager tape whenever the fused
        step can't serve the call (metrics that need forward outputs, an
        armed step guard, gradient accumulation).

        ``plan`` (a ``distributed.mesh.TrainMeshPlan``, from
        ``MeshRuntime.train_plan``) compiles the fused step SPMD: state
        lives sharded per the plan, the runtime SH/MEM gate vets the
        program before compile, and per-axis collective bytes feed the
        roofline gap attribution. Requires ``jit=True``."""
        if plan is not None and not jit:
            raise ValueError("prepare(plan=...) requires jit=True — the "
                             "mesh plan shards the FUSED train step")
        self._mesh_plan = plan
        self._optimizer = optimizer
        if loss is not None and not (isinstance(loss, Layer)
                                     or callable(loss)):
            raise TypeError("loss must be a Layer or a callable")
        self._loss = loss
        self._metrics = _to_list(metrics)
        for m in self._metrics:
            if not isinstance(m, Metric):
                raise TypeError(f"metric {m} is not a paddle_tpu.metric.Metric")
        self._amp_configs = amp_configs
        self._jit = bool(jit)
        self._train_step = None
        self._fused_n_in = None
        self._prepared = True

    def parameters(self, *args, **kwargs):
        return self.network.parameters(*args, **kwargs)

    # -- single-batch entry points -------------------------------------------
    def _forward(self, inputs):
        ins = [x if isinstance(x, Tensor) else Tensor(np.asarray(x))
               for x in _to_list(inputs)]
        outputs = self.network(*ins)
        return _to_list(outputs)

    def _compute_loss(self, outputs, labels):
        labels = [y if isinstance(y, Tensor) else Tensor(np.asarray(y))
                  for y in _to_list(labels)]
        loss = self._loss(*(outputs + labels))
        return loss, labels

    def train_batch(self, inputs, labels=None, update=True):
        """model.py train_batch analog: one eager forward/backward/(step).

        With a step guard enabled (enable_step_guard), a non-finite loss
        SKIPS backward + optimizer.step (NaN gradients would poison every
        weight), counts the skip, and after K consecutive bad steps rolls
        the model back to the last valid checkpoint."""
        import time as _time
        assert self._prepared, "call prepare() first"
        self.network.train()
        from ..resilience.chaos import fault_point
        spec = fault_point("train.step")
        if spec is None and self._can_fuse(update):
            return self._train_batch_fused(inputs, labels)
        t0 = _time.perf_counter()
        outputs = self._forward(inputs)
        loss, labels_t = self._compute_loss(outputs, labels)
        if spec is not None and spec.kind == "nan_grad":
            # the injected divergence: a NaN loss whose backward would
            # produce NaN gradients — exactly what the guard exists for
            loss = loss * float("nan")
        if self._step_guard is not None \
                and self._step_guard.observe(float(loss)) != "ok":
            # skip: no backward, no step; drop any accumulated gradients
            # (they may predate the rollback's restored weights)
            if self._optimizer is not None:
                self._optimizer.clear_grad()
            metrics = self._update_metrics(outputs, labels_t)
            self._observe_train_step(_time.perf_counter() - t0, inputs)
            return self._wrap_loss(loss, metrics)
        loss.backward()
        if update:
            self._optimizer.step()
            self._optimizer.clear_grad()
            self._pending_eager_grads = False
        else:
            self._pending_eager_grads = True
        metrics = self._update_metrics(outputs, labels_t)
        self._observe_train_step(_time.perf_counter() - t0, inputs)
        return self._wrap_loss(loss, metrics)

    # -- fused (compiled) train step ------------------------------------------
    def _can_fuse(self, update):
        """The fused TrainStep serves only the plain steady-state step:
        no metrics (they need eager forward outputs), no armed step guard
        (it inspects the loss BEFORE backward), no gradient accumulation
        in flight (the fused step fuses backward+update, it cannot add to
        an eager tape's accumulated grads)."""
        return (self._jit and update and not self._metrics
                and self._step_guard is None
                and not self._pending_eager_grads
                and self._loss is not None and self._optimizer is not None)

    def _ensure_train_step(self, n_in):
        if self._train_step is not None and self._fused_n_in == n_in:
            return self._train_step
        from .. import jit as jit_mod
        from ..perf.compile_cache import donation_safe
        donate, findings = donation_safe()
        if not donate:
            warnings.warn(
                f"DF006 alias audit reported {len(findings)} finding(s); "
                "the fused train step will NOT donate param/opt-state "
                "buffers (donation with a wrong alias declaration corrupts "
                "memory on hardware)")
        network, loss = self.network, self._loss

        def loss_fn(*batch):
            outputs = _to_list(network(*batch[:n_in]))
            return loss(*(outputs + list(batch[n_in:])))

        amp = self._amp_configs if isinstance(self._amp_configs, dict) \
            else None
        self._fused_n_in = n_in
        self._train_step = jit_mod.TrainStep(
            loss_fn, self._optimizer, amp=amp, donate=donate,
            mesh_plan=getattr(self, "_mesh_plan", None),
            opprof_label="hapi.train_step")
        return self._train_step

    def _train_batch_fused(self, inputs, labels):
        import time as _time
        t0 = _time.perf_counter()
        ins = [x if isinstance(x, Tensor) else Tensor(np.asarray(x))
               for x in _to_list(inputs)]
        lbls = [y if isinstance(y, Tensor) else Tensor(np.asarray(y))
                for y in _to_list(labels)]
        step = self._ensure_train_step(len(ins))
        args = ins + lbls
        if self._resume_replay:
            # TrainStep's discovery pass doubles as a REAL eager step, and
            # the eager optimizer update is not bitwise-identical to the
            # fused XLA one (different reassociation). An uninterrupted run
            # takes that eager step at step 1; a resumed run would take it
            # at the first post-restore step, forking the trajectory by an
            # ulp. Replay instead: snapshot restored state, let the
            # discovery build+compile, roll the state back (re-placed onto
            # the compiled step's shardings), and run the SAME batch through
            # the compiled path — every post-restore step is then the exact
            # executable the uninterrupted run used.
            self._resume_replay = False
            if not step._cache:
                snap = self._replay_snapshot()
                step(*args)  # discovery + compile; its update is discarded
                self._replay_rollback(snap)
        loss = step(*args)
        self._observe_train_step(_time.perf_counter() - t0, inputs)
        return self._wrap_loss(loss, [])

    def _replay_snapshot(self):
        """Everything the discovery pass mutates: live model tensors,
        optimizer accumulators/masters/step count, and the RNG key."""
        from ..core import random as _random
        opt = self._optimizer
        return {
            "tensors": [(t, t._data, t._grad)
                        for t in self.network._state_dict_raw().values()],
            "accs": {name: dict(store)
                     for name, store in opt._accumulators.items()},
            "masters": dict(opt._master_weights),
            "step_count": opt._step_count,
            "rng": _random.default_generator().get_state(),
        }

    @staticmethod
    def _place_like(old, cur):
        """Re-commit a snapshot array onto the sharding its slot now has
        (the build placed state onto the mesh plan; the compiled step's
        in_shardings reject anything else). device_put is bitwise."""
        import jax
        if old is cur or not isinstance(cur, jax.Array) \
                or not isinstance(old, jax.Array) \
                or getattr(cur, "sharding", None) is None \
                or old.shape != cur.shape:
            return old
        return jax.device_put(old, cur.sharding)

    def _replay_rollback(self, snap):
        from ..core import random as _random
        opt = self._optimizer
        for t, data, grad in snap["tensors"]:
            t._data = self._place_like(data, t._data)
            t._grad = grad
        for name, store in snap["accs"].items():
            cur = opt._accumulators.setdefault(name, {})
            for pid, arr in store.items():
                cur[pid] = self._place_like(arr, cur.get(pid, arr))
        for pid, arr in snap["masters"].items():
            opt._master_weights[pid] = self._place_like(
                arr, opt._master_weights.get(pid, arr))
        opt._step_count = snap["step_count"]
        _random.default_generator().set_state(snap["rng"])

    # -- resilience ----------------------------------------------------------
    def _checkpoint_state(self):
        """The ONE state-dict shape save_checkpoint and the rollback
        restore share (live tensors: restore fills them in place)."""
        sd = {"model": self.network.state_dict()}
        if self._ckpt_include_optimizer and self._optimizer is not None:
            sd["opt"] = self._optimizer.state_dict()
        return sd

    def save_checkpoint(self, manager, step: int, blocking: bool = True):
        """Publish model (+ optimizer) state through a resilience
        CheckpointManager (atomic, checksummed, retained)."""
        return manager.save(self._checkpoint_state(), step,
                            blocking=blocking)

    def resume_from(self, manager, runtime=None):
        """Restore the newest VALID checkpoint into the live model (and
        optimizer) and return its step, or None when the root holds no
        restorable step. Works with both manager flavors; for a
        ``ShardedCheckpointManager`` the restore is elastic — the
        checkpoint re-places under ``runtime`` (default: the prepared
        mesh plan's runtime), whatever mesh it was saved on. Optimizer
        state is pushed back through ``set_state_dict`` because
        ``Optimizer.state_dict()`` hands out fresh wrappers — filling
        those in place would not reach the live accumulators."""
        opt = self._optimizer
        if opt is not None and self._ckpt_include_optimizer:
            # a freshly-built optimizer creates accumulators lazily on
            # its first step; materialize them NOW (and the fp32 masters
            # multi_precision will want) so the checkpoint's moment/
            # master keys have live targets to restore into
            import jax.numpy as jnp
            for p in opt._parameter_list:
                opt._create_accumulators_for(p)
                if opt._multi_precision and p.dtype != jnp.float32:
                    opt._master_weight(p)
        sd = self._checkpoint_state()
        if runtime is None:
            runtime = getattr(getattr(self, "_mesh_plan", None),
                              "runtime", None)
        step = manager.restore_latest(sd, runtime=runtime)
        if step is not None and self._optimizer is not None \
                and "opt" in sd:
            self._optimizer.set_state_dict(sd["opt"])
        if step is not None:
            # the next fused train_batch must not let the discovery pass's
            # eager update touch the restored state (see _train_batch_fused)
            self._resume_replay = True
        return step

    def enable_step_guard(self, rollback_after: Optional[int] = None,
                          checkpoint_manager=None,
                          include_optimizer: bool = True):
        """Arm the non-finite-loss policy on train_batch: skip + count
        every bad step; with `checkpoint_manager` (and `rollback_after`
        = K), the K-th CONSECUTIVE bad step restores the newest valid
        checkpoint saved via save_checkpoint. Returns the StepGuard (its
        ``skipped`` / ``rollbacks`` counters are the test surface)."""
        from ..resilience.recovery import StepGuard
        self._ckpt_include_optimizer = include_optimizer
        restore_fn = None
        if checkpoint_manager is not None:
            def restore_fn():
                return checkpoint_manager.restore_latest(
                    self._checkpoint_state())
        self._step_guard = StepGuard(rollback_after=rollback_after,
                                     restore_fn=restore_fn)
        return self._step_guard

    def disable_step_guard(self):
        self._step_guard = None

    def _observe_train_step(self, dt, inputs):
        """Feed the telemetry registry: step latency, throughput, MFU."""
        from ..observability.metrics import get_registry
        reg = get_registry()
        reg.counter("train_steps_total", "hapi train_batch calls").inc()
        reg.histogram("train_step_seconds",
                      "hapi train_batch wall time").observe(dt)
        ins = _to_list(inputs)
        shapes = tuple(tuple(getattr(x, "shape", None)
                             or np.asarray(x).shape) for x in ins)
        tokens = int(np.prod(shapes[0])) if shapes and shapes[0] else 0
        if tokens and dt > 0:
            reg.gauge("train_tokens_per_sec",
                      "input elements consumed per second by "
                      "train_batch").set(tokens / dt)
        from ..ops import pallas as _pl
        # utilization is defined against a TPU's peak: off the chip the
        # step publishes its time and rate and no MFU
        fwd = self._fwd_flops_estimate(shapes) if _pl.on_tpu() else 0
        if fwd and dt > 0:
            from ..utils.flops import peak_device_flops
            # train ≈ 3× forward (fwd + ~2× bwd), the usual MFU convention
            mfu = 3.0 * fwd / (dt * peak_device_flops())
            reg.gauge("train_mfu",
                      "model FLOPs utilization of the train step").set(
                          mfu)
            # join against ROOFLINE.json: publishes roofline.mfu_gap and
            # the per-phase gap attribution (no-op without the file)
            from ..observability import roofline_attr
            comm_by_axis = None
            mp = getattr(self, "_mesh_plan", None)
            if mp is not None:
                comm_by_axis = mp.collective_bytes_by_axis() or None
                if comm_by_axis:
                    axis_bytes = reg.counter(
                        "collective.axis_bytes_total",
                        "analytic per-step collective bytes of the "
                        "compiled SPMD train step, by mesh axis",
                        labelnames=("axis",))
                    for ax, nb in comm_by_axis.items():
                        axis_bytes.labels(axis=ax).inc(nb)
            roofline_attr.observe_train_step(
                dt, observed_mfu=mfu, tokens=tokens or None,
                params=self._param_count_estimate(),
                comm_bytes_by_axis=comm_by_axis)

    def _param_count_estimate(self) -> Optional[int]:
        """Cached trainable-parameter count (roofline config matching)."""
        n = getattr(self, "_param_count", None)
        if n is None:
            try:
                n = sum(int(np.prod(p.shape))
                        for p in self.network.parameters())
            except Exception:
                n = 0
            self._param_count = n
        return n or None

    def _fwd_flops_estimate(self, shapes):
        """Per-input-shape forward-FLOPs estimate via utils.flops; 0 when
        the hook walker can't drive this net (e.g. int-id inputs)."""
        cache = getattr(self, "_flops_cache", None)
        if cache is None:
            cache = self._flops_cache = {}
        if shapes not in cache:
            try:
                from ..utils.flops import flops as _flops
                cache[shapes] = _flops(self.network,
                                       [list(s) for s in shapes])
            except Exception:
                cache[shapes] = 0
        return cache[shapes]

    def eval_batch(self, inputs, labels=None):
        assert self._prepared, "call prepare() first"
        self.network.eval()
        from ..autograd import no_grad
        with no_grad():
            outputs = self._forward(inputs)
            if self._loss is not None and labels is not None:
                loss, labels_t = self._compute_loss(outputs, labels)
            else:
                loss, labels_t = None, [
                    y if isinstance(y, Tensor) else Tensor(np.asarray(y))
                    for y in _to_list(labels)]
        metrics = self._update_metrics(outputs, labels_t)
        return self._wrap_loss(loss, metrics)

    def predict_batch(self, inputs):
        self.network.eval()
        from ..autograd import no_grad
        with no_grad():
            outputs = self._forward(inputs)
        return [_np(o) for o in outputs]

    def _update_metrics(self, outputs, labels):
        vals = []
        for m in self._metrics:
            computed = m.compute(*(outputs + labels))
            if not isinstance(computed, (list, tuple)):
                computed = [computed]
            vals.append(m.update(*computed))
        return vals

    def _wrap_loss(self, loss, metrics):
        loss_np = [float(loss)] if loss is not None else []
        if self._metrics:
            return loss_np, metrics
        return loss_np

    # -- loops ----------------------------------------------------------------
    def _make_loader(self, data, batch_size, shuffle, num_workers,
                     drop_last=False, prefetch=False):
        if data is None or isinstance(data, DataLoader):
            return data
        if isinstance(data, Dataset) or hasattr(data, "__getitem__"):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                              num_workers=num_workers, drop_last=drop_last,
                              prefetch_to_device=prefetch)
        return data  # any iterable of batches

    def _split_batch(self, batch, has_labels=True):
        """Split a loader batch into (inputs, labels) by declared arity."""
        batch = _to_list(batch)
        if self._labels:
            n_lbl = len(self._labels)
        elif self._loss is not None:
            n_lbl = 1
        else:
            n_lbl = 0
        if not has_labels and len(batch) <= n_lbl:
            # predict path with an unlabeled dataset: the whole batch is input
            return batch, []
        n_in = len(self._inputs) or max(len(batch) - n_lbl, 1)
        ins, lbls = batch[:n_in], batch[n_in:]
        return ins, lbls if has_labels else []

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None,
            prefetch_to_device=True, checkpoint=None, checkpoint_freq=1,
            resume=True):
        """model.py fit analog.

        ``prefetch_to_device`` (default on) double-buffers host-to-device
        transfers for loaders fit constructs itself: batch N+1 lands on
        device while step N runs. Pass a pre-built DataLoader to control
        prefetching yourself.

        ``checkpoint`` (a resilience ``CheckpointManager`` or
        ``ShardedCheckpointManager``) turns on periodic checkpointing:
        every ``checkpoint_freq`` global steps the model (+ optimizer)
        state publishes asynchronously (at most one save in flight; the
        next save joins the previous, so a failed publish surfaces as a
        crash whose restart falls back to the last committed step), and
        a final blocking save captures the end state. With ``resume``
        (default) fit first restores the newest valid step — elastically,
        under the prepared mesh plan's runtime — and fast-forwards the
        loader past the batches that step already consumed, so an
        interrupted run continues the SAME trajectory."""
        assert self._prepared, "call prepare() first"
        start_step = 0
        if checkpoint is not None and resume:
            restored = self.resume_from(checkpoint)
            if restored is not None:
                start_step = int(restored)
        loader = self._make_loader(train_data, batch_size, shuffle,
                                   num_workers, drop_last=drop_last,
                                   prefetch=prefetch_to_device)
        eval_loader = self._make_loader(eval_data, batch_size, False,
                                        num_workers)
        steps = len(loader) if hasattr(loader, "__len__") else None
        cbks = config_callbacks(callbacks, model=self, epochs=epochs,
                                batch_size=batch_size, steps=steps,
                                log_freq=log_freq, verbose=verbose,
                                save_freq=save_freq, save_dir=save_dir,
                                metrics=self._metrics_name())
        self.stop_training = False
        cbks.on_train_begin({})
        iters_done = start_step
        to_skip = start_step
        logs = {}
        for epoch in range(epochs):
            cbks.on_epoch_begin(epoch, {})
            for m in self._metrics:
                m.reset()
            pending_grads = False
            for step, batch in enumerate(loader):
                if to_skip > 0:
                    # resume fast-forward: these batches trained before
                    # the restored checkpoint was taken
                    to_skip -= 1
                    continue
                cbks.on_train_batch_begin(step, {})
                ins, lbls = self._split_batch(batch)
                update = ((step + 1) % accumulate_grad_batches == 0)
                res = self.train_batch(ins, lbls, update=update)
                pending_grads = not update
                logs = self._merge_logs(res)
                cbks.on_train_batch_end(step, logs)
                iters_done += 1
                if checkpoint is not None \
                        and iters_done % checkpoint_freq == 0:
                    checkpoint.wait()      # join the previous async save
                    self.save_checkpoint(checkpoint, iters_done,
                                         blocking=False)
                if num_iters is not None and iters_done >= num_iters:
                    self.stop_training = True
                if self.stop_training:
                    break
            if pending_grads:
                # flush the accumulation tail so gradients never leak into
                # the next epoch's window (works for len-less loaders too)
                self._optimizer.step()
                self._optimizer.clear_grad()
                self._pending_eager_grads = False
            cbks.on_epoch_end(epoch, logs)
            if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                self._run_eval(eval_loader, cbks)
            if self.stop_training:
                break
        if checkpoint is not None:
            checkpoint.wait()
            if iters_done > start_step \
                    and (iters_done % checkpoint_freq != 0
                         or checkpoint.latest_step() != iters_done):
                self.save_checkpoint(checkpoint, iters_done,
                                     blocking=True)
        cbks.on_train_end(logs)

    def _run_eval(self, loader, cbks, num_iters=None):
        for m in self._metrics:
            m.reset()
        steps = len(loader) if hasattr(loader, "__len__") else None
        cbks.on_eval_begin({"steps": steps})
        logs = {}
        for step, batch in enumerate(loader):
            cbks.on_eval_batch_begin(step, {})
            ins, lbls = self._split_batch(batch)
            res = self.eval_batch(ins, lbls)
            logs = self._merge_logs(res)
            cbks.on_eval_batch_end(step, logs)
            if num_iters is not None and step + 1 >= num_iters:
                break
        final = self._finalize_logs(logs)
        cbks.on_eval_end(final)
        return final

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_iters=None):
        """model.py evaluate analog: returns {'loss': [...], metric: value}."""
        assert self._prepared, "call prepare() first"
        loader = self._make_loader(eval_data, batch_size, False, num_workers)
        cbks = config_callbacks(callbacks, model=self, batch_size=batch_size,
                                log_freq=log_freq, verbose=verbose,
                                metrics=self._metrics_name(), mode="eval")
        return self._run_eval(loader, cbks, num_iters=num_iters)

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, verbose=1, callbacks=None):
        """model.py predict analog: list (per output) of per-batch arrays,
        or stacked along batch when stack_outputs=True."""
        loader = self._make_loader(test_data, batch_size, False, num_workers)
        cbks = config_callbacks(callbacks, model=self, batch_size=batch_size,
                                verbose=verbose, mode="predict")
        cbks.on_predict_begin({})
        outputs: Optional[List[list]] = None
        for step, batch in enumerate(loader):
            cbks.on_predict_batch_begin(step, {})
            ins, _ = self._split_batch(batch, has_labels=False)
            outs = self.predict_batch(ins)
            if outputs is None:
                outputs = [[] for _ in outs]
            for slot, o in zip(outputs, outs):
                slot.append(o)
            cbks.on_predict_batch_end(step, {})
        cbks.on_predict_end({})
        if outputs is None:
            return []
        if stack_outputs:
            return [np.concatenate(slot, axis=0) for slot in outputs]
        return outputs

    def _metrics_name(self):
        names = ["loss"]
        for m in self._metrics:
            names.extend(_to_list(m.name()))
        return names

    def _merge_logs(self, res):
        logs = {}
        if self._metrics:
            loss_np, _ = res
        else:
            loss_np = res
        if loss_np:
            logs["loss"] = loss_np[0] if len(loss_np) == 1 else loss_np
        for m in self._metrics:
            names = _to_list(m.name())
            vals = _to_list(m.accumulate())
            for n, v in zip(names, vals):
                logs[n] = v
        return logs

    def _finalize_logs(self, logs):
        return dict(logs)

    # -- persistence -----------------------------------------------------------
    def save(self, path: str, training: bool = True):
        """model.py save analog: <path>.pdparams (+ .pdopt). training=False
        exports the inference program via paddle_tpu.jit.save."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        if not training:
            from .. import jit
            spec = self._inputs or None
            jit.save(self.network, path, input_spec=spec)
            return
        from ..framework.io import save as fw_save
        fw_save(self.network.state_dict(), path + ".pdparams")
        if self._optimizer is not None:
            fw_save(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path: str, skip_mismatch: bool = False,
             reset_optimizer: bool = False):
        """model.py load analog."""
        from ..framework.io import load as fw_load
        params_path = path + ".pdparams"
        if not os.path.exists(params_path) and os.path.exists(
                path + ".pdiparams"):
            params_path = path + ".pdiparams"  # jit.save inference layout
        params = fw_load(params_path)
        state = self.network.state_dict()
        if skip_mismatch:
            matched = {}
            for k, v in params.items():
                if k in state and tuple(state[k].shape) == tuple(
                        np.asarray(v._data if isinstance(v, Tensor) else v)
                        .shape):
                    matched[k] = v
                else:
                    warnings.warn(f"skip loading {k} (mismatch)")
            params = matched
        self.network.set_state_dict(params)
        opt_path = path + ".pdopt"
        if (not reset_optimizer and self._optimizer is not None
                and os.path.exists(opt_path)):
            self._optimizer.set_state_dict(fw_load(opt_path))

    def summary(self, input_size=None, dtype=None):
        from .model_summary import summary
        if input_size is None and self._inputs:
            input_size = [tuple(s.shape) for s in self._inputs]
        return summary(self.network, input_size, dtypes=dtype)


__all__ = ["Model"]
