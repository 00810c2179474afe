"""paddle.Model facade (reference: python/paddle/hapi/model.py).

fit/evaluate/predict over a Layer + optimizer + loss, with callbacks. The
inner loop uses the jitted TrainStep when the model's forward is jit-safe
(static shapes), falling back to eager otherwise.

``fit`` is async-by-default: steps are DISPATCHED without pulling the
loss (the TRAIN_AB_r05 on-chip A/B showed the same step at MFU 0.4627
pipelined vs 0.2772 with a per-step host sync), metrics are host-pulled
every ``metrics_every`` steps (stale-by-k, near-zero wait because the
pulled loss was dispatched k steps earlier), input batches are staged
host->device one step ahead (double buffering), and the only hard
barriers are epoch ends — where checkpoint / early-stop / eval decisions
need exact state.
"""

from __future__ import annotations

import os
import time
import warnings
from typing import List, Optional

import numpy as np

from ..core.tensor import Tensor
from ..metric import Metric
from ..nn.layer import Layer
from ..testing.faults import InjectedFault
from . import callbacks as cb_mod
from .train_step import TrainStep


def _fit_recovery_metrics():
    """Lazily-bound fit-recovery counters on the r09 registry. Resolved
    per fit-recovery event — a cold path by definition."""
    from .. import observability as obs
    r = obs.registry()
    return {
        "retries": r.counter(
            "train_retries_total",
            "fit step-recovery attempts (sync to last-good state, "
            "emergency checkpoint, backoff, re-dispatch)"),
        "recoveries": r.counter(
            "train_recoveries",
            "fit step recoveries that resumed training"),
        "ckpts": r.counter(
            "train_emergency_checkpoints",
            "emergency checkpoints written by fit recovery / nan_policy"),
        "nans": r.counter(
            "train_nan_losses",
            "non-finite losses seen by the fit NaN/inf policy"),
    }


class Model:
    def __init__(self, network: Layer, inputs=None, labels=None):
        self.network = network
        self._loss = None
        self._optimizer = None
        self._metrics: List[Metric] = []
        self._train_step: Optional[TrainStep] = None
        self.stop_training = False

    def prepare(self, optimizer=None, loss=None, metrics=None, amp_configs=None):
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = list(metrics) if metrics else []
        if self._train_step is not None:
            # a rebuilt recipe invalidates the compiled step; pull the
            # trained params back into the Layer first
            self._train_step.sync_to_model()
            self._train_step = None

    # ----------------------------------------------------------------- train
    def _loss_value(self, outputs, labels):
        if isinstance(self._loss, Layer):
            return self._loss(outputs, labels)
        return self._loss(outputs, labels)

    def train_batch(self, inputs, labels=None, update=True):
        if self._train_step is not None:
            # eager training updates the Layer's tensors; a retained
            # jitted step would later sync its (now stale) device params
            # back over them in save() — pull once and drop it
            self._train_step.sync_to_model()
            self._train_step = None
        self.network.train()
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        outputs = self.network(*inputs)
        loss = self._loss_value(outputs, labels[0] if isinstance(labels, (list, tuple)) else labels)
        loss.backward()
        if update:
            self._optimizer.step()
            self._optimizer.clear_grad()
        return [float(loss)]

    def eval_batch(self, inputs, labels=None):
        self.network.eval()
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        outputs = self.network(*inputs)
        loss = self._loss_value(outputs, labels[0] if isinstance(labels, (list, tuple)) else labels)
        metrics = [float(loss)]
        for m in self._metrics:
            res = m.compute(outputs, labels[0] if isinstance(labels, (list, tuple)) else labels)
            m.update(res)
        return metrics

    def predict_batch(self, inputs):
        self.network.eval()
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        from ..core.autograd import no_grad_guard
        with no_grad_guard():
            out = self.network(*inputs)
        return out

    def _ensure_train_step(self, metrics_every, accumulate_grad_batches=1):
        """Build (or reuse) the jitted TrainStep for fit's inner loop.
        Returns None when the recipe can't be jitted (no loss/optimizer,
        or construction fails) — fit then runs the eager loop."""
        accum = max(1, int(accumulate_grad_batches or 1))
        if self._train_step is not None and \
                self._train_step.grad_accum_steps != accum:
            # a changed accumulation recipe invalidates the compiled step
            self._train_step.sync_to_model()
            self._train_step = None
        if self._train_step is not None:
            self._train_step.metrics_every = max(0, int(metrics_every))
            return self._train_step
        if self._optimizer is None or self._loss is None:
            return None
        try:
            self._train_step = TrainStep(
                self.network, self._optimizer, loss_fn=self._loss,
                grad_accum_steps=accum, metrics_every=metrics_every)
        except Exception as e:
            # eager still trains, but at the per-step-sync throughput the
            # async loop exists to avoid — never degrade silently
            import warnings
            warnings.warn(
                f"Model.fit: could not build the jitted TrainStep "
                f"({e!r}); falling back to the eager per-step loop "
                f"(slower). Pass jit=False to silence.")
            self._train_step = None
        return self._train_step

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None, metrics_every=None,
            jit=None, prefetch_to_device=True, use_process_workers=False,
            nan_policy="raise"):
        """Train. Async by default: the jitted TrainStep dispatches ahead
        of the device and the loss shown to callbacks is stale-by-k
        (``metrics_every``, default ``log_freq``); hard device syncs
        happen only every k steps (a near-free pull of an already-computed
        loss) and at epoch ends, where checkpoint/early-stop/eval read
        exact state. ``jit=False`` forces the eager per-step loop;
        ``metrics_every=1`` keeps the jitted loop but syncs every step.
        ``prefetch_to_device`` stages batch N+1 host->device while step N
        runs (double buffering). ``use_process_workers`` moves the
        ``num_workers`` loader workers into OS processes (shared-memory
        batch transport) for GIL-bound ``__getitem__`` transforms.

        Fault tolerance: a step that fails mid-flight (after at least
        one good step) is recovered — the async window drains to the
        last-good state, an emergency checkpoint is written under
        ``save_dir`` and the batch is re-dispatched with exponential
        backoff, ``FLAGS_train_max_retries`` times — before the original
        exception propagates. ``nan_policy`` decides what a non-finite
        loss does: ``'raise'`` (default) raises ``FloatingPointError``,
        ``'skip'`` counts it and keeps training, ``'stop'`` writes the
        emergency checkpoint and ends training cleanly."""
        from ..io import Dataset, DataLoader, DevicePrefetcher

        if nan_policy not in ("raise", "skip", "stop"):
            raise ValueError(
                f"nan_policy must be 'raise', 'skip' or 'stop'; got "
                f"{nan_policy!r}")

        if isinstance(train_data, Dataset):
            train_loader = DataLoader(train_data, batch_size=batch_size,
                                      shuffle=shuffle, drop_last=drop_last,
                                      num_workers=num_workers,
                                      use_process_workers=use_process_workers)
        else:
            train_loader = train_data

        # metrics_every=0 is meaningful (never pull; epoch-end sync only)
        # — only None defaults to the ProgBar cadence
        metrics_every = (int(metrics_every) if metrics_every is not None
                         else max(1, log_freq))
        step_obj = None
        if jit is not False:
            step_obj = self._ensure_train_step(metrics_every,
                                               accumulate_grad_batches)

        cbks = cb_mod.config_callbacks(
            callbacks, model=self, epochs=epochs, verbose=verbose,
            log_freq=log_freq, save_dir=save_dir, save_freq=save_freq,
            metrics=["loss"] + [m.name() for m in self._metrics])
        cbks.on_begin("train")
        it = 0
        for epoch in range(epochs):
            cbks.on_epoch_begin(epoch)
            logs = {}
            iterator = iter(train_loader)
            if step_obj is not None and prefetch_to_device:
                iterator = iter(DevicePrefetcher(iterator, self._stage_batch))
            # callbacks count steps per epoch; the TrainStep counts
            # globally — the base translates its loss_step/staleness tags
            epoch_base = step_obj._step_count if step_obj is not None else 0
            for step, batch in enumerate(iterator):
                cbks.on_batch_begin("train", step, {})
                if step_obj is not None:
                    n0 = step_obj._step_count
                    try:
                        logs = self._async_batch(step_obj, batch, step,
                                                 epoch_base)
                    except Exception as exc:
                        # Failures AFTER any successful jitted step (or
                        # an injected fault at any point) go through
                        # step recovery: drain to last-good state,
                        # emergency checkpoint, bounded backoff retry.
                        # A step-0 trace error instead falls back to the
                        # eager loop — the forward isn't jit-safe and no
                        # device progress exists to protect yet.
                        if step > 0 or step_obj._step_count > 0 or \
                                isinstance(exc, InjectedFault):
                            logs = self._recover_batch(
                                step_obj, batch, step, epoch_base,
                                save_dir, exc,
                                dispatched=step_obj._step_count > n0)
                        else:
                            from .train_step import StagedBatch
                            raw = (batch.raw
                                   if isinstance(batch, StagedBatch)
                                   else batch)
                            if raw is None:
                                raise
                            import sys
                            import traceback
                            traceback.print_exc(file=sys.stderr)
                            warnings.warn(
                                "Model.fit: first jitted step failed "
                                "(trace above); falling back to the "
                                "eager per-step loop (slower). Pass "
                                "jit=False to silence.")
                            step_obj = self._train_step = None
                            logs = self._eager_batch(raw, step)
                else:
                    logs = self._eager_batch(batch, step)
                loss_val = (logs.get("loss")
                            if isinstance(logs, dict) else None)
                if loss_val is not None and not np.isfinite(loss_val):
                    self._handle_nan(nan_policy, save_dir,
                                     float(loss_val),
                                     where=f"epoch {epoch} step {step}")
                cbks.on_batch_end("train", step, logs)
                it += 1
                if self.stop_training:
                    break               # nan_policy='stop' mid-epoch
                if num_iters is not None and it >= num_iters:
                    break
            done = self.stop_training or (num_iters is not None
                                          and it >= num_iters)
            want_eval = (eval_data is not None
                         and (epoch + 1) % eval_freq == 0)
            if step_obj is not None:
                # the ONE hard barrier of the epoch: exact loss for
                # EarlyStopping/checkpoint decisions (the epoch_sync span
                # nests the TrainStep's own train.sync span)
                from ..observability import span as _span
                logs = dict(logs)
                with _span("fit.epoch_sync", epoch=epoch):
                    logs["loss"] = self._sync_with_retry(step_obj)
                if logs["loss"] is not None and \
                        not np.isfinite(logs["loss"]):
                    self._handle_nan(nan_policy, save_dir,
                                     float(logs["loss"]),
                                     where=f"epoch {epoch} sync")
                m = step_obj.last_metrics
                if m is not None and m["loss_step"] >= epoch_base:
                    # retag: the barrier loss is exact — stale tags from
                    # the last mid-epoch pull must not survive on it
                    logs["loss_step"] = m["loss_step"] - epoch_base
                    logs["staleness"] = m["staleness"]
                if want_eval:
                    # eval reads the Layer's tensors — pull the on-device
                    # params back only when something needs them
                    # (ModelCheckpoint goes through Model.save, which
                    # syncs on its own cadence; the post-loop sync covers
                    # fit's end however the loop exits)
                    step_obj.sync_to_model()
            cbks.on_epoch_end(epoch, logs)
            if want_eval:
                self.evaluate(eval_data, batch_size=batch_size, verbose=0)
            if done or self.stop_training:
                break
        if step_obj is not None:
            step_obj.sync_to_model()
        cbks.on_end("train")

    # ------------------------------------------------------ fault tolerance
    def _sync_with_retry(self, step_obj):
        """Epoch-boundary sync with bounded retry of INJECTED sync
        faults only (host-side by construction — the window is intact);
        a real device failure propagates untouched."""
        from .. import flags
        max_retries = int(flags.get_flag("train_max_retries"))
        backoff = float(flags.get_flag("train_retry_backoff"))
        for attempt in range(max_retries + 1):
            try:
                return step_obj.sync()
            except InjectedFault:
                if attempt == max_retries:
                    raise
                time.sleep(min(backoff * (2 ** attempt), 2.0))

    def _recover_batch(self, step_obj, batch, step, epoch_base, save_dir,
                       exc, dispatched):
        """Step recovery: the dispatch (or its metrics pull) raised.
        Sync the async window to the last-good state — a dispatch-time
        failure never consumed its donated buffers, so every previously
        dispatched step retires cleanly — write an emergency checkpoint
        under ``save_dir``, back off, and re-dispatch the same batch.
        ``dispatched``: the failed call got PAST its dispatch (the
        raise came from the metrics pull), so the update is already
        applied and re-dispatching would train the batch twice — resume
        from the sync instead. Raises the last failure once
        ``FLAGS_train_max_retries`` is exhausted."""
        from .. import flags
        max_retries = int(flags.get_flag("train_max_retries"))
        backoff = float(flags.get_flag("train_retry_backoff"))
        m = _fit_recovery_metrics()
        warnings.warn(
            f"Model.fit: step {step} failed ({exc!r}); attempting "
            f"recovery (sync to last-good state + emergency checkpoint, "
            f"<= {max_retries} retries)")
        last = exc
        for attempt in range(1, max_retries + 1):
            m["retries"].inc()
            try:
                step_obj.sync()
            except InjectedFault as e:
                last = e
                time.sleep(min(backoff * (2 ** (attempt - 1)), 2.0))
                continue
            except Exception as e:
                # a step already in flight failed ON DEVICE: its donated
                # params are gone and nothing host-side can replay them
                raise RuntimeError(
                    "Model.fit recovery: draining the in-flight window "
                    "failed — a dispatched step died on device and its "
                    "donated state is unrecoverable; restart from the "
                    "last checkpoint") from e
            self._emergency_checkpoint(save_dir, m)
            if dispatched:
                # the update applied before the raise; resuming from the
                # sync is the exactly-once behavior
                m["recoveries"].inc()
                return {"step": step, "loss": step_obj._last_loss}
            time.sleep(min(backoff * (2 ** (attempt - 1)), 2.0))
            try:
                logs = self._async_batch(step_obj, batch, step,
                                         epoch_base)
                m["recoveries"].inc()
                return logs
            except Exception as e:
                last = e
        raise last

    def _emergency_checkpoint(self, save_dir, m=None):
        """Best-effort pre-retry checkpoint (``<save_dir>/emergency``):
        the state every successfully dispatched step produced, saved
        before anything is re-dispatched. Its own save path is retried
        (checkpoint_save is an injection site too); total failure warns
        and recovery proceeds — a missing checkpoint must not turn a
        recoverable step failure into a fatal one."""
        if save_dir is None:
            return None
        path = os.path.join(save_dir, "emergency")
        os.makedirs(save_dir, exist_ok=True)
        err = None
        for attempt in range(3):
            try:
                self.save(path)
                m["ckpts"].inc()
                return path
            except Exception as e:
                err = e
                time.sleep(0.02 * (2 ** attempt))
        warnings.warn(
            f"Model.fit: emergency checkpoint failed 3 times ({err!r}); "
            f"continuing recovery without it")
        return None

    def _handle_nan(self, policy, save_dir, loss, where):
        """Apply the fit ``nan_policy`` to one non-finite loss."""
        m = _fit_recovery_metrics()
        m["nans"].inc()
        if policy == "raise":
            raise FloatingPointError(
                f"Model.fit: non-finite loss {loss} at {where} "
                f"(nan_policy='raise'; use 'skip' or 'stop' to "
                f"tolerate)")
        if policy == "stop":
            warnings.warn(
                f"Model.fit: non-finite loss {loss} at {where}; "
                f"nan_policy='stop' — emergency checkpoint + clean stop")
            self._emergency_checkpoint(save_dir, m)
            self.stop_training = True
        else:
            warnings.warn(
                f"Model.fit: non-finite loss {loss} at {where}; "
                f"nan_policy='skip' — continuing")

    def _stage_batch(self, batch):
        """Split a loader batch into (inputs..., labels) and stage it on
        device with the TrainStep's data sharding (async). Batches the
        jitted loop can't consume pass through unchanged (the loop then
        falls back to eager)."""
        ts = self._train_step
        if ts is not None and isinstance(batch, (list, tuple)) \
                and len(batch) >= 2:
            staged = ts.stage(*batch)
            staged.raw = batch
            return staged
        return batch

    def _async_batch(self, step_obj, batch, step, epoch_base=0):
        """Dispatch one jitted step; never blocks on the loss. Returns
        callback logs: a fresh (stale-by-k) loss every metrics_every
        steps, None in between. ``loss_step`` is reported in the
        callback's per-epoch step numbering (``epoch_base`` = the
        TrainStep's global count at epoch start), and a pull that found
        nothing from THIS epoch (the window was just drained by the
        epoch-end sync) attaches nothing rather than re-labelling the
        previous epoch's loss."""
        from .train_step import StagedBatch
        if not isinstance(batch, StagedBatch):
            if not (isinstance(batch, (list, tuple)) and len(batch) >= 2):
                raise NotImplementedError(
                    "the jitted fit loop needs (inputs..., labels) batches")
            batch = self._stage_batch(batch)
        step_obj(batch)
        logs = {"step": step, "loss": None}
        m = step_obj.last_metrics
        if (m is not None and step_obj.metrics_every
                and step_obj._step_count % step_obj.metrics_every == 0
                and m["loss_step"] >= epoch_base):
            logs.update(loss=m["loss"], loss_step=m["loss_step"] - epoch_base,
                        staleness=m["staleness"])
        return logs

    def _eager_batch(self, batch, step):
        from .train_step import StagedBatch
        if isinstance(batch, StagedBatch):
            # a prefetcher can hold batches staged BEFORE an eager
            # fallback dropped the jitted step; replay their raw form
            if batch.raw is None:
                raise NotImplementedError(
                    "eager loop got a StagedBatch without its raw batch")
            batch = batch.raw
        if isinstance(batch, (list, tuple)) and len(batch) >= 2:
            *xs, y = batch
        else:
            xs, y = [batch], None
        return {"loss": self.train_batch(xs, y)[0], "step": step}

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_samples=None):
        from ..io import DataLoader, Dataset

        loader = (DataLoader(eval_data, batch_size=batch_size, num_workers=num_workers)
                  if isinstance(eval_data, Dataset) else eval_data)
        for m in self._metrics:
            m.reset()
        losses = []
        for batch in loader:
            if isinstance(batch, (list, tuple)) and len(batch) >= 2:
                *xs, y = batch
            else:
                xs, y = [batch], None
            losses.append(self.eval_batch(xs, y)[0])
        out = {"loss": float(np.mean(losses)) if losses else None}
        for m in self._metrics:
            out[m.name() if isinstance(m.name(), str) else m.name()[0]] = m.accumulate()
        return out

    def predict(self, test_data, batch_size=1, num_workers=0, stack_outputs=False,
                verbose=1, callbacks=None):
        from ..io import DataLoader, Dataset

        loader = (DataLoader(test_data, batch_size=batch_size, num_workers=num_workers)
                  if isinstance(test_data, Dataset) else test_data)
        outs = []
        for batch in loader:
            xs = batch if isinstance(batch, (list, tuple)) else [batch]
            outs.append(self.predict_batch(xs))
        return outs

    # ------------------------------------------------------------ state mgmt
    def save(self, path, training=True):
        from ..framework.io import save

        if self._train_step is not None:
            # fit's params live on device inside the TrainStep; the
            # Layer's tensors are stale (donated) until synced back
            self._train_step.sync_to_model()
        save(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            save(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework.io import load
        import os

        self.network.set_state_dict(load(path + ".pdparams"))
        if not reset_optimizer and self._optimizer is not None and os.path.exists(path + ".pdopt"):
            self._optimizer.set_state_dict(load(path + ".pdopt"))

    def parameters(self, *args, **kwargs):
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None):
        total = sum(p.size for p in self.network.parameters())
        trainable = sum(p.size for p in self.network.parameters() if not p.stop_gradient)
        print(f"Total params: {total:,}\nTrainable params: {trainable:,}")
        return {"total_params": total, "trainable_params": trainable}
