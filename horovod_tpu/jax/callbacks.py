"""Training-loop callbacks — the JAX-native counterpart of the
reference's Keras callback set (``horovod/_keras/callbacks.py``:
``BroadcastGlobalVariablesCallbackImpl:22``,
``MetricAverageCallbackImpl:48``, ``LearningRateScheduleCallbackImpl:89``,
``LearningRateWarmupCallbackImpl:172``).

JAX has no Model.fit; a training loop drives a ``CallbackList`` at the
standard hook points::

    cbs = hvt.jax.CallbackList([
        hvt.jax.BroadcastGlobalVariablesCallback(0),
        hvt.jax.MetricAverageCallback(),
        hvt.jax.LearningRateWarmupCallback(initial_lr=0.1 * hvt.size(),
                                           warmup_epochs=5,
                                           steps_per_epoch=100),
    ])
    state = cbs.on_train_begin(state)
    for epoch ...:
        cbs.on_epoch_begin(epoch)
        for batch ...:
            lr = cbs.learning_rate(step)     # or use the optax schedule
            ...
        metrics = cbs.on_epoch_end(epoch, metrics)

For purely functional loops the same warmup/schedule math is available as
optax schedules via ``warmup_schedule`` / ``exponential_schedule``.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import numpy as np


class Callback:
    def on_train_begin(self, state):
        return state

    def on_epoch_begin(self, epoch: int):
        pass

    def on_epoch_end(self, epoch: int, metrics: Optional[Dict] = None):
        return metrics

    def learning_rate(self, step: int) -> Optional[float]:
        return None


def _spanned(cb, hook, *args):
    """``cb.hook(*args)`` inside a host span named after the callback's
    class, on the profiler's clock (docs/timeline.md)."""
    with jax.profiler.TraceAnnotation(f"hvt_callback/{type(cb).__name__}"):
        return getattr(cb, hook)(*args)


class CallbackList(Callback):
    def __init__(self, callbacks: List[Callback]):
        self.callbacks = list(callbacks)

    def on_train_begin(self, state):
        for cb in self.callbacks:
            state = _spanned(cb, "on_train_begin", state)
        return state

    def on_epoch_begin(self, epoch):
        for cb in self.callbacks:
            _spanned(cb, "on_epoch_begin", epoch)

    def on_epoch_end(self, epoch, metrics=None):
        for cb in self.callbacks:
            metrics = _spanned(cb, "on_epoch_end", epoch, metrics)
        return metrics

    def learning_rate(self, step):
        lr = None
        for cb in self.callbacks:
            v = _spanned(cb, "learning_rate", step)
            lr = v if v is not None else lr
        return lr


class BroadcastGlobalVariablesCallback(Callback):
    """Broadcast the initial state pytree from ``root_rank`` at train
    start so all workers begin identical (reference
    ``BroadcastGlobalVariablesCallbackImpl:22``)."""

    def __init__(self, root_rank: int = 0):
        self.root_rank = root_rank

    def on_train_begin(self, state):
        from horovod_tpu.ops.functions import broadcast_parameters

        return broadcast_parameters(state, root_rank=self.root_rank)


class MetricAverageCallback(Callback):
    """Average epoch metrics across workers (reference
    ``MetricAverageCallbackImpl:48``). Metrics dict values may be floats
    or 0-d arrays."""

    def on_epoch_end(self, epoch, metrics=None):
        if not metrics:
            return metrics
        import horovod_tpu as hvt

        keys = sorted(metrics)
        vals = np.asarray([float(metrics[k]) for k in keys], np.float64)
        avg = np.asarray(hvt.allreduce(vals, name=f"metric_avg_e{epoch}",
                                       average=True))
        out = dict(metrics)
        out.update({k: float(v) for k, v in zip(keys, avg)})
        return out


class MetricsCallback(Callback):
    """Fold training-loop metrics into the ``horovod_tpu.metrics``
    registry so they ride the same scrape/snapshot plane as the engine
    counters.

    Every value in the epoch-end metrics dict becomes a sample of the
    ``hvt_train_metric`` gauge (labeled by metric name); epochs are
    counted in ``hvt_train_epochs_total``. Pair with
    :class:`MetricAverageCallback` (ordered before this one) to publish
    the cross-worker average instead of the local value. A Keras adapter
    is exported as ``horovod_tpu.keras.MetricsCallback``."""

    def __init__(self, registry=None, prefix: str = "hvt_train"):
        from horovod_tpu import metrics as _metrics

        reg = registry if registry is not None else _metrics.registry()
        self._gauge = reg.gauge(
            f"{prefix}_metric", "training metrics by name (last epoch)",
            ("metric",))
        self._epochs = reg.counter(f"{prefix}_epochs_total",
                                   "training epochs completed")

    def on_epoch_end(self, epoch, metrics=None):
        self._epochs.inc()
        for k, v in (metrics or {}).items():
            try:
                self._gauge.labels(metric=str(k)).set(float(v))
            except (TypeError, ValueError):
                continue  # non-numeric entries (e.g. strings) are skipped
        return metrics


class LearningRateScheduleCallback(Callback):
    """Piecewise/exponential LR schedule (reference
    ``LearningRateScheduleCallbackImpl:89``): from ``start_epoch`` until
    ``end_epoch``, lr = initial_lr * multiplier(epoch); ``staircase``
    holds the multiplier constant within an epoch, otherwise the epoch is
    fractional per step."""

    def __init__(self, initial_lr: float, multiplier, start_epoch: int = 0,
                 end_epoch: Optional[int] = None, staircase: bool = True,
                 steps_per_epoch: Optional[int] = None):
        self.initial_lr = initial_lr
        self.start_epoch = start_epoch
        self.end_epoch = end_epoch
        self.staircase = staircase
        self.steps_per_epoch = steps_per_epoch
        self.current_epoch = 0
        if callable(multiplier):
            self.multiplier = multiplier
        else:
            self.multiplier = lambda epoch: multiplier

    def on_epoch_begin(self, epoch):
        self.current_epoch = epoch

    def _in_range(self, epoch) -> bool:
        if epoch < self.start_epoch:
            return False
        return self.end_epoch is None or epoch < self.end_epoch

    def learning_rate(self, step):
        if self.staircase or not self.steps_per_epoch:
            epoch = self.current_epoch
        else:
            epoch = step / self.steps_per_epoch
        if not self._in_range(epoch):
            return None
        return self.initial_lr * self.multiplier(epoch)


class LearningRateWarmupCallback(LearningRateScheduleCallback):
    """Gradual warmup from lr/size to the scaled lr over the first
    epochs — "Accurate Large Minibatch SGD" style, reference
    ``LearningRateWarmupCallbackImpl:172``: multiplier =
    1/size * (epoch * (size - 1) / warmup_epochs + 1)."""

    def __init__(self, initial_lr: float, warmup_epochs: int = 5,
                 steps_per_epoch: Optional[int] = None, verbose: bool = False,
                 size: Optional[int] = None):
        import horovod_tpu as hvt

        self.size = size if size is not None else hvt.size()
        self.warmup_epochs = warmup_epochs
        self.verbose = verbose

        def multiplier(epoch):
            if self.size <= 1 or self.warmup_epochs == 0:
                return 1.0
            return 1.0 / self.size * (
                epoch * (self.size - 1) / self.warmup_epochs + 1)

        super().__init__(initial_lr=initial_lr, multiplier=multiplier,
                         start_epoch=0, end_epoch=warmup_epochs,
                         staircase=False, steps_per_epoch=steps_per_epoch)

    def learning_rate(self, step):
        lr = super().learning_rate(step)
        # after the warmup window, hold the target lr (the reference
        # leaves the optimizer at the scaled lr) instead of returning
        # None and leaving the loop without a value
        return lr if lr is not None else self.initial_lr

    def on_epoch_end(self, epoch, metrics=None):
        if self.verbose and epoch == self.end_epoch - 1:
            print(f"LearningRateWarmup: reached target lr "
                  f"{self.initial_lr:.6g} after {self.warmup_epochs} "
                  f"epochs")
        return metrics


def warmup_schedule(initial_lr: float, warmup_steps: int,
                    size: Optional[int] = None):
    """optax-compatible schedule: linear warmup from initial_lr/size to
    initial_lr over warmup_steps, then constant."""
    import horovod_tpu as hvt

    n = size if size is not None else hvt.size()

    def schedule(step):
        import jax.numpy as jnp

        frac = jnp.minimum(step / max(warmup_steps, 1), 1.0)
        start = initial_lr / n
        return start + (initial_lr - start) * frac

    return schedule


def exponential_schedule(initial_lr: float, decay: float,
                         steps_per_epoch: int, staircase: bool = True):
    """optax-compatible schedule matching LearningRateScheduleCallback
    with multiplier = decay**epoch."""

    def schedule(step):
        import jax.numpy as jnp

        epoch = step / steps_per_epoch
        if staircase:
            epoch = jnp.floor(epoch)
        return initial_lr * jnp.power(decay, epoch)

    return schedule
