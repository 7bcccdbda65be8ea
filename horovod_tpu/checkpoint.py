"""Checkpoint / resume (SURVEY.md §5.4).

The reference has no file-checkpoint subsystem of its own — its layers are
(a) broadcast of variables/optimizer state at start so rank-0 restores
propagate (``tensorflow/functions.py`` broadcast_variables,
``torch/functions.py`` broadcast_optimizer_state), (b) elastic
``State.commit()`` in-memory snapshots (``common/elastic.py:60-71``), and
(c) Spark estimator stores. This module adds the TPU-native file layer on
top: orbax async checkpointing (non-blocking save off the training
thread), with the reference's broadcast-on-restore semantics preserved —
restore happens once and is broadcast from ``root_rank`` so every worker
resumes identically.

Usage::

    mgr = hvt.checkpoint.CheckpointManager("/ckpts", max_to_keep=3)
    mgr.save(step, {"params": params, "opt_state": opt_state})
    state = mgr.restore_latest(
        template={"params": params, "opt_state": opt_state})
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax


def _orbax():
    try:
        import orbax.checkpoint as ocp

        return ocp
    except ImportError as e:
        raise ImportError(
            "checkpointing requires orbax-checkpoint "
            "(pip install orbax-checkpoint)") from e


class CheckpointManager:
    """Thin orbax CheckpointManager wrapper with broadcast-on-restore.

    - ``save`` is asynchronous by default (orbax writes in a background
      thread; the train loop is only blocked for the on-device →
      host copy).
    - ``restore_latest``/``restore`` return the state broadcast from
      ``root_rank`` when the eager engine is up with size > 1, so a
      restore from shared storage — or from rank 0's local disk — yields
      identical state everywhere (the reference's broadcast-on-restore
      layering).
    """

    def __init__(self, directory: str, max_to_keep: int = 5,
                 save_interval_steps: int = 1, async_save: bool = True):
        ocp = _orbax()
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            save_interval_steps=save_interval_steps,
            enable_async_checkpointing=async_save)
        self._mgr = ocp.CheckpointManager(self.directory, options=options)

    def save(self, step: int, state: Any, force: bool = False) -> bool:
        """Queue an async save of the state pytree at ``step``."""
        ocp = _orbax()
        with jax.profiler.TraceAnnotation("hvt_checkpoint/save"):
            return self._mgr.save(step, args=ocp.args.StandardSave(state),
                                  force=force)

    def wait(self):
        """Block until queued async saves are durable."""
        with jax.profiler.TraceAnnotation("hvt_checkpoint/wait"):
            self._mgr.wait_until_finished()

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def all_steps(self):
        return list(self._mgr.all_steps())

    def restore(self, step: int, template: Any = None,
                broadcast: bool = True, root_rank: int = 0) -> Any:
        ocp = _orbax()
        args = ocp.args.StandardRestore(template) if template is not None \
            else ocp.args.StandardRestore()
        with jax.profiler.TraceAnnotation("hvt_checkpoint/restore"):
            state = self._mgr.restore(step, args=args)
            if broadcast:
                state = _broadcast_if_distributed(state, root_rank)
        return state

    def restore_latest(self, template: Any = None, broadcast: bool = True,
                       root_rank: int = 0) -> Optional[Any]:
        step = self.latest_step()
        if step is None:
            return None
        return self.restore(step, template=template, broadcast=broadcast,
                            root_rank=root_rank)

    def close(self):
        self._mgr.close()


def _broadcast_if_distributed(state: Any, root_rank: int) -> Any:
    import horovod_tpu as hvt

    # standalone restore (inference, pre-init tooling) is a no-op; the
    # broadcast only applies inside an initialized multi-process job
    if not hvt.is_initialized() or hvt.size() <= 1:
        return state
    from horovod_tpu.ops.functions import broadcast_parameters

    return broadcast_parameters(state, root_rank=root_rank)


def save(path: str, state: Any):
    """One-shot synchronous save (no manager bookkeeping)."""
    ocp = _orbax()
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.abspath(path), state, force=True)
    ckptr.wait_until_finished()
    ckptr.close()


def restore(path: str, template: Any = None, broadcast: bool = True,
            root_rank: int = 0) -> Any:
    """One-shot restore + broadcast."""
    ocp = _orbax()
    ckptr = ocp.StandardCheckpointer()
    state = ckptr.restore(os.path.abspath(path), template)
    ckptr.close()
    if broadcast:
        state = _broadcast_if_distributed(state, root_rank)
    return state
