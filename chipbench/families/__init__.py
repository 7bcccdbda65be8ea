"""Model families. A configuration file names its family under
``"family"``; the harness imports ``chipbench.families.<family>`` and
calls ``build(config, traffic, rehearse)``, which returns a ``Job``.
That is the whole interface: a later PR that adds an architecture adds
one module here (with its plain reference under ``chipbench/reference/``)
and edits nothing.

A family uses the package as a user's training script would
(``horovod_tpu.models`` and the public ``hvt`` API), never ``bench.py``,
``chip_smoke.py`` or ``benchmarks/``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable


@dataclasses.dataclass
class Job:
    """One configuration under one traffic mix, ready for the harness.

    ``item``: what throughput counts ("tokens", "images").
    ``items_per_step_per_chip``: items one chip consumes in one step.
    ``flops_per_item``: required training FLOPs of one item, from
      ``chipbench/flops.py`` (recomputation not counted).
    ``init(key) -> (params, extra)``: pure and jittable; ``params`` is
      what the optimizer updates, ``extra`` is model state carried beside
      it (batch statistics; ``{}`` where there is none).
    ``make_batch(key, n_chips) -> batch``: pure and jittable; a pytree
      whose leaves lead with the global batch axis
      (``n_chips x per_chip_batch``), made from the key alone.
    ``loss(params, extra, batch) -> (loss, new_extra)``: the scalar the
      step differentiates, as a user would write it.
    ``optimizer()``: the optax transformation, before
      ``hvt.DistributedOptimizer`` wraps it.
    ``reference_loss(params, extra, batch) -> float``: the family's plain
      float32 reference of ``loss`` on the same tree and batch. The
      harness holds the loss the measured step itself returns to it,
      within ``loss_rel_bound`` (written in the family beside its reason).
    ``check(key) -> [Check]``: whatever else the family compares with its
      reference on the chip (gradients, on an instance the reference can
      hold). Runs after the window, once the optimizer state is freed.
    ``probe``: a small instance of the same code (same widths) on which a
      spelling can afford a second gradient program; may be the job
      itself.
    ``facts``: shapes the per-layer readers need (a plain dict).
    """

    item: str
    items_per_step_per_chip: int
    flops_per_item: float
    init: Callable[[Any], tuple]
    make_batch: Callable[[Any, int], Any]
    loss: Callable[[Any, Any, Any], tuple]
    optimizer: Callable[[], Any]
    reference_loss: Callable[[Any, Any, Any], float]
    loss_rel_bound: float
    check: Callable[[Any], list]
    probe: "Job | None" = None
    facts: dict = dataclasses.field(default_factory=dict)


def optimizer_from(spec: dict):
    """A configuration's ``optimizer`` entry as optax builds it:
    ``{"name": "adamw", "learning_rate": 1e-4}`` is
    ``optax.adamw(learning_rate=1e-4)``."""
    import optax

    return getattr(optax, spec["name"])(
        **{k: v for k, v in spec.items() if k != "name"})
