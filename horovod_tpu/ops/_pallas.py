"""What the Pallas kernel families under ``ops/`` share, and nothing that
only one of them uses.

A family is one module: its kernels, the plain ``jax.numpy`` body that is
their reference and the path off a TPU, and ``serves``, the rule that
chooses between the two from what can be observed. ``models/`` imports the
families, the families import this, and nothing points the other way.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NT = (((1,), (1,)), ((), ()))   # [m, d] x [n, d] -> [m, n]
NN = (((1,), (0,)), ((), ()))   # [m, n] x [n, d] -> [m, d]
TN = (((0,), (0,)), ((), ()))   # [n, m] x [n, d] -> [m, d]


def on_tpu() -> bool:
    """What every ``serves`` rule starts from: off a TPU a kernel is
    interpreted, far slower than its plain body."""
    return jax.default_backend() == "tpu"


def interpret() -> bool:
    """Only the CPU interprets: it has no Mosaic compiler, and the CPU
    test mesh runs the same kernel code. On any other backend the kernel
    is compiled and a compiler's refusal propagates. A family reads this
    where it makes its plan, outside its jitted calls, so that the backend
    is part of their cache's key and never read under a cached trace."""
    return jax.default_backend() == "cpu"


def out(shape, dtype, *operands):
    """A kernel's output: under ``jax.shard_map`` it varies over every
    mesh axis an operand varies over (``check_vma``, the default there,
    refuses an output that does not say)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def dot(a, b, dims):
    """``a`` by ``b`` over ``dims`` (``NT``, ``NN``, ``TN``), accumulated
    in float32."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def largest(total, most, step):
    """The largest multiple of ``step`` up to ``most`` that divides
    ``total``; ``total`` itself where there is none."""
    return next((n for n in range(min(most, total) // step * step, 0, -step)
                 if total % n == 0), total)


def count_trace(name, help, **labels):
    """An engagement counter: one count of ``name`` at ``labels`` (their
    values as strings) for what a trace put into a compiled program.
    Trace-time Python only, counted per trace and not per execution."""
    try:
        from horovod_tpu import metrics

        metrics.counter(name, help, tuple(labels)).labels(
            **{k: str(v) for k, v in labels.items()}).inc()
    except Exception:
        pass  # telemetry must never break a trace
