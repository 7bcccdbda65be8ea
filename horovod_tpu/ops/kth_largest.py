"""The ``k``-th largest score of every row, as one Pallas kernel: what a
router's choice of ``k`` of ``E`` experts needs where only the *set*
chosen is read (``models/moe.py``, a held expert layer: a token's experts
are those whose score is above its ``k``-th, and the first of those equal
to it).

``jax.lax.top_k`` over ``[16384, 512]`` is a sort of every row on a TPU
(about 1.4 ms a call on a v5e at ``k`` = 22, PERF.md section 6, PR 37).
The ``k``-th largest alone takes ``k`` passes over a row that never leave
VMEM: a grid step takes ``rows`` tokens, turns its ``[rows, E]`` block so
that the tokens lie along the lanes (a maximum over the experts is then
elementwise over registers and one fold of eight sublanes, not a
reduction across lanes), and for 128 tokens at a time walks the distinct
scores downwards: pass ``i`` takes the largest score below the last
pass's and counts how many scores were not below it. The ``k``-th
largest, **repeats counted as ``top_k`` counts them**, is the first level
that ``k`` or more scores reach. Exact: comparisons and maxima alone, no
arithmetic on a score.

``kth_largest_plain`` is the reference and what runs off a TPU.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import _pallas

# Tokens a grid step takes where the caller names nothing (the largest
# multiple of LANES up to it that divides the rows), and tokens a walk
# holds at once: a register's lanes.
ROWS, LANES = 1024, 128


class _Plan(NamedTuple):
    """All the call is built from besides its operand's shape. Made
    outside the jitted call, so that what the process holds besides the
    operand (the backend) is part of its cache's key."""
    k: int
    rows: int
    interpret: bool


def _count_trace(plan, width):
    _pallas.count_trace(
        "hvt_moe_kth_kernel_traces_total",
        "k-th largest score kernels traced into compiled programs "
        "(counted per trace, not per execution)",
        k=plan.k, width=width, rows=plan.rows)


def serves(rows: int, width: int, k: int) -> bool:
    """Whether a ``[rows, width]`` operand goes to the kernel, from what
    can be observed (static trace-time facts, so the choice compiles
    away): a TPU backend, and rows and a width in whole 128-lane tiles,
    the block is turned in."""
    return (_pallas.on_tpu() and rows % LANES == 0 and width % LANES == 0
            and 0 < k <= width)


def _kernel(x_ref, o_ref, turned_ref, *, plan):
    width = x_ref.shape[-1]
    turned_ref[...] = x_ref[...].T          # [width, rows]: tokens on lanes

    def walk(c, _):
        at = pl.ds(pl.multiple_of(c * LANES, LANES), LANES)

        def level(_, carry):
            bar, kth = carry                            # [1, LANES]
            x = turned_ref[:, at]
            below = x < bar
            reached = width - jnp.sum(below.astype(jnp.int32), axis=0,
                                      keepdims=True)
            top = jnp.max(jnp.where(below, x, -jnp.inf), axis=0,
                          keepdims=True)
            # fewer than k scores reach the last level: go one down
            return top, jnp.where(reached < plan.k, top, kth)

        start = jnp.full((1, LANES), jnp.inf, jnp.float32)
        _, kth = jax.lax.fori_loop(0, plan.k, level, (start, start))
        o_ref[:, at] = kth
        return 0

    jax.lax.fori_loop(0, plan.rows // LANES, walk, 0)


@functools.partial(jax.jit, static_argnames="plan")
def _call(x, *, plan):
    """``x [n, width]`` float32 -> ``[n, 1]``. A ``jax.jit`` of its own,
    as the other kernels' calls are: the layers of a model share one trace
    and one lowered function."""
    _count_trace(plan, x.shape[-1])
    n, width = x.shape
    out = pl.pallas_call(
        functools.partial(_kernel, plan=plan),
        grid=(n // plan.rows,),
        in_specs=[pl.BlockSpec((plan.rows, width), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, plan.rows), lambda i: (0, i)),
        out_shape=_pallas.out((1, n), jnp.float32, x),
        scratch_shapes=[pltpu.VMEM((width, plan.rows), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=plan.interpret, name="hvt_moe_kth")(x)
    return out.reshape(n, 1)


def kth_largest_plain(x, k: int):
    """``x [n, width]`` -> ``[n, 1]``: each row's ``k``-th largest, repeats
    counted: the last of ``jax.lax.top_k``'s values."""
    return jax.lax.top_k(x, k)[0][:, -1:]


def kth_largest(x, k: int, *, rows: Optional[int] = None):
    """``kth_largest_plain`` through the kernel, for a float32 ``x`` whose
    rows and width are multiples of 128 when compiled (``serves``; the
    interpreter takes any multiple of ``rows``). No gradient: a caller
    hands it what it has stopped one at. ``rows`` names a grid step's
    tokens (a test's or a microbenchmark's; a model names none)."""
    n = x.shape[0]
    rows = rows or _pallas.largest(n, ROWS, LANES)
    if n % rows or rows % LANES:
        raise ValueError(f"blocks of {rows} rows do not tile {n} rows in "
                         f"walks of {LANES}")
    return _call(x.astype(jnp.float32),
                 plan=_Plan(int(k), rows, _pallas.interpret()))
