"""The causal depthwise convolution of the mixers (a few taps down the
sequence, a bias, ``silu``): its plain ``jax.numpy`` body, two Pallas TPU
kernels under one custom VJP, and the rule that chooses between them.

``causal_conv`` is what a mixer calls; ``serves`` sends it to the kernels
(``causal_conv_kernels``) or to ``causal_conv_plain``, which is also the
kernels' reference and, with ``activation=None``, the bare taps of
``models/sconv.py``.

In plain ``jax.numpy`` the operand is cast to float32 and padded, the taps
are slices of that copy at rows 0, 1, 2, ... of the sequence axis (the
tile's sublane axis, so all but one are misaligned reads that XLA does not
keep in one fusion) and automatic differentiation keeps float32 residuals
of the operand's size. In the kernels nothing float32 of size ``[b, s, c]``
exists outside VMEM:

- ``hvt_causal_conv_fwd`` reads a block of ``x`` in ``x.dtype`` and the
  tile of rows before it, walks the block a few sublane tiles at a time
  with everything in registers (float32; a tap is a sublane rotation of
  the rows and the tile before them), and writes ``silu`` of the sum in
  ``x.dtype``.
- ``hvt_causal_conv_bwd`` reads ``x`` and the output's gradient, makes
  the pre-activation again, ``dpre = g silu'(pre)``, writes ``dx`` (whose
  taps reach *forward*: the tile after the block is its halo) in
  ``x.dtype`` and a block's float32 partial sums of ``dweight`` and
  ``dbias``, which XLA adds up (``taps + 1`` rows a block).

The residuals are the operands. Channels are lanes and positions
sublanes; a grid step takes ``rows x lanes`` of one sequence, and every
step is independent of every other (the halo is a second block spec over
the same array, not a carry).

On the CPU the same kernel code runs through the Pallas interpreter, at
any width; compiled, Mosaic wants lanes in multiples of 128 and rows in
multiples of the bf16 tile's 16 (``serves`` sends it nothing else).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import _pallas

_F32 = jnp.float32
# The rows of a halo block (a bf16 tile's, so that it is a whole tile in
# either dtype) and those of it a tap can reach: a float32 tile's.
HALO, EDGE = 16, 8
# What a grid step takes where the caller names nothing: the longest
# block of positions up to ROWS that divides the sequence, the widest of
# channels up to LANES that divides them in whole 128-lane tiles, and SUB
# positions a pass of the loop inside. On a v5e at 2 x 8192 x 8192 bf16 a
# forward and a backward call took 0.91 and 1.97 ms at these, 0.99 and
# 2.18 in passes of 16 rows and 1.33 and 2.70 in passes of 8, 0.88 and
# 2.41 at 1024 lanes, 1.10 and 1.94 at 256; blocks of 512 and 2048 rows
# within 0.1 ms of 1024 (benchmarks/causal_conv.py; PERF.md section 6,
# PR 36).
ROWS, LANES, SUB = 1024, 512, 32
# The shortest block ``serves`` sends to the kernels.
ROWS_MIN = 128
# The narrowest convolution that goes to the kernels. A measured boundary,
# not the kernels': alone they beat the plain body at a Mamba-2 mixer's
# 1280 channels too (0.48 against 2.51 ms forward and backward), but
# inside nemotron3s-s8192's step XLA then copies the slices of a narrow
# operand and of a result it can no longer write in the layout the scan's
# heads of 64 want (``ssm_conv`` 10.1 -> 5.2 ms a step, its neighbours
# +8.8: the step 3.0 ms longer), where at a Gated DeltaNet's 8192 in
# qwen3next-s8192 the step is 48.9 ms shorter. Nothing between the two was
# measured (PERF.md section 6, PR 36).
KERNELS_FROM = 2048


def serves(seq_len: int, channels: int, taps: int) -> bool:
    """Whether the convolution goes to the kernels, from what can be
    observed (static trace-time facts, so the choice compiles away): a TPU
    backend, channels that fill whole 128-lane tiles and are as many as
    the kernels were seen to pay for inside a step (``KERNELS_FROM``: a
    Gated DeltaNet's 8192 go, a Mamba-2 mixer's 1280 stay), a sequence
    their shortest block divides and taps that reach no further back than
    a tile. Everything else stays on ``causal_conv_plain``, so the choice
    never raises for a shape that serves."""
    return (_pallas.on_tpu() and channels % 128 == 0
            and channels >= KERNELS_FROM and seq_len % ROWS_MIN == 0
            and taps - 1 <= EDGE)


def causal_conv(x, weight, bias=None):
    """``x [b, s, c]``, ``weight [taps, c]``, ``bias [c]`` or none:
    position t gets ``sum_j weight[j] x[t - taps + 1 + j] + bias`` (zeros
    before the sequence), then ``silu``; float32 inside, ``x.dtype`` out.
    By the kernels where ``serves`` says so and by ``causal_conv_plain``
    everywhere else."""
    if serves(x.shape[1], x.shape[2], weight.shape[0]):
        return causal_conv_kernels(x, weight, bias)
    return causal_conv_plain(x, weight, bias)


def causal_conv_plain(x, weight, bias=None, activation=jax.nn.silu):
    """``causal_conv`` in plain ``jax.numpy``: the path of every backend
    and shape the kernels do not serve, and their reference. The operand
    is cast to float32 and padded, each tap a slice of that copy.
    ``activation=None`` leaves the sum of the taps as it is (the gated
    short convolution of ``models/sconv.py``, which the kernels, ``silu``
    alone, do not compute)."""
    taps, seq = weight.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    out = 0.0 if bias is None else bias.astype(jnp.float32)
    for j in range(taps):
        out = out + weight[j].astype(jnp.float32) * padded[:, j:j + seq]
    return (out if activation is None else activation(out)).astype(x.dtype)


class _Plan(NamedTuple):
    """All a kernel call is built from besides its operands' shapes. Made
    outside the jitted calls, so that what the process holds besides the
    operands (the backend) is part of their cache's key and never read
    under a cached trace."""
    taps: int
    rows: int
    lanes: int
    sub: int
    interpret: bool


def _count_trace(kernel, plan, channels):
    """Which kernels a job got, by the taps, the width and the block."""
    _pallas.count_trace(
        "hvt_causal_conv_kernel_traces_total",
        "causal depthwise convolution kernels traced into compiled "
        "programs (counted per trace, not per execution)",
        kernel=kernel, taps=plan.taps, channels=channels,
        block=f"{plan.rows}x{plan.lanes}")


def _plan(x, weight, rows, lanes, sub):
    _, seq, channels = x.shape
    taps = weight.shape[0]
    rows = rows or _pallas.largest(seq, ROWS, HALO)
    lanes = lanes or _pallas.largest(channels, LANES, 128)
    sub = sub or _pallas.largest(rows, SUB, EDGE)
    if (seq % rows or rows % HALO or channels % lanes or rows % sub
            or sub % EDGE or taps - 1 > EDGE):
        raise ValueError(
            f"a block of {rows} x {lanes} in passes of {sub} rows does not "
            f"tile [{seq}, {channels}] with {taps} taps: rows in multiples "
            f"of {HALO} that divide the sequence, passes in multiples of "
            f"{EDGE} that divide the rows, at most {EDGE + 1} taps")
    return _Plan(taps, rows, lanes, sub, _pallas.interpret())


# ---------------------------------------------------------------- kernels
#
# Grid (batch, block of channels, block of positions). Inside a step a
# loop walks the block ``plan.sub`` rows at a time, top to bottom, with
# the EDGE rows before the pass carried in registers; a value is
# ``[rows, lanes]`` float32, a few registers wide.

def _taps_of(w_ref, b_ref, plan):
    """The taps as ``[1, lanes]`` float32 rows, newest position first
    (``taps[d]`` multiplies the row ``d`` positions back), and the bias."""
    taps = [w_ref[pl.ds(plan.taps - 1 - d, 1), :].astype(_F32)
            for d in range(plan.taps)]
    return taps, None if b_ref is None else b_ref[...].astype(_F32)


def _back(rows, before, d):
    """``rows`` moved ``d`` positions down: row t holds what was at ``t -
    d``, the first ``d`` from the end of ``before [EDGE, lanes]``."""
    if d == 0:
        return rows
    return pltpu.roll(jnp.concatenate([before, rows], axis=0), d, 0)[EDGE:]


def _ahead(rows, after, d):
    """``rows`` moved ``d`` positions up: row t holds what was at ``t +
    d``, the last ``d`` from the start of ``after [EDGE, lanes]``."""
    if d == 0:
        return rows
    n = rows.shape[0]
    return pltpu.roll(jnp.concatenate([rows, after], axis=0),
                      n + EDGE - d, 0)[:n]


def _pre(moved, taps, bias):
    out = sum(w * x for w, x in zip(taps, moved))
    return out if bias is None else out + bias


def _edge_before(before_ref, plan):
    """The EDGE rows before the block, zeros before the sequence."""
    rows = before_ref[0].astype(_F32)[HALO - EDGE:]
    return jnp.where(pl.program_id(2) == 0, 0.0, rows)


def _fwd_kernel(x_ref, before_ref, w_ref, *rest, plan):
    b_ref, o_ref = rest if len(rest) == 2 else (None, *rest)
    taps, bias = _taps_of(w_ref, b_ref, plan)

    def one_pass(i, before):
        at = pl.ds(pl.multiple_of(i * plan.sub, plan.sub), plan.sub)
        rows = x_ref[0, at, :].astype(_F32)
        pre = _pre([_back(rows, before, d) for d in range(plan.taps)],
                   taps, bias)
        o_ref[0, at, :] = (pre * jax.nn.sigmoid(pre)).astype(o_ref.dtype)
        return rows[plan.sub - EDGE:]

    jax.lax.fori_loop(0, plan.rows // plan.sub, one_pass,
                      _edge_before(before_ref, plan))


def _bwd_kernel(x_ref, x_before_ref, x_after_ref, g_ref, g_after_ref, w_ref,
                *rest, plan):
    b_ref, dx_ref, sums_ref = rest if len(rest) == 3 else (None, *rest)
    taps, bias = _taps_of(w_ref, b_ref, plan)
    sub, lanes = plan.sub, plan.lanes

    def dpre_of(rows, before, g):
        """``dpre = g silu'(pre)`` of ``rows`` and the rows themselves
        moved back by each tap."""
        moved = [_back(rows, before, d) for d in range(plan.taps)]
        pre = _pre(moved, taps, bias)
        s = jax.nn.sigmoid(pre)
        return g * (s * (1.0 + pre * (1.0 - s))), moved

    def dx_of(dpre, after):
        return sum(w * _ahead(dpre, after, d) for d, w in enumerate(taps))

    def fold(rows):
        return sum(rows[k:k + EDGE] for k in range(0, rows.shape[0], EDGE))

    # dx of a pass needs dpre of the EDGE rows after it, so it leaves one
    # pass late: pass i writes dx of pass i - 1
    def one_pass(i, carry):
        before, late, sums = carry
        start = pl.multiple_of(i * sub, sub)
        at = pl.ds(start, sub)
        rows = x_ref[0, at, :].astype(_F32)
        dpre, moved = dpre_of(rows, before, g_ref[0, at, :].astype(_F32))

        @pl.when(i > 0)
        def _the_pass_before():
            dx_ref[0, pl.ds(pl.multiple_of(start - sub, sub), sub), :] = (
                dx_of(late, dpre[:EDGE]).astype(dx_ref.dtype))

        sums = [total + fold(dpre * x) for total, x in zip(sums, moved)] + [
            sums[-1] + fold(dpre)]
        return rows[sub - EDGE:], dpre, sums

    zeros = jnp.zeros((EDGE, lanes), _F32)
    before, late, sums = jax.lax.fori_loop(
        0, plan.rows // sub, one_pass,
        (_edge_before(x_before_ref, plan), jnp.zeros((sub, lanes), _F32),
         [zeros] * (plan.taps + 1)))
    # the EDGE rows after the block, nothing after the sequence
    after, _ = dpre_of(x_after_ref[0].astype(_F32)[:EDGE], before,
                       g_after_ref[0].astype(_F32)[:EDGE])
    after = jnp.where(pl.program_id(2) == pl.num_programs(2) - 1, 0.0, after)
    dx_ref[0, pl.ds(plan.rows - sub, sub), :] = dx_of(late, after).astype(
        dx_ref.dtype)
    # sums[d] is of dpre[t] x[t - d]: the gradient of weight[taps - 1 - d];
    # the last is the bias's and goes below them
    for row, total in zip((*reversed(range(plan.taps)), plan.taps), sums):
        sums_ref[0, 0, pl.ds(row, 1), :] = jnp.sum(total, axis=0,
                                                   keepdims=True)


def _specs(plan, seq):
    """Block specs by kind: a block of positions, the halo tile before it
    and after it (clamped inside the sequence; the kernels put zeros
    where there is nothing), a block's taps or bias."""
    tiles = plan.rows // HALO
    return {
        "block": pl.BlockSpec((1, plan.rows, plan.lanes),
                              lambda bi, ci, si: (bi, si, ci)),
        "before": pl.BlockSpec(
            (1, HALO, plan.lanes),
            lambda bi, ci, si: (bi, jnp.maximum(si * tiles - 1, 0), ci)),
        "after": pl.BlockSpec(
            (1, HALO, plan.lanes),
            lambda bi, ci, si: (bi, jnp.minimum((si + 1) * tiles,
                                                seq // HALO - 1), ci)),
        "taps": pl.BlockSpec((plan.taps, plan.lanes),
                             lambda bi, ci, si: (0, ci)),
        "bias": pl.BlockSpec((1, plan.lanes), lambda bi, ci, si: (0, ci)),
        "sums": pl.BlockSpec((1, 1, plan.taps + 1, plan.lanes),
                             lambda bi, ci, si: (bi, si, 0, ci)),
    }


def _call(kernel, name, plan, operands, in_specs, out_specs, out_shape):
    batch, seq, channels = operands[0].shape
    return pl.pallas_call(
        functools.partial(kernel, plan=plan),
        grid=(batch, channels // plan.lanes, seq // plan.rows),
        in_specs=in_specs, out_specs=out_specs,
        out_shape=[_pallas.out(shape, dtype, *operands)
                   for shape, dtype in out_shape],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=plan.interpret, name=name)(*operands)


def _with_bias(bias):
    return () if bias is None else (bias.reshape(1, -1),)


# Each call is a ``jax.jit`` of its own, as the flash kernels' are: a
# model's layers share one trace and one lowered function a kernel.
@functools.partial(jax.jit, static_argnames="plan")
def _fwd_call(x, weight, bias, *, plan):
    """``x [b, s, c]``, ``weight [taps, c]``, ``bias [c]`` or none ->
    ``silu(conv(x) + bias)`` like ``x``."""
    _count_trace("fwd", plan, x.shape[-1])
    spec = _specs(plan, x.shape[1])
    bias = _with_bias(bias)
    return _call(
        _fwd_kernel, "hvt_causal_conv_fwd", plan, (x, x, weight, *bias),
        [spec["block"], spec["before"], spec["taps"],
         *([spec["bias"]] * len(bias))],
        [spec["block"]], [(x.shape, x.dtype)])[0]


@functools.partial(jax.jit, static_argnames="plan")
def _bwd_call(x, weight, bias, g, *, plan):
    """``(dx, dweight, dbias)`` for ``_fwd_call``'s operands and its
    output's gradient ``g``; ``dbias`` none where there is no bias."""
    _count_trace("bwd", plan, x.shape[-1])
    batch, seq, channels = x.shape
    spec = _specs(plan, seq)
    with_bias = _with_bias(bias)
    dx, sums = _call(
        _bwd_kernel, "hvt_causal_conv_bwd", plan,
        (x, x, x, g, g, weight, *with_bias),
        [spec["block"], spec["before"], spec["after"], spec["block"],
         spec["after"], spec["taps"], *([spec["bias"]] * len(with_bias))],
        [spec["block"], spec["sums"]],
        [(x.shape, x.dtype),
         ((batch, seq // plan.rows, plan.taps + 1, channels), _F32)])
    sums = jnp.sum(sums, axis=(0, 1))
    return (dx, sums[:plan.taps].astype(weight.dtype),
            None if bias is None else sums[plan.taps].astype(bias.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv(x, weight, bias, plan):
    return _fwd_call(x, weight, bias, plan=plan)


def _conv_fwd(x, weight, bias, plan):
    return _fwd_call(x, weight, bias, plan=plan), (x, weight, bias)


def _conv_bwd(plan, res, g):
    return _bwd_call(*res, g, plan=plan)


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv_kernels(x, weight, bias=None, *, rows: Optional[int] = None,
                        lanes: Optional[int] = None,
                        sub: Optional[int] = None):
    """``causal_conv_plain`` through the kernels: ``x [b, s, c]``,
    ``weight [taps, c]``, ``bias [c]`` or none -> ``silu(sum_j weight[j]
    x[t - taps + 1 + j] + bias)`` like ``x`` (zeros before the sequence),
    float32 inside. Differentiable in all three. ``rows``, ``lanes`` and
    ``sub`` name a grid step's block and the rows a pass inside it takes
    (a test's or a microbenchmark's; a model names none)."""
    return _conv(x, weight, bias, _plan(x, weight, rows, lanes, sub))
