"""The per-head norms of ``models/gdn.py`` (the L2 norm of q and k before
the rule, ``RMSNorm(o) w silu(z)`` after it; ``models/kda.py``'s gate is
``sigmoid(z)``, the same functions with ``gate="sigmoid"``): their plain ``jax.numpy``
bodies, four Pallas TPU kernels under two custom VJPs on the flat ``[b, s,
H d]`` layout the mixer's other kernels read and write, and the rule that
chooses between them.

``l2_norm`` and ``gated_norm`` are what the mixer calls; ``serves`` sends
them to the kernels (``l2_norm_kernels``, ``gated_norm_kernels``) or to
``l2_norm_plain`` and ``gated_norm_plain``, the kernels' references.

In plain ``jax.numpy`` a norm over a head is taken on ``[b, s, H, d]``: at
``d`` = 128 that array and ``[b, s, H d]`` tile differently on the chip, so
each reshape between the convolution's result, the rule's operands, its
result and the out-projection's operand is a copy through HBM, and the
norms' float32 values pass through HBM beside them. A head is ``d``
adjacent lanes, so in the kernels a norm over a head is a reduction inside
a block's columns and nothing leaves ``[b, s, H d]``:

- ``hvt_l2_norm_fwd``: ``x rsqrt(sum x^2 + eps) scale`` a head.
- ``hvt_l2_norm_bwd``: the reciprocal root made again from ``x``; ``dx``.
- ``hvt_gated_norm_fwd``: ``o rsqrt(mean o^2 + eps) w silu(z)`` a head,
  the norm before the gate, ``w [d]`` shared by the heads.
- ``hvt_gated_norm_bwd``: the root, the normed ``o`` and the gate made
  again from ``o`` and ``z``; ``do``, ``dz`` and a block's float32 partial
  sums of ``dw`` (``[d]``: ``w`` is every head's), which XLA adds up over
  the blocks.

Every operand is read and every result written once in its own dtype;
float32 exists in registers alone, and the residuals are the operands.
Channels are lanes and positions sublanes; a grid step takes ``rows x
lanes`` of one sequence with ``lanes`` a whole number of heads, walks it
``sub`` rows at a time and a head at a time, and is independent of every
other step. A sequence the block does not divide ends in a block whose
rows past the end are never written (and are masked out of ``dw``).

On the CPU the same kernel code runs through the Pallas interpreter, at
any head width; compiled, Mosaic wants heads in whole 128-lane tiles and
rows in multiples of the bf16 tile's 16 (``serves`` sends it nothing
else).
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
# What a grid step takes where the caller names nothing: ROWS positions
# (the whole of a shorter sequence; fewer where a head is wider than LANES,
# so that the block stays this size), the most whole heads up to LANES that
# divide the channels, and SUB positions a pass of the loop inside. On a
# v5e, bf16, device ms a forward / a backward call of the gated norm at
# [2, 8192, 4096] and of the L2 norm at [2, 8192, 2048]: 0.652 / 1.075 and
# 0.216 / 0.331 at these (76% of the HBM rate); 0.700 / 1.197 and 0.279 /
# 0.448 at 512 lanes, 1.009 / 1.849 at 256; 0.704 / 1.115 at 256 rows;
# 0.998 / 1.847 in passes of 16 rows, within 0.01 in passes of 64; 2048
# lanes, or 1024 rows, and the gated backward's five blocks pass the
# default 16 MiB of VMEM (benchmarks/head_norm_kernels.py; PERF.md section
# 6, PR 39).
ROWS, LANES, SUB = 512, 1024, 32
_FOLD = 8       # a float32 tile's sublanes: what dw's sums are folded to


class _Plan(NamedTuple):
    """All a kernel call is built from besides its operands' shapes. Made
    outside the jitted calls, so that what the process holds besides the
    operands (the backend) is part of their cache's key and never read
    under a cached trace."""
    dim: int        # a head's channels
    eps: float
    scale: float    # what the L2 norm is multiplied by
    rows: int
    lanes: int
    sub: int
    interpret: bool
    gate: str = "silu"      # the gated norm's activation of z


def _count_trace(kernel, plan, channels):
    """Which kernels a job got, by the heads and their width."""
    _pallas.count_trace(
        "hvt_head_norm_kernel_traces_total",
        "per-head norm kernels traced into compiled programs "
        "(counted per trace, not per execution)",
        kernel=kernel, heads=channels // plan.dim, dim=plan.dim)


def serves(seq_len: int, dim: int) -> bool:
    """Whether a norm over heads of ``dim`` channels of ``[b, seq_len, H
    dim]`` goes to the kernels, from what can be observed (static
    trace-time facts, so the choice compiles away): a TPU backend, heads
    that fill whole 128-lane tiles and positions in whole bf16 tiles.
    Everything else stays on the plain bodies, so the choice never raises
    for a shape that serves."""
    return _pallas.on_tpu() and dim % 128 == 0 and seq_len % 16 == 0


def l2_norm(x, dim: Optional[int] = None, *, eps: float, scale: float = 1.0):
    """``x / sqrt(sum x^2 + eps) * scale`` over each group of ``dim``
    adjacent channels of the last axis (a head of ``[..., H dim]``; the
    whole axis where none is named), float32 inside, like ``x``. By the
    kernels where ``serves`` says so and by ``l2_norm_plain`` everywhere
    else."""
    dim = dim or x.shape[-1]
    if x.ndim == 3 and serves(x.shape[1], dim):
        return l2_norm_kernels(x, dim, eps=eps, scale=scale)
    return l2_norm_plain(x, dim, eps=eps, scale=scale)


# The gated norm's activations of z, by name: the function and, for the
# kernels' backward pass, its derivative from z and sigmoid(z).
_GATES = {
    "silu": (lambda z, s: z * s, lambda z, s: s * (1.0 + z * (1.0 - s))),
    "sigmoid": (lambda z, s: s, lambda z, s: s * (1.0 - s)),
}


def gated_norm(o, z, w, *, eps: float, gate: str = "silu"):
    """``RMSNorm(o) * w * gate(z)`` with the mean square over each group
    of ``w.shape[-1]`` adjacent channels of the last axis (a head of
    ``[..., H d]``, or of ``[..., H, d]``), the norm before the gate,
    ``gate`` ``"silu"`` (Gated DeltaNet) or ``"sigmoid"`` (Kimi Delta
    Attention); float32 inside, like ``o``. By the kernels where
    ``serves`` says so and by ``gated_norm_plain`` everywhere else."""
    if o.ndim == 3 and serves(o.shape[1], w.shape[-1]):
        return gated_norm_kernels(o, z, w, eps=eps, gate=gate)
    return gated_norm_plain(o, z, w, eps=eps, gate=gate)


def _by_heads(x, dim):
    return x.reshape(*x.shape[:-1], x.shape[-1] // dim, dim)


def l2_norm_plain(x, dim: Optional[int] = None, *, eps: float,
                  scale: float = 1.0):
    """``l2_norm`` in plain ``jax.numpy``: the path of every backend and
    shape the kernels do not serve, and their reference."""
    heads = _by_heads(x.astype(_F32), dim or x.shape[-1])
    return (heads * jax.lax.rsqrt(
        jnp.sum(heads * heads, axis=-1, keepdims=True) + eps) * scale
            ).astype(x.dtype).reshape(x.shape)


def gated_norm_plain(o, z, w, *, eps: float, gate: str = "silu"):
    """``gated_norm`` in plain ``jax.numpy``: the path of every backend
    and shape the kernels do not serve, and their reference."""
    o32 = _by_heads(o.astype(_F32), w.shape[-1])
    normed = o32 * jax.lax.rsqrt(
        jnp.mean(o32 * o32, axis=-1, keepdims=True) + eps)
    z32 = _by_heads(z.astype(_F32), w.shape[-1])
    gated = _GATES[gate][0](z32, jax.nn.sigmoid(z32))
    return (normed * w * gated).astype(o.dtype).reshape(o.shape)


def _plan(x, dim, eps, scale, rows, lanes, sub, gate="silu"):
    _, seq, channels = x.shape
    if channels % dim:
        raise ValueError(f"heads of {dim} do not divide {channels} channels")
    lanes = lanes or _pallas.largest(channels, max(LANES, dim), dim)
    # ROWS x LANES elements a block, in whole passes of SUB rows
    most = max(ROWS * LANES // max(lanes, LANES) // SUB, 1) * SUB
    rows = rows or min(most, seq)
    sub = sub or _pallas.largest(rows, SUB, _FOLD)
    if channels % lanes or lanes % dim or rows % sub or (
            sub % _FOLD and sub != rows):
        raise ValueError(
            f"a block of {rows} x {lanes} in passes of {sub} rows does not "
            f"tile [{seq}, {channels}] in heads of {dim}: lanes in whole "
            f"heads that divide the channels, passes in multiples of "
            f"{_FOLD} that divide the rows")
    if gate not in _GATES:
        raise ValueError(f"gate {gate!r}: one of {sorted(_GATES)}")
    return _Plan(dim, float(eps), float(scale), rows, lanes, sub,
                 _pallas.interpret(), gate)


# ---------------------------------------------------------------- kernels
#
# Grid (batch, block of channels, block of positions). Inside a step a
# loop walks the block ``plan.sub`` rows at a time and, inside a pass, the
# block's heads one after another: a value is ``[sub, dim]`` float32, a
# few registers, and a head's sum a reduction over its lanes.

def _passes(plan, body, carry=None):
    """``body(at, head, carry) -> carry`` for every pass of ``plan.sub``
    rows (``at``, a slice of the block's rows) and every head of the block
    (``head``, a slice of its lanes), the heads innermost. The heads are a
    loop that is traced once and unrolled when the kernel is lowered, so
    that a head's lanes are a constant there: as a loop in the kernel they
    made a call 2.7 times as long, and as a Python loop the step's trace
    2.4 s longer (PERF.md section 6, PR 39)."""
    def one_pass(i, carry):
        at = pl.ds(pl.multiple_of(i * plan.sub, plan.sub), plan.sub)
        return jax.lax.fori_loop(
            0, plan.lanes // plan.dim,
            lambda j, carry: body(at, pl.ds(pl.multiple_of(
                j * plan.dim, plan.dim), plan.dim), carry), carry,
            unroll=True)

    return jax.lax.fori_loop(0, plan.rows // plan.sub, one_pass, carry)


def _sum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _l2_fwd_kernel(x_ref, o_ref, *, plan):
    def body(at, head, _):
        x = x_ref[0, at, head].astype(_F32)
        root = jax.lax.rsqrt(_sum(x * x) + plan.eps)
        o_ref[0, at, head] = (x * root * plan.scale).astype(o_ref.dtype)

    _passes(plan, body)


def _l2_bwd_kernel(x_ref, g_ref, dx_ref, *, plan):
    # y = scale n, n = x root, root = (sum x^2 + eps)^-1/2:
    # dx = scale root (g - n sum(g n))
    def body(at, head, _):
        x = x_ref[0, at, head].astype(_F32)
        g = g_ref[0, at, head].astype(_F32)
        root = jax.lax.rsqrt(_sum(x * x) + plan.eps)
        n = x * root
        dx_ref[0, at, head] = ((g - n * _sum(g * n)) * (root * plan.scale)
                               ).astype(dx_ref.dtype)

    _passes(plan, body)


def _gated_fwd_kernel(o_ref, z_ref, w_ref, y_ref, *, plan):
    w = w_ref[...].astype(_F32)                         # [1, dim]
    act, _ = _GATES[plan.gate]

    def body(at, head, _):
        o = o_ref[0, at, head].astype(_F32)
        z = z_ref[0, at, head].astype(_F32)
        root = jax.lax.rsqrt(_sum(o * o) * (1.0 / plan.dim) + plan.eps)
        y_ref[0, at, head] = (o * root * w * act(z, jax.nn.sigmoid(z))
                              ).astype(y_ref.dtype)

    _passes(plan, body)


def _gated_bwd_kernel(o_ref, z_ref, w_ref, g_ref, do_ref, dz_ref, sums_ref,
                      *, plan, seq):
    # y = n w act(z), n = o root, root = (mean o^2 + eps)^-1/2, act the
    # plan's gate: t = g w act(z) is n's gradient, do = root (t - n mean(t
    # n)), dz = g n w act'(z), dw = sum of g n act(z) over rows and heads
    w = w_ref[...].astype(_F32)
    act, slope = _GATES[plan.gate]
    ragged = seq % plan.rows != 0
    first = pl.program_id(2) * plan.rows
    folds = plan.sub % _FOLD == 0   # whole float32 tiles a pass

    def body(at, head, total):
        o = o_ref[0, at, head].astype(_F32)
        z = z_ref[0, at, head].astype(_F32)
        g = g_ref[0, at, head].astype(_F32)
        root = jax.lax.rsqrt(_sum(o * o) * (1.0 / plan.dim) + plan.eps)
        n = o * root
        s = jax.nn.sigmoid(z)
        gated = g * act(z, s)                           # g act(z)
        t = gated * w
        do_ref[0, at, head] = ((t - n * (_sum(t * n) * (1.0 / plan.dim)))
                               * root).astype(do_ref.dtype)
        dz_ref[0, at, head] = (g * n * w * slope(z, s)
                               ).astype(dz_ref.dtype)
        dw = gated * n
        if ragged:      # rows past the sequence's end hold anything
            row = first + at.start + jax.lax.broadcasted_iota(
                jnp.int32, dw.shape, 0)
            dw = jnp.where(row < seq, dw, 0.0)
        if folds:
            dw = sum(dw[k:k + _FOLD] for k in range(0, plan.sub, _FOLD))
        else:
            dw = jnp.sum(dw, axis=0, keepdims=True)
        return total + dw           # w is every head's: one sum a block

    total = _passes(plan, body,
                    jnp.zeros((_FOLD if folds else 1, plan.dim), _F32))
    sums_ref[0, 0] = jnp.sum(total, axis=0, keepdims=True)


def _specs(plan):
    return {
        "block": pl.BlockSpec((1, plan.rows, plan.lanes),
                              lambda bi, ci, si: (bi, si, ci)),
        "scale": pl.BlockSpec((1, plan.dim), lambda bi, ci, si: (0, 0)),
        "sums": pl.BlockSpec(
            (1, 1, 1, plan.dim),
            lambda bi, ci, si: (bi, si * pl.num_programs(1) + ci, 0, 0)),
    }


def _call(kernel, name, plan, operands, in_specs, out_specs, out_shape):
    batch, seq, channels = operands[0].shape
    return pl.pallas_call(
        kernel,
        grid=(batch, channels // plan.lanes, pl.cdiv(seq, plan.rows)),
        in_specs=in_specs, out_specs=out_specs,
        out_shape=[_pallas.out(shape, dtype, *operands)
                   for shape, dtype in out_shape],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=plan.interpret, name=name)(*operands)


# Each call is a ``jax.jit`` of its own, as the other kernels' are: a
# model's layers share one trace and one lowered function a kernel.
@functools.partial(jax.jit, static_argnames="plan")
def _l2_fwd_call(x, *, plan):
    _count_trace("l2_fwd", plan, x.shape[-1])
    block = _specs(plan)["block"]
    return _call(functools.partial(_l2_fwd_kernel, plan=plan),
                 "hvt_l2_norm_fwd", plan, (x,), [block], [block],
                 [(x.shape, x.dtype)])[0]


@functools.partial(jax.jit, static_argnames="plan")
def _l2_bwd_call(x, g, *, plan):
    _count_trace("l2_bwd", plan, x.shape[-1])
    block = _specs(plan)["block"]
    return _call(functools.partial(_l2_bwd_kernel, plan=plan),
                 "hvt_l2_norm_bwd", plan, (x, g), [block, block], [block],
                 [(x.shape, x.dtype)])[0]


@functools.partial(jax.jit, static_argnames="plan")
def _gated_fwd_call(o, z, w, *, plan):
    _count_trace("gated_fwd", plan, o.shape[-1])
    spec = _specs(plan)
    return _call(functools.partial(_gated_fwd_kernel, plan=plan),
                 "hvt_gated_norm_fwd", plan, (o, z, w.reshape(1, -1)),
                 [spec["block"], spec["block"], spec["scale"]],
                 [spec["block"]], [(o.shape, o.dtype)])[0]


@functools.partial(jax.jit, static_argnames="plan")
def _gated_bwd_call(o, z, w, g, *, plan):
    """``(do, dz, dw)`` for ``_gated_fwd_call``'s operands and its
    output's gradient ``g``."""
    _count_trace("gated_bwd", plan, o.shape[-1])
    batch, seq, channels = o.shape
    spec = _specs(plan)
    do, dz, sums = _call(
        functools.partial(_gated_bwd_kernel, plan=plan, seq=seq),
        "hvt_gated_norm_bwd", plan, (o, z, w.reshape(1, -1), g),
        [spec["block"], spec["block"], spec["scale"], spec["block"]],
        [spec["block"], spec["block"], spec["sums"]],
        [(o.shape, o.dtype), (z.shape, z.dtype),
         ((batch, pl.cdiv(seq, plan.rows) * (channels // plan.lanes), 1,
           plan.dim), _F32)])
    dw = jnp.sum(sums, axis=(0, 1, 2))
    return do, dz, dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _l2(x, plan):
    return _l2_fwd_call(x, plan=plan)


def _l2_fwd(x, plan):
    return _l2_fwd_call(x, plan=plan), x


def _l2_bwd(plan, x, g):
    return (_l2_bwd_call(x, g, plan=plan),)


_l2.defvjp(_l2_fwd, _l2_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gated(o, z, w, plan):
    return _gated_fwd_call(o, z, w, plan=plan)


def _gated_fwd(o, z, w, plan):
    return _gated_fwd_call(o, z, w, plan=plan), (o, z, w)


def _gated_bwd(plan, res, g):
    return _gated_bwd_call(*res, g, plan=plan)


_gated.defvjp(_gated_fwd, _gated_bwd)


def l2_norm_kernels(x, dim: int, *, eps: float, scale: float = 1.0,
                    rows: Optional[int] = None, lanes: Optional[int] = None,
                    sub: Optional[int] = None):
    """``l2_norm_plain`` through the kernels: ``x [b, s, H dim]`` -> ``x
    rsqrt(sum x^2 + eps) scale`` with the sum over each head's ``dim``
    channels, float32 inside, like ``x``. Differentiable. ``rows``,
    ``lanes`` and ``sub`` name a grid step's block and the rows a pass
    inside it takes (a test's or a microbenchmark's; a model names
    none)."""
    return _l2(x, _plan(x, dim, eps, scale, rows, lanes, sub))


def gated_norm_kernels(o, z, w, *, eps: float, gate: str = "silu",
                       rows: Optional[int] = None,
                       lanes: Optional[int] = None,
                       sub: Optional[int] = None):
    """``gated_norm_plain`` through the kernels: ``o``, ``z``
    ``[b, s, H d]``, ``w [d]`` -> ``o rsqrt(mean o^2 + eps) w gate(z)``
    with the mean over each head's ``d`` channels, float32 inside, like
    ``o``. Differentiable in all three."""
    return _gated(o, z, w, _plan(o, w.shape[-1], eps, 1.0, rows, lanes, sub,
                                 gate))
