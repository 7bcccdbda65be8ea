"""The rotary position embedding of the three mixers that turn ``q`` and
``k`` (``models/transformer.py``'s ``Attention``, ``models/mla.py``'s
``LatentAttention``, ``models/dsa.py``'s ``SparseAttention``): its plain
``jax.numpy`` body, one Pallas TPU kernel under one custom VJP, and the
rule that chooses between them.

``rotary`` is what the mixers call, with one array or with the ``q`` and
``k`` of a layer as a pair; ``serves`` sends each array to the kernel
(``rotary_kernels``) or to ``rotary_plain``, the kernel's reference.

The first ``width`` channels of a head turn as two halves: channel ``j``
of the first half and of the second by ``positions x theta_j``; the rest
pass as they are. **The law** of the ``theta_j`` is the caller's
description, one argument (``base``) of every entry: a number is the plain
law ``base^(-2j / width)``; a ``Yarn`` is that law scaled channel by
channel (the fast channels as they are, the slow ones divided by a factor,
a ramp between) with ``cos`` and ``sin`` times an attention factor;
``law`` makes either from a ``rope_parameters`` entry of a published
configuration and refuses by name what is not built. ``_angles`` is the
one place a frequency is made: the kernel takes its tables from XLA and
knows nothing of the law. The flash kernels read ``[b, h, s, d]``, so on
a TPU XLA has the projection's product write its result heads major (the
transposition is the product's output layout and costs no pass), and the
plain body's slices of halves are computed there: at heads of 64 a half
is 32 of a register's 128 lanes, every product runs on registers a quarter
full, each half is written padded to a tile and the halves are put
together by a third fusion. Three float32 fusions a layer and pass.

The kernel (``hvt_rotary_fwd``, ``hvt_rotary_bwd``) reads and writes that
same ``[b, h, s, d]``, under the transpositions that XLA folds into its
neighbours' layouts: its operand is the product's result and its result
the flash kernels' operand, with no copy between. A grid step takes
``rows`` positions of every head, walks them ``sub`` rows at a time and a
128-lane tile at a time (two heads of 64 side by side fill one), makes a
channel's partner of the other half by two lane rotations and a select on
the lane's place in its head (one rotation where the halves are 64 lanes;
at a half of 128 lanes or more the partner is another tile and nothing
rotates), and writes ``x cos + partner sin`` with the first half's sign
folded into the ``sin`` table: float32 in registers, one rounding on the
way out. The tables ``[b, s, max(d, 128)]`` are made by XLA in ``x.dtype``
with ``cos`` 1 and ``sin`` 0 past ``width``. All the arrays of a call (a
layer's ``q`` and ``k``) go through one kernel call.

The rotary is linear and orthogonal, times the law's factor: the backward
pass is the same kernel on the gradient with ``sin`` negated (a scaled
rotation's transpose is the scaled rotation by the negated angle), and the
residuals are the tables alone.

On the CPU the same kernel code runs through the Pallas interpreter;
compiled, Mosaic wants whole 128-lane tiles and rows in multiples of the
bf16 tile's 16 (``serves`` sends it nothing else). **A block's positions
divide the sequence's**, so no grid step reaches past its arrays: XLA may
keep a kernel's operand in VMEM up to the memory's last byte, and a last
block that overhangs the array then reads past the memory itself
(``keyevl2-s16384``, PR 60: the ``sin`` table and the recomputed ``k`` ended
at byte 134,217,728 of 128 MiB, blocks of 112 of 16,384 positions overhung
them by 80, and the step never came back; blocks of 128 run).
"""

from __future__ import annotations

import functools
import math
from typing import Mapping, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import _pallas

_F32 = jnp.float32
_TILE = 128     # a register's lanes
# What a grid step takes where the caller names nothing: the most
# positions in multiples of SUB that divide the sequence's and for which a
# call's arrays hold up to ELEMENTS a block as VMEM holds it (a head
# narrower than 128 lanes fills 128; in and out, twice each for the
# pipeline, is 8 bytes an element of bf16), and SUB positions a pass of the
# loop inside, the bf16 tile's rows.
# On a v5e, bf16, device ms a call, forward and backward alike: q and k of
# 20 heads of 64 at [8, 1024] 0.216 in passes of 16 rows (95% of the HBM
# rate), 0.491 in passes of 32 and 0.406 of 64 (two heads' halves are put
# side by side in a register: past one packed tile that spills); 32 + 4
# heads of 128 at [1, 16384] 0.42 to 0.44 at every block tried (85%)
# (benchmarks/rotary_kernels.py; PERF.md section 6, PR 60). 640 Ki is 128
# positions a block at each of the benchmark's five shapes (36 to 40 heads
# as VMEM pads them), 4.7 MiB of the kernel's 16.
ELEMENTS, SUB = 640 * 1024, 16


class Yarn(NamedTuple):
    """The YaRN law (arXiv:2309.00071, as ``transformers``'
    ``_compute_yarn_parameters`` computes it): with ``c(n) = width ln(
    original_positions / (2 pi n)) / (2 ln base)``, ``low = c(beta_fast)``
    and ``high = c(beta_slow)`` (floored and ceiled under ``truncate``,
    clipped to ``[0, width - 1]``) and ``ramp_j = clip((j - low) / (high -
    low), 0, 1)``,

        theta_j = base^(-2j / width) x ((1 - ramp_j) + ramp_j / factor)

    so the channels under ``low`` turn as the plain law's, those from
    ``high`` up ``factor`` times slower, and ``cos`` and ``sin`` are both
    multiplied by ``attention_factor`` (None: ``0.1 ln(factor) + 1``, 1
    for a factor up to 1): a score of the turned q and k is its square
    times the plain one."""
    base: float
    factor: float
    original_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None
    truncate: bool = True


# A caller's description of the law: a base alone is the plain one.
Law = Union[float, Yarn]


def law(rope_parameters: Mapping) -> Law:
    """The law a published configuration's ``rope_parameters`` entry (or
    its ``rope_scaling`` with the ``rope_theta`` beside it) names, under
    the source's keys: ``rope_type`` (or ``type``) ``"default"`` is the
    base ``rope_theta``, ``"yarn"`` a ``Yarn`` of ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``attention_factor`` and ``truncate``. What is not built is refused by
    name: every other ``rope_type``, and ``mscale`` / ``mscale_all_dim``
    (an attention factor from two further numbers)."""
    kind = rope_parameters.get("rope_type", rope_parameters.get(
        "type", "default"))
    base = float(rope_parameters["rope_theta"])
    if kind == "default":
        return base
    if kind != "yarn":
        raise ValueError(
            f"rope_type {kind!r} is not built: the rotary's law is "
            f"'default' (base^(-2j / width)) or 'yarn'")
    for key in ("mscale", "mscale_all_dim"):
        if rope_parameters.get(key):
            raise ValueError(
                f"{key} ({rope_parameters[key]!r}) is not built: a yarn "
                f"law's attention factor is given, or 0.1 ln(factor) + 1")
    return Yarn(
        base, float(rope_parameters["factor"]),
        int(rope_parameters["original_max_position_embeddings"]),
        float(rope_parameters.get("beta_fast") or 32.0),
        float(rope_parameters.get("beta_slow") or 1.0),
        rope_parameters.get("attention_factor"),
        bool(rope_parameters.get("truncate", True)))


def law_name(described: Law) -> str:
    """``"yarn"`` or ``"plain"``: what an engagement counter calls it."""
    return "yarn" if isinstance(described, Yarn) else "plain"


def yarn_range(described: Yarn, width: int):
    """``(low, high)``: the channels between which a ``Yarn`` law over
    ``width`` channels goes from the plain frequencies to the divided
    ones."""
    at = lambda turns: (width * math.log(
        described.original_positions / (turns * 2 * math.pi))
        / (2 * math.log(described.base)))
    low, high = at(described.beta_fast), at(described.beta_slow)
    if described.truncate:
        low, high = math.floor(low), math.ceil(high)
    return max(low, 0), min(high, width - 1)


def frequencies(described: Law, half: int):
    """``(theta_j`` for the ``half`` channels ``j`` of a half, float64
    ``[half]``, the factor on ``cos`` and ``sin``)`` of the law."""
    scaled = isinstance(described, Yarn)
    plain = 1.0 / ((described.base if scaled else described)
                   ** (np.arange(0, half) / half))
    if not scaled:
        return plain, 1.0
    low, high = yarn_range(described, 2 * half)
    if low == high:
        high += 0.001       # no singularity (the source's guard)
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    on_tables = described.attention_factor
    if on_tables is None:
        on_tables = (1.0 if described.factor <= 1
                     else 0.1 * math.log(described.factor) + 1.0)
    return plain * ((1.0 - ramp) + ramp / described.factor), float(on_tables)


class _Plan(NamedTuple):
    """All a kernel call is built from besides its operands' shapes. Made
    outside the jitted call, so that what the process holds besides the
    operands (the backend) is part of its cache's key and never read under
    a cached trace."""
    dim: int            # a head's channels
    width: int          # of them turned
    rows: int
    sub: int
    backward: bool      # the gradient's pass: sin negated
    interpret: bool


def serves(shape, dtype, width: Optional[int] = None,
           flat: bool = False) -> bool:
    """Whether the rotary over the first ``width`` channels (None: all) of
    the heads of ``x [b, s, h, d]`` of this ``shape`` and ``dtype`` goes to
    the kernel, from what can be observed (static trace-time facts, so the
    choice compiles away): a TPU backend, rank 4, heads of 64 or of whole
    128-lane tiles, ``h d`` in whole tiles, positions in whole bf16 tiles,
    two bytes a channel, and an even width whose halves are partners inside
    a tile or whole tiles apart: what was compiled for a v5e and run there
    (``tests/test_chip_compile.py``, ``benchmarks/rotary_kernels.py``).
    Four bytes a channel stay plain: heads major a head of 64 is padded to
    128 lanes in HBM, which the flash kernels' bf16 operands are anyway and
    which in float32 is eight bytes a channel, read and written (the index
    queries of ``models/dsa.py``, float32 ``[1, 16384, 16, 64]``: through
    the kernel the compiled step of ``keyevl2-s16384`` takes 0.95 GB more;
    compiler, PR 60).

    ``flat`` is what the caller knows of where ``x`` comes from: not a
    projection's own result, which XLA writes heads major for the kernel at
    no cost, but an operation over the whole width ``h d`` (a norm of all
    the channels), which leaves it ``[b, s, h d]``. Heads of whole tiles
    are then laid heads major by a transposing copy of each array in front
    of the kernel (``olmoe-s4096``: +1.85% of ``hbm_step`` for +0.8% of
    throughput; my chip run, PR 60), so they stay plain; heads of 64 are
    padded to a tile for the flash kernels whoever turns them, and XLA
    writes them so from the norm.

    Everything else (one head of 64, a key without a head axis) stays on
    ``rotary_plain``, so the choice never raises for a shape that
    serves."""
    if not _pallas.on_tpu() or len(shape) != 4:
        return False
    _, seq, heads, dim = shape
    width = dim if width is None else width
    return ((heads * dim) % _TILE == 0 and seq % 16 == 0
            and (dim % _TILE == 0 or dim == _TILE // 2)
            and not (flat and dim % _TILE == 0)
            and jnp.dtype(dtype).itemsize == 2
            and width % 2 == 0 and 0 < width <= dim
            and (width <= _TILE or width % (2 * _TILE) == 0))


def rotary(x, positions, base: Law, width: Optional[int] = None,
           flat: bool = False):
    """Rotary position embeddings by the law ``base`` describes (a number:
    the plain law at that base; a ``Yarn``) over the first ``width``
    channels of a head (None: all of them) of ``x [.., seq, heads, d]`` or
    ``[.., seq, d]``, or of each array of a tuple (a layer's ``q`` and
    ``k``: one pass for both); ``positions [.., seq]``. Float32 phases,
    ``cos`` and ``sin`` rounded to ``x.dtype``, like ``x``. By the kernel
    where ``serves`` says so and by ``rotary_plain`` everywhere else;
    ``flat`` says that ``x`` comes of an operation over the whole width
    ``h d`` and not of a projection (``serves``)."""
    if not isinstance(x, tuple):
        return rotary((x,), positions, base, width, flat)[0]
    served = [serves(t.shape, t.dtype, width, flat) for t in x]
    turned = iter(rotary_kernels(
        tuple(t for t, kernel in zip(x, served) if kernel), positions, base,
        width) if any(served) else ())
    return tuple(next(turned) if kernel
                 else rotary_plain(t, positions, base, width)
                 for t, kernel in zip(x, served))


def _angles(positions, base, half):
    """``(positions x theta_j`` for the ``half`` channels ``j`` of a half,
    float32 ``[.., seq, half]``, the law's factor on ``cos`` and
    ``sin``)``: the one place a frequency is made (``frequencies``)."""
    freqs, factor = frequencies(base, half)
    return positions[..., None].astype(_F32) * freqs.astype(np.float32), factor


def _cos_sin(angles, factor, dtype):
    """``cos`` and ``sin`` of float32 ``angles`` times the law's factor,
    rounded to ``dtype`` once: what both bodies turn by."""
    # (under the plain law, factor 1, the program is what it was)
    scaled = lambda t: (t if factor == 1.0 else t * factor).astype(dtype)
    return scaled(jnp.cos(angles)), scaled(jnp.sin(angles))


def rotary_plain(x, positions, base: Law, width: Optional[int] = None):
    """``rotary`` in plain ``jax.numpy``: the path of every backend and
    shape the kernel does not serve, and its reference. ``x [.., seq,
    heads, d]`` (any number of axes between the positions' and the
    channels')."""
    head_dim = x.shape[-1]
    width = head_dim if width is None else width
    half = width // 2
    angles, factor = _angles(positions, base, half)
    angles = angles.reshape(angles.shape[:-1]
                            + (1,) * (x.ndim - angles.ndim) + (half,))
    cos, sin = _cos_sin(angles, factor, x.dtype)
    x1, x2 = x[..., :half], x[..., half:width]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos]
                           + ([x[..., width:]] if width < head_dim else []),
                           axis=-1)


def _tables(positions, base, dim, width, dtype):
    """``cos`` and the signed ``sin`` a channel of a head, rounded to
    ``dtype`` as the plain body rounds them, ``[b, seq, max(dim, 128)]``:
    a head's, side by side as often as a tile holds heads; ``cos`` 1 and
    ``sin`` 0 past ``width``."""
    angles, factor = _angles(positions, base, width // 2)
    rest = angles.shape[:-1] + (dim - width,)
    cos, sin = _cos_sin(angles, factor, dtype)
    cos = jnp.concatenate([cos, cos, jnp.ones(rest, dtype)], axis=-1)
    sin = jnp.concatenate([-sin, sin, jnp.zeros(rest, dtype)], axis=-1)
    times = max(_TILE // dim, 1)
    return jnp.tile(cos, times), jnp.tile(sin, times)


# ----------------------------------------------------------------- kernel
#
# Grid (batch, block of positions); a block holds every head of every
# array, ``[b, h, s, d]``. Inside a step a loop walks the block ``plan.sub``
# rows at a time and, inside a pass, every array's 128-lane tiles one after
# another: a value is ``[sub, 128]`` float32, a few registers. A tile is a
# head's lanes ``[place, place + 128)`` or, of heads narrower than a tile,
# as many heads side by side as fill one.

def _kernel(cos_ref, sin_ref, *refs, plan):
    dim, width, half = plan.dim, plan.width, plan.width // 2
    ins, outs = refs[:len(refs) // 2], refs[len(refs) // 2:]
    side = max(_TILE // dim, 1)         # heads side by side in a tile
    # where in its head a tile's lane lies: the first half's partner is
    # ``half`` lanes up, the second's as many down
    first = (jax.lax.broadcasted_iota(jnp.int32, (plan.sub, _TILE), 1)
             % min(dim, _TILE)) < half

    def tile(t, at):
        """Tile ``t`` of a block's ``heads x dim`` channels at the rows
        ``at``: its place in its head and the indices of its heads' lanes
        in a ref."""
        place = t * _TILE % dim
        lanes = slice(None) if side > 1 else pl.ds(place, _TILE)
        return place, [(0, t * _TILE // dim + j, at, lanes)
                       for j in range(side)]

    def read(ref, parts):
        parts = [ref[i].astype(_F32) for i in parts]
        return parts[0] if side == 1 else jnp.concatenate(parts, 1)

    def one_pass(i, carry):
        at = pl.ds(pl.multiple_of(i * plan.sub, plan.sub), plan.sub)
        cos = cos_ref[0, at, :].astype(_F32)
        sin = sin_ref[0, at, :].astype(_F32)
        if plan.backward:       # the transpose's turn is the inverse's
            sin = -sin
        for x_ref, o_ref in zip(ins, outs):
            for t in range(x_ref.shape[1] * dim // _TILE):
                place, parts = tile(t, at)
                x = read(x_ref, parts)
                table = lambda of: of[:, place:place + _TILE]
                if place >= width:              # nothing here turns
                    y = x
                elif half >= _TILE:             # the partner is a tile
                    other = t + (1 if place < half else -1) * half // _TILE
                    y = x * table(cos) + read(
                        x_ref, tile(other, at)[1]) * table(sin)
                else:
                    up = pltpu.roll(x, _TILE - half, 1)     # x[i + half]
                    partner = up if 2 * half == _TILE else jnp.where(
                        first, up, pltpu.roll(x, half, 1))
                    y = x * table(cos) + partner * table(sin)
                y = y.astype(o_ref.dtype)
                for j, index in enumerate(parts):
                    o_ref[index] = (y if side == 1
                                    else y[:, j * dim:(j + 1) * dim])
        return carry

    jax.lax.fori_loop(0, plan.rows // plan.sub, one_pass, None)


# The call is a ``jax.jit`` of its own, as the other kernels' are: a
# model's layers share one trace and one lowered function a shape.
@functools.partial(jax.jit, static_argnames="plan")
def _call(xs, cos, sin, *, plan):
    """The kernel over the arrays ``xs``, ``[b, h, s, d]`` each."""
    batch, _, seq, dim = xs[0].shape
    for x in xs:
        _pallas.count_trace(
            "hvt_rotary_kernel_traces_total",
            "arrays the rotary kernel turns in compiled programs, forward "
            "and backward apart (counted per trace, not per execution)",
            kernel="bwd" if plan.backward else "fwd", heads=x.shape[1],
            dim=dim, width=plan.width)
    block = lambda x: pl.BlockSpec((1, x.shape[1], plan.rows, dim),
                                   lambda bi, si: (bi, 0, si, 0))
    # [1, s, ..] tables are every sequence's
    table = pl.BlockSpec(
        (1, plan.rows, cos.shape[-1]),
        lambda bi, si: (bi if cos.shape[0] > 1 else 0, si, 0))
    return tuple(pl.pallas_call(
        functools.partial(_kernel, plan=plan),
        grid=(batch, pl.cdiv(seq, plan.rows)),
        in_specs=[table, table] + [block(x) for x in xs],
        out_specs=[block(x) for x in xs],
        out_shape=[_pallas.out(x.shape, x.dtype, x, cos) for x in xs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 2),
        interpret=plan.interpret,
        name="hvt_rotary_bwd" if plan.backward else "hvt_rotary_fwd",
    )(cos, sin, *xs))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _turn(xs, cos, sin, plan):
    return _call(xs, cos, sin, plan=plan)


def _turn_fwd(xs, cos, sin, plan):
    return _call(xs, cos, sin, plan=plan), (cos, sin)


def _turn_bwd(plan, tables, gs):
    cos, sin = tables
    # the tables come of whole-numbered positions: no gradient
    return (_call(tuple(gs), cos, sin, plan=plan._replace(backward=True)),
            jnp.zeros_like(cos), jnp.zeros_like(sin))


_turn.defvjp(_turn_fwd, _turn_bwd)


def rotary_kernels(xs, positions, base: Law, width: Optional[int] = None,
                   *, rows: Optional[int] = None, sub: Optional[int] = None):
    """``rotary_plain`` through the kernel for every array of the tuple
    ``xs``, ``[b, s, h, d]`` each with the same ``b``, ``s`` and ``d``:
    one kernel call. Differentiable. ``rows`` and ``sub`` name a grid
    step's positions, which divide ``s``, and the rows a pass inside it
    takes (a test's or a microbenchmark's; a model names neither)."""
    batch, seq, _, dim = xs[0].shape
    width = dim if width is None else width
    if any(x.shape[:2] != (batch, seq) or x.shape[3] != dim for x in xs):
        raise ValueError(
            f"the arrays of one call share batch, positions and a head's "
            f"width; got {[x.shape for x in xs]}")
    lanes = sum(x.shape[2] for x in xs) * max(dim, _TILE)   # as VMEM pads
    rows = rows or _pallas.largest(
        seq, max(ELEMENTS // lanes // SUB, 1) * SUB, SUB)
    sub = sub or _pallas.largest(rows, SUB, 8)
    if seq % rows or rows % sub or (sub % 8 and sub != seq) or width % 2 \
            or not 0 < width <= dim:
        raise ValueError(
            f"blocks of {rows} of {seq} positions in passes of {sub} rows "
            f"turning {width} of {dim} channels: blocks that divide the "
            f"positions (none reaches past its arrays), passes in multiples "
            f"of 8 that divide the rows, an even width up to the head's")
    plan = _Plan(dim, width, rows, sub, False, _pallas.interpret())
    # [1, seq] where every sequence has the same positions, else [b, seq]
    cos, sin = _tables(positions.reshape(-1, seq), base, dim, width,
                       xs[0].dtype)
    major = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    return tuple(major(o) for o in _turn(
        tuple(major(x) for x in xs), cos, sin, plan))
