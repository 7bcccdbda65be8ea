"""The selective scan of ``models/mamba.py`` (Mamba-1; Gu and Dao,
arXiv:2312.00752): a state-space recurrence whose decay is **one a channel
and state**: its plain ``jax.numpy`` body, two Pallas TPU kernels under one
custom VJP, and the rule that chooses between them.

For ``u [batch, s, D]``, a step size ``delta [batch, s, D]`` (after its
softplus), ``a [D, N]`` (negative) and ``b``, ``c`` ``[batch, s, N]`` the
recurrence carries a state ``h [D, N]`` a sequence,

    h_t = exp(delta_t[:, None] a) h_{t-1} + (delta_t u_t)[:, None] b_t[None, :]
    y_t = h_t c_t                                                     ([D])

from ``h = 0`` (the skip term and the gate are the mixer's). Mamba-2's
decay is one number a head, so a chunk there is ``C B^T`` masked by
cumulative decays, a product on the MXU (``models/ssm.py``). Here the
decay ``exp(delta_t[c] a[c, n])`` differs by channel and by state: no
``[chunk, chunk]`` matrix a head exists, the chunked-product form does not
either, and the scan is elementwise work on the ``[D, N]`` state a
position: vector-unit work beside the projections' products.

``selective_scan`` is what the mixer calls; ``serves`` sends it to the
kernels (``selective_scan_kernels``: ``hvt_mamba_scan_fwd`` and
``hvt_mamba_scan_bwd`` under one custom VJP) or to
``selective_scan_plain``, which holds the equations, has ``jax.grad`` of
itself for a backward pass, and is the kernels' reference and the path off
a TPU. One algorithm, two bodies.

**The contract** (in the words of the delta rules', ``ops/
channel_delta_rule.py``):

- *What is float32.* The decays, their exponents, the state and the sum
  over the state are float32 (``state_dtype``, the mixer's
  ``DECAY_DTYPE``) whatever the operands come in; ``y`` is rounded to
  ``u.dtype`` once, at the end.
- *Which exponents are taken.* Only ``exp(delta_t a)`` of one position,
  ``delta >= 0`` and ``a < 0``: every factor lies in ``[0, 1]``. No
  cumulative decay is formed and none is inverted.
- *What is held.* **No array of ``[s, D, N]`` over the whole sequence,
  forward or backward.** The sequence is walked in chunks of ``chunk``
  positions with the state carried, and the backward pass keeps the state
  at each chunk's start alone (``s / chunk`` states of ``N x D`` float32:
  42 MB a layer at 16,384 positions, 5,120 channels and a chunk of 128)
  and makes a chunk's own states again from it: the plain body under
  ``jax.checkpoint``, ``[chunk, D, N]`` in HBM a few times over, ``STEP``
  positions a pass of a loop (their decays and what they add made at once,
  ``[STEP, D, N]``, only the ``STEP`` multiply-adds on the state following
  one another); the kernels in VMEM, a group of channels at a time, with
  the state itself in registers for a whole chunk either way.
- *Which shapes ``serves`` sends to kernels.* On a TPU, a chunk of
  ``CHUNK`` (the kernels' tile) and a sequence it divides, channels in
  whole blocks of ``LANES``, a state of whole registers (a
  multiple of 16), operands in bf16 or float32 and a float32 state:
  ``phi4flash-s16384``'s 16,384 x 5,120 x 16 and its probe's 2,048
  positions, in bf16 and in float32. Everything else, and everything off
  a TPU, is the plain body's.
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
# The chunk the scan takes where the caller names none (the longest the
# sequence allows up to this), and the positions a pass of the plain body's
# loop inside a chunk takes at once. A chunk's price is what the backward
# pass holds for it, ``[chunk, D, N]`` float32 (in HBM a few times over for
# the plain body, 42 MB each at 128 x 5,120 x 16; in VMEM once for the
# kernels, a block of channels at a time), against ``s / chunk`` kept
# states; a pass's is ``STEP`` times the state in flight against one loop
# step's fixed cost.
CHUNK, STEP = 128, 8
# What a grid step of the kernels takes where the caller names nothing: the
# widest block of channels up to LANES that divides them in whole registers
# of 128 lanes, all walked side by side (``[N, 128]`` float32 is two
# registers at a state of 16: four such chains hide the latency of one
# position's multiply-add), and SUB positions a pass of the loop inside,
# which the compiler sees as one straight line (passes of 8: a forward
# call 6% longer and a backward 3.5%; my chip run, PR 68).
LANES, SUB = 512, 16
_TILE = 128     # lanes of a register
# What a kernel may ask of VMEM (ROADMAP S5 (e): a scope past 24.5 MiB
# slows the fusions beside it); the backward kernel's blocks, scratch, a
# block's states and the rows it folds come to 13.0 MiB at 128 x 512, the
# forward kernel's to 4.7.
VMEM_LIMIT = 24 * 2 ** 20
_LOG2E, _LN2 = 1.4426950408889634, 0.6931471805599453


def chunk_for(seq_len: int, chunk: Optional[int] = None) -> int:
    """The chunk length the scan uses for ``seq_len`` positions."""
    return max(1, min(chunk or CHUNK, seq_len))


def serves(seq_len: int, channels: int, state: int, chunk: int, dtype,
           state_dtype=_F32) -> bool:
    """Whether the scan goes to the kernels, from what can be observed
    (static trace-time facts, so the choice compiles away): a TPU backend,
    the chunk the kernels' tile is (``CHUNK``) and a sequence it divides,
    channels that fill whole blocks of ``LANES``, a state that fills whole
    registers both ways up (16 a channel: two sublane tiles going forward,
    and eight positions' sums a row of 128 lanes going back), operands in
    bf16 or float32 and a float32 state. Everything else stays on
    ``selective_scan_plain``, so the choice never raises for a shape that
    serves. The mixer's counter says ``body`` by this."""
    return (_pallas.on_tpu() and chunk == CHUNK and seq_len % CHUNK == 0
            and channels % LANES == 0 and state % 16 == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32))
            and jnp.dtype(state_dtype) == jnp.dtype(_F32))


def selective_scan(u, delta, a, b, c, *, chunk: Optional[int] = None,
                   state_dtype=_F32):
    """The selective scan, chunked: ``selective_scan_plain``'s arguments
    and result, by the kernels where ``serves`` says so and by the plain
    body everywhere else."""
    seq, channels = u.shape[1:]
    if serves(seq, channels, a.shape[-1], chunk_for(seq, chunk), u.dtype,
              state_dtype):
        return selective_scan_kernels(u, delta, a, b, c, chunk=chunk)
    return selective_scan_plain(u, delta, a, b, c, chunk=chunk,
                                state_dtype=state_dtype)


def _walk(h, a, u, delta, b, c):
    """``STEP`` (or fewer) positions from the state ``h [batch, N, D]``:
    ``u``, ``delta`` ``[batch, t, D]``, ``b``, ``c`` ``[batch, t, N]`` as
    they came (cast here, a pass at a time: no float32 copy of an operand
    over the whole sequence), ``a [N, D]``. Returns the state after them
    and ``y [batch, t, D]``, both in the state's dtype."""
    u, delta, b, c = (t.astype(h.dtype) for t in (u, delta, b, c))
    decay = jnp.exp(delta[:, :, None, :] * a)           # [batch, t, N, D]
    added = (delta * u)[:, :, None, :] * b[..., None]
    states = []
    for t in range(u.shape[1]):
        h = decay[:, t] * h + added[:, t]
        states.append(h)
    y = jnp.sum(jnp.stack(states, axis=1) * c[..., None], axis=2)
    return h, y


def selective_scan_plain(u, delta, a, b, c, *, chunk: Optional[int] = None,
                         state_dtype=_F32):
    """``u [batch, s, D]``, ``delta [batch, s, D]`` (non-negative), ``a [D,
    N]`` (negative), ``b``, ``c`` ``[batch, s, N]``. Returns ``y [batch, s,
    D]`` in ``u.dtype``. A sequence the chunk does not divide is padded
    with positions whose ``delta`` is 0: they decay nothing, add nothing
    and are cut off again."""
    batch, seq, channels = u.shape
    n = a.shape[-1]
    q = chunk_for(seq, chunk)
    step = min(STEP, q)
    pad = -seq % q
    a = a.astype(state_dtype).T                         # [N, D]: D the lanes

    def chunks(t):
        """``[batch, s, w]`` as ``[chunks, batch, q, w]``."""
        t = jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        t = t.reshape(batch, (seq + pad) // q, q, t.shape[-1])
        return jnp.moveaxis(t, 1, 0)                    # [chunks, batch, q, w]

    def chunk_body(h, xs):
        # a chunk that ``step`` does not divide ends in one shorter pass
        u_c, delta_c, b_c, c_c = xs                     # [batch, q, w]
        whole = q - q % step

        def passes(t):
            return jnp.moveaxis(t[:, :whole].reshape(
                batch, whole // step, step, t.shape[-1]), 1, 0)

        h, y = jax.lax.scan(
            lambda h, x: _walk(h, a, *x), h,
            (passes(u_c), passes(delta_c), passes(b_c), passes(c_c)))
        y = jnp.moveaxis(y, 0, 1).reshape(batch, whole, channels)
        if whole < q:
            h, rest = _walk(h, a, u_c[:, whole:], delta_c[:, whole:],
                            b_c[:, whole:], c_c[:, whole:])
            y = jnp.concatenate([y, rest], axis=1)
        return h, y

    # the backward pass keeps a chunk's entering state and makes the rest
    # of the chunk again
    init = jnp.zeros((batch, n, channels), state_dtype)
    _, y = jax.lax.scan(jax.checkpoint(chunk_body), init,
                        (chunks(u), chunks(delta), chunks(b), chunks(c)))
    y = jnp.moveaxis(y, 0, 1).reshape(batch, seq + pad, channels)[:, :seq]
    return y.astype(u.dtype)


def selective_scan_by_position(u, delta, a, b, c):
    """The recurrence a position at a time, float32: what the chunked body
    is held to in the tests."""
    a = a.astype(_F32)

    def one(h, x):
        u_t, delta_t, b_t, c_t = x                      # [batch, D] / [batch, N]
        h = (jnp.exp(delta_t[..., None] * a) * h
             + (delta_t * u_t)[..., None] * b_t[:, None, :])
        return h, jnp.einsum("bdn,bn->bd", h, c_t)

    by_position = lambda t: jnp.moveaxis(t.astype(_F32), 1, 0)
    init = jnp.zeros((u.shape[0], u.shape[2], a.shape[-1]), _F32)
    _, y = jax.lax.scan(one, init, tuple(map(by_position, (u, delta, b, c))))
    return jnp.moveaxis(y, 0, 1)


# ---- the kernels
#
# Channels are lanes and the state's ``N`` sublanes: the state of a block of
# channels is ``[G, N, 128]`` float32 (``G`` register tiles of 128 lanes,
# two registers each at a state of 16) and stays in registers for a whole
# tile of positions. A position's ``delta`` and ``delta u`` are rows of
# their tiles, laid out ``[G, T, 128]`` and read broadcast over the sublanes
# in one load; its ``b`` and ``c`` are columns, ``[2 N, 128]`` with every
# lane the same, made once a tile of positions (``b | c`` come transposed a
# pass of 16 positions at a time: a column is a lane of them) and shared by
# every channel: the grid walks a sequence's tiles in order and a tile's
# blocks of channels within it, each block's state carried in its own row
# of scratch. ``y_t`` is a sum over sublanes: a position leaves its
# products folded to one register a tile (``_halves``), and eight
# positions' are folded together at the end of their pass (``_fold``) into
# the ``[8, 128]`` tile the output wants; so are the backward pass's
# ``d(delta u)`` and ``ddelta``. ``db_t[n]`` and ``dc_t[n]`` are sums over every
# channel: added up register by register over a tile's lanes and blocks
# first, so that the one reduction over lanes is of ``[T, 2 N, 128]`` a
# tile and not of the states. Everything is vector-unit work, four slots a
# bundle; the bundles a pass of each loop takes are in PERF.md section 3.
#
# **What is traced once.** A kernel's body is traced for every program
# that holds it and lowered again on every start, cached executable or
# not: an equation costs 0.9 ms of ``trace_s`` and 0.2 ms of ``lower_s``
# on the chip's host (PERF.md section 6, PR 67, whose bodies of 2,982
# equations cost the cell 3.4 s of ``setup_s`` and the PR). So nothing
# here repeats in Python but the eight registers of a fold: a pass's
# ``SUB`` positions and its folds are ``fori_loop`` with ``unroll=True``,
# which Mosaic's lowering unrolls (the index a constant there: static rows,
# one straight-line pass for the scheduler, what a position leaves for the
# fold kept in registers) and the tracer walks once; the register tiles of
# a block are one array's leading axis and not a list. A loop the lowering
# keeps costs the chip its trips: the folds in loops of their own after the
# walk were 36 bundles a trip by the compiler's count and made a backward
# call 6.73 ms for 6.33, the 32 turns of ``db | dc`` in one 0.65 ms more
# (my chip runs, PR 68).
# ``tests/test_chip_compile.py`` holds the two bodies together to 1,000.


class _Plan(NamedTuple):
    tile: int       # positions a grid step: the chunk
    fwd: int        # channels a grid step, forward
    bwd: int        # and backward
    interpret: bool


def _plan(channels, tile, fwd, bwd):
    """A grid step's block either way; widths the caller names are taken
    as they are (a test's or a microbenchmark's), the others are the
    widest whole register tiles up to ``LANES`` that divide the channels."""
    derived = _pallas.largest(channels, LANES, _TILE)
    return _Plan(tile, fwd or derived, bwd or derived, _pallas.interpret())


def _each(count, body):
    """``body(i)`` for ``i`` below ``count``, traced once and unrolled by
    Mosaic's lowering (the interpreter's XLA loop likewise)."""
    jax.lax.fori_loop(0, count, lambda i, _: body(i), None, unroll=True)


def _each_tile(tiles, body):
    """``body(lane, at)`` for each of a block's ``tiles`` register tiles of
    lanes, ``at`` its 128 lanes of the block."""
    _each(tiles, lambda lane: body(
        lane, pl.ds(pl.multiple_of(lane * _TILE, _TILE), _TILE)))


def _lay(laid):
    """``laid``: pairs of ``rows(at) -> [T, 128]`` float32 (``at`` the 128
    lanes to read) and ``ref [G, T, 128]``, which gets them a register
    tile of lanes at a time: Mosaic reads a row broadcast over the
    sublanes only from a tile 128 lanes wide."""
    def one(lane, at):
        for rows, ref in laid:
            ref[lane] = rows(at)

    _each_tile(laid[0][1].shape[0], one)


def _row(ref, t, n, interpret):
    """Position ``t`` of ``ref [G, T, 128]`` over ``n`` sublanes, ``[G, n,
    128]``: compiled, one load whose sublanes stride by 0 (a register
    fewer a tile than a broadcast of the row read alone); the interpreter
    knows no such stride."""
    if interpret:
        return jnp.broadcast_to(ref[:, pl.ds(t, 1)],
                                (ref.shape[0], n, _TILE))
    return ref[:, pl.ds(t, n, stride=0)]


def _columns(bc_ref, col_ref):
    """``col_ref[t] [2 N, 128]``: position ``t`` of ``bc_ref [1, T / SUB,
    2 N, SUB]`` (``_by_state``) in every lane. A pass of ``SUB`` positions
    whose lanes are static: a lane named by a loop's index would have to
    be turned to its place first, and such a loop ran one position at a
    time at the turn's latency, three times the rest of the kernel."""
    def one_pass(i, _):
        rows = bc_ref[0, i]
        for r in range(SUB):
            col_ref[i * SUB + r] = jnp.broadcast_to(rows[:, r:r + 1],
                                                    col_ref.shape[1:])
        return _

    jax.lax.fori_loop(0, bc_ref.shape[1], one_pass, None)


def _halves(x):
    """``x [..., 8 k, w]`` summed over its ``k`` sublane tiles: ``[..., 8,
    w]``."""
    *lead, rows, w = x.shape
    return x if rows == 8 else jnp.sum(
        x.reshape(*lead, rows // 8, 8, w), axis=-3)


def _fold(groups, part, store, unroll=False):
    """``part(j) [..., 8, w]`` for ``j`` below ``8 groups``; ``store(k,
    rows)`` gets for each group ``k`` of eight the array whose row ``i`` is
    ``part(8 k + i)`` summed over its sublanes: three levels of folding two
    registers into one (a half of each kept, the other halves rotated onto
    them), so that eight sums cost what two and a half would alone and
    arrive as the tile they are stored as. ``unroll``: the groups in line
    (inside a pass) or a loop of the kernel's."""
    def one(k, _):
        level = [part(8 * k + j) for j in range(8)]
        axis = level[0].ndim - 2
        at = jax.lax.broadcasted_iota(jnp.int32, level[0].shape, axis)
        for shift in (1, 2, 4):
            mine = (at & (2 * shift - 1)) < shift
            level = [jax.lax.select(mine, x, y)
                     + pltpu.roll(jax.lax.select(mine, y, x), shift, axis)
                     for x, y in zip(level[::2], level[1::2])]
        store(k, level[0])
        return _

    jax.lax.fori_loop(0, groups, one, None, unroll=unroll)


def _step(t, h, a, d_ref, x_ref, col_ref, interpret):
    """The state ``h [G, N, 128]`` after position ``t``."""
    n = h.shape[1]
    return (jnp.exp2(_row(d_ref, t, n, interpret) * a) * h
            + _row(x_ref, t, n, interpret) * col_ref[t, pl.ds(0, n)])


def _fwd_kernel(u_ref, delta_ref, a_ref, bc_ref, y_ref, h0_ref,
                h_ref, d_ref, x_ref, col_ref, part_ref, rows_ref, *, plan):
    """``y`` of one tile of positions and block of channels, and the state
    the tile was entered with; ``h_ref[ci]`` carries a block's state from
    tile to tile, and the columns made at a tile's first block serve its
    others."""
    n = a_ref.shape[1]
    ci = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _from_nothing():
        h_ref[ci] = jnp.zeros(h_ref.shape[1:], _F32)

    @pl.when(ci == 0)
    def _the_tiles_columns():
        _columns(bc_ref, col_ref)

    h0_ref[0, 0] = h_ref[ci]
    _lay([(lambda at: delta_ref[0, :, at], d_ref),
          (lambda at: delta_ref[0, :, at] * u_ref[0, :, at].astype(_F32),
           x_ref)])
    a = a_ref[...]

    def one_pass(i, h):
        def position(r, h):
            t = i * SUB + r
            h = _step(t, h, a, d_ref, x_ref, col_ref, plan.interpret)
            part_ref[r] = _halves(h * col_ref[t, pl.ds(n, n)])
            return h

        def rows_of(k, rows):
            rows_ref[:, pl.ds(pl.multiple_of(i * SUB + 8 * k, 8), 8)] = rows

        h = jax.lax.fori_loop(0, SUB, position, h, unroll=True)
        _fold(SUB // 8, lambda j: part_ref[j], rows_of, unroll=True)
        return h

    h_ref[ci] = jax.lax.fori_loop(0, plan.tile // SUB, one_pass, h_ref[ci])

    def out(lane, at):
        y_ref[0, :, at] = rows_ref[lane].astype(y_ref.dtype)

    _each_tile(rows_ref.shape[0], out)


def _bwd_kernel(u_ref, delta_ref, a_ref, bc_ref, h0_ref, g_ref,
                du_ref, ddelta_ref, da_ref, dbc_ref,
                dh_ref, d_ref, x_ref, gy_ref, col_ref, part_ref, rows_ref,
                hs_ref, acc_ref, turned_ref, *, plan):
    """One tile of positions and block of channels, the sequence's tiles
    walked from the last: the block's states made again from the one the
    tile was entered with (``hs_ref[t]`` the state before position ``t``),
    then its positions walked back, ``dh_ref[ci]`` carrying ``dA_{t+1}
    dL/dh_{t+1}`` from tile to tile. ``da_ref`` stays in place over a
    sequence; ``db | dc`` of a tile add up lane by lane over its blocks of
    channels and are summed over the lanes at the last, a row of ``8 N``
    lanes every four positions."""
    n = a_ref.shape[1]
    tile = plan.tile
    ci = pl.program_id(2)
    row = lambda ref, t: _row(ref, t, n, plan.interpret)

    @pl.when(pl.program_id(1) == 0)
    def _from_nothing():
        dh_ref[ci] = jnp.zeros(dh_ref.shape[1:], _F32)
        da_ref[0, ci] = jnp.zeros(da_ref.shape[2:], _F32)

    @pl.when(ci == 0)
    def _the_tiles_columns():
        _columns(bc_ref, col_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _lay([(lambda at: delta_ref[0, :, at], d_ref),
          (lambda at: delta_ref[0, :, at] * u_ref[0, :, at].astype(_F32),
           x_ref),
          (lambda at: g_ref[0, :, at].astype(_F32), gy_ref)])
    a = a_ref[...]

    def again(i, h):
        def position(r, h):
            t = i * SUB + r
            hs_ref[t] = h
            return _step(t, h, a, d_ref, x_ref, col_ref, plan.interpret)

        return jax.lax.fori_loop(0, SUB, position, h, unroll=True)

    hs_ref[tile] = jax.lax.fori_loop(0, tile // SUB, again, h0_ref[0, 0])

    def back(i, carry):
        first = tile - SUB * (i + 1)

        def position(r, carry):
            dh, da, after = carry
            at = SUB - 1 - r
            t = first + at
            d_t, g_t, before = row(d_ref, t), row(gy_ref, t), hs_ref[t]
            dstate = g_t * col_ref[t, pl.ds(n, n)] + dh
            # over the block's register tiles of lanes: db | dc
            acc_ref[t] += jnp.concatenate(
                [jnp.sum(dstate * row(x_ref, t), axis=0),
                 jnp.sum(g_t * after, axis=0)], axis=0)
            part_ref[at] = _halves(dstate * col_ref[t, pl.ds(0, n)])
            dh = dstate * jnp.exp2(d_t * a)
            ddecay = dh * before
            part_ref[SUB + at] = _halves(ddecay * a)
            return dh, da + ddecay * d_t, before

        # d(delta u) in the first ``tile`` rows, ddelta's sum in the others
        def rows_of(k, rows):
            to = (k // (SUB // 8)) * tile + first + 8 * (k % (SUB // 8))
            rows_ref[:, pl.ds(pl.multiple_of(to, 8), 8)] = rows

        carry = jax.lax.fori_loop(0, SUB, position, carry, unroll=True)
        _fold(2 * SUB // 8, lambda j: part_ref[j], rows_of, unroll=True)
        return carry

    dh_ref[ci], da, _ = jax.lax.fori_loop(
        0, tile // SUB, back,
        (dh_ref[ci], jnp.zeros(a.shape, _F32), hs_ref[tile]))
    da_ref[0, ci] += da

    def out(lane, at):
        dx = rows_ref[lane, pl.ds(0, tile)]
        du_ref[0, :, at] = (dx * d_ref[lane]).astype(du_ref.dtype)
        ddelta_ref[0, :, at] = (rows_ref[lane, pl.ds(tile, tile)] * _LN2
                                + dx * u_ref[0, :, at].astype(_F32))

    _each_tile(rows_ref.shape[0], out)

    # the sums over lanes: four positions' [8 N, 128] turned, so that the
    # lanes to add are sublanes and the sums a row
    @pl.when(ci == pl.num_programs(2) - 1)
    def _the_tiles_sums():
        def turn(j):
            four = acc_ref[pl.ds(pl.multiple_of(4 * j, 4), 4)]
            turned_ref[j] = _halves(four.reshape(8 * n, _TILE).T)

        _each(tile // 4, turn)

        def sums_of(k, rows):
            dbc_ref[0, pl.ds(pl.multiple_of(8 * k, 8), 8)] = rows

        _fold(tile // 32, lambda j: turned_ref[j], sums_of)


def _specs(plan, lanes, state, blocks, tile_of):
    """Block specs by kind for blocks of ``lanes`` channels; ``tile_of(si)``
    is the tile the grid's step ``si`` works on."""
    t, tiles = plan.tile, lanes // _TILE
    return {
        "block": pl.BlockSpec((1, t, lanes),
                              lambda bi, si, ci: (bi, tile_of(si), ci)),
        "decays": pl.BlockSpec((tiles, state, _TILE),
                               lambda bi, si, ci: (ci, 0, 0)),
        "columns": pl.BlockSpec((1, t // SUB, 2 * state, SUB),
                                lambda bi, si, ci: (bi, tile_of(si), 0, 0)),
        "entered": pl.BlockSpec((1, 1, tiles, state, _TILE),
                                lambda bi, si, ci: (bi, tile_of(si), ci, 0, 0)),
        "ddecays": pl.BlockSpec((1, blocks, tiles, state, _TILE),
                                lambda bi, si, ci: (bi, 0, 0, 0, 0)),
        "sums": pl.BlockSpec((1, t // 4, 8 * state),
                             lambda bi, si, ci: (bi, tile_of(si), 0)),
    }


def _call(kernel, name, plan, lanes, operands, in_specs, out_specs,
          out_shape, scratch):
    """One of the two Pallas calls: a sequence's tiles in order and a
    tile's blocks of ``lanes`` channels within it, both carrying."""
    batch, seq, channels = operands[0].shape
    return pl.pallas_call(
        functools.partial(kernel, plan=plan),
        grid=(batch, seq // plan.tile, channels // lanes),
        in_specs=in_specs, out_specs=out_specs,
        out_shape=[_pallas.out(shape, dtype, *operands)
                   for shape, dtype in out_shape],
        scratch_shapes=[pltpu.VMEM(shape, _F32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=plan.interpret, name=name)(*operands)


def _by_state(a, b, c):
    """``a [D, N]`` as ``a log2(e) [D / 128, N, 128]`` (a decay is then
    ``2^(delta a)``, the one instruction the chip has for it, and
    ``ddelta`` gets its ``ln 2`` back a tile at a time) and ``b | c``
    ``[batch, s, 2 N]`` as ``[batch, s / SUB, 2 N, SUB]``, float32: the
    state on the sublanes, a pass's positions on the lanes (``_columns``)."""
    channels, n = a.shape
    batch, seq, _ = b.shape
    bc = jnp.concatenate([b, c], axis=-1).astype(_F32)
    return (jnp.swapaxes((a.astype(_F32) * _LOG2E).reshape(
                channels // _TILE, _TILE, n), -1, -2),
            jnp.swapaxes(bc.reshape(batch, seq // SUB, SUB, 2 * n), -1, -2))


def _scratch(plan, lanes, state, blocks, laid, parts):
    """What both kernels keep in VMEM: a carry a block of channels, ``laid``
    tiles of rows (``_lay``), the tile's ``b | c`` columns, ``parts``
    registers a position of a pass to fold and the rows a tile's fold to."""
    t, tiles = plan.tile, lanes // _TILE
    return [(blocks, tiles, state, _TILE), *[(tiles, t, _TILE)] * laid,
            (t, 2 * state, _TILE), (parts * SUB, tiles, 8, _TILE),
            (tiles, parts * t, _TILE)]


# Each call is a ``jax.jit`` of its own, as the flash kernels' are: a
# model's layers share one trace and one lowered function a kernel.
@functools.partial(jax.jit, static_argnames="plan")
def _fwd_call(u, delta, a, b, c, *, plan):
    """``selective_scan_plain``'s operands, ``s`` a multiple of the tile
    -> ``y`` like ``u`` and the state each tile was entered with, ``[batch,
    s / tile, D / 128, N, 128]`` float32, which the backward pass reads."""
    batch, seq, channels = u.shape
    n, t, blocks = a.shape[-1], plan.tile, channels // plan.fwd
    spec = _specs(plan, plan.fwd, n, blocks, lambda si: si)
    return _call(
        _fwd_kernel, "hvt_mamba_scan_fwd", plan, plan.fwd,
        (u, delta.astype(_F32), *_by_state(a, b, c)),
        [spec["block"], spec["block"], spec["decays"], spec["columns"]],
        [spec["block"], spec["entered"]],
        [(u.shape, u.dtype),
         ((batch, seq // t, channels // _TILE, n, _TILE), _F32)],
        _scratch(plan, plan.fwd, n, blocks, 2, 1))


@functools.partial(jax.jit, static_argnames="plan")
def _bwd_call(u, delta, a, b, c, entered, g, *, plan):
    """``(du, ddelta, da, db, dc)`` for ``_fwd_call``'s operands and its
    output's gradient ``g``."""
    batch, seq, channels = u.shape
    n, t, blocks = a.shape[-1], plan.tile, channels // plan.bwd
    tiles = seq // t
    spec = _specs(plan, plan.bwd, n, blocks, lambda si: tiles - 1 - si)
    du, ddelta, da, dbc = _call(
        _bwd_kernel, "hvt_mamba_scan_bwd", plan, plan.bwd,
        (u, delta.astype(_F32), *_by_state(a, b, c), entered, g),
        [spec["block"], spec["block"], spec["decays"], spec["columns"],
         spec["entered"], spec["block"]],
        [spec["block"], spec["block"], spec["ddecays"], spec["sums"]],
        [(u.shape, u.dtype), (delta.shape, _F32),
         ((batch, blocks, plan.bwd // _TILE, n, _TILE), _F32),
         ((batch, seq // 4, 8 * n), _F32)],
        # and a tile's states, db | dc lane by lane and their sums turned
        [*_scratch(plan, plan.bwd, n, blocks, 3, 2),
         (t + 1, plan.bwd // _TILE, n, _TILE), (t, 2 * n, _TILE),
         (t // 4, 8, 8 * n)])
    # [batch, blocks, tiles, N, 128] -> [D, N]
    da = jnp.swapaxes(jnp.sum(da, axis=0).reshape(-1, n, _TILE), 0, 1)
    dbc = dbc.reshape(batch, seq, 2 * n)
    return (du, ddelta.astype(delta.dtype),
            da.reshape(n, channels).T.astype(a.dtype),
            dbc[..., :n].astype(b.dtype), dbc[..., n:].astype(c.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(u, delta, a, b, c, plan):
    return _fwd_call(u, delta, a, b, c, plan=plan)[0]


def _scan_fwd(u, delta, a, b, c, plan):
    y, entered = _fwd_call(u, delta, a, b, c, plan=plan)
    return y, (u, delta, a, b, c, entered)


def _scan_bwd(plan, res, g):
    return _bwd_call(*res, g, plan=plan)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan_kernels(u, delta, a, b, c, *, chunk: Optional[int] = None,
                           fwd: Optional[int] = None,
                           bwd: Optional[int] = None):
    """``selective_scan_plain`` through the kernels, the state float32:
    its arguments and its result, differentiable in all five. A tile is a
    chunk (made whole groups of 32 positions: what ``db | dc`` are folded
    by); a sequence it does not divide is padded with positions whose
    ``delta`` is 0. ``fwd`` and ``bwd`` name the channels a grid step takes
    in each direction (a test's or a microbenchmark's; a model names
    none)."""
    seq, channels = u.shape[1:]
    tile = -(-chunk_for(seq, chunk) // 32) * 32
    pad = -seq % tile
    if pad:
        u, delta, b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                          for t in (u, delta, b, c))
    return _scan(u, delta, a, b, c, _plan(channels, tile, fwd, bwd))[:, :seq]
