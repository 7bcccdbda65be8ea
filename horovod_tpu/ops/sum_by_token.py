"""The sum of a round's rows by their token, as one Pallas kernel that
goes by the rows the router assigned: what a held expert layer's combine
runs forward and its dispatch backward (``models/moe.py``).

``total.at[token].add(rows)`` is a scatter-add, which XLA gives a TPU a
row at a time: 0.10 us for a row of 2048 float32 after 0.7 ms a call where
it has sorted the rows by token itself (a whole round's), 0.25 us a row
where it has not (a piece's inside a loop), a twelfth and a thirtieth of
the memory's rate and the same for a row of zeros (v5e; PERF.md section 6,
PR 41). A gather runs at the memory's rate. So the rows are *gathered*
into the order of their tokens (``plan``: one sort of the round's ``T``
tokens, the rows past the assigned last), after which the rows of a tile
of ``TILE`` tokens are one contiguous run, and the kernel makes each
tile's sum as products on the MXU: a chunk of ``CHUNK`` sorted rows at a
time, ``W [TILE, CHUNK]`` times the chunk, where ``W[t, i]`` is row
``i``'s weight if its token is ``t`` and 0 otherwise. A float32 weight
goes as three bfloat16 parts, whose products with a bfloat16 row are
exact and add up in float32; without weights ``W`` is ones and zeros and
one product does.

The grid is one step a *(tile, chunk)* that meet, at most ``T / CHUNK +
T / TILE`` of them, listed by ``plan`` and read by the block index maps as
prefetched scalars: a tile with no row has one step (its zeros, or its
part of ``total``, are written all the same), a step past the list is
skipped and moves nothing. What lies in the rows past the assigned is
masked before it meets a zero of ``W``.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import _pallas

# Tokens a tile and sorted rows a chunk, where they divide the round (the
# largest part of each that does, elsewhere: ``tile_rows``).
TILE, CHUNK = 256, 128


def tile_rows(n_rows: int, n_tokens: int):
    """``(tile, chunk)`` of a round of ``n_rows`` rows over ``n_tokens``
    tokens: tiles of tokens, chunks of rows."""
    return math.gcd(n_tokens, TILE), math.gcd(n_rows, CHUNK)


class Plan(NamedTuple):
    """What the sums of one round share (``plan``); ``R`` rows a round
    over ``T`` tokens."""
    order: jax.Array        # [R] the rows by their token, the assigned first
    token: jax.Array        # [R / chunk, 1, chunk] their tokens, R past them
    starts: jax.Array       # [T / tile + 1] the first sorted row of a tile
    tile_of: jax.Array      # [steps] the tile of a grid step
    chunk_of: jax.Array     # [steps] and its chunk of the sorted rows
    steps: jax.Array        # [1] how many of them there are


def plan(token, assigned, n_tokens: int) -> Plan:
    """The order and the grid of the sums by token of a round whose row
    ``i < assigned`` belongs to ``token[i]``, a token of ``n_tokens`` (a
    round holds a whole multiple of ``n_tokens`` rows, ``models/moe.py``'s
    ``held_rows``): a sort of the round's keys, and integer work on a
    number a tile and a grid step."""
    n = token.shape[0]
    tile, chunk = tile_rows(n, n_tokens)
    key = jnp.where(jnp.arange(n) < assigned, token, n).astype(jnp.int32)
    key, order = jax.lax.sort_key_val(key, jnp.arange(n, dtype=jnp.int32))
    # comparisons with every boundary: a search would be a loop
    starts = jnp.sum(key < jnp.arange(0, n_tokens + 1, tile)[:, None],
                     axis=1, dtype=jnp.int32)
    first = jnp.minimum(starts[:-1] // chunk, n // chunk - 1)
    last = jnp.maximum((starts[1:] - 1) // chunk, first)
    ends = jnp.cumsum(last - first + 1)                 # of steps, a tile
    step = jnp.arange(n // chunk + n_tokens // tile)
    tile_of = jnp.minimum(
        jnp.sum(ends <= step[:, None], axis=1, dtype=jnp.int32),
        n_tokens // tile - 1)
    chunk_of = jnp.minimum(
        first[tile_of] + step - (ends - (last - first + 1))[tile_of],
        last[tile_of])
    return Plan(order, key.reshape(-1, 1, chunk), starts, tile_of,
                chunk_of.astype(jnp.int32), ends[-1:].astype(jnp.int32))


def _kernel(tile_of, chunk_of, starts, steps, token_ref, *refs, weighted,
            onto):
    refs = list(refs)
    weight_ref = refs.pop(0) if weighted else None
    rows_ref = refs.pop(0)
    total_ref = refs.pop(0) if onto else None
    out_ref, sum_ref = refs
    (tile, _), chunk = sum_ref.shape, rows_ref.shape[0]
    i = pl.program_id(0)
    t = tile_of[i]

    @pl.when((i == 0) | (tile_of[jnp.maximum(i - 1, 0)] != t))
    def _():
        sum_ref[...] = (total_ref[...].astype(jnp.float32) if onto
                        else jnp.zeros_like(sum_ref))

    @pl.when(i < steps[0])
    def _():
        at = chunk_of[i] * chunk

        def live(shape, axis):      # which sorted rows of the chunk are t's
            row = at + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
            return (row >= starts[t]) & (row < starts[t + 1])

        mine = live((1, chunk), 1) & (
            token_ref[0] == t * tile + jax.lax.broadcasted_iota(
                jnp.int32, (tile, chunk), 0))
        rows = rows_ref[...]
        rows = jnp.where(live((chunk, 1), 0), rows, jnp.zeros_like(rows))
        w = jnp.where(mine, weight_ref[0] if weighted else 1.0, 0.0)
        if rows.dtype != jnp.bfloat16:
            sum_ref[...] += jnp.dot(
                w, rows.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            return
        for _ in range(3 if weighted else 1):
            part = w.astype(jnp.bfloat16)
            sum_ref[...] += jnp.dot(part, rows,
                                    preferred_element_type=jnp.float32)
            w = w - part.astype(jnp.float32)

    @pl.when((i == pl.num_programs(0) - 1)
             | (tile_of[jnp.minimum(i + 1, pl.num_programs(0) - 1)] != t))
    def _():
        out_ref[...] = sum_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("dtype", "interpret", "n_tokens"))
def _call(rows, weight, total, plan, *, dtype, interpret, n_tokens):
    """A ``jax.jit`` of its own, as the other kernels' calls are: the
    layers of a model share one trace and one lowered function."""
    n, width = rows.shape
    tile, chunk = tile_rows(n, n_tokens)
    a_chunk = pl.BlockSpec((1, 1, chunk),
                           lambda i, tile_of, chunk_of, *_: (chunk_of[i], 0, 0))
    a_tile = pl.BlockSpec((tile, width),
                          lambda i, tile_of, *_: (tile_of[i], 0))
    operands = [plan.token]
    specs = [a_chunk]
    if weight is not None:
        operands.append(weight[plan.order].astype(jnp.float32).reshape(
            plan.token.shape))
        specs.append(a_chunk)
    operands.append(rows.at[plan.order].get(mode="promise_in_bounds"))
    specs.append(pl.BlockSpec(
        (chunk, width), lambda i, tile_of, chunk_of, *_: (chunk_of[i], 0)))
    if total is not None:
        operands.append(total)
        specs.append(a_tile)
    return pl.pallas_call(
        functools.partial(_kernel, weighted=weight is not None,
                          onto=total is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(plan.tile_of.shape[0],),
            in_specs=specs, out_specs=a_tile,
            scratch_shapes=[pltpu.VMEM((tile, width), jnp.float32)]),
        out_shape=_pallas.out((n_tokens, width), dtype, rows),
        input_output_aliases=({4 + len(operands) - 1: 0}
                              if total is not None and total.dtype == dtype
                              else {}),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="hvt_moe_sum_by_token")(
            plan.tile_of, plan.chunk_of, plan.starts, plan.steps, *operands)


def sum_by_token(rows, plan: Plan, n_tokens: int, *, weight=None,
                 total=None, dtype=None):
    """``rows [R, d]`` -> ``[T, d]``, ``T = n_tokens`` as ``plan`` was made
    for: the sum, in float32, of the rows that ``plan`` calls assigned,
    each times its ``weight [R]`` (None: as it is), by their token, onto
    ``total [T, d]`` (None: zeros), in ``dtype`` (None: ``total``'s, else
    the rows')."""
    dtype = dtype or (rows.dtype if total is None else total.dtype)
    return _call(rows, weight, total, plan, dtype=jnp.dtype(dtype),
                 interpret=_pallas.interpret(), n_tokens=n_tokens)


def sum_by_token_plain(rows, token, assigned, *, weight=None, total=None):
    """The reference: one scatter-add of the round's rows, those past the
    assigned masked, in float32."""
    rows = rows.astype(jnp.float32)
    if weight is not None:
        rows = rows * weight[:, None]
    rows = jnp.where((jnp.arange(rows.shape[0]) < assigned)[:, None], rows,
                     0.0)
    if total is None:
        total = jnp.zeros(rows.shape, jnp.float32)
    return total.at[token].add(rows)
