"""Fused flash attention as a Pallas TPU kernel, with custom VJP.

The attention score matrix is the one intermediate XLA cannot fuse away on
its own; materializing it is O(S²) HBM traffic, which caps MXU utilization
at long context. This kernel keeps the [block_q × block_k] score tile in
VMEM, maintains online-softmax running (max, sum) statistics, and writes
only the O(S·D) output — the standard FlashAttention-2 decomposition, laid
out for the MXU (fp32 accumulation and statistics, operands as they
arrive). The score tile is derived from the shape (``_derive_tile``:
hundreds of rows by hundreds of columns, so that one pass of the inner
loop is long enough to hide its own overhead) unless the caller names one.
A pass of the forward's loop takes its sub-block as two halves by query
rows (``_chains``), both score products before either softmax, so that the
MXU and the vector unit work at once; a row depends on no other row, so
the result is the one-chain pass's to the last bit. Its schedule ends
where its query block's sight ends: a causal block's grid steps past the
tile that holds its diagonal, which compute nothing, fetch nothing either
(their block index is that tile's, ``_fwd_call``'s ``held``), and where
the block ends in the first half of the diagonal's sub-block, that pass
takes the half alone (``_takes_the_half``); the results are the parent
kernel's to the last bit.

Backward pass recomputes score tiles (FLOPs-for-HBM trade, the same choice
``jax.checkpoint`` makes) from the saved logsumexp in one kernel gridded
over K/V blocks: each tile is made once and gives its share of dQ, dK and
dV (five products). A pass of its loop is bound by the MXUs as it stands
(each product streams the sub-block's rows through them once), so the
loop does no less work by another order, only by doing none that is
wasted: it carries nothing (dK's and dV's sums are added to in their VMEM
scratch, as dQ's are), and where a causal K block begins past the first
half of a query sub-block, that pass takes the second half alone
(``_chains``); and the grid steps of a causal K block that come before
the tile it begins in, which compute nothing, fetch nothing either (their
block index is that tile's). The gradients are the parent kernel's to the
last bit.

A caller whose queries and keys come in two parts (latent attention: 128
columns a head from one projection, 64 rotated ones from another, **the
rotated key one vector a position that every head shares**) passes the
second part as a pair of its own, ``q_r [b, s, h, e]`` and ``k_r [b, s,
e]``: the score tile is then ``q k^T + q_r k_r^T``, two products summed in
float32 before the scale, the mask and the softmax, which are as they
were, and the backward makes seven products from the one ``p`` and ``ds``
(``dq_r = ds k_r``, a head's ``dk_r = ds^T q_r``, summed over the heads
outside). The same MXU passes as one width of ``d + e`` (128 deep is a
pass, 64 deep is a pass), and nothing ``d + e`` wide is written on either
side of the call: no key assembled 32 times, no gradient cut apart. The
pair's refs exist only in a call that passes it; a call without one
traces the kernel bodies, block specs, scratch shapes and compiler
parameters it traced before there was such a pair.

A caller whose queries see only some of the keys (attention over the keys
a learned indexer chose) passes ``choice [b, s, s]`` int8, 1 at a key the
query row attends: one row of keys a query, **the same for every head**.
The mask is applied where the causal mask would be, in the forward kernel
(a block's rows against the streamed tile's keys) and in the backward
kernel (the streamed tile's rows against the block's keys), with ``-inf``
for what is not chosen, so that a row may see no key of a sub-block.
``causal`` still says which tiles no row can see. Like the pair, the
choice's ref exists only in a call that passes it.

**The kernels' surface, in one place.** Every call: ``q [b, s, h, d]``,
``k [b, s, h_kv, d]`` (``h_kv`` divides ``h``: grouped queries, read
zero-copy), ``v [b, s, h_kv, d_v]`` (``d_v`` may differ from ``d``),
``causal``, ``scale``, a score tile or none. Optional, each absent from
the traced kernels of a call that does not pass it: a rotated pair ``q_r
[b, s, h, e]``, ``k_r [b, s, e]`` (needs ``h_kv == h``); a choice ``[b, s,
s]`` int8 (grouped queries allowed); a ``window``, a static count of keys
beside ``causal`` (grouped queries allowed): row ``t`` sees ``t - window <
s <= t``, and **neither kernel visits a tile outside the band**: the
forward's query block ``[q0, q1)`` walks the key sub-blocks from the one
holding ``q0 - window + 1`` to the diagonal's, the backward's K block
``[k0, k1)`` the query sub-blocks from its own to the one holding ``k1 +
window - 2``, the streamed tile is one sub-block and the grid's last axis
a band's tiles (``_band``, ``_band_steps``), so the tiles wholly before
the window are neither computed nor fetched, as those past the diagonal
are not; ``blocks``, a static length beside ``causal`` (grouped queries
allowed), with ``strict`` or not: the causal limit by blocks of that many
positions (block diffusion: a row sees its own block whole and the blocks
before it, or, strict, the blocks before it alone, the first block's rows
nothing), which has to divide every piece the kernels walk by, so that the
tiles with a visible pair are the causal call's and the schedule is left as
it is. What is not built is refused by name
(``flash_attention_with_lse``): one of the pair alone, a pair whose shapes
do not pair, **grouped queries with a rotated pair**, **a choice beside a
rotated pair**, a choice that is not int8 ``[b, s, s]``, **a window
without ``causal``, beside a choice or beside a rotated pair**, **blocks
without ``causal``, beside a window, a choice or a rotated pair, or of a
length that divides no sub-block**, and
sequence blocks that are not multiples of 8 where the kernel is compiled.

No reference-framework counterpart (Horovod ships gradients, not kernels);
this is part of the TPU framework's compute path. On the CPU the same
kernel code runs through the Pallas interpreter (the CPU has no Mosaic
compiler), so the CPU test mesh exercises it; on any other platform the
kernel is compiled and a compiler refusal propagates.
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
from horovod_tpu.ops._pallas import NN, NT, TN, dot

_NEG_INF = -1e30
_LANES = 128  # TPU vector lane width: scratch statistics are stored
              # broadcast across a full lane tile
_SUBLANES = 8   # rows of a float32 vector tile; a narrower dtype packs
                # 4 // itemsize times as many

# The shortest sequence at which the kernels were measured to beat the
# einsum path inside a training step: gpt2-large's 8192 tokens a step on
# a v5e, run both ways as the benchmark's cell runs them, not in a
# microbenchmark (whose einsum figure is 1.7x the step's). At 16 x 512
# einsum is ahead by 8.7%; at 8 x 1024 the kernels by 17.7%, at 4 x 2048
# by 50% (PERF.md section 6, PR 27).
_AUTO_FROM = 1024


def resolve_flash(use_flash, local_seq) -> bool:
    """Resolve a ``use_flash`` policy ("auto" | bool) for a given LOCAL
    sequence length (a static trace-time shape, so the choice compiles
    away). "auto" takes the kernels where all of what it can observe
    says they win: a TPU backend (elsewhere the kernel is interpreted,
    far slower than einsum), a sequence of at least ``_AUTO_FROM``
    positions, and one that takes a proper score tile, a multiple of
    128 (any other length clips the blocks to a few rows, or to fewer
    than Mosaic compiles). Every other length stays on einsum, so
    "auto" never raises for a length einsum serves.

    ``local_seq`` must be the length the attention actually runs over:
    the global length on a single device, the per-shard block length
    under the ring schedule. The shard functions in
    ``parallel/sequence.py`` resolve it themselves from their local
    (post-shard_map) shapes, where it is unambiguous (ADVICE r4)."""
    if isinstance(use_flash, str):
        if use_flash != "auto":
            raise ValueError(
                f"use_flash must be True, False, or 'auto'; got "
                f"{use_flash!r}")
        return (local_seq >= _AUTO_FROM and local_seq % _LANES == 0
                and _pallas.on_tpu())
    return bool(use_flash)


# Two-level decomposition: the sequence operand STREAMS through the
# grid's sequential LAST axis in large VMEM TILES (so per-kernel VMEM is
# O(tile), never O(seq) — a full-sequence-resident design exceeds the
# 16 MB scoped-VMEM limit at seq 8192), while INSIDE the kernel a
# fori_loop walks [block_q, block_k] score sub-blocks of the tile with
# causal skipping at that granularity (one block per grid step pays
# per-step pipeline overhead plus DMA of fully-masked blocks). A pass of
# that loop costs about half a microsecond before it does any arithmetic
# (v5e, PERF.md section 6, PR 25), so the sub-block is as large as VMEM
# allows (``_derive_tile``), not one MXU pass. Every sub-block of a causal
# call is masked, the ones wholly below the diagonal too: a second,
# unmasked loop for those measured 3 to 5% slower than the mask it saves.
#
# What a pass of the forward's loop does (v5e, PERF.md section 6, PR 50).
# The compiler schedules a pass as one basic block, close to program
# order, and a 512 x 1024 float32 score is 512 vector registers of 64: every
# stage of score product -> mask -> row maximum -> exp -> row sum -> value
# product goes through VMEM and waits for the stage before. So (1) the
# sub-block goes as ``chains`` pieces by query rows, each with its own rows
# of m, l and acc, and every piece's score product is issued before any
# piece's softmax: the MXU makes the second piece's scores while the vector
# unit is in the first piece's softmax, and the first piece's value product
# while it is in the second's. And (2) the loop carries nothing: acc, m and
# l (192 registers' worth) stay in their VMEM scratch across passes and
# tiles and a pass reads and writes each piece's rows once, where carried
# values were copied at the top and the bottom of every pass with no
# product in flight. m and l hold a row's value in every lane, so the
# maximum, the correction and the sum are whole-register operations.
#
# What the forward does not do (v5e, PERF.md section 6, PR 62). (1) A
# causal query block's grid steps past the tile that holds its diagonal
# computed nothing and still fetched their tile of K and V (and a rotated
# k_r's, and a choice's columns), a quarter of the steps at 8,192 positions
# and three eighths at 16,384: 1.70 ms of a 13.87 ms call at 128 + 64 on
# 128 (its K tile 256 lanes wide) and 2.13 of 20.84 with a choice, whose
# 512 x 4,096 bytes a step nothing hides; nothing at heads of 64, 128 or
# 256 alone, whose fetches the passes cover. They name the diagonal's tile
# again (``_fwd_call``'s ``held``). (2) ``_causal_n_eff`` rounds a block's
# last sub-block up, so every even 512-row block multiplied and
# exponentiated 512 keys of 1,024 that none of its rows sees: that pass
# goes by the half (``_takes_the_half``). By the compiler's schedule a
# half pass at heads of 128 has 55% of a whole one's operations in 78% of
# its bundles (1,848 for 2,375): a pass is as long as its chain of score,
# row maximum across lanes, exp, row sum, value product and accumulator,
# which half the keys do not shorten. So the half is worth the whole of
# its share at heads of 256 (7.82 -> 7.38 ms at 8,192), where the products
# outweigh the chain, a quarter of it at 64 (1.516 -> 1.478 at 4,096) and
# at 128 + 64 (12.17 -> 11.99), and nothing at 128 and 16,384, where the
# calls under a window, one edge of every band a half, ran no faster and
# walk whole sub-blocks.
#
# What a pass of the backward's loop does (v5e, PERF.md section 6, PR 54).
# Five products (seven with a rotated pair) on a 1024 x 512 sub-block, and
# each streams its 1,024 rows through one of the four MXUs once: by the
# compiler's schedule 92% of a pass's bundles have an MXU busy, the exp, the
# mask, dS and the casts ride under them, and two chains of query rows only
# push k and v (the stationary operands of three of the products) a second
# time. What was not MXU work was (1) the loop's carried dK, dV and dk_r,
# 128 to 160 registers' worth copied, spilled and filled at the two ends of
# every pass and read from and written back to their scratch around the
# loop (150 to 530 bundles a pass, 550 to 950 a grid step): the sums are
# now added to in the scratch, as dQ's always were; and (2) the rows that
# see nothing: a K block of 512 that begins in the middle of a sub-block of
# 1,024 leaves the first half without a visible key, so that pass (one a K
# block in two) takes the second half alone. p is exactly 0 where nothing
# is visible and the sums' order is unchanged, so every gradient is the
# parent's to the last bit. Around the loop, (3): a causal K block's grid
# steps before the tile it begins in computed nothing and still fetched
# their tile of q, dO, lse and delta (and a choice's rows), 10 us a step
# that no computation hid: a quarter of the steps at 8,192 positions, three
# eighths at 16,384 (``_bwd_call``'s ``seen``).

def _scaled(x, scale):
    """``(x', rest)`` with ``x' @ y * rest == x @ y * scale``: a power of
    two only moves exponents, so it is folded into the [block, D] operand
    once a grid step, exactly; any other scale stays on the f32 scores."""
    if scale > 0 and math.frexp(scale)[0] == 0.5:
        return x * scale, None
    return x, scale


def _visible(q0, k0, shape, window=None, blocks=None):
    """Causal mask of a [queries, keys] score sub-block whose first query
    position is ``q0`` and first key position ``k0``; with a ``window``,
    of the causal keys the last ``window`` alone: ``t - window < s <= t``;
    with ``blocks = (size, strict)`` the causal limit by blocks of ``size``
    positions: the keys of the row's own block and of those before it, or,
    ``strict``, of those before it alone."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if blocks is not None:
        size, strict = blocks
        # the first position of the row's block (a power of two is a mask)
        first = (q_pos & -size if size & (size - 1) == 0
                 else q_pos - jax.lax.rem(q_pos, size))
        return k_pos < (first if strict else first + size)
    if window is None:
        return k_pos <= q_pos
    return (k_pos <= q_pos) & (k_pos > q_pos - window)


def _chosen(mask):
    """A tile of a caller's choice (int8, 1 at the keys a query row sees)
    as the mask of a score sub-block. What is not chosen is filled with
    ``-inf`` and not ``_NEG_INF``: a row may see no key of a sub-block
    (the causal mask always shows it the first), and ``exp(-inf - m)`` is
    0 whatever ``m`` the row has reached."""
    return mask.astype(jnp.int32) != 0


def _causal_n_eff(qi, block_q, ti, tile, block_k, n_sub):
    """Number of k sub-blocks of this tile a causal Q block attends to
    (sub-blocks entirely above the diagonal are skipped); the backward
    kernel, which keeps the K block, uses the dual (`start`) form."""
    return jnp.clip(
        ((qi + 1) * block_q - ti * tile + block_k - 1) // block_k,
        0, n_sub)


def _sub_block(j, block):
    return pl.ds(pl.multiple_of(j * block, block), block)


def _band(kernel, i, block, tile, window):
    """``(first, last)`` tile of the streamed operand that holds anything
    for block ``i`` of the kept one under a window: the forward's query
    block ``[q0, q1)`` sees the keys ``q0 - window + 1 .. q1 - 1``, and the
    backward's K block ``[k0, k1)`` is seen by the rows ``k0 .. k1 + window
    - 2`` (``last`` may lie past the sequence's end). ``i`` a grid index or
    a Python int."""
    most = max if isinstance(i, int) else jnp.maximum
    if kernel == "fwd":
        return most(i * block - window + 1, 0) // tile, (
            (i + 1) * block - 1) // tile
    return i * block // tile, ((i + 1) * block + window - 2) // tile


def _band_steps(kernel, s, block, tile, window):
    """The most tiles a block's band holds: a windowed call's last grid
    axis, where a call without one has ``s // tile``."""
    spans = (_band(kernel, i, block, tile, window)
             for i in range(s // block))
    return max(min(last, s // tile - 1) - first + 1 for first, last in spans)


def _takes_the_half(causal, block_q, block_k, window=None):
    """Whether the forward's loop takes the sub-block that holds a query
    block's diagonal by its first half where the block ends inside that
    half. ``_causal_n_eff`` rounds a block's last sub-block up, and a
    whole pass over a sub-block of which no row sees the second half
    multiplies and exponentiates that half for an exact zero. It can only
    happen where the query block is shorter than the key block (the
    derived 512 x 1024: every even block), and a half is whole lane tiles
    (of the score, and of a choice's columns) or is not taken. Not under a
    window, where a half at either edge of a block's band was measured no
    faster than the whole (the comment on what the forward does not do,
    above)."""
    return (causal and window is None and block_q < block_k
            and block_k % (2 * _LANES) == 0)


def _held_steps(causal, s, block_q, tile, window=None):
    """How many grid steps of one head's forward walk name a tile again
    and so fetch nothing (``_fwd_call``'s ``held``): a causal query block's
    steps past the tile that holds its diagonal. None in a call of one
    tile, and none counted under a window, whose grid is a band's steps
    already."""
    if not causal or window is not None:
        return 0
    tiles = s // tile
    return sum(tiles - 1 - ((qi + 1) * block_q - 1) // tile
               for qi in range(s // block_q))


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, causal, block_k, chains,
                choice=False, window=None, blocks=None):
    # a rotated pair, where the caller passed one, comes after the three
    # operands every call has: q_r's block and the shared k_r's tile; a
    # choice, where the caller passed one, after those: the block's rows
    # of the mask against the tile's keys
    *rotated, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    choice_ref = rotated.pop() if choice else None
    block_q = q_ref.shape[2]
    rows = block_q // chains
    tile = k_ref.shape[2]
    qi = pl.program_id(2)
    step = pl.program_id(3)
    n_t = pl.num_programs(3)
    # the tile this step holds: the grid's own, or with a window the
    # ``step``-th of the block's band (``_band``)
    ti = step if window is None else step + _band(
        "fwd", qi, block_q, tile, window)[0]

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _tile():
        q, rest = _scaled(q_ref[0, 0], scale)         # [block_q, D]
        if rotated:
            qr_ref, kr_ref = rotated
            qr, _ = _scaled(qr_ref[0, 0], scale)

        def sub_block(j, width=block_k):
            # a pass: the block's rows against the tile's ``j``-th ``width``
            # keys, a sub-block or a half of one
            k = k_ref[0, 0, _sub_block(j, width), :]
            v = v_ref[0, 0, _sub_block(j, width), :]
            if rotated:
                kr = kr_ref[0, _sub_block(j, width), :]

            def score(c):
                mine = slice(c * rows, (c + 1) * rows)
                sc = dot(q[mine], k, NT)              # [rows, bk]
                if rotated:
                    sc = sc + dot(qr[mine], kr, NT)
                if rest is not None:
                    sc = sc * rest
                if choice:
                    sc = jnp.where(_chosen(choice_ref[
                        0, mine, _sub_block(j, width)]), sc, -jnp.inf)
                elif causal:
                    # past a window's far edge a row may see no key of a
                    # sub-block, as under a choice, and a row of the first
                    # block in the strict form none at all: ``-inf`` there
                    sc = jnp.where(
                        _visible(qi * block_q + c * rows,
                                 ti * tile + j * width, sc.shape, window,
                                 blocks),
                        sc, _NEG_INF if window is None and blocks is None
                        else -jnp.inf)
                return sc

            def softmax_and_values(c, sc):
                # the statistics are read and written where they live,
                # every lane of a row holding the row's
                mine = pl.ds(c * rows, rows)
                m, l = m_ref[mine, :], l_ref[mine, :]
                m_new = jnp.maximum(m, jnp.broadcast_to(
                    jnp.max(sc, axis=-1, keepdims=True), m.shape))
                p = jnp.exp(sc - m_new[:, :1])
                corr = jnp.exp(m - m_new)
                m_ref[mine, :] = m_new
                l_ref[mine, :] = l * corr + jnp.broadcast_to(
                    jnp.sum(p, axis=-1, keepdims=True), l.shape)
                acc_ref[mine, :] = acc_ref[mine, :] * corr[:, :1] + dot(
                    p.astype(v.dtype), v, NN)

            # every chain's score before any chain's softmax: the second
            # product is what the MXU does while the vector unit is in the
            # first softmax
            scores = [score(c) for c in range(chains)]
            for c, sc in enumerate(scores):
                softmax_and_values(c, sc)

        def whole(j, carry):
            sub_block(j)
            return carry

        n_sub = tile // block_k
        n_eff = (_causal_n_eff(qi, block_q, ti, tile, block_k, n_sub)
                 if causal else n_sub)
        # the sub-block holding the first key the block's first row sees
        first = 0 if window is None else jnp.clip(
            (qi * block_q - window + 1 - ti * tile) // block_k, 0, n_sub)
        # where the block ends in the first half of its last sub-block, that
        # sub-block goes by the half (``_takes_the_half``), after the whole
        # ones, as the keys run
        halved = _takes_the_half(causal, block_q, block_k, window)
        if halved:
            half = block_k // 2
            cut = ((qi + 1) * block_q - ti * tile
                   - (n_eff - 1) * block_k <= half)
            n_eff -= cut.astype(jnp.int32)
        jax.lax.fori_loop(first, n_eff, whole, 0)
        if halved:
            # the first half of the sub-block the loop stopped before
            pl.when(cut)(lambda: sub_block(2 * n_eff, half))

    if causal:
        # tiles entirely above the diagonal do no MXU work, and their grid
        # steps fetch nothing either: ``_fwd_call``'s ``held`` names the
        # diagonal's tile for them
        pl.when(ti * tile < (qi + 1) * block_q)(_tile)
    else:
        _tile()

    @pl.when(step == n_t - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:, :1] + jnp.log(l)


def _bwd_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, *refs,
                scale, causal, block_q, chains, choice=False, window=None,
                blocks=None):
    # with a rotated pair each group of refs (in, out, scratch) has two
    # more at its end: k_r's block and q_r's tile, dq_r and a head's dk_r,
    # their accumulators; a choice (never beside a pair) is one more
    # input: the tile's rows of the mask against the block's keys
    if choice:
        choice_ref, *refs = refs
    rotated = len(refs) > 6
    if rotated:
        (kr_ref, qr_ref, dq_ref, dk_ref, dv_ref, dqr_ref, dkr_ref,
         dq_acc_ref, dk_acc_ref, dv_acc_ref, dqr_acc_ref, dkr_acc_ref) = refs
    else:
        dq_ref, dk_ref, dv_ref, dq_acc_ref, dk_acc_ref, dv_acc_ref = refs
    block_k = k_ref.shape[2]
    tile = q_ref.shape[2]
    ki = pl.program_id(2)
    step = pl.program_id(3)   # Q/dO/lse/delta tiles stream
    n_k = pl.num_programs(2)
    n_t = pl.num_programs(3)
    if window is None:
        ti = step
        first_block, last_block = (lambda: ki == 0), (lambda: ki == n_k - 1)
    else:
        # the ``step``-th tile of the K block's band (``_band``), which may
        # lie past it or past the sequence; a tile's first K block is the
        # one holding the first key its first row sees, and its last (the
        # band begins in the tile the block lies in) ends where it ends
        first, last = _band("bwd", ki, block_k, tile, window)
        ti = step + first
        in_band = ti <= jnp.minimum(last, dq_acc_ref.shape[0] // tile - 1)
        first_block = lambda: in_band & (ki == jnp.maximum(
            ti * tile - window + 1, 0) // block_k)
        last_block = lambda: (step == 0) & (
            (ki + 1) % (tile // block_k) == 0)

    @pl.when(step == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)
        if rotated:
            dkr_acc_ref[...] = jnp.zeros_like(dkr_acc_ref)

    @pl.when(first_block())
    def _init_dq():
        # this tile's rows of the whole-sequence accumulator: every K
        # block that sees them adds to them, in the order the grid visits
        # the blocks
        rows = _sub_block(ti, tile)
        dq_acc_ref[rows, :] = jnp.zeros((tile, dq_acc_ref.shape[1]),
                                        jnp.float32)
        if rotated:
            dqr_acc_ref[rows, :] = jnp.zeros((tile, dqr_acc_ref.shape[1]),
                                             jnp.float32)

    def _tile():
        k = k_ref[0, 0]                               # [block_k, D]
        k_sc, rest = _scaled(k, scale)
        k = k.astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        if rotated:
            kr = kr_ref[0]                            # [block_k, e]
            kr_sc, _ = _scaled(kr, scale)
            kr = kr.astype(jnp.float32)

        def sub_block(j, rows):
            """The ``j``-th ``rows`` query rows of the tile against the
            block's keys: a pass. Its sums are added to where they live,
            dK's and dV's in the block's scratch, dQ's in its rows of the
            sequence's: the loop around it carries nothing."""
            mine = _sub_block(j, rows)
            in_seq = _sub_block(ti * (tile // rows) + j, rows)
            q = q_ref[0, 0, mine, :]
            do = do_ref[0, 0, mine, :].astype(jnp.float32)
            lse = lse_ref[0, 0, mine, :]              # [rows, 1]
            delta = delta_ref[0, 0, mine, :]
            sc = dot(q, k_sc, NT)                     # [rows, bk]
            if rotated:
                qr = qr_ref[0, 0, mine, :]
                sc = sc + dot(qr, kr_sc, NT)
            if rest is not None:
                sc = sc * rest
            if choice:
                sc = jnp.where(_chosen(choice_ref[0, mine, :]), sc,
                               -jnp.inf)
            elif causal:
                sc = jnp.where(
                    _visible(ti * tile + j * rows, ki * block_k, sc.shape,
                             window, blocks),
                    sc, _NEG_INF if window is None and blocks is None
                    else -jnp.inf)
            p = jnp.exp(sc - lse)
            dv_acc_ref[...] += dot(p, do, TN)
            dp = dot(do, v, NT)
            ds = p * (dp - delta)
            dk_acc_ref[...] += dot(ds, q.astype(jnp.float32), TN)
            dq_acc_ref[in_seq, :] += dot(ds, k, NN)
            if rotated:
                dqr_acc_ref[in_seq, :] += dot(ds, kr, NN)
                dkr_acc_ref[...] += dot(ds, qr.astype(jnp.float32), TN)

        def whole(i, carry):
            sub_block(i, block_q)
            return carry

        n_sub = tile // block_q
        if causal:
            # query rows strictly before this K block see nothing
            piece = block_q // chains
            start = first = jnp.clip((ki * block_k - ti * tile) // piece,
                                     0, chains * n_sub)
            if chains == 2:
                # the K block may begin in the middle of a sub-block, whose
                # first half then sees none of its keys: that pass takes
                # the second half alone (the first half's p is 0, adds 0)
                start = (first + 1) // 2
                pl.when(first % 2 == 1)(lambda: sub_block(first, piece))
        else:
            start = 0
        # with a window, no sub-block whose first row is past the last row
        # that sees the block's last key
        stop = n_sub if window is None else jnp.clip(
            ((ki + 1) * block_k + window - 2 - ti * tile) // block_q + 1,
            0, n_sub)
        jax.lax.fori_loop(start, stop, whole, 0)

    if window is not None:
        pl.when(in_band)(_tile)
    elif causal:
        # tiles whose every Q position precedes this K block are skipped
        pl.when((ti + 1) * tile > ki * block_k)(_tile)
    else:
        _tile()

    @pl.when(step == n_t - 1)
    def _finalize():
        # dS^T Q and dS K carry the scale once, here, not once a sub-block
        dk_ref[0, 0] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[...].astype(dv_ref.dtype)
        if rotated:
            dkr_ref[0, 0] = (dkr_acc_ref[...] * scale).astype(dkr_ref.dtype)

    @pl.when(last_block())
    def _finalize_dq():
        # the last K block has passed this tile's rows: dQ leaves once
        dq_ref[0, 0] = (dq_acc_ref[_sub_block(ti, tile), :]
                        * scale).astype(dq_ref.dtype)
        if rotated:
            dqr_ref[0, 0] = (dqr_acc_ref[_sub_block(ti, tile), :]
                             * scale).astype(dqr_ref.dtype)


def _blocks(s, requested):
    b = min(requested, s)
    while s % b:
        b //= 2
    return max(b, 1)


# The score tile each kernel prefers when the caller names none, as
# (block_q, block_k). Measured on a v5e at 2 x 20 heads x 4096 x 64 and
# 1 x 32/8 heads x 4096 x 128, bf16, causal and not (PERF.md section 6,
# PRs 25, 29): the forward wants a wide key block (its per-row bookkeeping
# is paid once a sub-block), the backward long query sub-blocks against
# the K block it keeps; larger tiles gained under 2% or were refused by
# the compiler.
_PREFERRED_TILE = {"fwd": (512, 1024), "bwd": (1024, 512)}

# The VMEM Mosaic scopes to one kernel by default, what a core has (v5e;
# the later generations have as much or more), and what XLA keeps of its
# own inside a kernel's scope: around a call in a model the compiler
# asked for up to 3 MiB more than for the kernel alone (PR 25's dK/dV at
# 1024 x 512 inside gpt2-large at 1 x 8192: 18.5 MiB against 15.5).
_SCOPED_VMEM = 16 * 2 ** 20
_VMEM = 128 * 2 ** 20
_XLA_VMEM = 3 * 2 ** 20

# What a kernel's buffers may take. The forward's are a tile's, and the
# default scope has always held them; the backward's grow with the
# sequence, so the core's VMEM is the bound.
_VMEM_BUDGET = {"fwd": _SCOPED_VMEM, "bwd": _VMEM - _XLA_VMEM}


def _vmem_bytes(kernel, block_q, block_k, d, itemsize, tile, s, d_v=None,
                choice=False):
    """Estimate of the VMEM one grid step of ``kernel`` holds: the f32
    [block_q, block_k] score tiles alive at once (one in the forward: its
    chains' scores are pieces of the one tile, each alive until its own
    softmax has made ``p`` of it, and the compiler strip-mines the rest;
    two in the
    backward, p beside ds), the streamed sequence tiles and the resident
    blocks, both double-buffered, and the f32 accumulators, of which the
    backward's dQ is as long as the sequence. ``d`` is the width of q and
    k (and of dQ and dK), ``d_v`` that of v and o (and dO and dV; None:
    ``d``, and every term is then what it was before the two were told
    apart). A position of an operand
    takes whole 128-lane rows whatever its width is, and so does a position
    of a [.., 1] statistic. ``choice``: the caller's mask streams beside
    the sequence tiles, a byte a query row and key, double-buffered (the
    forward a block's rows against the tile's keys, the backward the
    tile's rows against the block's keys). Checked against the least limit the compiler
    takes for the backward alone (v5e, bf16, 20 heads of 64): 19.0 MiB
    at 2 x 4096 and 1024 x 512 (estimate 21.5), 26.25 at 1024 x 1024
    (27.0), 21.0 at 1 x 8192 (23.5), 8.0 at 8 x 1024 and 512 x 512
    (7.5), 13.0 at 4 x 2048 (13.5). Since the backward's loop carries
    nothing (PR 54) the compiler takes 1.25 to 2.75 MiB less than it did
    (17.5 at 2 x 4096, 6.75 at 8 x 1024; 32.5 for 34.5 at 128 + 64 on 128,
    35.25 for 38.0 at d = 256, 31.0 for 32.5 with a choice at 16,384): the
    carried dK and dV were copies beside their scratch, which no term
    here ever counted, so the estimate stands and its margin is wider."""
    lanes = -(-d // _LANES) * _LANES
    lanes_v = lanes if d_v is None else -(-d_v // _LANES) * _LANES
    row, row_v = lanes * itemsize, lanes_v * itemsize
    acc, acc_v, stat = lanes * 4, lanes_v * 4, _LANES * 4
    score = 4 * block_q * block_k
    # past 128 lanes, what the float32 [block, d] values of a sub-block
    # (the scaled q, the rescaled accumulator, dQ's, dK's and dV's
    # contributions) outgrow the slack the figures above were checked
    # with: at d = 256, 2 x 8192, 16 heads on 2 (qwen3-next-80b) the
    # compiler took 16.31 MiB for the forward alone and 38.27 for the
    # backward inside the model, where the terms before this one and
    # XLA's share came to 15.5 and 38.0. The wider of the two widths
    # stands for all of them
    wide = 3 * (block_q + block_k) * (max(acc, acc_v) - stat)
    mask = 2 * tile * (block_q if kernel == "fwd" else block_k) * bool(choice)
    if kernel == "fwd":     # K V stream; q o lse blocks; acc m l scratch
        return (score + mask + 2 * tile * (row + row_v)
                + block_q * (2 * (row + row_v) + 2 * stat + acc_v + 2 * stat)
                + wide)
    # bwd: Q dO lse delta stream in, a dq tile out; k v dk dv blocks and
    # the two accumulators of a block; dq's accumulator
    return (2 * score + mask + 2 * tile * (row + stat + row_v + stat + row)
            + block_k * (2 * 2 * (row + row_v) + acc + acc_v) + s * acc
            + wide)


def _compiler_params(kernel, block_q, block_k, d, itemsize, tile, s, d_v=None,
                     choice=False):
    """The default scope where the estimate and XLA's share fit it; else
    just that much as the kernel's own ``vmem_limit_bytes``, and no more:
    the VMEM a kernel's scope takes is taken from the program around it.
    gpt2-large at 2 x 4096 (v5e, PERF.md section 6, PR 29) ran the same
    step in 713.3 ms with the backward's scope at 32.25 MiB (half as much
    again as the estimate, PR 25's rule) and in 700.4 at 24.5: XLA kept
    fewer operands of the fusions around each call in VMEM, and they and
    the kernel itself were slower; from 24.5 down to 20 nothing moved."""
    limit = _vmem_bytes(kernel, block_q, block_k, d, itemsize, tile, s,
                        d_v, choice) + _XLA_VMEM
    if limit <= _SCOPED_VMEM:
        return None
    if limit > _VMEM:
        raise ValueError(
            f"flash attention's backward pass keeps a float32 dQ "
            f"accumulator of the whole sequence in VMEM: {s} positions "
            f"need {limit / 2 ** 20:.0f} MiB of a core's "
            f"{_VMEM // 2 ** 20}. Shard the sequence (ring_attention) or "
            f"use the einsum path (use_flash=False)")
    return pltpu.CompilerParams(vmem_limit_bytes=limit)


def _derive_tile(kernel, s, d, itemsize, causal, d_v=None, choice=False):
    """The score tile of ``kernel`` ("fwd", "bwd") for a sequence of ``s``
    positions, q and k ``d`` wide and v and o ``d_v`` (None: ``d``): the
    largest (block_q, block_k) — multiples of 128 that
    divide ``s``, one dividing the other so the streamed tile is
    unaffected, neither above the kernel's preferred size — whose buffers
    fit ``_VMEM_BUDGET``; of equal areas the wider key block. A sequence
    that is no multiple of 128 gets one square block of at most 128, and
    one whose streamed tiles alone overflow (wide or f32 operands) 128 x
    128.

    A causal sequence no longer than the preferred tile's long side
    (1024) leaves that tile one sub-block a grid step, so the skipping of
    sub-blocks above the diagonal never happens, and what the chip
    prefers there differs by kernel (v5e, 8 x 20 heads x 1024 x 64 and 2
    x 16 x 1024 x 128, PERF.md section 6, PRs 27, 29). The forward's
    passes cost by their rows whatever they skip, so narrower key blocks
    lose; with the one key block every query block goes through whole, a
    split of the queries only adds grid steps: it takes the sequence
    whole (1024 x 1024, 15% under 512 x 1024). The backward's cost by
    their area: it halves its query sub-block (512 x 512), so that the
    second K block skips the half no query of which sees it."""
    if s % _LANES:
        block = _blocks(s, _LANES)
        return block, block
    most_q, most_k = _PREFERRED_TILE[kernel]
    if causal and kernel == "fwd" and s <= most_k:
        most_q = s
    if (causal and kernel == "bwd" and most_k < s <= most_q
            and s % (2 * _LANES) == 0):
        most_q = s // 2
    sizes = [b for b in range(_LANES, s + 1, _LANES) if s % b == 0]
    fit = [(bq, bk) for bq in sizes if bq <= most_q
           for bk in sizes if bk <= most_k
           if max(bq, bk) % min(bq, bk) == 0
           and _vmem_bytes(kernel, bq, bk, d, itemsize,
                           _seq_tile(s, bq, bk), s, d_v, choice)
           <= _VMEM_BUDGET[kernel]]
    return max(fit, key=lambda t: (t[0] * t[1], t[1]),
               default=(_LANES, _LANES))


def _score_tile(kernel, s, d, itemsize, causal, block_q, block_k, d_v=None,
                choice=False):
    """``(block_q, block_k, derived)`` for one of the two kernels: an
    explicit integer is honoured (clipped to divide ``s``);
    ``None`` takes that side of the tile derived from the shape."""
    derived = block_q is None or block_k is None
    if derived:
        auto_q, auto_k = _derive_tile(kernel, s, d, itemsize, causal, d_v,
                                      choice)
    bq = auto_q if block_q is None else _blocks(s, block_q)
    bk = auto_k if block_k is None else _blocks(s, block_k)
    return bq, bk, derived


def _smallest_piece(s, d, itemsize, d_v, block_q, block_k):
    """The shortest run of positions either kernel of a causal call walks
    by: the forward's query block and its key sub-block (or the half it is
    taken by, ``_takes_the_half``), the backward's K block and its query
    sub-block (or the piece of it, ``_chains``); their greatest common
    divisor, which a block length has to divide."""
    fq, fk, _ = _score_tile("fwd", s, d, itemsize, True, block_q, block_k,
                            d_v)
    bq, bk, _ = _score_tile("bwd", s, d, itemsize, True, block_q, block_k,
                            d_v)
    return math.gcd(
        fq, fk // 2 if _takes_the_half(True, fq, fk) else fk, bk,
        bq // _chains("bwd", bq, bk, itemsize, True))


def _mask_word(blocks):
    """What a traced kernel's name in the counter gains under the causal
    limit by blocks: ``_blocks<size>``, and ``_strict`` in that form; a
    call without one gains nothing."""
    if blocks is None:
        return ""
    return f"_blocks{blocks[0]}" + "_strict" * blocks[1]


def _count_trace(kernel, block_q, block_k, derived, d, d_v, d_rot, chains,
                 window, held_steps=0, halves=False):
    """Which kernel and mask (``fwd``, ``bwd``; ``_choice`` with a caller's
    choice of keys, ``_blocks<size>`` and ``_strict`` under the causal limit
    by blocks, ``_mask_word``), which score tile each traced kernel got,
    whether the rule or the caller chose it, the two widths it was built
    for (q and k's whole
    width, v and o's), how many of q and k's columns came as a rotated
    pair of their own (0: q and k came whole) and into how many pieces by
    query rows a pass of its loop takes its sub-block (``_chains``: the
    forward's two chains side by side; the backward's two halves, of which
    a causal pass leaves out the one that sees nothing), the window it
    was built with (0: none, and the schedule has one bound), and what the
    forward's schedule leaves undone: how many grid steps of one head's
    walk name a tile again and fetch nothing (``_held_steps``) and whether
    its loop takes the sub-block a block's diagonal ends in by its half
    (``_takes_the_half``); both 0 for the backward, whose own rules
    ``chains`` tells."""
    _pallas.count_trace(
        "hvt_flash_kernel_traces_total",
        "flash-attention kernels traced into compiled programs, by "
        "score tile (counted per trace, not per execution)",
        kernel=kernel, block_q=block_q, block_k=block_k,
        derived=int(derived), d_qk=d, d_v=d_v, d_rot=d_rot, chains=chains,
        window=window or 0, held_steps=held_steps, halves=int(halves))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, rotated, choice, scale, causal, block_q, block_k,
           out_dtype, window=None, blocks=None):
    """Differentiable (o, lse). The lse output carries its own gradient:
    d lse/dS = P, so a dlse cotangent folds into the backward kernel as
    delta := rowsum(do∘o) − dlse — the kernel is unchanged.

    q, k, v are ``[b, h, s, d]``; ``rotated`` is None or the pair
    ``(q_r [b, h, s, e], k_r [b, s, e])`` (``_rotated_width``);
    ``choice`` is None or the mask ``[b, s, s]`` int8 (no gradient)."""
    o, lse = _flash_fwd_impl(q, k, v, rotated, choice, scale, causal,
                             block_q, block_k, out_dtype, window, blocks)
    return o, lse


# Most positions of the streamed operand a grid step holds in VMEM: the
# whole sequence of every benchmark cell (1024, 4096) and half of 8192's,
# the only two things measured on the v5e.
_SEQ_TILE = 4096


def _seq_tile(s, block_q, block_k, window=None):
    """Streamed-sequence VMEM tile (elements of the seq axis per grid
    step): the largest multiple of lcm(block_q, block_k) that divides
    ``s`` and is at most ``_SEQ_TILE``; with a window that one multiple
    itself, the least the loops can walk, so that the tiles a block's band
    fetches cover it closely (at 16,384 positions, a window of 2,048 and
    512 x 1024 three tiles of 1,024 keys a query block, where two of 4,096
    would be fetched for the same 2,559).

    The tile must divide ``s`` AND be a multiple of both block sizes —
    the kernels walk ``tile // block`` sub-blocks, so a remainder would
    silently drop sequence positions. Both blocks divide s (``_blocks``),
    hence lcm(block_q, block_k) divides s and a valid tile always
    exists."""
    base = math.lcm(block_q, block_k)
    best, m = base, 2
    while window is None and m * base <= _SEQ_TILE:
        if s % (m * base) == 0:
            best = m * base
        m += 1
    return best


def _rotated_width(rotated):
    """``e`` of a call's rotated pair ``(q_r [b, h, s, e], k_r [b, s, e])``
    beside ``q [b, h, s, d]`` and ``k``: one key a position, which every
    head's grid steps read through an index without a head in it; 0 where
    the caller passed none. The tile, the VMEM estimate and the limit go
    by q and k's whole width ``d + e`` (128 + 64 takes the 256 lanes 192
    takes)."""
    return 0 if rotated is None else rotated[0].shape[-1]


def _chains(kernel, block_q, block_k, itemsize, causal):
    """Into how many pieces by query rows a pass of ``kernel``'s loop takes
    its sub-block: two halves wherever a half is whole sublane tiles of
    the operands (16 rows of bf16, 8 of float32), so that neither q's half
    nor its rows of a scratch is cut inside a tile, and the kernel has a
    use for them. The forward always has: it runs the halves as two
    independent chains side by side. The backward keeps its K block whole
    and its pass is bound by the MXUs, so two chains only push k and v
    twice (PERF.md section 6, PR 54); it tells the halves apart where a K
    block can begin past the first half of a sub-block (a causal call
    whose key block is shorter than the sub-block: the derived 1024 x
    512), and takes that one pass by the half that sees the block.
    Everything else goes through as the one piece it is: the few rows a
    sequence that is no multiple of 128 is clipped to, a backward that is
    not causal or whose sub-blocks begin where its K blocks do."""
    if block_q % (2 * _SUBLANES * 4 // itemsize):
        return 1
    return 2 if kernel == "fwd" or (causal and block_k % block_q) else 1


class _Plan(NamedTuple):
    """All a kernel call is built from besides its operands' shapes."""
    scale: float
    causal: bool
    block_q: int
    block_k: int
    derived: bool       # the rule chose the tile, not the caller
    tile: int           # positions of the streamed operand a grid step
    chains: int         # query-row pieces of a sub-block (``_chains``)
    interpret: bool
    window: int | None = None   # keys a query sees, itself among them
    # the causal limit by blocks of positions: ``(size, strict)``
    blocks: tuple | None = None


def _plan(kernel, q, scale, causal, block_q, block_k, d_v=None, d_rot=0,
          choice=False, window=None, blocks=None):
    """Made outside the jitted calls below, so that what the process
    holds besides the operands (the backend) is part of their cache's
    key and never read under a cached trace. ``d_rot``: the width of a
    rotated pair beside q and k, which the tile goes by as by q's own;
    ``choice``: the call streams a mask, which the tile leaves room for."""
    _, _, s, d = q.shape
    block_q, block_k, derived = _score_tile(
        kernel, s, d + d_rot, q.dtype.itemsize, causal, block_q, block_k,
        d_v, choice)
    return _Plan(scale, causal, block_q, block_k, derived,
                 _seq_tile(s, block_q, block_k, window),
                 _chains(kernel, block_q, block_k, q.dtype.itemsize, causal),
                 _pallas.interpret(), window, blocks)


def _flash_fwd_impl(q, k, v, rotated, choice, scale, causal, block_q,
                    block_k, out_dtype, window=None, blocks=None):
    return _fwd_call(q, k, v, rotated, choice, out_dtype=out_dtype,
                     plan=_plan("fwd", q, scale, causal, block_q, block_k,
                                v.shape[-1], _rotated_width(rotated),
                                choice is not None, window, blocks))


# Each of the two calls is a ``jax.jit`` of its own: a model's layers
# then share one trace and one lowered function a kernel (XLA inlines the
# calls, so the compiled program is the same), where 36 layers' kernels
# traced and lowered one by one were 20 s of every start (PERF.md section
# 6, PR 27).
@functools.partial(jax.jit, static_argnames=("plan", "out_dtype"))
def _fwd_call(q, k, v, rotated=None, choice=None, *, plan, out_dtype):
    """o, lse: grid (b, h, qi, ti): K and V tiles (a rotated ``k_r``'s, a
    choice's columns) stream past each query block, whose online softmax
    lives in VMEM scratch from its first tile to its last. A causal
    block's steps past the tile that holds its diagonal compute nothing
    and name that tile, so nothing is fetched for them (``held``); with a
    window the last axis is a band's tiles. Under GQA a query head reads
    its shared K/V head through the index map."""
    b, h, s, d = q.shape
    d_v, e = v.shape[-1], _rotated_width(rotated)
    block_q, block_k, tile = plan.block_q, plan.block_k, plan.tile
    chosen = choice is not None
    window = plan.window
    _count_trace("fwd" + "_choice" * chosen + _mask_word(plan.blocks),
                 block_q, block_k, plan.derived,
                 d + e, d_v, e, plan.chains, window,
                 _held_steps(plan.causal, s, block_q, tile, window),
                 _takes_the_half(plan.causal, block_q, block_k, window))
    # Grouped-query attention is served ZERO-COPY: query head hi reads
    # K/V head hi // group through the block index map — no repeat
    # materialization, and the shared K/V tile stays VMEM-resident
    # across the group's consecutive hi grid steps.
    group = h // k.shape[1]
    # K/V stream through the grid's sequential LAST axis in VMEM tiles;
    # scratch accumulators carry the online softmax across tiles
    # with a window the last axis is as long as a block's band and not as
    # the sequence, and a step names its tile of the band: the tiles
    # wholly before the window are neither walked nor fetched
    grid = (b, h, s // block_q, s // tile if window is None
            else _band_steps("fwd", s, block_q, tile, window))

    def held(qi, ti):
        """The tile of the streamed operands (K, V, a rotated ``k_r``, a
        choice's columns) that grid step ``ti`` of query block ``qi``
        names: the step's own up to the tile that holds the block's
        diagonal and that tile from there on, which the pipeline holds
        already (the kernel's own ``ti`` stays the grid's, and its
        ``pl.when`` skips those steps), as a windowed call's steps past
        its band's last tile always did. A call of one tile has no such
        step and names what it named."""
        if window is not None:
            first, last = _band("fwd", qi, block_q, tile, window)
            return jnp.minimum(first + ti, last)
        if plan.causal and s // tile > 1:
            return jnp.minimum(ti, ((qi + 1) * block_q - 1) // tile)
        return ti

    # q and k are ``d`` wide, v and o ``d_v``
    by_query = lambda width: pl.BlockSpec(
        (1, 1, block_q, width), lambda bi, hi, qi, ti: (bi, hi, qi, 0))
    by_tile = lambda width: pl.BlockSpec(
        (1, 1, tile, width),
        lambda bi, hi, qi, ti: (bi, hi // group, held(qi, ti), 0))
    in_specs = [by_query(d), by_tile(d), by_tile(d_v)]
    if rotated is not None:
        # q_r as q; the one k_r a position, whatever the head
        in_specs += [by_query(e), pl.BlockSpec(
            (1, tile, e), lambda bi, hi, qi, ti: (bi, held(qi, ti), 0))]
    if chosen:
        # the block's rows of the mask against the tile's keys, whatever
        # the head
        in_specs += [pl.BlockSpec(
            (1, block_q, tile),
            lambda bi, hi, qi, ti: (bi, qi, held(qi, ti)))]
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=plan.scale,
                          causal=plan.causal, block_k=block_k,
                          chains=plan.chains, choice=chosen, window=window,
                          blocks=plan.blocks),
        grid=grid,
        in_specs=in_specs,
        out_specs=[by_query(d_v), by_query(1)],
        out_shape=[_pallas.out((b, h, s, d_v), out_dtype, q, k, v),
                   _pallas.out((b, h, s, 1), jnp.float32, q, k, v)],
        scratch_shapes=[pltpu.VMEM((block_q, d_v), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32)],
        compiler_params=_compiler_params("fwd", block_q, block_k, d + e,
                                         q.dtype.itemsize, tile, s, d_v,
                                         chosen),
        interpret=plan.interpret,
        name="hvt_flash_fwd",
    )(q, k, v, *(rotated or ()), *((choice,) if chosen else ()))
    return o, lse


def _flash_fwd(q, k, v, rotated, choice, scale, causal, block_q, block_k,
               out_dtype, window=None, blocks=None):
    o, lse = _flash_fwd_impl(q, k, v, rotated, choice, scale, causal,
                             block_q, block_k, out_dtype, window, blocks)
    return (o, lse), (q, k, v, rotated, choice, o, lse)


def _flash_bwd(scale, causal, block_q, block_k, out_dtype, window, blocks,
               res, cot):
    do, dlse = cot
    q, k, v, rotated, choice, o, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)        # [B, H, S, 1]
    # lse cotangent: ds gains + P∘dlse, i.e. delta shifts by −dlse
    delta = delta - dlse.astype(jnp.float32)
    return _bwd_call(q, k, v, do, lse, delta, rotated, choice,
                     plan=_plan("bwd", q, scale, causal, block_q, block_k,
                                v.shape[-1], _rotated_width(rotated),
                                choice is not None, window, blocks))


@functools.partial(jax.jit, static_argnames="plan")
def _bwd_call(q, k, v, do, lse, delta, rotated=None, choice=None, *, plan):
    """dq, dk, dv: grid (b, h, ki, ti) — Q/dO/lse/delta tiles stream past
    each K/V block (the reduction axis must be LAST), and every score
    sub-block is made once for all three. dk/dv accumulate a K block;
    dq accumulates over the K blocks in a float32 VMEM scratch of the
    whole sequence and leaves a tile at a time while the last K block
    passes: until then its block index stays where it is, so nothing is
    written back. A causal K block's steps before the tile it begins in
    compute nothing and name that tile, so nothing is fetched for them.
    Under GQA the kernel reads the shared K/V head
    zero-copy via the index map but emits per-QUERY-head dk/dv (full h),
    which are then group-summed — each K/V head's gradient is the sum
    over its query group. A shared rotated key's gradient goes the same
    way: the kernel emits a head's, and the heads' sum is the key's.
    A choice has no gradient: the fifth result is None."""
    b, h, s, d = q.shape
    d_v, e = v.shape[-1], _rotated_width(rotated)
    block_q, block_k, tile = plan.block_q, plan.block_k, plan.tile
    chosen = choice is not None
    window = plan.window
    _count_trace("bwd" + "_choice" * chosen + _mask_word(plan.blocks),
                 block_q, block_k, plan.derived,
                 d + e, d_v, e, plan.chains, window)
    group = h // k.shape[1]
    n_k = s // block_k
    # q, k, dq and dk are ``d`` wide, v, do and dv ``d_v``
    kv_in_ki = lambda width: pl.BlockSpec(
        (1, 1, block_k, width),
        lambda bi, hi, ki, ti: (bi, hi // group, ki, 0))
    dkv_out_ki = lambda width: pl.BlockSpec(
        (1, 1, block_k, width), lambda bi, hi, ki, ti: (bi, hi, ki, 0))
    # A causal K block sees nothing of the tiles before the one it begins
    # in: those grid steps compute nothing, and with the tile's own index
    # they would still fetch it (q, dO, lse, delta and a choice's rows,
    # about 10 us a step that nothing hides: PERF.md section 6, PR 54). So
    # they name the first tile the block does see, which the pipeline
    # fetches once and keeps until the grid has passed it.
    # With a window the last axis is as long as a K block's band (the
    # tiles whose rows see a key of it) and a step names its tile of the
    # band, the steps past the band's end the last again; and a tile of
    # dQ leaves when the last K block that its rows see has passed it,
    # which is the block that ends where the tile ends, at its band's
    # first step: until then the index stays at the next tile to leave.
    def seen(ki, ti):
        if window is not None:
            first, last = _band("bwd", ki, block_k, tile, window)
            return jnp.minimum(first + ti, jnp.minimum(last, s // tile - 1))
        return jnp.maximum(ti, ki * block_k // tile) if plan.causal else ti

    def leaving(ki, ti):
        if window is None:
            return jnp.where(ki == n_k - 1, ti, 0)
        blocks = tile // block_k
        return jnp.where(ti == 0, ki // blocks, jnp.minimum(
            (ki + 1) // blocks, s // tile - 1))

    q_tile = lambda width: pl.BlockSpec(
        (1, 1, tile, width), lambda bi, hi, ki, ti: (bi, hi, seen(ki, ti), 0))
    dq_tile = lambda width: pl.BlockSpec(
        (1, 1, tile, width),
        lambda bi, hi, ki, ti: (bi, hi, leaving(ki, ti), 0))
    operands = (q, k, v, do, lse, delta)
    inputs = (k, v, q, do, lse, delta)
    in_specs = [kv_in_ki(d), kv_in_ki(d_v), q_tile(d), q_tile(d_v),
                q_tile(1), q_tile(1)]
    out_specs = [dq_tile(d), dkv_out_ki(d), dkv_out_ki(d_v)]
    out_shape = [_pallas.out(q.shape, q.dtype, *operands),
                 _pallas.out(q.shape, k.dtype, *operands),
                 _pallas.out((b, h, s, d_v), v.dtype, *operands)]
    scratch = [(s, d), (block_k, d), (block_k, d_v)]
    if rotated is not None:
        # k_r's block whatever the head and q_r's tile in; dq_r and a
        # head's dk_r out, each beside what it is a part of
        q_r, k_r = rotated
        inputs += (k_r, q_r)
        in_specs += [pl.BlockSpec((1, block_k, e),
                                  lambda bi, hi, ki, ti: (bi, ki, 0)),
                     q_tile(e)]
        out_specs += [dq_tile(e), dkv_out_ki(e)]
        out_shape += [_pallas.out(q_r.shape, q_r.dtype, *operands),
                      _pallas.out(q_r.shape, k_r.dtype, *operands)]
        scratch += [(s, e), (block_k, e)]
    if chosen:
        # the tile's rows of the mask against the block's keys
        inputs += (choice,)
        in_specs += [pl.BlockSpec(
            (1, tile, block_k),
            lambda bi, hi, ki, ti: (bi, seen(ki, ti), ki))]
    dq, dk, dv, *d_rotated = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=plan.scale,
                          causal=plan.causal, block_q=block_q,
                          chains=plan.chains, choice=chosen, window=window,
                          blocks=plan.blocks),
        grid=(b, h, n_k, s // tile if window is None
              else _band_steps("bwd", s, block_k, tile, window)),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        compiler_params=_compiler_params("bwd", block_q, block_k, d + e,
                                         q.dtype.itemsize, tile, s, d_v,
                                         chosen),
        interpret=plan.interpret,
        name="hvt_flash_bwd",
    )(*inputs)
    if group > 1:
        h_kv = h // group
        dk = dk.astype(jnp.float32).reshape(
            b, h_kv, group, s, d).sum(axis=2).astype(k.dtype)
        dv = dv.astype(jnp.float32).reshape(
            b, h_kv, group, s, -1).sum(axis=2).astype(v.dtype)
    if rotated is None:
        return dq, dk, dv, None, None
    dq_r, dk_r = d_rotated
    return dq, dk, dv, (dq_r, dk_r.astype(jnp.float32).sum(axis=1).astype(
        dk_r.dtype)), None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, q_r=None, k_r=None, choice=None, causal=True,
                    window=None, blocks=None, strict=False, scale=None,
                    block_q=None, block_k=None):
    """Fused multi-head attention.

    Args:
      q, k: [batch, seq, heads, head_dim] (BSHD, matching
        :mod:`horovod_tpu.models.transformer`); ``k`` may have fewer
        heads (grouped queries).
      v: [batch, seq, k's heads, value_dim]: a value width apart from
        the query-key width (latent attention: 192 on 128) or the same.
      q_r, k_r: None, or a second part of the queries and keys that the
        caller did not put beside the first (latent attention's rotated
        pair): ``q_r [batch, seq, heads, e]`` and **one key a position
        that every head reads, ``k_r [batch, seq, e]``**. The score is
        ``q k^T + q_r k_r^T`` inside the kernels, the softmax scale's
        default ``(head_dim + e) ** -0.5``, and ``k_r``'s gradient the
        sum over the heads (``flash_attention_with_lse``).
      choice: None, or which keys each query sees, ``[batch, seq, seq]``
        int8, 1 at a key the row attends and 0 elsewhere, the same for
        every head (attention over keys an indexer chose). It takes the
        place of the causal mask, so it holds the causal limit itself
        where there is one; ``causal`` still says which tiles no row can
        see. No gradient.
      causal: apply causal masking.
      window: None, or how many keys a query sees, itself among them: row
        ``t`` attends ``t - window < s <= t`` (sliding-window attention).
        A static count; needs ``causal``. Neither kernel visits a tile
        that lies wholly outside the band
        (``flash_attention_with_lse``).
      blocks, strict: None, or the length of the blocks the causal limit
        goes by (block diffusion): row ``t`` sees the keys of its own block
        ``t // blocks`` and of those before it, the whole of its own block
        in both directions; ``strict``: of those before it alone, so the
        first block's rows see nothing and come back as zero rows with
        ``lse = -inf``. A static length; needs ``causal``; the kernels walk
        the causal tiles and no other (``flash_attention_with_lse``).
      scale: softmax scale, default ``head_dim ** -0.5``.
      block_q / block_k: the score tile; ``None`` (the default) derives
        it from the shape, one tile a kernel (``_derive_tile``); an
        integer is honoured, clipped to divide seq.

    Returns [batch, seq, heads, value_dim] in q.dtype. Differentiable
    (custom VJP with a recompute-based backward kernel).
    """
    o, _ = flash_attention_with_lse(q, k, v, q_r=q_r, k_r=k_r, choice=choice,
                                    causal=causal, window=window,
                                    blocks=blocks, strict=strict,
                                    scale=scale, block_q=block_q,
                                    block_k=block_k)
    return o


def flash_attention_with_lse(q, k, v, *, q_r=None, k_r=None, choice=None,
                             causal=True, window=None, blocks=None,
                             strict=False, scale=None, block_q=None,
                             block_k=None, out_dtype=None):
    """Fused attention returning ``(o, lse)``; both are differentiable.
    ``q`` and ``k`` share one width and ``v`` and ``o`` another, which may
    be the same (``flash_attention``).

    With ``q_r [b, s, h, e]`` and ``k_r [b, s, e]`` the score is ``q k^T +
    q_r k_r^T``, two products summed in float32 a score tile, so a caller
    whose queries and keys come in two parts (latent attention: 128
    columns a head beside 64 rotated ones, the rotated key one a position)
    never writes them side by side. The tile, the streamed tile and the
    VMEM limit are those of one width of ``d + e``; ``k`` has q's heads
    then (grouped queries with a rotated pair are not built).

    With ``choice [b, s, s]`` int8 a query row sees the keys its row of the
    mask marks and no other: the mask is applied where the causal mask
    would be, in the forward kernel and in the backward kernel, every head
    reading the same tile of it; ``lse`` is then over the chosen keys. A
    row has to see at least one key. Grouped queries are served as without
    one; a rotated pair beside a choice is not built.

    With ``window`` (a static count of keys, beside ``causal``) row ``t``
    sees the keys ``t - window < s <= t``: the mask gains its second bound
    and so does the schedule. The forward's query block walks the
    sub-blocks from the one holding ``q0 - window + 1`` to the diagonal's;
    the backward's K block the query sub-blocks up to the one holding ``k1
    + window - 2``. The streamed tile is one sub-block long, the grid's
    last axis as long as a block's band (``_band_steps``) and not as the
    sequence, and a step's block index is its tile of the band, so what
    lies outside is neither computed nor fetched. A tile of dQ leaves as
    soon as the last K block its rows see has passed. A window no shorter
    than the sequence hides nothing, and the results are those of the call
    without one to the last bit. Refused: a window without ``causal``,
    beside a choice, beside a rotated pair.

    With ``blocks`` (a static length, beside ``causal``) the causal limit
    goes by blocks of that many positions: row ``t`` sees the keys ``s``
    with ``s // blocks <= t // blocks``, the whole of its own block in both
    directions, or, ``strict``, ``s // blocks < t // blocks``: the blocks
    before its own alone. The two forms are what a block-diffusion
    objective asks of one sequence's clean rows and of its noised rows on
    the clean keys (``models/transformer.py``). **The schedule is
    ``causal``'s**: ``blocks`` has to divide every piece either kernel
    walks by (the query and key blocks of both, the halves a pass is taken
    by), so every piece's edge is a block's edge, no row sees a key past
    its query block's last row, and the tiles with a visible pair are the
    causal ones exactly: the held steps, the halves and the backward's
    skipped pieces are what they are without it, and neither kernel
    computes or fetches a tile no row of which sees a key. In the strict
    form a row of the first block sees nothing: its output is a zero row,
    its ``lse`` is ``-inf`` and it has no gradient (what is masked is
    ``-inf`` and not the causal mask's finite fill, which would give such a
    row the mean of the keys it does not see). Refused: ``blocks`` without
    ``causal``, beside a window, a choice or a rotated pair, a length that
    does not divide the pieces, and the strict form at a length that is a
    whole piece (its diagonal tiles would hold no visible pair).

    ``lse[b, s, h]`` is the log-sum-exp of the (scaled, masked) scores for
    each query — exactly what blockwise/ring composition needs to combine
    partial attention outputs: given per-block ``(o_i, lse_i)``, the total
    is ``o = Σ_i exp(lse_i − logaddexp_i lse_i) · o_i``
    (``parallel/sequence.py`` ring attention uses this).

    ``out_dtype`` (default ``q.dtype``): dtype o is written in. Blockwise
    consumers should pass ``jnp.float32`` so the fp32 accumulator reaches
    the combine unrounded; the matmuls still run on bf16 operands.
    """
    b, s, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(
            f"GQA requires n_heads ({h}) divisible by n_kv_heads "
            f"({h_kv})")
    if k.shape[-1] != d or v.shape[:-1] != k.shape[:-1]:
        raise ValueError(
            f"q {q.shape} and k {k.shape} share the query-key width, and "
            f"v {v.shape} has k's positions and heads")
    e = 0
    if q_r is not None or k_r is not None:
        if q_r is None or k_r is None:
            raise ValueError(
                "a rotated pair is q_r and k_r together: got "
                f"{'k_r' if q_r is None else 'q_r'} alone")
        e = q_r.shape[-1]
        if q_r.shape != (b, s, h, e) or k_r.shape != (b, s, e):
            raise ValueError(
                f"q_r {q_r.shape} has q's {(b, s, h)} and a width of its "
                f"own, and k_r {k_r.shape} that width at every position, "
                f"without a head axis")
        if h_kv != h:
            raise ValueError(
                f"a rotated pair goes with as many key heads as query "
                f"heads; got {h} on {h_kv}")
    if choice is not None:
        if e:
            raise ValueError(
                "a choice of keys beside a rotated pair is not built: pass "
                "q and k whole, or no choice")
        if choice.shape != (b, s, s) or choice.dtype != jnp.int8:
            raise ValueError(
                f"a choice is int8 [batch, seq, seq] = {(b, s, s)}, one row "
                f"of keys a query for every head; got {choice.dtype} "
                f"{choice.shape}")
    if window is not None:
        if isinstance(window, bool) or not isinstance(window, int) or (
                window < 1):
            raise ValueError(
                f"a window is a static count of keys, at least 1 (the "
                f"query's own position); got {window!r}")
        if not causal:
            raise ValueError(
                "a window without causal is not built: the window is the "
                "last `window` of the causal keys")
        if choice is not None:
            raise ValueError(
                "a window beside a choice of keys is not built: put the "
                "window into the choice, or pass no choice")
        if e:
            raise ValueError(
                "a window beside a rotated pair is not built: pass q and k "
                "whole, or no window")
    if scale is None:
        scale = (d + e) ** -0.5
    bq, bk, _ = _score_tile("fwd", s, d + e, q.dtype.itemsize, causal,
                             block_q, block_k, v.shape[-1],
                             choice is not None)
    if blocks is None:
        if strict:
            raise ValueError("strict is a form of blocks: pass the blocks' "
                             "length, or no strict")
    else:
        if isinstance(blocks, bool) or not isinstance(blocks, int) or (
                blocks < 1):
            raise ValueError(
                f"blocks is a static length in positions, at least 1; got "
                f"{blocks!r}")
        for beside, what in ((not causal, "without causal"),
                             (window is not None, "beside a window"),
                             (choice is not None, "beside a choice of keys"),
                             (e, "beside a rotated pair")):
            if beside:
                raise ValueError(
                    f"blocks {what} is not built: the blocks are the causal "
                    f"limit's own, and the schedule the causal one")
        piece = _smallest_piece(s, d, q.dtype.itemsize, v.shape[-1], block_q,
                                block_k)
        if piece % blocks or (strict and piece == blocks):
            raise ValueError(
                f"blocks of {blocks} positions divide no sub-block: the "
                f"kernels walk {s} positions by pieces of {piece}, each of "
                f"which has to be whole blocks"
                + (", and more than one in the strict form" if strict
                   else ""))
        blocks = (blocks, bool(strict))
    if not _pallas.interpret() and (bq % 8 or bk % 8):
        # Mosaic refuses the kernel ("cannot statically prove that index
        # in dimension 2 is a multiple of 8"); the interpreter has no
        # such limit, so name it here rather than inside the compiler
        raise ValueError(
            f"flash attention compiles only with sequence blocks that "
            f"are multiples of 8: sequence length {s} clips the blocks "
            f"to ({bq}, {bk}). Pad the sequence to a multiple "
            f"of 8 or use the einsum path (use_flash=False)")
    # Kernels are gridded (batch, head, block): BHSD layout.
    to_bhsd = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    o, lse = _flash(to_bhsd(q), to_bhsd(k), to_bhsd(v),
                    None if q_r is None else (to_bhsd(q_r), k_r), choice,
                    float(scale), bool(causal), block_q, block_k,
                    jnp.dtype(out_dtype or q.dtype), window, blocks)
    if strict:
        # a row that saw nothing left the kernel with its statistics as
        # they began (``m = _NEG_INF``, ``l = 0``): no key, ``-inf``
        lse = jnp.where(lse > _NEG_INF, lse, -jnp.inf)
    # lse: [B, H, S, 1] → [B, S, H]
    return jnp.transpose(o, (0, 2, 1, 3)), jnp.transpose(lse[..., 0],
                                                         (0, 2, 1))
