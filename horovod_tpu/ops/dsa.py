"""What attention over keys that a learned indexer chooses needs beside
the flash kernels (``models/dsa.py``; DeepSeek's published sparse
attention): the index scores, the choice of the ``topk`` best causal keys
a query, and the indexer's own loss, each as a Pallas kernel with the
plain ``jax.numpy`` body that is its reference and the path off a TPU.

``index_scores``: ``I(t, u) = sum_j w_j(t) relu(q^I_j(t) . k^I(u))`` for
``u <= t`` and ``-inf`` above the diagonal, ``[b, s, s]`` float32. One
index key a position, ``J`` index heads: a ``[block_q, block_k]`` tile is
``J`` products ``e`` deep summed in float32, and a tile above the diagonal
is filled without a product.

``choose``: of every row of those scores the mask ``[b, s, s]`` int8 of
``S_t``: the ``topk`` largest of the causal keys, **equal scores to the
lower position** (``jax.lax.top_k``'s order), every causal key where there
are no more than ``topk``; **and beside it ``lse_i [b, s, 1]``, the
scores' log-sum-exp over ``S_t``**, which the indexer's loss needs and
which is there for the taking while the scores are held. ``jax.lax.top_k``
sorts every row, and the router's walk of ``ops/kth_largest.py`` takes
``k`` passes; at 2,048 of 16,384 neither will do. The kernel finds the
``topk``-th largest **by bisection on the scores' bit patterns**: a
float32's bits, the negative ones' order turned, compare as integers as
the numbers do, so 32 passes of "how many keys of the row are at or above
this pattern", each a comparison and a count **over the causal columns of
a block of rows** that never leaves VMEM, build the threshold bit by bit,
whatever ``topk`` is. A block of rows ``[t0, t0 + rows)`` has causal keys
in the columns below ``t0 + rows`` alone: every pass is a loop over those
columns in whole chunks, so a sequence's passes walk half of what lies
in VMEM (``hvt_dsa_kernel_traces_total``'s ``columns``), and the mask past
them is written as zeros. Exact: comparisons and counts alone, no
arithmetic on a score. The keys above the threshold are chosen, and of
those equal to it the first that are still needed, by a running count
along the row; the same walk adds ``exp(score - maximum)`` of the chosen
in float32, the maximum being the row's largest causal score, which is
always chosen. The causal limit is inside: a key above the diagonal takes
the least pattern and is never handed on.

``index_loss``: the KL term that trains the indexer, ``mean_t sum_{u in
S_t} pbar (log pbar - log r)`` with ``pbar`` the main attention's
probabilities averaged over the heads (from its queries, keys and saved
log-sum-exp, all detached) and ``r`` the indexer's softmax over ``S_t``. A
flash kernel never writes a probability, so the kernel makes each
``[block_q, block_k]`` tile of ``pbar`` again from ``q``, ``k`` and
``lse`` (one product a head), the tile of ``I`` from the indexer's parts,
and **beside the value the three gradients**, since ``dL/dI = (r - pbar)``
on ``S_t`` is there in the same tile: ``dw``, ``dq^I`` and ``dk^I`` leave
with the loss (as ``ops/losses.py``'s chunked loss keeps its gradients),
divided by the rows here as the value is: ``index_loss`` returns ``(value,
gradients of that value)`` and has no derivative itself. The caller owns
what becomes of them: the mixer pulls them back to its leaves in the same
pass and ties the result to the value with ``with_gradient``, whose
backward rule does the one thing left, scaling by the cotangent. Nothing
``[s, s]`` wide is written.

The mixer takes all of these or none, by the flash kernels' rule
(``resolve_flash``): there is no second rule here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import _pallas
from horovod_tpu.ops._pallas import NN, NT, TN, dot

LANES = 128
_INT_MIN = -2 ** 31
# Score tiles of the index-score kernel and of the loss's, rows of scores a
# step of the choice holds ([rows, s] float32 in, the patterns beside them,
# int8 out), and the columns of them one turn of a pass's loop takes (a
# turn's 64 vregs beside 8 of counts and 8 of the trial level: nothing
# spills, and the compare, convert and add fill the vector unit's slots).
INDEX_TILE = (256, 1024)
LOSS_TILE = (256, 512)
CHOICE_ROWS = 64
CHOICE_CHUNK = 1024
_XLA_VMEM = 3 * 2 ** 20


def _count_trace(kernel, columns=0, **labels):
    """``columns``: the choice's alone, ``_walked``."""
    _pallas.count_trace(
        "hvt_dsa_kernel_traces_total",
        "sparse-attention indexer kernels (index scores, choice, indexer "
        "loss) traced into compiled programs (counted per trace, not per "
        "execution)", kernel=kernel, **labels, columns=columns)


def _causal(q0, k0, shape):
    return (k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            <= q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0))


def _to_bhsd(x):
    return jnp.transpose(x, (0, 2, 1, 3))


# ------------------------------------------------------------- index scores

def index_scores_plain(q_i, k_i, w):
    """``q_i [b, s, J, e]``, ``k_i [b, s, e]``, ``w [b, s, J]`` -> ``I [b,
    s, s]`` float32, ``-inf`` above the diagonal."""
    s = q_i.shape[1]
    each = jax.nn.relu(jnp.einsum("bqje,bke->bqjk", q_i, k_i,
                                  preferred_element_type=jnp.float32))
    scores = jnp.einsum("bqj,bqjk->bqk", w.astype(jnp.float32), each)
    return jnp.where(_causal(0, 0, (s, s)), scores, -jnp.inf)


def _index_kernel(q_ref, k_ref, w_ref, o_ref):
    heads, block_q = q_ref.shape[1:3]
    block_k = k_ref.shape[1]
    q0, k0 = pl.program_id(1) * block_q, pl.program_id(2) * block_k
    below = k0 < q0 + block_q

    @pl.when(below)
    def _scores():
        k = k_ref[0]
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for j in range(heads):
            acc = acc + w_ref[0, :, j:j + 1] * jnp.maximum(
                dot(q_ref[0, j], k, NT), 0.0)
        o_ref[0] = jnp.where(_causal(q0, k0, acc.shape), acc, -jnp.inf)

    @pl.when(jnp.logical_not(below))
    def _above():
        o_ref[0] = jnp.full((block_q, block_k), -jnp.inf, jnp.float32)


@functools.partial(jax.jit, static_argnames="interpret")
def _index_call(q_i, k_i, w, *, interpret):
    b, heads, s, e = q_i.shape
    block_q = _pallas.largest(s, INDEX_TILE[0], LANES)
    block_k = _pallas.largest(s, INDEX_TILE[1], LANES)
    _count_trace("index", heads=heads, width=e, seq=s, topk=0)
    return pl.pallas_call(
        _index_kernel, grid=(b, s // block_q, s // block_k),
        in_specs=[
            pl.BlockSpec((1, heads, block_q, e), lambda bi, qi, ki: (bi, 0, qi, 0)),
            pl.BlockSpec((1, block_k, e), lambda bi, qi, ki: (bi, ki, 0)),
            pl.BlockSpec((1, block_q, heads), lambda bi, qi, ki: (bi, qi, 0))],
        out_specs=pl.BlockSpec((1, block_q, block_k),
                               lambda bi, qi, ki: (bi, qi, ki)),
        out_shape=_pallas.out((b, s, s), jnp.float32, q_i, k_i, w),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret, name="hvt_dsa_index")(q_i, k_i, w)


def index_scores(q_i, k_i, w):
    """``index_scores_plain`` through the kernel (a sequence in whole
    128-lane tiles). No gradient: the indexer learns through
    ``index_loss``, and the choice has none."""
    return _index_call(_to_bhsd(q_i), k_i,
                       w.astype(jnp.float32), interpret=_pallas.interpret())


# ------------------------------------------------------------------- choice

def choose_plain(scores, topk: int):
    """``scores [b, s, s]`` (what is above the diagonal is not read) ->
    ``(choice [b, s, s] int8, lse_i [b, s, 1] float32)``: 1 at the keys of
    ``S_t``, and the scores' log-sum-exp over them. The ``topk``-th largest
    is ``jax.lax.top_k``'s last value (its first the log-sum-exp's
    maximum); the keys equal to it are taken from the lowest position up
    until ``topk`` are chosen."""
    s = scores.shape[-1]
    causal = _causal(0, 0, (s, s))
    scores = jnp.where(causal, scores.astype(jnp.float32), -jnp.inf)
    k = min(topk, s)
    best = jax.lax.top_k(scores, k)[0]
    most, kth = best[..., :1], best[..., -1:]
    above, level = scores > kth, scores == kth
    needed = k - jnp.sum(above, -1, keepdims=True)
    chosen = (above | (level & (jnp.cumsum(level, -1) <= needed))) & causal
    # the row's largest score is always chosen: the log-sum-exp's maximum
    lse_i = most + jnp.log(jnp.sum(
        jnp.where(chosen, jnp.exp(scores - most), 0.0), -1, keepdims=True))
    return chosen.astype(jnp.int8), lse_i


def _walked(seq, rows, chunk):
    """Column tiles of 128 that one of the choice's passes walks in a
    sequence, summed over its row blocks: block ``i`` goes by the columns
    below its diagonal in whole chunks, ``[0, hi)``. Walking every column
    is ``seq // rows * seq // LANES``."""
    return sum(-(-(t0 + rows) // chunk) * chunk // LANES
               for t0 in range(0, seq, rows))


def _choice_kernel(x_ref, o_ref, lse_ref, keys_ref, *, topk, seq, chunk):
    rows = x_ref.shape[0]
    # the block's rows are positions [t0, t0 + rows) of one sequence, so
    # its causal keys lie in the columns [0, hi), hi = chunks * chunk.
    # Nothing past them is read.
    t0 = (pl.program_id(0) * rows) % seq
    t = t0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    chunks = (t0 + rows + chunk - 1) // chunk

    def over(body, init):
        """One pass over the columns [0, hi), a chunk at a time: ``body``
        of the chunk's first column and of what the pass carries."""
        return jax.lax.fori_loop(0, chunks, lambda c, carry: body(
            pl.multiple_of(c * chunk, chunk), carry), init)

    def by_lane(x, op):
        # a chunk's lane tiles onto one: whole vregs, nothing crosses lanes
        return functools.reduce(
            op, [x[:, at:at + LANES] for at in range(0, chunk, LANES)])

    def pattern(u0, most):
        at = pl.ds(u0, chunk)
        x = x_ref[:, at]
        seen = u0 + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) <= t
        bits = pltpu.bitcast(x, jnp.int32)
        bits = jnp.where(bits == _INT_MIN, 0, bits)     # -0.0 is 0.0
        # a float32's bits ordered as the numbers are: the negative ones'
        # order turned; a key above the diagonal below them all
        keys_ref[:, at] = jnp.where(
            seen, jnp.where(bits < 0, bits ^ 0x7fffffff, bits), _INT_MIN)
        # the row's largest causal score is always chosen: the maximum of
        # the log-sum-exp over the chosen keys
        return jnp.maximum(most, by_lane(jnp.where(seen, x, -jnp.inf),
                                         jnp.maximum))

    most = jnp.max(over(pattern, jnp.full((rows, LANES), -jnp.inf,
                                          jnp.float32)),
                   axis=1, keepdims=True)
    most = jnp.where(jnp.abs(most) == jnp.inf, 0.0, most)

    def count(reaches):
        """How many keys of each row ``reaches`` holds of: added lane by
        lane, summed across the lanes once a pass (float32 holds a count
        of columns exactly)."""
        each = over(lambda u0, acc: acc + by_lane(
            reaches(keys_ref[:, pl.ds(u0, chunk)]).astype(jnp.int32),
            jnp.add), jnp.zeros((rows, LANES), jnp.int32))
        return jnp.sum(each.astype(jnp.float32), axis=1, keepdims=True)

    def bit(n, kth):
        # the next bit from the top: kept where topk keys still reach it
        trial = kth ^ jnp.left_shift(jnp.int32(1), 31 - n)
        return jnp.where(count(lambda keys: keys >= trial) >= topk, trial,
                         kth)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.full((rows, 1), _INT_MIN, jnp.int32))
    needed = topk - count(lambda keys: keys > kth)
    upto = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
            <= jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
            ).astype(jnp.bfloat16)

    def walk(u0, carry):
        # of the keys equal to the topk-th, the first still needed: a
        # count along the row, 128 lanes at a time (0 and 1 summed on the
        # MXU in float32: exact; a chunk's products and their last
        # columns wait for nothing, only the sums go from tile to tile);
        # and exp(score - maximum) of the chosen, added lane by lane
        before, total = carry
        for u in range(0, chunk, LANES):
            at = pl.ds(u0 + u, LANES)
            keys = keys_ref[:, at]
            level = keys == kth
            within = dot(level.astype(jnp.bfloat16), upto, NN)
            seen = u0 + u + jax.lax.broadcasted_iota(
                jnp.int32, keys.shape, 1) <= t
            chosen = ((keys > kth)
                      | (level & (before + within <= needed))) & seen
            o_ref[:, at] = jnp.where(chosen, 1, 0).astype(jnp.int8)
            before = before + within[:, LANES - 1:]
            total = total + jnp.where(
                chosen, jnp.exp(x_ref[:, at] - most), 0.0)
        return before, total

    _, total = over(walk, (jnp.zeros((rows, 1), jnp.float32),
                           jnp.zeros((rows, LANES), jnp.float32)))
    lse_ref[...] = most + jnp.log(jnp.sum(total, axis=1, keepdims=True))

    def above(c, _):
        o_ref[:, pl.ds(pl.multiple_of(c * chunk, chunk), chunk)] = jnp.zeros(
            (rows, chunk), jnp.int8)

    jax.lax.fori_loop(chunks, seq // chunk, above, None)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def _choice_call(scores, *, topk, interpret):
    """``scores [n, s]``: rows of queries against ``s`` keys, row ``i`` the
    query at position ``i % s`` (whole sequences one after the other).
    The mask ``[n, s]`` int8 and the log-sum-exp over it ``[n, 1]``."""
    n, s = scores.shape
    rows = _pallas.largest(s, CHOICE_ROWS, 32)
    chunk = _pallas.largest(s, CHOICE_CHUNK, LANES)
    _count_trace("choice", heads=0, width=0, seq=s, topk=topk,
                 columns=_walked(s, rows, chunk))
    # the scores and the mask double-buffered, the patterns, and a few
    # chunks' values of a pass
    limit = rows * s * (2 * 4 + 4 + 2 * 1) + 16 * rows * chunk * 4 + _XLA_VMEM
    return pl.pallas_call(
        functools.partial(_choice_kernel, topk=min(topk, s), seq=s,
                          chunk=chunk),
        grid=(n // rows,),
        in_specs=[pl.BlockSpec((rows, s), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, s), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[_pallas.out((n, s), jnp.int8, scores),
                   _pallas.out((n, 1), jnp.float32, scores)],
        scratch_shapes=[pltpu.VMEM((rows, s), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(limit, 16 * 2 ** 20)),
        interpret=interpret, name="hvt_dsa_choice")(scores)


def choose(scores, topk: int):
    """``choose_plain`` through the kernel, for float32 scores of a
    sequence in whole 128-lane tiles. No gradient."""
    b, s, _ = scores.shape
    choice, lse_i = _choice_call(
        scores.astype(jnp.float32).reshape(b * s, s), topk=int(topk),
        interpret=_pallas.interpret())
    return choice.reshape(b, s, s), lse_i.reshape(b, s, 1)


# ------------------------------------------------------- the indexer's loss

def index_loss_plain(q, k, lse, q_i, k_i, w, choice, scale):
    """``mean_t sum_{u in S_t} pbar (log pbar - log r)``, differentiable
    by ``q_i``, ``k_i`` and ``w`` alone. ``q [b, s, H, d]``, ``k [b, s,
    H_kv, d]`` and ``lse [b, s, H]`` are the main attention's (detached
    here), ``choice [b, s, s]`` the mask of ``S_t``."""
    q, k, lse = (jax.lax.stop_gradient(t) for t in (q, k, lse))
    group = q.shape[2] // k.shape[2]
    seen = choice != 0
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, group, axis=2),
                        preferred_element_type=jnp.float32) * scale
    probs = jnp.exp(scores - jnp.transpose(lse, (0, 2, 1))[..., None])
    target = jnp.where(seen, jnp.mean(probs, axis=1), 0.0)
    log_r = jax.nn.log_softmax(
        jnp.where(seen, index_scores_plain(q_i, k_i, w), -jnp.inf), -1)
    each = jnp.where(target > 0, target * (
        jnp.log(jnp.where(target > 0, target, 1.0))
        - jnp.where(seen, log_r, 0.0)), 0.0)
    return jnp.mean(jnp.sum(each, axis=-1))


def _loss_kernel(q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, lsei_ref,
                 m_ref, kl_ref, dq_ref, dw_ref, dk_ref, *, scale):
    heads, block_q = q_ref.shape[1:3]
    group = heads // k_ref.shape[1]
    index_heads = qi_ref.shape[1]
    block_k = ki_ref.shape[1]
    qb, kb = pl.program_id(1), pl.program_id(2)
    keys = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)

    @pl.when((qb == 0) & (kb == 0))
    def _init_dk():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(kb == 0)
    def _init():
        kl_ref[...] = jnp.zeros_like(kl_ref)
        dq_ref[...] = jnp.zeros_like(dq_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(kb * block_k < (qb + 1) * block_q)
    def _tile():
        seen = m_ref[0].astype(jnp.int32) != 0
        target = jnp.zeros((block_q, block_k), jnp.float32)
        for h in range(heads):
            sc = dot(q_ref[0, h], k_ref[0, h // group], NT) * scale
            target = target + jnp.exp(sc - lse_ref[0, :, h:h + 1])
        target = jnp.where(seen, target * (1.0 / heads), 0.0)
        k_i = ki_ref[0]
        index = jnp.zeros((block_q, block_k), jnp.float32)
        for j in range(index_heads):
            index = index + w_ref[0, :, j:j + 1] * jnp.maximum(
                dot(qi_ref[0, j], k_i, NT), 0.0)
        log_r = index - lsei_ref[0]
        kl_ref[0] += jnp.sum(jnp.where(
            target > 0, target * (jnp.log(jnp.maximum(target, 1e-38))
                                  - log_r), 0.0), axis=1, keepdims=True)
        # dL/dI on S_t (the rows' 1/s comes outside)
        g = jnp.where(seen, jnp.exp(log_r), 0.0) - target
        k_32 = k_i.astype(jnp.float32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, index_heads), 1)
        dw = jnp.zeros((block_q, index_heads), jnp.float32)
        dk = jnp.zeros((block_k, k_i.shape[1]), jnp.float32)
        for j in range(index_heads):
            q_j = qi_ref[0, j]
            sc = dot(q_j, k_i, NT)
            dw = dw + jnp.where(lane == j, jnp.sum(
                g * jnp.maximum(sc, 0.0), axis=1, keepdims=True), 0.0)
            g_j = jnp.where(sc > 0, g * w_ref[0, :, j:j + 1], 0.0)
            dq_ref[0, j] += dot(g_j, k_32, NN)
            dk = dk + dot(g_j, q_j.astype(jnp.float32), TN)
        dw_ref[0] += dw
        dk_ref[0, keys, :] += dk


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _loss_call(q, k, lse, q_i, k_i, w, lse_i, choice, *, scale, interpret):
    """``kl [b, s, 1]`` and ``dq_i [b, J, s, e]``, ``dw [b, s, J]`` and
    ``dk_i [b, s, e]`` of its sum, float32."""
    b, heads, s, d = q.shape
    kv_heads = k.shape[1]
    index_heads, e = q_i.shape[1], q_i.shape[3]
    block_q = _pallas.largest(s, LOSS_TILE[0], LANES)
    block_k = _pallas.largest(s, LOSS_TILE[1], LANES)
    _count_trace("loss", heads=index_heads, width=e, seq=s, topk=0)
    # a block of queries of an operand [b, s, n], and of one [b, h, s, n]
    by_query = lambda n: pl.BlockSpec((1, block_q, n),
                                      lambda bi, qi, ki: (bi, qi, 0))
    by_head = lambda h, n: pl.BlockSpec((1, h, block_q, n),
                                        lambda bi, qi, ki: (bi, 0, qi, 0))
    operands = (q, k, lse, q_i, k_i, w, lse_i, choice)
    # the blocks double-buffered (an operand's position takes whole
    # 128-lane rows), the whole-sequence dk_i, and a dozen [block_q,
    # block_k] float32 values of a tile alive at once
    lanes = lambda n: -(-n // LANES) * LANES
    limit = (2 * 2 * block_q * (heads * lanes(d) + index_heads * lanes(e))
             + 2 * 2 * block_k * (kv_heads * lanes(d) + lanes(e))
             + 2 * 4 * block_q * (index_heads * lanes(e) + 4 * LANES)
             + 2 * 4 * s * lanes(e) + 12 * 4 * block_q * block_k
             + _XLA_VMEM)
    return pl.pallas_call(
        functools.partial(_loss_kernel, scale=scale),
        grid=(b, s // block_q, s // block_k),
        in_specs=[
            by_head(heads, d),
            pl.BlockSpec((1, kv_heads, block_k, d),
                         lambda bi, qi, ki: (bi, 0, ki, 0)),
            by_query(heads), by_head(index_heads, e),
            pl.BlockSpec((1, block_k, e), lambda bi, qi, ki: (bi, ki, 0)),
            by_query(index_heads), by_query(1),
            pl.BlockSpec((1, block_q, block_k),
                         lambda bi, qi, ki: (bi, qi, ki))],
        out_specs=[
            by_query(1), by_head(index_heads, e), by_query(index_heads),
            pl.BlockSpec((1, s, e), lambda bi, qi, ki: (bi, 0, 0))],
        out_shape=[
            _pallas.out((b, s, 1), jnp.float32, *operands),
            _pallas.out((b, index_heads, s, e), jnp.float32, *operands),
            _pallas.out((b, s, index_heads), jnp.float32, *operands),
            _pallas.out((b, s, e), jnp.float32, *operands)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(limit, 16 * 2 ** 20)),
        interpret=interpret, name="hvt_dsa_loss")(*operands)


def index_loss(q, k, lse, q_i, k_i, w, lse_i, choice, scale):
    """``(value, (dq_i, dk_i, dw))``: ``index_loss_plain`` through the
    kernel, and the value's gradients by ``q_i``, ``k_i`` and ``w``, in
    their types, which the same call makes (``jax.value_and_grad`` of the
    plain body, ``argnums=(3, 4, 5)``). Every operand is detached, so the
    pair has no derivative of its own: ``with_gradient`` ties gradients
    to a value. ``lse_i [b, s, 1]`` is the indexer's log-sum-exp over
    ``S_t``, which the choice of the same parts' ``index_scores`` hands on
    beside ``choice`` (``choose``)."""
    q, k, lse, q_i, k_i, w, lse_i = jax.lax.stop_gradient(
        (q, k, lse, q_i, k_i, w, lse_i))
    kl, dq, dw, dk = _loss_call(
        _to_bhsd(q), _to_bhsd(k), lse.astype(jnp.float32), _to_bhsd(q_i),
        k_i, w.astype(jnp.float32), lse_i, choice, scale=float(scale),
        interpret=_pallas.interpret())
    by_row = lambda g, like: (g / kl.size).astype(like.dtype)
    return jnp.sum(kl) / kl.size, (
        by_row(_to_bhsd(dq), q_i), by_row(dk, k_i), by_row(dw, w))


@jax.custom_vjp
def with_gradient(value, leaves, grads):
    """``value``, with ``grads`` (a tree like ``leaves``) as its gradient
    by ``leaves`` and no other: for a value whose gradients were made
    beside it. The backward rule only scales them by the cotangent, and
    they are all it keeps."""
    return value


def _with_gradient_fwd(value, leaves, grads):
    return value, grads


def _with_gradient_bwd(grads, ct):
    return None, jax.tree.map(lambda g: (ct * g).astype(g.dtype), grads), None


with_gradient.defvjp(_with_gradient_fwd, _with_gradient_bwd)

