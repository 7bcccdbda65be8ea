"""Mixture-of-experts layer: a top-k, dropless router over SwiGLU experts
(the OLMoE / Mixtral formulation), written so that the compiled step is
gathers and grouped matrix multiplications and nothing else.

For ``T = batch x seq`` tokens, ``E`` experts and ``k`` choices a token
the layer is four steps, each under a ``jax.named_scope`` of its name so
that a device trace can be split by them:

1. ``moe_route``: router logits in float32, softmax over the experts, the
   ``k`` largest probabilities of a token as its weights (not
   renormalised), the ``T x k`` assignments counted per expert and
   sorted by expert (stable), and the two auxiliary losses OLMoE trains
   with (arXiv:2409.02060): load balancing ``E x sum_e f_e P_e`` and the
   router z-loss ``mean(logsumexp(logits)^2)``.
2. ``moe_dispatch``: the tokens' rows gathered into expert order,
   ``[T x k, d]``. No capacity and no dropping: every assignment has its
   row, and an expert's group is as long as the router made it.
3. ``moe_experts``: ``down(silu(gate(x)) * up(x))`` as three grouped
   products over the group sizes: JAX's own Pallas grouped matrix
   multiplication (``jax.experimental.pallas.ops.tpu.megablox``, with
   its ``custom_vjp``), which at OLMoE's shape on a v5e took 2.2 to 2.4
   ms a product against 3.0 to 3.3 for ``jax.lax.ragged_dot`` (PERF.md,
   PR 26). The weight stacks ``[E, d, f]``, ``[E, d, f]``, ``[E, f, d]``
   are stored in float32 and multiplied in the layer's ``dtype``.
4. ``moe_combine``: rows gathered back into token order, weighted and
   summed over a token's ``k`` rows.

Dispatch and combine are a permutation and its inverse, so both
directions of both are gathers (``_take_rows``): the gradient program
of a layer that holds every expert has no scatter-add.

The same layer builds what other sparse models ask for, each an option
that defaults to the above: ``score="sigmoid"`` (scores ``sigmoid(h
W_r)``, the choice made from ``scores + choice_bias``, a buffer that
takes no gradient, the weights the scores at the chosen, divided by
their sum and times ``route_scale``; no auxiliary loss, ``aux`` is
``{}``), ``expert_act="relu2"`` (``down(relu(up(x))^2)``, two stacks),
``latent`` (the routed experts work in a narrower width between a down-
and an up-projection, scope ``moe_latent``), ``shared_ff`` (an expert
every token passes, beside the routed ones, scope ``moe_shared``;
``shared_gate``: its output times ``sigmoid(h w_g)``) and ``renormalise``
(the softmax score's chosen weights divided by their sum too: Qwen's
``norm_topk_prob``).

**A chip's share**: ``held = (first, count)`` builds the stacks for
experts ``[first, first + count)`` alone. The router still scores and
chooses over all ``n_experts``; only an assignment to a held expert gets
a row, and the layer returns the held experts' part of the sum (plus what
every chip computes alike: the latent projections and the shared expert).
Nothing is dropped: a token has one slot a held expert, assigned or not by
``[T, E]`` comparisons with the token's ``k``-th score (``_chosen``), and
the slots are sorted by expert with the unassigned last. The assigned
slots are worked through in **rounds of ``R`` rows, a static whole
multiple of ``T`` from what the share expects** (``held_rows``: ``T``, one
row a token, for a share that expects under 0.8 of a row a token, which is
every held cell of the benchmark until PR 61; ``3 T`` for 16 of 64 held
with 8 a token, whose two expected rows a token ran two rounds of ``T`` or
three by the seed; ``_held_experts``): round ``r`` takes the sorted slots
``[r R, (r + 1) R)``, gathers their tokens' rows, runs the same grouped
products on a ``[R, width]`` operand with the part of each group that
falls into the round, and adds the weighted rows to their tokens. How many
rounds run is the router's to say, ``ceil(sum(group_sizes) / R)``
(``ceil(min(k, count) T / R)`` at most, one at the loads a share sees as a
rule): a loop whose trips the data decide, with a backward pass written to
match
(``_held_experts_bwd``: the same loop, each round's forward made again for
its pullback). So a step pays for the rows assigned, a program holds the
round once (``_held_round`` is a ``jax.jit`` that every layer of one shape
shares) and nothing is traced, compiled or run for a round that is not
needed. What the rounds summed to carries the name ``HELD_SUM`` for a
caller's ``jax.checkpoint`` to keep. The group sizes of a round sum to the
rows really assigned, and the grouped product does not visit the tiles
past them (``megablox`` takes group sizes that sum to fewer rows than it
is given); what it leaves there nothing reads.

**Inside a round** what is not a grouped product goes by the assigned rows
too, a round being half empty at the loads a share sees. The gather of a
round's ``T`` rows runs at the memory's rate as it is
(``_rows_of_tokens``). The weighted sum by token and its twin in the
backward pass, the transpose of the gather, would be scatter-adds, which a
TPU takes a row at a time at a twelfth of the memory's rate: they are
``ops/sum_by_token.py``'s kernel (``_add_to_tokens``, ``_sum_by_token``).
The cotangent of the sum, a gather of float32 rows with the weights'
products and the weights' own gradient on it, goes **a piece of ``_PIECE``
rows a trip** of a loop that the round's count bounds (``_pieces``;
``move_rows``; ``_add_to_tokens_transposed``), so that no float32 pass
over ``[T, width]`` is left beside the grouped products but the total
itself. Gradients of the expert stacks are summed over rounds in the
layer's ``dtype``, where one pass rounds once. No code stands in for the
other chips or for the exchange with them.

Expert parallelism: the stacks carry ``P("ep", ...)`` in
``moe_param_partition_spec`` (and in ``transformer.param_partition_spec``
when given an ``ep_axis``); how GSPMD divides the ragged products over
``ep`` is not yet measured on chips.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops import _pallas
from horovod_tpu.ops import kth_largest as kth_kernel
from horovod_tpu.ops import sum_by_token as token_sum

# The grouped-product implementation, as the engagement counter names it.
PRODUCT = "megablox_gmm"
# Its tile, rows x contraction x columns: the fastest of nine timed at
# 65,536 rows x 2048 x 1024 over 64 groups on a v5e (PERF.md, PR 26);
# a larger one does not fit the kernels' VMEM.
_GMM_TILE = (512, 1024, 1024)
# The name (``jax.ad_checkpoint.checkpoint_name``) of what a held layer's
# rounds summed to, for a caller's ``jax.checkpoint`` policy to keep:
# ``models.GPT`` does under ``remat``, because the layer's own backward
# pass already makes each round's forward again.
HELD_SUM = "moe_held_sum"
# And of what a held layer's router chose, which takes no gradient: which
# experts a token was assigned (a bit a token and expert, ``_pack``) and
# its slots in their order. Kept, a recomputed block makes the scores
# again for their gradient but not the choice (the ``k``-th score, the
# comparisons, the sort): 1.5 MB a layer at 16,384 tokens, 512 experts
# and 8 held.
HELD_CHOICE = "moe_held_choice"


def _count_trace(n_experts, top_k, held, n_tokens):
    """One count a traced layer; ``round_rows`` is what a round of a share
    holds (``held_rows``)."""
    rows = held_rows(n_tokens, top_k, held, n_experts)[1] if held else None
    _pallas.count_trace(
        "hvt_moe_layers_traced_total",
        "mixture-of-experts layers traced into compiled programs "
        "(counted per trace, not per execution)",
        experts=n_experts, top_k=top_k, product=PRODUCT,
        held=held[1] if held else n_experts,
        round_rows=rows if held else "all",
        move_rows=move_rows(rows) if held else "all")


def _rows(x, index):
    return x.at[index].get(mode="promise_in_bounds")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, index, inverse, k):
    """Rows ``index // k`` of ``x [n, d]``, where ``index`` is a
    permutation of ``range(n x k)`` and ``inverse`` its inverse. The
    transpose of a gather is a scatter-add; of a permutation it is the
    gather by the inverse (then the sum over the ``k`` copies of a row),
    which is what the backward pass runs."""
    return _rows(x, index // k if k > 1 else index)


def _take_rows_fwd(x, index, inverse, k):
    return _take_rows(x, index, inverse, k), inverse


def _take_rows_bwd(k, inverse, g):
    g = _rows(g, inverse)
    if k > 1:
        g = g.reshape(-1, k, g.shape[-1]).sum(1)
    return g, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _chosen(pick, k, top):
    """``[T, E]`` bool: the experts ``jax.lax.top_k(pick, k)`` gives each
    token, from its ``k``-th largest score and comparisons ``[T, E]``, with
    nothing ``[T, k, E]``. Every score above the ``k``-th is chosen; of
    those equal to it, as many as are still needed, the lower index first,
    as ``top_k`` breaks a tie: a threshold alone would choose more than
    ``k`` where scores tie at the ``k``-th place, and a sigmoid's do once
    they saturate. The ``k``-th score is ``ops/kth_largest.py``'s kernel's
    where that serves (a TPU, whole 128-lane tiles: 0.28 ms at ``[16384,
    512]`` and ``k`` = 22 on a v5e where ``top_k``, a sort of every row,
    took 1.44; PERF.md section 6, PR 37) and the last of ``top [T, k]``,
    ``top_k``'s values, elsewhere."""
    kth = (kth_kernel.kth_largest(pick, k)
           if kth_kernel.serves(*pick.shape, k) else top[:, -1:])
    above, level = pick > kth, pick == kth
    needed = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    in_front = jnp.cumsum(level, axis=-1, dtype=jnp.int32) - level
    return above | (level & (in_front < needed))


def _pack(mask):
    """``mask [T, E]`` bool -> ``[ceil(T / 32), E]`` uint32, a token a
    bit: what is kept of a held layer's choice for the backward pass, an
    eighth of the mask's bytes. Along the tokens, so that the experts
    stay on the lanes both ways."""
    tokens, width = mask.shape
    rows = jnp.pad(mask, ((0, -tokens % 32), (0, 0))).reshape(-1, 32, width)
    return jnp.sum(rows.astype(jnp.uint32)
                   << jnp.arange(32, dtype=jnp.uint32)[:, None],
                   axis=1, dtype=jnp.uint32)


def _unpack(bits, tokens):
    """``_pack``'s mask of ``tokens`` rows again."""
    rows = (bits[:, None, :] >> jnp.arange(32, dtype=jnp.uint32)[:, None]) & 1
    return rows.reshape(-1, bits.shape[-1])[:tokens].astype(bool)


def _slots_by_expert(assigned):
    """``assigned [T, count]`` bool -> the ``T x count`` slots
    (token-major: slot ``t count + e``), the assigned first, by expert and
    within an expert by token: one stable sort (0.12 and 0.55 ms on a v5e
    at 16,384 x 8 and x 32 slots, a fifth of what two ``cumsum``s and a
    scatter to each slot's place took: ``benchmarks/moe_route_pieces.py``;
    PERF.md section 6, PR 37)."""
    count = assigned.shape[-1]
    return jnp.argsort(jnp.where(assigned, jnp.arange(count), count)
                       .reshape(-1), stable=True)


def moe_route(h, router, k, *, score="softmax", bias=None, scale=1.0,
              held=None, renormalise=None):
    """``h [T, d]``, ``router [d, E]`` -> the ``k`` choices of every
    token. Returns ``(experts [T, k], weights [T, k] float32, order
    [T x k], inverse [T x k], group_sizes [E], aux, probs [T, E])``:
    ``order`` sorts the flattened assignments (token-major, so assignment
    ``i`` is choice ``i % k`` of token ``i // k``) by expert, stably;
    ``inverse`` is its inverse permutation; ``aux`` holds the two losses,
    unweighted; ``probs`` is the softmax the weights were taken from. All
    of it in float32 whatever the experts compute in.

    ``score="sigmoid"``: ``probs`` is ``sigmoid(h W_r)``, the choice is
    the ``k`` largest of ``probs + bias`` (``bias [E]`` takes no
    gradient: it only enters the choice), the weights are ``probs`` at
    the chosen over their sum + 1e-20, times ``scale``; ``aux`` is
    ``{}``.

    ``renormalise``: whether a token's ``k`` weights are divided by their
    sum (+ 1e-20) and multiplied by ``scale``, over all its chosen, held
    here or not; None is each score's habit (the sigmoid's are, the
    softmax's are not).

    ``held = (first, count)``: the choice is still over all ``E`` and
    the same (``experts`` is ``top_k``'s, for a caller that sows or checks
    it), but a token has a slot a *held* expert in place of one a choice,
    and all of it comes from ``[T, E]`` comparisons with the token's
    ``k``-th score (``_chosen``): ``weights [T, count]`` (0 where the
    token did not choose the expert), ``order`` over the ``T x count``
    slots (token-major), the assigned first, by held expert and within one
    by token, ``inverse`` None (nothing reads a held layer's; only the
    first ``sum(group_sizes)`` of ``order`` mean anything, the rest are
    slots nobody chose) and ``group_sizes [count]``, which sum to the
    slots really assigned."""
    n_tokens, n_experts = h.shape[0], router.shape[-1]
    logits = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        probs = pick = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        pick = probs if bias is None else probs + bias.astype(jnp.float32)
    else:
        raise ValueError(f"score {score!r}: softmax or sigmoid")
    pick = jax.lax.stop_gradient(pick)
    top, experts = jax.lax.top_k(pick, k)
    if held is None:
        # [T, k, E]; the weights and the counts as sums over it, so that
        # neither a gather's transpose nor a histogram puts a scatter in
        chosen = experts[..., None] == jnp.arange(n_experts)
        weights = jnp.sum(jnp.where(chosen, probs[:, None, :], 0.0), axis=-1)
        counts = jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32)
    else:       # [T, E]: a token chooses an expert at most once
        assigned = _unpack(checkpoint_name(
            _pack(_chosen(pick, k, top)), HELD_CHOICE), n_tokens)
        weights = jnp.where(assigned, probs, 0.0)
        counts = jnp.sum(assigned, axis=0, dtype=jnp.int32)
    group_sizes = counts
    if renormalise is None:
        renormalise = score == "sigmoid"
    if renormalise:
        weights = weights * (scale / (
            jnp.sum(weights, axis=-1, keepdims=True) + 1e-20))
    if held is None:
        order = jnp.argsort(experts.reshape(-1), stable=True)
        inverse = jnp.argsort(order)
    else:
        first, count = held
        mine = slice(first, first + count)
        weights, group_sizes = weights[:, mine], counts[mine]
        order, inverse = checkpoint_name(
            _slots_by_expert(assigned[:, mine]), HELD_CHOICE), None
    if score == "sigmoid":
        return experts, weights, order, inverse, group_sizes, {}, probs
    share = counts.astype(jnp.float32) / (n_tokens * k)
    aux = {
        "load_balance": n_experts * jnp.sum(share * probs.mean(axis=0)),
        "router_z": jnp.mean(
            jax.scipy.special.logsumexp(logits, axis=-1) ** 2),
    }
    return experts, weights, order, inverse, group_sizes, aux, probs


# A round's rows over what a share expects (``held_rows``).
_ROUND_ROOM = 1.25


def held_rows(n_tokens, k, held, n_experts):
    """``(rounds at most, rows a round)`` of a share ``held = (first,
    count)`` of ``n_experts``: its sorted slots are worked through a
    round's rows at a time. **A round's
    rows are a static whole multiple of ``n_tokens``, from what the share
    expects**: a uniform router sends it ``k x count / n_experts`` rows a
    token, and a round holds ``ceil(1.25 x`` that ``)`` times ``n_tokens``
    (a quarter of room: the loads a fresh router gives run a third over
    and under their expectation), ``min(k, count)`` times at most. So a
    share that expects under 0.8 of a row a token works in rounds of
    ``n_tokens`` (one row a token: every held cell of the benchmark until
    PR 61), and one that expects two rows a token (16 of 64 with 8 a
    token) in rounds of three times that, where rounds of ``n_tokens``
    would run two or three a layer by the seed, each for a few rows past
    a whole multiple (5.1 ms a step a round that holds few rows; PERF.md
    section 6, PR 61). The most that can be assigned, a token choosing
    ``k`` experts and each at most once, is ``min(k, count) x n_tokens``
    rows: that is the static worst case, which nothing in the program is
    sized by: the rounds that run are the router's to say (``ceil(sum(
    group_sizes) / rows)``, ``_rounds``), so the layer is dropless without
    a bound. None for a layer that holds every expert: one pass over its
    ``n_tokens x k`` rows, every one of them real."""
    if held is None:
        return None
    most = min(k, held[1])
    times = max(1, min(most, math.ceil(
        _ROUND_ROOM * k * held[1] / n_experts)))
    return -(-most // times), times * n_tokens


def moe_dispatch(h, order, inverse, k):
    """``h [T, d]`` -> its rows in expert order, ``[T x k, d]`` (``k``
    the choices of a token)."""
    return _take_rows(h, order, inverse, k)


def _grouped_product(lhs, rhs, group_sizes):
    """``lhs [m, k]`` by ``rhs [g, k, n]``: rows of group ``i`` (the
    ``group_sizes[i]`` rows after those of group ``i - 1``) times
    ``rhs[i]``. The row tile has to divide ``m``."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    (m, k), n = lhs.shape, rhs.shape[-1]
    tile = (math.gcd(m, _GMM_TILE[0]), min(k, _GMM_TILE[1]),
            min(n, _GMM_TILE[2]))
    return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
               tiling=tile, interpret=_pallas.interpret())


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def moe_experts(rows, gate, up, down, group_sizes):
    """``down(silu(gate(x)) * up(x))`` of every row by its group's
    expert, multiplied in ``rows.dtype``; ``gate=None``:
    ``down(relu(up(x))^2)``."""
    if gate is None:
        hidden = _relu2(_grouped_product(rows, up.astype(rows.dtype),
                                         group_sizes))
        return _grouped_product(hidden, down.astype(rows.dtype), group_sizes)
    gate, up, down = (w.astype(rows.dtype) for w in (gate, up, down))
    hidden = (jax.nn.silu(_grouped_product(rows, gate, group_sizes))
              * _grouped_product(rows, up, group_sizes))
    return _grouped_product(hidden, down, group_sizes)


def moe_combine(rows, weights, order, inverse):
    """Rows ``[T x k, d]`` in expert order -> ``[T, d]`` float32: each
    token's ``k`` rows times their weights, summed."""
    k = weights.shape[-1]
    rows = _take_rows(rows, inverse, order, 1)
    rows = rows.reshape(-1, k, rows.shape[-1]).astype(jnp.float32)
    return jnp.sum(rows * weights[..., None], axis=1)


# ---- a chip's share: the held experts' rows, a round of one row a token
# at a time. A round's rows belong to tokens in no order and a token may
# own several of them, so the movement from tokens to rows is a gather and
# the one from rows to tokens a sum by token, each the other's transpose.
# The gather of a round's ``T`` rows runs at the memory's rate; the sums,
# and the gather of float32 rows that is the weighted sum's transpose, go
# by the rows the router assigned to the round.

# The rows a trip of the cotangent's gather takes (``_pieces``).
_PIECE = 2048


def move_rows(n_rows):
    """The rows of a piece of a round of ``n_rows``: ``_PIECE``, or the
    largest part of it that divides the round (the same rule as the
    grouped product's row tile), so that no piece hangs over its end."""
    return math.gcd(n_rows, _PIECE)


def _pieces(assigned, n_rows, body, carry):
    """``carry`` after ``body(at, live, carry)`` for every piece of a
    round's ``n_rows`` rows that holds one of its first ``assigned``:
    ``at`` the piece's first row, ``live [piece, 1]`` which of its rows
    are assigned. ``ceil(assigned / piece)`` trips, none for a round with
    no row: ``_rounds``' rule inside a round. A program holds the body
    once, and whatever lies past the last piece is neither read nor
    written."""
    piece = move_rows(n_rows)

    def one(p, carry):
        live = p * piece + jnp.arange(piece) < assigned
        return body(p * piece, live[:, None], carry)

    return jax.lax.fori_loop(jnp.int32(0), -(-assigned // piece), one, carry)


def _piece_of(at, live):
    """``x -> x[at:at + piece]``, the piece's rows of a round's array."""
    return lambda x: jax.lax.dynamic_slice_in_dim(x, at, live.shape[0])


def _set_piece(x, at, piece):
    return jax.lax.dynamic_update_slice_in_dim(x, piece.astype(x.dtype),
                                               at, 0)


def _add_to_tokens(total, rows, weight, where):
    """``total [T, d]`` float32 with a round's assigned rows, each times
    its ``weight`` (None: as it is), added to their tokens, in float32 (a
    token has at most ``min(k, count)`` rows). ``where = (token, plan,
    assigned)``: the rows' tokens, ``token_sum.plan`` of them and how many
    are assigned. As a scatter-add a TPU takes it a row at a time whatever
    is in the row, at a twelfth of the memory's rate at best, and pieces
    of it under a loop at a thirtieth (PERF.md, PR 41), so it is
    ``ops/sum_by_token.py``'s kernel: the rows gathered into the order of
    their tokens, and a tile of tokens' sum made on the MXU from the
    chunks of sorted rows that hold its own, grid steps past the assigned
    rows skipped. What the grouped product left in ``rows`` past the
    assigned is not read."""
    return token_sum.sum_by_token(rows, where[1], total.shape[0],
                                  weight=weight, total=total)


def _sum_by_token(rows, where, n_tokens):
    """``rows [R, d]`` -> ``[T, d]``, ``T = n_tokens``: the sum of a
    round's assigned rows by their token, in float32 (``_add_to_tokens``
    onto zeros)."""
    return token_sum.sum_by_token(rows, where[1], n_tokens)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rows_of_tokens(x, where, n_tokens):
    """``x [T, d]`` -> ``x[token] [R, d]``, the rows of a round (``T =
    n_tokens``), in one pass: a gather runs at the memory's rate, and the
    zeros that pieces would fill cost what it does. Past the assigned they
    are the rows of tokens nobody assigned, which the grouped product does
    not visit. Its transpose is the sum by token of the assigned
    (``_sum_by_token``), written out because a loop whose trips the data
    decide has no reverse mode, and so that it sums in float32 whatever
    ``x`` is in."""
    return _rows(x, where[0])


_rows_of_tokens.defvjp(
    lambda x, where, n_tokens: (_rows(x, where[0]), where),
    lambda n_tokens, where, g: (_sum_by_token(g, where, n_tokens), None))


def _add_to_tokens_transposed(g, rows, weight, where):
    """What ``g [T, d]``, the cotangent of ``_add_to_tokens``' result,
    gives back to ``rows`` (in their dtype) and to ``weight``: a piece of
    ``g[token]`` at a time, zeros past the assigned rows."""
    token, _, assigned = where

    def take(at, live, grads):
        of = _piece_of(at, live)
        mine = _rows(g, of(token))
        to_weight = jnp.sum(mine * of(rows).astype(jnp.float32), axis=-1)
        return (_set_piece(grads[0], at, jnp.where(
                    live, mine * of(weight)[:, None], 0.0)),
                _set_piece(grads[1], at, jnp.where(live[:, 0], to_weight,
                                                   0.0)))

    return _pieces(assigned, rows.shape[0], take,
                   (jnp.zeros_like(rows), jnp.zeros_like(weight)))


@functools.partial(jax.jit, static_argnames="rows")
def _held_round(tokens, weights, stacks, route, r, *, rows):
    """Round ``r`` of a share in rounds of ``R = rows`` rows
    (``held_rows``): the sorted slots ``[r R, (r + 1) R)`` of ``order``,
    their tokens' rows through the held experts (``stacks = (gate, up,
    down)``): ``((rows [R, width], their weights [R] float32), where)``,
    ``where = (their tokens [R], token_sum.plan of them, how many of the
    rows are assigned)``. ``route = (order, group_sizes)`` as
    ``moe_route`` made them; the round's group sizes are the part of each
    expert's group that falls into it. The grouped products take all ``R``
    rows in one call and visit the assigned; what they leave in the rows
    past them is whatever the memory held, which nothing reads: the sums
    on either side select the assigned rows by their count. A ``jax.jit``
    of its own so that a program holds one traced and lowered copy of it,
    however many layers call it."""
    order, group_sizes = route
    n_tokens, count = weights.shape
    ends = jnp.cumsum(group_sizes) - r * rows
    sizes = jnp.diff(jnp.clip(ends, 0, rows), prepend=0)
    assigned = jnp.sum(sizes)
    slots = jax.lax.dynamic_slice(order, (r * rows,), (rows,))
    token, expert = slots // count, slots % count
    with jax.named_scope("moe_dispatch"):
        where = token, token_sum.plan(token, assigned, n_tokens), assigned
        rows = _rows_of_tokens(tokens, where, n_tokens)
    with jax.named_scope("moe_experts"):
        rows = moe_experts(rows, *stacks, sizes)
    with jax.named_scope("moe_combine"):
        weight = jnp.sum(jnp.where(
            expert[:, None] == jnp.arange(count),
            _rows(weights, token), 0.0), axis=-1)
    return (rows, weight), where


def _rounds(route, rows, body, carry):
    """``carry`` after ``body(r, carry)`` for every round of ``rows`` rows
    the router's count asks for, ``ceil(assigned / rows)`` of them (one,
    as a rule; ``held_rows``' at most; none where no token chose a held
    expert): a loop whose trips the data decide, so nothing stands in for
    a round that does not run, no branch, no zeros, no copy of what is
    carried, and a program holds one round however many it may run."""
    return jax.lax.fori_loop(
        jnp.int32(0), -(-jnp.sum(route[1]) // rows), body, carry)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _held_experts(tokens, weights, stacks, route, rows):
    """The held experts' part of the layer's sum, ``[T, width]`` float32,
    in rounds of ``rows`` rows (``held_rows``, ``_rounds``), each round's
    weighted rows added to their tokens (``_add_to_tokens``, in place). A
    loop whose trips the data decide has no reverse mode of its own, so
    the backward pass is written here: the same loop, each round's forward
    made again for its pullback (a round's residuals then live for one
    trip, where one pass kept those of all ``T x min(k, count)`` rows)."""
    def one(r, total):
        (mine, weight), where = _held_round(tokens, weights, stacks, route,
                                            r, rows=rows)
        with jax.named_scope("moe_combine"):
            return _add_to_tokens(total, mine, weight, where)

    return _rounds(route, rows, one, jnp.zeros(tokens.shape, jnp.float32))


def _held_experts_bwd(rows, res, g):
    *of, route = res

    def one(r, grads):
        out, pull, where = jax.vjp(
            lambda *of: _held_round(*of, route, r, rows=rows), *of,
            has_aux=True)
        with jax.named_scope("moe_combine"):
            back = _add_to_tokens_transposed(g, *out, where)
        return jax.tree.map(jnp.add, grads, pull(back))

    return *_rounds(route, rows, one,
                    jax.tree.map(jnp.zeros_like, tuple(of))), None


_held_experts.defvjp(
    lambda *inputs: (_held_experts(*inputs), inputs[:-1]), _held_experts_bwd)


class MoEMlp(nn.Module):
    """The expert layer of a block: ``n_experts`` experts of width
    ``d_ff``, ``experts_per_token`` of them a token, nothing dropped.
    By default OLMoE's: softmax scores, SwiGLU experts (``gate``, ``up``,
    ``down``) on the model's width, every expert here. Returns ``(out,
    aux)``; ``aux`` is ``{"load_balance", "router_z"}``, each loss
    unweighted (OLMoE trains with 0.01 and 0.001), and ``{}`` for
    ``score="sigmoid"``. For a caller that asks for the collection
    ``intermediates``, what the router saw and said is sown there:
    ``router_input [T, d]``, ``router_probs [T, E]`` and the chosen
    ``experts [T, k]``.

    ``score``, ``route_scale``: see ``moe_route``; the sigmoid router's
    ``choice_bias [E]`` lives in the collection ``buffers`` (zeros; no
    gradient reaches it and no optimizer sees it). ``expert_act``:
    ``"swiglu"`` or ``"relu2"`` (stacks ``up`` and ``down`` alone).
    ``latent``: the routed experts' width, between ``latent_in [d,
    latent]`` and ``latent_out [latent, d]`` (0: the model's own).
    ``shared_ff``: the width of one shared expert of the experts' kind on
    the layer's own input (0: none), ``shared_gate``: its output times
    ``sigmoid(h w_g)``, ``shared_expert_gate [d, 1]``. ``renormalise``:
    see ``moe_route``. ``held = (first, count)``: the
    stacks hold experts ``[first, first + count)`` and the output is
    their part of the sum, with the latent projections and the shared
    expert, which every chip computes alike, in full."""

    n_experts: int
    d_ff: int
    experts_per_token: int
    dtype: Any = jnp.bfloat16
    score: str = "softmax"
    route_scale: float = 1.0
    expert_act: str = "swiglu"
    latent: int = 0
    shared_ff: int = 0
    held: Optional[tuple] = None
    renormalise: Optional[bool] = None
    shared_gate: bool = False

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        gated = {"swiglu": True, "relu2": False}[self.expert_act]
        if self.held is not None and not (
                0 <= self.held[0] and 0 < self.held[1]
                and sum(self.held) <= self.n_experts):
            raise ValueError(f"experts held {self.held} of {self.n_experts}")
        stack = self.held[1] if self.held else self.n_experts
        width = self.latent or d
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (d, self.n_experts))
        gate = self.param("gate", init, (stack, width, self.d_ff)) \
            if gated else None
        up = self.param("up", init, (stack, width, self.d_ff))
        down = self.param("down", init, (stack, self.d_ff, width))
        bias = self.variable(
            "buffers", "choice_bias", jnp.zeros, (self.n_experts,),
            jnp.float32).value if self.score == "sigmoid" else None
        h = x.reshape(-1, d)
        _count_trace(self.n_experts, self.experts_per_token, self.held,
                     h.shape[0])
        with jax.named_scope("moe_route"):
            (experts, weights, order, inverse, group_sizes, aux,
             probs) = moe_route(h, router, self.experts_per_token,
                                score=self.score, bias=bias,
                                scale=self.route_scale, held=self.held,
                                renormalise=self.renormalise)
        for name, value in (("router_input", h), ("router_probs", probs),
                            ("experts", experts)):
            self.sow("intermediates", name, value)
        # a [d_in, d_out] matrix of the layer, in what it multiplies in
        dense = lambda name, *shape: self.param(name, init, shape).astype(
            self.dtype)
        low = lambda: h.astype(self.dtype)     # cast under the user's scope
        if self.latent:
            with jax.named_scope("moe_latent"):
                tokens = jnp.dot(low(), dense("latent_in", d, width))
        if self.held:
            with jax.named_scope("moe_experts"):   # cast once, not a round
                stacks = tuple(None if w is None else w.astype(self.dtype)
                               for w in (gate, up, down))
            rounds, rows = held_rows(h.shape[0], self.experts_per_token,
                                     self.held, self.n_experts)
            # (no round reaches past the slots: the last of a share whose
            # rounds do not divide them reads slots nobody chose)
            beyond = rounds * rows - order.shape[0]
            if beyond > 0:
                order = jnp.pad(order, (0, beyond))
            out = checkpoint_name(_held_experts(
                tokens if self.latent else low(), weights, stacks,
                (order, group_sizes), rows), HELD_SUM)
        else:
            with jax.named_scope("moe_dispatch"):
                rows = moe_dispatch(tokens if self.latent else low(), order,
                                    inverse, self.experts_per_token)
            with jax.named_scope("moe_experts"):
                rows = moe_experts(rows, gate, up, down, group_sizes)
            with jax.named_scope("moe_combine"):
                out = moe_combine(rows, weights, order, inverse)
        if self.latent:
            with jax.named_scope("moe_latent"):
                out = jnp.dot(out.astype(self.dtype),
                              dense("latent_out", width, d))
        if self.shared_ff:
            with jax.named_scope("moe_shared"):
                hidden = jnp.dot(low(), dense("shared_up", d, self.shared_ff))
                hidden = (jax.nn.silu(jnp.dot(low(), dense(
                    "shared_gate", d, self.shared_ff))) * hidden
                    if gated else _relu2(hidden))
                shared = jnp.dot(hidden, dense("shared_down",
                                               self.shared_ff, d))
                if self.shared_gate:
                    shared = shared * jax.nn.sigmoid(jnp.dot(
                        low(), dense("shared_expert_gate", d, 1),
                        preferred_element_type=jnp.float32))
                out = out + shared
        return out.reshape(x.shape).astype(x.dtype), aux


def expert_leaf_spec(name: str, leaf, ep_axis, tp_axis):
    """PartitionSpec of one leaf of a ``MoEMlp``: the expert stacks
    shard their first axis over ``ep_axis`` and their ``d_ff`` axis over
    ``tp_axis``; the router replicates."""
    if leaf.ndim == 3 and name in ("gate", "up"):
        return P(ep_axis, None, tp_axis)
    if leaf.ndim == 3 and name == "down":
        return P(ep_axis, tp_axis, None)
    return P()


def moe_param_partition_spec(params, ep_axis: str = "ep",
                             tp_axis: Optional[str] = None):
    """PartitionSpecs for an MoE param tree: expert-stacked weights
    ([n_experts, ...]) shard over ``ep_axis`` (dim 0); everything else
    replicated (compose with the dense model's tp spec separately)."""

    def spec(path, leaf):
        last = str(getattr(path[-1], "key", path[-1])) if path else ""
        return expert_leaf_spec(last, leaf, ep_axis, tp_axis)

    return jax.tree_util.tree_map_with_path(spec, params)
