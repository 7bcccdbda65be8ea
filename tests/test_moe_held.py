"""A chip's share of an expert layer (``MoEMlp(held=(first, count))``): the
layer Nemotron-H's expert block needs (sigmoid scores with a choice bias,
renormalised and scaled weights, relu2 experts in a latent width, a shared
expert) against its plain reference, the held route bit for bit, the
rounds of one row a token and their pieces, what the compiled programs
hold and what they are counted as. Float32 and tiny sizes: the Pallas
kernels run in interpret mode on the CPU."""

import collections
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import nemotron_h as latent_reference
from chipbench.reference import olmoe as reference
from horovod_tpu.models import moe
from horovod_tpu.models.moe import MoEMlp
from tests.test_moe import _close, _layer, _scatters


# ---- the layer Nemotron-H's expert block needs: sigmoid scores with a
# choice bias, renormalised and scaled weights, relu2 experts in a latent
# width, a shared expert, and a chip's share of the experts

_LATENT = {"num_experts_per_tok": 3, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5}


def _latent_moe(held, k=3, n_experts=8):
    return MoEMlp(n_experts, 12, k, dtype=jnp.float32, score="sigmoid",
                  route_scale=2.5, expert_act="relu2", latent=8,
                  shared_ff=20, held=held)


def _latent_layer(held, k=3, n_experts=8, tokens=40, d=16, seed=0,
                  skewed=False, crowd=None):
    """A float32 latent layer, its parameters (scaled up from 0.02, as
    ``_layer`` does), a choice bias that changes some choices, and its
    input. ``skewed``: every token chooses the first held expert and none
    the second. ``crowd``: every token chooses the first ``crowd`` held
    experts (0: none of the held), so that ``crowd`` whole rounds of one
    row a token are assigned, and what the other choices add."""
    layer = _latent_moe(held, k, n_experts)
    h = jax.random.normal(jax.random.key(seed), (tokens, d))
    # (one compiled program: op by op, this is most of a case's seconds)
    params = jax.jit(lambda h: jax.tree.map(
        lambda w: w * 20.0, layer.init(jax.random.key(seed + 1), h)[
            "params"]))(h)
    bias = 0.2 * jax.random.normal(jax.random.key(seed + 2), (n_experts,))
    if skewed or crowd is not None:
        first, count = held
        h = h.at[:, 0].set(1.0)
        if crowd is None:
            pull = jnp.array([30.0, -30.0])
        else:
            pull = jnp.where(jnp.arange(count) < crowd, 30.0,
                             -30.0 if crowd == 0 else 0.0)
        mine = slice(first, first + pull.size)
        router = params["router"]
        router = router.at[0, mine].set(
            jnp.where(pull == 0.0, router[0, mine], pull))
        params = {**params, "router": router}
        if crowd:       # the bias must not move a crowd's choice
            bias = bias.at[first:first + crowd].set(1.0)
    return layer, params, {"choice_bias": bias}, h


def _latent_config(held, k=3):
    return {**_LATENT, "num_experts_per_tok": k,
            "experts_held_first": held[0] if held else 0}


@pytest.mark.parametrize("held, k", [
    (None, 3), ((4, 4), 3), ((0, 2), 3), ((2, 6), 3), ((3, 1), 2)],
    ids=["every-expert", "held-more-than-k", "held-fewer-than-k",
         "held-twice-k", "one-held"])
def test_latent_layer_matches_reference(held, k):
    """Sigmoid scores, the choice from scores + bias, the weights the
    scores at the chosen over their sum times the scale, relu2 experts in
    the latent width, the shared expert beside them: output and the
    gradient of every leaf and of the input against the reference given
    the program's expert indices; ``aux`` is empty; a share holds stacks
    of its experts alone and the bias takes no gradient."""
    layer, params, buffers, h = _latent_layer(held, k)
    stack = held[1] if held else 8
    assert params["up"].shape == (stack, 8, 12)
    assert params["down"].shape == (stack, 12, 8)
    assert set(params) == {"router", "up", "down", "latent_in",
                           "latent_out", "shared_up", "shared_down"}
    cot = jax.random.normal(jax.random.key(9), h.shape)
    config = _latent_config(held, k)

    def program(params, h, bias):
        (out, aux), sown = layer.apply(
            {"params": params, "buffers": {"choice_bias": bias}}, h,
            mutable=["intermediates"])
        return jnp.sum(out * cot), (out, aux, sown["intermediates"])

    (_, (out, aux, sown)), grads = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1, 2), has_aux=True))(
            params, h, buffers["choice_bias"])
    experts = sown["experts"][0]

    def plain(params, h):
        out, routing = latent_reference.experts_layer(
            h, params, buffers["choice_bias"], config, forced=experts)
        return jnp.sum(out * cot), (out, routing)

    (_, (want, routing)), want_grads = jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True)(params, h)
    assert aux == {}
    _close(out, want, "output")
    for name in params:
        _close(grads[0][name], want_grads[0][name], f"d {name}")
    _close(grads[1], want_grads[1], "d input")
    assert float(jnp.abs(grads[2]).max()) == 0.0
    # the program's own choice is the reference's, and the bias moved it
    np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                  np.sort(np.asarray(routing["own"]), -1))
    _, unbiased = latent_reference.route(h, params["router"], 0.0, k)
    assert (np.sort(np.asarray(unbiased), -1)
            != np.sort(np.asarray(experts), -1)).any()
    _close(sown["router_probs"][0], jax.nn.sigmoid(h @ params["router"]),
           "scores")


def test_held_route_gives_a_slot_a_held_expert():
    """The choice is over all the experts; the weights are renormalised
    over all a token chose, not over the held ones; the assigned slots
    come first, by held expert and within one by token, and the group
    sizes count them; a held route has no inverse permutation, and what
    follows the assigned in ``order`` are slots of the layer's own."""
    _, params, buffers, h = _latent_layer((4, 4))
    first, count, k = 4, 4, 3
    experts, weights, order, inverse, sizes, aux, scores = moe.moe_route(
        h, params["router"], k, score="sigmoid",
        bias=buffers["choice_bias"], scale=2.5, held=(first, count))
    want_scores, want = latent_reference.route(
        h, params["router"], buffers["choice_bias"], k)
    np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                  np.sort(np.asarray(want), -1))
    chosen = (np.asarray(want)[..., None] == np.arange(8)).any(1)
    picked = np.where(chosen, np.asarray(want_scores), 0.0)
    full = 2.5 * picked / picked.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(weights),
                               full[:, first:first + count], rtol=1e-5)
    assert aux == {} and weights.shape == (h.shape[0], count)
    held = chosen[:, first:first + count]
    np.testing.assert_array_equal(np.asarray(sizes), held.sum(0))
    assigned = int(sizes.sum())
    assert 0 < assigned < h.shape[0] * k
    assert inverse is None
    order = np.asarray(order)
    assert order.shape == (h.shape[0] * count,)
    assert order.min() >= 0 and order.max() < h.shape[0] * count
    key = np.where(held, np.arange(count), count).reshape(-1)
    np.testing.assert_array_equal(
        order[:assigned], np.argsort(key, kind="stable")[:assigned])
    # 4 of 8 with 3 a token expect 1.5 rows a token: rounds of two, two at
    # most; 2 of 8 expect 0.75: rounds of one, two at most
    assert moe.held_rows(h.shape[0], k, (first, count), 8) == (
        2, 2 * h.shape[0])
    assert moe.held_rows(h.shape[0], k, (0, 2), 8) == (2, h.shape[0])
    assert moe.held_rows(h.shape[0], k, None, 8) is None


def _held_route_as_it_was(h, router, k, *, score, bias, scale, held,
                          renormalise=None):
    """A held layer's route as ``moe_route`` made it until PR 37, kept
    here as the plain formulation the new one is held to: ``top_k``, the
    ``[T, k, E]`` comparison of the chosen indices with every expert, a
    stable ``argsort`` of all ``T x count`` slots. ``(experts, weights,
    order, group_sizes, probs)``."""
    logits = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        probs = pick = jax.nn.softmax(logits, axis=-1)
    else:
        probs = jax.nn.sigmoid(logits)
        pick = probs if bias is None else probs + bias.astype(jnp.float32)
    _, experts = jax.lax.top_k(pick, k)
    chosen = experts[..., None] == jnp.arange(router.shape[-1])
    assigned = jnp.any(chosen, axis=1)
    weights = jnp.where(assigned, probs, 0.0)
    counts = jnp.sum(assigned, axis=0, dtype=jnp.int32)
    if score == "sigmoid" if renormalise is None else renormalise:
        weights = weights * (scale / (
            jnp.sum(weights, axis=-1, keepdims=True) + 1e-20))
    first, count = held
    mine = slice(first, first + count)
    order = jnp.argsort(jnp.where(assigned[:, mine], jnp.arange(count),
                                  count).reshape(-1), stable=True)
    return experts, weights[:, mine], order, counts[mine], probs


def _routed(scene, held, tokens=48, d=16, n_experts=8):
    """``(h, router, bias)`` for a route over eight experts. ``ties``:
    experts 1, 2 and 6 share a router column and a bias, 0 and 4 another,
    and the input is large enough that a sigmoid saturates at 1 and a
    softmax underflows to 0, so that scores tie at every place, the
    ``k``-th among them; ``crowd``: every token chooses the first held
    expert; ``none``: no token chooses a held one."""
    keys = jax.random.split(jax.random.key(len(scene) + 7 * held[0]), 3)
    h = jax.random.normal(keys[0], (tokens, d))
    router = jax.random.normal(keys[1], (d, n_experts))
    bias = 0.05 * jax.random.normal(keys[2], (n_experts,))
    if scene == "ties":
        for same in ((1, 2, 6), (0, 4)):
            router = router.at[:, same].set(router[:, same[:1]])
            bias = bias.at[jnp.array(same)].set(bias[same[0]])
        h = h.at[::2].multiply(40.0)
    elif scene in ("crowd", "none"):
        h = h.at[:, 0].set(1.0)
        mine = slice(held[0], held[0] + (1 if scene == "crowd" else held[1]))
        router = router.at[0, mine].set(60.0 if scene == "crowd" else -60.0)
        bias = jnp.zeros_like(bias)     # the scores alone decide
    return h, router, bias


@pytest.mark.parametrize("scene", ["even", "ties", "crowd", "none"])
@pytest.mark.parametrize("k", [2, 5], ids=["k2", "k5"])
@pytest.mark.parametrize("held", [(0, 3), (3, 3), (5, 1)],
                         ids=["front", "middle", "one-expert"])
@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
def test_held_route_is_the_plain_formulation_bit_for_bit(score, held, k,
                                                         scene):
    """The route of a held layer, made from the ``k``-th score and ``[T,
    E]`` comparisons, against the formulation it replaced (``top_k``, the
    ``[T, k, E]`` mask, the stable ``argsort``), jitted both: the same
    experts, and ``weights``, ``group_sizes``, ``probs`` and the assigned
    part of ``order`` equal to the last bit, for ``k`` below and above the
    experts held, with scores tied at the ``k``-th place (where a
    threshold alone would assign more than ``k``), with every token on one
    held expert and with none on any."""
    h, router, bias = _routed(scene, held)
    options = dict(score=score, bias=bias if score == "sigmoid" else None,
                   scale=2.5, held=held, renormalise=True)
    experts, weights, order, inverse, sizes, _, probs = jax.jit(
        lambda h, router: moe.moe_route(h, router, k, **options))(h, router)
    want_experts, want, want_order, want_sizes, want_probs = jax.jit(
        lambda h, router: _held_route_as_it_was(h, router, k, **options))(
            h, router)
    assert inverse is None
    np.testing.assert_array_equal(np.asarray(experts),
                                  np.asarray(want_experts))
    for got, plain in ((weights, want), (sizes, want_sizes),
                       (probs, want_probs)):
        assert got.dtype == plain.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(plain))
    assigned = int(want_sizes.sum())
    np.testing.assert_array_equal(np.asarray(order)[:assigned],
                                  np.asarray(want_order)[:assigned])
    order = np.asarray(order)
    assert order.min() >= 0 and order.max() < h.shape[0] * held[1]
    pick = np.asarray(probs) + (np.asarray(bias) if score == "sigmoid"
                                else 0.0)
    kth = np.sort(pick, -1)[:, -k][:, None]
    if scene == "ties":     # a threshold alone would choose too many
        assert ((pick >= kth).sum(-1) > k).any()
    if scene == "crowd":
        assert int(sizes[0]) == h.shape[0]
    if scene == "none":
        assert assigned == 0


@pytest.mark.parametrize("held, crowd, rounds", [
    ((4, 4), None, 2), ((2, 2), None, 1), ((4, 4), 0, 0), ((4, 4), 2, 3),
    ((2, 3), 3, 3), ((1, 6), 3, 3)],
    ids=["bound-T-x-k", "bound-T-x-held", "no-row", "two-rounds-and-more",
         "every-held-expert", "three-of-six-held"])
def test_held_layer_drops_nothing_under_the_most_uneven_routing(
        held, crowd, rounds):
    """The rounds that do work, from none to all ``min(k, count)``: no
    token on a held expert; one held expert with every token and another
    with none (of two held: one round, exactly full; of four: a second
    round for what the others got); every token on two and on every held
    expert (more rows than a round holds, so the loop past the first round
    runs, to a last round partly or wholly full). Nothing is dropped, the
    rows past the real count multiply nothing, and the output and
    gradients are still the reference's."""
    layer, params, buffers, h = _latent_layer(held, skewed=crowd is None,
                                              crowd=crowd)
    *_, sizes, _, _ = jax.jit(lambda h, router, bias: moe.moe_route(
        h, router, 3, score="sigmoid", bias=bias, scale=2.5, held=held))(
            h, params["router"], buffers["choice_bias"])
    n_tokens = h.shape[0]
    if crowd is None:
        assert int(sizes[0]) == n_tokens and int(sizes[1]) == 0
    else:
        assert all(int(n) == n_tokens for n in sizes[:crowd])
    # (``rounds``: the rows a token that are assigned, rounded up; the
    # layer works them in rounds of ``held_rows``' rows, its most at most)
    assert -(-int(sizes.sum()) // n_tokens) == rounds
    most, rows = moe.held_rows(n_tokens, 3, held, 8)
    assert -(-int(sizes.sum()) // rows) <= most
    cot = jax.random.normal(jax.random.key(5), h.shape)
    variables = lambda p: {"params": p, "buffers": buffers}
    program = lambda p, h: jnp.sum(layer.apply(variables(p), h)[0] * cot)
    plain = lambda p, h: jnp.sum(latent_reference.experts_layer(
        h, p, buffers["choice_bias"], _latent_config(held))[0] * cot)
    _close(jax.jit(lambda p, h: layer.apply(variables(p), h)[0])(params, h),
           latent_reference.experts_layer(
               h, params, buffers["choice_bias"], _latent_config(held))[0],
           "output")
    got = jax.jit(jax.grad(program, argnums=(0, 1)))(params, h)
    want = jax.grad(plain, argnums=(0, 1))(params, h)
    for name in params:
        _close(got[0][name], want[0][name], f"d {name}")
    _close(got[1], want[1], "d input")


def test_rows_past_the_assigned_are_masked_on_both_sides():
    """What the grouped product leaves in the tiles it does not visit is
    not read: of a round's rows only the first ``sum(group_sizes)`` are
    added to their tokens, whatever is in the others, and so it is with
    the gradient that comes back to them (the dispatch's transposed sum);
    and the cotangent that goes to the grouped product is zero past
    them."""
    token = jnp.array([3, 0, 3, 5, 1, 2])
    where = token, moe.token_sum.plan(token, 2, 6), 2
    np.testing.assert_array_equal(np.asarray(where[1].order)[:2], [1, 0])
    rows = jnp.full((6, 3), jnp.nan).at[:2].set(1.0)
    weight = jnp.full((6,), jnp.nan).at[:2].set(2.0)
    want = np.zeros((6, 3))
    want[[3, 0]] = 1.0
    np.testing.assert_array_equal(
        np.asarray(moe._sum_by_token(rows, where, 6)), want)
    np.testing.assert_array_equal(np.asarray(moe._add_to_tokens(
        jnp.zeros((6, 3)), rows, weight, where)), 2 * want)
    x = jnp.arange(18.0).reshape(6, 3)
    got, pull = jax.vjp(lambda x: moe._rows_of_tokens(x, where, 6), x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x)[token])
    np.testing.assert_array_equal(np.asarray(pull(rows)[0]), want)
    d_rows, d_weight = moe._add_to_tokens_transposed(
        jnp.ones((6, 3)), rows, weight, where)
    np.testing.assert_array_equal(
        np.asarray(d_rows), np.concatenate([np.full((2, 3), 2.0),
                                            np.zeros((4, 3))]))
    np.testing.assert_array_equal(np.asarray(d_weight), [3, 3, 0, 0, 0, 0])


def _one_pass_held_experts(tokens, weights, stacks, route, rounds):
    """The held experts' sum as it was made until PR 41, every movement
    one pass over a round's ``T`` rows and plain ``jax.numpy`` that JAX
    differentiates by itself: a gather of ``T`` rows, masks on both sides
    of the experts, the weights' product over ``[T, width]``, one
    scatter-add of ``T`` rows. ``rounds`` is read off the route by the
    test, where the layer's loop reads it on the device."""
    order, group_sizes = route
    n_tokens, count = weights.shape
    total = jnp.zeros(tokens.shape, jnp.float32)
    for r in range(rounds):
        ends = jnp.cumsum(group_sizes) - r * n_tokens
        sizes = jnp.diff(jnp.clip(ends, 0, n_tokens), prepend=0)
        real = (jnp.arange(n_tokens) < jnp.sum(sizes))[:, None]
        slots = order[r * n_tokens:(r + 1) * n_tokens]
        token, expert = slots // count, slots % count
        rows = moe.moe_experts(jnp.where(real, tokens[token], 0.0),
                               *stacks, sizes)
        weight = jnp.sum(jnp.where(expert[:, None] == jnp.arange(count),
                                   weights[token], 0.0), axis=-1)
        total = total.at[token].add(
            jnp.where(real, rows, 0.0).astype(jnp.float32)
            * weight[:, None])
    return total


@pytest.mark.parametrize("assigned", [0, 5, 16, 32, 45], ids=[
    "no-row", "under-a-piece", "whole-pieces", "every-row", "two-rounds"])
def test_pieces_are_the_one_pass_movement(assigned, monkeypatch):
    """A round's movements by pieces of the assigned rows (here 8 rows
    of a round of 32) against the one pass over all its rows that they
    replace: the gather of the tokens' rows and its transposed sum, the
    weighted sum by token and what its cotangent gives back to the rows
    and to the weights; then the held experts' whole sum and its
    gradients in tokens, weights and stacks, over no round, one and two.
    Float32 on the CPU: the gathers equal to the last bit, the sums to a
    rounding of the last (a token's rows are added in the order of the
    sort by token, not in the rows')."""
    monkeypatch.setattr(moe, "_PIECE", 8)
    monkeypatch.setattr(moe, "_held_round",
                        jax.jit(moe._held_round.__wrapped__,
                                static_argnames="rows"))
    n_tokens, count, width = 32, 3, 8
    assert moe.move_rows(n_tokens) == 8
    keys = jax.random.split(jax.random.key(assigned), 6)
    chosen = jnp.zeros(n_tokens * count, bool).at[jax.random.permutation(
        keys[0], n_tokens * count)[:assigned]].set(True).reshape(
            n_tokens, count)
    route = (moe._slots_by_expert(chosen),
             jnp.sum(chosen, axis=0, dtype=jnp.int32))
    tokens = jax.random.normal(keys[1], (n_tokens, width))
    weights = jnp.where(chosen, jax.random.uniform(keys[2], chosen.shape),
                        0.0)
    stacks = tuple(jax.random.normal(k, shape) for k, shape in zip(
        keys[3:], [(count, width, 12)] * 2 + [(count, 12, width)]))
    same = lambda got, want: jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)), got, want)
    near = lambda got, want: jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6), got, want)

    first = min(assigned, n_tokens)           # the first round's movements
    token = route[0][:n_tokens] // count
    where = token, moe.token_sum.plan(token, first, n_tokens), first
    by_token = np.asarray(where[1].order)[:first]   # the assigned, by token
    assert sorted(by_token) == list(range(first))
    assert (np.diff(np.asarray(token)[by_token]) >= 0).all()
    real = (jnp.arange(n_tokens) < first)[:, None]
    weight = jax.random.uniform(keys[2], (n_tokens,))
    g = jax.random.normal(keys[1], (n_tokens, width)) + 1.0
    got, pull = jax.vjp(lambda x: moe._rows_of_tokens(x, where, n_tokens),
                        tokens)
    want, plain = jax.vjp(lambda x: jnp.where(real, x[token], 0.0), tokens)
    same(got[:first], want[:first])
    near(pull(g), plain(g))
    near(moe._sum_by_token(g, where, n_tokens), plain(g)[0])
    add = lambda rows, weight: jnp.ones_like(rows).at[token].add(
        jnp.where(real, rows * weight[:, None], 0.0))
    want, plain = jax.vjp(add, tokens, weight)
    near(moe._add_to_tokens(jnp.ones_like(tokens), tokens, weight, where),
         want)
    same(moe._add_to_tokens_transposed(g, tokens, weight, where), plain(g))

    rounds = -(-assigned // n_tokens)
    cot = jax.random.normal(keys[5], (n_tokens, width))
    got = jax.jit(jax.value_and_grad(lambda *of: jnp.sum(
        moe._held_experts(*of, route, n_tokens) * cot), argnums=(0, 1, 2)))(
            tokens, weights, stacks)
    want = jax.value_and_grad(lambda *of: jnp.sum(_one_pass_held_experts(
        *of, route, rounds) * cot), argnums=(0, 1, 2))(
            tokens, weights, stacks)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b, "the held experts' sum and its gradients")
    if assigned == 0:
        assert not any(np.asarray(leaf).any()
                       for leaf in jax.tree.leaves(got))


def _eight_shares_of_16_of_128():
    """``sdar-30b-a3b``'s cut at small widths: a softmax router 128 wide, 8
    a token, renormalised over the chosen, SwiGLU experts, no shared expert;
    the eight shares of 16 add up to the layer that holds all 128 (the
    output: the gradient of the input is read on the latent layer's four
    shares)."""
    layer = lambda held: MoEMlp(128, 6, 8, dtype=jnp.float32, held=held,
                                renormalise=True)
    h = jax.random.normal(jax.random.key(0), (48, 16))
    params = jax.jit(layer(None).init)(jax.random.key(1), h)["params"]
    assert moe.held_rows(48, 8, (16, 16), 128) == (4, 2 * 48)
    every = lambda h: layer(None).apply({"params": params}, h)[0]

    def total(h):
        parts = 0.0
        for first in range(0, 128, 16):
            mine = {**params, **{name: params[name][first:first + 16]
                                 for name in ("gate", "up", "down")}}
            parts = parts + layer((first, 16)).apply({"params": mine}, h)[0]
        return parts

    _close(jax.jit(total)(h), jax.jit(every)(h),
           "sum of the eight shares against every expert held")


@pytest.mark.parametrize("crowd", [None, 2, "eight-shares-of-16-of-128"],
                         ids=["even", "one-share-full",
                              "softmax-eight-shares-of-16-of-128"])
def test_the_shares_of_the_experts_add_up(crowd):
    """Four chips, two experts each, of a layer of eight: the held
    experts' parts, each through the latent up-projection (linear, no
    bias), summed over the shares, with the shared expert, which every
    chip computes alike, counted once, are the layer that holds every
    expert (and the uncut reference's), in output and in the gradient of
    the input. ``one-share-full``: every token chooses both experts of the
    first share, which then works through two full rounds while the
    others share what is left. And, another layer (``sdar-30b-a3b``'s):
    eight shares of 16 of 128 under a softmax router renormalised over 8 a
    token (``_eight_shares_of_16_of_128``)."""
    if isinstance(crowd, str):
        return _eight_shares_of_16_of_128()
    whole, params, buffers, h = _latent_layer(None)
    if crowd:
        _, params, buffers, h = _latent_layer((0, 8), crowd=crowd)
    shared = lambda h: jnp.square(jax.nn.relu(h @ params["shared_up"])
                                  ) @ params["shared_down"]

    def total(h):
        parts = 0.0
        for first in (0, 2, 4, 6):
            share = _latent_moe((first, 2))
            mine = {**params, "up": params["up"][first:first + 2],
                    "down": params["down"][first:first + 2]}
            out, _ = share.apply({"params": mine, "buffers": buffers}, h)
            parts = parts + (out - shared(h))
        return parts, parts + shared(h)

    parts, got = jax.jit(total)(h)
    want, _ = latent_reference.experts_layer(
        h, params, buffers["choice_bias"], _latent_config(None))
    _close(got, want, "sum of the shares")
    every = lambda h: whole.apply({"params": params, "buffers": buffers},
                                  h)[0]
    _close(got, jax.jit(every)(h),
           "sum of the shares against every expert held")
    assert float(jnp.linalg.norm(parts)) > 0.1 * float(jnp.linalg.norm(want))
    cot = jax.random.normal(jax.random.key(7), h.shape)
    _close(jax.jit(jax.grad(lambda h: jnp.sum(total(h)[1] * cot)))(h),
           jax.jit(jax.grad(lambda h: jnp.sum(every(h) * cot)))(h), "d input")
    if crowd:
        *_, sizes, _, _ = moe.moe_route(
            h, params["router"], 3, score="sigmoid",
            bias=buffers["choice_bias"], scale=2.5, held=(0, 2))
        assert int(sizes.sum()) == 2 * h.shape[0]


def test_held_layer_gradient_program_scatters_a_rounds_rows_alone():
    """Routing weights, counts, the sort and the mask differentiate
    without a scatter-add, as in the layer that holds every expert, and
    since PR 41 a round's sums by token are no scatter-add either
    (``ops/sum_by_token.py``: a gather into the tokens' order and
    products). What is left beside the grouped product's bookkeeping is
    the transpose of the gather of a round's weights, ``T x count``
    numbers in one pass under ``moe_combine``: nothing adds rows of the
    layer's width, a piece's, a round's or the ``T x count`` slots'."""
    layer, params, buffers, h = _latent_layer((4, 4))

    def loss(params, h):
        out, _ = layer.apply({"params": params, "buffers": buffers}, h)
        return jnp.sum(out ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, h).compile()
    found = _scatters(compiled)
    sums = [(elements, name) for elements, name in found
            if "/jit(gmm)/" not in name and "/jit(tgmm)/" not in name]
    assert len(sums) == 1, sums
    for elements, name in found:
        if (elements, name) in sums:
            assert "/moe_combine/" in name, name
            assert elements == h.shape[0] * 4, (elements, name)  # T x count
        else:
            assert elements <= 4 + h.shape[0] * 3, (elements, name)


@pytest.mark.parametrize("tokens, width", [(32, 8), (44, 8), (7, 3),
                                           (96, 16), (1, 1)])
def test_the_kept_choice_is_a_bit_a_token_and_expert(tokens, width):
    """What ``models.GPT`` keeps of a held layer's choice under ``remat``
    (``HELD_CHOICE``): the mask packed 32 tokens a word, whatever the
    token count, and the same mask unpacked."""
    mask = jax.random.bernoulli(jax.random.key(tokens), 0.3, (tokens, width))
    bits = moe._pack(mask)
    assert bits.shape == (-(-tokens // 32), width)
    assert bits.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(moe._unpack(bits, tokens)),
                                  np.asarray(mask))
    np.testing.assert_array_equal(
        np.asarray(moe._unpack(moe._pack(jnp.ones_like(mask)), tokens)), True)


def _element_counts(text):
    """``{elements: dimensions as written}`` of every array type in a
    program's text, lowered (``tensor<44x5x8xi1>``) or compiled
    (``pred[44,5,8]``)."""
    dims = re.findall(r"tensor<((?:\d+x)+)\w+>", text) + re.findall(
        r"\w+\[((?:\d+,)*\d+)\]", text)
    return {math.prod(int(n) for n in re.split(r"[x,]", d) if n): d
            for d in dims}


@pytest.mark.parametrize("program", ["forward", "gradient"])
@pytest.mark.parametrize("kind", ["sigmoid-latent", "softmax-renormalised"])
def test_held_layer_program_has_nothing_T_k_E_and_one_sort_of_its_slots(
        kind, program):
    """A held layer's route is ``[T, E]`` work: neither as traced nor as
    compiled does the layer, forward or with its gradient, hold a value
    of ``T x k x E`` elements (the mask of the chosen indices against
    every expert that the route was made from until PR 37), and it sorts
    its ``T x count`` slots once: the second sort, the inverse
    permutation that nothing read, is not traced. The layer that holds
    every expert still has both (its weights are one a choice)."""
    # (6 held of 8 with 5 a token: a round is 5 x 44 rows, not the 6 x 44
    # slots)
    tokens, n_experts, k, held = 44, 8, 5, (2, 6)
    assert moe.held_rows(tokens, k, held, n_experts) == (1, 5 * tokens)
    options = (dict(score="sigmoid", route_scale=2.5, expert_act="relu2",
                    latent=16, shared_ff=20) if kind == "sigmoid-latent"
               else dict(renormalise=True, shared_ff=10, shared_gate=True))
    h = jax.random.normal(jax.random.key(0), (tokens, 16))

    def lowered(held):
        layer = MoEMlp(n_experts, 12, k, dtype=jnp.float32, held=held,
                       **options)
        variables = jax.jit(layer.init)(jax.random.key(1), h)
        forward = lambda h: jnp.sum(layer.apply(variables, h)[0] ** 2)
        return jax.jit(forward if program == "forward"
                       else jax.grad(forward)).lower(h)

    step = lowered(held)
    slots = tokens * held[1]
    for text in (step.as_text(), step.compile().as_text()):
        counts = _element_counts(text)
        assert tokens * k * n_experts not in counts, counts[
            tokens * k * n_experts]
    sorts = re.findall(r"stablehlo\.sort.*?\}\) : \(tensor<(\d+)xi32>",
                       step.as_text(), re.DOTALL)
    assert sorts.count(str(slots)) == 1, sorts
    every = lowered(None).as_text()
    assert tokens * k * n_experts in _element_counts(every)
    assert len(re.findall(r"stablehlo\.sort", every)) == 2


def _grouped_products(jaxpr):
    """``[(name, row counts)]`` of every grouped product (megablox's
    ``gmm`` and ``tgmm``, each a ``jax.jit``) in ``jaxpr`` and what it
    calls, a traced function counted once however many equations call it
    (``jax.jit`` hands every caller of one function at one shape the same
    jaxpr), and how often each primitive was met on the way (a kernel's
    own body left out: its ``cond`` is ``pl.when``, a grid step's, not a
    branch of the program)."""
    found, primitives, seen = [], collections.Counter(), set()

    def walk(jaxpr):
        jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
        if id(jaxpr) in seen:
            return
        seen.add(id(jaxpr))
        for eqn in jaxpr.eqns:
            primitives[eqn.primitive.name] += 1
            if eqn.params.get("name") in ("gmm", "tgmm"):
                found.append((eqn.params["name"], sorted(
                    {n for v in eqn.invars[:2] for n in v.aval.shape})))
                continue
            if eqn.primitive.name == "pallas_call":
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) else (
                        value,):
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                        walk(sub)

    walk(jaxpr)
    return found, primitives


@pytest.mark.parametrize("act, stacks", [("relu2", 2), ("swiglu", 3)])
@pytest.mark.parametrize("held, k", [((2, 2), 3), ((1, 6), 4), ((3, 1), 2)],
                         ids=["two-rounds", "four-rounds", "one-round"])
def test_held_layer_holds_its_round_once(held, k, act, stacks):
    """The set-up's proxy a CPU can read: whatever ``min(k, count)`` is,
    the program of a held layer holds the round's grouped products at one
    row count, a round's (``held_rows``: ``T``, or the whole multiple of it
    that a share expecting over 0.8 of a row a token works in: 4 ``T`` for
    6 of 8 held with 4 a token), and once: as many products as the layer's one pass
    had, a stack each in the forward program; in the gradient that, the
    round made again in the backward loop (a stack each) and its pullback
    (two a stack). The rounds are a ``while`` that the router's count
    bounds, and there is no ``cond`` and no ``scan``. A round's sums by
    token are one kernel each (``ops/sum_by_token.py``, whose grid steps
    past the assigned rows are skipped) and the cotangent's rows a loop
    over its pieces, each held once: in the forward program the rounds'
    loop, the weighted sum's kernel and no scatter-add; in the gradient
    the two loops over the rounds and the pieces' in the backward one,
    the two sums' kernels, and one scatter-add, the transpose of the
    weights' gather."""
    tokens = 40
    layer = MoEMlp(8, 12, k, dtype=jnp.float32, expert_act=act, held=held,
                   **({"score": "sigmoid"} if act == "relu2" else {}))
    h = jax.random.normal(jax.random.key(0), (tokens, 16))
    variables = jax.jit(layer.init)(jax.random.key(1), h)
    forward = lambda h: layer.apply(variables, h)[0]
    for program, products, loops in (
            (forward, stacks, (1, 1, 0)),
            (jax.grad(lambda h: jnp.sum(forward(h) ** 2)), 4 * stacks,
             (3, 2, 1))):
        found, primitives = _grouped_products(jax.make_jaxpr(program)(h))
        assert len(found) == products, found
        rows = moe.held_rows(tokens, k, held, 8)[1]
        assert rows == {(2, 2): 1, (1, 6): 4, (3, 1): 1}[held] * tokens
        for _, sizes in found:
            assert rows in sizes
            assert not [n for n in sizes if n % tokens == 0 and n != rows]
        assert "while" in primitives
        assert "cond" not in primitives and "scan" not in primitives
        assert (primitives["while"], primitives["pallas_call"],
                primitives["scatter-add"]) == loops


def test_layer_that_holds_every_expert_has_no_loop():
    """``held=None`` keeps its single pass over ``T x k`` rows: no
    ``cond`` and no ``while`` in forward or gradient, and its products are
    over every row."""
    layer, params, h = _layer(8, 2, skewed=False)
    forward = lambda h: layer.apply({"params": params}, h)[0]
    for program in (forward, jax.grad(lambda h: jnp.sum(forward(h) ** 2))):
        found, primitives = _grouped_products(jax.make_jaxpr(program)(h))
        assert found and all(2 * h.shape[0] in sizes for _, sizes in found)
        assert not set(primitives) & {"cond", "while", "scan"}


def test_held_layer_names_add_no_operation(monkeypatch):
    """The scopes of a round are names: the lowered gradient of a held
    layer is the same text without them; and they are there, inside the
    loop over the rounds, in the forward and in the backward pass, where a
    device trace's readers look for them."""
    import contextlib

    def lowered():
        # a round is a jax.jit that remembers its trace: build it anew
        monkeypatch.setattr(moe, "_held_round",
                            jax.jit(moe._held_round.__wrapped__,
                                static_argnames="rows"))
        layer, params, buffers, h = _latent_layer((4, 4))
        return jax.jit(jax.grad(lambda p, h: jnp.sum(layer.apply(
            {"params": p, "buffers": buffers}, h)[0] ** 2))).lower(params, h)

    step = lowered()
    # a round is lowered once and called: its call site's names are put
    # before its own when the program becomes HLO
    names = set(re.findall(r'op_name="([^"]*)"', step.compile().as_text()))
    for scope in ("moe_dispatch", "moe_experts", "moe_combine"):
        inside = [n for n in names if "/while/body/" in n
                  and f"/{scope}/" in n]
        assert [n for n in inside if "transpose(" in n], scope
        assert [n for n in inside if "transpose(" not in n], scope
    monkeypatch.setattr(jax, "named_scope",
                        contextlib.contextmanager(lambda name: (yield)))
    assert lowered().as_text() == step.as_text()


def test_held_layers_are_counted_by_what_they_hold():
    from horovod_tpu import metrics

    def count(held, round_rows, move_rows):
        m = metrics.registry().get("hvt_moe_layers_traced_total")
        return m.labels(experts="8", top_k="3", product=moe.PRODUCT,
                        held=held, round_rows=round_rows,
                        move_rows=move_rows).value if m else 0.0

    layer, params, buffers, h = _latent_layer((4, 4))
    # a round of 4 of 8 held with 3 a token is two rows a token (1.25 x 1.5
    # expected, rounded up); a piece of it the largest part of 2,048 rows
    # that divides it: 16 of 80, the whole of 2,048 at a cell's 16,384
    rows = moe.held_rows(h.shape[0], 3, (4, 4), 8)[1]
    rows, piece = str(rows), str(moe.move_rows(rows))
    assert (rows, piece) == ("80", "16")
    assert moe.move_rows(16384) == moe._PIECE == 2048
    before = count("4", rows, piece), count("8", "all", "all")
    jax.jit(lambda p, h: layer.apply(
        {"params": p, "buffers": buffers}, h)[0]).lower(params, h)
    assert (count("4", rows, piece), count("8", "all", "all")) == (
        before[0] + 1, before[1])


@pytest.mark.parametrize("field, value, match", [
    ("score", "tanh", "softmax or sigmoid"), ("held", (6, 4), "held"),
    ("held", (0, 0), "held")])
def test_layer_refuses_what_it_does_not_build(field, value, match):
    layer = MoEMlp(8, 12, 2, dtype=jnp.float32, **{field: value})
    with pytest.raises(ValueError, match=match):
        layer.init(jax.random.key(0), jnp.zeros((4, 16)))


def test_swiglu_layer_with_a_shared_expert_and_a_share():
    """The options compose with OLMoE's kind too: softmax scores, SwiGLU
    experts (three stacks, a gated shared expert), two of four held: the
    held experts' part against the loop over them."""
    layer = MoEMlp(4, 8, 2, dtype=jnp.float32, shared_ff=6, held=(1, 2))
    h = jax.random.normal(jax.random.key(0), (24, 16))
    params = jax.jit(lambda h: jax.tree.map(lambda w: w * 20.0, layer.init(
        jax.random.key(1), h)["params"]))(h)
    assert set(params) == {"router", "gate", "up", "down", "shared_up",
                           "shared_gate", "shared_down"}
    out, aux = jax.jit(lambda p, h: layer.apply({"params": p}, h))(params, h)
    assert set(aux) == {"load_balance", "router_z"}
    probs, _, experts = reference.route(h, params["router"], 2)
    want = (jax.nn.silu(h @ params["shared_gate"]) * (h @ params["shared_up"])
            ) @ params["shared_down"]
    for e in (1, 2):
        weight = jnp.where((experts == e).any(-1), probs[:, e], 0.0)
        hidden = jax.nn.silu(h @ params["gate"][e - 1]) * (
            h @ params["up"][e - 1])
        want = want + weight[:, None] * (hidden @ params["down"][e - 1])
    _close(out, want, "output")
