"""MoE / expert-parallelism tests: the dropless top-k router, the sorted
dispatch and its inverse, the grouped products against the plain float32
reference (``chipbench/reference/olmoe.py``), compiled execution on a
dp×ep mesh, and Qwen3-Next's renormalised softmax layer (a chip's share of
a layer: ``tests/test_moe_held.py``). Float32 and tiny sizes: the Pallas
grouped product runs in interpret mode on the CPU."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench.reference import olmoe as reference
from horovod_tpu.models import moe
from horovod_tpu.models.moe import MoEMlp, moe_param_partition_spec
from horovod_tpu.parallel.mesh import make_parallel_mesh

REL = 1e-5      # float32 on both sides: summation order is all that differs


def _close(got, want, what):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
    assert err <= REL, f"{what}: relative error {err:.2e}"


def _layer(n_experts, k, skewed, tokens=48, d=16, d_ff=8, seed=0):
    """A float32 layer, its parameters and its input ``[tokens, d]``. A
    skewed router sends every token to expert 0 (the most one expert can
    take: a k-th of the assignments, all of them at k = 1) and none to
    the last."""
    layer = MoEMlp(n_experts, d_ff, k, dtype=jnp.float32)
    h = jax.random.normal(jax.random.key(seed), (tokens, d))
    params = layer.init(jax.random.key(seed + 1), h)["params"]
    # initialised at 0.02 the experts' output is 1e-5 of the input: scale
    # up so that a wrong row or weight cannot hide under the tolerance
    params = jax.tree.map(lambda w: w * 20.0, params)
    if skewed:
        h = h.at[:, 0].set(1.0)
        router = params["router"].at[0, 0].set(30.0).at[0, -1].set(-30.0)
        params = {**params, "router": router}
    return layer, params, h


def _scatters(compiled) -> list:
    """``(elements written to, op_name)`` of every scatter instruction
    of a compiled program (the opcode, not the word: a test's own name
    is in the program's metadata)."""
    found = []
    for line in compiled.as_text().splitlines():
        if " scatter(" in line:
            shape = re.search(r"= \w+\[([\d,]*)\]", line).group(1)
            found.append((math.prod(int(n) for n in shape.split(",") if n),
                          re.search(r'op_name="([^"]*)"', line).group(1)))
    return found


def _scalar(out, aux, cot):
    return (jnp.sum(out * cot) + 0.3 * aux["load_balance"]
            + 0.7 * aux["router_z"])


@pytest.mark.parametrize("skewed", [False, True], ids=["even", "skewed"])
@pytest.mark.parametrize("n_experts, k", [(8, 2), (64, 8), (4, 1)])
def test_layer_matches_reference(n_experts, k, skewed):
    """Output, both auxiliary losses and the gradient of every leaf and
    of the input against the reference's loop over experts; and nothing
    is dropped."""
    layer, params, h = _layer(n_experts, k, skewed)
    cot = jax.random.normal(jax.random.key(9), h.shape)

    def program(params, h):
        out, aux = layer.apply({"params": params}, h)
        return _scalar(out, aux, cot), (out, aux)

    def plain(params, h):
        out, load_balance, router_z, routing = reference.experts_layer(
            h, params, k)
        aux = {"load_balance": load_balance, "router_z": router_z}
        return _scalar(out, aux, cot), (out, aux, routing)

    (_, (out, aux)), grads = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True))(params, h)
    (_, (want, want_aux, routing)), want_grads = jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True)(params, h)
    _close(out, want, "output")
    for name in ("load_balance", "router_z"):
        _close(aux[name], want_aux[name], name)
    for name in ("router", "gate", "up", "down"):
        _close(grads[0][name], want_grads[0][name], f"d {name}")
    _close(grads[1], want_grads[1], "d input")
    # dropless: every assignment has its row in some group
    experts, _, order, inverse, sizes, *_ = moe.moe_route(
        h, params["router"], k)
    assert int(sizes.sum()) == h.shape[0] * k
    np.testing.assert_array_equal(
        np.sort(np.asarray(experts), -1),
        np.sort(np.asarray(routing["own"]), -1))
    if skewed:
        assert int(sizes[0]) == h.shape[0] and int(sizes[-1]) == 0


def test_router_sort_is_a_stable_permutation_by_expert():
    """``order`` lists the T x k assignments expert by expert, a token's
    rows in their own order; ``inverse`` undoes it; the group sizes are
    the lengths of the runs; the weights are the chosen probabilities,
    not renormalised."""
    _, params, h = _layer(8, 2, skewed=False)
    experts, weights, order, inverse, sizes, aux, probs = moe.moe_route(
        h, params["router"], 2)
    flat = np.asarray(experts).reshape(-1)
    order, inverse = np.asarray(order), np.asarray(inverse)
    assert sorted(order) == list(range(flat.size))
    np.testing.assert_array_equal(order[inverse], np.arange(flat.size))
    np.testing.assert_array_equal(order, np.argsort(flat, kind="stable"))
    np.testing.assert_array_equal(np.asarray(sizes),
                                  np.bincount(flat, minlength=8))
    np.testing.assert_allclose(
        np.asarray(probs),
        np.asarray(jax.nn.softmax(h @ params["router"], -1)), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(weights),
        np.take_along_axis(np.asarray(probs), np.asarray(experts), -1),
        rtol=1e-6)
    assert float(weights.sum(-1).max()) < 1.0       # top-2 of 8, as they are
    assert float(aux["load_balance"]) > 0 and float(aux["router_z"]) > 0


def test_layer_sows_what_its_router_saw_and_said():
    """For a caller that asks for ``intermediates`` (the benchmark's
    comparison with a float32 router): the router's input as the layer
    computed in, the probabilities it made of it and the experts it
    chose; a caller that does not ask gets the same output."""
    layer, params, h = _layer(8, 2, skewed=False)
    (out, _), sown = layer.apply({"params": params}, h.reshape(4, 12, 16),
                                 mutable=["intermediates"])
    sown = {name: value[0] for name, value in sown["intermediates"].items()}
    assert set(sown) == {"router_input", "router_probs", "experts"}
    np.testing.assert_array_equal(np.asarray(sown["router_input"]),
                                  np.asarray(h))
    probs, _, experts = reference.route(h, params["router"], 2)
    _close(sown["router_probs"], probs, "probabilities")
    np.testing.assert_array_equal(np.sort(np.asarray(sown["experts"]), -1),
                                  np.sort(np.asarray(experts), -1))
    plain, _ = layer.apply({"params": params}, h.reshape(4, 12, 16))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))


def test_router_drops_nothing_when_every_token_wants_one_expert():
    """The case a capacity would cut: identical tokens all choose the
    same expert, whose group is then every row; the output is that
    expert's for every token."""
    layer, params, _ = _layer(4, 1, skewed=False)
    h = jnp.ones((24, 16), jnp.float32)
    out, _ = layer.apply({"params": params}, h)
    experts, weights, _, _, sizes, *_ = moe.moe_route(h, params["router"], 1)
    e = int(experts[0, 0])
    assert sorted(np.asarray(sizes)) == [0, 0, 0, 24]
    want = float(weights[0, 0]) * (
        jax.nn.silu(h @ params["gate"][e]) * (h @ params["up"][e])
        @ params["down"][e])
    _close(out, want, "output")


def test_dispatch_and_combine_are_inverse_gathers():
    """``combine(dispatch(x))`` with unit weights is ``k x``, in value
    and in gradient, and the compiled gradient of the pair holds no
    scatter: the transpose of each gather is written as the gather by
    the inverse permutation."""
    k = 4
    _, params, h = _layer(8, k, skewed=False)
    _, _, order, inverse, *_ = moe.moe_route(h, params["router"], k)
    ones = jnp.ones((h.shape[0], k), jnp.float32)

    def pair(x):
        return moe.moe_combine(moe.moe_dispatch(x, order, inverse, k),
                               ones, order, inverse)

    np.testing.assert_allclose(np.asarray(pair(h)), k * np.asarray(h),
                               rtol=1e-6)
    cot = jax.random.normal(jax.random.key(3), h.shape)
    grad = jax.jit(jax.grad(lambda x: jnp.sum(pair(x) * cot)))
    np.testing.assert_allclose(np.asarray(grad(h)), k * np.asarray(cot),
                               rtol=1e-6)
    assert not _scatters(grad.lower(h).compile())


def test_layer_gradient_program_scatters_no_row():
    """The whole layer: routing weights, counts, dispatch, products and
    combine differentiate without a scatter-add over rows or weights.
    What is left is the grouped product's own bookkeeping (megablox's
    ``make_group_metadata``: which tile belongs to which group), over a
    vector of groups + tiles integers."""
    n_experts, k = 8, 2
    layer, params, h = _layer(n_experts, k, skewed=False)

    def loss(params, h):
        out, aux = layer.apply({"params": params}, h)
        return jnp.sum(out ** 2) + aux["load_balance"] + aux["router_z"]

    found = _scatters(jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, h).compile())
    for elements, name in found:
        assert "/jit(gmm)/" in name or "/jit(tgmm)/" in name, name
        assert elements <= n_experts + h.shape[0] * k, (elements, name)


def test_traced_layers_are_counted():
    from horovod_tpu import metrics

    def count():
        m = metrics.registry().get("hvt_moe_layers_traced_total")
        return m.labels(experts="8", top_k="2", product=moe.PRODUCT,
                        held="8", round_rows="all",
                        move_rows="all").value if m else 0.0

    layer, params, h = _layer(8, 2, skewed=False)
    before = count()
    jax.jit(lambda p, h: layer.apply({"params": p}, h)[0]).lower(params, h)
    assert count() == before + 1


def test_moe_compiles_on_dp_ep_mesh():
    """dp=2 × ep=4: tokens batch-sharded, experts ep-sharded; the jitted
    step must compile and run, and agree with the reference."""
    mesh = make_parallel_mesh(dp=2, ep=4)
    moe_layer = MoEMlp(n_experts=4, d_ff=32, experts_per_token=2,
                       dtype=jnp.float32)
    x = jnp.asarray(np.random.RandomState(4).randn(4, 16, 8)
                    .astype(np.float32))
    vars_ = moe_layer.init(jax.random.PRNGKey(5), x)
    plain = jax.tree.map(lambda w: w * 20.0, vars_["params"])
    pspecs = moe_param_partition_spec(plain)
    params = jax.tree.map(
        lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
        plain, pspecs, is_leaf=lambda v: isinstance(v, P))
    assert "ep" in str(params["gate"].sharding.spec)
    x_sharded = jax.device_put(x, NamedSharding(mesh, P("dp")))

    @jax.jit
    def step(params, x):
        out, aux = moe_layer.apply({"params": params}, x)
        return out.sum() + 0.01 * aux["load_balance"]

    # grads too: EP backward = the reverse exchanges
    val, grads = jax.value_and_grad(step)(params, x_sharded)
    jax.block_until_ready(val)

    def plain_step(params, x):
        out, load_balance, _, _ = reference.experts_layer(
            x.reshape(-1, x.shape[-1]), params, 2)
        return out.sum() + 0.01 * load_balance

    want, want_grads = jax.value_and_grad(plain_step)(plain, x)
    _close(val, want, "loss")
    for name in ("router", "gate", "up", "down"):
        _close(grads[name], want_grads[name], f"d {name}")


# ---- Qwen3-Next's expert layer: softmax scores renormalised over a
# token's chosen, a sigmoid gate on the shared expert

def _qwen_layer(held, renormalise=True, shared_gate=True):
    layer = MoEMlp(8, 12, 3, dtype=jnp.float32, shared_ff=10, held=held,
                   renormalise=renormalise, shared_gate=shared_gate)
    h = jax.random.normal(jax.random.key(0), (40, 16))
    whole = MoEMlp(8, 12, 3, dtype=jnp.float32, shared_ff=10,
                   renormalise=renormalise, shared_gate=shared_gate)
    params = jax.tree.map(lambda w: w * 15.0, whole.init(
        jax.random.key(1), h)["params"])
    return layer, params, h


_QWEN_LAYER = {"num_experts_per_tok": 3, "norm_topk_prob": True}


def test_renormalised_softmax_router():
    """``renormalise`` divides a token's chosen softmax weights by their
    sum, over all it chose whether held here or not; None keeps each
    score's habit (softmax: as they are; sigmoid: renormalised)."""
    h = jax.random.normal(jax.random.key(0), (40, 16))
    router = jax.random.normal(jax.random.key(1), (16, 8))
    experts, plain, *_, probs = moe.moe_route(h, router, 3)
    _, normed, *_ = moe.moe_route(h, router, 3, renormalise=True)
    np.testing.assert_allclose(np.asarray(normed.sum(-1)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(normed),
        np.asarray(plain / plain.sum(-1, keepdims=True)), rtol=1e-6)
    assert float(plain.sum(-1).min()) < 0.9
    np.testing.assert_allclose(
        np.asarray(plain), np.asarray(jnp.take_along_axis(probs, experts, 1)),
        rtol=1e-6)
    # a share's weights are the same numbers, sliced: their sum over the
    # held is what the token gave the held experts, not 1
    _, held, *_ = moe.moe_route(h, router, 3, renormalise=True, held=(2, 3))
    full = jnp.sum(jnp.where(experts[..., None] == jnp.arange(8),
                             normed[..., None], 0.0), axis=1)
    np.testing.assert_allclose(np.asarray(held), np.asarray(full[:, 2:5]),
                               rtol=1e-6)
    _, sig, *_ = moe.moe_route(h, router, 3, score="sigmoid")
    _, raw, *_ = moe.moe_route(h, router, 3, score="sigmoid",
                               renormalise=False)
    np.testing.assert_allclose(np.asarray(sig.sum(-1)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(sig), np.asarray(raw / raw.sum(-1, keepdims=True)),
        rtol=1e-6)


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["whole", "a-share"])
def test_qwen_layer_matches_reference(held):
    """Renormalised softmax weights, SwiGLU experts and the shared expert
    behind its sigmoid gate against chipbench/reference/qwen3_next.py:
    output and every gradient."""
    from chipbench.reference import qwen3_next as qwen_reference

    layer, params, h = _qwen_layer(held)
    assert params["shared_expert_gate"].shape == (16, 1)
    first, count = held or (0, 8)
    mine = {**params, **{name: params[name][first:first + count]
                         for name in ("gate", "up", "down")}}
    config = {**_QWEN_LAYER, "experts_held_first": first}
    out, aux = layer.apply({"params": mine}, h)
    want, _ = qwen_reference.experts_layer(h, mine, config)
    _close(out, want, "output")
    cot = jax.random.normal(jax.random.key(5), h.shape)
    got = jax.grad(lambda p, h: jnp.sum(
        layer.apply({"params": p}, h)[0] * cot), argnums=(0, 1))(mine, h)
    ref = jax.grad(lambda p, h: jnp.sum(
        qwen_reference.experts_layer(h, p, config)[0] * cot),
        argnums=(0, 1))(mine, h)
    for name in got[0]:
        _close(got[0][name], ref[0][name], f"d {name}")
    _close(got[1], ref[1], "d input")


@pytest.mark.parametrize("shares", [4, 2])
def test_the_shares_of_a_qwen_layer_add_up(shares):
    """The share test: the held experts' parts of the 4 (or 2) shares of a
    layer of eight, with the shared expert and its gate, which every chip
    computes alike, counted once, sum to the uncut reference layer; the
    weights are renormalised over all a token chose, so the parts are the
    whole layer's terms and not each share's own normalisation."""
    from chipbench.reference import qwen3_next as qwen_reference

    whole, params, h = _qwen_layer(None)
    count = 8 // shares
    shared = lambda h: jax.nn.sigmoid(h @ params["shared_expert_gate"]) * (
        (jax.nn.silu(h @ params["shared_gate"]) * (h @ params["shared_up"]))
        @ params["shared_down"])

    def total(h):
        parts = 0.0
        for first in range(0, 8, count):
            share, _, _ = _qwen_layer((first, count))
            mine = {**params, **{name: params[name][first:first + count]
                                 for name in ("gate", "up", "down")}}
            parts = parts + share.apply({"params": mine}, h)[0] - shared(h)
        return parts, parts + shared(h)

    parts, got = total(h)
    want, _ = qwen_reference.experts_layer(
        h, params, {**_QWEN_LAYER, "experts_held_first": 0})
    _close(got, want, "sum of the shares")
    _close(got, whole.apply({"params": params}, h)[0],
           "sum of the shares against every expert held")
    assert float(jnp.linalg.norm(parts)) > 0.1 * float(jnp.linalg.norm(want))
    cot = jax.random.normal(jax.random.key(7), h.shape)
    _close(jax.grad(lambda h: jnp.sum(total(h)[1] * cot))(h),
           jax.grad(lambda h: jnp.sum(whole.apply(
               {"params": params}, h)[0] * cot))(h), "d input")
