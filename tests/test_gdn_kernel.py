"""The gated delta rule's Pallas kernels (``ops/gated_delta_rule.py``),
interpreted on the CPU: output and all five gradients against ``jax.grad``
of the plain ``jax.numpy`` body of ``models/gdn.py`` and against the float32
recurrence of ``chipbench/reference/qwen3_next.py``; the states the forward
keeps; what is float32 inside the kernels; a state dtype steered from
outside; and which program gets the kernels, under which names."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import qwen3_next as family
from chipbench.reference import qwen3_next as reference
from horovod_tpu.models import gdn
from horovod_tpu.ops import gated_delta_rule as rule_op
from horovod_tpu.ops import head_norm as norm_op
from tests.test_gdn import _equations

REL = 2e-5          # float32 on both sides: summation order alone differs
DECAY_REL = 2e-4    # ... but for g: tests/test_gdn.py's DECAY_REL
CHUNK, D_K, D_V = 16, 8, 4


def _close(got, want, what, rel=REL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
    assert err <= rel, f"{what}: relative error {err:.2e}"


def _operands(seq, per_key, batch=2, key_heads=2, d_k=D_K, d_v=D_V,
              dtype=jnp.float32, seed=0):
    """Strong decays and gates across the whole of (0, 1), so that the
    carried state and the inverse both matter."""
    rng = np.random.RandomState(seed + seq + per_key)
    heads = key_heads * per_key
    normal = lambda *dims: jnp.asarray(rng.normal(size=dims), jnp.float32)
    unit = lambda x: norm_op.l2_norm(x, eps=gdn.L2_EPS)
    q = unit(normal(batch, seq, key_heads, d_k)) * d_k ** -0.5
    k = unit(normal(batch, seq, key_heads, d_k))
    v, cot = normal(batch, seq, heads, d_v), normal(batch, seq, heads, d_v)
    g = -jnp.asarray(rng.uniform(0, 2, (batch, seq, heads)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (batch, seq, heads)), jnp.float32)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g,
            beta), cot.astype(dtype)


def _with_gradients(rule, cot):
    return jax.jit(lambda *a: (lambda o, vjp: (o, *vjp(cot)))(
        *jax.vjp(rule, *a)))


NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


@pytest.mark.parametrize("per_key", [1, 2],
                         ids=["a-value-head-a-key-head", "two-a-key-head"])
@pytest.mark.parametrize("seq", [16, 12, 48, 40],
                         ids=["one-chunk", "one-chunk-padded", "three-chunks",
                              "three-chunks-padded"])
def test_kernels_match_the_plain_path_and_the_recurrence(seq, per_key):
    """Forward and backward kernels against ``jax.grad`` of
    ``gated_delta_rule_plain`` and of the position-by-position
    recurrence: one and three chunks a sequence, lengths the chunk divides
    and does not (padded with positions whose g and beta are 0), one and
    two value heads a key head, d_k != d_v."""
    args, cot = _operands(seq, per_key)

    def recurrence(q, k, v, g, beta):
        wide = lambda t: jnp.repeat(t, per_key, axis=2)
        return jax.vmap(reference.delta_rule)(wide(q), wide(k), v, g, beta)

    got = _with_gradients(lambda *a: rule_op.gated_delta_rule_kernels(
        *a, chunk=CHUNK), cot)(*args)
    plain = _with_gradients(lambda *a: rule_op.gated_delta_rule_plain(
        *a, chunk=CHUNK), cot)(*args)
    slow = _with_gradients(recurrence, cot)(*args)
    assert got[0].shape == args[2].shape and got[0].dtype == args[2].dtype
    for name, x, same, far in zip(NAMES, got, plain, slow):
        assert x.shape == same.shape and x.dtype == same.dtype, name
        _close(x, same, f"{name} against the plain path")
        _close(x, far, f"{name} against the recurrence",
               DECAY_REL if name == "dg" else REL)


@pytest.mark.parametrize("per_key", [1, 2])
def test_the_state_a_chunk_is_entered_with_is_the_recurrences(per_key):
    """What the forward kernel keeps for the backward: the state entering
    chunk ``n`` is the recurrence's after ``n`` chunks of positions."""
    (q, k, v, g, beta), _ = _operands(40, per_key, batch=1)

    def position(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[:, None, None] * state
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[:, :, None] * (
            beta_t[:, None] * (v_t - read))[:, None, :]
        return state, (state, jnp.einsum("hkv,hk->hv", state, q_t))

    wide = lambda t: jnp.repeat(t[0], per_key, axis=1)
    heads = v.shape[2]
    _, (states, o) = jax.lax.scan(
        position, jnp.zeros((heads, D_K, D_V)),
        (wide(q), wide(k), v[0], g[0], beta[0]))
    _close(o, reference.delta_rule(wide(q), wide(k), v[0], g[0], beta[0]),
           "the test's recurrence against the reference's")
    plan, operands, _ = rule_op._prepare(
        q, k, v, g, beta, CHUNK, jnp.float32, gdn.INVERSE_PRECISION)
    entering = rule_op._rule_fwd(*operands, plan)[1][-1]
    assert entering.shape == (1, 3, heads, D_K, D_V)
    assert entering.dtype == jnp.float32
    assert not np.any(np.asarray(entering[0, 0]))
    for n in (1, 2):
        _close(entering[0, n], states[n * CHUNK - 1], f"entering chunk {n}")


def test_decays_inverse_and_states_are_float32_inside_the_kernels():
    """The sister of ``test_state_and_decays_are_float32_in_a_bf16_layer``
    for the kernels' own jaxprs, bf16 operands: every ``exp`` and the
    cumulative sum of a float32; the only float32 products are the
    inverse's, at ``gdn.INVERSE_PRECISION``: two a step of the inverse by
    blocks after its first in ``hvt_gdn_inverse``, the transpose's two in
    ``hvt_gdn_bwd``, which reads the inverse the forward kept, none in
    ``hvt_gdn_fwd``; the scratch states ``S`` and ``dS`` and the kept
    states float32; all other products bf16."""
    args, cot = _operands(32, 2, batch=1, dtype=jnp.bfloat16)
    rule = lambda *a: rule_op.gated_delta_rule_kernels(
        *a, chunk=CHUNK, state_dtype=gdn.STATE_DTYPE,
        precision=gdn.INVERSE_PRECISION)
    jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(rule, *a)[1](cot))(*args)
    calls = {eqn.params["name"]: eqn
             for eqn in _equations(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"}
    outer = [eqn for eqn in _equations(jaxpr.jaxpr)
             if eqn.primitive.name == "cumsum"]
    assert outer and all(e.invars[0].aval.dtype == jnp.float32
                         for e in outer)
    steps = 2 * (int(np.log2(CHUNK)) - 1)
    # a grid step's four value heads, unrolled
    float32_products = {"hvt_gdn_inverse": 4 * steps, "hvt_gdn_fwd": 0,
                        "hvt_gdn_bwd": 4 * 2}
    assert set(calls) == set(float32_products)
    for name, expected in float32_products.items():
        body = calls[name].params["jaxpr"]
        if name != "hvt_gdn_inverse":       # it carries nothing
            scratch = body.invars[-1].aval
            assert scratch.dtype == jnp.float32, name
            assert scratch.shape == (4, D_K, D_V), name
        seen = {"exp": 0, "bf16_products": 0, "float32_products": 0}
        for eqn in _equations(body):
            if eqn.primitive.name == "exp":
                seen["exp"] += 1
                assert eqn.invars[0].aval.dtype == jnp.float32, eqn
            elif eqn.primitive.name == "dot_general":
                kinds = {v.aval.dtype for v in eqn.invars}
                assert len(kinds) == 1, eqn
                seen["bf16_products"] += kinds == {jnp.dtype(jnp.bfloat16)}
                if kinds == {jnp.dtype(jnp.float32)}:
                    seen["float32_products"] += 1
                    assert eqn.params["precision"] == (
                        gdn.INVERSE_PRECISION,) * 2, eqn
        assert seen["float32_products"] == expected, (name, seen)
        assert seen["exp"] and seen["bf16_products"], (name, seen)
    (inverse,) = [v.aval for v in calls["hvt_gdn_inverse"].outvars]
    _, entering = [v.aval for v in calls["hvt_gdn_fwd"].outvars]
    assert entering.dtype == jnp.float32 and inverse.dtype == jnp.bfloat16
    assert entering.shape == (1, 2, 4, D_K, D_V)
    assert inverse.shape == (1, 2, 4, CHUNK, CHUNK)


@pytest.mark.parametrize("size", [16, 24, 32, 128])
def test_inverse_through_half_the_rows_is_the_inverse(size):
    """The kernels' inverse by blocks, which from blocks of 8 up sends
    only the second halves' rows through its products, against the plain
    body's ``unit_lower_inverse`` and numpy's float64 inverse, at sizes
    that are and are not powers of two, with strongly correlated keys
    (every entry of ``N`` near one)."""
    rng = np.random.RandomState(size)
    plan = rule_op._Plan(size, 1, 1, 8, 8, 1, jnp.dtype(jnp.float32),
                         gdn.INVERSE_PRECISION, True)
    row, col = rule_op._positions(size)
    for system in (0.3 * rng.normal(size=(size, size)),
                   np.full((size, size), 0.999)):
        system = np.tril(system, -1)
        got = rule_op._unit_lower_inverse(jnp.asarray(system, jnp.float32),
                                          row, col, plan)
        _close(got, np.linalg.inv(np.eye(size) + system), "inverse", 1e-4)
        _close(got,
               rule_op.unit_lower_inverse(jnp.asarray(system, jnp.float32)),
               "against the plain path's", 1e-5)


def _cell_systems():
    """Strictly lower ``[128, 128]`` systems as the kernels form them
    (``beta_i (k_i . k_j) exp(G_i - G_j)``, unit keys) and two that are
    zero on one side of the blocks taken by substitution."""
    c = 128
    rng = np.random.RandomState(63)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    # qwen3next-s8192's ranges (benchmarks/gdn_kernels.py): beta a sigmoid
    # of a normal, g = -exp(normal) softplus(normal + 1) / 16
    keys = unit(rng.normal(size=(c, 128)))
    beta = 1 / (1 + np.exp(-rng.normal(size=(c, 1))))
    cum = np.cumsum(-np.exp(rng.normal()) * np.log1p(np.exp(
        rng.normal(size=c) + 1.0)) / 16)
    cells = beta * (keys @ keys.T) * np.exp(
        np.minimum(cum[:, None] - cum[None, :], 0.0))
    # every k_i . k_j near one, beta near one and no decay: the worst
    # conditioned system the rule can meet
    keys = unit(rng.normal(size=(c, 16)) + 3.0)
    correlated = rng.uniform(0.95, 1.0, (c, 1)) * (keys @ keys.T)
    dense = 0.3 * rng.normal(size=(c, c))
    block = np.arange(c) // rule_op._SOLVED
    same = block[:, None] == block[None, :]
    return {
        "cells-beta-and-decays": cells,
        "correlated-keys-beta-near-1": correlated,
        "one-block-of-16-alone": np.where(same & (block[:, None] == 3),
                                          dense, 0.0),
        "nothing-inside-the-blocks-of-16": np.where(same, 0.0, dense),
    }


EPS32, EPS16 = 2.0 ** -23, 2.0 ** -8


@pytest.mark.parametrize("name, state_dtype", [
    ("cells-beta-and-decays", jnp.float32),
    ("correlated-keys-beta-near-1", jnp.float32),
    ("one-block-of-16-alone", jnp.float32),
    ("nothing-inside-the-blocks-of-16", jnp.float32),
    ("cells-beta-and-decays", jnp.bfloat16),
    ("correlated-keys-beta-near-1", jnp.bfloat16)],
    ids=lambda v: v if isinstance(v, str) else jnp.dtype(v).name)
def test_blocks_of_16_by_substitution_are_the_inverse(name, state_dtype):
    """``_unit_lower_inverse`` alone at the kernels' chunk, where the
    diagonal blocks of 16 are taken by substitution and three levels by
    products: against numpy's float64 inverse, against the plain body's
    ``unit_lower_inverse`` and as a left inverse, ``max |T (I + N) - I|
    <= 32 eps max |T|`` (float32: observed 0.2 to 4 eps max |T|). In
    float32 within 2e-6 of both (observed 3e-7 at most, what doubling from
    blocks of 1 gave); with the state's dtype steered to bfloat16 every
    step of the substitution is rounded (``_rounder``), the result is a
    bfloat16's and within one bfloat16 ``eps`` (observed a tenth)."""
    c = 128
    low = rule_op._rounder(state_dtype)
    system = low(jnp.asarray(np.tril(_cell_systems()[name], -1),
                             jnp.float32))
    plan = rule_op._Plan(c, 1, 1, c, c, 1, jnp.dtype(state_dtype),
                         gdn.INVERSE_PRECISION, True)
    assert rule_op._solved(c) == 16
    got = rule_op._unit_lower_inverse(system, *rule_op._positions(c), plan)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(low(got)))
    whole = np.eye(c) + np.asarray(system, np.float64)
    want = np.linalg.inv(whole)
    plain = rule_op.unit_lower_inverse(system.astype(state_dtype),
                                       gdn.INVERSE_PRECISION)
    exact = state_dtype == jnp.float32
    _close(got, want, "against float64", 2e-6 if exact else 4 * EPS16)
    _close(got, plain, "against the plain body's",
           2e-6 if exact else EPS16)
    left = np.abs(np.asarray(got, np.float64) @ whole - np.eye(c)).max()
    assert left <= 32 * (EPS32 if exact else EPS16) * np.abs(want).max(), left
    # what is outside the lower triangle is exactly the identity's
    np.testing.assert_array_equal(np.triu(np.asarray(got)), np.eye(c))


def test_the_inverse_at_the_cells_chunk_makes_six_products():
    """``_unit_lower_inverse`` traced alone at a chunk of 128: the three
    levels above the blocks of 16 are six ``dot_general``, each on the 64
    rows of its blocks' second halves, at the mixer's precision, and the
    fifteen steps below them are one traced step that holds none; a chunk
    that 32 does not divide keeps every level a product. And both rules'
    trace counters say so at the cells' plans: ``solved="16"`` beside a
    chunk of 128 and heads of 128 x 128."""
    from horovod_tpu import metrics
    from horovod_tpu.ops import channel_delta_rule as channel_op

    def products(c):
        plan = rule_op._Plan(c, 1, 1, c, c, 1, jnp.dtype(jnp.float32),
                             gdn.INVERSE_PRECISION, True)
        jaxpr = jax.make_jaxpr(lambda n: rule_op._unit_lower_inverse(
            n, *rule_op._positions(c), plan))(jnp.zeros((c, c), jnp.float32))
        steps = [eqn for eqn in jaxpr.jaxpr.eqns
                 if eqn.primitive.name == "scan"]
        return ([eqn for eqn in _equations(jaxpr.jaxpr)
                 if eqn.primitive.name == "dot_general"], steps)

    made, steps = products(128)
    assert len(made) == 6
    for eqn in made:
        assert eqn.invars[0].aval.shape == (64, 128), eqn
        assert eqn.invars[1].aval.shape == (128, 128), eqn
        assert eqn.params["precision"] == (gdn.INVERSE_PRECISION,) * 2, eqn
    (step,) = steps
    assert step.params["length"] == 15 and step.params["unroll"] == 15
    assert not [eqn for eqn in _equations(step.params["jaxpr"].jaxpr)
                if eqn.primitive.name == "dot_general"]
    made, steps = products(16)
    assert len(made) == 2 * 3 and not steps     # m = 2, 4, 8

    count = lambda metric, **labels: metrics.registry().get(
        metric).labels(**{k: str(v) for k, v in labels.items()}).value
    like = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32)
    width = dict(kernel="inverse", chunk=128, key_dim=128, value_dim=128,
                 solved=16)
    plan = rule_op._Plan(128, 1, 1, 128, 128, 1, jnp.dtype(jnp.float32),
                         gdn.INVERSE_PRECISION, True)
    jax.eval_shape(lambda *a: rule_op._inverse_call(
        *a, plan=plan, dtype=jnp.bfloat16),
        like(1, 128, 128), like(1, 128, 1), like(1, 128, 1))
    assert count("hvt_gdn_kernel_traces_total", **width) >= 1
    plan = channel_op._Plan(128, 1, 128, 128, 1, jnp.dtype(jnp.float32),
                            gdn.INVERSE_PRECISION, True)
    jax.eval_shape(lambda *a: channel_op._inverse_call(
        *a, plan=plan, dtype=jnp.bfloat16),
        like(1, 128, 128), like(1, 128, 128), like(1, 128, 1))
    assert count("hvt_kda_kernel_traces_total", **width) >= 1


def _mixer_distance(dtype, monkeypatch, *, through_kernels):
    """``family.mixer_distance`` of a mixer of two key heads of 32 at 256
    positions (so that the rule's own chunk is the kernels' 128)."""
    from tests.test_gdn import _config, _mixer

    config = _config(2, 4) | {"linear_key_head_dim": 32,
                              "linear_value_head_dim": 32}
    layer, params, u = _mixer(key_heads=2, value_heads=4, dtype=dtype,
                              seq=256, batch=1, d_model=32, d_k=32, d_v=32)
    if through_kernels:
        monkeypatch.setattr(rule_op, "serves", lambda *shape: True)
    _, sown = layer.apply({"params": params}, u, mutable=["intermediates"])
    return family.mixer_distance(
        {k: v[0] for k, v in sown["intermediates"].items()}, params, config)


def test_a_state_dtype_steered_from_outside_reaches_the_kernels(monkeypatch):
    """``gdn.STATE_DTYPE`` set to bf16 from outside (what
    ``benchmarks/qwen3next_wrong_programs.py`` does) is refused through
    the kernels by the comparison that refuses it through the plain path:
    the family's bound on the mixer against the position-by-position
    reference; the kernels as they are pass it."""
    sound = _mixer_distance(jnp.bfloat16, monkeypatch, through_kernels=True)
    assert sound <= family.MIXER_REL_L2_BOUND, sound
    monkeypatch.setattr(gdn, "STATE_DTYPE", jnp.bfloat16)
    far = _mixer_distance(jnp.bfloat16, monkeypatch, through_kernels=True)
    assert not far <= family.MIXER_REL_L2_BOUND, (far, sound)


def test_a_recomputed_forward_keeps_the_inverses():
    """Under ``jax.checkpoint`` with the policy ``models.GPT`` gives its
    blocks, the backward pass makes the forward kernel again and not the
    inverses' (they carry the name ``KEPT_INVERSE``), and the gradients
    are the ones without it."""
    args, cot = _operands(40, 2, batch=1)
    rule = lambda *a: jnp.sum(
        rule_op.gated_delta_rule_kernels(*a, chunk=CHUNK) * cot)
    kept = jax.checkpoint(
        rule, policy=jax.checkpoint_policies.save_only_these_names(
            rule_op.KEPT_INVERSE))
    all_five = tuple(range(5))
    jaxpr = jax.make_jaxpr(jax.grad(kept, all_five))(*args)
    made = [eqn.params["name"] for eqn in _equations(jaxpr.jaxpr)
            if eqn.primitive.name == "pallas_call"]
    assert sorted(made) == ["hvt_gdn_bwd", "hvt_gdn_fwd", "hvt_gdn_fwd",
                            "hvt_gdn_inverse"], made
    for name, x, y in zip(NAMES[1:], jax.grad(kept, all_five)(*args),
                          jax.grad(rule, all_five)(*args)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), name)


# ---- which program gets the kernels, and the names the trace reads

def _has_pallas(fn, *args):
    return any(eqn.primitive.name == "pallas_call"
               for eqn in _equations(jax.make_jaxpr(fn)(*args).jaxpr))


def _kernel_counts():
    from horovod_tpu import metrics

    m = metrics.registry().get("hvt_gdn_kernel_traces_total")
    return {kernel: m.labels(kernel=kernel, chunk="20", key_dim="8",
                             value_dim="8", solved="0").value if m else 0.0
            for kernel in ("inverse", "fwd", "bwd")}


def _stacks(jaxpr, prefix=""):
    """Every equation with the name stack it runs under, through the
    nested jaxprs (a ``jax.jit``'s equations carry only their own)."""
    for eqn in jaxpr.eqns:
        here = f"{prefix}/{eqn.source_info.name_stack}"
        yield eqn, here
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else (
                    value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _stacks(inner, here)


def test_the_choice_and_the_names(monkeypatch):
    """On the CPU ``gated_delta_rule`` lowers to no ``pallas_call``
    and a small ``G`` model's lowered step is what the plain path gives;
    on a TPU backend a chunk of 128 with heads of 128 goes to the kernels
    and a head of 64 or a chunk of 8 to ``jax.numpy`` without raising;
    with the kernels forced, the forward, recomputed and backward steps of
    three layers hold ``hvt_gdn_fwd`` and ``hvt_gdn_bwd`` (and the forward
    step ``hvt_gdn_inverse``, kept under ``remat``) under the scope
    ``gdn_rule`` (what ``chipbench/layer_metrics/gdn_rule_ms.py`` matches;
    the lowered text is read in ``tests/test_chip_compile.py``) and the
    layers share a trace a kernel."""
    from horovod_tpu.models import GPT, GPTConfig

    wide, _ = _operands(128, 2, batch=1, key_heads=1, d_k=128, d_v=128)
    narrow, _ = _operands(128, 2, batch=1, key_heads=1, d_k=64, d_v=128)
    rule = lambda *a: rule_op.gated_delta_rule(*a)
    assert not rule_op.serves(128, 128, 128)
    assert not _has_pallas(rule, *wide)

    model = GPT(GPTConfig(
        vocab_size=64, n_layers=5, layer_pattern="G*G*G", d_model=32,
        n_heads=4, d_ff=16, dtype=jnp.float32, remat=True, use_flash=False,
        gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8))
    tokens = jnp.zeros((2, 20), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)["params"]
    loss = lambda p: model.apply({"params": p}, tokens).mean()
    step = jax.jit(jax.grad(loss))
    as_it_is = step.lower(params).as_text()
    assert "hvt_gdn" not in as_it_is
    with monkeypatch.context() as m:
        m.setattr(rule_op, "gated_delta_rule",
                  rule_op.gated_delta_rule_plain)
        jax.clear_caches()
        assert step.lower(params).as_text() == as_it_is

    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        assert rule_op.serves(128, 128, 128)
        assert rule_op.serves(128, 256, 128)
        assert not rule_op.serves(8, 128, 128)
        assert not rule_op.serves(128, 64, 128)
        assert not rule_op.serves(256, 128, 128)
        assert not _has_pallas(rule, *narrow)
        assert not _has_pallas(lambda *a: rule_op.gated_delta_rule(
            *a, chunk=8), *wide)
    assert _has_pallas(lambda *a: rule_op.gated_delta_rule_kernels(
        *a, chunk=128), *wide)

    before = _kernel_counts()
    with monkeypatch.context() as m:
        m.setattr(rule_op, "serves", lambda *shape: True)
        jax.clear_caches()
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    jax.clear_caches()
    under = {"hvt_gdn_inverse": [], "hvt_gdn_fwd": [], "hvt_gdn_bwd": []}
    for eqn, stack in _stacks(jaxpr.jaxpr):
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] in under):
            under[eqn.params["name"]].append(stack)
    forward = [n for n in under["hvt_gdn_fwd"]
               if "rematted_computation" not in n]
    again = [n for n in under["hvt_gdn_fwd"] if "rematted_computation" in n]
    backward = under["hvt_gdn_bwd"]
    for stacks, inside in ((forward, "jvp("), (again, "transpose(jvp("),
                           (backward, "transpose(jvp("),
                           (under["hvt_gdn_inverse"], "jvp(")):
        assert len(stacks) == 3, under
        for layer, stack in zip(sorted((0, 2, 4), reverse=inside != "jvp("),
                                stacks):
            assert f"/block_{layer}/gdn/gdn_rule/" in stack, stack
            assert inside in stack, stack
    assert not [n for n in forward + under["hvt_gdn_inverse"]
                if "transpose" in n]
    after = _kernel_counts()
    # three layers share a trace: one a kernel for each context JAX traces
    # it in (the forward pass and its recomputation), never one a layer
    assert after["bwd"] - before["bwd"] == 1
    assert 1 <= after["fwd"] - before["fwd"] <= 2
    assert 1 <= after["inverse"] - before["inverse"] <= 2
