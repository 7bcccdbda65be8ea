"""The Pallas kernels of the delta rule with a decay a key channel
(``ops/channel_delta_rule.py``), interpreted on the CPU: output and all five
gradients (``dg`` by channel) against ``jax.grad`` of the plain ``jax.numpy``
body and against the float32 recurrence of
``chipbench/reference/kimi_linear.py``; the states the forward keeps; what
is float32 inside the kernels and that every exponent there is of a
non-positive number; lower precisions steered from outside; and which
program gets the kernels, under which names."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import kda
from horovod_tpu.ops import channel_delta_rule as rule_op
from horovod_tpu.ops import gated_delta_rule as scalar_op
from tests.test_gdn import _equations
from tests.test_gdn_kernel import _stacks, _with_gradients
from tests.test_kda import DECAY_REL, REL, _close, _operands, _recurrence

CHUNK, HEADS, D_K, D_V = 16, 2, 8, 4
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")
KERNELS = ("hvt_kda_inverse", "hvt_kda_fwd", "hvt_kda_bwd")


def _kernels(*a, chunk=CHUNK, **options):
    return rule_op.channel_delta_rule_kernels(*a, chunk=chunk, **options)


def _calls(fn, *args):
    return [eqn for eqn in _equations(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "pallas_call"]


@pytest.mark.parametrize("seq, strong, chunk", [
    (12, True, CHUNK), (48, True, CHUNK), (40, False, CHUNK),
    (40, False, 32)],
    ids=["one-chunk-padded-A-16", "three-chunks-A-16", "three-chunks-padded",
         "two-chunks-of-32-blocks-of-16-by-substitution"])
def test_kernels_match_the_plain_path_and_the_recurrence(seq, strong, chunk):
    """Forward and backward kernels against ``jax.grad`` of
    ``channel_delta_rule_plain`` and of the position-by-position
    recurrence: one and three chunks a sequence, lengths the chunk divides
    and does not (padded with positions whose g and beta are 0), a mild
    decay and the strongest the initialisation allows, two heads a grid step, d_k != d_v;
    and at a chunk of 32, where the shared inverse takes the diagonal
    blocks of 16 by substitution as it does at the cell's 128."""
    assert scalar_op._solved(chunk) == (16 if chunk == 32 else 0)
    args = _operands(seq, strong, batch=1, heads=HEADS)
    cot = jax.random.normal(jax.random.key(7), args[2].shape)
    with jax.default_matmul_precision("highest"):
        got = _with_gradients(functools.partial(_kernels, chunk=chunk),
                              cot)(*args)
        plain = _with_gradients(lambda *a: rule_op.channel_delta_rule_plain(
            *a, chunk=chunk), cot)(*args)
        slow = _with_gradients(_recurrence, cot)(*args)
    assert got[0].shape == args[2].shape and got[0].dtype == args[2].dtype
    for name, x, same, far in zip(NAMES, got, plain, slow):
        assert x.shape == same.shape and x.dtype == same.dtype, name
        assert np.all(np.isfinite(np.asarray(x))), name
        # at the strongest decay dg is a millionth of the other gradients
        # and the plain body's is 1e-4 to 7e-4 from a float64 recurrence's
        # (the kernels' 5e-7: they keep q_i . k_i, which no decay reaches,
        # out of the sum that cancels)
        _close(x, same, f"{name} against the plain path",
               REL if name != "dg" else 2e-3 if strong else DECAY_REL)
        _close(x, far, f"{name} against the recurrence", REL)


def test_bf16_operands_match_the_plain_path():
    """bf16 operands, float32 ``g`` and ``beta``: the kernels take every
    pair of positions through a bf16 product where the plain body takes
    the pairs inside a sub-block in float32, so the two are a bf16
    product apart, and ``dg`` and ``dbeta`` come out float32."""
    q, k, v, g, beta = _operands(40, batch=1, heads=HEADS, d_k=16, d_v=8)
    low = lambda t: t.astype(jnp.bfloat16)
    args = (low(q), low(k), low(v), g, beta)
    cot = low(jax.random.normal(jax.random.key(7), v.shape))
    got = _with_gradients(_kernels, cot)(*args)
    plain = _with_gradients(lambda *a: rule_op.channel_delta_rule_plain(
        *a, chunk=CHUNK), cot)(*args)
    for name, x, same in zip(NAMES, got, plain):
        assert x.shape == same.shape and x.dtype == same.dtype, name
        _close(x, same, f"{name} against the plain path", 2e-2)
    assert got[4].dtype == got[5].dtype == jnp.float32
    assert got[4].shape == g.shape


def test_the_state_a_chunk_is_entered_with_is_the_recurrences():
    """What the forward kernel keeps for the backward: the state entering
    chunk ``n`` is the recurrence's after ``n`` chunks of positions,
    transposed (a row a value channel)."""
    q, k, v, g, beta = _operands(40, batch=1, heads=HEADS)

    def position(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[:, :, None] * state
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[:, :, None] * (
            beta_t[:, None] * (v_t - read))[:, None, :]
        return state, (state, jnp.einsum("hkv,hk->hv", state, q_t))

    _, (states, o) = jax.lax.scan(
        position, jnp.zeros((HEADS, D_K, D_V)),
        (q[0], k[0], v[0], g[0], beta[0]))
    _close(o, _recurrence(q, k, v, g, beta)[0],
           "the test's recurrence against the reference's")
    plan, operands, _ = rule_op._prepare(
        q, k, v, g, beta, CHUNK, jnp.float32, kda.INVERSE_PRECISION)
    entering = rule_op._rule_fwd(*operands, plan)[1][-1]
    assert entering.shape == (1, 3, HEADS, D_V, D_K)
    assert entering.dtype == jnp.float32
    assert not np.any(np.asarray(entering[0, 0]))
    for n in (1, 2):
        _close(jnp.swapaxes(entering[0, n], -1, -2), states[n * CHUNK - 1],
               f"entering chunk {n}")


def test_decays_inverse_and_states_are_float32_inside_the_kernels():
    """The sister of ``test_state_and_decays_are_float32_in_a_bf16_layer``
    for the kernels' own jaxprs, bf16 operands, two heads a grid step:
    every ``exp`` of a float32; the only float32 products are the
    inverse's, at ``kda.INVERSE_PRECISION``: the steps of the inverse by
    blocks after its first in ``hvt_kda_inverse`` (the scalar rule's own
    function) and the inverse's transpose (two) in ``hvt_kda_bwd``, none
    in ``hvt_kda_fwd`` (the sums of ``g`` are additions); the scratch of
    those sums, the states ``S`` and ``dS`` and the kept states float32;
    the kernels take ``g`` and hand back ``dg`` float32; all other
    products bf16."""
    # shared, not copied: the kernels call the scalar module's own
    assert rule_op.scalar_rule is scalar_op
    assert not hasattr(rule_op, "_unit_lower_inverse")
    low = lambda t: t.astype(jnp.bfloat16)
    q, k, v, g, beta = _operands(32, batch=1, heads=2)
    args, cot = (low(q), low(k), low(v), g, beta), low(v)
    rule = lambda *a: _kernels(*a, state_dtype=kda.STATE_DTYPE,
                               precision=kda.INVERSE_PRECISION)
    calls = {eqn.params["name"]: eqn
             for eqn in _calls(lambda *a: jax.vjp(rule, *a)[1](cot), *args)}
    steps = 2 * (int(np.log2(CHUNK)) - 1)
    float32_products = {"hvt_kda_inverse": 2 * steps, "hvt_kda_fwd": 0,
                        "hvt_kda_bwd": 2 * 2}
    assert set(calls) == set(float32_products)
    for name, expected in float32_products.items():
        body = calls[name].params["jaxpr"]
        scratch = [v.aval for v in body.invars[-(1 + (
            name != "hvt_kda_inverse")):]]
        assert all(s.dtype == jnp.float32 for s in scratch), name
        assert scratch[0].shape == (2, CHUNK, D_K), name
        assert [s.shape for s in scratch[1:]] == [(2, D_V, D_K)] * (
            len(scratch) - 1), name
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
                        kda.INVERSE_PRECISION,) * 2, eqn
        assert seen["float32_products"] == expected, (name, seen)
        assert seen["exp"] and seen["bf16_products"], (name, seen)
    (inverse,) = [v.aval for v in calls["hvt_kda_inverse"].outvars]
    _, entering = [v.aval for v in calls["hvt_kda_fwd"].outvars]
    assert entering.dtype == jnp.float32 and inverse.dtype == jnp.bfloat16
    assert entering.shape == (1, 2, 2, D_V, D_K)
    assert inverse.shape == (1, 2, 2, CHUNK, CHUNK)
    for name in KERNELS:            # g goes in float32, by channel
        assert calls[name].invars[2 + (name != "hvt_kda_inverse")
                                  ].aval.dtype == jnp.float32
    dg = calls["hvt_kda_bwd"].outvars[3].aval
    assert dg.dtype == jnp.float32 and dg.shape == (1, 32, 2 * D_K)


def test_every_exponent_inside_the_kernels_is_of_a_non_positive_number(
        monkeypatch):
    """The module's promise, watched inside the three kernels: at the
    strongest decay (a cumulative sum of some -1,000 inside a chunk) every
    ``exp`` they take, forward and backward, is of a number that is at
    most 0, and every value they make is finite."""
    seen = []
    real = jnp.exp

    def watched(x):
        out = real(x)
        jax.debug.callback(
            lambda top, finite: seen.append((float(top), bool(finite))),
            jnp.max(x), jnp.all(jnp.isfinite(out)))
        return out

    args = _operands(32, strong=True, batch=1, heads=2, d_k=16)
    assert float(jnp.min(jnp.cumsum(args[3], axis=1))) < -900.0
    jax.clear_caches()      # the kernels' calls are jitted: trace them anew
    monkeypatch.setattr(rule_op.jnp, "exp", watched)
    out, grads = jax.value_and_grad(
        lambda *a: jnp.sum(_kernels(*a)), argnums=(0, 1, 2, 3, 4))(*args)
    jax.effects_barrier()
    monkeypatch.undo()
    jax.clear_caches()
    # a kernel and chunk and head: exp(G), exp(G_last - G), exp(G_last) and
    # one a level
    assert len(seen) >= 3 * 2 * 2 * (3 + int(np.log2(CHUNK)))
    assert max(top for top, _ in seen) <= 0.0
    assert all(finite for _, finite in seen)
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in (out, *grads))


def test_float32_parts_in_bf16_reach_the_kernels(monkeypatch):
    """``test_kda.py``'s test of the family's ``float32_parts`` comparison
    with the rule sent to the kernels: the sound layer is at summation
    order, and the two of ``benchmarks/kimilinear_wrong_programs.py``'s
    lower precisions that the kernels have to be told of (the module's
    state dtype, through the plan; the carried state alone, through
    ``_carried``; ``g`` rounded once is rounded before either body),
    steered from outside as the script steers them, are beyond the
    family's bound."""
    from benchmarks import kimilinear_wrong_programs as script
    from benchmarks.qwen3next_wrong_programs import _swapped
    from chipbench.families import kimi_linear as family
    from tests.test_kda import _CONFIG, HEADS, RANK, D_H, _mixer, _run

    monkeypatch.setattr(rule_op, "serves", lambda *shape: True)
    layer, params, u = _mixer(chunk=8, seq=32, batch=1)
    low = kda.KimiDeltaAttention(HEADS, D_H, 4, RANK, chunk=8,
                                 dtype=jnp.bfloat16)

    def found():
        _, sown = _run(low, params, u, mutable=["intermediates"])
        return family.mixer_distances(
            {name: value[0] for name, value in sown["intermediates"].items()},
            params, _CONFIG, "kda", layer)

    sound = found()
    assert _kernel_counts(8, D_H, D_H)["fwd"] >= 1    # the kernels ran it
    assert sound["float32_parts"] < 1e-5 < 1e-3 < sound["mixer"]
    for wrong in (lambda: _swapped(kda, "STATE_DTYPE", jnp.bfloat16),
                  script._state_rounded):
        with wrong():
            far = found()["float32_parts"]
        assert far > family.MIXER_BOUNDS["kda", "float32_parts"]
        assert far > 100 * sound["float32_parts"]


def test_a_recomputed_forward_keeps_the_inverses():
    """Under ``jax.checkpoint`` with the policy ``models.GPT`` gives its
    blocks, the backward pass makes the forward kernel again (for the
    states it reads) and not the inverses' (they carry the name
    ``KEPT_INVERSE``), and the gradients are the ones without it."""
    args = _operands(40, batch=1, heads=HEADS)
    cot = jax.random.normal(jax.random.key(7), args[2].shape)
    rule = lambda *a: jnp.sum(_kernels(*a) * cot)
    kept = jax.checkpoint(
        rule, policy=jax.checkpoint_policies.save_only_these_names(
            rule_op.KEPT_INVERSE))
    all_five = tuple(range(5))
    made = [eqn.params["name"]
            for eqn in _calls(jax.grad(kept, all_five), *args)]
    assert sorted(made) == ["hvt_kda_bwd", "hvt_kda_fwd", "hvt_kda_fwd",
                            "hvt_kda_inverse"], made
    assert rule_op.KEPT_INVERSE != scalar_op.KEPT_INVERSE
    for name, x, y in zip(NAMES[1:], jax.jit(jax.grad(kept, all_five))(*args),
                          jax.jit(jax.grad(rule, all_five))(*args)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), name)


# ---- which program gets the kernels, and the names the trace reads

def _kernel_counts(chunk, key_dim, value_dim):
    from horovod_tpu import metrics

    m = metrics.registry().get("hvt_kda_kernel_traces_total")
    return {kernel: m.labels(kernel=kernel, chunk=str(chunk),
                             key_dim=str(key_dim), value_dim=str(value_dim),
                             solved=str(scalar_op._solved(chunk))
                             ).value if m else 0.0
            for kernel in ("inverse", "fwd", "bwd")}


def test_the_choice_and_the_names(monkeypatch):
    """On the CPU ``channel_delta_rule`` lowers to no ``pallas_call``; on a
    TPU backend the kernels' chunk with heads of 128, bf16 or float32
    operands, goes to the kernels and a head of 64, a chunk of 32 or
    float16 operands to ``jax.numpy`` without raising; with the kernels
    forced, the forward, recomputed and backward steps of two layers hold
    ``hvt_kda_fwd`` and ``hvt_kda_bwd`` (and the forward step
    ``hvt_kda_inverse``, kept under ``remat``) under the scope
    ``kda_rule`` (what ``chipbench/layer_metrics/kda_rule_ms.py`` matches),
    the layers share a trace a kernel, and the counter counts them."""
    from horovod_tpu.models import GPT, GPTConfig

    c = rule_op.CHUNK
    wide = _operands(c, batch=1, heads=1, d_k=128, d_v=128)
    narrow = _operands(c, batch=1, heads=1, d_k=64, d_v=128)
    rule = lambda *a: rule_op.channel_delta_rule(*a)
    assert rule_op.chunk_for(8192) == c and rule_op.chunk_for(24) == 24
    assert not rule_op.serves(c, 128, 128, jnp.bfloat16)
    assert not _calls(rule, *wide)
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        assert rule_op.serves(c, 128, 128, jnp.bfloat16)
        assert rule_op.serves(c, 256, 128, jnp.float32)
        assert not rule_op.serves(c, 128, 128, jnp.float16)
        assert not rule_op.serves(32, 128, 128, jnp.bfloat16)
        assert not rule_op.serves(c, 64, 128, jnp.bfloat16)
        assert not rule_op.serves(2 * c, 128, 128, jnp.bfloat16)
        assert not _calls(rule, *narrow)
        assert not _calls(lambda *a: rule_op.channel_delta_rule(
            *a, chunk=32), *wide)
    assert [eqn.params["name"] for eqn in _calls(
        lambda *a: _kernels(*a, chunk=c), *wide)] == list(KERNELS[:2])

    model = GPT(GPTConfig(
        vocab_size=64, n_layers=3, layer_pattern="K*K", d_model=32,
        n_heads=4, d_ff=16, dtype=jnp.float32, remat=True, use_flash=False,
        kda_heads=2, kda_head_dim=8, kda_gate_rank=4))
    tokens = jnp.zeros((2, 20), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)["params"]
    loss = lambda p: model.apply({"params": p}, tokens).mean()
    assert "hvt_kda" not in jax.jit(jax.grad(loss)).lower(params).as_text()

    before = _kernel_counts(32, 8, 8)    # 20 positions: a chunk of 32
    with monkeypatch.context() as m:
        m.setattr(rule_op, "serves", lambda *shape: True)
        jax.clear_caches()
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    jax.clear_caches()
    under = {name: [] for name in KERNELS}
    for eqn, stack in _stacks(jaxpr.jaxpr):
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] in under):
            under[eqn.params["name"]].append(stack)
    forward = [n for n in under["hvt_kda_fwd"]
               if "rematted_computation" not in n]
    again = [n for n in under["hvt_kda_fwd"] if "rematted_computation" in n]
    for stacks, inside in ((forward, "jvp("), (again, "transpose(jvp("),
                           (under["hvt_kda_bwd"], "transpose(jvp("),
                           (under["hvt_kda_inverse"], "jvp(")):
        assert len(stacks) == 2, under
        for layer, stack in zip(sorted((0, 2), reverse=inside != "jvp("),
                                stacks):
            assert f"/block_{layer}/kda/kda_rule/" in stack, stack
            assert inside in stack, stack
    assert not [n for n in forward + under["hvt_kda_inverse"]
                if "transpose" in n]
    after = _kernel_counts(32, 8, 8)
    # two layers share a trace: one a kernel for each context JAX traces
    # it in (the forward pass and its recomputation), never one a layer
    assert after["bwd"] - before["bwd"] == 1
    assert 1 <= after["fwd"] - before["fwd"] <= 2
    assert 1 <= after["inverse"] - before["inverse"] <= 2
