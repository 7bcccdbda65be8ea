"""The Kimi Delta Attention mixer (``horovod_tpu/models/kda.py``) and its
chunked delta rule with a decay a key channel
(``horovod_tpu/ops/channel_delta_rule.py``) against the plain float32
reference's position-by-position recurrence
(``chipbench/reference/kimi_linear.py``): output and every gradient, at
the strongest decay the initialisation allows with every exponent
non-positive and every value finite, the scalar rule as its special case,
the float32 state, and wrong programs, each told from the sound one by the
comparison the ``kimi_linear`` family makes on the chip. Float32 and tiny
sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from chipbench.reference import kimi_linear as reference
from horovod_tpu.models import kda
from horovod_tpu.models.kda import KimiDeltaAttention
from horovod_tpu.ops import channel_delta_rule as rule_op
from horovod_tpu.ops import gated_delta_rule as scalar_op
from horovod_tpu.ops import head_norm as norm_op

REL = 2e-5      # float32 on both sides: summation order is all that differs
# ... but for what reaches the decays (A_log, dt_bias, the decay's pair, g):
# the chunked form takes exp(G_i - G_r) of differences of cumulative sums
# where the recurrence multiplies one exp(g_t) after another, and their
# gradients sum those terms over every pair of positions
DECAY_REL = 4e-4
D_MODEL, HEADS, D_H, RANK = 16, 3, 8, 4
_CONFIG = {"linear_attn_config": {"num_heads": HEADS, "head_dim": D_H},
           "rms_norm_eps": 1e-6}
_DECAYS = ("A_log", "dt_bias", "decay_down", "decay_up")


def _close(got, want, what, rel=REL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
    assert err <= rel, f"{what}: relative error {err:.2e}"


def _operands(seq, strong=False, seed=0, batch=2, heads=HEADS, d_k=D_H,
              d_v=4):
    """``(q, k, v, g, beta)`` as the mixer hands them to the rule; with
    ``strong`` at the strongest decay the initialisation allows, ``A`` =
    16 under a softplus above 1."""
    keys = jax.random.split(jax.random.key(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (batch, seq, heads, d_k))
             ) / np.sqrt(d_k)
    k = unit(jax.random.normal(keys[1], (batch, seq, heads, d_k)))
    v = jax.random.normal(keys[2], (batch, seq, heads, d_v))
    a = jax.random.normal(keys[3], (batch, seq, heads, d_k))
    g = -(16.0 if strong else 0.5) * jax.nn.softplus(
        a + (1.5 if strong else 0.0))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, seq, heads)))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta):
    return jax.vmap(reference.delta_rule)(q, k, v, g, beta)


_MILD, _STRONG = "mild-decay", "A-16-softplus-above-1"


@pytest.mark.parametrize("seq, chunk, strong", [
    pytest.param(seq, chunk, strong, id=f"{name}-{_STRONG if strong else _MILD}")
    for (seq, chunk, name), decays in {
        (24, 8, "divides"): (False, True),
        (24, 7, "does-not-divide"): (False, True),
        (24, 24, "one-chunk"): (True,),
        (40, 16, "16-does-not-divide"): (False,),
        (48, 32, "32-does-not-divide"): (True,),
        (64, 64, "64"): (False, True),
        (130, 128, "128-does-not-divide"): (True,),
        (5, 1, "a-position-a-chunk"): (False,),
        (24, 12, "a-chunk-of-one-and-a-half-levels"): (False, True),
        (20, None, "default"): (False,)}.items()
    for strong in decays])
def test_rule_alone_against_the_recurrence(seq, chunk, strong):
    """Output and the gradients of all five operands against the
    reference's ``lax.scan`` over positions, at chunks of 1 to 128 that do
    and do not divide the length and sub-blocks of 1 to 8, at a mild decay
    and at the strongest the initialisation allows."""
    args = _operands(seq, strong)
    cot = jax.random.normal(jax.random.key(7), args[2].shape)
    run = lambda rule: jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(rule(*a) * cot), argnums=(0, 1, 2, 3, 4)))(*args)
    with jax.default_matmul_precision("highest"):
        got, got_grads = run(lambda *a: rule_op.channel_delta_rule(
            *a, chunk=chunk))
        want, want_grads = run(_recurrence)
    _close(got, want, "sum of the output")
    for name, g, w in zip("q k v g beta".split(), got_grads, want_grads):
        assert np.all(np.isfinite(np.asarray(g))), name
        _close(g, w, f"d{name}", DECAY_REL if name == "g" else REL)


def test_every_exponent_is_of_a_non_positive_number(monkeypatch):
    """The module's promise, watched: at the strongest decay (a cumulative
    sum of some -1,000 inside a chunk, whose negative overflows float32's
    exponent ten times over) every ``exp`` the rule takes, forward and
    backward, is of a number that is at most 0, and every value it makes
    is finite."""
    seen = []
    real = jnp.exp

    def watched(x):
        out = real(x)
        jax.debug.callback(
            lambda top, finite: seen.append((float(top), bool(finite))),
            jnp.max(x), jnp.all(jnp.isfinite(out)))
        return out

    monkeypatch.setattr(rule_op.jnp, "exp", watched)
    args = _operands(32, strong=True, batch=1)
    assert float(jnp.min(jnp.cumsum(args[3], axis=1))) < -900.0
    out, grads = jax.value_and_grad(
        lambda *a: jnp.sum(rule_op.channel_delta_rule(*a, chunk=16)),
        argnums=(0, 1, 2, 3, 4))(*args)
    jax.effects_barrier()
    monkeypatch.undo()
    assert len(seen) >= 6 and max(top for top, _ in seen) <= 0.0
    assert all(finite for _, finite in seen)
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in (out, *grads))


@pytest.mark.parametrize("chunk", [16, 12, 40])
def test_a_decay_a_head_is_the_scalar_rule(chunk):
    """With ``g`` the same in every channel the rule is
    ``gated_delta_rule_plain``'s: the scalar rule is its special case."""
    q, k, v, g, beta = _operands(40)
    g = jnp.broadcast_to(g[..., :1], g.shape)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: rule_op.channel_delta_rule(
            *a, chunk=chunk))(q, k, v, g, beta)
        want = jax.jit(lambda *a: scalar_op.gated_delta_rule_plain(
            *a, chunk=chunk))(q, k, v, g[..., 0], beta)
    _close(got, want, "o")


def test_the_chunk_is_the_shapes_and_the_inverse_is_shared():
    assert rule_op.chunk_for(8192) == rule_op.CHUNK
    assert rule_op.chunk_for(24) == 24 and rule_op.chunk_for(24, 8) == 8
    assert rule_op.unit_lower_inverse is scalar_op.unit_lower_inverse


# ------------------------------------------------------------------- mixer

def _mixer(chunk=None, dtype=jnp.float32, seq=24, batch=2, seed=0):
    """A mixer, its parameters (moved off their initial values, so that
    no term is 1 or 0 by construction, and the projections large enough
    that the gates and the decays are not all alike) and its input."""
    layer = KimiDeltaAttention(HEADS, D_H, 4, RANK, chunk=chunk, dtype=dtype)
    u = jax.random.normal(jax.random.key(seed), (batch, seq, D_MODEL))
    moved = {"norm_scale": 0.3, "dt_bias": 0.3, "in_proj_qkv": 0.3,
             "in_proj_beta": 0.5, "decay_down": 0.5, "decay_up": 0.5,
             "gate_down": 0.5, "gate_up": 0.5, "out_proj": 0.2}

    # (one compiled program: op by op, this is most of a case's seconds)
    @jax.jit
    def init(u):
        params = layer.init(jax.random.key(seed + 1), u)["params"]
        keys = iter(jax.random.split(jax.random.key(seed + 2), len(params)))
        return {name: w + moved.get(name, 0.0) * jax.random.normal(
            next(keys), w.shape) for name, w in params.items()}

    return layer, init(u), u


def _run(layer, params, u, **collect):
    """``layer.apply`` as one compiled program, traced anew a call (so
    what a test steers from outside is read again)."""
    return jax.jit(lambda p, u: layer.apply({"params": p}, u, **collect))(
        params, u)


def _reference(params, u):
    return jax.lax.map(
        lambda one: reference.kda_mixer(one, params, _CONFIG), u)


@pytest.mark.parametrize("chunk", [8, 7, 24, None, 1],
                         ids=["divides", "does-not-divide", "one-chunk",
                              "default", "a-position-a-chunk"])
def test_mixer_matches_the_position_by_position_reference(chunk):
    """Output, every parameter's gradient and the input's against the
    reference's ``lax.scan`` over positions."""
    layer, params, u = _mixer(chunk)
    assert {name: w.shape for name, w in params.items()} == {
        "in_proj_qkv": (16, 72), "conv_kernel": (4, 72),
        "in_proj_beta": (16, 3), "decay_down": (16, 4), "decay_up": (4, 24),
        "dt_bias": (24,), "A_log": (3,), "gate_down": (16, 4),
        "gate_up": (4, 24), "norm_scale": (8,), "out_proj": (24, 16)}
    cot = jax.random.normal(jax.random.key(9), u.shape)
    program = lambda p, u: jnp.sum(layer.apply({"params": p}, u) * cot)
    plain = lambda p, u: jnp.sum(_reference(p, u) * cot)
    with jax.default_matmul_precision("highest"):
        got, (dp, du) = jax.jit(jax.value_and_grad(program, (0, 1)))(params, u)
        want, (wp, wu) = jax.jit(jax.value_and_grad(plain, (0, 1)))(params, u)
    _close(got, want, "output")
    _close(du, wu, "du")
    for name in params:
        _close(dp[name], wp[name], name,
               DECAY_REL if name in _DECAYS else REL)


def test_state_and_decays_are_float32_in_a_bf16_layer():
    """A bf16 layer keeps the decays, their cumulative sums, the inverse
    and the carried state in float32: its output is within bf16 products
    of the reference's; and what ``STATE_DTYPE`` steers is those alone: in
    an otherwise float32 layer, bf16 there moves the output a thousand
    times further than summation order does."""
    layer, params, u = _mixer(chunk=8, seq=64)
    want = _reference(params, u)
    low = KimiDeltaAttention(HEADS, D_H, 4, RANK, chunk=8,
                             dtype=jnp.bfloat16)
    rel = lambda got: float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                            / jnp.linalg.norm(want))
    assert rel(_run(low, params, u)) < 3e-2
    assert rel(_run(layer, params, u)) < 1e-5
    was = kda.STATE_DTYPE
    try:
        kda.STATE_DTYPE = jnp.bfloat16
        assert rel(_run(layer, params, u)) > 1e-3
    finally:
        kda.STATE_DTYPE = was
    # the cumulative sums and the carried state of the bf16 layer, a head
    # a pass: G [n, 1, c, d] and S [1, d, d] float32
    jaxpr = str(jax.make_jaxpr(lambda p, u: low.apply({"params": p}, u))(
        params, u))
    assert "cumsum" not in jaxpr and "f32[8,1,8,8]" in jaxpr
    assert "bf16[8,1,8,8]" in jaxpr and "f32[1,8,8]" in jaxpr


def test_traced_layers_are_counted_and_sown():
    from horovod_tpu import metrics

    layer, params, u = _mixer(chunk=8)
    out, sown = _run(layer, params, u, mutable=["intermediates"])
    mine = sown["intermediates"]
    np.testing.assert_array_equal(np.asarray(mine["kda_input"][0]),
                                  np.asarray(u))
    np.testing.assert_array_equal(np.asarray(mine["kda_output"][0]),
                                  np.asarray(out))
    counted = metrics.registry().get("hvt_kda_layers_traced_total")
    assert counted.labels(heads="3", head_dim="8", gate_rank="4",
                          chunk="8").value >= 1


def test_initialisation_is_the_sources():
    """``A_log = log(uniform(1, 16))`` a head, ``dt_bias`` the
    softplus-inverse of a step log-uniform in (1e-3, 0.1) a channel, the
    norm's scale 1: a fresh layer's decays lie in ``-16 x 0.1`` to ``-1 x
    1e-3`` a position before the input moves them."""
    layer = KimiDeltaAttention(64, 8, 4, 8)
    params = layer.init(jax.random.key(0), jnp.zeros((1, 4, 16)))["params"]
    a = np.exp(np.asarray(params["A_log"]))
    assert 1.0 <= a.min() < 3.0 and 13.0 < a.max() <= 16.0
    step = np.asarray(jax.nn.softplus(params["dt_bias"]))
    assert 1e-3 <= step.min() < 2e-3 and 0.05 < step.max() <= 0.1 + 1e-6
    assert np.all(np.asarray(params["norm_scale"]) == 1.0)
    assert params["conv_kernel"].shape == (4, 3 * 64 * 8)


def test_leaf_rule_for_tensor_parallelism():
    specs = {name: kda.kda_leaf_spec(name, "tp") for name in (
        "in_proj_qkv", "conv_kernel", "in_proj_beta", "decay_down",
        "decay_up", "dt_bias", "A_log", "gate_down", "gate_up",
        "norm_scale", "out_proj")}
    assert specs == {
        "in_proj_qkv": P(), "conv_kernel": P(), "in_proj_beta": P(None, "tp"),
        "decay_down": P(), "decay_up": P(None, "tp"), "dt_bias": P("tp"),
        "A_log": P("tp"), "gate_down": P(), "gate_up": P(None, "tp"),
        "norm_scale": P(), "out_proj": P("tp", None)}


# ----------------------------------------------------------- wrong programs

def _one_decay_a_head(rule):
    return lambda q, k, v, g, beta, **kw: rule(
        q, k, v, jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape),
        beta, **kw)


WRONG_MIXERS = {
    "one-decay-a-head": lambda m: m.setattr(
        rule_op, "channel_delta_rule",
        _one_decay_a_head(rule_op.channel_delta_rule)),
    "no-decay": lambda m: m.setattr(
        rule_op, "channel_delta_rule",
        lambda q, k, v, g, beta, **kw: rule_op.channel_delta_rule_plain(
            q, k, v, jnp.zeros_like(g), beta, **kw)),
    "beta-left-out": lambda m: m.setattr(
        rule_op, "channel_delta_rule",
        lambda q, k, v, g, beta, **kw: rule_op.channel_delta_rule_plain(
            q, k, v, g, jnp.ones_like(beta), **kw)),
    "silu-in-the-gate": lambda m: m.setattr(
        norm_op, "gated_norm",
        lambda o, z, w, *, eps, gate: norm_op.gated_norm_plain(
            o, z, w, eps=eps, gate="silu")),
    "no-l2-norm": lambda m: m.setattr(
        norm_op, "l2_norm", lambda x, dim=None, *, eps, scale=1.0: x * scale),
}


@pytest.mark.parametrize("wrong", list(WRONG_MIXERS))
def test_wrong_mixers_are_told_from_the_sound_one(wrong, monkeypatch):
    """Each mixer wrong in one thing is further from the reference, by the
    family's own measure, than the bound the family holds a sound bf16
    mixer to on the chip."""
    from chipbench.families import kimi_linear as family

    layer, params, u = _mixer(chunk=8, seq=32)
    want = _reference(params, u)
    rel = lambda got: float(jnp.linalg.norm(got - want)
                            / jnp.linalg.norm(want))
    assert rel(_run(layer, params, u)) < 1e-5
    WRONG_MIXERS[wrong](monkeypatch)
    assert rel(_run(layer, params, u)) > family.MIXER_BOUNDS["kda", "mixer"]


def _lower_precisions():
    from benchmarks import kimilinear_wrong_programs as script
    from benchmarks.qwen3next_wrong_programs import _swapped

    return {
        "decays-sums-inverse-and-state": lambda: _swapped(
            kda, "STATE_DTYPE", jnp.bfloat16),
        "the-carried-state-alone": script._state_rounded,
        "the-decays-alone": lambda: script._rule_with(lambda g, beta: (
            script._as_bf16(g), beta))}


@pytest.mark.parametrize("part", list(_lower_precisions()))
def test_a_float32_part_in_bf16_is_told_by_the_float32_products(part):
    """What the family reads on the chip for a bf16 layer, twice
    (``mixer_distances``): the layer's own output, in which bf16 products
    hide a bf16 state or decay, and the same module with float32 products
    on the same input, in which the sound layer is at summation order and
    each of ``benchmarks/kimilinear_wrong_programs.py``'s three lower
    precisions is beyond the family's bound."""
    from chipbench.families import kimi_linear as family

    layer, params, u = _mixer(chunk=8, seq=64)
    low = KimiDeltaAttention(HEADS, D_H, 4, RANK, chunk=8,
                             dtype=jnp.bfloat16)

    def found():
        _, sown = _run(low, params, u, mutable=["intermediates"])
        return family.mixer_distances(
            {name: value[0] for name, value in sown["intermediates"].items()},
            params, _CONFIG, "kda", layer)

    sound = found()
    assert sound["float32_parts"] < 1e-5 < 1e-3 < sound["mixer"]
    with _lower_precisions()[part]():
        wrong = found()
    assert wrong["float32_parts"] > family.MIXER_BOUNDS["kda", "float32_parts"]
    assert wrong["float32_parts"] > 100 * sound["float32_parts"]
