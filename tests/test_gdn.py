"""The Gated DeltaNet mixer (``horovod_tpu/models/gdn.py``): its chunked
gated delta rule against the plain float32 reference's
position-by-position recurrence (``chipbench/reference/qwen3_next.py``),
output and every gradient; the triangular inverse by blocks; the float32
state; and wrong programs, each refused by the comparison the
``qwen3_next`` family makes on the chip. Float32 and tiny sizes."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import qwen3_next as family
from chipbench.reference import qwen3_next as reference
from horovod_tpu.models import gdn
from horovod_tpu.models.gdn import GatedDeltaNet
from horovod_tpu.ops import gated_delta_rule as rule_op
from horovod_tpu.ops import head_norm as norm_op

REL = 2e-5      # float32 on both sides: summation order is all that differs
# ... but for what reaches the decays (A_log, dt_bias, g): the chunked form
# takes exp(G_i - G_j) of a difference of cumulative sums where the
# recurrence multiplies one exp(g_t) after another, and their gradients
# sum those terms over every pair of positions
DECAY_REL = 2e-4
D_MODEL, D_K, D_V = 16, 8, 4


def _config(key_heads, value_heads):
    return {"linear_num_key_heads": key_heads,
            "linear_num_value_heads": value_heads,
            "linear_key_head_dim": D_K, "linear_value_head_dim": D_V,
            "rms_norm_eps": 1e-6}


def _close(got, want, what, rel=REL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
    assert err <= rel, f"{what}: relative error {err:.2e}"


def _mixer(chunk=None, key_heads=2, value_heads=4, dtype=jnp.float32,
           seq=24, batch=2, seed=0, d_model=D_MODEL, d_k=D_K, d_v=D_V):
    """A mixer, its parameters (moved off their initial values, so that
    no term is 1 or 0 by construction, and the projections large enough
    that the gates and the decays are not all alike) and its input."""
    layer = GatedDeltaNet(key_heads, value_heads, d_k, d_v, chunk=chunk,
                          dtype=dtype)
    u = jax.random.normal(jax.random.key(seed), (batch, seq, d_model))
    moved = {"norm_scale": 0.3, "dt_bias": 0.3, "in_proj_qkvz": 0.3,
             "in_proj_ba": 0.5, "out_proj": 0.2}

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


def _reference(params, u, config):
    return jax.lax.map(
        lambda one: reference.gdn_mixer(one, params, config), u)


@pytest.mark.parametrize("value_heads", [2, 4],
                         ids=["a-value-head-a-key-head", "two-a-key-head"])
@pytest.mark.parametrize("chunk", [8, 7, 24, None, 1],
                         ids=["divides", "does-not-divide", "one-chunk",
                              "default", "a-position-a-chunk"])
def test_mixer_matches_the_position_by_position_reference(chunk,
                                                          value_heads):
    """Output, every parameter's gradient and the input's against the
    reference's ``lax.scan`` over positions: chunk lengths that do and do
    not divide the 24 positions, one equal to them, the shape's default;
    one and two value heads a key head."""
    config = _config(2, value_heads)
    layer, params, u = _mixer(chunk, value_heads=value_heads)
    cot = jax.random.normal(jax.random.key(9), u.shape)
    program = lambda p, u: jnp.sum(layer.apply({"params": p}, u) * cot)
    plain = lambda p, u: jnp.sum(_reference(p, u, config) * cot)
    _close(_run(layer, params, u), _reference(params, u, config), "output")
    got = jax.jit(jax.grad(program, argnums=(0, 1)))(params, u)
    want = jax.grad(plain, argnums=(0, 1))(params, u)
    assert set(got[0]) == {"in_proj_qkvz", "in_proj_ba", "conv_kernel",
                           "dt_bias", "A_log", "norm_scale", "out_proj"}
    for name in got[0]:
        _close(got[0][name], want[0][name], f"d {name}",
               DECAY_REL if name in ("A_log", "dt_bias") else REL)
    _close(got[1], want[1], "d input")


@pytest.mark.parametrize("per_key", [1, 2])
@pytest.mark.parametrize("chunk", [16, 12, 40],
                         ids=["divides", "does-not-divide", "one-chunk"])
def test_rule_alone_against_the_recurrence(chunk, per_key):
    """``gated_delta_rule`` on given q, k, v, g, beta: output and all five
    gradients against ``reference.delta_rule``, with strong decays and
    gates across the whole of (0, 1), over several chunks so that the
    carried state matters."""
    rng = np.random.RandomState(chunk + per_key)
    b, s, key_heads = 2, 40, 2
    heads = key_heads * per_key
    normal = lambda *dims: jnp.asarray(rng.normal(size=dims), jnp.float32)
    unit = lambda x: norm_op.l2_norm(x, eps=gdn.L2_EPS)
    q = unit(normal(b, s, key_heads, D_K)) * D_K ** -0.5
    k = unit(normal(b, s, key_heads, D_K))
    v, cot = normal(b, s, heads, D_V), normal(b, s, heads, D_V)
    g = -jnp.asarray(rng.uniform(0, 2, (b, s, heads)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (b, s, heads)), jnp.float32)

    def plain(q, k, v, g, beta):
        wide = lambda t: jnp.repeat(t, per_key, axis=2)
        return jax.vmap(reference.delta_rule)(wide(q), wide(k), v, g, beta)

    program = lambda *a: rule_op.gated_delta_rule(*a, chunk=chunk)
    _close(jax.jit(program)(q, k, v, g, beta), plain(q, k, v, g, beta),
           "output")
    got = jax.jit(jax.grad(lambda *a: jnp.sum(program(*a) * cot),
                           argnums=range(5)))(q, k, v, g, beta)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * cot),
                    argnums=range(5))(q, k, v, g, beta)
    for name, x, y in zip(("q", "k", "v", "g", "beta"), got, want):
        _close(x, y, f"d {name}", DECAY_REL if name == "g" else REL)


@pytest.mark.parametrize("size", [1, 2, 5, 8, 12, 64])
def test_inverse_by_blocks_is_the_inverse(size):
    """``unit_lower_inverse`` against ``numpy.linalg.inv`` of ``I + N``,
    sizes that are and are not powers of two; it reads nothing on or above
    the diagonal; its own backward pass against ``jax.grad`` of a
    triangular solve; and a system of strongly correlated keys (every
    entry of ``N`` near one), whose inverse the powers of ``N`` would lose
    and the blocks keep."""
    rng = np.random.RandomState(size)
    full = jnp.asarray(0.3 * rng.normal(size=(3, size, size)), jnp.float32)
    strict = np.tril(np.asarray(full), -1)
    want = np.linalg.inv(np.eye(size) + strict.astype(np.float64))
    _close(rule_op.unit_lower_inverse(full), want, "inverse", rel=1e-4)
    cot = jnp.asarray(rng.normal(size=(3, size, size)), jnp.float32)
    solve = lambda n: jnp.linalg.inv(
        jnp.eye(size) + jnp.tril(n, -1))
    got = jax.grad(
        lambda n: jnp.sum(rule_op.unit_lower_inverse(n) * cot))(full)
    _close(got, jax.grad(lambda n: jnp.sum(solve(n) * cot))(full),
           "d inverse", rel=1e-4)
    assert not np.any(np.triu(np.asarray(got)))
    ones = jnp.ones((size, size), jnp.float32) * 0.999
    exact = np.linalg.inv(np.eye(size) + np.tril(np.asarray(ones, np.float64),
                                                 -1))
    _close(rule_op.unit_lower_inverse(ones), exact, "correlated keys",
           rel=1e-4)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else (
                    value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_state_and_decays_are_float32_in_a_bf16_layer():
    """Whatever the products run in: every ``exp``, every cumulative sum,
    the inverse's products and the carried state of a bf16 mixer's program
    are float32, forward and backward; the other products are bf16."""
    layer, params, u = _mixer(chunk=8, dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, u: jnp.sum(
        layer.apply({"params": p}, u).astype(jnp.float32))))(params, u)
    seen = {"exp": 0, "cumsum": 0, "scan": 0, "bf16_products": 0,
            "float32_products": 0}
    for eqn in _equations(jaxpr.jaxpr):
        name = eqn.primitive.name
        if name in ("exp", "cumsum"):
            seen[name] += 1
            assert eqn.invars[0].aval.dtype == jnp.float32, eqn
        elif name == "scan":
            seen["scan"] += 1
            carried = eqn.invars[eqn.params["num_consts"]:][
                :eqn.params["num_carry"]]
            assert all(v.aval.dtype == jnp.float32 for v in carried
                       if v.aval.ndim), eqn
        elif name == "dot_general":
            kinds = {v.aval.dtype for v in eqn.invars}
            seen["bf16_products"] += kinds == {jnp.dtype(jnp.bfloat16)}
            if kinds == {jnp.dtype(jnp.float32)}:
                seen["float32_products"] += 1
                assert eqn.params["precision"] is not None, eqn
    assert all(seen.values()), seen
    config = _config(2, 4)
    got = _run(layer, params, u).astype(jnp.float32)
    _close(got, _reference(params, u, config), "bf16 output", rel=3e-2)


def test_traced_layers_are_counted_and_sown():
    from horovod_tpu import metrics

    def count():
        m = metrics.registry().get("hvt_gdn_layers_traced_total")
        return m.labels(value_heads="4", key_dim="8", value_dim="4",
                        chunk="8").value if m else 0.0

    layer, params, u = _mixer(chunk=8)
    before = count()
    jax.jit(lambda p, u: layer.apply({"params": p}, u)).lower(params, u)
    assert count() == before + 1
    out, sown = _run(layer, params, u, mutable=["intermediates"])
    sown = {k: v[0] for k, v in sown["intermediates"].items()}
    assert set(sown) == {"gdn_input", "gdn_output"}
    np.testing.assert_array_equal(np.asarray(sown["gdn_input"]),
                                  np.asarray(u))
    np.testing.assert_array_equal(np.asarray(sown["gdn_output"]),
                                  np.asarray(out))
    assert gdn.chunk_for(8192) == gdn.CHUNK and gdn.chunk_for(24) == 24
    with pytest.raises(ValueError, match="whole number of value heads"):
        GatedDeltaNet(2, 3, D_K, D_V).init(jax.random.key(0), u)


def test_initialisation_is_the_sources():
    layer, _, u = _mixer(value_heads=4)
    p = jax.jit(GatedDeltaNet(2, 4, D_K, D_V).init)(
        jax.random.key(3), u)["params"]
    assert p["in_proj_qkvz"].shape == (D_MODEL, 2 * 2 * D_K + 2 * 4 * D_V)
    assert p["in_proj_ba"].shape == (D_MODEL, 8)
    assert p["conv_kernel"].shape == (4, 2 * 2 * D_K + 4 * D_V)
    assert p["out_proj"].shape == (4 * D_V, D_MODEL)
    np.testing.assert_array_equal(np.asarray(p["dt_bias"]), np.ones(4))
    np.testing.assert_array_equal(np.asarray(p["norm_scale"]), np.ones(D_V))
    a = np.exp(np.asarray(p["A_log"]))
    assert a.shape == (4,) and np.all((a >= 1e-3) & (a <= 16.0))


# ---- wrong programs: each is refused by the comparison the family makes
# of the mixer at the cell's length (``mixer_distance`` held to
# ``MIXER_REL_L2_BOUND``), which the program as it is passes

@contextlib.contextmanager
def _swapped(owner, name, value):
    was = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, was)


def _rule_given(**fixed):
    """``gated_delta_rule`` with an argument replaced by a constant."""
    right = rule_op.gated_delta_rule

    def rule(q, k, v, g, beta, **options):
        given = dict(g=g, beta=beta)
        given.update({name: jnp.full_like(given[name], value)
                      for name, value in fixed.items()})
        return right(q, k, v, given["g"], given["beta"], **options)

    return rule


def _gate_first(o, z, scale, eps):
    """``gated_norm``'s arguments (``o`` and ``z`` ``[b, s, H d]``,
    ``scale [d]``), the gate applied before the norm."""
    heads = lambda t: t.astype(jnp.float32).reshape(
        *t.shape[:-1], -1, scale.shape[-1])
    gated = heads(o) * jax.nn.silu(heads(z))
    return (gated * jax.lax.rsqrt(jnp.mean(gated * gated, -1, keepdims=True)
                                  + eps) * scale).astype(o.dtype).reshape(
                                      o.shape)


def _not_normalised(x, dim=None, eps=None, scale=1.0):
    """``l2_norm``'s arguments, the norm left out."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


WRONG_MIXERS = {
    "state-and-cumulative-sums-in-bf16": (
        jnp.bfloat16, lambda: _swapped(gdn, "STATE_DTYPE", jnp.bfloat16)),
    "l2-norm-left-out": (
        jnp.float32, lambda: _swapped(norm_op, "l2_norm", _not_normalised)),
    "beta-left-out": (
        jnp.float32, lambda: _swapped(rule_op, "gated_delta_rule",
                                      _rule_given(beta=1.0))),
    "decay-left-out": (
        jnp.float32, lambda: _swapped(rule_op, "gated_delta_rule",
                                      _rule_given(g=0.0))),
    "gate-before-the-norm": (
        jnp.float32, lambda: _swapped(norm_op, "gated_norm", _gate_first)),
}


# the two whose swapped names are the per-head norms', once more with the
# norms' Pallas kernels serving (interpreted): the names steer that path too
KERNELS_SERVE = "-kernels-serve"


@pytest.mark.parametrize("wrong", list(WRONG_MIXERS) + [
    "l2-norm-left-out" + KERNELS_SERVE,
    "gate-before-the-norm" + KERNELS_SERVE])
def test_wrong_mixers_are_refused(wrong, monkeypatch):
    """At 512 positions and heads of 32: the mixer as it is, in the
    precision the wrong one runs in, is within the family's bound of the
    position-by-position reference on its own input; the wrong one is
    outside it. Where the per-head norms' kernels serve, the mixer as it
    is holds all of them and the wrong one has lost the one it swapped."""
    served = wrong.endswith(KERNELS_SERVE)
    dtype, swap = WRONG_MIXERS[wrong.removesuffix(KERNELS_SERVE)]
    if served:
        monkeypatch.setattr(norm_op, "serves", lambda *shape: True)
    config = _config(2, 4) | {"linear_key_head_dim": 32,
                              "linear_value_head_dim": 32}
    layer, params, u = _mixer(key_heads=2, value_heads=4, dtype=dtype,
                              seq=512, batch=1, d_model=32, d_k=32, d_v=32)

    def distance():
        _, sown = _run(layer, params, u, mutable=["intermediates"])
        return family.mixer_distance(
            {k: v[0] for k, v in sown["intermediates"].items()}, params,
            config)

    def norm_kernels():
        jaxpr = jax.make_jaxpr(lambda u: layer.apply({"params": params}, u))(u)
        return sorted(eqn.params["name"] for eqn in _equations(jaxpr.jaxpr)
                      if eqn.primitive.name == "pallas_call")

    sound = distance()
    assert sound <= family.MIXER_REL_L2_BOUND, sound
    if served:
        assert norm_kernels() == ["hvt_gated_norm_fwd", "hvt_l2_norm_fwd",
                                  "hvt_l2_norm_fwd"]
    with swap():
        far = distance()
        lost = ("hvt_l2_norm_fwd" if wrong.startswith("l2-norm")
                else "hvt_gated_norm_fwd")
        assert not served or lost not in norm_kernels()
    assert not far <= family.MIXER_REL_L2_BOUND, (wrong, far, sound)
