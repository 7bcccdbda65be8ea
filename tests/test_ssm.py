"""The Mamba-2 mixer (``horovod_tpu/models/ssm.py``): its chunked scan
against the plain float32 reference's position-by-position recurrence
(``chipbench/reference/nemotron_h.py``), output and every gradient; the
float32 decays; a chip's share of the heads. Float32 and tiny sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import nemotron_h as reference
from horovod_tpu.models import ssm
from horovod_tpu.models.ssm import Mamba2Mixer

REL = 2e-5      # float32 on both sides: summation order is all that differs
CONFIG = {"ssm_state_size": 8, "mamba_head_dim": 4, "norm_eps": 1e-5}
HEADS, GROUPS, D_MODEL = 4, 2, 16


def _close(got, want, what, rel=REL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
    assert err <= rel, f"{what}: relative error {err:.2e}"


def _mixer(chunk=None, held=None, dtype=jnp.float32, seq=24, batch=2,
           seed=0):
    """A mixer, its parameters (moved off their initial values, so that
    no term is 1 or 0 by construction) and its input."""
    layer = Mamba2Mixer(HEADS, CONFIG["mamba_head_dim"], GROUPS,
                        CONFIG["ssm_state_size"], held=held, chunk=chunk,
                        dtype=dtype)
    u = jax.random.normal(jax.random.key(seed), (batch, seq, D_MODEL))
    params = layer.init(jax.random.key(seed + 1), u)["params"]
    keys = iter(jax.random.split(jax.random.key(seed + 2), len(params)))
    params = {name: w + 0.3 * jax.random.normal(next(keys), w.shape)
              if name in ("conv_bias", "D_skip", "norm_scale", "dt_bias")
              else w for name, w in params.items()}
    return layer, params, u


def _reference(params, u):
    return jax.lax.map(
        lambda one: reference.mamba_mixer(one, params, CONFIG), u)


@pytest.mark.parametrize("chunk", [8, 7, 5, 24, None, 1],
                         ids=["divides", "does-not-divide", "ragged-tail",
                              "one-chunk", "default", "a-position-a-chunk"])
def test_mixer_matches_the_position_by_position_reference(chunk):
    """Output, every parameter's gradient and the input's against the
    reference's ``lax.scan`` over positions: chunk lengths that do and do
    not divide the 24 positions, one chunk, and the shape's default."""
    layer, params, u = _mixer(chunk)
    cot = jax.random.normal(jax.random.key(9), u.shape)
    program = lambda p, u: jnp.sum(layer.apply({"params": p}, u) * cot)
    plain = lambda p, u: jnp.sum(_reference(p, u) * cot)
    _close(layer.apply({"params": params}, u), _reference(params, u),
           "output")
    got = jax.jit(jax.grad(program, argnums=(0, 1)))(params, u)
    want = jax.grad(plain, argnums=(0, 1))(params, u)
    assert set(got[0]) == {"in_proj", "conv_kernel", "conv_bias", "dt_bias",
                           "A_log", "D_skip", "norm_scale", "out_proj"}
    for name in got[0]:
        _close(got[0][name], want[0][name], f"d {name}")
    _close(got[1], want[1], "d input")


def test_scan_alone_decays_and_carries_across_chunks():
    """The scan without the layer around it, worked by hand: one head,
    one channel, one state, x = B = C = 1: ``h_t = e^{delta a} h_{t-1} +
    delta`` whatever the chunk, so a chunk boundary is invisible."""
    seq, delta, a = 12, 0.5, -0.7
    ones = jnp.ones((1, seq, 1, 1))
    want, h = [], 0.0
    for _ in range(seq):
        h = np.exp(delta * a) * h + delta
        want.append(h)
    for chunk in (3, 5, 12):
        y = ssm.ssm_scan(ones, jnp.full((1, seq, 1), delta),
                         jnp.array([a]), ones, ones, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y).reshape(-1), want,
                                   rtol=1e-6)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_decays_are_float32_in_a_bf16_layer():
    """Whatever the products run in: every ``exp`` (the decays, the
    softplus), every cumulative sum and the carried state of a bf16
    mixer's program are float32, forward and backward; the products
    themselves are bf16."""
    layer, params, u = _mixer(chunk=8, dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, u: jnp.sum(
        layer.apply({"params": p}, u).astype(jnp.float32))))(params, u)
    seen = {"exp": 0, "cumsum": 0, "scan": 0, "bf16_products": 0}
    for eqn in _equations(jaxpr.jaxpr):
        name = eqn.primitive.name
        if name in ("exp", "cumsum", "cumlogsumexp"):
            seen[name] += 1
            assert eqn.invars[0].aval.dtype == jnp.float32, eqn
        elif name == "scan":
            seen["scan"] += 1
            carried = eqn.invars[eqn.params["num_consts"]:][
                :eqn.params["num_carry"]]
            assert all(v.aval.dtype == jnp.float32 for v in carried), eqn
        elif name == "dot_general":
            seen["bf16_products"] += all(
                v.aval.dtype == jnp.bfloat16 for v in eqn.invars)
    assert all(seen.values()), seen
    # and close to the float32 reference at bf16's step, not at its square
    got = layer.apply({"params": params}, u).astype(jnp.float32)
    _close(got, _reference(params, u), "bf16 output", rel=3e-2)


def take_heads(params, first, count):
    """The slices of an uncut mixer's parameters that a chip holding
    heads ``[first, first + count)`` builds (whole groups)."""
    p, n = CONFIG["mamba_head_dim"], CONFIG["ssm_state_size"]
    per_group = HEADS // GROUPS
    inner, bc = HEADS * p, GROUPS * n
    heads = np.arange(first, first + count)
    groups = np.arange(first // per_group, (first + count) // per_group)
    channels = (heads[:, None] * p + np.arange(p)).reshape(-1)
    states = (groups[:, None] * n + np.arange(n)).reshape(-1)
    xbc = np.concatenate([channels, inner + states, inner + bc + states])
    columns = np.concatenate([channels, inner + xbc,
                              2 * inner + 2 * bc + heads])
    return {"in_proj": params["in_proj"][:, columns],
            "conv_kernel": params["conv_kernel"][:, xbc],
            "conv_bias": params["conv_bias"][xbc],
            "dt_bias": params["dt_bias"][heads],
            "A_log": params["A_log"][heads],
            "D_skip": params["D_skip"][heads],
            "norm_scale": params["norm_scale"][channels],
            "out_proj": params["out_proj"][channels]}


def test_the_shares_of_the_heads_add_up():
    """Two chips, a group each: each builds its slices with ``held`` and
    computes its share; the shares summed are the uncut reference's
    output (the grouped norm is local to a group, and the out-projection
    is linear)."""
    _, params, u = _mixer()
    total = 0.0
    for first in (0, 2):
        share, _, _ = _mixer(held=(first, 2))
        mine = take_heads(params, first, 2)
        shapes = jax.eval_shape(share.init, jax.random.key(0), u)["params"]
        assert jax.tree.map(lambda a: a.shape, mine) == jax.tree.map(
            lambda a: a.shape, dict(shapes))
        out = share.apply({"params": mine}, u)
        _close(out, _reference(mine, u), f"share from head {first}")
        total = total + out
    _close(total, _reference(params, u), "sum of the shares")
    assert float(jnp.linalg.norm(total - out)) > 0.1    # neither is it all


@pytest.mark.parametrize("held", [(1, 2), (0, 1), (0, 3), (2, 4), (0, 0)])
def test_a_share_is_whole_groups(held):
    with pytest.raises(ValueError, match="whole groups"):
        ssm.held_groups(HEADS, GROUPS, held)


def test_traced_layers_are_counted_and_sown():
    from horovod_tpu import metrics

    def count():
        m = metrics.registry().get("hvt_ssm_layers_traced_total")
        return m.labels(heads="2", state="8", chunk="8").value if m else 0.0

    layer, _, u = _mixer(chunk=8, held=(2, 2))
    params = layer.init(jax.random.key(0), u)["params"]
    before = count()
    jax.jit(lambda p, u: layer.apply({"params": p}, u)).lower(params, u)
    assert count() == before + 1
    # what a caller who asks for it is handed: the mixer's own input and
    # output, for a comparison with a reference on the same input
    out, sown = layer.apply({"params": params}, u, mutable=["intermediates"])
    sown = {k: v[0] for k, v in sown["intermediates"].items()}
    assert set(sown) == {"ssm_input", "ssm_output"}
    np.testing.assert_array_equal(np.asarray(sown["ssm_input"]),
                                  np.asarray(u))
    np.testing.assert_array_equal(np.asarray(sown["ssm_output"]),
                                  np.asarray(out))
    assert ssm.chunk_for(8192) == ssm.CHUNK and ssm.chunk_for(100) == 100


def test_initialisation_is_mamba2s():
    layer, _, u = _mixer()
    params = Mamba2Mixer(64, 4, 1, 8).init(
        jax.random.key(3), jnp.zeros((1, 4, 8)))["params"]
    step = jax.nn.softplus(params["dt_bias"])
    assert float(step.min()) >= ssm.DT_MIN * 0.999
    assert float(step.max()) <= ssm.DT_MAX * 1.001
    a = jnp.exp(params["A_log"])
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    assert float(jnp.abs(params["conv_bias"]).max()) == 0.0
    np.testing.assert_array_equal(np.asarray(params["D_skip"]), 1.0)
