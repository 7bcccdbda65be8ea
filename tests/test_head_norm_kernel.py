"""The Gated DeltaNet mixer's per-head norms as Pallas kernels
(``ops/head_norm.py``), interpreted on the CPU: output and every gradient
against ``jax.grad`` of the plain ``jax.numpy`` bodies of
``models/gdn.py``; what is float32 inside the kernels; what the custom VJPs
keep; and which program gets the kernels, under which names."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import gdn
from horovod_tpu.ops import head_norm as norm_op
from tests.test_gdn import _equations
from tests.test_gdn_kernel import _close, _has_pallas, _stacks

EPS = 1e-6
# (heads, a block's lanes in heads; none: the kernels derive it, and three
# heads are fewer than the LANES = 1024 of a block hold at either width)
HEADS = {"heads-fill-the-blocks": (4, 2), "heads-fill-no-block": (3, None),
         "a-head-a-block": (2, 1)}
# (positions, a block's rows, a pass's rows)
ROWS = {"one-block": (16, 16, 8), "two-blocks": (32, 16, 16),
        "rows-the-block-does-not-divide": (40, 16, 8),
        "derived": (24, None, None)}
# what two float32 bodies rounded to the dtype may differ by: summation
# order alone in float32, the odd last bit in bf16
REL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-3}


def _operands(seq, heads, dim, dtype=jnp.float32, batch=2, seed=0):
    rng = np.random.RandomState(seed + seq + heads + dim)
    wide = lambda: jnp.asarray(rng.normal(size=(batch, seq, heads * dim)),
                               jnp.float32).astype(dtype)
    w = jnp.asarray(rng.uniform(0.5, 1.5, (dim,)), jnp.float32)
    return (wide(), wide(), w), wide()


def _with_gradients(norm, cot):
    return jax.jit(lambda *a: (lambda o, vjp: (o, *vjp(cot)))(
        *jax.vjp(norm, *a)))


def _named(dim, heads, rows):
    (_, lanes), (_, rows, sub) = HEADS[heads], ROWS[rows]
    return dict(rows=rows, lanes=lanes and lanes * dim, sub=sub)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("rows", list(ROWS))
@pytest.mark.parametrize("heads", list(HEADS))
@pytest.mark.parametrize("dim", [128, 256])
def test_the_gated_norm_matches_the_plain_body(dim, heads, rows, dtype):
    """``hvt_gated_norm_fwd`` and ``hvt_gated_norm_bwd`` against
    ``gated_norm_plain`` and ``jax.grad`` of it (``y``, ``do``,
    ``dz``, ``dw``): heads of 128 and 256, head counts that fill a block's
    lanes and that do not, one and two blocks of positions, a sequence the
    block does not divide (the last block's rows past the end are never
    written and stay out of ``dw``) and the block the kernels derive."""
    args, cot = _operands(ROWS[rows][0], HEADS[heads][0], dim, dtype)
    got = _with_gradients(lambda *a: norm_op.gated_norm_kernels(
        *a, eps=EPS, **_named(dim, heads, rows)), cot)(*args)
    want = _with_gradients(lambda *a: norm_op.gated_norm_plain(*a, eps=EPS),
                           cot)(*args)
    for name, x, same in zip(("y", "do", "dz", "dw"), got, want):
        assert x.shape == same.shape and x.dtype == same.dtype, name
        _close(x, same, name, REL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("rows", ["two-blocks",
                                  "rows-the-block-does-not-divide"])
@pytest.mark.parametrize("gate", ["silu", "sigmoid"])
def test_the_gated_norm_takes_the_gates_activation(gate, rows, dtype):
    """``gate`` names what ``z`` passes through (``silu``: Gated DeltaNet,
    the default every caller had; ``sigmoid``: Kimi Delta Attention): the
    plain body is ``RMSNorm(o) w act(z)`` written out, the kernels are the
    plain body, output and all three gradients, and the default is
    ``silu``; any other name is refused."""
    args, cot = _operands(ROWS[rows][0], 3, 128, dtype)
    o, z, w = (t.astype(jnp.float32) for t in args)
    heads = lambda t: t.reshape(*t.shape[:-1], 3, 128)
    act = {"silu": jax.nn.silu, "sigmoid": jax.nn.sigmoid}[gate]
    written_out = (heads(o) * jax.lax.rsqrt(
        jnp.mean(heads(o) ** 2, -1, keepdims=True) + EPS) * w
        * act(heads(z))).reshape(o.shape)
    _close(norm_op.gated_norm_plain(*args, eps=EPS, gate=gate), written_out,
           "plain", REL[dtype])
    got = _with_gradients(lambda *a: norm_op.gated_norm_kernels(
        *a, eps=EPS, gate=gate, **_named(128, "heads-fill-no-block", rows)),
        cot)(*args)
    want = _with_gradients(lambda *a: norm_op.gated_norm_plain(
        *a, eps=EPS, gate=gate), cot)(*args)
    for name, x, same in zip(("y", "do", "dz", "dw"), got, want):
        assert x.shape == same.shape and x.dtype == same.dtype, name
        _close(x, same, name, REL[dtype])
    if gate == "silu":
        np.testing.assert_array_equal(
            np.asarray(norm_op.gated_norm(*args, eps=EPS), np.float32),
            np.asarray(norm_op.gated_norm_plain(*args, eps=EPS, gate="silu"),
                       np.float32))
    with pytest.raises((KeyError, ValueError)):
        norm_op.gated_norm_kernels(*args, eps=EPS, gate="tanh")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("rows", list(ROWS))
@pytest.mark.parametrize("heads", list(HEADS))
@pytest.mark.parametrize("dim", [128, 256])
def test_the_l2_norm_matches_the_plain_body(dim, heads, rows, dtype):
    """``hvt_l2_norm_fwd`` and ``hvt_l2_norm_bwd`` against
    ``l2_norm_plain`` and ``jax.grad`` of it, with q's scale
    ``dim^-1/2`` and with k's 1, over the same shapes and blocks."""
    (x, _, _), cot = _operands(ROWS[rows][0], HEADS[heads][0], dim, dtype)
    for scale in (dim ** -0.5, 1.0):
        got = _with_gradients(lambda x: norm_op.l2_norm_kernels(
            x, dim, eps=gdn.L2_EPS, scale=scale,
            **_named(dim, heads, rows)), cot)(x)
        want = _with_gradients(lambda x: norm_op.l2_norm_plain(
            x, dim, eps=gdn.L2_EPS, scale=scale), cot)(x)
        for name, a, same in zip(("y", "dx"), got, want):
            assert a.shape == same.shape and a.dtype == same.dtype, name
            _close(a, same, f"{name} at scale {scale}", REL[dtype])


def test_the_blocks_the_kernels_derive_and_refuse():
    """With no block named: the whole of a short sequence and the most
    whole heads up to 1024 lanes that divide the channels; at the cell's
    own shapes 512 positions by eight heads. Heads that do not divide the
    channels and blocks that do not tile them are refused by name."""
    plan = lambda shape, dim, **named: norm_op._plan(
        jax.ShapeDtypeStruct(shape, jnp.bfloat16), dim, EPS, 1.0,
        *(named.get(n) for n in ("rows", "lanes", "sub")))
    at = lambda p: (p.rows, p.lanes, p.sub)
    assert at(plan((2, 8192, 4096), 128)) == (512, 1024, 32)
    assert at(plan((2, 8192, 2048), 128)) == (512, 1024, 32)
    assert at(plan((1, 24, 384), 128)) == (24, 384, 24)
    assert at(plan((1, 100, 768), 256)) == (100, 768, 100)
    assert at(plan((1, 100, 1280), 256)) == (100, 256, 100)
    # a head wider than 1024 lanes: fewer rows, the same block
    assert at(plan((1, 2048, 4096), 2048)) == (256, 2048, 32)
    with pytest.raises(ValueError, match="do not divide"):
        plan((1, 32, 320), 128)
    with pytest.raises(ValueError, match="does not tile"):
        plan((1, 32, 384), 128, lanes=256)
    with pytest.raises(ValueError, match="does not tile"):
        plan((1, 32, 384), 128, rows=16, sub=12)


def test_float32_inside_the_kernels():
    """bf16 operands: every multiplication, addition, ``rsqrt`` and
    ``logistic`` of the four kernels' own jaxprs is of float32 values;
    what leaves is the operands' dtype for ``y``, ``do``, ``dz`` and
    ``dx`` and float32 for a block's sums of ``dw``. And the numbers say so:
    against the plain bodies on the same numbers in float32 the kernels
    are as close as the plain bodies in bf16 operands are (the outputs'
    rounding alone)."""
    dim = 128
    args, cot = _operands(32, 3, dim, jnp.bfloat16)
    gated = lambda *a: norm_op.gated_norm_kernels(*a, eps=EPS, rows=16)
    l2 = lambda x: norm_op.l2_norm_kernels(
        x, dim, eps=gdn.L2_EPS, scale=dim ** -0.5, rows=16)
    jaxpr = jax.make_jaxpr(lambda *a: (
        jax.vjp(gated, *a)[1](cot), jax.vjp(l2, a[0])[1](cot)))(*args)
    calls = {eqn.params["name"]: eqn for eqn in _equations(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"}
    assert set(calls) == {"hvt_gated_norm_fwd", "hvt_gated_norm_bwd",
                          "hvt_l2_norm_fwd", "hvt_l2_norm_bwd"}
    for name, call in calls.items():
        seen = {"mul": 0, "add": 0, "rsqrt": 0, "logistic": 0}
        for eqn in _equations(call.params["jaxpr"]):
            if eqn.primitive.name in seen and eqn.outvars[0].aval.shape:
                seen[eqn.primitive.name] += 1
                assert eqn.outvars[0].aval.dtype == jnp.float32, (name, eqn)
        assert all(seen[p] for p in ("mul", "add", "rsqrt")), (name, seen)
        assert bool(seen["logistic"]) == ("gated" in name), (name, seen)
    for name, outs in (("hvt_gated_norm_fwd", 1), ("hvt_l2_norm_fwd", 1),
                       ("hvt_l2_norm_bwd", 1), ("hvt_gated_norm_bwd", 3)):
        avals = [v.aval for v in calls[name].outvars]
        assert len(avals) == outs
        assert all(a.dtype == jnp.bfloat16 and a.shape == args[0].shape
                   for a in avals[:2]), name
    sums = calls["hvt_gated_norm_bwd"].outvars[2].aval
    # a row of [d] a block: w is every head's, the heads' sums are one
    assert sums.dtype == jnp.float32 and sums.shape == (2, 2, 1, dim)

    f32 = lambda t: t.astype(jnp.float32)
    want = _with_gradients(lambda *a: norm_op.gated_norm_plain(*a, eps=EPS),
                           f32(cot))(f32(args[0]), f32(args[1]), args[2])
    far = lambda got: [float(np.linalg.norm(f32(a) - w) / np.linalg.norm(w))
                       for a, w in zip(got, want)]
    through = far(_with_gradients(gated, cot)(*args))
    plain = far(_with_gradients(
        lambda *a: norm_op.gated_norm_plain(*a, eps=EPS), cot)(*args))
    for name, k, p in zip(("y", "do", "dz", "dw"), through, plain):
        assert k <= 1.05 * p + 1e-6, (name, k, p)


def test_the_vjps_keep_their_operands_and_nothing_float32_of_their_size():
    """The residuals of the two custom VJPs are the operands: for bf16
    ``o``, ``z`` and ``x`` nothing float32 of ``[b, s, H d]`` is kept
    between the passes, where automatic differentiation of the plain
    bodies keeps several."""
    dim = 128
    args, _ = _operands(32, 2, dim, jnp.bfloat16)
    big = lambda kept: [a for a in jax.tree.leaves(kept)
                        if a.size >= args[0].size and a.dtype != jnp.bfloat16]
    wide = lambda kept: [a for a in jax.tree.leaves(kept)
                         if a.size >= args[0].size]
    plan = norm_op._plan(args[0], dim, EPS, 1.0, None, None, None)
    _, residuals = norm_op._gated_fwd(*args, plan)
    assert [r is a for r, a in zip(residuals, args)] == [True] * 3
    _, residual = norm_op._l2_fwd(args[0], plan)
    assert residual is args[0]
    _, pullback = jax.vjp(
        lambda *a: norm_op.gated_norm_kernels(*a, eps=EPS), *args)
    assert not big(pullback) and len(wide(pullback)) == 2
    _, pullback = jax.vjp(lambda x: norm_op.l2_norm_kernels(x, dim, eps=EPS),
                          args[0])
    assert not big(pullback) and len(wide(pullback)) == 1
    _, plain = jax.vjp(lambda *a: norm_op.gated_norm_plain(*a, eps=EPS),
                       *args)
    assert big(plain)
    _, plain = jax.vjp(lambda x: norm_op.l2_norm_plain(x, dim, eps=EPS),
                       args[0])
    assert big(plain)


# ---- which program gets the kernels, and the names the trace reads

def _kernel_counts(heads, dim):
    from horovod_tpu import metrics

    m = metrics.registry().get("hvt_head_norm_kernel_traces_total")
    return {kernel: m.labels(kernel=kernel, heads=str(heads),
                             dim=str(dim)).value if m else 0.0
            for kernel in ("l2_fwd", "l2_bwd", "gated_fwd", "gated_bwd")}


def test_the_choice(monkeypatch):
    """On the CPU ``l2_norm`` and ``gated_norm`` lower to no
    ``pallas_call`` and are the plain bodies to the letter; on a TPU
    backend heads of 128 and 256 on ``[b, s, H d]`` go to the kernels, and
    heads of 64, positions in no whole bf16 tile and operands that are
    ``[b, s, H, d]`` already to ``jax.numpy`` without raising."""
    (o, z, w), _ = _operands(32, 2, 128, jnp.bfloat16, batch=1)
    (narrow, _, w64), _ = _operands(32, 4, 64, jnp.bfloat16, batch=1)
    (ragged, _, _), _ = _operands(20, 2, 128, jnp.bfloat16, batch=1)
    l2 = lambda x: norm_op.l2_norm(x, 128, eps=EPS, scale=128 ** -0.5)
    gated = lambda o, z, w: norm_op.gated_norm(o, z, w, eps=EPS)
    assert not norm_op.serves(8192, 128)
    assert not _has_pallas(l2, o) and not _has_pallas(gated, o, z, w)
    assert (jax.jit(gated).lower(o, z, w).as_text()
            == jax.jit(lambda *a: norm_op.gated_norm_plain(
                *a, eps=EPS)).lower(o, z, w).as_text())
    assert (jax.jit(l2).lower(o).as_text() == jax.jit(
        lambda x: norm_op.l2_norm_plain(
            x, 128, eps=EPS, scale=128 ** -0.5)).lower(o).as_text())
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        assert norm_op.serves(8192, 128)        # qwen3next-s8192
        assert norm_op.serves(2048, 128)        # its probe
        assert norm_op.serves(8192, 256)
        assert not norm_op.serves(8192, 64)
        assert not norm_op.serves(8192, 192)
        assert not norm_op.serves(8200, 128)
        # a function of its own each: a trace is cached by the function
        assert _has_pallas(lambda x: l2(x), o)
        assert _has_pallas(lambda *a: gated(*a), o, z, w)
        assert _has_pallas(jax.grad(lambda *a: gated(*a).sum().astype(
            jnp.float32), (0, 1, 2)), o, z, w)
        assert not _has_pallas(lambda x: norm_op.l2_norm(x, 64, eps=EPS),
                               narrow)
        assert not _has_pallas(lambda *a: gated(*a), narrow, narrow, w64)
        assert not _has_pallas(lambda x: l2(x), ragged)
        by_heads = o.reshape(1, 32, 2, 128)
        assert not _has_pallas(lambda x: norm_op.l2_norm(x, eps=EPS), by_heads)
        assert not _has_pallas(lambda *a: gated(*a), by_heads, by_heads, w)


def test_the_names(monkeypatch):
    """With the kernels forced, the forward, recomputed and backward steps
    of three mixers hold ``hvt_l2_norm_fwd`` and ``hvt_l2_norm_bwd`` (q's
    and k's: two a pass) under ``gdn_rule`` and ``hvt_gated_norm_fwd`` and
    ``hvt_gated_norm_bwd`` under ``gdn_gate_norm`` (what
    ``chipbench/layer_metrics/gdn_rule_ms.py`` and ``gdn_ms.py`` match; the
    lowered text is read in ``tests/test_chip_compile.py``), the layers
    share a trace a kernel, and the counter says which kernels, heads and
    width the job got."""
    from horovod_tpu.models import GPT, GPTConfig

    model = GPT(GPTConfig(
        vocab_size=64, n_layers=5, layer_pattern="G*G*G", d_model=32,
        n_heads=4, d_ff=16, dtype=jnp.float32, remat=True, use_flash=False,
        gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8))
    tokens = jnp.zeros((2, 32), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)["params"]
    loss = lambda p: model.apply({"params": p}, tokens).mean()
    assert "_norm_" not in jax.jit(jax.grad(loss)).lower(params).as_text()

    before = _kernel_counts(2, 8), _kernel_counts(4, 8)
    with monkeypatch.context() as m:
        m.setattr(norm_op, "serves", lambda *shape: True)
        jax.clear_caches()
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    jax.clear_caches()
    under = {name: [] for name in (
        "hvt_l2_norm_fwd", "hvt_l2_norm_bwd", "hvt_gated_norm_fwd",
        "hvt_gated_norm_bwd")}
    for eqn, stack in _stacks(jaxpr.jaxpr):
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] in under):
            under[eqn.params["name"]].append(stack)
    for norm, scope, a_pass in (("l2", "gdn_rule", 2),
                                ("gated", "gdn_gate_norm", 1)):
        fwd, bwd = under[f"hvt_{norm}_norm_fwd"], under[f"hvt_{norm}_norm_bwd"]
        forward = [n for n in fwd if "rematted_computation" not in n]
        again = [n for n in fwd if "rematted_computation" in n]
        for stacks, inside in ((forward, "jvp("), (again, "transpose(jvp("),
                               (bwd, "transpose(jvp(")):
            assert len(stacks) == 3 * a_pass, under
            layers = sorted((0, 2, 4) * a_pass, reverse=inside != "jvp(")
            for layer, stack in zip(layers, stacks):
                assert f"/block_{layer}/gdn/{scope}/" in stack, stack
                assert inside in stack, stack
        assert not [n for n in forward if "transpose" in n]
    after = _kernel_counts(2, 8), _kernel_counts(4, 8)
    # three layers (and q and k) share a trace: one a kernel for each
    # context JAX traces it in and each scale, never one a layer
    moved = lambda i, kernel: after[i][kernel] - before[i][kernel]
    assert moved(0, "l2_bwd") == 2 and moved(1, "gated_bwd") == 1
    assert 2 <= moved(0, "l2_fwd") <= 4 and 1 <= moved(1, "gated_fwd") <= 2
    assert moved(1, "l2_fwd") == moved(0, "gated_fwd") == 0


def test_the_counter_is_served():
    """``hvt_head_norm_kernel_traces_total`` on ``/metrics``: a trace of
    each kernel counts once under its heads and their width, a second call
    of the same shape is served from the trace and counts nothing."""
    from horovod_tpu import metrics

    args, cot = _operands(16, 5, 128, seed=7)
    before = _kernel_counts(5, 128)
    gated = _with_gradients(
        lambda *a: norm_op.gated_norm_kernels(*a, eps=EPS), cot)
    l2 = _with_gradients(
        lambda x: norm_op.l2_norm_kernels(x, 128, eps=EPS), cot)
    for _ in range(2):
        gated(*args)
        l2(args[0])
    after = _kernel_counts(5, 128)
    assert {k: after[k] - before[k] for k in after} == dict.fromkeys(after, 1)
    text = metrics.prometheus_text()
    assert "hvt_head_norm_kernel_traces_total{" in text
    assert 'kernel="gated_bwd"' in text and 'heads="5"' in text
