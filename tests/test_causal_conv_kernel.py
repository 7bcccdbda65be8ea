"""The causal depthwise convolution's Pallas kernels
(``ops/causal_conv.py``), interpreted on the CPU: output and the three
gradients against ``jax.grad`` of the module's plain ``jax.numpy``
body; causality and the zeros before the sequence; what is
float32 inside the kernels; what the custom VJP keeps; and which program
gets the kernels, under which names."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import causal_conv as conv_op
from tests.test_gdn import _equations
from tests.test_gdn_kernel import _close, _has_pallas, _stacks

TAPS = 4
# the cells' widths cut small: qwen3next-s8192's 8192 channels are 16
# blocks of 512 lanes, nemotron3s-s8192's 1280 are 5 of 256
WIDTHS = {"8192-like": (32, 8), "1280-like": (40, 8)}
# (positions, a block's rows, a pass's rows)
BLOCKS = {"one-block": (32, 32, 16), "two-blocks": (64, 32, 8),
          "four-blocks": (64, 16, 16)}


def _operands(seq, channels, with_bias, batch=2, dtype=jnp.float32, seed=0,
              taps=TAPS):
    rng = np.random.RandomState(seed + seq + channels)
    normal = lambda *dims: jnp.asarray(rng.normal(size=dims), jnp.float32)
    x, cot = normal(batch, seq, channels), normal(batch, seq, channels)
    weight = jnp.asarray(rng.uniform(-0.5, 0.5, (taps, channels)),
                         jnp.float32)
    bias = normal(channels) if with_bias else None
    return (x.astype(dtype), weight, bias), cot.astype(dtype)


def _with_gradients(conv, cot):
    return jax.jit(lambda *a: (lambda o, vjp: (o, *vjp(cot)))(
        *jax.vjp(conv, *a)))


NAMES = ("o", "dx", "dweight", "dbias")


@pytest.mark.parametrize("blocks", list(BLOCKS))
@pytest.mark.parametrize("with_bias", [True, False],
                         ids=["a-bias", "no-bias"])
@pytest.mark.parametrize("width", list(WIDTHS))
def test_kernels_match_the_plain_body(width, with_bias, blocks):
    """Forward and backward kernels against ``causal_conv_plain`` and
    ``jax.grad`` of it: both cells' widths cut small, with and without a
    bias, one, two and four blocks of positions a sequence (so every halo
    is read: the tile before a block in both kernels, the tile after it in
    the backward), passes of one and two sublane tiles."""
    (channels, lanes), (seq, rows, sub) = WIDTHS[width], BLOCKS[blocks]
    args, cot = _operands(seq, channels, with_bias)
    got = _with_gradients(lambda *a: conv_op.causal_conv_kernels(
        *a, rows=rows, lanes=lanes, sub=sub), cot)(*args)
    want = _with_gradients(conv_op.causal_conv_plain, cot)(*args)
    assert (got[3] is None) == (want[3] is None) == (not with_bias)
    for name, x, same in zip(NAMES, got, want):
        if same is not None:
            assert x.shape == same.shape and x.dtype == same.dtype, name
            _close(x, same, name)


@pytest.mark.parametrize("taps", [1, 2, 4, 9])
def test_the_block_the_kernels_derive_and_other_taps(taps):
    """With no block named the kernels derive one from the shape (here
    the whole of a short sequence and of channels 128 does not divide),
    and serve from one tap to the nine a float32 tile before a block can
    hold; a tenth is refused by name."""
    args, cot = _operands(48, 24, True, taps=taps)
    plan = conv_op._plan(*args[:2], None, None, None)
    assert (plan.rows, plan.lanes, plan.sub) == (48, 24, 24)
    got = _with_gradients(conv_op.causal_conv_kernels, cot)(*args)
    for name, x, same in zip(NAMES, got, _with_gradients(
            conv_op.causal_conv_plain, cot)(*args)):
        _close(x, same, name)
    if taps == 9:
        with pytest.raises(ValueError, match="at most 9 taps"):
            conv_op.causal_conv_kernels(args[0], jnp.zeros((10, 24)))
        with pytest.raises(ValueError, match="does not tile"):
            conv_op.causal_conv_kernels(*args, rows=32)


@pytest.mark.parametrize("at", [0, 2, 15, 16, 17, 31, 32, 47])
def test_nothing_reaches_back_and_zeros_come_first(at):
    """A change at position ``at`` (inside a block, at its last row, at
    the next one's first) moves the output at ``at`` to ``at + taps - 1``
    and nothing else, before it least of all; its gradient reaches ``x``
    from ``at`` back to ``at - taps + 1`` and nowhere else. The first
    ``taps - 1`` positions see zeros: position 0 is ``silu(weight[-1] x[0]
    + bias)``."""
    (x, weight, bias), _ = _operands(48, 16, True, batch=1)
    conv = lambda x: conv_op.causal_conv_kernels(x, weight, bias, rows=16,
                                                 sub=8)
    moved = np.asarray(conv(x.at[0, at].add(1.0)) - conv(x))[0]
    touched = np.flatnonzero(np.abs(moved).max(axis=1))
    assert touched.min() == at, touched
    assert touched.max() == min(at + TAPS - 1, 47), touched
    dx = np.asarray(jax.grad(lambda x: conv(x)[0, at].sum())(x))[0]
    read = np.flatnonzero(np.abs(dx).max(axis=1))
    assert list(read) == list(range(max(at - TAPS + 1, 0), at + 1)), read
    _close(conv(x)[0, 0], jax.nn.silu(weight[-1] * x[0, 0] + bias),
           "position 0")


def _inside_bf16(x, weight, bias):
    """The convolution with bf16 arithmetic inside: what the kernels must
    not be."""
    low = jnp.bfloat16
    padded = jnp.pad(x.astype(low), ((0, 0), (TAPS - 1, 0), (0, 0)))
    out = bias.astype(low)
    for j in range(TAPS):
        out = out + weight[j].astype(low) * padded[:, j:j + x.shape[1]]
    return jax.nn.silu(out)


def test_float32_inside_the_kernels():
    """bf16 operands: every multiplication, addition and ``logistic`` in
    the two kernels' own jaxprs is of float32 values; what leaves is
    ``x.dtype`` for the output and ``dx`` and float32 for the sums of
    ``dweight`` and ``dbias``. And the numbers say so: against the plain
    body on the same numbers in float32 the kernels are as close as the
    plain body in bf16 operands is (the output's rounding alone), a
    bf16-inside convolution several times further and the weight's
    gradient further still."""
    args, cot = _operands(64, 32, True, dtype=jnp.bfloat16)
    conv = lambda *a: conv_op.causal_conv_kernels(*a, rows=32, lanes=8)
    jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(conv, *a)[1](cot))(*args)
    calls = {eqn.params["name"]: eqn for eqn in _equations(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"}
    assert set(calls) == {"hvt_causal_conv_fwd", "hvt_causal_conv_bwd"}
    for name, call in calls.items():
        seen = {"mul": 0, "add": 0, "logistic": 0}
        for eqn in _equations(call.params["jaxpr"]):
            if eqn.primitive.name in seen and eqn.outvars[0].aval.shape:
                seen[eqn.primitive.name] += 1
                assert eqn.outvars[0].aval.dtype == jnp.float32, (name, eqn)
        assert all(seen.values()), (name, seen)
    (out,) = [v.aval for v in calls["hvt_causal_conv_fwd"].outvars]
    dx, sums = [v.aval for v in calls["hvt_causal_conv_bwd"].outvars]
    assert out.dtype == dx.dtype == jnp.bfloat16
    assert out.shape == dx.shape == args[0].shape
    assert sums.dtype == jnp.float32 and sums.shape == (2, 2, TAPS + 1, 32)

    f32 = lambda t: t.astype(jnp.float32)
    want = _with_gradients(conv_op.causal_conv_plain, f32(cot))(
        f32(args[0]), *args[1:])
    far = lambda got: [float(np.linalg.norm(f32(a) - w) / np.linalg.norm(w))
                       for a, w in zip(got, want)]
    through = far(_with_gradients(conv, cot)(*args))
    plain = far(_with_gradients(conv_op.causal_conv_plain, cot)(*args))
    inside = far(_with_gradients(_inside_bf16, cot)(*args))
    for name, k, p, low in zip(NAMES, through, plain, inside):
        assert k <= 1.05 * p + 1e-6, (name, k, p)
        assert low >= (1.5 if name in ("o", "dx") else 20) * k, (name, low, k)


def test_the_vjp_keeps_its_operands_and_nothing_float32_of_their_size():
    """The residuals of the custom VJP are ``x``, ``weight`` and ``bias``:
    for a bf16 ``x`` nothing float32 of ``[b, s, c]`` is kept between the
    passes, where automatic differentiation of the plain body keeps
    several."""
    args, _ = _operands(64, 32, True, dtype=jnp.bfloat16)
    big = lambda kept: [a for a in jax.tree.leaves(kept)
                        if a.size >= args[0].size and a.dtype != jnp.bfloat16]
    plan = conv_op._plan(*args[:2], None, None, None)
    _, residuals = conv_op._conv_fwd(*args, plan)
    assert [r is a for r, a in zip(residuals, args)] == [True] * 3
    _, pullback = jax.vjp(conv_op.causal_conv_kernels, *args)
    assert not big(pullback)
    kept = [a for a in jax.tree.leaves(pullback) if a.size >= args[0].size]
    assert len(kept) == 1 and kept[0].dtype == jnp.bfloat16
    _, plain = jax.vjp(conv_op.causal_conv_plain, *args)
    assert big(plain)


# ---- which program gets the kernels, and the names the trace reads

def _kernel_counts(channels, block):
    from horovod_tpu import metrics

    m = metrics.registry().get("hvt_causal_conv_kernel_traces_total")
    return {kernel: m.labels(kernel=kernel, taps=str(TAPS),
                             channels=str(channels),
                             block=block).value if m else 0.0
            for kernel in ("fwd", "bwd")}


def test_the_choice(monkeypatch):
    """On the CPU ``causal_conv`` lowers to no ``pallas_call`` and is the
    plain body to the letter; on a TPU backend qwen3next-s8192's shape
    goes to the kernels, and nemotron3s-s8192's 1280 channels (narrower
    than the kernels were seen to pay for inside a step), a width 128 does
    not divide, a sequence the shortest block does not divide or taps that
    reach past a tile to ``jax.numpy`` without raising."""
    cell, _ = _operands(128, 2048, False, batch=1)
    ragged, _ = _operands(100, 2048, True, batch=1)
    narrow, _ = _operands(128, 1280, True, batch=1)
    assert not conv_op.serves(8192, 8192, TAPS)
    assert not _has_pallas(conv_op.causal_conv, *cell)
    assert (jax.jit(conv_op.causal_conv).lower(*cell).as_text()
            == jax.jit(conv_op.causal_conv_plain).lower(*cell).as_text()
            .replace("causal_conv_plain", "causal_conv"))
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        assert conv_op.serves(8192, 8192, TAPS)     # qwen3next-s8192
        assert conv_op.serves(2048, 8192, TAPS)     # its probe
        assert conv_op.serves(128, 2048, 9)
        # nemotron3s-s8192 and its probe: the step was seen to lose there
        assert not conv_op.serves(8192, 1280, TAPS)
        assert not conv_op.serves(2048, 1280, TAPS)
        assert not conv_op.serves(8192, 8192 + 64, TAPS)
        assert not conv_op.serves(8192 + 64, 8192, TAPS)
        assert not conv_op.serves(100, 8192, TAPS)
        assert not conv_op.serves(128, 2048, 10)
        # a function of its own each: a trace is cached by the function
        assert _has_pallas(lambda *a: conv_op.causal_conv(*a), *cell)
        assert not _has_pallas(lambda *a: conv_op.causal_conv(*a), *ragged)
        assert not _has_pallas(lambda *a: conv_op.causal_conv(*a), *narrow)
        assert not _has_pallas(jax.grad(lambda *a: conv_op.causal_conv(
            *a).sum(), (0, 1, 2)), *ragged)


@pytest.mark.parametrize("pattern,scope,module,channels", [
    ("G*G*G", "gdn_conv", "gdn", 2 * 2 * 8 + 4 * 8),
    ("M*M*M", "ssm_conv", "ssm", 4 * 8 + 2 * 2 * 8),
], ids=["gated-delta-net", "mamba-2"])
def test_the_names(pattern, scope, module, channels, monkeypatch):
    """With the kernels forced, the forward, recomputed and backward steps
    of three mixers hold ``hvt_causal_conv_fwd`` and
    ``hvt_causal_conv_bwd`` under the mixer's convolution scope
    (``gdn_conv``: what ``chipbench/layer_metrics/gdn_ms.py`` matches;
    ``ssm_conv``: ``ssm_ms.py``; the lowered text is read in
    ``tests/test_chip_compile.py``), the layers share a trace a kernel,
    and the counter says which kernels, width and block the job got."""
    from horovod_tpu.models import GPT, GPTConfig

    model = GPT(GPTConfig(
        vocab_size=64, n_layers=5, layer_pattern=pattern, d_model=32,
        n_heads=4, d_ff=16, dtype=jnp.float32, remat=True, use_flash=False,
        gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8,
        ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_state=8))
    tokens = jnp.zeros((2, 32), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)["params"]
    loss = lambda p: model.apply({"params": p}, tokens).mean()
    assert "hvt_causal_conv" not in jax.jit(jax.grad(loss)).lower(
        params).as_text()

    before = _kernel_counts(channels, f"32x{channels}")
    with monkeypatch.context() as m:
        m.setattr(conv_op, "serves", lambda *shape: True)
        jax.clear_caches()
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    jax.clear_caches()
    under = {"hvt_causal_conv_fwd": [], "hvt_causal_conv_bwd": []}
    for eqn, stack in _stacks(jaxpr.jaxpr):
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] in under):
            under[eqn.params["name"]].append(stack)
    forward = [n for n in under["hvt_causal_conv_fwd"]
               if "rematted_computation" not in n]
    again = [n for n in under["hvt_causal_conv_fwd"]
             if "rematted_computation" in n]
    for stacks, inside in ((forward, "jvp("), (again, "transpose(jvp("),
                           (under["hvt_causal_conv_bwd"], "transpose(jvp(")):
        assert len(stacks) == 3, under
        for layer, stack in zip(sorted((0, 2, 4), reverse=inside != "jvp("),
                                stacks):
            assert f"/block_{layer}/{module}/{scope}/" in stack, stack
            assert inside in stack, stack
    assert not [n for n in forward if "transpose" in n]
    after = _kernel_counts(channels, f"32x{channels}")
    # three layers share a trace: one a kernel for each context JAX traces
    # it in (the forward pass and its recomputation), never one a layer
    assert after["bwd"] - before["bwd"] == 1
    assert 1 <= after["fwd"] - before["fwd"] <= 2


def test_the_counter_is_served():
    """``hvt_causal_conv_kernel_traces_total`` on ``/metrics``: a trace of
    each kernel counts once under its taps, width and block, a second
    call of the same shape is served from the trace and counts nothing."""
    from horovod_tpu import metrics

    args, cot = _operands(32, 24, False, seed=7)
    before = _kernel_counts(24, "16x24")
    step = _with_gradients(
        lambda *a: conv_op.causal_conv_kernels(*a, rows=16), cot)
    step(*args)
    step(*args)
    after = _kernel_counts(24, "16x24")
    assert after["fwd"] - before["fwd"] == 1
    assert after["bwd"] - before["bwd"] == 1
    text = metrics.prometheus_text()
    assert 'hvt_causal_conv_kernel_traces_total{' in text
    assert 'kernel="bwd"' in text and 'block="16x24"' in text
