"""The rotary of ``q`` and ``k`` as a Pallas kernel (``ops/rotary.py``),
interpreted on the CPU: output and gradients against ``rotary_plain`` and
against a float32 ``numpy`` rotary; what the custom VJP keeps; which
program gets the kernel, under which names; and the counter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import rotary as rotary_op
from tests.test_gdn_kernel import _close, _has_pallas, _stacks

BASE = 1e4
# (query heads, key heads, a head's channels, of them turned): heads of 64
# (two a lane tile) and of 128, h d of 1280 (ten tiles) and of 512, grouped
# keys with fewer heads, 64 of 256 turned and 256 of 256 (the partner a
# tile away)
HEADS = {"20-on-4-of-64": (20, 4, 64, None), "8-on-2-of-64": (8, 2, 64, None),
         "4-on-1-of-128": (4, 1, 128, None), "2-on-1-of-256-turning-64":
         (2, 1, 256, 64), "2-on-2-of-256": (2, 2, 256, None)}
# (positions, a block's rows, a pass's rows)
ROWS = {"one-block": (16, 16, 8), "two-blocks": (32, 16, 16),
        "five-blocks-of-a-sequence-no-16-divides": (40, 8, 8),
        "derived": (48, None, None)}
# against the plain body rounded to the dtype: the order of two products'
# sum in float32, the odd last bit in bf16 where XLA rounded a product
REL = {jnp.float32: 1e-6, jnp.bfloat16: 3e-3}


def _operands(seq, heads, dtype=jnp.float32, batch=2, packed=False, seed=0):
    """``(q, k)``, their cotangents and the positions: every sequence's
    ``0..seq`` or, ``packed``, documents that start anew inside a sequence
    at places that differ a sequence."""
    h, h_k, dim, _ = HEADS[heads]
    rng = np.random.RandomState(seed + seq + h + dim)
    like = lambda n: jnp.asarray(rng.normal(size=(batch, seq, n, dim)),
                                 jnp.float32).astype(dtype)
    positions = jnp.broadcast_to(jnp.arange(seq), (batch, seq))
    if packed:
        starts = rng.randint(1, seq - 1, size=(batch, 1))
        positions = jnp.where(positions < starts, positions,
                              positions - starts)
    return (like(h), like(h_k)), (like(h), like(h_k)), positions


def _with_gradients(turn, cot):
    return jax.jit(lambda *a: (lambda o, vjp: (*o, *vjp(cot)))(
        *jax.vjp(turn, *a)))


def _numpy_rotary(x, positions, base, width):
    """The rotary written out in float64 ``numpy``, a channel at a time in
    the head: ``j`` of the first half of the turned width with ``j +
    half``."""
    x = np.asarray(x, np.float64)
    width = width or x.shape[-1]
    half = width // 2
    angle = np.asarray(positions, np.float64)[..., None, None] * (
        base ** (-np.arange(half) / half))
    out = x.copy()
    out[..., :half] = (x[..., :half] * np.cos(angle)
                       - x[..., half:width] * np.sin(angle))
    out[..., half:width] = (x[..., :half] * np.sin(angle)
                            + x[..., half:width] * np.cos(angle))
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("rows", list(ROWS))
@pytest.mark.parametrize("heads", list(HEADS))
def test_the_kernel_matches_the_plain_body(heads, rows, dtype):
    """``hvt_rotary_fwd`` and ``hvt_rotary_bwd`` on a layer's ``q`` and
    ``k`` in one call against ``rotary_plain`` and ``jax.grad`` of it, with
    positions that differ a sequence: heads of 64, 128 and 256, grouped
    keys, a partial width, one, two and five blocks of positions and the
    block the kernel derives (a block that does not divide the positions is
    refused: ``test_the_blocks_the_kernel_derives_and_refuses``)."""
    seq, block, sub = ROWS[rows]
    width = HEADS[heads][3]
    xs, cot, positions = _operands(seq, heads, dtype, packed=True)
    got = _with_gradients(lambda *a: rotary_op.rotary_kernels(
        a, positions, BASE, width, rows=block, sub=sub), cot)(*xs)
    want = _with_gradients(lambda *a: tuple(rotary_op.rotary_plain(
        x, positions, BASE, width) for x in a), cot)(*xs)
    for name, x, same in zip(("q", "k", "dq", "dk"), got, want):
        assert x.shape == same.shape and x.dtype == same.dtype, name
        _close(x, same, name, REL[dtype])


@pytest.mark.parametrize("heads", list(HEADS))
def test_both_bodies_are_the_rotary_written_out(heads):
    """In float32 the plain body and the kernel are the float64 ``numpy``
    rotary to rounding, and the gradient is the inverse turn of the
    cotangent (the rotary is orthogonal): what is turned is the first
    ``width`` channels, position 0 turns nothing, and a later one
    does."""
    width = HEADS[heads][3]
    xs, cot, positions = _operands(32, heads, packed=True)
    for name, turn in (
            ("plain", lambda *a: tuple(rotary_op.rotary_plain(
                x, positions, BASE, width) for x in a)),
            ("kernel", lambda *a: rotary_op.rotary_kernels(
                a, positions, BASE, width))):
        q, k, dq, dk = _with_gradients(turn, cot)(*xs)
        for got, x in ((q, xs[0]), (k, xs[1])):
            _close(got, _numpy_rotary(x, positions, BASE, width), name, 2e-6)
        for got, g in ((dq, cot[0]), (dk, cot[1])):
            _close(got, _numpy_rotary(g, -positions, BASE, width),
                   f"{name}'s gradient", 2e-6)
        np.testing.assert_array_equal(np.asarray(q[:, 0]),
                                      np.asarray(xs[0][:, 0]))
        if width:
            np.testing.assert_array_equal(np.asarray(q[..., width:]),
                                          np.asarray(xs[0][..., width:]))
    # a table rounded to bf16 is the plain body's, to the letter
    x = xs[0].astype(jnp.bfloat16)
    cos, sin = rotary_op._tables(positions, BASE, x.shape[-1],
                                 width or x.shape[-1], x.dtype)
    assert cos.dtype == sin.dtype == jnp.bfloat16
    assert cos.shape == (2, 32, max(x.shape[-1], 128))


def test_one_array_any_rank_and_one_function():
    """``rotary`` takes an array or a tuple; off the kernel it is
    ``rotary_plain`` to the letter at any rank (a key without a head axis
    turns as a head's does); and the width None is the whole head."""
    (q, k), _, positions = _operands(16, "8-on-2-of-64")
    one = rotary_op.rotary(q, positions, BASE)
    pair = rotary_op.rotary((q, k), positions, BASE)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(pair[0]))
    np.testing.assert_array_equal(
        np.asarray(pair[1]),
        np.asarray(rotary_op.rotary_plain(k, positions, BASE, 64)))
    np.testing.assert_array_equal(
        np.asarray(rotary_op.rotary(k[:, :, 0], positions, BASE)),
        np.asarray(pair[1][:, :, 0]))
    assert (jax.jit(lambda x: rotary_op.rotary(x, positions, BASE))
            .lower(q).as_text()
            == jax.jit(lambda x: rotary_op.rotary_plain(x, positions, BASE))
            .lower(q).as_text())


def test_the_blocks_the_kernel_derives_and_refuses(monkeypatch):
    """A block's rows come of the heads as VMEM holds them (a head of 64
    fills 128 lanes) and divide the positions, in passes of the bf16 tile's
    16: 128 at each of the benchmark's shapes, the whole sequence where no
    multiple of 16 divides it. A block that does not divide the positions
    (its last step would reach past the arrays: on the chip, where XLA had
    laid one at the end of VMEM, past the memory), a pass that does not
    divide the block, arrays of different positions or widths and an odd
    width are refused by name."""
    (q, k), _, positions = _operands(32, "8-on-2-of-64")
    assert rotary_op.SUB == 16
    plans, real = [], rotary_op._turn
    monkeypatch.setattr(rotary_op, "_turn", lambda xs, cos, sin, plan: (
        plans.append(plan), real(xs, cos, sin, plan))[1])
    rotary_op.rotary_kernels((q, k), positions, BASE)
    like = lambda s, h, d: jax.ShapeDtypeStruct((1, s, h, d), jnp.bfloat16)
    for seq, heads, dim in ((1024, (20, 20), 64), (16384, (32, 4), 128),
                            (4096, (20, 20), 64), (8192, (32,), 64),
                            (8192, (16, 2), 256), (1040, (2, 1), 256),
                            (40, (8, 2), 64)):
        jax.eval_shape(
            lambda *a: rotary_op.rotary_kernels(
                a, jnp.arange(seq)[None], BASE),
            *(like(seq, h, dim) for h in heads))
    assert [(p.rows, p.sub) for p in plans] == [
        (32, 16), (128, 16), (128, 16), (128, 16), (128, 16), (128, 16),
        (208, 16), (40, 8)]
    assert not any(seq % p.rows for seq, p in zip(
        (32, 1024, 16384, 4096, 8192, 8192, 1040, 40), plans))
    with pytest.raises(ValueError, match="divide the positions"):
        rotary_op.rotary_kernels((q,), positions, BASE, rows=24, sub=8)
    with pytest.raises(ValueError, match="passes"):
        rotary_op.rotary_kernels((q,), positions, BASE, rows=32, sub=24)
    with pytest.raises(ValueError, match="even width"):
        rotary_op.rotary_kernels((q,), positions, BASE, 31)
    with pytest.raises(ValueError, match="share"):
        rotary_op.rotary_kernels((q, k[:, :16]), positions, BASE)


def test_the_vjp_keeps_the_tables_alone():
    """Nothing of ``x`` is kept for the backward pass: the residuals of
    ``jax.vjp`` through the kernel are the two tables, ``[b, s, 128]`` in
    ``x.dtype``."""
    (q, k), _, positions = _operands(32, "8-on-2-of-64", jnp.bfloat16)
    _, vjp = jax.vjp(lambda *a: rotary_op.rotary_kernels(
        a, positions, BASE), q, k)
    kept = [leaf for leaf in jax.tree.leaves(vjp) if hasattr(leaf, "shape")
            and leaf.size > 1]
    assert sorted((leaf.shape, str(leaf.dtype)) for leaf in kept) == [
        ((2, 32, 128), "bfloat16")] * 2, [leaf.shape for leaf in kept]


# ---- which program gets the kernel, and the names the trace reads

def _kernel_counts(heads, dim, width):
    from horovod_tpu import metrics

    m = metrics.registry().get("hvt_rotary_kernel_traces_total")
    return {kernel: m.labels(kernel=kernel, heads=str(heads), dim=str(dim),
                             width=str(width)).value if m else 0.0
            for kernel in ("fwd", "bwd")}


def test_the_choice(monkeypatch):
    """On the CPU ``rotary`` lowers to no ``pallas_call``; on a TPU backend
    ``[b, s, h, d]`` with ``h d`` in whole lane tiles goes to the kernel,
    and rank 3, ``h d`` = 64, an odd ``s``, a width that is neither inside
    a tile nor whole tiles apart, a head that straddles tiles or is
    narrower than 64, four bytes a channel, and heads of whole tiles behind
    a norm over the whole width (``flat``) go to ``jax.numpy`` without
    raising; of a pair each array by its own shape."""
    (q, k), _, positions = _operands(32, "8-on-2-of-64", jnp.bfloat16,
                                     batch=1)
    turn = lambda *a: rotary_op.rotary(a, positions, BASE)
    bf16 = jnp.bfloat16
    assert not rotary_op.serves(q.shape, bf16)
    assert not _has_pallas(turn, q, k)
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        assert rotary_op.serves((8, 1024, 20, 64), bf16)        # gpt2l-s1024
        assert rotary_op.serves((1, 16384, 32, 128), bf16)      # trinitymini
        assert rotary_op.serves((1, 16384, 4, 128), bf16)
        # four bytes a channel and heads of 32: not compiled for the chip
        assert not rotary_op.serves((1, 16384, 4, 128), jnp.float32)
        assert not rotary_op.serves((2, 8192, 8, 32), bf16)
        # behind a norm over the whole width (olmoe-s4096): heads of whole
        # tiles would be copied heads major; heads of 64 are padded anyway
        assert not rotary_op.serves((2, 4096, 16, 128), bf16, None, True)
        assert rotary_op.serves((2, 4096, 32, 64), bf16, None, True)
        assert rotary_op.serves((2, 8192, 32, 64), bf16)        # kanana2's q_r
        assert rotary_op.serves((2, 8192, 16, 256), bf16, 64)   # qwen3next
        assert rotary_op.serves((2, 8192, 2, 256), bf16, 256)
        assert not rotary_op.serves((2, 8192, 64), bf16)        # a shared key
        assert not rotary_op.serves((2, 8192, 1, 64), bf16)
        assert not rotary_op.serves((2, 8200, 20, 64), bf16)
        assert not rotary_op.serves((2, 8192, 16, 256), bf16, 192)
        assert not rotary_op.serves((2, 8192, 4, 96), bf16)
        assert not rotary_op.serves((2, 8192, 20, 64), bf16, 31)
        # keyevl2's index queries: four bytes a channel padded to a tile
        assert not rotary_op.serves((1, 16384, 16, 64), jnp.float32)
        # a function of its own each: a trace is cached by the function
        assert _has_pallas(lambda *a: turn(*a), q, k)
        assert _has_pallas(jax.grad(lambda *a: sum(
            t.sum().astype(jnp.float32) for t in turn(*a)), (0, 1)), q, k)
        one_head = k[:, :, :1]
        calls = [eqn for eqn, _ in _stacks(jax.make_jaxpr(
            lambda *a: turn(*a))(q, one_head).jaxpr)
            if eqn.primitive.name == "pallas_call"]
        assert len(calls) == 1 and len(calls[0].outvars) == 1
        assert not _has_pallas(lambda *a: turn(*a), one_head, k[:, :, 0])


def test_the_names(monkeypatch):
    """With the kernel forced, the forward, recomputed and backward steps
    of three attention layers hold one ``hvt_rotary_fwd`` or
    ``hvt_rotary_bwd`` a layer and pass, for ``q`` and ``k`` together,
    under ``attn_rope`` (what ``chipbench/layer_metrics/attn_elementwise_ms
    .py`` matches), the layers share a trace, and the counter says which
    heads and widths the job got."""
    from horovod_tpu.models import GPT, GPTConfig

    model = GPT(GPTConfig(
        vocab_size=64, n_layers=3, d_model=32, n_heads=4, n_kv_heads=2,
        d_ff=16, dtype=jnp.float32, remat=True, use_flash=False))
    tokens = jnp.zeros((2, 32), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)["params"]
    loss = lambda p: model.apply({"params": p}, tokens).mean()
    assert "hvt_rotary" not in jax.jit(jax.grad(loss)).lower(
        params).as_text()

    before = _kernel_counts(4, 8, 8), _kernel_counts(2, 8, 8)
    with monkeypatch.context() as m:
        m.setattr(rotary_op, "serves", lambda *shape: True)
        jax.clear_caches()
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    jax.clear_caches()
    under = {"hvt_rotary_fwd": [], "hvt_rotary_bwd": []}
    for eqn, stack in _stacks(jaxpr.jaxpr):
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] in under):
            assert len(eqn.outvars) == 2        # q and k: one call
            under[eqn.params["name"]].append(stack)
    fwd, bwd = under["hvt_rotary_fwd"], under["hvt_rotary_bwd"]
    forward = [n for n in fwd if "rematted_computation" not in n]
    again = [n for n in fwd if "rematted_computation" in n]
    for stacks, inside in ((forward, "jvp("), (again, "transpose(jvp("),
                           (bwd, "transpose(jvp(")):
        assert len(stacks) == 3, under
        for layer, stack in zip(
                sorted(range(3), reverse=inside != "jvp("), stacks):
            assert f"/block_{layer}/attn/attn_rope/" in stack, stack
            assert inside in stack, stack
    after = _kernel_counts(4, 8, 8), _kernel_counts(2, 8, 8)
    # three layers share a trace: one for each context JAX traces it in,
    # never one a layer
    for i in (0, 1):
        assert after[i]["bwd"] - before[i]["bwd"] == 1
        assert 1 <= after[i]["fwd"] - before[i]["fwd"] <= 2


def test_the_counter_is_served():
    """``hvt_rotary_kernel_traces_total`` on ``/metrics``: a trace counts
    each array once under its heads, their width and the width turned, and
    a second call of the same shape is served from the trace and counts
    nothing."""
    from horovod_tpu import metrics

    xs, cot, positions = _operands(16, "2-on-1-of-256-turning-64", seed=7)
    before = _kernel_counts(2, 256, 64), _kernel_counts(1, 256, 64)
    turn = _with_gradients(lambda *a: rotary_op.rotary_kernels(
        a, positions, BASE, 64), cot)
    for _ in range(2):
        turn(*xs)
    after = _kernel_counts(2, 256, 64), _kernel_counts(1, 256, 64)
    for was, now in zip(before, after):
        assert {k: now[k] - was[k] for k in now} == {"fwd": 1, "bwd": 1}
    text = metrics.prometheus_text()
    assert "hvt_rotary_kernel_traces_total{" in text
    assert 'kernel="bwd"' in text and 'width="64"' in text
