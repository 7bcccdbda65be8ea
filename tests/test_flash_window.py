"""The flash kernels with a window (``flash_attention(..., window=w)``: row
``t`` sees the keys ``t - w < s <= t``) in the Pallas interpreter against
one masked softmax over whole rows, forward and the gradients of ``q``,
``k`` and ``v``; the schedule's second bound (the grid's last axis is as
long as a block's band, and a step's block index its tile of the band);
what is refused by name; and that a call without a window runs none of
it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as fa


def _qkv(s, h, h_kv, d=16, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    like = lambda key, heads: jax.random.normal(key, (1, s, heads, d))
    return (like(keys[0], h), like(keys[1], h_kv), like(keys[2], h_kv),
            like(keys[3], h))


def _masked_softmax(q, k, v, window):
    """One softmax over whole rows of scores, the mask from positions."""
    s, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    t = jnp.arange(s)
    seen = t[None, :] <= t[:, None]
    if window is not None:
        seen &= t[:, None] - t[None, :] < window
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _value_and_grads(attend, q, k, v, weight):
    return jax.value_and_grad(
        lambda q, k, v: (attend(q, k, v) * weight).sum(), (0, 1, 2))(q, k, v)


# (positions, window, block_q, block_k, query heads, key-value heads)
@pytest.mark.parametrize("s, window, block_q, block_k, h, h_kv", [
    pytest.param(256, 32, 64, 64, 2, 2, id="smaller-than-a-block"),
    pytest.param(256, 64, 64, 64, 2, 2, id="a-block"),
    pytest.param(256, 128, 32, 64, 2, 2, id="two-key-blocks"),
    pytest.param(256, 100, 64, 32, 4, 2, id="no-multiple-grouped"),
    pytest.param(256, 96, 128, 32, 2, 1, id="backward-halves-grouped"),
    pytest.param(384, 64, None, None, 2, 1, id="derived-tile"),
    pytest.param(256, 1, 32, 64, 1, 1, id="its-own-position-alone"),
    # a key block of whole lane tiles in halves, which a call without a
    # window takes by the half where a block ends inside the first
    # (PR 62): a windowed call walks whole sub-blocks (the half measured no
    # faster under a window). The band's far edge in a sub-block's first
    # half for the even blocks and in its second for the odd ones; the
    # other way round; a square tile; a window that ends inside the
    # block's own sub-block; a block a quarter of the sub-block
    pytest.param(1024, 257, 128, 256, 2, 1, id="lane-tiles-far-edge-even-odd"),
    pytest.param(1024, 385, 128, 256, 2, 2, id="lane-tiles-far-edge-odd-even"),
    pytest.param(768, 200, 128, 256, 4, 2, id="lane-tiles-no-multiple"),
    pytest.param(768, 120, 256, 256, 2, 2, id="lane-tiles-square"),
    pytest.param(512, 1, 128, 256, 1, 1, id="lane-tiles-its-own-position"),
    pytest.param(1024, 300, 64, 256, 2, 1, id="lane-tiles-a-quarter-block"),
])
def test_windowed_kernels_against_a_masked_softmax(s, window, block_q,
                                                   block_k, h, h_kv):
    q, k, v, weight = _qkv(s, h, h_kv, seed=s + window)
    blocks = dict(block_q=block_q, block_k=block_k)
    got, got_grads = _value_and_grads(
        lambda q, k, v: fa.flash_attention(q, k, v, window=window, **blocks),
        q, k, v, weight)
    want, want_grads = _value_and_grads(
        lambda q, k, v: _masked_softmax(q, k, v, window), q, k, v, weight)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for mine, theirs in zip(got_grads, want_grads):
        np.testing.assert_allclose(mine, theirs, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s, block_q, block_k, window", [
    (256, 64, 32, 256), (256, 64, 32, 300),
    # the call without a window takes the diagonal's sub-block by its
    # half, the windowed one whole: p is 0 in the other half (PR 62)
    (512, 128, 256, 512), (512, 128, 256, 600)])
def test_a_window_no_shorter_than_the_sequence_hides_nothing(
        s, block_q, block_k, window):
    # the same sub-blocks in the same order: the call without a window, to
    # the last bit, though its tiles are four times as long
    q, k, v, weight = _qkv(s, 4, 2)
    blocks = dict(block_q=block_q, block_k=block_k)
    got = _value_and_grads(lambda q, k, v: fa.flash_attention(
        q, k, v, window=window, **blocks), q, k, v, weight)
    want = _value_and_grads(lambda q, k, v: fa.flash_attention(
        q, k, v, **blocks), q, k, v, weight)
    for mine, theirs in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (mine == theirs).all()


def _grids(fn, *args):
    """The grid of every Pallas call in ``fn``'s jaxpr, inner jits too."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_the_grid_is_as_long_as_a_band_and_not_as_the_sequence():
    q, k, v, weight = _qkv(512, 2, 1)
    blocks = dict(block_q=64, block_k=64)
    grads = lambda **kw: _grids(lambda q, k, v: _value_and_grads(
        lambda q, k, v: fa.flash_attention(q, k, v, **blocks, **kw),
        q, k, v, weight), q, k, v)
    jax.clear_caches()
    # without a window: one tile of the whole sequence a block
    assert grads() == [(1, 2, 8, 1), (1, 2, 8, 1)]
    # a window of 100 keys: a tile is one sub-block, and a query block's
    # 163 keys (a K block's 163 rows) lie in at most three of the eight
    assert grads(window=100) == [(1, 2, 8, 3), (1, 2, 8, 3)]
    assert fa._band_steps("fwd", 16384, 512, 1024, 2048) == 3
    assert fa._band_steps("bwd", 16384, 512, 1024, 2048) == 3
    assert fa._band_steps("fwd", 256, 64, 64, 256) == 256 // 64


def test_a_block_names_its_tiles_of_the_band():
    # query block 5 of 64 rows, window 100: keys 221 .. 383, tiles 3 .. 5;
    # K block 2: rows 128 .. 290, tiles 2 .. 4; the first blocks' bands
    # begin at the sequence's start and the last K blocks' end at its end
    assert fa._band("fwd", 5, 64, 64, 100) == (3, 5)
    assert fa._band("bwd", 2, 64, 64, 100) == (2, 4)
    assert fa._band("fwd", 0, 64, 64, 100) == (0, 0)
    assert fa._band("bwd", 7, 64, 64, 100)[0] == 7
    assert fa._seq_tile(16384, 512, 1024) == 4096
    assert fa._seq_tile(16384, 512, 1024, window=2048) == 1024


def test_a_call_without_a_window_runs_none_of_it(monkeypatch):
    def never(*args, **kwargs):
        raise AssertionError("a call without a window asked for a band")

    monkeypatch.setattr(fa, "_band", never)
    monkeypatch.setattr(fa, "_band_steps", never)
    q, k, v, weight = _qkv(128, 2, 1)
    jax.clear_caches()
    got, _ = _value_and_grads(lambda q, k, v: fa.flash_attention(q, k, v),
                              q, k, v, weight)
    want, _ = _value_and_grads(lambda q, k, v: _masked_softmax(q, k, v, None),
                               q, k, v, weight)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    jax.clear_caches()


def test_the_trace_counter_carries_the_window():
    from horovod_tpu import metrics

    def count(kernel, window):
        m = metrics.registry().get("hvt_flash_kernel_traces_total")
        return m.labels(kernel=kernel, block_q="64", block_k="64",
                        derived="0", d_qk="16", d_v="16", d_rot="0",
                        chains=str(fa._chains(kernel, 64, 64, 4, True)),
                        window=str(window), held_steps="0",
                        halves="0").value if m else 0.0

    q, k, v, weight = _qkv(128, 1, 1)
    jax.clear_caches()
    before = [count(kernel, w) for kernel in ("fwd", "bwd") for w in (0, 48)]
    _value_and_grads(lambda q, k, v: fa.flash_attention(
        q, k, v, window=48, block_q=64, block_k=64), q, k, v, weight)
    after = [count(kernel, w) for kernel in ("fwd", "bwd") for w in (0, 48)]
    assert after == [before[0], before[1] + 1, before[2], before[3] + 1]
    # a windowed forward holds no step again (its grid is the band's
    # already) and walks whole sub-blocks, where the call without a window
    # takes the diagonal's by its half (PR 62: 128 rows under 256 keys)
    q, k, v, weight = _qkv(512, 1, 1)

    def wide(kernel, window, halves):
        m = metrics.registry().get("hvt_flash_kernel_traces_total")
        return m.labels(kernel=kernel, block_q="128", block_k="256",
                        derived="0", d_qk="16", d_v="16", d_rot="0",
                        chains=str(fa._chains(kernel, 128, 256, 4, True)),
                        window=str(window), held_steps="0",
                        halves=str(halves)).value if m else 0.0

    sets = (("fwd", 300, 0), ("fwd", 300, 1), ("fwd", 0, 1), ("fwd", 0, 0),
            ("bwd", 300, 0), ("bwd", 0, 0))
    before = [wide(*labels) for labels in sets]
    for window in (300, None):
        _value_and_grads(lambda q, k, v: fa.flash_attention(
            q, k, v, window=window, block_q=128, block_k=256),
            q, k, v, weight)
    assert [wide(*labels) for labels in sets] == [
        n + took for n, took in zip(before, (1, 0, 1, 0, 1, 1))]
    jax.clear_caches()


@pytest.mark.parametrize("kwargs, match", [
    (dict(window=64, causal=False), "a window without causal"),
    (dict(window=64, choice=True), "a window beside a choice"),
    (dict(window=64, rotated=True), "a window beside a rotated pair"),
    (dict(window=0), "a static count of keys, at least 1"),
    (dict(window=64.0), "a static count of keys, at least 1"),
])
def test_what_is_not_built_is_refused_by_name(kwargs, match):
    q, k, v, _ = _qkv(128, 2, 2)
    if kwargs.pop("choice", False):
        kwargs["choice"] = jnp.ones((1, 128, 128), jnp.int8)
    if kwargs.pop("rotated", False):
        kwargs.update(q_r=q[..., :8], k_r=k[:, :, 0, :8])
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(lambda q, k, v: fa.flash_attention(q, k, v, **kwargs),
                       q, k, v)
