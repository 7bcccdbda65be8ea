"""``ops/sum_by_token.py``: the kernel (interpreted on the CPU) against one
scatter-add of the round's rows, and the grid its plan lists."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import sum_by_token as token_sum


def _round(n_tokens, assigned, seed, held=3):
    """A round's tokens as ``moe_route`` orders them (by held expert, by
    token within one; slots nobody chose after the assigned) and how many
    of its rows are assigned."""
    chosen = jnp.zeros(n_tokens * held, bool).at[jax.random.permutation(
        jax.random.key(seed), n_tokens * held)[:assigned]].set(True)
    order = jnp.argsort(jnp.where(chosen.reshape(n_tokens, held),
                                  jnp.arange(held), held).reshape(-1),
                        stable=True)
    return (order[:n_tokens] // held).astype(jnp.int32), min(assigned,
                                                             n_tokens)


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted-onto-a-total", "as-they-are"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n_tokens, width, assigned", [
    (32, 8, 0), (32, 8, 5), (32, 8, 16), (32, 8, 32), (32, 8, 45),
    (40, 8, 17), (7, 3, 4), (512, 128, 200), (512, 128, 512)],
    ids=["no-row", "a-few", "half", "every-row", "more-than-a-round",
         "tiles-of-8", "tiles-of-1", "two-tiles-four-chunks",
         "two-tiles-full"])
def test_kernel_is_the_scatter_add(n_tokens, width, assigned, dtype,
                                   weighted):
    """The sum by token of the assigned rows, each times its weight, onto
    a total, and of the rows as they are onto zeros, whatever lies in the
    rows past the assigned (here NaN). A bfloat16 row times the three
    bfloat16 parts of a float32 weight is the float32 product to a
    rounding of its last bit."""
    token, assigned = _round(n_tokens, assigned, seed=n_tokens + assigned)
    keys = jax.random.split(jax.random.key(width), 3)
    rows = jax.random.normal(keys[0], (n_tokens, width)).astype(dtype)
    rows = jnp.where((jnp.arange(n_tokens) < assigned)[:, None], rows,
                     jnp.nan)
    weight = jax.random.uniform(keys[1], (n_tokens,)) if weighted else None
    total = (jax.random.normal(keys[2], (n_tokens, width)) if weighted
             else None)
    plan = token_sum.plan(token, assigned, n_tokens)
    got = token_sum.sum_by_token(rows, plan, n_tokens, weight=weight,
                                 total=total, dtype=jnp.float32)
    want = token_sum.sum_by_token_plain(rows, token, assigned,
                                        weight=weight, total=total)
    assert got.dtype == jnp.float32 and got.shape == rows.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    if not weighted:        # ones and zeros: one exact product
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        low = token_sum.sum_by_token(rows, plan, n_tokens)
        assert low.dtype == rows.dtype
        np.testing.assert_array_equal(np.asarray(low),
                                      np.asarray(want.astype(rows.dtype)))


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted-onto-a-total", "as-they-are"])
@pytest.mark.parametrize("n_tokens, times, width, assigned", [
    (32, 3, 8, 0), (32, 3, 8, 70), (32, 3, 8, 96), (40, 2, 8, 57),
    (512, 3, 128, 1100), (256, 2, 128, 512)],
    ids=["no-row", "over-two-rows-a-token", "every-row", "tiles-of-8",
         "two-tiles-twelve-chunks", "one-tile-full"])
def test_a_round_of_several_rows_a_token(n_tokens, times, width, assigned,
                                         weighted):
    """A round of ``times x T`` rows over ``T`` tokens (a share that
    expects more than a row a token, ``models/moe.py``'s ``held_rows``): a
    token owns up to ``times`` of the round's rows and more, the plan's
    tiles are the tokens' and its chunks the rows', and the sum is the
    scatter-add onto ``[T, d]``."""
    held = times + 1
    chosen = jnp.zeros(n_tokens * held, bool).at[jax.random.permutation(
        jax.random.key(n_tokens + assigned), n_tokens * held)[
            :assigned]].set(True)
    order = jnp.argsort(jnp.where(chosen.reshape(n_tokens, held),
                                  jnp.arange(held), held).reshape(-1),
                        stable=True)
    n_rows = times * n_tokens
    token = (order[:n_rows] // held).astype(jnp.int32)
    keys = jax.random.split(jax.random.key(width), 3)
    rows = jax.random.normal(keys[0], (n_rows, width))
    rows = jnp.where((jnp.arange(n_rows) < assigned)[:, None], rows, jnp.nan)
    weight = jax.random.uniform(keys[1], (n_rows,)) if weighted else None
    total = (jax.random.normal(keys[2], (n_tokens, width)) if weighted
             else None)
    plan = token_sum.plan(token, assigned, n_tokens)
    assert plan.order.shape == (n_rows,)
    tile, chunk = token_sum.tile_rows(n_rows, n_tokens)
    assert plan.starts.shape == (n_tokens // tile + 1,)
    assert plan.tile_of.shape == (n_rows // chunk + n_tokens // tile,)
    got = token_sum.sum_by_token(rows, plan, n_tokens, weight=weight,
                                 total=total, dtype=jnp.float32)
    want = token_sum.sum_by_token_plain(
        rows, token, assigned, weight=weight,
        total=jnp.zeros((n_tokens, width)) if total is None else total)
    assert got.shape == (n_tokens, width)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("assigned", [0, 1, 100, 256, 511, 512])
def test_plan_lists_a_step_a_tile_and_chunk_that_meet(assigned,
                                                      monkeypatch):
    """Tiles of 64 tokens and chunks of 32 sorted rows over a round of
    512: the steps are the tiles in order, each with the chunks that hold
    its tokens' rows (one chunk, nothing live in it, for a tile with no
    row), as many as the assigned rows ask for and never more than
    ``T / chunk + T / tile``; the steps past them stay where the last
    one was, so that they move nothing."""
    monkeypatch.setattr(token_sum, "TILE", 64)
    monkeypatch.setattr(token_sum, "CHUNK", 32)
    n_tokens = 512
    token, assigned = _round(n_tokens, assigned, seed=assigned)
    plan = jax.tree.map(np.asarray,
                        token_sum.plan(token, assigned, n_tokens))
    sorted_tokens = plan.token.reshape(-1)
    np.testing.assert_array_equal(sorted_tokens[:assigned],
                                  np.sort(np.asarray(token)[:assigned]))
    assert (sorted_tokens[assigned:] == n_tokens).all()
    np.testing.assert_array_equal(
        np.asarray(token)[plan.order[:assigned]], sorted_tokens[:assigned])
    steps = int(plan.steps[0])
    assert plan.tile_of.shape == (n_tokens // 32 + n_tokens // 64,)
    assert n_tokens // 64 <= steps <= assigned // 32 + n_tokens // 64 + 1
    assert steps <= plan.tile_of.shape[0]
    assert (np.diff(plan.tile_of) >= 0).all()
    assert sorted(set(plan.tile_of[:steps])) == list(range(n_tokens // 64))
    for tile in range(n_tokens // 64):
        mine = np.flatnonzero((sorted_tokens >= tile * 64)
                              & (sorted_tokens < (tile + 1) * 64))
        assert (plan.starts[tile], plan.starts[tile + 1]) == (
            (mine[0], mine[-1] + 1) if mine.size else
            (plan.starts[tile],) * 2)
        chunks = plan.chunk_of[:steps][plan.tile_of[:steps] == tile]
        want = (np.arange(mine[0] // 32, mine[-1] // 32 + 1) if mine.size
                else chunks[:1])
        np.testing.assert_array_equal(chunks, want)
    assert (plan.tile_of[steps:] == n_tokens // 64 - 1).all()
    assert (plan.chunk_of[steps:] == plan.chunk_of[steps - 1]).all()
