"""``ops/dsa.py``: the index scores, the choice of the ``topk`` best causal
keys a query and the indexer's loss, each kernel in the Pallas interpreter
against its plain body, and the plain choice against ``jax.lax.top_k``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.models.dsa import chosen_attention
from horovod_tpu.ops import dsa

B, S, H, H_KV, D, J, E = 2, 256, 4, 2, 32, 4, 16


@pytest.fixture(scope="module")
def parts():
    keys = jax.random.split(jax.random.key(7), 6)
    normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    return {"q": normal(keys[0], B, S, H, D), "k": normal(keys[1], B, S, H_KV, D),
            "v": normal(keys[2], B, S, H_KV, D),
            "q_i": normal(keys[3], B, S, J, E), "k_i": normal(keys[4], B, S, E),
            "w": 0.1 * normal(keys[5], B, S, J)}


def _top_k_mask(scores, topk):
    """``jax.lax.top_k`` of every causal row, its indices scattered."""
    b, s, _ = scores.shape
    causal = np.tril(np.ones((s, s), bool))
    _, index = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                             min(topk, s))
    mask = np.zeros((b, s, s), bool)
    np.put_along_axis(mask, np.asarray(index), True, axis=-1)
    return mask & causal


def test_index_scores_kernel_is_the_plain_body(parts):
    want = dsa.index_scores_plain(parts["q_i"], parts["k_i"], parts["w"])
    got = dsa.index_scores(parts["q_i"], parts["k_i"], parts["w"])
    below = np.tril(np.ones((S, S), bool))
    assert np.all(np.isneginf(np.asarray(got))[:, ~below])
    assert np.all(np.isneginf(np.asarray(want))[:, ~below])
    np.testing.assert_allclose(np.asarray(got)[:, below],
                               np.asarray(want)[:, below], atol=2e-6)
    # sum_j w_j relu(q_j . k), by hand at one pair
    t, u = 200, 17
    by_hand = sum(float(parts["w"][1, t, j]) * max(0.0, float(
        parts["q_i"][1, t, j] @ parts["k_i"][1, u])) for j in range(J))
    assert float(want[1, t, u]) == pytest.approx(by_hand, abs=1e-5)


@pytest.fixture
def chunks_of_256():
    """The choice's passes in chunks of 256 columns, so that 768 positions
    are three of them and a row block's ``hi`` is rarely a whole one."""
    saved, dsa.CHOICE_CHUNK = dsa.CHOICE_CHUNK, 256
    dsa._choice_call.clear_cache()
    yield 256
    dsa.CHOICE_CHUNK = saved
    dsa._choice_call.clear_cache()


def _scores(parts, kind, seq):
    """Index scores ``[B, seq, seq]``, ``-inf`` above the diagonal."""
    if seq == S:
        scores = dsa.index_scores_plain(parts["q_i"], parts["k_i"],
                                        parts["w"])
    else:
        scores = jnp.where(
            np.tril(np.ones((seq, seq), bool)),
            jax.random.normal(jax.random.key(seq), (B, seq, seq)), -jnp.inf)
    if kind == "planted":       # halves, zeros of both signs among them
        scores = jnp.round(scores * 2) / 2
        assert bool(jnp.any((scores == 0) & jnp.signbit(scores)))
    elif kind == "all_equal":
        scores = jnp.zeros_like(scores)
    elif kind.startswith("ties_over_a_chunk_boundary"):
        # 38 distinct scores above four equal ones that lie on both sides
        # of column 256, everything else below: of the four, the first
        # two (columns 254, 255) or three (and 256) are still needed
        row = np.full(seq, -1.0, np.float32)
        row[:38] = 10.0 + np.arange(38)
        row[254:258] = 5.0
        scores = jnp.where(jnp.isneginf(scores), scores, jnp.asarray(row))
    return scores


@pytest.mark.parametrize("kind, seq, topk", [
    *((kind, S, topk) for kind in ("planted", "none", "all_equal")
      for topk in (40, 256, 300, 1)),
    # three chunks of columns, twelve row blocks a sequence, two sequences
    ("planted", 768, 300), ("none", 768, 300), ("all_equal", 768, 300),
    ("none", 768, 1), ("none", 768, 768),
    ("ties_over_a_chunk_boundary_2", 768, 40),
    ("ties_over_a_chunk_boundary_3", 768, 41),
    # the module's own chunk, two of them
    ("planted", 2 * dsa.CHOICE_CHUNK, 300)])
def test_choice_is_top_k_with_ties_to_the_lower_position(
        parts, kind, seq, topk, request):
    """Rows with planted ties (scores rounded to halves, zeros of both
    signs among them), a causal limit, ``topk`` below the row's length, at
    it and above it, one chunk of columns and several with equal scores on
    both sides of a chunk's edge: the plain body and the kernel are
    ``top_k``'s set, every row holds ``min(t + 1, topk)`` keys and none
    above ``t``, and the second result is the scores' log-sum-exp over the
    chosen keys, of one key the score itself."""
    if seq == 768:
        request.getfixturevalue("chunks_of_256")
    scores = _scores(parts, kind, seq)
    want = _top_k_mask(jnp.where(scores == 0, 0.0, scores), topk)
    plain, plain_lse = dsa.choose_plain(scores, topk)
    kernel, kernel_lse = dsa.choose(scores, topk)
    plain, kernel = np.asarray(plain), np.asarray(kernel)
    assert plain.dtype == kernel.dtype == np.int8
    assert np.array_equal(plain != 0, want)
    assert np.array_equal(kernel, plain)
    counts = np.minimum(np.arange(seq) + 1, topk)
    assert np.array_equal(kernel.sum(-1), np.broadcast_to(counts, (B, seq)))
    assert not kernel[:, ~np.tril(np.ones((seq, seq), bool))].any()
    if kind == "ties_over_a_chunk_boundary_2":
        assert list(kernel[1, -1, 254:258]) == [1, 1, 0, 0]
    if kind == "ties_over_a_chunk_boundary_3":
        assert list(kernel[1, -1, 254:258]) == [1, 1, 1, 0]
    lse = jax.nn.logsumexp(jnp.where(plain != 0, scores, -jnp.inf), axis=-1,
                           keepdims=True)
    for got in (plain_lse, kernel_lse):
        assert got.shape == (B, seq, 1) and got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(lse),
                                   rtol=1e-6, atol=1e-6)
        assert np.array_equal(np.asarray(got[:, 0, 0]),
                              np.asarray(scores[:, 0, 0]))


@pytest.mark.parametrize("planted", [1e9, np.inf, np.nan])
def test_choice_reads_nothing_above_the_diagonal(parts, planted):
    scores = dsa.index_scores_plain(parts["q_i"], parts["k_i"], parts["w"])
    noisy = jnp.where(jnp.isneginf(scores), planted, scores)
    for choose in (dsa.choose_plain, dsa.choose):
        for got, want in zip(choose(noisy, 40), choose(scores, 40)):
            assert np.array_equal(np.asarray(got), np.asarray(want))


def test_index_loss_kernel_is_the_plain_body_with_its_gradients(parts):
    """The value and the three gradients the kernel makes beside it against
    autodiff of the plain body; q, k and lse get none, and tied to the
    value (``with_gradient``) the three are ``jax.grad``'s, scaled by the
    cotangent."""
    scale = D ** -0.5
    scores = dsa.index_scores_plain(parts["q_i"], parts["k_i"], parts["w"])
    choice, _ = dsa.choose_plain(scores, 40)
    _, lse = chosen_attention(parts["q"], parts["k"], parts["v"], choice,
                              scale, False)
    plain = lambda q, k, q_i, k_i, w: dsa.index_loss_plain(
        q, k, lse, q_i, k_i, w, choice, scale)
    # what the mixer hands over: the choice kernel's two results
    chosen, lse_i = dsa.choose(scores, 40)
    assert np.array_equal(np.asarray(chosen), np.asarray(choice))

    def kernel(q, k, q_i, k_i, w):
        value, grads = dsa.index_loss(q, k, lse, q_i, k_i, w, lse_i, chosen,
                                      scale)
        return 3.0 * dsa.with_gradient(value, (q_i, k_i, w), grads)

    args = tuple(parts[n] for n in ("q", "k", "q_i", "k_i", "w"))
    want, want_grads = jax.value_and_grad(plain, argnums=(0, 1, 2, 3, 4))(*args)
    got, got_grads = jax.value_and_grad(kernel, argnums=(0, 1, 2, 3, 4))(*args)
    assert float(want) > 0.01
    assert float(got) == pytest.approx(3.0 * float(want), rel=1e-5)
    assert float(kernel(*args)) == pytest.approx(3.0 * float(want), rel=1e-5)
    for name, g, w in zip(("q", "k", "q_i", "k_i", "w"), got_grads,
                          want_grads):
        if name in ("q", "k"):
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(w))
        else:
            assert float(jnp.linalg.norm(w)) > 0
            assert float(jnp.linalg.norm(g - 3.0 * w)
                         / jnp.linalg.norm(3.0 * w)) < 1e-5, name


def test_index_loss_is_the_kl_by_hand(parts):
    """One row by hand: pbar the heads' mean of the softmax over the chosen
    keys, r the softmax of the index scores over the same keys."""
    scale = D ** -0.5
    scores = dsa.index_scores_plain(parts["q_i"], parts["k_i"], parts["w"])
    choice, _ = dsa.choose_plain(scores, 40)
    _, lse = chosen_attention(parts["q"], parts["k"], parts["v"], choice,
                              scale, False)
    each = []
    for b in range(B):
        for t in range(S):
            seen = np.asarray(choice[b, t]) != 0
            k = np.repeat(np.asarray(parts["k"][b]), H // H_KV, axis=1)
            main = np.einsum("hd,khd->hk", np.asarray(parts["q"][b, t]),
                             k)[:, seen] * scale
            pbar = np.mean(np.exp(main - main.max(-1, keepdims=True))
                           / np.exp(main - main.max(-1, keepdims=True)).sum(
                               -1, keepdims=True), axis=0)
            index = np.asarray(scores[b, t])[seen]
            log_r = index - index.max() - np.log(
                np.exp(index - index.max()).sum())
            each.append(float(np.sum(pbar * (np.log(pbar) - log_r))))
    got = dsa.index_loss_plain(parts["q"], parts["k"], lse, parts["q_i"],
                               parts["k_i"], parts["w"], choice, scale)
    assert float(got) == pytest.approx(np.mean(each), rel=1e-4)


def test_each_kernel_counts_its_trace_with_its_shape(parts):
    family = metrics.counter(
        "hvt_dsa_kernel_traces_total", "", ("kernel", "heads", "width", "seq",
                                            "topk", "columns"))
    at = lambda **labels: family.labels(
        **{k: str(v) for k, v in labels.items()}).value
    # the column tiles a pass walks in a sequence: both row blocks of 64
    # go by their one chunk of 128
    choice = dict(kernel="choice", heads=0, width=0, seq=128, topk=9,
                  columns=2)
    before = at(**choice)
    dsa.choose(jnp.zeros((1, 128, 128)), 9)
    assert at(**choice) == before + 1
    index = dict(kernel="index", heads=J, width=E, seq=128, topk=0, columns=0)
    before = at(**index)
    dsa.index_scores(parts["q_i"][:1, :128], parts["k_i"][:1, :128],
                     parts["w"][:1, :128])
    assert at(**index) == before + 1


def test_the_passes_walk_the_columns_below_a_row_blocks_diagonal():
    """``columns`` at the published length: 17,408 tiles of 128 where
    every column of every block is 32,768, and 8,256 of 16,384 (the
    causal share itself, 50.4%) were a chunk a tile and a block 128."""
    rows = dsa._pallas.largest(16384, dsa.CHOICE_ROWS, 32)
    chunk = dsa._pallas.largest(16384, dsa.CHOICE_CHUNK, dsa.LANES)
    assert (rows, chunk) == (64, 1024)
    assert dsa._walked(16384, rows, chunk) == 17408
    assert 16384 // rows * 16384 // dsa.LANES == 32768
    assert dsa._walked(16384, 128, 128) == 8256
    assert dsa._walked(256, 64, 256) == 4 * 2
