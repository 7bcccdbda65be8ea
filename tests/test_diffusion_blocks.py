"""Block-diffusion training (``GPTConfig.diffusion_block``; PR 64): the
flash kernels' causal limit by blocks (``flash_attention(..., blocks=B,
strict=...)``) in the Pallas interpreter against one masked softmax over
whole rows, the tiles each kernel visits, what is refused by name; the
weighted fused loss; the noising; and ``models.GPT`` on a clean and a
noised copy of every sequence against ``chipbench/reference/sdar.py``, with
the wrong programs that each tolerance has to tell."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import GPT, GPTConfig, noise_blocks, transformer
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.losses import softmax_cross_entropy_fused


# ------------------------------------------------------------- the kernels

def _qkv(s, h, h_kv, d=16, seed=0):
    keys = jax.random.split(jax.random.key(seed), 5)
    like = lambda key, heads: jax.random.normal(key, (1, s, heads, d))
    return (like(keys[0], h), like(keys[1], h_kv), like(keys[2], h_kv),
            like(keys[3], h), jax.random.normal(keys[4], (1, s, h)))


def _seen(s, size, strict):
    block = np.arange(s) // size
    return (block[None, :] < block[:, None] if strict
            else block[None, :] <= block[:, None])


def _masked_softmax(q, k, v, size, strict):
    """One softmax over whole rows, the rule as a dense mask; a row that
    sees nothing gives a zero row and ``lse = -inf``."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    scores = jnp.where(_seen(q.shape[1], size, strict), scores, -jnp.inf)
    most = jnp.max(scores, -1, keepdims=True)
    p = jnp.exp(scores - jnp.where(jnp.isfinite(most), most, 0.0))
    total = p.sum(-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.maximum(total, 1e-30), v)
    lse = (jnp.where(jnp.isfinite(most), most, 0.0) + jnp.log(total))[..., 0]
    return o, jnp.transpose(lse, (0, 2, 1))


def _weighed(attend, q, k, v, w_o, w_lse):
    """Value and the three gradients of a weighted sum of ``o`` and of
    ``lse`` where it is finite (a row that sees nothing has none)."""
    def total(q, k, v):
        o, lse = attend(q, k, v)
        return ((o * w_o).sum()
                + jnp.where(jnp.isfinite(lse), lse * w_lse, 0.0).sum()), (
                    o, lse)

    return jax.value_and_grad(total, (0, 1, 2), has_aux=True)(q, k, v)


# (positions, block length, strict, block_q, block_k, heads, key-value heads)
@pytest.mark.parametrize("s, size, strict, block_q, block_k, h, h_kv", [
    pytest.param(128, 4, False, 64, 64, 2, 2, id="inclusive-4"),
    pytest.param(128, 4, True, 64, 64, 2, 1, id="strict-4-grouped"),
    pytest.param(128, 32, False, 64, 32, 4, 2, id="inclusive-32-grouped"),
    pytest.param(128, 32, True, 64, 64, 2, 2, id="strict-32"),
    # a key block of whole lane tiles in halves: the diagonal's sub-block
    # by its half (PR 62), whose edge is a block's edge
    pytest.param(256, 4, True, 128, 256, 2, 1, id="strict-4-halves"),
])
def test_block_causal_kernels_against_a_masked_softmax(
        s, size, strict, block_q, block_k, h, h_kv):
    """Forward, ``lse`` and the gradients of q, k and v (through ``o`` and
    through ``lse``), in both forms; in the strict form the first block's
    rows see nothing: zero rows, ``lse = -inf``, no gradient, no NaN."""
    q, k, v, w_o, w_lse = _qkv(s, h, h_kv, seed=s + size)
    tile = dict(block_q=block_q, block_k=block_k)
    (_, (o, lse)), grads = _weighed(
        lambda q, k, v: fa.flash_attention_with_lse(
            q, k, v, blocks=size, strict=strict, **tile), q, k, v, w_o, w_lse)
    (_, (want_o, want_lse)), want = _weighed(
        lambda q, k, v: _masked_softmax(q, k, v, size, strict),
        q, k, v, w_o, w_lse)
    np.testing.assert_allclose(o, want_o, rtol=2e-5, atol=2e-5)
    nothing = np.isinf(np.asarray(want_lse))
    assert nothing.sum() == (size * h if strict else 0)
    np.testing.assert_array_equal(np.isinf(np.asarray(lse)), nothing)
    np.testing.assert_allclose(np.where(nothing, 0.0, lse),
                               np.where(nothing, 0.0, want_lse),
                               rtol=2e-5, atol=2e-5)
    if strict:
        assert (np.asarray(o)[:, :size] == 0).all()
        assert (np.asarray(grads[0])[:, :size] == 0).all()
    for mine, theirs in zip(grads, want):
        assert np.isfinite(np.asarray(mine)).all()
        np.testing.assert_allclose(mine, theirs, rtol=3e-5, atol=3e-5)


def _pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_eqns(sub)


def _block_index(mapping, *step):
    closed = mapping.index_map_jaxpr
    return tuple(int(i) for i in jax.core.eval_jaxpr(
        closed.jaxpr, closed.consts, *(jnp.int32(i) for i in step)))


@pytest.mark.parametrize("size, strict", [(4, False), (4, True), (32, True)])
def test_the_kernels_visit_exactly_the_tiles_with_a_visible_pair(
        size, strict, monkeypatch):
    """With the streamed tile one sub-block long, the tiles a kept block's
    grid steps name (read from the traced ``pallas_call``s' grids and index
    maps, as ``test_flash_window.py`` reads a band's) are the tiles some
    row of the block sees a key of (the forward), or some key of the block
    is seen from (the backward), counted from the rule itself: the causal
    call's tiles, no more and no fewer, in both forms."""
    s, block_q, block_k = 512, 64, 128
    monkeypatch.setattr(fa, "_SEQ_TILE", 128)
    q, k, v, *_ = _qkv(s, 1, 1)
    jax.clear_caches()
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: fa.flash_attention(
        q, k, v, blocks=size, strict=strict, block_q=block_q,
        block_k=block_k).sum(), (0, 1, 2)))(q, k, v)
    causal = jax.make_jaxpr(jax.grad(lambda q, k, v: fa.flash_attention(
        q, k, v, block_q=block_q, block_k=block_k).sum(), (0, 1, 2)))(q, k, v)
    jax.clear_caches()
    seen = _seen(s, size, strict)
    for name, kept, operand in (("hvt_flash_fwd", block_q, 1),
                                ("hvt_flash_bwd", block_k, 2)):
        call, = (e for e in _pallas_eqns(jaxpr.jaxpr)
                 if e.params["name"] == name)
        plain, = (e for e in _pallas_eqns(causal.jaxpr)
                  if e.params["name"] == name)
        grid = call.params["grid_mapping"].grid
        assert grid == plain.params["grid_mapping"].grid
        tile = 128
        assert grid == (1, 1, s // kept, s // tile)
        mapping = call.params["grid_mapping"].block_mappings[operand]
        for i in range(s // kept):
            named = {_block_index(mapping, 0, 0, i, step)[2]
                     for step in range(s // tile)}
            mine = slice(i * kept, (i + 1) * kept)
            pairs = seen[mine] if name == "hvt_flash_fwd" else seen[:, mine].T
            visible = {t for t in range(s // tile)
                       if pairs[:, t * tile:(t + 1) * tile].any()}
            assert named == visible, (name, i, named, visible)


@pytest.mark.parametrize("kwargs, match", [
    (dict(blocks=4, causal=False), "blocks without causal"),
    (dict(blocks=4, window=64), "blocks beside a window"),
    (dict(blocks=4, choice=True), "blocks beside a choice"),
    (dict(blocks=4, rotated=True), "blocks beside a rotated pair"),
    (dict(blocks=0), "a static length in positions"),
    (dict(blocks=4.0), "a static length in positions"),
    (dict(strict=True), "strict is a form of blocks"),
    (dict(blocks=48), "divide no sub-block"),
    (dict(blocks=64, strict=True, block_q=64, block_k=64),
     "more than one in the strict form"),
])
def test_what_is_not_built_beside_blocks_is_refused_by_name(kwargs, match):
    q, k, v, *_ = _qkv(128, 2, 2)
    if kwargs.pop("choice", False):
        kwargs["choice"] = jnp.ones((1, 128, 128), jnp.int8)
    if kwargs.pop("rotated", False):
        kwargs.update(q_r=q[..., :8], k_r=k[:, :, 0, :8])
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(lambda q, k, v: fa.flash_attention(q, k, v, **kwargs),
                       q, k, v)


def test_the_trace_counter_says_which_mask():
    from horovod_tpu import metrics

    def count(kernel):
        m = metrics.registry().get("hvt_flash_kernel_traces_total")
        return m.labels(kernel=kernel, block_q="64", block_k="64",
                        derived="0", d_qk="16", d_v="16", d_rot="0",
                        chains=str(fa._chains(kernel[:3], 64, 64, 4, True)),
                        window="0", held_steps="0",
                        halves="0").value if m else 0.0

    names = ("fwd", "bwd", "fwd_blocks4", "bwd_blocks4", "fwd_blocks4_strict",
             "bwd_blocks4_strict")
    q, k, v, w_o, w_lse = _qkv(128, 1, 1)
    jax.clear_caches()
    before = [count(name) for name in names]
    for strict in (False, True):
        _weighed(lambda q, k, v: fa.flash_attention_with_lse(
            q, k, v, blocks=4, strict=strict, block_q=64, block_k=64),
            q, k, v, w_o, w_lse)
    assert [count(name) for name in names] == [
        n + took for n, took in zip(before, (0, 0, 1, 1, 1, 1))]
    jax.clear_caches()


# ---------------------------------------------------------------- the loss

def _loss_case(seq=50, weighted=True):
    keys = jax.random.split(jax.random.key(3), 4)
    hidden = jax.random.normal(keys[0], (2, seq, 16))
    head = jax.random.normal(keys[1], (40, 16)) * 0.3
    targets = jax.random.randint(keys[2], (2, seq), 0, 40)
    weights = (jax.random.uniform(keys[3], (2, seq)) < 0.5) / jnp.linspace(
        0.05, 1.0, seq) if weighted else None
    return hidden, head, targets, weights


def _unchunked(hidden, head, targets, weights):
    logits = jnp.einsum("bsd,vd->bsv", hidden, head)
    each = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, targets[..., None], -1)[..., 0]
    return jnp.sum(each * weights) / targets.size


def test_weighted_fused_loss_against_the_unchunked_weighted_mean():
    """Value and both gradients at a length that is no multiple of the
    chunk (50 positions in chunks of 16), normalised by the number of
    positions and not by the weights' sum; the weights take no gradient."""
    hidden, head, targets, weights = _loss_case()
    assert abs(float(weights.sum()) - targets.size) > 1.0
    got = jax.value_and_grad(lambda h, e: softmax_cross_entropy_fused(
        h, e, targets, chunk=16, weights=weights), (0, 1))(hidden, head)
    want = jax.value_and_grad(lambda h, e: _unchunked(
        h, e, targets, weights), (0, 1))(hidden, head)
    for mine, theirs in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(mine, theirs, rtol=2e-5, atol=2e-6)
    d_weights = jax.grad(lambda w: softmax_cross_entropy_fused(
        hidden, head, targets, chunk=16, weights=w))(weights)
    assert (np.asarray(d_weights) == 0).all()
    with pytest.raises(ValueError, match="one a position"):
        softmax_cross_entropy_fused(hidden, head, targets,
                                    weights=weights[:, :-1])


def test_no_weights_is_the_loss_it_was_bit_for_bit():
    """``weights=None`` multiplies by the mask of the real positions, as
    before there was such an argument: weights of one at every position
    give the same bits, value and gradients, and the counter says which
    form was traced."""
    from horovod_tpu import metrics

    hidden, head, targets, _ = _loss_case(weighted=False)

    def forms():
        m = metrics.registry().get("hvt_loss_chunks_traced_total")
        return [m.labels(chunk="16", vocab="40", form=form).value if m
                else 0.0 for form in ("value_and_grads",
                                      "value_and_grads_weighted")]

    before = forms()
    run = lambda **kw: jax.value_and_grad(
        lambda h, e: softmax_cross_entropy_fused(
            h, e, targets, chunk=16, **kw), (0, 1))(hidden, head)
    plain = run()
    ones = run(weights=jnp.ones(targets.shape))
    for mine, theirs in zip(jax.tree.leaves(plain), jax.tree.leaves(ones)):
        assert (np.asarray(mine) == np.asarray(theirs)).all()
    assert forms() == [before[0] + 4, before[1] + 4]    # 50 in chunks of 16


# ------------------------------------------------------------- the noising

def test_noise_blocks():
    """The same key gives the same batch; a block's masked share goes with
    its ``t`` (read back from the weights, ``1 / t`` where masked); the
    mask id is nowhere in the clean half; targets are the ids."""
    ids = jax.random.randint(jax.random.key(0), (4, 4096), 0, 99)
    tokens, targets, weights = noise_blocks(jax.random.key(1), ids, 64, 99,
                                            eps=1e-3)
    again = noise_blocks(jax.random.key(1), ids, 64, 99, eps=1e-3)
    for a, b in zip((tokens, targets, weights), again):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    other = noise_blocks(jax.random.key(2), ids, 64, 99)[0]
    assert (np.asarray(other) != np.asarray(tokens)).any()
    tokens, weights = np.asarray(tokens), np.asarray(weights)
    assert tokens.shape == (4, 8192) and weights.shape == (4, 4096)
    np.testing.assert_array_equal(tokens[:, :4096], np.asarray(ids))
    np.testing.assert_array_equal(np.asarray(targets), np.asarray(ids))
    assert (tokens[:, :4096] != 99).all()
    masked = tokens[:, 4096:] == 99
    np.testing.assert_array_equal(masked, weights > 0)
    np.testing.assert_array_equal(tokens[:, 4096:][~masked],
                                  np.asarray(ids)[~masked])
    by_block = weights.reshape(4, 64, 64)
    most = by_block.max(-1)
    some = most > 0         # (at a small t a block may have nothing masked)
    assert some.mean() > 0.9
    t = 1.0 / most[some]                        # one t a block and sequence
    assert ((by_block[some] == 0)
            | np.isclose(by_block[some], 1 / t[:, None])).all()
    assert (t >= 1e-3).all() and (t <= 1.0).all()
    share = masked.reshape(4, 64, 64).mean(-1)[some]
    # 64 draws a block: a share within 4.5 deviations of its t
    assert (np.abs(share - t) <= 4.5 * np.sqrt(t * (1 - t) / 64) + 1e-6).all()
    assert 0.4 < masked.mean() < 0.6            # E[t] is a half
    with pytest.raises(ValueError, match="no whole blocks"):
        noise_blocks(jax.random.key(1), ids[:, :100], 64, 99)


# --------------------------------------------------------------- the model

SIZE, LENGTH, VOCAB = 4, 64, 64

CONFIG = {      # what chipbench/reference/sdar.py reads
    "rms_norm_eps": 1e-6, "rope_theta": 1000000, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "experts_held_first": 2, "block_length": SIZE}


def _model(use_flash, **changes) -> GPTConfig:
    return GPTConfig(**{**dict(
        vocab_size=VOCAB, n_layers=4, layer_pattern="*E*E", d_model=32,
        n_heads=4, n_kv_heads=2, head_dim=16, head_norm=True,
        rotary_base=1e6, n_experts=8, experts_per_token=2, moe_expert_ff=16,
        moe_renormalise=True, experts_held=(2, 4), tie_embeddings=False,
        dtype=jnp.float32, diffusion_block=SIZE, use_flash=use_flash,
        norm_eps=1e-6), **changes})


@pytest.fixture(scope="module")
def case():
    ids = jax.random.randint(jax.random.key(0), (2, LENGTH), 0, VOCAB - 1)
    tokens, targets, weights = noise_blocks(jax.random.key(1), ids, SIZE,
                                            VOCAB - 1)
    batch = {"tokens": tokens, "targets": targets, "weights": weights}
    params = jax.jit(GPT(_model(False)).init)(
        jax.random.key(2), tokens)["params"]
    # an embedding of unit deviation, as the cell's: the rows differ
    params = {**params, "embedding": params["embedding"] * 50.0}
    return params, batch


def _program(cfg, params, batch):
    """``(logits of the noised half, loss, gradients, experts chosen)``."""
    return jax.jit(lambda params, batch: _traced(cfg, params, batch))(
        params, batch)


def _traced(cfg, params, batch):
    def loss(params):
        hidden, sown = GPT(cfg).apply(
            {"params": params}, batch["tokens"], return_hidden=True,
            mutable=["intermediates"])
        experts = [kinds["moe"]["experts"][0] for _, kinds in sorted(
            sown["intermediates"].items()) if "moe" in kinds]
        return softmax_cross_entropy_fused(
            hidden, params["lm_head"], batch["targets"], chunk=48,
            weights=batch["weights"]), experts

    (value, experts), grads = jax.value_and_grad(loss, has_aux=True)(params)
    logits = GPT(cfg).apply({"params": params}, batch["tokens"])
    return logits, value, grads, experts


def _forward(cfg, params, batch):
    """``(logits of the noised half, loss)``: what tells a wrong program."""
    @jax.jit
    def forward(params, batch):
        logits = GPT(cfg).apply({"params": params}, batch["tokens"])
        each = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, batch["targets"][..., None], -1)[..., 0]
        return logits, jnp.sum(each * batch["weights"]) / each.size

    return forward(params, batch)


def _distances(got, want):
    far = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                             / jnp.linalg.norm(b))
    logits, value, *grads = got
    want_logits, want_value, want_grads = want
    found = {"logits": far(logits, want_logits),
             "loss": abs(float(value) - want_value) / abs(want_value)}
    if grads:
        found["grads"] = max(far(a, b) for a, b in zip(
            jax.tree.leaves(grads[0]), jax.tree.leaves(want_grads)))
    return found


# Float32 on the CPU against the float32 reference: what is left is the
# order of float32 sums (the flash path's online softmax and its merge of a
# noised row's two parts, the chunked loss), read at 2e-6 or under on every
# measure and path (logits 1.6e-7, loss 1.1e-7, the worst leaf's gradient
# 6.5e-7). Thirty times that and more; the wrong programs are told by the
# forward pass alone, each over ten times a tolerance: a bf16 program reads
# 4.1e-3 on the logits, a causal mask inside a block 7.4e-2, the noised
# copy turned at L + i 0.17; weights without 1 / t 0.52 on the loss and
# targets shifted by one 1.9e-2 (their logits are the sound program's).
TOLERANCE = {"logits": 5e-5, "loss": 2e-5, "grads": 2e-4}


@pytest.fixture(scope="module")
def want(case):
    """The reference's logits, loss, gradients and own choice of experts,
    made once for every test of this file."""
    from chipbench.reference import sdar as reference

    params, batch = case
    (value, routing), grads = reference.loss_and_grad(params, batch, CONFIG)
    return (reference.logits(params, batch["tokens"], CONFIG), float(value),
            grads), [np.asarray(r["own"]) for r in routing]


@pytest.mark.parametrize("use_flash", [False, True], ids=["einsum", "flash"])
def test_gpt_against_the_plain_reference(use_flash, case, want):
    """Logits of the noised half, loss and gradients, by both paths; top-k
    is discontinuous, and on the CPU in float32 the program's choice of
    experts is the reference's own."""
    params, batch = case
    want, chosen = want
    got = _program(_model(use_flash), params, batch)
    assert got[0].shape == (2, LENGTH, VOCAB)
    for mine, theirs in zip(got[3], chosen, strict=True):
        np.testing.assert_array_equal(
            np.sort(np.asarray(mine).reshape(theirs.shape), -1),
            np.sort(theirs, -1))
    found = _distances(got[:3], want)
    for measure, far in found.items():
        assert far <= TOLERANCE[measure], (measure, found)


def _wrong_mask(cfg, q, k, v, core):
    """``_attend_blocks`` with a causal mask inside a block: a clean row
    sees its own block up to itself alone."""
    rows, half = q.shape[-3], q.shape[-3] // 2
    k, v = transformer._repeat_kv(k, v, q.shape[-2] // k.shape[-2])
    scores = jnp.einsum("...qhd,...khd->...hqk", q, k) / np.sqrt(q.shape[-1])
    at = jnp.arange(rows)
    noised, pos = at >= half, at % half
    block = pos // cfg.diffusion_block
    clean_key = ~noised[None, :]
    seen = jnp.where(
        noised[:, None],
        (clean_key & (block[None, :] < block[:, None]))
        | (~clean_key & (block[None, :] == block[:, None])),
        clean_key & (pos[None, :] <= pos[:, None]))
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("...hqk,...khd->...qhd", probs, v)


@pytest.mark.parametrize("wrong", [
    "bf16", "causal-inside-a-block", "noised-half-turned-at-L-plus-i",
    "no-1-over-t", "targets-shifted-by-one"])
def test_each_wrong_program_fails_a_tolerance(wrong, case, want,
                                              monkeypatch):
    """What the tolerances are for (``benchmarks/mellum_wrong_programs.py``
    is the model): a program in bf16, a causal mask inside a block, the
    noised copy turned at ``L + i``, weights without ``1 / t`` and targets
    shifted by one each read over at least one of them."""
    params, batch = case
    cfg = _model(False)
    if wrong == "bf16":
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    elif wrong == "causal-inside-a-block":
        monkeypatch.setattr(transformer, "_attend_blocks", _wrong_mask)
    elif wrong == "noised-half-turned-at-L-plus-i":
        tile = jnp.tile
        monkeypatch.setattr(transformer.jnp, "tile", lambda a, n: jnp.arange(
            2 * a.shape[0]) if n == 2 and a.ndim == 1 else tile(a, n))
    elif wrong == "no-1-over-t":
        batch = {**batch, "weights": (batch["weights"] > 0).astype(
            jnp.float32)}
    else:
        batch = {**batch, "targets": jnp.roll(batch["targets"], -1, axis=1)}
    found = _distances(_forward(cfg, params, batch), want[0])
    over = {m: far for m, far in found.items() if far > 10 * TOLERANCE[m]}
    assert over, (wrong, found)


@pytest.mark.parametrize("use_flash", [False, True], ids=["einsum", "flash"])
def test_no_row_is_handed_its_answer(use_flash, case):
    """Changing clean token ``i`` changes no noised-row logit of block
    ``blk(i)`` or earlier (and does change a later one); changing noised
    token ``i`` changes no logit outside its own block's noised rows."""
    params, batch = case
    cfg = _model(use_flash, n_experts=0, layer_pattern=None, n_layers=2,
                 experts_held=None, d_ff=32)
    tokens = batch["tokens"]
    params = jax.jit(GPT(cfg).init)(jax.random.key(5), tokens)["params"]
    params = {**params, "embedding": params["embedding"] * 50.0}
    logits = jax.jit(lambda t: GPT(cfg).apply({"params": params}, t))
    base = np.asarray(logits(tokens))
    at = 41                                     # block 10: rows 40 .. 43
    first, last = at // SIZE * SIZE, at // SIZE * SIZE + SIZE
    other = lambda t, i: t.at[:, i].set((t[:, i] + 7) % (VOCAB - 1))
    moved = np.abs(np.asarray(logits(other(tokens, at))) - base).max(-1)
    assert (moved[:, :last] == 0).all(), moved[:, :last].max()
    assert (moved[:, last:] > 1e-4).any()
    moved = np.abs(np.asarray(logits(other(tokens, LENGTH + at)))
                   - base).max(-1)
    assert (moved[:, :first] == 0).all() and (moved[:, last:] == 0).all()
    assert (moved[:, first:last] > 1e-4).all()


@pytest.mark.parametrize("changes, match", [
    (dict(layer_pattern="WEWE", attn_window=16),
     r"'W' \(attention inside a window\)"),
    (dict(ring_mesh=object()), "beside ring_mesh"),
    (dict(layer_pattern="*EME"), r"'M' \(Mamba-2\)"),
    (dict(layer_pattern="*ESE"), r"'S' \(attention over chosen keys\)"),
])
def test_what_means_nothing_beside_a_diffusion_block_is_refused(changes,
                                                                match):
    cfg = _model(False, **changes)
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(lambda t: GPT(cfg).init(jax.random.key(0), t),
                       jnp.zeros((1, 2 * LENGTH), jnp.int32))


def test_the_rows_are_two_whole_copies():
    with pytest.raises(ValueError, match="a clean copy and then a noised"):
        jax.eval_shape(lambda t: GPT(_model(False)).init(
            jax.random.key(0), t), jnp.zeros((1, 2 * LENGTH + 2), jnp.int32))


def test_the_layer_counter_carries_the_block_length(case):
    from horovod_tpu import metrics

    def count(blocks):
        m = metrics.registry().get("hvt_attn_layers_traced_total")
        return m.labels(heads="4", kv_heads="2", head_dim="16", core="einsum",
                        window="0", rotary="plain",
                        blocks=str(blocks), differential="0",
                        shared="0").value if m else 0.0

    params, batch = case
    before = [count(0), count(SIZE)]
    jax.eval_shape(lambda t: GPT(_model(False)).apply({"params": params}, t),
                   batch["tokens"])
    assert [count(0), count(SIZE)] == [before[0], before[1] + 2]
