"""Mellum 2's layers in small (grouped-query attention inside a window and
turned by the plain rotary three to one with attention over every causal
key turned by a YaRN-scaled one, then a softmax top-8 router renormalised
over the chosen over a share of SwiGLU experts, an untied head) through
``models.GPT`` against ``chipbench/reference/mellum.py``, which shares no
code with the package: loss and gradients, each kind of mixer and which
law turns it, the YaRN table against the equations and the kernel under
it, the four shares of an expert layer against the whole, a share in two,
three and four rounds against the uncut layer restricted to it, the
scopes and the counter's label, and what is refused by name. The einsum
path except where the Pallas interpreter is the point."""

import ast
import math
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import mellum as reference
from horovod_tpu.models import GPT, GPTConfig
from horovod_tpu.models.moe import MoEMlp
from horovod_tpu.ops import rotary as rotary_op

WINDOWED, FULL = "sliding_attention", "full_attention"
_WINDOW = 6
# the published entry, and a small one whose three regimes fall inside a
# head of 16: low 1, high 6
_PUBLISHED = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
              "original_max_position_embeddings": 8192, "beta_fast": 32,
              "beta_slow": 1, "attention_factor": 1.2772588722239782}
_SMALL_YARN = {**_PUBLISHED, "rope_theta": 1000,
               "original_max_position_embeddings": 512}
_MELLUM = {"rms_norm_eps": 1e-6, "sliding_window": _WINDOW,
           "num_experts_per_tok": 8, "norm_topk_prob": True,
           "experts_held_first": 16, "layer_types": [WINDOWED, FULL],
           "rope_parameters": {
               WINDOWED: {"rope_type": "default", "rope_theta": 1000},
               FULL: _SMALL_YARN}}


def _config(pattern="WE*E", **changes) -> GPTConfig:
    """A share of a small Mellum: 4 query heads on 2 key-value heads of
    16, a window of 6, experts 16 to 31 of 64 with 8 a token."""
    return GPTConfig(**{**dict(
        vocab_size=64, n_layers=len(pattern), layer_pattern=pattern,
        d_model=32, n_heads=4, n_kv_heads=2, head_dim=16, rotary=True,
        rotary_base=1000.0, rotary_scaling=rotary_op.law(_SMALL_YARN),
        attn_window=_WINDOW, moe_expert_ff=12, dtype=jnp.float32,
        use_flash=False, tie_embeddings=False, norm_eps=1e-6, n_experts=64,
        experts_per_token=8, moe_score="softmax", moe_renormalise=True,
        experts_held=(16, 16)), **changes})


_TOKENS = jax.random.randint(jax.random.key(1), (2, 20), 0, 64)


def _params(pattern="WE*E"):
    @jax.jit
    def init(key):
        params = GPT(_config(pattern)).init(key, _TOKENS)["params"]
        # at their 0.02 the experts and the router barely move the loss
        return jax.tree_util.tree_map_with_path(
            lambda path, w: w * 10.0 if "moe" in jax.tree_util.keystr(path)
            else w, params)

    return init(jax.random.key(0))


def _loss(model, params, sow=False):
    import optax

    logits, sown = model.apply({"params": params}, _TOKENS,
                               mutable=["intermediates"])
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], _TOKENS[:, 1:]).mean()
    return (loss, sown["intermediates"]) if sow else loss


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def test_mellum_gpt_matches_reference():
    """Both kinds of attention each under its own law, the experts a
    chip's share in rounds: the tree, the loss and the gradient of every
    leaf against the reference given the program's choice of experts,
    under remat."""
    params = _params()
    kinds = [set(params[f"block_{i}"]) - {"norm"} for i in range(4)]
    assert kinds == [{"attn"}, {"moe"}, {"attn"}, {"moe"}]
    assert {k: v["kernel"].shape
            for k, v in params["block_0"]["attn"].items()} == {
        "q": (32, 4, 16), "k": (32, 2, 16), "v": (32, 2, 16),
        "o": (4, 16, 32)}
    assert params["block_1"]["moe"]["router"].shape == (32, 64)
    assert params["block_1"]["moe"]["up"].shape == (16, 32, 12)
    model = GPT(_config(remat=True))
    (got, sown), grads = jax.jit(jax.value_and_grad(
        lambda p: _loss(model, p, sow=True), has_aux=True))(params)
    chosen = [sown[f"block_{i}"]["moe"]["experts"][0] for i in (1, 3)]
    (want, routing), want_grads = reference.loss_and_grad(
        params, _TOKENS, _MELLUM, chosen)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for mine, theirs in zip(chosen, routing, strict=True):
        np.testing.assert_array_equal(np.sort(np.asarray(mine), -1),
                                      np.sort(np.asarray(theirs["own"]), -1))
    for (path, g), (_, w) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(want_grads), strict=True):
        assert _rel(g, w) <= 5e-5, jax.tree_util.keystr(path)


@pytest.mark.parametrize("block, kind", [(0, WINDOWED), (2, FULL)])
def test_each_kind_of_mixer_turns_by_its_own_law(block, kind):
    """A mixer's sown input and output: the windowed one sees 6 keys and
    turns plainly, the full one sees all and turns by the scaled law with
    the factor on its tables; each is the reference's of its kind and
    neither the other kind's nor its own kind under the other law."""
    params = _params()
    _, sown = jax.jit(lambda p: _loss(GPT(_config()), p, sow=True))(params)
    mixer = sown[f"block_{block}"]["attn"]
    u, got = mixer["attn_input"][0], mixer["attn_output"][0]
    p = params[f"block_{block}"]["attn"]
    want = reference.mixer(u, p, _MELLUM, kind)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    other = {WINDOWED: FULL, FULL: WINDOWED}[kind]
    assert _rel(got, reference.mixer(u, p, _MELLUM, other)) > 0.05
    swapped = {**_MELLUM, "rope_parameters": {
        kind: _MELLUM["rope_parameters"][other],
        other: _MELLUM["rope_parameters"][kind]}}
    assert _rel(got, reference.mixer(u, p, swapped, kind)) > 0.01


def test_the_windowed_layers_read_nothing_of_the_new_field():
    """``rotary_scaling`` is read in ``models/transformer.py`` inside the
    record of ``*`` and, since PR 66, of ``X`` (whose queries turn by the
    law the keys it reads were turned by) and nowhere else; a model of
    windowed layers alone lowers to the
    same step whatever the field says, and a model of full layers does
    not."""
    source = (pathlib.Path(__file__).resolve().parent.parent / "horovod_tpu"
              / "models" / "transformer.py").read_text()
    tree = ast.parse(source)
    reads = [node for node in ast.walk(tree) if isinstance(
        node, ast.Attribute) and node.attr == "rotary_scaling"]
    records = [call for call in ast.walk(tree) if isinstance(call, ast.Call)
               and getattr(call.func, "id", None) == "Kind"
               and call.args[0].value in "*X"]
    assert len(reads) == 2 and len(records) == 2
    assert all(read in list(ast.walk(record))
               for read, record in zip(reads, records))

    def step(pattern, **changes):
        cfg = _config(pattern, **changes)
        shapes = jax.eval_shape(GPT(cfg).init, jax.random.key(0),
                                _TOKENS)["params"]
        return jax.jit(jax.grad(lambda p: _loss(GPT(cfg), p))).lower(
            shapes).as_text()

    assert step("WE") == step("WE", rotary_scaling=None)
    assert step("*E") != step("*E", rotary_scaling=None)


def test_the_yarn_table_is_the_equations():
    """At the published numbers: ``low`` 18 and ``high`` 35 of the 64
    channels of a half (``c(32)`` = 18.08, ``c(1)`` = 34.98), the channels
    under 18 as published, those from 35 up sixteen times slower, a ramp
    between, and ``cos`` and ``sin`` times 1.2773, so a score is 1.6314
    times the unscaled one; not truncated, the range is the two numbers
    themselves."""
    law = rotary_op.law(_PUBLISHED)
    assert law == rotary_op.Yarn(500000.0, 16.0, 8192, 32.0, 1.0,
                                 1.2772588722239782, True)
    c = lambda n: 128 * math.log(8192 / (2 * math.pi * n)) / (
        2 * math.log(500000))
    assert c(32) == pytest.approx(18.08, abs=0.005)
    assert c(1) == pytest.approx(34.98, abs=0.005)
    assert rotary_op.yarn_range(law, 128) == (18, 35)
    assert rotary_op.yarn_range(law._replace(truncate=False), 128) == (
        pytest.approx(c(32)), pytest.approx(c(1)))
    assert reference.yarn_range(_PUBLISHED, 128) == (18, 35)
    theta, factor = rotary_op.frequencies(law, 64)
    plain, one = rotary_op.frequencies(500000.0, 64)
    assert (one, factor) == (1.0, 1.2772588722239782)
    assert factor == pytest.approx(0.1 * math.log(16) + 1)
    assert factor ** 2 == pytest.approx(1.6314, abs=5e-5)
    # no attention factor given: the paper's, from the factor
    assert rotary_op.frequencies(law._replace(attention_factor=None),
                                 64)[1] == pytest.approx(factor)
    j = np.arange(64)
    np.testing.assert_allclose(plain, 500000.0 ** (-2 * j / 128))
    np.testing.assert_array_equal(theta[:19], plain[:19])
    np.testing.assert_allclose(theta[35:], plain[35:] / 16)
    ramp = (j[19:35] - 18) / 17
    np.testing.assert_allclose(theta[19:35],
                               plain[19:35] * ((1 - ramp) + ramp / 16))
    assert np.all(np.diff(theta / plain) <= 0)
    want, _ = reference.thetas(_PUBLISHED, 128)
    np.testing.assert_allclose(theta.astype(np.float32), np.asarray(want),
                               rtol=1e-7)
    # the tables carry the factor, and the plain law's are what they were
    positions = jnp.arange(8)[None]
    cos, sin = rotary_op._tables(positions, law, 128, 128, jnp.float32)
    np.testing.assert_allclose(cos[0, 0], factor, rtol=1e-6)
    np.testing.assert_allclose(np.hypot(cos, sin), factor, rtol=1e-6)
    cos, _ = rotary_op._tables(positions, 500000.0, 128, 128, jnp.float32)
    assert float(cos[0, 0, 0]) == 1.0
    # the small law of this file's model: all three regimes in a head of 16
    small = rotary_op.law(_SMALL_YARN)
    assert rotary_op.yarn_range(small, 16) == (1, 6)
    ratio = rotary_op.frequencies(small, 8)[0] / rotary_op.frequencies(
        1000.0, 8)[0]
    np.testing.assert_allclose(ratio, [1, 1, 1 - 0.2 * 15 / 16,
                                       1 - 0.4 * 15 / 16, 1 - 0.6 * 15 / 16,
                                       1 - 0.8 * 15 / 16, 1 / 16, 1 / 16])


@pytest.mark.parametrize("dtype, rel", [(jnp.float32, 2e-6),
                                        (jnp.bfloat16, 1.2e-2)],
                         ids=["float32", "bf16"])
def test_the_kernel_under_the_scaled_law(dtype, rel):
    """``rotary_kernels`` (interpreted) against ``rotary_plain`` under the
    published YaRN law on a layer's q and k at heads of 128, forward and
    backward (the same kernel with ``sin`` negated: a scaled rotation's
    transpose is the scaled rotation by the negated angle); and both
    against the reference's turn written out."""
    law = rotary_op.law(_PUBLISHED)
    keys = jax.random.split(jax.random.key(3), 4)
    q, dq = (jax.random.normal(k, (1, 32, 4, 128), dtype) for k in keys[:2])
    k, dk = (jax.random.normal(k, (1, 32, 1, 128), dtype) for k in keys[2:])
    positions = jnp.arange(100, 132)[None]

    def with_gradients(turn):
        out, pull = jax.vjp(turn, q, k)
        return (*out, *pull((dq, dk)))

    got = with_gradients(lambda q, k: rotary_op.rotary_kernels(
        (q, k), positions, law))
    want = with_gradients(lambda q, k: tuple(
        rotary_op.rotary_plain(x, positions, law) for x in (q, k)))
    for name, x, same in zip(("q", "k", "dq", "dk"), got, want):
        assert x.shape == same.shape and x.dtype == same.dtype, name
        assert _rel(x.astype(jnp.float32), same.astype(jnp.float32)) <= rel, (
            name)
    if dtype == jnp.float32:
        # (the reference counts positions from 0: turn 132 and cut)
        long = jnp.pad(q[0], ((100, 0), (0, 0), (0, 0)))
        np.testing.assert_allclose(
            got[0][0], reference.rotary_halves(long, _PUBLISHED)[100:],
            rtol=1e-4, atol=1e-4)
        # the gradient is the turn by the negated angle, with the factor
        back = rotary_op.rotary_plain(dq, -positions, law)
        assert _rel(got[2], back) <= rel


def _expert_layer(held, **options):
    return MoEMlp(64, 12, 8, dtype=jnp.float32, score="softmax",
                  renormalise=True, held=held, **options)


def test_the_four_shares_of_an_expert_layer_add_up_to_the_whole():
    """The deployment in small: 64 experts divided 4 ways, 8 a token.
    Every share (``held = (0, 16)`` to ``(48, 16)``) routes over all 64
    and renormalises over all 8 a token chose; the shares' parts sum to
    the uncut reference's layer, and every assignment is some share's."""
    d, tokens = 32, 48
    h = jax.random.normal(jax.random.key(1), (1, tokens, d))
    whole = jax.jit(_expert_layer(None).init)(jax.random.key(0), h)
    params = jax.tree.map(lambda w: w * 10.0, whole["params"])
    config = {**_MELLUM, "experts_held_first": 0}
    want, routing = reference.experts(h[0], params, config)
    total, rows = jnp.zeros_like(want), 0
    for first in range(0, 64, 16):
        mine = {name: w[first:first + 16] if name != "router" else w
                for name, w in params.items()}
        (out, _), sown = jax.jit(lambda p, layer=_expert_layer(
            (first, 16)): layer.apply({"params": p}, h,
                                      mutable=["intermediates"]))(mine)
        total = total + out[0]
        np.testing.assert_array_equal(
            np.sort(np.asarray(sown["intermediates"]["experts"][0]), -1),
            np.sort(np.asarray(routing["own"]), -1))
        rows += int(jnp.sum((routing["own"] >= first)
                            & (routing["own"] < first + 16)))
    assert rows == tokens * 8           # every assignment is some share's
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-5, atol=3e-5)


@pytest.mark.parametrize("favoured, shunned, rounds", [
    (2, 14, 1), (3, 13, 1), (2, 8, 1), (3, 9, 2), (4, 12, 2), (8, 8, 3)])
def test_a_share_in_several_rounds_is_the_uncut_layer_restricted_to_it(
        favoured, shunned, rounds):
    """The routing forced: every token chooses the first ``favoured`` held
    experts and none of the last ``shunned`` (one feature of the input is
    constant and the router's row for it large), so a share of 16 of 64
    with 8 a token is assigned exactly two, three, four or eight rows a
    token, or with the rest left to the router a part of a row more. A
    round of this share holds three rows a token (``held_rows``: two
    expected, a quarter of room, rounded up), so two and three rows a
    token are one round, which rounds of one row a token ran as two and
    three, a few rows more a second and eight a third. The layer's sum
    and the gradients of its input and of every leaf are the reference's
    over the held experts alone, whatever the rounds."""
    from horovod_tpu.models import moe

    d, tokens, first = 32, 40, 16
    assert moe.held_rows(tokens, 8, (first, 16), 64) == (3, 3 * tokens)
    h = jax.random.normal(jax.random.key(1), (1, tokens, d)).at[..., 0].set(
        1.0)
    layer = _expert_layer((first, 16))
    params = jax.tree.map(lambda w: w * 10.0, jax.jit(layer.init)(
        jax.random.key(0), h)["params"])
    steer = jnp.zeros(64).at[first:first + favoured].set(40.0)
    steer = steer.at[first + 16 - shunned:first + 16].set(-40.0)
    params = {**params, "router": params["router"].at[0].set(steer)}
    cot = jax.random.normal(jax.random.key(2), (tokens, d))

    def mine(p, h):
        (out, _), sown = layer.apply({"params": p}, h,
                                     mutable=["intermediates"])
        return jnp.sum(out[0] * cot), sown["intermediates"]["experts"][0]

    (got, experts), grads = jax.jit(jax.value_and_grad(
        mine, argnums=(0, 1), has_aux=True))(params, h)
    held = int(jnp.sum((experts >= first) & (experts < first + 16)))
    assert -(-held // (3 * tokens)) == rounds
    if favoured + shunned == 16:
        assert held == favoured * tokens
    else:
        assert held % tokens
    config = {**_MELLUM, "experts_held_first": first}
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p, h: jnp.sum(reference.experts_layer(
                h[0], p, config, experts)[0] * cot), argnums=(0, 1)))(
                    params, h)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)
    for (path, g), (_, w) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(want_grads), strict=True):
        assert _rel(g, w) <= 5e-5, jax.tree_util.keystr(path)


@pytest.mark.parametrize("cell, k, count, n_experts, times", [
    ("nemotron3s-s8192", 22, 8, 512, 1), ("qwen3next-s8192", 10, 32, 512, 1),
    ("lfm2moe-s8192", 4, 8, 64, 1), ("kanana2-s8192", 6, 16, 128, 1),
    ("keyevl2-s16384", 8, 8, 128, 1), ("kimilinear-s8192", 8, 8, 256, 1),
    ("trinitymini-s16384", 8, 8, 128, 1), ("mellum2-s16384", 8, 16, 64, 3),
    ("a share of 8 of 64 with 8 a token", 8, 8, 64, 2),
    ("a share that is most of the layer", 8, 8, 16, 5)])
def test_a_rounds_rows_are_a_static_multiple_of_the_tokens(
        cell, k, count, n_experts, times):
    """``held_rows``: a round is ``ceil(1.25 k count / E)`` rows a token,
    ``min(k, count)`` at most: one in the seven held cells the benchmark
    had (their compiled programs and their counters' ``round_rows`` stay),
    three in this one; the rounds at most cover ``min(k, count)`` rows a
    token; and the label says what a round holds."""
    from horovod_tpu import metrics
    from horovod_tpu.models import moe

    tokens = 16384
    rounds, rows = moe.held_rows(tokens, k, (0, count), n_experts)
    assert rows == times * tokens
    assert rounds == -(-min(k, count) // times)
    assert rounds * rows >= min(k, count) * tokens
    # the label, on a small layer of the same share
    small = 16
    layer = MoEMlp(n_experts, 8, k, dtype=jnp.float32, held=(0, count))
    h = jnp.zeros((1, small, 8))

    def counted():
        m = metrics.registry().get("hvt_moe_layers_traced_total")
        return m.labels(experts=str(n_experts), top_k=str(k),
                        product=moe.PRODUCT, held=str(count),
                        round_rows=str(times * small),
                        move_rows=str(moe.move_rows(times * small))
                        ).value if m else 0.0

    before = counted()
    jax.eval_shape(layer.init, jax.random.key(0), h)
    assert counted() == before + 1


def test_a_share_that_is_most_of_the_layer_reads_no_slot_past_its_own():
    """8 of 16 held with 8 a token: a round is 5 rows a token and two
    rounds cover 10 where the slots are 8 a token: the last round's slots
    past them are padding nobody chose, and the sum is the reference's
    with every token sent to all 8 held experts (two full rounds)."""
    from horovod_tpu.models import moe

    d, tokens = 16, 24
    assert moe.held_rows(tokens, 8, (0, 8), 16) == (2, 5 * tokens)
    layer = MoEMlp(16, 12, 8, dtype=jnp.float32, renormalise=True,
                   held=(0, 8))
    h = jax.random.normal(jax.random.key(1), (1, tokens, d)).at[..., 0].set(
        1.0)
    params = jax.tree.map(lambda w: w * 10.0, jax.jit(layer.init)(
        jax.random.key(0), h)["params"])
    params = {**params, "router": params["router"].at[0].set(
        jnp.zeros(16).at[:8].set(40.0))}
    (out, _), sown = jax.jit(lambda p: layer.apply(
        {"params": p}, h, mutable=["intermediates"]))(params)
    experts = sown["intermediates"]["experts"][0]
    assert int(jnp.sum(experts < 8)) == 8 * tokens
    want, _ = reference.experts(h[0], params, {
        "num_experts_per_tok": 8, "norm_topk_prob": True}, experts)
    np.testing.assert_allclose(out[0], want, rtol=2e-5, atol=2e-5)


def test_the_step_names_attn_rope_in_both_kinds_and_the_counter_the_law():
    """``attn_rope`` is in the windowed layers and in the full ones,
    forward, backward and recomputed, and
    ``hvt_attn_layers_traced_total`` counts a layer by the law that turns
    it: ``plain`` in a window, ``yarn`` over every key, ``none`` where
    nothing turns."""
    from horovod_tpu import metrics

    def counted(window, rotary):
        m = metrics.registry().get("hvt_attn_layers_traced_total")
        return m.labels(heads="4", kv_heads="2", head_dim="16",
                        core="einsum", window=str(window),
                        rotary=rotary, blocks="0", differential="0",
                        shared="0").value if m else 0.0

    labels = [(_WINDOW, "plain"), (0, "yarn"), (0, "none"), (0, "plain")]
    before = [counted(*label) for label in labels]
    model = GPT(_config(remat=True))
    shapes = jax.eval_shape(model.init, jax.random.key(0), _TOKENS)["params"]
    names = set(re.findall(r'loc\("([^"]*)"', jax.jit(jax.grad(
        lambda p: _loss(model, p))).lower(shapes).as_text(debug_info=True)))
    after = [counted(*label) for label in labels]
    assert after[0] > before[0] and after[1] > before[1]
    assert after[0] - before[0] == after[1] - before[1]
    assert after[2:] == before[2:]
    for block in (0, 2):
        rope = [n for n in names if f"/block_{block}/attn/attn_rope/" in n]
        assert rope and [n for n in rope if "transpose" in n]
        assert [n for n in rope if "rematted_computation" in n]
    # the same model with nothing turned in its full layers, and with the
    # plain law there
    jax.eval_shape(GPT(_config(rotary=False)).init, jax.random.key(0),
                   _TOKENS)
    jax.eval_shape(GPT(_config(rotary_scaling=None)).init,
                   jax.random.key(0), _TOKENS)
    assert counted(0, "none") > before[2] and counted(0, "plain") > before[3]
    text = metrics.prometheus_text()
    for law in ("plain", "yarn", "none"):
        assert re.search(
            r'hvt_attn_layers_traced_total\{[^}]*rotary="%s"[^}]*\}' % law,
            text)


@pytest.mark.parametrize("changes, match", [
    ({"rope_type": "llama3"}, "rope_type 'llama3' is not built"),
    ({"rope_type": "linear"}, "rope_type 'linear' is not built"),
    ({"rope_type": "dynamic"}, "rope_type 'dynamic' is not built"),
    ({"mscale_all_dim": 1.0}, r"mscale_all_dim \(1.0\) is not built"),
    ({"mscale": 0.707}, r"mscale \(0.707\) is not built"),
])
def test_what_is_not_built_is_refused_by_name(changes, match):
    with pytest.raises(ValueError, match=match):
        rotary_op.law({**_PUBLISHED, **changes})


def test_a_default_entry_is_its_base_and_the_reference_is_alone():
    assert rotary_op.law({"rope_type": "default",
                          "rope_theta": 500000}) == 500000.0
    assert rotary_op.law({"rope_theta": 1e4}) == 1e4
    assert (rotary_op.law_name(1e4), rotary_op.law_name(
        rotary_op.law(_PUBLISHED))) == ("plain", "yarn")
    assert "horovod_tpu" not in open(reference.__file__).read().split(
        '"""', 2)[2]
