"""A patterned model whose layers read earlier layers beside the stream
(``models/transformer.py``: a gated memory unit on the nearest Mamba-1
layer's scan output, a cross layer on the nearest full-attention layer's
keys and values), at small sizes on the CPU: the six published layers 14
to 19 of a small Phi-4-mini-flash (``A-W-A-*-U-X-``) against the plain
reference of ``chipbench/reference/phi4flash.py``, loss and the gradient
of every leaf, with and without ``remat``; which layer feeds which derived
from the pattern, each held by a program that reads the wrong thing; a
reader with nothing before it refused by name; the new scopes in a lowered
step, names alone (the step with the scopes patched out is the same); that
a dense, a hybrid and a sparse model's steps hold none of them is
``tests/test_models_kinds.py``'s, which asks it of every kind.

Suite clock (``PERF.md`` section 3's rule): 30 test-seconds, 52 CPU-seconds
(``os.times()`` around the file alone, PR 66)."""

import contextlib
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest

import small_models as others
from chipbench.reference import phi4flash as reference
from horovod_tpu.models import GPT, GPTConfig, mamba, transformer

PATTERN, FIRST, WINDOW = "A-W-A-*-U-X-", 14, 6
CONFIG = {"layer_norm_eps": 1e-5, "sliding_window": WINDOW,
          "first_layer": FIRST, "published": {"num_hidden_layers": 32}}
SCOPES = ("mamba_in_proj", "mamba_conv", "mamba_step", "mamba_scan",
          "mamba_gate", "mamba_out_proj", "gmu_in_proj", "gmu_gate",
          "gmu_out_proj", "attn_diff")


def _config(remat=False, **changes):
    return GPTConfig(**{**dict(
        vocab_size=64, n_layers=len(PATTERN), layer_pattern=PATTERN,
        d_model=32, n_heads=8, n_kv_heads=4, head_dim=8, d_ff=48,
        mlp_act="swiglu", rotary=False, attn_window=WINDOW,
        attn_window_rotary=False, attn_differential=True, attn_bias=True,
        layer_norm=True, norm_eps=1e-5, first_layer=FIRST, mamba_state=4,
        mamba_rank=3, dtype=jnp.float32, remat=remat, use_flash=False),
        **changes})


TOKENS = jax.random.randint(jax.random.key(1), (2, 29), 0, 64)


@functools.cache
def _params():
    """Seeded random leaves by the tree's shapes, every bias away from 0
    and every norm's weight from 1 (no ``init`` is compiled)."""
    return others.random_tree(jax.eval_shape(
        GPT(_config()).init, jax.random.key(0), TOKENS)["params"],
        2, 0.2)


def _loss(cfg):
    def loss(params):
        logits = GPT(cfg).apply({"params": params}, TOKENS)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, TOKENS[:, 1:, None], -1))

    return loss


@functools.cache
def _reference():
    return reference.loss_and_grad(_params(), TOKENS, CONFIG)


@functools.cache
def _mine(remat=False):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(_loss(_config(remat))))(_params())


def _far(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def test_model_is_the_reference_loss_and_every_leafs_gradient(remat=True):
    """Under ``remat``, as the cell runs it: what a later block reads of an
    earlier one crosses the blocks as an output and an input. 1e-4: float32's own rounding over twelve layers. A key's bias has no
    gradient but rounding (the softmax takes a row's common shift out), in
    the program and in the reference."""
    (got, grads), (want, wanted) = _mine(remat), _reference()
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    assert jax.tree.structure(grads) == jax.tree.structure(wanted)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(wanted)):
        name = jax.tree_util.keystr(path)
        if "['k']['bias']" in name:
            assert float(jnp.abs(a).max()) < 1e-6, name
        else:
            assert _far(a, b) < 1e-4, name


def test_the_tree_is_the_six_layers():
    tree = jax.tree.map(jnp.shape, _params())
    mixers = [next(iter(set(tree[f"block_{i}"]) - {"norm"}))
              for i in range(len(PATTERN))]
    assert mixers == ["mamba", "mlp", "attn", "mlp", "mamba", "mlp", "attn",
                      "mlp", "gmu", "mlp", "cross", "mlp"]
    assert set(tree["block_10"]["cross"]) == set(
        tree["block_6"]["attn"]) - {"k", "v"}
    assert tree["block_8"]["gmu"] == {"in_proj": (32, 64),
                                      "out_proj": (64, 32)}
    assert "lm_head" not in tree


@pytest.mark.parametrize("wrong", ["unit_reads_the_gated_output",
                                   "unit_reads_the_first_mamba_layer",
                                   "cross_reads_the_windowed_layers_keys"])
def test_a_program_that_reads_the_wrong_thing_is_told(wrong, monkeypatch):
    """The sound model's loss is the reference's to 1e-5 (above); each of
    these moves it by 3e-4 of it or more."""
    if wrong == "unit_reads_the_gated_output":
        right = mamba.Mamba1Mixer.__call__

        def call(self, x):
            out, memory = right(self, x)
            z = jnp.split(jnp.dot(x, self.variables["params"]["in_proj"]),
                          2, -1)[1]
            return out, memory * jax.nn.silu(z)

        monkeypatch.setattr(mamba.Mamba1Mixer, "__call__", call)
    else:
        sound = transformer._feeds
        other = {"unit_reads_the_first_mamba_layer": (8, 0),
                 "cross_reads_the_windowed_layers_keys": (10, 2)}[wrong]
        monkeypatch.setattr(transformer, "_feeds", lambda pattern: {
            **sound(pattern), other[0]: other[1]})
        if other[1] == 2:   # a windowed layer hands nothing on by itself
            monkeypatch.setitem(transformer.KINDS, "W", dataclasses.replace(
                transformer.KINDS["W"], build=lambda cfg, depth=0,
                hands_on=False: transformer._layer(transformer.Attention(
                    cfg, rotary=False, window=cfg.attn_window, depth=depth,
                    hands_on=hands_on, name="attn"), positional=True)))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(_loss(_config()))(_params())
    want = float(_reference()[0])
    assert abs(float(got) - want) > 3e-4 * want


def test_feeds_are_derived_from_the_pattern():
    assert transformer._feeds(PATTERN) == {8: 4, 10: 6}
    assert transformer._feeds("A-U-A-U-*X*XX") == {2: 0, 6: 4, 9: 8, 11: 10,
                                                   12: 10}
    assert transformer._feeds("*-M-") == {} == transformer._feeds(None)


@pytest.mark.parametrize("pattern, words", [
    ("U-A-", "layer 0 is 'U' (gated memory unit), which reads the scan "
             "output before its gate of the nearest earlier layer that "
             "makes them, and none of 'A' (Mamba-1) comes before it"),
    ("A-W-X-", "layer 4 is 'X' (attention over an earlier layer's keys "
               "and values), which reads the keys and values of the "
               "nearest earlier layer that makes them, and none of '*' "
               "(attention) comes before it"),
])
def test_a_reader_with_nothing_before_it_is_refused_by_name(pattern, words):
    cfg = _config(layer_pattern=pattern, n_layers=len(pattern))
    with pytest.raises(ValueError, match=re.escape(words)):
        jax.eval_shape(GPT(cfg).init, jax.random.key(0), TOKENS)


def _lowered(cfg):
    return jax.jit(jax.grad(_loss(cfg))).lower(
        jax.eval_shape(lambda: _params()))


def _names(lowered) -> set:
    return set(re.findall(r'loc\("([^"]*)"',
                          lowered.as_text(debug_info=True)))


def test_the_scopes_are_in_a_lowered_step_and_are_names_alone(monkeypatch):
    """Every new scope forward, recomputed and backward; the kernels' calls
    stay under ``attn_core`` and the windowed ones under ``attn_window``,
    ``attn_diff`` inside ``attn_core`` and outside ``attn_window``; and the
    step with every scope patched out is the same program."""
    lowered = _lowered(_config(remat=True))
    names = _names(lowered)
    for scope in SCOPES:
        found = [n for n in names if f"/{scope}/" in n]
        assert [n for n in found if "transpose" in n], scope
        # (a layer's last product is not made again: its gradients read
        # its operands alone)
        assert scope.endswith("out_proj") or [
            n for n in found if "rematted_computation" in n], scope
    diff = [n for n in names if "/attn_diff/" in n]
    assert all("/attn_core/attn_diff/" in n for n in diff)
    assert not [n for n in diff if "/attn_window/" in n]
    assert [n for n in names if "/block_2/attn/attn_core/attn_window/" in n]
    assert [n for n in names if "/block_10/cross/attn_core/" in n]
    assert not [n for n in names if "/block_10/cross/attn_proj/k" in n]
    monkeypatch.setattr(jax, "named_scope",
                        contextlib.contextmanager(lambda name: (yield)))
    bare = _lowered(_config(remat=True))
    assert not [n for n in _names(bare)
                if any(f"/{scope}/" in n for scope in SCOPES)]
    assert bare.as_text() == lowered.as_text()
