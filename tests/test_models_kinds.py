"""``models/transformer.py``'s table of the kinds of layer (``KINDS``, a
record a letter of ``layer_pattern``): a kind's fields of ``GPTConfig`` are
read inside its record and nowhere else in that file (an ``ast`` walk;
nothing is imported for it), so the models that hold no layer of a kind
are as they were whatever its fields say, which one test shows of every
kind beside every other small model of ``tests/``; and the pattern error
names every letter in the table's words."""

import ast
import dataclasses
import functools
import io
import pathlib
import re
import tokenize

import jax
import jax.numpy as jnp
import pytest

import small_models as others
from horovod_tpu.models import GPT, GPTConfig
from horovod_tpu.models.transformer import KINDS

SOURCE = (pathlib.Path(__file__).resolve().parent.parent / "horovod_tpu"
          / "models" / "transformer.py")

# A kind's own fields of ``GPTConfig``, by the subtree its layers' leaves
# are in: ``field: (default, a value no model of this file has)``. What is
# not here (sizes, norms, the rotary, ``use_flash``) several kinds read, or
# a model names it that has no such layer (``mlp_act``, ``d_ff``).
# ``n_experts`` is the experts' and has no other value here: it is what
# ``Block`` asks to choose between "E" and "-".
OWN = {
    "attn": {"attn_window": (0, 4), "attn_window_rotary": (True, False)},
    "cross": {},
    "mamba": {"mamba_expand": (2, 3), "mamba_state": (16, 4),
              "mamba_conv": (4, 3), "mamba_rank": (0, 2)},
    "gmu": {},
    "ssm": {"ssm_heads": (0, 4), "ssm_head_dim": (64, 8),
            "ssm_groups": (1, 2), "ssm_state": (128, 16), "ssm_conv": (4, 3),
            "ssm_heads_held": (None, (0, 2))},
    "gdn": {"gdn_key_heads": (0, 2), "gdn_value_heads": (0, 4),
            "gdn_key_dim": (128, 8), "gdn_value_dim": (128, 8),
            "gdn_conv": (4, 3)},
    "kda": {"kda_heads": (0, 4), "kda_head_dim": (128, 8),
            "kda_conv": (4, 3), "kda_gate_rank": (128, 8)},
    "sconv": {"sconv_taps": (3, 5)},
    "mla": {"mla_kv_rank": (0, 16), "mla_nope_dim": (128, 8),
            "mla_rope_dim": (64, 4), "mla_value_dim": (128, 8)},
    "dsa": {"dsa_index_heads": (0, 2), "dsa_index_dim": (64, 8),
            "dsa_topk": (2048, 4)},
    "moe": {"n_experts": (0, 0), "experts_per_token": (0, 2),
            "experts_held": (None, (0, 1)),
            "moe_score": ("softmax", "sigmoid"),
            "moe_route_scale": (1.0, 2.0),
            "moe_expert_act": ("swiglu", "relu2"),
            "moe_latent": (0, 8), "moe_shared_ff": (0, 8),
            "moe_renormalise": (None, True), "moe_shared_gate": (False, True),
            "moe_expert_ff": (None, 12)},
    "mlp": {},
}
# the prefixes that make a field a kind's own
_PREFIX = {"attn": ("attn_window",), "mlp": (),
           "moe": ("moe_", "n_experts", "experts_")}
# what ``Block`` reads outside every record, to choose between two of them
_BLOCK_ASKS = [("Block", "n_experts")]
# a scope that only a layer of the letter puts into a program, where it is
# not ``/<subtree>_...``
_SCOPE = {"W": "/attn_window/", "-": "/dense_mlp/", "X": "/cross/"}


def _family(field):
    """The subtree whose kind the field belongs to, or None."""
    for subtree in OWN:
        if field.startswith(_PREFIX.get(subtree, (subtree + "_",))):
            return subtree
    return None


def test_every_kind_and_every_field_of_a_kind_is_in_this_files_table():
    assert {kind.subtree for kind in KINDS.values()} == set(OWN)
    fields = {f.name: f.default for f in dataclasses.fields(GPTConfig)}
    for subtree, own in OWN.items():
        assert {name: default for name, (default, _) in own.items()} == {
            name: default for name, default in fields.items()
            if _family(name) == subtree}, subtree


# ---- (i) a kind's fields are read inside its record


def _top(source):
    """``name: statement`` of the module's functions, classes and
    assignments."""
    top = {}
    for node in ast.parse(source).body:
        for target in ([node] if isinstance(
                node, (ast.FunctionDef, ast.ClassDef)) else
                getattr(node, "targets", [])):
            top[getattr(target, "name", getattr(target, "id", None))] = node
    return top


def _reads_outside_their_records(source):
    """``line: field in name`` of every read of a kind's own field
    (``<anything>.<field>``) in ``source`` that is neither inside a
    ``Kind(...)`` of that subtree in ``KINDS`` nor inside a module-level
    function or class that only that subtree's records reach (by name, and
    through what they name in turn), less what ``Block`` asks."""
    top = _top(source)
    records = [] if "KINDS" not in top else [
        call for call in ast.walk(top["KINDS"]) if isinstance(call, ast.Call)
        and getattr(call.func, "id", None) == "Kind"]

    def named(node):
        return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)
                and n.id in top and n.id not in ("Kind", "KINDS")}

    reached = {}                    # module-level name -> subtrees reaching it
    for record in records:
        subtree = record.args[1].value
        seen, todo = set(), named(record)
        while todo:
            name = todo.pop()
            if name not in seen:
                seen.add(name)
                todo |= named(top[name])
        for name in seen:
            reached.setdefault(name, set()).add(subtree)
    inside = {id(node): record.args[1].value for record in records
              for node in ast.walk(record)}
    found = []
    for name, statement in top.items():
        for node in ast.walk(statement):
            family = isinstance(node, ast.Attribute) and _family(node.attr)
            if family and inside.get(id(node)) != family and reached.get(
                    name) != {family} and (name, node.attr) not in _BLOCK_ASKS:
                found.append(f"{node.lineno}: {node.attr} in {name}")
    return found


def test_a_kinds_fields_are_read_inside_its_record():
    assert _reads_outside_their_records(SOURCE.read_text()) == []


_TABLE = '''
def _helper(cfg):
    return cfg.norm_eps{helper}
def _ssm(cfg):
    return Mixer(cfg.ssm_heads, _helper(cfg))
KINDS = {{kind.letter: kind for kind in (
    Kind("M", "ssm", "Mamba-2", _ssm, None),
    Kind("C", "sconv", "convolution",
         lambda cfg: _helper(cfg) + cfg.sconv_taps{record}, None))}}
class MixerBlock:
    def __call__(self, x):
        return KINDS[self.kind].build(self.cfg){block}
'''


@pytest.mark.parametrize("places, found", [
    ({}, []),
    ({"block": " + self.cfg.ssm_heads"}, ["12: ssm_heads in MixerBlock"]),
    ({"record": " + cfg.ssm_state"}, ["9: ssm_state in KINDS"]),
    ({"helper": " + cfg.sconv_taps"}, ["3: sconv_taps in _helper"]),
], ids=["sound", "in-a-block", "in-another-kinds-record", "in-what-two-share"])
def test_walk_finds_a_read_outside_its_record(places, found):
    source = _TABLE.format(**{"helper": "", "record": "", "block": "",
                              **places})
    assert _reads_outside_their_records(source) == found


def test_no_letter_is_named_outside_the_table():
    """Outside ``GPTConfig`` (whose comments are the fields'), the table
    and the functions its records name, no string and no comment of the
    file names a pattern letter, but ``Block`` the three it asks for: "*",
    "E" and "-"."""
    text = SOURCE.read_text()
    top = _top(text)
    records = {n.id for n in ast.walk(top["KINDS"]) if isinstance(n, ast.Name)
               and isinstance(top.get(n.id), ast.FunctionDef)}
    lines = lambda name: range(top[name].lineno, top[name].end_lineno + 1)
    table = {line for name in {"GPTConfig", "Kind", "KINDS"} | records
             for line in lines(name)}
    found = []
    for token in tokenize.generate_tokens(io.StringIO(text).readline):
        if token.type in (tokenize.STRING, tokenize.COMMENT) and not (
                set(range(token.start[0], token.end[0] + 1)) & table):
            said = set(re.findall(r"""['"`](.)['"`]""", token.string))
            said &= set(KINDS)
            if token.start[0] in lines("Block"):
                said -= set("*E-")
            found += [f"{token.start[0]}: {letter!r}"
                      for letter in sorted(said)]
    assert found == []


# ---- (ii) the models that hold no layer of a kind are as they were


def _dense_config(remat):
    return GPTConfig(vocab_size=64, n_layers=2, d_model=32, n_heads=4,
                     d_ff=128, max_seq_len=8, dtype=jnp.bfloat16,
                     remat=remat, use_flash="auto")


_OTHERS = {"dense": _dense_config, "olmoe": others.sparse_config,
           "nemotron_h": others.hybrid_config,
           "qwen3_next": others.qwen_config}


def _letters(cfg):
    return set(cfg.layer_pattern or ("*E" if cfg.n_experts else "*-"))


def _naming(cfg, letters):
    """``cfg`` with every field the letters' kinds own at another value."""
    return dataclasses.replace(cfg, **{
        field: value for letter in letters
        for field, (_, value) in OWN[KINDS[letter].subtree].items()})


@functools.cache
def _tree(cfg):
    """The shapes of the model's variables, nothing initialised."""
    return jax.tree.map(jnp.shape, jax.eval_shape(
        GPT(cfg).init, jax.random.key(0), jnp.zeros((2, 8), jnp.int32)))


@pytest.mark.parametrize("letter, other", [
    (letter, name) for letter, kind in KINDS.items() if OWN[kind.subtree]
    for name, config in _OTHERS.items()
    if letter not in _letters(config(True))])
def test_other_models_are_as_they_were(letter, other):
    """No other configuration's pattern holds the letter, its tree no leaf
    of the kind's subtree (and no ``post_norm``), and the kind's fields are
    at their defaults; and the fields belong to that kind alone: naming
    them gives the same tree as naming none. (The lowered steps are the
    next test's, a model at a time.)"""
    kind, cfg = KINDS[letter], _OTHERS[other](True)
    assert letter not in _letters(cfg)
    own = OWN[kind.subtree]
    assert {field: getattr(cfg, field) for field in own} == {
        field: default for field, (default, _) in own.items()}
    assert (cfg.post_norm, cfg.embed_scale) == (False, 1.0)
    leaves = [jax.tree_util.keystr(path) for path, _
              in jax.tree_util.tree_leaves_with_path(_tree(cfg))]
    assert not [leaf for leaf in leaves if "post_norm" in leaf
                or (letter != "W" and f"['{kind.subtree}']" in leaf)]
    assert _tree(_naming(cfg, [letter])) == _tree(cfg)


def _loss(other, cfg):
    """The loss of ``GPT(cfg)`` by its parameters, and their shapes: the
    model's own of ``small_models``, nothing initialised."""
    if other == "dense":
        tokens = jnp.zeros((2, 8), jnp.int32)
        return (lambda p: GPT(cfg).apply({"params": p}, tokens).astype(
            jnp.float32).sum()), jax.eval_shape(
                GPT(cfg).init, jax.random.key(0), tokens)["params"]
    make, loss = {"olmoe": (others.sparse_model, others.sparse_loss),
                  "nemotron_h": (others.hybrid_model, others.hybrid_loss),
                  "qwen3_next": (others.qwen_model, others.qwen_loss)}[other]
    params, *given = jax.eval_shape(lambda: make(remat=True)[1:])
    given = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), given)
    return (lambda p: loss(GPT(cfg), p, *given)), params


@pytest.mark.parametrize("other", list(_OTHERS))
def test_other_models_steps_are_as_they_were(other):
    """The lowered step of a model that holds no layer of some kinds has
    no scope of theirs (and no ``post_norm``), and is the same step,
    instruction for instruction, with every field of every such kind named
    at once; the experts' own width defaults to ``d_ff``: naming ``d_ff``
    as that width gives a model with experts the same tree and step."""
    cfg = _OTHERS[other](True)
    foreign = set(KINDS) - _letters(cfg)
    named = _naming(cfg, foreign)
    if "E" not in foreign:
        named = dataclasses.replace(named, moe_expert_ff=cfg.d_ff)
    assert _tree(named) == _tree(cfg)
    lowered = lambda c: (lambda loss, params: jax.jit(jax.grad(loss)).lower(
        params))(*_loss(other, c))
    mine = lowered(cfg)
    names = re.findall(r'loc\("([^"]*)"', mine.as_text(debug_info=True))
    assert names
    for scope in ["/post_norm/", "/attn_diff/"] + [
            _SCOPE.get(letter, f"/{KINDS[letter].subtree}_")
            for letter in foreign]:
        assert not [n for n in names if scope in n], scope
    assert lowered(named).as_text() == mine.as_text()


# ---- (iii) the pattern error


@pytest.mark.parametrize("letter", list(KINDS))
def test_pattern_error_names_the_letter(letter):
    cfg = GPTConfig(vocab_size=16, n_layers=1, d_model=8, n_heads=2,
                    layer_pattern="Q", dtype=jnp.float32)
    words = {"*": "attention", "W": "attention inside a window",
             "M": "Mamba-2", "G": "Gated DeltaNet",
             "K": "Kimi Delta Attention", "C": "gated short convolution",
             "L": "latent attention", "S": "attention over chosen keys",
             "E": "experts", "-": "MLP", "A": "Mamba-1",
             "U": "gated memory unit",
             "X": "attention over an earlier layer's keys and values"}[letter]
    with pytest.raises(ValueError, match=(
            "layer_pattern holds 'Q': a layer is one of .*"
            + re.escape(f"{letter!r} ({words})"))):
        jax.eval_shape(GPT(cfg).init, jax.random.key(0),
                       jnp.zeros((1, 4), jnp.int32))
