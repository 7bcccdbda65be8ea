"""``Attention``'s six scopes and its engagement counter
(``models/transformer.py``): a configuration's lowered gradient holds the
scopes of the pieces it makes and no other, forward, backward and in a
recomputed block; a latent-attention and a sparse-attention model hold
none of them; the scopes are names alone, so the lowered program without
locations and the parameter tree are those of a build with the scopes
patched out; and ``hvt_attn_layers_traced_total`` says which path the
rule chose for the products over positions."""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu import metrics
from horovod_tpu.models import GPT, GPTConfig
from horovod_tpu.ops import _pallas

SCOPES = ("attn_proj", "attn_norm", "attn_rope", "attn_core", "attn_gate",
          "attn_out_proj")
_SMALL = dict(vocab_size=64, n_layers=2, d_model=32, n_heads=4, d_ff=64,
              max_seq_len=16, dtype=jnp.float32, remat=True, use_flash=False)
# the pieces the benchmark's seven cells make: (changes, scopes beside
# attn_proj, attn_core and attn_out_proj)
CASES = {
    "nemotron3s": (dict(rotary=False, n_kv_heads=1), ()),
    "gpt2l": (dict(), ("attn_rope",)),
    "olmoe": (dict(qk_norm=True), ("attn_norm", "attn_rope")),
    "lfm2moe": (dict(head_norm=True, n_kv_heads=2, rotary_base=1e6),
                ("attn_norm", "attn_rope")),
    "qwen3next": (dict(head_norm=True, attn_gate=True, n_kv_heads=2,
                       head_dim=16, rotary_fraction=0.25,
                       norm_unit_offset=True),
                  ("attn_norm", "attn_rope", "attn_gate")),
    "gate_alone": (dict(rotary=False, attn_gate=True), ("attn_gate",)),
}
TOKENS = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % 64


def _lowered(cfg, tokens=TOKENS):
    """The gradient of a loss of ``GPT(cfg)``, lowered from shapes."""
    model = GPT(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)

    def loss(p):
        out = model.apply(p, tokens, mutable=["intermediates"])[0]
        logits = out[0] if isinstance(out, tuple) else out
        return (logits.astype(jnp.float32) ** 2).mean()

    return jax.jit(jax.grad(loss)).lower(params), params


def _names(lowered) -> set:
    return set(re.findall(r'loc\("([^"]*)"',
                          lowered.as_text(debug_info=True)))


@pytest.mark.parametrize("case", sorted(CASES))
def test_attention_holds_the_scopes_its_configuration_makes(case):
    changes, made = CASES[case]
    names = _names(_lowered(GPTConfig(**{**_SMALL, **changes}))[0])
    made = ("attn_proj", "attn_core", "attn_out_proj") + made
    for scope in SCOPES:
        found = [n for n in names if f"/{scope}/" in n]
        if scope not in made:
            assert not found, (scope, found[:3])
            continue
        assert [n for n in found if "transpose" in n], scope
        assert [n for n in found if "transpose" not in n], scope
        assert [n for n in found if "rematted_computation" in n], scope
    # every scope sits inside the module's own name, and the products'
    # flax names are under it as they were
    assert [n for n in names if "/attn/attn_proj/q/" in n]
    assert [n for n in names if "/attn/attn_out_proj/o/" in n]


@pytest.mark.parametrize("mixer", ["latent", "sparse"])
def test_latent_and_sparse_attention_hold_no_attention_scope(mixer):
    changes = {
        "latent": dict(layer_pattern="L-", mla_kv_rank=16, mla_nope_dim=8,
                       mla_rope_dim=4, mla_value_dim=8),
        "sparse": dict(layer_pattern="S-", n_kv_heads=2, head_dim=16,
                       dsa_index_heads=2, dsa_index_dim=8, dsa_topk=8),
    }[mixer]
    names = _names(_lowered(GPTConfig(**{**_SMALL, **changes}))[0])
    own = {"latent": "/mla_core/", "sparse": "/dsa_core/"}[mixer]
    assert [n for n in names if own in n]
    assert not [n for n in names if re.search(r"/attn_\w+/", n)]


@pytest.mark.parametrize("case", ["gpt2l", "qwen3next"])
def test_the_scopes_change_neither_the_program_nor_the_tree(case,
                                                            monkeypatch):
    cfg = GPTConfig(**{**_SMALL, **CASES[case][0]})
    lowered, params = _lowered(cfg)
    assert [n for n in _names(lowered) if "/attn_core/" in n]
    attn = params["params"]["block_0"]["attn"]
    assert sorted(attn) == sorted(["k", "o", "q", "v"] + (
        ["k_norm", "q_norm"] if cfg.head_norm else []))
    monkeypatch.setattr(jax, "named_scope",
                        contextlib.contextmanager(lambda name: (yield)))
    bare, bare_params = _lowered(cfg)
    assert not [n for n in _names(bare) if re.search(r"/attn_\w+/", n)]
    assert bare.as_text() == lowered.as_text()
    assert jax.tree.structure(bare_params) == jax.tree.structure(params)
    assert jax.tree.leaves(bare_params) == jax.tree.leaves(params)


def _counted(heads, kv_heads, head_dim, core, window=0, rotary="plain"):
    m = metrics.registry().get("hvt_attn_layers_traced_total")
    return m.labels(heads=str(heads), kv_heads=str(kv_heads),
                    head_dim=str(head_dim), core=core, window=str(window),
                    rotary=rotary, blocks="0", differential="0",
                    shared="0").value if m else 0.0


@pytest.mark.parametrize("use_flash, seq, on_tpu, core", [
    ("auto", 512, True, "einsum"),      # under the rule's 1,024 positions
    ("auto", 1024, True, "flash"),
    ("auto", 1088, True, "einsum"),     # 128 does not divide it
    ("auto", 1024, False, "einsum"),    # off a TPU a kernel is interpreted
    (False, 1024, True, "einsum"),
    (True, 128, False, "flash"),        # asked for: the interpreter here
])
def test_the_counter_says_which_path_the_rule_chose(use_flash, seq, on_tpu,
                                                    core, monkeypatch):
    monkeypatch.setattr(_pallas, "on_tpu", lambda: on_tpu)
    cfg = GPTConfig(**{**_SMALL, "n_layers": 1, "remat": False,
                       "n_kv_heads": 2, "max_seq_len": seq,
                       "use_flash": use_flash})
    tokens = jnp.zeros((1, seq), jnp.int32)
    others = [c for c in ("ring", "flash", "einsum") if c != core]
    before = [_counted(4, 2, 8, c) for c in [core] + others]
    jax.eval_shape(GPT(cfg).init, jax.random.key(0), tokens)
    after = [_counted(4, 2, 8, c) for c in [core] + others]
    assert after == [before[0] + 1] + before[1:]
    assert re.search(
        r'hvt_attn_layers_traced_total\{[^}]*core="%s"[^}]*\}' % core,
        metrics.prometheus_text())


def test_the_ring_schedule_is_counted_as_the_ring():
    from horovod_tpu.parallel.mesh import make_parallel_mesh

    mesh = make_parallel_mesh(sp=8)
    cfg = GPTConfig(**{**_SMALL, "n_layers": 1, "remat": False,
                       "max_seq_len": 32, "ring_mesh": mesh})
    before = _counted(4, 4, 8, "ring")
    names = _names(_lowered(cfg, jnp.zeros((2, 32), jnp.int32))[0])
    assert _counted(4, 4, 8, "ring") > before
    # the schedule is under the core's scope, forward and backward
    assert "jit(loss)/jvp(GPT)/block_0/attn/attn_core/shard_map" in names
    assert [n for n in names if n.endswith("/attn_core/shard_map")
            and "transpose" in n]
