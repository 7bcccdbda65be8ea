"""The ``afmoe`` family (Arcee's Trinity models): ``horovod_tpu.models.GPT``
with one mixer a layer, a decoder layer of the source being two of them
(``W`` gated attention inside a window, turned by the rotary, or ``*`` the
same attention over every causal key and not turned, as ``layer_types``
says; then ``-`` a SwiGLU MLP in the leading dense layers and ``E`` a
sigmoid top-k router with a choice bias over SwiGLU experts of their own
width, with one ungated shared expert, in the others), a norm before and
a norm after every mixer, the embedding times ``sqrt(d)``, driven by the
sizes of a configuration file under the names of the source's
``config.json``, for **one chip's share** of each layer.

Configuration keys: ``vocab_size``, ``num_hidden_layers``,
``num_dense_layers``, ``layer_types``, ``hidden_size``, ``rms_norm_eps``;
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``rope_theta``, ``sliding_window``; ``intermediate_size``;
``num_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``,
``num_shared_experts``, ``route_norm``, ``route_scale``, ``mup_enabled``;
plus ``dtype``, ``remat``, ``use_flash``, ``optimizer``. ``num_experts``
and ``vocab_size`` give what is **held here**; ``published`` holds the
model's own counts, from which the program takes the router's width;
``experts_held_first`` is the first held expert's number. The attention,
the dense MLPs, the router, the shared expert and the norms are whole.
What the package does not build is refused by name. Traffic keys:
``per_chip_batch``, ``seq_len``.

The loss never holds the float32 logits whole: the model returns its last
hidden states and the package's chunked
``ops.losses.softmax_cross_entropy_fused`` multiplies them by the untied
``lm_head`` a chunk of positions at a time.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable

import jax
import jax.numpy as jnp

from horovod_tpu.models import GPT, GPTConfig
from horovod_tpu.models.transformer import Attention
from horovod_tpu.ops.losses import softmax_cross_entropy_fused

from chipbench import compare
from chipbench.families import optimizer_from
from chipbench.families.nemotron_h import (HybridJob, _leaf_sums,
                                           router_distance)
from chipbench.families.olmoe import compare_choices, load
from chipbench.families.qwen3_next import held_rows
from chipbench.reference import afmoe as reference

WINDOWED, FULL = "sliding_attention", "full_attention"

# What --rehearse shrinks for a CPU dry run (control flow only): a share
# of a small model with all four kinds of layer (three decoder layers, the
# first dense, the last full), a window a quarter of the sequence.
# Float32, as olmoe's.
REHEARSAL = {
    "config": {
        "vocab_size": 256, "num_hidden_layers": 3, "num_dense_layers": 1,
        "layer_types": [WINDOWED, WINDOWED, FULL], "hidden_size": 64,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "sliding_window": 16, "num_experts": 4, "num_experts_per_tok": 3,
        "experts_held_first": 4, "dtype": "float32",
        "published": {"num_hidden_layers": 6, "num_experts": 16,
                      "vocab_size": 512}},
    "traffic": {"seq_len": 64, "per_chip_batch": 1}}

# The probe: a leading dense layer, an expert layer behind a windowed
# attention and one behind a full attention, at the published widths and
# shares.
PROBE_LAYER_TYPES = (WINDOWED, WINDOWED, FULL)
PROBE_DENSE_LAYERS = 1
# Positions of the probe's gradient comparison: twice the window, so that
# half the queries lose keys to it (at 2,048 a window of 2,048 hides
# nothing and the probe would pass without one); the program's attention is
# the Pallas kernels, as in the cell.
PROBE_SEQ_LEN = 4096
# Positions of the sequence whose logits the loss holds at once: 1024 rows
# against the 25024 x 2048 head, 102 MB of float32 logits.
LOSS_CHUNK = 1024


def layer_pattern(layer_types, n_dense: int) -> str:
    """The source's decoder layers as the package's pattern: layer ``i``
    is its attention, windowed (``W``) or full (``*``) as ``layer_types[i]``
    says, and then its feed-forward, the dense MLP in the first ``n_dense``
    layers (``num_dense_layers``) and the experts after them."""
    kinds = {WINDOWED: "W", FULL: "*"}
    return "".join(kinds[kind] + ("-" if i < n_dense else "E")
                   for i, kind in enumerate(layer_types))


def band_pairs(seq_len: int, window: int) -> int:
    """``sum_t min(t + 1, window)``: the (query, key) pairs a head's
    windowed attention requires of one sequence."""
    full = min(window, seq_len)
    return full * (full + 1) // 2 + (seq_len - full) * full


def forward_macs_per_token(pattern, d_model, heads, kv_heads, head_dim,
                           window, d_ff, n_experts, experts_held,
                           experts_per_token, d_expert, d_shared, vocab_size,
                           seq_len) -> dict:
    """Multiply-adds a token of one forward pass over a chip's share, by
    kind of layer and for the head, from shapes. ``experts_held`` and
    ``vocab_size`` are what is held.

    Both kinds of attention: the projections ``d (3 H + 2 H_kv) hd`` (the
    query, the gate and the output a query head, the key and the value a
    key-value head) and the scores and values ``2 H hd`` a visible pair:
    ``*`` over the ``(s + 1) / 2`` causal keys a query sees on average,
    ``W`` over **the band's pairs and not the causal ones, whatever the
    program executes**, ``band_pairs / s`` keys a query. ``-``: three
    matrices ``3 d d_ff``. ``E``: the router over all ``n_experts``, the
    shared expert's three matrices and the routed experts at their
    expectation under a uniform router: ``k x held / n_experts`` experts a
    token, three matrices each. The untied head once; the embedding lookup
    and its scale are free."""
    proj = d_model * (3 * heads + 2 * kv_heads) * head_dim
    layer = {
        "*": proj + 2 * heads * head_dim * (seq_len + 1) / 2,
        "W": proj + 2 * heads * head_dim * band_pairs(seq_len, window)
        / seq_len,
        "-": 3 * d_model * d_ff,
        "E": (d_model * n_experts + 3 * d_model * d_shared
              + experts_per_token * experts_held / n_experts
              * 3 * d_model * d_expert),
    }
    macs = {kind: pattern.count(kind) * each for kind, each in layer.items()}
    macs["head"] = vocab_size * d_model
    return macs


def n_params(pattern, d_model, heads, kv_heads, head_dim, d_ff, n_experts,
             experts_held, d_expert, d_shared, vocab_size) -> int:
    """Embedding, head and final norm; a layer its two norms and its
    mixer (an attention of either kind its projections and two head
    norms)."""
    attention = (d_model * (3 * heads + 2 * kv_heads) * head_dim
                 + 2 * head_dim)
    layer = {
        "*": attention, "W": attention,
        "-": 3 * d_model * d_ff,
        "E": (d_model * n_experts + 3 * d_model * d_shared
              + experts_held * 3 * d_model * d_expert),
    }
    return (2 * vocab_size * d_model + d_model
            + sum(layer[kind] + 2 * d_model for kind in pattern))


def attn_window_macs_per_step(layers, batch, heads, seq_len, head_dim,
                              window, remat) -> float:
    """Multiply-adds a training step requires of the windowed layers'
    products over positions (scope ``attn_window``): ``q k^T`` and ``p v``
    in the forward pass; the scores again, ``dO v^T``, ``p^T dO``, ``dS k``
    and ``dS^T q`` in the backward; under ``remat`` the forward pass a
    second time. ``band_pairs`` a sequence and head, exactly and not by
    tiles, whatever the program walks."""
    pairs = batch * heads * band_pairs(seq_len, window)
    return float(layers * pairs * head_dim * ((2 if remat else 1) * 2 + 5))


def attn_window_bytes_per_step(layers, batch, heads, kv_heads, seq_len,
                               head_dim, remat, itemsize=2) -> float:
    """Bytes the same products have to move once a call and position: the
    forward reads q (a query head) and k and v (a key-value head) and
    writes o and a float32 log-sum-exp; the backward reads q, k, v, o and
    dO and two float32 statistics and writes dQ, dK and dV."""
    forward = itemsize * head_dim * (2 * heads + 2 * kv_heads) + 4 * heads
    backward = (itemsize * head_dim * (4 * heads + 4 * kv_heads)
                + 8 * heads)
    return float(layers * batch * seq_len
                 * ((2 if remat else 1) * forward + backward))


def _model_config(config, seq_len) -> GPTConfig:
    published = config["published"]
    for key, want in (("model_type", "afmoe"), ("n_group", 1),
                      ("topk_group", 1), ("num_expert_groups", 1),
                      ("num_limited_groups", 1), ("score_func", "sigmoid"),
                      ("rope_scaling", None), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False)):
        if config.get(key, want) != want:
            raise ValueError(f"{key} is {config[key]!r}: the package builds "
                             f"{want!r} alone")
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - {
            WINDOWED, FULL}:
        raise ValueError(
            f"layer_types {kinds!r} names one of {WINDOWED!r} and {FULL!r} "
            f"for each of the {config['num_hidden_layers']} layers")
    pattern = layer_pattern(kinds, config["num_dense_layers"])
    return GPTConfig(
        vocab_size=config["vocab_size"], n_layers=len(pattern),
        layer_pattern=pattern, d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], head_norm=True, attn_gate=True,
        # the full layers turn nothing, the windowed ones the whole head
        rotary=False, attn_window=config["sliding_window"],
        rotary_base=float(config["rope_theta"]), post_norm=True,
        embed_scale=(math.sqrt(config["hidden_size"])
                     if config["mup_enabled"] else 1.0),
        max_seq_len=seq_len, dtype=jnp.dtype(config["dtype"]),
        remat=config["remat"], use_flash=config["use_flash"],
        tie_embeddings=False, norm_eps=config["rms_norm_eps"],
        mlp_act="swiglu", d_ff=config["intermediate_size"],
        moe_expert_ff=config["moe_intermediate_size"],
        n_experts=published["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        moe_score="sigmoid", moe_renormalise=config["route_norm"],
        moe_route_scale=float(config["route_scale"]),
        moe_shared_ff=(config["num_shared_experts"]
                       * config["moe_intermediate_size"]),
        experts_held=(config["experts_held_first"], config["num_experts"]))


def _sizes(cfg: GPTConfig, seq_len=None) -> dict:
    """The arguments the two counting functions share."""
    sizes = dict(
        pattern=cfg.layer_pattern, d_model=cfg.d_model, heads=cfg.n_heads,
        kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, d_ff=cfg.d_ff,
        n_experts=cfg.n_experts, experts_held=cfg.experts_held[1],
        d_expert=cfg.moe_expert_ff, d_shared=cfg.moe_shared_ff,
        vocab_size=cfg.vocab_size)
    if seq_len is not None:
        sizes.update(window=cfg.attn_window,
                     experts_per_token=cfg.experts_per_token,
                     seq_len=seq_len)
    return sizes


def mixer_distances(sown, p, config, windowed, float32_mixer) -> dict:
    """The program's attention mixer against the reference's (one masked
    softmax over whole rows, by query blocks in float32) on the input the
    program's mixer had, relative L2 of the output over every sequence:
    ``"mixer"`` the output the program sowed, bf16 products and all, and
    ``"float32_parts"`` that of ``float32_mixer``, the program's own
    module built with float32 products and run at the highest precision on
    the same input and parameters (at the cell's length through the same
    kernels). In the second nothing is left to read but what the
    configuration states as float32 in both (the heads' norms, the rotary's
    phases, the softmax and its statistics, the gate) and which keys a row
    sees: the first cannot see the softmax's precision under the bf16
    products' 6.4e-3."""
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    u = sown["attn_input"]
    want = reference.mixer(u, p, config, windowed)
    far = lambda got: float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                            / jnp.linalg.norm(want))
    positions = jnp.broadcast_to(jnp.arange(u.shape[1]), u.shape[:2])
    with jax.default_matmul_precision("highest"):
        again = jax.jit(lambda u, p: float32_mixer.apply(
            {"params": p}, u, positions))(f32(u), f32(p))
    return {"mixer": far(sown["attn_output"]), "float32_parts": far(again)}


# Every bound below lies between two readings on the chip (v5e, my chip
# runs, PR 57; `benchmarks/trinity_wrong_programs.py` makes them again):
# the largest a sound run gave over its seeds (that script's and the runs
# of the cell, twelve and more) and what a lower precision or wrong
# mathematics gives. PERF.md section 6 has the table, in the same numbers.
#
# The step's loss against the float32 reference on the parameters a window
# of training left and the batch it trained on, relative to the
# reference's. Sound: 2.8e-6 to 4.52e-5 after a window (eighteen runs),
# 3.1e-6 and 6.3e-6 on a fresh initialisation. There **no window reads
# 1.040e-3**, a window of 2,047 4.1e-6 and the softmax in bf16 1.8e-6, and
# the reference itself at the TPU's default precision 8.7e-6 and 9.2e-6: of
# everything `benchmarks/trinity_wrong_programs.py` plants in the attention
# this loss tells a missing window by a hair and nothing finer (a fresh
# model's loss is ln 25,024 whatever its mixers do; the checks below tell
# the rest). The bound is `gpt`'s, the accepted cells' one, which leaves
# the largest sound reading 22 times of room.
LOSS_REL_BOUND = 1e-3
# Gradients of the probe against the reference given the program's expert
# indices, relative L2. Over the whole tree: sound 1.361e-2 to 1.398e-2
# (the bf16 activations the configuration states; twenty-three readings
# within 2.7%); **a window of 2,047 2.131e-2 and of 2,049 2.136e-2**; the
# softmax in bf16 1.450e-2; the routed sum without 2.826 0.107, a window in
# the full layers too 0.112, the full layers turned 0.121, no norm a head
# 0.209, no window 0.452, no gate 0.562 and everything else more. The
# geometric middle of 1.398e-2 and 2.131e-2: the window's count in the
# backward pass (the mixers' `float32_parts` below hold it in the
# forward).
GRAD_REL_L2_BOUND = 1.72e-2
# ... and at the worst leaf, which is a head norm's weight (`k_norm` or
# `q_norm` of the second or third attention): sound 2.161e-2 to 2.735e-2;
# a window of 2,047 3.148e-2, of 2,049 3.216e-2 and the softmax in bf16
# 2.938e-2, which this check is not asked to tell; a window in the full
# layers too 0.496, no window 0.612, the routed sum without 2.826 0.628,
# no gate 0.969, no norm a head 1.00 and everything else more. Near the
# geometric middle of 2.735e-2 and 0.496.
GRAD_WORST_LEAF_BOUND = 0.115
# The program's router against sigmoid(h W_r) in float32 at highest
# precision on the very input the program's router had, the largest
# relative distance of a score over 4,096 tokens x 128 experts: sound
# 1.19e-7 (one unit in the last place) in every run; the product at the
# TPU's default precision 5e-3 in the families that ran it
# (`deepseek_v3`, `qwen3_next`). Their bound.
ROUTER_REL_BOUND = 3e-5
# The program's 8 of 128 against the reference's own. Top-k is
# discontinuous and the program's router sees bf16 hidden states that have
# been through bf16 mixers, so some tokens swap their 8th and 9th expert:
# sound 0.98993 to 0.99210 of the assignments agree; a window off by one
# 0.9865 and 0.9867; no norm a head 0.890, no window 0.815, no gate 0.708,
# the embedding unscaled 0.440 and the rest less. The middle of 0.98993
# and 0.890.
CHOICES_AGREE_BOUND = 0.94
# ... and every disagreement is a near-tie: the largest gap in the
# reference's scores that the program's choice overrode (a score is
# between 0 and 1), sound 4.4e-3 to 7.1e-3; a window of 2,049 1.4e-2, of
# 2,047 2.8e-2; no norm a head 9.7e-2, no gate 0.338 and the rest more.
# The geometric middle of 7.1e-3 and 9.7e-2: a tail statistic over some
# 300 disagreements, so the bound leaves it 3.7 times the largest seen.
NEAR_TIE_BOUND = 2.6e-2
# An attention mixer's output at the cell's 16,384 positions against the
# float32 reference (one masked softmax over whole rows, by query blocks)
# on the mixer's own input, relative L2, by two measures
# (`mixer_distances`), for the timed model's first and last windowed mixer
# (published layers 0 and 6) and its first full one (layer 3) on the
# parameters the window left and the batch it trained on.
#
# `MIXER_BOUNDS`: the output the step's own program made (bf16 products and
# a bf16 result; the heads' norms and the softmax in float32). It tells
# wrong mathematics; it cannot tell the softmax's precision, which hides
# under the bf16 products' own distance (the softmax in bf16 reads 7.34e-3
# to 7.38e-3, 4.59e-3 to 4.68e-3 and 4.54e-3 to 4.65e-3, 6 to 14% over a
# sound run): `FLOAT32_PARTS_BOUND` does.
MIXER_BOUNDS = {
    # Its input is the normed embedding, every token's own vector, so a
    # row's output is a mean of 2,048 unlike values and one key more or
    # less moves it by 1 / sqrt(2048) of itself. Sound 6.377e-3 to
    # 6.463e-3 (twenty-six readings within 1.4%); **a window of 2,049 1.656e-2
    # and of 2,047 1.645e-2 to 1.682e-2**; no norm a head 0.144, no window
    # 0.606, the windowed layers not turned 0.974, no gate 1.00. The
    # geometric middle of 6.463e-3 and 1.656e-2.
    "first_windowed": 1.03e-2,
    # The later mixers' inputs carry what six and three layers added to
    # every token alike, so a key at the window's edge moves nothing this
    # measure can read (a window of 2,047: 4.47e-3 and 4.24e-3). Sound
    # 4.272e-3 to 4.423e-3; the second norm after the residual sum 7.9e-3 to
    # 8.2e-3 and left out 8.5e-3 (the mixer's input is another); no norm a
    # head 1.01e-2, the windowed layers not turned 0.100, no window 0.325.
    # The geometric middle of 4.423e-3 and 1.01e-2.
    "last_windowed": 6.7e-3,
    # Sound 4.207e-3 to 4.416e-3; no norm a head 1.75e-2, the full layers
    # turned 0.246, a window in the full layers too 0.882. Near the
    # geometric middle of 4.416e-3 and 1.75e-2.
    "first_full": 8.7e-3,
}
# The same module built with float32 products and run at the highest
# precision on the same input and parameters, through the same kernels,
# against the same reference: what is left is float32's own rounding, and
# the bf16 products' 6.4e-3 is gone from both sides. Sound, ten readings
# a mixer within 7% (three seeds on a fresh initialisation and seven runs
# of the cell): first windowed 8.893e-7 to 8.958e-7, last windowed 3.201e-7
# to 3.305e-7, full 6.521e-7 to 6.966e-7. **The scores rounded to bf16 and
# the softmax computed in bf16, three seeds within 1.4%: 3.812e-3 to
# 3.818e-3, 1.674e-3 to 1.697e-3 and 1.653e-3 to 1.672e-3**, 2,450 to
# 5,100 times a sound run; **a window of 2,047 1.554e-2 and 1.030e-3, of
# 2,049 1.527e-2 and 1.032e-3** on the windowed mixers (the full one reads
# its 6.6e-7). One bound for the three, 33 to 91 times the largest sound
# reading and 34 to 127 times under the nearest wrong one: this is the
# check that holds the softmax to float32 and, in the forward pass, the
# window to its count.
FLOAT32_PARTS_BOUND = 3e-5


def gradients_close(name, got, want) -> list:
    """Relative L2 over the whole tree, held to ``GRAD_REL_L2_BOUND``,
    and at the worst leaf, held to ``GRAD_WORST_LEAF_BOUND``."""
    sums = {jax.tree_util.keystr(path): (float(num), float(den))
            for path, (num, den) in jax.tree_util.tree_leaves_with_path(
                _leaf_sums(got, want), is_leaf=lambda t: isinstance(t, tuple))}
    by_leaf = {k: math.sqrt(num / den) for k, (num, den) in sums.items()}
    worst = max(by_leaf, key=lambda k: (not math.isfinite(by_leaf[k]),
                                        by_leaf[k]))
    whole = math.sqrt(sum(n for n, _ in sums.values())
                      / sum(d for _, d in sums.values()))
    print("gradient distance by leaf: " + ", ".join(
        f"{k} {v:.2e}" for k, v in sorted(by_leaf.items(),
                                          key=lambda kv: -kv[1])[:8]),
          flush=True)
    return [
        compare.Check(name, math.isfinite(whole)
                      and whole <= GRAD_REL_L2_BOUND,
                      f"relative L2 {whole:.3e}", GRAD_REL_L2_BOUND),
        compare.Check(f"{name}_worst_leaf", math.isfinite(by_leaf[worst])
                      and by_leaf[worst] <= GRAD_WORST_LEAF_BOUND,
                      f"{worst}: relative L2 {by_leaf[worst]:.3e}",
                      GRAD_WORST_LEAF_BOUND)]


@dataclasses.dataclass
class AfmoeJob(HybridJob):
    """``config``: the configuration this instance's reference reads (its
    own ``layer_types``: the probe's are not the cell's);
    ``mixers_close(params, extra, tokens)``: its first and last windowed
    attention mixer and its first full one against the reference's, each
    on its own input."""

    config: dict | None = None
    mixers_close: Callable | None = None


def _attention_blocks(pattern) -> list:
    """``(block name, windowed)`` of the pattern's attention layers."""
    return [(f"block_{i}", kind == "W") for i, kind in enumerate(pattern)
            if kind in "W*"]


def _job(cfg: GPTConfig, config, per_chip_batch, seq_len,
         probe=None) -> AfmoeJob:
    """The job of one model instance on ``config`` (whose ``layer_types``
    are this instance's); ``probe`` is the small instance its gradients
    are checked on (the probe itself checks nothing)."""
    model = GPT(cfg)

    def init(key):
        variables = model.init(key, jnp.zeros((1, seq_len), jnp.int32))
        return variables["params"], {"buffers": variables["buffers"]}

    def make_batch(key, n_chips):
        return jax.random.randint(
            key, (n_chips * per_chip_batch, seq_len), 0, cfg.vocab_size,
            jnp.int32)

    def loss_and_sown(params, extra, tokens, sow=True):
        # one scope around all of it: JAX writes a transformation's name
        # (jvp, transpose) around the outermost scope, and the readers
        # look for "/lm_head/", "/moe_", "/attn_", "/post_norm/" and
        # "/dense_mlp/" further in
        with jax.named_scope("afmoe"):
            hidden, sown = model.apply(
                {"params": params, **extra}, tokens, return_hidden=True,
                mutable=["intermediates"] if sow else [])
            ce = softmax_cross_entropy_fused(
                hidden[:, :-1], params["lm_head"], tokens[:, 1:],
                chunk=LOSS_CHUNK)
        sown = {block: {name: value[0] for mixer in kinds.values()
                        for name, value in mixer.items()}
                for block, kinds in sown["intermediates"].items()} \
            if sow else None
        return ce, sown

    def loss(params, extra, tokens):
        return loss_and_sown(params, extra, tokens, sow=False)[0], extra

    blocks = _attention_blocks(cfg.layer_pattern)
    windowed = [b for b, is_windowed in blocks if is_windowed]
    full = [b for b, is_windowed in blocks if not is_windowed]
    chosen = {"first_windowed": (windowed[0], True),
              "last_windowed": (windowed[-1], True),
              "first_full": (full[0], False)}
    # the program's own mixer of either kind with float32 products
    # (`mixer_distances`)
    float32 = dataclasses.replace(cfg, dtype=jnp.float32)
    float32_mixers = {
        True: Attention(float32, rotary=True, window=cfg.attn_window),
        False: Attention(float32, rotary=cfg.rotary)}
    # what `mixers_close` read on the timed model: the harness hands the
    # window's parameters and batch to `reference_loss` alone and asks
    # `check` for the comparisons afterwards
    timed = []

    def mixers_close(params, extra, tokens, trained=False) -> list:
        """The first and the last windowed mixer and the first full one,
        at the length of ``tokens``, each on the input it had in this
        model's forward pass, against the reference's, by both measures
        of ``mixer_distances``."""
        keep = {block for block, _ in chosen.values()}
        sown = jax.jit(lambda *a: {
            block: {name: s[name] for name in ("attn_input", "attn_output")}
            for block, s in loss_and_sown(*a)[1].items() if block in keep})(
                params, extra, tokens)
        checks = []
        for name, (block, is_windowed) in chosen.items():
            found = mixer_distances(
                sown[block], params[block]["attn"], config, is_windowed,
                float32_mixers[is_windowed])
            for measure, against in (
                    ("mixer", "vs_reference_by_query_blocks"),
                    ("float32_parts", "with_float32_products")):
                far, bound = found[measure], (
                    MIXER_BOUNDS[name] if measure == "mixer"
                    else FLOAT32_PARTS_BOUND)
                checks.append(compare.holds(
                    ("trained_" if trained else "")
                    + f"{name}_{measure}_{against}_{tokens.shape[1]}",
                    math.isfinite(far) and far <= bound,
                    f"{block}: relative L2 of the mixer's output on its own "
                    f"input: {far:.3e}", bound))
        return checks

    def reference_loss(params, extra, tokens):
        # the timed model's own mixers, on the parameters the window left
        # and the batch it trained on
        timed[:] = mixers_close(params, extra, tokens, trained=True)
        value, routing = reference.loss(params, extra["buffers"], tokens,
                                        config)
        print("at the end of the window, a layer: load (largest group over "
              "the mean of all the router's experts) " + ", ".join(
                  f"{load(r['own'], cfg.n_experts):.3f}" for r in routing)
              + "; rows on the experts held " + ", ".join(
                  str(int(jnp.sum(held_rows(r["own"], cfg)))) for r in routing)
              + f" of a round of {tokens.size}", flush=True)
        return value

    def check(key):
        """On the probe (``W-WE*E`` at the published widths and shares):
        gradients at ``PROBE_SEQ_LEN`` positions against the reference
        given the program's expert indices, the router against a float32
        one on its own input, the two choices of experts against each
        other; then what ``reference_loss`` read of the timed model's own
        mixers at the cell's length."""
        if probe is None:
            return []
        marks = [("", time.perf_counter())]
        mark = lambda name: marks.append((name, time.perf_counter()))
        # (of three keys, so that a seed's probe is the one the bounds'
        # readings were made on)
        key_sample, key_probe, _ = jax.random.split(key, 3)
        sample = make_batch(key_sample, 1)[:1]
        short = sample[:, :min(PROBE_SEQ_LEN, seq_len)]
        pattern = probe.facts["pattern"]
        params, extra = jax.jit(probe.init)(key_probe)
        (_, sown), got = jax.jit(jax.value_and_grad(
            probe.loss_and_sown, has_aux=True))(params, extra, short)
        moe_blocks = [f"block_{i}" for i, kind in enumerate(pattern)
                      if kind == "E"]
        routed = sown[moe_blocks[0]]
        jax.block_until_ready(got)
        mark("the probe's gradients")
        (_, routing), want = reference.loss_and_grad(
            params, extra["buffers"], short, probe.config,
            [sown[block]["experts"] for block in moe_blocks])
        jax.block_until_ready(want)
        mark("the reference's")
        checks = gradients_close(
            f"grad_vs_reference_given_experts_{pattern}_{short.shape[1]}",
            got, want)
        mark("leaf by leaf")
        distance = router_distance(
            routed, params[moe_blocks[0]]["moe"]["router"])
        checks.append(compare.holds(
            "router_is_float32", distance <= ROUTER_REL_BOUND,
            f"largest |s / s_ref - 1| on the router's own input: "
            f"{distance:.3e}", ROUTER_REL_BOUND))
        agree, gap = compare_choices(routed["experts"], routing[0]["probs"],
                                     routing[0]["own"])
        print(f"load of a fresh initialisation (largest group over the "
              f"mean of all the router's experts): "
              f"{load(routed['experts'], cfg.n_experts):.3f}; rows of the "
              f"experts held: {held_rows(routed['experts'], cfg).tolist()}",
              flush=True)
        checks.append(compare.holds(
            "experts_agree_with_reference", agree >= CHOICES_AGREE_BOUND,
            f"share of assignments: {agree}", CHOICES_AGREE_BOUND))
        checks.append(compare.holds(
            "disagreements_are_near_ties", gap <= NEAR_TIE_BOUND,
            f"largest score gap overridden: {gap}", NEAR_TIE_BOUND))
        mark("router and choices")
        checks += timed
        print("seconds of the family's check: " + ", ".join(
            f"{name} {t - t0:.1f}"
            for (_, t0), (name, t) in zip(marks, marks[1:])), flush=True)
        return checks

    macs = forward_macs_per_token(**_sizes(cfg, seq_len))
    tokens = per_chip_batch * seq_len
    rows_expected = (tokens * cfg.experts_per_token * cfg.experts_held[1]
                     / cfg.n_experts)
    band = {"layers": cfg.layer_pattern.count("W"), "batch": per_chip_batch,
            "heads": cfg.n_heads, "seq_len": seq_len,
            "head_dim": cfg.head_dim}
    return AfmoeJob(
        config=config, loss_and_sown=loss_and_sown, item="tokens",
        items_per_step_per_chip=tokens,
        flops_per_item=6.0 * sum(macs.values()),
        init=init, make_batch=make_batch, loss=loss,
        optimizer=lambda: optimizer_from(config["optimizer"]),
        reference_loss=reference_loss, loss_rel_bound=LOSS_REL_BOUND,
        check=check, probe=probe, mixers_close=mixers_close,
        facts={"n_params": n_params(**_sizes(cfg)),
               "n_layers": cfg.n_layers, "remat": cfg.remat,
               "pattern": cfg.layer_pattern,
               "forward_macs_per_token": macs,
               # `rows` to `itemsize` are what moe_experts_roofline reads:
               # the rows a layer's grouped products have to take at the
               # router's expectation, the stacks held, one product's sizes
               "moe": {"layers": cfg.layer_pattern.count("E"),
                       "rows": int(rows_expected),
                       "experts": cfg.experts_held[1],
                       "d_model": cfg.d_model, "d_expert": cfg.moe_expert_ff,
                       "itemsize": jnp.dtype(cfg.dtype).itemsize,
                       "row_bound": tokens * min(cfg.experts_per_token,
                                                 cfg.experts_held[1]),
                       "routed_over": cfg.n_experts},
               # what attn_window_roofline reads: the sizes of the windowed
               # layers' products over positions and what a step requires
               "attn_window": {
                   **band, "kv_heads": cfg.n_kv_heads,
                   "window": cfg.attn_window,
                   "band_pairs": band_pairs(seq_len, cfg.attn_window),
                   "macs_per_step": attn_window_macs_per_step(
                       **band, window=cfg.attn_window, remat=cfg.remat),
                   "bytes_per_step": attn_window_bytes_per_step(
                       **band, kv_heads=cfg.n_kv_heads, remat=cfg.remat,
                       itemsize=jnp.dtype(cfg.dtype).itemsize)}})


def build(config: dict, traffic: dict) -> AfmoeJob:
    seq_len, batch = traffic["seq_len"], traffic["per_chip_batch"]
    cfg = _model_config(config, seq_len)
    probe_config = {**config, "layer_types": list(PROBE_LAYER_TYPES),
                    "num_hidden_layers": len(PROBE_LAYER_TYPES),
                    "num_dense_layers": PROBE_DENSE_LAYERS}
    pattern = layer_pattern(PROBE_LAYER_TYPES, PROBE_DENSE_LAYERS)
    probe = _job(dataclasses.replace(
        cfg, n_layers=len(pattern), layer_pattern=pattern),
        probe_config, batch, seq_len)
    return _job(cfg, config, batch, seq_len, probe=probe)
