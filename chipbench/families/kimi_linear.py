"""The Kimi Linear family: ``horovod_tpu.models.GPT`` with one mixer a
layer, a decoder layer of the source being two of them (``K`` a Kimi Delta
Attention mixer, the delta rule with a decay a key channel, or ``L``
multi-head latent attention **without a rotary**; then ``-`` a SwiGLU MLP
in the leading dense layers and ``E`` a sigmoid top-k router with a choice
bias over SwiGLU experts of their own width, with one ungated shared
expert, in the others), driven by the sizes of a configuration file under
the names of the source's ``config.json``, for **one chip's share** of
each layer.

Configuration keys: ``vocab_size``, ``num_hidden_layers``,
``first_k_dense_replace``, ``hidden_size``, ``rms_norm_eps``;
``linear_attn_config`` (``kda_layers`` and ``full_attn_layers``, numbered
from 1 and read up to ``num_hidden_layers``, ``num_heads``, ``head_dim``,
``short_conv_kernel_size``); ``num_attention_heads``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``mla_use_nope``, ``rope_theta`` (unused where ``mla_use_nope``);
``intermediate_size``; ``num_experts``, ``num_experts_per_token``,
``moe_intermediate_size``, ``num_shared_experts``, ``moe_renormalize``,
``routed_scaling_factor``; plus ``dtype``, ``remat``, ``use_flash``,
``optimizer``. ``num_experts`` and ``vocab_size`` give what is **held
here**; ``published`` holds the model's own counts, from which the program
takes the router's width; ``experts_held_first`` is the first held
expert's number. The mixers, the dense MLP, the router, the shared expert
and the norms are whole. What the package does not build is refused by
name. Traffic keys: ``per_chip_batch``, ``seq_len``.

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
from horovod_tpu.models import kda
from horovod_tpu.ops import channel_delta_rule
from horovod_tpu.ops.losses import softmax_cross_entropy_fused

from chipbench import compare
from chipbench.families import optimizer_from
from chipbench.families.deepseek_v3 import (mla_core_bytes_per_step,
                                            mla_core_macs_per_step)
from chipbench.families.nemotron_h import (HybridJob, _leaf_sums,
                                           router_distance)
from chipbench.families.olmoe import compare_choices, load
from chipbench.families.qwen3_next import held_rows
from chipbench.reference import kimi_linear as reference

# What --rehearse shrinks for a CPU dry run (control flow only): a share
# of a small model with all four kinds of layer (four decoder layers, the
# first dense, the fourth the latent attention). Float32, as olmoe's.
REHEARSAL = {
    "config": {
        "vocab_size": 256, "num_hidden_layers": 4,
        "first_k_dense_replace": 1, "hidden_size": 64,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "linear_attn_config": {
            "full_attn_layers": [4, 8], "kda_layers": [1, 2, 3, 5, 6, 7],
            "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4},
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "num_experts": 4, "num_experts_per_token": 3,
        "num_shared_experts": 1, "experts_held_first": 4,
        "dtype": "float32",
        "published": {"num_hidden_layers": 8, "num_experts": 16,
                      "vocab_size": 512}},
    "traffic": {"seq_len": 64, "per_chip_batch": 2}}

# Sequences on which the probe instance is compared.
SAMPLE_SEQUENCES = 2
# The probe: a Kimi Delta Attention layer and a latent attention, an expert
# layer after each, at the published widths and shares.
PROBE_PATTERN = "KELE"
# Positions of the probe's gradient comparison: the reference's backward
# pass walks the rule position by position (a [32, 128, 128] float32 state
# each, kept in runs of 128) and holds 256 queries' [32, 256, s] scores at
# a time; from 1024 up the program's attention is the Pallas kernels, as in
# the cell.
PROBE_SEQ_LEN = 2048
# Positions of the sequence whose logits the loss holds at once: 2 x 1024
# rows against the 20480 x 2304 head, 168 MB of float32 logits.
LOSS_CHUNK = 1024


def layer_pattern(n_layers: int, n_dense: int, kda_layers,
                  full_attn_layers) -> str:
    """The source's decoder layers 1 to ``n_layers`` as the package's
    pattern: layer ``i`` is its mixer (``K`` where ``kda_layers`` names it,
    ``L`` where ``full_attn_layers`` does) and then its feed-forward, the
    dense MLP in the first ``n_dense`` layers (``first_k_dense_replace``)
    and the experts after them (``moe_layer_freq`` 1)."""
    kinds = {**{i: "K" for i in kda_layers},
             **{i: "L" for i in full_attn_layers}}
    return "".join(kinds[i] + ("-" if i <= n_dense else "E")
                   for i in range(1, n_layers + 1))


def forward_macs_per_token(pattern, d_model, kda_heads, kda_dim, conv, rank,
                           heads, latent, nope, rope, value, d_ff, n_experts,
                           experts_held, experts_per_token, d_expert,
                           d_shared, vocab_size, seq_len) -> dict:
    """Multiply-adds a token of one forward pass over a chip's share, by
    kind of layer and for the head, from shapes (``chipbench/flops.py``
    counts no delta rule and no latent attention). ``experts_held`` and
    ``vocab_size`` are what is held.

    ``K``: the projections ``d 3 P`` (q, k, v), ``d H`` (beta), the
    decay's and the gate's pairs ``2 (d rank + rank P)`` and ``P d`` (out),
    the taps ``conv 3 P`` and the rule as the reference runs it (the
    state's read, its write and its read-out, ``3 H d_h^2``: no chunk
    length moves it). ``L``: the projections ``d heads (n + e)``, ``d (r +
    e)``, ``r heads (n + v)`` and ``heads v d``, and the two products over
    positions at their own widths over the ``(s + 1) / 2`` positions a
    query sees on average. ``-``: three matrices ``3 d d_ff``. ``E``: the
    router over all ``n_experts``, the shared expert's three matrices and
    the routed experts at their expectation under a uniform router: ``k x
    held / n_experts`` experts a token, three matrices each. The untied
    head once; the embedding lookup is free."""
    width = kda_heads * kda_dim
    layer = {
        "K": (d_model * (3 * width + kda_heads)
              + 2 * (d_model * rank + rank * width) + width * d_model
              + conv * 3 * width + 3 * kda_heads * kda_dim * kda_dim),
        "L": (d_model * heads * (nope + rope) + d_model * (latent + rope)
              + latent * heads * (nope + value) + heads * value * d_model
              + heads * (nope + rope + value) * (seq_len + 1) / 2),
        "-": 3 * d_model * d_ff,
        "E": (d_model * n_experts + 3 * d_model * d_shared
              + experts_per_token * experts_held / n_experts
              * 3 * d_model * d_expert),
    }
    macs = {kind: pattern.count(kind) * each for kind, each in layer.items()}
    macs["head"] = vocab_size * d_model
    return macs


def n_params(pattern, d_model, kda_heads, kda_dim, conv, rank, heads, latent,
             nope, rope, value, d_ff, n_experts, experts_held, d_expert,
             d_shared, vocab_size) -> int:
    """Embedding, head and final norm; a layer its norm and its mixer."""
    width = kda_heads * kda_dim
    layer = {
        "K": (d_model * (3 * width + kda_heads) + conv * 3 * width
              + 2 * (d_model * rank + rank * width) + width + kda_heads
              + kda_dim + width * d_model),
        "L": (d_model * heads * (nope + rope) + d_model * (latent + rope)
              + latent + latent * heads * (nope + value)
              + heads * value * d_model),
        "-": 3 * d_model * d_ff,
        "E": (d_model * n_experts + 3 * d_model * d_shared
              + experts_held * 3 * d_model * d_expert),
    }
    return (2 * vocab_size * d_model + d_model
            + sum(layer[kind] + d_model for kind in pattern))


def kda_rule_macs_per_step(layers, batch, heads, seq_len, head_dim,
                           remat) -> float:
    """Multiply-adds a training step requires of the delta rule (scope
    ``kda_rule``) **as the recurrence states it**: a position and head
    reads the state by its key (``S'^T k``), writes it (``k u^T``) and
    reads it by its query (``S^T q``), ``3 d_h^2``; the backward pass
    twice that; under ``remat`` the forward pass runs a second time. No
    chunk length moves it: a chunked program executes several times these
    products."""
    forward = 3 * heads * head_dim * head_dim
    return float(layers * batch * seq_len * forward * ((2 if remat else 1) + 2))


def kda_rule_bytes_per_step(layers, batch, heads, seq_len, head_dim, remat,
                            itemsize=2) -> float:
    """Bytes the rule has to move once a pass, a position: the forward
    reads ``q``, ``k``, ``v`` (``itemsize``) and the float32 ``g`` (a
    channel) and ``beta`` (a head) and writes ``o``; the backward reads
    the same with ``do`` for ``o`` and writes their five gradients."""
    width = heads * head_dim
    forward = itemsize * 4 * width + 4 * (width + heads)
    backward = forward + itemsize * 3 * width + 4 * (width + heads)
    return float(layers * batch * seq_len
                 * ((2 if remat else 1) * forward + backward))


def _model_config(config, seq_len) -> GPTConfig:
    published = config["published"]
    for key, want in (("model_type", "kimi_linear"), ("q_lora_rank", None),
                      ("num_expert_group", 1), ("topk_group", 1),
                      ("moe_router_activation_func", "sigmoid"),
                      ("moe_layer_freq", 1), ("rope_scaling", None),
                      ("hidden_act", "silu"), ("mla_use_nope", True),
                      ("tie_word_embeddings", False),
                      ("num_nextn_predict_layers", 0),
                      ("num_key_value_heads", config["num_attention_heads"])):
        if config.get(key, want) != want:
            raise ValueError(f"{key} is {config[key]!r}: the package builds "
                             f"{want!r} alone")
    linear = config["linear_attn_config"]
    pattern = layer_pattern(
        config["num_hidden_layers"], config["first_k_dense_replace"],
        linear["kda_layers"], linear["full_attn_layers"])
    return GPTConfig(
        vocab_size=config["vocab_size"], n_layers=len(pattern),
        layer_pattern=pattern, d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        mla_kv_rank=config["kv_lora_rank"],
        mla_nope_dim=config["qk_nope_head_dim"],
        mla_rope_dim=config["qk_rope_head_dim"],
        mla_value_dim=config["v_head_dim"],
        rotary=False,       # mla_use_nope: rope_theta is in the file, unused
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        kda_conv=linear["short_conv_kernel_size"],
        # the two-matrix gates' rank is a head's width, as the source's
        # module builds them (the configuration's ``assumed``)
        kda_gate_rank=linear["head_dim"],
        max_seq_len=seq_len, dtype=jnp.dtype(config["dtype"]),
        remat=config["remat"], use_flash=config["use_flash"],
        tie_embeddings=False, norm_eps=config["rms_norm_eps"],
        mlp_act="swiglu", d_ff=config["intermediate_size"],
        moe_expert_ff=config["moe_intermediate_size"],
        n_experts=published["num_experts"],
        experts_per_token=config["num_experts_per_token"],
        moe_score="sigmoid",
        moe_renormalise=config["moe_renormalize"],
        moe_route_scale=float(config["routed_scaling_factor"]),
        moe_shared_ff=(config["num_shared_experts"]
                       * config["moe_intermediate_size"]),
        experts_held=(config["experts_held_first"], config["num_experts"]))


def _sizes(cfg: GPTConfig, seq_len=None) -> dict:
    """The arguments the two counting functions share."""
    sizes = dict(
        pattern=cfg.layer_pattern, d_model=cfg.d_model,
        kda_heads=cfg.kda_heads, kda_dim=cfg.kda_head_dim,
        conv=cfg.kda_conv, rank=cfg.kda_gate_rank, heads=cfg.n_heads,
        latent=cfg.mla_kv_rank, nope=cfg.mla_nope_dim, rope=cfg.mla_rope_dim,
        value=cfg.mla_value_dim, d_ff=cfg.d_ff, n_experts=cfg.n_experts,
        experts_held=cfg.experts_held[1], d_expert=cfg.moe_expert_ff,
        d_shared=cfg.moe_shared_ff, vocab_size=cfg.vocab_size)
    if seq_len is not None:
        sizes.update(experts_per_token=cfg.experts_per_token,
                     seq_len=seq_len)
    return sizes


def mixer_distances(sown, p, config, kind, float32_mixer=None) -> dict:
    """The program's ``kind`` mixer (``"kda"``: against the rule one
    position after another; ``"mla"``: by query blocks) against the
    reference's in float32 on the input the program's mixer had, relative
    L2 of the output over every sequence: ``"mixer"`` the output the
    program sowed, bf16 products and all, and ``"float32_parts"`` that of
    ``float32_mixer``, the program's own module built with float32
    products and run at the highest precision on the same input and
    parameters. In the second nothing is left to read but what the
    configuration states as float32 in both (the decays, their cumulative
    sums, a chunk's system and inverse, the carried state) and the chunked
    form itself: the first cannot see them under the bf16 products' 5.7e-3."""
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    u, p = f32(sown[f"{kind}_input"]), f32(p)
    mixer = {"kda": reference.kda_mixer, "mla": reference.latent_attention}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda u, p: jax.lax.map(
            lambda one: mixer[kind](one, p, config), u))(u, p)
        far = lambda got: float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                                / jnp.linalg.norm(want))
        out = {"mixer": far(sown[f"{kind}_output"])}
        if float32_mixer is not None:
            out["float32_parts"] = far(jax.jit(lambda u, p: jax.lax.map(
                lambda one: float32_mixer.apply({"params": p}, one[None])[0],
                u))(u, p))
    return out


# Every bound below lies between two readings on the chip (v5e, my chip
# runs, PR 55; `benchmarks/kimilinear_wrong_programs.py` makes them again):
# the largest a sound run gave over its seeds (three of that script's and
# the cell's own runs), and what a lower precision or wrong mathematics
# gives. PERF.md section 6 has the table.
#
# The step's loss against the float32 reference on the parameters a
# window of training left and the batch it trained on, relative to the
# reference's. Sound: 4.8e-6 to 4.0e-5 after a window (seven runs), 3.6e-6
# and 2.8e-7 on a fresh initialisation; the reference itself at the TPU's
# default precision reads 1.5e-6 and 2.2e-6 from the reference: as in
# `nemotron_h`,
# `qwen3_next`, `lfm2_moe` and `deepseek_v3`, no lower precision is told
# from a sound run by this loss (the checks below do that), so the bound is
# `gpt`'s, the accepted cells' one, which leaves the largest sound reading
# 25 times of room.
LOSS_REL_BOUND = 1e-3
# Gradients of the probe against the reference given the program's expert
# indices, relative L2. Over the whole tree: sound 1.078e-2 to 1.116e-2
# (the bf16 activations the configuration states); the latent attention
# rotated 4.41e-2, the routed sum without 2.446 6.14e-2, the gate before
# the norm 0.374, `beta` left out 0.578, one decay a head 0.736, no shared
# expert 0.778, three taps 0.879, no decay 1.18, `silu` in the gate 2.00.
# The geometric middle of 1.116e-2 and 4.41e-2.
GRAD_REL_L2_BOUND = 2.2e-2
# ... and at the worst leaf, which is what a wrong expert layer or mixer
# moves while the head's and the embedding's gradients carry the tree's
# norm: sound 2.28e-2 to 2.71e-2 (the latent attention's `q_proj`); the
# gate before the norm 0.572 (the second router), the routed sum without
# 2.446 0.591 (`gate`), the latent attention rotated 0.826, everything else
# 1.0 and more. Near the geometric middle of 2.71e-2 and 0.572.
GRAD_WORST_LEAF_BOUND = 0.12
# The program's router against sigmoid(h W_r) in float32 at highest
# precision on the very input the program's router had, the largest
# relative distance of a score over 4,096 tokens x 256 experts: sound
# 1.19e-7 (one unit in the last place) in every run; the product at the
# TPU's default precision 5.65e-3. The geometric middle. This is the check
# that holds the router to float32.
ROUTER_REL_BOUND = 3e-5
# The program's 8 of 256 against the reference's own. Top-k is
# discontinuous and the program's router sees bf16 hidden states that have
# been through a bf16 mixer, so some tokens swap their 8th and 9th expert:
# sound 0.99103 to 0.99234 of the assignments agree; the gate before the
# norm 0.932, `beta` left out 0.650, one decay a head 0.515. The middle of
# 0.932 and 0.99103.
CHOICES_AGREE_BOUND = 0.96
# ... and every disagreement is a near-tie: the largest gap in the
# reference's scores that the program's choice overrode (a score is
# between 0 and 1), sound 2.68e-3 to 3.95e-3; the gate before the norm
# 4.02e-2, `beta` left out 0.325. Near the geometric middle of 3.95e-3 and
# 4.02e-2: a tail statistic, so the bound leaves it three times the
# largest seen.
NEAR_TIE_BOUND = 1.2e-2
# A mixer's output at the cell's 8192 positions against the reference on
# the mixer's own input, relative L2, by kind and measure
# (`mixer_distances`), for the probe's mixers on a fresh initialisation and
# for the timed model's first and last delta-rule mixer and its latent
# attention on the parameters the window left.
MIXER_BOUNDS = {
    # The Kimi Delta Attention mixer as the program ran it (bf16 products,
    # float32 decays, inverse and state) against the rule position by
    # position: sound 5.640e-3 to 5.859e-3 on the probe and the timed
    # model's first mixer (34 readings), 6.145e-3 to 6.555e-3 on its last
    # (ten); `beta` left out 0.383, one decay a head 0.520, three taps
    # 0.627, the gate before the norm 0.834, no decay 0.857, `silu` in the
    # gate 0.978, q and k not normalised not finite. Near the geometric
    # middle of 6.555e-3 and 0.383, on its tighter side as `qwen3_next`'s
    # is: 3.8 times the largest sound reading, a fifteenth of the nearest
    # wrong one. It tells wrong mathematics; it does not tell a lower
    # precision of what the configuration states as float32 (with all of
    # it in bf16 7.086e-3, under the bf16 products' own 5.7e-3): the next
    # does.
    ("kda", "mixer"): 2.5e-2,
    # The same module built with float32 products and run at the highest
    # precision on the same input and parameters, against the same
    # reference: what is left is the chunked form and what the
    # configuration states as float32 in both. Sound 2.467e-5 to 3.698e-5
    # on the probe and the first mixer (23 readings) and 3.927e-5 to
    # 6.004e-5 on the last (ten seeds): not float32's 5e-7, which the CPU
    # reads, because the reference multiplies 8192 of the chip's `exp` one
    # after another (its recurrence on the chip reads 4.7e-5 from the
    # CPU's on the same operands). With the decays, their cumulative sums,
    # the inverse and the carried state in bf16 4.094e-3 to 4.206e-3 (three
    # seeds), 68 times the largest sound reading; the carried state alone
    # rounded to bf16 after every chunk 1.020e-3 to 1.129e-3; the decays
    # alone, `g` rounded to bf16 once where the rule takes it and all else
    # float32, 1.424e-4 to 1.485e-4 (three seeds within 4%), the mildest
    # lower precision there is and 2.4 times the largest sound reading.
    # The bound is set to fail that one too, between 6.004e-5 and
    # 1.424e-4 with the more room above, where fresh seeds read (1.67
    # times; the sound readings spread by a half, the wrong program's by a
    # twentieth), and 1.42 times below. This is the check that holds the
    # rule's decays, cumulative sums, inverse and state to float32.
    ("kda", "float32_parts"): 1e-4,
    # The latent-attention mixer against the float32 reference by query
    # blocks with a whole unrotated key: sound 3.628e-3 to 3.815e-3 (probe
    # and timed model alike); the mixer rotated 2.984e-2. The geometric
    # middle.
    ("mla", "mixer"): 1.05e-2,
}


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
class KimiJob(HybridJob):
    """``blocks``: the model's ``block_<i>`` names by pattern letter;
    ``mixers_close(params, extra, tokens, which)``: its mixers against the
    reference's, each on its own input."""

    blocks: dict | None = None
    mixers_close: Callable | None = None


def _job(cfg: GPTConfig, config, per_chip_batch, seq_len,
         probe=None) -> KimiJob:
    """The job of one model instance; ``probe`` is the small instance its
    gradients are checked on (the probe itself checks nothing)."""
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
        # look for "/lm_head/", "/moe_", "/kda_", "/mla_" and
        # "/dense_mlp/" further in
        with jax.named_scope("kimi_linear"):
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

    blocks = {kind: [f"block_{i}" for i, k in enumerate(cfg.layer_pattern)
                     if k == kind] for kind in "KLE"}
    # the program's own mixer with float32 products (`mixer_distances`)
    float32_kda = kda.KimiDeltaAttention(
        cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv, cfg.kda_gate_rank,
        norm_eps=cfg.norm_eps, dtype=jnp.float32)
    # what `mixers_close` read on the timed model: the harness hands the
    # window's parameters and batch to `reference_loss` alone and asks
    # `check` for the comparisons afterwards
    timed = []

    def mixers_close(params, extra, tokens, which, trained=False) -> list:
        """The mixers of the blocks ``which`` names a kind, at the length
        of ``tokens``, each on the input it had in this model's forward
        pass, against the reference's: the Kimi Delta Attention mixers
        twice (``mixer_distances``), the latent attentions once."""
        keep = {block for named in which.values() for block in named}
        sown = jax.jit(lambda *a: {
            block: {name: value for name, value in s.items()
                    if name.endswith(("_input", "_output"))}
            for block, s in loss_and_sown(*a)[1].items() if block in keep})(
                params, extra, tokens)
        checks = []
        for kind, how in (("kda", "position_by_position"),
                          ("mla", "reference_by_query_blocks")):
            for block in which.get(kind, ()):
                found = mixer_distances(
                    sown[block], params[block][kind], config, kind,
                    float32_kda if kind == "kda" else None)
                for measure, against in (
                        ("mixer", f"vs_{how}"),
                        ("float32_parts", "with_float32_products")):
                    if measure not in found:
                        continue
                    far, bound = found[measure], MIXER_BOUNDS[kind, measure]
                    checks.append(compare.holds(
                        (f"trained_{block}_" if trained else "")
                        + f"{kind}_{measure}_{against}_{tokens.shape[1]}",
                        math.isfinite(far) and far <= bound,
                        f"relative L2 of the mixer's output on its own "
                        f"input: {far:.3e}", bound))
        return checks

    def reference_loss(params, extra, tokens):
        # the timed model's first and last delta-rule mixer and its last
        # latent attention, on the parameters the window left
        timed[:] = mixers_close(
            params, extra, tokens, trained=True, which={
                "kda": list(dict.fromkeys([blocks["K"][0], blocks["K"][-1]])),
                "mla": blocks["L"][-1:]})
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
        """On the probe (``KELE`` at the published widths and shares):
        gradients at ``PROBE_SEQ_LEN`` positions against the reference
        given the program's expert indices, the first router against a
        float32 one on its own input, the two choices of experts against
        each other, and the Kimi Delta Attention mixer and the latent
        attention at the cell's length against the reference (the rule one
        position after another) on their own inputs; then what
        ``reference_loss`` read of the timed model's own mixers."""
        if probe is None:
            return []
        marks = [("", time.perf_counter())]
        mark = lambda name: marks.append((name, time.perf_counter()))
        key_sample, key_probe = jax.random.split(key)
        sample = make_batch(key_sample, 1)[:SAMPLE_SEQUENCES]
        short = sample[:, :min(PROBE_SEQ_LEN, seq_len)]
        params, extra = jax.jit(probe.init)(key_probe)
        (_, sown), got = jax.jit(jax.value_and_grad(
            probe.loss_and_sown, has_aux=True))(params, extra, short)
        routed = sown[probe.blocks["E"][0]]
        jax.block_until_ready(got)
        mark("the probe's gradients")
        (_, routing), want = reference.loss_and_grad(
            params, extra["buffers"], short, config,
            [sown[block]["experts"] for block in probe.blocks["E"]])
        jax.block_until_ready(want)
        mark("the reference's")
        checks = gradients_close(
            f"grad_vs_reference_given_experts_{PROBE_PATTERN}_"
            f"{short.shape[1]}", got, want)
        mark("leaf by leaf")
        distance = router_distance(
            routed, params[probe.blocks["E"][0]]["moe"]["router"])
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
        checks += probe.mixers_close(params, extra, sample, {
            "kda": probe.blocks["K"][:1], "mla": probe.blocks["L"][:1]})
        mark("the probe's mixers at the cell's length")
        checks += timed
        print("seconds of the family's check: " + ", ".join(
            f"{name} {t - t0:.1f}"
            for (_, t0), (name, t) in zip(marks, marks[1:])), flush=True)
        return checks

    macs = forward_macs_per_token(**_sizes(cfg, seq_len))
    tokens = per_chip_batch * seq_len
    rows_expected = (tokens * cfg.experts_per_token * cfg.experts_held[1]
                     / cfg.n_experts)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    mla = {"layers": cfg.layer_pattern.count("L"), "batch": per_chip_batch,
           "heads": cfg.n_heads, "seq_len": seq_len,
           "qk_dim": cfg.mla_nope_dim + cfg.mla_rope_dim,
           "v_dim": cfg.mla_value_dim}
    rule = {"layers": cfg.layer_pattern.count("K"), "batch": per_chip_batch,
            "heads": cfg.kda_heads, "seq_len": seq_len,
            "head_dim": cfg.kda_head_dim}
    return KimiJob(
        loss_and_sown=loss_and_sown, blocks=blocks, mixers_close=mixers_close,
        item="tokens",
        items_per_step_per_chip=tokens,
        flops_per_item=6.0 * sum(macs.values()),
        init=init, make_batch=make_batch, loss=loss,
        optimizer=lambda: optimizer_from(config["optimizer"]),
        reference_loss=reference_loss, loss_rel_bound=LOSS_REL_BOUND,
        check=check, probe=probe,
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
                       "itemsize": itemsize,
                       "row_bound": tokens * min(cfg.experts_per_token,
                                                 cfg.experts_held[1]),
                       "routed_over": cfg.n_experts},
               # what mla_core_roofline reads: the sizes of the products
               # over positions and the multiply-adds a step requires
               "mla": {**mla,
                       "core_macs_per_step": mla_core_macs_per_step(
                           **mla, remat=cfg.remat),
                       "core_bytes_per_step": mla_core_bytes_per_step(
                           **mla, remat=cfg.remat, itemsize=itemsize)},
               # what kda_rule_roofline reads: the recurrence's own work
               "kda": {**rule, "chunk": channel_delta_rule.chunk_for(seq_len),
                       "rule_macs_per_step": kda_rule_macs_per_step(
                           **rule, remat=cfg.remat),
                       "rule_bytes_per_step": kda_rule_bytes_per_step(
                           **rule, remat=cfg.remat, itemsize=itemsize)}})


def build(config: dict, traffic: dict) -> KimiJob:
    seq_len, batch = traffic["seq_len"], traffic["per_chip_batch"]
    cfg = _model_config(config, seq_len)
    probe = _job(dataclasses.replace(
        cfg, n_layers=len(PROBE_PATTERN), layer_pattern=PROBE_PATTERN),
        config, batch, seq_len)
    return _job(cfg, config, batch, seq_len, probe=probe)
