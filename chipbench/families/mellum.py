"""The ``mellum`` family (JetBrains' Mellum 2): ``horovod_tpu.models.GPT``
with one mixer a layer, a decoder layer of the source being two of them
(``W`` grouped-query attention inside a window, turned by the plain
rotary, or ``*`` the same attention over every causal key, turned by the
YaRN-scaled one, as ``layer_types`` says: one entry of
``rope_parameters`` a kind of layer; then ``E`` a softmax top-k router
renormalised over the chosen, over SwiGLU experts of their own width, no
shared expert and no dense layer), driven by the sizes of a configuration
file under the names of the source's ``config.json``, for **one chip's
share** of each layer.

Configuration keys: ``vocab_size``, ``num_hidden_layers``,
``layer_types``, ``mlp_layer_types``, ``hidden_size``, ``rms_norm_eps``;
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``rope_parameters``, ``sliding_window``; ``num_experts``,
``num_experts_per_tok``, ``moe_intermediate_size``, ``norm_topk_prob``;
plus ``dtype``, ``remat``, ``use_flash``, ``optimizer``,
``embedding_std``. ``num_experts``
and ``vocab_size`` give what is **held here**; ``published`` holds the
model's own counts, from which the program takes the router's width;
``experts_held_first`` is the first held expert's number. The attention of
both kinds, the router and the norms are whole. What the package does not
build is refused by name. Traffic keys: ``per_chip_batch``, ``seq_len``.

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

from horovod_tpu.models import GPT, GPTConfig, moe, transformer
from horovod_tpu.models.transformer import Attention
from horovod_tpu.ops import rotary
from horovod_tpu.ops.losses import softmax_cross_entropy_fused

from chipbench import compare
from chipbench.families import optimizer_from
from chipbench.families.afmoe import (attn_window_bytes_per_step,
                                      attn_window_macs_per_step, band_pairs)
from chipbench.families.nemotron_h import HybridJob, _leaf_sums
from chipbench.families.olmoe import (compare_choices, load,
                                      router_distance)
from chipbench.families.qwen3_next import held_rows
from chipbench.reference import mellum as reference

WINDOWED, FULL = reference.WINDOWED, reference.FULL
SPARSE = "sparse"

# What --rehearse shrinks for a CPU dry run (control flow only): a share of
# a small model, one period of the source's (three windowed layers and a
# full one), a window a quarter of the sequence, 4 of 16 experts held with
# 8 a token so that a layer expects two rows a token and works in rounds
# of three, as the cell's do. Float32, as olmoe's.
REHEARSAL = {
    "config": {
        "vocab_size": 256, "num_hidden_layers": 4,
        "layer_types": [WINDOWED, WINDOWED, WINDOWED, FULL],
        "mlp_layer_types": [SPARSE] * 4, "hidden_size": 64,
        "moe_intermediate_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 16,
        "num_experts": 4, "num_experts_per_tok": 8, "experts_held_first": 4,
        "rope_parameters": {
            FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                   "original_max_position_embeddings": 32, "beta_fast": 32,
                   "beta_slow": 1, "attention_factor": 1.2772588722239782},
            WINDOWED: {"rope_type": "default", "rope_theta": 500000}},
        "dtype": "float32", "embedding_std": 1.0,
        "published": {"num_hidden_layers": 8, "num_experts": 16,
                      "vocab_size": 1024}},
    "traffic": {"seq_len": 64, "per_chip_batch": 1}}

# The probe: an expert layer behind a windowed attention and one behind a
# full attention, at the published widths and shares.
PROBE_LAYER_TYPES = (WINDOWED, FULL)
# Positions of the probe's gradient comparison: four times the window, so
# that three queries in four lose keys to it and the YaRN law's slow
# channels turn through a quarter of what they do at the cell's length;
# the program's attention is the Pallas kernels, as in the cell.
PROBE_SEQ_LEN = 4096
# Positions of the sequence whose logits the loss holds at once: 1024 rows
# against the 24576 x 2304 head, 101 MB of float32 logits.
LOSS_CHUNK = 1024


def layer_pattern(layer_types) -> str:
    """The source's decoder layers as the package's pattern: layer ``i``
    is its attention, windowed (``W``) or full (``*``) as ``layer_types[i]``
    says, and then its experts."""
    kinds = {WINDOWED: "W", FULL: "*"}
    return "".join(kinds[kind] + "E" for kind in layer_types)


def forward_macs_per_token(pattern, d_model, heads, kv_heads, head_dim,
                           window, n_experts, experts_held,
                           experts_per_token, d_expert, vocab_size,
                           seq_len) -> dict:
    """Multiply-adds a token of one forward pass over a chip's share, by
    kind of layer and for the head, from shapes. ``experts_held`` and
    ``vocab_size`` are what is held.

    Both kinds of attention: the projections ``d (2 H + 2 H_kv) hd`` (the
    query and the output a query head, the key and the value a key-value
    head) and the scores and values ``2 H hd`` a visible pair: ``*`` over
    the ``(s + 1) / 2`` causal keys a query sees on average, ``W`` over
    **the band's pairs and not the causal ones, whatever the program
    executes**, ``band_pairs / s`` keys a query. The rotary of either law
    is elementwise and counts nothing. ``E``: the router over all
    ``n_experts`` and the routed experts at their expectation under a
    uniform router: ``k x held / n_experts`` experts a token, three
    matrices each. The untied head once; the embedding lookup is free."""
    proj = d_model * (2 * heads + 2 * kv_heads) * head_dim
    layer = {
        "*": proj + 2 * heads * head_dim * (seq_len + 1) / 2,
        "W": proj + 2 * heads * head_dim * band_pairs(seq_len, window)
        / seq_len,
        "E": (d_model * n_experts + experts_per_token * experts_held
              / n_experts * 3 * d_model * d_expert),
    }
    macs = {kind: pattern.count(kind) * each for kind, each in layer.items()}
    macs["head"] = vocab_size * d_model
    return macs


def n_params(pattern, d_model, heads, kv_heads, head_dim, n_experts,
             experts_held, d_expert, vocab_size) -> int:
    """Embedding, head and final norm; a layer its norm and its mixer."""
    attention = d_model * (2 * heads + 2 * kv_heads) * head_dim
    layer = {"*": attention, "W": attention,
             "E": d_model * n_experts + experts_held * 3 * d_model * d_expert}
    return (2 * vocab_size * d_model + d_model
            + sum(layer[kind] + d_model for kind in pattern))


def _model_config(config, seq_len) -> GPTConfig:
    published = config["published"]
    for key, want in (("model_type", "mellum"), ("hidden_act", "silu"),
                      ("attention_bias", False),
                      ("tie_word_embeddings", False),
                      ("use_sliding_window", True), ("max_window_layers", 0)):
        if config.get(key, want) != want:
            raise ValueError(f"{key} is {config[key]!r}: the package builds "
                             f"{want!r} alone")
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - {
            WINDOWED, FULL}:
        raise ValueError(
            f"layer_types {kinds!r} names one of {WINDOWED!r} and {FULL!r} "
            f"for each of the {config['num_hidden_layers']} layers")
    if config["mlp_layer_types"] != [SPARSE] * len(kinds):
        raise ValueError(
            f"mlp_layer_types {config['mlp_layer_types']!r}: the family "
            f"builds {SPARSE!r} in every layer (the source has no dense one)")
    ropes = config["rope_parameters"]
    if set(ropes) != {WINDOWED, FULL}:
        raise ValueError(f"rope_parameters names {sorted(ropes)}: one entry "
                         f"each for {WINDOWED!r} and {FULL!r}")
    # (``rotary.law`` refuses by name what ``ops/rotary.py`` does not build)
    windowed, full = rotary.law(ropes[WINDOWED]), rotary.law(ropes[FULL])
    if isinstance(windowed, rotary.Yarn):
        raise ValueError(
            f"rope_parameters[{WINDOWED!r}] is a scaled law: the package's "
            f"windowed layers turn by the plain one (rope_type 'default')")
    if not isinstance(full, rotary.Yarn) and full != windowed:
        raise ValueError(
            f"rope_parameters names two plain laws at bases {windowed} and "
            f"{full}: the package's layers share rotary_base")
    pattern = layer_pattern(kinds)
    return GPTConfig(
        vocab_size=config["vocab_size"], n_layers=len(pattern),
        layer_pattern=pattern, d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rotary=True, rotary_base=windowed,
        rotary_scaling=full if isinstance(full, rotary.Yarn) else None,
        attn_window=config["sliding_window"],
        max_seq_len=seq_len, dtype=jnp.dtype(config["dtype"]),
        remat=config["remat"], use_flash=config["use_flash"],
        tie_embeddings=False, norm_eps=config["rms_norm_eps"],
        moe_expert_ff=config["moe_intermediate_size"],
        n_experts=published["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        moe_score="softmax", moe_renormalise=config["norm_topk_prob"],
        experts_held=(config["experts_held_first"], config["num_experts"]))


def _sizes(cfg: GPTConfig, seq_len=None) -> dict:
    """The arguments the two counting functions share."""
    sizes = dict(
        pattern=cfg.layer_pattern, d_model=cfg.d_model, heads=cfg.n_heads,
        kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        n_experts=cfg.n_experts, experts_held=cfg.experts_held[1],
        d_expert=cfg.moe_expert_ff, vocab_size=cfg.vocab_size)
    if seq_len is not None:
        sizes.update(window=cfg.attn_window,
                     experts_per_token=cfg.experts_per_token,
                     seq_len=seq_len)
    return sizes


def _far(got, want) -> float:
    return float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                 / jnp.linalg.norm(want))


def mixer_distances(sown, p, config, kind, float32_mixer) -> dict:
    """The program's attention mixer against the reference's (one masked
    softmax over whole rows, by query blocks in float32) on the input the
    program's mixer had, relative L2 of the output over every sequence:
    ``"mixer"`` the output the program sowed, bf16 products and all, and
    ``"float32_parts"`` that of ``float32_mixer``, the program's own
    module built with float32 products and run at the highest precision on
    the same input and parameters (at the cell's length through the same
    kernels). In the second nothing is left to read but what the
    configuration states as float32 in both (the rotary's phases, the
    softmax and its statistics) and which keys a row sees: the first
    cannot see the softmax's precision under the bf16 products' own
    distance."""
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    u = sown["attn_input"]
    want = reference.mixer(u, p, config, kind)
    positions = jnp.broadcast_to(jnp.arange(u.shape[1]), u.shape[:2])
    with jax.default_matmul_precision("highest"):
        again = jax.jit(lambda u, p: float32_mixer.apply(
            {"params": p}, u, positions))(f32(u), f32(p))
    return {"mixer": _far(sown["attn_output"], want),
            "float32_parts": _far(again, want)}


def experts_distance(sown, p, config, layer) -> float:
    """The program's expert layer (``layer``, the module the model builds,
    applied to the router's input the timed step sowed: the sort, the
    rounds and the grouped products in the cell's dtype) against the
    reference's sum over the held experts on the same input **given the
    program's choice of experts** (the weights the reference's own),
    relative L2 of the output."""
    h = sown["router_input"]
    got, _ = jax.jit(lambda h, p: layer.apply({"params": p}, h[None]))(h, p)
    want, _ = reference.experts(h, p, config, sown["experts"])
    return _far(got[0], want)


# Every bound below lies between two readings on the chip (v5e, my chip
# runs, PR 61; `benchmarks/mellum_wrong_programs.py` makes them again): the
# largest a sound run gave and what a lower precision or wrong mathematics
# gives. **Two initialisations were read.** Under the embedding at
# normal(1.0), the one the configuration states (third session): 10 whole
# runs of the cell on 10 seeds, the last four under the bounds as they
# stand, and three wrong programs (`high` and `low` not truncated on two
# seeds, the softmax in bf16 and the router at the default precision on
# one: the three that stood nearest a bound), given below as "now".
# Under normal(0.02) (the first two sessions: 26 sound runs on 17 seeds and
# all seven wrong programs on two seeds): given as "at 0.02" where no newer
# reading exists; the mixers and the expert layer are compared on their own
# inputs, unit in size under either, so those readings carry over but for
# the full mixer's, which was read again. PERF.md section 6 has the table.
#
# The step's loss against the float32 reference on the parameters a window
# of training left and the batch it trained on, relative to the
# reference's. Sound now 9.0e-8 to 7.9e-6 (at 0.02 to 2.19e-5). A fresh
# model's loss is ln 24,576 whatever its mixers do, so this tells little
# (the checks below tell the rest): `gpt`'s bound, the accepted cells' one.
LOSS_REL_BOUND = 1e-3
# Gradients of the probe against the reference given the program's expert
# indices, relative L2. Over the whole tree: sound now **5.661e-3 to
# 5.726e-3** (ten seeds within 1.2%: with the stream the token's own the
# bf16 activations' distance no longer goes with the seed; at 0.02 1.641e-2
# to 2.054e-2); **`high` and `low` not truncated 9.122e-3 and 9.196e-3**, which the full
# mixer's two measures tell as well, each 4 times over and more; the
# softmax in bf16 6.08e-3 and the router at the default precision 5.69e-3,
# which this check is not asked to tell (`FLOAT32_PARTS_BOUND` and
# `ROUTER_REL_BOUND` are); at 0.02 the YaRN frequencies without the factor
# 0.400, weights not renormalised 0.485, the plain rotary in the full layer
# 0.519, a window of 2,048 0.645. The geometric middle of 5.726e-3 and
# 9.122e-3, 26% of room on either side.
GRAD_REL_L2_BOUND = 7.2e-3
# ... and at the worst leaf (now a key projection of the full layer, at
# 0.02 the first router's weight): sound now 1.552e-2 to 1.584e-2 (at 0.02
# to 3.069e-2); not truncated 3.127e-2 and 3.157e-2, the two lower precisions 1.57e-2
# and 1.72e-2, which this check is not asked to tell; at 0.02 without the
# factor 0.638, not renormalised 0.754, the plain rotary 0.810, a window of
# 2,048 1.015. The geometric middle of 3.069e-2 and 0.638, as it was set:
# the nearest wrong reading this check has to tell was not read again.
GRAD_WORST_LEAF_BOUND = 0.14
# The program's router against softmax(h W_r) in float32 at highest
# precision on the very input the program's router had, the largest
# relative distance of a probability over 4,096 tokens x 64 experts: sound
# 1.55e-6 to 5.72e-6 in every run of either initialisation; **the product
# at the TPU's default precision 8.00e-3** (7.28e-3 and 8.21e-3 at 0.02).
# The other families' bound: five times the largest sound reading, 240
# times under the lower precision.
ROUTER_REL_BOUND = 3e-5
# The program's 8 of 64 against the reference's own. Top-k is
# discontinuous and the program's router sees bf16 hidden states that have
# been through a bf16 mixer, so some tokens swap their 8th and 9th expert:
# sound now 0.9972 to 0.9982 of the assignments agree (at 0.02 from
# 0.9929); a window of 2,048 0.695 (at 0.02). The middle of the two.
CHOICES_AGREE_BOUND = 0.85
# ... and every disagreement is a near-tie: the largest gap in the
# reference's probabilities that the program's choice overrode, sound now
# 2.0e-4 to 3.3e-4 (at 0.02 to 9.9e-4); a window of 2,048 0.258 (at 0.02).
# The geometric middle.
NEAR_TIE_BOUND = 1.6e-2
# An attention mixer's output at the cell's 16,384 positions against the
# float32 reference (one masked softmax over whole rows, by query blocks)
# on the mixer's own input, relative L2, by two measures
# (`mixer_distances`), for the timed model's first and last windowed mixer
# (published layers 0 and 2) and its full one (layer 3), on the parameters
# the window left and the batch it trained on (and, in the script, on a
# fresh initialisation).
#
# `MIXER_BOUNDS`: the output the step's own program made (bf16 products and
# a bf16 result; the softmax in float32). It tells wrong mathematics; it
# cannot tell the softmax's precision, which hides under the bf16 products'
# own distance (the softmax in bf16 reads now 6.93e-3, 6.54e-3 and 9.89e-3,
# 7 to 19% over a sound run): `FLOAT32_PARTS_BOUND` does.
MIXER_BOUNDS = {
    # Sound 5.780e-3 to 5.851e-3 under either initialisation (layer 0's
    # input is the normed embedding whatever its size); a window of 2,048
    # 0.574 (at 0.02). The geometric middle.
    "first_windowed": 5.8e-2,
    # Sound now 5.728e-3 to 5.869e-3 (its input is the tokens' own now, as
    # layer 0's; at 0.02 3.349e-3 to 3.406e-3); a window of 2,048 0.332
    # (at 0.02). The geometric middle of the readings at 0.02, which leaves
    # the newer sound reading 5.8 times of room.
    "last_windowed": 3.4e-2,
    # Sound now **8.582e-3 to 9.238e-3** (ten seeds after a window, 8.752e-3
    # fresh; at 0.02 3.916e-3 to 4.815e-3: over tokens of their own the
    # average of 16,384 values is smaller and the bf16 result's rounding a
    # larger part of it, so the bound of 7.5e-3 set at 0.02 failed every
    # sound run and was set again); **`high` and `low` not truncated
    # 4.623e-2 and 4.496e-2** (the ramp's 17 channels turn up to half a percent slower
    # or faster, two radians at 16,384 positions; 1.174e-2 at 0.02), at 0.02
    # the YaRN frequencies without the factor 0.169 and the plain rotary
    # 0.372. The geometric middle of 9.238e-3 and 4.623e-2: 2.24 times of
    # room on either side.
    "first_full": 2.07e-2,
}
# The same module built with float32 products and run at the highest
# precision on the same input and parameters, through the same kernels,
# against the same reference: what is left is float32's own rounding, and
# the bf16 products' 6 to 9e-3 is gone from both sides. Sound now: first
# windowed 7.282e-7 to 7.342e-7, last windowed 5.494e-7 to 5.651e-7, full
# 6.642e-7 to 6.848e-7 (at 0.02 to 7.375e-7). **The scores rounded to bf16
# and the softmax computed in bf16: 4.005e-3, 3.099e-3 and 4.663e-3**,
# 5,400 to 6,900 times a sound run; `high` and `low` not truncated 4.551e-2
# on the full mixer. One bound for the three, 41 times the largest sound
# reading and 100 times under the nearest wrong one: this is the check
# that holds the softmax to float32 and, in the forward pass, the law to
# its numbers.
FLOAT32_PARTS_BOUND = 3e-5
# The timed model's last expert layer (`experts_distance`): bf16 products
# and bf16 expert weights against the float32 sum given the program's
# choice. Sound 4.819e-3 to 4.887e-3 under either initialisation; the
# router at the default precision 5.04e-3 (`ROUTER_REL_BOUND` tells it);
# **weights not renormalised 0.598** (at 0.02). The geometric middle of
# 4.887e-3 and 0.598.
EXPERTS_BOUND = 5.4e-2


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
class MellumJob(HybridJob):
    """``config``: the configuration this instance's reference reads (its
    own ``layer_types``: the probe's are not the cell's);
    ``layers_close(params, extra, tokens)``: its first and last windowed
    attention mixer, its first full one and its last expert layer against
    the reference's, each on its own input."""

    config: dict | None = None
    layers_close: Callable | None = None


def _job(cfg: GPTConfig, config, per_chip_batch, seq_len,
         probe=None) -> MellumJob:
    """The job of one model instance on ``config`` (whose ``layer_types``
    are this instance's); ``probe`` is the small instance its gradients
    are checked on (the probe itself checks nothing)."""
    model = GPT(cfg)

    def init(key):
        params = model.init(key, jnp.zeros((1, seq_len), jnp.int32))["params"]
        # the package draws an embedding at normal(0.02); the file says at
        # what deviation this configuration's is drawn
        return {**params, "embedding": params["embedding"] * (
            config["embedding_std"] / 0.02)}, {}

    def make_batch(key, n_chips):
        return jax.random.randint(
            key, (n_chips * per_chip_batch, seq_len), 0, cfg.vocab_size,
            jnp.int32)

    def loss_and_sown(params, extra, tokens, sow=True):
        # one scope around all of it: JAX writes a transformation's name
        # (jvp, transpose) around the outermost scope, and the readers
        # look for "/lm_head/", "/moe_" and "/attn_" further in
        with jax.named_scope("mellum"):
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

    blocks = {kind: [f"block_{i}" for i, letter in enumerate(
        cfg.layer_pattern) if letter == kind] for kind in "W*E"}
    chosen = {"first_windowed": (blocks["W"][0], WINDOWED),
              "last_windowed": (blocks["W"][-1], WINDOWED),
              "first_full": (blocks["*"][0], FULL)}
    last_experts = blocks["E"][-1]
    # the program's own mixer of either kind with float32 products
    # (`mixer_distances`), as the kinds' records build them
    float32 = dataclasses.replace(cfg, dtype=jnp.float32)
    float32_mixers = {
        WINDOWED: Attention(float32, rotary=True, window=cfg.attn_window),
        FULL: Attention(float32, rotary=cfg.rotary,
                        scaling=cfg.rotary_scaling)}
    # what `layers_close` read on the timed model: the harness hands the
    # window's parameters and batch to `reference_loss` alone and asks
    # `check` for the comparisons afterwards
    timed = []

    def layers_close(params, extra, tokens, trained=False) -> list:
        """The first and the last windowed mixer, the first full one and
        the last expert layer, at the length of ``tokens``, each on the
        input it had in this model's forward pass, against the
        reference's."""
        wanted = {"attn_input", "attn_output", "router_input", "experts"}
        keep = {block for block, _ in chosen.values()} | {last_experts}
        sown = jax.jit(lambda *a: {
            block: {name: value for name, value in s.items()
                    if name in wanted}
            for block, s in loss_and_sown(*a)[1].items() if block in keep})(
                params, extra, tokens)
        before = "trained_" if trained else ""
        length = tokens.shape[1]
        checks = []
        for name, (block, kind) in chosen.items():
            found = mixer_distances(
                sown[block], params[block]["attn"], config, kind,
                float32_mixers[kind])
            for measure, against in (
                    ("mixer", "vs_reference_by_query_blocks"),
                    ("float32_parts", "with_float32_products")):
                far, bound = found[measure], (
                    MIXER_BOUNDS[name] if measure == "mixer"
                    else FLOAT32_PARTS_BOUND)
                checks.append(compare.holds(
                    f"{before}{name}_{measure}_{against}_{length}",
                    math.isfinite(far) and far <= bound,
                    f"{block}: relative L2 of the mixer's output on its own "
                    f"input: {far:.3e}", bound))
        # (the expert layer by its name in the package's module, each
        # time: a builder's script puts a wrong layer there)
        far = experts_distance(
            sown[last_experts], params[last_experts]["moe"], config,
            transformer._expert_layer(cfg))
        checks.append(compare.holds(
            f"{before}last_experts_vs_reference_given_experts_{length}",
            math.isfinite(far) and far <= EXPERTS_BOUND,
            f"{last_experts}: relative L2 of the layer's output on its own "
            f"input: {far:.3e}", EXPERTS_BOUND))
        return checks

    def reference_loss(params, extra, tokens):
        # the timed model's own layers, on the parameters the window left
        # and the batch it trained on
        timed[:] = layers_close(params, extra, tokens, trained=True)
        value, routing = reference.loss(params, tokens, config)
        rows = [int(jnp.sum(held_rows(r["own"], cfg))) for r in routing]
        # (what a round of this share holds is the package's to say)
        _, a_round = moe.held_rows(tokens.size, cfg.experts_per_token,
                                   cfg.experts_held, cfg.n_experts)
        print("at the end of the window, a layer: load (largest group over "
              "the mean of all the router's experts) " + ", ".join(
                  f"{load(r['own'], cfg.n_experts):.3f}" for r in routing)
              + "; rows on the experts held " + ", ".join(map(str, rows))
              + f" in rounds of {a_round}: " + ", ".join(
                  str(-(-n // a_round)) for n in rows) + " round(s)",
              flush=True)
        return value

    def check(key):
        """On the probe (``WE*E`` at the published widths and shares):
        gradients at ``PROBE_SEQ_LEN`` positions against the reference
        given the program's expert indices, the router against a float32
        one on its own input, the two choices of experts against each
        other; then what ``reference_loss`` read of the timed model's own
        layers at the cell's length."""
        if probe is None:
            return []
        marks = [("", time.perf_counter())]
        mark = lambda name: marks.append((name, time.perf_counter()))
        key_sample, key_probe = jax.random.split(key)
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
            params, short, probe.config,
            [sown[block]["experts"] for block in moe_blocks])
        jax.block_until_ready(want)
        mark("the reference's")
        checks = gradients_close(
            f"grad_vs_reference_given_experts_{pattern}_{short.shape[1]}",
            got, want)
        mark("leaf by leaf")
        distance = router_distance(
            routed, params[moe_blocks[0]]["moe"]["router"],
            cfg.experts_per_token)
        checks.append(compare.holds(
            "router_is_float32", distance <= ROUTER_REL_BOUND,
            f"largest |p / p_ref - 1| on the router's own input: "
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
            f"largest probability gap overridden: {gap}", NEAR_TIE_BOUND))
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
    return MellumJob(
        config=config, loss_and_sown=loss_and_sown, item="tokens",
        items_per_step_per_chip=tokens,
        flops_per_item=6.0 * sum(macs.values()),
        init=init, make_batch=make_batch, loss=loss,
        optimizer=lambda: optimizer_from(config["optimizer"]),
        reference_loss=reference_loss, loss_rel_bound=LOSS_REL_BOUND,
        check=check, probe=probe, layers_close=layers_close,
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


def build(config: dict, traffic: dict) -> MellumJob:
    seq_len, batch = traffic["seq_len"], traffic["per_chip_batch"]
    cfg = _model_config(config, seq_len)
    probe_config = {**config, "layer_types": list(PROBE_LAYER_TYPES),
                    "mlp_layer_types": [SPARSE] * len(PROBE_LAYER_TYPES),
                    "num_hidden_layers": len(PROBE_LAYER_TYPES)}
    pattern = layer_pattern(PROBE_LAYER_TYPES)
    probe = _job(dataclasses.replace(
        cfg, n_layers=len(pattern), layer_pattern=pattern),
        probe_config, batch, seq_len)
    return _job(cfg, config, batch, seq_len, probe=probe)
