"""The ``sdar`` family (JetLM's SDAR, ``model_type`` ``sdar_moe``):
``horovod_tpu.models.GPT`` with one mixer a layer, a decoder layer of the
source being two of them (``*`` grouped-query attention with a norm a head
on q and k and a plain rotary, then ``E`` a softmax top-k router
renormalised over the chosen, over SwiGLU experts of their own width, no
shared expert and no dense layer: Qwen3-MoE's layer), **trained by block
diffusion** (``GPTConfig.diffusion_block``): a step runs the model once on
a clean and a noised copy of every sequence (``models.noise_blocks``), and
the loss reads the noised rows alone, weighted by ``1 / t`` over the masked
positions (``ops.losses.softmax_cross_entropy_fused`` with ``weights``).
Driven by the sizes of a configuration file under the names of the source's
``config.json``, for **one chip's share** of each layer.

Configuration keys: ``vocab_size``, ``num_hidden_layers``, ``hidden_size``,
``rms_norm_eps``; ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``rope_theta``; ``num_experts``, ``num_experts_per_tok``,
``moe_intermediate_size``, ``norm_topk_prob``; ``block_length``,
``noise_eps``, ``mask_token_id``; plus ``dtype``, ``remat``, ``use_flash``,
``optimizer``, ``embedding_std``. ``num_experts`` and ``vocab_size`` give
what is **held here**; ``published`` holds the model's own counts, from
which the program takes the router's width; ``experts_held_first`` is the
first held expert's number. The attention, the router and the norms are
whole. What the package does not build is refused by name. Traffic keys:
``per_chip_batch``, ``seq_len`` (**data tokens** a sequence: the program's
rows are twice that).

**An item is a data token**: a step of one sequence of ``L`` counts ``L``,
not the ``2 L`` rows the program works. ``forward_macs_per_token`` counts
what the loss depends on and nothing else (it says where).
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import GPT, GPTConfig, moe, noise_blocks, transformer
from horovod_tpu.models.transformer import Attention
from horovod_tpu.ops.losses import softmax_cross_entropy_fused

from chipbench import compare
from chipbench.families import optimizer_from
from chipbench.families.afmoe import attn_window_bytes_per_step
from chipbench.families.mellum import _far, experts_distance
from chipbench.families.nemotron_h import HybridJob, _leaf_sums
from chipbench.families.olmoe import (compare_choices, load,
                                      router_distance)
from chipbench.families.qwen3_next import held_rows
from chipbench.reference import sdar as reference

# What --rehearse shrinks for a CPU dry run (control flow only): a share of
# a small model, two decoder layers, blocks of 4, 4 of 16 experts held with
# 4 a token so that a layer expects one row a program row and works in
# rounds of two, as the cell's do. Float32, as olmoe's.
REHEARSAL = {
    "config": {
        "vocab_size": 256, "num_hidden_layers": 2, "hidden_size": 64,
        "moe_intermediate_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_experts": 4,
        "num_experts_per_tok": 4, "experts_held_first": 4,
        "mask_token_id": 255, "dtype": "float32",
        "published": {"num_hidden_layers": 8, "num_experts": 16,
                      "vocab_size": 2048}},
    "traffic": {"seq_len": 64, "per_chip_batch": 1}}

# Decoder layers of the probe: the first reads the embedding, the second
# the stream a layer has written to.
PROBE_LAYERS = 2
# Data tokens of the probe's gradient comparison (2,048 rows): the
# program's attention is the Pallas kernels, as in the cell, 512 blocks.
PROBE_SEQ_LEN = 1024
# Noised rows whose logits the loss holds at once: 1024 rows against the
# 18992 x 2048 head, 78 MB of float32 logits.
LOSS_CHUNK = 1024


# ---------------------------------------------------------------- counting

def visible_pairs(seq_len: int, block: int) -> dict:
    """The (query, key) pairs a head's attention requires of one sequence
    of ``seq_len`` data tokens in ``n`` blocks of ``block``, exactly:
    ``clean`` rows on the clean keys of their own block and of those
    before it, ``B^2 n (n + 1) / 2``; ``noised`` rows on the clean keys of
    the blocks before theirs, ``B^2 n (n - 1) / 2``, and on the noised keys
    of their own block, ``L B``."""
    n = seq_len // block
    return {"clean": block * block * n * (n + 1) // 2,
            "noised": block * block * n * (n - 1) // 2 + seq_len * block}


def forward_macs_per_token(layers, d_model, heads, kv_heads, head_dim,
                           n_experts, experts_held, experts_per_token,
                           d_expert, vocab_size, seq_len, block) -> dict:
    """Multiply-adds **a data token** of one forward pass over a chip's
    share, by kind of layer and for the head, from shapes: **what the loss
    depends on and nothing else**. ``experts_held`` and ``vocab_size`` are
    what is held.

    A data token is two rows, a clean one and a noised one. ``*``: both
    rows through the projections ``d (2 H + 2 H_kv) hd`` (the query and the
    output a query head, the key and the value a key-value head) and the
    scores and values ``2 H hd`` a visible pair, the pairs exactly
    (``visible_pairs``, whatever tiles the program walks). ``E``: both rows
    through the router over all ``n_experts`` and the routed experts at
    their expectation under a uniform router, ``k x held / n_experts``
    experts a row, three matrices each. **The last layer's clean rows as
    far as their keys and values only**: no later row reads what they
    write, so their query and output projections, their own products over
    positions and their experts are not counted (``last_clean`` is what
    that takes off, as a negative entry). The untied head over the noised
    row alone; the embedding lookup is free; the rotary, the head norms and
    the merge of a noised row's two parts are elementwise and count
    nothing."""
    pairs = visible_pairs(seq_len, block)
    a_pair = 2 * heads * head_dim
    queries = d_model * 2 * heads * head_dim        # q and o, a row
    keys = d_model * 2 * kv_heads * head_dim        # k and v, a row
    experts = (d_model * n_experts + experts_per_token * experts_held
               / n_experts * 3 * d_model * d_expert)
    return {
        "*": layers * (2 * (queries + keys)
                       + a_pair * sum(pairs.values()) / seq_len),
        "E": layers * 2 * experts,
        "last_clean": -(queries + a_pair * pairs["clean"] / seq_len
                        + experts),
        "head": vocab_size * d_model}


def n_params(layers, d_model, heads, kv_heads, head_dim, n_experts,
             experts_held, d_expert, vocab_size) -> int:
    """Embedding, head and final norm; a decoder layer its attention (with
    the two head norms), its router and held experts, and a norm each."""
    attention = d_model * (2 * heads + 2 * kv_heads) * head_dim + 2 * head_dim
    experts = d_model * n_experts + experts_held * 3 * d_model * d_expert
    return (2 * vocab_size * d_model + d_model
            + layers * (attention + experts + 2 * d_model))


def attn_blocks_macs_per_step(layers, batch, heads, seq_len, head_dim, block,
                              remat) -> float:
    """Multiply-adds a training step requires of the diffusion layers'
    products over positions (scope ``attn_blocks``): ``q k^T`` and ``p v``
    in the forward pass; the scores again, ``dO v^T``, ``p^T dO``, ``dS k``
    and ``dS^T q`` in the backward; under ``remat`` the forward pass a
    second time. ``visible_pairs`` a sequence and head, exactly and not by
    tiles, the last layer's clean rows left out (the loss reads nothing of
    them), whatever the program walks."""
    pairs = visible_pairs(seq_len, block)
    required = layers * sum(pairs.values()) - pairs["clean"]
    return float(batch * heads * required * head_dim
                 * ((2 if remat else 1) * 2 + 5))


# ------------------------------------------------------------------- model

def _model_config(config, seq_len) -> GPTConfig:
    published = config["published"]
    for key, want in (("model_type", "sdar_moe"), ("hidden_act", "silu"),
                      ("attention_bias", False),
                      ("tie_word_embeddings", False),
                      ("use_sliding_window", False), ("sliding_window", None),
                      ("rope_scaling", None), ("mlp_only_layers", []),
                      ("decoder_sparse_step", 1)):
        if config.get(key, want) != want:
            raise ValueError(f"{key} is {config[key]!r}: the package builds "
                             f"{want!r} alone")
    if not 0 <= config["mask_token_id"] < config["vocab_size"]:
        raise ValueError(
            f"mask_token_id {config['mask_token_id']} is no row of the "
            f"{config['vocab_size']} held: a sliced vocabulary is a smaller "
            f"vocabulary")
    layers = config["num_hidden_layers"]
    return GPTConfig(
        vocab_size=config["vocab_size"], n_layers=2 * layers,
        layer_pattern="*E" * layers, d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], head_norm=True, rotary=True,
        rotary_base=float(config["rope_theta"]),
        diffusion_block=config["block_length"],
        max_seq_len=2 * seq_len, dtype=jnp.dtype(config["dtype"]),
        remat=config["remat"], use_flash=config["use_flash"],
        tie_embeddings=False, norm_eps=config["rms_norm_eps"],
        moe_expert_ff=config["moe_intermediate_size"],
        n_experts=published["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        moe_score="softmax", moe_renormalise=config["norm_topk_prob"],
        experts_held=(config["experts_held_first"], config["num_experts"]))


def place_the_mask_tokens_experts(params, cfg: GPTConfig, mask_id: int):
    """The routers' columns placed so that this share's load from the mask
    token is its expectation, whatever the seed.

    Every masked position shows one id, so a quarter of a step's rows carry
    one embedding; a fresh model's stream is that embedding (drawn at unit
    deviation, it outweighs what six layers add to it), so in every layer
    all of them choose the same ``k`` experts, and whether those lie among
    the ``count`` held here is the seed's to say: from none to five of
    eight a layer, 4,096 rows each (a step's rows on the held experts 78 to
    112 thousand over 12 seeds where 98 thousand are expected: my chip
    run, PR 64). A deployment places a hot token's experts evenly over its
    chips; this does that for the one token known to be hot: of the ``k``
    experts the mask token's own embedding prefers in a layer, ``k x count
    / n_experts`` (one) get a held slot, the rest of the held slots go to
    the experts it prefers least (far from its ``k``-th place, so that
    what the layers add to the stream moves none of them in), and every
    other expert keeps its rank's order over the slots not held. A router's
    columns are drawn alike and independently, so this is a draw of the
    same initialisation, conditioned on the mask token's load; what other
    tokens choose is untouched in law."""
    first, count = cfg.experts_held
    expected = round(cfg.experts_per_token * count / cfg.n_experts)
    held = np.arange(first, first + count)
    others = np.setdiff1d(np.arange(cfg.n_experts), held)
    token = params["embedding"][mask_id].astype(jnp.float32)
    token = token * jax.lax.rsqrt(jnp.mean(token * token) + cfg.norm_eps)

    def placed(block):
        router = block["moe"]["router"]                     # [d, E]
        ranks = jnp.argsort(-((token * block["norm"]["scale"]) @ router))
        to_held = jnp.concatenate([ranks[:expected],
                                   ranks[cfg.n_experts - count + expected:]])
        rest = ranks[expected:cfg.n_experts - count + expected]
        new = jnp.zeros_like(router).at[:, held].set(router[:, to_held])
        return {**block, "moe": {**block["moe"], "router": new.at[
            :, others].set(router[:, rest])}}

    return {name: placed(block) if "moe" in block else block
            for name, block in params.items()}


def _sizes(cfg: GPTConfig, seq_len=None) -> dict:
    """The arguments the two counting functions share."""
    sizes = dict(
        layers=cfg.n_layers // 2, d_model=cfg.d_model, heads=cfg.n_heads,
        kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        n_experts=cfg.n_experts, experts_held=cfg.experts_held[1],
        d_expert=cfg.moe_expert_ff, vocab_size=cfg.vocab_size)
    if seq_len is not None:
        sizes.update(experts_per_token=cfg.experts_per_token,
                     seq_len=seq_len, block=cfg.diffusion_block)
    return sizes


def mixer_distances(sown, p, config, float32_mixer, positions) -> dict:
    """The program's attention mixer against the reference's (one masked
    softmax over whole rows of ``2 L`` keys, by query blocks in float32) on
    the input the program's mixer had, relative L2 of the output:
    ``"mixer"`` the output the program sowed, bf16 products and all, and
    ``"float32_parts"`` that of ``float32_mixer``, the program's own module
    built with float32 products and run at the highest precision on the
    same input and parameters (at the cell's length through the same
    kernels, the same products of a block on itself, the same merge). In
    the second nothing is left to read but what the configuration states as
    float32 in both (the rotary's phases, the head norms, the softmax, the
    merge) and which keys a row sees: the first cannot see the softmax's
    precision under the bf16 products' own distance."""
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    u = sown["attn_input"]
    want = reference.mixer(u, p, config)
    with jax.default_matmul_precision("highest"):
        again = jax.jit(lambda u, p: float32_mixer.apply(
            {"params": p}, u, positions))(f32(u), f32(p))
    return {"mixer": _far(sown["attn_output"], want),
            "float32_parts": _far(again, want)}


# Every bound below lies between two readings on the chip (v5e, my chip
# runs, PR 64; `benchmarks/sdar_wrong_programs.py` makes them again): the
# largest a sound run gave (eight whole runs of the cell on eight seeds
# after a window of training, and the script's two on a fresh
# initialisation) and what a lower precision or wrong mathematics gives
# (the script, seed 2147600301). PERF.md section 6 has the table.
#
# The step's loss against the float32 reference on the parameters a window
# of training left and the batch it trained on, relative to the
# reference's. Sound 1.1e-6 to 3.0e-5. A fresh model's loss is about ln
# 18,992 times the weights' mean whatever its mixers do, so this tells
# little of them (the checks below tell the rest) and much of the
# objective: weights without 1 / t move it by half and targets shifted by
# one by 2% (`tests/test_diffusion_blocks.py`). `gpt`'s bound, the accepted
# cells' one, 33 times the largest sound reading.
LOSS_REL_BOUND = 1e-3
# Gradients of the probe against the reference given the program's expert
# indices, relative L2. Over the whole tree: sound 4.71e-3 to 6.39e-3 (ten
# seeds); **a causal mask inside a block 5.88e-2** (the softmax in bf16
# 5.49e-3 and the router at the default precision 5.30e-3, which this check
# is not asked to tell). The geometric middle of 6.39e-3 and 5.88e-2: three
# times of room on either side.
GRAD_REL_L2_BOUND = 2e-2
# ... and at the worst leaf (a head norm's or a router's weight, which
# goes with the seed): sound 1.15e-2 to 4.60e-2, and 5.67e-2 with the router
# at the default precision, which this check is not asked to tell; a causal
# mask inside a block 0.189, which the whole tree tells three times over.
# Three times the largest sound reading.
GRAD_WORST_LEAF_BOUND = 0.14
# The program's router against softmax(h W_r) in float32 at highest
# precision on the very input the program's router had, the largest
# relative distance of a probability over 2,048 rows x 128 experts: sound
# 1.67e-6 to 3.46e-6; **the product at the TPU's default precision
# 7.09e-3**. The other families' bound, nine times the largest sound
# reading and 240 times under the lower precision.
ROUTER_REL_BOUND = 3e-5
# The program's 8 of 128 against the reference's own: top-k is
# discontinuous and the program's router sees bf16 hidden states that have
# been through a bf16 mixer, so some rows swap their 8th and 9th expert:
# sound 0.9958 to 0.9979 of the assignments agree (a causal mask inside a
# block 0.9930: this one tells no mask).
CHOICES_AGREE_BOUND = 0.85
# ... and every disagreement is a near-tie: the largest gap in the
# reference's probabilities that the program's choice overrode, sound
# 1.3e-4 to 1.9e-4; a causal mask inside a block 2.57e-2. The other
# families' bound, near the geometric middle of the two (2.2e-3) on the
# sound side's far end.
NEAR_TIE_BOUND = 1.6e-2
# The first and the last attention mixer's output at the cell's 2 x 8,192
# rows against the float32 reference on the mixer's own input, relative L2,
# as the step's own program made it (bf16 products and a bf16 result; the
# softmax and the merge in float32): sound 5.77e-3 to 5.94e-3 (first) and
# 3.38e-3 to 3.76e-3 (last). It tells wrong mathematics (**a causal mask
# inside a block 0.217** on the first mixer, 2.41e-2 on the last, whose
# input is nearer the embedding's own; a copy turned at the wrong position
# 0.17 on the logits at a small size, `tests/test_diffusion_blocks.py`); it
# cannot tell the softmax's precision, which hides under the bf16 products'
# own distance (the softmax in bf16 6.82e-3 and 3.96e-3):
# `FLOAT32_PARTS_BOUND` does. Five times the largest sound reading, seven
# times under the wrong mask on the first mixer.
MIXER_BOUND = 3e-2
# ... and built again with float32 products at the highest precision,
# through the same kernels, the same products of a block on itself and the
# same merge: what is left is float32's own rounding over 16,384 keys.
# Sound 7.60e-6 to 1.04e-5 (first) and 1.73e-6 to 2.32e-6 (last). **The
# scores rounded to bf16 and the softmax in bf16: 3.63e-3 and 1.72e-3**; a
# causal mask inside a block 0.217 and 2.38e-2. This is the check that holds
# the softmax and the merge to float32 and the mask to its rule, in both
# mixers: near the geometric middle of 1.04e-5 and 1.72e-3 (1.3e-4), 19
# times the largest sound reading and 8.6 times under the nearest wrong
# one.
FLOAT32_PARTS_BOUND = 2e-4
# The timed model's last expert layer: bf16 products and bf16 expert
# weights against the float32 sum given the program's choice. Sound
# 4.80e-3 to 4.99e-3; weights not renormalised reads 0.6 on this
# comparison in the cell beside this one (`mellum2-s16384`, PR 61), whose
# bound this is.
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
class SdarJob(HybridJob):
    """``config``: the configuration this instance's reference reads;
    ``layers_close(params, extra, batch)``: its first and last attention
    mixer and its last expert layer against the reference's, each on its
    own input."""

    config: dict | None = None
    layers_close: Callable | None = None


def _job(cfg: GPTConfig, config, per_chip_batch, seq_len,
         probe=None) -> SdarJob:
    """The job of one model instance on ``config``; ``probe`` is the small
    instance its gradients are checked on (the probe itself checks
    nothing)."""
    model = GPT(cfg)
    size, mask_id = cfg.diffusion_block, config["mask_token_id"]

    def init(key):
        params = model.init(
            key, jnp.zeros((1, 2 * seq_len), jnp.int32))["params"]
        # the package draws an embedding at normal(0.02); the file says at
        # what deviation this configuration's is drawn
        params = {**params, "embedding": params["embedding"] * (
            config["embedding_std"] / 0.02)}
        return place_the_mask_tokens_experts(params, cfg, mask_id), {}

    def make_batch(key, n_chips, length=seq_len):
        """Ids uniform over the vocabulary held but the mask id (its last
        row), noised by the package's function: the ``[b, 2 L]`` tokens
        the model takes, the targets and the weights the loss takes."""
        key_ids, key_noise = jax.random.split(key)
        ids = jax.random.randint(
            key_ids, (n_chips * per_chip_batch, length), 0, mask_id,
            jnp.int32)
        tokens, targets, weights = noise_blocks(
            key_noise, ids, size, mask_id, config["noise_eps"])
        return {"tokens": tokens, "targets": targets, "weights": weights}

    def loss_and_sown(params, extra, batch, sow=True):
        # one scope around all of it: JAX writes a transformation's name
        # (jvp, transpose) around the outermost scope, and the readers
        # look for "/lm_head/", "/moe_" and "/attn_" further in
        with jax.named_scope("sdar"):
            hidden, sown = model.apply(
                {"params": params, **extra}, batch["tokens"],
                return_hidden=True, mutable=["intermediates"] if sow else [])
            ce = softmax_cross_entropy_fused(
                hidden, params["lm_head"], batch["targets"],
                chunk=LOSS_CHUNK, weights=batch["weights"])
        sown = {block: {name: value[0] for mixer in kinds.values()
                        for name, value in mixer.items()}
                for block, kinds in sown["intermediates"].items()} \
            if sow else None
        return ce, sown

    def loss(params, extra, batch):
        return loss_and_sown(params, extra, batch, sow=False)[0], extra

    blocks = {kind: [f"block_{i}" for i, letter in enumerate(
        cfg.layer_pattern) if letter == kind] for kind in "*E"}
    chosen = {"first_attention": blocks["*"][0],
              "last_attention": blocks["*"][-1]}
    last_experts = blocks["E"][-1]
    # the program's own mixer with float32 products (`mixer_distances`), as
    # the kind's record builds it
    float32_mixer = Attention(dataclasses.replace(cfg, dtype=jnp.float32),
                              rotary=True)
    # what `layers_close` read on the timed model: the harness hands the
    # window's parameters and batch to `reference_loss` alone and asks
    # `check` for the comparisons afterwards
    timed = []

    def layers_close(params, extra, batch, trained=False) -> list:
        """The first and the last attention mixer and the last expert
        layer, at the length of ``batch``, each on the input it had in this
        model's forward pass, against the reference's."""
        wanted = {"attn_input", "attn_output", "router_input", "experts"}
        keep = set(chosen.values()) | {last_experts}
        sown = jax.jit(lambda *a: {
            block: {name: value for name, value in s.items()
                    if name in wanted}
            for block, s in loss_and_sown(*a)[1].items() if block in keep})(
                params, extra, batch)
        before = "trained_" if trained else ""
        rows = batch["tokens"].shape[1]
        positions = jnp.broadcast_to(
            jnp.tile(jnp.arange(rows // 2), 2), batch["tokens"].shape)
        checks = []
        for name, block in chosen.items():
            found = mixer_distances(sown[block], params[block]["attn"],
                                    config, float32_mixer, positions)
            for measure, against, bound in (
                    ("mixer", "vs_reference_by_query_blocks", MIXER_BOUND),
                    ("float32_parts", "with_float32_products",
                     FLOAT32_PARTS_BOUND)):
                far = found[measure]
                checks.append(compare.holds(
                    f"{before}{name}_{measure}_{against}_{rows}",
                    math.isfinite(far) and far <= bound,
                    f"{block}: relative L2 of the mixer's output on its own "
                    f"input: {far:.3e}", bound))
        # (the expert layer by its name in the package's module, each
        # time: a builder's script puts a wrong layer there)
        far = experts_distance(
            sown[last_experts], params[last_experts]["moe"], config,
            transformer._expert_layer(cfg))
        checks.append(compare.holds(
            f"{before}last_experts_vs_reference_given_experts_{rows}",
            math.isfinite(far) and far <= EXPERTS_BOUND,
            f"{last_experts}: relative L2 of the layer's output on its own "
            f"input: {far:.3e}", EXPERTS_BOUND))
        return checks

    def reference_loss(params, extra, batch):
        # the timed model's own layers, on the parameters the window left
        # and the batch it trained on
        timed[:] = layers_close(params, extra, batch, trained=True)
        value, routing = reference.loss(params, batch, config)
        rows = [int(jnp.sum(held_rows(r["own"], cfg))) for r in routing]
        # (what a round of this share holds is the package's to say)
        _, a_round = moe.held_rows(batch["tokens"].size,
                                   cfg.experts_per_token, cfg.experts_held,
                                   cfg.n_experts)
        print("at the end of the window, a layer: load (largest group over "
              "the mean of all the router's experts) " + ", ".join(
                  f"{load(r['own'], cfg.n_experts):.3f}" for r in routing)
              + "; rows on the experts held " + ", ".join(map(str, rows))
              + f" in rounds of {a_round}: " + ", ".join(
                  str(-(-n // a_round)) for n in rows) + " round(s); "
              f"positions masked {int(jnp.sum(batch['weights'] > 0))} of "
              f"{batch['weights'].size}", flush=True)
        return value

    def check(key):
        """On the probe (two decoder layers at the published widths and
        shares): gradients at ``PROBE_SEQ_LEN`` data tokens against the
        reference given the program's expert indices, the router against a
        float32 one on its own input, the two choices of experts against
        each other; then what ``reference_loss`` read of the timed model's
        own layers at the cell's length."""
        if probe is None:
            return []
        marks = [("", time.perf_counter())]
        mark = lambda name: marks.append((name, time.perf_counter()))
        key_sample, key_probe = jax.random.split(key)
        short = jax.jit(lambda k: probe.make_batch(
            k, 1, min(PROBE_SEQ_LEN, seq_len)))(key_sample)
        short = jax.tree.map(lambda a: a[:1], short)
        params, extra = jax.jit(probe.init)(key_probe)
        (_, sown), got = jax.jit(jax.value_and_grad(
            probe.loss_and_sown, has_aux=True))(params, extra, short)
        moe_blocks = [f"block_{i}" for i, kind in enumerate(
            probe.facts["pattern"]) if kind == "E"]
        routed = sown[moe_blocks[0]]
        jax.block_until_ready(got)
        mark("the probe's gradients")
        (_, routing), want = reference.loss_and_grad(
            params, short, probe.config,
            [sown[block]["experts"] for block in moe_blocks])
        jax.block_until_ready(want)
        mark("the reference's")
        checks = gradients_close(
            f"grad_vs_reference_given_experts_{probe.facts['pattern']}_"
            f"{short['tokens'].shape[1]}", got, want)
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
    rows = 2 * tokens
    rows_expected = (rows * cfg.experts_per_token * cfg.experts_held[1]
                     / cfg.n_experts)
    shape = {"layers": cfg.n_layers // 2, "batch": per_chip_batch,
             "heads": cfg.n_heads, "seq_len": seq_len,
             "head_dim": cfg.head_dim}
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return SdarJob(
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
               # router's expectation (both copies of every token go
               # through every expert layer), the stacks held, one
               # product's sizes
               "moe": {"layers": cfg.layer_pattern.count("E"),
                       "rows": int(rows_expected),
                       "experts": cfg.experts_held[1],
                       "d_model": cfg.d_model, "d_expert": cfg.moe_expert_ff,
                       "itemsize": itemsize,
                       "row_bound": rows * min(cfg.experts_per_token,
                                               cfg.experts_held[1]),
                       "routed_over": cfg.n_experts},
               # what attn_blocks_roofline reads: the sizes of the diffusion
               # layers' products over positions and what a step requires;
               # the bytes are two kernel calls' a layer, each over one
               # copy's positions
               "attn_blocks": {
                   **shape, "kv_heads": cfg.n_kv_heads, "block": size,
                   "visible_pairs": visible_pairs(seq_len, size),
                   "macs_per_step": attn_blocks_macs_per_step(
                       **shape, block=size, remat=cfg.remat),
                   "bytes_per_step": attn_window_bytes_per_step(
                       **{**shape, "layers": 2 * shape["layers"]},
                       kv_heads=cfg.n_kv_heads, remat=cfg.remat,
                       itemsize=itemsize)}})


def build(config: dict, traffic: dict) -> SdarJob:
    seq_len, batch = traffic["seq_len"], traffic["per_chip_batch"]
    cfg = _model_config(config, seq_len)
    probe_config = {**config, "num_hidden_layers": PROBE_LAYERS}
    probe = _job(dataclasses.replace(
        cfg, n_layers=2 * PROBE_LAYERS, layer_pattern="*E" * PROBE_LAYERS),
        probe_config, batch, seq_len)
    return _job(cfg, config, batch, seq_len, probe=probe)
