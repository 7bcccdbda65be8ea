"""The Keye-VL-2.0 family's language model (no vision tower):
``horovod_tpu.models.GPT`` with one mixer a layer, a decoder layer of the
source being two of them (``S`` grouped-query attention over the keys a
learned indexer chooses, with the indexer's own KL loss, then ``E`` a
softmax top-k router over SwiGLU experts, renormalised, no shared expert),
driven by the sizes of a configuration file under the names of the
source's ``config.json``, for **one chip's share** of each layer.

Configuration keys: ``vocab_size``, ``num_hidden_layers``,
``hidden_size``, ``rms_norm_eps``; ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``rope_theta``; ``sa_config``
(``indexer_num_heads``, ``indexer_head_dim``, ``indexer_num_kv_heads``,
``topk``); ``num_experts``, ``num_experts_per_tok``,
``moe_intermediate_size``, ``norm_topk_prob``; plus ``dtype``, ``remat``,
``use_flash``, ``optimizer``. ``num_experts`` and ``vocab_size`` give what
is **held here**; ``published`` holds the model's own counts, from which
the program takes the router's width; ``experts_held_first`` is the first
held expert's number. The attention, the indexer, the router and the norms
are whole. What the package does not build is refused by name. Traffic
keys: ``per_chip_batch``, ``seq_len``.

The step's loss is the language-model loss plus the layers' indexer
losses, which ``GPT`` hands out beside its hidden states
(``return_aux``). The loss never holds the float32 logits whole: the
package's chunked ``ops.losses.softmax_cross_entropy_fused`` multiplies
the last hidden states by the untied ``lm_head`` a chunk of positions at a
time.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time

import jax
import jax.numpy as jnp

from horovod_tpu.models import GPT, GPTConfig
from horovod_tpu.ops.losses import softmax_cross_entropy_fused

from chipbench import compare
from chipbench.families import optimizer_from
from chipbench.families.nemotron_h import HybridJob, _leaf_sums
from chipbench.families.olmoe import compare_choices, load
from chipbench.families.qwen3_next import held_rows, router_distance
from chipbench.reference import keye_vl2 as reference

# What --rehearse shrinks for a CPU dry run (control flow only): a share
# of a small model, two decoder layers, a sequence of which most queries
# choose. Float32, as olmoe's.
REHEARSAL = {
    "config": {
        "vocab_size": 256, "num_hidden_layers": 2, "hidden_size": 64,
        "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
        "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 16},
        "moe_intermediate_size": 32, "num_experts": 4,
        "num_local_experts": 4, "num_experts_per_tok": 3,
        "experts_held_first": 4, "dtype": "float32",
        "published": {"num_hidden_layers": 4, "num_experts": 16,
                      "num_local_experts": 16, "vocab_size": 512}},
    "traffic": {"seq_len": 64, "per_chip_batch": 1}}

# The probe: one decoder layer at the published widths and shares.
PROBE_PATTERN = "SE"
# Positions of the probe's gradient comparison, two sequences cut from the
# sample: half the queries choose (topk 2048), and the program's
# attention, index scores, choice and indexer loss are the Pallas kernels,
# as in the cell.
PROBE_SEQ_LEN = 4096
PROBE_SEQUENCES = 2
# Positions of the sequence whose logits the loss holds at once: 1024 rows
# against the 18992 x 2048 head, 78 MB of float32 logits.
LOSS_CHUNK = 1024


def layer_pattern(n_layers: int) -> str:
    """The source's decoder layers as the package's pattern: layer ``i``
    is its sparse attention and then its experts (``decoder_sparse_step``
    1, ``mlp_only_layers`` [])."""
    return "SE" * n_layers


def chosen_pairs(seq_len: int, topk: int) -> int:
    """``sum_t min(t + 1, topk)``: the (query, key) pairs a head's
    attention requires of one sequence."""
    full = min(topk, seq_len)
    return full * (full + 1) // 2 + (seq_len - full) * topk


def forward_macs_per_token(pattern, d_model, heads, kv_heads, head_dim,
                           index_heads, index_dim, topk, n_experts,
                           experts_held, experts_per_token, d_expert,
                           vocab_size, seq_len) -> dict:
    """Multiply-adds a token of one forward pass over a chip's share, by
    kind of layer and for the head, from shapes. ``experts_held`` and
    ``vocab_size`` are what is held.

    ``S``: the projections ``d (2 H + 2 H_kv) hd``, the indexer's ``d (J e
    + e + J)``, the index scores over **all causal pairs** (the choice
    needs them), ``J e (s + 1) / 2``, and the scores and values over **the
    chosen pairs and not the causal ones, whatever the program executes**,
    ``2 H hd`` each of ``chosen_pairs / s`` keys a query. ``E``: the router
    over all ``n_experts`` and the routed experts at their expectation
    under a uniform router: ``k x held / n_experts`` experts a token, three
    matrices each. The untied head once; the embedding lookup is free."""
    layer = {
        "S": (d_model * (2 * heads + 2 * kv_heads) * head_dim
              + d_model * (index_heads * index_dim + index_dim + index_heads)
              + index_heads * index_dim * (seq_len + 1) / 2
              + 2 * heads * head_dim * chosen_pairs(seq_len, topk) / seq_len),
        "E": (d_model * n_experts + experts_per_token * experts_held
              / n_experts * 3 * d_model * d_expert),
    }
    macs = {kind: pattern.count(kind) * each for kind, each in layer.items()}
    macs["head"] = vocab_size * d_model
    return macs


def n_params(pattern, d_model, heads, kv_heads, head_dim, index_heads,
             index_dim, n_experts, experts_held, d_expert,
             vocab_size) -> int:
    """Embedding, head and final norm; a layer its norm and its mixer."""
    layer = {
        "S": (d_model * (2 * heads + 2 * kv_heads) * head_dim + 2 * head_dim
              + d_model * (index_heads * index_dim + index_dim + index_heads)
              + 2 * index_dim),
        "E": d_model * n_experts + experts_held * 3 * d_model * d_expert,
    }
    return (2 * vocab_size * d_model + d_model
            + sum(layer[kind] + d_model for kind in pattern))


def dsa_core_macs_per_step(layers, batch, heads, seq_len, head_dim, topk,
                           remat) -> float:
    """Multiply-adds a training step requires of the products over the
    chosen pairs (scope ``dsa_core``): ``q k^T`` and ``p v`` in the
    forward pass; the scores again, ``dO v^T``, ``p^T dO``, ``dS k`` and
    ``dS^T q`` in the backward; under ``remat`` the forward pass a second
    time. ``chosen_pairs`` a sequence and head, exactly and not by tiles,
    whatever the program walks."""
    pairs = batch * heads * chosen_pairs(seq_len, topk)
    return layers * pairs * head_dim * ((2 if remat else 1) * 2 + 5)


def dsa_core_bytes_per_step(layers, batch, heads, kv_heads, seq_len,
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


def dsa_select_bytes_per_step(layers, batch, seq_len) -> float:
    """What the choice has to move once a layer and step: a float32 index
    score read and a byte of the mask written for every causal pair."""
    return float(layers * batch * seq_len * (seq_len + 1) / 2 * (4 + 1))


def _model_config(config, seq_len) -> GPTConfig:
    published, sa = config["published"], config["sa_config"]
    for key, want in (("model_type", "KeyeVL2"), ("attention_bias", False),
                      ("hidden_act", "silu"), ("decoder_sparse_step", 1),
                      ("mlp_only_layers", []), ("sliding_window", None),
                      ("use_sliding_window", False),
                      ("tie_word_embeddings", False),
                      ("num_local_experts", config["num_experts"])):
        if config.get(key, want) != want:
            raise ValueError(f"{key} is {config[key]!r}: the package builds "
                             f"{want!r} alone")
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError(
            f"sa_config.indexer_num_kv_heads is "
            f"{sa['indexer_num_kv_heads']}: the package builds one index "
            f"key a position alone")
    pattern = layer_pattern(config["num_hidden_layers"])
    return GPTConfig(
        vocab_size=config["vocab_size"], n_layers=len(pattern),
        layer_pattern=pattern, d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rotary_base=float(config["rope_theta"]),
        dsa_index_heads=sa["indexer_num_heads"],
        dsa_index_dim=sa["indexer_head_dim"], dsa_topk=sa["topk"],
        max_seq_len=seq_len, dtype=jnp.dtype(config["dtype"]),
        remat=config["remat"], use_flash=config["use_flash"],
        tie_embeddings=False, norm_eps=config["rms_norm_eps"],
        mlp_act="swiglu", moe_expert_ff=config["moe_intermediate_size"],
        n_experts=published["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        moe_score="softmax", moe_renormalise=config["norm_topk_prob"],
        experts_held=(config["experts_held_first"], config["num_experts"]))


# The embedding's standard deviation (``assumed.initialisation``), as a
# multiple of the package's 0.02.
EMBEDDING_STD = 1.0


def initialisation(params, n_layers):
    """The configuration's ``assumed.initialisation`` on the package's
    normal(0.02) tree: the embedding at a standard deviation of
    ``EMBEDDING_STD`` (a token's own vector at the scale the layers' norms
    bring everything to) and every projection that writes to the residual
    (the attention's ``o_proj``, the experts' ``down``) times ``1 / sqrt(2
    x n_layers)`` of the model's published depth.

    Why: at normal(0.02) throughout, a share that holds 8 of 128 experts
    and has no shared expert starts with every token's hidden state nearly
    the same vector from the second layer on. The attention's output, a
    mean over two thousand values, is common to all queries, it is 1.3
    times its (normed) input where the embedding is 0.02 of it, and nothing
    a token owns is added for the 15 tokens in 16 whose experts are on
    other chips. Every token then chooses the same experts: a layer's rows
    here were 0 to 18,707 by the seed where the deployment gives 8,192,
    three layers in eight stood at the edge of a second round, and they
    drifted from there (my chip runs, PR 51; PERF.md section 6). The unit
    embedding keeps a token's own part the larger; the writers' scale keeps
    the common part from doubling a layer."""
    scale = 1.0 / math.sqrt(2 * n_layers)

    def one(path, leaf):
        names = {str(getattr(k, "key", k)) for k in path}
        if "embedding" in names:
            return leaf * (EMBEDDING_STD / 0.02)
        return leaf * scale if names & {"o_proj", "down"} else leaf

    return jax.tree_util.tree_map_with_path(one, params)


def _sizes(cfg: GPTConfig, seq_len=None) -> dict:
    """The arguments the two counting functions share."""
    sizes = dict(
        pattern=cfg.layer_pattern, d_model=cfg.d_model, heads=cfg.n_heads,
        kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        index_heads=cfg.dsa_index_heads, index_dim=cfg.dsa_index_dim,
        n_experts=cfg.n_experts, experts_held=cfg.experts_held[1],
        d_expert=cfg.moe_expert_ff, vocab_size=cfg.vocab_size)
    if seq_len is not None:
        sizes.update(topk=cfg.dsa_topk,
                     experts_per_token=cfg.experts_per_token,
                     seq_len=seq_len)
    return sizes


# The forward flash kernel's score sub-block at the cell's shape
# (``ops/flash_attention.py``, ``_PREFERRED_TILE``), for the price of
# walking tiles no query chooses from.
EMPTY_TILE = (512, 1024)


@jax.jit
def _choice_facts(got, scores, own):
    """The program's choice ``got [n, s, s]`` (int8) against the
    reference's ``own`` on the reference's index scores: the share of the
    chosen pairs on which they differ, the largest distance of a differing
    pair's score from the row's threshold (the least score the reference
    chose) over the spread of the row's causal scores, whether every row
    holds ``min(t + 1, topk)`` keys, whether any key is above ``t``, and
    of the forward kernel's causal score sub-blocks (``EMPTY_TILE``) the
    share from which no query of the block chose a key: what skipping
    tiles by occupancy could save."""
    got = got != 0
    s = got.shape[-1]
    rows, keys = (min(n, s) for n in EMPTY_TILE)
    tiles = got.reshape(-1, s // rows, rows, s // keys, keys).any((2, 4))
    walked = (jnp.arange(s // keys)[None, :] * keys
              < (jnp.arange(s // rows)[:, None] + 1) * rows)
    empty = jnp.sum(~tiles & walked) / (tiles.shape[0] * jnp.sum(walked))
    causal = jnp.tril(jnp.ones((s, s), bool))
    threshold = jnp.min(jnp.where(own, scores, jnp.inf), -1, keepdims=True)
    finite = jnp.where(causal, scores, 0.0)
    count = jnp.sum(causal, -1, keepdims=True)
    mean = jnp.sum(finite, -1, keepdims=True) / count
    spread = jnp.sqrt(jnp.sum(jnp.where(causal, jnp.square(scores - mean),
                                        0.0), -1, keepdims=True) / count)
    differ = got != own
    distance = jnp.where(differ, jnp.abs(scores - threshold)
                         / jnp.maximum(spread, 1e-30), 0.0)
    return (jnp.sum(differ) / jnp.sum(own), jnp.max(distance),
            jnp.all(jnp.sum(got, -1) == jnp.sum(own, -1)),
            jnp.any(got & ~causal), empty)


# Every bound below lies between two readings on the chip (v5e, my chip
# runs, PR 51; `benchmarks/keyevl2_wrong_programs.py` makes them again at
# one sequence of 4,096 positions): the largest a sound run gave over its
# seeds (three of that script's and the runs of the cell, whose mixers are
# read at 16,384) and what a lower precision or wrong mathematics gives.
# PERF.md section 6 has the table.
#
# The step's loss (L_LM + L_I) against the float32 reference on the
# parameters a window of training left and the batch it trained on,
# relative to the reference's. Sound: 4.3e-6 to 1.5e-5 after a window,
# 7.1e-6 and 9.9e-6 on a fresh initialisation. The reference itself at the
# TPU's default precision reads 9.7e-7 and 3.1e-6 from the reference: as
# in the other sparse families no lower precision is told from a sound run
# by this loss (the checks below do that), so the bound is no middle of
# two readings: it is `gpt`'s, the accepted cells' one that leaves the
# largest sound reading three times of room and more (67 times here).
LOSS_REL_BOUND = 1e-3
# Gradients of the probe against the reference given the program's expert
# indices and the program's choice of keys, relative L2. Over the whole
# tree: sound 3.08e-3 to 3.49e-3 (the bf16 activations the configuration
# states); no norm a head 1.7e-2, pbar of one head 5.5e-2, L_I dropped
# 0.109, no relu 0.199, a choice without the causal limit 0.416. Near the
# geometric middle of 3.49e-3 and 1.7e-2.
GRAD_REL_L2_BOUND = 7.5e-3
# ... and at the worst leaf, which among the indexer's four is `index_k`
# (its gradient is a sum over every query of terms that cancel a row, made
# from bf16 index products): sound 4.15e-2 to 8.10e-2 over five seeds; no
# norm a head 1.00 (a norm's weight), L_I dropped 1.00 (every indexer
# leaf), a choice without the causal limit 1.48, no relu 1.93, pbar of one
# head 2.09. The geometric middle of 8.10e-2 and 1.00.
GRAD_WORST_LEAF_BOUND = 0.28
# The program's router against softmax(h W_r) in float32 at highest
# precision on the very input the program's router had, the largest
# relative distance of a probability over 8,192 tokens x 128 experts:
# sound 1.8e-6 to 5.4e-6; the same product at the TPU's default precision
# reads 5e-3 in the families that ran it (`deepseek_v3`, `qwen3_next`).
# Their bound.
ROUTER_REL_BOUND = 3e-5
# The program's 8 of 128 against the reference's own: the program's router
# sees bf16 hidden states, so some tokens swap their 8th and 9th expert.
# Sound 0.99693 to 0.99805 of the assignments agree; no wrong program of
# this cell's list moves it (they are the mixer's), so the bound leaves
# the sound readings four times their distance from 1.
CHOICES_AGREE_BOUND = 0.985
# ... and every disagreement is a near-tie: the largest gap in the
# reference's probabilities that the program's choice overrode, sound
# 1.6e-4 to 2.6e-4 (a probability is about 1 / 128).
NEAR_TIE_BOUND = 1e-3
# The program's own index scores are float32: of a sequence's last 128
# rows' scores, the share with a bit set among the low 16 of the mantissa
# (a bf16 has none: scores rounded to bf16 before they are compared read
# 0; float32 scores read 1 - 2^-16 but for exact zeros).
SCORES_WIDE_BOUND = 0.5
# The program's choice of keys against the reference's ``top_k`` on the
# mixer's own input, the share of chosen (query, key) pairs that differ
# (the program's index products take bf16 operands, so keys near a row's
# threshold change sides). Sound at 16,384 positions: the first mixer
# 4.66e-3 to 4.71e-3, the last 1.07e-2 to 1.15e-2 (at 4,096: 1.37e-3 to
# 1.39e-3 and 3.18e-3 to 3.21e-3); no relu 0.140, no choice at all 0.333,
# topk 1,024 0.417, no causal limit 1.00. The geometric middle of 1.15e-2
# and 0.140.
KEYS_DIFFER_BOUND = 4e-2
# ... each a near-tie: the reference's score of a differing pair within
# this many of the row's standard deviations of the row's threshold.
# Sound at 16,384: the first mixer 2.8e-2 to 3.1e-2, the last 6.2e-2 to
# 7.5e-2 (at 4,096: 1.9e-2 to 2.4e-2 and 5.1e-2 to 5.8e-2); no relu 2.67,
# topk 1,024 5.70, no choice at all 6.12. The geometric middle of 7.5e-2
# and 2.67.
KEYS_NEAR_TIE_BOUND = 0.4
# The mixer's output against the reference given the program's choice, on
# the mixer's own input, relative L2 (bf16 products and a bf16 result, the
# heads' norms and the softmax in float32). Sound at 16,384: the first
# mixer 5.76e-3 to 5.80e-3, the last 3.35e-3 to 9.66e-3 (at 4,096:
# 5.67e-3 to 5.73e-3 and 9.67e-3 to 9.98e-3); no norm a head 0.190 to
# 0.200, no causal limit 0.88. The geometric middle of 9.98e-3 and 0.190.
MIXER_REL_L2_BOUND = 4e-2
# ... and its L_I, relative: sound 1.8e-5 to 1.1e-3; no norm a head
# 8.4e-2, L_I dropped 1.00, no relu 2.24, pbar of one head 4.10. The
# geometric middle of 1.1e-3 and 8.4e-2.
INDEX_LOSS_REL_BOUND = 1e-2


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
                                          key=lambda kv: -kv[1])),
          flush=True)
    return [
        compare.Check(name, math.isfinite(whole)
                      and whole <= GRAD_REL_L2_BOUND,
                      f"relative L2 {whole:.3e}", GRAD_REL_L2_BOUND),
        compare.Check(f"{name}_worst_leaf", math.isfinite(by_leaf[worst])
                      and by_leaf[worst] <= GRAD_WORST_LEAF_BOUND,
                      f"{worst}: relative L2 {by_leaf[worst]:.3e}",
                      GRAD_WORST_LEAF_BOUND)]


@functools.partial(jax.jit, static_argnums=2)
def _tail_facts(tail, got, topk):
    """The program's own float32 index scores of a sequence's last rows
    ``tail [n, r, s]`` and its choice ``got [n, s, s]``: the share of those
    scores that a bf16 could not hold (a bit set among the low 16 of the
    mantissa), and whether the choice of those rows is exactly
    ``jax.lax.top_k`` of them (ties to the lower position)."""
    n, r, s = tail.shape
    at = jnp.arange(s - r, s)
    finite = jnp.isfinite(tail)
    low = (jax.lax.bitcast_convert_type(tail, jnp.int32) & 0xffff) != 0
    want = jax.vmap(lambda one: reference.choose(one, at, topk))(tail)
    return (jnp.sum(low & finite) / jnp.sum(finite),
            jnp.all(want == (got[:, s - r:] != 0)))


def mixer_checks(name, sown, p, config) -> list:
    """One sparse-attention mixer of the program against the reference on
    the mixer's own input: the choice against the reference's ``top_k``,
    and the output and ``L_I`` against the reference given the program's
    choice."""
    got = sown["dsa_choice"]
    out, index_loss, (scores, own) = reference.mixer(
        sown["dsa_input"], p, config, got != 0)
    differ, distance, full, above, empty = (
        float(x) for x in _choice_facts(got, scores, own))
    print(f"{name}: of the causal {EMPTY_TILE[0]} x {EMPTY_TILE[1]} score "
          f"sub-blocks the forward kernel walks, the share with no chosen "
          f"key: {empty:.4f}", flush=True)
    far = float(jnp.linalg.norm(sown["dsa_output"].astype(jnp.float32) - out)
                / jnp.linalg.norm(out))
    want = float(jnp.mean(index_loss))
    seq = got.shape[-1]
    wide, exact = _tail_facts(sown["dsa_scores_tail"], got,
                              config["sa_config"]["topk"])
    return [
        compare.holds(f"{name}_index_scores_are_float32",
                      float(wide) >= SCORES_WIDE_BOUND,
                      f"share of the last rows' scores no bf16 holds: "
                      f"{float(wide):.4f}", SCORES_WIDE_BOUND),
        compare.holds(f"{name}_choice_is_top_k_of_its_own_scores",
                      bool(exact), f"exactly, on the last "
                      f"{sown['dsa_scores_tail'].shape[1]} rows: "
                      f"{bool(exact)}"),
        compare.holds(f"{name}_rows_hold_min_t_plus_1_and_topk_keys",
                      bool(full) and not above,
                      f"every row's count right: {bool(full)}; a key above "
                      f"t: {bool(above)}"),
        compare.holds(f"{name}_keys_agree_with_top_k_{seq}",
                      differ <= KEYS_DIFFER_BOUND,
                      f"share of chosen pairs that differ: {differ:.3e}",
                      KEYS_DIFFER_BOUND),
        compare.holds(f"{name}_differing_keys_are_near_ties",
                      distance <= KEYS_NEAR_TIE_BOUND,
                      f"largest |I - tau_t| of a differing pair, in the "
                      f"row's standard deviations: {distance:.3e}",
                      KEYS_NEAR_TIE_BOUND),
        compare.holds(f"{name}_output_vs_reference_given_choice_{seq}",
                      math.isfinite(far) and far <= MIXER_REL_L2_BOUND,
                      f"relative L2 of the mixer's output on its own input: "
                      f"{far:.3e}", MIXER_REL_L2_BOUND),
        compare.close(f"{name}_index_loss_vs_reference_given_choice_{seq}",
                      float(sown["dsa_index_loss"]), want,
                      INDEX_LOSS_REL_BOUND)]


def _job(cfg: GPTConfig, config, per_chip_batch, seq_len,
         probe=None) -> HybridJob:
    """The job of one model instance; ``probe`` is the small instance its
    gradients are checked on (the probe itself checks nothing)."""
    model = GPT(cfg)
    depth = config["published"]["num_hidden_layers"]

    def init(key):
        variables = model.init(key, jnp.zeros((1, seq_len), jnp.int32))
        return initialisation(variables["params"], depth), {}

    def make_batch(key, n_chips):
        return jax.random.randint(
            key, (n_chips * per_chip_batch, seq_len), 0, cfg.vocab_size,
            jnp.int32)

    def loss_and_sown(params, extra, tokens, sow=True):
        # one scope around all of it: JAX writes a transformation's name
        # (jvp, transpose) around the outermost scope, and the readers
        # look for "/lm_head/", "/moe_" and "/dsa_" further in
        with jax.named_scope("keye_vl2"):
            (hidden, aux), sown = model.apply(
                {"params": params}, tokens, return_hidden=True,
                return_aux=True, mutable=["intermediates"] if sow else [])
            ce = softmax_cross_entropy_fused(
                hidden[:, :-1], params["lm_head"], tokens[:, 1:],
                chunk=LOSS_CHUNK)
        sown = {block: {name: value[0] for mixer in kinds.values()
                        for name, value in mixer.items()}
                for block, kinds in sown["intermediates"].items()} \
            if sow else None
        return ce + aux["dsa_index"], sown

    def loss(params, extra, tokens):
        return loss_and_sown(params, extra, tokens, sow=False)[0], extra

    def reference_loss(params, extra, tokens):
        value, (ce, index_loss, routing) = reference.loss(
            params, tokens, config)
        print(f"the reference's L_LM {float(ce)} and L_I (the layers' sum) "
              f"{float(index_loss)}; at the end of the window, a layer: load "
              f"(largest group over the mean of all the router's experts) "
              + ", ".join(f"{load(r['own'], cfg.n_experts):.3f}"
                          for r in routing)
              + "; rows on the experts held " + ", ".join(
                  str(int(jnp.sum(held_rows(r["own"], cfg)))) for r in routing)
              + f" of a round of {tokens.size}", flush=True)
        return value

    def check(key):
        """On the probe (``SE`` at the published widths and shares):
        gradients at ``PROBE_SEQ_LEN`` positions against the reference
        given the program's expert indices and the program's choice of
        keys, the router against a float32 one on its own input, the two
        choices of experts against each other. On a fresh instance of the
        whole model at the cell's length: the first and the last mixer's
        choice against the reference's ``top_k`` on the mixer's own input,
        and their output and ``L_I`` against the reference given the
        program's choice."""
        if probe is None:
            return []
        marks = [("", time.perf_counter())]
        mark = lambda name: marks.append((name, time.perf_counter()))
        key_sample, key_probe, key_whole = jax.random.split(key, 3)
        sample = make_batch(key_sample, 1)[:1]
        length = min(PROBE_SEQ_LEN, seq_len)
        sequences = max(1, min(PROBE_SEQUENCES, seq_len // length))
        short = sample[0, :sequences * length].reshape(sequences, length)
        params, extra = jax.jit(probe.init)(key_probe)
        (_, sown), got = jax.jit(jax.value_and_grad(
            probe.loss_and_sown, has_aux=True))(params, extra, short)
        routed, chose = sown["block_1"], sown["block_0"]["dsa_choice"]
        jax.block_until_ready(got)
        mark("the probe's gradients")
        (_, (_, _, routing)), want = reference.loss_and_grad(
            params, short, config, [routed["experts"]], [chose != 0])
        jax.block_until_ready(want)
        mark("the reference's")
        checks = gradients_close(
            f"grad_vs_reference_given_experts_and_keys_{PROBE_PATTERN}_"
            f"{short.shape[1]}", got, want)
        mark("leaf by leaf")
        distance = router_distance(routed, params["block_1"]["moe"]["router"],
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
        del params, got, want, sown, routed, chose
        params, extra = jax.jit(init)(key_whole)
        _, sown = jax.jit(loss_and_sown)(params, extra, sample)
        mixers = [f"block_{i}" for i, kind in enumerate(cfg.layer_pattern)
                  if kind == "S"]
        for name, block in {"first": mixers[0], "last": mixers[-1]}.items():
            checks += mixer_checks(f"{name}_mixer", sown[block],
                                   params[block]["dsa"], config)
        mark("the first and the last mixer at the cell's length")
        print("seconds of the family's check: " + ", ".join(
            f"{name} {t - t0:.1f}"
            for (_, t0), (name, t) in zip(marks, marks[1:])), flush=True)
        return checks

    macs = forward_macs_per_token(**_sizes(cfg, seq_len))
    tokens = per_chip_batch * seq_len
    rows_expected = (tokens * cfg.experts_per_token * cfg.experts_held[1]
                     / cfg.n_experts)
    dsa = {"layers": cfg.layer_pattern.count("S"), "batch": per_chip_batch,
           "heads": cfg.n_heads, "seq_len": seq_len,
           "head_dim": cfg.head_dim}
    return HybridJob(
        loss_and_sown=loss_and_sown, item="tokens",
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
               # `rows` to `itemsize` are what moe_experts_roofline reads
               "moe": {"layers": cfg.layer_pattern.count("E"),
                       "rows": int(rows_expected),
                       "experts": cfg.experts_held[1],
                       "d_model": cfg.d_model, "d_expert": cfg.moe_expert_ff,
                       "itemsize": jnp.dtype(cfg.dtype).itemsize,
                       "row_bound": tokens * min(cfg.experts_per_token,
                                                 cfg.experts_held[1]),
                       "routed_over": cfg.n_experts},
               # what dsa_core_roofline and dsa_select_roofline read
               "dsa": {**dsa, "kv_heads": cfg.n_kv_heads,
                       "topk": cfg.dsa_topk,
                       "chosen_pairs": chosen_pairs(seq_len, cfg.dsa_topk),
                       "core_macs_per_step": dsa_core_macs_per_step(
                           **dsa, topk=cfg.dsa_topk, remat=cfg.remat),
                       "core_bytes_per_step": dsa_core_bytes_per_step(
                           **dsa, kv_heads=cfg.n_kv_heads, remat=cfg.remat,
                           itemsize=jnp.dtype(cfg.dtype).itemsize),
                       "select_bytes_per_step": dsa_select_bytes_per_step(
                           dsa["layers"], per_chip_batch, seq_len)}})


def build(config: dict, traffic: dict) -> HybridJob:
    seq_len, batch = traffic["seq_len"], traffic["per_chip_batch"]
    cfg = _model_config(config, seq_len)
    probe = _job(dataclasses.replace(
        cfg, n_layers=len(PROBE_PATTERN), layer_pattern=PROBE_PATTERN),
        config, batch, seq_len)
    return _job(cfg, config, batch, seq_len, probe=probe)
