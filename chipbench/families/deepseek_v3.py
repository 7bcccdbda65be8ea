"""The DeepSeek-V3 family (Kanana-2 is a model of its ``model_type``):
``horovod_tpu.models.GPT`` with one mixer a layer, a decoder layer of the
source being two of them (``L`` multi-head latent attention, then ``-`` a
SwiGLU MLP in the leading dense layers and ``E`` a sigmoid top-k router
with a choice bias over SwiGLU experts of their own width, with the shared
experts as one ungated SwiGLU, in the others), driven by the sizes of a
configuration file under the names of the source's ``config.json``, for
**one chip's share** of each layer.

Configuration keys: ``vocab_size``, ``num_hidden_layers``,
``first_k_dense_replace``, ``hidden_size``, ``rms_norm_eps``;
``num_attention_heads``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``rope_theta``;
``intermediate_size``; ``n_routed_experts``, ``num_experts_per_tok``,
``moe_intermediate_size``, ``n_shared_experts``, ``norm_topk_prob``,
``routed_scaling_factor``; plus ``dtype``, ``remat``, ``use_flash``,
``optimizer``. ``n_routed_experts`` and ``vocab_size`` give what is
**held here**; ``published`` holds the model's own counts, from which the
program takes the router's width; ``experts_held_first`` is the first
held expert's number. The attention, the dense MLP, the router, the
shared expert and the norms are whole. What the package does not build is
refused by name. Traffic keys: ``per_chip_batch``, ``seq_len``.

The loss never holds the float32 logits whole: the model returns its last
hidden states and the package's chunked
``ops.losses.softmax_cross_entropy_fused`` multiplies them by the untied
``lm_head`` a chunk of positions at a time.
"""

from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp

from horovod_tpu.models import GPT, GPTConfig
from horovod_tpu.ops.losses import softmax_cross_entropy_fused

from chipbench import compare
from chipbench.families import optimizer_from
from chipbench.families.nemotron_h import (HybridJob, _leaf_sums,
                                           router_distance)
from chipbench.families.olmoe import compare_choices, load
from chipbench.families.qwen3_next import held_rows
from chipbench.reference import deepseek_v3 as reference

# What --rehearse shrinks for a CPU dry run (control flow only): a share
# of a small model with all three kinds of layer (three decoder layers,
# the first dense), the query-key width apart from the value width.
# Float32, as olmoe's.
REHEARSAL = {
    "config": {
        "vocab_size": 256, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "hidden_size": 64,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "qk_head_dim": 24, "v_head_dim": 16, "n_routed_experts": 4,
        "num_experts_per_tok": 3, "n_shared_experts": 2,
        "experts_held_first": 4, "dtype": "float32",
        "published": {"num_hidden_layers": 6, "n_routed_experts": 16,
                      "vocab_size": 512}},
    "traffic": {"seq_len": 64, "per_chip_batch": 2}}

# Sequences on which the probe instance is compared.
SAMPLE_SEQUENCES = 2
# The probe: the leading dense layer and one expert layer, each behind its
# latent attention, at the published widths and shares.
PROBE_PATTERN = "L-LE"
# Positions of the probe's gradient comparison: the reference's backward
# pass holds a sequence's float32 [s, 6144] dense MLP and 256 queries'
# [32, 256, s] scores at a time; from 1024 up the program's attention is
# the Pallas kernels, as in the cell.
PROBE_SEQ_LEN = 2048
# Positions of the sequence whose logits the loss holds at once: 2 x 1024
# rows against the 16032 x 2048 head, 131 MB of float32 logits.
LOSS_CHUNK = 1024


def layer_pattern(n_layers: int, n_dense: int) -> str:
    """The source's decoder layers as the package's pattern: layer ``i``
    is its latent attention and then its feed-forward, the dense MLP in
    the first ``n_dense`` layers (``first_k_dense_replace``) and the
    experts after them (``moe_layer_freq`` 1)."""
    return "".join("L" + ("-" if i < n_dense else "E")
                   for i in range(n_layers))


def forward_macs_per_token(pattern, d_model, heads, rank, nope, rope, value,
                           d_ff, n_experts, experts_held, experts_per_token,
                           d_expert, d_shared, vocab_size, seq_len) -> dict:
    """Multiply-adds a token of one forward pass over a chip's share, by
    kind of layer and for the head, from shapes (``chipbench/flops.py``
    counts no latent attention and no gated MLP). ``experts_held`` and
    ``vocab_size`` are what is held.

    ``L``: the projections ``d heads (n + e)``, ``d (r + e)``, ``r heads
    (n + v)`` and ``heads v d``, and the two products over positions at
    their own widths, ``heads (n + e)`` for the scores and ``heads v`` for
    the values, over the ``(s + 1) / 2`` positions a query sees on average
    (the causal pairs exactly, not tiles; the published arithmetic: the
    key is up-projected, no matrix is absorbed). ``-``: three matrices ``3
    d d_ff``. ``E``: the router over all ``n_experts``, the shared expert's
    three matrices and the routed experts at their expectation under a
    uniform router: ``k x held / n_experts`` experts a token, three
    matrices each. The untied head once; the embedding lookup is free."""
    layer = {
        "L": (d_model * heads * (nope + rope) + d_model * (rank + rope)
              + rank * heads * (nope + value) + heads * value * d_model
              + heads * (nope + rope + value) * (seq_len + 1) / 2),
        "-": 3 * d_model * d_ff,
        "E": (d_model * n_experts + 3 * d_model * d_shared
              + experts_per_token * experts_held / n_experts
              * 3 * d_model * d_expert),
    }
    macs = {kind: pattern.count(kind) * each for kind, each in layer.items()}
    macs["head"] = vocab_size * d_model
    return macs


def n_params(pattern, d_model, heads, rank, nope, rope, value, d_ff,
             n_experts, experts_held, d_expert, d_shared, vocab_size) -> int:
    """Embedding, head and final norm; a layer its norm and its mixer."""
    layer = {
        "L": (d_model * heads * (nope + rope) + d_model * (rank + rope)
              + rank + rank * heads * (nope + value)
              + heads * value * d_model),
        "-": 3 * d_model * d_ff,
        "E": (d_model * n_experts + 3 * d_model * d_shared
              + experts_held * 3 * d_model * d_expert),
    }
    return (2 * vocab_size * d_model + d_model
            + sum(layer[kind] + d_model for kind in pattern))


def mla_core_macs_per_step(layers, batch, heads, seq_len, qk_dim, v_dim,
                           remat) -> float:
    """Multiply-adds a training step requires of the products over
    positions (scope ``mla_core``), counted over the ``s (s + 1) / 2``
    visible pairs of a sequence and head, exactly and not by tiles. The
    forward pass makes ``q k^T`` at ``qk_dim`` and ``p v`` at ``v_dim`` a
    pair; the backward pass makes the scores again at ``qk_dim``, ``dO
    v^T`` and ``p^T dO`` at ``v_dim`` and ``dS k`` and ``dS^T q`` at
    ``qk_dim``; under ``remat`` the forward pass runs a second time."""
    forward = qk_dim + v_dim
    backward = 3 * qk_dim + 2 * v_dim
    pairs = batch * heads * seq_len * (seq_len + 1) / 2
    return layers * pairs * ((2 if remat else 1) * forward + backward)


def mla_core_bytes_per_step(layers, batch, heads, seq_len, qk_dim, v_dim,
                            remat, itemsize=2) -> float:
    """Bytes the same products have to move once a call, a position of a
    sequence and head: the forward reads q and k (``qk_dim``) and v and
    writes o (``v_dim``) and a float32 log-sum-exp; the backward reads q,
    k, v, dO and two float32 statistics and writes dQ, dK and dV. The key
    counted a head, as the program assembles it."""
    forward = itemsize * (2 * qk_dim + 2 * v_dim) + 4
    backward = itemsize * (4 * qk_dim + 3 * v_dim) + 8
    return float(layers * batch * heads * seq_len
                 * ((2 if remat else 1) * forward + backward))


def _model_config(config, seq_len) -> GPTConfig:
    published = config["published"]
    for key, want in (("model_type", "deepseek_v3"), ("q_lora_rank", None),
                      ("n_group", 1), ("topk_group", 1),
                      ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("rope_scaling", None),
                      ("rope_interleave", True), ("attention_bias", False),
                      ("hidden_act", "silu"), ("moe_layer_freq", 1),
                      ("tie_word_embeddings", False),
                      ("num_key_value_heads", config["num_attention_heads"]),
                      ("qk_head_dim", config["qk_nope_head_dim"]
                       + config["qk_rope_head_dim"])):
        if config.get(key, want) != want:
            raise ValueError(f"{key} is {config[key]!r}: the package builds "
                             f"{want!r} alone")
    pattern = layer_pattern(config["num_hidden_layers"],
                            config["first_k_dense_replace"])
    return GPTConfig(
        vocab_size=config["vocab_size"], n_layers=len(pattern),
        layer_pattern=pattern, d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        mla_kv_rank=config["kv_lora_rank"],
        mla_nope_dim=config["qk_nope_head_dim"],
        mla_rope_dim=config["qk_rope_head_dim"],
        mla_value_dim=config["v_head_dim"],
        rotary_base=float(config["rope_theta"]), max_seq_len=seq_len,
        dtype=jnp.dtype(config["dtype"]), remat=config["remat"],
        use_flash=config["use_flash"], tie_embeddings=False,
        norm_eps=config["rms_norm_eps"], mlp_act="swiglu",
        d_ff=config["intermediate_size"],
        moe_expert_ff=config["moe_intermediate_size"],
        n_experts=published["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"],
        moe_score="sigmoid", moe_renormalise=config["norm_topk_prob"],
        moe_route_scale=float(config["routed_scaling_factor"]),
        moe_shared_ff=(config["n_shared_experts"]
                       * config["moe_intermediate_size"]),
        experts_held=(config["experts_held_first"],
                      config["n_routed_experts"]))


def _sizes(cfg: GPTConfig, seq_len=None) -> dict:
    """The arguments the two counting functions share."""
    sizes = dict(
        pattern=cfg.layer_pattern, d_model=cfg.d_model, heads=cfg.n_heads,
        rank=cfg.mla_kv_rank, nope=cfg.mla_nope_dim, rope=cfg.mla_rope_dim,
        value=cfg.mla_value_dim, d_ff=cfg.d_ff, n_experts=cfg.n_experts,
        experts_held=cfg.experts_held[1], d_expert=cfg.moe_expert_ff,
        d_shared=cfg.moe_shared_ff, vocab_size=cfg.vocab_size)
    if seq_len is not None:
        sizes.update(experts_per_token=cfg.experts_per_token,
                     seq_len=seq_len)
    return sizes


def mixer_distance(sown, p, config) -> float:
    """The program's latent-attention mixer against the reference's, by
    query blocks in float32, on the input the program's mixer had:
    relative L2 of the output over every sequence."""
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda u, p: jax.lax.map(
            lambda one: reference.latent_attention(one, p, config),
            u.astype(jnp.float32)))(
                sown["mla_input"], jax.tree.map(
                    lambda a: a.astype(jnp.float32), p))
    got = sown["mla_output"].astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# Every bound below lies between two readings on the chip (v5e, my chip
# runs, PR 48; `benchmarks/kanana2_wrong_programs.py` makes them again):
# the largest a sound run gave over its seeds (three of that script's and
# fourteen runs of the cell, seven of them on the final tree), and what a lower precision or wrong mathematics
# gives. PERF.md section 6 has the table.
#
# The step's loss against the float32 reference on the parameters a
# window of training left and the batch it trained on, relative to the
# reference's (the loss falls from 10.1 to 9.5 in a window at this
# learning rate). Sound: 1.6e-6 to 5.9e-5 after a window (14 runs), 3.1e-6
# and 5.1e-5 on a fresh initialisation. The reference itself at the TPU's
# default precision reads 1.2e-5 and 2.4e-5 from the reference: as in
# `nemotron_h`, `qwen3_next` and `lfm2_moe`, no lower precision is told
# from a sound run by this loss (the checks below do that), so the bound
# is no middle of two readings: it is `gpt`'s, the accepted cells' one
# that leaves the largest sound reading three times of room and more (17
# times here).
LOSS_REL_BOUND = 1e-3
# Gradients of the probe against the reference given the program's expert
# indices, relative L2. Over the whole tree: sound 1.224e-2 to 1.248e-2
# (the bf16 activations the configuration states; 17 seeds within 2%);
# the route scale at 1 0.106, the latent's norm left out 0.138, the chosen
# weights not renormalised 0.183, the scale at 128^-1/2 0.224, the rotary
# on halves without the permutation 0.700, the rotated key taken a head
# 0.834. Near the geometric middle of 1.248e-2 and 0.106.
GRAD_REL_L2_BOUND = 3.5e-2
# ... and at the worst leaf, which is what a wrong expert layer or mixer
# moves while the head's and the embedding's gradients carry the tree's
# norm: sound 1.79e-2 to 1.89e-2 (the second attention's `q_proj`); the
# scale at 128^-1/2 0.388, the route scale at 1 0.586, the latent's norm
# left out 1.00, the rotary without the permutation 1.04, the key a head
# 1.24, not renormalised 1.26. The geometric middle of 1.89e-2 and 0.388.
GRAD_WORST_LEAF_BOUND = 8.5e-2
# The program's router against sigmoid(h W_r) in float32 at highest
# precision on the very input the program's router had, the largest
# relative distance of a score over 4,096 tokens x 128 experts: sound
# 1.19e-7 (one unit in the last place) in every run; the product at the
# TPU's default precision 5.28e-3. The geometric middle. This is the
# check that holds the router to float32.
ROUTER_REL_BOUND = 3e-5
# The program's 6 of 128 against the reference's own. Top-k is
# discontinuous and the program's router sees bf16 hidden states that
# have been through bf16 mixers, so some tokens swap their 6th and 7th
# expert: sound 0.98897 to 0.99150 of the assignments agree; the latent's
# norm left out 0.9012, the scale at 128^-1/2 0.852, the rotary without
# the permutation 0.560, the key a head 0.482. The middle of 0.9012 and
# 0.98897.
CHOICES_AGREE_BOUND = 0.945
# ... and every disagreement is a near-tie: the largest gap in the
# reference's scores that the program's choice overrode (a score is
# between 0 and 1), sound 4.2e-3 to 6.9e-3; the latent's norm left out
# 7.5e-2, the scale at 128^-1/2 0.124, the rotary without the permutation
# 0.502, the key a head 0.635. Near the geometric middle of 6.9e-3 and
# 7.5e-2 (2.3e-2): a tail statistic over some 250 disagreements, so the
# bound leaves it 2.9 times the largest seen.
NEAR_TIE_BOUND = 2e-2
# The latent-attention mixer's output at the cell's 8192 positions against
# the float32 reference by query blocks on the mixer's own input, relative
# L2: sound 5.166e-3 to 5.281e-3 (bf16 products and a bf16 result, the
# latent's norm and the softmax in float32; 17 seeds within 2.2%); **the
# scores rounded to bf16 and the softmax computed in bf16 5.599e-3**; the
# latent's norm left out 0.125, the scale at 128^-1/2 0.154, the rotary
# without the permutation 0.405, the key a head 0.555. The geometric
# middle of 5.281e-3 and 5.599e-3: 2.8% of room above the largest sound
# reading, which is four times the seeds' whole range. This is the check
# that holds the softmax and the latent's norm to float32 (no other tells
# a bf16 softmax from a sound run: its gradients read 1.28e-2 and 1.92e-2).
MIXER_REL_L2_BOUND = 5.43e-3


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
                                          key=lambda kv: -kv[1])[:6]),
          flush=True)
    return [
        compare.Check(name, math.isfinite(whole)
                      and whole <= GRAD_REL_L2_BOUND,
                      f"relative L2 {whole:.3e}", GRAD_REL_L2_BOUND),
        compare.Check(f"{name}_worst_leaf", math.isfinite(by_leaf[worst])
                      and by_leaf[worst] <= GRAD_WORST_LEAF_BOUND,
                      f"{worst}: relative L2 {by_leaf[worst]:.3e}",
                      GRAD_WORST_LEAF_BOUND)]


def _job(cfg: GPTConfig, config, per_chip_batch, seq_len,
         probe=None) -> HybridJob:
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
        # look for "/lm_head/", "/moe_", "/mla_" and "/dense_mlp/"
        # further in
        with jax.named_scope("deepseek_v3"):
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

    def reference_loss(params, extra, tokens):
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
        """On the probe (``L-LE`` at the published widths and shares):
        gradients at ``PROBE_SEQ_LEN`` positions against the reference
        given the program's expert indices, the router against a float32
        one on its own input, the two choices of experts against each
        other, and the first latent-attention mixer at the cell's length
        against the reference by query blocks on its own input."""
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
        moe_block = f"block_{PROBE_PATTERN.index('E')}"
        mla_block = f"block_{PROBE_PATTERN.index('L')}"
        routed = sown[moe_block]
        jax.block_until_ready(got)
        mark("the probe's gradients")
        (_, routing), want = reference.loss_and_grad(
            params, extra["buffers"], short, config, [routed["experts"]])
        jax.block_until_ready(want)
        mark("the reference's")
        checks = gradients_close(
            f"grad_vs_reference_given_experts_{PROBE_PATTERN}_"
            f"{short.shape[1]}", got, want)
        mark("leaf by leaf")
        distance = router_distance(routed, params[moe_block]["moe"]["router"])
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
        _, sown = jax.jit(probe.loss_and_sown)(params, extra, sample)
        far = mixer_distance(sown[mla_block], params[mla_block]["mla"],
                             config)
        checks.append(compare.holds(
            f"mla_mixer_vs_reference_by_query_blocks_{sample.shape[1]}",
            math.isfinite(far) and far <= MIXER_REL_L2_BOUND,
            f"relative L2 of the mixer's output on its own input: "
            f"{far:.3e}", MIXER_REL_L2_BOUND))
        mark("the mixer at the cell's length")
        print("seconds of the family's check: " + ", ".join(
            f"{name} {t - t0:.1f}"
            for (_, t0), (name, t) in zip(marks, marks[1:])), flush=True)
        return checks

    macs = forward_macs_per_token(**_sizes(cfg, seq_len))
    tokens = per_chip_batch * seq_len
    rows_expected = (tokens * cfg.experts_per_token * cfg.experts_held[1]
                     / cfg.n_experts)
    mla = {"layers": cfg.layer_pattern.count("L"), "batch": per_chip_batch,
           "heads": cfg.n_heads, "seq_len": seq_len,
           "qk_dim": cfg.mla_nope_dim + cfg.mla_rope_dim,
           "v_dim": cfg.mla_value_dim}
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
               # what mla_core_roofline reads: the sizes of the products
               # over positions and the multiply-adds a step requires
               "mla": {**mla,
                       "core_macs_per_step": mla_core_macs_per_step(
                           **mla, remat=cfg.remat),
                       "core_bytes_per_step": mla_core_bytes_per_step(
                           **mla, remat=cfg.remat, itemsize=jnp.dtype(
                               cfg.dtype).itemsize)}})


def build(config: dict, traffic: dict) -> HybridJob:
    seq_len, batch = traffic["seq_len"], traffic["per_chip_batch"]
    cfg = _model_config(config, seq_len)
    probe = _job(dataclasses.replace(
        cfg, n_layers=len(PROBE_PATTERN), layer_pattern=PROBE_PATTERN),
        config, batch, seq_len)
    return _job(cfg, config, batch, seq_len, probe=probe)
