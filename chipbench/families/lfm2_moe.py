"""The LFM2-MoE family: ``horovod_tpu.models.GPT`` with one mixer a
layer, a decoder layer of the source being two of them (``C`` a gated
short convolution or ``*`` grouped-query attention with per-head norms and
a full rotary, then ``-`` a SwiGLU MLP in the leading dense layers and
``E`` a sigmoid top-k router with a choice bias over SwiGLU experts of
their own width in the others), driven by the sizes of a configuration
file under the names of the source's ``config.json``, for **one chip's
share** of each layer.

Configuration keys: ``vocab_size``, ``num_hidden_layers``,
``num_dense_layers``, ``layer_types``, ``hidden_size``, ``norm_eps``;
``conv_L_cache``; ``num_attention_heads``, ``num_key_value_heads``,
``rope_parameters``; ``intermediate_size``; ``num_experts``,
``num_experts_per_tok``, ``moe_intermediate_size``, ``norm_topk_prob``,
``routed_scaling_factor``; plus ``dtype``, ``remat``, ``use_flash``,
``optimizer``. ``num_experts`` and ``vocab_size`` give what is **held
here**; ``published`` holds the model's own counts, from which the program
takes the router's width; ``experts_held_first`` is the first held
expert's number. The mixers, the dense MLP, the router and the norms are
whole. What the package does not build is refused by name. Traffic keys:
``per_chip_batch``, ``seq_len``.

The loss never holds the float32 logits whole: the model returns its last
hidden states and the package's chunked
``ops.losses.softmax_cross_entropy_fused`` multiplies them by the tied
embedding a chunk of positions at a time.
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
from chipbench.reference import lfm2_moe as reference

# What --rehearse shrinks for a CPU dry run (control flow only): a share
# of a small model with all four kinds of layer (three decoder layers, the
# first dense, the second the attention). Float32, as olmoe's.
REHEARSAL = {
    "config": {
        "vocab_size": 256, "num_hidden_layers": 3, "num_dense_layers": 1,
        "layer_types": ["conv", "full_attention", "conv"],
        "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_experts": 4,
        "num_experts_per_tok": 3, "experts_held_first": 4,
        "dtype": "float32",
        "published": {"num_hidden_layers": 6, "num_dense_layers": 2,
                      "num_experts": 16, "vocab_size": 512}},
    "traffic": {"seq_len": 64, "per_chip_batch": 2}}

# Sequences on which the probe instance is compared.
SAMPLE_SEQUENCES = 2
# The probe: the dense layer, one attention and one convolution with their
# expert layers, at the published widths and shares.
PROBE_PATTERN = "C-*ECE"
# Positions of the probe's gradient comparison: the reference's backward
# pass holds a sequence's float32 [s, 11776] dense MLP and its 32 heads'
# [s, s] scores one at a time; from 1024 up the program's attention is the
# Pallas kernels, as in the cell.
PROBE_SEQ_LEN = 2048
# Positions of the sequence whose logits the loss holds at once: 2 x 1024
# rows against the 8192 x 2048 embedding, 67 MB of float32 logits.
LOSS_CHUNK = 1024


def layer_pattern(layer_types, n_dense: int) -> str:
    """The source's decoder layers as the package's pattern: layer ``i``
    is its operator (``conv``: C, ``full_attention``: *) and then its
    feed-forward, the dense MLP in the first ``n_dense`` layers and the
    experts after them."""
    op = {"conv": "C", "full_attention": "*"}
    return "".join(op[kind] + ("-" if i < n_dense else "E")
                   for i, kind in enumerate(layer_types))


def forward_macs_per_token(pattern, d_model, head_dim, heads, kv_heads, taps,
                           d_ff, n_experts, experts_held, experts_per_token,
                           d_expert, vocab_size, seq_len) -> dict:
    """Multiply-adds a token of one forward pass over a chip's share, by
    kind of layer and for the head, from shapes (``chipbench/flops.py``
    counts no convolutional mixer and no gated MLP). ``experts_held`` and
    ``vocab_size`` are what is held.

    ``C``: the in-projection ``3 d^2``, the taps ``taps d`` and the
    out-projection ``d^2`` (the two gates, a multiplication a channel
    each, are not counted). ``*``: q and o ``2 d heads hd``, k and v ``2 d
    kv hd`` and the causal half of the two score products, ``heads hd s``.
    ``-``: three matrices ``3 d d_ff``. ``E``: the router over all
    ``n_experts`` and the routed experts at their expectation under a
    uniform router: ``k x held / n_experts`` experts a token, three
    matrices each. The tied head once; the embedding lookup is free."""
    layer = {
        "C": 4 * d_model * d_model + taps * d_model,
        "*": (2 * d_model * heads * head_dim + 2 * d_model * kv_heads * head_dim
              + heads * head_dim * seq_len),
        "-": 3 * d_model * d_ff,
        "E": (d_model * n_experts + experts_per_token * experts_held
              / n_experts * 3 * d_model * d_expert),
    }
    macs = {kind: pattern.count(kind) * each for kind, each in layer.items()}
    macs["head"] = vocab_size * d_model
    return macs


def n_params(pattern, d_model, head_dim, heads, kv_heads, taps, d_ff,
             n_experts, experts_held, d_expert, vocab_size) -> int:
    """The tied embedding and the final norm; a layer its norm and its
    mixer."""
    layer = {
        "C": 4 * d_model * d_model + taps * d_model,
        "*": (2 * d_model * heads * head_dim + 2 * d_model * kv_heads * head_dim
              + 2 * head_dim),
        "-": 3 * d_model * d_ff,
        "E": d_model * n_experts + experts_held * 3 * d_model * d_expert,
    }
    return (vocab_size * d_model + d_model
            + sum(layer[kind] + d_model for kind in pattern))


def _model_config(config, seq_len) -> GPTConfig:
    published = config["published"]
    for key, want in (("conv_bias", False), ("use_expert_bias", True),
                      ("tie_word_embeddings", True),
                      ("model_type", "lfm2_moe")):
        if config.get(key, want) != want:
            raise ValueError(f"{key} is {config[key]!r}: the package builds "
                             f"{want!r} alone")
    rope = config["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_parameters is {rope!r}: the package builds "
                         f"the default rotary alone")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError(
            f"layer_types names {len(config['layer_types'])} layers, "
            f"num_hidden_layers is {config['num_hidden_layers']}")
    pattern = layer_pattern(config["layer_types"], config["num_dense_layers"])
    return GPTConfig(
        vocab_size=config["vocab_size"], n_layers=len(pattern),
        layer_pattern=pattern, d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_norm=True,
        rotary_base=float(rope["rope_theta"]), max_seq_len=seq_len,
        dtype=jnp.dtype(config["dtype"]), remat=config["remat"],
        use_flash=config["use_flash"], tie_embeddings=True,
        norm_eps=config["norm_eps"], mlp_act="swiglu",
        d_ff=config["intermediate_size"],
        moe_expert_ff=config["moe_intermediate_size"],
        sconv_taps=config["conv_L_cache"],
        n_experts=published["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        moe_score="sigmoid", moe_renormalise=config["norm_topk_prob"],
        moe_route_scale=float(config["routed_scaling_factor"]),
        experts_held=(config["experts_held_first"], config["num_experts"]))


def _sizes(cfg: GPTConfig, seq_len=None) -> dict:
    """The arguments the two counting functions share."""
    sizes = dict(
        pattern=cfg.layer_pattern, d_model=cfg.d_model,
        head_dim=cfg.d_model // cfg.n_heads, heads=cfg.n_heads,
        kv_heads=cfg.n_kv_heads, taps=cfg.sconv_taps, d_ff=cfg.d_ff,
        n_experts=cfg.n_experts, experts_held=cfg.experts_held[1],
        d_expert=cfg.moe_expert_ff, vocab_size=cfg.vocab_size)
    if seq_len is not None:
        sizes.update(experts_per_token=cfg.experts_per_token,
                     seq_len=seq_len)
    return sizes


def mixer_distance(sown, p, config) -> float:
    """The program's short-convolution mixer against the reference's, one
    position after another, on the input the program's mixer had: relative
    L2 of the output over every sequence."""
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda u, p: jax.lax.map(
            lambda one: reference.short_conv(one, p, config),
            u.astype(jnp.float32)))(
                sown["sconv_input"], jax.tree.map(
                    lambda a: a.astype(jnp.float32), p))
    got = sown["sconv_output"].astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# Every bound below lies between two readings on the chip (v5e, my chip
# runs, PR 40; `benchmarks/lfm2_wrong_programs.py` makes them again): the
# largest a sound run gave over its seeds, and what a lower precision or
# wrong mathematics gives. PERF.md section 6 has the table.
#
# The step's loss against the float32 reference on the parameters a
# window of training left and the batch it trained on, relative to the
# reference's (the loss falls from 9.4 to 7.7 in a window at this learning
# rate). Sound: 1.4e-6 to 1.2e-4 after a window (11 runs), 2.4e-5 and 2.8e-5 on a fresh
# initialisation (the program reads above the reference: bf16 noise in
# the logits raises a log-sum-exp, the more the sharper the model has
# become on its one batch). The reference itself at the TPU's default
# precision reads 9.7e-6 and 4.0e-7 from the reference: as in
# `nemotron_h` and `qwen3_next`, no lower precision is told from a sound
# run by this loss (the checks below do that), so the bound is no middle
# of two readings: it is `gpt`'s, the accepted cells' one that leaves the
# largest sound reading three times of room and more.
LOSS_REL_BOUND = 1e-3
# Gradients of the probe against the reference given the program's expert
# indices, relative L2. Over the whole tree: sound 1.536e-2 to 1.547e-2
# (the bf16 activations the configuration states; 14 seeds within
# 0.7%); the mixer's gates and taps in bf16 1.718e-2, every RMSNorm in
# bf16 1.728e-2, the rotary at base 1e4 0.172, the chosen weights not
# renormalised 0.372. The geometric middle of 1.547e-2 and 1.718e-2: this
# is the check that holds the norms to float32, and with the mixer's own
# the gates.
GRAD_REL_L2_BOUND = 1.63e-2
# ... and at the worst leaf, which is what a wrong expert layer or mixer
# moves while the embedding's gradient carries the tree's norm: sound
# 2.35e-2 to 3.02e-2 (the second router, or the attention's q or k norm); the
# rotary at base 1e4 1.06 (k), the gate B left out 1.44 (the taps), not
# renormalised 2.67 (the first router). Near the geometric middle of
# 3.02e-2 and 1.06.
GRAD_WORST_LEAF_BOUND = 0.15
# The program's router against sigmoid(h W_r) in float32 at highest
# precision on the very input the program's router had, the largest
# relative distance of a score over 4,096 tokens x 64 experts, the worse
# of the probe's two expert layers: sound 1.19e-7 (one unit in the last
# place) in every run; the product at the TPU's default precision 5.57e-3.
# The geometric middle. This is the check that holds the router to
# float32.
ROUTER_REL_BOUND = 3e-5
# The program's 4 of 64 against the reference's own, the worse of the
# probe's two expert layers. Top-k is discontinuous and the program's
# router sees bf16 hidden states that have been through bf16 mixers, so
# some tokens swap their 4th and 5th expert: sound 0.98492 to 0.98779 of
# the assignments agree; the rotary at base 1e4 0.914, not renormalised
# 0.835, the gate B left out 0.060. The middle of 0.914 and 0.9849.
CHOICES_AGREE_BOUND = 0.95
# ... and every disagreement is a near-tie: the largest gap in the
# reference's scores that the program's choice overrode (a score is
# between 0 and 1, the 4th of 64 near 0.53), sound 6.2e-3 to 1.00e-2; the
# rotary at base 1e4 0.142, not renormalised 0.383, the gate B left out
# 0.908. The geometric middle of 1.00e-2 and 0.142: a tail statistic over
# some 220 disagreements, so the bound leaves it 3.5 times the largest
# seen.
NEAR_TIE_BOUND = 3.5e-2
# The short-convolution mixer's output at the cell's 8192 positions
# against the position-by-position reference on the mixer's own input,
# relative L2: sound 4.971e-3 to 4.980e-3 (bf16 products and a bf16
# result, float32 gates and taps); with every product and sum of the gates
# and the taps rounded to bf16 6.099e-3; the gate B left out 1.50. The
# geometric middle of 4.980e-3 and 6.099e-3. This is the check that holds
# the gates and the convolution to float32.
MIXER_REL_L2_BOUND = 5.5e-3


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
        # look for "/lm_head/", "/moe_", "/sconv_" and "/dense_mlp/"
        # further in
        with jax.named_scope("lfm2_moe"):
            hidden, sown = model.apply(
                {"params": params, **extra}, tokens, return_hidden=True,
                mutable=["intermediates"] if sow else [])
            ce = softmax_cross_entropy_fused(
                hidden[:, :-1], params["embedding"], tokens[:, 1:],
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
        """On the probe (``C-*ECE`` at the published widths and shares):
        gradients at ``PROBE_SEQ_LEN`` positions against the reference
        given the program's expert indices, each router against a float32
        one on its own input, the two choices of experts against each
        other, and the first short-convolution mixer at the cell's length
        against the position-by-position reference on its own input."""
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
        moe_blocks = [f"block_{i}" for i, kind in enumerate(PROBE_PATTERN)
                      if kind == "E"]
        sconv_block = f"block_{PROBE_PATTERN.index('C')}"
        jax.block_until_ready(got)
        mark("the probe's gradients")
        (_, routing), want = reference.loss_and_grad(
            params, extra["buffers"], short, config,
            [sown[block]["experts"] for block in moe_blocks])
        jax.block_until_ready(want)
        mark("the reference's")
        checks = gradients_close(
            f"grad_vs_reference_given_experts_{PROBE_PATTERN}_"
            f"{short.shape[1]}", got, want)
        mark("leaf by leaf")
        distance = max(router_distance(
            sown[block], params[block]["moe"]["router"])
            for block in moe_blocks)
        checks.append(compare.holds(
            "router_is_float32", distance <= ROUTER_REL_BOUND,
            f"largest |s / s_ref - 1| on a router's own input: "
            f"{distance:.3e}", ROUTER_REL_BOUND))
        compared = [compare_choices(sown[block]["experts"], layer["probs"],
                                    layer["own"])
                    for block, layer in zip(moe_blocks, routing)]
        agree = min(share for share, _ in compared)
        gap = max(gap for _, gap in compared)
        print("load of a fresh initialisation (largest group over the "
              "mean of all the router's experts), a layer: " + ", ".join(
                  f"{load(sown[b]['experts'], cfg.n_experts):.3f}"
                  for b in moe_blocks) + "; rows of the experts held: "
              + ", ".join(str(held_rows(sown[b]["experts"], cfg).tolist())
                          for b in moe_blocks), flush=True)
        checks.append(compare.holds(
            "experts_agree_with_reference", agree >= CHOICES_AGREE_BOUND,
            f"share of assignments: {agree}", CHOICES_AGREE_BOUND))
        checks.append(compare.holds(
            "disagreements_are_near_ties", gap <= NEAR_TIE_BOUND,
            f"largest score gap overridden: {gap}", NEAR_TIE_BOUND))
        mark("routers and choices")
        _, sown = jax.jit(probe.loss_and_sown)(params, extra, sample)
        far = mixer_distance(sown[sconv_block],
                             params[sconv_block]["sconv"], config)
        checks.append(compare.holds(
            f"sconv_mixer_vs_position_by_position_{sample.shape[1]}",
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
                       "routed_over": cfg.n_experts}})


def build(config: dict, traffic: dict) -> HybridJob:
    seq_len, batch = traffic["seq_len"], traffic["per_chip_batch"]
    cfg = _model_config(config, seq_len)
    probe = _job(dataclasses.replace(
        cfg, n_layers=len(PROBE_PATTERN), layer_pattern=PROBE_PATTERN),
        config, batch, seq_len)
    return _job(cfg, config, batch, seq_len, probe=probe)
