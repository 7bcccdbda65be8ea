"""The Qwen3-Next family: ``horovod_tpu.models.GPT`` with one mixer a
layer, a decoder layer of the source being two of them (``G`` a Gated
DeltaNet or ``*`` gated softmax attention with per-head norms and a
partial rotary, then ``E`` a softmax top-k router over SwiGLU experts with
a gated shared expert), driven by the sizes of a configuration file under
the names of the source's ``config.json``, for **one chip's share** of
each layer.

Configuration keys: ``vocab_size``, ``num_hidden_layers``,
``full_attention_interval``, ``hidden_size``, ``rms_norm_eps``;
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``partial_rotary_factor``, ``rope_theta``; ``linear_num_key_heads``,
``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``, ``linear_conv_kernel_dim``; ``num_experts``,
``num_experts_per_tok``, ``moe_intermediate_size``,
``shared_expert_intermediate_size``, ``norm_topk_prob``; plus ``dtype``,
``remat``, ``use_flash``, ``optimizer``. ``num_experts`` and
``vocab_size`` give what is **held here**; ``published`` holds the
model's own counts, from which the program takes the router's width;
``experts_held_first`` is the first held expert's number. The mixers, the
router, the shared expert and the norms are whole. What the package does
not build is refused by name. Traffic keys: ``per_chip_batch``,
``seq_len``.

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
from horovod_tpu.models import gdn
from horovod_tpu.ops.losses import softmax_cross_entropy_fused

from chipbench import compare
from chipbench.families import optimizer_from
from chipbench.families.nemotron_h import HybridJob, _leaf_sums
from chipbench.families.olmoe import compare_choices, load
from chipbench.reference import qwen3_next as reference

# What --rehearse shrinks for a CPU dry run (control flow only): a share
# of a small model with all three kinds of layer (two decoder layers, the
# second the full attention). Float32, as olmoe's.
REHEARSAL = {
    "config": {
        "vocab_size": 256, "num_hidden_layers": 2,
        "full_attention_interval": 2, "hidden_size": 64, "head_dim": 32,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 16, "linear_value_head_dim": 16,
        "num_experts": 4, "num_experts_per_tok": 3,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 48,
        "experts_held_first": 4, "dtype": "float32",
        "published": {"num_hidden_layers": 4, "num_experts": 16,
                      "vocab_size": 512}},
    "traffic": {"seq_len": 64, "per_chip_batch": 2}}

# Sequences on which the probe instance is compared.
SAMPLE_SEQUENCES = 2
# The probe: one layer of each kind at the published widths and shares.
PROBE_PATTERN = "G*E"
# Positions of the probe's gradient comparison: the reference's backward
# pass walks the rule position by position (a [32, 128, 128] float32 state
# each, kept in runs of 128); from 1024 up the program's attention is the
# Pallas kernels, as in the cell.
PROBE_SEQ_LEN = 2048
# Positions of the sequence whose logits the loss holds at once: 2 x 1024
# rows against the 18992 x 2048 head, 156 MB of float32 logits.
LOSS_CHUNK = 1024


def layer_pattern(n_layers: int, interval: int) -> str:
    """The source's decoder layers as the package's pattern: layer ``i``
    is full attention where ``(i + 1) % interval == 0`` and a Gated
    DeltaNet elsewhere, an expert layer after each."""
    return "".join(("*" if (i + 1) % interval == 0 else "G") + "E"
                   for i in range(n_layers))


def forward_macs_per_token(pattern, d_model, head_dim, heads, kv_heads,
                           key_heads, value_heads, key_dim, value_dim, conv,
                           n_experts, experts_held, experts_per_token,
                           d_expert, d_shared, vocab_size, seq_len) -> dict:
    """Multiply-adds a token of one forward pass over a chip's share, by
    kind of layer and for the head, from shapes (``chipbench/flops.py``
    counts no delta rule). ``experts_held`` and ``vocab_size`` are what is
    held.

    ``G``: the two in-projections ``d (2 K + 2 V + 2 H_v)``, the
    convolution ``conv (2 K + V)``, the rule as the reference runs it (the
    state's read, its write and its read-out, ``3 H_v d_k d_v``: no chunk
    length moves it) and the out-projection ``V d``. ``*``: q with its
    gate ``2 d heads hd``, k and v ``2 d kv hd``, o ``heads hd d`` and the
    causal half of the two score products, ``heads hd s``. ``E``: the
    router over all ``n_experts``, the shared expert's three matrices and
    its gate, and the routed experts at their expectation under a uniform
    router: ``k x held / n_experts`` experts a token, three matrices each.
    The untied head once; the embedding lookup is free."""
    keys, values = key_heads * key_dim, value_heads * value_dim
    layer = {
        "G": (d_model * (2 * keys + 2 * values + 2 * value_heads)
              + conv * (2 * keys + values)
              + 3 * value_heads * key_dim * value_dim + values * d_model),
        "*": (2 * d_model * heads * head_dim + 2 * d_model * kv_heads * head_dim
              + heads * head_dim * d_model + heads * head_dim * seq_len),
        "E": (d_model * n_experts + 3 * d_model * d_shared + d_model
              + experts_per_token * experts_held / n_experts
              * 3 * d_model * d_expert),
    }
    macs = {kind: pattern.count(kind) * each for kind, each in layer.items()}
    macs["head"] = vocab_size * d_model
    return macs


def n_params(pattern, d_model, head_dim, heads, kv_heads, key_heads,
             value_heads, key_dim, value_dim, conv, n_experts, experts_held,
             d_expert, d_shared, vocab_size) -> int:
    """Embedding, head and final norm; a layer its norm and its mixer."""
    keys, values = key_heads * key_dim, value_heads * value_dim
    layer = {
        "G": (d_model * (2 * keys + 2 * values + 2 * value_heads)
              + conv * (2 * keys + values) + 2 * value_heads + value_dim
              + values * d_model),
        "*": (3 * d_model * heads * head_dim + 2 * d_model * kv_heads * head_dim
              + 2 * head_dim),
        "E": (d_model * n_experts + 3 * d_model * d_shared + d_model
              + experts_held * 3 * d_model * d_expert),
    }
    return (2 * vocab_size * d_model + d_model
            + sum(layer[kind] + d_model for kind in pattern))


def _model_config(config, seq_len) -> GPTConfig:
    published = config["published"]
    for key, want in (("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("hidden_act", "silu"), ("norm_topk_prob", True),
                      ("rope_scaling", None), ("use_sliding_window", False),
                      ("attention_bias", False),
                      ("tie_word_embeddings", False),
                      ("num_nextn_predict_layers", 0)):
        if config.get(key, want) != want:
            raise ValueError(f"{key} is {config[key]!r}: the package builds "
                             f"{want!r} alone")
    return GPTConfig(
        vocab_size=config["vocab_size"],
        n_layers=2 * config["num_hidden_layers"],
        layer_pattern=layer_pattern(config["num_hidden_layers"],
                                    config["full_attention_interval"]),
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], head_norm=True, attn_gate=True,
        rotary_base=float(config["rope_theta"]),
        rotary_fraction=float(config["partial_rotary_factor"]),
        max_seq_len=seq_len, dtype=jnp.dtype(config["dtype"]),
        remat=config["remat"], use_flash=config["use_flash"],
        tie_embeddings=False, norm_eps=config["rms_norm_eps"],
        norm_unit_offset=True,
        gdn_key_heads=config["linear_num_key_heads"],
        gdn_value_heads=config["linear_num_value_heads"],
        gdn_key_dim=config["linear_key_head_dim"],
        gdn_value_dim=config["linear_value_head_dim"],
        gdn_conv=config["linear_conv_kernel_dim"],
        n_experts=published["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        d_ff=config["moe_intermediate_size"], moe_score="softmax",
        moe_renormalise=True, moe_shared_gate=True,
        moe_shared_ff=config["shared_expert_intermediate_size"],
        experts_held=(config["experts_held_first"], config["num_experts"]))


def _sizes(cfg: GPTConfig, seq_len=None) -> dict:
    """The arguments the two counting functions share."""
    sizes = dict(
        pattern=cfg.layer_pattern, d_model=cfg.d_model,
        head_dim=cfg.head_dim, heads=cfg.n_heads, kv_heads=cfg.n_kv_heads,
        key_heads=cfg.gdn_key_heads, value_heads=cfg.gdn_value_heads,
        key_dim=cfg.gdn_key_dim, value_dim=cfg.gdn_value_dim,
        conv=cfg.gdn_conv, n_experts=cfg.n_experts,
        experts_held=cfg.experts_held[1], d_expert=cfg.d_ff,
        d_shared=cfg.moe_shared_ff, vocab_size=cfg.vocab_size)
    if seq_len is not None:
        sizes.update(experts_per_token=cfg.experts_per_token,
                     seq_len=seq_len)
    return sizes


def attention_kernels_normal(params, key, stddev=0.02):
    """The configuration's ``assumed`` initialisation where the package's
    differs: the attention's four projections drawn from normal(0.02) (its
    layers draw them lecun-normal), each from its own fold of ``key``."""
    def one(path, leaf):
        names = [str(getattr(k, "key", k)) for k in path]
        if "attn" in names and names[-1] == "kernel":
            fold = jax.random.fold_in(jax.random.fold_in(
                key, int(names[0].split("_")[1])), "qkvo".index(names[-2]))
            return stddev * jax.random.normal(fold, leaf.shape, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(one, params)


def router_distance(routed, router, k) -> float:
    """The program's router against a float32 one on the program's own
    input: the largest ``|p / p_ref - 1|`` over tokens and experts, ``p``
    the probabilities the layer sowed and ``p_ref`` the reference's
    ``softmax(h W_r)`` of the input the layer sowed (the bf16 hidden
    states, which float32 holds exactly)."""
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda h, w: reference.route(
            h.astype(jnp.float32), w.astype(jnp.float32), k)[0])(
                routed["router_input"], router)
    return float(jnp.max(jnp.abs(routed["router_probs"] / want - 1.0)))


def mixer_distance(sown, p, config) -> float:
    """The program's Gated DeltaNet mixer against the reference's, one
    position after another, on the input the program's mixer had: relative
    L2 of the output over every sequence."""
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda u, p: jax.lax.map(
            lambda one: reference.gdn_mixer(one, p, config),
            u.astype(jnp.float32)))(
                sown["gdn_input"], jax.tree.map(
                    lambda a: a.astype(jnp.float32), p))
    got = sown["gdn_output"].astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# Every bound below lies between two readings on the chip (v5e, my chip
# runs, PR 33; `benchmarks/qwen3next_wrong_programs.py` makes them again):
# the largest a sound run gave over its seeds, and what a lower precision
# or wrong mathematics gives. PERF.md section 6 has the table.
#
# The step's loss against the float32 reference on the parameters a
# window of training left and the batch it trained on, relative to the
# reference's (the loss stays above 9 in a window at this learning rate).
# Sound: 3.4e-6 to 4.5e-5 after a window (8 runs), 3.5e-6 and 7.8e-6 on a
# fresh initialisation; the reference itself at the TPU's default
# precision reads 3.0e-6 and 6.7e-6 from the reference: as in
# `nemotron_h`, no lower precision is told from a sound run by this loss
# (the checks below do that), so the bound is three times the largest
# sound reading.
LOSS_REL_BOUND = 1.5e-4
# Gradients of the probe against the reference given the program's expert
# indices, relative L2. Over the whole tree: sound 1.26e-2 to 1.31e-2 (the
# bf16 activations the configuration states); the chosen weights not
# renormalised 4.6e-2, the rotary over the whole head 0.136, the rule's
# decays, cumulative sums, inverse and state in bf16 0.172. Near the
# geometric middle of 1.31e-2 and 4.6e-2.
GRAD_REL_L2_BOUND = 2.5e-2
# ... and at the worst leaf, which is what a wrong expert layer or mixer
# moves while the head's and the embedding's gradients carry the tree's
# norm: sound 2.6e-2 to 5.4e-2 (most often the router, whose gradient is
# what is left of cancelling terms once the weights are renormalised); not
# renormalised 0.88 (`down`), the rotary over the whole head 0.92 (k), the
# rule in bf16 1.73 (`dt_bias`). Near the geometric middle of 5.4e-2 and
# 0.88.
GRAD_WORST_LEAF_BOUND = 0.2
# The program's router against softmax(h W_r) in float32 at highest
# precision on the very input the program's router had, the largest
# relative distance of a probability over 4,096 tokens x 512 experts:
# sound 1.19e-7 (one unit in the last place) in every run; the product at
# the TPU's default precision 7.3e-3. The geometric middle. This is the
# check that holds the router to float32.
ROUTER_REL_BOUND = 3e-5
# The program's 10 of 512 against the reference's own. Top-k is
# discontinuous and the program's router sees bf16 hidden states that have
# been through a bf16 mixer and attention, so some tokens swap their 10th
# and 11th expert: sound 0.98850 to 0.98953 of the assignments agree; with
# the rule in bf16 0.916, the rotary over the whole head 0.934, `beta`
# left out 0.806. Between 0.934 and 0.9885.
CHOICES_AGREE_BOUND = 0.97
# ... and every disagreement is a near-tie: the largest gap in the
# reference's probabilities that the program's choice overrode, over 4,096
# tokens (a probability is about 1 / 512 = 2.0e-3), sound 2.5e-4 to 3.6e-4;
# the rotary over the whole head 5.2e-3, `beta` left out 1.2e-2, the rule
# in bf16 1.7e-2. The geometric middle of 3.6e-4 and 5.2e-3: a tail
# statistic, so the bound leaves it 3.9 times the largest seen.
NEAR_TIE_BOUND = 1.4e-3
# The Gated DeltaNet mixer's output at the cell's 8192 positions against
# the position-by-position reference on the mixer's own input, relative
# L2: sound 6.55e-3 to 6.61e-3 (bf16 products, float32 decays, inverse and
# state); with the decays, their cumulative sums, the inverse and the
# carried state in bf16 0.114; `beta` left out 0.23, the gate before the
# norm 0.66, the decay left out 1.37, q and k not normalised not finite.
# Near the geometric middle of 6.61e-3 and 0.114. This is the check that
# holds the rule's decays, cumulative sums and state to float32.
MIXER_REL_L2_BOUND = 2.5e-2


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


def held_rows(experts, cfg: GPTConfig):
    """Rows of each held expert in one layer's choices ``[T, k]``."""
    held = slice(cfg.experts_held[0], sum(cfg.experts_held))
    return jnp.sum(experts[..., None] == jnp.arange(cfg.n_experts)[held],
                   axis=(0, 1))


def _job(cfg: GPTConfig, config, per_chip_batch, seq_len,
         probe=None) -> HybridJob:
    """The job of one model instance; ``probe`` is the small instance its
    gradients are checked on (the probe itself checks nothing)."""
    model = GPT(cfg)

    def init(key):
        key_model, key_attn = jax.random.split(key)
        variables = model.init(key_model, jnp.zeros((1, seq_len), jnp.int32))
        return attention_kernels_normal(variables["params"], key_attn), {}

    def make_batch(key, n_chips):
        return jax.random.randint(
            key, (n_chips * per_chip_batch, seq_len), 0, cfg.vocab_size,
            jnp.int32)

    def loss_and_sown(params, extra, tokens, sow=True):
        # one scope around all of it: JAX writes a transformation's name
        # (jvp, transpose) around the outermost scope, and the readers
        # look for "/lm_head/", "/moe_" and "/gdn_" further in
        with jax.named_scope("qwen3_next"):
            hidden, sown = model.apply(
                {"params": params}, tokens, return_hidden=True,
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
        value, routing = reference.loss(params, tokens, config)
        print("at the end of the window, a layer: load (largest group over "
              "the mean of all the router's experts) " + ", ".join(
                  f"{load(r['own'], cfg.n_experts):.3f}" for r in routing)
              + "; rows on the experts held " + ", ".join(
                  str(int(jnp.sum(held_rows(r["own"], cfg)))) for r in routing)
              + f" of a round of {tokens.size}", flush=True)
        return value

    def check(key):
        """On the probe (``G*E`` at the published widths and shares):
        gradients at ``PROBE_SEQ_LEN`` positions against the reference
        given the program's expert indices, the router against a float32
        one on its own input, the two choices of experts against each
        other, and the Gated DeltaNet mixer at the cell's length against
        the position-by-position reference on its own input."""
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
        moe_block, gdn_block = (
            f"block_{PROBE_PATTERN.index(kind)}" for kind in "EG")
        routed = sown[moe_block]
        jax.block_until_ready(got)
        mark("the probe's gradients")
        (_, routing), want = reference.loss_and_grad(
            params, short, config, [routed["experts"]])
        jax.block_until_ready(want)
        mark("the reference's")
        checks = gradients_close(
            f"grad_vs_reference_given_experts_{PROBE_PATTERN}_"
            f"{short.shape[1]}", got, want)
        mark("leaf by leaf")
        distance = router_distance(
            routed, params[moe_block]["moe"]["router"], cfg.experts_per_token)
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
        _, sown = jax.jit(probe.loss_and_sown)(params, extra, sample)
        far = mixer_distance(sown[gdn_block], params[gdn_block]["gdn"],
                             config)
        checks.append(compare.holds(
            f"gdn_mixer_vs_position_by_position_{sample.shape[1]}",
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
                       "d_model": cfg.d_model, "d_expert": cfg.d_ff,
                       "itemsize": jnp.dtype(cfg.dtype).itemsize,
                       "row_bound": tokens * min(cfg.experts_per_token,
                                                 cfg.experts_held[1]),
                       "routed_over": cfg.n_experts},
               "gdn": {"layers": cfg.layer_pattern.count("G"),
                       "value_heads": cfg.gdn_value_heads,
                       "chunk": gdn.chunk_for(seq_len)}})


def build(config: dict, traffic: dict) -> HybridJob:
    seq_len, batch = traffic["seq_len"], traffic["per_chip_batch"]
    cfg = _model_config(config, seq_len)
    probe = _job(dataclasses.replace(
        cfg, n_layers=len(PROBE_PATTERN), layer_pattern=PROBE_PATTERN),
        config, batch, seq_len)
    return _job(cfg, config, batch, seq_len, probe=probe)
