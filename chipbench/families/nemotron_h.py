"""The hybrid decoder family: ``horovod_tpu.models.GPT`` with one mixer a
layer in the order ``hybrid_override_pattern`` gives (``*`` attention
without a positional term, ``M`` a Mamba-2 mixer, ``E`` a latent
mixture of ``relu2`` experts behind a sigmoid router with a shared
expert), driven by the sizes of a configuration file under the names of
Nemotron-H's ``config.json``, for **one chip's share** of each layer.

Configuration keys: ``vocab_size``, ``num_hidden_layers``,
``hybrid_override_pattern``, ``hidden_size``, ``head_dim``, ``norm_eps``;
``num_attention_heads``, ``num_key_value_heads``; ``mamba_num_heads``,
``mamba_head_dim``, ``n_groups``, ``ssm_state_size``, ``conv_kernel``;
``n_routed_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``,
``moe_latent_size``, ``moe_shared_expert_intermediate_size``,
``routed_scaling_factor``, ``norm_topk_prob``; plus ``dtype``, ``remat``,
``use_flash``, ``optimizer``. The keys that count heads, key-value heads,
groups, experts and the vocabulary give what is **held here**;
``published`` holds the model's own counts, from which the program takes
the router's width, the size of a group of heads and the depth the
initialisation scales by; ``experts_held_first`` is the first held
expert's number (heads and groups are held from 0). What the package does
not build is refused by name. Traffic keys: ``per_chip_batch``,
``seq_len``.

The loss never holds the float32 logits whole: the model returns its
last hidden states and the package's chunked
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
from horovod_tpu.models import ssm
from horovod_tpu.ops.losses import softmax_cross_entropy_fused

from chipbench import compare
from chipbench.families import Job, optimizer_from
from chipbench.families.olmoe import compare_choices, load
from chipbench.reference import nemotron_h as reference

# What --rehearse shrinks for a CPU dry run (control flow only): a share
# of a small model with all three kinds of layer. Float32, as olmoe's.
REHEARSAL = {
    "config": {
        "vocab_size": 256, "num_hidden_layers": 3,
        "hybrid_override_pattern": "*EM", "hidden_size": 64, "head_dim": 16,
        "num_attention_heads": 2, "num_key_value_heads": 1,
        "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 1,
        "ssm_state_size": 16, "n_routed_experts": 4,
        "num_experts_per_tok": 3, "moe_intermediate_size": 32,
        "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 48,
        "experts_held_first": 4, "dtype": "float32",
        "published": {"num_hidden_layers": 6, "n_routed_experts": 16,
                      "mamba_num_heads": 8, "n_groups": 2,
                      "num_attention_heads": 4, "num_key_value_heads": 2,
                      "vocab_size": 512, "num_nextn_predict_layers": 0}},
    "traffic": {"seq_len": 64, "per_chip_batch": 2}}

# Sequences on which the probe instance is compared.
SAMPLE_SEQUENCES = 2
# The probe: one layer of each kind at the published widths and shares.
PROBE_PATTERN = "*EM"
# Positions of the probe's gradient comparison: the reference's backward
# pass keeps the recurrence's state at every position (512 KiB each at 16
# heads of 64 x 128), 1 GiB a sequence at 2048; from 1024 up the program's
# attention is the Pallas kernels, as in the cell.
PROBE_SEQ_LEN = 2048
# Positions of the sequence whose logits the loss holds at once: 2 x 1024
# rows against the 16384 x 4096 head, 134 MB of float32 logits.
LOSS_CHUNK = 1024


def forward_macs_per_token(pattern, d_model, head_dim, heads, kv_heads,
                           ssm_heads, ssm_head_dim, ssm_groups, ssm_state,
                           conv, n_experts, experts_held, experts_per_token,
                           latent, d_expert, d_shared, vocab_size,
                           seq_len) -> dict:
    """Multiply-adds a token of one forward pass over a chip's share, by
    kind of layer and for the head, from shapes (``chipbench/flops.py``
    counts no state-space or latent layer). ``heads``, ``kv_heads``,
    ``ssm_heads``, ``ssm_groups``, ``experts_held`` and ``vocab_size``
    are what is held.

    ``M``: the in-projection ``d (2 D + 2 G N + heads)``, the convolution
    ``conv (D + 2 G N)``, the recurrence as the reference runs it (the
    state's update and its read-out, ``2 heads P N``: no chunk length
    moves it) and the out-projection ``D d``. ``*``: q and o ``2 d heads
    hd``, k and v ``2 d kv hd``, and the causal half of the two score
    products, ``heads hd s``. ``E``: the router over all ``n_experts``,
    the two latent projections, the shared expert's two matrices, and the
    routed experts at their expectation under a uniform router:
    ``k x held / n_experts`` experts a token, two matrices each. The
    untied head once; the embedding lookup is free."""
    inner, bc = ssm_heads * ssm_head_dim, ssm_groups * ssm_state
    layer = {
        "M": (d_model * (2 * inner + 2 * bc + ssm_heads)
              + conv * (inner + 2 * bc)
              + 2 * ssm_heads * ssm_head_dim * ssm_state + inner * d_model),
        "*": (2 * d_model * heads * head_dim + 2 * d_model * kv_heads * head_dim
              + heads * head_dim * seq_len),
        "E": (d_model * n_experts + 2 * d_model * latent
              + 2 * d_model * d_shared
              + experts_per_token * experts_held / n_experts
              * 2 * latent * d_expert),
    }
    macs = {kind: pattern.count(kind) * each for kind, each in layer.items()}
    macs["head"] = vocab_size * d_model
    return macs


def n_params(pattern, d_model, head_dim, heads, kv_heads, ssm_heads,
             ssm_head_dim, ssm_groups, ssm_state, conv, n_experts,
             experts_held, latent, d_expert, d_shared, vocab_size) -> int:
    """Embedding, head and final norm; a layer its norm and its mixer."""
    inner, bc = ssm_heads * ssm_head_dim, ssm_groups * ssm_state
    layer = {
        "M": (d_model * (2 * inner + 2 * bc + ssm_heads)
              + (conv + 1) * (inner + 2 * bc) + 3 * ssm_heads + inner
              + inner * d_model),
        "*": 2 * d_model * (heads + kv_heads) * head_dim,
        "E": (d_model * n_experts + 2 * d_model * latent
              + 2 * d_model * d_shared + experts_held * 2 * latent * d_expert),
    }
    return (2 * vocab_size * d_model + d_model
            + sum(layer[kind] + d_model for kind in pattern))


def _model_config(config, seq_len) -> GPTConfig:
    published = config["published"]
    for key, want in (("n_group", 1), ("topk_group", 1),
                      ("n_shared_experts", 1), ("mlp_hidden_act", "relu2"),
                      ("mamba_hidden_act", "silu"), ("norm_topk_prob", True),
                      ("use_conv_bias", True), ("use_bias", False),
                      ("mamba_proj_bias", False), ("attention_bias", False),
                      ("tie_word_embeddings", False),
                      ("num_nextn_predict_layers", 0),
                      ("time_step_min", ssm.DT_MIN),
                      ("time_step_max", ssm.DT_MAX),
                      ("time_step_floor", ssm.DT_FLOOR)):
        if config.get(key, want) != want:
            raise ValueError(f"{key} is {config[key]!r}: the package builds "
                             f"{want!r} alone")
    if config["hidden_size"] != (published["num_attention_heads"]
                                 * config["head_dim"]):
        raise ValueError(
            f"head_dim is {config['head_dim']}: the package's heads are "
            f"hidden_size / num_attention_heads wide")
    return GPTConfig(
        vocab_size=config["vocab_size"],
        n_layers=config["num_hidden_layers"],
        layer_pattern=config["hybrid_override_pattern"],
        d_model=config["hidden_size"],
        n_heads=published["num_attention_heads"],
        n_kv_heads=published["num_key_value_heads"],
        heads_held=(0, config["num_attention_heads"]), rotary=False,
        max_seq_len=seq_len, dtype=jnp.dtype(config["dtype"]),
        remat=config["remat"], use_flash=config["use_flash"],
        tie_embeddings=False, norm_eps=config["norm_eps"], mlp_act="relu2",
        ssm_heads=published["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        ssm_groups=published["n_groups"], ssm_state=config["ssm_state_size"],
        ssm_conv=config["conv_kernel"],
        ssm_heads_held=(0, config["mamba_num_heads"]),
        n_experts=published["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"],
        d_ff=config["moe_intermediate_size"], moe_score="sigmoid",
        moe_route_scale=float(config["routed_scaling_factor"]),
        moe_expert_act="relu2", moe_latent=config["moe_latent_size"],
        moe_shared_ff=config["moe_shared_expert_intermediate_size"],
        experts_held=(config["experts_held_first"],
                      config["n_routed_experts"]))


def _sizes(cfg: GPTConfig, config, seq_len=None) -> dict:
    """The arguments the two counting functions share."""
    sizes = dict(
        pattern=cfg.layer_pattern, d_model=cfg.d_model,
        head_dim=config["head_dim"], heads=cfg.heads_held[1],
        kv_heads=config["num_key_value_heads"],
        ssm_heads=cfg.ssm_heads_held[1], ssm_head_dim=cfg.ssm_head_dim,
        ssm_groups=config["n_groups"], ssm_state=cfg.ssm_state,
        conv=cfg.ssm_conv, n_experts=cfg.n_experts,
        experts_held=cfg.experts_held[1], latent=cfg.moe_latent,
        d_expert=cfg.d_ff, d_shared=cfg.moe_shared_ff,
        vocab_size=cfg.vocab_size)
    if seq_len is not None:
        sizes.update(experts_per_token=cfg.experts_per_token,
                     seq_len=seq_len)
    return sizes


def rescale_residual_writers(params, n_layers):
    """``rescale_prenorm_residual`` as the configuration's ``assumed``
    reads it: every projection that writes to the residual, times ``1 /
    sqrt(2 x n_layers)`` of the model's published depth."""
    writers = {"o", "out_proj", "latent_out", "shared_down"}
    scale = 1.0 / math.sqrt(2 * n_layers)

    def one(path, leaf):
        names = {str(getattr(k, "key", k)) for k in path}
        return leaf * scale if names & writers else leaf

    return jax.tree_util.tree_map_with_path(one, params)


def router_distance(routed, router) -> float:
    """The program's router against a float32 one on the program's own
    input: the largest ``|s / s_ref - 1|`` over tokens and experts, ``s``
    the scores the layer sowed and ``s_ref`` the reference's ``sigmoid(h
    W_r)`` of the input the layer sowed (the bf16 hidden states, which
    float32 holds exactly)."""
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda h, w: jax.nn.sigmoid(
            h.astype(jnp.float32) @ w.astype(jnp.float32)))(
                routed["router_input"], router)
    return float(jnp.max(jnp.abs(routed["router_probs"] / want - 1.0)))


def mixer_distance(sown, p, config) -> float:
    """The program's Mamba-2 mixer against the reference's, one position
    after another, on the input the program's mixer had: relative L2 of
    the output over every sequence."""
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda u, p: jax.lax.map(
            lambda one: reference.mamba_mixer(one, p, config),
            u.astype(jnp.float32)))(
                sown["ssm_input"], jax.tree.map(
                    lambda a: a.astype(jnp.float32), p))
    got = sown["ssm_output"].astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# Every bound below lies between two readings on the chip (v5e, my chip
# runs, PR 30; `benchmarks/nemotron_wrong_programs.py` makes them again):
# the largest a sound run gave over its seeds, and what a lower precision
# or wrong mathematics gives. PERF.md section 6 has the table.
#
# The step's loss against the float32 reference on the parameters a
# window of training left and the batch it trained on, relative to the
# reference's (the loss stays above 5 in a window at this learning rate).
# Sound: 3.8e-5 to 1.0e-4 after a window (10 runs), 7.6e-6 and 2.0e-5 on
# a fresh initialisation. The reference itself at the TPU's default precision
# reads 4.5e-6 and 5.6e-6 from the reference: as in `olmoe`, no lower
# precision is told from a sound run by this loss (the checks below do
# that), so the bound is three times the largest sound reading. What a
# dropped layer or a wrong mask reads was not measured here.
LOSS_REL_BOUND = 3e-4
# Gradients of the probe against the reference given the program's expert
# indices, relative L2. Over the whole tree: sound 7.77e-3 to 8.20e-3 (25
# readings), the bf16 activations the configuration states; the scan's
# decays in bf16 4.3e-2, weights without `routed_scaling_factor` 2.4e-2.
GRAD_REL_L2_BOUND = 1.5e-2
# ... and at the worst leaf, which is what a wrong expert stack or a
# wrong router moves while the head's and the embedding's gradients carry
# the tree's norm: sound 1.3e-2 to 8.9e-2 (25 readings; median 2.7e-2), a
# wide range because the worst leaf is most often the router or
# `latent_out`, whose gradients are what is left of cancelling terms (the
# weights are renormalised, so a score's gradient is a difference of
# nearly equal sums of bf16 rows); the decays in bf16 0.41 (`dt_bias`),
# no scaling factor 0.80 (the router). The bound is near the geometric
# middle of 8.9e-2 and 0.41.
GRAD_WORST_LEAF_BOUND = 0.2
# The program's router against sigmoid(h W_r) in float32 at highest
# precision on the very input the program's router had, the largest
# relative distance of a score over 4,096 tokens x 512 experts: sound
# 1.19e-7 (one unit in the last place) in every run; the product at the
# TPU's default precision or in bf16 8.3e-3 (the two are one program:
# the input is bf16 already). The geometric middle. This is the check
# that holds the router to float32.
ROUTER_REL_BOUND = 3e-5
# The program's 22 of 512 against the reference's own. Top-k is
# discontinuous and the program's router sees bf16 hidden states that
# have been through bf16 attention, so some tokens swap their 22nd and
# 23rd expert: sound 0.99579 to 0.99658 of the assignments agree (0.99604
# with a bf16 router). A router fed something else agrees on about
# k / E = 0.043.
CHOICES_AGREE_BOUND = 0.98
# ... and every disagreement is a near-tie: the largest gap in the
# reference's scores that the program's choice overrode, over 4,096
# tokens, sound 1.36e-3 to 2.64e-3 (25 readings; a score is between 0
# and 1). The largest of some 340 disagreements, a tail statistic, so the
# bound leaves it 2.3 times the largest seen. What a choice that is no
# tie overrides was not measured: a router that saw another input fails
# the share above first.
NEAR_TIE_BOUND = 6e-3
# The Mamba-2 mixer's output at the cell's 8192 positions against the
# position-by-position reference on the mixer's own input, relative L2:
# sound 5.00e-3 to 5.36e-3 (bf16 products, float32 decays); with the
# decays, their cumulative sums and the carried state in bf16 6.64e-2.
# Near the geometric middle. This is the check that holds the scan's
# decays to float32.
MIXER_REL_L2_BOUND = 2e-2


@jax.jit
def _leaf_sums(got, want):
    """Of every leaf, ``sum((got - want)^2)`` and ``sum(want^2)``,
    float32 on the device: the host fetches scalars and no leaf."""
    return jax.tree.map(
        lambda x, y: (jnp.sum(jnp.square(x.astype(jnp.float32) - y)),
                      jnp.sum(jnp.square(y))), got, want)


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
    return [
        compare.Check(name, math.isfinite(whole)
                      and whole <= GRAD_REL_L2_BOUND,
                      f"relative L2 {whole:.3e}", GRAD_REL_L2_BOUND),
        compare.Check(f"{name}_worst_leaf", math.isfinite(by_leaf[worst])
                      and by_leaf[worst] <= GRAD_WORST_LEAF_BOUND,
                      f"{worst}: relative L2 {by_leaf[worst]:.3e}",
                      GRAD_WORST_LEAF_BOUND)]


@dataclasses.dataclass
class HybridJob(Job):
    """``loss_and_sown(params, extra, tokens) -> (loss, sown)``: the loss
    with what the expert layers' routers and the Mamba-2 mixers sowed
    (``models/moe.py``, ``models/ssm.py``), a dict a layer by its
    ``block_<i>`` name."""

    loss_and_sown: Callable | None = None


def _job(cfg: GPTConfig, config, per_chip_batch, seq_len,
         probe=None) -> HybridJob:
    """The job of one model instance; ``probe`` is the small instance its
    gradients are checked on (the probe itself checks nothing)."""
    model = GPT(cfg)
    depth = config["published"]["num_hidden_layers"]

    def init(key):
        variables = model.init(key, jnp.zeros((1, seq_len), jnp.int32))
        return (rescale_residual_writers(variables["params"], depth),
                {"buffers": variables.get("buffers", {})})

    def make_batch(key, n_chips):
        return jax.random.randint(
            key, (n_chips * per_chip_batch, seq_len), 0, cfg.vocab_size,
            jnp.int32)

    def loss_and_sown(params, extra, tokens, sow=True):
        # one scope around all of it: JAX writes a transformation's name
        # (jvp, transpose) around the outermost scope, and the readers
        # look for "/lm_head/", "/moe_" and "/ssm_" further in
        with jax.named_scope("nemotron_h"):
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
        print("load at the end of the window (largest group over the mean "
              "of all the router's experts, a layer): " + ", ".join(
                  f"{load(r['own'], cfg.n_experts):.3f}" for r in routing),
              flush=True)
        return value

    def check(key):
        """On the probe (``*EM`` at the published widths and shares):
        gradients at ``PROBE_SEQ_LEN`` positions against the reference
        given the program's expert indices, the router against a float32
        one on its own input, the two choices of experts against each
        other, and the Mamba-2 mixer at the cell's length against the
        position-by-position reference on its own input."""
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
        moe_block, ssm_block = (
            f"block_{PROBE_PATTERN.index(kind)}" for kind in "EM")
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
        held = slice(cfg.experts_held[0], sum(cfg.experts_held))
        counts = jnp.sum(routed["experts"][..., None] == jnp.arange(
            cfg.n_experts)[held], axis=(0, 1))
        print(f"load of a fresh initialisation (largest group over the "
              f"mean of all the router's experts): "
              f"{load(routed['experts'], cfg.n_experts):.3f}; rows of the "
              f"experts held: {[int(n) for n in counts]}", flush=True)
        checks.append(compare.holds(
            "experts_agree_with_reference", agree >= CHOICES_AGREE_BOUND,
            f"share of assignments: {agree}", CHOICES_AGREE_BOUND))
        checks.append(compare.holds(
            "disagreements_are_near_ties", gap <= NEAR_TIE_BOUND,
            f"largest score gap overridden: {gap}", NEAR_TIE_BOUND))
        mark("router and choices")
        _, sown = jax.jit(probe.loss_and_sown)(params, extra, sample)
        far = mixer_distance(sown[ssm_block], params[ssm_block]["ssm"],
                             config)
        checks.append(compare.holds(
            f"mamba2_mixer_vs_position_by_position_{sample.shape[1]}",
            math.isfinite(far) and far <= MIXER_REL_L2_BOUND,
            f"relative L2 of the mixer's output on its own input: "
            f"{far:.3e}", MIXER_REL_L2_BOUND))
        mark("the mixer at the cell's length")
        print("seconds of the family's check: " + ", ".join(
            f"{name} {t - t0:.1f}"
            for (_, t0), (name, t) in zip(marks, marks[1:])), flush=True)
        return checks

    macs = forward_macs_per_token(**_sizes(cfg, config, seq_len))
    tokens = per_chip_batch * seq_len
    return HybridJob(
        loss_and_sown=loss_and_sown, item="tokens",
        items_per_step_per_chip=tokens,
        flops_per_item=6.0 * sum(macs.values()),
        init=init, make_batch=make_batch, loss=loss,
        optimizer=lambda: optimizer_from(config["optimizer"]),
        reference_loss=reference_loss, loss_rel_bound=LOSS_REL_BOUND,
        check=check, probe=probe,
        facts={"n_params": n_params(**_sizes(cfg, config)),
               "n_layers": cfg.n_layers, "remat": cfg.remat,
               "pattern": cfg.layer_pattern,
               "forward_macs_per_token": macs,
               "moe": {"layers": cfg.layer_pattern.count("E"),
                       "row_bound": tokens * min(cfg.experts_per_token,
                                                 cfg.experts_held[1]),
                       "rows_expected": tokens * cfg.experts_per_token
                       * cfg.experts_held[1] / cfg.n_experts,
                       "experts": cfg.n_experts,
                       "held": cfg.experts_held[1]},
               "ssm": {"layers": cfg.layer_pattern.count("M"),
                       "heads": cfg.ssm_heads_held[1],
                       "chunk": ssm.chunk_for(seq_len)}})


def build(config: dict, traffic: dict) -> Job:
    seq_len, batch = traffic["seq_len"], traffic["per_chip_batch"]
    cfg = _model_config(config, seq_len)
    probe = _job(dataclasses.replace(
        cfg, n_layers=len(PROBE_PATTERN), layer_pattern=PROBE_PATTERN),
        config, batch, seq_len)
    return _job(cfg, config, batch, seq_len, probe=probe)
