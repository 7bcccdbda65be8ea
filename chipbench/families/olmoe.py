"""The sparse decoder family: ``horovod_tpu.models.GPT`` with a dropless
top-k router over SwiGLU experts in every block, driven by the sizes of a
configuration file under the names of OLMoE's ``config.json``.

Configuration keys: ``vocab_size``, ``num_hidden_layers``,
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``num_experts``, ``num_experts_per_tok``, ``intermediate_size`` (one
expert's width), ``rope_theta`` (10000: the one base the package's
rotary embedding has), ``rms_norm_eps``, ``tie_word_embeddings``,
``norm_topk_prob`` (false: the package's layer does not renormalise);
plus ``dtype``, ``remat``, ``use_flash``,
``optimizer`` and the two coefficients ``router_aux_loss_coef`` and
``router_z_loss_coef``. Traffic keys: ``per_chip_batch``, ``seq_len``.

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
import numpy as np

from horovod_tpu.models import GPT, GPTConfig
from horovod_tpu.ops.losses import softmax_cross_entropy_fused

from chipbench import compare
from chipbench.families import Job, optimizer_from
from chipbench.reference import olmoe as reference

# What --rehearse shrinks for a CPU dry run (control flow only). Float32:
# a second's window does not learn the batch by heart, and at a loss of
# 5 bf16 activations are 2e-5 from the reference, over LOSS_REL_BOUND.
REHEARSAL = {"config": {"vocab_size": 512, "num_hidden_layers": 2,
                        "hidden_size": 64, "num_attention_heads": 4,
                        "num_key_value_heads": 4, "num_experts": 8,
                        "num_experts_per_tok": 2, "intermediate_size": 32,
                        "dtype": "float32"},
             "traffic": {"seq_len": 64, "per_chip_batch": 4}}

# Sequences on which the probe instance's gradients are compared.
SAMPLE_SEQUENCES = 2
# Layers of the instance on which gradients are compared: the program's
# float32 gradient, the reference's and the probe's parameters are three
# trees of 2.5 GB a layer beside the reference's activations.
PROBE_LAYERS = 1
# Positions of the sequence whose logits the loss holds at once: 2 x 512
# rows against the 50304 x 2048 head is a product the MXU is busy with
# (at the loss's default of 128 the head is read from HBM 32 times a
# pass and the product is bound by that), and 2 x 512 x 50304 float32
# logits are 0.2 GB.
LOSS_CHUNK = 512


def train_flops_per_token(n_layers, d_model, n_experts, experts_per_token,
                          d_expert, vocab_size, seq_len) -> float:
    """``6 x [L x (4 d^2 + d E + k x 3 d f) + V d] + 6 L d s``, by
    ``flops.py``'s conventions: two FLOPs a multiply-add, training three
    times the forward pass, the causal half of the scores, no
    recomputation. A block: q, k, v, o (4 d^2, as many key-value heads as
    heads), the router (d E) and the k experts a token is sent to, each
    gate, up and down (3 d f); the untied head once (V d); the embedding
    lookup is free."""
    block = (4 * d_model * d_model + d_model * n_experts
             + experts_per_token * 3 * d_model * d_expert)
    return (6.0 * (n_layers * block + vocab_size * d_model)
            + 6.0 * n_layers * d_model * seq_len)


def n_params(n_layers, d_model, n_experts, d_expert, vocab_size) -> int:
    """Embedding and head; a block: q, k, v, o, the router, the three
    expert stacks, two block norms and the q and k norms (4 d)."""
    block = (4 * d_model * d_model + d_model * n_experts
             + n_experts * 3 * d_model * d_expert + 4 * d_model)
    return 2 * vocab_size * d_model + n_layers * block + d_model


def _model_config(config, seq_len):
    if config["norm_topk_prob"]:
        raise ValueError("norm_topk_prob is true: the package's expert "
                         "layer does not renormalise the chosen weights")
    if config["rope_theta"] != 10000:
        raise ValueError(f"rope_theta is {config['rope_theta']}: the "
                         f"package's rotary embedding has the base 10000")
    return GPTConfig(
        vocab_size=config["vocab_size"],
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq_len=seq_len,
        dtype=jnp.dtype(config["dtype"]), remat=config["remat"],
        use_flash=config["use_flash"], n_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"], qk_norm=True,
        tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"])


def load(experts, n_experts) -> float:
    """Largest group over the mean group of one layer's choices."""
    counts = np.bincount(np.asarray(experts).reshape(-1),
                         minlength=n_experts)
    return float(counts.max() / counts.mean())


def compare_choices(got, want_probs, want):
    """The program's experts ``got [T, k]`` against the reference's
    ``want [T, k]`` and its probabilities ``want_probs [T, E]``: the
    share of assignments on which the two agree, and over the tokens
    where they do not the largest gap, in the reference's probabilities,
    between an expert the reference chose and the program did not and
    one the program chose in its place."""
    got, want, probs = (np.asarray(a) for a in (got, want, want_probs))
    n_experts = probs.shape[-1]
    mask = lambda idx: (idx[..., None] == np.arange(n_experts)).any(1)
    in_got, in_want = mask(got), mask(want)
    agree = float((in_got & in_want).sum() / got.size)
    only_want = np.where(in_want & ~in_got, probs, -np.inf).max(-1)
    only_got = np.where(in_got & ~in_want, probs, np.inf).min(-1)
    differ = np.isfinite(only_want)
    gap = float((only_want - only_got)[differ].max()) if differ.any() else 0.0
    return agree, gap


def router_distance(routed, router, k) -> float:
    """The program's router against a float32 router on the program's
    own input: the largest ``|p / p_ref - 1|`` over tokens and experts,
    ``p`` the probabilities the layer sowed and ``p_ref`` the
    reference's ``softmax(h W_r)`` of the input the layer sowed (the
    bf16 hidden states, which float32 holds exactly). What feeds the
    router is the same on both sides, so this reads the router's own
    arithmetic and nothing else."""
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda h, w: reference.route(
            h.astype(jnp.float32), w.astype(jnp.float32), k)[0])(
                routed["router_input"], router)
    return float(jnp.max(jnp.abs(routed["router_probs"] / want - 1.0)))


@jax.jit
def _leaf_sums(got, want):
    """Of every leaf, ``sum((got - want)^2)`` and ``sum(want^2)`` over
    all but its leading axis, float32 on the device: the host fetches
    vectors and no leaf."""
    def sums(x, y):
        over = tuple(range(1, y.ndim))
        return (jnp.sum(jnp.square(x.astype(jnp.float32) - y), over),
                jnp.sum(jnp.square(y), over))

    return jax.tree.map(sums, got, want)


def worst_leaf_close(name, got, want, bound) -> compare.Check:
    """Relative L2 leaf by leaf, held to ``bound`` at the worst leaf.
    Over the whole tree the embedding's and the head's gradients carry
    most of the norm, and expert stacks that are wrong by a factor move
    it little. The squares are summed on the device along the trailing
    axes and the rest of the way in float64 on the host."""
    sums = {jax.tree_util.keystr(path): tuple(
                float(np.sum(np.asarray(v, np.float64))) for v in pair)
            for path, pair in jax.tree_util.tree_leaves_with_path(
                _leaf_sums(got, want), is_leaf=lambda t: isinstance(t, tuple))}
    by_leaf = {k: math.sqrt(num / den) for k, (num, den) in sums.items()}
    worst = max(by_leaf, key=lambda k: (not math.isfinite(by_leaf[k]),
                                        by_leaf[k]))
    whole = math.sqrt(sum(n for n, _ in sums.values())
                      / sum(d for _, d in sums.values()))
    return compare.Check(
        name, math.isfinite(by_leaf[worst]) and by_leaf[worst] <= bound,
        f"worst leaf {worst}: relative L2 {by_leaf[worst]:.3e}; whole "
        f"tree {whole:.3e}", bound)


# The step's loss against the float32 reference on the parameters a
# window of training left and the batch it trained on. The harness
# compares relative to max(|reference|, 1) and the window learns its one
# batch by heart (loss 0.0101, nearly all of it 0.01 x load balancing), so
# the bound is absolute and is set in that regime: sound runs on the chip
# read 1.6e-8 to 1.48e-6 apart (24 seeds; root mean square 8e-7). This
# is the coarse comparison. There a router at the TPU's default precision
# reads 6.5e-7 and 1.95e-6, one in bf16 2.6e-7 and 3.4e-6, the reference
# itself at default precision 8.5e-8 and 1.15e-6 and on parameters
# rounded to bf16 1.3e-6 and 2.4e-6: no lower precision is told from a
# sound run by this loss, and the router's and the gradients' checks
# below are what refuses them. A renormalised top-k reads 8.9e-6 and
# 1.7e-5 (PERF.md, PR 26).
LOSS_REL_BOUND = 5e-6
# Gradients of the probe, relative L2 of the worst leaf, against the
# reference given the program's expert indices: measured 1.40e-2 to
# 1.50e-2 on the chip over 26 seeds (the router, the q and k norm
# scales and the q projection are the worst leaves; the whole tree reads
# 1.29e-2), nearly all of it the bf16 activations the configuration
# states. Renormalised top-k weights read 0.40 to 0.44 and a capacity of
# 1.25 x the mean group 0.45; a wrong mask, scale or permutation reads
# O(1). It refuses wrong mathematics, not a lower precision: a router in
# bf16 reads 1.51e-2, inside (the router's own check is below).
GRAD_REL_L2_BOUND = 2.5e-2
# The program's router against the reference's softmax(h W_r) in float32
# at highest precision on the very input the program's router had, the
# largest relative distance of a probability over 8,192 tokens x 64
# experts: measured 1.7e-6 to 4.6e-6 on the chip (eleven seeds), what two
# summation orders leave. The product left at the TPU's default precision
# (float32 operands in one bf16 pass) reads 7.6e-3 and a router
# multiplied and stored in bf16 1.4e-2; the bound is near the geometric
# middle of 4.6e-6 and 7.6e-3. This is the check that holds the router to
# float32: the bf16 hidden states that feed it swap more experts than its
# own precision does, so the three comparisons around it pass a bf16
# router (PERF.md, PR 26).
ROUTER_REL_BOUND = 1e-4
# The program's choice of experts against the reference's own. Top-k is
# discontinuous, and the program's router sees bf16 hidden states that
# have been through bf16 attention, so some tokens swap their eighth
# and ninth expert: measured 0.9930 to 0.9942 of the assignments agree
# (27 seeds; 0.9929 with a router in bf16). A router fed something
# else (no norm, another layer's input) agrees on about k / E = 0.125.
CHOICES_AGREE_BOUND = 0.98
# ... and every disagreement is a near-tie: the largest gap in the
# reference's probabilities that the program's choice overrode, over
# the 8,192 tokens of the sample, measured 6.0e-4 to 1.20e-3 (26
# seeds, median 8.1e-4; 1.08e-3 and 1.12e-3 with a router in bf16; a
# probability is 1/64 = 1.6e-2 on average, so 8% of one). It is the
# largest of some 430 disagreements, a tail statistic whose second
# largest reading is 1.02e-3, so the bound leaves it 2.5 times the
# largest seen; a choice that is no tie overrides a gap of 1e-2 and more.
NEAR_TIE_BOUND = 3e-3


@dataclasses.dataclass
class SparseJob(Job):
    """``loss_and_routing(params, tokens) -> (loss, [routed a layer])``:
    the loss with what every layer's router saw and said, as
    ``models/moe.py`` sows it: ``router_input [T, d]``, ``router_probs
    [T, E]`` and the chosen ``experts [T, k]``, which ``check`` hands to
    the reference."""

    loss_and_routing: Callable | None = None


def _job(cfg: GPTConfig, config, per_chip_batch, seq_len,
         probe=None) -> SparseJob:
    """The job of one model instance; ``probe`` is the small instance
    its gradients are checked on (the probe itself checks nothing)."""
    model = GPT(cfg)
    coefs = {"load_balance": config["router_aux_loss_coef"],
             "router_z": config["router_z_loss_coef"]}

    def init(key):
        variables = model.init(key, jnp.zeros((1, seq_len), jnp.int32))
        return variables["params"], {}

    def make_batch(key, n_chips):
        return jax.random.randint(
            key, (n_chips * per_chip_batch, seq_len), 0, cfg.vocab_size,
            jnp.int32)

    def loss_and_routing(params, tokens, sow=True):
        """The training loss, and what ``models/moe.py`` sows of each
        layer's router (the training step asks for none of it)."""
        # one scope around all of it: JAX writes a transformation's name
        # (jvp, transpose) around the outermost scope, and the readers
        # look for "/lm_head/" (the loss names its projection as
        # models.GPT does) and "/moe_" further in
        with jax.named_scope("olmoe"):
            (hidden, aux), sown = model.apply(
                {"params": params}, tokens, return_hidden=True,
                return_aux=True, mutable=["intermediates"] if sow else [])
            ce = softmax_cross_entropy_fused(
                hidden[:, :-1], params["lm_head"], tokens[:, 1:],
                chunk=LOSS_CHUNK)
        routed = [{name: value[0] for name, value in
                   sown["intermediates"][f"block_{i}"]["moe"].items()}
                  for i in range(cfg.n_layers)] if sow else None
        return ce + sum(coefs[name] * aux[name] for name in coefs), routed

    def loss(params, extra, tokens):
        return loss_and_routing(params, tokens, sow=False)[0], extra

    def reference_loss(params, extra, tokens):
        value, routing = reference.loss(params, tokens, config)
        print("load at the end of the window (largest group over the mean, "
              "a layer): " + ", ".join(
                  f"{load(r['own'], cfg.n_experts):.3f}" for r in routing),
              flush=True)
        return value

    def check(key):
        """On the probe instance (same widths and sequence length, so
        the same attention path and the same expert shapes): gradients
        against the reference given the program's expert indices, the
        program's router against a float32 one on its own input, and
        the two choices of experts against each other."""
        if probe is None:
            return []
        marks = [("", time.perf_counter())]
        mark = lambda name: marks.append((name, time.perf_counter()))
        key_sample, key_probe = jax.random.split(key)
        sample = make_batch(key_sample, 1)[:SAMPLE_SEQUENCES]
        probe_params, _ = jax.jit(probe.init)(key_probe)
        (_, routed), got = jax.jit(jax.value_and_grad(
            probe.loss_and_routing, has_aux=True))(probe_params, sample)
        choices = [r["experts"] for r in routed]
        jax.block_until_ready(got)
        mark("the probe's gradients")
        (_, routing), want = reference.loss_and_grad(
            probe_params, sample, config, choices)
        jax.block_until_ready(want)
        mark("the reference's")
        checks = [worst_leaf_close(
            f"grad_vs_reference_given_experts_{probe.facts['n_layers']}"
            f"_layers", got, want, GRAD_REL_L2_BOUND)]
        mark("leaf by leaf")
        distance = tuple(
            router_distance(r, probe_params[f"block_{i}"]["moe"]["router"],
                            cfg.experts_per_token)
            for i, r in enumerate(routed))
        checks.append(compare.holds(
            "router_is_float32", max(distance) <= ROUTER_REL_BOUND,
            f"largest |p / p_ref - 1| on the router's own input, a layer: "
            f"{distance}", ROUTER_REL_BOUND))
        agree, gap = zip(*(compare_choices(c, r["probs"], r["own"])
                           for c, r in zip(choices, routing)))
        print("load of a fresh initialisation, as at the first step "
              "(largest group over the mean, a layer): " + ", ".join(
                  f"{load(c, cfg.n_experts):.3f}" for c in choices),
              flush=True)
        checks.append(compare.holds(
            "experts_agree_with_reference", min(agree) >= CHOICES_AGREE_BOUND,
            f"share of assignments, a layer: {agree}", CHOICES_AGREE_BOUND))
        checks.append(compare.holds(
            "disagreements_are_near_ties", max(gap) <= NEAR_TIE_BOUND,
            f"largest probability gap overridden, a layer: {gap}",
            NEAR_TIE_BOUND))
        mark("router and choices")
        print("seconds of the family's check: " + ", ".join(
            f"{name} {t - t0:.1f}"
            for (_, t0), (name, t) in zip(marks, marks[1:])), flush=True)
        return checks

    rows = per_chip_batch * seq_len * cfg.experts_per_token
    return SparseJob(
        loss_and_routing=loss_and_routing, item="tokens",
        items_per_step_per_chip=per_chip_batch * seq_len,
        flops_per_item=train_flops_per_token(
            cfg.n_layers, cfg.d_model, cfg.n_experts, cfg.experts_per_token,
            cfg.d_ff, cfg.vocab_size, seq_len),
        init=init, make_batch=make_batch, loss=loss,
        optimizer=lambda: optimizer_from(config["optimizer"]),
        reference_loss=reference_loss, loss_rel_bound=LOSS_REL_BOUND,
        check=check, probe=probe,
        facts={"n_params": n_params(cfg.n_layers, cfg.d_model, cfg.n_experts,
                                    cfg.d_ff, cfg.vocab_size),
               "n_layers": cfg.n_layers, "remat": cfg.remat,
               "moe": {"layers": cfg.n_layers, "rows": rows,
                       "experts": cfg.n_experts, "d_model": cfg.d_model,
                       "d_expert": cfg.d_ff,
                       "itemsize": jnp.dtype(cfg.dtype).itemsize},
               "attention": {"batch": per_chip_batch, "heads": cfg.n_heads,
                             "seq_len": seq_len,
                             "head_dim": cfg.d_model // cfg.n_heads}})


def build(config: dict, traffic: dict) -> Job:
    seq_len, batch = traffic["seq_len"], traffic["per_chip_batch"]
    cfg = _model_config(config, seq_len)
    probe = _job(dataclasses.replace(
        cfg, n_layers=min(PROBE_LAYERS, cfg.n_layers)), config, batch,
        seq_len)
    return _job(cfg, config, batch, seq_len, probe=probe)
