"""The ``phi4flash`` family (Microsoft's Phi-4-mini-flash-reasoning; SambaY,
arXiv:2507.06607): ``horovod_tpu.models.GPT`` with one mixer a layer, a
decoder layer of the source being two of them (its mixer, then ``-`` the
gated MLP), for **a pipeline stage of whole layers**: the published layers
``first_layer`` to ``first_layer + num_hidden_layers - 1`` as they stand.
Which mixer a published layer has is the source's rule
(``reference.layer_kind``): ``A`` Mamba-1, ``W`` differential attention
inside a window, ``*`` the same over every causal key (its keys and values
are what every later attention reads), ``U`` a gated memory unit on the
last Mamba-1 layer's scan output, ``X`` differential attention with a query
of its own over the full layer's keys and values. LayerNorm, biases on the
attention's projections, no positional term, a tied embedding.

Configuration keys (the source's names): ``vocab_size``,
``num_hidden_layers``, ``hidden_size``, ``intermediate_size``,
``num_attention_heads``, ``num_key_value_heads``, ``sliding_window``,
``layer_norm_eps``, ``mb_per_layer``, ``tie_word_embeddings``; the Mamba
sizes ``mamba_d_state``, ``mamba_d_conv``, ``mamba_expand``,
``mamba_dt_rank``; plus ``first_layer``, ``dtype``, ``remat``,
``use_flash``, ``optimizer``. ``num_hidden_layers`` and ``vocab_size`` give
what is **held here**; ``published`` holds the model's own counts, from
which the program takes the kinds by layer number. What the package does
not build is refused by name. Traffic keys: ``per_chip_batch``,
``seq_len``.

The loss never holds the float32 logits whole: the model returns its last
hidden states and the package's chunked
``ops.losses.softmax_cross_entropy_fused`` multiplies them by the tied
embedding a chunk of positions at a time.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable

import jax
import jax.numpy as jnp

from horovod_tpu.models import GPT, GPTConfig
from horovod_tpu.models.mamba import step_rank
from horovod_tpu.ops.losses import softmax_cross_entropy_fused

from chipbench import compare
from chipbench.families import optimizer_from
from chipbench.families.afmoe import band_pairs
from chipbench.families.mellum import _far
from chipbench.families.nemotron_h import HybridJob, _leaf_sums
from chipbench.reference import phi4flash as reference

MAMBA, WINDOWED, FULL, UNIT, CROSS = (
    reference.MAMBA, reference.WINDOWED, reference.FULL, reference.UNIT,
    reference.CROSS)
LETTER = {MAMBA: "A", WINDOWED: "W", FULL: "*", UNIT: "U", CROSS: "X"}
SUBTREE = {MAMBA: "mamba", WINDOWED: "attn", FULL: "attn", UNIT: "gmu",
           CROSS: "cross"}

# What --rehearse shrinks for a CPU dry run (control flow only): the same
# six published layers of a small model, a window a quarter of the
# sequence. Float32, as olmoe's.
REHEARSAL = {
    "config": {
        "vocab_size": 256, "num_hidden_layers": 6, "first_layer": 14,
        "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 8, "num_key_value_heads": 4,
        "sliding_window": 16, "mamba_d_state": 4, "mamba_d_conv": 4,
        "mamba_expand": 2, "mamba_dt_rank": 4, "dtype": "float32",
        "published": {"num_hidden_layers": 32, "vocab_size": 2048}},
    "traffic": {"seq_len": 64, "per_chip_batch": 1}}

# The probe: the published layers 15 to 19, one mixer of every kind (a
# window, Mamba-1 with the memory, the full layer with the shared keys and
# values, a unit, a cross layer) and one MLP after them, at the published
# widths: 263 M parameters, so that three gradient programs fit beside each
# other after the window.
PROBE_FIRST_LAYER, PROBE_LAYERS = 15, 5
# Positions of the probe's gradient comparison: four times the window, so
# that three queries in four lose keys to it, and 16 chunks of the scan;
# the program's attention is the Pallas kernels, as in the cell.
PROBE_SEQ_LEN = 2048
# Positions of the sequence whose logits the loss holds at once: 1024 rows
# against the 25008 x 2560 embedding, 102 MB of float32 logits.
LOSS_CHUNK = 1024


def layer_pattern(first_layer, n_layers, published_layers,
                  mlp_after_each=True) -> str:
    """The published layers ``first_layer`` on as the package's pattern:
    layer ``l`` is the mixer its number gives and then its MLP; without
    ``mlp_after_each`` the mixers alone and one MLP after the last."""
    mixers = [LETTER[reference.layer_kind(l, published_layers)]
              for l in range(first_layer, first_layer + n_layers)]
    return ("".join(m + "-" for m in mixers) if mlp_after_each
            else "".join(mixers) + "-")


def forward_macs_per_token(pattern, d_model, d_ff, heads, kv_heads, head_dim,
                           window, expand, state, rank, vocab_size,
                           seq_len) -> dict:
    """Multiply-adds a token of one forward pass, by kind of layer and for
    the head, from shapes; ``vocab_size`` is what is held.

    The attention of the three kinds: the projections (``*`` and ``W``: q,
    k, v and o; ``X``: q and o alone) and, a visible (query, key) pair,
    ``H / 2`` pairs of heads x 2 softmax maps x (``head_dim`` for the score
    + ``2 head_dim`` for the pair's value): ``*`` and ``X`` over the ``(s +
    1) / 2`` causal keys a query sees on average, ``W`` over **the band's
    pairs and not the causal ones, whatever the program executes**,
    ``band_pairs / s`` keys a query. ``A``: the four projections (``W_in``,
    ``W_x``, ``W_dt``, ``W_out``); the convolution and **the scan are
    elementwise and count nothing here** (no product on the MXU:
    ``mamba_scan_macs_per_step`` has the scan's own count). ``U`` its two
    matrices, ``-`` its three. The tied head once; the embedding lookup,
    the norms and the biases are free."""
    inner = expand * d_model
    a_pair = heads // 2 * 2 * 3 * head_dim
    q_o = 2 * d_model * heads * head_dim
    k_v = 2 * d_model * kv_heads * head_dim
    layer = {
        "*": q_o + k_v + a_pair * (seq_len + 1) / 2,
        "W": q_o + k_v + a_pair * band_pairs(seq_len, window) / seq_len,
        "X": q_o + a_pair * (seq_len + 1) / 2,
        "A": (d_model * 2 * inner + inner * (rank + 2 * state)
              + rank * inner + inner * d_model),
        "U": 2 * d_model * inner,
        "-": 3 * d_model * d_ff,
    }
    macs = {kind: pattern.count(kind) * each for kind, each in layer.items()}
    macs["head"] = vocab_size * d_model
    return macs


def n_params(pattern, d_model, d_ff, heads, kv_heads, head_dim, expand,
             state, rank, conv, vocab_size) -> int:
    """The tied embedding and the final LayerNorm; a layer its LayerNorm
    and its mixer (biases, the four lambda vectors and the pair's norm
    with the attention)."""
    inner = expand * d_model
    differential = 4 * head_dim + 2 * head_dim
    q_o = 2 * d_model * heads * head_dim + heads * head_dim + d_model
    k_v = 2 * (d_model + 1) * kv_heads * head_dim
    layer = {
        "*": q_o + k_v + differential, "W": q_o + k_v + differential,
        "X": q_o + differential,
        "A": (d_model * 2 * inner + (conv + 1) * inner
              + inner * (rank + 2 * state) + (rank + 1) * inner
              + inner * state + inner + inner * d_model),
        "U": 2 * d_model * inner, "-": 3 * d_model * d_ff}
    return (vocab_size * d_model + 2 * d_model
            + sum(layer[kind] + 2 * d_model for kind in pattern))


def mamba_scan_macs_per_step(layers, batch, seq_len, channels, state,
                             remat) -> float:
    """Multiply-adds the recurrence requires a training step, elementwise
    (none is a product on the MXU): a position and (channel, state) the
    decay's argument, what the position adds, the state's update and its
    share of the sum over the state, four forward; again under ``remat``;
    twice that backward."""
    return float(layers * batch * seq_len * channels * state * 4
                 * ((2 if remat else 1) + 2))


def mamba_scan_bytes_per_step(layers, batch, seq_len, channels, state, remat,
                              itemsize=2) -> float:
    """Bytes the recurrence has to move once a pass, a position: the
    forward reads ``u`` (``itemsize``), the float32 ``delta``, ``B`` and
    ``C`` and writes ``m``; the backward reads the same with ``dm`` for
    ``m`` and writes the four gradients. Nothing of ``[seq, D, N]`` is
    counted: a program that held the states whole would move 5.4 GB a layer
    and pass more."""
    inputs = channels * (itemsize + 4) + 2 * state * itemsize
    forward = inputs + channels * itemsize
    backward = forward + inputs
    return float(layers * batch * seq_len
                 * ((2 if remat else 1) * forward + backward))


def attn_window_macs_per_step(layers, batch, heads, seq_len, head_dim, window,
                              remat) -> float:
    """Multiply-adds a training step requires of the windowed layers'
    products over positions (scope ``attn_window``), ``heads`` softmax maps
    of ``head_dim`` on a value of ``2 head_dim``: ``q k^T`` and ``p v`` in
    the forward pass (``3 head_dim`` a pair and map); the scores again,
    ``dO v^T``, ``p^T dO``, ``dS k`` and ``dS^T q`` in the backward (``7
    head_dim``); under ``remat`` the forward pass a second time.
    ``band_pairs`` a sequence and map, exactly and not by tiles."""
    pairs = batch * heads * band_pairs(seq_len, window)
    return float(layers * pairs * head_dim * ((2 if remat else 1) * 3 + 7))


def attn_window_bytes_per_step(layers, batch, heads, kv_heads, seq_len,
                               head_dim, remat, itemsize=2) -> float:
    """Bytes the same products have to move once a call and position: the
    forward reads q (a map), k (a key head) and the pairs' values (a key
    head's width each, read once for both maps) and writes o (``2
    head_dim`` a map) and a float32 log-sum-exp; the backward reads those
    and dO and two float32 statistics and writes dQ, dK and dV."""
    forward = itemsize * head_dim * (3 * heads + 2 * kv_heads) + 4 * heads
    backward = (itemsize * head_dim * (6 * heads + 4 * kv_heads) + 8 * heads)
    return float(layers * batch * seq_len
                 * ((2 if remat else 1) * forward + backward))


def _model_config(config, seq_len, mlp_after_each=True) -> GPTConfig:
    for key, want in (("model_type", "phi4flash"), ("hidden_act", "silu"),
                      ("tie_word_embeddings", True), ("mlp_bias", False),
                      ("lm_head_bias", False), ("mb_per_layer", 2),
                      ("embd_pdrop", 0), ("resid_pdrop", 0)):
        if config.get(key, want) != want:
            raise ValueError(f"{key} is {config[key]!r}: the package builds "
                             f"{want!r} alone")
    d_model, heads = config["hidden_size"], config["num_attention_heads"]
    if d_model % heads:
        raise ValueError(f"hidden_size {d_model} over {heads} heads")
    pattern = layer_pattern(
        config["first_layer"], config["num_hidden_layers"],
        config["published"]["num_hidden_layers"], mlp_after_each)
    return GPTConfig(
        vocab_size=config["vocab_size"], n_layers=len(pattern),
        layer_pattern=pattern, d_model=d_model, n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        head_dim=d_model // heads, d_ff=config["intermediate_size"],
        mlp_act="swiglu", rotary=False, attn_window_rotary=False,
        attn_window=config["sliding_window"], attn_differential=True,
        attn_bias=True, layer_norm=True, norm_eps=config["layer_norm_eps"],
        first_layer=config["first_layer"],
        mamba_expand=config["mamba_expand"],
        mamba_state=config["mamba_d_state"],
        mamba_conv=config["mamba_d_conv"],
        mamba_rank=config["mamba_dt_rank"], tie_embeddings=True,
        max_seq_len=seq_len, dtype=jnp.dtype(config["dtype"]),
        remat=config["remat"], use_flash=config["use_flash"])


def _sizes(cfg: GPTConfig, seq_len=None) -> dict:
    """The arguments the two counting functions share."""
    sizes = dict(
        pattern=cfg.layer_pattern, d_model=cfg.d_model, d_ff=cfg.d_ff,
        heads=cfg.n_heads, kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        expand=cfg.mamba_expand, state=cfg.mamba_state,
        rank=step_rank(cfg.d_model, cfg.mamba_rank),
        vocab_size=cfg.vocab_size)
    if seq_len is None:
        return {**sizes, "conv": cfg.mamba_conv}
    return {**sizes, "window": cfg.attn_window, "seq_len": seq_len}


# Every bound below lies between two readings on the chip (v5e, my chip
# runs, PR 66; `benchmarks/phi4flash_wrong_programs.py` makes them again):
# the largest a sound run gave over its seeds (nine whole runs of the cell
# on nine seeds; the script's sound programs on six more, the probe's
# measures on eleven of them as they stand now) and what a lower precision
# or wrong mathematics gives (the script's eight wrong programs on seeds
# 2147660101, all measures, and 2147661006, the probe's as they stand now).
# PERF.md section 6 has the table.
#
# The step's loss against the float32 reference on the parameters a window
# of training left and the batch it trained on, relative to the
# reference's. Sound 4.8e-6 to 7.55e-5 in eight runs at the configuration's
# rate of 1e-6 (8.8659 against 8.8652 after 31 steps; at 1e-5 the one batch
# was learned to 0.35 and the two stood 1.28e-3 apart, which is why the
# rate is what it is). A fresh model's loss is ln 25,008 whatever its
# mixers do, so this tells little (the checks below tell the rest): `gpt`'s
# bound, the accepted cells' one, 13 times the largest sound reading.
LOSS_REL_BOUND = 1e-3
# Gradients of the probe in the cell's dtype (bf16 products, the Pallas
# kernels) against the reference's, relative L2. Over the whole tree: sound
# 2.59e-2 to 2.90e-2 on seventeen seeds (every leaf of size stands about 3%
# away: the embedding is drawn at normal(0.02), as `mellum2-s16384`'s read
# 1.6 to 2.1% under that draw); **a LayerNorm without its mean 0.135 and
# 0.144**, the cross layer on its own keys 0.738, the unit on the gated
# output 0.918, the other four wrong programs 0.99 to 1.19; the scan's
# decays and state in bf16 2.86e-2 and 3.10e-2, which this check is not
# asked to tell (the float32 program's are). The geometric middle of
# 2.90e-2 and 0.135: 2.2 times of room on either side.
GRAD_REL_L2_BOUND = 6.3e-2
# ... and at the worst leaf of size (`LEAF_FLOOR`): sound 3.11e-2 to 4.05e-2
# on eleven seeds; a LayerNorm without its mean 0.161, the unit on the gated
# output 1.04, the rest above it (the decays in bf16 9.25e-2, not asked of
# this check). The geometric middle of 4.05e-2 and 0.161: twice either way.
GRAD_WORST_LEAF_BOUND = 8e-2
# The probe **built with float32 products and run at the highest
# precision**, through the same kernels and the same chunked scan: its loss
# and its gradients against the reference's. Nothing is left to read but
# float32's own rounding, so this is the check that holds the mathematics
# (the window, lambda and lambda_init by the layer's number, the pair's
# norm, what the unit and the cross layer read, the LayerNorm's mean) and
# what the module constants state as float32 whatever the dtype (the scan's
# decays and state: `models/mamba.py`'s `DECAY_DTYPE`).
#
# The loss: sound 0 to 2.7e-7 on seventeen seeds; a LayerNorm without its
# mean 1.12e-4 and 3.63e-4, lambda 0 1.84e-4, no window 5.41e-4, the other
# four 4.05e-4 to 4.38e-3 (the decays in bf16 1.79e-7 and 3.77e-5: a fresh
# model's loss hardly sees them, and this check is not asked to). The
# geometric middle of 2.7e-7 and 1.12e-4: 20 times of room on either side.
FLOAT32_LOSS_BOUND = 5.5e-6
# The gradients over the whole tree: sound 3.36e-5 to 3.59e-5 on seventeen
# seeds; **the scan's decays and state in bf16 6.22e-3 and 1.70e-2**, a
# LayerNorm without its mean 0.133, the other six 0.74 to 1.19. The
# geometric middle of 3.59e-5 and 6.22e-3: 13 times of room on either side.
FLOAT32_GRAD_BOUND = 4.7e-4
# ... and at the worst leaf of size: sound 1.30e-4 to 3.60e-4 on eleven
# seeds; the decays in bf16 8.72e-2 (the Mamba-1 layer's `x_proj`), a
# LayerNorm without its mean 0.158, the other six 1.04 to 6.57. The
# geometric middle of 3.60e-4 and 8.72e-2: 15 times of room on either side.
FLOAT32_WORST_LEAF_BOUND = 5.6e-3
# A mixer's output at the cell's 16,384 positions against the float32
# reference on the mixer's own input (a unit on the memory **the last
# Mamba-1 layer sowed**, a cross layer on the keys and values **the
# reference makes of the full layer's input**), relative L2: bf16 products
# and a bf16 result; on the parameters the window left (nine runs) and on a
# fresh initialisation (three seeds), which read alike. Each bound the
# geometric middle of the largest sound reading and the nearest wrong one
# (seed 2147660101).
MIXER_BOUNDS = {
    # Sound 5.22e-3 to 5.24e-3 in layer 14 and 7.76e-3 to 7.86e-3 in layer
    # 16; the decays and the state in bf16 1.36e-2 and **6.11e-2** (layer
    # 16's steps are longer-lived: it fails there).
    MAMBA: 2.2e-2,
    # Sound 7.37e-3 to 8.47e-3; the pair's norm left out 0.917, lambda 0
    # 0.945, no window 0.999, lambda_init at layer number 1 2.35.
    WINDOWED: 8.8e-2,
    # Sound 6.18e-3 to 7.97e-3; the pair's norm left out 0.958, lambda 0
    # 1.18, lambda_init at layer number 1 2.77.
    FULL: 8.7e-2,
    # Sound 6.01e-3 to 6.08e-3; the unit reading the gated output 0.971.
    UNIT: 7.7e-2,
    # Sound 6.59e-3 to 8.27e-3; lambda 0 0.570, the pair's norm left out
    # 0.958, the layer reading its own input's keys 1.42, lambda_init at
    # layer number 1 2.27.
    CROSS: 6.9e-2,
}
# ... and a Mamba-1 layer's memory (the scan output before the gate): sound
# 3.48e-3 to 3.52e-3 in layer 14 and 5.36e-3 to 5.46e-3 in layer 16; the
# decays and the state in bf16 1.30e-2 and 6.11e-2.
MEMORY_BOUND = 1.8e-2

# The worst-leaf measures go over the leaves whose reference gradient's norm
# is at least this share of the largest leaf's. A smaller leaf's gradient is
# what is left of a sum that nearly cancels, its size goes with the seed and
# its relative distance is rounding over that size: a key projection's bias
# moves every score of a row alike, so its gradient is zero but for rounding
# (1e-9 of the largest leaf's, a relative distance of 7 to 4e4); the
# windowed layer's value bias stood at 2.0e-3 to 4.3e-2 of the largest leaf
# over six seeds and 0.232 to 0.03 away in bf16, 2.2e-3 to 1.4e-4 in float32;
# the four lambda vectors (one scalar's gradient) at 1e-3 to 6e-3 and 3 to
# 26% away in bf16 (my chip runs, PR 66; the seed that read 0.232 failed a
# bound that had held on five). At 1e-2 and over, 27 to 32 of the probe's 64
# leaves, a leaf's distance no longer goes with its size. Every leaf stays
# in the whole-tree measures, where it weighs what its size is.
LEAF_FLOOR = 1e-2


def gradients_close(name, got, want, whole_bound, worst_bound) -> list:
    """Relative L2 over the whole tree, and at the worst of the leaves whose
    reference gradient is at least ``LEAF_FLOOR`` of the largest leaf's."""
    sums = {jax.tree_util.keystr(path): (float(num), float(den))
            for path, (num, den) in jax.tree_util.tree_leaves_with_path(
                _leaf_sums(got, want), is_leaf=lambda t: isinstance(t, tuple))}
    floor = LEAF_FLOOR ** 2 * max(den for _, den in sums.values())
    by_leaf = {k: math.sqrt(num / den) for k, (num, den) in sums.items()
               if den >= floor}
    worst = max(by_leaf, key=lambda k: (not math.isfinite(by_leaf[k]),
                                        by_leaf[k]))
    whole = math.sqrt(sum(n for n, _ in sums.values())
                      / sum(d for _, d in sums.values()))
    print(f"{name} by leaf ({len(by_leaf)} of {len(sums)} held): " + ", ".join(
        f"{k} {v:.2e}" for k, v in sorted(by_leaf.items(),
                                          key=lambda kv: -kv[1])[:8]),
          flush=True)
    return [
        compare.Check(name, math.isfinite(whole) and whole <= whole_bound,
                      f"relative L2 {whole:.3e}", whole_bound),
        compare.Check(f"{name}_worst_leaf", math.isfinite(by_leaf[worst])
                      and by_leaf[worst] <= worst_bound,
                      f"{worst}: relative L2 {by_leaf[worst]:.3e}",
                      worst_bound)]


@dataclasses.dataclass
class Phi4FlashJob(HybridJob):
    """``config``: the configuration this instance's reference reads (its
    own ``first_layer``: the probe's is not the cell's); ``cfg``: its
    model's; ``layers_close(params, extra, tokens)``: every mixer against
    the reference's, each on its own input."""

    config: dict | None = None
    cfg: GPTConfig | None = None
    layers_close: Callable | None = None


def _job(cfg: GPTConfig, config, per_chip_batch, seq_len,
         probe=None) -> Phi4FlashJob:
    """The job of one model instance on ``config`` (whose ``first_layer``
    is this instance's); ``probe`` is the small instance its gradients are
    checked on (the probe itself checks nothing)."""

    def init(key):
        return GPT(cfg).init(
            key, jnp.zeros((1, seq_len), jnp.int32))["params"], {}

    def make_batch(key, n_chips):
        return jax.random.randint(
            key, (n_chips * per_chip_batch, seq_len), 0, cfg.vocab_size,
            jnp.int32)

    def loss_and_sown(params, extra, tokens, sow=True, cfg=cfg):
        # one scope around all of it: JAX writes a transformation's name
        # (jvp, transpose) around the outermost scope, and the readers
        # look for "/lm_head/", "/mamba_" and "/attn_" further in
        with jax.named_scope("phi4flash"):
            hidden, sown = GPT(cfg).apply(
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

    # (block, published layer, kind) of every mixer
    published = config["published"]["num_hidden_layers"]
    mixers, l = [], cfg.first_layer
    for i, letter in enumerate(cfg.layer_pattern):
        if letter != "-":
            mixers.append((f"block_{i}", l, reference.layer_kind(
                l, published)))
            l += 1
    # what `layers_close` read on the timed model: the harness hands the
    # window's parameters and batch to `reference_loss` alone and asks
    # `check` for the comparisons afterwards
    timed = []

    def layers_close(params, extra, tokens, trained=False) -> list:
        """Every mixer at the length of ``tokens``, each on the input it
        had in this model's forward pass, against the reference's. A unit's
        memory is the one the nearest Mamba-1 layer before it sowed, a
        cross layer's keys and values the reference's own of the full
        layer's input: a program whose readers read something else
        fails here."""
        blocks = {block for block, _, _ in mixers}
        sown = jax.jit(lambda *a: {
            block: s for block, s in loss_and_sown(*a)[1].items()
            if block in blocks})(params, extra, tokens)
        before = "trained_" if trained else ""
        length = tokens.shape[1]
        checks, memory, kv = [], None, None

        def hold(name, block, far, bound):
            checks.append(compare.holds(
                f"{before}{name}_vs_reference_{length}",
                math.isfinite(far) and far <= bound,
                f"{block}: relative L2 on its own input: {far:.3e}", bound))

        for block, l, kind in mixers:
            s, p = sown[block], params[block][SUBTREE[kind]]
            name = f"layer_{l}_{kind}"
            if kind == MAMBA:
                want, want_memory = reference.mixer(
                    kind, s["mamba_input"], p, config, l)
                memory = s["mamba_memory"]
                hold(f"{name}_memory", block, _far(memory, want_memory),
                     MEMORY_BOUND)
                got = s["mamba_output"]
            elif kind == UNIT:
                want = reference.mixer(kind, s["gmu_input"], p, config, l,
                                       read=memory)
                got = s["gmu_output"]
            else:
                if kind == FULL:
                    kv = reference.keys_values_of(s["attn_input"], p)
                want = reference.mixer(
                    kind, s["attn_input"], p, config, l,
                    read=kv if kind == CROSS else None)
                got = s["attn_output"]
            hold(name, block, _far(got, want), MIXER_BOUNDS[kind])
        return checks

    def reference_loss(params, extra, tokens):
        # the timed model's own layers, on the parameters the window left
        # and the batch it trained on
        timed[:] = layers_close(params, extra, tokens, trained=True)
        return reference.loss(params, tokens, config)

    def check(key):
        """On the probe (the published layers 15 to 19 and one MLP, at the
        published widths): gradients at ``PROBE_SEQ_LEN`` positions against
        the reference's, in the cell's dtype and with float32 products at
        the highest precision; then what ``reference_loss`` read of the
        timed model's own layers at the cell's length."""
        if probe is None:
            return []
        marks = [("", time.perf_counter())]
        mark = lambda name: marks.append((name, time.perf_counter()))
        key_sample, key_probe = jax.random.split(key)
        short = make_batch(key_sample, 1)[:1, :min(PROBE_SEQ_LEN, seq_len)]
        params, extra = jax.jit(probe.init)(key_probe)
        want_loss, want = reference.loss_and_grad(params, short,
                                                  probe.config)
        jax.block_until_ready(want)
        mark("the reference's gradients")
        grad = lambda cfg: jax.jit(jax.value_and_grad(
            lambda p: probe.loss_and_sown(p, extra, short, sow=False,
                                          cfg=cfg)[0]))(params)
        name = f"{probe.cfg.layer_pattern}_{short.shape[1]}"
        _, got = grad(probe.cfg)
        checks = gradients_close(f"grad_vs_reference_{name}", got, want,
                                 GRAD_REL_L2_BOUND, GRAD_WORST_LEAF_BOUND)
        mark("the probe's")
        with jax.default_matmul_precision("highest"):
            got_loss, got = grad(dataclasses.replace(probe.cfg,
                                                     dtype=jnp.float32))
        checks.append(compare.close(
            f"float32_loss_vs_reference_{name}", float(got_loss),
            float(want_loss), FLOAT32_LOSS_BOUND))
        checks += gradients_close(
            f"float32_grad_vs_reference_{name}", got, want,
            FLOAT32_GRAD_BOUND, FLOAT32_WORST_LEAF_BOUND)
        mark("with float32 products")
        checks += timed
        print("seconds of the family's check: " + ", ".join(
            f"{name} {t - t0:.1f}"
            for (_, t0), (name, t) in zip(marks, marks[1:])), flush=True)
        return checks

    macs = forward_macs_per_token(**_sizes(cfg, seq_len))
    tokens = per_chip_batch * seq_len
    itemsize = jnp.dtype(cfg.dtype).itemsize
    scan = {"layers": cfg.layer_pattern.count("A"), "batch": per_chip_batch,
            "seq_len": seq_len, "channels": cfg.mamba_expand * cfg.d_model,
            "state": cfg.mamba_state, "remat": cfg.remat}
    band = {"layers": cfg.layer_pattern.count("W"), "batch": per_chip_batch,
            "heads": cfg.n_heads, "seq_len": seq_len,
            "head_dim": cfg.head_dim, "remat": cfg.remat}
    return Phi4FlashJob(
        config=config, cfg=cfg, loss_and_sown=loss_and_sown, item="tokens",
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
               # what mamba_scan_roofline reads: the recurrence's sizes and
               # what a step requires of it
               "mamba": {
                   **scan,
                   "scan_macs_per_step": mamba_scan_macs_per_step(**scan),
                   "scan_bytes_per_step": mamba_scan_bytes_per_step(
                       **scan, itemsize=itemsize)},
               # what attn_window_roofline reads: the windowed layers'
               # products over positions, two softmax maps a pair of heads
               # on a value of twice the head's width
               "attn_window": {
                   **band, "kv_heads": cfg.n_kv_heads,
                   "window": cfg.attn_window,
                   "band_pairs": band_pairs(seq_len, cfg.attn_window),
                   "macs_per_step": attn_window_macs_per_step(
                       **band, window=cfg.attn_window),
                   "bytes_per_step": attn_window_bytes_per_step(
                       **band, kv_heads=cfg.n_kv_heads,
                       itemsize=itemsize)}})


def build(config: dict, traffic: dict) -> Phi4FlashJob:
    seq_len, batch = traffic["seq_len"], traffic["per_chip_batch"]
    cfg = _model_config(config, seq_len)
    probe_config = {**config, "first_layer": PROBE_FIRST_LAYER,
                    "num_hidden_layers": PROBE_LAYERS}
    probe = _job(
        _model_config(probe_config, seq_len, mlp_after_each=False),
        probe_config, batch, seq_len)
    return _job(cfg, config, batch, seq_len, probe=probe)
