"""Decoder-only transformer (GPT) — the flagship model for multi-chip
sharding (dp/tp/sp over a mesh).

The reference framework is model-agnostic (it ships gradients for
arbitrary TF/torch models); its benchmark models are CNNs. A modern
distributed-training framework is exercised hardest by transformer LMs, so
this is the model `__graft_entry__.py` shards over dp×tp×sp and the
long-context (ring attention) path targets.

TPU-first choices:
- bfloat16 activations, fp32 params + fp32 softmax/logits accumulation.
- shapes static, attention as batched einsums on the MXU.
- ``param_partition_spec`` maps every parameter to a PartitionSpec
  (Megatron-style tensor parallelism: column-parallel qkv/up projections,
  row-parallel out/down projections) so pjit/XLA inserts the ICI
  collectives — the TPU-native replacement for NCCL allreduce layers.
"""

from __future__ import annotations

import dataclasses
from importlib import import_module as _module
from typing import Callable, Optional, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops import _pallas


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 32000
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    # Grouped-query attention (LLaMA-2/Mistral lineage): number of K/V
    # heads; None → n_heads (standard MHA), 1 → MQA. Must divide
    # n_heads. Shrinks the K/V projection params and K/V HBM traffic by
    # n_heads/n_kv_heads. The flash kernels read the shared K/V heads
    # zero-copy (K/V block index-map aliasing: head hi reads kv head
    # hi // group); the flash backward emits per-query-head dK/dV then
    # group-sums (one transient full-h gradient array), and the
    # ring-mesh and einsum paths broadcast K/V to full heads before
    # attending — budget those paths at n_heads. With tensor
    # parallelism pass tp_size to param_partition_spec: K/V replicate
    # when n_kv_heads < tp (Megatron MQA layout).
    n_kv_heads: Optional[int] = None
    d_ff: int = 1024
    max_seq_len: int = 1024
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False  # jax.checkpoint each block (HBM ↔ FLOPs trade)
    # Attention implementation. False (default) = einsum-softmax; True =
    # pallas flash kernel; "auto" = pick per local sequence length by
    # the rule in ops/flash_attention.py (resolve_flash): the kernels
    # from 1024 positions up where the length is a multiple of 128, the
    # crossover measured in the benchmark's cells on a v5e (PERF.md
    # section 6, PR 27), einsum for every other length. "auto" only
    # upgrades to flash on a TPU backend (on the CPU the kernel runs in
    # pallas interpret mode, far slower than einsum). True needs
    # sequence blocks that are multiples of 8 wherever the kernel is
    # compiled; "auto" never asks for one that is not. Flash requires
    # the LOCAL sequence to be the full, contiguous sequence (its causal
    # mask is positional-by-block): under plain GSPMD sequence
    # parallelism the trace-time shape cannot reveal the sharding, so
    # neither True nor
    # "auto" is safe there — keep False, or use ring_mesh, where flash
    # composes with SP correctly (the ring schedule owns the blocks and
    # "auto" decides by the per-shard block length).
    use_flash: Union[bool, str] = False
    # Explicit ring-attention sequence parallelism: set to the
    # jax.sharding.Mesh the model runs under (must carry an 'sp' axis).
    # Attention then runs parallel/sequence.py's ring schedule under
    # shard_map — K/V shards stream over ICI with lax.ppermute instead
    # of GSPMD's allgather of the full K/V, and use_flash=True runs the
    # pallas kernel per block. Peak attention memory is O(seq/N).
    # hash/eq exclude nothing: Mesh is hashable, so the config stays a
    # valid jit-static argument.
    ring_mesh: Optional[object] = None
    # Sparse blocks (models/moe.py): n_experts > 0 replaces every block's
    # MLP by a dropless top-k router over n_experts SwiGLU experts, each
    # d_ff wide, experts_per_token of them a token. 0 = the dense MLP.
    n_experts: int = 0
    experts_per_token: int = 0
    # RMSNorm over the whole projected width of q and of k, before the
    # heads are split and rotated (OLMoE, OLMo 2).
    qk_norm: bool = False
    # False gives the logits a matrix of their own, `lm_head`
    # [vocab, d_model], in place of the embedding's transpose.
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    # One mixer a layer, in the order a string gives (hybrid models:
    # Nemotron-H's ``hybrid_override_pattern``): layer i is ``x +
    # mixer(RMSNorm(x))`` with the mixer ``layer_pattern[i]`` names, a
    # letter of ``KINDS`` below: the table holds a record a kind of layer
    # (what it is, which fields here it reads, how its leaves are
    # sharded). None (default) = every layer the attention + MLP (or
    # expert) pair of ``Block``.
    layer_pattern: Optional[str] = None
    # False leaves q and k unrotated: attention without a positional
    # term (the state-space or delta-rule layers of a hybrid carry the
    # order). Read by ``Attention`` and by ``LatentAttention``.
    rotary: bool = True
    # The dense MLP's activation: "gelu", "relu2" (relu(x)^2), or
    # "swiglu": the gated MLP ``down(silu(gate(x)) * up(x))``, a third
    # matrix ``gate`` beside ``up``.
    mlp_act: str = "gelu"
    # The Mamba-2 mixers' sizes: heads of ssm_head_dim channels in
    # ssm_groups groups, a state of ssm_state a channel, ssm_conv taps.
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    ssm_conv: int = 4
    # The expert layer beyond OLMoE's (models/moe.py, ``MoEMlp``): the
    # scoring ("softmax" | "sigmoid": with a choice bias, renormalised,
    # times moe_route_scale), the experts' kind ("swiglu" | "relu2"), a
    # latent width the routed experts work in (0: d_model) and one
    # shared expert's width (0: none).
    moe_score: str = "softmax"
    moe_route_scale: float = 1.0
    moe_expert_act: str = "swiglu"
    moe_latent: int = 0
    moe_shared_ff: int = 0
    # A chip's share of every layer, each ``(first, count)`` and None =
    # all: the routed experts whose stacks are here (the router stays
    # n_experts wide), the attention's query heads (with the key-value
    # heads they read) and the Mamba-2 heads (with their groups). The
    # sizes above stay the model's; a mixer's output is then its share of
    # the sum over all shares, and goes on to the next layer as it is:
    # nothing stands in for the other chips or the exchange with them.
    experts_held: Optional[tuple] = None
    heads_held: Optional[tuple] = None
    ssm_heads_held: Optional[tuple] = None
    # Attention beyond the above, each default today's layer (Qwen3-Next's
    # gated attention sets all five). ``head_dim``: a head's width where it
    # is not d_model / n_heads (None). ``head_norm``: RMSNorm a head on q
    # and on k, one [head_dim] scale for all heads, before the rotary
    # (``qk_norm`` is over the whole projected width). ``rotary_base`` and
    # ``rotary_fraction``: the rotary's base, and the leading share of a
    # head's channels it turns (the rest pass unrotated). ``attn_gate``:
    # the q projection is twice as wide, a head's columns ``[query |
    # gate]``, and the attention's output is multiplied by sigmoid(gate)
    # before ``o``.
    head_dim: Optional[int] = None
    head_norm: bool = False
    rotary_base: float = 10000.0
    rotary_fraction: float = 1.0
    attn_gate: bool = False
    # RMSNorm as ``x rsqrt(mean x^2 + eps) (1 + scale)`` with ``scale``
    # from zero, for the layers' norms, the final norm and ``head_norm``
    # (under weight decay the gain is pulled to 1, where the default's
    # ``scale`` from one is pulled to 0).
    norm_unit_offset: bool = False
    # The Gated DeltaNet mixers' sizes (models/gdn.py, pattern letter
    # "G"): gdn_key_heads of gdn_key_dim channels serving gdn_value_heads
    # of gdn_value_dim, gdn_conv taps.
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_conv: int = 4
    # The expert layer's further options: whether a token's chosen weights
    # are divided by their sum (``norm_topk_prob``; None: the sigmoid
    # score's are and the softmax's are not), and the shared expert's
    # output times sigmoid(h w_g).
    moe_renormalise: Optional[bool] = None
    moe_shared_gate: bool = False
    # The experts' width where it is not the dense MLP's (None: ``d_ff``
    # is both): a model with leading dense layers of one width and experts
    # of another has "-" and "E" in one pattern.
    moe_expert_ff: Optional[int] = None
    # The gated short-convolution mixers' taps (models/sconv.py, pattern
    # letter "C"); their channels are d_model.
    sconv_taps: int = 3
    # The latent-attention mixers' sizes (models/mla.py, pattern letter
    # "L"; DeepSeek-V3's names): n_heads heads whose keys and values come
    # out of a latent of mla_kv_rank channels (``kv_lora_rank``; 0: the
    # model has no such layer), a query-key width of mla_nope_dim unrotated
    # (``qk_nope_head_dim``) and mla_rope_dim rotated channels
    # (``qk_rope_head_dim``, one rotated key a position for all heads, at
    # ``rotary_base``) on values of mla_value_dim (``v_head_dim``).
    mla_kv_rank: int = 0
    mla_nope_dim: int = 128
    mla_rope_dim: int = 64
    mla_value_dim: int = 128
    # The sparse-attention mixers' indexer (models/dsa.py, pattern letter
    # "S"; DeepSeek's sparse attention under the names of ``sa_config``):
    # dsa_index_heads index heads (``indexer_num_heads``; 0: the model has
    # no such layer) of dsa_index_dim channels (``indexer_head_dim``) on
    # one index key a position, each query attending the dsa_topk causal
    # keys it scores highest (``topk``). The attention around it is
    # n_heads on n_kv_heads of head_dim with a norm a head, at
    # ``rotary_base``.
    dsa_index_heads: int = 0
    dsa_index_dim: int = 64
    dsa_topk: int = 2048
    # The Kimi Delta Attention mixers' sizes (models/kda.py, pattern letter
    # "K"): kda_heads heads (0: the model has no such layer) of
    # kda_head_dim key and value channels, kda_conv taps, and the rank
    # kda_gate_rank of the decay's and the output gate's two-matrix
    # projections.
    kda_heads: int = 0
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_gate_rank: int = 128
    # A second kind of attention layer, pattern letter "W": ``Attention``
    # in which a query sees the last attn_window causal keys, itself among
    # them (``t - attn_window < s <= t``; 0: the model has no such layer).
    # Both kinds read the sizes and pieces above (n_heads, n_kv_heads,
    # head_dim, heads_held, qk_norm, head_norm, attn_gate, rotary_base,
    # rotary_fraction, use_flash, ring_mesh). "*" sees every causal key and
    # turns q and k as ``rotary`` and ``rotary_scaling`` say; "W" sees its
    # window, turns them plainly (or not at all: ``attn_window_rotary``),
    # and reads neither of the two nor anything else of its own. A
    # model of local layers with positions and global ones without is
    # ``rotary=False`` beside a window here. A window on the ring path is
    # refused by name.
    attn_window: int = 0
    # False leaves the windowed layers' q and k unrotated as well (a model
    # with no positional term in any layer: ``rotary=False`` beside this).
    attn_window_rotary: bool = True
    # The law the "*" layers turn q and k by where it is not the plain one
    # at ``rotary_base``: an ``ops.rotary.Yarn`` (its own base in it), or
    # what ``ops.rotary.law`` makes of a published ``rope_parameters``
    # entry; None (default): the plain law. Read by "*" alone and only
    # under ``rotary``: a model whose windowed layers turn plainly and
    # whose full layers turn by a scaled law (one ``rope_parameters`` entry
    # a kind of layer) is this beside ``attn_window``; "W" keeps turning
    # plainly at ``rotary_base`` whatever this says.
    rotary_scaling: Optional[tuple] = None
    # A norm after the mixer as well, in a patterned model: a layer is ``x
    # + post_norm(mixer(norm(x)))``, the second RMSNorm with a weight of
    # its own on the mixer's output before the residual sum, for every
    # letter alike.
    post_norm: bool = False
    # The embedding's rows times this before the first layer (a model under
    # muP multiplies them by sqrt(d_model)), in the activations' dtype.
    embed_scale: float = 1.0
    # Block-diffusion training (Arriola et al., arXiv:2503.09573; SDAR): the
    # length of a block in positions, 0 = an autoregressive model and every
    # program what it was. With it set the model takes ``[b, 2 L]`` tokens,
    # **a clean copy of every sequence and then a noised one**
    # (``models.diffusion.noise_blocks`` makes them), both copies of position
    # ``i`` turned by the rotary at ``i``, and in every attention layer a
    # clean row sees the clean keys of its own block and of those before it
    # (the whole of its own block, both directions), a noised row the clean
    # keys of the blocks before its own and the noised keys of its own
    # block, and nothing else: no clean key of its own block or a later one,
    # which would hand it its answer. The final norm and the head are over
    # the ``L`` noised rows alone (``return_hidden`` gives ``[b, L, d]``).
    # The attention is ``*`` with whatever else it is set to; its products
    # over positions are ``_attend_blocks``: on the flash path two causal
    # walks of ``L`` (``ops/flash_attention.py``'s ``blocks``), a quarter
    # of ``(2 L)^2``. Refused by name: beside ``ring_mesh``, and in a
    # pattern with a mixer whose record does not say ``two_copies`` (a
    # windowed, a recurrent or a chosen-keys one).
    diffusion_block: int = 0
    # The Mamba-1 mixers' sizes (models/mamba.py, pattern letter "A"): an
    # inner width of mamba_expand x d_model channels, a state of
    # mamba_state a channel, mamba_conv taps, and the rank of the step
    # size's two-matrix projection (0: Mamba's own ceil(d_model / 16)).
    # Such a layer hands its scan output, before its gate, to the gated
    # memory units after it ("U": no field of their own, their width is the
    # memory's).
    mamba_expand: int = 2
    mamba_state: int = 16
    mamba_conv: int = 4
    mamba_rank: int = 0
    # Differential attention (Ye et al., arXiv:2410.05258) in every
    # attention layer of the three kinds that ``Attention`` builds ("*",
    # "W" and "X", which projects a query and an output alone and reads the
    # keys and values of the nearest "*" layer before it): the heads pair
    # up by neighbours, query heads ``2 j`` and ``2 j + 1`` on key heads
    # ``2 g`` and ``2 g + 1`` (``g = j // group``) make two softmax maps
    # over one value of twice the head width, ``[v_2g | v_2g+1]``, and the
    # pair's output is ``RMSNorm((P1 - lambda P2) V) (1 - lambda_init)``
    # with ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`` of
    # four learned vectors a layer and ``lambda_init = 0.8 - 0.6 exp(-0.3
    # l)``, ``l`` the layer's number: ``first_layer`` plus the mixers before
    # it in this model (a feed-forward layer of a pattern is part of the
    # decoder layer its mixer began). The difference, the norm and the
    # scale are float32.
    attn_differential: bool = False
    # The published number of this model's first decoder layer, where the
    # model is a stage of a deeper one and a layer reads its own number.
    first_layer: int = 0
    # A bias on the attention's four projections (q, k, v, o).
    attn_bias: bool = False
    # LayerNorm (the mean taken off, a weight and a bias) in place of
    # RMSNorm for the layers' norms and the final one.
    layer_norm: bool = False


def _repeat_kv(k, v, group):
    """Broadcast GQA K/V heads to the full query head count (no-op for
    MHA). The flash path never calls this — its kernel aliases the
    shared heads zero-copy."""
    if group == 1:
        return k, v
    return (jnp.repeat(k, group, axis=-2), jnp.repeat(v, group, axis=-2))


class RMSNorm(nn.Module):
    """``x rsqrt(mean x^2 + eps) scale`` over the last axis with ``scale``
    from one; ``unit_offset``: ``... (1 + scale)`` with ``scale`` from
    zero."""

    eps: float = 1e-6
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.initializers.zeros_init() if self.unit_offset
            else nn.initializers.ones_init(), (x.shape[-1],), jnp.float32)
        if self.unit_offset:
            scale = 1.0 + scale
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + self.eps) * scale).astype(x.dtype)


class LayerNorm(nn.Module):
    """``(x - mean x) rsqrt(var x + eps) scale + bias`` over the last axis,
    float32 inside."""

    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones_init(),
                           (x.shape[-1],), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(centred * centred, axis=-1, keepdims=True)
        return (centred * jax.lax.rsqrt(var + self.eps) * scale
                + bias).astype(x.dtype)


def _norm(cfg: "GPTConfig", name: str) -> nn.Module:
    if cfg.layer_norm:
        return LayerNorm(cfg.norm_eps, name=name)
    return RMSNorm(cfg.norm_eps, cfg.norm_unit_offset, name=name)


def held_heads(n_heads, n_kv, held):
    """``(query heads, key-value heads)`` of a share ``held = (first,
    count)`` of ``n_heads`` query heads over ``n_kv`` key-value heads:
    either whole groups, or a part of one group that divides it, so that
    the heads held group evenly over the key-value heads they read."""
    if held is None:
        return n_heads, n_kv
    first, count = held
    group = n_heads // n_kv
    whole = count % group == 0 and first % group == 0
    part = group % count == 0 and first % count == 0
    if not (0 < count <= n_heads - first and (whole or part)):
        raise ValueError(
            f"heads held {held} of {n_heads} over {n_kv} key-value heads: "
            f"a share is whole groups of {group}, or a divisor of one")
    return count, max(1, count // group)


def _count_trace(heads, kv_heads, head_dim, core, window, rotary, blocks,
                 differential, shared):
    """One count a traced layer; ``window`` 0 in a layer that sees every
    causal key; ``rotary`` the law its q and k turn by (``none``,
    ``plain``, ``yarn``); ``blocks`` the diffusion block's length, 0 in an
    autoregressive layer; ``differential`` 1 where the heads pair up;
    ``shared`` 1 in a layer that reads another's keys and values."""
    _pallas.count_trace(
        "hvt_attn_layers_traced_total",
        "attention layers traced into compiled programs, by the path "
        "their products over positions take: ring, flash or einsum "
        "(counted per trace, not per execution)",
        heads=heads, kv_heads=kv_heads, head_dim=head_dim, core=core,
        window=window, rotary=rotary, blocks=blocks,
        differential=int(differential), shared=int(shared))


def _attend(cfg, q, k, v, positions, core, window=0, out_dtype=None):
    """The products over positions by the path ``core`` names: the
    ring schedule, the flash kernels, or two einsums and a softmax; of the
    causal keys the last ``window`` alone where there is one; the result in
    ``out_dtype`` where one is named (not on the ring path)."""
    *_, n_heads, head_dim = q.shape
    n_kv = k.shape[-2]
    if core == "ring":
        from horovod_tpu.parallel.sequence import ring_attention

        if window:
            raise ValueError(
                f"a window ({window}) on the ring path is not built "
                f"(parallel/sequence.py's schedule sends every shard's keys "
                f"to every shard): unset ring_mesh, or attn_window")

        # GQA K/V go to the ring UN-repeated: the schedule
        # circulates the small h_kv buffers over ICI (payload
        # shrinks by the group factor — the point of GQA at long
        # context) and broadcasts locally per block (einsum path)
        # or aliases heads zero-copy in the kernel (flash path).
        # Exception: a 'tp' mesh axis shards the head dim, and the
        # small K/V head count may not divide it — repeat up front
        # there (the pre-r5 behavior) so the sharding stays valid.
        tp = dict(cfg.ring_mesh.shape).get("tp", 1)
        if n_kv % tp:
            k, v = _repeat_kv(k, v, n_heads // n_kv)
        # "auto" passes through UNRESOLVED: the ring shard function
        # resolves it against its local (post-shard_map) block
        # length, where the shape is unambiguous — dividing the
        # trace-time shape by the mesh factor here would divide
        # twice when a user invokes the model inside their own
        # shard_map (ADVICE r4)
        return ring_attention(q, k, v, mesh=cfg.ring_mesh,
                              causal=True,
                              scale=1.0 / np.sqrt(head_dim),
                              use_flash=cfg.use_flash)
    if core == "flash":
        from horovod_tpu.ops.flash_attention import (
            flash_attention, flash_attention_with_lse)

        # the kernel serves GQA zero-copy (K/V head index aliasing)
        if out_dtype is not None:
            return flash_attention_with_lse(
                q, k, v, causal=True, scale=1.0 / np.sqrt(head_dim),
                window=window or None, out_dtype=out_dtype)[0]
        return flash_attention(q, k, v, causal=True,
                               scale=1.0 / np.sqrt(head_dim),
                               **({"window": window} if window else {}))
    # XLA turns the repeat into a broadcast inside the dot
    k, v = _repeat_kv(k, v, n_heads // n_kv)
    scores = jnp.einsum("...qhd,...khd->...hqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(head_dim)
    qpos = positions[..., :, None]
    kpos = positions[..., None, :]
    causal = kpos <= qpos
    if window:
        causal = causal & (kpos > qpos - window)
    causal = causal[..., None, :, :]
    scores = jnp.where(causal, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    if out_dtype is not None:
        return jnp.einsum("...hqk,...khd->...qhd", probs, v,
                          preferred_element_type=out_dtype)
    return jnp.einsum("...hqk,...khd->...qhd", probs, v)


def differential_lambda_init(depth: int) -> float:
    """``lambda_init`` of differential attention in layer ``depth``."""
    return 0.8 - 0.6 * float(np.exp(-0.3 * depth))


def _by_halves(t, width):
    """The heads of ``t [.., heads, width]`` with the first of every pair
    of neighbours before every second: ``[0, 2, 4, .. | 1, 3, 5, ..]``."""
    pairs = t.shape[-2] // 2
    return jnp.swapaxes(t.reshape(*t.shape[:-2], pairs, 2, width), -3,
                        -2).reshape(*t.shape[:-2], 2 * pairs, width)


def _attend_blocks(cfg, q, k, v, core):
    """The products over positions of a block-diffusion layer: ``q``, ``k``
    and ``v`` hold ``2 L`` rows, the clean copy and then the noised one,
    and blocks are ``cfg.diffusion_block`` positions of a copy. A clean row
    ``i`` sees the clean keys ``j`` with ``blk(j) <= blk(i)``; a noised row
    the clean keys with ``blk(j) < blk(i)`` and the noised keys with
    ``blk(j) == blk(i)``.

    ``einsum``: that rule as a dense ``[2 L, 2 L]`` mask from (half,
    position), one softmax over whole rows (short sequences and the CPU).
    ``flash``: (a) the clean rows on the clean keys and (b) the noised rows
    on the clean keys through the kernels' ``blocks`` in its inclusive and
    its strict form, each a causal walk of one copy's positions; (c) the
    noised rows on their own noised block, ``L / B`` products of ``B x B`` in
    plain ``jax.numpy`` with a log-sum-exp of their own; (b) and (c) merged
    by their log-sum-exps in float32 (a row of the first block saw nothing
    in (b): ``lse = -inf``, weight 0). The ring schedule is refused."""
    size = cfg.diffusion_block
    *_, rows, n_heads, head_dim = q.shape
    n_kv, half = k.shape[-2], rows // 2
    scale = 1.0 / np.sqrt(head_dim)
    if core == "ring":
        raise ValueError(
            f"a diffusion block ({size}) on the ring path is not built "
            f"(parallel/sequence.py's schedule knows one causal mask): unset "
            f"ring_mesh, or diffusion_block")
    if core == "einsum":
        k, v = _repeat_kv(k, v, n_heads // n_kv)
        scores = jnp.einsum("...qhd,...khd->...hqk", q, k,
                            preferred_element_type=jnp.float32) * scale
        at = jnp.arange(rows)
        noised, block = at >= half, (at % half) // size
        clean_key = ~noised[None, :]
        seen = jnp.where(
            noised[:, None],
            (clean_key & (block[None, :] < block[:, None]))
            | (~clean_key & (block[None, :] == block[:, None])),
            clean_key & (block[None, :] <= block[:, None]))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("...hqk,...khd->...qhd", probs.astype(cfg.dtype), v)
    from horovod_tpu.ops.flash_attention import (flash_attention,
                                                 flash_attention_with_lse)

    halves = lambda t: (t[..., :half, :, :], t[..., half:, :, :])
    (q_c, q_n), (k_c, k_n), (v_c, v_n) = halves(q), halves(k), halves(v)
    clean = flash_attention(q_c, k_c, v_c, causal=True, blocks=size,
                            scale=scale)
    before, lse_before = flash_attention_with_lse(
        q_n, k_c, v_c, causal=True, blocks=size, strict=True, scale=scale,
        out_dtype=jnp.float32)
    with jax.named_scope("attn_blocks_own"):
        # a block's rows on its own block's keys, a key-value head at a
        # time: [.., n, B, h_kv, group, d] on [.., n, B, h_kv, d]
        lead = q_n.shape[:-3]
        in_blocks = lambda t, *heads: t.reshape(
            *lead, half // size, size, *heads, head_dim)
        q_b = in_blocks(q_n, n_kv, n_heads // n_kv)
        k_b, v_b = in_blocks(k_n, n_kv), in_blocks(v_n, n_kv)
        scores = jnp.einsum("...nqkgd,...nskd->...nqkgs", q_b, k_b,
                            preferred_element_type=jnp.float32) * scale
        lse_own = jax.nn.logsumexp(scores, axis=-1)
        own = jnp.einsum(
            "...nqkgs,...nskd->...nqkgd",
            jnp.exp(scores - lse_own[..., None]).astype(v.dtype), v_b,
            preferred_element_type=jnp.float32)
        own = own.reshape(*lead, half, n_heads, head_dim)
        lse_own = lse_own.reshape(*lead, half, n_heads)
    with jax.named_scope("attn_blocks_merge"):
        # the result does not depend on ``most``: no gradient through it
        most = jax.lax.stop_gradient(jnp.maximum(lse_before, lse_own))
        w_before = jnp.exp(lse_before - most)[..., None]
        w_own = jnp.exp(lse_own - most)[..., None]
        noised = ((w_before * before + w_own * own)
                  / (w_before + w_own)).astype(cfg.dtype)
    return jnp.concatenate([clean, noised], axis=-3)


class Attention(nn.Module):
    """The attention its kinds build (``KINDS``): over every causal key
    and turned as ``cfg.rotary`` says, by the law ``cfg.rotary_scaling``
    names where it names one; turned plainly and inside the configuration's
    window; or ``shared``, over the keys and values another layer made
    (``read``), with a query and an output projection of its own and no
    other. ``hands_on``: the layer returns ``(out, (k, v))``, its keys and
    values as its own products over positions took them."""

    cfg: GPTConfig
    rotary: bool        # whether q and k are turned
    window: int = 0     # keys a query sees (0: every causal key)
    # the law they turn by where it is not the plain one at
    # ``cfg.rotary_base`` (an ``ops.rotary.Yarn``)
    scaling: Optional[tuple] = None
    depth: int = 0      # the layer's number (differential attention's)
    shared: bool = False
    hands_on: bool = False
    # whether the layer sows its own input and output where its caller
    # collects ``intermediates``, as the other mixers do (a check of one
    # layer against a reference): a model with several kinds of layer
    sows: bool = False

    @nn.compact
    def __call__(self, x, positions, read=None):
        cfg = self.cfg
        head_dim = cfg.head_dim or cfg.d_model // cfg.n_heads
        dense = lambda feats, name: nn.DenseGeneral(
            feats, axis=-1, use_bias=cfg.attn_bias, dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name)
        n_kv = cfg.n_kv_heads or cfg.n_heads
        if cfg.n_heads % n_kv:
            raise ValueError(
                f"n_kv_heads ({n_kv}) must divide n_heads "
                f"({cfg.n_heads})")
        n_heads, n_kv = held_heads(cfg.n_heads, n_kv, cfg.heads_held)
        if cfg.qk_norm and cfg.head_norm:
            raise ValueError("qk_norm (over the whole projected width) and "
                             "head_norm (a head) are one or the other")
        if cfg.ring_mesh is not None:
            core = "ring"
        else:
            # (loaded here: importing this file loads no pallas)
            from horovod_tpu.ops.flash_attention import resolve_flash

            # (a diffusion layer's kernels walk a copy's positions)
            core = ("flash" if resolve_flash(
                cfg.use_flash, x.shape[-2] // (2 if cfg.diffusion_block
                                               else 1)) else "einsum")
        if cfg.diffusion_block and self.window:
            raise ValueError(
                f"a diffusion block ({cfg.diffusion_block}) in a layer that "
                f"sees a window ({self.window}) is not built: the blocks "
                f"are the causal limit's own")
        if cfg.attn_differential and (
                n_heads % 2 or n_kv % 2 or cfg.heads_held or core == "ring"
                or cfg.diffusion_block):
            raise ValueError(
                f"differential attention pairs {n_heads} query heads on "
                f"{n_kv} key-value heads by neighbours (both even), whole "
                f"(no heads_held), off the ring path and outside a "
                f"diffusion model: nothing else is built")
        if self.shared and (cfg.qk_norm or cfg.head_norm
                            or cfg.diffusion_block):
            raise ValueError(
                "a layer that reads another's keys and values beside "
                "qk_norm, head_norm or a diffusion block is not built")
        # (loaded here, as the flash kernels are)
        from horovod_tpu.ops.rotary import law_name, rotary

        law = self.scaling or cfg.rotary_base
        _count_trace(n_heads, n_kv, head_dim, core, self.window,
                     law_name(law) if self.rotary else "none",
                     cfg.diffusion_block, cfg.attn_differential, self.shared)
        sows = bool(self.sows or self.shared or cfg.diffusion_block
                    or cfg.attn_differential)
        if sows:
            self.sow("intermediates", "attn_input", x)
        with jax.named_scope("attn_proj"):
            q = dense((n_heads, head_dim * (2 if cfg.attn_gate else 1)),
                      "q")(x)
            if cfg.attn_gate:
                q, gate = jnp.split(q, 2, axis=-1)
            if self.shared:
                k, v = read
            else:
                k = dense((n_kv, head_dim), "k")(x)
                v = dense((n_kv, head_dim), "v")(x)
        if cfg.qk_norm:
            full_width = lambda t, name: RMSNorm(cfg.norm_eps, name=name)(
                t.reshape(*t.shape[:-2], -1)).reshape(t.shape)
            with jax.named_scope("attn_norm"):
                q, k = full_width(q, "q_norm"), full_width(k, "k_norm")
        if cfg.head_norm:
            with jax.named_scope("attn_norm"):
                q, k = _norm(cfg, "q_norm")(q), _norm(cfg, "k_norm")(k)
        if self.rotary:
            turned = (None if cfg.rotary_fraction == 1.0
                      else int(cfg.rotary_fraction * head_dim))
            with jax.named_scope("attn_rope"):
                # a norm over the whole width leaves q and k [b, s, h d]
                if self.shared:     # the keys came turned
                    q = rotary(q, positions, law, turned)
                else:
                    q, k = rotary((q, k), positions, law, turned,
                                  flat=cfg.qk_norm)
        handed = (k, v)
        if cfg.attn_differential:
            # the first softmax map of every pair and then the second, as
            # heads of one call: a head of either half reads the key head
            # of its own half, and both the pair's value of twice the width
            q, k = _by_halves(q, head_dim), _by_halves(k, head_dim)
            v = jnp.tile(v.reshape(*v.shape[:-2], n_kv // 2, 2 * head_dim),
                         (2, 1))
        # (the two maps' difference is taken of float32 results)
        attend = lambda *window: _attend(
            cfg, q, k, v, positions, core, *window,
            **({"out_dtype": jnp.float32} if cfg.attn_differential else {}))
        with jax.named_scope("attn_core"):
            if self.window:
                # a scope of its own inside the core's, so that a reader
                # of ``attn_core`` has both kinds and one of this the one
                with jax.named_scope("attn_window"):
                    out = attend(self.window)
            elif cfg.diffusion_block:
                # as a windowed layer's: the kernels' two walks, a block's
                # own products and the merge under one name
                with jax.named_scope("attn_blocks"):
                    out = _attend_blocks(cfg, q, k, v, core)
            else:
                out = attend()
            if cfg.attn_differential:
                vector = lambda name: self.param(
                    name, nn.initializers.normal(0.1), (head_dim,),
                    jnp.float32)
                lambdas = [vector(f"lambda_{part}")
                           for part in ("q1", "k1", "q2", "k2")]
                weight = self.param("subln", nn.initializers.ones_init(),
                                    (2 * head_dim,), jnp.float32)
                with jax.named_scope("attn_diff"):
                    start = differential_lambda_init(self.depth)
                    lam = (jnp.exp(jnp.sum(lambdas[0] * lambdas[1]))
                           - jnp.exp(jnp.sum(lambdas[2] * lambdas[3])) + start)
                    first, second = jnp.split(out.astype(jnp.float32), 2,
                                              axis=-2)
                    out = first - lam * second
                    out = out * jax.lax.rsqrt(jnp.mean(
                        out * out, axis=-1, keepdims=True) + cfg.norm_eps)
                    out = (out * weight * (1.0 - start)).astype(cfg.dtype)
                    out = out.reshape(*out.shape[:-2], n_heads, head_dim)
        if cfg.attn_gate:
            with jax.named_scope("attn_gate"):
                out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))).astype(cfg.dtype)
        with jax.named_scope("attn_out_proj"):
            out = nn.DenseGeneral(cfg.d_model, axis=(-2, -1),
                                  use_bias=cfg.attn_bias, dtype=cfg.dtype,
                                  param_dtype=jnp.float32, name="o")(out)
        if sows:
            self.sow("intermediates", "attn_output", out)
        return (out, handed) if self.hands_on else out


class MLP(nn.Module):
    """The dense MLP, ``down(act(up(x)))`` or, gated (``mlp_act``
    "swiglu"), ``down(silu(gate(x)) * up(x))``; under the scope
    ``dense_mlp``."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32,
            name=name)
        with jax.named_scope("dense_mlp"):
            h = dense(cfg.d_ff, "up")(x)
            if cfg.mlp_act == "swiglu":
                h = nn.silu(dense(cfg.d_ff, "gate")(x)) * h
            else:
                h = {"gelu": nn.gelu, "relu2": lambda t: jnp.square(
                    nn.relu(t))}[cfg.mlp_act](h)
            return dense(cfg.d_model, "down")(h)


def _expert_layer(cfg: GPTConfig):
    return _module("horovod_tpu.models.moe").MoEMlp(
        cfg.n_experts, cfg.moe_expert_ff or cfg.d_ff, cfg.experts_per_token,
        dtype=cfg.dtype, score=cfg.moe_score,
        route_scale=cfg.moe_route_scale, expert_act=cfg.moe_expert_act,
        latent=cfg.moe_latent, shared_ff=cfg.moe_shared_ff,
        held=cfg.experts_held, renormalise=cfg.moe_renormalise,
        shared_gate=cfg.moe_shared_gate, name="moe")


def _windowed_attention(cfg: GPTConfig, depth=0):
    if cfg.attn_window < 1:
        raise ValueError(
            f"layer_pattern holds 'W' and attn_window is {cfg.attn_window}: "
            f"a windowed layer sees at least its own position")
    return _layer(Attention(cfg, rotary=cfg.attn_window_rotary,
                            window=cfg.attn_window, depth=depth, sows=True,
                            name="attn"), positional=True)


def _sparse_attention(cfg: GPTConfig):
    layer = _module("horovod_tpu.models.dsa").SparseAttention(
        cfg.n_heads, cfg.n_kv_heads or cfg.n_heads,
        cfg.head_dim or cfg.d_model // cfg.n_heads, cfg.dsa_index_heads,
        cfg.dsa_index_dim, cfg.dsa_topk, rotary_base=cfg.rotary_base,
        norm_eps=cfg.norm_eps, use_flash=cfg.use_flash, dtype=cfg.dtype,
        name="dsa")

    def call(h, positions):
        out, index_loss = layer(h, positions)
        return out, {"dsa_index": index_loss}

    return call


def _layer(module, positional=False):
    """``module``, which has no auxiliary losses, called the one way a
    block calls every kind, ``(h, positions) -> (out, aux or None)``;
    ``positional`` where it takes the positions too. A kind that reads an
    earlier layer (``Kind.reads``) is handed that as a third argument."""
    return lambda h, positions, *read: (
        module(h, positions, *read) if positional else module(h, *read),
        None)


def _mamba(cfg: GPTConfig, hands_on=False):
    mixer = _module("horovod_tpu.models.mamba").Mamba1Mixer(
        cfg.mamba_expand, cfg.mamba_state, cfg.mamba_conv, cfg.mamba_rank,
        dtype=cfg.dtype, name="mamba")

    def call(h, positions):
        out, memory = mixer(h)
        return ((out, memory) if hands_on else out), None

    return call


def _mixers_rule(module, rule):
    """A mixer's own rule, ``(leaf's name, tp_axis)``, as a record's."""
    return lambda names, leaf, tp_axis, *_: getattr(_module(module), rule)(
        names[-1], tp_axis)


def _attention_leaf_spec(names, leaf, tp_axis, tp_size, ep_axis):
    """The attention's leaves, as ``param_partition_spec`` says."""
    bias = names[-1] == "bias"
    if names[0] in ("q", "k", "v"):
        heads = (leaf.shape[0 if bias else 1] if hasattr(leaf, "shape")
                 else None)
        if tp_size and heads is not None and heads % tp_size:
            return P()                     # replicated GQA K/V
        # (d_model, heads, head_dim), a bias (heads, head_dim)
        return P(tp_axis, None) if bias else P(None, tp_axis, None)
    # ``o``: (heads, head_dim, d_model); its bias, the differential
    # attention's four vectors and its norm's weight replicate
    return P(tp_axis, None, None) if names[0] == "o" and not bias else P()


@dataclasses.dataclass(frozen=True)
class Kind:
    """What this file knows of one kind of layer, in the one place it
    knows it: ``KINDS`` holds a record a letter of ``layer_pattern``, and
    the blocks, the ``remat`` policy, ``param_partition_spec`` and the
    pattern error read it. A new kind is its mixer's file (plain sizes: a
    mixer knows nothing of ``GPTConfig``), its fields in ``GPTConfig`` and
    its record here; a mixer's module is loaded when a record needs it."""

    letter: str
    subtree: str    # the layer's parameters are ``block_i/<subtree>``
    words: str      # what the pattern error calls it
    # ``build(cfg)``, inside a block's ``__call__``: the layer under the
    # subtree's name as ``(h, positions) -> (out, aux or None)``, and the
    # one place the kind's fields are read (tests/test_models_kinds.py)
    build: Callable
    # ``leaf_spec(names, leaf, tp_axis, tp_size, ep_axis)``: the
    # PartitionSpec of the leaf at the path ``names`` below the subtree
    leaf_spec: Callable
    # ``kept()``: the names (``checkpoint_name``) of what ``remat`` keeps
    # of the layer, each explained where it is defined
    kept: Callable = tuple
    # whether a layer of the kind serves a block-diffusion model
    # (``diffusion_block``), whose rows are a clean and a noised copy of
    # every sequence: its rows do not see each other, or it knows the two
    # copies apart
    two_copies: bool = False
    # what a layer of the kind hands to later layers beside the stream, in
    # words (None: nothing), and what it reads of the nearest earlier layer
    # that makes it. ``build`` takes ``hands_on=True`` where a later layer
    # reads this one, and the layer's ``out`` is then ``(out, made)``; a
    # reader's layer is called ``(h, positions, read)``. Which layer feeds
    # which is the pattern's to say (``_feeds``) and no field's.
    makes: Optional[str] = None
    reads: Optional[str] = None
    # whether ``build`` takes the layer's number, ``depth=``
    numbered: bool = False
    # whether a layer of the kind closes the decoder layer its mixer began
    # (and so has no number of its own)
    feed_forward: bool = False


_KEYS_VALUES, _SCAN_OUTPUT = "keys and values", "scan output before its gate"
KINDS = {kind.letter: kind for kind in (
    Kind("*", "attn", "attention",
         lambda cfg, depth=0, hands_on=False: _layer(Attention(
             cfg, rotary=cfg.rotary, scaling=cfg.rotary_scaling, depth=depth,
             hands_on=hands_on, sows=bool(cfg.attn_window), name="attn"),
             positional=True),
         _attention_leaf_spec, two_copies=True, makes=_KEYS_VALUES,
         numbered=True),
    Kind("W", "attn", "attention inside a window", _windowed_attention,
         _attention_leaf_spec, numbered=True),
    Kind("X", "cross", "attention over an earlier layer's keys and values",
         lambda cfg, depth=0: _layer(Attention(
             cfg, rotary=cfg.rotary, scaling=cfg.rotary_scaling, depth=depth,
             shared=True, name="cross"), positional=True),
         _attention_leaf_spec, reads=_KEYS_VALUES, numbered=True),
    Kind("A", "mamba", "Mamba-1", _mamba,
         _mixers_rule("horovod_tpu.models.mamba", "mamba_leaf_spec"),
         makes=_SCAN_OUTPUT),
    Kind("U", "gmu", "gated memory unit",
         lambda cfg: _layer(
             _module("horovod_tpu.models.mamba").GatedMemoryUnit(
                 dtype=cfg.dtype, name="gmu")),
         _mixers_rule("horovod_tpu.models.mamba", "gmu_leaf_spec"),
         reads=_SCAN_OUTPUT),
    Kind("M", "ssm", "Mamba-2",
         lambda cfg: _layer(_module("horovod_tpu.models.ssm").Mamba2Mixer(
             cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
             cfg.ssm_conv, held=cfg.ssm_heads_held, norm_eps=cfg.norm_eps,
             dtype=cfg.dtype, name="ssm")),
         _mixers_rule("horovod_tpu.models.ssm", "ssm_leaf_spec")),
    Kind("G", "gdn", "Gated DeltaNet",
         lambda cfg: _layer(_module("horovod_tpu.models.gdn").GatedDeltaNet(
             cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
             cfg.gdn_value_dim, cfg.gdn_conv, norm_eps=cfg.norm_eps,
             dtype=cfg.dtype, name="gdn")),
         _mixers_rule("horovod_tpu.models.gdn", "gdn_leaf_spec"),
         lambda: (_module("horovod_tpu.ops.gated_delta_rule").KEPT_INVERSE,)),
    Kind("K", "kda", "Kimi Delta Attention",
         lambda cfg: _layer(
             _module("horovod_tpu.models.kda").KimiDeltaAttention(
                 cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv,
                 cfg.kda_gate_rank, norm_eps=cfg.norm_eps, dtype=cfg.dtype,
                 name="kda")),
         _mixers_rule("horovod_tpu.models.kda", "kda_leaf_spec"),
         lambda: (
             _module("horovod_tpu.ops.channel_delta_rule").KEPT_INVERSE,)),
    Kind("C", "sconv", "gated short convolution",
         lambda cfg: _layer(_module("horovod_tpu.models.sconv").ShortConv(
             cfg.sconv_taps, dtype=cfg.dtype, name="sconv")),
         _mixers_rule("horovod_tpu.models.sconv", "sconv_leaf_spec")),
    Kind("L", "mla", "latent attention",
         lambda cfg: _layer(
             _module("horovod_tpu.models.mla").LatentAttention(
                 cfg.n_heads, cfg.mla_kv_rank, cfg.mla_nope_dim,
                 cfg.mla_rope_dim, cfg.mla_value_dim,
                 rotary_base=cfg.rotary_base, norm_eps=cfg.norm_eps,
                 use_flash=cfg.use_flash, dtype=cfg.dtype, rotary=cfg.rotary,
                 name="mla"), positional=True),
         _mixers_rule("horovod_tpu.models.mla", "mla_leaf_spec")),
    Kind("S", "dsa", "attention over chosen keys", _sparse_attention,
         _mixers_rule("horovod_tpu.models.dsa", "dsa_leaf_spec"),
         lambda: (_module("horovod_tpu.models.dsa").KEPT_CHOICE,
                  _module("horovod_tpu.models.dsa").KEPT_INDEX_GRADS)),
    # (``_expert_layer`` by its name in this module, each time: a builder's
    # script puts a wrong layer there)
    Kind("E", "moe", "experts",
         lambda cfg: lambda h, positions: _expert_layer(cfg)(h),
         lambda names, leaf, tp_axis, tp_size, ep_axis: _module(
             "horovod_tpu.models.moe").expert_leaf_spec(
                 names[-1], leaf, ep_axis, tp_axis),
         lambda: (_module("horovod_tpu.models.moe").HELD_SUM,
                  _module("horovod_tpu.models.moe").HELD_CHOICE),
         two_copies=True, feed_forward=True),
    Kind("-", "mlp", "MLP", lambda cfg: _layer(MLP(cfg, name="mlp")),
         lambda names, leaf, tp_axis, *_: {
             "up": P(None, tp_axis), "gate": P(None, tp_axis),
             "down": P(tp_axis, None)}.get(names[0], P()), two_copies=True,
         feed_forward=True),
)}


def _feeds(pattern) -> dict:
    """``{reader: maker}`` by the layers' places in ``pattern``: a layer
    whose kind reads something (``Kind.reads``) reads it of the nearest
    earlier layer whose kind makes it. A reader with nothing before it to
    read is refused by name. (A letter no record has is ``MixerBlock``'s
    to name.)"""
    feeds, last = {}, {}
    for i, letter in enumerate(pattern or ""):
        kind = KINDS.get(letter)
        if kind is not None and kind.reads:
            if kind.reads not in last:
                makers = ", ".join(
                    f"{k.letter!r} ({k.words})" for k in KINDS.values()
                    if k.makes == kind.reads)
                raise ValueError(
                    f"layer_pattern {pattern!r}: layer {i} is {letter!r} "
                    f"({kind.words}), which reads the {kind.reads} of the "
                    f"nearest earlier layer that makes them, and none of "
                    f"{makers} comes before it")
            feeds[i] = last[kind.reads]
        if kind is not None and kind.makes:
            last[kind.makes] = i
    return feeds


def _diffusion_half(cfg: GPTConfig, rows: int) -> int:
    """The positions of one copy of a block-diffusion model's ``rows``
    (clean and then noised), and by name what is not built beside a
    diffusion block."""
    size = cfg.diffusion_block
    if cfg.ring_mesh is not None:
        raise ValueError(
            f"a diffusion block ({size}) beside ring_mesh is not built: its "
            f"mask is not the ring schedule's")
    others = sorted(kind for kind in set(cfg.layer_pattern or "")
                    if kind in KINDS and not KINDS[kind].two_copies)
    if others:
        serve = ", ".join(f"{kind.letter!r} ({kind.words})"
                          for kind in KINDS.values() if kind.two_copies)
        raise ValueError(
            f"a diffusion block ({size}) in a pattern with "
            + ", ".join(f"{kind!r} ({KINDS[kind].words})" for kind in others)
            + ": every row of both copies goes through every mixer, and "
            f"only {serve} know the two copies apart or need not")
    if rows % 2 or (rows // 2) % size:
        raise ValueError(
            f"a diffusion model takes [b, 2 L] tokens, a clean copy and "
            f"then a noised one, L whole blocks of {size}; got {rows} rows")
    return rows // 2


class Block(nn.Module):
    """One pre-norm block, the kinds "*" and "-" or "E" of ``KINDS``.
    Returns ``(x, aux)``: ``aux`` is the expert layer's auxiliary losses
    (``models/moe.py``), None for a dense block."""

    cfg: GPTConfig
    depth: int = 0      # the layer's number (differential attention's)

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        out, _ = KINDS["*"].build(cfg, depth=self.depth)(
            _norm(cfg, "ln1")(x), positions)
        x = x + out
        out, aux = KINDS["E" if cfg.n_experts else "-"].build(cfg)(
            _norm(cfg, "ln2")(x), positions)
        return x + out, aux


class MixerBlock(nn.Module):
    """One layer of a patterned model: ``x + mixer(RMSNorm(x))`` with the
    one mixer ``kind`` names (a letter of ``KINDS``), or under
    ``post_norm`` ``x + RMSNorm(mixer(RMSNorm(x)))``. Returns ``(x, aux)``
    as ``Block`` does; called with what its kind reads of an earlier layer
    as a third argument (``Kind.reads``), and under ``hands_on`` it returns
    ``(x, aux, made)``, what its kind makes for a later one
    (``Kind.makes``)."""

    cfg: GPTConfig
    kind: str
    depth: int = 0      # the decoder layer's number
    hands_on: bool = False

    @nn.compact
    def __call__(self, x, positions, *read):
        cfg = self.cfg
        if self.kind not in KINDS:
            raise ValueError(
                f"layer_pattern holds {self.kind!r}: a layer is one of "
                + ", ".join(f"{kind.letter!r} ({kind.words})"
                            for kind in KINDS.values()))
        kind = KINDS[self.kind]
        out, aux = kind.build(
            cfg, **({"depth": self.depth} if kind.numbered else {}),
            **({"hands_on": True} if self.hands_on else {}))(
                _norm(cfg, "norm")(x), positions, *read)
        made = ()
        if self.hands_on:
            out, *made = out
        if cfg.post_norm:
            with jax.named_scope("post_norm"):
                out = _norm(cfg, "post_norm")(out)
        return (x + out, aux, *made)


class GPT(nn.Module):
    cfg: GPTConfig

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False,
                 return_aux: bool = False):
        """Logits by default; ``return_hidden=True`` returns the final
        (post-ln) hidden states instead, for ``ops.losses
        .softmax_cross_entropy_fused`` (with the embedding, or with an untied
        model's ``params["lm_head"]``): no [batch, seq, vocab] logits, at
        most [batch, chunk, vocab]. That loss makes both gradients beside each
        chunk's logits and keeps them for the backward pass, so it is
        differentiable once, in reverse mode. ``return_aux=True`` returns
        ``(that, aux)``: the expert layers' auxiliary losses summed over the
        layers unweighted (``{"load_balance", "router_z"}``; dense: ``{}``)
        and, of a model with sparse-attention mixers, their indexers' loss
        (``"dsa_index"``, ``models/dsa.py``), which a training script adds
        to its own."""
        cfg = self.cfg
        positions = jnp.arange(tokens.shape[-1])
        if cfg.diffusion_block:
            half = _diffusion_half(cfg, tokens.shape[-1])
            # the clean copy and then the noised one: both copies of a
            # position are turned at that position
            positions = jnp.tile(positions[:half], 2)
        positions = jnp.broadcast_to(positions, tokens.shape)
        emb = self.param("embedding", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        with jax.named_scope("embed"):
            x = emb[tokens].astype(cfg.dtype)
            if cfg.embed_scale != 1.0:
                x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
        if cfg.layer_pattern is not None and (
                len(cfg.layer_pattern) != cfg.n_layers):
            raise ValueError(
                f"layer_pattern {cfg.layer_pattern!r} names "
                f"{len(cfg.layer_pattern)} layers, n_layers is "
                f"{cfg.n_layers}")
        if cfg.post_norm and cfg.layer_pattern is None:
            raise ValueError(
                "post_norm without a layer_pattern is not built: the norm "
                "after the mixer is a patterned model's (MixerBlock)")
        block = Block if cfg.layer_pattern is None else MixerBlock
        if cfg.remat:
            # everything recomputed but what the kinds' records name
            block = nn.remat(
                block, static_argnums=(),
                policy=jax.checkpoint_policies.save_only_these_names(*(
                    name for kind in KINDS.values()
                    for name in kind.kept())))
        # what a layer reads of an earlier one beside the stream: under
        # ``remat`` it is one block's output and another's input, kept once
        feeds = _feeds(cfg.layer_pattern)
        aux, made, depth = {}, {}, cfg.first_layer
        for i in range(cfg.n_layers):
            kind = () if cfg.layer_pattern is None else (
                cfg.layer_pattern[i],)
            # a feed-forward layer of a pattern has the number of the
            # decoder layer it closes
            closes = any(letter in KINDS and KINDS[letter].feed_forward
                         for letter in kind)
            x, layer_aux, *handed = block(
                cfg, *kind, depth=depth - closes,
                **({"hands_on": True} if i in feeds.values() else {}),
                name=f"block_{i}")(
                    x, positions, *((made[feeds[i]],) if i in feeds else ()))
            if handed:
                made[i], = handed
            depth += not closes
            if layer_aux is not None:
                aux = {**aux, **{name: aux.get(name, 0.0) + value
                                 for name, value in layer_aux.items()}}
        if cfg.diffusion_block:
            # the loss reads the noised copy alone
            x = x[..., half:, :]
        x = _norm(cfg, "ln_f")(x)
        head = emb if cfg.tie_embeddings else self.param(
            "lm_head", nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.d_model), jnp.float32)
        if not return_hidden:
            with jax.named_scope("lm_head"):
                x = jnp.einsum("...ld,vd->...lv", x.astype(jnp.float32),
                               head)
        return (x, aux) if return_aux else x


def param_partition_spec(params, *, tp_axis="tp", tp_size=None,
                         ep_axis=None):
    """PartitionSpec pytree for Megatron-style tensor parallelism, a leaf
    by the rule of the kind of layer (``KINDS``) whose subtree it is in.

    Column-parallel: q/k/v and MLP up and gate kernels shard their output
    dim over ``tp_axis``; row-parallel: attention out and MLP down kernels
    shard their input dim, so XLA inserts exactly one psum per row-parallel
    matmul (the NCCL-allreduce-per-layer pattern, compiled).
    Embedding and an untied ``lm_head`` shard the vocab dim. Norm scales
    replicate. The expert stacks of a sparse model shard their expert
    axis over ``ep_axis`` where one is given (and their ``d_ff`` axis
    over ``tp_axis``), as ``moe.moe_param_partition_spec`` does.

    ``tp_size`` (the mesh's tp axis size, when known): a head axis not
    divisible by it — GQA/MQA K/V kernels with ``n_kv_heads < tp`` —
    falls back to REPLICATED K/V, the standard Megatron MQA layout
    (every tp rank holds the shared K/V heads; only Q/out shard).
    Without ``tp_size`` the spec assumes divisibility, matching the
    pre-GQA behavior.
    """
    by_subtree = {kind.subtree: kind for kind in KINDS.values()}

    def spec_for(path, leaf):
        names = [getattr(p, "key", None) for p in path]
        if "embedding" in names or "lm_head" in names:
            return P(tp_axis, None)
        for depth, name in enumerate(names):
            if name in by_subtree:
                return by_subtree[name].leaf_spec(
                    names[depth + 1:], leaf, tp_axis, tp_size, ep_axis)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params)
