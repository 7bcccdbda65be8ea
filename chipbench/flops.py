"""Operations and bytes, counted from shapes. The yardstick's arithmetic:
no program code is imported here, and a later PR cannot change it.

Conventions (the same for every family, so that ``mfu`` compares cells):
one multiply-add is two FLOPs; a training item costs three times its
forward pass (forward, gradient with respect to activations, gradient
with respect to weights); recomputation under ``remat`` is not counted;
causal attention is counted as the unmasked half of the score matrix.
"""

from __future__ import annotations

import json
import math
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip, by JAX's ``device_kind``. A device
    that is not in ``peaks.json`` is an error, not a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; add it "
            f"to chipbench/peaks.json with its source")
    return table[device_kind]


# ------------------------------------------------------------------- GPT

def gpt_params(vocab_size: int, n_layers: int, d_model: int,
               d_ff: int) -> int:
    """Parameters of the decoder as the package builds it: a tied
    embedding (counted once), per block q, k, v, o (4 d^2), up and down
    (2 d d_ff) and two norm scales, one final norm scale; no biases."""
    block = 4 * d_model * d_model + 2 * d_model * d_ff + 2 * d_model
    return vocab_size * d_model + n_layers * block + d_model


def gpt_train_flops_per_token(n_params: int, n_layers: int, d_model: int,
                              seq_len: int) -> float:
    """``6 N + 6 L d s``: 6 N for the dense multiplications (the tied
    embedding counts once, as the vocabulary projection), and for
    attention 2 (QK^T, PV) x 2 FLOPs x d x s/2 keys a token forward,
    times 3 for training = 6 L d s. ``12 L d s`` would count the masked
    half of a causal matrix as required work."""
    return 6.0 * n_params + 6.0 * n_layers * d_model * seq_len


# ---------------------------------------------------- counted from a jaxpr

def _conv_macs(eqn) -> int:
    out = eqn.outvars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    dn = eqn.params["dimension_numbers"]
    spatial = math.prod(rhs[i] for i in dn.rhs_spec[2:])
    in_per_group = rhs[dn.rhs_spec[1]]      # already divided by groups
    return math.prod(out) * spatial * in_per_group


def _dot_macs(eqn) -> int:
    lhs = eqn.invars[0].aval.shape
    out = eqn.outvars[0].aval.shape
    (contract_lhs, _), _ = eqn.params["dimension_numbers"]
    return math.prod(out) * math.prod(lhs[i] for i in contract_lhs)


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        values = value if isinstance(value, (list, tuple)) else [value]
        for v in values:
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield inner


def jaxpr_macs(jaxpr) -> int:
    """Multiply-adds of every convolution and matrix multiplication in a
    jaxpr, from their shapes (a loop body counts once: none is used)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            total += _conv_macs(eqn)
        elif eqn.primitive.name == "dot_general":
            total += _dot_macs(eqn)
        for inner in _sub_jaxprs(eqn):
            total += jaxpr_macs(inner)
    return total


def forward_macs(fn, *shapes) -> int:
    """Multiply-adds of ``fn(*shapes)`` (shapes as ShapeDtypeStructs or
    pytrees of them): the model as built, not a constant from a paper."""
    import jax

    return jaxpr_macs(jax.make_jaxpr(fn)(*shapes).jaxpr)


def train_flops_from_forward_macs(macs: int) -> float:
    """3 x (2 FLOPs a multiply-add) x forward multiply-adds."""
    return 6.0 * macs


# ------------------------------------------------- flash attention kernels

# Multiplications of [s, d] by [d, s] or [s, s] by [s, d] each kernel
# makes per (batch, head): forward QK^T and PV; dQ recomputes QK^T, then
# dO V^T and dS K; dK/dV recomputes QK^T, then dO V^T, P^T dO and dS^T Q.
FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}
# Arrays of [s, d] (bf16) and [s] (f32) each kernel must read or write
# once per (batch, head): fwd q k v o + lse; dq q k v do dq + lse delta;
# dkv k v q do dk dv + lse delta.
FLASH_ARRAYS = {"fwd": (4, 1), "dq": (5, 2), "dkv": (6, 2)}


def flash_call_flops(kind: str, batch: int, heads: int, seq_len: int,
                     head_dim: int, causal: bool = True) -> float:
    full = FLASH_MATMULS[kind] * 2.0 * seq_len * seq_len * head_dim
    return batch * heads * full * (0.5 if causal else 1.0)


def flash_call_bytes(kind: str, batch: int, heads: int, seq_len: int,
                     head_dim: int, itemsize: int = 2) -> float:
    wide, narrow = FLASH_ARRAYS[kind]
    return batch * heads * seq_len * (wide * head_dim * itemsize
                                      + narrow * 4.0)


def roofline_seconds(flops: float, nbytes: float, peak: dict):
    """Least time the chip could take, and which bound applies."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
