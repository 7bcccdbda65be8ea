"""The dense decoder family: ``horovod_tpu.models.GPT`` driven by the
sizes of a configuration file, as a user's script would build it.

Configuration keys (the names of the source's ``config.json``):
``vocab_size``, ``n_layer``, ``n_embd``, ``n_head``, ``n_inner``; plus
``dtype``, ``remat``, ``use_flash`` and ``optimizer`` ({"name": "adamw",
"learning_rate": ...}). Traffic keys: ``per_chip_batch``, ``seq_len``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import optax

from horovod_tpu.models import GPT, GPTConfig

from chipbench import compare, flops
from chipbench.families import Job, optimizer_from
from chipbench.reference import gpt as reference

# What --rehearse shrinks for a CPU dry run (control flow only).
REHEARSAL = {"config": {"vocab_size": 512, "n_layer": 3, "n_embd": 64,
                        "n_head": 4, "n_inner": 256},
             "traffic": {"seq_len": 64, "per_chip_batch": 4}}

# Sequences on which the probe instance's gradients are compared.
SAMPLE_SEQUENCES = 2
# Layers of the instance on which gradients are compared: a float32
# backward pass of the full depth does not fit beside the job.
PROBE_LAYERS = 2

# Program (bf16 activations, f32 parameters, f32 softmax and logits)
# against the float32 reference, on the parameters a window of training
# left and the batch it trained on. bf16 keeps 8 bits of mantissa (eps
# 3.9e-3); the loss is a mean over thousands of positions of
# logsumexp - logit, each computed in f32 from a bf16 hidden state, so
# rounding largely averages out: measured 1.3e-5 to 2.1e-4 apart on the
# chip at the published size (PERF.md, PR 22). A bf16 vocabulary
# projection or softmax, a wrong mask or a dropped layer moves it by
# several 1e-3 and more.
LOSS_REL_BOUND = 1e-3
# Gradients of two layers, relative L2 over the whole tree: measured
# 0.96e-2 to 1.04e-2 on the chip over six seeds (einsum at 1024, the
# Pallas kernels at 4096), nearly all of it the bf16 activations the
# configuration states. A wrong mask, scale or block reads O(1).
GRAD_REL_L2_BOUND = 2e-2


def _model_config(config, seq_len):
    return GPTConfig(
        vocab_size=config["vocab_size"], n_layers=config["n_layer"],
        d_model=config["n_embd"], n_heads=config["n_head"],
        d_ff=config["n_inner"], max_seq_len=seq_len,
        dtype=jnp.dtype(config["dtype"]), remat=config["remat"],
        use_flash=config["use_flash"])


def _job(cfg: GPTConfig, config, per_chip_batch, seq_len, probe=None) -> Job:
    """The job of one model instance; ``probe`` is the small instance
    its gradients are checked on (the probe itself checks nothing)."""
    model = GPT(cfg)

    def init(key):
        variables = model.init(key, jnp.zeros((1, seq_len), jnp.int32))
        return variables["params"], {}

    def make_batch(key, n_chips):
        return jax.random.randint(
            key, (n_chips * per_chip_batch, seq_len), 0, cfg.vocab_size,
            jnp.int32)

    def loss(params, extra, tokens):
        logits = model.apply({"params": params}, tokens)
        targets = jnp.roll(tokens, -1, axis=-1)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], targets[:, :-1])
        return ce.mean(), extra

    def check(key):
        """Gradients on the probe instance: same widths and sequence
        length, so the same attention path, kernels included."""
        if probe is None:
            return []
        key_sample, key_probe = jax.random.split(key)
        sample = make_batch(key_sample, 1)[:SAMPLE_SEQUENCES]
        probe_params, _ = jax.jit(probe.init)(key_probe)
        grad = jax.jit(jax.grad(lambda p, t: probe.loss(p, {}, t)[0]))
        got = grad(probe_params, sample)
        _, want = reference.loss_and_grad(probe_params, sample)
        return [compare.trees_close(
            f"grad_vs_reference_{probe.facts['n_layers']}_layers", got,
            want, GRAD_REL_L2_BOUND)]

    n_params = flops.gpt_params(cfg.vocab_size, cfg.n_layers, cfg.d_model,
                                cfg.d_ff)
    return Job(
        item="tokens",
        items_per_step_per_chip=per_chip_batch * seq_len,
        flops_per_item=flops.gpt_train_flops_per_token(
            n_params, cfg.n_layers, cfg.d_model, seq_len),
        init=init, make_batch=make_batch, loss=loss,
        optimizer=lambda: optimizer_from(config["optimizer"]),
        reference_loss=lambda params, extra, tokens: reference.loss(
            params, tokens),
        loss_rel_bound=LOSS_REL_BOUND, check=check, probe=probe,
        facts={"n_params": n_params, "n_layers": cfg.n_layers,
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
