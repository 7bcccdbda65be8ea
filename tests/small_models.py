"""Small instances of the sparse, hybrid and Qwen3-Next models and their
losses, for the tests that compare them with the plain references
(``test_models_hybrid.py``, ``test_models_qwen3_next.py``,
``test_sconv.py``). The builders initialise op by op and stay so: the
comparisons with the references stand at 0.96 of their bounds on these very
parameters, and one program's differ from them in the last bit. A test that
reads shapes, names or a lowered text takes a ``*_config`` and
``jax.eval_shape`` or a jitted ``init``."""

import dataclasses

import jax
import jax.numpy as jnp


# ---- the sparse decoder (OLMoE's block)

SPARSE = {"num_experts_per_tok": 8, "rope_theta": 10000.0,
          "rms_norm_eps": 1e-5, "router_aux_loss_coef": 0.01,
          "router_z_loss_coef": 0.001}


def sparse_config(remat):
    from horovod_tpu.models import GPTConfig

    return GPTConfig(vocab_size=64, n_layers=2, d_model=32, n_heads=2,
                     d_ff=8, dtype=jnp.float32, remat=remat, use_flash=False,
                     n_experts=64, experts_per_token=8, qk_norm=True,
                     tie_embeddings=False, norm_eps=1e-5)


def sparse_model(remat):
    from horovod_tpu.models import GPT

    model = GPT(sparse_config(remat))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 64)
    params = model.init(jax.random.key(0), tokens)["params"]
    # at their 0.02 the experts and the router barely move the loss
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w * 20.0 if "moe" in str(path) else w, params)
    return model, params, tokens


def sparse_loss(model, params, tokens):
    import optax

    logits, aux = model.apply({"params": params}, tokens, return_aux=True)
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]).mean()
    return (ce + SPARSE["router_aux_loss_coef"] * aux["load_balance"]
            + SPARSE["router_z_loss_coef"] * aux["router_z"])


# ---- the hybrid decoder (Nemotron-H's layers)

HYBRID = {"norm_eps": 1e-5, "ssm_state_size": 8, "mamba_head_dim": 4,
          "num_experts_per_tok": 3, "norm_topk_prob": True,
          "routed_scaling_factor": 2.5, "experts_held_first": 4}


def hybrid_config(remat=False, pattern="*EMEM", **changes):
    """A share of a small hybrid: 2 of 8 query heads on 1 of 2 key-value
    heads, 4 of 8 Mamba-2 heads in 1 of 2 groups, experts 4 to 7 of 16."""
    from horovod_tpu.models import GPTConfig

    cfg = GPTConfig(
        vocab_size=64, n_layers=len(pattern), layer_pattern=pattern,
        d_model=32, n_heads=8, n_kv_heads=2, heads_held=(4, 2), rotary=False,
        d_ff=24, dtype=jnp.float32, remat=remat, use_flash=False,
        tie_embeddings=False, norm_eps=1e-5, mlp_act="relu2", ssm_heads=8,
        ssm_head_dim=4, ssm_groups=2, ssm_state=8, ssm_heads_held=(4, 4),
        n_experts=16, experts_per_token=3, moe_score="sigmoid",
        moe_route_scale=2.5, moe_expert_act="relu2", moe_latent=16,
        moe_shared_ff=40, experts_held=(4, 4))
    return dataclasses.replace(cfg, **changes)


def hybrid_model(remat=False, pattern="*EMEM", **changes):
    from horovod_tpu.models import GPT

    model = GPT(hybrid_config(remat, pattern, **changes))
    tokens = jax.random.randint(jax.random.key(1), (2, 20), 0, 64)
    variables = model.init(jax.random.key(0), tokens)
    # at their 0.02 the experts and the router barely move the loss
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w * 10.0 if "moe" in str(path) else w,
        variables["params"])
    return model, params, variables.get("buffers", {}), tokens


def hybrid_loss(model, params, buffers, tokens):
    import optax

    logits = model.apply({"params": params, "buffers": buffers}, tokens)
    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]).mean()


# ---- Qwen3-Next's layers (Gated DeltaNet, gated attention with per-head
# norms and a partial rotary, a renormalised softmax router with a gated
# shared expert)

QWEN = {"rms_norm_eps": 1e-6, "linear_num_key_heads": 2,
        "linear_num_value_heads": 4, "linear_key_head_dim": 8,
        "linear_value_head_dim": 8, "head_dim": 16,
        "partial_rotary_factor": 0.25, "rope_theta": 1e7,
        "num_experts_per_tok": 3, "norm_topk_prob": True,
        "experts_held_first": 4}


def qwen_config(remat=False, pattern="GEGE*E", **changes):
    """A share of a small Qwen3-Next: heads of 16 where d_model / n_heads
    is 8, 4 query heads on 2 key-value heads, 2 key heads serving 4 value
    heads in the Gated DeltaNet mixers, experts 4 to 7 of 16."""
    from horovod_tpu.models import GPTConfig

    cfg = GPTConfig(
        vocab_size=64, n_layers=len(pattern), layer_pattern=pattern,
        d_model=32, n_heads=4, n_kv_heads=2, head_dim=16, head_norm=True,
        attn_gate=True, rotary_base=1e7, rotary_fraction=0.25, d_ff=16,
        dtype=jnp.float32, remat=remat, use_flash=False,
        tie_embeddings=False, norm_eps=1e-6, norm_unit_offset=True,
        gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8,
        n_experts=16, experts_per_token=3, moe_renormalise=True,
        moe_shared_gate=True, moe_shared_ff=24, experts_held=(4, 4))
    return dataclasses.replace(cfg, **changes)


def qwen_model(remat=False, pattern="GEGE*E", **changes):
    from horovod_tpu.models import GPT

    model = GPT(qwen_config(remat, pattern, **changes))
    tokens = jax.random.randint(jax.random.key(1), (2, 20), 0, 64)
    params = model.init(jax.random.key(0), tokens)["params"]
    # off their initial values: at 0.02 the layers barely move the loss,
    # and a norm's weight of 0 or a scale of 1 hides which of the two it is
    keys = iter(jax.random.split(jax.random.key(2),
                                 len(jax.tree.leaves(params))))
    params = jax.tree.map(
        lambda w: w + 0.2 * jax.random.normal(next(keys), w.shape), params)
    return model, params, tokens


def qwen_loss(model, params, tokens, sow=False):
    import optax

    logits, sown = model.apply({"params": params}, tokens,
                               mutable=["intermediates"])
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]).mean()
    return (loss, sown["intermediates"]) if sow else loss


# ---- a tree of seeded random leaves from shapes alone

def random_tree(shapes, seed, spread=0.1):
    """Leaves of ``shapes`` (a tree of ``ShapeDtypeStruct``, from
    ``jax.eval_shape`` of an ``init``: nothing initialised, nothing
    compiled; numpy draws them) at ``spread`` around 0, and around 1 where
    a leaf is a norm's weight or a skip term (``scale``, ``subln``,
    ``D_skip``): every bias away from 0 and every weight from 1, for a
    comparison with a reference on the same tree."""
    import numpy as np

    rng = np.random.default_rng(seed)
    centre = lambda path: float(any(
        name in jax.tree_util.keystr(path)
        for name in ("scale", "subln", "D_skip")))
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.asarray(
            centre(path) + spread * rng.standard_normal(leaf.shape),
            leaf.dtype), shapes)
