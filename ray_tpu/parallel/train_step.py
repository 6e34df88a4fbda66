"""Sharded train-state + train-step builders.

This is where a MeshPlan becomes a compiled program: params/optimizer state
initialized directly into their NamedShardings (no host round-trip), and a
single donated-argument jit whose gradient collectives are chosen by GSPMD
from the shardings (reference contrast: Ray Train wraps torch DDP,
train/torch/config.py:66 — here the "backend" is the compiler).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import transformer as tf
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.parallel.mesh import MeshPlan
from ray_tpu.parallel.pipeline import pipeline_apply, split_stages
from ray_tpu.parallel.ring import make_ring_attn_fn
from ray_tpu.parallel.ulysses import make_ulysses_attn_fn


def make_optimizer(lr: float = 3e-4, weight_decay: float = 0.1, warmup: int = 100, grad_clip: float = 1.0):
    sched = optax.warmup_cosine_decay_schedule(0.0, lr, warmup, max(warmup * 10, 1000))
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(sched, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def build_loss_fn(cfg: tf.TransformerConfig, plan: MeshPlan, mesh: Mesh, num_microbatches: int = 4):
    """Loss with the plan's parallelism baked in (ring or Ulysses
    attention for sp>1 per ``plan.sp_mode``, GPipe for pp>1)."""
    # sp dispatch: (shard_map wrapper for GSPMD-auto contexts, raw local
    # collective body for manual contexts like the pp pipeline)
    from ray_tpu.parallel.ring import ring_attention_local
    from ray_tpu.parallel.ulysses import ulysses_attention_local

    SP_MODES = {
        "ring": (make_ring_attn_fn, ring_attention_local),
        "ulysses": (make_ulysses_attn_fn, ulysses_attention_local),
    }
    attn_fn = None
    if plan.sp > 1:
        attn_fn = SP_MODES[plan.sp_mode][0](mesh)
    elif mesh.size > 1:
        # Pallas kernels can't be auto-partitioned by GSPMD — on any
        # multi-device mesh the flash attention must run inside its own
        # shard_map over the batch/head axes (ops/attention.py).
        from ray_tpu.ops.attention import make_flash_attn_fn

        attn_fn = make_flash_attn_fn(mesh)

    if plan.pp == 1:
        def loss(params, batch):
            return tf.loss_fn(params, batch, cfg, attn_fn)

        return loss

    S = plan.pp
    assert cfg.n_layers % S == 0, (cfg.n_layers, S)

    # pp × sp composition: the pipeline shard_map is manual over BOTH
    # axes, so the attention must be the raw per-shard collective body
    # (nested partial-manual shard_maps don't lower — see pipeline_apply).
    seq_axis = None
    stage_attn_fn = attn_fn
    if plan.sp > 1:
        stage_attn_fn = functools.partial(SP_MODES[plan.sp_mode][1], axis_name="sp")
        seq_axis = "sp"

    def stage_fn(stage_params, x, positions):
        def layer_fn(carry, lp):
            out = tf.decoder_layer(carry, lp, cfg, positions, stage_attn_fn)
            return out, None

        x, _ = jax.lax.scan(
            tf.checkpoint_layer(layer_fn, cfg), x, stage_params, unroll=cfg.scan_unroll
        )
        return x

    def loss(params, batch):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        b, s = inputs.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
        h = tf.embed(params, inputs, cfg)
        staged = split_stages(params["layers"], S)
        h = pipeline_apply(
            stage_fn, staged, h, positions, mesh, S, num_microbatches,
            seq_axis=seq_axis,
        )
        logits = tf.unembed(params, h, cfg)
        mask = batch.get("mask")
        return tf.token_nll(logits, targets, mask[:, 1:] if mask is not None else None)

    return loss


def make_train_state(
    cfg: tf.TransformerConfig,
    plan: MeshPlan,
    mesh: Mesh,
    optimizer=None,
    seed: int = 0,
):
    """Returns (params, opt_state, shardings dict) — initialized sharded."""
    optimizer = optimizer or make_optimizer()
    p_shard = mesh_lib.param_shardings(mesh, cfg, plan)

    @functools.partial(jax.jit, out_shardings=p_shard)
    def _init(key):
        return tf.init_params(key, cfg)

    params = _init(jax.random.PRNGKey(seed))

    opt_shard = _opt_state_shardings(optimizer, params, p_shard, mesh)

    @functools.partial(jax.jit, out_shardings=opt_shard)
    def _init_opt(p):
        return optimizer.init(p)

    opt_state = _init_opt(params)
    return params, opt_state, {"params": p_shard, "opt": opt_shard}


def _opt_state_shardings(optimizer, params, p_shard, mesh):
    """Optimizer-state subtrees that mirror the param tree (Adam moments)
    get the params' shardings — sharded optimizer state is the PAPERS.md
    cross-replica weight-update-sharding recipe; scalar leaves replicate."""
    shapes = jax.eval_shape(optimizer.init, params)
    rep = NamedSharding(mesh, P())
    params_treedef = jax.tree.structure(params)

    def is_param_like(subtree) -> bool:
        try:
            return jax.tree.structure(subtree) == params_treedef
        except Exception:
            return False

    leaves, treedef = jax.tree.flatten(shapes, is_leaf=is_param_like)
    out = [p_shard if is_param_like(leaf) else rep for leaf in leaves]
    return jax.tree.unflatten(treedef, out)


def make_train_step(
    cfg: tf.TransformerConfig,
    plan: MeshPlan,
    mesh: Mesh,
    optimizer=None,
    num_microbatches: int = 4,
) -> Callable:
    """jitted (params, opt_state, batch) → (params, opt_state, metrics)."""
    optimizer = optimizer or make_optimizer()
    loss_fn = build_loss_fn(cfg, plan, mesh, num_microbatches)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        gnorm = optax.global_norm(grads)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    # Shardings ride on the committed arrays (params/opt_state come out of
    # make_train_state sharded; callers device_put batches with
    # ``mesh_lib.batch_sharding``) — jit propagates them.
    return jax.jit(step, donate_argnums=(0, 1))
