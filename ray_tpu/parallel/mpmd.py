"""MPMD pipeline parallelism: one jit program per stage, per device set.

Reference: the compiled-DAG op-graph (python/ray/dag/dag_node_operation.py
:9-120 — per-actor READ/COMPUTE/WRITE op schedules with comm overlap) and
its NCCL device channels (experimental/channel/torch_tensor_nccl_channel
.py:190); SURVEY.md §7 names JaxPP-style MPMD as the hard part the
in-graph GPipe (parallel/pipeline.py) cannot cover: heterogeneous stages,
per-stage compilation, and pipelines spanning more devices than one XLA
program wants to address.

Shape here, TPU-first:
- each stage owns a disjoint device subset with its own ``Mesh`` and its
  own jit-compiled forward/backward programs (separate XLA programs — the
  "MPMD" in the name);
- activations hand off between stage meshes with ``jax.device_put`` —
  HBM→HBM over ICI when the meshes sit in one slice. Cross-PROCESS /
  cross-host handoff (DCN) is the collective-bridge program in
  hop_bridge.HopBridge, driven by the gang pipeline in mpmd_gang;
- the host issues the microbatch schedule; XLA's async dispatch runs
  stage programs concurrently, so issue order ≈ the reference's op-graph
  schedule. Backward for microbatch m is issued 1F1B-style (oldest
  first, interleaved with remaining forwards when the loss mode allows).

Two loss modes:
- ``full_head`` (default): the head (final-norm + unembed + NLL) runs
  once over the reassembled full batch — EXACTLY the math of the
  in-graph GPipe loss (train_step.build_loss_fn), so losses match
  bit-for-bit. Backward drains 1F1B-ordered after the head barrier.
- ``per_microbatch``: the head runs per microbatch (loss = mean over
  microbatches) — true 1F1B interleaving with bounded live activations,
  at the cost of a different (but mathematically equivalent) FP
  accumulation order.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import transformer as tf


# The cross-process/cross-host leg of the handoff lives in
# hop_bridge.HopBridge (a collective-bridge program per hop, jointly
# dispatched by both stage gangs); the gang-driven pipeline that uses it
# is parallel/mpmd_gang.MpmdGangPipeline. This module keeps the
# single-process form, whose handoffs are plain jax.device_put.


def make_stage_fn(cfg: "tf.TransformerConfig", attn_fn=None) -> Callable:
    """The per-stage layer-stack program. IDENTICAL structure to
    train_step.build_loss_fn's stage_fn — the bit-for-bit loss equality
    between MPMD (single- AND multi-process) and in-graph GPipe depends
    on every pipeline flavor using this one definition."""

    def stage_fn(stage_params, x, positions):
        def layer_fn(carry, lp):
            return tf.decoder_layer(carry, lp, cfg, positions, attn_fn), None

        x, _ = jax.lax.scan(tf.checkpoint_layer(layer_fn, cfg), x, stage_params)
        return x

    return stage_fn


def make_stage_bwd(stage_fn: Callable) -> Callable:
    """Recompute-in-backward VJP of a stage: only stage INPUTS are saved
    across the schedule, not intermediate activations."""

    def bwd(stage_params, x, positions, gy):
        y, vjp = jax.vjp(lambda p, xx: stage_fn(p, xx, positions), stage_params, x)
        gparams, gx = vjp(gy)
        del y
        return gx, gparams

    return bwd


def stage_programs(cfg: "tf.TransformerConfig", attn_fn, mesh: Mesh):
    """(fwd, bwd) bodies for the stage that lives on ``mesh``. A Pallas
    kernel cannot be auto-partitioned by GSPMD, so on a multi-device stage
    mesh the flash attention runs inside its own shard_map over that mesh
    — the same rule train_step.build_loss_fn applies to the in-graph
    plans (on a TPU the bare kernel is refused: "Mosaic kernels cannot be
    automatically partitioned")."""
    if attn_fn is None and mesh.size > 1:
        from ray_tpu.ops.attention import make_flash_attn_fn

        attn_fn = make_flash_attn_fn(mesh)
    stage_fn = make_stage_fn(cfg, attn_fn)
    return stage_fn, make_stage_bwd(stage_fn)


def make_head_loss(cfg: "tf.TransformerConfig") -> Callable:
    def head_loss(head_params, h, targets, mask):
        logits = tf.unembed(head_params, h, cfg)
        return tf.token_nll(logits, targets, mask)

    return head_loss


def make_embed_bwd(cfg: "tf.TransformerConfig") -> Callable:
    def embed_bwd(emb_params, tokens, gh):
        _, vjp = jax.vjp(lambda p: tf.embed(p, tokens, cfg), emb_params)
        (gp,) = vjp(gh)
        return gp

    return embed_bwd


@dataclass
class _Stage:
    index: int
    mesh: Mesh
    sharding: NamedSharding  # activation placement within the stage
    fwd: Callable  # (stage_params, x, positions) -> y
    bwd: Callable  # (stage_params, x, positions, gy) -> (gx, gparams)
    layer_shardings: Any = None  # per-leaf shardings of the stage's layers


class MpmdPipeline:
    """A transformer layer-stack pipeline where stage ``s`` is its own
    XLA program on its own devices.

    Stage interiors compose with tensor/FSDP parallelism: with
    ``stage_tp``/``stage_fsdp`` > 1 each stage's devices form a
    ``(fsdp, tp)`` mesh and the stage program is GSPMD-partitioned with
    the same Megatron/ZeRO specs the in-graph path uses
    (mesh.param_specs) — XLA inserts the per-block tp psums inside the
    stage while the pipeline schedule stays host-driven. Activations at
    stage boundaries are batch-sharded over fsdp and replicated over tp
    (the Megatron contract), so handoffs remain a single device_put."""

    def __init__(
        self,
        cfg: tf.TransformerConfig,
        num_stages: int,
        devices: Optional[List[Any]] = None,
        attn_fn=None,
        stage_tp: int = 1,
        stage_fsdp: int = 1,
    ):
        from ray_tpu.parallel import mesh as mesh_lib

        self.cfg = cfg
        self.num_stages = num_stages
        self.stage_tp = stage_tp
        self.stage_fsdp = stage_fsdp
        devices = list(devices if devices is not None else jax.devices())
        assert len(devices) % num_stages == 0, (len(devices), num_stages)
        assert cfg.n_layers % num_stages == 0, (cfg.n_layers, num_stages)
        per = len(devices) // num_stages
        inner = stage_tp * stage_fsdp
        assert per % inner == 0, (per, inner)
        # extra stage devices replicate over a leading "rep" axis
        rep = per // inner
        self._stage_plan = mesh_lib.MeshPlan(fsdp=stage_fsdp, tp=stage_tp)
        self._act_spec = P(("fsdp",) if stage_fsdp > 1 else None)
        self.stages: List[_Stage] = []

        all_specs = mesh_lib.param_specs(cfg, self._stage_plan)
        self._layer_specs = all_specs["layers"]
        for s in range(num_stages):
            devs = np.array(devices[s * per : (s + 1) * per]).reshape(
                rep, stage_fsdp, stage_tp
            )
            mesh = Mesh(devs, ("rep", "fsdp", "tp"))
            stage_fn, bwd = stage_programs(cfg, attn_fn, mesh)
            shard = NamedSharding(mesh, self._act_spec)
            lshard = jax.tree.map(
                lambda sp: NamedSharding(mesh, sp), self._layer_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
            self.stages.append(
                _Stage(
                    index=s,
                    mesh=mesh,
                    sharding=shard,
                    fwd=jax.jit(stage_fn, out_shardings=shard),
                    bwd=jax.jit(bwd, out_shardings=(shard, lshard)),
                    layer_shardings=lshard,
                )
            )
        first, last = self.stages[0], self.stages[-1]
        self._embed_shardings = {
            "embed": NamedSharding(first.mesh, all_specs["embed"])
        }
        self._head_shardings = {
            "final_norm": NamedSharding(last.mesh, all_specs["final_norm"]),
            "lm_head": NamedSharding(last.mesh, all_specs["lm_head"]),
        }
        # stage-resident programs for the model's ends
        self._embed = jax.jit(
            lambda emb_params, tokens: tf.embed(emb_params, tokens, cfg),
            out_shardings=first.sharding,
        )

        self._head_grad = jax.jit(
            jax.value_and_grad(make_head_loss(cfg), argnums=(0, 1)),
        )
        self._embed_bwd = jax.jit(
            make_embed_bwd(cfg), out_shardings=self._embed_shardings
        )

    # ------------------------------------------------------------------
    def split_params(self, params: Dict[str, Any]):
        """The flagship param tree → per-stage partitions, device_put onto
        each stage's mesh: embed params with stage 0, layer slices per
        stage, head (final_norm + lm_head) with the last stage."""
        L, S = self.cfg.n_layers, self.num_stages
        per = L // S
        stage_layers = []
        for s in range(S):
            sl = jax.tree.map(lambda x: x[s * per : (s + 1) * per], params["layers"])
            stage_layers.append(jax.device_put(sl, self.stages[s].layer_shardings))
        embed_params = jax.device_put(
            {k: v for k, v in params.items() if k == "embed"},
            self._embed_shardings,
        )
        head_params = jax.device_put(
            {k: params[k] for k in ("final_norm", "lm_head")},
            self._head_shardings,
        )
        return embed_params, stage_layers, head_params

    def _handoff(self, value, stage: _Stage):
        """Activation transfer onto ``stage``'s devices (ICI/HBM path).
        All stage meshes here are single-process; the cross-process form
        rides hop_bridge.HopBridge (see mpmd_gang)."""
        return jax.device_put(value, stage.sharding)

    # ------------------------------------------------------------------
    def forward(self, stage_layers, h_mb: List[jax.Array], positions):
        """Microbatch wavefront through the stage programs. Returns the
        per-microbatch outputs ON THE LAST STAGE's devices."""
        S = self.num_stages
        inflight: List[Any] = list(h_mb)
        saved_inputs = [[None] * len(h_mb) for _ in range(S)]
        pos_by_stage = [self._handoff(positions, st) for st in self.stages]
        outs: List[Any] = [None] * len(h_mb)
        # wavefront issue order == the op-graph's fwd schedule: stage s
        # runs microbatch m while stage s-1 runs m+1 (async dispatch)
        for m in range(len(h_mb)):
            x = self._handoff(inflight[m], self.stages[0])
            for s, st in enumerate(self.stages):
                saved_inputs[s][m] = x
                x = st.fwd(stage_layers[s], x, pos_by_stage[s])
                if s + 1 < S:
                    x = self._handoff(x, self.stages[s + 1])
            outs[m] = x
        return outs, saved_inputs, pos_by_stage

    def backward(self, stage_layers, saved_inputs, pos_by_stage, g_out_mb: List[jax.Array]):
        """1F1B-ordered backward drain: microbatch m's backward walks
        stages last→first; grads accumulate per stage in microbatch
        order (deterministic summation)."""
        S = self.num_stages
        g_stage: List[Any] = [None] * S
        g_first_inputs = []
        for m in range(len(g_out_mb)):
            gy = g_out_mb[m]
            for s in range(S - 1, -1, -1):
                st = self.stages[s]
                gy = self._handoff(gy, st)
                gx, gp = st.bwd(stage_layers[s], saved_inputs[s][m], pos_by_stage[s], gy)
                g_stage[s] = gp if g_stage[s] is None else jax.tree.map(
                    jnp.add, g_stage[s], gp
                )
                gy = gx
            g_first_inputs.append(gy)
        return g_stage, g_first_inputs

    # ------------------------------------------------------------------
    def loss_and_grads(self, params, batch, num_microbatches: int,
                       loss_mode: str = "full_head"):
        """Full fwd+bwd over the MPMD pipeline. Returns
        (loss, grads_by_partition) where grads_by_partition =
        (g_embed, [g_stage_layers...], g_head)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        b, s = inputs.shape
        assert b % num_microbatches == 0, (b, num_microbatches)
        mb = b // num_microbatches
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (mb, s))
        mask = batch.get("mask")
        embed_params, stage_layers, head_params = params

        tokens0 = self._handoff(inputs, self.stages[0])
        h = self._embed(embed_params, tokens0)
        h_mb = [h[m * mb : (m + 1) * mb] for m in range(num_microbatches)]
        outs, saved_inputs, pos_by_stage = self.forward(stage_layers, h_mb, positions)

        last = self.stages[-1]
        if loss_mode == "full_head":
            # EXACT in-graph GPipe math: one head over the full batch
            h_full = jnp.concatenate(outs, axis=0)
            targets_l = self._handoff(targets, last)
            mask_l = self._handoff(mask[:, 1:], last) if mask is not None else None
            loss, (g_head, g_h) = self._head_grad(head_params, h_full, targets_l, mask_l)
            g_out_mb = [g_h[m * mb : (m + 1) * mb] for m in range(num_microbatches)]
        elif loss_mode == "per_microbatch":
            # true 1F1B: per-microbatch head. Each microbatch's masked
            # mean must be re-weighted by ITS token count so the combined
            # objective equals the global masked mean (uniform 1/M would
            # over-weight sparse microbatches); unmasked microbatches are
            # equal-sized, so 1/M is exact there.
            if mask is not None:
                m_counts = [
                    jnp.maximum(mask[m * mb : (m + 1) * mb, 1:].sum(), 1)
                    for m in range(num_microbatches)
                ]
                total = sum(m_counts[1:], m_counts[0])
                weights = [c / total for c in m_counts]
            else:
                weights = [1.0 / num_microbatches] * num_microbatches
            losses, g_out_mb, g_head = [], [], None
            for m in range(num_microbatches):
                t_m = self._handoff(targets[m * mb : (m + 1) * mb], last)
                m_m = (
                    self._handoff(mask[m * mb : (m + 1) * mb, 1:], last)
                    if mask is not None else None
                )
                l_m, (gh_m, g_h_m) = self._head_grad(head_params, outs[m], t_m, m_m)
                w = weights[m]
                losses.append(l_m * w)
                g_out_mb.append(jax.tree.map(lambda x: x * w, g_h_m))
                gh_m = jax.tree.map(lambda x: x * w, gh_m)
                g_head = gh_m if g_head is None else jax.tree.map(jnp.add, g_head, gh_m)
            loss = sum(losses[1:], losses[0])
        else:
            raise ValueError(f"unknown loss_mode {loss_mode!r}")

        g_stage, g_first = self.backward(stage_layers, saved_inputs, pos_by_stage, g_out_mb)
        gh_embed = jnp.concatenate(
            [self._handoff(g, self.stages[0]) for g in g_first], axis=0
        )
        g_embed = self._embed_bwd(embed_params, tokens0, gh_embed)
        return loss, (g_embed, g_stage, g_head)


def mpmd_train_step_fns(cfg: tf.TransformerConfig, num_stages: int,
                        devices=None, optimizer=None, num_microbatches: int = 2,
                        stage_tp: int = 1, stage_fsdp: int = 1):
    """A full MPMD training step (loss + grads + per-partition optimizer
    update) as host-driven per-stage programs. Returns
    (pipeline, init_fn, step_fn):
      init_fn(params)   -> (split_params, opt_states)
      step_fn(split_params, opt_states, batch) -> (params', states', loss)
    """
    import optax

    optimizer = optimizer or optax.adamw(1e-3)
    pipe = MpmdPipeline(
        cfg, num_stages, devices, stage_tp=stage_tp, stage_fsdp=stage_fsdp
    )

    # One jitted apply serves every partition: output placement follows
    # the donated inputs, and the jit cache keys on shapes/shardings.
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def _apply_update(p, st, g):
        updates, st2 = optimizer.update(g, st, p)
        return optax.apply_updates(p, updates), st2

    def init_fn(params):
        split = pipe.split_params(params)
        embed_params, stage_layers, head_params = split
        opt_states = (
            jax.jit(optimizer.init)(embed_params),
            [jax.jit(optimizer.init)(sl) for sl in stage_layers],
            jax.jit(optimizer.init)(head_params),
        )
        return split, opt_states

    def step_fn(split, opt_states, batch, loss_mode: str = "full_head"):
        embed_params, stage_layers, head_params = split
        st_embed, st_stages, st_head = opt_states
        loss, (g_embed, g_stage, g_head) = pipe.loss_and_grads(
            split, batch, num_microbatches, loss_mode=loss_mode
        )
        embed_params, st_embed = _apply_update(embed_params, st_embed, g_embed)
        new_layers, new_states = [], []
        for s in range(num_stages):
            p2, s2 = _apply_update(stage_layers[s], st_stages[s], g_stage[s])
            new_layers.append(p2)
            new_states.append(s2)
        head_params, st_head = _apply_update(head_params, st_head, g_head)
        return (
            (embed_params, new_layers, head_params),
            (st_embed, new_states, st_head),
            loss,
        )

    return pipe, init_fn, step_fn
