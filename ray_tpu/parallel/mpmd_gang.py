"""Cross-process MPMD pipeline: per-stage gangs in one jax.distributed
runtime, activations crossing stage (and host) boundaries on the
collective fabric.

Reference: the reference's compiled DAGs run pipeline stages as actors
on different NODES with NCCL device channels between them
(python/ray/experimental/channel/torch_tensor_nccl_channel.py:190,
nccl_group.py:23, dag/dag_node_operation.py op-graph schedules). The
TPU-native shape replaces NCCL p2p with hop_bridge.HopBridge — a tiny
SPMD program over the two stages' device rows that both gangs dispatch
at the same schedule point, so XLA routes the activation over ICI/DCN
(gloo on the CPU simulation).

Topology: the global device list (sorted process-major) splits into
``num_stages`` contiguous equal groups. A process "participates" in a
stage when it owns any of that stage's devices — one process may own
several stages (the single-process degenerate case runs the exact same
code), and one stage may span several processes (its stage programs then
run SPMD across that gang).

Every participating process executes the SAME Python schedule; per-op
guards keep each process to its own stages plus the bridges adjacent to
them. Loss math is the ``full_head`` mode of parallel/mpmd (one head
over the re-assembled batch) built from the SAME stage_fn/head builders,
so the loss matches the in-graph GPipe loss bit-for-bit.
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
from ray_tpu.parallel.hop_bridge import HopBridge
from ray_tpu.parallel.mpmd import make_embed_bwd, make_head_loss, stage_programs


@dataclass
class _GangStage:
    index: int
    devices: List[Any]
    mesh: Mesh
    sharding: NamedSharding
    local: bool  # this process owns devices in the stage
    fwd: Optional[Callable]
    bwd: Optional[Callable]
    layer_shardings: Any = None  # per-leaf shardings of the stage's layers


def _local_copy(value) -> np.ndarray:
    """Host copy of a group-replicated global array via its first
    addressable shard (float()/np.asarray need full addressability)."""
    return np.asarray(value.addressable_shards[0].data)


class MpmdGangPipeline:
    """MPMD transformer pipeline across a jax.distributed gang."""

    def __init__(self, cfg: tf.TransformerConfig, num_stages: int, attn_fn=None,
                 stage_tp: int = 1):
        from ray_tpu.parallel import mesh as mesh_lib

        self.cfg = cfg
        self.num_stages = num_stages
        self.stage_tp = stage_tp
        devices = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
        assert len(devices) % num_stages == 0, (len(devices), num_stages)
        assert cfg.n_layers % num_stages == 0, (cfg.n_layers, num_stages)
        per = len(devices) // num_stages
        assert per % stage_tp == 0, (per, stage_tp)
        rep = per // stage_tp
        my_pid = jax.process_index()

        # tp inside a stage keeps activations replicated at the stage
        # boundary (Megatron contract), so the hop bridge is unchanged;
        # params are tp-sharded which needs single-owner commits.
        self._stage_plan = mesh_lib.MeshPlan(tp=stage_tp)
        all_specs = mesh_lib.param_specs(cfg, self._stage_plan)
        layer_specs = all_specs["layers"]

        self.stages: List[_GangStage] = []
        for s in range(num_stages):
            devs = devices[s * per : (s + 1) * per]
            owners = {d.process_index for d in devs}
            if stage_tp > 1 and len(owners) > 1:
                raise NotImplementedError(
                    "stage_tp > 1 needs each stage owned by one process "
                    "(stage-per-host MPMD); multi-process tp stages would "
                    f"need sharded cross-process commits (stage {s} spans "
                    f"processes {sorted(owners)})"
                )
            mesh = Mesh(
                np.array(devs).reshape(rep, 1, stage_tp), ("rep", "fsdp", "tp")
            )
            stage_fn, bwd_fn = stage_programs(cfg, attn_fn, mesh)
            shard = NamedSharding(mesh, P())
            lshard = jax.tree.map(
                lambda sp: NamedSharding(mesh, sp), layer_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
            local = any(d.process_index == my_pid for d in devs)
            self.stages.append(
                _GangStage(
                    index=s,
                    devices=devs,
                    mesh=mesh,
                    sharding=shard,
                    local=local,
                    fwd=jax.jit(stage_fn, out_shardings=shard) if local else None,
                    bwd=jax.jit(bwd_fn, out_shardings=(shard, lshard)) if local else None,
                    layer_shardings=lshard,
                )
            )
        # hop bridges between consecutive stages (collective programs;
        # construction is metadata-only, transfer() guards participation)
        self.bridges: List[HopBridge] = [
            HopBridge(self.stages[s].devices, self.stages[s + 1].devices)
            for s in range(num_stages - 1)
        ]
        first, last = self.stages[0], self.stages[-1]
        self._embed_shardings = {
            "embed": NamedSharding(first.mesh, all_specs["embed"])
        }
        self._head_shardings = {
            "final_norm": NamedSharding(last.mesh, all_specs["final_norm"]),
            "lm_head": NamedSharding(last.mesh, all_specs["lm_head"]),
        }
        self._embed = (
            jax.jit(
                lambda emb_params, tokens: tf.embed(emb_params, tokens, cfg),
                out_shardings=first.sharding,
            )
            if first.local else None
        )
        self._head_grad = (
            jax.jit(jax.value_and_grad(make_head_loss(cfg), argnums=(0, 1)))
            if last.local else None
        )
        self._embed_bwd = (
            jax.jit(make_embed_bwd(cfg), out_shardings=self._embed_shardings)
            if first.local else None
        )

    # ------------------------------------------------------------------
    def _commit(self, arr, stage: _GangStage, sharding=None):
        """Place host data onto a stage's (possibly multi-process) mesh —
        replicated by default, or per ``sharding`` (tp-sharded params).
        Participating processes only."""
        if not stage.local:
            return None
        from ray_tpu.parallel.hop_bridge import commit_replicated

        return commit_replicated(arr, stage.devices, sharding or stage.sharding)

    def split_params(self, params: Dict[str, Any]):
        """Full host param tree (identical on every process) → this
        process's stage partitions: embed with stage 0, layer slices per
        stage, head with the last stage. Non-participating partitions
        are None."""
        L, S = self.cfg.n_layers, self.num_stages
        per = L // S
        stage_layers = []
        for s in range(S):
            st = self.stages[s]
            if st.local:
                sl = jax.tree.map(
                    lambda x: np.asarray(x)[s * per : (s + 1) * per],
                    params["layers"],
                )
                stage_layers.append(
                    jax.tree.map(
                        lambda a, sh: self._commit(a, st, sh),
                        sl, st.layer_shardings,
                    )
                )
            else:
                stage_layers.append(None)
        embed_params = (
            {"embed": self._commit(params["embed"], self.stages[0],
                                   self._embed_shardings["embed"])}
            if self.stages[0].local else None
        )
        head_params = (
            {k: self._commit(params[k], self.stages[-1], self._head_shardings[k])
             for k in ("final_norm", "lm_head")}
            if self.stages[-1].local else None
        )
        return embed_params, stage_layers, head_params

    # ------------------------------------------------------------------
    def loss_and_grads(self, params, batch: Dict[str, np.ndarray],
                       num_microbatches: int):
        """Full fwd+bwd. ``batch`` is HOST data, identical on every
        participating process (the pipeline is dp=1; data parallelism is
        an outer axis). Returns (loss, (g_embed, g_stage, g_head)) where
        loss is a host float on every process and each grad partition is
        present only on its stage's processes."""
        cfg = self.cfg
        S, M = self.num_stages, num_microbatches
        tokens = np.asarray(batch["tokens"])
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        b, seq = inputs.shape
        assert b % M == 0, (b, M)
        mb = b // M
        act_shape = (mb, seq, cfg.d_model)
        act_dtype = cfg.dtype
        positions = np.broadcast_to(
            np.arange(seq, dtype=np.int32)[None, :], (mb, seq)
        )
        mask = batch.get("mask")
        embed_params, stage_layers, head_params = params
        first, last = self.stages[0], self.stages[-1]

        pos_by_stage = [
            self._commit(positions, st) if st.local else None
            for st in self.stages
        ]

        # ---- forward wavefront -------------------------------------
        h_mb: List[Any] = [None] * M
        if first.local:
            tokens0 = self._commit(inputs, first)
            h = self._embed(embed_params, tokens0)
            h_mb = [h[m * mb : (m + 1) * mb] for m in range(M)]
        saved_inputs = [[None] * M for _ in range(S)]
        outs: List[Any] = [None] * M
        for m in range(M):
            x = h_mb[m]
            for s in range(S):
                st = self.stages[s]
                if st.local:
                    saved_inputs[s][m] = x
                    x = st.fwd(stage_layers[s], x, pos_by_stage[s])
                if s + 1 < S:
                    x = self.bridges[s].transfer(
                        x if st.local else None, act_shape, act_dtype
                    )
            if last.local:
                outs[m] = x

        # ---- head over the re-assembled batch (full_head mode) ------
        loss_arr = None
        g_out_mb: List[Any] = [None] * M
        g_head = None
        if last.local:
            h_full = jnp.concatenate(outs, axis=0)
            targets_l = self._commit(targets, last)
            mask_l = self._commit(mask[:, 1:], last) if mask is not None else None
            loss_arr, (g_head, g_h) = self._head_grad(
                head_params, h_full, targets_l, mask_l
            )
            g_out_mb = [g_h[m * mb : (m + 1) * mb] for m in range(M)]

        # ---- backward drain (microbatch order, deterministic sums) --
        g_stage: List[Any] = [None] * S
        g_first_inputs: List[Any] = []
        for m in range(M):
            gy = g_out_mb[m]
            for s in range(S - 1, -1, -1):
                st = self.stages[s]
                if st.local:
                    gx, gp = st.bwd(
                        stage_layers[s], saved_inputs[s][m], pos_by_stage[s], gy
                    )
                    g_stage[s] = gp if g_stage[s] is None else jax.tree.map(
                        jnp.add, g_stage[s], gp
                    )
                    gy = gx
                if s > 0:
                    gy = self.bridges[s - 1].transfer(
                        gy if st.local else None, act_shape, act_dtype,
                        reverse=True,
                    )
            if first.local:
                g_first_inputs.append(gy)

        g_embed = None
        if first.local:
            gh_embed = jnp.concatenate(g_first_inputs, axis=0)
            g_embed = self._embed_bwd(embed_params, tokens0, gh_embed)

        # ---- loss rides the reverse bridges to every stage ----------
        # Take every received copy unconditionally: after hop s the loss
        # must be resident on stage s-1's devices for the NEXT hop (a
        # process owning several consecutive stages re-sends the copy it
        # just received, never a stale earlier-stage-resident one).
        for s in range(S - 1, 0, -1):
            got = self.bridges[s - 1].transfer(
                loss_arr if self.stages[s].local else None, (), jnp.float32,
                reverse=True,
            )
            if got is not None:
                loss_arr = got
        loss = float(_local_copy(loss_arr)) if loss_arr is not None else None
        return loss, (g_embed, g_stage, g_head)


def mpmd_gang_train_step_fns(cfg: tf.TransformerConfig, num_stages: int,
                             optimizer=None, num_microbatches: int = 2,
                             attn_fn=None, stage_tp: int = 1):
    """Training-step closure over MpmdGangPipeline, mirroring
    mpmd.mpmd_train_step_fns: init_fn(params) -> (split, opt_states);
    step_fn(split, opt_states, batch) -> (split', opt_states', loss)."""
    import optax

    optimizer = optimizer or optax.adamw(1e-3)
    pipe = MpmdGangPipeline(cfg, num_stages, attn_fn=attn_fn, stage_tp=stage_tp)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def _apply_update(p, st, g):
        updates, st2 = optimizer.update(g, st, p)
        return optax.apply_updates(p, updates), st2

    def _opt_init(p):
        return jax.jit(optimizer.init)(p) if p is not None else None

    def init_fn(params):
        split = pipe.split_params(params)
        embed_params, stage_layers, head_params = split
        opt_states = (
            _opt_init(embed_params),
            [_opt_init(sl) for sl in stage_layers],
            _opt_init(head_params),
        )
        return split, opt_states

    def step_fn(split, opt_states, batch):
        embed_params, stage_layers, head_params = split
        st_embed, st_stages, st_head = opt_states
        loss, (g_embed, g_stage, g_head) = pipe.loss_and_grads(
            split, batch, num_microbatches
        )
        if g_embed is not None:
            embed_params, st_embed = _apply_update(embed_params, st_embed, g_embed)
        new_layers, new_states = [], []
        for s in range(num_stages):
            if g_stage[s] is not None:
                p2, s2 = _apply_update(stage_layers[s], st_stages[s], g_stage[s])
            else:
                p2, s2 = stage_layers[s], st_stages[s]
            new_layers.append(p2)
            new_states.append(s2)
        if g_head is not None:
            head_params, st_head = _apply_update(head_params, st_head, g_head)
        return (
            (embed_params, new_layers, head_params),
            (st_embed, new_states, st_head),
            loss,
        )

    return pipe, init_fn, step_fn
