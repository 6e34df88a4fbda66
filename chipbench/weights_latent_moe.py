"""Seeded weights of the latent-attention expert decoder, made by the benchmark
and handed to the program.

The tree is the one ``ray_tpu.models.latent_moe`` takes: ``embed``,
``final_norm``, ``lm_head``, ``lead`` (the leading dense layers, stacked),
``layers`` (the expert layers less their routed experts, stacked) and
``experts`` (the routed experts HELD here: ``[expert layers, held, ...]``). Every layer has a key of its own and every expert a key
under its layer's, folded from the expert's index among ALL routed experts: so
``reference_latent_moe.py`` can make one layer's attention, or one expert,
again from the seed alone, and another share of the same layer draws the same
experts. The key is a traced argument: a new seed compiles nothing.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from chipbench.weights import _dense, seed_key  # noqa: F401 - seed_key is this module's too

@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, under the names the code uses."""

    vocab: int
    hidden: int
    layers: int
    lead: int  # leading dense layers
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    ffn: int
    expert_ffn: int
    experts: int  # routed experts of the whole layer: the router's width
    per_token: int
    shared: int
    scale: float
    rope_theta: float
    rms_eps: float
    held_first: int
    held: int  # routed experts of this chip's share

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        return cls(
            vocab=int(cfg["vocab_size"]), hidden=int(cfg["hidden_size"]),
            layers=int(cfg["num_hidden_layers"]), lead=int(cfg["first_k_dense_replace"]),
            heads=int(cfg["num_attention_heads"]), q_rank=int(cfg["q_lora_rank"]),
            kv_rank=int(cfg["kv_lora_rank"]), nope=int(cfg["qk_nope_head_dim"]),
            rope=int(cfg["qk_rope_head_dim"]), v_dim=int(cfg["v_head_dim"]),
            ffn=int(cfg["intermediate_size"]), expert_ffn=int(cfg["moe_intermediate_size"]),
            experts=int(cfg["n_routed_experts_published"]), per_token=int(cfg["num_experts_per_tok"]),
            shared=int(cfg["n_shared_experts"]), scale=float(cfg["routed_scaling_factor"]),
            rope_theta=float(cfg["rope_theta"]), rms_eps=float(cfg["rms_norm_eps"]),
            held_first=int(cfg["experts_held_first"]), held=int(cfg["n_routed_experts"]),
        )

    def attn_shapes(self) -> dict:
        d, h = self.hidden, self.heads
        return {"w_dq": (d, self.q_rank), "w_uq": (self.q_rank, h * (self.nope + self.rope)),
                "w_dkv": (d, self.kv_rank + self.rope),
                "w_ukv": (self.kv_rank, h * (self.nope + self.v_dim)), "wo": (h * self.v_dim, d)}

    def norm_shapes(self) -> dict:
        d = self.hidden
        return {"attn_norm": (d,), "q_norm": (self.q_rank,), "kv_norm": (self.kv_rank,),
                "post_attn_norm": (d,), "mlp_norm": (d,), "post_mlp_norm": (d,)}


def program_config(dims: Dims, dtype):
    """The program's configuration object for these sizes."""
    from ray_tpu.models.latent_moe import LatentMoEConfig

    return LatentMoEConfig(
        vocab_size=dims.vocab, hidden_size=dims.hidden, num_hidden_layers=dims.layers,
        first_k_dense_replace=dims.lead, num_attention_heads=dims.heads, q_lora_rank=dims.q_rank,
        kv_lora_rank=dims.kv_rank, qk_nope_head_dim=dims.nope, qk_rope_head_dim=dims.rope,
        v_head_dim=dims.v_dim, intermediate_size=dims.ffn, moe_intermediate_size=dims.expert_ffn,
        n_routed_experts=dims.experts, num_experts_per_tok=dims.per_token,
        n_shared_experts=dims.shared, routed_scaling_factor=dims.scale, rope_theta=dims.rope_theta,
        rms_norm_eps=dims.rms_eps, held_first=dims.held_first, held_count=dims.held, dtype=dtype)


def _layer_key(key, index):
    return jax.random.fold_in(key, index + 1)


def _matrices(key, shapes: dict) -> dict:
    return {name: _dense(jax.random.fold_in(key, j), shape, shape[0])
            for j, (name, shape) in enumerate(shapes.items())}


def norm_params(dims: Dims) -> dict:
    """A layer's six norm scales: one, whatever the seed and the layer."""
    return {name: jnp.ones(shape, jnp.float32) for name, shape in dims.norm_shapes().items()}


def attn_params(key: jax.Array, index, dims: Dims) -> dict:
    """Layer ``index``'s norms (all six) and attention matrices, float32."""
    return {**norm_params(dims),
            **_matrices(jax.random.fold_in(_layer_key(key, index), 0), dims.attn_shapes())}


def dense_params(key: jax.Array, index, dims: Dims) -> dict:
    """The feed-forward of a leading dense layer."""
    d, f = dims.hidden, dims.ffn
    return _matrices(jax.random.fold_in(_layer_key(key, index), 1),
                     {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)})


def moe_params(key: jax.Array, index, dims: Dims) -> dict:
    """An expert layer's router (over ALL routed experts) and shared expert."""
    d, s = dims.hidden, dims.expert_ffn * dims.shared
    return _matrices(jax.random.fold_in(_layer_key(key, index), 2),
                     {"router": (d, dims.experts), "shared_gate": (d, s), "shared_up": (d, s),
                      "shared_down": (s, d)})


def expert_params(key: jax.Array, index, expert, dims: Dims) -> dict:
    """Routed expert ``expert`` (its index among ALL of the layer's) of layer
    ``index``. Both may be traced."""
    d, f = dims.hidden, dims.expert_ffn
    ek = jax.random.fold_in(jax.random.fold_in(_layer_key(key, index), 3), expert)
    return _matrices(ek, {"e_gate": (d, f), "e_up": (d, f), "e_down": (f, d)})


def layer_params(key: jax.Array, index, dims: Dims, experts: bool) -> dict:
    """Layer ``index``, float32: a leading dense layer, or an expert layer less
    its routed experts (``held_params``). ``index`` may be traced (vmap)."""
    out = attn_params(key, index, dims)
    return {**out, **(moe_params if experts else dense_params)(key, index, dims)}


def held_params(key: jax.Array, index, dims: Dims) -> dict:
    """The routed experts of layer ``index`` that this share holds, stacked."""
    return jax.vmap(lambda e: expert_params(key, index, e, dims))(
        dims.held_first + jnp.arange(dims.held))


def top_params(key: jax.Array, dims: Dims) -> dict:
    """Embedding, final norm and head in float32 (the chip's slice of the
    vocabulary is the whole of what is made)."""
    tk = jax.random.fold_in(key, 0)
    return {
        "embed": _dense(jax.random.fold_in(tk, 0), (dims.vocab, dims.hidden), 1),
        "final_norm": jnp.ones((dims.hidden,), jnp.float32),
        "lm_head": _dense(jax.random.fold_in(tk, 1), (dims.hidden, dims.vocab), dims.hidden),
    }


def make_params(key: jax.Array, dims: Dims, dtype) -> dict:
    """The whole tree in ``dtype``. Call under ``jax.jit`` with the layouts the
    program wants as ``out_shardings``."""
    lead = jax.vmap(lambda i: layer_params(key, i, dims, False))(jnp.arange(dims.lead))
    expert_layers = jnp.arange(dims.lead, dims.layers)
    layers = jax.vmap(lambda i: layer_params(key, i, dims, True))(expert_layers)
    experts = jax.vmap(lambda i: held_params(key, i, dims))(expert_layers)
    tree = {**top_params(key, dims), "lead": lead, "layers": layers, "experts": experts}
    return jax.tree.map(lambda x: x.astype(dtype), tree)
