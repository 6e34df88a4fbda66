"""Seeded weights, made by the benchmark and handed to the program.

The tree is the one ``ray_tpu.models.transformer`` takes (``embed``,
``layers`` stacked on a leading layer axis, ``final_norm``, ``lm_head``).
Every layer has a key of its own, so ``reference.py`` can make layer ``i``
again from the seed alone, without holding the whole model in float32.
The key is a traced argument: a new seed never compiles a new program.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

LAYER_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, under the names the code uses."""

    vocab: int
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    rope_theta: float
    rms_eps: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        return cls(
            vocab=int(cfg["vocab_size"]), hidden=int(cfg["hidden_size"]),
            layers=int(cfg["num_hidden_layers"]), heads=int(cfg["num_attention_heads"]),
            kv_heads=int(cfg["num_key_value_heads"]), head_dim=int(cfg["head_dim"]),
            ffn=int(cfg["intermediate_size"]), rope_theta=float(cfg["rope_theta"]),
            rms_eps=float(cfg["rms_norm_eps"]),
        )

    def matrix_shapes(self) -> dict:
        d, q, kv, f = self.hidden, self.heads * self.head_dim, self.kv_heads * self.head_dim, self.ffn
        return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
                "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number: the driver's seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _dense(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)


def layer_params(key: jax.Array, index, dims: Dims) -> dict:
    """Layer ``index`` in float32. ``index`` may be traced (vmap)."""
    lk = jax.random.fold_in(key, index + 1)
    out = {"attn_norm": jnp.ones((dims.hidden,), jnp.float32),
           "mlp_norm": jnp.ones((dims.hidden,), jnp.float32)}
    for j, (name, shape) in enumerate(dims.matrix_shapes().items()):
        out[name] = _dense(jax.random.fold_in(lk, j), shape, shape[0])
    return out


def top_params(key: jax.Array, dims: Dims) -> dict:
    """Embedding, final norm and head in float32."""
    tk = jax.random.fold_in(key, 0)
    return {
        "embed": _dense(jax.random.fold_in(tk, 0), (dims.vocab, dims.hidden), 1),
        "final_norm": jnp.ones((dims.hidden,), jnp.float32),
        "lm_head": _dense(jax.random.fold_in(tk, 1), (dims.hidden, dims.vocab), dims.hidden),
    }


def make_params(key: jax.Array, dims: Dims, dtype) -> dict:
    """The whole tree in ``dtype``, layers stacked. Call under ``jax.jit``
    with the shardings (or layouts) the program wants as ``out_shardings``."""
    layers = jax.vmap(lambda i: layer_params(key, i, dims))(jnp.arange(dims.layers))
    tree = {**top_params(key, dims), "layers": layers}
    return jax.tree.map(lambda x: x.astype(dtype), tree)
