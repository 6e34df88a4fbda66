"""Seeded weights of the power retention decoder, made by the benchmark and
handed to the program.

The tree is the one ``ray_tpu.models.power_retention`` takes: ``embed``,
``final_norm``, ``lm_head`` (untied) and ``layers``, one kind of layer,
stacked. Every layer has a key of its own, folded from its number, so
``reference_power_retention.py`` makes layer ``i`` again from the seed alone.
The key is a traced argument: a new seed compiles nothing.

What is no matrix follows the configuration's ``assumed``: norms one (the two a
head on the queries and the keys too), and the gate's bias ``b_g`` the logit of
a decay a token whose distance from one is log-uniform in [0.0005, 0.1]: decays
0.9 to 0.9995, memories of ten to two thousand tokens. ``w_g`` is normal at
``GATE_STD`` / sqrt(hidden): a token moves its head's memory by a factor of e **
+-0.5 or so about the head's own.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from chipbench.weights import _dense, seed_key  # noqa: F401 - seed_key is this module's too

GATE_STD = 0.5
FORGETS = (5e-4, 0.1)  # one less a head's decay a token, log-uniform between
HEAD_SLICES = 8  # of the vocabulary's columns, each from its own key (``head_slice``)
MATRICES = ("w_q", "w_k", "w_v", "w_g", "w_o", "w_gate", "w_up", "w_down")


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

    @property
    def group(self) -> int:
        """Query heads that read one state."""
        return self.heads // self.kv_heads

    def shapes(self) -> dict:
        d, f, hd = self.hidden, self.ffn, self.head_dim
        q, kv = self.heads * hd, self.kv_heads * hd
        return {"norm": (d,), "w_q": (d, q), "w_k": (d, kv), "w_v": (d, kv), "w_g": (d, self.kv_heads),
                "b_g": (self.kv_heads,), "q_norm": (hd,), "k_norm": (hd,), "w_o": (q, d),
                "mlp_norm": (d,), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def program_config(dims: Dims, dtype):
    """The program's configuration object for these sizes."""
    from ray_tpu.models.power_retention import PowerRetentionConfig

    return PowerRetentionConfig(
        num_hidden_layers=dims.layers, vocab_size=dims.vocab, hidden_size=dims.hidden,
        intermediate_size=dims.ffn, num_attention_heads=dims.heads,
        num_key_value_heads=dims.kv_heads, head_dim=dims.head_dim, rope_theta=dims.rope_theta,
        rms_norm_eps=dims.rms_eps, dtype=dtype)


def _one(key, name: str, shape: tuple):
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    if name == "b_g":
        lo, hi = FORGETS
        forgets = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(lo), jnp.log(hi)))
        return jnp.log1p(-forgets) - jnp.log(forgets)  # sigmoid(b_g) = 1 - forgets
    return _dense(key, shape, shape[0]) * (GATE_STD if name == "w_g" else 1.0)


def layer_params(key: jax.Array, index, dims: Dims) -> dict:
    """Layer ``index`` in float32. ``index`` may be traced (vmap)."""
    lk = jax.random.fold_in(key, index + 1)
    return {name: _one(jax.random.fold_in(lk, j), name, shape)
            for j, (name, shape) in enumerate(dims.shapes().items())}


def embed_rows(key: jax.Array, tokens, dims: Dims):
    """The embedding's rows of ``tokens`` [..] in float32, unit variance: every
    row from a key of its own, so the reference makes the rows it looks up and
    no other (the table in float32 is 3.1 GB, beside an engine of 13)."""
    ek = jax.random.fold_in(jax.random.fold_in(key, 0), 0)
    one = lambda tok: jax.random.normal(jax.random.fold_in(ek, tok), (dims.hidden,), jnp.float32)  # noqa: E731
    return jax.vmap(one)(tokens.reshape(-1)).reshape(tokens.shape + (dims.hidden,))


def head_slice(key: jax.Array, j, dims: Dims):
    """Columns ``j * vocab / HEAD_SLICES ..`` of the untied head in float32, from
    a key of their own (``j`` may be traced): the reference multiplies a slice
    at a time, for the embedding's reason."""
    hk = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(key, 0), 1), j)
    return _dense(hk, (dims.hidden, dims.vocab // HEAD_SLICES), dims.hidden)


def final_norm(dims: Dims):
    return jnp.ones((dims.hidden,), jnp.float32)


def top_params(key: jax.Array, dims: Dims) -> dict:
    """Embedding, final norm and the untied head in float32."""
    return {
        "embed": embed_rows(key, jnp.arange(dims.vocab), dims),
        "final_norm": final_norm(dims),
        "lm_head": jnp.concatenate([head_slice(key, j, dims) for j in range(HEAD_SLICES)], axis=1),
    }


def make_params(key: jax.Array, dims: Dims, dtype) -> dict:
    """The whole tree in ``dtype``, layers stacked. Call under ``jax.jit`` with
    the layouts the program wants as ``out_shardings``."""
    layers = jax.vmap(lambda i: layer_params(key, i, dims))(jnp.arange(dims.layers))
    tree = {**top_params(key, dims), "layers": layers}
    return jax.tree.map(lambda x: x.astype(dtype), tree)
