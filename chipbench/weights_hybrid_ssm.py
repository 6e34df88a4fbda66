"""Seeded weights of the hybrid state-space decoder, made by the benchmark and
handed to the program.

The tree is the one ``ray_tpu.models.hybrid_ssm`` takes: ``embed`` (the head
too: tied), ``final_norm`` and ``layers``, ONE period stacked by kind
(``mamba``: ``[periods, a period's, ...]``, ``attn`` likewise). Every layer has
a key of its own, folded from its number among ALL layers, so
``reference_hybrid_ssm.py`` makes layer ``i`` again from the seed alone. The
key is a traced argument: a new seed compiles nothing.

What is no matrix follows the Mamba-2 convention (the configuration's
``assumed``): ``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of a
step drawn log-uniformly from [0.001, 0.1], ``D`` one, norms one; the
depthwise convolution's weights and bias uniform in +-1/sqrt(4). The embedding
is normal at ``EMBED_STD``: the head is the embedding, and at unit variance a
token's own row, times ``embedding_multiplier``, would outweigh all forty
layers and every next token would be the last one again.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from chipbench.weights import _dense, seed_key  # noqa: F401 - seed_key is this module's too

EMBED_STD = 0.004
MATRICES = ("in_proj", "out_proj", "wq", "wk", "wv", "wo", "w_in", "w_out")


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, under the names the code uses."""

    vocab: int
    hidden: int
    layers: int
    kinds: tuple  # "mamba" | "attention", one a layer
    heads: int
    kv_heads: int
    ffn: int
    ssm_heads: int
    ssm_head: int
    state: int
    conv: int
    expand: int
    groups: int
    embed_mult: float
    logits_scale: float
    residual_mult: float
    attn_mult: float
    rms_eps: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        return cls(
            vocab=int(cfg["vocab_size"]), hidden=int(cfg["hidden_size"]),
            layers=int(cfg["num_hidden_layers"]), kinds=tuple(cfg["layer_types"]),
            heads=int(cfg["num_attention_heads"]), kv_heads=int(cfg["num_key_value_heads"]),
            ffn=int(cfg["shared_intermediate_size"]), ssm_heads=int(cfg["mamba_n_heads"]),
            ssm_head=int(cfg["mamba_d_head"]), state=int(cfg["mamba_d_state"]),
            conv=int(cfg["mamba_d_conv"]), expand=int(cfg["mamba_expand"]),
            groups=int(cfg["mamba_n_groups"]), embed_mult=float(cfg["embedding_multiplier"]),
            logits_scale=float(cfg["logits_scaling"]),
            residual_mult=float(cfg["residual_multiplier"]),
            attn_mult=float(cfg["attention_multiplier"]), rms_eps=float(cfg["rms_norm_eps"]),
        )

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def inner(self) -> int:
        return self.expand * self.hidden

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.groups * self.state

    def of_kind(self, kind: str) -> tuple:
        """The numbers, among all layers, of the layers of one kind."""
        return tuple(i for i, k in enumerate(self.kinds) if k == kind)

    def shapes(self, kind: str) -> dict:
        d, f = self.hidden, self.ffn
        both = {"norm": (d,), "mlp_norm": (d,), "w_in": (d, 2 * f), "w_out": (f, d)}
        if kind == "mamba":
            h = self.ssm_heads
            return {**both, "in_proj": (d, self.inner + self.conv_dim + h),
                    "conv_w": (self.conv_dim, self.conv), "conv_b": (self.conv_dim,),
                    "dt_bias": (h,), "A_log": (h,), "D": (h,), "gate_norm": (self.inner,),
                    "out_proj": (self.inner, d)}
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return {**both, "wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}


def program_config(dims: Dims, dtype):
    """The program's configuration object for these sizes."""
    from ray_tpu.models.hybrid_ssm import HybridSSMConfig

    return HybridSSMConfig(
        vocab_size=dims.vocab, hidden_size=dims.hidden, num_hidden_layers=dims.layers,
        layer_types=dims.kinds, num_attention_heads=dims.heads, num_key_value_heads=dims.kv_heads,
        shared_intermediate_size=dims.ffn, mamba_n_heads=dims.ssm_heads,
        mamba_d_head=dims.ssm_head, mamba_d_state=dims.state, mamba_d_conv=dims.conv,
        mamba_expand=dims.expand, mamba_n_groups=dims.groups, embedding_multiplier=dims.embed_mult,
        logits_scaling=dims.logits_scale, residual_multiplier=dims.residual_mult,
        attention_multiplier=dims.attn_mult, rms_norm_eps=dims.rms_eps, dtype=dtype)


def _one(key, name: str, shape: tuple, dims: Dims):
    if name.endswith("norm") or name == "D":
        return jnp.ones(shape, jnp.float32)
    if name in ("conv_w", "conv_b"):
        bound = dims.conv ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        return step + jnp.log(-jnp.expm1(-step))  # softplus(dt_bias) = step
    return _dense(key, shape, shape[0])


def layer_params(key: jax.Array, index, dims: Dims, kind: str) -> dict:
    """Layer ``index`` (its number among all layers; may be traced), of
    ``kind``, in float32."""
    lk = jax.random.fold_in(key, index + 1)
    return {name: _one(jax.random.fold_in(lk, j), name, shape, dims)
            for j, (name, shape) in enumerate(dims.shapes(kind).items())}


def top_params(key: jax.Array, dims: Dims) -> dict:
    """The embedding, which is the head too, and the final norm, float32."""
    tk = jax.random.fold_in(key, 0)
    return {"embed": _dense(jax.random.fold_in(tk, 0), (dims.vocab, dims.hidden), EMBED_STD ** -2),
            "final_norm": jnp.ones((dims.hidden,), jnp.float32)}


def make_params(key: jax.Array, dims: Dims, dtype) -> dict:
    """The whole tree in ``dtype``. Call under ``jax.jit`` with the layouts the
    program wants as ``out_shardings``."""
    periods = program_config(dims, dtype).periods  # the program's own reading of ``layer_types``
    layers = {}
    for kind, name in (("mamba", "mamba"), ("attention", "attn")):
        numbers = jnp.asarray(dims.of_kind(kind), jnp.int32)
        stacked = jax.vmap(lambda i, kind=kind: layer_params(key, i, dims, kind))(numbers)
        layers[name] = jax.tree.map(
            lambda a: a.reshape((periods, len(numbers) // periods) + a.shape[1:]), stacked)
    tree = {**top_params(key, dims), "layers": layers}
    return jax.tree.map(lambda x: x.astype(dtype), tree)
