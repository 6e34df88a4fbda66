"""Dense decoder whose every mixer is power retention of degree 2, for the paged
serving path (``models/paged.py`` reaches it through ``paged_model``).

Names are the published configuration's (``brumby``: Brumby-14B-Base, a Qwen3
body retrained with its attention replaced). Every norm an RMSNorm with
``rms_norm_eps``; pre-norm residuals ``x += mixer(norm(x))``, ``x +=
swiglu(mlp_norm(x))`` (``transformer.mlp_block``).

**The mixer** (``H`` query heads on ``KV`` key/value heads of ``head_dim``, ``G =
H / KV`` queries a state; ``h = norm(x)``): ``q = h W_q``, ``k = h W_k``, ``v = h
W_v``; an RMSNorm a head on ``q`` and on ``k`` (one scale of ``head_dim`` each)
BEFORE the rotation (``rope_theta``, the repo's rope); one decay a key/value
head a token, ``log g = log_sigmoid(h W_g + b_g)``; then, with ``G_t`` the
running sum of ``log g``, token ``t`` weighs token ``j <= t`` by ``exp(G_t -
G_j) (q_t . k_j) ** 2 / head_dim`` and reads the weighted mean of the values
(the weights' sum ``+ eps`` below it); out ``= concat(y) W_o``.

**What a layer keeps**: no row a token at all. The weights are inner products of
``phi(q)`` and ``phi(k)`` (``ops/power_retention.expand``), so the past is a
state BY SLOT, ``[KV, VALUES, P]`` float32 (``power``: a head's ``phi(k) v^T``
sums with their normaliser folded in as one more value; 36 MB a slot a layer at
the published sizes). A decode step advances it for every slot whose ``lens`` is
above 0 and for no other (``ops/power_retention.power_update``); a chunk call
reads it where a segment does not begin its prompt and stores it after the
segment's last real token (``power_chunk_scan``). The model has NO pool of
blocks: ``tables``, ``table_rows`` and ``rows_at`` come with no column and
nothing reads them.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import paged
from ray_tpu.models.hybrid_ssm import _segments
from ray_tpu.models.transformer import Params, _rope, embed, mlp_block, rms_norm
from ray_tpu.ops.power_retention import phi_width, power_chunk_scan, power_update, values_rows


@dataclasses.dataclass(frozen=True)
class PowerRetentionConfig:
    num_hidden_layers: int = 40
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16  # compute dtype; the state is float32 whatever this is

    num_experts = 0  # what ``transformer.mlp_block`` asks a configuration: a dense SwiGLU

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads or self.head_dim % 2:
            raise ValueError("the query heads are whole groups of the key/value heads, and a "
                             "head's width is even (the rotation, and phi's layout)")

    @property
    def group(self) -> int:
        """Query heads that read one state."""
        return self.num_attention_heads // self.num_key_value_heads


def layer_shapes(cfg: PowerRetentionConfig) -> dict:
    """name -> shape of one layer."""
    D, F, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    H, KV = cfg.num_attention_heads, cfg.num_key_value_heads
    return {"norm": (D,), "w_q": (D, H * d), "w_k": (D, KV * d), "w_v": (D, KV * d),
            "w_g": (D, KV), "b_g": (KV,), "q_norm": (d,), "k_norm": (d,), "w_o": (H * d, D),
            "mlp_norm": (D,), "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}


GATE_STD = 0.5  # of ``h W_g``: a token moves a head's memory by a factor of e ** +-0.5 or so


def init_one(key, name: str, shape: tuple):
    """One parameter, float32: norms one, matrices normal at 1/sqrt(fan_in)
    (``w_g`` at ``GATE_STD`` of that), and ``b_g`` the logit of a decay a token
    whose distance from one is log-uniform in [0.0005, 0.1]: decays 0.9 to
    0.9995, memories of ten to two thousand tokens (at 0 a random gate is 0.5
    and nothing outlives ten tokens). ``shape``'s leading axis may be a stack."""
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    if name == "b_g":
        forgets = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(5e-4), jnp.log(0.1)))
        return jnp.log1p(-forgets) - jnp.log(forgets)
    scale = GATE_STD if name == "w_g" else 1.0
    return jax.random.normal(key, shape, jnp.float32) * (scale * shape[-2] ** -0.5)


def init_params(key: jax.Array, cfg: PowerRetentionConfig) -> Params:
    """Seeded float32 parameters (``init_one``), one kind of layer, stacked."""
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    D, L = cfg.hidden_size, cfg.num_hidden_layers
    return {
        "embed": jax.random.normal(k_emb, (cfg.vocab_size, D), jnp.float32),
        "layers": {name: init_one(jax.random.fold_in(k_layers, j), name, (L,) + shape)
                   for j, (name, shape) in enumerate(layer_shapes(cfg).items())},
        "final_norm": jnp.ones((D,), jnp.float32),
        "lm_head": jax.random.normal(k_out, (D, cfg.vocab_size), jnp.float32) * D ** -0.5,
    }


def _unembed(params: Params, x, cfg: PowerRetentionConfig):
    h = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.dot(h, params["lm_head"].astype(h.dtype), preferred_element_type=jnp.float32)


@jax.named_scope("power.project")
def _project(h, lp: Params, cfg: PowerRetentionConfig, positions):
    """Normed hidden [T, D] at ``positions`` [T] → (q [T, KV, G, d], k, v [T, KV,
    d], log g [T, KV]), all float32: a head's q and k normed, then rotated."""
    T = h.shape[0]
    H, KV, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

    def heads(name, n):
        out = jnp.dot(h, lp[name].astype(h.dtype), preferred_element_type=jnp.float32)
        return out.reshape(T, n, d)

    def turned(x, scale):
        return _rope(rms_norm(x, lp[scale], cfg.rms_norm_eps)[None], positions[None], cfg.rope_theta)[0]

    raw = jnp.dot(h, lp["w_g"].astype(h.dtype), preferred_element_type=jnp.float32)
    log_g = jax.nn.log_sigmoid(raw + lp["b_g"].astype(jnp.float32))
    q = turned(heads("w_q", H), "q_norm").reshape(T, KV, cfg.group, d)
    return q, turned(heads("w_k", KV), "k_norm"), heads("w_v", KV), log_g


def _out(y, lp: Params, cfg: PowerRetentionConfig):
    """The heads' reads [T, KV, G, d] float32 → the mixer's output [T, D]."""
    return y.reshape(y.shape[0], -1).astype(cfg.dtype) @ lp["w_o"].astype(cfg.dtype)


def _mlp(x, lp: Params, cfg: PowerRetentionConfig):
    with jax.named_scope("paged.mlp"):
        return mlp_block(x, lp, cfg, cfg.rms_norm_eps)


def _decode_layer(cfg: PowerRetentionConfig, x, pools, lp: Params, _tables, lens, _params, _index,
                  bases):
    """One layer, one token a slot (``PagedModel.decode_layer``). x: [b, 1, D];
    the pool ``[R, KV, VALUES, P]`` flat, rows ``bases[0] + slot`` this layer's;
    a slot with ``lens`` 0 keeps its row as it is."""
    (state,) = pools
    q, k, v, log_g = _project(rms_norm(x[:, 0], lp["norm"], cfg.rms_norm_eps), lp, cfg, lens)
    state, y = power_update(state, bases[0], lens, jnp.exp(log_g), k, q, v)
    return _mlp(x + _out(y, lp, cfg)[:, None], lp, cfg), (state,), None


def _chunk_layer(cfg: PowerRetentionConfig, x, pools, lp: Params, _table_rows, _rows_at, _offs,
                 qpos, live, _params, _index, bases, slot_of):
    """One layer over a chunk call's token axis (``PagedModel.chunk_layer``). x:
    [1, T, D], n tiles of C; tile t is of slot ``slot_of[t]`` (the slots' count:
    nobody's), begins at position ``qpos[t, 0]`` and holds ``live[t]`` real tokens."""
    (state,) = pools
    n, C = qpos.shape
    n_slots = state.shape[0] // cfg.num_hidden_layers
    fresh, cont, last = _segments(qpos, slot_of, n_slots)
    q, k, v, log_g = _project(rms_norm(x[0], lp["norm"], cfg.rms_norm_eps), lp, cfg, qpos.reshape(-1))
    state, y = power_chunk_scan(
        state, jnp.where(slot_of < n_slots, bases[0] + slot_of, state.shape[0]), fresh, cont, last,
        live, *(a.reshape((n, C) + a.shape[1:]) for a in (log_g, q, k, v)))
    return _mlp(x + _out(y.reshape((n * C,) + y.shape[2:]), lp, cfg)[None], lp, cfg), (state,), None


@paged.paged_model.register
def _(cfg: PowerRetentionConfig) -> paged.PagedModel:
    row = (cfg.num_key_value_heads, values_rows(cfg.head_dim), phi_width(cfg.head_dim))
    return paged.PagedModel(
        pools={"power": paged.Pool(row=row, layers=cfg.num_hidden_layers, unit="slots",
                                   dtype=jnp.float32)},
        decode_layer=functools.partial(_decode_layer, cfg),
        chunk_layer=functools.partial(_chunk_layer, cfg),
        embed=embed,
        unembed=_unembed,
    )
