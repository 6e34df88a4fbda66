"""Decoder with ``hc_mult`` residual streams a token, mixed around every sublayer
by manifold-constrained hyper-connections (``ops/hyper_connections.py``), whose
sublayers are ``models/latent_moe.py``'s: latent attention, and a dense SwiGLU
in the ``first_k_dense_replace`` leading layers or the sigmoid-routed expert
layer. For the paged serving path (``models/paged.py`` reaches it through
``paged_model``).

Names are the published configuration's (``xing4_0``). A token's state is ``X``
in ``R^{n x D}``; a layer is two sublayers, each with maps of its own
(``lp["hc_attn"]``, ``lp["hc_mlp"]``):

    Hpre, Hpost, Hres = maps(X);  u = Hpre X;  y = F(norm(u));  X' = Hres X + Hpost^T y

with ``F`` the attention (``attn_norm``) and then the feed-forward (``mlp_norm``),
neither changed: ``latent_moe.decode_attention`` / ``chunk_attention`` and
``latent_moe.feed_forward`` are called between ``mix_in`` and ``mix_out``.
There is no ``x + f(x)`` and no sandwich norm. ``embed`` makes the streams
(``n`` copies of the token's embedding), ``unembed`` joins them (their sum,
then ``final_norm`` and the head). The paged programs carry ``X`` as they carry
any ``x``: ``[b, s, n, D]`` where another model has ``[b, s, D]``.

Attention is ``latent_moe``'s with the rotary frequencies blended as YaRN has
them (``rope_frequencies``: each between itself and itself over ``rope_factor``
by the linear ramp of ``rope_beta_fast`` / ``rope_beta_slow`` over
``rope_original_positions``; static, whatever the length; cos and sin unscaled
while ``rope_mscale == rope_mscale_all_dim``) and the softmax scale times
``mscale^2``. The router selects on ``score + expert_bias`` in one group
(``latent_moe.route``).

Counts (``PagedModel``): ``latent_moe.routed_experts``' five, three that are
another model's (0), then token PLACES that went through ``mix_out`` (padding
and idle slots included: what the bytes follow) and real tokens that did, each
times the sublayers.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import latent_moe, paged
from ray_tpu.models.transformer import Params, rms_norm
from ray_tpu.ops import hyper_connections as hc

SUBLAYERS = 2  # mixes a layer: around the attention, around the feed-forward


@dataclasses.dataclass(frozen=True)
class HyperLatentMoEConfig(latent_moe.LatentMoEConfig):
    vocab_size: int = 131072
    hidden_size: int = 3584
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    rope_theta: float = 1e4
    rms_norm_eps: float = 1e-6
    # Residual streams a token, and what makes their maps (``ops/hyper_connections.py``).
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # YaRN (``rope_scaling``): a factor of 1 is the plain rotary embedding.
    rope_factor: float = 64.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_original_positions: int = 4096
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0

    def __post_init__(self):
        if self.rope_mscale != self.rope_mscale_all_dim:
            raise ValueError("cos and sin are left unscaled: rope_mscale must equal rope_mscale_all_dim")

    @property
    def rope_frequencies(self) -> Tuple[float, ...]:
        """The ``qk_rope_head_dim / 2`` rotary frequencies: pair ``i``'s own
        ``theta ** (-i / half)`` where it turns more than ``rope_beta_fast``
        times over the original positions, that over ``rope_factor`` where it
        turns fewer than ``rope_beta_slow`` times, a linear blend by the pair's
        number between."""
        dim, base = self.qk_rope_head_dim, self.rope_theta
        half = dim // 2
        own = [base ** (-i / half) for i in range(half)]
        if self.rope_factor == 1:
            return tuple(own)

        def pair_that_turns(rotations: float) -> float:
            return dim * math.log(self.rope_original_positions / (rotations * 2 * math.pi)) / (
                2 * math.log(base))

        low = max(math.floor(pair_that_turns(self.rope_beta_fast)), 0)
        high = min(math.ceil(pair_that_turns(self.rope_beta_slow)), dim - 1)
        span = (high - low) or 0.001
        ramp = [min(1.0, max(0.0, (i - low) / span)) for i in range(half)]
        return tuple(f * (1 - r) + f / self.rope_factor * r for f, r in zip(own, ramp))

    @property
    def softmax_scale(self) -> float:
        mscale = 1.0 if self.rope_factor <= 1 else (
            0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * mscale * mscale

    @classmethod
    def tiny(cls, **kw):
        """Small config for tests: two leading dense layers, two expert layers,
        four streams, YaRN over 16 original positions."""
        return super().tiny(**{**dict(
            n_routed_experts=16, rope_factor=4.0, rope_beta_fast=2.0, rope_beta_slow=0.5,
            rope_original_positions=16), **kw})


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def layer_shapes(cfg: HyperLatentMoEConfig, experts: bool) -> dict:
    """``latent_moe.layer_shapes`` less the sandwich norms, which this model has not."""
    return {name: shape for name, shape in latent_moe.layer_shapes(cfg, experts).items()
            if not name.startswith("post_")}


def hc_shapes(cfg: HyperLatentMoEConfig) -> dict:
    """name -> shape of ONE sublayer's maps' parameters (float32, whatever ``cfg.dtype``)."""
    n = cfg.hc_mult
    return {"phi": (n * cfg.hidden_size, 2 * n + n * n), "alpha": (3,), "b_pre": (n,),
            "b_post": (n,), "b_res": (n, n)}


def init_hc(key: jax.Array, cfg: HyperLatentMoEConfig, layers: int) -> Params:
    """One sublayer's maps' parameters for ``layers`` layers, stacked: ``phi``
    normal at 1/sqrt(n C), gains of one, biases normal, so that the maps move
    with the token and stand away from both the identity and the uniform matrix."""
    out = {}
    for j, (name, shape) in enumerate(hc_shapes(cfg).items()):
        k = jax.random.fold_in(key, j)
        if name == "alpha":
            out[name] = jnp.ones((layers,) + shape, jnp.float32)
        else:
            scale = shape[0] ** -0.5 if name == "phi" else 1.0
            out[name] = jax.random.normal(k, (layers,) + shape, jnp.float32) * scale
    return out


def plain_residual_hc(cfg: HyperLatentMoEConfig, layers: int) -> Params:
    """Parameters whose maps are the residual path every other model has:
    ``Hpre = Hpost = 1`` to the bit in float32 (``sigmoid(30)`` rounds to 1, ``2
    sigmoid(0)`` is 1) and ``Hres`` the identity (``exp(-60)`` of a row's
    largest entry is nothing beside it) less what ``hc_eps`` beside each sum
    takes off a 1: nothing where it is 0, a millionth at the published 1e-6."""
    n = cfg.hc_mult
    shapes = hc_shapes(cfg)
    full = lambda name, value: jnp.full((layers,) + shapes[name], value, jnp.float32)  # noqa: E731
    return {"phi": full("phi", 0.0), "alpha": full("alpha", 1.0), "b_pre": full("b_pre", 30.0),
            "b_post": full("b_post", 0.0),
            "b_res": jnp.broadcast_to(60.0 * jnp.eye(n, dtype=jnp.float32) - 30.0, (layers, n, n))}


def init_params(key: jax.Array, cfg: HyperLatentMoEConfig) -> Params:
    """Seeded float32 parameters: ``latent_moe.init_params``' draw without the
    sandwich norms, an ``expert_bias`` (normal, 0.01) in every expert layer and
    the maps' parameters of both sublayers in every layer."""
    params = latent_moe.init_params(key, cfg)
    k_bias, k_lead, k_layers = jax.random.split(jax.random.fold_in(key, 7), 3)
    for tree, experts, k in ((params["lead"], False, k_lead), (params["layers"], True, k_layers)):
        layers = tree["attn_norm"].shape[0]
        for name in [name for name in tree if name.startswith("post_")]:
            del tree[name]
        assert set(tree) == set(layer_shapes(cfg, experts))
        tree["hc_attn"] = init_hc(jax.random.fold_in(k, 0), cfg, layers)
        tree["hc_mlp"] = init_hc(jax.random.fold_in(k, 1), cfg, layers)
    n_expert_layers = cfg.num_hidden_layers - cfg.first_k_dense_replace
    params["layers"]["expert_bias"] = 0.01 * jax.random.normal(
        k_bias, (n_expert_layers, cfg.n_routed_experts), jnp.float32)
    return params


# ---------------------------------------------------------------------------
# The streams in and out, and the paged programs' layer bodies
# ---------------------------------------------------------------------------


def embed(params: Params, tokens, cfg: HyperLatentMoEConfig):
    """tokens [b, s] → the streams [b, s, n, D]: ``n`` copies of the embedding."""
    x = params["embed"].astype(cfg.dtype)[tokens]
    return jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (cfg.hc_mult, x.shape[-1]))


def unembed(params: Params, X, cfg: HyperLatentMoEConfig):
    """The streams [.., n, D] → float32 logits [.., V]: their sum, the final
    norm, the head."""
    h = jnp.sum(X.astype(jnp.float32), axis=-2).astype(X.dtype)
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return (h @ params["lm_head"].astype(h.dtype)).astype(jnp.float32)


def sublayer(X, hp: Params, cfg: HyperLatentMoEConfig, scale, f):
    """One sublayer around its mixes. X: [b, s, n, D]; ``f``: the normed input
    [b, s, D] → (the output [b, s, D], whatever else it gives). → (X', that)."""
    pre, post, res = hc.maps(X, hp, cfg)
    y, extra = f(rms_norm(hc.mix_in(pre, X), scale, cfg.rms_norm_eps))
    return hc.mix_out(res, post, X, y), extra


def _layer(cfg: HyperLatentMoEConfig, X, pool, lp: Params, attention, params, index, real):
    """Both sublayers of layer ``index``. ``attention``: (normed input, pool) →
    (output, pool); ``real``: the call's real tokens. → (X, (pool,), counts)."""
    X, pool = sublayer(X, lp["hc_attn"], cfg, lp["attn_norm"], lambda h: attention(h, pool))
    X, moe = sublayer(X, lp["hc_mlp"], cfg, lp["mlp_norm"],
                      lambda y: latent_moe.feed_forward(y, lp, cfg, params, index))
    places = X.shape[0] * X.shape[1]
    counts = jnp.concatenate([
        jnp.zeros((5,), jnp.int32) if moe is None else moe, jnp.zeros((3,), jnp.int32),
        jnp.stack([jnp.int32(SUBLAYERS * places), SUBLAYERS * real.astype(jnp.int32)])])
    return X, (pool,), counts


def _decode_layer(cfg: HyperLatentMoEConfig, X, pools, lp: Params, tables, lens, params, index, bases):
    """One layer, one token a slot. X: [b, 1, n, D]; pools: (rows [P, bs, R],);
    tables: [b, W] block ids into it, from ``bases[0]``; lens: [b] write
    positions, 0 a slot that holds no sequence."""
    def attention(h, pool):
        return latent_moe.decode_attention(h, pool, lp, cfg, tables + bases[0], lens)

    return _layer(cfg, X, pools[0], lp, attention, params, index, jnp.sum(lens > 0))


def _chunk_layer(cfg: HyperLatentMoEConfig, X, pools, lp: Params, table_rows, rows_at, offs, qpos,
                 live, params, index, bases, _slot_of):
    """One layer over a chunk call's token axis. X: [1, T, n, D]; the rest as
    ``latent_moe.chunk_attention`` has it, the block ids from ``bases[0]``."""
    def attention(h, pool):
        return latent_moe.chunk_attention(h, pool, lp, cfg, table_rows + bases[0],
                                          rows_at + bases[0], offs, qpos, live)

    return _layer(cfg, X, pools[0], lp, attention, params, index, jnp.sum(live))


@paged.paged_model.register
def _(cfg: HyperLatentMoEConfig) -> paged.PagedModel:
    return paged.PagedModel(
        pools={"rows": paged.Pool(row=(cfg.row_width,), layers=cfg.num_hidden_layers)},
        decode_layer=functools.partial(_decode_layer, cfg),
        chunk_layer=functools.partial(_chunk_layer, cfg),
        embed=embed, unembed=unembed,
    )
