"""Seeded weights of the multi-stream latent-attention expert decoder (``xing4_0``),
made by the benchmark and handed to the program.

The tree is the one ``ray_tpu.models.hyper_latent_moe`` takes:
``weights_latent_moe``'s (``embed``, ``final_norm``, ``lm_head``, ``lead``, ``layers``,
``experts``: the same draws from the same keys) WITHOUT the sandwich norms, which this
model has not, with an ``expert_bias`` in every expert layer (normal at 0.01, drawn and
NOT fitted, as ``weights_sparse_latent_moe`` draws it) and, in every layer, the maps'
parameters of its two sublayers (``hc_attn``, ``hc_mlp``), FLOAT32 whatever the
configuration's ``dtype``:

- ``phi`` ``[n hidden, 2n + n^2]`` normal at ``1 / sqrt(n hidden)``: the normed streams
  have mean square 1, so ``[p | q | r] = x^ phi`` is unit normal a token;
- ``alpha`` = (a_pre, a_post, a_res) = (1, 1, 1): a token moves each map's logit by a
  unit normal;
- ``b_pre``, ``b_post`` normal at ``HC_BIAS`` (1), ``b_res`` normal at ``HC_RES_BIAS`` (1.5):
  a layer's own offset, so that over a batch ``Hpre`` and ``Hpost`` stand far from
  constant and ``Hres`` far from the identity AND from the uniform matrix (the spreads
  are in the configuration's ``assumed``; ``chipbench/tests/test_hyper_latent_moe.py``
  holds them). A draw at the published initialisation (gains of 0.01, ``b_res`` the
  identity's logit) would leave every map where a plain residual has it, and a
  ``plain-residual`` fault would read inside any limit.

Every layer has a key of its own (``weights_latent_moe._layer_key``) and the maps of a
sublayer a key under it, so ``reference_hyper_latent_moe.py`` makes one sublayer's again
from the seed alone. The key is a traced argument: a new seed compiles nothing.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from chipbench import weights_latent_moe as L
from chipbench.weights import _dense, seed_key  # noqa: F401 - seed_key is this module's too
from chipbench.weights_latent_moe import (_layer_key, attn_params, dense_params,  # noqa: F401
                                          expert_params, held_params, top_params)

HC_BIAS = 1.0  # spread of a layer's b_pre and b_post
HC_RES_BIAS = 1.5  # spread of a layer's b_res
SUBLAYERS = ("hc_attn", "hc_mlp")
POST_NORMS = ("post_attn_norm", "post_mlp_norm")  # ``weights_latent_moe``'s that this model lacks


@dataclasses.dataclass(frozen=True)
class Dims(L.Dims):
    """``weights_latent_moe.Dims`` and what the streams and YaRN add."""

    streams: int = 4  # hc_mult
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    clamp_min: float = -30.0
    clamp_max: float = 30.0
    rope_factor: float = 1.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    rope_original: int = 4096
    mscale: float = 1.0
    mscale_all_dim: float = 1.0

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        scaling = cfg["rope_scaling"]
        if scaling["type"] != "yarn":
            raise ValueError(f"rope_scaling of type {scaling['type']!r}: only yarn is written here")
        return cls(
            **dataclasses.asdict(L.Dims.from_config(cfg)),
            streams=int(cfg["hc_mult"]), sinkhorn_iters=int(cfg["hc_sinkhorn_iters"]),
            hc_eps=float(cfg["hc_eps"]), clamp_min=float(cfg["mhc_h_res_clamp_min"]),
            clamp_max=float(cfg["mhc_h_res_clamp_max"]), rope_factor=float(scaling["factor"]),
            beta_fast=float(scaling["beta_fast"]), beta_slow=float(scaling["beta_slow"]),
            rope_original=int(scaling["original_max_position_embeddings"]),
            mscale=float(scaling["mscale"]), mscale_all_dim=float(scaling["mscale_all_dim"]))

    def norm_shapes(self) -> dict:
        """The four norms a layer of this model has (``attn_params`` makes these)."""
        return {name: shape for name, shape in super().norm_shapes().items()
                if name not in POST_NORMS}

    def hc_shapes(self) -> dict:
        n = self.streams
        return {"phi": (n * self.hidden, 2 * n + n * n), "b_pre": (n,), "b_post": (n,),
                "b_res": (n, n)}


def program_config(dims: Dims, dtype):
    """The program's configuration object for these sizes."""
    from ray_tpu.models.hyper_latent_moe import HyperLatentMoEConfig

    return HyperLatentMoEConfig(
        vocab_size=dims.vocab, hidden_size=dims.hidden, num_hidden_layers=dims.layers,
        first_k_dense_replace=dims.lead, num_attention_heads=dims.heads, q_lora_rank=dims.q_rank,
        kv_lora_rank=dims.kv_rank, qk_nope_head_dim=dims.nope, qk_rope_head_dim=dims.rope,
        v_head_dim=dims.v_dim, intermediate_size=dims.ffn, moe_intermediate_size=dims.expert_ffn,
        n_routed_experts=dims.experts, num_experts_per_tok=dims.per_token,
        n_shared_experts=dims.shared, routed_scaling_factor=dims.scale, rope_theta=dims.rope_theta,
        rms_norm_eps=dims.rms_eps, held_first=dims.held_first, held_count=dims.held, dtype=dtype,
        hc_mult=dims.streams, hc_sinkhorn_iters=dims.sinkhorn_iters, hc_eps=dims.hc_eps,
        mhc_h_res_clamp_min=dims.clamp_min, mhc_h_res_clamp_max=dims.clamp_max,
        rope_factor=dims.rope_factor, rope_beta_fast=dims.beta_fast, rope_beta_slow=dims.beta_slow,
        rope_original_positions=dims.rope_original, rope_mscale=dims.mscale,
        rope_mscale_all_dim=dims.mscale_all_dim)


def moe_params(key: jax.Array, index, dims: Dims) -> dict:
    """An expert layer's router (over ALL routed experts), its selection bias and its
    shared expert: ``weights_latent_moe``'s draws, and the bias from the key after them."""
    out = L.moe_params(key, index, dims)
    mk = jax.random.fold_in(_layer_key(key, index), 2)
    out["expert_bias"] = 0.01 * jax.random.normal(
        jax.random.fold_in(mk, len(out)), (dims.experts,), jnp.float32)
    return out


def hc_params(key: jax.Array, index, sublayer: int, dims: Dims) -> dict:
    """The maps' parameters of sublayer ``sublayer`` (0: attention, 1: feed-forward) of
    layer ``index``, float32."""
    hk = jax.random.fold_in(_layer_key(key, index), 4 + sublayer)
    spread = {"phi": (dims.streams * dims.hidden) ** -0.5, "b_pre": HC_BIAS, "b_post": HC_BIAS,
              "b_res": HC_RES_BIAS}
    out = {name: spread[name] * jax.random.normal(jax.random.fold_in(hk, j), shape, jnp.float32)
           for j, (name, shape) in enumerate(dims.hc_shapes().items())}
    return {**out, "alpha": jnp.ones((3,), jnp.float32)}


def layer_params(key: jax.Array, index, dims: Dims, experts: bool) -> dict:
    """Layer ``index``, float32: a leading dense layer, or an expert layer less its
    routed experts (``held_params``). ``index`` may be traced (vmap)."""
    return {**attn_params(key, index, dims),
            **(moe_params if experts else dense_params)(key, index, dims),
            **{name: hc_params(key, index, s, dims) for s, name in enumerate(SUBLAYERS)}}


def make_params(key: jax.Array, dims: Dims, dtype) -> dict:
    """The whole tree in ``dtype``, the maps' parameters in float32. Call under
    ``jax.jit`` with the layouts the program wants as ``out_shardings``."""
    lead = jax.vmap(lambda i: layer_params(key, i, dims, False))(jnp.arange(dims.lead))
    expert_layers = jnp.arange(dims.lead, dims.layers)
    layers = jax.vmap(lambda i: layer_params(key, i, dims, True))(expert_layers)
    experts = jax.vmap(lambda i: held_params(key, i, dims))(expert_layers)
    tree = {**top_params(key, dims), "lead": lead, "layers": layers, "experts": experts}
    cast = jax.tree.map(lambda x: x.astype(dtype), tree)
    for stack in ("lead", "layers"):
        for name in SUBLAYERS:
            cast[stack][name] = tree[stack][name]
    return cast
