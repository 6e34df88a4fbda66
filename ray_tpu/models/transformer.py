"""Flagship model: llama-style decoder-only transformer, TPU-first.

Design choices (vs. the reference, which delegates models to torch):
- Pure functional pytree params (nested dicts of jnp arrays) — shardings
  attach cleanly with jax.sharding, and optimizer state mirrors the tree.
- Layer parameters are STACKED along a leading [num_layers] axis and the
  decoder runs as one ``lax.scan`` — O(1) compile time in depth, and the
  leading axis doubles as the pipeline-stage axis when pp>1
  (ray_tpu/parallel/pipeline.py reshapes [L,...] → [S, L/S, ...]).
- bf16 compute / fp32 params + optimizer, fp32 logits for the loss.
- GQA attention through ray_tpu.ops.flash_attention (Pallas on TPU);
  when a sequence-parallel mesh axis is active the caller routes attention
  through ring attention instead (ray_tpu/parallel/ring.py).
- ``jax.checkpoint`` per layer to trade FLOPs for HBM (remat): a layer's
  XLA operations are recomputed on backward, the flash forward kernel's
  output and logsumexp are kept (``checkpoint_layer``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import FLASH_RESIDUAL_NAMES, flash_attention

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 11008
    rope_theta: float = 10000.0
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16  # compute dtype
    remat: bool = True
    # MoE (expert parallelism): 0 = dense MLP.
    num_experts: int = 0
    experts_per_token: int = 2
    # Blockwise cross-entropy chunk (tokens); 0 = materialize full logits.
    logits_chunk: int = 0
    # What a rematerialised layer keeps (``checkpoint_layer``). "full":
    # every XLA operation of the layer is recomputed on backward, and the
    # flash forward kernel's two results are kept so the kernel runs once
    # (one [b, s, heads x head_dim] in ``dtype`` and one [b, heads, s]
    # float32 a layer, beside the layer input the scan already keeps).
    # "dots": matmul outputs are kept as well
    # (jax.checkpoint_policies.dots_with_no_batch_dims_saveable) — far
    # less recompute FLOPs for more HBM.
    remat_policy: str = "full"
    # lax.scan unroll over the layer stack: >1 inlines several layer
    # bodies per scan step, widening XLA's fusion/scheduling scope
    # (each layer stays its own remat block; measured neutral-to-slower
    # on the flagship bench — kept as a tuning knob).
    scan_unroll: int = 1

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def llama7b(cls, **kw):
        return cls(**{**dict(vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
                             n_kv_heads=32, d_ff=11008), **kw})

    @classmethod
    def tiny(cls, **kw):
        """Small config for tests/dryrun."""
        return cls(**{**dict(vocab_size=256, d_model=64, n_layers=4, n_heads=4,
                             n_kv_heads=2, d_ff=128, max_seq_len=128), **kw})


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: TransformerConfig) -> Params:
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    L, D, F = cfg.n_layers, cfg.d_model, cfg.d_ff
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def norm_init(*shape):
        return jnp.ones(shape, jnp.float32)

    def dense_init(key, *shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * (fan_in**-0.5)).astype(jnp.float32)

    ks = jax.random.split(k_layers, 8)
    layers = {
        "attn_norm": norm_init(L, D),
        "wq": dense_init(ks[0], L, D, H * HD, fan_in=D),
        "wk": dense_init(ks[1], L, D, KV * HD, fan_in=D),
        "wv": dense_init(ks[2], L, D, KV * HD, fan_in=D),
        "wo": dense_init(ks[3], L, H * HD, D, fan_in=H * HD),
        "mlp_norm": norm_init(L, D),
    }
    if cfg.num_experts:
        E = cfg.num_experts
        layers.update(
            router=dense_init(ks[7], L, D, E, fan_in=D),
            w_gate=dense_init(ks[4], L, E, D, F, fan_in=D),
            w_up=dense_init(ks[5], L, E, D, F, fan_in=D),
            w_down=dense_init(ks[6], L, E, F, D, fan_in=F),
        )
    else:
        layers.update(
            w_gate=dense_init(ks[4], L, D, F, fan_in=D),
            w_up=dense_init(ks[5], L, D, F, fan_in=D),
            w_down=dense_init(ks[6], L, F, D, fan_in=F),
        )
    return {
        "embed": dense_init(k_emb, cfg.vocab_size, D, fan_in=1),
        "layers": layers,
        "final_norm": norm_init(D),
        "lm_head": dense_init(k_out, D, cfg.vocab_size, fan_in=D),
    }


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def _rope(x, positions, theta):
    """x: [b, s, h, hd]; rotate pairs (llama convention: split halves).
    ``theta``: the base of the ``hd / 2`` frequencies ``theta ** (-i / half)``,
    or the frequencies themselves, one a pair (a configuration that blends
    them, as YaRN does: ``models/hyper_latent_moe.py``)."""
    hd = x.shape[-1]
    half = hd // 2
    if isinstance(theta, (int, float)):
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(theta, jnp.float32)
        assert freqs.shape == (half,), (freqs.shape, half)
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]  # [b,s,half]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def project_qkv(h, lp: Params, cfg: TransformerConfig, positions):
    """Normed hidden → (roped q [b,s,H,hd], roped k [b,s,KV,hd], v) — the
    single source of the projection/rope math for training AND the
    KV-cache decode path (models/generate.py)."""
    b, s, _ = h.shape
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ lp["wq"].astype(h.dtype)).reshape(b, s, H, HD)
    k = (h @ lp["wk"].astype(h.dtype)).reshape(b, s, KV, HD)
    v = (h @ lp["wv"].astype(h.dtype)).reshape(b, s, KV, HD)
    return _rope(q, positions, cfg.rope_theta), _rope(k, positions, cfg.rope_theta), v


def attention_block(
    x,
    lp: Params,
    cfg: TransformerConfig,
    positions,
    attn_fn: Optional[Callable] = None,
    return_kv: bool = False,
):
    """x: [b, s, d]. attn_fn overrides the core attention (ring attention
    under sequence parallelism). With ``return_kv`` also returns the
    pre-repeat roped (k, v) for KV-cache prefill."""
    b, s, d = x.shape
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, lp["attn_norm"])
    q, k, v = project_qkv(h, lp, cfg, positions)
    if attn_fn is None or getattr(attn_fn, "supports_gqa", False):
        # flash_attention (and its shard_map wrapper) is GQA-NATIVE: the
        # kernel indexes the shared kv head per q-head group — no
        # repeated K/V in HBM (ops/attention.py)
        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        o = (
            flash_attention(qt, kt, vt, True, None)
            if attn_fn is None
            else attn_fn(qt, kt, vt)
        )
    else:
        # custom attention (ring/Ulysses SP) still takes equal head
        # counts — repeat kv heads for those paths
        kr, vr = k, v
        if KV != H:
            rep = H // KV
            kr = jnp.repeat(k, rep, axis=2)
            vr = jnp.repeat(v, rep, axis=2)
        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, kr, vr))
        o = attn_fn(qt, kt, vt)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, H * HD)
    out = x + o @ lp["wo"].astype(o.dtype)
    if return_kv:
        return out, k, v
    return out


def mlp_block(x, lp: Params, cfg: TransformerConfig, eps: float = 1e-5):
    h = rms_norm(x, lp["mlp_norm"], eps)
    if cfg.num_experts:
        return x + _moe_mlp(h, lp, cfg)
    gate = jax.nn.silu(h @ lp["w_gate"].astype(h.dtype))
    up = h @ lp["w_up"].astype(h.dtype)
    return x + (gate * up) @ lp["w_down"].astype(h.dtype)


def _moe_mlp(h, lp: Params, cfg: TransformerConfig):
    """Mixtral-style top-k MoE with dense dispatch.

    Dense dispatch (einsum over the expert axis) keeps shapes static so XLA
    shards experts over the ``ep`` mesh axis and inserts the all-to-alls;
    a capacity-based sparse dispatch kernel is a later optimization.
    """
    b, s, d = h.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    logits = (h @ lp["router"].astype(h.dtype)).astype(jnp.float32)  # [b,s,E]
    weights, idx = jax.lax.top_k(logits, K)
    weights = jax.nn.softmax(weights, axis=-1)
    # combine[b,s,E]: weight of each expert for each token (0 if unused)
    combine = jnp.zeros((b, s, E), jnp.float32).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None], idx
    ].set(weights)
    combine = combine.astype(h.dtype)
    gate = jax.nn.silu(jnp.einsum("bsd,edf->bsef", h, lp["w_gate"].astype(h.dtype)))
    up = jnp.einsum("bsd,edf->bsef", h, lp["w_up"].astype(h.dtype))
    expert_out = jnp.einsum("bsef,efd->bsed", gate * up, lp["w_down"].astype(h.dtype))
    return jnp.einsum("bsed,bse->bsd", expert_out, combine)


def decoder_layer(x, lp: Params, cfg: TransformerConfig, positions, attn_fn=None):
    x = attention_block(x, lp, cfg, positions, attn_fn)
    x = mlp_block(x, lp, cfg)
    return x


# ---------------------------------------------------------------------------
# Full forward
# ---------------------------------------------------------------------------


def embed(params: Params, tokens, cfg: TransformerConfig):
    return params["embed"].astype(cfg.dtype)[tokens]


def checkpoint_layer(layer_fn: Callable, cfg: TransformerConfig) -> Callable:
    """``layer_fn`` as the body of a layer scan: rematerialised if
    ``cfg.remat``. The ONE place that decides what such a layer keeps
    (``decoder_stack``, the pipeline stage of parallel/train_step.py and
    parallel/mpmd.py::make_stage_fn all come here). Under every policy the
    flash forward kernel's output and logsumexp are kept by name
    (ops/attention.py names them where the kernel returns): the backward
    kernels read them, so the recomputation is XLA operations only and the
    kernel runs once a layer a step. On a backend without the kernels
    nothing carries the names and nothing extra is kept."""
    if cfg.remat_policy not in ("full", "dots"):
        raise ValueError(
            f"remat_policy must be 'full' or 'dots', got {cfg.remat_policy!r}"
        )
    if not cfg.remat:
        return layer_fn
    policy = jax.checkpoint_policies.save_only_these_names(*FLASH_RESIDUAL_NAMES)
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable, policy
        )
    return jax.checkpoint(layer_fn, prevent_cse=False, policy=policy)


def decoder_stack(params: Params, h, cfg: TransformerConfig, positions, attn_fn=None):
    """Scan over stacked layers; optionally rematerialized."""

    def layer_fn(carry, lp):
        out = decoder_layer(carry, lp, cfg, positions, attn_fn)
        return out, None

    h, _ = jax.lax.scan(
        checkpoint_layer(layer_fn, cfg), h, params["layers"], unroll=cfg.scan_unroll
    )
    return h


def unembed(params: Params, h, cfg: TransformerConfig):
    h = rms_norm(h, params["final_norm"])
    return (h @ params["lm_head"].astype(h.dtype)).astype(jnp.float32)


def hidden_states(params: Params, tokens, cfg: TransformerConfig, attn_fn=None, positions=None):
    """tokens: [b, s] int32 → final hidden states [b, s, d] (pre-norm);
    the single embed+stack pipeline shared by forward() and the chunked
    loss path."""
    if positions is None:
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    h = embed(params, tokens, cfg)
    return decoder_stack(params, h, cfg, positions, attn_fn)


def forward(params: Params, tokens, cfg: TransformerConfig, attn_fn=None, positions=None):
    """tokens: [b, s] int32 → logits [b, s, vocab] fp32."""
    return unembed(params, hidden_states(params, tokens, cfg, attn_fn, positions), cfg)


def token_nll(logits: jax.Array, targets: jax.Array, mask=None):
    """Mean next-token negative log-likelihood, optionally mask-weighted."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return -ll.mean()


def chunked_token_nll(
    params: Params, h: jax.Array, targets: jax.Array, cfg: TransformerConfig, mask=None, chunk: int = 256
):
    """Blockwise next-token NLL: the [b, s, vocab] logits tensor is never
    materialized — sequence chunks are unembedded, reduced to per-token
    NLL, and discarded inside a scan. At b=8, s=2048, v=32k the full fp32
    logits are ~2.1 GB of HBM; chunking caps that at chunk/s of it, which
    is what lets the flagship step run bigger batches (higher MXU
    occupancy) on one chip."""
    b, s, d = h.shape
    pad = (-s) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
    n_chunks = h.shape[1] // chunk
    h_c = h.reshape(b, n_chunks, chunk, d).transpose(1, 0, 2, 3)
    t_c = targets.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        hc, tc = xs
        logp = jax.nn.log_softmax(unembed(params, hc, cfg), axis=-1)
        ll = jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0]
        return carry, ll

    # Remat the chunk: without it, scan's AD stacks each chunk's softmax
    # residuals — a [b, s, vocab] buffer, exactly what this path promises
    # never to materialize. Recomputed per chunk on backward instead.
    body = jax.checkpoint(body)
    _, ll = jax.lax.scan(body, 0.0, (h_c, t_c))
    ll = ll.transpose(1, 0, 2).reshape(b, s + pad)[:, :s]
    if mask is not None:
        return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return -ll.mean()


def loss_fn(
    params: Params, batch: Dict[str, jax.Array], cfg: TransformerConfig, attn_fn=None,
    logits_chunk: Optional[int] = None,
):
    """batch: {"tokens": [b, s+1]} — next-token cross-entropy.
    ``logits_chunk`` > 0 switches to the blockwise NLL (no full logits);
    defaults to ``cfg.logits_chunk``."""
    if logits_chunk is None:
        logits_chunk = cfg.logits_chunk
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    mask = batch.get("mask")
    mask = mask[:, 1:] if mask is not None else None
    if logits_chunk:
        h = hidden_states(params, inputs, cfg, attn_fn)
        return chunked_token_nll(params, h, targets, cfg, mask, chunk=logits_chunk)
    logits = forward(params, inputs, cfg, attn_fn)
    return token_nll(logits, targets, mask)
