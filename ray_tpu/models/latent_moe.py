"""Decoder with latent attention and sigmoid-routed experts, for the paged
serving path (``models/paged.py`` reaches it through ``paged_model``).

One layer, for a token's hidden state ``h`` at position ``t`` (RMSNorm
throughout; names are the published configuration's):

1. ``x = attn_norm(h)``; ``c_q = q_norm(x W_dq)``; ``q = c_q W_uq``: per head
   ``[q_nope | q_rope]``, ``q_rope`` rotated to ``t``.
2. ``[c_kv | k_r] = x W_dkv``; ``c = kv_norm(c_kv)``; ``k_rope = RoPE(k_r, t)``,
   ONE for all heads. The cache row of the token is ``[c | k_rope]``
   (``row_width``: padded with zeros to whole lane tiles of 128).
3. Attention. Expanded: per head ``[k_nope | v] = c W_ukv``, scores ``(q_nope .
   k_nope + q_rope . k_rope) / sqrt(nope + rope)``. Absorbed, the same numbers:
   ``qa = q_nope W_uk^T`` scores against the row itself, the weighted sum of
   ``c`` goes through ``W_uv`` after. The served path is absorbed in decode
   and prefill alike (``ops/latent_attention.py``); ``expanded_attention`` is
   the other form, for the tests.
4. Sandwich norms: ``h += post_attn_norm(a)``; ``y = mlp_norm(h)``; ``h +=
   post_mlp_norm(m)``.
5. ``m``: a SwiGLU of ``intermediate_size`` in the ``first_k_dense_replace``
   leading layers (``params["lead"]``, run before the scan), else the expert
   layer: ``s = sigmoid(float32(y) W_r)`` over ALL ``n_routed_experts``, the
   ``num_experts_per_tok`` largest, ``g = routed_scaling_factor * s / sum of
   the chosen``, ``m = shared(y) + sum g_e expert_e(y)``.

**The expert layer is told which experts it holds** (``held_first``,
``held_count``: this chip's share of a layer that several chips divide). It
routes over all of them and multiplies the token-expert pairs that land here,
every one (no capacity, no dropped token), in one of two loop nests that the
call's STATIC token count chooses (``routed_experts``, ``ops/moe.py``): a call
under the chip's ridge (a decode step's few tokens) sends all its tokens
through each TOUCHED expert in one Pallas kernel, ``moe_decode_experts``,
weighted by their gates (0 for a token that did not choose the expert),
reading the touched experts' weights once, where they lie; a call over it (a
chunk call's 1,024) sorts the pairs by expert and multiplies them as groups,
in one Pallas kernel too, ``moe_grouped_experts``, which reads each touched
expert's weights once for the tile or two of sorted rows its group lies in
(on a CPU, and at widths that are not lane tiles, three
``jax.lax.ragged_dot``). Then the shared expert for every token. What the
absent experts would have added is left out; a token none of whose experts is
here gets the shared expert only. It also counts: pairs computed here, held
experts with at least one pair, and which form ran.

Parameters: ``embed``, ``final_norm``, ``lm_head``; ``lead`` and ``layers``
(stacked by layer: attention, norms, and in ``layers`` the router and the
shared expert); and ``experts``, the held routed experts of ALL expert layers,
``[expert layers, held, ...]``, which no scan slices: both forms are kernels, a
kernel's operand has to exist in memory, and a layer's slice of the stack
would be copied there at every step (three copies of 0.5 GB a layer at the
published widths). ``ragged_dot`` is handed the whole stack as ``layers x
held`` groups of which only the layer's own hold rows; the two kernels take
the layer's number as a prefetched scalar of their blocks' index.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import paged
from ray_tpu.models.transformer import Params, _rope, rms_norm
from ray_tpu.ops import moe
from ray_tpu.ops.latent_attention import latent_attention, latent_chunk_attention


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    # Selection by groups (``route``): 1 and 1 is none, the largest of all.
    n_group: int = 1
    topk_group: int = 1
    rope_theta: float = 25.6e6
    rms_norm_eps: float = 1e-5
    # This chip's share of every expert layer: experts held_first ..
    # held_first + held_count - 1 (None: all of them).
    held_first: int = 0
    held_count: Optional[int] = None
    dtype: Any = jnp.bfloat16  # compute dtype

    @property
    def held(self) -> int:
        return self.n_routed_experts if self.held_count is None else self.held_count

    @property
    def row_width(self) -> int:
        """Numbers in one cache row: the latent and the rotary key, padded
        with zeros to whole lane tiles (a TPU pads the pool's rows so in
        memory whatever their logical width)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @classmethod
    def tiny(cls, **kw):
        """Small config for tests: two leading dense layers, two expert layers."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=4, first_k_dense_replace=2,
            num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=8, num_experts_per_tok=2, dtype=jnp.float32), **kw})


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def layer_shapes(cfg: LatentMoEConfig, experts: bool) -> dict:
    """name -> shape of one layer's parameters (a leading dense layer, or an
    expert layer less its routed experts: ``expert_shapes``)."""
    D, H = cfg.hidden_size, cfg.num_attention_heads
    out = {
        "attn_norm": (D,), "q_norm": (cfg.q_lora_rank,), "kv_norm": (cfg.kv_lora_rank,),
        "post_attn_norm": (D,), "mlp_norm": (D,), "post_mlp_norm": (D,),
        "w_dq": (D, cfg.q_lora_rank),
        "w_uq": (cfg.q_lora_rank, H * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)),
        "w_dkv": (D, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "w_ukv": (cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": (H * cfg.v_head_dim, D),
    }
    if not experts:
        F = cfg.intermediate_size
        return {**out, "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}
    S = cfg.moe_intermediate_size * cfg.n_shared_experts
    return {**out, "router": (D, cfg.n_routed_experts),
            "shared_gate": (D, S), "shared_up": (D, S), "shared_down": (S, D)}


def expert_shapes(cfg: LatentMoEConfig) -> dict:
    """name -> shape of ``params["experts"]``: every expert layer's held experts."""
    L, E = cfg.num_hidden_layers - cfg.first_k_dense_replace, cfg.held
    D, F = cfg.hidden_size, cfg.moe_intermediate_size
    return {"e_gate": (L, E, D, F), "e_up": (L, E, D, F), "e_down": (L, E, F, D)}


def init_params(key: jax.Array, cfg: LatentMoEConfig) -> Params:
    """Seeded float32 parameters: norms one, matrices normal at 1/sqrt(fan_in),
    the embedding unit variance; ``lead`` and ``layers`` stacked by layer."""
    def stack(key, n, experts):
        tree = {}
        for j, (name, shape) in enumerate(layer_shapes(cfg, experts).items()):
            if name.endswith("norm"):
                tree[name] = jnp.ones((n,) + shape, jnp.float32)
            else:
                tree[name] = jax.random.normal(
                    jax.random.fold_in(key, j), (n,) + shape, jnp.float32) * shape[-2] ** -0.5
        return tree

    k_emb, k_lead, k_layers, k_experts, k_out = jax.random.split(key, 5)
    lead = cfg.first_k_dense_replace
    D = cfg.hidden_size
    return {
        "embed": jax.random.normal(k_emb, (cfg.vocab_size, D), jnp.float32),
        "lead": stack(k_lead, lead, False),
        "layers": stack(k_layers, cfg.num_hidden_layers - lead, True),
        "experts": {name: jax.random.normal(jax.random.fold_in(k_experts, j), shape, jnp.float32)
                    * shape[-2] ** -0.5 for j, (name, shape) in enumerate(expert_shapes(cfg).items())},
        "final_norm": jnp.ones((D,), jnp.float32),
        "lm_head": jax.random.normal(k_out, (D, cfg.vocab_size), jnp.float32) * D ** -0.5,
    }


# ---------------------------------------------------------------------------
# Attention: projections and both forms
# ---------------------------------------------------------------------------


def _norm(x, scale, cfg):
    return rms_norm(x, scale, cfg.rms_norm_eps)


def _up_kv(lp: Params, cfg: LatentMoEConfig, dtype):
    """``W_ukv`` as (W_uk [rank, H, nope], W_uv [rank, H, v])."""
    w = lp["w_ukv"].astype(dtype).reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


@jax.named_scope("latent.project")
def project(h, lp: Params, cfg: LatentMoEConfig, positions):
    """Normed hidden [b, s, D] → (q_nope [b, s, H, nope], roped q_rope [b, s,
    H, rope], cache rows [b, s, row_width] = ``[c | roped k_rope | 0]``)."""
    b, s, _ = h.shape
    H, nope, rope = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if "w_dq" in lp:
        c_q = _norm(h @ lp["w_dq"].astype(h.dtype), lp["q_norm"], cfg)
        q = (c_q @ lp["w_uq"].astype(h.dtype)).reshape(b, s, H, nope + rope)
    else:  # no query latent (``models/kda_moe.py``): ONE matrix, the norm a head's
        q = _norm((h @ lp["w_q"].astype(h.dtype)).reshape(b, s, H, nope + rope), lp["q_norm"], cfg)
    # The frequencies themselves where the configuration blends them (YaRN), else their base.
    theta = getattr(cfg, "rope_frequencies", cfg.rope_theta)
    q_rope = _rope(q[..., nope:], positions, theta)
    kv = h @ lp["w_dkv"].astype(h.dtype)
    c = _norm(kv[..., :cfg.kv_lora_rank], lp["kv_norm"], cfg)
    k_rope = _rope(kv[..., None, cfg.kv_lora_rank:], positions, theta)[:, :, 0]
    pad = jnp.zeros((b, s, cfg.row_width - cfg.kv_lora_rank - rope), h.dtype)
    return q[..., :nope], q_rope, jnp.concatenate([c, k_rope, pad], axis=-1)


@jax.named_scope("latent.project")
def absorb(q_nope, q_rope, lp: Params, cfg: LatentMoEConfig):
    """The query that scores against a cache row: ``[q_nope W_uk^T | q_rope |
    0]`` per head, [.., H, row_width]."""
    w_uk, _ = _up_kv(lp, cfg, q_nope.dtype)
    qa = jnp.einsum("...hn,chn->...hc", q_nope, w_uk)
    pad = jnp.zeros(qa.shape[:-1] + (cfg.row_width - cfg.kv_lora_rank - q_rope.shape[-1],), qa.dtype)
    return jnp.concatenate([qa, q_rope, pad], axis=-1)


def attention_out(u, lp: Params, cfg: LatentMoEConfig, gate=None):
    """u: [.., H, rank] per-head weighted sums of latents → the attention
    block's output [.., D]: through ``W_uv`` and ``W_o``, a head's output
    times ``gate`` [.., H] between them where there is one."""
    _, w_uv = _up_kv(lp, cfg, u.dtype)
    o = jnp.einsum("...hc,chv->...hv", u, w_uv)
    if gate is not None:
        o = o * gate[..., None].astype(o.dtype)
    return o.reshape(o.shape[:-2] + (-1,)) @ lp["wo"].astype(u.dtype)


def absorbed_attention(q_nope, q_rope, rows, lp: Params, cfg: LatentMoEConfig):
    """Causal attention of ONE sequence in the absorbed form, plain float32
    einsums: q_nope/q_rope [s, H, .], rows [s, row_width] → u [s, H, rank]."""
    q = absorb(q_nope, q_rope, lp, cfg).astype(jnp.float32)
    rows = rows.astype(jnp.float32)
    s = q.shape[0]
    scores = jnp.einsum("qhr,kr->hqk", q, rows) * cfg.softmax_scale
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores, -1e30)
    return jnp.einsum("hqk,kc->qhc", jax.nn.softmax(scores, axis=-1), rows[:, :cfg.kv_lora_rank])


def expanded_attention(q_nope, q_rope, rows, lp: Params, cfg: LatentMoEConfig):
    """The same in the expanded form: every row up-projected to per-head keys
    and values. → o [s, H, v] (``absorbed_attention``'s ``u`` through ``W_uv``)."""
    rows = rows.astype(jnp.float32)
    c, k_rope = rows[:, :cfg.kv_lora_rank], rows[:, cfg.kv_lora_rank:][:, :cfg.qk_rope_head_dim]
    w_uk, w_uv = _up_kv(lp, cfg, jnp.float32)
    k_nope = jnp.einsum("kc,chn->khn", c, w_uk)
    v = jnp.einsum("kc,chv->khv", c, w_uv)
    s = c.shape[0]
    scores = (jnp.einsum("qhn,khn->hqk", q_nope.astype(jnp.float32), k_nope)
              + jnp.einsum("qhr,kr->hqk", q_rope.astype(jnp.float32), k_rope)) * cfg.softmax_scale
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores, -1e30)
    return jnp.einsum("hqk,khv->qhv", jax.nn.softmax(scores, axis=-1), v)


# ---------------------------------------------------------------------------
# Feed-forward: dense, and this chip's share of an expert layer
# ---------------------------------------------------------------------------


def _swiglu(y, gate, up, down):
    return (jax.nn.silu(y @ gate.astype(y.dtype)) * (y @ up.astype(y.dtype))) @ down.astype(y.dtype)


def route(y, lp: Params, cfg: LatentMoEConfig):
    """y: [T, D] → (experts [T, k] int32 among ALL routed experts, gates [T, k]
    float32): sigmoid scores in float32 (a product of the activation and the
    router in their own precision, accumulated in float32, is the float32
    product of those numbers), the k largest, normalised over the chosen,
    times ``routed_scaling_factor``.

    Where the layer has an ``expert_bias`` or the configuration groups
    (``n_group`` above 1), the k are SELECTED on ``scores + expert_bias``, among
    the experts of the ``topk_group`` groups whose two largest such numbers sum
    highest; the gates are still the chosen experts' scores, without the bias.
    A model with neither computes what it always did."""
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(jnp.dot(
            y, lp["router"].astype(y.dtype), preferred_element_type=jnp.float32))
        if "expert_bias" in lp or cfg.n_group > 1:
            experts = select_experts(scores, lp.get("expert_bias"), cfg.n_group, cfg.topk_group,
                                     cfg.num_experts_per_tok)
            top = jnp.take_along_axis(scores, experts, axis=-1)
        else:
            top, experts = jax.lax.top_k(scores, cfg.num_experts_per_tok)
        gates = cfg.routed_scaling_factor * top / jnp.sum(top, axis=-1, keepdims=True)
        return experts.astype(jnp.int32), gates


def select_experts(scores, bias, n_group: int, topk_group: int, k: int):
    """scores: [T, E] float32 → the chosen experts [T, k]: the k largest of
    ``scores + bias`` inside the ``topk_group`` groups (of ``n_group``, experts
    side by side) whose two largest sum highest."""
    chosen = scores if bias is None else scores + bias.astype(jnp.float32)
    if n_group > 1:
        T, E = chosen.shape
        grouped = chosen.reshape(T, n_group, E // n_group)
        best = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # [T, n_group]
        _, groups = jax.lax.top_k(best, topk_group)
        kept = jnp.any(groups[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
        chosen = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(T, E)
    return jax.lax.top_k(chosen, k)[1]


def routed_experts(y, lp: Params, cfg: LatentMoEConfig, held: Params, layer):
    """The held experts' part of the layer's result for y [T, D], and the
    counts (pairs computed here, held experts touched, 1, 1 if the decode
    kernel multiplied them, 1 if the grouped kernel did) as int32 [5]. ``held``
    is a stack of layers' held experts, ``[layers, held, ...]``, and ``layer``
    this layer's number in it (it may be traced).

    Every token-expert pair gets a key: its expert's index among the held
    ones, or ``held`` if the expert lives elsewhere. Two loop nests, chosen by
    the call's static token count (``ops/moe.fused``: under the chip's ridge,
    on a TPU): a few tokens go through every touched expert whole, weighted by
    their gates (``moe_decode_experts``, one kernel); many are sorted by key,
    a stable sort that puts the pairs of this chip first, grouped by expert,
    and the groups are multiplied (``_sorted_experts``: one kernel,
    ``moe_grouped_experts``, where ``ops/moe.grouped`` says so, else
    ``ragged_dot``; rows past the last group are not computed; a group of no
    rows reads no weights: the other layers' experts)."""
    T, D = y.shape
    k, E = cfg.num_experts_per_tok, cfg.held
    experts, gates = route(y, lp, cfg)
    with jax.named_scope("moe.experts"):
        local = experts - cfg.held_first
        here = (local >= 0) & (local < E)
        key = jnp.where(here, local, E)  # [T, k]
        chose = key[..., None] == jnp.arange(E)  # [T, k, E]
        sizes = jnp.sum(chose, axis=(0, 1)).astype(jnp.int32)
        pairs = jnp.sum(sizes)
        fused = moe.fused(T, held)
        grouped = not fused and moe.grouped(T, held)
        if fused:
            w = jnp.sum(jnp.where(chose, gates[..., None], 0.0), axis=1)  # [T, E] float32
            m = moe.moe_decode_experts(y, w, sizes, held, layer)
        else:
            m = _sorted_experts(y, key, jnp.where(here, gates, 0.0), sizes, held, layer,
                                moe.moe_grouped_experts if grouped else _ragged_products)
    counts = jnp.stack([pairs, jnp.sum(sizes > 0).astype(jnp.int32), jnp.int32(1),
                        jnp.int32(fused), jnp.int32(grouped)])
    return m, counts


def _ragged_products(x, sizes, held: Params, layer):
    """``moe_grouped_experts``' numbers by three ``ragged_dot``: the form of a
    CPU and of widths that are not lane tiles."""
    n_layers, E = held["e_gate"].shape[:2]
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros((n_layers * E,), jnp.int32), sizes, (layer * E,))

    def dot(a, w):  # w: [layers, E, in, out], as layers x E groups (a bitcast)
        return jax.lax.ragged_dot(a, w.reshape((-1,) + w.shape[2:]).astype(a.dtype), groups)

    return dot(jax.nn.silu(dot(x, held["e_gate"])) * dot(x, held["e_up"]), held["e_down"])


def _sorted_experts(y, key, weight, sizes, held: Params, layer, products):
    """``routed_experts``' sorted form. key: [T, k] a pair's held expert (or
    ``held``: elsewhere); weight: [T, k] float32, 0 for a pair elsewhere;
    sizes: [held] pairs an expert; products: (the sorted pairs' tokens [T*k,
    D], sizes, held, layer) -> each row through its group's expert; rows past
    the last group are nobody's (not computed, maybe never written)."""
    (T, D), k = y.shape, key.shape[1]
    order = jnp.argsort(key.reshape(T * k), stable=True)
    out = products(y[order // k], sizes, held, layer)
    # Back to (token, choice) order; a pair computed elsewhere weighs 0 and
    # its row, one past the last group, is not read: no pass of its own zeroes them.
    out = out[jnp.argsort(order)].reshape(T, k, D)
    weight = weight[..., None]
    return jnp.sum(jnp.where(weight != 0, out.astype(jnp.float32) * weight, 0), axis=1).astype(y.dtype)


def expert_layer(y, lp: Params, cfg: LatentMoEConfig, held: Params, layer):
    """y: [T, D] → (shared expert + this chip's routed part, counts [4])."""
    m, counts = routed_experts(y, lp, cfg, held, layer)
    with jax.named_scope("moe.shared"):
        m = m + _swiglu(y, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    return m, counts


def feed_forward(y, lp: Params, cfg: LatentMoEConfig, params: Params, index):
    """Layer ``index``'s feed-forward of the normed ``y`` [b, s, D]: the expert
    layer where the layer's parameters hold a router, else the dense SwiGLU.
    → (m [b, s, D], counts or None)."""
    if "router" in lp:
        m, counts = expert_layer(y.reshape(-1, y.shape[-1]), lp, cfg, params["experts"],
                                 index - cfg.first_k_dense_replace)
        return m.reshape(y.shape), counts
    return _swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"]), None


def _post(x, lp: Params, name: str, cfg):
    """A sandwich norm on a sublayer's output, where the layer has one (a
    model without them, ``models/hyper_latent_moe.py``, adds the output as it is)."""
    return _norm(x, lp[name], cfg) if name in lp else x


def _finish(x, a, lp: Params, cfg: LatentMoEConfig, params: Params, index):
    """Layer ``index`` after its attention output ``a`` [b, s, D]: sandwich
    norms around the feed-forward. → (x, counts or None)."""
    x = x + _post(a, lp, "post_attn_norm", cfg)
    m, counts = feed_forward(_norm(x, lp["mlp_norm"], cfg), lp, cfg, params, index)
    return x + _post(m, lp, "post_mlp_norm", cfg), counts


# ---------------------------------------------------------------------------
# The paged programs' layer bodies
# ---------------------------------------------------------------------------


def decode_attention(h, pool, lp: Params, cfg: LatentMoEConfig, tables, lens):
    """The attention sublayer, one token a slot. h: the normed hidden [b, 1,
    D]; pool: rows [P, bs, R]; tables: [b, W] block ids into it; lens: [b]
    write positions. → (its output [b, 1, D], the pool with the tokens' rows)."""
    bs = pool.shape[1]
    q_nope, q_rope, rows = project(h, lp, cfg, lens[:, None])
    q = absorb(q_nope[:, 0], q_rope[:, 0], lp, cfg)
    with jax.named_scope("latent.scatter"):
        phys = jnp.take_along_axis(tables, (lens // bs)[:, None], axis=1)[:, 0]
        pool = pool.at[phys, lens % bs].set(rows[:, 0])
    # After the scatter, so the token just written attends to itself.
    u = latent_attention(q, pool, tables, lens, cfg.softmax_scale, cfg.kv_lora_rank)
    return attention_out(u, lp, cfg)[:, None, :], pool


def chunk_attention(h, pool, lp: Params, cfg: LatentMoEConfig, table_rows, rows_at, offs, qpos, live):
    """The attention sublayer over a chunk call's token axis. h: the normed
    hidden [1, T, D]; table_rows: [n, W] each tile's slot's table; token j's
    row lands at (rows_at[j], offs[j]); qpos: [n, C] absolute positions by
    tile; live: [n] a tile's real tokens, the attention of the others is
    zeros. → (its output [1, T, D], the pool with the tokens' rows)."""
    n, C = qpos.shape
    q_nope, q_rope, rows = project(h, lp, cfg, qpos.reshape(1, n * C))
    q = absorb(q_nope[0], q_rope[0], lp, cfg)  # [T, H, R]
    with jax.named_scope("latent.scatter"):
        pool = pool.at[rows_at, offs].set(rows[0])
    u = latent_chunk_attention(
        q.reshape((n, C) + q.shape[1:]), pool, table_rows, qpos, live,
        cfg.softmax_scale, cfg.kv_lora_rank)
    return attention_out(u.reshape((1, n * C) + u.shape[2:]), lp, cfg), pool


def _decode_layer(cfg: LatentMoEConfig, x, pools, lp: Params, tables, lens, params, index, bases):
    """One layer, one token a slot. x: [b, 1, D]; pools: (rows [P, bs, R],);
    tables: [b, W] block ids into it, from ``bases[0]``; lens: [b] write positions."""
    tables = tables + bases[0]
    a, pool = decode_attention(_norm(x, lp["attn_norm"], cfg), pools[0], lp, cfg, tables, lens)
    x, counts = _finish(x, a, lp, cfg, params, index)
    return x, (pool,), counts


def _chunk_layer(cfg: LatentMoEConfig, x, pools, lp: Params, table_rows, rows_at, offs, qpos,
                 live, params, index, bases, _slot_of):
    """One layer over a chunk call's token axis. x: [1, T, D]; the rest as
    ``chunk_attention`` has it, the block ids from ``bases[0]``."""
    table_rows, rows_at = table_rows + bases[0], rows_at + bases[0]
    a, pool = chunk_attention(_norm(x, lp["attn_norm"], cfg), pools[0], lp, cfg,
                              table_rows, rows_at, offs, qpos, live)
    x, counts = _finish(x, a, lp, cfg, params, index)
    return x, (pool,), counts


@paged.paged_model.register
def _(cfg: LatentMoEConfig) -> paged.PagedModel:
    return paged.PagedModel(
        pools={"rows": paged.Pool(row=(cfg.row_width,), layers=cfg.num_hidden_layers)},
        decode_layer=functools.partial(_decode_layer, cfg),
        chunk_layer=functools.partial(_chunk_layer, cfg),
    )
