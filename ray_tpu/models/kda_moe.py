"""Hybrid decoder of delta-rule layers with a decay a channel (KDA) and latent
attention layers, every feed-forward past the leading ones a layer of many
small experts routed in groups; for the paged serving path (``models/paged.py``
reaches it through ``paged_model``).

Names are the published configuration's (``bailing_hybrid``: Ling-3.0-flash).
Every norm an RMSNorm with ``rms_norm_eps``; pre-norm residuals ``h +=
mixer(norm(h))``, ``h += ffn(mlp_norm(h))``. Layer ``i`` is latent attention
where ``(i + 1) % layer_group_size == 0``, else KDA.

1. **KDA mixer** (Kimi Delta Attention, arXiv:2510.26692; ``H`` heads, key and
   value width ``head_dim``; ``x = norm(h)``): ``[q | k | v] = silu(conv(x
   W_qkv))`` (``short_conv_kernel_size`` taps, causal, depthwise, no bias,
   zeros before position 0); a head's ``q`` and ``k`` L2-normalised, ``q``
   times ``head_dim ** -0.5``; ``beta = sigmoid(x W_b)`` one a head; the log
   decay a CHANNEL ``g = kda_lower_bound * sigmoid(exp(A_log) * (x W_g +
   dt_bias))`` (``A_log`` a head's, ``dt_bias`` a channel's; ``g`` in
   (``kda_lower_bound``, 0)), ``a = exp(g)``; the state ``S`` ``[head_dim keys,
   head_dim values]`` a head, float32: ``S <- diag(a) S``, ``u = v - S^T k``,
   ``S <- S + beta k u^T``, ``o = S^T q``; out ``= (o_norm(o) * sigmoid(x
   W_z)) W_o``, the norm a head's (one scale of ``head_dim`` for all heads).
2. **Latent attention mixer**: ``models/latent_moe.py``'s steps 1-3 with no
   query latent (ONE ``W_q``, a norm on each head's query before the rotation
   and one on the KV latent) and a gate a HEAD on the output: ``o_h <- o_h *
   sigmoid(x W_hg)_h`` before ``W_o``. The cache row is ``[c | k_rope | 0]``.
3. **Feed-forward**: a SwiGLU of ``intermediate_size`` in the
   ``first_k_dense_replace`` leading layers; else ``s = sigmoid(float32(y)
   W_r)`` over ALL ``num_experts``, SELECTION on ``s + expert_bias``: the
   experts in ``n_group`` groups, a group's score the sum of its two largest,
   the ``topk_group`` best groups kept, the ``num_experts_per_tok`` largest of
   theirs; the GATES from ``s`` without the bias, normalised over the chosen,
   times ``routed_scaling_factor``; plus the shared expert
   (``latent_moe.route``, ``.expert_layer``: this chip's share of the experts,
   ``held_first`` and ``held_count``, as there).

**Layers come in a period** of ``layer_group_size`` after the leading ones,
which are KDA layers and run one by one before the scan (``params["lead"]``).
``params["layers"]`` is ONE period, its layers stacked by kind (``kda``:
``[periods, a period's, ...]``, ``latent`` likewise) with their routers and
shared experts; ``params["experts"]`` the held experts outside the scan
(``models/latent_moe.py`` says why), a list with one entry a PLACE in the
period, each ``[periods, held, ...]``: a layer's experts are its place's stack
at the period's number. A chunk call's grouped product is handed that stack as
``periods x held`` groups of which only the period's own hold rows. The layers
after the leading ones must be whole periods: the published 42 = 2 + 40 are
not, and a pipeline's stage is cut so that its own are.

**What a layer keeps.** A latent layer: a row a token, in blocks (``rows``). A
KDA layer: BY SLOT its state (``kda``: ``[.., slots, H, head_dim, head_dim]``
float32) and the last inputs of its convolution (``conv``: ``[.., slots, taps
- 1, 3 * H * head_dim]``). A decode step advances both for every slot whose
``lens`` is above 0 and for no other (``ops/kda.kda_update``); a chunk call
reads them where a segment does not begin its prompt and stores them after the
segment's last real token (``ops/kda.kda_chunk_scan``: tiles in their chunked
form, the state from tile to tile).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import latent_moe, paged
from ray_tpu.models.hybrid_ssm import _layer_of, _segments
from ray_tpu.models.transformer import Params, rms_norm
from ray_tpu.ops.kda import kda_chunk_scan, kda_update
from ray_tpu.ops.latent_attention import latent_attention, latent_chunk_attention


@dataclasses.dataclass(frozen=True)
class KDAMoEConfig:
    num_hidden_layers: int  # leading layers, then whole periods of ``layer_group_size``
    first_k_dense_replace: int
    vocab_size: int = 157184
    hidden_size: int = 2560
    layer_group_size: int = 6
    num_attention_heads: int = 32
    head_dim: int = 128  # a KDA head's key and value width
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_experts: int = 512
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    rope_theta: float = 6e6
    rms_norm_eps: float = 1e-6
    # This chip's share of every expert layer: experts held_first ..
    # held_first + held_count - 1 (None: all of them).
    held_first: int = 0
    held_count: Optional[int] = None
    dtype: Any = jnp.bfloat16  # compute dtype; the state ``S`` is float32 whatever this is

    def __post_init__(self):
        lead, group = self.first_k_dense_replace, self.layer_group_size
        if lead >= group or (self.num_hidden_layers - lead) % group:
            raise ValueError("the leading layers are KDA layers (fewer than layer_group_size), and "
                             "the layers after them whole periods of layer_group_size")
        if self.num_experts % self.n_group:
            raise ValueError("n_group divides num_experts")

    def kind(self, i: int) -> str:
        return "latent" if (i + 1) % self.layer_group_size == 0 else "kda"

    @property
    def period(self) -> Tuple[str, ...]:
        """The kinds of one scanned body's layers, in their order."""
        lead = self.first_k_dense_replace
        return tuple(self.kind(i) for i in range(lead, lead + self.layer_group_size))

    @property
    def periods(self) -> int:
        return (self.num_hidden_layers - self.first_k_dense_replace) // self.layer_group_size

    @property
    def key_dim(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return 3 * self.key_dim

    # What ``models/latent_moe.py``'s attention and expert layer read of a configuration.
    @property
    def held(self) -> int:
        return self.num_experts if self.held_count is None else self.held_count

    @property
    def row_width(self) -> int:
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @classmethod
    def tiny(cls, **kw):
        """Small config for tests: a leading layer and two periods of (kda, latent, kda);
        heads of 128 values, which the decode kernel tiles."""
        return cls(**{**dict(
            num_hidden_layers=7, first_k_dense_replace=1, vocab_size=256, hidden_size=64,
            layer_group_size=3, num_attention_heads=2, head_dim=128, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
            moe_intermediate_size=32, moe_shared_expert_intermediate_size=32, num_experts=16,
            num_experts_per_tok=2, n_group=4, topk_group=2, dtype=jnp.float32), **kw})


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def mixer_shapes(cfg: KDAMoEConfig, kind: str) -> dict:
    """name -> shape of one layer's mixer, of a ``kda`` or a ``latent`` layer."""
    D, H = cfg.hidden_size, cfg.num_attention_heads
    both = {"norm": (D,), "mlp_norm": (D,)}
    if kind == "kda":
        return {**both, "w_qkv": (D, cfg.conv_dim), "conv_w": (cfg.conv_dim, cfg.short_conv_kernel_size),
                "w_g": (D, cfg.key_dim), "dt_bias": (cfg.key_dim,), "A_log": (H,), "w_b": (D, H),
                "w_z": (D, cfg.key_dim), "o_norm": (cfg.head_dim,), "w_o": (cfg.key_dim, D)}
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return {**both, "w_q": (D, H * qk), "q_norm": (qk,), "kv_norm": (cfg.kv_lora_rank,),
            "w_dkv": (D, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            "w_ukv": (cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "w_hg": (D, H), "wo": (H * cfg.v_head_dim, D)}


def ffn_shapes(cfg: KDAMoEConfig, experts: bool) -> dict:
    """A leading layer's feed-forward, or an expert layer's less its routed
    experts (``expert_shapes``)."""
    D = cfg.hidden_size
    if not experts:
        F = cfg.intermediate_size
        return {"w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}
    S = cfg.moe_shared_expert_intermediate_size
    return {"router": (D, cfg.num_experts), "expert_bias": (cfg.num_experts,),
            "shared_gate": (D, S), "shared_up": (D, S), "shared_down": (S, D)}


def expert_shapes(cfg: KDAMoEConfig) -> dict:
    """name -> shape of ONE entry of ``params["experts"]``: the held experts of
    the layers at one place of the period, over the periods."""
    L, E = cfg.periods, cfg.held
    D, F = cfg.hidden_size, cfg.moe_intermediate_size
    return {"e_gate": (L, E, D, F), "e_up": (L, E, D, F), "e_down": (L, E, F, D)}


def init_one(key, name: str, shape: tuple, cfg: KDAMoEConfig):
    """One parameter, float32: norms one, the bias zero, matrices normal at
    1/sqrt(fan_in), the convolution uniform in +-1/sqrt(taps), ``A_log = log
    U(0.5, 1.5)`` and ``dt_bias`` the logit of ``t / -kda_lower_bound``, ``t``
    log-uniform in [0.001, 3]: at ``x W_g = 0`` and ``A_log = 0`` a channel's
    decay is ``exp(-t)``, memories of one to a thousand tokens. ``shape``'s
    leading axes may be stacks of layers."""
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    if name == "expert_bias":
        return jnp.zeros(shape, jnp.float32)
    if name == "conv_w":
        bound = cfg.short_conv_kernel_size ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5))
    if name == "dt_bias":
        t = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(3.0)))
        share = t / -cfg.kda_lower_bound  # sigmoid(..) = share
        return jnp.log(share) - jnp.log1p(-share)
    return jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5


def init_params(key: jax.Array, cfg: KDAMoEConfig) -> Params:
    """Seeded float32 parameters (``init_one``); ``lead`` stacked by layer,
    ``layers`` one period stacked ``[periods, of the kind in a period, ...]``."""
    def tree(key, lead, shapes):
        return {name: init_one(jax.random.fold_in(key, j), name, lead + shape, cfg)
                for j, (name, shape) in enumerate(shapes.items())}

    k_emb, k_lead, k_layers, k_experts, k_out = jax.random.split(key, 5)
    D = cfg.hidden_size
    return {
        "embed": jax.random.normal(k_emb, (cfg.vocab_size, D), jnp.float32),
        "lead": tree(k_lead, (cfg.first_k_dense_replace,),
                     {**mixer_shapes(cfg, "kda"), **ffn_shapes(cfg, False)}),
        "layers": {kind: tree(jax.random.fold_in(k_layers, j), (cfg.periods, cfg.period.count(kind)),
                              {**mixer_shapes(cfg, kind), **ffn_shapes(cfg, True)})
                   for j, kind in enumerate(("kda", "latent"))},
        "experts": [tree(jax.random.fold_in(k_experts, at), (), expert_shapes(cfg))
                    for at in range(cfg.layer_group_size)],
        "final_norm": jnp.ones((D,), jnp.float32),
        "lm_head": jax.random.normal(k_out, (D, cfg.vocab_size), jnp.float32) * D ** -0.5,
    }


# ---------------------------------------------------------------------------
# What both programs share
# ---------------------------------------------------------------------------


def _norm(x, scale, cfg):
    return rms_norm(x, scale, cfg.rms_norm_eps)


def _embed(params: Params, tokens, cfg: KDAMoEConfig):
    return params["embed"].astype(cfg.dtype)[tokens]


def _unembed(params: Params, x, cfg: KDAMoEConfig):
    h = _norm(x, params["final_norm"], cfg)
    return jnp.dot(h, params["lm_head"].astype(h.dtype), preferred_element_type=jnp.float32)


def _ffn(x, lp: Params, cfg: KDAMoEConfig, held=None, period=None):
    """``x + ffn(mlp_norm(x))``: the expert layer where the layer's parameters
    hold a router (``held``: the held experts of the layer's place in the period,
    ``[periods, held, ...]``, and ``period`` which of them are its own), else the
    dense SwiGLU. → (x, counts or None)."""
    y = _norm(x, lp["mlp_norm"], cfg)
    if "router" in lp:
        m, counts = latent_moe.expert_layer(y.reshape(-1, y.shape[-1]), lp, cfg, held, period)
        return x + m.reshape(y.shape), counts
    with jax.named_scope("paged.mlp"):
        return x + latent_moe._swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"]), None


@jax.named_scope("kda.project")
def _project(u, lp: Params, cfg: KDAMoEConfig):
    """Normed hidden [.., D] → (the convolution's input [.., conv_dim], the log
    decay g [.., H, K] float32, beta [.., H] float32, the output gate's input
    [.., H * K])."""
    H, K = cfg.num_attention_heads, cfg.head_dim
    qkv = u @ lp["w_qkv"].astype(u.dtype)
    raw = jnp.dot(u, lp["w_g"].astype(u.dtype), preferred_element_type=jnp.float32)
    raw = (raw + lp["dt_bias"].astype(jnp.float32)).reshape(raw.shape[:-1] + (H, K))
    g = cfg.kda_lower_bound * jax.nn.sigmoid(jnp.exp(lp["A_log"].astype(jnp.float32))[:, None] * raw)
    beta = jax.nn.sigmoid(jnp.dot(u, lp["w_b"].astype(u.dtype), preferred_element_type=jnp.float32))
    return qkv, g, beta, u @ lp["w_z"].astype(u.dtype)


def _convolve(window, lp: Params):
    """window: [.., taps, conv_dim], a token's input last → silu of the
    depthwise sum, float32 [.., conv_dim]."""
    return jax.nn.silu(jnp.sum(window.astype(jnp.float32) * lp["conv_w"].astype(jnp.float32).T, axis=-2))


def _heads(conved, cfg: KDAMoEConfig):
    """The convolved [.., conv_dim] → (q, k [.., H, K], v [.., H, V]) float32:
    a head's q and k of length one, q over sqrt(K)."""
    H, K = cfg.num_attention_heads, cfg.head_dim
    q, k, v = (x.reshape(x.shape[:-1] + (H, K)) for x in jnp.split(conved, 3, axis=-1))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
    return unit(q) * K ** -0.5, unit(k), v


@jax.named_scope("kda.gate")
def _gate(o, z, lp: Params, cfg: KDAMoEConfig):
    """o: [.., H, V] float32, the state's output; z: [.., H * V] → the mixer's
    output [.., D]: a norm a head, the gate, ``W_o``."""
    o = _norm(o, lp["o_norm"], cfg).reshape(z.shape)
    return (o * jax.nn.sigmoid(z.astype(jnp.float32))).astype(z.dtype) @ lp["w_o"].astype(z.dtype)


# ---------------------------------------------------------------------------
# One token a slot
# ---------------------------------------------------------------------------


def _kda_step(cfg: KDAMoEConfig, x, state, conv, lp: Params, lens, base):
    """A KDA mixer for one token a slot. x: [b, 1, D]; state: [P, H, K, V] and
    conv: [P, taps - 1, conv_dim], flat pools whose rows ``base + slot`` are
    this layer's; a slot with ``lens`` 0 keeps its rows as they are."""
    b = x.shape[0]
    qkv, g, beta, z = _project(_norm(x[:, 0], lp["norm"], cfg), lp, cfg)
    with jax.named_scope("kda.conv"):
        old = jax.lax.dynamic_slice_in_dim(conv, base, b, axis=0)
        window = jnp.concatenate([old, qkv[:, None].astype(conv.dtype)], axis=1)
        kept = jnp.where((lens > 0)[:, None, None], window[:, 1:], old)
        conv = jax.lax.dynamic_update_slice_in_dim(conv, kept, base, axis=0)
        q, k, v = _heads(_convolve(window, lp), cfg)
    state, o = kda_update(state, base, lens, jnp.exp(g), k, q, v, beta)
    return x + _gate(o, z, lp, cfg)[:, None], state, conv


def _latent_step(cfg: KDAMoEConfig, x, pool, lp: Params, tables, lens):
    """A latent attention mixer for one token a slot. pool: [P, bs, R] flat,
    holding this layer's blocks at ``tables``' ids."""
    bs = pool.shape[1]
    u = _norm(x, lp["norm"], cfg)
    q_nope, q_rope, rows = latent_moe.project(u, lp, cfg, lens[:, None])
    q = latent_moe.absorb(q_nope[:, 0], q_rope[:, 0], lp, cfg)
    with jax.named_scope("latent.scatter"):
        phys = jnp.take_along_axis(tables, (lens // bs)[:, None], axis=1)[:, 0]
        pool = pool.at[phys, lens % bs].set(rows[:, 0])
    # After the scatter, so the token just written attends to itself.
    a = latent_attention(q, pool, tables, lens, cfg.softmax_scale, cfg.kv_lora_rank)
    return x + latent_moe.attention_out(a, lp, cfg, _head_gate(u[:, 0], lp))[:, None], pool


def _head_gate(u, lp: Params):
    """One gate a head, float32 [.., H]."""
    return jax.nn.sigmoid(jnp.dot(u, lp["w_hg"].astype(u.dtype), preferred_element_type=jnp.float32))


# ---------------------------------------------------------------------------
# A chunk call's token axis
# ---------------------------------------------------------------------------


def _kda_chunk(cfg: KDAMoEConfig, x, state, conv, lp: Params, qpos, live, slot_of, base, n_slots):
    """A KDA mixer over a chunk call's token axis. x: [1, T, D], n tiles of C;
    tile t is of slot ``slot_of[t]`` (``n_slots``: nobody's), begins at
    position ``qpos[t, 0]`` and holds ``live[t]`` real tokens."""
    n, C = qpos.shape
    H = cfg.num_attention_heads
    fresh, cont, last = _segments(qpos, slot_of, n_slots)
    row = base + jnp.minimum(slot_of, n_slots - 1)  # a read that nothing uses, for nobody's tile
    store = jnp.where(last, base + slot_of, conv.shape[0])  # past the pool: dropped
    qkv, g, beta, z = _project(_norm(x[0], lp["norm"], cfg), lp, cfg)
    with jax.named_scope("kda.conv"):
        qkv = qkv.reshape(n, C, cfg.conv_dim).astype(conv.dtype)
        width = conv.shape[1]  # the inputs a slot keeps: the convolution's, less one
        before = jnp.concatenate([jnp.zeros_like(qkv[:1, C - width:]), qkv[:-1, C - width:]])
        came = jnp.where(fresh[:, None, None], 0, jnp.where(cont[:, None, None], before, conv[row]))
        ext = jnp.concatenate([came, qkv], axis=1)  # [n, width + C, conv_dim]
        out = _convolve(jnp.stack([ext[:, j:j + C] for j in range(width + 1)], axis=2), lp)
        # The last ``width`` REAL inputs: those that end at the tile's ``live``.
        kept = jnp.take_along_axis(
            ext, (live[:, None] + jnp.arange(width)[None, :])[:, :, None], axis=1)
        conv = conv.at[store].set(kept, mode="drop")
        q, k, v = _heads(out, cfg)  # [n, C, H, K] each, float32
    state, o = kda_chunk_scan(
        state, jnp.where(slot_of < n_slots, base + slot_of, state.shape[0]), fresh, cont, last, live,
        g.reshape(n, C, H, -1), q, k, v, beta.reshape(n, C, H))
    return x + _gate(o.reshape((n * C,) + o.shape[2:]), z, lp, cfg)[None], state, conv


def _latent_chunk(cfg: KDAMoEConfig, x, pool, lp: Params, table_rows, rows_at, offs, qpos, live):
    """A latent attention mixer over a chunk call's token axis."""
    n, C = qpos.shape
    u = _norm(x, lp["norm"], cfg)
    q_nope, q_rope, rows = latent_moe.project(u, lp, cfg, qpos.reshape(1, n * C))
    q = latent_moe.absorb(q_nope[0], q_rope[0], lp, cfg)  # [T, H, R]
    with jax.named_scope("latent.scatter"):
        pool = pool.at[rows_at, offs].set(rows[0])
    a = latent_chunk_attention(
        q.reshape((n, C) + q.shape[1:]), pool, table_rows, qpos, live,
        cfg.softmax_scale, cfg.kv_lora_rank)
    a = a.reshape((1, n * C) + a.shape[2:])
    return x + latent_moe.attention_out(a, lp, cfg, _head_gate(u, lp)), pool


# ---------------------------------------------------------------------------
# The paged programs' bodies: a leading layer, or one period
# ---------------------------------------------------------------------------


def _layers(cfg: KDAMoEConfig, pools, lp: Params, params: Params, index, bases, x, kda, latent):
    """Call ``index`` of the programs: a leading layer (``lp`` is its own
    parameters) or a period, whose layers run in their order: ``kda(x, state,
    conv, lp, base)`` → (x, its two pools) and ``latent(x, rows, lp, base)`` →
    (x, rows), then the layer's feed-forward. A pool's base moves on by the
    pool's units a layer of its kind; the scan's own slice of ``layers`` is
    left unused (``hybrid_ssm._layer_of``)."""
    rows, state, conv = pools
    rows_base, state_base, _ = bases
    lead = cfg.first_k_dense_replace
    if "w_gate" in lp:
        x, state, conv = kda(x, state, conv, lp, state_base)
        x, _ = _ffn(x, lp, cfg)
        return x, (rows, state, conv), None
    period = index - lead
    blocks = rows.shape[0] // (cfg.periods * cfg.period.count("latent"))
    slots = state.shape[0] // (lead + cfg.periods * cfg.period.count("kda"))
    done = {"kda": 0, "latent": 0}
    total = None
    for at, kind in enumerate(cfg.period):
        j = done[kind]
        done[kind] += 1
        one = _layer_of(params["layers"][kind], period, j)
        if kind == "kda":
            x, state, conv = kda(x, state, conv, one, state_base + j * slots)
        else:
            x, rows = latent(x, rows, one, rows_base + j * blocks)
        x, counts = _ffn(x, one, cfg, params["experts"][at], period)
        total = paged._add_counts(total, counts)
    return x, (rows, state, conv), total


def _decode_layer(cfg: KDAMoEConfig, x, pools, lp, tables, lens, params, index, bases):
    """A leading layer or one PERIOD, one token a slot (``PagedModel.decode_layer``)."""
    return _layers(
        cfg, pools, lp, params, index, bases, x,
        lambda x, state, conv, one, base: _kda_step(cfg, x, state, conv, one, lens, base),
        lambda x, rows, one, base: _latent_step(cfg, x, rows, one, tables + base, lens))


def _chunk_layer(cfg: KDAMoEConfig, x, pools, lp, table_rows, rows_at, offs, qpos, live,
                 params, index, bases, slot_of):
    """A leading layer or one PERIOD over a chunk call's token axis (``PagedModel.chunk_layer``)."""
    n_slots = pools[1].shape[0] // (cfg.first_k_dense_replace + cfg.periods * cfg.period.count("kda"))
    return _layers(
        cfg, pools, lp, params, index, bases, x,
        lambda x, state, conv, one, base: _kda_chunk(
            cfg, x, state, conv, one, qpos, live, slot_of, base, n_slots),
        lambda x, rows, one, base: _latent_chunk(
            cfg, x, rows, one, table_rows + base, rows_at + base, offs, qpos, live))


@paged.paged_model.register
def _(cfg: KDAMoEConfig) -> paged.PagedModel:
    kda = cfg.first_k_dense_replace + cfg.periods * cfg.period.count("kda")
    H, K = cfg.num_attention_heads, cfg.head_dim
    return paged.PagedModel(
        pools={
            # No leading layer keeps latent rows: they are all KDA layers.
            "rows": paged.Pool(row=(cfg.row_width,), layers=cfg.periods * cfg.period.count("latent"),
                               lead=0),
            "kda": paged.Pool(row=(H, K, K), layers=kda, unit="slots", dtype=jnp.float32),
            "conv": paged.Pool(row=(cfg.short_conv_kernel_size - 1, cfg.conv_dim), layers=kda,
                               unit="slots"),
        },
        decode_layer=functools.partial(_decode_layer, cfg),
        chunk_layer=functools.partial(_chunk_layer, cfg),
        embed=_embed,
        unembed=_unembed,
    )
