"""Hybrid decoder of state-space (Mamba-2) and attention layers, for the paged
serving path (``models/paged.py`` reaches it through ``paged_model``).

Names are the published configuration's (``granitemoehybrid``). With ``d``
the hidden size, ``h`` state-space heads of ``p``, a state of ``n`` columns
and one group; every norm an RMSNorm:

- tokens: ``x = embedding_multiplier * E[tok]``; logits ``= (norm(x_L) E^T) /
  logits_scaling`` (the head is the embedding).
- every layer: ``x += residual_multiplier * mixer(norm(x))``, then ``x +=
  residual_multiplier * mlp(norm(x))``; ``mlp(v) = W_o (silu(g) * u)``, ``[g |
  u] = W_i v`` (``shared_intermediate_size`` each; there is no routed part).
- an ``attention`` layer's mixer: ``q, k, v`` without bias, no rotary or other
  position term, causal softmax of ``attention_multiplier * q k^T``, ``W_o``.
- a ``mamba`` layer's mixer: ``[z | xBC | dt] = W_in u``; ``xBC'_t = silu(sum_k
  w[:, k] xBC_{t-3+k} + b)`` (depthwise, causal, zeros before position 0); ``[x
  | B | C] = xBC'``; per head ``D_t = softplus(dt_t + dt_bias)``, ``a_t =
  exp(-D_t exp(A_log))``, ``S_t = a_t S_{t-1} + D_t x_t B_t^T`` (float32, ``S_{-1}
  = 0``), ``y_t = S_t C_t + D x_t``; out ``= W_out (norm(y_t * silu(z_t)))``, the
  norm over all ``h p`` numbers.

**Layers come in a period** (``layer_types``: the published model's is five
state-space layers, one attention layer, four state-space layers, four times
over). ``params["layers"]`` is ONE period, its layers stacked by kind
(``mamba``: ``[periods, a period's, ...]``, ``attn`` likewise), and the paged
programs scan it; the body runs the period's layers in their order.

**What a layer keeps.** An attention layer: keys and values of every token, in
blocks, a token's kv heads side by side on the lanes (``k``, ``v``: ``[.., bs,
kv_heads * head_dim]``, see ``ops/paged_attention.packed_paged_attention``). A
state-space layer: nothing that grows, but BY SLOT its state ``S`` (``ssm``:
``[.., slots, n, h * p]`` float32, the transpose of the equations' ``S``, see
``ops/ssm.py``) and the last three inputs of its convolution (``conv``: ``[..,
slots, 3, conv_dim]``). A decode step advances both for every slot whose
``lens`` is above 0 and for no other; a chunk call reads them where a segment
does not begin its prompt and stores them after the segment's last real token.

The chunk program computes the recurrence a tile at a time
(``ops/ssm.ssm_chunk_scan``: on a TPU one Pallas kernel a layer, else the plain
form): inside a tile the quadratic form (``y_i = sum_{j <= i} (C_i . B_j)
(prod_{j < k <= i} a_k) D_j x_j``, float32), plus what the tile's incoming
state gives; a tile's outgoing state goes to the next tile of the same
segment. A padded token has ``D = 0``: its decay is 1 and nothing of it enters.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import paged
from ray_tpu.models.transformer import Params, rms_norm
from ray_tpu.ops.paged_attention import packed_paged_attention
from ray_tpu.ops.ssm import ssm_chunk_scan, ssm_update

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclasses.dataclass(frozen=True)
class HybridSSMConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = _PERIOD * 4  # "mamba" | "attention", a whole number of periods
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    shared_intermediate_size: int = 8192
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    embedding_multiplier: float = 12.0
    logits_scaling: float = 8.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16  # compute dtype; the state ``S`` is float32 whatever this is

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers or self.num_hidden_layers % len(self.period):
            raise ValueError("layer_types names every layer, in whole periods")
        if self.mamba_n_groups != 1 or self.mamba_n_heads * self.mamba_d_head != self.inner:
            raise ValueError("one group, and heads x head size = expand x hidden_size")

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest run of kinds that ``layer_types`` repeats."""
        kinds = tuple(self.layer_types)
        return next(kinds[:n] for n in range(1, len(kinds) + 1)
                    if len(kinds) % n == 0 and kinds[:n] * (len(kinds) // n) == kinds)

    @property
    def periods(self) -> int:
        return self.num_hidden_layers // len(self.period)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @classmethod
    def tiny(cls, **kw):
        """Small config for tests: two periods of (mamba, mamba, attention, mamba);
        a state of 128 columns and 128 (head, p) pairs, which the kernel tiles."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=8,
            layer_types=("mamba", "mamba", "attention", "mamba") * 2, num_attention_heads=4,
            num_key_value_heads=2, shared_intermediate_size=128, mamba_n_heads=8, mamba_d_head=16,
            mamba_d_state=128, dtype=jnp.float32), **kw})


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def layer_shapes(cfg: HybridSSMConfig, kind: str) -> dict:
    """name -> shape of one layer's parameters, of a ``mamba`` or an ``attention`` layer."""
    D, F = cfg.hidden_size, cfg.shared_intermediate_size
    both = {"norm": (D,), "mlp_norm": (D,), "w_in": (D, 2 * F), "w_out": (F, D)}
    if kind == "mamba":
        h = cfg.mamba_n_heads
        return {**both, "in_proj": (D, cfg.inner + cfg.conv_dim + h),
                "conv_w": (cfg.conv_dim, cfg.mamba_d_conv), "conv_b": (cfg.conv_dim,),
                "dt_bias": (h,), "A_log": (h,), "D": (h,), "gate_norm": (cfg.inner,),
                "out_proj": (cfg.inner, D)}
    H, KV, HD = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    return {**both, "wq": (D, H * HD), "wk": (D, KV * HD), "wv": (D, KV * HD), "wo": (H * HD, D)}


def init_params(key: jax.Array, cfg: HybridSSMConfig, embed_std: float = 0.02) -> Params:
    """Seeded float32 parameters: norms and ``D`` one, matrices normal at
    1/sqrt(fan_in), the convolution uniform in +-1/sqrt(width), ``A_log = log
    U(1, 16)``, ``dt_bias`` the inverse softplus of a log-uniform step in
    [0.001, 0.1] (the Mamba-2 convention: decays that remember one to a
    thousand tokens); ``layers`` one period, stacked ``[periods, of the kind
    in a period, ...]``."""
    def one(key, name, shape):
        if name.endswith("norm") or name == "D":
            return jnp.ones(shape, jnp.float32)
        if name in ("conv_w", "conv_b"):
            bound = cfg.mamba_d_conv ** -0.5
            return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        if name == "A_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        return jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5

    k_emb, k_layers = jax.random.split(key)
    layers = {}
    for j, (kind, name) in enumerate((("mamba", "mamba"), ("attention", "attn"))):
        lead = (cfg.periods, cfg.period.count(kind))
        layers[name] = {
            field: one(jax.random.fold_in(jax.random.fold_in(k_layers, j), i), field, lead + shape)
            for i, (field, shape) in enumerate(layer_shapes(cfg, kind).items())}
    return {
        "embed": jax.random.normal(k_emb, (cfg.vocab_size, cfg.hidden_size), jnp.float32) * embed_std,
        "final_norm": jnp.ones((cfg.hidden_size,), jnp.float32),
        "layers": layers,
    }


# ---------------------------------------------------------------------------
# What both programs share
# ---------------------------------------------------------------------------


def _norm(x, scale, cfg):
    return rms_norm(x, scale, cfg.rms_norm_eps)


def _embed(params: Params, tokens, cfg: HybridSSMConfig):
    return params["embed"].astype(cfg.dtype)[tokens] * cfg.embedding_multiplier


def _unembed(params: Params, x, cfg: HybridSSMConfig):
    """The head is the embedding: logits in float32, over ``logits_scaling``."""
    h = _norm(x, params["final_norm"], cfg)
    logits = jnp.einsum("...d,vd->...v", h, params["embed"].astype(h.dtype),
                        preferred_element_type=jnp.float32)
    return logits / cfg.logits_scaling


def _mlp(x, lp: Params, cfg: HybridSSMConfig):
    with jax.named_scope("paged.mlp"):
        v = _norm(x, lp["mlp_norm"], cfg)
        g, u = jnp.split(v @ lp["w_in"].astype(v.dtype), 2, axis=-1)
        return x + cfg.residual_multiplier * ((jax.nn.silu(g) * u) @ lp["w_out"].astype(v.dtype))


@jax.named_scope("ssm.project")
def _project(u, lp: Params, cfg: HybridSSMConfig):
    """Normed hidden [.., D] → (z [.., inner], xBC [.., conv_dim], the step
    ``softplus(dt + dt_bias)`` [.., h] float32)."""
    zxbcdt = u @ lp["in_proj"].astype(u.dtype)
    z, xbc, dt = jnp.split(zxbcdt, (cfg.inner, cfg.inner + cfg.conv_dim), axis=-1)
    return z, xbc, jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))


def _convolve(window, lp: Params):
    """window: [.., width, conv_dim], a token's input last → silu of the
    depthwise sum, float32 [.., conv_dim]."""
    w = lp["conv_w"].astype(jnp.float32).T  # [width, conv_dim]
    return jax.nn.silu(jnp.sum(window.astype(jnp.float32) * w, axis=-2)
                       + lp["conv_b"].astype(jnp.float32))


@jax.named_scope("ssm.gate")
def _gate(y, xs, z, lp: Params, cfg: HybridSSMConfig):
    """y, xs: [.., h * p] float32 (the state's output, the convolved input);
    z: [.., inner] → the mixer's output [.., D]: the skip ``D x``, the gate,
    the norm over all of it, ``W_out``."""
    skip = jnp.repeat(lp["D"].astype(jnp.float32), cfg.mamba_d_head)
    y = (y + skip * xs) * jax.nn.silu(z.astype(jnp.float32))
    y = _norm(y, lp["gate_norm"], cfg).astype(z.dtype)
    return y @ lp["out_proj"].astype(z.dtype)


def _split_xbc(xbc, cfg: HybridSSMConfig):
    return jnp.split(xbc, (cfg.inner, cfg.inner + cfg.mamba_d_state), axis=-1)


# ---------------------------------------------------------------------------
# One token a slot
# ---------------------------------------------------------------------------


def _mamba_step(cfg: HybridSSMConfig, x, ssm, conv, lp: Params, lens, base):
    """A state-space layer for one token a slot. x: [b, 1, D]; ssm: [P, n, h*p]
    and conv: [P, 3, conv_dim], flat pools whose rows ``base + slot`` are this
    layer's; a slot with ``lens`` 0 keeps its rows as they are."""
    b = x.shape[0]
    z, xbc, dt = _project(_norm(x[:, 0], lp["norm"], cfg), lp, cfg)
    with jax.named_scope("ssm.conv"):
        old = jax.lax.dynamic_slice_in_dim(conv, base, b, axis=0)
        window = jnp.concatenate([old, xbc[:, None].astype(conv.dtype)], axis=1)
        kept = jnp.where((lens > 0)[:, None, None], window[:, 1:], old)
        conv = jax.lax.dynamic_update_slice_in_dim(conv, kept, base, axis=0)
        xs, B, C = _split_xbc(_convolve(window, lp), cfg)
    decay = jnp.exp(-dt * jnp.exp(lp["A_log"].astype(jnp.float32)))  # [b, h]
    p = cfg.mamba_d_head
    ssm, y = ssm_update(ssm, base, lens, jnp.repeat(decay, p, axis=-1),
                        jnp.repeat(dt, p, axis=-1) * xs, B, C)
    x = x + cfg.residual_multiplier * _gate(y, xs, z, lp, cfg)[:, None]
    return _mlp(x, lp, cfg), ssm, conv


def _attention_step(cfg: HybridSSMConfig, x, ck, cv, lp: Params, tables, lens):
    """An attention layer for one token a slot. ck/cv: [P, bs, KV*HD] flat
    pools that hold this layer's blocks at ``tables``' ids."""
    b = x.shape[0]
    bs = ck.shape[1]
    u = _norm(x[:, 0], lp["norm"], cfg)
    q = (u @ lp["wq"].astype(u.dtype)).reshape(b, cfg.num_attention_heads, cfg.head_dim)
    with jax.named_scope("paged.scatter"):
        phys = jnp.take_along_axis(tables, (lens // bs)[:, None], axis=1)[:, 0]
        ck = ck.at[phys, lens % bs].set((u @ lp["wk"].astype(u.dtype)).astype(ck.dtype))
        cv = cv.at[phys, lens % bs].set((u @ lp["wv"].astype(u.dtype)).astype(cv.dtype))
    # After the scatter, so the token just written attends to itself.
    o = packed_paged_attention(q, ck, cv, tables, lens, cfg.attention_multiplier)
    x = x + cfg.residual_multiplier * (o @ lp["wo"].astype(o.dtype))[:, None]
    return _mlp(x, lp, cfg), ck, cv


# ---------------------------------------------------------------------------
# A chunk call's token axis
# ---------------------------------------------------------------------------


def _segments(qpos, slot_of, n_slots: int):
    """Where each tile's state comes from and goes to. → (fresh [n]: from
    nothing; cont [n]: from the tile before; last [n]: the tile ends its
    segment and its slot is somebody's)."""
    fresh = qpos[:, 0] == 0
    same_as_before = jnp.concatenate([jnp.zeros((1,), bool), slot_of[1:] == slot_of[:-1]])
    ends = jnp.concatenate([slot_of[1:] != slot_of[:-1], jnp.ones((1,), bool)])
    return fresh, same_as_before & ~fresh, ends & (slot_of < n_slots)


def _mamba_chunk(cfg: HybridSSMConfig, x, ssm, conv, lp: Params, qpos, live, slot_of, base, n_slots):
    """A state-space layer over a chunk call's token axis. x: [1, T, D], n tiles
    of C; tile t is of slot ``slot_of[t]`` (``n_slots``: nobody's), begins at
    position ``qpos[t, 0]`` and holds ``live[t]`` real tokens."""
    n, C = qpos.shape
    h, p = cfg.mamba_n_heads, cfg.mamba_d_head
    fresh, cont, last = _segments(qpos, slot_of, n_slots)
    row = base + jnp.minimum(slot_of, n_slots - 1)  # a read that nothing uses, for nobody's tile
    store = jnp.where(last, base + slot_of, conv.shape[0])  # past the pool: dropped
    z, xbc, dt = _project(_norm(x[0], lp["norm"], cfg), lp, cfg)
    with jax.named_scope("ssm.conv"):
        xbc = xbc.reshape(n, C, cfg.conv_dim).astype(conv.dtype)
        width = conv.shape[1]  # the inputs a slot keeps: the convolution's, less one
        before = jnp.concatenate([jnp.zeros_like(xbc[:1, C - width:]), xbc[:-1, C - width:]])
        came = jnp.where(fresh[:, None, None], 0, jnp.where(cont[:, None, None], before, conv[row]))
        ext = jnp.concatenate([came, xbc], axis=1)  # [n, width + C, conv_dim]
        out = _convolve(jnp.stack([ext[:, k:k + C] for k in range(width + 1)], axis=2), lp)
        # The last ``width`` REAL inputs: those that end at the tile's ``live``.
        kept = jnp.take_along_axis(
            ext, (live[:, None] + jnp.arange(width)[None, :])[:, :, None], axis=1)
        conv = conv.at[store].set(kept, mode="drop")
        xs, B, Cm = _split_xbc(out, cfg)  # [n, C, inner], [n, C, N] x2, float32
    # Between tiles a segment's state goes from tile to tile; it comes from the
    # slot's row of the pool where a segment neither begins its prompt nor
    # follows its own tile, and its last tile leaves it there.
    ssm, y = ssm_chunk_scan(
        ssm, jnp.where(slot_of < n_slots, base + slot_of, ssm.shape[0]), fresh, cont, last, live,
        dt.reshape(n, C, h), jnp.exp(lp["A_log"].astype(jnp.float32)), xs, B, Cm)
    out = _gate(y.reshape(n * C, h * p), xs.reshape(n * C, h * p), z, lp, cfg)
    x = x + cfg.residual_multiplier * out[None]
    return _mlp(x, lp, cfg), ssm, conv


def _attention_chunk(cfg: HybridSSMConfig, x, ck, cv, lp: Params, table_rows, rows_at, offs, qpos):
    """An attention layer over a chunk call's token axis: K/V of the tokens
    into their blocks, then every tile through its slot's gathered table."""
    n, C = qpos.shape
    W, bs = table_rows.shape[1], ck.shape[1]
    H, KV, HD = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    u = _norm(x[0], lp["norm"], cfg)
    q = (u @ lp["wq"].astype(u.dtype)).reshape(n, C, H, HD)
    ck = ck.at[rows_at, offs].set((u @ lp["wk"].astype(u.dtype)).astype(ck.dtype))
    cv = cv.at[rows_at, offs].set((u @ lp["wv"].astype(u.dtype)).astype(cv.dtype))
    with jax.named_scope("paged.attend"):
        o = paged._attend_chunk(q, ck[table_rows].reshape(n, W * bs, KV, HD),
                                cv[table_rows].reshape(n, W * bs, KV, HD), qpos,
                                cfg.attention_multiplier)
    x = x + cfg.residual_multiplier * (o.reshape(1, n * C, H * HD) @ lp["wo"].astype(o.dtype))
    return _mlp(x, lp, cfg), ck, cv


# ---------------------------------------------------------------------------
# The paged programs' bodies: one period
# ---------------------------------------------------------------------------


def _layer_of(stack: Params, period, k: int) -> Params:
    """Layer ``k`` of period ``period`` (traced) from a kind's whole stack
    ``[periods, a period's, ...]``: ONE dynamic slice a matrix, which a product
    reads where it lies. Taken in two steps (the scan's slice of the period,
    then ``[k]``) the period's nine layers are first copied out whole, 0.7 GB
    a period a step."""
    def one(a):
        sizes = (1, 1) + a.shape[2:]
        return jax.lax.dynamic_slice(a, (period, k) + (0,) * (a.ndim - 2), sizes).reshape(a.shape[2:])
    return jax.tree.map(one, stack)


def _period(cfg: HybridSSMConfig, pools, params: Params, period, bases, x, mamba, attention):
    """Run period ``period``'s layers in their order: ``mamba(x, ssm, conv, lp,
    base)`` and ``attention(x, ck, cv, lp, base)``, each → (x, its two pools).
    A pool's base moves on by the pool's units a layer of its kind. The scan's
    own slice of ``layers`` is left unused (``_layer_of``)."""
    lp = params["layers"]
    ck, cv, ssm, conv = pools
    kv_base, _, ssm_base, _ = bases
    blocks = ck.shape[0] // (cfg.periods * cfg.period.count("attention"))
    slots = ssm.shape[0] // (cfg.periods * cfg.period.count("mamba"))
    done = {"mamba": 0, "attention": 0}
    for kind in cfg.period:
        k = done[kind]
        done[kind] += 1
        if kind == "mamba":
            x, ssm, conv = mamba(x, ssm, conv, _layer_of(lp["mamba"], period, k), ssm_base + k * slots)
        else:
            x, ck, cv = attention(x, ck, cv, _layer_of(lp["attn"], period, k), kv_base + k * blocks)
    return x, (ck, cv, ssm, conv), None


def _decode_layer(cfg: HybridSSMConfig, x, pools, _lp, tables, lens, params, index, bases):
    """One PERIOD, one token a slot (``PagedModel.decode_layer``)."""
    return _period(
        cfg, pools, params, index, bases, x,
        lambda x, ssm, conv, one, base: _mamba_step(cfg, x, ssm, conv, one, lens, base),
        lambda x, ck, cv, one, base: _attention_step(cfg, x, ck, cv, one, tables + base, lens))


def _chunk_layer(cfg: HybridSSMConfig, x, pools, _lp, table_rows, rows_at, offs, qpos, live,
                 params, index, bases, slot_of):
    """One PERIOD over a chunk call's token axis (``PagedModel.chunk_layer``)."""
    n_slots = pools[2].shape[0] // (cfg.periods * cfg.period.count("mamba"))
    return _period(
        cfg, pools, params, index, bases, x,
        lambda x, ssm, conv, one, base: _mamba_chunk(
            cfg, x, ssm, conv, one, qpos, live, slot_of, base, n_slots),
        lambda x, ck, cv, one, base: _attention_chunk(
            cfg, x, ck, cv, one, table_rows + base, rows_at + base, offs, qpos))


@paged.paged_model.register
def _(cfg: HybridSSMConfig) -> paged.PagedModel:
    attn = cfg.layer_types.count("attention")
    mamba = cfg.layer_types.count("mamba")
    kv = paged.Pool(row=(cfg.num_key_value_heads * cfg.head_dim,), layers=attn)
    return paged.PagedModel(
        pools={
            "k": kv, "v": kv,
            "ssm": paged.Pool(row=(cfg.mamba_d_state, cfg.inner), layers=mamba, unit="slots",
                              dtype=jnp.float32),
            "conv": paged.Pool(row=(cfg.mamba_d_conv - 1, cfg.conv_dim), layers=mamba, unit="slots"),
        },
        decode_layer=functools.partial(_decode_layer, cfg),
        chunk_layer=functools.partial(_chunk_layer, cfg),
        embed=_embed,
        unembed=_unembed,
    )
