"""Decoder whose full layers are latent attention that CHOOSES the cached tokens
it reads (a learned indexer with a cache of its own) and whose other layers are
latent attention of other sizes over a sliding window; every feed-forward past
the leading ones a layer of sigmoid-routed experts. For the paged serving path
(``models/paged.py`` reaches it through ``paged_model``).

Names are the published configuration's (``dots3_note``). Every norm an RMSNorm
with ``rms_norm_eps``; pre-norm residuals ``h += mixer(attn_norm(h))``, ``h +=
ffn(mlp_norm(h))``. Layer ``i`` is ``layer_types[i]``.

1. **Full layer** (``num_attention_heads`` heads, ``q_lora_rank``,
   ``kv_lora_rank``, ``qk_nope_head_dim`` + ``qk_rope_head_dim`` keys,
   ``rope_theta``), ``x = attn_norm(h)`` at position ``t``: ``models/latent_moe.py``'s
   steps 1-3 (``c_q = r_q q_norm(x W_dq)``, ``q = c_q W_uq``; ``[c_kv | k_r] = x
   W_dkv``, ``c = r_kv kv_norm(c_kv)``; the cache row ``[c | RoPE(k_r) | 0]``)
   with both latents RESCALED after their norms, ``r = sqrt(hidden_size /
   rank)`` (``apply_mla_qkv_lora_rescale``), and the attention over the
   positions the INDEXER chooses: ``q^I = c_q W_iq`` (``index_n_heads`` heads of
   ``index_head_dim``), ``k^I = index_norm(x W_ik)`` (ONE a token, cached: the
   pool ``index``), the first ``qk_rope_head_dim`` numbers of each rotated to
   their position, ``w = (x W_iw) / sqrt(index_n_heads * index_head_dim)``;
   ``I(t, s) = sum_j w_j relu(q^I_j(t) . k^I(s))`` in float32; the query attends
   to the ``index_topk`` positions ``s <= t`` of largest ``I`` and to no other
   (``ops/sparse_latent_attention.py``). Then a gate a head, ``o_j <- o_j *
   sigmoid(x W_hg)_j``, and ``W_o`` (``latent_moe.attention_out``).
2. **Sliding layer**: the same mixer at the ``swa_*`` sizes with NO indexer,
   attention over positions ``t - sliding_window_size < s <= t``. Its rows lie
   in a pool of their own (``window``), whole, under the same block table; the
   reads cover the window's blocks only.
3. **Feed-forward**: a SwiGLU of ``intermediate_size`` in the
   ``first_k_dense_replace`` leading layers, else ``latent_moe.expert_layer``:
   sigmoid scores over ALL ``n_routed_experts``, the ``num_experts_per_tok``
   largest of ``score + expert_bias``, gates from the scores normalised over
   the chosen, this chip's share of the experts (``held_first``,
   ``held_count``), one shared expert.

**Layers come in a period** after the leading ones (which are full layers, run
one by one before the scan, ``params["lead"]``): the kinds from one full layer
to the next. ``params["layers"]`` is ONE period, its layers stacked by kind
(``full``: ``[periods, a period's, ...]``, ``sliding`` likewise);
``params["experts"]`` the held experts outside the scan, one entry a PLACE in
the period (``models/kda_moe.py`` says why). The layers after the leading ones
must be WHOLE periods, so this module builds neither the published model in
one piece (46 = 1 + 11 x 4 + 1 ends on a full layer with no sliding layers
behind it) nor the stage of a pipeline that holds that last layer beside
others: a stage is cut on a period, and the last full layer is a stage of its
own kind that nothing here runs yet (ROADMAP M3's remainder says what it
would take: a trailing layer run after the scan as the leading one runs
before it).

Counts (``PagedModel``): ``latent_moe.routed_experts``' five, then cached
tokens the full layers' queries could have read, how many they selected, and
cache rows the sliding layers' reads covered.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import latent_moe, paged
from ray_tpu.models.hybrid_ssm import _layer_of
from ray_tpu.models.transformer import Params, _rope, rms_norm
from ray_tpu.ops import sparse_latent_attention as sparse

FULL, SLIDING = "full_attention", "sliding_attention"


class Mixer(NamedTuple):
    """One kind of layer's attention sizes, under the names ``latent_moe.project``,
    ``.absorb`` and ``.attention_out`` read off a configuration."""

    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_norm_eps: float

    @property
    def row_width(self) -> int:
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


@dataclasses.dataclass(frozen=True)
class SparseLatentMoEConfig:
    num_hidden_layers: int
    layer_types: Tuple[str, ...]  # one of FULL, SLIDING a layer
    first_k_dense_replace: int = 1
    vocab_size: int = 152064
    hidden_size: int = 5120
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    sliding_window_size: int = 513
    intermediate_size: int = 13824
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    # This chip's share of every expert layer: experts held_first ..
    # held_first + held_count - 1 (None: all of them).
    held_first: int = 0
    held_count: Optional[int] = None
    dtype: Any = jnp.bfloat16  # compute dtype

    # The published configuration has no ``n_group``: ONE group of experts, the
    # largest of all. What ``latent_moe.route`` reads, and nothing sets.
    n_group = property(lambda self: 1)
    topk_group = property(lambda self: 1)

    def __post_init__(self):
        lead, types = self.first_k_dense_replace, tuple(self.layer_types)
        object.__setattr__(self, "layer_types", types)
        rest = types[lead:]
        if (len(types) != self.num_hidden_layers or set(types) - {FULL, SLIDING}
                or any(t != FULL for t in types[:lead]) or not rest or rest[0] != FULL):
            raise ValueError("layer_types names every layer; the leading layers are full layers, "
                             "and so is the first after them")
        size = rest.index(FULL, 1) if FULL in rest[1:] else len(rest)
        if len(rest) % size or rest != rest[:size] * (len(rest) // size):
            raise ValueError("the layers after the leading ones must be whole periods (the kinds "
                             f"from one full layer to the next: {rest[:size]})")

    @property
    def period(self) -> Tuple[str, ...]:
        """The kinds of one scanned body's layers, in their order."""
        rest = self.layer_types[self.first_k_dense_replace:]
        return rest[:rest.index(FULL, 1) if FULL in rest[1:] else len(rest)]

    @property
    def periods(self) -> int:
        return (self.num_hidden_layers - self.first_k_dense_replace) // len(self.period)

    def mixer(self, kind: str) -> Mixer:
        if kind == FULL:
            return Mixer(self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank,
                         self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
                         self.rope_theta, self.rms_norm_eps)
        return Mixer(self.swa_num_attention_heads, self.swa_q_lora_rank, self.swa_kv_lora_rank,
                     self.swa_qk_nope_head_dim, self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                     self.swa_rope_theta, self.rms_norm_eps)

    @property
    def held(self) -> int:
        return self.n_routed_experts if self.held_count is None else self.held_count

    @classmethod
    def tiny(cls, **kw):
        """Small config for tests: a leading layer and two periods of (full,
        sliding, sliding); the selection and the window both far under a test's contexts."""
        return cls(**{**dict(
            num_hidden_layers=7, layer_types=(FULL,) + (FULL, SLIDING, SLIDING) * 2,
            vocab_size=256, hidden_size=64, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, index_n_heads=16,
            index_head_dim=16, index_topk=12, swa_num_attention_heads=2, swa_q_lora_rank=24,
            swa_kv_lora_rank=40, swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
            sliding_window_size=9, intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=8, num_experts_per_tok=2, dtype=jnp.float32), **kw})


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def mixer_shapes(cfg: SparseLatentMoEConfig, kind: str) -> dict:
    """name -> shape of one layer's mixer and norms, of a full or a sliding layer."""
    D, mx = cfg.hidden_size, cfg.mixer(kind)
    H, qk = mx.num_attention_heads, mx.qk_nope_head_dim + mx.qk_rope_head_dim
    out = {"attn_norm": (D,), "mlp_norm": (D,), "q_norm": (mx.q_lora_rank,),
           "kv_norm": (mx.kv_lora_rank,), "w_dq": (D, mx.q_lora_rank),
           "w_uq": (mx.q_lora_rank, H * qk), "w_dkv": (D, mx.kv_lora_rank + mx.qk_rope_head_dim),
           "w_ukv": (mx.kv_lora_rank, H * (mx.qk_nope_head_dim + mx.v_head_dim)),
           "w_hg": (D, H), "wo": (H * mx.v_head_dim, D)}
    if kind == FULL:
        out.update({"w_iq": (mx.q_lora_rank, cfg.index_n_heads * cfg.index_head_dim),
                    "w_ik": (D, cfg.index_head_dim), "index_norm": (cfg.index_head_dim,),
                    "w_iw": (D, cfg.index_n_heads)})
    return out


def ffn_shapes(cfg: SparseLatentMoEConfig, experts: bool) -> dict:
    """A leading layer's feed-forward, or an expert layer's less its routed
    experts (``expert_shapes``)."""
    D = cfg.hidden_size
    if not experts:
        F = cfg.intermediate_size
        return {"w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}
    S = cfg.moe_intermediate_size * cfg.n_shared_experts
    return {"router": (D, cfg.n_routed_experts), "expert_bias": (cfg.n_routed_experts,),
            "shared_gate": (D, S), "shared_up": (D, S), "shared_down": (S, D)}


def expert_shapes(cfg: SparseLatentMoEConfig) -> dict:
    """name -> shape of ONE entry of ``params["experts"]``: the held experts of
    the layers at one place of the period, over the periods."""
    L, E = cfg.periods, cfg.held
    D, F = cfg.hidden_size, cfg.moe_intermediate_size
    return {"e_gate": (L, E, D, F), "e_up": (L, E, D, F), "e_down": (L, E, F, D)}


def init_params(key: jax.Array, cfg: SparseLatentMoEConfig) -> Params:
    """Seeded float32 parameters: norms one, ``expert_bias`` normal at 0.01,
    matrices normal at 1/sqrt(fan_in), those that read a RESCALED latent
    (``w_uq``, ``w_iq``, ``w_ukv``) at 1/sqrt(hidden_size) (the rescale stands the
    latent where the hidden state would stand: their products have unit
    variance), the embedding unit variance; ``lead`` stacked by layer, ``layers``
    one period stacked ``[periods, of the kind in a period, ...]``."""
    def one(key, name, shape):
        if name.endswith("norm"):
            return jnp.ones(shape, jnp.float32)
        if name == "expert_bias":
            return 0.01 * jax.random.normal(key, shape, jnp.float32)
        fan_in = cfg.hidden_size if name in ("w_uq", "w_iq", "w_ukv") else shape[-2]
        return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5

    def tree(key, lead, shapes):
        return {name: one(jax.random.fold_in(key, j), name, lead + shape)
                for j, (name, shape) in enumerate(shapes.items())}

    k_emb, k_lead, k_layers, k_experts, k_out = jax.random.split(key, 5)
    D = cfg.hidden_size
    return {
        "embed": jax.random.normal(k_emb, (cfg.vocab_size, D), jnp.float32),
        "lead": tree(k_lead, (cfg.first_k_dense_replace,),
                     {**mixer_shapes(cfg, FULL), **ffn_shapes(cfg, False)}),
        "layers": {kind: tree(jax.random.fold_in(k_layers, j), (cfg.periods, cfg.period.count(kind)),
                              {**mixer_shapes(cfg, kind), **ffn_shapes(cfg, True)})
                   for j, kind in enumerate((FULL, SLIDING)) if kind in cfg.period},
        "experts": [tree(jax.random.fold_in(k_experts, at), (), expert_shapes(cfg))
                    for at in range(len(cfg.period))],
        "final_norm": jnp.ones((D,), jnp.float32),
        "lm_head": jax.random.normal(k_out, (D, cfg.vocab_size), jnp.float32) * D ** -0.5,
    }


# ---------------------------------------------------------------------------
# What both programs share
# ---------------------------------------------------------------------------


def _norm(x, scale, cfg):
    return rms_norm(x, scale, cfg.rms_norm_eps)


def _embed(params: Params, tokens, cfg: SparseLatentMoEConfig):
    return params["embed"].astype(cfg.dtype)[tokens]


def _unembed(params: Params, x, cfg: SparseLatentMoEConfig):
    h = _norm(x, params["final_norm"], cfg)
    return jnp.dot(h, params["lm_head"].astype(h.dtype), preferred_element_type=jnp.float32)


def _rescaled(lp: Params, cfg: SparseLatentMoEConfig, mx: Mixer) -> Params:
    """The layer with both latents' rescale folded into their norms' scales:
    ``r * norm(x) = norm(x) * (r * scale)``."""
    r_q, r_kv = (cfg.hidden_size / mx.q_lora_rank) ** 0.5, (cfg.hidden_size / mx.kv_lora_rank) ** 0.5
    return {**lp, "q_norm": lp["q_norm"].astype(jnp.float32) * r_q,
            "kv_norm": lp["kv_norm"].astype(jnp.float32) * r_kv}


def _project(u, lp: Params, cfg: SparseLatentMoEConfig, kind: str, positions):
    """Normed hidden [b, s, D] -> (the rescaled layer, absorbed queries [b, s, H,
    R], cache rows [b, s, R], the output's gate a head [b, s, H] float32)."""
    mx = cfg.mixer(kind)
    lp = _rescaled(lp, cfg, mx)
    q_nope, q_rope, rows = latent_moe.project(u, lp, mx, positions)
    gate = jax.nn.sigmoid(jnp.dot(u, lp["w_hg"].astype(u.dtype), preferred_element_type=jnp.float32))
    return lp, latent_moe.absorb(q_nope, q_rope, lp, mx), rows, gate


@jax.named_scope("sparse.project")
def _index_project(u, lp: Params, cfg: SparseLatentMoEConfig, positions):
    """The indexer's side of a full layer. u: normed hidden [b, s, D]; ``lp``
    rescaled -> (index queries [b, s, Hi, Di], the token's index key [b, s, Di],
    head weights [b, s, Hi] float32). ``c_q`` is the query latent of
    ``latent_moe.project``, the same product written again."""
    b, s, _ = u.shape
    Hi, Di, rope = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    c_q = _norm(u @ lp["w_dq"].astype(u.dtype), lp["q_norm"], cfg)
    qi = (c_q @ lp["w_iq"].astype(u.dtype)).reshape(b, s, Hi, Di)
    ki = _norm(u @ lp["w_ik"].astype(u.dtype), lp["index_norm"], cfg)[:, :, None, :]

    def rotated(x):
        return jnp.concatenate([_rope(x[..., :rope], positions, cfg.rope_theta), x[..., rope:]], axis=-1)

    w = jnp.dot(u, lp["w_iw"].astype(u.dtype), preferred_element_type=jnp.float32)
    return rotated(qi), rotated(ki)[:, :, 0], w * (Hi * Di) ** -0.5


def _ffn(x, lp: Params, cfg: SparseLatentMoEConfig, held=None, period=None):
    """``x + ffn(mlp_norm(x))``: the expert layer where the layer's parameters
    hold a router (``held``: the held experts of the layer's place in the period,
    ``[periods, held, ...]``, and ``period`` which of them are its own), else the
    dense SwiGLU. -> (x, the expert layer's counts or None)."""
    y = _norm(x, lp["mlp_norm"], cfg)
    if "router" in lp:
        m, counts = latent_moe.expert_layer(y.reshape(-1, y.shape[-1]), lp, cfg, held, period)
        return x + m.reshape(y.shape), counts
    with jax.named_scope("paged.mlp"):
        return x + latent_moe._swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"]), None


def _counts(moe, live=0, selected=0, covered=0):
    """A call's counts: the expert layer's five (zeros where there was none),
    then the three of this module."""
    moe = jnp.zeros((5,), jnp.int32) if moe is None else moe
    return jnp.concatenate([moe, jnp.stack([jnp.int32(live), jnp.int32(selected), jnp.int32(covered)])])


# ---------------------------------------------------------------------------
# One token a slot
# ---------------------------------------------------------------------------


def _write(pool, tables, lens, rows):
    """One token's row a slot into its (block, offset); idle slots point at the trash block."""
    bs = pool.shape[1]
    phys = jnp.take_along_axis(tables, (lens // bs)[:, None], axis=1)[:, 0]
    return pool.at[phys, lens % bs].set(rows.astype(pool.dtype))


def _full_step(cfg: SparseLatentMoEConfig, x, rows, index, lp: Params, tables, key_tables, lens):
    """A full layer's mixer for one token a slot. rows: [P, bs, R] and index:
    [P', bs, Di] flat pools that hold this layer's blocks at ``tables``' and
    ``key_tables``' ids. -> (x, rows, index, counts of the keys)."""
    mx = cfg.mixer(FULL)
    u = _norm(x, lp["attn_norm"], cfg)
    lp, q, new, gate = _project(u, lp, cfg, FULL, lens[:, None])
    qi, ki, w = _index_project(u, lp, cfg, lens[:, None])
    with jax.named_scope("latent.scatter"):
        rows = _write(rows, tables, lens, new[:, 0])
        index = _write(index, key_tables, lens, ki[:, 0])
    # After the scatter, so the token just written scores and attends to itself.
    a = sparse.sparse_attention(q[:, 0], qi[:, 0], w[:, 0], rows, index, tables, key_tables, lens,
                                mx.softmax_scale, mx.kv_lora_rank, cfg.index_topk)
    live = jnp.where(lens > 0, lens + 1, 0)  # ``lens`` 0: a slot that holds no sequence
    x = x + latent_moe.attention_out(a, lp, mx, gate[:, 0])[:, None]
    return x, rows, index, (jnp.sum(live), jnp.sum(jnp.minimum(live, cfg.index_topk)))


def _sliding_step(cfg: SparseLatentMoEConfig, x, pool, lp: Params, tables, lens):
    """A sliding layer's mixer for one token a slot. -> (x, pool, rows its reads covered)."""
    mx = cfg.mixer(SLIDING)
    u = _norm(x, lp["attn_norm"], cfg)
    lp, q, new, gate = _project(u, lp, cfg, SLIDING, lens[:, None])
    with jax.named_scope("latent.scatter"):
        pool = _write(pool, tables, lens, new[:, 0])
    a, covered = sparse.window_attention(q[:, 0], pool, tables, lens, mx.softmax_scale,
                                         mx.kv_lora_rank, cfg.sliding_window_size)
    x = x + latent_moe.attention_out(a, lp, mx, gate[:, 0])[:, None]
    return x, pool, jnp.sum(jnp.where(lens > 0, covered, 0))


# ---------------------------------------------------------------------------
# A chunk call's token axis
# ---------------------------------------------------------------------------


def _full_chunk(cfg: SparseLatentMoEConfig, x, rows, index, lp: Params, tables, key_tables,
                rows_at, keys_at, offs, qpos, live):
    """A full layer's mixer over a chunk call's token axis. x: [1, T, D], n
    tiles of C; token j's row lands at (rows_at[j], offs[j]) and its index key
    at (keys_at[j], offs[j])."""
    mx = cfg.mixer(FULL)
    n, C = qpos.shape
    u = _norm(x, lp["attn_norm"], cfg)
    lp, q, new, gate = _project(u, lp, cfg, FULL, qpos.reshape(1, n * C))
    qi, ki, w = _index_project(u, lp, cfg, qpos.reshape(1, n * C))
    with jax.named_scope("latent.scatter"):
        rows = rows.at[rows_at, offs].set(new[0].astype(rows.dtype))
        index = index.at[keys_at, offs].set(ki[0].astype(index.dtype))

    def tiles(a):
        return a[0].reshape((n, C) + a.shape[2:])

    a = sparse.sparse_chunk_attention(
        tiles(q), tiles(qi), tiles(w), rows, index, tables, key_tables, qpos, live,
        mx.softmax_scale, mx.kv_lora_rank, cfg.index_topk)
    x = x + latent_moe.attention_out(a.reshape((1, n * C) + a.shape[2:]), lp, mx, gate)
    keys = jnp.where(jnp.arange(C)[None, :] < live[:, None], qpos + 1, 0)
    return x, rows, index, (jnp.sum(keys), jnp.sum(jnp.minimum(keys, cfg.index_topk)))


def _sliding_chunk(cfg: SparseLatentMoEConfig, x, pool, lp: Params, tables, rows_at, offs, qpos, live):
    """A sliding layer's mixer over a chunk call's token axis."""
    mx = cfg.mixer(SLIDING)
    n, C = qpos.shape
    u = _norm(x, lp["attn_norm"], cfg)
    lp, q, new, gate = _project(u, lp, cfg, SLIDING, qpos.reshape(1, n * C))
    with jax.named_scope("latent.scatter"):
        pool = pool.at[rows_at, offs].set(new[0].astype(pool.dtype))
    a, covered = sparse.window_chunk_attention(
        q[0].reshape((n, C) + q.shape[2:]), pool, tables, qpos, live, mx.softmax_scale,
        mx.kv_lora_rank, cfg.sliding_window_size)
    x = x + latent_moe.attention_out(a.reshape((1, n * C) + a.shape[2:]), lp, mx, gate)
    return x, pool, jnp.sum(covered)


# ---------------------------------------------------------------------------
# The paged programs' bodies: a leading layer, or one period
# ---------------------------------------------------------------------------


def _layers(cfg: SparseLatentMoEConfig, pools, lp: Params, params: Params, index, bases, x, full,
            sliding):
    """Call ``index`` of the programs: a leading layer (``lp`` is its own
    parameters) or a period, whose layers run in their order: ``full(x, rows,
    keys, lp, rows_base, keys_base)`` -> (x, rows, keys, (live, selected)) and
    ``sliding(x, window, lp, base)`` -> (x, window, covered), then the layer's
    feed-forward. A pool's base moves on by the pool's units a layer of its
    kind; the scan's own slice of ``layers`` is left unused (``hybrid_ssm._layer_of``)."""
    rows, keys, window = pools
    rows_base, keys_base, window_base = bases
    lead = cfg.first_k_dense_replace
    if "w_gate" in lp:
        x, rows, keys, (live, selected) = full(x, rows, keys, lp, rows_base, keys_base)
        x, _ = _ffn(x, lp, cfg)
        return x, (rows, keys, window), _counts(None, live, selected)
    period = index - lead
    per = {kind: cfg.period.count(kind) for kind in (FULL, SLIDING)}
    blocks = rows.shape[0] // (lead + cfg.periods * per[FULL])  # a layer's units, in every pool
    done = {FULL: 0, SLIDING: 0}
    total = None
    for at, kind in enumerate(cfg.period):
        j = done[kind]
        done[kind] += 1
        one = _layer_of(params["layers"][kind], period, j)
        if kind == FULL:
            x, rows, keys, (live, selected) = full(
                x, rows, keys, one, rows_base + j * blocks, keys_base + j * blocks)
            mine = (live, selected, 0)
        else:
            x, window, covered = sliding(x, window, one, window_base + j * blocks)
            mine = (0, 0, covered)
        x, counts = _ffn(x, one, cfg, params["experts"][at], period)
        total = paged._add_counts(total, _counts(counts, *mine))
    return x, (rows, keys, window), total


def _decode_layer(cfg: SparseLatentMoEConfig, x, pools, lp, tables, lens, params, index, bases):
    """A leading layer or one PERIOD, one token a slot (``PagedModel.decode_layer``)."""
    return _layers(
        cfg, pools, lp, params, index, bases, x,
        lambda x, rows, keys, one, rb, kb: _full_step(
            cfg, x, rows, keys, one, tables + rb, tables + kb, lens),
        lambda x, window, one, base: _sliding_step(cfg, x, window, one, tables + base, lens))


def _chunk_layer(cfg: SparseLatentMoEConfig, x, pools, lp, table_rows, rows_at, offs, qpos, live,
                 params, index, bases, _slot_of):
    """A leading layer or one PERIOD over a chunk call's token axis (``PagedModel.chunk_layer``)."""
    return _layers(
        cfg, pools, lp, params, index, bases, x,
        lambda x, rows, keys, one, rb, kb: _full_chunk(
            cfg, x, rows, keys, one, table_rows + rb, table_rows + kb, rows_at + rb, rows_at + kb,
            offs, qpos, live),
        lambda x, window, one, base: _sliding_chunk(
            cfg, x, window, one, table_rows + base, rows_at + base, offs, qpos, live))


@paged.paged_model.register
def _(cfg: SparseLatentMoEConfig) -> paged.PagedModel:
    full = cfg.first_k_dense_replace + cfg.periods * cfg.period.count(FULL)
    return paged.PagedModel(
        pools={
            "rows": paged.Pool(row=(cfg.mixer(FULL).row_width,), layers=full),
            # The indexer's cache: one key a token a full layer, under the same table.
            "index": paged.Pool(row=(cfg.index_head_dim,), layers=full),
            # No leading layer is a sliding one.
            "window": paged.Pool(row=(cfg.mixer(SLIDING).row_width,),
                                 layers=cfg.periods * cfg.period.count(SLIDING), lead=0),
        },
        decode_layer=functools.partial(_decode_layer, cfg),
        chunk_layer=functools.partial(_chunk_layer, cfg),
        embed=_embed,
        unembed=_unembed,
    )
