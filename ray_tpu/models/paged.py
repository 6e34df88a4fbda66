"""Paged KV cache + batched decode for continuous-batching LLM serving.

The reference's LLM-serving story is vLLM running as Ray actors (SURVEY
§2.9); this framework serves natively on TPU, so the vLLM ideas —
block-paged KV memory and iteration-level (continuous) batching — are
re-designed for XLA's static-shape world:

- **Physical cache**: pools stacked by layer, each declared by the model
  (``paged_model(cfg).pools``, a ``Pool`` each) with its OWN count of
  layers, its own row and its own unit. A pool of ``blocks`` is
  ``[layers, num_blocks, block_size, *row]``: tokens in fixed-size blocks
  that the host allocates. A pool of ``slots`` is ``[layers, max_batch,
  *row]``: ONE row a decode slot, slot ``i``'s at index ``i``, for state
  that does not grow with the sequence (a state-space layer's). The dense
  decoder has ``k`` and ``v`` with rows ``[kv_heads, head_dim]``; the
  latent-attention decoder (``models/latent_moe.py``) ONE pool whose row
  is the token's latent and rotary key; the hybrid state-space decoder
  (``models/hybrid_ssm.py``) ``k`` and ``v`` for its few attention layers
  and two slot pools for its many state-space ones; the power retention
  decoder (``models/power_retention.py``) ONE slot pool and no pool of
  blocks at all. Block 0 is a reserved
  trash block that idle decode slots harmlessly write to, so the decode
  step never branches on slot liveness for a pool of blocks. A pool of
  slots has no trash row: a recurrent update is not idempotent, so an idle
  or still-prefilling slot (``lens`` 0 all through a window) must be left
  alone by the layer itself. The layer scans address the pools in place, as
  flat pools (``_scan_layers``).
- **Models**: the programs below are one skeleton (embed, the layers over
  the carried pools, the head, sampling) around a model's two layer
  bodies, one token a slot and a chunk call's token axis
  (``PagedModel.decode_layer``, ``.chunk_layer``). ``paged_model``
  dispatches on the TYPE of the configuration object; no flag selects.
  Parameters are ``embed``, ``final_norm``, ``lm_head``, ``layers`` (one
  body, stacked, scanned: one layer, or one PERIOD of several layers of
  several kinds, each pool giving the body as many of its layers as it has
  per period) and, where a model has leading layers whose parameters have
  other shapes, ``lead`` (stacked, run one by one BEFORE the scan, each
  on one layer of every pool).
- **Block tables**: each decode slot owns a row ``[max_blocks_per_seq]``
  of physical block ids. Tables/lengths are tiny int32 arrays passed
  into the jitted step each iteration — the host allocator (see
  ``ray_tpu/serve/llm_engine.py``) mutates them between steps, the
  device program never sees allocation logic.
- **Decode step** (``paged_decode_step``): fixed ``[max_batch]`` token
  vector in, next tokens out. Per layer inside one ``lax.scan``:
  scatter the token's new row(s) into (block, offset) slots via batched
  ``.at[].set``, then attend each slot's token to its blocks under a
  per-slot length mask (``ops/paged_attention.py``,
  ``ops/latent_attention.py``: on a TPU kernels that read the live
  blocks where they lie). Everything is static-shape; XLA sees one
  compiled program regardless of which slots are live.
- **Prefill** (``paged_prefill``): the dense decoder's full-attention
  forward over a padded prompt bucket, scattering each layer's roped K/V
  into the slot's blocks; a model without such a program (``prefill`` is
  None) prefills a whole prompt as ONE tile of the chunk program.
  Buckets (powers of two) bound the number of compilations.
- **Counts**: a layer body may return int32 counts (the expert layer's
  pairs and touched experts); their sums ride the outputs the host reads
  anyway, as extra rows of the window's tokens and extra entries of a
  chunk call's, and a model without them compiles to what it always did.

Sampling is on-device and per-slot (greedy where ``temps == 0``, else
temperature-scaled categorical), so one step moves only ``[b]`` int32s
host↔device.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.transformer import (
    Params,
    TransformerConfig,
    attention_block,
    embed,
    mlp_block,
    project_qkv,
    rms_norm,
    unembed,
)
from ray_tpu.ops.paged_attention import paged_attention

PagedCache = Dict[str, jax.Array]

TRASH_BLOCK = 0  # physical block 0 is the write target for idle slots


@dataclasses.dataclass(frozen=True)
class PagedConfig:
    """Shape of the paged cache; all fields are compile-time constants."""

    block_size: int = 16
    num_blocks: int = 64  # physical pool size, incl. the trash block
    max_batch: int = 8  # decode slots
    max_blocks_per_seq: int = 8  # block-table width W

    @property
    def max_seq_len(self) -> int:
        return self.block_size * self.max_blocks_per_seq

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1  # minus trash


class Pool(NamedTuple):
    """One pool of a model's cache: ``[layers, units, *row]`` where a unit is
    a block of tokens (``[num_blocks, block_size]``) or a decode slot
    (``[max_batch]``)."""

    row: Tuple[int, ...]  # what one token of a block, or one slot, holds
    layers: int  # layers that keep such rows (leading ones included)
    unit: str = "blocks"  # or "slots"
    dtype: Optional[Any] = None  # None: the configuration's
    lead: Optional[int] = None  # how many of them are leading layers (None: every leading layer)

    def units(self, pcfg: "PagedConfig") -> Tuple[int, ...]:
        return (pcfg.max_batch,) if self.unit == "slots" else (pcfg.num_blocks, pcfg.block_size)


class PagedModel(NamedTuple):
    """What a model gives the paged programs (``paged_model(cfg)``)."""

    pools: Dict[str, Pool]
    # One token a slot: (x [b, 1, d], pools, lp, tables [b, W], lens [b],
    # params, index, bases) -> (x, pools, counts). ``pools`` is a tuple in
    # the declared order, each ANY flat pool ``[layers * units, ..]``, and
    # ``bases`` a tuple beside it: the first unit (block, or slot row) of the
    # first of this call's layers in that pool, the next layer's ``units``
    # further. A layer addresses block ``tables[i, j]`` at ``bases[k] +
    # tables[i, j]`` and slot ``i``'s row at ``bases[k] + i``. A model with no
    # pool of blocks is handed ``tables`` (and a chunk call's ``table_rows`` and
    # ``rows_at``) with NO column: there is no block to address, and the row a
    # slot is all it reads. ``lp`` is the
    # body's own slice of ``lead`` or ``layers``; ``params`` the whole tree
    # and ``index`` the number of the call (leading layers, then scanned
    # bodies), for what a model keeps outside the scanned stack (weights a
    # kernel must be handed whole, not as a slice that would be copied);
    # ``counts`` is None or an int32 vector that the programs sum over layers
    # and steps. A slot whose ``lens`` is 0 holds no sequence: its rows of a
    # slot pool must come back as they went in.
    decode_layer: Callable
    # A chunk call's token axis: (x [1, T, d], pools, lp, table_rows [n, W],
    # rows_at [T], offs [T], qpos [n, C], live [n], params, index, bases,
    # slot_of [n]) -> (x, pools, counts): token j's row lands at (rows_at[j],
    # offs[j]) (block ids as ``tables``' are, less the base); tile t attends
    # through table_rows[t], its first live[t] tokens are real (what a layer
    # gives for the others nothing reads, so it may skip them) and it belongs
    # to decode slot slot_of[t] (``max_batch`` where to none): a segment's
    # tiles lie one after another, begin at qpos[t, 0] (0: from nothing; above:
    # from what the slot's rows hold) and leave the slot's rows as the
    # segment's last real token leaves them.
    chunk_layer: Callable
    # (params, tokens, cache, block_row, block_size) -> (logits [S, V], cache),
    # or None: a whole prompt is then tiles of the chunk program.
    prefill: Optional[Callable] = None
    # (params, tokens, cfg) -> x and (params, x, cfg) -> float32 logits, where
    # they are not ``models/transformer.py``'s (a scaled embedding, a tied head).
    embed: Callable = embed
    unembed: Callable = unembed


@functools.singledispatch
def paged_model(cfg) -> PagedModel:
    """The model behind a configuration object, by the object's type."""
    raise TypeError(f"no paged model is registered for {type(cfg).__name__}")


def _add_counts(total, counts):
    """Sum of two layers' (or steps') counts, either of which may be None."""
    if counts is None or total is None:
        return counts if total is None else total
    return total + counts


def init_paged_cache(cfg, pcfg: PagedConfig) -> PagedCache:
    return {name: jnp.zeros((pool.layers,) + pool.units(pcfg) + pool.row, pool.dtype or cfg.dtype)
            for name, pool in paged_model(cfg).pools.items()}


def slot_pools(cfg) -> Tuple[str, ...]:
    """The model's pools that hold one row a decode slot (recurrent state)."""
    return tuple(name for name, pool in paged_model(cfg).pools.items() if pool.unit == "slots")


def block_pools(cfg) -> Tuple[str, ...]:
    """The model's pools that hold blocks of tokens. None: every layer keeps its
    past by slot, and the block tables have no column (``PagedModel``)."""
    return tuple(name for name, pool in paged_model(cfg).pools.items() if pool.unit == "blocks")


def _scan_layers(layer, x, params: Params, cache: PagedCache, declared: Dict[str, Pool]):
    """Run ``layer(x, pools, lp, bases, index) -> (x, pools, counts)`` over the
    layers (``pools`` in the order of ``declared``, the model's own: a
    dictionary that crossed ``jit`` comes back sorted), carrying each stacked
    pool ``[L, units, ..]`` as one flat pool ``[L*units, ..]`` (a bitcast, both
    ways; ``units`` is the pool's own: its blocks, or the slots). ``bases[k]``
    is the first unit of the call's
    first layer in pool ``k``: a layer addresses its unit ``u`` at ``base +
    u`` and updates the carry in place; no layer's pool is taken out of the
    stack or put back, and only the bases know how layers are laid out.
    Leading layers (``params["lead"]``, their own parameter tree, stacked)
    run one by one before the scan over ``params["layers"]``, each on one
    layer of every pool (of every pool that has leading layers: ``Pool.lead``
    0 is a pool whose kind of layer is no leading one, and the base such a
    layer is handed for it is nobody's); a scanned body follows on as many
    layers of each pool as the pool has left per body (one, for a model of
    one kind of layer; a period's count of that kind, for a model of several).
    → (x, cache', summed counts or None)."""
    shapes = {name: cache[name].shape for name in declared}
    pools = tuple(cache[name].reshape((-1,) + shapes[name][2:]) for name in declared)
    lead = params.get("lead")
    n_lead = 0 if lead is None else jax.tree.leaves(lead)[0].shape[0]
    n_bodies = jax.tree.leaves(params["layers"])[0].shape[0]
    units = tuple(shape[1] for shape in shapes.values())
    # Leading layers of each pool, and its layers that one scanned body runs.
    leads = tuple(n_lead if pool.lead is None else pool.lead for pool in declared.values())
    per_body = tuple((shape[0] - k) // n_bodies for k, shape in zip(leads, shapes.values()))
    assert all(k + n * n_bodies == shape[0] for k, n, shape in zip(leads, per_body, shapes.values()))
    total = None
    for j in range(n_lead):
        x, pools, counts = layer(
            x, pools, jax.tree.map(lambda a: a[j], lead), tuple(j * u for u in units), j)
        total = _add_counts(total, counts)

    def body(carry, xs):
        lp, j = xs
        bases = tuple((k + j * n) * u for k, n, u in zip(leads, per_body, units))
        x, pools, counts = layer(*carry, lp, bases, n_lead + j)
        return (x, pools), counts

    (x, pools), counts = jax.lax.scan(
        body, (x, pools), (params["layers"], jnp.arange(n_bodies, dtype=jnp.int32)))
    total = _add_counts(total, None if counts is None else counts.sum(0))
    return x, {name: pool.reshape(shapes[name]) for name, pool in zip(shapes, pools)}, total


def _paged_layer_step(x, lp: Params, cfg: TransformerConfig, ck, cv, tables, lens):
    """One layer, one token per slot.

    x: [b, 1, d]; ck/cv: [blocks, bs, KV, HD], any pool that holds this
    layer's blocks; tables: [b, W] block ids into it; lens: [b] write
    positions.
    """
    bs = ck.shape[1]
    h = rms_norm(x, lp["attn_norm"])
    q, k, v = project_qkv(h, lp, cfg, lens[:, None])
    # The scopes below reach the profiler as the ``tf_op`` of each fused
    # operation (a fusion's own name is made from its operations).
    with jax.named_scope("paged.scatter"):
        # Scatter the new K/V at (block, offset) per slot. Idle slots are
        # pointed at the trash block by the host allocator.
        phys = jnp.take_along_axis(tables, (lens // bs)[:, None], axis=1)[:, 0]  # [b]
        off = lens % bs
        ck = ck.at[phys, off].set(k[:, 0])
        cv = cv.at[phys, off].set(v[:, 0])
    # After the scatter, so the token just written attends to itself.
    o = paged_attention(q[:, 0], ck, cv, tables, lens)
    x = x + (o @ lp["wo"].astype(o.dtype))[:, None, :]
    with jax.named_scope("paged.mlp"):
        x = mlp_block(x, lp, cfg)
    return x, ck, cv


def _decode_step(params: Params, cfg, tokens, cache: PagedCache, tables, lens):
    """``paged_decode_step`` with the layers' summed counts (or None) third."""
    model = paged_model(cfg)

    def layer(x, pools, lp, bases, index):
        return model.decode_layer(x, pools, lp, tables, lens, params, index, bases)

    x = model.embed(params, tokens[:, None], cfg)
    x, cache, counts = _scan_layers(layer, x, params, cache, model.pools)
    return model.unembed(params, x, cfg)[:, 0], cache, counts


def paged_decode_step(
    params: Params,
    cfg,
    tokens: jax.Array,  # [b] int32 — the tokens AT positions ``lens``
    cache: PagedCache,
    tables: jax.Array,  # [b, W] int32
    lens: jax.Array,  # [b] int32
) -> Tuple[jax.Array, PagedCache]:
    """One decode iteration over all slots → (logits [b, V] fp32, cache').

    The stacked pools ride the layer scan as a carry that every layer
    updates in place (``_scan_layers``). Passing per-layer slices as scan
    xs/ys instead would stack a fresh pool copy as the scan output (and
    chained windows would hold several such copies): at 7B that is
    multiple GB of pure waste and an OOM on a 16 GB chip."""
    logits, cache, _ = _decode_step(params, cfg, tokens, cache, tables, lens)
    return logits, cache


def _with_counts(out: jax.Array, counts) -> jax.Array:
    """``out`` (int32, tokens on its leading axis) with the counts behind it,
    one more leading entry each: they reach the host on the transfer that
    brings the tokens. None: ``out`` as it is."""
    if counts is None:
        return out
    extra = jnp.broadcast_to(
        counts.astype(out.dtype).reshape((-1,) + (1,) * (out.ndim - 1)),
        counts.shape[:1] + out.shape[1:])
    return jnp.concatenate([out, extra])


def sample_tokens(logits: jax.Array, temps: jax.Array, key: jax.Array) -> jax.Array:
    """Per-slot sampling: greedy where temps == 0, else categorical at
    that slot's temperature. logits: [b, V] fp32; temps: [b] fp32."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temps > 0, temps, 1.0)[:, None]
    sampled = jax.random.categorical(key, logits / safe_t).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def paged_decode_loop(
    params: Params,
    cfg,
    tokens: jax.Array,  # [b] int32 — tokens AT positions ``lens``
    cache: PagedCache,
    tables: jax.Array,  # [b, W] — FIXED across the window
    lens: jax.Array,  # [b]
    temps: jax.Array,  # [b]
    key: jax.Array,
    n_steps: int,
) -> Tuple[jax.Array, PagedCache]:
    """``n_steps`` decode iterations in ONE device program (lax.scan),
    feeding each step's sampled tokens to the next — the host syncs once
    per window instead of per token, amortizing dispatch/transfer
    latency (decisive when the host↔device link is slow; still a win on
    local PCIe). Requires every slot's block table to cover positions
    ``lens .. lens+n_steps-1`` (the engine allocates the window horizon
    up front). Returns ([n_steps, b] sampled tokens, cache'); where the
    model's layers count, the counts summed over the window follow the
    tokens as further rows, one a count (``_with_counts``).

    The window is UNROLLED (Python loop, n_steps is static), not a
    lax.scan: a scan carry holding the KV pool double-buffers it on top
    of the layer-scan's own double buffer (~4x pool HBM — an OOM at 7B
    on one chip), while the unrolled chain is straight-line dataflow
    whose intermediate caches XLA reuses in place. Compile time grows
    linearly in n_steps (~seconds for window 8)."""
    # A row whose table starts on the trash block holds no sequence (the
    # host points idle and still-prefilling slots there), yet every window
    # would advance it: hold it at 0 all through the window, so that it reads
    # and writes one position of the trash block however long it has idled,
    # and a layer that keeps state by SLOT knows to leave that slot's alone
    # (a live row's ``lens`` is its prompt's length or more, never 0). A
    # model with no pool of blocks has a table of no column: there the host's
    # 0 is all that says a row is idle, and whoever chains windows keeps it 0.
    idle = tables[:, 0] == TRASH_BLOCK if tables.shape[1] else lens == 0
    lens = jnp.where(idle, 0, lens)
    seq, total = [], None
    for _ in range(n_steps):
        key, sub = jax.random.split(key)
        logits, cache, counts = _decode_step(params, cfg, tokens, cache, tables, lens)
        tokens = sample_tokens(logits, temps, sub)
        lens = jnp.where(idle, 0, lens + 1)
        seq.append(tokens)
        total = _add_counts(total, counts)
    return _with_counts(jnp.stack(seq), total), cache


def paged_prefill(
    params: Params,
    cfg,
    tokens: jax.Array,  # [1, S] int32, S a multiple of block_size (padded)
    cache: PagedCache,
    block_row: jax.Array,  # [S // block_size] int32 physical block ids
    block_size: int,
) -> Tuple[jax.Array, PagedCache]:
    """Prefill of ONE slot from position 0, scattering its rows into its
    blocks: the model's own whole-prompt program, else one tile of the chunk
    program whose table is the prompt's own blocks.

    Returns (logits [S, V] fp32, cache'). Padded tail positions hold
    garbage inside the last real block; they are masked by the length
    mask during decode and overwritten as the sequence grows.
    """
    model = paged_model(cfg)
    if model.prefill is not None:
        return model.prefill(params, tokens, cache, block_row, block_size)
    if slot_pools(cfg):
        # Its padding would enter the slot's state, and no slot is named here.
        raise ValueError("a model that keeps state by slot prefills through paged_prefill_chunk, "
                         "which is told each tile's slot and its real tokens")
    S = tokens.shape[1]
    logits, cache, _ = _prefill_chunk(
        params, cfg, tokens, cache, block_row[None, :], block_row, block_size,
        jnp.zeros((1,), jnp.int32), jnp.arange(S, dtype=jnp.int32),
        jnp.full((1,), S, jnp.int32), jnp.zeros((1,), jnp.int32))
    return logits, cache


def _dense_prefill(cfg: TransformerConfig, params: Params, tokens, cache: PagedCache,
                   block_row, block_size: int):
    """The dense decoder's whole-prompt program: full (flash) attention over
    the padded bucket, every layer's roped K/V scattered after the scan."""
    b, S = tokens.shape
    assert b == 1 and S % block_size == 0
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    h = embed(params, tokens, cfg)

    def body(carry, lp):
        x, k, v = attention_block(carry, lp, cfg, positions, return_kv=True)
        x = mlp_block(x, lp, cfg)
        return x, (k, v)

    h, (ks, vs) = jax.lax.scan(body, h, params["layers"])
    logits = unembed(params, h, cfg)[0]
    # ks: [L, 1, S, KV, HD] → [L, S//bs, bs, KV, HD], scatter rows into
    # the pool at the slot's block ids (batched index scatter on axis 1).
    L = cfg.n_layers
    KV, HD = cfg.n_kv_heads, cfg.head_dim
    nb = S // block_size
    ks = ks.reshape(L, nb, block_size, KV, HD)
    vs = vs.reshape(L, nb, block_size, KV, HD)
    cache = {
        "k": cache["k"].at[:, block_row].set(ks),
        "v": cache["v"].at[:, block_row].set(vs),
    }
    return logits, cache


def prefill_and_sample(
    params, cfg, tokens, cache, block_row, block_size: int,
    real_len, temp, key,
):
    """Prefill one slot and sample its first generated token on-device.

    real_len: scalar int32 — the unpadded prompt length; the sampled
    token continues from position real_len - 1.
    """
    logits, cache = paged_prefill(params, cfg, tokens, cache, block_row, block_size)
    last = jax.lax.dynamic_index_in_dim(logits, real_len - 1, axis=0, keepdims=False)
    tok = sample_tokens(last[None, :], temp[None], key)[0]
    return tok, cache


def chunk_tile(width: int, block_size: int) -> int:
    """Tokens in one tile of a chunk call ``width`` wide: four blocks, or
    the largest share of four that divides the width (a width under four
    blocks is one tile; 1,568 = 49 x 32). A tile is the unit of attention:
    its queries go against ONE slot's table, so a slot's suffix is padded
    to tiles, and a wider tile pads more while a narrower one gathers a
    table for fewer queries."""
    return math.gcd(width, 4 * block_size)


def _attend_chunk(q, ck, cv, qpos, scale=None):
    """q: [n, C, H, HD] chunk queries by tile; ck/cv: [n, m, KV, HD] each
    tile's gathered block view (its slot's prefix + the chunk, post-
    scatter); qpos: [n, C] absolute positions — attend over cache
    positions <= qpos (causal, prefix inclusive). Same f32 einsum/softmax
    math as ``reference_paged_attention``. → [n, C, H*HD]."""
    n, C, H, HD = q.shape
    KV = ck.shape[2]
    G = H // KV
    qg = q.reshape(n, C, KV, G, HD)
    scores = jnp.einsum(
        "tckgd,tmkd->tckgm", qg.astype(jnp.float32), ck.astype(jnp.float32)
    ) * (HD**-0.5 if scale is None else scale)
    m = ck.shape[1]
    valid = jnp.arange(m)[None, None, :] <= qpos[:, :, None]  # [n, C, m]
    scores = jnp.where(valid[:, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    og = jnp.einsum("tckgm,tmkd->tckgd", probs, cv.astype(jnp.float32))
    return og.reshape(n, C, H * HD).astype(q.dtype)


def _dense_decode_layer(cfg: TransformerConfig, x, pools, lp: Params, tables, lens, _params,
                        _index, bases):
    """The dense decoder's layer, one token a slot (``PagedModel.decode_layer``)."""
    x, ck, cv = _paged_layer_step(x, lp, cfg, *pools, tables + bases[0], lens)
    return x, (ck, cv), None


def _dense_chunk_layer(cfg: TransformerConfig, x, pools, lp: Params, table_rows, rows_at, offs,
                       qpos, _live, _params, _index, bases, _slot_of):
    """The dense decoder's layer over a chunk call's token axis (``PagedModel
    .chunk_layer``): K/V of the tokens into their blocks, then every tile
    through its slot's gathered table. Padded queries are computed like real
    ones: a tile is a gather and two products whose cost a mask would not cut."""
    ck, cv = pools
    table_rows, rows_at = table_rows + bases[0], rows_at + bases[0]
    n, C = qpos.shape
    W, bs = table_rows.shape[1], ck.shape[1]
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, lp["attn_norm"])
    q, k, v = project_qkv(h, lp, cfg, qpos.reshape(1, n * C))
    ck = ck.at[rows_at, offs].set(k[0])
    cv = cv.at[rows_at, offs].set(v[0])
    ck_g = ck[table_rows].reshape(n, W * bs, KV, HD)
    cv_g = cv[table_rows].reshape(n, W * bs, KV, HD)
    o = _attend_chunk(q.reshape(n, C, H, HD), ck_g, cv_g, qpos)
    x = x + (o.reshape(1, n * C, H * HD) @ lp["wo"].astype(o.dtype))
    return mlp_block(x, lp, cfg), (ck, cv), None


def _prefill_chunk(params: Params, cfg, tokens, cache: PagedCache, table_rows, chunk_row,
                   block_size: int, starts, last_idx, live, slot_of):
    """``paged_prefill_chunk`` with the layers' summed counts (or None) third."""
    b, T = tokens.shape
    n = table_rows.shape[0]
    C = T // n
    assert b == 1 and T % block_size == 0 and T % n == 0
    qpos = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # [n, C]

    # Token j lands at (rows[j], offs[j]); padded tail rows point at the
    # trash block via chunk_row. Rows, not whole blocks: a scatter of ONE
    # block becomes an update-slice that copies the whole cache (v5e).
    rows = jnp.repeat(chunk_row, block_size)
    offs = jnp.tile(jnp.arange(block_size, dtype=jnp.int32), T // block_size)
    model = paged_model(cfg)

    def layer(x, pools, lp, bases, index):
        return model.chunk_layer(
            x, pools, lp, table_rows, rows, offs, qpos, live, params, index, bases, slot_of)

    x = model.embed(params, tokens, cfg)
    x, cache, counts = _scan_layers(layer, x, params, cache, model.pools)
    return model.unembed(params, x[:, last_idx], cfg)[0], cache, counts


def paged_prefill_chunk(
    params: Params,
    cfg,
    tokens: jax.Array,  # [1, T] int32 — n tiles of T // n tokens (``chunk_tile``)
    cache: PagedCache,
    table_rows: jax.Array,  # [n, W] int32 — per tile, its slot's FULL block table
    chunk_row: jax.Array,  # [T // block_size] int32 — blocks receiving the tokens
    block_size: int,
    starts: jax.Array,  # [n] int32 — per tile, absolute position of its first token
    last_idx: jax.Array,  # [n] int32 — per segment, where on the token axis it ends
    live: Optional[jax.Array] = None,  # [n] int32 — per tile, its real tokens (None: all)
    slot_of: Optional[jax.Array] = None,  # [n] int32 — per tile, its decode slot (None: none's)
) -> Tuple[jax.Array, PagedCache]:
    """Prefill several slots' suffixes in ONE call: the token axis holds
    them one after another, each padded to whole tiles, and a tile covers
    positions ``starts[t] ..`` of the slot whose table is
    ``table_rows[t]``, attending to that slot's already-resident
    blocks (prefix-cache hits or earlier chunks) plus the chunk itself.

    This is the suffix/chunked counterpart of ``paged_prefill``: instead
    of full attention over the whole prompt it scatters the tokens' rows
    into ``chunk_row``'s blocks and attends, tile by tile, through the
    slot's table under a causal position mask — so a prompt whose
    prefix is already in the cache only pays compute for the novel
    suffix. The projections, the FFN and the scatter run once over the
    whole axis, and only the rows ``last_idx`` names (one a segment, the
    prompt's final token where the segment is its final chunk) reach the
    head. Everything but the shapes is traced: one compilation per width
    T serves every mix of segments, a lone suffix or one chunk of a long
    prompt among them. A tile nobody uses points at the trash block and has
    ``live`` 0; a segment's last tile holds its remainder, and a model may
    leave the padding behind it uncomputed (``PagedModel.chunk_layer``).
    ``slot_of`` matters to a model that keeps state by slot, and to no other:
    a tile's state begins from nothing (``starts`` 0), from the tile before
    it (the same slot's) or from the slot's stored rows, and the segment's
    last tile leaves them as its last real token does. A slot number past
    the last slot is nobody's: nothing is read or stored for it.
    Returns (logits [n, V] fp32, cache')."""
    n = table_rows.shape[0]
    if live is None:
        live = jnp.full((n,), tokens.shape[1] // n, jnp.int32)
    if slot_of is None:
        if slot_pools(cfg):
            raise ValueError("this model keeps state by slot: say which slot each tile is of")
        slot_of = jnp.zeros((n,), jnp.int32)
    logits, cache, _ = _prefill_chunk(
        params, cfg, tokens, cache, table_rows, chunk_row, block_size, starts, last_idx, live,
        slot_of)
    return logits, cache


def prefill_chunk_and_sample(
    params, cfg, tokens, cache, table_rows, chunk_row,
    block_size: int, starts, last_idx, live, slot_of, temps, key,
):
    """Chunk prefill + on-device sampling of one token a segment, at
    ``last_idx`` and that segment's temperature. A segment's token is only
    meaningful where it holds its prompt's FINAL position; earlier chunks,
    and the entries past the last segment, are never read by the host, so
    the extra samples cost no sync. → (tokens [n] int32, cache'); where the
    model's layers count, the call's counts follow the tokens (``_with_counts``)."""
    logits, cache, counts = _prefill_chunk(
        params, cfg, tokens, cache, table_rows, chunk_row, block_size, starts, last_idx, live,
        slot_of)
    return _with_counts(sample_tokens(logits, temps, key), counts), cache


@paged_model.register
def _(cfg: TransformerConfig) -> PagedModel:
    kv = Pool(row=(cfg.n_kv_heads, cfg.head_dim), layers=cfg.n_layers)
    return PagedModel(
        pools={"k": kv, "v": kv},
        decode_layer=functools.partial(_dense_decode_layer, cfg),
        chunk_layer=functools.partial(_dense_chunk_layer, cfg),
        prefill=functools.partial(_dense_prefill, cfg),
    )
