"""Paged KV cache + batched decode for continuous-batching LLM serving.

The reference's LLM-serving story is vLLM running as Ray actors (SURVEY
§2.9); this framework serves natively on TPU, so the vLLM ideas —
block-paged KV memory and iteration-level (continuous) batching — are
re-designed for XLA's static-shape world:

- **Physical cache**: one pool of fixed-size blocks per layer,
  ``[L, num_blocks, block_size, kv_heads, head_dim]``. Block 0 is a
  reserved trash block that idle decode slots harmlessly write to, so
  the decode step never branches on slot liveness. The layer scans
  address it in place, as one flat pool (``_scan_layers``).
- **Block tables**: each decode slot owns a row ``[max_blocks_per_seq]``
  of physical block ids. Tables/lengths are tiny int32 arrays passed
  into the jitted step each iteration — the host allocator (see
  ``ray_tpu/serve/llm_engine.py``) mutates them between steps, the
  device program never sees allocation logic.
- **Decode step** (``paged_decode_step``): fixed ``[max_batch]`` token
  vector in, next tokens out. Per layer inside one ``lax.scan``:
  scatter the new K/V into (block, offset) slots via batched
  ``.at[].set``, then attend each slot's token to its blocks under a
  per-slot length mask (``ops/paged_attention.py``: on a TPU a kernel
  that reads the live blocks where they lie). Everything is
  static-shape; XLA sees one compiled program regardless of which
  slots are live.
- **Prefill** (``paged_prefill``): full-attention forward over a padded
  prompt bucket, scattering each layer's roped K/V into the slot's
  blocks. Buckets (powers of two) bound the number of compilations.

Sampling is on-device and per-slot (greedy where ``temps == 0``, else
temperature-scaled categorical), so one step moves only ``[b]`` int32s
host↔device.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

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


def init_paged_cache(cfg: TransformerConfig, pcfg: PagedConfig) -> PagedCache:
    shape = (
        cfg.n_layers,
        pcfg.num_blocks,
        pcfg.block_size,
        cfg.n_kv_heads,
        cfg.head_dim,
    )
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def _scan_layers(layer, x, layers: Params, cache: PagedCache):
    """Scan ``layer(x, ck, cv, lp, base) -> (x, ck, cv)`` over the layers,
    carrying the stacked cache as one flat pool ``[L*num_blocks, bs, KV,
    HD]`` (a bitcast, both ways). ``base`` is the layer's first block in
    it: a layer addresses its block ``b`` at ``base + b`` and updates the
    carry in place; no layer's pool is taken out of the stack or put back,
    and only ``base`` knows how layers are laid out. → (x, cache')."""
    shape = cache["k"].shape
    L, nb = shape[:2]
    flat = (L * nb,) + shape[2:]
    bases = jnp.arange(L, dtype=jnp.int32) * nb
    (x, ck, cv), _ = jax.lax.scan(
        lambda carry, xs: (layer(*carry, *xs), None),
        (x, cache["k"].reshape(flat), cache["v"].reshape(flat)), (layers, bases),
    )
    return x, {"k": ck.reshape(shape), "v": cv.reshape(shape)}


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


def paged_decode_step(
    params: Params,
    cfg: TransformerConfig,
    tokens: jax.Array,  # [b] int32 — the tokens AT positions ``lens``
    cache: PagedCache,
    tables: jax.Array,  # [b, W] int32
    lens: jax.Array,  # [b] int32
) -> Tuple[jax.Array, PagedCache]:
    """One decode iteration over all slots → (logits [b, V] fp32, cache').

    The stacked pool rides the layer scan as a carry that every layer
    updates in place (``_scan_layers``). Passing per-layer slices as scan
    xs/ys instead would stack a fresh pool copy as the scan output (and
    chained windows would hold several such copies): at 7B that is
    multiple GB of pure waste and an OOM on a 16 GB chip."""

    def layer(x, ck, cv, lp, base):
        return _paged_layer_step(x, lp, cfg, ck, cv, tables + base, lens)

    x = embed(params, tokens[:, None], cfg)
    x, cache = _scan_layers(layer, x, params["layers"], cache)
    return unembed(params, x, cfg)[:, 0], cache


def sample_tokens(logits: jax.Array, temps: jax.Array, key: jax.Array) -> jax.Array:
    """Per-slot sampling: greedy where temps == 0, else categorical at
    that slot's temperature. logits: [b, V] fp32; temps: [b] fp32."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temps > 0, temps, 1.0)[:, None]
    sampled = jax.random.categorical(key, logits / safe_t).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def paged_decode_loop(
    params: Params,
    cfg: TransformerConfig,
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
    up front). Returns ([n_steps, b] sampled tokens, cache').

    The window is UNROLLED (Python loop, n_steps is static), not a
    lax.scan: a scan carry holding the KV pool double-buffers it on top
    of the layer-scan's own double buffer (~4x pool HBM — an OOM at 7B
    on one chip), while the unrolled chain is straight-line dataflow
    whose intermediate caches XLA reuses in place. Compile time grows
    linearly in n_steps (~seconds for window 8)."""
    # A row whose table starts on the trash block holds no sequence (the
    # host points idle and still-prefilling slots there), yet every window
    # advances it: restart it, so that it reads and writes one block
    # however long it has idled.
    lens = jnp.where(tables[:, 0] == TRASH_BLOCK, 0, lens)
    seq = []
    for _ in range(n_steps):
        key, sub = jax.random.split(key)
        logits, cache = paged_decode_step(params, cfg, tokens, cache, tables, lens)
        tokens = sample_tokens(logits, temps, sub)
        lens = lens + 1
        seq.append(tokens)
    return jnp.stack(seq), cache


def paged_prefill(
    params: Params,
    cfg: TransformerConfig,
    tokens: jax.Array,  # [1, S] int32, S a multiple of block_size (padded)
    cache: PagedCache,
    block_row: jax.Array,  # [S // block_size] int32 physical block ids
    block_size: int,
) -> Tuple[jax.Array, PagedCache]:
    """Full-attention prefill of ONE slot, scattering K/V into its blocks.

    Returns (logits [S, V] fp32, cache'). Padded tail positions hold
    garbage K/V inside the last real block; they are masked by the
    length mask during decode and overwritten as the sequence grows.
    """
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
    params, cfg: TransformerConfig, tokens, cache, block_row, block_size: int,
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


def _attend_chunk(q, ck, cv, qpos, cfg: TransformerConfig):
    """q: [n, C, H, HD] chunk queries by tile; ck/cv: [n, m, KV, HD] each
    tile's gathered block view (its slot's prefix + the chunk, post-
    scatter); qpos: [n, C] absolute positions — attend over cache
    positions <= qpos (causal, prefix inclusive). Same f32 einsum/softmax
    math as ``reference_paged_attention``. → [n, C, H*HD]."""
    n, C, H, HD = q.shape
    KV = cfg.n_kv_heads
    G = H // KV
    qg = q.reshape(n, C, KV, G, HD)
    scores = jnp.einsum(
        "tckgd,tmkd->tckgm", qg.astype(jnp.float32), ck.astype(jnp.float32)
    ) * (HD**-0.5)
    m = ck.shape[1]
    valid = jnp.arange(m)[None, None, :] <= qpos[:, :, None]  # [n, C, m]
    scores = jnp.where(valid[:, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    og = jnp.einsum("tckgm,tmkd->tckgd", probs, cv.astype(jnp.float32))
    return og.reshape(n, C, H * HD).astype(q.dtype)


def paged_prefill_chunk(
    params: Params,
    cfg: TransformerConfig,
    tokens: jax.Array,  # [1, T] int32 — n tiles of T // n tokens (``chunk_tile``)
    cache: PagedCache,
    table_rows: jax.Array,  # [n, W] int32 — per tile, its slot's FULL block table
    chunk_row: jax.Array,  # [T // block_size] int32 — blocks receiving the tokens
    block_size: int,
    starts: jax.Array,  # [n] int32 — per tile, absolute position of its first token
    last_idx: jax.Array,  # [n] int32 — per segment, where on the token axis it ends
) -> Tuple[jax.Array, PagedCache]:
    """Prefill several slots' suffixes in ONE call: the token axis holds
    them one after another, each padded to whole tiles, and a tile covers
    positions ``starts[t] ..`` of the slot whose table is
    ``table_rows[t]``, attending to that slot's already-resident KV
    blocks (prefix-cache hits or earlier chunks) plus the chunk itself.

    This is the suffix/chunked counterpart of ``paged_prefill``: instead
    of full attention over the whole prompt it scatters the tokens' K/V
    into ``chunk_row``'s blocks and attends, tile by tile, through the
    gathered table under a causal position mask — so a prompt whose
    prefix is already in the cache only pays compute for the novel
    suffix. The projections, the FFN and the scatter run once over the
    whole axis, and only the rows ``last_idx`` names (one a segment, the
    prompt's final token where the segment is its final chunk) reach the
    head. Everything but the shapes is traced: one compilation per width
    T serves every mix of segments, a lone suffix or one chunk of a long
    prompt among them. A tile nobody uses points at the trash block.
    Returns (logits [n, V] fp32, cache')."""
    b, T = tokens.shape
    n, W = table_rows.shape
    C = T // n
    assert b == 1 and T % block_size == 0 and T % n == 0
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qpos = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # [n, C]
    positions = qpos.reshape(1, T)

    # Token j lands at (rows[j], offs[j]); padded tail rows point at the
    # trash block via chunk_row. Rows, not whole blocks: a scatter of ONE
    # block becomes an update-slice that copies the whole cache (v5e).
    rows = jnp.repeat(chunk_row, block_size)
    offs = jnp.tile(jnp.arange(block_size, dtype=jnp.int32), T // block_size)

    def layer(x, ck, cv, lp, base):
        h = rms_norm(x, lp["attn_norm"])
        q, k, v = project_qkv(h, lp, cfg, positions)
        ck = ck.at[rows + base, offs].set(k[0])
        cv = cv.at[rows + base, offs].set(v[0])
        ck_g = ck[table_rows + base].reshape(n, W * block_size, KV, HD)
        cv_g = cv[table_rows + base].reshape(n, W * block_size, KV, HD)
        o = _attend_chunk(q.reshape(n, C, H, HD), ck_g, cv_g, qpos, cfg)
        x = x + (o.reshape(1, T, H * HD) @ lp["wo"].astype(o.dtype))
        return mlp_block(x, lp, cfg), ck, cv

    x = embed(params, tokens, cfg)
    x, cache = _scan_layers(layer, x, params["layers"], cache)
    return unembed(params, x[:, last_idx], cfg)[0], cache


def prefill_chunk_and_sample(
    params, cfg: TransformerConfig, tokens, cache, table_rows, chunk_row,
    block_size: int, starts, last_idx, temps, key,
):
    """Chunk prefill + on-device sampling of one token a segment, at
    ``last_idx`` and that segment's temperature. A segment's token is only
    meaningful where it holds its prompt's FINAL position; earlier chunks,
    and the entries past the last segment, are never read by the host, so
    the extra samples cost no sync. → (tokens [n] int32, cache')."""
    logits, cache = paged_prefill_chunk(
        params, cfg, tokens, cache, table_rows, chunk_row, block_size, starts, last_idx
    )
    return sample_tokens(logits, temps, key), cache
