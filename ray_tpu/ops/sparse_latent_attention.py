"""Latent attention over a block-paged cache that does NOT walk a slot's table
from its first block: attention over a LIST of cached tokens that a learned
indexer chooses (the full layers of ``models/sparse_latent_moe.py``), and
attention over a sliding window (its other layers). Plain ``jax.numpy``
everywhere; every part stands under a scope of its own (``sparse.score``,
``sparse.select``, ``sparse.gather``, ``sparse.attend``, ``window.attend``).

**Selection.** Beside the pool of latent rows (``ops/latent_attention.py`` says
what a row is) the full layers keep a pool of INDEX KEYS, one short vector a
cached token, under the same block table. For a query at position ``t`` with
index queries ``qi`` [Hi, Di] and head weights ``w`` [Hi] the score of cached
token ``s <= t`` is ``I(t, s) = sum_j w_j relu(qi_j . k_s)`` (float32); the
query attends, in the absorbed form, to the ``k`` cached tokens of largest
score and to no other (to all of ``0..t`` while there are at most ``k``).
The selection is exact, and what it returns is each chosen token's PLACE IN THE
POOL of rows laid flat (``block * bs + offset``), not its position in the
sequence: ``select`` is ONE sort whose payload is made from the slot's table by
a broadcast before it, so no position is ever looked up in a table afterwards
and the gather is one flat index.

- Decode, one query a slot: ``index_scores`` (the slot's index keys through
  its table, positions past ``lens`` at ``-inf``), ``select``, ``gather_rows``,
  ``attend_rows``.
- A chunk call, a tile of queries a slot: ``sparse_chunk_attention``. Each
  QUERY has its own list. A tile's scores are ``[queries, keys]`` in float32,
  made ``_KV_ROWS`` keys at a time up to the tile's last real query (the
  ``[queries, heads, keys]`` of a step never leave the step); the lists are
  taken and read ``_QUERIES_PER_STEP`` queries at a time (a group's gathered
  rows are ``[queries, k, row]``: 168 MB at 64 x 2,048 x 640 in bfloat16), and
  a group with no real query does nothing and gives zeros.

**Window.** ``window_attention`` (decode) and ``window_chunk_attention`` read
only the blocks of a slot's table that hold positions ``t - window + 1 .. t``
(a static count of blocks from a start that depends on ``t``) and mask to the
window exactly.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG = -1e30  # a masked score of the attention's softmax
# Cached keys a tile of queries is scored against at once.
_KV_ROWS = 512
# Queries whose lists are taken and read at once.
_QUERIES_PER_STEP = 64


def _weighted_relu(qi, w, keys):
    """``sum_j w_j relu(qi_j . k)``: qi [.., q, Hi, Di], w [.., q, Hi] float32,
    keys [.., m, Di] -> [.., q, m] float32. Products in the operands' dtype,
    accumulated in float32."""
    s = jnp.einsum("...qhd,...md->...qhm", qi, keys, preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w[..., None].astype(jnp.float32), axis=-2)


@jax.named_scope("sparse.score")
def index_scores(qi, w, keys_pool, tables, lens):
    """Decode: qi [b, Hi, Di] one token's index queries a slot; w [b, Hi];
    keys_pool [P, bs, Di] a flat pool of index keys; tables [b, W]; lens [b]
    the position each slot's key was just written at -> [b, W * bs] float32,
    ``-inf`` past ``lens``."""
    b, W = tables.shape
    bs, Di = keys_pool.shape[1:]
    keys = keys_pool[tables].reshape(b, W * bs, Di)
    s = _weighted_relu(qi[:, None], w[:, None], keys)[:, 0]
    return jnp.where(jnp.arange(W * bs)[None, :] <= lens[:, None], s, -jnp.inf)


def _descending_key(scores):
    """float32 -> int32 whose ASCENDING order is the scores' descending one
    (``-0.0`` with ``0.0``; ``-inf`` last of all numbers): a sort of integers
    compares no float."""
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.int32)
    return ~jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


@jax.named_scope("sparse.select")
def select(scores, tables, bs: int, blocks: int, k: int):
    """scores [.., m] float32 over a sequence's positions (``-inf``: no such
    token); tables [.., m // bs] (or [m // bs]: one table for every list) the
    sequence's block ids in a pool of ``blocks`` blocks of ``bs`` -> (ids [.., k]
    int32: the places ``block * bs + offset``, in the pool laid flat, of the k
    largest scores, largest first and equal scores in the order of their
    positions, exactly as ``jax.lax.top_k`` orders positions; valid [.., k]:
    whether the place holds a token).

    One sort, which carries the places. ``top_k`` is this sort with the position
    as its second key; here the second key is ``position << bits | block`` while
    that fits 31 bits (the same order, and the block comes out with it), else
    the sort is stable with ``block * bs + offset`` for a payload (a third
    operand on a TPU: slower, as exact)."""
    m = scores.shape[-1]
    k = min(k, m)
    key = _descending_key(scores)
    pos = jnp.arange(m, dtype=jnp.int32)
    block = jnp.repeat(tables, bs, axis=-1)
    bits = (blocks - 1).bit_length()
    if (m - 1).bit_length() + bits <= 31:
        key, packed = jax.lax.sort((key, jnp.broadcast_to(pos << bits | block, scores.shape)),
                                   dimension=-1, num_keys=2, is_stable=False)
        packed = packed[..., :k]
        ids = (packed & ((1 << bits) - 1)) * bs + (packed >> bits) % bs
    else:
        key, ids = jax.lax.sort((key, jnp.broadcast_to(block * bs + pos % bs, scores.shape)),
                                dimension=-1, num_keys=1, is_stable=True)
        ids = ids[..., :k]
    return ids, key[..., :k] < _descending_key(jnp.float32(-jnp.inf))


@jax.named_scope("sparse.gather")
def gather_rows(pool, ids):
    """pool [P, bs, R]; ids [.., k] places in the pool laid flat (``select``)
    -> their cached rows [.., k, R]."""
    return pool.reshape(-1, pool.shape[-1])[ids]


@jax.named_scope("sparse.attend")
def attend_rows(q, rows, valid, scale: float, rank: int):
    """q [b, H, R] absorbed queries; rows [b, k, R] the rows each attends to;
    valid [b, k] -> [b, H, rank]: per head the softmax-weighted sum of the valid
    rows' first ``rank`` numbers. Scores, softmax and the sum in float32."""
    s = jnp.einsum("bhr,bkr->bhk", q, rows, preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(valid[:, None, :], s, NEG), axis=-1)
    return jnp.einsum("bhk,bkc->bhc", p.astype(rows.dtype), rows[..., :rank],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def sparse_attention(q, qi, w, rows_pool, keys_pool, tables, key_tables, lens, scale: float,
                     rank: int, topk: int):
    """Decode, the four parts in their order: q [b, H, R], qi [b, Hi, Di], w [b,
    Hi]; ``tables`` addresses ``rows_pool`` and ``key_tables`` the same blocks in
    ``keys_pool``; lens [b] -> [b, H, rank]."""
    ids, valid = select(index_scores(qi, w, keys_pool, key_tables, lens), tables,
                        rows_pool.shape[1], rows_pool.shape[0], topk)
    return attend_rows(q, gather_rows(rows_pool, ids), valid, scale, rank)


def sparse_chunk_attention(q, qi, w, rows_pool, keys_pool, tables, key_tables, qpos, live,
                           scale: float, rank: int, topk: int):
    """A chunk call: q [n, C, H, R] absorbed queries by tile; qi [n, C, Hi, Di];
    w [n, C, Hi]; tables / key_tables [n, W] each tile's slot's block ids in the
    two pools (the chunk's own rows and keys are in them already); qpos [n, C]
    absolute positions, CONSECUTIVE within a tile; live [n] how many of a
    tile's queries, its first ones, are real. Each query attends to the
    ``topk`` cached positions <= its own of largest index score. -> [n, C, H,
    rank] in ``q.dtype``: zeros from ``live`` rounded up to whole groups on."""
    n, C, H, R = q.shape
    bs = rows_pool.shape[1]
    W = tables.shape[1]
    per = max(1, min(_KV_ROWS // bs, W))  # blocks a step of the scores
    kv = per * bs
    pad = ((0, 0), (0, -W % per))  # the trash block, never scored
    tables, key_tables = jnp.pad(tables, pad), jnp.pad(key_tables, pad)
    m = tables.shape[1] * bs
    g = math.gcd(C, _QUERIES_PER_STEP)

    def tile(args):
        qt, qit, wt, row, key_row, pos, real = args

        def score_step(j, scores):
            blocks = jax.lax.dynamic_slice_in_dim(key_row, j * per, per)
            s = _weighted_relu(qit, wt, keys_pool[blocks].reshape(kv, -1))  # [C, kv]
            s = jnp.where(j * kv + jnp.arange(kv)[None, :] <= pos[:, None], s, -jnp.inf)
            return jax.lax.dynamic_update_slice_in_dim(scores, s, j * kv, axis=1)

        with jax.named_scope("sparse.score"):
            # Up to the last real query's position; no step where there is none.
            n_steps = jnp.where(real > 0, (pos[0] + real - 1) // kv + 1, 0)
            scores = jax.lax.fori_loop(
                0, n_steps, score_step, jnp.full((C, m), -jnp.inf, jnp.float32))

        def group(at):
            def attend():
                chosen, valid = select(jax.lax.dynamic_slice_in_dim(scores, at, g), row, bs,
                                       rows_pool.shape[0], topk)
                got = gather_rows(rows_pool, chosen)  # [g, k, R]
                return attend_rows(jax.lax.dynamic_slice_in_dim(qt, at, g), got, valid, scale, rank)

            return jax.lax.cond(at < real, attend, lambda: jnp.zeros((g, H, rank), q.dtype))

        return jax.lax.map(group, jnp.arange(0, C, g)).reshape(C, H, rank)

    return jax.lax.map(tile, (q, qi, w, tables, key_tables, qpos, live))


def window_blocks(window: int, queries: int, bs: int, W: int) -> int:
    """Blocks of a table that hold the windows of ``queries`` consecutive
    positions (``window + queries - 1`` positions on end), wherever in a
    block the first of them lies."""
    return min(W, -(-(window + queries - 2) // bs) + 1)


def _window_rows(pool, tables, first, nb: int):
    """The rows of ``nb`` blocks of each table from block ``first`` [n] on:
    ([n, nb * bs, R], their positions [n, nb * bs]); a block past the table's
    end is its last again, at positions no query has reached."""
    n, W = tables.shape
    bs = pool.shape[1]
    cols = first[:, None] + jnp.arange(nb)[None, :]
    blocks = jnp.take_along_axis(tables, jnp.minimum(cols, W - 1), axis=1)
    pos = first[:, None] * bs + jnp.arange(nb * bs)[None, :]
    return pool[blocks].reshape(n, nb * bs, -1), pos


@jax.named_scope("window.attend")
def window_attention(q, pool, tables, lens, scale: float, rank: int, window: int):
    """Decode: q [b, H, R] one absorbed query a slot; pool [P, bs, R]; tables
    [b, W]; lens [b] the position each slot's row was just written at. A query
    attends to positions ``lens - window + 1 .. lens`` -> ([b, H, rank], rows
    the read covered [b] int32)."""
    bs = pool.shape[1]
    nb = window_blocks(window, 1, bs, tables.shape[1])
    first = jnp.maximum(lens // bs - (nb - 1), 0)
    rows, pos = _window_rows(pool, tables, first, nb)
    valid = (pos <= lens[:, None]) & (pos > lens[:, None] - window)
    return attend_rows(q, rows, valid, scale, rank), (lens // bs - first + 1) * bs


@jax.named_scope("window.attend")
def window_chunk_attention(q, pool, tables, qpos, live, scale: float, rank: int, window: int):
    """A chunk call: q [n, C, H, R] absorbed queries by tile; tables [n, W];
    qpos [n, C] consecutive positions within a tile; live [n] a tile's real
    queries (the others are computed like real ones). A query attends to the
    ``window`` positions that end at its own -> ([n, C, H, rank], rows the
    tile's read covered [n] int32, 0 for a tile with no real query)."""
    n, C, H, R = q.shape
    bs = pool.shape[1]
    nb = window_blocks(window, C, bs, tables.shape[1])
    first = jnp.maximum((qpos[:, 0] - (window - 1)) // bs, 0)
    rows, pos = _window_rows(pool, tables, first, nb)
    valid = (pos[:, None, :] <= qpos[:, :, None]) & (pos[:, None, :] > qpos[:, :, None] - window)
    s = jnp.einsum("nqhr,nmr->nqhm", q, rows, preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(valid[:, :, None, :], s, NEG), axis=-1)
    out = jnp.einsum("nqhm,nmc->nqhc", p.astype(rows.dtype), rows[..., :rank],
                     preferred_element_type=jnp.float32).astype(q.dtype)
    covered = jnp.where(live > 0, ((qpos[:, 0] + live - 1) // bs - first + 1) * bs, 0)
    return out, covered
