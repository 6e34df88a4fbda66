"""The layer scans of ``models/paged.py`` address the stacked cache in place.

The reference below is the form they replaced, kept here as the plain
oracle: take layer ``i``'s pool ``[num_blocks, bs, KV, HD]`` out of the
stack, run the layer on it, put it back. Same arithmetic, so logits and
both cache halves must be equal to the bit. Three layers, so that a wrong
base in any layer but the first shows; a cache of noise, so that a gather
from another layer's blocks shows too.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import paged
from ray_tpu.models.paged import TRASH_BLOCK, PagedConfig, init_paged_cache
from ray_tpu.models.transformer import (
    TransformerConfig, embed, init_params, mlp_block, project_qkv, rms_norm, unembed,
)

BS = 8
PCFG = PagedConfig(block_size=BS, num_blocks=13, max_batch=4, max_blocks_per_seq=4)


@pytest.fixture(scope="module")
def model():
    cfg = TransformerConfig.tiny(n_layers=3, dtype=jnp.bfloat16, remat=False)
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), init_params(jax.random.PRNGKey(3), cfg)
    )
    zeros = init_paged_cache(cfg, PCFG)
    kk, kv = jax.random.split(jax.random.PRNGKey(4))
    cache = {
        "k": jax.random.normal(kk, zeros["k"].shape, jnp.float32).astype(cfg.dtype),
        "v": jax.random.normal(kv, zeros["v"].shape, jnp.float32).astype(cfg.dtype),
    }
    return cfg, params, cache


def _sliced_scan(layer, x, params, cache):
    """The old carry: slice layer ``i``'s pool out, run, put it back."""

    def body(carry, xs):
        x, ck_all, cv_all = carry
        lp, i = xs
        ck = jax.lax.dynamic_index_in_dim(ck_all, i, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cv_all, i, 0, keepdims=False)
        x, ck, cv = layer(x, ck, cv, lp)
        ck_all = jax.lax.dynamic_update_index_in_dim(ck_all, ck, i, 0)
        cv_all = jax.lax.dynamic_update_index_in_dim(cv_all, cv, i, 0)
        return (x, ck_all, cv_all), None

    L = cache["k"].shape[0]
    (x, ks, vs), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(L, dtype=jnp.int32)),
    )
    return x, {"k": ks, "v": vs}


def _ref_decode_step(params, cfg, tokens, cache, tables, lens):
    def layer(x, ck, cv, lp):
        return paged._paged_layer_step(x, lp, cfg, ck, cv, tables, lens)

    x, cache = _sliced_scan(layer, embed(params, tokens[:, None], cfg), params, cache)
    return unembed(params, x, cfg)[:, 0], cache


def _ref_attend_chunk(q, ck, cv, qpos, cfg):
    """One slot's chunk against its gathered view: the plain form that
    ``paged._attend_chunk`` batches over tiles."""
    C, H, HD = q.shape
    KV = cfg.n_kv_heads
    qg = q.reshape(C, KV, H // KV, HD)
    scores = jnp.einsum("ckgd,mkd->ckgm", qg.astype(jnp.float32), ck.astype(jnp.float32))
    valid = jnp.arange(ck.shape[0])[None, :] <= qpos[:, None]
    scores = jnp.where(valid[:, None, None, :], scores * (HD**-0.5), -1e30)
    og = jnp.einsum("ckgm,mkd->ckgd", jax.nn.softmax(scores, axis=-1), cv.astype(jnp.float32))
    return og.reshape(C, H * HD).astype(q.dtype)


def _ref_prefill_chunk(params, cfg, tokens, cache, table_row, chunk_row, bs, start):
    """ONE slot's chunk, the pool taken out of the stack and put back: the
    oracle of the packed program, which it serves a segment at a time."""
    C = tokens.shape[1]
    W, nb = table_row.shape[0], C // bs
    KV, HD = cfg.n_kv_heads, cfg.head_dim
    positions = start + jnp.arange(C, dtype=jnp.int32)[None, :]

    def layer(x, ck, cv, lp):
        h = rms_norm(x, lp["attn_norm"])
        q, k, v = project_qkv(h, lp, cfg, positions)
        ck = ck.at[chunk_row].set(k[0].reshape(nb, bs, KV, HD))
        cv = cv.at[chunk_row].set(v[0].reshape(nb, bs, KV, HD))
        ck_g = ck[table_row].reshape(W * bs, KV, HD)
        cv_g = cv[table_row].reshape(W * bs, KV, HD)
        o = _ref_attend_chunk(q[0], ck_g, cv_g, positions[0], cfg)
        x = x + (o @ lp["wo"].astype(o.dtype))[None]
        return mlp_block(x, lp, cfg), ck, cv

    x, cache = _sliced_scan(layer, embed(params, tokens, cfg), params, cache)
    return unembed(params, x, cfg)[0], cache


def _assert_bit_equal(got, want):
    (g_logits, g_cache), (w_logits, w_cache) = got, want
    assert g_cache["k"].shape == w_cache["k"].shape  # the public shape is kept
    for name, g, w in (("logits", g_logits, w_logits), ("k", g_cache["k"], w_cache["k"]),
                       ("v", g_cache["v"], w_cache["v"])):
        g, w = np.asarray(g.astype(jnp.float32)), np.asarray(w.astype(jnp.float32))
        assert np.array_equal(g, w), f"{name}: {np.abs(g - w).max()} apart"


# slot -> (its blocks, the position it writes); block ids are scattered over
# the pool and unequal across slots.
_TABLES = np.array([[3, 7, 1, 12], [5, 2, 9, 4], [11, 6, 8, 10], [10, 9, 8, 7]], np.int32)
DECODE_CASES = {
    # every slot mid-sequence, lengths mixed
    "all_live": (_TABLES, [5, 17, 30, 9], 1),
    # slot 2 is idle: the host points its whole row at the trash block
    "idle_on_trash": (np.where(np.arange(4)[:, None] == 2, TRASH_BLOCK, _TABLES),
                      [5, 17, 0, 9], 1),
    # two steps: slot 0 writes the last row of block 3 then the first of
    # block 7, slot 1 the last of 2 then the first of 9
    "crosses_block": (_TABLES, [BS - 1, 2 * BS - 1, 30, 9], 2),
}


@pytest.mark.parametrize("case", list(DECODE_CASES))
def test_decode_step_equals_slice_and_put_back(model, case):
    cfg, params, cache = model
    tables, lens, steps = DECODE_CASES[case]
    tables, lens = jnp.asarray(tables), jnp.asarray(lens, jnp.int32)
    tokens = jnp.asarray([7, 19, 3, 42], jnp.int32)
    new = jax.jit(lambda *a: paged.paged_decode_step(params, cfg, *a))
    ref = jax.jit(lambda *a: _ref_decode_step(params, cfg, *a))
    got_cache = want_cache = cache
    for _ in range(steps):
        got = new(tokens, got_cache, tables, lens)
        want = ref(tokens, want_cache, tables, lens)
        _assert_bit_equal(got, want)
        got_cache, want_cache = got[1], want[1]
        tokens = jnp.argmax(want[0], axis=-1).astype(jnp.int32)
        lens = lens + 1
    # the step wrote somewhere: a body that never scatters would also "agree"
    assert not np.array_equal(np.asarray(got_cache["k"].astype(jnp.float32)),
                              np.asarray(cache["k"].astype(jnp.float32)))


# case -> (tile, [segment: (table_row, blocks under its tiles, start, real tokens)]).
# A segment takes whole tiles of the token axis, one after another; the
# tiles left over belong to nobody.
CHUNK_CASES = {
    # one block, straight after a resident prefix
    "all_live": (BS, [([3, 7, 1, 12], [1], 2 * BS, BS)]),
    # a chunk padded to two blocks whose tail block is the trash block
    "idle_on_trash": (2 * BS, [([5, 2, 9, TRASH_BLOCK], [9, TRASH_BLOCK], 2 * BS, 5)]),
    # a chunk of three blocks that are nowhere near each other in the pool
    "crosses_block": (BS, [([11, 6, 8, 10], [6, 8, 10], BS, 3 * BS)]),
    # two slots' suffixes in one call, the second ending inside its last block
    "two_slots": (2 * BS, [([3, 7, 1, 12], [1, 12], 2 * BS, 2 * BS),
                           ([5, 2, 9, 4], [2, 9], BS, BS + 4)]),
    # five slots, a tile each, at five different depths of their tables
    "five_slots": (BS, [([3, 7, TRASH_BLOCK, TRASH_BLOCK], [7], BS, BS),
                        ([5, 2, 9, TRASH_BLOCK], [9], 2 * BS, 3),
                        ([11, 6, TRASH_BLOCK, TRASH_BLOCK], [6], BS, 1),
                        ([10, 8, 1, 12], [12], 3 * BS, BS - 1),
                        ([4, TRASH_BLOCK, TRASH_BLOCK, TRASH_BLOCK], [4], 0, BS)]),
    # one slot on two of four tiles: the other two lie on the trash block
    "idle_tiles": (BS, [([5, 2, 9, 4], [9, 4], 2 * BS, BS + 2)]),
}
_IDLE_TILES = {"idle_tiles": 2}


@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_prefill_chunk_equals_slice_and_put_back(model, case):
    """The packed chunk program against its segments served one call each
    by the plain form: the same logits at every segment's last token and
    the same cache, to the bit, outside the trash block (which takes every
    padded tail and every idle tile, in whatever order)."""
    cfg, params, cache = model
    tile, segments = CHUNK_CASES[case]
    rng = np.random.default_rng(5)
    tokens, table_rows, chunk_row, starts, last_idx = [], [], [], [], []
    want_logits, want_cache = [], cache
    ref = jax.jit(lambda t, c, tr, cr, s: _ref_prefill_chunk(params, cfg, t, c, tr, cr, BS, s))
    for table_row, under, start, real in segments:
        width = len(under) * BS
        toks = np.zeros(width, np.int32)
        toks[:real] = rng.integers(1, cfg.vocab_size, real)
        last_idx.append(len(tokens) * tile + real - 1)
        for t in range(width // tile):
            tokens.append(toks[t * tile:(t + 1) * tile])
            table_rows.append(table_row)
            starts.append(start + t * tile)
        chunk_row += under
        logits, want_cache = ref(jnp.asarray(toks)[None], want_cache,
                                 jnp.asarray(table_row, jnp.int32),
                                 jnp.asarray(under, jnp.int32), jnp.int32(start))
        want_logits.append(logits[real - 1])
    for _ in range(_IDLE_TILES.get(case, 0)):
        tokens.append(np.zeros(tile, np.int32))
        table_rows.append([TRASH_BLOCK] * 4)
        starts.append(0)
        chunk_row += [TRASH_BLOCK] * (tile // BS)
    n = len(tokens)
    last_idx += [0] * (n - len(segments))
    got_logits, got_cache = jax.jit(
        lambda t, c, tr, cr, s, li: paged.paged_prefill_chunk(params, cfg, t, c, tr, cr, BS, s, li)
    )(jnp.asarray(np.concatenate(tokens))[None], cache, jnp.asarray(table_rows, jnp.int32),
      jnp.asarray(chunk_row, jnp.int32), jnp.asarray(starts, jnp.int32),
      jnp.asarray(last_idx, jnp.int32))
    assert got_logits.shape == (n, cfg.vocab_size)  # a row a tile, whoever uses it
    live = lambda c: {name: a[:, TRASH_BLOCK + 1:] for name, a in c.items()}
    _assert_bit_equal((got_logits[:len(segments)], live(got_cache)),
                      (jnp.stack(want_logits), live(want_cache)))
    assert not np.array_equal(np.asarray(got_cache["v"].astype(jnp.float32)),
                              np.asarray(cache["v"].astype(jnp.float32)))


@pytest.mark.parametrize("idled", [10, 4 * BS, 10_000], ids=["a_window", "the_table", "long"])
def test_decode_window_restarts_a_row_that_holds_no_sequence(model, idled):
    """The host points an idle slot's row at the trash block and lets its
    ``lens`` run on, a window's steps with every window it sits out. The
    window restarts such a row at 0: however long it idled it touches the
    first rows of the trash block and nothing else, so the sampled tokens
    and the cache are the ones of a row that never ran on."""
    cfg, params, cache = model
    tables, lens, _ = DECODE_CASES["idle_on_trash"]
    ran_on = np.array(lens)
    assert ran_on[2] == 0 and (tables[2] == TRASH_BLOCK).all()
    ran_on[2] = idled
    window = jax.jit(lambda lens: paged.paged_decode_loop(
        params, cfg, jnp.asarray([7, 19, 3, 42], jnp.int32), cache, jnp.asarray(tables),
        lens, jnp.zeros(4, jnp.float32), jax.random.PRNGKey(0), 3))
    _assert_bit_equal(window(jnp.asarray(ran_on, jnp.int32)), window(jnp.asarray(lens, jnp.int32)))
