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


def _ref_prefill_chunk(params, cfg, tokens, cache, table_row, chunk_row, bs, start):
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
        o = paged._attend_chunk(q[0], ck_g, cv_g, positions[0], cfg)
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


CHUNK_CASES = {
    # (table_row, chunk_row, start): one block, straight after a resident prefix
    "all_live": ([3, 7, 1, 12], [1], 2 * BS),
    # a chunk padded to two blocks whose tail block is the trash block
    "idle_on_trash": ([5, 2, 9, TRASH_BLOCK], [9, TRASH_BLOCK], 2 * BS),
    # a chunk of three blocks that are nowhere near each other in the pool
    "crosses_block": ([11, 6, 8, 10], [6, 8, 10], BS),
}


@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_prefill_chunk_equals_slice_and_put_back(model, case):
    cfg, params, cache = model
    table_row, chunk_row, start = CHUNK_CASES[case]
    C = len(chunk_row) * BS
    tokens = (jnp.arange(C, dtype=jnp.int32)[None, :] * 5 + 1) % cfg.vocab_size
    args = (tokens, cache, jnp.asarray(table_row, jnp.int32),
            jnp.asarray(chunk_row, jnp.int32))
    got = jax.jit(
        lambda t, c, tr, cr, s: paged.paged_prefill_chunk(params, cfg, t, c, tr, cr, BS, s)
    )(*args, jnp.int32(start))
    want = jax.jit(
        lambda t, c, tr, cr, s: _ref_prefill_chunk(params, cfg, t, c, tr, cr, BS, s)
    )(*args, jnp.int32(start))
    _assert_bit_equal(got, want)
    assert not np.array_equal(np.asarray(got[1]["v"].astype(jnp.float32)),
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
