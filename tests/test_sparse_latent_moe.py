"""The decoder with selecting and sliding latent attention
(``models/sparse_latent_moe.py``, ``ops/sparse_latent_attention.py``) through the
paged programs and ``LLMEngine``, against the benchmark's plain float32 reference
(``chipbench/reference_sparse_latent_moe.py``: a dense index score and an exact
top-k a block of queries, attention expanded a head at a time under the mask; a
banded mask in the sliding layers) on seeded weights, at a small size on the CPU.

Every context here stands ABOVE ``index_topk`` (12) and above the window (9), so
the full layers select and the sliding ones slide. 16 index heads, not fewer:
with 4, one cached token in sixteen scores exactly 0 (every head's product
negative), the twelfth place is then a tie of zeros, and the reference (which
takes every score at or above the twelfth) and ``top_k`` (which takes twelve)
part ways by a sixteenth of the mass.

Tolerances. Every comparison of logits is of the largest difference over the
SPREAD of the reference's logits at that position. Program and reference both
run in float32 and differ in the order of their sums (the absorbed against the
expanded attention, a gathered list against a mask): they read ~1e-6 of the
spread apart. ``TOL`` leaves two orders of room; the int8 control reads ~1e-1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_hybrid_ssm as T  # the chunk call as the engine lays it out, the tilings
from chipbench import reference_sparse_latent_moe as R
from chipbench import weights_sparse_latent_moe as W
from ray_tpu.models import latent_moe as lm
from ray_tpu.models import paged
from ray_tpu.models import sparse_latent_moe as sm
from ray_tpu.models.paged import PagedConfig
from ray_tpu.ops import latent_attention as la
from ray_tpu.ops import sparse_latent_attention as sparse
from ray_tpu.serve.llm_engine import LLMEngine

TOL = 2e-4
F, S = W.FULL, W.SLIDING
CONF = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=7, first_k_dense_replace=1,
    layer_types=[F, F, S, S, F, S, S],  # a leading layer and two periods of (full, sliding, sliding)
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rope_theta=8e7, index_n_heads=16, index_head_dim=16, index_topk=12,
    swa_num_attention_heads=2, swa_q_lora_rank=24, swa_kv_lora_rank=40, swa_qk_nope_head_dim=24,
    swa_qk_rope_head_dim=8, swa_v_head_dim=16, swa_rope_theta=5e4, sliding_window_size=9,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts_published=16,
    n_routed_experts=8, experts_held_first=4, num_experts_per_tok=2, n_shared_experts=1,
    routed_scaling_factor=1.0, rms_norm_eps=1e-5)
SEED = 2**31 + 59
PROMPT, STEPS, BS = T.PROMPT, T.STEPS, T.BS


def make(conf=CONF, dtype=jnp.float32):
    """(dims, key, the program's configuration, its parameters)."""
    dims = W.Dims.from_config(conf)
    key = W.seed_key(SEED)
    params = jax.jit(lambda k: W.make_params(k, dims, dtype))(key)
    return dims, key, W.program_config(dims, dtype), params


@pytest.fixture(scope="module")
def model():
    return make()


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(3).integers(0, CONF["vocab_size"], PROMPT + STEPS).astype(np.int32)


def reference_logits(model, seq, **kw):
    dims, key, _cfg, _params = model
    return np.asarray(R.stream_logits(key, jnp.asarray(seq)[None], dims, jnp.float32, **kw)[0])


@pytest.fixture(scope="module")
def ref_logits(model, tokens):
    return reference_logits(model, tokens)


# ---------------------------------------------------------------------------
# The programs against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tiling", list(T.TILINGS))
def test_prefill_then_decode_through_the_pools_agree_with_the_reference(model, tokens, ref_logits, tiling):
    """The prompt through the chunk program under each tiling (a tile partly
    padding, a chunk boundary at 32, a call a block), then six decode steps
    across a block boundary: the LOGITS of the prompt's last token and of every
    step are the reference's full forward pass's, the contexts above
    ``index_topk`` and above the window throughout."""
    _dims, _key, cfg, params = model
    assert PROMPT > cfg.index_topk and PROMPT > cfg.sliding_window_size
    cache = paged.init_paged_cache(cfg, T.PCFG)
    for width, parts in T.TILINGS[tiling]:
        for start, end in parts:
            logits, cache = T.chunk_call(params, cfg, cache, width, [(T.SLOT, T.BLOCKS, tokens, start, end)])
    assert T.apart(logits[0], ref_logits[PROMPT - 1]) < TOL
    steps, _ = T.decode(params, cfg, cache, tokens, PROMPT)
    assert T.apart(steps, ref_logits[PROMPT:]) < TOL


def test_two_packed_segments_of_two_slots_in_one_call(model, tokens, ref_logits):
    """ONE call holds a later chunk of slot 2's prompt and, behind it, the whole
    prompt of slot 0: each query selects over its OWN slot's table."""
    _dims, _key, cfg, params = model
    other = np.random.default_rng(9).integers(0, CONF["vocab_size"], 20).astype(np.int32)
    cache = paged.init_paged_cache(cfg, T.PCFG)
    _, cache = T.chunk_call(params, cfg, cache, 32, [(T.SLOT, T.BLOCKS, tokens, 0, 32)])
    logits, cache = T.chunk_call(params, cfg, cache, 64, [
        (T.SLOT, T.BLOCKS, tokens, 32, PROMPT), (0, list(range(9, 13)), other, 0, 20)])
    assert T.apart(logits[0], ref_logits[PROMPT - 1]) < TOL
    assert T.apart(logits[1], reference_logits(model, other)[19]) < TOL


def test_a_prefix_cache_hit_on_a_resident_document_gives_the_logits_of_a_cold_prefill(model, tokens):
    """A second sequence shares the first's four resident blocks (rows, index
    keys and window rows alike: ONE table addresses all three pools) and
    prefills only its suffix: the suffix's logits are those of the same
    sequence prefilled cold into blocks of its own, and the reference's."""
    _dims, _key, cfg, params = model
    suffix = np.random.default_rng(11).integers(0, CONF["vocab_size"], 12).astype(np.int32)
    seq = np.concatenate([tokens[:32], suffix])
    cache = paged.init_paged_cache(cfg, T.PCFG)
    _, cache = T.chunk_call(params, cfg, cache, 32, [(T.SLOT, T.BLOCKS, tokens, 0, 32)])
    shared = T.BLOCKS[:4] + [9, 10]
    hit, cache = T.chunk_call(params, cfg, cache, 32, [(0, shared, seq, 32, 44)])
    cold, _ = T.chunk_call(params, cfg, cache, 64, [(1, list(range(11, 17)), seq, 0, 44)])
    assert T.apart(hit[0], cold[0]) < TOL
    assert T.apart(hit[0], reference_logits(model, seq)[43]) < TOL
    steps, _ = T.decode(params, cfg, cache, np.concatenate([seq, tokens[:3]]), 44, slot=0, blocks=shared)
    assert T.apart(steps, reference_logits(model, np.concatenate([seq, tokens[:3]]))[44:]) < TOL


def test_the_int8_control_fails_the_tolerance_the_float_path_passes(model, tokens, ref_logits):
    """The reference in int8 (router and indexer left in full precision) misses
    its own float32 logits by orders more than ``TOL``: the comparison can tell
    the nearest precision below apart."""
    low = reference_logits(model, tokens, quantize="int8")
    assert T.apart(low[PROMPT - 1:], ref_logits[PROMPT - 1:]) > 100 * TOL


def test_the_planted_fault_of_the_selection_moves_the_reference_only_above_index_topk(model, tokens, ref_logits):
    """``select="recent"`` (the full layers attend to the ``index_topk`` most
    recent positions, the control of a wrong selection): the reference's own
    logits while a context is no longer than ``index_topk``, where both read
    everything, and other logits above it."""
    dims = model[0]
    low = reference_logits(model, tokens, select="recent")
    assert np.array_equal(low[:dims.topk], ref_logits[:dims.topk])
    assert T.apart(low[PROMPT - 1:], ref_logits[PROMPT - 1:]) > 100 * TOL
    with pytest.raises(ValueError, match="unknown selection"):
        reference_logits(model, tokens, select="oldest")


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_a_sliding_layer_reads_no_block_behind_its_window(model, tokens, program):
    """The sliding layers' rows of every block that lies wholly behind the window
    are poisoned with NaN: the logits are what they were (a read that covered
    such a block would multiply a masked 0 into NaN). The full layers' pools
    are left alone: they read where the indexer points."""
    _dims, _key, cfg, params = model
    cache = paged.init_paged_cache(cfg, T.PCFG)
    _, cache = T.chunk_call(params, cfg, cache, 32, [(T.SLOT, T.BLOCKS, tokens, 0, 32)])
    behind = jnp.asarray(T.BLOCKS[:(32 - cfg.sliding_window_size + 1) // BS])  # positions 0..23
    assert len(behind) == 3
    poisoned = {**cache, "window": cache["window"].at[:, behind].set(jnp.nan)}
    if program == "chunk":
        want, _ = T.chunk_call(params, cfg, cache, 32, [(T.SLOT, T.BLOCKS, tokens, 32, PROMPT)])
        got, _ = T.chunk_call(params, cfg, poisoned, 32, [(T.SLOT, T.BLOCKS, tokens, 32, PROMPT)])
    else:
        want, _ = T.decode(params, cfg, cache, tokens[:34], 32)
        got, _ = T.decode(params, cfg, poisoned, tokens[:34], 32)
    assert np.isfinite(got).all() and np.array_equal(got, want)
    # ... and the same poison in the full layers' rows DOES reach the logits.
    rows = {**cache, "rows": cache["rows"].at[:, behind].set(jnp.nan)}
    reached, _ = T.decode(params, cfg, rows, tokens[:34], 32)
    assert not np.isfinite(reached).all()


# ---------------------------------------------------------------------------
# The ops
# ---------------------------------------------------------------------------


def _pool_and_queries(rng, b=3, H=4, R=128, rank=32, Hi=16, Di=16, blocks=24, W=6):
    pool = jnp.asarray(rng.normal(size=(blocks, BS, R)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(blocks, BS, Di)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, blocks))[:b * W].reshape(b, W), jnp.int32)
    return pool, keys, tables


@pytest.mark.parametrize("context", ["under", "above"])
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_selection_is_latent_attention_while_the_context_is_under_index_topk(program, context):
    """With at most ``topk`` cached tokens a query selects them all, and the
    gathered list gives what ``ops/latent_attention.py`` gives on the same pool
    (its plain forms: the CPU's); with more, it gives something else."""
    rng = np.random.default_rng(5)
    pool, keys, tables = _pool_and_queries(rng)
    b, H, R, rank, Hi, Di = 3, 4, 128, 32, 16, 16
    topk = 40 if context == "under" else 10
    if program == "decode":
        lens = jnp.asarray([5, 39, 17], jnp.int32)
        q = jnp.asarray(rng.normal(size=(b, H, R)), jnp.float32)
        qi = jnp.asarray(rng.normal(size=(b, Hi, Di)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(b, Hi)), jnp.float32)
        got = sparse.sparse_attention(q, qi, w, pool, keys, tables, tables, lens, 0.2, rank, topk)
        want = la.reference_latent_attention(q, pool, tables, lens, 0.2, rank)
        rows = slice(None)
    else:
        C = 16
        qpos = jnp.asarray([[0], [8], [24]], jnp.int32) + jnp.arange(C)[None, :]
        live = jnp.asarray([16, 9, 0], jnp.int32)
        q = jnp.asarray(rng.normal(size=(b, C, H, R)), jnp.float32)
        qi = jnp.asarray(rng.normal(size=(b, C, Hi, Di)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(b, C, Hi)), jnp.float32)
        got = sparse.sparse_chunk_attention(q, qi, w, pool, keys, tables, tables, qpos, live, 0.2, rank, topk)
        want = la.latent_chunk_attention(q, pool, tables, qpos, live, 0.2, rank)
        assert not np.asarray(got[2]).any()  # a tile with no real query: zeros, nothing read
        rows = (slice(0, 2), slice(0, 9))  # the real queries of both live tiles
    if context == "under":
        assert np.allclose(got[rows], want[rows], atol=1e-5)
    else:
        assert np.abs(np.asarray(got[rows] - want[rows])).max() > 1e-2


def test_select_takes_the_largest_scores_exactly_and_knows_which_places_are_empty():
    scores = jnp.asarray([[0.5, -jnp.inf, 2.0, 1.0, -3.0, -jnp.inf],
                          [1.0, 3.0, -jnp.inf, -jnp.inf, -jnp.inf, -jnp.inf]])
    pos, valid = sparse.select(scores, jnp.arange(6), 1, 6, 3)  # blocks of one: the places ARE positions
    assert np.array_equal(pos[0], [2, 3, 0]) and np.array_equal(valid, [[1, 1, 1], [1, 1, 0]])
    assert np.array_equal(pos[1][:2], [1, 0])
    # a list longer than the table: every position, once
    pos, valid = sparse.select(scores, jnp.arange(6), 1, 6, 99)
    assert pos.shape == (2, 6) and int(valid.sum()) == 6


# The selection as PR 59 had it, in two steps: ``jax.lax.top_k`` gives POSITIONS in the
# sequence, and each position is then looked up in the slot's table (a gather of its
# own). The oracle of the one-step form, whose sort carries the places in the pool.


def _top_k_positions(scores, k):
    top, pos = jax.lax.top_k(scores, min(k, scores.shape[-1]))
    return pos.astype(jnp.int32), top > -jnp.inf


def _through_the_table(tables, pos, bs):
    """tables [b, W] (or [1, W]: one table for every list); pos [b, k] positions
    -> their (block [b, k], offset [b, k]): a gather from the table."""
    tables = jnp.broadcast_to(tables, pos.shape[:1] + tables.shape[1:])
    return jnp.take_along_axis(tables, pos // bs, axis=1), pos % bs


def _scores(rng, kind, b=4, m=48):
    s = rng.normal(size=(b, m)).astype(np.float32)
    if kind == "tied":  # few distinct values, zeros of both signs among them
        s = np.round(s * 2) / 2
        s[:, ::7] = -0.0
    elif kind == "tails":  # a different count of real tokens a row; one row with none
        for i, live in enumerate([0, 5, 17, 48][:b]):
            s[i, live:] = -np.inf
    return jnp.asarray(s)


@pytest.mark.parametrize("blocks", [40, 2**27], ids=["packed", "stable"])
@pytest.mark.parametrize("kind,k,pad", [
    ("distinct", 12, 0), ("tied", 12, 0), ("tails", 12, 0), ("tails", 99, 0), ("tails", 12, 2)],
    ids=["distinct", "tied", "inf_tails", "k_above_m", "padded_table"])
def test_select_gives_top_k_looked_up_through_the_table(kind, k, pad, blocks):
    """The places the sort carries are exactly the (block, offset) that the
    positions of ``jax.lax.top_k`` give through the table: the same set in the
    same order, ties and ``-inf`` places in the order of their positions, and
    the same ``valid``; with a table padded by the trash block (0) as a chunk
    call pads it, whose positions score ``-inf``. In both forms: the second key
    that packs position and block (a pool of 40 blocks), and the stable sort
    with the place as payload (a pool too large to pack beside 48 positions)."""
    rng = np.random.default_rng(11)
    bs, W = 8, 6 - pad
    tables = jnp.asarray(rng.permutation(np.arange(1, 40))[:4 * W].reshape(4, W), jnp.int32)
    tables = jnp.pad(tables, ((0, 0), (0, pad)))
    scores = _scores(rng, kind)
    if pad:
        scores = scores.at[:, W * bs:].set(-jnp.inf)
    got, valid = jax.jit(lambda s, t: sparse.select(s, t, bs, blocks, k))(scores, tables)
    pos, want_valid = _top_k_positions(scores, k)
    block, offset = _through_the_table(tables, pos, bs)
    want = block * bs + offset
    assert got.dtype == jnp.int32 and got.shape == (4, min(k, 48))
    assert np.array_equal(got, want) and np.array_equal(valid, want_valid)
    if kind == "tails":
        assert [int(v) for v in valid.sum(axis=1)] == [min(n, k) for n in (0, 5, 17, 48 - pad * bs)]
    # one table for every list (a chunk call's tile)
    one, _ = sparse.select(scores, tables[0], bs, blocks, k)
    assert np.array_equal(one[0], want[0])


@pytest.mark.parametrize("program", ["decode", "chunk", "chunk_padded"])
def test_attention_over_carried_ids_is_bit_identical_to_the_two_step_form(program, monkeypatch):
    """At the rehearsal's sizes (4 slots, tables of 16 blocks of 8, 16 index
    heads of 16, 16 of up to 128 cached tokens, 4 heads on rows of 128; tiles
    of 32 queries): ``sparse_attention`` and ``sparse_chunk_attention`` give the
    SAME BITS as the program's own scores under ``top_k`` and the lookup
    through the table; ``chunk_padded`` scores 40 keys a step, so the table of
    16 is padded to 20 by the trash block."""
    rng = np.random.default_rng(12)
    b, Wd, H, R, rank, Hi, Di, topk, C = 4, 16, 4, 128, 32, 16, 16, 16, 32
    pool = jnp.asarray(rng.normal(size=(129, BS, R)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(129, BS, Di)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, 129))[:b * Wd].reshape(b, Wd), jnp.int32)
    key_tables = tables  # one pool a kind here: the model adds a layer's base to each
    if program == "decode":
        lens = jnp.asarray([83, 0, 9, 127], jnp.int32)  # above, idle, under ``topk``, the table's end
        q = jnp.asarray(rng.normal(size=(b, H, R)), jnp.float32)
        qi = jnp.asarray(rng.normal(size=(b, Hi, Di)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(b, Hi)), jnp.float32)
        got = jax.jit(lambda: sparse.sparse_attention(
            q, qi, w, pool, keys, tables, key_tables, lens, 0.2, rank, topk))()
        pos, valid = _top_k_positions(sparse.index_scores(qi, w, keys, key_tables, lens), topk)
        want = sparse.attend_rows(q, pool[_through_the_table(tables, pos, BS)], valid, 0.2, rank)
        assert np.array_equal(got, want)
        return
    if program == "chunk_padded":
        monkeypatch.setattr(sparse, "_KV_ROWS", 40)
    qpos = jnp.asarray([[64], [0], [40], [96]], jnp.int32) + jnp.arange(C)[None, :]
    live = jnp.asarray([32, 17, 0, 20], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, C, H, R)), jnp.float32)
    qi = jnp.asarray(rng.normal(size=(b, C, Hi, Di)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(b, C, Hi)), jnp.float32)

    def call(tile=slice(None)):
        return sparse.sparse_chunk_attention(
            q[tile], qi[tile], w[tile], pool, keys, tables[tile], key_tables[tile], qpos[tile],
            live[tile], 0.2, rank, topk)

    got = call()
    # The oracle a tile at a time: the program's own scores, then the two steps
    # through THAT tile's table, padded as the program pads it.
    padded = jnp.pad(tables, ((0, 0), (0, 4 if program == "chunk_padded" else 0)))
    monkeypatch.setattr(sparse, "select", lambda scores, _t, _bs, _blocks, k: _top_k_positions(scores, k))
    for i in range(b):
        monkeypatch.setattr(sparse, "gather_rows",
                            lambda rows, pos, i=i: rows[_through_the_table(padded[i:i + 1], pos, BS)])
        assert np.array_equal(got[i], call(slice(i, i + 1))[0]), i
    assert np.asarray(got[0]).any() and not np.asarray(got[2]).any()


@pytest.mark.parametrize("window,queries,bs,want", [
    (513, 1, 64, 9), (513, 256, 64, 13), (9, 1, 8, 2), (10, 1, 8, 3), (9, 32, 8, 6), (1, 1, 8, 1)])
def test_window_blocks_cover_the_windows_wherever_they_begin(window, queries, bs, want):
    """The static count of blocks a read covers holds ``window + queries - 1``
    positions on end at the worst alignment, and is never the whole table's."""
    assert sparse.window_blocks(window, queries, bs, 10_000) == want
    assert sparse.window_blocks(window, queries, bs, 4) == min(want, 4)
    for first in range(3 * bs):  # the first position of the stretch, anywhere in a block
        last = first + window + queries - 2
        assert last // bs - first // bs + 1 <= want


def test_window_attention_is_attention_over_the_last_window_positions():
    rng = np.random.default_rng(6)
    pool, _keys, tables = _pool_and_queries(rng)
    q = jnp.asarray(rng.normal(size=(3, 4, 128)), jnp.float32)
    lens = jnp.asarray([3, 40, 23], jnp.int32)
    got, covered = sparse.window_attention(q, pool, tables, lens, 0.2, 32, 9)
    rows = pool[tables].reshape(3, -1, 128)
    for i, t in enumerate([3, 40, 23]):
        lo = max(0, t - 8)
        s = jnp.einsum("hr,kr->hk", q[i], rows[i, lo:t + 1]) * 0.2
        want = jax.nn.softmax(s, axis=-1) @ rows[i, lo:t + 1, :32]
        assert np.allclose(got[i], want, atol=1e-5)
    assert np.array_equal(covered, [8, 16, 16])  # blocks 0; 4-5; 1-2


# ---------------------------------------------------------------------------
# The model's declarations, its experts, the engine
# ---------------------------------------------------------------------------


def test_the_pools_are_three_under_one_table_and_the_period_is_checked(model):
    """Latent rows and index keys for the leading layer and each period's full
    layer, window rows for the sliding layers alone (no leading layer keeps
    any); a stage whose layers are no whole periods is refused."""
    _dims, _key, cfg, _params = model
    pools = paged.paged_model(cfg).pools
    assert list(pools) == ["rows", "index", "window"]
    assert (pools["rows"].layers, pools["index"].layers, pools["window"].layers) == (3, 3, 4)
    assert (pools["rows"].row, pools["index"].row, pools["window"].row) == ((128,), (16,), (128,))
    assert pools["window"].lead == 0 and pools["rows"].lead is None
    assert paged.block_pools(cfg) == ("rows", "index", "window") and paged.slot_pools(cfg) == ()
    assert cfg.period == (F, S, S) and cfg.periods == 2
    full = sm.SparseLatentMoEConfig(num_hidden_layers=45, layer_types=(F,) + (F, S, S, S) * 11)
    assert full.mixer(F).row_width == 640 and full.mixer(S).row_width == 1152 and full.periods == 11
    with pytest.raises(ValueError, match="whole periods"):  # the published 46 end on a full layer
        sm.SparseLatentMoEConfig(num_hidden_layers=46, layer_types=(F,) + (F, S, S, S) * 11 + (F,))
    with pytest.raises(ValueError, match="whole periods"):
        sm.SparseLatentMoEConfig(num_hidden_layers=6, layer_types=(F, F, S, S, F, S))
    with pytest.raises(ValueError, match="leading layers are full"):
        sm.SparseLatentMoEConfig(num_hidden_layers=3, layer_types=(S, F, S))
    shapes = jax.eval_shape(lambda: sm.init_params(jax.random.PRNGKey(0), cfg))
    made = jax.eval_shape(lambda: W.make_params(W.seed_key(0), W.Dims.from_config(CONF), jnp.float32))
    assert jax.tree.map(lambda a: a.shape, shapes) == jax.tree.map(lambda a: a.shape, made)


def test_a_decode_step_counts_keys_live_keys_selected_and_window_rows(model, tokens):
    """Behind the expert layer's five counts: over the three full layers the
    live slot's ``lens + 1`` cached tokens and the ``index_topk`` it selected of
    them, over the four sliding layers the rows their reads covered; the idle
    slots (``lens`` 0) count nothing."""
    _dims, _key, cfg, params = model
    cache = paged.init_paged_cache(cfg, T.PCFG)
    _, cache = T.chunk_call(params, cfg, cache, 64, [(T.SLOT, T.BLOCKS, tokens, 0, PROMPT)])
    tables = np.zeros((T.PCFG.max_batch, T.PCFG.max_blocks_per_seq), np.int32)
    tables[T.SLOT] = T.BLOCKS
    tok, lens = np.zeros(T.PCFG.max_batch, np.int32), np.zeros(T.PCFG.max_batch, np.int32)
    tok[T.SLOT], lens[T.SLOT] = tokens[PROMPT], PROMPT
    _, _, counts = jax.jit(lambda c: paged._decode_step(
        params, cfg, jnp.asarray(tok), c, jnp.asarray(tables), jnp.asarray(lens)))(cache)
    assert counts.shape == (8,) and int(counts[2]) == 6  # six expert layers
    assert [int(x) for x in counts[5:]] == [3 * (PROMPT + 1), 3 * cfg.index_topk, 4 * 2 * BS]


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(model):
    """The reference's expert layer cut eight ways (two experts a share of 16):
    the eight routed parts and the shared expert ONCE are the uncut layer's
    output; and the program's share (experts 4-11) is the reference's for the
    same cut, the selection on ``score + expert_bias`` and the gates without it."""
    dims, key, cfg, params = model
    y = jnp.asarray(np.random.default_rng(8).normal(size=(64, CONF["hidden_size"])), jnp.float32)
    whole = W.Dims.from_config({**CONF, "n_routed_experts": 16, "experts_held_first": 0})
    shared, routed = R.expert_ffn(key, 2, y, whole, jnp.float32)
    parts = []
    for first in range(0, 16, 2):
        share = W.Dims.from_config({**CONF, "n_routed_experts": 2, "experts_held_first": first})
        shared_k, routed_k = R.expert_ffn(key, 2, y, share, jnp.float32)
        assert np.array_equal(shared_k, shared)
        parts.append(np.asarray(routed_k))
    assert np.abs(np.asarray(routed)).max() > 0.1
    assert np.allclose(sum(parts), routed, atol=1e-5)
    mine_shared, mine_routed = R.expert_ffn(key, 2, y, dims, jnp.float32)
    assert np.allclose(sum(parts[2:6]), mine_routed, atol=1e-5)
    lp = jax.tree.map(lambda a: a[0, 0], params["layers"][S])  # layer 2: period 0's first sliding layer
    assert float(jnp.abs(lp["expert_bias"]).max()) > 0
    got, counts = lm.expert_layer(y, lp, cfg, params["experts"][1], 0)  # the period's second place
    assert np.allclose(got, mine_shared + mine_routed, atol=1e-4)
    assert int(counts[2]) == 1 and 0 < int(counts[0]) <= 64 * 2 and 0 < int(counts[1]) <= 8
    # the bias moves the SELECTION: without it some token chooses otherwise
    experts, _ = lm.route(y, lp, cfg)
    plain, _ = lm.route(y, {k: v for k, v in lp.items() if k != "expert_bias"}, cfg)
    assert experts.shape == plain.shape == (64, 2)


def _deficits(ref, served):
    ref = np.asarray(ref)[:len(served)]
    return (ref.max(-1) - ref[np.arange(len(served)), served]) / ref.std(-1)


def test_engine_serves_the_references_tokens_with_cache_chunks_preemption_and_resume(model):
    """``LLMEngine`` end to end on the configuration: a prefix cache over a
    shared document, a fixed prefill chunk, a pool so small that requests are
    preempted and resumed. Every served token (greedy) is the reference's own
    choice at its position, the reference being fed the served tokens as a
    forced continuation; the counters of this model moved and add up."""
    dims, key, cfg, params = model
    p = PagedConfig(block_size=BS, num_blocks=22, max_batch=4, max_blocks_per_seq=16)
    eng = LLMEngine(params, cfg, p, decode_window=3, overlap=True, enable_prefix_cache=True,
                    prefill_chunk=16, seed=1)
    rng = np.random.default_rng(4)
    doc = rng.integers(0, CONF["vocab_size"], 36).tolist()
    prompts = [doc + rng.integers(0, CONF["vocab_size"], 4 + i).tolist() for i in range(5)]
    reqs = [eng.add_request(pr, 30) for pr in prompts]
    for _ in range(2000):
        if all(len(r.generated) == 30 for r in reqs):
            break
        eng.step()
    assert [len(r.generated) for r in reqs] == [30] * 5
    s = eng.stats
    assert s["preemptions"] > 0 and s["prefix_hit_tokens"] > 0 and s["prefill_chunks"] > 0
    assert eng._counted == 8
    expert_layers = dims.layers - dims.lead
    assert s["moe_layer_steps"] % expert_layers == 0 and s["moe_layer_steps"] > 0
    # every context is above index_topk: a query selects 12 of what it could read
    assert s["sparse_keys_live"] > 3 * s["sparse_keys_selected"] > 0
    assert s["sparse_keys_selected"] % cfg.index_topk == 0
    assert s["window_rows_read"] > 0 and s["window_rows_read"] % BS == 0
    for pr, r in zip(prompts, reqs):
        seq = np.asarray(pr + r.generated[:-1], np.int32)
        d = _deficits(reference_logits(model, seq)[len(pr) - 1:], r.generated)
        assert d.max() < 1e-4, d.max()
