"""The decoder with several residual streams a token (``models/hyper_latent_moe.py``,
``ops/hyper_connections.py``) through the paged programs and ``LLMEngine``, against the
benchmark's plain float32 reference (``chipbench/reference_hyper_latent_moe.py``: the
maps and both mixes a token at a time, attention expanded a head at a time) on seeded
weights, at a small size on the CPU: four streams, 20 Sinkhorn rounds, YaRN over 16
original positions (every context here stands above them, so the blended frequencies
are the ones that turn).

Tolerances. Every comparison of logits is of the largest difference over the SPREAD of
the reference's logits at that position. Program and reference both run in float32 and
differ in the order of their sums (the absorbed against the expanded attention, sums of
streams written out against products): they read ~2e-6 of the spread apart. ``TOL``
leaves two orders of room; the maps' parameters rounded to bfloat16 read ~3e-3.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_hybrid_ssm as T  # the chunk call as the engine lays it out, the tilings
from chipbench import reference_hyper_latent_moe as R
from chipbench import weights_hyper_latent_moe as W
from ray_tpu.models import hyper_latent_moe as hm
from ray_tpu.models import latent_moe as lm
from ray_tpu.models import paged
from ray_tpu.models.paged import PagedConfig
from ray_tpu.models.transformer import _rope
from ray_tpu.ops import hyper_connections as hc
from ray_tpu.serve.llm_engine import _COUNTS, LLMEngine

TOL = 2e-4
CONF = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=4, first_k_dense_replace=2,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rope_theta=10000,
    rope_scaling=dict(type="yarn", factor=4, beta_fast=2, beta_slow=0.5, mscale=1, mscale_all_dim=1,
                      original_max_position_embeddings=16),
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts_published=16,
    n_routed_experts=8, experts_held_first=4, num_experts_per_tok=2, n_shared_experts=1,
    routed_scaling_factor=2.0, rms_norm_eps=1e-6, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)
SEED = 2**31 + 61
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


def through_the_cache(cfg, params, tokens):
    """The prompt as one padded chunk call, then the decode steps: (the prompt's last
    logits, the steps' logits)."""
    cache = paged.init_paged_cache(cfg, T.PCFG)
    logits, cache = T.chunk_call(params, cfg, cache, 64, [(T.SLOT, T.BLOCKS, tokens, 0, PROMPT)])
    steps, _ = T.decode(params, cfg, cache, tokens, PROMPT)
    return logits[0], steps


# ---------------------------------------------------------------------------
# The programs against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tiling", list(T.TILINGS))
def test_prefill_then_decode_through_the_cache_agree_with_the_reference(model, tokens, ref_logits, tiling):
    """The prompt through the chunk program under each tiling (a tile partly padding, a
    chunk boundary at 32, a call a block), then six decode steps across a block boundary:
    the LOGITS of the prompt's last token and of every step are the reference's full
    forward pass's. ``_scan_layers`` and both programs carry ``[b, s, n, D]`` as they
    carry any ``x``: nothing of ``models/paged.py`` knows the streams."""
    _dims, _key, cfg, params = model
    assert PROMPT > cfg.rope_original_positions and cfg.hc_mult == 4
    cache = paged.init_paged_cache(cfg, T.PCFG)
    for width, parts in T.TILINGS[tiling]:
        for start, end in parts:
            logits, cache = T.chunk_call(params, cfg, cache, width, [(T.SLOT, T.BLOCKS, tokens, start, end)])
    assert T.apart(logits[0], ref_logits[PROMPT - 1]) < TOL
    steps, _ = T.decode(params, cfg, cache, tokens, PROMPT)
    assert T.apart(steps, ref_logits[PROMPT:]) < TOL


def test_two_packed_segments_of_two_slots_in_one_call(model, tokens, ref_logits):
    """ONE call holds a later chunk of slot 2's prompt and, behind it, the whole prompt
    of slot 0: a place's maps are made from its own streams and no other's."""
    _dims, _key, cfg, params = model
    other = np.random.default_rng(9).integers(0, CONF["vocab_size"], 20).astype(np.int32)
    cache = paged.init_paged_cache(cfg, T.PCFG)
    _, cache = T.chunk_call(params, cfg, cache, 32, [(T.SLOT, T.BLOCKS, tokens, 0, 32)])
    logits, cache = T.chunk_call(params, cfg, cache, 64, [
        (T.SLOT, T.BLOCKS, tokens, 32, PROMPT), (0, list(range(9, 13)), other, 0, 20)])
    assert T.apart(logits[0], ref_logits[PROMPT - 1]) < TOL
    assert T.apart(logits[1], reference_logits(model, other)[19]) < TOL


@pytest.mark.parametrize("what", ["weights", "maps"])
def test_bfloat16_where_float32_is_stated_fails_the_tolerance(model, tokens, ref_logits, what):
    """The comparison tells precisions apart: the whole model in bfloat16 against the
    reference on the same rounded weights, or ONLY the maps' parameters rounded to
    bfloat16 under a float32 model, miss the float32 logits by far more than ``TOL``."""
    if what == "weights":
        dims, key, cfg, params = make(dtype=jnp.bfloat16)
        assert params["layers"]["hc_attn"]["phi"].dtype == jnp.float32  # whatever the model's type
        ref = np.asarray(R.stream_logits(key, jnp.asarray(tokens)[None], dims, jnp.bfloat16)[0])
    else:
        (_dims, _key, cfg, params), ref = model, ref_logits
        params = {**params, **{stack: {**params[stack], **{
            name: jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params[stack][name])
            for name in W.SUBLAYERS}} for stack in ("lead", "layers")}}
    last, steps = through_the_cache(cfg, params, tokens)
    assert T.apart(steps, ref[PROMPT:]) > 4 * TOL and T.apart(last, ref[PROMPT - 1]) > 4 * TOL


@pytest.mark.parametrize("control,how", [("int8", {"quantize": "int8"}), ("plain-residual", {"residual": "plain"})])
def test_the_controls_fail_the_tolerance_the_float_path_passes(model, tokens, ref_logits, control, how):
    """The reference in int8 (router and maps left in full precision), and the reference
    with the PLANTED FAULT of the mechanism (``Hres`` the identity, ``Hpre = Hpost = 1``),
    miss its own float32 logits by orders more than ``TOL``: the weights' maps stand far
    from a plain residual."""
    low = reference_logits(model, tokens, **how)
    assert T.apart(low[PROMPT - 1:], ref_logits[PROMPT - 1:]) > 100 * TOL
    with pytest.raises(ValueError, match="unknown"):
        reference_logits(model, tokens, **{k: "other" for k in how})


# ---------------------------------------------------------------------------
# The maps and the mixes
# ---------------------------------------------------------------------------


def _streams(rng, cfg, shape=(3, 5)):
    return jnp.asarray(rng.normal(size=shape + (cfg.hc_mult, cfg.hidden_size)), jnp.float32)


@pytest.mark.parametrize("iters,doubly_stochastic", [(20, True), (1, False)])
def test_hres_is_doubly_stochastic_after_twenty_rounds_and_not_after_one(model, iters, doubly_stochastic):
    """Rows are divided last, so they sum to 1 within float32 after any round; the
    COLUMNS sum to 1 within 1e-3 after 20 rounds at these weights' spreads and miss it by
    more than 0.05 after one. The ops' maps are the reference's, a token at a time."""
    dims, key, cfg, _params = model
    cfg = dataclasses.replace(cfg, hc_sinkhorn_iters=iters)
    dims = dataclasses.replace(dims, sinkhorn_iters=iters)
    X = _streams(np.random.default_rng(5), cfg)
    hp = W.hc_params(key, 1, 0, dims)
    pre, post, res = hc.maps(X, hp, cfg)
    assert pre.shape == post.shape == (3, 5, 4) and res.shape == (3, 5, 4, 4)
    rows, cols = np.asarray(res.sum(-1)), np.asarray(res.sum(-2))
    assert np.abs(rows - 1).max() < 1e-5
    assert (np.abs(cols - 1).max() < 1e-3) == doubly_stochastic
    assert doubly_stochastic or np.abs(cols - 1).max() > 0.05
    assert float(pre.min()) > 0 and float(pre.max()) < 1 and float(post.max()) < 2
    want = jax.vmap(jax.vmap(lambda x: R.maps(x, hp, dims)))(X)
    for got, ref in zip((pre, post, res), want):
        assert np.allclose(got, ref, atol=1e-5)
    u, y = hc.mix_in(pre, X), _streams(np.random.default_rng(6), cfg)[..., 0, :]
    assert np.allclose(u, jax.vmap(jax.vmap(R.mix_in))(pre, X), atol=1e-5)
    assert np.allclose(hc.mix_out(res, post, X, y), jax.vmap(jax.vmap(R.mix_out))(res, post, X, y), atol=1e-5)


def test_the_weights_maps_stand_far_from_a_plain_residual_and_from_constants(model):
    """Over a batch of tokens and the sublayers of the model, as the configuration's
    ``assumed`` states for the published widths: ``Hres`` far from the identity AND from
    the uniform matrix, ``Hpre`` and ``Hpost`` far from constant."""
    dims, key, cfg, _params = model
    X = _streams(np.random.default_rng(7), cfg, (256,))
    res_all, pre_all, post_all = [], [], []
    for layer in range(dims.layers):
        for sub in range(2):
            pre, post, res = hc.maps(X, W.hc_params(key, layer, sub, dims), cfg)
            res_all.append(np.asarray(res)), pre_all.append(np.asarray(pre)), post_all.append(np.asarray(post))
    res, pre, post = np.stack(res_all), np.stack(pre_all), np.stack(post_all)
    assert np.abs(res - np.eye(4)).mean() > 0.25 and np.abs(res - 0.25).mean() > 0.1
    assert pre.std() > 0.2 and post.std() > 0.4


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_plain_residual_maps_give_latent_moes_layer_to_the_bit(model, tokens, program):
    """With ``plain_residual_hc``'s parameters (``Hres`` the identity, ``Hpre = Hpost =
    1``) on four equal streams, a layer is ``models/latent_moe.py``'s own layer on the
    same weights (which have no sandwich norms: ``latent_moe._post``), every stream equal
    to its ``x`` bit for bit, the cache rows too: the streams' sum is four times ``x``
    exactly, and with an eps of 0 the norm of that is the norm of ``x`` (and a Sinkhorn
    round leaves a 1 a 1; ``hc_eps`` beside the sums would take a millionth off it)."""
    _dims, _key, cfg, params = model
    cfg = dataclasses.replace(cfg, rms_norm_eps=0.0, hc_eps=0.0)
    mine = paged.paged_model(cfg)
    rng = np.random.default_rng(12)
    pool = jnp.asarray(rng.normal(size=(T.PCFG.num_blocks, BS, cfg.row_width)), jnp.float32)
    for index, stack in ((1, "lead"), (2, "layers")):
        lp = jax.tree.map(lambda a: a[index % 2], params[stack])
        lp = {**lp, **{name: jax.tree.map(lambda a: a[0], hm.plain_residual_hc(cfg, 1)) for name in W.SUBLAYERS}}
        their_lp = {k: v for k, v in lp.items() if k not in W.SUBLAYERS}
        if program == "decode":
            x = jnp.asarray(rng.normal(size=(4, 1, cfg.hidden_size)), jnp.float32)
            tables = jnp.asarray(np.arange(1, 17).reshape(4, 4), jnp.int32)
            lens = jnp.asarray([5, 0, 17, 30], jnp.int32)
            args = (lp, tables, lens, params, index, (0,))
            their_args = (their_lp,) + args[1:]
        else:
            x = jnp.asarray(rng.normal(size=(1, 32, cfg.hidden_size)), jnp.float32)
            table_rows = jnp.asarray([[1, 2, 3, 4, 5, 6, 0, 0]], jnp.int32)
            rows_at = jnp.repeat(jnp.asarray([2, 3, 4, 5], jnp.int32), BS)
            offs = jnp.tile(jnp.arange(BS, dtype=jnp.int32), 4)
            qpos = 8 + jnp.arange(32, dtype=jnp.int32)[None]
            args = (lp, table_rows, rows_at, offs, qpos, jnp.asarray([29], jnp.int32), params, index, (0,),
                    jnp.asarray([0], jnp.int32))
            their_args = (their_lp,) + args[1:]
        X = jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (4, x.shape[-1]))
        layer = mine.decode_layer if program == "decode" else mine.chunk_layer
        # latent_moe's own layer body, over this configuration (a LatentMoEConfig: its
        # frequencies and softmax scale are what ``project`` and the attention read)
        their_layer = lm._decode_layer if program == "decode" else lm._chunk_layer
        got, (got_pool,), _ = jax.jit(lambda X, pool: layer(X, (pool,), *args))(X, pool)
        want, (want_pool,), _ = jax.jit(lambda x, pool: their_layer(cfg, x, (pool,), *their_args))(x, pool)
        for i in range(4):
            assert np.array_equal(np.asarray(got[..., i, :]), np.asarray(want)), (stack, i)
        assert np.array_equal(np.asarray(got_pool), np.asarray(want_pool))


# ---------------------------------------------------------------------------
# The share, the pool, the counts, the rotary frequencies
# ---------------------------------------------------------------------------


def test_the_eight_shares_add_up_through_mix_out_to_the_uncut_layer(model):
    """An expert layer cut eight ways (two experts a share of 16): the eight shares'
    routed parts and the shared expert ONCE, each mixed into the streams by ``mix_out``
    (linear in what it is handed), add up to the uncut reference's layer; and the
    program's share (experts 4-11) is the reference's for the same cut."""
    dims, key, cfg, params = model
    X = _streams(np.random.default_rng(8), cfg, (64,))
    layer = 2
    whole = W.Dims.from_config({**CONF, "n_routed_experts": 16, "experts_held_first": 0})
    args = dict(dims=whole, weight_dtype=jnp.float32, quantize=None)
    shared, routed = R.expert_ffn(key, layer, X, whole, jnp.float32)
    uncut = np.asarray(R._mix_out(key, jnp.int32(layer), X + 0, shared, routed, **args))
    hp = W.hc_params(key, layer, 1, dims)
    _pre, post, res = hc.maps(X, hp, cfg)
    zero = jnp.zeros_like(shared)
    total = np.asarray(hc.mix_out(res, post, X, shared))  # Hres X and the shared expert, once
    parts = []
    for first in range(0, 16, 2):
        share = W.Dims.from_config({**CONF, "n_routed_experts": 2, "experts_held_first": first})
        shared_k, routed_k = R.expert_ffn(key, layer, X, share, jnp.float32)
        assert np.array_equal(shared_k, shared)
        parts.append(np.asarray(routed_k))
        total = total + np.asarray(hc.mix_out(jnp.zeros_like(res), post, X, routed_k))
    assert np.abs(np.asarray(routed)).max() > 0.1
    assert np.allclose(total, uncut, atol=2e-5)
    assert np.allclose(hc.mix_out(jnp.zeros_like(res), post, X, zero), 0)
    lp = jax.tree.map(lambda a: a[layer - dims.lead], params["layers"])
    got, counts = hm.sublayer(X[None], lp["hc_mlp"], cfg, lp["mlp_norm"],
                              lambda y: lm.feed_forward(y, lp, cfg, params, layer))
    mine = hc.mix_out(res, post, X, shared + sum(parts[2:6]))
    assert np.allclose(got[0], mine, atol=2e-4)
    assert int(counts[2]) == 1 and 0 < int(counts[0]) <= 64 * 2 and 0 < int(counts[1]) <= 8


def test_the_pool_is_one_and_the_model_is_found_by_its_configurations_type(model):
    """ONE pool of latent rows over all layers, the two leading ones among them; the
    streams' hooks are the model's own; ``LatentMoEConfig`` still finds ITS model."""
    dims, _key, cfg, params = model
    found = paged.paged_model(cfg)
    assert list(found.pools) == ["rows"] and found.pools["rows"].layers == dims.layers == 4
    assert found.pools["rows"].lead is None and found.prefill is None
    assert found.embed is hm.embed and found.unembed is hm.unembed
    assert paged.paged_model(lm.LatentMoEConfig.tiny()).embed is not hm.embed
    shapes = jax.eval_shape(lambda k: hm.init_params(k, cfg), jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, shapes) == jax.tree.map(lambda a: a.shape, params)
    X = hm.embed(params, jnp.asarray([[3, 7]]), cfg)
    assert X.shape == (1, 2, 4, 64) and np.array_equal(X[0, 0, 0], X[0, 0, 3])


def test_a_call_counts_places_mixed_and_tokens_mixed(model, tokens):
    """Behind the expert layer's five counts and three that are another model's: token
    PLACES times sublayers (padding and idle slots included) and real tokens times
    sublayers. A chunk call 64 wide with 40 real tokens: 64 and 40 places, 8 sublayers;
    a decode step of 4 slots, one live: 4 and 1."""
    _dims, _key, cfg, params = model
    assert _COUNTS[-2:] == ("hc_places_mixed", "hc_tokens_mixed") and len(_COUNTS) == 10
    cache = paged.init_paged_cache(cfg, T.PCFG)
    args = [jnp.asarray(a) for a in (
        np.pad(tokens[:PROMPT], (0, 24))[None], np.asarray([T.BLOCKS] * 2, np.int32),
        np.asarray(T.BLOCKS[:5] + [0] * 3, np.int32), np.asarray([0, 32], np.int32),
        np.asarray([PROMPT - 1, 0], np.int32), np.asarray([32, 8], np.int32), np.asarray([T.SLOT] * 2, np.int32))]
    _, cache, counts = jax.jit(lambda c: paged._prefill_chunk(
        params, cfg, args[0], c, args[1], args[2], BS, *args[3:]))(cache)
    assert counts.shape == (10,) and [int(x) for x in counts[5:]] == [0, 0, 0, 8 * 64, 8 * PROMPT]
    assert int(counts[2]) == 2  # two expert layers
    tables = np.zeros((T.PCFG.max_batch, T.PCFG.max_blocks_per_seq), np.int32)
    tables[T.SLOT] = T.BLOCKS
    tok, lens = np.zeros(T.PCFG.max_batch, np.int32), np.zeros(T.PCFG.max_batch, np.int32)
    tok[T.SLOT], lens[T.SLOT] = tokens[PROMPT], PROMPT
    _, _, counts = jax.jit(lambda c: paged._decode_step(
        params, cfg, jnp.asarray(tok), c, jnp.asarray(tables), jnp.asarray(lens)))(cache)
    assert [int(x) for x in counts[5:]] == [0, 0, 0, 8 * 4, 8 * 1]


def _rope_as_it_was(x, positions, theta):
    """``models/transformer._rope`` before it took frequencies (PR 60's tree)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@pytest.mark.parametrize("name,theta,rotary", [
    ("mistral-7b", 1e6, 128), ("pangu-ultra-moe", 25.6e6, 64), ("granite-4.0-h-micro", 1e7, 64),
    ("ling-3.0-flash", 6e6, 64), ("brumby-14b", 1e6, 128), ("dots3-note.full", 8e7, 64),
    ("dots3-note.sliding", 5e4, 64), ("default", 10000.0, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rope_of_every_accepted_configuration_is_bit_for_bit_what_it_was(name, theta, rotary, dtype):
    """A base (every configuration but this model's hands ``_rope`` one) gives the
    numbers it gave before ``_rope`` took frequencies, and the same lowered program."""
    rng = np.random.default_rng(rotary)
    x = jnp.asarray(rng.normal(size=(2, 9, 3, rotary)), dtype)
    positions = jnp.asarray(rng.integers(0, 40000, (2, 9)), jnp.int32)
    assert np.array_equal(np.asarray(_rope(x, positions, theta), np.float32),
                          np.asarray(_rope_as_it_was(x, positions, theta), np.float32))
    now = jax.jit(lambda x, p: _rope(x, p, theta)).lower(x, positions).as_text()
    was = jax.jit(lambda x, p: _rope_as_it_was(x, p, theta)).lower(x, positions).as_text()
    assert now == was


def test_yarn_blends_the_frequencies_by_the_ramp_and_scales_the_softmax():
    """The published sizes: pairs 0-10 keep ``10000^(-i/32)`` (they turn more than 32
    times over 4,096 positions: the ramp begins at pair 10), pairs 23-31 are that over 64
    (fewer than one turn), pair 16 is blended 6/13 of the way; the softmax scale is
    ``(0.1 ln 64 + 1)^2 / sqrt(192)``; the reference computes the same; and ``_rope`` with
    the frequencies rotates by them."""
    cfg = hm.HyperLatentMoEConfig()
    f = np.asarray(cfg.rope_frequencies)
    own = 10000.0 ** (-np.arange(32) / 32)
    assert f.shape == (32,) and np.allclose(f[:11], own[:11], rtol=1e-12)
    assert np.allclose(f[23:], own[23:] / 64, rtol=1e-12)
    assert np.isclose(f[16], own[16] * (1 - 6 / 13) + own[16] / 64 * 6 / 13, rtol=1e-12)
    assert np.isclose(cfg.softmax_scale, (0.1 * np.log(64) + 1) ** 2 / np.sqrt(192), rtol=1e-12)
    dims = W.Dims.from_config({**CONF, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rope_scaling": dict(
        type="yarn", factor=64, beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1,
        original_max_position_embeddings=4096)})
    assert np.allclose(R.rope_frequencies(dims), f, rtol=1e-6)
    assert np.isclose(R.softmax_scale(dims), cfg.softmax_scale, rtol=1e-12)
    plain = hm.HyperLatentMoEConfig(rope_factor=1.0)
    assert np.allclose(plain.rope_frequencies, own) and np.isclose(plain.softmax_scale, 192 ** -0.5)
    x = jnp.ones((1, 1, 1, 64), jnp.float32)
    got = _rope(x, jnp.asarray([[1000]]), cfg.rope_frequencies)[0, 0, 0]
    assert np.allclose(got[:32], np.cos(1000 * f) - np.sin(1000 * f), atol=1e-3)
    with pytest.raises(ValueError, match="mscale"):
        hm.HyperLatentMoEConfig(rope_mscale=0.7)


# ---------------------------------------------------------------------------
# LLMEngine
# ---------------------------------------------------------------------------


def _deficits(ref, served):
    ref = np.asarray(ref)[:len(served)]
    return (ref.max(-1) - ref[np.arange(len(served)), served]) / ref.std(-1)


def test_engine_serves_the_references_tokens_with_cache_chunks_preemption_and_resume(model):
    """``LLMEngine`` end to end on the configuration: a prefix cache over a shared
    document, a fixed prefill chunk, a pool so small that requests are preempted and
    resumed. Every served token (greedy) is the reference's own choice at its position,
    the reference being fed the served tokens as a forced continuation; the counters of
    this model moved and add up."""
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
    assert eng._counted == 10
    assert s["moe_layer_steps"] % (dims.layers - dims.lead) == 0 and s["moe_layer_steps"] > 0
    assert s["sparse_keys_live"] == s["sparse_keys_selected"] == s["window_rows_read"] == 0
    sublayers = 2 * dims.layers
    assert s["hc_places_mixed"] % sublayers == 0 and s["hc_tokens_mixed"] % sublayers == 0
    # every token the clients got went through every sublayer at least once, as did the prompts'
    assert s["hc_places_mixed"] > s["hc_tokens_mixed"] >= sublayers * (5 * 30 - 5)
    for pr, r in zip(prompts, reqs):
        seq = np.asarray(pr + r.generated[:-1], np.int32)
        d = _deficits(reference_logits(model, seq)[len(pr) - 1:], r.generated)
        assert d.max() < 1e-4, d.max()
