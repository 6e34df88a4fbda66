"""The power retention decoder (``models/power_retention.py``) through the paged
programs and ``LLMEngine``, against the benchmark's plain float32 reference
(``chipbench/reference_power_retention.py``: the masked quadratic form, which
builds neither ``phi`` nor a state) on seeded weights, at a small size on the CPU.

Tolerances. Every comparison of logits is of the largest difference over the
SPREAD of the reference's logits at that position. Program and reference both
run in float32 and differ in what they sum (a state of ``phi(k) v^T`` read by
``phi(q)``, token by token or tile by tile, against squared scores over all
earlier keys): they read 7e-7 to 4e-6 of the spread apart. ``TOL`` leaves that
over an order of room; a state rounded to bfloat16 between tokens reads 8e-3 and
fails it (``test_a_state_in_bfloat16_fails_the_tolerance``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_hybrid_ssm as T  # the tilings, the spread, the greedy-continuation check
from chipbench import reference_power_retention as R
from chipbench import weights_power_retention as W
from ray_tpu.models import paged
from ray_tpu.models import power_retention as pr
from ray_tpu.models.hybrid_ssm import _segments
from ray_tpu.models.paged import PagedConfig
from ray_tpu.ops import power_retention as ops
from ray_tpu.serve.llm_engine import LLMEngine

TOL = 2e-4
BS = 8
CONF = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=3, num_attention_heads=10,
    num_key_value_heads=2, head_dim=16, intermediate_size=128, rope_theta=1e6, rms_norm_eps=1e-6)
SEED = 2**31 + 53
# No pool of blocks: ``num_blocks`` is the trash block alone, and the table's
# length only bounds a sequence (128 positions).
PCFG = PagedConfig(block_size=BS, num_blocks=1, max_batch=4, max_blocks_per_seq=16)
SLOT = 2
PROMPT, STEPS = T.PROMPT, T.STEPS


def make(dtype=jnp.float32):
    dims = W.Dims.from_config(CONF)
    key = W.seed_key(SEED)
    params = jax.jit(lambda k: W.make_params(k, dims, dtype))(key)
    return dims, key, W.program_config(dims, dtype), params


@pytest.fixture(scope="module")
def model():
    return make()


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(3).integers(0, CONF["vocab_size"], PROMPT + STEPS).astype(np.int32)


def reference_logits(model, seq):
    dims, key, _cfg, _params = model
    return np.asarray(R.stream_logits(key, jnp.asarray(seq)[None], dims, jnp.float32)[0])


@pytest.fixture(scope="module")
def ref_logits(model, tokens):
    return reference_logits(model, tokens)


def chunk_call(params, cfg, cache, width: int, segs):
    """One call of the chunk program as ``LLMEngine._chunk_call`` lays it out for
    a model with no pool of blocks (a table and a row of blocks with NO entry);
    ``segs`` are (slot, tokens, start, end); → (logits a segment, cache)."""
    tile = paged.chunk_tile(width, BS)
    n = width // tile
    toks = np.zeros((1, width), np.int32)
    starts, last_idx, live = (np.zeros(n, np.int32) for _ in range(3))
    slot_of = np.full(n, PCFG.max_batch, np.int32)
    at = 0
    for k, (slot, full, start, end) in enumerate(segs):
        tiles = -(-(end - start) // tile)
        t0 = at // tile
        toks[0, at:at + end - start] = full[start:end]
        starts[t0:t0 + tiles] = start + tile * np.arange(tiles)
        live[t0:t0 + tiles] = np.minimum(tile, end - starts[t0:t0 + tiles])
        slot_of[t0:t0 + tiles] = slot
        last_idx[k] = at + end - start - 1
        at += tiles * tile
    assert at <= width
    logits, cache = jax.jit(lambda c, *a: paged.paged_prefill_chunk(
        params, cfg, a[0], c, jnp.zeros((n, 0), jnp.int32), jnp.zeros((0,), jnp.int32), BS, *a[1:]))(
            cache, *(jnp.asarray(a) for a in (toks, starts, last_idx, live, slot_of)))
    return np.asarray(logits)[:len(segs)], cache


def decode(params, cfg, cache, tokens, first: int, slot=SLOT, round_state=None):
    """Decode steps for ``tokens[first:]`` of the sequence in ``slot``, one token
    a slot through the pool (the other slots at ``lens`` 0: idle); → (logits a
    step, cache). ``round_state``: the state is rounded to that dtype after every step."""
    step = jax.jit(lambda tok, c, lens: paged.paged_decode_step(
        params, cfg, tok, c, jnp.zeros((PCFG.max_batch, 0), jnp.int32), lens))
    out = []
    for at in range(first, len(tokens)):
        tok, lens = np.zeros(PCFG.max_batch, np.int32), np.zeros(PCFG.max_batch, np.int32)
        tok[slot], lens[slot] = tokens[at], at
        logits, cache = step(jnp.asarray(tok), cache, jnp.asarray(lens))
        if round_state is not None:
            cache = {"power": cache["power"].astype(round_state).astype(jnp.float32)}
        out.append(np.asarray(logits[slot]))
    return np.stack(out), cache


# ---------------------------------------------------------------------------
# phi, and the three forms on LOGITS
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("d", [16, 128])
def test_phi_of_two_vectors_multiplies_to_the_square_of_their_product(d):
    """``expand(a) . expand(b) = (a . b) ** 2 / d``, in (d / 2 + 1) * d entries."""
    rng = np.random.default_rng(0)
    a, b = (jnp.asarray(rng.normal(size=(7, d)), jnp.float32) for _ in range(2))
    pa, pb = ops.expand(a), ops.expand(b)
    assert pa.shape == (7, ops.phi_width(d)) and ops.phi_width(128) == 8320
    want = np.sum(np.asarray(a, np.float64) * np.asarray(b, np.float64), -1) ** 2 / d
    assert np.allclose(np.sum(np.asarray(pa, np.float64) * np.asarray(pb, np.float64), -1), want,
                       rtol=1e-5, atol=1e-5)


def test_the_recurrence_token_by_token_is_the_references_quadratic_form(model, tokens, ref_logits):
    """One token through the chunk program (a slot at ``lens`` 0 is idle: no
    decode step runs position 0), then EVERY further token through the decode
    step, the state advanced a token at a time: each step's logits are the
    reference's, which never builds a state."""
    _dims, _key, cfg, params = model
    cache = paged.init_paged_cache(cfg, PCFG)
    logits, cache = chunk_call(params, cfg, cache, BS, [(SLOT, tokens, 0, 1)])
    assert T.apart(logits[0], ref_logits[0]) < TOL
    steps, _ = decode(params, cfg, cache, tokens, 1)
    assert T.apart(steps, ref_logits[1:]) < TOL


@pytest.mark.parametrize("end", [1, 7, 32, 33, PROMPT + STEPS])
def test_the_tiled_chunk_form_is_the_references_quadratic_form(model, tokens, ref_logits, end):
    """The first ``end`` tokens as tiles of ONE call 64 wide (tiles of 32: a lone
    token, a tile partly padding, a whole tile, a tile and a token, two tiles
    with the second partly padding): the last token's logits are the reference's."""
    _dims, _key, cfg, params = model
    logits, _ = chunk_call(params, cfg, paged.init_paged_cache(cfg, PCFG), 64, [(SLOT, tokens, 0, end)])
    assert T.apart(logits[0], ref_logits[end - 1]) < TOL


@pytest.mark.parametrize("tiling", list(T.TILINGS))
def test_prefill_then_decode_through_the_pools_agree_with_the_reference(model, tokens, ref_logits, tiling):
    """The prompt through the chunk program under each tiling (the state handed
    from tile to tile inside a call, and from the slot's stored row between
    CALLS: a prompt longer than the chunk width, as every prompt of the cell is),
    then six decode steps through the pool: the LOGITS of the prompt's last token
    and of every step are the reference's full forward pass's."""
    _dims, _key, cfg, params = model
    cache = paged.init_paged_cache(cfg, PCFG)
    for width, parts in T.TILINGS[tiling]:
        for start, end in parts:
            logits, cache = chunk_call(params, cfg, cache, width, [(SLOT, tokens, start, end)])
    assert T.apart(logits[0], ref_logits[PROMPT - 1]) < TOL
    steps, _ = decode(params, cfg, cache, tokens, PROMPT)
    assert T.apart(steps, ref_logits[PROMPT:]) < TOL


def test_two_packed_segments_one_carried_and_one_fresh(model, tokens, ref_logits):
    """ONE call holds a later chunk of slot 2's prompt (it takes up the state an
    earlier call stored) and, behind it, the whole prompt of slot 0 (it starts
    from nothing, whatever slot 0's row held): both read the reference's logits,
    and so do their decode steps."""
    _dims, _key, cfg, params = model
    other = np.random.default_rng(9).integers(0, CONF["vocab_size"], 20 + STEPS).astype(np.int32)
    ref_other = reference_logits(model, other)
    cache = paged.init_paged_cache(cfg, PCFG)
    cache = {"power": cache["power"].at[:, 0].set(0.5)}  # a request that ended there
    _, cache = chunk_call(params, cfg, cache, 32, [(SLOT, tokens, 0, 32)])
    logits, cache = chunk_call(params, cfg, cache, 64, [(SLOT, tokens, 32, PROMPT), (0, other, 0, 20)])
    assert T.apart(logits[0], ref_logits[PROMPT - 1]) < TOL
    assert T.apart(logits[1], ref_other[19]) < TOL
    steps, cache = decode(params, cfg, cache, tokens, PROMPT)
    assert T.apart(steps, ref_logits[PROMPT:]) < TOL
    steps, _ = decode(params, cfg, cache, other, 20, slot=0)
    assert T.apart(steps, ref_other[20:]) < TOL


def test_a_state_in_bfloat16_fails_the_tolerance(model, tokens, ref_logits):
    """The comparison sees the state's precision: the state rounded to bfloat16
    after the prefill and after every decode step misses the float32 logits by
    far more than ``TOL``."""
    _dims, _key, cfg, params = model
    cache = paged.init_paged_cache(cfg, PCFG)
    _, cache = chunk_call(params, cfg, cache, 64, [(SLOT, tokens, 0, PROMPT)])
    cache = {"power": cache["power"].astype(jnp.bfloat16).astype(jnp.float32)}
    steps, _ = decode(params, cfg, cache, tokens, PROMPT, round_state=jnp.bfloat16)
    assert T.apart(steps, ref_logits[PROMPT:]) > 4 * TOL


def test_five_query_heads_read_one_state(model):
    """The published grouping at the tests' size: ten query heads on two
    key/value heads. ONE pool by slot, a row ``[2, VALUES, P]`` a layer (no row
    a query head, no pool of blocks at all), and the five queries of a state
    read what each alone reads of it."""
    _dims, _key, cfg, _params = model
    assert cfg.group == 5
    pools = paged.paged_model(cfg).pools
    assert {k: (v.layers, v.unit, v.row) for k, v in pools.items()} == {
        "power": (3, "slots", (2, 24, 144))}
    assert paged.slot_pools(cfg) == ("power",) and paged.block_pools(cfg) == ()
    cache = paged.init_paged_cache(cfg, PCFG)
    assert cache["power"].shape == (3, 4, 2, 24, 144) and cache["power"].dtype == jnp.float32
    served = pr.PowerRetentionConfig(num_hidden_layers=8)
    assert paged.paged_model(served).pools["power"].row == (8, 136, 8320) and served.group == 5
    rng = np.random.default_rng(4)
    b, H, d = 2, 2, 16
    pool = jnp.asarray(np.abs(rng.normal(size=(b, H, 24, 144))), jnp.float32)
    g = jnp.asarray(rng.uniform(0.9, 1, (b, H)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(b, H, d)), jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(b, H, 5, d)), jnp.float32)
    lens = jnp.ones(b, jnp.int32)
    new, y = ops.reference_power_update(pool, jnp.int32(0), lens, g, k, q, v)
    for a in range(5):
        alone, y_a = ops.reference_power_update(pool, jnp.int32(0), lens, g, k, q[:, :, a:a + 1], v)
        assert np.array_equal(alone, new) and np.allclose(y_a[:, :, 0], y[:, :, a], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# ops/power_retention.py
# ---------------------------------------------------------------------------

LENS = {"some_skipped": [0, 3, 0, 0, 5, 1], "the_first_skipped": [0, 0, 2, 9, 0, 4],
        "one_live": [0, 0, 0, 7, 0, 0], "all_live": [1, 2, 3, 4, 5, 6], "none_live": [0] * 6}


def _real_state(rng, shape, d):
    """A state a past would leave: sums of ``phi(k) [v | 1]^T``, so that the
    normaliser's row is a sum of squares and the reads are of the values' size."""
    k = jnp.asarray(rng.normal(size=shape + (6, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=shape + (6, d)), jnp.float32)
    return jnp.einsum("...tv,...tp->...vp", ops.with_one(v), ops.expand(k))


@pytest.mark.parametrize("lens", list(LENS))
def test_power_state_update_kernel_reads_the_plain_forms_numbers(lens):
    """The kernel under the Pallas interpreter against the plain form, in the
    second of three layers of a flat pool, heads of 128 (a state of 136 x 8,320),
    live and idle slots mixed: the live slots' states and reads agree to
    rounding; an idle slot's rows are bit for bit what they were and its output
    zeros; the other layers' rows are untouched."""
    rng = np.random.default_rng(1)
    b, H, G, d = 6, 2, 5, 128
    pool = _real_state(rng, (3 * b, H), d)
    lens_ = jnp.asarray(LENS[lens], jnp.int32)
    g = jnp.asarray(rng.uniform(0.9, 1, (b, H)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(b, H, d)), jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(b, H, G, d)), jnp.float32)
    assert ops._tiles(pool, q)
    want_pool, want_y = ops.reference_power_update(pool, jnp.int32(b), lens_, g, k, q, v)
    got_pool, got_y = ops._power_state_update(pool, jnp.int32(b), lens_, g, k, q, v, interpret=True)
    assert np.allclose(got_pool, want_pool, rtol=1e-5, atol=1e-5)
    assert np.allclose(got_y, want_y, rtol=1e-4, atol=1e-4)
    skipped = np.flatnonzero(np.asarray(LENS[lens]) == 0)
    assert np.array_equal(np.asarray(got_pool)[b + skipped], np.asarray(pool)[b + skipped])
    assert not np.asarray(got_y)[skipped].any()
    assert np.array_equal(np.asarray(got_pool)[:b], np.asarray(pool)[:b])
    assert np.array_equal(np.asarray(got_pool)[2 * b:], np.asarray(pool)[2 * b:])


# A chunk call's tiles for ``power_chunk_scan``: a tile is (slot or None for
# nobody's, its first position, its real tokens). Slots 0-3 of the second of
# three layers; slot 1's row holds the state an earlier call left, every other 0.5.
SCANS = {
    "a_segment_that_ends_mid_tile": [(2, 0, 5)],
    "tiles_with_live_0_between_two_segments": [(0, 0, 8), (0, 8, 3), (None, 0, 0), (3, 0, 6)],
    "a_carried_state_behind_a_segment_from_nothing": [(1, 16, 8), (1, 24, 4), (2, 0, 8), (2, 8, 1)],
    "nobody_at_all": [(None, 0, 0), (None, 0, 0)],
}


def _scan_operands(rng, spec, slots, H, G, d, C):
    """``power_chunk_scan``'s arguments behind the pool for the tiles of
    ``spec``, slots of the second of three layers: decays from 0.9995 down to
    0.5 a token, normal queries, keys and values."""
    n = len(spec)
    slot_of = jnp.asarray([slots if s is None else s for s, _, _ in spec], jnp.int32)
    live = jnp.asarray([ln for _, _, ln in spec], jnp.int32)
    fresh, cont, last = _segments(jnp.asarray([a for _, a, _ in spec], jnp.int32)[:, None], slot_of, slots)
    row = jnp.where(slot_of < slots, slots + slot_of, 3 * slots).astype(jnp.int32)
    log_g = jnp.asarray(np.log(1 - np.exp(rng.uniform(np.log(5e-4), np.log(0.5), (n, C, H)))), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(n, C, H, d)), jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(n, C, H, G, d)), jnp.float32)
    return row, fresh, cont, last, live, log_g, q, k, v


@pytest.mark.parametrize("tiles", list(SCANS))
def test_power_chunk_scan_is_the_recurrence_token_by_token(tiles):
    """The tiled form (tiles of 8) against the recurrence a token at a time in
    float64, in the second of three layers of a flat pool, decays from 0.9995
    down to 0.5 a token: the reads and the WHOLE pool agree to rounding. A
    segment that ends mid-tile leaves the state after its last real token,
    whatever stands behind it; a tile with ``live`` 0 has zeros for its reads
    and touches no row; a fresh segment begins from nothing though its slot's
    row holds 0.5, a carried one from its row; rows no segment ends in, and the
    other layers', are bit for bit what they were.

    This is the test that holds ``ops._steps``, the masking of padding and the
    running sum of ``log g`` that the plain form and the kernel's wrapper share:
    the kernel's own test compares the two forms, which would both be wrong
    together. It is no repeat of that one."""
    rng = np.random.default_rng(7)
    slots, H, G, d, C = 4, 2, 5, 16, 8
    spec = SCANS[tiles]
    pool = np.full((3 * slots, H, ops.values_rows(d), ops.phi_width(d)), 0.5, np.float32)
    pool[slots + 1] = np.asarray(_real_state(rng, (H,), d))
    args = (jnp.asarray(pool),) + _scan_operands(rng, spec, slots, H, G, d, C)
    _, row, fresh, cont, last, live, log_g, q, k, v = args
    got_pool, got_y = (np.asarray(x) for x in jax.jit(ops.power_chunk_scan)(*args))
    want_pool, S = pool.astype(np.float64), None
    f64 = lambda x: np.asarray(x, np.float64)  # noqa: E731
    for t, (s, start, ln) in enumerate(spec):
        if s is None:
            assert not got_y[t].any()
            continue
        if start == 0 or not bool(cont[t]):
            S = np.zeros(pool.shape[1:]) if start == 0 else pool[slots + s].astype(np.float64)
        for i in range(ln):  # the equation, a token at a time
            S = (np.exp(f64(log_g[t, i]))[:, None, None] * S
                 + f64(ops.with_one(v[t, i]))[:, :, None] * f64(ops.expand(k[t, i]))[:, None, :])
            read = np.einsum("hgp,hvp->hgv", f64(ops.expand(q[t, i])), S)
            assert np.allclose(got_y[t, i], read[..., :d] / (read[..., d:d + 1] + ops.EPS),
                               rtol=1e-4, atol=1e-4), (t, i)
        want_pool[slots + s] = S
    assert np.allclose(got_pool, want_pool, rtol=1e-5, atol=1e-5)
    ended = {s for (s, _, _), e in zip(spec, np.asarray(last)) if e}
    kept = [r for r in range(3 * slots) if r - slots not in ended]
    assert np.array_equal(got_pool[kept], pool[kept])
    if tiles == "a_segment_that_ends_mid_tile":
        ln = spec[0][2]
        other = (args[0], row, fresh, cont, last, live, log_g.at[:, ln:].set(-3.0), q,
                 k.at[:, ln:].set(0.3), v.at[:, ln:].set(3.0))
        again, _ = jax.jit(ops.power_chunk_scan)(*other)
        assert np.array_equal(np.asarray(again), got_pool)


# The same for the kernel, at its own shapes: tiles of 128 tokens of heads of 128.
KERNEL_SCANS = {
    "a_fresh_segment_of_several_tiles": [(2, 0, 128), (2, 128, 128), (2, 256, 128)],
    "a_carried_segment": [(1, 256, 128), (1, 384, 128)],
    "two_packed_segments_one_carried_and_one_fresh": [(1, 256, 128), (1, 384, 30), (2, 0, 128), (2, 128, 17)],
    "a_segment_that_ends_mid_tile": [(3, 0, 128), (3, 128, 41)],
    "tiles_with_live_0_between_two_segments": [(0, 0, 77), (None, 0, 0), (None, 0, 0), (1, 128, 9)],
    "nobody_at_all": [(None, 0, 0), (None, 0, 0)],
}


@pytest.mark.parametrize("tiles", list(KERNEL_SCANS))
def test_power_chunk_scan_kernel_reads_the_plain_forms_numbers(tiles):
    """The chunk scan's kernel under the Pallas interpreter against the plain
    form, in the second of three layers of a flat pool, two states of five
    queries at heads of 128 and tiles of 128: the reads and the WHOLE pool agree
    to rounding (a carried segment begins from its row, a fresh one from nothing
    though its row holds a state); a tile with ``live`` 0 has zeros for its
    reads and, its row being past the pool, loads and stores nothing; rows no
    segment ends in, and the other layers', are bit for bit what they were; what
    stands behind a segment's last real token changes nothing."""
    rng = np.random.default_rng(11)
    slots, H, G, d, C = 4, 2, 5, 128, 128
    spec = KERNEL_SCANS[tiles]
    pool = _real_state(rng, (3 * slots, H), d)
    args = (pool,) + _scan_operands(rng, spec, slots, H, G, d, C)
    _, row, fresh, cont, last, live, log_g, q, k, v = args
    assert ops._scan_tiles(pool, q)
    want_pool, want_y = ops.reference_power_chunk_scan(*args)
    got_pool, got_y = (np.asarray(x) for x in ops._power_chunk_scan(*args, interpret=True))
    assert np.allclose(got_pool, want_pool, rtol=1e-5, atol=1e-5)
    assert np.allclose(got_y, want_y, rtol=1e-4, atol=1e-4)
    assert not got_y[np.asarray(live) == 0].any()
    ended = {s for (s, _, _), e in zip(spec, np.asarray(last)) if e}
    kept = [r for r in range(3 * slots) if r - slots not in ended]
    assert np.array_equal(got_pool[kept], np.asarray(pool)[kept])
    if ended:
        assert not np.array_equal(got_pool[slots + min(ended)], np.asarray(pool)[slots + min(ended)])
    if tiles == "a_segment_that_ends_mid_tile":
        ln = spec[-1][2]
        other = (pool, row, fresh, cont, last, live, log_g.at[-1, ln:].set(-3.0), q,
                 k.at[-1, ln:].set(0.3), v.at[-1, ln:].set(3.0))
        again, _ = ops._power_chunk_scan(*other, interpret=True)
        assert np.array_equal(np.asarray(again), got_pool)


# ---------------------------------------------------------------------------
# The programs' rules for state by slot, with no table to tell idle from live
# ---------------------------------------------------------------------------


def test_a_decode_window_leaves_idle_and_prefilling_slots_alone(model, tokens):
    """Three decode steps in one program with slot 2 live, slot 1 idle (its row
    holds what a finished request left) and slot 3 halfway through a chunked
    prefill: the table has NO column, so ``lens`` 0 is all that says a row holds
    no sequence, as the host keeps it until a prefill ends. The states of slots
    0, 1 and 3 are bit for bit what they were, in every layer; slot 2's moved."""
    _dims, _key, cfg, params = model
    cache = paged.init_paged_cache(cfg, PCFG)
    cache = {"power": cache["power"].at[:, 1].set(0.25)}
    _, cache = chunk_call(params, cfg, cache, 64, [(SLOT, tokens, 0, PROMPT)])
    _, cache = chunk_call(params, cfg, cache, 32, [(3, tokens, 0, 32)])
    before = np.asarray(cache["power"])
    assert np.abs(before[:, 3]).max() > 0
    lens = jnp.asarray([0, 0, PROMPT, 0], jnp.int32)
    cur = jnp.asarray([0, 5, tokens[PROMPT], 7], jnp.int32)
    _, cache = jax.jit(lambda c: paged.paged_decode_loop(
        params, cfg, cur, c, jnp.zeros((4, 0), jnp.int32), lens, jnp.zeros(4),
        jax.random.PRNGKey(0), 3))(cache)
    after = np.asarray(cache["power"])
    assert np.array_equal(after[:, [0, 1, 3]], before[:, [0, 1, 3]])
    assert (np.abs(after[:, SLOT] - before[:, SLOT]).reshape(after.shape[0], -1).max(-1) > 0).all()


def test_padding_behind_live_leaves_the_state_unchanged(model, tokens):
    """A segment of 20 tokens in a tile of 32: whatever tokens stand in the
    tile's other 12 places, the slot's stored state is the same bit for bit."""
    _dims, _key, cfg, params = model
    left = []
    for pad in (0, 199):
        padded = np.concatenate([tokens[:20], np.full(12, pad, np.int32)])[None]
        _, cache = jax.jit(lambda c, t: paged.paged_prefill_chunk(
            params, cfg, t, c, jnp.zeros((1, 0), jnp.int32), jnp.zeros((0,), jnp.int32), BS,
            jnp.zeros(1, jnp.int32), jnp.asarray([19]), jnp.asarray([20]), jnp.asarray([SLOT])))(
                paged.init_paged_cache(cfg, PCFG), jnp.asarray(padded))
        left.append(np.asarray(cache["power"][:, SLOT]))
    assert np.abs(left[0]).max() > 0 and np.array_equal(left[0], left[1])


# ---------------------------------------------------------------------------
# LLMEngine on a model with no pool of blocks
# ---------------------------------------------------------------------------


def test_engine_serves_the_references_tokens_and_allocates_no_block(model):
    """``LLMEngine`` end to end, four slots for seven requests, overlap on, a
    fixed prefill chunk, answers that end inside a window. No block is
    allocated, tabled, shipped or counted (the table has no column, the
    allocator is never asked, nothing is preempted: admission is by free slot),
    prompts longer than the chunk carry their state between calls, a slot given
    back starts its next request from nothing (seven requests on four slots: the
    served tokens are the reference's greedy continuation, which they could not
    be on a state that kept the last request's), and ``max_seq_len`` still
    bounds a request."""
    _dims, _key, cfg, params = model
    eng = LLMEngine(params, cfg, PCFG, decode_window=3, overlap=True, prefill_chunk=32, seed=1)
    assert eng.tables.shape == (4, 0) and eng._table_width == 0
    asked_for = []
    alloc = eng.alloc.alloc
    eng.alloc.alloc = lambda n: asked_for.append(n) or alloc(n)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, CONF["vocab_size"], n).tolist() for n in (5, 40, 17, 70, 9, 33, 12)]
    asked = [20, 31, 7, 22, 40, 11, 30]  # none a multiple of the window but one
    reqs = [eng.add_request(pr_, m) for pr_, m in zip(prompts, asked)]
    for _ in range(3000):
        if not (eng.active_count() or eng.waiting):
            break
        eng.step()
    assert [len(r.generated) for r in reqs] == asked
    s = eng.stats
    assert s["decode_blocks_table"] == 0 and s["decode_blocks_live"] == 0 and s["preemptions"] == 0
    assert not any(asked_for) and eng.alloc.available == 0 and not any(eng.slot_blocks)
    assert s["state_segments_carried"] > 0 and s["state_segments_fresh"] == len(prompts)
    assert s["state_slots_live"] > 0 and s["state_slots_table"] > 0 and s["spec_windows"] > 0
    # Three mirrors a dispatch: the table went over once, empty, and never again.
    assert eng._dev["tables"].shape == (4, 0) and s["h2d_skips"] >= s["steps"]
    T._assert_served_is_the_references_greedy_continuation(
        lambda seq: reference_logits(model, seq), prompts, reqs)
    too_long = eng.add_request([1] * 100, 40)
    assert "max_seq_len=128" in too_long.error
    fits = eng.add_request([1] * 100, 23)  # + the two windows' overshoot of 5: 128
    assert fits.error is None


def test_a_prefix_cache_with_state_by_slot_is_refused(model):
    _dims, _key, cfg, params = model
    with pytest.raises(ValueError, match="state by slot.*power"):
        LLMEngine(params, cfg, PCFG, enable_prefix_cache=True)
