"""The hybrid state-space decoder (``models/hybrid_ssm.py``) through the paged
programs and ``LLMEngine``, against the benchmark's plain float32 reference
(``chipbench/reference_hybrid_ssm.py``: the recurrence token by token) on seeded
weights, at a small size on the CPU.

Tolerances. Logits here are small (the embedding, which is the head too, is
drawn at a standard deviation of 0.004), so every comparison is of the largest
difference over the SPREAD of the reference's logits at that position. Program
and reference both run in float32 and differ in the order of their sums (the
tile's quadratic form against a token-by-token recurrence, an online softmax):
they read 1e-6 to 5e-6 of the spread apart. ``TOL`` leaves that two orders of
room; weights in bfloat16 read ~1e-1 and a state rounded to bfloat16 between
tokens ~5e-3, and both fail it (``test_bfloat16_fails_the_tolerance``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_hybrid_ssm as R
from chipbench import weights_hybrid_ssm as W
from ray_tpu.models import hybrid_ssm as hs
from ray_tpu.models import paged
from ray_tpu.models.paged import TRASH_BLOCK, PagedConfig
from ray_tpu.serve.llm_engine import LLMEngine

TOL = 5e-4
BS = 8
CONF = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=8,
    layer_types=["mamba", "mamba", "attention", "mamba"] * 2, num_attention_heads=4,
    num_key_value_heads=2, shared_intermediate_size=128, mamba_n_heads=8, mamba_d_head=16,
    mamba_d_state=128, mamba_d_conv=4, mamba_expand=2, mamba_n_groups=1,
    embedding_multiplier=12.0, logits_scaling=8.0, residual_multiplier=0.22,
    attention_multiplier=0.25, rms_norm_eps=1e-5)
SEED = 2**31 + 45
PCFG = PagedConfig(block_size=BS, num_blocks=33, max_batch=4, max_blocks_per_seq=8)
SLOT, BLOCKS = 2, list(range(1, 9))  # where the one sequence of the program tests lives
PROMPT, STEPS = 40, 6


def make(dtype=jnp.float32):
    dims = W.Dims.from_config(CONF)
    key = W.seed_key(SEED)
    params = jax.jit(lambda k: W.make_params(k, dims, dtype))(key)
    return dims, key, W.program_config(dims, dtype), params


@pytest.fixture(scope="module")
def model():
    return make()


@pytest.fixture(scope="module", params=["hybrid", "kda"])
def body(request):
    """Each model that keeps state by slot, for the engine's rules about such
    state: (its configuration, its parameters, ``reference(seq)`` -> the plain
    reference's logits [t, vocab], the names of its pools by slot). Both have a
    vocabulary of 256."""
    if request.param == "hybrid":
        dims, key, cfg, params = request.getfixturevalue("model")
        return cfg, params, lambda seq: np.asarray(
            R.stream_logits(key, jnp.asarray(seq)[None], dims, jnp.float32)[0]), ("ssm", "conv")
    import test_kda_moe as K  # the KDA / latent-attention expert decoder, at its tests' size

    made = K.make()
    return made[2], made[3], lambda seq: K.reference_logits(made, seq), K.STATE


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(3).integers(0, CONF["vocab_size"], PROMPT + STEPS).astype(np.int32)


@pytest.fixture(scope="module")
def ref_logits(model, tokens):
    dims, key, _cfg, _params = model
    return np.asarray(R.stream_logits(key, jnp.asarray(tokens)[None], dims, jnp.float32)[0])


def apart(got, ref) -> float:
    """The largest difference, over the spread of the reference's logits there."""
    return float((np.abs(got - ref).max(-1) / ref.std(-1)).max())


def chunk_call(params, cfg, cache, width: int, segs, pcfg=PCFG):
    """One call of the chunk program as ``LLMEngine._chunk_call`` lays it out:
    ``segs`` are (slot, its blocks, tokens, start, end); → (logits a segment, cache)."""
    tile = paged.chunk_tile(width, BS)
    n = width // tile
    toks = np.zeros((1, width), np.int32)
    trows = np.full((n, pcfg.max_blocks_per_seq), TRASH_BLOCK, np.int32)
    crow = np.full(width // BS, TRASH_BLOCK, np.int32)
    starts, last_idx, live = (np.zeros(n, np.int32) for _ in range(3))
    slot_of = np.full(n, pcfg.max_batch, np.int32)
    at = 0
    for k, (slot, blocks, full, start, end) in enumerate(segs):
        tiles = -(-(end - start) // tile)
        t0 = at // tile
        toks[0, at:at + end - start] = full[start:end]
        trows[t0:t0 + tiles, :len(blocks)] = blocks
        starts[t0:t0 + tiles] = start + tile * np.arange(tiles)
        live[t0:t0 + tiles] = np.minimum(tile, end - starts[t0:t0 + tiles])
        slot_of[t0:t0 + tiles] = slot
        under = blocks[start // BS:start // BS + tiles * tile // BS]
        crow[at // BS:at // BS + len(under)] = under
        last_idx[k] = at + end - start - 1
        at += tiles * tile
    assert at <= width
    logits, cache = jax.jit(lambda c, *a: paged.paged_prefill_chunk(params, cfg, a[0], c, a[1], a[2], BS, *a[3:]))(
        cache, *(jnp.asarray(a) for a in (toks, trows, crow, starts, last_idx, live, slot_of)))
    return np.asarray(logits)[:len(segs)], cache


def decode(params, cfg, cache, tokens, first: int, slot=SLOT, blocks=BLOCKS, round_state=None,
           state="ssm"):
    """Decode steps for ``tokens[first:]`` of the sequence in ``slot``, one
    token a slot through the cache; → (logits a step, cache). ``round_state``:
    the pool ``state`` is rounded to that dtype after every step."""
    tables = np.full((PCFG.max_batch, PCFG.max_blocks_per_seq), TRASH_BLOCK, np.int32)
    tables[slot, :len(blocks)] = blocks
    step = jax.jit(lambda tok, c, lens: paged.paged_decode_step(
        params, cfg, tok, c, jnp.asarray(tables), lens))
    out = []
    for at in range(first, len(tokens)):
        tok, lens = np.zeros(PCFG.max_batch, np.int32), np.zeros(PCFG.max_batch, np.int32)
        tok[slot], lens[slot] = tokens[at], at
        logits, cache = step(jnp.asarray(tok), cache, jnp.asarray(lens))
        if round_state is not None:
            cache = {**cache, state: cache[state].astype(round_state).astype(jnp.float32)}
        out.append(np.asarray(logits[slot]))
    return np.stack(out), cache


# How the prompt's 40 tokens reach the cache: (width, [(start, end), ..]) a call.
# Tiles are four blocks (32 tokens) of a call at least that wide.
TILINGS = {
    "one_tile": [(PROMPT, [(0, PROMPT)])],  # a width of its own: one tile of 40
    "two_tiles_the_second_partly_padding": [(64, [(0, PROMPT)])],
    "two_calls_the_second_carries_the_state": [(32, [(0, 32)]), (32, [(32, PROMPT)])],
    "three_calls_a_block_each_then_the_rest": [(8, [(0, 8)]), (8, [(8, 16)]), (32, [(16, PROMPT)])],
}


@pytest.mark.parametrize("tiling", list(TILINGS))
def test_prefill_then_decode_through_the_cache_agree_with_the_reference(model, tokens, ref_logits, tiling):
    """The prompt through the chunk program under each tiling (the state handed
    from tile to tile inside a call, and from the slot's stored rows between
    calls), then six decode steps through the cache: the logits of the prompt's
    last token and of every step are the reference's."""
    _dims, _key, cfg, params = model
    cache = paged.init_paged_cache(cfg, PCFG)
    for width, parts in TILINGS[tiling]:
        for start, end in parts:
            logits, cache = chunk_call(params, cfg, cache, width, [(SLOT, BLOCKS, tokens, start, end)])
    assert apart(logits[0], ref_logits[PROMPT - 1]) < TOL
    steps, _ = decode(params, cfg, cache, tokens, PROMPT)
    assert apart(steps, ref_logits[PROMPT:]) < TOL


def test_two_packed_segments_one_carried_and_one_fresh(model, tokens, ref_logits):
    """ONE call holds a later chunk of slot 2's prompt (it takes up the state an
    earlier call stored) and, behind it, the whole prompt of slot 0 (it starts
    from nothing, whatever slot 0's rows held): both read the reference's logits,
    and so do their decode steps."""
    dims, key, cfg, params = model
    other = np.random.default_rng(9).integers(0, CONF["vocab_size"], 20 + STEPS).astype(np.int32)
    ref_other = np.asarray(R.stream_logits(key, jnp.asarray(other)[None], dims, jnp.float32)[0])
    cache = paged.init_paged_cache(cfg, PCFG)
    # Slot 0's rows hold something: a request that ended there.
    cache = {**cache, **{name: cache[name].at[:, 0].set(0.5) for name in ("ssm", "conv")}}
    _, cache = chunk_call(params, cfg, cache, 32, [(SLOT, BLOCKS, tokens, 0, 32)])
    blocks0 = list(range(9, 13))
    logits, cache = chunk_call(params, cfg, cache, 64, [
        (SLOT, BLOCKS, tokens, 32, PROMPT), (0, blocks0, other, 0, 20)])
    assert apart(logits[0], ref_logits[PROMPT - 1]) < TOL
    assert apart(logits[1], ref_other[19]) < TOL
    steps, cache = decode(params, cfg, cache, tokens, PROMPT)
    assert apart(steps, ref_logits[PROMPT:]) < TOL
    steps, _ = decode(params, cfg, cache, other, 20, slot=0, blocks=blocks0)
    assert apart(steps, ref_other[20:]) < TOL


@pytest.mark.parametrize("what", ["weights", "state"])
def test_bfloat16_fails_the_tolerance(tokens, ref_logits, model, what):
    """The comparison tells precisions apart: weights (and activations) in
    bfloat16, or only the state ``S`` rounded to bfloat16 after every step,
    miss the float32 logits by far more than ``TOL``."""
    if what == "weights":
        dims, key, cfg, params = make(jnp.bfloat16)
        ref = np.asarray(R.stream_logits(key, jnp.asarray(tokens)[None], dims, jnp.bfloat16)[0])
        rounded = None
    else:
        (_dims, _key, cfg, params), ref, rounded = model, ref_logits, jnp.bfloat16
    cache = paged.init_paged_cache(cfg, PCFG)
    _, cache = chunk_call(params, cfg, cache, 64, [(SLOT, BLOCKS, tokens, 0, PROMPT)])
    if rounded is not None:
        cache = {**cache, "ssm": cache["ssm"].astype(rounded).astype(jnp.float32)}
    steps, _ = decode(params, cfg, cache, tokens, PROMPT, round_state=rounded)
    assert apart(steps, ref[PROMPT:]) > 4 * TOL


def test_a_decode_window_leaves_idle_and_prefilling_slots_alone(body, tokens):
    """Three decode steps in one program with slot 2 live, slot 1 idle (its rows
    hold what a finished request left) and slot 3 halfway through a chunked
    prefill (its table on the trash block, as the engine keeps it until the
    prefill ends; its device ``lens`` whatever an earlier window left): the
    state and the convolution's inputs of slots 0, 1 and 3 are bit for bit
    what they were, in every layer; slot 2's moved."""
    cfg, params, _reference, pools = body
    cache = paged.init_paged_cache(cfg, PCFG)
    cache = {**cache, **{name: cache[name].at[:, 1].set(0.25) for name in pools}}
    _, cache = chunk_call(params, cfg, cache, 64, [(SLOT, BLOCKS, tokens, 0, PROMPT)])
    _, cache = chunk_call(params, cfg, cache, 32, [(3, [9, 10, 11, 12, 13], tokens, 0, 32)])
    before = {name: np.asarray(cache[name]) for name in pools}
    assert all(np.abs(before[name][:, 3]).max() > 0 for name in before)
    tables = np.full((4, 8), TRASH_BLOCK, np.int32)
    tables[SLOT] = BLOCKS
    lens = jnp.asarray([0, 17, PROMPT, 30], jnp.int32)
    cur = jnp.asarray([0, 5, tokens[PROMPT], 7], jnp.int32)
    _, cache = jax.jit(lambda c: paged.paged_decode_loop(
        params, cfg, cur, c, jnp.asarray(tables), lens, jnp.zeros(4), jax.random.PRNGKey(0), 3))(cache)
    for name in before:
        after = np.asarray(cache[name])
        assert np.array_equal(after[:, [0, 1, 3]], before[name][:, [0, 1, 3]]), name
        assert (np.abs(after[:, SLOT] - before[name][:, SLOT]).reshape(after.shape[0], -1).max(-1) > 0).all()


def test_padding_behind_live_leaves_the_state_unchanged(model, tokens):
    """A segment of 20 tokens in a tile of 32: whatever tokens stand in the
    tile's other 12 places, the slot's stored state and convolution inputs are
    the same bit for bit, and they are what 20 tokens alone (a call one tile
    of 24 wide, 4 of padding) leave, to rounding."""
    _dims, _key, cfg, params = model
    left = []
    for pad in (0, 199):
        padded = np.concatenate([tokens[:20], np.full(40, pad, np.int32)])
        cache = paged.init_paged_cache(cfg, PCFG)
        # The call copies full[start:end] only; put the padding there by hand.
        tile = 32
        toks = padded[None, :tile]
        trows = np.full((1, 8), TRASH_BLOCK, np.int32)
        trows[0, :3] = BLOCKS[:3]
        crow = np.asarray(BLOCKS[:3] + [TRASH_BLOCK], np.int32)
        _, cache = jax.jit(lambda c, t: paged.paged_prefill_chunk(
            params, cfg, t, c, jnp.asarray(trows), jnp.asarray(crow), BS, jnp.zeros(1, jnp.int32),
            jnp.asarray([19]), jnp.asarray([20]), jnp.asarray([SLOT])))(cache, jnp.asarray(toks))
        left.append({name: np.asarray(cache[name][:, SLOT]) for name in ("ssm", "conv")})
    for name in ("ssm", "conv"):
        assert np.array_equal(left[0][name], left[1][name]), name
    cache = paged.init_paged_cache(cfg, PCFG)
    _, cache = chunk_call(params, cfg, cache, 24, [(SLOT, BLOCKS, tokens, 0, 20)])
    assert np.allclose(np.asarray(cache["ssm"][:, SLOT]), left[0]["ssm"], rtol=1e-4, atol=1e-6)
    assert np.allclose(np.asarray(cache["conv"][:, SLOT]), left[0]["conv"], rtol=1e-4, atol=1e-5)


def _assert_served_is_the_references_greedy_continuation(reference, prompts, reqs):
    """Every served token is the reference's own largest logit at its position,
    the reference being fed prompt + served tokens; a near-tie may go either
    way, so what is asserted is that the served token is within 1e-4 of the
    spread of the largest."""
    for prompt, req in zip(prompts, reqs):
        seq = np.asarray(prompt + req.generated, np.int32)
        ref = reference(seq)
        at = np.arange(len(prompt) - 1, len(seq) - 1)
        chosen = ref[at, np.asarray(req.generated)]
        deficit = (ref[at].max(-1) - chosen) / ref[at].std(-1)
        assert deficit.max() < 1e-4, (len(prompt), deficit.max())


def _spy(eng, monkeypatch):
    """Record what every program call runs: a decode window's (slot, slot
    generation, first position) for each live row, and a chunk call's (slot,
    generation, start, end) for each segment."""
    windows, chunks = [], []
    decode_fn, chunk_call_fn = eng._decode, eng._chunk_call

    def decode_spy(params, cur, cache, tables, lens, temps, key):
        live = np.asarray(tables)[:, 0] != TRASH_BLOCK
        windows.append([(i, eng._slot_gen[i], int(np.asarray(lens)[i])) for i in np.flatnonzero(live)])
        return decode_fn(params, cur, cache, tables, lens, temps, key)

    def chunk_spy(width, segs):
        chunks.append([(i, eng._slot_gen[i], start, end) for i, _req, _full, start, end in segs])
        return chunk_call_fn(width, segs)

    monkeypatch.setattr(eng, "_decode", decode_spy)
    monkeypatch.setattr(eng, "_chunk_call", chunk_spy)
    return windows, chunks


def test_engine_serves_the_references_tokens_and_runs_no_position_twice(body, monkeypatch):
    """``LLMEngine`` end to end, four slots for seven requests, overlap on, a
    fixed prefill chunk, answers that end inside a window, a pool so small
    that requests are preempted and resumed. Served tokens are the reference's
    greedy continuation. And the reason they can be: for every assignment of a
    slot, the chunk calls cover positions 0 .. prompt's end once, in order, and
    the decode windows that find the slot live start exactly where the one
    before them ended (a window more for each), so no live slot's state is
    advanced over a position twice; a preempted request comes back under a
    new assignment and from position 0."""
    cfg, params, reference, _pools = body
    p = PagedConfig(block_size=BS, num_blocks=20, max_batch=4, max_blocks_per_seq=16)
    eng = LLMEngine(params, cfg, p, decode_window=3, overlap=True, prefill_chunk=32, seed=1)
    windows, chunks = _spy(eng, monkeypatch)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, CONF["vocab_size"], n).tolist() for n in (5, 40, 17, 70, 9, 33, 12)]
    asked = [20, 31, 7, 22, 40, 11, 30]  # none a multiple of the window but one
    reqs = [eng.add_request(pr, m) for pr, m in zip(prompts, asked)]
    for _ in range(3000):
        if not (eng.active_count() or eng.waiting):
            break
        eng.step()
    assert [len(r.generated) for r in reqs] == asked
    s = eng.stats
    assert s["preemptions"] > 0 and s["spec_windows"] > 0 and s["windows_behind_prefill"] > 0
    assert s["state_segments_carried"] > 0 and s["state_segments_fresh"] >= len(prompts)
    _assert_served_is_the_references_greedy_continuation(reference, prompts, reqs)
    # Chunk calls: each assignment's segments tile [0, end) with no gap or overlap.
    by_assignment = {}
    for call in chunks:
        for slot, gen, start, end in call:
            assert start == by_assignment.get((slot, gen), 0), (slot, gen, start)
            by_assignment[(slot, gen)] = end
    # Windows: an assignment's first window starts where its prefill ended; each
    # later one where the last ended. (A window behind a slot that the host has
    # freed since still finds the old table: it is not in the record, because
    # the slot's generation moved on only at the NEXT assignment; it runs past
    # the transcript on a state nobody reads again.)
    at = {}
    for window in windows:
        for slot, gen, first in window:
            want = at.get((slot, gen), by_assignment.get((slot, gen)))
            assert first == want, (slot, gen, first, want)
            at[(slot, gen)] = first + 3


def test_a_prefix_cache_with_state_by_slot_is_refused(body):
    cfg, params, _reference, pools = body
    with pytest.raises(ValueError, match=f"state by slot.*{', '.join(pools)}"):
        LLMEngine(params, cfg, PCFG, enable_prefix_cache=True)
    with pytest.raises(ValueError, match="state by slot"):
        paged.paged_prefill(params, cfg, jnp.zeros((1, 8), jnp.int32),
                            paged.init_paged_cache(cfg, PCFG), jnp.asarray([1]), BS)


def test_the_pools_are_declared_each_with_its_own_layers_and_unit(model):
    _dims, _key, cfg, _params = model
    pools = paged.paged_model(cfg).pools
    assert {k: (v.layers, v.unit) for k, v in pools.items()} == {
        "k": (2, "blocks"), "v": (2, "blocks"), "ssm": (6, "slots"), "conv": (6, "slots")}
    cache = paged.init_paged_cache(cfg, PCFG)
    assert cache["k"].shape == (2, 33, BS, 2 * 16) and cache["ssm"].shape == (6, 4, 128, 128)
    assert cache["conv"].shape == (6, 4, 3, 128 + 2 * 128) and cache["ssm"].dtype == jnp.float32
    assert paged.slot_pools(cfg) == ("ssm", "conv")
    full = hs.HybridSSMConfig()  # the published model: a period of ten, four times
    assert full.period == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4 and full.periods == 4
    assert {k: v.layers for k, v in paged.paged_model(full).pools.items()} == {
        "k": 4, "v": 4, "ssm": 36, "conv": 36}


LENS = {"some_skipped": [0, 3, 0, 0, 5, 1], "the_first_skipped": [0, 0, 2, 9, 0, 4],
        "one_live": [0, 0, 0, 7, 0, 0], "all_live": [1, 2, 3, 4, 5, 6], "none_live": [0] * 6}


@pytest.mark.parametrize("lens", list(LENS))
def test_ssm_state_update_kernel_reads_the_plain_forms_numbers(lens):
    """The kernel under the Pallas interpreter against the plain form, in the
    second of three layers of a flat pool: the live slots' states and outputs
    agree to rounding; a skipped slot's state is bit for bit what it was and its
    output zeros; the other layers' rows are untouched."""
    from ray_tpu.ops import ssm

    rng = np.random.default_rng(1)
    b, n, hp = 6, 128, 256
    pool = jnp.asarray(rng.normal(size=(3 * b, n, hp)), jnp.float32)
    lens_ = jnp.asarray(LENS[lens], jnp.int32)
    decay = jnp.asarray(rng.uniform(0.1, 1, (b, hp)), jnp.float32)
    dx, B, C = (jnp.asarray(rng.normal(size=s), jnp.float32) for s in ((b, hp), (b, n), (b, n)))
    want_pool, want_y = ssm.reference_ssm_update(pool, jnp.int32(b), lens_, decay, dx, B, C)
    got_pool, got_y = jax.jit(lambda *a: ssm._ssm_state_update(*a, interpret=True))(
        pool, jnp.int32(b), lens_, decay, dx, B, C)
    assert np.allclose(got_pool, want_pool, rtol=1e-5, atol=1e-5)
    assert np.allclose(got_y, want_y, rtol=1e-4, atol=1e-4)
    skipped = np.flatnonzero(np.asarray(LENS[lens]) == 0)
    assert np.array_equal(np.asarray(got_pool)[b + skipped], np.asarray(pool)[b + skipped])
    assert not np.asarray(got_y)[skipped].any()
    assert np.array_equal(np.asarray(got_pool)[:b], np.asarray(pool)[:b])
    assert np.array_equal(np.asarray(got_pool)[2 * b:], np.asarray(pool)[2 * b:])


# A chunk call's tiles for ``ssm_chunk_scan``: a tile is (slot or None for nobody's,
# its first position, its real tokens). Slots 0-3 of the second of three layers;
# slot 1's row holds the state an earlier call left, every other row 0.5.
SCANS = {
    "a_full_tile": [(2, 0, 64)],
    "a_tile_partly_padding": [(2, 0, 23)],
    "nobodys_tile_between_two_segments": [(0, 0, 64), (0, 64, 9), (None, 0, 0), (3, 0, 40)],
    "a_fresh_segment_of_three_tiles": [(2, 0, 64), (2, 64, 64), (2, 128, 17), (None, 0, 0)],
    "a_stored_row_taken_up_behind_a_segment_from_nothing": [
        (1, 128, 64), (1, 192, 30), (2, 0, 64), (2, 64, 5)],
    "nobody_at_all": [(None, 0, 0), (None, 0, 0)],
}


@pytest.mark.parametrize("tiles", list(SCANS))
def test_ssm_chunk_scan_kernel_reads_the_plain_forms_numbers(tiles):
    """The chunk-scan kernel under the Pallas interpreter against the plain
    form, in the second of three layers of a flat pool, tiles of 64 and heads
    of 64: ``y`` and the WHOLE pool agree to rounding. A tile partly padding
    leaves the state after its last real token (whatever stands behind it); a
    nobody's tile has zeros for ``y`` and touches no row; a fresh segment begins
    from nothing though its slot's row holds 0.5, a carried one from its row;
    rows of slots no segment ends in, and the other layers', are bit for bit
    what they were."""
    from ray_tpu.ops import ssm

    rng = np.random.default_rng(7)
    slots, N, h, p, T = 4, 128, 4, 64, 64
    spec = SCANS[tiles]
    n = len(spec)
    pool = np.full((3 * slots, N, h * p), 0.5, np.float32)
    pool[slots + 1] = rng.normal(size=(N, h * p))
    slot_of = np.asarray([slots if s is None else s for s, _, _ in spec], np.int32)
    starts = np.asarray([a for _, a, _ in spec], np.int32)
    live = jnp.asarray([ln for _, _, ln in spec], jnp.int32)
    fresh, cont, last = hs._segments(jnp.asarray(starts)[:, None], jnp.asarray(slot_of), slots)
    row = jnp.where(slot_of < slots, slots + slot_of, 3 * slots).astype(jnp.int32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (n, T, h)), jnp.float32)
    A = jnp.asarray(rng.uniform(1, 16, h), jnp.float32)
    xs, B, C = (jnp.asarray(rng.normal(size=s), jnp.float32)
                for s in ((n, T, h * p), (n, T, N), (n, T, N)))
    args = (jnp.asarray(pool), row, fresh, cont, last, live, dt, A, xs, B, C)
    assert ssm._scan_tiles(args[0], dt, xs)
    want_pool, want_y = jax.jit(ssm.reference_ssm_chunk_scan)(*args)
    got_pool, got_y = jax.jit(lambda *a: ssm._ssm_chunk_scan(*a, interpret=True))(*args)
    assert np.allclose(got_y, want_y, rtol=1e-4, atol=1e-4)
    assert np.allclose(got_pool, want_pool, rtol=1e-5, atol=1e-5)
    got_pool, got_y = np.asarray(got_pool), np.asarray(got_y)
    ended = {s for (s, _, _), e in zip(spec, np.asarray(last)) if e}
    kept = [r for r in range(3 * slots) if r - slots not in ended]
    assert np.array_equal(got_pool[kept], pool[kept])
    assert all(np.abs(got_pool[slots + s] - pool[slots + s]).max() > 0 for s in ended)
    assert not got_y[np.asarray(live) == 0].any()
    if tiles == "a_tile_partly_padding":
        # The state is the state after the 23rd token: other tokens behind it change nothing.
        other = (args[0], row, fresh, cont, last, live, dt.at[:, 23:].set(0.05), A,
                 xs.at[:, 23:].set(3.0), B.at[:, 23:].set(-1.0), C)
        again, _ = jax.jit(lambda *a: ssm._ssm_chunk_scan(*a, interpret=True))(*other)
        assert np.array_equal(np.asarray(again), got_pool)


def test_packed_paged_attend_reads_the_plain_forms_numbers():
    """Heads of 64 side by side on the lanes, through the decode attention
    kernel (interpreter) as ONE wide head: the plain form's numbers, at the
    model's own scale, an idle slot included."""
    from ray_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(0)
    b, H, KV, HD, W, P = 4, 8, 2, 64, 6, 40
    q = jnp.asarray(rng.normal(size=(b, H, HD)), jnp.float32)
    ck, cv = (jnp.asarray(rng.normal(size=(P, 16, KV * HD)), jnp.float32) for _ in range(2))
    tables = jnp.asarray(rng.permutation(np.arange(1, P))[:b * W].reshape(b, W), jnp.int32)
    lens = jnp.asarray([0, 17, 95, 40], jnp.int32)
    plain = pa.packed_paged_attention(q, ck, cv, tables, lens, 0.015625)
    kernel = pa.packed_paged_attention(q, ck, cv, tables, lens, 0.015625, interpret=True)
    assert np.allclose(kernel, plain, rtol=1e-5, atol=1e-5)
    split = pa.reference_paged_attention(
        q, ck.reshape(P, 16, KV, HD), cv.reshape(P, 16, KV, HD), tables, lens, 0.015625)
    assert np.array_equal(plain, split)


def test_the_counters_reach_the_report_and_the_registry(model):
    """``state_slots_live`` / ``_table`` a dispatched window and
    ``state_segments_carried`` / ``_fresh`` a chunk call, in ``stats``, in
    ``report_state()`` beside the pools the engine holds, and in the registry."""
    from ray_tpu.serve.metrics import serve_metrics

    _dims, _key, cfg, params = model
    eng = LLMEngine(params, cfg, PCFG, decode_window=2, prefill_chunk=16, seed=1)
    eng.metrics_tags = {"deployment": "state-counters", "replica": "r0"}
    rng = np.random.default_rng(2)
    eng.generate_batch([rng.integers(0, 256, n).tolist() for n in (10, 40)], 4)
    s = eng.stats
    # 10 tokens: one fresh segment. 40: chunks of 16, 16, 8: one fresh, two carried.
    assert (s["state_segments_fresh"], s["state_segments_carried"]) == (2, 2)
    assert s["state_slots_table"] == 4 * s["steps"] and 0 < s["state_slots_live"] <= 2 * s["steps"]
    # A step's record says how many rows the window it dispatched moves.
    assert sum(r["state_slots_live"] for r in eng.recorder.steps) == s["state_slots_live"]
    snap = eng.report_state()
    assert snap["state"]["pools"] == ["ssm", "conv"]
    assert snap["state"]["slots_live_pct"] == 100.0 * s["state_slots_live"] / s["state_slots_table"]
    assert snap["state"]["segments_carried"] == 2
    assert snap["pools"]["ssm"] == {"shape": [6, 4, 128, 128], "dtype": "float32",
                                    "bytes": 6 * 4 * 128 * 128 * 4, "unit": "slots"}
    assert snap["pools"]["k"]["unit"] == "blocks"
    m = serve_metrics()
    for counter, name, want in (
            (m.engine_state_slots_live, "serve_engine_state_slots_live_total", s["state_slots_live"]),
            (m.engine_state_slots_table, "serve_engine_state_slots_table_total", s["state_slots_table"]),
            (m.engine_state_segments_carried, "serve_engine_state_segments_carried_total", 2),
            (m.engine_state_segments_fresh, "serve_engine_state_segments_fresh_total", 2)):
        assert counter.name == name
        assert [value for _n, _t, _d, tags, value in counter._drain()
                if dict(tags)["deployment"] == "state-counters"] == [want]


def test_a_model_without_state_counts_none_and_reports_its_pools():
    from ray_tpu.models import transformer as tf

    cfg = tf.TransformerConfig.tiny(dtype=jnp.float32)
    eng = LLMEngine(tf.init_params(jax.random.PRNGKey(0), cfg), cfg, PCFG, decode_window=2, seed=1)
    eng.generate_batch([[1, 2, 3]], 3)
    assert eng.stats["state_slots_table"] == 0 and eng.stats["state_segments_fresh"] == 0
    snap = eng.report_state()
    assert snap["state"]["pools"] == [] and set(snap["pools"]) == {"k", "v"}
    assert snap["pools"]["k"]["unit"] == "blocks"


@pytest.mark.parametrize("which", ["hybrid", "dense"])
def test_a_prefill_program_compiles_once_whoever_made_its_cache(model, which):
    """The chunk program meets a cache (and a ``cur``) of three makers: fresh,
    the decode window's output (uncommitted: that program is lowered from shapes
    alone) and a chunk call's own (committed to the parameters' device). Once
    every program has run once, none compiles again, in whichever order they
    follow each other: a second compilation would land on the served path."""
    from ray_tpu.models import transformer as tf
    from ray_tpu.util import compile_tracker

    if which == "hybrid":
        _dims, _key, cfg, params = model
    else:
        cfg = tf.TransformerConfig.tiny(dtype=jnp.float32)
        params = tf.init_params(jax.random.PRNGKey(0), cfg)
    leaves, tree = jax.tree.flatten(params)
    p = PagedConfig(block_size=BS, num_blocks=65, max_batch=4, max_blocks_per_seq=16)
    # Parameters made in the program's layout, as a replica makes them: committed.
    eng = LLMEngine(lambda: jax.tree.unflatten(tree, [a + 0 for a in leaves]), cfg, p,
                    decode_window=2, overlap=True, prefill_chunk=16, seed=1)
    compile_tracker.install()
    rng = np.random.default_rng(5)

    def serve(*lengths):
        eng.generate_batch([rng.integers(0, 256, n).tolist() for n in lengths], 3)
        return compile_tracker.snapshot()["compiles"]

    serve(5)  # fresh cache -> a prefill, then decode windows
    warmed = serve(40, 50)  # long prompts: chunk after chunk, chunk after a window
    assert serve(6) == warmed  # a prefill behind windows and chunk calls
    assert serve(41, 7, 51) == warmed


def test_a_burst_is_admitted_at_once_in_as_many_chunk_calls_as_it_needs(model):
    """Eight prompts arrive at once at an engine with a fixed chunk width of 64
    (tiles of 32: two prompts of under 32 tokens a call) and eight free slots:
    the first iteration admits them all, as the engine does for every model,
    in four packed chunk calls before ONE window, each segment's state begun
    from nothing in its own slot; and the served tokens are the reference's
    greedy continuation, so no call disturbed another call's slots."""
    dims, key, cfg, params = model
    p = PagedConfig(block_size=BS, num_blocks=65, max_batch=8, max_blocks_per_seq=8)
    eng = LLMEngine(params, cfg, p, decode_window=2, overlap=True, prefill_chunk=64, seed=1)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, CONF["vocab_size"], n).tolist() for n in (20, 30, 9, 31, 25, 12, 28, 17)]
    reqs = [eng.add_request(pr, 5) for pr in prompts]
    for _ in range(200):
        if not (eng.active_count() or eng.waiting):
            break
        eng.step()
    assert all(len(r.generated) == 5 for r in reqs)
    first = eng.recorder.steps[0]
    assert first["chunks"] == 4 and first["segments"] == 8 and first["state_slots_live"] == 8
    assert eng.stats["prefill_chunks"] == 4 and eng.stats["state_segments_fresh"] == 8
    assert eng.stats["state_segments_carried"] == 0
    _assert_served_is_the_references_greedy_continuation(
        lambda seq: np.asarray(R.stream_logits(key, jnp.asarray(seq)[None], dims, jnp.float32)[0]),
        prompts, reqs)
