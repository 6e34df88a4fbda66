"""The KDA / latent-attention expert decoder (``models/kda_moe.py``) through the
paged programs, against the benchmark's plain float32 reference
(``chipbench/reference_kda_moe.py``: the delta rule token by token, latent
attention expanded) on seeded weights, at a small size on the CPU. The engine's
rules for state kept by slot are ``tests/test_hybrid_ssm.py``'s and
``tests/test_engine_phases.py``'s, which run this body as a further case.

Tolerances. Every comparison of logits is of the largest difference over the
SPREAD of the reference's logits at that position. Program and reference both
run in float32 and differ in the order of their sums (a tile's triangular solve
against a token-by-token recurrence, an online softmax, the absorbed against the
expanded attention): they read 3e-6 to 6e-6 of the spread apart. ``TOL`` leaves
that over an order of room; a state rounded to bfloat16 between tokens reads
~1e-2 and fails it (``test_a_state_in_bfloat16_fails_the_tolerance``).
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_hybrid_ssm as T  # the chunk call as the engine lays it out, the tilings
import test_latent_moe as ML  # the expert kernel against the grouped form, the routings
from chipbench import reference_kda_moe as R
from chipbench import weights_kda_moe as W
from ray_tpu.models import kda_moe as km
from ray_tpu.models import latent_moe as lm
from ray_tpu.models import paged
from ray_tpu.models.hybrid_ssm import _segments
from ray_tpu.ops import kda

TOL = 2e-4
CONF = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=7, first_k_dense_replace=1,
    layer_group_size=3, num_attention_heads=2, head_dim=32, short_conv_kernel_size=4,
    kda_lower_bound=-5, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
    num_experts_published=16, num_experts=8, experts_held_first=4, num_experts_per_tok=2,
    n_group=4, topk_group=2, routed_scaling_factor=2.5, rope_theta=6e6, rms_norm_eps=1e-6)
SEED = 2**31 + 50
CAL_T = 64  # tokens a sequence the selection bias is fitted on
PROMPT, STEPS = T.PROMPT, T.STEPS
STATE = ("kda", "conv")


def make(conf=CONF):
    """(dims, key, the program's configuration, its parameters, the bias): float32."""
    dims = W.Dims.from_config(conf)
    key = W.seed_key(SEED)
    bias = W.calibrate(key, dims, jnp.float32, CAL_T)
    params = jax.jit(lambda k, b: W.make_params(k, dims, jnp.float32, b))(key, bias)
    return dims, key, W.program_config(dims, jnp.float32), params, bias


@pytest.fixture(scope="module")
def model():
    return make()


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(3).integers(0, CONF["vocab_size"], PROMPT + STEPS).astype(np.int32)


def reference_logits(model, seq, **kw):
    dims, key, _cfg, _params, bias = model
    return np.asarray(R.stream_logits(key, jnp.asarray(seq)[None], bias, dims, jnp.float32, **kw)[0])


@pytest.fixture(scope="module")
def ref_logits(model, tokens):
    return reference_logits(model, tokens)


@pytest.mark.parametrize("tiling", list(T.TILINGS))
def test_prefill_then_decode_through_the_pools_agree_with_the_reference(model, tokens, ref_logits, tiling):
    """The prompt through the chunk program under each tiling (the state handed
    from tile to tile inside a call, and from the slot's stored rows between
    CALLS: a prompt longer than the chunk width, which no cell runs), then six
    decode steps through the pools: the LOGITS of the prompt's last token and
    of every step are the reference's full forward pass's."""
    _dims, _key, cfg, params, _bias = model
    cache = paged.init_paged_cache(cfg, T.PCFG)
    for width, parts in T.TILINGS[tiling]:
        for start, end in parts:
            logits, cache = T.chunk_call(params, cfg, cache, width, [(T.SLOT, T.BLOCKS, tokens, start, end)])
    assert T.apart(logits[0], ref_logits[PROMPT - 1]) < TOL
    steps, _ = T.decode(params, cfg, cache, tokens, PROMPT)
    assert T.apart(steps, ref_logits[PROMPT:]) < TOL


def test_two_packed_segments_one_carried_and_one_fresh(model, tokens, ref_logits):
    """ONE call holds a later chunk of slot 2's prompt (it takes up the state an
    earlier call stored) and, behind it, the whole prompt of slot 0 (it starts
    from nothing, whatever slot 0's rows held): both read the reference's logits."""
    _dims, _key, cfg, params, _bias = model
    other = np.random.default_rng(9).integers(0, CONF["vocab_size"], 20).astype(np.int32)
    cache = paged.init_paged_cache(cfg, T.PCFG)
    cache = {**cache, **{name: cache[name].at[:, 0].set(0.5) for name in STATE}}
    _, cache = T.chunk_call(params, cfg, cache, 32, [(T.SLOT, T.BLOCKS, tokens, 0, 32)])
    logits, cache = T.chunk_call(params, cfg, cache, 64, [
        (T.SLOT, T.BLOCKS, tokens, 32, PROMPT), (0, list(range(9, 13)), other, 0, 20)])
    assert T.apart(logits[0], ref_logits[PROMPT - 1]) < TOL
    assert T.apart(logits[1], reference_logits(model, other)[19]) < TOL


def test_a_state_in_bfloat16_fails_the_tolerance(model, tokens, ref_logits):
    """The comparison sees the state's precision: ``S`` rounded to bfloat16
    after the prefill and after every decode step misses the float32 logits by
    far more than ``TOL``, in the program and in the reference's own recurrence."""
    _dims, _key, cfg, params, _bias = model
    cache = paged.init_paged_cache(cfg, T.PCFG)
    _, cache = T.chunk_call(params, cfg, cache, 64, [(T.SLOT, T.BLOCKS, tokens, 0, PROMPT)])
    cache = {**cache, "kda": cache["kda"].astype(jnp.bfloat16).astype(jnp.float32)}
    steps, _ = T.decode(params, cfg, cache, tokens, PROMPT, round_state=jnp.bfloat16, state="kda")
    assert T.apart(steps, ref_logits[PROMPT:]) > 4 * TOL
    rounded = reference_logits(model, tokens, state_dtype=jnp.bfloat16)
    assert T.apart(rounded[PROMPT:], ref_logits[PROMPT:]) > 4 * TOL


def test_the_pools_are_declared_each_with_its_own_layers_and_unit(model):
    """ONE pool of latent rows with a layer a period and NO leading layer, beside
    two pools by slot with the leading layer and two a period."""
    _dims, _key, cfg, _params, _bias = model
    pools = paged.paged_model(cfg).pools
    assert {k: (v.layers, v.unit, v.lead) for k, v in pools.items()} == {
        "rows": (2, "blocks", 0), "kda": (5, "slots", None), "conv": (5, "slots", None)}
    cache = paged.init_paged_cache(cfg, T.PCFG)
    assert cache["rows"].shape == (2, 33, T.BS, 128) and cache["kda"].shape == (5, 4, 2, 32, 32)
    assert cache["conv"].shape == (5, 4, 3, 3 * 64) and cache["kda"].dtype == jnp.float32
    assert paged.slot_pools(cfg) == STATE and cfg.period == ("kda", "latent", "kda")
    served = km.KDAMoEConfig(num_hidden_layers=7, first_k_dense_replace=1, held_count=128)
    assert served.period == ("kda",) * 4 + ("latent", "kda") and served.periods == 1
    assert {k: (v.layers, v.row) for k, v in paged.paged_model(served).pools.items()} == {
        "rows": (1, (640,)), "kda": (6, (32, 128, 128)), "conv": (6, (3, 12288))}
    with pytest.raises(ValueError, match="whole periods"):  # the published 42 = 2 + 40
        km.KDAMoEConfig(num_hidden_layers=42, first_k_dense_replace=2)


# ---------------------------------------------------------------------------
# ops/kda.py
# ---------------------------------------------------------------------------

LENS = {"some_skipped": [0, 3, 0, 0, 5, 1], "the_first_skipped": [0, 0, 2, 9, 0, 4],
        "one_live": [0, 0, 0, 7, 0, 0], "all_live": [1, 2, 3, 4, 5, 6], "none_live": [0] * 6}


def _unit(x):
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


@pytest.mark.parametrize("lens", list(LENS))
def test_kda_state_update_kernel_reads_the_plain_forms_numbers(lens):
    """The kernel under the Pallas interpreter against the plain form, in the
    second of three layers of a flat pool: the live slots' states and outputs
    agree to rounding; an idle row's state is bit for bit what it was and its
    output zeros; the other layers' rows are untouched."""
    rng = np.random.default_rng(1)
    b, H, K = 6, 3, 128
    pool = jnp.asarray(rng.normal(size=(3 * b, H, K, K)), jnp.float32)
    lens_ = jnp.asarray(LENS[lens], jnp.int32)
    a = jnp.asarray(rng.uniform(0.01, 1, (b, H, K)), jnp.float32)
    k, q = (jnp.asarray(_unit(rng.normal(size=(b, H, K))), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(b, H, K)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (b, H)), jnp.float32)
    assert kda._tiles(pool)
    want_pool, want_o = kda.reference_kda_update(pool, jnp.int32(b), lens_, a, k, q, v, beta)
    got_pool, got_o = jax.jit(lambda *x: kda._kda_state_update(*x, interpret=True))(
        pool, jnp.int32(b), lens_, a, k, q, v, beta)
    assert np.allclose(got_pool, want_pool, rtol=1e-5, atol=1e-5)
    assert np.allclose(got_o, want_o, rtol=1e-4, atol=1e-4)
    skipped = np.flatnonzero(np.asarray(LENS[lens]) == 0)
    assert np.array_equal(np.asarray(got_pool)[b + skipped], np.asarray(pool)[b + skipped])
    assert not np.asarray(got_o)[skipped].any()
    assert np.array_equal(np.asarray(got_pool)[:b], np.asarray(pool)[:b])
    assert np.array_equal(np.asarray(got_pool)[2 * b:], np.asarray(pool)[2 * b:])


def test_kda_update_is_the_delta_rule_as_written():
    """The plain form against the four lines of the equation, one slot one head."""
    rng = np.random.default_rng(2)
    K = 16
    S = rng.normal(size=(K, K))
    a, k, beta = rng.uniform(0.1, 1, K), _unit(rng.normal(size=K)), 0.7
    q, v = rng.normal(size=K), rng.normal(size=K)
    S1 = np.diag(a) @ S
    u = v - S1.T @ k
    S2 = S1 + beta * np.outer(k, u)
    f = lambda x: jnp.asarray(x, jnp.float32)[None, None]  # noqa: E731
    pool, o = kda.reference_kda_update(f(S), jnp.int32(0), jnp.ones(1, jnp.int32), f(a), f(k), f(q), f(v),
                                       jnp.full((1, 1), beta, jnp.float32))
    assert np.allclose(pool[0, 0], S2, atol=1e-5) and np.allclose(o[0, 0], S2.T @ q, atol=1e-5)


# A chunk call's tiles for ``kda_chunk_scan``: a tile is (slot or None for nobody's,
# its first position, its real tokens). Slots 0-3 of the second of three layers;
# slot 1's row holds the state an earlier call left, every other row 0.5.
SCANS = {
    "a_full_tile": [(2, 0, 64)],
    "a_segment_that_ends_mid_tile": [(2, 0, 23)],
    "tiles_with_live_0_between_two_segments": [(0, 0, 64), (0, 64, 9), (None, 0, 0), (3, 0, 40)],
    "a_fresh_segment_of_three_tiles": [(2, 0, 64), (2, 64, 64), (2, 128, 17), (None, 0, 0)],
    "a_carried_state_behind_a_segment_from_nothing": [(1, 128, 64), (1, 192, 30), (2, 0, 64), (2, 64, 5)],
    "nobody_at_all": [(None, 0, 0), (None, 0, 0)],
}


@pytest.mark.parametrize("tile_of", [64, 8])
@pytest.mark.parametrize("tiles", list(SCANS))
def test_kda_chunk_scan_is_the_recurrence_token_by_token(tiles, tile_of):
    """The chunked form (tiles of 64 in sub-tiles of 16, and tiles of 8 in one)
    against the recurrence a token at a time, in the second of three layers of a
    flat pool, log decays from -0.001 down to -4.9 a token (sixty-four of the
    last are exp(-314): the sub-tiles' reason): ``o`` and the WHOLE pool agree
    to rounding. A segment that ends mid-tile leaves the state after its last
    real token, whatever stands behind it; a tile with ``live`` 0 has zeros for
    ``o`` and touches no row; a fresh segment begins from nothing though its
    slot's row holds 0.5, a carried one from its row; rows no segment ends in,
    and the other layers', are bit for bit what they were."""
    rng = np.random.default_rng(7)
    slots, H, K, C = 4, 2, 32, tile_of
    scale = 64 // C
    spec = [(s, a // scale, -(-ln // scale)) for s, a, ln in SCANS[tiles]]
    n = len(spec)
    pool = np.full((3 * slots, H, K, K), 0.5, np.float32)
    pool[slots + 1] = rng.normal(size=(H, K, K))
    slot_of = np.asarray([slots if s is None else s for s, _, _ in spec], np.int32)
    starts = np.asarray([a for _, a, _ in spec], np.int32)
    live = jnp.asarray([ln for _, _, ln in spec], jnp.int32)
    fresh, cont, last = _segments(jnp.asarray(starts)[:, None], jnp.asarray(slot_of), slots)
    row = jnp.where(slot_of < slots, slots + slot_of, 3 * slots).astype(jnp.int32)
    g = jnp.asarray(-np.exp(rng.uniform(np.log(1e-3), np.log(4.9), (n, C, H, K))), jnp.float32)
    q, k = (jnp.asarray(_unit(rng.normal(size=(n, C, H, K))), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(n, C, H, K)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (n, C, H)), jnp.float32)
    args = (jnp.asarray(pool), row, fresh, cont, last, live, g, q, k, v, beta)
    got_pool, got_o = (np.asarray(x) for x in jax.jit(kda.kda_chunk_scan)(*args))
    want_pool, S = pool.astype(np.float64), None
    for t, (s, start, ln) in enumerate(spec):
        if s is None:
            assert not got_o[t].any()
            continue
        if start == 0 or not bool(cont[t]):
            S = np.zeros((H, K, K)) if start == 0 else pool[slots + s].astype(np.float64)
        for i in range(ln):  # the equation, a token at a time, in float64
            S = np.exp(np.asarray(g[t, i], np.float64))[..., None] * S
            u = np.asarray(v[t, i]) - np.einsum("hkv,hk->hv", S, np.asarray(k[t, i]))
            S = S + np.asarray(beta[t, i])[:, None, None] * np.asarray(k[t, i])[..., None] * u[:, None, :]
            want_o = np.einsum("hkv,hk->hv", S, np.asarray(q[t, i]))
            assert np.allclose(got_o[t, i], want_o, atol=2e-4), (t, i)
        want_pool[slots + s] = S
    assert np.allclose(got_pool, want_pool, atol=2e-4)
    ended = {s for (s, _, _), e in zip(spec, np.asarray(last)) if e}
    kept = [r for r in range(3 * slots) if r - slots not in ended]
    assert np.array_equal(got_pool[kept], pool[kept])
    if tiles == "a_segment_that_ends_mid_tile":
        ln = spec[0][2]
        other = (args[0], row, fresh, cont, last, live, g.at[:, ln:].set(-3.0), q, k.at[:, ln:].set(0.3),
                 v.at[:, ln:].set(3.0), beta.at[:, ln:].set(0.9))
        again, _ = jax.jit(kda.kda_chunk_scan)(*other)
        assert np.array_equal(np.asarray(again), got_pool)


# ---------------------------------------------------------------------------
# The expert layer: groups, the bias, the shares
# ---------------------------------------------------------------------------


def test_the_router_with_groups_and_bias_is_the_references(model):
    """``latent_moe.route`` on a layer with a bias and groups against the
    reference's selection and gates: the same experts (as sets) and the same
    gate for each; the bias moves the selection and never a gate."""
    dims, key, cfg, params, bias = model
    rng = np.random.default_rng(5)
    y = jnp.asarray(rng.normal(size=(200, CONF["hidden_size"])), jnp.float32)
    lp = {"router": W.moe_params(key, 3, dims)["router"], "expert_bias": bias[2]}
    experts, gates = lm.route(y, lp, cfg)
    scores = jax.nn.sigmoid(jnp.dot(y, lp["router"], precision="highest"))
    want = np.asarray(W.select(scores, bias[2], dims))
    want_gates = np.asarray(R.gates(scores, jnp.asarray(want), dims))
    order, want_order = np.argsort(np.asarray(experts), -1), np.argsort(want, -1)
    assert np.array_equal(np.take_along_axis(np.asarray(experts), order, -1),
                          np.take_along_axis(want, want_order, -1))
    assert np.allclose(np.take_along_axis(np.asarray(gates), order, -1),
                       np.take_along_axis(want_gates, want_order, -1), rtol=1e-5)
    # Every choice lies in the two groups (of four) a token kept; a large bias on expert 0
    # brings it in everywhere, and its gate is still its own score's share.
    groups = np.asarray(experts) // (dims.experts // dims.groups)
    assert all(len(set(g)) <= dims.top_groups for g in groups)
    pushed = {**lp, "expert_bias": bias[2].at[0].set(10.0)}
    e2, g2 = lm.route(y, pushed, cfg)
    assert (np.asarray(e2) == 0).any(-1).all()
    top = np.take_along_axis(np.asarray(scores), np.asarray(e2), -1)
    assert np.allclose(np.asarray(g2), 2.5 * top / top.sum(-1, keepdims=True), rtol=1e-5)


def test_a_router_with_neither_groups_nor_bias_routes_as_it_always_did():
    """Pangu's ``route`` (``n_group`` 1, no ``expert_bias``): the largest scores of
    all, their own normalised gates, digit for digit what the one ``top_k`` gives."""
    cfg = lm.LatentMoEConfig.tiny()
    rng = np.random.default_rng(6)
    y = jnp.asarray(rng.normal(size=(50, cfg.hidden_size)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(cfg.hidden_size, cfg.n_routed_experts)), jnp.float32)
    experts, gates = lm.route(y, {"router": router}, cfg)
    scores = jax.nn.sigmoid(jnp.dot(y, router, preferred_element_type=jnp.float32))
    top, want = jax.lax.top_k(scores, cfg.num_experts_per_tok)
    assert np.array_equal(experts, want)
    assert np.array_equal(gates, cfg.routed_scaling_factor * top / jnp.sum(top, axis=-1, keepdims=True))


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer(model):
    """The reference's expert layer cut four ways (experts 0-3, 4-7, 8-11, 12-15):
    the four routed parts and the shared expert ONCE are the uncut layer's output;
    and the program's share (4-11 here) is the reference's for the same cut."""
    dims, key, cfg, params, bias = model
    rng = np.random.default_rng(8)
    y = jnp.asarray(rng.normal(size=(CAL_T, CONF["hidden_size"])), jnp.float32)
    whole = W.Dims.from_config({**CONF, "num_experts": 16, "experts_held_first": 0})
    shared, routed = R.expert_ffn(key, 3, y, bias[2], whole, jnp.float32)
    parts = []
    for first in (0, 4, 8, 12):
        share = W.Dims.from_config({**CONF, "num_experts": 4, "experts_held_first": first})
        shared_k, routed_k = R.expert_ffn(key, 3, y, bias[2], share, jnp.float32)
        assert np.array_equal(shared_k, shared)
        parts.append(np.asarray(routed_k))
    assert np.abs(np.asarray(routed)).max() > 0.1
    assert np.allclose(sum(parts), routed, atol=1e-5)
    mine_shared, mine_routed = R.expert_ffn(key, 3, y, bias[2], dims, jnp.float32)
    assert np.allclose(parts[1] + parts[2], mine_routed, atol=1e-5)
    lp = jax.tree.map(lambda a: a[0, 1], params["layers"]["kda"])  # layer 3: period 0's second KDA layer
    got, counts = lm.expert_layer(y, lp, cfg, params["experts"][2], 0)  # the period's third place
    assert np.allclose(got, mine_shared + mine_routed, atol=1e-4)
    assert int(counts[2]) == 1 and 0 < int(counts[0]) <= CAL_T * 2 and 0 < int(counts[1]) <= 8


def test_a_places_stack_gives_a_layer_its_own_periods_experts(model):
    """``routed_experts`` is handed a PLACE's stack ``[periods, held, ...]`` and
    the period's number: the grouped product sees ``periods x held`` groups of
    which only that period's hold rows. The same tokens through that period's
    experts alone, as a stack of one, give the same numbers and the same
    counts; the other period's experts give others."""
    _dims, _key, cfg, params, _bias = model
    y = jnp.asarray(np.random.default_rng(12).normal(size=(24, CONF["hidden_size"])), jnp.float32)
    lp = jax.tree.map(lambda a: a[1, 0], params["layers"]["latent"])  # layer 5: period 1's latent layer
    stack = params["experts"][1]
    in_stack, counts = lm.routed_experts(y, lp, cfg, stack, 1)
    alone, counts_alone = lm.routed_experts(y, lp, cfg, jax.tree.map(lambda a: a[1:], stack), 0)
    other, _ = lm.routed_experts(y, lp, cfg, stack, 0)
    assert np.abs(np.asarray(in_stack)).max() > 0.1
    assert np.allclose(in_stack, alone, atol=1e-5) and np.array_equal(counts, counts_alone)
    assert not np.allclose(in_stack, other, atol=1e-2)


@pytest.mark.parametrize("name", ML.ROUTINGS)
@pytest.mark.parametrize("T,form", ML.KERNEL_CASES)
def test_moe_decode_kernel_reads_the_grouped_forms_numbers_on_a_places_stack(model, monkeypatch, T, form, name):
    """Held experts 4-11 of 16, two a token: the period's third place, its
    second period (a kernel is handed the place's stack and the period's number;
    the grouped kernel's is traced, as the period's scan hands it over)."""
    _dims, _key, cfg, params, _bias = model
    ML.kernel_against_grouped(monkeypatch, cfg, params["experts"][2], 1, T, name, form, traced=form == "grouped")


@pytest.mark.parametrize("T,width,tpu,form", [
    (64, 128, True, "decode"), (240, 128, True, "decode"), (241, 128, True, "grouped"),
    (1024, 128, True, "grouped"), (1024, 64, True, "ragged"), (1024, 128, False, "ragged")])
def test_the_token_count_alone_chooses_the_form_for_this_model_too(monkeypatch, T, width, tpu, form):
    """The same rule as ``test_latent_moe``'s, from this model's configuration
    object: nothing of the model is asked, only the call's static token count
    (and where it runs, and whether the widths tile)."""
    if tpu:
        monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")
    cfg = km.KDAMoEConfig.tiny(hidden_size=width, moe_intermediate_size=width, held_first=4, held_count=8)
    held = {name: jax.ShapeDtypeStruct(shape, jnp.bfloat16) for name, shape in km.expert_shapes(cfg).items()}
    lp = {"router": jax.ShapeDtypeStruct((width, cfg.num_experts), jnp.bfloat16),
          "expert_bias": jax.ShapeDtypeStruct((cfg.num_experts,), jnp.float32)}
    assert ML.form_of(jax.make_jaxpr(lambda y, lp, held: lm.routed_experts(y, lp, cfg, held, 0))(
        jax.ShapeDtypeStruct((T, width), jnp.bfloat16), lp, held)) == form


def test_the_fitted_bias_levels_this_chips_share_on_tokens_it_never_saw():
    """At the published router's shape (512 experts in 8 groups, 4 kept, 8 a
    token; this chip holds groups 0 and 1) on scores with a skew a seed: the
    rule levels every expert's load to within 10% of the mean on the positions
    it was fitted on, and on FRESH positions this chip's share of the pairs is
    within two points of 25%, where the unfitted router's is further out."""
    dims = W.Dims.from_config({**CONF, "num_experts_published": 512, "num_experts": 128,
                               "experts_held_first": 0, "num_experts_per_tok": 8, "n_group": 8,
                               "topk_group": 4})
    rng = np.random.default_rng(11)
    router = rng.normal(size=(64, 512)) / 8
    common = rng.normal(size=64) * 0.6  # what every position shares: the skew

    def scores(n):
        y = rng.normal(size=(n, 64)) + common
        return jnp.asarray(1 / (1 + np.exp(-(y / np.sqrt((y * y).mean(-1, keepdims=True))) @ router)),
                           jnp.float32)

    fitted_on, fresh = scores(4096), scores(4096)
    bias, steps, worst = W.level_bias(fitted_on, dims)
    assert float(worst) <= W.LEVEL and 0 < int(steps) < W.STEPS

    def share_here(s, b):
        return float((np.asarray(W.select(s, b, dims)) < 128).mean())

    unfitted = share_here(fresh, jnp.zeros(512))
    assert abs(share_here(fresh, bias) - 0.25) < 0.02 < abs(unfitted - 0.25)
    assert float(jnp.abs(W.loads(fresh, bias, dims) - 1).mean()) < 0.15
