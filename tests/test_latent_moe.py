"""The latent-attention expert decoder (``models/latent_moe.py``) through the
paged programs and ``LLMEngine``, against the benchmark's plain float32
reference (``chipbench/reference_latent_moe.py``) on seeded weights, at a small
size on the CPU.

Tolerances. Program and reference both run in float32 here and differ only in
the order of their sums (absorbed against expanded attention, an online
softmax, grouped against per-expert products): logits of size ~4 read 2e-6 to
5e-6 apart. ``TOL`` leaves that two orders of room; a program computing in
bfloat16 reads ~3e-2 and fails it (``test_bfloat16_fails_the_tolerance``).
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_latent_moe as R
from chipbench import weights_latent_moe as W
from ray_tpu.models import latent_moe as lm
from ray_tpu.models import paged
from ray_tpu.models.paged import TRASH_BLOCK, PagedConfig
from ray_tpu.serve.llm_engine import LLMEngine

TOL = 5e-4
BS = 8
CONF = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=4, first_k_dense_replace=2,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
    n_routed_experts_published=8, num_experts_per_tok=2, n_shared_experts=1,
    routed_scaling_factor=2.5, rope_theta=25.6e6, rms_norm_eps=1e-5,
    experts_held_first=2, n_routed_experts=4)
SEED = 2**31 + 11


def dims_of(**kw) -> W.Dims:
    return W.Dims.from_config({**CONF, **kw})


def make(dims: W.Dims, dtype=jnp.float32):
    key = W.seed_key(SEED)
    params = jax.jit(lambda k: W.make_params(k, dims, dtype))(key)
    return key, W.program_config(dims, dtype), params


@pytest.fixture(scope="module")
def model():
    dims = dims_of()
    return (dims,) + make(dims)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(3).integers(0, CONF["vocab_size"], (1, 40)).astype(np.int32)


@pytest.fixture(scope="module")
def ref_logits(model, tokens):
    dims, key, _cfg, _params = model
    return np.asarray(R.stream_logits(key, jnp.asarray(tokens), dims, jnp.float32)[0])


def _served(params, cfg, tokens):
    """Positions 0-23 by ``paged_prefill``, 24-39 by a chunk call on that cached
    prefix, position 39 once more by a decode step: the three programs' logits."""
    p = PagedConfig(block_size=BS, num_blocks=33, max_batch=4, max_blocks_per_seq=8)
    cache = paged.init_paged_cache(cfg, p)
    row = jnp.asarray([1, 2, 3], jnp.int32)
    pre, cache = jax.jit(lambda t, c: paged.paged_prefill(params, cfg, t, c, row, BS))(
        jnp.asarray(tokens[:, :24]), cache)
    table = np.full((1, 8), TRASH_BLOCK, np.int32)
    table[0, :5] = [1, 2, 3, 4, 5]
    tile = paged.chunk_tile(16, BS)
    n = 16 // tile
    chunk, cache = jax.jit(lambda t, c: paged.paged_prefill_chunk(
        params, cfg, t, c, jnp.asarray(np.repeat(table, n, 0)), jnp.asarray([4, 5], jnp.int32), BS,
        jnp.asarray([24 + tile * i for i in range(n)], jnp.int32),
        jnp.asarray([15] * n, jnp.int32)))(jnp.asarray(tokens[:, 24:40]), cache)
    tables = np.full((4, 8), TRASH_BLOCK, np.int32)
    tables[1] = table[0]
    lens = np.zeros(4, np.int32)
    lens[1] = 39
    cur = np.zeros(4, np.int32)
    cur[1] = tokens[0, 39]
    dec, cache = jax.jit(lambda c: paged.paged_decode_step(
        params, cfg, jnp.asarray(cur), c, jnp.asarray(tables), jnp.asarray(lens)))(cache)
    return np.asarray(pre), np.asarray(chunk[0]), np.asarray(dec[1]), cache


def test_prefill_chunk_and_decode_through_the_cache_agree_with_the_reference(model, tokens, ref_logits):
    _dims, _key, cfg, params = model
    pre, chunk, dec, _ = _served(params, cfg, tokens)
    assert np.abs(pre - ref_logits[:24]).max() < TOL
    assert np.abs(chunk - ref_logits[39]).max() < TOL  # the chunk's last row, on a cached prefix
    assert np.abs(dec - ref_logits[39]).max() < TOL  # the same position, one token a slot


def test_bfloat16_fails_the_tolerance(tokens, ref_logits):
    """The comparison tells precisions apart: the same programs in bfloat16
    (weights rounded, as the reference's) miss the float32 logits by far more."""
    dims = dims_of()
    key, cfg, params = make(dims, jnp.bfloat16)
    ref = np.asarray(R.stream_logits(key, jnp.asarray(tokens), dims, jnp.bfloat16)[0])
    pre, chunk, dec, _ = _served(params, cfg, tokens)
    assert np.abs(pre - ref[:24]).max() > 10 * TOL
    assert np.abs(dec - ref[39]).max() > 10 * TOL


def test_the_leading_layers_cache_is_block_base_0(model, tokens):
    """Layer ``i`` writes pool ``i`` of the stack, the leading (unscanned)
    layers first: after a prefill into blocks 1-3 every layer's pool holds
    rows there and nowhere else, and layer 0's rows are the leading layer's
    own latents (its ``project`` of the embedded tokens)."""
    _dims, _key, cfg, params = model
    _, _, _, cache = _served(params, cfg, tokens)
    (pool,) = cache.values()
    assert pool.shape[0] == cfg.num_hidden_layers
    written = np.abs(np.asarray(pool)).sum(axis=(2, 3)) > 0  # [L, blocks]
    assert written[:, 1:6].all() and not written[:, 6:].any()
    x = params["embed"][jnp.asarray(tokens[:, :24])]
    lead0 = jax.tree.map(lambda a: a[0], params["lead"])
    _, _, rows = lm.project(lm._norm(x, lead0["attn_norm"], cfg), lead0, cfg,
                            jnp.arange(24, dtype=jnp.int32)[None])
    np.testing.assert_allclose(np.asarray(pool[0, 1:4]).reshape(24, -1), np.asarray(rows[0]),
                               atol=1e-6)


def test_absorbed_and_expanded_attention_agree():
    cfg = lm.LatentMoEConfig.tiny()  # the module's own small size and seeded init
    params = lm.init_params(jax.random.PRNGKey(2), cfg)
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 19, cfg.hidden_size), jnp.float32)
    q_nope, q_rope, rows = lm.project(h, lp, cfg, jnp.arange(19, dtype=jnp.int32)[None])
    u = lm.absorbed_attention(q_nope[0], q_rope[0], rows[0], lp, cfg)
    _, w_uv = lm._up_kv(lp, cfg, jnp.float32)
    o = lm.expanded_attention(q_nope[0], q_rope[0], rows[0], lp, cfg)
    # float32 sums in another order: 1e-6 of values of size ~1
    np.testing.assert_allclose(np.asarray(jnp.einsum("qhc,chv->qhv", u, w_uv)), np.asarray(o),
                               atol=2e-5)


def test_router_takes_the_largest_normalises_and_scales(model):
    _dims, _key, cfg, params = model
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    y = jax.random.normal(jax.random.PRNGKey(6), (33, cfg.hidden_size), jnp.float32)
    experts, gates = lm.route(y, lp, cfg)
    scores = 1 / (1 + np.exp(-np.asarray(y, np.float64) @ np.asarray(lp["router"], np.float64)))
    want = np.argsort(-scores, axis=-1)[:, :cfg.num_experts_per_tok]
    assert np.array_equal(np.sort(np.asarray(experts), -1), np.sort(want, -1))
    chosen = np.take_along_axis(scores, np.asarray(experts), axis=-1)
    np.testing.assert_allclose(np.asarray(gates), 2.5 * chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-5)


def test_a_token_with_no_expert_here_gets_the_shared_expert_only(model):
    """Held experts 2-5 of 8, two a token: some tokens choose none of them. Their
    routed part is exactly 0 and the layer's output is the shared expert's."""
    _dims, _key, cfg, params = model
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    y = jax.random.normal(jax.random.PRNGKey(7), (64, cfg.hidden_size), jnp.float32)
    experts, _ = lm.route(y, lp, cfg)
    none_here = ~np.isin(np.asarray(experts), np.arange(2, 6)).any(-1)
    assert none_here.any() and not none_here.all()
    routed, _ = lm.routed_experts(y, lp, cfg, params["experts"], 0)
    assert np.abs(np.asarray(routed)[none_here]).max() == 0
    assert (np.abs(np.asarray(routed)[~none_here]).sum(-1) > 0).all()  # the others got something
    whole, _ = lm.expert_layer(y, lp, cfg, params["experts"], 0)
    shared = lm._swiglu(y, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    np.testing.assert_allclose(np.asarray(whole)[none_here], np.asarray(shared)[none_here], atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts on 4 chips of 2: the routed parts that the four shares compute
    (the program's expert layer, told which experts it holds) plus the shared
    expert ONCE equal the uncut layer of the reference, which holds all 8."""
    index = 2  # the first expert layer
    uncut = dims_of(experts_held_first=0, n_routed_experts=8)
    key = W.seed_key(SEED)
    y = jax.random.normal(jax.random.PRNGKey(8), (48, CONF["hidden_size"]), jnp.float32)
    shared, routed = R.expert_ffn(key, index, y, uncut, jnp.float32)
    want = np.asarray(shared + routed)
    total = np.asarray(shared).copy()
    pairs = 0
    for first in (0, 2, 4, 6):
        dims = dims_of(experts_held_first=first, n_routed_experts=2)
        cfg = W.program_config(dims, jnp.float32)
        lp, held = jax.jit(lambda k, dims=dims: (
            W.layer_params(k, index, dims, True), W.held_params(k, index, dims)))(key)
        part, counts = lm.routed_experts(y, lp, cfg, jax.tree.map(lambda a: a[None], held), 0)
        total += np.asarray(part)
        pairs += int(counts[0])
    assert pairs == 48 * CONF["num_experts_per_tok"]  # every pair computed on exactly one chip
    np.testing.assert_allclose(total, want, atol=2e-5)  # float32, sums in another order


def test_the_counters_add_up_over_a_known_batch(model):
    """pairs = the (token, choice) pairs whose expert is held; touched = the
    held experts among the chosen; one layer counted."""
    _dims, _key, cfg, params = model
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    y = jax.random.normal(jax.random.PRNGKey(9), (21, cfg.hidden_size), jnp.float32)
    experts = np.asarray(lm.route(y, lp, cfg)[0])
    here = (experts >= 2) & (experts < 6)
    _, counts = lm.routed_experts(y, lp, cfg, params["experts"], 1)
    assert [int(c) for c in counts] == [int(here.sum()), len(set(experts[here].tolist())), 1, 0, 0]


# --- the decode kernel of ops/moe.py under the interpreter --------------------
def routing(name: str, T: int, cfg, seed: int = 0):
    """(experts [T, k], gates [T, k]) of a named pattern over the share the
    configuration holds (``held_first`` .. ``held_first + held - 1`` of all):
    ``elsewhere``: drawn over ALL experts, so some pairs live on other chips;
    ``untouched``: every token on the first two held experts, the other held ones idle;
    ``one_expert``: every token on ONE held expert and one elsewhere;
    ``none_here``: no pair lands on a held expert."""
    rng = np.random.default_rng(seed)
    k, first, held = cfg.num_experts_per_tok, cfg.held_first, cfg.held
    total = first + held + 2  # at least two experts live elsewhere, behind the held ones
    if name == "elsewhere":
        experts = np.stack([rng.permutation(total)[:k] for _ in range(T)])
        experts[0] = np.r_[first, total - 1 - np.arange(k - 1)]  # the first token: one here, the rest not
    elif name == "untouched":
        experts = np.tile(np.arange(first, first + k), (T, 1))
    elif name == "one_expert":
        experts = np.tile(np.r_[first + 1, total - 1 - np.arange(k - 1)], (T, 1))
    else:
        experts = np.tile(total - 1 - np.arange(k), (T, 1))
    return jnp.asarray(experts, jnp.int32), jnp.asarray(rng.uniform(0.1, 1.5, (T, k)), jnp.float32)


ROW_TILE = 16  # of the grouped kernel under the interpreter: a tile of bfloat16's sublanes


def kernel_against_grouped(monkeypatch, cfg, held, layer, T, name, form="decode", traced=False):
    """``routed_experts`` through a kernel of ``ops/moe.py`` (the Pallas
    interpreter) against its sorted ``ragged_dot`` form on the same routing:
    ``moe_decode_experts`` (``form`` "decode") or the sorted pairs through
    ``moe_grouped_experts`` in row tiles of 16 ("grouped"; ``traced``: under a
    jit that is handed the layer's number). In float32 the same numbers but for
    the order of the sums, in bfloat16 within its rounding (the ``ragged_dot``
    form rounds each product, a kernel only what goes into the down
    projection); the three counts equal, the last two say which form ran. What
    the grouped kernel returns past its last group is 0 to the end of the row tile."""
    import functools

    from ray_tpu.ops import moe

    chosen = routing(name, T, cfg)
    monkeypatch.setattr(lm, "route", lambda y, lp, cfg: chosen)
    kernel = functools.partial(moe.moe_grouped_experts, row_tile=ROW_TILE, interpret=True)
    for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)):
        y = jax.random.normal(jax.random.PRNGKey(T), (T, held["e_gate"].shape[2]), jnp.float32).astype(dtype)
        weights = jax.tree.map(lambda a: a.astype(dtype), held)
        with monkeypatch.context() as m:
            m.setattr(moe, "fused", lambda T, held: False)
            want, counts = lm.routed_experts(y, {}, cfg, weights, layer)
        returned = []

        def grouped_products(x, sizes, held, layer):
            out = kernel(x, sizes, held, layer)
            returned.append((out, sizes))
            return out

        with monkeypatch.context() as m:
            m.setattr(moe, "fused", lambda T, held: form == "decode" and T <= moe.RIDGE_TOKENS)
            m.setattr(moe, "grouped", lambda T, held: form == "grouped")
            m.setattr(moe, "moe_decode_experts", functools.partial(moe.moe_decode_experts, interpret=True))
            m.setattr(moe, "moe_grouped_experts", grouped_products)
            if traced:
                got, counts_kernel = jax.jit(lambda y, layer: lm.routed_experts(y, {}, cfg, weights, layer))(
                    y, jnp.int32(layer))
            else:
                got, counts_kernel = lm.routed_experts(y, {}, cfg, weights, layer)
        assert got.dtype == want.dtype == dtype and got.shape == (T, y.shape[1])
        want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
        assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max()), (dtype, np.abs(got - want).max())
        assert np.array_equal(counts[:3], counts_kernel[:3])
        assert ([int(c) for c in counts[3:]], [int(c) for c in counts_kernel[3:]]) == (
            [0, 0], [1, 0] if form == "decode" else [0, 1])
        assert len(returned) == (form == "grouped")
        for out, sizes in () if traced else returned:
            assert out.shape == (T * cfg.num_experts_per_tok, y.shape[1]) and int(jnp.sum(sizes)) == int(counts[0])
            pairs = int(counts[0])  # 0 from the last pair to the end of its row tile
            assert not np.asarray(out[pairs:-(-pairs // ROW_TILE) * ROW_TILE], np.float32).any()
        if name == "none_here":
            assert not got.any() and int(counts[0]) == 0
        else:
            assert np.abs(want).max() > 0.05
    return counts


ROUTINGS = ("elsewhere", "untouched", "one_expert", "none_here")


# The grouped kernel's cases, in row tiles of 16 over two pairs a token: 24
# tokens are three tiles that "elsewhere"'s uneven groups cross and end inside
# (rows past the last group); 64 on "untouched" are two groups of four whole
# tiles and two of no rows; 300 on "one_expert" one group of 300 rows in 19
# tiles and as many rows elsewhere; "none_here" no group at all.
KERNEL_CASES = [(1, "decode"), (8, "decode"), (64, "decode"), (128, "decode"),
                (24, "grouped"), (64, "grouped"), (300, "grouped")]


@pytest.mark.parametrize("name", ROUTINGS)
@pytest.mark.parametrize("T,form", KERNEL_CASES)
def test_moe_decode_kernel_reads_the_grouped_forms_numbers(model, monkeypatch, T, form, name):
    """Held experts 2-5 of 8, two a token, the second expert layer of the stack."""
    _dims, _key, cfg, params = model
    counts = kernel_against_grouped(monkeypatch, cfg, params["experts"], 1, T, name, form)
    assert int(counts[1]) == {"untouched": 2, "one_expert": 1, "none_here": 0}.get(name, int(counts[1]))


# (tokens, hidden = expert width, on a TPU) -> the form
FORM_CASES = [(1, 128, True, "decode"), (64, 128, True, "decode"), (240, 128, True, "decode"),
              (241, 128, True, "grouped"), (1024, 128, True, "grouped"),
              (1024, 64, True, "ragged"), (64, 64, True, "ragged"), (1024, 128, False, "ragged"),
              (64, 128, False, "ragged")]


def form_of(routed) -> str:
    """Which form ``routed_experts`` traced to, from the jaxpr of ``routed``."""
    text = str(routed)
    kernels = [name for name in ("moe_decode_experts", "moe_grouped_experts") if name in text]
    dots = len(re.findall(r"= ragged_dot_general\[", text))
    assert ("pallas_call" in text, dots) == ((True, 0) if kernels else (False, 3)) and len(kernels) <= 1
    return kernels[0].split("_")[1] if kernels else "ragged"


@pytest.mark.parametrize("T,width,tpu,form", FORM_CASES)
def test_the_token_count_alone_chooses_the_form(monkeypatch, T, width, tpu, form):
    """On a TPU (forced: the lowering is never run) at widths that are whole
    lane tiles, ``routed_experts`` of a call of up to ``RIDGE_TOKENS`` tokens is
    the decode kernel, of one token more the grouped kernel over the sorted
    pairs, and neither has a ``ragged_dot``; on a CPU, and at widths that do not
    tile, three ``ragged_dot`` and no kernel. Nothing else is asked."""
    from ray_tpu.ops import moe

    assert moe.RIDGE_TOKENS == 240
    if tpu:
        monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")
    cfg = lm.LatentMoEConfig.tiny(hidden_size=width, moe_intermediate_size=width, held_first=2, held_count=4)
    held = {name: jax.ShapeDtypeStruct(shape, jnp.bfloat16) for name, shape in lm.expert_shapes(cfg).items()}
    lp = {"router": jax.ShapeDtypeStruct((width, cfg.n_routed_experts), jnp.bfloat16)}
    assert form_of(jax.make_jaxpr(lambda y, lp, held: lm.routed_experts(y, lp, cfg, held, 1))(
        jax.ShapeDtypeStruct((T, width), jnp.bfloat16), lp, held)) == form


def _deficits(ref, served):
    """The reference's largest logit less its logit of the served token, over
    the spread of its logits, at each generated position."""
    chosen = np.take_along_axis(ref, np.asarray(served)[:, None], axis=-1)[:, 0]
    return (ref.max(-1) - chosen) / ref.std(-1)


def _assert_every_served_token_is_the_references_choice(key, dims, prompts, reqs):
    """The reference is fed prompt + served tokens as a forced continuation."""
    for pr, r in zip(prompts, reqs):
        seq = np.asarray([pr + r.generated[:-1]], np.int32)
        ref = np.asarray(R.stream_logits(key, jnp.asarray(seq), dims, jnp.float32)[0])
        d = _deficits(ref[len(pr) - 1:], r.generated)
        # 0 where the served token is the reference's largest; float32 rounding
        # can swap a near-tie, which reads a deficit of its own size (~1e-6)
        assert d.max() < 1e-4, d.max()


def test_engine_serves_the_references_logits_with_cache_chunks_preemption_and_resume(model):
    """``LLMEngine`` end to end on the configuration: a prefix cache, a fixed
    prefill chunk, a pool so small that requests are preempted and resumed.
    Every served token (greedy) is the reference's own choice at its position,
    the reference being fed the served tokens as a forced continuation; the
    three counters moved and add up."""
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
    expert_layers = dims.layers - dims.lead
    assert s["moe_layer_steps"] >= expert_layers * s["steps"] * 3 - expert_layers * 3
    assert s["moe_layer_steps"] % expert_layers == 0
    assert 0 < s["moe_experts_touched"] <= dims.held * s["moe_layer_steps"]
    assert s["moe_experts_touched"] <= s["moe_pairs_here"] <= 4 * 2 * 16 * s["moe_layer_steps"]
    # On a CPU every counted layer ran ``ragged_dot``: neither kernel's count moved.
    assert (s["moe_fused_layer_steps"], s["moe_grouped_layer_steps"]) == (0, 0)
    assert eng.report_state()["moe"]["grouped_layer_steps"] == 0
    _assert_every_served_token_is_the_references_choice(key, dims, prompts, reqs)


def test_engine_tells_the_chunk_program_each_tiles_real_queries_and_counts_them(model, monkeypatch):
    """Suffixes shorter than their tiles, admitted in one iteration: the chunk
    call's ``per_tile`` has a fourth row, the real queries of each tile (a
    one-tile segment its length, a two-tile one a full tile and its
    remainder, a tile no segment uses 0), the attention skips what lies past
    them in programs of 4, and every served token is still the reference's
    choice. ``prefill_live_queries`` / ``prefill_tile_queries`` count both
    sides over the batch, and reach the report and the registry."""
    from ray_tpu.ops import latent_attention as LA
    from ray_tpu.serve.metrics import serve_metrics

    monkeypatch.setattr(LA, "_QUERIES_PER_STEP", 4)
    dims, key, cfg, params = model
    p = PagedConfig(block_size=BS, num_blocks=129, max_batch=4, max_blocks_per_seq=32)
    eng = LLMEngine(params, cfg, p, decode_window=3, overlap=True, enable_prefix_cache=True, seed=1)
    eng.metrics_tags = {"deployment": "live-queries", "replica": "r0"}
    assert paged.chunk_tile(128, BS) == 32
    rng = np.random.default_rng(5)
    doc = rng.integers(0, CONF["vocab_size"], 16).tolist()  # two blocks
    eng.generate_batch([doc], 2)  # a prompt with no hit: no chunk call, the doc is published
    assert (eng.stats["prefill_chunks"], eng.stats["prefill_tile_queries"]) == (0, 0)
    rows = []
    call = eng._prefill_chunk_fn
    monkeypatch.setattr(eng, "_prefill_chunk_fn",
                        lambda *a: rows.append(np.array(a[5])) or call(*a))
    prompts = [doc + rng.integers(0, CONF["vocab_size"], n).tolist() for n in (5, 40)]
    reqs = [eng.add_request(pr, 6) for pr in prompts]
    while eng.active_count() or eng.waiting:
        eng.step()
    # One call 128 wide: tiles of 32 for 5 and 32 + 8 tokens, and one to spare.
    (per_tile,) = rows
    assert per_tile.shape == (5, 4)  # the fifth: each tile's slot, for state kept by slot
    assert per_tile[0].tolist() == [16, 16, 48, 0] and per_tile[3].tolist() == [5, 32, 8, 0]
    s = eng.stats
    assert (s["prefill_chunks"], s["prefill_segments"]) == (1, 2)
    assert (s["prefill_tile_queries"], s["prefill_live_queries"]) == (3 * 32, 5 + 40)
    assert [len(r.generated) for r in reqs] == [6, 6]
    _assert_every_served_token_is_the_references_choice(key, dims, prompts, reqs)
    # A lone suffix of three tokens: a call one block wide, which is its tile.
    rows.clear()
    eng.generate_batch([doc + [7, 8, 9]], 2)
    assert [r.tolist() for r in rows] == [[[16], [2], [0], [3], [0]]]  # the fifth row: slot 0
    assert (s["prefill_tile_queries"], s["prefill_live_queries"]) == (96 + 8, 45 + 3)
    snap = eng.report_state()
    assert snap["prefill"] == {
        "chunks": 2, "segments": 3, "width_tokens": 128 + 8, "tile_queries": 104, "live_queries": 48,
        "live_query_pct": pytest.approx(100 * 48 / 104)}
    assert snap["stats"]["prefill_live_queries"] == 48
    m = serve_metrics()
    for counter, name, want in (
            (m.engine_prefill_tile_queries, "serve_engine_prefill_tile_queries_total", 104),
            (m.engine_prefill_live_queries, "serve_engine_prefill_live_queries_total", 48)):
        assert counter.name == name
        assert [value for _n, _t, _d, tags, value in counter._drain()
                if dict(tags)["deployment"] == "live-queries"] == [want]


# --- the two kernels of ops/latent_attention.py under the interpreter --------
def _pool_and_tables(rng, slots, W, P=64, bs=16, R=256):
    pool = jnp.asarray(rng.normal(size=(P, bs, R)), jnp.float32)
    tables = jnp.asarray(rng.permutation(P - 1)[:slots * W].reshape(slots, W) + 1, jnp.int32)
    return pool, tables.at[0].set(TRASH_BLOCK)  # slot 0 holds nothing


def test_latent_attend_kernel_reads_the_plain_forms_sums(monkeypatch):
    """The decode kernel under the Pallas interpreter against the gather-and-
    einsum form: several steps a slot, a context of one token, one that ends
    on a block's last row, an idle slot whose ``lens`` has run past the table."""
    from ray_tpu.ops import latent_attention as LA

    monkeypatch.setattr(LA, "_ROWS_PER_STEP", 64)  # 4 blocks a step
    rng = np.random.default_rng(0)
    pool, tables = _pool_and_tables(rng, 5, 9)
    q = jnp.asarray(rng.normal(size=(5, 8, 256)), jnp.float32)
    lens = jnp.asarray([5000, 0, 15, 100, 143], jnp.int32)
    want = LA.reference_latent_attention(q, pool, tables, lens, 0.1, 128)
    got = LA._latent_attend(q, pool, tables, lens, 0.1, 128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)  # float32, other order


# ``live`` by tile (slot 0's on the trash block, slots 1 and 2 deep in their
# tables, 8 queries a tile in programs of 4): full; partial and not a multiple
# of a program; one real query; none, deep in a table and on the trash tile.
@pytest.mark.parametrize("live", [(8, 8, 8), (8, 4, 3), (5, 1, 6), (0, 0, 7), (0, 8, 0)],
                         ids=lambda v: "live" + "-".join(map(str, v)))
def test_latent_prefill_kernel_reads_the_plain_forms_sums(monkeypatch, live):
    """The prefill kernel under the interpreter against the plain walk, and
    that against one query at a time in the decode's plain form: a tile on the
    trash block, tiles deep in their tables, steps of two blocks. The two
    forms agree on the WHOLE output, padding included: from ``live`` rounded
    up to a program on it is exactly 0, below ``live`` the oracle's."""
    from ray_tpu.ops import latent_attention as LA

    monkeypatch.setattr(LA, "_KV_ROWS", 32)
    monkeypatch.setattr(LA, "_QUERIES_PER_STEP", 4)
    rng = np.random.default_rng(1)
    pool, tables = _pool_and_tables(rng, 3, 9)
    q = jnp.asarray(rng.normal(size=(3, 8, 8, 256)), jnp.float32)
    starts = jnp.asarray([0, 40, 130], jnp.int32)
    qpos = starts[:, None] + jnp.arange(8)[None]
    padded = jnp.pad(tables, ((0, 0), (0, 1)))
    lv = jnp.asarray(live, jnp.int32)
    plain = np.asarray(LA._plain_chunk_attention(q, pool, padded, qpos, lv, 0.1, 128, 2))
    kernel = np.asarray(
        LA._latent_prefill_attend(q, pool, padded, starts, lv, 0.1, 128, 2, interpret=True))
    assert np.isfinite(kernel).all() and np.isfinite(plain).all()
    np.testing.assert_allclose(kernel, plain, atol=1e-5)
    one_by_one = np.asarray(jnp.stack([jnp.stack([LA.reference_latent_attention(
        q[t, c][None], pool, tables[t][None], qpos[t, c][None], 0.1, 128)[0]
        for c in range(8)]) for t in range(3)]))
    for t, real in enumerate(live):
        computed = -(-real // 4) * 4
        for out in (kernel, plain):
            assert not out[t, computed:].any()  # exactly 0, not merely small
            np.testing.assert_allclose(out[t, :real], one_by_one[t, :real], atol=1e-5)


def test_a_skipped_program_writes_zeros_over_what_its_block_held(monkeypatch):
    """A program past its tile's count WRITES its block of the output: the
    kernel's result does not depend on what the buffer held before (the
    interpreter hands a kernel an output full of NaN where it can)."""
    from ray_tpu.ops import latent_attention as LA

    monkeypatch.setattr(LA, "_KV_ROWS", 32)
    monkeypatch.setattr(LA, "_QUERIES_PER_STEP", 4)
    rng = np.random.default_rng(2)
    pool, tables = _pool_and_tables(rng, 2, 9)
    q = jnp.asarray(rng.normal(size=(2, 8, 8, 256)), jnp.float32)
    padded = jnp.pad(tables, ((0, 0), (0, 1)))
    args = (q, pool, padded, jnp.asarray([0, 70], jnp.int32))
    with jax.debug_nans(True):  # raises if a NaN leaves the kernel
        out = np.asarray(LA._latent_prefill_attend(
            *args, jnp.asarray([0, 2], jnp.int32), 0.1, 128, 2, interpret=True))
    assert not out[0].any() and not out[1, 4:].any() and out[1, :2].any()


def test_a_pool_that_took_a_chunk_calls_padded_rows_stays_finite_through_decode(
        monkeypatch, model, tokens):
    """The 0 x NaN hazard: a chunk call scatters its PADDED tokens' latent rows
    too, some into the slot's own last block past its length, and the next
    layer's come from what the attention gave for them. Both attentions
    multiply masked probabilities of 0 into those rows, so they must be
    finite: zeros go in, and the decode step that follows reads its
    position's logits off the reference."""
    from ray_tpu.ops import latent_attention as LA

    monkeypatch.setattr(LA, "_QUERIES_PER_STEP", 4)
    _dims, key, cfg, params = model
    dims = dims_of()
    p = PagedConfig(block_size=BS, num_blocks=33, max_batch=4, max_blocks_per_seq=8)
    cache = paged.init_paged_cache(cfg, p)
    # 17 real tokens on a chunk axis of 32, one tile (``chunk_tile(32, 8)``):
    # queries 17-19 are padding computed with query 16, 20-31 are skipped, and
    # the slot's last block (positions 16-23) takes rows of both kinds; the
    # fourth block of the axis lands on the trash block.
    plen = 17
    tile = paged.chunk_tile(32, BS)
    assert tile == 32
    table = np.full((1, 8), TRASH_BLOCK, np.int32)
    table[0, :3] = [1, 2, 3]
    toks = np.zeros((1, 32), np.int32)
    toks[0, :plen] = tokens[0, :plen]
    logits, cache = jax.jit(lambda t, c: paged.paged_prefill_chunk(
        params, cfg, t, c, jnp.asarray(table), jnp.asarray([1, 2, 3, TRASH_BLOCK], jnp.int32), BS,
        jnp.asarray([0], jnp.int32), jnp.asarray([plen - 1], jnp.int32),
        jnp.asarray([plen], jnp.int32)))(jnp.asarray(toks), cache)
    (pool,) = cache.values()
    assert np.isfinite(np.asarray(pool)).all()
    ref = np.asarray(R.stream_logits(key, jnp.asarray(tokens[:, :plen + 1]), dims, jnp.float32)[0])
    assert np.abs(np.asarray(logits[0]) - ref[plen - 1]).max() < TOL
    tables = np.full((4, 8), TRASH_BLOCK, np.int32)
    tables[2] = table[0]
    lens = np.zeros(4, np.int32)
    lens[2] = plen
    cur = np.zeros(4, np.int32)
    cur[2] = tokens[0, plen]
    dec, cache = jax.jit(lambda c: paged.paged_decode_step(
        params, cfg, jnp.asarray(cur), c, jnp.asarray(tables), jnp.asarray(lens)))(cache)
    assert np.isfinite(np.asarray(dec)).all()
    assert np.isfinite(np.asarray(next(iter(cache.values())))).all()
    assert np.abs(np.asarray(dec[2]) - ref[plen]).max() < TOL
