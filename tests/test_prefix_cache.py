"""Serve-path engine perf suite: prefix-aware KV reuse, chunked prefill,
host/device overlap, bucket warmup, and dirty-slot shipping.

Correctness contract for every feature: temp-0 outputs must be
IDENTICAL to the plain engine (same math, different scheduling /
memory reuse), plus allocator/refcount invariants that guard against
cross-request block aliasing.
"""
import collections
import random
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models.paged import PagedConfig, TRASH_BLOCK
from ray_tpu.models.transformer import TransformerConfig, init_params
from ray_tpu.serve.llm_engine import LLMEngine, _PrefixCache


@pytest.fixture(autouse=True)
def _highest_precision():
    """Token-for-token assertions across differently-shaped computations
    of the same math (full vs chunked prefill, cached vs recomputed KV);
    fp32 matmul precision keeps rounding from flipping an argmax."""
    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", prev)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False)
    params = init_params(jax.random.PRNGKey(7), cfg)
    params = jax.tree.map(lambda x: jax.device_put(x), params)
    return cfg, params


def _engine(cfg, params, **kw):
    pcfg_kw = dict(block_size=8, num_blocks=33, max_batch=4, max_blocks_per_seq=8)
    for k in list(kw):
        if k in pcfg_kw:
            pcfg_kw[k] = kw.pop(k)
    return LLMEngine(params, cfg, PagedConfig(**pcfg_kw), **kw)


SHARED = [7, 3, 9, 1, 4, 6, 2, 8, 11, 12, 13, 14, 15, 16, 17, 18, 21, 22, 23, 24]


def _cache_invariants(eng):
    """No block may be simultaneously free, cached, and/or slot-owned."""
    pc = eng.prefix_cache
    assert len(eng.alloc.free) == len(set(eng.alloc.free)), "double-freed block"
    free = set(eng.alloc.free)
    cached = set(pc.meta)
    in_use = {b for bl in eng.slot_blocks for b in bl}
    assert not free & cached, "block both free and cache-resident"
    assert TRASH_BLOCK not in free and TRASH_BLOCK not in cached
    # Every cached-but-referenced block must be mapped by some slot, and
    # every refcount must equal the number of slots mapping it.
    for bid, (_key, _parent, refs, _wanted) in pc.meta.items():
        mapped = sum(bl.count(bid) for bl in eng.slot_blocks)
        assert refs == mapped, f"block {bid}: refs {refs} != mapped {mapped}"
        if refs == 0:
            assert bid in pc.lru
            assert bid not in in_use
    # Full accounting: free + cached(ref0) + slot-owned == usable pool.
    owned_or_resident = len(free) + len(pc.lru) + len(in_use - cached)
    # slot-owned cached blocks are counted via in_use∩cached == refs>0 set
    owned_or_resident += len(in_use & cached)
    assert owned_or_resident == eng.pcfg.usable_blocks
    # A block is wanted once for every WAITING request that matched it when
    # it entered the queue, and by nobody else: no mark outlives its stay.
    marks = collections.Counter(id(m) for r in eng.waiting for m in r.wanted or ())
    for bid, m in pc.meta.items():
        assert m[3] == marks[id(m)], f"block {bid}: wanted {m[3]} != {marks[id(m)]}"
    assert all(s is None or s.wanted is None for s in eng.slots)


def test_prefix_cache_temp0_outputs_identical(tiny_model):
    """Requests sharing a prompt prefix must produce byte-identical
    greedy outputs with the cache on vs off, while >= 30% of prompt
    tokens are served from cache."""
    cfg, params = tiny_model
    prompts = [SHARED + [30 + i, 40 + i, 50 + i] for i in range(4)]
    base = _engine(cfg, params)
    expect = [base.generate_batch([p], 8)[0] for p in prompts]
    eng = _engine(cfg, params, enable_prefix_cache=True)
    outs = [eng.generate_batch([p], 8)[0] for p in prompts]
    assert outs == expect
    s = eng.stats
    assert s["prefix_lookup_tokens"] == sum(len(p) for p in prompts)
    # 3 warm requests x 2 full shared blocks (16 tokens) each.
    assert s["prefix_hit_tokens"] == 48
    assert s["prefix_hit_tokens"] / s["prefix_lookup_tokens"] >= 0.30
    # Cached prompt tokens were NOT prefilled again.
    assert s["prompt_tokens"] == s["prefix_lookup_tokens"] - s["prefix_hit_tokens"]
    _cache_invariants(eng)


def test_prefix_cache_refcounts_and_concurrent_sharing(tiny_model):
    """Concurrent requests sharing cached blocks pin them (refcount = #
    of mapping slots); finishing releases them into the LRU, never the
    free list, and the outputs still match the plain engine."""
    cfg, params = tiny_model
    prompts = [SHARED + [60 + i] for i in range(3)]
    base = _engine(cfg, params)
    expect = [base.generate_batch([p], 6)[0] for p in prompts]
    eng = _engine(cfg, params, enable_prefix_cache=True)
    # Warm the cache, then run the rest concurrently so they share blocks.
    first = eng.generate_batch([prompts[0]], 6)
    rest = eng.generate_batch(prompts[1:], 6)
    assert [first[0]] + rest == expect
    pc = eng.prefix_cache
    # The two full shared blocks, and the third block of each request, which
    # its answer filled (21 + 6 tokens: positions 0..25 are written).
    assert pc.resident_blocks == 5
    assert pc.evictable_blocks == 5  # all refs dropped at finish
    assert eng.stats["prefix_published_blocks"] == 3
    for bid, (_k, _p, refs, wanted) in pc.meta.items():
        assert refs == 0 and wanted == 0
    _cache_invariants(eng)


def test_prefix_cache_eviction_no_stale_aliasing(tiny_model):
    """Fill the pool with distinct prompts until cached blocks are
    evicted and re-allocated, then re-submit the first prompt: it must
    recompute (no stale hit via a reused block id) and match exactly."""
    cfg, params = tiny_model
    # Tiny pool: 12 usable blocks, so distinct prompts evict each other.
    kw = dict(num_blocks=13, max_batch=2, max_blocks_per_seq=6)
    base = _engine(cfg, params, **kw)
    eng = _engine(cfg, params, enable_prefix_cache=True, **kw)
    first = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17]
    others = [[i + 20] * 17 for i in range(6)]
    expect_first = base.generate_batch([first], 6)
    expect_others = [base.generate_batch([p], 6)[0] for p in others]
    assert eng.generate_batch([first], 6) == expect_first
    for p, exp in zip(others, expect_others):
        assert eng.generate_batch([p], 6)[0] == exp
        _cache_invariants(eng)
    assert eng.stats["prefix_evictions"] > 0
    # Re-run the first prompt after its blocks were evicted/reused.
    assert eng.generate_batch([first], 6) == expect_first
    _cache_invariants(eng)


def test_prefix_cache_preempt_resume_hits(tiny_model):
    """Preempted requests resume via re-prefill; with the cache on, the
    resume maps the already-resident prompt blocks instead of paying the
    full recompute — and still finishes with identical greedy output."""
    cfg, params = tiny_model
    kw = dict(num_blocks=13, max_batch=4, max_blocks_per_seq=6)
    prompts = [[i + 1, i + 2, i + 3, i + 4] * 2 for i in range(4)]
    calm = _engine(cfg, params)
    expect = calm.generate_batch(prompts, 28)
    eng = _engine(cfg, params, enable_prefix_cache=True, **kw)
    outs = eng.generate_batch(prompts, 28)
    assert outs == expect
    assert eng.stats["preemptions"] > 0
    assert eng.stats["prefix_hit_tokens"] > 0  # resume reused resident KV
    _cache_invariants(eng)


def test_chunked_prefill_matches_and_interleaves(tiny_model):
    """A long prompt split into chunks must decode identically, and a
    short stream admitted alongside keeps producing tokens between the
    long prompt's chunks (no head-of-line freeze)."""
    cfg, params = tiny_model
    long_p = list(range(1, 49))  # 48 tokens -> 6 chunks of 8
    short_p = [9, 8, 7]
    base = _engine(cfg, params)
    expect_long = base.generate_batch([long_p], 8)[0]
    expect_short = _engine(cfg, params).generate_batch([short_p], 12)[0]
    eng = _engine(cfg, params, prefill_chunk=8)
    short_req = eng.add_request(short_p, 12)
    eng.step()  # admit + prefill the short request first
    long_req = eng.add_request(long_p, 8)
    chunks_before_done = None
    while eng.active_count() or eng.waiting:
        eng.step()
        if chunks_before_done is None and short_req.out.qsize() > 2:
            # Short stream progressed while the long prefill is running.
            chunks_before_done = eng.stats["prefill_chunks"]
    assert list(long_req.tokens(timeout=60)) == expect_long
    assert list(short_req.tokens(timeout=60)) == expect_short
    assert eng.stats["prefill_chunks"] >= 6
    assert chunks_before_done is not None and chunks_before_done < 6


def test_chunked_prefill_with_cache_and_overlap(tiny_model):
    """The full perf suite composed: chunked prefill + prefix cache +
    overlap, greedy outputs identical to the plain engine."""
    cfg, params = tiny_model
    prompts = [SHARED + SHARED[:12] + [70 + i] for i in range(4)]  # 33 tokens
    base = _engine(cfg, params)
    expect = [base.generate_batch([p], 6)[0] for p in prompts]
    eng = _engine(
        cfg, params, enable_prefix_cache=True, prefill_chunk=16,
        overlap=True, decode_window=2,
    )
    outs = [eng.generate_batch([p], 6)[0] for p in prompts]
    assert outs == expect
    assert eng.stats["prefill_chunks"] > 0
    assert eng.stats["prefix_hit_tokens"] > 0
    _cache_invariants(eng)


def test_warmup_buckets(tiny_model):
    """Opt-in warmup compiles every prefill bucket at build time and
    records the spent wall time; live requests then behave identically."""
    cfg, params = tiny_model
    eng = _engine(cfg, params, warmup_buckets=True, enable_prefix_cache=True)
    # tiny: buckets 8..64 (4 prefill + 4 suffix-chunk) + decode = 9.
    assert eng.stats["warmup_compiles"] == 9
    assert eng.stats["warmup_s"] >= 0
    assert eng.alloc.available == eng.pcfg.usable_blocks  # warmup hit trash only
    base = _engine(cfg, params)
    prompts = [[5, 9, 2, 11, 3], [17, 1, 8]]
    assert eng.generate_batch(prompts, 8) == base.generate_batch(prompts, 8)


def test_dirty_slot_shipping_skips_stable_arrays(tiny_model):
    """Steady-state decode must not re-upload tables/lens/temps/cur every
    window: only admission/retirement/paging dirties them."""
    cfg, params = tiny_model
    eng = _engine(cfg, params, decode_window=1)
    eng.generate_batch([[5, 9, 2]], max_new_tokens=24)
    s = eng.stats
    assert s["h2d_skips"] > 0
    # 4 arrays x steps would be the wholesale-upload cost; dirty tracking
    # must beat it by a wide margin (tables only change on block faults).
    assert s["h2d_ships"] < 4 * s["steps"] / 2


def test_overlap_requires_wider_margin(tiny_model):
    """Overlap doubles the decode-window overshoot margin: a request that
    fits the classic margin but not 2*window-1 must be rejected up front
    (its speculated window could write past its block table)."""
    cfg, params = tiny_model
    eng = _engine(cfg, params, decode_window=4, overlap=True)  # max_seq 64
    req = eng.add_request([1] * 30, max_new_tokens=28)  # 30+28+7 = 65 > 64
    with pytest.raises(RuntimeError, match="exceeds capacity"):
        list(req.tokens(timeout=5))
    ok = eng.add_request([1] * 30, max_new_tokens=27)  # 64 — fits
    eng_out = []
    while eng.active_count() or eng.waiting:
        eng.step()
    eng_out = list(ok.tokens(timeout=5))
    assert len(eng_out) == 27


def test_eviction_spares_pinned_child_under_unpinned_chain(tiny_model):
    """A request that registers a novel tail under a chain ANOTHER
    request published first holds no references on that chain (its own
    table maps private duplicates of the parents) — so the chain can hit
    refcount 0 and be evicted while the child is pinned by a live slot.
    The eviction cascade must unregister such a child but NEVER free it:
    pre-fix this freed a block still mapped by a decoding request (KV
    corruption) and then double-freed it at slot release."""
    cfg, params = tiny_model
    eng = _engine(cfg, params, enable_prefix_cache=True, prefill_chunk=16,
                  num_blocks=15, max_batch=4)
    shared = list(range(1, 17))  # 2 full shared blocks
    a_prompt = shared + list(range(30, 54))  # 40 tokens, chunked (3 chunks)
    b_prompt = shared  # 16 tokens, single-shot: registers the chain FIRST
    c_prompt = [200 + i for i in range(40)]  # distinct: forces eviction
    calm = _engine(cfg, params)
    a_ref = calm.generate_batch([a_prompt], 24)[0]
    b_ref = calm.generate_batch([b_prompt], 2)[0]
    c_ref = calm.generate_batch([c_prompt], 4)[0]
    # A (chunked, registration deferred) + B (instant registration) race:
    # B publishes the shared chain; A's tail registers under B's blocks.
    a = eng.add_request(a_prompt, 24)
    b = eng.add_request(b_prompt, 2)
    while eng.slots[1] is not None or eng.waiting:  # B admitted+finished
        eng.step()
    assert list(b.tokens(timeout=60)) == b_ref
    # B's chain is now refcount-0/evictable while A still decodes with
    # its tail blocks registered (pinned) beneath it. C's admission must
    # evict B's chain — and must not touch A's pinned blocks.
    c = eng.add_request(c_prompt, 4)
    while eng.active_count() or eng.waiting:
        eng.step()
    assert eng.stats["prefix_evictions"] >= 2  # B's two chain blocks
    assert list(a.tokens(timeout=60)) == a_ref  # A's KV never corrupted
    assert list(c.tokens(timeout=60)) == c_ref
    _cache_invariants(eng)


def test_prefix_cache_unit_eviction_cascades():
    """Unit: evicting a parent must evict its cached descendants, so a
    reused parent id can never falsely re-link a stale child chain."""
    pc = _PrefixCache()
    a = pc.register(_PrefixCache.ROOT, (1, 2), 10)
    b = pc.register(a, (3, 4), 11)
    c = pc.register(b, (5, 6), 12)
    assert (a, b, c) == (10, 11, 12)
    for bid in (10, 11, 12):
        pc.release(bid)
    assert pc.evictable_blocks == 3
    freed = pc.evict_lru()  # coldest = 10, cascades to 11, 12
    assert set(freed) == {10, 11, 12}
    assert pc.resident_blocks == 0 and not pc.table
    # Re-register under the same ids with different tokens: no stale hits.
    pc.register(_PrefixCache.ROOT, (9, 9), 10)
    assert pc.match([1, 2, 3, 4], 2, 2) == []
    assert pc.match([9, 9, 3, 4], 2, 2) == [10]


def _chain(pc, first_bid, tokens, bs=2):
    """Register ``tokens`` as a chain of blocks first_bid, first_bid+1, ..
    and release it: evictable, root coldest."""
    parent, bids = _PrefixCache.ROOT, []
    for j in range(len(tokens) // bs):
        parent = pc.register(parent, tuple(tokens[j * bs:(j + 1) * bs]), first_bid + j)
        bids.append(parent)
    for b in bids:
        pc.release(b)
    return bids


def _parents_evict_lru(pc):
    """``evict_lru`` as it stood before eviction knew the queue (PR 30),
    word for word but for the fourth field of ``meta``: the oracle for
    'with nothing wanted the victims are the parent's'."""
    while pc.lru:
        bid, _ = pc.lru.popitem(last=False)
        if pc.meta.get(bid, [None, None, -1])[2] != 0:
            continue
        freed = []
        stack = [bid]
        while stack:
            b = stack.pop()
            m = pc.meta.pop(b, None)
            if m is None:
                continue
            key, parent, refs = m[:3]
            pc.table.pop(key, None)
            pc.children.get(parent, set()).discard(b)
            stack.extend(pc.children.pop(b, ()))
            pc.lru.pop(b, None)
            if refs == 0:
                freed.append(b)
        return freed
    return []


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_prefix_cache_unit_nothing_wanted_is_plain_lru(seed):
    """Unit: with an empty queue (or traffic that shares nothing) no block
    is wanted, and the freed ids come in the parent's order, cascade for
    cascade, over random trees of chains, pins and releases."""
    def build():
        rng = random.Random(seed)
        pc = _PrefixCache()
        bids = []
        for bid in range(1, 41):  # a random forest: parent = ROOT or an older block
            parent = rng.choice([_PrefixCache.ROOT] + bids[-6:])
            bids.append(pc.register(parent, (rng.randrange(10**6),), bid))
        rng.shuffle(bids)
        for b in bids[:32]:  # the rest stay pinned, some under released parents
            pc.release(b)
        for b in rng.sample(bids[:32], 8):  # re-warm a few, as a hit and release does
            pc.incref(b)
            pc.release(b)
        return pc

    ours, theirs = build(), build()
    assert list(ours.lru) == list(theirs.lru) and ours.evictable_blocks == 32
    # A request that matched nothing wants nothing.
    assert ours.want([5, 5], 1, 2) == []
    sequence = []
    while ours.evictable_blocks:
        freed = ours.evict_lru()
        assert freed == _parents_evict_lru(theirs)
        sequence.append(freed)
    assert theirs.evictable_blocks == 0 and ours.meta.keys() == theirs.meta.keys()
    assert any(len(f) > 1 for f in sequence)  # some cascades among them
    assert ours.spared == 0


def test_prefix_cache_unit_eviction_spares_the_wanted_chain():
    """Unit (a): the coldest chain is one a waiting request matches, a
    warmer one nobody asks for: the warmer one goes, whole, and the wanted
    one still hits."""
    pc = _PrefixCache()
    cold = _chain(pc, 10, [1, 2, 3, 4, 5, 6])
    warm = _chain(pc, 20, [7, 8, 9, 10])
    assert list(pc.lru) == cold + warm
    mine = pc.want([1, 2, 3, 4, 5, 6, 99], 2, 3)  # a follow-up in the queue
    assert [m[3] for m in mine] == [1, 1, 1]
    assert pc.evict_lru() == warm
    assert pc.spared == len(warm)
    assert pc.match([1, 2, 3, 4, 5, 6, 99], 2, 3) == cold
    # Nothing unwanted is left: plain eviction declines, the caller falls back.
    assert pc.evict_lru() == [] and pc.evictable_blocks == 3
    # Admitted (or gone): the marks go, and the chain is plain LRU's again.
    pc.unwant(mine)
    assert pc.evict_lru() == cold and pc.resident_blocks == 0


def test_prefix_cache_unit_fallback_takes_a_leaf_from_the_queues_tail():
    """Unit (b): every evictable block is wanted. The victim is ONE
    childless block, the leaf end of the chain of the request furthest
    back; what is left of that chain still hits; the head's chain is
    whole. The chains come last-in-queue first."""
    pc = _PrefixCache()
    head = _chain(pc, 10, [1, 2, 3, 4, 5, 6])  # coldest: released first
    back = _chain(pc, 20, [7, 8, 9, 10, 11, 12])
    w_head = pc.want([1, 2, 3, 4, 5, 6, 0], 2, 3)
    w_back = pc.want([7, 8, 9, 10, 11, 12, 0], 2, 3)
    assert pc.evict_lru() == []
    assert pc.evict_wanted([w_back, w_head]) == [back[-1]]
    assert pc.match([7, 8, 9, 10, 11, 12, 0], 2, 3) == back[:2]  # a partial chain survives
    assert pc.evict_wanted([w_back, w_head]) == [back[1]]
    assert pc.evict_wanted([w_back, w_head]) == [back[0]]
    assert pc.match([1, 2, 3, 4, 5, 6, 0], 2, 3) == head  # untouched until now
    assert pc.evict_wanted([w_back, w_head]) == [head[-1]]
    # The marks of evicted blocks died with them: a block that reuses an id
    # is nobody's, and unwant on the old chains leaves it alone.
    again = pc.register(_PrefixCache.ROOT, (50, 51), back[0])
    pc.unwant(w_back)
    pc.unwant(w_head)
    assert pc.meta[again][3] == 0
    assert all(m[3] == 0 for m in pc.meta.values())


def test_prefix_cache_unit_fallback_skips_a_chain_with_nothing_to_give():
    """Unit: the tail request's last resident block is pinned by a running
    twin (nothing of it is evictable), the next one's is a leaf: that one
    goes. With only a pinned descendant below every evictable block, the
    coldest goes whole and the pinned child is unregistered, not freed."""
    pc = _PrefixCache()
    twin = _chain(pc, 10, [1, 2, 3, 4])
    other = _chain(pc, 20, [5, 6, 7, 8])
    for b in twin:
        pc.incref(b)  # a running request maps the same chain
    w_tail = pc.want([1, 2, 3, 4, 0], 2, 2)
    w_next = pc.want([5, 6, 7, 8, 0], 2, 2)
    assert pc.evict_wanted([w_tail, w_next]) == [other[-1]]
    assert pc.evict_wanted([w_tail, w_next]) == [other[0]]
    assert pc.evict_wanted([w_tail, w_next]) == [] and pc.evictable_blocks == 0
    # A pinned child under an evictable wanted parent.
    pc.release(twin[0])
    assert pc.evictable_blocks == 1 and pc.evict_lru() == []
    assert pc.evict_wanted([w_tail]) == [twin[0]]
    assert pc.resident_blocks == 0  # the child was unregistered with it
    assert pc.release(twin[1]) is False  # its slot frees it to the allocator


def _pump(eng):
    while eng.active_count() or eng.waiting:
        eng.step()
        _cache_invariants(eng)


def test_eviction_spares_the_queued_follow_ups_history(tiny_model):
    """Engine (d): a conversation's first turn finishes, then another
    request's; the follow-up turn waits behind a third. The third's
    allocation must evict, and the coldest chain in the cache is the
    follow-up's history (released first): plain LRU took it (0 hit tokens
    for the follow-up at the parent), eviction that knows the queue takes
    the chain nobody waits for. The follow-up hits on all of its history
    that was ever cached, and serves what a cache-off engine serves."""
    cfg, params = tiny_model
    kw = dict(num_blocks=10, max_batch=1, max_blocks_per_seq=6)
    eng = _engine(cfg, params, enable_prefix_cache=True, **kw)
    history = [100 + i for i in range(24)]  # 3 full blocks
    answer = eng.generate_batch([history], 6)[0]
    eng.generate_batch([[150 + i for i in range(24)]], 6)  # ends: a warmer, dead chain
    assert eng.prefix_cache.evictable_blocks == 6 and eng.alloc.available == 3
    follow_up = history + answer + [7, 8]
    before = dict(eng.stats)
    ahead = eng.add_request([200 + i for i in range(24)], 6)  # 3 blocks, a 4th to decode
    asked = eng.add_request(follow_up, 6)
    eng.step()
    assert eng.slots[0] is ahead and list(eng.waiting) == [asked]
    assert [m[3] for m in asked.wanted] == [1, 1, 1]  # its history, marked while it waits
    _pump(eng)
    moved = {k: eng.stats[k] - before[k] for k in before}
    assert moved["prefix_evictions"] == 3 and moved["prefix_evictions_spared"] == 3
    assert moved["prefix_evictions_wanted"] == 0
    assert moved["prefix_hit_tokens"] == len(history)
    assert list(asked.tokens(timeout=60)) == _engine(cfg, params, **kw).generate_batch(
        [follow_up], 6)[0]
    snap = eng.report_state()["prefix_cache"]
    assert (snap["evictions"], snap["evictions_spared"], snap["evictions_wanted"]) == (
        eng.stats["prefix_evictions"], 3, 0)


@pytest.mark.parametrize("prompt_tokens,wanted,taken,hit_tokens", [
    (24, (1, 1, 1, 1), 2, 16), (8, (1, 1, 1, 1), 0, 32)], ids=["prompts_blocks", "answers_blocks"])
def test_preempted_request_is_wanted_while_it_waits(tiny_model, prompt_tokens, wanted, taken,
                                                    hit_tokens):
    """Engine (e): the pool runs out, the younger request is preempted and
    requeued: the blocks it released, those its decode steps filled among
    them, are wanted from that moment. The survivor then needs blocks and
    every evictable one is the waiting request's: the fallback takes them
    ONE at a time, from the leaf, so the resume hits on what is left (the
    parent evicted the root, the chain with it, and re-prefilled
    everything). With prompts of 24 tokens the survivor takes two, the
    block the preempted answer had filled and the prompt's last, and the
    resume hits on the prompt's first two; with prompts of one block the
    survivor ends before it needs any, and the resume hits on four blocks,
    three of them its own answer's, found again where it left them."""
    cfg, params = tiny_model
    kw = dict(num_blocks=10, max_batch=2, max_blocks_per_seq=8)
    prompts = [[100 + i for i in range(prompt_tokens)], [150 + i for i in range(prompt_tokens)]]
    expect = _engine(cfg, params).generate_batch(prompts, 30)
    eng = _engine(cfg, params, enable_prefix_cache=True, **kw)
    reqs = [eng.add_request(p, 30) for p in prompts]
    marks = set()
    while eng.active_count() or eng.waiting:
        eng.step()
        _cache_invariants(eng)
        if eng.waiting:
            assert list(eng.waiting) == [reqs[1]]  # the younger one, back at the front
            marks.add(tuple(m[3] for m in reqs[1].wanted))
    assert eng.stats["preemptions"] == 1
    # Wanted while it waited; the last marks' blocks were then evicted under it.
    assert marks == {wanted}
    assert eng.stats["prefix_evictions_wanted"] == taken
    assert eng.stats["prefix_hit_tokens"] == hit_tokens  # resumed on the partial chain
    assert [list(r.tokens(timeout=60)) for r in reqs] == expect
    assert all(m[3] == 0 for m in eng.prefix_cache.meta.values())


def test_wanted_marks_do_not_outlive_the_queue(tiny_model):
    """Engine (f): marks follow a request into the queue and leave with it
    — through admission, a put-back by ``_admit`` (no block for it yet),
    ``stop`` with requests still waiting, and a restart. When the queue has
    drained no block is wanted."""
    cfg, params = tiny_model
    eng = _engine(cfg, params, enable_prefix_cache=True, num_blocks=13, max_batch=2,
                  max_blocks_per_seq=6)
    eng.generate_batch([SHARED], 2)  # two shared blocks, cached
    reqs = [eng.add_request(SHARED + [60 + i, 61 + i], 20) for i in range(6)]
    eng.step()
    assert len(eng.waiting) == 4
    shared = eng.prefix_cache.match(SHARED, 8, 2)
    assert [eng.prefix_cache.meta[b][3] for b in shared] == [4, 4]
    _cache_invariants(eng)
    eng.start()
    time.sleep(0.05)
    eng.stop()  # mid-run: whoever still waits still wants
    assert eng._thread is None
    _cache_invariants(eng)
    eng.start()
    for r in reqs:
        assert len(list(r.tokens(timeout=120))) == 20
    eng.stop()
    assert not eng.waiting and eng.active_count() == 0
    _cache_invariants(eng)
    assert all(m[3] == 0 for m in eng.prefix_cache.meta.values())
    assert all(r.wanted is None for r in reqs)


def test_eviction_choices_reach_the_registry_counter(tiny_model):
    """``serve_engine_prefix_evictions_total`` carries how each evicted
    block was chosen; the three choices add up to ``prefix_evictions``."""
    from ray_tpu.serve.metrics import serve_metrics

    cfg, params = tiny_model
    eng = _engine(cfg, params, enable_prefix_cache=True, num_blocks=10, max_batch=2,
                  max_blocks_per_seq=8)
    eng.metrics_tags = {"deployment": "evictions", "replica": "r0"}
    eng.generate_batch([[100 + i for i in range(24)], [150 + i for i in range(24)]], 30)
    eng.generate_batch([[200 + i for i in range(24)]], 6)
    eng._maybe_flush_metrics(force=True)
    counter = serve_metrics().engine_prefix_evictions
    mine = {dict(tags)["choice"]: value for _n, _t, _d, tags, value in counter._drain()
            if dict(tags)["deployment"] == "evictions"}
    s = eng.stats
    assert mine["wanted"] == s["prefix_evictions_wanted"] == 2
    assert sum(mine.values()) == s["prefix_evictions"]
    assert set(mine) <= {"lru", "spared", "wanted"} and mine["lru"] > 0


# -- a given-back slot publishes its answer's blocks ---------------------------
GIVE_BACK = {
    # answer tokens, engine options, blocks the answer adds to the prompt's one.
    # prompt 12 + answer: positions 0 .. len-2 are written, so a block is
    # published when len - 1 reaches its end.
    "ends_inside_a_block": (8, {}, 1),  # 20 tokens: 19 written, blocks [0,8) [8,16)
    "last_token_opens_a_block": (5, {}, 1),  # 17: 16 written, and no third block
    "last_token_ends_a_block": (4, {}, 0),  # 16: position 15 was never written
    "one_more_fills_it": (13, {}, 2),  # 25: 24 written, blocks up to [16,24)
    "window_overshoots": (8, dict(decode_window=4), 1),  # steps 9-12 wrote 20..22
    "speculated_window_behind_it": (8, dict(decode_window=4, overlap=True), 1),
}


@pytest.mark.parametrize("case", list(GIVE_BACK))
def test_a_given_back_slot_publishes_what_its_steps_filled(tiny_model, case):
    """A finished request's full blocks below position ``len(prompt +
    answer) - 1`` are in the prefix cache, and none above: the last sampled
    token's K/V was never written, and what a window wrote past the end is
    overshoot. The next turn of the conversation hits on all of them, and
    serves the tokens it serves with the cache off."""
    cfg, params = tiny_model
    n_new, kw, answers_blocks = GIVE_BACK[case]
    history = [100 + i for i in range(12)]
    eng = _engine(cfg, params, enable_prefix_cache=True, **kw)
    answer = eng.generate_batch([history], n_new)[0]
    assert eng.prefix_cache.resident_blocks == 1 + answers_blocks
    assert eng.stats["prefix_published_blocks"] == answers_blocks
    assert eng.prefix_cache.resident_blocks == (len(history) + n_new - 1) // 8
    _cache_invariants(eng)
    follow_up = history + answer + [7, 8, 9]
    before = eng.stats["prefix_hit_tokens"]
    got = eng.generate_batch([follow_up], 6)[0]
    assert eng.stats["prefix_hit_tokens"] - before == 8 * (1 + answers_blocks)
    assert got == _engine(cfg, params, **kw).generate_batch([follow_up], 6)[0]
    assert eng.report_state()["prefix_cache"]["published_blocks"] == (
        eng.stats["prefix_published_blocks"])
    _cache_invariants(eng)


def test_an_answer_cut_by_eos_publishes_nothing_of_the_windows_overshoot(tiny_model):
    """An eos inside a window stops the request there; the window's later
    steps, and a speculated window behind it, wrote K/V of tokens that were
    never served into the slot's blocks. None of that is published: the
    follow-up hits on the served transcript alone and is served as with
    the cache off."""
    cfg, params = tiny_model
    kw = dict(decode_window=4, overlap=True)
    history = [100 + i for i in range(12)]
    whole = _engine(cfg, params, **kw).generate_batch([history], 16)[0]
    at = next(k for k in range(5, 16) if whole[k] not in whole[:k])  # its first appearance
    eng = _engine(cfg, params, enable_prefix_cache=True, **kw)
    answer = eng.generate_batch([history], 16, eos_id=whole[at])[0]
    assert answer == whole[:at + 1]
    assert eng.prefix_cache.resident_blocks == (len(history) + len(answer) - 1) // 8
    follow_up = history + answer + [7, 8, 9]
    assert eng.generate_batch([follow_up], 6)[0] == _engine(cfg, params, **kw).generate_batch(
        [follow_up], 6)[0]
    _cache_invariants(eng)


# -- the suffixes one iteration admits share one chunk call ----------------------
_WIDE = dict(num_blocks=129, max_batch=8, max_blocks_per_seq=32)  # widths 8 .. 256, tiles of 32


def _suffixes(k):
    """k prompts over SHARED's two blocks, their suffixes 3 .. 27 tokens."""
    return [SHARED + [30 + 7 * i + j for j in range(3 + 6 * i)] for i in range(k)]


@pytest.mark.parametrize("k", [1, 2, 5])
def test_packed_suffixes_serve_what_they_serve_a_call_each(tiny_model, k):
    """k requests that hit on a shared prefix and are admitted in one
    iteration reach the device as ONE chunk call of k segments, and serve
    the tokens they serve one call each (admitted one after another) and
    with the cache off."""
    cfg, params = tiny_model
    prompts = _suffixes(k)
    expect = _engine(cfg, params, **_WIDE).generate_batch(prompts, 8)
    one_each = _engine(cfg, params, enable_prefix_cache=True, **_WIDE)
    one_each.generate_batch([SHARED], 2)
    assert [one_each.generate_batch([p], 8)[0] for p in prompts] == expect
    assert one_each.stats["prefill_chunks"] == one_each.stats["prefill_segments"] == k
    eng = _engine(cfg, params, enable_prefix_cache=True, **_WIDE)
    eng.generate_batch([SHARED], 2)
    assert eng.generate_batch(prompts, 8) == expect
    assert (eng.stats["prefill_chunks"], eng.stats["prefill_segments"]) == (1, k)
    assert eng.stats["prefix_hit_tokens"] == 16 * k
    assert [(st["chunks"], st["segments"]) for st in eng.recorder.snapshot()["steps"]
            if st["segments"]] == [(1, k)]
    _cache_invariants(eng)


def test_a_wide_suffix_takes_a_call_of_its_own(tiny_model, monkeypatch):
    """The next suffix starts a call of its own when the joined call would
    be wider than the two apart. Suffixes of 104, 28 and 30 tokens: 104
    fills a call of 128, and 28 more would make it 256 wide, mostly
    padding, against 128 + 32 apart; the two narrow ones share a call of
    64, which is what they cost apart."""
    from ray_tpu.serve import llm_engine

    monkeypatch.setattr(llm_engine, "_WEIGHTS_WIDTH", 32)  # the toy's tile, as 256 is four of the cell's
    cfg, params = tiny_model
    prompts = [SHARED + [40 + n + j for j in range(n)] for n in (100, 24, 26)]
    expect = _engine(cfg, params, **_WIDE).generate_batch(prompts, 4)
    eng = _engine(cfg, params, enable_prefix_cache=True, **_WIDE)
    eng.generate_batch([SHARED], 2)
    widths = []
    call = eng._chunk_call
    monkeypatch.setattr(eng, "_chunk_call", lambda w, segs: widths.append((w, len(segs))) or call(w, segs))
    assert eng.generate_batch(prompts, 4) == expect
    assert widths == [(128, 1), (64, 2)]


@pytest.mark.parametrize("k", [2, 3, 5, 8])
def test_packed_calls_compile_nothing_after_one_request_a_width(tiny_model, k):
    """The compiled shape depends on the call's width alone: after ONE
    request for each width (what the benchmark's warm-up plays), calls of
    k segments at any of them find their program compiled."""
    cfg, params = tiny_model
    eng = _engine(cfg, params, enable_prefix_cache=True, **_WIDE)
    eng.generate_batch([SHARED], 2)
    for width in eng._widths:  # a lone suffix of exactly this width
        eng.generate_batch([SHARED + [50 + width + j for j in range(min(width - 7, 230))]], 2)
    compiled = eng._prefill_chunk_fn._cache_size()
    assert compiled == len(eng._widths)
    before = dict(eng.stats)
    for n in (2, 9, 20):  # k suffixes of n tokens: calls 64 to 256 wide
        eng.generate_batch([SHARED + [90 + 11 * i + n + j for j in range(n)] for i in range(k)], 2)
    assert eng.stats["prefill_segments"] - before["prefill_segments"] == 3 * k
    assert eng.stats["prefill_chunks"] - before["prefill_chunks"] == 3
    assert eng._prefill_chunk_fn._cache_size() == compiled
    _cache_invariants(eng)


@pytest.mark.slow
def test_engine_perf_suite_stress(tiny_model):
    """Long-running mixed workload (cache + chunks + overlap + windows +
    preemption pressure): invariants hold and every request completes
    with the right token count."""
    cfg, params = tiny_model
    eng = _engine(
        cfg, params, enable_prefix_cache=True, prefill_chunk=16,
        overlap=True, decode_window=4, num_blocks=25,
    )
    reqs = []
    for r in range(6):
        for i in range(6):
            n = 4 + (i * 7 + r) % 9
            reqs.append(eng.add_request(SHARED + [r, i], max_new_tokens=n))
        while eng.active_count() or eng.waiting:
            eng.step()
    for q in reqs:
        toks = list(q.tokens(timeout=60))
        assert len(toks) == q.max_new_tokens
    _cache_invariants(eng)
