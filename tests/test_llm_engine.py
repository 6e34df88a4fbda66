"""Paged KV cache + continuous-batching engine + @serve.batch.

The reference's serving parity story is vLLM-on-Ray (SURVEY §2.9); these
tests cover the native replacements: block-paged decode matching the
contiguous-cache reference path, iteration-level admission, recompute
preemption, and the serve.batch queue
(reference: python/ray/serve/batching.py:468).
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.generate import generate
from ray_tpu.models.paged import PagedConfig
from ray_tpu.models.transformer import TransformerConfig, init_params
from ray_tpu.serve.llm_engine import LLMEngine


@pytest.fixture(autouse=True)
def _highest_precision():
    """Token-for-token assertions compare two differently-shaped
    computations of the same math; run the whole module at fp32 matmul
    precision so rounding can't flip an argmax (see conftest note)."""
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
    pcfg = PagedConfig(**{**dict(block_size=8, num_blocks=33, max_batch=4,
                                 max_blocks_per_seq=8), **kw})
    return LLMEngine(params, cfg, pcfg)


def test_paged_decode_matches_contiguous_generate(tiny_model):
    """Greedy paged decode must match the contiguous-cache generate()
    path token for token (same math, different memory layout)."""
    cfg, params = tiny_model
    eng = _engine(cfg, params)
    prompts = [[5, 9, 2, 11, 3], [17, 1, 8], [30, 31, 32, 33, 34, 35, 36]]
    outs = eng.generate_batch(prompts, max_new_tokens=12)
    for p, o in zip(prompts, outs):
        ref = generate(params, cfg, jnp.asarray([p], jnp.int32), 12)
        assert o == list(np.asarray(ref[0])), f"prompt {p}"
    assert eng.stats["max_active"] == 3
    assert eng.stats["preemptions"] == 0


def test_continuous_admission_more_requests_than_slots(tiny_model):
    """8 requests through 4 slots: retired slots must be refilled from
    the waiting queue mid-flight (iteration-level scheduling)."""
    cfg, params = tiny_model
    eng = _engine(cfg, params, max_batch=4)
    prompts = [[i + 1, i + 2, i + 3] for i in range(8)]
    outs = eng.generate_batch(prompts, max_new_tokens=6)
    assert all(len(o) == 6 for o in outs)
    assert eng.stats["max_active"] == 4  # saturated
    assert eng.stats["prefills"] == 8


def test_preemption_recompute_completes(tiny_model):
    """A pool too small for all sequences forces eviction; evicted
    requests must resume via re-prefill and still finish."""
    cfg, params = tiny_model
    # 12 usable blocks * 8 = 96 cache tokens; 4 seqs * (4 + 28) = 128
    # tokens needed at full length → somebody must get preempted.
    eng = _engine(cfg, params, num_blocks=13, max_batch=4, max_blocks_per_seq=4)
    prompts = [[i + 1, i + 2, i + 3, i + 4] for i in range(4)]
    outs = eng.generate_batch(prompts, max_new_tokens=28)
    assert all(len(o) == 28 for o in outs)
    assert eng.stats["preemptions"] > 0
    # Preempted-and-resumed greedy decode must agree with an unpressured
    # run of the same prompt.
    calm = _engine(cfg, params)
    calm_outs = calm.generate_batch(prompts, max_new_tokens=28)
    assert outs == calm_outs


def test_eos_stops_early(tiny_model):
    cfg, params = tiny_model
    eng = _engine(cfg, params)
    [out] = eng.generate_batch([[3, 1, 4, 1, 5]], max_new_tokens=10)
    assert len(out) == 10
    # Pick an actually-produced token whose FIRST occurrence is
    # mid-stream (a repeated token would legitimately stop earlier).
    k = next((k for k in range(1, 10) if out.index(out[k]) == k), None)
    if k is None:
        pytest.skip("greedy output degenerated to pure repetition")
    eos = out[k]
    eng2 = _engine(cfg, params)
    [out2] = eng2.generate_batch([[3, 1, 4, 1, 5]], max_new_tokens=10, eos_id=eos)
    assert out2 == out[: k + 1]  # stops AT the eos token


def test_request_rejected_when_too_long(tiny_model):
    cfg, params = tiny_model
    eng = _engine(cfg, params)  # max_seq_len = 64
    req = eng.add_request([1] * 60, max_new_tokens=10)
    with pytest.raises(RuntimeError, match="exceeds capacity"):
        list(req.tokens(timeout=5))


def test_streaming_two_clients_share_one_batch(tiny_model):
    """Two concurrent clients stream tokens from the SAME decode batch —
    the engine pump thread serves both; token timelines interleave."""
    cfg, params = tiny_model
    eng = _engine(cfg, params)
    eng.start()
    try:
        results = {}

        def client(name, prompt):
            req = eng.add_request(prompt, max_new_tokens=16)
            toks = []
            for t in req.tokens(timeout=60):
                toks.append((t, time.monotonic()))
            results[name] = toks

        t1 = threading.Thread(target=client, args=("a", [2, 4, 6]))
        t2 = threading.Thread(target=client, args=("b", [1, 3, 5, 7]))
        t1.start(); t2.start(); t1.join(60); t2.join(60)
        assert len(results["a"]) == 16 and len(results["b"]) == 16
        assert eng.stats["max_active"] == 2  # truly shared a batch
        # Interleaved in time: a's stream starts before b's ends and
        # vice versa (not serial execution).
        a_times = [ts for _, ts in results["a"]]
        b_times = [ts for _, ts in results["b"]]
        assert a_times[0] < b_times[-1] and b_times[0] < a_times[-1]
    finally:
        eng.stop()


def test_serve_batch_decorator_batches_concurrent_calls():
    from ray_tpu.serve.batching import batch

    calls = []

    class Model:
        @batch(max_batch_size=4, batch_wait_timeout_s=0.2)
        def predict(self, items):
            calls.append(list(items))
            return [x * 10 for x in items]

    m = Model()
    results = {}
    threads = [
        threading.Thread(target=lambda i=i: results.__setitem__(i, m.predict(i)))
        for i in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert results == {0: 0, 1: 10, 2: 20, 3: 30}
    # All four went through in one (or at most two) underlying calls.
    assert len(calls) <= 2
    assert sum(len(c) for c in calls) == 4


def test_serve_batch_propagates_errors_and_size_mismatch():
    from ray_tpu.serve.batching import batch

    @batch(max_batch_size=2, batch_wait_timeout_s=0.05)
    def bad(items):
        return [1]  # wrong length on a 2-batch, right length on a 1-batch

    @batch(max_batch_size=1, batch_wait_timeout_s=0.01)
    def boom(items):
        raise RuntimeError("kaput")

    with pytest.raises(RuntimeError, match="kaput"):
        boom(1)
    # Single call → length-1 batch → valid.
    assert bad(5) == 1


def test_empty_prompt_rejected_and_pool_not_drained(tiny_model):
    """Regression: alloc(0) must not hand out the whole free list."""
    cfg, params = tiny_model
    eng = _engine(cfg, params)
    free_before = eng.alloc.available
    req = eng.add_request([], max_new_tokens=4)
    with pytest.raises(RuntimeError, match="non-empty"):
        list(req.tokens(timeout=5))
    assert eng.alloc.available == free_before
    # And a zero-alloc is an empty list, not the pool.
    assert eng.alloc.alloc(0) == []
    assert eng.alloc.available == free_before


def test_windowed_decode_matches_window1(tiny_model):
    """decode_window > 1 (multi-step scan per device call) must be
    token-for-token identical to per-step decode under greedy sampling,
    including eos mid-window and slot refill afterwards."""
    cfg, params = tiny_model
    prompts = [[5, 9, 2], [17, 1, 8, 4], [30, 31], [7, 6, 5, 4, 3]]
    base = _engine(cfg, params).generate_batch(prompts, max_new_tokens=13)
    eng_w = LLMEngine(
        params, cfg,
        PagedConfig(block_size=8, num_blocks=33, max_batch=2, max_blocks_per_seq=8),
        decode_window=4,
    )
    outs = eng_w.generate_batch(prompts, max_new_tokens=13)
    assert outs == base
    # 2 slots served 4 requests → retirement + refill at window seams.
    assert eng_w.stats["prefills"] == 4 and eng_w.stats["max_active"] == 2
    # eos mid-window stops exactly at the eos token.
    eos = base[0][5]
    eng_e = _engine(cfg, params, max_batch=4)
    eng_we = LLMEngine(
        params, cfg,
        PagedConfig(block_size=8, num_blocks=33, max_batch=4, max_blocks_per_seq=8),
        decode_window=4,
    )
    [e1] = eng_e.generate_batch([prompts[0]], max_new_tokens=13, eos_id=eos)
    [e2] = eng_we.generate_batch([prompts[0]], max_new_tokens=13, eos_id=eos)
    assert e1 == e2 and e1[-1] == eos


def test_overlap_decode_matches_synchronous(tiny_model):
    """Host/device overlap (window N+1 dispatched before N's tokens are
    read) must be token-for-token identical to synchronous stepping —
    including eos mid-window and slot retirement/refill at seams."""
    cfg, params = tiny_model
    prompts = [[5, 9, 2], [17, 1, 8, 4], [30, 31], [7, 6, 5, 4, 3]]
    for w in (1, 4):
        base = LLMEngine(
            params, cfg,
            PagedConfig(block_size=8, num_blocks=33, max_batch=4,
                        max_blocks_per_seq=8),
            decode_window=w,
        ).generate_batch(prompts, max_new_tokens=12)
        eng_o = LLMEngine(
            params, cfg,
            PagedConfig(block_size=8, num_blocks=33, max_batch=4,
                        max_blocks_per_seq=8),
            decode_window=w, overlap=True,
        )
        assert eng_o.generate_batch(prompts, max_new_tokens=12) == base
        # The point of overlap: most windows dispatched speculatively.
        assert eng_o.stats["spec_windows"] > 0
        # eos stops exactly at the eos token under speculation too (pick
        # a token whose FIRST occurrence is mid-stream, not a repeat).
        k = next(
            (k for k in range(1, 12) if base[0].index(base[0][k]) == k), None
        )
        if k is None:
            pytest.skip("greedy output degenerated to pure repetition")
        eos = base[0][k]
        eng_e = LLMEngine(
            params, cfg,
            PagedConfig(block_size=8, num_blocks=33, max_batch=4,
                        max_blocks_per_seq=8),
            decode_window=w, overlap=True,
        )
        [e] = eng_e.generate_batch([prompts[0]], max_new_tokens=12, eos_id=eos)
        assert e == base[0][: k + 1] and e[-1] == eos


def test_overlap_preemption_under_pressure(tiny_model):
    """Preempting a slot whose speculated window is still in flight must
    not corrupt any stream: the stale window's lanes are discarded (rid
    check) and the victim resumes to an identical greedy output."""
    cfg, params = tiny_model
    prompts = [[i + 1, i + 2, i + 3, i + 4] for i in range(4)]
    calm = _engine(cfg, params).generate_batch(prompts, max_new_tokens=24)
    eng = LLMEngine(
        params, cfg,
        PagedConfig(block_size=8, num_blocks=13, max_batch=4,
                    max_blocks_per_seq=4),
        decode_window=2, overlap=True,
    )
    outs = eng.generate_batch(prompts, max_new_tokens=24)
    assert outs == calm
    assert eng.stats["preemptions"] > 0


@pytest.mark.parametrize("new_tokens,eos_at", [(13, None), (9, None), (1, None), (13, 6)],
                         ids=["ends-on-a-window", "ends-mid-window", "first-token-only", "eos-mid-window"])
def test_a_windows_tokens_are_one_queue_entry_and_tokens_yields_each_once(tiny_model, new_tokens, eos_at):
    """A request is handed what a program call gave it as ONE entry of ``out``
    (the prefill's first token alone, then a window's four together, the last
    entry cut at the request's end), and ``Request.tokens()`` still yields token
    by token, every token once, whether the request ends on a window's last
    token, in the middle of one, with its first token, or on an eos mid-window."""
    cfg, params = tiny_model
    prompt = [5, 9, 2]
    [base] = _engine(cfg, params).generate_batch([prompt], max_new_tokens=13)
    eos = None
    if eos_at is not None:
        eos_at = next((k for k in range(eos_at, 13) if base.index(base[k]) == k), None)
        if eos_at is None:
            pytest.skip("greedy output degenerated to pure repetition")
        eos = base[eos_at]
    want = base[:new_tokens] if eos is None else base[:eos_at + 1]
    eng = LLMEngine(
        params, cfg,
        PagedConfig(block_size=8, num_blocks=33, max_batch=2, max_blocks_per_seq=8),
        decode_window=4,
    )
    req = eng.add_request(prompt, new_tokens, eos_id=eos)
    while eng.active_count() or eng.waiting:
        eng.step()
    entries = list(req.out.queue)
    assert entries[-1] is None and all(isinstance(e, list) and e for e in entries[:-1])
    # the first token alone, then whole windows, the last one cut at the end
    assert [len(e) for e in entries[:-1]] == [1] + [min(4, len(want) - 1 - k) for k in range(0, len(want) - 1, 4)]
    assert list(req.tokens(timeout=5)) == want == req.generated
    assert eng.stats["tokens"] == len(want) and eng.stats["emit_batches"] == len(entries) - 1


def test_a_reader_is_woken_once_a_window_not_once_a_token(tiny_model):
    """Two requests in one batch, each read by a thread as a streaming driver
    reads them: every token arrives once and in order, in a quarter of the
    queue entries (windows of four)."""
    cfg, params = tiny_model
    eng = LLMEngine(
        params, cfg,
        PagedConfig(block_size=8, num_blocks=33, max_batch=2, max_blocks_per_seq=8),
        decode_window=4,
    )
    prompts = [[5, 9, 2], [17, 1, 8, 4]]
    base = _engine(cfg, params).generate_batch(prompts, max_new_tokens=17)
    eng.start()
    try:
        reqs = [eng.add_request(p, 17) for p in prompts]
        got = [[] for _ in reqs]
        threads = [threading.Thread(target=lambda r=r, g=g: g.extend(r.tokens(timeout=60)))
                   for r, g in zip(reqs, got)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        eng.stop()
    assert got == base
    assert eng.stats["tokens"] == 34 and eng.stats["emit_batches"] == 2 * (1 + 4)
