"""Continuous-batching LLM engine over the paged KV cache.

The reference serves LLMs by running vLLM engines as Ray actors
(SURVEY §2.9 "delegated"); here the engine is native. It implements
iteration-level scheduling (Orca/vLLM): between every decode iteration
the host admits waiting requests into free slots, allocates KV blocks
on demand, and retires finished sequences — so one compiled decode
program continuously serves an evolving request mix.

Host/device split:
- Device (``ray_tpu/models/paged.py``): one jitted decode step over all
  ``max_batch`` slots; one jitted prefill per prompt bucket; one chunk
  program (suffix prefill attending to resident blocks). Sampling is
  on-device; a step moves only ``[b]`` int32 tokens back.
- Host (this module): block free-list, slot assignment, preemption
  (victim's blocks are freed and the request re-queued with its
  generated prefix folded into the prompt — recompute-on-resume, the
  vLLM default), per-request streaming queues.

Iteration-level perf suite (opt-in unless it says otherwise, see ``__init__``):
- **Prefix-aware KV reuse** (``enable_prefix_cache``): a request's full
  blocks are published to a refcounted exact-match index, the prompt's
  as its prefill ends and those its decode steps filled as its slot is
  given back, and kept resident after release (evicted on allocation
  pressure, the coldest that no waiting request matches first);
  requests sharing a prefix map resident blocks into their table and
  prefill only the novel suffix, so a conversation's next turn prefills
  what the user added. The suffixes one iteration admits share ONE call
  of the chunk program (``_run_chunks``).
- **Chunked prefill** (``prefill_chunk``): long prompts advance one
  fixed-size chunk per scheduler step, interleaved with decode windows,
  so an admission no longer head-of-line-blocks active streams.
- **Host/device overlap** (``overlap``): window N+1 is dispatched from
  window N's device-resident outputs before N's tokens are read; the
  host consumes/schedules while the device keeps stepping. Decode
  inputs live on device: ``tables``/``lens``/``temps`` are re-shipped only
  when the scheduler dirtied them (``_ship``), ``cur`` never leaves it.
- **The window behind the prefill** (always): a prefill program puts the
  token it sampled into the device's ``cur``, so the step dispatches its
  decode window before the host has read that token and the dispatch's
  host work runs while the device prefills (``step``).

Threading: ``step()`` is single-threaded; ``start()`` runs it in a pump
thread so serve replicas can stream from concurrent handler threads
while one engine drives the chip.
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import itertools
import logging
import os
import queue
import threading
import time
from typing import TYPE_CHECKING, Dict, List, Optional, Sequence, Union

logger = logging.getLogger("ray_tpu.serve.engine")

import jax
import numpy as np
from jax.experimental.layout import Format, Layout

from ray_tpu.models.paged import (
    TRASH_BLOCK,
    PagedConfig,
    block_pools,
    chunk_tile,
    init_paged_cache,
    paged_decode_loop,
    prefill_and_sample,
    prefill_chunk_and_sample,
    slot_pools,
)
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.util import tracing

if TYPE_CHECKING:
    from ray_tpu.models.hybrid_ssm import HybridSSMConfig
    from ray_tpu.models.kda_moe import KDAMoEConfig
    from ray_tpu.models.latent_moe import LatentMoEConfig

_req_ids = itertools.count()
_engine_ids = itertools.count()

# The phases of one scheduler iteration (``tracing.phase("engine.<name>")``):
# siblings that together cover ``LLMEngine.step`` with no span around them,
# because a reader of the device trace lays an idle gap to the host span that
# covers most of it, and an enclosing span always would. Each recorded step
# carries ``<name>_ms``.
_PHASES = ("harvest_wait", "emit", "admit", "prefill_wait", "dispatch", "record")
# The parts of ``admit`` and of ``dispatch``, ``tracing.phase("engine.admit.plan")``
# and so on, NESTED in their parent and never in one another: a gap that lies
# inside one part takes its name (the shortest span that covers it), a gap
# across several keeps the parent's. A span per admitted request and per
# program call, never per token, block or slot. Steps carry ``<part>_ms`` too.
_SUB_PHASES = ("admit_plan", "admit_build", "admit_key", "admit_launch",
               "dispatch_blocks", "dispatch_key", "dispatch_ship", "dispatch_launch")
# A recorded step's ``<name>_ms`` and the span it is the milliseconds of.
_STEP_MS = tuple((f"{name}_ms", "engine." + name) for name in _PHASES) + tuple(
    (f"{sub}_ms", "engine." + sub.replace("_", ".", 1)) for sub in _SUB_PHASES)
# Where the scheduler thread can be while the device has nothing queued
# (``LLMEngine._at``): ``stats["starved_us_<where>"]``. ``between`` is outside
# every phase: the turn of the loop, and what a step does between two phases.
_STARVED = _SUB_PHASES + ("emit", "record", "between")
# Why a window in flight was not overlapped: ``stats["spec_blocked_<reason>"]``.
_SPEC_BLOCKED = ("idle", "admission", "finishing")
# The counters a recorded step carries as what the iteration added to them.
_STEP_COUNTS = ("tokens", "prefills", "preemptions", "admitted", "prefill_chunks",
                "prefill_segments", "prefill_width_tokens", "prefix_hit_tokens",
                "windows_behind_prefill", "state_slots_live")
# The width under which a chunk call's time is the read of the weights and no
# longer its tokens' arithmetic: two FLOPs and two bytes a parameter a token
# put it at peak FLOP/s over peak bytes/s, 240 tokens on a v5e whatever the
# model, as long as a token multiplies every weight the call reads (an expert
# layer's token multiplies a few of them: its ridge lies higher). Measured on
# the dense decoder of Mistral-7B's widths, whose chunk program read 16.2 /
# 17.2 / 20.35 ms at 64 / 128 / 256 tokens and 0.086 ms a token above (PERF.md
# section 5). ``_run_suffixes`` packs by it, and a fixed ``prefill_chunk``'s
# ladder of widths ends above it (``_chunk_ladder``).
_WEIGHTS_WIDTH = 256
# Counts an expert model's programs return behind their tokens
# (``paged._with_counts``), in this order: token-expert pairs computed by the
# experts held here; held experts with at least one pair, summed over expert
# layers and steps (or calls); expert layers x steps (or calls) counted; of
# those, the ones whose products the kernel ``moe_decode_experts`` made (a call
# under the chip's ridge: ``ops/moe.fused``). They
# arrive on the transfers that bring the tokens: a chunk call whose output the
# host never reads (no segment of it ends a prompt) is not counted.
_MOE_COUNTS = ("moe_pairs_here", "moe_experts_touched", "moe_layer_steps", "moe_fused_layer_steps",
               "moe_grouped_layer_steps")
# Further counts of a model whose attention selects what it reads or slides a
# window (``models/sparse_latent_moe.py``), behind the five above: cached tokens
# a query could have read, summed over slots (or a chunk call's real queries),
# selecting layers and steps; how many of them it selected; cache rows the
# window layers' reads covered. Behind those, of a model with several residual
# streams a token (``models/hyper_latent_moe.py``): token places (padding and
# idle slots included) times the sublayers whose output was mixed into the
# streams, and real tokens times the same. A model returns the first so many
# of ``_COUNTS``.
_COUNTS = _MOE_COUNTS + ("sparse_keys_live", "sparse_keys_selected", "window_rows_read",
                         "hc_places_mixed", "hc_tokens_mixed")


def _chunk_ladder(chunk: int, block_size: int) -> List[int]:
    """The widths an engine with a fixed ``prefill_chunk`` calls its chunk
    program at, narrowest first: the chunk, then its halves for as long as a
    half is whole tiles of four blocks and no narrower than ``_WEIGHTS_WIDTH``
    (under it a call is the read of the weights whatever its width, so a
    narrower program would be compiled for nothing)."""
    widths = [chunk]
    while widths[0] % (8 * block_size) == 0 and widths[0] // 2 >= _WEIGHTS_WIDTH:
        widths.insert(0, widths[0] // 2)
    return widths


@dataclasses.dataclass
class Request:
    """One generation request; ``out`` streams generated token ids, a run
    of them an entry (what one program call gave the request: a window's
    tokens, or a prefill's first token alone), and a final ``None`` sentinel."""

    prompt: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    eos_id: Optional[int] = None
    rid: int = dataclasses.field(default_factory=lambda: next(_req_ids))
    out: "queue.Queue" = dataclasses.field(default_factory=queue.Queue)
    generated: List[int] = dataclasses.field(default_factory=list)
    # Set on rejection (prompt too long etc.); the sentinel is still sent.
    error: Optional[str] = None
    # Telemetry lifecycle marks (flight recorder + TTFT/TPOT accounting).
    submit_ts: float = dataclasses.field(default_factory=time.time)
    prefill_ts: Optional[float] = None
    first_token_ts: Optional[float] = None
    # Caller's trace context at add_request time, so the pump thread can
    # parent engine spans under the request's serve-path span tree.
    trace_ctx: Optional[Dict[str, str]] = None
    # While the request waits: the prefix-cache entries it matched when it
    # entered the queue (``_PrefixCache.want``), which eviction spares. None
    # = not looked up yet (or no cache); the scheduler thread alone sets it.
    wanted: Optional[List[list]] = None

    @property
    def remaining(self) -> int:
        return self.max_new_tokens - len(self.generated)

    @property
    def full_prompt(self) -> List[int]:
        """Prompt + everything generated so far — what a preempted
        request must re-prefill on resume (recompute policy)."""
        return self.prompt + self.generated

    def tokens(self, timeout: Optional[float] = None):
        """Iterate generated tokens until the sentinel (blocking: one wait
        a run, ``timeout`` each)."""
        while True:
            run = self.out.get(timeout=timeout)
            if run is None:
                if self.error:
                    raise RuntimeError(self.error)
                return
            yield from run


class FlightRecorder:
    """Fixed-size rings of per-step and per-finished-request records.

    Reference shape: Ray's per-worker task event buffer (bounded, drained
    for the timeline) and vLLM's engine stats loop. Appends happen on the
    engine's single scheduler thread and are plain deque appends (the
    maxlen bound makes them O(1) and allocation-free beyond the record
    dict) — ``snapshot()`` copies under the GIL, so readers never block
    the step loop.
    """

    def __init__(self, step_capacity: int = 256, request_capacity: int = 256):
        self.steps: "collections.deque[dict]" = collections.deque(maxlen=step_capacity)
        self.requests: "collections.deque[dict]" = collections.deque(maxlen=request_capacity)

    def record_step(self, rec: dict):
        self.steps.append(rec)

    def record_request(self, rec: dict):
        self.requests.append(rec)

    def latency_summary(self) -> Dict[str, Dict[str, float]]:
        """p50/p95/p99 per latency field over the recent-request ring —
        queryable without scraping Prometheus."""
        from ray_tpu.serve.metrics import summarize_latencies

        reqs = list(self.requests)
        return summarize_latencies({
            field: [r[field] for r in reqs if r.get(field) is not None]
            for field in ("queue_ms", "ttft_ms", "tpot_ms", "e2e_ms")
        })

    def snapshot(self) -> dict:
        return {
            "steps": list(self.steps),
            "recent_requests": list(self.requests),
            "latency_ms": self.latency_summary(),
        }


class _BlockAllocator:
    def __init__(self, pcfg: PagedConfig):
        # Block 0 is the trash block — never handed out.
        self.free = list(range(pcfg.num_blocks - 1, TRASH_BLOCK, -1))

    def alloc(self, n: int) -> Optional[List[int]]:
        if n <= 0:
            return []  # NOT free[-0:] — that slice is the whole list
        if len(self.free) < n:
            return None
        got, self.free = self.free[-n:], self.free[:-n]
        return got

    def release(self, blocks: Sequence[int]):
        self.free.extend(b for b in blocks if b != TRASH_BLOCK)

    @property
    def available(self) -> int:
        return len(self.free)


class _PrefixCache:
    """Refcounted index over resident KV blocks (vLLM automatic prefix
    caching, re-done for this engine's allocator).

    Each FULL block of a request's prompt and answer (``LLMEngine.
    _free_slot`` says which positions are certain) is keyed by
    ``(parent_block_id, block_tokens)``
    — an exact-match chain, so a hit can never alias a different prefix
    (no hash collisions; the parent link makes position implicit). Blocks
    referenced by live slots are pinned (refs > 0); released blocks stay
    RESIDENT in an LRU of refcount-0 blocks and are only returned to the
    allocator when an allocation actually needs them (eviction cascades
    to cached descendants, since a re-used parent id must never re-link
    a stale child chain).

    **Eviction order: the coldest block that no waiting request matches.**
    A block is *wanted* while a request in the engine's queue matched it
    when it entered (``want`` / ``unwant``; a count beside ``refs``).
    ``evict_lru`` takes the coldest refcount-0 block that is not wanted;
    with nothing wanted that is plain LRU. Only when every evictable
    block is wanted does ``evict_wanted`` take ONE childless block, the
    leaf end of the chain of the request furthest back in the queue.

    Why plain LRU was the worst case for a FIFO queue: a finished turn's
    blocks enter the LRU root first, so the coldest evictable block is
    the FIRST block of some conversation, and evicting it cascades to the
    whole chain. The LRU orders by release time, and a closed loop's
    follow-up turn enters the queue at that same moment: the coldest
    chain belongs to the request nearest the head of the queue, while the
    chains of conversations that ended sit warmer and survive. The same
    holds for a preempted request, whose blocks every later release
    pushes colder while it waits.
    """

    ROOT = -1  # parent id for the first block of every prompt

    def __init__(self):
        # (parent_bid, tokens) -> bid; bid -> [key, parent, refs, wanted]
        self.table: Dict[tuple, int] = {}
        self.meta: Dict[int, list] = {}
        self.children: Dict[int, set] = {}
        # refcount-0 residents, coldest first (re-warmed on hit/release).
        self.lru: "collections.OrderedDict[int, None]" = collections.OrderedDict()
        # Blocks freed by evictions that passed over a colder wanted block.
        self.spared = 0

    @property
    def resident_blocks(self) -> int:
        return len(self.meta)

    @property
    def evictable_blocks(self) -> int:
        return len(self.lru)

    def match(self, tokens: Sequence[int], bs: int, limit: int) -> List[int]:
        """Longest cached chain of full blocks covering ``tokens`` (read
        only — no refcount change), capped at ``limit`` blocks so the
        caller always keeps >= 1 suffix token to prefill (the engine
        needs last-position logits to sample the first output token)."""
        bids: List[int] = []
        parent = self.ROOT
        for j in range(limit):
            bid = self.table.get((parent, tuple(tokens[j * bs:(j + 1) * bs])))
            if bid is None:
                break
            bids.append(bid)
            parent = bid
        return bids

    def want(self, tokens: Sequence[int], bs: int, limit: int) -> List[list]:
        """Mark the chain ``match`` returns as wanted by one more waiting
        request; returns the marked ``meta`` ENTRIES (not block ids: an
        entry dies with its block, so ``unwant`` on a chain that lost
        blocks meanwhile can never touch a block that reuses their ids).
        A chain is marked from its root, so a block that is not wanted has
        no wanted descendant."""
        chain = [self.meta[b] for b in self.match(tokens, bs, limit)]
        for m in chain:
            m[3] += 1
        return chain

    def unwant(self, chain: Sequence[list]):
        for m in chain:
            m[3] -= 1

    def incref(self, bid: int):
        m = self.meta[bid]
        m[2] += 1
        if m[2] == 1:
            self.lru.pop(bid, None)  # pinned — no longer evictable

    def release(self, bid: int) -> bool:
        """Drop one reference; returns False if the block isn't cache-
        managed (caller then frees it to the allocator). A block hitting
        refcount 0 stays resident as the WARMEST eviction candidate."""
        m = self.meta.get(bid)
        if m is None:
            return False
        m[2] -= 1
        if m[2] == 0:
            self.lru[bid] = None
        return True

    def register(self, parent: int, toks: tuple, bid: int) -> int:
        """Publish ``bid`` for (parent, toks) with one reference held by
        the registering slot; returns the canonical bid (the existing one
        on a concurrent-duplicate insert, in which case the caller's own
        block stays private)."""
        key = (parent, toks)
        cur = self.table.get(key)
        if cur is not None:
            return cur
        self.table[key] = bid
        self.meta[bid] = [key, parent, 1, 0]
        self.children.setdefault(parent, set()).add(bid)
        return bid

    def evict_lru(self) -> List[int]:
        """Evict the coldest refcount-0 block that no waiting request
        wants, plus its cached descendants; returns the FREED block ids
        (empty if every evictable block is wanted, or none is)."""
        passed = False
        for bid in self.lru:
            if not self.meta[bid][3]:
                freed = self._drop(bid)
                if passed:
                    self.spared += len(freed)
                return freed
            passed = True
        return []

    def evict_wanted(self, chains: Sequence[Sequence[list]]) -> List[int]:
        """Every evictable block is wanted: take ONE childless block, the
        leaf end of the first of ``chains`` that has one to give (the
        waiting requests' ``want`` chains, the LAST in the queue first).
        What is left of that chain still hits, and no root cascades. A
        chain whose last resident block is pinned, or has children of
        another chain, gives nothing; if none gives (every evictable
        block sits above a pinned descendant), the coldest goes whole."""
        for chain in chains:
            for m in reversed(chain):
                bid = self.table.get(m[0])
                if bid is None or self.meta[bid] is not m:
                    continue  # evicted while its request waited
                if m[2] == 0 and not self.children.get(bid):
                    return self._drop(bid)
                break
        return self._drop(next(iter(self.lru))) if self.lru else []

    def _drop(self, bid: int) -> List[int]:
        """Unregister ``bid`` and its cached descendants (a reused parent
        id must never re-link a stale child chain); returns the ones with
        no reference, which the caller frees.

        A descendant with refs > 0 is possible: a request that registered
        a novel tail under a chain another request published first shares
        CONTENT with that chain, not block ownership — its own table maps
        private duplicates of the parents, so the parents can hit
        refcount 0 while the child stays pinned. Such a child is
        UNREGISTERED (its key would dangle off a reusable parent id) but
        never freed here — its live slot still maps it and returns it to
        the allocator on release."""
        freed: List[int] = []
        stack = [bid]
        while stack:
            b = stack.pop()
            m = self.meta.pop(b, None)
            if m is None:
                continue
            key, parent, refs, _wanted = m
            self.table.pop(key, None)
            self.children.get(parent, set()).discard(b)
            stack.extend(self.children.pop(b, ()))
            self.lru.pop(b, None)
            if refs == 0:
                freed.append(b)
        return freed


@dataclasses.dataclass
class _ChunkState:
    """Progress of one slot's in-flight chunked prefill: positions
    ``[0, pos)`` of ``tokens`` are KV-resident (cache hits + completed
    chunks); the slot stays OUT of the decode set until pos == plen."""

    req: Request
    tokens: List[int]
    pos: int  # next absolute position to prefill (block-aligned)
    plen: int


def _leave_persistent_compile_cache() -> None:
    """Take THIS process out of jax's persistent compile cache, for good.

    The engine's programs carry non-default array layouts, and an
    executable that comes back from the cache does not keep them: on a
    v5e (jax 0.9.0, libtpu 0.0.34) the cached weight-init program, asked
    for the decode program's layout, returned default-layout arrays, and
    the second start of a 7B replica died in warm-up with "Layout passed
    to jit does not match the layout on the respective arg". A cached
    prefill that silently misread its weights would be worse, so no
    engine program is read from or written to the cache. The flag is
    latched per process at the first compile; reset_cache() re-arms it."""
    from jax.experimental.compilation_cache import compilation_cache

    if jax.config.jax_enable_compilation_cache:
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        logger.info("persistent compile cache off in this process (AUTO-layout programs)")


class LLMEngine:
    """Continuous-batching engine for one model on one chip/mesh."""

    def __init__(
        self,
        params,
        cfg: Union[TransformerConfig, "LatentMoEConfig", "HybridSSMConfig", "KDAMoEConfig"],
        pcfg: Optional[PagedConfig] = None,
        *,
        decode_window: int = 1,
        seed: int = 0,
        metrics_tags: Optional[Dict[str, str]] = None,
        enable_prefix_cache: bool = False,
        prefill_chunk: Optional[int] = None,
        overlap: bool = False,
        warmup_buckets: bool = False,
    ):
        """``params``: the model weights — either an array pytree, or a
        ZERO-ARG CALLABLE returning one. Prefer the callable for big
        models: the engine compiles its decode program first, asks XLA
        which input layout it wants for the weights, and materializes
        them DIRECTLY in that layout (jit with out_shardings) — an
        already-materialized tree must instead be relaid out, transiently
        doubling its HBM footprint (fatal at 7B on a 16 GB chip if the
        caller still holds a reference).

        ``decode_window``: decode steps per device call (one host
        sync per window — see paged_decode_loop). >1 trades per-token
        streaming granularity and up to window-1 wasted steps per
        finishing sequence for amortized dispatch latency; scheduling
        (admission, paging, preemption) happens at window boundaries.

        ``metrics_tags``: {deployment, replica} tags for this engine's
        metric series; defaults to the ambient serve replica context
        (set by the Replica actor) or a standalone placeholder.

        ``enable_prefix_cache``: keep a request's full blocks, prompt and
        answer, resident after release and map them into later requests
        sharing the same prefix (system prompts, few-shot headers, a
        conversation's next turn, preempt-resume), so only the novel
        suffix is prefilled. Eviction of refcount-0 blocks
        under allocation pressure replaces unconditional free: the coldest
        that no waiting request matches (``_PrefixCache`` has the order).

        ``prefill_chunk``: split prompts longer than this many tokens
        into fixed-size chunks interleaved with decode windows, so one
        long admission no longer freezes every active stream (bounds
        TPOT). Rounded up to a block multiple; None/0 = single-shot
        prefill (existing behavior). A fixed chunk is a short LADDER of
        compiled widths (``_chunk_ladder``: the chunk and its halves down
        to ``_WEIGHTS_WIDTH``; 1,024, 512, 256), each an executable
        compiled from shapes while this constructor makes the weights,
        all at once on threads, and run once with no segment before it
        returns: the served path compiles nothing. A chunk call goes at
        the narrowest width whose tiles hold what it carries, a long
        prompt's last chunk included (``_chunk_width``).

        ``overlap``: double-buffer decode — dispatch window N+1 from
        window N's device-resident outputs BEFORE reading N's tokens, so
        the host consumes/schedules while the device keeps stepping. The
        capacity margin per request grows to 2*window-1 (a finishing
        sequence can overshoot into one speculated window).

        ``warmup_buckets``: compile every prefill bucket (and the chunk/
        decode programs) at build time so first live requests don't pay
        compilation on the serving path; wall time lands in
        ``stats["warmup_s"]``."""
        self.cfg = cfg
        self.pcfg = pcfg or PagedConfig()
        p = self.pcfg
        self.window = max(1, int(decode_window))
        self.overlap = bool(overlap)
        if prefill_chunk:
            # Chunks advance the block cursor: round to a block multiple.
            prefill_chunk = -(-int(prefill_chunk) // p.block_size) * p.block_size
            prefill_chunk = min(prefill_chunk, p.max_seq_len)
        self.prefill_chunk = int(prefill_chunk or 0)
        # Pools that hold one row a decode slot (a recurrent layer's state: a
        # state-space layer's, a delta rule's): row ``i`` is slot ``i``'s, so
        # there is nothing to allocate, but the row is not a function of a
        # block of tokens and a step over it is not idempotent. See
        # ``_start_prefill``, ``_chunk_call``, ``_free_slot``.
        self._state_pools = slot_pools(cfg)
        if self._state_pools and enable_prefix_cache:
            raise ValueError(
                f"enable_prefix_cache with a model that keeps state by slot (pools "
                f"{', '.join(self._state_pools)}): the prefix cache shares blocks of tokens, and "
                "a request that skipped a shared prefix would begin from a state that never saw "
                "it. It needs snapshots of the state at block boundaries, which nothing keeps")
        self.prefix_cache = _PrefixCache() if enable_prefix_cache else None
        # A model none of whose pools holds blocks of tokens (every layer keeps
        # its past by slot) is served with no block at all: none allocated,
        # tabled, shipped, counted or preempted for. ``tables`` has no column,
        # admission is by free slot, and ``max_seq_len`` alone bounds a request.
        self._blocks = bool(block_pools(cfg))
        self._table_width = p.max_blocks_per_seq if self._blocks else 0
        # The widths a prompt or a chunk call is padded to: block-multiple
        # powers of two, then the table's whole length. O(log max_seq_len)
        # compilations per program.
        self._widths = [p.block_size]
        while self._widths[-1] * 2 < p.max_seq_len:
            self._widths.append(self._widths[-1] * 2)
        if self._widths[-1] < p.max_seq_len:
            self._widths.append(p.max_seq_len)
        # A fixed chunk's widths, each an executable from build on; without
        # one, chunk calls go at ``_widths``, compiled as they are met.
        self._ladder = _chunk_ladder(self.prefill_chunk, p.block_size) if self.prefill_chunk else []
        _leave_persistent_compile_cache()
        # How many counts the programs return behind their tokens (the first so
        # many of ``_COUNTS``): ``_build_programs`` reads it off the decode program's output.
        self._counted = 0
        self.cache = init_paged_cache(cfg, p)
        # For ``report_state``: the arrays themselves are donated call by call.
        self._pool_facts = {
            name: {"shape": list(pool.shape), "dtype": str(pool.dtype), "bytes": int(pool.nbytes),
                   "unit": "slots" if name in self._state_pools else "blocks"}
            for name, pool in self.cache.items()}
        (self._decode, self._prefill, self._prefill_chunk_fn,
         self.params) = self._build_programs(params)
        self.alloc = _BlockAllocator(p)
        self.key = jax.random.PRNGKey(seed)
        # Slot state. ``tables``, ``lens`` and ``temps`` are the scheduler
        # thread's, on the host: the device is handed copies (``_hand_over``),
        # kept in ``_dev`` and re-uploaded ONLY when the scheduler dirtied
        # them (steady-state decode re-ships nothing: ``lens`` rides the
        # decode program's own output). ``cur``, the token each slot feeds
        # its next decode step, is the device's: made once, below, written
        # only by programs (a prefill's first token, a window's last), and
        # never on the host. An idle row's is whatever was left there; no
        # live row's output depends on it (``paged_decode_loop``).
        self.slots: List[Optional[Request]] = [None] * p.max_batch
        self.slot_blocks: List[List[int]] = [[] for _ in range(p.max_batch)]
        # Bumped on every (re)assignment of a slot: an in-flight window's
        # lane is only harvested if the slot STILL holds the same
        # assignment (a preempted request re-admitted into the same slot
        # would otherwise pass a bare request-identity check and receive
        # the stale speculated window's tokens twice).
        self._slot_gen = [0] * p.max_batch
        self.tables = np.full((p.max_batch, self._table_width), TRASH_BLOCK, np.int32)
        self.lens = np.zeros(p.max_batch, np.int32)
        self.temps = np.zeros(p.max_batch, np.float32)
        self._dev: Dict[str, Optional[jax.Array]] = {
            "tables": None, "lens": None, "temps": None,
            # Fresh and uncommitted, as the cache is: the prefill programs
            # name the placement of both (``_build_programs``).
            "cur": jax.numpy.zeros(p.max_batch, np.int32),
        }
        self._dirty = {"tables", "lens", "temps"}
        # In-flight speculated window: ([(slot, rid, gen), ...], seq device
        # array, number of its program call). Harvested (ONE host sync) at
        # the top of the next step.
        self._inflight: Optional[tuple] = None
        # Slots mid-chunked-prefill (excluded from the decode set);
        # _chunk_rr rotates which slot advances each step.
        self._prefilling: Dict[int, _ChunkState] = {}
        self._chunk_rr = -1
        self.waiting: "collections.deque[Request]" = collections.deque()
        # Prefill first-tokens awaiting ONE batched device→host transfer
        # (per-prefill int() syncs each pay a full link round-trip).
        self._pending_first: List = []
        self._pending_launch = 0  # the newest program call among them
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # Stats for tests/bench.
        self.stats = {"steps": 0, "tokens": 0, "emit_batches": 0, "max_active": 0, "preemptions": 0,
                      "prefills": 0, "admitted": 0, "prompt_tokens": 0,
                      "finished": 0, "prefill_chunks": 0, "spec_windows": 0,
                      "h2d_ships": 0, "h2d_skips": 0, "prefix_hit_tokens": 0,
                      "prefix_lookup_tokens": 0, "prefix_evictions": 0,
                      "prefix_evictions_wanted": 0, "prefix_evictions_spared": 0,
                      "prefix_published_blocks": 0, "prefill_segments": 0,
                      "prefill_tile_queries": 0, "prefill_live_queries": 0,
                      "prefill_width_tokens": 0,
                      "decode_blocks_live": 0, "decode_blocks_table": 0,
                      "windows_behind_prefill": 0, "prefill_flushed_first": 0,
                      "state_slots_live": 0, "state_slots_table": 0,
                      "state_segments_carried": 0, "state_segments_fresh": 0,
                      **{name: 0 for name in _COUNTS},
                      **{f"spec_blocked_{why}": 0 for why in _SPEC_BLOCKED},
                      "starved_us": 0, "unloaded_us": 0,
                      **{f"starved_us_{where}": 0 for where in _STARVED}}
        # Milliseconds by phase of the iteration in progress (tracing.phase).
        self._phase_ms: Dict[str, float] = {}
        # The account of the time the device waits for the host. Every program
        # takes the donated cache of the one before it, so the device runs them
        # in the order of their calls: once the host has read an output of
        # call number ``_launches`` nothing is queued until the next call
        # returns. ``_empty_since`` is that moment (whole microseconds of
        # ``perf_counter_ns``; None while something is queued), ``_where`` the
        # bucket of ``_STARVED`` the thread is in, ``_starved`` the iteration's
        # microseconds by bucket, which ``step`` adds to ``stats`` as it ends:
        # to ``starved_us`` and its bucket if the step found work, else to
        # ``unloaded_us`` (idle for want of load, not the host's doing).
        self._launches = 0
        self._empty_since: Optional[int] = None
        self._where = "between"
        self._starved: Dict[str, int] = {}
        self._idle = True  # the last step found no work
        for width in self._ladder:
            # No segment: every tile on the trash block. A width that does not
            # fit the device fails here and not in a served window.
            jax.block_until_ready(self._chunk_call(width, []))
        if warmup_buckets:
            t0 = time.perf_counter()
            self.stats["warmup_compiles"] = self._warmup()
            self.stats["warmup_s"] = round(time.perf_counter() - t0, 3)
        # Nothing is queued: warm-up waited for its last program.
        self._born_us = self._empty_since = time.perf_counter_ns() // 1000
        # -- telemetry ---------------------------------------------------
        # Flight recorder: bounded rings appended on the scheduler thread.
        self.recorder = FlightRecorder()
        self.engine_id = next(_engine_ids)
        from ray_tpu.serve.metrics import replica_context

        tags = metrics_tags or replica_context() or {
            "deployment": "_standalone", "replica": f"pid{os.getpid()}",
        }
        self.metrics_tags = dict(tags)
        # Registry metrics are flushed at a throttled cadence (not per
        # step, never per token): _maybe_flush_metrics diffs stats
        # against this baseline.
        self._metric_interval_s = 0.25
        self._last_metric_flush = 0.0
        self._flushed_stats: Dict[str, int] = dict(self.stats)
        # Serializes flushes between the pump thread (step cadence) and
        # the reporter thread (force=True): both diff against
        # _flushed_stats, so unsynchronized flushes double-count or drop
        # counter deltas. The throttle check stays outside the lock — the
        # step path normally never contends.
        self._metrics_lock = threading.Lock()
        self._report_interval_s = 1.0
        self._reporter: Optional[threading.Thread] = None
        # Idle suppression: when stats haven't moved since the last full
        # push, the periodic report degrades to a ts-only heartbeat (a
        # fleet of idle replicas must not stream ring snapshots at 1 Hz).
        self._last_pushed_stats: Optional[Dict[str, int]] = None
        self._last_full_push = 0.0

    def _build_programs(self, params):
        """Build the decode window + prefill programs.

        The decode program is AOT-compiled with AUTO input layouts and
        ``params`` is device_put into the layout the program chose: on
        TPU decode matvecs prefer a transposed tiling for the big
        projection stacks, and feeding default-layout params makes XLA
        insert per-call relayout copies (3 GB of HBM temps at 7B — an
        OOM on a 16 GB chip next to the weights). Prefill is then
        compiled to ACCEPT that same layout, so one params tree serves
        both programs copy-free. There is no plain-jit fallback: a
        failure here is the failure to report (at 7B the fallback's own
        program does not fit the chip and would bury it under an OOM).
        The chunk program is jitted too, and compiles at each width it
        meets; with a fixed ``prefill_chunk`` it is instead the executables
        of ``_ladder`` by width, compiled here."""
        cfg, p, window, blocks = self.cfg, self.pcfg, self.window, self._blocks
        bs = p.block_size

        def _decode(params, tokens, cache, tables, lens, temps, key):
            seq, cache = paged_decode_loop(
                params, cfg, tokens, cache, tables, lens, temps, key, window
            )
            # Also return next-window inputs (last sampled tokens, advanced
            # lens) as DEVICE outputs: chained windows and speculative
            # dispatch re-upload nothing from the host. (Rows past the
            # window's, where there are any, are counts: ``_MOE_COUNTS``.)
            # With no table to say which rows are idle, ``lens`` 0 says it, and
            # the chain must leave it 0 (``paged_decode_loop``).
            ahead = lens + window if blocks else jax.numpy.where(lens > 0, lens + window, 0)
            return seq, seq[window - 1], ahead, cache

        # A prefill program also puts what it sampled into the device's
        # ``cur``, at the slot it sampled it for, so the decode window that
        # follows needs nothing from the host (``step``). An index past the
        # last slot is dropped: warm-up, and a segment that does not end its
        # prompt. The slot rides an argument the call had (every host
        # argument is a transfer of its own, paid while the device waits).
        def _prefill(params, tokens, cache, block_row, len_slot, temp, key, cur):
            real_len, slot = len_slot
            tok, cache = prefill_and_sample(
                params, cfg, tokens, cache, block_row, bs, real_len, temp, key
            )
            return tok, cache, cur.at[slot].set(tok, mode="drop")

        def _chunk(params, tokens, cache, table_rows, chunk_row, per_tile, temps, key, cur):
            starts, last_idx, slot_of, live, state_of = per_tile
            toks, cache = prefill_chunk_and_sample(
                params, cfg, tokens, cache, table_rows, chunk_row, bs, starts,
                last_idx, live, state_of, temps, key,
            )
            # (Entries past the tiles', where there are any, are counts.)
            return toks, cache, cur.at[slot_of].set(toks[:slot_of.shape[0]], mode="drop")

        sds = jax.ShapeDtypeStruct
        b, W = p.max_batch, self._table_width
        if callable(params):
            params_s = jax.eval_shape(params)
        else:
            params_s = jax.tree.map(lambda x: sds(x.shape, x.dtype), params)
        cache_s = jax.tree.map(lambda x: sds(x.shape, x.dtype), self.cache)
        args_s = (
            params_s,
            sds((b,), np.int32),
            cache_s,
            sds((b, W), np.int32),
            sds((b,), np.int32),
            sds((b,), np.float32),
            sds((2,), np.uint32),
        )
        auto = jax.tree.map(lambda _: Format(Layout.AUTO), params_s)
        dec = jax.jit(
            _decode, donate_argnums=(2,),
            in_shardings=(auto, None, None, None, None, None, None),
        )
        compiled = dec.lower(*args_s).compile()
        (params_fmt, *_), _kwargs_fmt = compiled.input_formats
        # The counts this model's programs carry behind their tokens.
        self._counted = compiled.out_info[0].shape[0] - window
        # The cache and ``cur`` reach these two from three makers: fresh and
        # uncommitted, the decode program's output (uncommitted: it was lowered
        # from shapes alone) and their own (committed, because ``params_fmt``
        # names a device). Left unspecified, jit lowers AND compiles again for
        # each kind it meets, the second time on the served path (PERF.md: a
        # bucket compiled again on its first live use, PR 24; 14 s of the
        # hybrid decoder's chunk program, PR 45). Named, every kind is one.
        placed = jax.tree.leaves(params_fmt)[0].sharding
        prefill = jax.jit(
            _prefill, donate_argnums=(2,),
            in_shardings=(params_fmt, None, placed) + (None,) * 4 + (placed,),
        )
        chunk = jax.jit(
            _chunk, donate_argnums=(2,),
            in_shardings=(params_fmt, None, placed) + (None,) * 5 + (placed,),
        )

        def chunk_at(width):
            """The chunk program ``width`` wide as an executable, from the
            shapes ``_chunk_call`` builds."""
            n = width // chunk_tile(width, bs)
            return chunk.lower(
                params_s, sds((1, width), np.int32), cache_s, sds((n, W), np.int32),
                sds((width // bs if blocks else 0,), np.int32), sds((5, n), np.int32),
                sds((n,), np.float32), sds((2,), np.uint32), sds((b,), np.int32),
            ).compile()

        # A fixed chunk's widths need nothing of each other or of the weights:
        # each compiles on a thread of its own while this one makes the weights
        # (a chunk program compiles for 15-25 s at 5 B parameters; one after
        # another, or on the served path, each is that much set-up: PERF.md,
        # PR 57). An engine without a fixed chunk starts none.
        with concurrent.futures.ThreadPoolExecutor(len(self._ladder) or 1, "chunk-compile") as pool:
            compiling = {width: pool.submit(chunk_at, width) for width in self._ladder}
            if callable(params):
                # Materialize weights directly in the program's layout —
                # no second copy ever exists on device.
                params = jax.jit(params, out_shardings=params_fmt)()
            else:
                params = jax.device_put(params, params_fmt)
        if self._ladder:
            chunk = {}
            for width, done in compiling.items():
                try:
                    chunk[width] = done.result(timeout=0)  # the pool's exit waited for it
                except Exception as e:
                    raise RuntimeError(
                        f"the chunk program {width} tokens wide did not compile: {e}") from e
        return compiled, prefill, chunk, params

    def _warmup(self) -> int:
        """Compile every program shape the serving path can hit: each
        prefill bucket, the chunk program (every suffix bucket when the
        prefix cache may shorten prompts; a fixed chunk's widths are
        executables already), and the decode window. All warmup writes
        scatter into the trash block, so live cache blocks are untouched.
        Returns the number of program executions (== compilations on a
        cold process)."""
        bs = self.pcfg.block_size
        self.key, sub = jax.random.split(self.key)
        n = 0
        # A model with state by slot has no whole-prompt program to warm.
        for S in ([] if self._state_pools else self._widths):
            _tok, self.cache, self._dev["cur"] = self._prefill(
                self.params, jax.numpy.asarray(np.zeros((1, S), np.int32)),
                self.cache,
                jax.numpy.asarray(np.full(S // bs, TRASH_BLOCK, np.int32)),
                np.asarray([1, self.pcfg.max_batch], np.int32),  # no slot's
                np.float32(0.0), sub, self._dev["cur"],
            )
            n += 1
        # Cache hits, or whole prompts, in bucketed calls (a fixed chunk's widths
        # were compiled and run at build, whatever ``warmup_buckets``).
        bucketed = not self._ladder and (self.prefix_cache is not None or self._state_pools)
        for C in self._widths if bucketed else []:
            self._chunk_call(C, [])  # no segment: every tile on the trash block
            n += 1
        # Decode window: already compiled (AOT) — this is its first
        # execution, so a program that does not fit fails at build time.
        seq, self._dev["cur"], _lens, self.cache = self._decode(
            self.params, self._dev["cur"], self.cache,
            self._hand_over(self.tables), self._hand_over(self.lens),
            self._hand_over(self.temps), sub,
        )
        jax.block_until_ready(seq)
        return n + 1

    # ------------------------------------------------------------------
    # Public API
    # ------------------------------------------------------------------

    def add_request(
        self,
        prompt: Sequence[int],
        max_new_tokens: int,
        *,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
    ) -> Request:
        req = Request(list(prompt), max_new_tokens, temperature, eos_id)
        if not req.prompt:
            req.error = "prompt must be non-empty"
            req.out.put(None)
            return req
        # The decode window may overshoot a finishing sequence by up to
        # window-1 positions — one extra window with overlap, where an
        # eos-stopped slot can ride through a speculated window; capacity
        # must cover the overshoot so those writes stay inside the slot's
        # own blocks.
        overshoot = self.window * (2 if self.overlap else 1) - 1
        total = len(req.prompt) + max_new_tokens + overshoot
        worst_blocks = -(-total // self.pcfg.block_size) if self._blocks else 0
        if total > self.pcfg.max_seq_len or worst_blocks > self.pcfg.usable_blocks:
            req.error = (
                f"prompt({len(req.prompt)}) + max_new_tokens({max_new_tokens}) "
                f"(+ decode_window overshoot {overshoot}) exceeds capacity "
                f"(max_seq_len={self.pcfg.max_seq_len}, "
                f"usable_blocks={self.pcfg.usable_blocks})"
            )
            req.out.put(None)
            return req
        from ray_tpu.util import tracing

        if tracing.tracing_enabled():
            req.trace_ctx = tracing.current_context()
        with self._lock:
            self.waiting.append(req)
        self._wake.set()
        return req

    def start(self):
        """Run the pump loop in a daemon thread."""
        if self._thread is not None:
            return
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                if not self.step():
                    self._wake.wait(timeout=0.05)
                    self._wake.clear()

        self._thread = threading.Thread(target=loop, daemon=True, name="llm-engine")
        self._thread.start()
        # State reporter: pushes the flight-recorder snapshot to the
        # controller off the pump thread, so a slow RPC never stalls
        # decode.
        self._reporter = threading.Thread(
            target=self._report_loop, daemon=True, name="llm-engine-report"
        )
        self._reporter.start()

    def stop(self):
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        if self._reporter is not None:
            self._reporter.join(timeout=2.0)
            self._reporter = None
            self.report_state()  # final snapshot so shutdown state lands

    def generate_batch(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: int,
        *,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
    ) -> List[List[int]]:
        """Synchronous convenience: submit all, pump until done."""
        reqs = [
            self.add_request(p, max_new_tokens, temperature=temperature, eos_id=eos_id)
            for p in prompts
        ]
        if self._thread is None:
            while self.active_count() or self.waiting:
                self.step()
        return [list(r.tokens(timeout=120.0)) for r in reqs]

    def active_count(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    # ------------------------------------------------------------------
    # Scheduler internals
    # ------------------------------------------------------------------

    def _bucket(self, n: int) -> int:
        """Smallest of ``_widths`` >= n (the widest, if none is)."""
        return next((w for w in self._widths if w >= n), self._widths[-1])

    def _free_slot(self, i: int):
        """Give slot ``i`` back (a finish or a preemption). With the prefix
        cache on, what its decode steps filled is published first: the full
        blocks below position ``len(full) - 1`` of the HOST's transcript
        (prompt + emitted tokens). A step writes the position of the token
        it is FED, so the last emitted token's was never written, and all
        that a window writes beyond the transcript (the steps after a cap or
        an eos, a speculated window behind a stopped slot, the unharvested
        window of a slot preempted under ``overlap``) lies at or above it. A
        slot still in ``_prefilling`` publishes nothing: its prompt's blocks
        are not all written yet.

        State kept by slot (``_state_pools``) is left as it is, whatever it
        holds: the steps named above have advanced it past the transcript, a
        preempted prefill left it halfway, and neither matters, because the
        slot's next request begins at position 0 and a segment that does
        starts from nothing (``_chunk_call``), while every window from now
        until that request's prefill ends finds ``lens`` 0 here and leaves
        the row alone. A preempted request is such a next request: its
        ``full_prompt`` is prefilled from position 0 and its state rebuilt,
        never restored."""
        pc = self.prefix_cache
        if pc is None:
            self.alloc.release(self.slot_blocks[i])
        else:
            if i not in self._prefilling:
                full = self.slots[i].full_prompt
                self.stats["prefix_published_blocks"] += self._register_prefix(
                    full, self.slot_blocks[i], len(full) - 1)
            for b in self.slot_blocks[i]:
                # Cache-managed blocks stay RESIDENT (refcount drop, LRU
                # when unreferenced); private blocks go back to the pool.
                if not pc.release(b):
                    self.alloc.release((b,))
        self.slot_blocks[i] = []
        self.slots[i] = None
        self._prefilling.pop(i, None)
        self.tables[i] = TRASH_BLOCK
        self.lens[i] = 0
        self.temps[i] = 0.0
        self._dirty.update(("tables", "lens", "temps"))

    def _alloc_blocks(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` blocks, evicting prefix-cache residents as needed
        (refcount-0 only): the coldest that no waiting request matches,
        and only when all are matched, leaf blocks from the queue's tail
        (``_PrefixCache``). None if even eviction can't cover."""
        if n <= 0:
            return []
        pc = self.prefix_cache
        chains = None  # the queue is read only if the fallback engages
        while (
            self.alloc.available < n and pc is not None and pc.evictable_blocks
        ):
            freed = pc.evict_lru()
            if not freed:
                if chains is None:
                    with self._lock:
                        chains = [r.wanted for r in reversed(self.waiting) if r.wanted]
                freed = pc.evict_wanted(chains)
                self.stats["prefix_evictions_wanted"] += len(freed)
            if not freed:
                break
            self.alloc.release(freed)
            self.stats["prefix_evictions"] += len(freed)
        if pc is not None:
            self.stats["prefix_evictions_spared"] = pc.spared
        return self.alloc.alloc(n)

    def _want(self, req: Request):
        """Mark what ``req`` matches in the prefix cache as wanted, for as
        long as it waits (scheduler thread: the cache is this thread's)."""
        full = req.full_prompt
        req.wanted = self.prefix_cache.want(
            full, self.pcfg.block_size, (len(full) - 1) // self.pcfg.block_size)

    def _finish(self, i: int):
        req = self.slots[i]
        self._free_slot(i)
        req.out.put(None)
        self.stats["finished"] += 1
        now = time.time()
        n = len(req.generated)
        rec = {
            "rid": req.rid,
            "ts": now,
            "prompt_tokens": len(req.prompt),
            "output_tokens": n,
            "queue_ms": (req.prefill_ts - req.submit_ts) * 1000.0
            if req.prefill_ts else None,
            "ttft_ms": (req.first_token_ts - req.submit_ts) * 1000.0
            if req.first_token_ts else None,
            "tpot_ms": (now - req.first_token_ts) * 1000.0 / (n - 1)
            if n > 1 and req.first_token_ts else None,
            "e2e_ms": (now - req.submit_ts) * 1000.0,
        }
        self.recorder.record_request(rec)
        # Parent the engine-side request span under the serve-path trace
        # captured at add_request (cross-thread: explicit parenting).
        tracing.record_span(
            "engine:request", req.submit_ts, now, req.trace_ctx,
            {"rid": req.rid, "prompt_tokens": rec["prompt_tokens"],
             "output_tokens": n},
        )

    def _preempt_one(self) -> bool:
        """Evict the most-recently admitted slot (its prefix is shortest
        to recompute) and requeue it at the front; on resume its whole
        ``full_prompt`` (prompt + generated) is re-prefilled and
        generation continues — already-streamed tokens are not replayed.
        Reference policy: vLLM recompute-preemption."""
        victims = [i for i, s in enumerate(self.slots) if s is not None]
        if not victims:
            return False
        i = max(victims, key=lambda j: self.slots[j].rid)
        req = self.slots[i]
        self._free_slot(i)
        if self.prefix_cache is not None:
            self._want(req)  # the blocks it just released, while it waits
        with self._lock:
            self.waiting.appendleft(req)
        self.stats["preemptions"] += 1
        return True

    def _ensure_decode_blocks(self) -> bool:
        """Every active slot must own the blocks the coming window's
        writes land in (positions lens .. lens+window-1 — the table is
        fixed for the whole device call); allocate on demand, preempting
        if the pool is exhausted. False if a slot is left without: there
        is nobody to preempt, or first tokens are still unread. A victim
        may be the slot one of them belongs to (the youngest goes first),
        and a request preempted before the host has read its first token
        loses it and pays its prefill again: the host reads them first
        (``step``)."""
        if not self._blocks:
            return True  # no pool of blocks: nothing to own
        bs = self.pcfg.block_size
        for i in range(len(self.slots)):
            while self.slots[i] is not None and i not in self._prefilling:
                need_idx = (int(self.lens[i]) + self.window - 1) // bs
                if need_idx < len(self.slot_blocks[i]):
                    break  # this slot's window is covered
                got = self._alloc_blocks(1)
                if got is not None:
                    self.slot_blocks[i].append(got[0])
                    self.tables[i, len(self.slot_blocks[i]) - 1] = got[0]
                    self._dirty.add("tables")
                    continue
                # Pool exhausted: evict the youngest slot (possibly i
                # itself, in which case the outer while sees it freed).
                if self._pending_first or not self._preempt_one():
                    return False  # retry after the flush, or next step
        return True

    def _admit(self):
        """Move waiting requests into free slots while blocks allow; a
        prefix-cache hit maps already-resident blocks into the slot's
        table and only the novel suffix is prefilled."""
        suffixes: List[tuple] = []  # what the hits left to prefill, in queue order
        look = self.prefix_cache is not None
        while True:
            with tracing.phase("engine.admit.plan", self._phase_ms):
                self._at("admit_plan")
                if look:
                    # Once per stay in the queue: arrivals stand at its tail,
                    # behind everything this thread has already looked up.
                    with self._lock:
                        new = list(itertools.takewhile(
                            lambda r: r.wanted is None, reversed(self.waiting)))
                    for req in new:
                        self._want(req)
                    look = False
                placed = self._place_next()
            if placed is None:
                break
            self._start_prefill(*placed, suffixes)
        self._run_suffixes(suffixes)

    def _place_next(self) -> Optional[tuple]:
        """Give the queue's head a free slot and its blocks: ``(slot,
        request, first position to prefill)``, or None when no slot is
        free, nobody waits, or the blocks cannot be had (the request goes
        back to the head of the queue)."""
        bs = self.pcfg.block_size
        i = next((j for j, s in enumerate(self.slots) if s is None), None)
        if i is None:
            return None
        with self._lock:
            if not self.waiting:
                return None
            req = self.waiting.popleft()
        if self.prefix_cache is not None and req.wanted is None:
            self._want(req)  # it arrived after the look at the queue's tail
        full = req.full_prompt
        plen = len(full)
        real_blocks = -(-plen // bs) if self._blocks else 0  # ceil
        hits: List[int] = []
        if self.prefix_cache is not None:
            # Pin hits BEFORE allocating — the allocation may evict
            # refcount-0 residents, which a matched block must not be.
            hits = self.prefix_cache.match(full, bs, (plen - 1) // bs)
            for b in hits:
                self.prefix_cache.incref(b)
        got = self._alloc_blocks(real_blocks - len(hits))
        if got is None:
            for b in hits:
                self.prefix_cache.release(b)
            with self._lock:
                self.waiting.appendleft(req)  # still wanted: it still waits
            return None
        if self.prefix_cache is not None:
            self.prefix_cache.unwant(req.wanted)  # the hits are pinned now
            req.wanted = None
            self.stats["prefix_lookup_tokens"] += plen
            self.stats["prefix_hit_tokens"] += len(hits) * bs
        self.slots[i] = req
        self._slot_gen[i] += 1
        self.slot_blocks[i] = hits + got
        self.stats["admitted"] += 1
        return i, req, len(hits) * bs

    def _start_prefill(self, i: int, req: Request, start: int, suffixes: List[tuple]):
        """Begin prefilling slot ``i`` from absolute position ``start``
        (block-aligned; positions below it are cache hits). A prompt with
        no hit runs its full-attention prefill now; a suffix after a hit
        joins ``suffixes``, which ``_admit`` sends as packed chunk calls
        when its loop ends; prompts longer than ``prefill_chunk`` enter the
        chunked queue and advance one chunk per step."""
        full = req.full_prompt
        plen = len(full)
        if req.prefill_ts is None:  # first admission (not a resume)
            req.prefill_ts = time.time()
        self.stats["prefills"] += 1
        self.stats["prompt_tokens"] += plen - start
        if self.prefill_chunk and plen - start > self.prefill_chunk:
            self._prefilling[i] = _ChunkState(req, full, start, plen)
        elif start == 0 and not self._state_pools:
            self._finish_prefill(i, req, self._run_full_prefill(i, req, full), ())
        else:
            # A suffix behind a hit; or, for a model that keeps state by slot,
            # a whole prompt: tiles of the chunk program, which is told each
            # tile's slot and real tokens (a padded bucket would run its
            # padding through the state).
            suffixes.append((i, req, full, start, plen))

    def _chunk_width(self, lens: Sequence[int]) -> Optional[int]:
        """The narrowest chunk call that holds segments of ``lens`` tokens,
        each padded to that width's own tiles: a width of the fixed chunk's
        ladder when one is set (1,024, 512 or 256: a call that carries one
        short prompt does not run 1,024 places of matrices), else one of
        ``_widths``. None if they do not fit the widest."""
        bs = self.pcfg.block_size
        for width in self._ladder or self._widths:
            tile = chunk_tile(width, bs)
            if sum(-(-n // tile) * tile for n in lens) <= width:
                return width
        return None

    def _run_suffixes(self, suffixes: List[tuple]):
        """Send the suffixes one admission loop left as chunk calls, packed
        in queue order. The next suffix joins the call being filled unless
        the joined call would be wider than the two apart: together they do
        not fit the widest, or the next width up is mostly padding (a whole
        conversation re-prefilled 1,024 wide is better alone). A call counts
        as no narrower than ``_WEIGHTS_WIDTH``, so narrow ones always join."""
        def cost(width):
            return max(width, _WEIGHTS_WIDTH)

        group: List[tuple] = []
        lens: List[int] = []
        width = 0  # of the call that holds ``group``
        for seg in suffixes:
            *_, start, end = seg
            alone = self._chunk_width([end - start])
            joined = self._chunk_width(lens + [end - start])
            if group and (joined is None or cost(joined) > cost(width) + cost(alone)):
                self._run_chunks(width, group)
                group, lens, joined = [], [], alone
            group.append(seg)
            lens.append(end - start)
            width = joined
        if group:
            self._run_chunks(width, group)

    def _advance_chunked_prefills(self):
        """ONE chunk of forward progress per step, round-robin across
        mid-prefill slots — the per-window decode stall is bounded by a
        single chunk's latency no matter how many long admissions are in
        flight (a per-slot advance would serialize N chunk programs in
        front of every window)."""
        if not self._prefilling:
            return
        order = sorted(self._prefilling)
        i = next((j for j in order if j > self._chunk_rr), order[0])
        self._chunk_rr = i
        st = self._prefilling[i]
        start, st.pos = st.pos, min(st.pos + self.prefill_chunk, st.plen)
        # A prompt's last chunk may be short: the narrowest width that holds it.
        self._run_chunks(self._chunk_width([st.pos - start]), [(i, st.req, st.tokens, start, st.pos)])

    def _run_full_prefill(self, i: int, req: Request, full: List[int]):
        """Whole-prompt full-attention prefill (bucketed); returns the
        first sampled token as a DEVICE scalar."""
        bs = self.pcfg.block_size
        ph = self._phase_ms
        with tracing.phase("engine.admit.build", ph):
            self._at("admit_build")
            plen = len(full)
            S = self._bucket(plen)
            toks = np.zeros((1, S), np.int32)
            toks[0, :plen] = full
            # Block row covers the padded bucket; entries past the real
            # prompt scatter into the trash block.
            row = np.full(S // bs, TRASH_BLOCK, np.int32)
            nreal = -(-plen // bs)
            row[:nreal] = self.slot_blocks[i]
        sub = self._split_key("admit")
        with tracing.phase("engine.admit.launch", ph):
            self._at("admit_launch")
            tok, self.cache, self._dev["cur"] = self._prefill(
                self.params, jax.numpy.asarray(toks), self.cache,
                jax.numpy.asarray(row),
                np.asarray([plen, i], np.int32), np.float32(req.temperature), sub,
                self._dev["cur"],
            )
            self._launched()
        return tok

    def _run_chunks(self, width: int, segs: List[tuple]):
        """ONE chunk-program call ``width`` wide over ``segs``, each ``(slot,
        request, tokens, start, end)``: positions ``start .. end-1`` of that
        slot. A segment that ends its prompt leaves the chunked queue and
        finishes its prefill with the token sampled for it."""
        toks = self._chunk_call(width, segs)
        self.stats["prefill_chunks"] += 1
        self.stats["prefill_segments"] += len(segs)
        # Over ``prefill_chunks`` the mean call's width; ``prefill_tile_queries``
        # over it the share of a call's places that segments took.
        self.stats["prefill_width_tokens"] += width
        for k, (i, req, full, _start, end) in enumerate(segs):
            if end == len(full):
                self._prefilling.pop(i, None)
                self._finish_prefill(i, req, toks, k)

    def _chunk_call(self, width: int, segs: List[tuple]):
        """Build the chunk program's inputs for ``segs`` on a token axis
        ``width`` wide and call it: the one place that does. The axis is
        ``width // tile`` tiles; a segment takes whole tiles, one after
        another (each attends through its slot's table, from its own
        absolute position, and the slot's blocks under it receive its
        tokens; a padded tail past the slot's blocks lands on the trash
        block, as every tile no segment uses does). Returns the sampled
        tokens ``[tiles]`` on the device, segment ``k``'s at ``k``."""
        p = self.pcfg
        bs = p.block_size
        ph = self._phase_ms
        with tracing.phase("engine.admit.build", ph):
            self._at("admit_build")
            tile = chunk_tile(width, bs)
            n = width // tile
            toks = np.zeros((1, width), np.int32)
            trows = np.full((n, self._table_width), TRASH_BLOCK, np.int32)
            crow = np.full(width // bs if self._blocks else 0, TRASH_BLOCK, np.int32)
            # By tile: its first absolute position; by segment k: the axis
            # position of its last token, and whose ``cur`` its sampled token
            # is (no slot's, unless the segment ends its prompt); by tile
            # again: how many of its tokens are real (a segment's last tile
            # holds its remainder, a tile no segment uses none), and whose
            # state-by-slot it reads and leaves (its segment's slot; nobody's
            # for a tile no segment uses: a model without such state ignores
            # the row). One array: every host argument is a transfer of its own.
            per_tile = np.zeros((5, n), np.int32)
            starts, last_idx, slot_of, live, state_of = per_tile
            slot_of[:] = state_of[:] = p.max_batch
            temps = np.zeros(n, np.float32)
            at = 0  # the next free tile's first position on the axis
            for k, (i, req, full, start, end) in enumerate(segs):
                blocks = self.slot_blocks[i]
                tiles = -(-(end - start) // tile)
                t0 = at // tile
                toks[0, at:at + end - start] = full[start:end]
                trows[t0:t0 + tiles, :len(blocks)] = blocks
                starts[t0:t0 + tiles] = start + tile * np.arange(tiles)
                live[t0:t0 + tiles] = np.minimum(tile, end - starts[t0:t0 + tiles])
                state_of[t0:t0 + tiles] = i
                under = blocks[start // bs:start // bs + tiles * tile // bs]
                crow[at // bs:at // bs + len(under)] = under
                last_idx[k] = at + end - start - 1
                temps[k] = req.temperature
                if end == len(full):
                    slot_of[k] = i
                at += tiles * tile
            # Queries of the tiles the segments took, and the real ones among
            # them: their ratio is the share of a taken tile that is real.
            self.stats["prefill_tile_queries"] += at
            self.stats["prefill_live_queries"] += int(live.sum())
            if self._state_pools:
                # Segments that took up the slot's stored state (a later chunk
                # of a long prompt), and those that began from nothing.
                carried = sum(1 for seg in segs if seg[3] > 0)
                self.stats["state_segments_carried"] += carried
                self.stats["state_segments_fresh"] += len(segs) - carried
        sub = self._split_key("admit")
        with tracing.phase("engine.admit.launch", ph):
            self._at("admit_launch")
            # Built per call and never written again, so the program may read
            # them where they lie: only the slot mirrors, which the scheduler
            # mutates in place, need _hand_over's copy.
            program = self._prefill_chunk_fn
            if self._ladder:
                program = program[width]  # an executable: it cannot compile, a mismatch raises
            out, self.cache, self._dev["cur"] = program(
                self.params, toks, self.cache, trows, crow, per_tile, temps, sub,
                self._dev["cur"])
            self._launched()
        return out

    def _finish_prefill(self, i: int, req: Request, toks, k):
        """Prompt fully KV-resident: publish the slot to the decode set
        (tables/lens/temps become decode-visible, and dirty; the program
        that sampled the first token has put it into the device's ``cur``
        already) and queue that token, ``toks[k]`` of the program's device
        output (``k`` is ``()`` for a scalar), for the batched flush, which
        comes after the window's dispatch: the host reads it to emit it,
        the window does not wait for the host to."""
        full = req.full_prompt
        blocks = self.slot_blocks[i]
        self.tables[i] = TRASH_BLOCK
        self.tables[i, : len(blocks)] = blocks
        self.lens[i] = len(full)
        self.temps[i] = req.temperature
        self._dirty.update(("tables", "lens", "temps"))
        if self.prefix_cache is not None:
            self._register_prefix(full, blocks, len(full))
        # Defer the device→host read: prefill dispatches pipeline without
        # syncing; _flush_prefills fetches every pending first token in
        # one transfer, behind the decode window's dispatch (``step``).
        self._pending_first.append((i, req, toks, k))
        self._pending_launch = self._launches  # the call that made ``toks``

    def _register_prefix(self, full: List[int], blocks: List[int], written: int) -> int:
        """Publish the slot's FULL blocks below position ``written`` into
        the prefix index; returns how many were not in it yet. The caller
        says how far ``full``'s keys and values are certainly in place: the
        prompt's length as a prefill ends (the trailing partial block still
        receives decode writes and is never shared), the transcript's bound
        as the slot is given back (``_free_slot``). Already-cached chain
        links keep their canonical block id as the parent for the next key."""
        bs = self.pcfg.block_size
        pc = self.prefix_cache
        parent = _PrefixCache.ROOT
        new = 0
        for j in range(written // bs):
            toks = tuple(full[j * bs:(j + 1) * bs])
            cur = pc.table.get((parent, toks))
            if cur is not None:
                parent = cur  # a hit we mapped, or a concurrent duplicate
                continue
            parent = pc.register(parent, toks, blocks[j])
            new += 1
        return new

    def _flush_prefills(self):
        """Read the first tokens the iteration's prefill programs sampled (ONE
        transfer: the host blocks until the last of them has run) and emit
        them. ``step`` calls this AFTER it has dispatched the decode window,
        which took those tokens from the device's ``cur``: the host reads
        them only to emit them. Only when that dispatch has to preempt,
        and the victim may own one of them, does the flush come first
        (``_ensure_decode_blocks``)."""
        if not self._pending_first:
            return
        pend, self._pending_first = self._pending_first, []
        with tracing.phase("engine.prefill_wait", self._phase_ms):
            # One batched transfer; a call's segments share its one array.
            outs = {id(t): t for _, _, t, _ in pend}
            vals = dict(zip(outs, jax.device_get(list(outs.values()))))
        self._drained(self._pending_launch)
        if self._counted:
            for v in vals.values():
                if v.ndim:  # a chunk call's; a whole-prompt program's is one token
                    self._count(v[-self._counted:])
        with tracing.phase("engine.emit", self._phase_ms):
            self._at("emit")
            for i, req, t, k in pend:
                if self.slots[i] is not req:
                    continue  # preempted between prefill and flush
                self._emit(i, [int(vals[id(t)][k])])
        self._at("between")

    def _count(self, counts):
        """Add the counts one program call returned behind its tokens."""
        for name, n in zip(_COUNTS, counts):
            self.stats[name] += int(n)

    def _emit(self, i: int, toks: List[int]):
        """Record + stream the tokens one program call gave slot ``i``, as
        ONE queue entry (one wake-up of the request's reader): those up to
        the request's end (its eos, or its last token: the rest of a window
        is overshoot); retire the slot when done. Histograms/gauges flush at
        step cadence, not here."""
        req = self.slots[i]
        if req.first_token_ts is None:
            req.first_token_ts = time.time()
        toks = toks[:req.remaining]
        if req.eos_id is not None and req.eos_id in toks:
            toks = toks[:toks.index(req.eos_id) + 1]
        req.generated.extend(toks)
        req.out.put(toks)
        self.stats["tokens"] += len(toks)
        self.stats["emit_batches"] += 1
        if req.remaining <= 0 or (req.eos_id is not None and toks[-1] == req.eos_id):
            self._finish(i)

    @staticmethod
    def _hand_over(host: np.ndarray) -> jax.Array:
        """The one place where a host mirror becomes a device array: from
        a COPY that nobody writes again. The scheduler mutates its mirrors
        in place between dispatches (``_dispatch_window``, the harvest,
        ``_free_slot``) while the program they were handed to runs
        asynchronously, and a backend may alias the numpy buffer (the CPU
        client does, on a 64-byte boundary) or read it after the call
        returned. The copy is made on the host: ``jax.numpy.array(host,
        copy=True)`` reads ``host`` from a device program of its own
        (``convert_element_type``), as late as any other."""
        return jax.numpy.asarray(host.copy())

    def _ship(self) -> Dict[str, jax.Array]:
        """Device-resident decode inputs, re-uploading ONLY the mirrors the
        scheduler dirtied since the last dispatch, each as a copy
        (``_hand_over``); ``cur`` is the device's own and is never among
        them."""
        for name, host in (("tables", self.tables), ("lens", self.lens),
                           ("temps", self.temps)):
            # (A table with no column is handed over once, empty: nothing of
            # it can be out of date.)
            if self._dev[name] is None or (name in self._dirty and host.size):
                self._dev[name] = self._hand_over(host)
                self._dirty.discard(name)
                self.stats["h2d_ships"] += 1
            else:
                self.stats["h2d_skips"] += 1
        return self._dev

    def _decode_entries(self) -> List[tuple]:
        """(slot, rid, slot_gen) for every decodable slot — occupied and
        not mid-chunked-prefill. rid + generation let a harvest detect a
        slot that was freed/reused (even by the SAME re-admitted request)
        while its window was in flight."""
        return [(i, s.rid, self._slot_gen[i]) for i, s in enumerate(self.slots)
                if s is not None and i not in self._prefilling]

    def _dispatch_window(self) -> bool:
        """Dispatch ONE decode window over the decodable slots without
        reading it back: outputs (sampled tokens, advanced lens) stay on
        device and feed the next window directly. The host advances the
        ``lens`` of the rows it dispatched and no other: an idle or
        still-prefilling row stays at the 0 ``_free_slot`` left it at. The
        device's own ``lens`` output advances EVERY row; what an idle row
        holds there is ``paged_decode_loop``'s to define (it restarts a
        row whose table starts on the trash block).

        Speculated (a window still in flight) or not, the call is the same,
        and its ``_ensure_decode_blocks`` may preempt under that window:
        the host's ``lens`` were advanced when it was dispatched, so a ship
        of the three mirrors is current without its tokens, and ``cur`` is
        that window's own output, which no ship replaces. False if nothing
        is left to decode, or a slot is left without blocks."""
        ph = self._phase_ms
        with tracing.phase("engine.dispatch.blocks", ph):
            self._at("dispatch_blocks")
            entries = self._ensure_decode_blocks() and self._decode_entries()
            if not entries:
                return False
            self.stats["max_active"] = max(self.stats["max_active"], len(entries))
            # Blocks the occupied slots' tokens lie in as the window starts,
            # against the table the plain form of decode attention gathers whole.
            occupied = [i for i, _rid, _gen in entries]
            if self._blocks:
                self.stats["decode_blocks_live"] += int(
                    (self.lens[occupied] // self.pcfg.block_size + 1).sum())
                self.stats["decode_blocks_table"] += (
                    self.pcfg.max_batch * self.pcfg.max_blocks_per_seq)
            if self._state_pools:
                # Rows of state the window's steps read and write, of those held.
                self.stats["state_slots_live"] += len(entries)
                self.stats["state_slots_table"] += self.pcfg.max_batch
        sub = self._split_key("dispatch")
        with tracing.phase("engine.dispatch.ship", ph):
            self._at("dispatch_ship")
            args = self._ship()
        with tracing.phase("engine.dispatch.launch", ph):
            self._at("dispatch_launch")
            seq, cur_out, lens_out, self.cache = self._decode(
                self.params, args["cur"], self.cache,
                args["tables"], args["lens"], args["temps"], sub,
            )
            self._launched()
        self._dev["cur"] = cur_out
        self._dev["lens"] = lens_out
        self.lens[occupied] += self.window
        self.stats["steps"] += 1
        # Queued behind this iteration's prefill programs, their tokens unread.
        self.stats["windows_behind_prefill"] += bool(self._pending_first)
        self._inflight = (entries, seq, self._launches)
        return True

    def _dispatch(self) -> bool:
        """``_dispatch_window`` as the iteration's ``engine.dispatch`` phase."""
        with tracing.phase("engine.dispatch", self._phase_ms):
            dispatched = self._dispatch_window()
        self._at("between")
        return dispatched

    def _harvest(self) -> bool:
        if self._inflight is None:
            return False
        pending, self._inflight = self._inflight, None
        return self._harvest_window(pending)

    def _harvest_window(self, pending: tuple) -> bool:
        """Read one dispatched window's tokens (ONE host sync) and emit
        them. Slots freed/reused since dispatch fail the rid check and
        their lanes are discarded (overshoot)."""
        entries, seq, launch = pending
        with tracing.phase("engine.harvest_wait", self._phase_ms):
            nxt = np.asarray(seq)  # [window, b]: the host blocks on the device
        self._drained(launch)  # not behind a speculated window: that is newer
        if self._counted:
            self._count(nxt[self.window:, 0])
        with tracing.phase("engine.emit", self._phase_ms):
            self._at("emit")
            for i, rid, gen in entries:
                req = self.slots[i]
                if req is None or req.rid != rid or self._slot_gen[i] != gen:
                    continue  # finished / preempted / slot reused in flight
                self._emit(i, nxt[:self.window, i].tolist())
        self._at("between")
        return True

    def _can_speculate(self) -> Optional[str]:
        """Dispatch window N+1 before reading window N's tokens? None to
        go ahead, else the reason not to (one of ``_SPEC_BLOCKED``):
        ``idle``, nothing decodable; ``admission``, a waiting request
        could use a free slot first (it should join N+1, not N+2);
        ``finishing``, a slot's cap-finish inside N is already certain
        (the speculated window would be pure waste). An eos-stopped slot
        can still waste one window — capacity covers it (the 2*window-1
        overlap margin). A slot given back under N is no reason: N+1 takes
        its tokens from N's own output on the device."""
        entries = self._decode_entries()
        if not entries:
            return "idle"
        if self.waiting and any(s is None for s in self.slots):
            return "admission"
        if any(self.slots[i].remaining <= self.window for i, _, _ in entries):
            return "finishing"
        return None

    # -- the starvation account: integer adds at phase boundaries and launches --

    def _at(self, where: str):
        """The scheduler thread enters ``where`` (one of ``_STARVED``): the
        microseconds the device has had nothing queued since the last
        boundary go to the bucket the thread leaves."""
        since = self._empty_since
        if since is not None:
            now = time.perf_counter_ns() // 1000
            self._starved[self._where] = self._starved.get(self._where, 0) + now - since
            self._empty_since = now
        self._where = where

    def _launched(self):
        """A program call has RETURNED: its own host time was part of what
        the device waited for, and the wait ends here."""
        self._launches += 1
        self._at(self._where)
        self._empty_since = None

    def _drained(self, launch: int):
        """The host has read an output of program call number ``launch``:
        if that is the newest, nothing is queued from now on."""
        if launch == self._launches:
            self._empty_since = time.perf_counter_ns() // 1000

    def _split_key(self, part: str):
        """The next sampling key, as the ``key`` part of ``admit`` or
        ``dispatch``: device programs of its own, which end no starvation."""
        with tracing.phase("engine." + part + ".key", self._phase_ms):
            self._at(part + "_key")
            self.key, sub = jax.random.split(self.key)
        return sub

    def _settle(self, unloaded: bool):
        """Add the microseconds gathered since the last call to ``stats``:
        as the host's doing, by bucket, or as idle for want of load."""
        if unloaded:
            self.stats["unloaded_us"] += sum(self._starved.values())
        else:
            for where, us in self._starved.items():
                self.stats["starved_us"] += us
                self.stats["starved_us_" + where] += us
        self._starved.clear()

    def step(self) -> bool:
        """One scheduler iteration: [speculate] → harvest → admit → page
        → decode → flush. Returns True if any device work ran (False = idle).

        A step that launched a prefill dispatches its decode window BEFORE
        it reads the prefill's tokens: the programs that sampled them put
        them into the device's ``cur``, so the window queues behind the
        prefill and everything the dispatch costs the host (blocks, key,
        ship, launch) runs while the device prefills, not on a device that
        the read of the first tokens has just drained. The flush follows,
        ahead of any harvest of that window, so a request's first token is
        emitted before its window's. A request that ends AT its first token
        then has a lane in a window already in flight: the harvest's rid and
        generation check discards it, and its blocks are freed under that
        window as a speculated window's are (below). The one case in which
        the read comes first: the dispatch's own ``_ensure_decode_blocks``
        has to preempt. The victim, the youngest slot, may be the very one
        that owns an unread first token, which would be dropped and its
        prefill paid again; so the dispatch gives up, the flush comes
        first, and the preemption and the dispatch after it
        (``prefill_flushed_first``).

        With ``overlap`` the device is double-buffered: window N+1 is
        dispatched from N's device-resident outputs BEFORE N's tokens are
        read, so token emission, admission, paging and prefill dispatch
        all run while the device executes N+1 (the donated-cache chain
        serializes device-side writes, so a freed block re-used by a
        later prefill is always overwritten AFTER the stale window's
        writes land).

        The iteration runs as the six sibling ``tracing.phase``s of
        ``_PHASES`` (here and in ``_harvest_window``/``_flush_prefills``),
        none inside another and none around them all, with the parts of
        ``admit`` and ``dispatch`` (``_SUB_PHASES``) nested in those two; the
        step record carries their milliseconds, and how many of them the
        device had nothing queued (``starved_ms``; the time BETWEEN two
        steps is no step's and goes to ``stats`` alone)."""
        self._at("between")
        self._settle(self._idle)  # the turn of the loop, on the last step's verdict
        before = {k: self.stats[k] for k in _STEP_COUNTS}
        t_step = time.perf_counter()
        ph = self._phase_ms = {}
        worked = False
        overlapped = 0
        if self._inflight is not None:
            # Stash window N first: a speculated dispatch installs N+1 as
            # the new in-flight window, and N still owes its tokens.
            pending, self._inflight = self._inflight, None
            if self.overlap:
                # Exactly one of spec_windows and the spec_blocked_* counts
                # goes up for every window found in flight.
                blocked = self._can_speculate()
                if blocked is None and not self._dispatch():
                    blocked = "idle"  # its preemptions left nothing decodable
                if blocked is None:
                    self.stats["spec_windows"] += 1
                    overlapped = 1
                else:
                    self.stats["spec_blocked_" + blocked] += 1
            self._harvest_window(pending)
            worked = True
        with tracing.phase("engine.admit", ph):
            self._admit()
            self._advance_chunked_prefills()
        self._at("between")
        dispatched = False
        if self._inflight is None:
            dispatched = self._dispatch()
            if not dispatched and self._pending_first:
                self._flush_prefills()  # read first, then preempt and ship
                dispatched = self._dispatch()
                self.stats["prefill_flushed_first"] += dispatched
        self._flush_prefills()
        if dispatched:
            worked = True
            if not self.overlap:
                self._harvest()  # classic synchronous window
        moved = {k: self.stats[k] - before[k] for k in _STEP_COUNTS}
        # Record even decode-less iterations that did work — e.g. a
        # max_new_tokens=1 request finishes entirely inside the prefill
        # flush and must still appear in the step ring.
        worked = worked or any(moved.values())
        if worked:
            with tracing.phase("engine.record", ph):
                self._at("record")
                pc = self.prefix_cache
                rec = {
                    "ts": time.time(),
                    "active": self.active_count(),
                    "waiting": len(self.waiting),
                    "kv_blocks_free": self.alloc.available,
                    "kv_utilization": 1.0 - self.alloc.available
                    / max(1, self.pcfg.usable_blocks),
                    "tokens": moved["tokens"],
                    "prefills": moved["prefills"],
                    "preemptions": moved["preemptions"],
                    "admitted": moved["admitted"],
                    "chunks": moved["prefill_chunks"],  # chunk-program calls, and the
                    "segments": moved["prefill_segments"],  # suffixes or chunks in them
                    "chunk_width": moved["prefill_width_tokens"],  # the calls' widths, summed
                    "prefix_hit_tokens": moved["prefix_hit_tokens"],
                    "cached_blocks": pc.resident_blocks if pc else 0,
                    "overlapped": overlapped,
                    "behind_prefill": moved["windows_behind_prefill"],
                    # Rows of state by slot the window this step dispatched moves.
                    "state_slots_live": moved["state_slots_live"],
                }
                self._maybe_flush_metrics()
        self._at("between")
        if worked:
            st = self._starved
            # Host cost of this iteration, whole and by phase (the six add up
            # to it); the scheduler's own share is wall less the two waits,
            # and ``starved_ms`` the part of that with nothing queued.
            rec.update({
                "wall_ms": (time.perf_counter() - t_step) * 1e3,
                **{field: ph.get(span, 0.0) for field, span in _STEP_MS},
                "starved_ms": sum(st.values()) / 1e3,
                "starved_admit_ms": sum(
                    us for where, us in st.items() if where.startswith("admit_")) / 1e3,
                "starved_dispatch_ms": sum(
                    us for where, us in st.items() if where.startswith("dispatch_")) / 1e3,
            })
            self.recorder.record_step(rec)
        self._settle(unloaded=not worked)
        self._idle = not worked
        return worked

    # ------------------------------------------------------------------
    # Telemetry: registry metrics + controller state reports
    # ------------------------------------------------------------------

    def _maybe_flush_metrics(self, force: bool = False):
        """Push stats deltas into the metric registry at a throttled
        cadence — one batch of Counter/Gauge updates every
        ``_metric_interval_s``, never per token."""
        now = time.monotonic()
        if not force and now - self._last_metric_flush < self._metric_interval_s:
            return
        from ray_tpu.serve.metrics import serve_metrics

        with self._metrics_lock:
            if not force and (
                time.monotonic() - self._last_metric_flush < self._metric_interval_s
            ):
                return  # another thread flushed while we waited
            self._last_metric_flush = time.monotonic()
            m = serve_metrics()
            t = self.metrics_tags
            s = dict(self.stats)
            prev = self._flushed_stats
            for key, counter in (
                ("steps", m.engine_steps),
                ("tokens", m.engine_tokens),
                ("prompt_tokens", m.engine_prompt_tokens),
                ("prefills", m.engine_prefills),
                ("preemptions", m.engine_preemptions),
                ("prefill_chunks", m.engine_prefill_chunks),
                ("prefill_segments", m.engine_prefill_segments),
                ("prefill_tile_queries", m.engine_prefill_tile_queries),
                ("prefill_live_queries", m.engine_prefill_live_queries),
                ("prefix_published_blocks", m.engine_prefix_published_blocks),
                ("spec_windows", m.engine_overlap_windows),
                ("windows_behind_prefill", m.engine_windows_behind_prefill),
                ("prefill_flushed_first", m.engine_prefill_flushed_first),
                ("prefix_hit_tokens", m.engine_prefix_hit_tokens),
                ("prefix_lookup_tokens", m.engine_prefix_lookup_tokens),
                ("moe_pairs_here", m.engine_moe_pairs_here),
                ("moe_experts_touched", m.engine_moe_experts_touched),
                ("moe_layer_steps", m.engine_moe_layer_steps),
                ("moe_fused_layer_steps", m.engine_moe_fused_layer_steps),
                ("moe_grouped_layer_steps", m.engine_moe_grouped_layer_steps),
                ("emit_batches", m.engine_emit_batches),
                ("state_slots_live", m.engine_state_slots_live),
                ("state_slots_table", m.engine_state_slots_table),
                ("state_segments_carried", m.engine_state_segments_carried),
                ("state_segments_fresh", m.engine_state_segments_fresh),
            ):
                delta = s[key] - prev.get(key, 0)
                if delta:
                    counter.inc(delta, t)
            # Evicted blocks by how the victim was chosen: "lru" is what is
            # neither of the two counted apart (three values, a closed set).
            evicted, wanted, spared = (
                s[key] - prev.get(key, 0) for key in (
                    "prefix_evictions", "prefix_evictions_wanted", "prefix_evictions_spared"))
            for choice, delta in (("wanted", wanted), ("spared", spared),
                                  ("lru", evicted - wanted - spared)):
                if delta:
                    m.engine_prefix_evictions.inc(  # ray-tpu: lint-ignore[RTL004]
                        delta, {**t, "choice": choice})
            for why in _SPEC_BLOCKED:
                key = "spec_blocked_" + why
                delta = s[key] - prev.get(key, 0)
                if delta:  # four fixed reasons, not an open set of series
                    m.engine_overlap_blocked.inc(  # ray-tpu: lint-ignore[RTL004]
                        delta, {**t, "reason": why})
            for where in _STARVED:
                key = "starved_us_" + where
                delta = s[key] - prev.get(key, 0)
                if delta:  # eleven fixed places
                    m.engine_device_starved.inc(  # ray-tpu: lint-ignore[RTL004]
                        delta / 1e6, {**t, "where": where})
            self._flushed_stats = s
            m.engine_active.set(self.active_count(), t)
            m.engine_waiting.set(len(self.waiting), t)
            m.engine_kv_free.set(self.alloc.available, t)
            m.engine_kv_util.set(
                1.0 - self.alloc.available / max(1, self.pcfg.usable_blocks), t
            )
            pc = self.prefix_cache
            m.engine_cached_blocks.set(pc.resident_blocks if pc else 0, t)

    def _report_loop(self):
        while not self._stop.wait(self._report_interval_s):
            try:
                self.report_state()
            except Exception as e:  # noqa: BLE001 — telemetry must not kill serving
                logger.debug("engine state report failed: %s", e)

    def report_state(self) -> dict:
        """Snapshot occupancy + flight recorder and (best-effort) push it
        to the controller's serve-state table, which backs the
        ``/api/serve/engine`` endpoint and ``state.summarize_serve()``."""
        self._maybe_flush_metrics(force=True)
        snap = self.recorder.snapshot()
        # The push is a periodic heartbeat — ship the tail of the rings,
        # not all 256 records, to keep the RPC small.
        snap["steps"] = snap["steps"][-32:]
        snap["recent_requests"] = snap["recent_requests"][-64:]
        snap.update(
            ts=time.time(),
            engine_id=self.engine_id,
            tags=dict(self.metrics_tags),
            stats=dict(self.stats),
            occupancy={
                "active": self.active_count(),
                "waiting": len(self.waiting),
                "kv_blocks_free": self.alloc.available,
                "kv_blocks_total": self.pcfg.usable_blocks,
                "max_batch": self.pcfg.max_batch,
            },
            prefix_cache={
                "enabled": self.prefix_cache is not None,
                "resident_blocks": self.prefix_cache.resident_blocks
                if self.prefix_cache else 0,
                "evictable_blocks": self.prefix_cache.evictable_blocks
                if self.prefix_cache else 0,
                "hit_tokens": self.stats["prefix_hit_tokens"],
                "lookup_tokens": self.stats["prefix_lookup_tokens"],
                "hit_rate": self.stats["prefix_hit_tokens"]
                / max(1, self.stats["prefix_lookup_tokens"]),
                "evictions": self.stats["prefix_evictions"],
                # Of those: blocks taken from a waiting request's chain (every
                # evictable block was wanted), and blocks evicted in place of
                # a colder chain that a waiting request matched.
                "evictions_wanted": self.stats["prefix_evictions_wanted"],
                "evictions_spared": self.stats["prefix_evictions_spared"],
                # Blocks that entered the index as their slot was given back
                # (an answer's, a preempted request's), not at a prefill's end.
                "published_blocks": self.stats["prefix_published_blocks"],
            },
            # Chunk-program calls: the segments in them, the calls' widths
            # summed, the queries of the tiles the segments took and the real
            # ones among them (a segment is padded to whole tiles; a model may
            # skip the padding).
            prefill={
                "chunks": self.stats["prefill_chunks"],
                "segments": self.stats["prefill_segments"],
                "width_tokens": self.stats["prefill_width_tokens"],
                "tile_queries": self.stats["prefill_tile_queries"],
                "live_queries": self.stats["prefill_live_queries"],
                "live_query_pct": 100.0 * self.stats["prefill_live_queries"]
                / max(1, self.stats["prefill_tile_queries"]),
            },
            # An expert model's own counts (all 0 for a dense one): pairs the
            # held experts computed, and held experts touched a counted layer.
            moe={
                "pairs_here": self.stats["moe_pairs_here"],
                "experts_touched": self.stats["moe_experts_touched"],
                "layer_steps": self.stats["moe_layer_steps"],
                "fused_layer_steps": self.stats["moe_fused_layer_steps"],
                "grouped_layer_steps": self.stats["moe_grouped_layer_steps"],
                "experts_touched_per_layer_step": self.stats["moe_experts_touched"]
                / max(1, self.stats["moe_layer_steps"]),
                "pairs_per_touched_expert": self.stats["moe_pairs_here"]
                / max(1, self.stats["moe_experts_touched"]),
            },
            # What the cache holds: every pool the model declared, and what
            # the state kept by slot has cost (rows a window's steps moved, of
            # the rows held; chunk-call segments by where their state began).
            pools=self._pool_facts,
            state={
                "pools": list(self._state_pools),
                "slots_live": self.stats["state_slots_live"],
                "slots_table": self.stats["state_slots_table"],
                "slots_live_pct": 100.0 * self.stats["state_slots_live"]
                / max(1, self.stats["state_slots_table"]),
                "segments_carried": self.stats["state_segments_carried"],
                "segments_fresh": self.stats["state_segments_fresh"],
            },
            overlap={
                "enabled": self.overlap,
                "windows": self.stats["steps"],
                "spec_windows": self.stats["spec_windows"],
                # Fraction of windows dispatched while the previous one
                # was still unread — host/device overlap occupancy.
                "occupancy": self.stats["spec_windows"]
                / max(1, self.stats["steps"]),
                # Windows found in flight and not overlapped, by reason.
                "blocked": {why: self.stats["spec_blocked_" + why]
                            for why in _SPEC_BLOCKED},
                # Share of the block tables that held live tokens when
                # their window was dispatched: what decode attention reads.
                "decode_live_block_pct": 100.0 * self.stats["decode_blocks_live"]
                / max(1, self.stats["decode_blocks_table"]),
                "h2d_ships": self.stats["h2d_ships"],
                "h2d_skips": self.stats["h2d_skips"],
                # Windows queued behind their iteration's prefill programs,
                # the first tokens unread; and those that had to wait for
                # the read (their dispatch had to preempt).
                "windows_behind_prefill": self.stats["windows_behind_prefill"],
                "prefill_flushed_first": self.stats["prefill_flushed_first"],
                # Of the time since the engine was built, the share the
                # device had nothing queued while there was work for it, and
                # where the scheduler thread was meanwhile.
                "device_starved_pct": 100.0 * self.stats["starved_us"]
                / max(1, time.perf_counter_ns() // 1000 - self._born_us),
                "starved_us": {where: self.stats["starved_us_" + where]
                               for where in _STARVED},
            },
        )
        try:
            from ray_tpu.core import api

            core = api._global_worker
            if core is not None:
                key = "{}/{}/{}".format(
                    self.metrics_tags.get("deployment", "-"),
                    self.metrics_tags.get("replica", "-"),
                    self.engine_id,
                )
                now = time.monotonic()
                # Idle engine: heartbeat only (None), with a periodic
                # full push as self-repair against a restarted/pruned
                # controller table.
                idle = (
                    snap["stats"] == self._last_pushed_stats
                    and now - self._last_full_push < 30.0
                )
                core._call("serve_report", key, None if idle else snap)
                if not idle:
                    self._last_pushed_stats = dict(snap["stats"])
                    self._last_full_push = now
        except Exception as e:  # noqa: BLE001 — controller hiccups are non-fatal
            logger.debug("engine snapshot push failed: %s", e)
        return snap
