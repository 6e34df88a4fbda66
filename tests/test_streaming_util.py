"""Streaming generators + ActorPool + Queue.

Reference test models: python/ray/tests/test_streaming_generator.py,
test_actor_pool.py, test_queue.py.
"""
import time

import pytest

import ray_tpu
from ray_tpu.util.actor_pool import ActorPool
from ray_tpu.util.queue import Empty, Full, Queue

from conftest import shared_cluster_fixtures

# Shared cluster for the whole file (suite-time headroom). ActorPool /
# Queue actors left running hold 1 CPU each — the wide pool absorbs them.
ray_start_regular, _shared_cluster_guard = shared_cluster_fixtures(
    num_cpus=16, resources={"TPU": 4}
)



def test_streaming_task(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * i

    out = [ray_tpu.get(ref) for ref in gen.remote(5)]
    assert out == [0, 1, 4, 9, 16]


def test_streaming_produces_incrementally(ray_start_regular):
    @ray_tpu.remote
    def warm():
        return 1

    ray_tpu.get(warm.remote())  # exclude worker cold-start from timing

    @ray_tpu.remote(num_returns="streaming")
    def slow_gen():
        for i in range(3):
            time.sleep(1.0)
            yield i

    g = slow_gen.remote()
    t0 = time.monotonic()
    first = ray_tpu.get(next(g))
    first_latency = time.monotonic() - t0
    assert first == 0
    # Stream takes 3s to finish; the first item must arrive well before
    # that (margin sized for a loaded shared box).
    assert first_latency < 2.5, "first item should arrive before the stream ends"
    assert [ray_tpu.get(r) for r in g] == [1, 2]


def test_streaming_error_mid_stream(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def bad_gen():
        yield 1
        raise ValueError("stream broke")

    g = bad_gen.remote()
    assert ray_tpu.get(next(g)) == 1
    with pytest.raises(Exception, match="stream broke"):
        ray_tpu.get(next(g))
    with pytest.raises(StopIteration):
        next(g)


def test_streaming_actor_method(ray_start_regular):
    @ray_tpu.remote
    class Streamer:
        def chunks(self, n):
            for i in range(n):
                yield f"chunk-{i}"

    s = Streamer.remote()
    gen = s.chunks.options(num_returns="streaming").remote(3)
    assert [ray_tpu.get(r) for r in gen] == ["chunk-0", "chunk-1", "chunk-2"]


def test_streaming_generator_picklable(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def gen():
        yield "a"
        yield "b"

    @ray_tpu.remote
    def consume(g):
        return [ray_tpu.get(r) for r in g]

    g = gen.remote()
    assert ray_tpu.get(consume.remote(g)) == ["a", "b"]


# --- group commit: what a generator has yielded leaves as one shipment ---------
@ray_tpu.remote
class _Bursts:
    """Streams bursts from ONE worker process and reads that process's shipper
    (``core/worker_main._StreamShipper``: items, and the controller calls that
    carried them)."""

    def burst(self, n, fail_at=None):
        for i in range(n):
            if i == fail_at:
                raise ValueError(f"stream broke at {i}")
            yield i

    def first_then_late(self, gap_s):
        yield "first"
        time.sleep(gap_s)
        yield "late"

    def shipped(self):
        from ray_tpu.core.api import _require_worker

        shipper = _require_worker().stream_shipper
        return (0, 0) if shipper is None else (shipper.items, shipper.shipments)


@pytest.mark.parametrize("n", [40, 400])
def test_a_burst_arrives_in_order_in_far_fewer_shipments_a_ref_an_item(ray_start_regular, n):
    """By reference, as every plain consumer takes a stream: one ref an item,
    each resolving to its own item, in order; the burst left the worker in far
    fewer controller calls than it has items (a run is what gathered while the
    last shipment was on its way)."""
    a = _Bursts.remote()
    items0, ships0 = ray_tpu.get(a.shipped.remote())
    refs = list(a.burst.options(num_returns="streaming").remote(n))
    assert len({r.id for r in refs}) == n
    assert [ray_tpu.get(r) for r in refs] == list(range(n))
    items, ships = ray_tpu.get(a.shipped.remote())
    assert items - items0 == n
    assert 1 <= ships - ships0 <= n // 4, (ships - ships0, n)


def test_a_burst_taken_by_value_comes_in_runs(ray_start_regular):
    """``ObjectRefGenerator.take``: every item that has arrived, in one call,
    as (value, is_error) pairs; a stream read that way to its end gives every
    item once, in order, in far fewer takes than items; then StopIteration."""
    a = _Bursts.remote()
    gen = a.burst.options(num_returns="streaming").remote(300)
    got, takes = [], 0
    while True:
        try:
            run = gen.take()
        except StopIteration:
            break
        assert run and not any(is_error for _, is_error in run)
        got.extend(value for value, _ in run)
        takes += 1
    assert got == list(range(300)) and takes <= 75


def test_a_lone_item_leaves_without_waiting_for_company(ray_start_regular):
    a = _Bursts.remote()
    ray_tpu.get(a.shipped.remote())  # the worker is up
    gen = a.first_then_late.options(num_returns="streaming").remote(3.0)
    t0 = time.monotonic()
    assert ray_tpu.get(next(gen)) == "first"
    assert time.monotonic() - t0 < 2.0, "the first item waited for the second"
    _, ships = ray_tpu.get(a.shipped.remote())
    assert ships >= 1  # it went out alone, while the generator slept
    assert ray_tpu.get(next(gen)) == "late"
    with pytest.raises(StopIteration):
        next(gen)


def test_many_streams_of_one_process_share_the_shipper_and_keep_their_order(ray_start_regular):
    """Sixteen generators of one actor yield at once into the process's one
    shipper (more threads than this box has cores to spare, a short switch
    interval in the consumer): every stream arrives whole and in its own
    order, whether it is taken by value or by reference, and a shipment
    carried runs of several streams (far fewer shipments than items)."""
    import sys
    import threading

    a = _Bursts.options(max_concurrency=16).remote()
    items0, ships0 = ray_tpu.get(a.shipped.remote())
    n, streams = 250, 16
    gens = [a.burst.options(num_returns="streaming").remote(n) for _ in range(streams)]
    got = [[] for _ in gens]

    def consume(i):
        if i % 2:
            got[i].extend(ray_tpu.get(ref) for ref in gens[i])
            return
        while True:
            try:
                got[i].extend(value for value, _ in gens[i].take())
            except StopIteration:
                return

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=consume, args=(i,)) for i in range(streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    assert got == [list(range(n))] * streams
    items, ships = ray_tpu.get(a.shipped.remote())
    assert items - items0 == n * streams and ships - ships0 <= n * streams // 4


@pytest.mark.parametrize("by_value", [False, True], ids=["by-ref", "by-value"])
def test_an_error_after_k_items_of_a_burst_follows_exactly_those_k(ray_start_regular, by_value):
    a = _Bursts.remote()
    gen = a.burst.options(num_returns="streaming").remote(50, fail_at=7)
    if by_value:
        got = []
        while True:
            try:
                got.extend(gen.take())
            except StopIteration:
                break
        assert [v for v, is_error in got[:7]] == list(range(7)) and not any(e for _, e in got[:7])
        assert len(got) == 8 and got[7][1] and "stream broke at 7" in str(got[7][0])
    else:
        assert [ray_tpu.get(next(gen)) for _ in range(7)] == list(range(7))
        with pytest.raises(Exception, match="stream broke at 7"):
            ray_tpu.get(next(gen))
        with pytest.raises(StopIteration):
            next(gen)


# ---------------------------------------------------------------------------
def test_actor_pool(ray_start_regular):
    @ray_tpu.remote
    class Doubler:
        def double(self, x):
            return 2 * x

    pool = ActorPool([Doubler.remote() for _ in range(2)])
    assert list(pool.map(lambda a, v: a.double.remote(v), range(6))) == [0, 2, 4, 6, 8, 10]
    assert sorted(pool.map_unordered(lambda a, v: a.double.remote(v), range(4))) == [0, 2, 4, 6]


def test_queue_basic(ray_start_regular):
    q = Queue(maxsize=2)
    q.put("a")
    q.put("b")
    with pytest.raises(Full):
        q.put_nowait("c")
    assert q.qsize() == 2
    assert q.get() == "a"
    assert q.get() == "b"
    with pytest.raises(Empty):
        q.get_nowait()
    with pytest.raises(Empty):
        q.get(timeout=0.2)


def test_queue_across_tasks(ray_start_regular):
    q = Queue()

    @ray_tpu.remote
    def producer(q, n):
        for i in range(n):
            q.put(i)
        return True

    @ray_tpu.remote
    def consumer(q, n):
        return [q.get(timeout=10) for _ in range(n)]

    p = producer.remote(q, 5)
    c = consumer.remote(q, 5)
    assert ray_tpu.get(c) == [0, 1, 2, 3, 4]
    assert ray_tpu.get(p)
