"""ObjectRef: the distributed future handed back by task submission / put.

Reference: python/ray/includes/object_ref.pxi + ownership in
src/ray/core_worker/reference_count.cc. Distributed ref counting: every
ObjectRef construction/destruction in a worker process updates a local
ref table (the reference's AddLocalReference/RemoveLocalReference,
reference_count.h:142); deserializing a ref in another process registers
that process as a *borrower* the same way — the zero-crossings are
batch-flushed to the controller, which frees objects nobody references
(see controller._gc_sweep).
"""
from __future__ import annotations

import contextvars
from typing import Optional

from ray_tpu.utils.ids import ObjectID

# Process-global local-ref tracker, installed by CoreWorker on connect
# (None inside the controller and before init).
_tracker = None

# Active capture list: while serializing a value, every ObjectRef pickled
# into it records its id here — how nested/contained refs become pins
# (reference: the borrowing protocol's "contained in owned object" edges).
_capture: contextvars.ContextVar[Optional[list]] = contextvars.ContextVar(
    "ray_tpu_ref_capture", default=None
)


def set_ref_tracker(tracker) -> None:
    global _tracker
    _tracker = tracker


class ObjectRef:
    __slots__ = ("id", "__weakref__")

    def __init__(self, oid: ObjectID):
        self.id = oid
        t = _tracker
        if t is not None:
            t.inc(oid)

    def __del__(self):
        t = _tracker
        if t is not None:
            try:
                t.dec(self.id)
            except Exception:  # interpreter teardown
                pass

    def hex(self) -> str:
        return self.id.hex()

    def binary(self) -> bytes:
        return self.id.binary()

    def __hash__(self):
        return hash(self.id)

    def __eq__(self, other):
        return isinstance(other, ObjectRef) and other.id == self.id

    def __repr__(self):
        return f"ObjectRef({self.id.hex()})"

    def __reduce__(self):
        lst = _capture.get()
        if lst is not None:
            lst.append(self.id)
        return (ObjectRef, (self.id,))

    def call_site(self) -> str:
        """The creation call-site the memory census recorded for this ref
        (``file.py:line:func`` for puts, ``(task) <name>`` for task
        returns; ``""`` for borrowed refs or with the census disabled).
        Reference: ``ObjectRef.call_site()`` backed by the reference
        counter's per-ref call_site string."""
        t = _tracker
        return t.site_of(self.id.binary()) if t is not None else ""

    def future(self):
        """A concurrent.futures.Future resolving to the object's value."""
        from ray_tpu.core.api import _require_worker

        return _require_worker().get_async([self])


class ObjectRefGenerator:
    """Iterator over a streaming task's return refs, yielding each ref as
    the producer yields (reference: _raylet.pyx:1077/:1206 streaming
    generators + ObjectRefGenerator in python/ray/_raylet.pyx).

    next() blocks until the producer has yielded the next item (or the
    stream ends → StopIteration). Works from the driver or inside tasks.

    A consumer that wants the VALUES and no refs calls ``take()`` instead
    (serve's ``DeploymentStreamingResponse`` does): every item that has
    arrived, in one controller call. The producer ships the same way for
    both; which call the consumer makes is the whole difference.
    """

    def __init__(self, task_id):
        self.task_id = task_id
        self._index = 0
        # Optional per-item wait bound (seconds); None blocks until the
        # producer yields. Consumers (e.g. serve streaming) set this so a
        # stalled generator cannot hang them forever.
        self.timeout = None

    def __iter__(self):
        return self

    def __next__(self) -> "ObjectRef":
        from ray_tpu.core.api import _require_worker

        status = _require_worker()._call(
            "stream_next", self.task_id, self._index, timeout=self.timeout
        )
        if status is None:
            raise StopIteration
        ref = ObjectRef(ObjectID.for_task_return(self.task_id, self._index))
        self._index += 1
        return ref

    def take(self) -> list:
        """By value: block until the producer has yielded the next item,
        then return every item from there on that has arrived, as ``(value,
        is_error)`` pairs, in ONE controller call (inline items ride the
        reply; one that does not is fetched by its ref). StopIteration at
        the end of the stream. An item taken by value that no ref was ever
        taken to is freed: take a stream by value or by reference."""
        from ray_tpu.core.api import _require_worker
        from ray_tpu.utils.serialization import deserialize

        worker = _require_worker()
        items = worker._call("stream_take", self.task_id, self._index, timeout=self.timeout)
        if items is None:
            raise StopIteration
        out = []
        for item in items:
            if item is None:
                ref = ObjectRef(ObjectID.for_task_return(self.task_id, self._index))
                try:
                    out.append((worker.get(ref, timeout=self.timeout), False))
                except Exception as e:  # noqa: BLE001 — the item IS the error
                    out.append((e, True))
            else:
                data, is_error = item
                out.append((deserialize(data), is_error))
            self._index += 1
        return out

    def __reduce__(self):
        return (_rebuild_generator, (self.task_id, self._index))


def _rebuild_generator(task_id, index):
    g = ObjectRefGenerator(task_id)
    g._index = index
    return g


class _RefMarker:
    """Placeholder substituted for top-level ObjectRef args in a task's
    serialized arguments; the executing worker replaces it with the
    fetched value (reference: LocalDependencyResolver,
    src/ray/core_worker/transport/dependency_resolver.cc)."""

    __slots__ = ("oid",)

    def __init__(self, oid: ObjectID):
        self.oid = oid

    def __reduce__(self):
        return (_RefMarker, (self.oid,))
