"""ObjectRef — the distributed future handle.

Role parity: python/ray/includes/object_ref.pxi:38 — a typed handle to an
object in the cluster; awaiting/getting goes through the driver/worker's core
runtime. Refs are owner-tracked: the process that created the object (by put
or by task return) owns it and its reference count (reference_count.h:61).
"""

from __future__ import annotations

from typing import Optional

from ray_tpu.core.ids import ObjectID

# Installed by ClusterRuntime._finish_init; None in local mode / no runtime.
# Every ObjectRef created (including by deserialization in a borrowing
# worker) registers here, and deregisters on GC — the distributed refcount
# (reference_count.h:61) is driven entirely by these two hooks plus the
# submitter's explicit in-flight-arg pins (core/refcount.py).
_tracker = None


class ObjectRef:
    # ``_trace``: the trace_ctx of the actor call this ref is the return
    # of, where that call was made under a span; cleared by the get that
    # resolves it (runtime_cluster ``call.get``). None on every other ref.
    __slots__ = ("_id", "_owner", "_tracked", "_trace", "__weakref__")

    def __init__(self, object_id: ObjectID, owner: Optional[str] = None):
        self._id = object_id
        # Owner address string ("host:port" of the owning worker/driver) —
        # lets any holder resolve the object's location via the owner.
        self._owner = owner
        self._trace = None
        t = _tracker
        self._tracked = t is not None
        if t is not None:
            t.handle_created(object_id.binary())

    def __del__(self):
        if self._tracked:
            t = _tracker
            if t is not None:
                try:
                    t.handle_dropped(self._id.binary())
                except Exception:
                    pass  # interpreter teardown

    @property
    def id(self) -> ObjectID:
        return self._id

    @property
    def owner_address(self) -> Optional[str]:
        return self._owner

    def hex(self) -> str:
        return self._id.hex()

    def binary(self) -> bytes:
        return self._id.binary()

    def __eq__(self, other):
        return isinstance(other, ObjectRef) and other._id == self._id

    def __hash__(self):
        return hash(self._id)

    def __repr__(self):
        return f"ObjectRef({self._id.hex()})"

    def __reduce__(self):
        # Serializing a ref inside task args/returns is how borrowing happens;
        # the runtime's serializer also intercepts these to track borrowers.
        return (ObjectRef, (self._id, self._owner))

    def __await__(self):
        from ray_tpu.core.api import _async_get
        return _async_get(self).__await__()

    def future(self):
        """A concurrent.futures.Future resolving to the object's value."""
        from ray_tpu.core.api import _ref_future
        return _ref_future(self)


class ChannelResolvedRef(ObjectRef):
    """An ObjectRef whose value arrives over a subsystem resolver instead
    of the object plane — compiled-graph results read from an output
    channel (dag/compiled.py CompiledGraphRef). get()/wait() dispatch to
    ``_resolve``/``_is_ready`` (core/api.py), so these refs compose with
    plain ones in the public API while staying outside the distributed
    refcount (the channel ring, not the store, owns the value's slot).
    """

    __slots__ = ()

    def __init__(self, object_id: ObjectID):
        # Deliberately skips the tracker hooks: a channel-delivered value
        # has no store entry for the conductor ledger to count.
        self._id = object_id
        self._owner = None
        self._trace = None
        self._tracked = False

    def _resolve(self, timeout: Optional[float] = None):
        """Block until the value is available; return it (or raise the
        propagated error)."""
        raise NotImplementedError

    def _is_ready(self) -> bool:
        """Non-blocking readiness probe for wait()."""
        raise NotImplementedError

    def __reduce__(self):
        raise TypeError(
            "channel-resolved refs (compiled-graph results) cannot be "
            "serialized; get() the value and pass that instead")
