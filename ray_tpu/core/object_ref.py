"""ObjectRef — a future handle to a value in the distributed object store.

Mirrors the reference's ``ray.ObjectRef`` (``python/ray/_raylet.pyx`` ObjectRef class):
the ref carries its id plus the *owner's* RPC address (ownership-based object directory,
reference ``src/ray/object_manager/ownership_based_object_directory.h`` — the owner is
the source of truth for the value's location and lifetime).  Refs participate in
distributed reference counting: construction/destruction report to the process-local
ReferenceCounter (reference ``src/ray/core_worker/reference_count.h:61``).
"""

from __future__ import annotations

import gc
from typing import Optional

from .ids import ObjectID

# A cyclic collection starts at an eval-breaker check between any two
# bytecodes of any thread, also inside a ``with lock:`` of the reference
# counter, of the free buffer or of an IO lane's creation.  A finalizer that
# then takes the same lock on the same thread waits for itself, and every
# thread that wants the lock after it waits too (PERF.md section 7, PR 34: a
# caller's IO thread; PR 51: the storm test's 256 submitters and, behind
# them, ``shutdown``).  So a finalizer the collector calls hands its work to
# the IO loop, which runs it between callbacks, inside none of those
# sections.  Work handed on comes late, never early: no object is freed
# before its time.
_collecting = False


def _on_gc(phase: str, info: dict) -> None:
    global _collecting
    _collecting = phase == "start"


gc.callbacks.append(_on_gc)


def finalize(fn, *args) -> None:
    """Run a finalizer's work ``fn(*args)``: at once, or on the IO loop
    when it is the collector that called the finalizer.  ``args`` must not
    hold the dying object."""
    if _collecting:
        from .rpc import get_loop
        get_loop().call_soon_threadsafe(fn, *args)
    else:
        fn(*args)


class ObjectRef:
    __slots__ = ("id", "owner", "_registered", "__weakref__")

    def __init__(self, object_id: ObjectID, owner: str = "", _register: bool = True):
        self.id = object_id
        self.owner = owner  # rpc address of owning core worker ("" = local)
        self._registered = _register
        if _register:
            _ref_created(self)

    def hex(self) -> str:
        return self.id.hex()

    def binary(self) -> bytes:
        return self.id.binary()

    def task_id(self):
        return self.id.task_id()

    def future(self):
        """A concurrent.futures.Future resolved with the object's value."""
        from . import api
        return api.as_future(self)

    def __await__(self):
        from . import api
        return api.get_async(self).__await__()

    def __eq__(self, other):
        return isinstance(other, ObjectRef) and other.id == self.id

    def __hash__(self):
        return hash(self.id)

    def __repr__(self):
        return f"ObjectRef({self.id.hex()})"

    def __reduce__(self):
        # Plain pickle path (e.g. sending a ref through a non-ray channel).
        # Ray-internal serialization intercepts refs via persistent_id instead
        # so it can track borrowers.
        return (ObjectRef, (self.id, self.owner, False))

    def __del__(self):
        # Only refs that incremented the count on construction decrement it
        # (refs built with _register=False, e.g. transient lookups, must not
        # unbalance the count and free live objects).
        if not getattr(self, "_registered", False):
            return
        try:
            finalize(_ref_deleted, self.id, self.owner)
        except Exception:
            pass


# Bound on first use (core_worker imports this module, so a top-level
# import would be circular).  These run once per ObjectRef construction
# and destruction — the repeated `from .core_worker import ...` module
# machinery showed up in submit-path profiles.
_global_worker_or_none = None


def _worker():
    global _global_worker_or_none
    if _global_worker_or_none is None:
        from .core_worker import \
            global_worker_or_none as _global_worker_or_none
    return _global_worker_or_none()


def _ref_created(ref: ObjectRef):
    w = _worker()
    if w is not None:
        w.reference_counter.add_local_ref(ref.id, ref.owner)


def _ref_deleted(oid: ObjectID, owner: str):
    # binds the lookup too: the submit path registers a task's return refs
    # without passing through ``_ref_created``
    w = _worker()
    if w is not None:
        w.reference_counter.remove_local_ref(oid, owner)
