"""Object serialization: cloudpickle + out-of-band zero-copy buffers.

Role parity: python/ray/_private/serialization.py — values are pickled with
protocol 5; large contiguous buffers (numpy arrays, bytes) are extracted
out-of-band so readers can map them zero-copy out of shared memory.
ObjectRefs contained in a value are collected during serialization so the
runtime can track borrowing and task dependencies (reference_count.h:61).

Wire layout of a serialized object:

    [8B magic+version][8B pickle_len][4B nbuf]
    [8B len + pad-to-64 for each buffer] ... header, then:
    [pickle bytes][pad][buffer 0][pad][buffer 1] ...

Buffers are 64-byte aligned relative to the start of the blob so that a
reader holding the blob in an aligned shm mapping can reconstruct numpy
arrays as views without copying.
"""

from __future__ import annotations

import mmap
import pickle
import struct
import sys
import threading
import weakref
from typing import Any, List, Optional, Tuple

import cloudpickle

from ray_tpu.core.refs import ObjectRef

_MAGIC = b"RTOB\x00\x00\x00\x01"
# Array fast-path wire format (r16): a tiny fixed header instead of a
# pickle program. Layout after the magic:
#
#     [1B flags][1B order][2B dtype_len][2B device_len][2B pad]
#     [4B ndim][8B nbytes][ndim x 8B shape][dtype str][device str]
#     [pad-to-64][raw buffer]
#
# flags bit 0: the value was a jax.Array (device-resident producer; the
# ``device`` string records its placement). The buffer is the array's
# bytes in MEMORY order; ``order`` ('C'/'F') says how to fold them back.
_ARRAY_MAGIC = b"RTAR\x00\x00\x00\x01"
_ARRAY_HDR = struct.Struct("<BBHHHIQ")
_ARRAY_FLAG_JAX = 1
_ALIGN = 64


def _pad(n: int) -> int:
    return (-n) % _ALIGN


class _Pickler(cloudpickle.CloudPickler):
    def __init__(self, file, buffer_callback):
        super().__init__(file, protocol=5, buffer_callback=buffer_callback)
        self.contained_refs: List[ObjectRef] = []

    def persistent_id(self, obj):
        return None

    def reducer_override(self, obj):
        if isinstance(obj, ObjectRef):
            self.contained_refs.append(obj)
        if isinstance(obj, _JaxArrayPlaceholder.jax_array_types()):
            import numpy as np
            return (_restore_array, (np.asarray(obj),))
        # Defer to cloudpickle's own override (functions, classes, ...).
        return super().reducer_override(obj)


class _JaxArrayPlaceholder:
    _types = None

    @classmethod
    def jax_array_types(cls):
        if cls._types is None:
            # NEVER import jax here: a value can only BE a jax array if
            # jax is already in sys.modules, and importing it costs ~1.5s
            # CPU + hundreds of MB in every worker that pickles its first
            # numpy array (measured as a mystery 1.5s first-put stall).
            import sys
            jax = sys.modules.get("jax")
            if jax is None:
                return ()   # don't cache — jax may be imported later
            try:
                cls._types = (jax.Array,)
            except Exception:
                # jax is mid-import on another thread (module present but
                # not fully initialized): don't poison the cache.
                return ()
        return cls._types


def _restore_array(arr):
    return arr


# ---------------------------------------------------------------------------
# Array fast path (r16): top-level numpy/jax arrays skip pickle entirely —
# a fixed RTAR header plus the raw buffer as a zero-copy segment, so
# ObjectPlane.put copies the payload ONCE (straight into the shm mapping)
# and deserialize returns a read-only view over the pinned mapping.
# ---------------------------------------------------------------------------

# Live read-only array views whose base is a pinned shm mapping: each
# deserialized array registers a finalizer on the mmap, so the conftest
# hygiene gate (and the rt_array_pins_live gauge) can assert no test
# leaks a pin past its own teardown.
_pin_lock = threading.Lock()
_live_array_pins = 0


def _untrack_pin() -> None:
    global _live_array_pins
    with _pin_lock:
        _live_array_pins -= 1


def _track_pin(base) -> None:
    global _live_array_pins
    try:
        weakref.finalize(base, _untrack_pin)
    except TypeError:
        return  # bytes-backed view: no store pin behind it
    with _pin_lock:
        _live_array_pins += 1


def live_array_pins() -> int:
    """Read-only array views still holding a shm pin (hygiene gate)."""
    with _pin_lock:
        return _live_array_pins


def is_array_blob(buf) -> bool:
    """True when a serialized blob (or its first segment) is an RTAR
    array-header object (channel/plane callers dispatch on this)."""
    m = memoryview(buf)
    return m.nbytes >= 8 and bytes(m[:8]) == _ARRAY_MAGIC


def array_header(buf) -> Optional[dict]:
    """Parse an RTAR header without touching the payload — object-plane
    placement tagging and debug tooling read dtype/shape/device from
    the first segment only."""
    m = memoryview(buf)
    if m.nbytes < 8 + _ARRAY_HDR.size or bytes(m[:8]) != _ARRAY_MAGIC:
        return None
    flags, order, dtype_len, device_len, _r, ndim, nbytes = \
        _ARRAY_HDR.unpack_from(m, 8)
    off = 8 + _ARRAY_HDR.size
    shape = struct.unpack_from(f"<{ndim}q", m, off)
    off += 8 * ndim
    dtype = bytes(m[off:off + dtype_len]).decode()
    off += dtype_len
    device = bytes(m[off:off + device_len]).decode()
    return {"nbytes": nbytes, "shape": tuple(shape), "dtype": dtype,
            "order": chr(order), "device": device,
            "was_jax": bool(flags & _ARRAY_FLAG_JAX)}


def _export_array(value):
    """value -> (ndarray, was_jax, device) or None when not an exact
    top-level array (or the export fault site failed it)."""
    np = sys.modules.get("numpy")
    if np is None:
        return None
    was_jax = False
    device = ""
    if type(value) is not np.ndarray:
        jtypes = _JaxArrayPlaceholder.jax_array_types()
        if not (jtypes and isinstance(value, jtypes)):
            return None
        was_jax = True
        try:
            device = str(next(iter(value.devices())))
        except Exception:
            device = ""
        try:
            from ray_tpu.cluster import fault_plane
            fault_plane.fire("object.array.export", kind="jax")
            # dlpack first: zero-copy for host-backed (CPU) arrays — the
            # old path's np.asarray always paid a full host copy here.
            value = np.from_dlpack(value)
        except Exception:
            try:
                value = np.asarray(value)
            except Exception:
                return None
        if type(value) is not np.ndarray:
            return None
    else:
        try:
            from ray_tpu.cluster import fault_plane
            fault_plane.fire("object.array.export", kind="numpy")
        except Exception:
            return None  # injected export failure: classic pickle path
    d = value.dtype
    if d.hasobject or d.fields is not None:
        return None
    if not (value.flags.c_contiguous or value.flags.f_contiguous):
        return None
    return value, was_jax, device


def _array_segments(value) -> Optional[Tuple[int, List]]:
    """RTAR (total, segments) for a top-level array value, or None to
    take the classic pickle path."""
    exported = _export_array(value)
    if exported is None:
        return None
    arr, was_jax, device = exported
    order = b"C" if arr.flags.c_contiguous else b"F"
    # memoryview.cast requires C-contiguity; an F-ordered array's
    # transpose is the same memory seen C-contiguously.
    base = arr if arr.flags.c_contiguous else arr.T
    try:
        if arr.ndim == 0 or arr.size == 0:
            # cast("B") rejects 0-d/empty views; the "copy" is one itemsize.
            buf = memoryview(arr.tobytes())
        else:
            buf = memoryview(base)
            if buf.format != "B" or buf.ndim != 1:
                buf = buf.cast("B")
    except (ValueError, TypeError):
        return None  # datetime64 etc. refuse the buffer protocol
    dtype_b = arr.dtype.str.encode()
    device_b = device.encode()
    flags = _ARRAY_FLAG_JAX if was_jax else 0
    header = bytearray()
    header += _ARRAY_MAGIC
    header += _ARRAY_HDR.pack(flags, order[0], len(dtype_b), len(device_b),
                              0, arr.ndim, arr.nbytes)
    header += struct.pack(f"<{arr.ndim}q", *arr.shape)
    header += dtype_b + device_b
    header += b"\x00" * _pad(len(header))
    segments: List = [bytes(header), buf]
    total = len(segments[0]) + buf.nbytes
    tail = _pad(total)
    if tail:
        segments.append(b"\x00" * tail)
        total += tail
    return total, segments


def _deserialize_array(m: memoryview):
    """RTAR blob -> read-only ndarray view over the blob's memory. When
    ``m`` maps pinned shm, the array (and every slice of it) keeps the
    pin alive until the last view is garbage collected."""
    import numpy as np
    hdr = array_header(m)
    if hdr is None:
        raise ValueError("bad array blob header")
    ndim = len(hdr["shape"])
    off = 8 + _ARRAY_HDR.size + 8 * ndim + len(hdr["dtype"]) \
        + len(hdr["device"].encode())
    body = off + _pad(off)
    nbytes = hdr["nbytes"]
    arr = np.frombuffer(m[body:body + nbytes], dtype=np.dtype(hdr["dtype"]))
    arr = arr.reshape(hdr["shape"], order=hdr["order"])
    try:
        arr.flags.writeable = False
    except Exception:
        pass  # already read-only (PROT_READ mapping / bytes blob)
    base = getattr(m, "obj", None)
    if isinstance(base, mmap.mmap):
        _track_pin(base)
    return arr


# Exact-type primitives cannot contain ObjectRefs or out-of-band buffers,
# so their serialization skips the CloudPickler construction entirely
# (~20us/call — dominant in the inline-return reply path, where task
# results are typically None or a small scalar).
_PRIM_TYPES = frozenset((type(None), bool, int, float, str, bytes))


def serialize_segments(value: Any) -> Tuple[int, List, List[ObjectRef]]:
    """Serialize ``value`` into (total_len, segments, contained refs).

    Segments are bytes/memoryviews whose concatenation is the wire blob;
    large buffers stay as views so the object-plane put can copy them ONCE,
    directly into the destination shm mapping (the reference's plasma put
    is likewise single-copy, core_worker.cc:1095).
    """
    if type(value) in _PRIM_TYPES:
        pickled = pickle.dumps(value, protocol=5)
        seg0 = _MAGIC + struct.pack("<QI", len(pickled), 0) + pickled
        total = len(seg0)
        pad = _pad(total)
        if pad:
            return total + pad, [seg0, b"\x00" * pad], []
        return total, [seg0], []

    fast = _array_segments(value)
    if fast is not None:
        total, segments = fast
        return total, segments, []

    import io

    buffers: List[pickle.PickleBuffer] = []
    bio = io.BytesIO()
    p = _Pickler(bio, buffers.append)
    p.dump(value)
    pickled = bio.getvalue()

    raw: List[memoryview] = []
    for b in buffers:
        m = b.raw()
        if not m.contiguous:
            m = memoryview(bytes(m))
        if m.format != "B" or m.ndim != 1:
            m = m.cast("B")
        raw.append(m)

    header = bytearray()
    header += _MAGIC
    header += struct.pack("<QI", len(pickled), len(raw))
    for m in raw:
        header += struct.pack("<Q", m.nbytes)

    segments: List = [bytes(header) + pickled]
    total = len(segments[0])
    pad = _pad(total)
    if pad:
        segments.append(b"\x00" * pad)
        total += pad
    for m in raw:
        segments.append(m)
        total += m.nbytes
        pad = _pad(total)
        if pad:
            segments.append(b"\x00" * pad)
            total += pad
    return total, segments, p.contained_refs


def serialize(value: Any) -> Tuple[bytes, List[ObjectRef]]:
    """Serialize ``value``; returns (blob, contained ObjectRefs)."""
    total, segments, refs = serialize_segments(value)
    # join() accepts the memoryview segments directly (they are contiguous
    # "B" views by construction) — ONE copy into the blob, not two.
    return b"".join(segments), refs


def serialized_size(blob: bytes) -> int:
    return len(blob)


def deserialize(blob) -> Any:
    """Deserialize from a bytes-like (bytes or an shm-backed memoryview).

    When ``blob`` is a memoryview over shared memory, buffer-backed arrays are
    reconstructed as zero-copy views over that memory.
    """
    m = memoryview(blob)
    if bytes(m[:8]) == _ARRAY_MAGIC:
        return _deserialize_array(m)
    if bytes(m[:8]) != _MAGIC:
        raise ValueError("bad object blob magic")
    pickle_len, nbuf = struct.unpack_from("<QI", m, 8)
    off = 20
    buf_lens = []
    for i in range(nbuf):
        (blen,) = struct.unpack_from("<Q", m, off)
        buf_lens.append(blen)
        off += 8
    body = off
    pickled = m[body:body + pickle_len]
    cur = body + pickle_len
    cur += _pad(cur)
    bufs = []
    for blen in buf_lens:
        bufs.append(m[cur:cur + blen])
        cur += blen
        cur += _pad(cur)
    return pickle.loads(pickled, buffers=bufs)


def dumps(value: Any) -> bytes:
    """Plain cloudpickle (control-plane payloads: task specs, functions)."""
    return cloudpickle.dumps(value, protocol=5)


def _prims_only_args(value: Any) -> bool:
    """True iff ``value`` is the submit-path ``(args_list, kwargs_dict)``
    pair and every element is an exact primitive — such a payload cannot
    contain an ObjectRef (or anything needing cloudpickle), so the in-band
    ref-collecting pickler is pure overhead for it."""
    if type(value) is not tuple or len(value) != 2:
        return False
    a, kw = value
    if type(a) is not list or type(kw) is not dict:
        return False
    for v in a:
        if type(v) not in _PRIM_TYPES:
            return False
    for k, v in kw.items():
        if type(k) is not str or type(v) not in _PRIM_TYPES:
            return False
    return True


def dumps_with_refs(value: Any) -> Tuple[bytes, List[ObjectRef]]:
    """In-band cloudpickle that also reports every ObjectRef reachable from
    ``value`` (at any nesting depth) in ONE pass — the submit path pins
    these for the duration of the task handoff (reference_count.h:61
    in-flight argument references)."""
    if _prims_only_args(value):
        return pickle.dumps(value, protocol=5), []
    import io

    bio = io.BytesIO()
    p = _Pickler(bio, None)
    p.dump(value)
    return bio.getvalue(), p.contained_refs


def loads(blob: bytes) -> Any:
    return pickle.loads(blob)


def collect_refs(value: Any) -> List[ObjectRef]:
    """Find ObjectRefs inside a value without a full re-serialize when cheap.

    Falls back to a serializing walk for arbitrary nesting.
    """
    if isinstance(value, ObjectRef):
        return [value]
    if isinstance(value, (list, tuple, set)):
        out: List[ObjectRef] = []
        for v in value:
            out.extend(collect_refs(v))
        return out
    if isinstance(value, dict):
        out = []
        for k, v in value.items():
            out.extend(collect_refs(k))
            out.extend(collect_refs(v))
        return out
    if isinstance(value, (int, float, str, bytes, bool, type(None))):
        return []
    _, refs = serialize(value)
    return refs
