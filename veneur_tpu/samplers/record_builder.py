"""A metric segment's records built in native code, one call a chunk:
the ctypes.PyDLL binding of `native/record_builder.cpp`.

`MetricSegment.materialize()` hands its columns here where it can; the
interpreter then runs no frame a record.  The record class stays
defined in Python alone (`samplers.InterMetric`): the library reads the
slots' offsets from the class's member descriptors once, at load, and a
class whose layout it cannot vouch for is refused there — the caller
keeps its Python path for that, as for a host without a compiler or
the interpreter's headers.
"""

from __future__ import annotations

import ctypes
import logging
import os
import sysconfig
import threading
from typing import Optional

from veneur_tpu.util import native_build

logger = logging.getLogger("veneur.samplers")

_SRC = os.path.join(native_build.NATIVE_DIR, "record_builder.cpp")
_SO = os.path.join(native_build.BUILD_DIR, "libvnrecords.so")

# the record's slots, in the order vn_build_records stores them
SLOTS = ("name", "timestamp", "value", "tags", "type", "message",
         "hostname", "sinks")

# records a native call: the call holds the interpreter lock (a few
# milliseconds at this size), so this bounds how long the drain thread,
# the importers and the ticker wait for it — they get it between chunks
# as they get it between bytecodes on the interpreter's path
CHUNK = 16384

_build_lock = threading.Lock()
_lib = None


def load_builder_library():
    """Build (if stale) and load the library; raises on failure.  (Not
    `load_library`: the lock-order analysis resolves a call by its bare
    name, and two of them cost it ingest's edges.)"""
    global _lib
    with _build_lock:
        if _lib is not None:
            return _lib
        native_build.build_if_stale(
            _SRC, _SO, extra=["-I", sysconfig.get_paths()["include"]])
        # PyDLL: the calls keep the interpreter lock and an exception
        # set in the library is raised here
        lib = ctypes.PyDLL(_SO)
        lib.vn_record_layout.restype = ctypes.c_int
        lib.vn_record_layout.argtypes = [
            ctypes.py_object, ctypes.py_object,
            ctypes.POINTER(ctypes.c_ssize_t)]
        lib.vn_build_records.restype = ctypes.py_object
        lib.vn_build_records.argtypes = (
            [ctypes.py_object, ctypes.POINTER(ctypes.c_ssize_t)]
            + [ctypes.py_object] * 7 + [ctypes.c_ssize_t] * 2)
        _lib = lib
        return lib


class RecordBuilder:
    """Builds instances of one record class whose layout the library
    has verified (TypeError from the constructor where it refuses)."""

    def __init__(self, lib, cls: type):
        self._build = lib.vn_build_records
        self._cls = cls
        self._offsets = (ctypes.c_ssize_t * len(SLOTS))()
        lib.vn_record_layout(cls, SLOTS, self._offsets)

    def extend(self, out: list, bases: list, suffix: str, timestamp,
               values: list, tags: list, type,
               sinks: Optional[list]) -> None:
        """Append one record a row of the columns (lists of one length;
        `sinks` may be None) to `out`, CHUNK rows a native call."""
        n = len(values)
        for lo in range(0, n, CHUNK):
            out.extend(self._build(
                self._cls, self._offsets, bases, suffix, timestamp, values,
                tags, type, sinks, lo, min(lo + CHUNK, n)))


def load(cls: type) -> Optional[RecordBuilder]:
    """A builder for `cls`, or None — said once in the log — where this
    host cannot have one: no compiler, no Python.h, a build that fails,
    a class layout the library refuses."""
    try:
        return RecordBuilder(load_builder_library(), cls)
    except (OSError, RuntimeError, TypeError, AttributeError) as e:
        logger.warning("native record builder unavailable, records are "
                       "built in Python: %s", e)
        return None
