"""Native (C++) runtime components, loaded via ctypes.

Built on demand by :func:`load_eventsim` itself — a single ``g++ -O2
-shared`` subprocess invocation (no pybind11 in this environment; the
Python<->C boundary is a flat C API).  ``load_eventsim()`` returns the
shared library handle or None when no compiler is available — callers fall
back to the pure-Python implementation.

Every build output is named by a hash of its source and compile flags
(:func:`built_path`), so a binary is trusted only when it was built from
the source as it stands — never on its mtime, which a copied tree does
not preserve.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "eventsim.cpp")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

_CXX = ["g++", "-O2", "-std=c++17"]


def _flags(shared: bool):
    return _CXX + (["-shared", "-fPIC"] if shared else [])


def built_path(src: str, shared: bool = True) -> str:
    """Where the build of ``src`` lives: ``<stem>-<hash>`` beside it
    (``lib<stem>-<hash>.so`` for a shared library), the hash over the
    source bytes and the compile command."""
    with open(src, "rb") as f:
        h = hashlib.sha256(f.read())
    h.update(" ".join(_flags(shared)).encode())
    stem = os.path.splitext(os.path.basename(src))[0]
    name = (f"lib{stem}-{h.hexdigest()[:16]}.so" if shared
            else f"{stem}-{h.hexdigest()[:16]}")
    return os.path.join(os.path.dirname(src), name)


def build_native(src: str, shared: bool = True) -> Optional[str]:
    """The path of ``src``'s build (:func:`built_path`), compiling it
    first when absent; None when no compiler is available.  One g++
    invocation via a per-process temp path + os.replace, so concurrent
    builders (parallel pytest workers, two CLIs on a fresh checkout)
    can never interleave writes into a torn artifact.  Shared by the
    event sim (.so) and the native router (binary)."""
    out = built_path(src, shared)
    if os.path.exists(out):
        return out
    tmp = f"{out}.{os.getpid()}.tmp"
    try:
        subprocess.run(_flags(shared) + [src, "-o", tmp], check=True,
                       capture_output=True, timeout=120)
        os.replace(tmp, out)
        return out
    except (subprocess.SubprocessError, FileNotFoundError, OSError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None


def load_eventsim() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the event-sim core; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        so = build_native(_SRC, shared=True)
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            # truncated/wrong-arch .so: fall back to Python
            return None
        c = ctypes
        lib.gsim_create.restype = c.c_void_p
        lib.gsim_create.argtypes = [c.c_int32]
        lib.gsim_destroy.argtypes = [c.c_void_p]
        lib.gsim_config.argtypes = [c.c_void_p, c.c_double, c.c_double,
                                    c.c_double, c.c_int32, c.c_int32,
                                    c.c_double]
        lib.gsim_set_neighbors.argtypes = [c.c_void_p, c.c_int32,
                                           c.POINTER(c.c_int32), c.c_int32]
        lib.gsim_partition.argtypes = [c.c_void_p, c.c_int32, c.c_int32,
                                       c.c_double, c.c_double]
        lib.gsim_broadcast.argtypes = [c.c_void_p, c.c_int32, c.c_int64,
                                       c.c_double]
        lib.gsim_run.argtypes = [c.c_void_p, c.c_double]
        lib.gsim_msgs_sent.restype = c.c_int64
        lib.gsim_msgs_sent.argtypes = [c.c_void_p]
        lib.gsim_now.restype = c.c_double
        lib.gsim_now.argtypes = [c.c_void_p]
        lib.gsim_read_len.restype = c.c_int32
        lib.gsim_read_len.argtypes = [c.c_void_p, c.c_int32]
        lib.gsim_read.argtypes = [c.c_void_p, c.c_int32,
                                  c.POINTER(c.c_int64)]
        lib.gsim_min_hop.restype = c.c_int32
        lib.gsim_min_hop.argtypes = [c.c_void_p, c.c_int32, c.c_int64]
        lib.gsim_delivery_count.restype = c.c_int32
        lib.gsim_delivery_count.argtypes = [c.c_void_p]
        lib.gsim_deliveries.argtypes = [c.c_void_p, c.POINTER(c.c_double),
                                        c.POINTER(c.c_int32),
                                        c.POINTER(c.c_int64),
                                        c.POINTER(c.c_int32)]
        _lib = lib
        return _lib
