"""Build-on-demand loader for the native host runtime (ctypes ABI).

The compute path is JAX/XLA/Pallas; this package holds the HOST-side native
code (counterpart of the reference's ``csrc/`` CPU helpers): the packed-
buffer fill kernels behind ``train/batching.pack_sequences``.

The shared object compiles lazily with g++ into the package directory the
first time it is needed (no pybind11/setuptools dance; plain C ABI +
ctypes). Its file name carries a hash of ``packer.cpp``, so a build of any
other source is never loaded — whatever a copy of the tree did to mtimes,
and whichever git-ignored ``.so`` it brought along. Everything degrades
gracefully: if no compiler is available or the
build fails, callers fall back to the pure-numpy implementations —
``available()`` says which path is live. Set ``AREAL_DISABLE_NATIVE=1`` to
force the fallback (parity tests exercise both).
"""

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

import numpy as np

logger = logging.getLogger("areal_tpu.native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "packer.cpp")


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"_packer.{digest}.so")


_lib = None
_tried = False
_lock = threading.Lock()


def _build(so: str) -> bool:
    # per-process temp name: concurrent first-use builds (trainer +
    # evaluator child, multiple Slurm tasks on one FS) must not interleave
    # writes into one .tmp; os.replace is atomic, last writer wins
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp, _SRC]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, text=True, timeout=120
        )
        os.replace(tmp, so)
        return True
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            FileNotFoundError, OSError) as e:
        detail = getattr(e, "stderr", "") or str(e)
        logger.warning("native packer build failed (%s); using numpy fallback",
                       detail.strip()[:500])
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _load():
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        from areal_tpu.base import constants

        if constants.native_disabled():
            return None
        try:
            so = _so_path()
        except OSError as e:
            logger.warning("native packer source unreadable (%s)", e)
            return None
        if not os.path.exists(so) and not _build(so):
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            # a corrupt .so (e.g. built for another machine): rebuild once
            # before giving up
            if not _build(so):
                return None
            try:
                lib = ctypes.CDLL(so)
            except OSError as e:
                logger.warning("native packer load failed (%s)", e)
                return None
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        u8p = ctypes.c_void_p
        lib.plan_rows_lpt.argtypes = [i64p, ctypes.c_int64, ctypes.c_int64, i64p]
        lib.pack_copy.argtypes = [u8p, u8p, i64p, i64p, i64p, i64p,
                                  ctypes.c_int64, ctypes.c_int64, ctypes.c_int64]
        lib.pack_broadcast.argtypes = list(lib.pack_copy.argtypes)
        lib.pack_meta.argtypes = [i32p, i32p, i32p, i64p, i64p, i64p, i64p,
                                  i64p, ctypes.c_int64, ctypes.c_int64]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def plan_rows_lpt(lengths: np.ndarray, n_rows: int) -> np.ndarray:
    lib = _load()
    assert lib is not None
    lengths = np.ascontiguousarray(lengths, np.int64)
    out = np.empty(len(lengths), np.int64)
    lib.plan_rows_lpt(lengths, len(lengths), n_rows, out)
    return out


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


def pack_copy(dst, src, rows, starts, lengths, src_offsets):
    """dst [n_rows, capacity(, trailing...)] C-contiguous; src flat packed.
    `capacity` counts ELEMENTS of the trailing-item type (trailing dims fold
    into itemsize)."""
    lib = _load()
    assert lib is not None
    n_rows, capacity = dst.shape[0], dst.shape[1]
    itemsize = dst.dtype.itemsize * int(np.prod(dst.shape[2:], dtype=np.int64))
    lib.pack_copy(
        _ptr(dst), _ptr(src),
        np.ascontiguousarray(rows, np.int64),
        np.ascontiguousarray(starts, np.int64),
        np.ascontiguousarray(lengths, np.int64),
        np.ascontiguousarray(src_offsets, np.int64),
        len(rows), capacity, itemsize,
    )


def pack_broadcast(dst, src, rows, starts, lengths, src_idx):
    lib = _load()
    assert lib is not None
    capacity = dst.shape[1]
    itemsize = dst.dtype.itemsize * int(np.prod(dst.shape[2:], dtype=np.int64))
    lib.pack_broadcast(
        _ptr(dst), _ptr(src),
        np.ascontiguousarray(rows, np.int64),
        np.ascontiguousarray(starts, np.int64),
        np.ascontiguousarray(lengths, np.int64),
        np.ascontiguousarray(src_idx, np.int64),
        len(rows), capacity, itemsize,
    )


def pack_meta(segment_ids, positions, item_ids, rows, starts, lengths,
              segments, items):
    lib = _load()
    assert lib is not None
    capacity = segment_ids.shape[1]
    lib.pack_meta(
        segment_ids, positions, item_ids,
        np.ascontiguousarray(rows, np.int64),
        np.ascontiguousarray(starts, np.int64),
        np.ascontiguousarray(lengths, np.int64),
        np.ascontiguousarray(segments, np.int64),
        np.ascontiguousarray(items, np.int64),
        len(rows), capacity,
    )
