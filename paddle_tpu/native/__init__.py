"""Native (C++) runtime components, loaded via ctypes.

The reference keeps its data plane native (RecordIO chunks for the Go
master, PyDataProvider2's C++ prefetch queue); this package does the same
for the TPU framework: `recordio.cc` is compiled on first use with the
ambient g++ into a shared library (no pybind11 in this environment — the
C ABI + ctypes is the binding). There is no pure-Python stand-in: on a
machine without a C++ compiler every entry point raises RuntimeError
saying so.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_HERE, "_build")
_SRC = os.path.join(_HERE, "recordio.cc")
_INFER_SRC = os.path.join(_HERE, "inference.cc")
_lock = threading.Lock()
_lib = None
_build_error = None
_infer_lib = None
_infer_error = None


def _built(src: str, stem: str) -> str:
    """Path of the shared library for `src`, compiling it unless this
    exact source was already built here. The file name carries a hash
    of the source: `_build/` is ignored by git but travels with a copy
    of the tree, where modification times say nothing, so a library is
    only ever loaded for the source it was built from. The compiler
    writes to a name of this process's own and the finished file is
    renamed into place, so builders racing for one name (test workers)
    each publish a whole library and none loads a half-written one."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(_BUILD_DIR, "%s-%s.so" % (stem, digest))
    if os.path.exists(so):
        return so
    os.makedirs(_BUILD_DIR, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=_BUILD_DIR, prefix=stem + "-",
                               suffix=".tmp")
    os.close(fd)
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
             src, "-o", tmp],
            check=True, capture_output=True)
    except FileNotFoundError:
        raise RuntimeError(
            "no C++ compiler: g++ is not on PATH, and %s has to be "
            "compiled before use" % os.path.basename(src)) from None
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            "g++ failed on %s:\n%s" % (
                os.path.basename(src),
                e.stderr.decode(errors="replace")[-2000:])) from None
    else:
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return so


def lib():
    """The loaded shared library, building it on first use. Raises
    RuntimeError when no toolchain is available."""
    global _lib, _build_error
    with _lock:
        if _lib is not None:
            return _lib
        if _build_error is not None:
            raise RuntimeError("native build failed earlier: %s" % _build_error)
        try:
            L = ctypes.CDLL(_built(_SRC, "librecordio"))
        except (OSError, RuntimeError) as e:  # keep for later callers
            _build_error = e
            raise RuntimeError("cannot build/load native recordio: %s" % e)
        L.rio_writer_open.restype = ctypes.c_void_p
        L.rio_writer_open.argtypes = [ctypes.c_char_p]
        L.rio_write.restype = ctypes.c_int
        L.rio_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
        L.rio_writer_close.argtypes = [ctypes.c_void_p]
        L.rio_open.restype = ctypes.c_void_p
        L.rio_open.argtypes = [ctypes.c_char_p]
        L.rio_next.restype = ctypes.c_int64
        L.rio_next.argtypes = [ctypes.c_void_p]
        L.rio_data.restype = ctypes.POINTER(ctypes.c_uint8)
        L.rio_data.argtypes = [ctypes.c_void_p]
        L.rio_close.argtypes = [ctypes.c_void_p]
        L.pq_open.restype = ctypes.c_void_p
        L.pq_open.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_int,
        ]
        L.pq_next.restype = ctypes.c_int64
        L.pq_next.argtypes = [ctypes.c_void_p]
        L.pq_data.restype = ctypes.POINTER(ctypes.c_uint8)
        L.pq_data.argtypes = [ctypes.c_void_p]
        L.pq_close.argtypes = [ctypes.c_void_p]
        _lib = L
        return _lib


def available() -> bool:
    try:
        lib()
        return True
    except RuntimeError:
        return False


def infer_lib_path() -> str:
    """Build (if needed) and return the path of the native inference
    runner shared library — usable from ANY language via dlopen; no
    paddle_tpu import required at load/forward time (capi parity,
    reference capi/gradient_machine.h:36,73)."""
    global _infer_error
    with _lock:
        if _infer_error is not None:
            raise RuntimeError(
                "native inference build failed earlier: %s" % _infer_error
            )
        try:
            return _built(_INFER_SRC, "libptpu_infer")
        except (OSError, RuntimeError) as e:
            _infer_error = e
            raise RuntimeError("cannot build native inference: %s" % e)


def infer_lib():
    """ctypes handle to the native inference runner with signatures set."""
    global _infer_lib
    path = infer_lib_path()
    with _lock:
        if _infer_lib is not None:
            return _infer_lib
        L = ctypes.CDLL(path)
        L.ptpu_infer_create.restype = ctypes.c_void_p
        L.ptpu_infer_create.argtypes = [ctypes.c_char_p]
        L.ptpu_infer_num_feeds.argtypes = [ctypes.c_void_p]
        L.ptpu_infer_feed_name.restype = ctypes.c_char_p
        L.ptpu_infer_feed_name.argtypes = [ctypes.c_void_p, ctypes.c_int]
        L.ptpu_infer_num_fetch.argtypes = [ctypes.c_void_p]
        L.ptpu_infer_fetch_name.restype = ctypes.c_char_p
        L.ptpu_infer_fetch_name.argtypes = [ctypes.c_void_p, ctypes.c_int]
        L.ptpu_infer_set_input.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ]
        L.ptpu_infer_forward.argtypes = [ctypes.c_void_p]
        L.ptpu_infer_error.restype = ctypes.c_char_p
        L.ptpu_infer_error.argtypes = [ctypes.c_void_p]
        L.ptpu_infer_out_rank.argtypes = [ctypes.c_void_p, ctypes.c_int]
        L.ptpu_infer_out_shape.restype = ctypes.POINTER(ctypes.c_int64)
        L.ptpu_infer_out_shape.argtypes = [ctypes.c_void_p, ctypes.c_int]
        L.ptpu_infer_out_data.restype = ctypes.POINTER(ctypes.c_float)
        L.ptpu_infer_out_data.argtypes = [ctypes.c_void_p, ctypes.c_int]
        L.ptpu_infer_out_lod_len.argtypes = [ctypes.c_void_p, ctypes.c_int]
        L.ptpu_infer_out_lod.restype = ctypes.POINTER(ctypes.c_int64)
        L.ptpu_infer_out_lod.argtypes = [ctypes.c_void_p, ctypes.c_int]
        L.ptpu_infer_set_input_lod.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ]
        L.ptpu_infer_destroy.argtypes = [ctypes.c_void_p]
        _infer_lib = L
        return _infer_lib


class InferenceRunner(object):
    """Convenience Python wrapper over the C ABI (the C ABI itself is the
    deliverable; this class just saves ctypes boilerplate in-process)."""

    def __init__(self, dirname: str):
        import numpy as np

        self._np = np
        self._L = infer_lib()
        self._h = self._L.ptpu_infer_create(dirname.encode())
        if not self._h:
            raise IOError("cannot load inference bundle at %s" % dirname)

    @property
    def feed_names(self):
        L, h = self._L, self._h
        return [
            L.ptpu_infer_feed_name(h, i).decode()
            for i in range(L.ptpu_infer_num_feeds(h))
        ]

    @property
    def fetch_names(self):
        L, h = self._L, self._h
        return [
            L.ptpu_infer_fetch_name(h, i).decode()
            for i in range(L.ptpu_infer_num_fetch(h))
        ]

    def run(self, feeds: dict, lods: dict = None, return_lod: bool = False):
        """feeds: name -> array. lods: name -> offsets (ragged inputs).
        With return_lod, returns (outs, lods_out) where lods_out[k] is
        the k-th fetch's sequence offsets ([] when dense)."""
        np = self._np
        L, h = self._L, self._h
        for name, arr in feeds.items():
            arr = np.asarray(arr)
            if arr.dtype.kind in "iu":
                arr = np.ascontiguousarray(arr, np.int64)
                code = 1
            else:
                arr = np.ascontiguousarray(arr, np.float32)
                code = 0
            shape = (ctypes.c_int64 * arr.ndim)(*arr.shape)
            L.ptpu_infer_set_input(
                h, name.encode(),
                arr.ctypes.data_as(ctypes.c_void_p), code, shape, arr.ndim,
            )
        for name, off in (lods or {}).items():
            off = np.ascontiguousarray(off, np.int64)
            rc = L.ptpu_infer_set_input_lod(
                h, name.encode(),
                off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                len(off),
            )
            if rc != 0:
                raise KeyError(
                    "lod for unknown input %r (set its tensor first)"
                    % name
                )
        if L.ptpu_infer_forward(h) != 0:
            raise RuntimeError(
                "native forward failed: %s"
                % L.ptpu_infer_error(h).decode()
            )
        outs = []
        lods_out = []
        for i in range(L.ptpu_infer_num_fetch(h)):
            rank = L.ptpu_infer_out_rank(h, i)
            shape = [L.ptpu_infer_out_shape(h, i)[k] for k in range(rank)]
            n = int(np.prod(shape)) if shape else 1
            data = np.ctypeslib.as_array(
                L.ptpu_infer_out_data(h, i), shape=(n,)
            ).copy()
            outs.append(data.reshape(shape))
            if return_lod:
                ll = L.ptpu_infer_out_lod_len(h, i)
                ptr = L.ptpu_infer_out_lod(h, i) if ll else None
                lods_out.append([ptr[k] for k in range(ll)] if ll else [])
        return (outs, lods_out) if return_lod else outs

    def close(self):
        if self._h:
            self._L.ptpu_infer_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# ---------------------------------------------------------------------
# Python surface
# ---------------------------------------------------------------------


class RecordWriter(object):
    """Length-prefixed CRC-checked record file writer.

    NOTE: this is a bespoke on-disk format ([u32 len][u32 crc32][payload]
    per record, recordio.cc), NOT the reference RecordIO chunk layout
    (magic + compressed multi-record chunks, recordio library used by the
    Go master). Files are not interchangeable with reference-produced
    .recordio data; the capability being reproduced is the native
    record-stream + prefetch-queue data plane, not the wire format."""

    def __init__(self, path: str):
        self._h = lib().rio_writer_open(path.encode())
        if not self._h:
            raise IOError("cannot open %s for writing" % path)

    def write(self, payload: bytes):
        if lib().rio_write(self._h, payload, len(payload)) != 0:
            raise IOError("record write failed")

    def close(self):
        if self._h:
            lib().rio_writer_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


def read_records(path: str):
    """Synchronous record iterator."""
    L = lib()
    h = L.rio_open(path.encode())
    if not h:
        raise IOError("cannot open %s" % path)
    try:
        while True:
            n = L.rio_next(h)
            if n <= 0:
                return
            yield ctypes.string_at(L.rio_data(h), n)
    finally:
        L.rio_close(h)


class PrefetchReader(object):
    """Async prefetch over a list of record files: a native thread streams
    records into a bounded queue (PyDataProvider2 double-buffer parity);
    iteration pops from the queue."""

    def __init__(self, paths, capacity: int = 64):
        L = lib()
        arr = (ctypes.c_char_p * len(paths))(
            *[p.encode() for p in paths]
        )
        self._h = L.pq_open(arr, len(paths), capacity)
        self._L = L

    def __iter__(self):
        return self

    def __next__(self):
        n = self._L.pq_next(self._h)
        if n <= 0:
            self.close()
            raise StopIteration
        return ctypes.string_at(self._L.pq_data(self._h), n)

    def close(self):
        if self._h:
            self._L.pq_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
