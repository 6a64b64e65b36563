"""ctypes bindings for the native parsing core.

The reference's data path is native C++ (learn/base/*_parser.h over
dmlc-core's parser machinery); this package is its equivalent: a small
C++ shared library (`src/parsers.cc`; the radix sort and gather of
`src/sort.cc`; the tcoo pack of `src/pack.cc`) built with plain g++ and
bound via ctypes (no pybind11 in the image). The Python parsers in
wormhole_tpu/data/parsers.py and the numpy body of
ops/coo_kernels.pack_tile_coo stay the reference implementations and
the fallback — `tests/test_native.py` and `tests/test_coo_kernels.py`
cross-check each pair bit-for-bit.

The library is built lazily on first use (`make -C wormhole_tpu/native`).
A build that fails is reported once on stderr with the compiler's output
and recorded in `status()`; the callers then run the Python reference
paths, and the app's start-up line says so (`native=failed: ...`).
Set WORMHOLE_NO_NATIVE=1 to choose the pure-Python path on purpose, or
WORMHOLE_NATIVE_LIB=/path/to/lib.so to load a specific build — that is
how the sanitizer CI job runs the suite against the asan/tsan/ubsan
targets of the Makefile (the race/memory checking the reference never
had, SURVEY §5).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libwormhole_native.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_failure = ""  # why the library is not loaded, once a load was tried


def _stale() -> bool:
    """True when the built library predates any native source file — a
    stale .so from an older checkout lacks newer symbols and must be
    rebuilt rather than dlopened."""
    if not os.path.exists(_SO):
        return True
    so_m = os.path.getmtime(_SO)
    srcdir = os.path.join(_DIR, "src")
    for name in os.listdir(srcdir):
        if os.path.getmtime(os.path.join(srcdir, name)) > so_m:
            return True
    return False


def _build() -> str:
    """Compile to a per-process temp name, then os.replace into place, so
    concurrent first-use builds (multi-process launches on a shared
    filesystem) can never dlopen a half-written .so. Returns "" on
    success, else what went wrong (the compiler's stderr)."""
    tmp = f"libwormhole_native.{os.getpid()}.tmp.so"
    try:
        r = subprocess.run(
            ["make", "-C", _DIR, "-s", f"OUT={tmp}"],
            capture_output=True, timeout=120)
        if r.returncode != 0:
            return (f"make exited {r.returncode}\n"
                    + r.stderr.decode("utf-8", "replace").strip())
        os.replace(os.path.join(_DIR, tmp), _SO)
        return ""
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"{type(e).__name__}: {e}"
    finally:
        try:
            os.remove(os.path.join(_DIR, tmp))
        except OSError:
            pass


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.wh_parse.restype = ctypes.c_void_p
    lib.wh_parse.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                             ctypes.c_int64]
    lib.wh_rb_size.restype = ctypes.c_int64
    lib.wh_rb_size.argtypes = [ctypes.c_void_p]
    lib.wh_rb_nnz.restype = ctypes.c_int64
    lib.wh_rb_nnz.argtypes = [ctypes.c_void_p]
    lib.wh_rb_has_value.restype = ctypes.c_int
    lib.wh_rb_has_value.argtypes = [ctypes.c_void_p]
    lib.wh_rb_error.restype = ctypes.c_int64
    lib.wh_rb_error.argtypes = [ctypes.c_void_p]
    lib.wh_rb_copy.restype = None
    lib.wh_rb_copy.argtypes = [ctypes.c_void_p] + [ctypes.c_void_p] * 4
    lib.wh_rb_free.restype = None
    lib.wh_rb_free.argtypes = [ctypes.c_void_p]
    lib.wh_cityhash64.restype = ctypes.c_uint64
    lib.wh_cityhash64.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.wh_pack_tile_coo.restype = ctypes.c_int32
    lib.wh_pack_tile_coo.argtypes = ([ctypes.c_void_p] * 3
                                     + [ctypes.c_int64] * 7
                                     + [ctypes.c_void_p] * 10)
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _tried, _failure
    if _lib is not None:
        return _lib
    if os.environ.get("WORMHOLE_NO_NATIVE"):
        return None
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        override = os.environ.get("WORMHOLE_NATIVE_LIB")
        if override:
            # an explicit override must fail LOUDLY: silently returning
            # None would make every native test skip and a sanitizer CI
            # job pass while testing nothing
            try:
                _lib = _bind(ctypes.CDLL(override))
            except (OSError, AttributeError) as e:
                raise RuntimeError(
                    f"WORMHOLE_NATIVE_LIB={override!r} failed to load or "
                    f"is missing symbols: {e}") from e
            return _lib
        if _stale():
            _failure = _build()
        if not _failure:
            try:
                _lib = _bind(ctypes.CDLL(_SO))
            except OSError as e:
                _failure = f"dlopen failed: {e}"
        if _failure:
            import sys

            sys.stderr.write(
                "[native] libwormhole_native.so unavailable — parsing, "
                "hashing, radix sort and the tcoo pack fall back to the "
                f"Python/numpy reference paths:\n{_failure}\n")
        return _lib


def status() -> str:
    """Whether the C++ core serves this process: `loaded`, `disabled`
    (WORMHOLE_NO_NATIVE) or `failed: <reason>` (the full compiler output
    went to stderr). Apps print it at start-up and chip_smoke.py
    requires `loaded`. Triggers the lazy build/load."""
    if get_lib() is not None:
        return "loaded"
    if os.environ.get("WORMHOLE_NO_NATIVE"):
        return "disabled"
    return "failed: " + _failure.splitlines()[0]


def available() -> bool:
    return get_lib() is not None


_FORMATS = {"libsvm", "criteo", "criteo_test", "adfea"}


def parse_text(text: str, fmt: str):
    """Native parse of a text chunk -> RowBlock; None when the native path
    can't serve this request (lib missing or unknown format)."""
    lib = get_lib()
    if lib is None or fmt not in _FORMATS:
        return None
    from wormhole_tpu.data.rowblock import RowBlock

    data = text.encode("utf-8")
    h = lib.wh_parse(fmt.encode(), data, len(data))
    if not h:
        return None
    try:
        err = lib.wh_rb_error(h)
        if err >= 0:
            raise ValueError(
                f"malformed {fmt} input at row {err} (native parser)")
        n = lib.wh_rb_size(h)
        nnz = lib.wh_rb_nnz(h)
        has_val = bool(lib.wh_rb_has_value(h))
        label = np.empty(n, np.float32)
        offset = np.empty(n + 1, np.int64)
        index = np.empty(nnz, np.uint64)
        value = np.empty(nnz, np.float32) if has_val else None
        lib.wh_rb_copy(
            h,
            label.ctypes.data_as(ctypes.c_void_p),
            offset.ctypes.data_as(ctypes.c_void_p),
            index.ctypes.data_as(ctypes.c_void_p),
            value.ctypes.data_as(ctypes.c_void_p) if has_val else None,
        )
        return RowBlock(label=label, offset=offset, index=index, value=value)
    finally:
        lib.wh_rb_free(h)


def cityhash64(data) -> int:
    """Native CityHash64; falls back to the Python implementation."""
    lib = get_lib()
    s = data.encode() if isinstance(data, str) else bytes(data)
    if lib is None:
        from wormhole_tpu.ops.hashing import cityhash64 as py

        return py(s)
    return int(lib.wh_cityhash64(s, len(s)))


def radix_argsort(keys):
    """Stable argsort of uint32/uint64 keys via the native LSD radix sort;
    returns int32 order, or None when the native path is unavailable
    (callers fall back to np.argsort)."""
    lib = get_lib()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys)
    n = keys.shape[0]
    if n >= 2 ** 31:
        return None
    out = np.empty(n, np.int32)
    if keys.dtype == np.uint32:
        fn = lib.wh_argsort_u32
    elif keys.dtype == np.uint64:
        fn = lib.wh_argsort_u64
    elif keys.dtype == np.int32 and (n == 0 or keys.min() >= 0):
        keys = keys.view(np.uint32)
        fn = lib.wh_argsort_u32
    elif keys.dtype == np.int64 and (n == 0 or keys.min() >= 0):
        keys = keys.view(np.uint64)
        fn = lib.wh_argsort_u64
    else:
        return None
    fn.restype = None
    fn(keys.ctypes.data_as(ctypes.c_void_p), ctypes.c_int64(n),
       out.ctypes.data_as(ctypes.c_void_p))
    return out


def gather(src, order):
    """out[i] = src[order[i]] via the parallel native core for 4/8-byte
    element types; None when unavailable (callers use numpy indexing)."""
    lib = get_lib()
    if lib is None:
        return None
    src = np.ascontiguousarray(src)
    if src.ndim != 1 or len(order) >= 2 ** 31 or src.shape[0] >= 2 ** 31:
        return None  # int32 index domain only; callers fall back to numpy
    order = np.ascontiguousarray(order, dtype=np.int32)
    n = order.shape[0]
    if src.dtype.itemsize == 4:
        fn = lib.wh_gather_32
    elif src.dtype.itemsize == 8:
        fn = lib.wh_gather_64
    else:
        return None
    out = np.empty(n, src.dtype)
    fn.restype = None
    fn(src.ctypes.data_as(ctypes.c_void_p),
       order.ctypes.data_as(ctypes.c_void_p), ctypes.c_int64(n),
       out.ctypes.data_as(ctypes.c_void_p))
    return out


def pack_tile_coo(idx, seg, val, num_buckets: int, u_cap: int,
                  capacity, tile: int, blk: int, blk_u: int):
    """The whole tcoo pack in one call that holds no interpreter lock
    (src/pack.cc): one radix sort of the ids, then every array of
    ops/coo_kernels.TileCOO written in that order, bit-equal to the numpy
    body of ops/coo_kernels.pack_tile_coo. Returns a dict of those
    arrays and counts, or None when the library is missing or the batch
    is outside the pass's domain (ids not int32 in [0, num_buckets),
    sizes of 2^31 or more): the caller then runs the numpy body."""
    lib = get_lib()
    if (lib is None or not isinstance(idx, np.ndarray)
            or idx.dtype != np.int32 or idx.ndim != 1):
        return None
    n = idx.shape[0]
    # capacity None: that of the entries kept, at most n
    P = ((n if capacity is None else capacity) // blk + u_cap // tile) * blk
    if (not 0 < num_buckets < 2 ** 31 or not 0 < u_cap < 2 ** 31
            or max(n, P) >= 2 ** 31 or P < 0):
        return None
    idx = np.ascontiguousarray(idx)
    seg = np.ascontiguousarray(seg, np.int32)
    val = np.ascontiguousarray(val, np.float32)
    if seg.shape != idx.shape or val.shape != idx.shape:
        return None
    i32 = np.int32
    out = dict(  # in the order wh_pack_tile_coo takes them
        uniq=np.empty(u_cap, i32), tmap_u=np.empty(u_cap // blk_u, i32),
        first_u=np.empty(u_cap // blk_u, i32),
        last_u=np.empty(u_cap // blk_u, i32),
        idx=np.empty(P, i32), seg=np.empty(P, i32),
        val=np.empty(P, np.float32), tmap=np.empty(P // blk, i32),
        first=np.empty(P // blk, i32))
    counts = np.zeros(4, np.int64)

    def ptr(a):
        return a.ctypes.data_as(ctypes.c_void_p)

    rc = lib.wh_pack_tile_coo(
        ptr(idx), ptr(seg), ptr(val), n, num_buckets, u_cap,
        -1 if capacity is None else capacity, tile, blk, blk_u,
        *(ptr(a) for a in out.values()), ptr(counts))
    if rc != 0:
        return None
    if counts[3] != P:  # capacity None and entries cut: a shorter stream
        for k, per in (("idx", 1), ("seg", 1), ("val", 1), ("tmap", blk),
                       ("first", blk)):
            out[k] = out[k][:counts[3] // per]
    out.update(zip(("num_uniq", "dropped_uniq", "dropped_nnz"),
                   counts.tolist()))
    return out
