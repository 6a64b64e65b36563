"""Packed-batch epoch cache: skip the host pack from epoch 2 onward.

PERF.md's round-5 verdict: at the headline linear shape the device step
is ~17 ms but each batch costs the loader ~100 ms of sort + localize
pack — and that host work is bit-identical every epoch (the pack is a
pure function of the batch bytes and the pack parameters). This module
is the tf.data-style cache (Murray et al., VLDB 2021 §3.2 "cache") for
that work: prepared batches are stored under a content/config
fingerprint and replayed on later epochs, so the loader threads feed
the device from memory (or mmap'd disk) instead of re-sorting 2.5M keys
per batch.

Two tiers:

- an in-memory tier holding the prepared objects themselves, LRU-evicted
  against a byte budget (``WH_PACK_CACHE_MB``, default 512). Consumers
  treat prepared batches as read-only (they only ``jnp.asarray`` /
  ``device_put`` them), so handing back the same object is safe and
  bit-identical by construction;
- an optional disk tier (``WH_PACK_CACHE_DIR``): each entry is one file
  written atomically (temp + ``os.replace``) and loaded mmap-backed, so
  a cache shared across runs never serves a half-written entry and a
  100-GB cache costs no RSS until batches are actually consumed.

Keying: callers build keys with :func:`fingerprint` from (file part
identity + mtime/size, batch index within the part, pack parameters,
learner pack version). A learner that cannot replay a pack bit-
identically (e.g. difacto's train pack, whose admission depends on the
evolving count mirror) declines by returning ``None`` from its
``pack_cache_token`` — the loader then simply packs as before.

Everything is default-off: no env knob set means no cache object exists
and the loader path is byte-for-byte the pre-cache code.
"""

from __future__ import annotations

import dataclasses
import hashlib
import io
import logging
import os
import pickle
import tempfile
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Iterable, Iterator, Optional

import numpy as np

from wormhole_tpu.obs import trace as _trace
from wormhole_tpu.obs.metrics import REGISTRY

log = logging.getLogger(__name__)

#: bump when the on-disk entry format or the flatten skeleton changes
FORMAT_VERSION = 1

_MAGIC = b"WHPK%d\n" % FORMAT_VERSION

_HITS = REGISTRY.counter("pack_cache.hits")
_MISSES = REGISTRY.counter("pack_cache.misses")
_DISK_HITS = REGISTRY.counter("pack_cache.disk_hits")
_EVICTS = REGISTRY.counter("pack_cache.evictions")
_CORRUPT = REGISTRY.counter("pack_cache.corrupt")
_BYTES = REGISTRY.gauge("pack_cache.bytes")


def fingerprint(*parts) -> str:
    """Stable hex digest of a tuple of primitives / nested tuples.

    Cheap and collision-safe for cache keying; callers include every
    input that changes the pack output (file identity + mtime + size,
    batch index, pack geometry, learner pack version)."""
    h = hashlib.blake2b(repr(parts).encode(), digest_size=16)
    return h.hexdigest()


def file_stamp(path: str) -> tuple:
    """(size, mtime_ns) content stamp so an overwritten input file can
    never serve stale packs. Missing files stamp as None (remote URIs:
    the caller should fold its own version into the key instead)."""
    try:
        st = os.stat(path)
        return (st.st_size, st.st_mtime_ns)
    except OSError:
        return (None, None)


# ------------------------------------------------------- pytree plumbing
# Prepared batches are nested tuples/dataclasses of numpy arrays plus
# static metadata (SortedCOO, TileCOO, DeviceBatch, plain tuples...).
# _flatten pulls the array leaves out and leaves a picklable skeleton;
# _unflatten rebuilds the object around a fresh (possibly mmap-backed)
# leaf list. Device (jax) arrays are snapshotted to host numpy — the
# consumer re-stages them anyway.

_ARR = "__whpk_arr__"


def _flatten(obj, leaves: list) -> Any:
    if isinstance(obj, np.ndarray):
        leaves.append(obj)
        return (_ARR, len(leaves) - 1)
    if obj is None or isinstance(obj, (bool, int, float, str, bytes,
                                       np.integer, np.floating)):
        return obj
    if isinstance(obj, tuple):
        return ("__tuple__", [_flatten(x, leaves) for x in obj])
    if isinstance(obj, list):
        return ("__list__", [_flatten(x, leaves) for x in obj])
    if isinstance(obj, dict):
        return ("__dict__", [(k, _flatten(v, leaves))
                             for k, v in obj.items()])
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return ("__dc__", type(obj),
                [(f.name, _flatten(getattr(obj, f.name), leaves))
                 for f in dataclasses.fields(obj)])
    if hasattr(obj, "__array__"):  # jax.Array and friends -> host snapshot
        leaves.append(np.asarray(obj))
        return (_ARR, len(leaves) - 1)
    raise TypeError(f"pack_cache cannot serialize {type(obj)!r}")


def _unflatten(skel, leaves: list) -> Any:
    if isinstance(skel, tuple) and skel and skel[0] == _ARR:
        return leaves[skel[1]]
    if isinstance(skel, tuple) and skel and skel[0] == "__tuple__":
        return tuple(_unflatten(x, leaves) for x in skel[1])
    if isinstance(skel, tuple) and skel and skel[0] == "__list__":
        return [_unflatten(x, leaves) for x in skel[1]]
    if isinstance(skel, tuple) and skel and skel[0] == "__dict__":
        return {k: _unflatten(v, leaves) for k, v in skel[1]}
    if isinstance(skel, tuple) and skel and skel[0] == "__dc__":
        _, cls, fields = skel
        return cls(**{k: _unflatten(v, leaves) for k, v in fields})
    return skel


def nbytes_of(obj) -> int:
    """Approximate footprint of a prepared batch: the array leaves plus
    a small per-entry constant for the skeleton."""
    leaves: list = []
    _flatten(obj, leaves)
    return sum(a.nbytes for a in leaves) + 512


# ------------------------------------------------------------- disk tier
def _encode(obj) -> bytes:
    leaves: list = []
    skel = _flatten(obj, leaves)
    manifest = []
    off = 0
    for a in leaves:
        a = np.ascontiguousarray(a)
        manifest.append((str(a.dtype), a.shape, off, a.nbytes))
        off += a.nbytes
    head = pickle.dumps({"skel": skel, "manifest": manifest,
                         "data_bytes": off})
    buf = io.BytesIO()
    buf.write(_MAGIC)
    buf.write(len(head).to_bytes(8, "little"))
    buf.write(head)
    for a in leaves:
        buf.write(np.ascontiguousarray(a).tobytes())
    return buf.getvalue()


def _decode_file(path: str, mmap: bool = True):
    """Load one entry; raises on any structural damage (magic, header
    pickle, or file-size mismatch) — the caller treats that as a miss
    and deletes the file so the batch is simply repacked."""
    with open(path, "rb") as fh:
        magic = fh.read(len(_MAGIC))
        if magic != _MAGIC:
            raise ValueError(f"bad pack-cache magic in {path}")
        head_len = int.from_bytes(fh.read(8), "little")
        if head_len <= 0 or head_len > 1 << 30:
            raise ValueError("implausible pack-cache header length")
        head = pickle.loads(fh.read(head_len))
        data_start = len(_MAGIC) + 8 + head_len
    expect = data_start + head["data_bytes"]
    if os.path.getsize(path) != expect:
        raise ValueError(f"truncated pack-cache entry {path}")
    leaves = []
    for dtype, shape, off, nb in head["manifest"]:
        if mmap and nb:
            a = np.memmap(path, dtype=np.dtype(dtype), mode="r",
                          offset=data_start + off, shape=tuple(shape))
        else:
            with open(path, "rb") as fh:
                fh.seek(data_start + off)
                a = np.frombuffer(fh.read(nb), dtype=np.dtype(dtype)
                                  ).reshape(tuple(shape))
        leaves.append(a)
    return _unflatten(head["skel"], leaves)


class PackCache:
    """Two-tier packed-batch cache. Thread-safe: loader threads get/put
    concurrently; the lock covers only the in-memory index, disk I/O
    runs outside it (atomic temp+rename makes concurrent same-key
    writers harmless — last rename wins with identical bytes)."""

    def __init__(self, mem_bytes: int = 512 << 20,
                 disk_dir: Optional[str] = None, mmap: bool = True):
        self.mem_bytes = int(mem_bytes)
        self.disk_dir = disk_dir
        self.mmap = mmap
        self._lock = threading.Lock()
        self._mem: OrderedDict[str, tuple[Any, int]] = OrderedDict()
        self._mem_used = 0
        self.hits = 0
        self.misses = 0
        self.disk_hits = 0
        if disk_dir:
            os.makedirs(disk_dir, exist_ok=True)

    # ---------------------------------------------------------------- get
    def get(self, key: str):
        """The cached object or None. Memory first, then disk (a disk
        hit is promoted into the memory tier)."""
        return self.lookup(key)[0]

    def lookup(self, key: str) -> tuple:
        """(the cached object or None, the tier that answered: "mem",
        "disk" or "miss")."""
        with self._lock:
            got = self._mem.get(key)
            if got is not None:
                self._mem.move_to_end(key)
                self.hits += 1
                _HITS.inc()
                return got[0], "mem"
        if self.disk_dir:
            path = self._path(key)
            try:
                if os.path.exists(path):
                    obj = _decode_file(path, mmap=self.mmap)
                    with self._lock:
                        self.hits += 1
                        self.disk_hits += 1
                    _HITS.inc()
                    _DISK_HITS.inc()
                    self._mem_insert(key, obj, nbytes_of(obj))
                    return obj, "disk"
            except Exception as e:
                _CORRUPT.inc()
                log.warning("pack cache: dropping corrupt entry %s (%s); "
                            "the batch will be repacked", path, e)
                try:
                    os.remove(path)
                except OSError:
                    pass
        with self._lock:
            self.misses += 1
        _MISSES.inc()
        return None, "miss"

    # ---------------------------------------------------------------- put
    def put(self, key: str, obj) -> bool:
        """Insert into both tiers. Returns False (and caches nothing) if
        the object holds leaves the flattener does not understand —
        callers then just skip caching that batch."""
        try:
            nb = nbytes_of(obj)
        except TypeError as e:
            log.warning("pack cache: uncacheable batch (%s)", e)
            return False
        self._mem_insert(key, obj, nb)
        if self.disk_dir:
            path = self._path(key)
            if not os.path.exists(path):
                try:
                    blob = _encode(obj)
                    fd, tmp = tempfile.mkstemp(dir=self.disk_dir,
                                               prefix=".whpk_tmp_")
                    try:
                        with os.fdopen(fd, "wb") as fh:
                            fh.write(blob)
                        os.replace(tmp, path)  # atomic publish
                    except BaseException:
                        try:
                            os.remove(tmp)
                        except OSError:
                            pass
                        raise
                except Exception as e:
                    log.warning("pack cache: disk spill failed for %s "
                                "(%s)", key, e)
        return True

    def _mem_insert(self, key: str, obj, nb: int) -> None:
        if nb > self.mem_bytes:
            return  # larger than the whole budget: disk-tier only
        with self._lock:
            old = self._mem.pop(key, None)
            if old is not None:
                self._mem_used -= old[1]
            self._mem[key] = (obj, nb)
            self._mem_used += nb
            while self._mem_used > self.mem_bytes and self._mem:
                _, (_, enb) = self._mem.popitem(last=False)
                self._mem_used -= enb
                _EVICTS.inc()
            _BYTES.set(self._mem_used)

    def _path(self, key: str) -> str:
        return os.path.join(self.disk_dir, f"{key}.whpack")

    # -------------------------------------------------------------- stats
    def stats(self) -> dict:
        with self._lock:
            total = self.hits + self.misses
            return {
                "hits": self.hits,
                "misses": self.misses,
                "disk_hits": self.disk_hits,
                "hit_rate": self.hits / total if total else 0.0,
                "mem_bytes": self._mem_used,
                "mem_entries": len(self._mem),
            }

    def clear_memory(self) -> None:
        with self._lock:
            self._mem.clear()
            self._mem_used = 0
            _BYTES.set(0)


def from_env() -> Optional[PackCache]:
    """The run's cache per env knobs, or None (the default-off path:
    no object, no code-path change). WH_PACK_CACHE=1 enables the
    in-memory tier; WH_PACK_CACHE_DIR enables (and implies) the disk
    tier; WH_PACK_CACHE_MB sizes the memory tier (default 512)."""
    disk = os.environ.get("WH_PACK_CACHE_DIR") or None
    on = os.environ.get("WH_PACK_CACHE", "").lower() not in (
        "", "0", "false", "off")
    if not on and not disk:
        return None
    mem_mb = int(os.environ.get("WH_PACK_CACHE_MB", "512"))
    return PackCache(mem_bytes=mem_mb << 20, disk_dir=disk)


# ---------------------------------------------------- whole-part replay
def iter_part_cached(cache: Optional[PackCache], part_key,
                     raw_iter_fn: Callable[[], Iterable],
                     prepare_fn: Callable[[Any], Any], part: int = -1,
                     fetched: Optional[Callable[[float], None]] = None
                     ) -> Iterator:
    """Iterate one file part's prepared batches through the cache.

    ``part_key`` identifies the part AND the full pack configuration
    (fingerprint input tuple); batch ``i`` lives under
    fingerprint(part_key, i) and a terminal count entry under
    fingerprint(part_key, "n") records how many batches the part
    yields. On a warm epoch the part is replayed entirely from the
    cache — the source file is never opened, no parse and no pack run.

    Degradation is per-batch: if an entry was evicted (or a disk entry
    corrupted) mid-replay, the source iterator is reopened and fast-
    forwarded — already-served batches are re-parsed but NOT re-packed
    or re-yielded — and filling resumes from the gap.

    A replayed batch's fetch lies under span ``loader.source``
    (``part``, ``i``, ``cached=1``, the ``tier`` that answered), the
    name the solver gives its wait for a parsed block: a loader's wait
    for its next item, whichever the source. ``fetched`` is told the
    seconds of every fetch that gave a batch.

    With ``cache`` or ``part_key`` None this is exactly the uncached
    loop (the default-off path)."""
    if cache is None or part_key is None:
        for blk in raw_iter_fn():
            yield prepare_fn(blk)
        return
    start = 0
    n = cache.get(fingerprint(part_key, "n"))
    if n is not None:
        for i in range(int(n)):
            t0 = time.perf_counter()
            with _trace.span("loader.source", cat="loader", part=part,
                             i=i, cached=1) as fetch:
                b, tier = cache.lookup(fingerprint(part_key, i))
                fetch.set(tier=tier)
            if b is None:
                break
            if fetched is not None:
                fetched(time.perf_counter() - t0)
            yield b
            start = i + 1
        else:
            return
    count = start
    for i, blk in enumerate(raw_iter_fn()):
        if i < start:
            continue  # already served from cache before the gap
        b = prepare_fn(blk)
        cache.put(fingerprint(part_key, i), b)
        count = i + 1
        yield b
    cache.put(fingerprint(part_key, "n"), count)
