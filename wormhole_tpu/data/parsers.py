"""Text parsers: libsvm, Criteo CTR, adfea -> RowBlock.

Python reference implementations. The native C++ core
(wormhole_tpu/native/src/parsers.cc) is the production path: `parse_text`
hands it each chunk, tests/test_native.py holds it bit-identical to
these, and these serve when its library is missing.

Format parity with the reference:
- libsvm "label idx:val ..."                 (dmlc-core LibSVMParser)
- criteo tab-separated, 13 int + 26 categorical, features hashed with
  CityHash64 and field-packed (reference learn/base/criteo_parser.h:38-88)
- adfea "lineid #feat label fid:gid ..."     (learn/base/adfea_parser.h:35-90)
"""

from __future__ import annotations

from typing import Iterator, Optional

import numpy as np

from wormhole_tpu.data.rowblock import RowBlock
from wormhole_tpu.ops.hashing import cityhash64

_M = (1 << 64) - 1


def parse_libsvm(text: str) -> RowBlock:
    labels: list[float] = []
    offsets: list[int] = [0]
    idx: list[int] = []
    val: list[float] = []
    has_val = False
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        parts = line.split()
        labels.append(float(parts[0]))
        for tok in parts[1:]:
            if ":" in tok:
                k, v = tok.split(":", 1)
                idx.append(int(k))
                v = float(v)
                val.append(v)
                if v != 1.0:
                    has_val = True
            else:
                idx.append(int(tok))
                val.append(1.0)
        offsets.append(len(idx))
    return RowBlock(
        label=np.asarray(labels, dtype=np.float32),
        offset=np.asarray(offsets, dtype=np.int64),
        index=np.asarray(idx, dtype=np.uint64),
        # binary compaction: drop the all-ones value array
        # (reference minibatch_iter.h:114-116)
        value=np.asarray(val, dtype=np.float32) if has_val else None,
    )


def _criteo_key(token: str, field: int) -> int:
    return ((cityhash64(token) >> 10) | ((field & 0x3FF) << 54)) & _M


def parse_criteo(text: str, has_label: bool = True) -> RowBlock:
    """Criteo CTR lines: label \\t I1..I13 \\t C1..C26 (train) or no label
    (test). Integer features are hashed as "<field>/<value>" is NOT the
    reference scheme — the reference hashes the raw token text and packs the
    field id into the top 10 bits (criteo_parser.h:69-82); we do the same.
    Missing fields are skipped. All features are binary (value 1)."""
    labels: list[float] = []
    offsets: list[int] = [0]
    idx: list[int] = []
    for line in text.splitlines():
        if not line.strip():
            continue
        toks = line.rstrip("\n").split("\t")
        pos = 0
        if has_label:
            labels.append(float(toks[0]))
            pos = 1
        else:
            labels.append(0.0)
        for field, tok in enumerate(toks[pos:]):
            if field >= 39:
                break
            if tok == "":
                continue
            idx.append(_criteo_key(tok, field))
        offsets.append(len(idx))
    return RowBlock(
        label=np.asarray(labels, dtype=np.float32),
        offset=np.asarray(offsets, dtype=np.int64),
        index=np.asarray(idx, dtype=np.uint64),
        value=None,
    )


def parse_adfea(text: str) -> RowBlock:
    """adfea: "lineid num_features label fid:gid fid:gid ...". The group id
    is packed into the top 10 bits like criteo (adfea_parser.h:56-64);
    labels are 0/1 like the other parsers (adfea_parser.h emits 0/1)."""
    labels: list[float] = []
    offsets: list[int] = [0]
    idx: list[int] = []
    for line in text.splitlines():
        parts = line.split()
        if len(parts) < 3:
            continue
        labels.append(1.0 if float(parts[2]) > 0 else 0.0)
        for tok in parts[3:]:
            if ":" in tok:
                fid, gid = tok.split(":", 1)
                key = ((int(fid) >> 10) | ((int(gid) & 0x3FF) << 54)) & _M
            else:
                key = int(tok)
            idx.append(key)
        offsets.append(len(idx))
    return RowBlock(
        label=np.asarray(labels, dtype=np.float32),
        offset=np.asarray(offsets, dtype=np.int64),
        index=np.asarray(idx, dtype=np.uint64),
        value=None,
    )


_PARSERS = {
    "libsvm": lambda t: parse_libsvm(t),
    "criteo": lambda t: parse_criteo(t, has_label=True),
    "criteo_test": lambda t: parse_criteo(t, has_label=False),
    "adfea": lambda t: parse_adfea(t),
}


def parse_text(text: str, fmt: str) -> RowBlock:
    """Parse a chunk of text in the given format (dispatch parity with
    reference minibatch_iter.h:42-59). Uses the native C++ core when its
    shared library is available (wormhole_tpu/native), with these Python
    parsers as the reference implementation and fallback."""
    if fmt not in _PARSERS:
        raise ValueError(f"unknown data format: {fmt!r}")
    from wormhole_tpu import native

    blk = native.parse_text(text, fmt)
    if blk is not None:
        return blk
    return _PARSERS[fmt](text)


def iter_file_chunks(
    path: str,
    part: int = 0,
    num_parts: int = 1,
    chunk_bytes: int = 1 << 24,
) -> Iterator[str]:
    """Yield text chunks of (part k of n) of a file, split on line
    boundaries — the InputSplit contract (dmlc-core InputSplit::Create):
    a part starts at the first line beginning at-or-after its byte range
    start and ends at the first line boundary at-or-after its range end.
    `path` may be any URI data/filesys.py supports (Stream::Create
    parity).

    A chunk is one block `read` of up to `chunk_bytes` plus at most one
    `readline` that finishes the block's last line, so it closes at the
    first line end at-or-past `chunk_bytes` (or the part's end); the
    position advances by the bytes taken, with no `tell`. Keep it free
    of per-line calls: every `readline` refill and every `tell` on a
    buffered file is a syscall that drops the interpreter lock, and with
    the loader pool's other threads wanting it a chunk's ~45,000 lines
    cost seconds where the block read costs milliseconds (PERF.md §6,
    PR 25; tests/test_data.py holds the call count)."""
    from wormhole_tpu.data import filesys as fsys

    size = fsys.getsize(path)
    begin = size * part // num_parts
    end = size * (part + 1) // num_parts
    with fsys.open_stream(path, "rb") as f:
        if begin > 0:
            f.seek(begin - 1)
            # consume the partial line belonging to the previous part
            f.readline()
        pos = f.tell()
        while pos < end:
            chunk = f.read(min(chunk_bytes, end - pos))
            if not chunk:
                break
            if not chunk.endswith(b"\n"):
                chunk += f.readline()
            pos += len(chunk)
            yield chunk.decode("utf-8", errors="replace")
