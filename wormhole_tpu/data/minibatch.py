"""MinibatchIter: stream fixed-size RowBlock minibatches from file parts.

Parity with reference learn/base/minibatch_iter.h:
- wraps the parser in a background prefetch thread (ThreadedParser, :60)
- fixed minibatch size with carry-over across parsed chunks (:75-131)
- shuffle buffer: accumulate `shuf_buf` rows, random-permute, emit (:83-91)
- negative downsampling with label-dependent keep probability (:103-107)
- format dispatch libsvm/criteo/criteo_test/adfea/crb (:42-59)
"""

from __future__ import annotations

import queue
import threading
from typing import Iterator, Optional

import numpy as np

from wormhole_tpu.data.rowblock import RowBlock
from wormhole_tpu.data import parsers


def _iter_rowblocks(
    filename: str, part: int, num_parts: int, fmt: str
) -> Iterator[RowBlock]:
    """Parsed blocks of one file part. Each pull on the source is a
    `data.read` span (a text chunk off the file, or one crb record read,
    inflated and decoded) and each `parse_text` a `data.parse` span, on
    whichever thread iterates (the ThreadedParser's); none is open
    across a `yield`. `bytes` counts what the step produced: characters
    of text, or the decoded record's array bytes."""
    # not at import: `import wormhole_tpu` reaches this module, and a
    # process with telemetry off imports no obs (tests/test_obs.py)
    from wormhole_tpu.obs import trace as _trace

    text = fmt != "crb"
    if text:
        src = parsers.iter_file_chunks(filename, part, num_parts)
    else:
        from wormhole_tpu.data import crb

        src = crb.read_crb(filename, part, num_parts)
    while True:
        with _trace.span("data.read", cat="data", cpu=True,
                         part=part) as sp:
            got = next(src, None)
            if got is None:
                return
            if text:
                sp.set(bytes=len(got))
            else:
                sp.set(bytes=got.nbytes, rows=got.size)
        if text:
            with _trace.span("data.parse", cat="data", cpu=True,
                             part=part, bytes=len(got)) as sp:
                got = parsers.parse_text(got, fmt)
                sp.set(rows=got.size)
        if got.size:
            yield got


#: end-of-stream marker on the ThreadedParser queue
_END = object()


class _ParserError:
    """Queue sentinel carrying a producer-thread exception to the
    consumer."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class ThreadedParser:
    """Background prefetch over a RowBlock source (the reference's
    ThreadedParser, minibatch_iter.h:60).

    The producer thread's terminal state — end-of-stream OR an
    exception — always travels on the queue itself (`_END` /
    `_ParserError` sentinels), so a consumer blocked in `get()` is
    guaranteed a next item even when the parser dies mid-stream; the
    exception re-raises at the consumer's iteration point instead of
    the thread dying silently with the iterator parked forever."""

    def __init__(self, src, maxsize: int = 4):
        self._src = src
        self._q: queue.Queue = queue.Queue(maxsize=maxsize)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._produce, daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        """Bounded put that gives up if the consumer went away, so
        abandoning the iterator mid-stream can't park the producer (and
        its open file) forever."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self) -> None:
        try:
            for blk in self._src:
                if not self._put(blk):
                    return
            self._put(_END)
        except BaseException as e:  # noqa: BLE001 — relayed to consumer
            self._put(_ParserError(e))

    def close(self) -> None:
        self._stop.set()

    def __iter__(self):
        try:
            while True:
                item = self._q.get()
                if item is _END:
                    return
                if isinstance(item, _ParserError):
                    raise item.exc
                yield item
        finally:
            self.close()


class MinibatchIter:
    """Iterate fixed-size minibatches over (part k of n) of one file.

    Args mirror the reference's knobs (minibatch_iter.h:20-41 +
    config surface config.proto:88-133): minibatch_size, shuf_buf rows of
    shuffling, neg_sampling keep-probability for negative examples.
    """

    def __init__(
        self,
        filename: str,
        part: int = 0,
        num_parts: int = 1,
        fmt: str = "libsvm",
        minibatch_size: int = 1024,
        shuf_buf: int = 0,
        neg_sampling: float = 1.0,
        prefetch: bool = True,
        seed: int = 0,
    ):
        self.filename = filename
        self.part = part
        self.num_parts = num_parts
        self.fmt = fmt
        self.minibatch_size = int(minibatch_size)
        self.shuf_buf = int(shuf_buf)
        self.neg_sampling = float(neg_sampling)
        self.prefetch = prefetch
        self.rng = np.random.default_rng(seed)

    # -- internal stream of raw parsed blocks, optionally prefetched --------
    def _raw_blocks(self) -> Iterator[RowBlock]:
        src = _iter_rowblocks(self.filename, self.part, self.num_parts, self.fmt)
        if not self.prefetch:
            yield from src
            return
        yield from ThreadedParser(src)

    def _transformed(self) -> Iterator[RowBlock]:
        for blk in self._raw_blocks():
            if self.neg_sampling < 1.0:
                blk = self._neg_sample(blk)
                if blk.size == 0:
                    continue
            yield blk

    def _neg_sample(self, blk: RowBlock) -> RowBlock:
        keep = (blk.label > 0) | (
            self.rng.random(blk.size) < self.neg_sampling
        )
        if keep.all():
            return blk
        rows = np.nonzero(keep)[0]
        return _take_rows(blk, rows)

    def __iter__(self) -> Iterator[RowBlock]:
        mb = self.minibatch_size
        if self.shuf_buf > 0:
            buf: list[RowBlock] = []
            buffered = 0
            for blk in self._transformed():
                buf.append(blk)
                buffered += blk.size
                if buffered >= max(self.shuf_buf, mb):
                    yield from self._drain(buf, flush=False)
                    buffered = sum(b.size for b in buf)
            if buf:
                yield from self._drain(buf, flush=True)
        else:
            # emit cursor-advanced slices of each parsed chunk; only the
            # sub-minibatch tail is carried (and concat'd) into the next
            # chunk, keeping batching O(rows) overall
            tail: Optional[RowBlock] = None
            for blk in self._transformed():
                if tail is not None and tail.size:
                    blk = RowBlock.concat([tail, blk])
                    tail = None
                pos = 0
                while blk.size - pos >= mb:
                    yield blk.slice(pos, pos + mb)
                    pos += mb
                tail = blk.slice(pos, blk.size) if pos < blk.size else None
            if tail is not None and tail.size:
                yield tail

    def _drain(self, buf: list[RowBlock], flush: bool) -> Iterator[RowBlock]:
        big = RowBlock.concat(buf)
        perm = self.rng.permutation(big.size)
        big = _take_rows(big, perm)
        mb = self.minibatch_size
        n_emit = big.size if flush else (big.size // mb) * mb
        for b in range(0, n_emit, mb):
            yield big.slice(b, min(b + mb, n_emit))
        buf.clear()
        if n_emit < big.size:
            buf.append(big.slice(n_emit, big.size))


def _take_rows(blk: RowBlock, rows: np.ndarray) -> RowBlock:
    """Gather a subset/permutation of rows into a new RowBlock."""
    lens = np.diff(blk.offset)[rows]
    offset = np.zeros(len(rows) + 1, dtype=np.int64)
    np.cumsum(lens, out=offset[1:])
    # per-row source ranges -> flat nonzero gather indices
    starts = blk.offset[rows]
    gather = np.concatenate(
        [np.arange(s, s + l, dtype=np.int64) for s, l in zip(starts, lens)]
    ) if len(rows) else np.zeros(0, dtype=np.int64)
    return RowBlock(
        label=blk.label[rows],
        offset=offset,
        index=blk.index[gather],
        value=None if blk.value is None else blk.value[gather],
        weight=None if blk.weight is None else blk.weight[rows],
    )
