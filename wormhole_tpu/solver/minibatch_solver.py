"""The train/val/predict pass loop: scheduler + streaming workers.

Parity with reference learn/solver/minibatch_solver.h + iter_solver.h:
- `run()` drives `max_data_pass` passes of TRAIN then VAL, with model
  load before (model_in / load_iter) and saves during (save_iter) and
  after (model_out) — minibatch_solver.h:85-137.
- each pass dispatches virtual file parts from a WorkloadPool to loader
  workers (data_parallel.h:93-115); here workers are host threads that
  parse minibatches into a bounded queue (the max_concurrency
  backpressure of minibatch_solver.h:284-329) while the main thread runs
  the jitted device steps — async I/O under synchronous XLA steps. The
  threads and the queue are the run's, not the pass's (`_Feed`): a
  loader that has read a pass to its end reads on into the next.
- a progress row prints every print_sec (minibatch_solver.h:169-192) and
  a `stop()` hook supports early stopping (minibatch_solver.h:47-59).
- predict writes one output file per part (iter_solver.h:140-156).
"""

from __future__ import annotations

import collections
import contextlib
import os
import queue
import threading
import time
from typing import Callable, Optional

import jax
import numpy as np

from wormhole_tpu.data.minibatch import MinibatchIter
from wormhole_tpu.data import pack_cache as _pc
from wormhole_tpu.obs import pyprof as _pyprof
from wormhole_tpu.obs import report as _report
from wormhole_tpu.obs import trace as _trace
from wormhole_tpu.obs.metrics import REGISTRY
from wormhole_tpu.solver.progress import Progress
from wormhole_tpu.solver.workload import WorkloadPool, WorkType
from wormhole_tpu.utils import checkpoint as ckpt
from wormhole_tpu.utils.perf import Perf


def _env_flag(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.lower() not in ("", "0", "false", "off")


class LoaderController:
    """Stall-driven sizing of the loader thread pool, adjusted between
    passes (the pass is the train thread's measurement window — the
    tf.data AUTOTUNE idea with a coarser clock; the loader threads live
    as long as the run, so a growth starts a thread at the next pass's
    start and a shrink lets one loader retire at its next part). Inputs
    per pass, read from the same numbers the obs gauges carry: the main
    thread's total queue-wait (``loader.stall_s``, the pass's first wait
    in it) and how often the queue was found well-stocked
    (``queue.depth``).

    Policy (hysteresis keeps it from oscillating):
    - stall above ``grow_stall`` of wall => the device out-ran the
      loaders; grow by 1 (by 2 when starved hard, > 3x the threshold);
    - stall under ``shrink_stall`` AND the queue was >= half full on
      most gets => loaders are over-provisioned; shrink by 1. The
      queue-fullness gate stops a shrink when stall is low merely
      because the pass was short.
    PERF.md's headline measurement is the motivating data point: 2
    loader threads starve a ~17 ms device step behind ~100 ms packs,
    3 restore headroom."""

    def __init__(self, initial: int, lo: int = 1, hi: int | None = None,
                 grow_stall: float = 0.15, shrink_stall: float = 0.02):
        self.n = max(int(initial), lo)
        self.lo = lo
        # loaders spend most of their time in I/O and GIL-released numpy,
        # so 2x oversubscription over the cores is a sane ceiling
        self.hi = hi if hi is not None else max(2 * (os.cpu_count() or 2),
                                                self.n)
        self.grow_stall = grow_stall
        self.shrink_stall = shrink_stall
        self.decisions: list[dict] = []

    def record_pass(self, stall_s: float, wall_s: float, n_steps: int,
                    queue_high_frac: float) -> int:
        """Fold one pass's numbers in; returns the pool size to use for
        the next pass. Passes too short to be a signal (< 4 steps) leave
        the size unchanged."""
        stall_frac = stall_s / max(wall_s, 1e-9)
        new = self.n
        why = "steady"
        if n_steps >= 4:
            if stall_frac > self.grow_stall:
                step = 2 if stall_frac > 3 * self.grow_stall else 1
                new = min(self.n + step, self.hi)
                why = "starved"
            elif stall_frac < self.shrink_stall and queue_high_frac > 0.5:
                new = max(self.n - 1, self.lo)
                why = "overfed"
        self.decisions.append({
            "from": self.n, "to": new, "why": why,
            "stall_frac": round(stall_frac, 4),
            "queue_high_frac": round(queue_high_frac, 3),
            "n_steps": n_steps,
        })
        self.n = new
        return new


_QDEPTH = REGISTRY.gauge("queue.depth")
_STALL = REGISTRY.gauge("loader.stall_s")
_POOL = REGISTRY.gauge("loader.pool_size")
# what the loaders had staged of a pass before the train thread entered
# it, and the passes that had a pass of the same run before them
_AHEAD = REGISTRY.counter("solver.pass.batches_ahead")
_TURNS = REGISTRY.counter("solver.pass.turns")

# training-step stage decomposition (the serve.stage.* contract for
# the train plane — obs/report.train_stage_table): the train thread's
# wall per batch splits into load (queue wait) + step (jitted call) +
# metrics (merge/print); a loader's cycle per batch is source (its wait
# for the parser or the pack cache) + pack + h2d_wait (for the batch it
# staged last) + h2d + put (on a full queue), overlapped with compute;
# sync_s is observed by the PS client sync paths.
# Each boundary is also a span (obs/names.py: loader.source, loader.pack,
# loader.h2d_wait, loader.h2d, loader.put_wait, solver.queue_wait,
# solver.*_step, solver.merge) that a running device profile lays beside
# the chip's own operations; the spans of one batch share (part, i), the
# part's id and the batch's index in it. What a pass costs outside its
# steps lies under solver.pass_start and solver.pass_end.
_ST_LOAD = REGISTRY.histogram("train.stage.load_s")
_ST_SOURCE = REGISTRY.histogram("train.stage.source_s")
_ST_PACK = REGISTRY.histogram("train.stage.pack_s")
_ST_H2D_WAIT = REGISTRY.histogram("train.stage.h2d_wait_s")
_ST_H2D = REGISTRY.histogram("train.stage.h2d_s")
_ST_PUT = REGISTRY.histogram("train.stage.put_s")
_ST_STEP = REGISTRY.histogram("train.stage.step_s")
_ST_METRICS = REGISTRY.histogram("train.stage.metrics_s")
_ST_TOTAL = REGISTRY.histogram("train.stage.total_s")


_DONE = object()


def _sourced(blocks, part: int, fetched):
    """`blocks` (a part's raw iterator) with each `next` under span
    `loader.source`: what a loader waits for its parser thread or its
    file, the pack not in it. The span is closed before the block is
    handed on; `fetched` is told the seconds of every block that came."""
    it, i = iter(blocks), 0
    while True:
        t0 = time.perf_counter()
        with _trace.span("loader.source", cat="loader", part=part, i=i,
                         cached=0) as wait:
            blk = next(it, _DONE)
            if blk is _DONE:
                wait.set(end=1)
                return
        if fetched is not None:
            fetched(time.perf_counter() - t0)
        yield blk
        i += 1


class _Pass:
    """One pass of a feed as its loaders read it: the data, the mode and
    `data_pass` (the iterators' seed); once the feed has reached it, its
    pool of parts and its pack-cache token. `staged` counts the batches
    staged so far; `turned` says its end marker is on its way, and
    `cache` is where the pack cache's stats stood then: after this
    pass's last lookup and before the next pass's first."""

    def __init__(self, data: str, wtype: WorkType, data_pass: int):
        self.data, self.wtype, self.data_pass = data, wtype, data_pass
        self.train = wtype == WorkType.TRAIN
        self.pool: Optional[WorkloadPool] = None
        self.token = None
        self.staged = 0
        self.turned = False
        self.cache: Optional[dict] = None


class _Feed:
    """The loader threads, the bounded queue and the stop event of one
    run over the passes it was told of, in their order: the run owns the
    feed, a pass is a stretch of its stream. The loaders read one pass at
    a time. One that finds the pass's pool with nothing left to hand out
    waits until every part of it is finished (a pass that fills the pack
    cache has then written every part's count entry, so the next packs
    none of it again), and the loader that finished the last part puts
    the pass's end marker, the `_Pass` itself, on the queue, opens the
    next pass's pool and lets all read on. So the queue holds the passes
    one after the other, every batch of a pass before its end marker and
    the next pass's first after it, and the train thread, which takes a
    pass up to its marker, finds the next pass's first batches staged
    while it still steps through this one's last. What is staged and
    alive is bounded as inside a pass: `max_queued` on the queue, one in
    each loader's hands, one in the step. Nothing is read beyond the
    last pass. A loader's exception travels the queue too and is raised
    by the train thread in the pass the loader was reading."""

    def __init__(self, solver: "MinibatchSolver", passes):
        self.solver = solver
        self.q: queue.Queue = queue.Queue(maxsize=solver.max_queued)
        self.stop = threading.Event()
        # guards reading, open, live, retire, a pass's staged / turned
        self.turn = threading.Condition()
        # a run names a million passes and makes few: each is reached
        # when the one before it turns
        self._rest = iter(passes)
        # the passes reached and not yet taken whole by the train thread:
        # the first is the one it takes next
        self.open: collections.deque[_Pass] = collections.deque()
        self.reading = self._reach()    # the loaders' pass; None: all read
        self.turns = 0      # passes the train thread has taken whole
        self.threads: list[threading.Thread] = []
        self.live = 0       # loaders at work
        self.retire = 0     # of them, those to end at their next part

    # ------------------------------------------------- the train thread's
    def next_pass(self, data: str, wtype: WorkType,
                  data_pass: int) -> Optional[_Pass]:
        """The pass the train thread takes next, if it is this one."""
        with self.turn:
            ps = self.open[0] if self.open else None
        if ps is not None and (ps.data, ps.wtype, ps.data_pass) == (
                data, wtype, data_pass):
            return ps
        return None

    def taken(self) -> None:
        """The train thread has had the end marker of the pass it took."""
        with self.turn:
            self.open.popleft()
            self.turns += 1

    def staff(self, n: int) -> None:
        """`n` loaders from here on: a growth starts threads, a shrink
        lets loaders retire, each at its next part. The run's first call
        opens the first pass's pool."""
        with self.turn:
            if self.reading is None:
                return      # all is read: nothing for a new thread to do
            if self.reading.pool is None:
                self._open(self.reading)
            self.retire = max(self.live - n, 0)
            new = [threading.Thread(target=self._loader, daemon=True,
                                    args=(len(self.threads) + k,))
                   for k in range(n - self.live)]
            self.threads += new
            self.live += len(new)
            self.turn.notify_all()
        for t in new:
            t.start()

    def close(self) -> None:
        """Stop and join every loader and drop what they ran ahead."""
        self.stop.set()
        with self.turn:
            self.turn.notify_all()
        for t in self.threads:
            t.join()
        with contextlib.suppress(queue.Empty):
            while True:
                self.q.get_nowait()

    # --------------------------------------------------------- a loader's
    def _reach(self) -> Optional[_Pass]:
        """The next pass of the run, from here on among the open ones."""
        nxt = next(self._rest, None)
        if nxt is None:
            return None
        ps = _Pass(*nxt)
        with self.turn:
            self.open.append(ps)
        return ps

    def _open(self, ps: _Pass) -> None:
        cfg = self.solver.cfg
        pool = WorkloadPool()
        if pool.add(ps.data, cfg.num_parts_per_file, cfg.data_format) == 0:
            raise FileNotFoundError(f"no files match {ps.data}")
        ps.token = self.solver._pass_cache_token(ps.train)
        ps.pool = pool

    def _put(self, item) -> bool:
        """Bounded put that gives up when the consumer is gone, so a
        failed main-thread step can't park loaders on a full queue."""
        while not self.stop.is_set():
            try:
                self.q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def _take(self, node: str):
        """A part of the pass being read, with its pass; None where the
        loader is to end: retired, nothing left to read, or stopped."""
        with self.turn:
            while not self.stop.is_set():
                if self.retire:
                    self.retire -= 1
                    return None
                ps = self.reading
                if ps is None:
                    return None
                got = ps.pool.get(node)
                if got is not None:
                    return ps, *got
                self.turn.wait()    # for the pass's turn, or the stop
        return None

    def _finish(self, ps: _Pass, part_id: int) -> None:
        """A part is read. The loader that finds the pass all finished
        turns it: the end marker, the next pass's pool, and on."""
        ps.pool.finish(part_id)
        with self.turn:
            if ps.turned or not ps.pool.is_finished():
                return
            ps.turned = True
        if self.solver.pack_cache is not None:
            ps.cache = self.solver.pack_cache.stats()
        # the next pass is open before this one's marker is out: the
        # train thread asks for it as soon as it has the marker
        nxt = self._reach()
        if not self._put(ps):
            return
        if nxt is not None:
            self._open(nxt)
        with self.turn:
            self.reading = nxt
            self.turn.notify_all()

    def _loader(self, node_id: int) -> None:
        _pyprof.tag_thread("loader")
        solver, cfg = self.solver, self.solver.cfg
        prepare = getattr(solver.learner, "prepare_batch", None)
        # loader-side device staging (double-buffer): batch N+1's arrays
        # go to the device while the main thread steps batch N
        stage = getattr(solver.learner, "stage_batch", None)
        staged = None   # the batch this loader staged last, in any pass
        try:
            while (got := self._take(f"loader-{node_id}")) is not None:
                ps, part_id, f = got
                train, data_pass = ps.train, ps.data_pass
                # a loader's wait for a batch's source, a batch (train
                # passes)
                fetched = _ST_SOURCE.observe if train else None

                def raw_iter():
                    return _sourced(MinibatchIter(
                        f.filename, f.part, f.num_parts, f.format,
                        minibatch_size=cfg.minibatch,
                        shuf_buf=(cfg.rand_shuffle * cfg.minibatch
                                  if train else 0),
                        neg_sampling=(cfg.neg_sampling if train else 1.0),
                        seed=data_pass * 7919 + part_id,
                    ), part_id, fetched)

                i = 0   # batches of this part delivered so far

                def prep(blk):
                    # host-side batch prep (padding + pallas tile-sort)
                    # happens here in the loader thread, overlapped with
                    # the main thread's device steps
                    if prepare is None:
                        return blk
                    t0p = time.perf_counter()
                    with _trace.span("loader.pack", cat="loader", cpu=True,
                                     part=part_id, i=i, rows=blk.size):
                        out = prepare(blk, train=train)
                    if train:
                        _ST_PACK.observe(time.perf_counter() - t0p)
                    return out

                # identical (token, part, file bytes, batch geometry)
                # => identical pack; anything else misses
                part_key = None
                if ps.token is not None:
                    part_key = (
                        "train" if train else "eval", ps.token,
                        f.filename, f.part, f.num_parts, f.format,
                        cfg.minibatch, _pc.file_stamp(f.filename))
                for b in _pc.iter_part_cached(
                        solver.pack_cache, part_key, raw_iter, prep,
                        part=part_id, fetched=fetched):
                    if stage is not None:
                        # one transfer in flight a loader: staging
                        # returns before the bytes are over, and
                        # transfers under way share the link. A loader
                        # that runs ahead (from the pack cache: a dozen
                        # batches in a row) would hold back the two the
                        # train thread needs first (PERF.md §6, PR 37)
                        t0w = time.perf_counter()
                        if staged is not None:
                            with _trace.span("loader.h2d_wait", cat="loader",
                                             part=part_id, i=i):
                                jax.block_until_ready(staged)
                        t0h = time.perf_counter()
                        with _trace.span("loader.h2d", cat="loader",
                                         part=part_id, i=i):
                            b = staged = stage(b, train=train)
                        if train:
                            _ST_H2D_WAIT.observe(t0h - t0w)
                            _ST_H2D.observe(time.perf_counter() - t0h)
                    with self.turn:
                        ps.staged += 1
                    t0q = time.perf_counter()
                    with _trace.span("loader.put_wait", cat="loader",
                                     part=part_id, i=i,
                                     depth=self.q.qsize()):
                        if not self._put((b, part_id, i)):
                            return
                    if train:
                        _ST_PUT.observe(time.perf_counter() - t0q)
                    i += 1
                self._finish(ps, part_id)
        except BaseException as e:
            # to the train thread, behind what this pass has queued
            self._put(e)
        finally:
            with self.turn:
                self.live -= 1


class MinibatchSolver:
    """Drives a learner (train_batch/eval_batch/predict_batch/store) over
    sharded files with pooled loading and failure re-queue. `run()` makes
    one feed (`_Feed`: loader threads, bounded queue) for all its passes,
    so the loaders stage the next pass's first batches while the train
    thread steps through this pass's last; what lies between two passes
    (the PS barrier, the val pass, a checkpoint, the stop hook, the
    learner's `on_pass_start` and `nnz`) stays on the train thread
    between the last step of one and the first of the next, and a batch
    is stepped in its own pass only. `iterate()` called alone opens and
    closes a feed of its one pass. The pool's
    straggler watchdog is NOT started here: within one process, a
    re-queued part would be read twice and its examples double-trained;
    the watchdog is for the multi-host scheduler (launcher/dmlc_tpu.py)
    where a straggling host's parts move to another host."""

    def __init__(self, learner, cfg, num_loaders: int | None = None,
                 max_queued: int | None = None, verbose: bool = True):
        src = "arg"
        pinned = num_loaders is not None
        if num_loaders is None:
            env = os.environ.get("WH_NUM_LOADERS")
            if env:
                # hardware sweeps pin the pool without config edits
                num_loaders = max(1, int(env))
                src = "WH_NUM_LOADERS"
                pinned = True
            else:
                # the reference's max_concurrency knob (minibatch_solver.h:
                # 215-242): concurrently-prepared in-flight minibatches
                num_loaders = getattr(cfg, "max_concurrency", 2)
                src = "cfg.max_concurrency"
        self.learner = learner
        self.cfg = cfg
        self.num_loaders = num_loaders
        # a queued batch is a staged one and holds device memory: the
        # configuration says how many may wait (cfg.max_queued)
        self.max_queued = (max_queued if max_queued is not None
                           else getattr(cfg, "max_queued", 8))
        self.verbose = verbose
        self.t0 = time.time()
        # adaptive sizing defaults on, but a pinned count (explicit arg or
        # env) means the operator chose — stay fixed unless they also set
        # WH_ADAPTIVE_LOADERS=1
        self.controller: Optional[LoaderController] = (
            LoaderController(num_loaders)
            if _env_flag("WH_ADAPTIVE_LOADERS", default=not pinned)
            else None)
        self.pack_cache = _pc.from_env()
        # the feed of the run under way (run() opens and closes it)
        self._feed: Optional[_Feed] = None
        # early-stop hook: (pass progress, data_pass, type) -> bool
        self.stop_hook: Optional[Callable] = None
        # PS barrier hook (SyncedStore.flush): called before eval,
        # checkpoint saves, and predict so an async in-flight sync can't
        # leave those reading a half-merged model; None in single-process
        # runs (no PS plane) and the distributed runner wires it up
        self.sync_flush: Optional[Callable] = None
        # per-op accounting for the PS plane's sync paths (difacto
        # async_sgd.h:108-127 style; apps/_runner.py hands it on). The
        # pass loop itself keeps the train.stage.* histograms and spans
        self.perf = Perf(log=self._log)
        cache_desc = "off"
        if self.pack_cache is not None:
            cache_desc = f"mem={self.pack_cache.mem_bytes >> 20}MB"
            if self.pack_cache.disk_dir:
                cache_desc += f" disk={self.pack_cache.disk_dir}"
        self._log(f"[loader] {num_loaders} loader thread(s) ({src}), "
                  f"adaptive={'on' if self.controller else 'off'}, "
                  f"pack_cache={cache_desc}")
        from wormhole_tpu import native

        # where and how this run executes, stated once: the learner's
        # backend/mesh/kernel path and whether the C++ parsing core or
        # the Python parsers feed it
        placement = getattr(learner, "placement",
                            "[learner] placement not stated")
        self._log(f"{placement} native={native.status()}")

    @property
    def _ckpt_store(self):
        # learners with multiple KV stores expose a combined adapter
        return getattr(self.learner, "ckpt_store", None) or self.learner.store

    # ----------------------------------------------------------------- run
    def run(self) -> dict:
        cfg = self.cfg
        if cfg.model_in:
            ckpt.load_model(self._ckpt_store, cfg.model_in,
                            cfg.load_iter if cfg.load_iter >= 0 else None)
        result = {}
        with _trace.maybe_trace():
            result = self._run_passes(cfg)
        if _report.enabled() and not os.environ.get("WH_ROLE"):
            # single-process run: no scheduler to aggregate, so this
            # process's registry IS the whole job — write the report
            # directly (distributed runs get it from apps/_runner.py)
            path = _report.write(_report.build_local())
            self._log(f"[obs] run report written: {path}")
        return result

    def _flush(self) -> None:
        if self.sync_flush is not None:
            with _trace.span("solver.flush", cat="solver"):
                self.sync_flush()

    def _save(self, base: str, it: Optional[int] = None) -> None:
        # how long a save stalls the passes: a span of its own
        with _trace.span("solver.checkpoint", cat="solver"):
            ckpt.save_model(self._ckpt_store, base, it)

    def _passes(self, cfg):
        """The run's passes, in the order `_run_passes` makes them."""
        for dp in range(cfg.max_data_pass):
            yield cfg.train_data, WorkType.TRAIN, dp
            if cfg.val_data:
                yield cfg.val_data, WorkType.VAL, dp

    def _run_passes(self, cfg) -> dict:
        result = {}
        self._feed = _Feed(self, self._passes(cfg))
        try:
            for dp in range(cfg.max_data_pass):
                tr = self.iterate(cfg.train_data, WorkType.TRAIN, dp)
                result["train"] = tr
                self._flush()  # pass boundary: all of this pass is merged
                if cfg.val_data:
                    vl = self.iterate(cfg.val_data, WorkType.VAL, dp)
                    result["val"] = vl
                if cfg.model_out and cfg.save_iter > 0 and (
                    (dp + 1) % cfg.save_iter == 0
                    and dp + 1 < cfg.max_data_pass
                ):
                    self._flush()
                    self._save(cfg.model_out, dp)
                if self._should_stop(result, dp):
                    self._log(f"early stop after pass {dp}")
                    break
        finally:
            # an early stop, a step's exception: what ran ahead is dropped
            self._feed.close()
            self._feed = None
        self._flush()
        if cfg.model_out:
            self._save(cfg.model_out)
        if getattr(cfg, "predict_out", None):
            self.predict(cfg.val_data or cfg.train_data, cfg.predict_out)
        return result

    def _should_stop(self, result: dict, dp: int) -> bool:
        if self.stop_hook is None:
            return False
        key = "val" if "val" in result else "train"
        return bool(self.stop_hook(result[key], dp, key))

    # ------------------------------------------------------------- iterate
    def _pass_cache_token(self, train: bool):
        """The learner's pack version for this pass, or None when this
        pass's batch stream cannot be replayed bit-identically: shuffle
        and negative sampling draw from a seed that changes per pass, so
        a cached pack from pass 0 would be the wrong batch in pass 1."""
        if self.pack_cache is None:
            return None
        tok_fn = getattr(self.learner, "pack_cache_token", None)
        if tok_fn is None:
            return None
        if train and (self.cfg.rand_shuffle
                      or self.cfg.neg_sampling < 1.0):
            return None
        return tok_fn(train=train)

    def iterate(self, data: str, wtype: WorkType, data_pass: int = 0) -> Progress:
        """One pass. The train thread's time in it lies under
        `solver.<mode>_pass`, and inside that under `solver.pass_start`
        up to the first batch in hand, the loop's own spans (queue_wait,
        step, merge a batch), and `solver.pass_end` from the loop's exit
        on; `turn` holds whichever of the first and the last is open.
        Inside `run()` the batches come from the run's feed, which may
        have staged this pass's first ones already (`ahead`); a pass with
        no run round it, or not the one the run makes next, opens a feed
        of its own and closes it."""
        mode = "train" if wtype == WorkType.TRAIN else "eval"
        with _trace.span(f"solver.{mode}_pass", cat="solver",
                         data_pass=data_pass), \
                contextlib.ExitStack() as turn:
            feed = self._feed
            ps = (None if feed is None
                  else feed.next_pass(data, wtype, data_pass))
            if ps is not None:
                return self._iterate(feed, ps, mode, turn)
            feed = _Feed(self, [(data, wtype, data_pass)])
            try:
                return self._iterate(feed, feed.reading, mode, turn)
            finally:
                feed.close()

    def _iterate(self, feed: _Feed, ps: _Pass, mode: str,
                 turn: contextlib.ExitStack) -> Progress:
        data, data_pass, train = ps.data, ps.data_pass, ps.train
        with feed.turn:
            ahead = ps.staged
        start = turn.enter_context(_trace.span(
            "solver.pass_start", cat="solver", mode=mode,
            data_pass=data_pass, ahead=ahead))
        if feed.turns:
            _TURNS.inc()
            _AHEAD.inc(ahead)
        cfg = self.cfg
        hook = getattr(self.learner, "on_pass_start", None)
        if hook:
            hook()
        prog = Progress()
        if hasattr(self.learner, "nnz"):
            # seed the pass with the model's standing |w|_0 so the row's
            # sparsity column is cumulative across passes like the
            # reference log (progress.h:10-35), not per-pass deltas;
            # one host reduction per pass, not per row
            with _trace.span("solver.nnz", cat="solver"):
                nnz = float(self.learner.nnz())
            prog.merge({"new_w": nnz})
            prog.take_increment()
        n_loaders = self.controller.n if self.controller else self.num_loaders
        _POOL.set(n_loaders)
        start.set(loaders=n_loaders)
        feed.staff(n_loaders)

        step = (self.learner.train_batch if mode == "train"
                else self.learner.eval_batch)
        last_print = time.time()
        n_steps = 0
        t_step = 0.0
        stall_s = 0.0
        gets = 0
        high = 0
        t_pass0 = time.perf_counter()
        _pyprof.tag_thread("train")
        if self.verbose:
            self._log(f"{mode} pass {data_pass}: {data}")
            self._log(Progress.header())
        try:
            while True:
                depth = feed.q.qsize()
                _QDEPTH.set(depth)
                gets += 1
                if depth >= max(1, self.max_queued // 2):
                    high += 1
                t_w = time.perf_counter()
                with _trace.span("solver.queue_wait", cat="solver") as wait:
                    item = feed.q.get()
                    if gets == 1:
                        wait.set(first=1)
                    if item is ps:
                        wait.set(end=1)
                dw = time.perf_counter() - t_w
                stall_s += dw
                _STALL.set(stall_s)
                if item is ps:
                    break       # its end marker: the pass is over
                if isinstance(item, BaseException):
                    raise item  # a loader's, of this pass
                if not n_steps:
                    turn.close()   # a batch in hand: the start is over
                b, part_id, i = item
                t_s = time.perf_counter()
                with _trace.span(f"solver.{mode}_step", cat="solver",
                                 part=part_id, i=i):
                    out = step(b)
                dt = time.perf_counter() - t_s
                t_step += dt
                n_steps += 1
                t_m = time.perf_counter()
                with _trace.span("solver.merge", cat="solver"):
                    prog.merge(out)
                    if self.verbose and (time.time() - last_print
                                         >= cfg.print_sec):
                        self._log(prog.row(self.t0))
                        last_print = time.time()
                if train:
                    dm = time.perf_counter() - t_m
                    _ST_LOAD.observe(dw)
                    _ST_STEP.observe(dt)
                    _ST_METRICS.observe(dm)
                    _ST_TOTAL.observe(dw + dt + dm)
        finally:
            turn.close()
            turn.enter_context(_trace.span(
                "solver.pass_end", cat="solver", mode=mode,
                data_pass=data_pass, steps=n_steps))
        feed.taken()
        if self.verbose:
            self._log(prog.row(self.t0))
        wall = time.perf_counter() - t_pass0
        self.last_pass_stall_s = stall_s
        self.last_pass_wall_s = wall
        if n_steps:
            # FinishMinibatch-style pass summary (minibatch_solver.h:
            # 246-275): average device-step time and the share of wall
            # time spent outside compute (I/O + parse + any PS sync)
            overhead = max(0.0, 100.0 * (1.0 - t_step / max(wall, 1e-9)))
            self._log(
                f"{mode} pass {data_pass}: {n_steps} minibatches, "
                f"avg {1e3 * t_step / n_steps:.1f}ms/step, "
                f"{overhead:.0f}% io/comm overhead, "
                f"wall {wall:.2f}s, {ahead} staged ahead")
        if (s := ps.cache) is not None:
            self._log(
                f"[loader] pack cache: {s['hits']} hits / "
                f"{s['misses']} misses ({100 * s['hit_rate']:.0f}%), "
                f"mem {s['mem_bytes'] >> 20}MB/{s['mem_entries']} entries")
        if self.controller is not None:
            self.controller.record_pass(
                stall_s, wall, n_steps, high / max(gets, 1))
            d = self.controller.decisions[-1]
            if d["from"] != d["to"]:
                self._log(
                    f"[loader] controller: {d['from']} -> {d['to']} "
                    f"loaders ({d['why']}, stall "
                    f"{100 * d['stall_frac']:.0f}% of wall, queue "
                    f">=half-full {100 * d['queue_high_frac']:.0f}% "
                    f"of gets)")
        return prog

    # ------------------------------------------------------------- predict
    def predict(self, data: str, out_base: str) -> list[str]:
        """One PRED pass; margins written one file per part
        (iter_solver.h:140-156; users concatenate, criteo_kaggle.rst:97)."""
        cfg = self.cfg
        pool = WorkloadPool()
        if pool.add(data, cfg.num_parts_per_file, cfg.data_format) == 0:
            raise FileNotFoundError(f"no files match {data}")
        os.makedirs(os.path.dirname(out_base) or ".", exist_ok=True)
        out_files = []
        while True:
            got = pool.get("predictor")
            if got is None:
                break
            part_id, f = got
            path = f"{out_base}_part-{part_id}"
            with open(path, "w") as fh:
                for blk in MinibatchIter(
                    f.filename, f.part, f.num_parts, f.format,
                    minibatch_size=cfg.minibatch,
                ):
                    for m in self.learner.predict_batch(blk):
                        fh.write(f"{m:.6g}\n")
            out_files.append(path)
            pool.finish(part_id)
        return out_files

    def _log(self, msg: str) -> None:
        if self.verbose:
            print(msg, flush=True)
