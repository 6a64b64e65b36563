"""Sparse linear learner: async-SGD logistic regression, TPU-native.

Parity target: the reference's flagship `linear.dmlc` app
(learn/linear/async_sgd.h, loss.h, penalty.h, config.proto) — logistic /
squared-hinge loss over hashed sparse features, with per-key SGD / AdaGrad /
FTRL update rules and elastic-net regularization.

TPU design (vs the reference's worker/server processes):
- the weight/optimizer tables are a KVStore: hashed buckets sharded over
  the mesh model axis (the servers);
- a training step jits pull -> SpMV -> loss grad -> SpMV^T -> handle update
  end-to-end; the minibatch is sharded over the data axis (the workers) and
  XLA inserts the gather / reduce-scatter collectives that play
  ZPull/ZPush;
- the per-key Handle branches (async_sgd.h:71-180) become masked dense
  vector updates: untouched buckets carry zero gradient and a zero
  touched-mask, making the update a no-op exactly where the reference
  would not receive a push.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from wormhole_tpu import native
from wormhole_tpu.data.rowblock import DeviceBatch
from wormhole_tpu.models import minibatch_learner as mbl
from wormhole_tpu.obs.metrics import REGISTRY
from wormhole_tpu.ops import coo_kernels as ck
from wormhole_tpu.ops import metrics as M
from wormhole_tpu.ops.fused_update import apply_handle, scatter_update
from wormhole_tpu.ops.spmv import spmv, spmv_t
from wormhole_tpu.parallel.kvstore import KVStore, TableSpec, quantize_push
from wormhole_tpu.parallel.mesh import describe_placement

_log = logging.getLogger(__name__)

# the mesh pack, a batch at a time: nonzeros a full shard dropped, and
# the fullest cell beside the sum of all cells (hot shard = max * cells
# / sum)
_MESH_DROPPED = REGISTRY.counter("linear.mesh.dropped_nnz")
_MESH_NNZ_MAX = REGISTRY.counter("linear.mesh.shard_nnz_max")
_MESH_NNZ_SUM = REGISTRY.counter("linear.mesh.shard_nnz_sum")
# slots the packed mesh batches hold, D x M x P a batch: what the loader
# copies for those nonzeros
_MESH_SLOTS = REGISTRY.counter("linear.mesh.slots")
_CHUNKS = REGISTRY.counter("linear.blocks.chunks")
_CHUNKS_RUN = REGISTRY.counter("linear.blocks.chunks_run")
# tcoo batches packed, and those the native pass packed
_PACK_BATCHES = REGISTRY.counter("linear.pack.batches")
_PACK_NATIVE = REGISTRY.counter("linear.pack.native")


def _count_chunks(stream, dead, blk: int, kernels: int):
    """Count the chunks of a packed stream's grid blocks and those the
    `kernels` kernels that walk it will execute
    (ops/coo_kernels._live_chunks)."""
    n, run = ck.host_chunk_counts(stream, dead, blk)
    _CHUNKS.inc(n * kernels)
    _CHUNKS_RUN.inc(run * kernels)


def _nonzero_ids(packed) -> tuple:
    return (np.unique(packed.idx[packed.val != 0]).astype(np.int64),)


@dataclasses.dataclass
class LinearConfig:
    """Config surface of reference learn/linear/config.proto (subset that
    is meaningful on TPU; names kept)."""

    train_data: str = ""
    val_data: Optional[str] = None
    model_out: Optional[str] = None
    model_in: Optional[str] = None
    predict_out: Optional[str] = None
    data_format: str = "libsvm"
    max_data_pass: int = 1

    # loss/penalty (config.proto:24-43)
    loss: str = "logit"  # logit | square_hinge
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    # predict output: raw margins (default) or probabilities
    # (reference linear/loss.h:55-63 prob_prediction)
    prob_predict: bool = False

    # learning rate / algorithm (config.proto:45-77)
    algo: str = "ftrl"  # ftrl | adagrad | sgd
    lr_eta: float = 0.1
    lr_beta: float = 1.0

    # data / system knobs (config.proto:88-133)
    minibatch: int = 1000
    num_parts_per_file: int = 2
    rand_shuffle: int = 0  # shuffle buffer in minibatches (0 = off)
    neg_sampling: float = 1.0
    fixed_bytes: int = 0  # gradient-push quantization filter
    # zlib-compress the PS delta stream (the reference's msg_compression
    # filter, config.proto:123-133; COMPRESSING in async_sgd.h:290-301)
    msg_compression: int = 0
    # bounded staleness (reference config.proto:122 max_delay,
    # criteo.conf:21): in the multi-process launch, the max number of
    # minibatches a worker trains between syncs against the server group
    max_delay: int = 16
    # concurrent in-flight minibatches per worker (reference
    # minibatch_solver.h:215-242 max_concurrency): here the number of
    # loader threads preparing batches (parse + pack) while the device
    # steps — the synchronous-XLA analog of overlapping pull/compute/push
    # of successive minibatches. 4 keeps a ~17 ms device step fed when a
    # 64k-row pack costs ~100 ms of host work.
    max_concurrency: int = 4
    # staged minibatches that may wait in the solver's queue beside the
    # one each loader thread holds. A staged batch lies in device memory:
    # a job whose batches are large (a compact FM batch of 100,000 x 39
    # is 0.19 GB) lowers this to keep them out of the tables' way
    max_queued: int = 8
    # multi-process dispatch: online (greedy, straggler-reassigning) or
    # batch (stable n/num_workers assignment per pass); local_data asks
    # each worker to match train_data against ITS filesystem and report,
    # giving its parts node affinity (reference data_parallel.h:54-100,
    # config.proto local_data)
    dispatch: str = "online"
    local_data: bool = False
    # fault tolerance (docs/distributed.md "Fault tolerance"): cadence of
    # the ps servers' async shard snapshots (effective only when the
    # launcher provides a snapshot dir), and the worker-side PS retry
    # budget in seconds — 0 keeps the default fail-fast-on-server-death
    # behavior; the launcher's --max-server-restarts exports a matching
    # budget via WH_PS_RETRY_SEC, which a nonzero value here overrides
    server_snapshot_sec: float = 5.0
    ps_retry_sec: float = 0.0
    # global-mesh mode: the -n worker processes jax.distributed-initialize
    # into ONE SPMD mesh; gradients aggregate over ICI/DCN collectives
    # instead of the TCP parameter server (parallel/multihost.py)
    global_mesh: bool = False
    print_sec: int = 1
    save_iter: int = -1
    load_iter: int = -1

    # TPU-native capacity knobs (replace dynamic shapes; SURVEY §7 hard
    # parts): table size = hash-kernel bucket count (ps FLAGS_max_key
    # analog), row_capacity = max nnz per minibatch
    num_buckets: int = 1 << 20
    nnz_per_row: int = 64

    # in-process model-axis sharding: split the state tables over this
    # many mesh "model" shards (HBM residency for the hot parameter
    # plane; 1 = tables replicated, all devices on the data axis).
    # num_buckets must divide evenly over the shards.
    model_shards: int = 1

    # kernel = pallas (tiled MXU COO kernels, ops/coo_kernels.py) | xla
    # (segment ops) | auto (pallas on an unsharded-table TPU run, else xla)
    kernel: str = "auto"
    # Tile-aligned unique-key compaction (the Localizer path,
    # ops/localizer.py + coo_kernels.pack_tile_coo): the minibatch's
    # unique buckets get compact slots grouped by their home table tile;
    # a Pallas kernel streams only the TOUCHED tiles to gather w
    # (tile_gather), the COO kernels run over the compact domain, and the
    # optimizer update happens inside a Pallas kernel that rewrites each
    # touched tile in place (ops/fused_update.py) — no XLA element
    # gathers or scatters of state at all. Step cost O(touched tiles +
    # unique keys) instead of O(num_buckets): the TPU analog of the
    # reference server updating only pushed keys at their storage
    # (async_sgd.h:160-180). -1 = auto (sized from the first batch,
    # engaged when the compact domain is well under the table size),
    # 0 = off, >0 = explicit slot capacity (rounded up to a whole tile).
    compact_cap: int = -1
    # MXU compute dtype for the pallas kernels: bf16 (half the MXU cost;
    # table values and per-nnz gradients round to bfloat16) | f32 (exact,
    # matches kernel=xla numerics) | auto (f32 when fixed_bytes == 0 —
    # i.e. when gradient quantization is nominally off the kernel does not
    # silently re-introduce rounding — else bf16). Default "auto": default
    # numerics match the XLA path; bf16 is the documented opt-in for the
    # extra throughput (VERDICT r2 #8; both measured in PERF.md).
    kernel_dtype: str = "auto"

    @property
    def row_capacity(self) -> int:
        return self.minibatch * self.nnz_per_row


def _loss_dual(loss: str, y01, xw):
    """Per-example objective and gradient dual d = dObj/dXw.

    logit (reference linear/loss.h:93-130): obj = softplus(xw) - y*xw,
    d = sigmoid(xw) - y    (y in {0,1})
    square_hinge (loss.h:132-157): obj = max(0, 1 - ys*xw)^2,
    d = -2 ys max(0, 1 - ys*xw)   (ys in {-1,+1})
    """
    if loss == "logit":
        obj = jax.nn.softplus(xw) - y01 * xw
        d = jax.nn.sigmoid(xw) - y01
    elif loss == "square_hinge":
        ys = 2.0 * y01 - 1.0
        m = jnp.maximum(0.0, 1.0 - ys * xw)
        obj = m * m
        d = -2.0 * ys * m
    else:
        raise ValueError(f"unknown loss {loss!r}")
    return obj, d


def _update(algo: str, state, g, touched, cfg: LinearConfig):
    """Per-bucket update rules over whole tables: the handle math the
    fused kernel applies to a tile (ops/fused_update.apply_handle).
    Returns (new_state, new_w), new_w the step's |w|_0 delta. FTRL's
    state["w"] is written and not read: it is the derived table."""
    z2, n2, w2, w_old = apply_handle(
        algo, state.get("z"), state.get("n"),
        None if algo == "ftrl" else state["w"], g, touched,
        lr_eta=cfg.lr_eta, lr_beta=cfg.lr_beta, lambda_l1=cfg.lambda_l1,
        lambda_l2=cfg.lambda_l2)
    out = dict(state, w=w2)
    if z2 is not None:
        out["z"] = z2
    if n2 is not None:
        out["n"] = n2
    new_w = (jnp.sum(w2 != 0) - jnp.sum(w_old != 0)).astype(jnp.float32)
    return out, new_w


def _tables_for(algo: str) -> dict[str, TableSpec]:
    t = {"w": TableSpec()}
    if algo == "ftrl":
        # z, n are the state; w is their derived table (derived_tables),
        # written by the update and read by the pull alone
        t["z"] = TableSpec()
        t["n"] = TableSpec(wire_cap="bf16")  # second moment: see TableSpec
    elif algo == "adagrad":
        t["n"] = TableSpec(wire_cap="bf16")
    return t


class LinearLearner(mbl.MinibatchLearner):
    """Jitted train/eval/predict steps over a sharded weight table."""

    def __init__(self, cfg: LinearConfig, mesh=None):
        super().__init__(cfg, mesh)
        self.store = KVStore(self.mesh, cfg.num_buckets, _tables_for(cfg.algo))
        D = self.mesh.shape.get("data", 1)
        M = self.mesh.shape.get("model", 1)
        # per-shard kernel constraints: each model shard owns whole tiles,
        # each data shard owns whole lane groups (mesh_coo_* wrappers)
        shapes_ok = (cfg.num_buckets % (M * ck.TILE) == 0
                     and cfg.minibatch % (D * ck.LANES) == 0)
        self.use_pallas = cfg.kernel == "pallas" or (
            cfg.kernel == "auto"
            and jax.default_backend() == "tpu"
            and shapes_ok
        )
        why_not = ""
        if cfg.kernel == "xla":
            why_not = "kernel=xla"
        elif jax.default_backend() == "tpu" and not shapes_ok:
            why_not = (f"num_buckets % {M * ck.TILE} or minibatch % "
                       f"{D * ck.LANES} != 0")
        #: start-up statement of where and how this learner runs
        self.placement = describe_placement(self.mesh, "linear",
                                            self.use_pallas, why_not)
        # mesh layout (shard_map + psum collectives) whenever any axis > 1
        self._mesh_coo = self.use_pallas and (D > 1 or M > 1)
        if self.use_pallas and not self._mesh_coo:
            # which body of ck.pack_tile_coo this process has for the
            # compacted (tcoo) batches: counted a batch as
            # linear.pack.native over linear.pack.batches
            self.placement += (" tcoo_pack="
                               + ("native" if native.available() else "numpy"))
        self._shard_cap = ck.mesh_capacity(cfg.row_capacity, D, M)
        # the block a shard's runs are padded to, from the same geometry
        self._shard_blk = ck.mesh_block(self._shard_cap,
                                        cfg.num_buckets // M)
        if self.use_pallas:
            assert cfg.num_buckets % (M * ck.TILE) == 0, (
                f"pallas kernel needs num_buckets % {M * ck.TILE} == 0")
            assert cfg.minibatch % (D * ck.LANES) == 0, (
                f"pallas kernel needs minibatch % {D * ck.LANES} == 0")
        self._coo_dtype = mbl.kernel_dtype(cfg)

        mesh, dt = self.mesh, self._coo_dtype
        rows = partial(jax.device_put, device=self._bsh1)
        cells = partial(jax.device_put, device=NamedSharding(
            mesh, P("data", "model", None)))
        self._kinds = {
            "xla": mbl._Kind(
                lambda db, train: db,
                mbl._device_args(lambda db, train: (db.seg, db.idx, db.val),
                                 rows),
                *self._dense_steps(
                    lambda w, seg, idx, val, n: spmv(seg, idx, val, w, n),
                    lambda d, seg, idx, val, n: self.store.constrain(
                        "w", spmv_t(seg, idx, val, d, n))),
                _nonzero_ids),
            # NOTE r5: a row-major xw (XLA row gather from a widened w
            # table) was tried for coo and measured ~50 ns/row — the
            # dense table (num_buckets x 8 B, 32 MB at the headline
            # shape) is too large for the fast-gather regime (PERF.md
            # "Row-gather regimes"), and so, measured again in PR 32, is
            # the compact domain at any table that engages it. The
            # radix-image kernel pulls for every Pallas kind.
            "coo": mbl._Kind(
                self._pack_coo,
                mbl._device_args(
                    lambda p, train: (p.idx, p.seg, p.val, p.tmap, p.first),
                    jnp.asarray),
                *self._dense_steps(partial(ck.coo_spmv, dtype=dt),
                                   partial(ck.coo_spmv_t, dtype=dt)),
                _nonzero_ids),
            # tiles shard_map'ed over the model axis, rows over the data
            # axis; psum plays ZPull/ZPush (async_sgd.h:277-287). The
            # packed batch holds shard-local layouts, so its touched set
            # is left to the full delta scan
            "mcoo": mbl._Kind(
                self._pack_mcoo,
                mbl._device_args(
                    lambda mc, train: (mc.sidx, mc.sseg, mc.sval, mc.tmap,
                                       mc.first),
                    cells, rows),
                *self._dense_steps(
                    partial(ck.mesh_coo_spmv, mesh, dtype=dt),
                    partial(ck.mesh_coo_spmv_t, mesh, dtype=dt)),
                lambda mc: None),
        }

        # the compacted kind ("tcoo") joins the table lazily, once the
        # unique-key capacity is known (auto mode sizes it from the first
        # batch); the lock serializes the decide+build against concurrent
        # loader threads
        self._compact_cap: Optional[int] = None
        self._compact_lock = threading.Lock()
        if self._mesh_coo or not self.use_pallas or cfg.compact_cap == 0:
            self._compact_cap = 0

    # -- global-mesh SPMD protocol (apps/_runner._global_train) ------------
    def global_step_protocol(self):
        """(train_fn, eval_fn) over GLOBAL batch arrays: each runs the
        XLA kind's step and returns its progress, read off the device
        in one read."""
        xla = self._kinds["xla"]

        def train_fn(args, rng):
            self.store.state, prog = xla.train(self.store.state, *args)
            return mbl.read_progress(prog, mbl.TRAIN_KEYS)

        def eval_fn(args):
            return mbl.read_progress(xla.eval(self.store.state, *args),
                                     mbl.EVAL_KEYS)

        return train_fn, eval_fn

    def global_predict_protocol(self):
        """pred_fn over (seg, idx, val, mask) GLOBAL arrays returning
        (margins pinned to the batch sharding — so each rank reads back
        exactly its contributed rows — and the GLOBAL live-row count
        that drives the lockstep drain decision)."""
        predict, bsh = self._kinds["xla"].predict, self._bsh1

        @jax.jit
        def pred(state, seg, idx, val, mask):
            xw = predict(state, seg, idx, val)
            return jax.lax.with_sharding_constraint(xw, bsh), jnp.sum(mask)

        def pred_fn(args):
            seg, idx, val, mask = args
            return pred(self.store.state, seg, idx, val, mask)

        return pred_fn

    # -- the jitted steps ----------------------------------------------------
    def _read_steps(self, pull):
        """(eval, predict) of a kind whose `pull(w, *batch, rows)` is
        xw = X w over its own batch arrays."""
        cfg = self.cfg

        @jax.jit
        def eval_step(state, *args):
            *batch, label, mask = args
            xw = pull(state["w"], *batch, label.shape[0])
            obj, _ = _loss_dual(cfg.loss, label, xw)
            return mbl.pack_progress(_progress(obj, xw, label, mask),
                                     mbl.EVAL_KEYS)

        @jax.jit
        def predict_step(state, *batch):
            return pull(state["w"], *batch, cfg.minibatch)

        return eval_step, predict_step

    def _dense_steps(self, pull, push):
        """(train, eval, predict) of a kind that updates every bucket:
        `push(d, *batch, num_buckets)` is g = X^T d in table layout."""
        cfg = self.cfg

        @partial(jax.jit, donate_argnums=0)
        def train_step(state, *args):
            *batch, label, mask = args
            xw = pull(state["w"], *batch, label.shape[0])
            obj, d = _loss_dual(cfg.loss, label, xw)
            d = d * mask
            g = push(d, *batch, cfg.num_buckets)
            # touched is derived from the unquantized gradient so that
            # values the transfer filter rounds to zero still count as
            # pushed (the reference server receives and shrinks them too)
            raw_g = g
            g = quantize_push(g, cfg.fixed_bytes)
            # The touched mask marks buckets that received a push this step.
            # For FTRL it is unnecessary: g == 0 leaves z and n unchanged and
            # w is a pure function of (z, n), so untouched buckets are exact
            # no-ops without masking — this saves a second full scatter
            # (~25% of step time on TPU). adagrad/sgd apply repeated L1
            # shrinkage through l1l2_solve, so they still need the mask;
            # g != 0 reproduces the reference's per-key Push granularity
            # (async_sgd.h:160-175) except for exact zero-cancellation
            # gradients, which the reference would push and shrink on.
            if cfg.algo == "ftrl":
                touched = 1.0
            else:
                touched = (raw_g != 0).astype(jnp.float32)
            new_state, new_w = _update(cfg.algo, state, g, touched, cfg)
            return new_state, mbl.pack_progress(
                _progress(obj, xw, label, mask, new_w), mbl.TRAIN_KEYS)

        return (train_step, *self._read_steps(pull))

    # -- unique-key compaction ---------------------------------------------
    def ensure_compact(self, idx) -> int:
        """Decide (once, from the first batch) whether the unique-key
        compacted path engages and build its kind record. Returns the
        compact capacity (0 = dense path)."""
        with self._compact_lock:
            if self._compact_cap is None:
                cap = self._decide_compact_cap(idx)
                if cap:
                    self._build_tcoo(cap)
                # publish the cap only after the record exists, so a
                # racing reader can never see cap set but no "tcoo" kind
                self._compact_cap = cap
        return self._compact_cap

    def _decide_compact_cap(self, idx) -> int:
        """Pick the compact slot capacity from the first batch: 1.5x
        headroom in update blocks over what the batch needs (batches draw
        from the same key distribution; overflow falls back to
        drop-and-warn), rounded to whole tiles. Engaged only when the
        compact domain is well under the table size — otherwise the dense
        path's one padding block a tile and its O(num_buckets) update
        sweep are already cheaper than the extra tile_gather /
        scatter_update streaming (the factor 32 was measured on v5e
        with full-width blocks and not swept again since a block costs
        what it holds, ops/coo_kernels._live_chunks)."""
        cfg = self.cfg
        if cfg.compact_cap > 0:
            return -(-cfg.compact_cap // ck.TILE) * ck.TILE
        ids = np.unique(np.asarray(idx, np.int64))
        blocks = ck.tile_blocks_needed(ids, ck.TILE)
        cand = -(-int(1.5 * blocks) * ck.BLK_U // ck.TILE) * ck.TILE
        if cfg.num_buckets >= 32 * cand:
            return cand
        return 0

    def _build_tcoo(self, U: int):
        cfg, dt = self.cfg, self._coo_dtype
        def pull_c(w, uniq, tmap_u, sidx, sseg, sval, tmap, first, rows):
            # the touched weights into the compact domain, then the
            # radix-image kernel over the COO stream the push walks. Not
            # an XLA row gather from the compact domain: that takes 17.1
            # ms against this kernel's 8.7 at 2^29 buckets (U = 12.6 M)
            # and 12.0 against 8.3 at 2^26 (U = 1.6 M), v5e, 65,536 x 39
            # (PERF.md §6, PR 32), so one pull serves every size
            wc = ck.tile_gather(w.reshape(-1, ck.LANES), uniq, tmap_u,
                                dtype=dt)
            return ck.coo_spmv(wc, sidx, sseg, sval, tmap, first, rows,
                               dtype=dt)

        @partial(jax.jit, donate_argnums=0)
        def train_step_tcoo(state, uniq, tmap_u, first_u, last_u,
                            sidx, sseg, sval, tmap, first, label, mask):
            xw = pull_c(state["w"], uniq, tmap_u, sidx, sseg, sval, tmap,
                        first, cfg.minibatch)
            obj, d = _loss_dual(cfg.loss, label, xw)
            d = d * mask
            g = ck.coo_spmv_t(d, sidx, sseg, sval, tmap, first, U, dtype=dt)
            # the scatter, quantization filter, touched masking, and the
            # per-key handle update all happen inside the fused kernel,
            # in place on the touched tiles
            new_state, new_w = scatter_update(
                cfg.algo, state, g, uniq, tmap_u, first_u, last_u,
                lr_eta=cfg.lr_eta, lr_beta=cfg.lr_beta,
                lambda_l1=cfg.lambda_l1, lambda_l2=cfg.lambda_l2,
                fixed_bytes=cfg.fixed_bytes, dtype=dt)
            return new_state, mbl.pack_progress(
                _progress(obj, xw, label, mask, new_w), mbl.TRAIN_KEYS)

        def arrays(tc, train):
            # every step pulls over the COO stream; the update-block
            # bounds feed the train step's fused scatter alone, and go
            # only with it
            p = tc.coo
            mid = (tc.first_u, tc.last_u) if train else ()
            return (tc.uniq, tc.tmap_u, *mid, p.idx, p.seg, p.val, p.tmap,
                    p.first)

        self._kinds["tcoo"] = mbl._Kind(
            self._pack_tcoo, mbl._device_args(arrays, jnp.asarray),
            train_step_tcoo, *self._read_steps(pull_c),
            lambda tc: (tc.uniq[tc.uniq < cfg.num_buckets].astype(np.int64),))

    # -- batch kinds and their packs ---------------------------------------
    def _choose_kind(self, db: DeviceBatch) -> str:
        """The one place that decides what kind of batch this learner
        makes: everything downstream looks `self._kinds` up by the name
        the batch's tuple carries."""
        if not self.use_pallas:
            return "xla"
        if self._mesh_coo:
            return "mcoo"
        if self.ensure_compact(db.idx):
            return "tcoo"
        return "coo"

    def _pack_mcoo(self, db: DeviceBatch, train: bool):
        D = self.mesh.shape.get("data", 1)
        M = self.mesh.shape.get("model", 1)
        mc = ck.pack_mesh_coo(db.idx, db.seg, db.val,
                              self.cfg.num_buckets, self.cfg.minibatch,
                              D, M, self._shard_cap, self._shard_blk)
        _MESH_NNZ_MAX.inc(int(mc.cell_nnz.max()))
        _MESH_NNZ_SUM.inc(int(mc.cell_nnz.sum()))
        _MESH_SLOTS.inc(mc.sval.size)
        if mc.dropped_nnz:
            _MESH_DROPPED.inc(mc.dropped_nnz)
            _log.warning(
                "mesh shard overflow: dropped %d nonzeros — raise "
                "nnz_per_row or mesh_capacity slack", mc.dropped_nnz)
        if train:  # pull and push walk the same COO blocks
            _count_chunks(mc.sval, 0, self._shard_blk, 2)
        return mc

    def _pack_tcoo(self, db: DeviceBatch, train: bool):
        tc = ck.pack_tile_coo(db.idx, db.seg, db.val,
                              self.cfg.num_buckets, self._compact_cap,
                              capacity=self.cfg.row_capacity)
        _PACK_BATCHES.inc()
        _PACK_NATIVE.inc(int(tc.packed_native))
        if tc.dropped_nnz:
            _log.warning(
                "compaction overflow: dropped %d unique keys "
                "(%d nonzeros) — raise compact_cap (currently %d)",
                tc.dropped_uniq, tc.dropped_nnz, self._compact_cap)
        if train:  # tile_gather and the fused update; pull and push
            _count_chunks(tc.uniq, self.cfg.num_buckets, ck.BLK_U, 2)
            _count_chunks(tc.coo.val, 0, ck.BLK, 2)
        return tc

    def _pack_coo(self, db: DeviceBatch, train: bool):
        return ck.pack_sorted_coo(db.idx, db.seg, db.val,
                                  self.cfg.num_buckets,
                                  capacity=self.cfg.row_capacity)

    # -- epoch pack cache ----------------------------------------------------
    #: bump when prepare_batch's output layout changes for identical input
    _PACK_VERSION = 3

    def pack_cache_token(self, train: bool = True):
        """Everything (beyond the raw batch bytes) that decides what
        prepare_batch emits, or None while that is still undecided. The
        compact-path decision is made lazily from the first batch
        (ensure_compact), so until `_compact_cap` resolves the pack
        output is not yet a pure function of the key — the first cold
        part simply goes uncached and caching engages from the next
        part on."""
        if self._compact_cap is None:
            return None
        cfg = self.cfg
        return ("linear", self._PACK_VERSION, self.use_pallas,
                self._mesh_coo, self._compact_cap, self._shard_cap,
                self._shard_blk, cfg.minibatch, cfg.nnz_per_row,
                cfg.num_buckets,
                self.mesh.shape.get("data", 1),
                self.mesh.shape.get("model", 1),
                ck.TILE, ck.BLK, ck.BLK_U, ck.LANES)


def _progress(obj, xw, label, mask, new_w=None):
    """Per-batch mergeable progress vector (reference linear/progress.h:
    objv, auc, acc, #ex, new_w; scheduler-side weighted averaging).
    clk/pclk feed the COPC column (binary_class_evaluation.h:76-85);
    new_w is the |w|_0 delta the train step computed device-side."""
    n = jnp.sum(mask)
    p = {
        "objv": jnp.sum(obj * mask),
        "auc": M.auc(label, xw, mask) * n,
        "acc": M.accuracy(label, xw, mask) * n,
        "logloss": M.logloss(label, xw, mask) * n,
        "nex": n,
        "clk": jnp.sum(label * mask),
        "pclk": jnp.sum(jax.nn.sigmoid(xw) * mask),
    }
    if new_w is not None:
        p["new_w"] = new_w
    return p
