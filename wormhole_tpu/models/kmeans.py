"""Spherical k-means, TPU-native.

Parity target: reference learn/kmeans/kmeans.cc — BSP Lloyd iterations
with cosine distance: rows are unit-normalized, each rank sums its
assigned points into a (k x d+1) matrix (count in the last column), the
matrix is allreduced, and centroids are recomputed by dividing by counts
(kmeans.cc:169-208); init picks k random rows broadcast from random ranks
(:89-106); per-iteration checkpoints bound lost work on failure (:204).

TPU design: the assignment pass is two matmuls on the MXU — similarities
X_hat @ C_hat^T and the accumulation onehot(assign)^T @ [X | 1] — with the
minibatch sharded over the data axis and the (k x d+1) partial sums
psum-reduced by XLA (the rabit::Allreduce of kmeans.cc:190). The host
drives Lloyd iterations and writes a checkpoint per iteration.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from wormhole_tpu.data import pack_cache as _pc
from wormhole_tpu.data.rowblock import RowBlock, to_device_batch
from wormhole_tpu.parallel.mesh import (batch_sharding, describe_placement,
                                        make_mesh, replicated)
from wormhole_tpu.solver.workload import iter_parts, iter_rowblocks


@dataclasses.dataclass
class KmeansConfig:
    train_data: str = ""
    data_format: str = "libsvm"
    num_clusters: int = 10
    dim: int = 0               # feature-space dim; 0 = discover from data
    max_iter: int = 10
    minibatch: int = 4096
    nnz_per_row: int = 64
    num_parts_per_file: int = 1
    model_out: Optional[str] = None
    checkpoint_dir: Optional[str] = None  # per-iter state for resume
    seed: int = 0
    # multi-process SPMD over one jax.distributed mesh (apps/kmeans.py
    # _global_worker; the reference's rabit world)
    global_mesh: bool = False
    # assignment kernel: dense ([B, d] densify + two MXU matmuls — best
    # for small/moderate d like MNIST-784) | sparse (per-nonzero gathers
    # and scatter-adds, never materializing [B, d] — required for huge
    # hashed feature spaces, the reference's streaming sparse rows,
    # kmeans.cc:119-130) | auto (sparse when d > 16384)
    assign_kernel: str = "auto"
    # densify-kernel dtype for the packed fast path: f32 = exact
    # (matches the XLA scatter bit-for-bit); bf16 = documented
    # throughput opt-in (input values round to bfloat16; sums still
    # accumulate in f32) — ~40% faster on v5e
    kernel_dtype: str = "f32"


def discover_dim(pattern: str, fmt: str = "libsvm",
                 num_parts_per_file: int = 1) -> int:
    """Max feature id + 1 over all files — the Allreduce<Max> dimension
    discovery of the reference BSP apps (kmeans.cc:160, lbfgs.cc:107-113)."""
    max_id = -1
    for blk in iter_rowblocks(pattern, num_parts_per_file, fmt,
                              node="dim-scan"):
        if blk.nnz:
            max_id = max(max_id, int(blk.index.max()))
    return max_id + 1


class KmeansLearner:
    def __init__(self, cfg: KmeansConfig, mesh=None):
        if cfg.dim == 0:
            cfg.dim = discover_dim(cfg.train_data, cfg.data_format,
                                   cfg.num_parts_per_file)
        assert cfg.dim > 0, "empty data: could not discover dim"
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_mesh(num_model=1)
        self._bsh = batch_sharding(self.mesh, 1)
        self.centroids: Optional[jax.Array] = None  # [k, d], row-normalized
        self.start_iter = 0
        # epoch pack cache (data/pack_cache.py): None unless enabled by
        # env — the Lloyd loop replays identical batches every iteration
        self.pack_cache = _pc.from_env()

        k, d, B = cfg.num_clusters, cfg.dim, cfg.minibatch
        self._use_sparse = cfg.assign_kernel == "sparse" or (
            cfg.assign_kernel == "auto" and d > 16384)

        @jax.jit
        def densify(seg, idx, val, mask):
            """Sparse COO batch -> row-normalized dense [B, d]."""
            X = jnp.zeros((B, d), jnp.float32).at[seg, idx].add(val)
            X = X * mask[:, None]
            norm = jnp.linalg.norm(X, axis=1, keepdims=True)
            return X / jnp.maximum(norm, 1e-12)

        def _assign_from_dense(C, X, mask):
            """Assignment + accumulation given row-normalized dense X:
            returns ([k, d] sums, [k] counts, batch cost). Cosine
            distance = 1 - X_hat.C_hat."""
            Cn = C / jnp.maximum(
                jnp.linalg.norm(C, axis=1, keepdims=True), 1e-12)
            sim = X @ Cn.T                                   # MXU [B, k]
            assign = jnp.argmax(sim, axis=1)
            best = jnp.max(sim, axis=1)
            onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32)
            onehot = onehot * mask[:, None]
            sums = onehot.T @ X                              # MXU [k, d]
            counts = jnp.sum(onehot, axis=0)
            cost = jnp.sum((1.0 - best) * mask)
            return sums, counts, cost

        @jax.jit
        def assign_accumulate(C, seg, idx, val, mask):
            """One assignment pass over a raw COO batch."""
            return _assign_from_dense(C, densify(seg, idx, val, mask),
                                      mask)

        @jax.jit
        def assign_accumulate_sparse(C, seg, idx, val, mask):
            """Same contract without ever building [B, d]: similarities
            by gathering centroid columns per nonzero and segment-summing
            per row; accumulation by scatter-adding normalized values
            into the assigned centroid's row. Work is O(nnz * k), HBM is
            O(k * d) — the sparse streaming of the reference
            (kmeans.cc:119-130) for hashed feature spaces where B x d
            cannot exist."""
            Cn = C / jnp.maximum(
                jnp.linalg.norm(C, axis=1, keepdims=True), 1e-12)
            # row norms from the nonzeros alone
            sq = jax.ops.segment_sum(val * val, seg, num_segments=B)
            inv_norm = 1.0 / jnp.maximum(jnp.sqrt(sq), 1e-12)
            # sim[i, c] = sum_nz val * Cn[c, idx] / ||x_i||
            contrib = val[:, None] * jnp.take(Cn.T, idx, axis=0)  # [nnz, k]
            sim = jax.ops.segment_sum(contrib, seg, num_segments=B)
            sim = sim * inv_norm[:, None]
            # padding rows (mask 0) must not attract real similarity
            sim = sim * mask[:, None]
            assign = jnp.argmax(sim, axis=1)
            best = jnp.max(sim, axis=1)
            xhat_nz = val * jnp.take(inv_norm * mask, seg)
            sums = jnp.zeros((k, d), jnp.float32).at[
                jnp.take(assign, seg), idx].add(xhat_nz)
            counts = jax.ops.segment_sum(mask, assign, num_segments=k)
            cost = jnp.sum((1.0 - best) * mask)
            return sums, counts, cost

        self._assign_accumulate = (
            assign_accumulate_sparse if self._use_sparse
            else assign_accumulate)
        self._assign_dense = assign_accumulate
        self._assign_sparse = assign_accumulate_sparse
        self._densify = densify

        # packed fast path: the XLA densify scatter (2.6M random writes
        # at the MNIST bench shape, ~26 ms — the step's wall, PERF.md)
        # becomes the tile-scatter kernel over a flattened
        # (row * stride + col) bucket space — the same coo_spmv_t that
        # plays the linear gradient scatter. f32 (HIGHEST) so densify
        # is exact; the host pack rides the loader threads like every
        # other learner's.
        from wormhole_tpu.ops import coo_kernels as ck

        self._flat_stride = -(-d // 128) * 128
        self._num_flat = -(-(B * self._flat_stride) // ck.TILE) * ck.TILE
        # the kernel's dual vector wants lane-aligned rows (odd batch
        # sizes keep the scatter densify), and the raw pallas call has
        # no mesh variant — a data-sharded in-process mesh keeps the
        # GSPMD-partitioned scatter path. Off-TPU the kernel would only
        # run interpreted, which nothing asks for here.
        blockers = [reason for bad, reason in (
            (self._use_sparse, "assign_kernel=sparse"),
            (B % 128 != 0, "minibatch % 128 != 0"),
            (self.mesh.shape.get("data", 1) != 1, "data-sharded mesh"),
        ) if bad]
        self._use_packed = (not blockers
                            and jax.default_backend() == "tpu")
        #: start-up statement of where and how this learner runs
        self.placement = describe_placement(
            self.mesh, "kmeans", self._use_packed, "; ".join(blockers))
        assert cfg.kernel_dtype in ("f32", "bf16"), (
            f"kernel_dtype must be 'f32' or 'bf16', got "
            f"{cfg.kernel_dtype!r}")

        _kdt = (jnp.bfloat16 if cfg.kernel_dtype == "bf16"
                else jnp.float32)

        @jax.jit
        def assign_accumulate_packed(C, sidx, sseg, sval, tmap, first,
                                     mask):
            ones = jnp.ones((B,), jnp.float32)
            Xf = ck.coo_spmv_t(ones, sidx, sseg, sval, tmap, first,
                               self._num_flat, dtype=_kdt)
            X = Xf[: B * self._flat_stride].reshape(
                B, self._flat_stride)[:, :d]
            X = X * mask[:, None]
            norm = jnp.linalg.norm(X, axis=1, keepdims=True)
            X = X / jnp.maximum(norm, 1e-12)
            return _assign_from_dense(C, X, mask)

        self._assign_packed = assign_accumulate_packed

    def pack_batch(self, seg, idx, val):
        """Host-side pack for the flat-bucket densify kernel (numpy, on
        the loader threads; device transfer happens at consumption so
        the pack output stays cacheable)."""
        from wormhole_tpu.ops import coo_kernels as ck

        flat = (np.asarray(seg, np.int64) * self._flat_stride
                + np.asarray(idx, np.int64))
        cap = self.cfg.minibatch * self.cfg.nnz_per_row
        p = ck.pack_sorted_coo(flat, seg, val, self._num_flat,
                               capacity=cap)
        return (p.idx, p.seg, p.val, p.tmap, p.first)

    # -- data plumbing ------------------------------------------------------
    # The Lloyd loop re-reads the SAME batches every iteration (the seed
    # only matters to shuffle/negative sampling, both off here), which
    # makes k-means the ideal epoch-cache client: iteration 2+ replays
    # prepared batches from the cache instead of re-parsing and
    # re-packing. The loop runs per part so the cache keys whole parts.

    #: bump when _prep_db / pack_batch output layout changes
    _PACK_VERSION = 1

    def _part_key(self, f, mode: str):
        from wormhole_tpu.ops import coo_kernels as ck

        cfg = self.cfg
        return ("kmeans", self._PACK_VERSION, mode, cfg.dim,
                cfg.minibatch, cfg.nnz_per_row, self._flat_stride,
                self._num_flat, ck.TILE, ck.BLK, ck.LANES,
                f.filename, f.part, f.num_parts, cfg.data_format,
                _pc.file_stamp(f.filename))

    def _prep_db(self, blk: RowBlock):
        cfg = self.cfg
        if blk.nnz and int(blk.index.max()) >= cfg.dim:
            raise ValueError(
                f"feature id {int(blk.index.max())} >= dim "
                f"{cfg.dim}; set dim=0 to auto-discover")
        return to_device_batch(blk, cfg.minibatch,
                               cfg.minibatch * cfg.nnz_per_row, cfg.dim)

    def _host_dbs(self, mode: str, prep):
        """Per-part cached DeviceBatch/packed stream; with no cache
        configured this is exactly the old flat loop."""
        from wormhole_tpu.data.minibatch import MinibatchIter

        cfg = self.cfg
        for f in iter_parts(cfg.train_data, cfg.num_parts_per_file,
                            cfg.data_format, node="kmeans"):
            def raw(f=f):
                return MinibatchIter(f.filename, f.part, f.num_parts,
                                     f.format,
                                     minibatch_size=cfg.minibatch)
            key = (self._part_key(f, mode)
                   if self.pack_cache is not None else None)
            yield from _pc.iter_part_cached(self.pack_cache, key,
                                            raw, prep)

    def _host_batches(self, seed=0):
        yield from self._host_dbs("raw", self._prep_db)

    def _batches(self, seed=0):
        for db in self._host_batches(seed):
            put = lambda x: jax.device_put(x, self._bsh)
            yield (put(db.seg), put(db.idx), put(db.val),
                   put(db.row_mask))

    def _batches_packed(self, seed=0):
        """(packed flat-bucket COO, mask) pairs for the fast dense
        path."""
        def prep(blk):
            db = self._prep_db(blk)
            return (self.pack_batch(db.seg, db.idx, db.val), db.row_mask)

        for pk, mask in self._host_dbs("packed", prep):
            yield (tuple(jnp.asarray(a) for a in pk),
                   jax.device_put(mask, self._bsh))

    # -- init: random rows (kmeans.cc:89-106) -------------------------------
    def init_centroids(self) -> None:
        cfg = self.cfg
        rng = np.random.default_rng(cfg.seed)
        rows = []
        for b in self._batches():
            if self._use_sparse:
                # huge d: densify ONLY the sampled candidate rows on the
                # host instead of the whole [B, d] batch
                seg, idx, val, mask = (np.asarray(x) for x in b)
                n_real = int(mask.sum())
                take = min(cfg.num_clusters * 4, n_real)
                pick = rng.choice(n_real, size=take, replace=False)
                slot = np.full(len(mask), -1, np.int64)
                slot[pick] = np.arange(take)
                keep = (slot[seg] >= 0) & (val != 0)
                X = np.zeros((take, cfg.dim), np.float32)
                X[slot[seg[keep]], idx[keep].astype(np.int64)] = val[keep]
                norm = np.maximum(
                    np.linalg.norm(X, axis=1, keepdims=True), 1e-12)
                rows.append(X / norm)
            else:
                seg, idx, val, mask = b
                X = np.asarray(self._densify(seg, idx, val, mask))
                n_real = int(np.asarray(mask).sum())
                take = min(cfg.num_clusters * 4, n_real)
                rows.append(X[rng.choice(n_real, size=take, replace=False)])
            if sum(len(r) for r in rows) >= cfg.num_clusters * 8:
                break
        cand = np.concatenate(rows)
        if len(cand) < cfg.num_clusters:
            # fewer rows than clusters: reuse rows with jitter so every
            # centroid is initialized (empty clusters resolve in-loop)
            extra = cand[rng.integers(0, len(cand),
                                      cfg.num_clusters - len(cand))]
            extra = extra + 0.01 * rng.standard_normal(extra.shape)
            cand = np.concatenate([cand, extra.astype(cand.dtype)])
        # k distinct-ish rows among candidates
        pick = rng.choice(len(cand), size=cfg.num_clusters, replace=False)
        self.centroids = jax.device_put(
            jnp.asarray(cand[pick]), replicated(self.mesh))

    # -- Lloyd loop (kmeans.cc:169-208) -------------------------------------
    def run(self, verbose: bool = True) -> float:
        cfg = self.cfg
        if verbose:
            print(self.placement, flush=True)
        if self.centroids is None and not self._try_resume():
            self.init_centroids()
        cost = float("nan")
        for it in range(self.start_iter, cfg.max_iter):
            k, d = cfg.num_clusters, cfg.dim
            sums = jnp.zeros((k, d), jnp.float32)
            counts = jnp.zeros((k,), jnp.float32)
            cost_acc = jnp.zeros((), jnp.float32)
            n = 0
            if self._use_packed:
                batches = (
                    (self._assign_packed, (*pk, mask))
                    for pk, mask in self._batches_packed(seed=it))
            else:
                batches = ((self._assign_accumulate, b)
                           for b in self._batches(seed=it))
            for fn, b in batches:
                s, c, co = fn(self.centroids, *b)
                sums, counts = sums + s, counts + c
                cost_acc = cost_acc + co
                n += 1
            # empty clusters keep their previous centroid (divide-by-count
            # only where count > 0)
            new_C = jnp.where(
                counts[:, None] > 0,
                sums / jnp.maximum(counts[:, None], 1.0),
                self.centroids,
            )
            self.centroids = jax.device_put(new_C, replicated(self.mesh))
            cost = float(cost_acc) / max(float(jnp.sum(counts)), 1.0)
            if verbose:
                print(f"kmeans iter {it}: mean cosine distance {cost:.6f}",
                      flush=True)
            if cfg.checkpoint_dir:
                self._checkpoint(it)
        if cfg.model_out:
            self.save(cfg.model_out)
        return cost

    # -- persistence --------------------------------------------------------
    def save(self, path: str) -> None:
        """Text centroids, rank-0-writes-model parity (kmeans.cc:212-217)."""
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        C = np.asarray(self.centroids)
        with open(path, "w") as f:
            for row in C:
                f.write(" ".join(f"{v:.6g}" for v in row) + "\n")

    def _checkpoint(self, it: int) -> None:
        from wormhole_tpu.utils.checkpoint import atomic_savez

        os.makedirs(self.cfg.checkpoint_dir, exist_ok=True)
        atomic_savez(os.path.join(self.cfg.checkpoint_dir, "state.npz"),
                     centroids=np.asarray(self.centroids), next_iter=it + 1)

    def _try_resume(self) -> bool:
        """LoadCheckPoint parity (kmeans.cc:157-164): resume mid-run."""
        cdir = self.cfg.checkpoint_dir
        if not cdir or not os.path.exists(os.path.join(cdir, "state.npz")):
            return False
        st = np.load(os.path.join(cdir, "state.npz"))
        self.centroids = jax.device_put(jnp.asarray(st["centroids"]),
                                        replicated(self.mesh))
        self.start_iter = int(st["next_iter"])
        return True
