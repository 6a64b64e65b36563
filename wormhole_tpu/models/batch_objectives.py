"""Batch objectives for the L-BFGS solver: linear and FM.

Parity targets:
- learn/lbfgs-linear (lbfgs.cc, linear.h): logistic/linear regression with
  the bias stored at w[num_feature] (linear.h:91-99), feature count
  discovered as the max column id over all data shards (lbfgs.cc:107-113,
  an Allreduce<Max> in the reference — here a max over the host scan), and
  L1 via the solver's OWL-QN path.
- learn/lbfgs-fm (fm.cc, fm.h): factorization machine with the flat
  parameter layout [w(d); V(d x k); bias] (fm.cc:133-140), V initialized
  N(0, sigma) (fm.cc:141-156), FM margin math (fm.h:84-107).

TPU design: the dataset is loaded once into fixed-shape device batches
sharded over the data axis (the reference's per-rank RowBlockIter cache);
the flat parameter vector is sharded over all devices. The general
formulation is a pure per-batch loss over `segment_sum` margins with
jax.grad for the gradient: it runs on any backend, mesh and dimension and
needs no backward pass written out (fm.cc:209-242), but on a TPU XLA
lowers its gather and scatter to a sort and serial fusions (0.07 % of a
gradient pass's roofline at 2^26 columns, PERF_LEDGER.jsonl PR 47). Where
one TPU device holds the whole vector and the dimension is a whole number
of table tiles, the linear objective's two products, X w and X^T d, run
instead on the packed-COO Pallas kernels (ops/coo_kernels.py) at float32,
over a layout of the resident rows made once, at construction
(`LinearObjFunction`, `packed_rule`; docs/lbfgs.md "The passes").
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from wormhole_tpu.data.rowblock import to_device_batch
from wormhole_tpu.obs.metrics import REGISTRY
from wormhole_tpu.ops import coo_kernels as ck
from wormhole_tpu.parallel.mesh import batch_sharding, describe_placement
from wormhole_tpu.solver.lbfgs import fetch
from wormhole_tpu.solver.workload import iter_rowblocks


# a modulus that leaves every int32 id as it is: "no fold"
_RAW_IDS = 2 ** 31 - 1


def _max_id(blk) -> int:
    """The largest raw column id of a block (-1 for an empty one)."""
    return int(blk.index.max()) if blk.nnz else -1


def _device_batch(blk, rows: int, cap: int, num_feature: int = 0):
    """A block as a fixed-shape DeviceBatch of the batch solvers: the one
    place their column ids are made. `num_feature` 0 keeps the raw ids,
    no hash kernel (batch solvers use the true feature space like the
    reference's RowBlockIter path); over 0 folds them `mod num_feature`
    through `to_device_batch`'s `num_buckets`, the way 64-bit keys (the
    Criteo format's) become columns. Either way the ids have to fit the
    device's int32 index."""
    top = num_feature - 1 if num_feature else _max_id(blk)
    assert top < _RAW_IDS, "batch objectives need int32 ids"
    return to_device_batch(blk, rows, cap, num_feature or _RAW_IDS)


def _put(db, bsh):
    """A DeviceBatch's five arrays resident on the device under `bsh`."""
    return tuple(jax.device_put(x, bsh) for x in (
        db.seg, db.idx, db.val, db.label, db.row_mask))


def load_batches(pattern: str, mesh, fmt: str = "libsvm",
                 minibatch: int = 4096, nnz_per_row: int = 64,
                 num_parts_per_file: int = 1, num_feature: int = 0):
    """Read all data into device-resident fixed-shape batches; returns
    (batches, num_feature). `num_feature` 0 discovers it as max id + 1
    over all shards (the Allreduce<Max> of lbfgs.cc:107-113); over 0 it
    is the dimension given, and ids are folded into it."""
    bsh = batch_sharding(mesh, 1)
    batches = []
    max_id = -1
    for blk in iter_rowblocks(pattern, num_parts_per_file, fmt, minibatch):
        max_id = max(max_id, _max_id(blk))
        batches.append(_put(_device_batch(
            blk, minibatch, minibatch * nnz_per_row, num_feature), bsh))
    return batches, num_feature or max_id + 1


def load_batches_global(pattern: str, mesh, env, fmt: str = "libsvm",
                        minibatch: int = 4096, nnz_per_row: int = 64,
                        num_parts_per_file: int = 1):
    """Multi-process variant of load_batches (requires an initialized
    jax.distributed cluster): each process reads its rank-slice of file
    parts (the reference RowBlockIter(rank, world) split, lbfgs.cc:
    229-234) and contributes minibatch/num_workers rows of every GLOBAL
    batch; ranks with fewer local batches pad with masked empties so all
    processes hold the same batch count — every eval/grad over a batch
    is an SPMD collective and must run in lockstep."""
    from wormhole_tpu.data.minibatch import MinibatchIter
    from wormhole_tpu.parallel import multihost as mh

    rank, nproc = env.rank, env.num_workers
    assert minibatch % nproc == 0, (minibatch, nproc)
    local_rows = minibatch // nproc
    local_cap = local_rows * nnz_per_row
    local, max_id = [], -1
    for f, k in mh.rank_parts(pattern, num_parts_per_file, env):
        for blk in MinibatchIter(f, k, num_parts_per_file, fmt,
                                 minibatch_size=local_rows):
            max_id = max(max_id, _max_id(blk))
            local.append(blk)
    n_batches = mh.global_scalar_max(len(local))
    num_feature = mh.global_scalar_max(max_id) + 1
    empty = mh.empty_rowblock()
    bsh = batch_sharding(mesh, 1)
    out = []
    for i in range(n_batches):
        blk = local[i] if i < len(local) else empty
        db = _device_batch(blk, local_rows, local_cap)
        out.append(mh.global_coo_batch(bsh, db, rank, local_rows,
                                       minibatch, nnz_per_row))
    return out, num_feature


def load_batches_bsp(pattern: str, mesh, env, client, fmt: str = "libsvm",
                     minibatch: int = 4096, nnz_per_row: int = 64,
                     num_parts_per_file: int = 1, key: str = "lbfgs_dim"):
    """BSP-allreduce variant of load_batches: each rank loads ITS stable
    slice of file parts into LOCAL device batches (no jax.distributed —
    parameters are replicated per rank and the solver reduces gradients
    and losses over the worker ring instead). The global feature count
    (the Allreduce<Max> of lbfgs.cc:107-113) is agreed through the
    scheduler BLOB channel: blobs persist, so a respawned worker
    re-reads the identical value without consuming a collective counter
    — its (version, seq) sequence stays aligned with the survivors'."""
    from wormhole_tpu.data.minibatch import MinibatchIter
    from wormhole_tpu.parallel import multihost as mh

    local, max_id = [], -1
    for f, k in mh.rank_parts(pattern, num_parts_per_file, env):
        for blk in MinibatchIter(f, k, num_parts_per_file, fmt,
                                 minibatch_size=minibatch):
            max_id = max(max_id, _max_id(blk))
            local.append(blk)
    client.blob_put(f"{key}_{env.rank}", np.int64(max_id))
    if env.rank == 0 and not client.call(op="blob_get", key=key)["ok"]:
        dims = [int(client.blob_get(f"{key}_{r}", timeout=120))
                for r in range(env.num_workers)]
        client.blob_put(key, np.int64(max(dims)))
    num_feature = int(client.blob_get(key, timeout=120)) + 1
    bsh = batch_sharding(mesh, 1)
    # a zero-part rank simply holds no batches
    batches = [_put(_device_batch(blk, minibatch, minibatch * nnz_per_row),
                    bsh) for blk in local]
    return batches, num_feature


class _BatchObjBase:
    """Shared accumulate-over-batches eval/grad driver.

    The flat parameter vector is sharded over ALL mesh devices — the
    reference's rank partition of the weight vector and its history basis
    (lbfgs.h:127-136, 557-645). num_dim is zero-padded up to a multiple
    of the device count (named shardings need even splits); the padding
    is provably inert: it starts 0, receives 0 gradient (no data column
    references it), has l1_mask 0, and every solver update is a linear
    combination of such vectors."""

    def __init__(self, batches, mesh):
        self.batches = batches
        self.mesh = mesh
        ndev = mesh.size
        self.num_dim_padded = -(-self.num_dim // ndev) * ndev
        self._psh = NamedSharding(mesh, P(tuple(mesh.axis_names)))

        loss = self._batch_loss

        @jax.jit
        def eval_batch(p, *b):
            return loss(p, *b)

        @jax.jit
        def grad_batch(p, *b):
            return jax.grad(loss)(p, *b)

        self._eval_batch = eval_batch
        self._grad_batch = grad_batch

    def eval(self, p) -> float:
        tot = jnp.zeros(())
        for b in self.batches:
            tot = tot + self._eval_batch(p, *b)
        # the pass's one blocking read, which waits for every batch's
        # program: counted and spanned like the solver's own
        return fetch(tot)

    def grad(self, p):
        g = jnp.zeros_like(p)
        for b in self.batches:
            g = g + self._grad_batch(p, *b)
        return g

    def place(self, p):
        pad = self.num_dim_padded - p.shape[0]
        if pad:
            p = jnp.concatenate([p, jnp.zeros(pad, p.dtype)])
        p = np.asarray(p)
        # make_array_from_callback works on multi-process meshes too
        # (device_put cannot target non-addressable devices)
        return jax.make_array_from_callback(
            p.shape, self._psh, lambda idx: p[idx])

    def pad_mask(self, m):
        """Extend a logical-length mask to the padded vector (padding 0)."""
        pad = self.num_dim_padded - m.shape[0]
        if pad:
            m = jnp.concatenate([m, jnp.zeros(pad, m.dtype)])
        return m


# Rows of a resident batch that one call of the packed-COO kernels takes.
# The kernels' row one-hot is rows / 128 wide, so a whole batch in one call
# is not an option; each chunk is packed over all the columns (a block a
# table tile at least), so a smaller chunk means more and emptier blocks.
ROW_CHUNK = 65536


def packed_rule(batches, num_feature: int, mesh, row_chunk: int,
                any_backend: bool = False) -> str:
    """Why these resident batches cannot take the packed-COO kernels, ""
    where they can: everything here is what the code can observe of the
    backend, the placement and the shapes (as `kernel: auto` of
    models/linear.py), no key of a configuration. `any_backend` is the
    tests' way to the interpreted kernels off the chip."""
    dev = mesh.devices.flat[0]
    if dev.platform != "tpu" and not any_backend:
        return f"backend is {dev.platform}, not tpu"
    if mesh.size != 1:
        return f"the vector is sharded over {mesh.size} devices"
    if num_feature <= 0 or num_feature % ck.TILE:
        return f"num_feature {num_feature} is not a multiple of {ck.TILE}"
    if not batches:
        return "no resident batch"
    for b in batches:
        rows = b[3].shape[0]
        if rows % row_chunk or row_chunk % ck.LANES:
            return (f"a batch of {rows} rows is not a multiple of the row "
                    f"chunk {row_chunk} and of {ck.LANES}")
    return ""


def pack_row_chunks(batches, num_feature: int, row_chunk: int):
    """The resident batches as row chunks packed for the COO kernels, or
    (None, why) where a column id lies outside the table. Each batch is
    read back once; its live triples (val != 0: padding and explicit
    zeros add nothing to either product) are cut into chunks of
    `row_chunk` rows, and each chunk is sorted by column and laid in
    BLK-padded per-tile runs over all `num_feature` columns
    (ck.pack_sorted_coo, the dense layout). Every chunk gets the block
    count of the one that needs most, so one compiled program runs them
    all. A chunk is (sidx, sseg, sval, tmap, first, label, mask), all on
    the batch's device; sseg counts rows from the chunk's first."""
    tiles = num_feature // ck.TILE
    cut, need = [], 0
    for seg, idx, val, label, mask in batches:
        seg, idx, val = (np.asarray(x) for x in (seg, idx, val))
        live = val != 0
        if not live.all():
            seg, idx, val = seg[live], idx[live], val[live]
        if idx.size and not 0 <= idx.min() <= idx.max() < num_feature:
            return None, (f"a column id outside [0, {num_feature}): "
                          f"{idx.min()}..{idx.max()}")
        if (seg[1:] < seg[:-1]).any():    # to_device_batch gives CSR order
            order = np.argsort(seg, kind="stable")
            seg, idx, val = seg[order], idx[order], val[order]
        rows = label.shape[0]
        ends = np.searchsorted(
            seg, np.arange(0, rows + 1, row_chunk, dtype=seg.dtype))
        for c, (a, b) in enumerate(zip(ends[:-1], ends[1:])):
            r0 = c * row_chunk
            per_tile = np.bincount(idx[a:b] // ck.TILE, minlength=tiles)
            need = max(need, int(np.maximum(
                -(-per_tile // ck.BLK), 1).sum()))
            cut.append((idx[a:b], seg[a:b] - r0, val[a:b],
                        label[r0:r0 + row_chunk], mask[r0:r0 + row_chunk]))
    # rounded up (by 64 blocks at 1,024 tiles) so that another sample of
    # the same data gets the same shapes, and the compile cache's programs
    step = max(tiles // 16, 1)
    need = -(-need // step) * step
    # packed_size(capacity, num_feature) == need * BLK
    capacity = (need - tiles) * ck.BLK
    chunks = []
    for idx, seg, val, label, mask in cut:
        p = ck.pack_sorted_coo(idx, seg, val, num_feature, capacity)
        dev = label.sharding
        chunks.append(tuple(jax.device_put(x, dev) for x in (
            p.idx, p.seg, p.val, p.tmap, p.first)) + (label, mask))
    return chunks, ""


class LinearObjFunction(_BatchObjBase):
    """Logistic regression, layout [w(d); bias].

    Two lowerings of the same two sparse products, chosen at construction
    by `packed_rule`: on one TPU device with `num_feature` a whole number
    of table tiles and the batches' rows a multiple of `ROW_CHUNK`, a pass
    is `coo_spmv` (X w) a row chunk, the loss or the dual d = (sigmoid(xw)
    - y) * mask in XLA, and for the gradient `coo_spmv_t` (X^T d) into
    table layout with the bias's sum(d) beside it, all at float32
    (`self.packed`; `self.placement` says which and why; every such pass
    counts itself in `lbfgs.passes.packed`, which a packed objective
    registers and no other process lists). Anywhere else the
    `segment_sum` programs of `_BatchObjBase` run as they always did.
    `_packed` is for tests alone: a row chunk, which also admits the
    interpreted kernels off the chip; no app's configuration reaches it."""

    def __init__(self, batches, num_feature: int, mesh,
                 _packed: Optional[int] = None):
        self.num_feature = num_feature
        self.num_dim = num_feature + 1
        super().__init__(batches, mesh)
        row_chunk = _packed or ROW_CHUNK
        why = packed_rule(batches, num_feature, mesh, row_chunk,
                          any_backend=_packed is not None)
        self._chunks = None
        if not why:
            self._chunks, why = pack_row_chunks(batches, num_feature,
                                                row_chunk)
        self.packed = self._chunks is not None
        self.placement = describe_placement(mesh, "lbfgs", self.packed, why)
        if self.packed:
            self.placement += (f" chunks={len(self._chunks)}x{row_chunk} "
                               f"rows, {self._chunks[0][3].shape[0]} blocks")
            self._passes = REGISTRY.counter("lbfgs.passes.packed")
            self._build_packed(row_chunk)

    def _build_packed(self, rows: int):
        nf, f32 = self.num_feature, jnp.float32

        def margin(w, bias, sidx, sseg, sval, tmap, first):
            return ck.coo_spmv(w, sidx, sseg, sval, tmap, first, rows,
                               dtype=f32) + bias

        @jax.jit
        def eval_chunk(tot, w, bias, *c):
            *coo, label, mask = c
            xw = margin(w, bias, *coo)
            return tot + jnp.sum((jax.nn.softplus(xw) - label * xw) * mask)

        @partial(jax.jit, donate_argnums=(0, 1))
        def grad_chunk(gw, gb, w, bias, *c):
            *coo, label, mask = c
            d = (jax.nn.sigmoid(margin(w, bias, *coo)) - label) * mask
            return (ck.coo_spmv_t(d, *coo, nf, dtype=f32, acc=gw),
                    gb + jnp.sum(d))

        # once a pass: w as the kernels' table, the sums' zeros (programs'
        # outputs, so that a pass's first chunk and its later ones give
        # the chunk's program the same kind of argument: one compilation)
        # and g back in p's layout
        self._split = jax.jit(lambda p: (p[:nf], p[nf], jnp.zeros(())))
        self._split_g = jax.jit(lambda p: (
            p[:nf], p[nf], jnp.zeros(()), jnp.zeros((nf,), f32)))
        self._join = jax.jit(lambda gw, gb: jnp.concatenate([gw, gb[None]]))
        self._eval_chunk, self._grad_chunk = eval_chunk, grad_chunk

    def eval(self, p) -> float:
        if not self.packed:
            return super().eval(p)
        self._passes.inc()
        w, bias, tot = self._split(p)
        for c in self._chunks:
            tot = self._eval_chunk(tot, w, bias, *c)
        return fetch(tot)

    def grad(self, p):
        if not self.packed:
            return super().grad(p)
        self._passes.inc()
        w, bias, gb, gw = self._split_g(p)
        for c in self._chunks:
            gw, gb = self._grad_chunk(gw, gb, w, bias, *c)
        return self._join(gw, gb)

    def _margin(self, p, seg, idx, val, num_rows: int):
        w, bias = p[: self.num_feature], p[self.num_feature]
        return jax.ops.segment_sum(val * jnp.take(w, idx), seg,
                                   num_segments=num_rows) + bias

    def _batch_loss(self, p, seg, idx, val, label, mask):
        xw = self._margin(p, seg, idx, val, label.shape[0])
        return jnp.sum((jax.nn.softplus(xw) - label * xw) * mask)

    def init_model(self):
        return self.place(jnp.zeros(self.num_dim, jnp.float32))

    def l1_mask(self):
        m = jnp.ones(self.num_dim, jnp.float32)
        return self.pad_mask(m.at[self.num_feature].set(0.0))  # no L1 on bias

    def predict(self, p, seg, idx, val, num_rows: int):
        return self._margin(p, seg, idx, val, num_rows)


class FmObjFunction(_BatchObjBase):
    """FM, flat layout [w(d); V(d x k); bias] (fm.cc:133-140)."""

    def __init__(self, batches, num_feature: int, dim_k: int, mesh,
                 init_scale: float = 0.01, seed: int = 0):
        self.num_feature = num_feature
        self.k = dim_k
        self.num_dim = num_feature * (1 + dim_k) + 1
        self.init_scale = init_scale
        self.seed = seed
        super().__init__(batches, mesh)

    def _split(self, p):
        d, k = self.num_feature, self.k
        # bias lives at its layout slot, not p[-1]: the vector may carry
        # sharding padding past it
        return p[:d], p[d : d + d * k].reshape(d, k), p[d + d * k]

    def _margin(self, p, seg, idx, val, num_rows: int):
        w, V, bias = self._split(p)
        xw = jax.ops.segment_sum(val * jnp.take(w, idx), seg,
                                 num_segments=num_rows)
        vrows = jnp.take(V, idx, axis=0)
        xv = jax.ops.segment_sum(val[:, None] * vrows, seg,
                                 num_segments=num_rows)
        x2v2 = jax.ops.segment_sum((val ** 2)[:, None] * vrows ** 2, seg,
                                   num_segments=num_rows)
        return xw + 0.5 * jnp.sum(xv * xv - x2v2, axis=-1) + bias

    def _batch_loss(self, p, seg, idx, val, label, mask):
        margin = self._margin(p, seg, idx, val, label.shape[0])
        return jnp.sum((jax.nn.softplus(margin) - label * margin) * mask)

    def init_model(self):
        d, k = self.num_feature, self.k
        key = jax.random.PRNGKey(self.seed)
        V = self.init_scale * jax.random.normal(key, (d * k,))
        p = jnp.concatenate(
            [jnp.zeros(d), V, jnp.zeros(1)]).astype(jnp.float32)
        return self.place(p)

    def l1_mask(self):
        # L1 only on the linear weights; V and bias are L2-only territory
        m = jnp.zeros(self.num_dim, jnp.float32)
        return self.pad_mask(m.at[: self.num_feature].set(1.0))

    def predict(self, p, seg, idx, val, num_rows: int):
        return self._margin(p, seg, idx, val, num_rows)
