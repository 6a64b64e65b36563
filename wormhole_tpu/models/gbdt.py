"""Histogram gradient-boosted decision trees, TPU-native.

Parity target: the reference's distributed xgboost build — `bin/xgboost.dmlc`
run over rabit with row-split data (reference Makefile:63-72,
learn/xgboost/mushroom.hadoop.conf). The conf surface kept is exactly the
mushroom conf's: booster=gbtree, objective=binary:logistic, eta, gamma,
min_child_weight, max_depth, num_round, save_period, eval_train, dsplit=row,
plus lambda (leaf L2) and max_bin.

TPU design (vs the reference's CPU allreduce xgboost):
- features are quantile-binned once on the host into a dense uint8 matrix
  [rows, features]; rows are sharded over the mesh data axis (dsplit=row);
- tree growth is depth-wise: one jitted step per level builds the
  (node, feature, bin) gradient/hessian histograms with a flat
  segment-sum, `psum`s them over the data axis — the literal TPU analog
  of distributed xgboost's rabit::Allreduce of histograms — then scans
  cumulative G/H over bins to score every candidate split at once
  (gain = 1/2[GL^2/(HL+l) + GR^2/(HR+l) - G^2/(H+l)] - gamma) and routes
  rows to children, all with static shapes;
- trees are heap-indexed arrays (split_feat/split_bin/is_split/leaf_value)
  replicated over the mesh; prediction is a `fori_loop` of gathers scanned
  over rounds.
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from wormhole_tpu.data.rowblock import RowBlock
from wormhole_tpu.parallel.mesh import (
    DATA_AXIS,
    batch_sharding,
    describe_placement,
    make_mesh,
    replicated,
)
from wormhole_tpu.solver.workload import iter_rowblocks


@dataclasses.dataclass
class GbdtConfig:
    """mushroom.hadoop.conf surface (names kept; `lambda` -> reg_lambda)."""

    train_data: str = ""
    eval_data: Optional[str] = None   # conf key eval[<name>] = path
    eval_name: str = "test"
    data_format: str = "libsvm"
    model_out: Optional[str] = None
    model_in: Optional[str] = None
    # xgboost CLI task surface: task=pred + test:data + name_pred
    task: str = "train"
    test_data: Optional[str] = None
    pred_out: str = "pred.txt"

    booster: str = "gbtree"
    objective: str = "binary:logistic"   # or reg:squarederror
    eta: float = 0.3
    gamma: float = 0.0
    min_child_weight: float = 1.0
    max_depth: int = 6
    reg_lambda: float = 1.0              # xgboost `lambda`
    num_round: int = 10
    save_period: int = 0
    eval_train: int = 0
    dsplit: str = "row"                  # only row split is supported
    base_score: float = 0.5

    # multi-process SPMD over one jax.distributed mesh (apps/gbdt.py
    # _global_worker_body; the reference's rabit world)
    global_mesh: bool = False
    # multi-process BSP over the native allreduce ring (apps/gbdt.py
    # _bsp_worker_body over runtime/allreduce.py): each rank keeps its
    # own local mesh and row shard; per-level histograms reduce over
    # the worker ring — the literal rabit::Allreduce of histograms,
    # fault-tolerant via version checkpoints
    bsp: bool = False
    # TPU-native knobs
    max_bin: int = 256
    dim: int = 0        # feature count; 0 = discover from data
    minibatch: int = 65536  # streaming-load chunk size
    num_parts_per_file: int = 1
    seed: int = 0
    # histogram backend: mxu (Pallas one-hot-matmul kernel,
    # ops/hist.py — ~40x faster than the scatter on TPU) | xla
    # (segment-sum scatter) | auto (mxu on TPU, xla elsewhere — the
    # interpreted kernel is too slow for CPU test loops)
    hist_kernel: str = "auto"


# ---------------------------------------------------------------------------
# host-side dataset loading + quantile binning
# ---------------------------------------------------------------------------

_SKETCH_ROWS = 1 << 17  # quantile-sketch sample cap (approx sketch parity)


class Reservoir:
    """Uniform reservoir of sparse rows over any RowBlock stream (rows
    kept as (index, value) triples so no dense matrix exists before the
    feature count is known); tracks the running max feature id."""

    def __init__(self, cap: int, seed: int):
        self.cap = max(int(cap), 1)
        self.rng = np.random.default_rng(seed)
        self.sample: list = []
        self.n_seen = 0
        self.max_feat = -1

    def add_block(self, blk: RowBlock) -> None:
        if blk.nnz:
            self.max_feat = max(self.max_feat, int(blk.index.max()))
        vals = blk.values_or_ones()
        for r in range(blk.size):
            lo, hi = blk.offset[r], blk.offset[r + 1]
            row = (blk.index[lo:hi].copy(), vals[lo:hi].copy())
            if len(self.sample) < self.cap:
                self.sample.append(row)
            else:
                # classic reservoir: keep each new row with prob cap/n
                j = self.rng.integers(0, self.n_seen + 1)
                if j < self.cap:
                    self.sample[j] = row
            self.n_seen += 1


def _reservoir_sample(pattern: str, fmt: str, num_parts_per_file: int,
                      minibatch: int, seed: int,
                      cap: int = _SKETCH_ROWS):
    """One streaming pass: reservoir-sample up to `cap` rows and
    discover the feature dimension — the global approx sketch +
    Allreduce<Max> dim discovery of xgboost without materializing the
    dataset."""
    res = Reservoir(cap, seed)
    for blk in iter_rowblocks(pattern, num_parts_per_file, fmt,
                              minibatch, node="gbdt-sketch", seed=seed):
        res.add_block(blk)
    if res.n_seen == 0:
        raise ValueError(f"no rows in {pattern}")
    return res.sample, res.n_seen, res.max_feat


def _densify_sample(sample, dim: int) -> np.ndarray:
    X = np.zeros((len(sample), dim), np.float32)
    for r, (idx, val) in enumerate(sample):
        keep = idx < dim
        X[r, idx[keep].astype(np.int64)] = val[keep]
    return X


def _densify(blk: RowBlock, dim: int) -> np.ndarray:
    """Sparse CSR rows -> dense [n, dim] float32 (absent feature = 0,
    matching xgboost's default missing=0 treatment for libsvm data)."""
    n = blk.size
    X = np.zeros((n, dim), np.float32)
    rows = np.repeat(np.arange(n), np.diff(blk.offset).astype(np.int64))
    cols = blk.index.astype(np.int64)
    keep = cols < dim
    X[rows[keep], cols[keep]] = blk.values_or_ones()[keep]
    return X


def quantile_edges(X: np.ndarray, max_bin: int) -> np.ndarray:
    """Per-feature cut points, [dim, max_bin-1], padded with +inf.

    bin(x) = searchsorted(edges, x, 'right'); few distinct values get
    midpoint cuts, many get quantile cuts — the histogram/approx sketch
    of xgboost, computed on a host sample."""
    dim = X.shape[1]
    edges = np.full((dim, max_bin - 1), np.inf, np.float32)
    for f in range(dim):
        col = X[:, f]
        uniq = np.unique(col)
        if len(uniq) <= 1:
            continue
        if len(uniq) <= max_bin:
            cuts = (uniq[:-1] + uniq[1:]) / 2.0
        else:
            qs = np.quantile(col, np.linspace(0, 1, max_bin + 1)[1:-1])
            cuts = np.unique(qs.astype(np.float32))
        edges[f, : len(cuts)] = cuts
    return edges


def bin_matrix(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Apply cut points -> uint8 bins [n, dim]."""
    n, dim = X.shape
    out = np.empty((n, dim), np.uint8)
    for f in range(dim):
        e = edges[f]
        e = e[np.isfinite(e)]
        out[:, f] = np.searchsorted(e, X[:, f], side="right").astype(np.uint8)
    return out


@dataclasses.dataclass
class BinnedDataset:
    """Device-resident binned dataset, rows sharded over the data axis."""

    binned: jax.Array   # uint8 [N, dim]  (N padded to mesh data size)
    label: jax.Array    # float32 [N]
    mask: jax.Array     # float32 [N]  (0 for padding rows)
    num_real: int


# ---------------------------------------------------------------------------
# learner
# ---------------------------------------------------------------------------


class GbdtLearner:
    """Depth-wise histogram GBDT over a (data,) sharded row matrix."""

    def __init__(self, cfg: GbdtConfig, mesh=None):
        if cfg.booster != "gbtree":
            raise NotImplementedError(
                f"booster={cfg.booster!r}: only gbtree; for gblinear use "
                "wormhole_tpu.models.linear (the reference's gblinear is a "
                "distributed linear model)")
        if cfg.dsplit != "row":
            raise NotImplementedError("only dsplit=row (the reference "
                                      "mushroom.hadoop.conf:36 setting)")
        assert cfg.max_bin <= 256, "bins are uint8"
        self.cfg = cfg
        # the user-requested boosting rounds; cfg.num_round later becomes
        # the running total when continuing from model_in, so repeated
        # fit() calls must not compound it
        self._requested_rounds = cfg.num_round
        self.mesh = mesh if mesh is not None else make_mesh(num_model=1)
        self._n_data = self.mesh.shape[DATA_AXIS]
        self._use_mxu_hist = cfg.hist_kernel == "mxu" or (
            cfg.hist_kernel == "auto" and jax.default_backend() == "tpu")
        #: start-up statement of where and how this learner runs
        self.placement = describe_placement(
            self.mesh, "gbdt", self._use_mxu_hist,
            "hist_kernel=xla" if cfg.hist_kernel == "xla" else "")
        self.edges: Optional[np.ndarray] = None   # [dim, max_bin-1]
        # stacked per-round trees, each [T] where T = 2^(max_depth+1)-1
        self.trees: dict[str, np.ndarray] = _empty_trees(cfg)
        self._level_fns: dict = {}
        self._jit_cache: dict = {}
        # optional host allreduce over the worker ring (BSP mode): a
        # callable f(np.ndarray) -> np.ndarray summing over all ranks.
        # When set, fit_prepared reduces every level's histogram block
        # and the eval metric sums through it instead of assuming the
        # local mesh holds all the data.
        self.reducer = None

    # -- data ---------------------------------------------------------------
    def load_dataset(self, pattern: str, fit_bins: bool = False) -> BinnedDataset:
        """Stream the dataset into device-resident uint8 bins in bounded
        host memory: a sketch pass (reservoir sample -> quantile edges,
        discovering dim by running max — the Allreduce<Max> parity,
        lbfgs.cc:107-113) followed by a binning pass that densifies one
        chunk at a time. The full dataset never exists on the host as
        either CSR or float — only as the uint8 bin matrix it ships to
        the device as."""
        cfg = self.cfg
        if fit_bins or self.edges is None:
            sample, _, max_feat = _reservoir_sample(
                pattern, cfg.data_format, cfg.num_parts_per_file,
                cfg.minibatch, cfg.seed)
            if cfg.dim == 0:
                cfg.dim = max(max_feat + 1, 1)
            self.edges = quantile_edges(_densify_sample(sample, cfg.dim),
                                        cfg.max_bin)
            del sample
        # binning pass: one float chunk at a time
        chunks, labels = [], []
        for blk in iter_rowblocks(pattern, cfg.num_parts_per_file,
                                  cfg.data_format, cfg.minibatch,
                                  node="gbdt-load"):
            chunks.append(bin_matrix(_densify(blk, cfg.dim), self.edges))
            labels.append(blk.label.astype(np.float32))
        if not chunks:
            raise ValueError(f"no rows in {pattern}")
        n = sum(c.shape[0] for c in chunks)
        # pad rows to a multiple of the data axis
        pad = (-n) % self._n_data
        if pad:
            chunks.append(np.zeros((pad, cfg.dim), np.uint8))
        binned = np.concatenate(chunks)
        del chunks
        label = np.zeros(n + pad, np.float32)
        label[:n] = np.concatenate(labels)
        mask = np.zeros(n + pad, np.float32)
        mask[:n] = 1.0
        b1 = batch_sharding(self.mesh, 1)
        b2 = batch_sharding(self.mesh, 2)
        return BinnedDataset(
            binned=jax.device_put(binned, b2),
            label=jax.device_put(label, b1),
            mask=jax.device_put(mask, b1),
            num_real=n,
        )

    # -- objective ----------------------------------------------------------
    def _grad_hess(self, margin, label, mask):
        obj = self.cfg.objective
        if obj == "binary:logistic":
            p = jax.nn.sigmoid(margin)
            return (p - label) * mask, jnp.maximum(p * (1 - p), 1e-16) * mask
        if obj in ("reg:squarederror", "reg:linear"):
            return (margin - label) * mask, mask
        raise NotImplementedError(f"objective={obj!r}")

    def _base_margin(self):
        if self.cfg.objective == "binary:logistic":
            s = min(max(self.cfg.base_score, 1e-6), 1 - 1e-6)
            return float(np.log(s / (1 - s)))
        return float(self.cfg.base_score)

    # -- per-level jitted step ---------------------------------------------
    def _hyper_key(self):
        """Cache key component for every cfg field a compiled fn closes
        over, so mutating cfg (e.g. via load()) can never reuse stale
        compilations."""
        c = self.cfg
        return (c.dim, c.max_bin, c.max_depth, c.reg_lambda, c.gamma,
                c.min_child_weight, c.eta, c.objective, c.hist_kernel)

    def _level_parts(self, num_nodes: int, offset: int, last: bool):
        """Two traceable halves of one tree level.

        `hist_part` produces the level's stacked [G, H] statistics block
        (already psum'd over the LOCAL data axis) and `apply_part`
        consumes such a block to subtract siblings, score splits, and
        route rows. The single-process/global-mesh path composes them
        inside one jit (`_level_fn`), where the local psum already spans
        all the data; the BSP path jits them separately
        (`_bsp_level_fns`) and host-allreduces the block over the worker
        ring in between — the literal rabit::Allreduce of gradient
        histograms."""
        cfg = self.cfg
        F, B = cfg.dim, cfg.max_bin
        lam, gam, mcw, eta = (cfg.reg_lambda, cfg.gamma,
                              cfg.min_child_weight, cfg.eta)
        mesh = self.mesh
        # sibling subtraction (xgboost's classic halving): levels past
        # the root accumulate only the LEFT child of every split pair —
        # half the one-hot-matmul M axis — and derive the right child as
        # parent − left. Rows of a NON-splitting parent are active in
        # neither child, so its "right child" slot derives to the
        # parent's own histogram — garbage, but unreachable: routing
        # only ever descends into children of split nodes.
        sibling = num_nodes > 1
        hist_nodes = num_nodes // 2 if sibling else num_nodes

        def local_hist(binned, g, h, rel):
            """Per-shard (node, feature, bin) histograms + psum — the
            rabit::Allreduce of gradient histograms."""
            if self._use_mxu_hist:
                # MXU one-hot-matmul histogram (ops/hist.py): the XLA
                # scatter costs ~10ns per rows x F element on TPU
                from wormhole_tpu.ops.hist import level_hist

                G, H = level_hist(binned, g, h, rel, hist_nodes, B)
            else:
                n = g.shape[0]
                base = (rel[:, None] * (F * B)
                        + jnp.arange(F, dtype=jnp.int32)[None, :] * B)
                idx = base + binned.astype(jnp.int32)      # [n, F]
                # inactive rows got rel == hist_nodes -> index >=
                # num_segments, dropped by the scatter
                gb = jnp.broadcast_to(g[:, None], (n, F)).ravel()
                hb = jnp.broadcast_to(h[:, None], (n, F)).ravel()
                flat = idx.ravel()
                G = jax.ops.segment_sum(
                    gb, flat, num_segments=hist_nodes * F * B)
                H = jax.ops.segment_sum(
                    hb, flat, num_segments=hist_nodes * F * B)
                G = G.reshape(hist_nodes, F, B)
                H = H.reshape(hist_nodes, F, B)
            G = jax.lax.psum(G, DATA_AXIS)
            H = jax.lax.psum(H, DATA_AXIS)
            return G, H

        hist = shard_map(
            local_hist, mesh=mesh,
            in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS),
                      P(DATA_AXIS)),
            out_specs=(P(), P()),
            check_vma=False,  # pallas_call out_shape carries no vma
        )

        def local_totals(g, h, relh):
            """Per-pair (Σg, Σh) via a fused masked reduce + psum — the
            LAST level needs only node totals for leaf values, so the
            full (F, B) histogram pass (the round's single most
            expensive level) is skipped entirely."""
            sel = (jax.lax.broadcasted_iota(jnp.int32,
                                            (hist_nodes, g.shape[0]), 0)
                   == relh[None, :])
            Gt = jnp.sum(jnp.where(sel, g[None, :], 0.0), axis=-1)
            Ht = jnp.sum(jnp.where(sel, h[None, :], 0.0), axis=-1)
            return (jax.lax.psum(Gt, DATA_AXIS),
                    jax.lax.psum(Ht, DATA_AXIS))

        totals = shard_map(
            local_totals, mesh=mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(), P()),
            check_vma=False,
        )

        def hist_part(binned, g, h, node, active):
            """Local [2, ...] stacked G/H statistics for this level —
            the unit the BSP ring sums. Shape depends only on
            (num_nodes, F, B), never on the local row count, so every
            rank's block lines up regardless of data skew."""
            rel = jnp.where(active, node - offset, num_nodes).astype(jnp.int32)
            if sibling:
                # accumulate left children only (even rel -> pair id)
                relh = jnp.where(active & (rel % 2 == 0), rel // 2,
                                 hist_nodes).astype(jnp.int32)
                if last:
                    # leaf-only level: totals suffice (see local_totals)
                    Gt_l, Ht_l = totals(g, h, relh)
                    return jnp.stack([Gt_l, Ht_l])     # [2, hist_nodes]
                Gl, Hl = hist(binned, g, h, relh)
                return jnp.stack([Gl, Hl])     # [2, hist_nodes, F, B]
            G, H = hist(binned, g, h, rel)
            return jnp.stack([G, H])           # [2, num_nodes, F, B]

        def apply_part(stat, binned, node, active, trees, Gp, Hp):
            """Consume the (globally summed) statistics block: sibling
            subtraction, split scoring, row routing."""
            if sibling and last:
                Gt_l, Ht_l = stat[0], stat[1]
                Gt_p = Gp[:, 0, :].sum(-1)
                Ht_p = Hp[:, 0, :].sum(-1)
                Gt = jnp.stack([Gt_l, Gt_p - Gt_l], 1).reshape(
                    num_nodes)
                Ht = jnp.stack([Ht_l, Ht_p - Ht_l], 1).reshape(
                    num_nodes)
                leaf = -Gt / (Ht + lam) * eta
                sl = slice(offset, offset + num_nodes)
                trees = dict(trees)
                trees["leaf_value"] = trees["leaf_value"].at[sl].set(
                    leaf)
                return node, jnp.zeros_like(active), trees, Gp, Hp
            if sibling:
                Gl, Hl = stat[0], stat[1]
                G = jnp.stack([Gl, Gp - Gl], axis=1).reshape(
                    num_nodes, F, B)
                H = jnp.stack([Hl, Hp - Hl], axis=1).reshape(
                    num_nodes, F, B)
            else:
                G, H = stat[0], stat[1]
            Gt, Ht = G[:, 0, :].sum(-1), H[:, 0, :].sum(-1)   # node totals
            leaf = -Gt / (Ht + lam) * eta
            sl = slice(offset, offset + num_nodes)
            if last:
                trees = dict(trees)
                trees["leaf_value"] = trees["leaf_value"].at[sl].set(leaf)
                return node, jnp.zeros_like(active), trees, G, H
            # candidate splits: left = bins <= b (cumulative), right = rest
            GL = jnp.cumsum(G, axis=2)
            HL = jnp.cumsum(H, axis=2)
            GR, HR = Gt[:, None, None] - GL, Ht[:, None, None] - HL
            gain = 0.5 * (GL * GL / (HL + lam) + GR * GR / (HR + lam)
                          - (Gt * Gt / (Ht + lam))[:, None, None]) - gam
            ok = (HL >= mcw) & (HR >= mcw)
            ok = ok & (jnp.arange(B) < B - 1)[None, None, :]
            gain = jnp.where(ok, gain, -jnp.inf)
            flat_gain = gain.reshape(num_nodes, F * B)
            best = jnp.argmax(flat_gain, axis=1)
            best_gain = jnp.take_along_axis(flat_gain, best[:, None], 1)[:, 0]
            do_split = best_gain > 0.0
            bf = (best // B).astype(jnp.int32)
            bb = (best % B).astype(jnp.int32)
            trees = dict(trees)
            trees["split_feat"] = trees["split_feat"].at[sl].set(bf)
            trees["split_bin"] = trees["split_bin"].at[sl].set(bb)
            trees["is_split"] = trees["is_split"].at[sl].set(do_split)
            trees["leaf_value"] = trees["leaf_value"].at[sl].set(
                jnp.where(do_split, 0.0, leaf))
            # route rows into children (one-hot lookups: XLA per-row
            # gathers cost ~7ns/row even from a 127-entry table)
            T_all = trees["split_feat"].shape[0]
            nf, thr, isp, _ = _tree_lookup(node, trees, T_all)
            bv = _binned_at(binned, nf, F)
            splitting = isp & active
            node = jnp.where(splitting,
                             2 * node + 1 + (bv > thr).astype(jnp.int32),
                             node)
            return node, splitting, trees, G, H

        return hist_part, apply_part

    def _level_fn(self, num_nodes: int, offset: int, last: bool):
        key = (num_nodes, offset, last, self._hyper_key())
        fn = self._level_fns.get(key)
        if fn is not None:
            return fn
        hp, ap = self._level_parts(num_nodes, offset, last)

        @jax.jit
        def level_step(binned, g, h, node, active, trees, Gp, Hp):
            return ap(hp(binned, g, h, node, active), binned, node,
                      active, trees, Gp, Hp)

        self._level_fns[key] = level_step
        return level_step

    def _bsp_level_fns(self, num_nodes: int, offset: int, last: bool):
        """The level's halves jitted SEPARATELY, so the histogram block
        can hop to the host for the ring allreduce between them (the
        fused per-round program cannot host-call mid-trace)."""
        key = ("bsp", num_nodes, offset, last, self._hyper_key())
        fns = self._level_fns.get(key)
        if fns is None:
            hp, ap = self._level_parts(num_nodes, offset, last)
            fns = self._level_fns[key] = (jax.jit(hp), jax.jit(ap))
        return fns

    # -- boosting -----------------------------------------------------------
    def _fused_round_fn(self):
        """One jitted call per boosting round: grad/hess, every tree
        level, and the margin update in a single dispatch. The per-level
        steps are all static-shape, so the whole depth unrolls into one
        XLA program — one dispatch round-trip per boosting round instead
        of ~9 (a ~5x round-time cut at the HIGGS bench shape before the
        histogram/routing kernels; PERF.md has the corrected table)."""
        key = ("fused_round", self._hyper_key())
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg
        T = 2 ** (cfg.max_depth + 1) - 1

        @jax.jit
        def round_fn(binned, label, mask, margin):
            g, h = self._grad_hess(margin, label, mask)
            trees = {
                "split_feat": jnp.zeros(T, jnp.int32),
                "split_bin": jnp.zeros(T, jnp.int32),
                "is_split": jnp.zeros(T, jnp.bool_),
                "leaf_value": jnp.zeros(T, jnp.float32),
            }
            node = jnp.zeros(label.shape, jnp.int32)
            active = mask > 0
            # parent histograms thread level-to-level for the sibling
            # subtraction (level 0 ignores the zero placeholder)
            F, B = cfg.dim, cfg.max_bin
            Gp = jnp.zeros((1, F, B), jnp.float32)
            Hp = jnp.zeros((1, F, B), jnp.float32)
            for d in range(cfg.max_depth + 1):
                num_nodes, offset = 2 ** d, 2 ** d - 1
                fn_l = self._level_fn(num_nodes, offset,
                                      last=(d == cfg.max_depth))
                node, active, trees, Gp, Hp = fn_l(binned, g, h, node,
                                                   active, trees, Gp, Hp)
            _, _, _, leaf = _tree_lookup(node, trees, T)
            margin2 = margin + leaf
            return trees, node, margin2

        self._jit_cache[key] = round_fn
        return round_fn

    def _round_fns(self):
        key = ("round", self._hyper_key())
        fns = self._jit_cache.get(key)
        if fns is None:
            gh = jax.jit(lambda m, y, msk: self._grad_hess(m, y, msk))
            upd = jax.jit(lambda m, lv, node: m + lv[node])
            fns = self._jit_cache[key] = (gh, upd)
        return fns

    def _bsp_round(self, train: BinnedDataset, margin):
        """One boosting round with the histogram allreduce over the
        worker ring: grad/hess and each level's halves are jitted device
        steps; between a level's halves the stacked [G, H] block hops to
        the host and sums over all ranks through `self.reducer`. The
        ring fixes its accumulation order, so every rank consumes
        bit-identical reduced blocks — and therefore grows bit-identical
        trees, which is what lets a respawned worker's replay converge
        exactly (tests assert recovered == fault-free model)."""
        cfg = self.cfg
        T = 2 ** (cfg.max_depth + 1) - 1
        gh, upd = self._round_fns()
        g, h = gh(margin, train.label, train.mask)
        trees = {
            "split_feat": jnp.zeros(T, jnp.int32),
            "split_bin": jnp.zeros(T, jnp.int32),
            "is_split": jnp.zeros(T, jnp.bool_),
            "leaf_value": jnp.zeros(T, jnp.float32),
        }
        node = jnp.zeros(train.label.shape, jnp.int32)
        active = train.mask > 0
        F, B = cfg.dim, cfg.max_bin
        Gp = jnp.zeros((1, F, B), jnp.float32)
        Hp = jnp.zeros((1, F, B), jnp.float32)
        for d in range(cfg.max_depth + 1):
            num_nodes, offset = 2 ** d, 2 ** d - 1
            hp, ap = self._bsp_level_fns(num_nodes, offset,
                                         last=(d == cfg.max_depth))
            stat = hp(train.binned, g, h, node, active)
            stat = jnp.asarray(self.reducer(np.asarray(stat)))
            node, active, trees, Gp, Hp = ap(stat, train.binned, node,
                                             active, trees, Gp, Hp)
        margin2 = upd(margin, trees["leaf_value"], node)
        return trees, node, margin2

    def _metric_sums(self):
        """Jitted per-shard metric SUM vector — the sum-decomposable
        form that can ride the same allreduce as the histograms."""
        key = ("metric_sums", self._hyper_key())
        fn = self._jit_cache.get(key)
        if fn is None:
            if self.cfg.objective == "binary:logistic":

                @jax.jit
                def sums(margin, label, mask):
                    pred = (margin > 0).astype(jnp.float32)
                    err = jnp.sum(mask * jnp.abs(pred - label))
                    ll = jnp.sum(mask * (label * jax.nn.softplus(-margin)
                                         + (1.0 - label)
                                         * jax.nn.softplus(margin)))
                    return jnp.stack([err, ll, jnp.sum(mask)])
            else:

                @jax.jit
                def sums(margin, label, mask):
                    sq = jnp.sum(mask * (margin - label) ** 2)
                    return jnp.stack([sq, jnp.sum(mask)])

            fn = self._jit_cache[key] = sums
        return fn

    def _metrics_reduced(self, margin, ds: BinnedDataset) -> dict:
        """Distributed eval metrics: reduce per-rank sum vectors over
        the ring, finish the division on the host. AUC is skipped in
        BSP mode — it needs a global rank ordering of predictions and
        is not sum-decomposable over row shards."""
        s = self.reducer(
            np.asarray(self._metric_sums()(margin, ds.label, ds.mask)))
        if self.cfg.objective == "binary:logistic":
            n = max(float(s[2]), 1.0)
            return {"error": float(s[0]) / n, "logloss": float(s[1]) / n}
        n = max(float(s[1]), 1.0)
        return {"rmse": float(np.sqrt(float(s[0]) / n))}

    def _base_margins(self, ds: BinnedDataset):
        m = jnp.full(ds.label.shape, self._base_margin(), jnp.float32)
        return jax.device_put(m, batch_sharding(self.mesh, 1))

    def fit(self, verbose: bool = True) -> dict:
        """The boosting loop; prints `[round] name-metric:value` rows like
        the reference xgboost CLI. With model_in, continues boosting on
        top of the loaded trees (cfg.num_round more rounds), replaying
        the prior trees into the margins first."""
        cfg = self.cfg
        extra = self._requested_rounds
        r0 = 0
        if cfg.model_in:
            self.load(cfg.model_in)  # sets edges/dim/max_depth/objective
            r0 = cfg.num_round
            cfg.num_round = r0 + extra
        train = self.load_dataset(cfg.train_data, fit_bins=(r0 == 0))
        evals = []
        if cfg.eval_data:
            evals.append((cfg.eval_name, self.load_dataset(cfg.eval_data)))
        if cfg.eval_train:
            evals.append(("train", train))
        return self.fit_prepared(train, evals, r0=r0, verbose=verbose)

    def fit_prepared(self, train: BinnedDataset, evals, r0: int = 0,
                     verbose: bool = True, on_round=None) -> dict:
        """The boosting loop over already-loaded datasets — the entry the
        multi-process global-mesh app uses after assembling globally
        sharded datasets (every process must call this in lockstep: each
        round's histogram/split/metric steps are collectives). With
        `self.reducer` set (BSP mode) the per-level blocks and metric
        sums instead reduce over the worker ring; `on_round(r)` fires
        after round r's trees and metrics land — the BSP app's
        checkpoint hook (its placement matters: every collective of
        round r must complete BEFORE the checkpoint bumps the version,
        so a resumed worker's counter sequence lines up with the
        survivors')."""
        cfg = self.cfg
        if verbose:
            print(self.placement, flush=True)
        prior = self.trees
        self.trees = _empty_trees(cfg)
        for k in self.trees:
            self.trees[k][:r0] = prior[k][:r0]
        _, upd = self._round_fns()
        margin = self._base_margins(train)
        margins = {name: self._base_margins(ds)
                   for name, ds in evals if ds is not train}
        for r in range(r0):  # replay loaded trees (warm start)
            tree = {k: jnp.asarray(v[r]) for k, v in self.trees.items()}
            margin = upd(margin, tree["leaf_value"], self._route(train, tree))
            for name, ds in evals:
                if ds is not train:
                    margins[name] = upd(margins[name], tree["leaf_value"],
                                        self._route(ds, tree))
        last = {}
        round_fn = self._fused_round_fn() if self.reducer is None else None
        for r in range(r0, cfg.num_round):
            if self.reducer is not None:
                tree, node, margin = self._bsp_round(train, margin)
            else:
                tree, node, margin = round_fn(train.binned, train.label,
                                              train.mask, margin)
            if os.environ.get("WORMHOLE_DEBUG", "") not in ("", "0"):
                validate_routing(tree, node)
            for k in self.trees:
                self.trees[k][r] = np.asarray(tree[k])
            msgs = []
            for name, ds in evals:
                if ds is train:
                    em = margin
                else:
                    em = margins[name] = upd(
                        margins[name], tree["leaf_value"],
                        self._route(ds, tree))
                last[name] = m = (self._metrics_reduced(em, ds)
                                  if self.reducer is not None
                                  else self._metrics(em, ds))
                msgs += [f"{name}-{k}:{v:.6f}" for k, v in m.items()]
            if verbose:
                print(f"[{r}]\t" + "\t".join(msgs), flush=True)
            if on_round is not None:
                on_round(r)
            if cfg.save_period and cfg.model_out and (r + 1) % cfg.save_period == 0:
                self.save(f"{cfg.model_out}.{r + 1:04d}", rounds=r + 1)
        if cfg.model_out:
            self.save(cfg.model_out)
        return last

    # -- eval / predict -----------------------------------------------------
    def _route(self, ds: BinnedDataset, tree):
        key = ("route", ds.binned.shape, self.cfg.max_depth)
        fn = self._jit_cache.get(key)
        if fn is None:
            depth = self.cfg.max_depth

            @jax.jit
            def route(binned, sf, sb, isp):
                node = jnp.zeros(binned.shape[0], jnp.int32)
                F = binned.shape[1]
                trees_v = {"split_feat": sf, "split_bin": sb,
                           "is_split": isp,
                           "leaf_value": jnp.zeros_like(sf, jnp.float32)}

                def body(_, node):
                    f, sb_n, isp_n, _ = _tree_lookup(node, trees_v,
                                                     sf.shape[0])
                    bv = _binned_at(binned, f, F)
                    child = 2 * node + 1 + (bv > sb_n).astype(jnp.int32)
                    return jnp.where(isp_n, child, node)

                return jax.lax.fori_loop(0, depth + 1, body, node)

            fn = self._jit_cache[key] = route
        return fn(ds.binned, tree["split_feat"], tree["split_bin"],
                  tree["is_split"])

    def _metrics(self, margin, ds: BinnedDataset) -> dict:
        from wormhole_tpu.ops import metrics as M

        key = ("metrics", margin.shape, self._hyper_key())
        fn = self._jit_cache.get(key)
        if fn is None:
            if self.cfg.objective == "binary:logistic":

                @jax.jit
                def mfn(margin, label, mask):
                    return {
                        "error": 1.0 - M.accuracy(label, margin, mask),
                        "logloss": M.logloss(label, margin, mask),
                        "auc": M.auc(label, margin, mask),
                    }
            else:

                @jax.jit
                def mfn(margin, label, mask):
                    n = jnp.maximum(jnp.sum(mask), 1.0)
                    return {"rmse": jnp.sqrt(
                        jnp.sum(mask * (margin - label) ** 2) / n)}

            fn = self._jit_cache[key] = mfn
        return {k: float(v) for k, v in
                fn(margin, ds.label, ds.mask).items()}

    def predict_margin(self, ds: BinnedDataset, num_round: Optional[int] = None
                       ) -> np.ndarray:
        R = num_round if num_round is not None else self.cfg.num_round
        m = jnp.full(ds.label.shape, self._base_margin(), jnp.float32)
        for r in range(R):
            tree = {k: jnp.asarray(v[r]) for k, v in self.trees.items()}
            m = m + tree["leaf_value"][self._route(ds, tree)]
        return np.asarray(m)[: ds.num_real]

    def predict_blk(self, blk: RowBlock) -> np.ndarray:
        """Predict probabilities (binary:logistic) / values on raw rows."""
        assert self.edges is not None, "model not fit/loaded"
        X = _densify(blk, self.cfg.dim)
        binned = bin_matrix(X, self.edges)
        pad = (-blk.size) % self._n_data
        if pad:
            binned = np.concatenate(
                [binned, np.zeros((pad, self.cfg.dim), np.uint8)])
        ds = BinnedDataset(
            binned=jax.device_put(binned, batch_sharding(self.mesh, 2)),
            label=jnp.zeros(blk.size + pad, jnp.float32),
            mask=jnp.concatenate([jnp.ones(blk.size), jnp.zeros(pad)]),
            num_real=blk.size,
        )
        m = self.predict_margin(ds)
        if self.cfg.objective == "binary:logistic":
            return 1.0 / (1.0 + np.exp(-m))
        return m

    # -- persistence --------------------------------------------------------
    def save(self, path: str, rounds: Optional[int] = None) -> None:
        from wormhole_tpu.utils.checkpoint import atomic_savez

        R = rounds if rounds is not None else self.cfg.num_round
        R = min(R, len(self.trees["leaf_value"]))
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        atomic_savez(
            path,
            edges=self.edges,
            num_round=R,
            dim=self.cfg.dim,
            max_depth=self.cfg.max_depth,
            objective=np.bytes_(self.cfg.objective.encode()),
            base_score=self.cfg.base_score,
            **{k: v[:R] for k, v in self.trees.items()},
        )

    def load(self, path: str) -> None:
        if not os.path.exists(path) and not path.endswith(".npz"):
            path += ".npz"  # atomic_savez appends the suffix
        st = np.load(path)
        self.edges = st["edges"]
        self.cfg.dim = int(st["dim"])
        self.cfg.max_depth = int(st["max_depth"])
        self.cfg.num_round = int(st["num_round"])
        self.cfg.objective = bytes(st["objective"]).decode()
        self.cfg.base_score = float(st["base_score"])
        self.trees = {k: np.array(st[k]) for k in
                      ("split_feat", "split_bin", "is_split", "leaf_value")}


def _tree_lookup(node, trees, T: int):
    """Per-row lookups into the (T,)-sized tree arrays as one one-hot
    matmul — XLA's per-row gather from even a tiny table costs ~7ns/row
    on TPU (~14ms at the 2M-row HIGGS shape), the dominant cost of
    routing. Every channel must survive the bf16 encoding exactly:
    split_feat can exceed 256 (bf16's exact-integer limit), so it rides
    as hi/lo bytes (exact for dim < 65536); split_bin is < 256 (uint8
    bins); leaf values go through a bf16 hi/lo split (~f32 precision).
    Returns (split_feat, split_bin, is_split, leaf_value) per row."""
    oh = (node[:, None]
          == jnp.arange(T, dtype=jnp.int32)[None, :]).astype(jnp.bfloat16)
    lv = trees["leaf_value"]
    lv_hi = lv.astype(jnp.bfloat16)
    lv_lo = (lv - lv_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    sf = trees["split_feat"]
    tab = jnp.stack([
        (sf >> 8).astype(jnp.bfloat16),
        (sf & 255).astype(jnp.bfloat16),
        trees["split_bin"].astype(jnp.bfloat16),
        trees["is_split"].astype(jnp.bfloat16),
        lv_hi, lv_lo,
    ], axis=1)                                      # (T, 6)
    got = jax.lax.dot_general(
        oh, tab, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # (n, 6)
    nf = (got[:, 0].astype(jnp.int32) << 8) | got[:, 1].astype(jnp.int32)
    thr = got[:, 2].astype(jnp.int32)
    isp = got[:, 3] > 0.5
    leaf = got[:, 4] + got[:, 5]
    return nf, thr, isp, leaf


def _binned_at(binned, nf, F: int):
    """binned[i, nf[i]] as a one-hot masked sum (take_along_axis's
    per-row gather costs ~30ms at the HIGGS shape)."""
    oh = nf[:, None] == jnp.arange(F, dtype=jnp.int32)[None, :]
    return jnp.sum(jnp.where(oh, binned.astype(jnp.int32), 0), axis=1)


def validate_routing(tree, node) -> None:
    """Machine check for the sibling-subtraction invariant (the prose at
    `_level_fn`): the derived right-child histogram of a NON-splitting
    parent is garbage, which is safe only because routing never descends
    past a non-split node. This verifies exactly that — every node a row
    actually landed in must have an all-split ancestor chain — so a
    future routing edit that lets rows leak into a non-splitting
    parent's children trips here instead of silently training on garbage
    histograms. Enabled per round via WORMHOLE_DEBUG=1 (host-side walk
    over the unique landing nodes: O(T log T), negligible vs a round)."""
    isp = np.asarray(tree["is_split"])
    for t in np.unique(np.asarray(node)):
        path = []
        while t > 0:
            t = (t - 1) // 2
            path.append(t)
        bad = [p for p in path if not isp[p]]
        if bad:
            raise AssertionError(
                f"sibling-subtraction invariant violated: a row landed "
                f"in a descendant of non-split node(s) {bad} — routing "
                f"descended past a non-splitting parent, so derived "
                f"right-child histograms were trained on garbage")


def _empty_trees(cfg: GbdtConfig) -> dict[str, np.ndarray]:
    T = 2 ** (cfg.max_depth + 1) - 1
    R = cfg.num_round
    return {
        "split_feat": np.zeros((R, T), np.int32),
        "split_bin": np.zeros((R, T), np.int32),
        "is_split": np.zeros((R, T), np.bool_),
        "leaf_value": np.zeros((R, T), np.float32),
    }
