"""DiFacto: asynchronous factorization machine, TPU-native.

Parity target: reference learn/difacto (async_sgd.h, loss.h, config.proto;
doc/learn/difacto.rst): the FM model

    f(x) = <w, x> + 1/2 sum_k [ (Xv)_k^2 - (X^2)(V^2)_k ]

with adaptive embedding memory — the reference allocates a key's V slice
only once its occurrence count reaches `threshold` (and optionally only
while w != 0, the `l1_shrk` trick, difacto.rst:24-32); w trains with FTRL,
V with AdaGrad (async_sgd.h:262-296).

TPU design (SURVEY §7.5 two-table plan):
- `w` (+ FTRL z, n) tables over `num_buckets`, exactly as the linear
  learner;
- a separate dense `V` table [v_buckets, dim] (+ AdaGrad nV) with its own
  (smaller) hashed bucket space — the fixed-capacity stand-in for the
  reference's variable-length server entries;
- a `cnt` table accumulates per-bucket occurrence counts in-step (the
  pass-0 kPushFeaCnt push, async_sgd.h:374-381, becomes a fused
  segment-sum: the count push and the admission test live in the same
  jitted step, so no separate count pass is needed);
- admission = (cnt >= threshold) [* (w != 0) if l1_shrk]; the quadratic
  term and the V update both see V through the admission mask, so a
  never-admitted bucket behaves exactly like an unallocated entry.
- grad dropout / clipping / normalization knobs (loss.h:145-155).
"""

from __future__ import annotations

import dataclasses
import threading
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from wormhole_tpu.data.rowblock import DeviceBatch, RowBlock, to_device_batch
from wormhole_tpu.models import linear as linmod
from wormhole_tpu.ops import coo_kernels as ck
from wormhole_tpu.ops import metrics as M
from wormhole_tpu.ops.localizer import localize
from wormhole_tpu.ops.penalty import l1l2_solve
from wormhole_tpu.ops.spmv import row_squares, spmm, spmv, spmv_t
from wormhole_tpu.parallel.kvstore import KVStore, TableSpec, quantize_push
from wormhole_tpu.parallel.mesh import (batch_sharding, describe_placement,
                                        make_mesh)


@dataclasses.dataclass
class DifactoConfig(linmod.LinearConfig):
    """Extends the linear config surface with the embedding block of
    reference difacto config.proto (dim/threshold/lambda/init_scale/
    dropout/grad_clipping/grad_normalization)."""

    dim: int = 8                 # embedding dimension V_k
    threshold: int = 2           # occurrence count to admit an embedding
    l1_shrk: bool = False        # require w != 0 for admission
    lambda_V: float = 0.01       # l2 on V (AdaGrad update)
    V_init_scale: float = 0.01   # N(0, scale) init
    V_lr_eta: float = 0.01
    V_lr_beta: float = 1.0
    grad_clipping: float = 0.0   # clip each V grad entry to [-c, c]; 0=off
    grad_normalization: bool = False  # scale V grad by 1/|batch|
    dropout: float = 0.0         # zero a fraction of V grads
    v_buckets: int = 0           # embedding table size; 0 = num_buckets
    # early stop when val objv improves less than this (async_sgd.h:31-49)
    early_stop_epsilon: float = 0.0

    @property
    def vb(self) -> int:
        return self.v_buckets or self.num_buckets


def _fm_forward(cfg: DifactoConfig, w, V, cnt, seg, idx, vidx, val,
                num_rows: int):
    """Admission mask + FM margin, shared by the train and eval steps so
    the two can never desync. Returns (margin, xw, xv, vval)."""
    admit = cnt >= cfg.threshold
    if cfg.l1_shrk:
        admit = admit & (w != 0)
    admit_nz = jnp.take(admit.astype(jnp.float32), idx)
    xw = spmv(seg, idx, val, w, num_rows)
    vval = val * admit_nz  # un-admitted keys contribute no V terms
    xv = spmm(seg, vidx, vval, V, num_rows)          # [B, k]
    x2v2 = row_squares(seg, vidx, vval, V, num_rows)  # [B, k]
    margin = xw + 0.5 * jnp.sum(xv * xv - x2v2, axis=-1)
    return margin, xw, xv, vval


def _tables_for(cfg: DifactoConfig) -> dict[str, TableSpec]:
    def v_init(key, shape, dtype):
        return cfg.V_init_scale * jax.random.normal(key, shape, dtype)

    return {
        "w": TableSpec(),
        "z": TableSpec(),
        # second-moment / count accumulators floor at bf16 on the push
        # wire (huge-dynamic-range nonnegative deltas: see TableSpec)
        "n": TableSpec(wire_cap="bf16"),
        "cnt": TableSpec(dtype=jnp.float32, wire_cap="bf16"),
        "V": TableSpec(tail=(cfg.dim,), init=v_init),
        "nV": TableSpec(tail=(cfg.dim,), wire_cap="bf16"),
    }


class _CombinedStore:
    """Checkpoint adapter presenting the w-tables and V-tables as one
    store (utils/checkpoint.py only needs to_numpy/from_numpy/mesh)."""

    def __init__(self, *stores):
        self.stores = stores
        self.mesh = stores[0].mesh

    on_load = None  # callback fired after from_numpy (count-mirror sync)
    on_sparse_pull = None  # callback fired with {table: (idx, rows)}

    def to_numpy(self):
        out = {}
        for s in self.stores:
            out.update(s.to_numpy())
        return out

    def from_numpy(self, arrays):
        known = set().union(*(s.state for s in self.stores))
        unknown = set(arrays) - known
        assert not unknown, f"unknown tables {sorted(unknown)}"
        for s in self.stores:
            own = {k: v for k, v in arrays.items() if k in s.state}
            s.from_numpy(own)
        if self.on_load is not None:
            self.on_load()

    def _sub(self, name):
        for s in self.stores:
            if name in s.state:
                return s
        raise KeyError(name)

    def gather_rows(self, name, idx):
        return self._sub(name).gather_rows(name, idx)

    def gather_rows_multi(self, names, idx):
        by_store = {}
        for k in names:
            by_store.setdefault(id(self._sub(k)), (self._sub(k), []))[1] \
                .append(k)
        out = {}
        for s, ks in by_store.values():
            out.update(s.gather_rows_multi(ks, idx))
        return out

    def scatter_rows(self, name, idx, vals):
        self._sub(name).scatter_rows(name, idx, vals)

    def zero_init_names(self):
        out = set()
        for s in self.stores:
            out |= s.zero_init_names()
        return out

    def wire_cap_names(self):
        out = set()
        for s in self.stores:
            out |= s.wire_cap_names()
        return out

    @property
    def state(self):
        """Merged read view over both table groups (do not assign into
        it; use the sub-stores)."""
        out = {}
        for s in self.stores:
            out.update(s.state)
        return out

    def nnz(self, name="w"):
        for s in self.stores:
            if name in s.state:
                return s.nnz(name)
        raise KeyError(name)


class DifactoLearner:
    """Jitted FM train/eval/predict over sharded w and V tables."""

    def __init__(self, cfg: DifactoConfig, mesh=None, seed: int = 0):
        assert 0 < cfg.vb <= cfg.num_buckets, (
            f"v_buckets must be in (0, num_buckets]; got {cfg.vb}")
        assert cfg.algo == "ftrl", (
            "difacto trains w with FTRL (reference async_sgd.h:262-286); "
            f"algo={cfg.algo!r} is not supported here")
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_mesh(num_model=1)
        self.store = KVStore(self.mesh, cfg.num_buckets,
                             {k: v for k, v in _tables_for(cfg).items()
                              if v.tail == ()}, seed=seed)
        # V tables may use a smaller bucket space; keep them in a second
        # KVStore so each table's bucket axis shards over the model axis
        self.vstore = KVStore(self.mesh, cfg.vb,
                              {k: v for k, v in _tables_for(cfg).items()
                               if v.tail != ()}, seed=seed + 1)
        self._bsh1 = batch_sharding(self.mesh, 1)
        self._dropped_rows = 0
        self._step_count = 0
        self.ckpt_store = _CombinedStore(self.store, self.vstore)
        # compact Pallas FM path (see the block comment above _pack_fm);
        # l1_shrk needs device-resident w, sharded meshes use the XLA
        # collectives path
        D = self.mesh.shape.get("data", 1)
        M_ = self.mesh.shape.get("model", 1)
        want = cfg.kernel == "pallas" or (
            cfg.kernel == "auto" and jax.default_backend() == "tpu")
        # the fused in-place updates need tables that tile cleanly: dim a
        # power of two dividing 128, V and w tables whole numbers of
        # (TILE_HI, 128) flat tiles, lane-aligned rows; the row-gather
        # kernels compute flat int32 offsets uniq * dim, so the flat V
        # table must fit int32 (ADVICE r2; pack_tile_coo asserts the
        # same for w)
        blockers = [reason for bad, reason in (
            (cfg.l1_shrk, "l1_shrk needs device-resident w"),
            (D != 1 or M_ != 1, f"mesh {D}x{M_} has more than one device"),
            (cfg.minibatch % 128 != 0, "minibatch % 128 != 0"),
            (cfg.dim & (cfg.dim - 1) != 0 or 128 % cfg.dim != 0,
             f"dim {cfg.dim} is not a power of two dividing 128"),
            ((cfg.vb * cfg.dim) % ck.TILE != 0
             or cfg.num_buckets % ck.TILE != 0,
             f"tables are not whole {ck.TILE}-entry tiles"),
            (cfg.vb * cfg.dim >= 2**31, "flat V table overflows int32"),
        ) if bad]
        self._use_fm_pallas = want and not blockers
        #: start-up statement of where and how this learner runs
        self.placement = describe_placement(
            self.mesh, "difacto", self._use_fm_pallas,
            "; ".join(blockers) if want else
            "kernel=xla" if cfg.kernel == "xla" else "")
        self._fm_caps = None
        self._fm_steps = None
        self._fm_lock = threading.Lock()
        self._cnt_host = np.zeros(cfg.num_buckets, np.float32)
        # pack-version counter for the epoch cache: bumped whenever the
        # count mirror resyncs, since admission (hence the packed vval)
        # is a function of the mirror's contents
        self._pack_epoch = 0
        self.ckpt_store.on_load = self.refresh_count_mirror
        self.ckpt_store.on_sparse_pull = self._on_sparse_pull
        # sparse PS wire hints: unique w-space / V-space rows touched by
        # trained batches since the last collect_touched() drain
        self.track_touched = False
        self._touched_lock = threading.Lock()
        self._touched_w: list[np.ndarray] = []
        self._touched_v: list[np.ndarray] = []

        @partial(jax.jit, donate_argnums=(0, 1))
        def train_step(state, vstate, seg, idx, vidx, val, label, mask, rngkey):
            new_state = dict(state)
            new_vstate = dict(vstate)
            nb, vb, dim = cfg.num_buckets, cfg.vb, cfg.dim

            # ---- count push + admission (kPushFeaCnt parity) -------------
            push_cnt = self.store.constrain(
                "cnt",
                jax.ops.segment_sum((val != 0).astype(jnp.float32), idx,
                                    num_segments=nb))
            cnt = state["cnt"] + push_cnt
            new_state["cnt"] = cnt

            # ---- forward -------------------------------------------------
            w = state["w"]
            V = vstate["V"]
            margin, xw, xv, vval = _fm_forward(
                cfg, w, V, cnt, seg, idx, vidx, val, label.shape[0])
            obj, d = linmod._loss_dual(cfg.loss, label, margin)
            d = d * mask

            # ---- gradients ----------------------------------------------
            gw = spmv_t(seg, idx, val, d, nb)
            gw = quantize_push(gw, cfg.fixed_bytes)
            gw = self.store.constrain("w", gw)
            touched_w = (push_cnt > 0).astype(jnp.float32)

            # dV_j = sum_i d_i x_ij (Xv_i - x_ij V_j)   (loss.h:183-279)
            d_nz = jnp.take(d, seg) * vval                      # [nnz]
            xv_nz = jnp.take(xv, seg, axis=0)                   # [nnz, k]
            v_nz = jnp.take(V, vidx, axis=0)                    # [nnz, k]
            contrib = d_nz[:, None] * (xv_nz - vval[:, None] * v_nz)
            gV = jax.ops.segment_sum(contrib, vidx, num_segments=vb)
            if cfg.grad_normalization:
                gV = gV / jnp.maximum(jnp.sum(mask), 1.0)
            if cfg.grad_clipping > 0:
                gV = jnp.clip(gV, -cfg.grad_clipping, cfg.grad_clipping)
            if cfg.dropout > 0:
                keep = jax.random.bernoulli(rngkey, 1.0 - cfg.dropout,
                                            gV.shape)
                gV = gV * keep
            gV = quantize_push(gV, cfg.fixed_bytes)
            gV = self.vstore.constrain("V", gV)
            touched_v = self.vstore.constrain(
                "nV",
                jax.ops.segment_sum(
                    (vval != 0).astype(jnp.float32), vidx, num_segments=vb
                )[:, None] * jnp.ones((1, dim)),
            )
            touched_v = (touched_v > 0).astype(jnp.float32)

            # ---- updates: w by FTRL, V by AdaGrad ------------------------
            lin_state = {"w": state["w"], "z": state["z"], "n": state["n"]}
            lin_new = linmod._update("ftrl", lin_state, gw, touched_w, cfg)
            new_state.update(lin_new)

            nV = vstate["nV"] + touched_v * gV * gV
            eta = (cfg.V_lr_beta + jnp.sqrt(nV)) / cfg.V_lr_eta
            V_new = V - touched_v * (gV + cfg.lambda_V * V) / eta
            new_vstate["V"] = jnp.where(touched_v > 0, V_new, V)
            new_vstate["nV"] = nV

            new_w = (jnp.sum(new_state["w"] != 0)
                     - jnp.sum(w != 0)).astype(jnp.float32)
            prog = linmod._progress(obj, margin, label, mask, new_w)
            obj_w, _ = linmod._loss_dual(cfg.loss, label, xw)
            prog["objv_w"] = jnp.sum(obj_w * mask)
            return new_state, new_vstate, prog

        @jax.jit
        def fwd(state, vstate, seg, idx, vidx, val, label, mask):
            margin, _, _, _ = _fm_forward(
                cfg, state["w"], vstate["V"], state["cnt"],
                seg, idx, vidx, val, label.shape[0])
            obj, _ = linmod._loss_dual(cfg.loss, label, margin)
            return margin, linmod._progress(obj, margin, label, mask)

        self._train_step = train_step
        self._fwd = fwd
        self._rng = jax.random.PRNGKey(seed + 17)

    def derived_tables(self) -> dict:
        """w trains by FTRL (async_sgd.h:262-286): non-additive prox of
        the additive (z, n), recomputed server-side (see
        LinearLearner.derived_tables)."""
        cfg = self.cfg
        return {"w": {"kind": "ftrl_prox", "lr_eta": cfg.lr_eta,
                      "lr_beta": cfg.lr_beta, "lambda_l1": cfg.lambda_l1,
                      "lambda_l2": cfg.lambda_l2}}

    # -- compact Pallas FM path ---------------------------------------------
    # The XLA segment-op step spends ~85ms/step at Criteo shape: per-nnz
    # [nnz, dim] gathers + two segment-sums for the V terms, a 4M-wide
    # count scatter, and dense table updates. The compact path localizes
    # both key spaces on the host (the Localizer role), runs the scalar
    # COO kernels on the compact w domain and the FM/SpMM kernels
    # (fm_pull/fm_push) on the compact V domain, and updates/scatters
    # only touched entries. Admission (cnt >= threshold) is computed on a
    # HOST count mirror during packing — counts are pure data statistics
    # the host can track exactly, and the mirror resyncs from the store
    # after loads and PS pulls. l1_shrk needs device-resident w, so it
    # stays on the XLA path.

    def refresh_count_mirror(self) -> None:
        self._cnt_host = np.asarray(self.store.state["cnt"]).copy()
        self._pack_epoch += 1

    def on_pass_start(self) -> None:
        """Solver hook: resync the count mirror from the device table so
        any drift (e.g. batches packed but never consumed after an
        aborted pass) is bounded to one pass."""
        with self._fm_lock:
            self.refresh_count_mirror()

    def _fm_dtype_of(self):
        cfg = self.cfg
        if cfg.kernel_dtype == "f32":
            return jnp.float32
        if cfg.kernel_dtype == "auto" and cfg.fixed_bytes == 0:
            return jnp.float32
        return None  # kernel default (bf16 on TPU, f32 in interpret)

    @property
    def _v_rows_per_tile(self) -> int:
        return ck.TILE // self.cfg.dim

    def _pack_fm(self, db: DeviceBatch, train: bool):
        """Host pack (loader threads, serialized by _fm_lock so the count
        mirror sees batches in order): localize w keys and V row ids into
        tile-run-aligned compact slots (coo_kernels.assign_tile_slots),
        apply admission to the V values, and lay both out for the
        kernels. The tile alignment is what lets the training step update
        both tables in place (ops/fused_update.py) with no XLA element
        gathers or scatters."""
        cfg = self.cfg
        idx64 = db.idx.astype(np.int64)
        live = db.val != 0
        loc = localize(idx64.astype(np.uint64))
        uniq = loc.uniq_keys.astype(np.int64)
        inv = loc.local_index
        live_counts = np.bincount(
            inv[live], minlength=len(uniq)).astype(np.float32)
        with self._fm_lock:
            if self._fm_caps is None:
                # the first batch to pack may be a short tail part: scale
                # its unique counts up to a full minibatch's worth (capped
                # at 4x) so the permanent capacities are not sized from a
                # fragment
                fill = cfg.row_capacity / max(int(live.sum()), 1)
                scale = 1.5 * min(max(fill, 1.0), 4.0)
                blocks_w = ck.tile_blocks_needed(uniq, ck.TILE)
                uw = (-(-int(scale * blocks_w) * ck.BLK_U // ck.TILE)
                      * ck.TILE)
                vuniq0 = (np.unique(idx64[live] % cfg.vb)
                          if live.any() else np.zeros(1, np.int64))
                blocks_v = ck.tile_blocks_needed(vuniq0,
                                                 self._v_rows_per_tile)
                uv = int(scale * blocks_v + 1) * ck.BLK_U
                self._fm_caps = (uw, uv)
                self._build_fm(uw, uv)
        uw_cap, uv_cap = self._fm_caps

        ts_w = ck.assign_tile_slots(uniq, ck.TILE, uw_cap, cfg.num_buckets)
        slot_nz = ts_w.slot_of_uniq[inv]
        keep = slot_nz < uw_cap
        dropped = int(np.count_nonzero(~keep & live))
        idx64, seg, val, slot_nz = (idx64[keep], db.seg[keep],
                                    db.val[keep], slot_nz[keep])
        live = val != 0
        kept_r = ts_w.slot_of_uniq < uw_cap
        wcnts = np.zeros(uw_cap, np.float32)
        wcnts[ts_w.slot_of_uniq[kept_r]] = live_counts[kept_r]

        # admission per key from the mirror; training includes this
        # batch's own counts (the reference makes the weight pull depend
        # on the count push of the same minibatch, async_sgd.h:374-381).
        # Only this mirror read-modify-write needs the lock — packing
        # itself runs concurrently across loader threads.
        with self._fm_lock:
            cnt_key = self._cnt_host[uniq]
            if train:
                cnt_key = cnt_key + live_counts
                self._cnt_host[uniq[kept_r]] += live_counts[kept_r]
        adm_nz = (cnt_key >= cfg.threshold)[inv][keep] & live

        # V domain: localize (bucket % vb) row ids of the kept nonzeros
        vidx = (idx64 % cfg.vb).astype(np.uint64)
        loc_v = localize(vidx)
        ts_v = ck.assign_tile_slots(loc_v.uniq_keys, self._v_rows_per_tile,
                                    uv_cap, cfg.vb)
        vslot_nz = ts_v.slot_of_uniq[loc_v.local_index]
        vval = np.where(adm_nz, val, 0.0).astype(np.float32)
        keepv = vslot_nz < uv_cap
        dropped += int(np.count_nonzero(~keepv & (vval != 0)))
        segv, vvalv, vslotv = seg[keepv], vval[keepv], vslot_nz[keepv]
        # row-major padded view (minibatch x nnz_per_row) of the live
        # nonzeros, laid out over the W-SLOT domain (ck.build_rm): the
        # forward's xw AND xv/x2 sums become ONE XLA row gather from the
        # unified compact table U = [V-row | w] (indexed by w slot; see
        # _build_fm) + a dense reshape-reduce — no radix-image kernel on
        # the whole forward path. Slot `uw_cap` is the appended zero
        # row. Three channels ride the layout: the w slot, the w value
        # (all live nonzeros), and the ADMITTED value (V side — zero
        # where the count threshold or uv_cap overflow masks the
        # embedding, matching the reference's unallocated entries).
        W = cfg.nnz_per_row
        mb = cfg.minibatch
        rm_slot, (rm_wval, rm_vval), over = ck.build_rm(
            seg, slot_nz, val, mb, W, uw_cap,
            extra=(np.where(keepv, vval, 0.0),))
        rm_dropped = 0
        if len(over):
            # overflow beyond nnz_per_row: since the forward's xw rides
            # the SAME row-major layout, a row's nonzeros past
            # nnz_per_row are dropped from EVERY layout (rm forward —
            # including the linear xw term — wcoo backward, vcoo
            # backward) so pull and push agree about which nonzeros
            # exist
            rm_dropped = int(np.count_nonzero(val[over]))
            val = val.copy()
            val[over] = 0.0
            mask_src = np.ones(len(seg), bool)
            mask_src[over] = False
            vvalv[~mask_src[keepv]] = 0.0
        # per-w-slot V row for the unified table: slot's key -> its V
        # bucket's compact slot (uv_cap sentinel -> zero V row, covering
        # alignment holes AND uv_cap-overflowed keys)
        vslot_w = np.full(uw_cap, uv_cap, np.int32)
        w_slots_valid = np.flatnonzero(ts_w.uniq < cfg.num_buckets)
        vkeys = (ts_w.uniq[w_slots_valid].astype(np.int64)
                 % cfg.vb).astype(np.uint64)
        li = np.searchsorted(loc_v.uniq_keys, vkeys)
        li = np.clip(li, 0, max(len(loc_v.uniq_keys) - 1, 0))
        ok = loc_v.uniq_keys[li] == vkeys
        vs = np.minimum(ts_v.slot_of_uniq[li], uv_cap).astype(np.int32)
        vslot_w[w_slots_valid] = np.where(ok, vs, uv_cap)
        if dropped or rm_dropped:
            # two distinct causes with distinct remedies, counted
            # separately so an undersized nnz_per_row is diagnosable
            # (ADVICE #4): slot-cap overflow (the compact W/V tables
            # sized off the first batch ran out of slots — raise
            # compact caps / first-batch key diversity) vs row-cap
            # overflow (a row carried more than nnz_per_row nonzeros —
            # raise nnz_per_row; note the rm layout caps the xw forward
            # too, not just the V embeddings)
            import logging

            logging.getLogger(__name__).warning(
                "fm compaction overflow: dropped %d nonzeros to the "
                "slot caps (caps %s — raise key diversity of the first "
                "batch) and %d to the nnz_per_row row cap (%d — raise "
                "nnz_per_row; the row-major forward caps xw too)",
                dropped, self._fm_caps, rm_dropped, W)
        if not train:
            # eval/predict never scatter: the sorted COO streams (and
            # their radix sorts) are a train-only cost
            return (ts_w, wcnts, None, ts_v, None, None,
                    rm_slot, rm_wval, rm_vval, vslot_w)
        wcoo = ck.pack_sorted_coo(slot_nz, seg, val, uw_cap,
                                  capacity=cfg.row_capacity)
        vtouched = np.zeros(uv_cap, np.float32)
        vtouched[np.unique(vslotv[vvalv != 0])] = 1.0
        vcoo = ck.pack_sorted_coo(vslotv, segv, vvalv, uv_cap,
                                  capacity=cfg.row_capacity,
                                  tile=ck.TILE_HI, blk=ck.FM_BLK)
        return (ts_w, wcnts, wcoo, ts_v, vtouched, vcoo,
                rm_slot, rm_wval, rm_vval, vslot_w)

    def _build_fm(self, uw_cap: int, uv_cap: int) -> None:
        cfg = self.cfg
        dt = self._fm_dtype_of()
        # wire dtype for the XLA gather operands (U, xvd): dt resolves
        # to None in bf16 mode (the kernels pick bf16 internally), but
        # astype(None) is a float32 no-op — so name the gather dtype
        # explicitly. Half-width rows halve the forward/backward gather
        # bytes; sums still accumulate in f32 (bf16 mode is the
        # documented throughput opt-in; f32 mode stays exact).
        wire = dt if dt is not None else (
            jnp.float32 if ck._use_interpret() else jnp.bfloat16)
        from wormhole_tpu.ops.fused_update import (row_tile_gather,
                                                   scatter_update,
                                                   v_scatter_update)

        def gather_compact(state, vstate, uniq_w, wtm, uniq_v, vtm):
            wc = ck.tile_gather(state["w"].reshape(-1, ck.LANES),
                                uniq_w, wtm, dtype=dt)
            Vc = row_tile_gather(vstate["V"].reshape(-1, ck.LANES),
                                 uniq_v, vtm, cfg.dim, dtype=dt)
            return wc, Vc

        def forward_rm(wc, Vc, rm_slot, rm_wval, rm_vval, vslot_w):
            # row-major forward over the UNIFIED compact table
            # U[s] = [V-row of slot s's key | w[s]]: ONE XLA row gather
            # + a dense reshape-reduce yields xw AND xv/x2 together —
            # no radix-image kernel anywhere on the forward path (the
            # former coo_spmv xw was ~7.5 ms of the step, r4 PERF.md).
            # U's V side is a u_cap-sized row gather (cheap: compact
            # rows, not nnz), its w side is the tile-gathered compact
            # w. Rows move at the kernel dtype (half the bytes in bf16
            # mode); products and sums accumulate in f32.
            Vcz = jnp.concatenate(
                [Vc.astype(wire), jnp.zeros((1, cfg.dim), wire)], axis=0)
            U = jnp.concatenate(
                [jnp.take(Vcz, vslot_w, axis=0),
                 wc.astype(wire)[:, None]], axis=1)   # [uw_cap, dim+1]
            Uz = jnp.concatenate(
                [U, jnp.zeros((1, cfg.dim + 1), wire)], axis=0)
            U_nnz = jnp.take(Uz, rm_slot, axis=0)     # [mb*W, dim+1]
            xw = (rm_wval * U_nnz[:, cfg.dim].astype(jnp.float32)
                  ).reshape(cfg.minibatch, -1).sum(1)
            p = rm_vval[:, None] * U_nnz[:, :cfg.dim].astype(jnp.float32)
            xv = p.reshape(cfg.minibatch, -1, cfg.dim).sum(1)
            x2 = (p * p).reshape(cfg.minibatch, -1, cfg.dim).sum(1)
            margin = xw + 0.5 * jnp.sum(xv * xv - x2, axis=-1)
            return xw, xv, margin

        @partial(jax.jit, donate_argnums=(0, 1))
        def train_fm(state, vstate, uniq_w, wtm, wfi, wla, wcnts,
                     widx, wseg, wval, wtmap, wfirst,
                     uniq_v, vtm, vfi, vla, vtouched,
                     vidx, vseg, vval, vtmap, vfirst,
                     rm_slot, rm_wval, rm_vval, vslot_w,
                     label, mask, rngkey):
            wc, Vc = gather_compact(state, vstate, uniq_w, wtm,
                                    uniq_v, vtm)
            xw, xv, margin = forward_rm(wc, Vc, rm_slot, rm_wval,
                                        rm_vval, vslot_w)
            obj, d = linmod._loss_dual(cfg.loss, label, margin)
            d = d * mask

            # w: FTRL at the key's storage — scatter + handle update run
            # inside the fused kernel over touched tiles, in place
            gw = ck.coo_spmv_t(d, widx, wseg, wval, wtmap, wfirst,
                               uw_cap, dtype=dt)
            # cnt rides the fused update's touched-tile walk as an
            # additive table (an XLA element scatter into the 4M-bucket
            # table costs ~4 ms at the Criteo shape; sentinel slots
            # carry all-zero one-hot rows and scatter nothing)
            new_state, new_w = scatter_update(
                "ftrl", state, gw, uniq_w, wtm, wfi, wla,
                lr_eta=cfg.lr_eta, lr_beta=cfg.lr_beta,
                lambda_l1=cfg.lambda_l1, lambda_l2=cfg.lambda_l2,
                fixed_bytes=cfg.fixed_bytes, dtype=dt,
                add_table="cnt", add_values=wcnts)

            # V: AdaGrad at the row's storage, same treatment; the grad
            # filters apply on the compact gradient beforehand.
            # dV_j += sum_i c*(xv_i - val*V_j), c = d_i*val: the xv and
            # d factors ride ONE row gather from the [mb, dim+1] row
            # layout (padding entries carry val = 0 and vanish); the
            # kernel only re-derives tile V rows and scatters.
            xvd = jnp.concatenate([xv, d[:, None]], axis=1).astype(wire)
            G = jnp.take(xvd, vseg, axis=0)
            c = G[:, cfg.dim].astype(jnp.float32) * vval
            # kernel operands at the wire dtype: the contrib matmul
            # runs at the kernel dtype anyway, so f32 a/b would only
            # double the HBM traffic into the scatter kernel
            a = (c[:, None] * G[:, :cfg.dim].astype(jnp.float32)
                 ).astype(wire)
            b = (c * vval).astype(wire)
            gV = ck.fm_push_contrib(Vc, a, b, vidx, vtmap, vfirst,
                                    dtype=dt)
            if cfg.grad_normalization:
                gV = gV / jnp.maximum(jnp.sum(mask), 1.0)
            if cfg.grad_clipping > 0:
                gV = jnp.clip(gV, -cfg.grad_clipping, cfg.grad_clipping)
            if cfg.dropout > 0:
                keep = jax.random.bernoulli(rngkey, 1.0 - cfg.dropout,
                                            gV.shape)
                gV = gV * keep
            gV = quantize_push(gV, cfg.fixed_bytes)
            Vn, nVn = v_scatter_update(
                vstate["V"], vstate["nV"], gV, vtouched, uniq_v,
                vtm, vfi, vla, dim=cfg.dim, V_lr_eta=cfg.V_lr_eta,
                V_lr_beta=cfg.V_lr_beta, lambda_V=cfg.lambda_V, dtype=dt)
            new_vstate = dict(vstate)
            new_vstate["V"] = Vn
            new_vstate["nV"] = nVn

            prog = linmod._progress(obj, margin, label, mask, new_w)
            obj_w, _ = linmod._loss_dual(cfg.loss, label, xw)
            prog["objv_w"] = jnp.sum(obj_w * mask)
            return new_state, new_vstate, prog

        @jax.jit
        def fwd_fm(state, vstate, uniq_w, wtm, uniq_v, vtm,
                   rm_slot, rm_wval, rm_vval, vslot_w, label, mask):
            # eval/predict never scatter: only the compact gathers and
            # the rm channels ride along (the COO streams are a train-
            # only cost — _pack_fm skips packing them when train=False)
            wc, Vc = gather_compact(state, vstate, uniq_w, wtm,
                                    uniq_v, vtm)
            margin = forward_rm(wc, Vc, rm_slot, rm_wval, rm_vval,
                                vslot_w)[2]
            obj, _ = linmod._loss_dual(cfg.loss, label, margin)
            return margin, linmod._progress(obj, margin, label, mask)

        self._fm_steps = (train_fm, fwd_fm)

    def prepare_batch(self, blk: RowBlock, train: bool = True):
        """Host-side batch prep for the solver's loader threads."""
        cfg = self.cfg
        db = to_device_batch(blk, cfg.minibatch, cfg.row_capacity,
                             cfg.num_buckets)
        if db.dropped_rows:
            self._dropped_rows += db.dropped_rows
        if not self._use_fm_pallas:
            return ("xla", db, blk.size)
        pk = self._pack_fm(db, train)
        args = tuple(jax.device_put(a) for a in
                     self._fm_args(pk, db.label, db.row_mask, train))
        ids = None
        if train and self.track_touched:
            # host-side touched rows for the sparse PS wire, extracted
            # before the pack moves to device (sentinel slots filtered)
            ts_w, ts_v = pk[0], pk[3]
            ids = (ts_w.uniq[ts_w.uniq < cfg.num_buckets].astype(np.int64),
                   ts_v.uniq[ts_v.uniq < cfg.vb].astype(np.int64))
        return ("fm", args, blk.size, train, ids)

    def _fm_args(self, pk, label, mask, train: bool):
        (ts_w, wcnts, wcoo, ts_v, vtouched, vcoo,
         rm_slot, rm_wval, rm_vval, vslot_w) = pk
        j = jnp.asarray
        rm_parts = [j(rm_slot), j(rm_wval), j(rm_vval), j(vslot_w)]
        if train:
            wparts = [j(wcoo.idx), j(wcoo.seg), j(wcoo.val),
                      j(wcoo.tmap), j(wcoo.first)]
            vparts = [j(vcoo.idx), j(vcoo.seg), j(vcoo.val),
                      j(vcoo.tmap), j(vcoo.first)] + rm_parts
            return ([j(ts_w.uniq), j(ts_w.tmap_u), j(ts_w.first_u),
                     j(ts_w.last_u), j(wcnts)] + wparts
                    + [j(ts_v.uniq), j(ts_v.tmap_u), j(ts_v.first_u),
                       j(ts_v.last_u), j(vtouched)] + vparts
                    + [j(label), j(mask)])
        return ([j(ts_w.uniq), j(ts_w.tmap_u), j(ts_v.uniq),
                 j(ts_v.tmap_u)] + rm_parts + [j(label), j(mask)])

    # -- global-mesh SPMD protocol (apps/_runner._global_train) ------------
    def global_step_protocol(self):
        """(train_fn, eval_fn) over (seg, idx, val, label, mask) GLOBAL
        arrays; vidx derives on device. Both mutate learner state and
        return a progress dict of device scalars."""
        vb = self.cfg.vb

        def train_fn(args, rng):
            seg, idx, val, label, mask = args
            vidx = idx % np.int32(vb)
            self.store.state, self.vstore.state, prog = self._train_step(
                self.store.state, self.vstore.state, seg, idx, vidx, val,
                label, mask, rng)
            return prog

        def eval_fn(args):
            seg, idx, val, label, mask = args
            vidx = idx % np.int32(vb)
            _, prog = self._fwd(self.store.state, self.vstore.state,
                                seg, idx, vidx, val, label, mask)
            return prog

        return train_fn, eval_fn

    def global_predict_protocol(self):
        """pred_fn over (seg, idx, val, mask) GLOBAL arrays — see
        LinearLearner.global_predict_protocol."""
        import jax.numpy as jnp

        from wormhole_tpu.parallel.mesh import batch_sharding

        vb = self.cfg.vb
        bsh = batch_sharding(self.mesh, 1)

        @jax.jit
        def pred(state, vstate, seg, idx, val, mask):
            vidx = idx % np.int32(vb)
            margin, _ = self._fwd(state, vstate, seg, idx, vidx, val,
                                  jnp.zeros_like(mask), mask)
            return (jax.lax.with_sharding_constraint(margin, bsh),
                    jnp.sum(mask))

        def pred_fn(args):
            seg, idx, val, mask = args
            return pred(self.store.state, self.vstore.state,
                        seg, idx, val, mask)

        return pred_fn

    # -- epoch pack cache ----------------------------------------------------
    #: bump when prepare_batch's output layout changes for identical input
    _PACK_VERSION = 1

    def pack_cache_token(self, train: bool = True):
        """See LinearLearner.pack_cache_token. The compact FM train pack
        is NOT bit-identically replayable: admission depends on the
        evolving count mirror AND packing mutates it (_pack_fm), so a
        replayed pack would both be stale and skip the count push —
        decline with None. Eval packs are pure given a mirror snapshot,
        keyed by the pack-epoch counter that advances on every mirror
        resync. The XLA fallback path packs with no host state at all
        and caches for both."""
        cfg = self.cfg
        base = ("difacto", self._PACK_VERSION, self._use_fm_pallas,
                cfg.minibatch, cfg.nnz_per_row, cfg.num_buckets, cfg.vb,
                cfg.dim, cfg.threshold, cfg.l1_shrk)
        if not self._use_fm_pallas:
            return base
        if train:
            return None
        if self._fm_caps is None:
            return None  # slot caps not yet sized from a first batch
        return base + (self._fm_caps, self._pack_epoch,
                       ck.TILE, ck.BLK_U, ck.TILE_HI, ck.FM_BLK,
                       ck.LANES)

    # -- double-buffered device feed -----------------------------------------
    def stage_batch(self, b, train: bool = True):
        """Loader-side device placement. The compact FM pack already
        device_puts its args in prepare_batch; only the XLA fallback
        still carries host arrays, so stage those here."""
        b = self._prepared(b, train)
        if b[0] != "xla":
            return b
        db, size = b[1], b[2]
        ids = None
        if train and self.track_touched:
            ids_w = np.unique(db.idx[db.val != 0]).astype(np.int64)
            ids = (ids_w, ids_w % self.cfg.vb)
        return ("xla_staged", self._xla_args(db), size, train, ids)

    def _prepared(self, blk, train: bool):
        if isinstance(blk, RowBlock):
            return self.prepare_batch(blk, train=train)
        return blk

    def _xla_args(self, db):
        vidx = (db.idx % np.int32(self.cfg.vb)).astype(np.int32)
        put = lambda x: jax.device_put(x, self._bsh1)
        return (put(db.seg), put(db.idx), put(vidx), put(db.val),
                put(db.label), put(db.row_mask))

    def train_batch(self, blk) -> dict:
        b = self._prepared(blk, train=True)
        self._rng, sub = jax.random.split(self._rng)
        if b[0] == "fm":
            args = b[1]
            self.store.state, self.vstore.state, prog = self._fm_steps[0](
                self.store.state, self.vstore.state, *args, sub)
            if self.track_touched:
                self._note_touched(b[4])
        elif b[0] == "xla_staged":
            self.store.state, self.vstore.state, prog = self._train_step(
                self.store.state, self.vstore.state, *b[1], sub)
            if self.track_touched:
                self._note_touched(b[4])
        else:
            db = b[1]
            self.store.state, self.vstore.state, prog = self._train_step(
                self.store.state, self.vstore.state,
                *self._xla_args(db), sub)
            if self.track_touched:
                ids_w = np.unique(db.idx[db.val != 0]).astype(np.int64)
                self._note_touched((ids_w, ids_w % self.cfg.vb))
        self._step_count += 1
        return jax.tree_util.tree_map(float, prog)

    # -- sparse PS wire hints ------------------------------------------------
    def _note_touched(self, ids) -> None:
        if ids is None:
            ids = (None, None)
        with self._touched_lock:
            self._touched_w.append(ids[0])
            self._touched_v.append(ids[1])

    def collect_touched(self):
        """Sorted-unique global rows touched since the last call, per
        table (the sparse PS push set; reference ZPush of the
        minibatch's keys, async_sgd.h:270-287). Returns None if any
        trained batch lacked a hint (SyncedStore then falls back to a
        full delta scan for this sync)."""
        with self._touched_lock:
            tw, tv = self._touched_w, self._touched_v
            self._touched_w, self._touched_v = [], []
        if any(a is None for a in tw):
            return None
        uw = (np.unique(np.concatenate(tw)) if tw
              else np.empty(0, np.int64))
        uv = (np.unique(np.concatenate(tv)) if tv
              else np.empty(0, np.int64))
        out = {k: uw for k in self.store.state}
        out.update({k: uv for k in self.vstore.state})
        return out

    def _on_sparse_pull(self, updates) -> None:
        """Keep the host count mirror coherent with sparse PS pulls (the
        dense path refreshes it via on_load/from_numpy)."""
        got = updates.get("cnt")
        if got is None:
            return
        idx, rows = got
        with self._fm_lock:
            self._cnt_host[idx] = rows

    def _fwd_any(self, blk):
        b = self._prepared(blk, train=False)
        if b[0] == "fm":
            args, size = b[1], b[2]
            margin, prog = self._fm_steps[1](
                self.store.state, self.vstore.state, *args)
        elif b[0] == "xla_staged":
            size = b[2]
            margin, prog = self._fwd(self.store.state, self.vstore.state,
                                     *b[1])
        else:
            size = b[2]
            margin, prog = self._fwd(self.store.state, self.vstore.state,
                                     *self._xla_args(b[1]))
        return margin, prog, size

    def eval_batch(self, blk) -> dict:
        _, prog, _ = self._fwd_any(blk)
        return jax.tree_util.tree_map(float, prog)

    def predict_batch(self, blk) -> np.ndarray:
        margin, _, size = self._fwd_any(blk)
        out = np.asarray(margin)[:size]
        if self.cfg.prob_predict:
            out = 1.0 / (1.0 + np.exp(-out))
        return out

    def nnz(self) -> int:
        return self.store.nnz("w")

    def num_admitted(self) -> int:
        cnt = np.asarray(self.store.state["cnt"])
        admit = cnt >= self.cfg.threshold
        if self.cfg.l1_shrk:
            admit &= np.asarray(self.store.state["w"]) != 0
        return int(admit.sum())

    def v_collision_rate(self) -> float:
        """Fraction of ADMITTED keys whose V bucket (key % v_buckets) is
        shared with another admitted key. The reference stores exact
        per-key embeddings (async_sgd.h:135-209); the fixed-capacity V
        table is a hash kernel, and this is the metric that bounds the
        aliasing it introduces — size v_buckets so this stays small
        (rate ~ n_admitted / v_buckets for a uniform hash; see
        docs/difacto.md)."""
        cnt = np.asarray(self.store.state["cnt"])
        admit = cnt >= self.cfg.threshold
        if self.cfg.l1_shrk:
            admit &= np.asarray(self.store.state["w"]) != 0
        keys = np.flatnonzero(admit)
        if len(keys) == 0:
            return 0.0
        vb_of = keys % self.cfg.vb
        _, counts = np.unique(vb_of, return_counts=True)
        collided = int(np.sum(counts[counts > 1]))
        return collided / len(keys)


def make_early_stop_hook(cfg: DifactoConfig):
    """Early stop when validation objective stops improving by epsilon
    (reference AsyncScheduler::Stop, difacto async_sgd.h:31-49)."""
    best = {"objv": None}

    def hook(prog, dp, key) -> bool:
        if cfg.early_stop_epsilon <= 0 or key != "val":
            return False
        objv = prog.mean("objv")  # the trained objective, loss-agnostic
        if best["objv"] is not None and (
            best["objv"] - objv < cfg.early_stop_epsilon
        ):
            return True
        if best["objv"] is None or objv < best["objv"]:
            best["objv"] = objv
        return False

    return hook
