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
import logging
import threading
from collections.abc import Mapping
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from wormhole_tpu.data.rowblock import DeviceBatch
from wormhole_tpu.models import linear as linmod
from wormhole_tpu.models import minibatch_learner as mbl
from wormhole_tpu.obs.metrics import REGISTRY
from wormhole_tpu.ops import coo_kernels as ck
from wormhole_tpu.ops.fused_update import (row_gather, scatter_update,
                                           v_update)
from wormhole_tpu.ops.localizer import localize
from wormhole_tpu.ops.spmv import row_squares, spmm, spmv, spmv_t
from wormhole_tpu.parallel.kvstore import KVStore, TableSpec, quantize_push
from wormhole_tpu.parallel.mesh import describe_placement

_log = logging.getLogger(__name__)

# the compact path's own record (docs/observability.md): what the pack
# made and dropped on the host, what the step admitted on the device
_PACK_BATCHES = REGISTRY.counter("difacto.pack.batches")
_PACK_NNZ = REGISTRY.counter("difacto.pack.nnz")
_PACK_DROPPED = REGISTRY.counter("difacto.pack.dropped_nnz")
_V_ROWS = REGISTRY.counter("difacto.v.rows")
_STEP_LIVE = REGISTRY.counter("difacto.step.live_nnz")
_STEP_ADMITTED = REGISTRY.counter("difacto.step.admitted_nnz")

#: what a train step packs (mbl.pack_progress): the XLA step's
#: progress, and after it the compact step's two nonzero counts
XLA_TRAIN_KEYS = tuple(sorted(mbl.TRAIN_KEYS + ("objv_w",)))
FM_TRAIN_KEYS = XLA_TRAIN_KEYS + (
    "live_nnz.hi", "live_nnz.lo", "admitted_nnz.hi", "admitted_nnz.lo")


def _halves(name: str, count) -> dict:
    """An int32 count as its two 16-bit halves: f32, which the packed
    progress is, holds each exactly and would round the whole past
    2^24."""
    return {name + ".hi": count >> 16, name + ".lo": count & 0xFFFF}


def _whole(out: dict, name: str) -> int:
    """Take _halves' two entries out of a read progress dict again."""
    return (int(out.pop(name + ".hi")) << 16) | int(out.pop(name + ".lo"))


def _keyed(step):
    """`step(state, vstate, *batch, sub)` jitted as the step train_batch
    launches: it takes the learner's key in place of the sub-key, splits
    it as train_batch did on the host, and returns the next key after
    the step's own outputs. One launch a step, and the chain of sub-keys
    unchanged."""
    @partial(jax.jit, donate_argnums=(0, 1))
    def run(state, vstate, *args):
        *batch, key = args
        nxt, sub = jax.random.split(key)
        return (*step(state, vstate, *batch, sub), nxt)
    return run


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


def row_stride(dim: int) -> int:
    """Floats from one embedding row to the next where the table is held
    lane-packed (parallel/kvstore.TableSpec.stride): the smallest power
    of two that holds `dim`, so that 128 // stride whole rows make a lane
    line and none straddles two (50 -> 64, 5 -> 8, 8 -> 8). 0 where a
    row is wider than a line."""
    stride = 1 << max(dim - 1, 0).bit_length()
    return stride if stride <= ck.LANES else 0


def _tables_for(cfg: DifactoConfig, stride: int = 0) -> dict[str, TableSpec]:
    def v_init(key, shape, dtype):
        return cfg.V_init_scale * jax.random.normal(key, shape, dtype)

    return {
        "w": TableSpec(),
        "z": TableSpec(),
        # second-moment / count accumulators floor at bf16 on the push
        # wire (huge-dynamic-range nonnegative deltas: see TableSpec)
        "n": TableSpec(wire_cap="bf16"),
        "cnt": TableSpec(dtype=jnp.float32, wire_cap="bf16"),
        "V": TableSpec(tail=(cfg.dim,), init=v_init, stride=stride),
        "nV": TableSpec(tail=(cfg.dim,), wire_cap="bf16", stride=stride),
    }


class _CombinedStore:
    """Checkpoint adapter presenting the w-tables and V-tables as one
    store (utils/checkpoint.py only needs to_numpy/from_numpy/mesh)."""

    def __init__(self, *stores):
        self.stores = stores
        self.mesh = stores[0].mesh

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
        """Merged read view over both table groups as they are stored (do
        not assign into it; use the sub-stores)."""
        out = {}
        for s in self.stores:
            out.update(s.state)
        return out

    def nnz(self, name="w"):
        return self._sub(name).nnz(name)

    def rows_view(self, name):
        return self._sub(name).rows_view(name)


class _Tables(Mapping):
    """Every table by name, each as an array of rows (num_buckets,
    *tail): what `DifactoLearner.tables()` hands out. A lane-packed
    table is unpacked when it is asked for and not kept, so that two of
    them never lie unpacked side by side."""

    def __init__(self, store: _CombinedStore):
        self._store = store

    def __getitem__(self, name):
        return self._store.rows_view(name)

    def __iter__(self):
        return iter(self._store.state)

    def __len__(self):
        return len(self._store.state)


class DifactoLearner(mbl.MinibatchLearner):
    """Jitted FM train/eval/predict over sharded w and V tables."""

    # one forward program serves eval and predict: it takes the rows
    _predict_rows = True

    def __init__(self, cfg: DifactoConfig, mesh=None, seed: int = 0):
        assert 0 < cfg.vb <= cfg.num_buckets, (
            f"v_buckets must be in (0, num_buckets]; got {cfg.vb}")
        assert cfg.algo == "ftrl", (
            "difacto trains w with FTRL (reference async_sgd.h:262-286); "
            f"algo={cfg.algo!r} is not supported here")
        super().__init__(cfg, mesh)
        # compact Pallas FM path (see the block comment above _pack_fm);
        # l1_shrk needs w != 0 beside the count in the admission test,
        # sharded meshes use the XLA collectives path
        D = self.mesh.shape.get("data", 1)
        M_ = self.mesh.shape.get("model", 1)
        want = cfg.kernel == "pallas" or (
            cfg.kernel == "auto" and jax.default_backend() == "tpu")
        stride = row_stride(cfg.dim)
        # the w tables are walked by whole (TILE_HI, 128) tiles and the V
        # tables gathered by whole lane lines; ids and line numbers are
        # int32 on the device. Neither the row width nor the batch's row
        # count is among the blockers: a row is padded to its stride in
        # the table (zero lanes that stay zero), the batch to the
        # kernels' 128-row multiple under row_mask
        blockers = [reason for bad, reason in (
            (cfg.l1_shrk, "l1_shrk needs device-resident w"),
            (D != 1 or M_ != 1, f"mesh {D}x{M_} has more than one device"),
            (stride == 0, f"dim {cfg.dim} is wider than a {ck.LANES}-lane "
                          "line"),
            (cfg.num_buckets % ck.TILE != 0
             or (cfg.vb * stride) % ck.LANES != 0,
             f"tables are not whole {ck.TILE}-entry tiles and lane lines"),
            (cfg.num_buckets >= 2**31, "bucket ids overflow int32"),
        ) if bad]
        self._use_fm_pallas = want and not blockers
        #: floats from one V row to the next as the tables are stored:
        #: lane-packed on the compact path, else 0 (rows of dim)
        self._stride = stride if self._use_fm_pallas else 0
        #: rows of a compact batch: the minibatch padded to the kernels'
        #: lane multiple; rows past cfg.minibatch are masked and empty
        self._rows = -(-cfg.minibatch // ck.LANES) * ck.LANES
        if self._use_fm_pallas:
            self._batch_rows = self._rows
        specs = _tables_for(cfg, self._stride)
        self.store = KVStore(self.mesh, cfg.num_buckets,
                             {k: v for k, v in specs.items()
                              if v.tail == ()}, seed=seed)
        # V tables may use a smaller bucket space; keep them in a second
        # KVStore so each table's bucket axis shards over the model axis
        self.vstore = KVStore(self.mesh, cfg.vb,
                              {k: v for k, v in specs.items()
                               if v.tail != ()}, seed=seed + 1)
        self.ckpt_store = _CombinedStore(self.store, self.vstore)
        #: start-up statement of where and how this learner runs
        self.placement = describe_placement(
            self.mesh, "difacto", self._use_fm_pallas,
            "; ".join(blockers) if want else
            "kernel=xla" if cfg.kernel == "xla" else "")
        self._fm_caps = None
        self._fm_steps = None
        self._fm_lock = threading.Lock()

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
            lin_new, new_w = linmod._update("ftrl", lin_state, gw,
                                            touched_w, cfg)
            new_state.update(lin_new)

            nV = vstate["nV"] + touched_v * gV * gV
            eta = (cfg.V_lr_beta + jnp.sqrt(nV)) / cfg.V_lr_eta
            V_new = V - touched_v * (gV + cfg.lambda_V * V) / eta
            new_vstate["V"] = jnp.where(touched_v > 0, V_new, V)
            new_vstate["nV"] = nV

            prog = linmod._progress(obj, margin, label, mask, new_w)
            obj_w, _ = linmod._loss_dual(cfg.loss, label, xw)
            prog["objv_w"] = jnp.sum(obj_w * mask)
            return new_state, new_vstate, mbl.pack_progress(
                prog, XLA_TRAIN_KEYS)

        @jax.jit
        def fwd(state, vstate, seg, idx, vidx, val, label, mask):
            margin, _, _, _ = _fm_forward(
                cfg, state["w"], vstate["V"], state["cnt"],
                seg, idx, vidx, val, label.shape[0])
            obj, _ = linmod._loss_dual(cfg.loss, label, margin)
            return margin, mbl.pack_progress(
                linmod._progress(obj, margin, label, mask),
                mbl.EVAL_KEYS)

        # the global SPMD loop hands every rank the same sub-key
        # (global_step_protocol); train_batch chains the learner's own
        self._train_step = jax.jit(train_step, donate_argnums=(0, 1))
        self._train_keyed = _keyed(train_step)
        self._fwd = fwd
        self._rng = jax.random.PRNGKey(seed + 17)

        nb, vb = cfg.num_buckets, cfg.vb

        def with_v(ids_w):
            # a batch's rows in w's id space, and the V rows they hash to
            return ids_w, np.unique(ids_w % vb)

        self._kinds = {
            "xla": mbl._Kind(
                lambda db, train: db,
                mbl._device_args(
                    lambda db, train: (
                        db.seg, db.idx,
                        (db.idx % np.int32(vb)).astype(np.int32), db.val),
                    partial(jax.device_put, device=self._bsh1)),
                *self._steps(lambda: (self._train_keyed, self._fwd)),
                lambda db: with_v(linmod._nonzero_ids(db)[0]),
                XLA_TRAIN_KEYS),
            # the compact path (see the block comment above _pack_fm);
            # sentinel slots are no rows of the table
            "fm": mbl._Kind(
                self._pack_fm,
                mbl._device_args(self._fm_arrays, jax.device_put),
                *self._steps(lambda: self._fm_steps),
                lambda pk: with_v(pk[0][pk[0] < nb].astype(np.int64)),
                FM_TRAIN_KEYS),
        }

    def _id_spaces(self) -> tuple:
        return (self.store.state, self.vstore.state)

    def _steps(self, programs):
        """A kind's (train, eval, predict) over `programs()`, its jitted
        (keyed train step, forward) pair, looked up a call because the
        compact pair is built with the capacities, after its record:
        thin wrappers outside the jit, one launch a step. Beside the w
        tables a step reads the V tables where they live and writes them
        back; the key it drew from comes back advanced, and stays on the
        device."""
        def train(state, *args):
            state, self.vstore.state, prog, self._rng = programs()[0](
                state, self.vstore.state, *args, self._rng)
            return state, prog

        def forward(out):   # the forward gives (margins, progress)
            return lambda state, *args: programs()[1](
                state, self.vstore.state, *args)[out]

        return train, forward(1), forward(0)

    def _trained(self, out: dict) -> dict:
        if "live_nnz.hi" in out:    # the compact step's two counts
            _STEP_LIVE.inc(_whole(out, "live_nnz"))
            _STEP_ADMITTED.inc(_whole(out, "admitted_nnz"))
        return out

    def _choose_kind(self, db: DeviceBatch) -> str:
        return "fm" if self._use_fm_pallas else "xla"

    # -- compact Pallas FM path ---------------------------------------------
    # The XLA segment-op step makes dense temporaries of both tables'
    # shapes and per-nnz [nnz, dim] gathers: ~85 ms a step at dim 8, and
    # at dim 50 over 2^24 rows it does not fit a chip. The compact path
    # localizes the batch on the host (the Localizer role) and leaves on
    # the device only work that scales with the batch:
    #   w side   the batch's distinct buckets in tile-run-aligned compact
    #            slots (ck.assign_tile_slots): w and cnt pulled by
    #            ck.tile_gather, the gradient by ck.coo_spmv_t, FTRL and
    #            the count push by the fused in-place update
    #   V side   the batch's distinct lane lines of the lane-packed V
    #            table (128 // stride rows a line), sorted: a compact row
    #            domain of ul_cap * rpl rows, sized by the batch's keys
    #            and not by the table's tiles. Lines are fetched and
    #            written back by row gather / scatter (ops/fused_update);
    #            the gradient is summed by key (ck.fm_push_contrib over
    #            the batch's distinct keys), masked, and added up by row
    #   forward  a row gather a nonzero from a table over the batch's
    #            distinct keys, U[key] = [V row of the key, if admitted |
    #            w], laid out by position in the row (ck.build_rm,
    #            position-major): xw and xv / x2 together
    # Admission is decided in the step, as in the XLA step and upstream
    # (the weight pull waits for the count push of the same minibatch,
    # async_sgd.h:374-381): cnt is read at the compact w slots, the
    # batch's own counts added, `>= threshold` tested there, and the V
    # side of the forward and of the push masked by it. The pack decides
    # nothing of it, so a train pack is a pure function of the batch and
    # the capacities, and the pack cache replays it. l1_shrk needs w
    # beside the count, so it stays on the XLA path.

    #: what _pack_fm returns, in the order the steps take it: an eval
    #: pack, and the train pack with the two sorted COO streams between
    #: its head and its tail
    _FM_EVAL = ("uniq_w", "wtmap_u", "vlines", "key_slot", "key_vslot",
                "rm_key", "rm_wval")
    _FM_TRAIN = _FM_EVAL[:2] + (
        "wfirst_u", "wlast_u", "wcnts", "widx", "wseg", "wval", "wtmap",
        "wfirst", "vidx", "vseg", "vval", "vtmap", "vfirst",
    ) + _FM_EVAL[2:]

    def _size_fm(self, uniq, live_counts, nnz_live: int) -> tuple:
        """The permanent capacities, from the first batch packed: compact
        w slots (whole tiles' runs), distinct keys, distinct V lines.
        That batch may be a short tail part: its counts are scaled up to
        a full minibatch's worth (capped at 4x), then by 1.5, then
        rounded up to a coarse step (a sixteenth of their power of two),
        so that jobs whose first batches differ by a percent compile the
        same step and share its entry in the compile cache."""
        cfg = self.cfg
        fill = cfg.row_capacity / max(nnz_live, 1)
        scale = 1.5 * min(max(fill, 1.0), 4.0)

        def coarse(n: float, unit: int) -> int:
            step = max(unit, (1 << int(n).bit_length()) // 16 // unit * unit)
            return -(-int(n + 1) // step) * step

        blocks_w = ck.tile_blocks_needed(uniq, ck.TILE)
        uw = -(-int(scale * blocks_w) * ck.BLK_U // ck.TILE) * ck.TILE
        # the keys are tiled by TILE_HI in the V push
        uk = coarse(scale * len(uniq), ck.BLK_U)
        rpl = ck.LANES // self._stride
        lines = np.unique(uniq[live_counts > 0] % cfg.vb // rpl)
        ul = coarse(scale * len(lines), ck.TILE_HI)
        return uw, uk, ul

    def _pack_fm(self, db: DeviceBatch, train: bool) -> tuple:
        """Host pack (loader threads, concurrently): localize the w keys
        into tile-run-aligned compact slots and a dense key rank, the V
        rows into the batch's sorted distinct lane lines, and lay both
        out for the kernels. Returns host arrays in the order the step
        takes them, a pure function of the batch and the capacities."""
        cfg = self.cfg
        rpl = ck.LANES // self._stride
        idx64 = db.idx.astype(np.int64)
        live = db.val != 0
        loc = localize(idx64.astype(np.uint64))
        uniq = loc.uniq_keys.astype(np.int64)
        inv = loc.local_index
        live_counts = np.bincount(
            inv[live], minlength=len(uniq)).astype(np.float32)
        with self._fm_lock:
            if self._fm_caps is None:
                self._fm_caps = self._size_fm(uniq, live_counts,
                                              int(live.sum()))
                self._build_fm(*self._fm_caps)
        uw_cap, uk_cap, ul_cap = self._fm_caps
        uvr_cap = ul_cap * rpl

        # a key is kept if it has a w slot and a rank among the keys
        ts_w = ck.assign_tile_slots(uniq, ck.TILE, uw_cap, cfg.num_buckets)
        kept_key = ts_w.slot_of_uniq < uw_cap
        kept_key[uk_cap:] = False
        keep = kept_key[inv]
        dropped = int(np.count_nonzero(~keep & live))
        seg, val, key_nz = db.seg[keep], db.val[keep], inv[keep]
        slot_nz = ts_w.slot_of_uniq[key_nz]
        wcnts = np.zeros(uw_cap, np.float32)
        wcnts[ts_w.slot_of_uniq[kept_key]] = live_counts[kept_key]

        # V domain: the distinct lines of the live kept keys' rows; a
        # key's compact row is its line's rank * rpl + its place in the
        # line, past ul_cap the sentinel row uvr_cap, which reads zero
        vrow_key = uniq % cfg.vb
        live_key = kept_key & (live_counts > 0)
        lines = np.unique(vrow_key[live_key] // rpl)
        rank_key = np.searchsorted(lines, vrow_key // rpl)
        v_key = live_key & (rank_key < ul_cap)
        dropped += int(live_counts[live_key & ~v_key].sum())
        vslot_key = np.where(v_key, rank_key * rpl + vrow_key % rpl,
                             uvr_cap)
        # padding lines lie past the table's end, distinct and rising:
        # the gather reads zero there and the scatter drops them
        vlines = (cfg.vb // rpl + np.arange(ul_cap)).astype(np.int32)
        vlines[:min(len(lines), ul_cap)] = lines[:ul_cap]
        nk = min(len(uniq), uk_cap)
        key_slot = np.full(uk_cap, uw_cap, np.int32)
        key_slot[:nk] = np.minimum(ts_w.slot_of_uniq[:nk], uw_cap)
        key_vslot = np.full(uk_cap, uvr_cap, np.int32)
        key_vslot[:nk] = vslot_key[:nk]

        # row-major padded view (rows x nnz_per_row) of the live
        # nonzeros over the KEY domain (ck.build_rm): the forward's xw
        # and xv / x2 sums are row gathers from U (see _build_fm). Key
        # uk_cap is the appended zero row.
        W = cfg.nnz_per_row
        rm_key, rm_wval, over = ck.build_rm(
            seg, key_nz, val, cfg.minibatch, W, uk_cap)
        rm_dropped = 0
        if len(over):
            # a row's nonzeros past nnz_per_row are dropped from EVERY
            # layout (the rm forward, the w push, the V push) so that
            # pull and push agree about which nonzeros exist
            rm_dropped = int(np.count_nonzero(val[over]))
            val = val.copy()
            val[over] = 0.0
        pad = (self._rows - cfg.minibatch) * W
        rm_key = np.concatenate([rm_key, np.full(pad, uk_cap, np.int32)])
        rm_wval = np.concatenate([rm_wval, np.zeros(pad, np.float32)])
        # position-major: all rows' first nonzero, then all rows' second
        # ... so that the step sums a row's nonzeros one position at a
        # time into a [rows, S] accumulator and never holds [nnz, S]
        rm_key, rm_wval = (np.ascontiguousarray(
            a.reshape(self._rows, W).T).reshape(-1)
            for a in (rm_key, rm_wval))

        _PACK_BATCHES.inc()
        _PACK_NNZ.inc(int(live.sum()))
        _V_ROWS.inc(int(np.count_nonzero(v_key)))
        if dropped or rm_dropped:
            # two causes with two remedies: the capacities sized off the
            # first batch ran out (its key diversity was too low), or a
            # row carried more than nnz_per_row nonzeros (the rm forward
            # caps xw too, not just the embeddings)
            _PACK_DROPPED.inc(dropped + rm_dropped)
            _log.warning(
                "fm compaction overflow: dropped %d nonzeros to the "
                "capacities %s (w slots, keys, V lines) and %d to the "
                "nnz_per_row row cap (%d)", dropped, self._fm_caps,
                rm_dropped, W)
        head = (ts_w.uniq, ts_w.tmap_u)
        tail = (vlines, key_slot, key_vslot, rm_key, rm_wval)
        if not train:
            # eval/predict never scatter: the sorted COO streams (and
            # their radix sorts) are a train-only cost
            return head + tail
        wcoo = ck.pack_sorted_coo(slot_nz, seg, val, uw_cap,
                                  capacity=cfg.row_capacity)
        # tile_gather of w and of cnt, the fused update; the push
        linmod._count_chunks(ts_w.uniq, cfg.num_buckets, ck.BLK_U, 3)
        linmod._count_chunks(wcoo.val, 0, ck.BLK, 1)
        # the V stream: the same nonzeros sorted by their key's rank,
        # in the push kernel's geometry. The push sums by key, so that
        # the step can mask a key's sum by its admission before it adds
        # the keys of a row up.
        at = np.flatnonzero(val != 0)
        vcoo = ck.pack_sorted_coo(
            key_nz[at], seg[at], val[at], uk_cap,
            capacity=cfg.row_capacity, tile=ck.TILE_HI, blk=ck.FM_BLK)
        return (head + (ts_w.first_u, ts_w.last_u, wcnts, wcoo.idx,
                        wcoo.seg, wcoo.val, wcoo.tmap, wcoo.first)
                + (vcoo.idx, vcoo.seg, vcoo.val, vcoo.tmap, vcoo.first)
                + tail)

    def _build_fm(self, uw_cap: int, uk_cap: int, ul_cap: int) -> None:
        cfg = self.cfg
        S, rows = self._stride, self._rows
        uvr_cap = ul_cap * (ck.LANES // S)
        dt = mbl.kernel_dtype(cfg)
        # wire dtype for the XLA gather operands (U, xvd): dt resolves
        # to None in bf16 mode (the kernels pick bf16 internally), but
        # astype(None) is a float32 no-op — so name the gather dtype
        # explicitly. Half-width rows halve the forward/backward gather
        # bytes; sums still accumulate in f32 (bf16 mode is the
        # documented throughput opt-in; f32 mode stays exact).
        wire = dt if dt is not None else (
            jnp.float32 if ck._use_interpret() else jnp.bfloat16)

        def pull(state, vstate, uniq_w, wtm, vlines):
            # counts are whole numbers far over bf16's 256: their gather
            # is f32 whatever the kernel dtype (exact up to 2^24)
            wc = ck.tile_gather(state["w"].reshape(-1, ck.LANES),
                                uniq_w, wtm, dtype=dt)
            cc = ck.tile_gather(state["cnt"].reshape(-1, ck.LANES),
                                uniq_w, wtm, dtype=jnp.float32)
            return wc, cc, row_gather(vstate["V"], vlines)

        def forward_rm(wc, cnt_c, Vl, key_slot, key_vslot, rm_key,
                       rm_wval):
            # row-major forward over the table of the batch's distinct
            # keys, U[k] = [V row of key k, zero unless admitted | w[k]]:
            # one XLA row gather a nonzero position yields xw AND xv/x2
            # together. Admission masks a key's row of U, so two keys
            # that share a V row are admitted each on its own count.
            # Rows move at the kernel dtype (half the bytes in bf16
            # mode); products and sums accumulate in f32.
            admit = (cnt_c >= cfg.threshold).astype(jnp.float32)
            zero1 = jnp.zeros((1,), jnp.float32)
            w_key = jnp.take(jnp.concatenate([wc, zero1]), key_slot)
            adm_key = jnp.take(jnp.concatenate([admit, zero1]), key_slot)
            # each key's V row in f32: the push scales it by the key's
            # sum of b, the forward takes it at the wire dtype
            Vk = jnp.take(jnp.concatenate(
                [Vl.reshape(uvr_cap, S), jnp.zeros((1, S), jnp.float32)],
                axis=0), key_vslot, axis=0)
            Uz = jnp.concatenate([
                jnp.concatenate([Vk.astype(wire)
                                 * adm_key.astype(wire)[:, None],
                                 w_key.astype(wire)[:, None]], axis=1),
                jnp.zeros((1, S + 1), wire)], axis=0)   # [uk_cap+1, S+1]

            def position(acc, kv):
                # every row's nonzero at one position of the layout
                xw, xv, x2 = acc
                u = jnp.take(Uz, kv[0], axis=0).astype(jnp.float32)
                p = kv[1][:, None] * u[:, :S]
                return (xw + kv[1] * u[:, S], xv + p, x2 + p * p), None

            zero = jnp.zeros((rows, S), jnp.float32)
            (xw, xv, x2), _ = jax.lax.scan(
                position, (jnp.zeros((rows,), jnp.float32), zero, zero),
                (rm_key.reshape(-1, rows), rm_wval.reshape(-1, rows)))
            margin = xw + 0.5 * jnp.sum(xv * xv - x2, axis=-1)
            return xw, xv, margin, adm_key, Vk

        def train_fm(state, vstate, uniq_w, wtm, wfi, wla, wcnts,
                     widx, wseg, wval, wtmap, wfirst,
                     vidx, vseg, vval, vtmap, vfirst,
                     vlines, key_slot, key_vslot, rm_key, rm_wval,
                     label, mask, rngkey):
            wc, cc, Vl = pull(state, vstate, uniq_w, wtm, vlines)
            # admission sees this batch's own counts (module docstring)
            xw, xv, margin, adm_key, Vk = forward_rm(
                wc, cc + wcnts, Vl, key_slot, key_vslot, rm_key, rm_wval)
            obj, d = linmod._loss_dual(cfg.loss, label, margin)
            d = d * mask

            # w: FTRL at the key's storage — scatter + handle update run
            # inside the fused kernel over touched tiles, in place
            gw = ck.coo_spmv_t(d, widx, wseg, wval, wtmap, wfirst,
                               uw_cap, dtype=dt)
            # cnt rides the fused update's touched-tile walk as an
            # additive table (an XLA element scatter into the bucket
            # table costs ~4 ms at the Criteo shape; sentinel slots
            # carry all-zero one-hot rows and scatter nothing)
            new_state, new_w = scatter_update(
                "ftrl", state, gw, uniq_w, wtm, wfi, wla,
                lr_eta=cfg.lr_eta, lr_beta=cfg.lr_beta,
                lambda_l1=cfg.lambda_l1, lambda_l2=cfg.lambda_l2,
                fixed_bytes=cfg.fixed_bytes, dtype=dt,
                add_table="cnt", add_values=wcnts)

            # V: dV_r = sum over the admitted nonzeros of row r of
            # c*(xv_i - val*V_r), c = d_i*val. The push sums by KEY (the
            # xv and d factors ride one row gather from the [rows, S+1]
            # row layout, at the wire dtype; the kernel forms the
            # products and scatters them): a key's sum is then masked by
            # the key's admission, one multiply a key where a lookup a
            # nonzero cost 42 ms a step (PERF.md), and the keys of a row
            # are added up (a row has one key but for collisions).
            gK, nnzK = ck.fm_push_contrib(Vk, xv, d, vseg, vval, vidx,
                                          vtmap, vfirst, dtype=dt,
                                          wire=wire)
            by_row = jnp.zeros((uvr_cap + 1, S + 1), jnp.float32).at[
                key_vslot].add(jnp.concatenate(
                    [gK, (nnzK > 0)[:, None]], axis=1)
                    * adm_key[:, None])[:uvr_cap]
            gV, touched = by_row[:, :S], by_row[:, S]
            if cfg.grad_normalization:
                gV = gV / jnp.maximum(jnp.sum(mask), 1.0)
            if cfg.grad_clipping > 0:
                gV = jnp.clip(gV, -cfg.grad_clipping, cfg.grad_clipping)
            if cfg.dropout > 0:
                keep = jax.random.bernoulli(rngkey, 1.0 - cfg.dropout,
                                            gV.shape)
                gV = gV * keep
            gV = quantize_push(gV, cfg.fixed_bytes)
            # AdaGrad at the rows' storage, by line: a touched row is
            # updated over its whole window (its spare lanes hold zero
            # gradient and zero V, and stay zero)
            Vn, nVn = v_update(
                vstate["V"], vstate["nV"], Vl,
                gV.reshape(ul_cap, ck.LANES),
                jnp.broadcast_to(touched[:, None], (uvr_cap, S)
                                 ).reshape(ul_cap, ck.LANES),
                vlines, V_lr_eta=cfg.V_lr_eta, V_lr_beta=cfg.V_lr_beta,
                lambda_V=cfg.lambda_V)
            new_vstate = dict(vstate)
            new_vstate["V"] = Vn
            new_vstate["nV"] = nVn

            prog = linmod._progress(obj, margin, label, mask, new_w)
            obj_w, _ = linmod._loss_dual(cfg.loss, label, xw)
            prog["objv_w"] = jnp.sum(obj_w * mask)
            # for the step's two counters: whole numbers, summed as such
            prog.update(_halves("live_nnz",
                                jnp.sum(nnzK.astype(jnp.int32))))
            prog.update(_halves("admitted_nnz",
                                jnp.sum((nnzK * adm_key).astype(jnp.int32))))
            return new_state, new_vstate, mbl.pack_progress(
                prog, FM_TRAIN_KEYS)

        @jax.jit
        def fwd_fm(state, vstate, uniq_w, wtm, vlines, key_slot,
                   key_vslot, rm_key, rm_wval, label, mask):
            # eval/predict never scatter: only the compact gathers and
            # the rm channels ride along (the COO streams are a train-
            # only cost — _pack_fm skips packing them when train=False)
            wc, cc, Vl = pull(state, vstate, uniq_w, wtm, vlines)
            margin = forward_rm(wc, cc, Vl, key_slot, key_vslot, rm_key,
                                rm_wval)[2]
            obj, _ = linmod._loss_dual(cfg.loss, label, margin)
            return margin, mbl.pack_progress(
                linmod._progress(obj, margin, label, mask),
                mbl.EVAL_KEYS)

        self._fm_steps = (_keyed(train_fm), fwd_fm)

    # -- global-mesh SPMD protocol (apps/_runner._global_train) ------------
    def global_step_protocol(self):
        """(train_fn, eval_fn) over (seg, idx, val, label, mask) GLOBAL
        arrays; vidx derives on device. Both mutate learner state and
        return the step's progress, read off the device in one read."""
        vb = self.cfg.vb

        def train_fn(args, rng):
            seg, idx, val, label, mask = args
            vidx = idx % np.int32(vb)
            self.store.state, self.vstore.state, prog = self._train_step(
                self.store.state, self.vstore.state, seg, idx, vidx, val,
                label, mask, rng)
            return mbl.read_progress(prog, XLA_TRAIN_KEYS)

        def eval_fn(args):
            seg, idx, val, label, mask = args
            vidx = idx % np.int32(vb)
            _, prog = self._fwd(self.store.state, self.vstore.state,
                                seg, idx, vidx, val, label, mask)
            return mbl.read_progress(prog, mbl.EVAL_KEYS)

        return train_fn, eval_fn

    def global_predict_protocol(self):
        """pred_fn over (seg, idx, val, mask) GLOBAL arrays — see
        LinearLearner.global_predict_protocol."""
        vb, bsh = self.cfg.vb, self._bsh1

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
    _PACK_VERSION = 3

    def pack_cache_token(self, train: bool = True):
        """See LinearLearner.pack_cache_token. Both paths pack with no
        state but the compact path's capacities, which the first batch
        packed fixes: until then the pack is not yet a function of the
        key, and the first cold part goes uncached. Admission is the
        step's, so a train pack replays like an eval pack."""
        cfg = self.cfg
        base = ("difacto", self._PACK_VERSION, self._use_fm_pallas,
                cfg.minibatch, cfg.nnz_per_row, cfg.num_buckets, cfg.vb,
                cfg.dim)
        if not self._use_fm_pallas:
            return base
        if self._fm_caps is None:
            return None
        return base + (self._fm_caps, self._stride, ck.TILE, ck.BLK,
                       ck.BLK_U, ck.TILE_HI, ck.FM_BLK, ck.LANES)

    def _fm_arrays(self, pk, train: bool) -> tuple:
        """A compact pack on its way to the device, as it is. One this
        learner did not make (the pack cache's disk tier) brings the
        capacities, the lengths of its uniq_w, key_slot and vlines
        (_FM_EVAL's order), and the steps are built for them."""
        assert len(pk) == len(self._FM_TRAIN if train else self._FM_EVAL), (
            "batch was packed for the other step")
        with self._fm_lock:
            if self._fm_caps is None:
                self._fm_caps = (len(pk[0]), len(pk[-4]), len(pk[-5]))
                self._build_fm(*self._fm_caps)
        return pk

    # -- what a harness asks of the learner (benchmark/check.py) -------------
    def tables(self) -> Mapping:
        """Every table by name, each readable by row: (num_buckets,) or
        (v_buckets, dim), however it is stored."""
        return _Tables(self.ckpt_store)

    def batch_kind(self, b) -> str:
        # a staged XLA batch answers "xla_staged", the tuple head it had
        # until PR 50: tests/benchmark/fixtures/difacto-fixture.json
        # (expect_kind), test_benchmark_fetch_reads.py and
        # test_benchmark_vector_rows.py pin it (ROADMAP C15)
        kind = super().batch_kind(b)
        return "xla_staged" if (b[0], kind) == ("staged", "xla") else kind

    def _admitted(self) -> np.ndarray:
        admit = np.asarray(self.store.state["cnt"]) >= self.cfg.threshold
        if self.cfg.l1_shrk:
            admit &= np.asarray(self.store.state["w"]) != 0
        return admit

    def num_admitted(self) -> int:
        return int(self._admitted().sum())

    def v_collision_rate(self) -> float:
        """Fraction of ADMITTED keys whose V bucket (key % v_buckets) is
        shared with another admitted key. The reference stores exact
        per-key embeddings (async_sgd.h:135-209); the fixed-capacity V
        table is a hash kernel, and this is the metric that bounds the
        aliasing it introduces — size v_buckets so this stays small
        (rate ~ n_admitted / v_buckets for a uniform hash; see
        docs/difacto.md)."""
        keys = np.flatnonzero(self._admitted())
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
