"""The batch protocol between `MinibatchSolver` and a minibatch learner,
stated once for `LinearLearner` and `DifactoLearner`.

A batch is a tuple whose head says what it is. `prepare_batch` (host
side, loader threads) makes `(kind, packed, label, mask, size)`, or
`(kind, db, size)` where the padded batch is the packed one;
`stage_batch` moves it to the device as `("staged", kind, args, size,
ids, train)`; `train_batch` / `eval_batch` / `predict_batch` take a
RowBlock or either tuple and reach the kind's step this one way. `kind`
is a key of the learner's `_kinds`: one `_Kind` record a kind of batch,
which every method here looks up by that name.

A learner supplies its tables (`store`), its `_kinds`,
`_choose_kind(db)`, `_batch_rows`, `pack_cache_token`, and, where its
rows have more than one id space, `_id_spaces`.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from wormhole_tpu.data.rowblock import RowBlock, to_device_batch
from wormhole_tpu.obs import trace as _trace
from wormhole_tpu.obs.metrics import REGISTRY
from wormhole_tpu.parallel.mesh import batch_sharding, make_mesh

_log = logging.getLogger(__name__)

# a step's progress read off the device: the steps read, and the
# blocking device-to-host reads that took (read_progress)
_FETCH_STEPS = REGISTRY.counter("step.fetch.steps")
_FETCH_READS = REGISTRY.counter("step.fetch.reads")

#: what linear._progress holds for an eval step and for a train step, in
#: the order a step packs them and read_progress names them again:
#: sorted, as a jitted step returns a dict
EVAL_KEYS = ("acc", "auc", "clk", "logloss", "nex", "objv", "pclk")
TRAIN_KEYS = tuple(sorted(EVAL_KEYS + ("new_w",)))


def pack_progress(p: dict, keys) -> jax.Array:
    """A step's progress scalars as one f32[len(keys)] vector, traced
    inside the jitted step, so that the host reads a step's progress in
    one transfer and not one a scalar."""
    assert set(p) == set(keys), (sorted(p), keys)
    return jnp.stack([jnp.asarray(p[k], jnp.float32) for k in keys])


def read_progress(vec, keys) -> dict:
    """pack_progress's inverse on the host: the one blocking
    device-to-host read of a step, which returns when the device has
    finished the step. A value is the Python float that float() of the
    device scalar was."""
    _FETCH_STEPS.inc()
    _FETCH_READS.inc()
    host = np.asarray(vec)
    assert host.shape == (len(keys),), (host.shape, keys)
    return dict(zip(keys, host.tolist()))


@dataclasses.dataclass(frozen=True)
class _Kind:
    """One kind of batch. The learner's `_choose_kind` picks it; every
    other method looks the record up by the name the batch's tuple
    carries. `state` is the learner's `store.state`: what else a step
    reads and writes, the record's callables thread themselves."""

    pack: Callable     # (db, train) -> packed: host side, loader thread
    args: Callable     # _device_args: what a step takes after the state
    #: (state, *args) -> (state, packed progress); donates state
    train: Callable
    eval: Callable     # (state, *args) -> packed progress
    predict: Callable  # (state, *args) -> margins (`_predict_rows`)
    #: packed -> the unique rows it touches, an array an id space (the
    #: sparse PS push set; reference ZPush of the minibatch's keys,
    #: async_sgd.h:270-287), or None = unknown, which forces a full
    #: delta scan
    touched: Callable
    keys: tuple = TRAIN_KEYS   # what its train step packs


def _device_args(arrays, put, put_rows=None):
    """A kind's `args`: the host arrays `arrays(packed, train)` names go
    to the device through `put`, label and mask after them through
    `put_rows` (`put` where the rows go the same way); predict passes
    neither unless the learner's `_predict_rows` says so."""
    put_rows = put_rows or put

    def args(packed, label=None, mask=None, train=False):
        out = [put(x) for x in arrays(packed, train)]
        if label is not None:
            out += [put_rows(label), put_rows(mask)]
        return tuple(out)
    return args


def _split(b):
    """(kind, packed, label, mask, size) of a prepared batch: the short
    (kind, db, size) form carries its label and mask inside db."""
    if len(b) == 3:
        kind, db, size = b
        return kind, db, db.label, db.row_mask, size
    return b


def kernel_dtype(cfg):
    """MXU compute dtype for the COO kernels. None defers to the kernel
    default (bf16 on TPU, f32 in interpret mode); "auto" keeps f32
    whenever fixed_bytes == 0 so disabling gradient quantization also
    disables the kernels' bf16 rounding (ADVICE r1)."""
    if cfg.kernel_dtype == "f32" or (cfg.kernel_dtype == "auto"
                                     and cfg.fixed_bytes == 0):
        return jnp.float32
    return None


class MinibatchLearner:
    """What `MinibatchSolver` drives, the same for any learner: a batch
    of any form to its kind's step and the step's progress back."""

    #: whether a kind's predict takes the rows' label and mask as its
    #: eval does (a learner with one forward program for both)
    _predict_rows = False

    def __init__(self, cfg, mesh=None):
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_mesh(num_model=1)
        self._bsh1 = batch_sharding(self.mesh, 1)
        #: rows of a padded batch
        self._batch_rows = cfg.minibatch
        self._dropped_rows = 0
        # sparse PS wire hints: unique rows, an array an id space,
        # touched by trained batches since the last collect_touched()
        # drain (runtime/ps_server)
        self.track_touched = False
        self._touched_lock = threading.Lock()
        self._touched: list[Optional[tuple]] = []

    def _id_spaces(self) -> tuple:
        """The tables each id space numbers, in the order a kind's
        `touched` gives its arrays."""
        return (self.store.state,)

    def _trained(self, out: dict) -> dict:
        """A train step's read progress, on its way to the caller."""
        return out

    # -- device batch plumbing ---------------------------------------------
    def prepare_batch(self, blk: RowBlock, train: bool = True):
        """Host-side batch prep (runs in loader threads): pad to the fixed
        device shape, and pack as the kind `_choose_kind` picks asks (the
        Localizer role). Returns an opaque prepared batch accepted by
        train/eval/predict_batch: (kind, packed, label, mask, size), or
        (kind, db, size) where the padded batch is the packed one."""
        cfg = self.cfg
        db = to_device_batch(blk, self._batch_rows, cfg.row_capacity,
                             cfg.num_buckets)
        if db.dropped_rows:
            self._dropped_rows += db.dropped_rows
            _log.warning(
                "minibatch overflow: dropped %d rows (total %d) — raise "
                "nnz_per_row or minibatch capacity",
                db.dropped_rows, self._dropped_rows,
            )
        kind = self._choose_kind(db)
        packed = self._kinds[kind].pack(db, train)
        if packed is db:
            return (kind, db, blk.size)
        return (kind, packed, db.label, db.row_mask, blk.size)

    def _prepared(self, x, train: bool):
        if isinstance(x, RowBlock):
            x = self.prepare_batch(x, train)
        return x

    # -- double-buffered device feed -----------------------------------------
    def stage_batch(self, b, train: bool = True):
        """Move a batch's arrays to the device (a RowBlock is prepared
        first; a staged batch comes back as it is). The solver calls this
        from the loader thread, so the host->device transfer of batch N+1
        overlaps the main thread's step on batch N; train_batch /
        eval_batch call it on whatever they are given, so every batch
        reaches its step this one way. Returns ("staged", kind, args,
        size, ids, train). The `train` flag must match the consuming step
        and the pack (a kind may pack, or ship, what only training
        reads)."""
        b = self._prepared(b, train)
        if b[0] == "staged":
            return b
        kind, packed, label, mask, size = _split(b)
        k = self._kinds[kind]
        # the touched ids need the host arrays; grab them now because
        # after staging only device arrays remain
        ids = k.touched(packed) if (train and self.track_touched) else None
        args = k.args(packed, label, mask, train)
        # what the batch moves to the device (on a mesh a [1, M, P]
        # slice a shard): on the solver's loader.h2d span round this call
        _trace.annotate(bytes=sum(a.nbytes for a in args))
        return ("staged", kind, args, size, ids, train)

    # -- what a harness asks of the learner (benchmark/check.py) -------------
    def tables(self):
        """Every table by name, each readable by row."""
        return self.store.state

    @staticmethod
    def batch_kind(b) -> str:
        """A prepared or staged batch's kind: a key of `_kinds`."""
        return b[1] if b[0] == "staged" else b[0]

    def batch_label(self, b) -> np.ndarray:
        """A prepared or staged batch's labels on the host: the
        minibatch's rows, without the rows a kind pads on."""
        label = b[2][-2] if b[0] == "staged" else _split(b)[2]
        return np.asarray(label)[:self.cfg.minibatch]

    # -- sparse PS wire hints ------------------------------------------------
    def collect_touched(self):
        """Sorted-unique global rows touched since the last call, per
        table, or None if any batch lacked a hint (SyncedStore then
        falls back to a full delta scan for this sync)."""
        with self._touched_lock:
            acc = self._touched
            self._touched = []
        if any(a is None for a in acc):
            return None
        out = {}
        for i, names in enumerate(self._id_spaces()):
            u = (np.unique(np.concatenate([a[i] for a in acc])) if acc
                 else np.empty(0, np.int64))
            out.update({k: u for k in names})
        return out

    # -- the steps -----------------------------------------------------------
    def train_batch(self, blk) -> dict:
        # a step is one launch and one read. Two spans, so that a device
        # profile can tell a late dispatch from a late return out of the
        # blocking read (PERF.md §5: on the chip it is the read the
        # device idles under)
        with _trace.span("step.dispatch", cat="step") as sp:
            _, kind, args, _, ids, st_train = self.stage_batch(blk, True)
            assert st_train, "batch was staged for eval, not train"
            if self.track_touched:
                with self._touched_lock:
                    self._touched.append(ids)
            k = self._kinds[kind]
            self.store.state, prog = k.train(self.store.state, *args)
            sp.set(kind=kind)
        with _trace.span("step.fetch", cat="step"):
            # blocks until the device has finished the step
            return self._trained(read_progress(prog, k.keys))

    def eval_batch(self, blk) -> dict:
        _, kind, args, _, _, st_train = self.stage_batch(blk, False)
        assert not st_train, "batch was staged for train, not eval"
        prog = self._kinds[kind].eval(self.store.state, *args)
        return read_progress(prog, EVAL_KEYS)

    def predict_batch(self, blk) -> np.ndarray:
        kind, packed, label, mask, size = _split(self._prepared(blk, False))
        k = self._kinds[kind]
        rows = (label, mask) if self._predict_rows else ()
        xw = k.predict(self.store.state, *k.args(packed, *rows))
        out = np.asarray(xw)[:size]
        if self.cfg.prob_predict:
            out = 1.0 / (1.0 + np.exp(-out))
        return out

    def nnz(self) -> int:
        return self.store.nnz("w")

    def derived_tables(self) -> dict:
        """Tables that are non-additive pure functions of additive ones,
        for server-side recomputation in the multi-process PS data plane
        (runtime/ps_server.ServerNode._recompute_derived): w where it
        trains by FTRL (async_sgd.h:262-286), the prox of (z, n)."""
        cfg = self.cfg
        if cfg.algo != "ftrl":
            return {}
        return {"w": {"kind": "ftrl_prox", "lr_eta": cfg.lr_eta,
                      "lr_beta": cfg.lr_beta, "lambda_l1": cfg.lambda_l1,
                      "lambda_l2": cfg.lambda_l2}}
