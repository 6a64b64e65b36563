"""A step is one launch and one read (PR 37): the jitted steps of
`LinearLearner` and `DifactoLearner` hand their progress back as one
f32 vector, `read_progress` makes the one blocking device-to-host read,
and `DifactoLearner.train_batch` no longer splits its key on the host.

What the learners return must not have changed by a bit: the reference
in these tests is the same learner built with the packing taken out, so
that its steps return `_progress`'s dict of device scalars and its
`train_batch` reads them one `float(...)` at a time, as the code before
this PR did. Pallas kinds run interpreted, in float32, at tiny sizes."""

import jax
import numpy as np
import pytest

from wormhole_tpu.data.rowblock import RowBlock
from wormhole_tpu.models import difacto as df
from wormhole_tpu.models import linear as lin
from wormhole_tpu.models import minibatch_learner as mbl
from wormhole_tpu.obs.metrics import REGISTRY
from wormhole_tpu.ops import coo_kernels as ck
from wormhole_tpu.parallel.mesh import make_mesh

ROWS, NNZ = 256, 8
# sorted, as the parent's jitted steps returned their dicts
EVAL_KEYS = ["acc", "auc", "clk", "logloss", "nex", "objv", "pclk"]
TRAIN_KEYS = sorted(EVAL_KEYS + ["new_w"])
FM_KEYS = sorted(TRAIN_KEYS + ["objv_w"])

# case -> (learner class, its configuration beyond the sizes, mesh, the
# kind its batches must be, the keys train_batch returned at the parent)
_LINEAR = dict(algo="ftrl", lr_eta=0.5, lambda_l1=0.05, kernel_dtype="f32")
_FM = dict(dim=4, threshold=2, lr_eta=0.3, kernel_dtype="f32",
           v_buckets=ck.TILE)
_CASES = {
    "linear-xla": (lin, dict(_LINEAR, num_buckets=1 << 12, kernel="xla"),
                   (1, 1), "xla", TRAIN_KEYS),
    "linear-coo": (lin, dict(_LINEAR, num_buckets=2 * ck.TILE,
                             kernel="pallas", compact_cap=0),
                   (1, 1), "coo", TRAIN_KEYS),
    "linear-tcoo": (lin, dict(_LINEAR, num_buckets=8 * ck.TILE,
                              kernel="pallas", compact_cap=ck.TILE),
                    (1, 1), "tcoo", TRAIN_KEYS),
    "linear-mcoo": (lin, dict(_LINEAR, num_buckets=2 * ck.TILE,
                              kernel="pallas"),
                    (2, 2), "mcoo", TRAIN_KEYS),
    "difacto-xla": (df, dict(_FM, num_buckets=2 * ck.TILE, kernel="xla"),
                    (1, 1), "xla_staged", FM_KEYS),
    "difacto-fm": (df, dict(_FM, num_buckets=2 * ck.TILE, kernel="pallas"),
                   (1, 1), "fm", FM_KEYS),
}


def _blocks(n, rows=ROWS, nnz=NNZ, keys=3000, seed=37):
    """n batches over a few thousand keys, a third of the entries on 40
    hot ones, so that later steps pull trained weights and the hot keys
    pass an admission threshold."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        index = np.where(rng.random(rows * nnz) < 0.33,
                         rng.integers(0, 40, rows * nnz) * 1009,
                         rng.integers(0, keys, rows * nnz))
        out.append(RowBlock(
            label=(rng.random(rows) < 0.4).astype(np.float32),
            offset=np.arange(rows + 1, dtype=np.int64) * nnz,
            index=index.astype(np.uint64),
            value=(0.25 + rng.random(rows * nnz)).astype(np.float32)))
    return out


def _learner(case, **more):
    mod, kw, mesh, _, _ = _CASES[case]
    kw = dict(kw, minibatch=ROWS, nnz_per_row=NNZ, **more)
    if mod is lin:
        return lin.LinearLearner(lin.LinearConfig(**kw), make_mesh(*mesh))
    return df.DifactoLearner(df.DifactoConfig(**kw), make_mesh(*mesh))


def _fetch_counters():
    c = REGISTRY.snapshot()["counters"]
    return c.get("step.fetch.steps", 0), c.get("step.fetch.reads", 0)


def _unpacked(monkeypatch):
    """Take the packing out, for learners built from here on: their steps
    return the dict `_progress` made, and it is read a scalar at a time
    (`tree_map(float, prog)` was the parent's line)."""
    monkeypatch.setattr(mbl, "pack_progress",
                        lambda p, keys: {k: p[k] for k in keys})
    monkeypatch.setattr(
        mbl, "read_progress",
        lambda prog, keys: jax.tree_util.tree_map(float, prog))


@pytest.mark.parametrize("case", list(_CASES))
def test_one_read_returns_what_a_read_a_scalar_returned(case, monkeypatch):
    """Three train steps and an eval step of every kind a CPU run can
    build: the dicts `train_batch` and `eval_batch` return hold the keys
    they held at the parent, in its order, and bit for bit the values a
    per-scalar read of the same step's `_progress` gives; and each step
    is counted as one step and one read."""
    _, _, _, kind, train_keys = _CASES[case]
    blocks = _blocks(4)

    def run(lrn):
        out = []
        for i, blk in enumerate(blocks):
            b = lrn.prepare_batch(blk, train=i < 3)
            assert lrn.batch_kind(lrn.stage_batch(b, train=i < 3)) == kind
            before = _fetch_counters()
            out.append(lrn.train_batch(b) if i < 3 else lrn.eval_batch(b))
            out.append(tuple(b - a for a, b in zip(before,
                                                   _fetch_counters())))
        return out

    got = run(_learner(case))
    _unpacked(monkeypatch)
    want = run(_learner(case))
    for i in range(4):
        g, w = got[2 * i], want[2 * i]
        assert list(g) == list(w) == (train_keys if i < 3 else EVAL_KEYS)
        assert all(type(v) is float for v in g.values())
        assert g == w, (i, g, w)          # floats: equal is bit-equal
        assert g["nex"] == ROWS
        assert got[2 * i + 1] == (1, 1)   # one step, one read
        assert want[2 * i + 1] == (0, 0)  # the reference counts nothing
    # later steps pulled trained weights: the values are not trivial
    assert got[0]["pclk"] != got[4]["pclk"]


def test_pack_and_read_are_inverse_and_hold_the_order():
    """`pack_progress` orders by `keys` whatever the dict's own order,
    refuses a dict that holds another set, and `read_progress` hands
    back Python floats of the float32 values."""
    import jax.numpy as jnp

    p = {"b": jnp.float32(0.1), "a": jnp.float32(3.0)}
    vec = mbl.pack_progress(p, ("a", "b"))
    assert vec.shape == (2,) and vec.dtype == jnp.float32
    out = mbl.read_progress(vec, ("a", "b"))
    assert out == {"a": 3.0, "b": float(np.float32(0.1))}
    assert list(out) == ["a", "b"]
    with pytest.raises(AssertionError):
        mbl.pack_progress(p, ("a",))
    with pytest.raises(AssertionError):
        mbl.read_progress(vec, ("a", "b", "c"))


def test_fm_step_counts_arrive_exact_past_two_to_the_sixteen():
    """The compact FM step's two counts ride the progress vector as
    16-bit halves, so float32 holds them exactly: a batch of 73,728 live
    nonzeros bumps `difacto.step.live_nnz` by just that, and
    `difacto.step.admitted_nnz` by the nonzeros of the buckets this
    batch alone brought to the threshold."""
    rows, nnz, nb, threshold = 8192, 9, 2 * ck.TILE, 8
    cfg = df.DifactoConfig(minibatch=rows, nnz_per_row=nnz, num_buckets=nb,
                           v_buckets=ck.TILE, dim=4, threshold=threshold,
                           kernel="pallas", kernel_dtype="f32")
    fm = df.DifactoLearner(cfg, make_mesh(1, 1))
    (blk,) = _blocks(1, rows=rows, nnz=nnz, keys=4000, seed=3)
    _, per_bucket = np.unique(blk.index.astype(np.int64) % nb,
                              return_counts=True)
    live = rows * nnz
    admitted = int(per_bucket[per_bucket >= threshold].sum())
    assert live > 1 << 16 and admitted > 1 << 16 and admitted < live

    def counters():
        c = REGISTRY.snapshot()["counters"]
        return (c.get("difacto.step.live_nnz", 0),
                c.get("difacto.step.admitted_nnz", 0))

    before = counters()
    out = fm.train_batch(blk)
    assert list(out) == FM_KEYS and out["nex"] == rows
    assert tuple(b - a for a, b in zip(before, counters())) == (
        live, admitted)


def test_keyed_step_splits_the_key_as_the_host_did():
    """`_keyed` hands the step the second half of the split and returns
    the first as the next key: the chain `train_batch` made on the host
    with `self._rng, sub = jax.random.split(self._rng)`."""
    step = df._keyed(lambda state, vstate, x, sub: (state, vstate, sub))
    key = host = jax.random.PRNGKey(5 + 17)
    for _ in range(3):
        host, sub = jax.random.split(host)
        _, _, got_sub, key = step({}, {}, np.float32(0), key)
        assert np.array_equal(got_sub, sub) and np.array_equal(key, host)


@pytest.mark.parametrize("case", ["difacto-xla", "difacto-fm"])
def test_dropout_draws_the_masks_of_the_parents_key_chain(case):
    """With `dropout` 0.3 three train steps draw their masks from the
    sub-keys of the chain `split(PRNGKey(seed + 17))`, the parent's
    sequence, the split now traced inside the step: the learner's key
    ends where the host's chain does, the dropout engages (the tables
    part from a run without it) and, for the XLA step, the tables equal
    those of the parent's own loop: the jitted step given each sub-key
    of a chain split on the host."""
    seed, blocks = 5, _blocks(3)

    def tables(lrn):
        return {k: np.asarray(v) for k, v in lrn.vstore.state.items()}

    lrn = _learner(case, dropout=0.3)
    plain = _learner(case)
    assert np.array_equal(lrn._rng, jax.random.PRNGKey(0 + 17))
    lrn._rng = jax.random.PRNGKey(seed + 17)
    for blk in blocks:
        lrn.train_batch(blk)
        plain.train_batch(blk)
    host = jax.random.PRNGKey(seed + 17)
    subs = []
    for _ in blocks:
        host, sub = jax.random.split(host)
        subs.append(sub)
    assert np.array_equal(lrn._rng, host)
    got = tables(lrn)
    assert not np.array_equal(got["V"], tables(plain)["V"])
    if case == "difacto-xla":
        ref = _learner(case, dropout=0.3)
        for blk, sub in zip(blocks, subs):
            args = ref.stage_batch(blk, True)[2]
            ref.store.state, ref.vstore.state, _ = ref._train_step(
                ref.store.state, ref.vstore.state, *args, sub)
        want = tables(ref)
        for k in ("V", "nV"):
            assert np.array_equal(got[k], want[k]), k
