"""The compact FM step at widths and batch sizes that divide nothing:
against the benchmark's plain reference, against the XLA step, and the
purity of its pack.

Everything here runs the Pallas kernels interpreted, in float32, at tiny
table sizes; the real sizes are the chip's (benchmark/, PERF.md)."""

import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from wormhole_tpu.data import pack_cache as pc
from wormhole_tpu.data.rowblock import RowBlock
from wormhole_tpu.models.difacto import (DifactoConfig, DifactoLearner,
                                         row_stride)
from wormhole_tpu.obs import trace as obs_trace
from wormhole_tpu.ops import coo_kernels as ck
from wormhole_tpu.parallel.mesh import make_mesh
from wormhole_tpu.solver.minibatch_solver import MinibatchSolver

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference import fm_ftrl_adagrad_criteo as ref  # noqa: E402

NNZ, NB, VB, STEPS, THRESHOLD = 6, 2 * ck.TILE, 4096, 5, 4
HYPER = dict(lr_eta=0.05, lr_beta=1.0, lambda_l1=0.02, lambda_l2=0.0,
             lambda_V=0.01, V_lr_eta=0.05, V_lr_beta=1.0, V_init_scale=0.05,
             threshold=THRESHOLD)


def _batches(rows: int, seed: int, steps: int = STEPS):
    """(keys (rows, NNZ) uint64, labels) a step: three hot fields of a few
    values, whose keys pass the threshold in the first step, and three
    of some hundred, whose keys pass it one after the other over the
    run: admission changes under every step."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        hot = rng.integers(0, 7, size=(rows, 3)) + np.arange(3) * 1000
        cold = rng.integers(0, 90 * rows // 200, size=(rows, 3)) \
            + (3 + np.arange(3)) * 1000
        keys = (np.concatenate([hot, cold], axis=1).astype(np.uint64)
                * np.uint64(2654435761) + np.uint64(NB * 7 + 12345))
        out.append((keys, (rng.random(rows) < 0.4).astype(np.float32)))
    return out


def _block(keys, label) -> RowBlock:
    rows, nnz = keys.shape
    return RowBlock(label=label, offset=np.arange(rows + 1, dtype=np.int64)
                    * nnz, index=keys.reshape(-1).copy())


def _learner(dim: int, rows: int, kernel: str = "pallas", seed: int = 0,
             **kw) -> DifactoLearner:
    cfg = DifactoConfig(minibatch=rows, num_buckets=NB, v_buckets=VB,
                        nnz_per_row=NNZ, dim=dim, kernel=kernel,
                        kernel_dtype="f32", **{**HYPER, **kw})
    return DifactoLearner(cfg, make_mesh(1, 1), seed=seed)


def _random_start(lrn: DifactoLearner, seed: int) -> None:
    """Seeded random tables in place of the zeros: every leaf's update is
    then a function of what was there, and a wrong row would show."""
    rng = np.random.default_rng(seed)
    t = lrn.ckpt_store.to_numpy()
    n = rng.random(NB).astype(np.float32)
    z = rng.normal(scale=0.05, size=NB).astype(np.float32)
    c = lrn.cfg
    w = (-np.sign(z) * np.maximum(np.abs(z) - c.lambda_l1, 0)
         / ((c.lr_beta + np.sqrt(n)) / c.lr_eta + c.lambda_l2))
    lrn.ckpt_store.from_numpy({
        "z": z, "n": n, "w": w.astype(np.float32), "cnt": t["cnt"],
        "V": t["V"], "nV": rng.random(t["nV"].shape).astype(np.float32)})


@pytest.mark.parametrize("rows", [256, 200])
@pytest.mark.parametrize("dim", [5, 8, 50])
def test_compact_step_matches_the_plain_reference(dim, rows):
    """Five steps of the compact path against benchmark/reference/
    fm_ftrl_adagrad_criteo.py from the same seeded random tables, the
    threshold crossed by new keys in every step. A width that is no
    power of two is padded to its stride in the table, a batch that is
    no multiple of 128 to the kernels' rows under the mask: neither
    shows in a loss, a count or a table, and a padded lane stays zero."""
    lrn = _learner(dim, rows, seed=3)
    assert lrn._use_fm_pallas and "path=pallas" in lrn.placement
    assert lrn._stride == row_stride(dim) and lrn._rows == 256
    _random_start(lrn, seed=dim * 1000 + rows)
    batches = _batches(rows, seed=dim + rows)
    sizes = {"bucket": NB, "vrow": VB}
    per = [ref.space_ids(k, sizes) for k, _ in batches]
    ids = {s: np.unique(np.concatenate([p[s].reshape(-1) for p in per]))
           for s in ref.SPACES}
    tabs = lrn.tables()
    start = {"ids": ids, "tables": {
        k: np.asarray(tabs[k])[ids[d["space"]]]
        for k, d in ref.TABLES.items()}}
    assert start["tables"]["V"].shape == (len(ids["vrow"]), dim)
    want = ref.run_steps(batches, sizes, dict(HYPER, dim=dim),
                         {"tables": "f32"}, start=start)

    admitted = []
    for (keys, label), objv in zip(batches, want["objv"]):
        blk = _block(keys, label)
        assert lrn.batch_kind(lrn.prepare_batch(blk)) == "fm"
        out = lrn.train_batch(blk)
        assert out["nex"] == rows
        # float32 sums in another order than the reference's float64
        assert out["objv"] == pytest.approx(objv, rel=2e-5)
        admitted.append(lrn.num_admitted())
    # the threshold was crossed mid-run, by further keys in every step
    assert admitted[0] > 0 and all(np.diff(admitted) > 0), admitted
    tabs = lrn.tables()
    for k, d in ref.TABLES.items():
        got = np.asarray(tabs[k])[ids[d["space"]]]
        # counts are exact; the rest differs by summation order only
        # (a gradient summed in float32 over a bucket's rows: ~1e-6 of
        # it, and through sqrt(n) into the rate)
        np.testing.assert_allclose(
            got, want["states"][-1][k], rtol=0 if k == "cnt" else 2e-4,
            atol=0 if k == "cnt" else 2e-6, err_msg=k)
    moved = np.any(want["states"][-1]["V"] != start["tables"]["V"], axis=1)
    assert 0 < moved.sum() < len(moved)   # admitted rows moved, others not
    for k in ("V", "nV"):
        stored = np.asarray(lrn.vstore.state[k]).reshape(-1, lrn._stride)
        assert stored.shape[0] == VB
        assert not stored[:, dim:].any(), f"{k}: a padded lane is not zero"
        np.testing.assert_array_equal(stored[:, :dim],
                                      np.asarray(tabs[k]))


def test_fm_compact_matches_xla_with_three_packers():
    """The compact step and the XLA step state one semantics: admission
    by the device's count table, with the batch's own counts, inside the
    step. Five batches are packed by three threads, latest first, and
    trained in order: the tables and losses equal the XLA step's, which
    packs nothing. (With admission decided at the pack, from a host
    mirror in pack order, they could not.)"""
    rows, dim = 200, 8
    batches = _batches(rows, seed=99)
    xla, fm = _learner(dim, rows, "xla"), _learner(dim, rows, "pallas")
    assert not xla._use_fm_pallas and fm._use_fm_pallas
    # one start: the XLA learner's draw, lane-packed into the other
    fm.ckpt_store.from_numpy(xla.ckpt_store.to_numpy())
    with ThreadPoolExecutor(3) as pool:
        packed = list(pool.map(
            lambda b: fm.prepare_batch(_block(*b)), batches[::-1]))[::-1]
    for blk, pk in zip(batches, packed):
        a, b = xla.train_batch(_block(*blk)), fm.train_batch(pk)
        # the same float32 mathematics in another order of summation
        assert a["nex"] == b["nex"] == rows
        assert b["objv"] == pytest.approx(a["objv"], rel=2e-5)
    assert xla.num_admitted() == fm.num_admitted() > 0
    s_x, s_p = xla.ckpt_store.to_numpy(), fm.ckpt_store.to_numpy()
    for k in ("w", "z", "n", "cnt", "V", "nV"):
        np.testing.assert_allclose(
            s_x[k], s_p[k], rtol=0 if k == "cnt" else 2e-4,
            atol=0 if k == "cnt" else 2e-6, err_msg=f"table {k} diverged")


def _same_bytes(a, b) -> bool:
    if isinstance(a, np.ndarray):
        return (a.dtype == b.dtype and a.shape == b.shape
                and a.tobytes() == b.tobytes())
    if isinstance(a, tuple):
        return len(a) == len(b) and all(map(_same_bytes, a, b))
    return a == b


def test_train_pack_is_pure_and_cacheable(tmp_path):
    """A train pack is a function of the batch and the capacities alone:
    packing a batch twice, and again once other batches have trained and
    moved the counts, gives equal bytes; it survives the pack cache's
    disk tier; and the learner gives the cache a key for it."""
    rows = 200
    lrn = _learner(50, rows)
    assert lrn.pack_cache_token(train=True) is None   # no capacities yet
    batches = _batches(rows, seed=5)
    first = lrn.prepare_batch(_block(*batches[0]))
    token = lrn.pack_cache_token(train=True)
    assert token is not None and token[1] == lrn._PACK_VERSION == 3
    assert lrn._fm_caps in token and lrn.pack_cache_token(False) == token
    assert _same_bytes(first, lrn.prepare_batch(_block(*batches[0])))
    for b in batches[1:]:
        lrn.train_batch(_block(*b))
    assert lrn.num_admitted() > 0
    again = lrn.prepare_batch(_block(*batches[0]))
    assert _same_bytes(first, again)
    assert all(isinstance(a, np.ndarray) for a in first[1])  # host arrays
    cache = pc.PackCache(mem_bytes=1 << 20, disk_dir=str(tmp_path))
    assert cache.put("k", first)
    cache.clear_memory()
    assert _same_bytes(first, cache.get("k")) and cache.disk_hits == 1
    # a replayed pack trains like a fresh one
    twin = _learner(50, rows)
    twin.ckpt_store.from_numpy(lrn.ckpt_store.to_numpy())
    a, b = lrn.train_batch(again), twin.train_batch(cache.get("k"))
    assert a == b


def test_pack_cache_round_trip_with_three_loaders(tmp_path, monkeypatch):
    """Three passes through the solver with three loader threads and the
    pack cache on: the last pass's train batches come from the cache,
    every row is counted once a pass, and the count table holds three
    passes' occurrences."""
    from wormhole_tpu.obs.metrics import REGISTRY

    rows = 200
    data = tmp_path / "data"
    data.mkdir()
    batches = _batches(rows, seed=8, steps=6)
    for p in range(3):
        with open(data / f"train-{p}.libsvm", "w") as fh:
            for keys, label in batches[2 * p:2 * p + 2]:
                for r in range(rows):
                    fh.write(f"{int(label[r])} " + " ".join(
                        f"{int(k) % (1 << 40)}:1" for k in keys[r]) + "\n")
    monkeypatch.setenv("WH_PACK_CACHE", "1")
    monkeypatch.delenv("WH_PACK_CACHE_DIR", raising=False)
    cfg = DifactoConfig(
        train_data=str(data / r"train-\d\.libsvm"), max_data_pass=3,
        num_parts_per_file=1, minibatch=rows, num_buckets=NB, v_buckets=VB,
        nnz_per_row=NNZ, dim=50, kernel="pallas", kernel_dtype="f32",
        **HYPER)
    lrn = DifactoLearner(cfg, make_mesh(1, 1))
    sol = MinibatchSolver(lrn, cfg, num_loaders=3, verbose=False)
    before = REGISTRY.snapshot()["counters"]
    packed0 = before.get("difacto.pack.batches", 0)
    res = sol.run()
    packed = REGISTRY.snapshot()["counters"]["difacto.pack.batches"] - packed0
    stats = sol.pack_cache.stats()
    # pass 0 starts with no capacities, so with no key: it goes uncached.
    # Pass 1 packs once more and fills the cache, pass 2 is replayed
    assert stats["hits"] >= 3 and packed == 6 + 6, (stats, packed)
    assert res["train"].value("nex") == 6 * rows
    assert float(np.asarray(lrn.store.state["cnt"]).sum()) == \
        3 * 6 * rows * NNZ
    # the counters are the process's: another test's overflow stays in them
    assert REGISTRY.snapshot()["counters"].get(
        "difacto.pack.dropped_nnz", 0) == before.get(
        "difacto.pack.dropped_nnz", 0)


# ---------------------------------------------- what the linear path keeps
@pytest.mark.parametrize("body", ["native", "numpy"])
def test_tcoo_pack_is_bit_for_bit_the_one_before_this_pr(body, monkeypatch):
    """The vector-row work shares `assign_tile_slots` and
    `pack_sorted_coo` with the linear learner's `tcoo` pack. A seeded
    batch (a hot bucket, padding entries) packs to the bytes it packed to
    at the parent commit (PR 30; the digest was taken again at PR 50's
    parent over the arrays that remain, without the row-major companion
    that pack made until then), by either body of `pack_tile_coo`."""
    import hashlib

    from wormhole_tpu import native

    if body == "numpy":
        monkeypatch.setattr(native, "pack_tile_coo", lambda *a, **k: None)
    elif not native.available():
        pytest.skip("the native core is not built here")
    rng = np.random.default_rng(31)
    rows, nnz, nb = 512, 39, 8 * ck.TILE
    idx = rng.integers(0, nb, size=rows * nnz).astype(np.int32)
    idx[::7] = idx[0]
    seg = np.repeat(np.arange(rows, dtype=np.int32), nnz)
    val = np.ones(rows * nnz, np.float32)
    val[::11] = 0.0
    tc = ck.pack_tile_coo(idx, seg, val, nb, 2 * ck.TILE,
                          capacity=rows * nnz)
    assert tc.packed_native == (body == "native")
    h = hashlib.sha256()
    for a in (tc.uniq, tc.coo.idx, tc.coo.seg, tc.coo.val, tc.coo.tmap,
              tc.coo.first, tc.tmap_u, tc.first_u, tc.last_u):
        h.update(np.ascontiguousarray(a).tobytes())
    ts = ck.assign_tile_slots(np.unique(idx), ck.TILE, 2 * ck.TILE, nb)
    for a in (ts.uniq, ts.tmap_u, ts.first_u, ts.last_u, ts.slot_of_uniq):
        h.update(np.ascontiguousarray(a).tobytes())
    assert (tc.num_uniq, tc.dropped_uniq) == (16826, 0)
    assert h.hexdigest() == ("1a97c6bcee009d1cbe60ea6fd5bd50ca8720b07ecb6d8"
                             "7dbb03446f2cafb16aa")


# -------------------------------------------- the learner's three answers
@pytest.mark.parametrize("kernel,kinds", [("pallas", ("fm", "fm")),
                                          ("xla", ("xla", "xla_staged"))])
def test_difacto_learner_answers_the_harness_itself(kernel, kinds):
    """`tables()`, `batch_kind(b)`, `batch_label(b)`: what
    benchmark/check.py asks, answered by the learner for a prepared and
    for a staged batch; the labels are the minibatch's, without the rows
    the compact path pads on."""
    rows = 200
    lrn = _learner(50, rows, kernel)
    (keys, label), = _batches(rows, seed=1, steps=1)
    prepared = lrn.prepare_batch(_block(keys, label))
    # staged under the solver's span, which the learner tells the bytes
    with obs_trace._Span(None, "loader.h2d", "loader", {}) as h2d:
        staged = lrn.stage_batch(prepared)
    assert h2d.args == {"bytes": sum(a.nbytes for a in staged[2])}
    assert (lrn.batch_kind(prepared), lrn.batch_kind(staged)) == kinds
    for b in (prepared, staged):
        got = lrn.batch_label(b)
        assert got.dtype == np.float32 and np.array_equal(got, label)
    tabs = lrn.tables()
    assert sorted(tabs) == ["V", "cnt", "n", "nV", "w", "z"]
    assert tabs["w"].shape == (NB,) and tabs["V"].shape == (VB, 50)
    assert np.array_equal(np.asarray(tabs["V"]),
                          lrn.ckpt_store.to_numpy()["V"])


def test_linear_learner_answers_the_harness_itself():
    from wormhole_tpu.models.linear import LinearConfig, LinearLearner

    cfg = LinearConfig(minibatch=256, num_buckets=2 * ck.TILE,
                       nnz_per_row=NNZ, kernel="pallas")
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    (keys, label), = _batches(256, seed=2, steps=1)
    prepared = lrn.prepare_batch(_block(keys, label))
    staged = lrn.stage_batch(prepared)
    assert lrn.batch_kind(prepared) == lrn.batch_kind(staged) == "coo"
    assert np.array_equal(lrn.batch_label(prepared), label)
    assert np.array_equal(lrn.batch_label(staged), label)
    assert lrn.tables() is lrn.store.state


def test_compact_step_with_grad_normalization_matches_the_reference():
    """The benchmark's configuration divides the V gradient by the
    batch's rows (`grad_normalization`, upstream loss.h:145-155): the
    compact step and the plain reference agree on it, over the rows the
    batch really has (200), not the 256 the kernels see."""
    rows, dim = 200, 8
    lrn = _learner(dim, rows, seed=5, grad_normalization=True)
    batches = _batches(rows, seed=41, steps=3)
    sizes = {"bucket": NB, "vrow": VB}
    per = [ref.space_ids(k, sizes) for k, _ in batches]
    ids = {s: np.unique(np.concatenate([p[s].reshape(-1) for p in per]))
           for s in ref.SPACES}
    tabs = lrn.tables()
    start = {"ids": ids, "tables": {
        k: np.asarray(tabs[k])[ids[d["space"]]]
        for k, d in ref.TABLES.items()}}
    hyper = dict(HYPER, dim=dim)
    want = ref.run_steps(batches, sizes, dict(hyper, grad_normalization=1.0),
                         {"tables": "f32"}, start=start)
    plain = ref.run_steps(batches, sizes, hyper, {"tables": "f32"},
                          start=start)
    for keys, label in batches:
        lrn.train_batch(_block(keys, label))
    tabs = lrn.tables()
    for k in ("V", "nV"):
        got = np.asarray(tabs[k])[ids["vrow"]]
        np.testing.assert_allclose(got, want["states"][-1][k], rtol=2e-4,
                                   atol=1e-9, err_msg=k)
    # it is no small thing: nV is a sum of squares, 200^2 times smaller
    assert plain["states"][-1]["nV"].sum() > 1e4 * want["states"][-1][
        "nV"].sum() > 0


# ------------------------------------------------- the sparse PS push set
def _digest(a) -> tuple:
    import hashlib

    return (len(a), hashlib.sha256(np.ascontiguousarray(a).tobytes())
            .hexdigest()[:16])


@pytest.mark.parametrize("kernel,want_w,want_v", [
    ("pallas", (292, "f5a5016e289a6e95"), (292, "d1499ce3ce8173de")),
    ("xla", (291, "d286d720eeb0b891"), (291, "7880a0fb3363f091"))])
def test_collect_touched_gives_the_sets_it_gave_before_the_one_protocol(
        kernel, want_w, want_v):
    """After three train steps `collect_touched` names, for every table
    of the w id space and of the V id space, the sorted unique rows the
    steps wrote (the compact pack's live slots with the padding's
    bucket, or the XLA batch's nonzero ids; their V rows): lengths and
    digests taken at the parent commit of PR 50, where the learner kept
    two lists of its own. A drained learner answers empty sets, and a
    step whose batch was staged without the hint answers None."""
    rows = 200         # of a 256-row minibatch: the pack pads
    lrn = _learner(8, 256, kernel)
    lrn.track_touched = True
    batches = _batches(rows, seed=50, steps=4)
    for keys, label in batches[:3]:
        lrn.train_batch(_block(keys, label))
    got = lrn.collect_touched()
    assert sorted(got) == ["V", "cnt", "n", "nV", "w", "z"]
    for k in ("w", "z", "n", "cnt"):
        assert got[k] is got["w"]
    assert got["nV"] is got["V"]
    for a, nb in ((got["w"], NB), (got["V"], VB)):
        assert a.dtype == np.int64 and np.all(np.diff(a) > 0) and a[-1] < nb
    assert np.array_equal(got["V"], np.unique(got["w"] % VB))
    assert (_digest(got["w"]), _digest(got["V"])) == (want_w, want_v)
    again = lrn.collect_touched()
    assert sorted(again) == sorted(got) and all(
        len(a) == 0 for a in again.values())
    lrn.track_touched = False
    staged = lrn.stage_batch(_block(*batches[3]), True)
    lrn.track_touched = True
    lrn.train_batch(staged)
    assert lrn.collect_touched() is None
