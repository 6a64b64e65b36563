"""DiFacto FM tests: interaction learning (vs linear), admission
threshold, grad knobs, checkpoint with both tables, early stop."""

import os

import numpy as np
import pytest

from wormhole_tpu.data.minibatch import MinibatchIter
from wormhole_tpu.data.parsers import parse_libsvm
from wormhole_tpu.models.difacto import (
    DifactoConfig,
    DifactoLearner,
    make_early_stop_hook,
)
from wormhole_tpu.models.linear import LinearConfig, LinearLearner
from wormhole_tpu.parallel.mesh import make_mesh
from wormhole_tpu.solver.minibatch_solver import MinibatchSolver


def fm_synth_text(n_rows=3000, n_a=40, n_b=40, k=3, seed=0):
    """Labels from a low-rank interaction sign(u_f1 . v_f2): learnable by
    an FM with dim >= k, not by a linear model (marginals are ~0)."""
    rng = np.random.default_rng(seed)
    lat = np.random.default_rng(77)
    U = lat.normal(size=(n_a, k))
    Vt = lat.normal(size=(n_b, k))
    lines = []
    for _ in range(n_rows):
        a = rng.integers(n_a)
        b = rng.integers(n_b)
        y = 1 if (U[a] * Vt[b]).sum() > 0 else 0
        lines.append(f"{y} {a}:1 {n_a + b}:1")
    return "\n".join(lines) + "\n"


@pytest.fixture(scope="module")
def fm_file(tmp_path_factory):
    p = tmp_path_factory.mktemp("fm") / "fm.libsvm"
    p.write_text(fm_synth_text())
    return str(p)


def _train(lrn, path, passes, mb=256):
    last = {}
    for ep in range(passes):
        tot = {}
        for blk in MinibatchIter(path, fmt="libsvm", minibatch_size=mb,
                                 seed=ep):
            p = lrn.train_batch(blk)
            for k, v in p.items():
                tot[k] = tot.get(k, 0.0) + v
        last = tot
    return {k: v / last["nex"] for k, v in last.items() if k != "nex"}


def test_fm_beats_linear_on_interactions(fm_file):
    lin = LinearLearner(
        LinearConfig(minibatch=256, num_buckets=256, nnz_per_row=4,
                     algo="ftrl", lr_eta=0.5),
        make_mesh(1, 1))
    lin_prog = _train(lin, fm_file, passes=6)

    fm = DifactoLearner(
        DifactoConfig(minibatch=256, num_buckets=256, nnz_per_row=4,
                      dim=8, threshold=1, lr_eta=0.5, V_lr_eta=0.2,
                      V_init_scale=0.05),
        make_mesh(1, 1))
    fm_prog = _train(fm, fm_file, passes=6)

    assert lin_prog["auc"] < 0.65, "linear should NOT solve interactions"
    assert fm_prog["auc"] > 0.85, f"FM should: {fm_prog}"
    assert fm_prog["auc"] > lin_prog["auc"] + 0.2


def test_threshold_blocks_embeddings(fm_file):
    cfg = DifactoConfig(minibatch=256, num_buckets=256, nnz_per_row=4,
                        dim=4, threshold=10 ** 9, lr_eta=0.5)
    fm = DifactoLearner(cfg, make_mesh(1, 1))
    prog = _train(fm, fm_file, passes=3)
    assert fm.num_admitted() == 0
    # with V gated off the model is linear -> can't learn interactions
    assert prog["auc"] < 0.65


def test_admission_counts(fm_file):
    cfg = DifactoConfig(minibatch=256, num_buckets=256, nnz_per_row=4,
                        dim=4, threshold=5, lr_eta=0.5)
    fm = DifactoLearner(cfg, make_mesh(1, 1))
    _train(fm, fm_file, passes=1)
    # 80 distinct features x ~37 occurrences each >> threshold 5
    assert fm.num_admitted() == 80


def test_grad_knobs_compile(fm_file):
    cfg = DifactoConfig(minibatch=128, num_buckets=256, nnz_per_row=4,
                        dim=4, threshold=1, grad_clipping=0.5,
                        grad_normalization=True, dropout=0.3,
                        fixed_bytes=2, lambda_V=0.1, l1_shrk=True,
                        lambda_l1=0.01)
    fm = DifactoLearner(cfg, make_mesh(1, 1))
    prog = _train(fm, fm_file, passes=1)
    assert np.isfinite(prog["logloss"])


def test_mesh_equivalence(fm_file):
    def run(mesh):
        cfg = DifactoConfig(minibatch=256, num_buckets=256, nnz_per_row=4,
                            dim=8, threshold=1, lr_eta=0.5, V_lr_eta=0.2,
                            V_init_scale=0.05)
        fm = DifactoLearner(cfg, mesh, seed=3)
        return _train(fm, fm_file, passes=2), fm

    p1, f1 = run(make_mesh(1, 1))
    p8, f8 = run(make_mesh(4, 2))
    assert abs(p1["logloss"] - p8["logloss"]) < 2e-3
    np.testing.assert_allclose(f1.store.to_numpy()["w"],
                               f8.store.to_numpy()["w"],
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(f1.vstore.to_numpy()["V"],
                               f8.vstore.to_numpy()["V"],
                               rtol=2e-2, atol=2e-4)


def test_solver_checkpoint_both_tables(fm_file, tmp_path):
    cfg = DifactoConfig(
        train_data=fm_file.replace(".libsvm", r"\.libsvm"),
        minibatch=256, num_buckets=256, nnz_per_row=4, dim=4,
        threshold=1, max_data_pass=2, num_parts_per_file=2,
        model_out=str(tmp_path / "m/fm"))
    fm = DifactoLearner(cfg, make_mesh(1, 1))
    MinibatchSolver(fm, cfg, verbose=False).run()
    loaded = dict(np.load(str(tmp_path / "m/fm.npz")))
    assert set(loaded) == {"w", "z", "n", "cnt", "V", "nV"}
    assert loaded["V"].shape == (256, 4)

    # roundtrip: load into fresh learner, eval identical
    cfg2 = DifactoConfig(**{**cfg.__dict__, "model_in": str(tmp_path / "m/fm"),
                            "max_data_pass": 0, "model_out": None})
    fm2 = DifactoLearner(cfg2, make_mesh(4, 2))
    s2 = MinibatchSolver(fm2, cfg2, verbose=False)
    s2.run()
    blk = next(iter(MinibatchIter(fm_file, minibatch_size=256)))
    np.testing.assert_allclose(fm.predict_batch(blk), fm2.predict_batch(blk),
                               rtol=1e-4, atol=1e-5)


def test_early_stop_hook(fm_file, tmp_path):
    cfg = DifactoConfig(
        train_data=fm_file.replace(".libsvm", r"\.libsvm"),
        val_data=fm_file.replace(".libsvm", r"\.libsvm"),
        minibatch=256, num_buckets=256, nnz_per_row=4, dim=4, threshold=1,
        max_data_pass=50, early_stop_epsilon=0.5)  # huge eps -> stop early
    fm = DifactoLearner(cfg, make_mesh(1, 1))
    solver = MinibatchSolver(fm, cfg, verbose=False)
    solver.stop_hook = make_early_stop_hook(cfg)
    steps, step = [], fm.train_batch
    fm.train_batch = lambda b: steps.append(1) or step(b)
    solver.run()
    # big epsilon: second val pass can't improve by 0.5 -> stops at pass 1
    assert 0 < len(steps) <= 2 * 12 * 2


def test_predict_shape(fm_file):
    cfg = DifactoConfig(minibatch=64, num_buckets=256, nnz_per_row=4,
                        dim=4, threshold=1)
    fm = DifactoLearner(cfg, make_mesh(1, 1))
    blk = parse_libsvm("1 1:1 41:1\n0 2:1 42:1\n")
    m = fm.predict_batch(blk)
    assert m.shape == (2,) and np.isfinite(m).all()


# ------------------------------------------------------- compact FM path
def _train_file(lrn, path, passes=2, mb=256, train=True):
    tot = {}
    for ep in range(passes):
        tot = {}
        for blk in MinibatchIter(path, minibatch_size=mb, seed=ep):
            p = lrn.train_batch(blk) if train else lrn.eval_batch(blk)
            for k, v in p.items():
                tot[k] = tot.get(k, 0.0) + v
    return tot


def test_fm_compact_matches_xla_exactly(fm_file):
    """threshold=0 (admission always on) makes the compact Pallas path's
    math identical to the XLA segment path in f32: same metrics, same
    final tables."""
    from wormhole_tpu.ops import coo_kernels as ck

    def run(kernel):
        cfg = DifactoConfig(minibatch=256, num_buckets=2 * ck.TILE,
                            v_buckets=ck.TILE, nnz_per_row=8,
                            dim=4, threshold=0, lr_eta=0.3,
                            kernel=kernel, kernel_dtype="f32",
                            dropout=0.0)
        lrn = DifactoLearner(cfg, make_mesh(1, 1))
        tot = _train_file(lrn, fm_file, passes=1)
        return tot, lrn

    t_x, l_x = run("xla")
    t_p, l_p = run("pallas")
    assert l_p._use_fm_pallas and l_p._fm_steps is not None
    assert abs(t_x["logloss"] - t_p["logloss"]) / t_x["nex"] < 1e-4
    s_x, s_p = l_x.ckpt_store.to_numpy(), l_p.ckpt_store.to_numpy()
    for k in ("w", "z", "n", "cnt", "V", "nV"):
        np.testing.assert_allclose(
            s_x[k], s_p[k], rtol=2e-3, atol=2e-5,
            err_msg=f"table {k} diverged")


def test_fm_compact_admission_and_convergence(fm_file):
    """With a real threshold the compact step decides admission on the
    device: the count table holds every key's occurrences, nothing of it
    lives on the host, the step's counters say how many nonzeros were
    admitted, and the model still learns the interaction structure."""
    from wormhole_tpu.obs.metrics import REGISTRY
    from wormhole_tpu.ops import coo_kernels as ck

    cfg = DifactoConfig(minibatch=256, num_buckets=2 * ck.TILE,
                        v_buckets=ck.TILE, nnz_per_row=8,
                        dim=4, threshold=3, lr_eta=0.3, V_lr_eta=0.1,
                        kernel="pallas", kernel_dtype="f32")
    lrn = DifactoLearner(cfg, make_mesh(1, 1))
    c0 = REGISTRY.snapshot()["counters"]
    tot = _train_file(lrn, fm_file, passes=4)
    auc = tot["auc"] / tot["nex"]
    assert auc > 0.78, auc  # == the XLA path's AUC on this config
    # the device's count table == the data's occurrence counts
    want = np.zeros(cfg.num_buckets, np.float32)
    for blk in MinibatchIter(fm_file, minibatch_size=256):
        np.add.at(want, (blk.index % cfg.num_buckets).astype(np.int64), 4.0)
    np.testing.assert_array_equal(np.asarray(lrn.store.state["cnt"]), want)
    assert not hasattr(lrn, "_cnt_host")
    c1 = REGISTRY.snapshot()["counters"]
    live = c1["difacto.step.live_nnz"] - c0.get("difacto.step.live_nnz", 0)
    adm = (c1["difacto.step.admitted_nnz"]
           - c0.get("difacto.step.admitted_nnz", 0))
    assert live == 4 * 2 * 3000
    # 80 keys, each seen ~37 times a pass: all but their first two
    # occurrences are admitted
    assert live - 2 * 80 <= adm + 80 and adm < live
    # eval/predict run the compact forward too
    blk = next(iter(MinibatchIter(fm_file, minibatch_size=128)))
    margins = lrn.predict_batch(blk)
    assert margins.shape == (128,)
    ev = lrn.eval_batch(blk)
    acc = ((margins > 0) == (blk.label > 0.5)).mean()
    np.testing.assert_allclose(acc, ev["acc"] / ev["nex"], atol=1e-6)


def test_v_aliasing_measured_and_bounded(fm_file):
    """The V table is a hash kernel (vidx = key % v_buckets) where the
    reference keeps exact per-key embeddings (async_sgd.h:135-209).
    This bounds the aliasing: v_collision_rate() reports the admitted-key
    collision fraction, and shrinking v_buckets 8x on this workload must
    not cost more than a small logloss delta — the documented sizing
    guidance (docs/difacto.md) keeps the rate low."""
    from wormhole_tpu.ops import coo_kernels as ck

    def run(vb):
        cfg = DifactoConfig(minibatch=256, num_buckets=2 * ck.TILE,
                            v_buckets=vb, nnz_per_row=8, dim=4,
                            threshold=1, lr_eta=0.3, V_lr_eta=0.1,
                            kernel="xla")
        lrn = DifactoLearner(cfg, make_mesh(1, 1))
        tot = _train_file(lrn, fm_file, passes=3)
        return tot["logloss"] / tot["nex"], lrn

    ll_exact, l_exact = run(2 * ck.TILE)  # vb == num_buckets: 1:1
    # the fixture has 80 feature keys (0..79): vb=72 folds keys 72..79
    # onto 0..7, a 20% admitted-key collision rate
    ll_alias, l_alias = run(72)
    r_exact = l_exact.v_collision_rate()
    r_alias = l_alias.v_collision_rate()
    # with vb == num_buckets the map is injective: zero collisions
    assert r_exact == 0.0, r_exact
    # the aliased table must REPORT its collisions...
    np.testing.assert_allclose(r_alias, 16 / 80)
    # ...and at this collision level the quality cost is bounded: a few
    # percent of logloss, not a cliff
    assert ll_alias - ll_exact < 0.08, (ll_exact, ll_alias, r_alias)


def test_fm_pack_row_overflow_drops_from_both_layouts():
    """A row with more live V nonzeros than nnz_per_row overflows the
    row-major layout; the overflow must be dropped from BOTH the rm
    arrays and the slot-sorted COO (else the forward and the push would
    disagree about which interactions exist)."""
    import types

    from wormhole_tpu.ops import coo_kernels as ck

    W = 4
    cfg = DifactoConfig(minibatch=8, num_buckets=2 * ck.TILE,
                        v_buckets=ck.TILE, nnz_per_row=W, dim=4,
                        threshold=0, kernel="pallas", kernel_dtype="f32")
    lrn = DifactoLearner(cfg, make_mesh(1, 1))
    # row 0 carries 7 live nonzeros (> W); rows 1..7 carry 2 each
    segs, idxs, vals = [], [], []
    for j in range(7):
        segs.append(0); idxs.append(11 + j); vals.append(1.0 + j)
    for r in range(1, 8):
        for j in range(2):
            segs.append(r); idxs.append(100 + 10 * r + j); vals.append(1.0)
    seg = np.array(segs, np.int32)
    idx = np.array(idxs, np.int64)
    val = np.array(vals, np.float32)
    db = types.SimpleNamespace(seg=seg, idx=idx, val=val)
    pk = dict(zip(lrn._FM_TRAIN, lrn._pack_fm(db, train=True)))
    # the batch's 8 rows are padded to the kernels' 128; the layout is
    # position-major (all rows' first nonzero, then all rows' second...)
    rm_w2 = pk["rm_wval"].reshape(W, 128).T
    assert not rm_w2[8:].any()
    # row 0 keeps exactly W of its 7 interactions in the forward...
    assert np.count_nonzero(rm_w2[0]) == W
    # ...and both sorted streams keep the SAME multiset of values per row
    live = pk["vval"] != 0
    coo_row0 = np.sort(pk["vval"][live & (pk["vseg"] == 0)])
    np.testing.assert_array_equal(coo_row0, np.sort(rm_w2[0]))
    livew = pk["wval"] != 0
    wcoo_row0 = np.sort(pk["wval"][livew & (pk["wseg"] == 0)])
    np.testing.assert_array_equal(wcoo_row0, np.sort(rm_w2[0]))
    # the V stream is sorted by key rank: a nonzero's rank leads to its
    # key's w slot, and that bucket's V row lies in the line the key's
    # compact row belongs to (32 rows of 4 floats a line)
    rank = pk["vidx"][live]
    assert (np.diff(rank) >= 0).all()
    bucket = pk["uniq_w"][pk["key_slot"][rank]]
    assert np.array_equal(bucket % cfg.vb // 32,
                          pk["vlines"][pk["key_vslot"][rank] // 32])
    # untouched rows are intact in both layouts
    for r in range(1, 8):
        assert np.count_nonzero(rm_w2[r]) == 2
        assert np.count_nonzero(pk["vval"][live & (pk["vseg"] == r)]) == 2
