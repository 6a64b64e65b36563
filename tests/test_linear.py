"""Linear learner tests: convergence per algo/loss, mesh equivalence,
quantized push, predict. The golden-metric smoke strategy of the reference
(agaricus demo converging in 3 passes, SURVEY §4)."""

import numpy as np
import pytest

from wormhole_tpu.data.minibatch import MinibatchIter
from wormhole_tpu.data.parsers import parse_libsvm
from wormhole_tpu.models.linear import LinearConfig, LinearLearner
from wormhole_tpu.parallel.mesh import make_mesh

from conftest import synth_libsvm_text


def _train_passes(lrn, path, passes=2, mb=128):
    last = {}
    for ep in range(passes):
        tot = {}
        for blk in MinibatchIter(path, fmt="libsvm", minibatch_size=mb,
                                 seed=ep):
            p = lrn.train_batch(blk)
            for k, v in p.items():
                tot[k] = tot.get(k, 0.0) + v
        last = {k: v / tot["nex"] for k, v in tot.items() if k != "nex"}
        last["nex"] = tot["nex"]
    return last


@pytest.fixture(scope="module")
def synth_file(tmp_path_factory):
    p = tmp_path_factory.mktemp("lin") / "synth.libsvm"
    p.write_text(synth_libsvm_text(n_rows=2000, n_feat=300, nnz_per_row=12,
                                   seed=5))
    return str(p)


@pytest.mark.parametrize("algo", ["ftrl", "adagrad", "sgd"])
def test_linear_converges(synth_file, algo):
    cfg = LinearConfig(minibatch=128, num_buckets=1 << 10, nnz_per_row=16,
                       algo=algo, lr_eta=0.5 if algo != "sgd" else 5.0)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    prog = _train_passes(lrn, synth_file, passes=3)
    assert prog["auc"] > 0.90, f"{algo}: auc {prog['auc']}"
    assert prog["acc"] > 0.80, f"{algo}: acc {prog['acc']}"


def test_square_hinge_converges(synth_file):
    cfg = LinearConfig(minibatch=128, num_buckets=1 << 10, nnz_per_row=16,
                       algo="adagrad", loss="square_hinge", lr_eta=0.3)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    prog = _train_passes(lrn, synth_file, passes=3)
    assert prog["auc"] > 0.90


def test_l1_sparsifies(synth_file):
    dense_cfg = LinearConfig(minibatch=128, num_buckets=1 << 10,
                             nnz_per_row=16, algo="ftrl", lr_eta=0.5)
    sparse_cfg = LinearConfig(minibatch=128, num_buckets=1 << 10,
                              nnz_per_row=16, algo="ftrl", lr_eta=0.5,
                              lambda_l1=10.0)
    dense = LinearLearner(dense_cfg, make_mesh(1, 1))
    sparse = LinearLearner(sparse_cfg, make_mesh(1, 1))
    _train_passes(dense, synth_file, passes=1)
    _train_passes(sparse, synth_file, passes=1)
    assert sparse.nnz() < dense.nnz()


def test_mesh_equivalence(synth_file):
    """Same data, 1x1 vs 4x2 mesh: metric parity within float tolerance —
    the sharded path computes the same math (SURVEY §2.3 strategy 1+3)."""
    def run(mesh):
        cfg = LinearConfig(minibatch=256, num_buckets=1 << 10,
                           nnz_per_row=16, algo="ftrl", lr_eta=0.5,
                           lambda_l1=0.5)
        lrn = LinearLearner(cfg, mesh)
        return _train_passes(lrn, synth_file, passes=2), lrn

    p1, l1 = run(make_mesh(1, 1))
    p8, l8 = run(make_mesh(4, 2))
    assert abs(p1["logloss"] - p8["logloss"]) < 1e-3
    assert abs(p1["auc"] - p8["auc"]) < 1e-3
    w1 = l1.store.to_numpy()["w"]
    w8 = l8.store.to_numpy()["w"]
    np.testing.assert_allclose(w1, w8, rtol=1e-3, atol=1e-5)


def test_quantized_push_still_converges(synth_file):
    cfg = LinearConfig(minibatch=128, num_buckets=1 << 10, nnz_per_row=16,
                       algo="adagrad", lr_eta=0.5, fixed_bytes=2)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    prog = _train_passes(lrn, synth_file, passes=3)
    assert prog["auc"] > 0.88


def test_predict_matches_eval(synth_file):
    cfg = LinearConfig(minibatch=128, num_buckets=1 << 10, nnz_per_row=16)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    _train_passes(lrn, synth_file, passes=1)
    blk = next(iter(MinibatchIter(synth_file, minibatch_size=64)))
    margins = lrn.predict_batch(blk)
    assert margins.shape == (64,)
    assert np.isfinite(margins).all()
    # accuracy computed from margins agrees with eval_step's
    acc = ((margins > 0) == (blk.label > 0.5)).mean()
    ev = lrn.eval_batch(blk)
    np.testing.assert_allclose(acc, ev["acc"] / ev["nex"], atol=1e-6)


def test_untouched_buckets_not_shrunk():
    """L1 shrinkage must only hit pushed keys (per-key Handle semantics,
    reference async_sgd.h:160-175): training on disjoint features leaves
    other buckets' weights exactly unchanged."""
    cfg = LinearConfig(minibatch=4, num_buckets=64, nnz_per_row=4,
                       algo="ftrl", lr_eta=0.5, lambda_l1=1.0)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    lrn.train_batch(parse_libsvm("1 1:1\n0 2:1\n1 1:2\n0 2:2\n"))
    w_after_a = lrn.store.to_numpy()["w"].copy()
    lrn.train_batch(parse_libsvm("1 10:1\n0 11:1\n1 10:2\n0 11:2\n"))
    w_after_b = lrn.store.to_numpy()["w"]
    np.testing.assert_array_equal(w_after_a[[1, 2]], w_after_b[[1, 2]])
    assert (w_after_b[[10, 11]] != 0).any()


def test_agaricus_three_pass_convergence(agaricus):
    """The reference's demo smoke: linear on mushroom converges in 3
    passes (BASELINE.md smoke row)."""
    train, test = agaricus
    cfg = LinearConfig(minibatch=512, num_buckets=1 << 14, nnz_per_row=32,
                       algo="ftrl", lr_eta=0.1, lambda_l1=1.0)
    lrn = LinearLearner(cfg, make_mesh(4, 2))
    for ep in range(3):
        for blk in MinibatchIter(train, minibatch_size=512, seed=ep):
            lrn.train_batch(blk)
    tot = {}
    for blk in MinibatchIter(test, minibatch_size=512):
        p = lrn.eval_batch(blk)
        for k, v in p.items():
            tot[k] = tot.get(k, 0.0) + v
    auc = tot["auc"] / tot["nex"]
    acc = tot["acc"] / tot["nex"]
    assert auc > 0.99 and acc > 0.95, (auc, acc)


def test_new_w_tracks_model_sparsity(synth_file):
    """The train step's device-side new_w deltas must sum to the model's
    |w|_0 (reference linear progress.h:10-35 / async_sgd.h:35-41)."""
    cfg = LinearConfig(minibatch=128, num_buckets=1 << 10, nnz_per_row=16,
                       algo="ftrl", lr_eta=0.5, lambda_l1=2.0)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    new_w_sum = 0.0
    for blk in MinibatchIter(synth_file, fmt="libsvm", minibatch_size=128):
        p = lrn.train_batch(blk)
        assert "new_w" in p and "clk" in p and "pclk" in p
        new_w_sum += p["new_w"]
    assert int(new_w_sum) == lrn.nnz()


def test_prob_predict_is_sigmoid_of_margin(synth_file):
    cfg = LinearConfig(minibatch=128, num_buckets=1 << 10, nnz_per_row=16)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    blk = next(iter(MinibatchIter(synth_file, minibatch_size=128)))
    lrn.train_batch(blk)
    margins = lrn.predict_batch(blk)
    lrn.cfg.prob_predict = True
    probs = lrn.predict_batch(blk)
    np.testing.assert_allclose(probs, 1 / (1 + np.exp(-margins)), rtol=1e-6)
    assert ((probs > 0) & (probs < 1)).all()


# ------------------------------------------------ tile-aligned compaction
def test_pack_tile_coo_roundtrip():
    """pack_tile_coo maps (uniq, compact slot) back to the original
    bucket ids exactly, keeps each touched tile's slot run contiguous and
    block-aligned, and drops overflow nonzeros when the unique count
    exceeds u_cap."""
    from wormhole_tpu.ops import coo_kernels as ck

    rng = np.random.default_rng(3)
    nb = 64 * ck.TILE
    nnz = 400000
    idx = rng.integers(0, nb, size=nnz).astype(np.int64)
    seg = rng.integers(0, 128, size=nnz).astype(np.int32)
    val = rng.normal(size=nnz).astype(np.float32)
    tc = ck.pack_tile_coo(idx, seg, val, nb, u_cap=16 * ck.TILE,
                          capacity=nnz)
    assert tc.dropped_nnz == 0
    live = tc.coo.val != 0
    # reconstruct original bucket ids from compact slots
    orig = tc.uniq[tc.coo.idx[live]]
    np.testing.assert_array_equal(np.sort(orig), np.sort(idx[val != 0]))
    # slot-run structure: every real slot's full-table tile matches the
    # tmap_u entry of its block, and runs are sorted within a tile
    real = tc.uniq != nb
    slots = np.flatnonzero(real)
    np.testing.assert_array_equal(
        tc.uniq[real] // ck.TILE, tc.tmap_u[slots // ck.BLK_U])
    assert tc.first_u.sum() == tc.last_u.sum() > 0
    # overflow: tiny u_cap drops nonzeros and reports them
    tc2 = ck.pack_tile_coo(idx, seg, val, nb, u_cap=ck.TILE,
                           capacity=nnz)
    assert tc2.dropped_nnz > 0
    assert (tc2.coo.val != 0).sum() + tc2.dropped_nnz == (val != 0).sum()


def test_pack_counters_say_which_body_packed(synth_file, monkeypatch):
    """`linear.pack.batches` counts every tcoo batch `_pack_tcoo` packs,
    train or eval, and `linear.pack.native` those of them the native
    pass packed: their ratio is the benchmark's `pack_native_share`, 1.0
    where the library serves and 0.0 where only the numpy body runs. The
    start-up line says the same once."""
    from wormhole_tpu import native
    from wormhole_tpu.obs.metrics import REGISTRY
    from wormhole_tpu.ops import coo_kernels as ck

    if not native.available():
        pytest.skip("native library unavailable (no toolchain?)")

    def counters():
        c = REGISTRY.snapshot()["counters"]
        return c["linear.pack.batches"], c["linear.pack.native"]

    cfg = LinearConfig(minibatch=128, num_buckets=8 * ck.TILE,
                       nnz_per_row=16, algo="ftrl", kernel="pallas",
                       compact_cap=ck.TILE, kernel_dtype="f32")
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    assert lrn.placement.endswith("tcoo_pack=native")
    blk = next(iter(MinibatchIter(synth_file, fmt="libsvm",
                                  minibatch_size=128)))
    b0, n0 = counters()
    assert lrn.prepare_batch(blk, train=True)[0] == "tcoo"
    lrn.prepare_batch(blk, train=False)
    assert counters() == (b0 + 2, n0 + 2)
    monkeypatch.setattr(native, "pack_tile_coo", lambda *a, **k: None)
    assert lrn.prepare_batch(blk, train=True)[0] == "tcoo"
    assert counters() == (b0 + 3, n0 + 2)
    # a process without the library says so before its first batch
    monkeypatch.setattr(native, "available", lambda: False)
    assert LinearLearner(cfg, make_mesh(1, 1)).placement.endswith(
        "tcoo_pack=numpy")
    # the xla path packs no tcoo batch and names no pack
    xla = LinearLearner(LinearConfig(minibatch=128, num_buckets=1 << 10,
                                     nnz_per_row=16), make_mesh(1, 1))
    assert "tcoo_pack" not in xla.placement
    xla.prepare_batch(blk)
    assert counters() == (b0 + 3, n0 + 2)


@pytest.mark.parametrize("algo", ["ftrl", "adagrad", "sgd"])
def test_compacted_matches_xla(synth_file, algo):
    """The tile-compacted (Localizer + fused in-place update) path must
    train identically to the dense XLA path: same per-pass metrics and
    same final table, while streaming only touched tiles per step
    (reference per-key server updates, async_sgd.h:160-180)."""
    from wormhole_tpu.ops import coo_kernels as ck

    def run(kernel, compact_cap):
        cfg = LinearConfig(minibatch=128, num_buckets=8 * ck.TILE,
                           nnz_per_row=16, algo=algo, lr_eta=0.5,
                           lambda_l1=0.5, kernel=kernel,
                           compact_cap=compact_cap, kernel_dtype="f32")
        lrn = LinearLearner(cfg, make_mesh(1, 1))
        return _train_passes(lrn, synth_file, passes=2), lrn

    p_x, l_x = run("xla", 0)
    p_r, l_r = run("pallas", ck.TILE)
    assert l_r._compact_cap == ck.TILE and "tcoo" in l_r._kinds
    assert abs(p_x["logloss"] - p_r["logloss"]) < 1e-3
    assert abs(p_x["auc"] - p_r["auc"]) < 1e-3
    w_x = l_x.store.to_numpy()["w"]
    w_r = l_r.store.to_numpy()["w"]
    np.testing.assert_allclose(w_x, w_r, rtol=1e-3, atol=1e-5)


def test_compacted_quantized_push_matches_xla(synth_file):
    """fixed_bytes=1 (global-absmax int8 filter) must agree between the
    fused in-kernel quantize and parallel.kvstore.quantize_push — the
    scale is computed over the whole compact gradient outside the kernel
    exactly so this holds."""
    from wormhole_tpu.ops import coo_kernels as ck

    def run(kernel, compact_cap):
        cfg = LinearConfig(minibatch=128, num_buckets=8 * ck.TILE,
                           nnz_per_row=16, algo="ftrl", lr_eta=0.5,
                           lambda_l1=0.5, fixed_bytes=1, kernel=kernel,
                           compact_cap=compact_cap, kernel_dtype="f32")
        lrn = LinearLearner(cfg, make_mesh(1, 1))
        return _train_passes(lrn, synth_file, passes=2), lrn

    p_x, l_x = run("xla", 0)
    p_r, l_r = run("pallas", ck.TILE)
    assert abs(p_x["logloss"] - p_r["logloss"]) < 1e-3
    assert abs(p_x["auc"] - p_r["auc"]) < 1e-3
    np.testing.assert_allclose(l_x.store.to_numpy()["w"],
                               l_r.store.to_numpy()["w"],
                               rtol=1e-4, atol=1e-6)


def test_compacted_predict_and_eval(synth_file):
    from wormhole_tpu.ops import coo_kernels as ck

    cfg = LinearConfig(minibatch=128, num_buckets=8 * ck.TILE,
                       nnz_per_row=16, algo="ftrl", lr_eta=0.5,
                       kernel="pallas", compact_cap=ck.TILE,
                       kernel_dtype="f32")
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    _train_passes(lrn, synth_file, passes=1)
    blk = next(iter(MinibatchIter(synth_file, minibatch_size=64)))
    margins = lrn.predict_batch(blk)
    assert margins.shape == (64,)
    acc = ((margins > 0) == (blk.label > 0.5)).mean()
    ev = lrn.eval_batch(blk)
    np.testing.assert_allclose(acc, ev["acc"] / ev["nex"], atol=1e-6)


# ------------------------------------------- one record a kind, one path
# case -> (config, mesh shape, pack_cache_token written out): the linear
# learner's four kinds under their own names, DiFacto's two as
# "difacto-<kind>"; one protocol (models/minibatch_learner.py) for both.
# The token's tail is the block geometry (TILE, BLK, BLK_U, LANES, and
# for the compact FM pack its capacities, stride, TILE_HI and FM_BLK): a
# data format, constant beside the kernels; the mesh pack's block rides
# after the shard's capacity, like it a function of the table and the
# mesh that only an `mcoo` batch is packed by (ck.mesh_block: BLK here,
# where a shard has one to four tiles for its 4,096 nonzeros)
_VB = 4096
_KINDS = {
    "xla": (dict(kernel="xla"), (1, 1),
            ("linear", 3, False, False, 0, 4096, 4096, 256, 8, 262144, 1, 1,
             65536, 4096, 1024, 128)),
    "coo": (dict(kernel="pallas", compact_cap=0), (1, 1),
            ("linear", 3, True, False, 0, 4096, 4096, 256, 8, 262144, 1, 1,
             65536, 4096, 1024, 128)),
    "tcoo": (dict(kernel="pallas", compact_cap=1), (1, 1),
             ("linear", 3, True, False, 65536, 4096, 4096, 256, 8, 262144,
              1, 1, 65536, 4096, 1024, 128)),
    "mcoo": (dict(kernel="pallas", model_shards=2), (2, 2),
             ("linear", 3, True, True, 0, 4096, 4096, 256, 8, 262144, 2, 2,
              65536, 4096, 1024, 128)),
    "difacto-xla": (dict(kernel="xla"), (1, 1),
                    ("difacto", 3, False, 256, 8, 262144, _VB, 4)),
    "difacto-fm": (dict(kernel="pallas"), (1, 1),
                   ("difacto", 3, True, 256, 8, 262144, _VB, 4,
                    (65536, 3072, 512), 4, 65536, 4096, 1024, 512, 1024,
                    128)),
}
_LINEAR_KINDS = [c for c in _KINDS if "-" not in c]


def _kind_learner(case, **kw):
    conf, mesh, _ = _KINDS[case]
    sizes = dict(minibatch=256, num_buckets=1 << 18, nnz_per_row=8,
                 algo="ftrl", lr_eta=0.5, lambda_l1=0.1, kernel_dtype="f32")
    if case.startswith("difacto-"):
        from wormhole_tpu.models.difacto import DifactoConfig, DifactoLearner

        return DifactoLearner(DifactoConfig(
            v_buckets=_VB, dim=4, threshold=2, **sizes, **conf, **kw),
            make_mesh(*mesh))
    return LinearLearner(LinearConfig(**sizes, **conf, **kw),
                         make_mesh(*mesh))


def _kind_tables(lrn) -> dict:
    return (getattr(lrn, "ckpt_store", None) or lrn.store).to_numpy()


def _kind_blocks(n=3, rows=200, nnz=8, nb=1 << 18):
    from wormhole_tpu.data.rowblock import RowBlock

    rng = np.random.default_rng(28)
    return [RowBlock(
        label=(rng.random(rows) < 0.4).astype(np.float32),
        offset=np.arange(0, rows * nnz + 1, nnz, dtype=np.int64),
        index=rng.integers(0, nb, rows * nnz).astype(np.uint64),
        value=rng.random(rows * nnz).astype(np.float32))
        for _ in range(n)]


@pytest.mark.parametrize("kind", list(_KINDS))
def test_every_form_of_a_batch_takes_the_one_path(kind):
    """A RowBlock, prepare_batch's tuple and stage_batch's tuple reach the
    kind's step the same way: progress and tables are the same to the
    bit, and so are predict's margins for the two forms it takes."""
    forms = {
        "rowblock": lambda lrn, blk, train: blk,
        "prepared": lambda lrn, blk, train: lrn.prepare_batch(blk, train),
        "staged": lambda lrn, blk, train: lrn.stage_batch(
            lrn.prepare_batch(blk, train), train),
    }
    b0, b1, b2 = _kind_blocks()
    got = {}
    for name, form in forms.items():
        lrn = _kind_learner(kind)
        progs = [lrn.train_batch(form(lrn, b0, True)),
                 lrn.train_batch(form(lrn, b1, True)),
                 lrn.eval_batch(form(lrn, b2, False))]
        if name != "staged":    # a staged batch was never a predict input
            progs.append(lrn.predict_batch(form(lrn, b2, False)))
        got[name] = (progs, _kind_tables(lrn))
    assert got["rowblock"][0][0]["nex"] == 200.0
    assert got["rowblock"][0][0]["new_w"] > 0
    ref_progs, ref_tables = got["rowblock"]
    for name in ("prepared", "staged"):
        progs, tables = got[name]
        assert progs[:3] == ref_progs[:3], name
        for k, v in ref_tables.items():
            np.testing.assert_array_equal(tables[k], v, err_msg=name)
    np.testing.assert_array_equal(got["prepared"][0][3], ref_progs[3])
    assert ref_progs[3].shape == (200,) and np.any(ref_progs[3] != 0)


@pytest.mark.parametrize("kind", _LINEAR_KINDS)
def test_ftrl_w_is_the_derived_table_of_z_and_n(kind):
    """What lets FTRL's update write w without reading it: on every
    bucket the stored w is `ftrl_weight` of the stored z and n, bit for
    bit, after steps from zero tables and again after a checkpoint's
    round trip through the host and a further step; and the learner
    that took the round trip holds what one that never left the device
    holds."""
    import jax
    import jax.numpy as jnp

    from wormhole_tpu.ops.fused_update import ftrl_weight

    def derived(t, cfg):
        # under jit, as the step forms it: XLA folds the constants
        return np.asarray(jax.jit(lambda z, n: ftrl_weight(
            z, jnp.sqrt(n), cfg.lr_eta, cfg.lr_beta, cfg.lambda_l1,
            cfg.lambda_l2))(t["z"], t["n"]))

    b0, b1, b2 = _kind_blocks()
    lrn = _kind_learner(kind)
    lrn.train_batch(b0)
    lrn.train_batch(b1)
    t = lrn.store.to_numpy()
    assert np.count_nonzero(t["w"]) > 100
    np.testing.assert_array_equal(t["w"], derived(t, lrn.cfg))
    back = _kind_learner(kind)
    back.store.from_numpy(t)
    progs = [x.train_batch(b2) for x in (lrn, back)]
    assert progs[0] == progs[1]
    t, tb = lrn.store.to_numpy(), back.store.to_numpy()
    for k in t:
        np.testing.assert_array_equal(tb[k], t[k], err_msg=k)
    np.testing.assert_array_equal(t["w"], derived(t, lrn.cfg))


@pytest.mark.parametrize("kind", list(_KINDS))
def test_a_batch_staged_for_the_other_step_is_refused(kind):
    lrn = _kind_learner(kind)
    blk = _kind_blocks(1)[0]
    with pytest.raises(AssertionError, match="staged for eval"):
        lrn.train_batch(lrn.stage_batch(blk, train=False))
    with pytest.raises(AssertionError, match="staged for train"):
        lrn.eval_batch(lrn.stage_batch(blk, train=True))
    if kind == "difacto-fm":    # the one kind whose packs differ
        for train in (True, False):
            with pytest.raises(AssertionError, match="packed for the other"):
                lrn.stage_batch(lrn.prepare_batch(blk, not train), train)


@pytest.mark.parametrize("case", list(_KINDS))
def test_batch_layouts_and_pack_cache_token_are_pinned(case):
    """The pack cache's entries, benchmark/check.py and chip_smoke.py read
    these tuples by position, and the token keys the cache's entries:
    one layout for both learners. The touched ids are an array an id
    space: the buckets, and for DiFacto the V rows they hash to."""
    from wormhole_tpu.data.rowblock import DeviceBatch

    kind = case.rsplit("-", 1)[-1]
    lrn = _kind_learner(case)
    lrn.track_touched = True
    blk = _kind_blocks(1)[0]
    assert lrn._PACK_VERSION == 3
    if kind in ("tcoo", "fm"):  # undecided until the first batch sizes it
        assert lrn.pack_cache_token() is None
    b = lrn.prepare_batch(blk)
    assert lrn.pack_cache_token() == _KINDS[case][2]
    assert b[0] == kind and b[-1] == 200
    assert lrn.batch_kind(b) == kind
    for train in (True, False):
        b = lrn.prepare_batch(blk, train)
        if kind == "xla":
            assert len(b) == 3 and isinstance(b[1], DeviceBatch)
            label, mask = b[1].label, b[1].row_mask
        else:
            assert len(b) == 5
            label, mask = b[2], b[3]
        assert label.shape == mask.shape == (256,)
        np.testing.assert_array_equal(label[:200], blk.label)
        assert mask.sum() == 200
        st = lrn.stage_batch(b, train)
        assert len(st) == 6 and st[:2] == ("staged", kind)
        assert st[3] == 200 and st[5] is train
        assert lrn.stage_batch(st, train) is st
        args = st[2]
        assert isinstance(args, tuple)
        np.testing.assert_array_equal(np.asarray(args[-2]), label)
        np.testing.assert_array_equal(np.asarray(args[-1]), mask)
        for form in (b, st):
            np.testing.assert_array_equal(lrn.batch_label(form), label)
        ids = st[4]
        if not train or kind == "mcoo":   # mcoo: left to the delta scan
            assert ids is None
            continue
        want = np.unique(blk.index.astype(np.int64) % (1 << 18))
        if kind in ("tcoo", "fm"):  # the padding's bucket rides along
            want = np.union1d(want, [0])
        spaces = (want, np.unique(want % _VB))[:len(lrn._id_spaces())]
        assert len(ids) == len(spaces) == 1 + case.startswith("difacto-")
        for got, ref in zip(ids, spaces):
            np.testing.assert_array_equal(got, ref)


# ------------------------- the compact step pulls over its own COO stream
# case -> (live rows of a 256-row batch, entries a row, buckets,
# compact_cap). `nnz_per_row` is 8, so "short" rows leave padding inside
# the capacity, "long" rows go over 8 entries a row while the batch stays
# within it (one stream holds them all: a row-major companion of width 8
# would have cut them), and "overflow" touches every one of 128 table
# tiles with room for 64 update blocks, so the pack drops the keys of the
# upper tiles from the one stream pull and push both walk.
_PULL_CASES = {
    "full_rows": (256, (8, 8), 8, 1),
    "short_rows": (256, (1, 7), 8, 1),
    "long_rows": (128, (1, 15), 8, 1),
    "padding_rows": (200, (8, 8), 8, 1),
    "overflow": (256, (8, 8), 128, 1),
}


def _pull_blocks(case, n=4):
    from wormhole_tpu.data.rowblock import RowBlock
    from wormhole_tpu.ops import coo_kernels as ck

    rows, (lo, hi), tiles, _ = _PULL_CASES[case]
    rng = np.random.default_rng(32)
    out = []
    for _ in range(n):
        per_row = rng.integers(lo, hi + 1, rows)
        offset = np.concatenate([[0], np.cumsum(per_row)]).astype(np.int64)
        nnz = int(offset[-1])
        # a third of the entries on 40 hot keys, so that rows share keys
        # and the weights of the later steps are not all zero
        index = np.where(rng.random(nnz) < 0.33,
                         rng.integers(0, 40, nnz) * 1009,
                         rng.integers(0, tiles * ck.TILE, nnz))
        out.append(RowBlock(
            label=(rng.random(rows) < 0.4).astype(np.float32),
            offset=offset, index=index.astype(np.uint64),
            value=(0.25 + rng.random(nnz)).astype(np.float32)))
    return out


@pytest.fixture(scope="module", params=list(_PULL_CASES))
def pull_pair(request):
    """(case, tcoo learner, xla learner, their progress over three train
    steps, the fourth batch as each is to read it), trained once a case
    and dropped before the next. The xla learner gets the batches less
    what the compact pack dropped."""
    from wormhole_tpu.data.rowblock import RowBlock
    from wormhole_tpu.ops import coo_kernels as ck

    case = request.param
    _, _, tiles, cap = _PULL_CASES[case]
    nb = tiles * ck.TILE

    def learner(**kw):
        return LinearLearner(LinearConfig(
            minibatch=256, num_buckets=nb, nnz_per_row=8, algo="ftrl",
            lr_eta=0.5, lambda_l1=0.05, kernel_dtype="f32", **kw),
            make_mesh(1, 1))

    tc_l, x_l = learner(kernel="pallas", compact_cap=cap), learner(
        kernel="xla")

    def kept_only(blk, packed):
        kept = packed.uniq[packed.uniq < nb]
        live = np.isin(blk.index.astype(np.int64) % nb, kept)
        per_row = np.add.reduceat(live.astype(np.int64), blk.offset[:-1])
        return RowBlock(
            label=blk.label, index=blk.index[live], value=blk.value[live],
            offset=np.concatenate([[0], np.cumsum(per_row)]).astype(np.int64))

    progs, fourth = [], None
    for i, blk in enumerate(_pull_blocks(case)):
        b = tc_l.prepare_batch(blk, train=i < 3)
        assert b[0] == "tcoo"
        dropped = b[1].dropped_nnz
        assert (dropped > 0) == (case == "overflow"), (case, dropped)
        xblk = kept_only(blk, b[1]) if dropped else blk
        if i < 3:
            progs.append((tc_l.train_batch(b), x_l.train_batch(xblk)))
        else:
            fourth = (blk, xblk)
    return case, tc_l, x_l, progs, fourth


def _same_progress(got, want):
    assert got["nex"] == want["nex"] > 0
    for k in ("objv", "logloss", "pclk", "acc", "auc", "clk"):
        np.testing.assert_allclose(got[k], want[k], rtol=2e-5, atol=1e-5,
                                   err_msg=k)


@pytest.mark.parametrize("step", ["train", "eval", "predict"])
def test_compact_step_pulls_what_the_xla_kind_pulls(pull_pair, step):
    """The compact (`tcoo`) steps compute xw = X w with `tile_gather` and
    `coo_pull` over the batch's own COO stream, the one the push walks:
    margins, progress and the tables after three train steps equal the
    XLA kind's on the same batches (kernel_dtype f32: equal to summation
    order), whatever the rows hold, and where the compact domain
    overflows both agree on which nonzeros exist."""
    case, tc_l, x_l, progs, (blk, xblk) = pull_pair
    live = _PULL_CASES[case][0]
    if step == "train":
        for got, want in progs:
            assert got["nex"] == live
            _same_progress(got, want)
        # the later steps pulled trained weights, not zeros
        assert progs[0][0]["pclk"] != progs[2][0]["pclk"]
        got_t, want_t = tc_l.store.to_numpy(), x_l.store.to_numpy()
        assert np.count_nonzero(want_t["w"]) > 100
        for k in ("z", "n", "w"):
            np.testing.assert_allclose(got_t[k], want_t[k], rtol=2e-5,
                                       atol=1e-6, err_msg=k)
    elif step == "eval":
        _same_progress(tc_l.eval_batch(blk), x_l.eval_batch(xblk))
    else:
        got, want = tc_l.predict_batch(blk), x_l.predict_batch(xblk)
        assert got.shape == want.shape == (live,)
        assert np.count_nonzero(want) > live // 2
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


def test_compact_batches_ship_one_stream_and_no_row_major_companion():
    """Nothing of the linear learner reads a row-major companion any
    more: the pack makes none, and every step's arguments are the compact
    slots and the COO stream (train adds the update-block bounds)."""
    lrn = _kind_learner("tcoo")
    b = lrn.prepare_batch(_kind_blocks(1)[0])
    tc = b[1]
    import dataclasses

    assert [f.name for f in dataclasses.fields(tc)] == [
        "uniq", "coo", "tmap_u", "first_u", "last_u", "num_uniq",
        "dropped_uniq", "dropped_nnz"]
    p = tc.coo
    stream = (p.idx, p.seg, p.val, p.tmap, p.first)
    for train, head in ((True, (tc.uniq, tc.tmap_u, tc.first_u, tc.last_u)),
                        (False, (tc.uniq, tc.tmap_u))):
        args = lrn.stage_batch(b, train)[2]
        assert len(args) == len(head) + 5 + 2      # + label, mask
        for got, want in zip(args, head + stream):
            np.testing.assert_array_equal(np.asarray(got), want)
    # predict takes the same arrays less label and mask
    assert len(lrn._kinds["tcoo"].args(tc)) == 7
