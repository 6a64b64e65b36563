"""Mesh-sharded Pallas COO kernels: the tile grid shard_map'ed over the
model axis and rows over the data axis must reproduce the XLA segment-op
path exactly (interpret mode, f32) — the ZPull/ZPush key-sharded layout
of reference async_sgd.h:277-287 on a real mesh."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from wormhole_tpu.data.minibatch import MinibatchIter
from wormhole_tpu.models.linear import LinearConfig, LinearLearner
from wormhole_tpu.ops import coo_kernels as ck
from wormhole_tpu.parallel.mesh import make_mesh

from conftest import synth_libsvm_text

NB = 2 * ck.TILE  # 2 tiles -> one per model shard on a 2-wide model axis


def _random_coo(rng, nnz, num_rows, num_buckets):
    idx = rng.integers(0, num_buckets, size=nnz).astype(np.int32)
    seg = np.sort(rng.integers(0, num_rows, size=nnz)).astype(np.int32)
    val = rng.normal(size=nnz).astype(np.float32)
    return idx, seg, val


def test_pack_mesh_coo_partitions_exactly():
    rng = np.random.default_rng(0)
    num_rows, D, M = 256, 2, 2
    idx, seg, val = _random_coo(rng, 1000, num_rows, NB)
    cap = ck.mesh_capacity(4096, D, M)
    mc = ck.pack_mesh_coo(idx, seg, val, NB, num_rows, D, M, cap)
    assert mc.dropped_nnz == 0
    # every live nonzero lands in exactly one cell with local coordinates
    total = 0
    for d in range(D):
        for m in range(M):
            live = mc.sval[d, m] != 0
            total += int(live.sum())
            assert (mc.sidx[d, m][live] < NB // M).all()
            assert (mc.sseg[d, m][live] < num_rows // D).all()
    assert total == int((val != 0).sum())


@pytest.mark.parametrize("D,M", [(2, 2), (2, 1), (1, 2)])
def test_mesh_spmv_matches_dense(D, M):
    rng = np.random.default_rng(1)
    num_rows = 256
    idx, seg, val = _random_coo(rng, 2000, num_rows, NB)
    w = rng.normal(size=NB).astype(np.float32)
    d_vec = rng.normal(size=num_rows).astype(np.float32)

    mesh = make_mesh(D, M)
    cap = ck.mesh_capacity(4096, D, M)
    mc = ck.pack_mesh_coo(idx, seg, val, NB, num_rows, D, M, cap)
    args = tuple(jnp.asarray(x) for x in
                 (mc.sidx, mc.sseg, mc.sval, mc.tmap, mc.first))

    xw = ck.mesh_coo_spmv(mesh, jnp.asarray(w), *args, num_rows)
    want_xw = np.zeros(num_rows, np.float32)
    np.add.at(want_xw, seg, val * w[idx])
    np.testing.assert_allclose(np.asarray(xw), want_xw, rtol=2e-5,
                               atol=1e-5)

    g = ck.mesh_coo_spmv_t(mesh, jnp.asarray(d_vec), *args, NB)
    want_g = np.zeros(NB, np.float32)
    np.add.at(want_g, idx, val * d_vec[seg])
    np.testing.assert_allclose(np.asarray(g), want_g, rtol=2e-5, atol=1e-5)


def test_learner_pallas_matches_xla_on_2x2_mesh(tmp_path):
    """kernel=pallas on a 2x2 mesh trains the same model as kernel=xla
    (VERDICT r1 item 3 done-criterion)."""
    p = tmp_path / "t.libsvm"
    p.write_text(synth_libsvm_text(n_rows=512, n_feat=200, nnz_per_row=10,
                                   seed=3))
    common = dict(minibatch=256, num_buckets=NB, nnz_per_row=16,
                  algo="ftrl", lr_eta=0.5, lambda_l1=0.5,
                  kernel_dtype="f32")
    lrn_x = LinearLearner(LinearConfig(kernel="xla", **common),
                          make_mesh(2, 2))
    lrn_p = LinearLearner(LinearConfig(kernel="pallas", **common),
                          make_mesh(2, 2))
    assert lrn_p.use_pallas and lrn_p._mesh_coo
    for blk in MinibatchIter(str(p), minibatch_size=256):
        px = lrn_x.train_batch(blk)
        pp = lrn_p.train_batch(blk)
        np.testing.assert_allclose(pp["logloss"], px["logloss"], rtol=1e-4)
    wx = lrn_x.store.to_numpy()
    wp = lrn_p.store.to_numpy()
    for k in wx:
        np.testing.assert_allclose(wp[k], wx[k], rtol=1e-4, atol=1e-6)
    # predict agrees too
    blk = next(iter(MinibatchIter(str(p), minibatch_size=256)))
    np.testing.assert_allclose(lrn_p.predict_batch(blk),
                               lrn_x.predict_batch(blk),
                               rtol=1e-4, atol=1e-5)


# ------------------------------------------------- live-extent bodies
def test_pack_mesh_coo_runs_are_a_prefix():
    """Every cell's blocks hold their live nonzeros first: zero-valued
    input triples (padding) are dropped before the split, so a block's
    extent is exactly its run's length and the host's sampled chunk
    count is exact."""
    rng = np.random.default_rng(5)
    num_rows, D, M = 256, 2, 2
    idx, seg, val = _random_coo(rng, 3000, num_rows, NB)
    idx[:1500] = rng.integers(0, 40, size=1500)      # a hot run over CHUNK
    val[rng.random(len(val)) < 0.2] = 0.0            # input padding triples
    mc = ck.pack_mesh_coo(idx, seg, val, NB, num_rows, D, M,
                          ck.mesh_capacity(4096, D, M))
    live = (mc.sval != 0).reshape(-1, ck.BLK)
    n = live.sum(1)
    assert (live == (np.arange(ck.BLK)[None, :] < n[:, None])).all()
    assert n.sum() == int((val != 0).sum()) and n.max() > ck.CHUNK
    ext = np.asarray(ck.block_extents(
        jnp.asarray(mc.sval.reshape(-1)) != 0, ck.BLK))
    np.testing.assert_array_equal(ext, n)
    chunks, run = ck.host_chunk_counts(mc.sval, 0, ck.BLK)
    assert chunks == len(n) * (ck.BLK // ck.CHUNK)
    assert run == int(np.sum(ck.chunks_run(n, ck.BLK)))


@pytest.mark.parametrize("D,M", [(2, 2), (1, 2)])
def test_mesh_spmv_equals_full_width(D, M, monkeypatch):
    """Each shard's kernels bound their work by their own blocks'
    extents (derived inside shard_map): same results as the full-width
    bodies to summation order."""
    rng = np.random.default_rng(21)
    num_rows = 256
    idx, seg, val = _random_coo(rng, 2500, num_rows, NB)
    idx[:1200] = rng.integers(0, 30, size=1200)
    mesh = make_mesh(D, M)
    mc = ck.pack_mesh_coo(idx, seg, val, NB, num_rows, D, M,
                          ck.mesh_capacity(4096, D, M))
    args = tuple(jnp.asarray(a) for a in
                 (mc.sidx, mc.sseg, mc.sval, mc.tmap, mc.first))
    w = jnp.asarray(rng.normal(size=NB).astype(np.float32))
    d_vec = jnp.asarray(rng.normal(size=num_rows).astype(np.float32))

    def both():
        return (np.asarray(ck.mesh_coo_spmv(mesh, w, *args, num_rows)),
                np.asarray(ck.mesh_coo_spmv_t(mesh, d_vec, *args, NB)))

    got = both()
    monkeypatch.setattr(ck, "block_extents", lambda live, blk: jnp.full(
        (live.shape[0] // blk,), blk, jnp.int32))
    want = both()
    for a, b in zip(got, want):
        assert np.max(np.abs(a - b)) <= 1e-6 * np.max(np.abs(b))


@pytest.mark.parametrize("kind", ["tcoo", "mcoo"])
def test_chunks_run_counter_equals_the_devices_extents(kind, tmp_path):
    """`linear.blocks.chunks_run`, counted on the host at the pack from
    a few sampled slots a block, is the number of chunks below the
    extents the kernel wrappers derive on the device from the staged
    arrays."""
    from wormhole_tpu.obs.metrics import REGISTRY

    p = tmp_path / "t.libsvm"
    p.write_text(synth_libsvm_text(n_rows=256, n_feat=5000, nnz_per_row=12,
                                   seed=4))
    common = dict(minibatch=256, nnz_per_row=16, algo="ftrl",
                  kernel="pallas", kernel_dtype="f32")
    if kind == "tcoo":
        nb = 8 * ck.TILE
        lrn = LinearLearner(LinearConfig(num_buckets=nb,
                                         compact_cap=ck.TILE, **common),
                            make_mesh(1, 1))
    else:
        nb = NB
        lrn = LinearLearner(LinearConfig(num_buckets=nb, **common),
                            make_mesh(2, 2))
    blk = next(iter(MinibatchIter(str(p), minibatch_size=256)))

    def counters():
        c = REGISTRY.snapshot()["counters"]
        return (c.get("linear.blocks.chunks", 0),
                c.get("linear.blocks.chunks_run", 0))

    c0 = counters()
    b = lrn.prepare_batch(blk, train=True)
    assert b[0] == kind
    chunks, run = (a - b_ for a, b_ in zip(counters(), c0))
    # an eval batch is not counted: the metric is the train step's
    lrn.prepare_batch(blk, train=False)
    assert counters() == (c0[0] + chunks, c0[1] + run)

    def on_device(stream_is_live, block, kernels):
        ext = np.asarray(ck.block_extents(stream_is_live.reshape(-1),
                                          block))
        return (kernels * len(ext) * (block // ck.CHUNK),
                kernels * int(np.sum(ck.chunks_run(ext, block))))

    if kind == "tcoo":
        tc = b[1]
        # tile_gather and the fused update; pull and push (one stream)
        want = [on_device(jnp.asarray(tc.uniq) != nb, ck.BLK_U, 2),
                on_device(jnp.asarray(tc.coo.val) != 0, ck.BLK, 2)]
    else:
        want = [on_device(jnp.asarray(b[1].sval) != 0, ck.BLK, 2)]
    assert (chunks, run) == tuple(map(sum, zip(*want)))
    assert 0 < run < chunks
