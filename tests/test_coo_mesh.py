"""Mesh-sharded Pallas COO kernels: the tile grid shard_map'ed over the
model axis and rows over the data axis must reproduce the XLA segment-op
path exactly (interpret mode, f32) — the ZPull/ZPush key-sharded layout
of reference async_sgd.h:277-287 on a real mesh."""

import hashlib

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
# 16 tiles: a shard of a 2-wide model axis has 8 for its 4,096 nonzeros
# at capacity, an unsplit table 16: many sparse tiles, and the pack's
# block follows them (ck.mesh_block: 1,024)
NB_SPARSE = 16 * ck.TILE
TABLES = pytest.mark.parametrize("nb", [NB, NB_SPARSE],
                                 ids=["blk4096", "sparse-tiles"])


def _random_coo(rng, nnz, num_rows, num_buckets):
    idx = rng.integers(0, num_buckets, size=nnz).astype(np.int32)
    if num_buckets == NB_SPARSE:
        # 2,300 more in tile 0, so that its run needs more than one block
        # in every cell, and tile 3 empty; the blocks no run claims trail
        # the last tile as spares
        idx[idx // ck.TILE == 3] += ck.TILE
        idx = rng.permutation(np.concatenate(
            [idx, rng.integers(0, 40, size=2300).astype(np.int32)]))
        nnz = len(idx)
    seg = np.sort(rng.integers(0, num_rows, size=nnz)).astype(np.int32)
    val = rng.normal(size=nnz).astype(np.float32)
    return idx, seg, val


def _mesh_pack(idx, seg, val, nb, num_rows, D, M):
    cap = ck.mesh_capacity(4096, D, M)
    blk = ck.mesh_block(cap, nb // M)
    mc = ck.pack_mesh_coo(idx, seg, val, nb, num_rows, D, M, cap, blk)
    assert ck.stream_block(mc.sidx[0, 0], mc.tmap[0, 0]) == blk
    assert mc.dropped_nnz == 0
    if nb == NB:
        assert blk == ck.BLK
    else:
        assert blk == 1024
        n = (mc.sval != 0).reshape(D, M, -1, blk).sum(-1)
        # a run of several blocks (tile 0's), an empty tile's one block
        # (tile 3's), a spare block after the last tile's own
        assert (mc.tmap[:, 0, 1] == 0).any() and n[:, 0, 0].max() == blk
        t3 = mc.tmap[:, 0] == 3
        assert t3.sum() == D and (n[:, 0][t3] == 0).all()
        assert (mc.first[..., -1] == 0).all() and (n[..., -1] == 0).all()
    return mc, blk


def test_mesh_block_follows_a_shards_tile_occupancy():
    """STREAM_TILE where it holds twice a tile's mean at capacity, else
    the one-chip layout's BLK: the four-chip cell's shard (PERF.md §4)
    packs at 1,024, a shard with few tiles for its nonzeros at BLK, and
    nothing between the two (2,048 measured worse than BLK on the chip
    and is not understood: PERF.md §6, PR 49)."""
    cap = ck.mesh_capacity(65536 * 39, 1, 4)
    assert cap == 1277952 and ck.mesh_block(cap, 2**28) == 1024
    assert ck.packed_size(cap, 2**28, blk=1024) == 5472256
    assert ck.mesh_block(ck.mesh_capacity(4096, 2, 2), ck.TILE) == ck.BLK
    assert ck.mesh_block(cap, 2**20) == ck.BLK      # 79,872 a tile
    # never under the tile XLA lays a 1-D stream out in (the chip's
    # compiler refuses a block of 512)
    assert ck.mesh_block(4096, 2**28) == ck.STREAM_TILE == 1024
    # exactly twice the mean is enough, a nonzero more a tile is not; a
    # capacity is whole blocks of both, so what overflows does not
    # depend on the block
    assert ck.mesh_block(2 * ck.BLK, 16 * ck.TILE) == 1024
    assert ck.mesh_block(2 * ck.BLK + 16, 16 * ck.TILE) == ck.BLK
    assert ck.mesh_block(3 * ck.BLK, 16 * ck.TILE) == ck.BLK
    assert cap % 1024 == 0 and cap % ck.BLK == 0


def _jaxprs(dtype, nb, rows, stream):
    S = jax.ShapeDtypeStruct
    f32 = jnp.float32
    return [str(jax.make_jaxpr(f)(*a, *stream)) for f, a in (
        (lambda w, *s: ck.coo_spmv(w, *s, rows, dtype=dtype),
         [S((nb,), f32)]),
        (lambda d, *s: ck.coo_spmv_t(d, *s, nb, dtype=dtype),
         [S((rows,), f32)]),
        (lambda d, a, *s: ck.coo_spmv_t(d, *s, nb, dtype=dtype, acc=a),
         [S((rows,), f32), S((nb,), f32)]))]


# sha256 of the three jaxprs' text, taken from the parent of PR 49
# (3ecc448, where the block is the module's BLK and read from nowhere)
# at _jaxprs' shapes. The text holds no path and no address. A PR that
# changes what pull or push trace to at 4,096 pins its own.
_PARENT_JAXPRS = {
    "bf16": "4c246af0fefe2e7cc2ddd65e5d4b39db"
            "3550c0077da4fa373a799f64f3253f94",
    "f32": "56cce9933989bd7478117f11aabdfc98"
           "bc2035ddff00e9dd1822ed13daefe69c",
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_kernels_read_the_block_from_the_stream(dtype, request):
    """A layout carries its own block. On a stream packed at BLK (the
    one-chip kinds, DiFacto's scalar side, the batch solver's passes)
    pull, push and push-into-a-sum trace to the text the parent's
    kernels traced to, where BLK was the module's constant; on one
    packed at 1,024 the three streams go by blocks of 1,024 and no
    slice of the body is wider."""
    nb, rows, cap = 4 * ck.TILE, 512, 4 * ck.BLK
    S = jax.ShapeDtypeStruct

    def stream(blk):
        p = ck.packed_size(cap, nb, blk=blk)
        return ([S((p,), jnp.int32)] * 2 + [S((p,), jnp.float32)]
                + [S((p // blk,), jnp.int32)] * 2)

    got = _jaxprs(dtype, nb, rows, stream(ck.BLK))
    digest = hashlib.sha256("".join(got).encode()).hexdigest()
    assert digest == _PARENT_JAXPRS[request.node.callspec.id]
    for big, text in zip(got, _jaxprs(dtype, nb, rows, stream(1024))):
        assert "i32[4096]" in big and "i32[1024]" not in big
        assert "i32[1024]" in text and "[4096]" not in text


@TABLES
def test_pack_mesh_coo_partitions_exactly(nb):
    rng = np.random.default_rng(0)
    num_rows, D, M = 256, 2, 2
    idx, seg, val = _random_coo(rng, 1000, num_rows, nb)
    mc, _ = _mesh_pack(idx, seg, val, nb, num_rows, D, M)
    # every live nonzero lands in exactly one cell with local coordinates
    total = 0
    for d in range(D):
        for m in range(M):
            live = mc.sval[d, m] != 0
            total += int(live.sum())
            assert (mc.sidx[d, m][live] < nb // M).all()
            assert (mc.sseg[d, m][live] < num_rows // D).all()
    assert total == int((val != 0).sum())


@TABLES
@pytest.mark.parametrize("D,M", [(2, 2), (2, 1), (1, 2)])
def test_mesh_spmv_matches_dense(D, M, nb):
    rng = np.random.default_rng(1)
    num_rows = 256
    idx, seg, val = _random_coo(rng, 2000, num_rows, nb)
    w = rng.normal(size=nb).astype(np.float32)
    d_vec = rng.normal(size=num_rows).astype(np.float32)

    mesh = make_mesh(D, M)
    mc, _ = _mesh_pack(idx, seg, val, nb, num_rows, D, M)
    args = tuple(jnp.asarray(x) for x in
                 (mc.sidx, mc.sseg, mc.sval, mc.tmap, mc.first))

    xw = ck.mesh_coo_spmv(mesh, jnp.asarray(w), *args, num_rows)
    want_xw = np.zeros(num_rows, np.float32)
    np.add.at(want_xw, seg, val * w[idx])
    np.testing.assert_allclose(np.asarray(xw), want_xw, rtol=2e-5,
                               atol=1e-5)

    g = ck.mesh_coo_spmv_t(mesh, jnp.asarray(d_vec), *args, nb)
    want_g = np.zeros(nb, np.float32)
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
@TABLES
def test_pack_mesh_coo_runs_are_a_prefix(nb):
    """Every cell's blocks hold their live nonzeros first: zero-valued
    input triples (padding) are dropped before the split, so a block's
    extent is exactly its run's length and the host's sampled chunk
    count is exact, at whichever block the shard is packed."""
    rng = np.random.default_rng(5)
    num_rows, D, M = 256, 2, 2
    idx, seg, val = _random_coo(rng, 3000, num_rows, nb)
    idx[:1500] = rng.integers(0, 40, size=1500)      # a hot run over CHUNK
    val[rng.random(len(val)) < 0.2] = 0.0            # input padding triples
    mc, blk = _mesh_pack(idx, seg, val, nb, num_rows, D, M)
    live = (mc.sval != 0).reshape(-1, blk)
    n = live.sum(1)
    assert (live == (np.arange(blk)[None, :] < n[:, None])).all()
    assert n.sum() == int((val != 0).sum()) and n.max() > ck.CHUNK
    ext = np.asarray(ck.block_extents(
        jnp.asarray(mc.sval.reshape(-1)) != 0, blk))
    np.testing.assert_array_equal(ext, n)
    chunks, run = ck.host_chunk_counts(mc.sval, 0, blk)
    assert chunks == len(n) * (blk // ck.CHUNK)
    assert run == int(np.sum(ck.chunks_run(n, blk)))


@TABLES
@pytest.mark.parametrize("D,M", [(2, 2), (1, 2)])
def test_mesh_spmv_equals_full_width(D, M, nb, monkeypatch):
    """Each shard's kernels bound their work by their own blocks'
    extents (derived inside shard_map): same results as the full-width
    bodies to summation order."""
    rng = np.random.default_rng(21)
    num_rows = 256
    idx, seg, val = _random_coo(rng, 2500, num_rows, nb)
    idx[:1200] = rng.integers(0, 30, size=1200)
    mesh = make_mesh(D, M)
    mc, _ = _mesh_pack(idx, seg, val, nb, num_rows, D, M)
    args = tuple(jnp.asarray(a) for a in
                 (mc.sidx, mc.sseg, mc.sval, mc.tmap, mc.first))
    w = jnp.asarray(rng.normal(size=nb).astype(np.float32))
    d_vec = jnp.asarray(rng.normal(size=num_rows).astype(np.float32))

    def both():
        return (np.asarray(ck.mesh_coo_spmv(mesh, w, *args, num_rows)),
                np.asarray(ck.mesh_coo_spmv_t(mesh, d_vec, *args, nb)))

    got = both()
    monkeypatch.setattr(ck, "block_extents", lambda live, blk: jnp.full(
        (live.shape[0] // blk,), blk, jnp.int32))
    want = both()
    for a, b in zip(got, want):
        assert np.max(np.abs(a - b)) <= 1e-6 * np.max(np.abs(b))


def test_mesh_slots_counter_and_token_carry_the_block(tmp_path, monkeypatch):
    """`linear.mesh.slots` rises by D x M x P a packed batch, P at the
    block the learner chose with its shard capacity, and that block is
    part of the pack cache's key: an entry packed at another misses."""
    from wormhole_tpu.obs.metrics import REGISTRY

    p = tmp_path / "t.libsvm"
    p.write_text(synth_libsvm_text(n_rows=256, n_feat=5000, nnz_per_row=12,
                                   seed=4))
    blk = next(iter(MinibatchIter(str(p), minibatch_size=256)))
    cfg = LinearConfig(minibatch=256, nnz_per_row=16, algo="ftrl",
                       kernel="pallas", kernel_dtype="f32",
                       num_buckets=NB_SPARSE)

    def slots():
        return REGISTRY.snapshot()["counters"].get("linear.mesh.slots", 0)

    lrn = LinearLearner(cfg, make_mesh(2, 2))
    assert (lrn._shard_cap, lrn._shard_blk) == (4096, 1024)
    for n in (1, 2):
        s0 = slots()
        mc = lrn.prepare_batch(blk, train=n == 1)[1]
        assert mc.sval.shape == (2, 2, 4096 + 8 * 1024)
        assert slots() - s0 == 2 * 2 * (4096 + 8 * 1024)
    monkeypatch.setattr(ck, "mesh_block", lambda cap, nb_m: ck.BLK)
    other = LinearLearner(cfg, make_mesh(2, 2))
    assert other._shard_blk == ck.BLK
    a, b = lrn.pack_cache_token(), other.pack_cache_token()
    assert a is not None and len(a) == len(b)
    assert [(x, y) for x, y in zip(a, b) if x != y] == [(1024, ck.BLK)]


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
        want = [on_device(jnp.asarray(b[1].sval) != 0, lrn._shard_blk, 2)]
    assert (chunks, run) == tuple(map(sum, zip(*want)))
    assert 0 < run < chunks
