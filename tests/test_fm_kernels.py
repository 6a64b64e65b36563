"""FM (SpMM) hot path: the row-major forward (gather + reshape-reduce)
and the fm_push_contrib tile scatter must match the per-nnz reference
accumulation exactly in f32 interpret mode — the FM hot path of
reference difacto loss.h:53-157."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from wormhole_tpu.ops import coo_kernels as ck


def _pack_v(rng, nnz, num_rows, vrows, cap):
    idx = rng.integers(0, vrows, size=nnz).astype(np.int64)
    seg = rng.integers(0, num_rows, size=nnz).astype(np.int32)
    val = rng.normal(size=nnz).astype(np.float32)
    p = ck.pack_sorted_coo(idx, seg, val, vrows, capacity=cap,
                           tile=ck.TILE_HI)
    return idx, seg, val, p


def test_fm_forward_row_major_matches_reference():
    """The row-major FM forward (XLA gather + reshape-reduce over a
    [rows, nnz_per_row] padded layout — models/difacto.forward) must
    reproduce the per-nnz accumulation exactly."""
    rng = np.random.default_rng(5)
    num_rows, vrows, dim, W = 256, 4 * ck.TILE_HI, 8, 12
    nnz = num_rows * W
    idx = rng.integers(0, vrows, size=nnz).astype(np.int64)
    seg = np.repeat(np.arange(num_rows, dtype=np.int32), W)
    val = rng.normal(size=nnz).astype(np.float32)
    V = rng.normal(size=(vrows + 1, dim)).astype(np.float32)
    V[-1] = 0.0  # the appended sentinel zero row

    V_nnz = np.asarray(jnp.take(jnp.asarray(V), jnp.asarray(idx), axis=0))
    p = val[:, None] * V_nnz
    xv = p.reshape(num_rows, W, dim).sum(1)
    x2 = (p * p).reshape(num_rows, W, dim).sum(1)

    xv_ref = np.zeros((num_rows, dim), np.float32)
    x2_ref = np.zeros((num_rows, dim), np.float32)
    for j in range(nnz):
        xv_ref[seg[j]] += val[j] * V[idx[j]]
        x2_ref[seg[j]] += (val[j] * V[idx[j]]) ** 2
    np.testing.assert_allclose(xv, xv_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(x2, x2_ref, rtol=1e-4, atol=1e-4)


def test_fm_push_contrib_matches_reference():
    """fm_push_contrib (the row-major path's tile scatter with
    precomputed a = c*xv[seg], b = c*val) must equal the dense per-nnz
    dV accumulation; padding entries (val = 0) must vanish; the touch
    column is summed by row beside it."""
    rng = np.random.default_rng(6)
    num_rows, vrows, dim, nnz = 256, 4 * ck.TILE_HI, 8, 3000
    idx, seg, val, p = _pack_v(rng, nnz, num_rows, vrows, 8192)
    V = rng.normal(size=(vrows, dim)).astype(np.float32)
    d = rng.normal(size=num_rows).astype(np.float32)

    xv_ref = np.zeros((num_rows, dim), np.float32)
    for j in range(nnz):
        xv_ref[seg[j]] += val[j] * V[idx[j]]
    # kernel operands from the packed (sorted+padded) layout: each
    # nonzero's batch row and value; padding entries carry val == 0 and
    # every other live nonzero stands for one that is not admitted
    vv = np.where(np.arange(len(p.val)) % 2 == 0, p.val, 0.0)
    gV, touched = ck.fm_push_contrib(
        jnp.asarray(V), jnp.asarray(xv_ref), jnp.asarray(d),
        jnp.asarray(p.seg),
        jnp.asarray(vv.astype(np.float32)), jnp.asarray(p.idx),
        jnp.asarray(p.tmap), jnp.asarray(p.first), dtype=jnp.float32)
    gV = np.asarray(gV)
    np.testing.assert_array_equal(
        np.asarray(touched),
        np.bincount(p.idx, weights=vv != 0, minlength=vrows))
    # the reference below: over the admitted nonzeros only
    adm = np.zeros(nnz, bool)
    order = np.argsort(idx, kind="stable")
    adm[order] = (vv != 0)[p.val != 0]

    gV_ref = np.zeros((vrows, dim), np.float32)
    for j in np.flatnonzero(adm):
        gV_ref[idx[j]] += d[seg[j]] * val[j] * (
            xv_ref[seg[j]] - val[j] * V[idx[j]])
    np.testing.assert_allclose(gV, gV_ref, rtol=1e-3, atol=1e-3)


def test_pack_sorted_coo_custom_tile():
    """tile=TILE_HI packs runs at embedding-tile granularity."""
    rng = np.random.default_rng(7)
    vrows = 4 * ck.TILE_HI
    idx = rng.integers(0, vrows, size=1000).astype(np.int64)
    seg = np.zeros(1000, np.int32)
    val = np.ones(1000, np.float32)
    p = ck.pack_sorted_coo(idx, seg, val, vrows, capacity=4096,
                           tile=ck.TILE_HI)
    live = p.val != 0
    # every live entry sits in a block whose tmap covers its tile
    blk_of = np.arange(len(p.idx)) // ck.BLK
    assert (p.idx[live] // ck.TILE_HI == p.tmap[blk_of[live]]).all()
