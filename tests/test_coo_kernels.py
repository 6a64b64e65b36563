"""Pallas COO kernels vs the XLA segment-op reference implementations.

Runs in interpret mode on the CPU test mesh; the same code compiles to
Mosaic on TPU (tests/test_tpu_aot.py compiles them for v5e; chip_smoke.py
and the benchmark run them there).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wormhole_tpu.ops import coo_kernels as ck
from wormhole_tpu.ops import fused_update as fu
from wormhole_tpu.ops.coo_kernels import (
    BLK, TILE, pack_sorted_coo, packed_size, coo_spmv, coo_spmv_t,
)
from wormhole_tpu.ops.spmv import spmv, spmv_t


def make_batch(num_rows, nnz_per_row, num_buckets, seed=0, skew=False):
    rng = np.random.default_rng(seed)
    cap = num_rows * nnz_per_row
    if skew:
        # power-law-ish keys: most mass on few buckets (criteo shape)
        raw = rng.zipf(1.3, size=cap)
        idx = (raw % num_buckets).astype(np.int32)
    else:
        idx = rng.integers(0, num_buckets, size=cap).astype(np.int32)
    seg = np.repeat(np.arange(num_rows, dtype=np.int32), nnz_per_row)
    val = rng.normal(size=cap).astype(np.float32)
    val[rng.random(cap) < 0.1] = 0.0  # padding-like entries
    return seg, idx, val


@pytest.mark.parametrize("skew", [False, True])
def test_pull_matches_xla(skew):
    num_rows, nnz, nb = 256, 13, 2 * TILE
    seg, idx, val = make_batch(num_rows, nnz, nb, seed=1, skew=skew)
    w = np.random.default_rng(2).normal(size=nb).astype(np.float32)

    p = pack_sorted_coo(idx, seg, val, nb)
    got = coo_spmv(jnp.asarray(w), jnp.asarray(p.idx), jnp.asarray(p.seg),
                   jnp.asarray(p.val), jnp.asarray(p.tmap),
                   jnp.asarray(p.first), num_rows)
    want = spmv(jnp.asarray(seg), jnp.asarray(idx), jnp.asarray(val),
                jnp.asarray(w), num_rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("skew", [False, True])
def test_push_matches_xla(skew):
    num_rows, nnz, nb = 256, 13, 2 * TILE
    seg, idx, val = make_batch(num_rows, nnz, nb, seed=3, skew=skew)
    d = np.random.default_rng(4).normal(size=num_rows).astype(np.float32)

    p = pack_sorted_coo(idx, seg, val, nb)
    got = coo_spmv_t(jnp.asarray(d), jnp.asarray(p.idx), jnp.asarray(p.seg),
                     jnp.asarray(p.val), jnp.asarray(p.tmap),
                     jnp.asarray(p.first), nb)
    want = spmv_t(jnp.asarray(seg), jnp.asarray(idx), jnp.asarray(val),
                  jnp.asarray(d), nb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_packed_size_is_static():
    cap, nb = 999, TILE * 3
    assert packed_size(cap, nb) == (cap // BLK + 3) * BLK
    seg, idx, val = make_batch(37, 27, nb, seed=5)
    p = pack_sorted_coo(idx, seg, val, nb)
    assert p.idx.shape[0] == packed_size(len(idx), nb)
    assert p.num_blocks == p.idx.shape[0] // BLK
    # runs per tile are contiguous and tiles appear in order
    assert (np.diff(p.tmap) >= 0).all()
    assert p.first.sum() == nb // TILE  # every tile opened exactly once


def test_pack_concentrated_single_tile():
    # all keys in one tile: other tiles still get a zeroing block
    nb = 4 * TILE
    num_rows = 128
    rng = np.random.default_rng(7)
    idx = rng.integers(0, TILE, size=num_rows * 5).astype(np.int32)
    seg = np.repeat(np.arange(num_rows, dtype=np.int32), 5)
    val = rng.normal(size=len(idx)).astype(np.float32)
    p = pack_sorted_coo(idx, seg, val, nb)
    d = rng.normal(size=num_rows).astype(np.float32)
    got = coo_spmv_t(jnp.asarray(d), jnp.asarray(p.idx), jnp.asarray(p.seg),
                     jnp.asarray(p.val), jnp.asarray(p.tmap),
                     jnp.asarray(p.first), nb)
    want = spmv_t(jnp.asarray(seg), jnp.asarray(idx), jnp.asarray(val),
                  jnp.asarray(d), nb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    # untouched tiles are exactly zero
    assert not np.asarray(got[TILE:]).any()


# ------------------------------------------------- live-extent bodies
# The kernels build their one-hot operands only over a block's live
# prefix (ops/coo_kernels._live_chunks). The reference is the same
# kernel with every extent forced to a whole block: the full-width body,
# which is what the kernels ran before they looked at the extent.

CH, BU = ck.CHUNK, ck.BLK_U
# live slots of the one tile the case is about: empty, one slot, exactly
# one chunk, one over a chunk, half full + 1 (the full-width body's
# first), full, and a run that spills into a second block
U_FILLS = [0, 1, CH, CH + 1, BU // 2, BU // 2 + 1, BU, BU + 5]
C_FILLS = [0, 1, CH, CH + 1, BLK // 2, BLK // 2 + 1, BLK, BLK + 7]


@pytest.fixture
def full_width(monkeypatch):
    """Call it, and from then on the wrappers hand every block a
    whole-block extent: the kernels take their full-width body."""
    def whole(live, blk):
        return jnp.full((live.shape[0] // blk,), blk, jnp.int32)

    def force():
        monkeypatch.setattr(ck, "block_extents", whole)
        monkeypatch.setattr(fu, "block_extents", whole)
    return force


def _tile_slots(fill, nb_tiles=3, u_cap=8 * BU):
    """fill keys in tile 0, none in tile 1, 3 in tile 2; u_cap leaves
    trailing spare blocks."""
    rng = np.random.default_rng(fill)
    keys = np.concatenate([
        np.sort(rng.choice(TILE, size=fill, replace=False)),
        2 * TILE + np.array([5, 77, 4000])]).astype(np.int64)
    return ck.assign_tile_slots(keys, TILE, u_cap, nb_tiles * TILE), keys


def _assert_live_prefix(stream_is_live, blk):
    """In every block the live slots come first: no hole before one."""
    live = np.asarray(stream_is_live).reshape(-1, blk)
    n = live.sum(1)
    assert (live == (np.arange(blk)[None, :] < n[:, None])).all()
    return n


@pytest.mark.parametrize("fill", U_FILLS)
def test_assign_tile_slots_live_slots_are_a_prefix(fill):
    nb = 3 * TILE
    ts, keys = _tile_slots(fill)
    n = _assert_live_prefix(ts.uniq != nb, BU)
    assert n.sum() == len(keys) == ts.num_uniq
    used = -(-fill // BU) + 1 if fill else 1
    assert not n[used:].any()                  # trailing spare blocks
    # the extents the wrappers derive are those prefix lengths, and the
    # host's sampled count is the chunks below them
    ext = np.asarray(ck.block_extents(jnp.asarray(ts.uniq) != nb, BU))
    np.testing.assert_array_equal(ext, n)
    chunks, run = ck.host_chunk_counts(ts.uniq, nb, BU)
    assert chunks == len(n) * (BU // CH)
    assert run == int(np.sum(ck.chunks_run(ext, BU)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("fill", U_FILLS)
def test_tile_gather_equals_full_width(fill, dtype, full_width):
    nb = 3 * TILE
    ts, keys = _tile_slots(fill)
    w = np.random.default_rng(1).normal(size=nb).astype(np.float32)
    args = (jnp.asarray(w).reshape(-1, ck.LANES), jnp.asarray(ts.uniq),
            jnp.asarray(ts.tmap_u))
    got = np.asarray(ck.tile_gather(*args, dtype=dtype))
    full_width()
    want = np.asarray(ck.tile_gather(*args, dtype=dtype))
    np.testing.assert_array_equal(got, want)   # bit-equal
    live = ts.uniq != nb
    assert not got[~live].any()
    if dtype == jnp.float32:
        np.testing.assert_allclose(got[live], w[ts.uniq[live]], rtol=1e-6)


_HYPER = dict(lr_eta=0.3, lr_beta=1.0, lambda_l1=0.2, lambda_l2=0.01)


def _update_case(algo, fill, with_add):
    nb = 3 * TILE
    ts, keys = _tile_slots(fill)
    rng = np.random.default_rng(100 + fill)
    names = {"ftrl": ("z", "n", "w"), "adagrad": ("n", "w"),
             "sgd": ("w",)}[algo]
    state = {k: (np.abs(rng.normal(size=nb)) if k == "n"
                 else rng.normal(size=nb)).astype(np.float32)
             for k in names}
    live = ts.uniq != nb
    g = np.where(live, rng.normal(size=len(live)), 0).astype(np.float32)
    if algo == "ftrl":      # w is the derived table, as a run leaves it
        state["w"] = np.array(fu.ftrl_weight(
            state["z"], np.sqrt(state["n"]), **_HYPER))
    kw = dict(_HYPER, dtype=jnp.float32)
    adds = None
    if with_add:   # counts over 256: the additive scatter stays f32
        state["cnt"] = rng.integers(0, 9, size=nb).astype(np.float32)
        adds = np.where(live, rng.integers(1, 400, size=len(live)),
                        0).astype(np.float32)
        kw.update(add_table="cnt", add_values=jnp.asarray(adds))

    def run():
        st, nw = fu.scatter_update(
            algo, {k: jnp.asarray(v) for k, v in state.items()},
            jnp.asarray(g), jnp.asarray(ts.uniq), jnp.asarray(ts.tmap_u),
            jnp.asarray(ts.first_u), jnp.asarray(ts.last_u), **kw)
        return {k: np.asarray(v) for k, v in st.items()}, float(nw)
    return run, state, ts, adds, g


@pytest.mark.parametrize("algo,with_add", [
    ("ftrl", False), ("adagrad", False), ("sgd", False), ("ftrl", True)])
@pytest.mark.parametrize("fill", U_FILLS)
def test_fused_update_equals_full_width(fill, algo, with_add, full_width):
    run, before, ts, adds, _ = _update_case(algo, fill, with_add)
    got, nw = run()
    full_width()
    want, nw_want = run()
    assert nw == nw_want
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])   # bit-equal
    # the untouched tile came through as it was, and touched keys moved
    for k in got:
        np.testing.assert_array_equal(got[k][TILE:2 * TILE],
                                      before[k][TILE:2 * TILE])
    live = ts.uniq != 3 * TILE
    moved = got["w"][ts.uniq[live]] != before["w"][ts.uniq[live]]
    assert moved.any()
    if with_add:
        want_cnt = before["cnt"].copy()
        want_cnt[ts.uniq[live]] += adds[live]
        np.testing.assert_array_equal(got["cnt"], want_cnt)


def _xla_update(before, ts, g, adds):
    """models/linear._update over the whole tables, on the gradient the
    kernel's scatter forms: the dense path's twin of the case."""
    from wormhole_tpu.models.linear import LinearConfig, _update

    nb = 3 * TILE
    dense = np.zeros(nb + 1, np.float32)
    dense[ts.uniq] = g          # the sentinel's slot is cut off below
    state = {k: jnp.asarray(before[k]) for k in ("z", "n", "w")}
    want, nw = jax.jit(lambda st, gd: _update(
        "ftrl", st, gd, 1.0, LinearConfig(algo="ftrl", **_HYPER)))(
            state, jnp.asarray(dense[:nb]))
    want = {k: np.asarray(v) for k, v in want.items()}
    if adds is not None:
        want["cnt"] = before["cnt"].copy()
        want["cnt"][ts.uniq[ts.uniq != nb]] += adds[ts.uniq != nb]
    return want, float(nw)


@pytest.mark.parametrize("with_add", [False, True])
@pytest.mark.parametrize("fill", U_FILLS)
def test_fused_ftrl_update_equals_the_dense_rule(fill, with_add):
    """From a state a run can leave (w the derived table), the kernel
    and models/linear._update write the same z, n, w and count the same
    new_w: the two sites of the one rule."""
    run, before, ts, adds, g = _update_case("ftrl", fill, with_add)
    got, nw = run()
    want, nw_want = _xla_update(before, ts, g, adds)
    assert nw == nw_want
    for k in want:      # f32 to the last place: XLA fuses the two apart
        np.testing.assert_allclose(got[k], want[k], rtol=3e-7, atol=0,
                                   err_msg=k)


@pytest.mark.parametrize("with_add", [False, True])
@pytest.mark.parametrize("fill", U_FILLS)
def test_fused_ftrl_update_does_not_read_w(fill, with_add):
    """FTRL's w is written and never read: garbage in the stored w of a
    touched tile changes nothing the step writes there, and garbage in
    an untouched tile comes through in place (the alias holds)."""
    run, state, ts, _, _ = _update_case("ftrl", fill, with_add)
    want, nw_want = run()
    junk = np.random.default_rng(7).normal(size=TILE).astype(np.float32)
    junk[::5] = 0
    state["w"][2 * TILE:] = junk            # touched: keys 5, 77, 4000
    state["w"][TILE:2 * TILE] = junk        # no key of the batch
    got, nw = run()
    assert nw == nw_want        # counted from the derived weight too
    for k in want:
        np.testing.assert_array_equal(got[k][2 * TILE:], want[k][2 * TILE:])
        np.testing.assert_array_equal(got[k][:TILE], want[k][:TILE])
    np.testing.assert_array_equal(got["w"][TILE:2 * TILE], junk)


def _coo_case(fill, num_rows=256):
    """fill nonzeros in tile 0 (a tenth of them explicit zeros, as input
    padding triples are), none in tile 1, 3 in tile 2."""
    nb = 3 * TILE
    rng = np.random.default_rng(200 + fill)
    idx = np.concatenate([rng.integers(0, TILE, size=fill),
                          2 * TILE + np.array([5, 77, 4000])]).astype(
                              np.int32)
    seg = rng.integers(1, num_rows, size=len(idx)).astype(np.int32)
    val = rng.normal(size=len(idx)).astype(np.float32)
    val[:fill][rng.random(fill) < 0.1] = 0.0
    return idx, seg, val, nb


@pytest.mark.parametrize("fill", C_FILLS)
def test_pack_sorted_coo_runs_are_a_prefix(fill):
    idx, seg, val, nb = _coo_case(fill)
    p = pack_sorted_coo(idx, seg, val, nb, capacity=2 * BLK)
    # every input triple, zero-valued ones included, has seg >= 1 and
    # the pack's own padding seg == 0: no padding before an input triple
    n = _assert_live_prefix(p.seg != 0, BLK)
    assert n.sum() == len(idx)
    assert not p.val[p.seg == 0].any()
    # so past a block's extent (one past its last val != 0) all is zero,
    # and the extent is at most the run's length in the block
    ext = np.asarray(ck.block_extents(jnp.asarray(p.val) != 0, BLK))
    assert (ext <= n).all()
    chunks, run = ck.host_chunk_counts(p.val, 0, BLK)
    assert chunks == p.num_blocks * (BLK // CH)
    assert run <= int(np.sum(ck.chunks_run(n, BLK)))


@pytest.mark.parametrize("fill", C_FILLS)
def test_pull_and_push_equal_full_width(fill, full_width, dtype=jnp.float32):
    num_rows = 256
    idx, seg, val, nb = _coo_case(fill, num_rows)
    p = pack_sorted_coo(idx, seg, val, nb, capacity=2 * BLK)
    stream = [jnp.asarray(x) for x in (p.idx, p.seg, p.val, p.tmap,
                                       p.first)]
    rng = np.random.default_rng(9)
    w = jnp.asarray(rng.normal(size=nb).astype(np.float32))
    d = jnp.asarray(rng.normal(size=num_rows).astype(np.float32))
    xw = np.asarray(coo_spmv(w, *stream, num_rows, dtype=dtype))
    g = np.asarray(coo_spmv_t(d, *stream, nb, dtype=dtype))
    full_width()
    xw_want = np.asarray(coo_spmv(w, *stream, num_rows, dtype=dtype))
    g_want = np.asarray(coo_spmv_t(d, *stream, nb, dtype=dtype))
    # the same products summed in another order
    for got, want in ((xw, xw_want), (g, g_want)):
        scale = max(float(np.max(np.abs(want))), 1e-30)
        assert np.max(np.abs(got - want)) <= 1e-6 * scale
    assert not g[TILE:2 * TILE].any()          # the empty tile is zeroed
    want = spmv(jnp.asarray(seg), jnp.asarray(idx), jnp.asarray(val), w,
                num_rows)
    np.testing.assert_allclose(xw, np.asarray(want), rtol=1e-5, atol=1e-4)


# ------------------------------------------- the native tcoo pack (PR 29)
# pack_tile_coo has two bodies: one call of the native core
# (native/src/pack.cc: one radix sort, every array written in its order)
# and the numpy body (localize, assign_tile_slots, pack_sorted_coo). The second is the oracle: every field bit-equal.

def _rows_batch(rng, live_per_row, num_buckets, pad_to=None):
    """CSR-ordered triples with `live_per_row[r]` entries in row r, then
    padding triples as to_device_batch makes them (val 0, idx 0, seg the
    last row) up to `pad_to`."""
    rows = len(live_per_row)
    seg = np.repeat(np.arange(rows, dtype=np.int32), live_per_row)
    n = len(seg)
    idx = rng.integers(0, num_buckets, n).astype(np.int32)
    val = rng.normal(size=n).astype(np.float32)
    if pad_to is not None:
        pad = pad_to - n
        seg = np.concatenate([seg, np.full(pad, rows - 1, np.int32)])
        idx = np.concatenate([idx, np.zeros(pad, np.int32)])
        val = np.concatenate([val, np.zeros(pad, np.float32)])
    return idx, seg, val


def _keys_batch(keys, rng, repeat=2):
    """Each key `repeat` times, shuffled, one entry a row."""
    idx = rng.permutation(np.repeat(np.asarray(keys, np.int32), repeat))
    n = len(idx)
    return idx, np.arange(n, dtype=np.int32), rng.normal(
        size=n).astype(np.float32)


def _tile_pack_case(name):
    """kwargs of pack_tile_coo for a named case."""
    rng = np.random.default_rng(29)
    nb = 128 * TILE
    U = ck.BLK_U
    roomy = 4 * TILE               # 256 update blocks: nothing is cut
    if name == "fixed_width":      # the same count a row, the capacity
        idx, seg, val = _rows_batch(rng, [8] * 64, nb)    # filled
        return dict(idx=idx, seg=seg, val=val, num_buckets=nb, u_cap=roomy,
                    capacity=512)
    if name == "ragged_padded":
        live = rng.integers(0, 9, 64)
        idx, seg, val = _rows_batch(rng, live, nb, pad_to=512)
        val[rng.random(512) < 0.1] = 0.0          # explicit zeros too
        return dict(idx=idx, seg=seg, val=val, num_buckets=nb, u_cap=roomy,
                    capacity=512)
    if name == "long_rows":        # rows 3 and 9 hold 7 and 5 live
        live = [2] * 16            # entries where the others hold 2
        live[3], live[9] = 7, 5
        idx, seg, val = _rows_batch(rng, live, nb, pad_to=64)
        return dict(idx=idx, seg=seg, val=val, num_buckets=nb, u_cap=roomy,
                    capacity=64)
    if name in ("u_cap_truncates_boundary_tile", "u_cap_cuts_at_a_tile"):
        # u_cap = TILE is 64 update blocks. 63 tiles of 3 keys take 63;
        # tile 63's 1,500 keys want 2 and get 1 (1,024 kept), tile 64
        # is cut whole. Or 64 tiles fill it exactly and tile 64 goes.
        trunc = name == "u_cap_truncates_boundary_tile"
        keys = [t * TILE + 11 * k for t in range(63 if trunc else 64)
                for k in range(3)]
        if trunc:
            keys += list(63 * TILE + 5 * np.arange(1500))
        keys += [64 * TILE + k for k in range(5)]
        idx, seg, val = _keys_batch(keys, rng)
        val[rng.random(len(val)) < 0.2] = 0.0     # not counted as dropped
        return dict(idx=idx, seg=seg, val=val, num_buckets=nb, u_cap=TILE,
                    capacity=len(idx))
    if name == "cut_leaves_exact_rows":
        # the kept entries alone are exactly two a row in row order,
        # with cut entries lying between them
        keys = np.repeat(np.arange(64) * TILE + 5, 2)
        seg = np.repeat(np.arange(64, dtype=np.int32), 2)
        at = np.sort(rng.integers(0, 129, 9))
        idx = np.insert(keys, at, 70 * TILE + np.arange(9)).astype(np.int32)
        seg = np.insert(seg, at, 7).astype(np.int32)
        return dict(idx=idx, seg=seg, val=np.ones(len(idx), np.float32),
                    num_buckets=nb, u_cap=TILE, capacity=len(idx))
    if name in ("empty", "empty_with_capacity"):
        z = np.zeros(0, np.int32)
        return dict(idx=z, seg=z, val=np.zeros(0, np.float32),
                    num_buckets=nb, u_cap=TILE,
                    capacity=None if name == "empty" else 2 * BLK)
    if name == "tile_edges_and_block_runs":
        # a tile's first and last bucket (the table's too), a run of
        # exactly BLK_U keys in one tile and of BLK_U + 1 in another
        keys = [0, TILE - 1, TILE, 5 * TILE - 1, 5 * TILE, nb - TILE,
                nb - 1]
        keys += list(9 * TILE + 3 * np.arange(U))
        keys += list(20 * TILE + TILE - 1 - 7 * np.arange(U + 1))
        idx, seg, val = _keys_batch(keys, rng)
        return dict(idx=idx, seg=seg, val=val, num_buckets=nb, u_cap=roomy,
                    capacity=2 * len(idx))
    if name == "duplicate_keys_across_rows":
        # five keys, 600 entries: equal keys keep their input order
        live = [6] * 100
        idx, seg, val = _rows_batch(rng, live, nb)
        idx = rng.choice(np.array([3, TILE + 1, TILE + 2, 9 * TILE, nb - 1],
                                  np.int32), 600)
        return dict(idx=idx, seg=seg, val=val, num_buckets=nb, u_cap=roomy,
                    capacity=600)
    if name == "one_key":          # every digit constant: no sort pass
        idx, seg, val = _rows_batch(rng, [4] * 32, nb)
        return dict(idx=np.full_like(idx, 3 * TILE + 17), seg=seg, val=val,
                    num_buckets=nb, u_cap=roomy, capacity=128)
    if name == "ragged_unpadded":
        idx, seg, val = _rows_batch(rng, rng.integers(0, 9, 64), nb)
        return dict(idx=idx, seg=seg, val=val, num_buckets=nb, u_cap=roomy,
                    capacity=512)
    if name == "ids_up_to_2p31":   # 31 bits of key: three 11-bit digits
        top = 2**31 - TILE
        idx, seg, val = _rows_batch(rng, [8] * 64, 64 * TILE)
        idx[::2] += top - 64 * TILE               # the table's two ends
        idx[:3] = top - 1, 0, top - 1
        return dict(idx=idx, seg=seg, val=val, num_buckets=top, u_cap=roomy,
                    capacity=512)
    if name == "criteo_shape":
        # skewed keys over 64 tiles, a compact domain of 16 tiles, spare
        # capacity: what a batch of the benchmark looks like, smaller
        rows, width = 4096, 39
        raw = rng.zipf(1.2, rows * width).astype(np.uint64)
        idx = ((raw * np.uint64(0x9E3779B97F4A7C15)) % np.uint64(64 * TILE)
               ).astype(np.int32)
        seg = np.repeat(np.arange(rows, dtype=np.int32), width)
        return dict(idx=idx, seg=seg, val=np.ones(rows * width, np.float32),
                    num_buckets=64 * TILE, u_cap=16 * TILE,
                    capacity=rows * width + 3 * BLK)
    raise KeyError(name)


_TILE_PACK_CASES = [
    "fixed_width", "ragged_padded", "long_rows",
    "u_cap_truncates_boundary_tile", "u_cap_cuts_at_a_tile",
    "cut_leaves_exact_rows", "empty", "empty_with_capacity",
    "tile_edges_and_block_runs", "duplicate_keys_across_rows", "one_key",
    "ragged_unpadded", "ids_up_to_2p31", "criteo_shape"]


def _numpy_body(monkeypatch, **kw):
    """pack_tile_coo with the native pass out of reach."""
    from wormhole_tpu import native

    with monkeypatch.context() as m:
        m.setattr(native, "pack_tile_coo", lambda *a, **k: None)
        return ck.pack_tile_coo(**kw)


def _assert_same_bits(got, want, path="TileCOO"):
    import dataclasses

    if dataclasses.is_dataclass(want):
        assert type(got) is type(want), path
        for f in dataclasses.fields(want):
            _assert_same_bits(getattr(got, f.name), getattr(want, f.name),
                              f"{path}.{f.name}")
    elif isinstance(want, np.ndarray):
        assert got.dtype == want.dtype and got.shape == want.shape, (
            path, got.dtype, want.dtype, got.shape, want.shape)
        assert got.tobytes() == want.tobytes(), (
            path, np.flatnonzero(got != want)[:8])
    else:
        assert type(got) is type(want) and got == want, (path, got, want)


@pytest.mark.parametrize("case", _TILE_PACK_CASES)
def test_native_tile_pack_is_bit_equal_to_the_numpy_body(case, monkeypatch):
    from wormhole_tpu import native

    if not native.available():
        pytest.skip("native library unavailable (no toolchain?)")
    kw = _tile_pack_case(case)
    got = ck.pack_tile_coo(**kw)
    want = _numpy_body(monkeypatch, **kw)
    assert got.packed_native and not want.packed_native
    _assert_same_bits(got, want)
    # the cases are what their names say
    if case.startswith(("u_cap", "cut_")):
        assert want.dropped_uniq > 0 and want.dropped_nnz > 0
        # only a truncated tile's keys reach the domain's last slot
        assert (want.uniq[-1] != kw["num_buckets"]) == (
            case == "u_cap_truncates_boundary_tile")
    else:
        assert want.dropped_uniq == 0
    if case == "long_rows":        # one stream holds a row of any length
        assert np.count_nonzero(want.coo.val) == np.count_nonzero(kw["val"])
        # where the row-major layout the FM pack still makes (build_rm)
        # cuts a row at its width: rows 3 and 9 lose 3 + 1 at width 4
        _, rm_val, over = ck.build_rm(kw["seg"], kw["idx"], kw["val"], 16,
                                      4, kw["num_buckets"])
        assert len(over) == 4 and set(kw["seg"][over]) == {3, 9}
        assert np.count_nonzero(rm_val) == np.count_nonzero(kw["val"]) - 4
    if case == "fixed_width":      # build_rm's fast path: the input's
        rm_slot, rm_val, over = ck.build_rm(
            kw["seg"], kw["idx"], kw["val"], 64, 8, kw["num_buckets"])
        assert np.array_equal(rm_slot, kw["idx"]) and len(over) == 0
        assert np.shares_memory(rm_val, kw["val"])
    if case.startswith("empty"):   # a block a compact tile, all padding
        assert want.coo.val.shape == ((kw["capacity"] or 0) + BLK,)
        assert not want.coo.val.any()


@pytest.mark.parametrize("case", ["int64_ids", "rows_not_grouped",
                                  "over_capacity"])
def test_tile_pack_outside_the_native_domain_runs_the_numpy_body(
        case, monkeypatch):
    """What the native pass cannot take, it hands to the numpy body,
    which decides what such a batch means: the same answer or the same
    refusal as before."""
    from wormhole_tpu import native

    kw = _tile_pack_case("fixed_width")
    if case == "int64_ids":
        tc = ck.pack_tile_coo(**{**kw, "idx": kw["idx"].astype(np.int64)})
        assert not tc.packed_native
        _assert_same_bits(tc, ck.pack_tile_coo(**kw))
    elif case == "rows_not_grouped":
        # no layout that is left asks for the rows in order: the batch
        # is inside the native domain, and the stream holds the entry
        # under the row it names
        kw = _tile_pack_case("ragged_padded")
        live = np.flatnonzero(kw["val"])
        kw["seg"][live[0]] = 63
        tc = ck.pack_tile_coo(**kw)
        assert tc.packed_native == native.available()
        _assert_same_bits(tc, _numpy_body(monkeypatch, **kw))
        at = np.flatnonzero(tc.coo.val == kw["val"][live[0]])
        assert 63 in tc.coo.seg[at]
        with pytest.raises(ValueError, match="row-grouped"):    # as ever
            ck.build_rm(kw["seg"], kw["idx"], kw["val"], 64, 8,
                        kw["num_buckets"])
    else:
        # two blocks of entries in one compact tile, room for one
        idx, seg, val = _rows_batch(np.random.default_rng(1), [8] * 1024,
                                    TILE)
        with pytest.raises(AssertionError):
            ck.pack_tile_coo(idx, seg, val, TILE, TILE, capacity=0)
