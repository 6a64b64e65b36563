"""Pallas TPU kernels for sparse COO matvec against a huge hashed table.

The reference's hot loops are OpenMP CSR kernels (learn/base/spmv.h:72-119)
plus per-key hash-map updates on the servers. On TPU, XLA's generic
gather/scatter costs ~10ns per random index into an HBM-resident table —
~25ms per 640k-nnz minibatch step — because each index becomes an
independent HBM transaction. These kernels restructure both directions
around the memory hierarchy instead:

- The table (NB buckets) is processed in VMEM-resident tiles of
  TILE = 512*128 = 64k buckets (256 KB f32).
- The host pre-sorts each minibatch's COO triples by bucket id (the
  Localizer role, reference learn/base/localizer.h — the sort it already
  does to compact keys), so each table tile sees one contiguous slice of
  the nnz stream. Slices are padded to BLK-sized blocks with val=0.
- A bucket id splits radix-style into (hi, lo) = (id>>7, id&127): hi picks
  a sublane row of the (512, 128) tile, lo picks a lane.
- Row fetches (w[idx], d[seg]) are one-hot MXU matmuls E(n,R) @ table(R,128)
  followed by a lane select with `tpu.dynamic_gather` along lanes (Mosaic's
  dynamic_gather spans only 8 sublanes along dim 0, so the systolic array
  plays the row gather; the lane gather is native).
- PULL (xw = X w): per-row sums accumulate into a (num_rows/128, 128)
  radix image of xw via a one-hot matmul: xw2 += E_rowᵀ @ (p ⊙ C_row).
- PUSH (g = Xᵀ d): the gradient tile accumulates via
  g_tile += E_hiᵀ @ (c ⊙ C_lo) — the MXU plays the scatter-add, turning
  640k random writes into dense matmuls.

Both kernels visit each table tile's blocks consecutively (the host
layout guarantees it), so Pallas's output-revisiting keeps the
accumulator tile in VMEM and writes it to HBM once per tile.

Measured on v5e: ~25ms/step for the XLA gather/scatter formulation vs
~2ms/step for these kernels at 16k x 39 nnz, 4M buckets.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

import os

# Tile geometry. A grid block's cost is the one-hot gather/scatter
# operands it builds on the VPU and feeds the MXU: one row of TILE_HI
# (or LANES) columns for each slot it works on, whether or not the slot
# holds anything. At Criteo-1TB table sizes most blocks are a few percent
# full, so the kernels build those operands only over a block's live
# prefix (CHUNK and _live_chunks below), and a block costs what it
# holds. The geometry decides the layout of every packed batch and
# pack-cache entry (models/linear.pack_cache_token), so it is constant;
# BLK is the block of the one-chip layouts, a stream carries its own
# (pack_sorted_coo's `blk`: FM_BLK for the FM kernels, mesh_block for a
# shard of a mesh) and the product kernels read it off the stream.
TILE_HI = 512  # sublanes per tile
LANES = 128
TILE = TILE_HI * LANES  # buckets per table tile
BLK = 4096  # nnz per grid block
# The FM kernels keep dim-many per-nnz temporaries alive per block.
# Swept on v5e: 1024 beats 2048/4096 (their per-block operands blow the
# VMEM working set and stall the pipeline; the kernels are VPU-
# throughput-bound, ~1 ns/nnz/channel, not per-block-overhead-bound).
FM_BLK = 1024
_FM_VMEM_LIMIT = int(os.environ.get("WORMHOLE_FM_VMEM", 64 * 2**20))
# Scoped-VMEM ceiling for the scalar COO / compaction kernels: the
# compiler's 16 MB default rejects fatter grid blocks (BLK/BLK_U sweeps)
# long before v5e's 128 MB VMEM is actually at risk.
_VMEM_LIMIT = int(os.environ.get("WORMHOLE_VMEM", 96 * 2**20))


# Slots of a block that the one-hot bodies take at a time when the block
# is at most half full. The live slots of a block are always a prefix of
# it (assign_tile_slots: a tile's keys sit at base + rank;
# pack_sorted_coo: a tile's run is written at d0:d0+n and padded after
# it), so one number a block, the extent of that prefix, bounds its work.
CHUNK = 128
# The least block a 1-D stream goes by: XLA lays a 1-D 32-bit array out
# in tiles of 1,024 elements, and inside a jitted step Mosaic refuses a
# block under its operand's tile ("XLA layout {0:T(1024)} does not match
# Mosaic layout {0:T(512)}": PERF.md §6, PR 49; the kernel compiled
# alone gets the layout it asks for, so only the chip's step says it).
STREAM_TILE = 1024


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def block_extents(live, blk: int):
    """live: (P,) bool, P a multiple of blk. Returns (P // blk,) int32,
    for each block of blk slots one past its last live slot (0 = the block
    holds nothing). Derived on the device inside the kernel wrappers and
    handed to the kernel as a scalar-prefetch operand: one fused reduce
    over the stream, so the packed layouts carry no count."""
    live2 = live.reshape(-1, blk)
    pos = jax.lax.broadcasted_iota(jnp.int32, live2.shape, 1) + 1
    return jnp.max(jnp.where(live2, pos, 0), axis=1)


def stream_block(sidx, tmap) -> int:
    """Slots a grid block of a packed COO stream holds: a layout carries
    its own block (pack_sorted_coo's `blk`), one tmap entry a block, so
    the product kernels take it from the shapes they are handed."""
    blk, rest = divmod(sidx.shape[0], tmap.shape[0])
    assert rest == 0 and blk % STREAM_TILE == 0, (sidx.shape, tmap.shape)
    return blk


def chunks_run(ext, blk: int):
    """How many of a block's blk // CHUNK chunks the kernels execute at
    extent ext (array or scalar, numpy or jax): the chunks below the
    extent, or all of them once the block is more than half full and
    takes the full-width body (_live_chunks)."""
    return (ext > blk // 2) * (blk // CHUNK) + (ext <= blk // 2) * (
        -(-ext // CHUNK))


def host_chunk_counts(stream, dead, blk: int) -> tuple[int, int]:
    """(chunks, chunks the kernels execute) of a packed host stream whose
    slots equal to `dead` hold nothing: `uniq` with its sentinel, or a COO
    stream's `val` with 0. Live slots are a prefix of their block, so a
    chunk runs iff its first slot is live: only those slots are looked
    at, some thousands a batch where the stream has millions."""
    heads = np.asarray(stream).reshape(-1, blk)[:, ::CHUNK] != dead
    # a prefix of n live heads is an extent in ((n - 1) * CHUNK, n * CHUNK]
    return heads.size, int(chunks_run(heads.sum(1) * CHUNK, blk).sum())


def _live_chunks(ext, blk: int, body):
    """Run body(slice) over the live prefix of a grid block of blk slots:
    a block more than half full takes the whole block in one body, as the
    kernels always did; any other takes CHUNK-slot bodies below its
    extent, none at extent 0. Slices are static (the chunks are unrolled
    under pl.when), so each body lowers exactly like the full-width one.
    What lies past the extent adds 0.0 to every product in these
    kernels; where a kernel writes rather than accumulates (tile_gather)
    it zeroes its output first."""
    half = blk // 2

    @pl.when(ext > half)
    def _():
        body(slice(None))

    @pl.when(ext <= half)
    def _():
        for lo in range(0, half, CHUNK):
            @pl.when(lo < ext)
            def _(lo=lo):
                body(pl.ds(lo, CHUNK))


@dataclasses.dataclass
class SortedCOO:
    """A minibatch's COO triples sorted by bucket id and padded into
    BLK-aligned per-tile runs (host-side product; see pack_sorted_coo)."""

    idx: np.ndarray    # (P,) int32 bucket ids, sorted, pad = tile base
    seg: np.ndarray    # (P,) int32 row ids (arbitrary order within tile)
    val: np.ndarray    # (P,) f32 values, pad = 0
    tmap: np.ndarray   # (P/BLK,) int32: table tile of each block
    first: np.ndarray  # (P/BLK,) int32: 1 iff block is its tile's first

    @property
    def num_blocks(self) -> int:
        return self.tmap.shape[0]


def build_rm(seg, slot, val, num_rows: int, width: int, sentinel: int
             ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Row-major (num_rows x width) padded companion layout of a
    CSR-ordered COO batch: rm_slot[r*width + j] = slot of row r's j-th
    live nonzero (sentinel in padding), rm_val likewise (0.0 padding).
    The pull xw = X w then becomes ONE XLA row gather from the table
    (widened to >= 8-byte rows) + a dense reshape-reduce. That read
    ~2.4 ns/row against the radix-image kernel's ~3 ns/nnz when the
    table gathered from was a few MB (PERF.md r5); over the linear
    learner's compact domain it reads 4.7 ns/row at 12.6 MB and 6.7 at
    100 MB against the kernel's 3.2-3.4 ns/nnz, so since PR 32 that
    learner pulls with coo_spmv and the FM learner's forward
    (models/difacto.py, whose key table holds whole rows of dim floats)
    is the layout's remaining caller. Fast path: when the batch is
    exactly width-per-row in row order (the fixed-field Criteo shape),
    the layout IS the input and no packing runs.

    Returns (rm_slot, rm_val, overflow_pos): overflow_pos are input
    positions of live entries beyond `width` per row — the CALLER
    must zero their val in the scatter-side stream(s) too, so pull and
    push agree about which nonzeros exist (empty on the fast path)."""
    seg = np.asarray(seg, np.int32)
    slot = np.asarray(slot)
    val = np.asarray(val, np.float32)
    empty = np.empty(0, np.int64)
    n = num_rows * width
    if len(seg) == n:
        expect = np.repeat(np.arange(num_rows, dtype=np.int32), width)
        if np.array_equal(seg, expect):
            return slot.astype(np.int32, copy=False), val, empty
    rm_slot = np.full(n, sentinel, np.int32)
    rm_val = np.zeros(n, np.float32)
    live = val != 0
    seg_nz, slot_nz = seg[live], slot[live]
    if seg_nz.size and not (np.diff(seg_nz) >= 0).all():
        raise ValueError("build_rm expects row-grouped (CSR order) input")
    pos = (np.arange(seg_nz.shape[0])
           - np.searchsorted(seg_nz, seg_nz, side="left"))
    fit = pos < width
    over = empty
    if not fit.all():
        over = np.flatnonzero(live)[~fit]
        import logging

        logging.getLogger(__name__).warning(
            "row-major pack: dropped %d nonzeros from rows with more "
            "than %d live entries", len(over), width)
    rm_index = seg_nz[fit] * width + pos[fit]
    rm_slot[rm_index] = slot_nz[fit]
    rm_val[rm_index] = val[live][fit]
    return rm_slot, rm_val, over


def packed_size(capacity: int, num_buckets: int,
                tile: int | None = None, blk: int | None = None) -> int:
    """Static padded nnz capacity: every tile may waste up to one block,
    and every tile needs at least one block so its output tile is zeroed."""
    num_tiles = num_buckets // (tile or TILE)
    blk = blk or BLK
    return (capacity // blk + num_tiles) * blk


def pack_sorted_coo(idx, seg, val, num_buckets: int,
                    capacity: int | None = None,
                    tile: int | None = None,
                    blk: int | None = None) -> SortedCOO:
    """Sort COO triples by bucket id and lay them out in BLK-padded
    per-tile runs. Pure numpy (the C++ localizer does this off the hot
    path in production loaders). Shapes are static given (capacity,
    num_buckets) so the consuming jit never retraces.

    `tile` is the table rows each grid block's BlockSpec covers: the
    scalar kernels use TILE (= TILE_HI * LANES buckets viewed as a
    (TILE_HI, LANES) VMEM tile); the FM/SpMM kernels tile their
    [rows, dim] embedding tables at TILE_HI rows."""
    TILE = tile or globals()["TILE"]
    BLK = blk or globals()["BLK"]
    assert num_buckets % TILE == 0, f"num_buckets must be a multiple of {TILE}"
    num_tiles = num_buckets // TILE
    if capacity is None:
        capacity = len(idx)
    P = packed_size(capacity, num_buckets, TILE, BLK)
    nblk = P // BLK

    from wormhole_tpu import native

    order = native.radix_argsort(np.asarray(idx))
    if order is None:
        order = np.argsort(idx, kind="stable")

    def take(a, dtype):
        a = np.asarray(a, dtype)
        got = native.gather(a, order)
        return got if got is not None else a[order]

    sidx = take(idx, np.int32)
    sseg = take(seg, np.int32)
    sval = take(val, np.float32)
    # padding entries in the input batch (val == 0) keep their slot; they
    # are harmless anywhere, so no special casing.

    tile_of = sidx // TILE
    n_t = np.bincount(tile_of, minlength=num_tiles)
    blocks_t = np.maximum((n_t + BLK - 1) // BLK, 1)
    # trailing spare blocks belong to the last tile (keeps runs contiguous)
    spare = nblk - int(blocks_t.sum())
    assert spare >= 0, (nblk, blocks_t.sum(), capacity, len(idx))
    blocks_t[num_tiles - 1] += spare

    out_idx = np.empty(P, np.int32)
    out_seg = np.zeros(P, np.int32)
    out_val = np.zeros(P, np.float32)
    tmap = np.repeat(np.arange(num_tiles, dtype=np.int32), blocks_t)
    first = np.zeros(nblk, np.int32)

    src_off = np.concatenate([[0], np.cumsum(n_t)])
    dst_off = np.concatenate([[0], np.cumsum(blocks_t)]) * BLK
    for t in range(num_tiles):
        n = n_t[t]
        d0 = dst_off[t]
        first[d0 // BLK] = 1
        out_idx[d0:dst_off[t + 1]] = t * TILE  # pad default
        if n:
            s0 = src_off[t]
            out_idx[d0:d0 + n] = sidx[s0:s0 + n]
            out_seg[d0:d0 + n] = sseg[s0:s0 + n]
            out_val[d0:d0 + n] = sval[s0:s0 + n]
    return SortedCOO(out_idx, out_seg, out_val, tmap, first)


def _prec(dtype):
    """MXU precision of `tile_gather`'s and `fm_push_contrib`'s matmuls:
    at f32 request HIGHEST (bf16x6: both operands decomposed) so the
    "exact" kernel_dtype=f32 path really matches the XLA segment-op
    numerics — the default single-pass mode rounds f32 operands to bf16
    on the way into the systolic array. The two product kernels
    (`coo_pull`, `coo_push`) decompose the values' side alone
    (_onehot_dot, three passes for these six); these two keep HIGHEST
    because the DiFacto step runs them at f32 whatever its kernel_dtype
    (the count table's fetch, models/difacto.py) and that step's programs
    are to stay as they are until a change is measured in its own cell
    (ROADMAP.md A4 (2))."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32 else
            jax.lax.Precision.DEFAULT)


def _split3(x):
    """x (float32) as three bfloat16 addends, (hi + mid) + lo == x to the
    bit: each takes the next 8 of the 24 mantissa bits (a value so small
    that lo leaves bfloat16's exponent range loses those bits)."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    return hi, mid, (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _mxu(dtype):
    """The dtype of a one-hot the MXU multiplies by: bfloat16 at f32 too
    (a one-hot is exact in it; the values' side is split, _onehot_dot)."""
    return jnp.bfloat16 if dtype == jnp.float32 else dtype


def _onehot_dot(e, x, dtype):
    """One-hot e (dtype _mxu(dtype)) times the values x (f32) on the MXU,
    e's columns against x's rows; f32 out. At bf16 the values round to
    it: one pass. At f32 they go in as their three bfloat16 addends and
    every product is exact, summed in float32: three passes where
    Precision.HIGHEST (bf16x6) also multiplies the one-hot's zero mid
    and lo parts, so a fetch is exact and a scatter an f32 sum, at half
    HIGHEST's MXU cost."""
    def dot(v):
        return jax.lax.dot_general(
            e, v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )

    if dtype != jnp.float32:
        return dot(x.astype(dtype))
    hi, mid, lo = _split3(x)
    return (dot(hi) + dot(mid)) + dot(lo)


def _row_fetch(table2, hi, dtype):
    """table2: (R, 128); hi: (BLK,) row ids in [0, R). Returns (BLK, 128)
    f32: row hi[j] of table2 in row j — a one-hot MXU matmul (Mosaic's
    dynamic_gather only spans 8 sublanes along dim 0, so the systolic
    array plays the row gather instead)."""
    e = _onehot(hi, table2.shape[0], dtype)
    return jax.lax.dot_general(
        e, table2.astype(dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=_prec(dtype),
    )


def _row_fetch3(table2, hi, dtype):
    """_row_fetch for the two product kernels: the same rows, at f32 from
    the table's three bf16 addends against a bf16 one-hot (_onehot_dot)
    where _row_fetch asks HIGHEST; at bf16 the same matmul."""
    e = _onehot(hi, table2.shape[0], _mxu(dtype))
    return _onehot_dot(e, table2, dtype)


def _lane_pick(rows, lane_onehot):
    """rows: (BLK, 128); lane_onehot: (BLK, 128) one-hot of lane ids.
    Returns (BLK,) rows[j, lo[j]] as a mask-and-lane-reduce — measured
    ~15% faster kernel-wide than take_along_axis's dynamic_gather, and
    the one-hot is usually already needed for a scatter matmul."""
    return jnp.sum(rows * lane_onehot, axis=1)


def _onehot(ids, width: int, dtype):
    """(BLK, width) one-hot of int vector ids — the E/C matrices the
    MXU uses to play gather/scatter. One-hots are exact in any float
    dtype; bf16 halves the MXU cost of the matmuls they feed. The cast
    ROUTE matters ~2x on the VPU: i1 -> f32 (native select) then one
    f32 -> bf16 pack, instead of a direct i1 -> bf16 astype (Mosaic
    lowers that as a multi-pass cast chain — measured on the GBDT
    histogram build, tools/gbdt_hist_lab.py r5)."""
    cols = jax.lax.broadcasted_iota(jnp.int32, (ids.shape[0], width), 1)
    eq = (ids[:, None] == cols).astype(jnp.float32)
    return eq if dtype == jnp.float32 else eq.astype(dtype)


def _onehot_t(ids, width: int, dtype):
    """(width, BLK) one-hot — the TRANSPOSE of _onehot(ids, width),
    built directly in transposed layout. Scatter matmuls contract over
    the nnz axis; feeding dot_general an untransposed one-hot there
    makes Mosaic materialize a (BLK, width) transpose on the VPU, which
    measured ~1.5 ns/nnz — building the operand pre-transposed cuts the
    scatter side from ~2.4 to ~1.3 ns/nnz. Same f32-route cast as
    _onehot."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (width, ids.shape[0]), 0)
    eq = (ids[None, :] == rows).astype(jnp.float32)
    return eq if dtype == jnp.float32 else eq.astype(dtype)


# --------------------------------------------------------------------- pull
def _pull_kernel(tmap_ref, first_ref, ext_ref, w_ref, idx_ref, seg_ref,
                 val_ref, out_ref, *, num_rows: int, dtype):
    blk = pl.program_id(0)

    @pl.when(blk == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    base = tmap_ref[blk] * TILE

    def body(sl):
        local = idx_ref[sl] - base
        hi = local >> 7
        lo = local & (LANES - 1)
        w2 = w_ref[:].reshape(TILE_HI, LANES)
        c_lo = _onehot(lo, LANES, dtype)
        p = _lane_pick(_row_fetch3(w2, hi, dtype), c_lo) * val_ref[sl]

        rhi = seg_ref[sl] >> 7
        rlo = seg_ref[sl] & (LANES - 1)
        e_rt = _onehot_t(rhi, num_rows // LANES, _mxu(dtype))
        c_r = _onehot(rlo, LANES, dtype)
        out_ref[:] += _onehot_dot(e_rt, p[:, None] * c_r, dtype)

    # a block past whose extent every val is 0 adds nothing there
    _live_chunks(ext_ref[blk], idx_ref.shape[0], body)


def coo_spmv(w, sidx, sseg, sval, tmap, first, num_rows: int, dtype=None):
    """xw = X w over the sorted/padded COO batch; returns (num_rows,) f32.
    num_rows must be a multiple of 128. dtype is the MXU compute dtype:
    bf16 (default on TPU; one-hots stay exact, table values round — the
    reference's compressing-filter tradeoff) or f32 (every gathered value
    exact and every sum a float32 one, at three MXU passes for bf16's
    one, _onehot_dot; default off-TPU so CPU tests compare bit-tight)."""
    if dtype is None:
        dtype = jnp.bfloat16 if not _use_interpret() else jnp.float32
    assert num_rows % LANES == 0
    nblk = tmap.shape[0]
    blk = stream_block(sidx, tmap)
    ext = block_extents(sval != 0, blk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((TILE,), lambda b, tmap, *_: (tmap[b],)),
            pl.BlockSpec((blk,), lambda b, *_: (b,)),
            pl.BlockSpec((blk,), lambda b, *_: (b,)),
            pl.BlockSpec((blk,), lambda b, *_: (b,)),
        ],
        out_specs=pl.BlockSpec(
            (num_rows // LANES, LANES), lambda b, *_: (0, 0)),
    )
    out = pl.pallas_call(
        partial(_pull_kernel, num_rows=num_rows, dtype=dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_rows // LANES, LANES),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_use_interpret(),
        name="coo_pull",
    )(tmap, first, ext, w, sidx, sseg, sval)
    return out.reshape(num_rows)


# --------------------------------------------------------------------- push
def _push_kernel(tmap_ref, first_ref, ext_ref, d_ref, idx_ref, seg_ref,
                 val_ref, *acc_out, dtype):
    *acc_ref, out_ref = acc_out
    blk = pl.program_id(0)

    # a tile's sum starts from zero, or from the caller's tile of `acc`
    @pl.when(first_ref[blk] == 1)
    def _():
        out_ref[:] = acc_ref[0][:] if acc_ref else jnp.zeros_like(out_ref)

    base = tmap_ref[blk] * TILE

    def body(sl):
        rhi = seg_ref[sl] >> 7
        rlo = seg_ref[sl] & (LANES - 1)
        c_r = _onehot(rlo, LANES, dtype)
        c = _lane_pick(_row_fetch3(d_ref[:], rhi, dtype), c_r) * val_ref[sl]

        local = idx_ref[sl] - base
        hi = local >> 7
        lo = local & (LANES - 1)
        e_hit = _onehot_t(hi, TILE_HI, _mxu(dtype))
        c_lo = _onehot(lo, LANES, dtype)
        out_ref[:] += _onehot_dot(e_hit, c[:, None] * c_lo, dtype)

    # an empty block only zeroes its tile (above) when it opens one
    _live_chunks(ext_ref[blk], idx_ref.shape[0], body)


def coo_spmv_t(d, sidx, sseg, sval, tmap, first, num_buckets: int,
               dtype=None, acc=None):
    """g = Xᵀ d in table layout; returns (num_buckets,) f32. d is the
    per-row dual vector, len(d) a multiple of 128. With `acc`, a
    (num_buckets,) f32 sum so far, returns acc + Xᵀ d written where acc
    lay: every tile is read once and written once, where adding the
    product afterwards reads two tables and writes a third (18 ms of a
    624 ms gradient pass of 16 chunks at 2^26 buckets, PERF.md §6, PR
    48). For the dense layout, whose every tile has one run."""
    if dtype is None:
        dtype = jnp.bfloat16 if not _use_interpret() else jnp.float32
    num_rows = d.shape[0]
    assert num_rows % LANES == 0
    assert num_buckets % TILE == 0
    nblk = tmap.shape[0]
    d2 = d.reshape(num_rows // LANES, LANES)
    blk = stream_block(sidx, tmap)
    ext = block_extents(sval != 0, blk)
    tile_spec = pl.BlockSpec(
        (TILE_HI, LANES), lambda b, tmap, *_: (tmap[b], 0))
    # the sum so far rides as one more input, tiled and aliased as the output
    sums = () if acc is None else (acc.reshape(num_buckets // LANES, LANES),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((num_rows // LANES, LANES), lambda b, *_: (0, 0)),
            pl.BlockSpec((blk,), lambda b, *_: (b,)),
            pl.BlockSpec((blk,), lambda b, *_: (b,)),
            pl.BlockSpec((blk,), lambda b, *_: (b,)),
        ] + [tile_spec] * len(sums),
        out_specs=tile_spec,
    )
    out = pl.pallas_call(
        partial(_push_kernel, dtype=dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_buckets // LANES, LANES),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_use_interpret(),
        name="coo_push",
        input_output_aliases={7: 0} if sums else {},
    )(tmap, first, ext, d2, sidx, sseg, sval, *sums)
    return out.reshape(num_buckets)


# ------------------------------------------- tile-aligned compaction
# At Criteo-1TB table sizes (>=2^26 buckets) a minibatch touches a tiny,
# hash-spread fraction of the table: ~160k unique buckets scattered
# across all of it. Processing the table densely (one padding block per
# tile above, plus an O(num_buckets) optimizer sweep) then scales with
# the table, not the batch — the exact failure the reference avoids by
# updating only pushed keys on its servers (async_sgd.h:160-175). The
# compacted path is the TPU analog of the reference Localizer
# (learn/base/localizer.h:42-221): map the batch's unique bucket ids to
# a compact [0, u_cap) slot space and run the SAME kernels over the
# compact domain, coo_spmv for the pull and coo_spmv_t for the push over
# the one COO stream of the batch (its tile count is ~uniques/TILE
# instead of num_buckets/TILE). A plain dense slot assignment would
# still pay XLA element gather/scatter of the compact entries (~20 ns
# per random access — latency-bound, ~22 ms per 64k-row step at 2^26
# buckets), so slots are instead grouped so each TOUCHED full-table
# tile's unique keys occupy a BLK_U-aligned contiguous slot run. Then
# - pulling the touched entries is a Pallas kernel streaming only the
#   touched table tiles (tile_gather below), and
# - the optimizer update runs INSIDE a Pallas kernel that scatters the
#   compact gradient into each touched tile and rewrites the tile in
#   place (ops/fused_update.py, aliased in/out) — the TPU analog of the
#   reference server handle updating the entry at its storage on push
#   (async_sgd.h:160-180), with untouched tiles never streamed at all.
#
# What a step then costs (measured on v5e, PERF.md §5): at 2^26 buckets
# a batch touches 1,024 tiles of ~240 keys each; at 2^29 it touches
# every one of the 8,192 tiles with ~30 keys each, so each update block
# of BLK_U slots is 3 % full and the 1.5x headroom of the compact
# capacity adds half as many empty ones. Streaming the tiles is still
# the right formulation there (a random access is ~20-50 ns a key and
# table; the update touches z, n, w twice), but only because a block's
# one-hot work follows its live extent (_live_chunks): with every block
# built at full width the pull took 2.84 us a block where the tile's
# DMA is 0.32 us; bounded by the extent it takes ~0.55 us and the fused
# update runs near the bytes it moves. ("The pull" of these figures is
# tile_gather, the fetch into the compact domain; xw = X w over that
# domain is coo_spmv, because an XLA row gather is latency-bound at any
# domain over a few MB: models/linear._build_tcoo.)

# slots per update block; 1024 is the minimum 1D block Mosaic accepts
# against XLA's s32[...]{0:T(1024)} layout for large 1D operands
BLK_U = 1024
assert TILE % BLK_U == 0, "BLK_U must divide TILE (block map alignment)"



@dataclasses.dataclass
class TileCOO:
    """A minibatch localized into a tile-aligned compact slot space."""

    uniq: np.ndarray    # (u_cap,) int32 full-table ids per slot, sorted;
    #                     sentinel num_buckets in alignment holes
    coo: SortedCOO      # the batch packed over the compact domain
    tmap_u: np.ndarray  # (u_cap/BLK_U,) int32 full-table tile per block
    first_u: np.ndarray  # (u_cap/BLK_U,) 1 iff block starts its tile's run
    last_u: np.ndarray  # (u_cap/BLK_U,) 1 iff block ends its tile's run
    num_uniq: int
    dropped_uniq: int   # unique keys cut on u_cap overflow
    dropped_nnz: int    # their nonzeros, dropped with them
    #: which body of pack_tile_coo made this batch. Not a field: it is
    #: not part of the batch (compared, stored in the pack cache), only
    #: the packer's word to its caller's counter
    packed_native = False


@dataclasses.dataclass
class TileSlots:
    """Tile-run-aligned compact slot assignment for a set of unique ids
    (scalar bucket ids, or embedding ROW ids when rows_per_tile < TILE)."""

    uniq: np.ndarray      # (u_cap,) int32 id per slot; sentinel in holes
    tmap_u: np.ndarray    # (u_cap/BLK_U,) int32 table tile per block
    first_u: np.ndarray   # (u_cap/BLK_U,)
    last_u: np.ndarray    # (u_cap/BLK_U,)
    slot_of_uniq: np.ndarray  # (n_uniq,) int64 slot per unique (u_cap = cut)
    num_uniq: int
    dropped_uniq: int


def tile_blocks_needed(ids, rows_per_tile: int) -> int:
    """How many BLK_U update blocks assign_tile_slots will allocate for
    these unique ids: the ceil-div per touched tile. Capacity sizers must
    use this (not a hand-copied formula) so they can never drift from the
    packing policy."""
    n_t = np.bincount(np.asarray(ids, np.int64) // rows_per_tile)
    n_t = n_t[n_t > 0]
    if len(n_t) == 0:
        return 1
    return int(np.sum(-(-n_t // BLK_U)))


def assign_tile_slots(uniq, rows_per_tile: int, u_cap: int,
                      sentinel: int) -> TileSlots:
    """Group sorted unique ids by home table tile (rows_per_tile ids per
    tile) and give each tile's run a BLK_U-aligned contiguous slot range.
    On overflow, whole tiles (plus a truncated boundary tile) are kept in
    id order and the rest cut."""
    assert u_cap % BLK_U == 0
    uniq = np.asarray(uniq, np.int64)
    nb = u_cap // BLK_U

    tile_of = uniq // rows_per_tile
    t_ids, n_t = np.unique(tile_of, return_counts=True)
    b_t = np.maximum((n_t + BLK_U - 1) // BLK_U, 1)
    # cap: keep whole tiles (and a truncated final tile) within nb blocks
    cum_b = np.cumsum(b_t)
    n_keep_tiles = int(np.searchsorted(cum_b, nb, side="right"))
    dropped_uniq = 0
    if n_keep_tiles < len(t_ids):
        # truncate the boundary tile to the blocks that still fit
        blocks_left = nb - (cum_b[n_keep_tiles - 1] if n_keep_tiles else 0)
        if blocks_left > 0:
            b_t[n_keep_tiles] = blocks_left
            n_t[n_keep_tiles] = min(n_t[n_keep_tiles],
                                    blocks_left * BLK_U)
            n_keep_tiles += 1
        kept_uniq = int(np.sum(n_t[:n_keep_tiles]))
        dropped_uniq = len(uniq) - kept_uniq
        t_ids, n_t, b_t = (t_ids[:n_keep_tiles], n_t[:n_keep_tiles],
                           b_t[:n_keep_tiles])
    else:
        kept_uniq = len(uniq)

    # slot of each kept unique = its tile's aligned base + rank in tile
    dst_base = np.concatenate([[0], np.cumsum(b_t)[:-1]]) * BLK_U
    src_base = np.concatenate([[0], np.cumsum(n_t)[:-1]])
    rank = np.arange(len(uniq), dtype=np.int64)
    tile_rank = np.searchsorted(t_ids, tile_of[:kept_uniq])
    slot_of_uniq = np.full(len(uniq), u_cap, np.int64)  # dropped -> u_cap
    slot_of_uniq[:kept_uniq] = (dst_base[tile_rank]
                                + rank[:kept_uniq] - src_base[tile_rank])

    out_uniq = np.full(u_cap, sentinel, np.int32)
    out_uniq[slot_of_uniq[:kept_uniq]] = uniq[:kept_uniq]

    tmap_u = np.zeros(nb, np.int32)
    first_u = np.zeros(nb, np.int32)
    last_u = np.zeros(nb, np.int32)
    used = int(np.sum(b_t))
    tmap_u[:used] = np.repeat(t_ids, b_t)
    if used:
        tmap_u[used:] = t_ids[-1]  # trailing spare blocks: inert revisits
        ends = np.cumsum(b_t)
        first_u[ends - b_t] = 1
        last_u[ends - 1] = 1
    else:  # degenerate empty batch: one harmless copy-through of tile 0
        first_u[0] = 1
        last_u[0] = 1
    return TileSlots(out_uniq, tmap_u, first_u, last_u, slot_of_uniq,
                     kept_uniq, dropped_uniq)


def pack_tile_coo(idx, seg, val, num_buckets: int, u_cap: int,
                  capacity: int | None = None) -> TileCOO:
    """Localize bucket ids (the reference Localizer's sort+unique+remap,
    localizer.h:98-221) into tile-run-aligned compact slots and pack the
    COO triples over that domain (host-side, loader threads).

    Where the native core is loaded and the batch is what
    to_device_batch makes (int32 ids in [0, num_buckets)), the whole pack
    is one call of it that holds no interpreter lock: one radix sort,
    whose order serves the slots too (slot order is key order), and every
    array written in that order (native/src/pack.cc). The numpy body
    below is the same function for every other input, bit for bit."""
    assert u_cap % TILE == 0, f"u_cap must be a multiple of {TILE}"
    assert num_buckets < 2**31, "sentinel id must fit int32"
    from wormhole_tpu import native
    from wormhole_tpu.ops.localizer import localize

    got = native.pack_tile_coo(idx, seg, val, num_buckets, u_cap, capacity,
                               TILE, BLK, BLK_U)
    if got is not None:
        tc = TileCOO(
            got["uniq"], SortedCOO(*(got[k] for k in (
                "idx", "seg", "val", "tmap", "first"))),
            got["tmap_u"], got["first_u"], got["last_u"], got["num_uniq"],
            got["dropped_uniq"], got["dropped_nnz"])
        tc.packed_native = True
        return tc

    idx = np.asarray(idx, np.int64)
    seg = np.asarray(seg, np.int32)
    val = np.asarray(val, np.float32)
    loc = localize(idx.astype(np.uint64))
    ts = assign_tile_slots(loc.uniq_keys, TILE, u_cap, num_buckets)

    new_slot = ts.slot_of_uniq[loc.local_index]
    keep = new_slot < u_cap
    # count only real (nonzero-valued) dropped entries: padding triples
    # carry val == 0 and losing them loses nothing (ADVICE r2)
    dropped_nnz = int(np.count_nonzero(~keep & (val != 0)))
    p = pack_sorted_coo(new_slot[keep], seg[keep], val[keep], u_cap,
                        capacity=capacity)
    return TileCOO(ts.uniq, p, ts.tmap_u, ts.first_u, ts.last_u,
                   ts.num_uniq, ts.dropped_uniq, dropped_nnz)


def _tile_gather_kernel(tmap_ref, ext_ref, w_ref, uniq_ref, out_ref, *,
                        dtype):
    b = pl.program_id(0)
    base = tmap_ref[b] * TILE
    ext = ext_ref[b]

    def body(sl):
        local = uniq_ref[sl] - base
        hi = local >> 7
        lo = local & (LANES - 1)
        # sentinel slots (uniq == num_buckets) produce hi outside
        # [0, TILE_HI): their one-hot row is all zeros, so they fetch
        # 0.0 — no clamp needed
        c_lo = _onehot(lo, LANES, dtype)
        out_ref[sl] = _lane_pick(_row_fetch(w_ref[:], hi, dtype), c_lo)

    # past the extent every slot is a sentinel hole and reads 0.0
    @pl.when(ext <= BLK_U // 2)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    _live_chunks(ext, BLK_U, body)


def tile_gather(table2, uniq, tmap_u, dtype=None):
    """Gather table entries at the tile-aligned compact slots: returns
    (u_cap,) f32 with out[s] = table[uniq[s]] (0.0 at sentinel holes).
    table2 is the table viewed (num_buckets//128, 128); only TOUCHED
    tiles are streamed — the whole point vs an XLA gather, whose per-
    element random-access latency (~20ns) dwarfs the tile bandwidth."""
    if dtype is None:
        dtype = jnp.bfloat16 if not _use_interpret() else jnp.float32
    nb = tmap_u.shape[0]
    u_cap = nb * BLK_U
    ext = block_extents(uniq != table2.shape[0] * LANES, BLK_U)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((TILE_HI, LANES), lambda b, tmap, *_: (tmap[b], 0)),
            pl.BlockSpec((BLK_U,), lambda b, *_: (b,)),
        ],
        out_specs=pl.BlockSpec((BLK_U,), lambda b, *_: (b,)),
    )
    return pl.pallas_call(
        partial(_tile_gather_kernel, dtype=dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((u_cap,), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_use_interpret(),
        name="tile_gather",
    )(tmap_u, ext, table2, uniq)


# ------------------------------------------------------------ FM / SpMM
# Vector-valued COO kernels for the factorization machine: the table is a
# compact embedding matrix [rows, dim] (dim ~ 8..64), tiled at TILE_HI
# rows. Row fetches are one-hot MXU matmuls E(BLK, TILE_HI) @ tile
# (TILE_HI, dim) — no lane select needed because ALL dim values of a row
# are wanted — and the scatter side is a single Eᵀ @ contrib matmul.
# These replace the [nnz, dim] XLA gather + two segment-sums of the FM
# hot path (difacto loss.h:53-157 SpMM), measured ~8x faster at Criteo
# shape on v5e.


def _fm_push_contrib_kernel(tmap_ref, first_ref, V_ref, g_ref, vv_ref,
                            idx_ref, out_ref, acc_ref, *, dim: int,
                            dtype):
    # The row-major FM path's scatter. Each nonzero arrives with the row
    # it belongs to already looked up (g = [xv | d | 0] of its row: one
    # cheap XLA row gather, since the forward keeps xv in row layout)
    # and its admitted value vv; the kernel forms its contributions
    #   c = d * vv,  a = c * xv,  b = c * vv,  t = (vv != 0)
    # on the VPU, so that none of them is ever written to HBM. The
    # per-nnz V-row term needs NO in-kernel fetch at all: with e the
    # (BLK, TILE_HI) one-hot of the slot ids,
    #   eᵀ @ (b ⊙ (e @ V_tile)) = (eᵀ @ diag(b) @ e) @ V_tile
    #                            = diag(eᵀ b) @ V_tile
    # because eᵀ diag(b) e is diagonal (each nnz hits one slot). So the
    # kernel scatters [a | b | t] with ONE eᵀ matmul and applies the
    # b-sums as a per-row scale of the tile it already streams:
    #   dV_tile += eᵀ @ a - (eᵀ @ b) ⊙ V_tile
    # and the t-sums, a row's count of admitted nonzeros, ride out in
    # the column beside it.
    blk = pl.program_id(0)

    @pl.when(first_ref[blk] == 1)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    g = g_ref[:].astype(jnp.float32)                # (BLK, dim + 2)
    vv = vv_ref[:][:, None]                         # (BLK, 1)
    c = g[:, dim:dim + 1] * vv
    lane = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    abt = jnp.where(lane < dim, c * g,
                    jnp.where(lane == dim, c * vv,
                              (vv != 0).astype(jnp.float32)))
    local = idx_ref[:] - tmap_ref[blk] * TILE_HI
    e_t = _onehot_t(local, TILE_HI, dtype)
    acc_ref[:] += jax.lax.dot_general(
        e_t, abt.astype(dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=_prec(dtype),
    )

    # last block of this tile's run: the next block is another tile's
    # first (or the grid ends) — apply the diagonal b-sum term and flush
    nblk = pl.num_programs(0)
    is_last = jnp.where(blk == nblk - 1, 1,
                        first_ref[jnp.minimum(blk + 1, nblk - 1)])

    @pl.when(is_last == 1)
    def _():
        acc = acc_ref[:]
        out_ref[:, :dim] = acc[:, :dim] - acc[:, dim:dim + 1] * V_ref[:]
        out_ref[:, dim:] = acc[:, dim + 1:]


def fm_push_contrib(V, xv, d, seg, vv, sidx, tmap, first, dtype=None,
                    wire=jnp.float32):
    """FM embedding gradient over the slot-sorted COO (row-major FM
    path): dV[j] = sum over the nonzeros of slot j of c * (xv[seg] - vv *
    V[j]), c = d[seg] * vv. xv: [rows, dim] and d: [rows] by batch row,
    looked up a nonzero at a time at the dtype `wire`; seg, vv, sidx:
    [P] the nonzero's batch row, admitted value (0 where it is padding
    or not admitted) and slot. Returns ([slots, dim] dV, [slots] each
    slot's count of nonzeros with vv != 0)."""
    if dtype is None:
        dtype = jnp.bfloat16 if not _use_interpret() else jnp.float32
    rows, dim = V.shape
    assert rows % TILE_HI == 0
    nblk = tmap.shape[0]
    blk = sidx.shape[0] // nblk
    # the nonzero's row of [xv | d | 0]: the kernel overwrites the two
    # last columns with b and t, so the operand has its final shape
    g = jnp.take(jnp.concatenate(
        [xv, d[:, None], jnp.zeros_like(d)[:, None]], axis=1).astype(wire),
        seg, axis=0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((TILE_HI, dim), lambda b_, tmap, first: (tmap[b_], 0)),
            pl.BlockSpec((blk, dim + 2), lambda b_, *_: (b_, 0)),
            pl.BlockSpec((blk,), lambda b_, *_: (b_,)),
            pl.BlockSpec((blk,), lambda b_, *_: (b_,)),
        ],
        out_specs=pl.BlockSpec((TILE_HI, dim + 1),
                               lambda b_, tmap, first: (tmap[b_], 0)),
        scratch_shapes=[pltpu.VMEM((TILE_HI, dim + 2), jnp.float32)],
    )
    out = pl.pallas_call(
        partial(_fm_push_contrib_kernel, dim=dim, dtype=dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, dim + 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_FM_VMEM_LIMIT),
        interpret=_use_interpret(),
        name="fm_push_contrib",
    )(tmap, first, V, g, vv, sidx)
    return out[:, :dim], out[:, dim]


# ---------------------------------------------------------- mesh sharding
# The 1x1-mesh kernels above generalize to a (data x model) mesh the same
# way ps-lite shards keys across servers and examples across workers
# (reference async_sgd.h:277-287): each model shard owns a contiguous
# bucket range (a whole number of tiles), each data shard owns a
# contiguous row range, and device (d, m) runs the kernel on exactly the
# nonzeros that fall in its (row range x bucket range) cell. PULL partial
# sums psum over the model axis; PUSH gradients psum over the data axis —
# the two collectives that play ZPull and ZPush.


@dataclasses.dataclass
class MeshCOO:
    """Per-(data, model)-shard packed COO: leading [D, M] axes are laid
    out over the mesh; trailing axes are each shard's SortedCOO."""

    sidx: np.ndarray   # [D, M, P]
    sseg: np.ndarray   # [D, M, P] row ids local to the data shard
    sval: np.ndarray   # [D, M, P]
    tmap: np.ndarray   # [D, M, P/blk], blk = mesh_block(capacity, nb_m)
    first: np.ndarray  # [D, M, P/blk]
    dropped_nnz: int   # nonzeros beyond a shard's capacity (overflow)
    cell_nnz: np.ndarray  # [D, M] live nonzeros a cell got, before the cut


def mesh_capacity(capacity: int, D: int, M: int, slack: float = 2.0) -> int:
    """Per-shard nnz capacity: an even split of the batch capacity across
    the D*M cells, padded by `slack` for hash skew (keys hash ~uniformly
    over bucket ranges — the byte-reversal spreading argument of
    localizer.h:16-26 — so 2x covers realistic imbalance), and never less
    than one block."""
    per = int(capacity * slack / (D * M))
    return max((per + BLK - 1) // BLK, 1) * BLK


def mesh_block(capacity_per_shard: int, nb_m: int) -> int:
    """Slots a shard's runs are padded to. Every tile of a shard's
    nb_m buckets gets at least one block, so where a shard has many
    tiles for its nonzeros the blocks are mostly padding, and padding
    is bytes the loader copies to the chip each batch: at 2^28 buckets
    a shard and 1,277,952 nonzeros at capacity, 312 a tile, BLK-sized
    blocks make a batch of 867 MB, 28 slots a nonzero. Where
    STREAM_TILE slots hold twice a tile's mean at capacity the shard
    packs at STREAM_TILE (263 MB there); every other shard keeps the
    one-chip layout's BLK. The two values the chip measured (PERF.md
    §6, PR 49: 512 is refused, 2,048 read worse than BLK and is not
    understood, so the rule never gives it)."""
    fits = STREAM_TILE * (nb_m // TILE) >= 2 * capacity_per_shard
    return STREAM_TILE if fits else BLK


def pack_mesh_coo(idx, seg, val, num_buckets: int, num_rows: int,
                  D: int, M: int, capacity_per_shard: int,
                  blk: int) -> MeshCOO:
    """Split COO triples into (data, model) mesh cells and pack each cell
    (host-side, loader threads) at the capacity and the block the caller
    took from the shard's geometry (mesh_capacity, mesh_block).
    Zero-valued entries (padding) are dropped before splitting — they
    contribute nothing."""
    nb_m = num_buckets // M
    rows_d = num_rows // D
    assert nb_m % TILE == 0, (num_buckets, M)
    assert rows_d % LANES == 0, (num_rows, D)
    P = packed_size(capacity_per_shard, nb_m, blk=blk)
    nblk = P // blk
    idx = np.asarray(idx, np.int64)
    seg = np.asarray(seg, np.int64)
    val = np.asarray(val, np.float32)
    live = val != 0
    d_of = seg // rows_d
    m_of = idx // nb_m

    sidx = np.zeros((D, M, P), np.int32)
    sseg = np.zeros((D, M, P), np.int32)
    sval = np.zeros((D, M, P), np.float32)
    tmap = np.zeros((D, M, nblk), np.int32)
    first = np.zeros((D, M, nblk), np.int32)
    cell_nnz = np.zeros((D, M), np.int64)
    dropped = 0
    for d in range(D):
        for m in range(M):
            sel = live & (d_of == d) & (m_of == m)
            ci = idx[sel] - m * nb_m
            cs = seg[sel] - d * rows_d
            cv = val[sel]
            cell_nnz[d, m] = len(ci)
            if len(ci) > capacity_per_shard:
                dropped += len(ci) - capacity_per_shard
                ci = ci[:capacity_per_shard]
                cs = cs[:capacity_per_shard]
                cv = cv[:capacity_per_shard]
            p = pack_sorted_coo(ci, cs, cv, nb_m,
                                capacity=capacity_per_shard, blk=blk)
            sidx[d, m] = p.idx
            sseg[d, m] = p.seg
            sval[d, m] = p.val
            tmap[d, m] = p.tmap
            first[d, m] = p.first
    return MeshCOO(sidx, sseg, sval, tmap, first, dropped, cell_nnz)


def mesh_coo_spmv(mesh, w, sidx, sseg, sval, tmap, first,
                  num_rows: int, dtype=None):
    """xw = X w on a (data x model) mesh. w is table-sharded over the
    model axis; returns xw sharded over the data axis. The psum over the
    model axis is the ZPull collective."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from wormhole_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    D = mesh.shape[DATA_AXIS]

    def local(w_l, si, ss, sv, tm, fi):
        xw = coo_spmv(w_l, si[0, 0], ss[0, 0], sv[0, 0], tm[0, 0],
                      fi[0, 0], num_rows // D, dtype=dtype)
        return jax.lax.psum(xw, MODEL_AXIS)

    coo_spec = P(DATA_AXIS, MODEL_AXIS, None)
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(MODEL_AXIS), coo_spec, coo_spec, coo_spec,
                  coo_spec, coo_spec),
        out_specs=P(DATA_AXIS),
        check_vma=False,  # pallas_call out_shape carries no vma
    )(w, sidx, sseg, sval, tmap, first)


def mesh_coo_spmv_t(mesh, d, sidx, sseg, sval, tmap, first,
                    num_buckets: int, dtype=None):
    """g = X^T d on a (data x model) mesh. d is row-sharded over the data
    axis; returns g table-sharded over the model axis. The psum over the
    data axis is the ZPush reduce."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from wormhole_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    M = mesh.shape[MODEL_AXIS]

    def local(d_l, si, ss, sv, tm, fi):
        g = coo_spmv_t(d_l, si[0, 0], ss[0, 0], sv[0, 0], tm[0, 0],
                       fi[0, 0], num_buckets // M, dtype=dtype)
        return jax.lax.psum(g, DATA_AXIS)

    coo_spec = P(DATA_AXIS, MODEL_AXIS, None)
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(DATA_AXIS), coo_spec, coo_spec, coo_spec,
                  coo_spec, coo_spec),
        out_specs=P(MODEL_AXIS),
        check_vma=False,  # pallas_call out_shape carries no vma
    )(d, sidx, sseg, sval, tmap, first)
