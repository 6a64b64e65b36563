"""Fused scatter + optimizer update over touched table tiles, in place.

The reference's server applies the update rule AT the key's storage when
a push arrives (learn/linear/async_sgd.h:160-180: FTRLHandle::Push
mutates the entry in the server's map). The TPU analog here: one Pallas
kernel walks the batch's TOUCHED table tiles (the tile-aligned compact
layout of ops/coo_kernels.pack_tile_coo), scatters the compact gradient
into each tile with an MXU one-hot matmul, applies the FTRL / AdaGrad /
SGD handle math to the whole (512, 128) tile, and writes the tile back
through aliased in/out buffers — so a training step performs NO XLA
element gathers or scatters of optimizer state at all, and untouched
tiles are never streamed.

Cost: the scatter's one-hot operands are built only over a block's live
slots (a prefix of the block, whose extent scatter_update derives on the
device; coo_kernels._live_chunks), so a touched tile costs its three
tiles in and out plus the handle math, whatever the block's capacity:
at 2^29 buckets, ~30 keys in each of 8,192 tiles, ~2.6 us a tile for
1.5 MB of traffic (measured on v5e, PERF.md §5). A block that both
opens and closes its tile's run skips the copy-through: the apply
overwrites every output tile anyway.

Semantics match models/linear._update exactly:
- FTRL: w is a pure function of (z, n); entries with zero gradient
  round-trip unchanged, so updating the whole tile is a no-op exactly
  where the reference would not receive a push.
- AdaGrad/SGD: repeated L1 shrinkage must only hit pushed keys, so the
  tile update is masked by g != 0 (the touched mask).
- fixed_bytes: the push-quantization filter applies to the scattered
  gradient before the update; the int8 mode's absmax scale is computed
  over the WHOLE compact gradient outside the kernel and passed in.
  With dtype=f32 numerics match parallel.kvstore.quantize_push
  bit-for-bit; with the bf16 MXU dtype the scatter matmul rounds the
  gradient to bfloat16 BEFORE _quantize runs, so int8 parity is only
  approximate there (bf16-of-int8-steps) — quantized + bf16 composes
  two roundings by design.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from wormhole_tpu.ops.coo_kernels import (_VMEM_LIMIT, BLK_U, LANES,
                                          TILE, TILE_HI, _live_chunks,
                                          _onehot, _onehot_t, _prec,
                                          _row_fetch, _use_interpret,
                                          block_extents)
from wormhole_tpu.ops.penalty import l1l2_solve


def _quantize(g, fixed_bytes: int, qscale):
    """In-kernel mirror of parallel.kvstore.quantize_push: bf16 rounding
    for fixed_bytes >= 2, global-absmax int8 for fixed_bytes == 1."""
    if fixed_bytes == 0:
        return g
    if fixed_bytes >= 2:
        return g.astype(jnp.bfloat16).astype(g.dtype)
    q = jnp.clip(jnp.round(g / qscale), -127, 127)
    # round-trip through int8 like quantize_push (values already integral)
    return q * qscale


def _apply(algo: str, z, n, w, g, touched, *, lr_eta, lr_beta,
           lambda_l1, lambda_l2):
    """The per-entry handle math of models/linear._update, on a tile."""
    if algo == "ftrl":
        sigma = (jnp.sqrt(n + g * g) - jnp.sqrt(n)) / lr_eta
        z2 = z + touched * (g - sigma * w)
        n2 = n + touched * g * g
        eta = (lr_beta + jnp.sqrt(n2)) / lr_eta
        w2 = l1l2_solve(-z2, eta, lambda_l1, lambda_l2)
        w2 = jnp.where(touched > 0, w2, w)
        return z2, n2, w2
    if algo == "adagrad":
        n2 = n + touched * g * g
        eta = (lr_beta + jnp.sqrt(n2)) / lr_eta
        w2 = l1l2_solve(eta * w - g, eta, lambda_l1, lambda_l2)
        w2 = jnp.where(touched > 0, w2, w)
        return None, n2, w2
    if algo == "sgd":
        eta = 1.0 / lr_eta
        w2 = l1l2_solve(eta * w - g, eta, lambda_l1, lambda_l2)
        w2 = jnp.where(touched > 0, w2, w)
        return None, None, w2
    raise ValueError(f"unknown algo {algo!r}")


def _kernel(tmap_ref, first_ref, last_ref, ext_ref, qscale_ref, g_ref,
            uniq_ref, *refs, algo: str, dtype, fixed_bytes: int,
            hyper: dict, n_state: int, with_add: bool):
    # refs = [add values (if with_add)] + state-in tiles (n_state, plus
    # the additive table last if with_add), then the matching out tiles,
    # then nw_out, then the g_acc scratch (+ add_acc scratch)
    add_ref = refs[0] if with_add else None
    refs = refs[1:] if with_add else refs
    n_tabs = n_state + (1 if with_add else 0)
    in_refs = refs[:n_tabs]
    out_refs = refs[n_tabs:2 * n_tabs]
    nw_ref = refs[2 * n_tabs]
    acc_ref = refs[2 * n_tabs + 1]
    add_acc = refs[2 * n_tabs + 2] if with_add else None
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        nw_ref[:] = jnp.zeros_like(nw_ref)

    @pl.when(first_ref[b] == 1)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        if with_add:
            add_acc[:] = jnp.zeros_like(add_acc)

    # copy-through so a tile whose run has further blocks flushes its
    # original values, never uninitialized VMEM; a block that also ends
    # its tile's run overwrites all of them below
    @pl.when((first_ref[b] == 1) & (last_ref[b] == 0))
    def _():
        for i_ref, o_ref in zip(in_refs, out_refs):
            o_ref[:] = i_ref[:]

    base = tmap_ref[b] * TILE

    def scatter(sl):
        local = uniq_ref[sl] - base
        hi = local >> 7
        lo = local & (LANES - 1)
        # sentinel slots (uniq == num_buckets) fall outside [0, TILE_HI)
        # and contribute all-zero one-hot rows — they scatter nothing
        e_t = _onehot_t(hi, TILE_HI, dtype)
        c_lo = _onehot(lo, LANES, dtype)
        acc_ref[:] += jax.lax.dot_general(
            e_t, (g_ref[sl][:, None] * c_lo).astype(dtype),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(dtype),
        )
        if with_add:
            # a second additive table (difacto's cnt) rides the same
            # one-hots: scattering it here replaces an XLA element
            # scatter into the full bucket table (~4 ms at the Criteo
            # shape). Occurrence counts above 256 would round in bf16,
            # so this matmul stays f32 regardless of the kernel dtype
            # (counts are integers — exact in f32 up to 2^24).
            add_acc[:] += jax.lax.dot_general(
                e_t.astype(jnp.float32),
                add_ref[sl][:, None] * c_lo.astype(jnp.float32),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )

    # only the block's live slots scatter; the first / last bookkeeping
    # above and the apply below run whatever the block holds
    _live_chunks(ext_ref[b], BLK_U, scatter)

    @pl.when(last_ref[b] == 1)
    def _():
        raw_g = acc_ref[:]
        g = _quantize(raw_g, fixed_bytes, qscale_ref[0])
        if algo == "ftrl":
            touched = 1.0
            z, n, w = in_refs[0][:], in_refs[1][:], in_refs[2][:]
        else:
            touched = (raw_g != 0).astype(jnp.float32)
            z = None
            n = in_refs[0][:] if algo == "adagrad" else None
            w = in_refs[n_state - 1][:]
        w_old = w if algo != "ftrl" else in_refs[2][:]
        z2, n2, w2 = _apply(algo, z, n, w, g, touched, **hyper)
        outs = {"ftrl": (z2, n2, w2), "adagrad": (n2, w2),
                "sgd": (w2,)}[algo]
        for o_ref, v in zip(out_refs[:n_state], outs):
            o_ref[:] = v
        if with_add:
            out_refs[n_state][:] = in_refs[n_state][:] + add_acc[:]
        delta = (jnp.sum((w2 != 0).astype(jnp.float32))
                 - jnp.sum((w_old != 0).astype(jnp.float32)))
        nw_ref[:] += delta


# ---------------------------------------------- embedding-row variants
# The difacto V table is [rows, dim] (dim 1..128, a power-of-two lane
# divisor). Viewed flat, a row occupies dim consecutive lanes and never
# straddles a (TILE_HI, 128) tile, so the same touched-tile streaming
# works with a per-row dim-wide lane window instead of a single lane.


def _row_window(off, dim: int, dtype):
    """(BLK_U, 128) mask of each row's dim-wide lane window at offset
    off (off is a multiple of dim for real rows)."""
    shift = dim.bit_length() - 1
    lanes = jax.lax.broadcasted_iota(jnp.int32, (off.shape[0], LANES), 1)
    return ((lanes >> shift) == (off[:, None] >> shift)).astype(dtype)


def _row_gather_kernel(tmap_ref, V_ref, uniq_ref, out_ref, *, dim, dtype):
    b = pl.program_id(0)
    lf = uniq_ref[:] * dim - tmap_ref[b] * TILE    # flat offset in tile
    hi = lf >> 7
    off = lf & (LANES - 1)
    # sentinel rows produce hi outside [0, TILE_HI): all-zero one-hot
    groups = _row_fetch(V_ref[:], hi, dtype)       # (BLK_U, 128)
    cols = [jnp.sum(groups * _onehot(off + j, LANES, dtype),
                    axis=1, keepdims=True) for j in range(dim)]
    out_ref[:] = jnp.concatenate(cols, axis=1)


def row_tile_gather(flat2, uniq_rows, tmap_u, dim: int, dtype=None):
    """Gather [row, dim] entries at tile-aligned compact row slots from a
    flat row-major table viewed (rows*dim//128, 128). Returns
    (u_cap, dim) f32 (zeros at sentinel holes)."""
    if dtype is None:
        dtype = jnp.bfloat16 if not _use_interpret() else jnp.float32
    assert LANES % dim == 0 and dim & (dim - 1) == 0, \
        "dim must be a power of two dividing 128"
    nb = tmap_u.shape[0]
    u_cap = nb * BLK_U
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((TILE_HI, LANES), lambda b, tmap: (tmap[b], 0)),
            pl.BlockSpec((BLK_U,), lambda b, *_: (b,)),
        ],
        out_specs=pl.BlockSpec((BLK_U, dim), lambda b, *_: (b, 0)),
    )
    return pl.pallas_call(
        partial(_row_gather_kernel, dim=dim, dtype=dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((u_cap, dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_use_interpret(),
        name="row_gather",
    )(tmap_u, flat2, uniq_rows)


def _v_update_kernel(tmap_ref, first_ref, last_ref, gV_ref, tch_ref,
                     uniq_ref, V_ref, nV_ref, V_out, nV_out, gacc, tacc,
                     *, dim, dtype, V_lr_eta, V_lr_beta, lambda_V):
    b = pl.program_id(0)

    @pl.when(first_ref[b] == 1)
    def _():
        gacc[:] = jnp.zeros_like(gacc)
        tacc[:] = jnp.zeros_like(tacc)
        V_out[:] = V_ref[:]
        nV_out[:] = nV_ref[:]

    lf = uniq_ref[:] * dim - tmap_ref[b] * TILE
    hi = lf >> 7
    off = lf & (LANES - 1)
    e_t = _onehot_t(hi, TILE_HI, dtype)
    # rhs: each compact row's dim gradient values at its lane window;
    # touched flags broadcast across the whole window (the reference
    # updates the entire [w,V] entry when a row is pushed). The lane
    # offset takes only LANES/dim distinct values (off = dim * residue),
    # and a row's target lane for channel j is exactly column
    # residue*dim + j — so concatenating the residue-masked gradients
    # IS the scatter image: no per-channel one-hot builds at all (the
    # former dim-iteration loop was this kernel's VPU wall).
    nres = LANES // dim
    res = off // dim
    masks = [(res == r).astype(jnp.float32)[:, None] for r in range(nres)]
    rhs = jnp.concatenate([gV_ref[:] * m for m in masks], axis=1)
    win = _row_window(off, dim, jnp.float32)
    gacc[:] += jax.lax.dot_general(
        e_t, rhs.astype(dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=_prec(dtype),
    )
    tacc[:] += jax.lax.dot_general(
        e_t, (tch_ref[:][:, None] * win).astype(dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=_prec(dtype),
    )

    @pl.when(last_ref[b] == 1)
    def _():
        g = gacc[:]
        tch = (tacc[:] > 0).astype(jnp.float32)
        nV, V = nV_ref[:], V_ref[:]
        nV2 = nV + tch * g * g
        etaV = (V_lr_beta + jnp.sqrt(nV2)) / V_lr_eta
        V2 = jnp.where(tch > 0, V - (g + lambda_V * V) / etaV, V)
        V_out[:] = V2
        nV_out[:] = nV2


def v_scatter_update(Vflat, nVflat, gV, vtouched, uniq_rows, tmap_u,
                     first_u, last_u, *, dim, V_lr_eta, V_lr_beta,
                     lambda_V, dtype=None):
    """AdaGrad update of the embedding table at the touched tiles, in
    place (difacto AdaGradHandle V branch, async_sgd.h:289-296): the
    compact [u_cap, dim] gradient is scattered into each touched tile of
    the flat table and the tile rewritten through aliased buffers.
    Returns (Vflat', nVflat')."""
    if dtype is None:
        dtype = jnp.bfloat16 if not _use_interpret() else jnp.float32
    assert LANES % dim == 0 and dim & (dim - 1) == 0, \
        "dim must be a power of two dividing 128"
    nb = tmap_u.shape[0]
    V2 = Vflat.reshape(-1, LANES)
    nV2 = nVflat.reshape(-1, LANES)
    n_rows2 = V2.shape[0]

    def tile_map(b, tmap, first, last):
        return (tmap[b], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((BLK_U, dim), lambda b, *_: (b, 0)),   # gV
            pl.BlockSpec((BLK_U,), lambda b, *_: (b,)),         # touched
            pl.BlockSpec((BLK_U,), lambda b, *_: (b,)),         # uniq rows
            pl.BlockSpec((TILE_HI, LANES), tile_map),           # V
            pl.BlockSpec((TILE_HI, LANES), tile_map),           # nV
        ],
        out_specs=[pl.BlockSpec((TILE_HI, LANES), tile_map),
                   pl.BlockSpec((TILE_HI, LANES), tile_map)],
        scratch_shapes=[pltpu.VMEM((TILE_HI, LANES), jnp.float32),
                        pltpu.VMEM((TILE_HI, LANES), jnp.float32)],
    )
    aliases = {3 + 3: 0, 3 + 4: 1}  # V, nV in -> out
    Vn, nVn = pl.pallas_call(
        partial(_v_update_kernel, dim=dim, dtype=dtype,
                V_lr_eta=V_lr_eta, V_lr_beta=V_lr_beta, lambda_V=lambda_V),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_rows2, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((n_rows2, LANES), jnp.float32)],
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_use_interpret(),
        name="v_update",
    )(tmap_u, first_u, last_u, gV, vtouched, uniq_rows, V2, nV2)
    return Vn.reshape(Vflat.shape), nVn.reshape(nVflat.shape)


def scatter_update(algo: str, state: dict, g, uniq, tmap_u, first_u,
                   last_u, *, lr_eta, lr_beta, lambda_l1, lambda_l2,
                   fixed_bytes: int = 0, dtype=None, add_table=None,
                   add_values=None):
    """Apply the algo's handle update to the touched tiles of the state
    tables, in place (aliased), driven by the tile-aligned compact
    gradient g. Returns (new_state, new_w) where new_w is the |w|_0
    delta of this step (reference progress.h new_w accounting).

    state holds flat (num_buckets,) tables: ftrl {w,z,n}, adagrad {w,n},
    sgd {w}. g/uniq are (u_cap,) from coo_spmv_t / pack_tile_coo.

    add_table/add_values: an optional extra ADDITIVE table in the same
    bucket space (difacto's cnt) updated as table[uniq] += values inside
    the same touched-tile walk; `state[add_table]` is replaced with the
    result."""
    if dtype is None:
        dtype = jnp.bfloat16 if not _use_interpret() else jnp.float32
    order = {"ftrl": ("z", "n", "w"), "adagrad": ("n", "w"),
             "sgd": ("w",)}[algo]
    n_state = len(order)
    with_add = add_table is not None
    if with_add:
        order = order + (add_table,)
    tabs = [state[k].reshape(-1, LANES) for k in order]
    nb = tmap_u.shape[0]
    num_buckets = tabs[0].shape[0] * LANES
    if fixed_bytes == 1:
        qscale = (jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / 127.0)[None]
    else:
        qscale = jnp.ones((1,), jnp.float32)
    hyper = dict(lr_eta=lr_eta, lr_beta=lr_beta, lambda_l1=lambda_l1,
                 lambda_l2=lambda_l2)

    def tile_map(b, tmap, *_):
        return (tmap[b], 0)

    ext = block_extents(uniq != num_buckets, BLK_U)
    add_specs = ([pl.BlockSpec((BLK_U,), lambda b, *_: (b,))]
                 if with_add else [])
    add_args = [add_values] if with_add else []
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((BLK_U,), lambda b, *_: (b,)),   # g
            pl.BlockSpec((BLK_U,), lambda b, *_: (b,)),   # uniq
        ] + add_specs
        + [pl.BlockSpec((TILE_HI, LANES), tile_map) for _ in tabs],
        out_specs=[pl.BlockSpec((TILE_HI, LANES), tile_map)
                   for _ in tabs] + [
            pl.BlockSpec((8, LANES), lambda b, *_: (0, 0))],
        scratch_shapes=[pltpu.VMEM((TILE_HI, LANES), jnp.float32)]
        + ([pltpu.VMEM((TILE_HI, LANES), jnp.float32)]
           if with_add else []),
    )
    out_shapes = [jax.ShapeDtypeStruct((num_buckets // LANES, LANES),
                                       jnp.float32) for _ in tabs] + [
        jax.ShapeDtypeStruct((8, LANES), jnp.float32)]
    # alias each state table input onto its output: flat input index =
    # 5 scalar-prefetch args + 2 (g, uniq) + optional add values +
    # table position
    base_in = 5 + 2 + (1 if with_add else 0)
    aliases = {base_in + i: i for i in range(len(tabs))}
    outs = pl.pallas_call(
        partial(_kernel, algo=algo, dtype=dtype, fixed_bytes=fixed_bytes,
                hyper=hyper, n_state=n_state, with_add=with_add),
        grid_spec=grid_spec,
        out_shape=out_shapes,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_use_interpret(),
        name="fused_update",
    )(tmap_u, first_u, last_u, ext, qscale, g, uniq, *add_args, *tabs)
    new_tabs, nw = outs[:-1], outs[-1]
    new_state = dict(state)
    for k, t in zip(order, new_tabs):
        new_state[k] = t.reshape(-1)
    return new_state, nw[0, 0]
