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
device; coo_kernels._live_chunks), so a touched tile costs its tiles in
and out plus the handle math, whatever the block's capacity. FTRL
streams two tiles in (z, n) and three out (z, n, w): at 2^29 buckets,
~30 keys in each of 8,192 tiles, ~2.3 us a tile for 1.25 MB of traffic,
of it ~2.2 the streams' and the scatter's and the rest handle math that
the DMA does not hide (measured on v5e, PERF.md §5, §6 PR 44; ~2.6 us
for 1.5 MB while w was read). A block that both opens and closes its
tile's run skips the copy-through: the apply overwrites every output
tile anyway.

Semantics are models/linear._update's, which runs the same
apply_handle over whole tables:
- FTRL: w is a pure function of (z, n), the derived table: the update
  forms the old weight from the z and n it holds and writes w without
  reading it. Entries with zero gradient round-trip unchanged, so
  updating the whole tile is a no-op exactly where the reference would
  not receive a push.
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
                                          _use_interpret, block_extents)
from wormhole_tpu.ops.penalty import l1l2_solve


# Rows of a tile the apply takes at a time, unrolled. Measured on v5e at
# 2^29 buckets (PERF.md §6, PR 44), ms a step / seconds the step's first
# call takes with the compile cache warm (every unrolled group is
# lowered again, in Python): 32 rows 18.88 / 1.40, 128 rows 18.95 /
# 1.01, 256 rows 19.53, the whole tile 19.96 / 0.87
APPLY_ROWS = 128


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


def ftrl_weight(z, sq, lr_eta, lr_beta, lambda_l1, lambda_l2):
    """FTRL's weight from z and sq = sqrt(n): the derived table."""
    return l1l2_solve(-z, (lr_beta + sq) / lr_eta, lambda_l1, lambda_l2)


def apply_handle(algo: str, z, n, w, g, touched, *, lr_eta, lr_beta,
                 lambda_l1, lambda_l2):
    """The per-entry handle math (reference async_sgd.h:71-180), on
    whatever shape the tables come in: a kernel's tile here, a whole
    table in models/linear._update. touched masks entries that received
    a push, so that regularizer shrinkage applies exactly when the
    reference's per-key Push would run. Returns (z2, n2, w2, w_old),
    None for a table the algo lacks; w_old is the weight before the
    update.

    FTRL does not read w (pass None): the stored weight is
    ftrl_weight of the stored z and n, written by this same rule one
    update earlier (or zero over zero tables), and forming it again is
    the same operations on the same values, the stored weight bit for
    bit. With lr_beta = 0 = lambda_l2 an entry with n = 0 divides by
    zero (docs/linear.md)."""
    if algo == "ftrl":
        hyper = (lr_eta, lr_beta, lambda_l1, lambda_l2)
        sq = jnp.sqrt(n)
        w = ftrl_weight(z, sq, *hyper)
        sigma = (jnp.sqrt(n + g * g) - sq) / lr_eta
        z2 = z + touched * (g - sigma * w)
        n2 = n + touched * g * g
        w2 = ftrl_weight(z2, jnp.sqrt(n2), *hyper)
        return z2, n2, jnp.where(touched > 0, w2, w), w
    if algo == "adagrad":
        n2 = n + touched * g * g
        eta = (lr_beta + jnp.sqrt(n2)) / lr_eta
        w2 = l1l2_solve(eta * w - g, eta, lambda_l1, lambda_l2)
        return None, n2, jnp.where(touched > 0, w2, w), w
    if algo == "sgd":
        eta = 1.0 / lr_eta  # constant step size lr_eta
        w2 = l1l2_solve(eta * w - g, eta, lambda_l1, lambda_l2)
        return None, None, jnp.where(touched > 0, w2, w), w
    raise ValueError(f"unknown algo {algo!r}")


def _kernel(tmap_ref, first_ref, last_ref, ext_ref, qscale_ref, g_ref,
            uniq_ref, *refs, algo: str, dtype, fixed_bytes: int,
            hyper: dict, n_state: int, with_add: bool):
    # refs = [add values (if with_add)] + state-in tiles (n_state, plus
    # the additive table last if with_add), then the matching out tiles,
    # then nw_out, then the g_acc scratch (+ add_acc scratch). FTRL's w
    # comes in as the whole table in HBM, aliased onto its output and
    # never read: the ref at `derived`
    add_ref = refs[0] if with_add else None
    refs = refs[1:] if with_add else refs
    n_tabs = n_state + (1 if with_add else 0)
    in_refs = refs[:n_tabs]
    out_refs = refs[n_tabs:2 * n_tabs]
    derived = 2 if algo == "ftrl" else None     # w among z, n, w
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
        for k, (i_ref, o_ref) in enumerate(zip(in_refs, out_refs)):
            if k == derived:
                o_ref[:] = ftrl_weight(in_refs[0][:],
                                       jnp.sqrt(in_refs[1][:]), **hyper)
            else:
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
        # the handle math APPLY_ROWS rows at a time, so that a row
        # group's values stay near the registers from its loads to its
        # stores: over the whole tile at once every intermediate is the
        # size of the register file and goes through VMEM
        def rows(i, count):
            r = pl.ds(pl.multiple_of(i * APPLY_ROWS, APPLY_ROWS),
                      APPLY_ROWS)
            raw_g = acc_ref[r, :]
            g = _quantize(raw_g, fixed_bytes, qscale_ref[0])
            if algo == "ftrl":
                touched = 1.0
                z, n, w = in_refs[0][r, :], in_refs[1][r, :], None
            else:
                touched = (raw_g != 0).astype(jnp.float32)
                z = None
                n = in_refs[0][r, :] if algo == "adagrad" else None
                w = in_refs[n_state - 1][r, :]
            z2, n2, w2, w_old = apply_handle(algo, z, n, w, g, touched,
                                             **hyper)
            outs = {"ftrl": (z2, n2, w2), "adagrad": (n2, w2),
                    "sgd": (w2,)}[algo]
            for o_ref, v in zip(out_refs[:n_state], outs):
                o_ref[r, :] = v
            if with_add:
                out_refs[n_state][r, :] = (in_refs[n_state][r, :]
                                           + add_acc[r, :])
            return count + ((w2 != 0).astype(jnp.float32)
                            - (w_old != 0).astype(jnp.float32))

        count = jax.lax.fori_loop(
            0, TILE_HI // APPLY_ROWS, rows,
            jnp.zeros((APPLY_ROWS, LANES), jnp.float32), unroll=True)
        nw_ref[:] += jnp.sum(count)


# ------------------------------------------------ vector rows, by lane line
# The difacto V table holds rows of dim floats, lane-packed at a stride
# that divides 128 (parallel/kvstore.TableSpec.stride): 128 // stride rows
# to a (128,) lane line, a row never straddling two. A batch touches a
# few hundred thousand rows spread evenly over millions, so a walk over
# touched 64k-entry tiles would stream the whole table (V and nV in and
# out: 4 x the table's bytes a step). The rows are fetched and written
# back by line instead: one XLA row gather / scatter of whole lines at
# the batch's sorted distinct lines, 512 B each, which costs by the
# batch and not by the table (PERF.md section 4 has both reckonings).


def row_gather(lines2, vlines):
    """The (len(vlines), 128) lane lines of a lane-packed table at the
    batch's distinct lines: sorted, distinct, padded past the table's
    end, where a line reads zero."""
    with jax.named_scope("row_gather"):
        return jnp.take(lines2, vlines, axis=0, mode="fill", fill_value=0,
                        unique_indices=True, indices_are_sorted=True)


def v_update(V2, nV2, Vl, gl, tl, vlines, *, V_lr_eta, V_lr_beta,
             lambda_V):
    """AdaGrad with L2 on the embedding rows the batch touched (difacto
    AdaGradHandle V branch, async_sgd.h:289-296), at their storage:
    Vl are the batch's lines of V as row_gather read them, gl the
    gradient and tl the touched flag (> 0 over a touched row's window)
    in the same lines; nV's lines are read here, and both tables written
    back by line (in place when the caller donates them). A lane past a
    row's width holds zero in Vl, gl and nV, and stays zero. Returns
    (V2', nV2')."""
    with jax.named_scope("v_update"):
        nVl = row_gather(nV2, vlines)
        tch = tl > 0
        nVn = jnp.where(tch, nVl + gl * gl, nVl)
        eta = (V_lr_beta + jnp.sqrt(nVn)) / V_lr_eta
        Vn = jnp.where(tch, Vl - (gl + lambda_V * Vl) / eta, Vl)
        put = dict(mode="drop", unique_indices=True,
                   indices_are_sorted=True)
        return (V2.at[vlines].set(Vn, **put),
                nV2.at[vlines].set(nVn, **put))


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
    # FTRL's w is derived from (z, n) and never read (apply_handle): it
    # stays in HBM, no DMA is issued for it, and the alias onto its
    # tile-mapped output brings untouched tiles through in place.
    # AdaGrad's and SGD's w is state, streamed in like the rest
    w_in = (pl.BlockSpec(memory_space=pl.ANY) if algo == "ftrl"
            else pl.BlockSpec((TILE_HI, LANES), tile_map))
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
        + [w_in if k == "w" else pl.BlockSpec((TILE_HI, LANES), tile_map)
           for k in order],
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
