"""Gradient-histogram kernel for the GBDT learner, on the MXU.

The split search needs, per tree level, G[n, f, b] = sum of gradients of
the rows assigned to node n whose feature f falls in bin b (and the same
for hessians) — the quantity the reference's xgboost accumulates in
per-thread CPU histograms and rabit-allreduces (SURVEY §2.2). The
natural XLA formulation is a segment-sum scatter of rows x features
elements, which on TPU costs ~10 ns per element — ~0.6 s per level at
the HIGGS shape (2M x 28 x 256 bins), hopeless.

This kernel restates the histogram as matmuls so the MXU does the
accumulation. Three tricks set the shape:

- The node-one-hot operand arrives pre-transposed (the dot contracts
  over rows) and pre-weighted by the gradients.
- Gradients and hessians are split hi/lo into PAIRS of bf16 planes
  (g == g_hi + g_lo to ~f32 precision; the one-hot side is exact in
  bf16), and all four planes stack along the matmul's M axis:
  [g_hi; g_lo; h_hi; h_lo] x nodes rows. A single-pass bf16 matmul
  then computes G and H at once with the MXU's M dimension actually
  filled — per-level node counts (1..64) would otherwise pad to the
  128-row systolic height, and an f32 HIGHEST matmul would add 3-6
  decomposition passes on top.
- The per-feature bin one-hots are built per row-block inside the
  kernel (they would be rows x F x B materialized otherwise) and
  concatenated in channel groups so each dot has a wide N.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from wormhole_tpu.ops.coo_kernels import _use_interpret

HBLK = 4096   # rows per grid block
# features per in-kernel matmul group: the standalone-kernel lab favored
# one full-width group, but inside the fused round the production vmem
# budget favors 7 (tools/gbdt_hist_lab + whole-round A/B, r5)
FGROUP = 7


def _hist_kernel(s_ref, binned_ref, out_ref, *, F: int, B: int):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    bb = binned_ref[:].astype(jnp.int32)          # (HBLK, F)
    s = s_ref[:]                                  # (M, HBLK) bf16
    cols = jax.lax.broadcasted_iota(jnp.int32, (bb.shape[0], B), 1)
    for f0 in range(0, F, FGROUP):
        f1 = min(f0 + FGROUP, F)
        # cast route matters 2x: i1 -> f32 per part, then ONE f32 ->
        # bf16 pack over the concatenated group. The direct
        # astype(bfloat16) lowers as a multi-pass cast chain and
        # measured 17 ms/level vs 8.6 for this route at the HIGGS
        # shape (tools/gbdt_hist_lab.py, r5). Values are exactly
        # 0.0/1.0 either way.
        a = jnp.concatenate(
            [(jax.lax.slice_in_dim(bb, f, f + 1, axis=1) == cols)
             .astype(jnp.float32) for f in range(f0, f1)], axis=1)
        out_ref[:, f0 * B:f1 * B] += jax.lax.dot_general(
            s, a.astype(jnp.bfloat16),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def level_hist(binned, g, h, rel, num_nodes: int, B: int):
    """Per-level gradient/hessian histograms.

    binned: (rows, F) uint8 bin ids; g, h: (rows,) f32; rel: (rows,)
    int32 node of each row relative to the level (rows not in the level
    carry rel == num_nodes and contribute nothing). Returns
    (G, H): (num_nodes, F, B) f32, exact to the bf16 hi/lo split
    (~f32 precision).
    """
    rows, F = binned.shape
    nodes_p = max(8, num_nodes)
    rows_p = -(-rows // HBLK) * HBLK
    pad = rows_p - rows
    if pad:
        binned = jnp.pad(binned, ((0, pad), (0, 0)))
        g = jnp.pad(g, (0, pad))
        h = jnp.pad(h, (0, pad))
        rel = jnp.pad(rel, (0, pad), constant_values=num_nodes)
    sel = (jax.lax.broadcasted_iota(jnp.int32, (nodes_p, rows_p), 0)
           == rel[None, :])

    def planes(x):
        hi = x.astype(jnp.bfloat16)
        lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        zero = jnp.bfloat16(0)
        return (jnp.where(sel, hi[None, :], zero),
                jnp.where(sel, lo[None, :], zero))

    s = jnp.concatenate(planes(g) + planes(h), axis=0)   # (4*nodes_p, rows)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(rows_p // HBLK,),
        in_specs=[
            pl.BlockSpec((4 * nodes_p, HBLK), lambda b: (0, b)),
            pl.BlockSpec((HBLK, F), lambda b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((4 * nodes_p, F * B), lambda b: (0, 0)),
    )
    out = pl.pallas_call(
        partial(_hist_kernel, F=F, B=B),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((4 * nodes_p, F * B), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 2**20),
        interpret=_use_interpret(),
        name="level_hist",
    )(s, binned)
    G = (out[:nodes_p] + out[nodes_p:2 * nodes_p])[:num_nodes]
    H = (out[2 * nodes_p:3 * nodes_p] + out[3 * nodes_p:])[:num_nodes]
    return G.reshape(num_nodes, F, B), H.reshape(num_nodes, F, B)
