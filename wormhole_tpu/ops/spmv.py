"""Sparse matrix x vector/matrix products on device.

The reference's OpenMP CSR kernels (learn/base/spmv.h:72-119, spmm.h:41-123)
become XLA gather + segment-sum on a fixed-shape COO DeviceBatch: the
general formulation, which runs on any backend, mesh and table size, and
whose transposed product lands directly in the (sharded) parameter table
layout. It is not the fast one on a TPU: XLA lowers a gather or a
scatter-add over millions of unsorted ids to a sort and serial fusions
(23.7 and 43.3 ns a nonzero for the batch solver's two passes at 2^26
columns, 0.1 % and 0.07 % of their rooflines: PERF_LEDGER.jsonl, PR 47,
`lbfgs1tb.resident`). Where the batch can be sorted by column ahead of
time the same two products run on the packed-COO Pallas kernels of
ops/coo_kernels.py (the minibatch learners on a TPU, and the batch
solver's linear objective since PR 48).

All functions are jit-safe (static shapes, no Python branching on values).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def spmv(seg, idx, val, w, num_rows: int):
    """y[i] = sum_{j in row i} val[j] * w[idx[j]]   (SpMV::Times parity).

    seg/idx/val are a DeviceBatch's COO arrays; padding has val==0 so it
    contributes nothing."""
    return jax.ops.segment_sum(val * jnp.take(w, idx, axis=0), seg,
                               num_segments=num_rows)


def spmv_t(seg, idx, val, d, table_size: int):
    """g = Dᵀ d scattered into a dense table: g[k] = sum_{j: idx[j]=k}
    val[j] * d[seg[j]]   (SpMV::TransTimes parity, output is the gradient
    in parameter-table layout)."""
    return jax.ops.segment_sum(val * jnp.take(d, seg, axis=0), idx,
                               num_segments=table_size)


def spmm(seg, idx, val, V, num_rows: int):
    """Y = D V for a dense k-column block V[table, k]
    (SpMM::Times parity, spmm.h:41-52): Y[i, :] = sum_j val[j] * V[idx[j], :]."""
    contrib = val[:, None] * jnp.take(V, idx, axis=0)
    return jax.ops.segment_sum(contrib, seg, num_segments=num_rows)


def spmm_t(seg, idx, val, D, table_size: int):
    """G = Xᵀ D for dense D[num_rows, k] (SpMM::TransTimes parity):
    G[key, :] = sum_{j: idx[j]=key} val[j] * D[seg[j], :]."""
    contrib = val[:, None] * jnp.take(D, seg, axis=0)
    return jax.ops.segment_sum(contrib, idx, num_segments=table_size)


def row_squares(seg, idx, val, V, num_rows: int):
    """sum_j val[j]^2 * V[idx[j], :]^2 per row — the (X^2)(V^2) term of the
    FM quadratic part (reference difacto/loss.h:62-84)."""
    contrib = (val ** 2)[:, None] * jnp.take(V, idx, axis=0) ** 2
    return jax.ops.segment_sum(contrib, seg, num_segments=num_rows)
