"""Plain reference: sparse logistic regression trained by per-key FTRL.

The mathematics of the `linear` app (upstream learn/linear/async_sgd.h,
loss.h, penalty.h) in straightforward `jax.numpy`: float32 tables, float32
accumulation, `jax.default_matmul_precision("highest")`, segment sums and
dense gathers. No Pallas, no pack, no batching tricks, and no import from
the program. One step on a minibatch X (binary features, `val`), labels y:

    xw = X w                      margins
    obj = softplus(xw) - y xw     logistic loss, summed
    d = sigmoid(xw) - y           dual
    g = X^T d                     gradient per bucket
    sigma = (sqrt(n + g^2) - sqrt(n)) / lr_eta
    z += g - sigma w ;  n += g^2
    w = -sgn(z) max(|z| - lambda_l1, 0) / ((lr_beta + sqrt(n)) / lr_eta
                                           + lambda_l2)

A bucket no row of the batch touches has g = 0, so z and n stay and w,
a pure function of (z, n), stays too. The tables start at zero (or at a
state given on just those buckets), so the reference keeps them only
over the buckets the given batches touch — the
same numbers a dense table of `num_buckets` entries would hold there —
while bucket ids are taken mod the configuration's real `num_buckets`.

Precision is the configuration's, stated in its file under `precision`
and nothing lower: `tables` and accumulation float32; three operands are
rounded to `kernel_dtype` exactly where the program's kernels round them
(derived from ops/coo_kernels.py and ops/fused_update.py, PR 23):

  pull_w   w at the table fetch (`_row_fetch(..., dtype)` in the pull and
           tile-gather kernels); the product with `val` is rounded again
           in the dense pull, which is exact for binary features;
  push_d   the dual d at its fetch in the push kernel (`_row_fetch(d_ref)`),
           its product with `val` likewise;
  push_g   the summed gradient, where the compacted path scatters it into
           the touched tiles through a one-hot matmul
           (`fused_update._kernel`: `(g * c_lo).astype(dtype)`); the dense
           path updates in XLA and does not round it.

`tables` other than float32 is the control of the benchmark's check (the
tables stored in a lower precision between steps), never a configuration.

What `correct` compares is declared here and named nowhere in the harness
(PR 30): `SPACES`, the id spaces the tables are indexed by, each with the
conf key that holds its size; `TABLES`, the leaves compared, each with its
id space and whether it starts at zero; `GRADIENT`, the leaf that after
one step from the start is the first gradient as the optimizer got it;
`space_ids`, from a batch's keys to the ids of each space.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# (exponent bits, mantissa bits) of the precisions a file may state
_FORMATS = {"f32": None, "bf16": (8, 7)}


def _rounded(x, name: str):
    """x rounded to the named precision, kept in float32. Through
    `lax.reduce_precision`, which the compiler may not simplify away: a
    pair of converts f32 -> bf16 -> f32 it may, and on the TPU does
    (`xla_allow_excess_precision`; measured, PR 23: with converts the
    "bfloat16" control came out bit-equal to the float32 reference)."""
    fmt = _FORMATS[name]
    if fmt is None:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=fmt[0],
                                    mantissa_bits=fmt[1])


SPACES = {"bucket": "num_buckets"}
TABLES = {"z": {"space": "bucket", "zero_start": True},
          "n": {"space": "bucket", "zero_start": True},
          "w": {"space": "bucket", "zero_start": True}}
# from zero tables z after one step IS the first gradient: sigma * w = 0
GRADIENT = "z"


def bucket_ids(keys: np.ndarray, num_buckets: int) -> np.ndarray:
    """The hash kernel: a raw 64-bit key's bucket is key mod num_buckets
    (upstream localizer.h:107-115 under FLAGS_max_key)."""
    return (keys % np.uint64(num_buckets)).astype(np.int64)


def space_ids(keys: np.ndarray, sizes: dict) -> dict:
    """The ids a batch's keys touch, by id space, in the keys' shape."""
    return {"bucket": bucket_ids(keys, sizes["bucket"])}


def _step(z, n, w, lidx, seg, val, label, *, rows, hyper, prec):
    f32 = jnp.float32
    wq = _rounded(w, prec["pull_w"])[lidx]
    xw = jax.ops.segment_sum(_rounded(wq * val, prec["pull_w"]), seg,
                             num_segments=rows)
    obj = jnp.sum(jax.nn.softplus(xw) - label * xw)
    d = jax.nn.sigmoid(xw) - label
    c = _rounded(_rounded(d, prec["push_d"])[seg] * val, prec["push_d"])
    g = _rounded(jax.ops.segment_sum(c, lidx, num_segments=z.shape[0]),
                 prec["push_g"])
    eta, beta = f32(hyper["lr_eta"]), f32(hyper["lr_beta"])
    l1, l2 = f32(hyper["lambda_l1"]), f32(hyper["lambda_l2"])
    sigma = (jnp.sqrt(n + g * g) - jnp.sqrt(n)) / eta
    z = z + g - sigma * w
    n = n + g * g
    w = -jnp.sign(z) * jnp.maximum(jnp.abs(z) - l1, 0.0) / (
        (beta + jnp.sqrt(n)) / eta + l2)
    t = prec["tables"]
    return _rounded(z, t), _rounded(n, t), _rounded(w, t), obj


def run_steps(batches, sizes: dict, hyper: dict, precision: dict,
              start: dict | None = None):
    """Train over `batches`, in order, from zeroed tables or from `start`
    (`ids`: by id space the sorted ids that hold every id the batches
    touch; `tables`: z, n, w on them, a leaf left out starting at zero).
    `sizes` holds each id space's size. Each batch is (keys (rows, nnz)
    uint64, label (rows,)); features are binary. Returns per step the
    summed loss, the touched ids of each batch by id space, and after
    each step the tables on all touched ids: `ids` (by id space, sorted),
    `states[k]` = dict z, n, w after step k+1 (numpy, len(ids)), and
    `gradient`: the `GRADIENT` leaf and its id space. With one id space,
    `sizes` may be its size alone."""
    num_buckets = sizes["bucket"] if isinstance(sizes, dict) else int(sizes)
    idx = [bucket_ids(k, num_buckets) for k, _ in batches]
    ids = np.unique(np.concatenate([i.reshape(-1) for i in idx]))
    if start is None:
        z = n = w = jnp.zeros(len(ids), jnp.float32)
    else:
        if not np.array_equal(ids, start["ids"]["bucket"]):
            raise ValueError("start holds other buckets than the batches "
                             "touch")
        zero = np.zeros(len(ids), np.float32)
        z, n, w = (jnp.asarray(start["tables"].get(k, zero), jnp.float32)
                   for k in "znw")
    objs, states, touched = [], [], []
    step = jax.jit(functools.partial(_step, hyper=dict(hyper),
                                     prec=dict(precision)),
                   static_argnames=("rows",))
    with jax.default_matmul_precision("highest"):
        for (keys, label), gi in zip(batches, idx):
            rows, nnz = keys.shape
            lidx = np.searchsorted(ids, gi.reshape(-1)).astype(np.int32)
            seg = np.repeat(np.arange(rows, dtype=np.int32), nnz)
            z, n, w, obj = step(z, n, w, lidx, seg,
                                np.ones(rows * nnz, np.float32),
                                np.asarray(label, np.float32), rows=rows)
            objs.append(float(obj))
            states.append({"z": np.asarray(z), "n": np.asarray(n),
                           "w": np.asarray(w)})
            touched.append({"bucket": np.unique(gi)})
    return {"ids": {"bucket": ids}, "objv": objs, "states": states,
            "touched": touched,
            "gradient": (GRADIENT, TABLES[GRADIENT]["space"])}
