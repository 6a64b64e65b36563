"""Plain reference: a factorization machine with admission by count,
per-key FTRL on w and AdaGrad on V, at a published row width.

The mathematics of the `difacto` app (upstream learn/difacto async_sgd.h,
loss.h, config.proto; its Criteo-Terabyte job learn/difacto/guide/
criteo.conf) in straightforward numpy: float32 tables, sums accumulated in
float64 and rounded once, a block of rows at a time so that 100,000 rows x
39 keys x 50 floats fit a host. No Pallas, no pack, no import from the
program. One step on a minibatch of binary features (rows x nnz keys),
labels y:

    b = key mod num_buckets         the w-side id ("bucket")
    r = b mod v_buckets             the V-side id ("vrow")
    cnt[b] += occurrences of b      the count push, inside the step
    a_j = cnt[b_j] >= threshold     admission of nonzero j's key
    xv = sum_j a_j V[r_j]           per row, [dim]
    f = <w, x> + 1/2 sum_k (xv_k^2 - sum_j a_j V[r_j]_k^2)
    obj = softplus(f) - y f ;  d = sigmoid(f) - y
    gw[b] = sum of d over the rows that hold b
    gV[r] = sum over the admitted nonzeros j with r_j = r of
            d (xv - V[r])          = sum_j A_j - (sum_j B_j) V[r],
            A_j = d xv, B_j = d    (the form the program sums it in);
            divided by the batch's rows where `grad_normalization` is
            set (upstream loss.h:145-155)
    w: FTRL on the buckets the batch touches
       sigma = (sqrt(n + g^2) - sqrt(n)) / lr_eta
       z += g - sigma w ;  n += g^2
       w = -sgn(z) max(|z| - lambda_l1, 0)
           / ((lr_beta + sqrt(n)) / lr_eta + lambda_l2)
    V: on the rows with an admitted nonzero in the batch
       nV += gV^2 ;  V -= (gV + lambda_V V)
                          / ((V_lr_beta + sqrt(nV)) / V_lr_eta)

Precision is the configuration's, stated in its file under `precision`
and nothing lower: tables float32, every sum float32 or better. Six
operands are rounded to the kernel dtype exactly where the program's
compact step rounds them (models/difacto.py `_build_fm`,
ops/coo_kernels.py, ops/fused_update.py):

  pull_w   w at its fetch from the table (`tile_gather(..., dtype)`)
  pull_v   a V row at its way into the forward's key table (`Vcz`)
  push_d   the dual d at its fetch in the w push (`coo_spmv_t`)
  push_g   the summed w gradient at the fused update's scatter
  push_xv  a row's xv and d where the V push looks them up by nonzero
  push_ab  a nonzero's contributions A_j, B_j at the V push's scatter
           matmul; their sums, and the product with V[r], are float32

`tables` other than f32 is the control of the benchmark's check (all six
tables, counts included, stored in a lower precision between steps),
never a configuration.

Departures from upstream learn/difacto, each the program's
(wormhole_tpu/models/difacto.py, docs/difacto.md):

  * fixed-capacity hashed tables: w, z, n, cnt over `num_buckets`, and V,
    nV over `v_buckets` with r = b mod v_buckets, where upstream keeps an
    exact entry a uint64 key whose V slice is allocated on admission.
    Admitted keys that share a row share its embedding; each is admitted
    on its own bucket's count.
  * the count push (upstream's kPushFeaCnt, a push of its own that the
    weight pull of the same minibatch waits for, async_sgd.h:374-381) is
    part of the train step: admission sees the counts with this batch's
    occurrences already added. Upstream counts in its first pass only
    when told to (`prob_predict` apart); here every train step counts.
  * one synchronous process: no `max_delay`, every step sees the last.
  * V's start is the program's (V_init_scale * normal from its own key):
    a run is followed from the rows read back before its first step;
    `draw_start` draws a start of the same law for the control, which
    may take nothing the program made. Upstream draws a key's slice at
    its admission; here every row has its draw from the start, and an
    unadmitted key does not read it.
  * `lambda_V` is applied to a row only in a step that pushes to it (an
    admitted nonzero of the batch), as upstream's AdaGradHandle does.
  * grad_clipping, dropout and l1_shrk are off in the configuration and
    not implemented here.

What `correct` compares is declared here (benchmark/check.py): `SPACES`,
`TABLES`, `GRADIENT`, `space_ids`, `run_steps`, `draw_start`.
"""

from __future__ import annotations

import numpy as np

SPACES = {"bucket": "num_buckets", "vrow": "v_buckets"}
TABLES = {"z": {"space": "bucket", "zero_start": True},
          "n": {"space": "bucket", "zero_start": True},
          "w": {"space": "bucket", "zero_start": True},
          "cnt": {"space": "bucket", "zero_start": True},
          "V": {"space": "vrow", "zero_start": False},
          "nV": {"space": "vrow", "zero_start": True}}
# from zero z, n, w the FTRL table z after one step is the first gradient
GRADIENT = "z"

F32, F64 = np.float32, np.float64
# rows of a batch worked on at a time: 4,096 x 39 x 50 float64 is 64 MB
BLOCK_ROWS = 4096
# the rounded operands; a configuration that names none of them (its
# `precision` has `tables` alone) rounds nothing
OPERANDS = ("pull_w", "pull_v", "push_d", "push_g", "push_xv", "push_ab")


def space_ids(keys: np.ndarray, sizes: dict) -> dict:
    """The ids a batch's keys touch, by id space, in the keys' shape."""
    b = (keys % np.uint64(sizes["bucket"])).astype(np.int64)
    return {"bucket": b, "vrow": b % np.int64(sizes["vrow"])}


def _rounded(x: np.ndarray, name: str) -> np.ndarray:
    """x rounded (to nearest even) to the named precision, in float32."""
    if name == "f32":
        return np.asarray(x, F32)
    if name != "bf16":
        raise ValueError(f"no precision {name!r}")
    u = np.ascontiguousarray(x, F32).view(np.uint32)
    u = (u + ((u >> np.uint32(16)) & np.uint32(1)) + np.uint32(0x7FFF)) \
        & np.uint32(0xFFFF0000)
    return u.view(F32)


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer: a counter to 64 well-mixed bits."""
    with np.errstate(over="ignore"):
        x = (x + np.uint64(0x9E3779B97F4A7C15))
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def draw_start(ids: dict, sizes: dict, hyper: dict, seed: int) -> dict:
    """The leaves that do not start at zero, on `ids`: V as V_init_scale
    * normal, each element a function of (seed, row, column) alone, so
    that a row reads the same whichever set of ids it is asked in."""
    dim = int(hyper["dim"])
    with np.errstate(over="ignore"):
        c = (ids["vrow"].astype(np.uint64)[:, None] * np.uint64(dim)
             + np.arange(dim, dtype=np.uint64)) * np.uint64(2) \
            + _mix(np.full(1, seed, np.uint64))
    u1 = ((_mix(c) >> np.uint64(11)) + 1.0) / 2.0 ** 53
    u2 = (_mix(c + np.uint64(1)) >> np.uint64(11)) / 2.0 ** 53
    normal = np.sqrt(-2.0 * np.log(u1)) * np.cos(2.0 * np.pi * u2)
    return {"V": (hyper["V_init_scale"] * normal).astype(F32)}


def _add_by(out: np.ndarray, idx: np.ndarray, x: np.ndarray) -> None:
    """out[idx[j]] += x[j] (float64 rows), the rows summed by sorting."""
    order = np.argsort(idx, kind="stable")
    first = np.flatnonzero(np.diff(idx[order], prepend=-1))
    out[idx[order][first]] += np.add.reduceat(x[order], first, axis=0)


def _step(t: dict, lb, lv, label, hyper: dict, prec: dict) -> float:
    """One train step in place on the tables `t` (over the touched ids);
    lb, lv: (rows, nnz) local bucket and vrow indices. Returns the
    summed loss."""
    rows, nnz = lb.shape
    nb, nv = len(t["w"]), len(t["V"])
    push = np.bincount(lb.reshape(-1), minlength=nb).astype(F32)
    t["cnt"] = t["cnt"] + push
    admit = t["cnt"] >= F32(hyper["threshold"])
    w, V = t["w"], t["V"]
    wq, Vq = _rounded(w, prec["pull_w"]), _rounded(V, prec["pull_v"])

    obj = 0.0
    g = np.zeros(nb, F64)
    gA, gB = np.zeros(V.shape, F64), np.zeros(nv, F64)
    pushed_v = np.zeros(nv, bool)
    for r0 in range(0, rows, BLOCK_ROWS):
        b, v = lb[r0:r0 + BLOCK_ROWS], lv[r0:r0 + BLOCK_ROWS]
        y = label[r0:r0 + BLOCK_ROWS].astype(F64)
        a = admit[b]                                      # (rb, nnz)
        Va = np.where(a[:, :, None], Vq[v], F32(0))       # (rb, nnz, dim)
        xv = Va.sum(1, dtype=F64)
        x2 = np.square(Va).sum(1, dtype=F64)
        f = (wq[b].sum(1, dtype=F64)
             + 0.5 * (xv * xv - x2).sum(-1)).astype(F32).astype(F64)
        obj += float(np.sum(np.logaddexp(0.0, f) - y * f))
        d = (1.0 / (1.0 + np.exp(-f)) - y).astype(F32)
        # the w push: the dual of each row to each of its buckets
        dw = _rounded(d, prec["push_d"]).astype(F64)
        g += np.bincount(b.reshape(-1), weights=np.repeat(dw, nnz),
                         minlength=nb)
        # the V push, over the admitted nonzeros: A = d xv, B = d
        dv = _rounded(d, prec["push_xv"])
        xvq = _rounded(xv.astype(F32), prec["push_xv"])
        at = np.flatnonzero(a.reshape(-1))
        row_of, fv = at // nnz, v.reshape(-1)[at]
        _add_by(gA, fv, _rounded(dv[row_of, None] * xvq[row_of],
                                 prec["push_ab"]).astype(F64))
        gB += np.bincount(fv, weights=_rounded(dv, prec["push_ab"])
                          .astype(F64)[row_of], minlength=nv)
        pushed_v[fv] = True
    g = _rounded(g.astype(F32), prec["push_g"])
    gV = (gA.astype(F32) - gB.astype(F32)[:, None] * V).astype(F32)
    if hyper.get("grad_normalization"):
        gV = gV / F32(rows)

    eta, beta = F32(hyper["lr_eta"]), F32(hyper["lr_beta"])
    l1, l2 = F32(hyper["lambda_l1"]), F32(hyper["lambda_l2"])
    hit = push > 0
    z, n = t["z"], t["n"]
    sigma = (np.sqrt(n + g * g) - np.sqrt(n)) / eta
    z = np.where(hit, z + g - sigma * w, z)
    n = np.where(hit, n + g * g, n)
    solved = -np.sign(z) * np.maximum(np.abs(z) - l1, F32(0)) / (
        (beta + np.sqrt(n)) / eta + l2)
    t["z"], t["n"], t["w"] = z, n, np.where(hit, solved, w)

    pv = pushed_v[:, None]
    nV = np.where(pv, t["nV"] + gV * gV, t["nV"])
    rate = (F32(hyper["V_lr_beta"]) + np.sqrt(nV)) / F32(hyper["V_lr_eta"])
    t["V"] = np.where(pv, V - (gV + F32(hyper["lambda_V"]) * V) / rate, V)
    t["nV"] = nV
    return obj


def run_steps(batches, sizes: dict, hyper: dict, precision: dict,
              start: dict | None = None):
    """Train over `batches`, in order, from `start` (`ids`: by id space
    the sorted ids that hold every id the batches touch; `tables`: the
    leaves on them, a leaf left out starting at zero; V may not be left
    out). Each batch is (keys (rows, nnz) uint64, label (rows,)). Returns
    per step the summed loss, the touched ids of each batch by id space,
    after each step the six tables on all touched ids, and `gradient`:
    the `GRADIENT` leaf and its id space."""
    per = [space_ids(k, sizes) for k, _ in batches]
    ids = {s: np.unique(np.concatenate([p[s].reshape(-1) for p in per]))
           for s in SPACES}
    if start is None or "V" not in start["tables"]:
        raise ValueError("V does not start at zero: a start is needed")
    if any(not np.array_equal(ids[s], start["ids"][s]) for s in SPACES):
        raise ValueError("start holds other rows than the batches touch")
    dim = int(hyper["dim"])
    shape = {k: (len(ids[d["space"]]),) + ((dim,) if k in ("V", "nV") else ())
             for k, d in TABLES.items()}
    t = {k: np.array(start["tables"].get(k, np.zeros(shape[k])), F32)
         for k in TABLES}
    if any(t[k].shape != shape[k] for k in TABLES):
        raise ValueError("start's tables are not on its ids")
    prec = {**{k: "f32" for k in OPERANDS}, **precision}
    objs, states, touched = [], [], []
    with np.errstate(over="ignore"):
        for (keys, label), p in zip(batches, per):
            objs.append(_step(
                t, np.searchsorted(ids["bucket"], p["bucket"]),
                np.searchsorted(ids["vrow"], p["vrow"]),
                np.asarray(label, F32), hyper, prec))
            for k in TABLES:
                t[k] = _rounded(t[k].astype(F32), prec["tables"])
            states.append({k: v.copy() for k, v in t.items()})
            touched.append({s: np.unique(p[s]) for s in SPACES})
    return {"ids": ids, "objv": objs, "states": states, "touched": touched,
            "gradient": (GRADIENT, TABLES[GRADIENT]["space"])}
