"""Plain reference: a factorization machine with admission by count,
trained by per-key FTRL on w and AdaGrad on V.

Test fixture (PR 30), not a reference of any cell: `tests/benchmark`
copies it to `benchmark/reference/` of a temporary copy to show that a
learner with vector rows, a second id space and a count table becomes a
cell by files alone. A later configuration's own reference may start from
it. numpy only, float32 state, sums accumulated in float64 and rounded
once; no import from the program. One step on a minibatch of binary
features X (rows x nnz keys), labels y:

    b = key mod num_buckets         the w-side id ("bucket")
    r = b mod v_buckets             the V-side id ("vrow")
    cnt[b] += occurrences of b      the count push, inside the step
    a_j = cnt[b_j] >= threshold     admission (and w[b_j] != 0, l1_shrk)
    xv = sum_j a_j V[r_j]           per row, [dim]
    f = <w, x> + 1/2 sum_k (xv_k^2 - sum_j a_j V[r_j]_k^2)
    obj = softplus(f) - y f ;  d = sigmoid(f) - y
    gw[b]  = sum of d over the rows that hold b
    gV[r]  = sum_j over nonzeros with r_j = r:  d a_j (xv - a_j V[r_j])
    w: FTRL as benchmark/reference/linear_ftrl.py has it, on the buckets
       the batch touches
    V: nV += gV^2 ;  V -= (gV + lambda_V V) / ((V_lr_beta + sqrt(nV))
       / V_lr_eta), on the rows with an admitted nonzero in the batch

Departures from upstream learn/difacto (async_sgd.h, loss.h), each the
program's (wormhole_tpu/models/difacto.py, docs/difacto.md):

  * fixed-capacity hashed tables: w, z, n, cnt over `num_buckets`, and V,
    nV over `v_buckets` with r = b mod v_buckets, where upstream keeps an
    exact entry a key whose V slice is allocated on admission. Admitted
    keys that share a row share its embedding.
  * the count push (upstream's kPushFeaCnt, a push of its own before the
    pull) is fused into the train step: admission sees the counts with
    this batch's occurrences already added.
  * one synchronous process: no max_delay, every step sees the last.
  * V's start is the program's (V_init_scale * normal from its own key):
    a run is followed from the rows read back before its first step;
    `draw_start` draws a start of the same law for the control, which
    may take nothing the program made.
  * grad_clipping, grad_normalization and dropout are off in the
    fixture's configuration and not implemented here.

`tables` other than f32 (the control) rounds all six tables to bfloat16
between steps, counts included.
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

F32 = np.float32


def space_ids(keys: np.ndarray, sizes: dict) -> dict:
    """The ids a batch's keys touch, by id space, in the keys' shape."""
    b = (keys % np.uint64(sizes["bucket"])).astype(np.int64)
    return {"bucket": b, "vrow": b % np.int64(sizes["vrow"])}


def _rounded(x: np.ndarray, name: str) -> np.ndarray:
    """x rounded (to nearest even) to the named precision, in float32."""
    if name == "f32":
        return x
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


def _sum_by(idx: np.ndarray, x: np.ndarray, n: int) -> np.ndarray:
    """Rows of x (float64) summed by idx into n rows."""
    order = np.argsort(idx, kind="stable")
    first = np.flatnonzero(np.diff(idx[order], prepend=-1))
    out = np.zeros((n,) + x.shape[1:])
    out[idx[order][first]] = np.add.reduceat(x[order], first, axis=0)
    return out


def _step(t: dict, lb, lv, label, hyper: dict) -> float:
    """One train step in place on the tables `t` (over the touched ids);
    lb, lv: (rows, nnz) local bucket and vrow indices. Returns the
    summed loss."""
    rows, nnz = lb.shape
    fb, fv = lb.reshape(-1), lv.reshape(-1)
    w, V = t["w"], t["V"]
    push = np.bincount(fb, minlength=len(w)).astype(F32)
    t["cnt"] = t["cnt"] + push
    admit = t["cnt"] >= F32(hyper["threshold"])
    if hyper.get("l1_shrk"):
        admit &= w != 0
    a = admit[fb]
    xw = w[lb].astype(np.float64).sum(1)
    Va = np.where(a[:, None], V[fv], F32(0)).astype(np.float64)
    xv = Va.reshape(rows, nnz, -1).sum(1)
    x2 = np.square(Va).reshape(rows, nnz, -1).sum(1)
    f = (xw + 0.5 * (xv * xv - x2).sum(-1)).astype(F32).astype(np.float64)
    y = label.astype(np.float64)
    obj = float(np.sum(np.logaddexp(0.0, f) - y * f))
    d = (1.0 / (1.0 + np.exp(-f)) - y).astype(F32).astype(np.float64)
    seg = np.repeat(np.arange(rows), nnz)
    g = np.bincount(fb, weights=d[seg], minlength=len(w)).astype(F32)
    contrib = (d[seg] * a)[:, None] * (xv[seg] - Va)
    gV = _sum_by(fv, contrib, len(V)).astype(F32)
    touched_v = np.bincount(fv, weights=a, minlength=len(V)) > 0

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

    nV = np.where(touched_v[:, None], t["nV"] + gV * gV, t["nV"])
    rate = (F32(hyper["V_lr_beta"]) + np.sqrt(nV)) / F32(hyper["V_lr_eta"])
    t["V"] = np.where(touched_v[:, None],
                      V - (gV + F32(hyper["lambda_V"]) * V) / rate, V)
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
    objs, states, touched = [], [], []
    with np.errstate(over="ignore"):
        for (keys, label), p in zip(batches, per):
            objs.append(_step(
                t, np.searchsorted(ids["bucket"], p["bucket"]),
                np.searchsorted(ids["vrow"], p["vrow"]),
                np.asarray(label, F32), hyper))
            for k in TABLES:
                t[k] = _rounded(t[k].astype(F32), precision["tables"])
            states.append({k: v.copy() for k, v in t.items()})
            touched.append({s: np.unique(p[s]) for s in SPACES})
    return {"ids": ids, "objv": objs, "states": states, "touched": touched,
            "gradient": (GRADIENT, TABLES[GRADIENT]["space"])}
