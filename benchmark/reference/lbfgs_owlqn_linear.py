"""Plain reference: L1-regularised logistic regression by OWL-QN L-BFGS.

The mathematics of upstream's `lbfgs-linear` job (learn/lbfgs-linear/
lbfgs.cc, linear.h on learn/solver/lbfgs.h) in straightforward numpy with
float64 sums. No device, no jit, no Gram matrix, and no import from the
program: nothing here is shared with `solver/lbfgs.py`, `ops/spmv.py` or
`models/batch_objectives.py`. The rows are resident: every pass reads all
of them. Over rows X (binary features), labels y, weights w with the bias
at w[num_feature]:

    xw   = X w + bias                        margins
    loss = sum softplus(xw) - y xw           logistic loss, summed
    g    = X^T (sigmoid(xw) - y), g[bias] = sum (sigmoid(xw) - y)
    F(w) = loss + reg_L2/2 |w|^2 + reg_L1 |w off the bias|_1

and one iteration from (w, g, F, the history S, Y of at most m pairs):

    pg   the pseudo-gradient of F at w (SetL1Dir, lbfgs.h:358-378): g
         + reg_L2 w + reg_L1 sign(w), and where w = 0 the subgradient
         nearest zero
    d    -H pg by the two-loop recursion over (S, Y), scaled by
         s.y / y.y of the newest pair (-pg while there is no pair), then
         zeroed wherever it does not point against pg (FixDirL1Sign)
    the orthant: sign(w), and -sign(pg) where w = 0
    backtracking from step 1 by factor .5: the first step a with
         F(P(w + a d)) <= F(w) + c1 a pg.d, c1 = 1e-4, P zeroing what
         left the orthant, off the bias (FixWeightL1Sign); at most
         `max_linesearch_iter` trials, after which the job stops where
         it stands; pg.d >= 0 drops the history and takes d = -pg
    s = w' - w, y = g(w') - g(w) (+ reg_L2 s), kept where s.y > 1e-10

A column no row holds has g = 0 and pg = 0 at w = 0, so it never leaves
zero: the reference keeps its vectors only over the columns the rows
touch and the bias (`ids`, sorted, the bias last), the same numbers a
dense vector of num_feature + 1 holds there, while a key's column is
`key mod num_feature` at the configuration's real `num_feature`.

Precision is the configuration's, stated in its file under `precision`
and nothing lower. It rounds where the program rounds: `tables` is what
the vectors w, g, s, y are kept in between operations (float32: every
elementwise result is rounded to it as the program's float32 arrays
are), `passes` what a row's margin and dual are rounded to. Every sum
(a margin's 39 terms, the loss, a column's gradient, a dot product) is
float64 over those rounded values. `tables` other than float32 is the
control of the benchmark's check (w, g, S, Y kept in bfloat16 between
iterations), never a configuration.

What `correct` compares is declared here: `SPACES`, `TABLES` (w, the
gradient g, and the newest pair s, y), `GRADIENT`, `space_ids`.
"""

from __future__ import annotations

import numpy as np

SPACES = {"feature": "num_feature"}
TABLES = {"w": {"space": "feature", "zero_start": True},
          "g": {"space": "feature", "zero_start": True},
          "s": {"space": "feature", "zero_start": True},
          "y": {"space": "feature", "zero_start": True}}
# g after one iteration: the gradient at the first accepted point
GRADIENT = "g"

C1, BACKOFF = 1e-4, 0.5
MAX_TRIALS = 20         # where `hyper` names no `max_linesearch_iter`
MIN_CURVATURE = 1e-10
# rows a block: a pass's temporaries are a block's, not the data's
BLOCK_ROWS = 1 << 17


def _rounded(x, name: str):
    """x rounded to the named precision, kept in float32."""
    x = np.asarray(x, np.float32)
    if name == "f32":
        return x
    if name != "bf16":
        raise ValueError(f"no precision {name!r}")
    u = np.ascontiguousarray(x).view(np.uint32)
    # round to nearest, ties to even, on the upper 16 bits
    u = (u + (((u >> np.uint32(16)) & np.uint32(1)) + np.uint32(0x7FFF))
         ) & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def feature_ids(keys: np.ndarray, num_feature: int) -> np.ndarray:
    """A raw 64-bit key's column: key mod num_feature."""
    return (keys % np.uint64(num_feature)).astype(np.int64)


def space_ids(keys: np.ndarray, sizes: dict) -> dict:
    """The ids a batch's rows touch: its keys' columns and the bias,
    which every row touches (flat, the bias last)."""
    nf = sizes["feature"]
    return {"feature": np.append(feature_ids(keys, nf).reshape(-1), nf)}


def _dot(a, b) -> float:
    return float(np.dot(a.astype(np.float64), b.astype(np.float64)))


class _Rows:
    """The resident rows over the compact ids, and the two passes."""

    def __init__(self, batches, ids, passes: str):
        nf = int(ids[-1])
        self.lidx = [np.searchsorted(ids, feature_ids(k, nf)).astype(
            np.int32) for k, _ in batches]
        self.label = [np.asarray(y, np.float64) for _, y in batches]
        self.n, self.passes = len(ids), passes

    def _blocks(self, skip=()):
        """(local ids, labels) a block of rows, over every batch but
        those `skip` names."""
        for b, (lidx, label) in enumerate(zip(self.lidx, self.label)):
            if b in skip:
                continue
            for a in range(0, len(label), BLOCK_ROWS):
                yield lidx[a:a + BLOCK_ROWS], label[a:a + BLOCK_ROWS]

    def _margin(self, w, lidx):
        xw = w[:-1].astype(np.float64)[lidx].sum(axis=1) + float(w[-1])
        return _rounded(xw, self.passes).astype(np.float64)

    def loss(self, w) -> float:
        tot = 0.0
        for lidx, label in self._blocks():
            xw = self._margin(w, lidx)
            tot += float(np.sum(np.logaddexp(0.0, xw) - label * xw))
        return tot

    def grad(self, w, skip=()):
        """g over every batch but those `skip` names (a planted fault)."""
        g = np.zeros(self.n, np.float64)
        for lidx, label in self._blocks(skip):
            xw = self._margin(w, lidx)
            d = _rounded(1.0 / (1.0 + np.exp(-xw)) - label,
                         self.passes).astype(np.float64)
            g[:-1] += np.bincount(lidx.reshape(-1),
                                  weights=np.repeat(d, lidx.shape[1]),
                                  minlength=self.n - 1)
            g[-1] += d.sum()
        return g


def _direction(pg, S, Y):
    """-H pg by the two-loop recursion, float64."""
    q = -pg.astype(np.float64)
    if not S:
        return q
    S64 = [s.astype(np.float64) for s in S]
    Y64 = [y.astype(np.float64) for y in Y]
    rho = [1.0 / float(s @ y) for s, y in zip(S64, Y64)]
    alpha = [0.0] * len(S)
    for i in range(len(S) - 1, -1, -1):
        alpha[i] = rho[i] * float(S64[i] @ q)
        q -= alpha[i] * Y64[i]
    q *= float(S64[-1] @ Y64[-1]) / float(Y64[-1] @ Y64[-1])
    for i in range(len(S)):
        beta = rho[i] * float(Y64[i] @ q)
        q += (alpha[i] - beta) * S64[i]
    return q


def run_steps(batches, sizes: dict, hyper: dict, precision: dict,
              start: dict | None = None, iters: int | None = None,
              fault: str | None = None):
    """OWL-QN over the rows of all `batches`, resident, from w = 0 or
    from `start`, for `iters` iterations (one a batch given where it is
    left out: how `control.py`, which knows a batch a step, reads). Each
    batch is (keys (rows, nnz) uint64, label (rows,)); features are
    binary. `sizes` holds the feature space's size. `start`: `ids` (by
    id space the sorted ids that hold every id the batches touch, the
    bias last), `tables` (w, g on them; a leaf left out is zero) and
    `history` ((S, Y), each (pairs, len(ids)), oldest first; left out,
    the pair `tables` holds as s, y is the history, if it has curvature).
    From a start the objective is evaluated there and g is taken as given.

    Returns `ids`, `objv` (the objective at the start and after each
    iteration), `trials` (the line search's count an iteration),
    `states[k]` (w, g and the newest pair s, y after iteration k + 1, on
    `ids`), `touched` (every iteration touches every id), `gradient`
    and `history` (S, Y as they stand at the end). `fault` plants one
    for the tests: `batch_left_out` (the last batch out of every
    gradient), `l1_left_out` (reg_L1 out of the objective) or
    `no_projection` (a trial point is not clipped to its orthant)."""
    nf = sizes["feature"] if isinstance(sizes, dict) else int(sizes)
    t, l1, l2 = precision["tables"], float(hyper["reg_L1"]), float(
        hyper["reg_L2"])
    m = int(hyper["m"])
    max_trials = int(hyper.get("max_linesearch_iter", MAX_TRIALS))
    iters = len(batches) if iters is None else int(iters)
    if start is None:
        ids = np.unique(np.concatenate(
            [space_ids(k, {"feature": nf})["feature"] for k, _ in batches]))
    else:
        ids = start["ids"]["feature"]
    rows = _Rows(batches, ids, precision["passes"])
    n = len(ids)
    mask = np.ones(n, np.float32)
    mask[-1] = 0.0                       # reg_L1 off the bias
    skip = (len(batches) - 1,) if fault == "batch_left_out" else ()

    def objective(w):
        o = rows.loss(w) + 0.5 * l2 * _dot(w, w)
        if l1 > 0 and fault != "l1_left_out":
            o += l1 * float(np.sum(np.abs(w).astype(np.float64) * mask))
        return o

    def gradient(w):
        return _rounded(rows.grad(w, skip), t)

    zero = np.zeros(n, np.float32)
    S: list = []
    Y: list = []
    if start is None:
        w, g = zero, gradient(zero)
    else:
        if ids[-1] != nf or any(
                (ids[li] != feature_ids(k, nf)).any()
                for li, (k, _) in zip(rows.lidx, batches)):
            raise ValueError("start holds other ids than the rows touch")
        tab = start["tables"]
        w = _rounded(tab.get("w", zero), t)
        g = _rounded(tab.get("g", zero), t)
        if "history" in start:
            S = [_rounded(s, t) for s in start["history"][0]]
            Y = [_rounded(y, t) for y in start["history"][1]]
        elif "s" in tab and _dot(tab["s"], tab["y"]) > MIN_CURVATURE:
            S, Y = [_rounded(tab["s"], t)], [_rounded(tab["y"], t)]
    def state():
        return {"w": w, "g": g, "s": S[-1] if S else zero,
                "y": Y[-1] if Y else zero}

    objv = [objective(w)]
    trials, states = [], []
    f32 = np.float32
    for _ in range(iters):
        gl = g + f32(l2) * w
        if l1 > 0:
            at_zero = np.where(gl - f32(l1) * mask > 0, gl - f32(l1) * mask,
                               np.where(gl + f32(l1) * mask < 0,
                                        gl + f32(l1) * mask, f32(0)))
            pg = np.where((w == 0) & (mask > 0), at_zero,
                          gl + f32(l1) * np.sign(w) * mask)
        else:
            pg = gl
        d = _direction(pg, S, Y).astype(f32)
        if l1 > 0:
            d = np.where(d * -pg > 0, d, f32(0))
        orthant = np.where(w != 0, np.sign(w), -np.sign(pg))
        gd = _dot(pg, d)
        if gd >= 0:
            S, Y = [], []
            d = -pg
            gd = _dot(pg, d)
        alpha, taken = 1.0, None
        for k in range(max_trials):
            trial = w + f32(alpha) * d
            if l1 > 0 and fault != "no_projection":
                trial = np.where((trial * orthant >= 0) | (mask == 0),
                                 trial, f32(0))
            o = objective(trial)
            if o <= objv[-1] + C1 * alpha * gd:
                taken = trial
                break
            alpha *= BACKOFF
        trials.append(k + 1)
        if taken is None:
            # the line search failed: the job stops where it stands
            objv.append(objv[-1])
            states.append(state())
            break
        w_new = _rounded(taken, t)
        g_new = gradient(w_new)
        s = _rounded(w_new - w, t)
        y = _rounded((g_new + f32(l2) * w_new) - (g + f32(l2) * w), t)
        if _dot(s, y) > MIN_CURVATURE:
            S, Y = (S + [s])[-m:], (Y + [y])[-m:]
        w, g = w_new, g_new
        objv.append(o)
        states.append(state())
    return {"ids": {"feature": ids}, "objv": objv, "trials": trials,
            "states": states, "touched": [{"feature": ids}] * len(states),
            "gradient": (GRADIENT, TABLES[GRADIENT]["space"]),
            "history": (S, Y)}
