"""What `correct` compares: the learner's first steps against the plain
reference, number by number, each beside its limit.

Set-up builds one learner and drives it from zeroed tables through its
first train steps, through the solver's own loaders and the learner's own
`prepare_batch`/`stage_batch`/`train_batch` at the cell's real sizes; the
same learner then goes to the window. `FirstSteps` watches those steps
from the tap: which generated batch each step trained on (told by its
labels, since loader threads deliver in no fixed order), the loss the
step reported, and the tables z, n, w read back on the touched buckets
after the first step and after the last of them. Once the window has
closed, the reference trains on the same batches in the same order and
`numbers` sets the two side by side:

  loss_gap        largest relative gap of a step's summed loss (and the
                  step must have counted every row: `nex` is compared
                  exactly). Hardly moved by precision; there to catch rows
                  left out of a batch.
  grad_norm_gap   | ||z1|| - ||z1_ref|| | / ||z1_ref||: from zero tables z
                  after one step IS the first gradient as the optimizer got
                  it (sigma * w = 0), and every sum in it is exact.
  delta_norm_gap  worst leaf of z, n, w after the last step: gap of the
                  norms of the change from the (zero) start, against the
                  reference's norm of that leaf or of the median leaf. There
                  to catch a step that returns its state unchanged.
  state_off_share worst leaf of the share of touched buckets whose value is
                  off the reference's by more than 2^-12 of it: the number a
                  lower precision fails. Float32 summation order moves a
                  value by ~2^-20 of it; a table kept in bfloat16 moves
                  nearly every value by 2^-10..2^-8 of it.

Why a share and not a norm of the difference: the configuration rounds the
dual d and (compacted path) the summed gradient g to bfloat16, and a
rounding is a step function. Where the float32 sum the program made and
the one the reference made differ in their last bits (another order over a
bucket's thousands of rows) and lie on either side of a bfloat16 boundary,
that one bucket's g differs by a whole bfloat16 ulp, 2^-8 of it. On a hot
bucket, which carries a large part of ||z||, one such flip moves the
relative L2 error of the whole table to ~1e-3 — as large as the control's
(chip readings in PERF.md: 1 sound run in 10 read 1.6e-3 against the
others' 5.4e-8 and the control's 3.1e-3..5.2e-3). The share of buckets
that are off counts a flip as one bucket in some hundred thousand.
`state_rel_l2` is still printed, not compared. One kind of flip reaches
further: of w of a hot bucket at the pull. It moves the margin of every
row that holds the bucket (one row in six), with it those rows' duals,
and so the gradient of their rare buckets by 1e-4..1e-3 of it: the share
read 0.0096 in 1 sound run of 39 (0..8e-6 in the others) against the
control's 0.61, and the limit stands between the two.

The first steps come through the loaders and the pack; in a replay cell
the window's batches come from the pack cache instead. So one more step
is followed once the window has closed (`ServedStep`): the next batch the
window's feed delivers, whatever that feed is, with the tables read back
on its buckets before and after. The reference makes the same step from
the state read before it; `served_numbers` compares the step's loss, the
norm of its change and the share of buckets that are off.

Limits are in the configuration's file (`correct.limits`), set from chip
readings given in PERF.md.
"""

from __future__ import annotations

import functools

import numpy as np

LEAVES = ("z", "n", "w")


def batch_kind(b) -> str:
    return b[1] if b[0] == "staged" else b[0]


def batch_label(b) -> np.ndarray:
    """Labels of a prepared or staged batch, on the host."""
    if b[0] == "staged":
        return np.asarray(b[2][-2])
    if b[0] == "xla":
        return np.asarray(b[1].label)
    return np.asarray(b[-3])


@functools.cache
def _take():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda table, ids: jnp.take(table, ids))


def read_tables(state, ids: np.ndarray, capacity: int) -> dict:
    """z, n, w of the learner on bucket `ids`, read back to the host. The
    ids are padded to `capacity` so that every run compiles the same
    gather."""
    padded = np.zeros(max(capacity, len(ids)), np.int32)
    padded[:len(ids)] = ids
    return {k: np.asarray(_take()(state[k], padded))[:len(ids)]
            for k in LEAVES}


class FirstSteps:
    """Follows the learner's first `steps` train steps."""

    def __init__(self, dataset, num_buckets: int, steps: int,
                 bucket_ids):
        self.ds, self.num_buckets, self.k = dataset, num_buckets, steps
        self.bucket_ids = bucket_ids      # the reference's hash kernel
        self.order: list[tuple[int, int]] = []
        self.objv: list[float] = []
        self.nex: list[float] = []
        self.z1 = None
        self.final = None
        self.ids1 = self.ids = None
        self.problem = None
        self.reference = None     # the reference's result, once it has run

    @property
    def done(self) -> bool:
        return len(self.order) >= self.k or self.problem is not None

    def after_step(self, learner, b, out) -> None:
        if self.done:
            return
        pj = self.ds.by_label.get(batch_label(b).tobytes())
        if pj is None:
            self.problem = ("a train step's labels match no generated "
                            "batch: rows were dropped, reordered or split")
            return
        self.order.append(pj)
        self.objv.append(out["objv"])
        self.nex.append(out["nex"])
        step = len(self.order)
        if step not in (1, self.k):
            return
        cap = self.ds.minibatch * self.ds.batch(*pj)[0].shape[1]
        ids = np.unique(np.concatenate([
            self.bucket_ids(self.ds.batch(*o)[0], self.num_buckets)
            .reshape(-1) for o in self.order]))
        got = read_tables(learner.store.state, ids, cap * step)
        if step == 1:
            self.ids1, self.z1 = ids, got["z"]
        if step == self.k:
            self.ids, self.final = ids, got

    def as_run(self) -> dict:
        return {"objv": self.objv, "nex": self.nex, "ids1": self.ids1,
                "z1": self.z1, "ids": self.ids, "final": self.final}


class ServedStep:
    """Follows one more train step once the window has closed: the next
    batch the window's own feed delivers (in a replay cell one served
    from the pack cache, which the first steps never are), with the
    tables read back on the batch's buckets before and after it."""

    def __init__(self, dataset, num_buckets: int, bucket_ids):
        self.ds, self.num_buckets = dataset, num_buckets
        self.bucket_ids = bucket_ids
        self.seen = None
        self.problem = None

    def run(self, learner, b):
        pj = self.ds.by_label.get(batch_label(b).tobytes())
        if pj is None:
            self.problem = ("the step after the window: its labels match "
                            "no generated batch")
            return learner.train_batch(b)
        keys = self.ds.batch(*pj)[0]
        ids = np.unique(self.bucket_ids(keys, self.num_buckets))
        pre = read_tables(learner.store.state, ids, keys.size)
        out = learner.train_batch(b)
        post = read_tables(learner.store.state, ids, keys.size)
        self.seen = {"batch": pj, "ids": ids, "pre": pre, "post": post,
                     "objv": out["objv"], "nex": out["nex"]}
        return out


def served_numbers(run: dict, ref: dict) -> dict:
    """One step from a given state: `run` and `ref` hold `pre`, `post`
    (z, n, w on the same buckets), `objv` and `nex`.

      served_loss_gap   relative gap of the step's summed loss
      served_delta_gap  worst leaf: gap of the norms of the step's change,
                        against the reference's norm of that leaf's change
                        or of the median leaf's
      served_off_share  worst leaf: share of the batch's buckets off the
                        reference by more than 2^-12 of its value"""
    dn = {k: _norm(ref["post"][k].astype(np.float64) - ref["pre"][k])
          for k in LEAVES}
    floor = float(np.median(list(dn.values())))
    same = run["nex"] == ref["nex"]
    return {
        "served_loss_gap": abs(run["objv"] - ref["objv"]) / ref["objv"]
        if same else float("inf"),
        "served_delta_gap": max(
            abs(_norm(run["post"][k].astype(np.float64) - run["pre"][k])
                - dn[k]) / max(dn[k], floor, 1e-30) for k in LEAVES),
        "served_off_share": max(_off_share(run["post"][k], ref["post"][k])
                                for k in LEAVES),
    }


def reference_as_run(ref: dict, rows: int) -> dict:
    """A reference result (reference/<model>.run_steps) in the shape of
    `FirstSteps.as_run`, so that the control can stand in the program's
    place."""
    ids1 = ref["touched"][0]
    pos = np.searchsorted(ref["ids"], ids1)
    return {"objv": ref["objv"], "nex": [float(rows)] * len(ref["objv"]),
            "ids1": ids1, "z1": ref["states"][0]["z"][pos],
            "ids": ref["ids"], "final": ref["states"][-1]}


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))


OFF_RELATIVE = 2.0 ** -12


def _off_share(p, r) -> float:
    p, r = p.astype(np.float64), r.astype(np.float64)
    return float(np.mean(np.abs(p - r) > OFF_RELATIVE * np.abs(r)))


def numbers(run: dict, ref: dict) -> dict:
    """The compared numbers (and `state_rel_l2`, printed only); `run` is
    the program (or the control in its place), `ref` the float32
    reference, both as `as_run` gives them."""
    assert np.array_equal(run["ids1"], ref["ids1"]) and np.array_equal(
        run["ids"], ref["ids"]), "program and reference touched other buckets"
    loss = [abs(p - r) / r if pn == rn else float("inf")
            for p, pn, r, rn in zip(run["objv"], run["nex"], ref["objv"],
                                    ref["nex"])]
    rn = {k: _norm(ref["final"][k]) for k in LEAVES}
    floor = float(np.median(list(rn.values())))
    return {
        "loss_gap": max(loss),
        "grad_norm_gap": abs(_norm(run["z1"]) - _norm(ref["z1"]))
        / _norm(ref["z1"]),
        "delta_norm_gap": max(abs(_norm(run["final"][k]) - rn[k])
                              / max(rn[k], floor) for k in LEAVES),
        "state_off_share": max(_off_share(run["final"][k], ref["final"][k])
                               for k in LEAVES),
        "state_rel_l2": max(_norm(run["final"][k].astype(np.float64)
                                  - ref["final"][k]) / rn[k]
                            for k in LEAVES),
    }


def verdict(nums: dict, limits: dict) -> tuple[bool, list[str]]:
    """Each number beside its limit, and whether all hold."""
    lines, ok = [], True
    for name, limit in limits.items():
        v = nums[name]
        good = bool(np.isfinite(v) and v <= limit)
        ok &= good
        lines.append(f"{name} = {v:.6g}  (limit {limit:g})  "
                     f"{'ok' if good else 'OVER'}")
    for name in sorted(set(nums) - set(limits)):
        lines.append(f"{name} = {nums[name]:.6g}  (printed, not compared)")
    return ok, lines
