"""What `correct` compares: the learner's first steps against the plain
reference, number by number, each beside its limit.

Set-up builds one learner and drives it from its starting tables through
its first train steps, through the solver's own loaders and the learner's own
`prepare_batch`/`stage_batch`/`train_batch` at the cell's real sizes; the
same learner then goes to the window. `FirstSteps` watches those steps
from the tap: which generated batch each step trained on (told by its
labels, since loader threads deliver in no fixed order), the loss the
step reported, and the tables read back on the touched rows after the
first step and after the last of them: the leaves the reference declares
(`reference/<model>.py`: `TABLES`, each with its id space and whether it
starts at zero; `GRADIENT`; `space_ids`), read by row from the mapping
the learner gives. This file names no table. A leaf that does not start
at zero is read once more, before the first step, on every row the
distinct train parts can touch, and the reference starts from what was
read. Once the window has closed, the reference trains on the same
batches in the same order and `numbers` sets the two side by side:

  loss_gap        largest relative gap of a step's summed loss (and the
                  step must have counted every row: `nex` is compared
                  exactly). Hardly moved by precision; there to catch rows
                  left out of a batch.
  grad_norm_gap   | ||g1|| - ||g1_ref|| | / ||g1_ref||, g1 the reference's
                  `GRADIENT` leaf after one step (linear FTRL: from zero
                  tables z after one step IS the first gradient as the
                  optimizer got it, sigma * w = 0, and every sum in it is
                  exact).
  grad_off_share  share of g1's values (a touched row of the `GRADIENT`
                  leaf after one step) off the reference's by more than
                  2^-12 of it. Where the summed gradient is rounded to
                  bfloat16 (`push_g`) a norm stands on the few hottest
                  rows, and one rounding step of one of them moves it as
                  far as a lane group of rows left out of the push does
                  (PERF.md section 2); the share counts such a step as one
                  row in some hundred thousand and the lane group as every
                  row it touches. Compared where the configuration's
                  limits name it.
  delta_norm_gap  worst leaf after the last step: gap of the norms of the
                  change from the start (zero, or what was read before the
                  first step), against the reference's norm of that leaf
                  or of the median leaf. There to catch a step that
                  returns its state unchanged.
  state_off_share worst leaf of the share of touched values (a vector row
                  counts element by element) whose value is
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
on its rows before and after. The reference makes the same step from
the state read before it; `served_numbers` compares the step's loss, the
norm of its change and the share of buckets that are off.

Limits are in the configuration's file (`correct.limits`), set from chip
readings given in PERF.md.
"""

from __future__ import annotations

import functools
import importlib

import numpy as np


def _ask(learner, name, *args):
    """What the harness asks of the learner, by name. The learner's own
    method where it has one; else the function of that name in
    `benchmark/learners/<module>.py` (`<module>` the last part of the
    module that defines the learner's class), which gets the learner
    first. The harness itself reads no store and no batch tuple."""
    own = getattr(learner, name, None)
    if callable(own):
        return own(*args)
    adapter = importlib.import_module(
        "benchmark.learners." + type(learner).__module__.rsplit(".", 1)[-1])
    return getattr(adapter, name)(learner, *args)


def tables(learner) -> dict:
    """Every table the learner keeps, as one mapping by name."""
    return _ask(learner, "tables")


def batch_kind(learner, b) -> str:
    return _ask(learner, "batch_kind", b)


def batch_label(learner, b) -> np.ndarray:
    """Labels of a prepared or staged batch, on the host."""
    return np.asarray(_ask(learner, "batch_label", b))


def __getattr__(name):
    # tests/test_linear_mesh_deploy.py lies outside the benchmark's paths,
    # so no benchmark PR can move it off this name: the linear
    # reference's declared leaves
    if name == "LEAVES":
        from benchmark.reference import linear_ftrl

        return tuple(linear_ftrl.TABLES)
    raise AttributeError(name)


def space_sizes(reference, conf: dict) -> dict:
    """The size of each id space the reference declares, from the conf
    keys as run."""
    return {s: int(conf[k]) for s, k in reference.SPACES.items()}


def union_ids(reference, sizes: dict, keys_list) -> dict:
    """By id space the sorted distinct ids that the given batches' keys
    touch."""
    per = [reference.space_ids(k, sizes) for k in keys_list]
    return {s: np.unique(np.concatenate([p[s].reshape(-1) for p in per]))
            for s in reference.SPACES}


@functools.cache
def _take():
    import jax
    import jax.numpy as jnp

    # rows along axis 0; for a 1-D table this lowers to the program that
    # `jnp.take(table, ids)` does (a test holds the two equal)
    return jax.jit(lambda table, ids: jnp.take(table, ids, axis=0))


def read_tables(tables, ids, capacity: int, decl: dict | None = None) -> dict:
    """The declared leaves `decl` (name -> its `space`) of the learner's
    `tables` on rows `ids[space]`, read back to the host; without `decl`
    every table of the mapping, on the one array `ids`. The ids are
    padded to `capacity` so that every run compiles the same gather."""
    if decl is None:
        decl, ids = {k: {"space": None} for k in tables}, {None: ids}
    padded = {}
    for s in {d["space"] for d in decl.values()}:
        padded[s] = np.zeros(max(capacity, len(ids[s])), np.int32)
        padded[s][:len(ids[s])] = ids[s]
    return {k: np.asarray(_take()(tables[k], padded[d["space"]]))
            [:len(ids[d["space"]])] for k, d in decl.items()}


def _rows_at(all_ids: dict, rows: dict, decl: dict, ids: dict) -> dict:
    """`rows` (leaf -> values on `all_ids[space]`) cut to `ids[space]`."""
    return {k: v[np.searchsorted(all_ids[decl[k]["space"]],
                                 ids[decl[k]["space"]])]
            for k, v in rows.items()}


START_CHUNK = 1 << 20


def _reference_and_sizes(reference, sizes):
    """The reference's module and its id spaces' sizes, from how callers
    give them: the module or one of its functions (older callers hand
    its hash kernel), and sizes by id space or, where the reference
    declares one space, that space's size alone."""
    if not hasattr(reference, "TABLES"):
        reference = importlib.import_module(reference.__module__)
    if not isinstance(sizes, dict):
        (space,) = reference.SPACES
        sizes = {space: int(sizes)}
    return reference, sizes


class FirstSteps:
    """Follows the learner's first `steps` train steps."""

    def __init__(self, dataset, sizes, steps: int, reference):
        self.ds, self.k = dataset, steps
        self.ref_mod, self.sizes = _reference_and_sizes(reference, sizes)
        self.decl = self.ref_mod.TABLES
        self.order: list[tuple[int, int]] = []
        self.objv: list[float] = []
        self.nex: list[float] = []
        self.grad1 = None
        self.final = None
        self.ids1 = self.ids = None
        # leaves that do not start at zero, on every row the distinct
        # train parts can touch, read before the first step
        self._start_ids = self._start_rows = None
        self.problem = None
        self.reference = None     # the reference's result, once it has run

    @property
    def done(self) -> bool:
        return len(self.order) >= self.k or self.problem is not None

    def before_step(self, learner) -> None:
        """Before the first train step. Where every leaf starts at zero
        nothing is read; else each such leaf on every row the distinct
        train parts can touch, `START_CHUNK` rows a gather, so that the
        same program serves every seed and a wide row's read-back stays
        small beside the tables."""
        if self._start_rows is not None:
            return
        self._start_rows = {}
        seeded = {k: d for k, d in self.decl.items() if not d["zero_start"]}
        if not seeded:
            return
        keys = [self.ds.batch(p, j)[0] for p in range(self.ds.train_parts)
                for j in range(self.ds.batches_per_part)]
        self._start_ids = union_ids(self.ref_mod, self.sizes, keys)
        tabs = tables(learner)
        for k, d in seeded.items():
            s, ids = d["space"], self._start_ids[d["space"]]
            chunk = min(START_CHUNK, self.sizes[s])
            self._start_rows[k] = np.concatenate([
                read_tables(tabs, {s: ids[a:a + chunk]}, chunk, {k: d})[k]
                for a in range(0, max(len(ids), 1), chunk)])

    def after_step(self, learner, b, out) -> None:
        if self.done:
            return
        pj = self.ds.by_label.get(batch_label(learner, b).tobytes())
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
        ids = union_ids(self.ref_mod, self.sizes,
                        [self.ds.batch(*o)[0] for o in self.order])
        if step == 1:
            g = self.ref_mod.GRADIENT
            self.ids1 = ids
            self.grad1 = read_tables(tables(learner), ids, cap,
                                     {g: self.decl[g]})[g]
        if step == self.k:
            self.ids = ids
            self.final = read_tables(tables(learner), ids, cap * step,
                                     self.decl)

    def start(self) -> dict | None:
        """What the reference starts from: None where every leaf starts
        at zero, else the rows read before the first step, on the ids the
        followed steps touched."""
        if not self._start_rows:
            return None
        return {"ids": self.ids, "tables": _rows_at(
            self._start_ids, self._start_rows, self.decl, self.ids)}

    def as_run(self) -> dict:
        return {"objv": self.objv, "nex": self.nex, "ids1": self.ids1,
                "grad1": self.grad1, "ids": self.ids, "final": self.final,
                "start": (self.start() or {}).get("tables", {})}


class ServedStep:
    """Follows one more train step once the window has closed: the next
    batch the window's own feed delivers (in a replay cell one served
    from the pack cache, which the first steps never are), with the
    tables read back on the batch's rows before and after it."""

    def __init__(self, dataset, sizes, reference):
        self.ds = dataset
        self.ref_mod, self.sizes = _reference_and_sizes(reference, sizes)
        self.seen = None
        self.problem = None

    def run(self, learner, b):
        pj = self.ds.by_label.get(batch_label(learner, b).tobytes())
        if pj is None:
            self.problem = ("the step after the window: its labels match "
                            "no generated batch")
            return learner.train_batch(b)
        keys = self.ds.batch(*pj)[0]
        ids = union_ids(self.ref_mod, self.sizes, [keys])
        decl = self.ref_mod.TABLES
        pre = read_tables(tables(learner), ids, keys.size, decl)
        out = learner.train_batch(b)
        post = read_tables(tables(learner), ids, keys.size, decl)
        self.seen = {"batch": pj, "ids": ids, "pre": pre, "post": post,
                     "objv": out["objv"], "nex": out["nex"]}
        return out


def served_numbers(run: dict, ref: dict) -> dict:
    """One step from a given state: `run` and `ref` hold `pre`, `post`
    (the declared leaves on the same rows), `objv` and `nex`.

      served_loss_gap   relative gap of the step's summed loss
      served_delta_gap  worst leaf: gap of the norms of the step's change,
                        against the reference's norm of that leaf's change
                        or of the median leaf's
      served_off_share  worst leaf: share of the batch's values off the
                        reference by more than 2^-12 of its value"""
    leaves = list(ref["post"])
    dn = {k: _norm(ref["post"][k].astype(np.float64) - ref["pre"][k])
          for k in leaves}
    floor = float(np.median(list(dn.values())))
    same = run["nex"] == ref["nex"]
    return {
        "served_loss_gap": abs(run["objv"] - ref["objv"]) / ref["objv"]
        if same else float("inf"),
        "served_delta_gap": max(
            abs(_norm(run["post"][k].astype(np.float64) - run["pre"][k])
                - dn[k]) / max(dn[k], floor, 1e-30) for k in leaves),
        "served_off_share": max(_off_share(run["post"][k], ref["post"][k])
                                for k in leaves),
    }


def reference_as_run(ref: dict, rows: int, start: dict | None = None) -> dict:
    """A reference result (reference/<model>.run_steps, started from
    `start`) in the shape of `FirstSteps.as_run`, so that the control
    can stand in the program's place."""
    g, space = ref["gradient"]
    ids1 = ref["touched"][0]
    pos = np.searchsorted(ref["ids"][space], ids1[space])
    return {"objv": ref["objv"], "nex": [float(rows)] * len(ref["objv"]),
            "ids1": ids1, "grad1": ref["states"][0][g][pos],
            "ids": ref["ids"], "final": ref["states"][-1],
            "start": (start or {}).get("tables", {})}


def _norm(x) -> float:
    """Euclidean norm over every element: a vector row counts element by
    element, a table of any shape as the flat list of its values."""
    return float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))


OFF_RELATIVE = 2.0 ** -12


def _off_share(p, r) -> float:
    """Share of the values (a vector row counts element by element) off
    the reference's by more than 2^-12 of it."""
    p, r = p.astype(np.float64), r.astype(np.float64)
    return float(np.mean(np.abs(p - r) > OFF_RELATIVE * np.abs(r)))


def _same_ids(a: dict, b: dict) -> bool:
    return a.keys() == b.keys() and all(
        np.array_equal(a[s], b[s]) for s in a)


def _change(side: dict, k: str):
    """A leaf's change from its start: the leaf itself where it started
    at zero."""
    if k not in side["start"]:
        return side["final"][k]
    return side["final"][k].astype(np.float64) - side["start"][k]


def numbers(run: dict, ref: dict) -> dict:
    """The compared numbers (and `state_rel_l2`, printed only); `run` is
    the program (or the control in its place), `ref` the float32
    reference, both as `as_run` gives them."""
    assert _same_ids(run["ids1"], ref["ids1"]) and _same_ids(
        run["ids"], ref["ids"]), "program and reference touched other rows"
    leaves = list(ref["final"])
    loss = [abs(p - r) / r if pn == rn else float("inf")
            for p, pn, r, rn in zip(run["objv"], run["nex"], ref["objv"],
                                    ref["nex"])]
    rn = {k: _norm(_change(ref, k)) for k in leaves}
    floor = float(np.median(list(rn.values())))
    return {
        "loss_gap": max(loss),
        "grad_norm_gap": abs(_norm(run["grad1"]) - _norm(ref["grad1"]))
        / _norm(ref["grad1"]),
        "grad_off_share": _off_share(run["grad1"], ref["grad1"]),
        "delta_norm_gap": max(abs(_norm(_change(run, k)) - rn[k])
                              / max(rn[k], floor) for k in leaves),
        "state_off_share": max(_off_share(run["final"][k], ref["final"][k])
                               for k in leaves),
        "state_rel_l2": max(_norm(run["final"][k].astype(np.float64)
                                  - ref["final"][k]) / max(rn[k], 1e-30)
                            for k in leaves),
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
