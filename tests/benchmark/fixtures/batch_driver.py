"""A batch job behind the harness's seam (PR 43): a driver by the contract
of `benchmark/drivers/__init__.py` that runs `apps/lbfgs_linear`'s own
path (`load_batches`, `LinearObjFunction`, `LBFGSSolver`) over the
benchmark's generated rows. A test fixture, CPU only and tiny, NOT a
cell: it shows that `run.py` carries a job that is no minibatch-solver
run by a module its configuration names, and what such a module needs of
`run.py` (nothing but `T_START`; `say` and `memory_peak_bytes` come from
`benchmark.drivers`). tests/benchmark lays it into a copy of the
benchmark as `benchmark/drivers/batch_driver.py`. Since PR 46
test_benchmark_takes_a_cell.py also lays it into a copy of the real
`BENCHMARK.json`, beside which the copy's own tests stay green.

Set-up: rows from the seed (`gen.Dataset`), folded into `num_feature`
raw ids (`load_batches` takes int32 ids and Criteo keys are 64 bits:
what a real cell has to settle, PERF.md section 3) and written as libsvm;
`load_batches` makes them resident; one job of `max_lbfgs_iter`
iterations from w = 0 compiles every program, is followed for `correct`
and yields `val_logloss`. The window is that job again and again on the
same solver (a new one would compile its programs anew); a step of it is
one pass over the resident rows, an objective or a gradient evaluation,
and `train_ex_per_s` the rows of those passes a second. A traced run has
no device plane here: `plan` is not used.
"""

from __future__ import annotations

import os
import time
from types import SimpleNamespace

import numpy as np

from benchmark import gen, window
from benchmark.drivers import memory_peak_bytes, say

# the process's start: run.py hands over its own when it loads a driver
T_START = time.perf_counter()


class WindowClosed(Exception):
    """Raised from inside a pass once the window is over."""


class Passes:
    """The objective as the solver sees it: it forwards everything and
    notes each pass over the resident rows."""

    def __init__(self, obj, rows: int, clog, warns):
        self._obj, self._rows = obj, rows
        self._clog, self._warns = clog, warns
        self.grad_at: list = []     # set-up: where each gradient was taken
        self.seconds = None         # the window's length, once it may open
        self.t_open = None
        self.closed = False
        self.ends: list[float] = []
        self.rows: list[float] = []
        self.step_s: list[float] = []

    def __getattr__(self, name):
        return getattr(self._obj, name)

    def eval(self, w) -> float:
        return self._pass(self._obj.eval, w)

    def grad(self, w):
        import jax

        if self.seconds is None:
            self.grad_at.append(np.asarray(w))
        return self._pass(lambda p: jax.block_until_ready(self._obj.grad(p)),
                          w)

    def _pass(self, fn, w):
        if self.closed:
            raise WindowClosed()
        t0 = time.perf_counter()
        out = fn(w)
        t1 = time.perf_counter()
        if self.seconds is None:
            return out
        if self.t_open is None:
            # the first pass completed after set-up opens the window
            self.t_open = t1
            self._clog.phase = self._warns.phase = "window"
            return out
        self.ends.append(t1)
        self.rows.append(float(self._rows))
        self.step_s.append(t1 - t0)
        if t1 - self.t_open >= self.seconds:
            self.closed = True
            self._clog.phase = self._warns.phase = "after"
        return out


def fold(keys, num_feature: int):
    return (keys % np.uint64(num_feature)).astype(np.int64)


def write_libsvm(path: str, ids, label) -> None:
    with open(path, "w") as fh:
        for y, row in zip(label, ids):
            fh.write(f"{int(y)} " + " ".join(f"{i}:1" for i in row) + "\n")


def job(solver):
    """One training job from w = 0 on a solver that may have run one."""
    solver.S.clear()
    solver.Y.clear()
    solver.iter = 0
    solver.objv_history.clear()
    return solver.run(verbose=False)


def measure(cell, config, conf, traffic, work, seed, seconds, plan, clog,
            warns):
    from wormhole_tpu.models.batch_objectives import (LinearObjFunction,
                                                      load_batches)
    from wormhole_tpu.parallel.mesh import make_mesh
    from wormhole_tpu.solver.lbfgs import LBFGSConfig, LBFGSSolver

    minibatch, dim = int(conf["minibatch"]), int(conf["num_feature"])
    t0 = time.perf_counter()
    ds = gen.Dataset(work, gen.KeyModel(config["keys"]), seed,
                     traffic["data_format"], minibatch,
                     traffic["train_parts"], traffic["batches_per_part"],
                     traffic["val_parts"])
    ids, label = [], []
    for p in range(ds.train_parts):
        for j in range(ds.batches_per_part):
            keys, y = ds.batch(p, j)
            ids.append(fold(keys, dim))
            label.append(y)
            write_libsvm(os.path.join(work, f"rows-{p:03d}-{j}.libsvm"),
                         ids[-1], y)
    for p in range(ds.val_parts):
        rows = gen.Rows(ds.model, seed, gen.VAL_STREAM, p, minibatch)
        write_libsvm(os.path.join(work, f"held-{p:03d}.libsvm"),
                     fold(rows.keys(), dim), rows.label)
    say(f"data: {ds.train_rows} train + {ds.val_rows} val rows as libsvm, "
        f"ids folded into {dim}, in {time.perf_counter() - t0:.1f}s")
    mesh = make_mesh()
    nnz = int(conf["nnz_per_row"])
    batches, num_feature = load_batches(
        os.path.join(work, r"rows-\d+-\d+\.libsvm"), mesh, "libsvm",
        minibatch, nnz)
    held, _ = load_batches(os.path.join(work, r"held-\d+\.libsvm"), mesh,
                           "libsvm", minibatch, nnz)
    passes = Passes(LinearObjFunction(batches, num_feature, mesh),
                    ds.train_rows, clog, warns)
    solver = LBFGSSolver(passes, LBFGSConfig(
        max_iter=int(conf["max_lbfgs_iter"]), m=int(conf["m"])))
    t0 = time.perf_counter()
    w, _ = job(solver)
    val = LinearObjFunction(held, num_feature, mesh).eval(w) / ds.val_rows
    objv = list(solver.objv_history)
    say(f"fixed job: {time.perf_counter() - t0:.1f}s, {solver.iter} "
        f"iterations, {len(batches)} resident batches, {num_feature} "
        f"features, objective {objv[0]:.2f} -> {objv[-1]:.2f}")
    passes.seconds = float(seconds)
    clog.phase = warns.phase = "warmup"
    try:
        while True:
            job(solver)
    except WindowClosed:
        pass
    return SimpleNamespace(
        t_open=passes.t_open, ends=passes.ends, rows=passes.rows,
        step_s=passes.step_s, val_logloss=val, objv=objv,
        w1=passes.grad_at[1], ids=np.concatenate(ids),
        label=np.concatenate(label), num_feature=num_feature)


def result(run, seconds, warns, traffic) -> dict:
    peak = memory_peak_bytes()
    n = len(run.ends)
    if n < 2:
        raise SystemExit(f"batch_driver: {n} pass(es) completed in the "
                         "window")
    say(f"window: {n} passes, {sum(run.rows):.0f} rows in "
        f"{run.ends[-1] - run.t_open:.3f}s (nominal {seconds}s); set-up "
        f"{run.t_open - T_START:.1f}s")
    failed = warns.count("window")
    return {"correct": False, "attempted": n + failed, "failed": failed,
            "metrics": {}, "device": {"memory_peak_bytes": peak}}


def end_to_end(run) -> dict:
    return {"train_ex_per_s": window.rate(run.t_open, run.ends, run.rows),
            "val_logloss": run.val_logloss,
            "setup_s": run.t_open - T_START}


def batch(conf, config, run) -> dict:
    rows = int(conf["minibatch"])
    return {"rows": rows, "nnz": rows * int(conf["nnz_per_row"]),
            "num_feature": run.num_feature}


def first_step(ids, label, dim: int, c1=1e-4, rho=0.5):
    """The plain reference: one L-BFGS iteration of logistic regression
    from w = 0 in float64. With no history the direction is -g; the step
    is the first of 1, 1/2, 1/4, ... that meets the Armijo condition.
    Returns the objective before and after and the step's norm."""
    def f(p):
        xw = p[ids].sum(axis=1) + p[dim]
        return float(np.sum(np.logaddexp(0.0, xw) - label * xw))

    g = np.zeros(dim + 1)
    np.add.at(g, ids, (0.5 - label)[:, None])
    g[dim] = np.sum(0.5 - label)
    f0, alpha = f(np.zeros(dim + 1)), 1.0
    while f(-alpha * g) > f0 - c1 * alpha * (g @ g):
        alpha *= rho
    return f0, f(-alpha * g), float(np.linalg.norm(alpha * g))


def correct(config, run, clog):
    spec = config["correct"]
    f0, f1, step = first_step(run.ids, run.label.astype(np.float64),
                              run.num_feature)
    nums = {"objv0_gap": abs(run.objv[0] - f0) / f0,
            "objv1_gap": abs(run.objv[1] - f1) / f1,
            "step_norm_gap": abs(float(np.linalg.norm(run.w1)) - step) / step}
    lines, ok, compared = [], True, {}
    for k, limit in spec["limits"].items():
        good = nums[k] <= limit
        ok &= good
        compared[k] = [nums[k], limit]
        lines.append(f"{k} = {nums[k]:.6g}  (limit {limit:g})  "
                     f"{'ok' if good else 'FAILED'}")
    in_window = clog.compiles("window")
    lines.append(f"compilations inside the window = {in_window}  (limit 0)")
    ok &= in_window == 0
    compared["window_compiles"] = [in_window, 0]
    falling = run.objv[-1] < run.objv[0]
    lines.append(f"val_logloss = {run.val_logloss:.6f}  (limit < "
                 f"{spec['val_logloss_max']:.6f}); the fixed job's "
                 f"objective {'falling' if falling else 'NOT falling'}")
    ok &= run.val_logloss < spec["val_logloss_max"] and falling
    compared["val_logloss"] = [run.val_logloss, spec["val_logloss_max"]]
    return ok, lines, compared
