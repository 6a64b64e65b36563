"""The batch-solver job: `apps/lbfgs_linear.make_solver` -> `load_batches`
(rows resident on the device) -> `LBFGSSolver.run`, stepped from its
`on_iter` hook. No loader, no minibatch: a step of this job is one
L-BFGS / OWL-QN iteration over all the resident rows, a gradient pass and
the objective passes of its line search.

Set-up (all of it `setup_s`): the program's `make_solver` resolved first,
so that a program without it ends at once, by name, before any data is
made; rows from the seed as crb parts (`gen.Dataset`, the held-out part
beside them); `make_solver`; then the job from w = 0. Its first
`setup_iters` iterations are set-up: every program compiles, the history
fills, the first `correct.steps` of them are followed for the reference
check (the vectors read back on the columns the rows touch, one small
gather each), and the held-out logloss is taken after the last of them,
on the state the window starts from. The window opens there and is the
same job going on: closed loop, nothing loaded, nothing compiled. It closes at the end of the
iteration in which `--seconds` pass, so it holds whole iterations (where
the clock cuts an iteration moves the rate by 3 %: a gradient pass costs
two objective passes). A job that ends inside it (the stop rule,
`max_lbfgs_iter`, a failed line search) starts again from w = 0 at once,
on the same solver, and the window goes on. `train_ex_per_s` is the rows
swept by all the window's passes, gradient and objective, over all its
seconds. With
a trace plan the profiler starts at the first iteration's end past `plan["at"]` and the
window ends at the first past `plan["seconds"]` more: whole iterations.

Once the window has closed the memory peak is read, then the state the
next iteration starts from (w, g, S, Y) is read back, that iteration is
run and w read after it: the served iteration, which the reference makes
again from the state read. It starts from a full history (m pairs), as
the window's iterations do: where the job ended with the window, the
next job runs on until it has one.

`benchmark/drivers/__init__.py` says what a driver gives `run.py`; this
one's `run` is the `Follow` that the solver called at every iteration's
end; its `hist_open` and `hist_close` hold the program's `lbfgs.*`
counters as they stood when the window opened and where its host-side
numbers end, in the shape `tap.hist_delta` takes (count, sum), so that a
reducer reads what the window added to them. It imports nothing of
`benchmark.run`.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
import os
import time

import numpy as np

from benchmark import check, gen
from benchmark.drivers import memory_peak_bytes, say

# the process's start: run.py hands over its own when it loads a driver
T_START = time.perf_counter()

ENTRY = "make_solver"
KIND = "resident"


def entry(config: dict):
    """The program's `make_solver`, or the run's end: at once, naming
    what the program lacks, before any data is made."""
    app = importlib.import_module(config["app"])
    fn = getattr(app, ENTRY, None)
    if fn is None:
        raise SystemExit(f"batch driver: {config['app']} has no {ENTRY}: "
                         "this program cannot run the configuration")
    return fn


def app_config(config: dict, conf: dict, **more):
    """The app's own configuration from the conf keys it knows (the
    rest are the driver's: `train_rows`, `val_rows`, `setup_iters`)."""
    mod, cls = config["config_class"].split(":")
    klass = getattr(importlib.import_module(mod), cls)
    known = {f.name for f in dataclasses.fields(klass)}
    return klass(**{k: v for k, v in conf.items() if k in known}, **more)


# --------------------------------------------------------------------- data
def make_data(work, traffic, conf, config, seed):
    """The train parts (a resident batch each part) and one held-out
    part, as crb; returns the dataset and the held-out part's pattern."""
    minibatch = int(conf["minibatch"])
    t0 = time.perf_counter()
    ds = gen.Dataset(work, gen.KeyModel(config["keys"]), seed,
                     traffic["data_format"], minibatch,
                     traffic["train_parts"], traffic["batches_per_part"], 0)
    if ds.train_rows != int(conf["train_rows"]):
        raise SystemExit(
            f"batch driver: the mix makes {ds.train_rows} train rows "
            f"({traffic['train_parts']} parts of "
            f"{traffic['batches_per_part']} x {minibatch}), the "
            f"configuration says {conf['train_rows']}")
    val_rows = int(conf["val_rows"])
    held = gen.Rows(ds.model, seed, gen.VAL_STREAM, 0, val_rows)
    gen.write_part(os.path.join(work, f"held-000.{ds.ext}"), held,
                   traffic["data_format"], val_rows)
    say(f"data: {ds.train_rows} train + {val_rows} val rows of "
        f"{traffic['data_format']} in {time.perf_counter() - t0:.1f}s")
    return ds, os.path.join(work, r"held-\d+\." + ds.ext)


# ------------------------------------------------------------------ the run
def counters() -> dict:
    """The program's `lbfgs.*` counters as they stand, each as (count,
    sum) with no sum: what `tap.hist_delta` takes the difference of."""
    from wormhole_tpu.obs.metrics import REGISTRY

    return {k: (v, 0.0) for k, v in REGISTRY.snapshot()["counters"].items()
            if k.startswith("lbfgs.")}


class Follow:
    """What the solver calls at each iteration's end (`on_iter`), and the
    run as `run.py` reads it. Phases: `setup` (the first `setup_iters`
    iterations; the first `steps` followed), `window`, `served` (one
    more iteration, the state read back before and w after), `done`."""

    def __init__(self, rows, ids, steps, setup_iters, full, seconds, plan,
                 clog, warns, val_logloss):
        import jax
        import jax.numpy as jnp

        self.rows, self.ids = rows, ids
        self._ids_dev = jnp.asarray(ids, jnp.int32)
        # one small gather a vector: the same program for every read
        self._take = jax.jit(lambda v, i: jnp.take(v, i))
        self.steps, self.setup_iters = steps, setup_iters
        self.full = full           # pairs of a full history: m
        self.seconds, self.plan = float(seconds), plan
        self._clog, self._warns = clog, warns
        self._val_logloss = val_logloss
        self.phase = "setup"
        self.jobs = 1
        self._pending = 2          # passes since the last iteration's end
        # set-up
        self.objv: list[float] = []
        self.trials: list[int] = []
        self.grad1 = self.final = None
        self.val_logloss = None
        self.t_job = time.perf_counter()
        # the window
        self.t_open = self.t_close = None
        self.ends: list[float] = []
        self.passes: list[int] = []
        self.step_s: list[float] = []
        self.window_jobs = 1
        self.history = 0
        self.peak = 0
        # what run.py's per_layer reads of a traced run
        self.hist_open = self.hist_close = None
        self.t_hist_close = None
        self.trace_t0 = self.trace_t1 = None
        self.trace_steps = 0
        # the served iteration
        self.pre = self.post = None
        self._pre_job = None
        self.served_objv = self.served_trials = None

    def read(self, v) -> np.ndarray:
        """A vector of the program on the columns the rows touch."""
        return np.asarray(self._take(v, self._ids_dev))

    def new_job(self) -> None:
        """The solver was reset: its next run starts with a gradient
        pass and an objective pass at w = 0."""
        self.jobs += 1
        self._pending += 2
        if self.phase == "window":
            self.window_jobs += 1

    def __call__(self, it, objv, trials, state) -> bool:
        now = time.perf_counter()
        passes, self._pending = self._pending + trials + 1, 0
        if self.phase == "setup":
            self._setup(it, objv, trials, state)
        elif self.phase == "window":
            self._window(now, passes, state)
        elif self.phase == "served":
            return self._served(objv, trials, state)
        return False

    # ------------------------------------------------------------- set-up
    def _setup(self, it, objv, trials, state):
        if not self.objv:
            self.objv.append(state["objv"][0])
        self.objv.append(objv)
        self.trials.append(trials)
        if it == 1:
            self.grad1 = self.read(state["g"])
        if it == self.steps:
            self.final = {"w": self.read(state["w"]),
                          "g": self.read(state["g"]),
                          "s": self.read(state["S"][-1]),
                          "y": self.read(state["Y"][-1])}
        if it < self.setup_iters:
            return
        self.val_logloss = self._val_logloss(state["w"])
        say(f"fixed job: {it} iterations, objective {self.objv[0]:.1f} -> "
            f"{objv:.1f}, trials {self.trials[:self.steps]} in the first "
            f"{self.steps}, held-out logloss {self.val_logloss:.6f} after "
            f"them, history {len(state['S'])}, "
            f"{time.perf_counter() - self.t_job:.1f}s")
        self.phase = "window"
        self._clog.phase = self._warns.phase = "window"
        self.hist_open = counters()
        self.t_open = time.perf_counter()

    # ------------------------------------------------------------- window
    def _window(self, now, passes, state):
        self.step_s.append(now - (self.ends[-1] if self.ends
                                  else self.t_open))
        self.ends.append(now)
        self.passes.append(passes)
        self.history = len(state["S"])
        if self.trace_t0 is not None:
            self.trace_steps += 1
            if now - self.trace_t0 >= self.plan["seconds"]:
                return self._close(now, state)
        elif now - self.t_open >= self.seconds:
            return self._close(now, state)
        elif self.plan is not None and now - self.t_open >= self.plan["at"]:
            import jax

            # the host-side numbers stand up to here: the profiler's
            # cost is in none of them; the window ends with the trace
            self.t_hist_close, self.hist_close = now, counters()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.plan["dir"],
                                     profiler_options=opts)
            self.trace_t0 = time.perf_counter()

    def _close(self, now, state):
        if self.trace_t0 is not None:
            import jax

            self.trace_t1 = time.perf_counter()
            jax.profiler.stop_trace()
        self.t_close = now
        if self.t_hist_close is None:
            self.t_hist_close, self.hist_close = now, counters()
        self._clog.phase = self._warns.phase = "after"
        self.peak = memory_peak_bytes()
        self.phase = "served"
        self._read_pre(state)

    # ------------------------------------------------------------- served
    def _read_pre(self, state):
        t0 = time.perf_counter()
        self.pre = {"w": self.read(state["w"]), "g": self.read(state["g"]),
                    "S": [self.read(s) for s in state["S"]],
                    "Y": [self.read(y) for y in state["Y"]],
                    "objv": state["objv"][-1]}
        self._pre_job = self.jobs
        self.t_pre = time.perf_counter() - t0

    def _served(self, objv, trials, state) -> bool:
        if self._pre_job != self.jobs or len(self.pre["S"]) < self.full:
            # the job ended with the window and another began: go on
            # until an iteration starts from a full history, as every
            # iteration of a window does whose job does not end in it
            self._read_pre(state)
            return False
        self.post = {"w": self.read(state["w"])}
        self.served_objv, self.served_trials = objv, trials
        self.phase = "done"
        return True


def measure(cell, config, conf, traffic, work, seed, seconds, plan, clog,
            warns):
    """Set-up and the window; the `Follow` that saw them is the run."""
    make_solver = entry(config)
    ds, held_pattern = make_data(work, traffic, conf, config, seed)
    reference = importlib.import_module(
        f"benchmark.reference.{config['reference']}")
    sizes = check.space_sizes(reference, conf)
    keys = [ds.batch(p, j)[0] for p in range(ds.train_parts)
            for j in range(ds.batches_per_part)]
    ids = check.union_ids(reference, sizes, keys)
    (space,) = reference.SPACES

    t0 = time.perf_counter()
    fmt = traffic["data_format"]
    solver, obj, batches, num_feature = make_solver(app_config(
        config, conf, data=ds.train_pattern, data_format=fmt))
    from wormhole_tpu.models.batch_objectives import load_batches

    val_rows = int(conf["val_rows"])
    held, _ = load_batches(held_pattern, obj.mesh, fmt, val_rows,
                           int(conf["nnz_per_row"]), 1, num_feature)
    held_obj = type(obj)(held, num_feature, obj.mesh)
    say(f"load: {len(batches)} resident batches of {conf['minibatch']} "
        f"rows, {num_feature} features, {len(ids[space])} columns "
        f"touched, {len(held)} held out, in "
        f"{time.perf_counter() - t0:.1f}s")

    run = Follow(ds.train_rows, ids[space],
                 int(config["correct"]["steps"]), int(conf["setup_iters"]),
                 int(conf["m"]), seconds, plan, clog, warns,
                 lambda w: held_obj.eval(w) / val_rows)
    run.ds, run.reference, run.sizes, run.space = ds, reference, sizes, space
    # the hyper-parameters as run: the configuration's, at a rehearsal's
    # own values where it has them
    run.hyper = {k: float(conf[k]) for k in config["hyper"]}
    run.kinds = {KIND if all(hasattr(x, "devices") for b in batches
                             for x in b) else "host"}
    run.num_feature, run.batches = num_feature, len(batches)
    solver.run(verbose=False, on_iter=run)
    while run.phase != "done":
        if run.phase == "setup":
            raise SystemExit(
                f"batch driver: the job ended after {solver.iter} "
                f"iterations, before the {conf['setup_iters']} of set-up")
        solver.reset()
        run.new_job()
        solver.run(verbose=False, on_iter=run)
    return run


def rate(run) -> float:
    """Rows swept a second: every pass of the window, gradient and
    objective, over the time from its opening to its close."""
    return sum(run.passes) * run.rows / (run.t_close - run.t_open)


def result(run, seconds, warns, traffic) -> dict:
    """The result line but for `correct` and the metrics; the memory
    peak is the one read when the window closed."""
    n = len(run.ends)
    if n < 2:
        raise SystemExit(f"batch driver: {n} iteration(s) completed in "
                         "the window")
    passes = sum(run.passes)
    took = run.t_close - run.t_open
    # beside the rate, for the reader: an objective pass costs about half
    # a gradient pass, so a line search's further trial lifts the rate
    two = [s for p, s in zip(run.passes, run.step_s) if p == 2]
    say(f"window: {run.window_jobs} job(s), {n} iterations, {passes} "
        f"passes ({n} gradient, {passes - n} objective), "
        f"{passes * run.rows} rows in {took:.3f}s (nominal {seconds}s): "
        f"{rate(run):.1f} examples/s; {len(two)} iterations of two "
        f"passes, {1e3 * sum(two) / max(len(two), 1):.1f} ms each; history "
        f"{run.history} at its end; longest iteration "
        f"{max(run.step_s):.3f}s; set-up {run.t_open - T_START:.1f}s")
    say(f"served iteration: history {len(run.pre['S'])} read back in "
        f"{run.t_pre:.1f}s, {run.served_trials} trial(s)")
    failed = warns.count("window")
    return {"correct": False, "attempted": passes + failed,
            "failed": failed, "metrics": {},
            "device": {"memory_peak_bytes": run.peak}}


def end_to_end(run) -> dict:
    return {"train_ex_per_s": rate(run),
            "val_logloss": run.val_logloss,
            "setup_s": run.t_open - T_START}


def batch(conf, config, run) -> dict:
    """What a kernel count reads: the resident rows' shapes, the
    vectors' length (the bias with them) and the basis of the Gram
    matrix as the window's last iteration had it."""
    rows = int(conf["train_rows"])
    return {"rows": rows, "nnz": rows * int(conf["nnz_per_row"]),
            "dim": run.num_feature + 1, "basis": 2 * run.history + 1,
            "uniq": len(run.ids), "hyper": dict(run.hyper)}


# ------------------------------------------------------------------ correct
def off_share(p, r, scale=None) -> float:
    """Share of p's values off r's by more than 2^-12 of `scale` (of
    r's own size where none is given: `check.numbers`' rule for a leaf)."""
    p, r = p.astype(np.float64), r.astype(np.float64)
    scale = np.abs(r) if scale is None else scale
    return float(np.mean(np.abs(p - r) > check.OFF_RELATIVE * scale))


def state_numbers(mine: dict, ref: dict) -> dict:
    """`check.numbers`, with `state_off_share` taken leaf by leaf and y
    held to the scale of what it is the difference of. y = g' - g, and at
    the deployment's size the two gradients agree to three digits: an
    error of 1e-6 of g, which `grad_off_share` passes 4,000 times over,
    is 1e-3 of y, so y's share by its own size reads a tenth to a half on
    sound float32 arithmetic and says little (`y_off_share`, printed).
    Each gradient is held to 2^-12 of itself, so their difference is held
    to 2^-12 of |g'| + |g| an entry (the reference's: g' is its g, g its
    g - y): y is then as exact as the gradients are, which is what the
    program can be held to. `state_off_share` is the worst of w, g, s by
    their own size and of y by that scale; each leaf's is printed."""
    nums = check.numbers(mine, ref)
    p, r = mine["final"], ref["final"]
    leaf = {k: off_share(p[k], r[k]) for k in ("w", "g", "s")}
    g = r["g"].astype(np.float64)
    leaf["y_by_g"] = off_share(p["y"], r["y"],
                               np.abs(g) + np.abs(g - r["y"]))
    nums["y_off_share"] = off_share(p["y"], r["y"])
    nums.update({f"off_share.{k}": v for k, v in leaf.items()})
    nums["state_off_share"] = max(leaf.values())
    return nums


def first_iterations(config, run, rows):
    """The set-up's first iterations against the reference on the same
    rows: the compared numbers, the two sides' trial counts, a line."""
    t0 = time.perf_counter()
    k = run.steps
    ref = run.reference.run_steps(rows, run.sizes, run.hyper,
                                  config["precision"], iters=k)
    ids = {run.space: run.ids}
    mine = {"objv": run.objv[:k + 1], "nex": [float(run.rows)] * (k + 1),
            "ids1": ids, "grad1": run.grad1, "ids": ids,
            "final": run.final, "start": {}}
    nums = state_numbers(mine, check.reference_as_run(ref, run.rows))
    line = (f"reference: {k} iterations over {run.rows} rows, "
            f"{len(run.ids)} columns, objective {ref['objv'][0]:.2f} -> "
            f"{ref['objv'][-1]:.2f}, {time.perf_counter() - t0:.1f}s "
            "(not in setup_s)")
    return nums, run.trials[:k], ref["trials"], line


def served_iteration(config, run, rows):
    """One more iteration by the reference from the state read back."""
    t0 = time.perf_counter()
    pre = run.pre
    ref = run.reference.run_steps(
        rows, run.sizes, run.hyper, config["precision"], iters=1,
        start={"ids": {run.space: run.ids},
               "tables": {"w": pre["w"], "g": pre["g"]},
               "history": (pre["S"], pre["Y"])})
    nums = check.served_numbers(
        {"pre": {"w": pre["w"]}, "post": run.post,
         "objv": run.served_objv, "nex": float(run.rows)},
        {"pre": {"w": pre["w"]}, "post": {"w": ref["states"][0]["w"]},
         "objv": ref["objv"][1], "nex": float(run.rows)})
    line = (f"served iteration: from the state read back (history "
            f"{len(pre['S'])}), objective {pre['objv']:.2f} -> "
            f"{run.served_objv:.2f} (the reference's "
            f"{ref['objv'][0]:.2f} -> {ref['objv'][1]:.2f}), "
            f"{time.perf_counter() - t0:.1f}s")
    return nums, [run.served_trials], ref["trials"], line


def correct(config, run, clog):
    """(i) the first iterations against the reference, trial counts
    equal; (ii) the served iteration likewise; (iii) no compilation
    inside the window; (iv) held-out logloss under ln 2 and the
    objective falling over the set-up's iterations; (v) the rows
    resident as the configuration says. Returns whether all hold, a
    line for each, and every number compared beside its limit."""
    spec = config["correct"]
    lines, ok, compared = [], True, {}

    def same_trials(name, mine, ref):
        nonlocal ok
        good = list(mine) == list(ref)
        ok &= good
        lines.append(f"{name}: line-search trials {list(mine)}  (the "
                     f"reference's {list(ref)})  "
                     f"{'ok' if good else 'DIFFER'}")
        compared[name.replace(" ", "_") + "_trials_equal"] = [int(good), 1]

    ds = run.ds
    rows = [ds.batch(p, j) for p in range(ds.train_parts)
            for j in range(ds.batches_per_part)]
    nums, mine, theirs, line = first_iterations(config, run, rows)
    good, ls = check.verdict(nums, spec["limits"])
    ok &= good
    lines += ls
    compared.update({k: [nums[k], v] for k, v in spec["limits"].items()})
    same_trials("first iterations", mine, theirs)
    lines.append(line)

    nums, mine, theirs, line = served_iteration(config, run, rows)
    good, ls = check.verdict(nums, spec["served_limits"])
    ok &= good
    lines += ls
    compared.update({k: [nums[k], v]
                     for k, v in spec["served_limits"].items()})
    same_trials("served iteration", mine, theirs)
    lines.append(line)

    in_window = clog.compiles("window")
    lines.append(f"compilations inside the window = {in_window}  (limit 0)")
    ok &= in_window == 0
    compared["window_compiles"] = [in_window, 0]
    val, objv = run.val_logloss, run.objv
    falling = all(math.isfinite(x) for x in objv) and all(
        b < a for a, b in zip(objv, objv[1:]))
    lines.append(f"val_logloss = {val:.6f}  (limit < "
                 f"{spec['val_logloss_max']:.6f}); the fixed job's "
                 f"objective {objv[0]:.1f} -> {objv[-1]:.1f} "
                 f"{'falling' if falling else 'NOT falling'} over its "
                 f"{len(objv) - 1} iterations")
    ok &= math.isfinite(val) and val < spec["val_logloss_max"] and falling
    compared["val_logloss"] = [val, spec["val_logloss_max"]]
    lines.append(f"batch kinds {sorted(run.kinds)}  (expected "
                 f"{config['expect_kind']!r})")
    ok &= run.kinds == {config["expect_kind"]}
    # a number that is not finite has no JSON: it is named
    return ok, lines, {k: [v if math.isfinite(v) else repr(v), lim]
                       for k, (v, lim) in compared.items()}
