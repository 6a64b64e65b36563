"""The minibatch-solver job: `run_minibatch_app` -> `MinibatchSolver` ->
loader threads -> `train_batch` (`apps/linear.py`, `apps/difacto.py`).

Set-up (all of it `setup_s`): data from the seed, the learner through the
program's own entry, the fixed pass (one train pass over the distinct
parts and one val pass: compiles, warms every shape, follows the first
steps for the reference check, yields `val_logloss`), then a second
solver run over the long pass with the same learner, whose warm-up passes
(if the traffic has any) still count as set-up. The window opens at the
first train step completed after that; the step that crosses `--seconds`
is its last. One more batch, delivered the way the window's were, is
then followed for the served-step check, and the run ends.

`benchmark/drivers/__init__.py` says what a driver gives `run.py`; this
one's `run` is the `tap.Tap` the solver saw as its learner, which carries
the first steps, the served step and the reference they are held against.
"""

from __future__ import annotations

import importlib
import math
import os
import time

from benchmark import check, gen, tap as tp, window
from benchmark.drivers import memory_peak_bytes, say

# the process's start: run.py hands over its own when it loads a driver
T_START = time.perf_counter()


# -------------------------------------------------------------------- conf
def write_conf(path: str, kv: dict) -> str:
    with open(path, "w") as fh:
        for k, v in kv.items():
            fh.write(f"{k} = {v}\n")
    return path


def run_app(config: dict, conf_path: str, make_learner):
    """One job the way `python -m <app> conf` runs it."""
    from wormhole_tpu.apps._runner import parse_cli, run_minibatch_app

    mod, cls = config["config_class"].split(":")
    cfg = parse_cli(getattr(importlib.import_module(mod), cls), [conf_path])
    return run_minibatch_app(cfg, make_learner)


# --------------------------------------------------------------------- run
def measure(cell, config, conf, traffic, work, seed, seconds, plan, clog,
            warns):
    """Set-up and the window; the tap that saw them is the run."""
    ds = make_data(work, traffic, conf, config, seed)
    reference = importlib.import_module(
        f"benchmark.reference.{config['reference']}")
    sizes = check.space_sizes(reference, conf)
    first = check.FirstSteps(ds, sizes, config["correct"]["steps"],
                             reference)
    served = check.ServedStep(ds, sizes, reference)
    tap = drive(config, traffic, conf, ds, seconds, plan,
                tp.Tap(None, clog, warns, first, served))
    tap.reference = reference
    return tap


def make_data(work, traffic, conf, config, seed):
    minibatch = int(conf["minibatch"])
    t0 = time.perf_counter()
    ds = gen.Dataset(work, gen.KeyModel(config["keys"]), seed,
                     traffic["data_format"], minibatch,
                     traffic["train_parts"], traffic["batches_per_part"],
                     traffic["val_parts"])
    # a rehearsal's smaller batches need fewer rows to outlast the window
    scale = minibatch / int(config["conf"]["minibatch"])
    links = ds.link_until(int(traffic["min_pass_rows"] * scale))
    say(f"data: {ds.train_rows} train + {ds.val_rows} val rows of "
        f"{traffic['data_format']} in {time.perf_counter() - t0:.1f}s; "
        f"distinct parts linked {links}x more: one pass holds "
        f"{ds.train_rows * (1 + links)} rows")
    return ds


def drive(config, traffic, conf, ds, seconds, plan, tap):
    """The two solver runs: the fixed pass (one train pass over the
    distinct parts, one val pass), then the long pass with the same
    learner, which the tap ends by closing the window."""
    app = importlib.import_module(config["app"])

    def make_learner(cfg, env):
        if tap._learner is None:
            tap._learner = app.make_learner(cfg, env)
        return tap

    base = dict(conf, data_format=traffic["data_format"])
    t0 = time.perf_counter()
    run_app(config, write_conf(os.path.join(ds.root, "fixed.conf"), dict(
        base, train_data=ds.train_pattern, val_data=ds.val_pattern,
        max_data_pass=1)), make_learner)
    say(f"fixed pass: {time.perf_counter() - t0:.1f}s, kinds "
        f"{sorted(tap.kinds)}, {tap._learner.placement}")
    tap.begin_window(seconds, int(traffic["warmup_passes"]), plan)
    try:
        run_app(config, write_conf(os.path.join(ds.root, "window.conf"), dict(
            base,
            train_data=ds.long_pattern if ds.links else ds.train_pattern,
            max_data_pass=int(traffic["window_passes"]))), make_learner)
    except tp.WindowClosed:
        return tap
    raise SystemExit("run.py: the long pass ended before the window did; "
                     "raise the traffic's min_pass_rows or window_passes")


def result(tap, seconds, warns, traffic) -> dict:
    """The result line but for `correct` and the metrics. A mix that sizes
    its pass to hold the window (`min_pass_rows` over 0) gets no line from
    a window that reached a further pass: a pass's turn changes the loader
    pool, and the window would be two jobs."""
    peak = memory_peak_bytes()
    n = len(tap.ends)
    if n < 2:
        raise SystemExit(f"run.py: {n} step(s) completed in the window")
    say(f"window: {n} steps, {sum(tap.rows):.0f} rows in "
        f"{tap.ends[-1] - tap.t_open:.3f}s (nominal {seconds}s); pass "
        f"{tap.pass_close} of the window run; {n} gaps; set-up "
        f"{tap.t_open - T_START:.1f}s")
    if int(traffic["min_pass_rows"]) > 0 and tap.pass_close != tap.pass_open:
        raise SystemExit(
            f"run.py: the window opened in pass {tap.pass_open} of the "
            f"window run and closed in pass {tap.pass_close}: the long pass "
            "ended before the window did; raise the traffic's min_pass_rows")
    gaps = window.gaps_ms(tap.t_open, tap.ends)
    say(f"window: longest gap {max(gaps):.1f} ms, "
        f"{sum(g > 1e3 * tp.STALL_DUMP_S for g in gaps)} over "
        f"{1e3 * tp.STALL_DUMP_S:.0f} ms; longest step "
        f"{1e3 * max(tap.step_s):.1f} ms")
    failed = warns.count("window")
    return {"correct": False, "attempted": n + failed, "failed": failed,
            "metrics": {}, "device": {"memory_peak_bytes": peak}}


def end_to_end(tap) -> dict:
    val = tap.fixed_val[-1]
    return {
        "train_ex_per_s": window.rate(tap.t_open, tap.ends, tap.rows),
        "batch_gap_p95_ms": window.p95(window.gaps_ms(tap.t_open,
                                                      tap.ends)),
        "val_logloss": val["logloss"] / val["nex"],
        "setup_s": tap.t_open - T_START,
    }


def batch_shapes(conf, config, reference, first) -> dict:
    """What a kernel's `cost(batch)` may read: the batch's shapes, the
    configuration's `hyper` values (a row's width among them) and, by id
    space, the mean count of distinct rows a followed first step touched
    (`distinct`); `uniq` is that of the first space the reference
    declares, the learner's main table."""
    touched = (first.reference or {}).get("touched", [])
    distinct = {s: sum(len(t[s]) for t in touched) / max(len(touched), 1)
                for s in reference.SPACES}
    rows = int(conf["minibatch"])
    return {"rows": rows, "nnz": rows * int(conf["nnz_per_row"]),
            "uniq": distinct[next(iter(reference.SPACES))],
            "num_buckets": int(conf["num_buckets"]),
            "hyper": dict(config["hyper"]), "distinct": distinct}


def batch(conf, config, tap) -> dict:
    return batch_shapes(conf, config, tap.reference, tap.first)


def correct(config, tap, clog):
    """(i) the reference check, (ii) no compilation inside the window,
    (iii) held-out logloss under ln 2 and the fixed pass's train logloss
    falling, (iv) the staged batch kind the configuration names. Returns
    whether all hold, a line for each, and every number compared beside
    its limit (`[value, limit]` by name)."""
    first, reference = tap.first, tap.reference
    lines, ok, compared = [], True, {}
    spec = config["correct"]
    if first.problem or not first.done:
        ok = False
        lines.append("reference check: " + (
            first.problem or f"only {len(first.order)} of {first.k} first "
            "steps were seen"))
    else:
        t0 = time.perf_counter()
        batches = [first.ds.batch(*o) for o in first.order]
        start = first.start()
        ref = reference.run_steps(batches, first.sizes, config["hyper"],
                                  config["precision"], start=start)
        first.reference = ref
        nums = check.numbers(first.as_run(), check.reference_as_run(
            ref, first.ds.minibatch, start))
        good, ls = check.verdict(nums, spec["limits"])
        ok &= good
        lines += ls
        compared.update({k: [nums[k], v] for k, v in spec["limits"].items()})
        touched = ", ".join(
            f"{len(v)} touched {s}s of {first.sizes[s]}"
            for s, v in ref["ids"].items())
        lines.append(f"reference: {len(batches)} steps on batches "
                     f"{first.order}, {touched}, "
                     f"{time.perf_counter() - t0:.1f}s (not in setup_s)")
    seen = tap.served.seen
    if seen is None:
        ok = False
        lines.append("served step: " + (
            tap.served.problem or "no step was followed after the window"))
    else:
        t0 = time.perf_counter()
        ref = reference.run_steps(
            [first.ds.batch(*seen["batch"])], first.sizes,
            config["hyper"], config["precision"],
            start={"ids": seen["ids"], "tables": seen["pre"]})
        nums = check.served_numbers(seen, {
            "pre": seen["pre"], "post": ref["states"][0],
            "objv": ref["objv"][0], "nex": float(first.ds.minibatch)})
        good, ls = check.verdict(nums, spec["served_limits"])
        ok &= good
        lines += ls
        compared.update({k: [nums[k], v]
                         for k, v in spec["served_limits"].items()})
        read_back = ", ".join(f"{len(v)} {s}s"
                              for s, v in seen["ids"].items())
        lines.append(f"served step: batch {seen['batch']} as the window's "
                     f"feed delivered it (pass {tap.pass_no} of the window "
                     f"run), {read_back} read back before and after, "
                     f"{time.perf_counter() - t0:.1f}s")
    in_window = clog.compiles("window")
    lines.append(f"compilations inside the window = {in_window}  (limit 0)")
    ok &= in_window == 0
    compared["window_compiles"] = [in_window, 0]
    val = tap.fixed_val[-1]["logloss"] / tap.fixed_val[-1]["nex"]
    losses = tap.fixed_train[0]["losses"]
    falling = (sum(losses[-2:]) < sum(losses[:2])) and all(
        math.isfinite(x) for x in losses)
    lines.append(f"val_logloss = {val:.6f}  (limit < "
                 f"{spec['val_logloss_max']:.6f}); fixed-pass train "
                 f"logloss {losses[0]:.4f} -> {losses[-1]:.4f} "
                 f"{'falling' if falling else 'NOT falling'}")
    ok &= math.isfinite(val) and val < spec["val_logloss_max"] and falling
    compared["val_logloss"] = [val, spec["val_logloss_max"]]
    lines.append(f"staged batch kinds {sorted(tap.kinds)}  (expected "
                 f"{config['expect_kind']!r})")
    ok &= tap.kinds == {config["expect_kind"]}
    # a number that is not finite has no JSON: it is named
    return ok, lines, {k: [v if math.isfinite(v) else repr(v), lim]
                       for k, (v, lim) in compared.items()}
