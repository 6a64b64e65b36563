#!/usr/bin/env python3
"""One cell of the benchmark, once, in one process — the one that holds
the chip.

  python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (all of it `setup_s`): data from the seed, the learner through the
program's own entry (`run_minibatch_app` -> `MinibatchSolver` -> loader
threads -> `train_batch`), the fixed pass (one train pass over the
distinct parts and one val pass: compiles, warms every shape, follows the
first steps for the reference check, yields `val_logloss`), then a second
solver run over the long pass with the same learner, whose warm-up passes
(if the traffic has any) still count as set-up. The window opens at the
first train step completed after that; the step that crosses `--seconds`
is its last. One more batch, delivered the way the window's were, is
then followed for the served-step check, and the run ends.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` (and `breakdown` with
`--trace 1`), then `compared`: every number `correct` compared, as
`[value, limit]` by name; the same, in words, are the last lines of
standard error. Everything else is on earlier lines. Without a TPU, with
interpreted kernels, without the native parser or with another number of
chips than the cell asks for, nothing is measured and the exit code is
not 0. The cell, its configuration, its traffic mix and every per-layer
metric are files found by the names in BENCHMARK.json; see README.md.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, gen, tap as tp, window, xplane  # noqa: E402

TAG = "[bench]"


def say(msg: str) -> None:
    print(f"{TAG} {msg}", flush=True)


# ------------------------------------------------------------------ files
def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by name."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


def resolve(bench: dict, workload: str) -> dict:
    """The cell's entry, its configuration file and its traffic file."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {"cell": cell,
            "config": load_json(ROOT, entry["file"]),
            "traffic": load_json(HERE, "traffic", cell["traffic"] + ".json")}


def metrics_of(bench: dict, key: str, workload: str) -> list[dict]:
    return [m for m in bench[key]
            if "workloads" not in m or workload in m["workloads"]]


# ------------------------------------------------------------------ device
def require_chip(chips: int) -> None:
    """Fail at once unless this process would really measure on the
    chips the cell asks for. There is no fallback."""
    try:
        import jax

        from wormhole_tpu import native
        from wormhole_tpu.ops import coo_kernels as ck
    except ImportError as e:
        raise SystemExit(f"run.py: cannot import the program ({e}); run it "
                         "from the root of a wormhole-tpu checkout")
    if jax.default_backend() != "tpu" or ck._use_interpret():
        raise SystemExit(
            f"run.py: no TPU — jax.default_backend() is "
            f"{jax.default_backend()!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}); the Pallas kernels "
            "would run interpreted. Nothing was measured.")
    if native.status() != "loaded":
        raise SystemExit(f"run.py: the native parsing core is "
                         f"{native.status()}: the Python parsers would "
                         "feed the run. Nothing was measured.")
    if len(jax.devices()) != chips:
        raise SystemExit(f"run.py: the cell asks for {chips} chip(s), JAX "
                         f"sees {len(jax.devices())}. Nothing was measured.")


def device_info() -> dict:
    from importlib import metadata

    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    devs = jax.devices()
    say(f"platform={devs[0].platform} device_kind={devs[0].device_kind!r} "
        f"devices={len(devs)} python={sys.version.split()[0]} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak on the fullest chip (0 where the backend reports none)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


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
def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, rehearsal: bool = False, keep_trace=None) -> dict:
    """Everything after argument parsing. `rehearsal` (rehearse.py and
    the tests) skips the look for a chip and applies the configuration's
    tiny `rehearsal` sizes; its result names the platform it ran on like
    any other."""
    res = resolve(bench, workload)
    cell, config, traffic = res["cell"], res["config"], res["traffic"]
    conf, config = sized(config, rehearsal)
    if not rehearsal:
        require_chip(cell["chips"])
    device = device_info()
    for k, v in traffic["env"].items():
        os.environ[k] = str(v)
    # a pool the operator pinned is not "as the program chooses it"
    os.environ.pop("WH_NUM_LOADERS", None)
    work = tempfile.mkdtemp(prefix="whbench_")
    warns = tp.WarningLog()
    logging.getLogger("wormhole_tpu").addHandler(warns)
    try:
        with tp.CompileLog() as clog:
            ds = make_data(work, traffic, conf, config, seed)
            reference = load_module("reference", config["reference"])
            sizes = check.space_sizes(reference, conf)
            first = check.FirstSteps(ds, sizes, config["correct"]["steps"],
                                     reference)
            served = check.ServedStep(ds, sizes, reference)
            plan = trace_plan(work, traffic, seconds) if trace else None
            tap = drive(config, traffic, conf, ds, seconds, plan,
                        tp.Tap(None, clog, warns, first, served))
            out = result(tap, seconds, warns, traffic)
            ok, lines, compared = correct(config, first, reference, tap,
                                          clog)
            for line in lines:
                say("correct: " + line)
            out["correct"] = bool(ok)
            e2e = end_to_end(tap)
            if not trace:
                for m in metrics_of(bench, "end_to_end", workload):
                    out["metrics"][m["name"]] = {
                        "value": e2e[m["name"]], "unit": m["unit"]}
            else:
                say("a traced run: the end-to-end numbers on this line "
                    "are not results: " + json.dumps(e2e))
                per_layer(out, bench, workload, config, conf, tap, first,
                          reference, clog, plan, keep_trace)
    finally:
        logging.getLogger("wormhole_tpu").removeHandler(warns)
        shutil.rmtree(work, ignore_errors=True)
    out["device"] = {**device, **out["device"]}
    # each number compared beside its limit: the result line's last key
    # and the last lines of standard error (what a record of a run that
    # came out not correct keeps)
    out["compared"] = compared
    for line in lines:
        print(f"{TAG} correct: {line}", file=sys.stderr, flush=True)
    return out


def sized(config: dict, rehearsal: bool) -> tuple[dict, dict]:
    """The conf keys as run and the configuration with the precision its
    reference is to state: the real ones, or the tiny `rehearsal` ones."""
    conf = dict(config["conf"])
    if rehearsal:
        conf.update(config["rehearsal"]["conf"])
        config = dict(config, precision=config["rehearsal"]["precision"])
    return conf, config


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


def trace_plan(work, traffic, seconds) -> dict:
    """The profiler runs over the window's last seconds and ends it."""
    tsec = min(float(traffic["trace_seconds"]), 0.4 * seconds)
    return {"dir": os.path.join(work, "trace"), "seconds": tsec,
            "at": max(seconds - tsec - 1.0, 0.5 * seconds)}


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


def per_layer(out, bench, workload, config, conf, tap, first, reference,
              clog, plan, keep_trace) -> None:
    """The traced run's metrics, `device.busy_s`/`window_s` and the
    breakdown, each layer metric through its own file and reducer."""
    import jax

    pb = xplane.find(plan["dir"])
    if keep_trace:
        os.makedirs(keep_trace, exist_ok=True)
        shutil.copy(pb, os.path.join(keep_trace, f"{workload}.xplane.pb"))
    summary = xplane.summarize(xplane.load(pb),
                               tap.trace_t1 - tap.trace_t0)
    kind = jax.devices()[0].device_kind
    peaks = load_json(HERE, "peaks.json")
    if kind not in peaks:
        raise SystemExit(f"run.py: no peaks for device kind {kind!r} in "
                         "benchmark/peaks.json")
    ctx = {
        "hist": tp.hist_delta(tap.hist_open, tap.hist_close),
        "host_window_s": tap.t_hist_close - tap.t_open,
        "step_s": tap.step_s,
        "compile_events": [e for e in clog.events
                           if e[0] in ("setup", "warmup")],
        "trace": summary,
        "trace_steps": tap.trace_steps,
        "peaks": peaks[kind],
        "batch": batch_shapes(conf, config, reference, first),
        "kernels": [load_module("kernels", k) for k in config["kernels"]],
    }
    say(f"trace: {summary['window_s']:.3f}s traced, device busy "
        f"{summary['busy_s']:.3f}s, {tap.trace_steps} steps, "
        f"{summary['steps_marked']} marked")
    for m in metrics_of(bench, "per_layer", workload):
        spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
        v = load_module("reducers", spec["reducer"]).read(
            ctx, **spec.get("params", {}))
        if v is not None:
            out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    out["device"].update(busy_s=summary["busy_s"],
                         window_s=summary["window_s"])
    out["breakdown"] = xplane.breakdown(summary)


def correct(config, first, reference, tap, clog):
    """(i) the reference check, (ii) no compilation inside the window,
    (iii) held-out logloss under ln 2 and the fixed pass's train logloss
    falling, (iv) the staged batch kind the configuration names. Returns
    whether all hold, a line for each, and every number compared beside
    its limit (`[value, limit]` by name)."""
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced run's .xplane.pb here (for "
                         "reading one by hand; the driver never sets it)")
    args = ap.parse_args(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), keep_trace=args.keep_trace)
    say(f"total {time.perf_counter() - T_START:.1f}s")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
