#!/usr/bin/env python3
"""One cell of the benchmark, once, in one process — the one that holds
the chip.

  python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This file is the generic half of a run: the command line, the cell's
files found by the names in BENCHMARK.json, the look for a chip, the
traced run's per-layer metrics (each through its own file and reducer)
and the result line. The job's half — how its data is made and loaded,
what set-up and the window drive, what an end-to-end number is made of,
what `correct` compares — is a driver: the module the configuration's
`driver` key names, `benchmark.drivers.minibatch` where it names none
(`run_minibatch_app` -> `MinibatchSolver` -> loader threads ->
`train_batch`; its docstring says what set-up and the window are).
`benchmark/drivers/__init__.py` says what a driver gives this file.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` (and `breakdown` with
`--trace 1`), then `compared`: every number `correct` compared, as
`[value, limit]` by name; the same, in words, are the last lines of
standard error. Everything else is on earlier lines. Without a TPU, with
interpreted kernels, without the native parser or with another number of
chips than the cell asks for, nothing is measured and the exit code is
not 0. A fatal signal (exit code 139 or 134) leaves every thread's stack
on standard error (`faulthandler`, on from `main`'s first statement,
before the program is imported). The cell, its configuration, its
traffic mix, its driver and every per-layer metric are files found by
the names in BENCHMARK.json; see README.md.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import tap as tp, xplane  # noqa: E402
from benchmark.drivers import TAG, memory_peak_bytes, say  # noqa: E402,F401
# the minibatch driver's own, under the names tests and tools know
from benchmark.drivers.minibatch import batch_shapes, result  # noqa: E402,F401

DEFAULT_DRIVER = "benchmark.drivers.minibatch"


# ------------------------------------------------------------------ files
def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by name."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


def load_driver(config: dict):
    """The job's half of the run (benchmark/drivers/__init__.py): the
    module the configuration names by its dotted path, or the default;
    it is told when the process started."""
    name = config.get("driver", DEFAULT_DRIVER)
    try:
        driver = importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name is None or not name.startswith(e.name):
            raise
        raise SystemExit(f"run.py: no driver {name!r} (the configuration's "
                         f"`driver`, a module's dotted path): {e}")
    driver.T_START = T_START
    return driver


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
    driver = load_driver(config)
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
            plan = trace_plan(work, traffic, seconds) if trace else None
            run = driver.measure(cell, config, conf, traffic, work, seed,
                                 seconds, plan, clog, warns)
            out = driver.result(run, seconds, warns, traffic)
            ok, lines, compared = driver.correct(config, run, clog)
            for line in lines:
                say("correct: " + line)
            out["correct"] = bool(ok)
            e2e = driver.end_to_end(run)
            if not trace:
                for m in metrics_of(bench, "end_to_end", workload):
                    out["metrics"][m["name"]] = {
                        "value": e2e[m["name"]], "unit": m["unit"]}
            else:
                say("a traced run: the end-to-end numbers on this line "
                    "are not results: " + json.dumps(e2e))
                per_layer(out, bench, workload, config, conf, driver, run,
                          clog, plan, keep_trace)
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


def trace_plan(work, traffic, seconds) -> dict:
    """The profiler runs over the window's last seconds and ends it."""
    tsec = min(float(traffic["trace_seconds"]), 0.4 * seconds)
    return {"dir": os.path.join(work, "trace"), "seconds": tsec,
            "at": max(seconds - tsec - 1.0, 0.5 * seconds)}


def per_layer(out, bench, workload, config, conf, driver, tap, clog, plan,
              keep_trace) -> None:
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
        "end_to_end": driver.end_to_end(tap),
        "hist": tp.hist_delta(tap.hist_open, tap.hist_close),
        "host_window_s": tap.t_hist_close - tap.t_open,
        "step_s": tap.step_s,
        "compile_events": [e for e in clog.events
                           if e[0] in ("setup", "warmup")],
        "trace": summary,
        "trace_steps": tap.trace_steps,
        "peaks": peaks[kind],
        "batch": driver.batch(conf, config, tap),
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


def main(argv=None) -> int:
    # a fatal signal names the thread and the frame that died
    faulthandler.enable(all_threads=True)
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
