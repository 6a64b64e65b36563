"""PR 43: `run.py` asks a module the configuration names for the job's
half of a run. The default is the minibatch-solver path, moved to
`benchmark/drivers/minibatch.py`; a job that is none (the fixture: L-BFGS
over resident rows) becomes a cell by a module and files; a fatal signal
leaves every thread's stack on standard error. Nothing here is a speed."""

import importlib
import inspect
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
FIXTURES = os.path.join(REPO, "tests", "benchmark", "fixtures")
sys.path.insert(0, REPO)

from benchmark import drivers, run  # noqa: E402
from benchmark.drivers import minibatch  # noqa: E402

sys.path.insert(0, FIXTURES)
import batch_driver  # noqa: E402

CONTRACT = ("measure", "result", "end_to_end", "correct", "batch")


def _json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def _env(tmp, **extra):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"), **extra)
    env.pop("XLA_FLAGS", None)  # one device, like the chip
    env.pop("PYTHONFAULTHANDLER", None)
    return env


# ------------------------------------------------------------- the seam
def test_a_driver_is_found_by_the_name_the_configuration_gives(
        tmp_path, monkeypatch):
    (tmp_path / "a_driver_of_pr43.py").write_text("T_START = None\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    driver = run.load_driver({"driver": "a_driver_of_pr43"})
    assert driver is importlib.import_module("a_driver_of_pr43")
    # the process's start, as run.py took it in its first statement
    assert driver.T_START == run.T_START


def test_every_accepted_configuration_names_no_driver_and_gets_the_default():
    bench = _json(REPO, "BENCHMARK.json")
    assert len(bench["configs"]) >= 3
    for entry in bench["configs"]:
        config = _json(REPO, entry["file"])
        if "driver" not in config:
            assert run.load_driver(config) is minibatch, entry["name"]
    assert run.load_driver({}) is minibatch
    assert run.DEFAULT_DRIVER == minibatch.__name__


def test_an_unknown_driver_is_an_exit_that_names_it():
    with pytest.raises(SystemExit) as e:
        run.load_driver({"driver": "benchmark.drivers.no_such_job"})
    assert "benchmark.drivers.no_such_job" in str(e.value)
    assert "driver" in str(e.value)


def test_a_driver_whose_own_import_fails_is_not_called_unknown(
        tmp_path, monkeypatch):
    (tmp_path / "a_broken_driver_of_pr43.py").write_text(
        "import no_such_module_of_pr43\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    with pytest.raises(ModuleNotFoundError) as e:
        run.load_driver({"driver": "a_broken_driver_of_pr43"})
    assert e.value.name == "no_such_module_of_pr43"


@pytest.mark.parametrize("module", (minibatch, batch_driver),
                         ids=lambda m: m.__name__)
@pytest.mark.parametrize("name", CONTRACT)
def test_a_driver_gives_what_the_contract_lists(module, name):
    fn = getattr(module, name)
    params = list(inspect.signature(fn).parameters)
    assert len(params) == {"measure": 10, "result": 4, "end_to_end": 1,
                           "correct": 3, "batch": 3}[name]
    # and the contract's words name it
    assert f"  {name}(" in drivers.__doc__


@pytest.mark.parametrize("path", (
    os.path.join(BENCH, "drivers", "__init__.py"),
    os.path.join(BENCH, "drivers", "minibatch.py"),
    os.path.join(FIXTURES, "batch_driver.py")), ids=os.path.basename)
def test_a_driver_imports_nothing_of_run_py(path):
    src = open(path).read()
    assert not re.search(r"^\s*(from|import)\s+benchmark\.run\b", src, re.M)
    assert not re.search(r"^\s*from\s+benchmark\s+import\s+.*\brun\b", src,
                         re.M)


@pytest.mark.parametrize("name", (
    "run_cell", "result", "batch_shapes", "sized", "load_json",
    "load_module", "ROOT", "HERE", "T_START", "say", "memory_peak_bytes"))
def test_the_names_tests_and_tools_know_stay_on_run_py(name):
    """What tests/benchmark, tests/test_linear_mesh_deploy.py, rehearse.py
    and control.py import from `benchmark.run`, and the three that moved
    to `benchmark.drivers` with the minibatch path."""
    assert hasattr(run, name)


def test_the_minibatch_path_is_the_drivers_and_run_py_keeps_no_copy():
    assert run.result is minibatch.result
    assert run.batch_shapes is minibatch.batch_shapes
    src = open(os.path.join(BENCH, "run.py")).read()
    for moved in ("write_conf", "run_app", "make_data", "drive",
                  "end_to_end", "correct", "result", "batch_shapes"):
        assert f"def {moved}(" not in src, moved
        assert callable(getattr(minibatch, moved)), moved
    # the generic half names the program's entry in its words only
    code = src.split('"""', 2)[2]
    assert "run_minibatch_app" not in code
    assert "MinibatchSolver" not in code


def test_the_minibatch_driver_asks_its_tap_for_the_check_s_two_sides():
    """`batch` and `correct` take the run alone: the tap carries the
    first steps and the reference `measure` built them with."""
    from benchmark.reference import linear_ftrl

    first = NS(reference={"touched": [{"bucket": np.arange(6)},
                                      {"bucket": np.arange(8)}]})
    tap = NS(first=first, reference=linear_ftrl)
    conf = {"minibatch": 256, "nnz_per_row": 39, "num_buckets": 1 << 17}
    b = minibatch.batch(conf, {"hyper": {"lr_eta": 0.1}}, tap)
    assert b == minibatch.batch_shapes(conf, {"hyper": {"lr_eta": 0.1}},
                                       linear_ftrl, first)
    assert b["uniq"] == 7.0 and b["rows"] == 256


# ------------------------------------------------------- a fatal signal
_CRASH = """
import os, signal, sys, threading, time
from benchmark import run

def stub_run_cell(*args, **kwargs):
    threading.Thread(target=time.sleep, args=(60,), daemon=True).start()
    os.kill(os.getpid(), signal.SIGSEGV)

run.run_cell = stub_run_cell
sys.exit(run.main(["--workload", "any", "--seed", "1", "--seconds", "1"]))
"""


def test_a_fatal_signal_leaves_every_thread_s_stack_on_standard_error(
        tmp_path):
    r = subprocess.run([sys.executable, "-c", _CRASH], cwd=REPO,
                       env=_env(tmp_path), capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == -signal.SIGSEGV      # what a shell calls 139
    assert "Fatal Python error: Segmentation fault" in r.stderr
    assert "Current thread 0x" in r.stderr
    assert re.search(r"^Thread 0x", r.stderr, re.M)     # the sleeper
    assert "in stub_run_cell" in r.stderr       # the frame that died
    assert "{" not in r.stdout                  # no result of any kind


def test_importing_run_py_turns_no_handler_on(tmp_path):
    """`main` does, as its first statement: rehearse.py, control.py and
    the tests import the module and keep the handlers they have."""
    r = subprocess.run(
        [sys.executable, "-c", "import faulthandler\n"
         "from benchmark import run\nprint(faulthandler.is_enabled())"],
        cwd=REPO, env=_env(tmp_path), capture_output=True, text=True,
        timeout=300)
    assert r.stdout.strip() == "False", r.stdout + r.stderr
    src = inspect.getsource(run.main)
    assert src.index("faulthandler.enable(all_threads=True)") < src.index(
        "argparse")


# ---------------------------------------------------- the fixture's job
CELL = "lbfgs-fixture.resident-small"


@pytest.fixture(scope="module")
def batch_job(tmp_path_factory):
    """In a copy of the benchmark: the fixture's driver, its
    configuration and a mix become a cell by files and entries, and the
    cell is rehearsed (CPU, a few thousand rows)."""
    tmp = tmp_path_factory.mktemp("batch_job")
    shutil.copytree(BENCH, tmp / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp / "benchmark").rglob("*")
              if p.is_file()}
    shutil.copy(os.path.join(FIXTURES, "batch_driver.py"),
                tmp / "benchmark" / "drivers")
    shutil.copy(os.path.join(FIXTURES, "lbfgs-linear-fixture.json"),
                tmp / "benchmark" / "configs")
    mix = _json(BENCH, "traffic", "replay.json")
    mix.update(name="resident-small", env={}, train_parts=2,
               batches_per_part=4, val_parts=2,
               what="every row resident; the window is jobs of a fixed "
                    "number of iterations, one after the other")
    (tmp / "benchmark/traffic/resident-small.json").write_text(
        json.dumps(mix))
    bench = _json(REPO, "BENCHMARK.json")
    bench["configs"].append({
        "name": "lbfgs-linear-fixture", "source": "learn/lbfgs-linear",
        "file": "benchmark/configs/lbfgs-linear-fixture.json",
        "reduced": [], "why": "a batch job behind the seam"})
    bench["workloads"].append({
        "name": CELL, "config": "lbfgs-linear-fixture",
        "traffic": "resident-small", "chips": 1, "why": "test"})
    # the rate lists the cells that report it end to end: a new one
    # appends its name
    (rate,) = [m for m in bench["end_to_end"]
               if m["name"] == "train_ex_per_s"]
    rate["workloads"].append(CELL)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    r = subprocess.run(
        [sys.executable, "benchmark/rehearse.py", "--workload", CELL,
         "--seed", "2147484301", "--seconds", "2"], cwd=tmp, env=_env(tmp),
        capture_output=True, text=True, timeout=600)
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"
    return r


def test_the_batch_job_s_line_is_the_contract_s_object(batch_job):
    r = batch_job
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = json.loads(r.stdout.splitlines()[-1])
    assert list(out)[-1] == "compared"
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device", "compared"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 2
    assert out["device"]["platform"] == "cpu"   # no measurement of anything
    assert set(out["metrics"]) == {"train_ex_per_s", "val_logloss",
                                   "setup_s"}
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0.3 < m["val_logloss"] < math.log(2)
    assert m["train_ex_per_s"] > 0 and m["setup_s"] > 0
    assert out["metrics"]["train_ex_per_s"]["unit"] == "examples/s"


def test_the_batch_job_was_the_program_s_own_path_by_the_named_driver(
        batch_job):
    lines = [ln for ln in batch_job.stdout.splitlines()
             if ln.startswith("[bench]")]
    (fixed,) = [ln for ln in lines if "fixed job:" in ln]
    assert "8 iterations, 8 resident batches" in fixed
    (win,) = [ln for ln in lines if "window:" in ln]
    assert "passes" in win and "set-up" in win
    # the minibatch driver's words are not in it
    assert not any("fixed pass:" in ln or "of the window run" in ln
                   for ln in lines)


def test_the_batch_job_compares_each_number_beside_its_limit(batch_job):
    out = json.loads(batch_job.stdout.splitlines()[-1])
    limits = _json(FIXTURES, "lbfgs-linear-fixture.json")["correct"]
    for k, limit in limits["limits"].items():
        value, lim = out["compared"][k]
        assert lim == limit and 0 <= value <= limit, k
    assert out["compared"]["window_compiles"] == [0, 0]
    assert out["compared"]["val_logloss"][1] == math.log(2)
    # each number beside its limit: the last lines of standard error too
    err = [ln for ln in batch_job.stderr.splitlines()
           if ln.startswith("[bench] correct:")]
    assert len(err) == len(limits["limits"]) + 2
    assert batch_job.stderr.splitlines()[-1] == err[-1]


# -------------------------------------- its reference and what fails it
def _rows(n=512, dim=64, seed=5):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, dim, (n, 39))
    truth = rng.normal(0, 0.3, dim)
    label = (rng.random(n) < 1 / (1 + np.exp(-truth[ids].sum(1)))
             ).astype(np.float64)
    return ids, label, dim


def test_the_reference_s_first_step_is_a_descent_step_by_armijo():
    ids, label, dim = _rows()
    f0, f1, step = batch_driver.first_step(ids, label, dim)
    assert f0 == pytest.approx(len(label) * math.log(2))
    assert f1 < f0 and step > 0


@pytest.mark.parametrize("fault", ("step_halved", "rows_left_out",
                                   "state_unchanged"))
def test_a_broken_first_step_comes_out_not_correct(fault):
    """The fixture's `correct` over a run whose first iteration is the
    reference's own, then with a fault planted in what the program would
    hand over: a step half as long, a batch's rows left out of the
    objective, w handed back unchanged."""
    ids, label, dim = _rows()
    f0, f1, step = batch_driver.first_step(ids, label, dim)
    # `correct` reads the step's norm alone
    w1 = np.full(dim + 1, step / math.sqrt(dim + 1))
    config = _json(FIXTURES, "lbfgs-linear-fixture.json")
    sound = dict(ids=ids, label=label, num_feature=dim, objv=[f0, f1, f1 - 1],
                 w1=w1.astype(np.float32), val_logloss=0.5)
    clog = NS(compiles=lambda phase: 0)
    ok, lines, compared = batch_driver.correct(config, NS(**sound), clog)
    assert ok, lines
    broken = dict(sound)
    if fault == "step_halved":
        broken["w1"] = sound["w1"] / 2
    elif fault == "rows_left_out":
        broken["objv"] = [f0 * 0.75, f1 * 0.75, f1 * 0.75 - 1]
    else:
        broken["w1"] = np.zeros_like(sound["w1"])
    ok, lines, compared = batch_driver.correct(config, NS(**broken), clog)
    assert not ok
    assert any("FAILED" in ln for ln in lines)
    # and a compilation inside the window alone fails a sound run
    ok, _, compared = batch_driver.correct(
        config, NS(**sound), NS(compiles=lambda phase: 1))
    assert not ok and compared["window_compiles"] == [1, 0]
