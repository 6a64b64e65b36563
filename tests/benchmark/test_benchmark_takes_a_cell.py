"""PR 46: the repository's own `BENCHMARK.json` takes a cell by new files,
appended entries and the cell's name appended to the lists of the metrics
it reports, and the tests of this directory that read the file stay
green. PR 45 built such a cell (`lbfgs1tb.resident`) and could not enter
it: two accepted tests counted the cells of the day.

Each case lays a cell into a copy of `BENCHMARK.json`, `benchmark/` and
`tests/benchmark/`, holds that nothing that was there changed and that the
file differs by appended items alone, and runs the copy's own tests in a
child (the program is imported from the checkout). The cells are the
fixtures other tests rehearse: the batch job behind the seam
(test_benchmark_drivers.py) and the Kaggle job
(test_benchmark_files.py). Nothing here runs a solver or is a speed.
"""

import inspect
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(REPO, "tests", "benchmark", "fixtures")
sys.path.insert(0, FIXTURES)

import batch_driver  # noqa: E402

RATE = "train_ex_per_s"
STREAM = "criteo1tb.crb-stream"
FOUR = "criteo1tb-2p30.replay-8"
KERNELS = ("kernel_ms_per_step", "step_kernels_roofline")
REPLAY = {"criteo1tb.replay", FOUR, "difacto1tb.replay"}
RATE_GUARD = ("test_benchmark_stream_twins.py::test_the_stream_cell_s_"
              "end_to_end_metrics_are_the_steady_two")
KERNEL_GUARD = ("test_benchmark_vector_rows.py::test_the_list_less_kernel_"
                "metrics_name_the_one_chip_cells")
TURN_GUARD = ("test_benchmark_pass_turn.py::test_the_benchmark_lists_the_"
              "seven_after_the_accepted_entries")
TCOO_GUARD = ("test_benchmark_tcoo_pull.py::test_the_metric_s_file_loads_and_"
              "lists_the_compact_cells_only")
# the tests of the copy that read BENCHMARK.json or the files it names;
# what rehearses a solver or profiles a run is left out (a minute each)
READERS = (
    "test_benchmark_files.py",
    "test_benchmark_stream_twins.py",
    "test_benchmark_drivers.py::test_every_accepted_configuration_names_no_"
    "driver_and_gets_the_default",
    "test_benchmark_mesh_cell.py::test_the_cell_is_one_four_chip_entry_of_"
    "files_that_exist",
    "test_benchmark_one_pass.py::test_a_stream_mix_holds_one_pass_at_twice_"
    "the_best_rate",
    "test_benchmark_one_pass.py::test_each_of_the_nine_is_an_entry_after_"
    "tcoo_pull_ms",
    "test_benchmark_one_pass.py::test_the_idle_and_step_lists_hold_every_"
    "cell_the_four_chip_one_too",
    "test_benchmark_pass_turn.py::test_the_file_loads_and_names_an_accepted_"
    "reducer",
    TURN_GUARD,
    "test_benchmark_spans.py::test_recorded_trace_gives_all_twelve_metrics",
    TCOO_GUARD,
    KERNEL_GUARD,
    "test_benchmark_vector_rows.py::test_delta_norm_gap_stands_between_a_"
    "flip_and_an_unchanged_state",
)
REHEARSES = ("test_benchmark_files.py::test_a_new_cell_is_new_files_and_one_"
             "entry_each")

_REDUCER = '''"""Rows a step of the window trained on (the batch's count)."""


def read(ctx):
    return float(ctx["batch"]["rows"])
'''

_KERNEL_COUNT = '''"""A pass over the resident rows: ids, labels read once."""


def cost(batch):
    return {"bytes": 4.0 * (batch["nnz"] + batch["rows"]),
            "flops": 2.0 * batch["nnz"]}
'''


def _json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


# ---------------------------------------------------------------- the copy
def _copy(tmp):
    """The benchmark as git holds it (and the conftest.py its tests
    import), and the bytes of every file."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp)
    (tmp / "tests").mkdir()
    shutil.copy(os.path.join(REPO, "tests", "conftest.py"), tmp / "tests")
    for d in ("benchmark", os.path.join("tests", "benchmark")):
        shutil.copytree(os.path.join(REPO, d), tmp / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return {p: p.read_bytes() for p in tmp.rglob("*") if p.is_file()}


def _lay(tmp, rel, content):
    path = tmp / "benchmark" / rel
    assert not path.exists(), rel
    path.write_text(content if isinstance(content, str)
                    else json.dumps(content))


def _layer_metric(tmp, name, layer, reducer):
    """A layer metric of the cell's own: its file, and its entry but for
    the cells."""
    entry = {"name": name, "unit": "rows", "better": "higher",
             "source": "program_counter", "layer": layer, "moves": RATE}
    _lay(tmp, f"layer_metrics/{name}.json",
         dict(entry, reducer=reducer, params={}))
    return entry


def a_cell_of_another_driver(tmp):
    """The refused case: the batch job's driver, a configuration that
    names it, a mix, a kernel count, a plain reference, a reducer and a
    layer metric. The configuration is the fixture's with the keys
    test_benchmark_files.py asks of every configuration's file."""
    shutil.copy(os.path.join(FIXTURES, "batch_driver.py"),
                tmp / "benchmark" / "drivers")
    config = _json(FIXTURES, "lbfgs-linear-fixture.json")
    conf = config["conf"]
    config.update(
        name="lbfgs-linear-resident",
        app="wormhole_tpu.apps.lbfgs_linear",
        config_class="wormhole_tpu.apps.lbfgs_linear:LbfgsLinearConfig",
        equals_source={"m": 10},
        assumed={k: "the fixture's" for k in conf if k != "m"},
        reduced={"train_rows": "what one window reads"},
        reference="lbfgs_first_step", hyper={"m": 10.0},
        precision={"vectors": "f32"}, control_precision={"vectors": "bf16"},
        expect_kind="resident", kernels=["resident_pass"],
        correct=dict(config["correct"],
                     limits={"loss_gap": 1e-4, "grad_norm_gap": 1e-4,
                             "delta_norm_gap": 1e-4,
                             "state_off_share": 1e-3},
                     served_limits={"served_loss_gap": 1e-4,
                                    "served_delta_gap": 1e-4,
                                    "served_off_share": 1e-3}))
    _lay(tmp, "configs/lbfgs-linear-resident.json", config)
    _lay(tmp, "reference/lbfgs_first_step.py",
         '"""The first L-BFGS iteration from w = 0, in float64."""\n\n'
         "import numpy as np\n\n\n"
         + inspect.getsource(batch_driver.first_step))
    _lay(tmp, "kernels/resident_pass.py", _KERNEL_COUNT)
    _lay(tmp, "reducers/rows_per_pass.py", _REDUCER)
    mix = _json(REPO, "benchmark", "traffic", "replay.json")
    mix.update(name="resident-tiny", env={}, train_parts=2,
               batches_per_part=4, what="every row resident")
    _lay(tmp, "traffic/resident-tiny.json", mix)
    return {
        "config": {"name": "lbfgs-linear-resident",
                   "source": "upstream wormhole learn/lbfgs-linear",
                   "file": "benchmark/configs/lbfgs-linear-resident.json",
                   "reduced": ["train_rows"],
                   "why": "a batch job behind the seam"},
        "cell": {"name": "lbfgs.resident-tiny",
                 "config": "lbfgs-linear-resident",
                 "traffic": "resident-tiny", "chips": 1,
                 "why": "every row resident; no loader in the window"},
        "metric": _layer_metric(tmp, "resident_rows_per_pass",
                                "the batch stack", "rows_per_pass")}


def a_cell_of_the_minibatch_driver(tmp):
    """The Kaggle job of test_benchmark_files.py: its configuration names
    no driver, its step runs the dense `coo` kernels (whose counts the
    benchmark holds since PR 41). The configuration is the fixture's
    with every conf key said to be the source's or assumed, as
    test_benchmark_files.py asks of every configuration's file."""
    shutil.copy(os.path.join(FIXTURES, "criteo-kaggle.json"),
                tmp / "benchmark" / "keys")
    config = _json(FIXTURES, "linear-ftrl-kaggle.json")
    source = {"algo": "ftrl", "lambda_l1": 4, "lr_eta": 0.1}
    config.update(equals_source=source, assumed={
        k: config["assumed"].get(k, "the program's default")
        for k in config["conf"] if k not in source})
    _lay(tmp, "configs/linear-ftrl-kaggle.json", config)
    mix = _json(REPO, "benchmark", "traffic", "replay.json")
    mix.update(name="replay-tiny", train_parts=2, batches_per_part=3)
    _lay(tmp, "traffic/replay-tiny.json", mix)
    _lay(tmp, "reducers/rows_per_window_step.py", _REDUCER)
    return {
        "config": {"name": "linear-ftrl-kaggle",
                   "source": "upstream wormhole doc/tutorial/"
                             "criteo_kaggle.rst",
                   "file": "benchmark/configs/linear-ftrl-kaggle.json",
                   "reduced": ["train_rows"],
                   "why": "the tutorial job, dense coo kernel set"},
        "cell": {"name": "kaggle.replay-tiny",
                 "config": "linear-ftrl-kaggle", "traffic": "replay-tiny",
                 "chips": 1, "why": "passes >= 2 from the pack cache"},
        "metric": _layer_metric(tmp, "rows_per_window_step", "jitted step",
                                "rows_per_window_step")}


def _enter(bench, laid, lists):
    """One entry each, the new layer metric listing the cell alone, and
    the cell's name after the others in each list of `lists`."""
    cell = laid["cell"]["name"]
    bench["configs"].append(laid["config"])
    bench["workloads"].append(laid["cell"])
    bench["per_layer"].append(dict(laid["metric"], workloads=[cell]))
    for m in bench["end_to_end"] + bench["per_layer"][:-1]:
        if m["name"] in lists:
            m["workloads"].append(cell)
    return cell


def grown(old, new):
    """Holds that `new` is `old` with items appended and nothing else:
    entries after the accepted ones, names after the accepted ones in a
    metric's `workloads`. Returns the lists that grew."""
    assert list(new) == list(old)
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key], key
    lists = {}
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(new[key]) >= len(old[key]), key
        for a, b in zip(old[key], new[key]):
            if a == b:
                continue
            assert "workloads" in a and "workloads" in b, (key, a["name"])
            assert dict(b, workloads=a["workloads"]) == a, a["name"]
            n = len(a["workloads"])
            assert b["workloads"][:n] == a["workloads"], a["name"]
            lists[a["name"]] = b["workloads"][n:]
    return lists


def _run_the_copy_s_tests(tmp, *ids):
    """`python -m pytest` from the copy's root: its tests find their files
    by their own path. Returns the tests that failed and the count of
    those that passed."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"))
    env.pop("XLA_FLAGS", None)
    args = [os.path.join("tests", "benchmark", i) for i in ids]
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rf", "-p",
         "no:cacheprovider", "-p", "no:randomly", f"--rootdir={tmp}",
         "--deselect", os.path.join("tests", "benchmark", REHEARSES), *args],
        cwd=tmp, env=env, capture_output=True, text=True, timeout=600)
    failed = {m.split("tests/benchmark/")[1] for m in re.findall(
        r"^FAILED (\S+)", r.stdout, re.M)}
    passed = re.search(r"(\d+) passed", r.stdout)
    assert r.returncode in (0, 1), r.stdout[-3000:] + r.stderr[-3000:]
    assert (r.returncode == 0) == (not failed), r.stdout[-3000:]
    return failed, int(passed.group(1)) if passed else 0


# ---------------------------------------------------------------- a cell
@pytest.mark.parametrize("lay,driver,kernels,refused_by", [
    (a_cell_of_another_driver, "benchmark.drivers.batch_driver", (), set()),
    (a_cell_of_the_minibatch_driver, None, KERNELS, set()),
    # the guard of PERF.md section 7 (a) keeps its teeth: a one-chip cell
    # of the minibatch step that is not under the two is refused
    (a_cell_of_the_minibatch_driver, None, (), {KERNEL_GUARD}),
], ids=["another_driver", "minibatch_driver", "minibatch_driver_unlisted"])
def test_the_real_file_takes_a_cell_by_files_and_appended_items(
        tmp_path, lay, driver, kernels, refused_by):
    """The lists the README's "Adding a cell" names: the rate's, and for a
    cell of the minibatch driver the two kernel metrics' and those of the
    step, the loaders and the idle shares (every layer metric that lists
    the three replay cells)."""
    before = _copy(tmp_path)
    old = _json(REPO, "BENCHMARK.json")
    new = json.loads(json.dumps(old))
    lists = (RATE,) + kernels
    if driver is None:
        lists += tuple(m["name"] for m in old["per_layer"]
                       if REPLAY <= set(m.get("workloads", ())))
        assert len(lists) - len(kernels) >= 21
    laid = lay(tmp_path)
    cell = _enter(new, laid, lists)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new, indent=1))
    # nothing that was there changed but the one file that makes files a
    # cell, and that by appended items alone
    for p, content in before.items():
        if p != tmp_path / "BENCHMARK.json":
            assert p.read_bytes() == content, f"{p} was edited"
    assert grown(old, new) == {name: [cell] for name in lists}
    assert [len(new[k]) - len(old[k]) for k in (
        "configs", "workloads", "end_to_end", "per_layer")] == [1, 1, 0, 1]
    assert _json(tmp_path, laid["config"]["file"]).get("driver") == driver
    failed, passed = _run_the_copy_s_tests(tmp_path, *READERS)
    assert failed == refused_by
    assert passed + len(failed) >= 64       # the readers ran (64 at PR 46)


# ----------------------------------------------- what the rules still hold
def _move(bench, metric, cell, into):
    (m,) = [m for m in bench["end_to_end"] + bench["per_layer"]
            if m["name"] == metric]
    if into:
        m["workloads"].append(cell)
    else:
        m["workloads"].remove(cell)


@pytest.mark.parametrize("metric,cell,into,refused_by", [
    (RATE, STREAM, True, RATE_GUARD),
    ("kernel_ms_per_step", "criteo1tb.replay", False, KERNEL_GUARD),
    ("step_kernels_roofline", "difacto1tb.replay", False, KERNEL_GUARD),
    ("kernel_ms_per_step", FOUR, True, KERNEL_GUARD),
    ("step_kernels_roofline", STREAM, True, KERNEL_GUARD),
    ("loader_source_ms", STREAM, True, TURN_GUARD),
    ("h2d_wait_ms", "difacto1tb.replay", False, TURN_GUARD),
    ("tcoo_pull_ms", "difacto1tb.replay", True, TCOO_GUARD),
], ids=["stream_cell_under_the_rate", "replay_off_the_kernels_list",
        "difacto_off_the_roofline_s_list", "four_chip_cell_under_the_kernels",
        "stream_cell_under_the_roofline", "stream_cell_under_a_loader_s_wait",
        "difacto_off_a_loader_s_wait", "difacto_under_the_compact_pull"])
def test_the_rules_refuse_what_the_counts_refused(tmp_path, metric, cell,
                                                  into, refused_by):
    """The tests PR 46 restated keep what they guard: the stream cell's
    rate is no end-to-end metric; the two kernel metrics list the
    one-chip cells of the minibatch step, the stream cell under the
    twins, the four-chip cell under the per-shard pair; a loader's waits
    list the replay cells and no cell of a one-pass mix; the compact pull
    lists cells of the `tcoo` kind alone."""
    _copy(tmp_path)
    bench = _json(REPO, "BENCHMARK.json")
    _move(bench, metric, cell, into)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    failed, _ = _run_the_copy_s_tests(tmp_path, RATE_GUARD, KERNEL_GUARD,
                                      TURN_GUARD, TCOO_GUARD)
    assert failed == {refused_by}


def test_grown_refuses_an_edit_to_an_accepted_entry():
    old = _json(REPO, "BENCHMARK.json")
    for edit in (lambda b: b["end_to_end"][0].update(bound=0.05),
                 lambda b: b["workloads"][0].update(why="another"),
                 lambda b: b["per_layer"][0]["workloads"].insert(0, "a"),
                 lambda b: b["per_layer"].pop(),
                 lambda b: b["end_to_end"][-1].update(bound=0.05),
                 lambda b: b["configs"][0]["reduced"].append("a"),
                 lambda b: b.update(run_seconds=10)):
        new = json.loads(json.dumps(old))
        edit(new)
        with pytest.raises(AssertionError):
            grown(old, new)
    assert grown(old, json.loads(json.dumps(old))) == {}
