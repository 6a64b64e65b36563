"""The pass's turn and a loader's waits as layer metrics (PR 38): seven
data files under `benchmark/layer_metrics/`, each through a reducer the
benchmark already has, read from the profile of a real solver run on the
CPU (three passes, the pack cache on). Since PR 41 `BENCHMARK.json`
holds their entries, after `tcoo_pull_ms` (whose test finds its entry by
name now); the four of a pass's turn list the cells whose window holds
one. Nothing here is a speed."""

import importlib
import json
import os
import sys
import tempfile
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import xplane  # noqa: E402
from benchmark.reducers import _spans  # noqa: E402
from conftest import synth_libsvm_text  # noqa: E402

LOOP, H2D, DEVICE = "pass loop and loader pool", "stage h2d", "device"
# name -> (layer, unit, source, reducer, the span it reads)
SEVEN = {
    "loader_source_ms": (LOOP, "ms", "program_span", "span_mean",
                         "loader.source"),
    "loader_put_wait_ms": (LOOP, "ms", "program_span", "span_mean",
                           "loader.put_wait"),
    "h2d_wait_ms": (H2D, "ms", "program_span", "span_mean",
                    "loader.h2d_wait"),
    "pass_start_ms": (LOOP, "ms", "program_span", "span_mean",
                      "solver.pass_start"),
    "pass_end_ms": (LOOP, "ms", "program_span", "span_mean",
                    "solver.pass_end"),
    "idle_pass_start_share": (DEVICE, "%", "device_trace",
                              "idle_overlap_share", "solver.pass_start"),
    "idle_pass_end_share": (DEVICE, "%", "device_trace",
                            "idle_overlap_share", "solver.pass_end"),
}


def _spec(name):
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           name + ".json")) as fh:
        return json.load(fh)


def _read(name, ctx):
    """As run.py's `per_layer` does: the metric's file, then its reducer."""
    spec = _spec(name)
    reducer = importlib.import_module("benchmark.reducers." + spec["reducer"])
    return reducer.read(ctx, **spec.get("params", {}))


@pytest.mark.parametrize("name", sorted(SEVEN))
def test_the_file_loads_and_names_an_accepted_reducer(name):
    layer, unit, source, reducer, span = SEVEN[name]
    spec = _spec(name)
    assert spec == {
        "name": name, "layer": layer, "unit": unit, "better": "lower",
        "source": source, "moves": "train_ex_per_s", "reducer": reducer,
        "params": spec["params"]}
    assert spec["params"]["span"] == span
    assert spec["params"].get("value", "duration") == "duration"
    # a layer the benchmark already names, a reducer it already has (the
    # parent's: this PR adds none), a span the program registers
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    assert layer in {m["layer"] for m in bench["per_layer"]}
    accepted = {_spec(m["name"])["reducer"] for m in bench["per_layer"]}
    assert reducer in accepted
    from wormhole_tpu.obs import names

    assert span in names.SPANS


def test_the_benchmark_lists_the_seven_after_the_accepted_entries():
    """Each is an entry of `per_layer`, after `tcoo_pull_ms` (that it
    equals its file is test_benchmark_files.py's, for every entry). A
    loader's waits are read in every cell (by rule since PR 46: the
    three replay cells and whatever cell a later PR appends, the stream
    cell under the twin); the four of a pass's turn list the cells whose
    window holds a turn: not a mix that says its window lies in one
    pass (`min_pass_rows` over 0)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    names = [m["name"] for m in bench["per_layer"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    one_pass = set()
    for w in bench["workloads"]:
        with open(os.path.join(REPO, "benchmark", "traffic",
                               w["traffic"] + ".json")) as fh:
            if json.load(fh)["min_pass_rows"] > 0:
                one_pass.add(w["name"])
    assert "criteo1tb.crb-stream" in one_pass
    replay = {"criteo1tb.replay", "criteo1tb-2p30.replay-8",
              "difacto1tb.replay"}
    for name in SEVEN:
        assert names.index(name) > names.index("tcoo_pull_ms")
        listed = set(entries[name]["workloads"])
        assert replay <= listed and not listed & one_pass, name
        if "pass_" not in name:
            # every cell: the replay cells under the name, the stream
            # cell under its twin's (PR 43: its rate is a layer metric)
            assert set(entries[name + ".stream"]["workloads"]) == one_pass


@pytest.fixture(scope="module")
def profiled_run(tmp_path_factory):
    """The `.xplane.pb` of a real solver run on the CPU, where run.py
    would have left it: three passes of eleven batches, the pack cache
    on, two loaders."""
    from wormhole_tpu.models.linear import LinearConfig, LinearLearner
    from wormhole_tpu.solver.minibatch_solver import MinibatchSolver

    tmp = tmp_path_factory.mktemp("pass_turn")
    p = tmp / "d.libsvm"
    p.write_text(synth_libsvm_text(n_rows=640, n_feat=100, nnz_per_row=8,
                                   seed=7))
    cfg = LinearConfig(train_data=str(p).replace(".libsvm", r"\.libsvm"),
                       minibatch=64, num_buckets=1 << 10, nnz_per_row=16,
                       max_data_pass=3, num_parts_per_file=2)
    trace = tmp / "whbench_cpu" / "trace"
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("WH_PACK_CACHE", "1")
        mp.setenv("WORMHOLE_PROFILE_DIR", str(trace))
        solver = MinibatchSolver(LinearLearner(cfg), cfg, num_loaders=2,
                                 verbose=False)
        solver.run()
    return tmp


def _with_a_chip(pd):
    """A CPU profile has no device plane. Give it one whose operations
    are the run's own `step.fetch` spans (the host waits there for the
    device): the idle gaps are then what lies between two fetches."""
    fetches = [e for plane in pd.planes for line in plane.lines
               for e in line.events if e.name == "step.fetch"]
    ops = NS(name=xplane.OPS_LINE, events=[
        NS(name="%step = f32[8]{0} fusion(%p)", start_ns=e.start_ns,
           duration_ns=e.duration_ns) for e in fetches])
    return NS(planes=[*pd.planes, NS(name="/device:TPU:0", lines=[ops])])


@pytest.fixture
def ctx(profiled_run, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(profiled_run))
    monkeypatch.setattr(_spans, "_KEPT", {})
    load = xplane.load
    monkeypatch.setattr(xplane, "load", lambda path: _with_a_chip(load(path)))
    t = _spans.parse(xplane.load(_spans.newest_trace()))
    return {"trace": {"steps_marked": t["steps_marked"],
                      "busy_s": t["busy_s"]}}


def test_each_of_the_seven_reads_a_number_from_a_real_run(ctx):
    got = {name: _read(name, ctx) for name in SEVEN}
    assert all(isinstance(v, float) and v >= 0.0 for v in got.values()), got
    t = _spans.of_run(ctx)
    # what each was the mean of: three passes, a wait before every
    # staging but a loader's first of a pass, a put a batch
    steps = len(_spans.spans(t, "solver.train_step"))
    assert steps >= 30
    assert len(_spans.spans(t, "solver.pass_start")) == 3 == len(
        _spans.spans(t, "solver.pass_end"))
    assert len(_spans.spans(t, "loader.put_wait")) == steps
    assert steps - 6 <= len(_spans.spans(t, "loader.h2d_wait")) < steps
    assert len(_spans.spans(t, "loader.source")) >= steps
    # a pass's start holds its first wait; the idle time between two
    # passes lies under the end of one and the start of the next, and
    # the three spans follow one another on one thread
    waits = importlib.import_module(
        "benchmark.reducers.idle_overlap_share").read(
            ctx, span="solver.queue_wait")
    assert got["idle_pass_start_share"] > 0 and got["idle_pass_end_share"] > 0
    assert (got["idle_pass_start_share"] + got["idle_pass_end_share"]
            <= 100.0 + 1e-9)
    assert waits is not None and waits <= 100.0


def test_a_program_without_the_spans_leaves_all_seven_out(monkeypatch,
                                                          tmp_path):
    """The parent's trace holds no such span: each reducer gives None and
    the line would leave the metric out; none raises."""
    import gzip

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    monkeypatch.setattr(_spans, "_KEPT", {})
    d = tmp_path / "whbench_old" / "trace"
    d.mkdir(parents=True)
    with gzip.open(os.path.join(
            HERE, "crb-stream-v5e-spans.xplane.pb.gz")) as fh:
        (d / "host.xplane.pb").write_bytes(fh.read())
    summary = xplane.summarize(xplane.load(str(d / "host.xplane.pb")), 4.04)
    old = {"trace": summary}
    assert _spans.of_run(old) is not None        # it is the run's own
    for name in SEVEN:
        assert _read(name, old) is None, name
