"""The reducers that read the program's own spans out of the traced
run's profile (benchmark/reducers/_spans.py, span_mean, span_per_rows,
idle_overlap_share), on a hand-made profile with known answers: they
find the run's trace by themselves, refuse one that is not the run's,
and leave a metric out where the program wrote no such span."""

import gzip
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

from benchmark import xplane  # noqa: E402
from benchmark.reducers import (_spans, idle_overlap_share,  # noqa: E402
                                span_mean, span_per_rows)

MS = 10**6      # ns


def _ev(name, start, end, **stats):
    return NS(name=name, start_ns=start, duration_ns=end - start,
              stats=list(stats.items()))


def _profile():
    """Two device ops with one idle gap of 100 ms between them
    (100..200 ms). The train thread dispatches over 50..150 (half the
    gap), fetches over 150..260 (the other half), then waits on the
    queue after the last op; a loader packs twice; a parser reads and
    parses two chunks of half a batch each."""
    dev = NS(name="/device:TPU:0", lines=[
        NS(name=xplane.OPS_LINE, events=[
            _ev("%tile_gather.3 = f32[8]{0} custom-call(%p), "
                'custom_call_target="tpu_custom_call"', 0, 100 * MS),
            _ev("%fusion = f32[8]{0} fusion(%tile_gather.3)", 200 * MS,
                300 * MS)])])
    train = NS(name="python", events=[
        _ev(xplane.STEP_MARK, 50 * MS, 260 * MS),
        _ev("step.dispatch", 50 * MS, 150 * MS, kind="tcoo"),
        _ev("step.fetch", 150 * MS, 260 * MS),
        _ev("solver.queue_wait", 300 * MS, 400 * MS)])
    loader = NS(name="python", events=[
        _ev("loader.pack", 0, 300 * MS, part=0, i=0, rows=65536,
            cpu_us=100_000),
        _ev("loader.pack", 300 * MS, 700 * MS, part=0, i=1, rows=65536,
            cpu_us=200_000)])
    parser = NS(name="python", events=[
        _ev("data.read", 0, 2 * MS, part=0, bytes=10, cpu_us=1000),
        _ev("data.parse", 2 * MS, 8 * MS, part=0, rows=32768, cpu_us=2000),
        _ev("data.read", 8 * MS, 8 * MS, part=0, cpu_us=0),
        _ev("data.parse", 10 * MS, 16 * MS, part=0, rows=32768,
            cpu_us=2000)])
    return NS(planes=[dev, NS(name="/host:CPU",
                              lines=[train, loader, parser])])


CTX = {"trace": {"steps_marked": 1, "busy_s": 0.2},
       "batch": {"rows": 65536}}


@pytest.fixture
def run_dir(tmp_path, monkeypatch):
    """A `whbench_*/trace` under the temporary directory, as run.py
    leaves one while the reducers run, holding the hand-made profile."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    monkeypatch.setattr(_spans, "_KEPT", {})
    d = tmp_path / "whbench_abc" / "trace" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    loads = []
    monkeypatch.setattr(xplane, "load",
                        lambda path: loads.append(path) or _profile())
    return loads


def test_parse_keeps_spans_threads_and_device_intervals():
    t = _spans.parse(_profile())
    assert t["steps_marked"] == 1 and t["busy_s"] == pytest.approx(0.2)
    assert t["busy"] == {"/device:TPU:0": [(0, 100 * MS),
                                           (200 * MS, 300 * MS)]}
    assert _spans.idle(t) == {"/device:TPU:0": [(100 * MS, 200 * MS)]}
    (d,) = _spans.spans(t, "step.dispatch")
    assert d[:2] == (50 * MS, 150 * MS) and d[3] == {"kind": "tcoo"}
    packs = _spans.spans(t, "loader.pack")
    assert [p[3]["i"] for p in packs] == [0, 1]
    assert packs[0][2] != d[2]                  # another thread's line
    assert _spans.spans(t, "no.such") == []


def test_an_idle_gap_half_inside_a_span_reads_fifty(run_dir):
    assert idle_overlap_share.read(CTX, span="step.dispatch") == (
        pytest.approx(50.0))
    assert idle_overlap_share.read(CTX, span="step.fetch") == (
        pytest.approx(50.0))
    # waited after the last device op: outside every gap
    assert idle_overlap_share.read(CTX, span="solver.queue_wait") == 0.0
    assert idle_overlap_share.read(CTX, span="solver.merge") is None
    assert len(run_dir) == 1                    # read once, then kept


def test_span_means_wall_and_cpu(run_dir):
    assert span_mean.read(CTX, span="step.dispatch") == pytest.approx(100.0)
    assert span_mean.read(CTX, span="step.fetch") == pytest.approx(110.0)
    assert span_mean.read(CTX, span="loader.pack") == pytest.approx(350.0)
    assert span_mean.read(CTX, span="loader.pack", value="cpu_us") == (
        pytest.approx(150.0))
    # a span that carries no such argument: nothing to read
    assert span_mean.read(CTX, span="step.fetch", value="cpu_us") is None
    assert span_mean.read(CTX, span="no.such") is None


def test_span_time_per_batch_of_rows(run_dir):
    both = ["data.read", "data.parse"]
    # 2 + 6 + 0 + 6 ms for 65,536 rows = one batch
    assert span_per_rows.read(CTX, spans=both) == pytest.approx(14.0)
    assert span_per_rows.read(CTX, spans=both, value="cpu_us") == (
        pytest.approx(5.0))
    half = dict(CTX, batch={"rows": 32768})
    assert span_per_rows.read(half, spans=both) == pytest.approx(7.0)
    # reads alone produced no rows: nothing to divide by
    assert span_per_rows.read(CTX, spans=["data.read"]) is None


@pytest.mark.parametrize("said", [{"steps_marked": 2, "busy_s": 0.2},
                                  {"steps_marked": 1, "busy_s": 0.21}])
def test_a_foreign_trace_reads_none(run_dir, said):
    """The newest trace under the temporary directory is another run's
    (its marks or its busy time are not what this run's trace gave):
    every span reducer leaves its metric out."""
    ctx = dict(CTX, trace=said)
    assert _spans.of_run(ctx) is None
    assert idle_overlap_share.read(ctx, span="step.dispatch") is None
    assert span_mean.read(ctx, span="step.dispatch") is None
    assert span_per_rows.read(ctx, spans=["data.parse"]) is None


def test_no_trace_directory_reads_none(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    assert _spans.newest_trace() is None and _spans.of_run(CTX) is None
    (tmp_path / "whbench_x" / "trace").mkdir(parents=True)   # empty
    assert _spans.newest_trace() is None
    assert span_mean.read(CTX, span="step.dispatch") is None


def test_the_newest_run_directory_wins(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    for i, name in enumerate(("whbench_old", "whbench_new")):
        d = tmp_path / name / "trace"
        d.mkdir(parents=True)
        (d / "x.xplane.pb").write_bytes(b"")
        os.utime(d, (1000 + i, 1000 + i))
    assert _spans.newest_trace() == str(
        tmp_path / "whbench_new" / "trace" / "x.xplane.pb")


# ------------------------------------------------------- a recorded trace
# `crb-stream-v5e-spans.xplane.pb.gz`: the whole trace of a traced
# `criteo1tb.crb-stream` run of PR 24's tree on one TPU v5 lite (my chip
# run, PR 24; seed 2147490001, 4.04 s, 36 train steps), kept with
# `--keep-trace` and gzipped. The numbers asserted from it are what that
# run's result line printed: properties of the file, not benchmark results.
RECORDED = {
    "window_s": 4.0408337899999935, "trace_steps": 36,
    "metrics": {
        "parse_ms_per_batch": 102.03902037142856,
        "parse_cpu_ms_per_batch": 83.42845714285714,
        "pack_cpu_ms": 193.82297058823528,
        "step_dispatch_ms": 3.3504804166666666,
        "step_fetch_ms": 105.33983133333332,
        "merge_ms": 0.023731790524185416,
        "idle_dispatch_share": 7.735213568887672,
        "idle_fetch_share": 74.74821974759584,
        "idle_queue_wait_share": 0.28416538258837687,
        "tile_gather_ms": 34.88097925000001,
        "fused_update_ms": 31.67104944444445,
        "coo_push_ms": 9.692376750000005,
        "kernel_ms_per_step": 76.24440544444445,
    },
}


@pytest.fixture
def recorded_run(tmp_path, monkeypatch):
    """The recorded trace where run.py would have left it, and the `ctx`
    run.py would have built from it."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    monkeypatch.setattr(_spans, "_KEPT", {})
    d = tmp_path / "whbench_rec" / "trace" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    with gzip.open(os.path.join(
            HERE, "crb-stream-v5e-spans.xplane.pb.gz")) as fh:
        (d / "host.xplane.pb").write_bytes(fh.read())
    summary = xplane.summarize(xplane.load(str(d / "host.xplane.pb")),
                               RECORDED["window_s"])
    # the one metric of the twelve that is not in the trace: the mean of
    # the run's `train.stage.metrics_s` histogram, as its line printed it
    merge_s = 1e-3 * RECORDED["metrics"]["merge_ms"]
    return {"trace": summary, "trace_steps": RECORDED["trace_steps"],
            "batch": {"rows": 65536},
            "hist": {"train.stage.metrics_s": {"count": 1,
                                               "sum": merge_s}}}


def _read_metric(name, ctx):
    """As run.py's `per_layer` does: the metric's file, then its reducer."""
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           name + ".json")) as fh:
        spec = json.load(fh)
    reducer = importlib.import_module(
        "benchmark.reducers." + spec["reducer"])
    return reducer.read(ctx, **spec.get("params", {}))


def test_recorded_trace_gives_all_twelve_metrics(recorded_run):
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        new = [m["name"] for m in json.load(fh)["per_layer"]
               if m["name"] in RECORDED["metrics"]
               and m["name"] != "kernel_ms_per_step"]
    assert len(new) == 12
    got = {name: _read_metric(name, recorded_run)
           for name in new + ["kernel_ms_per_step"]}
    for name, want in RECORDED["metrics"].items():
        assert got[name] == pytest.approx(want, rel=1e-9), name
    # the kernels, by name, are the step's custom calls and nothing else
    assert (got["tile_gather_ms"] + got["fused_update_ms"]
            + got["coo_push_ms"]) == pytest.approx(
                got["kernel_ms_per_step"], rel=1e-9)
    # work is never more than wall
    assert got["parse_cpu_ms_per_batch"] <= got["parse_ms_per_batch"]
    assert got["pack_cpu_ms"] <= span_mean.read(recorded_run,
                                                span="loader.pack")


def test_recorded_trace_closes(recorded_run):
    """Dispatch + fetch is the tap's `bench.step` mark to within 2 %;
    the device's idle time lies under dispatch, fetch and queue wait but
    for the train thread's time between those spans. ISSUE 24 expected at
    least 85 % there; this run reads 82.8 % (PERF.md §6, PR 24: between
    the spans the train thread waits to get the interpreter lock back
    from a loader thread), so 80 is what the file can pin."""
    t = _spans.of_run(recorded_run)
    marks = [(b - a) * 1e-6 for a, b, _, _ in _spans.spans(
        t, xplane.STEP_MARK)]
    assert len(marks) == 36
    inner = (span_mean.read(recorded_run, span="step.dispatch")
             + span_mean.read(recorded_run, span="step.fetch"))
    assert inner == pytest.approx(sum(marks) / len(marks), rel=0.02)
    assert inner <= sum(marks) / len(marks)
    shares = [idle_overlap_share.read(recorded_run, span=s) for s in (
        "step.dispatch", "step.fetch", "solver.queue_wait")]
    assert 80.0 <= sum(shares) <= 100.0
    assert shares[1] > shares[0] > shares[2]    # the fetch leads
    # every step's spans sit on one thread, the packs on others
    train = {line for _, _, line, _ in _spans.spans(t, "step.fetch")}
    packs = {line for _, _, line, _ in _spans.spans(t, "loader.pack")}
    assert len(train) == 1 and packs and not train & packs
    # a step's (part, i) names one pack of the trace or one before it
    steps = _spans.spans(t, "solver.train_step")
    assert all({"part", "i"} <= set(a) for _, _, _, a in steps)


def test_recorded_breakdown_names_the_kernels(recorded_run):
    names = [n for n, _ in xplane.breakdown(recorded_run["trace"])[
        "device_ops"]]
    assert names[:2] == ["%tile_gather.1 custom-call[tpu_custom_call]",
                         "%fused_update.1 custom-call[tpu_custom_call]"]
    assert "%coo_push.1 custom-call[tpu_custom_call]" in names
    assert not any("train_step_tcoo" in n for n in names)


def test_the_parents_trace_has_no_spans_and_reads_none(tmp_path,
                                                       monkeypatch):
    """The older recorded trace is of a program without the spans and
    with unnamed kernels (PR 23): every new metric but the histogram's is
    left out, none raises."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    monkeypatch.setattr(_spans, "_KEPT", {})
    d = tmp_path / "whbench_old" / "trace"
    d.mkdir(parents=True)
    with gzip.open(os.path.join(
            HERE, "crb-stream-v5e-4s.xplane.pb.gz")) as fh:
        (d / "host.xplane.pb").write_bytes(fh.read())
    summary = xplane.summarize(xplane.load(str(d / "host.xplane.pb")), 4.05)
    ctx = {"trace": summary, "trace_steps": 35, "batch": {"rows": 65536},
           "hist": {}}
    assert _spans.of_run(ctx) is not None        # it is the run's own
    for name in RECORDED["metrics"]:
        if name != "kernel_ms_per_step":
            assert _read_metric(name, ctx) is None, name
