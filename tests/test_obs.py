"""Telemetry tests: metrics registry under thread contention, trace
JSONL round-trip through tools/trace_viewer.py, scheduler metrics
aggregation across fake nodes, the Progress.row() race regression, and
an end-to-end WH_OBS_DIR smoke over a tiny in-process linear job."""

import importlib.util
import json
import os
import subprocess
import sys
import threading

import pytest

from wormhole_tpu.obs import metrics as obs_metrics
from wormhole_tpu.obs import report as obs_report
from wormhole_tpu.obs import trace as obs_trace
from wormhole_tpu.runtime.tracker import Scheduler, SchedulerClient
from wormhole_tpu.solver.progress import Progress

from conftest import synth_libsvm_text

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def retrace(monkeypatch):
    """Re-init tracing around a test and guarantee it ends disabled
    (the module inits from env at import; tests mutate the env)."""
    yield monkeypatch
    monkeypatch.delenv("WH_OBS_DIR", raising=False)
    obs_trace.init_from_env()
    assert obs_trace.ACTIVE is None


# ----------------------------------------------------------- instruments
def _hammer(fn, threads=8, iters=2000):
    barrier = threading.Barrier(threads)

    def work():
        barrier.wait()
        for i in range(iters):
            fn(i)

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return threads * iters


def test_counter_under_contention():
    c = obs_metrics.Counter("t.contended_counter")
    n = _hammer(lambda i: c.inc())
    assert c.value() == n


def test_gauge_under_contention():
    g = obs_metrics.Gauge("t.contended_gauge")
    _hammer(lambda i: g.set(i))
    # last write wins; whatever interleaving happened, the value must be
    # one that was actually set
    assert 0 <= g.value() <= 1999


def test_histogram_under_contention():
    h = obs_metrics.Histogram("t.contended_hist", reservoir=64)
    n = _hammer(lambda i: h.observe(i), threads=8, iters=2000)
    assert h.count == n
    assert h.min == 0.0 and h.max == 1999.0
    snap = h.snapshot()
    assert snap["count"] == n
    assert len(snap["res"]) == 64  # bounded no matter the volume
    assert all(0.0 <= v <= 1999.0 for v in snap["res"])
    q = h.quantile(0.5)
    assert 0.0 <= q <= 1999.0


def test_histogram_quantiles_exact_when_small():
    h = obs_metrics.Histogram("t.small_hist")
    for v in range(100):
        h.observe(v)
    assert h.quantile(0.0) == 0.0
    assert h.quantile(0.5) == 50.0
    assert h.quantile(1.0) == 99.0


def test_registry_get_or_create_and_reset():
    r = obs_metrics.Registry()
    assert r.counter("a") is r.counter("a")
    assert r.histogram("h") is r.histogram("h")
    r.counter("a").inc(3)
    r.gauge("g").set(7)
    with r.timer("h"):
        pass
    snap = r.snapshot()
    assert snap["counters"]["a"] == 3
    assert snap["gauges"]["g"] == 7.0
    assert snap["hists"]["h"]["count"] == 1
    r.reset()
    assert r.snapshot() == {"counters": {}, "gauges": {}, "hists": {}}


def test_merge_snapshots():
    a = obs_metrics.Registry()
    b = obs_metrics.Registry()
    a.counter("pushes").inc(10)
    b.counter("pushes").inc(5)
    b.counter("pulls").inc(2)
    a.gauge("epoch").set(1)
    b.gauge("epoch").set(3)
    for v in (0.1, 0.2):
        a.histogram("lat").observe(v)
    for v in (0.4, 0.8, 1.6):
        b.histogram("lat").observe(v)
    m = obs_metrics.merge_snapshots([a.snapshot(), b.snapshot()])
    assert m["counters"] == {"pushes": 15, "pulls": 2}
    assert m["gauges"]["epoch"] == 3.0  # max: the furthest-along node
    lat = m["hists"]["lat"]
    assert lat["count"] == 5
    assert lat["sum"] == pytest.approx(3.1)
    assert lat["min"] == 0.1 and lat["max"] == 1.6
    assert sorted(lat["res"]) == [0.1, 0.2, 0.4, 0.8, 1.6]
    stats = obs_metrics.hist_stats(lat)
    assert stats["mean"] == pytest.approx(3.1 / 5)
    assert stats["p99"] == 1.6
    # reservoir pooling stays bounded
    big = obs_metrics.Registry()
    for v in range(1000):
        big.histogram("lat").observe(float(v))
    m2 = obs_metrics.merge_snapshots([m, big.snapshot()], reservoir=128)
    assert m2["hists"]["lat"]["count"] == 1005
    assert len(m2["hists"]["lat"]["res"]) == 128


# ----------------------------------------------------------------- trace
def _load_trace_viewer():
    spec = importlib.util.spec_from_file_location(
        "trace_viewer", os.path.join(REPO, "tools", "trace_viewer.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_jsonl_roundtrip_through_viewer(tmp_path, retrace):
    retrace.setenv("WH_OBS_DIR", str(tmp_path))
    retrace.setenv("WH_RUN_ID", "test-run")
    tracer = obs_trace.init_from_env()
    assert tracer is not None and obs_trace.ACTIVE is tracer
    with obs_trace.span("step", cat="solver", part=3):
        pass
    obs_trace.event("recovered", cat="recovery", rank=1)
    with pytest.raises(ValueError):
        with obs_trace.span("boom", cat="solver"):
            raise ValueError("x")  # span must record, not swallow
    path = tracer.path
    assert os.path.basename(path).startswith("trace-")
    lines = [json.loads(l) for l in open(path)]
    anchor = lines[0]
    assert anchor["ph"] == "M" and anchor["run"] == "test-run"
    assert {"wall", "mono", "node", "pid"} <= set(anchor)
    phs = [l["ph"] for l in lines[1:]]
    assert phs == ["X", "i", "X"]
    assert lines[1]["name"] == "step" and lines[1]["args"]["part"] == 3
    assert lines[2]["args"]["rank"] == 1
    assert lines[3]["args"]["error"] == "ValueError"

    tv = _load_trace_viewer()
    merged = tv.merge_traces([path])
    evs = merged["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"process_name", "step", "recovered", "boom"} <= names
    step = next(e for e in evs if e["name"] == "step")
    assert step["ph"] == "X" and step["ts"] >= 0 and step["dur"] >= 0
    inst = next(e for e in evs if e["name"] == "recovered")
    assert inst["ph"] == "i" and inst["s"] == "t"
    assert merged["metadata"]["run_ids"] == ["test-run"]
    # the viewer CLI writes valid JSON too
    rc = tv.main([str(tmp_path), "-o", str(tmp_path / "out.json")])
    assert rc == 0
    assert json.load(open(tmp_path / "out.json"))["traceEvents"]


def test_trace_viewer_merges_nodes_on_shared_axis(tmp_path):
    # two fake nodes whose monotonic clocks disagree wildly but whose
    # anchors pin the same wall instant: the viewer must line them up
    for node, mono0, ts in (("worker-0", 5.0, 5.5), ("server-0", 900.0,
                                                     900.5)):
        with open(tmp_path / f"trace-{node}-1.jsonl", "w") as fh:
            fh.write(json.dumps({"ph": "M", "run": "r", "node": node,
                                 "pid": 1, "wall": 1000.0,
                                 "mono": mono0}) + "\n")
            fh.write(json.dumps({"ph": "X", "name": "op", "cat": "c",
                                 "ts": ts, "dur": 0.1, "tid": 0}) + "\n")
    tv = _load_trace_viewer()
    evs = tv.merge_traces([str(tmp_path / f) for f in os.listdir(tmp_path)])
    spans = [e for e in evs["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 2
    # both spans started 0.5s after their anchor = the same wall time
    assert spans[0]["ts"] == pytest.approx(spans[1]["ts"], abs=1.0)
    # distinct chrome pids, both named
    pids = {e["pid"] for e in spans}
    named = {e["pid"] for e in evs["traceEvents"]
             if e.get("name") == "process_name"}
    assert len(pids) == 2 and pids <= named


def test_trace_disabled_is_noop(retrace):
    retrace.delenv("WH_OBS_DIR", raising=False)
    assert obs_trace.init_from_env() is None
    s1 = obs_trace.span("a", x=1)
    s2 = obs_trace.span("b")
    assert s1 is s2  # shared null object: zero allocation per call
    with s1:
        pass
    obs_trace.event("nothing")  # must not raise


# ------------------------------------------------- scheduler aggregation
def test_scheduler_metrics_verb_aggregates_nodes():
    sched = Scheduler(node_timeout=10)
    sched.serve()
    try:
        def snap(pushes, epoch, lat):
            r = obs_metrics.Registry()
            r.counter("t.sched_agg.pushes").inc(pushes)
            r.gauge("t.sched_agg.epoch").set(epoch)
            for v in lat:
                r.histogram("t.sched_agg.lat").observe(v)
            return r.snapshot()

        w0 = SchedulerClient(sched.uri, "worker-0")
        w1 = SchedulerClient(sched.uri, "worker-1")
        # heartbeats piggyback the snapshots (LivenessPinger contract)
        w0.call(op="epoch", metrics=snap(7, 1, [0.1]))
        w1.call(op="epoch", metrics=snap(5, 2, [0.3, 0.5]))
        got = w0.call(op="metrics")
        assert got["ok"]
        assert got["nodes"] == ["worker-0", "worker-1"]
        agg = got["aggregate"]
        assert agg["counters"]["t.sched_agg.pushes"] == 12
        assert agg["gauges"]["t.sched_agg.epoch"] == 2.0
        assert agg["hists"]["t.sched_agg.lat"]["count"] == 3
        # the scheduler folds in its own registry: dispatch latency for
        # the ops above must already be visible
        assert agg["hists"]["sched.op.epoch_s"]["count"] >= 2

        # a later snapshot from the same node REPLACES its old one
        # (respawned-incarnation semantics) instead of double counting
        w0.call(op="epoch", metrics=snap(9, 1, []))
        agg = w0.call(op="metrics")["aggregate"]
        assert agg["counters"]["t.sched_agg.pushes"] == 14
    finally:
        sched.stop()


def test_report_build_and_write(tmp_path, retrace):
    r = obs_metrics.Registry()
    r.counter("ps.client.bytes_push").inc(111)
    r.counter("ps.client.replays").inc(4)
    r.counter("ps.client.replay_dedup").inc(4)
    for v in (0.002, 0.004):
        r.histogram("ps.client.rpc_s").observe(v)
    report = obs_report.build(
        r.snapshot(), nodes=["worker-0", "scheduler"], run_id="rid",
        ps_stats={0: {"num_push": 10, "num_pull": 20}})
    s = report["summary"]
    assert s["num_push"] == 10 and s["num_pull"] == 20  # stats() wins
    assert s["bytes_pushed"] == 111
    assert s["journal_replays"] == 4 and s["replay_dedup_hits"] == 4
    assert s["rpc_p99_ms"] == pytest.approx(4.0)
    assert report["nodes"] == ["scheduler", "worker-0"]
    assert report["hists"]["ps.client.rpc_s"]["count"] == 2
    # machine line round-trips
    line = obs_report.machine_line(report)
    assert line.startswith(obs_report.REPORT_PREFIX)
    assert json.loads(line[len(obs_report.REPORT_PREFIX):]) == json.loads(
        json.dumps(report, default=str))
    for ln in obs_report.format_lines(report):
        assert isinstance(ln, str)
    retrace.setenv("WH_OBS_DIR", str(tmp_path))
    path = obs_report.write(report)
    assert path == str(tmp_path / "run_report.json")
    assert json.load(open(path))["summary"]["num_push"] == 10


# ------------------------------------------------- progress row race fix
def test_progress_row_snapshot_consistent_under_merge():
    """Regression: row() used to take the increment under the lock but
    read totals unlocked, so merges landing in between produced rows
    whose cumulative increments never reconciled with the totals."""
    prog = Progress()
    stop = threading.Event()

    def merger():
        while not stop.is_set():
            prog.merge({"nex": 1.0})

    ts = [threading.Thread(target=merger) for _ in range(4)]
    for t in ts:
        t.start()
    try:
        seen = 0.0
        for _ in range(300):
            inc, tot = prog.take_row_snapshot()
            seen += inc.get("nex", 0.0)
            # the invariant the race used to break: totals in a snapshot
            # are EXACTLY the sum of all increments handed out so far
            assert seen == tot.get("nex", 0.0)
    finally:
        stop.set()
        for t in ts:
            t.join()
    inc, tot = prog.take_row_snapshot()
    assert seen + inc.get("nex", 0.0) == tot.get("nex", 0.0)
    assert prog.row(0.0)  # formatting still works on top of the snapshot


# ------------------------------------------------------ end-to-end smoke
def test_obs_smoke_linear_job(tmp_path, retrace):
    """Tiny in-process linear run with WH_OBS_DIR set: report + trace
    files must land and be well-formed."""
    from wormhole_tpu.models.linear import LinearConfig, LinearLearner
    from wormhole_tpu.parallel.mesh import make_mesh
    from wormhole_tpu.solver.minibatch_solver import MinibatchSolver

    data = tmp_path / "train.libsvm"
    data.write_text(synth_libsvm_text(n_rows=256, n_feat=100,
                                      nnz_per_row=8))
    obs_dir = tmp_path / "obs"
    retrace.setenv("WH_OBS_DIR", str(obs_dir))
    retrace.setenv("WH_RUN_ID", "smoke-run")
    retrace.delenv("WH_ROLE", raising=False)
    obs_trace.init_from_env()
    cfg = LinearConfig(train_data=str(data), data_format="libsvm",
                       minibatch=64, num_buckets=1 << 9, nnz_per_row=8,
                       algo="ftrl", max_data_pass=1)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    # the registry is the process's, and the solver observes train.stage.*
    # with or without WH_OBS_DIR: the report holds every solver pass this
    # worker ran before. What this run did is the difference
    before = obs_metrics.REGISTRY.snapshot()["hists"]
    MinibatchSolver(lrn, cfg, verbose=False).run()
    obs_trace.ACTIVE.close()
    after = obs_metrics.REGISTRY.snapshot()["hists"]

    def grew(name, field):
        return after[name][field] - before.get(name, {}).get(field, 0)

    report = json.load(open(obs_dir / "run_report.json"))
    assert report["run_id"] == "smoke-run"
    assert set(report) >= {"summary", "counters", "gauges", "hists",
                           "nodes"}
    assert {"load", "step", "metrics"} <= set(
        report["train_stages"]["stages"])
    # a loader's whole cycle a batch, for an operator without a profiler
    cycle = ("source", "pack", "h2d_wait", "h2d", "put")
    assert set(cycle) <= set(report["train_stages"]["stages"])
    for s in cycle:
        assert grew(f"train.stage.{s}_s", "count") == 4, s
    # the pass loop's per-batch timings are the train.stage.* histograms
    # alone: it feeds no `perf.*` mirror beside them
    stages = [f"train.stage.{s}_s" for s in ("load", "step", "metrics")]
    for name in stages + ["train.stage.total_s"]:
        assert grew(name, "count") == 4, name
        assert report["hists"][name]["count"] == after[name]["count"]
    assert not [k for k in after if k.startswith("perf.")
                and k not in before]
    # training-step stage attribution: the train thread's pipeline
    # stages (load + step + metrics) are the whole of the per-batch wall
    assert sum(grew(n, "sum") for n in stages) == pytest.approx(
        grew("train.stage.total_s", "sum"), rel=1e-6)
    traces = [f for f in os.listdir(obs_dir)
              if f.startswith("trace-") and f.endswith(".jsonl")]
    assert len(traces) == 1
    lines = [json.loads(l) for l in open(obs_dir / traces[0])]
    assert lines[0]["ph"] == "M" and lines[0]["run"] == "smoke-run"
    spans = [l for l in lines if l.get("ph") == "X"]
    assert any(l["name"] == "solver.train_pass" for l in spans)
    assert any(l["name"] == "solver.train_step" for l in spans)
    tv = _load_trace_viewer()
    assert tv.merge_traces([str(obs_dir / traces[0])])["traceEvents"]


def test_package_import_pulls_no_obs():
    """`import wormhole_tpu` with telemetry disabled must not import the
    obs package (the no-op guarantee starts at import time)."""
    # WH_SAN is stripped too: the sanitizer's class instrumentation
    # imports obs by design, and this test probes the *default* path
    env = {k: v for k, v in os.environ.items()
           if k not in ("WH_OBS_DIR", "WH_SAN")}
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; import wormhole_tpu; "
         "mods = [m for m in sys.modules "
         "if m.startswith('wormhole_tpu.obs')]; "
         "assert not mods, mods; print('clean')"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clean" in r.stdout
