"""Solver harness tests: workload pool (straggler/failure re-assignment
with fake workloads, SURVEY §4), full solver loop, checkpoint/resume,
predict output."""

import os
import re
import time

import numpy as np
import pytest

from wormhole_tpu.models.linear import LinearConfig, LinearLearner
from wormhole_tpu.parallel.mesh import make_mesh
from wormhole_tpu.solver.minibatch_solver import MinibatchSolver
from wormhole_tpu.solver.workload import WorkloadPool, WorkType
from wormhole_tpu.utils import checkpoint as ckpt

from conftest import profiled_spans, synth_libsvm_text


# ------------------------------------------------------------- pool logic
def _fake_pool(tmp_path, nfiles=4, nparts=2):
    for i in range(nfiles):
        (tmp_path / f"part-{i}").write_text("")
    pool = WorkloadPool()
    n = pool.add(str(tmp_path / r"part-\d+"), nparts)
    assert n == nfiles
    return pool


def test_pool_dispatch_all(tmp_path):
    pool = _fake_pool(tmp_path)
    got = []
    while True:
        item = pool.get("w0")
        if item is None:
            break
        got.append(item)
    assert len(got) == 8  # 4 files x 2 parts
    for pid, f in got:
        pool.finish(pid)
    assert pool.is_finished()


def test_pool_failure_requeue(tmp_path):
    """Dead node's parts go back to available (data_parallel.h:131-135)."""
    pool = _fake_pool(tmp_path)
    a = pool.get("alive")
    d1 = pool.get("dead")
    d2 = pool.get("dead")
    assert pool.reset("dead") == 2
    remaining = []
    while (item := pool.get("alive")) is not None:
        remaining.append(item)
    # the 2 re-queued parts are dispatchable again
    assert len(remaining) == 7
    assert pool.pending() == 8


def test_pool_straggler_requeue(tmp_path):
    """A job running > max(2 x mean, 5s)... the 5s floor makes real waits
    slow, so exercise the sample-count gate and the limit math."""
    pool = _fake_pool(tmp_path, nfiles=6, nparts=2)
    # fewer than 10 finished -> watchdog must not fire
    s = pool.get("w0")
    assert pool.remove_stragglers() == 0
    pool.finish(s[0])
    for _ in range(10):
        pid, _f = pool.get("w0")
        pool.finish(pid)
    # one long-running assignment, backdated past the 5s floor
    pid, _f = pool.get("slow")
    pool._parts[pid]["t_start"] -= 100.0
    assert pool.remove_stragglers() == 1
    # it is available again and finishing the original id is idempotent
    assert pool.get("w1") is not None
    pool.finish(pid)
    pool.finish(pid)


def test_pool_finish_after_reassign_no_doublecount(tmp_path):
    pool = _fake_pool(tmp_path, nfiles=1, nparts=1)
    pid, _ = pool.get("a")
    pool.finish(pid)
    n = pool.num_finished
    pool.finish(pid)
    assert pool.num_finished == n


# ------------------------------------------------------------- solver loop
@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("solver_data")
    for i in range(3):
        (d / f"train-part_{i}.libsvm").write_text(
            synth_libsvm_text(n_rows=400, n_feat=200, nnz_per_row=10,
                              seed=i))
    (d / "val-part_0.libsvm").write_text(
        synth_libsvm_text(n_rows=400, n_feat=200, nnz_per_row=10, seed=99))
    return d


def _cfg(d, tmp_path, **kw):
    defaults = dict(
        train_data=str(d / r"train-part_.*\.libsvm"),
        val_data=str(d / r"val-part_.*\.libsvm"),
        data_format="libsvm",
        minibatch=128,
        num_buckets=1 << 10,
        nnz_per_row=16,
        algo="ftrl",
        lr_eta=0.5,
        max_data_pass=2,
        num_parts_per_file=2,
        model_out=str(tmp_path / "model/out"),
    )
    defaults.update(kw)
    return LinearConfig(**defaults)


def test_solver_end_to_end(data_dir, tmp_path):
    cfg = _cfg(data_dir, tmp_path)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    solver = MinibatchSolver(lrn, cfg, verbose=False)
    result = solver.run()
    assert result["train"].value("nex") == 1200
    assert result["val"].value("nex") == 400
    assert result["val"].mean("auc") > 0.85
    assert os.path.exists(str(tmp_path / "model/out.npz"))


def test_solver_model_roundtrip(data_dir, tmp_path):
    cfg = _cfg(data_dir, tmp_path)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    MinibatchSolver(lrn, cfg, verbose=False).run()
    val1 = MinibatchSolver(lrn, cfg, verbose=False).iterate(
        cfg.val_data, WorkType.VAL)

    # fresh learner, load saved model on a DIFFERENT mesh shape
    cfg2 = _cfg(data_dir, tmp_path, model_in=str(tmp_path / "model/out"),
                max_data_pass=0)
    lrn2 = LinearLearner(cfg2, make_mesh(4, 2))
    MinibatchSolver(lrn2, cfg2, verbose=False).run()
    val2 = MinibatchSolver(lrn2, cfg2, verbose=False).iterate(
        cfg.val_data, WorkType.VAL)
    np.testing.assert_allclose(val1.mean("logloss"), val2.mean("logloss"),
                               rtol=1e-5)


def test_solver_predict_out(data_dir, tmp_path):
    cfg = _cfg(data_dir, tmp_path, predict_out=str(tmp_path / "pred/out"),
               max_data_pass=1)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    solver = MinibatchSolver(lrn, cfg, verbose=False)
    solver.run()
    # one file per part: 1 val file x 2 parts
    files = sorted(os.listdir(tmp_path / "pred"))
    assert len(files) == 2
    n = sum(len(open(tmp_path / "pred" / f).read().splitlines())
            for f in files)
    assert n == 400


def test_solver_early_stop(data_dir, tmp_path):
    cfg = _cfg(data_dir, tmp_path, max_data_pass=10)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    solver = MinibatchSolver(lrn, cfg, verbose=False)
    calls = []

    def stop(prog, dp, key):
        calls.append(dp)
        return dp >= 1  # stop after 2nd pass

    solver.stop_hook = stop
    solver.run()
    assert calls == [0, 1]


def test_checkpoint_iter_naming(data_dir, tmp_path):
    cfg = _cfg(data_dir, tmp_path, max_data_pass=4, save_iter=2)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    MinibatchSolver(lrn, cfg, verbose=False).run()
    names = sorted(os.listdir(tmp_path / "model"))
    # intermediate save at pass 2 (iter-1) + final; single shard writes
    # the plain <base>.npz form
    assert "out_iter-1.npz" in names
    assert "out.npz" in names


def test_checkpoint_reshard_removes_stale_parts(data_dir, tmp_path):
    """Saving with fewer shards must remove the old extra part files so a
    later load doesn't concatenate mixed generations."""
    cfg = _cfg(data_dir, tmp_path, max_data_pass=1)
    l2 = LinearLearner(cfg, make_mesh(4, 2))  # 2 model shards
    MinibatchSolver(l2, cfg, verbose=False).run()
    assert os.path.exists(str(tmp_path / "model/out_part-1.npz"))
    l1 = LinearLearner(cfg, make_mesh(1, 1))  # 1 shard, same base
    MinibatchSolver(l1, cfg, verbose=False).run()
    assert not os.path.exists(str(tmp_path / "model/out_part-1.npz"))
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    ckpt.load_model(lrn.store, str(tmp_path / "model/out"))  # no shape error


def test_solver_step_failure_no_thread_leak(data_dir, tmp_path):
    """A failing train step must not park loader threads forever."""
    import threading

    cfg = _cfg(data_dir, tmp_path, model_out=None)
    lrn = LinearLearner(cfg, make_mesh(1, 1))

    class Boom(RuntimeError):
        pass

    def bad_step(blk):
        raise Boom()

    lrn.train_batch = bad_step
    before = threading.active_count()
    solver = MinibatchSolver(lrn, cfg, verbose=False)
    with pytest.raises(Boom):
        solver.run()
    deadline = 50
    while threading.active_count() > before and deadline:
        time.sleep(0.1)
        deadline -= 1
    assert threading.active_count() <= before


def test_a_loader_finishes_one_transfer_before_it_stages_the_next(
        data_dir, tmp_path):
    """Staging returns before the bytes are on the device, and transfers
    under way share the link: a loader that stages batch after batch (a
    pass's start, from the pack cache) would hold back the batch the
    train thread needs first. So a loader waits for the batch it staged
    last before it stages another (PR 37); the first it stages waits for
    nothing, and every batch still reaches its step."""
    import threading

    cfg = _cfg(data_dir, tmp_path, model_out=None, max_data_pass=1,
               val_data=None)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    real, lock, log = lrn.stage_batch, threading.Lock(), []

    class Staged(tuple):
        """A staged batch that says when it is waited for: no pytree
        node, so a leaf to `jax.block_until_ready`."""

        def block_until_ready(self):
            with lock:
                log.append(("waited", threading.get_ident(), id(self)))
            return self

    kept = []

    def stage(b, train=True):
        if isinstance(b, Staged):      # train_batch: staged already
            return real(tuple(b), train)
        out = Staged(real(b, train))
        kept.append(out)               # ids stay distinct
        with lock:
            log.append(("staged", threading.get_ident(), id(out)))
        return out

    lrn.stage_batch = stage
    solver = MinibatchSolver(lrn, cfg, verbose=False)
    assert solver.run()["train"].value("nex") == 1200
    by_thread = {}
    for what, thread, batch in log:
        by_thread.setdefault(thread, []).append((what, batch))
    assert sum(len(v) for v in by_thread.values()) >= 2 * len(kept) - len(
        by_thread)
    for events in by_thread.values():
        staged = [b for w, b in events if w == "staged"]
        assert events[0] == ("staged", staged[0])
        # between two stagings: a wait for the first of them
        for a, b in zip(staged, staged[1:]):
            i, j = events.index(("staged", a)), events.index(("staged", b))
            assert ("waited", a) in events[i + 1:j], events


def test_predict_missing_data_raises(data_dir, tmp_path):
    cfg = _cfg(data_dir, tmp_path)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    solver = MinibatchSolver(lrn, cfg, verbose=False)
    with pytest.raises(FileNotFoundError):
        solver.predict(r"/nonexistent/x.*", str(tmp_path / "p/out"))


def test_checkpoint_missing_raises(tmp_path):
    cfg = LinearConfig(num_buckets=64)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    with pytest.raises(FileNotFoundError):
        ckpt.load_model(lrn.store, str(tmp_path / "nope"))


def test_perf_accounting_and_pass_summary(tmp_path, capsys):
    """The solver logs FinishMinibatch-style pass summaries (avg step
    time + io/comm overhead share, reference minibatch_solver.h:246-275);
    its per-batch accounting is the train.stage.* histograms alone — the
    pass loop calls `Perf` no more — and `Perf` classifies the PS plane's
    op timings difacto-style (async_sgd.h:108-127)."""
    from wormhole_tpu.models.linear import LinearConfig, LinearLearner
    from wormhole_tpu.obs.metrics import REGISTRY
    from wormhole_tpu.solver.minibatch_solver import MinibatchSolver
    from wormhole_tpu.utils.perf import Perf

    p = tmp_path / "d.libsvm"
    p.write_text(synth_libsvm_text(n_rows=600, n_feat=100, nnz_per_row=8,
                                   seed=3))
    cfg = LinearConfig(train_data=str(p).replace(".libsvm", r"\.libsvm"),
                       minibatch=128, num_buckets=1 << 10, nnz_per_row=16,
                       max_data_pass=1)
    stages = {k: REGISTRY.histogram(f"train.stage.{k}_s")
              for k in ("load", "pack", "h2d", "step", "metrics")}
    before = {k: (h.count, h.sum) for k, h in stages.items()}
    solver = MinibatchSolver(LinearLearner(cfg), cfg, verbose=True)
    solver.run()
    out = capsys.readouterr().out
    assert "io/comm overhead" in out and "ms/step" in out
    n = int(re.search(r"train pass 0: (\d+) minibatches", out).group(1))
    assert n >= 5                                   # 600 rows / 128
    for k, h in stages.items():
        assert h.count - before[k][0] == n, k
    assert stages["step"].sum > before["step"][1]
    assert solver.perf.snapshot() == ({}, {})

    # Perf unit behavior: periodic row logging
    rows = []
    pf = Perf(log=rows.append, log_every=4)
    for _ in range(8):
        pf.add("op_a", 0.001)
    assert len(rows) == 2 and "op_a" in rows[0]


def test_profile_trace_env(tmp_path, monkeypatch):
    """WORMHOLE_PROFILE_DIR wraps the run in a JAX profiler trace."""
    import os

    from wormhole_tpu.obs.trace import maybe_trace

    out = tmp_path / "trace"
    monkeypatch.setenv("WORMHOLE_PROFILE_DIR", str(out))
    import jax.numpy as jnp
    with maybe_trace():
        float(jnp.sum(jnp.arange(8.0)))
    files = [os.path.join(r, f) for r, _, fs in os.walk(out) for f in fs]
    assert files, "no profiler output written"


def test_training_spans_in_the_device_profile(tmp_path, monkeypatch):
    """A solver run under WORMHOLE_PROFILE_DIR: the nine spans of the
    training path lie in the profile's host plane, each on the thread
    that does the work, and the spans of one batch share (part, i)."""
    p = tmp_path / "d.libsvm"
    p.write_text(synth_libsvm_text(n_rows=640, n_feat=100, nnz_per_row=8,
                                   seed=5))
    cfg = LinearConfig(train_data=str(p).replace(".libsvm", r"\.libsvm"),
                       minibatch=64, num_buckets=1 << 10, nnz_per_row=16,
                       max_data_pass=1, num_parts_per_file=2)
    solver = MinibatchSolver(LinearLearner(cfg), cfg, num_loaders=2,
                             verbose=False)
    monkeypatch.setenv("WORMHOLE_PROFILE_DIR", str(tmp_path / "prof"))
    prog = solver.run()["train"]
    spans = profiled_spans(tmp_path / "prof")
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    steps = by["solver.train_step"]
    n = len(steps)
    assert n >= 10 and prog.value("nex") == 640
    for name in ("loader.pack", "loader.h2d", "step.dispatch", "step.fetch",
                 "solver.merge"):
        assert len(by[name]) == n, name
    assert len(by["solver.queue_wait"]) >= n      # the end markers too
    # read + parse work in chunks, not batches: both parts were read and
    # every row came out of a parse span
    assert {s["args"]["part"] for s in by["data.parse"]} == {0, 1}
    assert sum(s["args"]["rows"] for s in by["data.parse"]) == 640
    assert sum(s["args"]["bytes"] for s in by["data.read"]
               if "bytes" in s["args"]) == len(p.read_text())
    for s in by["data.read"] + by["data.parse"] + by["loader.pack"]:
        assert 0 <= s["args"]["cpu_us"] <= (s["end"] - s["start"]) / 1e3 + 1e3

    # threads: one train thread, loader threads, parser threads, disjoint
    (train,) = {s["thread"] for s in by["solver.train_pass"]}
    for name in ("solver.queue_wait", "solver.train_step", "step.dispatch",
                 "step.fetch", "solver.merge"):
        assert {s["thread"] for s in by[name]} == {train}, name
    loaders = {s["thread"] for s in by["loader.pack"] + by["loader.h2d"]}
    parsers_ = {s["thread"] for s in by["data.read"] + by["data.parse"]}
    assert train not in loaders | parsers_ and not loaders & parsers_

    # a step holds its dispatch, then its fetch, and nothing overlaps
    for st, d, f in zip(steps, by["step.dispatch"], by["step.fetch"]):
        assert st["start"] <= d["start"] <= d["end"] <= f["start"]
        assert f["end"] <= st["end"]
        assert d["args"]["kind"] in ("xla", "coo", "tcoo", "mcoo")
    # (part, i) joins a step to the one pack and the one h2d of its batch
    packs = {(s["args"]["part"], s["args"]["i"]): s
             for s in by["loader.pack"]}
    h2ds = {(s["args"]["part"], s["args"]["i"]) for s in by["loader.h2d"]}
    keys = [(s["args"]["part"], s["args"]["i"]) for s in steps]
    assert len(set(keys)) == n and set(keys) == set(packs) == h2ds
    for key, st in zip(keys, steps):
        assert packs[key]["end"] <= st["start"]
        assert packs[key]["args"]["rows"] <= 64
