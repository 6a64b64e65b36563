"""Solver harness tests: workload pool (straggler/failure re-assignment
with fake workloads, SURVEY §4), full solver loop, checkpoint/resume,
predict output."""

import gc
import os
import random
import re
import threading
import time
import weakref

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


_ROLES = (("solver.", "train"), ("step.", "train"), ("loader.", "loader"),
          ("data.", "parser"))


def _role(name):
    return next(r for prefix, r in _ROLES if name.startswith(prefix))


def _one_role_at_a_time(spans):
    """A host line of the profile is keyed by the OS thread's id, and a
    thread that ended hands its id on: one line may hold a parser's spans
    and later a loader's. What holds is that no line carries two roles
    at the same time. Returns the one line the train thread's spans lie
    on, which holds no other role's at any time."""
    lines = {}
    for s in spans:
        lines.setdefault(s["thread"], []).append(s)
    for evs in lines.values():
        open_until = {}     # role -> the latest end seen so far
        for s in sorted(evs, key=lambda s: s["start"]):
            role = _role(s["name"])
            for other, end in open_until.items():
                assert other == role or end <= s["start"], (
                    f"{s['name']} opens on a line where a {other} span "
                    "is still open")
            open_until[role] = max(open_until.get(role, 0), s["end"])
    (train,) = {s["thread"] for s in spans if s["name"] == "solver.train_pass"}
    assert {_role(s["name"]) for s in lines[train]} == {"train"}
    return train


def _inside(outer, spans):
    return [s for s in spans
            if outer["start"] <= s["start"] and s["end"] <= outer["end"]]


def _loaders_of(spans):
    """The `loader.*` spans of a run, a list a loader thread (a line: the
    run's loaders live side by side from its first pass to its last, so
    they hold different ids), each in order."""
    out = {}
    for s in spans:
        if s["name"].startswith("loader."):
            out.setdefault(s["thread"], []).append(s)
    return list(out.values())


def _check_pass_turn(passes, by, spans):
    """The turn's spans' nesting and order, pass by pass:
    `solver.pass_start` holds `solver.nnz` and the pass's first queue wait
    and ends before the first step; `solver.pass_end` follows the last;
    `first=1` and `end=1` once a pass each (its end marker); `ahead` is 0
    in a run's first pass; a loader waits for its last transfer before
    every staging but its first of the run."""
    assert len(by["solver.pass_start"]) == len(passes) == len(
        by["solver.pass_end"]) == len(by["solver.nnz"])
    for dp, (p, head, tail) in enumerate(zip(
            passes, by["solver.pass_start"], by["solver.pass_end"])):
        assert p["args"]["data_pass"] == dp
        loaders, ahead = head["args"]["loaders"], head["args"]["ahead"]
        assert head["args"] == {"mode": "train", "data_pass": dp,
                                "loaders": loaders, "ahead": ahead}
        assert ahead >= 0 and (dp or ahead == 0)
        assert p["start"] <= head["start"] and tail["end"] <= p["end"]
        steps = _inside(p, by["solver.train_step"])
        assert tail["args"] == {"mode": "train", "data_pass": dp,
                                "steps": len(steps)}
        assert head["end"] <= steps[0]["start"]
        assert steps[-1]["end"] <= tail["start"]
        (nnz,) = _inside(head, by["solver.nnz"])
        waits = _inside(p, by["solver.queue_wait"])
        (first,) = [w for w in waits if w["args"].get("first")]
        assert first is waits[0] and nnz["end"] <= first["start"]
        # the start holds the one wait that brought the first batch
        assert _inside(head, waits) == [first]
        (end,) = [w for w in waits if w["args"].get("end")]
        assert end is waits[-1]
        assert len(waits) == len(steps) + 1
    # the loaders are the run's: a loader's last batch survives the turn,
    # so it waits before every staging but its very first
    assert 1 <= len(_loaders_of(spans)) <= max(
        h["args"]["loaders"] for h in by["solver.pass_start"])
    for mine in _loaders_of(spans):
        h2ds = [s for s in mine if s["name"] == "loader.h2d"]
        wts = [s for s in mine if s["name"] == "loader.h2d_wait"]
        assert len(wts) == len(h2ds) - 1
        for prev, w, h in zip(h2ds, wts, h2ds[1:]):
            assert prev["end"] <= w["start"] <= w["end"] <= h["start"]
            assert (w["args"]["part"], w["args"]["i"]) == (
                h["args"]["part"], h["args"]["i"])
        for h in h2ds:
            assert h["args"]["bytes"] > 0


def test_training_spans_in_the_device_profile(tmp_path, monkeypatch):
    """A solver run under WORMHOLE_PROFILE_DIR: the spans of the training
    path lie in the profile's host plane, each on the thread that does
    the work, nested and ordered as the code runs, and the spans of one
    batch share (part, i)."""
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
    for name in ("loader.pack", "loader.h2d", "loader.put_wait",
                 "step.dispatch", "step.fetch", "solver.merge"):
        assert len(by[name]) == n, name
    assert len(by["solver.queue_wait"]) == n + 1      # the end marker too
    # read + parse work in chunks, not batches: both parts were read and
    # every row came out of a parse span
    assert {s["args"]["part"] for s in by["data.parse"]} == {0, 1}
    assert sum(s["args"]["rows"] for s in by["data.parse"]) == 640
    assert sum(s["args"]["bytes"] for s in by["data.read"]
               if "bytes" in s["args"]) == len(p.read_text())
    for s in by["data.read"] + by["data.parse"] + by["loader.pack"]:
        assert 0 <= s["args"]["cpu_us"] <= (s["end"] - s["start"]) / 1e3 + 1e3

    # threads: one train thread, and no line holds two roles at once
    train = _one_role_at_a_time(spans)
    for name in ("solver.queue_wait", "solver.train_step", "step.dispatch",
                 "step.fetch", "solver.merge", "solver.pass_start",
                 "solver.nnz", "solver.pass_end"):
        assert {s["thread"] for s in by[name]} == {train}, name
    _check_pass_turn(by["solver.train_pass"], by, spans)
    assert by["solver.pass_start"][0]["args"]["loaders"] == 2

    # a step holds its dispatch, then its fetch, and nothing overlaps
    for st, d, f in zip(steps, by["step.dispatch"], by["step.fetch"]):
        assert st["start"] <= d["start"] <= d["end"] <= f["start"]
        assert f["end"] <= st["end"]
        assert d["args"]["kind"] in ("xla", "coo", "tcoo", "mcoo")
    # (part, i) joins a step to the one source, pack, h2d and put of its
    # batch, which follow one another on one loader's line
    def keyed(name):
        out = {(s["args"]["part"], s["args"]["i"]): s for s in by[name]
               if not s["args"].get("end")}
        assert len(out) == n, name
        return out

    sources, packs, h2ds, puts = (keyed(name) for name in (
        "loader.source", "loader.pack", "loader.h2d", "loader.put_wait"))
    keys = [(s["args"]["part"], s["args"]["i"]) for s in steps]
    assert len(set(keys)) == n
    assert set(keys) == set(sources) == set(packs) == set(h2ds) == set(puts)
    for key, st in zip(keys, steps):
        cycle = [sources[key], packs[key], h2ds[key], puts[key]]
        assert len({s["thread"] for s in cycle}) == 1
        for a, b in zip(cycle, cycle[1:]):
            assert a["end"] <= b["start"], (a["name"], b["name"])
        assert puts[key]["start"] <= st["start"]     # handed over
        assert packs[key]["args"]["rows"] <= 64
        assert sources[key]["args"]["cached"] == 0
        assert 0 <= puts[key]["args"]["depth"] <= solver.max_queued
    # a part's source says once that it is over
    over = [s for s in by["loader.source"] if s["args"].get("end")]
    assert sorted(s["args"]["part"] for s in over) == [0, 1]


_LETTER = {"solver.pass_start": "S", "solver.train_step": "T",
           "solver.merge": "M", "solver.queue_wait": "W",
           "solver.pass_end": "E", "solver.checkpoint": "C",
           "solver.flush": "F"}


def test_the_train_thread_is_under_a_named_span_from_first_step_to_last(
        tmp_path, monkeypatch):
    """Three passes with the pack cache on (the first packs and fills,
    the next two replay), a barrier and a save after each: every call the
    train thread makes into the learner, the pool, the cache, the PS
    plane's barrier or the checkpoint is made under the span that names
    it, and the spans below the pass
    follow one another in the loop's own order with nothing between them
    that is not one of them. Asserted by order and by the span open at
    the call, never by a duration. The three new histograms count the
    batches."""
    from wormhole_tpu.data import pack_cache
    from wormhole_tpu.obs import trace as obs_trace
    from wormhole_tpu.obs.metrics import REGISTRY
    from wormhole_tpu.solver import minibatch_solver as ms

    p = tmp_path / "d.libsvm"
    p.write_text(synth_libsvm_text(n_rows=640, n_feat=100, nnz_per_row=8,
                                   seed=6))
    monkeypatch.setenv("WH_PACK_CACHE", "1")
    cfg = LinearConfig(train_data=str(p).replace(".libsvm", r"\.libsvm"),
                       minibatch=64, num_buckets=1 << 10, nnz_per_row=16,
                       max_data_pass=3, num_parts_per_file=2,
                       model_out=str(tmp_path / "model/out"), save_iter=1)
    lrn = LinearLearner(cfg)
    solver = MinibatchSolver(lrn, cfg, num_loaders=2, verbose=False)

    calls = []      # (what was called, the innermost span open there)
    train_thread = threading.get_ident()

    def told(what, fn):
        def wrapper(*a, **kw):
            sp = getattr(obs_trace._TLS, "span", None)
            where = sp.name if sp is not None else None
            if threading.get_ident() != train_thread and what in (
                    "pool.add", "cache.stats"):
                where = "a loader"
            calls.append((what, where))
            return fn(*a, **kw)
        return wrapper

    lrn.nnz = told("nnz", lrn.nnz)
    lrn.train_batch = told("train_batch", lrn.train_batch)
    monkeypatch.setattr(ms.WorkloadPool, "add",
                        told("pool.add", ms.WorkloadPool.add))
    monkeypatch.setattr(pack_cache.PackCache, "stats",
                        told("cache.stats", pack_cache.PackCache.stats))
    monkeypatch.setattr(pack_cache.PackCache, "lookup",
                        told("cache.lookup", pack_cache.PackCache.lookup))
    monkeypatch.setattr(ms.ckpt, "save_model",
                        told("save_model", ms.ckpt.save_model))
    monkeypatch.setattr(
        ms.LoaderController, "record_pass",
        told("controller", ms.LoaderController.record_pass))
    solver.controller = ms.LoaderController(2)
    solver.sync_flush = told("sync_flush", lambda: None)   # a PS barrier

    new = {k: REGISTRY.histogram(f"train.stage.{k}_s")
           for k in ("source", "h2d_wait", "put", "h2d", "load")}
    before = {k: h.count for k, h in new.items()}
    monkeypatch.setenv("WORMHOLE_PROFILE_DIR", str(tmp_path / "prof"))
    solver.run()
    spans = profiled_spans(tmp_path / "prof")
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    n = len(by["solver.train_step"])
    per = n // 3                 # a pass's batches
    assert per >= 10 and n == 3 * per
    for k, h in new.items():
        assert h.count - before[k] == n, k

    # each call lies under the span that names it
    under = {}
    for what, span in calls:
        under.setdefault(what, set()).add(span)
    assert under == {
        "nnz": {"solver.nnz"},
        # the run's first pool by the train thread, the next passes' by
        # the loader that turned the pass before
        "pool.add": {"solver.pass_start", "a loader"},
        "train_batch": {"solver.train_step"},
        # where the cache stood at a pass's end, from the loader that
        # turned the pass: the next pass's lookups are not in it
        "cache.stats": {"a loader"},
        "controller": {"solver.pass_end"},
        "save_model": {"solver.checkpoint"},
        "sync_flush": {"solver.flush"},
        # a loader's fetch of a batch; the part's count is looked up
        # outside it, on the loader's thread too
        "cache.lookup": {"loader.source", None}}
    # a pass, the barrier after it, the barrier before the save, the save
    assert [w for w, _ in calls
            if w in ("nnz", "sync_flush", "save_model")] == [
        "nnz", "sync_flush", "sync_flush", "save_model"] * 3
    assert [where for w, where in calls if w == "pool.add"] == [
        "solver.pass_start", "a loader", "a loader"]

    # the train thread's line, one level below the pass: the loop's order
    train = _one_role_at_a_time(spans)
    passes = by["solver.train_pass"]
    assert len(passes) == 3
    _check_pass_turn(passes, by, spans)
    # the pool's size is the controller's, pass by pass
    assert [h["args"]["loaders"] for h in by["solver.pass_start"]] == [2] + [
        d["to"] for d in solver.controller.decisions[:2]]
    nested = ("step.dispatch", "step.fetch", "solver.nnz",
              "solver.train_pass")
    level = [s for s in spans if s["thread"] == train
             and s["name"] not in nested]
    heads = by["solver.pass_start"]
    level = [s for s in level if not any(
        h is not s and h["start"] <= s["start"] and s["end"] <= h["end"]
        for h in heads)]                # the waits inside a pass's start
    for a, b in zip(level, level[1:]):
        assert a["end"] <= b["start"], (a["name"], b["name"])
    word = "".join(_LETTER[s["name"]] for s in level)
    assert re.fullmatch(r"(STM(W+TM)*W+EFFC)+", word), word
    assert word.count("T") == n and word.count("C") == 3
    first, last = by["solver.train_step"][0], by["solver.train_step"][-1]
    assert level[0]["end"] <= first["start"] and level[-1]["start"] >= (
        last["end"])

    # the first pass packed what the other two were handed from the
    # cache. The loaders read ahead of the train thread, so a pass's
    # fetches are told by their order, not by the pass's span: the feed
    # reads one pass at a time
    src = [s for s in by["loader.source"] if not s["args"].get("end")]
    assert len(src) == n and len(by["loader.pack"]) == per
    for dp in range(3):
        mine = src[dp * per:(dp + 1) * per]
        assert {s["args"]["cached"] for s in mine} == {0 if dp == 0 else 1}
        assert {s["args"].get("tier") for s in mine} == {
            None if dp == 0 else "mem"}
        assert sorted((s["args"]["part"], s["args"]["i"]) for s in mine) == (
            sorted((s["args"]["part"], s["args"]["i"]) for s in src[:per]))
    # and what was staged ahead of a pass is what its start says
    for dp, head in enumerate(by["solver.pass_start"]):
        mine = by["loader.h2d"][dp * per:(dp + 1) * per]
        assert head["args"]["ahead"] <= sum(
            h["end"] <= head["start"] for h in mine)


# ------------------------------------------------- the run owns the feed
def _within(seconds, fn, *args, **kw):
    """`fn`'s result or its exception, and a failure where it is not over
    in `seconds`: a feed that hangs fails its test, not the suite."""
    box = {}

    def run():
        try:
            box["out"] = fn(*args, **kw)
        except BaseException as e:      # handed on below
            box["err"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(seconds)
    assert not t.is_alive(), f"not over after {seconds}s"
    if "err" in box:
        raise box["err"]
    return box["out"]


class _Blk:
    """What the fake part iterator yields: the seed says the pass."""

    size = 4

    def __init__(self, seed, name, part, i):
        self.tag = (seed // 7919, name, part, i)


class _Staged:
    def __init__(self, packed, train):
        self.packed, self.train = packed, train


class _Recorder:
    """A learner with no tables that records what the solver asks of it:
    `events` in the train thread's order, `packs` and `staged` as the
    loaders made them, and how many staged batches were alive at once
    (staged and not yet through their step)."""

    placement = "[recorder]"
    store = None

    def __init__(self, step_s=0.0, pack_s=None, boom_at=None):
        self.step_s, self.pack_s, self.boom_at = step_s, pack_s or {}, boom_at
        self.lock = threading.Lock()
        self.events, self.packs, self.by_thread = [], [], {}
        self.alive = self.peak = self.staged = 0
        self.kept = weakref.WeakSet()
        self.on_step = None

    def on_pass_start(self):
        self.events.append(("start",))

    def nnz(self):
        self.events.append(("nnz",))
        return 0.0

    def pack_cache_token(self, train=True):
        return ("recorder", 1)

    def prepare_batch(self, blk, train=True):
        time.sleep(self.pack_s.get(blk.tag[1:3], 0.0))
        with self.lock:
            self.packs.append(blk.tag)
        return ("packed", *blk.tag, np.zeros(4, np.float32))

    def stage_batch(self, b, train=True):
        out = _Staged(b, train)
        with self.lock:
            self.kept.add(out)
            self.staged += 1
            self.alive += 1
            self.peak = max(self.peak, self.alive)
            self.by_thread.setdefault(threading.get_ident(), []).append(b[1:])
        return out

    def _step(self, what, b):
        assert isinstance(b, _Staged) and b.train == (what == "train")
        self.events.append((what, *b.packed[1:5]))
        if self.on_step is not None:
            self.on_step(what, b)
        if self.boom_at == len(self.events):
            raise _Boom()
        time.sleep(self.step_s)
        with self.lock:
            self.alive -= 1
        return {"nex": 4.0, "logloss": 1.0}

    def train_batch(self, b):
        return self._step("train", b)

    def eval_batch(self, b):
        return self._step("eval", b)


class _Boom(RuntimeError):
    pass


_PARTS, _PER_PART = 6, 2       # train parts (files) and batches a part
_PER_PASS = _PARTS * _PER_PART


def _fed(tmp_path, monkeypatch, lrn, cache=True, loaders=2, max_queued=3,
         bad=None, **kw):
    """A solver over `lrn` whose parts come from a fake iterator (no file
    is parsed): `_PARTS` train files of `_PER_PART` batches and one val
    file; `bad` = (data_pass, file, i) makes that batch's source raise."""
    from wormhole_tpu.solver import minibatch_solver as ms

    for k in range(_PARTS):
        (tmp_path / f"train-{k}").write_text("")
    (tmp_path / "val-0").write_text("")

    def parts(filename, part, num_parts, fmt, minibatch_size, shuf_buf,
              neg_sampling, seed):
        for i in range(_PER_PART):
            blk = _Blk(seed, os.path.basename(filename), part, i)
            if bad == (blk.tag[0], blk.tag[1], i):
                raise _Boom()
            yield blk

    monkeypatch.setattr(ms, "MinibatchIter", parts)
    if cache:
        monkeypatch.setenv("WH_PACK_CACHE", "1")
    else:
        monkeypatch.delenv("WH_PACK_CACHE", raising=False)
    cfg = LinearConfig(**{**dict(
        train_data=str(tmp_path / r"train-\d+"), val_data=None,
        minibatch=4, num_buckets=64, max_data_pass=3, max_queued=max_queued,
        num_parts_per_file=1), **kw})
    made = []

    class Kept(ms._Feed):
        def __init__(self, *a):
            super().__init__(*a)
            made.append(self)

    monkeypatch.setattr(ms, "_Feed", Kept)
    solver = MinibatchSolver(lrn, cfg, num_loaders=loaders, verbose=False)
    solver.feeds = made     # every feed the solver opens, for the test
    return solver


def _all_joined(solver, lrn, before):
    """Every feed closed: its loaders ended, its queue empty, and no
    staged batch left anywhere (what ran ahead was dropped)."""
    assert solver._feed is None and solver.feeds
    for feed in solver.feeds:
        assert not any(t.is_alive() for t in feed.threads)
        assert feed.q.empty() and feed.live == 0
    assert threading.active_count() <= before
    gc.collect()
    assert len(lrn.kept) == 0


@pytest.mark.parametrize("cache", [False, True], ids=["packing", "replay"])
def test_a_batch_is_stepped_in_its_own_pass_only(tmp_path, monkeypatch,
                                                 cache):
    """(a) Three passes with a val pass, a barrier, a save and the stop
    hook between: the train thread's calls come in `_run_passes`' order
    and every pass steps its own batches, all of them, once, though the
    loaders have staged the next pass's first ones by then. Packing, a
    batch's seed names its pass; replayed from the cache the same packed
    batch serves every pass, and a pass is told by its count."""
    from wormhole_tpu.solver import minibatch_solver as ms

    lrn = _Recorder(step_s=0.002)
    solver = _fed(tmp_path, monkeypatch, lrn, cache=cache,
                  val_data=str(tmp_path / r"val-\d+"),
                  model_out=str(tmp_path / "m/out"), save_iter=1)
    solver.sync_flush = lambda: lrn.events.append(("flush",))
    monkeypatch.setattr(ms.ckpt, "save_model",
                        lambda *a: lrn.events.append(("save",)))
    solver.stop_hook = lambda prog, dp, key: lrn.events.append(
        ("stop?", dp, key))
    _within(60, solver.run)
    want, at = [], 0
    for dp in range(3):
        want += ["start", "nnz"] + ["train"] * _PER_PASS + ["flush"]
        want += ["start", "nnz"] + ["eval"] * _PER_PART
        want += ["flush", "save"] * (dp < 2) + ["stop?"]
    want += ["flush", "save"]
    assert [e[0] for e in lrn.events] == want
    every = sorted((f"train-{k}", 0, i) for k in range(_PARTS)
                   for i in range(_PER_PART))
    for dp in range(3):
        at = want.index("train", at)
        mine = lrn.events[at:at + _PER_PASS]
        at += _PER_PASS
        assert sorted(e[2:] for e in mine) == every
        # the pass that packed them is in a batch's tag
        assert {e[1] for e in mine} == {0 if cache else dp}
    assert len(lrn.packs) == (1 if cache else 3) * (_PER_PASS + _PER_PART)


@pytest.fixture
def counted(monkeypatch):
    """`solver.pass.turns` and `solver.pass.batches_ahead` as they stood
    at every `on_pass_start` (the solver counts a turn before it calls
    the hook), less where they stood when the test began."""
    from wormhole_tpu.obs.metrics import REGISTRY

    turns = REGISTRY.counter("solver.pass.turns")
    ahead = REGISTRY.counter("solver.pass.batches_ahead")
    base = turns.value(), ahead.value()
    seen = []
    real = _Recorder.on_pass_start

    def hook(self):
        seen.append((turns.value() - base[0], ahead.value() - base[1]))
        real(self)

    monkeypatch.setattr(_Recorder, "on_pass_start", hook)
    return seen


@pytest.mark.parametrize("loaders,max_queued", [(1, 3), (2, 3), (4, 2)])
def test_staged_batches_alive_stay_under_the_pass_s_own_bound(
        tmp_path, monkeypatch, counted, loaders, max_queued):
    """(b) and (c): across two turns of a replayed run no more staged
    batches are alive than inside a pass (the queue's `max_queued`, one
    in each loader's hands, one in the step); every turn found batches
    staged ahead; nothing was staged beyond the last pass."""
    lrn = _Recorder(step_s=0.003)
    solver = _fed(tmp_path, monkeypatch, lrn, loaders=loaders,
                  max_queued=max_queued)
    before = threading.active_count()
    _within(60, solver.run)
    assert max_queued <= lrn.peak <= max_queued + loaders + 1
    assert lrn.staged == 3 * _PER_PASS == sum(
        e[0] == "train" for e in lrn.events) and lrn.alive == 0
    # pass 0 is the run's first: no turn; passes 1 and 2 each found some
    assert [t for t, _ in counted] == [0, 1, 2]
    gains = [b - a for (_, a), (_, b) in zip(counted, counted[1:])]
    assert counted[0][1] == 0 and all(1 <= g <= max_queued + loaders
                                      for g in gains), counted
    _all_joined(solver, lrn, before)


def test_one_pass_and_iterate_alone_stage_nothing_ahead(
        tmp_path, monkeypatch, counted):
    """(c) A run of one pass has no turn, and `iterate()` called alone
    opens a feed of its own pass and closes it: nothing runs ahead."""
    lrn = _Recorder()
    solver = _fed(tmp_path, monkeypatch, lrn, max_data_pass=1)
    before = threading.active_count()
    _within(60, solver.run)
    for dp in (5, 6):
        prog = _within(60, solver.iterate, solver.cfg.train_data,
                       WorkType.TRAIN, dp)
        assert prog.value("nex") == 4.0 * _PER_PASS
    assert counted == [(0, 0)] * 3
    assert [f.turns for f in solver.feeds] == [1, 1, 1]
    assert lrn.staged == 3 * _PER_PASS
    _all_joined(solver, lrn, before)


@pytest.mark.parametrize("loaders", [1, 2, 4])
def test_a_pass_that_fills_the_cache_packs_every_part_once(
        tmp_path, monkeypatch, loaders):
    """(d) `iter_part_cached` writes a part's count entry when the part
    ends: a loader let into part P of the next pass while P of this one
    is still being packed would miss and pack it again. One part here
    packs slowly, so every other loader is done long before it."""
    lrn = _Recorder(pack_s={("train-3", 0): 0.15})
    solver = _fed(tmp_path, monkeypatch, lrn, loaders=loaders)
    _within(60, solver.run)
    assert sorted(lrn.packs) == sorted(
        (0, f"train-{k}", 0, i) for k in range(_PARTS)
        for i in range(_PER_PART))
    stats = solver.pack_cache.stats()
    # a filling pass misses once a part (its count entry), no more
    assert stats["misses"] == _PARTS
    assert stats["hits"] == 2 * (_PARTS + _PER_PASS)


@pytest.mark.parametrize("how", ["stop_hook", "step", "loader"])
def test_a_run_that_ends_early_joins_its_loaders_and_drops_what_ran_ahead(
        tmp_path, monkeypatch, how):
    """(e) The stop hook after pass 0, an exception out of pass 1's third
    step, a loader's error in pass 1: each ends the run with every loader
    joined, the queue empty and no staged batch alive; the loader's error
    is raised in the pass it belongs to."""
    kw = {}
    if how == "step":
        # start, nnz, 12 steps; start, nnz, 3 steps
        kw["boom_at"] = 2 + _PER_PASS + 2 + 3
    lrn = _Recorder(step_s=0.002, **kw)
    solver = _fed(tmp_path, monkeypatch, lrn, max_data_pass=4,
                  bad=(1, "train-2", 1) if how == "loader" else None,
                  cache=how != "loader")
    before = threading.active_count()
    if how == "stop_hook":
        solver.stop_hook = lambda prog, dp, key: True
        _within(60, solver.run)
        done = 1
    else:
        with pytest.raises(_Boom):
            _within(60, solver.run)
        done = 2
    starts = [k for k, e in enumerate(lrn.events) if e[0] == "start"]
    assert len(starts) == done
    steps = [e for e in lrn.events[starts[0]:] if e[0] == "train"]
    assert len(steps[:_PER_PASS]) == _PER_PASS      # pass 0 whole
    if how == "loader":
        # pass 1 came as far as the loaders brought it
        assert {e[1] for e in steps[_PER_PASS:]} <= {1}
        assert len(steps) < 2 * _PER_PASS
    elif how == "step":
        assert len(steps) == _PER_PASS + 3
    # the loaders had run ahead, and what they staged is gone
    assert lrn.staged > len(steps) or how == "loader"
    _all_joined(solver, lrn, before)


def test_a_run_equals_its_passes_made_one_at_a_time(data_dir, tmp_path):
    """(f) Tables and progress of a three-pass run with a val pass equal,
    bit for bit, those of the same passes made by `iterate()` one at a
    time: with one loader the parts come in the order the pool's
    `random.choice` draws them, seeded alike."""
    def tables(lrn):
        return {k: np.asarray(v) for k, v in lrn.tables().items()}

    cfg = _cfg(data_dir, tmp_path, max_data_pass=3, model_out=None)
    a = LinearLearner(cfg, make_mesh(1, 1))
    random.seed(11)
    got = _within(120, MinibatchSolver(a, cfg, num_loaders=1,
                                       verbose=False).run)
    b = LinearLearner(cfg, make_mesh(1, 1))
    solver = MinibatchSolver(b, cfg, num_loaders=1, verbose=False)
    random.seed(11)
    for dp in range(3):
        tr = _within(60, solver.iterate, cfg.train_data, WorkType.TRAIN, dp)
        vl = _within(60, solver.iterate, cfg.val_data, WorkType.VAL, dp)
    assert got["train"].tot == tr.tot and got["val"].tot == vl.tot
    ta, tb = tables(a), tables(b)
    assert ta.keys() == tb.keys() and len(ta) >= 3
    for k in ta:
        assert np.array_equal(ta[k], tb[k]), k
    assert np.count_nonzero(ta["w"]) > 0


def test_the_controller_s_growth_and_shrink_reach_the_living_pool(
        tmp_path, monkeypatch):
    """(g) The controller decides at a pass's end, as before; the pool
    lives on, so a growth starts threads at the next pass's start and a
    shrink lets loaders retire, each at its next part."""
    from wormhole_tpu.obs.metrics import REGISTRY

    sizes = iter([4, 1, 1, 1])      # after passes 0, 1, 2, 3

    class Scripted:
        n, decisions = 2, []

        def record_pass(self, stall_s, wall_s, n_steps, queue_high_frac):
            new = next(sizes)
            self.decisions.append({"from": self.n, "to": new, "why": "told",
                                   "stall_frac": 0.0, "queue_high_frac": 0.0})
            self.n = new
            return new

    lrn = _Recorder(step_s=0.002)
    solver = _fed(tmp_path, monkeypatch, lrn, cache=False, max_data_pass=4)
    solver.controller = Scripted()
    seen = []       # at a pass's first step: (loaders wanted, gauge)

    def on_step(what, b):
        if sum(e[0] == "train" for e in lrn.events) % _PER_PASS == 1:
            (feed,) = solver.feeds
            with feed.turn:
                seen.append((feed.live - feed.retire, sum(
                    t.is_alive() for t in feed.threads),
                    REGISTRY.gauge("loader.pool_size").value()))

    lrn.on_step = on_step
    _within(60, solver.run)
    (feed,) = solver.feeds
    assert [w for w, _, _ in seen] == [2, 4, 1, 1]
    assert [g for _, _, g in seen] == [2.0, 4.0, 1.0, 1.0]
    # the growth made two threads more, none was made after it
    assert len(feed.threads) == 4 and seen[1][1] == 4
    # by the fourth pass three had retired, each at a part's end
    assert seen[3][1] == 1
    # and the grown pool worked: pass 1's batches came from over two
    # threads, though two loaders had read ahead into it
    pass1 = {t for t, tags in lrn.by_thread.items()
             if any(tag[0] == 1 for tag in tags)}
    assert len(pass1) >= 3


def test_a_run_reaches_its_passes_one_at_a_time(tmp_path, monkeypatch):
    """The benchmark's window run names a million passes and a stop ends
    it: the feed makes a pass when the one before it turns, not a million
    at its start (which cost the four-chip cell 1.9 s of set-up)."""
    lrn = _Recorder()
    solver = _fed(tmp_path, monkeypatch, lrn, max_data_pass=10 ** 6)
    solver.stop_hook = lambda prog, dp, key: dp >= 1
    before = threading.active_count()
    _within(60, solver.run)
    (feed,) = solver.feeds
    assert feed.turns == 2 and len(feed.open) <= 2
    # the passes after the ones reached were never made
    assert next(feed._rest)[2] <= 4
    _all_joined(solver, lrn, before)
