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


def _loaders_of(pass_span, spans):
    """The `loader.*` spans of one pass, a list a loader thread (a line
    within the pass: the pass's loaders live side by side from its start,
    so they hold different ids), each in order."""
    out = {}
    for s in _inside(pass_span, spans):
        if s["name"].startswith("loader."):
            out.setdefault(s["thread"], []).append(s)
    return list(out.values())


def _check_pass_turn(passes, by, spans):
    """The new spans' nesting and order, pass by pass: `solver.pass_start`
    holds `solver.nnz` and the pass's first queue wait and ends before the
    first step; `solver.pass_end` follows the last; `first=1` once a
    pass, `end=1` once a loader; a loader waits for its last transfer
    before every staging but its first."""
    assert len(by["solver.pass_start"]) == len(passes) == len(
        by["solver.pass_end"]) == len(by["solver.nnz"])
    for dp, (p, head, tail) in enumerate(zip(
            passes, by["solver.pass_start"], by["solver.pass_end"])):
        assert p["args"]["data_pass"] == dp
        loaders = head["args"]["loaders"]
        assert head["args"] == {"mode": "train", "data_pass": dp,
                                "loaders": loaders}
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
        assert first in _inside(head, waits)
        # the waits inside the start but the last ended in an end marker
        assert all(w["args"].get("end") for w in _inside(head, waits)[:-1])
        ends = [w for w in waits if w["args"].get("end")]
        assert len(ends) == loaders and ends[-1] is waits[-1]
        assert len(waits) == len(steps) + loaders
        assert 1 <= len(_loaders_of(p, spans)) <= loaders
        for mine in _loaders_of(p, spans):
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
    assert len(by["solver.queue_wait"]) == n + 2      # the end markers too
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

    def told(what, fn):
        def wrapper(*a, **kw):
            sp = getattr(obs_trace._TLS, "span", None)
            calls.append((what, sp.name if sp is not None else None))
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
        "nnz": {"solver.nnz"}, "pool.add": {"solver.pass_start"},
        "train_batch": {"solver.train_step"},
        "cache.stats": {"solver.pass_end"},
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

    # the first pass packed what the other two were handed from the cache
    for dp, p_ in enumerate(passes):
        src = [s for s in _inside(p_, by["loader.source"])
               if not s["args"].get("end")]
        assert len(src) == per
        assert {s["args"]["cached"] for s in src} == {0 if dp == 0 else 1}
        assert {s["args"].get("tier") for s in src} == {
            None if dp == 0 else "mem"}
        assert len(_inside(p_, by["loader.pack"])) == (per if dp == 0 else 0)
