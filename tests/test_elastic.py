"""Elastic worker membership: join/leave without restart, epoch-fenced
part completions, retire-and-drain, and ring rebuild on the BSP plane.

The in-process tests drive the real Scheduler, WorkloadPool, and
BspWorker machinery in one process. The slow tier runs the launcher for
real: a `--elastic` difacto job scripted through a 2->3->2 churn
(WH_ELASTIC_PLAN) must converge to logloss parity with the fixed-world
run — joins and retirements shift WHERE parts execute, never whether
their examples are counted exactly once.
"""

import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from tests.conftest import synth_libsvm_text
from wormhole_tpu.runtime.allreduce import BspWorker
from wormhole_tpu.runtime.tracker import (
    MembershipController,
    RemotePool,
    Scheduler,
    SchedulerClient,
)
from wormhole_tpu.solver.workload import WorkloadPool

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- WorkloadPool fence semantics -------------------------------------------

def _pool_with(files, mepoch_parts=1):
    pool = WorkloadPool()
    pool.add_files(files, mepoch_parts)
    return pool


def test_fence_rejects_dead_nodes_late_finish():
    """A node declared dead has its assignment reset (stamp cleared); the
    part sits unassigned, yet the dead node's late finish must NOT count
    — the double-apply hole the membership epoch closes."""
    pool = _pool_with(["a", "b"])
    pid, _ = pool.get("worker-0", mepoch=0)
    assert pool.reset("worker-0") == 1
    assert pool.finish(pid, node="worker-0", mepoch=0) is False
    assert pool.num_finished == 0
    # the re-execution by a live owner is what counts
    pid2, _ = pool.get("worker-1", mepoch=1)
    assert pool.finish(pid2, node="worker-1", mepoch=1) is True


def test_fence_accepts_straggler_twins_late_finish():
    """A straggler re-queue clears the owner but keeps the membership
    stamp: the slow owner's work is still the same work, so its late
    finish lands (and the twin's duplicate is dropped)."""
    pool = _pool_with(["a", "b"])
    pid, _ = pool.get("worker-0", mepoch=3)
    # age the assignment past the watchdog limit and give it the >= 10
    # finished samples it needs to act
    pool._durations.extend([0.001] * 10)
    with pool._lock:
        pool._parts[pid]["t_start"] = time.monotonic() - 60.0
    assert pool.remove_stragglers() == 1
    with pool._lock:
        assert pool._parts[pid]["node"] is None
        assert pool._parts[pid]["mepoch"] == 3
    assert pool.finish(pid, node="worker-0", mepoch=3) is True
    # the twin that picked up the re-queued copy double-finishes: dropped
    assert pool.finish(pid, node="worker-1", mepoch=3) is False
    assert pool.num_finished == 1


def test_fence_stale_epoch_twin_rejected():
    """A straggler-requeued part re-assigned AFTER a membership change
    carries the new stamp; the old owner's echo of the old stamp no
    longer matches and is fenced."""
    pool = _pool_with(["a"])
    pid, _ = pool.get("worker-0", mepoch=1)
    with pool._lock:  # straggler-style requeue: owner cleared, stamp kept
        pool._parts[pid].update(state=0, node=None)
    pid2, _ = pool.get("worker-1", mepoch=2)
    assert pid2 == pid
    assert pool.finish(pid, node="worker-0", mepoch=1) is False
    assert pool.finish(pid, node="worker-1", mepoch=2) is True


def test_fence_legacy_callers_unfenced():
    """In-process pools (no node/mepoch args) keep accept-any semantics."""
    pool = _pool_with(["a"])
    pid, _ = pool.get("worker-0")
    pool.reset("worker-0")
    pool.get("worker-1")
    assert pool.finish(pid) is True


def test_repin_is_idempotent():
    pool = _pool_with(["a", "b", "c", "d"])
    pool.assign_stable(["worker-0", "worker-1"])
    assert pool.repin(["worker-0", "worker-1"]) == 0
    moved = pool.repin(["worker-0", "worker-1", "worker-2"])
    assert moved > 0
    # same set again: pin follows part order, so nothing moves
    assert pool.repin(["worker-0", "worker-1", "worker-2"]) == 0
    # online-mode pools (no pins) are untouched
    online = _pool_with(["a", "b"])
    assert online.repin(["worker-0"]) == 0


# -- Scheduler membership ops ------------------------------------------------

@pytest.fixture
def sched(tmp_path):
    for i in range(2):
        (tmp_path / f"part-{i}.libsvm").write_text(
            synth_libsvm_text(64, seed=i))
    s = Scheduler("127.0.0.1", 0, node_timeout=30.0, straggler=False)
    s.serve()
    yield s, str(tmp_path / "part-.*")
    s.stop()


def _worker(uri, name):
    c = SchedulerClient(uri, name)
    c.register()
    return c, RemotePool(c, poll=0.02)


def test_join_bumps_membership_epoch_once(sched):
    s, _ = sched
    c, pool = _worker(s.uri, "worker-0")
    m0 = s.membership_epoch
    r = pool.join()
    assert r["mepoch"] == m0 + 1
    assert pool.mepoch == m0 + 1
    # a joiner retrying its join RPC bumps only once
    assert pool.join()["mepoch"] == m0 + 1
    assert s.membership_epoch == m0 + 1


def test_leave_requeues_and_fences(sched):
    """A leaving worker's held part is re-queued with the stamp cleared;
    its post-leave finish echo is fenced out while the re-execution by a
    survivor counts — exactly once, under churn."""
    from wormhole_tpu.solver.workload import WorkType

    s, pattern = sched
    s.start_round(pattern, 1, "libsvm", WorkType.TRAIN, 0)
    c0, p0 = _worker(s.uri, "worker-0")
    c1, p1 = _worker(s.uri, "worker-1")
    assert p0.sync_round() is not None
    assert p1.sync_round() is not None
    pid, _ = p0.get()
    stamp = p0._part_mepoch[pid]
    m0 = s.membership_epoch
    p0.leave()
    assert s.membership_epoch == m0 + 1
    # the dead incarnation's late completion does not count
    r = c0.call(op="finish", part_id=pid, epoch=p0.epoch, mepoch=stamp)
    assert r["counted"] is False
    # the survivor drains the round, re-queued part included
    done = 0
    while True:
        got = p1.get()
        if got is None:
            break
        p1.finish(got[0])
        done += 1
    assert done == 2
    threading.Thread(target=s.announce_shutdown, daemon=True).start()
    s.wait_round(verbose=False)


def test_retire_drains_highest_rank(sched):
    s, _ = sched
    _c0, p0 = _worker(s.uri, "worker-0")
    _c1, p1 = _worker(s.uri, "worker-1")
    s.set_elastic_target(1)
    r = _c0.call(op="elastic")
    assert r["target"] == 1
    assert r["retiring"] == ["worker-1"]
    # the retiring worker gets no new parts and latches retire; the
    # survivor is untouched
    assert p1.get() is None
    assert p1.retire is True
    assert p1.sync_round(wait=False) is None
    assert p0.retire is False


def test_elastic_op_publishes_target(sched):
    s, _ = sched
    c, _pool = _worker(s.uri, "worker-0")
    r = c.call(op="elastic", target=3)
    assert r["target"] == 3
    assert r["live"] == ["worker-0"]


def test_elastic_op_reports_shutdown(sched):
    """The launcher's elastic supervisor gates spawning on this flag:
    after shutdown, workers draining out make alive < target look like
    a deficit, and a worker spawned then would strand against a
    scheduler that exits before it can register."""
    s, _ = sched
    c, _pool = _worker(s.uri, "worker-0")
    assert c.call(op="elastic", target=3)["shutdown"] is False
    s.announce_shutdown()
    assert c.call(op="elastic")["shutdown"] is True


def test_remote_pool_observes_epoch_bumps(sched):
    """Every reply latches the membership epoch so a worker's store can
    absorb bumps between parts without a dedicated RPC."""
    s, _ = sched
    _c0, p0 = _worker(s.uri, "worker-0")
    p0.sync_round(wait=False)  # any op=epoch reply carries mepoch
    assert p0.mepoch == s.membership_epoch
    _c1, p1 = _worker(s.uri, "worker-1")
    p1.join()
    p0.sync_round(wait=False)
    assert p0.mepoch == s.membership_epoch == p1.mepoch


# -- MembershipController policy ---------------------------------------------

def test_controller_grows_on_sustained_stall():
    c = MembershipController(2, lo=1, hi=4, grow_after=3)
    assert c.record(0.0, 1.0) == 2
    assert c.record(0.0, 1.0) == 2
    assert c.record(0.0, 1.0) == 3  # third consecutive starved obs
    assert c.decisions[-1]["why"] == "starved"


def test_controller_shrinks_on_sustained_idle():
    c = MembershipController(2, lo=1, hi=4, shrink_after=6)
    for _ in range(5):
        assert c.record(4.0, 0.0) == 2
    assert c.record(4.0, 0.0) == 1
    assert c.decisions[-1]["why"] == "overfed"


def test_controller_hysteresis_resets_on_mixed_signal():
    c = MembershipController(2, lo=1, hi=4, grow_after=3)
    c.record(0.0, 1.0)
    c.record(0.0, 1.0)
    c.record(0.0, 0.2)  # neither starved nor idle: streaks reset
    assert c.record(0.0, 1.0) == 2
    assert c.record(0.0, 1.0) == 2
    assert c.record(0.0, 1.0) == 3


def test_controller_clamps_to_bounds():
    c = MembershipController(1, lo=1, hi=2, grow_after=1, shrink_after=1)
    assert c.record(0.0, 1.0) == 2
    assert c.record(0.0, 1.0) == 2  # hi
    assert c.record(4.0, 0.0) == 1
    assert c.record(4.0, 0.0) == 1  # lo


# -- BSP plane: ring rebuild -------------------------------------------------

@pytest.fixture
def ring():
    sched = Scheduler("127.0.0.1", 0, node_timeout=10.0)
    sched.serve()
    made = []

    def make(rank, world, **kw):
        c = SchedulerClient(sched.uri, f"worker-{rank}")
        c.register()
        w = BspWorker(rank, world, c, step_timeout=0.5, retry_sec=20.0,
                      **kw)
        made.append(w)
        return w

    yield make
    for w in made:
        w.close()
    sched.stop()


def _run_ranks(fns):
    results = [None] * len(fns)
    errors = []

    def runner(i, fn):
        try:
            results[i] = fn()
        except BaseException as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

    ts = [threading.Thread(target=runner, args=(i, f))
          for i, f in enumerate(fns)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    if errors:
        raise errors[0]
    assert all(not t.is_alive() for t in ts), "ring deadlocked"
    return results


def test_bsp_leave_rebuilds_shrunk_ring(ring):
    """A rank resigning (bsp_leave) bumps the generation; survivors'
    next collective aborts against the dead peer, adopts the shrunk
    peer list (re-indexed rank/world), and completes over 2 — the reduced
    value bit-identical across survivors."""
    world = 3
    c0, c1, c2 = _run_ranks([lambda r=r: ring(r, world)
                             for r in range(world)])
    xs = [np.full(13, float(r + 1), np.float32) for r in range(world)]
    outs = _run_ranks([lambda c=c, x=x: c.allreduce(x)
                       for c, x in zip((c0, c1, c2), xs)])
    assert float(outs[0][0]) == pytest.approx(6.0)
    gen0 = c0.gen
    c2.leave()
    c2.close()
    outs = _run_ranks([lambda c=c, x=x: c.allreduce(x)
                       for c, x in zip((c0, c1), xs[:2])])
    np.testing.assert_allclose(outs[0], xs[0] + xs[1])
    assert np.array_equal(outs[0], outs[1])
    assert c0.gen > gen0
    assert c0.world == 2 and c1.world == 2
    assert {c0.rank, c1.rank} == {0, 1}


def test_bsp_join_bumps_generation(ring):
    """Once the group has formed, a never-seen rank registering is an
    elastic JOIN: the generation bumps and bsp_peers reports the grown
    set — the signal survivors rebuild over at their round boundary."""
    world = 2
    c0, c1 = _run_ranks([lambda r=r: ring(r, world) for r in range(world)])
    _run_ranks([lambda c=c: c.allreduce(np.ones(4, np.float32))
                for c in (c0, c1)])
    gen0 = c0.gen
    host, port = c0.client.addr
    c2_client = SchedulerClient(f"{host}:{port}", "worker-2")
    c2_client.register()
    r = c2_client.call(op="register_bsp", rank=2, world=3,
                       uri="127.0.0.1:1")
    assert int(r["gen"]) == gen0 + 1
    peers = c2_client.call(op="bsp_peers", world=2)
    assert peers["ready"] and len(peers["uris"]) == 3
    assert c0._poll_gen() is True
    assert c0.world == 3 and c0.rank == 0


# -- slow tier: launcher churn drill ----------------------------------------

@pytest.mark.slow
def test_launcher_elastic_churn_converges(tmp_path):
    """End-to-end 2->3->2 churn: an `--elastic` difacto job whose plan
    joins a worker at 3s and retires one at 9s must exit clean, show the
    membership machinery in its stdout, and land within tolerance of the
    fixed-world logloss."""
    for i in range(2):
        (tmp_path / f"train-{i}.libsvm").write_text(
            synth_libsvm_text(1500, seed=i))
    (tmp_path / "val.libsvm").write_text(synth_libsvm_text(1500, seed=9))
    conf = tmp_path / "elastic.conf"
    conf.write_text(f"""
train_data = "{tmp_path}/train-.*"
val_data = "{tmp_path}/val.libsvm"
algo = ftrl
dim = 4
threshold = 2
lambda_l1 = 0.5
minibatch = 128
num_buckets = 16384
v_buckets = 4096
max_data_pass = 5
max_delay = 1
""")

    def run(plan):
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
                   WH_ASYNC_SYNC="1", WH_ELASTIC_SEC="1")
        for k in ("WH_FAULT_SPEC", "WH_OBS_DIR", "WH_ELASTIC_PLAN",
                  "WH_SCHED_PORT"):
            env.pop(k, None)
        argv = [sys.executable, "-m", "wormhole_tpu.launcher.dmlc_tpu",
                "-n", "2", "-s", "1", "--node-timeout", "10"]
        if plan is not None:
            env["WH_ELASTIC_PLAN"] = plan
            argv.append("--elastic")
        argv += ["--", sys.executable, "-m", "wormhole_tpu.apps.difacto",
                 str(conf)]
        r = subprocess.run(argv, capture_output=True, text=True,
                           timeout=240, env=env, cwd=REPO)
        assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
        m = re.findall(r"final val: logloss=([0-9.]+)", r.stdout)
        assert m, r.stdout[-4000:]
        return float(m[-1]), r.stdout

    base, _ = run(None)
    churned, out = run("join@3,leave@9")
    assert "[membership] epoch -> 1 (join: worker-2)" in out
    assert "retiring worker-2" in out
    assert abs(churned - base) < 0.01, (base, churned)
