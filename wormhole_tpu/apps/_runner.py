"""Shared app runner: conf parsing + role dispatch + distributed loops.

The reference's minibatch apps are a scheduler/server/worker triple over
ps-lite (reference linear.cc:6-25 role dispatch; minibatch_solver.h:85-195
scheduler loop; :284-329 worker loop; servers async_sgd.h:200-226). Here:

- no role env (the common case): single process drives the full solver on
  the local device mesh — scheduler, "servers" (sharded tables in HBM)
  and worker in one.
- scheduler role: owns the control plane — per-pass workload rounds,
  merged progress rows, early stop, model save commands to the server
  group, shutdown announcement.
- server role: a runtime.ps_server.ServerNode owning a bucket-range shard
  of every state table; workers push deltas / pull merged state through
  it, so ALL workers train ONE model (the defining ps-lite semantic,
  async_sgd.h:240-288). Staleness is bounded by the `max_delay` knob:
  a worker trains at most max_delay minibatches between syncs.
- worker role: a MinibatchSolver whose pool is the scheduler's
  RemotePool; device state syncs against the server group per part and
  every max_delay minibatches.

With `-s 0` (no servers) workers fall back to independent replicas — a
file-throughput test mode only; rank 0 alone saves its replica.
"""

from __future__ import annotations

import os
import sys
import time

from wormhole_tpu.config import knob_value, load_config
from wormhole_tpu.obs import metrics as _obs
from wormhole_tpu.obs import report as _report
from wormhole_tpu.obs import trace as _trace
from wormhole_tpu.parallel.hot_plane import HotPlane
from wormhole_tpu.runtime.ps_server import PSClient, ServerNode, SyncedStore
from wormhole_tpu.runtime.tracker import (
    RemotePool, Scheduler, SchedulerClient, node_env,
)
from wormhole_tpu.solver.minibatch_solver import MinibatchSolver
from wormhole_tpu.solver.progress import Progress
from wormhole_tpu.solver.workload import WorkType
from wormhole_tpu.utils import checkpoint as ckpt


def parse_cli(cls, argv):
    """conf file (optional first arg without '=') + key=value overrides —
    the reference's `app.dmlc conf k=v` convention (arg_parser.h:36-45)."""
    conf = None
    rest = list(argv)
    if rest and "=" not in rest[0]:
        conf = rest.pop(0)
    return load_config(cls, conf_file=conf, argv=rest)


def run_minibatch_app(cfg, make_learner, verbose: bool = True) -> dict:
    """Entry for linear/difacto-style streaming apps."""
    env = node_env()
    if env.role is None:
        learner = make_learner(cfg, env)
        return MinibatchSolver(learner, cfg, verbose=verbose).run()
    if env.role.value == "serve":
        # online serving shard: independent of the train data plane, so
        # it dispatches the same way under global_mesh or PS mode
        from wormhole_tpu.serving.server import run_serve_role

        return run_serve_role(cfg, env)
    if getattr(cfg, "global_mesh", False):
        # one SPMD program over every worker's devices (parallel/multihost)
        if env.role.value == "scheduler":
            return _run_scheduler_global(env)
        if env.role.value == "server":
            return {}  # no PS data plane: collectives carry the model
        return _run_worker_global(cfg, env, make_learner, verbose)
    if env.role.value == "scheduler":
        return _run_scheduler(cfg, env, verbose)
    if env.role.value == "server":
        return _run_server(cfg, env)
    return _run_worker(cfg, env, make_learner, verbose)


def maybe_run_global(cfg, worker_body):
    """Role dispatch for global-mesh BSP apps: returns an exit code when
    this process has a distributed role under global_mesh=1, else None
    (caller falls through to the single-process path). `worker_body` is
    called as worker_body(cfg, env, client) inside a multihost
    worker_session."""
    if not getattr(cfg, "global_mesh", False):
        return None
    env = node_env()
    if env.role is None:
        return None
    if env.role.value == "scheduler":
        _run_scheduler_global(env)
        return 0
    if env.role.value == "server":
        return 0
    from wormhole_tpu.parallel import multihost as mh

    with mh.worker_session(env) as client:
        return worker_body(cfg, env, client)


def maybe_run_bsp(cfg, worker_body):
    """Role dispatch for BSP-allreduce apps (bsp=1 under the launcher):
    returns an exit code when this process has a distributed role, else
    None (caller falls through to the single-process path). Each worker
    gets a `BspWorker` (runtime/allreduce.py) registered with the
    tracker; `worker_body` is called as worker_body(cfg, env, client,
    comm). The scheduler runs a liveness-only loop and emits the run
    report at drain; servers are idle (`-s 0` is the natural launch)."""
    if not getattr(cfg, "bsp", False):
        return None
    env = node_env()
    if env.role is None:
        return None
    if env.role.value == "scheduler":
        _run_scheduler_bsp(env)
        return 0
    if env.role.value == "server":
        return 0
    from wormhole_tpu.runtime.allreduce import BspWorker
    from wormhole_tpu.runtime.tracker import LivenessPinger

    client = SchedulerClient(env.scheduler_uri, f"worker-{env.rank}")
    client.register()
    pinger = LivenessPinger(client)
    comm = BspWorker(env.rank, env.num_workers, client)
    try:
        rc = worker_body(cfg, env, client, comm)
    finally:
        pinger.stop()
        comm.close()
    try:
        # final metrics snapshot rides the deregistration (same contract
        # as _run_worker: bye ONLY on clean completion — a crashed
        # worker must instead be evicted, which is what lets the
        # launcher's respawn rejoin the group)
        client.call(op="bye", metrics=_obs.REGISTRY.snapshot())
    except Exception:
        pass
    return rc


def _run_scheduler_bsp(env) -> None:
    """BSP-mode scheduler: liveness + rendezvous (register_bsp/bsp_peers/
    blobs) — the collectives themselves are worker-to-worker. Exits once
    every worker registered and left, emitting the aggregated run
    report; bounded startup so a mis-launched job fails loudly."""
    sched = Scheduler.from_env(env)
    sched.serve()
    if knob_value("WH_ELASTIC"):
        sched.start_membership_controller(env.num_workers)
    startup_deadline = time.monotonic() + max(60.0, sched.node_timeout * 4)
    try:
        # a respawned scheduler (journal replay) already saw workers in a
        # previous incarnation — the startup deadline must not fire while
        # the restored group rides out the restart on its retry budgets
        seen_any = sched.incarnation > 0
        while True:
            time.sleep(0.5)
            seen_any = seen_any or bool(sched.live_workers())
            if seen_any and sched.workers_drained(env.num_workers):
                break
            if not seen_any and time.monotonic() > startup_deadline:
                raise RuntimeError(
                    "no BSP worker registered within the startup deadline")
        _emit_run_report(sched, None, verbose=True)
    finally:
        sched.stop()


def _run_scheduler_global(env) -> dict:
    """Global-mesh mode scheduler: pure liveness — the SPMD collectives
    synchronize the workers, so the control plane only keeps the launcher
    happy and reports worker deaths. Exits with an error if no worker
    ever shows up (e.g. the jax.distributed rendezvous failed)."""
    sched = Scheduler.from_env(env)
    sched.serve()
    startup_deadline = time.monotonic() + max(60.0, sched.node_timeout * 4)
    try:
        # a respawned scheduler (journal replay) already saw workers in a
        # previous incarnation — the startup deadline must not fire while
        # the restored group rides out the restart on its retry budgets
        seen_any = sched.incarnation > 0
        while True:
            time.sleep(1.0)
            # ever-seen, not live-now: a pure-predict job's workers can
            # register and leave between two polls
            seen_any = seen_any or sched.workers_ever_seen() > 0
            if seen_any and not sched.live_workers():
                return {}
            if not seen_any and time.monotonic() > startup_deadline:
                raise RuntimeError(
                    "no worker registered within the startup deadline — "
                    "the jax.distributed rendezvous likely failed")
    finally:
        sched.stop()


def _run_worker_global(cfg, env, make_learner, verbose: bool) -> dict:
    """Lockstep SPMD worker: all `-n` processes form ONE mesh and run the
    SAME jitted steps; each contributes minibatch/num_workers rows per
    step from its stable slice of file parts (the reference's
    RowBlockIter(rank, world) split, kmeans.cc:149-154). End-of-pass is a
    collective fact: a step whose global example count is zero means all
    ranks drained."""
    from wormhole_tpu.parallel import multihost as mh

    with mh.worker_session(env) as client:
        return _global_train(cfg, env, make_learner, verbose, client)


def _global_train(cfg, env, make_learner, verbose, client) -> dict:
    import dataclasses as _dc

    from wormhole_tpu.data.minibatch import MinibatchIter
    from wormhole_tpu.data.rowblock import to_device_batch
    from wormhole_tpu.parallel import multihost as mh
    from wormhole_tpu.parallel.mesh import batch_sharding

    nproc = env.num_workers
    assert cfg.minibatch % nproc == 0, (
        f"minibatch {cfg.minibatch} must divide over {nproc} workers")
    local_rows = cfg.minibatch // nproc
    # the SPMD xla path: the pallas packs are per-process host products
    cfg = _dc.replace(cfg, kernel="xla")
    learner = make_learner(cfg, env)  # make_mesh() sees GLOBAL devices
    mesh = learner.mesh
    assert mesh.devices.size == len(__import__("jax").devices()), (
        "global-mesh mode expects the learner on the full device set")
    bsh = batch_sharding(mesh, 1)
    local_cap = local_rows * cfg.nnz_per_row
    rank = env.rank
    empty = mh.empty_rowblock()

    def global_args(blk):
        db = to_device_batch(blk, local_rows, local_cap, cfg.num_buckets)
        return mh.global_coo_batch(bsh, db, rank, local_rows,
                                   cfg.minibatch, cfg.nnz_per_row)

    train_fn, eval_fn = learner.global_step_protocol()
    rng = __import__("jax").random.PRNGKey(0)

    def run_pass(pattern, train: bool, seed: int):
        nonlocal rng
        prog_tot: dict = {}

        def batches():
            for f, k in mh.rank_parts(pattern, cfg.num_parts_per_file,
                                      env):
                yield from MinibatchIter(
                    f, k, cfg.num_parts_per_file, cfg.data_format,
                    minibatch_size=local_rows,
                    shuf_buf=(cfg.rand_shuffle * local_rows
                              if train else 0),
                    neg_sampling=(cfg.neg_sampling if train else 1.0),
                    seed=seed)

        it = batches()
        while True:
            blk = next(it, None)
            args = global_args(blk if blk is not None else empty)
            if train:
                # identical key sequence on every rank keeps any
                # stochastic pieces (e.g. difacto grad dropout) in SPMD
                # agreement
                rng, sub = __import__("jax").random.split(rng)
                prog = train_fn(args, sub)
            else:
                prog = eval_fn(args)
            # prog holds Python floats: the step's one read is made
            # (models/minibatch_learner.read_progress). nex is a GLOBAL sum (the
            # batch mask is mesh-sharded): zero means every rank drained.
            # The decision must be THE SAME on every rank (the next step
            # is a collective), so it depends only on this global value —
            # never on local state.
            if prog["nex"] == 0:
                break
            for k, v in prog.items():
                prog_tot[k] = prog_tot.get(k, 0.0) + v
        return prog_tot

    result = {}
    if cfg.model_in:
        arrays = ckpt.load_parts(
            cfg.model_in, cfg.load_iter if cfg.load_iter >= 0 else None)
        mh.load_replicated(_store(learner), arrays)
    for dp in range(cfg.max_data_pass):
        tr = run_pass(cfg.train_data, True, dp)
        result["train"] = tr
        if rank == 0 and verbose:
            n = max(tr.get("nex", 0.0), 1.0)
            print(f"[global-mesh] train pass {dp}: "
                  f"nex={int(tr.get('nex', 0.0))} "
                  f"logloss={tr.get('logloss', 0.0) / n:.6f}",
                  flush=True)
        if cfg.val_data:
            vl = run_pass(cfg.val_data, False, dp)
            result["val"] = vl
            if rank == 0 and verbose:
                n = max(vl.get("nex", 0.0), 1.0)
                print(f"[global-mesh] val pass {dp}: "
                      f"logloss={vl.get('logloss', 0.0) / n:.6f}",
                      flush=True)
    if "val" in result and rank == 0 and verbose:
        vl = result["val"]
        n = max(vl.get("nex", 0.0), 1.0)
        print(f"final val: logloss={vl.get('logloss', 0.0) / n:.6f} "
              f"auc={vl.get('auc', 0.0) / n:.6f} "
              f"acc={vl.get('acc', 0.0) / n:.6f}", flush=True)
    if cfg.model_out and rank == 0:
        # tables are replicated over the global mesh (model axis 1):
        # fetch each process-locally and save single-file
        class _GlobalView:
            mesh = learner.mesh

            @staticmethod
            def to_numpy():
                return {k: mh.fetch_replicated(v)
                        for k, v in _store(learner).state.items()}

        ckpt.save_model(_GlobalView, cfg.model_out)
        if verbose:
            print(f"model saved: {cfg.model_out}", flush=True)
    if getattr(cfg, "predict_out", None):
        _global_predict(cfg, env, learner, global_args, empty, verbose)
    return result


def _global_predict(cfg, env, learner, global_args, empty, verbose) -> None:
    """Lockstep SPMD predict (PredictStream parity, iter_solver.h:140-156
    + the reference's per-part output files): each rank streams ITS
    stable part slice through the shared jitted forward — every step is
    a collective, so drained ranks keep feeding masked-empty batches
    until the GLOBAL live-row count hits zero — and writes margins for
    its contributed rows to `{predict_out}_rank-R_part-J` (same naming
    as the PS-mode per-rank predict)."""
    import os

    import numpy as np

    from wormhole_tpu.data.minibatch import MinibatchIter
    from wormhole_tpu.parallel import multihost as mh

    rank = env.rank
    local_rows = cfg.minibatch // env.num_workers
    pred_fn = learner.global_predict_protocol()
    data = cfg.val_data or cfg.train_data
    parts = mh.rank_parts(data, cfg.num_parts_per_file, env)
    os.makedirs(os.path.dirname(cfg.predict_out) or ".", exist_ok=True)
    prob = bool(getattr(cfg, "prob_predict", False))

    def path(j):
        return f"{cfg.predict_out}_rank-{rank}_part-{j}"

    for j in range(len(parts)):  # zero-row parts still get their file
        open(path(j), "w").close()

    def blocks():
        for j, (f, k) in enumerate(parts):
            for blk in MinibatchIter(f, k, cfg.num_parts_per_file,
                                     cfg.data_format,
                                     minibatch_size=local_rows):
                yield j, blk

    it = blocks()
    while True:
        got = next(it, None)
        blk = got[1] if got is not None else empty
        size = blk.size
        seg, idx, val, _, mask = global_args(blk)
        margins, nex = pred_fn((seg, idx, val, mask))
        if float(nex) == 0.0:
            break  # every rank drained (collective fact)
        if got is None or size == 0:
            continue
        local = mh.fetch_local_rows(margins, rank * local_rows,
                                    rank * local_rows + size)
        if prob:
            local = 1.0 / (1.0 + np.exp(-local))
        with open(path(got[0]), "a") as fh:
            for m in local:
                fh.write(f"{m:.6g}\n")
    if verbose and rank == 0:
        print(f"predict written: {cfg.predict_out}_rank-*", flush=True)


def _wait_server_group(sched: Scheduler, timeout: float = 60.0) -> PSClient:
    """Block until every `-s` server registered its URI; returns a client
    over the group (the scheduler's command channel for load/save)."""
    deadline = time.monotonic() + timeout
    while True:
        with sched._lock:
            if len(sched._server_uris) >= sched.num_servers:
                break
        if time.monotonic() >= deadline:
            raise RuntimeError(
                "ps servers did not all register within "
                f"{timeout:.0f}s ({len(sched._server_uris)}"
                f"/{sched.num_servers})")
        time.sleep(0.2)
    # under recovery (launcher exports WH_PS_RETRY_SEC) the command
    # channel must survive a server respawn too: a dead server's save/
    # load lands on its reborn URI, which the scheduler itself holds
    # authoritatively via re-registration
    retry = float(os.environ.get("WH_PS_RETRY_SEC", "0") or 0)
    return PSClient(_server_uris(sched), retry_deadline=retry,
                    resolver=(lambda: _server_uris(sched))
                    if retry > 0 else None)


_MODEL_LOADED_KEY = "__ps_model_loaded__"


def _run_scheduler(cfg, env, verbose: bool) -> dict:
    """Scheduler loop with the reference's iteration protocol
    (minibatch_solver.h:96-133): command the server group to LOAD
    model_in before any worker initializes (resuming pass numbering at
    load_iter+1), SAVE `_iter-K` checkpoints every save_iter passes, and
    save the final model at job end."""
    sched = Scheduler.from_env(env)
    sched.serve()
    if knob_value("WH_ELASTIC"):
        # elastic membership: scripted churn (WH_ELASTIC_PLAN) or
        # gauge-driven worker-count control; the launcher's elastic
        # supervisor turns the published target into spawned joiners,
        # the scheduler itself marks the shrink side retiring
        sched.start_membership_controller(env.num_workers)
    t0 = time.time()
    result = {}
    ps = None
    start_pass = 0
    try:
        if cfg.model_in and cfg.load_iter >= 0:
            # resume pass numbering in EVERY mode (PS servers load below;
            # replica-mode workers load model_in themselves) — the
            # already-trained passes must not be re-dispatched
            start_pass = cfg.load_iter + 1
        if env.num_servers > 0:
            ps = _wait_server_group(sched)
            if cfg.model_in:
                if sched.has_blob(_MODEL_LOADED_KEY):
                    # respawned scheduler: the journal says the load was
                    # already commanded before the crash — the PS shards
                    # hold the (possibly further-trained) model, and
                    # re-loading would roll their state back
                    if verbose:
                        print("model load skipped (already loaded before "
                              "the scheduler restart)", flush=True)
                else:
                    it = cfg.load_iter if cfg.load_iter >= 0 else None
                    ps.load(cfg.model_in, it)
                    if verbose:
                        print(f"model loaded from {cfg.model_in}"
                              + (f" iter {cfg.load_iter}"
                                 if cfg.load_iter >= 0 else " (last)"),
                              flush=True)
                    # release the workers gated on the load (they must not
                    # create fresh tables while servers are still loading);
                    # journaled so a restart does not re-command the load
                    sched.publish_blob(_MODEL_LOADED_KEY, "1")
        # resume point from the replayed journal: a respawned scheduler
        # (incarnation > 0) rejoins the pass loop where the last journaled
        # round left it instead of re-dispatching from pass 0. An
        # in-flight round is WAITED OUT (the restored pool still tracks
        # its unfinished parts — workers keep pulling from it through
        # their retry budgets); a finished round is skipped.
        resume_wait = None   # "train" | "val": first pass rejoins mid-round
        skip_train = False   # TRAIN of the first pass already finished
        if sched.incarnation > 0 and sched._round is not None:
            rdp = int(sched._round.get("data_pass", 0))
            in_flight = not sched.pool.is_finished()
            if int(sched._round.get("type", 0)) == int(WorkType.TRAIN):
                start_pass = max(start_pass, rdp)
                if in_flight:
                    resume_wait = "train"
                else:
                    skip_train = True
            elif in_flight:    # VAL still running
                start_pass = max(start_pass, rdp)
                skip_train = True
                resume_wait = "val"
            else:              # VAL finished: the whole pass is done
                start_pass = max(start_pass, rdp + 1)
                result["val"] = sched.progress
            if verbose:
                print(f"resuming at pass {start_pass} from the scheduler "
                      f"journal (incarnation {sched.incarnation}"
                      + (f", waiting out the in-flight {resume_wait} round"
                         if resume_wait else "") + ")", flush=True)
        for dp in range(start_pass, cfg.max_data_pass):
            first = dp == start_pass
            if not (first and skip_train):
                if first and resume_wait == "train":
                    if verbose:
                        print(f"training pass {dp}: resumed mid-round",
                              flush=True)
                else:
                    n = sched.start_round(cfg.train_data,
                                          cfg.num_parts_per_file,
                                          cfg.data_format, WorkType.TRAIN,
                                          dp,
                                          local_data=getattr(
                                              cfg, "local_data", False),
                                          dispatch=getattr(cfg, "dispatch",
                                                           "online"))
                    if verbose:
                        print(f"training pass {dp}: {n} files", flush=True)
                result["train"] = sched.wait_round(cfg.print_sec, t0,
                                                   verbose)
            if cfg.val_data:
                if first and resume_wait == "val":
                    if verbose:
                        print(f"validation pass {dp}: resumed mid-round",
                              flush=True)
                else:
                    sched.start_round(cfg.val_data, cfg.num_parts_per_file,
                                      cfg.data_format, WorkType.VAL, dp)
                    if verbose:
                        print(f"validation pass {dp}", flush=True)
                result["val"] = sched.wait_round(cfg.print_sec, t0, verbose)
            if (ps is not None and cfg.model_out
                    and getattr(cfg, "save_iter", 0) > 0
                    and (dp + 1) % cfg.save_iter == 0
                    and dp + 1 < cfg.max_data_pass):
                # periodic `_iter-K` snapshot of the server shards — the
                # mid-job recovery point (minibatch_solver.h:124-127)
                paths = ps.save(cfg.model_out, it=dp)
                if verbose:
                    print(f"model saved for iter {dp}: {paths}",
                          flush=True)
        if "val" in result:
            # machine-readable final metrics line (the tutorial log's final
            # row, criteo_kaggle.rst:78)
            v = result["val"]
            print(f"final val: logloss={v.mean('logloss'):.6f} "
                  f"auc={v.mean('auc'):.6f} acc={v.mean('acc'):.6f}",
                  flush=True)
        # command the server group to save its shards, then release
        # everyone (IterScheduler::SaveModel -> kServerGroup parity)
        if ps is not None and cfg.model_out:
            paths = ps.save(cfg.model_out)
            if verbose:
                print(f"model saved: {paths}", flush=True)
        sched.announce_shutdown()
        # wait for the workers' TAIL work (final wire stats, per-rank
        # predict) before tearing down the planes they still need —
        # each worker deregisters with op=bye when done, and its
        # liveness pings keep it visible until then. Drained means ALL
        # `-n` workers registered and left: a pure-predict job
        # (max_data_pass=0) reaches this point before slow-starting
        # workers have even registered, and a fast worker's bye must
        # not read as "everyone finished". Bounded so a worker that
        # died (liveness eviction, no bye) or never came up cannot
        # hold the job open.
        drain_deadline = time.monotonic() + max(120.0,
                                                sched.node_timeout * 4)
        # fast path for a mis-launched job (predict with a wrong -n is
        # the classic): if NO worker has ever registered after a
        # startup-sized grace (generous enough for slow JAX/TPU init —
        # node_timeout only bounds ping gaps of REGISTERED workers),
        # none is coming — exit LOUDLY instead of holding the scheduler
        # for the full drain bound
        # the same bound as drain_deadline: a max_data_pass=0 job whose
        # workers spend 60-120s in JAX/TPU init must not find the PS
        # plane torn down the moment they register (ADVICE #1)
        none_deadline = time.monotonic() + max(120.0,
                                               sched.node_timeout * 4)
        while (not sched.workers_drained(env.num_workers)
               and time.monotonic() < drain_deadline):
            if (sched.workers_ever_seen() == 0
                    and time.monotonic() >= none_deadline):
                print("[scheduler] WARNING: no worker ever registered; "
                      "abandoning shutdown drain (mis-launched job? "
                      "check -n and the worker logs)", flush=True)
                break
            time.sleep(0.2)
        # end-of-run telemetry: per-server push/pull truth straight from
        # the (still-alive) servers, then the aggregated report — AFTER
        # the drain so the final snapshots workers piggybacked on their
        # `bye` are in, BEFORE shutdown while the stats op still answers
        ps_stats = None
        if ps is not None:
            try:
                ps_stats = {r: ps.stats(r) for r in range(ps.world)}
            except Exception as e:
                print(f"[obs] ps stats unavailable at shutdown: {e}",
                      flush=True)
            ps.shutdown()
        _emit_run_report(sched, ps_stats, verbose)
        return result
    finally:
        sched.stop()


def _emit_run_report(sched: Scheduler, ps_stats, verbose: bool) -> None:
    """Build the end-of-run report from the scheduler's aggregated
    metrics, print the human summary plus the `[run-report]` machine
    line (the launcher scrapes it), and write run_report.json when
    WH_OBS_DIR is set. Telemetry must never fail the job."""
    try:
        agg = sched.aggregate_metrics()
        report = _report.build(agg["aggregate"], nodes=agg["nodes"],
                               ps_stats=ps_stats)
        if verbose:
            for line in _report.format_lines(report):
                print(line, flush=True)
        print(_report.machine_line(report), flush=True)
        if _report.enabled():
            path = _report.write(report)
            if verbose:
                print(f"[obs] run report written: {path}", flush=True)
    except Exception as e:
        print(f"[obs] run report failed: {e}", flush=True)


def _server_uris(sched: Scheduler) -> list[str]:
    with sched._lock:
        return [sched._server_uris[r] for r in sorted(sched._server_uris)]


def _run_server(cfg, env) -> dict:
    """One ps server process: bucket-range shard owner. When the
    launcher provides a snapshot dir (WH_SNAPSHOT_DIR), the node writes
    periodic async shard snapshots there, and a respawned incarnation
    (WH_RESTORE_EPOCH > 0) restores from them before serving — then
    re-announces its NEW uri through the scheduler (register_server
    overwrites the rank's entry, and worker-side retry re-resolves)."""
    epoch = int(os.environ.get("WH_RESTORE_EPOCH", "0") or 0)
    node = ServerNode(env.rank, env.num_servers, epoch=epoch)
    snap_dir = os.environ.get("WH_SNAPSHOT_DIR", "")
    if snap_dir:
        snap_base = os.path.join(snap_dir, "srv")
        if epoch > 0:
            if not node.restore_snapshot(snap_base):
                print(f"[ps server {env.rank}] respawn epoch {epoch}: no "
                      "snapshot yet — restarting empty (pre-first-"
                      "snapshot state is not recoverable)", flush=True)
    node.serve()
    client = SchedulerClient(env.scheduler_uri, f"server-{env.rank}")
    client.call(op="register_server", rank=env.rank, uri=node.uri)
    if snap_dir:
        node.start_snapshots(os.path.join(snap_dir, "srv"),
                             float(getattr(cfg, "server_snapshot_sec", 5.0)
                                   or 5.0))
    try:
        while not node.wait_shutdown(2.0):
            # liveness ping, carrying this incarnation's metrics
            # snapshot for the scheduler's aggregation
            client.call(op="epoch", metrics=_obs.REGISTRY.snapshot())
    finally:
        node.stop()
    return {}


def _run_worker(cfg, env, make_learner, verbose: bool) -> dict:
    from wormhole_tpu.runtime.tracker import LivenessPinger

    learner = make_learner(cfg, env)
    # the worker is the one role that opens the device; its solver runs
    # quiet, so state here where it landed (the launcher prefixes the role)
    print(learner.placement, flush=True)
    client = SchedulerClient(env.scheduler_uri, f"worker-{env.rank}")
    client.register()
    # background liveness pings: a worker streaming a large part (or in
    # its first jit compile) makes no scheduler RPC for minutes; without
    # pings the liveness sweep would evict it and — with the
    # all-workers-lost abort — kill a healthy single-worker job
    pinger = LivenessPinger(client)
    try:
        result = _run_worker_body(cfg, env, verbose, learner, client)
    finally:
        pinger.stop()
    # deregister ONLY on clean completion, so the scheduler's shutdown
    # drain sees the tail work (wire stats, predict) finished. A worker
    # that CRASHES must instead time out of the liveness table — that
    # eviction is what re-queues its in-flight parts (a bye from a
    # crash path would silently disable the failure recovery).
    try:
        # the bye carries this worker's FINAL metrics snapshot — the
        # pinger's last periodic one may predate the tail work
        client.call(op="bye", metrics=_obs.REGISTRY.snapshot())
    except Exception:
        pass
    return result


def _run_worker_body(cfg, env, verbose, learner, client) -> dict:
    pool = RemotePool(client)
    if knob_value("WH_ELASTIC_JOIN"):
        # elastic joiner (spawned mid-job by the launcher's supervisor):
        # announce the join so the scheduler bumps the membership epoch
        # and rebalances pinned parts over the grown set
        pool.join()
    if cfg.model_in and env.num_servers == 0:
        # replica mode only: with a server group the SCHEDULER commands
        # the servers to load (the model never crosses the worker wire);
        # this worker just gates on that load and pulls the stamped rows
        ckpt.load_model(_store(learner), cfg.model_in,
                        cfg.load_iter if cfg.load_iter >= 0 else None)
    synced = None
    if env.num_servers > 0:
        deadline = time.monotonic() + 60.0
        while not (s := client.call(op="servers"))["ready"]:
            if time.monotonic() >= deadline:
                raise RuntimeError(
                    f"only {s.get('num_known', 0)}/{s['num_servers']} ps "
                    "servers registered within 60s — a server process "
                    "likely died at startup")
            time.sleep(0.2)
        if cfg.model_in:
            # wait for the scheduler's load command to finish — an
            # init_spec racing ahead of it would create FRESH tables and
            # the load would then (correctly) refuse to clobber them
            load_deadline = time.monotonic() + 120.0
            while not client.call(op="blob_get",
                                  key=_MODEL_LOADED_KEY)["ok"]:
                if time.monotonic() >= load_deadline:
                    raise RuntimeError(
                        "scheduler never announced the model_in load")
                time.sleep(0.2)
        # server-death recovery (opt-in): with a retry budget the client
        # survives a dead server — it re-resolves the rank's NEW uri
        # through the scheduler, fences with `hello`, and replays its
        # push journal (the server's seq dedup makes over-replay safe).
        # Zero (the default) keeps the original fail-fast behavior.
        retry_sec = float(os.environ.get("WH_PS_RETRY_SEC", "0") or 0)
        cfg_retry = float(getattr(cfg, "ps_retry_sec", 0.0) or 0.0)
        if cfg_retry > 0:
            retry_sec = cfg_retry

        def _resolve():
            try:
                got = client.call(op="servers")
                return got["uris"] if got.get("ready") else None
            except Exception:
                return None

        ps = PSClient(s["uris"], sender=f"worker-{env.rank}",
                      retry_deadline=retry_sec,
                      resolver=_resolve if retry_sec > 0 else None)
        learner.track_touched = hasattr(learner, "collect_touched")
        plane = _pick_plane(env)
        plane_cls = HotPlane if plane == "hot" else SyncedStore
        synced = plane_cls(
            _store(learner), ps,
            max_delay=getattr(cfg, "max_delay", 16),
            fixed_bytes=getattr(cfg, "fixed_bytes", 0),
            derived=getattr(learner, "derived_tables", dict)(),
            touched_fn=getattr(learner, "collect_touched", None),
            compress=bool(getattr(cfg, "msg_compression", 0)))
        if env.rank == 0:
            import jax as _jax

            print(f"[ps-plane] {plane} (workers={env.num_workers}, "
                  f"local_devices={_jax.local_device_count()})", flush=True)
        synced.init()
    solver = MinibatchSolver(learner, cfg, verbose=False)
    if synced is not None:
        synced.perf = solver.perf
        solver.sync_flush = synced.flush
    result = {}
    last_train = None  # (nex, seconds) of the last train round (warm)
    last_round_wire = 0.0  # wire bytes/sync of that round alone
    while (rnd := pool.sync_round()) is not None:
        wtype = WorkType(rnd["type"])
        if synced is not None:
            # adopt the merged model at round start (val rounds then score
            # the shared model, not this worker's replica)
            synced.pull()
            if env.rank == 0 and hasattr(learner, "nnz"):
                # seed the scheduler's fresh round Progress with the
                # shared model's standing |w|_0 so its printed sparsity
                # column is cumulative like the single-process solver's
                # (every worker just pulled the same state; one reporter
                # avoids N-fold overcounting)
                client.report({"new_w": float(learner.nnz())})
        t_rnd = time.perf_counter()
        if synced is not None and wtype == WorkType.TRAIN:
            rnd_b0 = synced.client.bytes_push + synced.client.bytes_pull
            rnd_s0 = synced.num_syncs
        prog = _drain_round(solver, learner, pool, wtype, rnd["data_pass"],
                            synced)
        if wtype == WorkType.TRAIN:
            last_train = (prog.value("nex"), time.perf_counter() - t_rnd)
            if synced is not None:
                # last TRAIN round's wire volume in isolation: epoch 2+
                # is where the key cache ships digest-only frames, and
                # a whole-run average would hide that behind epoch 1's
                # full key sends (the bench's >=25% saving check)
                db = (synced.client.bytes_push + synced.client.bytes_pull
                      - rnd_b0)
                ds = max(synced.num_syncs - rnd_s0, 1)
                last_round_wire = db / ds
        result["train" if wtype == WorkType.TRAIN else "val"] = prog
    if synced is not None:
        synced.close()  # drain + stop the async comms thread
    if pool.retire:
        # retired by the membership controller: every contribution is
        # merged (each train part ends in a flush), so resign cleanly —
        # the scheduler drops us from liveness NOW, re-queues nothing
        # (we hold no part), and bumps the membership epoch for the
        # survivors. Tail work (predict) belongs to workers that stay.
        print(f"[worker-{env.rank}] retiring (membership controller)",
              flush=True)
        pool.leave()
        return result
    if synced is not None and last_train is not None:
        # machine-readable wire accounting (the sparse-PS bench parses
        # this; wire bytes/sync is the measured sparse-wire claim)
        import json as _json

        stats = dict(synced.wire_stats(), rank=env.rank,
                     last_round_nex=last_train[0],
                     last_round_sec=round(last_train[1], 3),
                     last_round_bytes_per_sync=round(last_round_wire, 1))
        if synced.perf is not None:
            # per-class wall sums so the PS bench can attribute the
            # dist-vs-single gap (push wire+merge / pull / loader wait /
            # device step) instead of guessing (VERDICT r4 weak #1)
            sums, cnts = synced.perf.snapshot()
            stats["perf_sec"] = {k: round(v, 3) for k, v in sums.items()}
            stats["perf_cnt"] = cnts
        print(f"[ps-wire] {_json.dumps(stats)}", flush=True)
    if synced is None:
        if cfg.model_out and env.rank == 0:
            # replica mode: single writer (rank 0) saves its full model
            ckpt.save_model(_store(learner), cfg.model_out)
    if getattr(cfg, "predict_out", None):
        # the last round-end sync already pulled the merged model; the
        # servers may have shut down by now, so predict on that state
        # (staleness <= one other worker's final part)
        solver.predict(cfg.val_data or cfg.train_data,
                       f"{cfg.predict_out}_rank-{env.rank}")
    return result


def _pick_plane(env) -> str:
    """Resolve WH_PS_PLANE. `hot` keeps the model device-resident
    (sharded over the local mesh, aggregation in-jit) and demotes the
    TCP servers to a flush-barrier cold tier — valid only when ALL
    data-parallel workers share this process's device mesh. `auto`
    picks hot exactly in that regime (one worker process, >= 2 local
    devices) and the TCP plane everywhere else."""
    plane = (os.environ.get("WH_PS_PLANE") or "auto").lower()
    if plane not in ("auto", "tcp", "hot"):
        raise ValueError(
            f"WH_PS_PLANE={plane!r}: expected auto, tcp, or hot")
    if plane == "tcp":
        return "tcp"
    import jax

    if plane == "hot":
        if env.num_workers > 1:
            raise RuntimeError(
                "WH_PS_PLANE=hot requires all data-parallel workers in "
                f"one process (job has -n {env.num_workers}): the hot "
                "plane's tables are sharded over the LOCAL device mesh, "
                "and separate worker processes would each train a "
                "private copy. Use -n 1 (the local mesh is the data "
                "parallelism) or WH_PS_PLANE=tcp.")
        return "hot"
    return ("hot" if env.num_workers == 1 and jax.local_device_count() >= 2
            else "tcp")


def _store(learner):
    return getattr(learner, "ckpt_store", None) or learner.store


def _drain_round(solver, learner, pool: RemotePool, wtype, data_pass,
                 synced=None):
    """Worker side of one dispatch round: pull parts until the round is
    globally done, stream minibatches through the learner, report summed
    progress per part (the finish RPC carries it, replacing the timed
    ps::Slave channel). Training state syncs against the server group
    every max_delay minibatches and always before a part's finish RPC —
    so when the scheduler sees the round finished, every contribution is
    already merged on the servers."""
    from wormhole_tpu.data.minibatch import MinibatchIter

    cfg = solver.cfg
    prog = Progress()
    train = wtype == WorkType.TRAIN
    step = learner.train_batch if train else learner.eval_batch
    span_name = "solver.train_step" if train else "solver.eval_step"
    absorb = getattr(synced, "absorb_membership", None)
    while (got := pool.get()) is not None:
        part_id, f = got
        part_prog: dict = {}
        for blk in MinibatchIter(
            f.filename, f.part, f.num_parts, f.format,
            minibatch_size=cfg.minibatch,
            shuf_buf=(cfg.rand_shuffle * cfg.minibatch if train else 0),
            neg_sampling=(cfg.neg_sampling if train else 1.0),
            seed=data_pass * 7919 + part_id,
        ):
            with _trace.span(span_name, cat="solver"):
                p = step(blk)
            for k, v in p.items():
                part_prog[k] = part_prog.get(k, 0.0) + float(v)
            if train and synced is not None:
                synced.maybe_sync()
        if train and synced is not None:
            # barrier, not plain sync: with async sync on there may
            # be a round-trip still in flight — the finish RPC's
            # contract is "every contribution already merged"
            synced.flush()
        prog.merge(part_prog)
        pool.finish(part_id, part_prog)
        if absorb is not None and pool.mepoch:
            # membership epoch bump observed on the control plane (a
            # peer joined/left/was evicted): fence + re-handshake the
            # PS plane at the part boundary — cheap when nothing
            # changed (absorb_membership no-ops on seen epochs)
            absorb(pool.mepoch)
    return prog


def app_main(cls, make_learner, argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    cfg = parse_cli(cls, argv)
    run_minibatch_app(cfg, make_learner)
    return 0
