"""lbfgs linear.dmlc: batch logistic/linear regression trained by
distributed L-BFGS/OWL-QN (reference learn/lbfgs-linear/lbfgs.cc).
Rabit-style key=value args:

  python -m wormhole_tpu.apps.lbfgs_linear data=train.libsvm \
      reg_L1=1 max_lbfgs_iter=30 model_out=model.npz \
      task=train|pred [test_data=... pred_out=...]
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Optional

import numpy as np

from wormhole_tpu.apps._runner import parse_cli
from wormhole_tpu.models.batch_objectives import (
    LinearObjFunction, load_batches,
)
from wormhole_tpu.parallel.mesh import make_mesh
from wormhole_tpu.solver.lbfgs import LBFGSConfig, LBFGSSolver


@dataclasses.dataclass
class LbfgsLinearConfig:
    """Key surface of the reference lbfgs.cc SetParam loop (:236-241):
    reg_L1, max_lbfgs_iter, lbfgs_stop_tol, max_linesearch_iter,
    model_in/out, task."""

    data: str = ""
    test_data: Optional[str] = None
    data_format: str = "libsvm"
    task: str = "train"         # train | pred  (lbfgs.cc:55-69)
    model_in: Optional[str] = None
    model_out: Optional[str] = None
    pred_out: str = "pred.txt"
    reg_L1: float = 0.0
    reg_L2: float = 0.0
    max_lbfgs_iter: int = 30
    lbfgs_stop_tol: float = 1e-7
    # backtracking trials an iteration before the job gives up: from
    # w = 0 the first step along -g has to shrink by about 1 / rows
    # (22 halvings at a million rows)
    max_linesearch_iter: int = 20
    m: int = 10
    minibatch: int = 4096
    nnz_per_row: int = 64
    num_parts_per_file: int = 1
    # 0 discovers the dimension as the reference does (max id + 1,
    # lbfgs.cc:107-113); over 0 it is the dimension, and column ids are
    # folded `mod num_feature` (64-bit keys, e.g. the Criteo format's)
    num_feature: int = 0
    # multi-process SPMD over one jax.distributed mesh: the weight vector
    # and history shard over every process's devices (the reference's
    # rank partition, lbfgs.h:127-136) and all dot products ride the
    # mesh collectives
    global_mesh: bool = False
    # multi-process BSP over the native allreduce ring
    # (runtime/allreduce.py): parameters replicated per rank, data
    # partitioned, gradient/loss reduced over the ring — the reference's
    # rabit layout, fault-tolerant via version checkpoints
    bsp: bool = False


def _solver_config(cfg) -> LBFGSConfig:
    return LBFGSConfig(
        max_iter=cfg.max_lbfgs_iter, m=cfg.m, reg_l1=cfg.reg_L1,
        reg_l2=cfg.reg_L2, min_rel_decrease=cfg.lbfgs_stop_tol,
        max_linesearch=cfg.max_linesearch_iter)


def _state_path(obj) -> None:
    """The start-up statement of which lowering the passes take and,
    where it is the `segment_sum` fall-back, why: on stderr, so that
    what `main` prints is the same on every path."""
    print(obj.placement, file=sys.stderr, flush=True)


def make_solver(cfg, mesh=None):
    """The single-process training job of `cfg`, ready to run: its rows
    resident on the device, the objective over them and the solver.
    Returns (solver, obj, batches, num_feature). `main` runs the job
    through this, and so does anything that drives it from outside (the
    benchmark's batch driver steps `solver.run(on_iter=...)`)."""
    mesh = make_mesh() if mesh is None else mesh
    batches, num_feature = load_batches(
        cfg.data, mesh, cfg.data_format, cfg.minibatch, cfg.nnz_per_row,
        cfg.num_parts_per_file, cfg.num_feature)
    obj = LinearObjFunction(batches, num_feature, mesh)
    _state_path(obj)
    return (LBFGSSolver(obj, _solver_config(cfg)), obj, batches,
            num_feature)


def _global_worker_body(cfg, env, client) -> int:
    import jax

    from wormhole_tpu.models.batch_objectives import load_batches_global
    from wormhole_tpu.parallel import multihost as mh
    from wormhole_tpu.parallel.mesh import replicated

    rank = env.rank
    mesh = make_mesh()
    batches, num_feature = load_batches_global(
        cfg.data, mesh, env, cfg.data_format, cfg.minibatch,
        cfg.nnz_per_row, cfg.num_parts_per_file)
    obj = LinearObjFunction(batches, num_feature, mesh)
    if rank == 0:
        _state_path(obj)
    solver = LBFGSSolver(obj, _solver_config(cfg))
    # every rank drives the identical host loop on identical global
    # scalars, so all jitted collectives stay in lockstep
    w, objv = solver.run(verbose=(rank == 0))
    if cfg.model_out:
        # the replication all-gather is a COLLECTIVE: every rank must run
        # it, then only rank 0 writes the file
        full = jax.jit(lambda x: x, out_shardings=replicated(mesh))(w)
        w_host = mh.fetch_replicated(full)
        if rank == 0:
            np.savez(cfg.model_out, w=w_host, num_feature=num_feature)
            print(f"saved model to {cfg.model_out}", flush=True)
    if rank == 0:
        print(f"final objective: {objv:.6f}", flush=True)
    return 0


def _bsp_worker_body(cfg, env, client, comm) -> int:
    """Distributed L-BFGS over the native BSP allreduce ring: this rank
    loads its part slice, the solver reduces the two data-dependent
    quantities (gradient, raw loss) over the ring, and every iteration
    ends in a version checkpoint — a killed worker respawns, reloads
    (w, g, history, S, Y), and replays the collectives it missed from
    peers' result caches."""
    from wormhole_tpu.models.batch_objectives import load_batches_bsp

    assert cfg.task == "train", "bsp supports task=train"
    rank = env.rank
    mesh = make_mesh()
    batches, num_feature = load_batches_bsp(
        cfg.data, mesh, env, client, cfg.data_format, cfg.minibatch,
        cfg.nnz_per_row, cfg.num_parts_per_file)
    obj = LinearObjFunction(batches, num_feature, mesh)
    if rank == 0:
        _state_path(obj)
    solver = LBFGSSolver(obj, _solver_config(cfg), comm=comm)
    # every rank drives the identical host loop on identical reduced
    # scalars; w is replicated, so rank 0 alone saves it
    w, objv = solver.run(verbose=(rank == 0))
    if rank == 0:
        if cfg.model_out:
            np.savez(cfg.model_out, w=np.asarray(w),
                     num_feature=num_feature)
            print(f"saved model to {cfg.model_out}", flush=True)
        print(f"final objective: {objv:.6f}", flush=True)
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    cfg = parse_cli(LbfgsLinearConfig, argv)
    from wormhole_tpu.apps._runner import maybe_run_bsp, maybe_run_global

    rc = maybe_run_bsp(cfg, _bsp_worker_body)
    if rc is not None:
        return rc

    def body(cfg, env, client):
        assert cfg.task == "train", "global_mesh supports task=train"
        return _global_worker_body(cfg, env, client)

    rc = maybe_run_global(cfg, body)
    if rc is not None:
        return rc
    mesh = make_mesh()
    if cfg.task == "pred":
        # the reference's TaskPred: load binf model, write one margin per
        # example (lbfgs.cc:70-85)
        assert cfg.model_in, "pred task needs model_in"
        if not cfg.model_in.endswith(".npz"):
            cfg.model_in += ".npz"
        st = np.load(cfg.model_in)
        w = st["w"]
        # the saved vector may carry sharding padding past the bias;
        # num_feature is recorded at save time (old files fall back to
        # the unpadded len - 1 layout)
        nf = int(st["num_feature"]) if "num_feature" in st else len(w) - 1
        batches, _ = load_batches(
            cfg.test_data or cfg.data, mesh, cfg.data_format,
            cfg.minibatch, cfg.nnz_per_row, cfg.num_parts_per_file)
        obj = LinearObjFunction(batches, nf, mesh)
        wp = obj.place(np.asarray(w[: nf + 1], np.float32))
        n = 0
        with open(cfg.pred_out, "w") as f:
            for seg, idx, val, label, mask in batches:
                margins = np.asarray(
                    obj.predict(wp, seg, idx, val, cfg.minibatch))
                keep = np.asarray(mask) > 0
                for m in margins[keep]:
                    f.write(f"{m:.6g}\n")
                n += int(keep.sum())
        print(f"wrote {n} predictions to {cfg.pred_out}")
        return 0

    solver, _, _, num_feature = make_solver(cfg, mesh)
    w, objv = solver.run()
    print(f"final objective: {objv:.6f}")
    if cfg.model_out:
        np.savez(cfg.model_out, w=np.asarray(w), num_feature=num_feature)
        print(f"saved model to {cfg.model_out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
