"""Batch L-BFGS / OWL-QN solver, TPU-native.

Parity target: reference learn/solver/lbfgs.h — vector-free L-BFGS with
backtracking line search and OWL-QN L1 handling: the weight vector and its
2m+1 history basis are partitioned across ranks (lbfgs.h:127-136,557-645),
global quantities are reconstructed from allreduced dot products
(:235-303), the line search evaluates the objective via allreduce per
trial (:321-356), and rabit checkpoints make iterations elastic
(:120,194).

TPU design: one process drives the whole mesh, so "partitioned across
ranks" becomes sharding the flat weight/history arrays over all devices
(models/batch_objectives.py pads num_dim to an even split). Each
iteration fetches ONE Gram matrix of the [S..., Y..., pg] basis — local
partial dots + an XLA psum, the same math as the reference's single
Allreduce<Sum> of the 5n dot-product vector (lbfgs.h:235-252) — then
runs the two-loop recursion on (2m+1)-sized host vectors and forms the
direction as one device linear combination. The objective accumulates
over device-resident data batches sharded on the data axis. Host Python
drives the outer iteration and the data-dependent line search (a host
loop of jitted evals, the analog of the reference's rank-coordinated
trials).

OWL-QN specifics (lbfgs.h:358-407): pseudo-gradient at w=0, direction
sign-fix against the pseudo-gradient, and orthant projection of each
line-search trial point.

What an iteration's time is made of is under spans (obs/names.py,
`lbfgs.*`): a pass over the rows is dispatched without waiting and ends
in a blocking read, so `lbfgs.grad_pass` and `lbfgs.obj_pass` each close
on the read that waits for their device work, and `lbfgs.combine` on the
read of pg . d where OWL-QN makes one. Nothing waits for a span's sake:
the job's first gradient, at its starting point, is under none (the first
objective pass's read waits for it). With no tracer and no profiler
session a span is the shared no-op.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Callable, Optional, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from wormhole_tpu.obs import trace as _trace
from wormhole_tpu.obs.metrics import REGISTRY

_ITERS = REGISTRY.counter("lbfgs.iters")
_PASSES = REGISTRY.counter("lbfgs.passes")
_TRIALS = REGISTRY.counter("lbfgs.linesearch_trials")
_HOST_SYNCS = REGISTRY.counter("lbfgs.host_syncs")

# elements of each basis vector a step of the Gram matrix and of the
# combine take, where a device holds the vectors whole
_CHUNK = 1 << 22

# .solver = the LBFGSSolver whose run() this thread is inside, so that a
# read the objective makes on its behalf lands in its `host_syncs` too
_RUNNING = threading.local()


def fetch(x, read=float):
    """One blocking device-to-host read (`read`: float for a scalar,
    np.asarray for the Gram matrix), counted and under `lbfgs.fetch`:
    the one way the solver and the batch objectives read the device, so
    that `lbfgs.host_syncs` counts every read a pass makes."""
    _HOST_SYNCS.inc()
    solver = getattr(_RUNNING, "solver", None)
    if solver is not None:
        solver.host_syncs += 1
    with _trace.span("lbfgs.fetch", cat="lbfgs"):
        return read(x)


class ObjFunction(Protocol):
    """The IObjFunction surface (reference lbfgs.h:23-52)."""

    num_dim: int

    def init_model(self) -> jax.Array: ...
    def eval(self, w: jax.Array) -> float: ...          # sum loss over data
    def grad(self, w: jax.Array) -> jax.Array: ...
    def l1_mask(self) -> jax.Array: ...  # 1 where L1 applies (not bias/V)


@dataclasses.dataclass
class LBFGSConfig:
    max_iter: int = 30
    m: int = 10                 # history pairs
    reg_l1: float = 0.0         # OWL-QN when > 0
    reg_l2: float = 0.0
    c1: float = 1e-4            # sufficient-decrease constant
    rho: float = 0.5            # backtracking factor
    alpha0: float = 1.0
    max_linesearch: int = 20
    min_rel_decrease: float = 1e-7  # convergence: relative objv decrease
    checkpoint_dir: Optional[str] = None


class LBFGSSolver:
    """Host-driven L-BFGS over device-sharded vectors.

    With `comm` set (a runtime/allreduce.py BspWorker), the solver runs
    the reference's distributed layout: parameters and history are
    REPLICATED per rank, data is partitioned, and the two data-dependent
    quantities — the gradient and the raw objective — allreduce over the
    worker ring (lbfgs.h:235-303,321-356). Every other scalar (Gram
    matrix, dots, line-search decisions) is computed from those reduced,
    bit-identical-across-ranks values, so all ranks drive the identical
    host loop in lockstep. Checkpoints ride the ring's version protocol
    (rabit CheckPoint parity): the state includes g and the objective
    history so a resumed worker SKIPS the init grad/eval recompute —
    which is what keeps its per-version collective counters aligned with
    the survivors'."""

    def __init__(self, obj: ObjFunction, cfg: LBFGSConfig, comm=None):
        self.obj = obj
        self.cfg = cfg
        self.comm = comm
        self.S: list[jax.Array] = []   # s_k = w_{k+1} - w_k
        self.Y: list[jax.Array] = []   # y_k = g_{k+1} - g_k
        self.iter = 0
        self.objv_history: list[float] = []

        l2 = cfg.reg_l2

        @jax.jit
        def full_obj(w, raw_loss):
            o = raw_loss + 0.5 * l2 * jnp.vdot(w, w)
            if cfg.reg_l1 > 0:
                o = o + cfg.reg_l1 * jnp.sum(
                    jnp.abs(w) * self.obj.l1_mask())
            return o

        @jax.jit
        def pseudo_gradient(w, g):
            """OWL-QN pseudo-gradient of reg_l1*|w| at w (SetL1Dir parity,
            lbfgs.h:358-378): at w=0 the subgradient closest to zero."""
            g = g + l2 * w
            if cfg.reg_l1 <= 0:
                return g
            m_ = self.obj.l1_mask()
            l1 = cfg.reg_l1
            gp = g + l1 * m_
            gm = g - l1 * m_
            pg_zero = jnp.where(gm > 0, gm, jnp.where(gp < 0, gp, 0.0))
            return jnp.where(
                (w == 0) & (m_ > 0), pg_zero,
                g + l1 * jnp.sign(w) * m_)

        @jax.jit
        def fix_dir_sign(d, pg):
            """Restrict direction to the descent orthant
            (FixDirL1Sign, lbfgs.h:380-389)."""
            return jnp.where(d * -pg > 0, d, 0.0) if cfg.reg_l1 > 0 else d

        @jax.jit
        def orthant_project(w_new, orthant):
            """Clip the trial point to the chosen orthant
            (FixWeightL1Sign, lbfgs.h:391-407)."""
            if cfg.reg_l1 <= 0:
                return w_new
            keep = w_new * orthant >= 0
            m_ = self.obj.l1_mask()
            return jnp.where(keep | (m_ == 0), w_new, 0.0)

        hi = jax.lax.Precision.HIGHEST  # float32 products on the MXU too
        # a device that holds the vectors whole (one-device mesh) works
        # through them _CHUNK elements at a time: the stacked basis is
        # then a temporary of (2m + 1) x _CHUNK, not a second copy of
        # 2m + 1 vectors (at 2^26 columns that copy does not fit a chip).
        # Vectors no longer than a chunk are the one chunk. Sharded
        # vectors are stacked whole, a shard's part a device.
        whole = getattr(getattr(obj, "mesh", None), "size", 1) == 1

        def pieces(vs, start, size):
            return jnp.stack([jax.lax.dynamic_slice(v, (start,), (size,))
                              for v in vs])

        @jax.jit
        def gram(*vs):
            """B Bᵀ for the stacked basis [S..., Y..., pg]: every dot
            product the two-loop recursion needs, in ONE device program /
            ONE host fetch (the reference's single Allreduce<Sum> of the
            5n dot-product vector, lbfgs.h:235-252)."""
            def block(B):
                return jnp.dot(B, B.T, precision=hi)

            if not whole:
                return block(jnp.stack(vs))
            n, k = vs[0].shape[0], len(vs)
            c = min(_CHUNK, n)
            G = jax.lax.fori_loop(
                0, n // c, lambda i, G: G + block(pieces(vs, i * c, c)),
                jnp.zeros((k, k), jnp.float32))
            return G + block(pieces(vs, n - n % c, n % c)) if n % c else G

        @jax.jit
        def combine(coef, *vs):
            def block(B):
                return jnp.einsum("i,in->n", coef, B, precision=hi)

            if not whole:
                return block(jnp.stack(vs))
            n = vs[0].shape[0]
            c = min(_CHUNK, n)
            d = jax.lax.fori_loop(
                0, n // c, lambda i, d: jax.lax.dynamic_update_slice(
                    d, block(pieces(vs, i * c, c)), (i * c,)),
                jnp.zeros((n,), jnp.float32))
            if n % c:
                d = jax.lax.dynamic_update_slice(
                    d, block(pieces(vs, n - n % c, n % c)), (n - n % c,))
            return d

        self._gram = gram
        self._combine = combine
        self._full_obj = full_obj
        self._pseudo_gradient = pseudo_gradient
        self._fix_dir_sign = fix_dir_sign
        self._orthant_project = orthant_project
        # host-sync counter: every device->host scalar/array fetch made
        # inside run(), the objective's one a pass included (the quantity
        # the reference minimizes by batching dots into one allreduce;
        # tests assert the fused path stays lean). The registry's
        # `lbfgs.host_syncs` is the same count over every solver.
        self.host_syncs = 0

    def reset(self) -> None:
        """Forget the job: the next run() starts from w = 0 with no
        history, on the programs this solver has already compiled."""
        self.S.clear()
        self.Y.clear()
        self.iter = 0
        self.objv_history.clear()

    # -- two-loop recursion in basis coordinates (lbfgs.h:216-318) ----------
    def _direction(self, pg: jax.Array):
        """Returns (d, pg . d): the search direction, restricted to the
        descent orthant where OWL-QN is on. It is computed vector-free:
        one Gram matrix of the [S..., Y..., pg] basis comes back to the
        host (ONE sync per iteration instead of ~4m), the two-loop
        recursion runs on (2m+1)-sized host vectors, and the result is a
        single device linear combination of the basis. pg . d falls out
        of the same Gram matrix (d = sum coef_i B_i) except where the
        sign fix altered d: it is then read, and `lbfgs.combine` closes
        on that read, which waits for the combination."""
        l1 = self.cfg.reg_l1 > 0
        if not self.S:
            d = self._fix_dir_sign(-pg, pg)
            return d, fetch(jnp.vdot(pg, d))
        k = len(self.S)
        basis = self.S + self.Y + [pg]
        with _trace.span("lbfgs.gram", cat="lbfgs", vectors=len(basis)):
            G = fetch(self._gram(*basis), np.asarray)
        with _trace.span("lbfgs.two_loop", cat="lbfgs"):
            coef = np.zeros(2 * k + 1)
            coef[2 * k] = -1.0  # q = -pg
            alphas = np.zeros(k)
            rhos = np.zeros(k)
            for i in range(k - 1, -1, -1):
                rhos[i] = 1.0 / G[i, k + i]            # 1 / (s_i . y_i)
                alphas[i] = rhos[i] * float(G[i] @ coef)   # rho (s_i . q)
                coef[k + i] -= alphas[i]               # q -= a y_i
            gamma = G[k - 1, 2 * k - 1] / G[2 * k - 1, 2 * k - 1]
            coef *= gamma
            for i in range(k):
                b = rhos[i] * float(G[k + i] @ coef)   # rho (y_i . q)
                coef[i] += alphas[i] - b               # q += (a - b) s_i
        with _trace.span("lbfgs.combine", cat="lbfgs"):
            d = self._fix_dir_sign(
                self._combine(jnp.asarray(coef, jnp.float32), *basis), pg)
            gd = fetch(jnp.vdot(pg, d)) if l1 else float(G[2 * k] @ coef)
        return d, gd

    # -- one iteration (UpdateOneIter, lbfgs.h:168-196) ----------------------
    def _eval_full(self, w) -> float:
        """Full objective at w: one pass over the rows, from its launch
        to the read that waits for it. The RAW data loss reduces over
        the ring BEFORE regularization: the reg terms are functions of
        the replicated w and must be added exactly once, not `world`
        times (the reference reduces sum_loss the same way,
        lbfgs.h:321-340)."""
        _PASSES.inc()
        with _trace.span("lbfgs.obj_pass", cat="lbfgs"):
            raw = self.obj.eval(w)
            if self.comm is not None:
                raw = np.float32(self.comm.allreduce(np.float32(raw)))
            return fetch(self._full_obj(w, raw))

    def _grad(self, w):
        """Gradient of the data loss, dispatched and not waited for:
        local accumulation, then one ring allreduce, re-placed under the
        objective's sharding (the single Allreduce<Sum> per iteration of
        lbfgs.h:194). The caller's `lbfgs.grad_pass` span closes on the
        read that waits for it."""
        _PASSES.inc()
        g = self.obj.grad(w)
        if self.comm is not None:
            g = np.asarray(self.comm.allreduce(np.asarray(g)))
            place = getattr(self.obj, "place", None)
            g = place(jnp.asarray(g, jnp.float32)) if place else (
                jnp.asarray(g, jnp.float32))
        return g

    def run(self, verbose: bool = True,
            on_iter: Optional[Callable] = None) -> tuple[jax.Array, float]:
        """The job from where the solver stands: from w = 0 (or the
        checkpoint) until the stop rule, `max_iter`, a failed line search
        or `on_iter` ends it. `on_iter(iter, objv, trials, state)` is
        called at each iteration's end, outside its span, with the
        iteration's number, its objective, the line search's trial count
        and the state a checkpoint holds as it lies on the device (`w`,
        `g`, `S`, `Y`, `objv`: the history); a true return ends the run
        there (as `gbdt.fit_prepared` has `on_round`). Call `reset()`
        before running another job on the same solver."""
        outer = getattr(_RUNNING, "solver", None)
        _RUNNING.solver = self
        try:
            return self._run(verbose, on_iter)
        finally:
            _RUNNING.solver = outer

    def _run(self, verbose, on_iter):
        cfg = self.cfg
        w, g, objv = self._try_resume()
        resumed = w is not None
        if not resumed:
            w = self.obj.init_model()
        # a full (comm/new-format) checkpoint carries g and the
        # objective history, so the resumed run skips both recomputes —
        # required in BSP mode for counter alignment, a free speedup
        # otherwise. Old file checkpoints (no g) just recompute.
        if g is None:
            # not waited for and under no span: the first objective
            # pass's read, next, waits for both
            g = self._grad(w)
        if objv is None:
            objv = self._eval_full(w)
        if not resumed:  # resumed history already ends with this objv
            self.objv_history.append(objv)
        if verbose:
            print(f"lbfgs {'resume' if resumed else 'init'}: "
                  f"objv {objv:.6f}", flush=True)

        while self.iter < cfg.max_iter:
            # convergence is judged from the (checkpointed) history at
            # the loop TOP, so a worker that died after the final
            # checkpoint resumes, observes the same convergence fact the
            # survivors did, and exits instead of ringing alone
            if len(self.objv_history) >= 2:
                prev, cur = self.objv_history[-2], self.objv_history[-1]
                rel = (prev - cur) / max(abs(prev), 1e-12)
                if 0 <= rel < cfg.min_rel_decrease:
                    if verbose:
                        print("lbfgs: converged", flush=True)
                    break
            with _trace.span("lbfgs.iter", cat="lbfgs",
                             iter=self.iter + 1) as sp:
                step = self._iterate(w, g, objv)
                sp.set(trials=step[-1])
            if step[0] is None:
                if verbose:
                    print("lbfgs: line search failed, stopping", flush=True)
                break
            w, g, objv, alpha, trials = step
            self.iter += 1
            _ITERS.inc()
            self.objv_history.append(objv)
            if verbose:
                print(f"lbfgs iter {self.iter}: objv {objv:.6f} "
                      f"alpha {alpha:.3g}", flush=True)
            self._checkpoint(w, g)
            if on_iter is not None and on_iter(
                    self.iter, objv, trials,
                    dict(w=w, g=g, S=list(self.S), Y=list(self.Y),
                         objv=list(self.objv_history))):
                break
        return w, objv

    def _iterate(self, w, g, objv):
        """One iteration from (w, g, objv): (w, g, objv, alpha, trials)
        after it, the first three None where the line search failed."""
        cfg = self.cfg
        pg = self._pseudo_gradient(w, g)
        with _trace.span("lbfgs.direction", cat="lbfgs"):
            d, gd = self._direction(pg)

        # orthant for this step: sign(w), or -sign(pg) where w == 0
        orthant = jnp.where(w != 0, jnp.sign(w), -jnp.sign(pg))

        # backtracking line search (lbfgs.h:321-356)
        if gd >= 0:  # not a descent direction: reset history
            self.S.clear()
            self.Y.clear()
            d = -pg
            gd = fetch(jnp.vdot(pg, d))
        alpha = cfg.alpha0
        w_new, trials = None, 0
        with _trace.span("lbfgs.linesearch", cat="lbfgs") as sp:
            for _ in range(cfg.max_linesearch):
                trial = self._orthant_project(w + alpha * d, orthant)
                trials += 1
                o = self._eval_full(trial)
                if o <= objv + cfg.c1 * alpha * gd:
                    w_new, objv_new = trial, o
                    break
                alpha *= cfg.rho
            _TRIALS.inc(trials)
            sp.set(trials=trials)
        if w_new is None:
            return None, None, None, alpha, trials

        # the pass ends in the read of s.y, which waits for it
        with _trace.span("lbfgs.grad_pass", cat="lbfgs"):
            g_new = self._grad(w_new)
            s = w_new - w
            y = (g_new + cfg.reg_l2 * w_new) - (g + cfg.reg_l2 * w)
            sy = fetch(jnp.vdot(s, y))
        if sy > 1e-10:
            self.S.append(s)
            self.Y.append(y)
            if len(self.S) > cfg.m:
                self.S.pop(0)
                self.Y.pop(0)
        return w_new, g_new, objv_new, alpha, trials

    # -- elastic state (rabit CheckPoint parity, lbfgs.h:120,194) -----------
    def _state(self, w, g) -> dict:
        dim = getattr(self.obj, "num_dim_padded", self.obj.num_dim)
        return dict(
            w=np.asarray(w),
            g=np.asarray(g),
            iter=np.int64(self.iter),
            objv=np.asarray(self.objv_history, dtype=np.float64),
            S=np.stack([np.asarray(s) for s in self.S])
            if self.S else np.zeros((0, dim)),
            Y=np.stack([np.asarray(y) for y in self.Y])
            if self.Y else np.zeros((0, dim)),
        )

    def _checkpoint(self, w, g) -> None:
        if self.comm is not None:
            # version-stamped ring checkpoint: bumps (version, seq) on
            # every rank in lockstep and persists under the launcher's
            # snapshot dir for the respawned incarnation
            self.comm.checkpoint(self._state(w, g))
            return
        cdir = self.cfg.checkpoint_dir
        if not cdir:
            return
        from wormhole_tpu.utils.checkpoint import atomic_savez

        os.makedirs(cdir, exist_ok=True)
        atomic_savez(os.path.join(cdir, "lbfgs_state.npz"),
                     **self._state(w, g))

    def _restore_vec(self, v):
        """Re-place a checkpointed vector under the CURRENT objective:
        strip any old sharding padding (padding is provably zero) and
        let place() re-pad and shard for this mesh, so a checkpoint
        moves between device counts and resumed state keeps the
        non-replicated sharding."""
        v = np.asarray(v)[: self.obj.num_dim]
        place = getattr(self.obj, "place", None)
        return place(jnp.asarray(v, jnp.float32)) if place else (
            jnp.asarray(v, jnp.float32))

    def _try_resume(self):
        """Returns (w, g, objv) — g/objv None when the checkpoint
        predates them (old file format) and must be recomputed."""
        if self.comm is not None:
            st = self.comm.load_checkpoint()
        else:
            cdir = self.cfg.checkpoint_dir
            if not cdir:
                return None, None, None
            path = os.path.join(cdir, "lbfgs_state.npz")
            if not os.path.exists(path):
                return None, None, None
            st = dict(np.load(path))
        if st is None:
            return None, None, None
        self.iter = int(st["iter"])
        self.objv_history = list(st["objv"])
        self.S = [self._restore_vec(s) for s in st["S"]]
        self.Y = [self._restore_vec(y) for y in st["Y"]]
        g = self._restore_vec(st["g"]) if "g" in st else None
        objv = self.objv_history[-1] if (
            "g" in st and self.objv_history) else None
        return self._restore_vec(st["w"]), g, objv
