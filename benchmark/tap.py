"""The probes the harness hangs on the program, from outside.

`Tap` is the learner as the solver sees it: it forwards everything and
notes each train and eval step. `CompileLog` counts XLA compilations by
phase through `jax.monitoring`; `WarningLog` collects what the program
warns about (dropped rows, compaction or shard overflow arrive as
warnings). Copied (PR 23) from `chip_smoke.py` and extended with the
measured window; nothing here imports it.
"""

from __future__ import annotations

import faulthandler
import logging
import sys
import time

from benchmark.check import batch_kind

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = COMPILE_EVENTS[2]
CACHE_HIT = "/jax/compilation_cache/cache_hits"
STAGE_HISTS = ("load", "pack", "h2d", "step", "metrics")
# a window step that takes longer than this has every thread's stack
# written to standard error while it hangs: what was the host doing?
STALL_DUMP_S = 1.0


class WindowClosed(Exception):
    """Raised by the tap from inside `train_batch` when the window is
    over; `MinibatchSolver.iterate`'s `finally` stops the loaders."""


class CompileLog:
    """Every jit compile request (persistent-cache hits included) emits
    one backend_compile duration; they are kept with the phase they fell
    in."""

    def __init__(self):
        self.phase = "setup"
        self.events: list[tuple[str, str, float]] = []
        self.cache_hits = 0

    def _on_duration(self, event: str, secs: float, **_):
        if event in COMPILE_EVENTS:
            self.events.append((self.phase, event, secs))

    def _on_event(self, event: str, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def __enter__(self):
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    def compiles(self, phase: str) -> int:
        return sum(1 for ph, ev, _ in self.events
                   if ev == BACKEND_COMPILE and ph == phase)


class WarningLog(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.messages: list[tuple[str, str]] = []
        self.phase = "setup"

    def emit(self, record):
        self.messages.append((self.phase, record.getMessage()))

    def count(self, phase: str) -> int:
        return sum(1 for ph, _ in self.messages if ph == phase)


def stage_hists() -> dict:
    """(count, sum) of the solver's own per-batch stage histograms
    (`train.stage.*_s`, solver/minibatch_solver.py)."""
    from wormhole_tpu.obs.metrics import REGISTRY

    out = {}
    for k in STAGE_HISTS:
        h = REGISTRY.histogram(f"train.stage.{k}_s")
        out[f"train.stage.{k}_s"] = (h.count, h.sum)
    return out


def hist_delta(h0: dict, h1: dict) -> dict:
    return {k: {"count": h1[k][0] - h0[k][0], "sum": h1[k][1] - h0[k][1]}
            for k in h0}


class Tap:
    """Forwards to the learner; in phase `fixed` it notes kinds, losses
    and the first steps; in phase `window` it keeps the measured window
    and, when asked, the profiler around a few seconds of it. When the
    window has closed it lets one more batch through, for the served-step
    check, and ends the run with `WindowClosed`."""

    def __init__(self, learner, clog: CompileLog, warns: WarningLog,
                 first=None, served=None):
        self._learner = learner
        self._clog, self._warns = clog, warns
        self.first = first
        self.served = served    # check.ServedStep: one step after the close
        self._closed = False
        self.phase = "fixed"
        self.kinds: set[str] = set()
        self.fixed_train: list[dict] = []   # per pass: losses, nex, objv
        self.fixed_val: list[dict] = []
        self._mode = None
        # the window
        self.seconds = 0.0
        self.warmup_passes = 0
        self.pass_no = -1
        # the window run's pass in which the window opened and the one in
        # which its last step ran: a mix that says its window lies in one
        # pass is held to it (run.py)
        self.pass_open = self.pass_close = None
        self.t_open = None
        self.ends: list[float] = []
        self.rows: list[float] = []
        self.step_s: list[float] = []
        # the solver observes a step's histograms after the step returns,
        # so what steps <= k left there is read on entry to step k + 1
        self._entry_hist = None
        self._want_hist_close = False
        self.hist_open = self.hist_close = None
        self.t_hist_close = None
        self.trace = None                  # dict(dir, at, seconds) or None
        self.trace_t0 = self.trace_t1 = None
        self.trace_steps = 0

    def __getattr__(self, name):
        return getattr(self._learner, name)

    # ---------------------------------------------------------------- phases
    def begin_window(self, seconds: float, warmup_passes: int,
                     trace=None) -> None:
        self.phase = "window"
        self.seconds, self.warmup_passes = float(seconds), warmup_passes
        self.pass_no = -1
        self.trace = trace
        self._clog.phase = self._warns.phase = "warmup"

    def on_pass_start(self):
        self.pass_no += 1
        self._mode = None
        hook = getattr(self._learner, "on_pass_start", None)
        if hook is not None:
            hook()

    # ----------------------------------------------------------------- steps
    def train_batch(self, b):
        if self._closed:
            # the window is over. The solver has by now observed its last
            # step; this batch, delivered the way the window's were, is
            # the one the served-step check follows
            if self.hist_close is None:
                self.hist_close = stage_hists()
            if self.served is not None:
                self.served.run(self._learner, b)
            raise WindowClosed()
        if self.t_open is not None:
            self._entry_hist = stage_hists()
            if self.hist_open is None:
                self.hist_open = self._entry_hist
            if self._want_hist_close and self.hist_close is None:
                self.hist_close = self._entry_hist
            faulthandler.dump_traceback_later(STALL_DUMP_S, file=sys.stderr)
        elif self.phase == "fixed" and self.first is not None:
            self.first.before_step(self._learner)
        try:
            if self.trace_t0 is not None and self.trace_t1 is None:
                import jax

                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.step"):
                    out = self._learner.train_batch(b)
            else:
                t0 = time.perf_counter()
                out = self._learner.train_batch(b)
        finally:
            faulthandler.cancel_dump_traceback_later()
        t1 = time.perf_counter()
        if self.phase == "fixed":
            self._note_fixed("train", self.fixed_train, b, out)
            if self.first is not None:
                self.first.after_step(self._learner, b, out)
        elif self.pass_no >= self.warmup_passes:
            self._note_window(b, out, t0, t1)
        return out

    def eval_batch(self, b):
        out = self._learner.eval_batch(b)
        if self.phase == "fixed":
            self._note_fixed("val", self.fixed_val, b, out)
        return out

    def _note_fixed(self, mode, passes, b, out):
        if self._mode != mode:
            self._mode = mode
            passes.append(dict(losses=[], nex=0.0, logloss=0.0))
        p = passes[-1]
        self.kinds.add(batch_kind(self._learner, b))
        p["nex"] += out["nex"]
        p["logloss"] += out["logloss"]
        p["losses"].append(out["logloss"] / max(out["nex"], 1.0))

    def _note_window(self, b, out, t0, t1):
        self.kinds.add(batch_kind(self._learner, b))
        if self.t_open is None:
            # the first step completed after warm-up opens the window
            self.t_open, self.pass_open = t1, self.pass_no
            self._clog.phase = self._warns.phase = "window"
            return
        self.ends.append(t1)
        self.rows.append(out["nex"])
        self.step_s.append(t1 - t0)
        if t1 - self.t_open >= self.seconds:
            # the step that crosses the deadline is the window's last: a
            # stall in it is inside the window like any other
            return self._close(t1)
        if self.trace is not None:
            self._trace_tick(t1)

    def _close(self, now):
        if self.trace_t0 is not None and self.trace_t1 is None:
            import jax

            self.trace_t1 = time.perf_counter()
            jax.profiler.stop_trace()
        if self.t_hist_close is None:
            self.t_hist_close = now     # the histograms: at the next entry
        self._clog.phase = self._warns.phase = "after"
        self._closed, self.pass_close = True, self.pass_no

    def _trace_tick(self, t1):
        """With --trace 1: the host-side layer metrics are taken from the
        window's start up to the moment the profiler starts, so that its
        cost is in none of them; the window ends with the trace."""
        import jax

        if self.trace_t0 is None:
            if t1 - self.t_open >= self.trace["at"]:
                self._want_hist_close, self.t_hist_close = True, t1
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(self.trace["dir"],
                                         profiler_options=opts)
                self.trace_t0 = time.perf_counter()
            return
        self.trace_steps += 1
        if t1 - self.trace_t0 >= self.trace["seconds"]:
            self._close(t1)
