"""The job's half of a run: one module a kind of job, found by name.

`run.py` is the generic half: the command line, the cell's files, the look
for a chip, the traced run's per-layer metrics, the result line. What the
job *is* (how its data is made and loaded, what set-up and the window
drive, what an end-to-end number is made of, what `correct` compares)
belongs to a driver: the module the configuration's `driver` key names by
its dotted path (`benchmark.drivers.minibatch` where the key is absent).
A job that is not a minibatch-solver run becomes a cell by a new module
here and files, and no edit to `run.py`.

A driver gives `run.py`:

  measure(cell, config, conf, traffic, work, seed, seconds, plan, clog,
          warns) -> run
      Set-up and the window. `cell`, `traffic`: the cell's entry and its
      mix; `config`, `conf`: the configuration and its conf keys as
      `run.sized` gives them (the tiny ones in a rehearsal); `work`: a
      directory of the run's own, removed at its end; `plan`: None, or
      the traced run's `{"dir", "at", "seconds"}` (start the profiler
      into `dir` `at` seconds into the window, trace `seconds`, end the
      window with it); `clog`, `warns`: `tap.CompileLog` and
      `tap.WarningLog`, whose `phase` the driver sets to "window" when
      the window opens and to "after" when it closes. Data and weights
      come from `seed`; nothing compiles inside the window.
  result(run, seconds, warns, traffic) -> dict
      The result line but for `correct` and the metrics: `attempted`,
      `failed`, `device.memory_peak_bytes`, read when the window has
      closed and before any reference runs. It prints the `[bench]
      window:` lines, and exits where the window is not one to report.
  end_to_end(run) -> {name: value}
      Every end-to-end metric of BENCHMARK.json that the driver's cells
      list, by name; `setup_s` runs from `T_START`.
  correct(config, run, clog) -> (ok, lines, compared)
      The comparison with the plain reference, after the window; a line
      for each number, and `{name: [value, limit]}`.
  batch(conf, config, run) -> dict
      What a kernel's `cost(batch)` may read (README, "kernel count").

and on `run` what `run.per_layer` reads in a traced run: `t_open`,
`step_s`, `hist_open`, `hist_close`, `t_hist_close`, `trace_t0`,
`trace_t1`, `trace_steps`.

`T_START` is the process's start as `run.py` took it in its first
statement: `run.py` sets it on the driver's module when it loads it. A
driver imports nothing of `benchmark.run`; what both halves need is here.
"""

from __future__ import annotations

TAG = "[bench]"


def say(msg: str) -> None:
    print(f"{TAG} {msg}", flush=True)


def memory_peak_bytes() -> int:
    """Peak on the fullest chip (0 where the backend reports none)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
