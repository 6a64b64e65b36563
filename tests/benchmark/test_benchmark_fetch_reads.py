"""A step's progress reaches the host in one blocking read (PR 37): the
counters `step.fetch.reads` / `step.fetch.steps` as the accepted reducer
`counter_ratio` reads them, over the rehearsal of the linear cell the
gain is claimed in and of the vector-row fixture. The benchmark itself
gains no metric in this PR (an accepted test holds `per_layer`'s last
entry: PERF.md, Open questions). Nothing here is a speed."""

import json
import os
import subprocess
import sys

from test_benchmark_vector_rows import CELL as FM_CELL
from test_benchmark_vector_rows import copy  # noqa: F401  (a fixture)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PARAMS = {"num": "step.fetch.reads", "den": "step.fetch.steps"}

# a cell's rehearsal, then the ratio as a traced run's line would hold
# it: the counters stand as the run left them
REHEARSE_AND_READ = """
import json
import sys

from benchmark import run

bench = run.load_json(run.ROOT, "BENCHMARK.json")
out = run.run_cell(bench, sys.argv[1], int(sys.argv[2]), 2.0, False,
                   rehearsal=True)
out["layer"] = run.load_module("reducers", "counter_ratio").read(
    {}, **json.loads(sys.argv[3]))
print(json.dumps(out))
"""


def test_the_counters_are_registered_and_the_span_says_one_read():
    sys.path.insert(0, REPO)
    from wormhole_tpu.obs import names

    assert set(PARAMS.values()) <= set(names.COUNTERS)
    assert "one blocking read" in names.SPANS["step.fetch"]


def test_a_program_without_the_counters_gives_the_reducer_nothing():
    """The parent has no `step.fetch.*` counters: the reducer gives None
    there, so a line would leave the ratio out; with them it reads reads
    over steps."""
    sys.path.insert(0, REPO)
    from benchmark.reducers import counter_ratio
    from wormhole_tpu.obs.metrics import REGISTRY

    assert counter_ratio.read({}, num="step.fetch.reads.absent",
                              den="step.fetch.steps.absent") is None
    steps = REGISTRY.counter("step.fetch.steps")
    reads = REGISTRY.counter("step.fetch.reads")
    s0, r0 = steps.value(), reads.value()
    steps.inc(4)
    reads.inc(4)
    assert counter_ratio.read({}, **PARAMS) == (r0 + 4) / (s0 + 4)


def rehearse_and_read(cell, seed, cwd, tmp):
    """(the rehearsal's result object with the ratio under `layer`, its
    standard output) of `cell` in the benchmark under `cwd`."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"))
    env.pop("XLA_FLAGS", None)  # one device, like the chip
    r = subprocess.run(
        [sys.executable, "-c", REHEARSE_AND_READ, cell, str(seed),
         json.dumps(PARAMS)], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return json.loads(r.stdout.splitlines()[-1]), r.stdout


def test_replay_rehearses_to_correct_and_reads_one_read_a_step(tmp_path):
    out, said = rehearse_and_read("criteo1tb.replay", 2147487201, REPO,
                                  tmp_path)
    assert out["correct"] is True and out["failed"] == 0
    assert "staged batch kinds ['tcoo']" in said
    # every train and eval step of set-up, window and check: one read
    assert out["layer"] == 1.0


def test_the_fixture_s_steps_reach_the_host_in_one_read_each(copy):  # noqa: F811
    """The vector-row learner's XLA steps pack their progress too: the
    fixture's cell stays `correct` and the ratio reads 1.0 over every
    train and eval step of its rehearsal."""
    root, _ = copy
    out, said = rehearse_and_read(FM_CELL, 2147483837, root, root)
    assert out["correct"] is True and out["failed"] == 0
    assert "staged batch kinds ['xla_staged']" in said
    assert out["layer"] == 1.0
