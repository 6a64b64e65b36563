"""`tcoo_pull_ms` (PR 32): the layer metric that says the compact step's
pull is the `coo_pull` kernel, as a file the harness can read, and the
cell it moves rehearsed through the step that now pulls with it. Nothing
here is a speed."""

import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
CONFIG = "linear-ftrl-criteo1tb"


def _json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def test_the_metric_s_file_loads_and_lists_the_compact_cells_only():
    bench = _json(REPO, "BENCHMARK.json")
    spec = _json(BENCH, "layer_metrics", "tcoo_pull_ms.json")
    # found by its name: entries that later PRs add go after it
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "tcoo_pull_ms"]
    assert spec["name"] == "tcoo_pull_ms"
    assert bench["per_layer"][-1]["name"] != "tcoo_pull_ms"
    for key in ("layer", "unit", "better", "source", "moves"):
        assert entry[key] == spec[key], key
    assert (spec["layer"], spec["moves"], spec["better"]) == (
        "kernels", "train_ex_per_s", "lower")
    assert os.path.isfile(os.path.join(BENCH, "reducers",
                                       spec["reducer"] + ".py"))
    # the cells whose step is the compact one: those of a configuration
    # whose kind is tcoo (by rule since PR 46: the one-chip linear
    # configuration's, and a later one's of that kind), and no other
    cells = {w["name"]: w for w in bench["workloads"]}
    kinds = {c["name"]: _json(REPO, c["file"])["expect_kind"]
             for c in bench["configs"]}
    (twin,) = [m for m in bench["per_layer"]
               if m["name"] == "tcoo_pull_ms.stream"]
    assert _json(BENCH, "layer_metrics", "tcoo_pull_ms.stream.json")[
        "params"] == spec["params"]
    # (the stream cell reads it under the twin's name since PR 43)
    listed = set(entry["workloads"]) | set(twin["workloads"])
    assert {"criteo1tb.crb-stream", "criteo1tb.replay"} <= listed
    assert all(kinds[cells[n]["config"]] == "tcoo" for n in listed)
    assert kinds[CONFIG] == "tcoo"
    assert cells["criteo1tb.replay"]["config"] == CONFIG
    # the pull and nothing else: the op as a device trace names it, not
    # the mesh cell's metric of the same kernel's push, nor a fusion
    rx = re.compile(spec["params"]["pattern"])
    assert rx.search("%coo_pull.1 = f32[512,128]{1,0} custom-call(")
    assert rx.search("ROOT %coo_pull = f32[512,128]{1,0} custom-call(")
    for other in ("%coo_push.1 = ", "%tile_gather.1 = ", "%fusion = ",
                  "%fused_update.1 = ", "%get-tuple-element = (%coo_pull.1"):
        assert not rx.search(other), other
    # a trace without the op (the parent's) leaves the metric out
    sys.path.insert(0, REPO)
    from benchmark.reducers import kernel_ms_per_step as reducer
    ctx = {"trace": {"ops": {"%fusion = f32[2555904,2] fusion(": (66, 1.0),
                             "%coo_push.1 = f32[] custom-call(": (66, .5)}},
           "trace_steps": 66}
    assert reducer.read(ctx, **spec["params"]) is None
    ctx["trace"]["ops"]["%coo_pull.1 = f32[512,128] custom-call("] = (66, .33)
    assert reducer.read(ctx, **spec["params"]) == 5.0


def test_replay_rehearses_to_correct_with_the_compact_kind(tmp_path):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)  # one device, like the chip
    r = subprocess.run(
        [sys.executable, "benchmark/rehearse.py", "--workload",
         "criteo1tb.replay", "--seed", "2147485201", "--seconds", "2"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = json.loads(r.stdout.splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert "batch_gap_p95_ms" in out["metrics"]
    lines = [ln for ln in r.stdout.splitlines() if "correct:" in ln]
    assert any("staged batch kinds ['tcoo']" in x for x in lines)
    for name in ("loss_gap", "grad_norm_gap", "delta_norm_gap",
                 "state_off_share", "served_loss_gap", "served_delta_gap",
                 "served_off_share"):
        (ln,) = [x for x in lines if f" {name} = " in x]
        assert ln.rstrip().endswith("ok"), ln
