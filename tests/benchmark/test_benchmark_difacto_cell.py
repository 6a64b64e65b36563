"""The cell `difacto1tb.replay` (PR 31): the compact FM step at the
published row width rehearses to `correct` true against its own plain
reference, and a run that hands V, nV back unchanged, and the control
(tables in bfloat16), come out not correct. The kernels' counts by hand
for one shape. Nothing here is a speed."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

CELL, CONFIG = "difacto1tb.replay", "difacto-criteo1tb"


def _env(tmp):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"))
    env.pop("XLA_FLAGS", None)  # one device, like the chip
    return env


def _config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           CONFIG + ".json")) as fh:
        return json.load(fh)


def test_the_configuration_runs_the_source_s_shapes():
    cfg = _config()
    conf, src = cfg["conf"], cfg["equals_source"]
    assert (conf["dim"], conf["minibatch"], conf["threshold"]) == (
        50, 100000, 100) == (src["dim"], src["minibatch"], src["threshold"])
    assert conf["lr_eta"] == src["lr_eta"] == 0.01
    # the L2 on V is the source's (BASELINE.md:20), not the code's default
    assert conf["lambda_V"] == src["lambda_V"] == cfg["hyper"]["lambda_V"] \
        == 1 and "lambda_V" not in cfg["assumed"]
    assert conf["num_buckets"] == 1 << 28 and conf["v_buckets"] == 1 << 23
    assert set(cfg["reduced"]) == {"train_rows", "num_parts_per_file",
                                   "num_buckets", "v_buckets"}
    assert "NOT MEASURED" in cfg["reduced"]["v_buckets"]
    assert cfg["expect_kind"] == "fm"
    # the rehearsal changes sizes, never the width, the threshold or a rate
    assert set(cfg["rehearsal"]["conf"]) == {
        "num_buckets", "v_buckets", "minibatch", "kernel"}
    with open(os.path.join(REPO, "benchmark", "reference",
                           cfg["reference"] + ".py")) as fh:
        assert "wormhole_tpu" not in "".join(
            ln for ln in fh if ln.startswith(("import ", "from ")))


def test_the_cell_rehearses_to_correct_on_the_compact_path(tmp_path):
    r = subprocess.run(
        [sys.executable, "benchmark/rehearse.py", "--workload", CELL,
         "--seed", "2147483801", "--seconds", "1"], cwd=REPO,
        env=_env(tmp_path), capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = json.loads(r.stdout.splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"train_ex_per_s", "val_logloss", "setup_s"}
    lines = [ln for ln in r.stdout.splitlines() if "correct:" in ln]
    for name in ("loss_gap", "grad_norm_gap", "grad_off_share",
                 "delta_norm_gap", "state_off_share", "served_loss_gap",
                 "served_delta_gap", "served_off_share"):
        (ln,) = [x for x in lines if f" {name} = " in x]
        assert "(limit" in ln and ln.rstrip().endswith("ok"), ln
    assert set(out["compared"]) == {
        "loss_gap", "grad_norm_gap", "grad_off_share", "delta_norm_gap",
        "state_off_share", "served_loss_gap", "served_delta_gap",
        "served_off_share", "window_compiles", "val_logloss"}
    (ln,) = [x for x in lines if "reference: 3 steps" in x]
    assert "touched vrows of 65536" in ln
    assert any("staged batch kinds ['fm']  (expected 'fm')" in x
               for x in lines)
    assert any("compilations inside the window = 0" in x for x in lines)
    # the start-up line names the compact path and no blocker
    (ln,) = [x for x in r.stdout.splitlines() if "fixed pass:" in x]
    assert "path=pallas" in ln and "dim" not in ln.split("path=")[1]
    assert "compaction overflow" not in r.stdout + r.stderr


_FROZEN = """
import json
import sys

from benchmark import run
from wormhole_tpu.apps import difacto as app

make = app.make_learner


def broken(cfg, env):
    learner = make(cfg, env)
    real = learner.train_batch

    def frozen(b):
        import jax.numpy as jnp
        keep = {k: jnp.array(v) for k, v in learner.vstore.state.items()}
        out = real(b)
        learner.vstore.state = keep    # V, nV handed back unchanged
        return out

    learner.train_batch = frozen
    return learner


app.make_learner = broken              # the program underneath, not run.py
bench = run.load_json(run.ROOT, "BENCHMARK.json")
out = run.run_cell(bench, "difacto1tb.replay", 11, 1.0, False,
                   rehearsal=True)
print(json.dumps(out))
"""


def test_vector_rows_handed_back_unchanged_fail_delta_norm_gap(tmp_path):
    r = subprocess.run([sys.executable, "-c", _FROZEN], cwd=REPO,
                       env=_env(tmp_path), capture_output=True, text=True,
                       timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = json.loads(r.stdout.splitlines()[-1])
    assert out["correct"] is False
    for name in ("delta_norm_gap", "served_delta_gap"):
        (ln,) = [x for x in r.stdout.splitlines()
                 if f"correct: {name} = " in x]
        assert ln.rstrip().endswith("OVER"), ln
    assert out["compared"]["delta_norm_gap"][0] > 0.5    # V never moved


def test_the_control_fails_the_cells_limits(tmp_path):
    """All six tables kept in bfloat16 between steps, V drawn by the
    reference from the seed: every seed has to come out not correct."""
    r = subprocess.run(
        [sys.executable, "benchmark/control.py", "--config", CONFIG,
         "--seeds", "21,22,23", "--rehearsal", "1"], cwd=REPO,
        env=_env(tmp_path), capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert r.stdout.count("correct=False") == 3
    for name in ("state_off_share", "served_off_share"):
        lines = [x for x in r.stdout.splitlines() if f" {name} = " in x]
        assert len(lines) == 3 and all("OVER" in x for x in lines), name


def test_vector_row_kernel_counts_by_hand_for_one_shape():
    from benchmark.kernels import (fm_push_contrib, fused_update_cnt,
                                   row_gather, tile_gather_cnt, v_update)

    batch = {"rows": 1000, "nnz": 39000, "uniq": 3000.0,
             "distinct": {"bucket": 3000.0, "vrow": 2500.0},
             "hyper": {"dim": 50}}
    assert row_gather.cost(batch) == {
        "bytes": 2500 * (4 + 2 * (200 + 200)), "flops": 0.0}
    assert v_update.cost(batch) == {
        "bytes": 2500 * (4 + 5 * 200), "flops": 8.0 * 2500 * 50}
    assert fm_push_contrib.cost(batch) == {
        "bytes": 39000 * 12 + 1000 * 4 * 51 + 3000 * 400,
        "flops": 100.0 * (39000 + 3000)}
    assert tile_gather_cnt.cost(batch) == {"bytes": 3000 * 12, "flops": 0.0}
    assert fused_update_cnt.cost(batch) == {
        "bytes": 3000 * (12 + 16 + 16), "flops": 21.0 * 3000}
    # the published width, not the stored stride of 64
    wide = dict(batch, hyper={"dim": 64})
    assert row_gather.cost(wide)["bytes"] > row_gather.cost(batch)["bytes"]


def test_the_new_layer_metrics_read_the_difacto_counters():
    """`admitted_nnz_share` and `fm_dropped_nnz_share` through the
    accepted reducer; a program without the counters (the parent) gives
    None, and the metric is left out of the line."""
    from benchmark.reducers import counter_ratio
    from wormhole_tpu.obs.metrics import REGISTRY

    def spec(name):
        with open(os.path.join(REPO, "benchmark", "layer_metrics",
                               name + ".json")) as fh:
            return json.load(fh)

    import wormhole_tpu.models.difacto  # noqa: F401  (registers them)

    live = REGISTRY.counter("difacto.step.live_nnz")
    adm = REGISTRY.counter("difacto.step.admitted_nnz")
    nnz = REGISTRY.counter("difacto.pack.nnz")
    l0, a0 = live.value(), adm.value()
    live.inc(1000)
    adm.inc(250)
    nnz.inc(1)
    got = counter_ratio.read({}, **spec("admitted_nnz_share")["params"])
    assert got == pytest.approx((a0 + 250) / (l0 + 1000))
    got = counter_ratio.read({}, **spec("fm_dropped_nnz_share")["params"])
    assert got is not None and got >= 0.0
    assert counter_ratio.read({}, num="no.such.counter",
                              den="difacto.pack.nnz") is None


# device operations of a traced run of the cell on a TPU v5 lite (my chip
# run, PR 31), as the trace names them: the whole HLO instruction
_OPS = {
    "gather_V": "%fusion.10 = f32[495616,128]{1,0:T(8,128)} fusion(f32[4194304,128]{1,0:T(8,128)} %vstate__V__.1, s32[495616]{0:T(1024)S(1)} %custom-call.26), kind=kCustom, calls=%fused_computation.clone",
    "gather_nV": "%fusion.13 = f32[495616,128]{1,0:T(8,128)} fusion(f32[4194304,128]{1,0:T(8,128)} %vstate__nV__.1, s32[495616]{0:T(1024)S(1)} %custom-call.26), kind=kCustom, calls=%fused_computation.3.clone",
    "scatter_V": "%fusion.8 = f32[4194304,128]{1,0:T(8,128)} fusion(f32[4194304,128]{1,0:T(8,128)} %vstate__V__.1, s32[495616]{0:T(1024)} %get-tuple-element.54, pred[495616,128]{1,0:T(8,128)(4,1)S(1)} %copy.17, f32[495616,128]{1,0:T(8,128)} %reshape.54), kind=kCustom, calls=%fused_computation.8",
    "scatter_nV": "%fusion.9 = f32[4194304,128]{1,0:T(8,128)} fusion(f32[4194304,128]{1,0:T(8,128)} %vstate__nV__.1, s32[495616]{0:T(1024)} %get-tuple-element.54, pred[495616,128]{1,0:T(8,128)(4,1)S(1)} %copy.17, f32[495616,128]{1,0:T(8,128)} %fusion.11), kind=kCustom, calls=%fused_computation.9",
    "push": "%fm_push_contrib.1 = f32[991232,65]{1,0:T(8,128)} custom-call(s32[5744]{0:T(1024)S(1)} %copy-done.31), custom_call_target=\"tpu_custom_call\"",
    "w_update": "%fused_update.1 = (f32[2097152,128]{1,0:T(8,128)}, f32[8,128]{1,0:T(8,128)S(1)}) custom-call(s32[6144]{0:T(1024)S(1)} %copy-done.35), custom_call_target=\"tpu_custom_call\"",
    "key_rows": "%fusion.5 = bf16[516096,64]{1,0:T(8,128)(2,1)} fusion(bf16[991233,64]{1,0:T(8,128)(2,1)} %pad.4, s32[516096]{0:T(1024)S(1)} %copy-done.7), kind=kCustom, calls=%fused_computation.5",
}


@pytest.mark.parametrize("metric,ops,ms", [
    ("row_gather_ms", ("gather_V", "gather_nV"), 2.0 + 3.0),
    ("v_update_ms", ("scatter_V", "scatter_nV"), 5.0 + 7.0),
    ("fm_push_contrib_ms", ("push",), 11.0),
    ("fm_kernel_ms_per_step", ("gather_V", "gather_nV", "scatter_V",
                               "scatter_nV", "push"), 28.0),
])
def test_the_vector_row_metrics_find_their_device_operations(metric, ops,
                                                             ms):
    """The by-line gather and the update are XLA's own operations: the
    patterns find them by the table they read (the step's `vstate`
    argument) and by their operands, each among the others."""
    from benchmark.reducers import kernel_ms_per_step

    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           metric + ".json")) as fh:
        spec = json.load(fh)
    assert spec["reducer"] == "kernel_ms_per_step"
    steps = 4
    times = dict(zip(_OPS, (2.0, 3.0, 5.0, 7.0, 11.0, 13.0, 17.0)))
    ctx = {"trace_steps": steps, "trace": {"ops": {
        _OPS[k]: [steps, 1e-3 * steps * t] for k, t in times.items()}}}
    assert kernel_ms_per_step.read(ctx, **spec["params"]) == \
        pytest.approx(ms)
    # a program without these operations (the parent): nothing to read
    none = {"trace_steps": steps, "trace": {"ops": {
        _OPS["w_update"]: [steps, 1.0], _OPS["key_rows"]: [steps, 1.0]}}}
    assert kernel_ms_per_step.read(none, **spec["params"]) is None


def test_the_vector_row_roofline_counts_its_own_kernels():
    from benchmark.kernels import fm_push_contrib, row_gather, v_update
    from benchmark.reducers import kernel_roofline_share_of

    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           "fm_kernels_roofline.json")) as fh:
        spec = json.load(fh)
    assert spec["reducer"] == "kernel_roofline_share_of"
    batch = {"rows": 1000, "nnz": 39000, "uniq": 3000.0,
             "distinct": {"bucket": 3000.0, "vrow": 2500.0},
             "hyper": {"dim": 50}}
    peaks = {"bytes_per_s": 8e11, "flops_per_s": 2e14}
    steps = 4
    ctx = {"trace_steps": steps, "peaks": peaks, "batch": batch,
           "kernels": [], "trace": {"ops": {
               op: [steps, 1e-3 * steps] for op in _OPS.values()}}}
    least = sum(max(m.cost(batch)["bytes"] / 8e11,
                    m.cost(batch)["flops"] / 2e14)
                for m in (row_gather, fm_push_contrib, v_update))
    # five matched operations of 1 ms a step each
    assert kernel_roofline_share_of.read(ctx, **spec["params"]) == \
        pytest.approx(100.0 * least / 5e-3)
    assert kernel_roofline_share_of.read(
        dict(ctx, trace={"ops": {}}), **spec["params"]) is None


def test_the_configuration_bounds_the_staged_queue():
    """A queued batch is a staged one and holds device memory: the
    solver's queue takes its bound from the configuration
    (`max_queued`), the new cell's conf sets it to four, and a conf that
    says nothing keeps eight."""
    from wormhole_tpu.models.difacto import DifactoConfig
    from wormhole_tpu.models.linear import LinearConfig
    from wormhole_tpu.solver.minibatch_solver import MinibatchSolver

    conf = _config()["conf"]
    assert conf["max_queued"] == 4 and "max_queued" in _config()["assumed"]
    cfg = DifactoConfig(max_queued=conf["max_queued"])
    assert MinibatchSolver(None, cfg, verbose=False).max_queued == 4
    assert MinibatchSolver(None, LinearConfig(),
                           verbose=False).max_queued == 8
