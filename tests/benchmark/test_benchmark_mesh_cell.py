"""The four-chip cell `criteo1tb-2p30.replay-8`, rehearsed where there is
no chip: a whole run under the forced host devices (eight here, so
`make_mesh(num_model=4)` gives mesh 2 x 4; on the chip 1 x 4), kind
`mcoo`, the reference agreeing and the window served from the pack
cache; the control (bfloat16 tables) over the new configuration's
limits; and the same rehearsal with one visible device, where the app
clamps `model_shards` to 1 and the run must come out NOT correct: the
clamp cannot pass for the deployment.

A rehearsal names the platform it ran on (`cpu`); nothing here is a speed.
"""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "criteo1tb-2p30.replay-8"
CONFIG = "linear-ftrl-criteo1tb-2p30"


def _env(tmp, devices: int):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"))
    env.pop("XLA_FLAGS", None)
    if devices > 1:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}")
    return env


def _rehearse(tmp, devices: int):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "rehearse.py"),
         "--workload", CELL, "--seed", "2147483693", "--seconds", "2"],
        capture_output=True, text=True, timeout=900,
        env=_env(tmp, devices), cwd=REPO)


@pytest.fixture(scope="module")
def mesh_run(tmp_path_factory):
    r = _rehearse(tmp_path_factory.mktemp("mesh"), 8)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return r.stdout


def test_the_cell_is_one_four_chip_entry_of_files_that_exist():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config=CONFIG, traffic="replay-8", chips=4)
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        CELL]
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["train_rows"]
    assert "criteo_kaggle.rst" in entry["source"]
    assert "criteo-terabyte.json" in entry["source"]
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == ["shard_pull_ms", "shard_push_ms",
                    "shard_kernel_ms_per_step", "shard_kernels_roofline",
                    "collective_ms", "hot_shard_ratio"]
    # the cell reports the three end-to-end metrics its bounds are for
    e2e = [m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])]
    assert e2e == ["train_ex_per_s", "val_logloss", "setup_s"]
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "replay-8.json")) as fh:
        mix = json.load(fh)
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "replay.json")) as fh:
        accepted = json.load(fh)
    differ = {k for k in mix if mix[k] != accepted[k]}
    # (`trace_seconds` since PR 41: the accepted mix traces 7 s to hold a
    # whole pass of its longer cell; a pass of this one is a quarter second)
    assert differ == {"name", "what", "train_parts", "batches_per_part",
                      "trace_seconds"}
    assert mix["train_parts"] * mix["batches_per_part"] == 8
    # 8 packed batches of [1, 4, 18,055,168] x (idx, seg, val) fit the
    # budget; the accepted mix's 32 would not
    batch_mb = 4 * 18_055_168 * 12 / 2**20
    assert 8 * batch_mb < int(mix["env"]["WH_PACK_CACHE_MB"]) < 32 * batch_mb


def test_rehearsal_on_the_forced_devices_is_correct_and_sharded(mesh_run):
    out = json.loads(mesh_run.splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"train_ex_per_s", "val_logloss",
                                   "setup_s"}
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 8
    assert "mesh=2x4 (data x model) path=pallas" in mesh_run
    assert "clamping" not in mesh_run
    lines = [ln for ln in mesh_run.splitlines() if "correct:" in ln]
    assert any("staged batch kinds ['mcoo']  (expected 'mcoo')" in x
               for x in lines)
    assert any("compilations inside the window = 0" in x for x in lines)
    for name in ("loss_gap", "grad_norm_gap", "delta_norm_gap",
                 "state_off_share", "served_loss_gap", "served_delta_gap",
                 "served_off_share"):
        (ln,) = [x for x in lines if f" {name} = " in x]
        assert "(limit" in ln and ln.rstrip().endswith("ok"), ln


def test_the_window_is_served_from_the_pack_cache(mesh_run):
    """The window run's first pass (set-up) packs its 8 batches into a
    new cache, one miss a part for the part's count entry; no later
    pass misses, and the step followed after the window came from a
    later pass: from the cache."""
    stats = [(int(h), int(m)) for h, m in re.findall(
        r"pack cache: (\d+) hits / (\d+) misses", mesh_run)]
    # the fixed pass's own cache: 4 train parts + 2 val parts
    assert stats[:3] == [(0, 4), (0, 6), (0, 4)], stats
    # the window run's later passes, as many as ended inside the window:
    # 3 hits a part (the count entry and two batches), no miss
    assert [m for _, m in stats[2:]] == [4] * len(stats[2:])
    assert [h for h, _ in stats[2:]] == [12 * k for k in range(
        len(stats[2:]))]
    (ln,) = [x for x in mesh_run.splitlines() if "served step: batch" in x]
    assert int(re.search(r"pass (\d+) of the window run", ln).group(1)) >= 1


def test_the_control_fails_the_new_configurations_limits(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "control.py"),
         "--config", CONFIG, "--seeds", "41,42,43", "--rehearsal", "1"],
        capture_output=True, text=True, timeout=600, env=_env(tmp_path, 1),
        cwd=REPO)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert r.stdout.count("correct=False") == 3
    for name in ("state_off_share", "served_off_share"):
        lines = [x for x in r.stdout.splitlines() if f" {name} = " in x]
        assert len(lines) == 3 and all("OVER" in x for x in lines), name


def test_with_one_device_the_clamp_does_not_pass_for_the_deployment(
        tmp_path):
    """One visible device: the app clamps `model_shards` 4 -> 1 with its
    printed line, the learner compacts (`tcoo`), the mathematics still
    agrees with the reference, and the run is not correct because the
    staged kinds are not the configuration's."""
    r = _rehearse(tmp_path, 1)
    assert r.returncode == 1, r.stdout[-3000:] + r.stderr[-3000:]
    out = json.loads(r.stdout.splitlines()[-1])
    assert out["correct"] is False and out["device"]["count"] == 1
    assert "[linear] model_shards=4 > 1 devices; clamping to 1" in r.stdout
    assert "mesh=1x1" in r.stdout
    (ln,) = [x for x in r.stdout.splitlines() if "staged batch kinds" in x]
    assert "['tcoo']  (expected 'mcoo')" in ln
    for name in ("loss_gap", "state_off_share", "served_off_share"):
        (ln,) = [x for x in r.stdout.splitlines() if f" {name} = " in x]
        assert ln.rstrip().endswith("ok"), ln
