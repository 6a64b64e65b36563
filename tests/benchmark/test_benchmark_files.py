"""BENCHMARK.json and the files it names: everything resolves, every name
keeps to the contract's characters, every arrow points at a metric the
cell reports, and a new cell is files plus one entry."""

import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


def test_top_level_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert bench["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10


def test_every_configuration_resolves_and_is_used(bench):
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("benchmark/configs/")
        assert c["file"] not in files
        files.add(c["file"])
        cfg = _load("configs", os.path.basename(c["file"]))
        assert cfg["name"] == c["name"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg["reduced"]
            assert not key.endswith(("_dim", "_rank"))
        # what the harness reads from a configuration
        for key in ("app", "config_class", "keys", "conf", "reference",
                    "hyper", "precision", "control_precision", "expect_kind",
                    "kernels", "rehearsal", "correct", "assumed"):
            assert key in cfg, key
        assert os.path.isfile(os.path.join(BENCH, "keys",
                                           cfg["keys"] + ".json"))
        # every conf key is the source's or is listed: none goes unsaid
        said = set(cfg["equals_source"]) | set(cfg["assumed"]) | set(
            cfg["reduced"])
        assert set(cfg["conf"]) <= said, set(cfg["conf"]) - said
        for k, v in cfg["equals_source"].items():
            if k in cfg["conf"]:
                assert cfg["conf"][k] == v, k
        for k, v in cfg["hyper"].items():
            assert float(cfg["conf"][k]) == v, k
        assert os.path.isfile(os.path.join(
            BENCH, "reference", cfg["reference"] + ".py"))
        for k in cfg["kernels"]:
            assert os.path.isfile(os.path.join(BENCH, "kernels", k + ".py"))
        # the four every configuration compares; `grad_off_share` where
        # a norm of the first gradient cannot tell sound from fault
        assert set(cfg["correct"]["limits"]) - {"grad_off_share"} == {
            "loss_gap", "grad_norm_gap", "delta_norm_gap", "state_off_share"}
        assert set(cfg["correct"]["served_limits"]) == {
            "served_loss_gap", "served_delta_gap", "served_off_share"}


def test_key_model_skew_follows_its_rule_from_the_published_counts():
    """benchmark/keys/criteo-terabyte.json: a field's skew is the largest
    exponent, never over the cap, for which the model's rarest value is
    still expected once in the set's rows; the list in the file is the
    rule's, recomputed here."""
    spec = _load("keys", "criteo-terabyte.json")
    rows, cap = spec["rows"], 1.2
    assert rows == 4373472329
    assert sum(spec["categorical_cardinalities"]) == 882774559
    assert len(spec["categorical_cardinalities"]) == 26
    assert len(spec["integer_cardinalities"]) == 13

    def p_last(v, s):
        a = 1.0 - s
        if abs(a) < 1e-9:
            return (math.log(v + 1) - math.log(v)) / math.log(v + 1)
        return ((v + 1.0) ** a - float(v) ** a) / ((v + 1.0) ** a - 1.0)

    for v, s in zip(spec["categorical_cardinalities"],
                    spec["categorical_skew"]):
        lo, hi = 0.0, 3.0
        for _ in range(60):
            mid = (lo + hi) / 2
            lo, hi = (mid, hi) if rows * p_last(v, mid) >= 1 else (lo, mid)
        assert s == pytest.approx(min(cap, lo), abs=6e-4), v


def test_every_cell_resolves(bench):
    configs = {c["name"] for c in bench["configs"]}
    seen = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        mix = _load("traffic", w["traffic"] + ".json")
        assert mix["name"] == w["traffic"]
        for key in ("data_format", "env", "train_parts", "batches_per_part",
                    "val_parts", "min_pass_rows", "window_passes",
                    "warmup_passes", "trace_seconds"):
            assert key in mix, key
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(len(bench["workloads"]) // 4, 1)


def test_metrics_names_units_bounds(bench):
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200


def test_every_cell_reports_setup_one_more_and_a_layer_metric(bench):
    for w in bench["workloads"]:
        def reported(key):
            return {m["name"] for m in bench[key]
                    if w["name"] in m.get("workloads", [w["name"]])}
        e2e = reported("end_to_end")
        assert "setup_s" in e2e and len(e2e) >= 2
        assert reported("per_layer")


def test_every_arrow_points_at_a_metric_the_cell_reports(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]], (
                f"{m['name']} moves {m['moves']}, which {cell} does not "
                "report")


def test_every_layer_metric_has_its_file_and_reducer(bench):
    for m in bench["per_layer"]:
        spec = _load("layer_metrics", m["name"] + ".json")
        for key in ("name", "layer", "unit", "moves", "source", "better"):
            assert spec[key] == m[key], (m["name"], key)
        path = os.path.join(BENCH, "reducers", spec["reducer"] + ".py")
        assert os.path.isfile(path), path


def test_files_under_paths_are_named_from_allowed_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root in bench["paths"]:
        for d, dirs, files in os.walk(os.path.join(REPO, root)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), REPO)
                assert ok.match(rel), rel


def test_peaks_table_is_keyed_by_device_kind():
    peaks = _load("peaks.json")
    v5e = peaks["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9
    assert "source" in v5e


def test_run_py_branches_on_no_cell_configuration_or_mix_name(bench):
    """The harness is driven by data: run.py may not name a cell, a
    configuration or a traffic mix."""
    src = open(os.path.join(BENCH, "run.py")).read()
    names = {w["name"] for w in bench["workloads"]}
    names |= {w["traffic"] for w in bench["workloads"]}
    names |= {c["name"] for c in bench["configs"]}
    names |= {"criteo1tb", "kaggle", "replay", "stream"}
    for n in names:
        assert n not in src, f"run.py names {n!r}"


def test_nothing_of_the_benchmark_imports_bench_py_smoke_or_tools():
    bad = re.compile(r"^\s*(from|import)\s+(bench|chip_smoke|tools)\b", re.M)
    ref_bad = re.compile(r"wormhole_tpu")
    for d, dirs, files in os.walk(BENCH):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            if not f.endswith(".py"):
                continue
            src = open(os.path.join(d, f)).read()
            assert not bad.search(src), os.path.join(d, f)
            if os.path.basename(d) == "reference":
                code = "\n".join(ln for ln in src.splitlines()
                                 if ln.lstrip().startswith(("import ",
                                                            "from ")))
                assert not ref_bad.search(code), f


_NEW_REDUCER = '''"""Mean rows a window step trained on (a counter read by a new file)."""


def read(ctx):
    return float(ctx["batch"]["rows"])
'''


def test_a_new_cell_is_new_files_and_one_entry_each(tmp_path, bench):
    """In a copy of the benchmark: a configuration that BENCHMARK.json
    does not list (with its key distribution and a kernel count of its
    own, from tests/benchmark/fixtures), a new traffic mix and a new layer
    metric with its own reducer become a cell by adding files and entries — no file that was
    there is edited — and the cell runs (tiny size, CPU, interpreted
    kernels; here the dense `coo` kernel set)."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
              if p.is_file()}
    fixtures = os.path.join(REPO, "tests", "benchmark", "fixtures")
    for f, to in (("linear-ftrl-kaggle.json", "configs"),
                  ("criteo-kaggle.json", "keys"), ("coo_pull.py", "kernels")):
        shutil.copy(os.path.join(fixtures, f), tmp_path / "benchmark" / to)
    mix = _load("traffic", "replay.json")
    mix.update(name="replay-small", train_parts=2, batches_per_part=3)
    (tmp_path / "benchmark/traffic/replay-small.json").write_text(
        json.dumps(mix))
    (tmp_path / "benchmark/reducers/rows_per_step.py").write_text(
        _NEW_REDUCER)
    (tmp_path / "benchmark/layer_metrics/rows_per_step.json").write_text(
        json.dumps({"name": "rows_per_step", "layer": "jitted step",
                    "unit": "rows", "better": "higher",
                    "source": "program_counter", "moves": "train_ex_per_s",
                    "reducer": "rows_per_step", "params": {}}))
    new = json.loads(json.dumps(bench))
    new["configs"].append({
        "name": "linear-ftrl-kaggle", "source": "criteo_kaggle.rst",
        "file": "benchmark/configs/linear-ftrl-kaggle.json",
        "reduced": ["train_rows"], "why": "dense coo kernel set"})
    new["workloads"].append({
        "name": "kaggle.replay-small", "config": "linear-ftrl-kaggle",
        "traffic": "replay-small", "chips": 1, "why": "test"})
    new["per_layer"].append({
        "name": "rows_per_step", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "jitted step",
        "moves": "train_ex_per_s", "workloads": ["kaggle.replay-small"]})
    # since PR 43 the rate lists its cells (the stream cell's is a layer
    # metric): a cell that reports it appends its name, as to any list
    # (which lists a cell appends to, and that the tests beside this one
    # then stay green: test_benchmark_takes_a_cell.py)
    (rate,) = [m for m in new["end_to_end"] if m["name"] == "train_ex_per_s"]
    rate["workloads"].append("kaggle.replay-small")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)  # one device, like the chip
    r = subprocess.run(
        [sys.executable, "benchmark/rehearse.py", "--workload",
         "kaggle.replay-small", "--seconds", "2"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = json.loads(r.stdout.splitlines()[-1])
    assert out["correct"] is True
    assert "kinds ['coo']" in r.stdout
    # the cell is outside batch_gap_p95_ms's `workloads`: three metrics
    assert set(out["metrics"]) == {"train_ex_per_s", "val_logloss",
                                   "setup_s"}
    # the new layer metric's file and reducer are found by name
    r = subprocess.run(
        [sys.executable, "-c",
         "from benchmark import run\n"
         "spec = run.load_json(run.HERE, 'layer_metrics', "
         "'rows_per_step.json')\n"
         "print(run.load_module('reducers', spec['reducer']).read("
         "{'batch': {'rows': 256}}))"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120)
    assert r.stdout.strip() == "256.0", r.stdout + r.stderr
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"
