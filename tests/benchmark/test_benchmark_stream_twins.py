"""PR 43, after the check's refusal: `criteo1tb.crb-stream`'s rate is too
unsteady for the largest bound (PERF.md section 2), so the cell reports
`val_logloss` and `setup_s` end to end, its rate as the layer metric
`stream_ex_per_s`, and each layer metric it shares with the replay cells
under a twin's name, `<name>.stream`."""

import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
STREAM = "criteo1tb.crb-stream"


def _json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


BENCHMARK = _json(REPO, "BENCHMARK.json")
TWINS = [m["name"] for m in BENCHMARK["per_layer"]
         if m["name"].endswith(".stream")]


def test_the_stream_cell_s_end_to_end_metrics_are_the_steady_two():
    from benchmark import run

    names = [m["name"] for m in run.metrics_of(BENCHMARK, "end_to_end",
                                               STREAM)]
    assert names == ["val_logloss", "setup_s"]
    (rate,) = [m for m in BENCHMARK["end_to_end"]
               if m["name"] == "train_ex_per_s"]
    # by rule, not by count (PR 46): the stream cell is out, the three
    # replay cells PR 43 left are in, and a cell that a later PR adds
    # appends its name like any other entry of `workloads`
    assert STREAM not in rate["workloads"]
    assert rate["workloads"][:3] == [
        "criteo1tb.replay", "criteo1tb-2p30.replay-8", "difacto1tb.replay"]
    assert set(rate["workloads"]) <= {w["name"]
                                      for w in BENCHMARK["workloads"]}
    assert 0.01 <= rate["bound"] <= 0.1


def test_the_stream_cell_s_layer_metrics_name_a_metric_it_reports():
    from benchmark import run

    mine = run.metrics_of(BENCHMARK, "per_layer", STREAM)
    assert {m["moves"] for m in mine} == {"val_logloss", "setup_s"}
    names = {m["name"] for m in mine}
    assert "stream_ex_per_s" in names and len(TWINS) == 23
    assert set(TWINS) < names
    # what the cell read before (ledger, PR 41: 28 layer metrics), and its
    # rate: under the same name where the metric was the cell's alone
    assert len(names) == 29
    assert {"pack_wall_ms", "pack_cpu_ms", "parse_ms_per_batch",
            "parse_cpu_ms_per_batch", "compile_s"} < names


@pytest.mark.parametrize("twin", TWINS)
def test_a_twin_reads_what_its_original_reads(twin):
    """The same reducer on the same parameters, the same layer, unit and
    source: only the name, the cells and the arrow differ."""
    entries = {m["name"]: m for m in BENCHMARK["per_layer"]}
    name = twin[:-len(".stream")]
    a = _json(BENCH, "layer_metrics", name + ".json")
    b = _json(BENCH, "layer_metrics", twin + ".json")
    for key in ("layer", "unit", "better", "source", "reducer"):
        assert a[key] == b[key], key
    assert a.get("params", {}) == b.get("params", {})
    assert (a["moves"], b["moves"]) == ("train_ex_per_s", "val_logloss")
    assert entries[twin]["workloads"] == [STREAM]
    assert STREAM not in entries[name]["workloads"]
    for key in ("layer", "unit", "better", "source"):
        assert entries[twin][key] == entries[name][key], key


def test_the_rate_as_a_layer_metric_is_the_window_s_own_arithmetic():
    from benchmark.reducers import end_to_end

    spec = _json(BENCH, "layer_metrics", "stream_ex_per_s.json")
    assert spec["reducer"] == "end_to_end"
    assert spec["params"] == {"metric": "train_ex_per_s"}
    ctx = {"end_to_end": {"train_ex_per_s": 951234.5, "setup_s": 23.0}}
    assert end_to_end.read(ctx, **spec["params"]) == 951234.5
    # nothing to read: no number, never 0
    assert end_to_end.read({}, **spec["params"]) is None
    assert end_to_end.read({"end_to_end": {"train_ex_per_s": 0.0}},
                           **spec["params"]) is None
