"""PR 47: cell `lbfgs1tb.resident`, the batch solver's first deployment
(configuration `lbfgs-linear-criteo1tb`, driver `benchmark.drivers.batch`,
mix `resident`): the cell rehearsed through `run_cell` at the
configuration's `rehearsal` sizes, its kernel counts by hand, its two new
reducers on hand-made fragments, its twelve layer metrics' files, and the
control. Counts and control flow on the CPU; nothing here is a speed."""

import contextlib
import io
import json
import os
import re
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import check, control, gen, run, xplane  # noqa: E402
from benchmark.drivers import batch as driver  # noqa: E402
from benchmark.kernels import (lbfgs_gram, lbfgs_grad_pass,  # noqa: E402
                               lbfgs_obj_pass)
from benchmark.reducers import (_spans, counter_window_ratio,  # noqa: E402
                                span_device_roofline)

CELL, CONFIG = "lbfgs1tb.resident", "lbfgs-linear-criteo1tb"
SPAN_METRICS = {"lbfgs_iter_ms": "lbfgs.iter",
                "lbfgs_grad_pass_ms": "lbfgs.grad_pass",
                "lbfgs_obj_pass_ms": "lbfgs.obj_pass",
                "lbfgs_gram_ms": "lbfgs.gram",
                "lbfgs_combine_ms": "lbfgs.combine",
                "lbfgs_two_loop_host_ms": "lbfgs.two_loop",
                "lbfgs_fetch_ms": "lbfgs.fetch"}
COUNTER_METRICS = {"lbfgs_host_syncs_per_iter": "lbfgs.host_syncs",
                   "lbfgs_linesearch_trials_per_iter":
                   "lbfgs.linesearch_trials"}
ROOFLINES = {"lbfgs_grad_pass_roofline": "lbfgs.grad_pass",
             "lbfgs_obj_pass_roofline": "lbfgs.obj_pass",
             "lbfgs_gram_roofline": "lbfgs.gram"}
NEW = {**SPAN_METRICS, **COUNTER_METRICS, **ROOFLINES}


@pytest.fixture(scope="module")
def bench():
    return run.load_json(run.ROOT, "BENCHMARK.json")


@pytest.fixture(scope="module")
def config():
    return run.load_json(run.HERE, "configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def rehearsed(bench):
    """The cell once through `run_cell` at the rehearsal sizes: the
    result and what it printed."""
    said = io.StringIO()
    with contextlib.redirect_stdout(said), contextlib.redirect_stderr(
            io.StringIO()):
        out = run.run_cell(bench, CELL, 3000004707, 2.0, False,
                           rehearsal=True)
    return out, said.getvalue()


# ------------------------------------------------------------ the rehearsal
def test_the_rehearsal_is_correct_on_the_program_s_normal_path(rehearsed):
    out, said = rehearsed
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert "batch kinds ['resident']" in said
    for name in ("loss_gap", "grad_norm_gap", "grad_off_share",
                 "delta_norm_gap", "state_off_share", "served_loss_gap",
                 "served_delta_gap", "served_off_share"):
        value, limit = out["compared"][name]
        assert 0 <= value <= limit, name
    assert out["compared"]["first_iterations_trials_equal"] == [1, 1]
    assert out["compared"]["served_iteration_trials_equal"] == [1, 1]
    assert out["compared"]["window_compiles"] == [0, 0]


def test_the_cell_reports_the_three_end_to_end_metrics(rehearsed):
    out, _ = rehearsed
    assert set(out["metrics"]) == {"train_ex_per_s", "val_logloss",
                                   "setup_s"}
    assert 0 < out["metrics"]["val_logloss"]["value"] < 0.6931
    assert out["metrics"]["train_ex_per_s"]["value"] > 0


WINDOW = (r"window: (\d+) job\(s\), (\d+) iterations, (\d+) passes "
          r"\((\d+) gradient, (\d+) objective\), (\d+) rows in "
          r"([\d.]+)s \(nominal 2.0s\): ([\d.]+) examples/s; (\d+) "
          r"iterations of two passes")


def test_the_window_is_whole_iterations_and_the_rate_all_their_rows(
        rehearsed):
    out, said = rehearsed
    m = re.search(WINDOW, said)
    jobs, iters, passes, grad, obj, rows = map(int, m.groups()[:6])
    took, every, two = float(m.group(7)), float(m.group(8)), int(m.group(9))
    assert passes == grad + obj == out["attempted"] and grad == iters
    assert rows == passes * 2048
    # it closed at the end of the iteration in which the 2 s passed
    assert 2.0 <= took < 2.5
    # every pass's rows over all the window's seconds, further trials
    # and a started job's first passes with them
    assert every == pytest.approx(rows / took, rel=1e-3)
    assert out["metrics"]["train_ex_per_s"]["value"] == pytest.approx(
        every, rel=1e-3)
    assert 0.5 * iters < two < iters


def test_the_rate_is_all_the_work_over_all_the_time():
    """Four iterations of 2.8 s with two passes each and one of 3.8 s
    with three: eleven passes' rows over the fifteen seconds, the slow
    iteration and its further trial in it."""
    run = NS(rows=1000, passes=[2, 2, 3, 2, 2],
             step_s=[2.8, 2.8, 3.8, 2.8, 2.8], t_open=0.0, t_close=15.0)
    assert driver.rate(run) == pytest.approx(11000 / 15.0)
    assert driver.end_to_end(NS(**vars(run), val_logloss=0.5))[
        "train_ex_per_s"] == pytest.approx(11000 / 15.0)
    # a stall inside an iteration of three passes shows
    run.step_s[2], run.t_close = 13.8, 25.0
    assert driver.rate(run) == pytest.approx(11000 / 25.0)


def test_a_job_that_ends_inside_the_window_starts_again(rehearsed):
    """The rehearsal's job has 7 iterations, 4 of them set-up: it ends
    and starts again many times in 2 s, each start two more passes."""
    _, said = rehearsed
    jobs, iters, _, grad, obj = map(int, re.search(WINDOW, said).groups()[:5])
    assert jobs > 2 and iters >= 7 * (jobs - 2)
    # a started job's first gradient and objective passes are counted
    # as objective-side passes of the window: more than a trial an
    # iteration by at least two a job
    assert obj >= iters + 2 * (jobs - 1)
    assert "fixed job: 4 iterations" in said and "history 3" in said
    assert "after them" in said


def test_a_program_without_make_solver_ends_at_once_with_its_name(
        bench, monkeypatch):
    from wormhole_tpu.apps import lbfgs_linear

    def no_data(*a, **k):
        raise AssertionError("data was made")

    monkeypatch.delattr(lbfgs_linear, "make_solver")
    monkeypatch.setattr(gen, "Dataset", no_data)
    with pytest.raises(SystemExit, match="has no make_solver"):
        run.run_cell(bench, CELL, 7, 2.0, False, rehearsal=True)


def test_a_mix_that_makes_other_rows_than_the_configuration_says(
        config, tmp_path):
    conf, _ = run.sized(config, True)
    mix = run.load_json(run.HERE, "traffic", "resident.json")
    with pytest.raises(SystemExit, match="the configuration says 2048"):
        driver.make_data(str(tmp_path), dict(mix, train_parts=3), conf,
                         config, 7)


# ------------------------------------------------------- the kernel counts
@pytest.mark.parametrize("rows,dim", [(1048576, 67108865), (2048, 4097)])
def test_the_passes_counts_are_the_algorithm_s_bytes(rows, dim):
    """A pass streams seg, idx, val once (12 B a nonzero), labels and
    mask once, reads w and (the gradient pass) writes g once."""
    b = {"rows": rows, "nnz": rows * 39, "dim": dim, "basis": 21}
    grad, obj = lbfgs_grad_pass.cost(b), lbfgs_obj_pass.cost(b)
    assert obj["bytes"] == rows * 39 * 12 + rows * 8 + dim * 4
    assert grad["bytes"] - obj["bytes"] == dim * 4          # g written
    assert grad["flops"] == 2 * obj["flops"] == 4 * rows * 39
    if rows == 1048576:
        # 1.04 GB: 1.27 ms at 819 GB/s
        assert grad["bytes"] / 819e9 == pytest.approx(1.266e-3, rel=1e-3)


@pytest.mark.parametrize("basis,dim", [(21, 67108865), (7, 4097)])
def test_the_gram_matrix_reads_its_vectors_once(basis, dim):
    c = lbfgs_gram.cost({"basis": basis, "dim": dim})
    assert c["bytes"] == basis * dim * 4
    assert c["flops"] == basis * (basis + 1) * dim
    if basis == 21:
        assert c["bytes"] / 819e9 == pytest.approx(6.883e-3, rel=1e-3)
        assert c["flops"] / 197e12 < c["bytes"] / 819e9     # bound by bytes


def test_the_driver_s_batch_holds_what_the_counts_read(config):
    conf, _ = run.sized(config, False)
    b = driver.batch(conf, config, NS(num_feature=67108864, history=10,
                                      ids=range(5), hyper={"m": 10.0}))
    assert b["rows"] == 1048576 and b["nnz"] == 1048576 * 39
    assert b["dim"] == 67108865 and b["basis"] == 21
    for mod in (lbfgs_grad_pass, lbfgs_obj_pass, lbfgs_gram):
        assert mod.cost(b)["bytes"] > 0


# ------------------------------------------------- the reducer, on a trace
MS = 10**6      # ns


def _ev(name, start, end, **stats):
    return NS(name=name, start_ns=start, duration_ns=end - start,
              stats=list(stats.items()))


def _profile():
    """Two gradient passes of 100 ms on the host's clock: the device
    busy 80 ms under the first (two fusions) and 60 ms under the second
    (one that had begun 10 ms before the span, so 50 of it count, and
    10 more), and one operation of 30 ms under no span."""
    dev = NS(name="/device:TPU:0", lines=[NS(name=xplane.OPS_LINE, events=[
        _ev("%fusion.1 = f32[8]{0} fusion(%p)", 0, 50 * MS),
        _ev("%fusion.2 = f32[8]{0} fusion(%p)", 60 * MS, 90 * MS),
        _ev("%fusion.3 = f32[8]{0} fusion(%p)", 190 * MS, 250 * MS),
        _ev("%fusion.4 = f32[8]{0} fusion(%p)", 280 * MS, 290 * MS),
        _ev("%fusion.5 = f32[8]{0} fusion(%p)", 400 * MS, 430 * MS)])])
    host = NS(name="python", events=[
        _ev("lbfgs.grad_pass", 0, 100 * MS),
        _ev("lbfgs.grad_pass", 200 * MS, 300 * MS)])
    return NS(planes=[dev, NS(name="/host:CPU", lines=[host])])


@pytest.fixture
def traced(monkeypatch):
    t = _spans.parse(_profile())
    monkeypatch.setattr(_spans, "of_run", lambda ctx: t)
    return {"kernels": [lbfgs_grad_pass, lbfgs_gram],
            "batch": {"rows": 1000, "nnz": 39000, "dim": 4097, "basis": 3},
            "peaks": {"bytes_per_s": 1e9, "flops_per_s": 1e15}}


def test_device_time_is_taken_by_the_span_not_by_a_name(traced, capsys):
    """(80 + 60) / 2 = 70 ms of device time a pass; the count's bytes at
    the table's bandwidth are 0.508776 ms: 0.7268 %."""
    least = (39000 * 12 + 1000 * 8 + 2 * 4097 * 4) / 1e9
    assert least == pytest.approx(508.776e-6)
    got = span_device_roofline.read(traced, span="lbfgs.grad_pass",
                                    kernel="lbfgs_grad_pass")
    assert got == pytest.approx(100.0 * least / 70e-3)
    assert "bound by bytes" in capsys.readouterr().out
    assert span_device_roofline.busy_ns([(0, 50), (60, 90)], 40, 70) == 20


def test_no_span_or_no_count_reads_nothing(traced, monkeypatch):
    read = span_device_roofline.read
    assert read(traced, span="lbfgs.gram", kernel="lbfgs_gram") is None
    assert read(traced, span="lbfgs.grad_pass",
                kernel="lbfgs_obj_pass") is None        # not the config's
    monkeypatch.setattr(_spans, "of_run", lambda ctx: None)
    assert read(traced, span="lbfgs.grad_pass",
                kernel="lbfgs_grad_pass") is None


def test_a_counter_s_ratio_is_what_the_window_added(rehearsed):
    """Set-up's 22 trials of one iteration and the served iteration's
    reads are in neither counter's difference: 14 iterations of the
    window with 15 trials and 70 reads."""
    opened = {"lbfgs.iters": (11, 0.0), "lbfgs.linesearch_trials": (32, 0.0),
              "lbfgs.host_syncs": (100, 0.0)}
    closed = {"lbfgs.iters": (25, 0.0), "lbfgs.linesearch_trials": (47, 0.0),
              "lbfgs.host_syncs": (170, 0.0)}
    from benchmark import tap

    ctx = {"hist": tap.hist_delta(opened, closed)}
    read = counter_window_ratio.read
    assert read(ctx, num="lbfgs.linesearch_trials",
                den="lbfgs.iters") == pytest.approx(15 / 14)
    assert read(ctx, num="lbfgs.host_syncs", den="lbfgs.iters") == 5.0
    assert read(ctx, num="lbfgs.passes", den="lbfgs.iters") is None
    assert read({"hist": tap.hist_delta(opened, opened)},
                num="lbfgs.host_syncs", den="lbfgs.iters") is None
    assert read({"hist": {}}, num="lbfgs.host_syncs",
                den="lbfgs.iters") is None
    # the driver's own snapshot: the program's lbfgs.* counters alone
    snap = driver.counters()
    assert set(snap) == {"lbfgs.iters", "lbfgs.passes",
                         "lbfgs.linesearch_trials", "lbfgs.host_syncs"}
    assert all(s == 0.0 and c > 0 for c, s in snap.values())


def test_y_is_held_to_the_scale_of_the_gradients_it_is_the_difference_of():
    """An error of 1e-6 of g an entry: nothing by g's scale, and by y's
    own (y a thousandth of g) every entry off. `state_off_share` takes y
    by g's scale; a y off by 1e-3 of g fails it."""
    import numpy as np

    rng = np.random.default_rng(0)
    g = rng.standard_normal(4096).astype(np.float32) + 3
    y = (1e-3 * g).astype(np.float32)
    w = rng.standard_normal(4096).astype(np.float32)
    ids = {"feature": np.arange(4096)}

    def side(y_):
        return {"objv": [1.0], "nex": [8.0], "ids1": ids, "grad1": g,
                "ids": ids, "start": {},
                "final": {"w": w, "g": g, "s": w, "y": y_}}

    nums = driver.state_numbers(side(y + np.float32(1e-6) * g), side(y))
    assert nums["y_off_share"] > 0.9 and nums["off_share.y_by_g"] == 0
    assert nums["state_off_share"] == 0 == nums["off_share.g"]
    nums = driver.state_numbers(side(y + np.float32(1e-3) * g), side(y))
    assert nums["state_off_share"] == nums["off_share.y_by_g"] > 0.9
    assert driver.off_share(g * np.float32(1.001), g) == 1.0


# ------------------------------------------------- the entries and the files
@pytest.mark.parametrize("name", sorted(NEW))
def test_a_layer_metric_of_the_cell_is_a_file_and_an_entry(bench, name):
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL] and entry["moves"] == "train_ex_per_s"
    spec = run.load_json(run.HERE, "layer_metrics", name + ".json")
    if name in SPAN_METRICS:
        assert spec["reducer"] == "span_mean" and spec["params"] == {
            "span": SPAN_METRICS[name], "value": "duration"}
        assert entry["source"] == "program_span"
    elif name in COUNTER_METRICS:
        assert spec["reducer"] == "counter_window_ratio" and spec[
            "params"] == {
            "num": COUNTER_METRICS[name], "den": "lbfgs.iters"}
        assert entry["source"] == "program_counter"
    else:
        assert spec["reducer"] == "span_device_roofline"
        assert spec["params"] == {"span": ROOFLINES[name],
                                  "kernel": name[:-len("_roofline")]}
        assert entry["unit"] == "%" and entry["source"] == "device_trace"
        assert entry["layer"] == "kernels"
    # the program registers what the metric reads
    from wormhole_tpu.obs import names

    for v in spec["params"].values():
        if str(v).startswith("lbfgs."):
            assert v in names.SPANS or v in names.COUNTERS, v


def test_the_cell_is_under_the_rate_and_the_idle_share_and_no_other(bench):
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config=CONFIG, traffic="resident", chips=1)
    assert bench["workloads"][-1] == cell
    listing = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if CELL in m.get("workloads", ())}
    assert listing == {"train_ex_per_s", "device_idle_share", *NEW}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("train_ex_per_s", "device_idle_share"):
            assert m["workloads"][-1] == CELL
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["train_rows", "num_feature"]
    assert bench["configs"][-1] == entry


def test_the_configuration_states_the_deployment(config):
    conf = config["conf"]
    assert config["driver"] == "benchmark.drivers.batch"
    assert (conf["reg_L1"], conf["reg_L2"], conf["m"]) == (1, 0, 10)
    assert conf["num_feature"] == 2 ** 26
    assert conf["train_rows"] == 2 ** 20 == 2 * conf["minibatch"]
    assert conf["val_rows"] == 262144
    # the Gram matrix of the full basis is first formed in iteration
    # m + 1: set-up holds it, so that nothing compiles in the window
    assert conf["setup_iters"] == conf["m"] + 1
    # held-out logloss after set-up's last iteration: no knob moves it
    assert "val_iter" not in conf and "val_iter" not in config["assumed"]
    assert set(config["equals_source"]) >= {"reg_L1", "m", "linesearch",
                                            "stop_rule"}
    # the first iteration's line search is not the source's
    assert "second iteration on" in config["equals_source"]["linesearch"]
    assert {"lbfgs_stop_tol", "max_lbfgs_iter", "minibatch",
            "nnz_per_row"} <= set(config["assumed"])
    assert set(config["reduced"]) == {"train_rows", "num_feature"}
    assert "one rank" in config["deployment"].lower()
    src = open(os.path.join(run.HERE, "drivers", "batch.py")).read()
    assert not re.search(r"^\s*(from|import)\s+benchmark(\.|\s+import\s+)run"
                         r"\b", src, re.M)


# ------------------------------------------------------------- the control
def test_the_bfloat16_control_fails_the_limits_at_the_rehearsal_size(config):
    """`control.py`, unedited: w, g, S, Y kept in bfloat16 between
    iterations, over three iterations from w = 0 and one more."""
    nums = control.control_numbers(config, 11, rehearsal=True)
    ok, lines = check.verdict(nums, {**config["correct"]["limits"],
                                     **config["correct"]["served_limits"]})
    assert not ok, lines
    assert sum("OVER" in ln for ln in lines) >= 2
