"""The reduction from a profiler trace to numbers (benchmark/xplane.py and
the trace reducers), on hand-made events and on a small trace recorded on
the chip: `crb-stream-v5e-4s.xplane.pb.gz`, 4.05 s of the
`criteo1tb.crb-stream` cell on one TPU v5 lite (my chip run, PR 23; 35
train steps). The numbers asserted from it are properties of that file,
not benchmark results."""

import gzip
import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import xplane  # noqa: E402
from benchmark.reducers import (device_idle_share,  # noqa: E402
                                kernel_ms_per_step)

RECORDED_WINDOW_S = 4.048267355   # the host's clock round the profiler


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _trace(ops, marks=()):
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[_ev("module", 0, 10**9)]),
            NS(name=xplane.OPS_LINE, events=[_ev(*o) for o in ops])]),
        NS(name="/host:CPU", lines=[
            NS(name="main", events=[_ev(xplane.STEP_MARK, a, b - a)
                                    for a, b in marks])]),
        NS(name="/host:metadata", lines=[])])


def test_union_merges_overlapping_and_touching_intervals():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert xplane.union([]) == []


def test_busy_is_the_union_not_the_sum():
    # a while loop's body ops lie inside the while op's own interval
    ops = [("%while", 0, 100), ("%body.1", 10, 20), ("%k", 200, 50)]
    s = xplane.summarize(_trace(ops))
    assert s["busy_s"] == pytest.approx(150e-9)
    assert s["device_span_s"] == pytest.approx(250e-9)
    assert s["ops"]["%body.1"] == [1, pytest.approx(20e-9)]
    assert device_idle_share.read({"trace": s}) == pytest.approx(40.0)


def test_only_the_xla_ops_line_of_device_planes_counts():
    s = xplane.summarize(_trace([("%a", 0, 10)]))
    assert set(s["ops"]) == {"%a"} and s["chips"] == 1


def test_a_trace_without_device_ops_is_an_error():
    with pytest.raises(ValueError):
        xplane.summarize(_trace([]))


def test_idle_gaps_are_labelled_by_where_the_train_thread_was():
    ops = [("%a", 0, 10), ("%b", 110, 10), ("%c", 1120, 10)]
    # the first gap (10..110) lies inside a bench.step, the second does not
    s = xplane.summarize(_trace(ops, marks=[(0, 125)]))
    assert s["gaps"] == [["queue_wait+merge", pytest.approx(1000e-9)],
                         ["step_host", pytest.approx(100e-9)]]
    assert s["steps_marked"] == 1


def test_short_name_keeps_name_and_opcode():
    op = ('%train_step_tcoo.3 = f32[12582912]{0:T(1024)} custom-call('
          's32[12288]{0:T(1024)S(1)} %copy-done.11), custom_call_target='
          '"tpu_custom_call", frontend_attributes={kernel_metadata={}}')
    assert xplane.short_name(op) == (
        "%train_step_tcoo.3 custom-call[tpu_custom_call]")
    assert xplane.short_name(
        "%copy-done = s32[8]{0:T(1024)S(1)} copy-done((s32[8]{0}, "
        "u32[]{:S(2)}) %copy-start)") == "%copy-done copy-done"
    assert xplane.short_name("plain") == "plain"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    with gzip.open(os.path.join(
            HERE, "crb-stream-v5e-4s.xplane.pb.gz")) as fh:
        pd = ProfileData.from_serialized_xspace(fh.read())
    return xplane.summarize(pd, RECORDED_WINDOW_S)


def test_recorded_trace_busy_union_and_idle_share(recorded):
    assert recorded["chips"] == 1 and recorded["steps_marked"] == 35
    assert recorded["busy_s"] == pytest.approx(3.249360953, rel=1e-9)
    # the union is less than the plain sum of the op durations
    assert sum(s for _, s in recorded["ops"].values()) > recorded["busy_s"]
    assert recorded["busy_s"] < recorded["device_span_s"] < RECORDED_WINDOW_S
    assert device_idle_share.read({"trace": recorded}) == pytest.approx(
        19.7345267, rel=1e-6)


def test_recorded_trace_kernel_sum_per_step(recorded):
    kernels = {k: v for k, v in recorded["ops"].items()
               if "tpu_custom_call" in k}
    assert len(kernels) == 3 and {n for n, _ in kernels.values()} == {35}
    assert sum(s for _, s in kernels.values()) == pytest.approx(2.671915108)
    ctx = {"trace": recorded, "trace_steps": 35}
    assert kernel_ms_per_step.read(ctx, pattern="tpu_custom_call") == (
        pytest.approx(76.3404316, rel=1e-6))
    # no kernel of that name: nothing to read, the metric is left out
    assert kernel_ms_per_step.read(ctx, pattern="no_such_kernel") is None


def test_recorded_trace_breakdown_fits_the_result_line(recorded):
    b = xplane.breakdown(recorded)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0][0] == (
        "%train_step_tcoo.3 custom-call[tpu_custom_call]")
    assert all(len(n) < 80 for n, _ in b["device_ops"])
    assert {g[0] for g in b["idle_gaps"]} <= {"step_host",
                                              "queue_wait+merge"}
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
