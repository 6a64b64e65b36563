"""The per-chip reducers of the four-chip cell on a hand-built trace of
four device planes: `op_ms_per_step_per_chip`, `roofline_share_per_chip`
and `counter_ratio`, each beside what the one-chip reducers read there.

`xplane.summarize` sums an operation's count over the planes and averages
its seconds over them, so the median count the one-chip reducers divide
by is steps x chips: on four planes `kernel_ms_per_step` reads a quarter
of a chip's kernel time and `step_kernels_roofline` four times too high.
That is pinned here as the defect it is (PERF.md §7); the per-chip
reducers divide the count by `trace.chips` first."""

import os
import sys
from types import SimpleNamespace as NS

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import xplane  # noqa: E402
from benchmark.kernels import coo_pull, coo_push  # noqa: E402
from benchmark.reducers import (counter_ratio,  # noqa: E402
                                kernel_ms_per_step, kernel_roofline_share,
                                op_ms_per_step_per_chip,
                                roofline_share_per_chip)

PULL = ('%coo_pull.1 = f32[512,128]{1,0:T(8,128)} custom-call(s32[4408]{0} '
        '%p), custom_call_target="tpu_custom_call"')
PUSH = ('%coo_push.1 = f32[2097152,128]{1,0:T(8,128)} custom-call(s32[4408]'
        '{0} %p), custom_call_target="tpu_custom_call"')
PSUM = "%all-reduce.1 = f32[65536]{0:T(1024)} all-reduce(f32[65536]{0} %x)"
FUSION = "%fusion.7 = (f32[268435456]{0}) fusion(f32[268435456]{0} %z)"
STEPS = 5
MS = 10**6      # nanoseconds


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _plane(chip: int, slow: float = 1.0):
    """One chip's operations over STEPS steps of 100 ms: pull 20 ms (the
    hot chip `slow` times that), the psum 0.5 ms, push 30 ms, the dense
    update 9 ms."""
    evs = []
    for s in range(STEPS):
        t = s * 100 * MS
        evs += [_ev(PULL, t, int(20 * MS * slow)),
                _ev(PSUM, t + 40 * MS, MS // 2),
                _ev(PUSH, t + 41 * MS, 30 * MS),
                _ev(FUSION, t + 72 * MS, 9 * MS)]
    return NS(name=f"/device:TPU:{chip}", lines=[
        NS(name=xplane.OPS_LINE, events=evs)])


def _summary(chips: int):
    planes = [_plane(0, slow=2.0)] + [_plane(c) for c in range(1, chips)]
    planes.append(NS(name="/host:CPU", lines=[NS(name="main", events=[
        _ev(xplane.STEP_MARK, s * 100 * MS, 99 * MS)
        for s in range(STEPS)])]))
    return xplane.summarize(NS(planes=planes))


@pytest.fixture(scope="module")
def four():
    s = _summary(4)
    assert s["chips"] == 4 and s["steps_marked"] == STEPS
    # counts summed over the planes, seconds averaged over them
    assert s["ops"][PUSH] == [4 * STEPS, pytest.approx(STEPS * 0.030)]
    assert s["ops"][PULL] == [4 * STEPS, pytest.approx(STEPS * 0.025)]
    return {"trace": s, "trace_steps": STEPS}


@pytest.mark.parametrize("pattern,ms", [
    (r"^(ROOT )?%coo_pull[.\w]* = ", 25.0),     # (40 + 20 + 20 + 20) / 4
    (r"^(ROOT )?%coo_push[.\w]* = ", 30.0),
    ("tpu_custom_call", 55.0),
    (r"^(ROOT )?%all-reduce", 0.5),
])
def test_op_ms_per_step_per_chip_on_four_planes(four, pattern, ms):
    assert op_ms_per_step_per_chip.read(four, pattern=pattern) == (
        pytest.approx(ms))


def test_kernel_ms_per_step_reads_a_quarter_on_four_planes(four):
    """The accepted reducer, as it stands: not what a chip spends."""
    assert kernel_ms_per_step.read(four, pattern="tpu_custom_call") == (
        pytest.approx(55.0 / 4))


def test_the_two_agree_on_one_plane():
    ctx = {"trace": _summary(1), "trace_steps": STEPS}
    assert ctx["trace"]["chips"] == 1
    for pattern in ("tpu_custom_call", r"^(ROOT )?%all-reduce"):
        assert op_ms_per_step_per_chip.read(ctx, pattern=pattern) == (
            pytest.approx(kernel_ms_per_step.read(ctx, pattern=pattern)))


def test_nothing_to_read_is_none(four):
    assert op_ms_per_step_per_chip.read(four, pattern="no_such_op") is None
    assert roofline_share_per_chip.read(
        dict(four, batch={"uniq": 1}, kernels=[], peaks={}),
        pattern="no_such_op") is None
    # steps unknown (the profiler saw no whole step): nothing, as the
    # accepted kernel reducers have it
    assert op_ms_per_step_per_chip.read(dict(four, trace_steps=0),
                                        pattern="tpu_custom_call") is None


def test_roofline_share_per_chip_divides_the_least_time_by_the_chips(
        four, capsys):
    batch = {"rows": 65536, "nnz": 65536 * 39, "uniq": 245_000,
             "num_buckets": 1 << 30}
    ctx = dict(four, batch=batch, kernels=[coo_pull, coo_push],
               peaks={"bytes_per_s": 819e9, "flops_per_s": 197e12})
    whole = sum(m.cost(batch)["bytes"] for m in (coo_pull, coo_push)) / 819e9
    share = roofline_share_per_chip.read(ctx, pattern="tpu_custom_call")
    assert share == pytest.approx(100 * (whole / 4) / 0.055)
    assert share < 100
    said = capsys.readouterr().out
    assert "coo_pull least" in said and "coo_push least" in said
    assert "measured 25.000 ms" in said and "measured 30.000 ms" in said
    # the accepted reducer on the same trace: the whole batch's least time
    # over a quarter of a chip's kernel time, 16 times the per-chip share
    assert kernel_roofline_share.read(ctx, pattern="tpu_custom_call") == (
        pytest.approx(16 * share))


def test_counter_ratio_reads_the_registry_and_leaves_out_what_is_absent():
    from wormhole_tpu.obs.metrics import REGISTRY

    ctx = {"trace": {"chips": 4}}
    num, den = "test.mesh.cell_max", "test.mesh.cell_sum"
    # a program without the counters (the parent of the PR that added
    # them): nothing, and reading creates none
    assert counter_ratio.read(ctx, num=num, den=den) is None
    assert num not in REGISTRY.snapshot()["counters"]
    REGISTRY.counter(num).inc(1_032_643)
    assert counter_ratio.read(ctx, num=num, den=den) is None   # den absent
    REGISTRY.counter(den).inc(2_555_904)
    assert counter_ratio.read(ctx, num=num, den=den) == pytest.approx(
        1_032_643 / 2_555_904)
    assert counter_ratio.read(ctx, num=num, den=den, times_chips=True) == (
        pytest.approx(4 * 1_032_643 / 2_555_904))
