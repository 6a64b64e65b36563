"""Request-tracing tests: trace-context propagation across a real
socket frame, deterministic sampling, the null-span hot path with
tracing off, worker-thread rebinding, the scheduler snapshot ring, the
Prometheus exposition golden, and tracer lifecycle (re-init +
atexit-close idempotency)."""

import json
import os
import socket
import threading

import pytest

from wormhole_tpu.obs import metrics as obs_metrics
from wormhole_tpu.obs import prom as obs_prom
from wormhole_tpu.obs import trace as obs_trace
from wormhole_tpu.runtime.net import recv_frame, send_frame

from conftest import profiled_spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def retrace(monkeypatch):
    """Re-init tracing around a test and guarantee it ends disabled
    (the module inits from env at import; tests mutate the env)."""
    yield monkeypatch
    monkeypatch.delenv("WH_OBS_DIR", raising=False)
    monkeypatch.delenv("WH_TRACE_SAMPLE", raising=False)
    obs_trace.init_from_env()
    assert obs_trace.ACTIVE is None and obs_trace.SAMPLE_N == 0


def _trace_lines(tracer) -> list[dict]:
    tracer.close()
    return [json.loads(l) for l in open(tracer.path)]


def _spans(lines: list[dict]) -> list[dict]:
    return [l for l in lines if l.get("ph") == "X"]


# ------------------------------------------------------------ propagation
def test_trace_context_rides_the_frame_header(tmp_path, retrace):
    """A bound context must cross a REAL socket as header['tctx'] and
    bind_wire on the receiver must parent the handler span to the
    sender's span — the cross-node stitch in miniature."""
    retrace.setenv("WH_OBS_DIR", str(tmp_path))
    retrace.setenv("WH_TRACE_SAMPLE", "1")
    tracer = obs_trace.init_from_env()
    a, b = socket.socketpair()
    fa, fb = a.makefile("rwb"), b.makefile("rwb")
    try:
        with obs_trace.bind(obs_trace.start_request()):
            with obs_trace.request_span("serve.request", cat="serve"):
                sender = obs_trace.current_ctx()
                assert sender is not None
                send_frame(fa, {"op": "fetch"})
        header, arrays, _ = recv_frame(fb)
        assert header["tctx"] == {"t": sender[0], "s": sender[1]}
        # receiver side: adopt and emit the handler span
        with obs_trace.bind_wire(header):
            with obs_trace.request_span("serve.shard.fetch", cat="serve"):
                pass
    finally:
        for f in (fa, fb):
            f.close()
        a.close()
        b.close()
    spans = _spans(_trace_lines(tracer))
    shard = next(s for s in spans if s["name"] == "serve.shard.fetch")
    root = next(s for s in spans if s["name"] == "serve.request")
    assert root["trace"] == sender[0] and "psid" not in root
    assert shard["trace"] == sender[0]      # same request
    assert shard["psid"] == sender[1]       # parented across the wire
    assert shard["sid"] != root["sid"]


def test_request_span_nesting_builds_psid_chain(tmp_path, retrace):
    retrace.setenv("WH_OBS_DIR", str(tmp_path))
    retrace.setenv("WH_TRACE_SAMPLE", "1")
    tracer = obs_trace.init_from_env()
    ctx = obs_trace.start_request()
    assert ctx is not None and ctx[1] is None  # root binds trace only
    with obs_trace.bind(ctx):
        with obs_trace.request_span("serve.request", cat="serve"):
            with obs_trace.request_span("serve.stage.pack", cat="serve"):
                pass
            obs_trace.event("mid", cat="serve")
    assert obs_trace.current_ctx() is None  # bind restored
    lines = _trace_lines(tracer)
    spans = _spans(lines)
    pack = next(s for s in spans if s["name"] == "serve.stage.pack")
    root = next(s for s in spans if s["name"] == "serve.request")
    assert root["trace"] == pack["trace"] == ctx[0]
    assert "psid" not in root               # the root has no parent
    assert pack["psid"] == root["sid"]      # child -> parent
    ev = next(l for l in lines if l.get("ph") == "i")
    assert ev["trace"] == ctx[0] and ev["psid"] == root["sid"]


def test_ctx_rebinds_into_worker_threads(tmp_path, retrace):
    """Thread pools don't inherit thread-locals: the router captures
    current_ctx() and rebinds in the pool thread (router._rpc_traced);
    this is that contract in isolation."""
    retrace.setenv("WH_OBS_DIR", str(tmp_path))
    retrace.setenv("WH_TRACE_SAMPLE", "1")
    tracer = obs_trace.init_from_env()
    got = {}

    def worker(ctx):
        got["inherited"] = obs_trace.current_ctx()
        with obs_trace.bind(ctx):
            with obs_trace.request_span("serve.rpc.fetch", cat="serve"):
                got["wire"] = obs_trace.wire_ctx()

    with obs_trace.bind(obs_trace.start_request()):
        with obs_trace.request_span("serve.request", cat="serve"):
            t = threading.Thread(target=worker,
                                 args=(obs_trace.current_ctx(),))
            t.start()
            t.join()
    assert got["inherited"] is None         # proof TLS does NOT inherit
    assert got["wire"] is not None          # rebinding restores the link
    spans = _spans(_trace_lines(tracer))
    rpc = next(s for s in spans if s["name"] == "serve.rpc.fetch")
    root = next(s for s in spans if s["name"] == "serve.request")
    assert rpc["trace"] == root["trace"]
    assert rpc["psid"] == root["sid"]


# --------------------------------------------------------------- sampling
def test_sampling_is_deterministic_and_counter_based(tmp_path, retrace):
    retrace.setenv("WH_OBS_DIR", str(tmp_path))
    retrace.setenv("WH_TRACE_SAMPLE", "4")
    obs_trace.init_from_env()
    pattern = [obs_trace.start_request() is not None for _ in range(8)]
    assert pattern == [False, False, False, True,
                       False, False, False, True]
    # a fresh incarnation samples the SAME ordinals (replayable runs)
    obs_trace.init_from_env()
    assert [obs_trace.start_request() is not None
            for _ in range(8)] == pattern
    # trace ids are unique and carry the request ordinal
    obs_trace.init_from_env()
    ids = [obs_trace.start_request() for _ in range(8)]
    sampled = [c for c in ids if c is not None]
    assert len(sampled) == 2
    assert len({c[0] for c in sampled}) == 2
    assert all(c[0].endswith(("r4", "r8")) for c in sampled)


def test_sample_zero_never_samples(tmp_path, retrace):
    retrace.setenv("WH_OBS_DIR", str(tmp_path))
    retrace.setenv("WH_TRACE_SAMPLE", "0")
    obs_trace.init_from_env()
    assert all(obs_trace.start_request() is None for _ in range(32))
    # request_span without a bound ctx is the shared no-op even with
    # the tracer active
    assert obs_trace.request_span("a") is obs_trace.request_span("b")


def test_bad_sample_value_means_off(tmp_path, retrace):
    retrace.setenv("WH_OBS_DIR", str(tmp_path))
    retrace.setenv("WH_TRACE_SAMPLE", "banana")
    obs_trace.init_from_env()
    assert obs_trace.SAMPLE_N == 0
    assert obs_trace.start_request() is None


# --------------------------------------------------------- off = zero cost
def test_tracing_off_is_null_on_every_hook(retrace):
    retrace.delenv("WH_OBS_DIR", raising=False)
    retrace.delenv("WH_TRACE_SAMPLE", raising=False)
    assert obs_trace.init_from_env() is None
    s = obs_trace.span("a", x=1)
    assert s is obs_trace.span("b")
    assert s is obs_trace.request_span("c")
    assert obs_trace.start_request() is None
    assert obs_trace.wire_ctx() is None
    assert obs_trace.bind_wire({"op": "x"}) is s  # shared null object
    with obs_trace.bind(None), obs_trace.request_span("d"):
        pass  # binding still composes as a no-op

    # and a frame sent with tracing off must NOT grow a tctx field,
    # even under a stale bound context
    a, b = socket.socketpair()
    fa, fb = a.makefile("rwb"), b.makefile("rwb")
    try:
        with obs_trace.bind(("stale:1:r1", "stale:1:1")):
            send_frame(fa, {"op": "fetch"})
        header, _, _ = recv_frame(fb)
        assert "tctx" not in header
    finally:
        for f in (fa, fb):
            f.close()
        a.close()
        b.close()


def test_no_sink_means_the_shared_noop_and_no_clock_read(retrace,
                                                        monkeypatch):
    """With the JSONL tracer, the flight ring and the profiler all off,
    span() is one object for every caller and reads no clock — `cpu`
    asked for or not."""
    retrace.delenv("WH_OBS_DIR", raising=False)
    assert obs_trace.init_from_env() is None
    assert not obs_trace._profiling()

    def no_clock():
        raise AssertionError("a clock was read with no sink on")

    monkeypatch.setattr(obs_trace.time, "monotonic", no_clock)
    monkeypatch.setattr(obs_trace.time, "thread_time", no_clock)
    with obs_trace.span("test.off", cpu=True, part=1) as sp:
        sp.set(rows=3)
    assert sp is obs_trace._NULL_SPAN
    assert obs_trace.span("test.off2") is sp


def test_span_lands_in_a_running_profiler_session(tmp_path, retrace):
    """The third sink: under a jax.profiler session a span is a
    TraceAnnotation in the host plane, with the arguments given at entry,
    the ones set inside the block and its thread CPU time — from the
    main thread and from a worker thread, each on its own line."""
    import jax

    retrace.delenv("WH_OBS_DIR", raising=False)
    assert obs_trace.init_from_env() is None

    def burn(tag):
        with obs_trace.span("test.burn", cat="t", cpu=True, part=tag) as sp:
            n = sum(range(200_000))
            sp.set(rows=7, kind="tcoo")
        return n

    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        assert obs_trace._profiling()
        burn(1)
        t = threading.Thread(target=burn, args=(2,))
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        with obs_trace.span("test.plain"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert obs_trace.span("test.after") is obs_trace._NULL_SPAN
    spans = profiled_spans(tmp_path / "prof")
    burns = {s["args"]["part"]: s for s in spans if s["name"] == "test.burn"}
    assert set(burns) == {1, 2}
    assert burns[1]["thread"] != burns[2]["thread"]
    for s in burns.values():
        assert s["args"]["rows"] == 7 and s["args"]["kind"] == "tcoo"
        wall_us = (s["end"] - s["start"]) / 1e3
        # the loop is pure CPU: most of the wall, and never more than it
        assert 0 < s["args"]["cpu_us"] <= wall_us + 1000
    plain = next(s for s in spans if s["name"] == "test.plain")
    assert "cpu_us" not in plain["args"]


def test_profiler_sink_composes_with_the_jsonl_tracer(tmp_path, retrace):
    import jax

    retrace.setenv("WH_OBS_DIR", str(tmp_path / "obs"))
    tracer = obs_trace.init_from_env()
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        with obs_trace.span("test.both", cpu=True, part=4):
            pass
    finally:
        jax.profiler.stop_trace()
    (both,) = [s for s in _spans(_trace_lines(tracer))
               if s["name"] == "test.both"]
    assert both["args"]["part"] == 4 and "cpu_us" in both["args"]
    (prof,) = [s for s in profiled_spans(tmp_path / "prof")
               if s["name"] == "test.both"]
    assert prof["args"]["part"] == 4
    assert prof["args"]["cpu_us"] == both["args"]["cpu_us"]


def test_maybe_trace_is_off_without_its_variable(monkeypatch):
    monkeypatch.delenv("WORMHOLE_PROFILE_DIR", raising=False)
    with obs_trace.maybe_trace():
        assert not obs_trace._profiling()


# ------------------------------------------------------------- lifecycle
def test_init_from_env_is_reentrant_and_closes_predecessor(tmp_path,
                                                           retrace):
    retrace.setenv("WH_OBS_DIR", str(tmp_path))
    first = obs_trace.init_from_env()
    second = obs_trace.init_from_env()
    assert second is obs_trace.ACTIVE and second is not first
    assert first._closed  # the replaced tracer was closed, not leaked
    # close is idempotent, including via the atexit hook
    second.close()
    second.close()
    obs_trace._shutdown()
    obs_trace._shutdown()
    # writes after close are swallowed, not raised
    second.emit_span("late", "t", 0.0, 0.0)


def test_init_from_env_concurrent_reinit_is_safe(tmp_path, retrace):
    retrace.setenv("WH_OBS_DIR", str(tmp_path))
    barrier = threading.Barrier(8)

    def reinit():
        barrier.wait()
        obs_trace.init_from_env()

    ts = [threading.Thread(target=reinit) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # whoever won last, the module ends in a usable single-tracer state
    tracer = obs_trace.ACTIVE
    assert tracer is not None and not tracer._closed
    with obs_trace.span("after.reinit", cat="t"):
        pass
    assert any(s["name"] == "after.reinit"
               for s in _spans(_trace_lines(tracer)))


# ------------------------------------------------------------- ring + prom
def test_snapshot_ring_retains_newest_in_order():
    ring = obs_metrics.SnapshotRing(4)
    assert len(ring) == 0 and ring.items() == []
    for i in range(10):
        ring.add(float(i), {"counters": {"n": i}})
    assert len(ring) == 4
    got = ring.items()
    assert [ts for ts, _ in got] == [6.0, 7.0, 8.0, 9.0]
    assert [s["counters"]["n"] for _, s in got] == [6, 7, 8, 9]
    # items() hands out an independent list (callers may mutate)
    got.clear()
    assert len(ring) == 4


def test_prometheus_exposition_golden():
    snap = {
        "counters": {"net.bytes_sent": 17, "serve.router.requests": 3,
                     "admit.sheds": 5, "flight.dumps": 2,
                     "serve.batch.rounds": 9,
                     "net.bshuf.bytes_out": 7,
                     "wire.codec.bytes_raw": 400,
                     "wire.codec.bytes_wire": 100},
        "gauges": {"slo.serve.latency_burn": 0.25,
                   "prof.overhead_frac": 0.004,
                   "wire.codec.ef_resid_norm": 0.125},
        "hists": {
            "serve.batch.size": {"count": 3, "sum": 12.0, "min": 1.0,
                                 "max": 8.0, "res": [1.0, 3.0, 8.0]},
            "serve.latency_s": {"count": 4, "sum": 1.0, "min": 0.1,
                                "max": 0.4, "res": [0.1, 0.2, 0.3, 0.4]},
            "train.stage.step_s": {"count": 2, "sum": 0.5, "min": 0.2,
                                   "max": 0.3, "res": [0.2, 0.3]},
            "never.observed_s": {"count": 0, "sum": 0.0, "res": []},
        },
    }

    def _q(name, q):
        return repr(float(obs_metrics.hist_quantile(
            snap["hists"][name], q)))

    body = obs_prom.render_snapshot(snap)
    assert body == (
        "# TYPE wh_admit_sheds_total counter\n"
        "wh_admit_sheds_total 5\n"
        "# TYPE wh_flight_dumps_total counter\n"
        "wh_flight_dumps_total 2\n"
        "# TYPE wh_net_bshuf_bytes_out_total counter\n"
        "wh_net_bshuf_bytes_out_total 7\n"
        "# TYPE wh_net_bytes_sent_total counter\n"
        "wh_net_bytes_sent_total 17\n"
        "# TYPE wh_serve_batch_rounds_total counter\n"
        "wh_serve_batch_rounds_total 9\n"
        "# TYPE wh_serve_router_requests_total counter\n"
        "wh_serve_router_requests_total 3\n"
        "# TYPE wh_wire_codec_bytes_raw_total counter\n"
        "wh_wire_codec_bytes_raw_total 400\n"
        "# TYPE wh_wire_codec_bytes_wire_total counter\n"
        "wh_wire_codec_bytes_wire_total 100\n"
        "# TYPE wh_prof_overhead_frac gauge\n"
        "wh_prof_overhead_frac 0.004\n"
        "# TYPE wh_slo_serve_latency_burn gauge\n"
        "wh_slo_serve_latency_burn 0.25\n"
        "# TYPE wh_wire_codec_ef_resid_norm gauge\n"
        "wh_wire_codec_ef_resid_norm 0.125\n"
        "# TYPE wh_serve_batch_size summary\n"
        'wh_serve_batch_size{quantile="0.5"} '
        + _q("serve.batch.size", 0.5) + "\n"
        'wh_serve_batch_size{quantile="0.9"} '
        + _q("serve.batch.size", 0.9) + "\n"
        'wh_serve_batch_size{quantile="0.99"} '
        + _q("serve.batch.size", 0.99) + "\n"
        "wh_serve_batch_size_sum 12.0\n"
        "wh_serve_batch_size_count 3\n"
        "# TYPE wh_serve_latency_s summary\n"
        'wh_serve_latency_s{quantile="0.5"} '
        + _q("serve.latency_s", 0.5) + "\n"
        'wh_serve_latency_s{quantile="0.9"} '
        + _q("serve.latency_s", 0.9) + "\n"
        'wh_serve_latency_s{quantile="0.99"} '
        + _q("serve.latency_s", 0.99) + "\n"
        "wh_serve_latency_s_sum 1.0\n"
        "wh_serve_latency_s_count 4\n"
        "# TYPE wh_train_stage_step_s summary\n"
        'wh_train_stage_step_s{quantile="0.5"} '
        + _q("train.stage.step_s", 0.5) + "\n"
        'wh_train_stage_step_s{quantile="0.9"} '
        + _q("train.stage.step_s", 0.9) + "\n"
        'wh_train_stage_step_s{quantile="0.99"} '
        + _q("train.stage.step_s", 0.99) + "\n"
        "wh_train_stage_step_s_sum 0.5\n"
        "wh_train_stage_step_s_count 2\n"
    )
    assert obs_prom.render_snapshot({}) == ""
    assert obs_prom.prom_name("serve.stage.pack_s") == \
        "wh_serve_stage_pack_s"
