#!/usr/bin/env python
"""Serving-tier load lab: latency/QPS for the router + shard predict path.

Spins up an in-process serving group (N ModelServer shards over a
write_snapshot_set snapshot) and drives it through the Router with a
closed-loop (fixed concurrency, each thread fires its next request the
moment the last returns) or open-loop (Poisson-paced target QPS;
latency is measured from the SCHEDULED arrival, so queueing delay
shows up in the tail instead of being absorbed by backpressure)
generator. Reports p50/p99/p999 latency, achieved QPS, and error rate
— plus hot-swap counts/stall when --swap writes newer snapshot
versions mid-load, and shard kill/respawn recovery when --chaos kills
a shard mid-load (the run asserts ZERO failed requests: the router
must absorb the death through redial + seq-replayed fetches).

This is where PERF.md serving numbers come from; the final line is
machine-readable:

    [serve-lab] {"qps": ..., "p50_ms": ..., "p99_ms": ..., ...}

Both serving dataflows are drivable: --mode fetch pulls weight slices
to the router (the PR-13 path), --mode score pushes shard-local
scoring + router micro-batching (the fast path); auto (default)
resolves to score when the scorer supports it.

Usage: python tools/serve_lab.py [--shards N] [--buckets N] [--nnz N]
       [--duration S] [--concurrency N] [--open-qps Q] [--mode M]
       [--swap] [--chaos] [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from wormhole_tpu.data.rowblock import RowBlock
from wormhole_tpu.models.linear import LinearConfig
from wormhole_tpu.obs import metrics as _obs
from wormhole_tpu.obs import report as _report
from wormhole_tpu.obs import slo as _slo
from wormhole_tpu.runtime import overload as _overload
from wormhole_tpu.serving import LinearScorer, ModelServer, Router
from wormhole_tpu.utils.manifest import write_snapshot_set


def _synth_blocks(rng, num_blocks: int, minibatch: int, nnz: int):
    """A pool of distinct predict batches (reused round-robin so the
    load is not one memoized key set)."""
    out = []
    for _ in range(num_blocks):
        n = minibatch
        counts = rng.integers(max(nnz // 2, 1), nnz + 1, size=n)
        offset = np.zeros(n + 1, np.int64)
        offset[1:] = np.cumsum(counts)
        out.append(RowBlock(
            label=np.zeros(n, np.float32),
            offset=offset,
            index=rng.integers(0, 1 << 62, size=int(offset[-1]),
                               dtype=np.int64).astype(np.uint64),
            value=rng.normal(size=int(offset[-1])).astype(np.float32),
        ))
    return out


def _pct(lat_ms: list, q: float) -> float:
    if not lat_ms:
        return float("nan")
    s = sorted(lat_ms)
    return s[min(len(s) - 1, int(q * len(s)))]


def run(num_shards: int = 2, num_buckets: int = 1 << 20,
        minibatch: int = 256, nnz: int = 32, duration_s: float = 3.0,
        concurrency: int = 4, open_qps: float = 0.0,
        swap_every_s: float = 0.0, chaos_at_s: float = 0.0,
        deadline_ms: float = 0.0, seed: int = 0,
        serve_mode: str = "auto", verbose: bool = True) -> dict:
    """Drive one load run; returns the result row (the [serve-lab] dict).

    swap_every_s > 0: write a newer snapshot version every interval —
    the shard watchers hot-swap under load.
    chaos_at_s > 0: hard-stop shard 0 at that offset and respawn it on
    a NEW port; the router must recover through the resolver with zero
    failed requests.
    deadline_ms > 0: bind that budget around every request (it rides
    the fan-out frames; expired work is shed server-side). Goodput —
    replies within the deadline, measured from the SCHEDULED arrival —
    is then reported separately from raw throughput, and deadline
    misses (shed or timed out) separately from hard errors.
    """
    rng = np.random.default_rng(seed)
    cfg = LinearConfig(minibatch=minibatch, num_buckets=num_buckets,
                       nnz_per_row=nnz)
    tmp = tempfile.mkdtemp(prefix="wh_serve_lab_")
    base = os.path.join(tmp, "srv")
    # zeros: the lab measures the serving path, not the model; rows move
    # over the wire either way
    # uncompressed: at bench scale (64M buckets) a compressed 256 MB set
    # write outlasts the swap interval and no swap lands in the window
    write_snapshot_set(base, {"w": np.zeros(num_buckets, np.float32)},
                       world=num_shards, clock=0, epoch=0,
                       compressed=False)

    servers = [ModelServer(r, num_shards, base, poll_sec=0.05)
               for r in range(num_shards)]
    for s in servers:
        s.serve()
    uris = [s.uri for s in servers]  # mutated by the chaos respawn
    state = {"servers": servers, "uris": list(uris), "respawns": 0}
    state_lock = threading.Lock()

    def resolver():
        with state_lock:
            return list(state["uris"])

    router = Router(resolver(), LinearScorer(cfg), resolver=resolver,
                    retry_deadline=max(30.0, duration_s * 2),
                    mode=serve_mode)
    blocks = _synth_blocks(rng, 8, minibatch, nnz)
    # warm the jit caches so compile time is not in the measured window
    router.predict_block(blocks[0])

    before = _obs.REGISTRY.snapshot()
    lat_ms: list = []
    errors = [0]
    done = [0]
    good = [0]       # replies within the deadline (== done when none)
    misses = [0]     # deadline misses: shed server-side or timed out
    degraded = [0]   # replies stamped degraded=1
    lock = threading.Lock()
    stop = threading.Event()
    t_start = time.perf_counter()
    deadline = t_start + duration_s

    def _is_deadline_miss(e: Exception) -> bool:
        return isinstance(e, TimeoutError) or "deadline expired" in str(e)

    def loop(tid: int):
        lrng = np.random.default_rng(seed + 1000 + tid)
        local_lat, local_done, local_err = [], 0, 0
        local_good, local_miss, local_deg = 0, 0, 0
        i = tid
        # open loop: each thread owns an independent Poisson arrival
        # process at open_qps/concurrency
        next_at = time.perf_counter()
        while not stop.is_set() and time.perf_counter() < deadline:
            if open_qps > 0:
                now = time.perf_counter()
                if now < next_at:
                    time.sleep(next_at - now)
                sched = next_at
                next_at += lrng.exponential(concurrency / open_qps)
            else:
                sched = time.perf_counter()
            try:
                # the per-request budget starts at the SCHEDULED
                # arrival: a request that queued past its deadline
                # before being issued ships an already-expired budget
                # and is shed at the first hop instead of computed
                rem = (deadline_ms / 1e3 - (time.perf_counter() - sched)
                       if deadline_ms > 0 else None)
                with (_overload.bind_in(rem) if rem is not None
                      else _overload.bind(None)):
                    _, _, meta = router.predict_block_ex(
                        blocks[i % len(blocks)])
                lat = (time.perf_counter() - sched) * 1e3
                local_lat.append(lat)
                local_done += 1
                if meta.get("degraded"):
                    local_deg += 1
                if deadline_ms <= 0 or lat <= deadline_ms:
                    local_good += 1
                else:
                    local_miss += 1
            except Exception as e:
                if deadline_ms > 0 and _is_deadline_miss(e):
                    local_miss += 1
                else:
                    local_err += 1
                    if verbose:
                        print(f"[serve-lab] request failed: {e!r}",
                              flush=True)
            i += concurrency
        with lock:
            lat_ms.extend(local_lat)
            done[0] += local_done
            errors[0] += local_err
            good[0] += local_good
            misses[0] += local_miss
            degraded[0] += local_deg

    def swapper():
        epoch = 0
        while not stop.wait(swap_every_s):
            epoch += 1
            write_snapshot_set(
                base, {"w": np.full(num_buckets, float(epoch),
                                    np.float32)},
                world=num_shards, clock=epoch, epoch=epoch,
                compressed=False)

    def chaos():
        if stop.wait(chaos_at_s):
            return
        with state_lock:
            victim = state["servers"][0]
        if verbose:
            print("[serve-lab] chaos: killing shard 0", flush=True)
        victim.stop()
        time.sleep(0.2)  # let in-flight RPCs hit the dead socket
        replacement = ModelServer(0, num_shards, base, poll_sec=0.05)
        replacement.serve()
        with state_lock:
            state["servers"][0] = replacement
            state["uris"][0] = replacement.uri
            state["respawns"] += 1
        if verbose:
            print(f"[serve-lab] chaos: shard 0 respawned at "
                  f"{replacement.uri}", flush=True)

    threads = [threading.Thread(target=loop, args=(t,), daemon=True)
               for t in range(concurrency)]
    extras = []
    if swap_every_s > 0:
        extras.append(threading.Thread(target=swapper, daemon=True))
    if chaos_at_s > 0:
        extras.append(threading.Thread(target=chaos, daemon=True))
    for t in threads + extras:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    for t in extras:
        t.join(timeout=5)
    elapsed = time.perf_counter() - t_start

    after = _obs.REGISTRY.snapshot()

    def delta(name: str) -> int:
        return (after["counters"].get(name, 0)
                - before["counters"].get(name, 0))

    stall_h = after["hists"].get("serve.swap_stall_s") or {}
    stall_before = before["hists"].get("serve.swap_stall_s") or {}
    stall_ms = ((stall_h.get("sum", 0.0) - stall_before.get("sum", 0.0))
                * 1e3)
    # stage decomposition over THIS run's observations: count/sum are
    # delta'd against the run-start snapshot so a previous run in the
    # same process (a caller that runs fetch then score back to back)
    # cannot leak stages it exercised — or its means — into this run's
    # table. Quantiles still read the full reservoirs, which are
    # recent-sample-biased toward this run (and the single warmup
    # request is ~1/reservoir of the samples — noise).
    run_hists = {}
    for _name, _h in (after.get("hists") or {}).items():
        _hb = (before.get("hists") or {}).get(_name) or {}
        _dc = _h.get("count", 0) - _hb.get("count", 0)
        if _dc > 0:
            run_hists[_name] = {
                **_h, "count": _dc,
                "sum": _h.get("sum", 0.0) - _hb.get("sum", 0.0)}
    stage_table = _report.serve_stage_table({**after,
                                             "hists": run_hists})
    slos = _slo.evaluate(after, publish=False)

    def hist_delta(name: str, field: str) -> float:
        return ((after["hists"].get(name) or {}).get(field, 0.0)
                - (before["hists"].get(name) or {}).get(field, 0.0))

    batch_rounds = delta("serve.batch.rounds")
    batch_n = hist_delta("serve.batch.size", "count")
    row = {
        "shards": num_shards,
        "buckets": num_buckets,
        "minibatch": minibatch,
        "mode": "open" if open_qps > 0 else "closed",
        "serve_mode": router.mode,
        "concurrency": concurrency,
        "requests": done[0],
        "errors": errors[0],
        "error_rate": errors[0] / max(done[0] + errors[0], 1),
        "qps": done[0] / elapsed,
        "p50_ms": _pct(lat_ms, 0.50),
        "p99_ms": _pct(lat_ms, 0.99),
        "p999_ms": _pct(lat_ms, 0.999),
        "swap_count": delta("serve.swaps"),
        "swap_stall_ms": stall_ms,
        "router_retries": delta("serve.router.retries"),
        "epoch_retries": delta("serve.router.epoch_retries"),
        "respawns": state["respawns"],
        # overload-protection plane: goodput (replies within deadline)
        # vs raw throughput, plus shed/hedge/degrade tallies
        "deadline_ms": deadline_ms,
        "goodput_qps": good[0] / elapsed,
        "deadline_misses": misses[0],
        "sheds_deadline": delta("serve.shed.deadline"),
        "sheds_busy": delta("serve.shed.busy"),
        "sheds_admit": delta("admit.sheds"),
        "hedges_issued": delta("serve.hedge.issued"),
        "hedge_wins": delta("serve.hedge.wins"),
        "degraded_replies": degraded[0],
        # micro-batcher plane (score mode; zeros under fetch)
        "batch_rounds": batch_rounds,
        "batch_coalesced": delta("serve.batch.coalesced"),
        "batch_mean_size": (hist_delta("serve.batch.size", "sum")
                            / batch_n if batch_n else 0.0),
    }
    for stage, st in (stage_table.get("stages") or {}).items():
        row[f"{stage}_ms"] = st["p50_ms"]
    if stage_table:
        row["stage_explained_frac"] = stage_table.get("explained_frac")
    row["slo_ok"] = all(v["ok"] for v in slos) if slos else None
    if verbose and stage_table:
        print("[serve-lab] stage attribution (p50/p99/mean ms):",
              flush=True)
        for stage, st in stage_table["stages"].items():
            print(f"  {stage:<7} p50={st['p50_ms']:8.3f} "
                  f"p99={st['p99_ms']:8.3f} mean={st['mean_ms']:8.3f} "
                  f"n={st['count']}", flush=True)
        if stage_table.get("explained_frac") is not None:
            print(f"  request mean {stage_table['latency_mean_ms']:.3f} "
                  f"ms (p50 {stage_table['latency_p50_ms']:.3f} ms), "
                  f"{stage_table['explained_frac'] * 100:.0f}% explained "
                  "by batch_wait+pack+fanout+sum+score", flush=True)
    if verbose and slos:
        print("\n".join(_slo.format_lines(slos)), flush=True)
    router.close()
    with state_lock:
        servers = list(state["servers"])
    for s in servers:
        s.stop()
    if chaos_at_s > 0 and errors[0]:
        raise AssertionError(
            f"chaos run dropped {errors[0]} requests; the router must "
            "absorb a shard death with zero failures")
    return row


def overload_sweep(num_shards: int = 2, num_buckets: int = 1 << 20,
                   minibatch: int = 256, nnz: int = 32,
                   duration_s: float = 3.0, concurrency: int = 8,
                   deadline_ms: float = 0.0, seed: int = 0,
                   serve_mode: str = "auto",
                   verbose: bool = True) -> dict:
    """The overload drill: measure capacity closed-loop, then step
    offered load to 3x capacity open-loop with the protection stack on
    (WH_ADMIT_AIMD + WH_HEDGE + deadline shedding) and a per-request
    deadline. Congestion collapse would show as goodput falling off a
    cliff past 1x; the pass bar is goodput >= 80% of capacity at 3x,
    zero hard errors, and hedge overhead within its <=5% budget."""
    deadline_ms = deadline_ms or 500.0  # the serving latency SLO
    knobs = {"WH_ADMIT_AIMD": "1", "WH_HEDGE": "1",
             "WH_DEADLINE_SHED": "1"}
    saved = {k: os.environ.get(k) for k in knobs}
    os.environ.update(knobs)
    steps = []
    try:
        # capacity = what the PROTECTED stack sustains closed-loop (the
        # stack's own overhead — deadline stamps, gate bookkeeping,
        # hedge timers — belongs in the baseline the 3x bar is 80% of)
        if verbose:
            print("[serve-lab] overload sweep: measuring capacity "
                  "(closed loop)...", flush=True)
        cap_row = run(num_shards, num_buckets, minibatch, nnz,
                      duration_s, concurrency, seed=seed,
                      serve_mode=serve_mode, verbose=False)
        capacity = cap_row["qps"]
        if verbose:
            print(f"[serve-lab] capacity {capacity:.0f} qps "
                  f"(p50 {cap_row['p50_ms']:.1f} ms)", flush=True)
        for mult in (1.0, 1.5, 2.0, 3.0):
            offered = capacity * mult
            # size the driver pool for fail-fast holds, not full-
            # deadline holds: with the router gate bouncing at entry a
            # thread holds a request for ~the admitted service latency
            # (or ~0 for a bounce), so a modest pool keeps the Poisson
            # pacing — and client threads share this box's cores with
            # the servers, so overshooting the pool THROTTLES the very
            # capacity being measured
            conc = int(min(max(concurrency, offered * 0.05), 32))
            # longer than the capacity probe: the router's AIMD gate
            # starts at WH_ADMIT_MAX and needs ~1s of completions to
            # walk down to the sustainable limit — the pass bar should
            # measure the converged regime, not the transient
            row = run(num_shards, num_buckets, minibatch, nnz,
                      max(duration_s, 6.0), conc, open_qps=offered,
                      deadline_ms=deadline_ms, seed=seed,
                      serve_mode=serve_mode, verbose=False)
            row["offered_qps"] = round(offered, 1)
            row["offered_x"] = mult
            steps.append(row)
            if verbose:
                print(f"[serve-lab] {mult:.1f}x ({offered:6.0f} qps "
                      f"offered): goodput {row['goodput_qps']:6.0f} qps, "
                      f"throughput {row['qps']:6.0f} qps, "
                      f"p99 {row['p99_ms']:7.1f} ms, "
                      f"{row['deadline_misses']} missed, "
                      f"{row['sheds_deadline'] + row['sheds_busy'] + row['sheds_admit']} shed, "
                      f"{row['hedges_issued']} hedged, "
                      f"{row['errors']} errors", flush=True)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    last = steps[-1]
    hedge_frac = last["hedges_issued"] / max(last["requests"], 1)
    return {
        "mode": "overload",
        "serve_mode": cap_row["serve_mode"],
        "shards": num_shards, "buckets": num_buckets,
        "minibatch": minibatch, "deadline_ms": deadline_ms,
        "capacity_qps": capacity,
        "steps": [{k: r[k] for k in (
            "offered_x", "offered_qps", "qps", "goodput_qps", "p50_ms",
            "p99_ms", "deadline_misses", "sheds_deadline", "sheds_busy",
            "sheds_admit", "hedges_issued", "degraded_replies",
            "errors")}
            for r in steps],
        "goodput_at_3x_qps": last["goodput_qps"],
        "goodput_at_3x_frac": last["goodput_qps"] / max(capacity, 1e-9),
        "hedge_frac_at_3x": hedge_frac,
        "errors": sum(r["errors"] for r in steps),
        "ok": bool(last["goodput_qps"] >= 0.8 * capacity
                   and hedge_frac <= 0.05
                   and all(r["errors"] == 0 for r in steps)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--buckets", type=int, default=1 << 20)
    ap.add_argument("--minibatch", type=int, default=256)
    ap.add_argument("--nnz", type=int, default=32)
    ap.add_argument("--duration", type=float, default=3.0)
    ap.add_argument("--concurrency", type=int, default=4)
    ap.add_argument("--open-qps", type=float, default=0.0,
                    help="open-loop target QPS (0 = closed loop)")
    ap.add_argument("--mode", default="auto",
                    choices=("auto", "fetch", "score"),
                    help="serving dataflow: fetch (pull weight slices) "
                         "or score (shard-local partials + micro-"
                         "batching); auto picks score when the scorer "
                         "supports it")
    ap.add_argument("--swap", action="store_true",
                    help="write a newer snapshot version every 0.5s "
                         "so the shards hot-swap under load")
    ap.add_argument("--chaos", action="store_true",
                    help="kill shard 0 mid-load and respawn it on a "
                         "new port; fails unless zero requests failed")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline budget; goodput (replies "
                         "within it) is reported separately from "
                         "throughput")
    ap.add_argument("--overload", action="store_true",
                    help="overload drill: measure capacity, then step "
                         "offered load to 3x with admission control, "
                         "hedging, and deadline shedding on; fails "
                         "unless goodput at 3x stays >= 80%% of "
                         "capacity with zero hard errors")
    ap.add_argument("--prof", action="store_true",
                    help="run with the sampling profiler on "
                         "(obs/pyprof.py) and print the heaviest folded "
                         "stacks + measured overhead at the end")
    ap.add_argument("--json", action="store_true",
                    help="print only the [serve-lab] machine line")
    args = ap.parse_args(argv)
    prof = None
    if args.prof:
        # the import-time init already ran with WH_PROF unset; re-arm
        from wormhole_tpu.obs import pyprof as _pyprof

        os.environ["WH_PROF"] = "1"
        prof = _pyprof.init_from_env()
    try:
        return _main(args)
    finally:
        if os.environ.get("WH_SAN") == "1":
            # the lab is one process of threads — exactly the workload
            # the sanitizer watches; arm with WH_SAN=1 before launch
            from tools import wormsan

            print("[serve-lab] san: "
                  + json.dumps(wormsan.summary(), sort_keys=True),
                  flush=True)
            for f in wormsan.findings():
                print(f"[serve-lab] san [{f['detector']}] "
                      f"{f['message']}", flush=True)
        if prof is not None:
            print(f"[serve-lab] prof: overhead "
                  f"{prof.overhead_frac() * 100:.2f}% "
                  f"(budget {prof.budget * 100:.0f}%), "
                  "heaviest stacks:", flush=True)
            for line in prof.folded(top=8):
                print(f"  {line}", flush=True)
            prof.stop()


def _main(args) -> int:
    if args.overload:
        row = overload_sweep(
            num_shards=args.shards, num_buckets=args.buckets,
            minibatch=args.minibatch, nnz=args.nnz,
            duration_s=args.duration, concurrency=args.concurrency,
            deadline_ms=args.deadline_ms, serve_mode=args.mode,
            verbose=not args.json)
        print("[serve-lab] " + json.dumps(row, sort_keys=True),
              flush=True)
        return 0 if row["ok"] else 1
    row = run(num_shards=args.shards, num_buckets=args.buckets,
              minibatch=args.minibatch, nnz=args.nnz,
              duration_s=args.duration, concurrency=args.concurrency,
              open_qps=args.open_qps,
              swap_every_s=0.5 if args.swap else 0.0,
              chaos_at_s=args.duration / 3 if args.chaos else 0.0,
              deadline_ms=args.deadline_ms, serve_mode=args.mode,
              verbose=not args.json)
    if not args.json:
        print(f"{row['mode']}-loop x{row['concurrency']}: "
              f"{row['qps']:.0f} qps, p50 {row['p50_ms']:.2f} ms, "
              f"p99 {row['p99_ms']:.2f} ms, p999 {row['p999_ms']:.2f} "
              f"ms, {row['requests']} ok / {row['errors']} failed, "
              f"{row['swap_count']} swaps "
              f"({row['swap_stall_ms']:.2f} ms stall), "
              f"{row['respawns']} respawns", flush=True)
    print("[serve-lab] " + json.dumps(row, sort_keys=True), flush=True)
    if row["errors"]:
        return 1
    # error-kind SLO violations fail the lab; latency burns are only
    # reported (this box's speed is not an objective)
    slo_failed = any(v["kind"] == "errors" and not v["ok"]
                     for v in _slo.evaluate(_obs.REGISTRY.snapshot(),
                                            publish=False))
    return 1 if slo_failed else 0


if __name__ == "__main__":
    sys.exit(main())
