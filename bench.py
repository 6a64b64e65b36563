#!/usr/bin/env python
"""Benchmarks over the BASELINE.json reference configs.

Emits ONE JSON line per config — difacto (FM, Criteo operating shape),
kmeans (MNIST-784 shape), GBDT (HIGGS shape), linear FTRL at the
Criteo-1TB table scale (2^26 hashed buckets) — and LAST the headline
linear FTRL throughput at Criteo-Kaggle shape, the one number the
reference itself publishes (~2.0e6 examples/sec aggregate on 10 CPU
workers + 10 servers, doc/tutorial/criteo_kaggle.rst:66-75; BASELINE.md).
The driver parses the last line; the earlier lines carry the wider
coverage (VERDICT r1 item 6).

The synthetic workloads reproduce each dataset's shape AND key
statistics: Criteo rows carry 39 features (13 integer + 26 categorical,
criteo_parser.h:55-82) with per-field cardinalities spanning ~10 to
~10M and Zipf-ish within-field skew, hashed into the bucket table. Key
skew matters: it drives the table-tile locality the TPU kernels exploit,
exactly as it drives cache locality for the reference's CPU servers.

All device timing is two-point — t(3N) - t(N) over chained jitted steps
forced by one scalar fetch — so the fixed dispatch/fetch latency cancels
and what remains is the device's time per step.

Every row names the platform, device kind and device count of the process
that produced its number: this process for the in-process accelerator
rows, the launcher children (pinned to JAX_PLATFORMS=cpu below, because
this process may hold the chip) for the PS and BSP rows. A failed group
prints its traceback, the remaining groups still run, and the exit code
is non-zero.
"""

import json
import os
import re
import sys
import time
import traceback

import numpy as np

BASELINE_EXAMPLES_PER_SEC = 2.0e6  # criteo_kaggle.rst tutorial log

# 64k examples per device step: the large synchronous device batches of
# the TPU design (SURVEY §7 "async PS semantics"); the reference's own
# Criteo-1TB operating point uses minibatch=100000
# (learn/difacto/guide/criteo.conf). Throughput plateaus here on v5e.
MINIBATCH = 1 << 16
NUM_BUCKETS = 1 << 22    # 4M hashed buckets (headline config)
WARMUP_STEPS = 5
BENCH_STEPS = 60

# Criteo-like per-field value cardinalities: 13 integer features (small
# ranges after the log transform) + 26 categorical with a mix of tiny
# (geo/flag-like) and huge (id-like) vocabularies.
FIELD_CARDS = [50] * 13 + [
    10, 100, 1000, 10_000, 100_000, 1_000_000, 10_000_000,
    25, 250, 2500, 25_000, 250_000, 2_500_000,
    40, 400, 4000, 40_000, 400_000, 4_000_000,
    60, 600, 6000, 60_000, 600_000,
    80, 800,
]
assert len(FIELD_CARDS) == 39


def criteo_field_draws(rng, n):
    """(n, 39) per-field value draws: Zipf-ish within each field's
    vocabulary (CTR datasets are power-law within each field)."""
    draws = np.empty((n, len(FIELD_CARDS)), dtype=np.uint64)
    for f, card in enumerate(FIELD_CARDS):
        draws[:, f] = rng.zipf(1.2, size=n).astype(np.uint64) % card
    return draws


def mix_field_values(draws):
    """64-bit key per (field, value): per-field salt then a splitmix-style
    mix, matching the criteo parser's field-salted hashing
    (criteo_parser.h:69-82)."""
    with np.errstate(over="ignore"):  # 64-bit mixing wraps by design
        x = draws + (np.arange(draws.shape[1], dtype=np.uint64)
                     * np.uint64(0x9E3779B97F4A7C15))
        x ^= x >> np.uint64(30)
        x *= np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(27)
    return x


def synth_criteo_batch(rng, minibatch, num_buckets=None):
    """Hashed keys with per-field Zipf-ish value draws."""
    if num_buckets is None:
        num_buckets = NUM_BUCKETS
    nnz = len(FIELD_CARDS)
    vals = mix_field_values(criteo_field_draws(rng, minibatch))
    idx = (vals.reshape(-1) % np.uint64(num_buckets)).astype(np.int32)
    seg = np.repeat(np.arange(minibatch, dtype=np.int32), nnz)
    val = np.ones(minibatch * nnz, dtype=np.float32)
    label = (rng.random(minibatch) < 0.3).astype(np.float32)
    mask = np.ones(minibatch, dtype=np.float32)
    return seg, idx, val, label, mask


def this_device():
    """Platform, device kind and device count of THIS process's JAX
    backend — the label of every row measured in-process."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def child_device(stdout):
    """The same label for a row measured in launcher children, read off
    the start-up line the worker prints (apps/_runner._run_worker;
    parallel/mesh.describe_placement). No line, no row."""
    m = re.search(r"backend=(\S+) device_kind='([^']*)' devices=(\d+)",
                  stdout)
    if m is None:
        raise AssertionError("child printed no placement line:\n"
                             + stdout[-2000:])
    return {"platform": m.group(1), "device_kind": m.group(2),
            "device_count": int(m.group(3))}


def emit(metric, value, unit, vs_baseline=None, device=None, **extra):
    """One BENCH JSON line; keyword extras (e.g. an `obs` telemetry
    snapshot) ride along as additional row fields. `device` labels the
    process that produced the number (default: this one)."""
    row = {"metric": metric, "value": round(value, 1), "unit": unit,
           "vs_baseline": (round(vs_baseline, 3)
                           if vs_baseline is not None else None)}
    row.update(device if device is not None else this_device())
    row.update({k: v for k, v in extra.items() if v is not None})
    print(json.dumps(row), flush=True)
    return row


def two_point(run_chain, steps):
    """Wall-clock per unit of work: run N then 3N chained steps; the
    difference cancels fixed dispatch/fetch latency."""
    run_chain(WARMUP_STEPS)
    t0 = time.perf_counter()
    run_chain(steps)
    t_short = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_chain(3 * steps)
    t_long = time.perf_counter() - t0
    return max(t_long - t_short, 1e-9) / (2 * steps)


# ---------------------------------------------------------------- linear
def bench_linear(num_buckets, minibatch, steps=BENCH_STEPS):
    from wormhole_tpu.models.linear import LinearConfig, LinearLearner
    from wormhole_tpu.ops import coo_kernels as ck
    from wormhole_tpu.parallel.mesh import make_mesh

    cfg = LinearConfig(
        minibatch=minibatch,
        num_buckets=num_buckets,
        nnz_per_row=len(FIELD_CARDS),
        algo="ftrl",
        lr_eta=0.1,
        lambda_l1=1.0,
        # the documented throughput opt-in (default is "auto" = f32 when
        # quantization is off, matching XLA numerics; PERF.md has both)
        kernel_dtype="bf16",
    )
    lrn = LinearLearner(cfg, make_mesh(num_data=1, num_model=1))
    rng = np.random.default_rng(0)
    batches = []
    for _ in range(8):
        seg, idx, val, label, mask = synth_criteo_batch(
            rng, minibatch, num_buckets)
        if lrn.use_pallas and lrn.ensure_compact(idx):
            tc = ck.pack_tile_coo(idx, seg, val, num_buckets,
                                  lrn._compact_cap,
                                  capacity=cfg.row_capacity,
                                  rm_rows=minibatch,
                                  rm_width=cfg.nnz_per_row)
            batches.append(tuple(lrn._tcoo_args(tc, label, mask,
                                                train=True)))
            step = lrn._tcoo_steps[0]
        elif lrn.use_pallas:
            p = ck.pack_sorted_coo(idx, seg, val, num_buckets,
                                   capacity=cfg.row_capacity)
            batches.append(tuple(lrn._coo_args(p, label, mask)))
            step = lrn._train_step_coo
        else:
            batches.append(tuple(lrn._shard(seg, idx, val, label, mask)))
            step = lrn._train_step

    def run_chain(n):
        state = lrn.store.state
        prog = None
        for i in range(n):
            state, prog = step(state, *batches[i % len(batches)])
        float(prog["objv"])  # forces the whole chain
        lrn.store.state = state

    sec = two_point(run_chain, steps)
    return minibatch / sec


def bench_linear_epoch2(num_buckets, minibatch, steps=30):
    """Epoch-2 steady state at the headline shape: the packed-batch
    cache is warm, so a loader thread replays prepared batches from
    memory and stages them to the device (stage_batch) while the main
    thread steps — the full data/pack_cache.py pipeline minus the one
    cold pack per batch. Returns (examples/sec, loader stall seconds,
    wall seconds, cache hit rate): the acceptance bar is stall < 15%
    of wall, i.e. the device — not the host — paces epoch 2+."""
    import queue as _queue
    import threading

    from wormhole_tpu.data import pack_cache as pc
    from wormhole_tpu.data.rowblock import RowBlock
    from wormhole_tpu.models.linear import LinearConfig, LinearLearner
    from wormhole_tpu.parallel.mesh import make_mesh

    cfg = LinearConfig(
        minibatch=minibatch,
        num_buckets=num_buckets,
        nnz_per_row=len(FIELD_CARDS),
        algo="ftrl",
        lr_eta=0.1,
        lambda_l1=1.0,
        kernel_dtype="bf16",
    )
    lrn = LinearLearner(cfg, make_mesh(num_data=1, num_model=1))
    rng = np.random.default_rng(0)
    nnz_row = len(FIELD_CARDS)
    cache = pc.PackCache(mem_bytes=8 << 30)
    nbatch = 8
    blks = []
    for _ in range(nbatch):
        seg, idx, val, label, mask = synth_criteo_batch(
            rng, minibatch, num_buckets)
        offset = np.arange(minibatch + 1, dtype=np.int64) * nnz_row
        blks.append(RowBlock(label=label, offset=offset,
                             index=idx.astype(np.uint64), value=val))
    # epoch 1 (cold): pack once, fill the cache
    for i, blk in enumerate(blks):
        cache.put(pc.fingerprint("bench", i), lrn.prepare_batch(blk))

    def run_epoch(n):
        q: _queue.Queue = _queue.Queue(maxsize=4)
        END = object()

        def loader():
            for i in range(n):
                b = cache.get(pc.fingerprint("bench", i % nbatch))
                if b is None:  # eviction fallback; not expected here
                    b = lrn.prepare_batch(blks[i % nbatch])
                q.put(lrn.stage_batch(b, train=True))
            q.put(END)

        threading.Thread(target=loader, daemon=True).start()
        stall = 0.0
        while True:
            t0 = time.perf_counter()
            item = q.get()
            stall += time.perf_counter() - t0
            if item is END:
                break
            # train_batch fetches the progress scalars, so every step
            # blocks to completion — the wall below is honest per-step
            # time including the fetch, like the solver's own loop
            lrn.train_batch(item)
        return stall

    run_epoch(WARMUP_STEPS)  # compile + device warmup
    t0 = time.perf_counter()
    stall = run_epoch(steps)
    wall = time.perf_counter() - t0
    hit = cache.stats()["hit_rate"]
    return minibatch * steps / wall, stall, wall, hit


# --------------------------------------------------------------- difacto
def bench_difacto(steps=20):
    """FM at the reference's Criteo operating shape: dim=8, two tables
    (w over 4M buckets, V over 1M), count-threshold admission on
    (learn/difacto/guide/criteo.conf; config.proto)."""
    import jax

    from wormhole_tpu.models.difacto import DifactoConfig, DifactoLearner
    from wormhole_tpu.parallel.mesh import make_mesh

    mb = 1 << 16
    cfg = DifactoConfig(
        minibatch=mb,
        num_buckets=1 << 22,
        v_buckets=1 << 20,
        nnz_per_row=len(FIELD_CARDS),
        dim=8,
        threshold=2,
        lr_eta=0.1,
        lambda_l1=1.0,
        kernel_dtype="bf16",  # documented opt-in; default "auto" = f32
    )
    lrn = DifactoLearner(cfg, make_mesh(num_data=1, num_model=1))
    rng = np.random.default_rng(1)
    import types

    import jax.numpy as jnp

    batches = []
    for _ in range(4):
        seg, idx, val, label, mask = synth_criteo_batch(
            rng, mb, cfg.num_buckets)
        if lrn._use_fm_pallas:
            db = types.SimpleNamespace(seg=seg, idx=idx, val=val)
            pk = lrn._pack_fm(db, train=True)
            args = [jax.device_put(a) for a in
                    lrn._fm_args(pk, label, mask, train=True)]
            batches.append(tuple(args))
        else:
            vidx = (idx % np.int32(cfg.vb)).astype(np.int32)
            put = lambda x: jax.device_put(jnp.asarray(x), lrn._bsh1)
            batches.append((put(seg), put(idx), put(vidx), put(val),
                            put(label), put(mask)))
    step = (lrn._fm_steps[0] if lrn._use_fm_pallas else lrn._train_step)

    def run_chain(n):
        state, vstate = lrn.store.state, lrn.vstore.state
        prog = None
        for i in range(n):
            lrn._rng, sub = jax.random.split(lrn._rng)
            state, vstate, prog = step(
                state, vstate, *batches[i % len(batches)], sub)
        float(prog["objv"])
        lrn.store.state, lrn.vstore.state = state, vstate

    sec = two_point(run_chain, steps)
    return mb / sec


# ------------------------------------------------------- distributed PS
def bench_linear_ps(num_buckets=1 << 26, minibatch=25000, nrows=100_000):
    """Multi-process PS data plane at the Criteo-1TB table scale
    (2^26 hashed buckets, criteo.conf operating point): launches the
    real scheduler/server/worker processes through the launcher and
    measures (a) worker examples/sec vs a single-process run on the
    same data, and (b) wire bytes per sync — which the sparse
    touched-key wire (runtime/ps_server.py) keeps proportional to the
    minibatch's unique keys, not the table (a dense (z, n) push at this
    scale would be ~0.5 GB per sync).

    One worker + one server: this box has a single core, so worker
    counts > 1 would only measure core timesharing; with one worker the
    single-process run is the exact compute baseline and the measured
    gap IS the PS-plane overhead."""
    import subprocess
    import tempfile
    import types

    rng = np.random.default_rng(7)
    nnz = len(FIELD_CARDS)
    repo = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as td:
        nparts = 4
        rows_part = nrows // nparts
        for p in range(nparts):
            _, idx, _, label, _ = synth_criteo_batch(
                rng, rows_part, num_buckets)
            ids = idx.reshape(rows_part, nnz)
            with open(f"{td}/train-{p}.libsvm", "w") as fh:
                for i in range(rows_part):
                    feats = " ".join(f"{k}:1" for k in ids[i])
                    fh.write(f"{int(label[i])} {feats}\n")
        conf = f"""
train_data = "{td}/train-.*"
algo = ftrl
lambda_l1 = 1
minibatch = {minibatch}
num_buckets = {num_buckets}
num_parts_per_file = 1
max_data_pass = 2
max_delay = 2
print_sec = 3600
"""
        confp = f"{td}/ps.conf"
        with open(confp, "w") as fh:
            fh.write(conf)
        # the children run on CPU: this process may hold the chip, and
        # a chip belongs to one process at a time. Their rows say so
        # (child_device reads the platform back from the worker's log).
        env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")
        env.pop("JAX_PLATFORM_NAME", None)

        def run_group(argv, timeout, extra_env=None):
            """subprocess.run with whole-process-group kill on timeout:
            run()'s own timeout kills only the direct child, leaking the
            launcher's role processes to compete with every later bench
            config (observed after the r3 timeout)."""
            e = dict(env, **extra_env) if extra_env else env
            p = subprocess.Popen(argv, stdout=subprocess.PIPE,
                                 stderr=subprocess.PIPE, text=True,
                                 env=e, cwd=repo, start_new_session=True)
            try:
                out, err = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                os.killpg(p.pid, 9)
                p.wait()
                raise
            return types.SimpleNamespace(returncode=p.returncode,
                                         stdout=out, stderr=err)

        # the distributed run also records its obs telemetry so the
        # BENCH row carries wire volume + RPC quantiles alongside the
        # throughput (run_report.json, wormhole_tpu/obs/report.py).
        # Two runs: the production operating point (async overlapped
        # sync + key caching) and the plain synchronous plane, so the
        # row shows the overlap/caching gain, not just one number.
        def run_dist(tag, async_sync, plane="tcp", extra_argv=(),
                     wire_env=None):
            obs_dir = f"{td}/obs_dist_{tag}"
            flag = "1" if async_sync else "0"
            ev = {"WH_OBS_DIR": obs_dir, "WH_ASYNC_SYNC": flag,
                  "WH_KEYCACHE": flag, "WH_PS_PLANE": plane}
            if wire_env:
                ev.update(wire_env)
            if plane == "hot":
                # the worker needs a real >= 2 device mesh; must land
                # before its jax import, hence via the environment
                ev["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "")
                    + " --xla_force_host_platform_device_count=4").strip()
            r = run_group(
                [sys.executable, "-m", "wormhole_tpu.launcher.dmlc_tpu",
                 "-n", "1", "-s", "1", "--",
                 sys.executable, "-m", "wormhole_tpu.apps.linear", confp,
                 *extra_argv],
                timeout=600, extra_env=ev)
            assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
            m = re.search(r"\[ps-wire\] (\{.*\})", r.stdout)
            assert m, r.stdout[-2000:]
            w = json.loads(m.group(1))
            w["device"] = child_device(r.stdout)
            eps = w["last_round_nex"] / max(w["last_round_sec"], 1e-9)
            return w, eps, obs_dir

        def grab_obs(obs_dir, keys):
            try:
                with open(f"{obs_dir}/run_report.json") as fh:
                    s = json.load(fh)["summary"]
                return {k: s.get(k) for k in keys}
            except (OSError, KeyError, json.JSONDecodeError):
                return None  # telemetry must not fail the bench

        wire, dist_eps, obs_dir = run_dist("async", True)
        wire_off, dist_eps_off, _ = run_dist("sync", False)
        # the wire codec at its full operating point on the same data:
        # int8 error-feedback deltas both directions + byte-shuffle
        # framing (WH_WIRE family, runtime/net.py). Same async+keycache
        # plane as the recorded dist row, so the delta IS the codec.
        wire_q, dist_eps_q, _ = run_dist(
            "int8ef", True,
            wire_env={"WH_WIRE": "int8", "WH_WIRE_EF": "1",
                      "WH_WIRE_COMP": "bshuf"})
        # the hot plane at the same operating point: tables sharded over
        # the forced 4-device host mesh, TCP tier at flush barriers only
        wire_hot, hot_eps, obs_dir_hot = run_dist(
            "hot", True, plane="hot", extra_argv=("model_shards=2",))
        obs = grab_obs(obs_dir, (
            "num_push", "num_pull", "bytes_pushed", "bytes_pulled",
            "net_bytes_sent", "net_bytes_recv",
            "rpc_p50_ms", "rpc_p99_ms",
            "keycache_hits", "keycache_misses"))
        obs_hot = grab_obs(obs_dir_hot, (
            "num_push", "num_pull", "bytes_pushed", "bytes_pulled",
            "net_bytes_sent", "net_bytes_recv",
            "hot_plane_steps", "hot_plane_flushes"))

        r1 = run_group(
            [sys.executable, "-m", "wormhole_tpu.apps.linear", confp],
            timeout=600)
        assert r1.returncode == 0, r1.stdout[-2000:] + r1.stderr[-2000:]
        walls = re.findall(r"train pass \d+: .* wall ([0-9.]+)s",
                           r1.stdout)
        assert walls, r1.stdout[-2000:]
        single_eps = nrows / float(walls[-1])
        single_device = child_device(r1.stdout)

    # dense wire at this operating point: push z+n deltas, pull w+z+n
    dense_bytes = 5 * num_buckets * 4
    return dist_eps, dist_eps_off, single_eps, single_device, wire, \
        wire_off, dense_bytes, obs, hot_eps, wire_hot, obs_hot, wire_q, \
        dist_eps_q


# ---------------------------------------------------------------- kmeans
def bench_kmeans(steps=30, kernel_dtype="bf16"):
    """Spherical k-means assignment+accumulate throughput at the
    BASELINE MNIST-784 shape (k=10). Recorded at BOTH kernel dtypes:
    bf16 is the documented opt-in (values rounded on input, f32
    accumulation), f32 is bit-exact vs the XLA scatter path — the
    record should show both sides of that trade (VERDICT r4 weak #4)."""
    import jax
    import jax.numpy as jnp

    from wormhole_tpu.models.kmeans import KmeansConfig, KmeansLearner
    from wormhole_tpu.parallel.mesh import make_mesh

    mb, d, k, nnz_row = 16384, 784, 10, 160
    cfg = KmeansConfig(num_clusters=k, dim=d, minibatch=mb,
                       nnz_per_row=nnz_row,
                       kernel_dtype=kernel_dtype)
    lrn = KmeansLearner(cfg, make_mesh(num_data=1, num_model=1))
    assert lrn._use_packed  # the run loop's fast path at this shape
    rng = np.random.default_rng(2)
    # MNIST-ish: ~20% dense nonzeros
    nnz = mb * nnz_row
    seg = np.repeat(np.arange(mb, dtype=np.int32), nnz_row)
    batches = []
    for _ in range(4):
        idx = rng.integers(0, d, size=nnz).astype(np.int32)
        val = rng.random(nnz).astype(np.float32)
        mask = jax.device_put(jnp.ones(mb, jnp.float32), lrn._bsh)
        batches.append((lrn.pack_batch(seg, idx, val), mask))
    C = jnp.asarray(rng.standard_normal((k, d)).astype(np.float32))

    def run_chain(n):
        nonlocal C
        cost = None
        Cl = C
        for i in range(n):
            pk, mask = batches[i % len(batches)]
            sums, counts, cost = lrn._assign_packed(Cl, *pk, mask)
            Cl = sums / jnp.maximum(counts[:, None], 1.0)
        float(cost)
        C = Cl

    sec = two_point(run_chain, steps)
    return mb / sec


# ------------------------------------------------------------------ gbdt
def bench_gbdt(rounds=8):
    """Histogram-GBDT boosting rounds/sec at the BASELINE HIGGS shape
    (28 dense features, depth 6, 256 bins), 2M synthetic rows."""
    import jax

    from wormhole_tpu.models.gbdt import (BinnedDataset, GbdtConfig,
                                          GbdtLearner, bin_matrix,
                                          quantile_edges)
    from wormhole_tpu.parallel.mesh import batch_sharding, make_mesh

    n, d = 2_000_000, 28
    cfg = GbdtConfig(dim=d, max_depth=6, num_round=rounds, eta=0.3)
    lrn = GbdtLearner(cfg, make_mesh(num_data=1, num_model=1))
    rng = np.random.default_rng(3)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X[:, :4].sum(axis=1) + 0.5 * rng.standard_normal(n) > 0)
    lrn.edges = quantile_edges(X[: 1 << 17], cfg.max_bin)
    binned = np.empty((n, d), np.uint8)
    for lo in range(0, n, 1 << 18):
        hi = min(lo + (1 << 18), n)
        binned[lo:hi] = bin_matrix(X[lo:hi], lrn.edges)
    b1 = batch_sharding(lrn.mesh, 1)
    b2 = batch_sharding(lrn.mesh, 2)
    ds = BinnedDataset(
        binned=jax.device_put(binned, b2),
        label=jax.device_put(y.astype(np.float32), b1),
        mask=jax.device_put(np.ones(n, np.float32), b1),
        num_real=n,
    )
    round_fn = lrn._fused_round_fn()
    margin = lrn._base_margins(ds)

    def do_rounds(r):
        nonlocal margin
        for _ in range(r):
            # one dispatch per round: grad/hess + all levels + update
            tree, node, margin = round_fn(ds.binned, ds.label, ds.mask,
                                          margin)

    import jax.numpy as jnp

    def force():
        float(jnp.sum(margin))  # one scalar fetch ends the chained rounds

    do_rounds(2)  # warmup/compile
    force()
    t0 = time.perf_counter()
    do_rounds(rounds)
    force()
    sec = (time.perf_counter() - t0) / rounds
    return 1.0 / sec, n / sec


# ------------------------------------------------------------- BSP ring
def bench_bsp(workers=3):
    """Fault-free overhead of the native BSP allreduce stack
    (`bsp = 1`, launcher `-s 0`, runtime/allreduce.py): per-collective
    ring time and per-checkpoint cost straight from the run report,
    plus the wall-clock price of one worker kill + respawn
    (recovery_overhead_s). chaos_lab verifies the recovered model is
    bit-identical; this row prices the same machinery."""
    import tempfile

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.chaos_lab import run_bsp_job, synth_libsvm

    rows = []
    with tempfile.TemporaryDirectory() as td:
        for p in range(workers):
            synth_libsvm(f"{td}/train-{p}.libsvm", 400, seed=p)
        synth_libsvm(f"{td}/val.libsvm", 200, seed=9)
        jobs = [
            ("gbdt", "wormhole_tpu.apps.gbdt",
             [f"train_data={td}/train-.*", f"eval_data={td}/val.libsvm",
              "bsp=1", "num_round=4", "max_depth=3", "max_bin=16",
              "minibatch=256"],
             "worker:1:kill@allreduce:6"),
            ("lbfgs", "wormhole_tpu.apps.lbfgs_linear",
             [f"data={td}/train-.*", "bsp=1", "max_lbfgs_iter=6",
              "reg_L2=0.001", "minibatch=256"],
             "worker:1:kill@allreduce:4"),
        ]
        for tag, module, app_args, kill in jobs:
            # restarts=1 even fault-free: supervision is what arms the
            # snapshot dir, and the checkpoint cost is part of the
            # overhead being priced
            rc, out, wall, rep = run_bsp_job(
                module, app_args, "", workers=workers, restarts=1,
                timeout=300, obs_dir=f"{td}/obs_{tag}_base")
            assert rc == 0, out[-3000:]
            assert rep is not None, f"{tag}: no run_report.json"
            s = rep["summary"]
            hists = rep.get("hists") or {}
            ar = hists.get("bsp.allreduce_s") or {}
            ck = hists.get("bsp.checkpoint_s") or {}
            rc2, out2, wall_kill, rep_kill = run_bsp_job(
                module, app_args, kill, workers=workers, restarts=1,
                timeout=300, obs_dir=f"{td}/obs_{tag}_kill")
            assert rc2 == 0, out2[-3000:]
            nck = max(int(s.get("bsp_checkpoints") or 0), 1)
            ksum = (rep_kill or {}).get("summary") or {}
            rows.append((tag, {
                "allreduce_ms": (ar.get("mean") or 0.0) * 1e3,
                "allreduce_p99_ms": round((ar.get("p99") or 0.0) * 1e3, 3),
                "checkpoint_ms": round((ck.get("mean") or 0.0) * 1e3, 3),
                "checkpoint_bytes": int(s.get("bsp_checkpoint_bytes", 0))
                // nck,
                "bsp_rounds": int(s.get("bsp_rounds", 0)),
                "bsp_checkpoints": int(s.get("bsp_checkpoints", 0)),
                "wall_s": round(wall, 2),
                "recovery_overhead_s": round(wall_kill - wall, 2),
                "kill_recoveries": int(ksum.get("bsp_recoveries", 0)),
            }))
    return rows


def emit_bsp():
    got = _safe("bsp", bench_bsp)
    if got is None:
        return
    # importing tools.chaos_lab pinned JAX_PLATFORMS for the workers it
    # launches (cpu unless the caller exported something else); the ring
    # they time is host TCP between those processes
    platform = os.environ["JAX_PLATFORMS"]
    for tag, r in got:
        emit(f"{tag}_bsp_dist_3w_allreduce_ms_per_round",
             r.pop("allreduce_ms"), "ms",
             device={"platform": platform, "device_kind": platform,
                     "device_count": 1}, **r)


def bench_serve(num_shards=2, num_buckets=1 << 26, duration_s=12.0,
                serve_mode="fetch", concurrency=4,
                price_tracing=False):
    """The serving tier at Criteo-1TB table scale: 2 in-process shards
    each holding half the 64M-bucket w table, a router scoring
    closed-loop predict batches through them, and a snapshot writer
    forcing hot swaps mid-load so the row records swap count and the
    request-visible stall (tools/serve_lab.py is the harness; this is
    its bench operating point). The window is sized so a full 256 MB
    set write (~2 s) + the watcher's slice load lands well inside it —
    a 6 s run clocked zero in-window swaps.

    serve_mode picks the dataflow: "fetch" pulls weight slices to the
    router (the PR-13 anchor), "score" runs shard-local scoring with
    router micro-batching (the fast path). Either way the run fails
    here if the stage table explains < 90% of request p50 — a silent
    attribution gap is a bench regression, not a footnote."""
    import shutil
    import tempfile

    from tools.serve_lab import run as serve_run
    from wormhole_tpu.obs import trace as obs_trace

    row = serve_run(num_shards=num_shards, num_buckets=num_buckets,
                    minibatch=1000, nnz=64, duration_s=duration_s,
                    concurrency=concurrency, swap_every_s=2.0,
                    serve_mode=serve_mode, verbose=False)
    if price_tracing:
        # price the tracing plane: the same load with spans sampled 1
        # in 64 into a scratch WH_OBS_DIR, vs the tracing-off run
        # above. The overhead lands in the row so a regression shows
        # up as a number.
        obs_dir = tempfile.mkdtemp(prefix="wh_bench_obs_")
        saved = {k: os.environ.get(k) for k in ("WH_OBS_DIR",
                                                "WH_TRACE_SAMPLE")}
        os.environ["WH_OBS_DIR"] = obs_dir
        os.environ["WH_TRACE_SAMPLE"] = "64"
        obs_trace.init_from_env()
        try:
            traced = serve_run(
                num_shards=num_shards, num_buckets=num_buckets,
                minibatch=1000, nnz=64, duration_s=duration_s,
                concurrency=concurrency, swap_every_s=2.0,
                serve_mode=serve_mode, seed=1, verbose=False)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            obs_trace.init_from_env()
            shutil.rmtree(obs_dir, ignore_errors=True)
        row["qps_traced_1_in_64"] = round(traced["qps"], 1)
        row["obs_overhead_pct"] = round(
            (1.0 - traced["qps"] / row["qps"]) * 100.0, 2) if row["qps"] \
            else None
    frac = row.get("stage_explained_frac")
    if frac is not None and frac < 0.9:
        raise AssertionError(
            f"serve[{serve_mode}] stage table explains only "
            f"{frac:.2f} of request p50 (floor 0.90) — a stage is "
            "missing from the attribution")
    return row


def _serve_row_kw(row):
    stage_kw = {f"{st}_ms": row[f"{st}_ms"]
                for st in ("batch_wait", "pack", "fanout", "wire",
                           "queue", "partial", "score", "sum")
                if row.get(f"{st}_ms") is not None}
    return dict(
        p50_ms=round(row["p50_ms"], 3), p99_ms=round(row["p99_ms"], 3),
        p999_ms=round(row["p999_ms"], 3),
        serve_mode=row["serve_mode"],
        shards=row["shards"], concurrency=row["concurrency"],
        requests=row["requests"], errors=row["errors"],
        swap_count=row["swap_count"],
        swap_stall_ms=round(row["swap_stall_ms"], 3),
        epoch_retries=row["epoch_retries"],
        stage_explained_frac=row.get("stage_explained_frac"),
        qps_traced_1_in_64=row.get("qps_traced_1_in_64"),
        obs_overhead_pct=row.get("obs_overhead_pct"),
        **stage_kw)


def emit_serve():
    # the fetch anchor: the pull-the-weights dataflow at its recorded
    # operating point (the PERF.md 79.7 qps row came from here)
    fetch = _safe("serve_fetch", bench_serve, serve_mode="fetch")
    # the score fast path: closed-loop round size tracks concurrency,
    # so drive it at 32 to give the micro-batcher real rounds
    score = _safe("serve_score", bench_serve, serve_mode="score",
                  concurrency=32, price_tracing=True)
    if fetch is not None:
        emit("linear_ftrl_serve_64m_buckets", round(fetch["qps"], 1),
             "qps", **_serve_row_kw(fetch))
    if score is not None:
        # vs_baseline = speedup over the fetch anchor on the same box
        emit("linear_ftrl_serve_64m_buckets_score",
             round(score["qps"], 1), "qps",
             vs_baseline=(score["qps"] / fetch["qps"]
                          if fetch and fetch["qps"] else None),
             batch_rounds=score.get("batch_rounds"),
             batch_mean_size=round(score.get("batch_mean_size") or 0.0,
                                   1),
             **_serve_row_kw(score))


#: groups that raised; a non-empty list makes the exit code non-zero
FAILED = []


def _safe(what, fn, *args, **kw):
    """Failure isolation: one config blowing up must never suppress the
    lines after it — r3 lost its headline to exactly that (the PS bench
    subprocess timeout propagated and killed the script at rc=1). The
    failure is still the run's result: main() exits non-zero."""
    try:
        return fn(*args, **kw)
    except Exception:
        print(f"[bench-error] {what} failed:", file=sys.stderr)
        traceback.print_exc()
        sys.stderr.flush()
        FAILED.append(what)
        return None


def emit_device_rows():
    """The in-process accelerator rows: everything that runs a learner's
    jitted steps on this process's device."""
    eps = _safe("difacto", bench_difacto)
    if eps is not None:
        emit("difacto_fm_dim8_criteo_shape_examples_per_sec", eps,
             "examples/sec")
    eps = _safe("kmeans", bench_kmeans)
    if eps is not None:
        emit("kmeans_k10_mnist_shape_examples_per_sec", eps, "examples/sec")
    eps = _safe("kmeans_f32", bench_kmeans, kernel_dtype="f32")
    if eps is not None:
        emit("kmeans_k10_mnist_shape_f32_examples_per_sec", eps,
             "examples/sec")
    got = _safe("gbdt", bench_gbdt)
    if got is not None:
        emit("gbdt_depth6_higgs_shape_rounds_per_sec", got[0], "rounds/sec")
    eps = _safe("linear_64m", bench_linear, 1 << 26, 1 << 16)
    if eps is not None:
        emit("linear_ftrl_criteo1tb_scale_64m_buckets_examples_per_sec",
             eps, "examples/sec", eps / BASELINE_EXAMPLES_PER_SEC)
    got = _safe("linear_epoch2", bench_linear_epoch2, NUM_BUCKETS, MINIBATCH)
    if got is not None:
        eps, stall, wall, hit = got
        emit("linear_ftrl_criteo_shape_epoch2_cached_examples_per_sec", eps,
             "examples/sec", eps / BASELINE_EXAMPLES_PER_SEC,
             pack_cache_hit_rate=round(hit, 4),
             loader_stall_s=round(stall, 4),
             loader_stall_frac=round(stall / max(wall, 1e-9), 4))


def emit_headline():
    eps = _safe("headline", bench_linear, NUM_BUCKETS, MINIBATCH)
    if eps is not None:
        emit("linear_ftrl_criteo_shape_examples_per_sec", eps,
             "examples/sec", eps / BASELINE_EXAMPLES_PER_SEC)


def emit_ps():
    got = _safe("linear_ps", bench_linear_ps)
    if got is None:
        return
    (dist_eps, dist_eps_off, single_eps, single_device, wire, wire_off,
     dense_bytes, obs, hot_eps, wire_hot, obs_hot,
     wire_q, dist_eps_q) = got
    # vs_baseline here = ratio to the single-process run on the same
    # data/platform; the recorded run is the production operating
    # point (WH_ASYNC_SYNC=1 WH_KEYCACHE=1), async_off_eps the plain
    # synchronous plane on the same data — see PERF.md "PS plane"
    emit("linear_ftrl_ps_dist_64m_buckets_examples_per_sec", dist_eps,
         "examples/sec", dist_eps / single_eps, device=wire["device"],
         obs=obs,
         async_off_eps=round(dist_eps_off, 1),
         ps_sync_overlap_frac=wire.get("sync_overlap_frac"),
         ps_push_ms_per_sync=wire.get("push_ms_per_sync"),
         ps_pull_ms_per_sync=wire.get("pull_ms_per_sync"),
         keycache_hit_rate=wire.get("keycache_hit_rate"),
         wire_codec=wire.get("wire_codec"),
         wire_bytes_per_sync=wire.get("bytes_per_sync"),
         wire_bytes_per_sync_int8ef=wire_q.get("bytes_per_sync"))
    # the codec row: same operating point (async + keycache), int8
    # error-feedback push deltas + bf16-capped pull refreshes +
    # bshuf framing.
    # vs_baseline = speedup over the raw-f32 dist row — the codec
    # must not cost throughput while it cuts the wire
    emit("linear_ftrl_ps_dist_64m_buckets_int8ef", dist_eps_q,
         "examples/sec", dist_eps_q / dist_eps, device=wire_q["device"],
         wire_codec=wire_q.get("wire_codec"),
         wire_ef=wire_q.get("wire_ef"),
         wire_comp=wire_q.get("wire_comp"),
         wire_bytes_per_sync=wire_q.get("bytes_per_sync"),
         raw_bytes_per_sync=wire.get("bytes_per_sync"),
         wire_savings_x=round(wire["bytes_per_sync"]
                              / max(wire_q.get("bytes_per_sync", 0),
                                    1), 2),
         ef_resid_norm=wire_q.get("wire_ef_resid_norm"))
    # vs_baseline = fraction of what a dense-table sync would move;
    # the saving field compares the LAST train round (epoch 2, where
    # the key cache ships digest-only frames) against the cache-off
    # run at the same operating point
    kc_on = wire.get("last_round_bytes_per_sync") or 0
    kc_off = wire_off.get("last_round_bytes_per_sync") or 0
    emit("ps_wire_bytes_per_sync_64m_buckets", wire["bytes_per_sync"],
         "bytes", wire["bytes_per_sync"] / dense_bytes,
         device=wire["device"],
         epoch2_bytes_per_sync=kc_on,
         epoch2_bytes_per_sync_nocache=kc_off,
         keycache_saving_frac=round(1.0 - kc_on / kc_off, 4)
         if kc_off else None)
    # the hot plane at the same table scale and data: device-resident
    # sharded tables, TCP tier demoted to flush barriers.
    # vs_baseline = speedup over the TCP dist row (the ~170x gap this
    # plane exists to close); single_chip_eps anchors the ceiling
    emit("linear_ftrl_ps_hot_64m_buckets_examples_per_sec", hot_eps,
         "examples/sec", hot_eps / dist_eps, device=wire_hot["device"],
         plane=wire_hot.get("plane"), workers=1, servers=1,
         devices=wire_hot.get("devices"),
         model_shards=2,
         cold_flushes=wire_hot.get("flushes"),
         hot_steps=wire_hot.get("hot_steps"),
         tcp_dist_eps=round(dist_eps, 1),
         single_chip_eps=round(single_eps, 1),
         single_chip_device=single_device,
         obs=obs_hot)


def main():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--group", choices=["all", "device", "bsp", "serve"],
                    default="all",
                    help="run one bench group instead of the full suite "
                         "(device: the in-process accelerator rows, "
                         "headline last, and nothing that spawns CPU "
                         "children; bsp: the native BSP allreduce stack; "
                         "serve: the online serving tier)")
    args = ap.parse_args()
    if args.group == "bsp":
        emit_bsp()
    elif args.group == "serve":
        emit_serve()
    else:
        if args.group == "device" and this_device()["platform"] == "cpu":
            sys.exit("bench.py --group device: JAX found no accelerator "
                     "(platform cpu); these rows are device figures and "
                     "are not taken on the host")
        emit_device_rows()
        if args.group == "all":
            emit_ps()
            emit_bsp()
            emit_serve()
        # headline LAST: the driver parses the final JSON line
        emit_headline()
    if FAILED:
        sys.exit(f"[bench-error] failed groups: {', '.join(FAILED)}")


if __name__ == "__main__":
    main()
