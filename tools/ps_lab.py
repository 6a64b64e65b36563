#!/usr/bin/env python
"""PS-plane microbench: per-stage ms/sync for the sparse sync path.

Times each stage of one SyncedStore sync in isolation — gather (touched
device/host rows -> delta arrays), encode (wire serialization of the
push payload), merge (server-side push apply: key-cache resolve +
scatter-add + version stamping), pull_read (server-side versioned-pull
row assembly), pull_apply (client-side scatter of pulled rows), wire
(everything else in the round-trip: framing, sockets, decode) — then
the composed loops: sync mode ms/sync, async mode ms/sync as the train
loop sees it (with simulated compute between syncs) plus the measured
overlap fraction, and the key-cache wire saving (bytes/sync, first sync
vs steady state). This is where PERF.md "PS plane" numbers come from.

The hot-plane stage table (hot_* rows) times the device-resident path
the same sync rides when WH_PS_PLANE=hot: sharded row gather (ZPull),
sharded row scatter (pull apply), the ZPush sharding-constraint
collective (XLA reduce-scatter onto the owning model shard), and the
shard-local optimizer update — plus the kv.jit_cache_misses steady
state, which must be flat once every padded size has compiled.

CPU-safe: defaults JAX_PLATFORMS=cpu when unset, and forces a
multi-device host topology so the hot-plane rows exercise a real >= 2
shard mesh anywhere the tests run (tests/test_ps_async.py wires it
into the slow tier).

Usage: python tools/ps_lab.py [--buckets N] [--nnz N] [--syncs N]
       [--servers N] [--compute-ms MS] [--model-shards N] [--json]
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# multi-device topology for the hot-plane stage rows; must land before
# the first jax import, which is why it lives at module top
if os.environ["JAX_PLATFORMS"] == "cpu" and \
        "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from wormhole_tpu.config import declare_knob, knob_value

declare_knob("WH_PS_LAB_SYNCS", int, 4,
             "Default number of sync rounds for tools/ps_lab.py "
             "(overridden by --syncs).", group="tools")


class _Store:
    """Host-numpy stand-in for the learner's KV store; records time
    spent in scatter_rows so pull-apply cost is attributable."""

    def __init__(self, nb):
        self.tables = {k: np.zeros(nb, np.float32) for k in ("w", "z", "n")}
        self.scatter_s = 0.0

    def to_numpy(self):
        return dict(self.tables)

    def from_numpy(self, arrays):
        for k, v in arrays.items():
            self.tables[k] = np.array(v, np.float32)

    def gather_rows(self, k, idx):
        return self.tables[k][idx]

    def scatter_rows(self, k, idx, vals):
        t0 = time.perf_counter()
        self.tables[k][idx] = vals
        self.scatter_s += time.perf_counter() - t0

    def zero_init_names(self):
        return set(self.tables)


class _OpTimer:
    """Wraps ServerNode._dispatch to attribute server-side wall per op
    (the handler runs in-process, so this is real merge/scan time)."""

    def __init__(self, nodes):
        self.s = {}
        self._orig = []
        for n in nodes:
            orig = n._dispatch

            def timed(header, arrays, _orig=orig):
                t0 = time.perf_counter()
                try:
                    return _orig(header, arrays)
                finally:
                    op = header.get("op")
                    self.s[op] = self.s.get(op, 0.0) \
                        + time.perf_counter() - t0

            n._dispatch = timed
            self._orig.append((n, orig))

    def take(self, op):
        return self.s.pop(op, 0.0)


def _mk(nb, nnz, servers, keycache, async_sync, touched):
    from wormhole_tpu.runtime.ps_server import (PSClient, ServerNode,
                                                SyncedStore)

    nodes = [ServerNode(r, servers) for r in range(servers)]
    for n in nodes:
        n.serve()
    client = PSClient([n.uri for n in nodes], sender="lab-0",
                      keycache=keycache)
    st = _Store(nb)
    derived = {"w": {"kind": "ftrl_prox", "lr_eta": 0.1, "lr_beta": 1.0,
                     "lambda_l1": 1.0, "lambda_l2": 0.0}}
    ss = SyncedStore(st, client, max_delay=1, derived=derived,
                     async_sync=async_sync,
                     touched_fn=lambda: {k: touched for k in ("z", "n")})
    ss.init()
    return nodes, client, st, ss


def _teardown(nodes, client, ss):
    ss.close()
    client.close()
    for n in nodes:
        n.stop()


def _hot_stage(args, emit):
    """hot_* rows: per-stage ms of the device-resident (WH_PS_PLANE=hot)
    data plane on a real model-sharded mesh. These are the stages a
    training step actually rides — there is no wire, so the comparison
    row for sync_total is hot_step_total."""
    import jax
    import jax.numpy as jnp

    from wormhole_tpu.obs import metrics as _obs
    from wormhole_tpu.parallel.kvstore import KVStore, TableSpec
    from wormhole_tpu.parallel.mesh import make_mesh

    nm = max(args.model_shards, 1)
    nb = args.buckets - args.buckets % nm
    mesh = make_mesh(num_model=nm)
    store = KVStore(mesh, nb,
                    {k: TableSpec() for k in ("w", "z", "n")})
    rng = np.random.default_rng(1)
    touched = np.unique(
        rng.zipf(1.2, size=args.nnz).astype(np.int64) % nb)
    vals = rng.standard_normal(touched.shape[0]).astype(np.float32)

    def misses():
        return int(_obs.REGISTRY.snapshot()["counters"]
                   .get("kv.jit_cache_misses", 0))

    # ZPush aggregation: a dense gradient in table layout pinned to the
    # table's sharding — XLA reduce-scatters it onto the owning shard
    coll = jax.jit(lambda g: store.constrain("z", g))

    # shard-local FTRL-shaped update over the constrained gradient
    def _upd(state, g):
        z = state["z"] + g
        n = state["n"] + g * g
        w = (jnp.sign(z) * jnp.maximum(jnp.abs(z) - 1.0, 0.0)
             / (1.0 + jnp.sqrt(n)))
        return {"w": w, "z": z, "n": n}

    upd = jax.jit(_upd, donate_argnums=0)
    grad = jax.device_put(
        np.zeros(nb, np.float32), store.sharding("z"))

    # warmup: compile every padded size / program once
    m0 = misses()
    store.gather_rows_multi(["z", "n"], touched)
    store.scatter_rows("w", touched, vals)
    jax.block_until_ready(coll(grad))
    store.state = upd(store.state, coll(grad))
    jax.block_until_ready(store.state["w"])
    warm = misses() - m0

    g_s = s_s = c_s = u_s = 0.0
    m1 = misses()
    for _ in range(args.syncs):
        t0 = time.perf_counter()
        store.gather_rows_multi(["z", "n"], touched)
        t1 = time.perf_counter()
        store.scatter_rows("w", touched, vals)
        t2 = time.perf_counter()
        jax.block_until_ready(coll(grad))
        t3 = time.perf_counter()
        store.state = upd(store.state, grad)
        jax.block_until_ready(store.state["w"])
        t4 = time.perf_counter()
        g_s += t1 - t0
        s_s += t2 - t1
        c_s += t3 - t2
        u_s += t4 - t3
    steady = misses() - m1
    n = args.syncs
    dims = dict(devices=int(mesh.devices.size), model_shards=nm)
    emit("hot_gather", 1e3 * g_s / n, rows=int(touched.shape[0]), **dims)
    emit("hot_scatter", 1e3 * s_s / n, rows=int(touched.shape[0]), **dims)
    emit("hot_collective", 1e3 * c_s / n, table_rows=nb, **dims)
    emit("hot_update", 1e3 * u_s / n, table_rows=nb, **dims)
    emit("hot_step_total", 1e3 * (c_s + u_s) / n, **dims)
    emit("hot_jit_cache", 0.0, misses_warmup=warm, misses_steady=steady,
         **dims)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--buckets", type=int, default=1 << 22,
                    help="table rows (bench operating point: 1<<26)")
    ap.add_argument("--nnz", type=int, default=100_000,
                    help="zipf draws per sync (bench point: 975000)")
    ap.add_argument("--syncs", type=int, default=knob_value("WH_PS_LAB_SYNCS"))
    ap.add_argument("--servers", type=int, default=1)
    ap.add_argument("--compute-ms", type=float, default=50.0,
                    help="simulated device compute between async syncs")
    ap.add_argument("--model-shards", type=int, default=2,
                    help="mesh model-axis shards for the hot_* stage rows")
    ap.add_argument("--no-hot", action="store_true",
                    help="skip the hot-plane stage rows (no jax needed)")
    ap.add_argument("--json", action="store_true",
                    help="one JSON object per stage instead of a table")
    args = ap.parse_args(argv)

    from wormhole_tpu.runtime import net

    rng = np.random.default_rng(0)
    touched = np.unique(
        rng.zipf(1.2, size=args.nnz).astype(np.int64) % args.buckets)
    rows = []

    def emit(stage, ms, **kw):
        rows.append(dict({"stage": stage, "ms_per_sync": round(ms, 3)},
                         **kw))

    # ---- per-stage, sync mode, key cache off (the un-overlapped truth)
    nodes, client, st, ss = _mk(args.buckets, len(touched), args.servers,
                                keycache=False, async_sync=False,
                                touched=touched)
    opt = _OpTimer(nodes)
    g_s = e_s = push_s = pull_s = 0.0
    # warmup sync: first push materializes the spec-created tables and
    # version arrays server-side (a one-time O(table) cost that must not
    # pollute the steady-state per-stage numbers)
    st.tables["z"][touched] += 0.1
    st.tables["n"][touched] += 0.01
    ss.sync()
    opt.take("push"), opt.take("pull")  # drop init+warmup ops
    st.scatter_s = 0.0
    for _ in range(args.syncs):
        st.tables["z"][touched] += 0.1
        st.tables["n"][touched] += 0.01
        t0 = time.perf_counter()
        got = ss._touched_groups()
        t1 = time.perf_counter()
        g_s += t1 - t0
        for a in (*got[0].values(), *got[1].values()):
            net._encode(a)
        e_s += time.perf_counter() - t1
        t2 = time.perf_counter()
        client.push_sparse(*got)
        t3 = time.perf_counter()
        ss._apply_pull()
        push_s += t3 - t2
        pull_s += time.perf_counter() - t3
    n = args.syncs
    merge_s = opt.take("push")
    pread_s = opt.take("pull")
    papply_s = st.scatter_s
    emit("gather", 1e3 * g_s / n)
    emit("encode", 1e3 * e_s / n)
    emit("merge", 1e3 * merge_s / n)
    emit("pull_read", 1e3 * pread_s / n)
    emit("pull_apply", 1e3 * papply_s / n)
    # the push encode ran twice (standalone + inside push_sparse): wire
    # = round-trip minus the attributed server/encode/apply shares
    wire = (push_s + pull_s) - e_s - merge_s - pread_s - papply_s
    emit("wire", 1e3 * max(wire, 0.0) / n)
    emit("sync_total", 1e3 * (g_s + push_s + pull_s) / n,
         touched_rows=int(len(touched)))
    _teardown(nodes, client, ss)

    # ---- key-cache wire saving: first sync ships keys, steady state
    # ships digests + values only
    nodes, client, st, ss = _mk(args.buckets, len(touched), args.servers,
                                keycache=True, async_sync=False,
                                touched=touched)
    per_sync = []
    for _ in range(max(args.syncs, 2)):
        st.tables["z"][touched] += 0.1
        st.tables["n"][touched] += 0.01
        b0 = client.bytes_push + client.bytes_pull
        ss.sync()
        per_sync.append(client.bytes_push + client.bytes_pull - b0)
    kc_hit_rate = (client.kc_hits / max(client.kc_hits + client.kc_misses, 1))
    emit("keycache", 0.0, bytes_first_sync=per_sync[0],
         bytes_steady_sync=per_sync[-1],
         saving_frac=round(1.0 - per_sync[-1] / max(per_sync[0], 1), 4),
         hit_rate=round(kc_hit_rate, 4))
    _teardown(nodes, client, ss)

    # ---- async overlap timeline: the train loop's view of sync() with
    # simulated compute in between (sleep stands in for device steps)
    for mode, async_on in (("sync_loop", False), ("async_loop", True)):
        nodes, client, st, ss = _mk(args.buckets, len(touched),
                                    args.servers, keycache=True,
                                    async_sync=async_on, touched=touched)
        st.tables["z"][touched] += 0.1
        ss.sync()
        ss.flush()  # warmup: table materialization + key-list exchange
        # the warmup flush waited out its whole round-trip; start the
        # overlap accounting fresh
        ss._rt_wall = ss._wait_wall = ss._push_s = ss._pull_s = 0.0
        ss.num_syncs = 0
        t_loop = time.perf_counter()
        sync_wall = 0.0
        for _ in range(args.syncs):
            time.sleep(args.compute_ms / 1e3)
            st.tables["z"][touched] += 0.1
            st.tables["n"][touched] += 0.01
            t0 = time.perf_counter()
            ss.sync()
            sync_wall += time.perf_counter() - t0
        ss.flush()
        wall = time.perf_counter() - t_loop
        ws = ss.wire_stats()
        emit(mode, 1e3 * sync_wall / n, wall_ms_total=round(1e3 * wall, 1),
             overlap_frac=ws["sync_overlap_frac"],
             keycache_hit_rate=ws["keycache_hit_rate"])
        _teardown(nodes, client, ss)

    # ---- hot plane: the device-resident stage table (WH_PS_PLANE=hot)
    if not args.no_hot:
        _hot_stage(args, emit)

    if args.json:
        for r in rows:
            print(json.dumps(r))
    else:
        print(f"{'stage':<12} {'ms/sync':>9}   detail")
        for r in rows:
            extra = " ".join(f"{k}={v}" for k, v in r.items()
                             if k not in ("stage", "ms_per_sync"))
            print(f"{r['stage']:<12} {r['ms_per_sync']:>9.3f}   {extra}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
