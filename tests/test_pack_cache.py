"""Packed-batch epoch cache tests: bit-identical replay for all three
learners, LRU byte budgeting, disk round-trip + corruption fallback,
concurrent access, whole-part replay with gap recovery — plus the
pipeline pieces that ride with it (ThreadedParser error relay, the
adaptive LoaderController, WH_NUM_LOADERS, and end-to-end cache on/off
equivalence through the solver)."""

import os
import threading

import numpy as np
import pytest

from wormhole_tpu.data import pack_cache as pc
from wormhole_tpu.data.minibatch import MinibatchIter, ThreadedParser
from wormhole_tpu.data.rowblock import RowBlock
from wormhole_tpu.models.linear import LinearConfig, LinearLearner
from wormhole_tpu.parallel.mesh import make_mesh
from wormhole_tpu.solver.minibatch_solver import (LoaderController,
                                                  MinibatchSolver)

from conftest import synth_libsvm_text


def assert_bit_identical(a, b):
    """Same skeleton, same leaves, byte-for-byte (dtype + shape + bits)."""
    la, lb = [], []
    sa = pc._flatten(a, la)
    sb = pc._flatten(b, lb)
    assert repr(sa) == repr(sb)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


def _rowblock(n_rows=64, n_feat=500, nnz=8, seed=0):
    rng = np.random.default_rng(seed)
    idx = np.concatenate([
        rng.choice(n_feat, size=nnz, replace=False) for _ in range(n_rows)
    ]).astype(np.uint64)
    return RowBlock(
        label=(rng.random(n_rows) < 0.5).astype(np.float32),
        offset=np.arange(n_rows + 1, dtype=np.int64) * nnz,
        index=idx,
        value=rng.random(n_rows * nnz).astype(np.float32),
    )


# ------------------------------------------------------------ fingerprint
def test_fingerprint_stable_and_sensitive():
    k = pc.fingerprint("a", 1, (2, 3))
    assert k == pc.fingerprint("a", 1, (2, 3))
    assert k != pc.fingerprint("a", 1, (2, 4))
    assert k != pc.fingerprint("a", 2, (2, 3))


# -------------------------------------------- bit-identity, all learners
def test_linear_pack_disk_roundtrip_bit_identical(tmp_path):
    cfg = LinearConfig(minibatch=64, num_buckets=1 << 9, nnz_per_row=8,
                       algo="ftrl")
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    blk = _rowblock()
    fresh = lrn.prepare_batch(blk)
    cache = pc.PackCache(mem_bytes=1 << 20, disk_dir=str(tmp_path))
    assert cache.put("k", fresh)
    cache.clear_memory()  # force the disk tier
    got = cache.get("k")
    assert cache.disk_hits == 1
    assert_bit_identical(fresh, got)
    # and a second pack of the same block matches both (pack is pure)
    assert_bit_identical(fresh, lrn.prepare_batch(_rowblock()))


def test_difacto_pack_disk_roundtrip_bit_identical(tmp_path):
    from wormhole_tpu.models.difacto import DifactoConfig, DifactoLearner

    cfg = DifactoConfig(minibatch=64, num_buckets=1 << 9, nnz_per_row=8,
                        dim=4, threshold=1)
    fm = DifactoLearner(cfg, make_mesh(1, 1))
    # the XLA path packs with no state at all: both packs have a key (the
    # compact path's, once its capacities are known: test_difacto_compact)
    assert fm.pack_cache_token(train=True) is not None and not fm._use_fm_pallas
    blk = _rowblock()
    fresh = fm.prepare_batch(blk, train=False)
    cache = pc.PackCache(mem_bytes=1 << 20, disk_dir=str(tmp_path))
    assert cache.put("k", fresh)
    cache.clear_memory()
    assert_bit_identical(fresh, cache.get("k"))


def test_kmeans_pack_disk_roundtrip_bit_identical(tmp_path):
    from wormhole_tpu.models.kmeans import KmeansConfig, KmeansLearner

    d = tmp_path / "km.libsvm"
    d.write_text(synth_libsvm_text(n_rows=256, n_feat=64, nnz_per_row=6))
    cfg = KmeansConfig(train_data=str(d), num_clusters=4, dim=64,
                       minibatch=128, nnz_per_row=8)
    km = KmeansLearner(cfg, make_mesh(1, 1))
    dbs = list(km._host_dbs("raw", km._prep_db))
    assert dbs
    pk = (km.pack_batch(dbs[0].seg, dbs[0].idx, dbs[0].val),
          dbs[0].row_mask)
    cache = pc.PackCache(mem_bytes=1 << 20, disk_dir=str(tmp_path / "c"))
    assert cache.put("k", pk)
    cache.clear_memory()
    assert_bit_identical(pk, cache.get("k"))


def test_kmeans_host_dbs_replay_bit_identical(tmp_path):
    """Iteration 2 of the Lloyd loop serves the SAME bytes the uncached
    loop would pack."""
    from wormhole_tpu.models.kmeans import KmeansConfig, KmeansLearner

    d = tmp_path / "km.libsvm"
    d.write_text(synth_libsvm_text(n_rows=300, n_feat=64, nnz_per_row=6))
    cfg = KmeansConfig(train_data=str(d), num_clusters=4, dim=64,
                       minibatch=128, nnz_per_row=8)
    km = KmeansLearner(cfg, make_mesh(1, 1))
    uncached = list(km._host_dbs("raw", km._prep_db))
    km.pack_cache = pc.PackCache(mem_bytes=64 << 20)
    cold = list(km._host_dbs("raw", km._prep_db))   # fills the cache
    warm = list(km._host_dbs("raw", km._prep_db))   # replays it
    assert km.pack_cache.hits >= len(uncached)
    assert len(uncached) == len(cold) == len(warm)
    for u, c, w in zip(uncached, cold, warm):
        assert_bit_identical(u, c)
        assert_bit_identical(u, w)


# --------------------------------------------------------------- eviction
def test_lru_eviction_order():
    mk = lambda: np.zeros(1000, dtype=np.float64)  # 8000 B + 512 skeleton
    cache = pc.PackCache(mem_bytes=3 * 8512)
    cache.put("a", mk())
    cache.put("b", mk())
    cache.put("c", mk())
    assert cache.get("a") is not None  # refresh a: b is now LRU
    cache.put("d", mk())
    assert cache.get("b") is None      # evicted first
    assert cache.get("a") is not None
    assert cache.get("c") is not None
    assert cache.get("d") is not None
    assert cache.stats()["mem_entries"] == 3


def test_oversize_entry_skips_memory(tmp_path):
    cache = pc.PackCache(mem_bytes=100, disk_dir=str(tmp_path))
    assert cache.put("big", np.zeros(1000))
    assert cache.stats()["mem_entries"] == 0
    got = cache.get("big")  # served by the disk tier
    assert got is not None and np.asarray(got).nbytes == 8000


# -------------------------------------------------------------- disk tier
def test_disk_corrupt_entry_falls_back_to_miss(tmp_path):
    cache = pc.PackCache(mem_bytes=1 << 20, disk_dir=str(tmp_path))
    cache.put("k", {"x": np.arange(10), "meta": 3})
    cache.clear_memory()
    (path,) = [os.path.join(tmp_path, f) for f in os.listdir(tmp_path)]
    with open(path, "r+b") as fh:  # stomp the magic
        fh.write(b"GARBAGE!")
    assert cache.get("k") is None
    assert not os.path.exists(path)  # dropped, will be repacked
    assert cache.misses == 1


def test_disk_truncated_entry_falls_back_to_miss(tmp_path):
    cache = pc.PackCache(mem_bytes=1 << 20, disk_dir=str(tmp_path))
    cache.put("k", np.arange(1000))
    cache.clear_memory()
    (path,) = [os.path.join(tmp_path, f) for f in os.listdir(tmp_path)]
    with open(path, "r+b") as fh:
        fh.truncate(os.path.getsize(path) - 100)
    assert cache.get("k") is None
    assert not os.path.exists(path)


def test_disk_hit_promotes_to_memory(tmp_path):
    cache = pc.PackCache(mem_bytes=1 << 20, disk_dir=str(tmp_path))
    cache.put("k", np.arange(10))
    cache.clear_memory()
    assert cache.get("k") is not None
    assert cache.disk_hits == 1
    assert cache.get("k") is not None
    assert cache.disk_hits == 1  # second hit came from memory


def test_uncacheable_object_returns_false():
    cache = pc.PackCache(mem_bytes=1 << 20)
    assert cache.put("k", {"bad": {1, 2, 3}}) is False
    assert cache.get("k") is None


# ------------------------------------------------------------- concurrency
def test_concurrent_get_put():
    cache = pc.PackCache(mem_bytes=4 << 20)
    errs = []

    def worker(w):
        try:
            rng = np.random.default_rng(w)
            for i in range(200):
                k = f"k{i % 37}"
                got = cache.get(k)
                if got is not None:
                    # values are keyed by name: a hit must be consistent
                    assert int(np.asarray(got)[0]) == i % 37
                else:
                    cache.put(k, np.full(64, i % 37, dtype=np.int64))
                if rng.random() < 0.02:
                    cache.clear_memory()
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(w,)) for w in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs


# ----------------------------------------------------- whole-part replay
def test_iter_part_cached_replay_skips_source():
    cache = pc.PackCache(mem_bytes=16 << 20)
    opened, prepared = [], []

    def raw():
        opened.append(1)
        return iter([np.full(8, i) for i in range(5)])

    prep = lambda b: (prepared.append(1), b * 2)[1]
    key = ("part", 0)
    cold = list(pc.iter_part_cached(cache, key, raw, prep))
    assert len(cold) == 5 and len(opened) == 1 and len(prepared) == 5
    warm = list(pc.iter_part_cached(cache, key, raw, prep))
    assert len(opened) == 1 and len(prepared) == 5  # source never reopened
    for c, w in zip(cold, warm):
        assert_bit_identical(c, w)


def test_iter_part_cached_gap_refills():
    """An evicted mid-part entry reopens the source, fast-forwards past
    already-served batches, and refills from the gap."""
    cache = pc.PackCache(mem_bytes=16 << 20)
    opened, prepared = [], []

    def raw():
        opened.append(1)
        return iter([np.full(8, i) for i in range(5)])

    def prep(b):
        prepared.append(int(b[0]))
        return b * 2

    key = ("part", 0)
    cold = list(pc.iter_part_cached(cache, key, raw, prep))
    # knock out batch 2: replay serves 0-1 from cache, re-packs 2-4
    assert cache._mem.pop(pc.fingerprint(key, 2)) is not None
    prepared.clear()
    warm = list(pc.iter_part_cached(cache, key, raw, prep))
    assert len(warm) == 5 and len(opened) == 2
    assert prepared == [2, 3, 4]  # 0-1 were NOT re-packed
    for c, w in zip(cold, warm):
        assert_bit_identical(c, w)
    # and the gap is healed for the next epoch
    prepared.clear()
    list(pc.iter_part_cached(cache, key, raw, prep))
    assert prepared == [] and len(opened) == 2


def test_iter_part_cached_none_cache_is_plain_loop():
    out = list(pc.iter_part_cached(None, ("k",), lambda: iter([1, 2]),
                                   lambda b: b + 1))
    assert out == [2, 3]


def test_from_env_default_off(monkeypatch):
    for k in ("WH_PACK_CACHE", "WH_PACK_CACHE_DIR", "WH_PACK_CACHE_MB"):
        monkeypatch.delenv(k, raising=False)
    assert pc.from_env() is None
    monkeypatch.setenv("WH_PACK_CACHE", "1")
    monkeypatch.setenv("WH_PACK_CACHE_MB", "7")
    cache = pc.from_env()
    assert cache is not None and cache.mem_bytes == 7 << 20
    assert cache.disk_dir is None


# ------------------------------------------------------- threaded parser
def test_threaded_parser_relays_midstream_error():
    def src():
        yield np.arange(4)
        yield np.arange(4)
        raise RuntimeError("parser died mid-stream")

    it = iter(ThreadedParser(src()))
    assert next(it) is not None
    assert next(it) is not None
    with pytest.raises(RuntimeError, match="mid-stream"):
        next(it)


def test_threaded_parser_end_of_stream():
    got = list(ThreadedParser(iter(range(10))))
    assert got == list(range(10))


def test_minibatch_iter_propagates_parse_error(tmp_path):
    """The regression the sentinel exists for: a bad row must raise at
    the consumer, not hang the iterator behind a dead producer."""
    p = tmp_path / "bad.libsvm"
    p.write_text("1 5:1.0\n0 not_a_feature\n")
    with pytest.raises(Exception):
        list(MinibatchIter(str(p), minibatch_size=4))


# ---------------------------------------------------- loader controller
def test_controller_grows_on_stall():
    c = LoaderController(2, hi=16)
    assert c.record_pass(stall_s=3.0, wall_s=10.0, n_steps=50,
                         queue_high_frac=0.0) == 3
    assert c.decisions[-1]["why"] == "starved"


def test_controller_grows_by_two_when_starved_hard():
    c = LoaderController(2, hi=16)
    assert c.record_pass(stall_s=6.0, wall_s=10.0, n_steps=50,
                         queue_high_frac=0.0) == 4


def test_controller_shrinks_only_when_queue_full():
    c = LoaderController(4, hi=16)
    # low stall but the queue was mostly empty -> hold steady
    assert c.record_pass(0.0, 10.0, 50, queue_high_frac=0.1) == 4
    # low stall AND a well-stocked queue -> shrink
    assert c.record_pass(0.0, 10.0, 50, queue_high_frac=0.9) == 3
    assert c.decisions[-1]["why"] == "overfed"


def test_controller_ignores_short_passes_and_respects_bounds():
    c = LoaderController(1, lo=1, hi=2)
    assert c.record_pass(9.0, 10.0, n_steps=2, queue_high_frac=0.0) == 1
    assert c.record_pass(9.0, 10.0, n_steps=50, queue_high_frac=0.0) == 2
    assert c.record_pass(9.0, 10.0, n_steps=50, queue_high_frac=0.0) == 2
    c2 = LoaderController(1, lo=1, hi=8)
    assert c2.record_pass(0.0, 10.0, 50, queue_high_frac=1.0) == 1


# -------------------------------------------------------- solver wiring
def _solver_cfg(d, **kw):
    defaults = dict(
        train_data=str(d / r"train-.*\.libsvm"), data_format="libsvm",
        minibatch=128, num_buckets=1 << 9, nnz_per_row=16, algo="ftrl",
        lr_eta=0.5, max_data_pass=2,
    )
    defaults.update(kw)
    return LinearConfig(**defaults)


@pytest.fixture(scope="module")
def cache_data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("pack_cache_data")
    for i in range(2):
        (d / f"train-{i}.libsvm").write_text(
            synth_libsvm_text(n_rows=400, n_feat=200, nnz_per_row=10,
                              seed=i))
    return d


def test_wh_num_loaders_env_override(cache_data_dir, monkeypatch):
    monkeypatch.setenv("WH_NUM_LOADERS", "5")
    monkeypatch.delenv("WH_ADAPTIVE_LOADERS", raising=False)
    cfg = _solver_cfg(cache_data_dir)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    sol = MinibatchSolver(lrn, cfg, verbose=False)
    assert sol.num_loaders == 5
    # a pinned count means the operator chose: adaptive stays off...
    assert sol.controller is None
    # ...unless explicitly re-enabled
    monkeypatch.setenv("WH_ADAPTIVE_LOADERS", "1")
    sol2 = MinibatchSolver(lrn, cfg, verbose=False)
    assert sol2.controller is not None and sol2.controller.n == 5


def test_solver_cache_default_off(cache_data_dir, monkeypatch):
    for k in ("WH_PACK_CACHE", "WH_PACK_CACHE_DIR"):
        monkeypatch.delenv(k, raising=False)
    cfg = _solver_cfg(cache_data_dir)
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    assert MinibatchSolver(lrn, cfg, verbose=False).pack_cache is None


def test_solver_cache_on_vs_off_equivalent(cache_data_dir, monkeypatch):
    """Same data, cache on vs off: pass 2+ is served from the cache
    (hits recorded) and training quality is unchanged. Weight bit-
    equality is NOT asserted: the workload pool's part order and loader
    interleaving make even two uncached runs differ — the bit-identity
    guarantee lives at the pack level (tests above)."""
    def run(with_cache):
        if with_cache:
            monkeypatch.setenv("WH_PACK_CACHE", "1")
        else:
            monkeypatch.delenv("WH_PACK_CACHE", raising=False)
        cfg = _solver_cfg(cache_data_dir, max_data_pass=3)
        lrn = LinearLearner(cfg, make_mesh(1, 1))
        sol = MinibatchSolver(lrn, cfg, verbose=False)
        res = sol.run()
        return sol, res["train"]

    sol_off, tr_off = run(False)
    sol_on, tr_on = run(True)
    assert tr_on.value("nex") == tr_off.value("nex")
    stats = sol_on.pack_cache.stats()
    # passes 2-3 replay both parts fully from the cache
    assert stats["hits"] > 0 and stats["hit_rate"] > 0.5
    assert abs(tr_on.mean("auc") - tr_off.mean("auc")) < 0.05


@pytest.mark.slow
def test_loader_lab_reports_all_stages():
    """tools/loader_lab.py runs end to end on CPU and reports a ms/batch
    figure for every pipeline stage."""
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "tools/loader_lab.py", "--rows", "512",
         "--minibatch", "128", "--num-buckets", "2048", "--nnz", "8",
         "--steps", "4", "--json"],
        capture_output=True, text=True, timeout=240, cwd=repo,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stdout + r.stderr
    rows = [json.loads(l) for l in r.stdout.splitlines() if l.strip()]
    stages = {row["stage"] for row in rows}
    assert {"parse", "pack", "cache_put", "cache_get", "stage", "step",
            "epoch1_cold", "epoch2_cached"} <= stages
    assert all(row["ms_per_batch"] >= 0 for row in rows)
