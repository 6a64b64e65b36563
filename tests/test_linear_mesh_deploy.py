"""The four-shard linear FTRL deployment (benchmark configuration
`linear-ftrl-criteo1tb-2p30`) at a tiny size on the forced host devices:
the learner on a 1 x 4 and on a 2 x 4 mesh against the benchmark's plain
reference under the configuration's own limits, the shards' shares adding
up to the one-device result, the check's read-back gather on a sharded
table, an `mcoo` batch through the pack cache, shard overflow counted,
the clamp of `model_shards` counted, and the bytes on `loader.h2d`.

Seeded Criteo-shape rows (benchmark/gen.py): the 13 integer fields draw
from 50 values under a power law and lose their field in the bucket
(`key mod num_buckets`), so a few buckets hold a third of a batch's
nonzeros and the shard that owns them is the hot one. Kernels interpreted;
nothing here is a speed.
"""

import json
import logging
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import check, control, gen, tap as tp  # noqa: E402
from benchmark.reference import linear_ftrl as reference  # noqa: E402
from wormhole_tpu.data import pack_cache as pc  # noqa: E402
from wormhole_tpu.data.rowblock import RowBlock  # noqa: E402
from wormhole_tpu.models.linear import LinearConfig, LinearLearner  # noqa: E402
from wormhole_tpu.obs import trace as obs_trace  # noqa: E402
from wormhole_tpu.obs.metrics import REGISTRY  # noqa: E402
from wormhole_tpu.ops import coo_kernels as ck  # noqa: E402
from wormhole_tpu.parallel.kvstore import KVStore, TableSpec  # noqa: E402
from wormhole_tpu.parallel.mesh import local_mesh, make_mesh  # noqa: E402

with open(os.path.join(REPO, "benchmark", "configs",
                       "linear-ftrl-criteo1tb-2p30.json")) as _fh:
    CONFIG = json.load(_fh)
KEYS = gen.KeyModel(CONFIG["keys"])
SEED = 2147483777
STEPS = CONFIG["correct"]["steps"]


CONF = {**CONFIG["conf"], **CONFIG["rehearsal"]["conf"]}
ROWS, NB = int(CONF["minibatch"]), int(CONF["num_buckets"])
SHARDS = int(CONF["model_shards"])


def _cfg(**over) -> LinearConfig:
    keep = {k: v for k, v in CONF.items()
            if k in LinearConfig.__dataclass_fields__}
    return LinearConfig(**{**keep, **over})


def _counter(name: str) -> int:
    return REGISTRY.snapshot()["counters"].get(name, 0)


def _mesh_counters() -> dict:
    return {n: _counter(f"linear.mesh.{n}") for n in (
        "dropped_nnz", "shard_nnz_max", "shard_nnz_sum")}


class Batches:
    """What `check.FirstSteps` asks of a dataset: the generated batches
    by (part, j) and by their labels."""

    def __init__(self, n: int, seed: int = SEED):
        self.minibatch = ROWS
        self._b = {}
        self.by_label = {}
        for p in range(n):
            r = gen.Rows(KEYS, seed, gen.TRAIN_STREAM, p, ROWS)
            self._b[p, 0] = (r.keys(), r.label)
            self.by_label[r.label.tobytes()] = (p, 0)

    def batch(self, part, j):
        return self._b[part, j]

    def block(self, part) -> RowBlock:
        keys, label = self._b[part, 0]
        return RowBlock(label=label, offset=np.arange(
            0, ROWS * gen.NNZ + 1, gen.NNZ, dtype=np.int64),
            index=keys.reshape(-1), value=None)


@pytest.fixture(scope="module")
def data():
    return Batches(STEPS + 1)


def _train(learner, data, parts, first=None):
    """`parts` through prepare_batch / stage_batch / train_batch, the way
    a loader thread and the train thread hand a batch on."""
    outs = []
    for p in parts:
        b = learner.stage_batch(learner.prepare_batch(data.block(p)),
                                train=True)
        assert b[:2] == ("staged", "mcoo" if learner._mesh_coo else "coo")
        out = learner.train_batch(b)
        if first is not None:
            first.after_step(learner, b, out)
        outs.append(out)
    return outs


# ------------------------------------------------ against the reference
@pytest.mark.parametrize("shape", ["1x4", "2x4"])
def test_mesh_learner_agrees_with_the_reference_under_the_cells_limits(
        data, shape):
    """Three FTRL steps from zeroed tables on an explicit 1 x 4 mesh (the
    chip's) and on the 2 x 4 mesh `make_mesh(num_model=4)` gives under
    eight devices (a rehearsal's), through `check.FirstSteps` as the
    benchmark's tap drives it."""
    mesh = make_mesh(1, SHARDS) if shape == "1x4" else make_mesh(
        num_model=SHARDS)
    assert "x".join(str(mesh.shape[a]) for a in ("data", "model")) == shape
    learner = LinearLearner(_cfg(), mesh)
    assert learner._mesh_coo and f"mesh={shape}" in learner.placement
    first = check.FirstSteps(data, NB, STEPS, reference.bucket_ids)
    outs = _train(learner, data, range(STEPS), first)
    assert first.done and first.problem is None
    assert first.order == [(p, 0) for p in range(STEPS)]
    assert [o["nex"] for o in outs] == [float(ROWS)] * STEPS
    ref = reference.run_steps(
        [data.batch(p, 0) for p in range(STEPS)], NB, CONFIG["hyper"],
        CONFIG["rehearsal"]["precision"])
    nums = check.numbers(first.as_run(), check.reference_as_run(ref, ROWS))
    ok, lines = check.verdict(nums, CONFIG["correct"]["limits"])
    assert ok, lines
    # interpreted kernels compute in f32: what is left is summation order
    assert nums["state_off_share"] <= 2e-3 and nums["loss_gap"] < 1e-5


def test_the_control_fails_the_new_configurations_limits():
    """bfloat16 tables in the reference's place: over at least one limit
    of the first steps and of the served step, on each seed."""
    limits = CONFIG["correct"]
    for seed in (31, 32, 33):
        nums = control.control_numbers(CONFIG, seed, rehearsal=True)
        assert not check.verdict(nums, limits["limits"])[0], nums
        assert not check.verdict(nums, limits["served_limits"])[0], nums
        assert nums["state_off_share"] > limits["limits"]["state_off_share"]


def test_the_configuration_states_what_the_mesh_kernels_round():
    """The `mcoo` step sums the gradient in f32 (no compacted one-hot
    scatter): `push_g` is f32 and everything else is the accepted
    configuration's."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "linear-ftrl-criteo1tb.json")) as fh:
        one_chip = json.load(fh)
    assert CONFIG["precision"] == dict(one_chip["precision"], push_g="f32")
    assert CONFIG["control_precision"]["tables"] == "bf16"
    assert CONFIG["hyper"] == one_chip["hyper"]
    assert CONFIG["correct"] == one_chip["correct"]
    differ = {k for k in CONFIG["conf"]
              if CONFIG["conf"][k] != one_chip["conf"][k]}
    assert differ == {"num_buckets", "model_shards"}
    assert CONFIG["conf"]["num_buckets"] == 1 << 30
    assert (CONFIG["conf"]["num_buckets"]
            >= sum(KEYS.cards[gen.N_INT:]) > CONFIG["conf"]["num_buckets"] / 2)
    assert set(CONFIG["reduced"]) == {"train_rows"}
    assert CONFIG["rehearsal"]["conf"] == one_chip["rehearsal"]["conf"]


# ------------------------------------------------------ the share adds up
def _packed(data, part):
    keys, _ = data.batch(part, 0)
    idx = reference.bucket_ids(keys, NB).reshape(-1)
    seg = np.repeat(np.arange(ROWS), gen.NNZ)
    return idx, seg, np.ones(len(idx), np.float32)


def _mesh_pack(idx, seg, val):
    cap = ck.mesh_capacity(ROWS * gen.NNZ, 1, SHARDS)
    return ck.pack_mesh_coo(idx, seg, val, NB, ROWS, 1, SHARDS, cap,
                            ck.mesh_block(cap, NB // SHARDS))


def test_one_shard_is_hotter_than_the_rest(data):
    idx, seg, val = _packed(data, 0)
    mc = _mesh_pack(idx, seg, val)
    cells = mc.cell_nnz[0]
    assert cells.sum() == len(idx) and mc.dropped_nnz == 0
    assert [int((mc.sval[0, m] != 0).sum()) for m in range(SHARDS)] == list(
        cells)
    assert cells.max() * SHARDS / cells.sum() > 1.15


def test_partial_margins_of_the_four_shards_sum_to_the_one_device_margins(
        data):
    idx, seg, val = _packed(data, 0)
    w = np.random.default_rng(5).normal(size=NB).astype(np.float32)
    one = ck.pack_sorted_coo(idx, seg, val, NB, capacity=ROWS * gen.NNZ)
    want = np.asarray(ck.coo_spmv(
        jnp.asarray(w), *(jnp.asarray(x) for x in (
            one.idx, one.seg, one.val, one.tmap, one.first)), ROWS))
    mc = _mesh_pack(idx, seg, val)
    nb_m = NB // SHARDS
    parts = [np.asarray(ck.coo_spmv(
        jnp.asarray(w[m * nb_m:(m + 1) * nb_m]), *(jnp.asarray(x[0, m])
                                                   for x in (
            mc.sidx, mc.sseg, mc.sval, mc.tmap, mc.first)), ROWS))
        for m in range(SHARDS)]
    assert all(np.abs(p).max() > 0 for p in parts)   # every shard has a say
    got = np.sum(parts, axis=0, dtype=np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    # and the mesh kernel's psum is that sum
    mesh = make_mesh(1, SHARDS)
    xw = ck.mesh_coo_spmv(mesh, jnp.asarray(w), *(jnp.asarray(x) for x in (
        mc.sidx, mc.sseg, mc.sval, mc.tmap, mc.first)), ROWS)
    np.testing.assert_allclose(np.asarray(xw), want, rtol=2e-6, atol=2e-6)


def test_sharded_tables_equal_the_one_device_learners_on_touched_buckets(
        data):
    ids = np.unique(np.concatenate([
        reference.bucket_ids(data.batch(p, 0)[0], NB).reshape(-1)
        for p in range(STEPS)]))
    assert len(np.unique(ids // (NB // SHARDS))) == SHARDS
    one = LinearLearner(_cfg(model_shards=1, compact_cap=0), make_mesh(1, 1))
    four = LinearLearner(_cfg(), make_mesh(1, SHARDS))
    outs1 = _train(one, data, range(STEPS))
    outs4 = _train(four, data, range(STEPS))
    for a, b in zip(outs1, outs4):
        assert a["nex"] == b["nex"]
        assert a["objv"] == pytest.approx(b["objv"], rel=1e-6)
    t1 = check.read_tables(one.store.state, ids, len(ids))
    t4 = check.read_tables(four.store.state, ids, len(ids))
    for k in check.LEAVES:
        assert np.abs(t4[k]).max() > 0
        # another summation order, and z + g - sigma w nearly cancels
        # on a bucket or two of some thousands
        np.testing.assert_allclose(t4[k], t1[k], rtol=1e-4, atol=1e-6)
        # nothing but the touched buckets moved
        assert int(jnp.sum(four.store.state[k] != 0)) == int(
            np.sum(t4[k] != 0))


# ------------------------------------------------- the check's read-back
@pytest.mark.parametrize("shape", [(1, 4), (2, 4)])
def test_read_tables_gathers_from_a_model_sharded_table(shape):
    mesh = make_mesh(*shape)
    store = KVStore(mesh, NB, {k: TableSpec() for k in check.LEAVES})
    full = {k: (np.arange(NB, dtype=np.float32) * (i + 1) + 0.5)
            for i, k in enumerate(check.LEAVES)}
    state = {k: jax.device_put(v, store.sharding(k)) for k, v in full.items()}
    assert len(state["w"].sharding.device_set) == shape[0] * shape[1]
    nb_m = NB // shape[1]
    ids = np.unique(np.concatenate(
        [[m * nb_m, m * nb_m + 12345, (m + 1) * nb_m - 1]
         for m in range(shape[1])] + [np.random.default_rng(2).integers(
             0, NB, 500)])).astype(np.int64)
    got = check.read_tables(state, ids, capacity=2048)   # padded with id 0
    for k in check.LEAVES:
        assert got[k].shape == ids.shape
        np.testing.assert_array_equal(got[k], full[k][ids])


# ------------------------------------------------------- the pack cache
def test_an_mcoo_batch_survives_the_pack_cache_and_replays_the_same_step(
        data, tmp_path):
    mesh = make_mesh(1, SHARDS)
    fresh, replay = LinearLearner(_cfg(), mesh), LinearLearner(_cfg(), mesh)
    assert fresh.pack_cache_token() == replay.pack_cache_token() is not None
    assert fresh.pack_cache_token() != LinearLearner(
        _cfg(model_shards=1), make_mesh(1, 1)).pack_cache_token()
    packed = fresh.prepare_batch(data.block(0))
    assert packed[0] == "mcoo"
    # the memory tier hands back the object, the disk tier its bytes
    mem = pc.PackCache(mem_bytes=pc.nbytes_of(packed) + 1)
    assert mem.put("k", packed) and mem.get("k") is packed
    disk = pc.PackCache(mem_bytes=0, disk_dir=str(tmp_path))
    assert disk.put("k", packed)
    back = disk.get("k")
    assert back is not packed and back[0] == "mcoo" and back[-1] == ROWS
    for name in ("sidx", "sseg", "sval", "tmap", "first", "cell_nnz"):
        a, b = getattr(packed[1], name), getattr(back[1], name)
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    assert back[1].dropped_nnz == 0
    np.testing.assert_array_equal(back[2], packed[2])
    out_a = fresh.train_batch(fresh.stage_batch(packed))
    out_b = replay.train_batch(replay.stage_batch(back))
    assert out_a == out_b
    for k in check.LEAVES:
        np.testing.assert_array_equal(np.asarray(fresh.store.state[k]),
                                      np.asarray(replay.store.state[k]))


# ------------------------------------------------------- shard overflow
def test_shard_overflow_raises_the_counter_and_counts_as_failed():
    """Every nonzero in shard 0: 256 x 39 = 9,984 against a shard's
    8,192 slots. The warning stays, the counter rises by what was
    dropped, and the benchmark's `failed` (warnings inside the window)
    counts the batch."""
    learner = LinearLearner(_cfg(), make_mesh(1, SHARDS))
    cap = ck.mesh_capacity(ROWS * gen.NNZ, 1, SHARDS)
    assert cap == 8192 < ROWS * gen.NNZ
    rng = np.random.default_rng(9)
    blk = RowBlock(
        label=(rng.random(ROWS) < 0.3).astype(np.float32),
        offset=np.arange(0, ROWS * gen.NNZ + 1, gen.NNZ, dtype=np.int64),
        index=rng.integers(0, NB // SHARDS, ROWS * gen.NNZ).astype(np.uint64),
        value=None)
    warns = tp.WarningLog()
    warns.phase = "window"
    log = logging.getLogger("wormhole_tpu")
    log.addHandler(warns)
    before = _mesh_counters()
    try:
        b = learner.prepare_batch(blk)
    finally:
        log.removeHandler(warns)
    dropped = ROWS * gen.NNZ - cap
    assert b[1].dropped_nnz == dropped
    assert list(b[1].cell_nnz[0]) == [ROWS * gen.NNZ, 0, 0, 0]
    assert _counter("linear.mesh.dropped_nnz") - before[
        "dropped_nnz"] == dropped
    # the fullest cell counts what fell into it, cut or not
    assert _counter("linear.mesh.shard_nnz_max") - before[
        "shard_nnz_max"] == ROWS * gen.NNZ
    assert _counter("linear.mesh.shard_nnz_sum") - before[
        "shard_nnz_sum"] == ROWS * gen.NNZ
    assert warns.count("window") == 1       # run.result(): failed = 1
    assert "mesh shard overflow" in warns.messages[0][1]
    # the step still runs on what was kept
    out = learner.train_batch(learner.stage_batch(b))
    assert out["nex"] == float(ROWS)


def test_a_batch_that_fits_drops_nothing_and_counts_its_cells(data):
    learner = LinearLearner(_cfg(), make_mesh(1, SHARDS))
    before = _mesh_counters()
    b = learner.prepare_batch(data.block(1))
    assert _counter("linear.mesh.dropped_nnz") == before["dropped_nnz"]
    assert _counter("linear.mesh.shard_nnz_sum") - before[
        "shard_nnz_sum"] == ROWS * gen.NNZ
    assert _counter("linear.mesh.shard_nnz_max") - before[
        "shard_nnz_max"] == int(b[1].cell_nnz.max())


# ------------------------------------------------ the clamp, and the span
@pytest.mark.parametrize("app", ["linear", "difacto"])
def test_model_shards_beyond_the_devices_are_clamped_printed_and_counted(
        app, capsys):
    ndev = len(jax.devices())
    name = f"{app}.mesh.clamped_shards"
    before = _counter(name)
    mesh = local_mesh(app, 2 * ndev)
    assert mesh.shape["model"] == ndev and mesh.shape["data"] == 1
    assert _counter(name) - before == ndev
    assert f"[{app}] model_shards={2 * ndev} > {ndev} devices" in (
        capsys.readouterr().out)
    # a conf the devices can hold is not touched
    mesh = local_mesh(app, SHARDS)
    assert mesh.shape["model"] == SHARDS
    assert mesh.shape["data"] == ndev // SHARDS
    assert _counter(name) - before == ndev and not capsys.readouterr().out


def test_the_apps_build_their_mesh_through_the_clamp():
    from wormhole_tpu.apps import linear as app

    learner = app.make_learner(_cfg(), None)
    assert learner.mesh.shape["model"] == SHARDS and learner._mesh_coo


def test_annotate_reaches_the_innermost_open_span_and_h2d_carries_bytes(
        data, tmp_path, monkeypatch):
    monkeypatch.setenv("WH_OBS_DIR", str(tmp_path / "obs"))
    tracer = obs_trace.init_from_env()
    try:
        obs_trace.annotate(nobody=1)            # no span open: nothing
        with obs_trace.span("test.outer"):
            with obs_trace.span("test.inner"):
                obs_trace.annotate(rows=3)
            obs_trace.annotate(parts=2)
        learner = LinearLearner(_cfg(), make_mesh(1, SHARDS))
        packed = learner.prepare_batch(data.block(2))
        with obs_trace.span("loader.h2d", cat="loader", part=0, i=0):
            staged = learner.stage_batch(packed)
        one = LinearLearner(_cfg(model_shards=1, compact_cap=0),
                            make_mesh(1, 1))
        with obs_trace.span("loader.h2d", cat="loader", part=0, i=1):
            flat = one.stage_batch(one.prepare_batch(data.block(2)))
    finally:
        tracer.close()
        monkeypatch.delenv("WH_OBS_DIR")
        obs_trace.init_from_env()
    spans = [json.loads(ln) for ln in open(tracer.path)]
    by = {(s["name"], s["args"].get("i")): s["args"] for s in spans
          if s.get("ph") == "X"}
    assert by["test.inner", None] == {"rows": 3}
    assert by["test.outer", None] == {"parts": 2}
    mc = packed[1]
    want = sum(x.nbytes for x in (mc.sidx, mc.sseg, mc.sval, mc.tmap,
                                  mc.first, packed[2], packed[3]))
    assert by["loader.h2d", 0]["bytes"] == want == sum(
        a.nbytes for a in staged[2])
    # one device: every batch kind says its bytes since PR 38
    assert by["loader.h2d", 1]["bytes"] == sum(a.nbytes for a in flat[2]) > 0
    assert obs_trace.ACTIVE is None
