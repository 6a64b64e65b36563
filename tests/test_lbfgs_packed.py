"""The batch solver's packed passes (PR 48): `LinearObjFunction`'s two
sparse products on the packed-COO kernels at float32, against the
`segment_sum` programs they replace on one TPU device. Here off the chip:
the kernels interpreted, `num_feature` one table tile, a few hundred rows
in chunks of 128 (`_packed`, the tests' private way in). Agreement and
counts; nothing here is a speed."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import tap  # noqa: E402
from wormhole_tpu.apps import lbfgs_linear as app  # noqa: E402
from wormhole_tpu.models import batch_objectives as bo  # noqa: E402
from wormhole_tpu.obs import trace as obs_trace  # noqa: E402
from wormhole_tpu.obs.metrics import REGISTRY  # noqa: E402
from wormhole_tpu.ops import coo_kernels as ck  # noqa: E402
from wormhole_tpu.parallel.mesh import batch_sharding, make_mesh  # noqa: E402
from wormhole_tpu.solver.lbfgs import LBFGSConfig, LBFGSSolver  # noqa: E402

NF, ROWS, PER, CHUNK = ck.TILE, 256, 12, 128
HOT = 777


def one_mesh():
    return make_mesh(1, 1, devices=jax.devices()[:1])


def make_batch(rng, kind, mesh, rows=ROWS, nf=NF):
    """A resident batch as `load_batches` gives it (CSR order, padding
    with val 0 at the last row), of one of the shapes a pack must get
    right."""
    cap = rows * PER
    seg = np.full(cap, rows - 1, np.int32)
    idx = np.zeros(cap, np.int32)
    val = np.zeros(cap, np.float32)
    live_rows = rows - 56 if kind == "masked_tail" else rows
    k = 0
    for r in range(live_rows):
        n = int(rng.integers(1, PER + 1))
        if kind == "empty_fields" and r % 3 == 0:
            n = 0                                   # a row with no field
        cols = rng.integers(0, nf, n)
        if kind == "repeated_column" and n > 2:
            cols[1:3] = cols[0]                     # thrice in one row
        if kind == "hot_column" and n and r % 8:
            cols[-1] = HOT                          # in most rows
        seg[k:k + n], idx[k:k + n] = r, cols
        val[k:k + n] = rng.standard_normal(n)
        if kind == "empty_fields" and n > 1:
            val[k + 1] = 0.0                        # an explicit zero
        k += n
    label = np.zeros(rows, np.float32)
    label[:live_rows] = rng.integers(0, 2, live_rows)
    mask = np.zeros(rows, np.float32)
    mask[:live_rows] = 1.0
    bsh = batch_sharding(mesh, 1)
    return tuple(jax.device_put(x, bsh)
                 for x in (seg, idx, val, label, mask))


KINDS = ["empty_fields", "repeated_column", "hot_column", "masked_tail"]


@pytest.fixture(scope="module")
def pair():
    """The two lowerings over the same two batches, every shape of
    `KINDS` among their rows' chunks, and a point to evaluate them at."""
    rng = np.random.default_rng(48)
    mesh = one_mesh()
    batches = [make_batch(rng, k, mesh) for k in KINDS]
    xla = bo.LinearObjFunction(batches, NF, mesh)
    packed = bo.LinearObjFunction(batches, NF, mesh, _packed=CHUNK)
    p = (0.3 * rng.standard_normal(NF + 1)).astype(np.float32)
    return xla, packed, p


# ----------------------------------------------------- the two lowerings
def test_the_rule_packs_by_request_and_says_so(pair):
    xla, packed, _ = pair
    assert not xla.packed and "path=xla (backend is cpu, not tpu)" in (
        xla.placement)
    assert packed.packed and "path=pallas (interpret mode, by request)" in (
        packed.placement)
    assert f"chunks={len(KINDS) * ROWS // CHUNK}x{CHUNK} rows" in (
        packed.placement)


@pytest.mark.parametrize("kind", KINDS)
def test_a_packed_chunk_holds_the_rows_live_nonzeros(pair, kind):
    """Each chunk: the batch's live triples of its rows, sorted by column
    in BLK-padded tile runs, the rows counted from the chunk's first."""
    xla, packed, _ = pair
    b = KINDS.index(kind)
    seg, idx, val, label, mask = (np.asarray(x) for x in xla.batches[b])
    per = ROWS // CHUNK
    shapes = {tuple(x.shape for x in c) for c in packed._chunks}
    assert len(shapes) == 1                     # one program runs them all
    for c in range(per):
        sidx, sseg, sval, tmap, first, clabel, cmask = (
            np.asarray(x) for x in packed._chunks[b * per + c])
        live = (val != 0) & (seg // CHUNK == c)
        got = sval != 0
        assert got.sum() == live.sum()
        want = sorted(zip(idx[live], seg[live] - c * CHUNK, val[live]))
        assert sorted(zip(sidx[got], sseg[got], sval[got])) == want
        assert (np.diff(sidx[got]) >= 0).all() and first[0] == 1
        assert not tmap.any()                   # one table tile
        np.testing.assert_array_equal(clabel,
                                      label[c * CHUNK:(c + 1) * CHUNK])
        np.testing.assert_array_equal(cmask, mask[c * CHUNK:(c + 1) * CHUNK])


@pytest.mark.parametrize("kind", KINDS)
def test_packed_margins_equal_segment_sums(pair, kind):
    """X w + bias of each chunk against the `segment_sum` margin of its
    rows: float32 rounding apart (another order of summation)."""
    xla, packed, p = pair
    b = KINDS.index(kind)
    seg, idx, val, _, _ = xla.batches[b]
    want = np.asarray(xla.predict(jnp.asarray(p), seg, idx, val, ROWS))
    w, bias, _ = packed._split(jnp.asarray(p))
    per = ROWS // CHUNK
    got = np.concatenate([
        np.asarray(ck.coo_spmv(w, *c[:5], CHUNK, dtype=jnp.float32) + bias)
        for c in packed._chunks[b * per:(b + 1) * per]])
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


def test_packed_loss_and_gradient_equal_the_segment_sum_objective_s(pair):
    """A pass each way at the same point: the summed loss, every entry of
    the gradient, the bias's with them; nothing off the touched columns."""
    xla, packed, p = pair
    pa, pb = xla.place(p), packed.place(p)
    np.testing.assert_allclose(packed.eval(pb), xla.eval(pa), rtol=1e-6)
    ga, gb = np.asarray(xla.grad(pa)), np.asarray(packed.grad(pb))
    assert gb.shape == ga.shape == (NF + 1,) and gb.dtype == np.float32
    scale = np.abs(ga).max()
    np.testing.assert_allclose(gb, ga, rtol=1e-5, atol=1e-6 * scale)
    np.testing.assert_allclose(gb[NF], ga[NF], rtol=1e-6)    # the bias
    assert abs(ga[HOT]) > 0 and (gb != 0).sum() == (ga != 0).sum()


def test_the_float32_bodies_are_exact_where_bfloat16_rounds():
    """A fetch through the float32 bodies returns the table's value to
    the bit (three bfloat16 addends, each product exact); the bfloat16
    bodies, the cell's control precision, round it."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal(4096).astype(np.float32) * np.float32(1e-3)
    hi, mid, lo = (np.asarray(a, np.float32) for a in ck._split3(
        jnp.asarray(x)))
    assert ((hi + mid) + lo).tobytes() == x.tobytes()
    w = np.zeros(NF, np.float32)
    cols = rng.choice(NF, 128, replace=False).astype(np.int32)
    w[cols] = rng.standard_normal(128)
    p = ck.pack_sorted_coo(cols, np.arange(128, dtype=np.int32),
                           np.ones(128, np.float32), NF)
    args = (p.idx, p.seg, p.val, p.tmap, p.first, 128)
    exact = np.asarray(ck.coo_spmv(w, *args, dtype=jnp.float32))
    assert exact.tobytes() == w[cols].tobytes()
    rounded = np.asarray(ck.coo_spmv(w, *args, dtype=jnp.bfloat16))
    assert (rounded != w[cols]).mean() > 0.9
    np.testing.assert_allclose(rounded, w[cols], rtol=2 ** -8)


def test_the_push_adds_into_a_sum_so_far(pair):
    """`coo_spmv_t(acc=)`: acc + X^T d, what the product added afterwards
    gives (a tile's sum starts from acc's tile, so a column that two
    bodies of a block add to may round otherwise)."""
    _, packed, _ = pair
    rng = np.random.default_rng(9)
    d = jnp.asarray(rng.standard_normal(CHUNK).astype(np.float32))
    acc = np.zeros(NF, np.float32)
    acc[rng.choice(NF, 4096, replace=False)] = rng.standard_normal(4096)
    coo = packed._chunks[5][:5]
    alone = ck.coo_spmv_t(d, *coo, NF, dtype=jnp.float32)
    added = ck.coo_spmv_t(d, *coo, NF, dtype=jnp.float32,
                          acc=jnp.asarray(acc))
    assert np.asarray(alone).any()
    np.testing.assert_allclose(np.asarray(added), acc + np.asarray(alone),
                               rtol=1e-6, atol=1e-6)
    untouched = np.asarray(alone) == 0
    assert np.asarray(added)[untouched].tobytes() == acc[untouched].tobytes()


# ------------------------------------------- one program, and the counter
PACKED = "lbfgs.passes.packed"


def _counters():
    """The two counters as a snapshot lists them: reading must not
    register the packed one, which building a packed objective does."""
    snap = REGISTRY.snapshot()["counters"]
    return {k: snap.get(k, 0) for k in ("lbfgs.passes", PACKED)}


@pytest.fixture(scope="module", autouse=True)
def the_packed_counter_leaves_with_this_file():
    """`tests/benchmark/test_benchmark_batch_cell.py` pins the `lbfgs.*`
    counters a process that never packed lists; a worker that runs it
    after this file must find them so."""
    yield
    with REGISTRY._lock:
        REGISTRY._counters.pop(PACKED, None)


def _job(obj, iters=3):
    solver = LBFGSSolver(obj, LBFGSConfig(max_iter=iters, m=3, reg_l1=1.0,
                                          max_linesearch=30))
    objv = []
    solver.run(verbose=False, on_iter=lambda it, o, *_: objv.append(o))
    return solver, objv


def test_a_job_on_the_packed_path_follows_the_fall_back_s_and_counts_itself(
        pair):
    """The same job on both lowerings: the same trial counts and
    objectives to float32 rounding; `lbfgs.passes.packed`, which the
    objective counts, moves with the solver's `lbfgs.passes` on the
    packed path and not at all on the fall-back, and a pass asked of the
    objective by anyone else counts there alone; every chunk of every
    pass ran the one program a pass kind has, and a restart compiles
    nothing."""
    xla, packed, p = pair
    before = _counters()
    s_xla, o_xla = _job(xla)
    mid = _counters()
    assert mid["lbfgs.passes"] > before["lbfgs.passes"]
    assert mid[PACKED] == before[PACKED]
    s_packed, o_packed = _job(packed)
    after = _counters()
    passes = after["lbfgs.passes"] - mid["lbfgs.passes"]
    assert passes == mid["lbfgs.passes"] - before["lbfgs.passes"] > 6
    assert after[PACKED] - mid[PACKED] == passes
    for obj, counted in ((xla, 0), (packed, 2)):
        obj.eval(obj.place(p)), obj.grad(obj.place(p))
        assert _counters() == {"lbfgs.passes": after["lbfgs.passes"],
                               PACKED: after[PACKED] + counted}
    assert s_packed.iter == s_xla.iter == 3
    np.testing.assert_allclose(o_packed, o_xla, rtol=2e-6)
    np.testing.assert_allclose(s_packed.objv_history, s_xla.objv_history,
                               rtol=2e-6)
    assert packed._eval_chunk._cache_size() == 1
    assert packed._grad_chunk._cache_size() == 1
    with tap.CompileLog() as clog:
        clog.phase = "again"
        s_packed.reset()
        again = []
        s_packed.run(verbose=False,
                     on_iter=lambda it, o, *_: again.append(o))
    assert clog.compiles("again") == 0 and again == o_packed


def test_no_span_or_counter_is_opened_for_the_path_without_a_sink(pair):
    """The path adds one counter and no span: with no tracer a pass on
    either lowering opens the shared no-op, and the packed programs wait
    for nothing but the pass's one counted read."""
    import inspect

    assert obs_trace.ACTIVE is None
    assert obs_trace.span("lbfgs.obj_pass") is obs_trace.span("lbfgs.fetch")
    src = inspect.getsource(bo)
    assert "block_until_ready" not in src and "span(" not in src
    # the one counter is the packed objective's own: the fall-back has none
    assert src.count("REGISTRY.") == 1
    xla, packed, _ = pair
    assert not hasattr(xla, "_passes")
    assert packed._passes is REGISTRY.counter(PACKED)


# ------------------------------------------------- the arms of the rule
def _parent_passes(batches, nf, p):
    """A pass each of the `segment_sum` formulation, written out as the
    parent commit has it: what every fall-back must give to the bit."""
    def loss(p, seg, idx, val, label, mask):
        xw = jax.ops.segment_sum(val * jnp.take(p[:nf], idx), seg,
                                 num_segments=label.shape[0]) + p[nf]
        return jnp.sum((jax.nn.softplus(xw) - label * xw) * mask)

    ev, gr = jax.jit(loss), jax.jit(jax.grad(loss))
    tot, g = jnp.zeros(()), jnp.zeros_like(p)
    for b in batches:
        tot, g = tot + ev(p, *b), g + gr(p, *b)
    return float(tot), np.asarray(g)


def _arm(name):
    """(batches, num_feature, mesh, forced chunk, the reason's words)."""
    rng = np.random.default_rng(5)
    mesh = one_mesh()
    if name == "not_a_tpu":
        return [make_batch(rng, "hot_column", mesh)], NF, mesh, None, (
            "backend is cpu, not tpu")
    if name == "mesh_of_two":
        mesh = make_mesh(2, 1, devices=jax.devices()[:2])
        return [make_batch(rng, "hot_column", mesh)], NF, mesh, CHUNK, (
            "sharded over 2 devices")
    if name == "unaligned_num_feature":
        nf = 4096
        return [make_batch(rng, "hot_column", mesh, nf=nf)], nf, mesh, (
            CHUNK), "num_feature 4096 is not a multiple of 65536"
    if name == "rows_not_a_multiple_of_the_chunk":
        return [make_batch(rng, "hot_column", mesh, rows=192)], NF, mesh, (
            CHUNK), "192 rows is not a multiple of the row chunk 128"
    if name == "chunk_not_a_multiple_of_128":
        return [make_batch(rng, "hot_column", mesh, rows=192)], NF, mesh, (
            64), "192 rows is not a multiple of the row chunk 64 and of 128"
    if name == "a_column_outside_the_table":
        b = make_batch(rng, "hot_column", mesh, nf=2 * NF)
        return [b], NF, mesh, CHUNK, "a column id outside [0, 65536)"
    raise AssertionError(name)


ARMS = ["not_a_tpu", "mesh_of_two", "unaligned_num_feature",
        "rows_not_a_multiple_of_the_chunk", "chunk_not_a_multiple_of_128",
        "a_column_outside_the_table"]


@pytest.mark.parametrize("name", ARMS)
def test_an_arm_of_the_rule_takes_the_fall_back_to_the_bit(name):
    batches, nf, mesh, chunk, why = _arm(name)
    obj = bo.LinearObjFunction(batches, nf, mesh, _packed=chunk)
    assert not obj.packed and obj._chunks is None
    assert "path=xla" in obj.placement and why in obj.placement
    if name == "a_column_outside_the_table":
        return              # `take` past the table's end is not a result
    rng = np.random.default_rng(6)
    p = obj.place((0.3 * rng.standard_normal(nf + 1)).astype(np.float32))
    tot, g = _parent_passes(batches, nf, p)
    assert obj.eval(p) == tot
    assert np.asarray(obj.grad(p)).tobytes() == g.tobytes()


def test_the_production_chunk_is_the_rule_s_on_a_tpu_of_one_device():
    """What the rule reads, with the device's platform played: the
    deployment's shapes pack (2^26 columns, batches of 524,288 and
    262,144 rows in chunks of `ROW_CHUNK`), the rehearsal's do not."""
    class Dev:
        platform = "tpu"

    class Mesh:
        size = 1
        devices = np.array([Dev()], object)

    def shaped(rows):
        return [(None, None, None, np.zeros(rows, np.float32), None)]

    assert bo.ROW_CHUNK % ck.LANES == 0
    assert bo.packed_rule(shaped(524288), 1 << 26, Mesh, bo.ROW_CHUNK) == ""
    assert bo.packed_rule(shaped(262144), 1 << 26, Mesh, bo.ROW_CHUNK) == ""
    assert "num_feature 4096" in bo.packed_rule(shaped(1024), 4096, Mesh,
                                                bo.ROW_CHUNK)
    assert "num_feature 0" in bo.packed_rule(shaped(524288), 0, Mesh,
                                             bo.ROW_CHUNK)
    assert "1024 rows" in bo.packed_rule(shaped(1024), 1 << 26, Mesh,
                                         bo.ROW_CHUNK)
    assert bo.packed_rule([], 1 << 26, Mesh, bo.ROW_CHUNK) == (
        "no resident batch")


def test_the_fm_objective_has_no_packed_path():
    rng = np.random.default_rng(7)
    mesh = one_mesh()
    obj = bo.FmObjFunction([make_batch(rng, "hot_column", mesh)], NF, 4,
                           mesh)
    assert not getattr(obj, "packed", False)
    assert type(obj).eval is bo._BatchObjBase.eval
    assert type(obj).grad is bo._BatchObjBase.grad


# ------------------------------------------------------------- the seam
def _libsvm(tmp_path, rows=512):
    rng = np.random.default_rng(11)
    w = rng.standard_normal(NF)
    path = tmp_path / "rows.libsvm"
    with open(path, "w") as fh:
        for _ in range(rows):
            ids = np.sort(rng.choice(NF, 9, replace=False))
            ids[0] = HOT
            y = int(w[ids].sum() > 0)
            fh.write(f"{y} " + " ".join(f"{j}:1" for j in ids) + "\n")
    return str(path).replace(".libsvm", r"\.libsvm")


def test_make_solver_s_result_reads_as_resident_on_the_packed_path(
        tmp_path, monkeypatch, capsys):
    """The benchmark driver's seam: `make_solver` gives (solver, obj,
    batches, num_feature), the batches device arrays in row order (kind
    `resident`), the held-out objective `type(obj)(held, nf, obj.mesh)`;
    the start-up statement goes to stderr, so stdout stays `main`'s."""
    from benchmark.drivers import batch as driver

    class Forced(bo.LinearObjFunction):
        def __init__(self, batches, num_feature, mesh):
            super().__init__(batches, num_feature, mesh, _packed=CHUNK)

    pattern = _libsvm(tmp_path)
    cfg = app.LbfgsLinearConfig(data=pattern, minibatch=256, nnz_per_row=16,
                                num_feature=NF, reg_L1=1.0,
                                max_lbfgs_iter=2, max_linesearch_iter=30)
    monkeypatch.setattr(app, "LinearObjFunction", Forced)
    solver, obj, batches, nf = app.make_solver(cfg, one_mesh())
    io = capsys.readouterr()
    assert io.out == "" and io.err.startswith("[lbfgs] backend=cpu")
    assert "path=pallas" in io.err and obj.packed and nf == NF
    assert len(batches) == 2 and obj.batches is batches
    assert {driver.KIND if all(hasattr(x, "devices") for b in batches
                               for x in b) else "host"} == {"resident"}
    held_obj = type(obj)(batches[:1], nf, obj.mesh)
    assert held_obj.packed and len(held_obj._chunks) == 256 // CHUNK
    w, objv = solver.run(verbose=False)
    assert solver.iter == 2 and objv < solver.objv_history[0]
    assert np.isfinite(held_obj.eval(w))


def test_main_s_output_is_the_same_with_the_statement_on_stderr(
        tmp_path, capsys):
    """Off the chip `main` takes the fall-back: stdout is what the parent
    printed (iterations, the final objective), the path on stderr."""
    pattern = _libsvm(tmp_path, rows=300)
    assert app.main([f"data={pattern}", "reg_L1=1", "max_lbfgs_iter=2",
                     "minibatch=256", "nnz_per_row=16",
                     f"num_feature={NF}", "max_linesearch_iter=30"]) == 0
    io = capsys.readouterr()
    lines = io.out.splitlines()
    assert lines[0].startswith("lbfgs init: objv ")
    assert [ln.split(":")[0] for ln in lines[1:]] == [
        "lbfgs iter 1", "lbfgs iter 2", "final objective"]
    assert io.err.count("[lbfgs] ") == 1 and (
        "path=xla (backend is cpu, not tpu)" in io.err)
