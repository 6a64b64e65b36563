"""The batch solver as a deployment (PR 47): the program's own path
(`apps/lbfgs_linear.make_solver` -> `load_batches` with the fold ->
`LBFGSSolver.run` with `on_iter`) against the benchmark's plain
reference `benchmark/reference/lbfgs_owlqn_linear.py` on seeded Criteo
rows at a small size: 64-bit keys folded into 4,096 columns, 2,048 rows.
Counts and agreement on the CPU; nothing here is a speed."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import check, gen, tap  # noqa: E402
from benchmark.reference import lbfgs_owlqn_linear as ref_mod  # noqa: E402
from wormhole_tpu.apps import lbfgs_linear as app  # noqa: E402
from wormhole_tpu.models.batch_objectives import (  # noqa: E402
    LinearObjFunction, load_batches)
from wormhole_tpu.obs import trace as obs_trace  # noqa: E402
from wormhole_tpu.obs.metrics import REGISTRY  # noqa: E402
from wormhole_tpu.parallel.mesh import make_mesh  # noqa: E402
from wormhole_tpu.solver.lbfgs import LBFGSConfig, LBFGSSolver  # noqa: E402

NF, MB, SEED = 4096, 1024, 3000004700
SIZES = {"feature": NF}
F32 = {"tables": "f32", "passes": "f32"}


def _config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "lbfgs-linear-criteo1tb.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def ds(tmp_path_factory):
    """Two crb parts of 1,024 generated rows: the format's 64-bit keys."""
    return gen.Dataset(str(tmp_path_factory.mktemp("rows")),
                       gen.KeyModel("criteo-terabyte"), SEED, "crb", MB,
                       2, 1, 0)


def _rows(ds):
    return [ds.batch(p, 0) for p in range(ds.train_parts)]


def _cfg(ds, **kw):
    return app.LbfgsLinearConfig(
        data=ds.train_pattern, data_format="crb", minibatch=MB,
        nnz_per_row=gen.NNZ, num_feature=NF, **kw)


def _job(ds, reg_l1, iters):
    """The program's job, every iteration's end noted."""
    solver, *_ = app.make_solver(_cfg(ds, reg_L1=reg_l1,
                                      max_lbfgs_iter=iters))
    seen = []

    def on_iter(it, objv, trials, state):
        seen.append({"iter": it, "objv": objv, "trials": trials,
                     "w": np.asarray(state["w"]),
                     "s": np.asarray(state["S"][-1]),
                     "y": np.asarray(state["Y"][-1]),
                     "objv0": state["objv"][0]})

    solver.run(verbose=False, on_iter=on_iter)
    return solver, seen


def _hyper(reg_l1):
    return {"reg_L1": reg_l1, "reg_L2": 0.0, "m": 10.0}


# ------------------------------------------- the program and the reference
@pytest.mark.parametrize("reg_l1", [0.0, 1.0])
@pytest.mark.parametrize("iters", [3, 12])
def test_the_program_follows_the_reference(ds, reg_l1, iters):
    """Trial counts equal, every objective, and w and the newest pair
    after the last iteration, with `reg_L1` 0 (plain L-BFGS) and 1
    (OWL-QN: pseudo-gradient, sign fix, orthant projection)."""
    _, seen = _job(ds, reg_l1, iters)
    ref = ref_mod.run_steps(_rows(ds), SIZES, _hyper(reg_l1), F32,
                            iters=iters)
    assert [s["trials"] for s in seen] == ref["trials"]
    assert len(seen) == iters and seen[0]["trials"] > 1
    objv = [seen[0]["objv0"]] + [s["objv"] for s in seen]
    # float32 sums against float64 ones: 1e-6 an iteration at first, and
    # the two paths part by a few 1e-5 over a dozen iterations
    np.testing.assert_allclose(objv[:4], ref["objv"][:4], rtol=2e-6)
    np.testing.assert_allclose(objv, ref["objv"], rtol=2e-4)
    ids, last = ref["ids"]["feature"], ref["states"][-1]
    assert ids[-1] == NF                                # the bias
    far = 1.0 if iters == 3 else 100.0
    for leaf, tol in (("w", 1e-4), ("s", 5e-4), ("y", 5e-4)):
        got, want = seen[-1][leaf][ids], last[leaf]
        gap = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert gap <= far * tol, (leaf, gap)
        # nothing moved off the columns the rows touch
        assert not np.delete(seen[-1][leaf], ids).any()
    if reg_l1:
        # OWL-QN leaves exact zeros, the same ones on both sides but for
        # a handful at the orthant's edge
        zeros = (seen[-1]["w"][ids] == 0) == (last["w"] == 0)
        assert zeros.mean() > 0.995 and (last["w"] == 0).sum() > 10


def test_the_line_search_s_cap_is_the_app_s_key_and_the_reference_s(ds):
    """From w = 0 the first step along -g shrinks by about 1 / rows: 13
    halvings at 2,048 rows (22 at the deployment's million, over the
    default 20). Under a cap of 5 the job stops where it stands, in the
    program and in the reference."""
    solver, *_ = app.make_solver(_cfg(ds, reg_L1=1.0, max_lbfgs_iter=3,
                                      max_linesearch_iter=5))
    seen = []
    w, objv = solver.run(verbose=False, on_iter=lambda *a: seen.append(a))
    assert solver.iter == 0 and not seen and not np.asarray(w).any()
    assert objv == solver.objv_history[0]
    ref = ref_mod.run_steps(_rows(ds), SIZES, dict(
        _hyper(1.0), max_linesearch_iter=5.0), F32, iters=3)
    assert ref["trials"] == [5] and not ref["states"][0]["w"].any()
    assert app.LbfgsLinearConfig().max_linesearch_iter == 20
    assert ref_mod.run_steps(_rows(ds), SIZES, _hyper(1.0), F32,
                             iters=1)["trials"] == [13]


# ------------------------------------------------------------- the fold
def test_the_fold_is_key_mod_num_feature(ds):
    batches, nf = load_batches(ds.train_pattern, make_mesh(), "crb", MB,
                               gen.NNZ, 1, NF)
    assert nf == NF and len(batches) == 2
    folded = {(ds.batch(p, 0)[0] % np.uint64(NF)).astype(np.int32).tobytes()
              for p in range(2)}
    assert max(int(k.max()) for k, _ in _rows(ds)) >= 2 ** 54  # 64-bit keys
    for seg, idx, val, label, mask in batches:
        assert np.asarray(idx).dtype == np.int32
        assert np.asarray(idx).tobytes() in folded
        assert float(np.asarray(mask).sum()) == MB


def test_num_feature_zero_is_the_old_discovery_to_the_bit(tmp_path):
    """Raw ids, dimension max id + 1, the batch's arrays what
    `to_device_batch` gives under the modulus that changes no int32 id."""
    from wormhole_tpu.data.rowblock import to_device_batch
    from wormhole_tpu.solver.workload import iter_rowblocks

    path = _libsvm(tmp_path)
    pattern = str(path).replace(".libsvm", r"\.libsvm")
    batches, nf = load_batches(pattern, make_mesh(), minibatch=512,
                               nnz_per_row=16)
    old, top = [], -1
    for blk in iter_rowblocks(pattern, 1, "libsvm", 512):
        top = max(top, int(blk.index.max()))
        db = to_device_batch(blk, 512, 512 * 16, 2 ** 31 - 1)
        old.append((db.seg, db.idx, db.val, db.label, db.row_mask))
    assert nf == top + 1 == 200 and len(batches) == len(old) == 3
    for new, was in zip(batches, old):
        for a, b in zip(new, was):
            assert np.asarray(a).tobytes() == b.tobytes()
    with pytest.raises(AssertionError, match="int32"):
        load_batches(pattern, make_mesh(), num_feature=2 ** 31)


def _libsvm(tmp_path):
    rng = np.random.default_rng(3)
    w = rng.standard_normal(200)
    path = tmp_path / "t.libsvm"
    with open(path, "w") as fh:
        for _ in range(1500):
            ids = np.sort(rng.choice(200, 9, replace=False))
            y = int(w[ids].sum() + 0.3 * rng.standard_normal() > 0)
            fh.write(f"{y} " + " ".join(f"{j}:1" for j in ids) + "\n")
        fh.write("1 199:1\n")
    return path


def test_main_through_make_solver_gives_the_final_objective(tmp_path,
                                                            capsys):
    """`main` runs the job `make_solver` builds: the objective it prints
    is the one the parts give when put together by hand."""
    path = _libsvm(tmp_path)
    pattern = str(path).replace(".libsvm", r"\.libsvm")
    args = dict(reg_l1=1.0, max_iter=8)
    assert app.main([f"data={pattern}", "reg_L1=1", "max_lbfgs_iter=8",
                     "minibatch=512", "nnz_per_row=16"]) == 0
    out = capsys.readouterr().out
    mesh = make_mesh()
    batches, nf = load_batches(pattern, mesh, minibatch=512, nnz_per_row=16)
    solver = LBFGSSolver(LinearObjFunction(batches, nf, mesh), LBFGSConfig(
        m=10, reg_l2=0.0, min_rel_decrease=1e-7, **args))
    _, objv = solver.run(verbose=False)
    assert f"final objective: {objv:.6f}" in out
    assert out.count("lbfgs iter ") == 8


# ------------------------------------------------- on_iter and the restart
def test_on_iter_is_called_once_an_iteration_and_can_end_the_run(ds):
    solver, seen = _job(ds, 1.0, 5)
    assert [s["iter"] for s in seen] == [1, 2, 3, 4, 5] == list(
        range(1, solver.iter + 1))
    solver.reset()
    calls = []
    solver.run(verbose=False,
               on_iter=lambda it, *_: calls.append(it) or it == 2)
    assert calls == [1, 2] and solver.iter == 2


def test_a_restart_compiles_nothing(ds):
    """`reset()` then `run()` is the same job again on the programs the
    solver has: no backend compilation, the same objectives."""
    solver, seen = _job(ds, 1.0, 6)
    with tap.CompileLog() as clog:
        clog.phase = "again"
        solver.reset()
        again = []
        solver.run(verbose=False,
                   on_iter=lambda it, objv, *_: again.append(objv))
    assert clog.compiles("again") == 0
    assert again == [s["objv"] for s in seen]
    assert len(solver.S) == 6 and solver.iter == 6


def test_the_basis_is_worked_through_in_chunks_where_a_device_holds_it_whole(
        ds, monkeypatch):
    """At 2^26 columns a stacked copy of the basis (2m + 1 vectors) does
    not fit beside the vectors themselves: on a one-device mesh the Gram
    matrix and the combine take `_CHUNK` elements of each vector at a
    time. Here with a chunk of 1,000 over 4,097 elements (four chunks
    and 97 left over): the same matrix, the same direction, the same
    job."""
    from wormhole_tpu.solver import lbfgs

    rng = np.random.default_rng(5)
    vs = [rng.standard_normal(NF + 1).astype(np.float32) for _ in range(7)]
    coef = rng.standard_normal(7).astype(np.float32)
    whole, _ = _job(ds, 1.0, 6)
    monkeypatch.setattr(lbfgs, "_CHUNK", 1000)
    chunked, seen = _job(ds, 1.0, 6)
    B = np.stack(vs).astype(np.float64)
    np.testing.assert_allclose(np.asarray(chunked._gram(*vs)), B @ B.T,
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(whole._gram(*vs)), B @ B.T,
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(chunked._combine(coef, *vs)),
                               coef.astype(np.float64) @ B, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(chunked.objv_history, whole.objv_history,
                               rtol=1e-6)
    assert [s["trials"] for s in seen] == [13, 1, 1, 1, 1, 1]


# --------------------------------------------------- spans and the counter
def _counter(name):
    return REGISTRY.counter(name).value()


def test_the_spans_nest_and_host_syncs_equals_the_attribute(
        ds, tmp_path, monkeypatch):
    before = {k: _counter("lbfgs." + k) for k in (
        "iters", "passes", "linesearch_trials", "host_syncs")}
    monkeypatch.setattr(obs_trace, "ACTIVE", obs_trace.Tracer(
        str(tmp_path), "run", "local-test"))
    try:
        solver, seen = _job(ds, 1.0, 4)
    finally:
        obs_trace.ACTIVE.close()
    with open(obs_trace.ACTIVE.path) as fh:
        spans = [json.loads(ln) for ln in fh]
    spans = [s for s in spans if s.get("ph") == "X"]
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append((s["ts"], s["ts"] + s["dur"]))

    def inside(child, parent):
        return all(any(a <= c and d <= b + 1e-6 for a, b in by[parent])
                   for c, d in by[child])

    trials = sum(s["trials"] for s in seen)
    assert len(by["lbfgs.iter"]) == 4 == len(by["lbfgs.linesearch"])
    # the job's first gradient, at w = 0, is waited for by the first
    # objective pass's read and under no span of its own
    assert len(by["lbfgs.grad_pass"]) == 4
    assert len(by["lbfgs.obj_pass"]) == trials + 1
    # the first iteration has no history: no Gram matrix, no recursion
    assert len(by["lbfgs.gram"]) == 3 == len(by["lbfgs.two_loop"]) == len(
        by["lbfgs.combine"])
    for child in ("lbfgs.gram", "lbfgs.two_loop", "lbfgs.combine"):
        assert inside(child, "lbfgs.direction")
    assert inside("lbfgs.direction", "lbfgs.iter")
    assert inside("lbfgs.linesearch", "lbfgs.iter")
    assert inside("lbfgs.grad_pass", "lbfgs.iter")
    assert sum(any(a <= c and d <= b + 1e-6
                   for a, b in by["lbfgs.linesearch"])
               for c, d in by["lbfgs.obj_pass"]) == trials
    # every blocking read is under lbfgs.fetch, inside a pass, the Gram
    # matrix or the direction (pg.d: under lbfgs.combine from the second
    # iteration on, which so closes on the read that waits for it)
    assert sum(any(a <= c and d <= b + 1e-6 for a, b in by["lbfgs.combine"])
               for c, d in by["lbfgs.fetch"]) == 3
    delta = {k: _counter("lbfgs." + k) - v for k, v in before.items()}
    assert len(by["lbfgs.fetch"]) == delta["host_syncs"] == solver.host_syncs
    assert inside("lbfgs.fetch", "lbfgs.iter") is False  # the first passes'
    assert delta["iters"] == 4 and delta["linesearch_trials"] == trials
    assert delta["passes"] == trials + 1 + 5
    # an objective pass makes two reads (the summed loss, the regularised
    # value), an iteration pg.d and s.y, and from the second the matrix
    assert solver.host_syncs == 2 * (trials + 1) + 2 * 4 + 3


def test_no_span_is_opened_without_a_sink(ds):
    assert obs_trace.ACTIVE is None
    assert obs_trace.span("lbfgs.iter") is obs_trace.span("lbfgs.fetch")
    # and nothing waits for a span's sake: every blocking wait of the
    # solver is a counted read through `fetch`
    import inspect

    from wormhole_tpu.solver import lbfgs

    assert "block_until_ready" not in inspect.getsource(lbfgs)


# ------------------------------------------- what the comparison refuses
@pytest.mark.parametrize("fault", ["batch_left_out", "l1_left_out",
                                   "no_projection", "bf16"])
def test_a_planted_fault_fails_the_comparison(ds, fault):
    """The reference with a fault planted (a batch left out of the
    gradient; `reg_L1` left out of the objective; the orthant projection
    skipped) or with w, g, S, Y kept in bfloat16 (the control), in the
    program's place: the configuration's limits refuse each, and admit
    the sound reference against itself."""
    config = _config()
    limits = {**config["correct"]["limits"]}
    rows, hyper = _rows(ds), _hyper(1.0)
    sound = ref_mod.run_steps(rows, SIZES, hyper, F32, iters=3)
    if fault == "bf16":
        other = ref_mod.run_steps(rows, SIZES, hyper,
                                  config["control_precision"], iters=3)
    else:
        other = ref_mod.run_steps(rows, SIZES, hyper, F32, iters=3,
                                  fault=fault)
    n = float(ds.train_rows)
    nums = check.numbers(check.reference_as_run(other, n),
                         check.reference_as_run(sound, n))
    ok, lines = check.verdict(nums, limits)
    assert not ok, lines
    same = check.numbers(check.reference_as_run(sound, n),
                         check.reference_as_run(sound, n))
    assert check.verdict(same, limits)[0]
