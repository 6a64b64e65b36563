"""`correct` compares the tables the reference declares (PR 30): a learner
with vector rows, a second id space, a table that does not start at zero
and a count table becomes a cell by files alone, and the comparison sees
all six of its tables.

The fixture (tests/benchmark/fixtures): a configuration of
`wormhole_tpu.apps.difacto` at tiny sizes, its plain numpy reference
`fm_ftrl_adagrad.py` with the declarations, and what the harness asks of
that learner (`difacto_learner.py`). A test adds them to a copy of the
benchmark and rehearses the new cell; broken runs and the control must
come out not correct. Nothing here is a speed.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(REPO, "tests", "benchmark", "fixtures")
sys.path.insert(0, REPO)
sys.path.insert(0, FIXTURES)

from benchmark import check, gen  # noqa: E402
from benchmark.reference import linear_ftrl  # noqa: E402
import fm_ftrl_adagrad as fm  # noqa: E402

CELL = "fm.replay-small"
HYPER = {"lr_eta": 0.1, "lr_beta": 1.0, "lambda_l1": 1.0, "lambda_l2": 0.0,
         "dim": 4, "threshold": 2, "lambda_V": 0.01, "V_lr_eta": 0.01,
         "V_lr_beta": 1.0, "V_init_scale": 0.01}
SIZES = {"bucket": 1 << 17, "vrow": 1 << 12}


def _env(tmp):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"))
    env.pop("XLA_FLAGS", None)  # one device, like the chip
    return env


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy of the benchmark with the fixture added as new files and one
    entry each; returns (root, the bytes of every file that was there)."""
    root = tmp_path_factory.mktemp("vector_rows")
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    for f, to in (("difacto-fixture.json", "configs/difacto-fixture.json"),
                  ("criteo-kaggle.json", "keys/criteo-kaggle.json"),
                  ("fm_ftrl_adagrad.py", "reference/fm_ftrl_adagrad.py"),
                  ("difacto_learner.py", "learners/difacto.py")):
        assert not (root / "benchmark" / to).exists()
        shutil.copy(os.path.join(FIXTURES, f), root / "benchmark" / to)
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "replay.json")) as fh:
        mix = json.load(fh)
    mix.update(name="replay-small", train_parts=2, batches_per_part=3)
    (root / "benchmark/traffic/replay-small.json").write_text(
        json.dumps(mix))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append({
        "name": "difacto-fixture", "source": "criteo.conf",
        "file": "benchmark/configs/difacto-fixture.json",
        "reduced": ["train_rows"], "why": "vector rows, a count table"})
    bench["workloads"].append({
        "name": CELL, "config": "difacto-fixture",
        "traffic": "replay-small", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before


# ------------------------------------------------------------ whole runs
def test_a_vector_row_learner_is_a_cell_by_files_and_one_entry_each(copy):
    """Six tables in two id spaces, V read back before the first step:
    the new cell rehearses to `correct` true (the XLA step on the CPU)
    and no file that was there is edited."""
    root, before = copy
    r = subprocess.run(
        [sys.executable, "benchmark/rehearse.py", "--workload", CELL,
         "--seed", "2147483801", "--seconds", "1"], cwd=root, env=_env(root),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = json.loads(r.stdout.splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    lines = [ln for ln in r.stdout.splitlines() if "correct:" in ln]
    for name in ("loss_gap", "grad_norm_gap", "delta_norm_gap",
                 "state_off_share", "served_loss_gap", "served_delta_gap",
                 "served_off_share"):
        (ln,) = [x for x in lines if f" {name} = " in x]
        assert "(limit" in ln and ln.rstrip().endswith("ok"), ln
    (ln,) = [x for x in lines if "reference: 3 steps" in x]
    assert "touched buckets of 131072" in ln
    assert "touched vrows of 16384" in ln
    assert any("staged batch kinds ['xla_staged']  (expected 'xla_staged')"
               in x for x in lines)
    assert any("compilations inside the window = 0" in x for x in lines)
    # each number beside its limit: the result line's last key, and the
    # last lines of standard error
    assert list(out)[-1] == "compared"
    assert set(out["compared"]) >= {"delta_norm_gap", "served_off_share",
                                    "window_compiles", "val_logloss"}
    err = r.stderr.rstrip().splitlines()[-len(lines):]
    assert err == lines
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"


_BROKEN = """
import json
import sys

from benchmark import check, run
from wormhole_tpu.apps import difacto as app

fault, sound_steps = sys.argv[1], int(sys.argv[2])
make = app.make_learner


def broken(cfg, env):
    if fault == "admits_nothing":
        cfg.threshold = 2 ** 30     # the count table fills, nothing passes
    learner = make(cfg, env)
    real, calls = learner.train_batch, [0]

    def frozen(b):
        import jax.numpy as jnp
        calls[0] += 1
        if calls[0] <= sound_steps:
            return real(b)
        keep = {k: jnp.array(v) for k, v in learner.vstore.state.items()}
        out = real(b)
        learner.vstore.state = keep    # V, nV handed back unchanged
        return out

    if fault == "frozen_rows":
        learner.train_batch = frozen
    return learner


app.make_learner = broken              # the program underneath, not run.py
numbers = check.numbers


def both(run_, ref):
    # beside the comparison as it is, the one PR 29's fixed z, n, w made
    def three(side):
        return dict(side, start={}, final={k: side["final"][k]
                                           for k in ("z", "n", "w")})
    print("three-leaf: " + json.dumps(numbers(three(run_), three(ref))))
    return numbers(run_, ref)


check.numbers = both
bench = run.load_json(run.ROOT, "BENCHMARK.json")
out = run.run_cell(bench, "fm.replay-small", 11, 1.0, False, rehearsal=True)
print(json.dumps(out))
"""


def _broken_run(copy, fault, sound_steps):
    root, _ = copy
    r = subprocess.run(
        [sys.executable, "-c", _BROKEN, fault, str(sound_steps)], cwd=root,
        env=_env(root), capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = json.loads(r.stdout.splitlines()[-1])
    (ln,) = [x for x in r.stdout.splitlines() if x.startswith("three-leaf: ")]
    three = json.loads(ln[len("three-leaf: "):])
    verdicts = {x.split("correct: ")[1].split(" = ")[0]:
                x.rstrip().rsplit(None, 1)[-1]
                for x in r.stdout.splitlines()
                if "correct: " in x and "(limit " in x and " = " in x}
    return out, three, verdicts


@pytest.mark.parametrize("sound_steps", [0, 2])
def test_vector_rows_handed_back_unchanged_fail_delta_norm_gap(
        copy, sound_steps):
    """V, nV handed back unchanged while w, z, n, cnt train: from the
    first step on, or from the third. From the third, z, n, w and every
    step's loss are exactly what they should be (a step's margin sees V
    as the steps before left it), so the comparison over the old fixed
    z, n, w passes all its limits, and only the vector rows' own change,
    one step short, gives the fault away."""
    out, three, verdicts = _broken_run(copy, "frozen_rows", sound_steps)
    assert out["correct"] is False
    assert verdicts["delta_norm_gap"] == "OVER"
    assert verdicts["served_delta_gap"] == "OVER"
    if sound_steps:
        with open(os.path.join(FIXTURES, "difacto-fixture.json")) as fh:
            limits = json.load(fh)["correct"]["limits"]
        assert all(three[k] <= v for k, v in limits.items()), three
        assert verdicts["loss_gap"] == verdicts["grad_norm_gap"] == "ok"
    else:
        assert three["grad_norm_gap"] < 1e-5   # step 1 saw the right V


def test_a_count_table_that_admits_nothing_comes_out_not_correct(copy):
    """Admission never granted (the count table itself fills as it
    should): V and nV never move, which `delta_norm_gap` reads as 1."""
    out, _, verdicts = _broken_run(copy, "admits_nothing", 0)
    assert out["correct"] is False
    assert verdicts["delta_norm_gap"] == "OVER"
    assert verdicts["state_off_share"] == "OVER"


def test_the_control_fails_the_fixtures_limits(copy):
    """All six tables kept in bfloat16 between steps, V drawn by the
    reference from the seed: every seed has to come out not correct."""
    root, _ = copy
    r = subprocess.run(
        [sys.executable, "benchmark/control.py", "--config",
         "difacto-fixture", "--seeds", "21,22,23", "--rehearsal", "1"],
        cwd=root, env=_env(root), capture_output=True, text=True,
        timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert r.stdout.count("correct=False") == 3
    for name in ("state_off_share", "served_off_share"):
        lines = [x for x in r.stdout.splitlines() if f" {name} = " in x]
        assert len(lines) == 3 and all("OVER" in x for x in lines), name


# ----------------------------------------------------- reading the tables
def test_the_1d_gather_lowers_to_the_parents_program():
    """PR 29's `jnp.take(table, ids)` and the gather by row along axis 0
    are one program for a 1-D table: no accepted cell compiles anything
    new."""
    import jax
    import jax.numpy as jnp

    parent = jax.jit(lambda table, ids: jnp.take(table, ids))
    table = jax.ShapeDtypeStruct((1 << 21,), jnp.float32)
    ids = jax.ShapeDtypeStruct((3 * 256 * 39,), jnp.int32)
    assert (check._take().lower(table, ids).as_text()
            == parent.lower(table, ids).as_text())


def test_tables_are_read_by_row_in_each_leafs_own_id_space():
    tables = {"w": np.arange(10, dtype=np.float32),
              "V": np.arange(12, dtype=np.float32).reshape(4, 3),
              "other": np.zeros(3)}
    decl = {"w": {"space": "bucket"}, "V": {"space": "vrow"}}
    ids = {"bucket": np.array([1, 7, 9]), "vrow": np.array([0, 3])}
    got = check.read_tables(tables, ids, capacity=8, decl=decl)
    assert set(got) == {"w", "V"}
    assert got["w"].tolist() == [1.0, 7.0, 9.0]
    assert got["V"].tolist() == [[0.0, 1.0, 2.0], [9.0, 10.0, 11.0]]
    # with nothing declared: every table of the mapping, on one array
    got = check.read_tables({"a": tables["w"], "V": tables["V"]},
                            np.array([2, 3]), capacity=4)
    assert got["a"].tolist() == [2.0, 3.0]
    assert got["V"].tolist() == [[6.0, 7.0, 8.0], [9.0, 10.0, 11.0]]


def test_a_start_is_read_in_chunks_on_every_row_the_parts_can_touch(
        monkeypatch):
    """A leaf that does not start at zero is read before the first step,
    a fixed number of rows a gather; leaves that start at zero are not."""
    monkeypatch.setattr(check, "START_CHUNK", 64)
    batches = _fm_batches(2)
    V = np.arange(SIZES["vrow"] * 4, dtype=np.float32).reshape(-1, 4)
    asked = []

    class Dataset:
        train_parts, batches_per_part, minibatch = 1, 2, 64

        def batch(self, p, j):
            return batches[j]

    class Learner:
        def tables(self):
            asked.append(self)
            return {"V": V}             # no other table is touched

    learner = Learner()
    first = check.FirstSteps(Dataset(), SIZES, 3, fm)
    first.before_step(learner)
    ids = check.union_ids(fm, SIZES, [k for k, _ in batches])["vrow"]
    assert len(ids) > 3 * 64            # several gathers
    assert set(first._start_rows) == {"V"}
    assert np.array_equal(first._start_rows["V"], V[ids])
    first.before_step(learner)          # only once
    assert asked == [learner]
    # where every leaf starts at zero nothing is asked of the learner;
    # one id space may be sized by a bare number, and the reference be
    # named by one of its functions (tests/test_linear_mesh_deploy.py)
    first = check.FirstSteps(Dataset(), 1 << 20, 3, linear_ftrl.bucket_ids)
    assert first.ref_mod is linear_ftrl and first.sizes == {
        "bucket": 1 << 20}
    first.before_step(learner)
    assert asked == [learner] and first.start() is None


def test_the_learners_own_method_is_asked_before_the_adapter():
    """`benchmark/learners/<module>.py`, by the module that defines the
    learner's class, answers what the learner does not."""
    class Store:
        state = {"w": 1}

    class Plain:
        __module__ = "wormhole_tpu.models.linear"
        store = Store()

    class Answers(Plain):
        __module__ = "wormhole_tpu.models.linear"

        def tables(self):
            return {"w": 2}

        def batch_kind(self, b):
            return "mine"

    staged = ("staged", "tcoo", (0, np.ones(2, np.float32), 0), 2, None, 1)
    assert check.tables(Plain()) == {"w": 1}
    assert check.batch_kind(Plain(), staged) == "tcoo"
    assert check.batch_label(Plain(), staged).tolist() == [1.0, 1.0]
    assert check.tables(Answers()) == {"w": 2}
    assert check.batch_kind(Answers(), staged) == "mine"
    # the adapter still answers what the learner does not
    assert check.batch_label(Answers(), staged).tolist() == [1.0, 1.0]
    # a name older callers outside the benchmark's paths still use
    assert check.LEAVES == ("z", "n", "w")
    with pytest.raises(AttributeError):
        check.no_such_name


# --------------------------------------------------------- compared numbers
def test_a_leaf_that_does_not_start_at_zero_is_compared_by_its_change():
    ids = {"bucket": np.arange(2), "vrow": np.arange(2)}
    start = {"V": np.full((2, 3), 10.0, np.float32)}
    final = {"z": np.array([0.3, 0.4], np.float32),
             "V": start["V"] + np.float32(0.5)}

    def side(final_):
        return {"objv": [1.0], "nex": [2.0], "ids1": ids, "ids": ids,
                "grad1": final["z"], "final": final_, "start": start}

    ref = side(final)
    assert set(check.numbers(ref, ref).values()) == {0.0}
    # V handed back unchanged: against its value the gap of the norms
    # would be 5 %, against its change it is the whole of it (its change
    # is the larger of the two leaves', so the median leaf is no floor)
    n = check.numbers(side(dict(final, V=start["V"])), ref)
    assert n["delta_norm_gap"] == pytest.approx(1.0)
    assert n["state_off_share"] == pytest.approx(1.0)
    # a vector row counts element by element
    half = final["V"].copy()
    half[:, 0] = 0.0
    assert check.numbers(side(dict(final, V=half)), ref)[
        "state_off_share"] == pytest.approx(1 / 3)


def test_what_a_kernel_count_may_read():
    from benchmark import run

    class First:
        reference = {"touched": [
            {"bucket": np.arange(6), "vrow": np.arange(2)},
            {"bucket": np.arange(8), "vrow": np.arange(4)}]}

    conf = {"minibatch": 256, "nnz_per_row": 39, "num_buckets": 1 << 17}
    b = run.batch_shapes(conf, {"hyper": HYPER}, fm, First())
    assert b == {"rows": 256, "nnz": 256 * 39, "uniq": 7.0,
                 "num_buckets": 1 << 17, "hyper": HYPER,
                 "distinct": {"bucket": 7.0, "vrow": 3.0}}
    First.reference = None           # the reference did not run
    b = run.batch_shapes(conf, {"hyper": HYPER}, linear_ftrl, First())
    assert b["uniq"] == 0.0 and b["distinct"] == {"bucket": 0.0}


# ------------------------------------------------- the fixture's reference
def _fm_batches(n):
    keys = gen.KeyModel("criteo-terabyte")
    rows = [gen.Rows(keys, 37, 0, p, 64) for p in range(n)]
    return [(r.keys(), r.label) for r in rows]


def test_fixture_reference_declares_two_id_spaces_and_six_tables():
    assert fm.SPACES == {"bucket": "num_buckets", "vrow": "v_buckets"}
    assert list(fm.TABLES) == ["z", "n", "w", "cnt", "V", "nV"]
    assert [k for k, d in fm.TABLES.items() if not d["zero_start"]] == ["V"]
    assert {d["space"] for d in fm.TABLES.values()} == set(fm.SPACES)
    assert fm.GRADIENT == "z"
    keys = _fm_batches(1)[0][0]
    ids = fm.space_ids(keys, SIZES)
    assert ids["bucket"].shape == ids["vrow"].shape == keys.shape
    assert np.array_equal(ids["bucket"], linear_ftrl.bucket_ids(
        keys, SIZES["bucket"]))
    assert np.array_equal(ids["vrow"], ids["bucket"] % SIZES["vrow"])
    # the reference imports nothing of the program
    src = open(os.path.join(FIXTURES, "fm_ftrl_adagrad.py")).read()
    assert "wormhole_tpu" not in "\n".join(
        ln for ln in src.splitlines()
        if ln.lstrip().startswith(("import ", "from ")))


def test_fixture_reference_from_a_given_state_equals_its_continuation():
    batches = _fm_batches(2)
    prec = {"tables": "f32"}
    ids = check.union_ids(fm, SIZES, [k for k, _ in batches])
    start = {"ids": ids, "tables": fm.draw_start(ids, SIZES, HYPER, 5)}
    both = fm.run_steps(batches, SIZES, HYPER, prec, start=start)
    assert both["states"][1]["V"].shape == (len(ids["vrow"]), HYPER["dim"])
    # keys seen often enough are admitted, and their rows have moved
    moved = np.any(both["states"][1]["V"] != start["tables"]["V"], axis=1)
    assert 0 < moved.sum() < len(moved)
    assert both["states"][1]["cnt"].sum() == 2 * 64 * gen.NNZ
    ids2 = check.union_ids(fm, SIZES, [batches[1][0]])
    pre = {k: v[np.searchsorted(ids[fm.TABLES[k]["space"]],
                                ids2[fm.TABLES[k]["space"]])]
           for k, v in both["states"][0].items()}
    one = fm.run_steps(batches[1:], SIZES, HYPER, prec,
                       start={"ids": ids2, "tables": pre})
    assert one["objv"][0] == both["objv"][1]
    for k, d in fm.TABLES.items():
        pos = np.searchsorted(ids[d["space"]], ids2[d["space"]])
        assert np.array_equal(one["states"][0][k],
                              both["states"][1][k][pos]), k
    with pytest.raises(ValueError):
        fm.run_steps(batches, SIZES, HYPER, prec)         # V needs a start
    with pytest.raises(ValueError):
        fm.run_steps(batches[1:], SIZES, HYPER, prec, start=start)


def test_fixture_reference_draws_a_row_the_same_in_any_set_of_ids():
    a = fm.draw_start({"vrow": np.array([3, 9, 4000])}, SIZES, HYPER, 7)["V"]
    b = fm.draw_start({"vrow": np.array([9])}, SIZES, HYPER, 7)["V"]
    c = fm.draw_start({"vrow": np.array([9])}, SIZES, HYPER, 8)["V"]
    assert a.shape == (3, HYPER["dim"]) and a.dtype == np.float32
    assert np.array_equal(a[1], b[0]) and not np.array_equal(b, c)
    big = fm.draw_start({"vrow": np.arange(4096)}, SIZES, HYPER, 7)["V"]
    assert abs(big.mean()) < 1e-3
    assert big.std() == pytest.approx(HYPER["V_init_scale"], rel=0.05)


def test_fixture_reference_bfloat16_rounds_to_nearest_even():
    x = np.array([1.0, 1.0 + 2.0 ** -8, 1.0 + 3 * 2.0 ** -8, -3.1415926],
                 np.float32)
    got = fm._rounded(x, "bf16")
    assert got.tolist() == [1.0, 1.0, 1.0 + 2.0 ** -6, -3.140625]
    assert fm._rounded(x, "f32") is x


# ------------------------------------------------------------- the entries
def test_the_list_less_kernel_metrics_name_the_one_chip_cells():
    """PERF.md section 7 (a): on a trace of four device planes the two
    read 3.02 ms and 2.58 % where the per-chip pair reads 12.09 ms and
    0.161 %; they list the one-chip cells now. By rule since PR 46: the
    one-chip cells of the minibatch driver, whose step runs the kernels
    the two read; a cell of another driver has no such step and is under
    neither."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    minibatch, drivers = "benchmark.drivers.minibatch", {}
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as fh:
            drivers[c["name"]] = json.load(fh).get("driver", minibatch)
    # (the stream cell under the twin's name since PR 43)
    stream = "criteo1tb.crb-stream"
    one_chip = [w["name"] for w in bench["workloads"]
                if w["chips"] == 1 and drivers[w["config"]] == minibatch
                and w["name"] != stream]
    assert {"criteo1tb.replay", "difacto1tb.replay"} <= set(one_chip)
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in ("kernel_ms_per_step", "step_kernels_roofline"):
        assert by[name]["workloads"] == one_chip, name
        assert by[name + ".stream"]["workloads"] == [stream], name
    (four,) = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    for name in ("shard_kernel_ms_per_step", "shard_kernels_roofline"):
        assert by[name]["workloads"] == [four]


@pytest.mark.parametrize("config", ["linear-ftrl-criteo1tb",
                                    "linear-ftrl-criteo1tb-2p30"])
def test_delta_norm_gap_stands_between_a_flip_and_an_unchanged_state(config):
    """PERF.md section 2: a sound run's hot-bucket flip read 4.4e-3, a
    state handed back unchanged reads 1.0."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           config + ".json")) as fh:
        limits = json.load(fh)["correct"]["limits"]
    assert 2 * 4.447e-3 < limits["delta_norm_gap"] == 0.01 < 1.0 / 10
