"""chip_smoke.py, checked where there is no chip.

(a) the no-fallback property: without a TPU backend the script exits
non-zero, names the reason and prints no result; (b) its data generator,
conf builder and stage runners complete at a tiny size with the Pallas
kernels in interpret mode — so the command is debugged on the CPU before
chip time is spent on it; (c) the last stdout line is the two-key verdict
the chip check reads; (d) the premise of stage 1b's bf16 reference.
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu", **extra)
    # one device, like the chip: the app meshes every visible device
    env.pop("XLA_FLAGS", None)
    return env


def test_refuses_to_run_without_a_tpu():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env=_env(), cwd=REPO)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr, r.stderr
    assert "{" not in r.stdout, r.stdout  # no JSON result of any kind


_TINY = """
import json, sys, tempfile
import chip_smoke as cs
size = cs.Size(minibatch=256, train_parts=2, batches_per_part=2, val_parts=2,
               big_buckets=1 << 21, small_buckets=1 << 17,
               v_buckets=1 << 16, kernel="pallas")
with tempfile.TemporaryDirectory() as td:
    out = cs.run_stages(["1a", "1b", "1c", "2"], size, td)
print("RESULT " + json.dumps(out))
"""


def test_stages_complete_at_tiny_size_in_interpret_mode(tmp_path):
    """Every stage through the real entry path on one CPU device:
    2 parts x 2 x 256 rows, kernel=pallas -> interpret mode. The table
    sizes keep the production kernel sets: 2^21 buckets still compact
    (tcoo), 2^17 stay dense (coo)."""
    cache = tmp_path / "cache"
    r = subprocess.run([sys.executable, "-c", _TINY], capture_output=True,
                       text=True, timeout=600, cwd=REPO,
                       env=_env(JAX_COMPILATION_CACHE_DIR=str(cache)))
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert [out[s]["kind"] for s in ("1a", "1c", "2")] == [
        "tcoo", "coo", "fm"]
    assert out["1a"]["steps"] == 8 and out["1a"]["examples"] == 2048
    assert out["1a"]["pass2_compiles"] == 0
    assert out["1a"]["compiles"] > 0 and out["1a"]["compile_s"] > 0
    assert out["1b"]["f32_vs_xla"] <= 1e-4
    # the start-up line names the backend, the path and why
    assert "backend=cpu" in r.stdout and "interpret mode" in r.stdout
    assert "native=loaded" in r.stdout
    # a cache placed from outside is used, and the checkout's is not set
    assert cache.is_dir()


def test_last_stdout_line_is_the_two_key_verdict(monkeypatch, capfd):
    """What the chip check reads: the last stdout line is exactly
    {"ok", "device": {"platform", "kind", "count"}}; the wider summary is
    the line before it; a failed phase says ok=false and still raises."""
    import chip_smoke as cs

    monkeypatch.setattr(cs, "require_tpu", cs.device_info)
    monkeypatch.setattr(cs, "run_stages",
                        lambda stages, size, scratch: {"1c": {"kind": "coo"}})
    assert cs.main(["--stages", "1c"]) == 0
    lines = capfd.readouterr().out.splitlines()
    verdict = json.loads(lines[-1])
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert isinstance(verdict["device"]["count"], int)
    assert lines[-2].startswith(cs.SUMMARY_TAG)
    summary = json.loads(lines[-2][len(cs.SUMMARY_TAG):])
    assert summary["stages"] == {"1c": {"kind": "coo"}}
    assert summary["device"] == verdict["device"]
    assert list(summary)[-1] == "claim" and summary["claim"] is None

    def fail(stages, size, scratch):
        raise cs.SmokeFailure("1c: staged batch kinds ['xla']")

    monkeypatch.setattr(cs, "run_stages", fail)
    with pytest.raises(cs.SmokeFailure):
        cs.main(["--stages", "1c"])
    lines = capfd.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": False,
                                     "device": verdict["device"]}
    assert not any(x.startswith(cs.SUMMARY_TAG) for x in lines)


def test_bf16_kernels_round_only_the_table_values():
    """Stage 1b takes XLA on bf16-rounded weights as the exact reference
    of the bf16 kernels: one-hot matmuls select exactly, so the only
    rounding is w -> bfloat16 at the fetch (interpret mode can show that;
    through the apps it resolves kernel_dtype=bf16 to f32)."""
    from wormhole_tpu.ops import coo_kernels as ck

    rng = np.random.default_rng(0)
    nb, rows, nnz = 2 * ck.TILE, 128, 8
    w = rng.standard_normal(nb).astype(np.float32)
    wr = np.asarray(jnp.asarray(w).astype(jnp.bfloat16).astype(jnp.float32))
    idx = rng.integers(0, nb, rows * nnz)
    seg = np.repeat(np.arange(rows, dtype=np.int32), nnz)
    val = np.ones(rows * nnz, np.float32)

    tc = ck.pack_tile_coo(idx, seg, val, nb, ck.TILE)
    wc = np.asarray(ck.tile_gather(
        jnp.asarray(w).reshape(-1, ck.LANES), jnp.asarray(tc.uniq),
        jnp.asarray(tc.tmap_u), dtype=jnp.bfloat16))
    live = tc.uniq < nb
    np.testing.assert_array_equal(wc[live], wr[tc.uniq[live]])

    p = ck.pack_sorted_coo(idx, seg, val, nb)
    xw = np.asarray(ck.coo_spmv(
        jnp.asarray(w), *(jnp.asarray(a) for a in
                          (p.idx, p.seg, p.val, p.tmap, p.first)),
        rows, dtype=jnp.bfloat16))
    ref = np.zeros(rows, np.float32)
    np.add.at(ref, seg, wr[idx])
    np.testing.assert_allclose(xw, ref, atol=1e-5)
    # the compact step's pull (PR 32): the same kernel over the compact
    # domain, whose values the fetch has rounded already; with binary
    # features the product the kernel rounds before the row sum is that
    # value, so nothing rounds twice
    c = tc.coo
    xwc = np.asarray(ck.coo_spmv(
        jnp.asarray(wc), *(jnp.asarray(a) for a in
                           (c.idx, c.seg, c.val, c.tmap, c.first)),
        rows, dtype=jnp.bfloat16))
    np.testing.assert_allclose(xwc, ref, atol=1e-5)
