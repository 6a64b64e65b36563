"""Test harness: run on a virtual 8-device CPU mesh.

Multi-chip behavior is tested without real TPU hardware the same way the
reference tests multi-node without a cluster (dmlc_local.py spawning all
roles on localhost, reference learn/test/data_parallel_test.cc:8): here the
"cluster" is 8 virtual XLA CPU devices in one process.
"""

import os
import sys

# Must happen before any jax backend initialization: tests always run on
# the virtual CPU mesh, whatever the machine holds. The env var reaches
# the subprocesses tests spawn; the config update below covers a jax that
# was imported (and read its environment) before this file.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from wormhole_tpu.data.rowblock import RowBlock  # noqa: E402


AGARICUS_TRAIN = "/root/reference/learn/data/agaricus.txt.train"
AGARICUS_TEST = "/root/reference/learn/data/agaricus.txt.test"


def synth_libsvm_text(n_rows=512, n_feat=1000, nnz_per_row=8, seed=0,
                      labels01=True, w_seed=1234):
    """Synthetic linearly-separable-ish sparse binary data in libsvm text.
    The ground-truth weights come from w_seed so files with different data
    seeds are drawn from the SAME model (train/val consistency)."""
    rng = np.random.default_rng(seed)
    w = np.random.default_rng(w_seed).normal(size=n_feat)
    lines = []
    for _ in range(n_rows):
        idx = rng.choice(n_feat, size=nnz_per_row, replace=False)
        val = rng.random(nnz_per_row).astype(np.float32) + 0.5
        margin = float((w[idx] * val).sum())
        y = 1 if margin + rng.normal(scale=0.3) > 0 else 0
        if not labels01:
            y = 1 if y else -1
        lines.append(
            f"{y} " + " ".join(f"{i}:{v:.4f}" for i, v in zip(idx, val))
        )
    return "\n".join(lines) + "\n"


def profiled_spans(logdir, prefixes=("data.", "loader.", "solver.", "step.",
                                    "test.")):
    """The program's spans in the newest JAX profile under `logdir`, as
    dicts(name, start, end, thread, args) sorted by start; `thread`
    numbers the host line (one per OS thread) the span lies on."""
    import glob

    from jax.profiler import ProfileData

    pbs = sorted(glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                           recursive=True))
    assert pbs, f"no profile under {logdir}"
    out, thread = [], 0
    for plane in ProfileData.from_file(pbs[-1]).planes:
        for line in plane.lines:
            thread += 1
            for e in line.events:
                if e.name.startswith(prefixes):
                    out.append(dict(name=e.name, start=e.start_ns,
                                    end=e.start_ns + e.duration_ns,
                                    thread=thread, args=dict(e.stats)))
    return sorted(out, key=lambda s: (s["start"], -s["end"]))


@pytest.fixture
def synth_libsvm_file(tmp_path):
    p = tmp_path / "synth.libsvm"
    p.write_text(synth_libsvm_text())
    return str(p)


@pytest.fixture
def agaricus():
    """The reference's mushroom smoke dataset, if the reference is mounted."""
    if not os.path.exists(AGARICUS_TRAIN):
        pytest.skip("reference agaricus data not available")
    return AGARICUS_TRAIN, AGARICUS_TEST
