"""Device mesh: the TPU replacement for Wormhole's worker/server topology.

The reference launches `-n` worker and `-s` server processes (tracker,
reference doc/common/build.rst:57-71). Here the same two launch dimensions
become the two axes of a `jax.sharding.Mesh`:

- axis "data"  — data parallelism: minibatches are split across it
  (the workers);
- axis "model" — parameter sharding: hashed tables are range-sharded
  across it (the servers' key shards, localizer.h byte-reversal spreading
  becomes contiguous range sharding of the hashed bucket space).

Both axes ride ICI within a slice; XLA inserts the collectives (the psum of
gradients plays rabit::Allreduce, the cross-axis gather plays ZPull).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from wormhole_tpu.obs.metrics import REGISTRY

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(
    num_data: Optional[int] = None,
    num_model: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a (data x model) mesh. Defaults to all devices on the data
    axis — the reference's common shape of many workers and fewer servers
    maps to data-major ordering so neighboring workers share ICI links."""
    devs = list(devices if devices is not None else jax.devices())
    if num_data is None:
        num_data = len(devs) // num_model
    need = num_data * num_model
    assert need <= len(devs), (
        f"mesh {num_data}x{num_model} needs {need} devices, have {len(devs)}"
    )
    assert num_data >= 1 and num_model >= 1, (
        f"mesh {num_data}x{num_model} has an empty axis "
        f"({len(devs)} devices can't fill {num_model} model shards)"
    )
    devs = devs[:need]
    arr = np.array(devs).reshape(num_data, num_model)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def local_mesh(learner: str, model_shards: int) -> Mesh:
    """The in-process mesh of an app's conf: `model_shards` splits the
    state tables over the mesh "model" axis (the hot plane's HBM
    residency), the remaining devices take the data axis; cross-PROCESS
    sharding stays the ps server group's job (ps_server.py). A conf
    that asks for more shards than there are devices is clamped, with a
    printed line and the counter `<learner>.mesh.clamped_shards` raised
    by the shards that went missing: a conf written for four chips
    trains one shard on a one-chip machine, and says so."""
    shards = max(int(model_shards), 1)
    ndev = len(jax.devices())
    if shards > ndev:
        print(f"[{learner}] model_shards={shards} > {ndev} devices; "
              f"clamping to {ndev}", flush=True)
        REGISTRY.counter(f"{learner}.mesh.clamped_shards").inc(
            shards - ndev)
        shards = ndev
    return make_mesh(num_model=shards)


def table_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Parameter tables: bucket dimension sharded over the model axis
    (the PS key-shard layout); trailing dims (embedding k) replicated."""
    return NamedSharding(mesh, P(MODEL_AXIS, *([None] * (ndim - 1))))


def batch_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Minibatch arrays: leading dimension split over the data axis."""
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def single_device_mesh() -> Mesh:
    """1x1 mesh on the first device — single-chip paths."""
    return make_mesh(1, 1, devices=jax.devices()[:1])


def describe_placement(mesh: Mesh, learner: str, pallas: bool,
                       why_not: str = "") -> str:
    """The one start-up line a learner states at construction: the
    backend and device kind it runs on, its mesh, the kernel path it
    chose, and the reason whenever that is not the compiled Pallas path
    — so a run that quietly landed on CPU, in interpret mode or on the
    XLA formulation says so (the solver prints it beside its `[loader]`
    line)."""
    dev = mesh.devices.flat[0]
    shape = "x".join(str(mesh.shape[a]) for a in (DATA_AXIS, MODEL_AXIS))
    if pallas:
        # off-TPU the kernels only run interpreted, and only on request
        why = "" if dev.platform == "tpu" else "interpret mode, by request"
    else:
        why = why_not or f"backend is {dev.platform}, not tpu"
    line = (f"[{learner}] backend={dev.platform} "
            f"device_kind={dev.device_kind!r} devices={mesh.devices.size} "
            f"mesh={shape} (data x model) "
            f"path={'pallas' if pallas else 'xla'}")
    return f"{line} ({why})" if why else line
