"""wormhole-tpu: a TPU-native distributed machine-learning framework.

Capabilities mirror DMLC Wormhole (reference: mstebelev/wormhole): sparse
linear models (SGD/AdaGrad/FTRL), the DiFacto factorization machine, k-means,
distributed L-BFGS/OWL-QN, and histogram GBDT — redesigned for TPU:

- model/optimizer state lives as named-sharded jax Arrays in HBM (the
  "parameter server" of ps-lite becomes a hashed, mesh-sharded table);
- gradient aggregation and parameter exchange are XLA collectives (psum /
  all-gather / reduce-scatter) over ICI/DCN under jit/shard_map, replacing
  rabit allreduce and zmq push/pull;
- sparse feature-matrix x weight products compile to XLA segment ops and
  Pallas kernels;
- the host side (data parsing, workload scheduling, minibatch streaming)
  keeps Wormhole's architecture: parsers, MinibatchIter, WorkloadPool,
  scheduler/worker harness — with the hot parsing path in native C++.

See SURVEY.md for the reference structural analysis this build follows.
"""

__version__ = "0.1.0"

import os as _os


def _place_compile_cache() -> None:
    """Persistent XLA compile cache for every entry point (apps,
    benchmark/run.py, chip_smoke.py, launcher children): a cold linear step costs ~1 min of
    compile, mostly the AUC sort (PERF.md §6, PR 21). The path is part of
    the cache key, so it is fixed — JAX_COMPILATION_CACHE_DIR when the
    environment places it (JAX reads that itself; nothing is set here),
    else <checkout>/.jax_cache."""
    if _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))


_place_compile_cache()


def _install_stackdump() -> None:
    """WORMHOLE_STACKDUMP=1: dump all-thread Python stacks to stderr on
    SIGUSR1 — the only way to see where a launcher-spawned role process
    is stuck on boxes without gdb/py-spy (used to diagnose the r3 PS
    bench stall)."""
    if _os.environ.get("WORMHOLE_STACKDUMP") != "1":
        return
    try:
        import faulthandler
        import signal

        faulthandler.register(signal.SIGUSR1, all_threads=True)
    except (ImportError, AttributeError, ValueError):
        pass  # non-main thread / platform without SIGUSR1


_install_stackdump()


def _arm_wormsan() -> bool:
    """WH_SAN=1: install the runtime concurrency sanitizer
    (tools/wormsan) before any submodule import creates a lock, so every
    ``threading.Lock``/``RLock`` in the process is wrapped.  Class
    instrumentation (the lockset race detector over wormlint's
    shared-state model) is deferred to after this package finishes
    importing — instrumenting imports the model's modules, which would
    re-enter a half-initialized wormhole_tpu."""
    if _os.environ.get("WH_SAN") != "1":
        return False
    try:
        from tools import wormsan
    except ImportError:
        import sys as _sys

        _sys.stderr.write("[wormsan] WH_SAN=1 but tools.wormsan is not "
                          "importable (run from the repo root)\n")
        return False
    wormsan.install(instrument=False)
    return True


_WORMSAN_ARMED = _arm_wormsan()

from wormhole_tpu.data.rowblock import RowBlock, DeviceBatch  # noqa: F401

if _WORMSAN_ARMED:
    from tools import wormsan as _wormsan

    _wormsan.instrument_classes()
