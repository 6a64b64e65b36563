"""KVStore: the parameter server, TPU-native.

ps-lite's server group (reference: OnlineServer + per-key Handle state,
learn/linear/async_sgd.h:200-226; key sharding across `-s` servers) becomes
a set of fixed-capacity hashed tables living as named-sharded jax Arrays in
HBM, bucket dimension sharded over the mesh "model" axis:

- ZPull (worker pulls weights for its minibatch's keys,
  async_sgd.h:277-287)  -> `jnp.take` of bucket rows inside the jitted
  step; XLA turns the cross-shard gather into ICI collectives.
- ZPush (worker pushes gradients, key-sharded scatter)  -> segment-sum of
  per-nonzero contributions into table layout + a sharding constraint, so
  XLA reduce-scatters gradients onto the owning model shard before the
  update runs shard-local.
- server Handle (FTRL/AdaGrad per-key update logic)  -> a functional
  update step over the state pytree, written by each learner.
- message filters (fixed-point/compressing transfer,
  async_sgd.h:290-301)  -> dtype quantization of the pushed gradient.

State is functional: learners thread `store.state` (a dict of arrays)
through jitted steps and assign back. Save/load uses one npz per model
shard with the reference's part naming (see utils/checkpoint.py).
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from wormhole_tpu.obs import metrics as _obs
from wormhole_tpu.parallel.mesh import table_sharding

_GATHER_S = _obs.REGISTRY.histogram("kv.gather_s")
_SCATTER_S = _obs.REGISTRY.histogram("kv.scatter_s")
_GATHER_ROWS = _obs.REGISTRY.counter("kv.gather_rows")
_SCATTER_ROWS = _obs.REGISTRY.counter("kv.scatter_rows")
_JIT_MISSES = _obs.REGISTRY.counter("kv.jit_cache_misses")

LANES = 128  # a lane line: the minor dimension of a lane-packed table


@dataclasses.dataclass
class TableSpec:
    """One named state table: shape = (num_buckets, *tail).

    `wire_cap` floors the wire encoding of this table's PUSH deltas:
    "bf16" means WH_WIRE=int8/int4 still ships this table at bf16.
    Second-moment / count accumulators (FTRL n, difacto n/cnt/nV) need
    it: their per-sync deltas are nonnegative with huge dynamic range
    (a hot bucket's n grows ~minibatch per sync while a cold bucket's
    grows ~1), so an absmax group code quantizes the cold buckets at
    the hot neighbor's granularity — mis-scaling their per-coordinate
    learning rates, which error feedback cannot undo (EF repairs the
    accumulated STATE over rounds, not the optimizer trajectory already
    taken at the wrong rate). bf16's per-element relative precision
    (~0.4%) is safe at any magnitude. Sign-mixed gradient-like streams
    (z, V) keep the full int8/int4+EF treatment."""

    tail: tuple = ()
    dtype: object = jnp.float32
    init: Optional[Callable] = None  # (key, shape, dtype) -> array; 0 if None
    wire_cap: str = ""  # "" (no floor) or "bf16"
    # Lane-packed vector rows. 0: the array is (num_buckets, *tail). A
    # stride s (a divisor of 128, >= tail[0]) stores the table as
    # (num_buckets * s // 128, 128): row r lies at flat offset r * s,
    # 128 // s rows to a lane line, its lanes [tail[0], s) zero. On the
    # chip a (rows, 50) f32 array pads its minor dimension to 128 lanes
    # (2.56x the bytes); the packed form is what the compact FM step
    # gathers and scatters by line (ops/fused_update.py). Every
    # host-facing method below still speaks rows of `tail`.
    stride: int = 0


def pack_rows(rows: np.ndarray, stride: int) -> np.ndarray:
    """(n, dim) rows -> (n * stride // 128, 128) lane lines, the lanes
    [dim, stride) of every row zero."""
    return np.pad(rows, ((0, 0), (0, stride - rows.shape[1]))
                  ).reshape(-1, LANES)


def unpack_rows(lines, stride: int, dim: int):
    """The inverse view: (n, dim) rows of a lane-packed table."""
    return lines.reshape(-1, stride)[:, :dim]



class KVStore:
    """Hashed, mesh-sharded parameter/optimizer state tables."""

    def __init__(
        self,
        mesh: Mesh,
        num_buckets: int,
        specs: dict[str, TableSpec],
        seed: int = 0,
    ):
        self.mesh = mesh
        self.num_buckets = int(num_buckets)
        self.specs = dict(specs)
        nshards = mesh.shape.get("model", 1)
        assert self.num_buckets % max(nshards, 1) == 0, (
            f"num_buckets {num_buckets} must divide over {nshards} model shards"
        )
        key = jax.random.PRNGKey(seed)
        self.state: dict[str, jax.Array] = {}
        for name, spec in self.specs.items():
            shape = self.stored_shape(name)
            sh = table_sharding(mesh, ndim=len(shape))
            key, sub = jax.random.split(key)
            if spec.stride and spec.init is not None:
                # drawn in the stored shape (a (rows, dim) temporary would
                # pad to 128 lanes on the chip), the stride's spare lanes
                # zeroed: they stay zero under every update
                init, live = spec.init, (
                    np.arange(LANES) % spec.stride < spec.tail[0])
                arr = jax.jit(
                    lambda sub=sub, init=init, live=live: jnp.where(
                        live, init(sub, shape, spec.dtype), 0),
                    out_shardings=sh,
                )()
            elif spec.init is None:
                arr = jax.jit(
                    lambda: jnp.zeros(shape, spec.dtype), out_shardings=sh
                )()
            else:
                init = spec.init
                arr = jax.jit(
                    lambda sub=sub, init=init: init(sub, shape, spec.dtype),
                    out_shardings=sh,
                )()
            self.state[name] = arr
        # jitted gather/scatter caches, keyed by the _pad_pow2 padded
        # length (and table set / name). jax.jit caches per shape
        # internally, but an explicit per-size entry makes the compile
        # set countable: kv.jit_cache_misses stays flat once every
        # padded size in the touched-row distribution has been seen, so
        # the lab can show steady-state compilation is zero.
        self._gather_fns: dict[tuple, Callable] = {}
        self._multi_gather_fns: dict[tuple, Callable] = {}
        self._scatter_fns: dict[tuple, Callable] = {}

    def stored_shape(self, name: str) -> tuple:
        """The shape `state[name]` has: (num_buckets, *tail), or the
        lane-packed lines of a table with a stride (TableSpec.stride)."""
        spec = self.specs[name]
        if not spec.stride:
            return (self.num_buckets, *spec.tail)
        assert len(spec.tail) == 1 and LANES % spec.stride == 0 \
            and spec.tail[0] <= spec.stride, (name, spec.tail, spec.stride)
        assert self.num_buckets * spec.stride % LANES == 0
        return (self.num_buckets * spec.stride // LANES, LANES)

    def rows_view(self, name: str):
        """`state[name]` as (num_buckets, *tail) on the device: the array
        itself, or a lane-packed table unpacked into a new one (lane-
        padded on the chip: for reading rows back, not for a step)."""
        spec = self.specs[name]
        if not spec.stride:
            return self.state[name]
        return _unpack_jit(self.state[name], spec.stride, spec.tail[0])

    # -- helpers used inside learner-jitted steps ---------------------------
    def sharding(self, name: str):
        return table_sharding(self.mesh, ndim=len(self.stored_shape(name)))

    def constrain(self, name: str, arr):
        """Pin an intermediate (e.g. a dense gradient in table layout) to
        the table's sharding so XLA reduce-scatters it to the owning shard
        (the ZPush key-routing)."""
        return jax.lax.with_sharding_constraint(arr, self.sharding(name))

    def update(self, new_state: dict[str, jax.Array]) -> None:
        assert set(new_state) == set(self.state), "state keys changed"
        self.state = new_state

    # -- sparse host<->device row access (the PS data plane's unit) ---------
    # Row-index lengths vary per sync; padding to the next power of two
    # bounds XLA retraces to O(log max-touched) compiled shapes.
    @staticmethod
    def _pad_pow2(idx: np.ndarray, fill: int) -> tuple[np.ndarray, int]:
        n = int(idx.shape[0])
        m = 8
        while m < n:
            m <<= 1
        out = np.full(m, fill, dtype=np.int64)
        out[:n] = idx
        return out, n

    def gather_rows(self, name: str, idx: np.ndarray) -> np.ndarray:
        """Fetch rows `idx` of a table to host — a device gather plus an
        O(touched) transfer, never a full-table copy (the ZPush side of
        the sparse PS wire reads current values this way)."""
        spec = self.specs[name]
        if idx.size == 0:
            return np.empty((0, *spec.tail), np.float32)
        t0 = time.perf_counter()
        pad, n = self._pad_pow2(np.asarray(idx), 0)
        key = (pad.shape[0], spec.stride)
        fn = self._gather_fns.get(key)
        if fn is None:
            fn = jax.jit(partial(_take_rows, spec=spec))
            self._gather_fns[key] = fn
            _JIT_MISSES.inc()
        out = fn(self.state[name], jnp.asarray(pad))
        out = np.asarray(out[:n], dtype=np.float32)
        _GATHER_S.observe(time.perf_counter() - t0)
        _GATHER_ROWS.inc(n)
        return out

    def gather_rows_multi(self, names: list[str],
                          idx: np.ndarray) -> dict[str, np.ndarray]:
        """gather_rows for several same-height tables sharing one index
        set (FTRL's z and n always do): one index transfer and one
        jitted dispatch for the whole group instead of per-table
        round-trips — the sync-snapshot path's gather cost halves."""
        if idx.size == 0:
            return {k: np.empty((0, *self.specs[k].tail), np.float32)
                    for k in names}
        t0 = time.perf_counter()
        pad, n = self._pad_pow2(np.asarray(idx), 0)
        names_key = tuple(names)
        key = (names_key, pad.shape[0])
        fn = self._multi_gather_fns.get(key)
        if fn is None:
            specs = self.specs
            fn = jax.jit(lambda st, i: {
                k: _take_rows(st[k], i, specs[k]) for k in names_key})
            self._multi_gather_fns[key] = fn
            _JIT_MISSES.inc()
        outs = fn({k: self.state[k] for k in names}, jnp.asarray(pad))
        res = {k: np.asarray(v[:n], dtype=np.float32)
               for k, v in outs.items()}
        _GATHER_S.observe(time.perf_counter() - t0)
        _GATHER_ROWS.inc(n * len(names))
        return res

    def scatter_rows(self, name: str, idx: np.ndarray,
                     vals: np.ndarray) -> None:
        """Overwrite rows `idx` with `vals` in place on device (the
        sparse pull apply). Padding rows use an out-of-range index and
        mode='drop', so they never land."""
        if idx.size == 0:
            return
        t0 = time.perf_counter()
        spec = self.specs[name]
        pad, n = self._pad_pow2(np.asarray(idx), self.num_buckets)
        key = (name, pad.shape[0])
        fn = self._scatter_fns.get(key)
        if fn is None:
            sh = self.sharding(name)
            fn = jax.jit(
                lambda a, i, v: jax.lax.with_sharding_constraint(
                    _set_rows(a, i, v, spec.stride), sh),
                donate_argnums=0)
            self._scatter_fns[key] = fn
            _JIT_MISSES.inc()
        tail = spec.tail
        v = np.zeros((pad.shape[0], *tail), np.float32)
        v[:n] = vals
        self.state[name] = fn(self.state[name], jnp.asarray(pad),
                              jnp.asarray(v))
        _SCATTER_S.observe(time.perf_counter() - t0)
        _SCATTER_ROWS.inc(n)

    def zero_init_names(self) -> set[str]:
        """Tables created as zeros (spec.init is None) — the PS plane
        creates these server-side from shape alone, with no array on the
        startup wire (runtime/ps_server.py init_from_specs)."""
        return {k for k, s in self.specs.items() if s.init is None}

    def wire_cap_names(self) -> set[str]:
        """Tables whose push deltas must never drop below bf16 on the
        wire (see TableSpec.wire_cap) — read by SyncedStore's
        _quantize_deltas."""
        return {k for k, s in self.specs.items() if s.wire_cap}

    # -- host-side views ----------------------------------------------------
    def nnz(self, name: str = "w") -> int:
        """|w|_0 — the model-sparsity column of the progress row
        (reference linear progress.h:10-25 'new_w' tracking)."""
        return int(jnp.sum(self.state[name] != 0))

    def to_numpy(self) -> dict[str, np.ndarray]:
        """Every table on the host, as (num_buckets, *tail)."""
        out = {}
        for k, v in self.state.items():
            spec = self.specs[k]
            out[k] = (unpack_rows(np.asarray(v), spec.stride, spec.tail[0])
                      if spec.stride else np.asarray(v))
        return out

    def from_numpy(self, arrays: dict[str, np.ndarray]) -> None:
        for k, v in arrays.items():
            assert k in self.state, f"unknown table {k}"
            spec = self.specs[k]
            want = (self.num_buckets, *spec.tail)
            assert tuple(v.shape) == want, (
                f"table {k}: loaded shape {v.shape} != {want}"
            )
            if spec.stride:
                v = pack_rows(np.asarray(v), spec.stride)
            sh = self.sharding(k)
            self.state[k] = jax.device_put(jnp.asarray(v), sh)


def _take_rows(a, i, spec: TableSpec):
    """Rows i of a table as (len(i), *tail): of a lane-packed one the
    rows' lines, then each row's window of its line."""
    if not spec.stride:
        return a[i]
    rpl = LANES // spec.stride
    lines = a[i // rpl].reshape(i.shape[0], rpl, spec.stride)
    return lines[jnp.arange(i.shape[0]), i % rpl, :spec.tail[0]]


def _set_rows(a, i, v, stride: int):
    """a with rows i overwritten by v (out-of-range rows dropped)."""
    if not stride:
        return a.at[i].set(v, mode="drop")
    rpl = LANES // stride
    lane = (i % rpl * stride)[:, None] + jnp.arange(v.shape[1])
    return a.at[(i // rpl)[:, None], lane].set(v, mode="drop")


# lane lines unpacked at a time by rows_view: the (rows, dim) result is
# lane-padded on the chip, and so would a whole (rows, stride) view of
# the table on the way to it be
_UNPACK_LINES = 1 << 16


@partial(jax.jit, static_argnums=(1, 2))
def _unpack_jit(lines, stride: int, dim: int):
    n = lines.shape[0]
    if n <= _UNPACK_LINES or n % _UNPACK_LINES:
        return unpack_rows(lines, stride, dim)
    rows = _UNPACK_LINES * (LANES // stride)

    def chunk(i, out):
        part = jax.lax.dynamic_slice_in_dim(lines, i * _UNPACK_LINES,
                                            _UNPACK_LINES)
        return jax.lax.dynamic_update_slice_in_dim(
            out, unpack_rows(part, stride, dim), i * rows, 0)

    return jax.lax.fori_loop(
        0, n // _UNPACK_LINES, chunk,
        jnp.zeros((n * (LANES // stride), dim), lines.dtype))


def quantize_push(grad, nbytes: int = 0):
    """Transfer-filter parity (fixed_bytes knob, reference
    config.proto:126-133 + FIXING_FLOAT filter): round the pushed gradient
    to a lower-precision dtype before aggregation. 0 = off, 2 = bfloat16,
    1 = int8-scaled."""
    if nbytes == 0:
        return grad
    if nbytes >= 2:
        return grad.astype(jnp.bfloat16).astype(grad.dtype)
    # 1 byte: per-array absmax int8 scaling
    scale = jnp.maximum(jnp.max(jnp.abs(grad)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(grad / scale), -127, 127).astype(jnp.int8)
    return q.astype(grad.dtype) * scale
