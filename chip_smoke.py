#!/usr/bin/env python3
"""chip_smoke.py: does the trainer path users run still start on the chip?

One process — the one that owns the chip — drives the normal entry path
(`wormhole_tpu.apps._runner.run_minibatch_app`, the function behind
`python -m wormhole_tpu.apps.linear conf`) over Criteo-shape text files it
generates from a seed: native parser -> loader threads -> prepare/stage ->
jitted Pallas steps -> save -> load -> predict. Stages:

  1a  linear FTRL at the Criteo-1TB table scale (2^26 buckets x 65,536 rows
      x 39 nnz, bf16 kernels), two passes + val + save + predict; the staged
      batches must be `tcoo` on one chip (`mcoo` on several), pass 2 must
      trigger no XLA compilation, logloss must fall below pass 1 and ln 2.
  1b  three pure-predict jobs on the saved model (pallas/bf16, pallas/f32,
      xla) plus xla on the bf16-rounded weights: kernels against reference.
  1c  the headline shape's other kernel set: 2^22 buckets, dense `coo`.
  2   DiFacto dim 8 on the same files, one pass + val.

It refuses to run without a TPU backend, in interpret mode, or on the
Python parsers: there is no fallback, and any failed check raises. The last
stdout line is the verdict, one JSON object with exactly two keys,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`;
the line before it, `[smoke] summary: {...}`, carries everything else
(versions, mesh, per-stage kind / steps / compile_s / logloss, cache
entries, `"claim": null`). The rates in it are smoke figures from a single
sample, not benchmark results.

  python chip_smoke.py                        # the chip check (one chip)
  python chip_smoke.py --stages 1a --model-shards 2   # builder runs, 4 chips
"""

from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import glob
import json
import logging
import math
import os
import re
import shutil
import sys
import tempfile
import time

import numpy as np

NNZ = 39            # Criteo: 13 integer + 26 categorical fields
SEED = 20260926
# Labels come from a planted linear model over the (field, value) keys, so
# that a learner which works must push logloss well under ln 2: margin =
# PLANT_BIAS + sum of 39 weights uniform in +-PLANT_SCALE/2 (std ~1.8,
# click rate ~0.27).
PLANT_SCALE = 1.0
PLANT_BIAS = -1.5
# Margins are compared through predict_out text, written `%.6g`: two files
# may each be off by half a unit in the sixth digit.
TEXT_RTOL = 2e-6
# f32 Pallas against the XLA segment-op reference: same f32 products, other
# summation order (PERF.md claims bit-exact on hardware).
F32_ATOL = 1e-4
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = COMPILE_EVENTS[2]
CACHE_HIT = "/jax/compilation_cache/cache_hits"
SUMMARY_TAG = "[smoke] summary: "


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Size:
    """Geometry of a run. The defaults are the real thing; only
    tests/test_chip_smoke.py shrinks them (and asks for `kernel=pallas`,
    i.e. interpret mode, which the chip run never does)."""

    minibatch: int = 65536
    train_parts: int = 4          # files per pass: >= 2 loaders stay busy
    batches_per_part: int = 2     # 4 x 2 = 8 minibatches per pass
    val_parts: int = 2
    big_buckets: int = 1 << 26    # Criteo-1TB table scale -> compacted
    small_buckets: int = 1 << 22  # headline shape -> dense coo
    v_buckets: int = 1 << 20
    kernel: str = "auto"
    model_shards: int = 1


# ------------------------------------------------------------------- device
def require_tpu() -> dict:
    """Fail at once unless this process would really run on the chip."""
    try:
        import jax

        from wormhole_tpu.ops import coo_kernels as ck
    except ImportError as e:
        sys.exit(f"chip_smoke: cannot import the program ({e}); run it "
                 "from the root of a wormhole-tpu checkout")
    if jax.default_backend() != "tpu" or ck._use_interpret():
        sys.exit(f"chip_smoke: no TPU — jax.default_backend() is "
                 f"{jax.default_backend()!r} (JAX_PLATFORMS="
                 f"{os.environ.get('JAX_PLATFORMS')!r}); the Pallas kernels "
                 "would run interpreted. Nothing was run.")
    return device_info()


def device_info() -> dict:
    from importlib import metadata

    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    devs = jax.devices()
    info = {"device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)},
            "versions": {"python": sys.version.split()[0],
                         "jax": jax.__version__,
                         "jaxlib": jaxlib.__version__, "libtpu": libtpu}}
    print(f"[smoke] platform={devs[0].platform} "
          f"device_kind={devs[0].device_kind!r} devices={len(devs)} "
          + " ".join(f"{k}={v}" for k, v in info["versions"].items()),
          flush=True)
    return info


# --------------------------------------------------------------------- data
# Criteo-like per-field value cardinalities: 13 integer features (small
# ranges after the log transform) + 26 categorical with a mix of tiny
# (geo/flag-like) and huge (id-like) vocabularies.
FIELD_CARDS = [50] * 13 + [
    10, 100, 1000, 10_000, 100_000, 1_000_000, 10_000_000,
    25, 250, 2500, 25_000, 250_000, 2_500_000,
    40, 400, 4000, 40_000, 400_000, 4_000_000,
    60, 600, 6000, 60_000, 600_000,
    80, 800,
]
assert len(FIELD_CARDS) == 39


def criteo_field_draws(rng, n):
    """(n, 39) per-field value draws: Zipf-ish within each field's
    vocabulary (CTR datasets are power-law within each field)."""
    draws = np.empty((n, len(FIELD_CARDS)), dtype=np.uint64)
    for f, card in enumerate(FIELD_CARDS):
        draws[:, f] = rng.zipf(1.2, size=n).astype(np.uint64) % card
    return draws


def mix_field_values(draws):
    """64-bit key per (field, value): per-field salt then a splitmix-style
    mix, matching the criteo parser's field-salted hashing
    (criteo_parser.h:69-82)."""
    with np.errstate(over="ignore"):  # 64-bit mixing wraps by design
        x = draws + (np.arange(draws.shape[1], dtype=np.uint64)
                     * np.uint64(0x9E3779B97F4A7C15))
        x ^= x >> np.uint64(30)
        x *= np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(27)
    return x


def write_criteo_files(out_dir: str, prefix: str, parts: int,
                       rows_per_part: int, seed: int) -> str:
    """Criteo-format text (label, 13 ints, 26 hex categoricals, tabs) with
    FIELD_CARDS' cardinalities and Zipf draws; returns the file
    pattern. Categorical tokens carry the field-salted mix of the value,
    integer tokens the bare value (like the real data, where the same
    integer in two fields is the same token)."""
    fmt = "%d\t" + "%d\t" * 13 + "\t".join(["%08x"] * 26) + "\n"
    for p in range(parts):
        rng = np.random.default_rng([seed, p])
        draws = criteo_field_draws(rng, rows_per_part)
        mixed = mix_field_values(draws)
        wt = (mixed >> np.uint64(40)).astype(np.float64) / 2.0**24 - 0.5
        margin = PLANT_BIAS + PLANT_SCALE * wt.sum(axis=1)
        label = rng.random(rows_per_part) < 1.0 / (1.0 + np.exp(-margin))
        cols = np.concatenate(
            [label[:, None].astype(np.uint64), draws[:, :13],
             mixed[:, 13:] & np.uint64(0xFFFFFFFF)], axis=1)
        with open(os.path.join(out_dir, f"{prefix}-{p}.criteo"), "w") as fh:
            fh.writelines(fmt % tuple(r) for r in cols.tolist())
    return os.path.join(out_dir, f"{prefix}-.*")


@dataclasses.dataclass(frozen=True)
class Data:
    train: str
    val: str
    train_rows: int
    val_rows: int


def make_data(scratch: str, size: Size) -> Data:
    t0 = time.perf_counter()
    rows = size.minibatch * size.batches_per_part
    data = Data(
        train=write_criteo_files(scratch, "train", size.train_parts, rows,
                                 SEED),
        val=write_criteo_files(scratch, "val", size.val_parts,
                               size.minibatch, SEED + 1),
        train_rows=rows * size.train_parts,
        val_rows=size.minibatch * size.val_parts)
    print(f"[smoke] data: {data.train_rows} train + {data.val_rows} val "
          f"rows of criteo text in {time.perf_counter() - t0:.1f}s",
          flush=True)
    return data


# --------------------------------------------------------------------- conf
def write_conf(path: str, **kv) -> str:
    """A `key = value` conf file, as `python -m wormhole_tpu.apps.linear
    conf` takes it."""
    with open(path, "w") as fh:
        for k, v in kv.items():
            fh.write(f"{k} = {v}\n")
    return path


def base_conf(data: Data, size: Size, num_buckets: int) -> dict:
    return dict(train_data=data.train, val_data=data.val,
                data_format="criteo", num_parts_per_file=1,
                minibatch=size.minibatch, nnz_per_row=NNZ,
                num_buckets=num_buckets, model_shards=size.model_shards,
                algo="ftrl", lambda_l1=1, kernel=size.kernel,
                kernel_dtype="bf16", print_sec=10)


# ------------------------------------------------------------------- probes
class CompileLog:
    """Counts XLA compilations by the phase they fell in, through
    jax.monitoring: every jit compile request (persistent-cache hits
    included) emits one backend_compile duration."""

    def __init__(self):
        self.phase = "setup"
        self.events: list[tuple[str, str, float]] = []
        self.cache_hits = 0

    def _on_duration(self, event: str, secs: float, **_):
        if event in COMPILE_EVENTS:
            self.events.append((self.phase, event, secs))

    def _on_event(self, event: str, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def __enter__(self):
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    def compiles(self, since: int = 0, phases=None) -> int:
        return sum(1 for ph, ev, _ in self.events[since:]
                   if ev == BACKEND_COMPILE and (phases is None
                                                 or ph in phases))

    def seconds(self, since: int = 0) -> float:
        return sum(s for _, _, s in self.events[since:])


def stage_hists() -> dict:
    """(count, sum) of the solver's own per-batch stage histograms
    (train.stage.*, obs/metrics): queue wait of the train thread, and
    pack / host-to-device / step time per batch."""
    from wormhole_tpu.obs.metrics import REGISTRY

    hs = {k: REGISTRY.histogram(f"train.stage.{k}_s")
          for k in ("load", "pack", "h2d", "step")}
    return {k: (h.count, h.sum) for k, h in hs.items()}


class Tap:
    """The learner as the solver sees it, observed from outside: forwards
    everything, and notes per pass the staged batch kinds, steps, examples
    and logloss — the solver keeps only the last pass's progress."""

    def __init__(self, learner, clog: CompileLog):
        self._learner = learner
        self._clog = clog
        self.passes: list[dict] = []

    def __getattr__(self, name):
        return getattr(self._learner, name)

    def _open(self, mode):
        self.passes.append(dict(mode=mode, kinds=set(), steps=0, nex=0.0,
                                logloss=0.0, losses=[],
                                t0=time.perf_counter(), t1=None,
                                hists=stage_hists()))
        self._clog.phase = f"pass{len(self.passes) - 1}"

    def on_pass_start(self):
        self._open(None)
        hook = getattr(self._learner, "on_pass_start", None)
        if hook is not None:
            hook()

    def _step(self, mode, fn, b):
        out = fn(b)
        p = self.passes[-1]
        p["t1"] = time.perf_counter()
        p["mode"] = mode
        p["kinds"].add(self._learner.batch_kind(b))
        p["steps"] += 1
        p["nex"] += out["nex"]
        p["logloss"] += out["logloss"]
        p["losses"].append(out["logloss"] / max(out["nex"], 1.0))
        return out

    def train_batch(self, b):
        return self._step("train", self._learner.train_batch, b)

    def eval_batch(self, b):
        return self._step("val", self._learner.eval_batch, b)

    def predict_batch(self, blk):
        if not self.passes or self.passes[-1]["mode"] != "predict":
            self._open("predict")
        return self._learner.predict_batch(blk)

    def of(self, mode) -> list[dict]:
        return [p for p in self.passes if p["mode"] == mode]


class WarningLog(logging.Handler):
    """Everything the program warns about: dropped rows, compaction or
    mesh-shard overflow, row-cap overflow all arrive as warnings."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.messages: list[str] = []

    def emit(self, record):
        self.messages.append(record.getMessage())


# ------------------------------------------------------------------- stages
def run_app(app, cfg_cls, argv, clog: CompileLog):
    """Run one job the way `app.main(argv)` does, keeping a handle on the
    learner (`app_main` drops the solver's result)."""
    from wormhole_tpu.apps._runner import parse_cli, run_minibatch_app

    cfg = parse_cli(cfg_cls, list(argv))
    made = []

    def make_learner(cfg, env):
        made.append(Tap(app.make_learner(cfg, env), clog))
        return made[0]

    warns = WarningLog()
    logging.getLogger("wormhole_tpu").addHandler(warns)
    mark, hits0 = len(clog.events), clog.cache_hits
    clog.phase = "setup"
    t0 = time.perf_counter()
    try:
        run_minibatch_app(cfg, make_learner)
    finally:
        logging.getLogger("wormhole_tpu").removeHandler(warns)
    wall = time.perf_counter() - t0
    tap = made[0]
    check(not warns.messages,
          f"the program warned during the run: {warns.messages[:3]}")
    check(tap._learner._dropped_rows == 0,
          f"{tap._learner._dropped_rows} rows dropped on batch overflow")
    return cfg, tap, dict(
        wall_s=round(wall, 2), compile_s=round(clog.seconds(mark), 2),
        compiles=clog.compiles(mark),
        cache_hits=clog.cache_hits - hits0), mark


def train_summary(name, tap: Tap, base: dict, data: Data, passes: int,
                  expect_kind: str) -> dict:
    """Checks every training stage shares, and its JSON entry."""
    train, val = tap.of("train"), tap.of("val")
    check(len(train) == passes and len(val) == passes,
          f"{name}: {len(train)} train / {len(val)} val passes, "
          f"expected {passes} each")
    kinds = set().union(*(p["kinds"] for p in train + val))
    check(kinds == {expect_kind},
          f"{name}: staged batch kinds {sorted(kinds)}, expected "
          f"{expect_kind!r} — {tap.placement}")
    for p in train:
        check(p["nex"] == data.train_rows,
              f"{name}: a train pass saw {p['nex']} examples, the files "
              f"hold {data.train_rows}")
    for p in val:
        check(p["nex"] == data.val_rows,
              f"{name}: a val pass saw {p['nex']} examples, the files "
              f"hold {data.val_rows}")
    loss = {m: [p["logloss"] / p["nex"] for p in ps]
            for m, ps in (("train", train), ("val", val))}
    check(all(math.isfinite(x) for xs in loss.values() for x in xs),
          f"{name}: non-finite logloss {loss}")
    first, last = train[0]["losses"], train[-1]["losses"]
    check(np.mean(last[-2:]) < np.mean(first[:2]),
          f"{name}: train logloss did not fall: first steps {first[:2]}, "
          f"last steps {last[-2:]}")
    check(loss["val"][-1] < math.log(2),
          f"{name}: val logloss {loss['val'][-1]:.4f} is not under ln 2")
    warm = train[-1]
    # the val pass that follows opens where the last train pass ends
    h0, h1 = warm["hists"], tap.passes[tap.passes.index(warm) + 1]["hists"]
    stage_ms = {k: round(1e3 * (h1[k][1] - h0[k][1])
                         / max(h1[k][0] - h0[k][0], 1), 1) for k in h0}
    return dict(
        base, kind=expect_kind,
        mesh="x".join(str(tap.mesh.shape[a]) for a in ("data", "model")),
        steps=sum(p["steps"] for p in train),
        examples=int(sum(p["nex"] for p in train)),
        logloss={m: [round(x, 5) for x in xs] for m, xs in loss.items()},
        # single-sample smoke figures of the last train pass, loaders on.
        # Per batch: the train thread waiting on the loaders (load) and in
        # the blocking step (dispatch + progress fetch); loader threads
        # packing and staging (pack, h2d)
        smoke_stage_ms=stage_ms,
        smoke_pass_examples_per_s=round(
            warm["nex"] / (warm["t1"] - warm["t0"])))


def placement_evidence(learner) -> dict:
    """Where the tables landed: the devices holding shards of `w`, and
    each mesh device's live bytes (nothing may pile up on device 0)."""
    w = learner.store.state["w"]
    devs = list(learner.mesh.devices.flat)
    stats = [d.memory_stats() for d in devs]
    out = {"w_shard_devices": sorted(s.device.id
                                     for s in w.addressable_shards),
           "w_shard_rows": sorted({s.data.shape[0]
                                   for s in w.addressable_shards})}
    if all(stats):  # the CPU backend reports none
        out["bytes_in_use"] = [s["bytes_in_use"] for s in stats]
        out["peak_bytes_in_use"] = [s["peak_bytes_in_use"] for s in stats]
    return out


def stage_1a(data: Data, size: Size, out: str, clog: CompileLog) -> dict:
    from wormhole_tpu.apps import linear as app
    from wormhole_tpu.models.linear import LinearConfig

    conf = write_conf(
        os.path.join(out, "1a.conf"),
        **base_conf(data, size, size.big_buckets), max_data_pass=2,
        model_out=os.path.join(out, "model_1a"),
        predict_out=os.path.join(out, "pred_1a"))
    cfg, tap, base, mark = run_app(app, LinearConfig, [conf], clog)
    lrn = tap._learner
    check(lrn.use_pallas, f"1a: not on the Pallas path — {lrn.placement}")
    ndev = lrn.mesh.devices.size
    res = train_summary("1a", tap, base, data, 2,
                        "tcoo" if ndev == 1 else "mcoo")
    loss = res["logloss"]
    check(loss["train"][1] < loss["train"][0] < math.log(2),
          f"1a: train logloss by pass {loss['train']} is not falling "
          "under ln 2")
    # held-out loss falls too at the real size (0.54 -> 0.52); the margin
    # only absorbs batch-order noise at the tests' tiny one
    check(loss["val"][1] < loss["val"][0] + 0.005,
          f"1a: val logloss by pass {loss['val']} is rising")
    # passes 0/1 are the first train/val, 2/3 the second
    res["pass2_compiles"] = clog.compiles(mark, phases=("pass2", "pass3"))
    check(res["pass2_compiles"] == 0,
          f"1a: {res['pass2_compiles']} XLA compilations during pass 2")
    res["compact_cap"] = lrn._compact_cap
    res["placement"] = place = placement_evidence(lrn)
    check(len(set(place["w_shard_devices"])) == ndev,
          f"1a: w has shards on devices {place['w_shard_devices']}, the "
          f"mesh has {ndev}")
    if ndev > 1 and "bytes_in_use" in place:
        lo, hi = min(place["bytes_in_use"]), max(place["bytes_in_use"])
        check(hi <= 2 * lo, f"1a: device memory is lopsided: "
                            f"{place['bytes_in_use']}")
    margins = read_margins(cfg.predict_out)
    check(len(margins) == data.val_rows and np.isfinite(margins).all(),
          f"1a: predict_out holds {len(margins)} margins for "
          f"{data.val_rows} val rows (or non-finite ones)")
    res["model"] = cfg.model_out
    return res


def read_margins(predict_out: str) -> np.ndarray:
    """predict_out is one text file per part (iter_solver.h:140-156)."""
    files = sorted(glob.glob(predict_out + "_part-*"),
                   key=lambda f: int(re.search(r"_part-(\d+)$", f).group(1)))
    check(files, f"no predict files at {predict_out}_part-*")
    return np.concatenate([np.loadtxt(f, ndmin=1) for f in files])


def stage_1b(data: Data, size: Size, out: str, clog: CompileLog,
             model: str) -> dict:
    """Save/load/predict and kernel-vs-reference, still through the entry
    point: pure-predict jobs (max_data_pass=0) on stage 1a's model."""
    import jax.numpy as jnp

    from wormhole_tpu.apps import linear as app
    from wormhole_tpu.models.linear import LinearConfig
    from wormhole_tpu.ops import coo_kernels as ck
    from wormhole_tpu.utils import checkpoint as ckpt

    conf = write_conf(os.path.join(out, "1b.conf"),
                      **base_conf(data, size, size.big_buckets),
                      max_data_pass=0)
    # the bf16 kernels' one rounding is w -> bfloat16 at the table fetch
    # (one-hot matmuls select exactly, values here are 1.0, sums are f32):
    # XLA on the rounded weights is their exact reference
    tables = ckpt.load_parts(model)
    wmax = float(np.abs(tables["w"]).max())
    check(wmax > 0, "1b: the saved model is all zeros")
    tables["w"] = np.asarray(jnp.asarray(tables["w"]).astype(jnp.bfloat16)
                             .astype(jnp.float32))
    rounded = os.path.join(out, "model_1a_bf16w")
    ckpt.atomic_savez(rounded + ".npz", compressed=True, **tables)
    del tables

    pallas = "pallas" if size.kernel == "auto" else size.kernel
    jobs = {"bf16": (pallas, "bf16", model), "f32": (pallas, "f32", model),
            "xla": ("xla", "f32", model),
            "xla_bf16w": ("xla", "f32", rounded)}
    got, res = {}, dict(wall_s=0.0, compile_s=0.0, compiles=0)
    for tag, (kernel, dtype, model_in) in jobs.items():
        cfg, tap, base, _ = run_app(app, LinearConfig, [
            conf, f"kernel={kernel}", f"kernel_dtype={dtype}",
            f"model_in={model_in}",
            f"predict_out={os.path.join(out, 'pred_1b_' + tag)}"], clog)
        check(tap._learner.use_pallas == (kernel != "xla"),
              f"1b/{tag}: kernel={kernel} but {tap.placement}")
        got[tag] = read_margins(cfg.predict_out)
        check(len(got[tag]) == data.val_rows,
              f"1b/{tag}: {len(got[tag])} margins, {data.val_rows} rows")
        for k in res:
            res[k] = round(res[k] + base[k], 2)

    def gap(a, b):
        return float(f"{np.max(np.abs(got[a] - got[b])):.3g}")

    def close(a, b, atol):
        return bool(np.allclose(got[a], got[b], rtol=TEXT_RTOL, atol=atol))

    # worst case of that rounding: <= 39 unit-valued terms per row, each
    # off by at most half a bfloat16 ulp of its weight (2^-9 relative)
    bf16_bound = NNZ * wmax * 2.0**-9 + F32_ATOL
    res.update(rows=data.val_rows, max_abs_w=round(wmax, 4),
               margin_abs_max=round(float(np.abs(got["xla"]).max()), 3),
               f32_vs_xla=gap("f32", "xla"), f32_atol=F32_ATOL,
               bf16_vs_xla_bf16w=gap("bf16", "xla_bf16w"),
               bf16_vs_xla=gap("bf16", "xla"),
               bf16_bound=round(bf16_bound, 5))
    check(close("f32", "xla", F32_ATOL),
          f"1b: f32 kernels differ from XLA by {res['f32_vs_xla']:.3g}")
    # kernel_dtype=bf16 asks for the kernels' default dtype, which in
    # interpret mode (tests only) is f32: there the bf16 job is an f32 job
    exact_ref = "xla" if ck._use_interpret() else "xla_bf16w"
    check(close("bf16", exact_ref, F32_ATOL),
          f"1b: bf16 kernels differ from their reference ({exact_ref}) by "
          f"{gap('bf16', exact_ref):.3g}")
    check(close("bf16", "xla", bf16_bound),
          f"1b: bf16 kernels differ from XLA by {res['bf16_vs_xla']:.3g},"
          f" over the rounding bound {bf16_bound:.3g}")
    return res


def stage_1c(data: Data, size: Size, out: str, clog: CompileLog) -> dict:
    from wormhole_tpu.apps import linear as app
    from wormhole_tpu.models.linear import LinearConfig

    conf = write_conf(os.path.join(out, "1c.conf"),
                      **base_conf(data, size, size.small_buckets),
                      max_data_pass=1)
    _, tap, base, _ = run_app(app, LinearConfig, [conf], clog)
    lrn = tap._learner
    check(lrn.use_pallas, f"1c: not on the Pallas path — {lrn.placement}")
    return train_summary("1c", tap, base, data, 1,
                         "coo" if lrn.mesh.devices.size == 1 else "mcoo")


def stage_2(data: Data, size: Size, out: str, clog: CompileLog) -> dict:
    from wormhole_tpu.apps import difacto as app
    from wormhole_tpu.models.difacto import DifactoConfig

    conf = write_conf(os.path.join(out, "2.conf"),
                      **base_conf(data, size, size.small_buckets),
                      v_buckets=size.v_buckets, dim=8, threshold=2,
                      max_data_pass=1)
    _, tap, base, _ = run_app(app, DifactoConfig, [conf], clog)
    lrn = tap._learner
    one = lrn.mesh.devices.size == 1
    check(lrn._use_fm_pallas == one,
          f"2: {'not ' if one else ''}on the Pallas FM path — "
          f"{lrn.placement}")
    return train_summary("2", tap, base, data, 1,
                         "fm" if one else "xla_staged")


# --------------------------------------------------------------------- main
def run_stages(stages, size: Size, scratch: str) -> dict:
    """Generate the data and run the named stages; returns their JSON
    entries. Callable at a tiny `size` on CPU (tests)."""
    from wormhole_tpu import native

    check(native.status() == "loaded",
          f"the native parsing core is {native.status()}: the Python "
          "parsers would feed the run")
    data = make_data(scratch, size)
    out = {}
    with CompileLog() as clog:
        if "1a" in stages:
            out["1a"] = stage_1a(data, size, scratch, clog)
        if "1b" in stages:
            check("1a" in out, "stage 1b needs stage 1a's model")
            out["1b"] = stage_1b(data, size, scratch, clog,
                                 out["1a"]["model"])
        if "1c" in stages:
            out["1c"] = stage_1c(data, size, scratch, clog)
        if "2" in stages:
            out["2"] = stage_2(data, size, scratch, clog)
    for res in out.values():
        res.pop("model", None)
        print(f"[smoke] stage result: {json.dumps(res)}", flush=True)
    return out


def cache_entries() -> tuple[str, int]:
    import jax

    import wormhole_tpu  # noqa: F401  (places the cache on import)

    d = jax.config.jax_compilation_cache_dir
    return d, (len(os.listdir(d)) if d and os.path.isdir(d) else 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--stages", default="1a,1b,1c,2",
                    help="comma-separated subset of 1a,1b,1c,2")
    ap.add_argument("--model-shards", type=int, default=1,
                    help="model_shards for every job (several chips: the "
                         "mesh is devices/shards x shards)")
    ap.add_argument("--deadline", type=int, default=1150,
                    help="dump all stacks and exit non-zero after this "
                         "many seconds (the chip check allows 1200)")
    args = ap.parse_args(argv)
    stages = args.stages.split(",")
    check(set(stages) <= {"1a", "1b", "1c", "2"}, f"unknown stage in "
                                                 f"{args.stages!r}")
    faulthandler.dump_traceback_later(args.deadline, exit=True)
    t0 = time.perf_counter()
    info = require_tpu()
    cache_dir, before = cache_entries()
    scratch = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        results = run_stages(stages, Size(model_shards=args.model_shards),
                             scratch)
    except BaseException:
        # nothing is carried past a failed phase: say so in the verdict's
        # own format, then let the failure end the process non-zero
        print_verdict(False, info)
        raise
    finally:
        faulthandler.cancel_dump_traceback_later()
        shutil.rmtree(scratch, ignore_errors=True)
    from wormhole_tpu import native

    summary = dict(
        ok=True, **info, stages=results, native=native.status(),
        compile_cache=dict(dir=cache_dir, entries_before=before,
                           entries_after=cache_entries()[1]),
        wall_s=round(time.perf_counter() - t0, 1),
        note="rates are single-sample smoke figures, not benchmark results",
        claim=None)
    print(f"{SUMMARY_TAG}{json.dumps(summary)}", flush=True)
    print_verdict(True, info)
    return 0


def print_verdict(ok: bool, info: dict) -> None:
    """The last stdout line, and all the chip check reads: exactly `ok`
    and the device as JAX reports it. Everything else the run learned is
    on the `[smoke] summary:` line before it."""
    print(json.dumps({"ok": ok, "device": info["device"]}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
