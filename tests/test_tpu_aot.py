"""Every Pallas kernel in ops/ still compiles for the chip — checked
without one.

`jax.experimental.topologies.get_topology_desc` describes a v5e host to the
installed libtpu, and `jit(...).lower(...).compile()` against its devices
runs the real Mosaic + XLA:TPU compile at production geometry (TILE_HI=512,
BLK=4096, BLK_U=1024, FM_BLK=1024; 65,536 x 39 batches; bf16 and f32). A
Mosaic rejection — a VMEM limit, a block shape, an op it no longer lowers —
fails here, on the CPU, instead of on chip time.

Each kernel also has to reach the compiled program under its own name
(`pallas_call(name=...)` becomes the HLO instruction's name, which is
what a device trace calls the operation): the per-kernel metrics of the
benchmark match on `%tile_gather`, `%fused_update`, `%coo_push`,
`%coo_pull`.

The benchmark's own geometries are here too (PERF.md §4): the compacted
kernels at 2^29 buckets with the 12,582,912-slot compact domain its
batches get, and the `mcoo` pair on a 1 x 4 mesh at 2^28 buckets a shard.
Their bodies run over chunks of a block under `pl.when` (ops/coo_kernels
`_live_chunks`), which only Mosaic can accept or refuse.

Kernel-only programs on purpose: a whole train step adds the AUC sort,
which alone compiles for ~25-50 s (PERF.md §6, PR 21). There is one
installation, so a topology that cannot be built is a failure, not a skip.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from wormhole_tpu.ops import coo_kernels as ck
from wormhole_tpu.ops import fused_update as fu
from wormhole_tpu.ops import hist

ROWS, NNZ = 65536, 39
CAP = ROWS * NNZ
NB_DENSE = 1 << 22          # headline table: dense coo kernels
NB_BIG = 1 << 26            # Criteo-1TB table: compacted kernels
U_CAP = 1572864             # its auto compact_cap (24 tiles)
NB_1TB = 1 << 29            # the benchmark's one-chip table
U_CAP_1TB = 12582912        # its compact domain: 12,288 update blocks
NB_MESH = 1 << 30           # the four-chip table, 2^28 buckets a shard
VB = 1 << 20                 # DiFacto smoke shape: V rows
# the benchmark's FM cell: dim 50 at stride 64, 100,000 rows padded to 128s
FM_1TB = (50, 64, 100096)
UW_CAP, UV_CAP = 6 * ck.TILE, 256 * ck.BLK_U
DTYPES = [jnp.bfloat16, jnp.float32]


@pytest.fixture(scope="module")
def topo():
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert topo.devices[0].device_kind == "TPU v5 lite", topo.devices
    return topo


@pytest.fixture(scope="module")
def v5e(topo):
    return NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())


@pytest.fixture(autouse=True)
def compiled_not_interpreted(monkeypatch):
    # the test process runs on CPU, where the kernels would pick interpret
    # mode; the AOT target is the chip
    for mod in (ck, fu, hist):
        monkeypatch.setattr(mod, "_use_interpret", lambda: False)
    assert (ck.TILE_HI, ck.BLK, ck.BLK_U, ck.FM_BLK) == (512, 4096, 1024,
                                                         1024)


def aot(kernel, fn, sharding, *shapes):
    """Compile fn for the described chip; shapes are (shape, dtype), or
    (shape, dtype, sharding) where an argument has one of its own.
    `kernel` is the name its one Pallas kernel has to carry."""
    args = [jax.ShapeDtypeStruct(s[0], s[1], sharding=(s[2:] or
                                                       (sharding,))[0])
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    # Mosaic really ran (an interpreted kernel leaves no custom call),
    # and the instruction is named for the kernel, not for the jit
    calls = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = .*custom_call_target="
                       r'"tpu_custom_call"', text, re.M)
    assert calls and all(re.fullmatch(re.escape(kernel) + r"(\.\d+)*", c)
                         for c in calls), (kernel, calls)


def streamed_tables(fn, *shapes):
    """How the fused update's one Pallas call takes its inputs: the
    number the pipeline streams by (TILE_HI, LANES) tile, and the number
    left whole in HBM (memory space ANY), which get no DMA."""
    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    jaxpr = jax.make_jaxpr(fn)(*[jax.ShapeDtypeStruct(*s) for s in shapes])
    (call,) = calls(jaxpr.jaxpr)
    gm = call.params["grid_mapping"]
    avals = [str(bm.transformed_block_aval)
             for bm in gm.block_mappings[:gm.num_inputs]]
    tile = f"float32[{ck.TILE_HI},{ck.LANES}]"
    return (sum(a == f"Ref{{{tile}}}" for a in avals),
            sum(a.startswith("Ref<any>") for a in avals))


def coo_stream(capacity, num_buckets, tile=None, blk=None):
    """(idx, seg, val, tmap, first) shapes of a packed COO stream."""
    p = ck.packed_size(capacity, num_buckets, tile, blk)
    nblk = p // (blk or ck.BLK)
    i32, f32 = jnp.int32, jnp.float32
    return [((p,), i32), ((p,), i32), ((p,), f32), ((nblk,), i32),
            ((nblk,), i32)]


def slot_blocks(u_cap, n):
    """n per-update-block int32 vectors (tmap_u, first_u, last_u)."""
    return [((u_cap // ck.BLK_U,), jnp.int32)] * n


def hlo_lines(compiled):
    """The compiled program's instructions, operands with their shapes,
    as the profiler names an operation."""
    from jax._src.lib import xla_client as xc

    opts = xc._xla.HloPrintOptions()
    opts.print_operand_shape = True
    return [ln.strip() for ln in compiled.runtime_executable()
            .hlo_modules()[0].to_string(opts).splitlines()]


def metric_pattern(name):
    """The pattern by which the benchmark's layer metric `name` finds
    its device operations."""
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "layer_metrics",
        name + ".json")
    with open(path) as fh:
        return re.compile(json.load(fh)["params"]["pattern"])


def progress_outputs(compiled):
    """Shapes of a compiled train step's float32 outputs beside its
    tables (the dicts): the packed progress alone. No output of the
    step, whatever its type, may be a scalar: each would be a
    device-to-host read of its own."""
    outs = compiled.out_info
    assert all(o.shape != () for o in jax.tree_util.tree_leaves(outs)), outs
    return [tuple(o.shape) for o in outs
            if not isinstance(o, dict) and o.dtype == jnp.float32]


@pytest.mark.parametrize("dtype", DTYPES)
def test_coo_pull_push_dense(v5e, dtype):
    stream = coo_stream(CAP, NB_DENSE)
    aot("coo_pull", lambda w, *s: ck.coo_spmv(w, *s, ROWS, dtype=dtype),
        v5e, ((NB_DENSE,), jnp.float32), *stream)
    aot("coo_push",
        lambda d, *s: ck.coo_spmv_t(d, *s, NB_DENSE, dtype=dtype),
        v5e, ((ROWS,), jnp.float32), *stream)


def test_batch_solver_passes_at_the_benchmarks_columns(v5e):
    """The batch solver's packed passes (models/batch_objectives.py, PR
    48) as `lbfgs1tb.resident` runs them: a row chunk over all 1,024
    tiles of 2^26 columns, float32 (the three-pass bodies of
    `_onehot_dot`). The objective's chunk is `coo_pull` alone; the
    gradient's is `coo_pull` then `coo_push`, and adds into gw in place."""
    from wormhole_tpu.models import batch_objectives as bo

    obj = object.__new__(bo.LinearObjFunction)
    obj.num_feature = NB_BIG
    obj._build_packed(bo.ROW_CHUNK)
    f32 = jnp.float32
    # the pack gives a chunk the blocks its fullest one needs: a block a
    # tile and a few more for the hot columns' tiles
    need = NB_BIG // ck.TILE + 16
    stream = coo_stream((need - NB_BIG // ck.TILE) * ck.BLK, NB_BIG)
    rows = [((bo.ROW_CHUNK,), f32)] * 2
    aot("coo_pull", obj._eval_chunk, v5e, ((), f32), ((NB_BIG,), f32),
        ((), f32), *stream, *rows)
    args = [jax.ShapeDtypeStruct(s[0], s[1], sharding=v5e)
            for s in [((NB_BIG,), f32), ((), f32), ((NB_BIG,), f32),
                      ((), f32), *stream, *rows]]
    compiled = obj._grad_chunk.lower(*args).compile()
    text = compiled.as_text()
    calls = re.findall(r"%([\w.\-]+) = .*custom_call_target="
                       r'"tpu_custom_call"', text)
    assert [c.split(".")[0] for c in calls] == ["coo_pull", "coo_push"]
    # gw is donated and the kernel adds into it tile by tile (`acc`): the
    # sum is written where the old one lay, with no table-sized temporary
    assert "input_output_alias" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("dtype", DTYPES)
def test_compacted_linear_kernels(v5e, dtype):
    f32, i32 = jnp.float32, jnp.int32
    table2 = ((NB_BIG // ck.LANES, ck.LANES), f32)
    aot("tile_gather",
        lambda t, u, tm: ck.tile_gather(t, u, tm, dtype=dtype),
        v5e, table2, ((U_CAP,), i32), *slot_blocks(U_CAP, 1))
    aot("coo_push", lambda d, *s: ck.coo_spmv_t(d, *s, U_CAP, dtype=dtype),
        v5e, ((ROWS,), f32), *coo_stream(CAP, U_CAP))

    def update(z, n, w, g, uniq, tm, fi, la):
        return fu.scatter_update(
            "ftrl", {"z": z, "n": n, "w": w}, g, uniq, tm, fi, la,
            lr_eta=0.1, lr_beta=1.0, lambda_l1=1.0, lambda_l2=0.0,
            dtype=dtype)

    shapes = (*[((NB_BIG,), f32)] * 3, ((U_CAP,), f32), ((U_CAP,), i32),
              *slot_blocks(U_CAP, 3))
    aot("fused_update", update, v5e, *shapes)
    # FTRL streams z and n in by tile and no w: w is derived, written
    # through its alias and never read (ops/fused_update.apply_handle)
    assert streamed_tables(update, *shapes) == (2, 1)


@pytest.mark.parametrize("dtype", DTYPES)
def test_compacted_linear_kernels_at_the_benchmarks_table(v5e, dtype):
    f32, i32 = jnp.float32, jnp.int32
    aot("tile_gather",
        lambda t, u, tm: ck.tile_gather(t, u, tm, dtype=dtype),
        v5e, ((NB_1TB // ck.LANES, ck.LANES), f32), ((U_CAP_1TB,), i32),
        *slot_blocks(U_CAP_1TB, 1))
    aot("coo_push",
        lambda d, *s: ck.coo_spmv_t(d, *s, U_CAP_1TB, dtype=dtype),
        v5e, ((ROWS,), f32), *coo_stream(CAP, U_CAP_1TB))
    # the compact step's pull since PR 32: a (TILE,) block of the
    # 12.6 M-slot compact domain a grid step
    aot("coo_pull", lambda wc, *s: ck.coo_spmv(wc, *s, ROWS, dtype=dtype),
        v5e, ((U_CAP_1TB,), f32), *coo_stream(CAP, U_CAP_1TB))

    def update(z, n, w, g, uniq, tm, fi, la):
        return fu.scatter_update(
            "ftrl", {"z": z, "n": n, "w": w}, g, uniq, tm, fi, la,
            lr_eta=0.1, lr_beta=1.0, lambda_l1=4.0, lambda_l2=0.0,
            dtype=dtype)

    shapes = (*[((NB_1TB,), f32)] * 3, ((U_CAP_1TB,), f32),
              ((U_CAP_1TB,), i32), *slot_blocks(U_CAP_1TB, 3))
    aot("fused_update", update, v5e, *shapes)
    assert streamed_tables(update, *shapes) == (2, 1)


@pytest.mark.parametrize("dtype", DTYPES)
def test_mesh_coo_pull_push_at_a_quarter_of_2p30(topo, dtype):
    from wormhole_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    mesh = Mesh(np.array(topo.devices).reshape(1, 4),
                (DATA_AXIS, MODEL_AXIS))
    M = 4
    cell = NamedSharding(mesh, P(DATA_AXIS, MODEL_AXIS, None))
    table = NamedSharding(mesh, P(MODEL_AXIS))
    rows = NamedSharding(mesh, P(DATA_AXIS))
    cap = ck.mesh_capacity(CAP, 1, M)
    # the pack's own rule gives the shard's block (1,024 since PR 49)
    stream = [((1, M) + s, d, cell) for s, d in coo_stream(
        cap, NB_MESH // M, blk=ck.mesh_block(cap, NB_MESH // M))]
    assert stream[0][0] == (1, 4, 5472256)   # PERF.md §4
    assert stream[3][0] == (1, 4, 5344)
    aot("coo_pull",
        lambda w, *s: ck.mesh_coo_spmv(mesh, w, *s, ROWS, dtype=dtype),
        None, ((NB_MESH,), jnp.float32, table), *stream)
    aot("coo_push",
        lambda d, *s: ck.mesh_coo_spmv_t(mesh, d, *s, NB_MESH,
                                         dtype=dtype),
        None, ((ROWS,), jnp.float32, rows), *stream)


@pytest.mark.parametrize("algo,tables", [("adagrad", 2), ("sgd", 1)])
def test_fused_update_other_handles(v5e, algo, tables):
    f32, i32 = jnp.float32, jnp.int32
    names = ("n", "w")[2 - tables:]

    def update(*a):
        state = dict(zip(names, a[:tables]))
        return fu.scatter_update(algo, state, *a[tables:], lr_eta=0.1,
                                 lr_beta=1.0, lambda_l1=1.0, lambda_l2=0.0,
                                 fixed_bytes=1, dtype=jnp.bfloat16)

    shapes = (*[((NB_BIG,), f32)] * tables, ((U_CAP,), f32),
              ((U_CAP,), i32), *slot_blocks(U_CAP, 3))
    aot("fused_update", update, v5e, *shapes)
    # their w is state, not derived: every table is read by tile
    assert streamed_tables(update, *shapes) == (tables, 0)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("dim,stride,rows", [(8, 8, ROWS), FM_1TB])
def test_fm_kernels(v5e, dtype, dim, stride, rows):
    """The vector-row side of the FM step: the one Pallas kernel it has
    (`fm_push_contrib`, whose row width is the table's stride) at the
    smoke's dim 8 and at the benchmark's dim 50 / stride 64 / 100,096
    rows, and the by-line gather and update round it, which are XLA's:
    they have to compile beside it with the tables donated."""
    f32, i32 = jnp.float32, jnp.int32
    rpl = ck.LANES // stride
    ul_cap, cap = UV_CAP // rpl // 4, rows * NNZ
    uvr_cap = ul_cap * rpl
    idx, _, _, tmap, first = coo_stream(cap, uvr_cap, ck.TILE_HI, ck.FM_BLK)
    p = idx[0][0]
    wire = dtype  # xv, d are looked up at the wire dtype (_build_fm)

    def push(V2, nV2, vlines, xv, d, seg, vv, si, tm, fi):
        Vl = fu.row_gather(V2, vlines)
        gV, touched = ck.fm_push_contrib(
            Vl.reshape(uvr_cap, stride), xv, d, seg, vv, si, tm, fi,
            dtype=dtype, wire=wire)
        return fu.v_update(
            V2, nV2, Vl, gV.reshape(ul_cap, ck.LANES),
            jnp.broadcast_to(touched[:, None], (uvr_cap, stride)
                             ).reshape(ul_cap, ck.LANES),
            vlines, V_lr_eta=0.01, V_lr_beta=1.0, lambda_V=0.01)

    v2 = ((VB * stride // ck.LANES, ck.LANES), f32)
    aot("fm_push_contrib", push, v5e, v2, v2, ((ul_cap,), i32),
        ((rows, stride), f32), ((rows,), f32), ((p,), i32), ((p,), f32),
        idx, tmap, first)

    # difacto's w update: FTRL with cnt riding as the additive table
    def update(z, n, w, cnt, g, uniq, tm, fi, la, wcnts):
        return fu.scatter_update(
            "ftrl", {"z": z, "n": n, "w": w, "cnt": cnt}, g, uniq, tm, fi,
            la, lr_eta=0.1, lr_beta=1.0, lambda_l1=1.0, lambda_l2=0.0,
            dtype=dtype, add_table="cnt", add_values=wcnts)

    shapes = (*[((NB_DENSE,), f32)] * 4, ((UW_CAP,), f32),
              ((UW_CAP,), i32), *slot_blocks(UW_CAP, 3), ((UW_CAP,), f32))
    aot("fused_update", update, v5e, *shapes)
    assert streamed_tables(update, *shapes) == (3, 1)    # z, n, cnt; w


def test_gbdt_histogram(v5e):
    rows, F, B, nodes = 1 << 21, 28, 256, 32   # HIGGS shape, a deep level
    aot("level_hist",
        lambda b, g, h, rel: hist.level_hist(b, g, h, rel, nodes, B), v5e,
        ((rows, F), jnp.uint8), ((rows,), jnp.float32),
        ((rows,), jnp.float32), ((rows,), jnp.int32))


def test_fm_step_carries_the_names_its_layer_metrics_match(v5e):
    """The benchmark finds the vector-row step's device operations by
    text: the Pallas kernels by the name their call carries, XLA's line
    gathers and scatters by the jit argument they read (`vstate['V']`,
    `vstate['nV']` lower to `%vstate__V__`, `%vstate__nV__`). The real
    train step, built by the learner and compiled for the chip at dim 50
    (tables large enough that XLA gathers from HBM as it does at the
    benchmark's size), has to match every such pattern: a renamed
    argument or kernel would otherwise make a metric read nothing,
    silently."""
    import types

    from wormhole_tpu.models import difacto as df
    from wormhole_tpu.parallel.mesh import make_mesh

    rows, nnz = 256, 8
    cfg = df.DifactoConfig(minibatch=rows, nnz_per_row=nnz,
                           num_buckets=16 * ck.TILE, v_buckets=VB, dim=50,
                           threshold=2, kernel="pallas", kernel_dtype="bf16")
    fm = df.DifactoLearner(cfg, make_mesh(1, 1))
    rng = np.random.default_rng(0)
    db = types.SimpleNamespace(
        seg=np.repeat(np.arange(rows, dtype=np.int32), nnz),
        idx=rng.integers(0, cfg.num_buckets, rows * nnz).astype(np.int32),
        val=np.ones(rows * nnz, np.float32))
    pack = fm._pack_fm(db, True)

    def shaped(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e)

    mask = jax.ShapeDtypeStruct((fm._rows,), jnp.float32, sharding=v5e)
    step = fm._fm_steps[0].lower(
        jax.tree_util.tree_map(shaped, fm.store.state),
        jax.tree_util.tree_map(shaped, fm.vstore.state),
        *map(shaped, pack), mask, mask,
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e)).compile()
    lines = hlo_lines(step)
    # how many operations of one step each metric has to find
    for name, want in (("row_gather_ms", 2), ("v_update_ms", 2),
                       ("fm_push_contrib_ms", 1), ("tile_gather_ms", 2),
                       ("fused_update_ms", 1), ("coo_push_ms", 1),
                       ("fm_kernel_ms_per_step", 5),
                       ("kernel_ms_per_step", 5)):
        hit = [ln for ln in lines if metric_pattern(name).search(ln)]
        assert len(hit) == want, (name, hit)
    # one read a step (PR 37): the tables, the progress with the two
    # counts' halves as one f32 vector, the next key; no scalar is left
    assert progress_outputs(step) == [(13,)]


def test_tcoo_step_pulls_and_pushes_over_one_stream(v5e):
    """The linear learner's own compact train step, lowered for the chip
    at `criteo1tb.replay`'s sizes (65,536 x 39, 2^29 buckets, the
    12,582,912-slot compact domain, bf16): one call of each of its four
    kernels, under the names the benchmark's metrics match; the pull and
    the push read one computation of their stream's block extents; and
    no XLA gather reads the compact domain or anything as large (the
    `jnp.take` over a (U + 1, 2) copy of it that the step pulled with
    until PR 32 cost 15.5 ms of a 52 ms device step). The learner is
    built over a two-tile table: the step takes the table's size from
    its arguments alone."""
    from wormhole_tpu.models.linear import LinearConfig, LinearLearner
    from wormhole_tpu.parallel.mesh import make_mesh

    cfg = LinearConfig(minibatch=ROWS, nnz_per_row=NNZ,
                       num_buckets=2 * ck.TILE, algo="ftrl", lr_eta=0.1,
                       lambda_l1=4.0, kernel="pallas", kernel_dtype="bf16")
    lrn = LinearLearner(cfg, make_mesh(1, 1))
    lrn._build_tcoo(U_CAP_1TB)

    def shaped(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    f32 = jnp.float32
    state = {k: shaped((NB_1TB,), f32) for k in lrn.store.state}
    assert sorted(state) == ["n", "w", "z"]
    stream = [shaped(s, d) for s, d in coo_stream(CAP, U_CAP_1TB)]
    step = lrn._kinds["tcoo"].train.lower(
        state, shaped((U_CAP_1TB,)),
        *[shaped(s, d) for s, d in slot_blocks(U_CAP_1TB, 3)], *stream,
        shaped((ROWS,), f32), shaped((ROWS,), f32)).compile()
    lines = hlo_lines(step)

    calls = {}
    for ln in lines:
        m = re.match(r"(?:ROOT )?%([\w.\-]+) = .*custom_call_target="
                     r'"tpu_custom_call"', ln)
        if m:
            calls.setdefault(re.sub(r"(\.\d+)*$", "", m.group(1)),
                             []).append(ln)
    assert {k: len(v) for k, v in calls.items()} == {
        "tile_gather": 1, "coo_pull": 1, "coo_push": 1, "fused_update": 1}
    # one read a step (PR 37): beside the tables the step returns its
    # eight progress values as one f32 vector, and no scalar
    assert progress_outputs(step) == [(8,)]

    for name, kernel in (("tcoo_pull_ms", "coo_pull"),
                         ("coo_push_ms", "coo_push"),
                         ("tile_gather_ms", "tile_gather"),
                         ("fused_update_ms", "fused_update")):
        rx = metric_pattern(name)
        assert [ln for ln in lines if rx.search(ln)] == calls[kernel], name
    rx = metric_pattern("kernel_ms_per_step")
    assert len([ln for ln in lines if rx.search(ln)]) == 4

    # operands of a call, in order: (tmap, first, ext, ...) for the pair
    def operands(ln):
        return re.findall(r"%([\w.\-]+)", ln.split("custom-call(", 1)[1]
                          .split("), custom_call_target", 1)[0])

    pull, push = operands(calls["coo_pull"][0]), operands(calls["coo_push"][0])
    nblk = stream[3].shape[0]
    ext = [ln for ln in lines
           if re.match(rf"(?:ROOT )?%{re.escape(pull[2])} = s32\[{nblk}\]",
                       ln)]
    assert len(ext) == 1 and pull[2] == push[2], (pull[:3], push[:3])
    assert pull[:2] == push[:2]          # tmap, first: the one stream

    def gathered_rows(ln):
        """Rows of the operand a `gather` instruction reads from (fused
        computations are printed too), or None for any other line."""
        if " gather(" not in ln:
            return None
        return int(re.search(r"\w+\[(\d+)", ln.split(" gather(", 1)[1])
                   .group(1))

    # the step's pull until PR 32, as the chip's compiler printed it
    assert gathered_rows(
        "%gather.3 = f32[2555904,2]{1,0:T(8,128)} gather(f32[12582913,2]"
        "{0,1:T(2,128)S(1)} %param_0.2, s32[2555904]{0:T(1024)} "
        "%transpose.5), offset_dims={1}") == U_CAP_1TB + 1
    assert all(r is None or r < U_CAP_1TB for r in map(gathered_rows, lines))


def test_dense_ftrl_step_reads_w_for_the_pull_alone(v5e):
    """The dense kinds' train step (`_dense_steps`: `coo` here, the same
    `_update` under the mesh kinds), lowered for the chip at the
    headline table: the stored w is the pull's operand and nothing
    else's. The update forms the old weight from z and n, so its sweep
    over the tables reads g, z, n and writes z, n, w, and the |w|_0
    count rides in it; a second reader of `state['w']` would be the
    seventh stream back."""
    from wormhole_tpu.models.linear import LinearConfig, LinearLearner
    from wormhole_tpu.parallel.mesh import make_mesh

    cfg = LinearConfig(minibatch=ROWS, nnz_per_row=NNZ,
                       num_buckets=NB_DENSE, algo="ftrl", lr_eta=0.1,
                       lambda_l1=4.0, kernel="pallas", kernel_dtype="bf16",
                       compact_cap=0)
    lrn = LinearLearner(cfg, make_mesh(1, 1))

    def shaped(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    f32 = jnp.float32
    step = lrn._kinds["coo"].train.lower(
        {k: shaped((NB_DENSE,), f32) for k in lrn.store.state},
        *[shaped(s, d) for s, d in coo_stream(CAP, NB_DENSE)],
        shaped((ROWS,), f32), shaped((ROWS,), f32)).compile()
    readers = [ln for ln in hlo_lines(step)
               if re.search(r"\(.*%state__w__", ln)
               and not ln.startswith("ENTRY")]
    assert len(readers) == 1 and readers[0].startswith("%coo_pull"), readers
    assert progress_outputs(step) == [(8,)]
