#!/usr/bin/env python
"""Per-component profile of the DiFacto FM training step at the bench
shape (PERF.md's component table). Each component is timed with the
two-point chained method: a jitted wrapper threads a scalar from the
previous output into the next input, so the calls form one dependent
chain on the device. Run on the TPU (default env); ~2 min.

Usage: python tools/profile_difacto.py [steps]
"""

import sys
import time
import types

import numpy as np

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp

import bench
from wormhole_tpu.models.difacto import DifactoConfig, DifactoLearner
from wormhole_tpu.ops import coo_kernels as ck
from wormhole_tpu.parallel.mesh import make_mesh

STEPS = int(sys.argv[1]) if len(sys.argv) > 1 else 10
MB = 1 << 16


def main():
    cfg = DifactoConfig(
        minibatch=MB, num_buckets=1 << 22, v_buckets=1 << 20,
        nnz_per_row=len(bench.FIELD_CARDS), dim=8, threshold=2,
        lr_eta=0.1, lambda_l1=1.0, kernel_dtype="bf16")
    lrn = DifactoLearner(cfg, make_mesh(num_data=1, num_model=1))
    rng = np.random.default_rng(1)
    seg, idx, val, label, mask = bench.synth_criteo_batch(
        rng, MB, cfg.num_buckets)
    db = types.SimpleNamespace(seg=seg, idx=idx, val=val)
    pk = lrn._pack_fm(db, train=True)
    args = [jax.device_put(jnp.asarray(a)) for a in
            lrn._fm_args(pk, label, mask, train=True)]
    (uniq_w, wtm, wfi, wla, wcnts, widx, wseg, wval, wtmap, wfirst,
     uniq_v, vtm, vfi, vla, vtouched, vidx, vseg, vval, vtmap, vfirst,
     rm_slot, rm_wval, rm_vval, vslot_w, labelj, maskj) = args
    uw_cap, uv_cap = lrn._fm_caps
    dt = jnp.bfloat16
    dim = cfg.dim

    nblk_w = int(wtmap.shape[0])
    nblk_vcoo = int(vtmap.shape[0])
    nblk_uw = int(wtm.shape[0])
    nblk_uv = int(vtm.shape[0])
    print(f"uw_cap={uw_cap} uv_cap={uv_cap} BLK_U={ck.BLK_U} "
          f"blocks: wcoo={nblk_w} vcoo={nblk_vcoo} "
          f"uw={nblk_uw} uv={nblk_uv} nnz={len(idx)}")

    from wormhole_tpu.ops.fused_update import (row_tile_gather,
                                               scatter_update,
                                               v_scatter_update)

    state = dict(lrn.store.state)
    vstate = dict(lrn.vstore.state)
    w2 = state["w"].reshape(-1, ck.LANES)
    V2 = vstate["V"].reshape(-1, ck.LANES)

    wc = ck.tile_gather(w2, uniq_w, wtm, dtype=dt)
    Vc = row_tile_gather(V2, uniq_v, vtm, dim, dtype=dt)
    d = jnp.ones((MB,), jnp.float32) * 0.1
    xv = jnp.ones((MB, dim), jnp.float32) * 0.05
    xvd = jnp.concatenate([xv, d[:, None]],
                          axis=1).astype(dt)  # bf16 wire (r5)
    G = jnp.take(xvd, vseg, axis=0)
    c = G[:, dim].astype(jnp.float32) * vval
    a = c[:, None] * G[:, :dim]
    b = c * vval
    gV = ck.fm_push_contrib(Vc, a, b, vidx, vtmap, vfirst, dtype=dt)
    gw = ck.coo_spmv_t(d, widx, wseg, wval, wtmap, wfirst, uw_cap,
                       dtype=dt)
    Vcz = jnp.concatenate([Vc.astype(dt), jnp.zeros((1, dim), dt)], 0)

    def timed(name, fn, *xs):
        """fn(eps, *xs) -> scalar; chained via eps."""
        f = jax.jit(fn)

        def chain(n):
            eps = jnp.float32(0.0)
            for _ in range(n):
                eps = f(eps * 1e-30, *xs)
            float(eps)

        chain(3)
        t0 = time.perf_counter()
        chain(STEPS)
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        chain(3 * STEPS)
        t2 = time.perf_counter() - t0
        ms = max(t2 - t1, 1e-9) / (2 * STEPS) * 1e3
        print(f"{name:28s} {ms:7.2f} ms")
        return ms

    timed("tile_gather wc", lambda e, w2: jnp.sum(ck.tile_gather(
        w2 + e, uniq_w, wtm, dtype=dt)), w2)
    timed("row_tile_gather Vc", lambda e, V2: jnp.sum(row_tile_gather(
        V2 + e, uniq_v, vtm, dim, dtype=dt)), V2)
    def u_build(e, Vcz, wc):
        U = jnp.concatenate([jnp.take(Vcz + e.astype(Vcz.dtype),
                                      vslot_w, axis=0),
                             wc[:, None]], axis=1)
        return jnp.sum(U[:64])

    timed("U build (vslot take)", u_build, Vcz, wc)

    Uz = jnp.concatenate(
        [jnp.take(Vcz, vslot_w, axis=0), wc[:, None]], axis=1)
    Uz = jnp.concatenate([Uz, jnp.zeros((1, dim + 1), Uz.dtype)], axis=0)

    def u_take(e, Uz):
        U_nnz = jnp.take(Uz + e.astype(Uz.dtype), rm_slot, axis=0)
        xw = (rm_wval * U_nnz[:, dim]).reshape(MB, -1).sum(1)
        pv = rm_vval[:, None] * U_nnz[:, :dim]
        xv = pv.reshape(MB, -1, dim).sum(1)
        x2 = (pv * pv).reshape(MB, -1, dim).sum(1)
        return jnp.sum(xw) + jnp.sum(xv) + jnp.sum(x2)

    timed("U take + reduces", u_take, Uz)
    timed("coo_spmv_t gw", lambda e, d: jnp.sum(ck.coo_spmv_t(
        d + e, widx, wseg, wval, wtmap, wfirst, uw_cap, dtype=dt)), d)
    timed("xvd take (G)", lambda e, xvd: jnp.sum(jnp.take(
        xvd + e, vseg, axis=0)), xvd)
    timed("fm_push_contrib gV", lambda e, a: jnp.sum(ck.fm_push_contrib(
        Vc, a + e, b, vidx, vtmap, vfirst, dtype=dt)), a)

    def vsc(e, gV):
        Vn, nVn = v_scatter_update(
            vstate["V"], vstate["nV"], gV + e, vtouched,
            uniq_v, vtm, vfi, vla, dim=dim, V_lr_eta=cfg.V_lr_eta,
            V_lr_beta=cfg.V_lr_beta, lambda_V=cfg.lambda_V, dtype=dt)
        return jnp.sum(Vn[:8]) + jnp.sum(nVn[:8])

    timed("v_scatter_update", vsc, gV)

    def ftrl(e, gw):
        ns, nw = scatter_update(
            "ftrl", state, gw + e, uniq_w, wtm, wfi, wla,
            lr_eta=cfg.lr_eta, lr_beta=cfg.lr_beta,
            lambda_l1=cfg.lambda_l1, lambda_l2=cfg.lambda_l2,
            fixed_bytes=cfg.fixed_bytes, dtype=dt,
            add_table="cnt", add_values=wcnts)
        return jnp.sum(ns["w"][:8]) + jnp.sum(nw)

    timed("scatter_update ftrl+cnt", ftrl, gw)

    # full step for reference
    step = lrn._fm_steps[0]

    def full(n):
        st, vt = lrn.store.state, lrn.vstore.state
        prog = None
        for i in range(n):
            lrn._rng, sub = jax.random.split(lrn._rng)
            st, vt, prog = step(st, vt, *args, sub)
        float(prog["objv"])
        # the step donates state buffers: rebind so the next chain
        # doesn't feed already-donated arrays (TPU InvalidArgument)
        lrn.store.state, lrn.vstore.state = st, vt

    full(3)
    t0 = time.perf_counter()
    full(STEPS)
    t1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    full(3 * STEPS)
    t2 = time.perf_counter() - t0
    ms = max(t2 - t1, 1e-9) / (2 * STEPS) * 1e3
    print(f"{'FULL train_fm step':28s} {ms:7.2f} ms   "
          f"({MB / ms * 1e3 / 1e3:.0f}k ex/s)")


if __name__ == "__main__":
    main()
