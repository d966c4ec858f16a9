"""Decide the sparse-A matvec strategy on TPU at reference-UC shapes.

Candidates for y = A x with A (m, n) ~0.03% dense, batched over S:
  dense   — current (S, n) @ (n, m) matmul against dense A
  coo     — gather + segment_sum (scatter-add) in CSR order
  ell     — hybrid: narrow rows via padded row-wise gather (regular, no
            scatter), wide rows (balance/reserves) via a compact dense
            matmul over the columns they touch
Same for the transpose A' y (columns are uniformly narrow: pure ELL).

Usage: python scripts/profile_sparse_matvec.py [S] [horizon]
"""

import sys
import time

import numpy as np

S = int(sys.argv[1]) if len(sys.argv) > 1 else 1000
horizon = int(sys.argv[2]) if len(sys.argv) > 2 else 24

import jax
import jax.numpy as jnp

import tpusppy
tpusppy.disable_tictoc_output()
from tpusppy.ir import ScenarioBatch
from tpusppy.models import uc_data

DATA = "/root/reference/paperruns/larger_uc/1000scenarios_wind"
names = uc_data.scenario_names_creator(data_dir=DATA)[:4]
kw = {"data_dir": DATA, "horizon": horizon, "relax_integers": False,
      "num_scens": 4}
batch = ScenarioBatch.from_problems(
    [uc_data.scenario_creator(nm, **kw) for nm in names])
A = np.asarray(batch.A_shared)
m, n = A.shape
rows, cols = np.nonzero(A)
vals = A[rows, cols]
nnz = vals.size
row_counts = np.bincount(rows, minlength=m)
col_counts = np.bincount(cols, minlength=n)
print(f"A: ({m}, {n}) nnz={nnz} row nnz p50/p99/max="
      f"{np.percentile(row_counts, 50):.0f}/"
      f"{np.percentile(row_counts, 99):.0f}/{row_counts.max()} "
      f"col nnz p50/max={np.percentile(col_counts, 50):.0f}/"
      f"{col_counts.max()}", flush=True)

dt = jnp.float32
x = jnp.asarray(np.random.default_rng(0).normal(size=(S, n)), dt)
y = jnp.asarray(np.random.default_rng(1).normal(size=(S, m)), dt)
Ad = jnp.asarray(A, dt)


def bench(tag, fn, *args):
    # matrices are ARGUMENTS (closure-captured constants would be baked
    # into the HLO); timing ends with a device->host fetch of the result,
    # which cannot return before the queue has drained
    f = jax.jit(fn)
    out = f(*args)
    np.asarray(jnp.sum(out))
    reps = 20
    t0 = time.time()
    for _ in range(reps):
        out = f(*args)
    np.asarray(jnp.sum(out))
    dt_ms = (time.time() - t0) / reps * 1e3
    print(f"  {tag:28s} {dt_ms:8.2f} ms", flush=True)
    return out, dt_ms


print(f"\nforward A x -> (S={S}, m):", flush=True)
ref, t_dense = bench("dense matmul", lambda xx, Am: xx @ Am.T, x, Ad)

# --- COO / segment-sum --------------------------------------------------
order = np.lexsort((cols, rows))
r_s, c_s, v_s = rows[order], cols[order], vals[order]
rj = jnp.asarray(r_s, jnp.int32)
cj = jnp.asarray(c_s, jnp.int32)
vj = jnp.asarray(v_s, dt)


def coo_matvec(xx, cjj, vjj, rjj):
    g = xx[:, cjj] * vjj[None, :]
    return jax.ops.segment_sum(g.T, rjj, num_segments=m,
                               indices_are_sorted=True).T


out, t_coo = bench("coo segment_sum", coo_matvec, x, cj, vj, rj)
print(f"    coo relerr {float(jnp.abs(out - ref).max() / jnp.abs(ref).max()):.2e}")

# --- hybrid ELL + dense wide rows --------------------------------------
K_ELL = 8
narrow = row_counts <= K_ELL
wide = ~narrow
print(f"    narrow rows {narrow.sum()} (k<={K_ELL}), wide {wide.sum()} "
      f"touching {np.unique(cols[np.isin(rows, np.flatnonzero(wide))]).size}"
      f" cols")
ell_cols = np.zeros((m, K_ELL), np.int32)
ell_vals = np.zeros((m, K_ELL), np.float64)
for r in np.flatnonzero(narrow):
    mask = rows == r
    k = mask.sum()
    ell_cols[r, :k] = cols[mask]
    ell_vals[r, :k] = vals[mask]
ec = jnp.asarray(ell_cols)
ev = jnp.asarray(ell_vals, dt)
Aw = jnp.asarray(A[wide], dt)          # (mw, n) dense wide rows
widx = jnp.asarray(np.flatnonzero(wide), jnp.int32)


def ell_matvec(xx, ecc, evv, Aww, wii):
    out = jnp.einsum("smk,mk->sm", xx[:, ecc], evv)
    return out.at[:, wii].set(xx @ Aww.T)


out, t_ell = bench("ell + dense wide", ell_matvec, x, ec, ev, Aw, widx)
print(f"    ell relerr {float(jnp.abs(out - ref).max() / jnp.abs(ref).max()):.2e}")

print(f"\ntranspose A' y -> (S={S}, n):", flush=True)
refT, tT_dense = bench("dense matmul", lambda yy, Am: yy @ Am, y, Ad)

orderT = np.lexsort((rows, cols))
rT = jnp.asarray(rows[orderT], jnp.int32)
cT = jnp.asarray(cols[orderT], jnp.int32)
vT = jnp.asarray(vals[orderT], dt)


def coo_rmatvec(yy, rTT, vTT, cTT):
    g = yy[:, rTT] * vTT[None, :]
    return jax.ops.segment_sum(g.T, cTT, num_segments=n,
                               indices_are_sorted=True).T


out, tT_coo = bench("coo segment_sum", coo_rmatvec, y, rT, vT, cT)
print(f"    coo relerr {float(jnp.abs(out - refT).max() / jnp.abs(refT).max()):.2e}")

KT = int(col_counts.max())
ellT_rows = np.zeros((n, KT), np.int32)
ellT_vals = np.zeros((n, KT), np.float64)
fill = np.zeros(n, np.int32)
for idx in range(nnz):
    c = cols[idx]
    ellT_rows[c, fill[c]] = rows[idx]
    ellT_vals[c, fill[c]] = vals[idx]
    fill[c] += 1
erT = jnp.asarray(ellT_rows)
evT = jnp.asarray(ellT_vals, dt)


def ell_rmatvec(yy, err, evv):
    return jnp.einsum("snk,nk->sn", yy[:, err], evv)


out, tT_ell = bench(f"ell (k={KT})", ell_rmatvec, y, erT, evT)
print(f"    ell relerr {float(jnp.abs(out - refT).max() / jnp.abs(refT).max()):.2e}")

print(f"\nspeedups: fwd coo {t_dense/t_coo:.1f}x ell {t_dense/t_ell:.1f}x; "
      f"transpose coo {tT_dense/tT_coo:.1f}x ell {tT_dense/tT_ell:.1f}x",
      flush=True)
