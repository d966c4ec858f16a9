"""Pallas TPU kernel: fused ADMM sweep block.

The ADMM inner loop is bandwidth-bound: every sweep re-reads the (S, n, n)
K-inverse/K pair and the (S, m, n) constraint matrix from HBM (three to five
matrix passes per sweep).  This kernel runs ``n_sweeps`` sweeps over a block
of scenarios with all matrices resident in VMEM, so HBM sees each matrix once
per kernel call instead of once per sweep — the hot-op fusion the build brief
calls for (SURVEY §7 step 2; the XLA einsum path remains the fallback for
CPU, dense-P, and shapes that exceed the VMEM budget).

All contractions are per-scenario matvecs with tiny n/m (tens), so the VPU
multiply-reduce form ``(M * v[:, None, :]).sum(-1)`` is used rather than MXU
dots (the 128-lane MXU tiles would be mostly padding at these sizes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM budget for one scenario block's matrices (bytes).  v5e has ~16 MB of
# scoped VMEM per core, and the measured end-to-end footprint is ~5x the
# naive single-block byte count (Mosaic double-buffers inputs AND outputs
# for the grid pipeline, plus scratch): a block sized to 4.15 MB of
# operands compiled to a 20.7 MB scoped allocation (S=10000, n=11).  3 MB
# keeps the real footprint ~14-15 MB worst case while preserving bs=128 at
# the farmer bench shape (n=44), where the kernel measures 2.0x XLA.
_VMEM_BUDGET = 3 * 1024 * 1024


def sweep_block_size(S, m, n, itemsize=4, precision="highest") -> int:
    """Scenarios per grid step so A/Kinv/K (+vectors) fit in VMEM.

    ``precision="default"`` stores A/At/Kinv in bf16 (half the bytes —
    the mixed-precision sweep mode's VMEM dividend; K stays f32, it is
    the refinement-defect operand)."""
    if precision == "default":
        mat = (m * n + n * n) * 2 + n * n * itemsize
    else:
        mat = (m * n + 2 * n * n) * itemsize
    per_scen = mat + (6 * n + 6 * m) * itemsize
    bs = max(1, _VMEM_BUDGET // max(per_scen, 1))
    return int(min(S, bs))


def _sweeps_kernel(q_ref, A_ref, At_ref, Kinv_ref, K_ref, cl_ref, cu_ref,
                   lb_ref, ub_ref, rho_a_ref, rho_x_ref, x_ref, z_ref,
                   zx_ref, y_ref, yx_ref, Ax_ref, x_out, z_out, zx_out,
                   y_out, yx_out, Ax_out, *, n_sweeps, n_refine, sigma,
                   alpha, m, n, precision):
    """Scenario-on-lanes layout: every tensor is (..., Sb) with the scenario
    block on the 128-lane axis, so each matvec step is a full-width VPU
    multiply-accumulate.  Contractions loop over the LEADING (untiled) dim
    with static Python indices (m, n are small trace-time constants):

      A'(v):  out[j] += A[i, j, :] * v[i, :]   via A (m, n, Sb), loop i<m
      A x:    out[i] += At[j, i, :] * x[j, :]  via At (n, m, Sb), loop j<n
      K^-1 r: sym matrix, loop over rows.

    ``precision``: "default" takes A/At/Kinv in bf16 storage and rounds
    the vector operand of each sweep contraction to bf16 — matching the
    XLA mixed-precision sweep emulation (solvers/precision.py), with the
    refinement defect against the f32 K exact.  Every other mode runs the
    exact f32 path (the VPU has no MXU passes to economize, so "high"
    here is simply full f32 — at least as accurate as bf16x3 asks)."""
    dt = K_ref.dtype
    # matrices stay in their STORAGE dtype (bf16 under "default" — that is
    # the VMEM dividend); upcasts happen per leading-dim slice inside the
    # contraction, so no full f32 copy of A/At/Kinv is ever materialized
    A = A_ref[:]          # (m, n, Sb)
    At = At_ref[:]        # (n, m, Sb)
    Kinv = Kinv_ref[:]    # (n, n, Sb)
    K = K_ref[:]
    q = q_ref[:]          # (n, Sb)
    cl, cu, lb, ub = cl_ref[:], cu_ref[:], lb_ref[:], ub_ref[:]
    rho_a, rho_x = rho_a_ref[:], rho_x_ref[:]
    x, z, zx, y, yx, Ax = (x_ref[:], z_ref[:], zx_ref[:], y_ref[:],
                           yx_ref[:], Ax_ref[:])
    lowered = precision == "default"

    def rnd(v):
        """bf16 input rounding of the vector operand (lowered mode only)."""
        return v.astype(jnp.bfloat16).astype(dt) if lowered else v

    def contract(M, v, rows):
        """out[k, :] = sum_i M[i, k, :] * v[i, :] (loop over leading dim;
        per-slice upcast of bf16-stored matrices)."""
        acc = M[0].astype(dt) * v[0][None, :]
        for i in range(1, rows):
            acc = acc + M[i].astype(dt) * v[i][None, :]
        return acc

    def body(_, carry):
        x, z, zx, y, yx, Ax = carry
        rhs = (sigma * x - q + contract(A, rnd(rho_a * z - y), m)
               + (rho_x * zx - yx))
        xt = contract(Kinv, rnd(rhs), n)      # Kinv symmetric
        for _ in range(n_refine):
            r = rhs - contract(K, xt, n)      # defect: exact f32 K
            xt = xt + contract(Kinv, rnd(r), n)
        Axt = contract(At, rnd(xt), n)
        x_new = alpha * xt + (1 - alpha) * x
        Ax_new = alpha * Axt + (1 - alpha) * Ax

        za_arg = alpha * Axt + (1 - alpha) * z + y / rho_a
        z_new = jnp.clip(za_arg, cl, cu)
        y_new = y + rho_a * (alpha * Axt + (1 - alpha) * z - z_new)

        zx_arg = alpha * xt + (1 - alpha) * zx + yx / rho_x
        zx_new = jnp.clip(zx_arg, lb, ub)
        yx_new = yx + rho_x * (alpha * xt + (1 - alpha) * zx - zx_new)
        return x_new, z_new, zx_new, y_new, yx_new, Ax_new

    x, z, zx, y, yx, Ax = jax.lax.fori_loop(
        0, n_sweeps, body, (x, z, zx, y, yx, Ax))
    x_out[:] = x
    z_out[:] = z
    zx_out[:] = zx
    y_out[:] = y
    yx_out[:] = yx
    Ax_out[:] = Ax


@functools.partial(jax.jit,
                   static_argnames=("n_sweeps", "n_refine", "sigma", "alpha",
                                    "bs", "precision", "interpret"))
def fused_sweeps(q, A, At, Kinv, K, cl, cu, lb, ub, rho_a, rho_x,
                 x, z, zx, y, yx, Ax, n_sweeps, n_refine, sigma, alpha, bs,
                 precision="highest", interpret=False):
    """Run ``n_sweeps`` sweeps; ALL arrays in scenario-last layout
    (m,n,S)/(n,S) etc.  Returns transposed-state (x, z, zx, y, yx, Ax).

    ``precision="default"`` is the mixed-precision sweep mode: pass
    A/At/Kinv in bf16 (callers cast; K stays f32 for exact defects) —
    VMEM per scenario nearly halves, so blocks grow and fewer grid steps
    re-stream HBM.  "high"/"highest" run the exact f32 kernel (see
    ``_sweeps_kernel``).

    ``interpret=True`` runs the kernel through the Pallas interpreter —
    platform-independent, used by the CPU correctness tests
    (tests/test_pallas.py) to pin the kernel to the XLA sweep semantics."""
    m, n, S = A.shape
    grid = ((S + bs - 1) // bs,)

    def spec3(d0, d1):
        return pl.BlockSpec((d0, d1, bs), lambda i: (0, 0, i),
                            memory_space=pltpu.VMEM)

    def spec2(d0):
        return pl.BlockSpec((d0, bs), lambda i: (0, i),
                            memory_space=pltpu.VMEM)

    kern = functools.partial(_sweeps_kernel, n_sweeps=n_sweeps,
                             n_refine=n_refine, sigma=sigma, alpha=alpha,
                             m=m, n=n, precision=precision)
    dt = K.dtype
    out_shape = [
        jax.ShapeDtypeStruct((n, S), dt),   # x
        jax.ShapeDtypeStruct((m, S), dt),   # z
        jax.ShapeDtypeStruct((n, S), dt),   # zx
        jax.ShapeDtypeStruct((m, S), dt),   # y
        jax.ShapeDtypeStruct((n, S), dt),   # yx
        jax.ShapeDtypeStruct((m, S), dt),   # Ax
    ]
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            spec2(n),            # q
            spec3(m, n),         # A
            spec3(n, m),         # At
            spec3(n, n),         # Kinv
            spec3(n, n),         # K
            spec2(m), spec2(m),  # cl cu
            spec2(n), spec2(n),  # lb ub
            spec2(m), spec2(n),  # rho_a rho_x
            spec2(n), spec2(m), spec2(n), spec2(m), spec2(n),  # x z zx y yx
            spec2(m),            # Ax
        ],
        out_specs=[spec2(n), spec2(m), spec2(n), spec2(m), spec2(n),
                   spec2(m)],
        out_shape=out_shape,
        interpret=interpret,
    )(q, A, At, Kinv, K, cl, cu, lb, ub, rho_a, rho_x, x, z, zx, y, yx, Ax)


def usable(S, m, n, platform=None, P=None, precision="highest") -> int | None:
    """Block size if the fused per-scenario kernel applies, else None.

    ``precision="default"`` widens the applicable range: bf16 matrix
    storage halves the per-scenario VMEM, so larger (m, n) still fit."""
    if P is not None:
        return None
    platform = platform or jax.default_backend()
    if platform != "tpu":
        return None
    budget = sweep_block_size(S, m, n, precision=precision)
    if budget >= S:
        return S          # one block covering the whole (lane) dimension
    # the lane-dim block must be a multiple of 128 (Mosaic tiling); the grid
    # uses ceiling division, so S need not divide evenly
    bs = (budget // 128) * 128
    return bs if bs >= 128 else None


# --------------------------------------------------------------------------
# Batched dense elimination, scenario on the lanes
# --------------------------------------------------------------------------
#
# XLA:TPU expands LuDecomposition / Cholesky / TriangularSolve on a batch of
# small matrices into one dependent step per column over arrays laid out
# batch-outermost: the refresh solve's nine (n+m)-sized polish LUs cost far
# more than their few 1e8 flops (PERF.md section 5).  This kernel does the
# same arithmetic in the layout of ``fused_sweeps``: a block of
# scenarios' systems stays in VMEM from the first pivot to the last
# back-substitution, every step one full-width VPU pass with the scenario on
# the lanes, a grid over blocks.


def _lanes_solve_kernel(M_ref, rhs_ref, x_ref, W_ref, *, N):
    """Gaussian elimination with partial pivoting on the augmented block
    [M | rhs], then back-substitution.  ``M_ref`` (N, N, Sb): row on the
    leading (untiled) axis, column on the sublanes, scenario on the lanes;
    ``rhs_ref``/``x_ref`` (N, R, Sb); ``W_ref`` the working copy of M.

    The pivot of column k is the entry of largest magnitude at or below
    the diagonal, the first such on ties (the rule of
    ``jnp.linalg.solve``'s LU).  It differs per lane, so one scan down the
    rows keeps the running best row by selects, and a second scan
    eliminates, handing the displaced row k to the lane's pivot row as it
    passes: no gather and no per-scenario control flow.  Columns left of
    ``k``'s sublane tile are never read again and are not updated.  A zero
    pivot divides by zero: non-finite values on that lane only."""
    W_ref[...] = M_ref[...]
    x_ref[...] = rhs_ref[...]

    def column(i, k):
        return W_ref[i, pl.ds(k, 1), :]

    # the pivot columns one sublane tile at a time, so that the slab a row
    # operation touches is a static slice; within a tile the column is a
    # loop index (an unrolled step per column multiplies the time to trace
    # and lower the kernel by N)
    for c0 in range(0, N - 1, 8):

        def step(k, _, c0=c0):
            row_k = W_ref[k, c0:, :]
            rhs_k = x_ref[k]
            a_kk = column(k, k)

            def search(i, carry):
                best, piv, idx, prow, prhs = carry
                a = column(i, k)
                better = jnp.abs(a) > best
                return (jnp.where(better, jnp.abs(a), best),
                        jnp.where(better, a, piv),
                        jnp.where(better, i, idx),
                        jnp.where(better, W_ref[i, c0:, :], prow),
                        jnp.where(better, x_ref[i], prhs))

            _, piv, idx, prow, prhs = jax.lax.fori_loop(
                k + 1, N, search,
                (jnp.abs(a_kk), a_kk, jnp.full(a_kk.shape, k, jnp.int32),
                 row_k, rhs_k))

            def eliminate(i, _):
                took = idx == i      # this lane's pivot came from row i
                f = jnp.where(took, a_kk, column(i, k)) / piv
                W_ref[i, c0:, :] = (jnp.where(took, row_k, W_ref[i, c0:, :])
                                    - f * prow)
                x_ref[i] = jnp.where(took, rhs_k, x_ref[i]) - f * prhs
                return 0

            jax.lax.fori_loop(k + 1, N, eliminate, 0)
            W_ref[k, c0:, :] = prow
            x_ref[k] = prhs
            return 0

        jax.lax.fori_loop(c0, min(c0 + 8, N - 1), step, 0)

    def back(t, _):
        j = N - 1 - t
        xj = x_ref[j] / column(j, j)
        x_ref[j] = xj

        def substitute(i, _):
            x_ref[i] = x_ref[i] - column(i, j) * xj
            return 0

        jax.lax.fori_loop(0, j, substitute, 0)
        return 0

    jax.lax.fori_loop(0, N, back, 0)


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def lanes_solve(M, rhs, *, bs, interpret=False):
    """Solve ``M[:, :, s] x = rhs[:, :, s]`` for every scenario ``s``.

    ``M`` (N, N, S) and ``rhs`` (N, R, S), scenario last: R = 1 for one
    right-hand side, R = N with the identity for an inverse.  Returns x
    (N, R, S).  ``bs`` scenarios a grid step (:func:`usable_solve`); a
    ragged last block computes on padding that is never written back."""
    N, R, S = rhs.shape
    spec = lambda d1: pl.BlockSpec((N, d1, bs), lambda i: (0, 0, i),
                                   memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_lanes_solve_kernel, N=N),
        grid=((S + bs - 1) // bs,),
        in_specs=[spec(N), spec(R)],
        out_specs=spec(R),
        out_shape=jax.ShapeDtypeStruct((N, R, S), M.dtype),
        scratch_shapes=[pltpu.VMEM((N, N, bs), M.dtype)],
        interpret=interpret,
    )(M, rhs)


def usable_solve(S, N, R, platform=None, dtype=jnp.float32) -> int | None:
    """Block size if :func:`lanes_solve` applies, else None: float32 on
    the TPU, a batch that fills the 128 lanes, and 128 scenarios' systems
    inside the VMEM budget (the kernel holds M three times: Mosaic's two
    input buffers and the working copy)."""
    platform = platform or jax.default_backend()
    if platform != "tpu" or jnp.dtype(dtype) != jnp.float32 or S < 128:
        return None
    per_scen = (N * N + 2 * N * R) * 4
    return 128 if 128 * per_scen <= _VMEM_BUDGET else None


# --------------------------------------------------------------------------
# Fused shared-A sweep kernel (the frozen shared-engine fast path)
# --------------------------------------------------------------------------
#
# The shared-A engine (solvers/shared_admm) keeps ONE (m, n) constraint
# matrix and ONE (n, n) KKT inverse for the whole scenario batch; its sweep
# contractions are genuine (Sb, k) @ (k, j) MXU matmuls — exactly where
# lowered matmul precision pays (1/3/6 bf16 passes per f32 multiply-add).
# This kernel runs a whole ``check_every`` sweep block per call with the
# shared matrices VMEM-resident (constant index_map: Mosaic keeps revisited
# blocks in place) and the scenario block on the SUBLANE axis, and applies
# the precision mode with explicit bf16 operand splits — identical
# semantics under Mosaic and the interpreter, so the CPU parity tests pin
# it to the XLA mixed-precision sweep (solvers/precision.py emulation).


def _prep_mat(M, mode):
    """(M1, M2) bf16 expansion of a matrix for ``mode`` ("highest": the
    matrix itself, no split).  Splits go THROUGH f32 — exactly the
    rounding chain of precision.contract's emulation (and a no-op on the
    f32 arrays real TPU runs carry), so interpret-mode parity with the
    XLA mixed-precision path is exact up to summation order."""
    if mode == "highest":
        return (M, None)
    Mf = M.astype(jnp.float32)
    M1 = Mf.astype(jnp.bfloat16)
    if mode == "default":
        return (M1, None)
    return (M1, (Mf - M1.astype(jnp.float32)).astype(jnp.bfloat16))


def _pdot(u, Msplit, mode, dt, transpose=False):
    """u @ M (or u @ M.T) at ``mode``; u is rounded/split per call, M is
    pre-split by :func:`_prep_mat`."""
    dn = (((1,), (1 if transpose else 0,)), ((), ()))
    d = functools.partial(jax.lax.dot_general, dimension_numbers=dn,
                          preferred_element_type=dt)
    M1, M2 = Msplit
    if mode == "highest":
        return d(u, M1, precision=jax.lax.Precision.HIGHEST)
    uf = u.astype(jnp.float32)
    u1 = uf.astype(jnp.bfloat16)
    if mode == "default":
        return d(u1, M1)
    u2 = (uf - u1.astype(jnp.float32)).astype(jnp.bfloat16)
    return d(u1, M1) + d(u1, M2) + d(u2, M1)


def _shared_sweeps_kernel(q_ref, A_ref, Kinv_ref, K_ref, cl_ref, cu_ref,
                          lb_ref, ub_ref, rho_a_ref, rho_x_ref, dq2_ref,
                          has_ref, gamma_ref, x_ref, z_ref, zx_ref, y_ref,
                          yx_ref, Ax_ref, x_out, z_out, zx_out, y_out,
                          yx_out, Ax_out, *, n_sweeps, n_refine, n_extra,
                          sigma, alpha, precision):
    """One ``n_sweeps`` block of the shared-A frozen sweep (the exact
    semantics of ``shared_admm._core``'s block(): per-scenario gamma
    scaling, dq2 refinement against the exact f32 K with the lax.cond
    extra passes reproduced as a global-``has`` select)."""
    dt = K_ref.dtype
    A = _prep_mat(A_ref[:], precision)          # (m, n)
    Kinv = _prep_mat(Kinv_ref[:], precision)    # (n, n)
    K = K_ref[:]                                # exact, defect operand
    q = q_ref[:]                                # (Sb, n)
    cl, cu, lb, ub = cl_ref[:], cu_ref[:], lb_ref[:], ub_ref[:]
    g = gamma_ref[:]                            # (Sb, 1)
    has = has_ref[0, 0]                         # global any(dq2 != 0)
    dq2 = dq2_ref[:]                            # (Sb, n)
    sigma_s = g * sigma
    rho_a_s = g * rho_a_ref[:]                  # (Sb, m)
    rho_x_s = g * rho_x_ref[:]                  # (Sb, n)
    x, z, zx, y, yx, Ax = (x_ref[:], z_ref[:], zx_ref[:], y_ref[:],
                           yx_ref[:], Ax_ref[:])

    def kdefect(rhs, xt):
        # exact per-scenario system defect at full f32 (the refinement's
        # accuracy anchor — never lowered)
        Kx = jax.lax.dot_general(
            xt, K, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=dt)
        return rhs - (g * Kx + dq2 * xt)

    def body(_, carry):
        x, z, zx, y, yx, Ax = carry
        rhs = (sigma_s * x - q + _pdot(rho_a_s * z - y, A, precision, dt)
               + (rho_x_s * zx - yx))
        xt = _pdot(rhs / g, Kinv, precision, dt)
        for _ in range(n_refine):
            xt = xt + _pdot(kdefect(rhs, xt) / g, Kinv, precision, dt)
        for _ in range(n_extra):
            xt2 = xt + _pdot(kdefect(rhs, xt) / g, Kinv, precision, dt)
            xt = jnp.where(has > 0, xt2, xt)
        Axt = _pdot(xt, A, precision, dt, transpose=True)
        x_new = alpha * xt + (1 - alpha) * x
        Ax_new = alpha * Axt + (1 - alpha) * Ax

        za_arg = alpha * Axt + (1 - alpha) * z + y / rho_a_s
        z_new = jnp.clip(za_arg, cl, cu)
        y_new = y + rho_a_s * (alpha * Axt + (1 - alpha) * z - z_new)

        zx_arg = alpha * xt + (1 - alpha) * zx + yx / rho_x_s
        zx_new = jnp.clip(zx_arg, lb, ub)
        yx_new = yx + rho_x_s * (alpha * xt + (1 - alpha) * zx - zx_new)
        return x_new, z_new, zx_new, y_new, yx_new, Ax_new

    x, z, zx, y, yx, Ax = jax.lax.fori_loop(
        0, n_sweeps, body, (x, z, zx, y, yx, Ax))
    x_out[:] = x
    z_out[:] = z
    zx_out[:] = zx
    y_out[:] = y
    yx_out[:] = yx
    Ax_out[:] = Ax


@functools.partial(jax.jit,
                   static_argnames=("n_sweeps", "n_refine", "n_extra",
                                    "sigma", "alpha", "bs", "precision",
                                    "interpret"))
def fused_sweeps_shared(q, A, Kinv, K, cl, cu, lb, ub, rho_a, rho_x, dq2,
                        has_dq2, gamma, x, z, zx, y, yx, Ax, n_sweeps,
                        n_refine, n_extra, sigma, alpha, bs,
                        precision="highest", interpret=False):
    """``n_sweeps`` shared-A frozen sweeps per call, scenario-blocked on
    the sublane axis.  Shapes: A/Kinv/K shared ((m,n)/(n,n)/(n,n)); rho_a
    (1, m), rho_x (1, n); per-scenario state/bounds (S, m)/(S, n); gamma
    (S, 1); dq2 (S, n); has_dq2 (1, 1) — the traced global
    ``any(dq2 != 0)`` flag that reproduces the XLA path's lax.cond.
    Returns (x, z, zx, y, yx, Ax)."""
    S, n = q.shape
    m = cl.shape[1]
    grid = ((S + bs - 1) // bs,)

    def shared2(d0, d1):
        return pl.BlockSpec((d0, d1), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)

    def scen(d1):
        return pl.BlockSpec((bs, d1), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)

    kern = functools.partial(_shared_sweeps_kernel, n_sweeps=n_sweeps,
                             n_refine=n_refine, n_extra=n_extra,
                             sigma=sigma, alpha=alpha, precision=precision)
    dt = K.dtype
    out_shape = [
        jax.ShapeDtypeStruct((S, n), dt),   # x
        jax.ShapeDtypeStruct((S, m), dt),   # z
        jax.ShapeDtypeStruct((S, n), dt),   # zx
        jax.ShapeDtypeStruct((S, m), dt),   # y
        jax.ShapeDtypeStruct((S, n), dt),   # yx
        jax.ShapeDtypeStruct((S, m), dt),   # Ax
    ]
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            scen(n),             # q
            shared2(m, n),       # A
            shared2(n, n),       # Kinv
            shared2(n, n),       # K
            scen(m), scen(m),    # cl cu
            scen(n), scen(n),    # lb ub
            shared2(1, m),       # rho_a
            shared2(1, n),       # rho_x
            scen(n),             # dq2
            shared2(1, 1),       # has_dq2
            scen(1),             # gamma
            scen(n), scen(m), scen(n), scen(m), scen(n),  # x z zx y yx
            scen(m),             # Ax
        ],
        out_specs=[scen(n), scen(m), scen(n), scen(m), scen(n), scen(m)],
        out_shape=out_shape,
        interpret=interpret,
    )(q, A, Kinv, K, cl, cu, lb, ub, rho_a, rho_x, dq2, has_dq2, gamma,
      x, z, zx, y, yx, Ax)


# --------------------------------------------------------------------------
# Fused SPARSE/structured-KKT shared-A sweep kernel
# --------------------------------------------------------------------------
#
# Extends the fused-sweep coverage to the SparseA engines (gather/
# segment-sum matvecs, dense-Kinv or block/Woodbury x-update) so those
# paths can participate in the fused body (megastep scans included).  The
# constraint matvecs run in padded-ELL form (:class:`~tpusppy.solvers.
# sparse.EllA`): kr/kc static multiply-accumulate steps per matvec, each a
# full-width gather of the scenario block — matching the XLA engine's
# "sparse matvecs are exact VPU work" contract (only the Kinv applies run
# at the lowered precision mode; the refinement defect is matrix-free
# through the ELL arrays at full precision, exactly the
# ``shared_admm._solve_shared_K`` split).  The structured-KKT engine
# participates through a DENSIFIED (n, n) K^-1 operand: at kernel-eligible
# sizes (the shared matrices must fit VMEM) the BlockWoodbury memory
# saving is irrelevant, so the caller materializes ``kinv_apply(bw, I)``
# once per refresh and the kernel stays one code path.


def _ell_mv(cols, vals, x, k):
    """A x in ELL row form: out[:, i] = sum_j vals[i, j] * x[:, cols[i, j]]
    (k static; padded slots are col 0 / val 0 — inert)."""
    acc = jnp.take(x, cols[:, 0], axis=1) * vals[:, 0][None, :]
    for j in range(1, k):
        acc = acc + jnp.take(x, cols[:, j], axis=1) * vals[:, j][None, :]
    return acc


def _sparse_sweeps_kernel(q_ref, rc_ref, rv_ref, cr_ref, cv_ref, Kinv_ref,
                          diagK_ref, cl_ref, cu_ref, lb_ref, ub_ref,
                          rho_a_ref, rho_x_ref, dq2_ref, has_ref,
                          gamma_ref, x_ref, z_ref, zx_ref, y_ref, yx_ref,
                          Ax_ref, x_out, z_out, zx_out, y_out, yx_out,
                          Ax_out, *, n_sweeps, n_refine, n_extra, sigma,
                          alpha, precision):
    """One ``n_sweeps`` block of the sparse shared-A frozen sweep — the
    exact semantics of ``shared_admm._core``'s block() on a SparseA:
    per-scenario gamma scaling, EXACT ELL matvecs, lowered Kinv applies,
    matrix-free dq2 refinement defect with the lax.cond extra passes
    reproduced as a global-``has`` select."""
    dt = Kinv_ref.dtype
    rc, rv = rc_ref[:], rv_ref[:]           # (m, kr)
    cr, cv = cr_ref[:], cv_ref[:]           # (n, kc)
    kr, kc = rc.shape[1], cr.shape[1]
    Kinv = _prep_mat(Kinv_ref[:], precision)
    diagK = diagK_ref[:]                    # (1, n)
    q = q_ref[:]
    cl, cu, lb, ub = cl_ref[:], cu_ref[:], lb_ref[:], ub_ref[:]
    g = gamma_ref[:]                        # (Sb, 1)
    has = has_ref[0, 0]
    dq2 = dq2_ref[:]
    rho_a = rho_a_ref[:]                    # (1, m) shared, unscaled
    rho_a_s = g * rho_a
    rho_x_s = g * rho_x_ref[:]
    sigma_s = g * sigma
    x, z, zx, y, yx, Ax = (x_ref[:], z_ref[:], zx_ref[:], y_ref[:],
                           yx_ref[:], Ax_ref[:])

    def mv(v):                              # A v: (Sb, n) -> (Sb, m)
        return _ell_mv(rc, rv, v, kr)

    def rmv(v):                             # A' v: (Sb, m) -> (Sb, n)
        return _ell_mv(cr, cv, v, kc)

    def kdefect(rhs, xt):
        # exact per-scenario system defect, matrix-free through the ELL
        # arrays at full precision (the refinement's accuracy anchor)
        Kx = xt * diagK + rmv(mv(xt) * rho_a)
        return rhs - (g * Kx + dq2 * xt)

    def body(_, carry):
        x, z, zx, y, yx, Ax = carry
        rhs = (sigma_s * x - q + rmv(rho_a_s * z - y)
               + (rho_x_s * zx - yx))
        xt = _pdot(rhs / g, Kinv, precision, dt)
        for _ in range(n_refine):
            xt = xt + _pdot(kdefect(rhs, xt) / g, Kinv, precision, dt)
        for _ in range(n_extra):
            xt2 = xt + _pdot(kdefect(rhs, xt) / g, Kinv, precision, dt)
            xt = jnp.where(has > 0, xt2, xt)
        Axt = mv(xt)
        x_new = alpha * xt + (1 - alpha) * x
        Ax_new = alpha * Axt + (1 - alpha) * Ax

        za_arg = alpha * Axt + (1 - alpha) * z + y / rho_a_s
        z_new = jnp.clip(za_arg, cl, cu)
        y_new = y + rho_a_s * (alpha * Axt + (1 - alpha) * z - z_new)

        zx_arg = alpha * xt + (1 - alpha) * zx + yx / rho_x_s
        zx_new = jnp.clip(zx_arg, lb, ub)
        yx_new = yx + rho_x_s * (alpha * xt + (1 - alpha) * zx - zx_new)
        return x_new, z_new, zx_new, y_new, yx_new, Ax_new

    x, z, zx, y, yx, Ax = jax.lax.fori_loop(
        0, n_sweeps, body, (x, z, zx, y, yx, Ax))
    x_out[:] = x
    z_out[:] = z
    zx_out[:] = zx
    y_out[:] = y
    yx_out[:] = yx
    Ax_out[:] = Ax


@functools.partial(jax.jit,
                   static_argnames=("n_sweeps", "n_refine", "n_extra",
                                    "sigma", "alpha", "bs", "precision",
                                    "interpret"))
def fused_sweeps_sparse(q, rowcols, rowvals, colrows, colvals, Kinv, diagK,
                        cl, cu, lb, ub, rho_a, rho_x, dq2, has_dq2, gamma,
                        x, z, zx, y, yx, Ax, n_sweeps, n_refine, n_extra,
                        sigma, alpha, bs, precision="highest",
                        interpret=False):
    """``n_sweeps`` sparse shared-A frozen sweeps per call, scenario-
    blocked on the sublane axis.  Shapes: ELL arrays (m, kr)/(n, kc)
    shared; ``Kinv`` (n, n) — the dense shared inverse, or the densified
    BlockWoodbury apply for the structured-KKT engine; ``diagK`` (1, n) =
    q2ref + rho_x + sigma (the matrix-free defect diagonal); ``rho_a``
    (1, m) UNSCALED shared row penalties; everything else as
    :func:`fused_sweeps_shared`.  Returns (x, z, zx, y, yx, Ax)."""
    S, n = q.shape
    m = cl.shape[1]
    kr = rowcols.shape[1]
    kc = colrows.shape[1]
    grid = ((S + bs - 1) // bs,)

    def shared2(d0, d1):
        return pl.BlockSpec((d0, d1), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)

    def scen(d1):
        return pl.BlockSpec((bs, d1), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)

    kern = functools.partial(_sparse_sweeps_kernel, n_sweeps=n_sweeps,
                             n_refine=n_refine, n_extra=n_extra,
                             sigma=sigma, alpha=alpha, precision=precision)
    dt = Kinv.dtype
    out_shape = [
        jax.ShapeDtypeStruct((S, n), dt),   # x
        jax.ShapeDtypeStruct((S, m), dt),   # z
        jax.ShapeDtypeStruct((S, n), dt),   # zx
        jax.ShapeDtypeStruct((S, m), dt),   # y
        jax.ShapeDtypeStruct((S, n), dt),   # yx
        jax.ShapeDtypeStruct((S, m), dt),   # Ax
    ]
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            scen(n),                  # q
            shared2(m, kr), shared2(m, kr),   # rowcols rowvals
            shared2(n, kc), shared2(n, kc),   # colrows colvals
            shared2(n, n),            # Kinv
            shared2(1, n),            # diagK
            scen(m), scen(m),         # cl cu
            scen(n), scen(n),         # lb ub
            shared2(1, m),            # rho_a
            shared2(1, n),            # rho_x
            scen(n),                  # dq2
            shared2(1, 1),            # has_dq2
            scen(1),                  # gamma
            scen(n), scen(m), scen(n), scen(m), scen(n),  # x z zx y yx
            scen(m),                  # Ax
        ],
        out_specs=[scen(n), scen(m), scen(n), scen(m), scen(n), scen(m)],
        out_shape=out_shape,
        interpret=interpret,
    )(q, rowcols, rowvals, colrows, colvals, Kinv, diagK, cl, cu, lb, ub,
      rho_a, rho_x, dq2, has_dq2, gamma, x, z, zx, y, yx, Ax)


def sparse_kernel_possible(platform=None) -> bool:
    """Could :func:`fused_sweeps_sparse` EVER engage in this process:
    TPU backend + the experimental
    ``TPUSPPY_PALLAS_SPARSE=1`` opt-in.  The ONE engagement gate —
    ``SparseA.from_dense``'s ``ell="auto"`` asks it before paying for the
    ELL twin build, and :func:`usable_sparse` layers the per-shape VMEM
    budget on top."""
    import os

    platform = platform or jax.default_backend()
    return (platform == "tpu"
            and os.environ.get("TPUSPPY_PALLAS_SPARSE") == "1")


def usable_sparse(S, m, n, kr, kc, platform=None, itemsize=4) -> int | None:
    """Scenario block size if the fused sparse kernel applies, else None.

    EXPERIMENTAL on real TPU: the ELL matvec's lane-axis gathers
    (``jnp.take`` inside the kernel) are not validated against every
    Mosaic version, so the kernel additionally requires the
    ``TPUSPPY_PALLAS_SPARSE=1`` opt-in there; interpret-mode tests pin
    the semantics platform-independently.  The shared operands (densified
    Kinv + ELL arrays) must fit VMEM alongside one scenario block."""
    if not sparse_kernel_possible(platform):
        return None
    from .sparse import ELL_MAX_K
    if max(kr, kc) > ELL_MAX_K:
        return None
    mat = n * n * itemsize + (m * kr + n * kc) * (itemsize + 4) \
        + n * itemsize
    if mat > _VMEM_BUDGET // 2:
        return None
    per_scen = (8 * n + 6 * m + 2) * itemsize
    bs = (_VMEM_BUDGET - mat) // max(per_scen, 1)
    if bs >= S:
        return int(S)
    bs = (bs // 8) * 8
    return int(bs) if bs >= 8 else None


def usable_shared(S, m, n, platform=None, itemsize=4) -> int | None:
    """Scenario block size if the fused shared-A kernel applies, else None.

    The shared matrices (A + Kinv + K) must fit VMEM alongside one
    scenario block's state; the block rides the SUBLANE axis (multiples
    of 8 for f32).  Reference-scale UC (n=16008) exceeds the matrix
    budget by orders of magnitude and correctly declines — the kernel is
    the small/medium-n shared-family fast path."""
    platform = platform or jax.default_backend()
    if platform != "tpu":
        return None
    mat = (m * n + 2 * n * n) * itemsize
    if mat > _VMEM_BUDGET // 2:
        return None
    per_scen = (6 * n + 6 * m + 2) * itemsize
    bs = (_VMEM_BUDGET - mat) // max(per_scen, 1)
    if bs >= S:
        return int(S)
    bs = (bs // 8) * 8
    return int(bs) if bs >= 8 else None
