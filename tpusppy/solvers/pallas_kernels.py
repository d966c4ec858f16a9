"""Pallas TPU kernels of the dense per-scenario engine (:mod:`.admm`).

Two kernels, each with the gate that sizes its block, both scenario-on-lanes:

- :func:`fused_sweeps` (gate :func:`usable`, block :func:`sweep_block_size`):
  the fused ADMM sweep block;
- :func:`lanes_solve` (gate :func:`usable_solve`): the batched dense
  elimination behind the refresh solve's polish (one right-hand side a
  saddle system) and behind its explicit inverses of K (the identity on
  the right).

The shared-A engine (:mod:`.shared_admm`) has no kernel here: its sweeps are
(S, n) x (n, m) MXU matmuls that XLA:TPU already fuses, in every regime.

The ADMM inner loop is bandwidth-bound: every sweep re-reads the (S, n, n)
K-inverse/K pair and the (S, m, n) constraint matrix from HBM (three to five
matrix passes per sweep).  The sweep kernel runs ``n_sweeps`` sweeps over a
block of scenarios with all matrices resident in VMEM, so HBM sees each matrix
once per kernel call instead of once per sweep — the hot-op fusion the build
brief calls for (SURVEY §7 step 2; the XLA einsum path remains the fallback for
CPU, dense-P, and shapes that exceed the VMEM budget).

One call is one whole step of the engine's sweep loop (``admm._sweep_loop``):
beside the state the kernel hands back the four residual rows of the iterate
it ends on (``admm.residual_rows``, from a true ``A x`` of the final ``x``),
while ``A`` is at hand and not padded, and the state's outputs alias its
inputs, so the loop carries the state in this layout from its first step to
its last and nothing but (S,) bookkeeping stands between two calls.  The
lowered mode (bf16 matrix storage) hands back the state alone: its residuals
are pinned to float32 operands, which the kernel does not hold.

All contractions are per-scenario matvecs with tiny n/m (tens), so the VPU
multiply-reduce form ``(M * v[:, None, :]).sum(-1)`` is used rather than MXU
dots (the 128-lane MXU tiles would be mostly padding at these sizes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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


def _sweeps_kernel(q_ref, q2_ref, A_ref, At_ref, Kinv_ref, K_ref, cl_ref,
                   cu_ref, lb_ref, ub_ref, rho_a_ref, rho_x_ref, x_ref,
                   z_ref, zx_ref, y_ref, yx_ref, x_out, z_out, zx_out,
                   y_out, yx_out, res_out=None, *, n_sweeps, n_refine,
                   sigma, alpha, m, n, precision):
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
    here is simply full f32 — at least as accurate as bf16x3 asks).

    ``res_out`` (4, Sb), in every mode but "default": the residual rows
    ``pri``, ``dua``, ``prinorm``, ``duanorm`` of the iterate the sweeps end
    on, the formulas of the engine's own checkpoint."""
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
    x, z, zx, y, yx = x_ref[:], z_ref[:], zx_ref[:], y_ref[:], yx_ref[:]
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
        x, z, zx, y, yx = carry
        rhs = (sigma * x - q + contract(A, rnd(rho_a * z - y), m)
               + (rho_x * zx - yx))
        xt = contract(Kinv, rnd(rhs), n)      # Kinv symmetric
        for _ in range(n_refine):
            r = rhs - contract(K, xt, n)      # defect: exact f32 K
            xt = xt + contract(Kinv, rnd(r), n)
        Axt = contract(At, rnd(xt), n)
        x_new = alpha * xt + (1 - alpha) * x

        za_arg = alpha * Axt + (1 - alpha) * z + y / rho_a
        z_new = jnp.clip(za_arg, cl, cu)
        y_new = y + rho_a * (alpha * Axt + (1 - alpha) * z - z_new)

        zx_arg = alpha * xt + (1 - alpha) * zx + yx / rho_x
        zx_new = jnp.clip(zx_arg, lb, ub)
        yx_new = yx + rho_x * (alpha * xt + (1 - alpha) * zx - zx_new)
        return x_new, z_new, zx_new, y_new, yx_new

    x, z, zx, y, yx = jax.lax.fori_loop(0, n_sweeps, body,
                                        (x, z, zx, y, yx))
    x_out[:] = x
    z_out[:] = z
    zx_out[:] = zx
    y_out[:] = y
    yx_out[:] = yx
    if res_out is not None:
        from .admm import residual_rows

        q2 = q2_ref[:]
        rows = residual_rows(
            q, x, z, zx, y, yx, contract(At, x, n),
            lambda y: contract(A, y, m), lambda x: q2 * x,
            lambda v: jnp.max(jnp.abs(v), axis=0, keepdims=True))
        for i, row in enumerate(rows):
            res_out[i:i + 1, :] = row


@functools.partial(jax.jit,
                   static_argnames=("n_sweeps", "n_refine", "sigma", "alpha",
                                    "bs", "precision", "interpret"))
def fused_sweeps(q, q2, A, At, Kinv, K, cl, cu, lb, ub, rho_a, rho_x,
                 x, z, zx, y, yx, n_sweeps, n_refine, sigma, alpha, bs,
                 precision="highest", interpret=False):
    """Run ``n_sweeps`` sweeps; ALL arrays in scenario-last layout
    (m,n,S)/(n,S) etc.  Returns the transposed state and the residual rows
    of the iterate it ends on, ``(x, z, zx, y, yx, res)``: ``res`` (4, S)
    holds ``pri``, ``dua``, ``prinorm``, ``duanorm``, one value a scenario,
    in full float32 from a true ``A x`` of the final ``x`` (``None`` under
    ``precision="default"``, whose matrices the kernel holds in bf16: the
    caller keeps those products).  The state's outputs alias its inputs.

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
    # x z zx y yx, and the four residual rows
    dims = [n, m, n, m, n] + ([] if precision == "default" else [4])
    outs = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            spec2(n), spec2(n),  # q q2
            spec3(m, n),         # A
            spec3(n, m),         # At
            spec3(n, n),         # Kinv
            spec3(n, n),         # K
            spec2(m), spec2(m),  # cl cu
            spec2(n), spec2(n),  # lb ub
            spec2(m), spec2(n),  # rho_a rho_x
            spec2(n), spec2(m), spec2(n), spec2(m), spec2(n),  # x z zx y yx
        ],
        out_specs=[spec2(d) for d in dims],
        out_shape=[jax.ShapeDtypeStruct((d, S), dt) for d in dims],
        # the state is updated in place: inputs 12-16 are outputs 0-4
        input_output_aliases={12 + i: i for i in range(5)},
        interpret=interpret,
    )(q, q2, A, At, Kinv, K, cl, cu, lb, ub, rho_a, rho_x, x, z, zx, y, yx)
    return (*outs[:5], outs[5] if len(outs) > 5 else None)


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
# batch-outermost: the refresh solve's nine (n+m)-sized polish LUs and its
# four n-sized inverses cost far more than their few 1e8 flops (PERF.md
# section 5).  This kernel does the same arithmetic (an LU with partial
# pivoting; for the SPD K's, where Cholesky stood, as backward stable) in
# the layout of ``fused_sweeps``: a block of
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
