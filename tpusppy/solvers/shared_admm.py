"""Shared-constraint-matrix ADMM: the memory-wall breaker for big families.

Most stochastic-programming families at scale (the reference's headline
1000-scenario UC above all — ``paperruns/larger_uc``, wind uncertainty enters
the power-balance rhs) have scenarios that differ only in costs, rhs and
bounds: the constraint matrix ``A`` is IDENTICAL across scenarios.  The dense
batched solver (:mod:`tpusppy.solvers.admm`) stores (S, m, n) A plus an
(S, n, n) KKT inverse — at reference UC scale (30 gens x 48 h, S=1000) that is
~67 GB and cannot fit one chip's HBM.  Here:

- ``A`` is stored ONCE as (m, n): memory drops S-fold (67 GB -> 67 MB);
- Ruiz scaling, row penalties and the KKT matrix are shared, so there is ONE
  (n, n) factorization instead of S of them;
- the hot x-update becomes ``rhs @ Kinv`` — a single large (S, n) x (n, n)
  MXU matmul, and the constraint matvecs are (S, m) x (m, n) matmuls: the
  best-possible TPU shapes (large, static, batched on the leading axis).

Per-scenario DIAGONAL deviations (PH rho vectors that differ across
scenarios, per-scenario clamp boosting) are handled by iterative refinement:
the shared ``K`` is the preconditioner, and the exact per-scenario system
``K_s = K + diag(dq2_s)`` is applied matrix-free in the refinement residual.
Row penalties and the scaling stay shared — scenarios in one family are
near-identically conditioned, which is exactly why they form a family.

No active-set polish on this path (a per-scenario (n+m)^2 KKT batch is the
memory wall all over again): outer bounds stay certified through weak duality
(:func:`tpusppy.solvers.admm.dual_objective` handles 2-D A), and LP-exact
primal residue is delegated to the host straggler rescue
(``spopt.SPOpt._rescue_stragglers``).

Reference analogue: the per-rank persistent-solver loop (spopt.py:85-307);
this module is its shape-shared fast path, dispatched automatically by
``SPOpt.solve_loop`` when ``ScenarioBatch.A_shared`` is set.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs import metrics as _metrics
from . import aot as _aot
from . import turns as _turns
from .admm import (ADMMSettings, BatchSolution, BIG, _clean_bounds,
                   _done_mask, _explicit_inverse, _frozen_sweep_phases,
                   _plateau_update)
from .sparse import SparseA
from .structured_kkt import (apply_kinv_like, factor_lowrank,
                             factor_structured, lowrank_kinv, zero_factors,
                             zero_lowrank)


def _mv(A, x):
    """A x: (S, n) -> (S, m) for dense (m, n) or :class:`SparseA`."""
    return A.matvec(x) if isinstance(A, SparseA) else x @ A.T


def _rmv(A, y):
    """A' y: (S, m) -> (S, n) for dense (m, n) or :class:`SparseA`."""
    return A.rmatvec(y) if isinstance(A, SparseA) else y @ A


class SharedFactors(NamedTuple):
    """Reusable solve state for the frozen path (shared-A analogue of
    :class:`tpusppy.solvers.admm.Factors`)."""

    D: jax.Array       # (n,) Ruiz column scaling (shared)
    E: jax.Array       # (m,) Ruiz row scaling (shared)
    cost: jax.Array    # scalar objective scaling (shared)
    rho_a: jax.Array   # (m,) row penalties actually used last
    rho_x: jax.Array   # (n,) variable-box penalties actually used last
    gamma: jax.Array   # (S,) per-scenario penalty scales actually used last
    Kinv: jax.Array    # (n, n) explicit inverse of the shared x-update
                       # system, or an operator of structured_kkt applied
                       # by apply_kinv_like: BlockWoodbury (sparse-A
                       # families with block/Woodbury structure) or
                       # DiagLowRank (a dense A of few rows beside its
                       # columns: lowrank_kinv)
    K: jax.Array       # (n, n) exact shared K for dense refinement, or None:
                       # refinement then runs matrix-free through the
                       # scaled shared A.  None where factors_keep_K=False
                       # and wherever Kinv is an operator (BlockWoodbury,
                       # DiagLowRank), whatever that setting says
    q2ref: jax.Array   # (n,) scaled q2 the K was built with


class _Masks(NamedTuple):
    fin_cl: jax.Array  # (S, m)
    fin_cu: jax.Array  # (S, m)
    fin_lb: jax.Array  # (S, n)
    fin_ub: jax.Array  # (S, n)
    eq: jax.Array      # (m,) equality row in EVERY scenario (shared classes)
    loose: jax.Array   # (m,) two-sided-infinite row in every scenario
    eqx: jax.Array     # (n,) zero-width variable box in every scenario


def _ruiz_shared(A, q2ref, iters):
    """Ruiz equilibration of the single shared A (dense or sparse);
    returns (D (n,), E (m,))."""
    m, n = A.shape
    D = jnp.ones((n,), A.dtype)
    E = jnp.ones((m,), A.dtype)
    sparse = isinstance(A, SparseA)

    def body(_, DE):
        D, E = DE
        Ps = q2ref * D * D
        if sparse:
            As = A.scale(E, D)
            col = jnp.maximum(As.col_absmax(), jnp.abs(Ps))
            row = As.row_absmax()
        else:
            As = A * E[:, None] * D[None, :]
            col = jnp.maximum(jnp.max(jnp.abs(As), axis=0), jnp.abs(Ps))
            row = jnp.max(jnp.abs(As), axis=1)
        col = jnp.where(col < 1e-12, 1.0, col)
        row = jnp.where(row < 1e-12, 1.0, row)
        return D / jnp.sqrt(col), E / jnp.sqrt(row)

    D, E = jax.lax.fori_loop(0, iters, body, (D, E))
    return D, E


def _factor_shared(q2ref, A, rho_a, rho_x, sigma):
    """(Kinv, K) of the SHARED K = diag(q2ref + rho_x) + sigma I + A'RA —
    one (n, n) system for the whole scenario batch.

    Four regimes, by the type and the shape of A (read at trace time):
    - dense (m, n) array: dense K + explicit inverse;
    - dense (m, n) array of few rows beside its columns
      (:func:`structured_kkt.lowrank_kinv`): K^-1 as diagonal plus rank m
      (:class:`structured_kkt.DiagLowRank`) and no (n, n) object at all:
      K is None, and the refinement reaches K x through the m rows of A
      (``_core``'s matrix-free ``Kmul``);
    - :class:`SparseA` WITH attached block/Woodbury structure: the
      structured factorization (no (n, n) object at all; K is None and
      refinement runs matrix-free through the sparse A);
    - SparseA without structure: K assembled via a transient dense
      scatter, explicit inverse kept, K dropped (matrix-free refinement
      keeps the factors small)."""
    n = A.shape[1]
    if isinstance(A, SparseA):
        if A.structure is not None:
            bw = factor_structured(A, A.structure, q2ref + rho_x, rho_a,
                                   sigma)
            return bw, None
        Ad = A.todense()
        K = jnp.einsum("mn,m,mk->nk", Ad, rho_a, Ad)
        K = K + jnp.eye(n, dtype=Ad.dtype) * sigma
        K = K + jnp.diag(q2ref + rho_x)
        return _explicit_inverse(K[None])[0], None
    if lowrank_kinv(A):
        return factor_lowrank(A, q2ref + rho_x + sigma, rho_a), None
    K = jnp.einsum("mn,m,mk->nk", A, rho_a, A)
    K = K + jnp.eye(n, dtype=A.dtype) * sigma
    K = K + jnp.diag(q2ref + rho_x)
    return _explicit_inverse(K[None])[0], K


def _solve_shared_K(Kinv, Kmul, dq2, gamma, b, refine, extra_if_dq2=2,
                    prec=None):
    """x s.t. (gamma_s K + diag(dq2_s)) x_s = b_s per scenario, via the shared
    inverse + refinement against the exact per-scenario system; ``Kmul``
    applies the exact K (dense row-vector product, or matrix-free via the
    scaled A when the factors don't carry K — see ``factors_keep_K``).

    ``gamma`` (S, 1) is the per-scenario penalty scale: rho_a, rho_x and
    sigma are all free ADMM parameters, so scaling the WHOLE penalty profile
    by a per-scenario scalar keeps the x-update system an exact multiple of
    the shared K (plus the diagonal objective deviation dq2) — per-scenario
    rho adaptation without per-scenario factorizations.  The refinement
    iteration matrix has spectral radius max_j dq2_j / (gamma K_jj) — the
    adaptation clamps gamma so this stays < 1 (see the QP clamp in the
    restart loop); ``extra_if_dq2`` adds passes only when a nonzero dq2 is
    actually present (LP batches skip them at runtime via lax.cond).

    ``prec``: mixed-precision mode for the K^-1 applies; ``Kmul`` (the
    defect) must then be full-precision — the caller builds it pinned."""
    def steps(x, k):
        for _ in range(k):
            r = b - (gamma * Kmul(x) + dq2 * x)
            x = x + apply_kinv_like(Kinv, r / gamma, prec)
        return x

    x = steps(apply_kinv_like(Kinv, b / gamma, prec), refine)
    if extra_if_dq2 > 0:
        x = jax.lax.cond(jnp.any(dq2 != 0),
                         lambda v: steps(v, extra_if_dq2), lambda v: v, x)
    return x


class _IterState(NamedTuple):
    x: jax.Array
    z: jax.Array
    zx: jax.Array
    y: jax.Array
    yx: jax.Array
    gamma: jax.Array   # (S,) per-scenario penalty scale — adapts IN-loop
    pri: jax.Array
    dua: jax.Array
    prinorm: jax.Array
    duanorm: jax.Array
    k: jax.Array
    best: jax.Array    # scalar: best batch-worst eps-normalized residual
    stall: jax.Array   # scalar int32: consecutive non-improving windows


def _core(q, q2s, q2ref, A, cl, cu, lb, ub, state, Kinv, K, rho_a, rho_x,
          glo, ghi, st: ADMMSettings, adaptive=False, prec=None):
    """Inner ADMM sweep at a fixed shared rho profile with IN-LOOP
    per-scenario gamma adaptation.

    Scaling the whole penalty profile (rho_a, rho_x, sigma) by gamma_s keeps
    the x-update system an exact multiple of the shared K — so adapting
    gamma needs NO refactorization and runs every residual checkpoint
    (OSQP's adaptive rho at zero factorization cost).  Restarts are only
    needed to move the SHARED profile (base rho, row boosts).  All matvecs
    are (S, m) @ (m, n) or (S, n) @ (n, n) matmuls against shared matrices.
    ``glo``/``ghi`` bound gamma: wide for LP batches (dq2 = 0, exact at any
    gamma), clamped near 1 for QP (keeps the dq2 refinement contractive).

    ``prec``: None keeps the legacy program; a mode string runs the sweep
    matvecs at lowered matmul precision with defect/residual bookkeeping
    pinned at full f32 (solvers/precision.py).
    """
    sparse = isinstance(A, SparseA)
    if prec is None or sparse:
        # sparse: gather/segment-sum matvecs are elementwise VPU work — no
        # MXU passes to economize; only the (n, n)/block-Woodbury x-update
        # applies run lowered (via _solve_shared_K's prec)
        mv_lo, rmv_lo = _mv, _rmv
        mv_hi, rmv_hi = _mv, _rmv
    else:
        from . import precision as _precision
        mv_lo = lambda M, x: _precision.contract("sn,mn->sm", x, M, prec)
        rmv_lo = lambda M, y: _precision.contract("sm,mn->sn", y, M, prec)
        mv_hi = lambda M, x: _precision.contract(
            "sn,mn->sm", x, M, "highest")
        rmv_hi = lambda M, y: _precision.contract(
            "sm,mn->sn", y, M, "highest")
    # exact-K application for refinement: dense when K is carried, else
    # matrix-free through the (scaled) shared A — identical product, two
    # (S,m)/(S,n) matmuls instead of one (S,n)x(n,n), and no (n,n) K in
    # the factors (memory matters when several wheel cylinders coexist
    # on one chip).  Pinned full-precision under a low sweep mode: the
    # defect is the refinement's accuracy anchor.
    if K is not None:
        # TRACE-time counter: one per compiled program whose refinement
        # multiplies by the dense (n, n) K
        _metrics.inc("shared_admm.dense_K_refine_programs")
        if prec is None:
            Kmul = lambda x: x @ K
        else:
            from . import precision as _precision
            Kmul = lambda x: _precision.contract("sn,nk->sk", x, K,
                                                 "highest")
    else:
        diagK = q2ref + rho_x + st.sigma
        Kmul = lambda x: (x * diagK[None, :]
                          + rmv_hi(A, mv_hi(A, x) * rho_a[None, :]))
    alpha = st.alpha

    def block(x, z, zx, y, yx, Ax, gamma):
        g = gamma[:, None]
        sigma_s = g * st.sigma           # (S, 1): scaled prox parameter
        rho_a_s = g * rho_a[None, :]     # (S, m)
        rho_x_s = g * rho_x[None, :]     # (S, n)
        dq2 = q2s - g * q2ref[None, :]

        for _ in range(max(1, st.check_every)):
            rhs = (sigma_s * x - q + rmv_lo(A, rho_a_s * z - y)
                   + (rho_x_s * zx - yx))
            xt = _solve_shared_K(Kinv, Kmul, dq2, g, rhs, st.solve_refine,
                                 prec=prec)
            Axt = mv_lo(A, xt)
            x_new = alpha * xt + (1 - alpha) * x
            Ax_new = alpha * Axt + (1 - alpha) * Ax

            za_arg = alpha * Axt + (1 - alpha) * z + y / rho_a_s
            z_new = jnp.clip(za_arg, cl, cu)
            y_new = y + rho_a_s * (alpha * Axt + (1 - alpha) * z - z_new)

            zx_arg = alpha * xt + (1 - alpha) * zx + yx / rho_x_s
            zx_new = jnp.clip(zx_arg, lb, ub)
            yx_new = yx + rho_x_s * (alpha * xt + (1 - alpha) * zx - zx_new)
            x, z, zx, y, yx, Ax = x_new, z_new, zx_new, y_new, yx_new, Ax_new
        return x, z, zx, y, yx, Ax

    def residuals(x, z, zx, y, yx, Ax):
        pri = jnp.maximum(
            jnp.max(jnp.abs(Ax - z), axis=1),
            jnp.max(jnp.abs(x - zx), axis=1),
        )
        Aty = rmv_hi(A, y)
        Pxv = q2s * x
        dua = jnp.max(jnp.abs(Pxv + q + Aty + yx), axis=1)
        prinorm = jnp.maximum(
            jnp.max(jnp.abs(Ax), axis=1), jnp.max(jnp.abs(z), axis=1))
        duanorm = jnp.maximum(
            jnp.maximum(jnp.max(jnp.abs(Pxv), axis=1),
                        jnp.max(jnp.abs(Aty), axis=1)),
            jnp.max(jnp.abs(q), axis=1))
        return pri, dua, prinorm, duanorm

    def cont(carry):
        s, _ = carry
        done = _done_mask(s.pri, s.dua, s.prinorm, s.duanorm, st)
        go = (s.k < st.max_iter) & ~jnp.all(done)
        if st.sweep_plateau_rtol > 0:
            go = go & (s.stall < 2)
        return go

    def multi_step(carry):
        s, Ax_prev = carry
        x, z, zx, y, yx, Ax = block(s.x, s.z, s.zx, s.y, s.yx, Ax_prev,
                                    s.gamma)
        Ax = mv_hi(A, x)   # re-anchor carried Ax (see admm._admm_core;
        # pinned f32 under a low sweep mode — the defect control)
        pri, dua, prinorm, duanorm = residuals(x, z, zx, y, yx, Ax)
        # Per-scenario divergence guard: unstructured random families (and
        # frozen solves whose dq2 deviation is large enough to make the
        # shared-K refinement non-contractive) can EXPLODE — iterates race
        # to inf within one checkpoint block and every later residual is
        # NaN, which poisons stop_stats and the plateau detector.  Freeze
        # exploding scenarios at their last finite iterate (the carried-in
        # Ax_prev is exactly A @ s.x from the previous re-anchor, so the
        # revert costs no extra matvec) and report INF residuals: done
        # stays False, the host sees an honest "diverged" instead of NaN,
        # and the straggler rescue / rho-restart machinery owns recovery.
        finite = (jnp.all(jnp.isfinite(x), axis=1)
                  & jnp.all(jnp.isfinite(z), axis=1)
                  & jnp.all(jnp.isfinite(zx), axis=1)
                  & jnp.all(jnp.isfinite(y), axis=1)
                  & jnp.all(jnp.isfinite(yx), axis=1))
        # negated <= so NaN residuals land in the guard set too
        bad = ~finite | ~(pri <= BIG) | ~(dua <= BIG)
        bv = bad[:, None]
        x = jnp.where(bv, s.x, x)
        z = jnp.where(bv, s.z, z)
        zx = jnp.where(bv, s.zx, zx)
        y = jnp.where(bv, s.y, y)
        yx = jnp.where(bv, s.yx, yx)
        Ax = jnp.where(bv, Ax_prev, Ax)
        inf_dt = jnp.asarray(jnp.inf, pri.dtype)
        pri = jnp.where(bad, inf_dt, pri)
        dua = jnp.where(bad, inf_dt, dua)
        prinorm = jnp.where(bad, s.prinorm, prinorm)
        duanorm = jnp.where(bad, s.duanorm, duanorm)
        # OSQP-style per-scenario adaptation on normalized residual ratios.
        # Cadence matters: adapting every checkpoint thrashes (early ratios
        # are always imbalanced and rho oscillates); every ~128 sweeps
        # matches the restart cadence that converges, at zero
        # refactorization cost.  (A faster cadence to beat the in-loop
        # plateau exit was tried and thrashes LP batches, whose free gamma
        # oscillates.  Instead, ADAPTIVE solves delay plateau-stall
        # counting past the first gamma opportunity via min_k below;
        # frozen solves, whose gamma was already adapted at refresh,
        # keep the earliest exit.)
        done = _done_mask(pri, dua, prinorm, duanorm, st)
        pri_rel = pri / jnp.maximum(prinorm, 1e-10)
        dua_rel = dua / jnp.maximum(duanorm, 1e-10)
        ratio = jnp.sqrt(
            jnp.maximum(pri_rel, 1e-12) / jnp.maximum(dua_rel, 1e-12))
        ck = max(1, st.check_every)
        period = max(1, 128 // ck)
        k_next = s.k + ck
        due = (k_next // ck) % period == 0
        move = due & ((ratio > 5.0) | (ratio < 0.2))
        gnew = jnp.clip(s.gamma * jnp.clip(ratio, 0.1, 10.0), glo, ghi)
        gamma = jnp.where(done | ~move, s.gamma, gnew)
        if st.sweep_plateau_rtol > 0:
            best, stall = _plateau_update(s, pri, dua, prinorm, duanorm,
                                          st, min_k=128 if adaptive else 0)
            # an ACTUAL gamma move changes the iteration itself: give the
            # new penalties a fresh plateau grace instead of exiting on
            # residuals produced by the OLD gamma.  (gnew clipped back to
            # its old value is a no-op and must NOT reset the grace — a
            # pinned gamma at the clip bound would otherwise defeat the
            # plateau exit forever.)
            moved = jnp.any(move & ~done & (gnew != s.gamma))
            stall = jnp.where(moved, 0, stall)
            best = jnp.where(moved, jnp.asarray(jnp.inf, best.dtype), best)
        else:
            best, stall = s.best, s.stall
        return (_IterState(x, z, zx, y, yx, gamma, pri, dua, prinorm,
                           duanorm, s.k + max(1, st.check_every),
                           best, stall), Ax)

    Ax0 = _mv(A, state.x)
    state, _ = jax.lax.while_loop(cont, multi_step, (state, Ax0))
    return state


def _prep_shared(c, q2, A, cl, cu, lb, ub, settings, want_masks=True):
    """``want_masks=False`` skips the mask reductions (several (S, m)/(S, n)
    jnp.all's) for the frozen path, which never reads them — inside a fused
    multi-iteration scan they would otherwise run once per PH iteration."""
    dt = settings.jdtype()
    c, q2 = jnp.asarray(c, dt), jnp.asarray(q2, dt)
    A = A.astype(dt) if isinstance(A, SparseA) else jnp.asarray(A, dt)
    cl, cu = _clean_bounds(jnp.asarray(cl, dt), jnp.asarray(cu, dt))
    lb, ub = _clean_bounds(jnp.asarray(lb, dt), jnp.asarray(ub, dt))
    if not want_masks:
        return c, q2, A, cl, cu, lb, ub, None
    masks = _Masks(
        fin_cl=cl > -BIG / 2, fin_cu=cu < BIG / 2,
        fin_lb=lb > -BIG / 2, fin_ub=ub < BIG / 2,
        # shared row/column penalty classes: a row is boosted only when it is
        # an equality in EVERY scenario (families share structure, so in
        # practice these are uniform; a non-uniform row just loses the boost,
        # never correctness)
        eq=jnp.all(jnp.abs(cu - cl) < 1e-10, axis=0),
        loose=jnp.all((cl <= -BIG / 2) & (cu >= BIG / 2), axis=0),
        eqx=jnp.all(jnp.abs(ub - lb) < 1e-10, axis=0),
    )
    return c, q2, A, cl, cu, lb, ub, masks


def _scale_shared(c, q2, A, cl, cu, lb, ub, D, E, cost, warm, dt):
    As = A.scale(E, D) if isinstance(A, SparseA) else (
        A * E[:, None] * D[None, :])
    q2s = q2 * (D * D)[None, :] * cost
    qs = c * D[None, :] * cost
    cls, cus = cl * E[None, :], cu * E[None, :]
    lbs, ubs = lb / D[None, :], ub / D[None, :]
    if warm is not None:
        x0, z0, y0, yx0 = warm
        warm = (
            jnp.asarray(x0, dt) / D[None, :],
            jnp.asarray(z0, dt) * E[None, :],
            jnp.asarray(y0, dt) / E[None, :] * cost,
            jnp.asarray(yx0, dt) * D[None, :] * cost,
        )
    return qs, q2s, As, cls, cus, lbs, ubs, warm


class _Scaled(NamedTuple):
    """What every restart of one adaptive solve reads: the scaled problem
    and the shared classes it was scaled with."""

    qs: jax.Array
    q2s: jax.Array
    As: jax.Array
    cls: jax.Array
    cus: jax.Array
    lbs: jax.Array
    ubs: jax.Array
    q2ref: jax.Array   # (n,) scaled q2 the shared K is built with
    D: jax.Array
    E: jax.Array
    cost: jax.Array
    eq: jax.Array
    loose: jax.Array
    eqx: jax.Array
    glo: jax.Array
    ghi: jax.Array


def _shared_setup(c, q2, A, cl, cu, lb, ub, settings, warm):
    """(scaled problem, restart carry) of one adaptive shared-A solve."""
    dt = settings.jdtype()
    c, q2, A, cl, cu, lb, ub, masks = _prep_shared(
        c, q2, A, cl, cu, lb, ub, settings)
    S, n = c.shape
    m = A.shape[0]

    q2ref_raw = jnp.mean(q2, axis=0)
    D, E = _ruiz_shared(A, q2ref_raw, settings.scaling_iters)
    # shared scalar objective scaling (median scenario magnitude): scenarios
    # in a family have comparable cost scales, and a shared scalar keeps the
    # scaled q2 — hence the K — shared
    cost = 1.0 / jnp.maximum(
        jnp.median(jnp.max(jnp.abs(c * D[None, :]), axis=1)), 1e-8)
    qs, q2s, As, cls, cus, lbs, ubs, warm = _scale_shared(
        c, q2, A, cl, cu, lb, ub, D, E, cost, warm, dt)
    q2ref = jnp.mean(q2s, axis=0)

    if warm is None:
        x0 = jnp.zeros((S, n), dt)
        z0 = jnp.clip(jnp.zeros((S, m), dt), cls, cus)
        y0 = jnp.zeros((S, m), dt)
        yx0 = jnp.zeros((S, n), dt)
    else:
        x0, z0, y0, yx0 = warm
    zx0 = jnp.clip(x0, lbs, ubs)
    inf = jnp.full((S,), jnp.inf, dt)
    one = jnp.ones((S,), dt)
    state0 = _IterState(x0, z0, zx0, y0, yx0, jnp.ones((S,), dt),
                        inf, inf, one, one, jnp.zeros((), jnp.int32),
                        jnp.asarray(jnp.inf, dt),
                        jnp.zeros((), jnp.int32))

    # Per-scenario gamma runs FREE for (near-)LP batches: dq2 = 0 there, so
    # the shared inverse solves every scenario's x-update exactly at any
    # gamma.  Significant q2 (PH prox solves) clamps gamma near 1 to keep
    # the dq2 = q2(1-gamma) refinement contractive (radius <= |1-gamma|/
    # gamma) — prox solves are strongly convex and need little adaptation.
    lp_like = jnp.max(jnp.abs(q2s)) < 1e-12
    glo = jnp.where(lp_like, 1e-4, 0.6)
    ghi = jnp.where(lp_like, 1e4, 1.8)

    # (Kinv, K) carry placeholders must match the factorization regime's
    # pytree structure (lax.scan carries are structure-invariant): dense
    # (n, n) pair for a dense A, (DiagLowRank, None) for a dense A of few
    # rows, (dense, None) for unstructured sparse, (BlockWoodbury, None)
    # for the structured path
    zK = None
    if isinstance(As, SparseA):
        if As.structure is not None:
            zKinv = zero_factors(As.structure, n, dt)
        else:
            zKinv = jnp.zeros((n, n), dt)
    elif lowrank_kinv(As):
        zKinv = zero_lowrank(m, n, dt)
    else:
        zKinv = zK = jnp.zeros((n, n), dt)
    carry0 = (state0, jnp.asarray(settings.rho, dt),
              jnp.zeros((), jnp.int32),
              jnp.ones((m,), dt), jnp.ones((n,), dt),
              jnp.zeros((m,), dt), jnp.zeros((n,), dt), zKinv, zK)
    scaled = _Scaled(qs, q2s, As, cls, cus, lbs, ubs, q2ref, D, E, cost,
                     masks.eq, masks.loose, masks.eqx, glo, ghi)
    return scaled, carry0


def _shared_restart(sc: _Scaled, carry, st: ADMMSettings):
    """One rho setting of the adaptive solve: factor, sweep, re-adapt."""
    dt = st.jdtype()
    n = sc.qs.shape[1]
    qs, q2s, As = sc.qs, sc.q2s, sc.As
    cls, cus, lbs, ubs = sc.cls, sc.cus, sc.lbs, sc.ubs

    def rho_vec(base):
        r = jnp.where(sc.eq, base * st.rho_eq_scale, base)
        return jnp.where(sc.loose, st.rho_min, r)

    def rho_x_vec(base):
        return jnp.where(sc.eqx, base * st.rho_eq_scale,
                         jnp.full((n,), base, dt))

    state, base, total, mult, multx = carry[:5]
    rho_a = rho_vec(base)
    rho_x = rho_x_vec(base)
    if st.rho_row_adapt:
        rho_a = jnp.minimum(rho_a * mult, st.rho_row_max)
        rho_x = jnp.minimum(rho_x * multx, st.rho_row_max)
    Kinv, K = _factor_shared(sc.q2ref, As, rho_a, rho_x, st.sigma)
    state = _core(qs, q2s, sc.q2ref, As, cls, cus, lbs, ubs,
                  state._replace(k=jnp.zeros((), jnp.int32),
                                 best=jnp.asarray(jnp.inf, dt),
                                 stall=jnp.zeros((), jnp.int32)),
                  Kinv, K, rho_a, rho_x, sc.glo, sc.ghi, st, adaptive=True)
    total = total + state.k
    done = _done_mask(state.pri, state.dua, state.prinorm,
                      state.duanorm, st)
    eps_pri = st.eps_abs + st.eps_rel * jnp.maximum(state.prinorm, 1.0)
    pri_rel = state.pri / jnp.maximum(state.prinorm, 1e-10)
    dua_rel = state.dua / jnp.maximum(state.duanorm, 1e-10)
    ratio = jnp.sqrt(
        jnp.maximum(pri_rel, 1e-12) / jnp.maximum(dua_rel, 1e-12))
    # shared base: adapt on the geometric-mean ratio of UNCONVERGED
    # scenarios (converged ones would anchor the ratio at its stale
    # value); per-scenario adaptation lives in-loop via gamma.
    # Diverged scenarios (inf residuals from the in-loop guard) have a
    # NaN ratio and are EXCLUDED — one exploding scenario must not
    # poison the shared base for the whole batch.
    ok = jnp.isfinite(ratio)
    logr = jnp.where(done | ~ok, 0.0,
                     jnp.log(jnp.clip(ratio, 0.1, 10.0)))
    denom = jnp.maximum(jnp.sum(~done & ok), 1)
    gmean = jnp.exp(jnp.sum(logr) / denom)
    base = jnp.where(jnp.all(done), base,
                     jnp.clip(base * gmean, st.rho_min, st.rho_max))
    if st.rho_row_adapt:
        stuck = (state.pri > 100.0 * eps_pri)[:, None]
        gate = jnp.maximum(0.3 * state.pri, 10.0 * eps_pri)[:, None]
        Ax = _mv(As, state.x)
        viol = jnp.maximum(cls - Ax, Ax - cus)
        hit = jnp.any(stuck & (viol > gate), axis=0)       # max over S
        mult = jnp.where(hit, mult * st.rho_row_boost, mult)
        violx = jnp.maximum(lbs - state.x, state.x - ubs)
        hitx = jnp.any(stuck & (violx > gate), axis=0)
        multx = jnp.where(hitx, multx * st.rho_row_boost, multx)
    return (state, base, total, mult, multx, rho_a, rho_x, Kinv, K)


def _shared_finish(sc: _Scaled, carry, st: ADMMSettings, want_factors):
    """The unscaled solution (and the last restart's factors)."""
    state, _, total, _, _, rho_a, rho_x, Kinv, K = carry
    S = sc.qs.shape[0]
    D, E, cost = sc.D, sc.E, sc.cost
    x, z, y, yx = (state.x * D[None, :], state.z / E[None, :],
                   state.y * E[None, :] / cost,
                   state.yx / D[None, :] / cost)
    sol = BatchSolution(
        x=x, z=z, y=y, yx=yx,
        pri_res=state.pri, dua_res=state.dua,
        iters=jnp.broadcast_to(total, (S,)),
        done=_done_mask(state.pri, state.dua, state.prinorm,
                        state.duanorm, st),
        raw=(x, z, y, yx),
    )
    if want_factors:
        return sol, SharedFactors(D=D, E=E, cost=cost, rho_a=rho_a,
                                  rho_x=rho_x, gamma=state.gamma, Kinv=Kinv,
                                  K=K if st.factors_keep_K else None,
                                  q2ref=sc.q2ref)
    return sol


def _solve_shared_impl(c, q2, A, cl, cu, lb, ub, settings, warm,
                       want_factors=False):
    # TRACE-time counter (wrappers are jitted; this body runs only while
    # XLA builds the program): one per adaptive shared-A program compiled
    _metrics.inc("shared_admm.adaptive_programs")
    sc, carry0 = _shared_setup(c, q2, A, cl, cu, lb, ub, settings, warm)
    carry, _ = jax.lax.scan(
        lambda carry, _: (_shared_restart(sc, carry, settings), None),
        carry0, None, length=settings.restarts)
    return _shared_finish(sc, carry, settings, want_factors)


def _solve_shared_frozen_impl(c, q2, A, cl, cu, lb, ub,
                              factors: SharedFactors, warm, settings):
    """Sweep-only shared solve reusing a refresh's :class:`SharedFactors`.
    Valid while (A, bounds structure) are unchanged; per-scenario q2 drift is
    absorbed by the refinement against K + diag(dq2).

    ``settings.sweep_precision`` routes this solve through the
    mixed-precision fast path: a lowered-precision sweep phase (f32-pinned
    residual bookkeeping) followed, when not eps-converged, by a bounded
    full-precision refinement phase on the same factors."""
    # TRACE-time counter: one per frozen shared-A program compiled
    _metrics.inc("shared_admm.frozen_programs")
    dt = settings.jdtype()
    c, q2, A, cl, cu, lb, ub, _ = _prep_shared(
        c, q2, A, cl, cu, lb, ub, settings, want_masks=False)
    D, E, cost = factors.D, factors.E, factors.cost
    qs, q2s, As, cls, cus, lbs, ubs, warm = _scale_shared(
        c, q2, A, cl, cu, lb, ub, D, E, cost, warm, dt)
    S, n = c.shape
    m = A.shape[0]
    if warm is None:
        x0 = jnp.zeros((S, n), dt)
        z0 = jnp.clip(jnp.zeros((S, m), dt), cls, cus)
        y0 = jnp.zeros((S, m), dt)
        yx0 = jnp.zeros((S, n), dt)
    else:
        x0, z0, y0, yx0 = warm
    zx0 = jnp.clip(x0, lbs, ubs)
    inf = jnp.full((S,), jnp.inf, dt)
    one = jnp.ones((S,), dt)
    state0 = _IterState(x0, z0, zx0, y0, yx0, factors.gamma,
                        inf, inf, one, one, jnp.zeros((), jnp.int32),
                        jnp.asarray(jnp.inf, dt),
                        jnp.zeros((), jnp.int32))

    lp_like = jnp.max(jnp.abs(q2s)) < 1e-12
    glo = jnp.where(lp_like, 1e-4, 0.6)
    ghi = jnp.where(lp_like, 1e4, 1.8)
    def run_core(st0, st, prec):
        return _core(qs, q2s, factors.q2ref, As, cls, cus, lbs, ubs, st0,
                     factors.Kinv, factors.K, factors.rho_a,
                     factors.rho_x, glo, ghi, st, prec=prec)

    state = _frozen_sweep_phases(run_core, state0, settings, dt)
    x, z, y, yx = (state.x * D[None, :], state.z / E[None, :],
                   state.y * E[None, :] / cost,
                   state.yx / D[None, :] / cost)
    return BatchSolution(
        x=x, z=z, y=y, yx=yx,
        pri_res=state.pri, dua_res=state.dua,
        iters=jnp.broadcast_to(state.k, (S,)),
        done=_done_mask(state.pri, state.dua, state.prinorm,
                        state.duanorm, settings),
        raw=(x, z, y, yx),
    )


@functools.partial(jax.jit, static_argnames=("settings",))
def solve_shared(c, q2, A, cl, cu, lb, ub,
                 settings: ADMMSettings = ADMMSettings(),
                 warm=None) -> BatchSolution:
    """Solve a shared-A batch: A is (m, n); everything else (S, ...)."""
    with jax.default_matmul_precision(settings.matmul_precision):
        return _solve_shared_impl(c, q2, A, cl, cu, lb, ub, settings, warm)


# AOT executable cache (tpusppy/solvers/aot.py): same warm-start wrapping
# as the dense entry points in admm.py — passthrough when disarmed
solve_shared = _aot.cached_program(solve_shared, "shared.solve",
                                   static_names=("settings",))


@functools.partial(jax.jit, static_argnames=("settings",))
def solve_shared_factored(c, q2, A, cl, cu, lb, ub,
                          settings: ADMMSettings = ADMMSettings(),
                          warm=None):
    """Adaptive shared-A solve that also returns :class:`SharedFactors`."""
    with jax.default_matmul_precision(settings.matmul_precision):
        return _solve_shared_impl(c, q2, A, cl, cu, lb, ub, settings, warm,
                                  want_factors=True)


solve_shared_factored = _aot.cached_program(
    solve_shared_factored, "shared.solve_factored",
    static_names=("settings",))


@functools.partial(jax.jit, static_argnames=("settings",))
def solve_shared_frozen(c, q2, A, cl, cu, lb, ub, factors: SharedFactors,
                        settings: ADMMSettings = ADMMSettings(),
                        warm=None) -> BatchSolution:
    """Jitted frozen-factor shared-A solve."""
    with jax.default_matmul_precision(settings.matmul_precision):
        return _solve_shared_frozen_impl(c, q2, A, cl, cu, lb, ub, factors,
                                         warm, settings)


solve_shared_frozen = _aot.cached_program(
    solve_shared_frozen, "shared.solve_frozen",
    static_names=("settings",))


# ---------------------------------------------------------------------------
# Device turns (solvers/turns.py): what a spoke of a wheel whose gate is
# engaged calls in place of the three programs above.  An adaptive solve
# goes restart by restart, each a program of its own taken in a turn (the
# restart is the scan body of the one-program solve, so the iterates are
# the same up to how XLA fuses them); a frozen solve is one piece.
# Anybody else's call passes through to the one-program solve.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("settings",))
def _setup_program(c, q2, A, cl, cu, lb, ub, settings, warm):
    with jax.default_matmul_precision(settings.matmul_precision):
        return _shared_setup(c, q2, A, cl, cu, lb, ub, settings, warm)


@functools.partial(jax.jit, static_argnames=("settings",))
def _restart_program(sc, carry, settings):
    with jax.default_matmul_precision(settings.matmul_precision):
        return _shared_restart(sc, carry, settings)


@functools.partial(jax.jit, static_argnames=("settings", "want_factors"))
def _finish_program(sc, carry, settings, want_factors):
    with jax.default_matmul_precision(settings.matmul_precision):
        return _shared_finish(sc, carry, settings, want_factors)


_pieces: dict = {}     # (piece, shapes, settings, ...) -> executable


def _piece(program, key, *args, **static):
    """``program`` compiled for ``args``, once a key: on the host and
    outside any turn, so that nobody waits at the gate for a compiler
    and no piece's seconds hold a compile."""
    exe = _pieces.get((program, key))
    if exe is None:
        exe = program.lower(*args, **static).compile()
        _pieces[program, key] = exe
    return exe


def adaptive_in_turns(c, q2, A, cl, cu, lb, ub,
                      settings: ADMMSettings = ADMMSettings(), warm=None,
                      want_factors=False):
    """:func:`solve_shared` (or :func:`solve_shared_factored` with
    ``want_factors``), one restart a device turn where the caller is a
    spoke of an engaged gate."""
    if isinstance(A, SparseA) or not _turns.pieces():
        fn = solve_shared_factored if want_factors else solve_shared
        return fn(c, q2, A, cl, cu, lb, ub, settings=settings, warm=warm)
    dt = settings.jdtype()
    args = tuple(jnp.asarray(a, dt) for a in (c, q2, A, cl, cu, lb, ub))
    if warm is not None:
        warm = tuple(jnp.asarray(w, dt) for w in warm)
    key = (args[0].shape, args[2].shape, settings)
    setup = _piece(_setup_program, key + (warm is None,), *args,
                   settings=settings, warm=warm)
    shapes = _pieces.get(("shapes", key, warm is None))
    if shapes is None:
        shapes = _pieces["shapes", key, warm is None] = jax.eval_shape(
            functools.partial(_setup_program, settings=settings), *args,
            warm=warm)
    restart = _piece(_restart_program, key, *shapes, settings=settings)
    finish = _piece(_finish_program, key + (want_factors,), *shapes,
                    settings=settings, want_factors=want_factors)
    last = max(1, settings.restarts) - 1
    sc = carry = out = None
    for r in range(last + 1):
        with _turns.chunk():
            if r == 0:
                sc, carry = setup(*args, warm=warm)
            carry = restart(sc, carry)
            if r == last:
                out = finish(sc, carry)
            jax.block_until_ready(carry[0].x if out is None else out)
    return out


def frozen_in_turn(*args, **kw):
    """:func:`solve_shared_frozen` as one piece of a spoke's turn."""
    with _turns.chunk() as held:
        sol = solve_shared_frozen(*args, **kw)
        if held:
            jax.block_until_ready(sol.x)
    return sol
