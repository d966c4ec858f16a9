"""Batched OSQP-style ADMM QP/LP solver in JAX — the TPU-native subproblem engine.

This replaces the reference's external-MIP-solver hot loop (``solve_one`` /
``solve_loop``, spopt.py:85-307, and the persistent-solver objective refresh at
spopt.py:129-144): the entire local scenario batch is solved by ONE device
program — batched dense Cholesky factorizations ride the MXU, the ADMM sweep is a
``lax.while_loop``, and PH's per-iteration objective update is just new (q, rho)
tensors plus a warm start.

Canonical form per scenario (see :mod:`tpusppy.ir`):

    minimize    0.5 x' diag(q2) x + c' x
    subject to  cl <= A x <= cu,   lb <= x <= ub

Splitting (OSQP, Stellato et al.): introduce z_a = A x and z_x = x; the
variable-bound block is an implicit identity that never gets materialized — it
contributes only diagonal terms to the KKT system:

    (diag(q2) + sigma I + A' R_a A + R_x) x~ =
        sigma x - q + A'(R_a z_a - y_a) + (R_x z_x - y_x)

with per-row penalties R (equality rows boosted, free rows damped).  Ruiz
equilibration preconditions the batch; adaptive-rho restarts refactorize (cheap
for the dense sizes scenarios have).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import aot as _aot

BIG = 1e20  # stand-in for +inf inside kernels (keeps arithmetic finite)


@dataclasses.dataclass(frozen=True)
class ADMMSettings:
    sigma: float = 1e-6
    alpha: float = 1.6
    rho: float = 0.1
    rho_eq_scale: float = 1e3
    rho_min: float = 1e-6
    rho_max: float = 1e6
    max_iter: int = 1000          # inner iterations per rho setting
    restarts: int = 4             # rho-adaptation refactorizations
    check_every: int = 4          # sweeps per termination check (unrolled)
    solve_refine: int = 2         # refinement passes per x-update solve
    eps_abs: float = 1e-8
    eps_rel: float = 1e-8
    scaling_iters: int = 10
    polish: bool = True           # active-set KKT polish (OSQP-style)
    polish_passes: int = 4        # active-set correction passes
    polish_delta: float = 1e-8
    # Fused Pallas sweep kernel (scenario-on-lanes layout).  "auto"
    # (default) enables it in its MEASURED win regime on TPU — dense
    # batches whose block partition is fine-grained (n big enough that a
    # block is <=512 scenarios: 2.0x at S=1000 n=44, 6.5x at S=10000
    # n=44) or single-block (1.14x at S=1000 n=11) — and stays off where
    # it measured slower (many coarse blocks: 0.68x at S=10000 n=11).
    # True forces it wherever usable; False disables.
    use_pallas: bool | str = "auto"
    # Per-ROW rho adaptation between restarts: rows (and variable boxes) with
    # persistent primal violation get their penalty boosted.  Cures ADMM
    # stalls on strongly-coupled LPs (UC's ramp/genlim rows) that global rho
    # adaptation cannot fix — the global ratio is balanced while a handful of
    # rows are far from feasible.
    rho_row_adapt: bool = True
    rho_row_boost: float = 10.0
    rho_row_max: float = 1e6
    dtype: str = "float64"
    # Carry the exact K inside SharedFactors for dense refinement ("True",
    # fastest sweeps) or drop it and refine matrix-free through the shared A
    # ("False", ~1 GB less HBM per factors at reference UC shapes — the host
    # wheel path defaults this off via SPBase since several cylinders'
    # factors coexist on one chip).
    factors_keep_K: bool = True
    # Segmented continuations stop when one whole extra segment improves
    # the worst scaled residual by less than this fraction (plateau):
    # first-order batches on hard LP families park at a residual floor
    # regardless of budget, and further dispatches are pure waste.  0
    # disables (always run the full sweep budget).
    segment_plateau_rtol: float = 0.05
    # Matmul precision for the solve programs.  "highest" = full f32
    # (bf16x6 passes on TPU MXU — ~6x the flops of plain bf16); "high" =
    # bf16x3; "default" = bf16.  Lower precisions trade residual floor for
    # sweep throughput; certified-bound programs (dual_objective/dual_cut)
    # always run "highest" regardless.
    matmul_precision: str = "highest"
    # Mixed-precision FROZEN sweep engine (solvers/precision.py; see
    # doc/precision.md).  None (the default) leaves every path exactly as
    # before; "default" (bf16) or "high" (bf16x3) runs the frozen sweep
    # phase at lowered MXU precision — with the x-update defect and ALL
    # residual bookkeeping pinned to full f32, so the OSQP termination
    # test stays trustworthy — then, if not eps-converged, a bounded
    # full-precision refinement phase (``precision_refine_iters`` sweeps
    # on the SAME cached factors) restores the f32 residual floor.
    # Refresh/adaptive solves and certified-bound programs are never
    # lowered.  The autotuner (tpusppy.tune) picks this per shape: the
    # fastest mode whose warmup residuals certify.
    sweep_precision: str | None = None
    # f32 refinement sweep budget appended to a low-precision frozen sweep
    # phase that did not reach eps (skipped entirely when it did — the
    # f32-measured residuals already certify the iterate).
    precision_refine_iters: int = 64
    # Host-side fallback guard (spopt._solve_amortized): a low-precision
    # frozen solve whose worst residual exceeds ``precision_guard`` x the
    # last full-precision refresh floor (and is not converged) is re-run
    # at full precision on the same factors.  <= 0 disables.
    precision_guard: float = 10.0
    # In-loop plateau exit: leave the sweep while_loop when the batch-worst
    # eps-normalized residual improved by less than this fraction over each
    # of 2 consecutive windows of ``sweep_plateau_window`` sweeps.  Hard LP
    # families (reference-scale UC) park at a residual floor far above eps,
    # and every further sweep is waste — the segment-level host detector
    # (``segmented.continue_frozen``) catches the same condition only at
    # whole-dispatch granularity and burns 2 extra dispatches proving it.
    # 0 disables.  ``BatchSolution.done`` reports true eps-convergence, so
    # a plateau exit is never mistaken for convergence by callers.
    sweep_plateau_rtol: float = 0.0
    sweep_plateau_window: int = 32
    # Overlapped dispatch pipeline (doc/pipeline.md): segmented frozen
    # continuations speculatively launch segment k+1 from segment k's
    # device-resident iterate BEFORE fetching segment k's stop-stats, so
    # the per-segment host RPC overlaps device compute.  Results are
    # identical to the serial protocol (speculative segments are
    # discarded when the verdict says stop; waste is bounded at one
    # segment and billed against the sweep budget).  False forces the
    # legacy serial fetch-then-dispatch protocol everywhere (the
    # ``admm_pipeline`` config flag).  Host-dispatch-only: the traced
    # programs are unchanged.
    pipeline: bool = True
    # Device-resident wheel megakernel (doc/pipeline.md): the PH hub runs
    # N wheel iterations (frozen solve + xbar/W outer update) in ONE
    # donated lax.scan dispatch and fetches ONE packed measurement per
    # megastep instead of one per iteration.  0 = auto (the hub picks N
    # from the autotuner's banked verdict when one exists, else from the
    # refresh cadence clamped by the watchdog cap —
    # ``segmented.megastep_cap``); 1 forces the legacy per-iteration
    # dispatch everywhere (the ``admm_megastep`` config flag); k > 1
    # requests that N (still watchdog-clamped).  Host-dispatch-only for
    # the legacy toggle: the per-iteration traced programs are unchanged.
    megastep: int = 0

    def jdtype(self):
        dt = jnp.dtype(self.dtype)
        if dt == jnp.float64 and not jax.config.jax_enable_x64:
            # jax would truncate to float32 in silence and the run would
            # hold f32 iterates to f64 tolerances
            raise ValueError(
                "ADMMSettings(dtype='float64') needs jax_enable_x64; on "
                "the TPU pass solver_options={'dtype': 'float32', "
                "'eps_abs': 1e-5, 'eps_rel': 1e-5} (README, \"Testing\")")
        return dt

    def sweep_mode(self) -> str:
        """Effective frozen-sweep matmul precision (for MFU/report use)."""
        return self.sweep_precision or self.matmul_precision


class BatchSolution(NamedTuple):
    x: jax.Array       # (S, n)
    z: jax.Array       # (S, m) constraint-row auxiliaries
    y: jax.Array       # (S, m) constraint-row duals
    yx: jax.Array      # (S, n) variable-bound duals
    pri_res: jax.Array  # (S,)
    dua_res: jax.Array  # (S,)
    iters: jax.Array   # (S,) total inner iterations used (same for all)
    done: jax.Array    # (S,) met the eps tolerances (False = budget spent or
    # plateau exit) — callers must use this, never an iters-vs-cap compare,
    # to decide convergence (the plateau exit leaves the loop early)
    raw: tuple         # pre-polish (x, z, y, yx) — the ONLY valid warm start
    # (polished states are exact-KKT candidates, not consistent ADMM
    # iterates; feeding them back as warm starts destabilizes later solves)
    # how much of the batch was still being swept (``_admm_core``'s
    # narrowing; None from the shared-A engine, whose one loop runs at full
    # width: ``width_counters`` reads either)
    narrow: jax.Array | None = None  # (S,) sweeps run below full width
    # (same for all, like ``iters``)
    swept: jax.Array | None = None   # (S,) sweeps each row was carried
    # through: their sum over ``iters`` x S is the share of the full-width
    # work done


class _Scaling(NamedTuple):
    D: jax.Array       # (S, n) column scaling
    E: jax.Array       # (S, m) row scaling
    cost: jax.Array    # (S,) objective scaling


class Factors(NamedTuple):
    """Reusable solve state for the frozen-factor path.

    PH changes only the linear term between iterations (spopt.py:129-144 is
    the reference's persistent-solver analogue); the Ruiz scaling, the adapted
    rho vectors, and the KKT factorization all depend only on (A, q2, bounds)
    — so they can be computed once at a "refresh" solve and reused for many
    cheap sweep-only solves.  On TPU this removes the batched factorization
    (the dominant per-iteration cost) from the steady-state PH iteration.
    """

    D: jax.Array       # (S, n) Ruiz column scaling
    E: jax.Array       # (S, m) Ruiz row scaling
    cost: jax.Array    # (S,) objective scaling
    rho_a: jax.Array   # (S, m) row penalties actually used last
    rho_x: jax.Array   # (S, n) variable-box penalties actually used last
    Kinv: jax.Array    # (S, n, n) explicit inverse of the x-update system
    K: jax.Array       # (S, n, n) exact K for iterative refinement


class _BoundMasks(NamedTuple):
    """Finiteness/equality classification of the UNSCALED bounds."""

    fin_cl: jax.Array  # (S, m) lower row bound finite
    fin_cu: jax.Array  # (S, m) upper row bound finite
    fin_lb: jax.Array  # (S, n) lower var bound finite
    fin_ub: jax.Array  # (S, n) upper var bound finite
    eq: jax.Array      # (S, m) equality row
    eqx: jax.Array     # (S, n) zero-width variable box (clamped column)


def _clean_bounds(lo, hi):
    lo = jnp.nan_to_num(lo, nan=-BIG, neginf=-BIG, posinf=BIG)
    hi = jnp.nan_to_num(hi, nan=BIG, neginf=-BIG, posinf=BIG)
    return jnp.maximum(lo, -BIG), jnp.minimum(hi, BIG)


def _ruiz(A, q2, iters):
    """Ruiz equilibration of [P A'; A 0] restricted to diagonal scalings.

    Returns (D, E) with the scaled matrix E A D having ~unit inf-norm rows/cols.
    Batched over the leading axis by construction (all ops are elementwise or
    row/col reductions).
    """
    S, m, n = A.shape
    D = jnp.ones((S, n), A.dtype)
    E = jnp.ones((S, m), A.dtype)

    def body(_, DE):
        D, E = DE
        As = A * E[:, :, None] * D[:, None, :]
        Ps = q2 * D * D
        col = jnp.maximum(jnp.max(jnp.abs(As), axis=1), jnp.abs(Ps))
        row = jnp.max(jnp.abs(As), axis=2)
        # empty rows/columns (e.g. cut slots not yet populated, objective-only
        # variables) must keep unit scaling: dividing by sqrt(eps) each sweep
        # compounds into astronomically wrong D/E otherwise
        col = jnp.where(col < 1e-12, 1.0, col)
        row = jnp.where(row < 1e-12, 1.0, row)
        D = D / jnp.sqrt(col)
        E = E / jnp.sqrt(row)
        return D, E

    D, E = jax.lax.fori_loop(0, iters, body, (D, E))
    return D, E


def _lanes_bs(st: "ADMMSettings | None", S, N, dt, R=1):
    """Block size when ``pallas_kernels.lanes_solve`` takes a batch of S
    (N, N) systems with R right-hand sides each (1: the polish's saddle
    systems; N: an inverse), else None (the XLA path).
    ``use_pallas=False`` turns the kernel off as it turns the sweep kernel
    off; "auto", True and a caller with no settings (``st=None``) follow
    ``usable_solve`` (TPU, float32, a batch of 128 or more, the VMEM
    budget)."""
    if st is not None and st.use_pallas is False:
        return None
    from . import pallas_kernels
    return pallas_kernels.usable_solve(S, N, R, dtype=dt)


def lanes_linalg(st: "ADMMSettings", S, m, n) -> bool:
    """Whether an adaptive (refresh) solve of an (S, m, n) dense batch
    runs its polish on ``lanes_solve``: the host's twin of the choice
    ``_polish`` makes while tracing (spopt counts ``refresh.lanes_linalg``
    by it)."""
    return bool(st.polish
                and _lanes_bs(st, S, n + m, st.jdtype()) is not None)


def lanes_inverse(st: "ADMMSettings", S, m, n) -> bool:
    """Whether an adaptive (refresh) solve of an (S, m, n) dense batch
    inverts its K's on ``lanes_solve``: the host's twin of the choice
    ``_explicit_inverse`` makes while tracing (spopt counts
    ``refresh.lanes_inverse`` by it)."""
    return _lanes_bs(st, S, n, st.jdtype(), R=n) is not None


def _factor(q2, A, rho_a, rho_x, st: "ADMMSettings", P=None):
    """Inverse of K = P + diag(q2) + sigma I + A' diag(rho_a) A + diag(rho_x).

    ``P`` is an optional dense (S, n, n) quadratic term (FWPH's simplex QP and
    other column-space problems need one); the diagonal-only path stays the
    default.  Returns (Kinv, K); K is kept for iterative refinement of the
    applies of Kinv — essential in float32, where cond(K) ~ 1/sigma *
    rho_eq_scale otherwise stalls ADMM around 1e-2 residuals.
    """
    n = A.shape[-1]
    K = jnp.einsum("smn,sm,smk->snk", A, rho_a, A)
    K = K + jnp.eye(n, dtype=A.dtype)[None] * st.sigma
    K = K + jax.vmap(jnp.diag)(q2 + rho_x)
    if P is not None:
        K = K + P
    # Explicit inverse: triangular substitution is SEQUENTIAL on TPU
    # (length-n dependency chain per solve), so the hot loop applies K^-1
    # as one matrix product per solve instead.  Iterative refinement against
    # the exact K (kept alongside) recovers the digits the explicit inverse
    # loses — cheaper than two triangular sweeps per inner iteration.
    return _explicit_inverse(K, st), K


# Matrices larger than 2 * this go through the recursive Schur inversion,
# avoiding XLA:TPU's TriangularSolve lowering at big n: one
# (16008, 16008) \ (16008, 2048) solve compiles to 9.2 GB of HLO temps
# (chunked substitution keeps ~n/128 O(n*rhs) accumulator copies live),
# which OOMed the headline UC refresh program at 62 GB demand on a 16 GB
# chip.  The recursion is pure MXU matmuls — measured at n=16008: 1.2 GB
# temps, 1.6 s steady-state (8x faster than the triangular path),
# comparable f32 accuracy (iterative refinement against the exact K in
# _chol_solve covers the rest).  Base cases — up to 2x the leaf size, i.e.
# n <= 4096 — still use Cholesky + triangular solves, where the lowering
# is cheap.
_EXPLICIT_INV_LEAF_N = 2048


def _explicit_inverse(K, st=None):
    """K^-1 of an SPD batch.

    A batch that ``pallas_kernels.usable_solve`` takes (TPU, float32, 128
    or more matrices, n <= 45 by its VMEM budget; ``st.use_pallas`` not
    False) is inverted by ``lanes_solve`` against the identity: Gaussian
    elimination with partial pivoting, the batch on the lanes.  XLA:TPU's
    Cholesky and two triangular solves walk the n columns one dependent
    step at a time over arrays laid out batch-outermost: 6.0 ms where the
    kernel takes 0.95 at farmer's (1000, 44, 44) (PERF.md section 7).  Both
    are backward stable on an SPD matrix, and every consumer refines
    against the exact K.  A batch of 1 (what the shared-A engine hands
    over) never qualifies.

    Otherwise recursive blocked Schur inversion:
    inv([[A, B], [B', C]]) = [[Ai + W Si W', -W Si], [-Si W', Si]] with
    Ai = inv(A), W = Ai B, Si = inv(C - B' Ai B); Schur complements of SPD
    are SPD, so the recursion is well posed.  Base cases (n <= 2 * leaf =
    4096) use Cholesky + triangular solves against I, where XLA's lowering
    is cheap.  Split points are multiples of the leaf size for tidy MXU
    tiling.
    """
    n = K.shape[-1]
    bs = _lanes_bs(st, K.shape[0], n, K.dtype, R=n) if K.ndim == 3 else None
    if bs is not None:
        from . import pallas_kernels
        eye = jnp.broadcast_to(jnp.eye(n, dtype=K.dtype)[:, :, None],
                               (n, n, K.shape[0]))
        return jnp.transpose(
            pallas_kernels.lanes_solve(jnp.transpose(K, (1, 2, 0)), eye,
                                       bs=bs), (2, 0, 1))
    leaf = _EXPLICIT_INV_LEAF_N
    if n <= 2 * leaf:
        # XLA:TPU's blocked TriangularSolve lowering has a broken window
        # when the diagonal block IS the (sub-128) matrix: 64 < n < 128
        # allocates a fixed 18.95 MB of scoped VMEM (> the 16 MB limit)
        # in InvertDiagBlocksLowerTriangular regardless of batch size —
        # observed at n=88 for batches 139/190/1000 alike, while n=44
        # (unblocked path) and n>=128 (128-wide diag blocks) compile fine.
        # Embed K into a 128x128 identity-extended SPD and slice back.
        # TPU-only (trace-time check): other backends' lowerings are fine
        # and would just pay ~3x the flops for the padding.  (The lanes
        # kernel above stops at n = 45, so this window pads either way.)
        if 64 < n < 128 and jax.default_backend() == "tpu":
            pad = 128 - n
            eye_pad = jnp.eye(128, dtype=K.dtype)[n:, :]
            Kp = jnp.concatenate([
                jnp.concatenate(
                    [K, jnp.zeros(K.shape[:-1] + (pad,), K.dtype)], axis=-1),
                jnp.broadcast_to(eye_pad, K.shape[:-2] + (pad, 128)),
            ], axis=-2)
            return _explicit_inverse_oneshot(Kp)[..., :n, :n]
        return _explicit_inverse_oneshot(K)
    return _explicit_inverse_schur(K)


def _explicit_inverse_oneshot(K):
    """Cholesky + two triangular solves against I (small/medium n)."""
    n = K.shape[-1]
    L = jnp.linalg.cholesky(K)
    eye = jnp.broadcast_to(jnp.eye(n, dtype=K.dtype), K.shape)
    t = jax.scipy.linalg.solve_triangular(L, eye, lower=True)
    return jax.scipy.linalg.solve_triangular(L, t, lower=True, trans=1)


def _explicit_inverse_schur(K):
    n = K.shape[-1]
    leaf = _EXPLICIT_INV_LEAF_N
    h = ((n // 2 + leaf - 1) // leaf) * leaf
    A = K[..., :h, :h]
    B = K[..., :h, h:]
    C = K[..., h:, h:]
    Ai = _explicit_inverse(A)
    AiB = Ai @ B
    Si = _explicit_inverse(C - jnp.swapaxes(B, -1, -2) @ AiB)
    TR = -(AiB @ Si)
    TL = Ai - TR @ jnp.swapaxes(AiB, -1, -2)
    top = jnp.concatenate([TL, TR], axis=-1)
    bot = jnp.concatenate([jnp.swapaxes(TR, -1, -2), Si], axis=-1)
    return jnp.concatenate([top, bot], axis=-2)


def _chol_solve(LK, b, refine=2, prec=None):
    """K^-1 b via the explicit inverse + refinement against the exact K.

    ``prec``: None = legacy path (ambient matmul precision, unchanged
    programs).  A mode string runs the Kinv applies at that precision
    while the DEFECT ``b - K x`` stays pinned at full f32 — the classic
    mixed-precision iterative-refinement split (defect at high precision,
    correction at low)."""
    Kinv, K = LK
    if prec is None:
        x = jnp.einsum("snk,sk->sn", Kinv, b)
        for _ in range(refine):
            r = b - jnp.einsum("snk,sk->sn", K, x)
            x = x + jnp.einsum("snk,sk->sn", Kinv, r)
        return x
    from . import precision
    x = precision.contract("snk,sk->sn", Kinv, b, prec)
    for _ in range(refine):
        r = b - precision.contract("snk,sk->sn", K, x, "highest")
        x = x + precision.contract("snk,sk->sn", Kinv, r, prec)
    return x


class _IterState(NamedTuple):
    x: jax.Array
    z: jax.Array   # (S, m)
    zx: jax.Array  # (S, n)
    y: jax.Array
    yx: jax.Array
    pri: jax.Array
    dua: jax.Array
    prinorm: jax.Array
    duanorm: jax.Array
    k: jax.Array
    best: jax.Array   # scalar: best batch-worst eps-normalized residual
    stall: jax.Array  # scalar int32: consecutive non-improving windows
    # what the narrowing sweep loop spent (``_admm_core``), summed over a
    # solve's core runs: no core run resets them
    narrow: jax.Array  # scalar int32: sweeps (of k) run below full width
    swept: jax.Array   # (S,) int32: sweeps each row was carried through
    since: jax.Array   # (S,) int32: sweeps the row has now passed the test
    # for, checkpoint after checkpoint (0: it does not pass); kept only
    # where the loop has a narrower rung to leave the row out of.  An
    # adaptive solve's restarts carry it on: a row that is done keeps its
    # rho (``_solve_scaled``: ``where(done, base, new_base)``, no boost), so
    # the next restart would sweep it on at the factors it was left at


def _start_state(x0, z0, zx0, y0, yx0):
    """The iterate a solve starts from: nothing measured, nothing swept."""
    S, dt = x0.shape[0], x0.dtype
    inf = jnp.full((S,), jnp.inf, dt)
    one = jnp.ones((S,), dt)
    zero = jnp.zeros((), jnp.int32)
    return _IterState(x0, z0, zx0, y0, yx0, inf, inf, one, one, zero,
                      jnp.asarray(jnp.inf, dt), zero, zero,
                      jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32))


def _done_mask(pri, dua, prinorm, duanorm, st: ADMMSettings):
    """Per-scenario eps-convergence (the while_loop's own OSQP test)."""
    eps_pri = st.eps_abs + st.eps_rel * jnp.maximum(prinorm, 1.0)
    eps_dua = st.eps_abs + st.eps_rel * jnp.maximum(duanorm, 1.0)
    return (pri < eps_pri) & (dua < eps_dua)


def _plateau_update(s, pri, dua, prinorm, duanorm, st: ADMMSettings,
                    min_k=0):
    """(best, stall) update at a residual checkpoint; evaluated every
    ``sweep_plateau_window`` sweeps.

    The progress metric is the GEOMETRIC MEAN of per-scenario
    eps-normalized residual excesses (clipped to [1, 1e6]): converged
    scenarios contribute a neutral 1 (so scenarios crossing eps register
    as progress), a NaN/diverged scenario contributes the constant cap
    instead of poisoning the whole batch, and — unlike a batch-max — one
    parked scenario cannot stall the detector while the rest are still
    descending (stopping is all-or-nothing for the batched loop, so the
    exit must wait for COLLECTIVE stagnation; the host rescue ladder owns
    the per-scenario stragglers afterwards).

    ``min_k``: stall counting starts only at checkpoints past this sweep
    index — the shared engine's ADAPTIVE solve passes its in-loop gamma
    cadence so the exit cannot preempt the first adaptation opportunity
    (a batch that stalls precisely until gamma moves would otherwise be
    abandoned at 3 windows); its frozen solves, whose gamma is already
    adapted, pass 0 and keep the earliest exit."""
    eps_pri = st.eps_abs + st.eps_rel * jnp.maximum(prinorm, 1.0)
    eps_dua = st.eps_abs + st.eps_rel * jnp.maximum(duanorm, 1.0)
    excess = jnp.maximum(pri / eps_pri, dua / eps_dua)
    excess = jnp.clip(jnp.nan_to_num(excess, nan=1e6, posinf=1e6), 1.0, 1e6)
    gmean = jnp.exp(jnp.mean(jnp.log(excess)))
    ck = max(1, st.check_every)
    # ceil-divide: a window below (or not a multiple of) check_every must
    # round UP to the next checkpoint, not silently shrink the effective
    # window and fire the exit earlier than configured
    period = max(1, -(-st.sweep_plateau_window // ck))
    due = (((s.k // ck) + 1) % period == 0) & (s.k >= min_k)
    # near-eps grace: once the batch gmean sits within rtol of eps the
    # >=1 floor makes fractional improvement unmeasurable, so a batch 2
    # windows from crossing eps would be force-exited — treat that zone
    # as improving and let it finish (a batch PARKED there runs out its
    # budget instead, which is bounded and effectively converged anyway)
    improved = (gmean < (1.0 - st.sweep_plateau_rtol) * s.best) | (
        gmean <= 1.0 + st.sweep_plateau_rtol)
    stall = jnp.where(due, jnp.where(improved, 0, s.stall + 1), s.stall)
    best = jnp.where(due, jnp.minimum(s.best, gmean), s.best)
    return best, stall


def _sweep_block(st: ADMMSettings, S, m, n, P=None, prec=None):
    """``(bs, kprec)``: the block of ``pallas_kernels.fused_sweeps`` at a
    batch of S (None: XLA's sweep) and the precision the kernel stores its
    matrices in."""
    from . import pallas_kernels

    if isinstance(st.use_pallas, str) and st.use_pallas != "auto":
        raise ValueError(
            f"use_pallas must be True, False, or 'auto'; got "
            f"{st.use_pallas!r} (strings other than 'auto' would silently "
            f"force the kernel on)")
    # dense-kernel precision: "default" stores the matrices in bf16 (halved
    # VMEM per scenario, bf16-rounded operands); "high" keeps f32 — the
    # kernel's VPU contractions run full f32 anyway, so bf16x3 has nothing
    # to save there (the kernel is then at least as accurate as the mode
    # asks; see pallas_kernels.fused_sweeps)
    kprec = "default" if prec == "default" else "highest"
    if st.use_pallas == "auto":
        bs = pallas_kernels.usable(S, m, n, P=P, precision=kprec)
        if bs is not None and bs < S and bs > 512:
            bs32 = (pallas_kernels.usable(S, m, n, P=P)
                    if kprec == "default" else bs)
            if (kprec == "default" and bs32 is not None
                    and not (bs32 < S and bs32 > 512)):
                # bf16 storage WIDENED an f32-ACCEPTED block into the
                # measured-loss band: clamp back to the band's top — the
                # mode's VMEM dividend must never turn the kernel OFF for
                # a shape the f32 path accepts.  Shapes the f32 heuristic
                # itself rejects stay rejected (the loss regime was
                # measured; bf16 storage doesn't re-litigate it).
                bs = 512
            else:
                bs = None      # measured-loss regime (many coarse blocks)
    elif st.use_pallas:
        bs = pallas_kernels.usable(S, m, n, P=P, precision=kprec)
    else:
        bs = None
    return bs, kprec


def kernel_checkpoint(st: ADMMSettings, S, m, n) -> bool:
    """Whether an adaptive (refresh) solve of an (S, m, n) dense batch runs
    each step of its sweep loop as one kernel call that makes the residuals
    too (``_lanes_sweep_loop`` at full precision, as a refresh always is):
    the host's twin of the choice ``_sweep_block`` makes while tracing
    (spopt counts ``refresh.kernel_checkpoint`` by it)."""
    return _sweep_block(st, S, m, n)[0] is not None


def _sweep_loop(q, q2, A, cl, cu, lb, ub, state, LK, rho_a, rho_x,
                st: ADMMSettings, P=None, prec=None, leave_at=None):
    """The sweep loop at fixed rho and at the width of its arguments:
    runs until the budget is spent or every row is done; under
    ``_admm_core``'s cascade (``leave_at`` not None) it keeps
    ``state.since`` and, where ``leave_at`` is not 0, also leaves once at
    most that many rows are not settled (``_settled``).  Returns final
    state.  Where ``_sweep_block`` picks a block, the ``while_loop`` is
    ``_lanes_sweep_loop``'s (one kernel call a step); the test ``cont`` and
    the bookkeeping ``checkpoint`` are the same for both.

    ``prec``: None keeps the legacy (ambient-precision) program
    byte-for-byte; a mode string runs the SWEEP matvecs at that precision
    (solvers/precision.py) while residual bookkeeping and the
    checkpoint Ax re-anchor stay pinned at full f32 — so the while_loop's
    OSQP test measures true residuals whatever the sweep mode."""
    sigma, alpha = st.sigma, st.alpha

    if prec is None:
        lo = hi = lambda spec, a, b: jnp.einsum(spec, a, b)
    else:
        from . import precision
        lo = lambda spec, a, b: precision.contract(spec, a, b, prec)
        hi = lambda spec, a, b: precision.contract(spec, a, b, "highest")

    def Px(x):
        base = q2 * x
        if P is not None:
            base = base + hi("snk,sk->sn", P, x)
        return base

    def sweep(x, z, zx, y, yx, Ax):
        """One ADMM sweep WITHOUT residual bookkeeping.  Ax is carried
        incrementally (Ax_new = alpha*Axt + (1-alpha)*Ax), saving one matvec
        per sweep."""
        rhs = (
            sigma * x - q
            + lo("smn,sm->sn", A, rho_a * z - y)
            + (rho_x * zx - yx)
        )
        xt = _chol_solve(LK, rhs, refine=st.solve_refine, prec=prec)
        Axt = lo("smn,sn->sm", A, xt)
        x_new = alpha * xt + (1 - alpha) * x
        Ax_new = alpha * Axt + (1 - alpha) * Ax

        za_arg = alpha * Axt + (1 - alpha) * z + y / rho_a
        z_new = jnp.clip(za_arg, cl, cu)
        y_new = y + rho_a * (alpha * Axt + (1 - alpha) * z - z_new)

        zx_arg = alpha * xt + (1 - alpha) * zx + yx / rho_x
        zx_new = jnp.clip(zx_arg, lb, ub)
        yx_new = yx + rho_x * (alpha * xt + (1 - alpha) * zx - zx_new)
        return x_new, z_new, zx_new, y_new, yx_new, Ax_new

    def cont(s):
        # OSQP termination: eps_abs + eps_rel * residual-scale norms
        done = _done_mask(s.pri, s.dua, s.prinorm, s.duanorm, st)
        go = (s.k < st.max_iter) & ~jnp.all(done)
        if leave_at:
            # the rung of ``_admm_core`` holds the rows left
            go = go & (jnp.sum(~_settled(s)) > leave_at)
        if st.sweep_plateau_rtol > 0:
            go = go & (s.stall < 2)
        return go

    ck = max(1, st.check_every)

    def checkpoint(s, x, z, zx, y, yx, res):
        """The state after one step: the iterate, its residual rows and the
        (S,) bookkeeping on them."""
        pri, dua, prinorm, duanorm = res
        if st.sweep_plateau_rtol > 0:
            best, stall = _plateau_update(s, pri, dua, prinorm, duanorm, st)
        else:
            best, stall = s.best, s.stall
        since = s.since
        if leave_at is not None:
            since = jnp.where(_done_mask(pri, dua, prinorm, duanorm, st),
                              since + ck, 0)
        return _IterState(x, z, zx, y, yx, pri, dua, prinorm, duanorm,
                          s.k + ck, best, stall, s.narrow, s.swept, since)

    S, m, n = A.shape
    bs, kprec = _sweep_block(st, S, m, n, P, prec)
    if bs is not None:
        return _lanes_sweep_loop(q, q2, A, cl, cu, lb, ub, state, LK, rho_a,
                                 rho_x, st, bs, kprec, cont, checkpoint)

    def multi_step(carry):
        # unrolled sweeps between termination checks: each sweep is a handful
        # of tiny batched matvecs, so per-iteration overhead and residual
        # bookkeeping are amortized over check_every sweeps
        s, Ax = carry
        x, z, zx, y, yx = s.x, s.z, s.zx, s.y, s.yx
        for _ in range(ck):
            x, z, zx, y, yx, Ax = sweep(x, z, zx, y, yx, Ax)
        # re-anchor the incrementally carried Ax: the relaxation combination
        # (alpha=1.6) amplifies carried floating error exponentially across
        # sweeps, so one true matvec per checkpoint resets the drift
        # (pinned f32 under a low sweep mode — the defect control)
        Ax = hi("smn,sn->sm", A, x)
        res = residual_rows(
            q, x, z, zx, y, yx, Ax, lambda y: hi("smn,sm->sn", A, y), Px,
            lambda v: jnp.max(jnp.abs(v), axis=1))
        return checkpoint(s, x, z, zx, y, yx, res), Ax

    Ax0 = jnp.einsum("smn,sn->sm", A, state.x)
    state, _ = jax.lax.while_loop(lambda carry: cont(carry[0]), multi_step,
                                  (state, Ax0))
    return state


def residual_rows(q, x, z, zx, y, yx, Ax, Aty_of, Px_of, top):
    """``(pri, dua, prinorm, duanorm)`` of an iterate, one value a scenario:
    the residuals of the sweep loop's test and the OSQP-normalized scales
    for its tolerances and the rho adaptation.  One set of formulas for
    every layout the loop runs in: ``Ax`` is a TRUE product of ``x``,
    ``Aty_of(y)`` gives ``A' y``, ``Px_of(x)`` the quadratic term's product
    and ``top(v)`` the largest magnitude of a scenario's entries (the XLA
    sweep's rows; the same on the lanes, in XLA's hands under the lowered
    mode and in ``pallas_kernels.fused_sweeps`` otherwise)."""
    pri = jnp.maximum(top(Ax - z), top(x - zx))
    Aty = Aty_of(y)
    Pxv = Px_of(x)
    dua = top(Pxv + q + Aty + yx)
    prinorm = jnp.maximum(top(Ax), top(z))
    duanorm = jnp.maximum(jnp.maximum(top(Pxv), top(Aty)), top(q))
    return pri, dua, prinorm, duanorm


def _lanes_sweep_loop(q, q2, A, cl, cu, lb, ub, state, LK, rho_a, rho_x,
                      st: ADMMSettings, bs, kprec, cont, checkpoint):
    """``_sweep_loop``'s ``while_loop`` where the fused Pallas sweep block
    runs it (``_sweep_block`` picked ``bs``): all matrices stay in VMEM
    across the check_every sweeps instead of re-streaming from HBM every
    sweep, in scenario-on-lanes layout.  Matrices and state are transposed
    ONCE per rho setting, on the way in, and the state once on the way out:
    a step of the loop is one kernel call, which hands back the residual
    rows of the iterate it ends on, and ``checkpoint``'s (S,) bookkeeping.

    Under the lowered sweep mode (``kprec == "default"``) the kernel holds
    ``A`` in bf16, and the residuals are pinned to float32 operands
    (doc/precision.md): their products stay XLA's, on the lanes-layout
    state and the float32 ``A`` from before the cast."""
    from . import pallas_kernels

    S, m, n = A.shape
    Kinv, K = LK
    tT = lambda a: jnp.transpose(a, (1, 2, 0))
    AT, AtT = tT(A), jnp.transpose(A, (2, 1, 0))
    KinvT, KT = tT(Kinv), tT(K)
    qT, q2T = q.T, q2.T
    lowered = kprec == "default"
    if lowered:
        from . import precision
        hi = lambda spec, a, b: precision.contract(spec, a, b, "highest")
        A32 = AT
        # bf16 storage for the sweep matrices (halved VMEM -> bigger
        # blocks); K stays f32 — it is the refinement DEFECT operand,
        # which must be exact (matches the XLA path's pinned-f32 defect)
        AT, AtT, KinvT = (a.astype(jnp.bfloat16) for a in (AT, AtT, KinvT))
    fixed = (qT, q2T, AT, AtT, KinvT, KT, cl.T, cu.T, lb.T, ub.T, rho_a.T,
             jnp.broadcast_to(rho_x, (S, n)).T)

    # the residual rows travel as the one (4, S) array the kernel makes
    rows_of = lambda s: jnp.stack([s.pri, s.dua, s.prinorm, s.duanorm])
    bare = lambda s: s._replace(pri=None, dua=None, prinorm=None,
                                duanorm=None)
    whole = lambda carry: carry[0]._replace(
        **dict(zip(("pri", "dua", "prinorm", "duanorm"), carry[1])))

    def step(carry):
        s = whole(carry)
        *it, res = pallas_kernels.fused_sweeps(
            *fixed, s.x, s.z, s.zx, s.y, s.yx,
            n_sweeps=max(1, st.check_every), n_refine=st.solve_refine,
            sigma=float(st.sigma), alpha=float(st.alpha), bs=bs,
            precision=kprec)
        if lowered:
            res = jnp.stack(residual_rows(
                qT, *it, hi("mns,ns->ms", A32, it[0]),
                lambda y: hi("mns,ms->ns", A32, y), lambda x: q2T * x,
                lambda v: jnp.max(jnp.abs(v), axis=0)))
        return bare(checkpoint(s, *it, tuple(res))), res

    lanes = lambda s: s._replace(x=s.x.T, z=s.z.T, zx=s.zx.T, y=s.y.T,
                                 yx=s.yx.T)
    state = lanes(state)
    return lanes(whole(jax.lax.while_loop(
        lambda carry: cont(whole(carry)), step,
        (bare(state), rows_of(state)))))


# an ``_IterState``'s fields that hold a row to each scenario
_ROW_FIELDS = ("x", "z", "zx", "y", "yx", "pri", "dua", "prinorm",
               "duanorm", "swept", "since")

# Sweeps a row goes on being swept after it first passes the test, before
# the rung may leave it out.  The test passes a row at float32's residual
# floor, but its objective is then good to 3e-7..8e-7 in the median and
# 4e-6..5e-5 at p99, and the sweeps the all-or-nothing loop went on giving
# it are what every check of the benchmark was set on.  On the chip (farmer
# S=1000, `scripts/done_trajectory.py --linger`; PERF.md section 6, PR 45)
# 128 more read 1.1e-7..1.3e-7 and 7e-7..8.5e-7, 192 more are within a
# tenth of what the whole budget gives (1e-7 and 6e-7..7e-7), and 256 are
# there: the floor plus a third.  At 256 the wheel's check values are the
# parent's; at 512 it runs 14% slower for the same values.  Left out at
# the checkpoint that passes them, rows put the hub's refresh eight times
# further from HiGHS (`prox_gap_rel` 4e-3 for 5e-4) and the megastep
# rejected one iterate in eight.
_LINGER = 256


def _settled(s: _IterState):
    """Rows the rung may leave out: done, and for ``_LINGER`` sweeps
    running."""
    return s.since >= _LINGER

# the block of XLA's sweep, which has none of its own: the lanes of one
# vector register
_LANES = 128


def _rung_width(S, bs):
    """The width below S at which ``_admm_core`` runs its sweep loop again,
    chosen at trace time: a quarter of S's blocks (the kernel's ``bs``, 128
    rows under XLA's sweep), one at least.  0 where S is one block or less:
    S = 1000 at a block of 128 gives 256, S = 300 gives 128, S <= 128 none.
    One rung and not a ladder of halvings: each is one more traced copy of
    the loop body and one more Mosaic kernel in every program that holds
    the loop, and on the chip farmer's wheel read 4% faster with this one
    than with rungs at a half and at one block, its set-up 8% over the
    parent's for 27% (PERF.md section 6, PR 45)."""
    unit = bs or _LANES
    blocks = -(-S // unit)
    return max(1, blocks // 4) * unit if blocks > 1 else 0


def _admm_core(q, q2, A, cl, cu, lb, ub, state, LK, rho_a, rho_x,
               st: ADMMSettings, P=None, prec=None):
    """Inner ADMM sweeps at fixed rho, narrowing to the rows that are not
    done.  Returns final state.

    Stopping is per row in the mathematics and all-or-nothing in a batched
    loop: a row that passed the test at sweep 100 would be swept on for as
    long as any other row needs.  So the loop runs twice, at the full width
    and at one narrower static width (``_rung_width``): the full-width loop
    also leaves once the rows not settled (``_settled``: done for
    ``_LINGER`` sweeps running) fit the rung; those rows are gathered
    (settled rows fill what is left of the rung) with all the sweep reads
    for them, the same loop carries ``k`` on against the same budget at
    that width, and the rows are scattered back.  A row left behind keeps
    the iterate and the residuals it had.  The test, eps and the budget are
    the loop's own throughout, and it still ends when every row passes;
    ``best``/``stall`` start again at the rung as at a restart.

    Where S is one block or less there is no rung: one ``while_loop``, no
    gather.  ``state.narrow`` and ``state.swept`` count what ran where.
    ``prec``: see ``_sweep_loop``."""
    S, m, n = A.shape
    width = _rung_width(S, _sweep_block(st, S, m, n, P, prec)[0])
    k0 = state.k
    state = _sweep_loop(q, q2, A, cl, cu, lb, ub, state, LK, rho_a, rho_x,
                        st, P, prec, leave_at=width or None)
    state = state._replace(swept=state.swept + (state.k - k0))
    if not width:
        return state
    # everything the sweep reads, a row to each scenario
    rows = (q, q2, A, cl, cu, lb, ub, LK, rho_a,
            jnp.broadcast_to(rho_x, (S, n)), P)
    settled = _settled(state)

    def rung(state):
        # the rows not settled first, each group in its own order
        idx = jnp.argsort(settled, stable=True)[:width]
        sq, sq2, sA, scl, scu, slb, sub, sLK, sra, srx, sP = (
            jax.tree.map(lambda a: a[idx], rows))
        part = {f: getattr(state, f)[idx] for f in _ROW_FIELDS}
        out = _sweep_loop(
            sq, sq2, sA, scl, scu, slb, sub,
            state._replace(best=jnp.full_like(state.best, jnp.inf),
                           stall=jnp.zeros_like(state.stall), **part),
            sLK, sra, srx, st, sP, prec, leave_at=0)
        back = {f: getattr(state, f).at[idx].set(getattr(out, f))
                for f in _ROW_FIELDS}
        back["swept"] = state.swept.at[idx].add(out.k - state.k)
        return out._replace(narrow=state.narrow + (out.k - state.k), **back)

    # budget left, some row not done, and the rows not settled fit the rung
    # (more of them than fit: the loop above left on its plateau exit, and
    # nobody sweeps on)
    go = ((state.k < st.max_iter) & (jnp.sum(~settled) <= width)
          & ~jnp.all(_done_mask(state.pri, state.dua, state.prinorm,
                                state.duanorm, st)))
    return jax.lax.cond(go, rung, lambda s: s, state)


def _solve_scaled(q, q2, A, cl, cu, lb, ub, warm, masks, st: ADMMSettings,
                  P=None):
    """Adaptive-rho outer loop; everything already Ruiz-scaled.

    ``masks`` carries finiteness/equality classifications computed from the
    UNSCALED bounds (scaling can shrink +/-BIG below the BIG/2 test)."""
    S, m, n = A.shape
    dt = A.dtype
    eq = masks.eq
    loose = ~masks.fin_cl & ~masks.fin_cu

    def rho_vec(base):
        r = jnp.where(eq, base * st.rho_eq_scale, base)
        return jnp.where(loose, st.rho_min, r)

    def rho_x_vec(base):
        # clamped columns (lb == ub, the fix-nonants / Benders trick) get the
        # same equality boosting as equality rows: without it ADMM can stall
        # at ~1e-2 primal residuals on fix-and-evaluate solves
        return jnp.where(masks.eqx, base * st.rho_eq_scale,
                         jnp.broadcast_to(base, (S, n)))

    if warm is None:
        x0 = jnp.zeros((S, n), dt)
        z0 = jnp.clip(jnp.zeros((S, m), dt), cl, cu)
        zx0 = jnp.clip(x0, lb, ub)
        y0 = jnp.zeros((S, m), dt)
        yx0 = jnp.zeros((S, n), dt)
    else:
        x0, z0, y0, yx0 = warm
        zx0 = jnp.clip(x0, lb, ub)

    base0 = jnp.full((S,), st.rho, dt)
    state0 = _start_state(x0, z0, zx0, y0, yx0)

    # Restart loop as a lax.scan with the factorization in the CARRY, so
    # the LAST rho vectors + factorization survive to become the reusable
    # :class:`Factors` of the frozen-factor path.  (A python-unrolled loop
    # multiplies the traced program by `restarts`; at restarts=8 the XLA:CPU
    # compiler has been observed to segfault on the resulting program.)
    def restart(carry, _):
        state, base, total, mult, multx = carry[:5]
        rho_a = rho_vec(base[:, None])
        rho_x = rho_x_vec(base[:, None])
        if st.rho_row_adapt:
            rho_a = jnp.minimum(rho_a * mult, st.rho_row_max)
            rho_x = jnp.minimum(rho_x * multx, st.rho_row_max)
        LK = _factor(q2, A, rho_a, rho_x, st, P)
        state = _admm_core(
            q, q2, A, cl, cu, lb, ub,
            state._replace(k=jnp.zeros((), jnp.int32),
                           best=jnp.asarray(jnp.inf, dt),
                           stall=jnp.zeros((), jnp.int32)),
            LK, rho_a, rho_x, st, P,
        )
        total = total + state.k
        # OSQP rho adaptation on NORMALIZED residuals (raw residual ratios
        # push rho the wrong way when primal/dual scales differ).  CONVERGED
        # scenarios keep their rho: their restarts do zero sweeps, so
        # adapting on the stale residual ratio would compound x10 per
        # remaining restart into a runaway rho that only ever reaches the
        # Factors (and wrecks the frozen path's dual convergence).
        done = _done_mask(state.pri, state.dua, state.prinorm,
                          state.duanorm, st)
        eps_pri = st.eps_abs + st.eps_rel * jnp.maximum(state.prinorm, 1.0)
        pri_rel = state.pri / jnp.maximum(state.prinorm, 1e-10)
        dua_rel = state.dua / jnp.maximum(state.duanorm, 1e-10)
        ratio = jnp.sqrt(
            jnp.maximum(pri_rel, 1e-12) / jnp.maximum(dua_rel, 1e-12)
        )
        new_base = jnp.clip(base * jnp.clip(ratio, 0.1, 10.0),
                            st.rho_min, st.rho_max)
        base = jnp.where(done, base, new_base)
        if st.rho_row_adapt:
            # Per-row boost for the DOMINANT violated rows of scenarios that
            # are genuinely stuck: global adaptation balances aggregate
            # residual ratios while a few strongly-coupled rows (UC
            # ramp/genlim) stay infeasible for thousands of sweeps.  The
            # double gate (scenario far from converged AND row near the max
            # violation) keeps ordinary mid-convergence rows un-boosted --
            # indiscriminate boosting wrecks dual convergence and poisons
            # the frozen-path factors.  Boost-only + bounded.
            stuck = (state.pri > 100.0 * eps_pri)[:, None]
            gate = jnp.maximum(0.3 * state.pri,
                               10.0 * eps_pri)[:, None]
            Ax = jnp.einsum("smn,sn->sm", A, state.x)
            viol = jnp.maximum(cl - Ax, Ax - cu)
            mult = jnp.where(stuck & (viol > gate),
                             mult * st.rho_row_boost, mult)
            violx = jnp.maximum(lb - state.x, state.x - ub)
            multx = jnp.where(stuck & (violx > gate),
                              multx * st.rho_row_boost, multx)
        return (state, base, total, mult, multx,
                rho_a, rho_x, LK[0], LK[1]), None

    zK = jnp.zeros((S, n, n), dt)
    carry0 = (state0, base0, jnp.zeros((), jnp.int32),
              jnp.ones((S, m), dt), jnp.ones((S, n), dt),
              jnp.zeros((S, m), dt), jnp.zeros((S, n), dt), zK, zK)
    (state, _, total, _, _, rho_a, rho_x, Kinv, K), _ = jax.lax.scan(
        restart, carry0, None, length=st.restarts)
    return state, total, rho_a, rho_x, (Kinv, K)


def _polish(state: _IterState, q, q2, A, cl, cu, lb, ub, masks,
            st: ADMMSettings, P=None):
    """OSQP-style polish: guess the active set from dual signs + slacks, solve
    the resulting equality-constrained KKT system exactly, and accept per
    scenario only where it improves the worst residual.

    The KKT system is built at FIXED shape (no per-scenario gather): inactive
    rows contribute the trivial equation nu_i = 0, inactive bounds mu_j = 0, so
    the whole batch is one vmapped dense solve — vertex-exact LP solutions from
    mediocre ADMM iterates, replacing thousands of extra sweeps.
    """
    S, m, n = A.shape
    dt = A.dtype
    # Per-side activity tolerances; an infinite side is never active.
    # Finiteness comes from the UNSCALED bounds via ``masks``.
    fin_cl, fin_cu = masks.fin_cl, masks.fin_cu
    tol_cl = 1e-6 * (1.0 + jnp.where(fin_cl, jnp.abs(cl), 0.0))
    tol_cu = 1e-6 * (1.0 + jnp.where(fin_cu, jnp.abs(cu), 0.0))
    ytol = 1e-6 * jnp.maximum(jnp.max(jnp.abs(state.y), axis=1, keepdims=True), 1.0)
    act_lo = ((state.y < -ytol) | (state.z < cl + tol_cl)) & fin_cl
    act_up = ((state.y > ytol) | (state.z > cu - tol_cu)) & fin_cu

    fin_lb, fin_ub = masks.fin_lb, masks.fin_ub
    tol_lb = 1e-6 * (1.0 + jnp.where(fin_lb, jnp.abs(lb), 0.0))
    tol_ub = 1e-6 * (1.0 + jnp.where(fin_ub, jnp.abs(ub), 0.0))
    yxtol = 1e-6 * jnp.maximum(jnp.max(jnp.abs(state.yx), axis=1, keepdims=True), 1.0)
    v_lo = ((state.yx < -yxtol) | (state.zx < lb + tol_lb)) & fin_lb
    v_up = ((state.yx > yxtol) | (state.zx > ub - tol_ub)) & fin_ub

    eq = masks.eq

    eye_n = jnp.eye(n, dtype=dt)[None]
    ftol = 1e-7
    # Reduced augmented-Lagrangian system instead of the full (n+m+n) KKT:
    # active rows and bounds become quadratic penalties with weight 1/delta,
    # so each solve is an n x n batched Cholesky (MXU-friendly) rather than
    # an LU of the 3x-larger saddle system.  A pure penalty would need
    # delta ~ 1e-8 for vertex accuracy — hopeless in float32 — so instead a
    # few multiplier (AL) iterations at a MODERATE delta reuse one
    # factorization and converge the constraint error geometrically:
    # nu_{k+1} = nu_k + (A x_k - b)/delta.
    # AL penalty parameter deliberately DECOUPLED from polish_delta: the
    # multiplier iterations exist so a moderate delta (f64-safe conditioning,
    # cond(K) ~ 1e7) still reaches vertex-exact primal feasibility; the
    # residual dual shift is delta*|x| and is absorbed at bound-active
    # coordinates by the recovery step below.
    delta = jnp.asarray(max(st.polish_delta, 1e-7), dt)
    AL_ITERS = 4
    lanes_bs = _lanes_bs(st, S, n + m, dt)

    def saddle_solve_lanes(var_act, var_b, row_act, row_b, Qblock, pd):
        """``kkt_solve_full``'s system, assembled scenario-last and solved
        by the batched elimination kernel: XLA's LU walks the n+m columns
        one at a time over the (S, n+m, n+m) batch, nine times a polish;
        the kernel keeps 128 scenarios' systems in VMEM throughout."""
        from . import pallas_kernels
        va = var_act.T[:, None, :]
        ra = row_act.T[:, None, :]
        M = jnp.concatenate([
            jnp.concatenate([
                jnp.where(va, eye_n[0][:, :, None],
                          jnp.transpose(Qblock, (1, 2, 0))),
                jnp.where(va, 0.0, jnp.transpose(A, (2, 1, 0)))], axis=1),
            jnp.concatenate([
                jnp.where(ra, jnp.transpose(A, (1, 2, 0)), 0.0),
                jnp.where(ra, -pd, 1.0) * jnp.eye(m, dtype=dt)[:, :, None]],
                axis=1)], axis=0)
        rhs = jnp.concatenate([jnp.where(var_act, var_b, -q),
                               jnp.where(row_act, row_b, 0.0)], axis=1)
        return pallas_kernels.lanes_solve(
            M, rhs.T[:, None, :], bs=lanes_bs)[:, 0, :].T

    def kkt_solve_full(act_lo, act_up, v_lo, v_up):
        """Row-replacement saddle LU at (n+m) — float32's accurate option.

        The reduced system's 1/delta conditioning exceeds what f32 Cholesky
        plus refinement can recover, so f32 needs a backward-stable LU of an
        O(1)-entry system.  Instead of the full (n+m+n) KKT, the variable
        -bound dual block is eliminated EXACTLY: for bound-active columns the
        stationarity row is replaced by ``x_j = vb_j`` and the bound dual is
        recovered afterwards from the stationarity residual (same recovery
        step the reduced path uses) — a 3x smaller batched LU, which is the
        dominant polish cost on TPU (batched LU is sequential per step).
        """
        row_act = act_lo | act_up
        row_b = jnp.where(act_up, cu, cl)
        var_act = v_lo | v_up
        var_b = jnp.where(v_up, ub, lb)
        N = n + m
        eye_m = jnp.eye(m, dtype=dt)[None]
        # f32 floor on the row regularizer: 1e-8 is below f32 eps, so a
        # degenerate (redundant) active row set would make the LU singular
        pd = jnp.asarray(max(st.polish_delta,
                             1e-6 if dt == jnp.float32 else 0.0), dt)
        Qblock = jax.vmap(jnp.diag)(q2) + pd * eye_n
        if P is not None:
            Qblock = Qblock + P
        if lanes_bs is not None:
            sol = saddle_solve_lanes(var_act, var_b, row_act, row_b, Qblock,
                                     pd)
        else:
            va = var_act[:, :, None]
            ra = row_act[:, :, None]
            M = jnp.zeros((S, N, N), dt)
            rhs = jnp.zeros((S, N), dt)
            M = M.at[:, :n, :n].set(jnp.where(va, eye_n, Qblock))
            M = M.at[:, :n, n:].set(
                jnp.where(va, 0.0, jnp.swapaxes(A, 1, 2)))
            rhs = rhs.at[:, :n].set(jnp.where(var_act, var_b, -q))
            M = M.at[:, n:, :n].set(jnp.where(ra, A, 0.0))
            M = M.at[:, n:, n:].set(jnp.where(ra, -pd * eye_m, eye_m))
            rhs = rhs.at[:, n:].set(jnp.where(row_act, row_b, 0.0))
            sol = jnp.linalg.solve(M, rhs[..., None])[..., 0]
        xp, yp = sol[:, :n], sol[:, n:]
        # bound duals absorb the stationarity residual at active columns
        Pxp = (q2 * xp if P is None
               else q2 * xp + jnp.einsum("snk,sk->sn", P, xp))
        r_d = Pxp + q + jnp.einsum("smn,sm->sn", A, yp)
        yxp = jnp.where(var_act, -r_d, 0.0)
        return xp, yp, yxp

    def kkt_solve_reduced(act_lo, act_up, v_lo, v_up):
        row_act = act_lo | act_up
        row_b = jnp.where(act_up, cu, cl)
        var_act = v_lo | v_up
        var_b = jnp.where(v_up, ub, lb)
        w_row = row_act.astype(dt) / delta          # (S, m)
        w_var = var_act.astype(dt) / delta          # (S, n)
        K = jnp.einsum("smn,sm,smk->snk", A, w_row, A)
        K = K + delta * eye_n
        K = K + jax.vmap(jnp.diag)(q2 + w_var)
        if P is not None:
            K = K + P
        Kinv = _explicit_inverse(K, st)
        ra = row_act.astype(dt)
        va = var_act.astype(dt)
        nu = jnp.zeros_like(row_b)
        mu = jnp.zeros_like(var_b)
        xp = jnp.zeros_like(q)
        for _ in range(AL_ITERS):
            rhs = (-q + jnp.einsum("smn,sm->sn", A, w_row * row_b - ra * nu)
                   + (w_var * var_b - va * mu))
            xp = _chol_solve((Kinv, K), rhs, refine=1)
            Ax = jnp.einsum("smn,sn->sm", A, xp)
            nu = nu + w_row * (Ax - row_b)
            mu = mu + w_var * (xp - var_b)
        yp, yxp = ra * nu, va * mu
        # exact bound-dual recovery: at bound-active coordinates mu absorbs
        # the stationarity residual exactly — critical for consumers of
        # clamp duals (Benders cut gradients are -yx on clamped columns)
        Pxp = q2 * xp if P is None else q2 * xp + jnp.einsum(
            "snk,sk->sn", P, xp)
        r_d = Pxp + q + jnp.einsum("smn,sm->sn", A, yp) + yxp
        yxp = jnp.where(var_act, yxp - r_d, yxp)
        return xp, yp, yxp

    kkt_solve = (kkt_solve_full if dt == jnp.float32 else kkt_solve_reduced)

    def refine_add_only(xp, yp, yxp, sets):
        """ADD violated rows at the violated side, never drop.  Robust when
        the initial guess is near-correct: dropping actives by dual sign can
        oscillate (a dropped land/balance row lets the penalized solve blow
        x to -q/delta and the next pass re-adds it, forever)."""
        act_lo, act_up, v_lo, v_up = sets
        Ax = jnp.einsum("smn,sn->sm", A, xp)
        act_lo = act_lo | (Ax < cl - ftol) | eq
        act_up = act_up | (Ax > cu + ftol) | eq
        v_lo = (v_lo | (xp < lb - ftol)) & fin_lb
        v_up = (v_up | (xp > ub + ftol)) & fin_ub
        return act_lo, act_up, v_lo, v_up

    def refine_textbook(xp, yp, yxp, sets):
        """Textbook add-and-drop: also prune actives whose dual sign is
        wrong.  Recovers from BAD initial guesses (e.g. stalled clamped
        solves) where add-only is stuck with over-constrained sets."""
        act_lo, act_up, v_lo, v_up = sets
        Ax = jnp.einsum("smn,sn->sm", A, xp)
        act_lo = ((act_lo & ~(yp > ftol)) | (Ax < cl - ftol) | eq)
        act_up = ((act_up & ~(yp < -ftol)) | (Ax > cu + ftol) | eq)
        v_lo = ((v_lo & ~(yxp > ftol)) | (xp < lb - ftol)) & fin_lb
        v_up = ((v_up & ~(yxp < -ftol)) | (xp > ub + ftol)) & fin_ub
        return act_lo, act_up, v_lo, v_up

    # the initial solve on the guessed sets is shared by both disciplines
    sets0 = (act_lo | eq, act_up | eq, v_lo, v_up)
    first = kkt_solve(*sets0)

    def run_passes(refine):
        sets = sets0
        xp, yp, yxp = first
        for _ in range(st.polish_passes):
            sets = refine(xp, yp, yxp, sets)
            xp, yp, yxp = kkt_solve(*sets)
        Ax = jnp.einsum("smn,sn->sm", A, xp)
        zp = jnp.clip(Ax, cl, cu)
        zxp = jnp.clip(xp, lb, ub)
        pri = jnp.maximum(
            jnp.max(jnp.abs(Ax - zp), axis=1),
            jnp.max(jnp.abs(xp - zxp), axis=1),
        )
        Aty = jnp.einsum("smn,sm->sn", A, yp)
        Pxp = (q2 * xp if P is None
               else q2 * xp + jnp.einsum("snk,sk->sn", P, xp))
        dua = jnp.max(jnp.abs(Pxp + q + Aty + yxp), axis=1)
        return xp, zp, zxp, yp, yxp, pri, dua

    # run BOTH refinement disciplines; per scenario, keep whichever candidate
    # (or the original state) has the best worst-case residual
    cand = run_passes(refine_add_only)
    cand2 = run_passes(refine_textbook)
    worse2 = jnp.maximum(cand2[5], cand2[6]) >= jnp.maximum(cand[5], cand[6])
    cand = tuple(
        jnp.where(worse2[:, None] if a.ndim == 2 else worse2, a, b)
        for a, b in zip(cand, cand2)
    )
    xp, zp, zxp, yp, yxp, pri, dua = cand

    better = jnp.maximum(pri, dua) < jnp.maximum(state.pri, state.dua)
    pick = lambda a, b: jnp.where(better[:, None], a, b)
    return state._replace(
        x=pick(xp, state.x), z=pick(zp, state.z), zx=pick(zxp, state.zx),
        y=pick(yp, state.y), yx=pick(yxp, state.yx),
        pri=jnp.where(better, pri, state.pri),
        dua=jnp.where(better, dua, state.dua),
    )


@functools.partial(jax.jit, static_argnames=("settings",))
def solve_batch(c, q2, A, cl, cu, lb, ub, settings: ADMMSettings = ADMMSettings(),
                warm=None, P=None) -> BatchSolution:
    """Solve a batch of box-QP/LPs. All arrays (S, ...) as in ScenarioBatch.

    ``warm``: optional (x, z, y, yx) from a previous call — PH's persistent-solver
    analogue (spopt.py:129-144): between PH iterations only (q, rho-terms) change,
    so the previous primal/dual iterates are excellent starts.

    ``P``: optional dense (S, n, n) quadratic term added to diag(q2) — used by
    FWPH's simplex QPs; omit for the separable scenario subproblems.

    On TPU, float32 matmuls default to bf16 MXU accumulation, which stalls ADMM
    below ~1e-3 residuals; the solve traces at ``settings.matmul_precision``
    (default "highest": f32 full-precision passes on the MXU).  Lowering it
    trades residual floor for sweep throughput.
    """
    with jax.default_matmul_precision(settings.matmul_precision):
        return _solve_impl(c, q2, A, cl, cu, lb, ub, settings, warm, P)


# AOT executable cache (tpusppy/solvers/aot.py): the batch-solve entry
# points are what spopt's amortized solve loop dispatches every wheel
# iteration — persisting their executables is the wheel's warm start.
# Strict passthrough when TPUSPPY_AOT_CACHE is disarmed, and nested
# (in-trace) calls inline exactly like the plain jit.
solve_batch = _aot.cached_program(solve_batch, "admm.solve_batch",
                                  static_names=("settings",))


def _prep(c, q2, A, cl, cu, lb, ub, settings, P, want_masks=True):
    """Dtype casting, bound cleaning, finiteness masks — shared by the
    adaptive and frozen entry points.  ``want_masks=False`` skips the mask
    reductions for callers that never use them (polish-free frozen solves:
    inside a fused multi-iteration scan those reductions would otherwise
    run once per PH iteration for nothing)."""
    dt = settings.jdtype()
    c, q2, A = (jnp.asarray(v, dt) for v in (c, q2, A))
    if P is not None:
        P = jnp.asarray(P, dt)
    cl, cu = _clean_bounds(jnp.asarray(cl, dt), jnp.asarray(cu, dt))
    lb, ub = _clean_bounds(jnp.asarray(lb, dt), jnp.asarray(ub, dt))
    masks = None
    if want_masks:
        masks = _BoundMasks(
            fin_cl=cl > -BIG / 2, fin_cu=cu < BIG / 2,
            fin_lb=lb > -BIG / 2, fin_ub=ub < BIG / 2,
            eq=jnp.abs(cu - cl) < 1e-10,
            eqx=jnp.abs(ub - lb) < 1e-10,
        )
    return c, q2, A, cl, cu, lb, ub, masks, P


def _scale(c, q2, A, cl, cu, lb, ub, D, E, cost, P, warm, dt):
    As = A * E[:, :, None] * D[:, None, :]
    q2s = q2 * D * D * cost[:, None]
    qs = c * D * cost[:, None]
    Ps = None
    if P is not None:
        Ps = P * D[:, :, None] * D[:, None, :] * cost[:, None, None]
    cls, cus = cl * E, cu * E
    lbs, ubs = lb / D, ub / D
    if warm is not None:
        x0, z0, y0, yx0 = warm
        warm = (
            jnp.asarray(x0, dt) / D,
            jnp.asarray(z0, dt) * E,
            jnp.asarray(y0, dt) / E * cost[:, None],
            jnp.asarray(yx0, dt) * D * cost[:, None],
        )
    return qs, q2s, As, cls, cus, lbs, ubs, Ps, warm


def _solve_impl(c, q2, A, cl, cu, lb, ub, settings, warm, P=None,
                want_factors=False):
    dt = settings.jdtype()
    c, q2, A, cl, cu, lb, ub, masks, P = _prep(
        c, q2, A, cl, cu, lb, ub, settings, P)

    D, E = _ruiz(A, q2, settings.scaling_iters)
    cost = 1.0 / jnp.maximum(jnp.max(jnp.abs(c * D), axis=1), 1e-8)
    qs, q2s, As, cls, cus, lbs, ubs, Ps, warm = _scale(
        c, q2, A, cl, cu, lb, ub, D, E, cost, P, warm, dt)

    state, total, rho_a, rho_x, LK = _solve_scaled(
        qs, q2s, As, cls, cus, lbs, ubs, warm, masks, settings, Ps)

    def unscale(s):
        return (s.x * D, s.z / E, s.y * E / cost[:, None],
                s.yx / D / cost[:, None])

    raw = unscale(state)
    if settings.polish:
        state = _polish(state, qs, q2s, As, cls, cus, lbs, ubs, masks,
                        settings, Ps)
    x, z, y, yx = unscale(state)
    S = A.shape[0]
    sol = BatchSolution(
        x=x, z=z, y=y, yx=yx,
        pri_res=state.pri, dua_res=state.dua,
        iters=jnp.broadcast_to(total, (S,)),
        done=_done_mask(state.pri, state.dua, state.prinorm,
                        state.duanorm, settings),
        raw=raw,
        narrow=jnp.broadcast_to(state.narrow, (S,)), swept=state.swept,
    )
    if want_factors:
        return sol, Factors(D=D, E=E, cost=cost, rho_a=rho_a, rho_x=rho_x,
                            Kinv=LK[0], K=LK[1])
    return sol


def _frozen_sweep_phases(run_core, state0, settings, dt):
    """Two-phase frozen sweep shared by BOTH engines (dense per-scenario
    and shared-A — their ``_IterState``s both carry k/best/stall, which is
    all this touches).  ``run_core(state, st, prec)`` runs one engine core.

    Full precision: a single legacy-path core run.  Lowered
    (``settings.sweep_precision``): a bf16/bf16x3 sweep phase (f32-pinned
    residuals, so the while_loop's eps test is real), then — only when
    not every scenario reached eps — a bounded full-precision refinement
    phase on the SAME factors restores the f32 floor.  The reported
    residuals/done always come from f32 measurements; iteration counts
    accumulate across phases."""
    from . import precision as _precision
    if not _precision.is_low(settings.sweep_precision):
        return run_core(state0, settings, None)
    mode = _precision.canon(settings.sweep_precision)
    state = run_core(state0, settings, mode)
    if settings.precision_refine_iters > 0:
        k1 = state.k
        st_r = dataclasses.replace(
            settings, max_iter=int(settings.precision_refine_iters))
        state = run_core(
            state._replace(k=jnp.zeros((), jnp.int32),
                           best=jnp.asarray(jnp.inf, dt),
                           stall=jnp.zeros((), jnp.int32)),
            st_r, "highest")
        state = state._replace(k=state.k + k1)
    return state


def _solve_frozen_impl(c, q2, A, cl, cu, lb, ub, factors: Factors, warm,
                       settings, P=None, polish=False) -> BatchSolution:
    """Sweep-only solve reusing a previous refresh's :class:`Factors`.

    No Ruiz recomputation, no factorization, no rho adaptation — the
    steady-state PH iteration on TPU.  Valid while (A, q2, bounds) are
    unchanged since the refresh (only the linear term q may move); accuracy
    is still enforced by the residual-based while_loop, so a drifted active
    set costs extra sweeps, not correctness.

    ``polish=True`` additionally applies the active-set KKT polish to the
    final iterate (honoring ``settings.polish``): the segmented-dispatch
    refresh path ends its continuation with one short polishing dispatch so
    large shapes keep single-dispatch refresh accuracy.
    """
    dt = settings.jdtype()
    c, q2, A, cl, cu, lb, ub, masks, P = _prep(
        c, q2, A, cl, cu, lb, ub, settings, P,
        want_masks=polish and settings.polish)
    D, E, cost = factors.D, factors.E, factors.cost
    qs, q2s, As, cls, cus, lbs, ubs, Ps, warm = _scale(
        c, q2, A, cl, cu, lb, ub, D, E, cost, P, warm, dt)

    S, m, n = A.shape
    if warm is None:
        x0 = jnp.zeros((S, n), dt)
        z0 = jnp.clip(jnp.zeros((S, m), dt), cls, cus)
        y0 = jnp.zeros((S, m), dt)
        yx0 = jnp.zeros((S, n), dt)
    else:
        x0, z0, y0, yx0 = warm
    state0 = _start_state(x0, z0, jnp.clip(x0, lbs, ubs), y0, yx0)

    LK = (factors.Kinv, factors.K)

    def run_core(st0, st, prec):
        return _admm_core(qs, q2s, As, cls, cus, lbs, ubs, st0, LK,
                          factors.rho_a, factors.rho_x, st, Ps, prec=prec)

    state = _frozen_sweep_phases(run_core, state0, settings, dt)

    def unscale(s):
        return (s.x * D, s.z / E, s.y * E / cost[:, None],
                s.yx / D / cost[:, None])

    raw = unscale(state)
    if polish and settings.polish:
        state = _polish(state, qs, q2s, As, cls, cus, lbs, ubs, masks,
                        settings, Ps)
    x, z, y, yx = unscale(state)
    return BatchSolution(
        x=x, z=z, y=y, yx=yx,
        pri_res=state.pri, dua_res=state.dua,
        iters=jnp.broadcast_to(state.k, (S,)),
        done=_done_mask(state.pri, state.dua, state.prinorm,
                        state.duanorm, settings),
        raw=raw,
        narrow=jnp.broadcast_to(state.narrow, (S,)), swept=state.swept,
    )


@functools.partial(jax.jit, static_argnames=("settings", "polish"))
def solve_batch_frozen(c, q2, A, cl, cu, lb, ub, factors: Factors,
                       settings: ADMMSettings = ADMMSettings(),
                       warm=None, P=None, polish=False) -> BatchSolution:
    """Jitted frozen-factor solve; see :func:`_solve_frozen_impl`."""
    with jax.default_matmul_precision(settings.matmul_precision):
        return _solve_frozen_impl(c, q2, A, cl, cu, lb, ub, factors, warm,
                                  settings, P, polish=polish)


solve_batch_frozen = _aot.cached_program(
    solve_batch_frozen, "admm.solve_batch_frozen",
    static_names=("settings", "polish"))


@jax.jit
def stop_stats(sol: BatchSolution):
    """[max iters, max pri_res, max dua_res, all_done] as ONE device array.

    Segmented continuations (:mod:`.segmented`) need the iteration counter
    (stop-dispatch test), the worst residuals (plateau detector) and the
    convergence vote on the host between segments; fetched separately that
    is several serial host<->device round-trips per segment, each
    blocking the dispatch that follows it.  This reduces them to one fetch.
    ``all_done`` lets the stop test catch a mixed-precision solve whose
    phase-1 sweep count hit the segment cap but whose f32 refinement phase
    then converged (iters alone would schedule a pointless extra
    dispatch)."""
    dt = sol.pri_res.dtype
    return jnp.stack([sol.iters.max().astype(dt),
                      sol.pri_res.max().astype(dt),
                      sol.dua_res.max().astype(dt),
                      jnp.all(sol.done).astype(dt)])


stop_stats = _aot.cached_program(stop_stats, "admm.stop_stats")


def precision_guard_trips(sol: BatchSolution, settings: ADMMSettings,
                          ref_worst=None, stats=None) -> bool:
    """Host-side residual guard for the mixed-precision frozen path.

    True when a low-precision frozen solve must be re-run at full
    precision: it is not eps-converged AND its worst residual exceeds
    ``precision_guard`` x the reference floor — the worst residual of the
    last FULL-precision refresh solve of the same family (``ref_worst``),
    floored at eps.  Plateau families (whose full-precision floor is far
    above eps) therefore never trip the guard on residuals full precision
    could not beat either; a genuinely precision-limited solve (parked
    orders of magnitude above the f32 floor, or non-finite) always does.

    ``stats``: optional precomputed ``(worst_residual, all_done)`` pair —
    callers that already hold a fetched measurement (the single-fetch
    amortized path, :func:`measure_unpack`) pass it so the guard costs
    ZERO additional device round-trips; without it the guard performs one
    :func:`stop_stats` fetch itself.
    """
    if not settings.sweep_precision or settings.sweep_precision == "highest":
        return False
    if settings.precision_guard <= 0:
        return False
    if stats is not None:
        worst, all_done = float(stats[0]), bool(stats[1])
    else:
        # ONE device fetch (stop_stats: iters/residual maxima/all_done) —
        # the guard sits in the amortized hot path, where each separate
        # fetch would block the next dispatch
        from . import hostsync
        st4 = hostsync.fetch(stop_stats(sol))
        worst, all_done = float(max(st4[1], st4[2])), bool(st4[3])
    if all_done:
        return False
    if not np.isfinite(worst):
        return True
    floor = max(settings.eps_abs, settings.eps_rel)
    bar = settings.precision_guard * max(float(ref_worst or 0.0), floor)
    return worst > bar


# how much of the batch a solve was still sweeping: the names of
# ``width_counters``' three, in its order, wherever they travel (the packed
# measurements, ``trace.outcome``'s ``solve.<cylinder>.<kind>.<field>``)
WIDTH_FIELDS = ("narrow_sweeps", "row_sweeps", "full_row_sweeps")


def width_counters(sol: BatchSolution):
    """:data:`WIDTH_FIELDS` of one solve, a (3,) vector (traceable): the
    sweeps run below full width, the sum over all sweeps of the width each
    ran at, and sweeps x S beside it, what the same sweeps cost at full
    width.  The shared-A engine's loop never narrows: 0 and twice the full
    count."""
    dt = sol.pri_res.dtype
    full = sol.iters.max().astype(dt) * sol.iters.shape[0]
    if sol.swept is None:
        return jnp.stack([jnp.zeros((), dt), full, full])
    return jnp.stack([sol.narrow.max().astype(dt),
                      jnp.sum(sol.swept.astype(dt)), full])


@jax.jit
def measure_pack(sol: BatchSolution):
    """Everything the host wheel iteration reads from one solve, as ONE
    flat device vector: ``[pri_res (S) | dua_res (S) | iters_max |
    all_done | n_done | narrow_sweeps | row_sweeps | full_row_sweeps |
    x.ravel (S*n)]`` (``n_done``: how many rows the program's own stopping
    test passed, of which ``all_done`` is the case ``n_done == S``; the
    three after it: :func:`width_counters`).

    The amortized solve loop used to fetch ``x``, ``pri_res`` and
    ``dua_res`` separately (plus a ``stop_stats`` fetch when the
    mixed-precision guard is armed) — 3-4 serial blocking fetches per PH
    iteration.  Assembling the measurement device-side
    collapses them into a single fetch (:func:`measure_unpack` splits it
    back on the host); the warm-start state stays device-resident and is
    never fetched at all.
    """
    dt = sol.pri_res.dtype
    return jnp.concatenate([
        sol.pri_res.astype(dt),
        sol.dua_res.astype(dt),
        sol.iters.max().astype(dt)[None],
        jnp.all(sol.done).astype(dt)[None],
        jnp.sum(sol.done).astype(dt)[None],
        width_counters(sol),
        sol.x.astype(dt).reshape(-1),
    ])


# key_extra: the vector's layout, which the call's signature does not show
measure_pack = _aot.cached_program(
    measure_pack, "admm.measure_pack",
    key_extra=("pri|dua|iters|all_done|n_done|width3|x",))


def measure_unpack(vec, S, n):
    """Split a fetched :func:`measure_pack` vector; returns a dict with
    ``pri`` (S,), ``dua`` (S,), ``iters`` (int), ``all_done`` (bool),
    ``n_done`` (int), the three :data:`WIDTH_FIELDS` (int) and ``x``
    (S, n)."""
    vec = np.asarray(vec)
    return {
        "pri": vec[:S],
        "dua": vec[S:2 * S],
        "iters": int(vec[2 * S]),
        "all_done": bool(vec[2 * S + 1]),
        "n_done": int(vec[2 * S + 2]),
        **{k: int(v) for k, v in zip(WIDTH_FIELDS,
                                     vec[2 * S + 3:2 * S + 6])},
        "x": vec[2 * S + 6:].reshape(S, n),
    }


def _Aty(A, y):
    """A'y per scenario; A may be (S, m, n), a shared (m, n), or a
    :class:`~tpusppy.solvers.sparse.SparseA` (certified-bound programs
    then ride the exact sparse transpose matvec)."""
    from .sparse import SparseA
    if isinstance(A, SparseA):
        return A.rmatvec(y)
    return y @ A if A.ndim == 2 else jnp.einsum("smn,sm->sn", A, y)




def _highest_precision(fn):
    """Pin a jitted certified-bound program to full-f32 matmuls regardless
    of ambient or settings precision (the bound's validity is numerical)."""

    @functools.wraps(fn)
    def wrapped(*a, **k):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **k)

    return wrapped


@_highest_precision
@jax.jit
def dual_objective(c, q2, A, cl, cu, lb, ub, y, x_hint, margin_scale=100.0):
    """(S,) LOWER bounds on each scenario optimum from row duals ``y``.

    Weak duality: for ANY y, ``g(y) = min_x L(x, y)`` bounds the optimum below
    — unlike the primal objective of an inexact solve, which the reference's
    Lagrangian spoke (lagrangian_bounder.py:19-56) gets exact from its MIP
    solver but an iterative solver only gets to tolerance.  Construction:

    - rows: contribute ``-y+·cu + y-·cl``; y is first CLIPPED to the dual
      cone of finite sides (clipping just picks a different valid y),
    - variables are NOT dualized: ``min_x [0.5 x'diag(q2)x + (c + A'y)'x]``
      is solved in closed form per coordinate over the variable box.

    For coordinates whose needed side is infinite (free variables with
    residual reduced cost), the box is capped at ``X = margin_scale *
    (1 + max|x_hint|)`` per scenario: the result is a certificate under the
    assumption that the true optimizer lies within X (use
    :func:`dual_objective_capped` to know which scenarios relied on it).
    Models with finite variable bounds get an unconditional certificate.

    Implemented as :func:`dual_cut` with nothing clamped.
    """
    base, _ = dual_cut(c, q2, A, cl, cu, lb, ub, y, x_hint,
                       jnp.zeros(c.shape[1], dtype=bool), margin_scale)
    return base


@_highest_precision
@jax.jit
def dual_objective_margin(c, q2, A, cl, cu, lb, ub, y, x_hint,
                          margin_scale=100.0, widen=10.0):
    """(S,) defensive margins for :func:`dual_objective`'s X-cap.

    ``dual_objective`` evaluates free coordinates over a synthetic box of
    half-width ``X = margin_scale*(1+max|x_hint|)``; its value is certified
    only under ``|x*| <= X``.  Subtracting this margin extends the validity
    box to ``widen*X``: for each coordinate whose needed side is infinite,
    the margin is the decrease of the coordinate minimum when the box grows
    from X to widen*X (exact for linear coordinates, an upper bound for
    quadratic ones).  Tight duals make every margin ~0, so the cost of the
    widened certificate vanishes exactly when the bound is good.
    """
    cl, cu = _clean_bounds(cl, cu)
    lb, ub = _clean_bounds(lb, ub)
    fin_lb, fin_ub = lb > -BIG / 2, ub < BIG / 2
    y = jnp.where(~(cu < BIG / 2) & (y > 0), 0.0, y)
    y = jnp.where(~(cl > -BIG / 2) & (y < 0), 0.0, y)
    g = c + _Aty(A, y)
    X = margin_scale * (1.0 + jnp.max(jnp.abs(x_hint), axis=1, keepdims=True))
    # linear coords: value at the capped side is g*(+-X); widening multiplies
    # the capped side by `widen`, decreasing the minimum by |g|*(widen-1)*X.
    # quadratic coords: the minimum over a LARGER box can only decrease, and
    # by at most the same linear envelope (q2 >= 0), so the bound applies too.
    need_hi = ~fin_ub & (g < 0)
    need_lo = ~fin_lb & (g > 0)
    # a quadratic coordinate only hits the cap when its unconstrained
    # minimizer |g|/q2 lies beyond X; interior minima are exact as-is
    engaged = (q2 <= 1e-14) | (jnp.abs(g) > q2 * X)
    per = jnp.where((need_hi | need_lo) & engaged,
                    jnp.abs(g) * (widen - 1.0) * X, 0.0)
    return jnp.sum(per, axis=1)


@jax.jit
def _dual_objective_with_margin_jit(c, q2, A, cl, cu, lb, ub, y, x_hint,
                                    margin_scale=100.0):
    base = dual_objective(c, q2, A, cl, cu, lb, ub, y, x_hint,
                          margin_scale)
    marg = dual_objective_margin(c, q2, A, cl, cu, lb, ub, y, x_hint,
                                 margin_scale)
    return jnp.stack([base, marg])


# _highest_precision OUTSIDE the executable cache so an AOT lower+compile
# still traces under the pinned full-precision matmul context
dual_objective_with_margin = _highest_precision(_aot.cached_program(
    _dual_objective_with_margin_jit, "admm.dual_objective_with_margin"))


def dual_objective_with_margin_traced(c, q2, A, cl, cu, lb, ub, y, x_hint,
                                      margin_scale=100.0):
    """TRACEABLE twin of :func:`dual_objective_with_margin` for callers
    fusing the certified-bound assembly into a larger device program (the
    in-wheel bound pass of ``parallel.sharded.make_wheel_megastep``).
    Same (2, S) stack of [dual_objective, margin], traced under the SAME
    ``_highest_precision`` matmul pin as the spoke-path wrapper — the
    bound's validity is numerical, so the fused assembly must not
    inherit a caller's lowered (bf16) matmul precision.  The
    tolerance-absorbing margin stays single-sourced here."""
    with jax.default_matmul_precision("highest"):
        return _dual_objective_with_margin_jit(c, q2, A, cl, cu, lb, ub, y,
                                               x_hint, margin_scale)
dual_objective_with_margin.__doc__ = \
    """(2, S): :func:`dual_objective` stacked with
    :func:`dual_objective_margin` in ONE device program.

    Bound spokes evaluate both every wheel iteration; as two separate
    jitted calls they cost two serial host fetches per iteration —
    this packs them into a single dispatch + fetch (the
    single-fetch wheel-iteration discipline, doc/pipeline.md).
    """


@_highest_precision
@jax.jit
def dual_cut(c, q2, A, cl, cu, lb, ub, y, x_hint, clamp_mask,
             margin_scale=100.0):
    """Benders-cut data valid for ANY duals ``y`` (weak duality).

    For the value function of a problem whose ``clamp_mask`` columns are
    fixed at x̂ (lb = ub = x̂), the dual objective decomposes into terms
    independent of x̂ plus a term LINEAR in x̂:

        Q(x̂') >= base + g[clamp] . x̂'      for every x̂'

    with ``g = c + A'y`` and ``base`` the row term plus the non-clamped
    coordinate minima.  Unlike the raw clamp duals ``-yx`` (exact only for
    sign-FEASIBLE optimal duals — a polished dual at a degenerate optimum
    can satisfy stationarity with wrong-signed multipliers and yield an
    INVALID cut), this construction can only weaken, never invalidate.
    Returns ``(base (S,), g (S, n))``; callers slice g at the clamp columns.
    """
    dt = c.dtype
    cl, cu = _clean_bounds(cl, cu)
    lb, ub = _clean_bounds(lb, ub)
    fin_cl, fin_cu = cl > -BIG / 2, cu < BIG / 2
    fin_lb, fin_ub = lb > -BIG / 2, ub < BIG / 2

    y = jnp.where(~fin_cu & (y > 0), 0.0, y)
    y = jnp.where(~fin_cl & (y < 0), 0.0, y)
    yp = jnp.maximum(y, 0.0)
    ym = jnp.minimum(y, 0.0)
    row_term = jnp.sum(-yp * jnp.where(fin_cu, cu, 0.0)
                       - ym * jnp.where(fin_cl, cl, 0.0), axis=1)

    X = margin_scale * (1.0 + jnp.max(jnp.abs(x_hint), axis=1, keepdims=True))
    L = jnp.where(fin_lb, lb, -X)
    U = jnp.where(fin_ub, ub, X)
    g = c + _Aty(A, y)
    quad = q2 > 1e-14
    xq = jnp.clip(jnp.where(quad, -g / jnp.where(quad, q2, 1.0), 0.0), L, U)
    val_quad = 0.5 * q2 * xq * xq + g * xq
    val_lin = g * jnp.where(g >= 0, L, U)
    term = jnp.where(quad, val_quad, val_lin)
    base = row_term + jnp.sum(jnp.where(clamp_mask[None, :], 0.0, term),
                              axis=1)
    return base, g


@functools.partial(jax.jit, static_argnames=("settings",))
def solve_batch_factored(c, q2, A, cl, cu, lb, ub,
                         settings: ADMMSettings = ADMMSettings(),
                         warm=None, P=None):
    """Adaptive solve that ALSO returns the reusable :class:`Factors` for
    subsequent :func:`solve_batch_frozen` calls."""
    with jax.default_matmul_precision(settings.matmul_precision):
        return _solve_impl(c, q2, A, cl, cu, lb, ub, settings, warm, P,
                           want_factors=True)


solve_batch_factored = _aot.cached_program(
    solve_batch_factored, "admm.solve_batch_factored",
    static_names=("settings",))


class SingleSolution(NamedTuple):
    x: jax.Array
    y: jax.Array
    pri_res: jax.Array
    dua_res: jax.Array


def solve_single(c, q2, A, cl, cu, lb, ub, settings: ADMMSettings = ADMMSettings(),
                 **kw) -> SingleSolution:
    """Convenience wrapper: one problem as a batch of 1 (EF solves)."""
    sol = solve_batch(
        c[None], q2[None], A[None], cl[None], cu[None], lb[None], ub[None],
        settings=settings, **kw,
    )
    return SingleSolution(sol.x[0], sol.y[0], sol.pri_res[0], sol.dua_res[0])
