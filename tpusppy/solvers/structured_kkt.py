"""Block/Woodbury factorization of the shared-A KKT system.

The shared x-update system K = diag(d) + A' R A separates for
block-structured families (UC above all: generator-local ramp/min-up/
segment rows + a few hundred wide balance/reserve rows) into

    K = B + A_w' R_w A_w,     B block-diagonal over variable components.

Instead of the dense (n, n) explicit inverse (O(n^3) to build, O(S n^2)
to apply, n^2 floats of HBM — 4.1 GB at reference horizon 48), this
factors each variable block independently (batched per size bucket) and
applies the wide-row coupling through the Woodbury identity

    K^-1 = B^-1 - B^-1 A_w' C^-1 A_w B^-1,
    C    = R_w^-1 + A_w B^-1 A_w'            (r x r, SPD).

Apply cost per x-update drops from O(S n^2) to O(S (sum_b bs^2 + 2 n r))
— ~6x fewer flops at WECC-240 horizon-24 shape (n=16008, r=1098), and
the factors hold O(sum_b bs^2 + n r + r^2) floats instead of n^2.

The structure (variable components, bucketed padding, wide-row set) is
detected host-side once per family by
:func:`tpusppy.solvers.sparse.detect_structure`; this module runs on
device inside the jitted factor/solve programs.

Reference analogue: Gurobi's internal sparse LU/ordering on each
subproblem (spopt.py:85-223); parapint's Schur-complement decomposition
(opt/sc.py:59-106) is the same algebra applied at the scenario level —
here it is applied INSIDE the per-scenario KKT, batched over scenarios.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .sparse import KKTStructure, SparseA


class StructureArrays(NamedTuple):
    """Device-resident static index arrays of a :class:`KKTStructure`.

    ``bvars[k]`` is (nb, bs) int32 (dummy slot = n), ``brows[k]`` is
    (nb, mb) int32 (dummy slot = m); ``wide_rows`` is (r,) int32.
    Tuples keep per-bucket shapes static under jit.
    """

    bvars: tuple
    brows: tuple
    wide_rows: jax.Array

    @classmethod
    def from_structure(cls, st: KKTStructure):
        return cls(
            bvars=tuple(jnp.asarray(bv) for bv, _ in st.buckets),
            brows=tuple(jnp.asarray(br) for _, br in st.buckets),
            wide_rows=jnp.asarray(st.wide_rows, jnp.int32),
        )


class BlockWoodbury(NamedTuple):
    """Factored K^-1 operator (the structured stand-in for the dense
    ``Kinv`` array inside :class:`~tpusppy.solvers.shared_admm.SharedFactors`)."""

    binv: tuple        # per bucket (nb, bs, bs) explicit block inverses
    bvars: tuple       # per bucket (nb, bs) variable ids (dummy = n)
    Aw: jax.Array      # (r, n) dense scaled wide rows
    Cinv: jax.Array    # (r, r) inverse Woodbury cap


class DiagLowRank(NamedTuple):
    """K^-1 of K = diag(d) + A' R A for a DENSE shared A of few rows
    beside its columns (:func:`lowrank_kinv`), by the Woodbury identity

        K^-1 = diag(1/d) - W' N,   W = A diag(1/d),   N = (R^-1 + W A')^-1 W:

    two thin products an apply, ``2 m n`` a row where the explicit
    inverse costs ``n n``.  The dense-A stand-in for the ``Kinv`` array
    inside :class:`~tpusppy.solvers.shared_admm.SharedFactors`, as
    :class:`BlockWoodbury` is the sparse-A one; like it, it comes with no
    (n, n) ``K`` (``shared_admm._factor_shared``): the refinement's exact
    ``K x`` is ``x d + (R (x A')) A``, two thin products more."""

    dinv: jax.Array    # (n,) 1 / d
    W: jax.Array       # (m, n) A diag(1/d)
    N: jax.Array       # (m, n) (R^-1 + W A')^-1 W


#: The MXU contracts and emits tiles of this edge: a thin product pays
#: for m rounded up to it.
_MXU_TILE = 128


def lowrank_kinv(A) -> bool:
    """Whether the shared engine applies K^-1 as :class:`DiagLowRank` for
    this shared constraint matrix: the ONE place the regime is chosen,
    from the type and the shape of ``A`` alone (``_factor_shared`` asks at
    trace time, spopt's ``refresh.lowrank_kinv`` reads the factors it
    made).  An apply is two (S, n) x (n, m) products with m padded to the
    MXU's tile, ``2 roundup(m, 128) n`` a row, against ``n n`` for the
    explicit inverse: the operator is taken where that is at most half
    (sslp 10 x 50: 256 against 520; PERF.md section 6, PR 33, has a chip
    reading either side).  A :class:`SparseA` keeps its own regimes."""
    if isinstance(A, SparseA):
        return False
    m, n = A.shape
    return 4 * (-(-m // _MXU_TILE) * _MXU_TILE) <= n


def factor_lowrank(A, dvec, rho_a) -> DiagLowRank:
    """Factor K = diag(dvec) + A' diag(rho_a) A for a dense, Ruiz-SCALED
    (m, n) ``A``; the (m, m) cap goes through the inverse routine the
    dense regime uses.  ``dvec`` = q2ref + rho_x + sigma is positive by
    construction (q2ref >= 0 for a convex objective, rho_x >= rho_min > 0,
    sigma > 0), so the division is safe."""
    from .admm import _explicit_inverse

    dinv = 1.0 / dvec
    W = A * dinv[None, :]
    C = W @ A.T
    C = 0.5 * (C + C.T) + jnp.diag(1.0 / rho_a)
    return DiagLowRank(dinv=dinv, W=W, N=_explicit_inverse(C[None])[0] @ W)


def zero_lowrank(m: int, n: int, dt) -> DiagLowRank:
    """Shape-matching all-zeros :class:`DiagLowRank`: the restart scan's
    carry initializer (see :func:`zero_factors`)."""
    return DiagLowRank(dinv=jnp.zeros((n,), dt), W=jnp.zeros((m, n), dt),
                       N=jnp.zeros((m, n), dt))


def _bapply(binv: tuple, bvars: tuple, b, prec=None):
    """B^-1 b for b (..., n): gather per bucket, batched block matmul,
    scatter back.  Blocks partition the variables, so scatters never
    collide (the dummy slot n collides only with itself).

    ``prec``: optional matmul precision mode for the block matmuls
    (solvers/precision.py); None keeps the legacy ambient-precision op."""
    n = b.shape[-1]
    b_pad = jnp.concatenate(
        [b, jnp.zeros(b.shape[:-1] + (1,), b.dtype)], axis=-1)
    out = jnp.zeros_like(b_pad)
    for inv_k, bv_k in zip(binv, bvars):
        g = b_pad[..., bv_k]                        # (..., nb, bs)
        if prec is None:
            r = jnp.einsum("...kb,kbt->...kt", g, inv_k)
        else:
            from . import precision
            r = precision.contract("...kb,kbt->...kt", g, inv_k, prec)
        out = out.at[..., bv_k.reshape(-1)].set(
            r.reshape(r.shape[:-2] + (-1,)))
    return out[..., :n]


def factor_structured(A: SparseA, struct: StructureArrays, dvec, rho_a,
                      sigma) -> BlockWoodbury:
    """Factor K = diag(dvec) + sigma I + A' diag(rho_a) A given the
    block/Woodbury split.  ``A`` must already be Ruiz-SCALED.

    Runs inside the jitted refresh program.  The dense (m+1, n+1)
    scatter of A is transient (alive only during block extraction) and
    its buffer is reused by XLA once the (nb, mb, bs) block tensors are
    built.
    """
    m, n = A.shape
    dt = A.dtype
    A_pad = jnp.zeros((m + 1, n + 1), dt).at[A.rows, A.cols].add(A.vals)
    d_pad = jnp.concatenate([dvec + sigma, jnp.ones((1,), dt)])
    rho_pad = jnp.concatenate([rho_a, jnp.zeros((1,), dt)])

    from .admm import _explicit_inverse

    binv = []
    for bv_k, br_k in zip(struct.bvars, struct.brows):
        Ablk = A_pad[br_k[:, :, None], bv_k[:, None, :]]   # (nb, mb, bs)
        Bb = jnp.einsum("kms,kmt,km->kst", Ablk, Ablk, rho_pad[br_k])
        diag = d_pad[bv_k]                                  # (nb, bs)
        Bb = Bb + jax.vmap(jnp.diag)(diag)
        binv.append(_explicit_inverse(Bb))
    binv = tuple(binv)

    Aw = A_pad[struct.wide_rows, :n]                        # (r, n)
    rho_w = rho_a[struct.wide_rows]
    T = _bapply(binv, struct.bvars, Aw)                     # (r, n)
    C = Aw @ T.T
    C = 0.5 * (C + C.T) + jnp.diag(1.0 / rho_w)
    Cinv = _explicit_inverse(C[None])[0]
    return BlockWoodbury(binv=binv, bvars=struct.bvars, Aw=Aw, Cinv=Cinv)


def zero_factors(struct: StructureArrays, n: int, dt) -> BlockWoodbury:
    """Shape-matching all-zeros BlockWoodbury — the lax.scan carry
    initializer for the adaptive restart loop (the first restart
    overwrites it; a real factorization at carry init would double the
    factor cost for nothing)."""
    binv = tuple(jnp.zeros(bv.shape + (bv.shape[1],), dt)
                 for bv in struct.bvars)
    r = struct.wide_rows.shape[0]
    return BlockWoodbury(binv=binv, bvars=struct.bvars,
                         Aw=jnp.zeros((r, n), dt),
                         Cinv=jnp.zeros((r, r), dt))


def kinv_apply(bw: BlockWoodbury, b, prec=None):
    """K^-1 b for b (..., n) via the Woodbury identity.

    ``prec`` lowers the matmul precision of the apply (the mixed-precision
    sweep fast path — the defect correction against the exact system lives
    in the caller, :func:`tpusppy.solvers.shared_admm._solve_shared_K`)."""
    t = _bapply(bw.binv, bw.bvars, b, prec)
    if prec is None:
        u = t @ bw.Aw.T
        v = u @ bw.Cinv
        w = v @ bw.Aw
    else:
        from . import precision
        u = precision.contract("...n,rn->...r", t, bw.Aw, prec)
        v = precision.contract("...r,rq->...q", u, bw.Cinv, prec)
        w = precision.contract("...r,rn->...n", v, bw.Aw, prec)
    return t - _bapply(bw.binv, bw.bvars, w, prec)


def is_dense_kinv(Kinv) -> bool:
    """The (n, n) explicit-inverse array, not an operator."""
    return not isinstance(Kinv, (BlockWoodbury, DiagLowRank))


def apply_kinv_like(Kinv, b, prec=None):
    """Uniform K^-1 application: dense (n, n) array, BlockWoodbury or
    DiagLowRank."""
    if isinstance(Kinv, BlockWoodbury):
        return kinv_apply(Kinv, b, prec)
    if isinstance(Kinv, DiagLowRank):
        if prec is None:
            return b * Kinv.dinv - (b @ Kinv.W.T) @ Kinv.N
        from . import precision
        u = precision.contract("...n,mn->...m", b, Kinv.W, prec)
        return b * Kinv.dinv - precision.contract(
            "...m,mn->...n", u, Kinv.N, prec)
    if prec is None:
        return b @ Kinv
    from . import precision
    return precision.contract("...n,nk->...k", b, Kinv, prec)
