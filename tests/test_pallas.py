"""Pallas fused-sweep kernel: interpreter-mode correctness vs the XLA sweep.

The kernel (solvers/pallas_kernels.py) fuses ``n_sweeps`` ADMM sweeps with
all matrices VMEM-resident, in scenario-on-lanes layout.  On CPU it runs
through the Pallas interpreter, which pins its semantics to the reference
XLA sweep recurrence of ``admm._admm_core`` exactly (same relaxation, same
refinement; the XLA sweep's incrementally carried ``Ax`` feeds its residuals
alone, and the kernel makes its own from a true product) — so kernel drift
is caught without TPU hardware (VERDICT r2 weak #4).
"""

import dataclasses
import functools

import numpy as np
import pytest

from tpusppy.solvers import pallas_kernels


def _xla_sweeps(q, A, cl, cu, lb, ub, rho_a, rho_x, state, n_sweeps,
                n_refine, sigma, alpha, Kinv, K):
    """The reference recurrence, transcribed from admm._admm_core.sweep
    (batched einsum form, incremental Ax carry)."""
    import jax.numpy as jnp

    x, z, zx, y, yx, Ax = state

    def chol_solve(b):
        v = jnp.einsum("snk,sk->sn", Kinv, b)
        for _ in range(n_refine):
            r = b - jnp.einsum("snk,sk->sn", K, v)
            v = v + jnp.einsum("snk,sk->sn", Kinv, r)
        return v

    for _ in range(n_sweeps):
        rhs = (sigma * x - q
               + jnp.einsum("smn,sm->sn", A, rho_a * z - y)
               + (rho_x * zx - yx))
        xt = chol_solve(rhs)
        Axt = jnp.einsum("smn,sn->sm", A, xt)
        x_new = alpha * xt + (1 - alpha) * x
        Ax_new = alpha * Axt + (1 - alpha) * Ax
        za_arg = alpha * Axt + (1 - alpha) * z + y / rho_a
        z_new = jnp.clip(za_arg, cl, cu)
        y_new = y + rho_a * (alpha * Axt + (1 - alpha) * z - z_new)
        zx_arg = alpha * xt + (1 - alpha) * zx + yx / rho_x
        zx_new = jnp.clip(zx_arg, lb, ub)
        yx_new = yx + rho_x * (alpha * xt + (1 - alpha) * zx - zx_new)
        x, z, zx, y, yx, Ax = x_new, z_new, zx_new, y_new, yx_new, Ax_new
    return x, z, zx, y, yx, Ax


def test_fused_sweeps_matches_xla_sweep():
    import jax.numpy as jnp

    rng = np.random.RandomState(7)
    S, m, n = 6, 9, 5
    sigma, alpha = 1e-6, 1.6
    n_sweeps, n_refine = 5, 2

    A = rng.randn(S, m, n)
    q = rng.randn(S, n)
    cl = -np.abs(rng.randn(S, m)) - 0.5
    cu = np.abs(rng.randn(S, m)) + 0.5
    lb = -np.ones((S, n)) * 2
    ub = np.ones((S, n)) * 2
    rho_a = np.full((S, m), 0.7)
    rho_x = np.full((S, n), 0.4)
    # K = sigma I + A' diag(rho_a) A + diag(rho_x), as in admm._factor
    K = np.einsum("smn,sm,smk->snk", A, rho_a, A)
    K += sigma * np.eye(n)[None]
    K += np.einsum("sn,nk->snk", rho_x, np.eye(n))
    Kinv = np.linalg.inv(K)

    x = rng.randn(S, n) * 0.1
    z = np.clip(rng.randn(S, m), cl, cu)
    zx = np.clip(x, lb, ub)
    y = rng.randn(S, m) * 0.1
    yx = rng.randn(S, n) * 0.1
    Ax = np.einsum("smn,sn->sm", A, x)

    ref = _xla_sweeps(q, A, cl, cu, lb, ub, rho_a, rho_x,
                      (x, z, zx, y, yx, Ax), n_sweeps, n_refine, sigma,
                      alpha, Kinv, K)

    tT = lambda a: jnp.transpose(jnp.asarray(a), (1, 2, 0))
    outs = pallas_kernels.fused_sweeps(
        jnp.asarray(q).T, jnp.zeros((n, S)), tT(A),
        jnp.transpose(jnp.asarray(A), (2, 1, 0)), tT(Kinv), tT(K),
        jnp.asarray(cl).T, jnp.asarray(cu).T,
        jnp.asarray(lb).T, jnp.asarray(ub).T,
        jnp.asarray(rho_a).T, jnp.asarray(rho_x).T,
        jnp.asarray(x).T, jnp.asarray(z).T, jnp.asarray(zx).T,
        jnp.asarray(y).T, jnp.asarray(yx).T,
        n_sweeps=n_sweeps, n_refine=n_refine, sigma=sigma, alpha=alpha,
        bs=S, interpret=True,
    )
    got = [np.asarray(o).T for o in outs[:5]]
    for g, r, name in zip(got, ref, ["x", "z", "zx", "y", "yx"]):
        np.testing.assert_allclose(g, np.asarray(r), rtol=1e-10, atol=1e-12,
                                   err_msg=name)
    assert len(outs[5]) == 4 and all(r.shape == (S,) for r in outs[5])


def test_usable_gating():
    """The kernel only engages on TPU with no dense P and a VMEM-fitting
    block; everything else must fall back to the XLA path."""
    assert pallas_kernels.usable(100, 20, 10, platform="cpu") is None
    assert pallas_kernels.usable(100, 20, 10, platform="tpu", P=1) is None
    bs = pallas_kernels.usable(1000, 28, 44, platform="tpu")
    assert bs == 1000 or (bs is not None and bs % 128 == 0)
    # a shape whose per-scenario matrices exceed VMEM must be rejected
    assert pallas_kernels.usable(100000, 4626, 2928, platform="tpu") is None
    # bf16 matrix storage (precision="default") widens the usable range:
    # never smaller blocks, sometimes usable where f32 storage is not
    for S, m, n in [(1000, 28, 44), (10000, 80, 96), (2000, 120, 150)]:
        b32 = pallas_kernels.usable(S, m, n, platform="tpu")
        b16 = pallas_kernels.usable(S, m, n, platform="tpu",
                                    precision="default")
        if b32 is not None:
            assert b16 is not None and b16 >= b32


def test_fused_sweeps_default_precision_matches_emulation():
    """Dense kernel at precision="default" (bf16 matrix storage + vector
    operand rounding) against the XLA mixed-precision sweep recurrence
    (admm._admm_core with prec="default": solvers/precision.py emulation,
    f32-exact defect against K)."""
    import jax.numpy as jnp

    from tpusppy.solvers import precision

    rng = np.random.RandomState(21)
    S, m, n = 8, 9, 5
    sigma, alpha = 1e-6, 1.6
    n_sweeps, n_refine = 4, 2

    A = rng.randn(S, m, n)
    q = rng.randn(S, n)
    cl = -np.abs(rng.randn(S, m)) - 0.5
    cu = np.abs(rng.randn(S, m)) + 0.5
    lb = -np.ones((S, n)) * 2
    ub = np.ones((S, n)) * 2
    rho_a = np.full((S, m), 0.7)
    rho_x = np.full((S, n), 0.4)
    K = np.einsum("smn,sm,smk->snk", A, rho_a, A)
    K += sigma * np.eye(n)[None]
    K += np.einsum("sn,nk->snk", rho_x, np.eye(n))
    Kinv = np.linalg.inv(K)

    x = rng.randn(S, n) * 0.1
    z = np.clip(rng.randn(S, m), cl, cu)
    zx = np.clip(x, lb, ub)
    y = rng.randn(S, m) * 0.1
    yx = rng.randn(S, n) * 0.1
    Ax = np.einsum("smn,sn->sm", A, x)

    lo = lambda spec, a, b: precision.contract(spec, jnp.asarray(a),
                                               jnp.asarray(b), "default",
                                               platform="cpu")
    hi = lambda spec, a, b: precision.contract(spec, jnp.asarray(a),
                                               jnp.asarray(b), "highest")

    rx, rz, rzx, ry, ryx, rAx = (jnp.asarray(v)
                                 for v in (x, z, zx, y, yx, Ax))
    for _ in range(n_sweeps):
        rhs = (sigma * rx - q + lo("smn,sm->sn", A, rho_a * rz - ry)
               + (rho_x * rzx - ryx))
        xt = lo("snk,sk->sn", Kinv, rhs)
        for _ in range(n_refine):
            r = rhs - hi("snk,sk->sn", K, xt)
            xt = xt + lo("snk,sk->sn", Kinv, r)
        Axt = lo("smn,sn->sm", A, xt)
        x_new = alpha * xt + (1 - alpha) * rx
        Ax_new = alpha * Axt + (1 - alpha) * rAx
        za = alpha * Axt + (1 - alpha) * rz + ry / rho_a
        z_new = jnp.clip(za, cl, cu)
        y_new = ry + rho_a * (alpha * Axt + (1 - alpha) * rz - z_new)
        zxa = alpha * xt + (1 - alpha) * rzx + ryx / rho_x
        zx_new = jnp.clip(zxa, lb, ub)
        yx_new = ryx + rho_x * (alpha * xt + (1 - alpha) * rzx - zx_new)
        rx, rz, rzx, ry, ryx, rAx = (x_new, z_new, zx_new, y_new, yx_new,
                                     Ax_new)

    tT = lambda a: jnp.transpose(jnp.asarray(a), (1, 2, 0))
    bf = lambda a: a.astype(jnp.bfloat16)
    outs = pallas_kernels.fused_sweeps(
        jnp.asarray(q).T, jnp.zeros((n, S)), bf(tT(A)),
        bf(jnp.transpose(jnp.asarray(A), (2, 1, 0))), bf(tT(Kinv)), tT(K),
        jnp.asarray(cl).T, jnp.asarray(cu).T,
        jnp.asarray(lb).T, jnp.asarray(ub).T,
        jnp.asarray(rho_a).T, jnp.asarray(rho_x).T,
        jnp.asarray(x).T, jnp.asarray(z).T, jnp.asarray(zx).T,
        jnp.asarray(y).T, jnp.asarray(yx).T,
        n_sweeps=n_sweeps, n_refine=n_refine, sigma=sigma, alpha=alpha,
        bs=S, precision="default", interpret=True,
    )
    # the lowered mode's residuals are pinned to float32 operands, which
    # the kernel (A in bf16) does not hold: it hands back the state alone
    assert outs[5] is None
    got = [np.asarray(o).T for o in outs[:5]]
    # tolerance floor: the XLA emulation accumulates in f32 (the TPU MXU
    # accumulator) while the interpret-mode kernel under x64 accumulates
    # the IDENTICAL bf16 products in f64 — a ~1e-7 accumulation-order
    # difference, far below the bf16 operand error the modes introduce
    for g, r, name in zip(got, (rx, rz, rzx, ry, ryx),
                          ["x", "z", "zx", "y", "yx"]):
        np.testing.assert_allclose(g, np.asarray(r), rtol=1e-5, atol=1e-6,
                                   err_msg=name)


# --------------------------------------------------------------------------
# The kernel's checkpoint: the residual rows of the iterate it ends on, and
# the sweep loop that carries the lanes layout
# --------------------------------------------------------------------------

_F32 = dict(dtype="float32", eps_abs=1e-5, eps_rel=1e-5)


@functools.lru_cache(maxsize=None)
def _farmer_step(crops_multiplier, dtype, S=300):
    """What one step of ``admm._sweep_loop`` reads on a farmer batch, Ruiz
    scaled as the engine scales it: the problem, the factors of a refresh
    solve and its raw iterate, with the linear term moved (a PH iteration's
    W) so that the iterate is some sweeps from the test."""
    import jax.numpy as jnp

    from tpusppy.ir import ScenarioBatch
    from tpusppy.models import farmer
    from tpusppy.solvers import admm

    b = ScenarioBatch.from_problems(
        [farmer.scenario_creator(nm, num_scens=S,
                                 crops_multiplier=crops_multiplier)
         for nm in farmer.scenario_names_creator(S)])
    st = admm.ADMMSettings(**(_F32 if dtype == "float32" else {}))
    dt = st.jdtype()
    prob = (b.c, b.q2, b.A, b.cl, b.cu, b.lb, b.ub)
    sol, f = admm.solve_batch_factored(*prob, settings=st)
    c, q2, A, cl, cu, lb, ub, _, _ = admm._prep(*prob, st, None,
                                                want_masks=False)
    c = c * (1.0 + 0.05 * jnp.asarray(
        np.random.RandomState(5).randn(*c.shape), dt))
    q, q2, A, cl, cu, lb, ub, _, warm = admm._scale(
        c, q2, A, cl, cu, lb, ub, f.D, f.E, f.cost, None, sol.raw, dt)
    x, z, y, yx = warm
    return st, (q, q2, A, cl, cu, lb, ub), f, (x, z, jnp.clip(x, lb, ub),
                                               y, yx)


def _lanes_args(prob, f, state):
    """``fused_sweeps``'s operands, as ``admm._lanes_sweep_loop`` lays
    them out."""
    import jax.numpy as jnp

    q, q2, A, cl, cu, lb, ub = prob
    tT = lambda a: jnp.transpose(a, (1, 2, 0))
    return (q.T, q2.T, tT(A), jnp.transpose(A, (2, 1, 0)), tT(f.Kinv),
            tT(f.K), cl.T, cu.T, lb.T, ub.T, f.rho_a.T, f.rho_x.T,
            *(v.T for v in state))


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("bs", [300, 128], ids=["one_block", "ragged"])
@pytest.mark.parametrize("crops_multiplier", [1, 4])
def test_fused_sweeps_hands_back_the_residual_rows_of_its_last_iterate(
        crops_multiplier, bs, dtype):
    """Through the interpreter, on a farmer batch (n = 11 or 44; one block,
    or blocks of 128 with a ragged last one of 44): the state is the XLA
    sweep recurrence's, and the four residual rows are
    ``admm.residual_rows`` (the formulas of ``_sweep_loop``'s XLA
    checkpoint, with a true ``A x``) of the iterate the kernel ended on."""
    import jax.numpy as jnp

    from tpusppy.solvers import admm

    st, prob, f, state = _farmer_step(crops_multiplier, dtype)
    q, q2, A, cl, cu, lb, ub = prob
    S, m, n = A.shape
    assert (m, n) == (7 * crops_multiplier, 11 * crops_multiplier)
    assert A.dtype == jnp.dtype(dtype)
    n_sweeps = max(1, st.check_every)
    *got, rows = pallas_kernels.fused_sweeps(
        *_lanes_args(prob, f, state), n_sweeps=n_sweeps,
        n_refine=st.solve_refine, sigma=float(st.sigma),
        alpha=float(st.alpha), bs=bs, interpret=True)
    got = [o.T for o in got]
    Ax0 = jnp.einsum("smn,sn->sm", A, state[0])
    ref = _xla_sweeps(q, A, cl, cu, lb, ub, f.rho_a, f.rho_x,
                      (*state, Ax0), n_sweeps, st.solve_refine, st.sigma,
                      st.alpha, f.Kinv, f.K)
    # float32: both sum the same products in another order, four sweeps
    # through a K of condition 1e3
    tol = 2e-4 if dtype == "float32" else 1e-9
    for g, r, name in zip(got, ref, ["x", "z", "zx", "y", "yx"]):
        assert g.dtype == jnp.dtype(dtype) and g.shape == r.shape
        scale = float(jnp.max(jnp.abs(r))) + 1.0
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=0,
                                   atol=tol * scale, err_msg=name)
    x, z, zx, y, yx = got
    want = admm.residual_rows(
        q, x, z, zx, y, yx, jnp.einsum("smn,sn->sm", A, x),
        lambda y: jnp.einsum("smn,sm->sn", A, y), lambda x: q2 * x,
        lambda v: jnp.max(jnp.abs(v), axis=1))
    assert len(rows) == 4
    # the residuals cancel against norms of order one: held to the norms
    norm = np.maximum(np.asarray(want[2]), np.asarray(want[3])) + 1.0
    tol = 2e-6 if dtype == "float32" else 1e-13
    for g, r, name in zip(rows, want,
                          ["pri", "dua", "prinorm", "duanorm"]):
        assert g.shape == (S,) and g.dtype == jnp.dtype(dtype)
        assert np.all(np.abs(np.asarray(g) - np.asarray(r)) <= tol * norm), \
            name


@pytest.fixture
def sweep_kernel_interpreted(monkeypatch):
    """``admm._sweep_block`` answered as on the TPU (a block of 128, the
    whole batch where it is smaller) and the kernel run through the Pallas
    interpreter: the test steers the program's choice, the program has no
    option for it."""
    from tpusppy.solvers import admm

    monkeypatch.setattr(
        admm, "_sweep_block",
        lambda st, S, m, n, P=None, prec=None: (
            min(S, 128), "default" if prec == "default" else "highest"))
    monkeypatch.setattr(
        pallas_kernels, "fused_sweeps",
        functools.partial(pallas_kernels.fused_sweeps, interpret=True))


@pytest.mark.parametrize("leave_at", [None, 0, 40],
                         ids=["plain", "rung", "full_width"])
def test_sweep_loop_on_the_kernel_agrees_with_the_xla_loop(
        leave_at, request):
    """``admm._sweep_loop`` on one farmer batch (cm1, S = 300, float64),
    on XLA's sweep and with each step one kernel call (interpreted, three
    blocks, the state carried in the lanes layout): the same ``k``, the
    same rows done, ``since`` within one checkpoint, the iterates to float
    tolerance; in each of the loop's three roles under ``_admm_core``."""
    import jax
    import jax.numpy as jnp

    from tpusppy.solvers import admm

    st, prob, f, (x, z, zx, y, yx) = _farmer_step(1, "float64")
    st = dataclasses.replace(st, max_iter=120, eps_abs=1e-4, eps_rel=1e-4)
    state0 = admm._start_state(x, z, zx, y, yx)

    def loop():
        return jax.jit(lambda: admm._sweep_loop(
            *prob, state0, (f.Kinv, f.K), f.rho_a, f.rho_x, st,
            leave_at=leave_at))()

    xla = loop()
    request.getfixturevalue("sweep_kernel_interpreted")
    lanes = loop()
    assert int(lanes.k) == int(xla.k) > 0
    done = lambda s: np.asarray(admm._done_mask(s.pri, s.dua, s.prinorm,
                                                s.duanorm, st))
    np.testing.assert_array_equal(done(lanes), done(xla))
    assert 0 < done(xla).sum() < x.shape[0]
    ck = max(1, st.check_every)
    assert np.all(np.abs(np.asarray(lanes.since) - np.asarray(xla.since))
                  <= ck)
    if leave_at is None:
        assert not np.any(np.asarray(lanes.since))      # not kept
    else:
        assert np.any(np.asarray(lanes.since))
    for name in ("x", "z", "zx", "y", "yx", "pri", "dua", "prinorm",
                 "duanorm"):
        a, b = getattr(lanes, name), getattr(xla, name)
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0,
            atol=1e-9 * (float(jnp.max(jnp.abs(b))) + 1.0), err_msg=name)
    for name in ("narrow", "swept", "best", "stall"):
        np.testing.assert_array_equal(np.asarray(getattr(lanes, name)),
                                      np.asarray(getattr(xla, name)))


def test_sweep_loop_lowered_mode_keeps_its_residuals_in_float32(
        sweep_kernel_interpreted):
    """Under ``prec="default"`` the kernel holds ``A`` in bf16 and hands
    back no residual rows: the loop makes them in XLA from the float32
    ``A``, on the lanes-layout state.  The rows it ends with are
    ``residual_rows`` of its final iterate against the float32 matrices to
    float32 rounding, which bf16 products (2e-3) would miss by far."""
    import jax
    import jax.numpy as jnp

    from tpusppy.solvers import admm

    st, prob, f, (x, z, zx, y, yx) = _farmer_step(1, "float32")
    st = dataclasses.replace(st, max_iter=8)
    q, q2, A, cl, cu, lb, ub = prob
    out = jax.jit(lambda: admm._sweep_loop(
        *prob, admm._start_state(x, z, zx, y, yx), (f.Kinv, f.K), f.rho_a,
        f.rho_x, st, prec="default"))()
    assert int(out.k) == 8
    hi = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    want = admm.residual_rows(
        q, out.x, out.z, out.zx, out.y, out.yx, hi("smn,sn->sm", A, out.x),
        lambda y: hi("smn,sm->sn", A, y), lambda x: q2 * x,
        lambda v: jnp.max(jnp.abs(v), axis=1))
    norm = np.maximum(np.asarray(want[2]), np.asarray(want[3])) + 1.0
    for name, r in zip(("pri", "dua", "prinorm", "duanorm"), want):
        assert np.all(np.abs(np.asarray(getattr(out, name)) - np.asarray(r))
                      <= 2e-6 * norm), name


# --------------------------------------------------------------------------
# lanes_solve: batched elimination, scenario on the lanes
# --------------------------------------------------------------------------

def _lanes(M, rhs, bs=128):
    """lanes_solve through the interpreter on (S, N, N), (S, N, R) float32
    arrays in the batch-first layout of ``jnp.linalg.solve``."""
    import jax.numpy as jnp

    x = pallas_kernels.lanes_solve(
        jnp.transpose(jnp.asarray(M), (1, 2, 0)),
        jnp.transpose(jnp.asarray(rhs), (1, 2, 0)), bs=bs, interpret=True)
    return np.transpose(np.asarray(x), (2, 0, 1))


def _rel_err(x, ref):
    """Per-scenario relative error against the float64 solution."""
    S = x.shape[0]
    return (np.linalg.norm((x - ref).reshape(S, -1), axis=1)
            / np.linalg.norm(ref.reshape(S, -1), axis=1))


def _assert_as_good_as_xla(M, rhs):
    """The kernel does the LU's arithmetic in another order of additions:
    both are held to the float64 solution, the kernel's error to a small
    multiple of ``jnp.linalg.solve``'s own in float32."""
    import jax.numpy as jnp

    assert M.dtype == np.float32 and rhs.dtype == np.float32
    got = _lanes(M, rhs)
    assert got.dtype == np.float32 and got.shape == rhs.shape
    xla = np.asarray(jnp.linalg.solve(jnp.asarray(M), jnp.asarray(rhs)))
    ref = np.linalg.solve(M.astype(np.float64), rhs.astype(np.float64))
    e_got, e_xla = _rel_err(got, ref), _rel_err(xla, ref)
    assert np.all(np.isfinite(got))
    assert np.median(e_got) <= 2.0 * np.median(e_xla) + 1e-7
    assert e_got.max() <= 8.0 * e_xla.max() + 1e-6
    # and to each other, at the scale of the worse of the two
    assert np.all(_rel_err(got, xla.astype(np.float64))
                  <= 8.0 * np.maximum(e_got, e_xla) + 1e-6)


@pytest.mark.parametrize("S", [128, 1000])      # 1000: a ragged last block
@pytest.mark.parametrize("R", ["one", "N"])
@pytest.mark.parametrize("N", [12, 44, 72, 100])
def test_lanes_solve_matches_linalg_solve_on_random_systems(N, R, S):
    rng = np.random.RandomState(1000 * N + S)
    M = (rng.randn(S, N, N) / np.sqrt(N)
         + 2.0 * np.eye(N)).astype(np.float32)   # well conditioned
    if R == "one":
        rhs = rng.randn(S, N, 1).astype(np.float32)
    else:   # an inverse: the identity on the right
        rhs = np.broadcast_to(np.eye(N, dtype=np.float32), (S, N, N)).copy()
    _assert_as_good_as_xla(M, rhs)


def _farmer_saddle(S, crops_multiplier):
    """The polish's saddle systems (``admm._polish.kkt_solve_full``: the
    stationarity row of a bound-active column replaced by ``x_j = bound``,
    an inactive row by the identity row ``nu_i = 0``) at the active sets of
    a farmer batch's float64 LP solutions, in float32."""
    from tpusppy.ir import ScenarioBatch
    from tpusppy.models import farmer
    from tpusppy.solvers.admm import ADMMSettings, solve_batch

    base = min(S, 128)
    b = ScenarioBatch.from_problems(
        [farmer.scenario_creator(nm, num_scens=base,
                                 crops_multiplier=crops_multiplier)
         for nm in farmer.scenario_names_creator(base)])
    sol = solve_batch(b.c, b.q2, b.A, b.cl, b.cu, b.lb, b.ub,
                      ADMMSettings())
    x, A = np.asarray(sol.x), np.asarray(b.A)
    n, m = b.num_vars, b.num_rows
    Ax = np.einsum("smn,sn->sm", A, x)
    tol = 1e-6 * (1.0 + np.abs(Ax))
    row_act = (np.abs(Ax - b.cl) < tol) | (np.abs(Ax - b.cu) < tol)
    at_ub = np.abs(x - b.ub) < 1e-6 * (1.0 + np.abs(x))
    var_act = (np.abs(x - b.lb) < 1e-6 * (1.0 + np.abs(x))) | at_ub
    assert 0 < row_act.mean() < 1 and 0 < var_act.mean() < 1
    pd = 1e-6
    va, ra = var_act[:, :, None], row_act[:, :, None]
    eye_n, eye_m = np.eye(n)[None], np.eye(m)[None]
    M = np.zeros((base, n + m, n + m))
    M[:, :n, :n] = np.where(va, eye_n, pd * eye_n)
    M[:, :n, n:] = np.where(va, 0.0, np.swapaxes(A, 1, 2))
    M[:, n:, :n] = np.where(ra, A, 0.0)
    M[:, n:, n:] = np.where(ra, -pd * eye_m, eye_m)
    rhs = np.concatenate([
        np.where(var_act, np.where(at_ub, b.ub, b.lb), -b.c),
        np.where(row_act, np.where(np.abs(Ax - b.cu) < tol, b.cu, b.cl),
                 0.0)], axis=1)
    # Ruiz-like row/column scaling, as the program's systems are scaled
    r = 1.0 / np.sqrt(np.abs(M).max(axis=2, keepdims=True))
    c = 1.0 / np.sqrt(np.abs(M * r).max(axis=1, keepdims=True))
    M, rhs = M * r * c, rhs * r[:, :, 0]
    reps = -(-S // base)
    M = np.tile(M, (reps, 1, 1))[:S]
    rhs = np.tile(rhs, (reps, 1))[:S]
    return M.astype(np.float32), rhs[..., None].astype(np.float32)


@pytest.mark.parametrize("S", [128, 1000])
@pytest.mark.parametrize("crops_multiplier", [1, 4])   # n+m = 18, 72
def test_lanes_solve_on_the_polish_saddle_systems(crops_multiplier, S):
    M, rhs = _farmer_saddle(S, crops_multiplier)
    assert M.shape[1] == 18 * crops_multiplier
    _assert_as_good_as_xla(M, rhs)


def test_lanes_inverse_of_farmers_K_agrees_with_the_cholesky_inverse():
    """The refresh solve's own K (farmer x4, n = 44, float32, the rho of the
    last restart) at S = 256, inverted by the kernel against the identity
    and by ``admm._explicit_inverse_oneshot``: ``K Kinv`` is the identity to
    2e-6 either way (cond K is 1e3 after the Ruiz scaling), the two agree
    to 2e-6 of the inverse's largest entry, and a solve through either
    with the program's two refinement passes stands 5e-7 from float64's."""
    import jax.numpy as jnp

    from tpusppy.ir import ScenarioBatch
    from tpusppy.models import farmer
    from tpusppy.solvers import admm

    S = 256
    b = ScenarioBatch.from_problems(
        [farmer.scenario_creator(nm, num_scens=S, crops_multiplier=4)
         for nm in farmer.scenario_names_creator(S)])
    st = admm.ADMMSettings(dtype="float32", eps_abs=1e-5, eps_rel=1e-5)
    _, factors = admm.solve_batch_factored(
        b.c, b.q2, b.A, b.cl, b.cu, b.lb, b.ub, settings=st)
    K = factors.K
    n = b.num_vars
    assert K.shape == (S, n, n) == (S, 44, 44) and K.dtype == jnp.float32
    chol = admm._explicit_inverse_oneshot(K)
    lanes = jnp.asarray(_lanes(
        K, np.broadcast_to(np.eye(n, dtype=np.float32), K.shape)))
    assert lanes.dtype == jnp.float32
    K64 = np.asarray(K, np.float64)
    scale = np.abs(np.linalg.inv(K64)).max()
    for Kinv in (chol, lanes):
        assert np.abs(K64 @ np.asarray(Kinv, np.float64)
                      - np.eye(n)).max() < 2e-6
    assert float(jnp.max(jnp.abs(lanes - chol))) < 2e-6 * scale
    rhs = np.random.RandomState(0).randn(S, n).astype(np.float32)
    ref = np.linalg.solve(K64, rhs.astype(np.float64)[..., None])[..., 0]
    for Kinv in (chol, lanes):
        x = admm._chol_solve((Kinv, K), jnp.asarray(rhs),
                             refine=st.solve_refine)
        assert np.abs(np.asarray(x) - ref).max() < 5e-7 * np.abs(ref).max()


def test_lanes_solve_singular_system_is_nonfinite_on_its_lane_only():
    rng = np.random.RandomState(3)
    S, N = 128, 12
    M = (rng.randn(S, N, N) / np.sqrt(N) + 2.0 * np.eye(N)).astype(
        np.float32)
    rhs = rng.randn(S, N, 1).astype(np.float32)
    bad = 37
    M[bad, :, 5] = 0.0          # a zero column: no pivot to find
    got = _lanes(M, rhs)
    assert not np.all(np.isfinite(got[bad]))
    ok = np.arange(S) != bad
    assert np.all(np.isfinite(got[ok]))
    ref = np.linalg.solve(M[ok].astype(np.float64),
                          rhs[ok].astype(np.float64))
    assert _rel_err(got[ok], ref).max() < 1e-4


def test_lanes_solve_pivots_where_no_pivoting_would_fail():
    """A zero on the diagonal with a usable entry below it: partial
    pivoting solves what elimination in place would divide by zero."""
    S, N = 128, 12
    rng = np.random.RandomState(4)
    M = (rng.randn(S, N, N) / np.sqrt(N) + 2.0 * np.eye(N)).astype(
        np.float32)
    M[::2, 0, 0] = 0.0          # every other lane needs a row swap at once
    rhs = rng.randn(S, N, 1).astype(np.float32)
    _assert_as_good_as_xla(M, rhs)


@pytest.mark.parametrize("case, expect", [
    (dict(S=1000, N=72, R=1, platform="tpu"), 128),
    (dict(S=128, N=72, R=1, platform="tpu"), 128),
    (dict(S=1000, N=44, R=44, platform="tpu"), 128),
    (dict(S=1000, N=72, R=1, platform="cpu"), None),     # off the TPU
    (dict(S=1000, N=72, R=1), None),                     # the tests' backend
    (dict(S=127, N=72, R=1, platform="tpu"), None),      # lanes not filled
    (dict(S=1000, N=72, R=1, platform="tpu", dtype="float64"), None),
    (dict(S=1000, N=100, R=1, platform="tpu"), None),    # past the budget
    (dict(S=1000, N=72, R=72, platform="tpu"), None),    # past the budget
], ids=lambda v: "-".join(f"{k}{x}" for k, x in v.items())
   if isinstance(v, dict) else None)
def test_usable_solve_gating(case, expect):
    assert pallas_kernels.usable_solve(**case) == expect
