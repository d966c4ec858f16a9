"""Batched ADMM solver vs HiGHS ground truth (property tests per SURVEY §4:
in-repo solver lets us test against EF/LP ground truth instead of smoke-only)."""

import dataclasses

import numpy as np
import pytest

from tpusppy.ir import ScenarioBatch
from tpusppy.models import farmer
from tpusppy.solvers import scipy_backend
from tpusppy.solvers.admm import ADMMSettings, solve_batch, solve_single


def random_feasible_lp(rng, n=8, m=6):
    """Random LP with a known feasible point so it's never infeasible."""
    A = rng.normal(size=(m, n))
    x_feas = rng.uniform(0.2, 0.8, size=n)
    slack = rng.uniform(0.5, 1.5, size=m)
    Ax = A @ x_feas
    cu = Ax + slack
    cl = np.where(rng.uniform(size=m) < 0.3, Ax - slack, -np.inf)
    eq = rng.uniform(size=m) < 0.2
    cl = np.where(eq, Ax, cl)
    cu = np.where(eq, Ax, cu)
    c = rng.normal(size=n)
    lb = np.zeros(n)
    ub = np.full(n, 2.0)
    return c, A, cl, cu, lb, ub


SETTINGS = ADMMSettings(max_iter=2000, restarts=8, eps_abs=1e-9, eps_rel=1e-9)


class TestRandomLPs:
    def test_batch_matches_highs(self):
        rng = np.random.RandomState(0)
        S, n, m = 16, 8, 6
        probs = [random_feasible_lp(rng, n, m) for _ in range(S)]
        stack = [np.stack([p[i] for p in probs]) for i in range(6)]
        c, A, cl, cu, lb, ub = stack
        sol = solve_batch(c, np.zeros((S, n)), A, cl, cu, lb, ub, SETTINGS)
        for s in range(S):
            ref = scipy_backend.solve_lp(c[s], A[s], cl[s], cu[s], lb[s], ub[s])
            obj = float(c[s] @ np.asarray(sol.x[s]))
            assert obj == pytest.approx(ref.obj, abs=1e-4), f"scenario {s}"

    def test_qp_diagonal(self):
        rng = np.random.RandomState(1)
        n, m = 6, 4
        c, A, cl, cu, lb, ub = random_feasible_lp(rng, n, m)
        q2 = rng.uniform(0.5, 2.0, size=n)
        sol = solve_single(c, q2, A, cl, cu, lb, ub, SETTINGS)
        x = np.asarray(sol.x)
        # KKT check: gradient stationarity within tolerance
        grad = q2 * x + c + A.T @ np.asarray(sol.y)
        # components not at variable bounds must have ~zero gradient+bound-dual
        assert float(sol.pri_res) < 1e-6
        assert float(sol.dua_res) < 1e-6
        # compare against a fine grid of projected gradient? use scipy minimize
        import scipy.optimize as sopt

        res = sopt.minimize(
            lambda v: 0.5 * v @ (q2 * v) + c @ v,
            x0=np.clip(np.zeros(n), lb, ub),
            jac=lambda v: q2 * v + c,
            bounds=np.stack([lb, ub], axis=1),
            constraints=[
                {"type": "ineq", "fun": lambda v, i=i: cu[i] - A[i] @ v}
                for i in range(m) if np.isfinite(cu[i])
            ] + [
                {"type": "ineq", "fun": lambda v, i=i: A[i] @ v - cl[i]}
                for i in range(m) if np.isfinite(cl[i])
            ],
            method="SLSQP",
        )
        obj_admm = 0.5 * x @ (q2 * x) + c @ x
        assert obj_admm == pytest.approx(res.fun, abs=1e-5)

    def test_warm_start_fewer_iters(self):
        rng = np.random.RandomState(2)
        c, A, cl, cu, lb, ub = random_feasible_lp(rng, 8, 6)
        arrs = [v[None] for v in (c, np.zeros(8), A, cl, cu, lb, ub)]
        st = ADMMSettings(max_iter=3000, restarts=4)
        sol1 = solve_batch(*arrs, st)
        sol2 = solve_batch(*arrs, st, warm=(sol1.x, sol1.z, sol1.y, sol1.yx))
        assert int(sol2.iters[0]) <= int(sol1.iters[0])
        obj1 = float(c @ np.asarray(sol1.x[0]))
        obj2 = float(c @ np.asarray(sol2.x[0]))
        assert obj2 == pytest.approx(obj1, abs=1e-5)


class TestFrozenFactors:
    """Factorization-amortized path: factors from an adaptive refresh are
    reused by sweep-only solves on PH-style perturbed objectives."""

    def _stack(self, rng, S=12, n=8, m=6):
        probs = [random_feasible_lp(rng, n, m) for _ in range(S)]
        return [np.stack([p[i] for p in probs]) for i in range(6)]

    def test_frozen_matches_adaptive_on_perturbed_q(self):
        from tpusppy.solvers.admm import solve_batch_factored, solve_batch_frozen

        import dataclasses

        rng = np.random.RandomState(3)
        c, A, cl, cu, lb, ub = self._stack(rng)
        S, n = c.shape
        q2 = np.full((S, n), 0.5)          # strongly convex: unique optimum
        # eps tighter than the asserts but reachable within one sweep budget
        # (the frozen path has no restarts: OSQP-relative convergence at
        # eps=1e-9 can need a final rho re-adaptation it doesn't have)
        st = dataclasses.replace(SETTINGS, eps_abs=1e-7, eps_rel=1e-7)
        sol0, factors = solve_batch_factored(c, q2, A, cl, cu, lb, ub, st)
        assert float(np.max(sol0.pri_res)) < 1e-6

        # PH-style: only the linear term moves (a little) between iterations
        qp = c + 0.05 * rng.normal(size=c.shape)
        frz = solve_batch_frozen(qp, q2, A, cl, cu, lb, ub, factors, st,
                                 warm=sol0.raw)
        ada = solve_batch(qp, q2, A, cl, cu, lb, ub, st, warm=sol0.raw)
        assert int(frz.iters[0]) < st.max_iter    # converged within budget
        assert float(np.max(frz.pri_res)) < 5e-6  # OSQP-relative at eps=1e-7
        assert float(np.max(frz.dua_res)) < 5e-6
        np.testing.assert_allclose(np.asarray(frz.x), np.asarray(ada.x),
                                   atol=1e-4)

        # a LARGE objective change can outgrow the frozen rho: the contract
        # is detectability — budget exhaustion shows in ``iters`` (this is
        # what SPOpt.solve_loop uses to fall back to an adaptive refresh)
        qbig = c + 0.5 * rng.normal(size=c.shape)
        frz2 = solve_batch_frozen(qbig, q2, A, cl, cu, lb, ub, factors, st,
                                  warm=sol0.raw)
        bad = (np.asarray(frz2.pri_res) > 1e-6) | (np.asarray(frz2.dua_res)
                                                   > 1e-6)
        assert (not bad.any()) or int(frz2.iters[0]) >= st.max_iter

    def test_solve_loop_frozen_refresh_cycle(self):
        """SPOpt.solve_loop alternates refresh/frozen transparently and keeps
        returning correct solutions as the PH objective moves."""
        from tpusppy.spopt import SPOpt

        n = 3
        names = farmer.scenario_names_creator(n)
        opt = SPOpt({"solver_refresh_every": 8,
                     "solver_options": {"max_iter": 2000, "restarts": 8,
                                        "eps_abs": 1e-9, "eps_rel": 1e-9}},
                    names, farmer.scenario_creator,
                    scenario_creator_kwargs={"num_scens": n})
        b = opt.batch
        ref = scipy_backend.solve_batch(b, mip=False)
        rng = np.random.RandomState(4)
        opt.solve_loop()          # refresh (cold)
        for it in range(4):       # frozen iterations on perturbed objectives
            q = b.c + rng.normal(scale=1e-3 * np.abs(b.c).max(),
                                 size=b.c.shape)
            x = opt.solve_loop(q=q)
            # residuals are OSQP-relative: scale tolerance by problem norms
            assert opt.pri_res.max() < 1e-5
        # back to the ORIGINAL objective: must recover the HiGHS optimum
        x = opt.solve_loop()
        objs = b.objective(x)
        for s in range(n):
            assert objs[s] == pytest.approx(ref[s].obj, rel=1e-5)
        assert opt._factors_age > 1   # the frozen path was actually exercised


class TestFarmerADMM:
    def make_batch(self, num_scens=3):
        names = farmer.scenario_names_creator(num_scens)
        return ScenarioBatch.from_problems(
            [farmer.scenario_creator(nm, num_scens=num_scens) for nm in names]
        )

    def test_scenario_batch_solve(self):
        batch = self.make_batch(3)
        sol = solve_batch(
            batch.c, batch.q2, batch.A, batch.cl, batch.cu, batch.lb, batch.ub,
            SETTINGS,
        )
        ref = scipy_backend.solve_batch(batch, mip=False)
        objs = batch.objective(np.asarray(sol.x))
        for s in range(3):
            assert objs[s] == pytest.approx(ref[s].obj, rel=1e-5)

    def test_ef_via_admm(self):
        from tpusppy.ef import solve_ef

        batch = self.make_batch(3)
        obj, xs = solve_ef(batch, solver="admm", settings=SETTINGS)
        assert obj == pytest.approx(-108390.0, rel=1e-4)


class TestBlockedExplicitInverse:
    """The large-n recursive Schur-inversion path (admm._explicit_inverse).

    XLA:TPU's TriangularSolve lowering OOMs around n~16k (9.2 GB of temps for
    a single full-height solve), so large SPD inverses recurse on 2x2 Schur
    blocks instead; the recursive path must agree with the Cholesky leaf
    path and handle batch dims and odd (non-multiple-of-leaf) sizes.
    """

    def test_blocked_matches_oneshot_and_numpy(self, monkeypatch):
        import jax.numpy as jnp

        from tpusppy.solvers import admm

        rng = np.random.default_rng(7)
        n = 97  # odd, prime: exercises uneven split points
        M = rng.standard_normal((3, n, n))
        K = jnp.asarray(M @ M.transpose(0, 2, 1) + n * np.eye(n))
        ref = admm._explicit_inverse(K)
        monkeypatch.setattr(admm, "_EXPLICIT_INV_LEAF_N", 16)
        blocked = admm._explicit_inverse(K)
        np.testing.assert_allclose(
            np.asarray(blocked), np.asarray(ref), rtol=0, atol=1e-9)
        np.testing.assert_allclose(
            np.asarray(blocked), np.linalg.inv(np.asarray(K)),
            rtol=0, atol=1e-9)

    def test_solve_batch_through_blocked_path(self, monkeypatch):
        """End-to-end LP solve with the factorization forced recursive."""
        from tpusppy.solvers import admm

        monkeypatch.setattr(admm, "_EXPLICIT_INV_LEAF_N", 4)
        rng = np.random.default_rng(3)
        c, A, cl, cu, lb, ub = random_feasible_lp(rng, n=11, m=9)
        ref = scipy_backend.solve_lp(c, A, cl, cu, lb, ub)
        # fresh jit cache key: settings differ from other tests' SETTINGS
        st = ADMMSettings(max_iter=2000, restarts=8,
                          eps_abs=1e-9, eps_rel=1e-9, sigma=1e-7)
        sol = solve_single(c, np.zeros(11), A, cl, cu, lb, ub, st)
        obj = float(c @ np.asarray(sol.x))
        assert abs(obj - ref.obj) <= 1e-5 * max(1.0, abs(ref.obj))


def _steer_lanes(monkeypatch, take):
    """Answer ``usable_solve`` as the TPU would for the right-hand-side
    counts ``take(R)`` accepts and run ``lanes_solve`` through the Pallas
    interpreter: the tests steer the program's choice, the program has no
    option for it."""
    import functools

    from tpusppy.solvers import pallas_kernels as pk

    usable, solve = pk.usable_solve, pk.lanes_solve
    monkeypatch.setattr(
        pk, "usable_solve",
        lambda S, N, R, platform=None, **kw: usable(
            S, N, R, "tpu" if take(R) else platform, **kw))
    monkeypatch.setattr(pk, "lanes_solve",
                        functools.partial(solve, interpret=True))


@pytest.fixture
def lanes_interpreted(monkeypatch):
    """The polish's saddle systems (one right-hand side) on the kernel; the
    inverses of K stay XLA's, so that the ADMM iterate is the same float."""
    _steer_lanes(monkeypatch, lambda R: R == 1)


@pytest.fixture
def lanes_inverse_interpreted(monkeypatch):
    """Both uses of the kernel, as the TPU runs a qualifying batch."""
    _steer_lanes(monkeypatch, lambda R: True)


F32 = ADMMSettings(dtype="float32", eps_abs=1e-5, eps_rel=1e-5)


def _two_refreshes(S, use_pallas):
    """Two refresh solves of a float32 farmer PH of S scenarios; the
    metrics registry they counted in."""
    from tpusppy.obs import metrics
    from tpusppy.opt.ph import PH

    ph = PH({"defaultPHrho": 1.0, "PHIterLimit": 1,
             "solver_refresh_every": 1,
             "solver_options": dict(
                 dtype="float32", eps_abs=1e-5, eps_rel=1e-5,
                 use_pallas=use_pallas, megastep=1)},
            farmer.scenario_names_creator(S), farmer.scenario_creator,
            scenario_creator_kwargs={"num_scens": S})
    ph.solve_loop()
    ph.solve_loop()
    assert metrics.value("phase.main.refresh.count") == 2
    return metrics


class TestPolishOnLanesSolve:
    """``_polish`` in float32 with its saddle systems on the batched
    elimination kernel (interpreted) against the ``jnp.linalg.solve`` path,
    farmer S=128: same scenarios accepted, same vertex."""

    S = 128

    def batch(self):
        return ScenarioBatch.from_problems(
            [farmer.scenario_creator(nm, num_scens=self.S)
             for nm in farmer.scenario_names_creator(self.S)])

    @staticmethod
    def both_paths(fn, request):
        """``fn()`` traced on the XLA path, then with the kernel forced."""
        import jax

        xla = jax.jit(fn)()
        request.getfixturevalue("lanes_interpreted")
        return xla, jax.jit(lambda: fn())()

    @staticmethod
    def close(a, b, scale, rel):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.all(np.abs(a - b) <= rel * np.maximum(1.0, scale))

    def test_adaptive_solve_accepts_the_same_scenarios(self, request):
        """The program's own flow: the float32 ADMM iterate, then the
        polish, accepted per scenario only where it beats the iterate."""
        from tpusppy.solvers import admm

        b = self.batch()
        args = (b.c, b.q2, b.A, b.cl, b.cu, b.lb, b.ub)
        xla, lanes = self.both_paths(
            lambda: admm._solve_impl(*args, F32, None), request)
        np.testing.assert_array_equal(np.asarray(xla.raw[0]),
                                      np.asarray(lanes.raw[0]))
        took = lambda s: np.any(np.asarray(s.x) != np.asarray(s.raw[0]),
                                axis=1)
        assert 0 < took(xla).sum() < self.S
        np.testing.assert_array_equal(took(xla), took(lanes))
        self.close(xla.pri_res, lanes.pri_res,
                   np.abs(np.asarray(xla.z)).max(axis=1), 1e-5)
        self.close(xla.dua_res, lanes.dua_res,
                   np.abs(np.asarray(b.c)).max(axis=1), 1e-5)
        obj = lambda s: b.objective(np.asarray(s.x, np.float64))
        self.close(obj(xla), obj(lanes), np.abs(obj(xla)), 1e-6)

    def test_polish_from_an_accurate_iterate(self, request):
        """Every scenario's polish accepted (the incoming residuals are
        infinite): all 128 vertices agree between the two paths."""
        import jax.numpy as jnp

        from tpusppy.solvers import admm

        b = self.batch()
        args = (b.c, b.q2, b.A, b.cl, b.cu, b.lb, b.ub)
        s64 = solve_batch(*args, ADMMSettings())
        dt = jnp.float32
        c, q2, A, cl, cu, lb, ub, masks, _ = admm._prep(*args, F32, None)
        D, E = admm._ruiz(A, q2, F32.scaling_iters)
        cost = 1.0 / jnp.maximum(jnp.max(jnp.abs(c * D), axis=1), 1e-8)
        qs, q2s, As, cls, cus, lbs, ubs, _, (x, z, y, yx) = admm._scale(
            c, q2, A, cl, cu, lb, ub, D, E, cost, None,
            (s64.x, s64.z, s64.y, s64.yx), dt)
        state = admm._start_state(x, z, jnp.clip(x, lbs, ubs), y, yx)
        xla, lanes = self.both_paths(
            lambda: admm._polish(state, qs, q2s, As, cls, cus, lbs, ubs,
                                 masks, F32), request)
        assert np.all(np.isfinite(xla.pri)) and np.all(np.isfinite(lanes.pri))
        self.close(xla.pri, lanes.pri, np.abs(np.asarray(xla.z)).max(axis=1),
                   1e-5)
        self.close(xla.dua, lanes.dua, np.abs(np.asarray(qs)).max(axis=1),
                   1e-5)
        obj = lambda s: b.objective(np.asarray(s.x * D, np.float64))
        self.close(obj(xla), obj(lanes), np.abs(obj(xla)), 1e-6)

    @pytest.mark.parametrize("use_pallas, counted", [("auto", 2), (True, 2),
                                                     (False, 0)])
    def test_refresh_counter(self, lanes_interpreted, use_pallas, counted):
        """``refresh.lanes_linalg`` counts once per refresh when the
        selector engages, never under ``use_pallas=False``."""
        metrics = _two_refreshes(self.S, use_pallas)
        assert metrics.value("refresh.lanes_linalg") == counted
        # the host's twin of the inverse's choice said no (the CPU)
        assert metrics.value("refresh.lanes_inverse") == 0


class TestInverseOnLanesSolve:
    """``_explicit_inverse`` of the dense engine's K on the batched
    elimination kernel (interpreted) against XLA's Cholesky inverse: the
    gate, the counter, and the adaptive solve around it."""

    S = 128

    @pytest.mark.parametrize("S, n, dtype, use_pallas, expect", [
        (1000, 44, "float32", "auto", 128),     # farmer x4: the cells' shape
        (1000, 44, "float32", True, 128),
        (128, 11, "float32", "auto", 128),
        (1000, 45, "float32", "auto", 128),     # the budget's last size
        (1000, 46, "float32", "auto", None),    # past the VMEM budget
        (64, 44, "float32", "auto", None),      # lanes not filled
        (1, 44, "float32", "auto", None),       # what the shared engine hands
        (1000, 44, "float64", "auto", None),
        (1000, 44, "float32", False, None),
    ])
    def test_gate(self, lanes_inverse_interpreted, S, n, dtype, use_pallas,
                  expect):
        """``usable_solve`` with R = N decides, at trace time and in the
        host's twin alike; ``use_pallas=False`` turns it off."""
        import jax
        import jax.numpy as jnp

        from tpusppy.solvers import admm

        st = ADMMSettings(dtype=dtype, use_pallas=use_pallas)
        assert admm._lanes_bs(st, S, n, st.jdtype(), R=n) == expect
        assert admm.lanes_inverse(st, S, 28, n) == (expect is not None)
        jaxpr = jax.make_jaxpr(lambda K: admm._explicit_inverse(K, st))(
            jax.ShapeDtypeStruct((S, n, n), jnp.dtype(dtype)))
        assert ("pallas_call" in str(jaxpr)) == (expect is not None)

    def test_no_settings_follows_usable_solve(self, lanes_inverse_interpreted):
        """A caller with no settings (``ipm``, the shared engine's batch of
        1) gets the gate alone."""
        import jax
        import jax.numpy as jnp

        from tpusppy.solvers import admm

        for shape, took in (((1000, 44, 44), True), ((1, 44, 44), False),
                            ((1, 520, 520), False)):
            jaxpr = jax.make_jaxpr(admm._explicit_inverse)(
                jax.ShapeDtypeStruct(shape, jnp.float32))
            assert ("pallas_call" in str(jaxpr)) == took, shape

    def test_adaptive_solve_agrees(self, request):
        """The refresh solve's program with its four inverses on the
        kernel: the refinement against the exact K makes either inverse the
        same operator: the same scenarios take the polish and end at the
        same vertex (1e-6), and a row that keeps its ADMM iterate (float32
        spends its whole budget at a primal residual near 2e-3) agrees in
        the objective to 1e-4."""
        import jax

        from tpusppy.solvers import admm

        b = ScenarioBatch.from_problems(
            [farmer.scenario_creator(nm, num_scens=self.S)
             for nm in farmer.scenario_names_creator(self.S)])
        args = (b.c, b.q2, b.A, b.cl, b.cu, b.lb, b.ub)
        fn = lambda: admm._solve_impl(*args, F32, None)
        xla = jax.jit(fn)()
        request.getfixturevalue("lanes_inverse_interpreted")
        lanes = jax.jit(lambda: fn())()
        took = lambda s: np.any(np.asarray(s.x) != np.asarray(s.raw[0]),
                                axis=1)
        assert 0 < took(xla).sum() < self.S
        np.testing.assert_array_equal(took(xla), took(lanes))
        close = TestPolishOnLanesSolve.close
        obj = lambda s: b.objective(np.asarray(s.x, np.float64))
        t = took(xla)
        close(obj(xla)[t], obj(lanes)[t], np.abs(obj(xla))[t], 1e-6)
        close(obj(xla), obj(lanes), np.abs(obj(xla)), 1e-4)
        close(xla.pri_res, lanes.pri_res,
              np.abs(np.asarray(xla.z)).max(axis=1), 1e-4)

    @pytest.mark.parametrize("use_pallas, counted", [("auto", 2), (True, 2),
                                                     (False, 0)])
    def test_refresh_counter(self, lanes_inverse_interpreted, use_pallas,
                             counted):
        """``refresh.lanes_inverse`` counts once per refresh when the
        host's twin of the trace-time choice says yes, never under
        ``use_pallas=False`` (and never on the CPU:
        ``TestPolishOnLanesSolve.test_refresh_counter``)."""
        metrics = _two_refreshes(self.S, use_pallas)
        assert metrics.value("refresh.lanes_inverse") == counted
        assert metrics.value("refresh.lanes_linalg") == counted


@pytest.fixture
def sweep_kernel_interpreted(monkeypatch):
    """``pallas_kernels.usable`` answered as on the TPU and the sweep kernel
    run through the Pallas interpreter, so that ``admm._sweep_block`` picks
    a block by its own rule: the test steers what the program observes,
    the program has no option for it."""
    import functools

    from tpusppy.solvers import pallas_kernels as pk

    usable, sweeps = pk.usable, pk.fused_sweeps
    monkeypatch.setattr(
        pk, "usable", lambda S, m, n, platform=None, **kw: usable(
            S, m, n, "tpu", **kw))
    monkeypatch.setattr(pk, "fused_sweeps",
                        functools.partial(sweeps, interpret=True))


class TestKernelCheckpoint:
    """``admm.kernel_checkpoint``, the host's twin of the choice that makes
    a step of the dense engine's sweep loop one kernel call, and the
    counter ``refresh.kernel_checkpoint`` spopt keeps by it."""

    @pytest.mark.parametrize("S, m, n, use_pallas, expect", [
        (1000, 28, 44, "auto", 128),    # farmer x4: the cells' shape
        (256, 28, 44, "auto", 128),     # its rung
        (1000, 28, 44, True, 128),
        (3, 7, 11, "auto", 3),          # one block: the whole batch
        (1000, 28, 44, False, None),
        (10000, 7, 11, "auto", None),   # many coarse blocks: measured loss
        (100, 4626, 2928, True, None),  # past the VMEM budget
    ])
    def test_twin_follows_sweep_block(self, sweep_kernel_interpreted, S, m,
                                      n, use_pallas, expect):
        from tpusppy.solvers import admm

        st = dataclasses.replace(F32, use_pallas=use_pallas)
        assert admm._sweep_block(st, S, m, n)[0] == expect
        assert admm.kernel_checkpoint(st, S, m, n) == (expect is not None)

    @pytest.mark.parametrize("use_pallas", ["auto", True, False])
    def test_false_off_the_tpu(self, use_pallas):
        """The tests' backend: ``_sweep_block`` picks no block, XLA's sweep
        runs, and the twin says so."""
        from tpusppy.solvers import admm

        st = dataclasses.replace(F32, use_pallas=use_pallas)
        for S, m, n in ((1000, 28, 44), (3, 7, 11)):
            assert admm._sweep_block(st, S, m, n)[0] is None
            assert not admm.kernel_checkpoint(st, S, m, n)

    def test_false_with_a_dense_P(self, sweep_kernel_interpreted):
        from tpusppy.solvers import admm

        assert admm._sweep_block(F32, 1000, 28, 44, P=object())[0] is None

    @pytest.mark.parametrize("use_pallas, counted", [("auto", 2), (True, 2),
                                                     (False, 0)])
    def test_refresh_counter(self, sweep_kernel_interpreted, use_pallas,
                             counted):
        """Two refresh solves of a float32 farmer PH with every step of
        their sweep loops one (interpreted) kernel call: counted once a
        refresh, never under ``use_pallas=False``; the elimination kernel's
        counters keep their own gates (the CPU: 0)."""
        metrics = _two_refreshes(32, use_pallas)
        assert metrics.value("refresh.kernel_checkpoint") == counted
        assert metrics.value("refresh.lanes_inverse") == 0

    def test_counter_is_zero_on_xla(self):
        metrics = _two_refreshes(8, "auto")
        assert metrics.value("refresh.kernel_checkpoint") == 0


# sha256 (first 16 hex digits) of ``lower(...).as_text()`` of the dense
# engine's two solve programs on the CPU, farmer with 3 scenarios, at the
# commit before PR 48 (0d46a39): the XLA sweep's loop body is that
# commit's byte for byte, whatever the kernel path became
XLA_SWEEP_TEXT = {
    ("float64", "solve_batch_factored"): "bfdf0de6b7ecd734",
    ("float64", "solve_batch_frozen"): "fcfbcf84bd7223e8",
    ("float32", "solve_batch_factored"): "ce70dbe0a9b4b443",
    ("float32", "solve_batch_frozen"): "b90d92099cc7e486",
    ("float32_default", "solve_batch_factored"): "ce70dbe0a9b4b443",
    ("float32_default", "solve_batch_frozen"): "a0b3f78670bab760",
}


@pytest.mark.parametrize("mode", ["float64", "float32", "float32_default"])
def test_xla_sweep_lowers_to_the_text_it_had(mode):
    """The lowered text of ``solve_batch_factored`` and
    ``solve_batch_frozen`` on the CPU at a tier-1 shape (farmer, 3
    scenarios; float64, float32, and float32 under the lowered sweep
    mode) hashes to what the parent of PR 48 lowered: the XLA sweep is
    untouched by the kernel path's one-call step."""
    import functools
    import hashlib

    import jax
    import jax.numpy as jnp

    from tpusppy.solvers import admm

    S = 3
    b = ScenarioBatch.from_problems(
        [farmer.scenario_creator(nm, num_scens=S)
         for nm in farmer.scenario_names_creator(S)])
    st = {"float64": ADMMSettings(), "float32": F32,
          "float32_default": dataclasses.replace(
              F32, sweep_precision="default")}[mode]
    dt = st.jdtype()
    assert not admm.kernel_checkpoint(st, S, b.num_rows, b.num_vars)
    args = tuple(jnp.asarray(v, dt)
                 for v in (b.c, b.q2, b.A, b.cl, b.cu, b.lb, b.ub))
    _, factors = jax.eval_shape(
        functools.partial(admm.solve_batch_factored._jitted, settings=st),
        *args)
    warm = tuple(jax.ShapeDtypeStruct(s, dt)
                 for s in ((S, b.num_vars), (S, b.num_rows),
                           (S, b.num_rows), (S, b.num_vars)))
    lowered = {
        "solve_batch_factored": admm.solve_batch_factored._jitted.lower(
            *args, settings=st),
        "solve_batch_frozen": admm.solve_batch_frozen._jitted.lower(
            *args, factors, settings=st, warm=warm)}
    for prog, low in lowered.items():
        digest = hashlib.sha256(low.as_text().encode()).hexdigest()[:16]
        assert digest == XLA_SWEEP_TEXT[mode, prog], (mode, prog, digest)
