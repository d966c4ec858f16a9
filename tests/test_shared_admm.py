"""Shared-A engine divergence guard (solvers/shared_admm.py).

Known pre-existing failure mode (PR 2 notes: "shared engine NaNs on
random fixtures"): when the per-scenario diagonal deviation dq2 is large
relative to the shared K — e.g. SharedFactors from an LP refresh
(q2ref = 0) reused for a big-prox frozen solve, or unstructured random
families whose free gamma adaptation explodes — the shared-K refinement
iteration is non-contractive, the iterates race to inf within one
checkpoint block, and every later residual is NaN.  NaN then poisons
``stop_stats``, the plateau detector and the host acceptance tests.

The in-loop guard freezes exploding scenarios at their last finite
iterate and reports INF residuals with ``done=False`` — an honest
"diverged" the host rescue machinery can act on — and the restart-level
shared-rho adaptation excludes the non-finite ratios so one exploding
scenario cannot poison the shared base.
"""

import numpy as np
import pytest

from tpusppy.solvers import admm, shared_admm
from tpusppy.solvers.admm import ADMMSettings


def _lp_family(seed=0, S=4, m=8, n=6):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(m, n))
    c = rng.normal(size=(S, n))
    q2 = np.zeros((S, n))
    b = rng.normal(size=(S, m))
    return (c, q2, A, b - 1.0, b + 1.0,
            np.full((S, n), -100.0), np.full((S, n), 100.0))


def test_frozen_dq2_divergence_is_guarded():
    """Known-diverging reproduction (seed 0): LP-refresh factors reused
    with a large prox q2.  Without the guard every iterate and residual
    ends NaN; with it the iterates stay finite, the residuals report inf,
    done stays False, and stop_stats carries no NaN."""
    c, q2, A, cl, cu, lb, ub = _lp_family(seed=0)
    st = ADMMSettings(max_iter=300, restarts=3, polish=False)
    sol, fac = shared_admm.solve_shared_factored(
        c, q2, A, cl, cu, lb, ub, settings=st)
    q2_big = np.full_like(q2, 50.0)     # sudden big prox: dq2 refinement
    sol2 = shared_admm.solve_shared_frozen(      # is non-contractive
        c, q2_big, A, cl, cu, lb, ub, fac, settings=st, warm=sol.raw)
    pri = np.asarray(sol2.pri_res)
    dua = np.asarray(sol2.dua_res)
    # the reproduction actually diverges (inf reported, never NaN)
    assert np.isinf(pri).any() or np.isinf(dua).any()
    assert not np.isnan(pri).any() and not np.isnan(dua).any()
    # frozen iterates: every state leaf stays finite
    for leaf in (sol2.x, sol2.z, sol2.y, sol2.yx, *sol2.raw):
        assert np.isfinite(np.asarray(leaf)).all()
    # diverged scenarios are NOT reported converged
    assert not np.asarray(sol2.done)[np.isinf(pri) | np.isinf(dua)].any()
    # stop_stats (the segmented continuation's single-fetch decision
    # vector) carries inf, never NaN
    st4 = np.asarray(admm.stop_stats(sol2))
    assert not np.isnan(st4).any()
    assert not bool(st4[3])


def test_guard_does_not_perturb_healthy_solves():
    """The guard is a no-op on healthy batches: the same LP family solved
    adaptively converges to its usual residual floor."""
    c, q2, A, cl, cu, lb, ub = _lp_family(seed=0)
    st = ADMMSettings(max_iter=2000, restarts=6, polish=False,
                      eps_abs=1e-8, eps_rel=1e-8)
    sol = shared_admm.solve_shared(c, q2, A, cl, cu, lb, ub, settings=st)
    assert float(np.asarray(sol.pri_res).max()) < 1e-5
    assert float(np.asarray(sol.dua_res).max()) < 1e-5
    assert np.isfinite(np.asarray(sol.x)).all()


def test_adaptive_base_survives_partial_divergence():
    """One diverging scenario in an otherwise-healthy ADAPTIVE batch must
    not poison the shared rho base (the restart gmean excludes non-finite
    ratios): the healthy scenarios still converge."""
    c, q2, A, cl, cu, lb, ub = _lp_family(seed=1)
    # scenario 0 gets an absurd objective scale so its iterates blow past
    # BIG within the first restarts while the rest stay ordinary
    c = c.copy()
    c[0] *= 1e18
    lb = lb.copy(); ub = ub.copy()
    lb[0] = -1e18
    ub[0] = 1e18
    st = ADMMSettings(max_iter=800, restarts=4, polish=False)
    sol = shared_admm.solve_shared(c, q2, A, cl, cu, lb, ub, settings=st)
    pri = np.asarray(sol.pri_res)
    dua = np.asarray(sol.dua_res)
    assert not np.isnan(pri).any() and not np.isnan(dua).any()
    # the healthy tail stays at ordinary ADMM accuracy regardless of
    # scenario 0 (a poisoned shared base drives EVERY scenario to inf/NaN)
    assert float(np.maximum(pri, dua)[1:].max()) < 1e-1
    assert np.isfinite(np.asarray(sol.x)).all()


# ---------------------------------------------------------------------------
# K^-1 as diagonal plus low rank (structured_kkt.DiagLowRank): the regime a
# dense shared A of few rows beside its columns takes.  sslp at the
# benchmark's instance (10 servers, 50 clients: A is 60 x 520) is the one
# family that meets the rule; "dense" below is the same call handed the
# (n, n) explicit inverse.
# ---------------------------------------------------------------------------
SSLP_BENCH = dict(num_servers=10, num_clients=50)
TOL = {"float32": 5e-6, "float64": 1e-12}   # a K^-1 apply against float64


@pytest.fixture(scope="module")
def sslp8():
    from tpusppy.ir import ScenarioBatch
    from tpusppy.models import sslp

    b = ScenarioBatch.from_problems(
        [sslp.scenario_creator(nm, **SSLP_BENCH)
         for nm in sslp.scenario_names_creator(8)])
    assert b.A_shared is not None and b.A_shared.shape == (60, 520)
    return b


def _args(b, q=None, q2=None):
    return (b.c if q is None else q, b.q2 if q2 is None else q2,
            np.asarray(b.A_shared), b.cl, b.cu, b.lb, b.ub)


def _prox(b, rho=1.0):
    """The hub's objective around a made-up xbar: prox rho on the nonants."""
    idx = b.tree.nonant_indices
    q, q2 = np.array(b.c), np.array(b.q2)
    q[:, idx] -= rho * 0.4
    q2[:, idx] += rho
    return q, q2


def _dense_twin(f, A, st):
    """``f`` with the (n, n) explicit inverse of the K its operator
    inverts, built from the same scaled A and penalties."""
    import jax.numpy as jnp

    As = jnp.asarray(A, f.D.dtype) * f.E[:, None] * f.D[None, :]
    K = (jnp.einsum("mn,m,mk->nk", As, f.rho_a, As)
         + jnp.diag(f.q2ref + f.rho_x + st.sigma))
    return f._replace(Kinv=admm._explicit_inverse(K[None])[0])


def _dense_regime(fn):
    """``fn`` run with the shape rule answering no: the parent's program.
    The jitted entry points cache a trace by shapes, so the caller hands an
    un-jitted impl."""
    from unittest import mock

    with mock.patch.object(shared_admm, "lowrank_kinv", lambda A: False):
        return fn()


@pytest.mark.parametrize("profile", ["lp", "prox", "eqx"])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_lowrank_operator_is_the_dense_inverse_on_sslps_K(sslp8, dtype,
                                                          profile):
    """The operator against float64 ``solve`` on sslp's Ruiz-scaled K at
    the restart loop's own penalties: an LP (the Lagrangian spoke), the
    hub's prox term, a dive's clamped columns (``eqx`` raises rho_x a
    thousandfold); the first apply and after the two refinement passes
    against the exact K, neither worse than the explicit inverse's."""
    import jax.numpy as jnp

    from tpusppy.solvers import structured_kkt as sk

    b = sslp8
    st = ADMMSettings(dtype=dtype)
    dt = st.jdtype()
    n, idx = b.num_vars, b.tree.nonant_indices
    A = jnp.asarray(b.A_shared, dt)
    q2raw = np.zeros(n)
    if profile == "prox":
        q2raw[idx] = 1.0
    D, E = shared_admm._ruiz_shared(A, jnp.asarray(q2raw, dt),
                                    st.scaling_iters)
    As = A * E[:, None] * D[None, :]
    eq = np.all(np.abs(b.cu - b.cl) < 1e-10, axis=0)
    rho_a = jnp.asarray(np.where(eq, st.rho * st.rho_eq_scale, st.rho), dt)
    rho_x = np.full(n, st.rho)
    if profile == "eqx":
        rho_x[idx] *= st.rho_eq_scale
    q2ref = jnp.asarray(q2raw, dt) * D * D
    d = q2ref + jnp.asarray(rho_x, dt) + st.sigma
    assert float(jnp.min(d)) > 0.0
    op = sk.factor_lowrank(As, d, rho_a)
    assert isinstance(op, sk.DiagLowRank)
    assert (op.dinv.shape, op.W.shape, op.N.shape) == ((n,), (60, n), (60, n))
    K = jnp.einsum("mn,m,mk->nk", As, rho_a, As) + jnp.diag(d)
    Kinv = admm._explicit_inverse(K[None])[0]
    K64 = np.asarray(K, np.float64)
    rhs = np.random.default_rng(3).normal(size=(8, n))
    truth = np.linalg.solve(K64, rhs.T).T

    def errs(kinv):
        off = lambda x: float(np.max(
            np.linalg.norm(np.asarray(x, np.float64) - truth, axis=1)
            / np.linalg.norm(truth, axis=1)))
        r = jnp.asarray(rhs, dt)
        x = sk.apply_kinv_like(kinv, r)
        first = off(x)
        for _ in range(st.solve_refine):
            x = x + sk.apply_kinv_like(kinv, r - x @ K)
        return first, off(x)

    cond = np.linalg.cond(K64)
    for got, dense in zip(errs(op), errs(Kinv)):
        assert got <= max(2.0 * dense, TOL[dtype] * cond), (got, dense, cond)
    # the operator laid out is the inverse, entry by entry
    laid = np.asarray(sk.apply_kinv_like(op, jnp.eye(n, dtype=dt)))
    exact = np.linalg.inv(K64)
    assert (np.max(np.abs(laid - exact))
            <= TOL[dtype] * cond * np.max(np.abs(exact)))


def _three_solves(b, st, dense):
    """(adaptive x, frozen prox x, factors) of sslp under ``st``: the
    rule's regime, or the dense one (the adaptive impl traced with the
    rule answering no, the frozen solve handed the explicit inverse)."""
    import jax

    if dense:
        def adaptive():
            with jax.default_matmul_precision(st.matmul_precision):
                return jax.jit(lambda *a: shared_admm._solve_shared_impl(
                    *a, st, None, want_factors=True))(*_args(b))
        sol, f = _dense_regime(adaptive)
    else:
        sol, f = shared_admm.solve_shared_factored(*_args(b), settings=st)
    q, q2 = _prox(b)    # dq2 != 0: the two extra refinement passes
    froz = shared_admm.solve_shared_frozen(
        *_args(b, q, q2), f, settings=st, warm=sol.raw)
    return sol, froz, f


def test_lowrank_solves_agree_with_the_dense_regime(sslp8):
    """``solve_shared``, ``solve_shared_factored`` then
    ``solve_shared_frozen`` on sslp in float64: the rule's regime against
    the dense one, sweep for sweep."""
    import jax.numpy as jnp

    from tpusppy.solvers import structured_kkt as sk

    b = sslp8
    kw = dict(eps_abs=1e-8, eps_rel=1e-8, max_iter=400, restarts=3)
    st = ADMMSettings(factors_keep_K=False, **kw)
    sol, froz, f = _three_solves(b, st, dense=False)
    assert isinstance(f.Kinv, sk.DiagLowRank) and f.K is None
    plain = shared_admm.solve_shared(*_args(b), settings=st)
    np.testing.assert_allclose(np.asarray(plain.x), np.asarray(sol.x),
                               atol=1e-7)
    sol_d, froz_d, f_d = _three_solves(b, st, dense=True)
    assert sk.is_dense_kinv(f_d.Kinv) and f_d.Kinv.shape == (520, 520)
    np.testing.assert_allclose(np.asarray(f.rho_a), np.asarray(f_d.rho_a),
                               rtol=1e-6)
    for got, want in ((sol, sol_d), (froz, froz_d)):
        np.testing.assert_allclose(np.asarray(got.x), np.asarray(want.x),
                                   atol=1e-4)
        assert int(got.iters[0]) == int(want.iters[0])
    # the same factors handed the explicit inverse of the K they invert
    twin = shared_admm.solve_shared_frozen(
        *_args(b, *_prox(b)), _dense_twin(f, b.A_shared, st), settings=st,
        warm=sol.raw)
    np.testing.assert_allclose(np.asarray(froz.x), np.asarray(twin.x),
                               atol=1e-6)
    # the operator comes with no K, whatever factors_keep_K says ...
    sol_k, froz_k, f_k = _three_solves(b, ADMMSettings(**kw), dense=False)
    assert isinstance(f_k.Kinv, sk.DiagLowRank) and f_k.K is None
    np.testing.assert_array_equal(np.asarray(froz_k.x), np.asarray(froz.x))
    # ... and factors handed the dense K refine against it with one
    # (n, n) product, as the adaptive solve did before: the same answer
    As = jnp.asarray(b.A_shared) * f.E[:, None] * f.D[None, :]
    K = (jnp.einsum("mn,m,mk->nk", As, f.rho_a, As)
         + jnp.diag(f.q2ref + f.rho_x + st.sigma))
    froz_K = shared_admm.solve_shared_frozen(
        *_args(b, *_prox(b)), f._replace(K=K), settings=st, warm=sol.raw)
    np.testing.assert_allclose(np.asarray(froz_K.x), np.asarray(froz.x),
                               atol=1e-6)


def test_lowrank_float32_stands_as_near_float64_as_the_dense_regime(sslp8):
    """In float32 two regimes (as two fusings of one program) part by the
    defect's own floor within tens of sweeps, so each is held to the
    float64 iterate of the same budget: the operator's solves stand no
    further from it than the explicit inverse's."""
    b = sslp8
    budget = dict(max_iter=40, restarts=1, factors_keep_K=False)
    st = ADMMSettings(dtype="float32", eps_abs=1e-5, eps_rel=1e-5, **budget)
    ref = _three_solves(b, ADMMSettings(eps_abs=1e-5, eps_rel=1e-5,
                                        **budget), dense=False)
    low = _three_solves(b, st, dense=False)
    dense = _three_solves(b, st, dense=True)
    for k in (0, 1):
        off = lambda s: float(np.max(np.abs(
            np.asarray(s[k].x, np.float64) - np.asarray(ref[k].x))))
        assert off(low) <= 2.0 * off(dense) + 1e-4, (k, off(low), off(dense))
        assert off(low) < 5e-2
        worst = lambda s: float(max(np.max(s[k].pri_res),
                                    np.max(s[k].dua_res)))
        assert worst(low) <= 2.0 * worst(dense)


@pytest.mark.parametrize("mode", ["high", "default"])
def test_lowrank_applies_run_lowered_under_sweep_precision(sslp8, mode):
    """Under a lowered ``sweep_precision`` the operator's two products go
    through ``precision.contract`` as ``kinv_apply``'s do: the frozen solve
    lands inside the mixed-precision guard's bar of the full-precision one
    on the same factors (the bar ``tests/test_precision.py`` holds the
    shared engine to)."""
    import dataclasses

    from tpusppy.solvers import precision
    from tpusppy.solvers import structured_kkt as sk

    b = sslp8
    st = ADMMSettings(max_iter=300, restarts=2, factors_keep_K=False)
    q, q2 = _prox(b)
    sol, f = shared_admm.solve_shared_factored(*_args(b, q, q2), settings=st)
    assert isinstance(f.Kinv, sk.DiagLowRank)
    ref = shared_admm.solve_shared_frozen(*_args(b, q, q2), f, settings=st,
                                          warm=sol.raw)
    ref_worst = float(max(np.max(ref.pri_res), np.max(ref.dua_res)))
    st_lo = dataclasses.replace(st, sweep_precision=mode,
                                precision_refine_iters=300)
    got = shared_admm.solve_shared_frozen(*_args(b, q, q2), f,
                                          settings=st_lo, warm=sol.raw)
    worst = float(max(np.max(got.pri_res), np.max(got.dua_res)))
    assert np.isfinite(worst)
    assert worst <= 10.0 * max(ref_worst, st.eps_abs)
    # the lowered apply is the lowered contraction of the same operator
    r = np.random.default_rng(5).normal(size=(8, 520))
    want = r * np.asarray(f.Kinv.dinv) - np.asarray(precision.contract(
        "sm,mn->sn", precision.contract("sn,mn->sm", r, f.Kinv.W, mode),
        f.Kinv.N, mode))
    np.testing.assert_allclose(
        np.asarray(sk.apply_kinv_like(f.Kinv, r, mode)), want, atol=1e-12)
    exact = np.asarray(sk.apply_kinv_like(f.Kinv, r))
    assert 0 < np.max(np.abs(want - exact)) < 1e-1 * np.max(np.abs(exact))


def test_lowrank_factors_ride_a_megastep_window(sslp8):
    """One window of ``sharded.make_wheel_megastep`` on sslp S=8 carrying
    the operator through ``jit_mega`` against the same window handed the
    dense inverse."""
    from tpusppy.parallel import sharded
    from tpusppy.solvers import structured_kkt as sk

    st = ADMMSettings(max_iter=120, restarts=2, factors_keep_K=False)
    mesh = sharded.make_mesh(1)
    arr = sharded.shard_batch(sslp8, mesh)
    idx = sslp8.tree.nonant_indices
    refresh, _ = sharded.make_ph_step_pair(idx, st, mesh)
    state = sharded.init_state(arr, 1.0, st)
    state, _, _ = refresh(state, arr, 0.0)
    state, _, factors = refresh(state, arr, 1.0)
    assert isinstance(factors.Kinv, sk.DiagLowRank)
    mega = sharded.make_wheel_megastep(idx, st, mesh, n_iters=3,
                                       donate=False)
    S, nv = arr.c.shape
    out = {}
    for tag, fac in (("lowrank", factors),
                     ("dense", _dense_twin(factors, sslp8.A_shared, st))):
        s, packed = mega(state, arr, 1.0, fac, -1.0, 3, np.inf)
        m = sharded.megastep_unpack(np.asarray(packed), 3, S, nv,
                                    arr.nid_sk.shape[1])
        assert m["executed"] == 3 and not m["refresh_hit"]
        out[tag] = (np.asarray(s.x), np.asarray(s.W), m["conv"])
    for a, b in zip(out["lowrank"], out["dense"]):
        np.testing.assert_allclose(a, b, atol=1e-6)


def _sslp_A(servers, clients):
    from tpusppy.models import sslp

    return sslp.scenario_creator("Scenario1", num_servers=servers,
                                 num_clients=clients).A


@pytest.mark.parametrize("A, takes", [
    (lambda: _sslp_A(10, 50), True),             # 60 x 520: the benchmark's
    (lambda: _sslp_A(5, 15), False),             # 20 x 85: sslp's default
    (lambda: np.zeros((128, 512)), True),        # one MXU tile of rows
    (lambda: np.zeros((129, 512)), False),       # ... and one row more
    (lambda: np.zeros((60, 511)), False),
    (lambda: np.zeros((275, 339)), False),       # usar: m of the order of n
    (lambda: np.zeros((242, 132)), False),       # uc_lite: more rows
], ids=["sslp_10_50", "sslp_default", "128x512", "129x512", "60x511",
        "usar", "uc_lite"])
def test_lowrank_rule_reads_the_shape(A, takes):
    from tpusppy.solvers import structured_kkt as sk

    A = np.asarray(A())
    assert sk.lowrank_kinv(A) is takes


def test_lowrank_rule_leaves_a_sparse_family_alone(sslp8):
    """The same 60 x 520 matrix as a ``SparseA``: its own regimes, and the
    factors of its solve carry the dense inverse as before."""
    from tpusppy.solvers import structured_kkt as sk
    from tpusppy.solvers.sparse import SparseA

    A = SparseA.from_dense(np.asarray(sslp8.A_shared))
    assert not sk.lowrank_kinv(A)
    st = ADMMSettings(max_iter=20, restarts=1)
    b = sslp8
    _, f = shared_admm.solve_shared_factored(
        b.c, b.q2, A, b.cl, b.cu, b.lb, b.ub, settings=st)
    assert sk.is_dense_kinv(f.Kinv) and f.K is None


@pytest.mark.parametrize("size, counted", [(SSLP_BENCH, 2), ({}, 0)],
                         ids=["sslp_10_50", "sslp_default"])
def test_refresh_counts_lowrank_kinv_once_a_refresh(size, counted):
    """``refresh.lowrank_kinv`` (spopt ``_solve_amortized``): one a refresh
    solve whose factors are the operator, none where the rule says no."""
    from tpusppy.models import sslp
    from tpusppy.obs import metrics
    from tpusppy.opt.ph import PH

    ph = PH({"defaultPHrho": 1.0, "PHIterLimit": 1,
             "solver_refresh_every": 1,
             "solver_options": dict(max_iter=20, restarts=1, megastep=1)},
            sslp.scenario_names_creator(3), sslp.scenario_creator,
            scenario_creator_kwargs=dict(size))
    ph.solve_loop()
    ph.solve_loop()
    assert metrics.value("phase.main.refresh.count") == 2
    assert metrics.value("refresh.lowrank_kinv") == counted


def test_aot_keys_the_frozen_program_on_the_factors_pytree(sslp8, tmp_path):
    """``solvers/aot.py`` keys a call on its treedef and leaf avals: the
    frozen program handed the operator is another key than the one handed
    the (n, n) inverse at the same shapes (a clean miss, never a stale
    hit), and each key is compiled once."""
    from tpusppy.obs import metrics
    from tpusppy.solvers import aot

    b = sslp8
    st = ADMMSettings(max_iter=8, restarts=1, factors_keep_K=False)
    sol, f = shared_admm.solve_shared_factored(*_args(b), settings=st)
    aot.set_cache_path(str(tmp_path))
    xs = []
    for fac in (f, _dense_twin(f, b.A_shared, st), f):
        xs.append(np.asarray(shared_admm.solve_shared_frozen(
            *_args(b), fac, settings=st, warm=sol.raw).x))
    assert metrics.value("aot.misses") == 2 and metrics.value("aot.hits") == 0
    np.testing.assert_allclose(xs[0], xs[1], atol=1e-6)
    np.testing.assert_array_equal(xs[0], xs[2])


@pytest.mark.parametrize("regime", ["dense", "lowrank", "sparse",
                                    "structured"])
@pytest.mark.parametrize("keep_K", [False, True])
def test_factors_hold_a_K_in_the_dense_regime_only(regime, keep_K):
    """What ``solve_shared_factored`` hands back by regime (sslp 5 x 15
    dense and as triplets, sslp 10 x 50, uc_lite with its block structure
    attached): the (n, n) ``K`` only beside the explicit inverse of a dense
    ``A`` and only where ``factors_keep_K`` asks; an operator comes with
    none under either setting, and the restart scan's carry agrees."""
    import functools

    import jax

    from tpusppy.ir import ScenarioBatch
    from tpusppy.models import sslp, uc_lite
    from tpusppy.solvers import structured_kkt as sk
    from tpusppy.solvers.sparse import SparseA

    mod, kw = {"dense": (sslp, {}), "sparse": (sslp, {}),
               "lowrank": (sslp, SSLP_BENCH),
               "structured": (uc_lite, {"num_gens": 12, "horizon": 8,
                                        "num_scens": 3,
                                        "relax_integers": True})}[regime]
    b = ScenarioBatch.from_problems(
        [mod.scenario_creator(nm, **kw)
         for nm in mod.scenario_names_creator(3)])
    A = np.asarray(b.A_shared)
    assert sk.lowrank_kinv(A) == (regime == "lowrank")
    if regime in ("sparse", "structured"):
        A = SparseA.from_dense(A, structure=regime == "structured",
                               min_blocks=2)
        assert (A.structure is not None) == (regime == "structured")
    st = ADMMSettings(max_iter=8, restarts=2, factors_keep_K=keep_K)
    _, f = jax.eval_shape(
        functools.partial(shared_admm.solve_shared_factored._jitted,
                          settings=st), b.c, b.q2, A, b.cl, b.cu, b.lb, b.ub)
    n = b.num_vars
    assert (f.K is not None) == (keep_K and regime == "dense")
    assert sk.is_dense_kinv(f.Kinv) == (regime in ("dense", "sparse"))
    assert isinstance(f.Kinv, sk.DiagLowRank) == (regime == "lowrank")
    assert isinstance(f.Kinv, sk.BlockWoodbury) == (regime == "structured")
    assert n * n not in [int(np.prod(leaf.shape)) for leaf in
                         jax.tree.leaves(f)] or regime in ("dense", "sparse")
