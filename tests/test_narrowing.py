"""The dense engine's sweep loop narrows to the rows that are not done
(``admm._admm_core``): the same loop again at one narrower static width
where the batch is wider than one block, the parent's one loop where it is
not.  XLA's sweep, float64; a block is 128 rows here (no kernel on the CPU).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpusppy.ir import ScenarioBatch
from tpusppy.models import farmer
from tpusppy.parallel import sharded
from tpusppy.solvers import admm
from tpusppy.solvers.admm import ADMMSettings

ST = ADMMSettings()


def make_batch(S):
    return ScenarioBatch.from_problems(
        [farmer.scenario_creator(nm, num_scens=S)
         for nm in farmer.scenario_names_creator(S)])


def _ph_problem(b, xbar, W, rho=1.0):
    idx = np.asarray(b.tree.nonant_indices)
    q, q2 = np.array(b.c), np.array(b.q2)
    q[:, idx] += W - rho * xbar
    q2[:, idx] += rho
    return (q, q2, b.A, b.cl, b.cu, b.lb, b.ub)


def _wheel(S, st=ST):
    """A batch of S two steps into a PH run: the LP from nothing, then the
    prox problem's refresh from it (a warm adaptive solve: it narrows), the
    factors of that refresh and the next step's problem and warm start."""
    b = make_batch(S)
    idx = np.asarray(b.tree.nonant_indices)
    sol = admm.solve_batch(b.c, b.q2, b.A, b.cl, b.cu, b.lb, b.ub,
                           settings=st)
    x = np.asarray(sol.x)[:, idx]
    xbar = b.probs @ x
    W = x - xbar
    first = _ph_problem(b, xbar, W)
    refresh, factors = admm.solve_batch_factored(*first, settings=st,
                                                 warm=sol.raw)
    x = np.asarray(refresh.x)[:, idx]
    xbar = b.probs @ x
    W = W + x - xbar
    return {"batch": b, "cold": sol, "first": first, "first_warm": sol.raw,
            "refresh": refresh, "factors": factors,
            "problem": _ph_problem(b, xbar, W), "warm": refresh.raw}


@pytest.fixture(scope="module")
def wheel300():
    """Three blocks: one rung, of 128."""
    return _wheel(300)


@pytest.fixture(scope="module")
def wheel520():
    """Five blocks: the rung is one block still."""
    return _wheel(520)


def _scaled(wheel, st=ST):
    """The frozen solve's own preparation (``_solve_frozen_impl``): the
    scaled problem, the start state and what ``_admm_core`` takes."""
    f = wheel["factors"]
    c, q2, A, cl, cu, lb, ub, _, _ = admm._prep(
        *wheel["problem"], st, None, want_masks=False)
    qs, q2s, As, cls, cus, lbs, ubs, _, (x0, z0, y0, yx0) = admm._scale(
        c, q2, A, cl, cu, lb, ub, f.D, f.E, f.cost, None, wheel["warm"],
        st.jdtype())
    state0 = admm._start_state(x0, z0, jnp.clip(x0, lbs, ubs), y0, yx0)
    return (qs, q2s, As, cls, cus, lbs, ubs), state0, (
        (f.Kinv, f.K), f.rho_a, f.rho_x)


def _done(state, st=ST):
    return np.asarray(admm._done_mask(state.pri, state.dua, state.prinorm,
                                      state.duanorm, st))


def test_rung_width():
    assert admm._rung_width(1000, 128) == 256
    assert admm._rung_width(1000, None) == 256
    assert admm._rung_width(640, 128) == 128
    assert admm._rung_width(300, None) == 128
    assert admm._rung_width(256, 128) == 128
    assert admm._rung_width(2000, 256) == 512
    # one block or less, whoever sized it: the parent's loop
    for S, bs in ((3, None), (64, None), (128, None), (128, 128),
                  (1000, 1000), (100, 100)):
        assert admm._rung_width(S, bs) == 0, (S, bs)
    # whole blocks, one at least, at most half the (whole-block) width
    # above and at most a quarter of it once that is a block
    for S in (129, 257, 300, 777, 1000, 4097):
        width, above = admm._rung_width(S, 128), -(-S // 128) * 128
        assert width >= 128 and width % 128 == 0
        assert 2 * width <= above and (width == 128 or 4 * width <= above)


def test_gathered_rows_match_a_batch_of_their_own(wheel300):
    """The rows a rung gathers end where the same rows end solved alone
    from the same state; a row left behind was settled (done for
    ``_LINGER`` sweeps running) and keeps, bit for bit, the iterate and the
    residuals of the checkpoint that released it."""
    data, state0, (LK, rho_a, rho_x) = _scaled(wheel300)
    core = jax.jit(lambda s: admm._admm_core(*data, s, LK, rho_a, rho_x, ST))
    out = core(state0)
    # the checkpoint that released them: the full-width loop on its own
    at = jax.jit(lambda s: admm._sweep_loop(
        *data, s, LK, rho_a, rho_x, ST, leave_at=128))(state0)
    k_at = int(at.k)
    settled = np.asarray(admm._settled(at))
    assert admm._LINGER < k_at < int(out.k) <= ST.max_iter
    assert 0 < 300 - settled.sum() <= 128
    idx = np.argsort(settled, kind="stable")[:128]
    left = np.setdiff1d(np.arange(300), idx)
    assert settled[left].all() and _done(at)[left].all()
    # some row passes the test there and is gathered all the same: it has
    # not passed for long enough
    assert (_done(at) & ~settled).any()
    for f in ("x", "z", "zx", "y", "yx", "pri", "dua", "prinorm", "duanorm"):
        np.testing.assert_array_equal(np.asarray(getattr(out, f))[left],
                                      np.asarray(getattr(at, f))[left], f)
    # the 128 alone, from that state, against the same budget
    take = lambda a: a[idx]
    sub = at._replace(best=jnp.full_like(at.best, jnp.inf),
                      **{f: getattr(at, f)[idx] for f in admm._ROW_FIELDS})
    alone = jax.jit(lambda s: admm._sweep_loop(
        *jax.tree.map(take, data), s, jax.tree.map(take, LK),
        take(rho_a), take(rho_x), ST))(sub)
    assert int(alone.k) == int(out.k)
    for f in ("x", "z", "zx", "y", "yx", "pri", "dua"):
        np.testing.assert_allclose(np.asarray(getattr(out, f))[idx],
                                   np.asarray(getattr(alone, f)),
                                   rtol=1e-12, atol=1e-14, err_msg=f)
    # and the counters: who was swept how long
    swept = np.asarray(out.swept)
    assert (swept[left] == k_at).all() and (swept[idx] == int(out.k)).all()
    assert int(out.narrow) == int(out.k) - k_at


def test_a_row_is_left_out_only_after_it_has_stayed_done(wheel300):
    """``since`` counts the sweeps a row has now passed the test for,
    checkpoint after checkpoint; the sweep loop keeps it only under the
    cascade."""
    data, state0, (LK, rho_a, rho_x) = _scaled(wheel300)
    st = dataclasses.replace(ST, max_iter=200)
    ck = ST.check_every
    tracked = jax.jit(lambda s: admm._sweep_loop(
        *data, s, LK, rho_a, rho_x, st, leave_at=0))(state0)
    since, done = np.asarray(tracked.since), _done(tracked, st)
    assert (since[~done] == 0).all() and (since[done] >= ck).all()
    assert since.max() <= 200 and (since % ck == 0).all()
    assert done.any() and not done.all()
    plain = jax.jit(lambda s: admm._sweep_loop(
        *data, s, LK, rho_a, rho_x, st))(state0)
    assert (np.asarray(plain.since) == 0).all()
    np.testing.assert_array_equal(np.asarray(plain.x), np.asarray(tracked.x))
    # no rung before a row has been done for _LINGER sweeps: a budget
    # shorter than that never narrows
    out = jax.jit(lambda s: admm._admm_core(
        *data, s, LK, rho_a, rho_x, st))(state0)
    assert int(out.k) == 200 <= admm._LINGER and int(out.narrow) == 0


@pytest.mark.parametrize("S", [300, 520])
def test_counters_are_consistent(S, request):
    """``done``, ``iters``, the residuals and the two counters of a solve
    that narrowed (a warm adaptive solve, every restart its own cascade),
    and of the solve from nothing before it."""
    wheel = request.getfixturevalue(f"wheel{S}")
    b, sol = wheel["batch"], wheel["refresh"]
    iters, narrow = int(sol.iters[0]), int(sol.narrow[0])
    swept = np.asarray(sol.swept)
    done = np.asarray(sol.done)
    assert (np.asarray(sol.iters) == iters).all()
    assert (np.asarray(sol.narrow) == narrow).all()
    assert 0 < narrow < iters
    # nobody is swept longer than the loop ran, nor shorter than its
    # full-width part; a row still not done was in every sweep
    assert swept.max() == iters and swept.min() >= iters - narrow
    assert (swept[~done] == iters).all()
    meas = admm.measure_unpack(np.asarray(admm.measure_pack(sol)), S,
                               b.num_vars)
    assert meas["narrow_sweeps"] == narrow
    assert meas["row_sweeps"] == swept.sum() < meas["full_row_sweeps"]
    assert meas["full_row_sweeps"] == iters * S
    assert meas["n_done"] == done.sum()
    # the answers are the all-or-nothing loop's, to the solver's tolerance
    wide = _without_rungs(lambda: admm._solve_impl(
        *wheel["first"], ST, wheel["first_warm"]))
    assert int(wide.narrow[0]) == 0
    assert (np.asarray(wide.swept) == int(wide.iters[0])).all()
    assert done.sum() >= np.asarray(wide.done).sum() - 2
    q, q2 = wheel["first"][:2]
    obj = lambda s: np.einsum("sn,sn->s", q + 0.5 * q2 * np.asarray(s.x),
                              np.asarray(s.x))
    np.testing.assert_allclose(obj(sol), obj(wide), rtol=1e-6)
    # a solve from nothing goes through the same cascade
    cold = wheel["cold"]
    assert np.asarray(cold.done).any()
    swept = np.asarray(cold.swept)
    assert swept.max() == int(cold.iters[0])
    assert swept.min() >= int(cold.iters[0]) - int(cold.narrow[0])


def _without_rungs(fn):
    """``fn`` traced with no narrower rung: the parent's one loop."""
    width = admm._rung_width
    admm._rung_width = lambda S, bs: 0
    try:
        with jax.default_matmul_precision(ST.matmul_precision):
            return jax.jit(fn)()
    finally:
        admm._rung_width = width


def test_rows_that_all_park_never_narrow(wheel300):
    """A budget too short for any row: no rung is entered, every sweep ran
    at full width."""
    st = dataclasses.replace(ST, max_iter=40, restarts=2, polish=False)
    b = wheel300["batch"]
    sol = admm.solve_batch(*wheel300["problem"], settings=st,
                           warm=tuple(0 * w for w in wheel300["warm"]))
    assert not np.asarray(sol.done).any()
    assert int(sol.iters[0]) == 80 and int(sol.narrow[0]) == 0
    assert (np.asarray(sol.swept) == 80).all()
    meas = admm.measure_unpack(np.asarray(admm.measure_pack(sol)), 300,
                               b.num_vars)
    assert meas["row_sweeps"] == meas["full_row_sweeps"] == 80 * 300
    assert meas["narrow_sweeps"] == 0


def test_a_plateau_exit_leaves_no_row_half_swept(wheel300):
    """The in-loop plateau exit (``sweep_plateau_rtol``) ends a core run
    with more rows not done than a rung holds: no rung picks some of them
    and sweeps on.  Every row not done was in every sweep."""
    st = dataclasses.replace(ST, sweep_plateau_rtol=0.5,
                             sweep_plateau_window=8, polish=False)
    sol = admm.solve_batch(*wheel300["problem"], settings=st,
                           warm=tuple(0 * w for w in wheel300["warm"]))
    iters, done = int(sol.iters[0]), np.asarray(sol.done)
    assert iters < st.restarts * st.max_iter and not done.all()
    assert (np.asarray(sol.swept)[~done] == iters).all()


def _count(jaxpr, names):
    """How many equations of the named primitives a jaxpr holds, the
    jaxprs inside its equations included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name in names
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _count(inner, names)
    return n


def _core_jaxpr(S, n=5, m=3):
    dt = jnp.float64
    z = lambda *shape: jnp.zeros(shape, dt)
    state = admm._start_state(z(S, n), z(S, m), z(S, n), z(S, m), z(S, n))
    return jax.make_jaxpr(lambda s: admm._admm_core(
        z(S, n), z(S, n), z(S, m, n), z(S, m), z(S, m), z(S, n), z(S, n),
        s, (z(S, n, n), z(S, n, n)), jnp.ones((S, m), dt),
        jnp.ones((S, n), dt), ST))(state).jaxpr


@pytest.mark.parametrize("S, rungs", [(3, 0), (48, 0), (128, 0), (129, 1),
                                      (300, 1), (520, 1), (1000, 1)])
def test_one_while_loop_a_rung(S, rungs):
    """At one block or less the traced sweep loop is the parent's: one
    ``while``, no gather, no sort, no branch.  Wider: one more ``while``
    for the rung, behind its ``cond``."""
    jaxpr = _core_jaxpr(S)
    assert _count(jaxpr, {"while"}) == 1 + rungs
    assert _count(jaxpr, {"cond"}) == rungs
    assert (_count(jaxpr, {"gather", "scatter", "scatter-add", "sort"})
            > 0) == (rungs > 0)


@pytest.fixture(scope="module")
def mega300():
    """Three megastep iterations on a batch of 300 after its refresh."""
    settings = ADMMSettings()
    batch = make_batch(300)
    mesh = sharded.make_mesh(1)
    arr = sharded.shard_batch(batch, mesh)
    idx = batch.tree.nonant_indices
    refresh, _ = sharded.make_ph_step_pair(idx, settings, mesh)
    state = sharded.init_state(arr, 1.0, settings)
    state, _, _ = refresh(state, arr, 0.0)
    state, _, factors = refresh(state, arr, 1.0)
    mega = sharded.make_wheel_megastep(idx, settings, mesh, n_iters=3,
                                       donate=False, pack="lean")
    _, packed = mega(state, arr, 1.0, factors, -1.0, 3, np.inf)
    return sharded.megastep_unpack(np.asarray(packed), 3, 300,
                                   batch.num_vars, len(idx), pack="lean")


def test_megastep_pack_carries_the_counters(mega300):
    meas = mega300
    assert meas["executed"] == 3
    sweeps = meas["iters"]
    np.testing.assert_array_equal(meas["full_row_sweeps"], sweeps * 300)
    assert (meas["narrow_sweeps"] <= sweeps).all()
    assert (meas["row_sweeps"] <= meas["full_row_sweeps"]).all()
    assert (meas["row_sweeps"]
            >= meas["full_row_sweeps"] - meas["narrow_sweeps"] * 172).all()
    # this batch narrows: some iteration ran sweeps at one block
    assert meas["narrow_sweeps"].sum() > 0
    assert meas["row_sweeps"].sum() < meas["full_row_sweeps"].sum()


def _numbered(length):
    return np.arange(1.0, length + 1.0)


def test_the_three_unpackers_name_the_same_rows():
    """``megastep_unpack`` and its bucketed and tenant twins read the
    per-iteration stats block by one list of names, the counters last."""
    N, S, n, K = 4, 5, 3, 2
    rows = sharded._STATS_ROWS
    assert rows[-3:] == ("narrow_sweeps", "row_sweeps", "full_row_sweeps")
    assert sharded.MEGA_STATS == len(rows) == 9
    vec = _numbered(sharded.megastep_measure_len(N, S, n, K))
    solo = sharded.megastep_unpack(vec, N, S, n, K)
    shapes = [(2, 3), (3, 2)]
    bvec = _numbered(sharded.bucketed_megastep_measure_len(N, shapes, K))
    bucketed = sharded.bucketed_megastep_unpack(bvec, N, shapes, K)
    tvec = _numbered(sharded.tenant_megastep_measure_len(N, S, 2))
    tenant = sharded.tenant_megastep_unpack(tvec, N, S, 2)
    block = np.arange(1.0, 9 * N + 1.0).reshape(9, N)
    for i, name in enumerate(rows):
        want = block[i] != 0.0 if name == "all_done" else block[i]
        np.testing.assert_array_equal(solo[name], want)
        np.testing.assert_array_equal(bucketed[name], want)
        np.testing.assert_array_equal(tenant[name][0], want)
        assert len(tenant[name]) == 2
    assert solo["executed"] == bucketed["executed"] == 9 * N + 1
    assert tenant["executed"] == [9 * N + 1, 2 * (9 * N) + 2 + 3 * S + 1]


def test_megastep_outcome_records_the_widths(mega300):
    """``SPOpt._megastep_outcome`` sums the executed iterations' counters
    into ``solve.<cylinder>.mega.*``."""
    from tpusppy.obs import metrics
    from tpusppy.spopt import SPOpt

    class Opt:
        admm_settings = ADMMSettings()

    meas = dict(mega300, refresh_hit=False)
    SPOpt._megastep_outcome(Opt(), meas, 3)
    got = {k.split(".")[-1]: v for k, v in metrics.dump().items()
           if k.startswith("solve.") and ".mega." in k}
    for field in ("narrow_sweeps", "row_sweeps", "full_row_sweeps"):
        assert got[field] == meas[field].sum()
    assert got["sweeps"] * 300 == got["full_row_sweeps"]
