"""sslp against the plain reference ``benchmarks/references/two_stage_mip``.

The deployment ``sslp_10_50_2000`` (integer in both stages, one ``A`` for
all scenarios found by value, the shared-A engine) held, small and on the
CPU, to the numpy/HiGHS reference that decides ``correct`` on the chip: the
hub's arithmetic (Iter0, xbar, W, the prox subproblem), the incumbent that
XhatShuffle keeps, the certificates the Lagrangian spoke keeps, and the
shared engine against the dense one on the same batch.  Two sizes (5 x 25
with S=5, whose integer extensive form HiGHS solves in a second, and the
cell's 10 x 50 with S=8) and two precisions (float64, and the cell's float32
recipe).  The reference makes its data by calling the model's creator
itself: it never sees the batch the program ingested.
"""

import dataclasses
import math

import numpy as np
import pytest

from benchmarks.references.two_stage_mip import Reference
from tpusppy.cylinders.lagrangian_bounder import LagrangianOuterBound
from tpusppy.cylinders.xhatshufflelooper_bounder import XhatShuffleInnerBound
from tpusppy.ir import ScenarioBatch
from tpusppy.models import sslp
from tpusppy.obs import metrics
from tpusppy.opt.ph import PH
from tpusppy.spin_the_wheel import WheelSpinner
from tpusppy.utils import cfg_vanilla as vanilla
from tpusppy.utils import config

SIZES = {"5x25_S5": (5, 25, 5), "10x50_S8": (10, 50, 8)}
RECIPES = {
    "float64": {"dtype": "float64", "eps_abs": 1e-8, "eps_rel": 1e-8},
    # the cell's recipe (benchmarks/configs/sslp_10_50_2000.json)
    "float32": {"dtype": "float32", "eps_abs": 1e-5, "eps_rel": 1e-5},
}
# What each precision is held to.  The shared engine has no active-set
# polish, so a row that the host rescue did not re-solve stands at the
# sweep's tolerance: eps 1e-8 / 1e-5 on scaled residuals, which the
# objective sees a few hundred times larger on these LPs (costs up to 80,
# a penalty of 1000 on the overflow columns).
TOL = {
    "float64": dict(iter0=1e-6, xbar=1e-12, w=1e-12, prox=1e-4, feas=1e-6,
                    price=1e-9, shared=5e-4, spread=1e-9),
    # spread: a clamped column comes back as the solver's x, equal to its
    # bound to single precision and not bit for bit
    "float32": dict(iter0=2e-3, xbar=1e-6, w=1e-6, prox=1e-1, feas=1e-3,
                    price=1e-6, shared=1e-1, spread=1e-5),
}
CASES = [(size, prec) for size in SIZES for prec in RECIPES]


def kwargs_of(size):
    ns, nc, _S = SIZES[size]
    return {"num_servers": ns, "num_clients": nc, "seedoffset": 11,
            "relax_integers": False}


def rel(a, b):
    return abs(float(a) - float(b)) / max(1.0, abs(float(b)))


@pytest.fixture(scope="module")
def reference():
    made = {}

    def get(size):
        if size not in made:
            names = sslp.scenario_names_creator(SIZES[size][2])
            made[size] = Reference(sslp, names, kwargs_of(size))
        return made[size]

    return get


@pytest.fixture(scope="module")
def stepped():
    """A PH object after Iter0 and one legacy iteration, with the state
    before and after the step copied off it."""
    made = {}

    def get(size, prec):
        if (size, prec) not in made:
            names = sslp.scenario_names_creator(SIZES[size][2])
            ph = PH({"defaultPHrho": 1.0, "PHIterLimit": 2,
                     "convthresh": -1.0, "solver_options": RECIPES[prec]},
                    names, sslp.scenario_creator,
                    scenario_creator_kwargs=kwargs_of(size))
            ph.Iter0()
            out = {"ph": ph, "x0": np.array(ph.local_x, float),
                   "W0": np.array(ph.W, float),
                   "xbars0": np.array(ph.xbars, float)}
            ph._iterk_one(1, -1.0)
            out.update(x1=np.array(ph.local_x, float),
                       W1=np.array(ph.W, float),
                       xbars1=np.array(ph.xbars, float),
                       rho=np.array(ph.rho, float))
            made[size, prec] = out
        return made[size, prec]

    return get


def spin(size, prec, iterations, lift=None):
    """PH hub + Lagrangian + XhatShuffle through ``WheelSpinner``, built
    through ``cfg_vanilla`` as ``examples/sslp/sslp_cylinders.py`` and the
    benchmark's driver build it, every integer option at its default."""
    S = SIZES[size][2]
    cfg = config.Config()
    cfg.num_scens_required()
    cfg.popular_args()
    cfg.two_sided_args()
    cfg.ph_args()
    cfg.lagrangian_args()
    cfg.xhatshuffle_args()
    cfg.parse_command_line("test_sslp_reference", args=[
        "--num-scens", str(S), "--max-iterations", str(iterations),
        "--default-rho", "1.0", "--rel-gap", "0.001", "--solver-options",
        " ".join(f"{k}={v}" for k, v in RECIPES[prec].items()),
        "--lagrangian", "--xhatshuffle"])
    beans = dict(cfg=cfg, scenario_creator=sslp.scenario_creator,
                 scenario_denouement=sslp.scenario_denouement,
                 all_scenario_names=sslp.scenario_names_creator(S),
                 scenario_creator_kwargs=sslp.kw_creator(
                     cfg, **kwargs_of(size)))
    hub_dict = vanilla.ph_hub(**beans)
    hub_dict["opt_kwargs"]["options"]["convthresh"] = -1.0
    spokes = [vanilla.lagrangian_spoke(**beans),
              vanilla.xhatshuffle_spoke(**beans)]
    if lift is not None:
        spokes[0]["opt_kwargs"]["options"]["lagrangian_milp_lift"] = lift
    ws = WheelSpinner(hub_dict, spokes)
    ws.spin()
    return ws


@pytest.fixture(scope="module")
def wheel():
    made = {}

    def get(size, prec):
        if (size, prec) not in made:
            ws = spin(size, prec, iterations=12)
            made[size, prec] = (ws, dict(metrics.dump()))
        return made[size, prec]

    return get


def spoke_of(ws, cls):
    return next(c for c in ws.spoke_comms if isinstance(c, cls))


# -- the hub's arithmetic -----------------------------------------------------
@pytest.mark.parametrize("size,prec", CASES)
def test_every_iter0_row_against_the_scenario_optimum(size, prec, reference,
                                                      stepped):
    ref, st = reference(size), stepped(size, prec)
    assert st["ph"].batch.A_shared is not None        # the shared engine
    got = ref.objective(st["x0"])
    gaps = [rel(got[s], ref.scenario_opt(s)) for s in range(ref.S)]
    assert max(gaps) <= TOL[prec]["iter0"], gaps


@pytest.mark.parametrize("size,prec", CASES)
def test_xbar_and_w_after_a_step(size, prec, reference, stepped):
    ref, st = reference(size), stepped(size, prec)
    want = ref.xbar_of(st["x1"])
    assert np.abs(st["xbars1"] - want[None, :]).max() \
        <= TOL[prec]["xbar"] * max(1.0, np.abs(want).max())
    want_w = ref.w_after(st["W0"], st["rho"], st["x1"][:, ref.nonant],
                         st["xbars1"])
    assert np.abs(st["W1"] - want_w).max() \
        <= TOL[prec]["w"] * max(1.0, np.abs(want_w).max())
    # PH keeps the probability-weighted mean of W at 0
    assert np.abs(ref.probs @ st["W1"]).max() \
        <= 10 * TOL[prec]["w"] * max(1.0, np.abs(st["W1"]).max())


@pytest.mark.parametrize("size,prec", CASES)
def test_prox_gap_of_the_step(size, prec, reference, stepped):
    """Every scenario's subproblem solve of the step against the reference:
    the Frank-Wolfe gap of the PH subproblem, by one LP a scenario."""
    ref, st = reference(size), stepped(size, prec)
    gaps = [abs(ref.prox_gap(s, st["x1"][s], st["W0"][s], st["xbars0"][s],
                             st["rho"][s])) for s in range(ref.S)]
    assert max(gaps) <= TOL[prec]["prox"], gaps


# -- the incumbent and the bounds ----------------------------------------------
@pytest.mark.parametrize("size,prec", CASES)
def test_incumbent_that_xhatshuffle_keeps(size, prec, reference, wheel):
    """Feasible, integral on every integer column of both stages, one first
    stage for all scenarios, and priced at the inner bound the hub holds."""
    ref, (ws, counters) = reference(size), wheel(size, prec)
    bound, inc = spoke_of(ws, XhatShuffleInnerBound).best_snapshot()
    assert inc is not None and math.isfinite(bound)
    inc = np.asarray(inc, float)
    assert max(ref.infeasibility(s, inc[s]) for s in range(ref.S)) \
        <= TOL[prec]["feas"]
    cols = inc[:, ref.is_int]
    assert np.abs(cols - np.round(cols)).max() <= 1e-5
    na = inc[:, ref.nonant]
    assert (na.max(axis=0) - na.min(axis=0)).max() <= TOL[prec]["spread"]
    assert rel(ws.spcomm.BestInnerBound,
               ref.probs @ ref.objective(inc)) <= TOL[prec]["price"]
    assert ws.spcomm.BestInnerBound == pytest.approx(bound)
    # the tiers that made it: every candidate was a dive, and the counters
    # say how far it had to go
    dives = sum(v for k, v in counters.items()
                if k.startswith("phase.") and k.endswith(".dive.count"))
    assert dives >= 1
    assert counters["xhat.dive_rounds"] >= dives
    assert "xhat.dive_wedged_rows" in counters
    assert counters.get("xhat.retry_rows", 0) \
        >= counters["xhat.dive_wedged_rows"]
    assert counters["ingest.a_shared_by_value"] == 3     # three cylinders


@pytest.mark.parametrize("size,prec", CASES)
def test_outer_never_passes_inner(size, prec, wheel):
    ws, _ = wheel(size, prec)
    hub = ws.spcomm
    assert math.isfinite(hub.BestOuterBound)
    assert hub.BestOuterBound <= hub.BestInnerBound + 1e-6 * max(
        1.0, abs(hub.BestInnerBound))


@pytest.mark.parametrize("prec", sorted(RECIPES))
def test_bounds_bracket_the_integer_extensive_form(prec, reference, wheel):
    """5 x 25, S=5: ``outer <= ef_int bound`` and ``ef_int price <= inner``."""
    ref, (ws, _) = reference("5x25_S5"), wheel("5x25_S5", prec)
    price, x, bound = ref.ef_int()
    assert bound <= price + 1e-6 * max(1.0, abs(price))
    hub = ws.spcomm
    slack = TOL[prec]["price"] * max(1.0, abs(price))
    assert hub.BestOuterBound <= bound + slack
    assert price <= hub.BestInnerBound + slack


def held_to(ref, d, W, minimum, slack):
    """Every certificate against the minimum of its scenario's own program
    at the W it was computed at."""
    worst = -math.inf
    for s in range(ref.S):
        cost = np.array(ref.c[s], float)
        cost[ref.nonant] += W[s]
        worst = max(worst, (d[s] - ref.const[s]) - minimum(s, cost))
    return worst <= slack, worst


@pytest.mark.parametrize("size,prec", CASES)
def test_outer_certificates_are_readable_after_teardown(size, prec,
                                                        reference, wheel):
    """The Lagrangian spoke keeps, beside its best bound, the per-scenario
    certificates and the W behind it: ``d_s <= lin_min(s, c + W_s)`` (with
    every option at its default the certificates are LP ones)."""
    ref, (ws, _) = reference(size), wheel(size, prec)
    bound, d, W = spoke_of(ws, LagrangianOuterBound).best_certificates()
    assert d.shape == (ref.S,) and W.shape == (ref.S, ref.nonant.size)
    assert bound == pytest.approx(float(ref.probs @ d), rel=1e-12)
    # nothing the hub holds is better than what the spoke kept (the hub's
    # own trivial bound is the same certificate at W = 0)
    assert ws.spcomm.BestOuterBound <= bound + 1e-9 * max(1.0, abs(bound))
    # weak duality absorbs the solver's tolerance: the slack is rounding
    ok, worst = held_to(ref, d, W, ref.lin_min,
                        1e-7 if prec == "float64" else 1e-3)
    assert ok, worst


def test_lifted_certificates_are_held_to_the_integer_minimum(reference):
    """Under ``lagrangian_milp_lift`` a certificate is a HiGHS MILP dual
    bound: ``d_s <= int_min(s, c + W_s)``, and some pass the LP minimum."""
    ref = reference("5x25_S5")
    ws = spin("5x25_S5", "float64", iterations=6,
              lift={"budget_s": 30.0, "every": 1})
    bound, d, W = spoke_of(ws, LagrangianOuterBound).best_certificates()
    ok, worst = held_to(ref, d, W, ref.int_min, 1e-6)
    assert ok, worst
    assert bound == pytest.approx(float(ref.probs @ d), rel=1e-12)
    _ok, over_lp = held_to(ref, d, W, ref.lin_min, 0.0)
    assert over_lp >= -1e-7          # at least as tight as the LP minimum
    price, _x, ef_bound = ref.ef_int()
    assert bound <= price + 1e-6 * max(1.0, abs(price))


# -- the shared engine against the dense one ------------------------------------
@pytest.mark.parametrize("size,prec", CASES)
def test_shared_engine_against_the_dense_one_on_one_batch(size, prec):
    """One batch, built once with ``A_shared`` (as the rule finds it) and
    once with it set to None: the two engines' answers to the same plain
    solve, with no host rescue behind it, agree to what the solver reaches
    inside its budget.  (CPU, PR 32, against HiGHS: in float64 the shared
    engine ends at 6e-7 or better and the dense one spends its 4000 sweeps
    at 10 x 50 and stands 1.1e-4 off; in float32 both spend the budget at
    a primal residual near 2e-3 and stand 4-6% off in objective, which is
    why the wheel's Iter0 hands its worst rows to the host.)"""
    from tpusppy.solvers.admm import ADMMSettings
    from tpusppy.spopt import batch_solve_dispatch

    names = sslp.scenario_names_creator(SIZES[size][2])
    shared = ScenarioBatch.from_problems(
        [sslp.scenario_creator(nm, **kwargs_of(size)) for nm in names])
    assert shared.A_shared is not None
    dense = dataclasses.replace(shared, A=np.array(shared.A), A_shared=None)
    st = ADMMSettings(**RECIPES[prec])
    sols = [batch_solve_dispatch(b, b.c, b.q2, b.cl, b.cu, b.lb, b.ub,
                                 settings=st) for b in (shared, dense)]
    obj = [shared.objective(np.asarray(s.x, float)) for s in sols]
    gap = np.abs(obj[0] - obj[1]) / np.maximum(1.0, np.abs(obj[1]))
    assert gap.max() <= TOL[prec]["shared"], gap
    for s in sols:
        assert np.asarray(s.pri_res).max() <= 10 * TOL[prec]["feas"]
