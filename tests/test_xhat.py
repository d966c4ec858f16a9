"""Xhat machinery: fix-and-evaluate, in-hub incumbent finders, slam caches.

Mirrors the reference's xhat patterns (utils/xhat_eval.py, extensions/xhatbase
family): every inner bound must be >= the EF optimum for minimization, and
evaluating the EF solution itself must reproduce the EF objective.
"""

import numpy as np
import pytest

from tpusppy.ef import solve_ef
from tpusppy.extensions.xhatbase import XhatBase, donor_cache, slam_cache
from tpusppy.extensions.xhatlooper import XhatLooper
from tpusppy.extensions.xhatxbar import XhatXbar
from tpusppy.models import farmer, hydro
from tpusppy.opt.ph import PH
from tpusppy.xhat_eval import Xhat_Eval

EF3 = -108390.0


def make_eval(num_scens=3, **opts):
    options = {"defaultPHrho": 1.0, "PHIterLimit": 1, **opts}
    return Xhat_Eval(
        options,
        farmer.scenario_names_creator(num_scens),
        farmer.scenario_creator,
        scenario_creator_kwargs={"num_scens": num_scens},
    )


class TestXhatEval:
    def test_ef_solution_reproduces_ef_objective(self):
        ev = make_eval(3)
        obj_ef, xs = solve_ef(ev.batch, solver="highs")
        cache = xs[:, ev.tree.nonant_indices]
        assert ev.evaluate(cache) == pytest.approx(obj_ef, rel=1e-4)

    def test_candidate_bounds_ef_from_above(self):
        ev = make_eval(3)
        # wait-and-see solutions of each scenario as candidates
        ev.solve_loop()
        xk = ev.nonants_of(ev.local_x)
        obj_ef, _ = solve_ef(ev.batch, solver="highs")
        for s in range(3):
            cache = donor_cache(ev, xk, s)
            z = ev.evaluate(cache)
            assert z >= obj_ef - 1.0

    def test_evaluate_one_matches_scenario_objective(self):
        ev = make_eval(3)
        ev.solve_loop()
        xk = ev.nonants_of(ev.local_x)
        cache = donor_cache(ev, xk, 1)
        vals = ev.objective_values(cache)
        z1 = ev.evaluate_one(cache, 1)
        assert z1 == pytest.approx(vals[1], abs=1e-6)

    def test_state_restored_after_eval(self):
        ev = make_eval(3)
        ev.solve_loop()
        assert ev._fixed_lb is None
        ev.evaluate(np.zeros(ev.nonant_length))
        assert ev._fixed_lb is None  # restore_nonants ran


    def test_candidates_are_counted_priced_or_refused(self):
        """Three candidates, one of which plants 1200 acres on 500:
        ``xhat.candidates`` is the refused ones plus the priced ones, and
        the refused one is refused by every row."""
        from tpusppy.obs import metrics

        ev = make_eval(3)
        cands = ([170.0, 80.0, 250.0], [400.0, 400.0, 400.0],
                 [100.0, 100.0, 300.0])
        with metrics.window() as win:
            prices = [ev.evaluate(np.array(c)) for c in cands]
        assert [np.isfinite(z) for z in prices] == [True, False, True]
        assert prices[0] == pytest.approx(EF3, rel=1e-4)
        d = win.deltas()
        assert d["xhat.candidates"] == 3
        assert d["xhat.infeasible"] == 1
        assert d["xhat.candidates"] == d["xhat.infeasible"] + sum(
            map(np.isfinite, prices))
        assert d["xhat.infeasible_rows"] == d["xhat.infeasible_of_rows"] == 3
        # a single scenario's price goes through no gate of the batch
        ev.evaluate_one(np.array(cands[1]), 0)
        assert metrics.value("xhat.candidates") == 3


class TestDonorCache:
    def test_two_stage_single_donor(self):
        ev = make_eval(3)
        ev.solve_loop()
        xk = ev.nonants_of(ev.local_x)
        cache = donor_cache(ev, xk, 2)
        assert np.allclose(cache, np.broadcast_to(xk[2], cache.shape))

    def test_multistage_nonanticipative(self):
        names = hydro.scenario_names_creator(9)
        probs = [hydro.scenario_creator(nm, branching_factors=[3, 3])
                 for nm in names]
        from tpusppy.ir import ScenarioBatch

        batch = ScenarioBatch.from_problems(probs)
        opts = {"defaultPHrho": 1.0, "PHIterLimit": 1}
        ev = Xhat_Eval(opts, names,
                       lambda nm, **kw: hydro.scenario_creator(nm, **kw),
                       scenario_creator_kwargs={"branching_factors": [3, 3]})
        ev.solve_loop()
        xk = ev.nonants_of(ev.local_x)
        cache = donor_cache(ev, xk, 0)
        # stage-1 slots identical everywhere; stage-2 identical within groups
        assert np.allclose(cache[:, :4], cache[0, :4])
        for g in range(3):
            grp = cache[3 * g:3 * g + 3, 4:]
            assert np.allclose(grp, grp[0])

    def test_dict_donors(self):
        ev = make_eval(3)
        ev.solve_loop()
        xk = ev.nonants_of(ev.local_x)
        cache = donor_cache(ev, xk, {"ROOT": 1})
        assert np.allclose(cache, np.broadcast_to(xk[1], cache.shape))


class TestSlam:
    def test_slam_max_min_bracket(self):
        ev = make_eval(3)
        ev.solve_loop()
        xk = ev.nonants_of(ev.local_x)
        cmax = slam_cache(ev, xk, "max")
        cmin = slam_cache(ev, xk, "min")
        assert np.all(cmax >= cmin - 1e-12)
        assert np.allclose(cmax, np.broadcast_to(xk.max(axis=0), cmax.shape))


class TestXhatExtensionsInPH:
    def _ph(self, ext, iters=20, **opts):
        options = {
            "defaultPHrho": 1.0,
            "PHIterLimit": iters,
            "convthresh": 1e-6,
            **opts,
        }
        return PH(
            options,
            farmer.scenario_names_creator(3),
            farmer.scenario_creator,
            scenario_creator_kwargs={"num_scens": 3},
            extensions=ext,
        )

    def test_xhatlooper_finds_inner_bound(self):
        ph = self._ph(XhatLooper, xhat_looper_options={"scen_limit": 3})
        ph.ph_main()
        assert ph.best_inner_bound < np.inf
        assert ph.best_inner_bound >= EF3 - 1.0
        assert ph.best_inner_bound == pytest.approx(EF3, rel=2e-2)

    def test_xhatxbar_near_optimal_after_convergence(self):
        ph = self._ph(XhatXbar, iters=60)
        ph.ph_main()
        assert ph.best_inner_bound == pytest.approx(EF3, rel=5e-3)

    def test_try_one_preserves_ph_state(self):
        ph = self._ph(XhatBase, iters=2)
        ph.Iter0()
        x_before = ph.local_x.copy()
        xb = XhatBase(ph)
        xk = ph.nonants_of(ph.local_x)
        xb._try_one(donor_cache(ph, xk, 0))
        assert np.array_equal(ph.local_x, x_before)
