"""Lagrangian outer-bound spoke.

TPU-native analogue of ``mpisppy/cylinders/lagrangian_bounder.py:5-95``: take
the hub's PH dual weights W, solve every scenario subproblem with W active and
the prox term OFF, and report the weighted sum of subproblem objectives — a
valid lower (outer) bound for minimization since PH keeps the probability-
weighted W summing to zero per node.  One batched ADMM call per fresh W.
"""

from __future__ import annotations

import numpy as np

from .. import global_toc
from ..obs import metrics as _metrics
from .spoke import OuterBoundWSpoke


def in_wheel_outer_bound(opt) -> float:
    """The Lagrangian outer bound computed from ``opt``'s CURRENT state —
    no fresh batched solve: the W-augmented (W on, prox off) objective
    evaluated through the weak-duality assembly with the warm state's row
    duals.  This is EXACTLY what the in-wheel bound pass fuses into the
    megastep window (``parallel.sharded._bound_pass_terms``), exposed
    host-side so parity tests and spoke-less callers share one
    definition.  Any duals certify (weak duality); the carried duals of a
    near-converged wheel are tight, which is why a self-certifying wheel
    needs no spoke device program (doc/pipeline.md "In-wheel
    certification").

    The device-resident posture syncs the host mirrors first (one billed
    boundary fetch); requires a prior solve (warm duals must exist).
    """
    if getattr(opt, "_host_state_stale", False):
        opt._sync_host_state()
    b = opt.batch
    idx = opt.tree.nonant_indices
    q = np.array(b.c, copy=True)
    q[:, idx] += np.asarray(opt.W, dtype=float)
    return opt.Edualbound(q=q, q2=b.q2)


class LagrangianOuterBound(OuterBoundWSpoke):
    """'L' spoke: Lagrangian dual bound from hub Ws
    (lagrangian_bounder.py:5-95)."""

    converger_spoke_char = 'L'

    def lagrangian_prep(self):
        """The reference's PH_Prep(attach_prox=False) + _reenable_W
        (lagrangian_bounder.py:9-17): our opt object needs no model surgery —
        just force the W-on/prox-off objective mode."""
        self.opt.W_on = True
        self.opt.prox_on = False

    def lagrangian(self) -> float:
        """Solve the W-augmented batch and return the dual bound
        (lagrangian_bounder.py:19-56): E[obj + W·x_nonant].  The
        per-scenario certificates behind it stay in ``last_certificates``;
        :meth:`_put` keeps them with their W when the bound is the best so
        far (:meth:`OuterBoundWSpoke.best_certificates`).

        The objective comes from the opt object's own ``_augmented_q`` (with
        W on, prox off per ``lagrangian_prep``) so the assembly stays single-
        sourced with PH.

        With ``lagrangian_milp_lift`` in the opt options (a dict of
        :func:`tpusppy.solvers.milp_bound.milp_lift` kwargs plus ``every``),
        per-scenario LP certificates are lifted to host MILP dual bounds on
        integer families — the reference spoke's integer subproblem minima
        (its persistent solver is a MIP solver), which close the integrality
        gap a pure LP-relaxation bound cannot.  The lift is budget-elastic
        and valid at ANY completed subset of scenarios.
        """
        certs = self._certificates()
        self.last_certificates = certs
        return float(self.opt.probs @ certs)

    def _put(self, bound):
        """Report ``bound`` to the hub, keeping the certificates of the
        pass that made it if it is the best so far."""
        self.keep_if_best(bound, self.last_certificates, self.opt.W)
        self.bound = bound

    def _certificates(self) -> np.ndarray:
        """(S,) certified per-scenario bounds at the opt object's W."""
        opt = self.opt
        q, q2 = opt._augmented_q()
        donor_cfg = opt.options.get("lagrangian_dual_donors")
        # full scale (lagrangian_skip_solve): the batched S-solve exists
        # only to produce ADMM duals, which plateau orders-of-magnitude
        # loose at reference scale AND starve the chip for the hub/eval
        # cylinders (the r5 run-4 trace: the spoke's first pass never
        # finished inside a 3000s wheel).  Donor transfer needs no solve —
        # bound from k host-exact donor duals alone.
        skip_solve = bool(opt.options.get("lagrangian_skip_solve")
                          and donor_cfg)
        if opt.options.get("lagrangian_skip_solve") and not donor_cfg:
            # the knob reads as armed but is NOT: skipping the solve is
            # only sound when donor duals supply the bound, so without
            # ``lagrangian_dual_donors`` this silently downgraded to the
            # full batched solve the caller believed they had skipped —
            # say so loudly once, and record the decline
            _metrics.inc("lagrangian.skip_declined")
            if not getattr(self, "_skip_declined_warned", False):
                self._skip_declined_warned = True
                global_toc(
                    "WARNING: lagrangian_skip_solve is set but "
                    "lagrangian_dual_donors is not — the skip is "
                    "DECLINED (full batched solve runs; configure "
                    "donors, or drop the knob)", True)
        if not skip_solve:
            opt.solve_loop(q=q, q2=q2)
        # CERTIFIED bound: dual objective of the W-augmented subproblems
        # (weak duality absorbs solver tolerance; an inexact primal objective
        # can overshoot the true bound and falsely certify rel_gap)
        base = None
        if donor_cfg:
            # plateaued ADMM duals are orders-of-magnitude loose and
            # per-scenario host rescue is O(S) seconds — transfer k
            # host-EXACT donor duals batch-wide instead
            # (spopt.dual_donor_bounds; any y is valid for any scenario)
            donors = opt.dual_donor_bounds(q=q, q2=q2, **dict(donor_cfg))
            if donors is not None:
                base = donors
                if not skip_solve:
                    base = np.maximum(
                        opt.Edualbound_perscen(q=q, q2=q2), donors)
            elif skip_solve:
                # donors failed entirely: fall back to the solve path
                opt.solve_loop(q=q, q2=q2)
        lift_cfg = opt.options.get("lagrangian_milp_lift")
        if lift_cfg and bool(np.asarray(opt.batch.is_int).any()):
            every = max(1, int(lift_cfg.get("every", 1)))
            if getattr(self, "dk_iter", 1) % every == 0:
                from ..solvers.milp_bound import milp_lift

                if base is None:
                    base = opt.Edualbound_perscen(q=q, q2=q2)
                kw = {k: v for k, v in lift_cfg.items() if k != "every"}
                lifted, n = milp_lift(opt.batch, q, base, **kw)
                self.last_milp_lift_count = n
                return lifted
        if base is not None:
            return base
        return opt.Edualbound_perscen(q=q, q2=q2)

    def _set_weights_and_solve(self) -> float:
        self.opt.W = np.asarray(self.localWs, dtype=float).copy()
        return self.lagrangian()

    def main(self):
        self.lagrangian_prep()
        self.opt.W = np.zeros(
            (self.opt.batch.num_scenarios, self.opt.nonant_length)
        )
        with self.bound_pass():
            self.trivial_bound = self.lagrangian()
            self._put(self.trivial_bound)
        self.dk_iter = 1
        while not self.got_kill_signal():
            if self.new_Ws:
                with self.bound_pass():
                    bound = self._set_weights_and_solve()
                    if bound is not None and np.isfinite(bound):
                        self._put(bound)
                self.dk_iter += 1

    def finalize(self):
        """One final pass with the last Ws (lagrangian_bounder.py:85-95).

        With ``lagrangian_milp_ascent`` in the opt options (kwargs for
        :func:`tpusppy.solvers.milp_bound.milp_dual_ascent`), the final W is
        additionally polished by projected subgradient ascent on the INTEGER
        Lagrangian dual — every iterate is a certified bound, the best one
        is reported.  This is the reference Lagranger spoke's own-steps
        posture (lagranger_bounder.py) with MIP subproblem minima.  (The
        ascent's own bound keeps no certificates: ``best_certificates``
        stands at the best of the passes.)
        """
        self.final_bound = self._set_weights_and_solve()
        if np.isfinite(self.final_bound):
            self._put(self.final_bound)
        ascent_cfg = dict(self.opt.options.get("lagrangian_milp_ascent")
                          or {})
        # the hub ships its current (outer, inner) bounds in the W payload
        # tail: when the wheel has ALREADY certified a gap at or below
        # ``skip_if_gap_at``, the ascent polish can only burn the wall
        # clock the watchdog is counting
        skip_at = float(ascent_cfg.pop("skip_if_gap_at", 0.0))
        if ascent_cfg and skip_at > 0.0 and self._locals.shape[0] >= 2:
            ob, ib = self.hub_outer_bound, self.hub_inner_bound
            # the HUB's own gap convention (hub.py): minimization,
            # (ib - ob)/|ob|; a negative difference means crossed bounds —
            # never a reason to skip
            if (self.opt.is_minimizing and np.isfinite(ob)
                    and np.isfinite(ib) and abs(ob) > 0
                    and 0 <= (ib - ob) / abs(ob) <= skip_at):
                ascent_cfg = None
        if ascent_cfg and bool(np.asarray(self.opt.batch.is_int).any()):
            from ..solvers.milp_bound import milp_dual_ascent

            opt = self.opt

            def base_fn(W):
                opt.W = np.asarray(W, dtype=float)
                q, q2 = opt._augmented_q()
                # no straggler rescue inside ascent steps: the MILP lift
                # supplies the certificates, the LP duals are only the
                # partial-lift fallback — host-rescuing dozens of stalled
                # LPs per subgradient step would eat the ascent budget
                saved = opt.options.get("straggler_rescue", True)
                opt.options["straggler_rescue"] = False
                try:
                    opt.solve_loop(q=q, q2=q2)
                finally:
                    opt.options["straggler_rescue"] = saved
                return q, opt.Edualbound_perscen(q=q, q2=q2)

            best, _ = milp_dual_ascent(
                opt.batch, opt.W, base_fn, **dict(ascent_cfg))
            if np.isfinite(best) and (not np.isfinite(self.final_bound)
                                      or best > self.final_bound):
                self.final_bound = best
                self.bound = best
        return self.final_bound
