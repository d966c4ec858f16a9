"""The plain reference of a continuous two-stage deployment: scipy/HiGHS
and numpy in float64, nothing else.  The one a configuration gets that
names no ``"reference"``.

It is given the scenario data as the model's own creator makes it from the
seed and stacks it itself, never the batch the program ingested (neither
``ScenarioBatch.from_problems`` nor the server's canonical batch), and
nothing the program computed except the answers under comparison.  The HiGHS assemblies are
copies of ``chip_smoke.py``'s (PR 26).  Integrality, where the creator
states any, is relaxed here: ``two_stage_mip`` is the reference that reads
``is_int``.

What a check may call is the class's methods (``benchmarks/README.md`` has
the table); a check asks ``harness.checks.ref_has`` first and has nothing to
compare where a reference lacks the method.
"""

from __future__ import annotations

import numpy as np
import scipy.optimize as sopt
import scipy.sparse as sp


class Reference:
    """Scenario data of one two-stage deployment, stacked here from the
    problems the model's creator returns (one per scenario name)."""

    def __init__(self, module, names, creator_kwargs):
        ps = [module.scenario_creator(nm, **creator_kwargs) for nm in names]
        if len({(p.A.shape, p.c.shape) for p in ps}) != 1:
            raise ValueError("the reference wants scenarios of one shape")
        self.S = len(ps)
        self.m, self.n = ps[0].A.shape

        def stack(field):
            return np.stack([np.asarray(getattr(p, field), float)
                             for p in ps])

        self.c, self.q2 = stack("c"), stack("q2")
        self.cl, self.cu = stack("cl"), stack("cu")
        self.lb, self.ub = stack("lb"), stack("ub")
        self.const = np.array([float(p.const) for p in ps])
        given = [p.prob for p in ps]
        if all(g is None for g in given):
            self.probs = np.full(self.S, 1.0 / self.S)
        else:
            self.probs = np.array(given, float)
            if abs(self.probs.sum() - 1.0) > 1e-9:
                raise ValueError("scenario probabilities do not sum to 1")
        if any(len(p.nodes) != 1 for p in ps):
            raise ValueError("the reference is two-stage: one node a scenario")
        self.nonant = np.asarray(ps[0].nodes[0].nonant_indices)
        if any(not np.array_equal(p.nodes[0].nonant_indices, self.nonant)
               for p in ps):
            raise ValueError("nonant columns differ between scenarios")
        # one matrix object for all scenarios converts once
        self._A = [p.A for p in ps]
        self._shared = (sp.csr_matrix(np.asarray(ps[0].A, float))
                        if all(a is ps[0].A for a in self._A) else None)
        self._read(ps)

    def _read(self, problems):
        """What a reference built on this one reads besides (``is_int``)."""

    def csr(self, s):
        """Scenario ``s``'s constraint matrix, sparse."""
        if self._shared is not None:
            return self._shared
        return sp.csr_matrix(np.asarray(self._A[s], float))

    def objective(self, x):
        """(S,) plain objective of the rows of ``x``."""
        x = np.asarray(x, float)
        return ((self.c * x).sum(1) + 0.5 * (self.q2 * x * x).sum(1)
                + self.const)

    def lin_min(self, s, cost, integrality=None):
        """min cost.x over scenario ``s``'s feasible set (relaxed, unless
        ``integrality`` marks columns as scipy's ``milp`` reads it) -> value;
        ``-inf`` where HiGHS finds the LP unbounded (a gradient taken at a poor
        iterate can point down an unbounded ray).  HiGHS now and then ends a
        degenerate LP with its presolve on in the state "unknown, primal
        feasible": such an LP is solved again without presolve."""
        for options in ({}, {"presolve": False}):
            res = sopt.milp(
                c=np.asarray(cost, float),
                constraints=sopt.LinearConstraint(self.csr(s), self.cl[s],
                                                  self.cu[s]),
                integrality=integrality,
                bounds=sopt.Bounds(self.lb[s], self.ub[s]), options=options)
            if res.status == 0:
                return float(res.fun)
        if res.status == 3:
            return -np.inf
        raise RuntimeError(f"HiGHS scenario {s}: status {res.status}: "
                           f"{res.message}")

    def scenario_opt(self, s):
        """Optimal objective of scenario ``s``'s LP (integrality relaxed)."""
        return self.lin_min(s, self.c[s]) + float(self.const[s])

    def _ef_program(self):
        """(c, A, cl, cu, lb, ub, col_of) of the two-stage extensive form,
        assembled sparsely: first-stage columns shared, the rest private per
        scenario.  ``col_of`` is (S, n): each scenario's columns in it."""
        S, n, m, nonant = self.S, self.n, self.m, self.nonant
        K = nonant.size
        rest = np.setdiff1d(np.arange(n), nonant)
        ncols = K + S * rest.size
        c = np.zeros(ncols)
        lb = np.full(ncols, -np.inf)
        ub = np.full(ncols, np.inf)
        data, rows, cols = [], [], []
        cols_of = np.empty((S, n), np.int64)
        for s in range(S):
            col_of = cols_of[s]
            col_of[nonant] = np.arange(K)
            col_of[rest] = K + s * rest.size + np.arange(rest.size)
            np.add.at(c, col_of, self.probs[s] * self.c[s])
            lb[col_of] = np.maximum(lb[col_of], self.lb[s])
            ub[col_of] = np.minimum(ub[col_of], self.ub[s])
            a = self.csr(s).tocoo()
            data.append(a.data)
            rows.append(a.row + s * m)
            cols.append(col_of[a.col])
        A = sp.csr_matrix(
            (np.concatenate(data),
             (np.concatenate(rows), np.concatenate(cols))),
            shape=(S * m, ncols))
        return (c, A, np.concatenate(list(self.cl)),
                np.concatenate(list(self.cu)), lb, ub, cols_of)

    def ef(self):
        """Optimal objective of the two-stage extensive form (integrality
        relaxed)."""
        c, A, cl, cu, lb, ub, _cols = self._ef_program()
        res = sopt.milp(c=c, constraints=sopt.LinearConstraint(A, cl, cu),
                        bounds=sopt.Bounds(lb, ub))
        if res.status != 0:
            raise RuntimeError(f"HiGHS EF: status {res.status}")
        return float(res.fun + self.probs @ self.const)

    # -- Progressive Hedging in plain numpy (two-stage: one node) -----------
    def xbar_of(self, x):
        """Compute_Xbar: the probability-weighted mean of the nonants,
        (K,)."""
        return self.probs @ np.asarray(x, float)[:, self.nonant]

    def w_after(self, W_prev, rho, x_na, xbar):
        """Update_W: W + rho (x - xbar)."""
        return W_prev + rho * (x_na - xbar)

    def infeasibility(self, s, x):
        """Worst violation of scenario ``s``'s rows and bounds by ``x``, each
        against the size of what it bounds (1 at least)."""
        ax = self.csr(s) @ x
        row = np.maximum(np.maximum(self.cl[s] - ax, ax - self.cu[s]), 0.0)
        row_scale = np.maximum(1.0, np.maximum(
            np.where(np.isfinite(self.cl[s]), np.abs(self.cl[s]), 0.0),
            np.where(np.isfinite(self.cu[s]), np.abs(self.cu[s]), 0.0)))
        box = np.maximum(np.maximum(self.lb[s] - x, x - self.ub[s]), 0.0)
        box_scale = np.maximum(1.0, np.abs(x))
        return float(max((row / row_scale).max(initial=0.0),
                         (box / box_scale).max(initial=0.0)))

    def prox_gap(self, s, x, W_prev, xbar_prev, rho):
        """How far ``x`` is from optimal for scenario ``s``'s PH subproblem

            min  c.x + q2/2 x.x + W.x_na + rho/2 |x_na - xbar|^2

        by one LP: for a convex f, f(x) - min f <= g.x - min_y g.y with g the
        gradient at x (the Frank-Wolfe gap), which is 0 at the optimum.  The
        value is relative to |f(x)| (1 at least)."""
        na = self.nonant
        g = self.c[s] + self.q2[s] * x
        g[na] += W_prev + rho * (x[na] - xbar_prev)
        f = (self.c[s] @ x + 0.5 * (self.q2[s] * x) @ x + W_prev @ x[na]
             + 0.5 * (rho * (x[na] - xbar_prev)) @ (x[na] - xbar_prev))
        return float((g @ x - self.lin_min(s, g)) / max(1.0, abs(f)))
