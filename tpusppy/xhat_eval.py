"""Xhat_Eval: fix-and-evaluate candidate first-stage solutions.

TPU-native analogue of ``mpisppy/utils/xhat_eval.py:29-434``.  The reference
fixes the nonant Pyomo variables to a candidate and re-solves every scenario
through the external solver (``evaluate`` / ``evaluate_one``,
xhat_eval.py:261-330).  Here "fixing" is a bound clamp on the nonant columns of
the HBM-resident batch (lb = ub = candidate) and the evaluation is one batched
ADMM solve — so trying a candidate costs a single device program, which is what
makes the inner-bound spokes (xhatshuffle et al.) cheap.

Feasibility of the fixed problem is judged by the solver's primal residual
(the analogue of spopt.py:175-195 solver-status checks); an infeasible
candidate evaluates to +inf (for minimization).
"""

from __future__ import annotations

import numpy as np

from .obs import metrics as _metrics
from .obs import trace as _trace
from .spopt import SPOpt


class Xhat_Eval(SPOpt):
    """An SPOpt that evaluates fixed first-stage candidates.

    Typical use (mirrors xhat_eval.py:261-330)::

        ev = Xhat_Eval(options, names, scenario_creator, ...)
        z_hat = ev.evaluate(nonant_cache)   # expected objective, or +inf

    Integer recourse: the reference's external MIP solver returns integral
    second-stage solutions natively; here a ROUND-AND-DIVE loop over the
    batched LP solves does (fix near-integral integer columns, re-solve,
    repeat) — options["xhat_dive_rounds"] bounds the dives (default 12).
    """

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.tee_rank0_solves = False

    @staticmethod
    def _dive_round(x, ints, lb, ub, choose_up):
        """One dive clamp: snap near-integral free integer columns, then
        force the single most fractional free column per row toward the
        direction ``choose_up`` picks (True=ceil).  Forced values are CLIPPED
        into the current box first — an out-of-box force (e.g. flooring an
        x-iterate that sits just below lb) must tighten inside the domain,
        never collapse the box past its true bounds.
        Returns the updated (lb, ub) or None when nothing is left to do."""
        import numpy as np

        free = ints[None, :] & (ub > lb)
        frac = np.where(free, np.abs(x - np.round(x)), -1.0)
        if not free.any() or frac.max() < 1e-6:
            return None
        near = free & (frac < 0.1)
        vals = np.round(np.where(near, x, 0.0))
        pick = frac.argmax(axis=1)
        # force only when the worst column is OUTSIDE the snap band: if all
        # free columns are near-integral, snapping already progresses, and a
        # force would override the snap and round a ~0.08 binary the wrong way
        has = free.any(axis=1) & (frac.max(axis=1) >= 0.1)
        B = x.shape[0]
        up = choose_up(B)
        force = np.zeros_like(near)
        force[np.arange(B), pick] = has
        fx = np.where(force, x, 0.0)
        fv = np.where(up[:, None], np.ceil(fx - 1e-9), np.floor(fx + 1e-9))
        vals = np.where(force, fv, vals)
        vals = np.clip(vals, lb, ub)
        clamp = near | force
        lb = np.where(clamp, np.maximum(vals, lb), lb)
        ub = np.where(clamp, np.minimum(vals, ub), ub)
        return lb, np.maximum(ub, lb)

    def _integer_dive(self, lb, ub):
        """Drive remaining fractional integer columns integral.

        Per round: solve the batch; clamp integer columns within 0.1 of an
        integer to that integer, plus (to guarantee progress) each
        scenario's single most fractional integer column rounded UP
        (covering-style constraints stay satisfiable; the re-solve lets
        other free columns compensate).
        """
        import numpy as np

        from .spopt import batch_solve_dispatch

        b = self.batch
        ints = b.is_int
        rounds = max(1, int(self.options.get("xhat_dive_rounds", 12)))
        lb = np.array(lb, copy=True)
        ub = np.array(ub, copy=True)
        x = None
        done = 0
        with _trace.phase("dive") as ph:
            for done in range(1, rounds + 1):
                sol = batch_solve_dispatch(b, b.c, b.q2, b.cl, b.cu, lb, ub,
                                           settings=self.admm_settings)
                x = np.asarray(sol.x)
                self.local_x = x
                self.pri_res = np.asarray(sol.pri_res)
                self.dua_res = np.asarray(sol.dua_res)
                nxt = self._dive_round(x, ints, lb, ub,
                                       lambda B: np.ones(B, dtype=bool))
                if nxt is None:
                    break
                lb, ub = nxt
            ph.add(rounds=done)
        _metrics.inc("xhat.dive_rounds", done)
        return x

    def _retry_dive(self, lb0, ub0, bad):
        """Batched randomized-rounding retries for the scenarios a plain dive
        wedged (device path; replaces most uses of the serial host MILP).

        Each wedged scenario is tiled R times; every replica gets a random
        rounding direction for its forced column each round, and all
        replicas re-dive TOGETHER in one batch.  The deterministic round-up
        dive wedges exactly when some column needed the other direction
        (e.g. cardinality rows); randomization explores the corners at batch
        cost instead of per-scenario host MILPs.  Work is chunked so the
        replica batch never exceeds ``xhat_dive_retry_batch`` rows.
        Returns (solutions (len(bad), n), feasible flags).
        """
        import numpy as np

        from .spopt import batch_solve_dispatch

        b = self.batch
        cap = max(1, int(self.options.get("xhat_dive_retry_batch", 512)))
        # R in [1, cap] so the replica batch honors the memory cap
        R = max(1, min(int(self.options.get("xhat_dive_retries", 8)), cap))
        rng = np.random.RandomState(
            int(self.options.get("xhat_dive_seed", 0)))
        ints = b.is_int
        tol = max(self.options.get("feas_tol", 1e-3),
                  10.0 * self.admm_settings.eps_rel)
        rounds = max(1, int(self.options.get("xhat_dive_rounds", 12)))
        chunk = max(1, cap // R)

        xs = np.zeros((bad.size, b.num_vars))
        feas = np.zeros(bad.size, dtype=bool)
        _metrics.inc("xhat.retry_rows", bad.size * R)
        with _trace.phase("retry_dive", rows=int(bad.size), replicas=R):
            for c0 in range(0, bad.size, chunk):
                sel = bad[c0:c0 + chunk]
                tile = lambda a: np.repeat(a[sel], R, axis=0)
                c_t, q2_t = tile(b.c), tile(b.q2)
                cl_t, cu_t = tile(b.cl), tile(b.cu)
                lb_t, ub_t = tile(lb0), tile(ub0)
                x = None
                for _ in range(rounds):
                    sol = batch_solve_dispatch(
                        b, c_t, q2_t, cl_t, cu_t, lb_t, ub_t,
                        settings=self.admm_settings, rows=sel, tile=R)
                    x = np.asarray(sol.x)
                    nxt = self._dive_round(x, ints, lb_t, ub_t,
                                           lambda B: rng.rand(B) < 0.5)
                    if nxt is None:
                        break
                    lb_t, ub_t = nxt
                # best feasible replica per wedged scenario
                objs = (np.einsum("bn,bn->b", c_t, x)
                        + 0.5 * np.einsum("bn,bn->b", q2_t, x * x))
                pri = np.asarray(sol.pri_res)
                frac = np.where(ints[None, :], np.abs(x - np.round(x)), 0.0)
                ok = (pri <= tol) & (frac.max(axis=1) < 1e-5)
                objs = np.where(ok, objs, np.inf)
                for i in range(sel.size):
                    grp = objs[i * R:(i + 1) * R]
                    j = int(np.argmin(grp))
                    feas[c0 + i] = np.isfinite(grp[j])
                    xs[c0 + i] = x[i * R + j]
        return xs, feas

    def _host_milp(self, lb, ub, only=None):
        """Per-scenario HiGHS MILP with nonants clamped — the LAST-DITCH
        fallback when both diving and batched retries wedge.  This is the
        role the reference's external MIP solver plays for incumbent
        evaluation; ``only`` restricts the loop to the still-wedged slice.
        """
        import numpy as np

        from .solvers import scipy_backend

        b = self.batch
        S = b.num_scenarios
        scens = range(S) if only is None else only
        xs = np.array(self.local_x, copy=True) if self.local_x is not None \
            else np.zeros((S, b.num_vars))
        pri = np.zeros(S)
        limit = float(self.options.get("xhat_mip_time_limit", 2.0))
        gap = float(self.options.get("xhat_mip_rel_gap", 1e-4))
        _metrics.inc("xhat.host_milp_rows", len(scens))
        with _trace.phase("host_milp", rows=len(scens)):
            for s in scens:
                res = scipy_backend.solve_lp(
                    b.c[s], b.A[s], b.cl[s], b.cu[s], lb[s], ub[s],
                    is_int=b.is_int, mip_rel_gap=gap, time_limit=limit)
                if res.feasible:
                    xs[s] = res.x
                else:
                    pri[s] = np.inf
        self.local_x = xs
        self.pri_res = pri
        self.dua_res = np.zeros(S)
        return xs

    def _fix_and_solve_bucketed(self, nonant_cache):
        """Ragged (bucketed) fix-and-evaluate with INTEGER support: each
        bucket runs the full homogeneous machinery (clamp, dive, batched
        retries, host-MILP residue) on its compact sub-batch, results
        scattered back to the bookkeeping layout.  Valid because bundle
        construction keeps the packed nonant-slot order identical between
        the global tree and every bucket's local tree (same root nonants,
        same order — only the column indices differ)."""
        import numpy as np

        from .ir import BucketedBatch

        b = self.batch
        assert isinstance(b, BucketedBatch)
        cache = np.asarray(nonant_cache, dtype=float)
        if cache.ndim == 1:
            cache = np.broadcast_to(cache, (b.num_scenarios, cache.shape[0]))
        S, n_max = b.c.shape
        x_out = np.zeros((S, n_max))
        pri = np.zeros(S)
        dua = np.zeros(S)
        # snapshot EVERY solver-state attribute the solve path touches
        # (including caches keyed on the batch — they'd go stale against the
        # sub-batches otherwise).  No cross-call amortization is lost here:
        # the homogeneous clamp path itself solves cold (solve_loop with
        # warm=False; clamped geometry makes stale duals counterproductive).
        saved = {k: getattr(self, k, None) for k in (
            "batch", "tree", "nid_sk", "_warm", "_factors", "_factors_sig",
            "_factors_age", "local_x", "pri_res", "dua_res", "_fixed_lb",
            "_fixed_ub", "_dev_consts", "_bucket_dev_consts",
            "_cached_nonants")}
        try:
            for idx_arr, sub in b.buckets:
                self.batch = sub
                self.tree = sub.tree
                self.nid_sk = sub.tree.nid_sk()
                self._warm = None
                self._factors = None
                self._factors_sig = None
                self._factors_age = 0
                self.local_x = None
                self.pri_res = None
                self.dua_res = None
                x = self._fix_and_solve(cache[idx_arr])
                x_out[idx_arr, :sub.num_vars] = np.asarray(x)
                if self.pri_res is not None:
                    pri[idx_arr] = np.asarray(self.pri_res)
                if self.dua_res is not None:
                    dua[idx_arr] = np.asarray(self.dua_res)
        finally:
            for k, v in saved.items():
                setattr(self, k, v)
        self.local_x = x_out
        self.pri_res = pri
        self.dua_res = dua
        return x_out

    def _round_int_nonants(self, cache):
        """Snap integer nonant coordinates of a candidate to integers (see
        :meth:`_fix_and_solve`); no-op for continuous families."""
        import numpy as np

        if not self.options.get("xhat_round_ints", True):
            return cache
        nid = np.asarray(self.batch.tree.nonant_indices)
        ints = np.asarray(self.batch.is_int)[nid].astype(bool)
        if not ints.any():
            return cache
        cache = np.array(cache, dtype=float, copy=True)
        cache[..., ints] = np.round(cache[..., ints])
        return cache

    def _fix_and_solve(self, nonant_cache):
        """Clamp nonants to the candidate and solve the whole batch.

        ``nonant_cache``: (K,) single candidate shared by all scenarios, or
        (S, K) per-scenario (multistage xhats fix per-node values; scenarios of
        one node must carry identical values there).

        Integer nonant coordinates are snapped to the nearest integer first
        (``xhat_round_ints``, default on): device-path donors carry
        LP-relaxation values, so families whose integers are ALL first-stage
        (UC commitment) would otherwise be "evaluated" at fractional
        commitments — never a valid incumbent, and catastrophically priced
        when fractional capacity triggers VOLL shedding.  The reference
        never faces this: its donors come from MIP subproblem solves and are
        integral already (xhatshufflelooper_bounder.py donor caches).
        Snapping preserves per-node equality, so multistage fixing is safe.
        """
        import numpy as np

        from .ir import BucketedBatch

        if isinstance(self.batch, BucketedBatch):
            return self._fix_and_solve_bucketed(nonant_cache)
        nonant_cache = self._round_int_nonants(nonant_cache)
        self.fix_nonants(nonant_cache)
        try:
            b = self.batch
            leftover_ints = b.is_int.any() and bool(
                (b.is_int[None, :] & (self._fixed_ub > self._fixed_lb)).any()
            )
            if leftover_ints and self.options.get(
                    "xhat_integer_strategy", "dive") == "milp":
                # exact per-scenario host MILPs instead of device dives:
                # the right tool for families whose SECOND stage is mostly
                # binary scheduling (e.g. USAR), where rounding dives wedge
                # on hundreds of coupled binaries but each scenario MILP is
                # solver-trivial — the reference's posture for every
                # incumbent evaluation (extensions/xhatbase.py:38-230)
                x = self._host_milp(self._fixed_lb, self._fixed_ub)
            elif leftover_ints:
                x = self._integer_dive(self._fixed_lb, self._fixed_ub)
                tol = max(self.options.get("feas_tol", 1e-3),
                          10.0 * self.admm_settings.eps_rel)
                ints = b.is_int[None, :]
                frac = np.where(ints, np.abs(x - np.round(x)), 0.0)
                bad = np.flatnonzero(
                    (np.asarray(self.pri_res) > tol)
                    | (frac.max(axis=1) > 1e-5))
                _metrics.inc("xhat.dive_wedged_rows", bad.size)
                if bad.size:
                    # batched randomized-rounding retries for wedged
                    # scenarios (device path)
                    xs, feas = self._retry_dive(self._fixed_lb,
                                                self._fixed_ub, bad)
                    x = np.array(x, copy=True)   # jax arrays are read-only
                    x[bad[feas]] = xs[feas]
                    self.local_x = x
                    pri = np.array(self.pri_res, copy=True)
                    pri[bad[feas]] = 0.0
                    self.pri_res = pri
                    still = bad[~feas]
                    if still.size:
                        # last ditch: exact host MILPs on the residue only
                        x = self._host_milp(self._fixed_lb, self._fixed_ub,
                                            only=still)
            else:
                # cold start: the clamped problem's geometry differs enough
                # that stale warm duals slow ADMM down rather than help.
                # With a model repair available, the host-LP straggler
                # rescue is pure waste here (O(seconds) per plateaued
                # scenario; the repair certifies feasibility for free)
                saved_rescue = self.options.get("straggler_rescue", True)
                if getattr(self.batch, "repair_fn", None) is not None:
                    self.options["straggler_rescue"] = False
                try:
                    x = self.solve_loop(warm=False)
                finally:
                    self.options["straggler_rescue"] = saved_rescue
            x = self._repair_and_verify(x)
        finally:
            self.restore_nonants()
        return x

    def _repair_and_verify(self, x):
        """Model-declared feasibility repair + EXACT verification.

        Families with full recourse attach ``repair_fn`` to their batch
        (e.g. UC: shed/reserve slacks close any dispatch residual in closed
        form — models/uc_data._make_repair).  The repaired point is
        verified against the ORIGINAL rows/bounds with one sparse matvec
        per scenario; verified scenarios get an exact zero residual, so
        ``evaluate``'s feasibility gate passes on true feasibility instead
        of ADMM residuals.  This is what makes S=1000 incumbent evaluation
        affordable: the host-LP straggler rescue prices O(seconds) PER
        plateaued scenario (spopt straggler_lp_max), which forbade
        full-scale evaluation outright.
        """
        rf = getattr(self.batch, "repair_fn", None)
        if rf is None:
            return x
        import numpy as np
        import scipy.sparse as sp

        b = self.batch
        x = rf(np.asarray(x, float), b)
        A_sh = getattr(b, "A_shared", None)
        key = (id(A_sh if A_sh is not None else b.A), b.version)
        cached = getattr(self, "_verify_csr", None)
        if cached is None or cached[0] != key:
            An = np.asarray(A_sh) if A_sh is not None \
                else None
            self._verify_csr = (key, sp.csr_matrix(An)
                                if An is not None else None)
            cached = self._verify_csr
        tol = float(self.options.get("repair_verify_tol", 1e-6))
        S = b.num_scenarios
        if cached[1] is not None:
            r = np.asarray((cached[1] @ x.T).T)          # (S, m)
        else:
            r = np.einsum("smn,sn->sm", np.asarray(b.A), x)
        scale = np.maximum(1.0, np.maximum(
            np.abs(np.where(np.isfinite(b.cl), b.cl, 0.0)),
            np.abs(np.where(np.isfinite(b.cu), b.cu, 0.0))))
        row_viol = np.maximum(
            np.maximum(b.cl - r, r - b.cu), 0.0) / scale
        bscale = np.maximum(1.0, np.maximum(
            np.abs(np.where(np.isfinite(b.lb), b.lb, 0.0)),
            np.abs(np.where(np.isfinite(b.ub), b.ub, 0.0))))
        bnd_viol = np.maximum(
            np.maximum(b.lb - x, x - b.ub), 0.0) / bscale
        pri = np.maximum(row_viol.max(axis=1), bnd_viol.max(axis=1))
        # verified scenarios are EXACTLY feasible; the rest keep their true
        # violation (the inf gate then reports genuine infeasibility, e.g.
        # a candidate breaking min-up/down rows the repair cannot touch)
        self.local_x = x
        self.pri_res = np.where(pri <= tol, 0.0, pri + 1.0)
        self.dua_res = np.zeros(S)
        return x

    def evaluate_one(self, nonant_cache, scenario_index: int) -> float:
        """Objective of ONE scenario at the fixed candidate
        (xhat_eval.py:261-292)."""
        x = self._fix_and_solve(nonant_cache)
        if self.pri_res is not None:
            tol = self.options.get("feas_tol", 1e-3)
            if self.pri_res[scenario_index] > tol:
                return np.inf
        return float(self.batch.objective(x)[scenario_index])

    def evaluate(self, nonant_cache) -> float:
        """Expected objective at the fixed candidate; +inf if any scenario is
        infeasible (xhat_eval.py:293-330 + feas_prob check)."""
        x = self._fix_and_solve(nonant_cache)
        _metrics.inc("xhat.candidates")
        if self.feas_prob() < 1.0 - 1e-9:
            # refused, and by how many rows of how many
            bad = ~self.feasible_rows()
            _metrics.inc("xhat.infeasible")
            _metrics.inc("xhat.infeasible_rows", int(np.count_nonzero(bad)))
            _metrics.inc("xhat.infeasible_of_rows", bad.size)
            return np.inf
        return float(self.probs @ self.batch.objective(x))

    def objective_values(self, nonant_cache) -> np.ndarray:
        """(S,) per-scenario objectives at the fixed candidate."""
        x = self._fix_and_solve(nonant_cache)
        return self.batch.objective(x)
