"""PHBase: Progressive Hedging state and iteration.

TPU-native analogue of ``mpisppy/phbase.py:176-1050``.  PH state — duals W,
penalty rho, node averages xbar — are (S, K) arrays over the packed nonant
layout.  The two global reductions of the reference become tensor contractions:

* ``Compute_Xbar`` (phbase.py:27-107): per-tree-node probability-weighted means
  via a one-hot node-membership contraction (replacing one Allreduce per node on
  per-node communicators), sharding-ready (psum over the scenario mesh axis).
* ``convergence_diff`` (phbase.py:321-343): scaled L1 deviation from xbar.

The augmented objective (attach_PH_to_objective, phbase.py:617-699)
``obj += W_on * W.x + prox_on * (rho/2)(x^2 - 2 xbar x + xbar^2)`` never touches
a model: it is just a (q, q2) override for the batched ADMM solve, and the prox
term needs no linearization cuts (prox_approx.py) because the solver is a QP
solver natively.
"""

from __future__ import annotations

import numpy as np

from . import global_toc
from .obs import metrics as _metrics
from .obs import trace as _trace
from .spopt import SPOpt
from .extensions.extension import Extension


class PHBase(SPOpt):
    """PH state + iteration drivers (Iter0 / iterk_loop / post_loops)."""

    def __init__(self, *args, extensions=None, extension_kwargs=None,
                 ph_converger=None, rho_setter=None, **kwargs):
        super().__init__(*args, **kwargs)
        self._options_check(["defaultPHrho", "PHIterLimit"], self.options)
        K = self.nonant_length
        S = self.batch.num_scenarios

        self.W = np.zeros((S, K))
        self.xbars = np.zeros((S, K))       # per-scenario view of node xbar
        self.xsqbars = np.zeros((S, K))
        self.rho = self._initial_rho(rho_setter)
        self.W_on = True
        self.prox_on = True
        self.conv = None
        self._iter = 0
        self.best_bound = -np.inf if self.is_minimizing else np.inf

        ext_cls = extensions if extensions is not None else Extension
        self.extobject = ext_cls(self, **(extension_kwargs or {})) \
            if extension_kwargs else ext_cls(self)
        self.ph_converger = ph_converger(self) if ph_converger else None
        self.spcomm = None

        # Precompute node-membership one-hot for xbar contraction: (S, K) -> N
        self._onehot = self.tree.onehot_sk_n()

    def _initial_rho(self, rho_setter):
        K = self.nonant_length
        S = self.batch.num_scenarios
        rho = np.full((S, K), float(self.options["defaultPHrho"]))
        if rho_setter is not None:
            # rho_setter(batch) -> (K,) or (S, K) array (cf. phbase.py rho_setter)
            r = np.asarray(rho_setter(self.batch), dtype=float)
            rho = np.broadcast_to(r, (S, K)).copy() if r.ndim == 1 else r.copy()
        return rho

    # ---- reductions ---------------------------------------------------------
    def _nonants_cached(self) -> np.ndarray:
        """(S, K) nonants of the CURRENT ``local_x``, gathered once per
        solve: Compute_Xbar / Update_W / convergence_diff and the hub's
        nonant payload all read the same snapshot instead of re-gathering
        4x per iteration (part of the single-fetch wheel-iteration
        discipline, doc/pipeline.md).  Keyed on the ``local_x`` object
        identity — every solve path ASSIGNS a fresh array; paths that
        mutate rows in place (APH's fractional dispatch) drop the cache
        explicitly."""
        if getattr(self, "_xk_src", None) is not self.local_x:
            self._xk = self.nonants_of(self.local_x)
            self._xk_src = self.local_x
        return self._xk

    @property
    def sync_version(self):
        """Monotone token of the hub-visible PH state (W / nonants /
        iteration).  The hub's mailbox writes skip when it has not
        advanced — the linger loop polls sync several times a second, and
        re-Putting identical payloads would bump write-ids and force every
        spoke to recompute on data it already acted on."""
        return (self._iter, getattr(self, "_state_version", 0))

    def _bump_state_version(self):
        self._state_version = getattr(self, "_state_version", 0) + 1

    def _node_avgs(self, xk):
        """(xbars, xsqbars) as scenario-indexed (S, K): per-node
        probability-weighted E[x] and E[x^2] gathered back through
        ``nid_sk`` (the Compute_Xbar core)."""
        p = self.probs[:, None]                                  # (S, 1)
        num = np.einsum("skn,sk->nk", self._onehot, p * xk)      # (N, K)
        sqnum = np.einsum("skn,sk->nk", self._onehot, p * xk * xk)
        den = np.einsum("skn,sk->nk", self._onehot, np.broadcast_to(p, xk.shape))
        den = np.maximum(den, 1e-300)
        kidx = np.arange(self.nonant_length)[None, :]
        return ((num / den)[self.nid_sk, kidx],
                (sqnum / den)[self.nid_sk, kidx])

    def Compute_Xbar(self, verbose=False):
        """Per-node weighted averages of nonants (phbase.py:27-107)."""
        with _trace.phase("xbar"):
            xk = self._nonants_cached()                          # (S, K)
            self.xbars, self.xsqbars = self._node_avgs(xk)
        if verbose:
            global_toc(f"xbar[:8]={self.xbars[0][:8]}")

    def Update_W(self, verbose=False):
        """Dual update W += rho (x - xbar) (phbase.py:293-318)."""
        with _trace.phase("w_update"):
            xk = self._nonants_cached()
            self.W = self.W + self.rho * (xk - self.xbars)
            self._bump_state_version()
        if verbose:
            global_toc(f"W[0][:8]={self.W[0][:8]}")

    def convergence_diff(self) -> float:
        """Scaled norm of x - xbar (phbase.py:321-343)."""
        xk = self._nonants_cached()
        dev = np.abs(xk - self.xbars).mean(axis=1)
        return float(self.probs @ dev)

    # ---- augmented objective ------------------------------------------------
    def _augmented_q(self):
        """(q, q2) for the PH subproblem (attach_PH_to_objective)."""
        b = self.batch
        idx = self.tree.nonant_indices
        q = np.array(b.c, copy=True)
        if self.W_on:
            q[:, idx] += self.W
        if self.prox_on:
            q[:, idx] += -self.rho * self.xbars
        return q, self._augmented_q2()

    def _augmented_q2(self):
        """q2 alone — the Factors-signature input (:meth:`_solve_sig`
        never reads q).  Skips the W/xbars q assembly ``_augmented_q``
        pays, which matters on the megastep hot loop where this runs
        once per window as a pure staleness check."""
        q2 = np.array(self.batch.q2, copy=True)
        if self.prox_on:
            q2[:, self.tree.nonant_indices] += self.rho
        return q2

    def solve_ph_subproblems(self):
        with _trace.phase("solve"):
            self.extobject.pre_solve_loop()
            q, q2 = self._augmented_q()
            self.solve_loop(q=q, q2=q2)
            self.extobject.post_solve_loop()

    # ---- drivers ------------------------------------------------------------
    def Iter0(self) -> float:
        """Initial solves with W=prox off; returns the trivial bound
        (phbase.py:758-872)."""
        # one phase from the plain solve to the first hub sync: the
        # refresh, rescue, xbar, w_update and sync phases nest inside
        with _trace.phase("iter0"):
            self._iter0()
        # serving SLO seam (doc/serving.md): the solve server records
        # time-to-iter-1 per request here — the warm-path acceptance
        # metric (a warm family reaches this point without compiling)
        cb = self.options.get("on_iter0_done")
        if cb is not None:
            try:
                cb()
            except Exception:   # a telemetry hook must never cost the run
                pass
        return self.trivial_bound

    def _iter0(self):
        self.extobject.pre_iter0()
        self._iter = 0
        self.solve_loop()  # plain objective
        feas = self.feas_prob()
        if feas < 1.0 - 1e-6:
            # residuals above feas_tol conflate two states: a truly
            # infeasible scenario (the reference's hard-quit case,
            # phbase.py:818-823) and a first-order-solver PLATEAU (large
            # coupled families park at ~5e-3 scaled primal regardless of
            # budget).  Disambiguate host-exactly on a bounded sample of
            # the worst offenders: if every checked scenario IS feasible,
            # this is plateau, not infeasibility — proceed.
            from .solvers import scipy_backend

            tol = max(self.options.get("feas_tol", 1e-3),
                      10.0 * self.admm_settings.eps_rel)
            pri0 = np.asarray(self.pri_res)
            # ~(pri <= tol), NOT (pri > tol): NaN residuals (diverged
            # solves) must land in the check set, not slip past it
            bad = np.flatnonzero(~(pri0 <= tol))
            key = np.where(np.isnan(pri0[bad]), np.inf, pri0[bad])
            worst = bad[np.argsort(-key)][:16]
            b = self.batch
            truly_bad = []
            for s in worst:
                r = scipy_backend.solve_lp(
                    np.zeros(b.num_vars), b.A[s], b.cl[s], b.cu[s],
                    b.lb[s], b.ub[s])
                if not r.feasible:
                    truly_bad.append(int(s))
            if truly_bad:
                raise RuntimeError(
                    f"Infeasibility detected at iter0; feasible mass "
                    f"{feas:.4f}, host-verified infeasible scenarios "
                    f"{truly_bad} (cf. phbase.py:818-823 hard quit)"
                )
            checked_all = len(worst) == bad.size
            global_toc(
                f"iter0: {bad.size} scenario(s) above feas_tol are a "
                "solver plateau (host feasibility check passed on "
                + ("ALL of them" if checked_all
                   else f"the {len(worst)} worst — a sampled check")
                + ") — continuing", True)
        # CERTIFIED trivial bound: weak duality, not the primal objective.
        # Ebound() of an inexact iter0 solve OVERESTIMATES the wait-and-see
        # bound by the solver residual — at reference scale (S=1000 WECC,
        # solves parked at plateau) by double digits, which crossed the
        # bounds and FALSELY certified a negative gap in the r5 full-scale
        # wheel.  With converged solves the two coincide to tolerance.
        self.trivial_bound = self.Edualbound()
        eb = self.Ebound()
        if np.isfinite(eb) and abs(eb - self.trivial_bound) > \
                1e-3 * max(1.0, abs(eb)):
            global_toc(
                f"iter0: certified trivial bound {self.trivial_bound:.4e} "
                f"(primal objective {eb:.4e} is solver-tolerance-loose "
                "and NOT used as a bound)", True)
        self.best_bound = self.trivial_bound
        self.Compute_Xbar()
        self.Update_W()
        self._apply_resume()
        self.conv = self.convergence_diff()
        self.extobject.post_iter0()
        if self.spcomm is not None:
            self.spcomm.sync()
            self.extobject.post_iter0_after_sync()
        global_toc(
            f"Iter0 trivial bound {self.trivial_bound:.4f} conv {self.conv:.3e}",
            self.options.get("display_progress", False),
        )

    def _apply_resume(self):
        """Re-seat checkpointed PH state, when a resume was requested.

        Runs at the END of Iter0 (the WXBarReader seam): the plain warm-up
        solve has populated warm states and the trivial bound, and the
        (W, xbars, rho) it derived are REPLACED wholesale by the
        checkpoint's, so the first iterk solve reproduces the augmented
        objective of the iteration after the snapshot.  Also sets
        ``_iter_base`` so ``PHIterLimit`` keeps meaning TOTAL iterations
        across restarts (``iterk_loop`` starts past the base)."""
        ck = getattr(self, "_resume_ckpt", None)
        if ck is None:
            return
        from .resilience import checkpoint as _ckpt

        _ckpt.restore_ph(self, ck)
        self._resume_ckpt = None

    # ---- wheel megakernel (N iterations per dispatch) -----------------------
    def _megastep_request(self) -> int:
        """Resolved megakernel width N (>= 2) when the device-resident
        wheel megastep may drive this hub's iterations, else 0 (legacy
        per-iteration dispatch).

        Gates (each falls back to legacy, never errors): the
        ``ADMMSettings.megastep`` knob (1 = forced legacy); homogeneous
        batch; trivial extensions and no ph_converger (their per-
        iteration callouts cannot run inside the scan); no nonant fixing
        overlay; W/prox on (the iterk posture); a frozen-amortized
        refresh cadence; and shapes that fit ONE dispatch (megasteps
        never segment).  N is the autotuner's banked verdict when one
        exists (:func:`tpusppy.tune.megastep_verdict`), else the refresh
        window (``refresh_every - 1``: one legacy refresh dispatch + one
        megastep per cadence block), clamped by the watchdog cap
        (:func:`~tpusppy.solvers.segmented.megastep_cap` — a megastep is
        N iterations of work inside one dispatch budget).
        """
        from .extensions.extension import Extension
        from .ir import BucketedBatch
        from .solvers import segmented
        from .solvers.sparse import SparseA

        st = self.admm_settings
        req = int(getattr(st, "megastep", 0) or 0)
        if req == 1:
            return 0
        b = self.batch
        if type(self.extobject) is not Extension \
                or self.ph_converger is not None:
            return 0
        if self._fixed_lb is not None or self._fixed_ub is not None:
            return 0
        if not (self.W_on and self.prox_on):
            return 0
        refresh_every = self._refresh_every()
        if refresh_every <= 2:
            return 0
        if isinstance(b, BucketedBatch):
            # bucketed megakernel: EVERY bucket must fit one dispatch, and
            # the watchdog cap sums the buckets' per-iteration worst cases
            # (one scan step sweeps them all) — megastep_cap_multi
            from .spopt import bucket_shared

            shapes = []
            for idx, sub in b.buckets:
                fb = 1 if bucket_shared(sub) else idx.size
                _, seg_f = segmented.dispatch_segments(
                    idx.size, sub.num_vars, sub.num_rows, st,
                    factor_batch=fb)
                if seg_f < st.max_iter:
                    return 0
                shapes.append((idx.size, sub.num_vars, sub.num_rows, fb))
            cap = self._megastep_cap_with_bounds(
                lambda bp: segmented.megastep_cap_multi(
                    shapes, st, bound_pass=bp))
            if req > 1:
                n_sel = req
            else:
                from . import tune

                n_sel = tune.megastep_verdict(
                    tuple(s[:3] for s in shapes), settings=st) \
                    or (refresh_every - 1)
            n_sel = min(n_sel, refresh_every - 1, cap)
            return n_sel if n_sel >= 2 else 0
        S, n, m = b.num_scenarios, b.num_vars, b.num_rows
        shared = getattr(b, "A_shared", None) is not None
        sf = (segmented.SPARSE_DISPATCH_FACTOR if isinstance(
            self._device_consts(st.jdtype())[0], SparseA) else 1.0)
        fb = 1 if shared else S
        _, seg_f = segmented.dispatch_segments(S, n, m, st, factor_batch=fb,
                                               sparse_factor=sf)
        if seg_f < st.max_iter:
            return 0          # segmentation regime: the step pair owns it
        cap = self._megastep_cap_with_bounds(
            lambda bp: segmented.megastep_cap(S, n, m, st, factor_batch=fb,
                                              sparse_factor=sf,
                                              bound_pass=bp))
        if req > 1:
            n_sel = req
        else:
            from . import tune

            n_sel = tune.megastep_verdict(S, n, m, settings=st) \
                or (refresh_every - 1)
        n_sel = min(n_sel, refresh_every - 1, cap)
        return n_sel if n_sel >= 2 else 0

    def _mega_age(self) -> int:
        """Frozen-factor age for the megastep readiness gate: the
        homogeneous slot's age, or the OLDEST bucket slot's (every bucket
        sweeps in one scan step, so the stalest factors gate the window)."""
        from .ir import BucketedBatch

        if isinstance(self.batch, BucketedBatch):
            slots = getattr(self, "_bucket_slots", None) or []
            if not slots:
                return 10 ** 9
            return max(s.get("age", 0) for s in slots)
        return self._factors_age

    def _mega_slots_ready(self, refresh_every) -> bool:
        """Frozen-amortization slots valid for a megastep window: factors
        + warm present, not aged out, and the validity signature matches
        (per bucket, for a bucketed batch)."""
        from .ir import BucketedBatch

        b = self.batch
        if isinstance(b, BucketedBatch):
            slots = getattr(self, "_bucket_slots", None)
            if not slots or len(slots) != len(b.buckets):
                return False
            q2_full = self._augmented_q2()
            lb = np.asarray(b.lb)
            ub = np.asarray(b.ub)
            for (idx, sub), slot in zip(b.buckets, slots):
                if slot.get("warm") is None or slot.get("factors") is None:
                    return False
                if slot.get("age", 0) >= refresh_every:
                    return False
                n = sub.num_vars
                if self._solve_sig(q2_full[idx, :n], lb[idx, :n],
                                   ub[idx, :n]) != slot.get("sig"):
                    return False
            return True
        if self._factors is None or self._warm is None:
            return False
        if self._factors_age >= refresh_every:
            return False
        return self._solve_sig(self._augmented_q2(), b.lb, b.ub) \
            == self._factors_sig

    def _megastep_dispatch(self, n_req, n_live, convthresh,
                           bound_live=None):
        """Route one window to the homogeneous or bucketed megakernel.
        ``bound_live``: the in-wheel certification flag for THIS window
        (None = bound-pass variant not armed — the legacy program)."""
        from .ir import BucketedBatch

        if isinstance(self.batch, BucketedBatch):
            return self._megastep_solve_bucketed(
                n_req, n_live, convthresh, self.W, self.xbars, self.rho,
                bound_live=bound_live)
        return self._megastep_solve(n_req, n_live, convthresh,
                                    self.W, self.xbars, self.rho,
                                    bound_live=bound_live)

    # ---- in-wheel certification (doc/pipeline.md) ---------------------------
    def _megastep_cap_with_bounds(self, cap_fn):
        """Watchdog cap with the in-wheel bound-pass reservation — and
        the reservation must never KILL the megastep: a family that
        barely fits (plain cap 2, reserved cap < 2) would otherwise
        silently lose both the megastep AND the bounds.  There, in-wheel
        certification is disabled for this family loudly (the bound
        spokes remain the certification path) and the plain cap is
        kept."""
        if not self._inwheel_on():
            return cap_fn(False)
        # the reservation scales with the pass's evaluation count — the
        # batched integer sweep reserves C candidates + 1 re-solve
        cap = cap_fn(self._inwheel_pass_evals())
        if cap >= 2:
            return cap
        cap_plain = cap_fn(False)
        if cap_plain >= 2 and not getattr(self, "_inwheel_cap_declined",
                                          False):
            self._inwheel_cap_declined = True
            global_toc(
                "in_wheel_bounds: the bound-pass watchdog reservation "
                "would disable the megastep for this shape — in-wheel "
                "certification disabled (bound spokes remain the "
                "certification path)", True)
        return cap_plain

    def _inwheel_on(self) -> bool:
        """Whether megastep windows run the fused bound pass — the
        ``in_wheel_bounds`` option, gated to minimization (the
        weak-duality outer assembly and the xhat feasibility gate are
        minimization-convention, like the bound spokes they replace)."""
        if not self.options.get("in_wheel_bounds"):
            return False
        if getattr(self, "_inwheel_cap_declined", False):
            return False    # the bound-pass reservation would kill the
            # megastep for this shape (_megastep_cap_with_bounds)
        if not self.is_minimizing:
            if not getattr(self, "_inwheel_min_warned", False):
                self._inwheel_min_warned = True
                global_toc(
                    "in_wheel_bounds: maximization families are not "
                    "supported (bound spokes remain the certification "
                    "path) — disabled", True)
            return False
        return True

    def _inwheel_inner_ok(self) -> bool:
        """Whether the in-wheel INNER bound may be consumed: every
        integer column must be a nonant slot (the device candidate
        rounds those integral; leftover second-stage integers need the
        Xhat_Eval dive/MILP machinery, which cannot run in-scan — the
        xhat spokes keep that posture)."""
        ok = getattr(self, "_inwheel_inner_ok_cache", None)
        if ok is None:
            from .ir import BucketedBatch

            b = self.batch
            subs = ([sub for _, sub in b.buckets]
                    if isinstance(b, BucketedBatch) else [b])
            ok = True
            for sub in subs:
                free = np.ones(sub.num_vars, dtype=bool)
                free[sub.tree.nonant_indices] = False
                if np.asarray(sub.is_int, bool)[free].any():
                    ok = False
                    break
            self._inwheel_inner_ok_cache = ok
            if not ok:
                global_toc(
                    "in_wheel_bounds: second-stage integer columns — the "
                    "in-wheel INNER bound is not certified (outer-only "
                    "mode; run xhat spokes to evaluate incumbents, or "
                    "the wheel cannot close the gap)", True)
        return ok

    def _inwheel_every(self) -> int:
        """Bound-pass cadence in WINDOWS: ``in_wheel_bound_every`` when
        set, else the autotuner's banked verdict (the ``integer`` kind's
        cadence for integer-sweep families, else the ``bound_cadence``
        kind), else every window."""
        every = self.options.get("in_wheel_bound_every")
        if every:
            return max(1, int(every))
        from . import tune

        if self._inwheel_int_sweep_on():
            vi = tune.integer_verdict(self._mega_shape_key(),
                                      settings=self.admm_settings)
            if vi is not None:
                return max(1, int(vi.every))
        v = tune.bound_cadence_verdict(self._mega_shape_key(),
                                       settings=self.admm_settings)
        return max(1, int(v)) if v else 1

    def _consume_inwheel_bounds(self, meas):
        """Install one window's fused bound evidence through the typed
        hub updates (``OuterBoundUpdate``/``InnerBoundUpdate``, source
        char ``'M'`` — megastep) so ``compute_gaps`` termination and the
        gap-vs-wall trace see in-wheel bounds exactly like spoke bounds;
        tracked on the opt too for hub-less runs.  The inner bound is
        offered only when the frozen evaluation was feasible on the
        whole batch (the ``Xhat_Eval`` all-scenarios gate)."""
        if not meas.get("bound_computed"):
            return
        c = self.spcomm
        ob = float(meas["bound_outer"])
        if np.isfinite(ob):
            if ob > getattr(self, "inwheel_outer_bound", -np.inf):
                self.inwheel_outer_bound = ob
            if ob > self.best_bound:
                self.best_bound = ob
            if c is not None and hasattr(c, "OuterBoundUpdate"):
                c.OuterBoundUpdate(ob, char='M')
        # integer-sweep evidence (doc/integer.md): candidate/fixing
        # counters feed the flight recorder and the bench's integer
        # segment — feasible_hits > 0 is the "device sweep supplies
        # incumbents" acceptance signal
        if "int_feas_cands" in meas:
            from .ir import BucketedBatch
            from .solvers import integer as integer_solvers

            th = self._inwheel_int_thresholds() or ()
            # the bucketed kernel evaluates the ladder WITHOUT the slams
            # (nonanticipativity — doc/integer.md); count what actually
            # ran, matching _inwheel_pass_evals' billing arithmetic
            n_cand = len(th) + (
                0 if isinstance(self.batch, BucketedBatch)
                else integer_solvers.N_SLAM)
            _metrics.inc("integer.candidates", n_cand)
            _metrics.inc("integer.feasible_hits",
                         int(meas["int_feas_cands"]))
            _metrics.inc("integer.rcfix_slots",
                         int(meas["int_rcfix_slots"]))
            self._int_best_idx = int(meas["int_best_idx"])
        # the all-scenarios rule with a DTYPE-AWARE slack (single-sourced
        # in solvers.integer.feas_slack with the device argmin's gate):
        # the device computes the mass as probs @ mask in the settings
        # dtype, and an all-feasible f32 sum over S non-representable
        # probabilities (0.1) lands ~S*eps below 1.0 — a bare 1e-9 gate
        # would reject every feasible window on the float32 TPU posture
        from .solvers.integer import feas_slack as _feas_slack

        slack = _feas_slack(self.batch.num_scenarios,
                            self.admm_settings.jdtype())
        feasible = meas["bound_inner_feas"] >= 1.0 - slack
        if feasible and self._inwheel_inner_ok():
            self._offer_inwheel_inner(float(meas["bound_inner_obj"]))
        elif feasible and "int_best_idx" in meas:
            # second-stage-integer families (sizes): the device eval is a
            # RELAXATION of the true second-stage cost — certify the
            # sweep's best candidate by per-scenario host MIPs instead
            self._maybe_integer_inner_mip(int(meas["int_best_idx"]))
        elif not feasible:
            _metrics.inc("megastep.bound_pass_infeasible")
            if "int_best_idx" in meas and not self._inwheel_inner_ok():
                # gate miss on a second-stage-integer family: the LP
                # rescue cannot certify (relaxed second stage) — the MIP
                # escalation leg is the rescue
                self._maybe_integer_inner_mip(int(meas["int_best_idx"]))
            else:
                self._maybe_inwheel_rescue()
        self._maybe_integer_escalation()

    def _offer_inwheel_inner(self, ib: float, char: str = 'M'):
        """Track + typed-install one certified in-wheel incumbent value
        (source char ``'M'`` — megastep; ``'I'`` — integer host
        escalation)."""
        if not np.isfinite(ib):
            return
        if ib < getattr(self, "inwheel_inner_bound", np.inf):
            self.inwheel_inner_bound = ib
        c = self.spcomm
        if c is not None and hasattr(c, "InnerBoundUpdate"):
            c.InnerBoundUpdate(ib, char=char)

    def _maybe_inwheel_rescue(self):
        """Cadence gate in front of :meth:`_inwheel_host_rescue`: fire on
        the first feasibility-gate miss, then every
        ``in_wheel_rescue_every``-th miss (default 4 — a rescue is S host
        LPs, so it must not run every window on big-S wheels).  A rescue
        that DECLINES (the candidate is genuinely infeasible — the
        iter-1 consensus usually is) retries with a growing backoff
        (next miss, then +2, ... capped at the cadence) instead of
        spending a full cadence slot: the earliest windows fail
        together, and one early decline must not starve the wheel of
        its first certified incumbent for ``every`` more windows.
        ``in_wheel_host_rescue=False`` disables."""
        if not self.options.get("in_wheel_host_rescue", True):
            return
        if not self._inwheel_inner_ok():
            return
        every = max(1, int(self.options.get("in_wheel_rescue_every", 4)))
        miss = getattr(self, "_inwheel_gate_misses", 0)
        self._inwheel_gate_misses = miss + 1
        if miss < getattr(self, "_inwheel_next_rescue", 0):
            return
        ib = self._inwheel_host_rescue()
        if ib is None:
            declines = getattr(self, "_inwheel_rescue_declines", 0) + 1
            self._inwheel_rescue_declines = declines
            self._inwheel_next_rescue = miss + min(declines, every)
        else:
            self._inwheel_next_rescue = miss + every
            self._offer_inwheel_inner(ib)

    def _inwheel_host_rescue(self):
        """Host-EXACT inner-bound rescue — the straggler-rescue
        philosophy applied to the certification path.  Stiff families
        (UC's pmin/ramp coupling at fixed commitments) stall batched
        ADMM on the clamped evaluation even at refresh grade, so the
        fused pass's ``Xhat_Eval`` gate keeps declining; here the SAME
        candidate (the single-sourced ``xbar_candidate`` rule: rounded
        at the in-wheel threshold, clipped to the nonant box) is
        evaluated by per-scenario host solves — an LP, or the exact host
        QP when the scenario carries a quadratic objective (the
        straggler rescue's own split; the LP-only HiGHS wrapper raises
        on q2) — so the expected objective is a certified incumbent.
        Integer nonants are FIXED at their rounded values and
        :meth:`_inwheel_inner_ok` guarantees no other integer columns,
        so the value is the true candidate value, not a relaxation.
        Zero spoke threads, zero device programs.  Returns the bound, or
        None when any scenario is genuinely infeasible at the candidate
        — or when the host solver errors: a rescue failure must decline,
        never kill the wheel."""
        from .cylinders.xhatxbar_bounder import clamp_candidate
        from .ir import BucketedBatch
        from .solvers import scipy_backend

        if getattr(self, "_host_state_stale", False):
            self._sync_host_state()
        _metrics.inc("megastep.bound_rescues")
        thr = self._inwheel_threshold()
        b = self.batch
        xbars = np.asarray(self.xbars, dtype=float)
        eval_clamped = self._inwheel_eval_candidate_host
        try:
            if self._inwheel_int_sweep_on():
                # the batched integer posture: sweep the SAME rounding
                # ladder the device evaluates, device-preferred order
                # (its best index first, then the SLAM-up slam — the
                # most conservative commit, usually the first feasible
                # on under-converged consensus), first feasible wins —
                # the host leg of the best-of-C recovery
                from .solvers import integer as integer_solvers

                th = self._inwheel_int_thresholds() or ()
                cands = integer_solvers.host_candidates(self, th)
                order = list(range(len(cands)))
                slam_up = len(th)      # first slam after the ladder
                pref = [min(getattr(self, "_int_best_idx", 0),
                            len(cands) - 1), slam_up]
                order = list(dict.fromkeys(pref + order))
                for ci in order:
                    total = eval_clamped(np.asarray(cands[ci], float))
                    if total is not None:
                        # a host-CERTIFIED sweep candidate: the device
                        # ladder supplied the incumbent, host LPs
                        # certified it (doc/integer.md counter contract)
                        _metrics.inc("integer.feasible_hits")
                        return total
                return None
            # legacy single-candidate path: the candidate rule applied
            # per part (bucketed batches carry is_int per bucket)
            cand = np.array(xbars, copy=True)
            parts = (b.buckets if isinstance(b, BucketedBatch)
                     else [(np.arange(b.num_scenarios), b)])
            for idx, sub in parts:
                rows = np.asarray(idx)
                cand[rows], _, _ = clamp_candidate(
                    sub, sub.tree.nonant_indices, xbars[rows], thr)
            return eval_clamped(cand)
        except Exception as e:     # a failed rescue declines, loudly
            global_toc(f"in-wheel host rescue failed ({e!r}) — declined",
                       True)
            return None

    def _inwheel_eval_candidate_host(self, cand_sk):
        """Expected objective of ONE fixed candidate via per-scenario
        host solves — the host-EXACT certification leg shared by the
        rescue and the escalation heuristics (None = any scenario
        infeasible).  LP scenarios through HiGHS, quadratic ones through
        the exact host QP (the straggler rescue's split)."""
        from .ir import BucketedBatch
        from .solvers import scipy_backend

        b = self.batch
        probs = np.asarray(self.probs, dtype=float)
        cand_sk = np.asarray(cand_sk, dtype=float)
        total = 0.0
        parts = (b.buckets if isinstance(b, BucketedBatch)
                 else [(np.arange(b.num_scenarios), b)])
        for idx, sub in parts:
            rows = np.asarray(idx)
            lb = np.array(sub.lb, copy=True)
            ub = np.array(sub.ub, copy=True)
            nid = sub.tree.nonant_indices
            lb[:, nid] = cand_sk[rows]
            ub[:, nid] = cand_sk[rows]
            objs = []
            for s in range(sub.num_scenarios):
                q2s = np.asarray(sub.q2[s])
                if q2s.any():
                    r = scipy_backend.solve_qp_with_duals(
                        sub.c[s], q2s, sub.A[s], sub.cl[s],
                        sub.cu[s], lb[s], ub[s], const=sub.const[s])
                else:
                    r = scipy_backend.solve_lp(
                        sub.c[s], sub.A[s], sub.cl[s], sub.cu[s],
                        lb[s], ub[s], const=sub.const[s])
                objs.append(r.obj)
            objs = np.asarray(objs, dtype=float)
            if not np.isfinite(objs).all():
                return None
            total += float(probs[rows] @ objs)
        return total

    # ---- integer host escalation tier (doc/integer.md) ----------------------
    def _integer_budget(self):
        """The wheel's shared :class:`~tpusppy.solvers.integer.
        EscalationBudget` (lazily built; ``integer_escalation_budget_s``
        option, default 30 host-seconds): every host escalation — the
        gap-ranked MILP lift AND the candidate MIP certification — draws
        from this one pool, so the host tail is bounded per wheel."""
        b = getattr(self, "_int_budget", None)
        if b is None:
            from .solvers.integer import EscalationBudget

            b = self._int_budget = EscalationBudget(
                float(self.options.get("integer_escalation_budget_s",
                                       30.0)))
        return b

    def _integer_escalation_on(self) -> bool:
        """Whether the gap-ranked host escalation tier is armed: the
        ``integer_escalation`` option (default on), in-wheel
        certification running, an integer homogeneous family (the MILP
        lift iterates ``batch.A[s]`` — bucketed batches have no global
        A tensor)."""
        if not self.options.get("integer_escalation", True):
            return False
        if not self._inwheel_on():
            return False
        from .ir import BucketedBatch

        b = self.batch
        if isinstance(b, BucketedBatch):
            return False
        return bool(np.asarray(b.is_int).any())

    def _integer_gap_target(self):
        """(rel_gap, abs_gap) certification targets the escalation tier
        aims for — the hub's when attached, else the opt options'."""
        opts = getattr(self.spcomm, "options", None) or {}
        return (opts.get("rel_gap", self.options.get("rel_gap")),
                opts.get("abs_gap", self.options.get("abs_gap")))

    def _integer_bounds_now(self):
        """(inner, outer) best-known bounds across the in-wheel tracking
        and the hub (when attached)."""
        ib = getattr(self, "inwheel_inner_bound", np.inf)
        ob = getattr(self, "inwheel_outer_bound", -np.inf)
        c = self.spcomm
        if c is not None:
            ib = min(ib, getattr(c, "BestInnerBound", np.inf))
            ob = max(ob, getattr(c, "BestOuterBound", -np.inf))
        return ib, ob

    def _maybe_integer_inner_mip(self, best_idx: int):
        """Certify the device sweep's best candidate by per-scenario
        host MIPs — the inner-bound escalation leg for families with
        SECOND-STAGE integers (the device evaluation relaxes those
        columns, so ``_inwheel_inner_ok`` rightly refuses it; fixing the
        nonants at the candidate and solving each scenario MIP exactly
        IS an incumbent).  Cadence-gated like the host rescue (S host
        MIPs must not run every window), budgeted from the shared
        escalation pool, installed under source char ``'I'``."""
        if not self.options.get("in_wheel_host_rescue", True):
            return
        if not self._integer_escalation_on():
            return
        every = max(1, int(self.options.get("in_wheel_rescue_every", 4)))
        cnt = getattr(self, "_int_mip_calls", 0)
        self._int_mip_calls = cnt + 1
        if cnt % every:
            return
        from .solvers import integer as integer_solvers

        budget = self._integer_budget()
        if budget.remaining <= 0.05:
            return
        try:
            th = self._inwheel_int_thresholds() or ()
            cands = integer_solvers.host_candidates(self, th)
            # device-preferred order, then the SLAM-up slam, then the
            # rest — one infeasible best-index candidate must not end
            # the round (the LP rescue's ladder-sweep discipline)
            bi = min(max(int(best_idx), 0), len(cands) - 1)
            order = list(dict.fromkeys(
                [bi, len(th)] + list(range(len(cands)))))
            ib = None
            for ci in order:
                if budget.remaining <= 0.05:
                    break
                ib = integer_solvers.escalate_inner(self, budget,
                                                    cands[ci])
                if ib is not None:
                    break
        except Exception as e:   # a failed escalation declines, loudly
            global_toc(f"integer inner escalation failed ({e!r}) — "
                       "declined", True)
            return
        if ib is not None:
            # a MIP-certified sweep candidate is a sweep-supplied
            # incumbent (the doc/integer.md counter contract)
            _metrics.inc("integer.feasible_hits")
            self._offer_inwheel_inner(ib, char='I')

    def _maybe_integer_escalation(self):
        """ONE gap-gated round of the gap-ranked host MILP escalation
        (doc/integer.md tier 3): when the wheel's certified gap still
        misses its target and integrality gap remains, spend a slice of
        the shared HiGHS budget lifting the per-scenario LP certificates
        with the LARGEST estimated remaining gap first, and install the
        lifted outer bound under source char ``'I'``.  Fires on the
        ``integer_escalation_every`` window cadence (default 4) once an
        incumbent exists; an exhausted budget leaves every untouched
        scenario on its LP certificate (budget-elastic by
        construction)."""
        if not self._integer_escalation_on():
            return
        budget = self._integer_budget()
        if budget.remaining <= 0.05:
            return
        ib, ob = self._integer_bounds_now()
        if not np.isfinite(ib):
            return          # no incumbent yet: nothing to close against
        rel, abs_ = self._integer_gap_target()
        gap = ib - ob
        relgap = (gap / (abs(ob) or 1.0)) if np.isfinite(ob) else np.inf
        hit = ((rel is not None and relgap <= float(rel))
               or (abs_ is not None and gap <= float(abs_)))
        if hit or (rel is None and abs_ is None):
            return          # already certified (or no target to chase)
        every = max(1, int(self.options.get("integer_escalation_every",
                                            4)))
        cnt = getattr(self, "_int_esc_calls", 0)
        self._int_esc_calls = cnt + 1
        if cnt % every:
            return
        from .solvers import integer as integer_solvers

        upper = None
        try:
            th = self._inwheel_int_thresholds()
            if th is not None:
                cands = integer_solvers.host_candidates(self, th)
                bi = min(getattr(self, "_int_best_idx", 0),
                         len(cands) - 1)
                u, ok = integer_solvers.candidate_upper_perscen(
                    self, cands[bi])
                upper = np.where(ok, u, np.inf)
        except Exception:
            upper = None    # ranking falls back to probability order
        try:
            ob2, X = integer_solvers.escalate_outer(
                self, budget,
                want_s=self.options.get("integer_escalation_slice_s"),
                upper_perscen=upper, want_x=True)
        except Exception as e:
            global_toc(f"integer outer escalation failed ({e!r}) — "
                       "declined", True)
            return
        if ob2 is None or not np.isfinite(ob2):
            return
        if ob2 > getattr(self, "inwheel_outer_bound", -np.inf):
            self.inwheel_outer_bound = ob2
        if ob2 > self.best_bound:
            self.best_bound = ob2
        c = self.spcomm
        if c is not None and hasattr(c, "OuterBoundUpdate"):
            c.OuterBoundUpdate(ob2, char='I')
        self._integer_lift_incumbents(X, budget)

    def _integer_lift_incumbents(self, X, budget):
        """Lagrangian-heuristic incumbent recovery from the MILP lift's
        per-scenario minimizers: when every scenario was lifted
        gap-closed, the rows' per-node consensus (rounded) and SLAM-up
        slam are natural integer candidates — the subproblem minima
        under a near-converged W nearly agree, so their consensus is
        usually feasible and far tighter than a relaxation-consensus
        rounding.  Certified host-exact (LPs, or per-scenario MIPs for
        second-stage-integer families), installed under ``'I'``."""
        if X is None or np.isnan(np.asarray(X)[:, 0]).any():
            return
        from .cylinders.xhatxbar_bounder import xbar_candidate
        from .extensions.xhatbase import slam_cache
        from .solvers import integer as integer_solvers

        try:
            nid = self.tree.nonant_indices
            xk = np.asarray(X, dtype=float)[:, nid]
            ints = integer_solvers.int_mask_rows(self)
            lo = np.asarray(self.batch.lb)[:, nid]
            hi = np.asarray(self.batch.ub)[:, nid]
            cands = [xbar_candidate(self, xk, threshold=0.5)]
            up = slam_cache(self, xk, how="max")
            cands.append(np.clip(
                np.where(ints, np.ceil(up - 1e-9), up), lo, hi))
            inner_ok = self._inwheel_inner_ok()
            best = None
            for cand in cands:
                if inner_ok:
                    if budget.remaining <= 0.05:
                        break
                    with budget.timed():
                        ib = self._inwheel_eval_candidate_host(cand)
                else:
                    ib = integer_solvers.escalate_inner(self, budget,
                                                        cand)
                if ib is not None and (best is None or ib < best):
                    best = ib
            # strongest host heuristic last: the restricted-EF dive on
            # the minimizers' agreement pattern (certified by
            # construction — any feasible restricted-EF solution is an
            # EF incumbent)
            ib = integer_solvers.restricted_ef_incumbent(self, X, budget)
            if ib is not None and (best is None or ib < best):
                best = ib
            if best is not None:
                _metrics.inc("integer.feasible_hits")
                self._offer_inwheel_inner(best, char='I')
        except Exception as e:
            global_toc(f"integer lift-incumbent recovery failed ({e!r}) "
                       "— declined", True)

    def _mega_shape_key(self):
        """The autotuner shape key: (S, n, m), or the tuple of per-bucket
        (S_b, n_b, m_b) for a bucketed batch (per-bucket verdict keys —
        an S=1000 verdict can never serve an S=10000 family)."""
        from .ir import BucketedBatch

        b = self.batch
        if isinstance(b, BucketedBatch):
            return tuple((idx.size, sub.num_vars, sub.num_rows)
                         for idx, sub in b.buckets)
        return (b.num_scenarios, b.num_vars, b.num_rows)

    def _megastep_window(self, k, max_iters, convthresh, n_req):
        """One megastep window starting at iteration ``k``: returns
        ``(executed, conv_hit)`` — ``executed == 0`` means the slot was
        not megastep-ready (stale/aged factors, a dirty previous
        measurement) and the caller must run a legacy iteration, which
        refreshes/rescues and restores readiness."""
        refresh_every = self._refresh_every()
        if not self._mega_slots_ready(refresh_every):
            return 0, False
        # previous measurement must be clean — the serial frozen path's
        # acceptance test; a dirty iterate routes through the legacy
        # iteration (adaptive refresh + straggler rescue)
        pri, dua = self.pri_res, self.dua_res
        if pri is None or dua is None:
            return 0, False
        _, tol_qp = self._straggler_tols()
        if not bool(np.all((pri <= tol_qp) & (dua <= tol_qp))):
            # mirror the in-scan acceptance's all-done escape: an
            # eps-converged batch is clean regardless of the residual
            # ladder, and a window accepted that way may carry
            # non-finite residuals on divergence-frozen scenarios —
            # without the escape one frozen scenario would disable the
            # megakernel for the rest of the run
            if not getattr(self, "_last_all_done", False):
                return 0, False
        n_live = min(n_req, refresh_every - self._mega_age(),
                     max_iters - k + 1)
        if n_live < 1:
            return 0, False
        # opt-in measured N (the tune.py megastep stage): the first
        # eligible window runs the three probe windows through the normal
        # machinery — real iterations, applied normally — and banks the
        # verdict (persistent via TPUSPPY_TUNE_CACHE) for SUBSEQUENT runs
        # of this shape; without the knob, auto-N stays cadence-derived
        if (self.options.get("megastep_autotune")
                and not getattr(self, "_mega_tuned", False)
                and n_live >= 10):
            self._mega_tuned = True
            from . import tune

            if tune.megastep_verdict(self._mega_shape_key(),
                                     settings=self.admm_settings) is None:
                prog = {"k": k, "executed": 0}

                def run_window(nl):
                    # a probe must never run past convergence: once the
                    # threshold fired, later windows do nothing (the
                    # serial protocol would have broken the loop)
                    if self.conv is not None and self.conv < convthresh:
                        return 0
                    # a rejected probe exhausts the factors (refresh_hit
                    # ages them out); a further timed window from the
                    # same state would deterministically re-reject — bail
                    # like the normal window's readiness gate does
                    if self._mega_age() >= refresh_every:
                        return 0
                    m = self._megastep_dispatch(n_req, nl, convthresh)
                    ex = m["executed"]
                    if ex:
                        self._apply_megastep_meas(prog["k"], m)
                        prog["k"] += ex
                        prog["executed"] += ex
                    return ex

                tune.autotune_megastep(
                    run_window, self._mega_shape_key(), n_cap=n_req,
                    settings=self.admm_settings)
                return prog["executed"], bool(self.conv < convthresh)
        bound_live = None
        if self._inwheel_on():
            wc = getattr(self, "_mega_window_count", 0)
            self._mega_window_count = wc + 1
            # opt-in measured integer stage (tune.py "integer" kind):
            # two real probe windows — one with the batched integer
            # sweep, one plain — measure the sweep's marginal cost, and
            # the banked (K, cadence) verdict serves this and later runs
            # of the shape.  A verdict can TRUNCATE the ladder, which is
            # a DIFFERENT compiled program: the megastep fn cache is
            # dropped so the next window rebuilds at the picked K.
            if (self._inwheel_int_sweep_on()
                    and self.options.get("in_wheel_int_autotune")
                    and not self.options.get("in_wheel_int_thresholds")
                    and not getattr(self, "_int_tuned", False)):
                self._int_tuned = True
                from . import tune

                if tune.integer_verdict(
                        self._mega_shape_key(),
                        settings=self.admm_settings) is None:
                    prog = {"k": k, "executed": 0}

                    def run_iwin(int_live):
                        if self.conv is not None \
                                and self.conv < convthresh:
                            return 0
                        nl = min(n_req,
                                 refresh_every - self._mega_age(),
                                 max_iters - prog["k"] + 1)
                        if nl < 1:
                            return 0
                        m = self._megastep_dispatch(
                            n_req, nl, convthresh,
                            bound_live=bool(int_live))
                        self._consume_inwheel_bounds(m)
                        ex = m["executed"]
                        if ex:
                            self._apply_megastep_meas(prog["k"], m)
                            prog["k"] += ex
                            prog["executed"] += ex
                        return ex

                    from .solvers.integer import DEFAULT_THRESHOLDS

                    tune.autotune_integer(
                        run_iwin, self._mega_shape_key(),
                        settings=self.admm_settings,
                        k_full=len(self._inwheel_int_thresholds()
                                   or DEFAULT_THRESHOLDS))
                    self._mega_fn_cache = {}
                    return prog["executed"], bool(self.conv < convthresh)
            # opt-in measured cadence (the tune.py bound-cadence stage):
            # two real probe windows — one with the fused bound pass, one
            # without — measure its marginal cost, and the banked verdict
            # (persistent via TPUSPPY_TUNE_CACHE) serves this and later
            # runs of the shape; probes are real iterations, applied
            # normally, so warmup work is never wasted
            if (self.options.get("in_wheel_bound_autotune")
                    and not self.options.get("in_wheel_bound_every")
                    and not getattr(self, "_bound_tuned", False)):
                self._bound_tuned = True
                from . import tune

                if tune.bound_cadence_verdict(
                        self._mega_shape_key(),
                        settings=self.admm_settings) is None:
                    prog = {"k": k, "executed": 0}

                    def run_bwin(bl):
                        if self.conv is not None and self.conv < convthresh:
                            return 0
                        nl = min(n_req, refresh_every - self._mega_age(),
                                 max_iters - prog["k"] + 1)
                        if nl < 1:
                            return 0
                        m = self._megastep_dispatch(n_req, nl, convthresh,
                                                    bound_live=bl)
                        # same contract as the main path: an executed==0
                        # (first-iterate-rejected) window's bound
                        # evidence still certifies the INCOMING state
                        self._consume_inwheel_bounds(m)
                        ex = m["executed"]
                        if ex:
                            self._apply_megastep_meas(prog["k"], m)
                            prog["k"] += ex
                            prog["executed"] += ex
                        return ex

                    tune.autotune_bound_cadence(
                        run_bwin, self._mega_shape_key(),
                        settings=self.admm_settings)
                    return prog["executed"], bool(self.conv < convthresh)
            bound_live = (wc % self._inwheel_every() == 0)
        meas = self._megastep_dispatch(n_req, n_live, convthresh,
                                       bound_live=bound_live)
        if bound_live is not None:
            # bound evidence is valid on whatever state the window ended
            # with — including an executed == 0 (first-iterate-rejected)
            # window, whose bounds certify the INCOMING state
            self._consume_inwheel_bounds(meas)
        executed = meas["executed"]
        if executed == 0:
            # the window's FIRST iterate failed the in-scan acceptance
            # test (discarded; _megastep_solve exhausted the factors age)
            # — the caller's legacy iteration refreshes, as serial would
            return 0, False
        self._apply_megastep_meas(k, meas)
        # a short window is NOT convergence when the in-scan acceptance
        # test ended it (refresh_hit): the loop continues through the
        # legacy refresh instead
        conv_hit = bool(self.conv < convthresh)
        return executed, conv_hit

    def _apply_megastep_meas(self, k, meas):
        """Install one megastep window's packed measurement as the host PH
        state (copies: the unpack returns views into one fetched vector).

        A LEAN measurement (device-resident posture, ``ph_device_state``)
        carries no x/W/xbars blocks: the (S, K)/(S, n) mirrors stay where
        they are and are marked STALE — :meth:`_sync_host_state` refreshes
        them with one explicit billed fetch at the next checkpoint/
        termination/refresh boundary.  The per-scenario residual
        diagnostics and the scalar stats install either way, so the
        readiness gates and the convergence test never read stale data."""
        executed = meas["executed"]
        if "W" in meas:
            self.W = np.array(meas["W"], dtype=float)
            self.xbars = np.array(meas["xbars"], dtype=float)
            self.local_x = np.array(meas["x"], dtype=float)
        else:
            self._host_state_stale = True
        self.pri_res = np.array(meas["pri"], dtype=float)
        self.dua_res = np.array(meas["dua"], dtype=float)
        self._last_all_done = bool(np.all(meas["done"]))
        if "W" in meas:
            # xsqbars is not packed (no in-scan consumer): recompute the
            # second moment host-side from the window's final x so PH
            # state stays internally consistent — checkpoints capture it,
            # and heuristics read it between windows (xbars comes off the
            # device; the redundant E[x] half costs one einsum per
            # WINDOW).  The lean posture defers this to the boundary sync
            _, self.xsqbars = self._node_avgs(self._nonants_cached())
        self.conv = float(meas["conv"][executed - 1])
        self._iter = k + executed - 1
        self._bump_state_version()
        global_toc(
            f"PH megastep {k}..{self._iter} conv {self.conv:.6e}",
            self.options.get("display_progress", False),
        )

    def _sync_host_state(self):
        """Refresh the (S, K)/(S, n) host mirrors from the device-resident
        wheel state — ONE explicit billed fetch (``phstate.boundary_
        fetches``), called only at window boundaries that actually READ
        host state: checkpoint capture, hub payloads, the legacy refresh
        fallback, and loop termination.  No-op when the mirrors are
        already authoritative, so the legacy (full-pack) path never pays
        anything here."""
        st = getattr(self, "_dev_state", None)
        if st is None or not getattr(self, "_host_state_stale", False):
            self._host_state_stale = False
            return
        from .obs import metrics as _metrics
        from .solvers import hostsync

        W, xbars, x = hostsync.fetch((st.W, st.xbars, st.x))
        self.W = np.array(W, dtype=float)
        self.xbars = np.array(xbars, dtype=float)
        self.local_x = np.array(x, dtype=float)
        self._host_state_stale = False
        _, self.xsqbars = self._node_avgs(self._nonants_cached())
        self._bump_state_version()
        _metrics.inc("phstate.boundary_fetches")

    def _spcomm_needs_host_state(self) -> bool:
        """Whether the imminent ``spcomm.sync()`` will read host PH state:
        W/nonant spoke payloads, or a due checkpoint capture (which must
        find fresh mirrors — the capture itself is pinned zero-fetch)."""
        c = self.spcomm
        if c is None:
            return False
        if getattr(c, "has_w_spokes", False) or \
                getattr(c, "has_nonant_spokes", False):
            return True
        due = getattr(c, "checkpoint_due", None)
        return bool(due and due(self._iter))

    def iterk_loop(self):
        """Main PH loop (phbase.py:875-979).

        When the device-resident wheel megakernel is eligible
        (:meth:`_megastep_request`), iterations run in megastep WINDOWS:
        one donated N-iteration device dispatch + ONE packed fetch per
        window (doc/pipeline.md), with hub/spoke sync, termination checks
        and checkpoint capture at window boundaries.  The legacy
        per-iteration body below remains the refresh/rescue path (and the
        whole path, under ``ADMMSettings.megastep = 1``).
        """
        convthresh = self.options.get("convthresh", 0.0)
        max_iters = self.options["PHIterLimit"]
        # resumed runs continue the ITERATION COUNT from the checkpoint:
        # the limit stays the total-budget knob it always was
        start = int(getattr(self, "_iter_base", 0)) + 1
        mega_n = self._megastep_request()
        k = start
        while k <= max_iters:
            if mega_n:
                executed, conv_hit = self._megastep_window(
                    k, max_iters, convthresh, mega_n)
                if executed:
                    k += executed
                    if self.spcomm is not None:
                        # device-resident posture: refresh the host
                        # mirrors BEFORE a sync that reads them (payload
                        # spokes, a due checkpoint capture) — the capture
                        # itself stays pinned zero-fetch
                        if self._spcomm_needs_host_state():
                            self._sync_host_state()
                        self.spcomm.sync()
                        self.extobject.enditer_after_sync()
                        if self.spcomm.is_converged():
                            global_toc("Cylinder termination", True)
                            break
                    if conv_hit:
                        global_toc(
                            f"Convergence threshold {convthresh} reached "
                            f"at iter {self._iter}",
                            self.options.get("display_progress", False),
                        )
                        break
                    continue
            # the legacy body assembles the augmented objective from the
            # host mirrors — they must be authoritative (no-op unless the
            # device-resident posture left them stale)
            self._sync_host_state()
            k = self._iterk_one(k, convthresh)
            if k is None:
                break
            k += 1
        # loop exit (termination, convergence, iteration limit): whatever
        # reads follow — post_loops' Eobjective, the final checkpoint
        # capture, bench metrics — get authoritative host state
        self._sync_host_state()

    def _iterk_one(self, k, convthresh):
        """One legacy PH iteration (the pre-megakernel loop body).
        Returns ``k`` to continue, or None to terminate the loop."""
        self._iter = k
        # one span per PH iteration on the cylinder's own track
        # (the wheel spinner names cylinder threads; solo runs land
        # on "main") — the hub/spoke timeline rows of the trace
        with _trace.phase("ph_iter") as _sp:
            self.extobject.miditer()
            self.solve_ph_subproblems()
            self.Compute_Xbar()
            self.Update_W()
            self.conv = self.convergence_diff()
            if _trace.enabled():   # payload dicts only when tracing
                _sp.add(iter=k, conv=self.conv)
            self.extobject.enditer()
        if self.spcomm is not None:
            self.spcomm.sync()
            self.extobject.enditer_after_sync()
            if self.spcomm.is_converged():
                global_toc("Cylinder termination", True)
                return None
        global_toc(
            f"PH iter {k} conv {self.conv:.6e} Eobj {self.Eobjective():.4f}",
            self.options.get("display_progress", False),
        )
        if self.conv is not None and self.conv < convthresh:
            global_toc(
                f"Convergence threshold {convthresh} reached at iter {k}",
                self.options.get("display_progress", False),
            )
            return None
        if self.ph_converger is not None and self.ph_converger.is_converged():
            global_toc(f"User converger triggered at iter {k}", True)
            return None
        return k

    def post_loops(self) -> float:
        """Final expected objective (phbase.py:982-1037)."""
        self.extobject.post_everything()
        return self.Eobjective()
