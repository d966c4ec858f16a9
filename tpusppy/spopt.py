"""SPOpt: batched subproblem solving and expectation reductions.

TPU-native analogue of ``mpisppy/spopt.py:23-868``.  The reference's
``solve_one``/``solve_loop`` (spopt.py:85-307) — a serial per-rank loop handing
each Pyomo model to an external solver — becomes ONE vmapped ADMM call on the
HBM-resident batch, warm-started between calls (the persistent-solver analogue,
spopt.py:129-144).  Expectations (``Eobjective``/``Ebound``/``feas_prob``,
spopt.py:310-466) are probability-weighted contractions; under a mesh they are
psums on the scenario axis.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import threading
import time

import numpy as np

from . import global_toc
from .obs import metrics as _metrics
from .obs import trace as _trace
from .spbase import SPBase
from .solvers import admm, hostsync
from .solvers import turns as _turns

_BATCH_TOKENS = itertools.count(1)


def _batch_token(b):
    """Monotone identity token for cache keys: unlike ``id()`` it is never
    reused after the batch is collected, and unlike the object itself it is
    safely ``==``-comparable inside key tuples (dataclass ``__eq__`` on
    numpy fields raises)."""
    tok = getattr(b, "_sig_token", None)
    if tok is None:
        tok = next(_BATCH_TOKENS)
        b._sig_token = tok
    return tok


# Content-keyed device cache for big constraint matrices.  Every cylinder in
# a wheel builds its own ScenarioBatch from the same scenario_creator, so
# without content sharing each one uploads (and keeps) its own device copy
# of the identical shared (m, n) A — ~800 MB x n_cylinders at reference UC
# shapes, a large slice of one chip's HBM.  Keyed by sha1 of the bytes;
# tiny LRU since distinct big matrices rarely coexist.  The lock matters:
# wheel cylinders are threads that reach their first solve near-
# simultaneously, and both the hash and the host->device upload release
# the GIL — unlocked, every thread would miss and upload its own copy.
_DEV_A_CACHE: dict = collections.OrderedDict()
_DEV_A_LOCK = threading.Lock()


def _cached_dev_A(A_np, tag_key, build):
    """Content-keyed device-A cache insert/lookup with the shared eviction
    policy: keep the single newest prior same-(shape, dtype, kind) entry
    (cut rounds mutate the shared A; round k and k-1 coexist) and a
    4-entry LRU cap — stale versions must never strand HBM, on the dense
    OR the sparse path."""
    import hashlib

    with _DEV_A_LOCK:
        digest = hashlib.sha1(
            memoryview(np.ascontiguousarray(A_np))).hexdigest()
        key = (digest,) + tag_key
        dev = _DEV_A_CACHE.pop(key, None)
        if dev is None:
            same = [k for k in _DEV_A_CACHE if k[1:] == key[1:]]
            for k in same[:-1]:
                del _DEV_A_CACHE[k]
            dev = build()
        _DEV_A_CACHE[key] = dev         # re-insert = LRU touch
        while len(_DEV_A_CACHE) > 4:
            _DEV_A_CACHE.popitem(last=False)
        return dev


def _device_A(A_src, dt, sparse="auto"):
    import jax.numpy as jnp

    from .solvers.sparse import SparseA, should_sparsify

    A_np = np.asarray(A_src)
    # large very-sparse SHARED matrices upload as SparseA: gather/
    # segment-sum matvecs + block/Woodbury structured KKT (see
    # tpusppy/solvers/sparse.py) — the same policy the sharded rate path
    # applies in parallel/sharded.shard_batch.  (Checked before the
    # small-matrix early return so tests can force sparse=True on small
    # families.)
    if A_np.ndim == 2 and (sparse is True or
                           (sparse == "auto" and should_sparsify(A_np))):
        return _cached_dev_A(
            A_np, (A_np.shape, str(dt), "sparse"),
            lambda: SparseA.from_dense(A_np, jnp.dtype(dt), structure=True))
    if A_np.nbytes < 16 << 20:          # small matrices: not worth hashing
        return jnp.asarray(A_np, dt)
    return _cached_dev_A(A_np, (A_np.shape, str(dt)),
                         lambda: jnp.asarray(A_np, dt))


def clear_device_caches():
    """Release the content-keyed device-A cache (e.g. between benchmark
    phases that need the HBM back; ``jax.clear_caches()`` doesn't reach
    module-level array references)."""
    with _DEV_A_LOCK:
        _DEV_A_CACHE.clear()


def _np_dual_objective(q, A, cl, cu, lb, ub, y, x_hint, margin_scale=100.0):
    """Single-scenario numpy twin of :func:`admm.dual_objective` (LP case),
    used by the straggler rescue to validate host duals."""
    base, g = _np_dual_cut(q, A, cl, cu, lb, ub, y, x_hint,
                           np.zeros(q.shape[0], dtype=bool), margin_scale)
    return base


def _np_dual_cut(q, A, cl, cu, lb, ub, y, x_hint, clamp_mask,
                 margin_scale=100.0):
    """Single-scenario numpy twin of :func:`admm.dual_cut` (LP case):
    ``Q(x̂') >= base + g[clamp].x̂'`` for any y (weak duality)."""
    big = admm.BIG
    cl = np.clip(np.nan_to_num(cl, nan=-big), -big, big)
    cu = np.clip(np.nan_to_num(cu, nan=big), -big, big)
    fin_cl, fin_cu = cl > -big / 2, cu < big / 2
    fin_lb, fin_ub = lb > -big / 2, ub < big / 2
    y = np.where(~fin_cu & (y > 0), 0.0, y)
    y = np.where(~fin_cl & (y < 0), 0.0, y)
    row = (-np.maximum(y, 0) * np.where(fin_cu, cu, 0.0)
           - np.minimum(y, 0) * np.where(fin_cl, cl, 0.0)).sum()
    X = margin_scale * (1.0 + np.abs(x_hint).max())
    L = np.where(fin_lb, np.maximum(lb, -big), -X)
    U = np.where(fin_ub, np.minimum(ub, big), X)
    g = q + A.T @ y
    term = g * np.where(g >= 0, L, U)
    base = float(row + np.where(clamp_mask, 0.0, term).sum())
    return base, g


def batch_solve_dispatch(b, q, q2, cl, cu, lb, ub, settings, warm=None,
                         rows=None, tile=1):
    """One-shot batched solve honoring shared-A.

    Callers pass their (possibly row-sliced / replica-tiled) objective and
    bound arrays; the constraint matrix is taken from the batch: the single
    (m, n) ``A_shared`` when present (NEVER materializing the (S, m, n)
    broadcast view — that is the memory wall shared-A exists to break),
    else the dense per-scenario tensor sliced by ``rows`` / repeated
    ``tile`` times to match the leading axis.
    """
    from .solvers import shared_admm

    if getattr(b, "A_shared", None) is not None:
        return shared_admm.adaptive_in_turns(
            q, q2, b.A_shared, cl, cu, lb, ub, settings=settings, warm=warm)
    A = b.A if rows is None else b.A[rows]
    if tile > 1:
        A = np.repeat(A, tile, axis=0)
    return admm.solve_batch(q, q2, A, cl, cu, lb, ub, settings=settings,
                            warm=warm)


def dispatch_A(b):
    """The A to hand device code: the single (m, n) shared matrix when the
    batch has one (never the (S, m, n) broadcast view), else the dense
    per-scenario tensor."""
    A_shared = getattr(b, "A_shared", None)
    return b.A if A_shared is None else A_shared


def mega_arrays_for_batch(b, dt, sparse="auto"):
    """Device-resident :class:`~tpusppy.parallel.sharded.PHArrays` for
    one HOMOGENEOUS ScenarioBatch, built WITHOUT an opt instance — the
    standalone twin of :meth:`SPOpt._mega_arrays` for callers that own
    no PHBase (the continuous-batching runner,
    :mod:`tpusppy.service.batching`, builds one per tenant slot).  Rides
    the same content-keyed device-A cache (``_device_A``), so K tenants
    of one family with identical shared A hold ONE device copy."""
    import jax.numpy as jnp

    from .parallel import sharded

    A_shared = getattr(b, "A_shared", None)
    A_src = b.A if A_shared is None else A_shared
    if A_shared is None:
        sparse = False            # per-scenario A: dense batched path
    S = b.num_scenarios
    tree = b.tree
    return sharded.PHArrays(
        c=jnp.asarray(b.c, dt), q2=jnp.asarray(b.q2, dt),
        A=_device_A(A_src, dt, sparse=sparse),
        cl=jnp.asarray(b.cl, dt), cu=jnp.asarray(b.cu, dt),
        lb=jnp.asarray(b.lb, dt), ub=jnp.asarray(b.ub, dt),
        const=jnp.asarray(np.broadcast_to(b.const, (S,)), dt),
        probs=jnp.asarray(tree.scen_prob, dt),
        onehot=jnp.asarray(tree.onehot_sk_n(), dt),
        nid_sk=jnp.asarray(tree.nid_sk(), jnp.int32))


def bucket_shared(sub) -> bool:
    """Whether a bucket's sub-batch runs the SHARED-A engine.  Sharing
    must be real: a singleton sub-batch trivially detects identity-shared
    A (``all(p.A is A0)`` over one member), but dense is equally cheap at
    S_b=1 and the shared engine's batch-level rho adaptation/termination
    semantics converge differently on some families — the observed case
    is a 3-merge farmer bundle whose shared solve stalls where the dense
    solve converges."""
    return getattr(sub, "A_shared", None) is not None \
        and sub.num_scenarios > 1


#: Why a refresh solve ran: the ``solve.<cylinder>.refresh.<reason>``
#: counters, which sum to the refresh solves (``phase.<cylinder>.refresh
#: .count``).
_REFRESH_REASONS = ("cold", "signature", "age", "declined")


def _width_spent(meas) -> dict:
    """How much of the batch a solve was still sweeping, as its fetch
    carries it (``admm.width_counters``) and ``trace.outcome`` takes it."""
    return {k: meas[k] for k in admm.WIDTH_FIELDS}


def _refresh_reason(slot, sig, warm, refresh_every):
    """Why a solve cannot try its slot's frozen factors (one of
    :data:`_REFRESH_REASONS`), or ``None`` where it can."""
    if not (refresh_every > 1 and warm and slot.get("warm") is not None
            and slot.get("factors") is not None):
        return "cold"
    if slot.get("sig") != sig:
        return "signature"
    if slot.get("age", 0) >= refresh_every:
        return "age"
    return None


def _certified_dual_eval(args):
    """(dvals, margin) — the weak-duality bound with its X-cap hardening
    margin (admm.dual_objective_margin: extends the certificate's validity
    box on free coordinates from X to 10X; ~0 for tight duals).  Single
    source for every certified dual-bound site (Edualbound_perscen, donor
    transfer).  ONE device program + ONE fetch
    (admm.dual_objective_with_margin) — bound spokes call this every wheel
    iteration, and two separate jitted evaluations cost two serial
    blocking fetches."""
    packed = hostsync.fetch(admm.dual_objective_with_margin(*args))
    packed = np.asarray(packed, dtype=float)
    return packed[0], packed[1]


def _pick_dual_sign(q, A, cl, cu, lb, ub, duals, x, obj):
    """scipy's marginal sign convention is opposite ours and varies by
    constraint shape; rather than trust it, pick the sign whose dual
    objective is closest to the primal optimum (strong duality makes the
    right one ~exact; the wrong one collapses toward -inf).  Returns y."""
    best = None
    for sign in (-1.0, 1.0):
        ys = sign * duals
        dval = _np_dual_objective(q, A, cl, cu, lb, ub, ys, x)
        if best is None or abs(obj - dval) < abs(best[0]):
            best = (obj - dval, ys)
    return best[1]


def host_exact_clamp_cut(batch, q, s, lb, ub, clamp_idx):
    """Host-exact clamped-scenario solve + weak-duality cut (LP only).

    Returns ``(ok, obj, cut_base, grad)`` with const included in obj/base;
    ``Q_s(x̂') >= cut_base + grad . x̂'`` for every clamp value x̂'.  Simplex
    duals are exact and sign-feasible, so the weak-duality cut is TIGHT —
    the shared fallback for Benders/cross-scenario cut generation when the
    batched solve's duals leave a cut gap (degenerate or stalled scenarios).
    """
    from .solvers import scipy_backend

    res = scipy_backend.solve_lp_with_duals(
        q[s], batch.A[s], batch.cl[s], batch.cu[s], lb[s], ub[s])
    if not res.feasible or res.duals is None:
        return False, np.inf, None, None
    obj = float(q[s] @ res.x)
    ys = _pick_dual_sign(q[s], batch.A[s], batch.cl[s], batch.cu[s],
                         lb[s], ub[s], res.duals, res.x, obj)
    mask = np.zeros(batch.A.shape[2], dtype=bool)
    mask[clamp_idx] = True
    base, g = _np_dual_cut(q[s], batch.A[s], batch.cl[s], batch.cu[s],
                           lb[s], ub[s], ys, res.x, mask)
    return (True, obj + batch.const[s], base + batch.const[s], g[clamp_idx])


class SPOpt(SPBase):
    """Adds solving to SPBase."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._warm = None            # (x, z, y, yx) device arrays
        self.local_x = None          # (S, n) last solution
        self.pri_res = None
        self.dua_res = None
        self._fixed_lb = None        # active nonant fixing overlay (S, n) or None
        self._fixed_ub = None
        self._cached_nonants = None
        self._factors = None         # admm.Factors of the last refresh solve
        self._factors_sig = None
        self._factors_age = 0
        self._dev_state = None       # device-resident PHState (lean megasteps)
        self._host_state_stale = False

    def _device_consts(self, dt):
        """Device-resident (A, cl, cu) cached on batch.version: the (S, m, n)
        constraint tensor dominates host->device traffic and never changes
        between solves (both the solve_loop hot path and the spokes'
        Edualbound calls go through here)."""
        import jax.numpy as jnp

        b = self.batch
        # the batch token in the key: version numbers can collide across
        # DIFFERENT batch objects (e.g. sub-batches temporarily installed
        # by _fix_and_solve_bucketed, all at version 0)
        key = (_batch_token(b), getattr(b, "version", 0), str(dt))
        cached = getattr(self, "_dev_consts", None)
        if cached is None or cached[0] != key:
            # shared-A batches upload the single (m, n) matrix, not the
            # (S, m, n) broadcast view (which would materialize S copies)
            A_src = b.A if getattr(b, "A_shared", None) is None else b.A_shared
            sparse = self.options.get("sparse_device_A", "auto")
            if getattr(b, "A_shared", None) is None:
                sparse = False            # per-scenario A: dense batched path
            cached = (key, (_device_A(A_src, dt, sparse=sparse),
                            jnp.asarray(b.cl, dt),
                            jnp.asarray(b.cu, dt)))
            self._dev_consts = cached
        return cached[1]

    def _solve_sig(self, q2, lb, ub):
        """Validity signature of cached Factors.

        The factorization depends on (A, q2, rho patterns); rho patterns
        depend only on which rows are equalities/loose and which columns are
        clamped/finite — NOT on bound values.  So fix-and-evaluate solves
        (same clamp pattern, new candidate values) keep reusing factors.
        """
        lb = np.asarray(lb)
        ub = np.asarray(ub)
        patt = ((np.abs(ub - lb) < 1e-10).astype(np.uint8)
                + 2 * (lb > -admm.BIG / 2).astype(np.uint8)
                + 4 * (ub < admm.BIG / 2).astype(np.uint8))
        return (float(np.sum(np.asarray(q2))), hash(patt.tobytes()),
                _batch_token(self.batch),
                getattr(self.batch, "version", 0), self.admm_settings)

    # ---- the hot loop -------------------------------------------------------
    def solve_loop(self, q=None, q2=None, warm=True, dis_W=None, dis_prox=None):
        """Solve the whole local batch; returns (S, n) solutions.

        ``q``/``q2`` override the linear/diagonal-quadratic objective (PH passes
        its augmented objective here).  ``dis_W``/``dis_prox`` exist for API
        parity (PHBase computes q itself); they are accepted and ignored here.

        Factorization-amortized: a full adaptive "refresh" solve every
        ``solver_refresh_every`` calls (and whenever the problem structure
        changes) caches Ruiz scaling + rho vectors + the KKT factorization;
        calls in between are sweep-only frozen solves — no batched
        factorization or polish in the program at all.  A frozen solve that
        exhausts its sweep budget triggers an immediate adaptive re-solve, so
        accuracy never silently degrades.
        """
        ext = getattr(self, "extobject", None)
        if ext is not None:
            ext.pre_solve()
        # any host-path solve supersedes the device-resident wheel state
        # (callers synced the mirrors first — PHBase.iterk_loop's
        # boundary protocol); keeping a stale _dev_state here would let a
        # later megastep window resume from pre-refresh duals
        self._dev_state = None
        b = self.batch
        q = b.c if q is None else q
        q2 = b.q2 if q2 is None else q2
        lb = b.lb if self._fixed_lb is None else self._fixed_lb
        ub = b.ub if self._fixed_ub is None else self._fixed_ub

        from .ir import BucketedBatch

        if isinstance(b, BucketedBatch):
            x = self._solve_loop_bucketed(b, q, q2, lb, ub, warm)
            if ext is not None:
                ext.post_solve()
            return x

        shared = getattr(b, "A_shared", None) is not None
        # device-resident (A, cl, cu): avoids re-uploading the constraint
        # tensor (up to ~GB for shared-A UC) on EVERY solve call, and shares
        # one device copy of identical A across wheel cylinders
        A_d, cl_d, cu_d = self._device_consts(self.admm_settings.jdtype())
        slot = {"warm": self._warm, "factors": self._factors,
                "sig": self._factors_sig, "age": self._factors_age,
                "ref_worst": getattr(self, "_factors_ref_worst", None),
                "n_div_prev": getattr(self, "_n_div_prev", 0)}
        sol, meas = self._solve_amortized(
            (q, q2, A_d, cl_d, cu_d, lb, ub), slot, warm, None,
            shared=shared)
        self._warm = slot["warm"]
        self._factors = slot["factors"]
        self._factors_sig = slot["sig"]
        self._factors_age = slot["age"]
        self._factors_ref_worst = slot.get("ref_worst")
        self._n_div_prev = slot.get("n_div_prev", 0)
        # everything the iteration reads came back in the ONE packed fetch
        # _solve_amortized already performed (doc/pipeline.md)
        self.local_x = meas["x"]
        self.pri_res = meas["pri"]
        self.dua_res = meas["dua"]
        self._last_all_done = bool(meas["all_done"])
        if ext is not None:
            ext.post_solve()
        return self.local_x

    def _fetch_measure(self, sol):
        """ONE device fetch of everything the host reads from a solve
        (admm.measure_pack: residuals + iteration counter + convergence
        vote + x) — the single-fetch wheel-iteration discipline
        (doc/pipeline.md).  Returns the measure_unpack dict."""
        S, n = sol.x.shape
        return admm.measure_unpack(
            hostsync.fetch(admm.measure_pack(sol)), S, n)

    def _solve_amortized(self, args, slot: dict, warm: bool, rescue_batch,
                         shared: bool = False):
        """The factorization-amortization protocol shared by the homogeneous
        and bucketed paths: frozen attempt under a validity signature with a
        sweep-budget fallback, else an adaptive factored solve + straggler
        rescue.  ``slot`` carries warm/factors/sig/age state; ``args`` is
        the (q, q2, A, cl, cu, lb, ub) tuple (A is (m, n) when ``shared``,
        dispatching to the shared-A engine).  Polished states warm-start
        the NEXT objective's solve well (the PH persistent-solver pattern);
        raw iterates matter only when re-solving the SAME problem repeatedly
        (e.g. the Benders root).

        Returns ``(sol, meas)``: the device solution (its warm state never
        leaves the device) and the single-fetch measurement dict
        (:meth:`_fetch_measure`) every downstream host read — acceptance
        test, mixed-precision guard, straggler rescue, ``local_x`` — is
        served from.  Steady-state frozen cost: ONE measurement RPC per
        PH iteration for shapes that fit a single dispatch (the common
        wheel families), plus — only when the shape segments — the
        continuation's own per-segment stop-stats fetches (one for the
        incoming verdict, the rest overlapped with device compute under
        the pipelined protocol).  Previously every iteration paid 3-4
        separate array fetches regardless.
        """
        if shared:
            from .solvers import shared_admm
            from .solvers.structured_kkt import DiagLowRank
            # a spoke's solves take the device in turns with the hub's
            # (solvers/turns.py); anybody else's pass straight through
            frozen_fn = shared_admm.frozen_in_turn
            factored_fn = functools.partial(
                shared_admm.adaptive_in_turns, want_factors=True)
        else:
            frozen_fn = admm.solve_batch_frozen
            factored_fn = admm.solve_batch_factored
        refresh_every = self._refresh_every()
        sig = (self._solve_sig(args[1], args[5], args[6])
               if refresh_every > 1 else None)
        sol = meas = None
        from .solvers import segmented

        S = np.shape(args[0])[0]
        why = _refresh_reason(slot, sig, warm, refresh_every)
        if why is None:
            # segmented: oversized sweep loops are split into bounded
            # dispatches (segmented's per-dispatch budget);
            # want_converged=False — the convergence vote rides the packed
            # measurement below instead of a separate done fetch
            with _turns.hub_step(), _trace.phase("frozen") as _sp:
                cand, _ = segmented.solve_frozen_segmented(
                    frozen_fn, args, slot["factors"], self.admm_settings,
                    warm=slot["warm"], want_converged=False)
                meas_c = self._fetch_measure(cand)
                if _trace.enabled():   # payload dicts only when tracing
                    _sp.add(iters=meas_c["iters"],
                            all_done=meas_c["all_done"])
            # a segmented solve's counter is its LAST dispatch's: such a
            # solve reports no sweeps (and no budget to hold them against)
            spent = ({"sweeps": meas_c["iters"], "budget":
                      segmented.frozen_budget(self.admm_settings),
                      **_width_spent(meas_c)}
                     if segmented.one_dispatch(args, self.admm_settings)
                     else {})
            worst_c = float(max(np.max(meas_c["pri"]),
                                np.max(meas_c["dua"])))
            if admm.precision_guard_trips(
                    cand, self.admm_settings, slot.get("ref_worst"),
                    stats=(worst_c, meas_c["all_done"])):
                # mixed-precision residual guard: the low-precision frozen
                # solve parked far above the family's full-precision floor
                # — fall back to the full-precision frozen program on the
                # SAME cached factors (no refactorization)
                _metrics.inc("precision.guard_trips")
                if _trace.enabled():
                    _trace.instant(None, "precision_guard_trip",
                                   worst=worst_c,
                                   ref_worst=slot.get("ref_worst"))
                st_full = dataclasses.replace(self.admm_settings,
                                              sweep_precision="highest")
                cand, _ = segmented.solve_frozen_segmented(
                    frozen_fn, args, slot["factors"], st_full,
                    warm=slot["warm"], want_converged=False)
                meas_c = self._fetch_measure(cand)
                if spent:   # one attempt still: it spent both solves'
                    spent["sweeps"] += meas_c["iters"]
                    spent["budget"] += segmented.frozen_budget(st_full)
                    for k, v in _width_spent(meas_c).items():
                        spent[k] += v
            # accept when the sweep budget sufficed (converged to eps) OR
            # every scenario already sits inside the rescue-tolerance
            # ladder: an adaptive re-solve of a plateaued batch (UC prox
            # batches plateau at ~1e-3 primal no matter the budget) burns
            # a full factored solve per hub iteration for nothing — the
            # refresh cadence (slot age) re-solves adaptively anyway
            n_in_tol = int(np.count_nonzero(
                self._rows_in_tol(meas_c, args[1])[0]))
            if meas_c["all_done"] or n_in_tol == S:
                sol, meas = cand, meas_c
                slot["age"] = slot.get("age", 0) + 1
            else:
                why = "declined"
            _trace.outcome(
                "frozen", count=1, rows=S, rows_done=meas_c["n_done"],
                rows_in_tol=n_in_tol, accepted=int(sol is not None),
                **spent)
        if sol is None:
            # the REFRESH runs full precision end to end — including its
            # segmented frozen continuations and polish finale — both by
            # design (doc/precision.md: refresh solves are never lowered)
            # and so ref_worst below is a genuine full-precision floor for
            # the guard to anchor on
            st_adpt = self.admm_settings
            if st_adpt.sweep_precision not in (None, "highest"):
                st_adpt = dataclasses.replace(st_adpt,
                                              sweep_precision="highest")
            with _turns.hub_step(), _trace.phase("refresh"):
                sol, factors, _ = segmented.solve_factored_segmented(
                    frozen_fn, factored_fn, args, st_adpt,
                    warm=slot.get("warm") if warm else None, shared=shared,
                    want_converged=False)
                slot["factors"] = factors
                slot["sig"] = sig
                slot["age"] = 1
                meas = self._fetch_measure(sol)
            if not shared:
                if admm.lanes_linalg(st_adpt, *args[2].shape):
                    # this refresh's polish ran on pallas_kernels.lanes_solve
                    _metrics.inc("refresh.lanes_linalg")
                if admm.lanes_inverse(st_adpt, *args[2].shape):
                    # and its four K's were inverted on the same kernel
                    _metrics.inc("refresh.lanes_inverse")
                if admm.kernel_checkpoint(st_adpt, *args[2].shape):
                    # each step of its sweep loop was one call of
                    # pallas_kernels.fused_sweeps, residuals included
                    _metrics.inc("refresh.kernel_checkpoint")
            if shared and isinstance(factors.Kinv, DiagLowRank):
                # this refresh's factors apply K^-1 as diagonal plus
                # low rank (structured_kkt.lowrank_kinv)
                _metrics.inc("refresh.lowrank_kinv")
            # full-precision residual floor of this family at this
            # operating point — the mixed-precision guard's reference
            slot["ref_worst"] = float(
                max(np.max(meas["pri"]), np.max(meas["dua"])))
            # what the device's solve spent and left, before any rescue
            # (whose check counts rows_in_tol); how often is the phase's
            # count
            spent = ({"sweeps": meas["iters"], "budget":
                      max(1, st_adpt.restarts) * st_adpt.max_iter,
                      **_width_spent(meas)}
                     if segmented.one_dispatch(args, st_adpt, adaptive=True)
                     else {})
            _trace.outcome(
                "refresh", rows=S, rows_done=meas["n_done"],
                **{r: int(r == why) for r in _REFRESH_REASONS}, **spent)
            sol, meas = self._rescue_stragglers(
                sol, args[0], args[1], args[5], args[6],
                batch=rescue_batch, meas=meas)
        # shared-A divergence guard observability: frozen (exploded)
        # scenarios surface as non-finite residuals in the packed
        # measurement — count them so a run quietly degrading to frozen
        # iterates is visible in the flight recorder, not just in a
        # failed convergence assertion three reruns later.  Billed on
        # the INCREASE over this slot's previous solve only: a frozen
        # scenario stays non-finite every subsequent iteration, and
        # re-counting it would inflate the freeze count ~iterations-fold
        n_div = int(np.count_nonzero(~np.isfinite(meas["pri"])))
        new_div = n_div - slot.get("n_div_prev", 0)
        slot["n_div_prev"] = n_div
        if new_div > 0:
            _metrics.inc("solve.divergence_freezes", new_div)
            if _trace.enabled():
                _trace.instant(None, "divergence_freeze", scenarios=new_div,
                               total_frozen=n_div)
        slot["warm"] = (sol.x, sol.z, sol.y, sol.yx)
        return sol, meas

    def _solve_loop_bucketed(self, b, q, q2, lb, ub, warm):
        """Per-bucket batched solves for ragged families (one compact
        compiled program per shape bucket), scattered back into the
        (S, n_max) bookkeeping layout.  Per-bucket warm states chain like
        the homogeneous path's; factors amortization is per-bucket too.

        Device-lifted (ROADMAP item 1): each bucket's (A, cl, cu) is
        device-resident (:meth:`_bucket_device_consts` — no re-upload per
        solve), and a bucket whose sub-batch carries ``A_shared``
        dispatches the shared-A engine on the single (m, n) matrix
        instead of materializing the (S_b, m, n) broadcast.
        """
        S, n_max = b.c.shape
        x_out = np.zeros((S, n_max))
        pri = np.zeros(S)
        dua = np.zeros(S)
        all_done = True
        slots = getattr(self, "_bucket_slots", None)
        if slots is None or len(slots) != len(b.buckets):
            slots = self._bucket_slots = [dict() for _ in b.buckets]
        consts = self._bucket_device_consts(self.admm_settings.jdtype())
        for k, (idx, sub) in enumerate(b.buckets):
            n, m = sub.num_vars, sub.num_rows
            A_d, cl_d, cu_d = consts[k]
            args = (np.asarray(q)[idx, :n], np.asarray(q2)[idx, :n],
                    A_d, cl_d, cu_d,
                    np.asarray(lb)[idx, :n], np.asarray(ub)[idx, :n])
            _, meas = self._solve_amortized(
                args, slots[k], warm, sub, shared=bucket_shared(sub))
            x_out[idx, :n] = meas["x"]
            pri[idx] = meas["pri"]
            dua[idx] = meas["dua"]
            all_done = all_done and bool(meas["all_done"])
        self._warm = None          # homogeneous-path caches do not apply
        self._factors = None
        self._last_all_done = all_done
        self.local_x = x_out
        self.pri_res = pri
        self.dua_res = dua
        return x_out

    def _refresh_every(self) -> int:
        """Frozen-factor refresh cadence — the ONE knob every consumer
        (amortized solve slot, megastep window sizing/eligibility, age
        exhaustion) must read identically."""
        return int(self.options.get("solver_refresh_every", 16) or 0)

    def _straggler_tols(self):
        """(tol_lp, tol_qp) rescue-tolerance ladder.

        LP scenarios (bound spokes, xhat dives) rescue at ``straggler_tol``
        (default 1e-4) — exact primal/dual states keep bounds tight.  QP
        (prox-on PH hub) scenarios rescue only past ``straggler_tol_qp``
        (default 1e-2): PH is a fixed-point iteration whose xbar/W updates
        tolerate subproblem inexactness of that order (the reference hub
        runs Gurobi at default tolerances for the same reason), and host
        rescue of hundreds of mildly-stalled prox solves per iteration is
        exactly the wheel-stalling cost the batch exists to avoid.  An
        explicitly-set ``straggler_tol`` with no ``straggler_tol_qp``
        covers both kinds (explicit intent, and what round-3 tests pin).
        """
        tol_lp = max(float(self.options.get("straggler_tol", 1e-4)),
                     10.0 * self.admm_settings.eps_rel)
        if "straggler_tol_qp" in self.options:
            # explicit setting is honored as-is (floored only by solver eps)
            tol_qp = max(float(self.options["straggler_tol_qp"]),
                         10.0 * self.admm_settings.eps_rel)
        elif "straggler_tol" in self.options:
            tol_qp = tol_lp
        else:
            tol_qp = max(1e-2, tol_lp)
        return tol_lp, tol_qp

    def _rows_in_tol(self, meas, q2):
        """``(ok, is_qp)``, both (S,): the rows of a fetched measurement
        whose two residuals stand inside :meth:`_straggler_tols` (the
        frozen attempt's acceptance test and the rescue's selection are
        this one mask), and which rows carry a quadratic term."""
        tol_lp, tol_qp = self._straggler_tols()
        is_qp = np.any(np.asarray(q2) != 0.0, axis=-1)
        tol_s = np.where(is_qp, tol_qp, tol_lp)
        # <= so that NaN residuals (diverged solves) stand outside
        return (meas["pri"] <= tol_s) & (meas["dua"] <= tol_s), is_qp

    def _rescue_stragglers(self, sol, q, q2, lb, ub, batch=None, meas=None):
        """Host-exact re-solve of the few scenarios batched ADMM left
        unconverged.  Returns ``(sol, meas)``.

        Strongly-coupled LPs (UC ramp/genlim rows) occasionally stall a
        handful of scenarios at ~1e-1 residuals regardless of sweep budget.
        Re-solving that straggler slice host-exact — primal AND dual, so
        bounds stay certified — costs milliseconds per scenario once per
        refresh, while the batch stays the hot path.  LP scenarios go
        through HiGHS; QP scenarios (prox-on PH-hub solves) through the
        dense Mehrotra IPM (:func:`scipy_backend.solve_qp_with_duals`),
        whose dual convention is ours, so no sign vote is needed.  The
        hybrid mirrors the reference's posture: an exact solver where
        exactness matters (spopt.py:85-223), tensor batching everywhere
        else.

        ``meas`` (the caller's packed measurement) serves pri/dua/x; the
        ADMM aux state (z, y, yx, done) is fetched only when stragglers
        actually exist — the common all-converged refresh costs ZERO
        device round-trips here.
        """
        if meas is None:
            meas = self._fetch_measure(sol)
        if not self.options.get("straggler_rescue", True):
            return sol, meas
        ok, is_qp = self._rows_in_tol(meas, q2)
        bad = np.flatnonzero(~ok)
        # the one mask a refresh's measurement is held to
        _trace.outcome("refresh", rows_in_tol=ok.size - bad.size)
        if bad.size == 0:
            return sol, meas
        with _trace.phase("rescue", rows=int(bad.size)):
            return self._rescue_rows(
                sol, q, q2, lb, ub, self.batch if batch is None else batch,
                meas, bad, is_qp)

    def _rescue_rows(self, sol, q, q2, lb, ub, b, meas, bad, is_qp):
        """The host re-solves of :meth:`_rescue_stragglers` for the rows
        ``bad``; bills ``rescue.rows`` (re-solved host-exact) and
        ``rescue.left_at_batch`` (rows a cap left at batch accuracy)."""
        from .solvers import scipy_backend

        pri, dua = meas["pri"], meas["dua"]
        q = np.asarray(q, dtype=float)
        q2 = np.asarray(q2, dtype=float)
        lb = np.asarray(lb, dtype=float)
        ub = np.asarray(ub, dtype=float)
        x = np.array(meas["x"], copy=True)
        # straggler path only: the aux state the rescue rewrites
        z, y, yx = (np.array(hostsync.fetch(a), copy=True)
                    for a in (sol.z, sol.y, sol.yx))
        pri = pri.copy()
        dua = dua.copy()
        done = np.array(hostsync.fetch(sol.done), copy=True)
        n_resc = n_left = 0
        qp_bad = bad[is_qp[bad]]
        if qp_bad.size:
            # QP scenarios: batched host IPM over the straggler slice
            # (duals already in our convention); shared-A families pass the
            # single (m, n) A through with zero extra memory.  Chunked: the
            # IPM's KKT workspace is k*(n+me)^2 doubles, so an unbounded k
            # (hundreds of stalled prox solves at reference UC shape) would
            # OOM the host for no throughput gain
            A_shared = getattr(b, "A_shared", None)
            max_n = int(self.options.get("straggler_qp_max_n", 2000))
            if b.num_vars > max_n:
                # the host IPM is dense ((n, n) factorization per Newton
                # step): past ~2k vars one rescue costs minutes and stalls
                # the wheel worse than the inexact prox solves it repairs.
                # PH tolerates the inexactness; certified bounds never come
                # from prox solves (weak duality / LP rescue paths).
                if not getattr(self, "_qp_rescue_size_warned", False):
                    self._qp_rescue_size_warned = True
                    global_toc(
                        f"straggler rescue: {qp_bad.size} stalled QP "
                        f"scenario(s) left at batch accuracy (n="
                        f"{b.num_vars} > straggler_qp_max_n={max_n})",
                        True)
                n_left += int(qp_bad.size)
                qp_bad = np.empty(0, dtype=int)
            chunk = max(1, int(self.options.get("straggler_qp_chunk", 16)))
            for lo in range(0, qp_bad.size, chunk):
                sl = qp_bad[lo:lo + chunk]
                A_arg = A_shared if A_shared is not None else b.A[sl]
                xb, yb, feas = scipy_backend.solve_qp_batch_with_duals(
                    q[sl], q2[sl], A_arg,
                    b.cl[sl], b.cu[sl], lb[sl], ub[sl])
                for j, s in enumerate(sl):
                    if not feas[j]:
                        continue    # genuine infeasibility: leave residuals
                    xs, ys = xb[j], yb[j]
                    yx[s] = -(q[s] + q2[s] * xs + b.A[s].T @ ys)
                    x[s], y[s] = xs, ys
                    z[s] = b.A[s] @ xs
                    pri[s] = 0.0
                    dua[s] = 0.0
                    done[s] = True
                    n_resc += 1
        lp_bad = bad[~is_qp[bad]]
        max_lp = int(self.options.get("straggler_lp_max", 64))
        if lp_bad.size > max_lp:
            # big-batch stall tails (hundreds of mildly-stalled scenarios at
            # reference scale) would serialize hundreds of host LPs per
            # solve; rescue the worst offenders, leave the rest at batch
            # accuracy (bounds stay certified via weak duality regardless)
            worst = np.argsort(-np.maximum(pri[lp_bad], dua[lp_bad]))
            n_left += int(lp_bad.size) - max_lp
            lp_bad = lp_bad[worst[:max_lp]]
        # shared-A families: ONE csr conversion per rescue round (the
        # (m, n) dense scan per scenario was the hot cost at WECC scale) —
        # built only when there is LP work, so QP-only rounds skip it
        import scipy.sparse as _sp

        A_csr = (_sp.csr_matrix(np.asarray(b.A_shared))
                 if lp_bad.size
                 and getattr(b, "A_shared", None) is not None else None)
        for s in lp_bad:
            res = scipy_backend.solve_lp_with_duals(
                q[s], A_csr if A_csr is not None else b.A[s],
                b.cl[s], b.cu[s], lb[s], ub[s])
            if not res.feasible or res.duals is None:
                continue        # genuine infeasibility: leave residuals
            xs = res.x
            obj_s = float(q[s] @ xs)
            ys = _pick_dual_sign(q[s], b.A[s], b.cl[s], b.cu[s],
                                 lb[s], ub[s], res.duals, xs, obj_s)
            # stationarity-exact bound duals
            yxs = -(q[s] + q2[s] * xs + b.A[s].T @ ys)
            x[s], y[s], yx[s] = xs, ys, yxs
            z[s] = b.A[s] @ xs
            pri[s] = 0.0
            dua[s] = 0.0
            done[s] = True
            n_resc += 1
        _metrics.inc("rescue.rows", n_resc)
        _metrics.inc("rescue.left_at_batch", n_left)
        if n_resc:
            global_toc(
                f"straggler rescue: {n_resc}/{b.num_scenarios} scenarios "
                "re-solved host-exact", self.options.get("verbose", False))
        meas = dict(meas, x=x, pri=pri, dua=dua, all_done=bool(done.all()),
                    n_done=int(np.count_nonzero(done)))
        return (sol._replace(x=x, z=z, y=y, yx=yx, pri_res=pri, dua_res=dua,
                             done=done, raw=(x, z, y, yx)), meas)

    # ---- wheel megakernel (device-resident N-iteration dispatch) ------------
    def _mega_arrays(self, dt):
        """Device-resident :class:`~tpusppy.parallel.sharded.PHArrays` for
        the wheel megakernel (single-controller host path), cached on
        batch identity/version like ``_device_consts`` (whose A/cl/cu it
        shares — one device copy across cylinders).  Requires the PH-layer
        attributes (``_onehot``/``nid_sk``/``probs``) the megastep's
        device outer update contracts over; only :class:`PHBase` callers
        reach here (the eligibility gate)."""
        import jax.numpy as jnp

        from .parallel import sharded

        b = self.batch
        key = (_batch_token(b), getattr(b, "version", 0), str(dt))
        cached = getattr(self, "_mega_arr_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        A_d, cl_d, cu_d = self._device_consts(dt)
        S = b.num_scenarios
        arr = sharded.PHArrays(
            c=jnp.asarray(b.c, dt), q2=jnp.asarray(b.q2, dt), A=A_d,
            cl=cl_d, cu=cu_d,
            lb=jnp.asarray(b.lb, dt), ub=jnp.asarray(b.ub, dt),
            const=jnp.asarray(np.broadcast_to(b.const, (S,)), dt),
            probs=jnp.asarray(self.probs, dt),
            onehot=jnp.asarray(self._onehot, dt),
            nid_sk=jnp.asarray(self.nid_sk, jnp.int32))
        self._mega_arr_cache = (key, arr)
        return arr

    def _device_state_on(self) -> bool:
        """Device-resident PH-state posture (the O(1)-host big-S wheel):
        megastep windows fetch the LEAN packed measurement only, and the
        (S, K)/(S, n) host mirrors are refreshed by one explicit billed
        fetch at checkpoint/termination/refresh boundaries
        (:meth:`tpusppy.phbase.PHBase._sync_host_state`) instead of every
        window.  Opt-in: the ``ph_device_state`` hub option or
        ``TPUSPPY_DEVICE_STATE=1``."""
        import os

        v = self.options.get("ph_device_state")
        if v is None:
            v = os.environ.get("TPUSPPY_DEVICE_STATE", "0") != "0"
        return bool(v)

    def _inwheel_int_mask(self, batch=None):
        """(K,) integer mask of nonant slots for the in-wheel xhat
        candidate rounding (None when the family has no integer
        nonants)."""
        b = self.batch if batch is None else batch
        mask = np.asarray(b.is_int, bool)[self.tree.nonant_indices]
        return mask if mask.any() else None

    def _inwheel_feas_tol(self) -> float:
        """THE feasibility-gate tolerance — single-sourced for
        :meth:`feas_prob`, the ``Xhat_Eval`` integer gate, and the fused
        in-wheel evaluation (their claimed parity depends on one
        definition): option ``feas_tol`` floored at 10x the solver's own
        eps (a loose solve cannot certify tighter than itself)."""
        return max(float(self.options.get("feas_tol", 1e-3)),
                   10.0 * self.admm_settings.eps_rel)

    def _inwheel_threshold(self) -> float:
        """Integer rounding threshold of the in-wheel xhat candidate (the
        ``xbar_candidate`` rule; ``in_wheel_xhat_threshold`` option)."""
        return float(self.options.get("in_wheel_xhat_threshold", 0.5))

    def _inwheel_int_thresholds(self):
        """The batched integer sweep's rounding ladder (doc/integer.md),
        or None when the sweep is off: no integer nonants, or the
        ``in_wheel_int_sweep`` option disables it.  Resolution order:
        the ``in_wheel_int_thresholds`` option, then the autotuner's
        banked "integer" verdict (which truncates the default ladder to
        its measured K), then :data:`~tpusppy.solvers.integer.
        DEFAULT_THRESHOLDS`."""
        from .ir import BucketedBatch

        b = self.batch
        if isinstance(b, BucketedBatch):
            if all(self._inwheel_int_mask(batch=sub) is None
                   for _, sub in b.buckets):
                return None
        elif self._inwheel_int_mask() is None:
            return None
        if not self.options.get("in_wheel_int_sweep", True):
            return None
        th = self.options.get("in_wheel_int_thresholds")
        if th:
            return tuple(float(t) for t in th)
        from .solvers import integer as integer_solvers

        ladder = integer_solvers.DEFAULT_THRESHOLDS
        try:
            from . import tune

            v = tune.integer_verdict(self._mega_shape_key(),
                                     settings=self.admm_settings)
        except AttributeError:      # non-PH opt: no shape key — default
            v = None
        if v is not None and v.k:
            ladder = ladder[:max(1, int(v.k))]
        return tuple(float(t) for t in ladder)

    def _inwheel_int_sweep_on(self) -> bool:
        """Whether the bounds=True megastep for this instance compiles
        the batched integer sweep (and its longer packed tail)."""
        return self._inwheel_int_thresholds() is not None

    def _inwheel_pass_evals(self) -> int:
        """Frozen-evaluation count of ONE in-wheel bound pass — the
        watchdog-reservation and FLOP-billing unit: 1 for the legacy
        single-candidate pass; for the batched integer sweep, the
        ladder evaluations (+ the SLAM slams on the homogeneous kernel
        only — the bucketed posture drops them) + 1 reduced-cost
        re-solve when the fixing is certificate-safe for the family."""
        th = self._inwheel_int_thresholds()
        if th is None:
            return 1
        from .ir import BucketedBatch
        from .solvers import integer as integer_solvers

        c = len(th)
        if not isinstance(self.batch, BucketedBatch):
            c += integer_solvers.N_SLAM
        return c + (1 if self._inwheel_inner_ok() else 0)

    def _megastep_fn(self, n_req: int, pack: str = "full",
                     bounds: bool = False):
        """The jitted megakernel for this instance at width ``n_req``
        (one compile per distinct (N, pack, bounds); the traced
        ``n_live`` budget serves every executed count below it, and the
        traced ``bound_live`` flag serves every bound cadence)."""
        cache = getattr(self, "_mega_fn_cache", None)
        if cache is None:
            cache = self._mega_fn_cache = {}
        fn = cache.get((n_req, pack, bounds))
        if fn is None:
            from .parallel import sharded

            int_rounding = (self._inwheel_int_thresholds() if bounds
                            else None)
            fn = sharded.make_wheel_megastep(
                self.tree.nonant_indices, self.admm_settings, None,
                n_iters=n_req, donate=True, pack=pack, bounds=bounds,
                int_nonants=self._inwheel_int_mask() if bounds else None,
                xhat_threshold=(self._inwheel_threshold() if bounds
                                else 0.5),
                int_rounding=int_rounding,
                int_cols=(np.asarray(self.batch.is_int, bool)
                          if bounds and int_rounding else None),
                # reduced-cost fixing is only certificate-safe when the
                # candidate evaluation is at a true integer-feasible
                # point — every integer column a nonant slot
                int_rcfix=(self._inwheel_inner_ok()
                           if bounds and int_rounding else True))
            cache[(n_req, pack, bounds)] = fn
        return fn

    def _megastep_solve(self, n_req: int, n_live: int, convthresh: float,
                        W, xbars, rho, bound_live=None):
        """Dispatch ONE wheel megastep window and fetch its packed
        measurement — the megakernel twin of ``n_live`` frozen
        ``_solve_amortized`` iterations, sharing the same amortization
        slot: warm state stays device-resident (the returned
        :class:`~tpusppy.parallel.sharded.PHState` buffers become
        ``self._warm``), the factors age advances by the executed count,
        and the mega-dispatch is billed
        (:func:`~tpusppy.solvers.segmented.bill_megastep`).  ONE host
        fetch per window; the divergence / mixed-precision-guard
        bookkeeping runs on the fetched measurement, and an unclean
        final iterate forces the NEXT solve onto the legacy refresh path
        (``_factors_age`` maxed) — the serial acceptance test at window
        granularity.

        ``bound_live`` (None = the bound-pass program variant is not even
        compiled): in-wheel certification — True runs the fused
        outer/inner bound pass on the window's final device state, False
        rides the same compiled program through the dead cadence branch.
        """
        import jax.numpy as jnp

        from .parallel import sharded
        from .solvers import segmented
        from .solvers.sparse import SparseA

        st = self.admm_settings
        dt = st.jdtype()
        arr = self._mega_arrays(dt)
        b = self.batch
        S, n, m = b.num_scenarios, b.num_vars, b.num_rows
        K = self.nonant_length
        pack = "lean" if self._device_state_on() else "full"
        state = getattr(self, "_dev_state", None)
        if state is None:
            warm = self._warm
            state = sharded.PHState(
                W=jnp.asarray(W, dt), xbars=jnp.asarray(xbars, dt),
                rho=jnp.asarray(rho, dt),
                x=jnp.asarray(warm[0], dt), z=jnp.asarray(warm[1], dt),
                y=jnp.asarray(warm[2], dt), yx=jnp.asarray(warm[3], dt))
        # in-scan acceptance at the serial ladder: the megastep solves
        # the PH prox objective, so every scenario is QP
        _, tol_qp = self._straggler_tols()
        bounds = bound_live is not None
        with _turns.hub_step(), _trace.phase("megastep") as _sp:
            fn = self._megastep_fn(n_req, pack, bounds=bounds)
            if bounds:
                state, packed = fn(
                    state, arr, 1.0, self._factors, convthresh, n_live,
                    tol_qp, bool(bound_live), self._inwheel_feas_tol())
            else:
                state, packed = fn(
                    state, arr, 1.0, self._factors, convthresh, n_live,
                    tol_qp)
            # rebind the warm slot BEFORE the blocking fetch: the old
            # buffers were donated into the dispatch, so a fetch failure
            # (runtime error, fault injection) must not leave
            # self._warm pointing at deleted device memory
            self._warm = (state.x, state.z, state.y, state.yx)
            # device-resident posture: the RETURNED state (W/xbars
            # included) is the authoritative wheel state; host mirrors
            # go stale until a boundary sync fetches them explicitly
            self._dev_state = state if pack == "lean" else None
            meas = sharded.megastep_unpack(
                hostsync.fetch(packed), n_req, S, n, K, pack=pack,
                bounds=bounds,
                int_sweep=bounds and self._inwheel_int_sweep_on())
            if _trace.enabled():
                _sp.add(n_live=n_live, iters=meas["executed"],
                        refresh_hit=meas["refresh_hit"],
                        bound_pass=bool(meas.get("bound_computed")))
        executed = meas["executed"]
        self._factors_age += executed
        sf = (segmented.SPARSE_DISPATCH_FACTOR
              if isinstance(arr.A, SparseA) else 1.0)
        sweeps, rej = self._megastep_outcome(meas, n_req)
        segmented.bill_megastep(S, n, m, executed, sweeps, sparse_factor=sf,
                                rejected_sweeps=rej)
        if meas.get("bound_computed"):
            segmented.bill_bound_pass(S, n, m, meas["bound_sweeps"],
                                      sparse_factor=sf,
                                      n_evals=self._inwheel_pass_evals())

        refresh_every = self._refresh_every()
        guard = False
        if executed:
            # mixed-precision residual guard on EVERY accepted iterate
            # (the serial path runs it per frozen solve — a mid-window
            # iterate parked above the precision floor must force the
            # refresh even when the final iterate dips back under): the
            # packed measurement's per-iteration worst residuals make
            # this free of extra fetches.  The in-scan program cannot
            # re-run at full precision, so a trip routes the NEXT solve
            # through the legacy refresh (full precision by design).
            ref = getattr(self, "_factors_ref_worst", None)
            worsts = np.maximum(meas["pri_max"][:executed],
                                meas["dua_max"][:executed])
            guard = any(
                admm.precision_guard_trips(
                    None, st, ref,
                    stats=(float(worsts[i]), bool(meas["all_done"][i])))
                for i in range(executed))
            if guard:
                _metrics.inc("precision.guard_trips")
        if meas["refresh_hit"] or guard:
            # an in-scan iterate failed the serial acceptance test and
            # was discarded (or the guard tripped): exhaust the factors
            # age so the next iteration runs the legacy adaptive refresh
            # + straggler rescue — exactly where the serial protocol
            # lands, minus the already-discarded frozen attempt
            self._factors_age = max(self._factors_age, refresh_every)
            _metrics.inc("megastep.refresh_hits")
        return meas

    def _megastep_outcome(self, meas, n_req: int):
        """What one megastep window spent, from its fetched measurement:
        records ``trace.outcome("mega", ...)`` (how many iterations it
        executed is ``dispatch.mega_iterations``) and returns ``(sweeps,
        rej)`` for :func:`~tpusppy.solvers.segmented.bill_megastep`: the
        mean sweeps of an executed iteration, and the sweeps of the iterate
        the in-scan acceptance test discarded, or None.  Homogeneous and
        bucketed windows alike (a bucketed window's counter is the
        cross-bucket max)."""
        from .solvers import segmented

        executed = meas["executed"]
        iters = meas["iters"][:executed]
        # a rejected iterate (refresh_hit) is dispatched-but-discarded
        # work; its stats sit at index ``executed`` of the packed arrays
        rej = (float(meas["iters"][executed])
               if meas["refresh_hit"] and executed < n_req else None)
        _trace.outcome(
            "mega", sweeps=int(np.sum(iters)),
            budget=executed * segmented.frozen_budget(self.admm_settings),
            all_done=int(np.count_nonzero(meas["all_done"][:executed])),
            rejected_sweeps=int(rej or 0),
            **{k: int(np.sum(meas[k][:executed])) for k in admm.WIDTH_FIELDS})
        return (float(np.mean(iters)) if executed else 0.0), rej

    def _mega_arrays_bucketed(self, dt):
        """Per-bucket :class:`~tpusppy.parallel.sharded.PHArrays` tuple
        for the bucketed wheel megakernel: each bucket's compact problem
        data (sharing :meth:`_bucket_device_consts`' device A/cl/cu) plus
        its GLOBAL-tree slices of probs/onehot/nid_sk — the cross-bucket
        outer update couples through those, so bucket-local probability
        normalization never enters the device reductions."""
        import jax.numpy as jnp

        from .parallel import sharded

        b = self.batch
        key = (_batch_token(b), getattr(b, "version", 0), str(dt))
        cached = getattr(self, "_mega_arr_bucket_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        consts = self._bucket_device_consts(dt)
        arrs = []
        for (idx, sub), (A_d, cl_d, cu_d) in zip(b.buckets, consts):
            n = sub.num_vars
            S_b = idx.size
            arrs.append(sharded.PHArrays(
                c=jnp.asarray(sub.c, dt), q2=jnp.asarray(sub.q2, dt),
                A=A_d, cl=cl_d, cu=cu_d,
                lb=jnp.asarray(sub.lb, dt), ub=jnp.asarray(sub.ub, dt),
                const=jnp.asarray(
                    np.broadcast_to(sub.const, (S_b,)), dt),
                probs=jnp.asarray(self.probs[idx], dt),
                onehot=jnp.asarray(self._onehot[idx], dt),
                nid_sk=jnp.asarray(self.nid_sk[idx], jnp.int32)))
        arrs = tuple(arrs)
        self._mega_arr_bucket_cache = (key, arrs)
        return arrs

    def _bucketed_megastep_fn(self, n_req: int, bounds: bool = False):
        cache = getattr(self, "_mega_fn_cache", None)
        if cache is None:
            cache = self._mega_fn_cache = {}
        keyb = ("bucketed", n_req, bounds)
        fn = cache.get(keyb)
        if fn is None:
            from .parallel import sharded

            int_masks = None
            int_rounding = None
            int_cols = None
            if bounds:
                # per-bucket integer masks: bucketing may key on the
                # integer pattern, so nonant integrality can differ
                int_masks = tuple(
                    self._inwheel_int_mask(batch=sub)
                    for _, sub in self.batch.buckets)
                int_rounding = self._inwheel_int_thresholds()
                if int_rounding:
                    int_cols = tuple(
                        np.asarray(sub.is_int, bool)
                        for _, sub in self.batch.buckets)
            fn = sharded.make_bucketed_wheel_megastep(
                self.tree.nonant_indices, self.admm_settings,
                n_iters=n_req, donate=True, bounds=bounds,
                int_nonants=int_masks,
                xhat_threshold=(self._inwheel_threshold() if bounds
                                else 0.5),
                int_rounding=int_rounding, int_cols=int_cols,
                int_rcfix=(self._inwheel_inner_ok()
                           if bounds and int_rounding else True))
            cache[keyb] = fn
        return fn

    def _megastep_solve_bucketed(self, n_req: int, n_live: int,
                                 convthresh: float, W, xbars, rho,
                                 bound_live=None):
        """Bucketed twin of :meth:`_megastep_solve`: ONE device dispatch
        runs ``n_live`` wheel iterations over every bucket's compact
        shapes, the packed per-bucket blocks scatter back through each
        bucket's scenario indices into the global bookkeeping layout, and
        each bucket's amortization slot advances exactly as its scattered
        host solves would have (warm rebind before the fetch, age +=
        executed, per-bucket billing)."""
        import jax.numpy as jnp

        from .parallel import sharded
        from .solvers import segmented

        st = self.admm_settings
        dt = st.jdtype()
        if self._device_state_on() and \
                not getattr(self, "_bucketed_lean_warned", False):
            # the lean (device-resident) pack is homogeneous-only today:
            # a bucketed family silently running full-pack windows would
            # look like the O(1)-host posture while paying O(S·n) per
            # window — say so once instead
            self._bucketed_lean_warned = True
            global_toc(
                "ph_device_state: bucketed families run FULL-pack "
                "megasteps (the lean O(1)-host posture is homogeneous-"
                "only; doc/scaling.md)", True)
        arrs = self._mega_arrays_bucketed(dt)
        b = self.batch
        slots = self._bucket_slots
        K = self.nonant_length
        W = np.asarray(W)
        xbars = np.asarray(xbars)
        rho = np.asarray(rho)
        states = []
        for (idx, sub), slot in zip(b.buckets, slots):
            warm = slot["warm"]
            states.append(sharded.PHState(
                W=jnp.asarray(W[idx], dt),
                xbars=jnp.asarray(xbars[idx], dt),
                rho=jnp.asarray(rho[idx], dt),
                x=jnp.asarray(warm[0], dt), z=jnp.asarray(warm[1], dt),
                y=jnp.asarray(warm[2], dt), yx=jnp.asarray(warm[3], dt)))
        factors = tuple(slot["factors"] for slot in slots)
        _, tol_qp = self._straggler_tols()
        shapes = [(idx.size, sub.num_vars) for idx, sub in b.buckets]
        bounds = bound_live is not None
        with _turns.hub_step(), _trace.phase("megastep") as _sp:
            fnb = self._bucketed_megastep_fn(n_req, bounds=bounds)
            if bounds:
                states, packed = fnb(
                    tuple(states), arrs, 1.0, factors, convthresh,
                    n_live, tol_qp, bool(bound_live),
                    self._inwheel_feas_tol())
            else:
                states, packed = fnb(
                    tuple(states), arrs, 1.0, factors, convthresh,
                    n_live, tol_qp)
            # rebind every bucket's warm slot BEFORE the blocking fetch
            # (the donated buffers are gone — same contract as the
            # homogeneous path)
            for slot, stb in zip(slots, states):
                slot["warm"] = (stb.x, stb.z, stb.y, stb.yx)
            bmeas = sharded.bucketed_megastep_unpack(
                hostsync.fetch(packed), n_req, shapes, K, bounds=bounds,
                int_sweep=bounds and self._inwheel_int_sweep_on())
            if _trace.enabled():
                _sp.add(n_live=n_live, iters=bmeas["executed"],
                        refresh_hit=bmeas["refresh_hit"], buckets=len(arrs))
        executed = bmeas["executed"]
        # scatter the per-bucket blocks into the global layout so the
        # caller's install path (_apply_megastep_meas) is bucket-agnostic
        S, n_max = b.num_scenarios, b.num_vars
        meas = {k: bmeas[k] for k in (
            "conv", "eobj", "pri_max", "dua_max", "iters", "all_done",
            "executed", "refresh_hit") + admm.WIDTH_FIELDS}
        if bounds:
            meas.update({k: bmeas[k] for k in (
                "bound_computed", "bound_outer", "bound_inner_obj",
                "bound_inner_feas", "bound_sweeps")})
            for k in ("int_feas_cands", "int_best_idx",
                      "int_rcfix_slots", "bound_outer_base"):
                if k in bmeas:
                    meas[k] = bmeas[k]
        pri = np.zeros(S)
        dua = np.zeros(S)
        done = np.zeros(S, dtype=bool)
        x = np.zeros((S, n_max))
        Wg = np.zeros((S, K))
        xbg = np.zeros((S, K))
        for bi, (idx, sub) in enumerate(b.buckets):
            pri[idx] = bmeas["pri"][bi]
            dua[idx] = bmeas["dua"][bi]
            done[idx] = bmeas["done"][bi]
            x[idx, :sub.num_vars] = bmeas["x"][bi]
            Wg[idx] = bmeas["W"][bi]
            xbg[idx] = bmeas["xbars"][bi]
        meas.update(pri=pri, dua=dua, done=done, x=x, W=Wg, xbars=xbg)
        refresh_every = self._refresh_every()
        guard = False
        if executed:
            ref = max((slot.get("ref_worst") or 0.0) for slot in slots) \
                if any(slot.get("ref_worst") is not None
                       for slot in slots) else None
            worsts = np.maximum(meas["pri_max"][:executed],
                                meas["dua_max"][:executed])
            guard = any(
                admm.precision_guard_trips(
                    None, st, ref,
                    stats=(float(worsts[i]), bool(meas["all_done"][i])))
                for i in range(executed))
            if guard:
                _metrics.inc("precision.guard_trips")
        sweeps, rej = self._megastep_outcome(meas, n_req)
        # loop-invariant: the threshold-ladder resolution behind this is
        # a per-bucket scan + verdict lookup, not per-bucket billing work
        pass_evals = (self._inwheel_pass_evals()
                      if meas.get("bound_computed") else 1)
        for bi, (slot, (idx, sub)) in enumerate(zip(slots, b.buckets)):
            # per-bucket FLOP billing on each bucket's own shapes (the
            # packed sweep counter is the cross-bucket max —
            # conservative); the window is ONE dispatch, so only the
            # first bucket counts toward the dispatch counters
            segmented.bill_megastep(idx.size, sub.num_vars, sub.num_rows,
                                    executed, sweeps, rejected_sweeps=rej,
                                    count_dispatch=bi == 0)
            if meas.get("bound_computed"):
                segmented.bill_bound_pass(
                    idx.size, sub.num_vars, sub.num_rows,
                    meas["bound_sweeps"], count_pass=bi == 0,
                    n_evals=pass_evals)
            slot["age"] = slot.get("age", 0) + executed
            if meas["refresh_hit"] or guard:
                slot["age"] = max(slot["age"], refresh_every)
        if meas["refresh_hit"] or guard:
            _metrics.inc("megastep.refresh_hits")
        return meas

    # ---- expectations (Allreduce analogues) ---------------------------------
    def Eobjective(self, x=None) -> float:
        """Probability-weighted expected objective (spopt.py:310-345)."""
        x = self.local_x if x is None else np.asarray(x)
        return float(self.probs @ self.batch.objective(x))

    def Ebound(self, x=None, extra_obj=None) -> float:
        """Expected bound from current subproblem objectives (spopt.py:346-393).

        With W active and prox off, this is the Lagrangian outer bound.
        ``extra_obj``: (S,) additive per-scenario objective terms (e.g. W·x).
        """
        x = self.local_x if x is None else np.asarray(x)
        vals = self.batch.objective(x)
        if extra_obj is not None:
            vals = vals + np.asarray(extra_obj)
        return float(self.probs @ vals)

    def Edualbound(self, q=None, q2=None) -> float:
        """Expectation of :meth:`Edualbound_perscen` (see there)."""
        return float(self.probs @ self.Edualbound_perscen(q, q2))

    def Edualbound_perscen(self, q=None, q2=None) -> np.ndarray:
        """CERTIFIED per-scenario outer bounds ((S,)) from the last solve's
        row duals; ``Edualbound`` is their expectation, and the MILP lift
        (:mod:`tpusppy.solvers.milp_bound`) raises individual entries.

        ``Ebound`` evaluates the primal objective of an inexact solve — valid
        only to solver tolerance (the reference gets exactness from its
        external MIP solver).  This uses weak duality instead: for any duals
        y, the per-scenario dual objective bounds the subproblem optimum from
        below, so solver tolerance can only make the reported bound WEAKER,
        never invalid.  See :func:`tpusppy.solvers.admm.dual_objective` for
        the free-variable margin caveat.
        """
        from .ir import BucketedBatch

        if isinstance(self.batch, BucketedBatch):
            return self._Edualbound_bucketed_perscen(q, q2)
        if self._warm is None:
            raise RuntimeError("Edualbound requires a prior solve_loop")
        b = self.batch
        q = b.c if q is None else q
        q2 = b.q2 if q2 is None else q2
        lb = b.lb if self._fixed_lb is None else self._fixed_lb
        ub = b.ub if self._fixed_ub is None else self._fixed_ub
        x, _, y, _ = self._warm
        dt = self.admm_settings.jdtype()
        import jax.numpy as jnp

        A_d, cl_d, cu_d = self._device_consts(dt)
        args = (jnp.asarray(q, dt), jnp.asarray(q2, dt), A_d, cl_d, cu_d,
                jnp.asarray(lb, dt), jnp.asarray(ub, dt),
                jnp.asarray(y, dt), jnp.asarray(x, dt))
        dvals, margin = _certified_dual_eval(args)
        self.last_bound_margin = margin
        return dvals - margin + b.const

    def dual_donor_bounds(self, q=None, q2=None, k=16, budget_s=90.0,
                          time_limit=30.0,
                          refresh_every=4) -> np.ndarray | None:
        """(S,) certified bounds from EXACT donor duals, transferred
        batch-wide — the scalable outer-bound mechanism at full scale.

        The per-scenario ADMM duals of plateaued reference-scale solves
        are loose (bounds off by ORDERS of magnitude), and host-exact dual
        rescue prices O(seconds) per scenario — at S=1000 neither works
        (the r5 full-scale traces showed Lagrangian bounds of -2e9 against
        an optimum near 1.2e7).  But weak duality accepts ANY y per
        scenario: solve ``k`` donor scenarios host-exact (HiGHS, with
        THEIR W-augmented objectives), then evaluate every donor's dual
        against ALL scenarios through :func:`admm.dual_objective` (one
        batched device call per donor) and keep the per-scenario best.
        Wind-ladder scenarios are small perturbations of each other, so
        exact duals transfer nearly tight — O(k) host LPs total instead
        of O(S).

        Donor duals are CACHED across calls: a y computed for an earlier W
        remains a valid certificate for any new q (weak duality), so each
        round re-evaluates every cached dual with two cheap batched device
        calls and re-solves the host LPs only every ``refresh_every``-th
        call (the host LP cost would otherwise dominate the spoke at
        exactly the scale this exists for).  ``time_limit`` caps each
        donor LP; the budget is also enforced between solves.

        Returns None when no donor duals are available (e.g. bucketed
        batches — no homogeneous warm state — or every LP failed); callers
        degrade to their base bound.
        """
        from .ir import BucketedBatch
        from .solvers import scipy_backend

        b = self.batch
        if isinstance(b, BucketedBatch):
            return None
        q = np.asarray(b.c if q is None else q, dtype=float)
        q2 = np.asarray(b.q2 if q2 is None else q2, dtype=float)
        lb = np.asarray(b.lb if self._fixed_lb is None else self._fixed_lb)
        ub = np.asarray(b.ub if self._fixed_ub is None else self._fixed_ub)
        S = b.num_scenarios
        if self._warm is not None:
            x_hint = np.asarray(self._warm[0])
        else:
            # no prior batched solve (the full-scale Lagrangian skips it —
            # donors ARE the bound): a conservative hint sized from the
            # finite problem data keeps the X-cap certificate box far
            # outside any reachable optimizer (exact donor duals leave
            # ~zero reduced cost on capped coordinates, so the margin
            # stays ~0 regardless)
            finite_max = 1.0
            for arr in (b.cl, b.cu, lb, ub):
                fa = np.abs(arr[np.isfinite(arr)])
                if fa.size:
                    finite_max = max(finite_max, float(fa.max()))
            x_hint = np.full((S, b.num_vars), finite_max)
        cache = getattr(self, "_donor_dual_cache", None)
        age = getattr(self, "_donor_dual_age", 0)
        if cache is None or age >= max(1, int(refresh_every)):
            sel = np.unique(
                np.linspace(0, S - 1, min(int(k), S)).astype(int))
            import scipy.sparse as _sp

            A_sh = getattr(b, "A_shared", None)
            A_csr = (_sp.csr_matrix(np.asarray(A_sh))
                     if A_sh is not None else None)
            deadline = time.monotonic() + float(budget_s)
            cache = []
            for s_k in sel:
                remaining = deadline - time.monotonic()
                if remaining <= 1.0:
                    break
                res = scipy_backend.solve_lp_with_duals(
                    q[s_k], A_csr if A_csr is not None else b.A[s_k],
                    b.cl[s_k], b.cu[s_k], lb[s_k], ub[s_k],
                    time_limit=min(float(time_limit), remaining))
                if not res.feasible or res.duals is None:
                    continue
                obj_k = float(q[s_k] @ res.x)
                cache.append(_pick_dual_sign(
                    q[s_k], b.A[s_k], b.cl[s_k], b.cu[s_k],
                    lb[s_k], ub[s_k], res.duals, res.x, obj_k))
            if not cache:
                # refresh produced nothing (every LP timed out): KEEP the
                # previous duals — still valid certificates — and leave the
                # cache unset otherwise so the next call retries instead of
                # serving an empty cache for refresh_every-1 rounds
                prev = getattr(self, "_donor_dual_cache", None)
                if prev:
                    cache = prev
                else:
                    self._donor_dual_cache = None
                    self._donor_dual_age = 0
                    return None
            self._donor_dual_cache = cache
            age = 0
        self._donor_dual_age = age + 1
        if not cache:
            return None
        dt = self.admm_settings.jdtype()
        import jax.numpy as jnp

        A_d, cl_d, cu_d = self._device_consts(dt)
        lb_d, ub_d = jnp.asarray(lb, dt), jnp.asarray(ub, dt)
        q_d, q2_d = jnp.asarray(q, dt), jnp.asarray(q2, dt)
        xh_d = jnp.asarray(x_hint, dt)
        const = np.asarray(np.broadcast_to(b.const, (S,)))
        best = None
        for y_k in cache:
            y_tiled = jnp.broadcast_to(jnp.asarray(y_k, dt), (S, y_k.size))
            args = (q_d, q2_d, A_d, cl_d, cu_d, lb_d, ub_d, y_tiled, xh_d)
            dvals, margin = _certified_dual_eval(args)
            dv = dvals - margin + const
            best = dv if best is None else np.maximum(best, dv)
        return best

    def _Edualbound_bucketed_perscen(self, q=None, q2=None) -> np.ndarray:
        """Certified dual bound for RAGGED (bucketed) batches: the weak-
        duality construction per compact bucket, scattered back — closes
        the r2 limitation where bound-spoke wheels required unbucketed
        batches."""
        import jax.numpy as jnp

        b = self.batch
        slots = getattr(self, "_bucket_slots", None)
        # freshness: a rebucketed batch invalidates the slot list exactly as
        # the solve path's own check does (zip would silently truncate and
        # report a falsely tight "certificate" otherwise)
        if (not slots or len(slots) != len(b.buckets)
                or any(s.get("warm") is None for s in slots)):
            raise RuntimeError("Edualbound requires a prior solve_loop")
        q = np.asarray(b.c if q is None else q)
        q2 = np.asarray(b.q2 if q2 is None else q2)
        lb = np.asarray(b.lb if self._fixed_lb is None else self._fixed_lb)
        ub = np.asarray(b.ub if self._fixed_ub is None else self._fixed_ub)
        dt = self.admm_settings.jdtype()
        consts = self._bucket_device_consts(dt)
        vals = np.zeros(b.num_scenarios)
        margin_out = np.zeros(b.num_scenarios)
        for (idx_arr, sub), slot, (A_d, cl_d, cu_d) in zip(
                b.buckets, slots, consts):
            n = sub.num_vars
            x, _, y, _ = slot["warm"]
            args = (jnp.asarray(q[idx_arr, :n], dt),
                    jnp.asarray(q2[idx_arr, :n], dt), A_d, cl_d, cu_d,
                    jnp.asarray(lb[idx_arr, :n], dt),
                    jnp.asarray(ub[idx_arr, :n], dt),
                    jnp.asarray(y, dt), jnp.asarray(x, dt))
            dv, mg = _certified_dual_eval(args)
            vals[idx_arr] = dv
            margin_out[idx_arr] = mg
        self.last_bound_margin = margin_out
        return vals - margin_out + b.const

    def _bucket_device_consts(self, dt):
        """Per-bucket device-resident (A, cl, cu), cached on batch.version —
        the bucketed analogue of _device_consts (spoke hot loops call
        Edualbound per iteration)."""
        import jax.numpy as jnp

        b = self.batch
        key = (_batch_token(b), getattr(b, "version", 0), str(dt),
               len(b.buckets))
        cached = getattr(self, "_bucket_dev_consts", None)
        if cached is None or cached[0] != key:
            # a (really) shared-A bucket uploads its single (m, n) matrix
            # (the shared engine and the dual-bound programs both accept
            # the 2-D form), never the (S_b, m, n) broadcast view
            consts = [
                (jnp.asarray(
                    sub.A_shared if bucket_shared(sub) else sub.A, dt),
                 jnp.asarray(sub.cl, dt),
                 jnp.asarray(sub.cu, dt)) for _, sub in b.buckets]
            cached = (key, consts)
            self._bucket_dev_consts = cached
        return cached[1]

    def feas_prob(self, tol=None) -> float:
        """Probability mass of feasible scenarios (spopt.py:394-433): here,
        scenarios whose ADMM primal residual is within tolerance.

        Default tolerance 1e-3 (option "feas_tol"): the float32 TPU path
        floors its scaled primal residual around 1e-4.  A solver run at loose
        eps (e.g. via the Gapper schedule) cannot certify feasibility tighter
        than its own tolerance, so the floor scales with eps_rel."""
        ok = self.feasible_rows(tol)
        return 1.0 if ok is None else float(self.probs @ ok)

    def feasible_rows(self, tol=None):
        """(S,) the rows :meth:`feas_prob` counts as feasible, or ``None``
        before any solve."""
        if tol is None:
            tol = self._inwheel_feas_tol()   # the ONE gate tolerance
        if self.pri_res is None:
            return None
        return np.asarray(self.pri_res) < tol

    def infeas_prob(self, tol=None) -> float:
        return 1.0 - self.feas_prob(tol)

    # ---- nonant caches / fixing (spopt.py:528-740) --------------------------
    def save_nonants(self):
        self._cached_nonants = self.nonants_of(self.local_x).copy()

    def restore_nonants(self):
        """Drop any fixing overlay (the cache itself is for xhat bookkeeping)."""
        self._fixed_lb = None
        self._fixed_ub = None

    def fix_nonants(self, cache):
        """Clamp nonant slots to candidate values (spopt.py:557-591): the batch
        equivalent of fixing Pyomo vars — lb=ub=candidate on nonant columns.

        ``cache``: (K,) a single candidate for all scenarios, or (S, K).
        """
        b = self.batch
        cache = np.asarray(cache, dtype=float)
        if cache.ndim == 1:
            cache = np.broadcast_to(cache, (b.num_scenarios, cache.shape[0]))
        if np.any(self.batch.is_int[self.tree.nonant_indices]):
            ints = self.batch.is_int[self.tree.nonant_indices]
            cache = np.where(ints[None, :], np.round(cache), cache)
        lb = b.lb.copy()
        ub = b.ub.copy()
        idx = self.tree.nonant_indices
        lb[:, idx] = cache
        ub[:, idx] = cache
        self._fixed_lb, self._fixed_ub = lb, ub

    # Scenario bundling (spbase.py:219-253, spopt.py:743-836): in the batched
    # design a bundle is a block-diagonal merge of member scenarios applied at
    # batch construction — see tpusppy.bundles once implemented (not yet).
