"""Continuous batching: K isomorphic tenants fused into ONE megastep.

ROADMAP item 2, doc/serving.md "Continuous batching".  The serving layer
proved equal shape family => identical compiled programs
(:func:`tpusppy.service.canonical.ingest`); time-slicing nevertheless ran
those identical programs ONE TENANT AT A TIME, paying a park/resume +
WheelSpinner setup/teardown + per-window host sync per quantum per
tenant while the device idled between slices.  The LLM-serving idiom
(Orca-style continuous batching, as adopted by vLLM-class servers)
removes exactly that overhead: stack K concurrent requests' scenario
batches along a tenant axis, run ONE fused megastep per window, and swap
a finishing tenant's rows for a queued one at a window boundary so the
device never drains.

:class:`BatchedFamilyRunner` is the scheduler-side half of the tenant
kernel (:func:`tpusppy.parallel.sharded.make_tenant_megastep`):

* **Slots.**  K slots, each holding one tenant's OWN
  :class:`~tpusppy.parallel.sharded.PHState`/arrays/ADMM factors — the
  per-slot computation is the exact solo wheel (the 1e-9 parity
  contract), only the dispatch is shared.  An empty slot rides as a
  GHOST (inert rows, ``live_mask`` False) until a join backfills it.
* **Joins/evictions at window boundaries only.**  Join = write the
  newcomer's arrays + fresh (or checkpoint-resumed) W/xbars into a free
  slot; evict = bank the slot's W/xbars/rho through the existing
  checkpoint seam (:mod:`tpusppy.resilience.checkpoint`) so the tenant
  re-enters the solo OR batched path later — the banked file is a
  normal :class:`WheelCheckpoint`, composing with PR-13 restart
  recovery (each slot of a killed batched server resumes from its own
  banked slice).
* **Per-tenant certification.**  ``bounds=True`` windows return one
  bound pack per tenant; each slot's :class:`BoundTracker` replicates
  the hub's typed-update semantics (minimizing: outer keeps max, inner
  keeps min, inner offered only when the frozen evaluation was feasible
  on the whole batch) under the batched source char ``'B'``.
* **SLO attribution.**  One fused dispatch serves K tenants; the shared
  wall is split by LIVE-ROW fraction (``flops.tenant_shares`` —
  ``S_t * max(1, executed_t)`` rows per tenant) and FLOPs are billed per
  tenant from the same flop model the solo megastep bills
  (:mod:`tpusppy.solvers.segmented`), so per-request SLO records stay
  comparable across the batched and time-sliced paths.

Observability: ``batching.joins`` / ``batching.evictions`` /
``batching.ghost_rows`` / ``batching.windows`` counters and the
``batching.slots`` gauge (doc/observability.md).

What the runner does NOT do: admission, QoS ordering, journaling,
deadlines — that is :class:`tpusppy.service.server.SolveServer`'s job
(the runner is deliberately schedule-free so kernel-level tests can
drive it without a server).
"""

from __future__ import annotations

import time
from math import inf

import numpy as np

from ..obs import metrics as _metrics
from ..obs import telemetry as _telemetry
from ..obs import trace as _trace
from ..obs.log import get_logger
from ..resilience import checkpoint as _ckpt
from ..solvers import flops as _flops
from ..solvers import segmented as _segmented
from ..solvers.integer import feas_slack as _feas_slack
from ..spbase import make_admm_settings

_log = get_logger("service.batching")

_CTR_JOINS = _metrics.counter("batching.joins")
_CTR_EVICTIONS = _metrics.counter("batching.evictions")
_CTR_GHOST_ROWS = _metrics.counter("batching.ghost_rows")
_CTR_WINDOWS = _metrics.counter("batching.windows")
_G_SLOTS = _metrics.gauge("batching.slots")

#: Source char for bound updates installed from the batched wheel —
#: joins the established glyph set ('*' default, 'M' megastep, 'I'
#: integer escalation, 'R' resume seed; doc/pipeline.md).
BATCH_SOURCE_CHAR = "B"

#: QoS classes (the explicit PR-12 debt): lower rank = admitted into a
#: free slot first.  Ties break on submission order, so same-class
#: requests keep today's FIFO semantics.
QOS_CLASSES = {"interactive": 0, "standard": 1, "batch": 2}


def qos_rank(qos) -> int:
    """Slot-assignment rank for a QoS class name (unknown -> standard)."""
    return QOS_CLASSES.get(str(qos or "standard"), QOS_CLASSES["standard"])


class BoundTracker:
    """Per-tenant bound state replicating the hub's typed-update
    semantics (minimizing: ``OuterBoundUpdate`` keeps the max,
    ``InnerBoundUpdate`` the min) for a tenant whose window bounds come
    from the batched kernel instead of a hub — source char ``'B'``."""

    def __init__(self, best_inner=inf, best_outer=-inf):
        self.best_inner = float(best_inner)
        self.best_outer = float(best_outer)

    def outer_update(self, v: float):
        v = float(v)
        if np.isfinite(v) and v > self.best_outer:
            self.best_outer = v

    def inner_update(self, v: float):
        v = float(v)
        if np.isfinite(v) and v < self.best_inner:
            self.best_inner = v

    def gaps(self):
        """(abs_gap, rel_gap) — the hub's ``compute_gaps`` arithmetic."""
        if not (np.isfinite(self.best_inner)
                and np.isfinite(self.best_outer)):
            return inf, inf
        abs_gap = self.best_inner - self.best_outer
        return abs_gap, abs_gap / (abs(self.best_outer) or 1.0)


class _Slot:
    """One tenant slot: live wheel state, or a finished tenant's inert
    residue serving as the ghost filler (structurally valid arrays the
    dead ``lax.cond`` branch can carry — values never read)."""

    __slots__ = ("rid", "dir", "arr", "state", "factors", "age", "iters",
                 "iter_limit", "convthresh", "tracker", "live", "batch",
                 "gate_misses", "next_rescue", "declines", "trace_id")

    def __init__(self, rid, tenant_dir, arr, state, iter_limit,
                 convthresh, tracker, iters=0, batch=None,
                 trace_id=None):
        self.rid = rid
        self.dir = tenant_dir
        self.arr = arr
        self.state = state
        self.factors = None
        self.age = inf          # forces a prox-on refresh at first window
        self.iters = int(iters)
        self.iter_limit = int(iter_limit)
        self.convthresh = float(convthresh)
        self.tracker = tracker
        self.live = True
        self.batch = batch      # host arrays, for the inner-bound rescue
        self.gate_misses = 0    # feasibility-gate miss cadence state
        self.next_rescue = 0    # (PHBase._maybe_inwheel_rescue semantics)
        self.declines = 0
        self.trace_id = trace_id


class BatchedFamilyRunner:
    """K-slot fused wheel for ONE shape family.

    Args:
      canon: any member's :class:`~tpusppy.service.canonical.CanonicalModel`
        — the family template (nonant indices, settings, shapes).  Each
        tenant still brings its OWN canonical model at :meth:`admit`
        (same family => same shapes; different numbers).
      opt_options: the family's resolved opt options (the canonical
        settings key — equal for every member by family equality).
      k_slots: slot count K.  The fused program's AOT key is
        (family, K); pick K once per runner (tune's "batched" verdict).
      axis: mesh axis name for the solver fns.
    """

    def __init__(self, canon, opt_options, k_slots, axis="scen"):
        from ..parallel import sharded

        self._sharded = sharded
        self.opt_options = dict(opt_options)
        self.settings = make_admm_settings(dict(opt_options),
                                           canon.bundling)
        self.dt = self.settings.jdtype()
        b = canon.batch
        self.S, self.n, self.m = (b.num_scenarios, b.num_vars,
                                  b.num_rows)
        self.nonant_idx = b.tree.nonant_indices
        self.k_slots = int(k_slots)
        self.default_rho = float(self.opt_options.get("defaultPHrho", 1.0))
        self.refresh_every = max(
            int(self.opt_options.get("solver_refresh_every", 16) or 16), 1)
        self.in_wheel = bool(self.opt_options.get("in_wheel_bounds"))
        self.feas_tol = max(
            float(self.opt_options.get("feas_tol", 1e-3)),
            10.0 * self.settings.eps_rel)
        # the in-scan acceptance ladder: the SAME tol_qp arithmetic the
        # solo wheel's frozen iterations accept under
        # (spopt._straggler_tols — parity demands one definition)
        floor = 10.0 * self.settings.eps_rel
        tol_lp = max(float(self.opt_options.get("straggler_tol", 1e-4)),
                     floor)
        if "straggler_tol_qp" in self.opt_options:
            self.accept_tol = max(
                float(self.opt_options["straggler_tol_qp"]), floor)
        elif "straggler_tol" in self.opt_options:
            self.accept_tol = tol_lp
        else:
            self.accept_tol = max(1e-2, tol_lp)
        fb = 1 if getattr(b, "A_shared", None) is not None else self.S
        self._sparse_factor = 1.0
        # watchdog: one scan step runs EVERY live slot's frozen sweep
        # back to back — the per-dispatch budget is the bucketed
        # (sum-over-slots) accounting at K copies of the family shape
        cap = _segmented.megastep_cap_multi(
            [(self.S, self.n, self.m, fb)] * self.k_slots,
            self.settings, bound_pass=self.in_wheel)
        self.n_window = max(1, min(self.refresh_every, int(cap)))
        self._refresh, _ = sharded.make_ph_step_pair(
            self.nonant_idx, self.settings, None, axis)
        self._mega = sharded.make_tenant_megastep(
            self.nonant_idx, self.settings, n_iters=self.n_window,
            donate=True, axis=axis, bounds=self.in_wheel)
        self.slots: list = [None] * self.k_slots
        self.windows = 0
        _G_SLOTS.set(float(self.k_slots))

    # ---- slot inventory -----------------------------------------------------
    def _find(self, rid):
        for s in self.slots:
            if s is not None and s.live and s.rid == rid:
                return s
        return None

    def has(self, rid) -> bool:
        return self._find(rid) is not None

    def live_rids(self) -> list:
        return [s.rid for s in self.slots if s is not None and s.live]

    def free_slots(self) -> int:
        return sum(1 for s in self.slots if s is None or not s.live)

    def tracker(self, rid) -> BoundTracker:
        return self._find(rid).tracker

    # ---- joins --------------------------------------------------------------
    def admit(self, rid, canon, tenant_dir, iter_limit, resume=True,
              best_inner=inf, best_outer=-inf, trace_id=None) -> dict:
        """Join ``rid`` into a free slot at this window boundary.

        ``resume=True`` seeds W/xbars/rho (+ banked bounds) from the
        tenant's newest checkpoint when one exists — a previously
        evicted (or solo-parked) tenant continues its SAME trajectory;
        the first prox-on refresh rebuilds the x/z/y/yx iterates, the
        adaptive-refresh resume idiom.  A fresh tenant runs Iter0 (plain
        objective, W=0, prox off) exactly like the solo wheel.

        ``trace_id`` (optional) carries the request's distributed-trace
        context into the slot: every per-window sample and lifecycle
        instant the runner records lands on the request's own track
        (``req:<rid>``) tagged with it, so evict->bank->rejoin keeps ONE
        trace across slot generations.

        Returns ``{"iteration", "resumed"}``."""
        from .. import spopt

        idx = None
        for i, s in enumerate(self.slots):
            if s is None or not s.live:
                idx = i
                break
        if idx is None:
            raise RuntimeError(f"no free slot for {rid!r} "
                               f"(K={self.k_slots})")
        arr = spopt.mega_arrays_for_batch(canon.batch, self.dt)
        state = self._sharded.init_state(arr, self.default_rho,
                                         self.settings)
        tracker = BoundTracker(best_inner=best_inner,
                               best_outer=best_outer)
        banked = _ckpt.load_latest(tenant_dir) if resume else None
        resumed = banked is not None and banked.W is not None
        it0 = 0
        if resumed:
            import jax.numpy as jnp

            state = state._replace(
                W=jnp.asarray(banked.W, self.dt),
                xbars=jnp.asarray(banked.xbars, self.dt),
                rho=jnp.asarray(banked.rho, self.dt))
            it0 = int(banked.iteration)
            tracker.inner_update(banked.best_inner)
            tracker.outer_update(banked.best_outer)
            for _, bd in (banked.spoke_bounds or {}).items():
                kind, val = bd[0], float(bd[1])
                (tracker.outer_update if kind == "outer"
                 else tracker.inner_update)(val)
        else:
            # Iter0: plain-objective solve (W=0, prox off); its adaptive
            # factors are DISCARDED (they factor the prox-off KKT) — the
            # first window's refresh builds the prox-on ones
            state, _, _ = self._refresh(state, arr, 0.0)
        slot = _Slot(rid, tenant_dir, arr, state, iter_limit,
                     float(self.opt_options.get("convthresh", -1.0)),
                     tracker, iters=it0, batch=canon.batch,
                     trace_id=trace_id)
        self.slots[idx] = slot
        _CTR_JOINS.inc(1)
        _telemetry.tenant_instant(rid, trace_id, "batch_join", slot=idx,
                                  resumed=resumed, iteration=it0)
        _log.info("batch join: %s -> slot %d (%s, iter %d)", rid, idx,
                  "resumed" if resumed else "fresh", it0)
        return {"iteration": it0, "resumed": resumed}

    # ---- evictions ----------------------------------------------------------
    def _bank(self, s) -> int:
        """Write one slot's W/xbars/rho + best bounds through the
        checkpoint seam — a normal :class:`WheelCheckpoint`, so solo
        resume, batched re-join and restart recovery all read it."""
        ck = _ckpt.WheelCheckpoint(
            iteration=s.iters,
            W=np.asarray(s.state.W), xbars=np.asarray(s.state.xbars),
            rho=np.asarray(s.state.rho),
            best_inner=s.tracker.best_inner,
            best_outer=s.tracker.best_outer,
            meta={"batched": True, "source": BATCH_SOURCE_CHAR})
        _ckpt.save(ck, _ckpt.checkpoint_path(s.dir, s.iters))
        _telemetry.tenant_instant(s.rid, s.trace_id, "batch_bank",
                                  iteration=s.iters)
        return s.iters

    def bank(self, rid) -> int:
        """Mid-run checkpoint of a LIVE slot (the server's
        ``checkpoint_every_secs`` cadence inside a batch) — bounds what
        a server crash can cost a batched tenant, exactly like the solo
        wheel's mid-slice cadence.  The slot keeps running."""
        return self._bank(self._find(rid))

    def evict(self, rid, bank=True) -> int:
        """Evict ``rid``'s slot at this window boundary; ``bank=True``
        banks its state first (see :meth:`bank`).  The slot's arrays
        stay behind as the ghost filler.  Returns the slot's
        iteration."""
        s = self._find(rid)
        if s is None:
            raise KeyError(f"{rid!r} holds no live slot")
        if bank:
            self._bank(s)
        s.live = False
        s.batch = None
        _CTR_EVICTIONS.inc(1)
        _telemetry.tenant_instant(rid, s.trace_id, "batch_evict",
                                  iteration=s.iters, banked=bank)
        _log.info("batch evict: %s at iter %d (%s)", rid, s.iters,
                  "banked" if bank else "unbanked")
        return s.iters

    def complete(self, rid):
        """Retire a FINISHED tenant's slot (no eviction counter, no
        checkpoint — the record carries the result); the residue stays
        as ghost filler until a join overwrites it, but the HOST arrays
        are released (a long-lived runner must not retain every
        tenant's coefficient tensors)."""
        s = self._find(rid)
        if s is not None:
            s.live = False
            s.batch = None

    # ---- the inner-bound host rescue ----------------------------------------
    def _maybe_rescue(self, s):
        """Per-slot twin of ``PHBase._maybe_inwheel_rescue``: when the
        device feasibility gate misses, evaluate the SAME xhat candidate
        (``clamp_candidate`` at the in-wheel threshold on the slot's own
        xbars) by per-scenario host-exact solves and offer the certified
        expected objective as the slot's inner bound — first miss, then
        every ``in_wheel_rescue_every``-th, declines retried with the
        growing backoff.  Only non-integer homogeneous families are
        admitted into a batch, so the candidate value is exact, never a
        relaxation."""
        if not self.opt_options.get("in_wheel_host_rescue", True):
            return
        every = max(1, int(self.opt_options.get("in_wheel_rescue_every",
                                                4)))
        miss = s.gate_misses
        s.gate_misses = miss + 1
        if miss < s.next_rescue:
            return
        ib = self._eval_candidate_host(s)
        if ib is None:
            s.declines += 1
            s.next_rescue = miss + min(s.declines, every)
        else:
            s.next_rescue = miss + every
            s.tracker.inner_update(ib)

    def _eval_candidate_host(self, s):
        """Expected objective of the slot's clamped xhat candidate via
        per-scenario host solves (None = infeasible / solver error — a
        failed rescue declines, never kills the batch)."""
        from ..cylinders.xhatxbar_bounder import clamp_candidate
        from ..solvers import scipy_backend

        b = s.batch
        if b is None:
            return None
        _metrics.inc("megastep.bound_rescues")
        try:
            nid = b.tree.nonant_indices
            xbars = np.asarray(s.state.xbars, dtype=float)
            thr = float(self.opt_options.get("in_wheel_xhat_threshold",
                                             0.5))
            _, lb, ub = clamp_candidate(b, nid, xbars, thr)
            probs = np.asarray(b.tree.scen_prob, dtype=float)
            objs = []
            for i in range(b.num_scenarios):
                q2s = np.asarray(b.q2[i])
                if q2s.any():
                    r = scipy_backend.solve_qp_with_duals(
                        b.c[i], q2s, b.A[i], b.cl[i], b.cu[i],
                        lb[i], ub[i], const=b.const[i])
                else:
                    r = scipy_backend.solve_lp(
                        b.c[i], b.A[i], b.cl[i], b.cu[i],
                        lb[i], ub[i], const=b.const[i])
                objs.append(r.obj)
            objs = np.asarray(objs, dtype=float)
            if not np.isfinite(objs).all():
                return None
            return float(probs @ objs)
        except Exception as e:
            _log.warning("batched inner rescue failed (%r) — declined",
                         e)
            return None

    # ---- the fused window ---------------------------------------------------
    def window(self) -> dict:
        """Run ONE fused window over every live slot; returns
        ``{rid: report}`` with per-tenant ``executed`` / cumulative
        ``iters`` / ``outer`` / ``inner`` / ``abs_gap`` / ``rel_gap`` /
        ``wall_s`` (live-row-fraction share of the shared dispatch) /
        ``flops`` (this tenant's own flop-model bill) /
        ``exhausted`` (iteration budget spent).

        Boundary semantics: joins/evictions happen BETWEEN calls —
        inside the call the slot population is frozen, and a slot that
        certifies mid-window simply stops iterating (its per-tenant
        ``stopped`` mask) without perturbing siblings."""
        import jax.numpy as jnp

        sharded = self._sharded
        live = [s for s in self.slots if s is not None and s.live]
        if not live:
            return {}
        t0 = time.monotonic()
        # per-slot adaptive refresh where due — the same AOT-cached
        # refresh program the solo wheel runs, so trajectory AND warm
        # binding are shared with the time-sliced path
        for s in live:
            if s.factors is None or s.age >= self.refresh_every:
                if s.iters >= s.iter_limit:
                    continue           # budget spent: ride inert below
                s.state, _, s.factors = self._refresh(s.state, s.arr, 1.0)
                s.age = 0
                s.iters += 1
        # ghost fillers: empty slots carry a live slot's arrays (shapes
        # only — the dead branch never reads values) + their own state
        # buffers (the donated-states tuple must not alias)
        donor = live[0]
        for i, s in enumerate(self.slots):
            if s is None:
                self.slots[i] = s = _Slot(
                    None, None, donor.arr,
                    sharded.init_state(donor.arr, self.default_rho,
                                       self.settings),
                    0, -1.0, BoundTracker())
                s.live = False
            if s.factors is None:
                s.factors = donor.factors
        slots = self.slots
        n_ghost = sum(1 for s in slots if not s.live)
        live_mask = np.array([s.live for s in slots])
        n_live = np.array(
            [max(0, min(self.n_window, self.refresh_every - s.age,
                        s.iter_limit - s.iters)) if s.live else 0
             for s in slots], dtype=np.int32)
        convthresh = np.array([s.convthresh for s in slots])
        args = [tuple(s.state for s in slots),
                tuple(s.arr for s in slots), 1.0,
                tuple(s.factors for s in slots),
                convthresh, n_live, self.accept_tol, live_mask]
        if self.in_wheel:
            args += [live_mask, self.feas_tol]
        with _trace.span("batch", "window", live=len(live),
                         k=self.k_slots):
            states, packed = self._mega(*args)
        meas = sharded.tenant_megastep_unpack(
            np.asarray(packed), self.n_window, self.S, len(slots),
            bounds=self.in_wheel)
        wall = time.monotonic() - t0
        self.windows += 1
        _CTR_WINDOWS.inc(1)
        _CTR_GHOST_ROWS.inc(float(n_ghost * self.S))
        # shared-dispatch attribution: wall splits by live-row fraction
        rows = [self.S * max(1, meas["executed"][i]) if s.live else 0
                for i, s in enumerate(slots)]
        shares = _flops.tenant_shares(rows)
        slack = _feas_slack(self.S, self.dt)
        reports = {}
        first = True
        for i, s in enumerate(slots):
            s.state = states[i]
            if not s.live:
                continue
            ex = int(meas["executed"][i])
            s.iters += ex
            s.age += ex
            if meas["refresh_hit"][i]:
                # divergence freeze: the rejected iterate was discarded
                # in-scan; force a refresh at the next window boundary
                s.age = self.refresh_every
            fl = 0.0
            if ex:
                sweeps = float(np.mean(meas["iters"][i][:ex]))
                _segmented.bill_megastep(self.S, self.n, self.m, ex,
                                         sweeps, count_dispatch=first)
                fl += _flops.megastep_flops(self.S, self.n, self.m, ex,
                                            sweeps)
                first = False
            if self.in_wheel and meas["bound_computed"][i]:
                bsweeps = float(meas["bound_sweeps"][i])
                _segmented.bill_bound_pass(self.S, self.n, self.m,
                                           bsweeps, count_pass=(i == 0))
                fl += _flops.bound_pass_flops(self.S, self.n, self.m,
                                              bsweeps)
                s.tracker.outer_update(meas["bound_outer"][i])
                # the Xhat_Eval all-scenarios gate, per tenant: the
                # frozen xhat evaluation certifies an inner bound only
                # when the whole batch was feasible; a miss falls back
                # to the per-slot host-exact rescue (its own cadence)
                if meas["bound_inner_feas"][i] >= 1.0 - slack:
                    s.tracker.inner_update(meas["bound_inner_obj"][i])
                else:
                    self._maybe_rescue(s)
            abs_gap, rel_gap = s.tracker.gaps()
            if _trace.enabled():
                # per-request trace series (source 'B'): report.py
                # buckets these by the payload's request_id, so a
                # batched run's gap-vs-wall is no longer empty
                for nm, v in (("rel_gap", rel_gap), ("abs_gap", abs_gap),
                              ("best_outer", s.tracker.best_outer),
                              ("best_inner", s.tracker.best_inner)):
                    if np.isfinite(v):
                        _telemetry.tenant_counter(s.rid, s.trace_id,
                                                  nm, v, source="B")
            reports[s.rid] = {
                "executed": ex, "iters": s.iters,
                "outer": s.tracker.best_outer,
                "inner": s.tracker.best_inner,
                "abs_gap": abs_gap, "rel_gap": rel_gap,
                "wall_s": wall * shares[i], "flops": fl,
                "exhausted": s.iters >= s.iter_limit,
            }
        return reports
