"""Hub communicators: bound bookkeeping, gap termination, spoke traffic.

TPU-native analogue of ``mpisppy/cylinders/hub.py:23-771``.  The hub owns the
optimization object (PH here), pushes W / nonant / bound payloads into the
per-spoke outbound mailboxes each ``sync()`` (hub.py:501-514), pulls spoke
bounds with write-id freshness checks (hub.py:174-200,396-436), tracks the
best inner/outer bounds, and terminates the wheel on ``rel_gap`` / ``abs_gap``
/ ``max_stalled_iters`` (hub.py:77-161) by broadcasting the kill sentinel
(hub.py:438-450).

Bound source chars: spokes report through their class chars (L/X/I/O/...),
``'T'`` is the trivial bound, ``'R'`` a checkpoint re-seed, ``'B'`` the
Benders root, and ``'M'`` an IN-WHEEL bound — the megastep's fused bound
pass (doc/pipeline.md "In-wheel certification") landing through the same
typed ``OuterBoundUpdate``/``InnerBoundUpdate`` path, so gap termination
and the gap-vs-wall trace treat in-wheel and spoke bounds identically; a
single-cylinder wheel certifies with zero spoke device programs.
"""

from __future__ import annotations

from math import inf

import numpy as np

from .. import global_toc
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from .spcommunicator import SPCommunicator
from .spoke import ConvergerSpokeType


class Hub(SPCommunicator):
    """Base hub (hub.py:23-450)."""

    def __init__(self, spbase_object, strata_rank, fabric, spokes,
                 options=None):
        super().__init__(spbase_object, strata_rank, fabric, options)
        self.spokes = list(spokes)           # list of dicts with spoke_class
        self.remote_write_ids = {}           # spoke idx -> last accepted id
        self.latest_ib_char = None
        self.latest_ob_char = None
        self.print_init = True
        self.stalled_iter_cnt = 0
        self.last_gap = inf
        # resilience attachments (tpusppy.resilience): the wheel spinner
        # wires a SpokeSupervisor (degradation) and a CheckpointManager
        # (async snapshots) when configured; both stay None otherwise
        self.supervisor = None
        self._ckpt_mgr = None
        self.latest_spoke_bounds = {}        # idx -> last bound read (meta)
        self.resumed_from_iteration = None
        # tenant preemption (tpusppy.service, doc/serving.md): True once
        # options["preempt_check"] asked this wheel to park — the run
        # terminated at a window boundary WITHOUT certifying, and its
        # final checkpoint is the parked state a later resume continues
        self.preempted = False

    # ---- resilience (tpusppy.resilience) ------------------------------------
    def attach_supervisor(self, sup):
        self.supervisor = sup

    def attach_checkpointer(self, mgr):
        self._ckpt_mgr = mgr

    def seed_resume(self, ckpt):
        """Re-seed the hub's bounds from a checkpoint (call after
        ``setup_hub``).  Bound updates only ever improve on these, so the
        certified gap trajectory is monotone across the restart.

        Per-spoke bounds re-seed by their STORED kind ([kind, bound]
        entries — the kind, not the resumed wheel's slot assignment,
        decides whether a value may tighten the outer or the inner side,
        so a reordered/trimmed spoke list can never install an outer
        bound as an incumbent).  Kind-less legacy floats are skipped —
        the global bests already carry their contribution."""
        if np.isfinite(ckpt.best_outer):
            self.OuterBoundUpdate(float(ckpt.best_outer), char='R')
        if np.isfinite(ckpt.best_inner):
            self.InnerBoundUpdate(float(ckpt.best_inner), char='R')
        for key, entry in (ckpt.spoke_bounds or {}).items():
            if not (isinstance(entry, (list, tuple)) and len(entry) == 2):
                continue
            kind, b = entry
            try:
                idx, b = int(key), float(b)
            except (TypeError, ValueError):
                continue
            if not np.isfinite(b):
                continue
            self.latest_spoke_bounds[idx] = b
            # idx only picks the display char, and only when the resumed
            # slot still has the same role
            if kind == "outer":
                same = idx in self.outerbound_spoke_indices
                self.OuterBoundUpdate(b, idx if same else None, char='R')
            elif kind == "inner":
                same = idx in self.innerbound_spoke_indices
                self.InnerBoundUpdate(b, idx if same else None, char='R')
        self.resumed_from_iteration = int(ckpt.iteration)

    def checkpoint_due(self, iteration) -> bool:
        """Whether the next :meth:`sync` will capture a checkpoint at
        ``iteration`` — the device-resident wheel posture asks BEFORE
        syncing so it can refresh the host mirrors the capture reads
        (the capture itself stays pinned zero-fetch)."""
        return (self._ckpt_mgr is not None
                and self._ckpt_mgr._due(int(iteration)))

    def _resilience_tick(self):
        """Per-sync health + checkpoint pass: observe spoke liveness and
        capture a snapshot when the cadence is due.  The snapshot reads
        only host-resident PH state (capture_ph), so this adds zero
        blocking fetches to the dispatch decision path."""
        if self.supervisor is not None:
            self.supervisor.observe()
        if self._ckpt_mgr is not None:
            from ..resilience import checkpoint as _ckpt
            from ..resilience import supervisor as _sup

            _sup.heartbeat("hub")
            if getattr(self.opt, "_host_state_stale", False):
                # device-resident posture (doc/scaling.md): the host
                # mirrors are stale mid-window.  The boundary pre-sync
                # (PHBase._spcomm_needs_host_state) refreshes them when
                # checkpoint_due() fires, but a WALL-CLOCK cadence can
                # cross its threshold between that check and this tick —
                # capturing here would stamp one-window-old W/xbars with
                # the current iteration.  Skip without advancing the
                # cadence: the next boundary's due check pre-syncs and
                # the capture lands fresh.
                return
            try:
                self._ckpt_mgr.maybe_capture(
                    self.current_iteration(),
                    lambda: _ckpt.capture_ph(self.opt, hub=self))
            except Exception as e:
                # a capture failure (host OOM copying (S, K) arrays, a
                # transfer-guard trip on an exotic opt) costs the run's
                # RESUMABILITY, never the run — same policy as the write
                # path and the final capture
                _metrics.inc("checkpoint.capture_errors")
                if not getattr(self, "_ckpt_err_warned", False):
                    self._ckpt_err_warned = True
                    global_toc(
                        f"WARNING: checkpoint capture failed (run "
                        f"continues, resumability degraded): {e!r}", True)

    # ---- spoke typing (hub.py:297-344) --------------------------------------
    def initialize_spoke_indices(self):
        self.outerbound_spoke_indices = set()
        self.innerbound_spoke_indices = set()
        self.nonant_spoke_indices = set()
        self.w_spoke_indices = set()
        self.outerbound_spoke_chars = {}
        self.innerbound_spoke_chars = {}
        for i, spoke in enumerate(self.spokes):
            cls = spoke["spoke_class"]
            for cst in getattr(cls, "converger_spoke_types", ()):
                if cst == ConvergerSpokeType.OUTER_BOUND:
                    self.outerbound_spoke_indices.add(i + 1)
                    self.outerbound_spoke_chars[i + 1] = cls.converger_spoke_char
                elif cst == ConvergerSpokeType.INNER_BOUND:
                    self.innerbound_spoke_indices.add(i + 1)
                    self.innerbound_spoke_chars[i + 1] = cls.converger_spoke_char
                elif cst == ConvergerSpokeType.W_GETTER:
                    self.w_spoke_indices.add(i + 1)
                elif cst == ConvergerSpokeType.NONANT_GETTER:
                    self.nonant_spoke_indices.add(i + 1)
        self.bounds_only_indices = (
            (self.outerbound_spoke_indices | self.innerbound_spoke_indices)
            - (self.w_spoke_indices | self.nonant_spoke_indices)
        )
        self.has_outerbound_spokes = bool(self.outerbound_spoke_indices)
        self.has_innerbound_spokes = bool(self.innerbound_spoke_indices)
        self.has_nonant_spokes = bool(self.nonant_spoke_indices)
        self.has_w_spokes = bool(self.w_spoke_indices)
        self.has_bounds_only_spokes = bool(self.bounds_only_indices)

    def initialize_bound_values(self):
        if self.opt.is_minimizing:
            self.BestInnerBound, self.BestOuterBound = inf, -inf
            self._ib_better = lambda new, old: new < old
            self._ob_better = lambda new, old: new > old
        else:
            self.BestInnerBound, self.BestOuterBound = -inf, inf
            self._ib_better = lambda new, old: new > old
            self._ob_better = lambda new, old: new < old

    # ---- gap / termination (hub.py:77-161) ----------------------------------
    def compute_gaps(self):
        if self.opt.is_minimizing:
            abs_gap = self.BestInnerBound - self.BestOuterBound
        else:
            abs_gap = self.BestOuterBound - self.BestInnerBound
        if np.isfinite(abs_gap) and np.isfinite(self.BestOuterBound):
            # a legitimately-zero outer bound (optimum at 0) falls back to
            # the absolute gap as the "relative" gap so rel_gap termination
            # still fires; the reference (hub.py:88-97) returns inf there
            # and can never terminate on rel_gap.  Nonzero bounds keep the
            # reference's convention exactly.
            rel_gap = abs_gap / (abs(self.BestOuterBound) or 1.0)
        else:
            rel_gap = inf
        if _trace.enabled() and np.isfinite(rel_gap):
            # the gap-vs-wall series of the flight recorder: one sample
            # per gap computation, so the report's array ends at the
            # final certified gap (report.py collects "rel_gap"/"abs_gap")
            _trace.counter("hub", "rel_gap", rel_gap)
            _trace.counter("hub", "abs_gap", abs_gap)
        # live progress seam (doc/observability.md): the solve service
        # plants options["progress_cb"] the way it plants preempt_check;
        # the callback dedupes, so calling on EVERY gap computation is
        # fine — and a progress fault must never kill a solve
        cb = self.options.get("progress_cb")
        if cb is not None:
            try:
                cb(abs_gap, rel_gap, self.BestOuterBound,
                   self.BestInnerBound, self.current_iteration())
            except Exception:
                pass
        return abs_gap, rel_gap

    def _check_preempt(self) -> bool:
        """Tenant preemption (doc/serving.md): the scheduler's
        ``options["preempt_check"]`` fires between iterations — at
        exactly the window boundaries checkpoint capture already owns —
        and a True verdict means PARK: the wheel tears down normally,
        the final checkpoint banks (W, xbars, rho, bounds), and the
        resumed run continues with bounds monotone by the
        ``seed_resume`` contract."""
        # getattr: unit tests build bare hubs via __new__ (no __init__)
        if not hasattr(self, "preempted"):
            self.preempted = False
        pc = self.options.get("preempt_check")
        if pc is not None and not self.preempted and pc():
            self.preempted = True
            _metrics.inc("service.preemptions")
            global_toc("Hub preempted: parking wheel at window boundary",
                       True)
            if _trace.enabled():
                _trace.instant("hub", "preempt",
                               iter=self.current_iteration(),
                               best_outer=self.BestOuterBound,
                               best_inner=self.BestInnerBound)
        return self.preempted

    def determine_termination(self) -> bool:
        opts = self.options
        if not any(k in opts for k in ("rel_gap", "abs_gap",
                                       "max_stalled_iters")):
            # no gap targets: preemption is the only possible verdict
            return self._check_preempt()
        abs_gap, rel_gap = self.compute_gaps()
        rel_ok = "rel_gap" in opts and rel_gap <= opts["rel_gap"]
        abs_ok = "abs_gap" in opts and abs_gap <= opts["abs_gap"]
        stalled = False
        if "max_stalled_iters" in opts:
            if abs_gap < self.last_gap:
                self.last_gap = abs_gap
                self.stalled_iter_cnt = 0
            else:
                self.stalled_iter_cnt += 1
                stalled = self.stalled_iter_cnt >= opts["max_stalled_iters"]
        if abs_ok:
            global_toc(f"Terminating: absolute gap {abs_gap:.4f}", True)
        if rel_ok:
            global_toc(f"Terminating: relative gap {rel_gap * 100:.3f}%", True)
        if stalled:
            global_toc(f"Terminating: stalled {self.stalled_iter_cnt} iters", True)
        if (abs_ok or rel_ok or stalled) and _trace.enabled():
            # the termination verdict WITH its evidence, on the timeline
            _trace.instant(
                "hub", "terminate",
                reason=("abs_gap" if abs_ok else
                        "rel_gap" if rel_ok else "stalled"),
                abs_gap=abs_gap, rel_gap=rel_gap,
                best_outer=self.BestOuterBound,
                best_inner=self.BestInnerBound,
                stalled_iters=self.stalled_iter_cnt)
        if abs_ok or rel_ok or stalled:
            # certification outranks preemption: a wheel whose gap just
            # closed must COMPLETE, not pay a park/resume cycle for a
            # quantum that expired in the same window
            return True
        return self._check_preempt()

    # ---- screen trace (hub.py:111-123) --------------------------------------
    def _update_string(self):
        ob = self.latest_ob_char or ' '
        ib = self.latest_ib_char or ' '
        return f"{ob} {ib}"

    def screen_trace(self):
        it = self.current_iteration()
        abs_gap, rel_gap = self.compute_gaps()
        if self.print_init:
            global_toc(
                f'{"Iter.":>5s}     {"Best Bound":>14s}  {"Best Incumbent":>14s}'
                f'  {"Rel. Gap":>12s}  {"Abs. Gap":>14s}', True)
            self.print_init = False
        global_toc(
            f"{it:5d} {self._update_string()} {self.BestOuterBound:14.4f}  "
            f"{self.BestInnerBound:14.4f}  {rel_gap * 100:12.3f}%  "
            f"{abs_gap:14.4f}", True)
        self.latest_ib_char = None
        self.latest_ob_char = None

    # ---- mailbox traffic (hub.py:370-436) -----------------------------------
    def hub_to_spoke(self, values, idx: int):
        self.fabric.to_spoke[idx].put(values)

    def hub_to_spoke_versioned(self, idx: int, token, build):
        """Put that SKIPS when the payload source (``token``) has not
        advanced since the last send to this spoke: redundant Puts bump
        write-ids and make spokes recompute on data they already acted on
        (acute in the hub linger loop, which polls sync() twice a
        second).  ``build`` is a zero-arg payload constructor, called only
        when a send actually happens.  Transports without versioned puts
        (the TCP window fabric) fall back to hub-side token tracking."""
        mb = self.fabric.to_spoke[idx]
        if hasattr(mb, "put_versioned"):
            mb.put_versioned(token, build)
            return
        sent = getattr(self, "_sent_tokens", None)
        if sent is None:
            sent = self._sent_tokens = {}
        if sent.get(idx) == token:
            return
        self.hub_to_spoke(build(), idx)
        sent[idx] = token

    def hub_from_spoke(self, idx: int):
        """Returns (payload, True) when the spoke's write-id is fresh."""
        data, wid = self.fabric.to_hub[idx].get()
        last = self.remote_write_ids.get(idx, 0)
        if wid > last or wid < 0:
            self.remote_write_ids[idx] = wid
            return data, True
        return data, False

    def receive_outerbounds(self):
        # lost spokes are still READ (a bound posted before death is
        # valid); loss only stops the hub waiting on them (linger/join)
        for idx in self.outerbound_spoke_indices:
            data, is_new = self.hub_from_spoke(idx)
            if is_new:
                self.latest_spoke_bounds[idx] = float(data[0])
                self.OuterBoundUpdate(float(data[0]), idx)

    def receive_innerbounds(self):
        for idx in self.innerbound_spoke_indices:
            data, is_new = self.hub_from_spoke(idx)
            if is_new:
                self.latest_spoke_bounds[idx] = float(data[0])
                self.InnerBoundUpdate(float(data[0]), idx)

    def OuterBoundUpdate(self, new_bound, idx=None, char='*'):
        if self._ob_better(new_bound, self.BestOuterBound):
            old = self.BestOuterBound
            self.latest_ob_char = (
                char if idx is None else self.outerbound_spoke_chars[idx]
            )
            self.BestOuterBound = new_bound
            _metrics.inc("hub.outer_bound_updates")
            if _trace.enabled():
                _trace.instant("hub", "outer_bound_update", old=old,
                               new=new_bound, spoke=idx, char=char)
                _trace.counter("hub", "best_outer", new_bound)
        return self.BestOuterBound

    def InnerBoundUpdate(self, new_bound, idx=None, char='*'):
        if self._ib_better(new_bound, self.BestInnerBound):
            old = self.BestInnerBound
            self.latest_ib_char = (
                char if idx is None else self.innerbound_spoke_chars[idx]
            )
            self.BestInnerBound = new_bound
            _metrics.inc("hub.inner_bound_updates")
            if _trace.enabled():
                _trace.instant("hub", "inner_bound_update", old=old,
                               new=new_bound, spoke=idx, char=char)
                _trace.counter("hub", "best_inner", new_bound)
        return self.BestInnerBound

    def send_terminate(self):
        self.fabric.send_terminate()

    def hub_finalize(self):
        if self.has_outerbound_spokes:
            self.receive_outerbounds()
        if self.has_innerbound_spokes:
            self.receive_innerbounds()
        self.print_init = True
        global_toc("Statistics at termination", True)
        self.screen_trace()

    def current_iteration(self):
        raise NotImplementedError


class PHHub(Hub):
    """PH-flavored hub (hub.py:453-598): sends W and nonants, receives bounds.

    Payload layouts (flat float64, mirroring the reference buffers):
      W spokes:       [W.ravel() (S*K), BestOuterBound, BestInnerBound]
      nonant spokes:  [xk.ravel() (S*K), BestOuterBound, BestInnerBound]
      bounds-only:    [BestOuterBound, BestInnerBound]
    """

    def setup_hub(self):
        self.initialize_spoke_indices()
        self.initialize_bound_values()
        if self.outerbound_spoke_indices & self.innerbound_spoke_indices:
            raise RuntimeError(
                "A spoke providing both inner and outer bounds is unsupported"
            )
        if self.w_spoke_indices & self.nonant_spoke_indices:
            raise RuntimeError(
                "A spoke needing both Ws and nonants is unsupported"
            )

    def sync(self):
        with _trace.phase("sync"):
            if self.has_w_spokes:
                self.send_ws()
            if self.has_nonant_spokes:
                self.send_nonants()
            if self.has_bounds_only_spokes:
                self.send_boundsout()
            if self.has_outerbound_spokes:
                self.receive_outerbounds()
            if self.has_innerbound_spokes:
                self.receive_innerbounds()
        self._resilience_tick()

    sync_with_spokes = sync

    def is_converged(self):
        # first PAST-THE-BASE iteration: resumed runs offer the (re-derived)
        # trivial bound too — the update keeps whichever is better
        if self.opt._iter - getattr(self.opt, "_iter_base", 0) == 1:
            self.OuterBoundUpdate(self.opt.trivial_bound, char='T')
        # in-hub xhat extensions land their incumbents on the opt object
        bib = getattr(self.opt, "best_inner_bound", None)
        if bib is not None and np.isfinite(bib):
            self.InnerBoundUpdate(float(bib), char='X')
        self.screen_trace()
        if not self.has_innerbound_spokes and not np.isfinite(
                self.BestInnerBound):
            # a park request must still land: preemption is the ONE
            # termination that needs no bounds at all (gap termination
            # stays blocked — the stall counter must not advance while
            # no incumbent exists)
            return self._check_preempt()
        return self.determine_termination()

    def current_iteration(self):
        return self.opt._iter

    def main(self):
        self.opt.ph_main(finalize=False)
        self._linger()

    def _linger(self):
        """Keep syncing after the hub's own iterations finish, harvesting
        late spoke bounds until the gap certifies or ``linger_secs`` pass.

        The reference hub's iterations each take an external-MIP-solve long,
        so spokes get wall-time for free; our iterations are milliseconds,
        and a hub that exits immediately throws away whatever the spokes are
        mid-way through computing (acute for cross-process spokes that
        cold-start).  Lingering costs idle time only and can only improve
        the certified gap.
        """
        import time

        linger = float(self.options.get("linger_secs", 0.0))
        if linger <= 0.0 or not self.spokes:
            return
        # nudge cadence: the versioned puts skip identical state, so
        # without an advancing epoch the spokes would idle for the whole
        # linger window after their first non-improving round; a re-send
        # every ``linger_nudge_secs`` keeps their warm-started refinement
        # going at a fraction of the old every-poll Put traffic
        nudge = float(self.options.get("linger_nudge_secs", 2.0))
        with _trace.phase("linger"):
            t0 = time.time()
            last_trace = 0.0
            while time.time() - t0 < linger:
                if self.supervisor is not None and self.supervisor.all_lost():
                    # nobody left to harvest from: idling out the linger
                    # budget would only delay the (already best-known) result
                    global_toc("Hub linger: all spokes lost — ending harvest",
                               True)
                    break
                self._nudge_epoch = int((time.time() - t0) / max(nudge, 0.25))
                self.sync()
                # quiet convergence check (is_converged prints a trace row per
                # call — at poll frequency that floods the screen); trace at
                # most every 5s
                if time.time() - last_trace > 5.0:
                    last_trace = time.time()
                    if self.is_converged():
                        global_toc("Hub linger: gap certified", True)
                        break
                elif self.determine_termination():
                    global_toc("Hub linger: gap certified", True)
                    break
                time.sleep(0.5)

    def finalize(self):
        return self.opt.post_loops()

    def _state_token(self, kind):
        """Freshness token for outbound payloads: the opt's PH state
        version (bumped by solves / W updates, frozen during linger)
        plus the bounds that ride every payload, plus the linger NUDGE
        epoch — during the linger harvest a slow periodic re-send of the
        (unchanged) final state keeps spokes refining on it (their
        warm-started solves tighten bounds across re-runs), without the
        old 2x/sec redundant Puts during the hot loop."""
        return (kind, getattr(self.opt, "sync_version", None),
                getattr(self, "_nudge_epoch", 0),
                self.BestOuterBound, self.BestInnerBound)

    @staticmethod
    def _build_once(build):
        """Memoize a payload constructor for one send round: the payload
        is identical for every spoke of the round, and Mailbox.put copies
        it into each buffer — assemble it at most once even when several
        spokes accept the token."""
        box = []

        def cached():
            if not box:
                box.append(build())
            return box[0]

        return cached

    def send_ws(self):
        build = self._build_once(lambda: np.concatenate(
            [np.asarray(self.opt.W, dtype=np.float64).ravel(),
             [self.BestOuterBound, self.BestInnerBound]]))
        token = self._state_token("W")
        for idx in self.w_spoke_indices:
            self.hub_to_spoke_versioned(idx, token, build)

    def _nonant_payload(self):
        xk = (self.opt._nonants_cached()
              if hasattr(self.opt, "_nonants_cached")
              else self.opt.nonants_of(self.opt.local_x))
        return np.concatenate(
            [np.asarray(xk, dtype=np.float64).ravel(),
             [self.BestOuterBound, self.BestInnerBound]]
        )

    def send_nonants(self):
        token = self._state_token("nonants")
        build = self._build_once(self._nonant_payload)
        for idx in self.nonant_spoke_indices:
            self.hub_to_spoke_versioned(idx, token, build)

    def send_boundsout(self):
        token = self._state_token("bounds")
        build = self._build_once(
            lambda: np.array([self.BestOuterBound, self.BestInnerBound]))
        for idx in self.bounds_only_indices:
            self.hub_to_spoke_versioned(idx, token, build)


class CrossScenarioHub(PHHub):
    """PH hub that additionally feeds nonants to cross-scenario cut spokes
    and routes their cut payloads to the CrossScenarioExtension
    (cross_scen_hub.py:11-156)."""

    def setup_hub(self):
        super().setup_hub()
        from .cross_scen_spoke import CrossScenarioCutSpoke

        self.cs_spoke_indices = {
            i + 1 for i, sd in enumerate(self.spokes)
            if sd["spoke_class"] is CrossScenarioCutSpoke
        }

    def sync(self):
        super().sync()
        if not self.cs_spoke_indices:
            return
        token = self._state_token("cs-nonants")
        build = self._build_once(self._nonant_payload)
        S = self.opt.batch.num_scenarios
        K = self.opt.nonant_length
        ext = getattr(self.opt, "extobject", None)
        for idx in self.cs_spoke_indices:
            self.hub_to_spoke_versioned(idx, token, build)
            data, is_new = self.hub_from_spoke(idx)
            if is_new and ext is not None and hasattr(ext, "add_cuts"):
                ext.add_cuts(data.reshape(S, K + 1))

    sync_with_spokes = sync


class APHHub(PHHub):
    """APH-flavored hub (hub.py:691-771).  The reference's variant skips
    cylinder barriers in Put/Get; our mailboxes are barrier-free already, so
    only the driver differs."""

    def main(self):
        self.opt.APH_main(spcomm=self, finalize=False)

    def finalize(self):
        return self.opt.post_loops()


class LShapedHub(Hub):
    """L-shaped-flavored hub (hub.py:600-689): nonant-only sync, outer bound
    from the Benders root objective."""

    def setup_hub(self):
        self.initialize_spoke_indices()
        self.initialize_bound_values()
        if self.has_w_spokes:
            raise RuntimeError("LShaped hub does not compute dual weights (Ws)")
        if self.outerbound_spoke_indices & self.innerbound_spoke_indices:
            raise RuntimeError(
                "A spoke providing both inner and outer bounds is unsupported"
            )
        self._iter_count = 0

    def sync(self, send_nonants=True):
        self._iter_count += 1
        if send_nonants and self.has_nonant_spokes:
            self.send_nonants()
        if self.has_bounds_only_spokes:
            self.send_boundsout()
        if self.has_outerbound_spokes:
            self.receive_outerbounds()
        if self.has_innerbound_spokes:
            self.receive_innerbounds()
        self._resilience_tick()   # Benders roots have no W: capture skips

    def is_converged(self):
        # the Benders root objective is itself a valid outer bound
        ob = getattr(self.opt, "outer_bound", None)
        if ob is not None and np.isfinite(ob):
            self.OuterBoundUpdate(float(ob), char='B')
        ib = getattr(self.opt, "inner_bound", None)
        if ib is not None and np.isfinite(ib):
            self.InnerBoundUpdate(float(ib), char='B')
        self.screen_trace()
        return self.determine_termination()

    def current_iteration(self):
        return self._iter_count

    def main(self):
        self.opt.lshaped_algorithm()

    def send_nonants(self):
        """Broadcast the root x to nonant spokes (every scenario row gets the
        same candidate — it is already nonanticipative)."""
        x = self.opt.root_x
        if x is None:
            return
        S = self.opt.batch.num_scenarios
        xk = np.broadcast_to(np.asarray(x, dtype=np.float64),
                             (S, x.shape[0]))
        payload = np.concatenate(
            [xk.ravel(), [self.BestOuterBound, self.BestInnerBound]]
        )
        for idx in self.nonant_spoke_indices:
            self.hub_to_spoke(payload, idx)
