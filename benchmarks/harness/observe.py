"""Observation seams: subclasses that only read.

Copied from ``chip_smoke.py`` (PR 26) so that a later change to that script
cannot move the yardstick.  An Extension would switch the hub's megastep
off; a subclass that only reads does not, so the wheel that is measured is
the wheel a user runs.
"""

from __future__ import annotations

import time

import numpy as np


class CompileClock:
    """Seconds jax spent in backend compiles, persistent-cache retrievals
    included (jax.monitoring).  One per process: listeners cannot be
    unregistered."""

    def __init__(self):
        import jax.monitoring

        self.secs = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, secs, **_kw):
        if event.endswith("backend_compile_duration"):
            self.secs += secs
            self.count += 1

    def _ev(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class _Span:
    """A ``jax.profiler.TraceAnnotation`` that is opened at one boundary
    and closed at the next, on the thread that runs both (the hub's)."""

    def __init__(self):
        self._open = None

    def switch(self, name):
        import jax

        self.close()
        self._open = jax.profiler.TraceAnnotation(name)
        self._open.__enter__()

    def close(self):
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


class HubWatch:
    """What one hub run showed.  Filled by :func:`probed` (Iter0) and by
    :meth:`boundary`, which the harness plants as (or in front of) the hub
    option ``preempt_check``: the hub calls that between iterations, at
    every boundary it offers, and during the linger."""

    def __init__(self, clock=time.monotonic, annotate=False):
        self.clock = clock
        self.annotate = annotate
        self._span = _Span()
        self.opt = None
        self.x0 = None               # Iter0 solution as the wheel went on
        #                              to use it: the device's, the rows that
        #                              the program re-solved on the host theirs
        self.rescued0 = None         # (S,) rows the host re-solved at Iter0
        self.t_iter0 = None          # end of the hub's Iter0
        self.marks = []              # (t, iteration) at every boundary call
        self.last = None             # (iteration, W, xbars) at the newest
        # every hub step that was ONE iteration (a legacy iteration, not a
        # megastep window): (W, xbars) before it, (x, W, xbars) after
        self.steps = []
        self.step_iters = 0          # iterations of the newest hub step
        self.spokes = []             # the wheel's spoke communicators, where
        #                              :func:`probed_spoke` registered them
        self.on_boundary = None      # harness hook: (watch, t, it) -> bool

    # -- filled through the subclass seam ---------------------------------
    def on_iter0(self, opt):
        self.opt = opt
        pri, dua = np.asarray(opt.pri_res), np.asarray(opt.dua_res)
        # the straggler rescue re-solves Iter0 rows with HiGHS on the host
        # and zeroes their residuals: that is how it is counted here
        self.rescued0 = (pri == 0.0) & (dua == 0.0)
        self.t_iter0 = self.clock()
        self._remember(0)
        if self.annotate:
            self._span.switch("bench:hub_step")

    def _remember(self, it):
        opt = self.opt
        new = (it, np.array(opt.W, dtype=float),
               np.array(opt.xbars, dtype=float))
        if self.last is not None and it - self.last[0] == 1:
            self.steps.append({"iteration": it, "W_prev": self.last[1],
                               "xbars_prev": self.last[2],
                               "x": np.array(opt.local_x, dtype=float),
                               "W": new[1], "xbars": new[2]})
        self.last = new

    def boundary(self):
        """Called by the hub between iterations.  True asks it to park."""
        if self.opt is None:         # a boundary before Iter0 ended
            return False
        it, t = int(self.opt._iter), self.clock()
        self.marks.append((t, it))
        stepped = it != self.last[0]
        if stepped:
            self.step_iters = it - self.last[0]
            self._remember(it)
        # the boundary as an instant in the trace, on both sides of the
        # hook: a slice that the hook starts or stops here holds one
        self._instant(it)
        # the step that ended here closes before the hook runs (the hook
        # may stop the trace) and the next opens after it (it may start one)
        self._span.close()
        stop = bool(self.on_boundary(self, t, it)) if self.on_boundary else False
        self._instant(it)
        if self.annotate and not stop:
            self._span.switch("bench:hub_step" if stepped or it == 0
                              else "bench:hub_linger")
        return stop

    def _instant(self, it):
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(f"bench:iter={it}"):
                pass

    def done(self):
        self._span.close()

    # -- read afterwards ----------------------------------------------------
    def t_first_at(self, iteration):
        """When the hub first stood at ``iteration`` (or beyond)."""
        for t, it in self.marks:
            if it >= iteration:
                return t
        return None


def probed(ph_cls, watch):
    """``ph_cls`` with its Iter0 observed."""

    class ProbedPH(ph_cls):
        def _rescue_stragglers(self, sol, q, q2, lb, ub, batch=None,
                               meas=None):
            out = super()._rescue_stragglers(sol, q, q2, lb, ub,
                                             batch=batch, meas=meas)
            if watch.x0 is None:                 # the Iter0 solve
                watch.x0 = np.array(out[1]["x"], dtype=float)
            return out

        def Iter0(self):
            out = super().Iter0()
            watch.on_iter0(self)
            return out

    ProbedPH.__name__ = ph_cls.__name__
    return ProbedPH


def probed_spoke(spoke_cls, watch):
    """``spoke_cls`` whose instances are remembered in ``watch.spokes``: the
    way to a spoke of a wheel that the server builds and drops itself (a
    solo ``WheelSpinner`` keeps its own in ``spoke_comms``)."""

    class ProbedSpoke(spoke_cls):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            watch.spokes.append(self)

    ProbedSpoke.__name__ = spoke_cls.__name__
    return ProbedSpoke


def incumbent_of(spoke_comms):
    """The (S, n) solution behind the best inner bound that a spoke of the
    wheel kept (``InnerBoundNonantSpoke.best_snapshot``: the bound and its
    ``best_solution_cache``, read as a pair), copied; None where no spoke
    kept one.  Read after the wheel tore down."""
    best, sol = None, None
    for comm in spoke_comms:
        if not hasattr(comm, "best_snapshot"):
            continue
        bound, cache = comm.best_snapshot()
        if cache is None or not np.isfinite(bound):
            continue
        better = best is None or (
            bound < best if getattr(comm, "is_minimizing", True)
            else bound > best)
        if better:
            best, sol = bound, np.array(cache, dtype=float)
    return sol


def hub_device_ready(opt):
    """``block_until_ready`` on every jax array the hub holds, so that a
    clock read after it is not ahead of the device."""
    import jax

    for name in ("_warm", "_factors", "_dev_state"):
        jax.block_until_ready(getattr(opt, name, None))


def device_state_leaves(opt):
    """(leaves on the expected device, leaves elsewhere) of what the hub
    holds on the device."""
    import jax

    want, good, wrong = jax.devices()[0], 0, 0
    for name in ("_warm", "_factors", "_dev_state"):
        for leaf in jax.tree_util.tree_leaves(getattr(opt, name, None)):
            if isinstance(leaf, jax.Array):
                if leaf.devices() == {want}:
                    good += 1
                else:
                    wrong += 1
    return good, wrong
