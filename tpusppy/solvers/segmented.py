"""Budget-bounded segmented solve wrappers.

Every single program execution is held to a wall-clock budget
(``_DISPATCH_TARGET_SECS``): solves whose sweep loops would run longer are
split into bounded segments re-entered from the host.  The
frozen-factor protocol makes continuation free: factors are computed once,
segments warm-start from the previous raw iterate.

Two consumers share this module: the scenario-sharded jitted PH step
(:mod:`tpusppy.parallel.sharded`) and the host solve loop
(:meth:`tpusppy.spopt.SPOpt._solve_amortized` — the path every cylinder in
a wheel runs).  Shapes that fit one dispatch pass through unchanged.

Reference context: the reference's per-rank Gurobi solves
(``mpisppy/spopt.py:85-223``) have no analogue of this constraint — the
solver runs on the host.  On TPU the solve IS a device program, so dispatch
length becomes a correctness concern, not a tuning knob.
"""

from __future__ import annotations

import collections
import dataclasses

import numpy as np

from ..obs import metrics as _metrics
from ..obs import trace as _trace
from . import flops as flops_model
from . import hostsync

# Per-dispatch budget (whether an attached chip needs one at all is
# ROADMAP C12): long enough that the solver's IN-LOOP plateau exit
# (earliest at 3 x sweep_plateau_window = 96 sweeps) can fire inside one
# dispatch — at 18 s the reference-UC S=1000 segments capped at 52 sweeps
# and the in-loop exit could never trigger, wasting 2 whole continuation
# dispatches proving the plateau at host granularity.  30 s x the model's
# built-in overestimate (~1.5x vs measured sweep times) lands actual
# dispatches around 20-30 s.
_DISPATCH_TARGET_SECS = 30.0
# effective sweep throughput on the model's (n^2 + 2nm) flop accounting
# under matmul precision "highest" (bf16x6): measured 6.9-7.7e12 flop/s at
# reference-UC shapes on v5e (48.8 ms/sweep at S=256, n=16008, m=12408,
# solve_refine=2); 6e12 keeps ~15% conservatism
_DISPATCH_EFF_FLOPS = 6e12
# the 6.9-7.7e12 evidence is all SHARED-A shapes; the per-scenario dense
# path (batched small factorizations, factor_batch=S) has no measured
# sweep times at watchdog-relevant scale, so it keeps the pre-raise
# conservative constant — dispatch_segments clamps to this when
# factor_batch > 1
_DISPATCH_EFF_FLOPS_DENSE = 4e12


def _frozen_refine_iters(st):
    """Worst-case full-precision refinement sweeps a LOWERED frozen solve
    appends inside one dispatch (0 for full-precision settings)."""
    if st.sweep_precision in (None, "highest"):
        return 0
    return max(0, int(st.precision_refine_iters))


def frozen_budget(st) -> int:
    """Sweeps ONE frozen solve may spend: the ``max_iter`` that
    :func:`solve_frozen_segmented` is sized to, plus the refinement
    phase a lowered sweep mode appends."""
    return int(st.max_iter) + _frozen_refine_iters(st)


def _frozen_iter_secs(st, t_sweep):
    """Worst-case seconds of ONE frozen iteration: the full ``max_iter``
    sweep budget at the (possibly lowered) sweep precision, plus the
    in-dispatch f32 refinement phase a lowered mode appends.  The ONE
    expression the fused-iteration budget and the megastep watchdog cap
    must share — they are two views of the same worker-kill worst case."""
    return (st.max_iter * t_sweep
            / flops_model.sweep_speedup(st.sweep_precision)
            + _frozen_refine_iters(st) * t_sweep)


def seg_settings(settings, seg_iter):
    """Per-dispatch settings for one segment: the sweep cap, plus — for
    lowered sweep modes — the in-dispatch f32 refinement budget clamped
    to the same cap, so one dispatch can never embed a refinement phase
    larger than the watchdog-sized segment itself (dispatch_segments
    bills exactly this worst case)."""
    kw = {"max_iter": seg_iter}
    if _frozen_refine_iters(settings) > seg_iter:
        kw["precision_refine_iters"] = seg_iter
    return dataclasses.replace(settings, **kw)


def _dense_clamped_eff(eff_flops, factor_batch):
    """Default throughput, dense-clamped.  An EXPLICIT eff_flops stays
    authoritative (callers/tests monkeypatch the module constants to force
    dispatch regimes); only the defaults get the per-scenario-dense clamp."""
    if eff_flops is not None:
        return eff_flops
    if factor_batch > 1:
        return min(_DISPATCH_EFF_FLOPS, _DISPATCH_EFF_FLOPS_DENSE)
    return _DISPATCH_EFF_FLOPS


def dispatch_segments(S, n, m, st, factor_batch=1,
                      eff_flops=None, target_secs=None,
                      sparse_factor=1.0):
    """(seg_refresh, seg_frozen): per-dispatch sweep caps for these shapes.

    ``S`` is the PER-DEVICE scenario count (mesh callers divide by the mesh
    size); ``factor_batch`` is how many factorizations one adaptive solve
    performs per restart (the scenario count for dense per-scenario A, 1
    for the shared-A engine).  Returns (max_iter, max_iter) — i.e. "don't
    segment" — when the whole solve fits one dispatch under the worker
    watchdog.

    Floors: rho adaptation on fewer than ~32 sweeps of residual evidence
    misadapts (restart ratios are meaningless at cold residuals), and a
    frozen segment must exceed one check interval or a converged batch
    (which always burns its first ``check_every`` sweeps) is
    indistinguishable from an unconverged one.

    Pipelined continuations (:func:`continue_frozen` with speculation)
    need NO extra headroom here: a speculative segment is its own device
    program under exactly these caps — the worker watchdog is
    per-EXECUTION, and queued programs each get their own budget — and
    its sweeps are billed against the continuation budget at dispatch
    time, so the total dispatched work (the waste included, modeled by
    :func:`..flops.speculation_flops`) never exceeds the serial worst
    case of ``refresh_budget``/``max_iter`` sweeps.
    """
    eff = _dense_clamped_eff(eff_flops, factor_batch)
    target = _DISPATCH_TARGET_SECS if target_secs is None else target_secs
    ce = max(1, st.check_every)
    # ``sparse_factor``: scale applied by SparseA callers — sweeps there
    # replace the dense n^2/nm matmuls with gather/segment-sum matvecs and
    # the block/Woodbury x-update (measured 2-4x cheaper than the dense
    # accounting at reference-UC shapes; 0.25 keeps dispatches inside the
    # watchdog with the same 2x margin).  Flop accounting lives in
    # solvers/flops.py (shared with the autotuner + MFU reporting).
    t_sweep = flops_model.sweep_flops(S, n, m, sparse_factor) / eff
    # frozen sweeps run at the (possibly lowered) sweep precision —
    # conservatively faster (flops.SWEEP_SPEEDUP), so frozen dispatches may
    # carry more sweeps; refresh solves always run full precision.  A
    # lowered frozen dispatch also carries an in-dispatch f32 refinement
    # phase, which :func:`seg_settings` clamps to the SEGMENT cap — so the
    # worst case per lowered frozen sweep is one lowered sweep plus one
    # full-precision refinement sweep, billed jointly here (a flat
    # subtraction of the unclamped refine budget can go negative at
    # reference-UC sweep costs, which would break the watchdog bound the
    # sizing exists for).
    t_sweep_f = t_sweep / flops_model.sweep_speedup(st.sweep_precision)
    if _frozen_refine_iters(st) > 0:
        t_sweep_f = t_sweep_f + t_sweep
    t_factor = flops_model.factor_flops(n, m, factor_batch,
                                        sparse_factor) / eff
    rst = max(1, st.restarts)

    def _cap(budget_secs, floor, ts):
        raw = budget_secs / max(ts, 1e-12)
        return int(max(min(floor, st.max_iter),
                       min(st.max_iter, ce * int(raw / ce))))

    seg_r = _cap(target / rst - t_factor, 32, t_sweep)
    seg_f = _cap(target, 2 * ce, t_sweep_f)
    return seg_r, seg_f


def fused_iteration_budget(S, n, m, st, refresh_every, factor_batch=1,
                           eff_flops=None, target_secs=None,
                           sparse_factor=1.0):
    """Max PH iterations fusable into ONE device program (multiple of
    ``refresh_every``; 0 = don't fuse — the shape needs segmentation).

    Worst-case accounting on the :func:`dispatch_segments` flop model: every
    frozen iteration burns its full ``max_iter`` sweep budget (the
    while_loop usually exits earlier — this is the safety bound, not the
    expectation), every refresh runs ``restarts`` adaptation rounds plus the
    factorizations.  One block = 1 refresh + (refresh_every-1) frozen
    iterations; as many whole blocks as fit ``target_secs``.
    """
    eff = _dense_clamped_eff(eff_flops, factor_batch)
    target = _DISPATCH_TARGET_SECS if target_secs is None else target_secs
    t_sweep = flops_model.sweep_flops(S, n, m, sparse_factor) / eff
    t_factor = flops_model.factor_flops(n, m, factor_batch,
                                        sparse_factor) / eff
    rst = max(1, st.restarts)
    t_frozen_iter = _frozen_iter_secs(st, t_sweep)
    # the adaptive solve factorizes once PER RESTART (admm._solve_scaled's
    # restart scan calls _factor each round), matching dispatch_segments'
    # per-restart budget accounting
    t_refresh_iter = rst * (st.max_iter * t_sweep + t_factor)
    t_block = t_refresh_iter + (refresh_every - 1) * t_frozen_iter
    return int(target / max(t_block, 1e-12)) * refresh_every


def megastep_cap(S, n, m, st, eff_flops=None, target_secs=None,
                 factor_batch=1, sparse_factor=1.0, bound_pass=False):
    """Max wheel iterations ONE megastep dispatch may carry for these
    shapes under the worker watchdog (0 or 1 = don't megastep: the shape
    is in the segmentation regime, or barely fits one iteration).

    A megastep is N iterations of work inside a single device program, so
    the per-dispatch kill budget must scale with N: the cap is sized on
    the same worst-case flop model as :func:`dispatch_segments` — every
    frozen iteration billed at its full ``max_iter`` sweep budget, plus
    the in-dispatch f32 refinement phase a lowered sweep mode appends —
    against the same ``target_secs`` watchdog budget.  The in-scan
    early-exit mask never shrinks the worst case (a masked iteration does
    no sweeps, but the cap must hold when nothing converges).

    ``bound_pass`` (in-wheel certification, doc/pipeline.md): the
    dispatch may end with the fused bound pass — worst-cased at one extra
    frozen iteration PER EVALUATION (the xhat frozen evaluation's full
    sweep budget; the dual-objective contraction is a rounding error next
    to it) — so that many frozen-iteration budgets are reserved out of
    the watchdog window.  ``True`` reserves 1 (the legacy single-
    candidate pass); an int reserves that many (the batched integer
    sweep reserves its C candidate evaluations + 1 reduced-cost
    re-solve, doc/integer.md).
    """
    eff = _dense_clamped_eff(eff_flops, factor_batch)
    target = _DISPATCH_TARGET_SECS if target_secs is None else target_secs
    t_sweep = flops_model.sweep_flops(S, n, m, sparse_factor) / eff
    t_iter = _frozen_iter_secs(st, t_sweep)
    if bound_pass:
        target = max(target - int(bound_pass) * t_iter, 0.0)
    return int(target / max(t_iter, 1e-12))


def megastep_cap_multi(shapes, st, eff_flops=None, target_secs=None,
                       bound_pass=False):
    """Watchdog cap for a BUCKETED megastep: one scan step runs EVERY
    bucket's frozen sweep back to back inside the same program, so the
    per-iteration worst case is the SUM over buckets of the homogeneous
    :func:`megastep_cap` accounting.  ``shapes`` is
    ``[(S_b, n_b, m_b[, factor_batch_b[, sparse_factor_b]]), ...]``.
    ``bound_pass`` reserves cross-bucket frozen-iteration budgets for
    the fused bound pass — ``True`` = 1, an int = that many evaluations
    (the batched integer sweep; see :func:`megastep_cap`)."""
    target = _DISPATCH_TARGET_SECS if target_secs is None else target_secs
    total = 0.0
    for shp in shapes:
        S, n, m = shp[0], shp[1], shp[2]
        fb = shp[3] if len(shp) > 3 else 1
        sf = shp[4] if len(shp) > 4 else 1.0
        eff = _dense_clamped_eff(eff_flops, fb)
        t_sweep = flops_model.sweep_flops(S, n, m, sf) / eff
        total += _frozen_iter_secs(st, t_sweep)
    if bound_pass:
        target = max(target - int(bound_pass) * total, 0.0)
    return int(target / max(total, 1e-12))


def bill_megastep(S, n, m, n_iters, sweeps, sparse_factor=1.0,
                  rejected_sweeps=None, count_dispatch=True):
    """Bill one EXECUTED megastep into the metrics registry.

    ``n_iters`` is the number of wheel iterations the dispatch ACCEPTED
    (the packed measurement's stop counter — iterations the early-exit
    mask skipped did no sweeps and are NOT billed; a watchdog- or
    window-capped megastep likewise bills only what was dispatched);
    ``sweeps`` is the mean measured ADMM sweep count per iteration.
    ``rejected_sweeps``: the sweep count of an iterate the in-scan
    acceptance test DISCARDED (refresh_hit) — real dispatched work whose
    result was dropped, billed into ``dispatch.flops`` and counted under
    ``megastep.rejected_iterations`` but never into
    ``dispatch.mega_iterations`` (it is not a fused PH iteration).

    ``count_dispatch=False``: bill the FLOPS only — the bucketed
    megakernel calls this once per bucket (each bucket's own shapes) but
    the window is ONE dispatch of ``n_iters`` fused PH iterations, so
    only the first bucket's call counts toward the dispatch counters."""
    if count_dispatch:
        _metrics.inc("dispatch.megasteps")
        _metrics.inc("dispatch.mega_iterations", int(n_iters))
    fl = flops_model.megastep_flops(S, n, m, n_iters, sweeps, sparse_factor)
    if rejected_sweeps is not None:
        if count_dispatch:
            _metrics.inc("megastep.rejected_iterations")
        fl += flops_model.megastep_flops(S, n, m, 1, rejected_sweeps,
                                         sparse_factor)
    if fl:
        _metrics.inc("dispatch.flops", fl)
    return fl


def bill_bound_pass(S, n, m, sweeps, sparse_factor=1.0,
                    count_pass=True, n_evals=1):
    """Bill one EXECUTED in-wheel bound pass (doc/pipeline.md "In-wheel
    certification"): the xhat-at-xbar frozen evaluation's measured
    ``sweeps`` plus the Lagrangian dual-objective contraction, at this
    shape, into ``dispatch.flops`` — dispatched work inside the megastep
    window that is certification, not PH iterations, so it never inflates
    ``dispatch.mega_iterations``.  ``count_pass=False``: FLOPS only (the
    bucketed kernel bills per bucket but the window ran ONE pass).
    ``n_evals``: frozen evaluations in the pass (the batched integer
    sweep runs C candidates + 1 reduced-cost re-solve, doc/integer.md)."""
    if count_pass:
        _metrics.inc("megastep.bound_passes")
    fl = flops_model.bound_pass_flops(S, n, m, sweeps, sparse_factor,
                                      n_evals=n_evals)
    if fl:
        _metrics.inc("dispatch.flops", fl)
    return fl


# measured 2-4x cheaper sweeps on the SparseA/block-Woodbury path vs the
# dense flop accounting at reference-UC shapes; 0.25 keeps worst-case
# dispatches inside the worker watchdog with the same 2x margin (see
# dispatch_segments) — single source, reused by parallel.sharded
SPARSE_DISPATCH_FACTOR = 0.25


def _sparse_factor(args):
    """SPARSE_DISPATCH_FACTOR for SparseA solves, else 1."""
    from .sparse import SparseA
    return SPARSE_DISPATCH_FACTOR if isinstance(args[2], SparseA) else 1.0


def _shapes(args, shared):
    q, q2, A = args[0], args[1], args[2]
    S, n = np.shape(q)
    # A.shape works for numpy/jax arrays AND SparseA (np.shape would try
    # to materialize the latter)
    m = A.shape[0] if shared else A.shape[1]
    return S, n, m


def _seg_flops(args, shared, seg_f):
    """Model flops of ONE frozen segment — the speculation billing unit
    (``flops.sweep_flops`` x the segment's sweep cap)."""
    S, n, m = _shapes(args, shared)
    return flops_model.sweep_flops(S, n, m, _sparse_factor(args)) * seg_f


def _segmenting_events(S, n, m, seg_r, seg_f):
    """Observability of a budget-driven segmentation decision: the
    per-dispatch sweep caps this shape was sized to."""
    _metrics.inc("dispatch.segmented_solves")
    if _trace.enabled():
        _trace.instant("dispatch", "watchdog_caps", S=S, n=n, m=m,
                       seg_refresh=seg_r, seg_frozen=seg_f)


def refresh_budget(settings, seg_r):
    """Sweep budget left for frozen continuations after a segmented
    adaptive dispatch (which ran ``restarts`` rounds of ``seg_r``)."""
    rst = max(1, settings.restarts)
    return rst * settings.max_iter - rst * seg_r


# ---------------------------------------------------------------------------
# Pipelined continuation policy.  Per-shape verdicts measured by
# tpusppy.tune.autotune_pipeline land here: tiny shapes whose segment is
# cheaper than a stop-stats RPC gain nothing from speculation (the fetch
# dominates wall time either way) and are disabled.  Unmeasured shapes
# default to speculating — the waste is bounded at ``overlap`` segments
# per solve and billed against the sweep budget (see continue_frozen).
# ---------------------------------------------------------------------------
_PIPELINE_POLICY: dict = {}


def _policy_key(S, n, m):
    return (int(S), int(n), int(m))


def set_pipeline_policy(S, n, m, enabled: bool):
    """Record a measured per-shape speculation verdict (tune stage)."""
    _PIPELINE_POLICY[_policy_key(S, n, m)] = bool(enabled)


def pipeline_enabled(settings, S, n, m) -> bool:
    """Whether the segmented continuation for these shapes may speculate:
    the ``pipeline`` setting (the ``admm_pipeline`` config flag) is the
    hard off-switch; under it, a measured per-shape verdict wins, and
    unmeasured shapes speculate."""
    if not getattr(settings, "pipeline", True):
        return False
    return _PIPELINE_POLICY.get(_policy_key(S, n, m), True)


def continue_frozen(run_segment, sol, seg_f, budget, all_done=None,
                    plateau_rtol=None, pipeline=False, overlap=1,
                    check_incoming=False, seg_flops=None):
    """Generic frozen-continuation loop shared by the host solve path and
    the jitted sharded PH step: re-dispatch ``run_segment(warm)`` until
    converged, plateaued, or the sweep budget is spent.

    ``all_done(sol)`` decides whether to STOP DISPATCHING; the default
    reads the iteration counter — the while_loop leaves before its cap
    when every scenario met eps OR the in-loop plateau exit fired
    (``sweep_plateau_rtol``), and in both cases further dispatches are
    pointless.  It is a stop signal, NOT a convergence signal: use
    ``BatchSolution.done`` for convergence.  Multi-controller callers
    MUST pass a deterministic ``all_done`` (e.g. ``lambda sol: False``)
    and ``plateau_rtol=None``: both defaults
    fetch scenario-sharded data, which is impossible for non-addressable
    shards — and even a local-shard check would let processes disagree on
    the loop count and deadlock the collective dispatches.

    ``plateau_rtol``: stop when a whole extra segment improved the worst
    scaled residual by less than this fraction — further sweeps are futile
    (first-order UC batches park around 5e-2 at ANY budget; the host
    path's rescue-tolerance ladder already embraces exactly this).

    With the default ``all_done`` (None), the per-segment host decision
    reads ONE fetched 4-vector (:func:`..admm.stop_stats`: iters + worst
    residuals) instead of three separate array fetches — each per-segment
    host sync blocks the next dispatch, and the segmented UC path pays
    them every dispatch.  A caller-provided ``all_done`` keeps
    the legacy separate-fetch protocol (and NEVER speculates — the same
    restriction as the deterministic multi-controller schedules).

    ``pipeline=True`` (single-controller, default ``all_done`` only)
    overlaps the host decision with device compute: segment k+1 is
    dispatched from segment k's device-resident raw iterate BEFORE
    segment k's stop-stats are fetched, so the fetch RPC resolves while
    k+1 runs.  The stop-stats program for each segment is dispatched
    immediately after the segment itself (ahead of its successor), so
    its value is ready the moment the segment finishes and the host read
    never waits on speculative work.  If the verdict says "stop", the
    in-flight speculative segments are DISCARDED — pure-functional state
    makes this safe, and the result is identical to the serial protocol
    on the same stop decisions (the parity tests pin this).  Waste is
    bounded at ``overlap`` segments per continuation and BILLED: the
    sweep budget is charged at dispatch time, so the total dispatched
    work never exceeds the serial worst case (budget exhaustion) and no
    single dispatch grows — every speculative segment is its own device
    program under the same ``dispatch_segments`` watchdog cap.

    ``seg_flops`` (optional): model flops of ONE segment, used to bill
    dispatched/speculated/discarded work into the metrics registry
    (``dispatch.flops``, ``speculation.flops``,
    ``speculation.discarded_flops`` — doc/observability.md); segment
    counts are billed regardless.

    ``check_incoming=True`` additionally evaluates the INCOMING
    solution's stats first and returns it untouched when it already says
    stop (the first-frozen-dispatch test previously inlined in
    :func:`solve_frozen_segmented`).  The pipelined protocol reads this
    verdict BEFORE its first speculative dispatch: the stats value is
    already complete so the fetch costs exactly what serial pays, and
    the steady-state hot case — a warm frozen solve converged in its
    first dispatch, every PH iteration — then wastes nothing; later
    segments' verdicts are the ones worth overlapping.
    """
    from . import admm as _admm

    def _worst(s):
        return max(float(hostsync.fetch(s.pri_res).max()),
                   float(hostsync.fetch(s.dua_res).max()))

    if all_done is None:
        def _stats_launch(s):
            """Dispatch the (tiny) stop-stats program for a real pytree
            BatchSolution; scripted stand-ins (tests) carry their stats as
            plain attributes and need no device program."""
            if isinstance(s, _admm.BatchSolution):
                return _admm.stop_stats(s)
            return None

        def _stats_read(s, dev, overlapped=False):
            """(stop_dispatching, worst_residual) — ONE host fetch.  The
            eps vote catches solves whose iteration counter includes a
            refinement phase (mixed precision) on top of a capped sweep
            phase."""
            if dev is not None:
                st = hostsync.fetch(dev, overlapped=overlapped)
                stop = int(st[0]) < seg_f or bool(st[3])
                return stop, max(float(st[1]), float(st[2]))
            stop = int(hostsync.fetch(
                s.iters, overlapped=overlapped).max()) < seg_f
            return stop, _worst(s)
    else:
        pipeline = False      # legacy protocol: deterministic schedules
        # (multi-controller) and custom stop functions must not speculate

        def _stats_launch(s):
            return None

        def _stats_read(s, dev, overlapped=False):
            return all_done(s), _worst(s) if plateau_rtol else None

    if pipeline and overlap >= 1:
        return _continue_frozen_pipelined(
            run_segment, sol, seg_f, budget, _stats_launch, _stats_read,
            plateau_rtol, check_incoming, overlap, seg_flops)

    # ---- serial protocol --------------------------------------------------
    if check_incoming:
        done, worst = _stats_read(sol, _stats_launch(sol))
        if done:
            return sol
        best = worst if plateau_rtol else None
    else:
        # best is seeded from the INCOMING iterate so an already-parked
        # batch exits quickly
        best = _worst(sol) if plateau_rtol else None
    # two consecutive non-improving segments are required so a transient
    # residual uptick (ADMM is not monotone segment-to-segment) cannot
    # abort a budget that was still making progress
    stall = 0
    while budget > 0:
        # payload attach is guarded so the disabled path builds no dict
        # (the module contract: hot sites stay allocation-free when off)
        with _trace.span("dispatch", "segment") as _sp:
            if _trace.enabled():
                _sp.add(seg_f=seg_f)
            sol = run_segment(sol.raw)
        _metrics.inc("dispatch.segments")
        if seg_flops:
            _metrics.inc("dispatch.flops", seg_flops)
        budget -= seg_f
        done, worst = _stats_read(sol, _stats_launch(sol))
        if done:
            break
        if plateau_rtol:
            if worst > (1.0 - plateau_rtol) * best:
                stall += 1
                if stall >= 2:
                    break
            else:
                stall = 0
            best = min(best, worst)
    return sol


def _continue_frozen_pipelined(run_segment, sol, seg_f, budget,
                               stats_launch, stats_read, plateau_rtol,
                               check_incoming, overlap, seg_flops=None):
    """Speculative variant of the continuation loop (see
    :func:`continue_frozen`).  Dispatch order per segment is
    segment → its stop-stats program → successor segment, so each stats
    vector is computed before any speculative work and the host fetch of
    segment k's verdict overlaps segment k+1's execution."""
    pend = collections.deque()    # (candidate, stats_device) to validate

    def _fill(newest, newest_read=False):
        """Dispatch speculative segments from the newest iterate until the
        pipeline is ``overlap`` deep or the budget is spent.  The budget
        is charged at DISPATCH time: a discarded segment is still paid
        for, so the total dispatched work can never exceed the serial
        worst case.

        Speculation billing: a dispatch is speculative iff its SOURCE
        iterate's stop verdict is unread at dispatch time — entries on
        ``pend`` always are, and ``newest`` is unless the caller just
        read it (``newest_read``; only the check-incoming seed).  At the
        production ``overlap=1`` every steady-state dispatch launches
        from the just-popped candidate BEFORE its verdict fetch — that
        is the overlap, and it is speculative."""
        nonlocal budget
        while len(pend) < overlap and budget > 0:
            speculative = bool(pend) or not newest_read
            src = pend[-1][0] if pend else newest
            with _trace.span("dispatch", "segment") as _sp:
                if _trace.enabled():
                    _sp.add(seg_f=seg_f, speculative=speculative)
                cand = run_segment(src.raw)
            _metrics.inc("dispatch.segments")
            if seg_flops:
                _metrics.inc("dispatch.flops", seg_flops)
            if speculative:
                _metrics.inc("speculation.segments")
                if seg_flops:
                    _metrics.inc("speculation.flops", seg_flops)
            budget -= seg_f
            pend.append((cand, stats_launch(cand)))

    def _discard():
        """Bill the in-flight speculative segments a stop verdict just
        invalidated (the work was dispatched and paid for — the billing
        contract — but its results are dropped)."""
        if not pend:
            return
        _metrics.inc("speculation.discarded_segments", len(pend))
        if seg_flops:
            _metrics.inc("speculation.discarded_flops",
                         len(pend) * seg_flops)
        if _trace.enabled():
            _trace.instant("dispatch", "speculation_discard",
                           segments=len(pend))

    # the incoming iterate's stats are launched BEFORE any speculative
    # dispatch (the stats program must not queue behind one)
    seed_dev = (stats_launch(sol)
                if (check_incoming or plateau_rtol) else None)
    if check_incoming:
        # read the incoming verdict FIRST: its device value is already
        # complete, so this costs exactly the serial protocol's fetch —
        # and the steady-state hot case (a warm frozen solve converged in
        # its first dispatch, every PH iteration) then dispatches NOTHING
        # instead of burning a discarded segment per solve.  Speculation
        # starts only once the continuation is confirmed live.
        done, worst = stats_read(sol, seed_dev)
        if done:
            return sol
        best = worst if plateau_rtol else None
        _fill(sol, newest_read=True)   # seed verdict just read: confirmed
    else:
        # the first dispatch from the incoming iterate is MANDATORY work
        # the serial protocol performs identically (it has no incoming
        # verdict to read either) — billing it as speculation would
        # overstate the pipeline's waste vs serial
        _fill(sol, newest_read=True)
        best = (stats_read(sol, seed_dev, overlapped=bool(pend))[1]
                if plateau_rtol else None)
    stall = 0
    cur = sol
    while pend:
        cand, sdev = pend.popleft()
        _fill(cand)
        cur = cand
        if not pend:
            # budget exhausted and nothing speculative in flight: the
            # verdict cannot change what is returned — skip the fetch
            break
        done, worst = stats_read(cand, sdev, overlapped=True)
        if done:
            _discard()            # in-flight speculation discarded
            break
        if plateau_rtol:
            if worst > (1.0 - plateau_rtol) * best:
                stall += 1
                if stall >= 2:
                    _discard()
                    break
            else:
                stall = 0
            best = min(best, worst)
    return cur


def _continue_frozen(frozen_fn, args, factors, sol, st_f, seg_f, budget,
                     pipeline=False, check_incoming=False, seg_flops=None,
                     **kw):
    """Host-path adapter for :func:`continue_frozen`."""
    return continue_frozen(
        lambda warm: frozen_fn(*args, factors, settings=st_f, warm=warm,
                               **kw),
        sol, seg_f, budget,
        plateau_rtol=st_f.segment_plateau_rtol, pipeline=pipeline,
        check_incoming=check_incoming, seg_flops=seg_flops)


def _is_shared(args):
    return getattr(args[2], "ndim", None) == 2


def _caps(args, settings, shared):
    """:func:`dispatch_segments` for a solve's ``args``."""
    S, n, m = _shapes(args, shared)
    return S, n, m, dispatch_segments(S, n, m, settings,
                                      factor_batch=1 if shared else S,
                                      sparse_factor=_sparse_factor(args))


def one_dispatch(args, settings, adaptive=False) -> bool:
    """Whether :func:`solve_frozen_segmented` (``adaptive``:
    :func:`solve_factored_segmented`) runs these shapes as ONE dispatch:
    only then is the solution's iteration counter the whole solve's (a
    segmented solve's is its last dispatch's)."""
    *_, (seg_r, seg_f) = _caps(args, settings, _is_shared(args))
    return seg_f >= settings.max_iter and (
        not adaptive or seg_r >= settings.max_iter)


def solve_factored_segmented(frozen_fn, factored_fn, args, settings,
                             warm=None, shared=False, want_converged=True):
    """Adaptive solve + factors, segmented when the shapes demand it.

    Equivalent to ``factored_fn(*args, settings=settings, warm=warm)`` for
    shapes that fit one dispatch.  Returns (sol, factors, converged);
    ``want_converged=False`` skips the final ``sol.done`` fetch (one host
    RPC) and returns ``converged=None`` — for callers that read the
    convergence vote from their own packed measurement fetch
    (``admm.measure_pack``).

    SINGLE-CONTROLLER ONLY: the ``converged`` flag (and the continuation's
    defaults) fetch scenario-sharded device data, which raises on a
    multi-controller mesh with non-addressable shards — and even local-shard
    votes could disagree across processes and deadlock the collectives.
    Multi-controller callers drive the jitted sharded step with a
    deterministic schedule instead (see :func:`continue_frozen`).
    """
    S, n, m, (seg_r, seg_f) = _caps(args, settings, shared)

    def _conv(s):
        return (bool(hostsync.fetch(s.done).all()) if want_converged
                else None)

    if seg_r >= settings.max_iter and seg_f >= settings.max_iter:
        with _trace.span("dispatch", "adaptive_solve"):
            sol, factors = factored_fn(*args, settings=settings, warm=warm)
        return sol, factors, _conv(sol)
    _segmenting_events(S, n, m, seg_r, seg_f)
    st_r = dataclasses.replace(settings, max_iter=seg_r)
    st_f = seg_settings(settings, seg_f)
    with _trace.span("dispatch", "adaptive_segment") as _sp:
        if _trace.enabled():
            _sp.add(S=S, seg_r=seg_r)
        sol, factors = factored_fn(*args, settings=st_r, warm=warm)
    sol = _continue_frozen(frozen_fn, args, factors, sol, st_f, seg_f,
                           refresh_budget(settings, seg_r),
                           pipeline=pipeline_enabled(settings, S, n, m),
                           seg_flops=_seg_flops(args, shared, seg_f))
    if not shared and settings.polish and settings.polish_passes:
        # dense-path parity with the one-dispatch adaptive solve, which
        # polishes its final iterate; frozen continuations don't
        ce = max(1, settings.check_every)
        st_p = dataclasses.replace(settings, max_iter=2 * ce)
        sol = frozen_fn(*args, factors, settings=st_p, warm=sol.raw,
                        polish=True)
    # convergence from the RETURNED sol (post-polish), so the flag and
    # sol.done can never disagree
    return sol, factors, _conv(sol)


def solve_frozen_segmented(frozen_fn, args, factors, settings, warm=None,
                           want_converged=True):
    """Frozen solve, segmented when the shapes demand it.

    Returns (sol, converged) — callers must use ``converged`` (computed
    from ``BatchSolution.done``, the solver's own eps test) instead of any
    iters-vs-cap compare: iters reflects only the LAST segment's counter,
    and the in-loop plateau exit (``sweep_plateau_rtol``) leaves the sweep
    loop early without convergence.  ``want_converged=False`` skips that
    final done fetch (converged=None) for callers reading the vote from
    their own packed measurement fetch.

    SINGLE-CONTROLLER ONLY — same contract as
    :func:`solve_factored_segmented`: the convergence fetch and the
    data-dependent continuation need addressable shards.
    """
    shared = _is_shared(args)
    S, n, m, (seg_r, seg_f) = _caps(args, settings, shared)

    def _conv(s):
        return (bool(hostsync.fetch(s.done).all()) if want_converged
                else None)

    if seg_f >= settings.max_iter:
        with _trace.span("dispatch", "frozen_solve"):
            sol = frozen_fn(*args, factors, settings=settings, warm=warm)
        return sol, _conv(sol)
    _segmenting_events(S, n, m, seg_r, seg_f)
    st_f = seg_settings(settings, seg_f)
    with _trace.span("dispatch", "frozen_segment") as _sp:
        if _trace.enabled():
            _sp.add(S=S, seg_f=seg_f)
        sol = frozen_fn(*args, factors, settings=st_f, warm=warm)
    # check_incoming replaces the separate first-dispatch iters fetch the
    # serial protocol used to inline here (single-fetch stop_stats; the
    # pipelined policy overlaps every LATER segment's verdict)
    sol = _continue_frozen(frozen_fn, args, factors, sol, st_f, seg_f,
                           settings.max_iter - seg_f,
                           pipeline=pipeline_enabled(settings, S, n, m),
                           check_incoming=True,
                           seg_flops=_seg_flops(args, shared, seg_f))
    return sol, _conv(sol)
