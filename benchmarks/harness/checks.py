"""The numbers that decide ``correct``: each is computed by the plain
reference (``reference.py``) from what the timed path left behind, and
is held to a limit that the cell's workload file states.

A wheel's evidence is a dict:
  ref      RefData of the deployment, from the creator and the seed
  watch    HubWatch of the hub that the window drove
  first_iteration     the hub iteration at which the window opened
  x, W, xbars, rho    the hub's PH state after the wheel tore down
  outer, inner        the bounds the spokes returned, as the hub kept them
  record   the server's record of the request (served cells), else None
  iter_limit          the request's PHIterLimit (served cells)
Every row of the batch is compared where the reference is arithmetic or a
small LP, and ``n_check`` rows drawn from the seed at EVERY single-iteration
hub step of the window where each row costs an LP a step.  A check returns
a float; None (nothing to compare) fails the run, except for a bound that
the spokes did not return, whose spec says ``"absent": "skip"``.  A number
over several wheels (the requests of a served window) is the worst of them.
"""

from __future__ import annotations

import math

import numpy as np

from . import reference as R


def _sample(ev, n):
    """``n`` scenarios drawn from the seed."""
    if "idx" not in ev:
        S = ev["ref"].S
        rng = np.random.default_rng(ev["seed"])
        ev["idx"] = np.sort(rng.choice(S, size=min(n, S), replace=False))
    return ev["idx"]


def _steps(ev):
    """The single-iteration hub steps that lie inside the window."""
    return [st for st in ev["watch"].steps
            if st["iteration"] > ev.get("first_iteration", 0)]


def _ef(ev):
    if "ef" not in ev:
        ev["ef"] = R.two_stage_ef(ev["ref"])
    return ev["ef"]


def _rel(a, b):
    return abs(float(a) - float(b)) / max(1.0, abs(float(b)))


def _iter0_gaps(ev):
    """(S,) every scenario's Iter0 objective, as the wheel went on to use
    it, against HiGHS on the creator's data."""
    if "iter0_gaps" not in ev:
        ref = ev["ref"]
        got = ref.objective(ev["watch"].x0)
        ev["iter0_gaps"] = np.array([_rel(got[s], R.scenario_lp(ref, s))
                                     for s in range(ref.S)])
    return ev["iter0_gaps"]


def iter0_obj_median_rel(ev, p):
    """Ingest and the Iter0 batch solve: the median scenario's gap.  A fault
    of ingest, of the solve or of its precision moves every row."""
    return float(np.median(_iter0_gaps(ev)))


def iter0_obj_worst_rel(ev, p):
    """Ingest and the Iter0 batch solve: the worst scenario's gap.  A row
    whose data went wrong, or that was left unsolved, shows here."""
    return float(_iter0_gaps(ev).max())


def xbar_rel(ev, p):
    """Compute_Xbar at the window's end, redone in numpy."""
    want = R.xbar_of(ev["ref"], ev["x"])
    got = np.asarray(ev["xbars"], float)
    return float(np.abs(got - want[None, :]).max()
                 / max(1.0, np.abs(want).max()))


def w_mean_rel(ev, p):
    """PH keeps the probability-weighted mean of W at 0."""
    W = np.asarray(ev["W"], float)
    return float(np.abs(ev["ref"].probs @ W).max()
                 / max(1.0, np.abs(W).max()))


def feas_rel(ev, p):
    """The last iterates of every scenario against the creator's own rows
    and bounds: the worst."""
    x = np.asarray(ev["x"], float)
    return max(R.infeasibility(ev["ref"], s, x[s])
               for s in range(ev["ref"].S))


def w_update_rel(ev, p):
    """Update_W over every single-iteration hub step of the window, redone
    in numpy: the worst."""
    ref, rho = ev["ref"], np.asarray(ev["rho"], float)
    worst = None
    for st in _steps(ev):
        want = R.w_after(st["W_prev"], rho, st["x"][:, ref.nonant],
                         st["xbars"])
        gap = float(np.abs(st["W"] - want).max()
                    / max(1.0, np.abs(want).max()))
        worst = gap if worst is None else max(worst, gap)
    return worst


def prox_gaps(ev, n):
    """(steps, n) Frank-Wolfe gaps: the subproblem solves of every
    single-iteration hub step of the window against HiGHS, for the ``n``
    drawn scenarios."""
    if "prox_gaps" not in ev:
        rho = np.asarray(ev["rho"], float)
        ev["prox_gaps"] = np.array(
            [[abs(R.prox_gap(ev["ref"], int(s), st["x"][s], st["W_prev"][s],
                             st["xbars_prev"][s], rho[s]))
              for s in _sample(ev, n)] for st in _steps(ev)])
    return ev["prox_gaps"]


def prox_gap_rel(ev, p):
    """The subproblem solves of every single-iteration hub step of the
    window: the worst gap over steps and drawn scenarios."""
    g = prox_gaps(ev, p["n_check"])
    return float(g.max()) if g.size else None


def eobj_vs_ef_rel(ev, p):
    """The expected objective of the last iterates against the HiGHS
    extensive form: PH's fixed point is the EF's optimum."""
    ref = ev["ref"]
    return _rel(ref.probs @ ref.objective(ev["x"]), _ef(ev))


def xbar_spread_rel(ev, p):
    """Consensus: the probability-weighted mean distance of the scenarios'
    first stages from their mean, against the mean's size."""
    ref, x = ev["ref"], np.asarray(ev["x"], float)
    xbar = R.xbar_of(ref, x)
    dev = np.abs(x[:, ref.nonant] - xbar[None, :]).mean(axis=1)
    return float(ref.probs @ dev / max(1.0, np.abs(xbar).mean()))


def outer_over_ef_rel(ev, p):
    """An outer bound may not pass the optimum."""
    ef = _ef(ev)
    o = float(ev["outer"])
    return max(0.0, o - ef) / max(1.0, abs(ef)) if math.isfinite(o) else None


def inner_under_ef_rel(ev, p):
    """An inner bound may not lie under the optimum."""
    ef = _ef(ev)
    i = float(ev["inner"])
    return max(0.0, ef - i) / max(1.0, abs(ef)) if math.isfinite(i) else None


def outer_over_inner_rel(ev, p):
    o, i = float(ev["outer"]), float(ev["inner"])
    if not (math.isfinite(o) and math.isfinite(i)):
        return None
    return max(0.0, o - i) / max(1.0, abs(i))


def nonfinite(ev, p):
    """Count of non-finite entries in the PH state, NaN bounds included."""
    n = sum(int((~np.isfinite(np.asarray(ev[k], float))).sum())
            for k in ("x", "W", "xbars"))
    return float(n + math.isnan(float(ev["outer"]))
                 + math.isnan(float(ev["inner"])))


def state_off_device(ev, p):
    """Leaves of the hub's device state that are not on the chip (and 1
    more if it holds none at all)."""
    good, wrong = ev["device_leaves"]
    return float(wrong + (good == 0))


def record_bad(ev, p):
    """Served cells: the record says done, and the iterations asked for
    were run unless the gap certified first."""
    rec = ev["record"]
    ok = rec["status"] == "done" and (
        rec["iters"] == ev["iter_limit"] or rec["certified"])
    return 0.0 if ok else 1.0


NOT_FINITE = 1e300

CHECKS = {f.__name__: f for f in (
    iter0_obj_median_rel, iter0_obj_worst_rel, xbar_rel, w_mean_rel, feas_rel, w_update_rel,
    prox_gap_rel, eobj_vs_ef_rel, xbar_spread_rel, outer_over_ef_rel,
    inner_under_ef_rel, outer_over_inner_rel, nonfinite, state_off_device,
    record_bad)}


def decide(evidence, specs):
    """(correct, rows): every check of ``specs`` over every wheel of
    ``evidence``.  A row is {name, value, limit, ok}; a value of None (no
    wheel had anything to compare) fails, unless the spec says ``"absent":
    "skip"`` (a bound that no spoke returned: the row then says so)."""
    rows, correct = [], True
    for spec in specs:
        fn = CHECKS[spec["name"]]
        vals = [v for v in (fn(ev, spec) for ev in evidence) if v is not None]
        value = max(vals) if vals else None
        if value is None:
            ok = spec.get("absent") == "skip" and bool(evidence)
        else:
            ok = math.isfinite(value) and value <= spec["limit"]
            if not math.isfinite(value):
                value = NOT_FINITE          # JSON has no infinity
        correct = correct and ok
        rows.append({"name": spec["name"], "value": value,
                     "limit": spec["limit"], "ok": ok})
    return correct and bool(evidence), rows
