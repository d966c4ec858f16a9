"""The numbers that decide ``correct``: each is a file of its own,
``checks/<name>.py`` with ``value(ev, spec) -> float | None``, found by the
name a cell's workload file gives it, computed from what the timed path left
behind, and held to the limit that the workload file states.  This module
holds what they share and the rule that decides.

A wheel's evidence is a dict:
  ref      the plain reference of the deployment (``references/<name>.py``,
           named by the configuration), from the creator and the seed
  watch    HubWatch of the hub that the window drove
  first_iteration     the hub iteration at which the window opened
  x, W, xbars, rho    the hub's PH state after the wheel tore down
  outer, inner        the bounds the spokes returned, as the hub kept them
  incumbent           the (S, n) solution behind the best inner bound, copied
                      from the spoke that kept it; None where none did
  record   the server's record of the request (served cells), else None
  iter_limit          the request's PHIterLimit (served cells)
Every row of the batch is compared where the reference is arithmetic or a
small LP, and ``n_check`` rows drawn from the seed at EVERY single-iteration
hub step of the window where each row costs an LP a step.  A check returns
a float; None (nothing to compare) fails the run, except for a bound that
the spokes did not return, whose spec says ``"absent": "skip"``.  A number
over several wheels (the requests of a served window) is the worst of them.

A check reaches the truth through ``ev["ref"]`` alone and imports no
reference: it serves any reference that has the methods it calls
(:func:`ref_has`) and has nothing to compare where one has not.
"""

from __future__ import annotations

import math

import numpy as np

from . import byname

BENCH_DIR = byname.BENCH_DIR


def load_check(name, bench_dir=BENCH_DIR):
    """``checks/<name>.py``'s ``value``."""
    return byname.load("checks", name, bench_dir, "value")


def ref_has(ev, *names):
    """Whether the reference has every attribute named."""
    return all(getattr(ev["ref"], n, None) is not None for n in names)


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
    """The optimum of the reference's (relaxed) extensive form, solved once
    a wheel; None where the reference has none."""
    if "ef" not in ev:
        ev["ef"] = ev["ref"].ef() if ref_has(ev, "ef") else None
    return ev["ef"]


def _rel(a, b):
    return abs(float(a) - float(b)) / max(1.0, abs(float(b)))


def _iter0_gaps(ev):
    """(S,) every scenario's Iter0 objective, as the wheel went on to use
    it, against the reference's scenario optimum on the creator's data."""
    if "iter0_gaps" not in ev:
        ref = ev["ref"]
        if not ref_has(ev, "objective", "scenario_opt"):
            ev["iter0_gaps"] = None
        else:
            got = ref.objective(ev["watch"].x0)
            ev["iter0_gaps"] = np.array([_rel(got[s], ref.scenario_opt(s))
                                         for s in range(ref.S)])
    return ev["iter0_gaps"]


def prox_gaps(ev, n):
    """(steps, n) Frank-Wolfe gaps: the subproblem solves of every
    single-iteration hub step of the window against the reference, for the
    ``n`` drawn scenarios."""
    if "prox_gaps" not in ev:
        rho = np.asarray(ev["rho"], float)
        ev["prox_gaps"] = np.array(
            [[abs(ev["ref"].prox_gap(int(s), st["x"][s], st["W_prev"][s],
                                     st["xbars_prev"][s], rho[s]))
              for s in _sample(ev, n)] for st in _steps(ev)])
    return ev["prox_gaps"]


def _incumbent(ev):
    """The (S, n) incumbent, or None where no spoke kept one."""
    inc = ev.get("incumbent")
    return None if inc is None else np.asarray(inc, float)


NOT_FINITE = 1e300


def decide(evidence, specs, bench_dir=BENCH_DIR):
    """(correct, rows): every check of ``specs`` over every wheel of
    ``evidence``.  A row is {name, value, limit, ok}; a value of None (no
    wheel had anything to compare) fails, unless the spec says ``"absent":
    "skip"`` (a bound that no spoke returned: the row then says so)."""
    rows, correct = [], True
    for spec in specs:
        fn = load_check(spec["name"], bench_dir)
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
