"""What the program says of its own solves: the window's deltas of the
registry counters ``solve.<cylinder>.<kind>.<field>`` that
``tpusppy.obs.trace.outcome`` feeds (kinds ``frozen``, ``refresh``,
``mega``; every field a sum: ``sweeps``, ``budget``, ``rows``,
``rows_done``, ``accepted``, ...).  Where a phase gives a layer's seconds,
these give the work under them.  With a program that has no such counter
(the parent of the PR that added them) every sum is ``None`` and so is
every reader built on it.
"""

from __future__ import annotations

KINDS = ("frozen", "refresh", "mega")


def total(obs, field, cylinders="", kinds=KINDS):
    """Sum of ``solve.<cylinder>.<kind>.<field>`` over the cylinders whose
    name starts with ``cylinders`` (``hub``; ``spoke`` for every spoke;
    nothing for every cylinder) and the kinds in ``kinds``; ``None`` where
    the program has no such counter."""
    found = None
    for key, value in obs["counters"].items():
        parts = key.split(".")
        if (len(parts) == 4 and parts[0] == "solve" and parts[3] == field
                and parts[2] in kinds
                and parts[1].startswith(cylinders)):
            found = (found or 0.0) + value
    return found


def sweeps(obs, cylinders=""):
    """Every sweep the cylinders' solves ran on the device in the window:
    the kept ones and the iterate a megastep window discarded."""
    kept = total(obs, "sweeps", cylinders)
    if kept is None:
        return None
    return kept + (total(obs, "rejected_sweeps", cylinders) or 0.0)


def sweeps_per_iter(obs, cylinders):
    """:func:`sweeps` over the window's hub iterations."""
    n = sweeps(obs, cylinders)
    if n is None or not obs["iterations"]:
        return None
    return n / obs["iterations"]


def share(obs, field, of, cylinders="", kinds=KINDS):
    """100 x ``field`` over ``of``; ``None`` where there is nothing to
    divide by."""
    num = total(obs, field, cylinders, kinds)
    den = total(obs, of, cylinders, kinds)
    return 100.0 * num / den if num is not None and den else None
