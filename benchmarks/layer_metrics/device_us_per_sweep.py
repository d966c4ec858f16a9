"""Megastep program and sweep kernels: microseconds of the device per
sweep of the whole (S, n) batch, the HUB's: the device seconds of the runs
that the hub's thread launched (``harness/progtrace.py``) per hub
iteration, over the sweeps its solves counted per hub iteration
(``hub_sweeps_per_iter``).  Both sides stand between hub boundaries: the
device seconds over the whole turns of the hub's cycle inside the traced
slice, the sweeps over the window's, and a hub iteration's sweeps are the
same in either (a turn is one refresh and a megastep window); the spokes'
sweeps, which follow the trace's end, stay out.  Everything the hub's
programs do is charged to its sweeps: a refresh's factorizations and
polish, the megastep's reductions."""

from benchmarks.harness import outcomes, progtrace


def read(obs):
    per_iter = outcomes.sweeps_per_iter(obs, "hub")
    red = progtrace.of(obs)
    if not per_iter or red is None:
        return None
    turns = red["iterations"]
    if turns is not None:
        secs, n = turns["device_s"], turns["count"]
    elif obs["trace"]["iterations"] is not None:
        # a run launched before the trace began still held the device at
        # the slice's only whole turn: the slice itself, which the harness
        # cuts at two hub boundaries of one kind
        secs, n = red["device_s"], obs["trace"]["iterations"]["count"]
    else:
        return None
    hub_s = sum(secs.get("hub", {}).values())
    return 1e6 * hub_s / n / per_iter if hub_s > 0 else None
