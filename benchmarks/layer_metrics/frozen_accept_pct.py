"""Hub loop: share of the window's frozen attempts, every cylinder's,
whose candidate was kept (100 x ``solve.*.frozen.accepted`` over
``solve.*.frozen.count``); the rest were thrown away and the same rows
solved again by a refresh.  Nothing where no attempt was made."""

from benchmarks.harness import outcomes


def read(obs):
    return outcomes.share(obs, "accepted", "count", kinds=("frozen",))
