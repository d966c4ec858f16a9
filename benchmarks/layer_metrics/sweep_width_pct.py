"""Megastep program and sweep kernels: how much of the batch the window's
solves were still sweeping, every cylinder's and every kind's (100 x
``solve.*.*.row_sweeps`` over ``solve.*.*.full_row_sweeps``: the sum over
all sweeps of the width each ran at, over the same sweeps at the full width
of the batch, which the program counts beside it).  The dense engine's loop
narrows to the rows that are not done; 100: every sweep at full width (the
shared-A engine's loop, a batch of one block, rows that never finish)."""

from benchmarks.harness import outcomes


def read(obs):
    return outcomes.share(obs, "row_sweeps", "full_row_sweeps")
