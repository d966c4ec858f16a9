"""Megastep program and sweep kernels: share of the rows of the window's
frozen attempts and refresh solves, every cylinder's, that the program's
own stopping test passed when the solve ended (100 x
``solve.*.{frozen,refresh}.rows_done`` over ``.rows``; the count comes back
in the solve's one packed fetch).  A megastep window reports no rows."""

from benchmarks.harness import outcomes


def read(obs):
    return outcomes.share(obs, "rows_done", "rows",
                          kinds=("frozen", "refresh"))
