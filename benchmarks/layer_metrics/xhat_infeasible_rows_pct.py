"""Spoke bound passes: of the candidates refused in the window, the share
of their rows that missed the feasibility gate (100 x
``xhat.infeasible_rows`` over ``xhat.infeasible_of_rows``, the rows those
candidates had: S each): one row in a thousand, or all of them.  0 where
candidates were priced and none was refused; nothing where none was
priced."""


def read(obs):
    of = obs["counters"].get("xhat.infeasible_of_rows")
    if not of:
        return 0.0 if obs["counters"].get("xhat.candidates") else None
    return 100.0 * obs["counters"].get("xhat.infeasible_rows", 0.0) / of
