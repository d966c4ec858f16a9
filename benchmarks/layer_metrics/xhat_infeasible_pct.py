"""Spoke bound passes: share of the candidates that ``Xhat_Eval.evaluate``
priced in the window that it refused with ``+inf`` because a row missed
the feasibility gate (100 x ``xhat.infeasible`` over ``xhat.candidates``).
At 100 no inner bound can arrive."""


def read(obs):
    n = obs["counters"].get("xhat.candidates")
    if not n:
        return None
    return 100.0 * obs["counters"].get("xhat.infeasible", 0.0) / n
