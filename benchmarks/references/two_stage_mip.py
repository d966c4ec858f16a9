"""The plain reference of a two-stage deployment with integer columns:
``two_stage_lp`` and, beside it, the integer truths.

The PH hub solves relaxations, so everything ``two_stage_lp`` gives (the
scenario optimum, the minimum of a linear cost, ``ef``, the prox gap, xbar,
W) is the relaxed number here too, from the same code, and Iter0, the prox
gap, xbar and W are held to those.  What this file adds reads ``is_int``
from the creator's problems:

  ``is_int``       (n,) the columns the creator marks integer
  ``int_min``      the integer minimum of a linear cost over one scenario's
                   set (HiGHS with ``integrality``)
  ``ef_int``       the integer extensive form, solved only where it has at
                   most ``EF_INT_MAX_COLS`` columns (the CPU tests' sizes:
                   a timed cell's would not end inside a run), else None,
                   and only as far as ``EF_INT_OPTIONS`` lets HiGHS go: it
                   returns the best point found, its price and HiGHS's
                   bound, and the optimum lies between the two
"""

from __future__ import annotations

import numpy as np
import scipy.optimize as sopt

from benchmarks.references.two_stage_lp import Reference as _TwoStageLP

# sslp 5 x 25 with S=5 has 655 columns and solves in a second; the cap is
# what HiGHS ends in about a minute on such families, not a tuned number
EF_INT_MAX_COLS = 5000
# sizes with S=3 (345 columns) is not proven optimal in 40 minutes: HiGHS
# stops here, and what it found stands with the bound it reached
EF_INT_OPTIONS = {"time_limit": 60.0, "mip_rel_gap": 1e-6}


class Reference(_TwoStageLP):
    def _read(self, problems):
        self.is_int = np.asarray(problems[0].is_int, bool)
        if any(not np.array_equal(p.is_int, self.is_int) for p in problems):
            raise ValueError("integer columns differ between scenarios")

    def int_min(self, s, cost):
        """min cost.x over scenario ``s``'s set with its integer columns
        integer -> value (``-inf`` where unbounded)."""
        return self.lin_min(s, cost, integrality=self.is_int.astype(int))

    def ef_int(self, **highs_options):
        """(price, x, bound) of the integer extensive form: ``x`` (S, n) the
        best point HiGHS found, with one first stage for all scenarios, its
        price, and HiGHS's lower bound on the optimum (equal to the price,
        to ``mip_rel_gap``, where it ended before ``time_limit``).  None where the
        form has more than ``EF_INT_MAX_COLS`` columns."""
        c, A, cl, cu, lb, ub, cols_of = self._ef_program()
        if c.size > EF_INT_MAX_COLS:
            return None
        integrality = np.zeros(c.size, int)
        integrality[cols_of[:, self.is_int]] = 1
        res = sopt.milp(c=c, constraints=sopt.LinearConstraint(A, cl, cu),
                        integrality=integrality, bounds=sopt.Bounds(lb, ub),
                        options=dict(EF_INT_OPTIONS, **highs_options))
        if res.x is None:       # 0: optimal, 1: a limit reached, a point found
            raise RuntimeError(f"HiGHS integer EF: status {res.status}: "
                               f"{res.message}")
        const = float(self.probs @ self.const)
        return (float(res.fun) + const, res.x[cols_of],
                float(res.mip_dual_bound) + const)
