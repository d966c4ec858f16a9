"""XhatShuffle inner-bound spoke: shuffled scenario cycling over hub nonants.

TPU-native analogue of ``mpisppy/cylinders/xhatshufflelooper_bounder.py:20-300``.
Each pass: take the hub's current nonant values, pick the next donor scenario
from a seeded shuffle (the reference's ``ScenarioCycler``, multistage-aware via
per-node donor completion), fix the nonant columns to the donated candidate,
solve the whole batch in one device program (``Xhat_Eval``), and push the
expected objective to the hub when it improves the incumbent.
"""

from __future__ import annotations

import numpy as np

from .spoke import InnerBoundNonantSpoke
from ..extensions.xhatbase import donor_cache


class ScenarioCycler:
    """Seeded shuffled cycle over donor scenario indices
    (xhatshufflelooper_bounder.py:158-300).

    ``reverse``: iterate the shuffle backwards (the reference's
    reverse-looper option).
    """

    def __init__(self, num_scenarios: int, seed: int = 0, reverse: bool = False):
        self._S = int(num_scenarios)
        self._rng = np.random.default_rng(seed)
        self._reverse = reverse
        self._order = []
        self._pos = 0

    def _reshuffle(self):
        self._order = list(self._rng.permutation(self._S))
        if self._reverse:
            self._order.reverse()
        self._pos = 0

    def get_next(self) -> int:
        if self._pos >= len(self._order):
            self._reshuffle()
        s = self._order[self._pos]
        self._pos += 1
        return int(s)


class XhatShuffleInnerBound(InnerBoundNonantSpoke):
    """'X' spoke (xhatshufflelooper_bounder.py:20-157)."""

    converger_spoke_char = 'X'

    def xhatbase_prep(self):
        """No iter0 solves needed — the opt object (Xhat_Eval) evaluates
        candidates directly (xhatshufflelooper_bounder.py:24-61)."""
        opts = self.opt.options
        lopts = opts.get("xhat_looper_options", {})
        self.cycler = ScenarioCycler(
            self.opt.batch.num_scenarios,
            seed=int(lopts.get("seed", 0)),
            reverse=bool(lopts.get("reverse", False)),
        )
        self.scen_limit = int(lopts.get("scen_limit", 3))
        # Donor-MILP mode: candidates come from an exact host MILP of the
        # donor scenario instead of the donor's row of the hub nonants.
        # This is the reference's donor semantics — its donors are solved
        # (MIP) scenario instances (xhatshufflelooper_bounder.py:139-141)
        # — where ours carry LP-relaxation values from the device solves,
        # which integer-snap poorly on families like UC whose relaxation
        # is fractional in exactly the nonant (commitment) coordinates.
        # Two-stage only (per-node donors would need per-node MILPs).
        self.donor_milp = bool(lopts.get("donor_milp", False)) and \
            self.opt.tree.num_stages == 2
        self.donor_milp_gap = float(lopts.get("donor_milp_gap", 1e-3))
        self.donor_milp_time = float(lopts.get("donor_milp_time", 30.0))
        self._milp_donor_cache: dict = {}
        self._milp_evaluated: set = set()

    def _donor_milp_candidate(self, donor):
        """(K,) nonant candidate from the donor scenario's exact MILP
        (cached: the plain-c scenario optimum is iteration-independent)."""
        if donor in self._milp_donor_cache:
            return self._milp_donor_cache[donor]
        from ..solvers import scipy_backend

        b = self.opt.batch
        res = scipy_backend.solve_lp(
            b.c[donor], b.A[donor], b.cl[donor], b.cu[donor],
            b.lb[donor], b.ub[donor], is_int=b.is_int,
            mip_rel_gap=self.donor_milp_gap,
            time_limit=self.donor_milp_time)
        cand = (np.asarray(res.x)[self.opt.tree.nonant_indices]
                if res.feasible else None)
        # cache misses only for DEFINITIVE outcomes: a time-limit hit with
        # no incumbent (status "1", x None) is transient host load, and the
        # donor deserves a retry on a later pass
        if cand is not None or res.status == "2":
            self._milp_donor_cache[donor] = cand
        return cand

    def _try_candidates(self, final=False):
        """Try up to scen_limit donors against the current hub nonants.

        Aborts early on the kill sentinel via ``peek_kill_signal`` so a
        nonant payload posted mid-evaluation keeps its freshness for the
        next main-loop poll — except on the finalize pass, where the
        sentinel is permanently set and all donors should be tried."""
        xk = self.localnonants
        for _ in range(self.scen_limit):
            donor = self.cycler.get_next()
            if self.donor_milp:
                if donor in self._milp_evaluated:
                    # donor-MILP candidates are iteration-independent: a
                    # re-evaluation can never improve the incumbent.  Once
                    # every donor has been tried, fall back to hub-nonant
                    # donors (those DO evolve with the hub iterates).
                    if (len(self._milp_evaluated)
                            >= self.opt.batch.num_scenarios):
                        self.donor_milp = False
                    continue
                cache = self._donor_milp_candidate(donor)
                if cache is None:       # infeasible donor (or retry later)
                    continue
                self._milp_evaluated.add(donor)
            else:
                cache = donor_cache(self.opt, xk, donor)
            obj = self.opt.evaluate(cache)
            self.update_if_improving(obj)
            if not final and self.peek_kill_signal():
                return

    def main(self):
        self.xhatbase_prep()
        self._seen = False
        while not self.got_kill_signal():
            if self.new_nonants:
                self._seen = True
                with self.bound_pass():
                    self._try_candidates()

    def finalize(self):
        """One final candidate pass with the last hub nonants (the
        reference's spokes also sweep once after the kill sentinel —
        without it a fast hub can outrun the spoke and terminate with a
        stale incumbent, which made short wheels timing-flaky)."""
        if getattr(self, "_seen", False):
            self._try_candidates(final=True)
        return super().finalize()
