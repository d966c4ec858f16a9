"""Scenario-problem intermediate representation (IR).

The reference represents each scenario as a Pyomo ``ConcreteModel`` built by a
user-supplied ``scenario_creator`` and solved by an external MIP solver
(spbase.py:255-291, spopt.py:85-223).  Here a scenario is a dense tensor record in
the canonical conic-box form used by first-order LP/QP solvers (OSQP/PDLP style):

    minimize    0.5 * x' diag(q2) x + c' x  (+ const)
    subject to  cl <= A x <= cu
                lb <=   x <= ub
                x[i] integer for is_int[i]

Equality rows are cl == cu; one-sided rows use +/-inf.  A batch of scenarios from
one model family shares shapes, so the whole batch lives in HBM as stacked arrays
and every solve is a single vmapped device program — this is the TPU replacement
for the per-rank serial ``solve_loop`` (spopt.py:226-307).

Nonanticipativity structure comes from :mod:`tpusppy.scenario_tree` annotations;
``ScenarioBatch`` packs them into device-friendly index arrays.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .obs import metrics as _metrics
from .scenario_tree import ScenarioNode, TreeInfo, build_tree

INF = np.inf


class LinearModelBuilder:
    """Tiny row-wise builder so model files read declaratively.

    The Pyomo-analogue authoring surface: declare variables with bounds and
    costs, then add rows ``cl <= sum coef*var <= cu``.  Produces a
    :class:`ScenarioProblem`.
    """

    def __init__(self, name: str):
        self.name = name
        self._varnames: list[str] = []
        self._names: set[str] = set()
        self._lb: list[float] = []
        self._ub: list[float] = []
        self._c: list[float] = []
        self._q2: list[float] = []
        self._is_int: list[bool] = []
        # (dict column -> value, or (columns, coefficients)), cl, cu
        self._rows: list[tuple] = []
        self.nodes: list[ScenarioNode] = []
        self.prob: float | None = None
        self.const: float = 0.0

    def add_var(self, name, lb=0.0, ub=INF, cost=0.0, quad=0.0, integer=False) -> int:
        """Declare a variable; returns its flat index."""
        if name in self._names:
            raise ValueError(f"duplicate variable {name}")
        self._names.add(name)
        self._varnames.append(name)
        self._lb.append(float(lb))
        self._ub.append(float(ub))
        self._c.append(float(cost))
        self._q2.append(float(quad))
        self._is_int.append(bool(integer))
        return len(self._varnames) - 1

    def add_vars(self, prefix, k, **kw) -> list[int]:
        return self.add_named_vars([f"{prefix}[{i}]" for i in range(k)], **kw)

    def add_named_vars(self, names, lb=0.0, ub=INF, cost=0.0, quad=0.0,
                       integer=False) -> list[int]:
        """Declare ``len(names)`` variables at once; every keyword is one
        value for all of them or one value each.  Returns their indices."""
        names = list(names)
        k, first = len(names), len(self._varnames)
        if len(set(names)) != k or not self._names.isdisjoint(names):
            dup = next(nm for i, nm in enumerate(names)
                       if nm in self._names or nm in names[:i])
            raise ValueError(f"duplicate variable {dup}")
        self._names.update(names)
        self._varnames.extend(names)
        for store, value, kind in ((self._lb, lb, float), (self._ub, ub, float),
                                   (self._c, cost, float),
                                   (self._q2, quad, float),
                                   (self._is_int, integer, bool)):
            store.extend(np.broadcast_to(np.asarray(value, dtype=kind),
                                         (k,)).tolist())
        return list(range(first, first + k))

    def add_row(self, coeffs, cl=-INF, cu=INF):
        """Add constraint cl <= sum_j coeffs[j]*x_j <= cu: ``coeffs`` a dict
        (column index or name -> coefficient) or a whole row as a pair
        (column indices, coefficients)."""
        if isinstance(coeffs, dict):
            coeffs = {
                (self._varnames.index(k) if isinstance(k, str) else int(k)):
                float(v) for k, v in coeffs.items()}
        self._rows.append((coeffs, float(cl), float(cu)))

    def add_eq(self, coeffs, rhs):
        self.add_row(coeffs, rhs, rhs)

    def add_le(self, coeffs, rhs):
        self.add_row(coeffs, -INF, rhs)

    def add_ge(self, coeffs, rhs):
        self.add_row(coeffs, rhs, INF)

    def set_cost(self, var, cost):
        i = self._varnames.index(var) if isinstance(var, str) else int(var)
        self._c[i] = float(cost)

    def build(self) -> "ScenarioProblem":
        n = len(self._varnames)
        m = len(self._rows)
        A = np.zeros((m, n))
        cl = np.zeros(m)
        cu = np.zeros(m)
        for r, (coeffs, lo, hi) in enumerate(self._rows):
            if isinstance(coeffs, dict):
                for j, v in coeffs.items():
                    A[r, j] = v
            else:
                A[r, coeffs[0]] = coeffs[1]
            cl[r], cu[r] = lo, hi
        return ScenarioProblem(
            name=self.name,
            c=np.asarray(self._c),
            q2=np.asarray(self._q2),
            A=A,
            cl=cl,
            cu=cu,
            lb=np.asarray(self._lb),
            ub=np.asarray(self._ub),
            is_int=np.asarray(self._is_int, dtype=bool),
            prob=self.prob,
            nodes=list(self.nodes),
            var_names=list(self._varnames),
            const=self.const,
        )


@dataclasses.dataclass
class ScenarioProblem:
    """One scenario in canonical form (host-side, numpy)."""

    name: str
    c: np.ndarray          # (n,)
    q2: np.ndarray         # (n,) diagonal of the quadratic term (0 => LP)
    A: np.ndarray          # (m, n)
    cl: np.ndarray         # (m,)
    cu: np.ndarray         # (m,)
    lb: np.ndarray         # (n,)
    ub: np.ndarray         # (n,)
    is_int: np.ndarray     # (n,) bool
    prob: float | None     # _mpisppy_probability; None => uniform (spbase.py:505-520)
    nodes: list            # list[ScenarioNode], stage order
    var_names: list | None = None
    const: float = 0.0     # objective constant
    # optional model-declared feasibility repair: callable
    # ``(x: (S, n), batch) -> (S, n)`` mapping near-feasible solver points
    # to EXACTLY feasible ones (full-recourse families close violations in
    # their slack columns in closed form).  The scalable certified-inner-
    # bound mechanism: Xhat_Eval repairs + verifies + prices exactly
    # instead of per-scenario host LP rescues (O(S) seconds each).
    repair_fn: object = None

    @property
    def num_vars(self) -> int:
        return int(self.c.shape[0])

    @property
    def num_rows(self) -> int:
        return int(self.A.shape[0])

    def nonant_indices(self) -> np.ndarray:
        return np.concatenate([nd.nonant_indices for nd in self.nodes])


def _pad_problem(p: ScenarioProblem, n: int, m: int) -> ScenarioProblem:
    """Pad a scenario to (n vars, m rows) with inert slots (fixed-at-0 vars,
    0 <= 0 <= 0 rows) so ragged families batch under vmap (SURVEY §7 hard part 2)."""
    dn, dm = n - p.num_vars, m - p.num_rows
    if dn == 0 and dm == 0:
        return p
    return dataclasses.replace(
        p,
        c=np.pad(p.c, (0, dn)),
        q2=np.pad(p.q2, (0, dn)),
        A=np.pad(p.A, ((0, dm), (0, dn))),
        cl=np.pad(p.cl, (0, dm)),
        cu=np.pad(p.cu, (0, dm)),
        lb=np.pad(p.lb, (0, dn)),
        ub=np.pad(p.ub, (0, dn)),
        is_int=np.pad(p.is_int, (0, dn)),
        var_names=None if p.var_names is None else p.var_names + [f"_pad{i}" for i in range(dn)],
    )


def _shares_one_A(problems) -> bool:
    """Whether every scenario carries the same constraint matrix: the same
    object, or one of the same shape and content (a creator that builds its
    rows anew for every scenario, as the families ported through
    ``LinearModelBuilder`` do).  The comparison stops at the first scenario
    that differs, so a family whose matrices are random (farmer's yields)
    pays for one pair.  THE one place that decides sharedness: everything
    downstream reads ``ScenarioBatch.A_shared``."""
    A0 = problems[0].A
    by_value = False
    for p in problems[1:]:
        if p.A is A0:
            continue
        if p.A.shape != A0.shape or not np.array_equal(p.A, A0):
            return False
        by_value = True
    if by_value:
        _metrics.inc("ingest.a_shared_by_value")
    return True


@dataclasses.dataclass
class ScenarioBatch:
    """A stacked batch of scenarios + compiled tree info.

    This is the unit of work the TPU runtime operates on: the analogue of one
    rank's ``local_scenarios`` dict (spbase.py:255-291), but stored as arrays of
    shape (S, ...) ready for vmapped solves and node-grouped reductions.
    """

    names: list
    c: np.ndarray          # (S, n)
    q2: np.ndarray         # (S, n)
    A: np.ndarray          # (S, m, n) — a zero-copy broadcast view when shared
    cl: np.ndarray         # (S, m)
    cu: np.ndarray         # (S, m)
    lb: np.ndarray         # (S, n)
    ub: np.ndarray         # (S, n)
    is_int: np.ndarray     # (n,) bool (shared across scenarios)
    const: np.ndarray      # (S,)
    tree: TreeInfo
    var_names: list | None = None  # (n,) shared column names, if known
    # mutation counter: bump after ANY in-place edit of the arrays above
    # (e.g. cross-scenario cut injection) so cached solver factorizations
    # keyed on it (SPOpt._solve_sig) invalidate
    version: int = 0
    # Shared constraint matrix (m, n), set when every scenario carries the
    # SAME A, by identity or by value (uncertainty in costs/rhs/bounds only —
    # the reference's headline UC is this shape: wind enters the
    # power-balance rhs).  ``from_problems`` finds it (``_shares_one_A``): a
    # creator need not reuse one numpy array, though one that does spares
    # the comparison.  ``.A`` is then a broadcast view (no (S, m, n) memory)
    # and solves dispatch to the shared-A engine
    # (tpusppy.solvers.shared_admm), which keeps ONE (n, n) factorization
    # for the whole batch.
    A_shared: np.ndarray | None = None
    # model-declared feasibility repair (see ScenarioProblem.repair_fn)
    repair_fn: object = None

    @classmethod
    def from_problems(cls, problems: list[ScenarioProblem]) -> "ScenarioBatch":
        probs = [p.prob for p in problems]
        if all(pr is None for pr in probs):
            # uniform default, as spbase.py:505-520
            problems = [
                dataclasses.replace(p, prob=1.0 / len(problems)) for p in problems
            ]
        elif any(pr is None for pr in probs):
            raise ValueError("either all or no scenarios may carry a probability")

        n = max(p.num_vars for p in problems)
        m = max(p.num_rows for p in problems)
        # shared-A detection BEFORE padding (a shared family needs none:
        # all members have one shape)
        a_shared = _shares_one_A(problems)
        problems = [_pad_problem(p, n, m) for p in problems]

        tree = build_tree(problems)
        is_int = problems[0].is_int
        for p in problems:
            if not np.array_equal(p.is_int, is_int):
                raise ValueError("integer pattern must match across scenarios")
        # Column names are only meaningful if every scenario agrees; degrade to
        # index labels otherwise (never mislabel a checkpoint column).
        var_names = problems[0].var_names
        if any(p.var_names != var_names for p in problems):
            var_names = None

        if a_shared:
            A_shared = np.ascontiguousarray(problems[0].A)
            A = np.broadcast_to(A_shared[None], (len(problems), m, n))
        else:
            A_shared = None
            A = np.stack([p.A for p in problems])
        return cls(
            names=[p.name for p in problems],
            c=np.stack([p.c for p in problems]),
            q2=np.stack([p.q2 for p in problems]),
            A=A,
            A_shared=A_shared,
            cl=np.stack([p.cl for p in problems]),
            cu=np.stack([p.cu for p in problems]),
            lb=np.stack([p.lb for p in problems]),
            ub=np.stack([p.ub for p in problems]),
            is_int=is_int,
            const=np.array([p.const for p in problems]),
            tree=tree,
            var_names=var_names,
            repair_fn=problems[0].repair_fn,
        )

    @property
    def num_scenarios(self) -> int:
        return len(self.names)

    @property
    def num_vars(self) -> int:
        return int(self.c.shape[1])

    @property
    def num_rows(self) -> int:
        return int(self.A.shape[1])

    @property
    def probs(self) -> np.ndarray:
        return self.tree.scen_prob

    def nonant_mask(self) -> np.ndarray:
        """(n,) bool mask of nonant slots."""
        mask = np.zeros(self.num_vars, dtype=bool)
        mask[self.tree.nonant_indices] = True
        return mask

    def augment(self, extra_cols: int, extra_rows: int,
                col_lb=0.0, col_ub=0.0,
                col_names=None) -> "ScenarioBatch":
        """A NEW batch with ``extra_cols`` zero-cost columns and
        ``extra_rows`` inactive (-inf, +inf) row slots appended.

        The device-batch analogue of the reference's model reshaping
        (cross_scen_extension.py:120-283 attaches eta variables and cut
        Constraints to every scenario model): fixed shapes mean one compiled
        program, so structural additions must be PREALLOCATED slots that
        later in-place writes activate (then bump ``version``).  Appending
        keeps every existing column index — tree/nonant arrays stay valid.
        """
        S, m, n = self.A.shape
        dc, dr = int(extra_cols), int(extra_rows)
        pad_c = np.zeros((S, dc))
        if self.A_shared is not None:
            # sharedness SURVIVES augmentation: the new slots start zero in
            # the single (m+dr, n+dc) matrix and later in-place writes must
            # go through ``A_shared`` (identical coefficients for every
            # scenario — the eta-vector cut formulation guarantees this;
            # per-scenario structure belongs in costs/rhs/bounds)
            A_shared = np.zeros((m + dr, n + dc))
            A_shared[:m, :n] = self.A_shared
            A = np.broadcast_to(A_shared[None], (S, m + dr, n + dc))
        else:
            A_shared = None
            A = np.zeros((S, m + dr, n + dc))
            A[:, :m, :n] = self.A
        names = None
        if self.var_names is not None:
            names = self.var_names + list(
                col_names or [f"_aug{i}" for i in range(dc)])
        return dataclasses.replace(
            self,
            c=np.concatenate([self.c, pad_c], axis=1),
            q2=np.concatenate([self.q2, pad_c], axis=1),
            A=A,
            A_shared=A_shared,
            cl=np.concatenate([self.cl, np.full((S, dr), -INF)], axis=1),
            cu=np.concatenate([self.cu, np.full((S, dr), INF)], axis=1),
            lb=np.concatenate(
                [self.lb, np.full((S, dc), float(col_lb))], axis=1),
            ub=np.concatenate(
                [self.ub, np.full((S, dc), float(col_ub))], axis=1),
            is_int=np.concatenate([self.is_int, np.zeros(dc, dtype=bool)]),
            var_names=names,
            version=self.version + 1,
        )

    def objective(self, x: np.ndarray) -> np.ndarray:
        """(S,) per-scenario objective values at x of shape (S, n)."""
        lin = np.einsum("sn,sn->s", self.c, x)
        quad = 0.5 * np.einsum("sn,sn->s", self.q2, x * x)
        return lin + quad + self.const


def _quantize(v: int, quantum: int) -> int:
    return int(-(-v // quantum) * quantum)


@dataclasses.dataclass
class BucketedBatch:
    """Shape-bucketed scenario batch for RAGGED families (SURVEY §7 hard
    part 2; VERDICT r1 weak #9).

    ``ScenarioBatch`` pads every scenario to the family maximum — one
    oversized scenario makes the whole (S, m, n) constraint tensor pay
    quadratically.  Here scenarios are grouped by their (n, m) rounded up to
    a quantum; each bucket is its own compact :class:`ScenarioBatch` (its
    own compiled solver program), while the LINEAR-memory bookkeeping
    arrays (c, q2, lb, ub, cl, cu — all 2-D) are still exposed padded to
    the global maxima so PH/xhat bookkeeping code is unchanged.  The
    quadratic ``A`` tensor deliberately has NO padded global view.

    Uneven bundling (np.array_split remainders) is the in-repo source of
    ragged shapes; per-bucket ``is_int`` also lifts ScenarioBatch's
    same-integer-pattern-across-scenarios restriction for bundles.
    """

    names: list
    buckets: list          # [(np.ndarray scenario indices, ScenarioBatch)]
    tree: "TreeInfo"
    c: np.ndarray          # (S, n_max) — bookkeeping views, zero-padded
    q2: np.ndarray
    lb: np.ndarray
    ub: np.ndarray
    cl: np.ndarray         # (S, m_max)
    cu: np.ndarray
    const: np.ndarray      # (S,)
    var_names: list | None = None   # column names are bucket-local; the
    # global bookkeeping layout degrades to slot indices (None)
    version: int = 0

    @classmethod
    def from_problems(cls, problems, quantum: int = 16) -> "BucketedBatch":
        groups: dict = {}
        for i, p in enumerate(problems):
            nq = _quantize(p.num_vars, quantum)
            mq = _quantize(p.num_rows, quantum)
            # subgroup by the PADDED integer pattern: ScenarioBatch requires
            # one is_int pattern per batch, and shape-padding alone can make
            # patterns differ within a quantized bucket (integer columns in
            # the tail of the wider member)
            patt = np.zeros(nq, dtype=bool)
            patt[:p.num_vars] = p.is_int
            key = (nq, mq, patt.tobytes())
            groups.setdefault(key, []).append(i)
        order = sorted(groups)          # deterministic bucket order
        probs = [p.prob for p in problems]
        if all(pr is None for pr in probs):
            problems = [dataclasses.replace(p, prob=1.0 / len(problems))
                        for p in problems]
        elif any(pr is None for pr in probs):
            raise ValueError(
                "either all or no scenarios may carry a probability")
        buckets = []
        for key in order:
            idx = np.asarray(groups[key], dtype=np.int64)
            members = [problems[i] for i in idx]
            # normalize probs within the bucket: the sub-batch's internal
            # tree is solver plumbing only (reductions use the OUTER tree),
            # but its construction validates a unit probability mass
            tot = sum(p.prob for p in members)
            members = [dataclasses.replace(p, prob=p.prob / tot)
                       for p in members]
            sub = ScenarioBatch.from_problems(members)
            buckets.append((idx, sub))
        tree = build_tree(problems)
        S = len(problems)
        n_max = max(p.num_vars for p in problems)
        m_max = max(p.num_rows for p in problems)

        def pad2(get, width):
            out = np.zeros((S, width))
            for i, p in enumerate(problems):
                v = get(p)
                out[i, :v.shape[0]] = v
            return out

        lb = pad2(lambda p: p.lb, n_max)
        ub = pad2(lambda p: p.ub, n_max)   # padded slots clamp at 0
        return cls(
            names=[p.name for p in problems],
            buckets=buckets, tree=tree,
            c=pad2(lambda p: p.c, n_max), q2=pad2(lambda p: p.q2, n_max),
            lb=lb, ub=ub,
            cl=pad2(lambda p: p.cl, m_max), cu=pad2(lambda p: p.cu, m_max),
            const=np.array([p.const for p in problems]),
        )

    # ---- ScenarioBatch-compatible surface -------------------------------
    @property
    def num_scenarios(self) -> int:
        return len(self.names)

    @property
    def num_vars(self) -> int:
        return int(self.c.shape[1])

    @property
    def num_rows(self) -> int:
        return int(self.cl.shape[1])

    @property
    def probs(self) -> np.ndarray:
        return self.tree.scen_prob

    @property
    def A(self):
        raise AttributeError(
            "BucketedBatch has no global A tensor (that padding is the "
            "quadratic cost bucketing exists to avoid); iterate .buckets "
            "or disable shape_buckets for features needing batch.A")

    @property
    def is_int(self):
        ints = [sub.is_int[:sub.c.shape[1]] for _, sub in self.buckets]
        if any(i.any() for i in ints):
            raise AttributeError(
                "BucketedBatch does not expose a shared is_int pattern "
                "(buckets differ); integer xhat diving requires an unbucketed "
                "batch")
        return np.zeros(self.num_vars, dtype=bool)

    def nonant_mask(self) -> np.ndarray:
        mask = np.zeros(self.num_vars, dtype=bool)
        mask[self.tree.nonant_indices] = True
        return mask

    def padded_elements(self) -> int:
        """Total A elements across buckets (the memory the solve pays)."""
        return int(sum(idx.size * sub.num_rows * sub.num_vars
                       for idx, sub in self.buckets))

    def objective(self, x: np.ndarray) -> np.ndarray:
        out = np.zeros(self.num_scenarios)
        for idx, sub in self.buckets:
            out[idx] = sub.objective(x[idx][:, :sub.num_vars])
        return out
