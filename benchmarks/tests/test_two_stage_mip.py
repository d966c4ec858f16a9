"""``references/two_stage_mip.py`` with integers on (``relax_integers:
false``), at sizes a test run can hold: its relaxed numbers are
``two_stage_lp``'s, its integer numbers are those of MIPs assembled here
independently, and the four checks of the incumbent pass on the integer
extensive form's own point and fail, each, on the fault it is there for."""

import importlib

import numpy as np
import pytest
import scipy.optimize as sopt

from benchmarks.harness import checks, core

FAMILIES = {
    "sslp": ("sslp", 5, {"num_servers": 5, "num_clients": 25,
                         "seedoffset": 7, "relax_integers": False}),
    "sizes": ("sizes", 3, {"scenario_count": 3, "relax_integers": False}),
    "netdes": ("netdes", 3, {"num_nodes": 10, "num_scens": 3,
                             "seedoffset": 3, "relax_integers": False}),
}
# sizes-3 is not proven optimal in 40 minutes of HiGHS: both sides stop
# after this long and the interval [bound, price] is what is compared
SIZES_SECONDS = 10.0
SIZES_GOLDEN = 224_000.0           # tpusppy/models/sizes.py, "Golden"
_refs = {}


def refs(family):
    """(two_stage_mip, two_stage_lp) on the same creator's data."""
    if family not in _refs:
        model, S, kw = FAMILIES[family]
        module = importlib.import_module("tpusppy.models." + model)
        names = module.scenario_names_creator(S)
        _refs[family] = tuple(
            core.load_reference({"reference": name})(module, names, kw)
            for name in ("two_stage_mip", "two_stage_lp"))
    return _refs[family]


_efs = {}


def ef_int(family):
    if family not in _efs:
        mip = refs(family)[0]
        _efs[family] = (mip.ef_int(time_limit=SIZES_SECONDS)
                        if family == "sizes" else mip.ef_int())
    return _efs[family]


@pytest.mark.parametrize("family", FAMILIES)
def test_its_relaxed_numbers_are_two_stage_lps(family):
    mip, lp = refs(family)
    assert mip.is_int.any() and not hasattr(lp, "is_int")
    assert mip.ef() == lp.ef()
    rng = np.random.default_rng(5)
    x = np.clip(rng.uniform(0, 2, (mip.S, mip.n)), mip.lb,
                np.where(np.isfinite(mip.ub), mip.ub, 2.0))
    assert np.array_equal(mip.objective(x), lp.objective(x))
    assert np.array_equal(mip.xbar_of(x), lp.xbar_of(x))
    K = mip.nonant.size
    for s in range(mip.S):
        cost = rng.normal(size=mip.n) + 2.0 * np.abs(mip.c[s])
        assert mip.scenario_opt(s) == lp.scenario_opt(s)
        assert mip.lin_min(s, cost) == lp.lin_min(s, cost)
        assert mip.infeasibility(s, x[s]) == lp.infeasibility(s, x[s])
        a = (s, x[s], rng.normal(size=K), x[0, mip.nonant], np.full(K, 2.0))
        assert mip.prox_gap(*a) == lp.prox_gap(*a)


def independent_ef(ref, **options):
    """The integer extensive form assembled another way: every scenario
    keeps all its columns, and rows tie the first stages together."""
    S, n, m, K = ref.S, ref.n, ref.m, ref.nonant.size
    A = np.zeros((S * m + (S - 1) * K, S * n))
    cl, cu = np.zeros(A.shape[0]), np.zeros(A.shape[0])
    for s in range(S):
        A[s * m:(s + 1) * m, s * n:(s + 1) * n] = ref.csr(s).toarray()
        cl[s * m:(s + 1) * m], cu[s * m:(s + 1) * m] = ref.cl[s], ref.cu[s]
    for s in range(1, S):
        for k, j in enumerate(ref.nonant):
            r = S * m + (s - 1) * K + k
            A[r, j], A[r, s * n + j] = 1.0, -1.0
    res = sopt.milp(
        c=(ref.probs[:, None] * ref.c).ravel(),
        constraints=sopt.LinearConstraint(A, cl, cu),
        integrality=np.tile(ref.is_int.astype(int), S),
        bounds=sopt.Bounds(ref.lb.ravel(), ref.ub.ravel()), options=options)
    const = float(ref.probs @ ref.const)
    return res.fun + const, res.mip_dual_bound + const


@pytest.mark.parametrize("family", ["sslp", "netdes"])
def test_its_integer_ef_is_the_mip_assembled_independently(family):
    mip = refs(family)[0]
    price, x, bound = ef_int(family)
    want, _ = independent_ef(mip)
    assert price == pytest.approx(want, rel=1e-6, abs=1e-6)
    assert bound == pytest.approx(price, rel=1e-5, abs=1e-6)
    assert x.shape == (mip.S, mip.n)
    assert price == pytest.approx(mip.probs @ mip.objective(x), rel=1e-9)
    assert price >= mip.ef() - 1e-9          # never under the relaxation


def test_sizes3_brackets_the_golden():
    """HiGHS does not prove sizes-3 optimal in any time a test has: the
    price of the point it found and its bound bracket the model file's
    golden 224,000, and so do the independent assembly's."""
    mip = refs("sizes")[0]
    price, x, bound = ef_int("sizes")
    want_price, want_bound = independent_ef(mip, time_limit=SIZES_SECONDS)
    for lo, hi in ((bound, price), (want_bound, want_price), (bound, want_price),
                   (want_bound, price)):
        assert mip.ef() - 1e-6 <= lo <= SIZES_GOLDEN + 1000.0
        assert SIZES_GOLDEN - 1000.0 <= hi <= 1.02 * SIZES_GOLDEN
    # two digits, as the source's own test rounds it (220,000)
    assert round(bound, -4) == 220_000.0
    assert price == pytest.approx(mip.probs @ mip.objective(x), rel=1e-9)


@pytest.mark.parametrize("family", ["sslp", "netdes"])
def test_the_integer_minimum_of_a_linear_cost(family):
    mip = refs(family)[0]
    for s in range(mip.S):
        got = mip.int_min(s, mip.c[s])
        res = sopt.milp(
            c=mip.c[s], integrality=mip.is_int.astype(int),
            constraints=sopt.LinearConstraint(
                np.asarray(mip.csr(s).toarray()), mip.cl[s], mip.cu[s]),
            bounds=sopt.Bounds(mip.lb[s], mip.ub[s]))
        assert got == pytest.approx(res.fun, rel=1e-9, abs=1e-9)
        assert got >= mip.lin_min(s, mip.c[s]) - 1e-9


def test_above_the_cap_there_is_no_integer_ef(monkeypatch):
    mip = refs("netdes")[0]
    its = type(mip).ef_int.__globals__       # the file's own names
    assert its["EF_INT_MAX_COLS"] >= 655     # sslp 5 x 25, S=5
    monkeypatch.setitem(its, "EF_INT_MAX_COLS", 10)
    assert mip.ef_int() is None


INCUMBENT = [{"name": "incumbent_infeas_rel", "limit": 1e-5},
             {"name": "incumbent_frac", "limit": 1e-5},
             {"name": "incumbent_nonant_spread", "limit": 1e-9},
             {"name": "inner_vs_incumbent_rel", "limit": 1e-9}]


def evidence(family):
    mip = refs(family)[0]
    price, x, _bound = ef_int(family)
    return {"ref": mip, "incumbent": x.copy(), "inner": price}


def fractional_column(ev):
    """Half a unit on one integer column: of one scenario where the second
    stage has one, else (netdes) of every scenario's first stage alike."""
    ref = ev["ref"]
    rest = np.setdiff1d(np.flatnonzero(ref.is_int), ref.nonant)
    if rest.size:
        ev["incumbent"][0, rest[0]] += 0.5
    else:
        ev["incumbent"][:, np.flatnonzero(ref.is_int)[0]] += 0.5


def violated_row(ev):
    """Whole steps along one second-stage column until a row of scenario
    1 breaks: the point stays integral and nonanticipative."""
    ref, x = ev["ref"], ev["incumbent"]
    a = ref.csr(1).toarray()
    j = next(j for j in np.setdiff1d(np.arange(ref.n), ref.nonant)
             if np.abs(a[:, j]).max() > 0)
    x[1, j] += np.ceil(
        2.0 * (np.abs(a @ x[1]).max() + 1.0) / np.abs(a[:, j]).max())


def first_stage_differs(ev):
    ev["incumbent"][1, ev["ref"].nonant[0]] += 1.0


def mispriced_inner_bound(ev):
    ev["inner"] = ev["inner"] - 0.01 * max(1.0, abs(ev["inner"]))


FAULTS = {"incumbent_frac": fractional_column,
          "incumbent_infeas_rel": violated_row,
          "incumbent_nonant_spread": first_stage_differs,
          "inner_vs_incumbent_rel": mispriced_inner_bound}


@pytest.mark.parametrize("family", FAMILIES)
def test_the_incumbent_checks_pass_on_the_integer_efs_own_point(family):
    ok, rows = checks.decide([evidence(family)], INCUMBENT)
    assert ok, rows


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("check", FAULTS)
def test_each_incumbent_check_fails_on_its_planted_fault(family, check):
    ev = evidence(family)
    FAULTS[check](ev)
    if check != "inner_vs_incumbent_rel":
        # the bound is still the price of the point: the fault is the point
        ev["inner"] = ev["ref"].probs @ ev["ref"].objective(ev["incumbent"])
    ok, rows = checks.decide([ev], INCUMBENT)
    failed = {r["name"] for r in rows if not r["ok"]}
    assert not ok and check in failed, rows
    if check != "incumbent_infeas_rel":      # a step off a row may leave a box
        assert failed <= {check, "incumbent_infeas_rel"}, rows


def test_no_incumbent_is_nothing_to_compare():
    ev = dict(evidence("netdes"), incumbent=None)
    ok, rows = checks.decide([ev], INCUMBENT)
    assert not ok and all(r["value"] is None for r in rows)
    ok, rows = checks.decide([ev], [dict(c, absent="skip") for c in INCUMBENT])
    assert ok
    # a reference that reads no is_int cannot say what is integral
    ev = dict(evidence("netdes"), ref=refs("netdes")[1])
    ok, rows = checks.decide([ev], INCUMBENT)
    assert {r["name"] for r in rows if r["value"] is None} == {
        "incumbent_frac"}
