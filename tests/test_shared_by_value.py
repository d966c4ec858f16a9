"""``ScenarioBatch.from_problems`` finds a shared constraint matrix by value.

One rule in one place (``ir._shares_one_A``): identity first, then shape and
content, stopping at the first scenario that differs.  Everything downstream
(``spopt``, ``sharded``, ``canonical``, ``xhat_eval``) reads ``A_shared``.
Also here: the sslp creator's output is bit for bit what it was before its
build was made fast (whole rows handed to the builder, instance data drawn
once), by digests taken on the tree before the change.
"""

import dataclasses
import hashlib
import importlib

import numpy as np
import pytest

from tpusppy.ir import ScenarioBatch
from tpusppy.models import farmer, sslp
from tpusppy.obs import metrics

SSLP_KW = dict(num_servers=5, num_clients=25, relax_integers=False)


def sslp_problems(S=4, **kw):
    kw = dict(SSLP_KW, **kw)
    return [sslp.scenario_creator(nm, **kw)
            for nm in sslp.scenario_names_creator(S)]


def by_value_count():
    return metrics.dump().get("ingest.a_shared_by_value", 0)


def test_equal_by_value_is_shared_and_A_is_a_broadcast_view():
    ps = sslp_problems()
    assert len({id(p.A) for p in ps}) == len(ps)     # an A a scenario
    batch = ScenarioBatch.from_problems(ps)
    assert batch.A_shared is not None
    assert batch.A_shared.shape == ps[0].A.shape
    assert batch.A.shape == (len(ps),) + ps[0].A.shape
    assert batch.A.strides[0] == 0                    # no (S, m, n) memory
    assert np.shares_memory(batch.A, batch.A_shared)
    np.testing.assert_array_equal(batch.A[3], ps[3].A)
    assert by_value_count() == 1


def test_one_entry_changed_in_one_scenario_is_not_shared():
    ps = sslp_problems()
    A = ps[2].A.copy()
    A[0, 0] += 1.0
    ps[2] = dataclasses.replace(ps[2], A=A)
    batch = ScenarioBatch.from_problems(ps)
    assert batch.A_shared is None
    assert batch.A.strides[0] != 0
    np.testing.assert_array_equal(batch.A[2], A)
    assert by_value_count() == 0


def test_a_ragged_family_is_not_shared():
    ps = sslp_problems(S=2, relax_integers=True) + [sslp.scenario_creator(
        "Scenario3", **dict(SSLP_KW, num_clients=20, relax_integers=True))]
    batch = ScenarioBatch.from_problems(ps)
    assert batch.A_shared is None
    assert batch.A.shape == (3,) + ps[0].A.shape      # padded to the widest
    assert by_value_count() == 0


def test_identity_is_still_shared_and_is_not_counted_as_by_value():
    ps = sslp_problems()
    ps = [dataclasses.replace(p, A=ps[0].A) for p in ps]
    batch = ScenarioBatch.from_problems(ps)
    assert batch.A_shared is ps[0].A
    assert by_value_count() == 0


def test_farmer_differs_at_the_first_pair():
    """farmer's yields are random: its ingest pays for one comparison."""
    ps = [farmer.scenario_creator(nm, num_scens=3)
          for nm in farmer.scenario_names_creator(3)]
    assert not np.array_equal(ps[0].A, ps[1].A)
    assert ScenarioBatch.from_problems(ps).A_shared is None


def test_family_key_of_a_by_value_batch_equals_identity_shared():
    from tpusppy.service import canonical
    from tpusppy.solvers.admm import ADMMSettings

    ps = sslp_problems()
    by_value = ScenarioBatch.from_problems(ps)
    by_identity = ScenarioBatch.from_problems(
        [dataclasses.replace(p, A=ps[0].A) for p in ps])
    st = ADMMSettings()
    assert canonical.family_key(by_value, st) \
        == canonical.family_key(by_identity, st)
    assert canonical.content_fingerprint(by_value) \
        == canonical.content_fingerprint(by_identity)
    dense = dataclasses.replace(by_value, A=np.array(by_value.A),
                                A_shared=None)
    assert canonical.family_key(dense, st) != canonical.family_key(
        by_value, st)


FAMILIES = {
    # family: (creator kwargs, shared by value)
    "sslp": (SSLP_KW, True),
    "gbd": ({}, True),
    "hydro": ({"branching_factors": [3, 3]}, True),
    "netdes": ({"num_nodes": 6}, True),
    "sizes": ({"scenario_count": 3}, True),
    "usar": ({}, True),
    "farmer": ({"num_scens": 3}, False),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_which_families_the_rule_finds_shared(family):
    """The families whose creators build an ``A`` a scenario with the same
    content run the shared engine; ``.A`` of each is what the creators made."""
    kw, shared = FAMILIES[family]
    module = importlib.import_module("tpusppy.models." + family)
    n = 9 if family == "hydro" else 3
    ps = [module.scenario_creator(nm, **kw)
          for nm in module.scenario_names_creator(n)]
    batch = ScenarioBatch.from_problems(ps)
    assert (batch.A_shared is not None) == shared
    for s, p in enumerate(ps):
        np.testing.assert_array_equal(batch.A[s], p.A)


def digest(p):
    h = hashlib.sha256()
    for f in ("c", "q2", "A", "cl", "cu", "lb", "ub", "is_int"):
        a = np.ascontiguousarray(getattr(p, f))
        h.update(f.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    h.update(repr(p.var_names).encode())
    h.update(repr((p.name, p.prob, p.const)).encode())
    nd = p.nodes[0]
    h.update(repr((nd.name, nd.cond_prob, nd.stage,
                   str(nd.nonant_indices.dtype),
                   nd.nonant_indices.tolist())).encode())
    return h.hexdigest()


SMALL = dict(num_servers=5, num_clients=25, seedoffset=0,
             relax_integers=False)
CELL = dict(num_servers=10, num_clients=50, seedoffset=3000000123,
            relax_integers=False)
RELAXED = dict(num_servers=5, num_clients=15, seedoffset=7,
               relax_integers=True)
# sha256 of every field of the creator's output at commit a0a7c73, before
# the builder took whole rows (the function above, run there)
GOLDEN = [
    (SMALL, "Scenario1",
     "01ec8cf7bd879a3ec0277e9fa387ebfc2e7a45090fbf5b2c79c6b4fcef7878e9"),
    (SMALL, "Scenario8",
     "dffdea9cd4eb7f320bb197e81b17a88a9e5122fcf820bbdf96f8ad98136e68f3"),
    (SMALL, "Scenario2000",
     "1b70daf50b5fbba4fc4c79945ce293cf32d5a150f7788dbc40430749f0f273f3"),
    (CELL, "Scenario1",
     "1d4f0d38ac04014b1bd698ce213a6b05862040fa21103eb2f0bba71f3577c262"),
    (CELL, "Scenario8",
     "29d71ceab920ea559ff6bf5d6cfdb39bcb67d8f0a826e921e1acc1913f3b29e6"),
    (CELL, "Scenario2000",
     "f167accdcb31446952abe313a1d4c5b547987e7eaaf903294d668f7c37b8eb9d"),
    (RELAXED, "Scenario1",
     "fa4fd11b0c27a7ae0a42d706ba896c568557ba9f2958b2b1a8d1188665664e50"),
    (RELAXED, "Scenario8",
     "7ba13a9685595470dac03006c58cc33bbb123c4162655ef829d3b48a3f24cf34"),
    (RELAXED, "Scenario2000",
     "31317ddfa0ba6877377176566605dcdabe790a62434f8c0c23827be6f151c33c"),
]


@pytest.mark.parametrize("kw,name,sha", GOLDEN)
def test_sslp_creator_output_is_bit_for_bit_what_it_was(kw, name, sha):
    assert digest(sslp.scenario_creator(name, **kw)) == sha


def test_builder_takes_whole_rows_and_named_blocks():
    """A row handed whole (columns, coefficients) builds what the same row
    handed as a dict does; a block of variables takes one value each."""
    from tpusppy.ir import LinearModelBuilder

    one = LinearModelBuilder("a")
    x = one.add_vars("x", 3, ub=[1.0, 2.0, 3.0], cost=np.arange(3.0))
    one.add_le({x[0]: 1.0, x[2]: -2.0}, 4.0)
    one.add_eq({"x[1]": 5.0}, 1.0)
    two = LinearModelBuilder("a")
    for i in range(3):
        two.add_var(f"x[{i}]", ub=i + 1.0, cost=float(i))
    two.add_le((np.array([0, 2]), np.array([1.0, -2.0])), 4.0)
    two.add_eq(([1], [5.0]), 1.0)
    p, q = one.build(), two.build()
    for f in ("c", "q2", "A", "cl", "cu", "lb", "ub", "is_int"):
        np.testing.assert_array_equal(getattr(p, f), getattr(q, f))
    assert p.var_names == q.var_names
    with pytest.raises(ValueError, match="duplicate variable x\\[1\\]"):
        one.add_var("x[1]")
    with pytest.raises(ValueError, match="duplicate variable y"):
        one.add_named_vars(["y", "y"])
