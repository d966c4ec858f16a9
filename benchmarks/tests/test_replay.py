"""The by-name checks against what the harness gave before references and
checks became files (PR 31): ``data/replay_<kind>.*`` hold the evidence of
one tiny farmer wheel and one tiny served request (CPU, float64), recorded
by ``record_replay.py`` on the harness as PR 29 left it, with the value each
of the 15 checks of ``harness/checks.py::CHECKS`` gave.  The same evidence
through ``checks/<name>.py`` and ``references/two_stage_lp.py`` gives the same
floats, bit for bit."""

import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmarks.harness import checks, core
from benchmarks.tests.record_replay import NAMES, STEP_KEYS

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def evidence(kind):
    with open(os.path.join(DATA, f"replay_{kind}.json")) as f:
        meta = json.load(f)
    arr = np.load(os.path.join(DATA, f"replay_{kind}.npz"))
    r = meta["ref"]
    module = importlib.import_module("tpusppy.models." + r["model"])
    ref = core.load_reference({})(
        module, module.scenario_names_creator(r["num_scens"]),
        r["creator_kwargs"])
    steps = [dict({k: arr["steps_" + k][i] for k in STEP_KEYS}, iteration=it)
             for i, it in enumerate(meta["step_iterations"])]
    watch = types.SimpleNamespace(x0=arr["x0"], steps=steps)
    ev = dict(ref=ref, watch=watch, seed=meta["seed"],
              first_iteration=meta["first_iteration"],
              outer=meta["outer"], inner=meta["inner"], incumbent=None,
              device_leaves=tuple(meta["device_leaves"]),
              record=meta["record"], iter_limit=meta["iter_limit"],
              **{k: arr[k] for k in ("x", "W", "xbars", "rho")})
    return ev, meta


CASES = [(kind, name) for kind in ("wheel", "served") for name in NAMES
         if (kind, name) != ("wheel", "record_bad")]
_kept = {}


@pytest.mark.parametrize("kind,name", CASES)
def test_a_check_found_by_name_gives_the_float_the_table_gave(kind, name):
    if kind not in _kept:           # one evidence a kind: the caches in it
        _kept[kind] = evidence(kind)
    ev, meta = _kept[kind]
    want = meta["values"][name]
    _ok, rows = checks.decide(
        [ev], [{"name": name, "limit": 1.0, "n_check": meta["n_check"]}])
    assert want is not None
    assert rows[0]["value"] == want, (rows[0]["value"] - want)


def test_the_record_holds_all_fifteen():
    served = evidence("served")[1]["values"]
    assert sorted(served) == sorted(NAMES) and len(NAMES) == 15
    assert all(v is not None for v in served.values())
