"""chip_smoke.py's contract off the chip, and the small seams it leans on.

The smoke itself only means something on the TPU (``chiprun -- python
chip_smoke.py``); here: it refuses a CPU backend before building anything,
its host references and placement check are right on known answers, and
the no-silent-fallback seams this bring-up added hold (``make_mesh``,
``ADMMSettings.jdtype``, the one compile-cache-directory function).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from tpusppy.ir import ScenarioBatch  # noqa: E402
from tpusppy.models import farmer  # noqa: E402
from tpusppy.parallel import sharded  # noqa: E402
from tpusppy.solvers import aot  # noqa: E402
from tpusppy.solvers.admm import ADMMSettings  # noqa: E402


def _farmer(S, **kw):
    return ScenarioBatch.from_problems(
        [farmer.scenario_creator(nm, num_scens=S, **kw)
         for nm in farmer.scenario_names_creator(S)])


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_smoke_refuses_cpu_before_building_anything(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")] + argv,
        env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=120)
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    # the verdict is the ONLY line: no "start" line, no phase line — the
    # script returned before it imported the program or built a model
    assert len(lines) == 1
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"


def test_highs_references_match_the_farmer_goldens():
    batch = _farmer(3)
    assert chip_smoke.highs_two_stage_ef(batch) == pytest.approx(
        -108390.0, rel=1e-9)
    objs = [chip_smoke.highs_scenario_lp(batch, s) for s in range(3)]
    assert sorted(objs) == pytest.approx(
        [-167666.66666667, -118600.0, -59950.0], rel=1e-9)


def test_placement_check_catches_a_leaf_left_on_one_device():
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = sharded.make_mesh(4)
    S = 8
    split = jax.device_put(np.zeros((S, 3)), NamedSharding(mesh, P("scen")))
    lone = jax.device_put(np.zeros((S, 3)), jax.devices()[0])
    good = chip_smoke._sharded_placement({"t": {"a": split}}, S, 4, "cpu")
    assert good["ok"] and good["sharded_leaves"] == 1
    bad = chip_smoke._sharded_placement(
        {"t": {"a": split, "b": lone}}, S, 4, "cpu")
    assert not bad["ok"] and bad["bad"][0]["devices"] == 1


def test_make_mesh_raises_when_asked_for_more_devices_than_exist():
    assert len(jax.devices()) == 8
    with pytest.raises(ValueError, match="need 16 devices, have 8"):
        sharded.make_mesh(16)
    assert sharded.make_mesh(8).devices.size == 8


def test_float64_settings_raise_without_x64():
    st = ADMMSettings(dtype="float64")
    assert st.jdtype() == np.float64          # conftest runs x64
    with jax.enable_x64(False):
        with pytest.raises(ValueError, match="jax_enable_x64"):
            st.jdtype()
        assert ADMMSettings(dtype="float32").jdtype() == np.float32


def test_compile_cache_dir_is_the_variable_else_the_checkout(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert aot.compile_cache_dir() == "/somewhere/else"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert aot.compile_cache_dir() == os.path.join(REPO, ".jax_cache")
