"""The main path's kernels compile for the chip — asked of the TPU's own
compiler here, without the chip (on-chip-measurement §2, rehearsal 3).

Each test lowers one program at the REAL shape for a described ``v5e:2x2``
device and compiles it: what Mosaic or XLA:TPU would refuse on the chip
(unaligned blocks, scoped-VMEM overflow, i64 block indices) is refused
here, at no chip time.  Shapes are the ones ``chip_smoke.py`` runs: the
``serve`` phase's farmer (S=1000, crops_multiplier=4) for the per-scenario
sweep kernel, the polish's elimination kernel and the wheel megastep, and
the served ``uc_lite`` family for the shared-A kernel.

Rules this file keeps (the driver runs the suite under ``-n 6``): nothing
chip-related happens at import or collection; the topology is described
inside a module-scoped fixture, in this process, and the fixture skips when
it cannot be described; all such tests live in this ONE file (a second file
could land on another xdist worker, which cannot load libtpu again).

Mosaic refuses i64 block indices and tests/conftest.py forces x64, so the
tests arrange 32-bit themselves (``jax.enable_x64(False)``); where program
code asks ``jax.default_backend()`` (it sees the CPU here) the test steers
it, not a new option of the program.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from tpusppy.solvers import pallas_kernels as pk
from tpusppy.solvers.admm import ADMMSettings

F32 = dict(dtype="float32", eps_abs=1e-5, eps_rel=1e-5)   # README recipe
FARMER_S, FARMER_MULT = 1000, 4


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip32(monkeypatch):
    """32-bit jax, jax's persistent compile cache off (a described-device
    executable is written but can never be read back without a chip), and
    the program's ``jax.default_backend()`` asks answered with the device
    the test compiles for."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _farmer_batch(S):
    from tpusppy.ir import ScenarioBatch
    from tpusppy.models import farmer

    return ScenarioBatch.from_problems(
        [farmer.scenario_creator(nm, num_scens=S,
                                 crops_multiplier=FARMER_MULT)
         for nm in farmer.scenario_names_creator(S)])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _has_mosaic_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_fused_sweeps_compiles_at_the_served_farmer_shape(one_chip, chip32):
    b = _farmer_batch(2)
    S, m, n = FARMER_S, b.num_rows, b.num_vars
    bs = pk.usable(S, m, n, platform="tpu")
    # lane-dim blocks: the whole batch or a multiple of 128 (Mosaic tiling)
    assert bs is not None and (bs == S or bs % 128 == 0)
    mat = lambda d0, d1: _spec((d0, d1, S), one_chip)
    vec = lambda d0: _spec((d0, S), one_chip)
    args = (vec(n), mat(m, n), mat(n, m), mat(n, n), mat(n, n),
            vec(m), vec(m), vec(n), vec(n), vec(m), vec(n),
            vec(n), vec(m), vec(n), vec(m), vec(n), vec(m))
    st = ADMMSettings(**F32)
    compiled = pk.fused_sweeps.lower(
        *args, n_sweeps=max(1, st.check_every), n_refine=st.solve_refine,
        sigma=float(st.sigma), alpha=float(st.alpha), bs=bs).compile()
    assert _has_mosaic_kernel(compiled)


def test_lanes_solve_compiles_at_the_served_farmer_polish_shape(one_chip,
                                                                chip32):
    """The polish's (n+m) saddle systems of farmer S=1000 x4, one
    right-hand side: the block the selector picks fits Mosaic's VMEM."""
    b = _farmer_batch(2)
    S, N = FARMER_S, b.num_vars + b.num_rows
    bs = pk.usable_solve(S, N, 1, platform="tpu")
    assert bs == 128
    compiled = pk.lanes_solve.lower(
        _spec((N, N, S), one_chip), _spec((N, 1, S), one_chip),
        bs=bs).compile()
    assert _has_mosaic_kernel(compiled)


def test_fused_sweeps_shared_compiles_at_the_served_uc_lite_shape(
        one_chip, chip32):
    from tpusppy.ir import ScenarioBatch
    from tpusppy.models import uc_lite

    S = 100
    b = ScenarioBatch.from_problems(
        [uc_lite.scenario_creator(nm, num_scens=2)
         for nm in uc_lite.scenario_names_creator(2)])
    assert b.A_shared is not None
    m, n = b.num_rows, b.num_vars
    bs = pk.usable_shared(S, m, n, platform="tpu")
    # sublane-dim blocks: the whole batch or a multiple of 8
    assert bs is not None and (bs == S or bs % 8 == 0)
    sh = lambda *shape: _spec(shape, one_chip)
    args = (sh(S, n), sh(m, n), sh(n, n), sh(n, n),
            sh(S, m), sh(S, m), sh(S, n), sh(S, n),
            sh(1, m), sh(1, n), sh(S, n), sh(1, 1), sh(S, 1),
            sh(S, n), sh(S, m), sh(S, n), sh(S, m), sh(S, n), sh(S, m))
    st = ADMMSettings(**F32)
    compiled = pk.fused_sweeps_shared.lower(
        *args, n_sweeps=max(1, st.check_every), n_refine=st.solve_refine,
        n_extra=2, sigma=float(st.sigma), alpha=float(st.alpha),
        bs=bs).compile()
    assert _has_mosaic_kernel(compiled)


def test_wheel_megastep_compiles_at_the_served_farmer_shape(one_chip,
                                                            chip32):
    """The hub's hot program (spopt._megastep_fn: mesh=None, donated
    state) at farmer S=1000 x4, one refresh window wide, with its sweep on
    the Pallas kernel the TPU branch picks."""
    from tpusppy.parallel import sharded

    settings = ADMMSettings(**F32)
    batch = _farmer_batch(FARMER_S)
    idx = batch.tree.nonant_indices
    arr = sharded.shard_batch(batch, sharded.make_mesh(1))
    state = sharded.init_state(arr, 1.0, settings)
    assert state.x.dtype == jnp.float32
    refresh, _ = sharded.make_ph_step_pair(idx, settings, None)
    _, _, factors = jax.eval_shape(refresh, state, arr, 1.0)
    on_chip = lambda tree: jax.tree.map(
        lambda a: _spec(a.shape, one_chip, a.dtype), tree)
    mega = sharded.make_wheel_megastep(idx, settings, None, n_iters=15,
                                       donate=True)
    compiled = mega._jitted.lower(
        on_chip(state), on_chip(arr), 1.0, on_chip(factors), 0.0, 15,
        1e-2).compile()
    mem = compiled.memory_analysis()
    print("wheel_megastep farmer S=1000 x4 memory_analysis:", mem)
    assert _has_mosaic_kernel(compiled)
    # one program's footprint must sit far inside one v5e chip (16 GiB)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < 2 ** 32


def test_sslp_frozen_solve_compiles_with_half_the_flops_on_lowrank_factors(
        one_chip, chip32):
    """The benchmark's sslp frozen solve (S=2000, n=520, m=60, float32, no
    K in the factors, as every wheel sets it): XLA:TPU compiles it with the
    diagonal-plus-rank-60 K^-1 that ``_factor_shared`` now returns at this
    shape, and counts under half the operations of the same program handed
    the (520, 520) explicit inverse."""
    from tpusppy.solvers import shared_admm, structured_kkt

    S, m, n = 2000, 60, 520
    st = ADMMSettings(factors_keep_K=False, **F32)
    sh = lambda *shape: _spec(shape, one_chip)
    assert structured_kkt.lowrank_kinv(sh(m, n))

    def flops(Kinv):
        fac = shared_admm.SharedFactors(
            D=sh(n), E=sh(m), cost=sh(), rho_a=sh(m), rho_x=sh(n),
            gamma=sh(S), Kinv=Kinv, K=None, q2ref=sh(n))
        compiled = shared_admm.solve_shared_frozen._jitted.lower(
            sh(S, n), sh(S, n), sh(m, n), sh(S, m), sh(S, m), sh(S, n),
            sh(S, n), fac, settings=st,
            warm=(sh(S, n), sh(S, m), sh(S, m), sh(S, n))).compile()
        assert not _has_mosaic_kernel(compiled)
        return compiled.cost_analysis()["flops"]

    lowrank = flops(structured_kkt.DiagLowRank(
        dinv=sh(n), W=sh(m, n), N=sh(m, n)))
    dense = flops(sh(n, n))
    print("sslp frozen solve flops a check block: lowrank", lowrank,
          "dense", dense)
    assert 0 < lowrank < 0.5 * dense
