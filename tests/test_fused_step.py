"""Fused multi-iteration PH step: trajectory parity with the step pair.

The fused program (``sharded.make_ph_fused_step``) exists to make the
headline rate latency-proof — k PH iterations per device dispatch instead
of one, so a fetch per iteration cannot serialize the device.
It must be a pure re-packaging: same refresh cadence, bit-comparable
trajectory to driving the (refresh, frozen) pair from the host.
"""

import numpy as np
import pytest

from tpusppy.ir import ScenarioBatch
from tpusppy.models import farmer
from tpusppy.parallel import sharded
from tpusppy.solvers.admm import ADMMSettings


def make_batch(n, **kw):
    names = farmer.scenario_names_creator(n)
    return ScenarioBatch.from_problems(
        [farmer.scenario_creator(nm, num_scens=n, **kw) for nm in names]
    )


def _host_loop(refresh, frozen, state, arr, iters, refresh_every):
    factors = None
    for i in range(iters):
        if i % refresh_every == 0:
            state, out, factors = refresh(state, arr, 1.0)
        else:
            state, out = frozen(state, arr, 1.0, factors)
    return state, out


@pytest.mark.parametrize("shared", [False, True])
def test_fused_matches_step_pair(shared):
    if shared:
        # uc_lite's uncertainty enters the rhs only -> A_shared engine
        from tpusppy.models import uc_lite
        names = uc_lite.scenario_names_creator(6)
        batch = ScenarioBatch.from_problems([
            uc_lite.scenario_creator(nm, num_scens=6, relax_integers=True)
            for nm in names])
        assert batch.A_shared is not None
    else:
        batch = make_batch(6)
    mesh = sharded.make_mesh()
    settings = ADMMSettings(max_iter=120, restarts=2)
    arr = sharded.shard_batch(batch, mesh)
    idx = batch.tree.nonant_indices
    refresh, frozen = sharded.make_ph_step_pair(idx, settings, mesh)
    state0 = sharded.init_state(arr, 1.0, settings)
    state0, _, _ = refresh(state0, arr, 0.0)  # Iter0

    s_ref, out_ref = _host_loop(refresh, frozen, state0, arr, 8, 4)

    fused = sharded.make_ph_fused_step(idx, settings, mesh,
                                       chunk=8, refresh_every=4)
    s_f, out_f = fused(state0, arr, 1.0)

    np.testing.assert_allclose(np.asarray(out_f.conv),
                               np.asarray(out_ref.conv), rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(np.asarray(out_f.eobj),
                               np.asarray(out_ref.eobj), rtol=1e-9)
    np.testing.assert_allclose(np.asarray(s_f.W), np.asarray(s_ref.W),
                               rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(np.asarray(s_f.xbars), np.asarray(s_ref.xbars),
                               rtol=1e-8, atol=1e-10)


def test_fused_single_refresh_block():
    """chunk == refresh_every: one refresh then frozen sweeps, one program."""
    batch = make_batch(4)
    mesh = sharded.make_mesh()
    settings = ADMMSettings(max_iter=120, restarts=2)
    arr = sharded.shard_batch(batch, mesh)
    idx = batch.tree.nonant_indices
    refresh, frozen = sharded.make_ph_step_pair(idx, settings, mesh)
    state0 = sharded.init_state(arr, 1.0, settings)
    state0, _, _ = refresh(state0, arr, 0.0)

    s_ref, out_ref = _host_loop(refresh, frozen, state0, arr, 5, 5)
    fused = sharded.make_ph_fused_step(idx, settings, mesh, chunk=5)
    s_f, out_f = fused(state0, arr, 1.0)
    np.testing.assert_allclose(np.asarray(out_f.eobj),
                               np.asarray(out_ref.eobj), rtol=1e-9)
    np.testing.assert_allclose(np.asarray(s_f.W), np.asarray(s_ref.W),
                               rtol=1e-8, atol=1e-10)


@pytest.mark.parametrize("chunk,refresh_every", [(6, 3), (10, 4), (7, 7)])
def test_fused_parity_nondefault_cadences(chunk, refresh_every):
    """Trajectory parity vs the unfused pair at 1e-9 on the 1-device mesh
    for autotuner-reachable (chunk, refresh_every) combinations — including
    the non-multiple-of-refresh case (10, 4): a trailing partial block
    (refresh + 1 frozen) must keep the host cadence exactly."""
    batch = make_batch(5)
    mesh = sharded.make_mesh(1)
    settings = ADMMSettings(max_iter=120, restarts=2)
    arr = sharded.shard_batch(batch, mesh)
    idx = batch.tree.nonant_indices
    refresh, frozen = sharded.make_ph_step_pair(idx, settings, mesh)
    state0 = sharded.init_state(arr, 1.0, settings)
    state0, _, _ = refresh(state0, arr, 0.0)

    s_ref, out_ref = _host_loop(refresh, frozen, state0, arr, chunk,
                                refresh_every)
    fused = sharded.make_ph_fused_step(
        idx, settings, mesh, chunk=chunk, refresh_every=refresh_every,
        donate=False)
    s_f, out_f = fused(state0, arr, 1.0)
    np.testing.assert_allclose(np.asarray(out_f.conv),
                               np.asarray(out_ref.conv),
                               rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(np.asarray(out_f.eobj),
                               np.asarray(out_ref.eobj), rtol=1e-9)
    np.testing.assert_allclose(np.asarray(s_f.W), np.asarray(s_ref.W),
                               rtol=1e-9, atol=1e-10)
    np.testing.assert_allclose(np.asarray(s_f.xbars),
                               np.asarray(s_ref.xbars),
                               rtol=1e-9, atol=1e-10)


def test_fused_trace_collection():
    """collect='trace' returns the device-side per-iteration PHStepOut
    stack; last entry equals the collect='last' result and the sweep
    counters feed the MFU model."""
    batch = make_batch(4)
    mesh = sharded.make_mesh(1)
    settings = ADMMSettings(max_iter=120, restarts=2)
    arr = sharded.shard_batch(batch, mesh)
    idx = batch.tree.nonant_indices
    refresh, _ = sharded.make_ph_step_pair(idx, settings, mesh)
    state0 = sharded.init_state(arr, 1.0, settings)
    state0, _, _ = refresh(state0, arr, 0.0)

    f_last = sharded.make_ph_fused_step(idx, settings, mesh, chunk=7,
                                        refresh_every=3, donate=False)
    f_tr = sharded.make_ph_fused_step(idx, settings, mesh, chunk=7,
                                      refresh_every=3, donate=False,
                                      collect="trace")
    _, out = f_last(state0, arr, 1.0)
    _, tr = f_tr(state0, arr, 1.0)
    assert np.asarray(tr.conv).shape == (7,)
    assert np.asarray(tr.iters).shape == (7,)
    np.testing.assert_allclose(np.asarray(tr.conv)[-1],
                               np.asarray(out.conv), rtol=1e-12)
    assert (np.asarray(tr.iters) >= 1).all()


def test_fused_donation_consumes_state():
    """donate=True (the default) aliases the PHState buffers into the
    program: the input state is deleted after the call and the returned
    state carries the trajectory forward."""
    batch = make_batch(4)
    mesh = sharded.make_mesh(1)
    settings = ADMMSettings(max_iter=80, restarts=2)
    arr = sharded.shard_batch(batch, mesh)
    idx = batch.tree.nonant_indices
    refresh, _ = sharded.make_ph_step_pair(idx, settings, mesh)
    state, _, _ = refresh(sharded.init_state(arr, 1.0, settings), arr, 0.0)

    fused = sharded.make_ph_fused_step(idx, settings, mesh, chunk=4,
                                       refresh_every=4)
    prev = state
    state, out = fused(state, arr, 1.0)
    assert prev.W.is_deleted()
    assert not state.W.is_deleted()
    # re-entry with the donated-output state works (steady-state loop)
    state, out2 = fused(state, arr, 1.0)
    assert np.isfinite(float(np.asarray(out2.conv)))


def test_fused_rejects_bad_cadence():
    with pytest.raises(ValueError):
        sharded.make_ph_fused_step(np.arange(3), ADMMSettings(), chunk=0)
    with pytest.raises(ValueError):
        sharded.make_ph_fused_step(np.arange(3), ADMMSettings(),
                                   chunk=4, refresh_every=0)
    with pytest.raises(ValueError):
        sharded.make_ph_fused_step(np.arange(3), ADMMSettings(),
                                   chunk=4, collect="everything")


def test_fused_iteration_cap_regimes():
    mesh = sharded.make_mesh()
    settings = ADMMSettings(max_iter=200, restarts=2)
    small = sharded.shard_batch(make_batch(8), mesh)
    cap = sharded.fused_iteration_cap(small, settings, mesh, refresh_every=16)
    assert cap >= 16 and cap % 16 == 0
    # reference-UC-scale shapes must refuse to fuse (worker watchdog)
    huge = int(
        sharded.segmented_solvers.fused_iteration_budget(
            1000, 16008, 12408, settings, 16, factor_batch=1))
    assert huge == 0
