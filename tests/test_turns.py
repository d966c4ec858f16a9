"""Device turns (``tpusppy/solvers/turns.py``): the gate through which a
wheel's cylinder threads take one device once its programs are long, and
the shared-A engine's adaptive solve handed over restart by restart."""

import threading
import time

import numpy as np
import pytest

from tpusppy.ir import ScenarioBatch
from tpusppy.models import sslp
from tpusppy.obs import metrics
from tpusppy.solvers import admm, shared_admm, turns


def run_party(gate, role, body):
    def target():
        turns.join(gate, role)
        try:
            body()
        finally:
            turns.join(None, None)

    t = threading.Thread(target=target, daemon=True)
    t.start()
    return t


def test_outside_a_wheel_everything_passes_through():
    turns.join(None, None)
    assert not turns.pieces()
    with turns.hub_step():
        pass
    with turns.chunk() as held:
        assert held is False


def test_the_gate_stays_out_of_the_way_until_a_hub_step_is_long():
    gate = turns.DeviceTurns(engage_secs=0.05)
    seen = {}

    def hub():
        with turns.hub_step():
            time.sleep(0.01)
        seen["after_short"] = gate.engaged
        with turns.hub_step():
            time.sleep(0.06)
        seen["after_long"] = gate.engaged

    run_party(gate, "hub", hub).join(5)
    assert seen == {"after_short": False, "after_long": True}

    def spoke():
        seen["pieces"] = turns.pieces()
        with turns.chunk() as held:
            seen["held"] = held

    # the accounts opened at zero: nothing is owed, the hub is away, so
    # the spoke goes once its patience is spent
    run_party(gate, "spoke", spoke).join(5)
    assert seen["pieces"] is True and seen["held"] is True
    gate.close()
    run_party(gate, "spoke", spoke).join(5)
    assert seen["pieces"] is False and seen["held"] is False


@pytest.mark.parametrize("share", [1.0 / 3.0, 0.2])
def test_one_holder_at_a_time_and_the_spokes_get_their_share(share):
    """A hub of long steps and two hungry spokes of uneven pieces: never
    two holders, and the spokes' seconds stand at their share of all
    seconds handed out, to within a piece."""
    gate = turns.DeviceTurns(spoke_share=share, engage_secs=0.0)
    stop = time.monotonic() + 1.5
    inside = []
    clash = []

    def hold(secs):
        inside.append(1)
        if len(inside) > 1:
            clash.append(len(inside))
        time.sleep(secs)
        inside.pop()

    def hub():
        k = 0
        while time.monotonic() < stop:
            with turns.hub_step():
                hold(0.04 if k % 2 else 0.015)
            k += 1
            time.sleep(0.001)           # the hub's host work

    def spoke(piece):
        def body():
            while time.monotonic() < stop:
                with turns.chunk() as held:
                    if held:
                        hold(piece)
                time.sleep(0.0005)
        return body

    parties = [run_party(gate, "hub", hub),
               run_party(gate, "spoke", spoke(0.004)),
               run_party(gate, "spoke", spoke(0.007))]
    for t in parties:
        t.join(10)
    gate.close()
    assert not clash
    hub_secs, spoke_secs = gate.accounts()
    assert hub_secs > 0.5
    assert abs(spoke_secs - share / (1 - share) * hub_secs) < 0.06


def test_a_waiting_spoke_is_let_go_when_the_gate_closes():
    gate = turns.DeviceTurns(engage_secs=0.0)
    state = {}

    def hub():
        with turns.hub_step():
            pass                          # engages
        with turns.hub_step():
            state["holding"] = True
            time.sleep(0.5)

    def spoke():
        with turns.chunk() as held:
            state["held"] = held
        state["done"] = time.monotonic()

    h = run_party(gate, "hub", hub)
    while "holding" not in state:
        time.sleep(0.005)
    s = run_party(gate, "spoke", spoke)
    time.sleep(0.05)
    assert "done" not in state            # the hub holds the device
    t0 = time.monotonic()
    gate.close()
    s.join(5)
    assert state["held"] is False and state["done"] - t0 < 0.2
    h.join(5)


def test_spokes_that_stay_away_are_not_owed_without_end():
    gate = turns.DeviceTurns(spoke_share=0.5, engage_secs=0.0)

    def hub():
        for _ in range(12):
            with turns.hub_step():
                time.sleep(0.01)

    run_party(gate, "hub", hub).join(5)
    hub_secs, spoke_secs = gate.accounts()
    # share one half: a second owed a hub second, less two longest steps
    assert hub_secs - spoke_secs < 0.05


# -- the shared-A engine, restart by restart -----------------------------------
def small_batch():
    names = sslp.scenario_names_creator(6)
    probs = [sslp.scenario_creator(nm, num_servers=5, num_clients=25,
                                   seedoffset=7) for nm in names]
    return ScenarioBatch.from_problems(probs)


@pytest.mark.parametrize("want_factors", [False, True])
def test_restart_by_restart_is_the_one_program_solve(want_factors):
    b = small_batch()
    assert b.A_shared is not None
    st = admm.ADMMSettings(dtype="float64", eps_abs=1e-8, eps_rel=1e-8)
    args = (b.c, b.q2, b.A_shared, b.cl, b.cu, b.lb, b.ub)
    whole = (shared_admm.solve_shared_factored if want_factors
             else shared_admm.solve_shared)(*args, settings=st)
    gate = turns.DeviceTurns(engage_secs=0.0)
    out = {}

    def hub():
        with turns.hub_step():
            pass

    def spoke():
        assert turns.pieces()
        out["before"] = metrics.dump().get("turns.spoke_secs", 0.0)
        out["sol"] = shared_admm.adaptive_in_turns(
            *args, settings=st, want_factors=want_factors)
        out["after"] = metrics.dump().get("turns.spoke_secs", 0.0)

    run_party(gate, "hub", hub).join(5)
    run_party(gate, "spoke", spoke).join(300)
    gate.close()
    assert out["before"] < out["after"]
    sol_w, sol_p = (whole[0], out["sol"][0]) if want_factors \
        else (whole, out["sol"])
    np.testing.assert_allclose(np.asarray(sol_p.x), np.asarray(sol_w.x),
                               rtol=0, atol=1e-9)
    np.testing.assert_array_equal(np.asarray(sol_p.iters),
                                  np.asarray(sol_w.iters))
    np.testing.assert_allclose(np.asarray(sol_p.pri_res),
                               np.asarray(sol_w.pri_res), rtol=1e-6,
                               atol=1e-12)
    if want_factors:
        for got, ref in zip(out["sol"][1], whole[1]):
            if ref is None:
                assert got is None
            else:
                np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                           rtol=1e-9, atol=1e-12)


def test_a_wheel_whose_gate_engaged_ends_with_both_bounds(monkeypatch):
    """sslp 5 x 25, S=5, PH + Lagrangian + XhatShuffle with the gate
    engaged from the hub's first step: the wheel ends, both spokes took
    turns, and the bounds bracket as they do without the gate."""
    from tests.test_sslp_reference import spin

    monkeypatch.setattr(turns, "in_order_device", lambda: True)
    monkeypatch.setattr(turns, "ENGAGE_SECS", 0.0)
    # a tiny wheel compiles all through its few iterations
    monkeypatch.setattr(turns, "_clean", lambda _c0: True)
    before = dict(metrics.dump())
    ws = spin("5x25_S5", "float64", iterations=12)
    after = metrics.dump()

    def grew(name):
        return after.get(name, 0.0) - before.get(name, 0.0)

    assert grew("turns.engaged") == 1
    assert grew("turns.hub_secs") > 0 and grew("turns.spoke_secs") > 0
    hub = ws.spcomm
    assert np.isfinite(hub.BestOuterBound) and np.isfinite(hub.BestInnerBound)
    assert hub.BestOuterBound <= hub.BestInnerBound + 1e-6
    assert not ws.spoke_errors and not getattr(ws, "hung_spokes", [])
