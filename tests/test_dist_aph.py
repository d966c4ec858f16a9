"""Distributed APH: cross-host listener reductions (parallel/dist_aph.py).

The reference overlaps MPI Allreduces with solves on a listener thread
(mpisppy/opt/aph.py:198-330 + listener_util.py:277-327).  Here two OS
processes each run batched APH on half the farmer scenarios; their node
averages are reduced across processes by APHPartialSync's listener threads
over the C++ TCP window service — the DCN path — while workers solve.
Asserted: both processes converge to ONE consensus (identical root xbar),
and the consensus policy — priced EXACTLY per scenario with the first
stage fixed — lands within 1% of the EF optimum.
"""

import json
import os
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCENS = 6


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _env(extra):
    env = dict(os.environ)     # carries conftest's compile-cache dir
    env.update({
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": "cpu",
        "JAX_ENABLE_X64": "1",
    })
    env.update({k: str(v) for k, v in extra.items()})
    return env


@pytest.mark.slow
def test_two_process_aph_cross_host_reductions():
    port = _free_port()
    secret = 0xA9B8C7D6
    ready = os.path.join(tempfile.gettempdir(),
                         f"distaph_ready_{os.getpid()}")
    if os.path.exists(ready):
        os.remove(ready)
    common = {
        "DIST_NPROC": 2, "DIST_SCENS": SCENS,
        "FABRIC_PORT": port, "FABRIC_SECRET": secret,
        "FABRIC_READY": ready, "DIST_DISPATCH": 0.67,
    }
    script = os.path.join(REPO, "tests", "dist_aph_worker.py")
    p0 = subprocess.Popen([sys.executable, script],
                          env=_env(common | {"DIST_PID": 0}),
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True)
    procs = [p0]
    try:
        t0 = time.time()
        while not os.path.exists(ready):
            assert time.time() - t0 < 120, "sync server never came up"
            assert p0.poll() is None, p0.communicate()
            time.sleep(0.2)
        os.remove(ready)
        procs.append(subprocess.Popen(
            [sys.executable, script], env=_env(common | {"DIST_PID": 1}),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
        outs = []
        for p in procs:
            out, err = p.communicate(timeout=600)
            assert p.returncode == 0, f"rc={p.returncode}\n{err[-4000:]}"
            outs.append(json.loads(
                [ln for ln in out.splitlines() if ln.startswith("{")][-1]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    r0, r1 = sorted(outs, key=lambda r: r["pid"])
    # one consensus: the root xbar derives from the same global sums
    np.testing.assert_allclose(r0["xbar_root"], r1["xbar_root"],
                               rtol=1e-6, atol=1e-8)
    # the CONSENSUS POLICY is the deterministic certificate: fix the
    # first stage to the agreed xbar and price it exactly per scenario —
    # the result must land within 1% of the EF optimum.  (Eobjective over
    # per-scenario stale x is NOT anchored to EF: nonants still differ
    # across scenarios mid-asynchrony.)
    EF_OBJ = -110628.90487928  # farmer 6-scenario EF optimum (HiGHS)
    from tpusppy.ir import ScenarioBatch
    from tpusppy.models import farmer
    from tpusppy.solvers import scipy_backend

    b = ScenarioBatch.from_problems([
        farmer.scenario_creator(nm, num_scens=SCENS)
        for nm in farmer.scenario_names_creator(SCENS)])
    nid = b.tree.nonant_indices
    xbar = np.asarray(r0["xbar_root"], float)
    # mid-convergence xbar can overshoot the 500-acre row by a hair;
    # project (exactly what an xhat evaluator's repair would do)
    if xbar.sum() > 500.0:
        xbar = xbar * (500.0 / xbar.sum())
    lb = b.lb.copy()
    ub = b.ub.copy()
    lb[:, nid] = xbar[None, :]
    ub[:, nid] = xbar[None, :]
    vals = []
    for s in range(SCENS):
        res = scipy_backend.solve_lp(
            b.c[s], b.A[s], b.cl[s], b.cu[s], lb[s], ub[s])
        assert res.feasible
        vals.append(float(b.c[s] @ res.x))
    policy_obj = float(b.tree.scen_prob @ np.asarray(vals))
    assert policy_obj == pytest.approx(EF_OBJ, rel=1e-2)
    # NOTE: no trajectory-level xbar comparison against a single-process
    # APH run — farmer's optimum sits in a near-flat valley and genuine
    # asynchrony legitimately lands different runs on different
    # near-optimal points; the exact policy pricing above IS the
    # asynchrony-proof certificate.
