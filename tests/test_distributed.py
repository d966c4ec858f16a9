"""Multi-controller (2-process) scenario parallelism within one cylinder.

The reference scales ONE cylinder across MPI ranks with rank-local scenario
lists and per-node Allreduce (sputils.py:774-840, spbase.py:184-216).  Here
two OS processes each own half the farmer scenarios, join one
``jax.distributed`` job over 2x4 virtual CPU devices, and run the SAME
jitted PH step as the single-controller path — consensus reductions cross
the process boundary as XLA collectives.  Parity is asserted against the
host PH on the full family.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCENS = 6


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env(pid, nproc, port):
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "DIST_COORD": f"127.0.0.1:{port}",
        "DIST_NPROC": str(nproc),
        "DIST_PID": str(pid),
        "DIST_SCENS": str(SCENS),
    })
    return env


@pytest.mark.slow
def test_two_process_distributed_ph_matches_host_ph():
    port = _free_port()
    script = os.path.join(REPO, "tests", "dist_ph_worker.py")
    procs = [
        subprocess.Popen([sys.executable, script],
                         env=_worker_env(pid, 2, port),
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True)
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        outs.append(json.loads(out.strip().splitlines()[-1]))

    # both processes report the identical, fully-reduced result
    assert outs[0]["iters"] == outs[1]["iters"]
    assert outs[0]["conv"] == pytest.approx(outs[1]["conv"], rel=1e-9)
    assert outs[0]["eobj"] == pytest.approx(outs[1]["eobj"], rel=1e-9)
    np.testing.assert_allclose(outs[0]["xbars"], outs[1]["xbars"],
                               rtol=1e-9)

    # convergence parity vs the EF optimum — the same contract the
    # single-controller mesh path pins (test_sharded_matches_host_ph):
    # per-iteration trajectories differ legitimately between the class API
    # and the functional sharded step, the fixed point must not
    from tpusppy.ef import solve_ef
    from tpusppy.ir import ScenarioBatch
    from tpusppy.models import farmer

    names = farmer.scenario_names_creator(SCENS)
    batch = ScenarioBatch.from_problems(
        [farmer.scenario_creator(nm, num_scens=SCENS) for nm in names])
    ef_obj, ef_x = solve_ef(batch, solver="highs")
    assert outs[0]["conv"] < 0.5   # absolute L1 on O(100)-acre values
    assert outs[0]["eobj"] == pytest.approx(ef_obj, rel=2e-3)
    nid = batch.tree.nonant_indices
    np.testing.assert_allclose(np.asarray(outs[0]["xbars"]),
                               np.asarray(ef_x)[0, nid], rtol=0.02)


def test_scen_to_process_partition():
    from tpusppy.parallel.distributed import scen_to_process

    assert scen_to_process(10, 4) == [(0, 3), (3, 6), (6, 8), (8, 10)]
    assert scen_to_process(10, 4, 1) == (3, 6)
    slices = scen_to_process(4000, 256)
    assert slices[0][0] == 0 and slices[-1][1] == 4000
    sizes = {hi - lo for lo, hi in slices}
    assert sizes <= {15, 16}
