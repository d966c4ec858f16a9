"""Multi-controller hub cylinder INSIDE a wheel + write-id acceptance vote.

The reference's headline topology: every cylinder spans many ranks
(spin_the_wheel.py:219-237), with all-ranks-agree write-id votes on both
sides (spoke.py:99-118, hub.py:424-436).  Here the hub cylinder spans TWO
controller processes of one jax.distributed job (scenarios sharded over a
2x4 virtual-CPU-device mesh, consensus psums crossing the process
boundary), spokes attach as separate OS processes over the C++ TCP window
fabric, and every hub-side mailbox read is voted
(parallel/dist_wheel.read_voted).

Covered here:
- the full wheel reaches a certified rel-gap on farmer with BOTH
  controllers reporting identical bounds (determinism contract),
- the mismatched-id retry path of the vote (unit test with injected
  disagreeing reads — live runs only race occasionally).
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
EF_OBJ = -110628.90487928  # farmer 6-scenario EF optimum (HiGHS)


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


# ---------------------------------------------------------------------------
# the vote itself: mismatched-id retry path, deterministically exercised
# ---------------------------------------------------------------------------

class _RacyMailbox:
    """First read returns a payload mid-update (stale id on one controller);
    subsequent reads are consistent."""

    name = "racy"

    def __init__(self):
        self.reads = 0

    def get(self):
        self.reads += 1
        if self.reads == 1:
            return np.array([1.0]), 3     # this controller read id 3 ...
        return np.array([2.0]), 4         # ... re-read sees the final put


def test_read_voted_retries_on_mismatch():
    from tpusppy.parallel.dist_wheel import read_voted

    mb = _RacyMailbox()
    calls = {"n": 0}

    def allgather(wid):
        calls["n"] += 1
        # round 1: the OTHER controller already saw id 4 -> mismatch;
        # round 2: both see 4 -> accept
        return [wid, 4.0]

    data, wid, retries = read_voted(mb, allgather, sleep_s=0.0)
    assert retries == 1 and wid == 4 and data[0] == 2.0 and mb.reads == 2


def test_read_voted_kill_converges():
    from tpusppy.parallel.dist_wheel import read_voted

    class _KilledBox:
        name = "killed"

        def __init__(self):
            self.reads = 0

        def get(self):
            self.reads += 1
            # kill is terminal: every re-read sees -1
            return np.zeros(1), -1

    votes = iter([[-1.0, 7.0], [-1.0, -1.0]])  # laggard catches up
    data, wid, retries = read_voted(_KilledBox(), lambda w: next(votes),
                                    sleep_s=0.0)
    assert wid == -1 and retries == 1


def test_read_voted_gives_up():
    from tpusppy.parallel.dist_wheel import read_voted

    mb = _RacyMailbox()
    with pytest.raises(RuntimeError):
        read_voted(mb, lambda w: [0.0, 1.0], max_tries=3, sleep_s=0.0)


# ---------------------------------------------------------------------------
# tier-1 smoke: 2-controller SPOKELESS hub, deterministic schedule
# ---------------------------------------------------------------------------

def _run_smoke_workers(extra_env, timeout):
    port = _free_port()
    script = os.path.join(REPO, "tests", "dist_wheel_smoke_worker.py")
    common = {
        "DIST_COORD": f"127.0.0.1:{port}",
        "DIST_NPROC": 2,
        # >= global device count so every process owns real scenarios
        "DIST_SCENS": 8,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        **extra_env,
    }
    procs = [
        subprocess.Popen([sys.executable, script],
                         env=_env(common | {"DIST_PID": pid}),
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True)
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            assert p.returncode == 0, f"worker rc={p.returncode}\n{err[-3000:]}"
            outs.append(json.loads(
                [ln for ln in out.splitlines() if ln.startswith("{")][-1]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    r0, r1 = outs
    assert r0["iters"] == r1["iters"] == 3     # the bounded schedule ran
    assert r0["conv"] == r1["conv"]            # identical reduced results
    assert r0["eobj"] == r1["eobj"]
    assert r0["outer"] == r1["outer"]
    assert np.isfinite(r0["conv"]) and np.isfinite(r0["eobj"])
    return r0, r1


def test_two_process_hub_smoke():
    """Fast (<~20 s) tier-1 coverage of the 2-process hub cylinder: the
    cross-process PH collective, the replicated consensus fetch and the
    voted termination decision run a BOUNDED deterministic schedule (tiny
    farmer, 3 iterations, no spokes, no gap target) and both controllers
    must report identical fully-reduced results.  This path found two
    deadlock classes and previously had no routine (non-slow) coverage —
    the full TCP-fabric wheel stays in the slow tier."""
    r0, r1 = _run_smoke_workers({}, timeout=120)
    # shard-local consensus routing (ROADMAP item 1): each controller's
    # device->host consensus traffic is EXACTLY its own row slice —
    # per iteration, (S/nproc) rows of W (K cols) + (S/nproc) rows of x
    # (n cols), never the full replicated (S, K)/(S, n) state.
    from tpusppy.models import farmer

    p0 = farmer.scenario_creator("scen0", num_scens=8)
    n_vars = p0.num_vars
    K = len(p0.nodes[0].nonant_indices)
    rows_pp = 8 // 2                       # S=8 over 2 controllers
    per_iter = rows_pp * (K + n_vars)
    for r in (r0, r1):
        assert r["consensus_doubles"] == r["iters"] * per_iter, \
            (r["consensus_doubles"], r["iters"], per_iter)


def test_two_process_hub_checkpoint_resume(tmp_path):
    """Resilience on the real 2-process mesh (tpusppy.resilience,
    doc/resilience.md): run 1 checkpoints (controller 0 writes the
    snapshots), then — same jax.distributed job, after a barrier — run 2
    RESUMES with a larger budget, exercising the sharded-W restore
    (make_array_from_callback) and the iteration-base continuation.
    Back in tier-1: the PR-5 slow-marking was a full-suite-contention
    coordination-service heartbeat false positive — initialize_backend
    now widens the heartbeat window (TPUSPPY_DIST_HB_* envs) and the
    supervisor's staleness grace is load-adaptive, verified over 20
    consecutive local repetitions."""
    ckdir = str(tmp_path / "dist_ck")
    r0, r1 = _run_smoke_workers({"DIST_CKPT_DIR": ckdir}, timeout=300)
    # the resumed run continued the TOTAL iteration count (3 banked + 2
    # more), identically on both controllers; the artifact is on disk
    from tpusppy.resilience import checkpoint as _ckpt

    assert r0["iters2"] == r1["iters2"] == 5
    assert r0["conv2"] == r1["conv2"]
    assert r0["outer2"] == r1["outer2"]
    ck = _ckpt.load_latest(ckdir)
    assert ck is not None and ck.iteration >= 3


@pytest.mark.slow
def test_two_process_hub_sharded_checkpoint_resume(tmp_path):
    """SHARD-WRITTEN checkpoints on the real 2-process Gloo mesh
    (scenario scale-out, doc/scaling.md): every controller writes ONLY
    its scenario-row shard (sliced from the already-fetched consensus —
    the workers pin checkpoint.capture_fetches == 0 under the D2H
    transfer guard), and the resume leg restores W via the shard-read
    ``make_array_from_callback`` path, each process touching only its
    own shard files.  Results must stay identical across controllers,
    exactly as the single-writer variant."""
    ckdir = str(tmp_path / "dist_ck_sharded")
    r0, r1 = _run_smoke_workers(
        {"DIST_CKPT_DIR": ckdir, "DIST_CKPT_SHARDED": "1"}, timeout=300)
    from tpusppy.resilience import checkpoint as _ckpt

    assert r0["iters2"] == r1["iters2"] == 5
    assert r0["conv2"] == r1["conv2"]
    assert r0["outer2"] == r1["outer2"]
    # zero-extra-fetch pin on BOTH writers
    assert r0["capture_fetches"] == 0 and r1["capture_fetches"] == 0
    assert r0["captures"] >= 1 and r1["captures"] >= 1
    # the artifact really is a complete per-shard set: both shard files
    # exist, and the assembled view matches the full (S, K) state shape
    p = _ckpt.latest(ckdir)
    assert p is not None and ".s000of002.npz" in p
    parts = _ckpt.shard_set_paths(p)
    assert len(parts) == 2
    ck = _ckpt.load_latest(ckdir)
    assert ck is not None and ck.iteration >= 3
    assert ck.W is not None and ck.W.shape[0] == 8


# ---------------------------------------------------------------------------
# elastic re-shard parity on REAL meshes: checkpoint on 3 controllers,
# restore onto 2 (doc/resilience.md "Elastic recovery")
# ---------------------------------------------------------------------------

def _run_single_leg(nproc, extra_env, timeout, devices_per_proc=1):
    port = _free_port()
    script = os.path.join(REPO, "tests", "dist_wheel_smoke_worker.py")
    common = {
        "DIST_COORD": f"127.0.0.1:{port}",
        "DIST_NPROC": nproc,
        "DIST_SCENS": 7,
        "DIST_SINGLE_LEG": 1,
        "XLA_FLAGS":
            f"--xla_force_host_platform_device_count={devices_per_proc}",
        **extra_env,
    }
    procs = [
        subprocess.Popen([sys.executable, script],
                         env=_env(common | {"DIST_PID": pid}),
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True)
        for pid in range(nproc)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            assert p.returncode == 0, \
                f"worker rc={p.returncode}\n{err[-3000:]}"
            outs.append(json.loads(
                [ln for ln in out.splitlines() if ln.startswith("{")][-1]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return outs


@pytest.mark.slow
def test_elastic_reshard_parity_3_to_2_controllers(tmp_path):
    """The satellite contract end to end on REAL meshes: an S=7 wheel
    checkpointed (shard-per-process) on a 3-controller Gloo mesh is
    restored onto a SURVIVING 2-controller mesh — different process
    count, different device count, different ghost padding — and its
    post-resume trajectory must match an uninterrupted single-process
    golden at 1e-9, bit-identically across the two survivors."""
    from tpusppy.models import farmer
    from tpusppy.parallel.dist_wheel import distributed_wheel_hub
    from tpusppy.resilience import checkpoint as _ckpt

    ckdir = str(tmp_path / "elastic_ck")
    # leg 1: 3 controllers bank sharded snapshots for iterations 1..3
    outs3 = _run_single_leg(3, {"DIST_CKPT_DIR": ckdir, "DIST_ITERS": 3},
                            timeout=300)
    assert all(o["iters"] == 3 for o in outs3)
    p = _ckpt.latest(ckdir)
    assert p is not None and ".s000of003.npz" in p
    # leg 2: the two SURVIVORS resume onto their smaller mesh (rows
    # re-cut by the row-range reader: the old 3-shard layout never
    # matches the new per-process rows)
    outs2 = _run_single_leg(2, {"DIST_CKPT_DIR": ckdir, "DIST_ITERS": 5,
                                "DIST_RESUME": "1"}, timeout=300)
    r0, r1 = outs2
    assert r0["iters"] == r1["iters"] == 5
    assert r0["trajectory"] == r1["trajectory"]   # determinism contract
    assert r0["elastic_restores"] == 1 and r1["elastic_restores"] == 1
    assert [t[0] for t in r0["trajectory"]] == [4, 5]

    # golden: uninterrupted single-process wheel, same math
    golden = distributed_wheel_hub(
        farmer.scenario_names_creator(7), farmer.scenario_creator,
        scenario_creator_kwargs={"num_scens": 7},
        options={"defaultPHrho": 1.0, "PHIterLimit": 5,
                 "record_trajectory": True, "linger_secs": 0.0,
                 "solver_options": {"dtype": "float64", "eps_abs": 1e-12,
                                    "eps_rel": 1e-12, "max_iter": 8000,
                                    "restarts": 3, "scaling_iters": 2,
                                    "polish": False}},
        fabric=None, spoke_roles=[])
    tail = {t[0]: t for t in golden.trajectory[3:]}
    for it, conv, eobj in r0["trajectory"]:
        _g_it, g_conv, g_eobj = tail[it]
        assert conv == pytest.approx(g_conv, rel=1e-9, abs=5e-9)
        assert eobj == pytest.approx(g_eobj, rel=1e-9)


# ---------------------------------------------------------------------------
# the full topology: 2-controller hub + 2 spoke processes, certified gap
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_two_controller_hub_wheel_certifies():
    """POST-MORTEM (the PR-12 fix; this test aborted deterministically
    before it): the consensus fetch used to be two back-to-back
    separately-jitted single-collective programs — replicate(W) then
    replicate(x).  Separately lowered single-collective programs get the
    SAME collective channel id, and XLA:CPU's Gloo adapter derives its
    op slots from the channel — so when one controller lagged inside the
    W all-gather while its peer (having finished W locally) dispatched
    the x all-gather, the peer's x payload (4 local rows x 11 vars = 44
    doubles) landed against the W gather's posted 12-double (4 x K=3)
    receive and Gloo aborted the whole job: "op.preamble.length <=
    op.nbytes. 44 vs 12".  The abort needed receiver-side lag, so it
    fired only in the busiest posture (2 controllers x 4 devices + live
    TCP spokes + bound traffic) and always a few iterations in.  Fix:
    ONE fused gather per fetch (shard-local row blocks concatenated into
    a single host vector, one process_allgather) — no same-channel
    adjacent programs left in the loop.  This test is the regression
    gate; the fetch-size pin lives in test_two_process_hub_smoke."""
    coord_port, fabric_port = _free_port(), _free_port()
    secret = 0x5EC0DE5EC0DE
    ready = os.path.join(tempfile.gettempdir(),
                         f"distwheel_ready_{os.getpid()}")
    if os.path.exists(ready):
        os.remove(ready)

    common = {
        "DIST_COORD": f"127.0.0.1:{coord_port}",
        "DIST_NPROC": 2,
        "DIST_SCENS": SCENS,
        "FABRIC_PORT": fabric_port,
        "FABRIC_SECRET": secret,
        "FABRIC_READY": ready,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    }
    hub_script = os.path.join(REPO, "tests", "dist_wheel_worker.py")
    hubs = [
        subprocess.Popen([sys.executable, hub_script],
                         env=_env(common | {"DIST_PID": pid}),
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True)
        for pid in range(2)
    ]
    spokes = []
    try:
        # spawn spokes once the box server is up (readiness sentinel)
        t0 = time.time()
        while not os.path.exists(ready):
            assert time.time() - t0 < 120, "fabric server never came up"
            assert all(h.poll() is None for h in hubs), \
                [h.communicate() for h in hubs if h.poll() is not None]
            time.sleep(0.2)
        os.remove(ready)
        spoke_script = os.path.join(REPO, "tests", "dist_wheel_spoke.py")
        spoke_env = {k: v for k, v in common.items()
                     if k not in ("XLA_FLAGS",)}
        for rank, kind in ((1, "lagrangian"), (2, "xhatxbar")):
            spokes.append(subprocess.Popen(
                [sys.executable, spoke_script],
                env=_env(spoke_env | {"SPOKE_RANK": rank,
                                      "SPOKE_KIND": kind}),
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))

        outs = []
        for h in hubs:
            out, err = h.communicate(timeout=900)
            assert h.returncode == 0, f"hub rc={h.returncode}\n{err[-4000:]}"
            outs.append(json.loads(
                [ln for ln in out.splitlines() if ln.startswith("{")][-1]))
    finally:
        for p in hubs + spokes:
            if p.poll() is None:
                p.kill()

    r0, r1 = sorted(outs, key=lambda r: r["pid"])
    # determinism contract: both controllers saw identical voted bounds
    assert r0["inner"] == r1["inner"]
    assert r0["outer"] == r1["outer"]
    assert r0["iters"] == r1["iters"]
    # certified: finite bounds from BOTH spoke kinds, gap at target
    assert np.isfinite(r0["inner"]) and np.isfinite(r0["outer"])
    assert r0["rel_gap"] <= 1e-3
    # bounds bracket the EF optimum (farmer is minimizing)
    assert r0["outer"] <= r0["inner"] + 1e-6
    assert r0["outer"] <= EF_OBJ + 1.0
    assert r0["inner"] >= EF_OBJ - 1.0
