#!/usr/bin/env python
"""Chaos smoke: SIGKILL one of three wheel CONTROLLERS mid-run; the
survivors must detect, re-mesh, resume from the sharded checkpoints, and
still certify — never hang.

The nightly acceptance for elastic mesh recovery
(tpusppy/parallel/elastic.py, doc/resilience.md "Elastic recovery"),
runnable locally::

    JAX_PLATFORMS=cpu python scripts/chaos_smoke.py

Topology per leg: a 3-controller CPU Gloo hub cylinder (scenarios
sharded across the processes) + 2 spoke processes (Lagrangian outer,
XhatXbar inner) attached over the TCP window fabric.  The fabric boxes
are served by THIS parent process — off-controller, so spoke state
survives controller re-exec (the production posture for an elastic
wheel; a controller-served fabric works too but rides the reconnect
path).

1. **golden** — uninterrupted run to a certified ``rel_gap <= 1e-3``;
   its final gap is the bar.
2. **chaos** — same wheel, per-iteration SHARDED checkpoints; once >= 2
   complete 3-shard sets exist the parent SIGKILLs controller rank 1 (a
   real, uncatchable kill).  Both survivors must turn the next hung/
   failed collective into ControllerLost within ``TPUSPPY_MESH_TIMEOUT``,
   agree on the survivor set over the liveness side-channel, re-exec
   onto a fresh 2-controller mesh (epoch 1), restore the wheel via
   row-range shard reads, and certify a gap no worse than the golden's —
   with the whole recovery visible in the final processes' obs counters
   (``mesh.controller_lost`` / ``mesh.remesh`` /
   ``checkpoint.elastic_restores``) and bounds monotone w.r.t. the
   checkpoint they resumed from.

Known NON-survivable cases (typed errors, documented in
doc/resilience.md): loss of a majority of the original controllers, and
loss of the epoch's rank-min CONTROLLER (the jax coordination service
lives there; its client terminates peers on coordinator transport
failure) — which is why the victim here is rank 1.

The whole script is bounded by a HARD watchdog (``CHAOS_DEADLINE_SECS``,
default 1500): a regression that hangs fails loudly instead of pinning
CI.  Worker legs are this same file with ``--controller`` / ``--spoke``.
Exit code 0 = pass.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCENS = int(os.environ.get("CHAOS_SCENS", "6"))
K = 3                       # farmer root nonants (crops)
MESH_TIMEOUT = float(os.environ.get("TPUSPPY_MESH_TIMEOUT", "20"))
DEADLINE = float(os.environ.get("CHAOS_DEADLINE_SECS", "1800"))
GAP = float(os.environ.get("CHAOS_GAP", "1e-3"))
# bound-harvest budget after the PH loop: 7 concurrent jax processes on
# one CI box make spoke rounds slow — the gap target needs wall time,
# not more hub iterations
HARVEST = float(os.environ.get("CHAOS_HARVEST_SECS", "420"))


def log(msg):
    print(f"chaos-smoke: {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Controller leg (child process; re-execs itself on re-mesh)
# ---------------------------------------------------------------------------
def controller():
    sys.path.insert(0, REPO)
    from tpusppy.models import farmer
    from tpusppy.obs import metrics
    from tpusppy.parallel import elastic
    from tpusppy.runtime.tcp_window_service import TcpWindowFabric

    spec = elastic.ElasticSpec(
        rank=int(os.environ["CHAOS_RANK"]),
        n_original=int(os.environ["CHAOS_N"]),
        checkpoint_dir=os.environ["CHAOS_CKPT_DIR"],
        coord_port_base=int(os.environ["CHAOS_COORD_BASE"]),
        liveness_port_base=int(os.environ["CHAOS_LIVENESS_BASE"]),
        secret=int(os.environ["CHAOS_SECRET"]),
        mesh_timeout_secs=MESH_TIMEOUT)

    def fabric_factory(spec):
        # every controller is a CLIENT of the parent-served box fabric
        return TcpWindowFabric(
            connect=("127.0.0.1", int(os.environ["CHAOS_FABRIC_PORT"])),
            secret=int(os.environ["CHAOS_FABRIC_SECRET"]))

    options = {
        "defaultPHrho": 1.0, "PHIterLimit": 200,
        "rel_gap": GAP, "linger_secs": 8.0, "harvest_secs": HARVEST,
        "checkpoint_every_iters": 1, "checkpoint_every_secs": None,
        "solver_options": {"dtype": "float64", "eps_abs": 1e-8,
                           "eps_rel": 1e-8, "max_iter": 300,
                           "restarts": 3}}
    res = elastic.elastic_wheel_hub(
        spec, farmer.scenario_names_creator(SCENS),
        farmer.scenario_creator,
        scenario_creator_kwargs={"num_scens": SCENS},
        options=options, fabric_factory=fabric_factory,
        spoke_roles=[{"bound": "outer", "wants": "W"},
                     {"bound": "inner", "wants": "nonants"}])
    print(json.dumps({
        "rank": spec.rank,
        "epoch": int(os.environ.get(elastic.ENV_EPOCH, "0")),
        "detect_secs": float(os.environ.get(elastic.ENV_DETECT_SECS, "0")),
        "inner": res.BestInnerBound, "outer": res.BestOuterBound,
        "rel_gap": res.rel_gap, "iters": res.iters,
        "controller_lost": metrics.value("mesh.controller_lost"),
        "remesh": metrics.value("mesh.remesh"),
        "elastic_restores": metrics.value("checkpoint.elastic_restores"),
    }), flush=True)


# ---------------------------------------------------------------------------
# Spoke leg (child process; attached to the PARENT's fabric — must ride
# straight through the controller outage)
# ---------------------------------------------------------------------------
def spoke():
    sys.path.insert(0, REPO)
    from tpusppy.models import farmer
    from tpusppy.spin_the_wheel import _spoke_worker

    rank = int(os.environ["SPOKE_RANK"])
    if os.environ["SPOKE_KIND"] == "lagrangian":
        from tpusppy.cylinders import LagrangianOuterBound
        from tpusppy.phbase import PHBase

        spoke_class, opt_class = LagrangianOuterBound, PHBase
    else:
        from tpusppy.cylinders import XhatXbarInnerBound
        from tpusppy.xhat_eval import Xhat_Eval

        spoke_class, opt_class = XhatXbarInnerBound, Xhat_Eval
    sd = {
        "spoke_class": spoke_class, "opt_class": opt_class,
        "opt_kwargs": {
            "options": {"defaultPHrho": 1.0, "PHIterLimit": 300,
                        "convthresh": -1.0,
                        "solver_options": {"dtype": "float64",
                                           "eps_abs": 1e-8,
                                           "eps_rel": 1e-8,
                                           "max_iter": 300,
                                           "restarts": 3}},
            "all_scenario_names": farmer.scenario_names_creator(SCENS),
            "scenario_creator": farmer.scenario_creator,
            "scenario_creator_kwargs": {"num_scens": SCENS},
        },
    }
    _spoke_worker(
        ("tcp", "127.0.0.1", int(os.environ["CHAOS_FABRIC_PORT"]),
         f"chaos{os.getpid()}_{rank}",
         int(os.environ["CHAOS_FABRIC_SECRET"])),
        sd, rank)


# ---------------------------------------------------------------------------
# Orchestration (parent: serves the fabric, runs both legs, hard watchdog)
# ---------------------------------------------------------------------------
def _arm_hard_watchdog(procs_box):
    """A regression must FAIL CI, not hang it: past the deadline, kill
    every child and the parent itself."""
    def fire():
        time.sleep(DEADLINE)
        log(f"HARD WATCHDOG: {DEADLINE}s deadline breached — killing "
            "everything")
        for p in procs_box:
            try:
                p.kill()
            except Exception:
                pass
        os._exit(2)

    t = threading.Thread(target=fire, daemon=True)
    t.start()


def _env_for(role_env):
    from tpusppy.solvers import aot

    env = dict(os.environ)
    env.update({
        "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
        "JAX_ENABLE_X64": "1",
        "TPUSPPY_MESH_TIMEOUT": str(MESH_TIMEOUT),
        "JAX_COMPILATION_CACHE_DIR": aot.compile_cache_dir(),
    })
    env.update({k: str(v) for k, v in role_env.items()})
    return env


def _run_leg(tag, ckdir, procs_box, kill_rank=None):
    from tpusppy.resilience import checkpoint as _ckpt
    from tpusppy.runtime.tcp_window_service import TcpWindowFabric

    from tpusppy.parallel.elastic import free_port_block

    n_ctl = 3
    lengths = [(SCENS * K + 2, 1), (SCENS * K + 2, 1)]
    fabric = TcpWindowFabric(spoke_lengths=lengths)
    common = {
        "CHAOS_N": n_ctl, "CHAOS_CKPT_DIR": ckdir,
        # whole CONSECUTIVE blocks reserved: coordinators use base+epoch,
        # liveness servers base+rank — a single free port only vouches
        # for the base
        "CHAOS_COORD_BASE": free_port_block(n_ctl),
        "CHAOS_LIVENESS_BASE": free_port_block(n_ctl),
        "CHAOS_SECRET": 0x5EC0DE + os.getpid(),
        "CHAOS_FABRIC_PORT": fabric.port,
        "CHAOS_FABRIC_SECRET": fabric.secret,
        "CHAOS_SCENS": SCENS,
        # one virtual device per controller: 3-way sharded epoch 0,
        # 2-way (uneven, ghost-padded) epoch 1
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    }
    me = os.path.abspath(__file__)
    ctls = [subprocess.Popen(
        [sys.executable, me, "--controller"],
        env=_env_for(common | {"CHAOS_RANK": r}),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for r in range(n_ctl)]
    spoke_env = {k: v for k, v in common.items() if k != "XLA_FLAGS"}
    spokes = [subprocess.Popen(
        [sys.executable, me, "--spoke"],
        env=_env_for(spoke_env | {"SPOKE_RANK": r, "SPOKE_KIND": kind}),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for r, kind in ((1, "lagrangian"), (2, "xhatxbar"))]
    procs_box.extend(ctls + spokes)

    killed_at = None
    if kill_rank is not None:
        # wait for >= 2 COMPLETE sharded sets, then the real kill
        t0 = time.time()
        while True:
            sets = [p for _it, p in _ckpt.list_checkpoints(ckdir)
                    if f".s000of{n_ctl:03d}.npz" in p]
            if len(sets) >= 2:
                break
            dead = [i for i, c in enumerate(ctls)
                    if c.poll() is not None]
            assert not dead, \
                f"controller(s) {dead} exited before the kill: " \
                + str([ctls[i].communicate()[1][-2000:] for i in dead])
            assert time.time() - t0 < 900, \
                "no sharded snapshots within 900s"
            time.sleep(0.25)
        killed_at = _ckpt.load_latest(ckdir)
        os.kill(ctls[kill_rank].pid, signal.SIGKILL)
        log(f"{tag}: SIGKILLed controller rank {kill_rank} at "
            f"checkpoint iteration {killed_at.iteration} "
            f"(outer={killed_at.best_outer:.2f} "
            f"inner={killed_at.best_inner:.2f})")

    outs = {}
    raw = {}
    for r, c in enumerate(ctls):
        if kill_rank is not None and r == kill_rank:
            c.wait(timeout=60)
            continue
        try:
            raw[r] = c.communicate(timeout=DEADLINE)
        except subprocess.TimeoutExpired:
            c.kill()
            raw[r] = c.communicate()
    # post-mortem trail for EVERY controller before any verdict: the
    # interesting failures are cross-process timing, and asserting on
    # the first bad controller would discard its peer's evidence
    for r, (out, err) in raw.items():
        with open(os.path.join(ckdir, f"controller_{r}.stderr"),
                  "w") as f:
            f.write(err)
    for r, (out, err) in raw.items():
        assert ctls[r].returncode == 0, \
            f"{tag}: controller {r} rc={ctls[r].returncode}\n{err[-4000:]}"
        outs[r] = json.loads(
            [ln for ln in out.splitlines() if ln.startswith("{")][-1])
    for sp in spokes:
        try:
            sp.wait(timeout=120)
        except subprocess.TimeoutExpired:
            sp.kill()                       # bounded teardown, not a fail
    fabric.close()
    return outs, killed_at


def main():
    import tempfile

    sys.path.insert(0, REPO)
    procs_box = []
    _arm_hard_watchdog(procs_box)
    base = tempfile.mkdtemp(prefix="chaos_smoke_")
    log(f"workdir {base} (mesh timeout {MESH_TIMEOUT}s)")

    t0 = time.time()
    golden, _ = _run_leg("golden", os.path.join(base, "golden_ck"),
                         procs_box)
    g_gap = golden[0]["rel_gap"]
    log(f"golden rel_gap={g_gap:.3e} in {time.time() - t0:.0f}s")
    assert g_gap <= GAP + 1e-12, "golden run did not certify"
    assert all(o["epoch"] == 0 for o in golden.values())

    t1 = time.time()
    chaos, killed_at = _run_leg("chaos", os.path.join(base, "chaos_ck"),
                                procs_box, kill_rank=1)
    log(f"chaos leg done in {time.time() - t1:.0f}s")
    r0, r2 = chaos[0], chaos[2]

    # survivors re-meshed exactly once and agree bit-for-bit
    assert r0["epoch"] == 1 and r2["epoch"] == 1, (r0, r2)
    assert r0["inner"] == r2["inner"] and r0["outer"] == r2["outer"]
    # detection within the mesh timeout (+ first-poll slack), never a hang
    for r in (r0, r2):
        assert 0 < r["detect_secs"] <= MESH_TIMEOUT + 10.0, r
    # the whole recovery is visible in the FINAL processes' registries
    for r in (r0, r2):
        assert r["controller_lost"] >= 1, r
        assert r["remesh"] >= 1, r
        assert r["elastic_restores"] >= 1, r
    # bounds monotone w.r.t. the snapshot the survivors resumed from
    assert r0["outer"] >= killed_at.best_outer - 1e-9, \
        (r0["outer"], killed_at.best_outer)
    assert r0["inner"] <= killed_at.best_inner + 1e-9, \
        (r0["inner"], killed_at.best_inner)
    # certified no worse than the uninterrupted golden
    assert r0["rel_gap"] <= max(g_gap, GAP) + 1e-9, \
        f"post-recovery gap {r0['rel_gap']} worse than golden {g_gap}"
    log(f"recovered: detect {r0['detect_secs']:.1f}s + "
        f"{r2['detect_secs']:.1f}s, epoch-1 gap {r0['rel_gap']:.3e} "
        f"(golden {g_gap:.3e})")
    log("PASS")


if __name__ == "__main__":
    if "--controller" in sys.argv[1:]:
        controller()
    elif "--spoke" in sys.argv[1:]:
        spoke()
    else:
        main()
