"""Run ALL example drivers end-to-end; assert objectives, not just rc==0.

The analogue of the reference's ``examples/run_all.py`` (the de-facto
regression harness per examples/AAAReadme.txt / SURVEY §4) — EXCEEDING it
on the axis SURVEY §4 flags as its liability ("exit code 0 only"): wheel
drivers write a ``TPUSPPY_RESULT_JSON`` sidecar ({inner, outer, rel_gap})
and runs with an ``expect`` entry are asserted against golden objectives
and certified-gap ceilings, so a 1%-level objective regression fails the
harness.  Usage::

    python run_all.py            # everything
    python run_all.py nouc       # skip the UC family (reference flag parity)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

EXDIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, EXDIR)
from _harness_env import child_env  # noqa: E402

# ``expect`` semantics (all optional):
#   obj: golden EF objective — the sidecar INNER bound must match within
#        rel (incumbent at/above the optimum, within the driver's gap)
#   rel: relative tolerance for obj (default 1e-2)
#   gap: ceiling on the certified rel_gap (inner vs outer)
RUNS = [
    ("farmer/farmer_ef.py",
     ["--num-scens", "3", "--EF-solver-name", "admm"], None),
    ("farmer/farmer_ef.py",
     ["--num-scens", "3", "--EF-solver-name", "highs"], None),
    ("farmer/farmer_cylinders.py",
     ["--num-scens", "3", "--max-iterations", "20", "--default-rho", "1.0",
      "--rel-gap", "0.01", "--lagrangian", "--xhatshuffle"],
     {"obj": -108390.0, "rel": 1e-2, "gap": 0.02}),
    ("farmer/farmer_cylinders.py",
     ["--num-scens", "3", "--max-iterations", "10", "--default-rho", "1.0",
      "--rel-gap", "0.02", "--fwph", "--lagranger", "--xhatlooper"],
     {"obj": -108390.0, "rel": 1e-2, "gap": 0.05}),
    ("sizes/sizes_cylinders.py",
     ["--num-scens", "3", "--max-iterations", "30", "--default-rho", "0.01",
      "--rel-gap", "0.05", "--lagrangian", "--xhatshuffle"],
     {"obj": 219842.875, "rel": 2e-2, "gap": 0.10}),
    ("sslp/sslp_cylinders.py",
     # NEUTRAL rho: the driver's default adaptive-rho posture
     # (NormRhoUpdater, on unless --no-adaptive-rho) replaces the
     # hand-tuned rho=100 this entry used to need — with a static rho,
     # 5.0 parked the incumbent 16% off optimum (gap 26%).  Adaptation
     # needs runway: rho doubles per firing iteration, so 200 hub
     # iterations replace 40 (measured from rho=5: gap 4.2-4.8% by 200
     # even on a loaded host; 120 leaves 5.9-7.3% under load — the async
     # spokes' progress per hub iteration is machine-dependent).
     ["--num-scens", "4", "--max-iterations", "200", "--default-rho", "5.0",
      "--rel-gap", "0.02", "--lagrangian", "--xhatshuffle"],
     {"obj": -24.0285, "rel": 2e-2, "gap": 0.05}),
    ("netdes/netdes_cylinders.py",
     ["--num-scens", "3", "--max-iterations", "20", "--default-rho", "1.0",
      "--rel-gap", "0.05", "--lagrangian", "--xhatshuffle"],
     {"obj": 376.3056, "rel": 2e-2, "gap": 0.10}),
    ("netdes/netdes_cylinders.py",
     ["--num-scens", "3", "--max-iterations", "12", "--default-rho", "1.0",
      "--rel-gap", "0.05", "--cross-scenario-cuts", "--xhatshuffle"],
     {"obj": 376.3056, "rel": 2e-2}),
    # the batched integer wheel (doc/integer.md): the TRUE integer
    # instance, hub-only — in-wheel bounds + rounding sweep + gap-ranked
    # MILP escalation must certify strictly inside the family's ~5.5%
    # EF integrality gap (golden MIP objective 398.333; no spokes)
    ("netdes/netdes_cylinders.py",
     ["--num-scens", "3", "--max-iterations", "60", "--default-rho", "1.0",
      "--rel-gap", "0.04", "--integer"],
     {"obj": 398.3333, "rel": 2e-2, "gap": 0.04}),
    ("hydro/hydro_pysp.py", [], None),
    ("hydro/hydro_cylinders.py",
     ["--branching-factors", "3 3", "--max-iterations", "20",
      "--default-rho", "1.0", "--rel-gap", "0.02", "--lagrangian",
      "--xhatshuffle"],
     {"obj": 186.1739, "rel": 1e-2, "gap": 0.05}),
    ("aircond/aircond_cylinders.py",
     ["--branching-factors", "3 2", "--max-iterations", "10",
      "--default-rho", "1.0", "--rel-gap", "0.05", "--lagrangian",
      "--xhatshuffle"], None),
    ("uc/uc_cylinders.py",
     ["--num-scens", "4", "--uc-num-gens", "3", "--uc-horizon", "6",
      "--max-iterations", "20", "--default-rho", "50.0",
      "--rel-gap", "0.02", "--lagrangian", "--xhatshuffle"], None),
    ("battery/battery_cylinders.py",
     ["--num-scens", "6", "--battery-lam", "0.1", "--battery-use-lp",
      "--max-iterations", "8", "--default-rho", "0.5",
      "--rel-gap", "0.02", "--lagrangian", "--xhatshuffle"], None),
    ("acopf3/ccopf_cylinders.py",
     ["--branching-factors", "2 2", "--max-iterations", "20",
      "--default-rho", "0.1", "--rel-gap", "0.01", "--lagrangian",
      "--xhatshuffle"], None),
    ("usar/usar_ef.py",
     ["--num-scens", "3", "--output-dir", "/tmp/tpusppy_usar_out"], None),
    ("usar/usar_cylinders.py",
     ["--num-scens", "3", "--max-iterations", "20", "--default-rho", "1.0",
      "--rel-gap", "0.05", "--lagrangian", "--xhatrestrictedef",
      "--xhat-ef-every", "1", "--output-dir", "/tmp/tpusppy_usar_out"],
     {"gap": 0.05}),
]


def check_expect(expect, sidecar_path):
    """Returns a failure string or None."""
    if expect is None:
        return None
    if not os.path.exists(sidecar_path):
        return "no result sidecar written"
    with open(sidecar_path) as f:
        res = json.load(f)
    inner, gap = res.get("inner"), res.get("rel_gap")
    if "obj" in expect:
        rel = expect.get("rel", 1e-2)
        if not (abs(inner - expect["obj"])
                <= rel * max(1.0, abs(expect["obj"]))):
            return (f"inner bound {inner:.4f} off golden "
                    f"{expect['obj']:.4f} (rel tol {rel})")
    if "gap" in expect and not (gap <= expect["gap"]):
        return f"certified rel_gap {gap:.4f} > ceiling {expect['gap']}"
    return None


def main():
    skip_uc = "nouc" in sys.argv[1:]
    badguys = []
    for script, args, expect in RUNS:
        if skip_uc and script.startswith("uc/"):
            continue
        path = os.path.join(EXDIR, script)
        cmd = [sys.executable, path] + args
        print("==>", " ".join(cmd), flush=True)
        # child env: repo root on PYTHONPATH, cpu pinned
        # (EXAMPLES_KEEP_ENV=1 opts out)
        env = child_env(os.path.dirname(EXDIR))
        sidecar = os.path.join(
            tempfile.gettempdir(),
            f"tpusppy_runall_{os.getpid()}_{script.replace('/', '_')}.json")
        if os.path.exists(sidecar):
            os.remove(sidecar)
        env["TPUSPPY_RESULT_JSON"] = sidecar
        res = subprocess.run(cmd, cwd=os.path.dirname(path), env=env)
        why = (f"rc={res.returncode}" if res.returncode != 0
               else check_expect(expect, sidecar))
        if why:
            badguys.append(f"{script} {' '.join(args)}: {why}")
        if os.path.exists(sidecar):
            os.remove(sidecar)
    if badguys:
        print("BAD GUYS:")
        for b in badguys:
            print("  ", b)
        sys.exit(1)
    print(f"All {len(RUNS)} example runs succeeded (objectives asserted).")


if __name__ == "__main__":
    main()
