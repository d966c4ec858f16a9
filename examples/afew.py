"""Run a few example drivers end-to-end and fail on any error.

The analogue of the reference's ``examples/afew.py`` smoke harness (the
de-facto integration suite posture of SURVEY §4): shell out to driver CLIs,
assert exit status 0, collect the bad guys.
"""

from __future__ import annotations

import os
import subprocess
import sys

EXDIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, EXDIR)
from _harness_env import child_env  # noqa: E402

RUNS = [
    ("farmer/farmer_ef.py",
     ["--num-scens", "3", "--EF-solver-name", "admm"]),
    ("farmer/farmer_cylinders.py",
     ["--num-scens", "3", "--max-iterations", "20", "--default-rho", "1.0",
      "--rel-gap", "0.01", "--lagrangian", "--xhatshuffle"]),
    ("sizes/sizes_cylinders.py",
     ["--num-scens", "3", "--max-iterations", "30", "--default-rho", "0.01",
      "--rel-gap", "0.05", "--lagrangian", "--xhatshuffle"]),
    ("uc/uc_cylinders.py",
     ["--num-scens", "4", "--uc-num-gens", "3", "--uc-horizon", "6",
      "--max-iterations", "20", "--default-rho", "50.0",
      "--rel-gap", "0.02", "--lagrangian", "--xhatshuffle"]),
]


def main():
    badguys = []
    for script, args in RUNS:
        path = os.path.join(EXDIR, script)
        cmd = [sys.executable, path] + args
        print("==>", " ".join(cmd), flush=True)
        # child env: repo root on PYTHONPATH, cpu pinned
        # (EXAMPLES_KEEP_ENV=1 opts out)
        env = child_env(os.path.dirname(EXDIR))
        res = subprocess.run(cmd, cwd=os.path.dirname(path), env=env)
        if res.returncode != 0:
            badguys.append(script)
    if badguys:
        print("BAD GUYS:", badguys)
        sys.exit(1)
    print("All example runs succeeded.")


if __name__ == "__main__":
    main()
