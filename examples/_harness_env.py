"""Child environment for the example harnesses.

``run_all.py``/``afew.py`` run every example driver as a CPU child with
the repo root on PYTHONPATH and x64 on, so they are green in any shell
(reference CI posture: ``straight.yml`` runs anywhere).

Set ``EXAMPLES_KEEP_ENV=1`` to keep the ambient environment (e.g. to run
the examples on an attached TPU with the float32 solver options).
"""

from __future__ import annotations

import os


def child_env(repo_root: str) -> dict:
    """Environment for an example-driver child process."""
    env = dict(os.environ)
    if os.environ.get("EXAMPLES_KEEP_ENV"):
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        return env
    env["PYTHONPATH"] = repo_root
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("JAX_ENABLE_X64", "1")
    return env
