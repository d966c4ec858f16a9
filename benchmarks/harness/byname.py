"""A file found by name: ``<bench_dir>/<kind>/<name>.py``.

Per-layer metrics (``layer_metrics``), references (``references``) and
checks (``checks``) are each a file of their own, so that a later PR adds
one without editing a file that is there.
"""

from __future__ import annotations

import importlib.util
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(kind, name, bench_dir, attr, stem=False):
    """Attribute ``attr`` of ``<bench_dir>/<kind>/<name>.py``, run anew.
    With ``stem``, a name that has no file of its own shares the file named
    for what stands before its first dot."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if stem and not os.path.exists(path):
        path = os.path.join(bench_dir, kind, name.split(".", 1)[0] + ".py")
    if not os.path.exists(path):
        have = sorted(f[:-3] for f in os.listdir(os.path.join(bench_dir, kind))
                      if f.endswith(".py") and f != "__init__.py")
        raise FileNotFoundError(
            f"no {kind}/{name}.py under {bench_dir} (have {have})")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr)
