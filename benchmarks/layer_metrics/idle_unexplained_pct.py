"""Device: share of the traced slice's device-idle time whose middle no
``tpusppy:`` phase of any thread covers (``harness/progtrace.py``): idle
time that the program's own phases do not name."""

from benchmarks.harness import progtrace


def read(obs):
    red = progtrace.of(obs)
    if red is None or red["idle_s"] <= 0:
        return None
    return 100.0 * red["idle_unexplained_s"] / red["idle_s"]
