"""Megastep program and sweep kernels: device milliseconds per hub iteration
of the runs that the HUB's thread launched (``harness/progtrace.py``: each
``XLA Modules`` run joined to the thread whose ``tpusppy:hub:*`` phases
stand on the launching line), from the traced slice's first hub boundary to
its last, over the hub iterations between them; where runs launched before
the trace began still hold the device, from the first boundary of the same
kind behind them, so that whole turns of the hub's cycle are counted."""

from benchmarks.harness import progtrace


def read(obs):
    return progtrace.device_ms_per_iter(obs, "hub")
