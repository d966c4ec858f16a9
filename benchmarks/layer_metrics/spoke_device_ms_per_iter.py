"""Spoke bound passes: device milliseconds per hub iteration of the runs
that the spokes' threads (``tpusppy:spoke<n>:*``) launched, over the same
whole turns of the hub's cycle as ``hub_device_ms_per_iter``
(``harness/progtrace.py``)."""

from benchmarks.harness import progtrace


def read(obs):
    return progtrace.device_ms_per_iter(obs, "spoke*")
