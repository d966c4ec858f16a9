"""Ingest, AOT bind and Iter0: seconds a request spends in the server's
``ingest`` phase on the submitting thread (the scenario creator's calls
and the canonical batch), ``phase.*.ingest.secs`` over the window's
requests."""

from benchmarks.harness import progtrace


def read(obs):
    return progtrace.phase_per_request_s(obs, "*.ingest")
