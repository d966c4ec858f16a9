"""Ingest, AOT bind and Iter0: seconds a request spends building its wheel
on the executor's thread, the server's ``slice_build`` phase (hub and spoke
dicts) plus the WheelSpinner's ``build`` (construction up to the spoke
threads' start), over the window's requests."""

from benchmarks.harness import progtrace


def read(obs):
    parts = [progtrace.phase_per_request_s(obs, p)
             for p in ("*.slice_build", "*.build")]
    return None if None in parts else sum(parts)
