"""Service and hub linger: seconds a request's wheel spends in its
``teardown`` phase, hub main's return to the WheelSpinner's return
(terminate, joins, the spokes' final passes, finalize),
``phase.*.teardown.secs`` over the window's requests."""

from benchmarks.harness import progtrace


def read(obs):
    return progtrace.phase_per_request_s(obs, "*.teardown")
