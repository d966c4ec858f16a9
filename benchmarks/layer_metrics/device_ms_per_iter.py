"""Megastep program and sweep kernels: milliseconds on the device per hub
iteration, all of it from the profiler's trace: the time in which a run of
one of the workload file's ``device_programs`` (compiled programs, by the
start of their name in the trace: the megastep and the batch solve with its
sweeps) was on the device between the traced slice's first and last hub
boundary, over the hub iterations between those two.  The trace cannot
tell the cylinders apart: the spokes' runs of the same programs count."""


def read(obs):
    tr = obs.get("trace")
    if tr is None or tr["iterations"] is None:
        return None
    want = tuple(obs["workload"]["device_programs"])
    busy = sum(s for name, s in tr["iterations"]["program_busy_s"].items()
               if name.startswith(want))
    return 1e3 * busy / tr["iterations"]["count"] if busy > 0 else None
