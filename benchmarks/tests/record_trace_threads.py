"""Record ``data/threads.xplane.pb`` on the chip (run by hand, once):

    chiprun -- python3 benchmarks/tests/record_trace_threads.py chiprun_out

Two threads named as the wheel names its cylinders (``hub``,
``spoke1:Recorder``), each launching a program of its own and one that both
share inside a phase of the program's own tracer
(``tpusppy.obs.trace.phase``), without waiting between launches, so that
some are enqueued by the launching thread and some, their inputs not yet
computed, by a worker of the runtime; then a sleep inside a phase, one
launch outside any phase, and a sleep outside any.  Traced with the
harness's ``Tracer``; writes the XSpace and, beside it, what was launched
by construction and what ``progtrace`` read on the chip."""

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

ROUNDS = 4
PROGRAM = {"own_a": "jit_own_a", "own_b": "jit_own_b",
           "shared": "jit_shared_on_device"}    # as the trace names them
# launches per round: (thread track, phase, [(program, launches)])
PLAN = {
    "hub": ("megastep", [("own_a", 2), ("shared", 1)]),
    "spoke1:Recorder": ("pass", [("own_b", 3), ("shared", 2)]),
}


def main(out_dir):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import progtrace, tracered
    from tpusppy.obs import trace

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace_threads.py records on the TPU only")

    def dot(a, b):               # some milliseconds of the chip each
        return jnp.matmul(a, b, precision="highest")

    @jax.jit
    def own_a(x):
        return jnp.tanh(dot(dot(x, x), x)) * 0.5

    @jax.jit
    def own_b(x):
        return jnp.sin(dot(dot(x, x.T), x)) * 0.5

    @jax.jit
    def shared_on_device(x, h):
        return (dot(x, x) + h) * 0.25

    def shared(x):
        # a new host array each launch: its transfer is still under way
        # when the program is launched, so a worker enqueues the run
        return shared_on_device(x, np.full(x.shape, 1e-4, np.float32))

    progs = {"own_a": own_a, "own_b": own_b, "shared": shared}
    x0 = jnp.ones((4096, 4096), jnp.float32) * 1e-4
    for fn in progs.values():
        fn(x0).block_until_ready()                  # compile outside
    barrier = threading.Barrier(len(PLAN))

    def cylinder(track):
        trace.set_thread_track(track)
        phase, launches = PLAN[track]
        x = x0
        for _ in range(ROUNDS):
            barrier.wait()
            with trace.phase(phase):
                for name, n in launches:
                    for _ in range(n):
                        x = progs[name](x)
                x.block_until_ready()
            barrier.wait()
            with trace.phase("wait"):
                time.sleep(0.03)                    # idle inside a phase
            x = progs["shared"](x)                  # outside any phase
            x.block_until_ready()
            barrier.wait()
            time.sleep(0.02)                        # idle outside any phase

    tracer = tracered.Tracer(30.0, keep_path=os.path.join(
        out_dir, "threads.xplane.pb"))
    tracer.start()
    threads = [threading.Thread(target=cylinder, args=(track,), name=track)
               for track in PLAN]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    tracer.stop()
    data = jax.profiler.ProfileData.from_serialized_xspace(tracer._xspace)
    red = progtrace.reduce(progtrace.load(data))
    launched = {track.split(":")[0]: {
        phase: {PROGRAM[name]: n * ROUNDS for name, n in launches},
        "None": {PROGRAM["shared"]: ROUNDS}}
        for track, (phase, launches) in PLAN.items()}
    with open(os.path.join(out_dir, "threads.expected.json"), "w") as f:
        json.dump({"launched": launched, "rounds": ROUNDS,
                   "slept_in_phase_s": 0.03 * ROUNDS,
                   "slept_outside_s": 0.02 * ROUNDS,
                   "reduced_on_the_chip": red,
                   "device": jax.devices()[0].device_kind}, f, indent=1)
    print(json.dumps(red))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out")
