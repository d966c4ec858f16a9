"""Record ``data/small.xplane.pb`` on the chip (run by hand, once):

    chiprun -- python3 benchmarks/tests/record_trace.py chiprun_out

Three bursts of matrix products with sleeps between them, each burst and
its sleep inside a ``bench:hub_step`` span, traced with the harness's own
``Tracer``.  Writes the XSpace and what the host clock saw beside it."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir):
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import tracered

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace.py records on the TPU only")

    @jax.jit
    def burst(x):
        for _ in range(8):
            x = jnp.tanh(x @ x) * 0.5
        return x

    x = jnp.ones((1024, 1024), jnp.float32) * 0.01
    burst(x).block_until_ready()                    # compile outside
    tracer = tracered.Tracer(30.0, keep_path=os.path.join(
        out_dir, "small.xplane.pb"))
    tracer.start()
    t0 = time.monotonic()
    busy_bound = slept = 0.0
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench:hub_step"):
            b0 = time.monotonic()
            for _ in range(5):
                x = burst(x)
            x.block_until_ready()
            busy_bound += time.monotonic() - b0
            time.sleep(0.05)
            slept += 0.05
    t1 = time.monotonic()
    tracer.stop()
    red = tracer.result()
    with open(os.path.join(out_dir, "small.expected.json"), "w") as f:
        json.dump({"host_window_s": t1 - t0, "host_busy_bound_s": busy_bound,
                   "slept_s": slept, "reduced_on_the_chip": red,
                   "device": jax.devices()[0].device_kind}, f, indent=1)
    print(json.dumps(red))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out")
