"""Megastep program and sweep kernels: share of the window's refresh
solves, every cylinder's, whose sweep loop made one kernel call a step:
the fused sweep kernel hands back the residual rows of the iterate it ends
on and the loop carries the kernel's layout (the program's counter
``refresh.kernel_checkpoint`` over ``phase.<cylinder>.refresh.count``).  A
program without the counter made its checkpoints in XLA: 0."""

from benchmarks.harness import progtrace


def read(obs):
    refreshes = progtrace.phase_counter(obs, "*.refresh", "count")
    if not refreshes:
        return None
    return (100.0 * obs["counters"].get("refresh.kernel_checkpoint", 0)
            / refreshes)
