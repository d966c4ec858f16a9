"""Host integer tier: scenarios handed to a host MILP per bound pass of
the cylinder that dives (``xhat.host_milp_rows`` over
``phase.spoke<n>.pass.count`` of the spokes that have a ``dive`` phase).  A
window that holds dives and the end of no pass holds a part of one pass:
it counts as one."""


def read(obs):
    counters = obs["counters"]
    if "xhat.dive_rounds" not in counters:
        return None
    passes = sum(
        counters.get(f"phase.{key.split('.')[1]}.pass.count", 0)
        for key in counters
        if key.startswith("phase.spoke") and key.endswith(".dive.count"))
    return counters.get("xhat.host_milp_rows", 0) / max(passes, 1.0)
