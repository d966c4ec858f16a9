"""Hub loop under the server: seconds per hub iteration between the end of
Iter0 and the hub's last iteration, on the benchmark's clock, mean over
requests."""


def read(obs):
    vals = [r["exec_iter_s"] for r in obs["requests"]]
    return sum(vals) / len(vals) if vals else None
