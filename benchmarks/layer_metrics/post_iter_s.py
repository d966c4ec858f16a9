"""Service / hub linger: seconds from the hub's last iteration to the
request's completion, on the benchmark's clock, mean over requests."""


def read(obs):
    vals = [r["post_iter_s"] for r in obs["requests"]]
    return sum(vals) / len(vals) if vals else None
