"""Service: share of the window's requests whose record says ``certified``."""


def read(obs):
    recs = obs["records"]
    if not recs:
        return None
    return 100.0 * sum(bool(r["certified"]) for r in recs) / len(recs)
