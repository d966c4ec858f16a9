"""Host rescue: Iter0 rows that ``spopt._rescue_stragglers`` re-solved with
HiGHS on the host (their residuals come back zeroed)."""


def read(obs):
    return obs.get("host_rescued_iter0")
