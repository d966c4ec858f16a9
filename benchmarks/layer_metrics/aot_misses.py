"""Compile / AOT load: ``aot.misses`` counted inside the window (a warm
family binds without one)."""


def read(obs):
    return obs["counters"].get("aot.misses")
