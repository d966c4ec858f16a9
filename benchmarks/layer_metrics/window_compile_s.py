"""Compile / AOT load: seconds jax spent in backend compiles, cache
retrievals included, inside the window (jax.monitoring).  0 is the
expected reading: set-up warms every shape."""


def read(obs):
    return obs["compile_s"]
