"""Seconds in the backend compiler (or retrieving from the cache) up to the window's close."""


def read(obs):
    return obs["compile_after"]["backend_compile_s"]
