"""Programs the persistent cache did not hold, from process start to the window's close (``core.compile_cache.stats()``); 0 on every run of a cell after its first."""


def read(obs):
    return obs["compile_after"]["misses"]
