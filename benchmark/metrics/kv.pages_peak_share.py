"""Most pages of the pool ever in use, over the pool."""


def read(obs):
    kv = obs["kv_after"]
    return 100.0 * kv["pages_peak"] / kv["pages_total"]
