"""Peak bytes in use on the fullest chip (``memory_stats()``), read when the window closes."""


def read(obs):
    return obs["memory_peak_bytes"] / 1e9 if obs["memory_peak_bytes"] else None
