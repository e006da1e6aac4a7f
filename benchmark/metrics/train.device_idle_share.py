"""Share of the traced window in which no operation ran on the device (profiler trace, mean over the chips used)."""


def read(obs):
    tr = obs["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
