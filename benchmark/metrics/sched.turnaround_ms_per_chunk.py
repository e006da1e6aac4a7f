"""Host turn-around a chunk: from the return of a chunk's sync to the next call of a compiled program on the engine
thread (admission or decode), the device's idle gap as the host sees it; chunks after which the loop waited for work
are not counted."""
from benchmark.metrics import _spans


def read(obs):
    return _spans.ratio(obs, ("turnaround_s",), "turnaround_n", 1e3)
