"""Operations of every prompt token computed and every token decoded in the window, over the window and the peak: the whole step's share beside `decode.step_roofline`, moving the same metric."""
from benchmark.metrics import _serve


def read(obs):
    return _serve.whole_mfu(obs)
