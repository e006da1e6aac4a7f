"""Operations of every prompt token computed and every token decoded in the window, over the window and the peak."""
from benchmark.metrics import _serve


def read(obs):
    return _serve.whole_mfu(obs)
