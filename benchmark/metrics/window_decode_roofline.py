"""The window layers' decode walk (``paged_window_attention_decode``) against its roofline: the keys of each live
slot's window and its query, new row and output once, over the calls' device time (``_walk``)."""
from benchmark.metrics import _walk


def read(obs):
    return _walk.roofline(obs, "paged_window_attention", True)
