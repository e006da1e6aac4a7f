"""The full layers' decode walk (``paged_gqa_attention_decode``, keys of 192 over values of 128 read as one row a
token) against its roofline: every live token's keys and values and each slot's query, new row and output once, over
the calls' device time (``_walk``)."""
from benchmark.metrics import _walk


def read(obs):
    return _walk.roofline(obs, "paged_gqa_attention", False)
