"""Host time a chunk between its sync and the next admission: tokens appended, slots retired, futures set and their
callbacks run, pages released (span ``serve.deliver``)."""
from benchmark.metrics import _spans


def read(obs):
    return _spans.ratio(obs, ("span_s.serve.deliver",), "span_n.serve.chunk_sync", 1e3)
