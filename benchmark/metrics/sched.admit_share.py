"""Share of its busy time (iterations that ran a chunk, less waiting for work) that the engine thread spent inside
admissions and the wait for their first tokens (``span_s.serve.admit`` + ``span_s.serve.first_sync`` over
``loop_busy_s``): how long the decoding slots starve behind prefills. Nothing to read on a program without the phase clock."""
from benchmark.metrics import _spans


def read(obs):
    return _spans.ratio(obs, ("span_s.serve.admit", "span_s.serve.first_sync"), "loop_busy_s", 100.0)
