"""Share of its busy time (iterations that ran a chunk, less waiting for work) that the engine thread spent waiting for
the device: spans ``serve.first_sync`` + ``serve.chunk_sync`` over ``loop_busy_s``. Both spans lie inside that time, so
the share is at most 100; two copies torn between the loop's two updates could read over, and then read nothing."""
from benchmark.metrics import _spans


def read(obs):
    share = _spans.ratio(obs, ("span_s.serve.first_sync", "span_s.serve.chunk_sync"), "loop_busy_s", 100.0)
    return share if share is not None and share <= 100.0 else None
