"""Host time an admission: prefix plan, page reservation, page-table row, padding and the admission program's dispatch
(span ``serve.admit``; refused attempts add their time, not a count)."""
from benchmark.metrics import _spans


def read(obs):
    return _spans.ratio(obs, ("span_s.serve.admit",), "span_n.serve.admit", 1e3)
