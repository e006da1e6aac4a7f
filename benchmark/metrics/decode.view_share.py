"""Pages of the K/V view that the decode calls gathered and attended over, as a share of the whole page table's:
``decode_view_pages`` over ``decode_table_pages`` in ``ServingEngine.stats``, each added once per decode call (the view
follows the longest live context; 100 is the whole ``max_len`` table in every call). Nothing to read on a program
without the counters, or where no decode call ran between the two copies."""
from benchmark.metrics import _spans


def read(obs):
    return _spans.ratio(obs, ("decode_view_pages",), "decode_table_pages", 100.0)
