"""``decode.view_share`` in the cells whose documents fill the table: ``decode_view_pages`` over ``decode_table_pages``
between the two copies of ``ServingEngine.stats``. Near 100 it is the witness that the cell bypasses the bounded view.
Nothing to read on a program without the counters, or where no decode call ran between the two copies."""
from benchmark.metrics import _spans


def read(obs):
    return _spans.ratio(obs, ("decode_view_pages",), "decode_table_pages", 100.0)
