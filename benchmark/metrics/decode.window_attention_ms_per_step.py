"""The decode step's window layers' attention, by their calls in the decode program (``paged_window_attention_decode``:
the page walk over each slot's ring of window pages), over the same steps as ``decode.device_ms_per_step`` (runs of
``jit_run`` x ``decode_chunk``): the part of that step the window layers' attention takes. None on a program without
the window walk."""
from benchmark.metrics import _kernels, _serve

PATTERN = _kernels.call(("paged_window_attention",), "decode")


def read(obs):
    return _kernels.ms_per_run(obs, PATTERN, _serve.DECODE_MODULES, obs["decode_chunk"])
