"""The decode step's attention kernels, by their calls in the decode program (``paged_gqa_attention_decode``,
``paged_latent_attention_decode``), over the same steps as ``decode.device_ms_per_step`` (runs of ``jit_run`` x
``decode_chunk``): the part of that step the paged attention takes."""
from benchmark.metrics import _kernels, _serve

PATTERN = _kernels.call(("paged_gqa_attention", "paged_latent_attention"), "decode")


def read(obs):
    return _kernels.ms_per_run(obs, PATTERN, _serve.DECODE_MODULES, obs["decode_chunk"])
