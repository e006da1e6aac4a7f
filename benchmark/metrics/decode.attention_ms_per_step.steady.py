"""``decode.attention_ms_per_step`` in a cell below its knee, where it moves the time to a first token (a first token
waits for the chunk after its admission): the paged attention kernels' calls in the decode program over its steps."""
from benchmark.metrics import _kernels, _serve

PATTERN = _kernels.call(("paged_gqa_attention", "paged_latent_attention"), "decode")


def read(obs):
    return _kernels.ms_per_run(obs, PATTERN, _serve.DECODE_MODULES, obs["decode_chunk"])
