"""The long prefill's attention kernel, by its calls in the admission programs (``latent_prefill_attention_admit``),
over the same runs as ``prefill.device_ms_per_request.saturated`` (whole-document prefills and questions behind a
cached document alike): the part of an admission the blocked attention takes."""
from benchmark.metrics import _kernels, _serve

PATTERN = _kernels.call(("latent_prefill_attention",), "admit")


def read(obs):
    return _kernels.ms_per_run(obs, PATTERN, _serve.ADMIT_MODULES)
