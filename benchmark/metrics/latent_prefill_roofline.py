"""The long prefill's attention kernel's share of its roofline (compute-bound from a few hundred keys on): the least
time of the kernel's calls in the traced window over their device time. The trace shows a Mosaic call by its HLO text;
this kernel's result is ``[heads, query rows padded to whole blocks, v_head_dim]``, a shape no other call of the
program has. A call's prompt is the largest of the configuration's whole-prompt buckets (``engine_facts.admit_buckets``)
that its padded rows hold, so the count never exceeds what ran. Nothing to read where no such call ran (the parent; a
cell whose prompts stay under the bound of the one-piece form) or where the family counts no such kernel."""
import re

from benchmark import work

CALL = re.compile(r'= \w+\[(\d+),(\d+),(\d+)\]\S* custom-call\(.*custom_call_target="tpu_custom_call"')


def read(obs):
    cfg, counts, tr = obs["config"], work.counts(obs), obs["trace"]
    if not hasattr(counts, "prefill_kernel_ops"):
        return None
    shape = (cfg["num_attention_heads"], cfg["v_head_dim"])
    buckets = cfg.get("engine_facts", {}).get("admit_buckets", ())
    pk = work.peaks(obs["device_kind"])
    seconds = least = 0.0
    for text, spent in tr["op_text_s"].items():
        m = CALL.search(text)
        if m is None or (int(m[1]), int(m[3])) != shape:
            continue
        tokens = max((b for b in buckets if b <= int(m[2])), default=int(m[2]))
        seconds += spent
        least += tr["op_text_n"][text] * work.roofline_least_s(
            counts.prefill_kernel_ops(cfg, 0, tokens), counts.prefill_kernel_bytes(cfg, 0, tokens), pk)
    return 100.0 * least / seconds if seconds else None
