"""Device peak specs — the denominators of every roofline number.

One table for peak matmul FLOP/s (the MFU denominator ``bench.py`` has
used since round 1, moved here so the cost registry and the bench share
one definition) and one for peak HBM bandwidth (the bandwidth-bound half
of the roofline). Values are the published per-chip peaks for the bf16
MXU path, keyed by the ``device_kind`` string the chip reports. An
accelerator that is not in the table raises: a utilization against
somebody else's peak is not a number. The CPU gets deliberately tiny
figures so CPU test runs still produce finite, obviously-not-a-TPU
ratios (ROADMAP 3.6 retires them with the tests that pin them).
"""

from __future__ import annotations

from typing import Optional

# device_kind (lower-cased, as jax reports it) ->
#   (peak bf16 FLOP/s, peak HBM bytes/s); source: Google Cloud TPU docs
_TABLE = {
    "tpu v6 lite": (918e12, 1640e9),   # Trillium (v6e)
    "tpu v5": (459e12, 2765e9),        # v5p
    "tpu v5 lite": (197e12, 819e9),    # v5e — the chip this round measures
    "tpu v4": (275e12, 1228e9),
    "tpu v3": (123e12, 900e9),
    "tpu v2": (45e12, 700e9),
}
_DEFAULT_CPU = (1e12, 100e9)        # container CPU: keeps ratios finite


def _lookup(device) -> tuple:
    kind = str(getattr(device, "device_kind", ""))
    row = _TABLE.get(kind.lower())
    if row is not None:
        return row
    platform = getattr(device, "platform", "cpu")
    if platform == "cpu":
        return _DEFAULT_CPU
    raise KeyError(
        f"no peak specs for device_kind {kind!r} (platform {platform!r}): "
        "add its published peaks to observability/perf/device.py before "
        "quoting a utilization on it")


def peak_flops(device=None) -> float:
    """Peak bf16 matmul FLOP/s for ``device`` (default: jax.devices()[0])."""
    return specs(device)["peak_flops"] if device is None \
        else _lookup(device)[0]


def peak_hbm_bytes_per_s(device=None) -> float:
    """Peak HBM bandwidth in bytes/s."""
    return specs(device)["peak_hbm_bytes_per_s"] if device is None \
        else _lookup(device)[1]


_specs: Optional[dict] = None


def specs(device=None) -> dict:
    """Resolved peak-spec dict for the process's default device (cached —
    the registry derives every roofline number from it). Passing a device
    bypasses the cache."""
    global _specs
    if device is not None:
        flops, bw = _lookup(device)
        return {
            "device": str(getattr(device, "device_kind", "")
                          or getattr(device, "platform", "?")),
            "platform": getattr(device, "platform", "?"),
            "peak_flops": flops,
            "peak_hbm_bytes_per_s": bw,
            "ridge_flops_per_byte": flops / bw,
        }
    if _specs is None:
        import jax

        _specs = specs(jax.devices()[0])
    return _specs


def reset_cache() -> None:
    global _specs
    _specs = None
