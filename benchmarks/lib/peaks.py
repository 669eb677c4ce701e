"""Published peaks per chip, keyed by ``device_kind`` as JAX reports it.

Copied from ``bench.PEAKS`` (PR 21) so that later PRs to the program cannot
move the denominator. A device missing from the table is an error, not a
default.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197.0e12,
        "hbm_bytes_per_s": 819.0e9,
        "hbm_bytes": 16.0e9,
        "source": "Google Cloud documentation, \"TPU v5e\"",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s or bandwidth recorded for device_kind="
            f"{device_kind!r}; add it to benchmarks/lib/peaks.py with its "
            f"source (known: {sorted(PEAKS)})") from None
