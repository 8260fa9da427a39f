"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

Every roofline and utilisation share of the benchmark divides by a row of
this table. A device that is not in it is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,  # bf16 FLOP/s per chip
        "hbm_bw": 819e9,  # B/s per chip
        "ici_bw": 50e9,  # B/s per link (1,600 Gbit/s per chip over 4 links)
        "name": "tpu-v5e",
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks_for(device_kind: str) -> dict:
    """The :data:`PEAKS` row of ``device_kind``; raises for an unknown one."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add a sourced row to PEAKS"
        ) from None
