"""Peak rates of each chip the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A chip not in the table is an error.

Source: Google Cloud TPU documentation, "TPU v5e" (system architecture:
197 TFLOP/s bf16, 394 TOP/s int8, 16 GiB HBM2 at 819 GB/s per chip).
"""

from __future__ import annotations

import dataclasses

SOURCE = ("Google Cloud TPU documentation, 'TPU v5e' system architecture "
          "page (per-chip peaks)")


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float          # FLOP/s
    int8_ops: float            # OP/s
    hbm_bytes_per_s: float     # B/s
    hbm_bytes: float           # B


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=394e12,
                         hbm_bytes_per_s=819e9, hbm_bytes=16 * 2**30),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; ``KeyError`` for a chip the table
    does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table holds {sorted(PEAKS)}") from None
