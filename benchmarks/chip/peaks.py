"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A kind that is not in the table is an error, never a
default: a roofline share against a guessed peak means nothing."""
from __future__ import annotations

import dataclasses

SOURCE = ("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
          "393 TOP/s int8, 16 GB HBM at 819 GB/s per chip")


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s
    int8_ops: float        # OP/s
    hbm_bw: float          # bytes/s
    hbm_bytes: float       # bytes


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9,
                         hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
