"""What every equations module's work counts share: bytes per element of
a configuration's dtype, and the roofline's least time at the chip's
peaks. The counts themselves (``step``, ``kernels``) are each
configuration's own, in ``equations/<name>.py``."""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def least_time(work: dict, peaks) -> float:
    """The least time the chip could take: the larger of FLOPs over peak
    FLOP/s and bytes over peak bandwidth."""
    return max(work["flops"] / peaks.bf16_flops, work["bytes"] / peaks.hbm_bw)


def bound_by(work: dict, peaks) -> str:
    return ("compute" if work["flops"] / peaks.bf16_flops
            >= work["bytes"] / peaks.hbm_bw else "memory")
