"""Kernels: the least time the decode step's ``decode_attention`` runs
require at the chip's peaks (the configuration's ``equations.kernels``,
``peaks.py``) over their measured device time per decode step. Nothing
where no such kernel ran or the equations define none."""


def read(rec):
    t = rec.kernel_s("decode", "decode_attention")
    least = rec.kernel_least_s("decode", "decode_attention")
    if t is None or least is None:
        return None
    return 100.0 * least / t
