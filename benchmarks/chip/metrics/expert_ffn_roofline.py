"""Kernels: the least time the decode step's ``expert_ffn`` runs (one a
layer holding experts) require at the chip's peaks (the configuration's
``equations.kernels``, ``peaks.py``) over their measured device time per
decode step. Nothing where no such kernel ran or the equations define
none."""


def read(rec):
    t = rec.kernel_s("decode", "expert_ffn")
    least = rec.kernel_least_s("decode", "expert_ffn")
    if t is None or least is None:
        return None
    return 100.0 * least / t
