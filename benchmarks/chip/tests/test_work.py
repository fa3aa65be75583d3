"""Work and peaks: parameter counts, hand counts of a step's and a
kernel's FLOPs and bytes (the dense equations module), and the roofline's
bound."""
import json
import pathlib

import pytest

import peaks
import run
import work

dense = run.load_plugin("equations", "dense")

HERE = pathlib.Path(__file__).resolve().parent
CONFIGS = HERE.parent / "configs"
DATA = HERE / "data"           # configurations no cell runs yet


def cfg(name):
    path = CONFIGS / f"{name}.json"
    if not path.is_file():
        path = DATA / f"{name}.json"
    return json.loads(path.read_text())


def test_phi3_params_3_82b():
    assert dense.param_count(cfg("phi3-mini-3.8b")) == 3_821_079_552


def test_stablelm3b_params_about_2_8b():
    n = dense.param_count(cfg("stablelm-3b"))
    assert 2.79e9 < n < 2.80e9


def test_phi3_prefill_slice_flops_hand_count():
    c = cfg("phi3-mini-3.8b")
    d, f, v, L, b, s = 3072, 8192, 32064, 32, 2, 512
    per_layer = 4 * d * d + 3 * d * f          # q, k, v, o; gate, up, down
    matmul = 2 * b * s * (L * per_layer + d * v)
    attn = L * b * 4 * d * (s * (s + 1) // 2)  # QK^T and PV, causal once
    assert dense.prefill(c, b, s)["flops"] == matmul + attn
    # about 39.7 ms at the v5e's 197 TFLOP/s
    t = dense.prefill(c, b, s)["flops"] / peaks.peaks_for(
        "TPU v5 lite").bf16_flops
    assert 0.039 < t < 0.040


def test_decode_bytes_count_cache_only_to_position():
    c = cfg("phi3-mini-3.8b")
    per_pos = 32 * 16 * 2 * 32 * 96 * 2        # layers, seqs, k+v, kv, hd, bf16
    a = dense.decode(c, 16, 256)["bytes"]
    b = dense.decode(c, 16, 257)["bytes"]
    assert b - a == per_pos
    # a 512-long cache at position 256 is read for 256 positions, not 512
    assert dense.step(c, "decode", 16, 512)["bytes"] == a


def test_decode_is_memory_bound_prefill_compute_bound():
    pk = peaks.peaks_for("TPU v5 lite")
    c = cfg("phi3-mini-3.8b")
    assert work.bound_by(dense.step(c, "prefill", 2, 512), pk) == "compute"
    assert work.bound_by(dense.step(c, "decode", 16, 512), pk) == "memory"


@pytest.mark.parametrize("name", ["phi3-mini-3.8b", "stablelm-3b"])
@pytest.mark.parametrize("phase,batch", [("prefill", 2), ("decode", 16)])
def test_roofline_share_never_exceeds_one(name, phase, batch):
    pk = peaks.peaks_for("TPU v5 lite")
    w = dense.step(cfg(name), phase, batch, 512)
    least = work.least_time(w, pk)
    # the least time is the larger bound, so a step measured at the
    # table's peaks reads a share of at most 1
    assert least == max(w["flops"] / pk.bf16_flops, w["bytes"] / pk.hbm_bw)
    assert least > 0


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


# The dense counts as ``work.py`` gave them before they moved into
# ``equations/dense.py``: (parameters, prefill, decode) at a tiny-pd
# slice (prefill 2 x 64, decode 4 at 32 of 64) for the tiny configurations
# and a pd-closed slice (prefill 2 x 512, decode 16 at 256 of 512) for
# stablelm-3b.
BEFORE = {
    "tiny-phi3": ((2, 64), (4, 64), 459392,
                  {"flops": 104923136.0, "bytes": 952064.0, "tokens": 128},
                  {"flops": 3280896.0, "bytes": 923920.0, "tokens": 4}),
    "tiny-stablelm": ((2, 64), (4, 64), 460032,
                      {"flops": 104923136.0, "bytes": 953344.0,
                       "tokens": 128},
                      {"flops": 3280896.0, "bytes": 925200.0, "tokens": 4}),
    "stablelm-3b": ((2, 512), (16, 512), 2795443200,
                    {"flops": 5546715381760.0, "bytes": 5441599488.0,
                     "tokens": 1024},
                    {"flops": 86670049280.0, "bytes": 6677198912.0,
                     "tokens": 16}),
}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_counts_unchanged_by_the_move(name):
    pre, dec, params, prefill, decode = BEFORE[name]
    c = cfg(name)
    assert dense.param_count(c) == params
    assert dense.step(c, "prefill", *pre) == prefill
    assert dense.step(c, "decode", *dec) == decode


def test_decode_attention_kernel_hand_count():
    """q in, output out, k and v of positions 0..256 read, for each of
    the 32 layers: 1.62 GB, about 1.98 ms at 819 GB/s."""
    c = cfg("phi3-mini-3.8b")
    k = dense.kernels(c, "decode", 16, 512)["decode_attention"]
    L, b, h, hd, pos = 32, 16, 32, 96, 256
    assert k["bytes"] == L * (2 * b * h * hd + 2 * b * (pos + 1) * h * hd) * 2
    assert k["flops"] == L * b * 4 * h * hd * (pos + 1)
    pk = peaks.peaks_for("TPU v5 lite")
    assert work.bound_by(k, pk) == "memory"
    assert 1.97e-3 < work.least_time(k, pk) < 1.99e-3
    # a part of the decode step's required work
    step = dense.decode(c, b, pos)
    assert k["flops"] < step["flops"] and k["bytes"] < step["bytes"]


def test_prefill_defines_no_kernel():
    assert dense.kernels(cfg("phi3-mini-3.8b"), "prefill", 2, 512) == {}
