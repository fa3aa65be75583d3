"""Work and peaks: parameter counts, hand counts of a step's FLOPs and
bytes, and the roofline's bound."""
import json
import pathlib

import pytest

import peaks
import work

HERE = pathlib.Path(__file__).resolve().parent
CONFIGS = HERE.parent / "configs"
DATA = HERE / "data"           # configurations no cell runs yet


def cfg(name):
    path = CONFIGS / f"{name}.json"
    if not path.is_file():
        path = DATA / f"{name}.json"
    return json.loads(path.read_text())


def test_phi3_params_3_82b():
    assert work.param_count(cfg("phi3-mini-3.8b")) == 3_821_079_552


def test_stablelm3b_params_about_2_8b():
    n = work.param_count(cfg("stablelm-3b"))
    assert 2.79e9 < n < 2.80e9


def test_phi3_prefill_slice_flops_hand_count():
    c = cfg("phi3-mini-3.8b")
    d, f, v, L, b, s = 3072, 8192, 32064, 32, 2, 512
    per_layer = 4 * d * d + 3 * d * f          # q, k, v, o; gate, up, down
    matmul = 2 * b * s * (L * per_layer + d * v)
    attn = L * b * 4 * d * (s * (s + 1) // 2)  # QK^T and PV, causal once
    assert work.prefill(c, b, s)["flops"] == matmul + attn
    # about 39.7 ms at the v5e's 197 TFLOP/s
    t = work.prefill(c, b, s)["flops"] / peaks.peaks_for("TPU v5 lite").bf16_flops
    assert 0.039 < t < 0.040


def test_decode_bytes_count_cache_only_to_position():
    c = cfg("phi3-mini-3.8b")
    per_pos = 32 * 16 * 2 * 32 * 96 * 2        # layers, seqs, k+v, kv, hd, bf16
    a = work.decode(c, 16, 256)["bytes"]
    b = work.decode(c, 16, 257)["bytes"]
    assert b - a == per_pos
    # a 512-long cache at position 256 is read for 256 positions, not 512
    assert work.step(c, "decode", 16, 512)["bytes"] == a


def test_decode_is_memory_bound_prefill_compute_bound():
    pk = peaks.peaks_for("TPU v5 lite")
    c = cfg("phi3-mini-3.8b")
    assert work.bound_by(work.step(c, "prefill", 2, 512), pk) == "compute"
    assert work.bound_by(work.step(c, "decode", 16, 512), pk) == "memory"


@pytest.mark.parametrize("name", ["phi3-mini-3.8b", "stablelm-3b"])
@pytest.mark.parametrize("phase,batch", [("prefill", 2), ("decode", 16)])
def test_roofline_share_never_exceeds_one(name, phase, batch):
    pk = peaks.peaks_for("TPU v5 lite")
    w = work.step(cfg(name), phase, batch, 512)
    least = work.least_time(w, pk)
    # the least time is the larger bound, so a step measured at the
    # table's peaks reads a share of at most 1
    assert least == max(w["flops"] / pk.bf16_flops, w["bytes"] / pk.hbm_bw)
    assert least > 0


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
