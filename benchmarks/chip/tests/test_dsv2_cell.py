"""The DeepSeek-V2 share (``equations/deepseek_v2.py``, the
``deepseek-v2-236b-ep8`` configuration and the ``dsv2.pd-closed`` cell):
the program check, hand counts of the required work, the
``expert_ffn_roofline`` reader, and whole runs at a CPU test size, sound
and broken.

The whole runs serve the program's reduced configuration in float32
(``tiny-deepseek-v2.json`` states float32): in bf16 at this size a
rounding flips some token's routing among near-tied experts in most
slices, and the gap that one flip leaves (up to 0.4 over 8 seeds) is as
wide as the gap of a program that ignores the group limit (0.13-0.26),
so no limit would tell the two apart here. In float32 the program's gap
is rounding of sums, and each fault's is orders of magnitude above it.
"""
import contextlib
import dataclasses
import functools
import json
import pathlib
from types import SimpleNamespace

import jax
import pytest

import peaks
import run
import work

DATA = pathlib.Path(__file__).resolve().parent / "data"
EQ = run.load_plugin("equations", "deepseek_v2")
FILE = json.loads((run.HERE / "configs" / "deepseek-v2-236b-ep8.json")
                  .read_text())
BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
MIX = json.loads((run.HERE / "traffic" / "ep8-pd-closed.json").read_text())
PEAKS = peaks.peaks_for("TPU v5 lite")


# --------------------------------------------------------------------- #
# the program check
# --------------------------------------------------------------------- #
def program(**moe):
    from repro.configs import get_config
    cfg = get_config("deepseek-v2-236b-ep8")
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **moe))


def test_check_accepts_the_served_share():
    EQ.check(program(), FILE)


@pytest.mark.parametrize("moe,what", [
    ({"norm_topk_prob": True}, "norm_topk_prob"),
    ({"held": None}, "all experts held: the capacity path, which drops"),
    ({"held": (20, 40)}, "another group"),
    ({"n_group": 1, "topk_group": 1}, "no group limit"),
    ({"routed_scaling_factor": 1.0}, "unscaled weights"),
    ({"num_shared_experts": 1}, "one shared expert")])
def test_check_refuses_another_program(moe, what):
    with pytest.raises(RuntimeError, match="departs from the file"):
        EQ.check(program(**moe), FILE)


def test_check_refuses_a_program_without_yarn():
    with pytest.raises(RuntimeError, match="yarn"):
        EQ.check(dataclasses.replace(program(), yarn=None), FILE)


def test_param_count_is_the_programs():
    from repro.models import transformer as T
    shapes = jax.eval_shape(lambda k: T.init_params(program(), k),
                            jax.random.PRNGKey(0))
    assert EQ.param_count(FILE) == T.count_params(shapes) == 4_062_970_880


# --------------------------------------------------------------------- #
# required work, by hand
# --------------------------------------------------------------------- #
D, H, QL, KL, NOPE, ROPE, V_HD = 5120, 128, 1536, 512, 128, 64, 128
DFF, F, VOCAB = 12288, 1536, 102400
ATTN = (D * QL + QL * H * (NOPE + ROPE) + D * (KL + ROPE)
        + KL * H * (NOPE + V_HD) + H * V_HD * D)      # 149,225,472
EXPERT = 3 * D * F                                     # 23,592,960
NORMS = 5 * (QL + KL + 2 * D) + D


def test_decode_hand_count():
    """256 sequences at position 512: every weight once (the router in
    float32), 256 embedding rows, the latent cache up to 512, ids in and
    logits out; FLOPs of the absorbed attention and of the held group's
    192 expected pairs (256 x 6 x 20 / 160)."""
    b, pos = 256, 512
    weights = (4 * 20 * EXPERT * 2           # held experts: 3.77 GB
               + 5 * ATTN * 2                # MLA: 1.49 GB
               + D * VOCAB * 2               # head: 1.05 GB
               + (4 * 2 * EXPERT + 3 * D * DFF) * 2   # shared, dense
               + 4 * D * 160 * 4             # routers, float32
               + NORMS * 2)
    cache = 5 * b * pos * (KL + ROPE) * 2    # 0.75 GB
    nbytes = weights + b * D * 2 + cache + b * 4 + b * VOCAB * 2
    proj = (D * QL + QL * H * (NOPE + ROPE) + D * (KL + ROPE)
            + H * NOPE * KL + H * KL * V_HD + H * V_HD * D)
    attn = 5 * b * (2 * proj + 2 * (pos + 1) * H * (2 * KL + ROPE))
    ffn = (2 * b * 3 * D * DFF
           + 4 * (2 * b * D * 160 + 2 * 192 * EXPERT + 2 * b * 2 * EXPERT))
    flops = attn + ffn + 2 * b * D * VOCAB
    got = EQ.step(FILE, "decode", b, 1024)
    assert got == {"flops": float(flops), "bytes": float(nbytes),
                   "tokens": b}
    assert 7.8e9 < nbytes < 7.95e9
    assert work.bound_by(got, PEAKS) == "memory"


def test_prefill_hand_count():
    """2 prompts of 512: projections of 1,024 tokens, causal pairs
    2 x 512 x 513 / 2 over 128 heads of q.k (192) and p.v (128), the held
    group's 768 expected pairs."""
    b, s = 2, 512
    t = b * s
    pairs = b * s * (s + 1) // 2
    attn = 5 * (2 * t * ATTN + 2 * pairs * H * (NOPE + ROPE + V_HD))
    ffn = (2 * t * 3 * D * DFF
           + 4 * (2 * t * D * 160 + 2 * 768 * EXPERT + 2 * t * 2 * EXPERT))
    got = EQ.step(FILE, "prefill", b, s)
    assert got["flops"] == float(attn + ffn + 2 * t * D * VOCAB)
    assert 3.5e12 < got["flops"] < 3.7e12
    assert work.bound_by(got, PEAKS) == "compute"


def test_expert_ffn_kernel_hand_count():
    """Per decode step, 4 expert layers: the 20 held experts' three
    matrices read once, 192 rows in and out, 192 pairs' SwiGLU FLOPs."""
    got = EQ.kernels(FILE, "decode", 256, 1024)
    assert got == {"expert_ffn": {
        "flops": float(4 * 2 * 192 * EXPERT),
        "bytes": float(4 * (20 * EXPERT + 2 * 192 * D) * 2)}}
    least = work.least_time(got["expert_ffn"], PEAKS)
    assert least == pytest.approx(3_790_602_240 / 819e9)     # 4.63 ms


# --------------------------------------------------------------------- #
# the kernel's reader
# --------------------------------------------------------------------- #
TENANTS = {t["name"]: {k: t[k] for k in ("phase", "batch", "seq")}
           for t in MIX["tenants"]}


def record(trace):
    s = SimpleNamespace(
        tenants=TENANTS,
        work={n: EQ.step(FILE, t["phase"], t["batch"], t["seq"])
              for n, t in TENANTS.items()},
        kernels={n: EQ.kernels(FILE, t["phase"], t["batch"], t["seq"])
                 for n, t in TENANTS.items()})
    return run.Record(SimpleNamespace(drains=[], s=s), trace, PEAKS)


TRACE = {"window_s": 2.0, "busy_s": 1.9, "devices": 1,
         "modules": {"prefill": {"count": 10, "device_s": 0.2},
                     "decode": {"count": 50, "device_s": 0.6}},
         "kernels": {"decode": {
             "expert_ffn": {"count": 200, "device_s": 0.3},
             "fusion": {"count": 500, "device_s": 0.2}}}}


def test_expert_ffn_roofline():
    """50 decode steps ran 200 kernels (4 a step) in 300 ms: 6 ms a step,
    against the 4.63 ms their bytes take at 819 GB/s."""
    got = run.load_plugin("metrics", "expert_ffn_roofline").read(
        record(TRACE))
    assert got == pytest.approx(100.0 * 3_790_602_240 / 819e9 / 6e-3)
    assert 77.0 < got < 77.2


@pytest.mark.parametrize("trace", [
    {**TRACE, "kernels": {"decode": {"fusion": {"count": 1,
                                                "device_s": 0.1}}}},
    {k: v for k, v in TRACE.items() if k != "kernels"}, {}],
    ids=["no-expert-kernel", "no-kernels", "no-trace"])
def test_expert_ffn_roofline_reads_nothing_where_none_ran(trace):
    assert run.load_plugin("metrics", "expert_ffn_roofline").read(
        record(trace)) is None


# --------------------------------------------------------------------- #
# whole runs at a CPU test size
# --------------------------------------------------------------------- #
@pytest.fixture
def float32_program(monkeypatch):
    """The program's reduced configurations in float32, as the tiny file
    states; ``serve`` bound ``reduced`` at import."""
    import repro.configs as configs
    import repro.launch.serve as serve
    real = configs.reduced

    def in_f32(cfg):
        return dataclasses.replace(real(cfg), dtype="float32")
    monkeypatch.setattr(configs, "reduced", in_f32)
    monkeypatch.setattr(serve, "reduced", in_f32)


def spec():
    return {"cell": {"name": "tiny", "chips": 1},
            "config": json.loads((DATA / "tiny-deepseek-v2.json")
                                 .read_text()),
            "traffic": json.loads((DATA / "tiny-pd.json").read_text()),
            "end_to_end": BENCH["end_to_end"], "per_layer": []}


def test_sound_share_is_correct(float32_program):
    out = run.run_cell(spec(), 2**31 + 21, 1.0, False, on_chip=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def served_by(moe=None, patch=contextlib.nullcontext):
    """A fault: every tenant served by the program with ``moe`` changed,
    or compiled under ``patch``, on the same weights and inputs."""
    def fault(s):
        from repro.configs import get_config, reduced
        from repro.launch.serve import decode_logits, prefill_logits
        cfg = reduced(get_config(s.config["program"]["arch"]))
        if moe:
            cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, **moe))
        for name, t in s.tenants.items():
            step = prefill_logits if t["phase"] == "prefill" \
                else decode_logits
            with patch():
                s.srv._exec[name] = jax.jit(functools.partial(
                    step, cfg=cfg)).lower(*s.srv._args[name]).compile()
    return fault


@contextlib.contextmanager
def capacity_drops():
    """Every expert keeps only its first quarter-tile of rows, a capacity
    below the rows it gets on average (a tile is at least twice that):
    the rest of its tokens are dropped."""
    from repro.kernels import ops
    kernel = ops.expert_ffn

    def dropping(xs, wi, wg, wo, te, n, layer, tm):
        y = kernel(xs, wi, wg, wo, te, n, layer, tm)
        tiles = y.reshape(-1, tm, y.shape[-1])
        return tiles.at[:, tm // 4:].set(0).reshape(y.shape)
    ops.expert_ffn = dropping
    try:
        yield
    finally:
        ops.expert_ffn = kernel


@pytest.mark.parametrize("fault", [
    served_by({"n_group": 1, "topk_group": 1}),
    served_by({"num_shared_experts": 0}),
    served_by({"norm_topk_prob": True}),
    served_by(patch=capacity_drops)],
    ids=["group-limit-ignored", "shared-expert-left-out",
         "top-k-renormalised", "tokens-dropped"])
def test_broken_share_is_not_correct(float32_program, fault):
    out = run.run_cell(spec(), 2**31 + 22, 1.0, False, on_chip=False,
                       fault=fault)
    assert not out["correct"], out["checks"]
