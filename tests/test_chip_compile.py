"""Compiles the main path for a described TPU v5e chip, none attached.

Each Pallas kernel at real widths, the full-width phi3-mini-3.8b decode
step, and deepseek-v2-236b-ep8's prefill and decode steps at the
``dsv2.pd-closed`` cell's shapes, with their weights as arguments, go
through the chip's own compiler:
what it refuses (unaligned blocks, unlowerable primitives, scoped-VMEM
overruns, programs larger than HBM) fails here, where interpret mode would
pass. Kernels are called with ``interpret=False`` directly, because the
``ops`` wrappers see the CPU backend and would interpret.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.profiles import V5E, device_peaks
from repro.kernels import coschedule as cs
from repro.kernels import decode_attention as da
from repro.kernels import expert_ffn as ef
from repro.kernels import flash_attention as fa
from repro.kernels import rg_lru as lru
from repro.kernels import rwkv6_scan as wkv
from repro.kernels import sliced_matmul as sm
from repro.launch import serve
from repro.models import transformer as T


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host. The persistent compile cache
    is off meanwhile: an entry compiled for a described chip cannot be read
    back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            assert topo.devices[0].device_kind == V5E
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


def _kernel_case(name):
    """(function, argument shapes) of one kernel at the widths it runs."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    if name == "coschedule":      # the fused-interleave bench shapes
        return (lambda a, b, x: cs.coschedule(a, b, x),
                [((8192, 8192), bf16), ((8192, 8192), bf16),
                 ((65536, 8192), bf16)])
    if name == "sliced_matmul":
        return (lambda a, b: sm.sliced_matmul(a, b, slice_size=64),
                [((4096, 4096), bf16)] * 2)
    if name == "flash_attention":  # phi3-mini head_dim
        return (lambda q, k, v: fa.flash_attention(q, k, v),
                [((1, 32, 4096, 96), bf16)] * 3)
    if name == "rwkv6_scan":
        return (lambda r, k, v, w, u: wkv.rwkv6_scan(r, k, v, w, u),
                [((1, 2048, 32, 64), bf16)] * 3
                + [((1, 2048, 32, 64), f32), ((32, 64), f32)])
    if name == "rg_lru":
        return (lambda x, a: lru.rg_lru(x, a),
                [((1, 2048, 4096), f32)] * 2)
    if name.startswith("decode_attention"):
        # phi3's cache (head 96: stored positions minor), and a GQA one
        # (starcoder2-15b: 48 heads on 4 kv heads of 128, row-major)
        layers, h, kvh, hd, minor = ((32, 32, 32, 96, True)
                                     if name == "decode_attention"
                                     else (40, 48, 4, 128, False))
        cache = ((layers, 16, 512, kvh, hd), bf16)
        return (lambda q, kc, vc, layer, t, kn, vn: da.decode_attention(
                    q, kc, vc, layer, t, kn, vn, positions_minor=minor),
                [((16, h, hd), bf16), cache, cache, ((), jnp.int32),
                 ((), jnp.int32), ((16, kvh, hd), bf16),
                 ((16, kvh, hd), bf16)])
    if name == "expert_ffn":      # deepseek-v2-236b-ep8's decode step
        rows, tm = 2176, 32            # 68 tiles of 32: 256 tokens x 6
        return (lambda x, wi, wg, wo, te, n, layer: ef.expert_ffn(
                    x, wi, wg, wo, te, n, layer, tm=tm),
                [((rows, 5120), bf16), ((4, 20, 5120, 1536), bf16),
                 ((4, 20, 5120, 1536), bf16), ((4, 20, 1536, 5120), bf16),
                 ((rows // tm,), jnp.int32), ((), jnp.int32),
                 ((), jnp.int32)])
    raise KeyError(name)


@pytest.mark.parametrize("name", ["coschedule", "sliced_matmul",
                                  "flash_attention", "rwkv6_scan", "rg_lru",
                                  "decode_attention", "decode_attention_gqa",
                                  "expert_ffn"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_phi3_decode_step_compiles_for_v5e(one_chip):
    cfg = get_config("phi3-mini-3.8b")
    batch, max_len = 8, 512

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(functools.partial(T.init_params, cfg),
                                    jax.random.PRNGKey(0)))
    caches = on_chip(jax.eval_shape(functools.partial(
        T.init_decode_caches, cfg, batch, max_len)))
    tok = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    step = functools.partial(serve.decode_logits, cfg=cfg)
    compiled = jax.jit(step).lower(params, caches, tok, t).compile()
    mem = compiled.memory_analysis()
    weights = 2 * cfg.param_count()                   # bf16
    assert mem.argument_size_in_bytes >= weights      # weights are arguments
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < device_peaks(V5E).hbm_bytes


def test_phi3_decode_reads_cache_in_place_on_v5e(one_chip, monkeypatch):
    """The served decode step at the cell's shapes (batch 16, cache 512):
    the ``decode_attention`` kernel reads each layer's k and v from the
    stacked cache where it lies, so no slice, copy, relayout or write of a
    layer's cache or of the stacked cache is left, and the temp stays in
    the few-MB range."""
    from repro.kernels import ops

    # the model path asks the default device (here the CPU) whether to
    # interpret and how the cache is laid out: answer for the chip
    chip = next(iter(one_chip.device_set))
    positions_minor = ops.cache_positions_minor
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    monkeypatch.setattr(ops, "cache_positions_minor",
                        lambda shape, dtype: positions_minor(shape, dtype,
                                                             chip))
    cfg = get_config("phi3-mini-3.8b")
    batch, max_len = 16, 512

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(functools.partial(T.init_params, cfg),
                                    jax.random.PRNGKey(0)))
    caches = on_chip(jax.eval_shape(functools.partial(
        T.init_decode_caches, cfg, batch, max_len)))
    tok = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    step = functools.partial(serve.decode_logits, cfg=cfg)
    compiled = jax.jit(step).lower(params, caches, tok, t).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the chip stores the cache with positions minor, as the kernel reads it
    for fmt in compiled.input_formats[0][1]["stage0"]["sub0"].values():
        assert fmt.layout.major_to_minor[-1] == 2, fmt
    n_l, kvh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    cache_shapes = {f"bf16[{','.join(map(str, s))}]" for s in (
        (1, batch, max_len, kvh, hd), (batch, max_len, kvh, hd),
        (n_l, batch, max_len, kvh, hd))}
    made = re.findall(r"^\s*(?:ROOT )?%\S+ = (bf16\[[\d,]*\])\S* ([\w-]+)\(",
                      text, re.MULTILINE)
    # the two cache parameters, and the layer loop's handles on them
    ops_on_cache = [op for shape, op in made if shape in cache_shapes]
    assert ops_on_cache.count("parameter") == 2
    assert set(ops_on_cache) == {"parameter", "get-tuple-element"}
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_dsv2_ep8_step_reads_experts_in_place_on_v5e(one_chip, monkeypatch,
                                                     phase):
    """deepseek-v2-236b-ep8's served steps at the cell's shapes (prefill 2
    x 512; decode 256 sequences at a 1024 cache) compile for the chip, fit
    its memory beside their weights, and run the ``expert_ffn`` kernel on
    each expert layer's held experts where they lie in the stage's
    stacked weights: nothing slices, copies or relayouts an expert
    matrix."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = get_config("deepseek-v2-236b-ep8")

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(functools.partial(T.init_params, cfg),
                                    jax.random.PRNGKey(0)))
    if phase == "prefill":
        args = (params, {"tokens": jax.ShapeDtypeStruct(
            (2, 512), jnp.int32, sharding=one_chip)})
        step = functools.partial(serve.prefill_logits, cfg=cfg)
    else:
        caches = on_chip(jax.eval_shape(functools.partial(
            T.init_decode_caches, cfg, 256, 1024)))
        args = (params, caches,
                jax.ShapeDtypeStruct((256,), jnp.int32, sharding=one_chip),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
        step = functools.partial(serve.decode_logits, cfg=cfg)
    compiled = jax.jit(step).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%expert_ffn\.\d+ = \S+ custom-call", text)) == 1
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 2 * cfg.param_count()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < device_peaks(V5E).hbm_bytes
    m = cfg.moe
    shapes = {f"bf16[{','.join(map(str, s))}]" for s in (
        (m.num_held, cfg.d_model, m.d_ff_expert),
        (m.num_held, m.d_ff_expert, cfg.d_model),
        (cfg.num_layers - 1, m.num_held, cfg.d_model, m.d_ff_expert),
        (cfg.num_layers - 1, m.num_held, m.d_ff_expert, cfg.d_model))}
    made = re.findall(r"^\s*(?:ROOT )?%\S+ = (bf16\[[\d,]*\])\S* ([\w-]+)\(",
                      text, re.MULTILINE)
    ops_on_experts = [op for shape, op in made if shape in shapes]
    assert ops_on_experts.count("parameter") == 3
    assert set(ops_on_experts) == {"parameter", "get-tuple-element"}
