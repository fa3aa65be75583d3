"""Work a step of a dense decoder requires, counted from the configuration
file and the step's shape, whatever program implements it.

Required means: causal attention counts each (query, key) pair with key
<= query once; every weight is read once per step; a decode step reads the
cache only up to its position; activations between layers are not counted
(a fused program need not spill them). Matmul FLOPs are 2 per multiply-add.
Everything is computed from the sizes in the file, so a kernel's roofline
reads the same work whichever code runs it.
"""
from __future__ import annotations

import dataclasses

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool
    norm: str              # "rmsnorm" | "layernorm"
    tied: bool
    wbytes: int            # bytes per weight and cache element


def dims(cfg: dict) -> Dims:
    """Sizes of a dense decoder from its configuration file (Hugging Face
    ``config.json`` keys)."""
    heads = cfg["num_attention_heads"]
    d = cfg["hidden_size"]
    return Dims(
        layers=cfg["num_hidden_layers"], d=d, heads=heads,
        kv_heads=cfg.get("num_key_value_heads", heads),
        head_dim=cfg.get("head_dim") or d // heads,
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        gated=cfg.get("hidden_act", "silu") in ("silu", "swiglu"),
        norm="layernorm" if "layer_norm_eps" in cfg else "rmsnorm",
        tied=bool(cfg.get("tie_word_embeddings", False)),
        wbytes=DTYPE_BYTES[cfg.get("torch_dtype", "bfloat16")])


def layer_matmul_params(m: Dims) -> int:
    attn = m.d * m.heads * m.head_dim * 2 + 2 * m.d * m.kv_heads * m.head_dim
    mlp = (3 if m.gated else 2) * m.d * m.d_ff
    return attn + mlp


def norm_params(m: Dims) -> int:
    return m.d * (2 if m.norm == "layernorm" else 1)


def param_count(cfg: dict) -> int:
    """Every parameter: embedding, layers (projections and two norms each),
    final norm and head."""
    m = dims(cfg)
    embed = m.vocab * m.d * (1 if m.tied else 2)
    per_layer = layer_matmul_params(m) + 2 * norm_params(m)
    return embed + m.layers * per_layer + norm_params(m)


def streamed_weight_bytes(m: Dims, tokens: int) -> int:
    """Weights a step reads once: every layer, final norm and head, plus
    the embedding rows of its tokens (a gather, not the whole table)."""
    n = (m.layers * (layer_matmul_params(m) + 2 * norm_params(m))
         + norm_params(m) + m.vocab * m.d)
    return (n + tokens * m.d) * m.wbytes


def prefill(cfg: dict, batch: int, seq: int) -> dict:
    """One prefill slice: ``batch`` prompts of ``seq`` tokens, logits at
    every position."""
    m = dims(cfg)
    tokens = batch * seq
    matmul = 2 * tokens * (m.layers * layer_matmul_params(m) + m.d * m.vocab)
    pairs = seq * (seq + 1) // 2                 # causal, diagonal included
    attn = m.layers * batch * 4 * m.heads * m.head_dim * pairs
    nbytes = (streamed_weight_bytes(m, tokens) + tokens * 4       # ids in
              + tokens * m.vocab * m.wbytes)                      # logits out
    return {"flops": float(matmul + attn), "bytes": float(nbytes),
            "tokens": tokens}


def decode(cfg: dict, batch: int, pos: int) -> dict:
    """One decode step for ``batch`` sequences whose new token sits at
    position ``pos``: attention over the ``pos`` cached positions and the
    new one; the cache is read only up to ``pos``."""
    m = dims(cfg)
    matmul = 2 * batch * (m.layers * layer_matmul_params(m) + m.d * m.vocab)
    attn = m.layers * batch * 4 * m.heads * m.head_dim * (pos + 1)
    cache = m.layers * batch * pos * 2 * m.kv_heads * m.head_dim * m.wbytes
    nbytes = (streamed_weight_bytes(m, batch) + cache + batch * 4
              + batch * m.vocab * m.wbytes)
    return {"flops": float(matmul + attn), "bytes": float(nbytes),
            "tokens": batch}


def step(cfg: dict, phase: str, batch: int, seq: int) -> dict:
    """Required work of one slice of a tenant, as the traffic file states
    it: a prefill over ``seq`` tokens, or a decode step at ``seq // 2`` of a
    ``seq``-long cache."""
    if phase == "prefill":
        return prefill(cfg, batch, seq)
    if phase == "decode":
        return decode(cfg, batch, seq // 2)
    raise ValueError(f"unknown phase {phase!r}")


def least_time(work: dict, peaks) -> float:
    """The least time the chip could take: the larger of FLOPs over peak
    FLOP/s and bytes over peak bandwidth."""
    return max(work["flops"] / peaks.bf16_flops, work["bytes"] / peaks.hbm_bw)


def bound_by(work: dict, peaks) -> str:
    return ("compute" if work["flops"] / peaks.bf16_flops
            >= work["bytes"] / peaks.hbm_bw else "memory")
