"""The yardstick's arithmetic: chip peaks, the work of one fused-kernel call,
and the model FLOPs of the served tokens.

Copied in spirit from ``repro.launch.roofline`` (``active_params`` /
``model_flops``), but read from the configuration file and the chip's own
``device_kind``, never from fixed constants.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))

# the fused CORDIC kernel's least storage: FxP8 weights at 1 byte, bfloat16
# activations in and out, whatever the program stores today
WEIGHT_BYTES = 1
ACT_BYTES = 2


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``; unknown kinds raise."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def fused_call_work(m: int, k: int, n: int) -> Dict[str, int]:
    """Operations and bytes of one fused dot ``(m, k) x (k, n)``: the integer
    multiply-adds, the weight read once at its FxP8 size, the activations
    read and the output written in bfloat16."""
    return {"ops": 2 * m * k * n,
            "bytes": k * n * WEIGHT_BYTES + (m * k + m * n) * ACT_BYTES}


def least_seconds(work: Dict[str, int], peak: Dict[str, float]) -> float:
    """The roofline's time for ``work``: the larger of compute and memory."""
    return max(work["ops"] / peak["int8_ops"],
               work["bytes"] / peak["hbm_bytes_per_s"])


def matmul_params(cfg: Dict) -> int:
    """Parameters a token multiplies: every layer's projections plus the
    output head (the embedding lookup is no matmul)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg.get("num_key_value_heads", cfg["num_attention_heads"])
    attn = d * d * 2 + 2 * d * kv * hd
    mlp = 3 * d * f
    return cfg["num_hidden_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def token_flops(cfg: Dict, context: int) -> float:
    """Model FLOPs of one token that attends ``context`` positions."""
    attn = 4.0 * context * cfg["hidden_size"] * cfg["num_hidden_layers"]
    return 2.0 * matmul_params(cfg) + attn


def prompt_flops(cfg: Dict, prompt_lens: Sequence[int]) -> float:
    """Model FLOPs of prefilling whole prompts (causal: row p sees p + 1)."""
    per = 2.0 * matmul_params(cfg)
    attn = 4.0 * cfg["hidden_size"] * cfg["num_hidden_layers"]
    return sum(p * per + attn * p * (p + 1) / 2 for p in prompt_lens)
