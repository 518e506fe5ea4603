"""The yardstick's arithmetic for a Zamba2 hybrid: parameters, the model
FLOPs of the served tokens, and the least bytes of a decode step.

Read from the configuration file's own keys (``layers_block_type``,
``n_mamba_heads``, ...), as ``work`` reads a dense decoder's; ``work`` stays
dense. A ``mamba`` layer is one Mamba2 mixer; a ``hybrid`` layer is a mixer
plus one call of a shared block (attention on the ``attention_hidden_size``
wide ``[h ; e]``, a gated MLP), the call's adapter on the MLP's gate/up and
the layer's ``linear``. The output head is tied to the embedding.
"""
from __future__ import annotations

from typing import Dict, Sequence

STATE_BYTES = 4  # the slot cache stores mixer state and K/V in float32
WEIGHT_BYTES = 1  # int8 served weights


def _layers(cfg: Dict):
    kinds = cfg["layers_block_type"][:cfg["num_hidden_layers"]]
    return kinds.count("mamba") + kinds.count("hybrid"), kinds.count("hybrid")


def mixer_dims(cfg: Dict) -> Dict[str, int]:
    """One mixer's widths: ``inner`` (heads x head dim), the conv's
    channels, the in-projection's outputs (z, x, B, C, dt) and the state."""
    heads, p = cfg["n_mamba_heads"], cfg["mamba_headdim"]
    gn = cfg["mamba_ngroups"] * cfg["mamba_d_state"]
    inner = heads * p
    return {"inner": inner, "conv": inner + 2 * gn,
            "proj": 2 * inner + 2 * gn + heads,
            "state": heads * cfg["mamba_d_state"] * p}


def mixer_matmul_params(cfg: Dict) -> int:
    d, m = cfg["hidden_size"], mixer_dims(cfg)
    return d * m["proj"] + m["inner"] * d


def shared_matmul_params(cfg: Dict) -> int:
    """One call of a shared block: q, k, v on the concatenated input, o,
    and the gated MLP (gate and up, down)."""
    d, a = cfg["hidden_size"], cfg["attention_hidden_size"]
    hd = cfg["attention_head_dim"]
    qkv = a * (cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]) * hd
    o = cfg["num_attention_heads"] * hd * d
    return qkv + o + 3 * d * cfg["ffn_hidden_size"]


def call_matmul_params(cfg: Dict) -> int:
    """A hybrid layer's own projections: the adapter (to its rank, then to
    gate and up) and ``linear``."""
    d, r = cfg["hidden_size"], cfg["adapter_rank"]
    return d * r + r * 2 * cfg["ffn_hidden_size"] + d * d


def matmul_params(cfg: Dict) -> int:
    """Parameters a token multiplies: every mixer's in and out projections,
    each hybrid layer's shared-block call, adapter and linear, and the
    output head."""
    n, hybrid = _layers(cfg)
    return (n * mixer_matmul_params(cfg)
            + hybrid * (shared_matmul_params(cfg) + call_matmul_params(cfg))
            + cfg["hidden_size"] * cfg["vocab_size"])


def param_count(cfg: Dict) -> int:
    """Every parameter of the model (the tied head counted once)."""
    n, hybrid = _layers(cfg)
    d, m = cfg["hidden_size"], mixer_dims(cfg)
    heads, w = cfg["n_mamba_heads"], cfg["mamba_d_conv"]
    mixer = (mixer_matmul_params(cfg) + (w + 1) * m["conv"] + 3 * heads
             + m["inner"] + d)  # conv and its bias, A_log/D/dt_bias, norms
    shared = shared_matmul_params(cfg) + cfg["attention_hidden_size"] + d
    return (n * mixer + cfg["num_mem_blocks"] * shared
            + hybrid * call_matmul_params(cfg)
            + cfg["vocab_size"] * d + d)


def state_bytes(cfg: Dict) -> int:
    """One slot's mixer state (SSM and conv window) over every layer."""
    n, _ = _layers(cfg)
    m = mixer_dims(cfg)
    return n * (m["state"] + (cfg["mamba_d_conv"] - 1) * m["conv"]) * STATE_BYTES


def kv_row_bytes(cfg: Dict) -> int:
    """One position's K and V over every hybrid layer."""
    _, hybrid = _layers(cfg)
    return (hybrid * 2 * cfg["num_key_value_heads"] * cfg["attention_head_dim"]
            * STATE_BYTES)


def token_flops(cfg: Dict, context: int) -> float:
    """Model FLOPs of one token that attends ``context`` positions: its
    projections, each mixer's conv and state update and read-out, and the
    attention of each hybrid layer."""
    n, hybrid = _layers(cfg)
    m = mixer_dims(cfg)
    mixer = 2.0 * cfg["mamba_d_conv"] * m["conv"] + 4.0 * m["state"]
    attn = 4.0 * context * cfg["num_attention_heads"] * cfg["attention_head_dim"]
    return 2.0 * matmul_params(cfg) + n * mixer + hybrid * attn


def prompt_flops(cfg: Dict, prompt_lens: Sequence[int]) -> float:
    """Model FLOPs of prefilling whole prompts (causal: row p sees p + 1)."""
    _, hybrid = _layers(cfg)
    per = token_flops(cfg, 0)
    attn = 4.0 * cfg["num_attention_heads"] * cfg["attention_head_dim"] * hybrid
    return sum(p * per + attn * p * (p + 1) / 2 for p in prompt_lens)


def decode_bytes(cfg: Dict, steps: int, contexts: Sequence[int]) -> float:
    """Least bytes of ``steps`` decode steps that decoded tokens at the
    given ``contexts``: the int8 weights once per step, and per token its
    slot's mixer state read and written once and its K/V rows up to its
    context read once."""
    weights = matmul_params(cfg) * WEIGHT_BYTES
    return (steps * weights + 2.0 * state_bytes(cfg) * len(contexts)
            + kv_row_bytes(cfg) * float(sum(contexts)))
