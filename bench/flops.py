"""Model FLOPs of the benchmark's work, from the configuration's shapes.

Counted as the algorithm needs them: rematerialisation and padding are
not counted, and each adapter's LoRA work is counted at its true rank.
"""
from __future__ import annotations

import json
import os
from typing import Dict

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def _dims(spec: Dict):
    d = spec["hidden_size"]
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = spec.get("head_dim") or d // H
    return d, H, KV, hd, spec["intermediate_size"], spec["vocab_size"]


def matmul_params(spec: Dict) -> int:
    """Weights of the frozen matrix products one token passes through:
    every layer's q, k, v, o, gate, up and down projections, and the
    output head."""
    d, H, KV, hd, ff, V = _dims(spec)
    per_layer = d * (H * hd + 2 * KV * hd) + H * hd * d + 3 * d * ff
    return spec["num_hidden_layers"] * per_layer + d * V


def lora_widths(spec: Dict) -> int:
    """Sum over one layer's adapted projections of (d_in + d_out)."""
    d, H, KV, hd, ff, _ = _dims(spec)
    widths = {"q_proj": d + H * hd, "k_proj": d + KV * hd,
              "v_proj": d + KV * hd, "o_proj": H * hd + d,
              "gate_proj": d + ff, "up_proj": d + ff, "down_proj": ff + d}
    return sum(widths[t] for t in spec["lora"]["targets"])


def backbone_train_flops_per_token(spec: Dict, seq: int) -> float:
    """Frozen backbone, per trained token at sequence length ``seq``: the
    forward pass (2 per weight) and the activation gradients (2 per
    weight; a frozen weight gets no gradient of its own), plus causal
    attention's two products forward (4 * H * hd per visible key, on
    average (seq + 1) / 2 keys) and their four backward products."""
    d, H, KV, hd, _, _ = _dims(spec)
    attn_fwd = 4 * H * hd * (seq + 1) / 2
    return 4.0 * matmul_params(spec) + \
        3.0 * attn_fwd * spec["num_hidden_layers"]


def lora_train_flops_per_token(spec: Dict, rank: int) -> float:
    """One adapter at its true ``rank``, per trained token: x A and (x A) B
    forward (2 r (d_in + d_out)), their weight gradients and their input
    gradients (2 r (d_in + d_out) each)."""
    return 6.0 * rank * lora_widths(spec) * spec["num_hidden_layers"]


def train_flops_per_token(spec: Dict, seq: int, rank: int) -> float:
    return backbone_train_flops_per_token(spec, seq) + \
        lora_train_flops_per_token(spec, rank)


def peak(device_kind: str, key: str = "bf16_flops_per_s") -> float:
    """A published peak of the device; an unknown device is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"have {sorted(table)}")
    return float(table[device_kind][key])
