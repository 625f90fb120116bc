"""Work counts from shapes, and the chips' published peaks.

These are the yardstick's own arithmetic: a later change to the program
cannot move them. Each count says what the algorithm needs, not what the
program happens to compute (recomputation and padding do not count).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def lm_param_count(m: Dict) -> int:
    """Parameters of a dense Llama-style decoder (``m`` in Hugging Face
    key names): embedding, per layer two norms, q/k/v/o and a gated MLP,
    the final norm, and an output head unless it is tied."""
    d, f = m["hidden_size"], m["intermediate_size"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    dh = m.get("head_dim") or d // h
    per_layer = 2 * d + d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f
    head = 0 if m["tie_word_embeddings"] else d * m["vocab_size"]
    return (m["vocab_size"] * d + d + head
            + m["num_hidden_layers"] * per_layer)


def lm_train_flops_per_token(m: Dict, seq: int) -> float:
    """Forward and backward model FLOPs per token: 6 per parameter, plus
    causal attention (QK^T and AV over on average half the context:
    12 * layers * heads * head_dim * seq / 2)."""
    h = m["num_attention_heads"]
    dh = m.get("head_dim") or m["hidden_size"] // h
    attn = 6 * m["num_hidden_layers"] * h * dh * seq
    return 6.0 * lm_param_count(m) + attn


def ps_step_bytes(dim: int, queue_slots: int, burst: int, drain_k: int,
                  param_bytes: int) -> int:
    """Least HBM traffic of one parameter-server step over a flattened
    update of ``dim`` f32: the (Q, D) queue read and written once, the
    (U, D) burst read once, the (k, D) drained rows written once, the
    parameters (``param_bytes`` each) and both f32 Adam moments read and
    written once, and the gradient read once."""
    f32 = 4
    queue = 2 * queue_slots * dim * f32
    rows = (burst + drain_k) * dim * f32
    opt = 2 * dim * param_bytes + 4 * dim * f32
    grad = dim * f32
    return queue + rows + opt + grad
