"""Operations a model requires, counted from a configuration's shapes,
and the chip peaks they are held against."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str, key: str = "bf16_flops") -> float:
    """One chip's peak from the benchmark's own table; a device kind that
    is not in the table is an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS.name}; "
                       f"known: {sorted(k for k in table if k[0] != '_')}")
    return float(table[device_kind][key])


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matmul per token: the blocks'
    projections and the output head over the real vocabulary.  The
    embedding gather, norms and biases are not matmuls."""
    d, f = cfg["d_model"], cfg["d_ff"]
    qd = cfg["n_heads"] * cfg["d_head"]
    kvd = cfg["n_kv"] * cfg["d_head"]
    attn = d * qd + 2 * d * kvd + qd * d
    mlp = (3 if cfg["act"] in ("swiglu", "geglu") else 2) * d * f
    return cfg["n_layers"] * (attn + mlp) + cfg["vocab"] * d


def param_count(cfg: dict) -> int:
    """Every stored weight, the padded embedding rows included."""
    d, f, L = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    qd = cfg["n_heads"] * cfg["d_head"]
    kvd = cfg["n_kv"] * cfg["d_head"]
    vp = -(-cfg["vocab"] // cfg["vocab_pad_to"]) * cfg["vocab_pad_to"]
    gated = cfg["act"] in ("swiglu", "geglu")
    norm = (2 if cfg["norm"] == "layernorm" else 1) * d
    attn = d * qd + 2 * d * kvd + qd * d
    attn += (qd + 2 * kvd) if cfg["qkv_bias"] else 0
    mlp = (3 if gated else 2) * d * f + (0 if gated else f + d)
    head = 0 if cfg["tie_embeddings"] else d * vp
    return vp * d + head + L * (attn + mlp + 2 * norm) + norm


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward operations per trained token: 6 per matmul
    weight, plus causal attention, whose query at position t scores and
    mixes t + 1 keys (2 q_dim (t + 1) operations each for q k^T and for
    p v forward, three times that with the backward), averaged over the
    sequence.  Recomputation is not counted."""
    qd = cfg["n_heads"] * cfg["d_head"]
    attn = 6 * cfg["n_layers"] * qd * (seq_len + 1)
    return 6.0 * matmul_params(cfg) + attn
